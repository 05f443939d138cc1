"""Seeded cluster that a platform team retrofits with mutate-existing
policies (``packs/mutate-existing.yaml``): bare Pods and Deployments whose
template is such a Pod, from ``mixed_cluster``'s ``make_pod`` (imported, not
copied), spread over tenant namespaces by Zipf(1.1) in a rank order the seed
chooses, and the Namespace objects themselves.

One Deployment in 25 (by count, not by a draw) has five containers, more
than the device's four element slots, so its ``retrofit-pull-policy`` row
falls back to the host engine; on about 3 workloads in 10 every container
already sets ``imagePullPolicy``, as ``config5_dump.py``'s Pods do.

``generate(seed, **params)`` gives the Namespaces first, then the workloads.
``prefix`` names the namespaces (``<prefix>-0000``, ...): the warm cluster
of set-up takes another one, so that it shares no namespace with the
measured cluster.
"""

import random

import benchlib

_mixed = benchlib.load_module('generators', 'mixed_cluster')

ZIPF_S = 1.1
#: every this many Deployments, one with five containers
FIVE_EVERY = 25
#: share of workloads whose every container sets imagePullPolicy
PULL_SHARE = 0.3


def namespace_name(prefix: str, i: int) -> str:
    return f'{prefix}-{i:04d}'


def generate(seed: int, n: int, namespaces: int = 2000,
             deployment_share: float = 0.3, prefix: str = 'team') -> list:
    rng = random.Random(seed)
    by_rank = list(range(namespaces))
    random.Random(seed ^ 0x5EED).shuffle(by_rank)
    weights = [1.0 / (r + 1) ** ZIPF_S for r in range(namespaces)]
    ranks = rng.choices(range(namespaces), weights=weights, k=n)
    out = [{'apiVersion': 'v1', 'kind': 'Namespace',
            'metadata': {'name': namespace_name(prefix, i)}}
           for i in range(namespaces)]
    deployments = 0
    for i in range(n):
        pod = _mixed.make_pod(rng, i)
        meta = pod['metadata']
        meta['namespace'] = namespace_name(prefix, by_rank[ranks[i]])
        containers = pod['spec']['containers']
        deployment = rng.random() < deployment_share
        if deployment:
            deployments += 1
            if deployments % FIVE_EVERY == 0:
                containers += [{'name': f'c{c}', 'image': 'app'}
                               for c in range(len(containers), 5)]
        if rng.random() < PULL_SHARE:
            for cont in containers:
                cont['imagePullPolicy'] = 'Always'
        if deployment:
            out.append({
                'apiVersion': 'apps/v1', 'kind': 'Deployment',
                'metadata': {'name': f'deploy-{i}',
                             'namespace': meta['namespace'],
                             'labels': dict(meta['labels'])},
                'spec': {'replicas': 1 + i % 3,
                         'selector': {'matchLabels':
                                      {'app': meta['labels']['app']}},
                         'template': {
                             'metadata': {'labels': dict(meta['labels'])},
                             'spec': pod['spec']}}})
        else:
            out.append(pod)
    return out
