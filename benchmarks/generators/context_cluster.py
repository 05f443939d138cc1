"""Seeded cluster of a platform team that keeps its allowlists in ConfigMaps:
the Pods and Deployments of ``mixed_cluster`` (its ``make_config4_pod``,
imported, not copied) spread over tenant namespaces by Zipf, with the fields
the four policies of ``packs/context.yaml`` read, and the ConfigMaps they
load.

``generate(seed, **params)`` gives the resources, ``context_objects(seed,
**params)`` the ConfigMaps, both from the same ``params`` of the
configuration file.  The names of namespaces, ConfigMaps and keys do not
depend on the seed (the policies name them, and policies are compiled in);
the seed chooses which namespace is busy, what each allows and who breaks it.

Which namespaces lack their ``tenant-policy`` ConfigMap is a matter of rank,
not of a draw: the ranks of ``ABSENT_RANKS`` hold 5% of the Zipf(1.1) mass
over 500 namespaces whatever the seed, and the seed only decides which names
stand at those ranks.
"""

import json
import random

import benchlib

_mixed = benchlib.load_module('generators', 'mixed_cluster')
pod_spec = _mixed.pod_spec

PRIORITY_CLASSES = ['system-critical', 'platform-high', 'tenant-high',
                    'tenant-default', 'batch-low', 'best-effort']
ROLES = ['frontend', 'backend', 'worker', 'admin', 'debug']
TIERS = ['web', 'api', 'batch', 'cache']
N_EXCLUDED = 12
ZIPF_S = 1.1
#: ranks (0 the busiest) whose namespaces never created ``tenant-policy``:
#: 4.99% of the Zipf(1.1) mass at 500 namespaces
ABSENT_RANKS = (4, 23, 41, 60, 67, 101, 115, 150, 211, 290, 377, 460)
#: ranks whose namespaces have no key of their own in
#: ``allowed-pod-priorities``: the policy's ``|| ""`` default decides there
KEYLESS_RANKS = tuple(range(470, 500)) + (13,)


def namespace_name(i: int) -> str:
    return f'tenant-{i:03d}'


def _layout(seed: int, namespaces: int):
    """``(by_rank, weights)``: the namespace index at each popularity rank,
    in an order the seed chooses, and the ranks' Zipf weights."""
    rng = random.Random(seed ^ 0x5EED)
    by_rank = list(range(namespaces))
    rng.shuffle(by_rank)
    weights = [1.0 / (r + 1) ** ZIPF_S for r in range(namespaces)]
    return by_rank, weights


def absent_ranks(namespaces: int) -> tuple:
    """The ranks without a ``tenant-policy``, at this many namespaces."""
    if namespaces >= 500:
        return ABSENT_RANKS
    # the rehearsal's and the tests' small clusters: one rank, 5.3% of
    # the mass at 20 namespaces
    return (min(4, namespaces - 1),)


def generate(seed: int, n: int, deployment_share: float = 0.3,
             namespaces: int = 500, cronjob_share: float = 0.0) -> list:
    """``n`` resources: bare Pods, Deployments whose template is such a Pod
    and, where ``cronjob_share`` asks, CronJobs whose job template is."""
    rng = random.Random(seed)
    by_rank, weights = _layout(seed, namespaces)
    ranks = rng.choices(range(namespaces), weights=weights, k=n)
    out = []
    for i in range(n):
        pod = _mixed.make_config4_pod(rng, i)
        ns = namespace_name(by_rank[ranks[i]])
        meta = pod['metadata']
        meta['namespace'] = ns
        if rng.random() < 0.6:
            pod['spec']['priorityClassName'] = rng.choice(PRIORITY_CLASSES)
        if rng.random() < 0.5:
            meta['labels']['foo'] = rng.choice(['bar', 'baz'])
        shape = rng.random()
        if shape < deployment_share:
            dmeta = {'name': f'deploy-{i}', 'namespace': ns,
                     'labels': dict(meta['labels'])}
            if rng.random() < 0.8:
                dmeta['annotations'] = {'role': rng.choice(ROLES)}
            out.append({
                'apiVersion': 'apps/v1', 'kind': 'Deployment',
                'metadata': dmeta,
                'spec': {'replicas': 1 + i % 3,
                         'selector': {'matchLabels':
                                      {'app': meta['labels']['app']}},
                         'template': {
                             'metadata': {'labels': dict(meta['labels'])},
                             'spec': pod['spec']}}})
        elif shape < deployment_share + cronjob_share:
            out.append({
                'apiVersion': 'batch/v1', 'kind': 'CronJob',
                'metadata': {'name': f'cron-{i}', 'namespace': ns,
                             'labels': dict(meta['labels'])},
                'spec': {'schedule': '*/5 * * * *',
                         'jobTemplate': {'spec': {'template': {
                             'metadata': {'labels': dict(meta['labels'])},
                             'spec': pod['spec']}}}}})
        else:
            out.append(pod)
    return out


def _config_map(namespace: str, name: str, data: dict) -> dict:
    return {'apiVersion': 'v1', 'kind': 'ConfigMap',
            'metadata': {'name': name, 'namespace': namespace},
            'data': data}


def context_objects(seed: int, n: int = 0, deployment_share: float = 0.3,
                    namespaces: int = 500, cronjob_share: float = 0.0
                    ) -> list:
    """The ConfigMaps of the cluster ``generate`` gives for the same seed
    and parameters; every value a string, lists as JSON arrays."""
    del n, deployment_share, cronjob_share
    rng = random.Random(seed ^ 0xC0F16)
    by_rank, _weights = _layout(seed, namespaces)
    keyless = {by_rank[r] for r in KEYLESS_RANKS if r < namespaces}
    absent = {by_rank[r] for r in absent_ranks(namespaces)}
    priorities = {}
    tenants = []
    for i in range(namespaces):
        allowed = rng.sample(PRIORITY_CLASSES, rng.randint(1, 4))
        tiers = rng.sample(TIERS, rng.randint(1, 3))
        if i not in keyless:
            priorities[namespace_name(i)] = json.dumps(allowed)
        if i not in absent:
            tenants.append(_config_map(namespace_name(i), 'tenant-policy',
                                       {'tiers': json.dumps(tiers)}))
    excluded = sorted(namespace_name(i) for i in
                      rng.sample(range(namespaces),
                                 min(N_EXCLUDED, namespaces)))
    return [
        _config_map('default', 'allowed-pod-priorities', priorities),
        _config_map('default', 'roles-dictionary',
                    {'allowed-roles': json.dumps(rng.sample(ROLES, 3))}),
        _config_map('default', 'namespace-filters',
                    {'exclude': json.dumps(excluded)}),
    ] + tenants
