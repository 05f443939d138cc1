"""Seeded cluster generator: bare Pods and Deployments whose template is such
a Pod, with a realistic violation mix.

Copied from ``bench.py`` (``make_pod``, ``make_config4_pod``) and
``chip_smoke.py`` (``make_cluster``), where they passed on
the chip in PR 22; the benchmark keeps its own copy so that a later PR cannot
change the inputs it is measured on.  ``generate`` is the entry the harness
calls: ``generate(seed, **params)`` with the ``params`` of the configuration
file.
"""

import random

_IMAGES = ['nginx:1.25.3', 'nginx:latest', 'ghcr.io/org/app:v2.1',
           'redis:7', 'docker.io/library/busybox', 'gcr.io/proj/svc:prod',
           'app', 'registry.internal:5000/team/api:canary']
_CAPS = ['NET_ADMIN', 'SYS_TIME', 'CHOWN', 'KILL', 'AUDIT_WRITE', 'ALL']


def make_pod(rng, i: int) -> dict:
    """Synthetic Pod with a realistic violation mix."""
    n_containers = 1 + (i % 3)
    containers = []
    for c in range(n_containers):
        cont = {'name': f'c{c}', 'image': _IMAGES[(i + c) % len(_IMAGES)]}
        if rng.random() < 0.8:
            cont['resources'] = {
                'requests': {'memory': '64Mi', 'cpu': '100m'},
                'limits': {'memory': rng.choice(['128Mi', '2Gi', '8Gi'])},
            }
        if rng.random() < 0.5:
            sc = {}
            if rng.random() < 0.5:
                sc['allowPrivilegeEscalation'] = rng.random() < 0.3
            if rng.random() < 0.3:
                sc['privileged'] = rng.random() < 0.3
            if rng.random() < 0.4:
                sc['capabilities'] = {
                    'add': rng.sample(_CAPS, rng.randint(1, 2)),
                    'drop': rng.choice([['ALL'], [], ['KILL']]),
                }
            if rng.random() < 0.4:
                sc['runAsNonRoot'] = rng.random() < 0.7
            cont['securityContext'] = sc
        if rng.random() < 0.3:
            cont['ports'] = [{'containerPort': rng.choice([80, 8080, 443]),
                              'hostPort': rng.choice([0, 80, 9000])}]
        containers.append(cont)
    spec = {'containers': containers}
    if rng.random() < 0.1:
        spec['hostNetwork'] = True
    if rng.random() < 0.08:
        spec['hostPID'] = True
    if rng.random() < 0.15:
        spec['volumes'] = [{'name': 'v0', 'hostPath': {'path': '/var/run'}}
                           if rng.random() < 0.5 else
                           {'name': 'v0', 'emptyDir': {}}]
    if rng.random() < 0.2:
        spec['securityContext'] = {'sysctls': [
            {'name': rng.choice(['kernel.shm_rmid_forced',
                                 'net.core.rmem_max']),
             'value': '1'}]}
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': f'pod-{i}', 'namespace': f'ns-{i % 7}',
                         'labels': {'app': f'app-{i % 11}'}},
            'spec': spec}


def make_config4_pod(rng, i: int) -> dict:
    pod = make_pod(rng, i)
    labels = pod['metadata'].setdefault('labels', {})
    if rng.random() < 0.6:
        labels['tier'] = rng.choice(['web', 'api', 'batch', 'cache'])
    if rng.random() < 0.3:
        labels['env'] = rng.choice(['prod', 'staging'])
    if rng.random() < 0.25:
        pod['metadata']['annotations'] = {
            'budget.io/max-cpu': str(rng.choice([2, 8, 24]))}
    if rng.random() < 0.4:
        for cont in pod['spec']['containers']:
            if rng.random() < 0.7:
                cont['livenessProbe'] = {
                    'httpGet': {'path': '/healthz', 'port': 8080}}
    if rng.random() < 0.1:
        pod['spec']['containers'][0]['image'] = \
            'gcr.io/proj/svc@sha256:' + '0' * 64
    return pod


def generate(seed: int, n: int, deployment_share: float = 0.3) -> list:
    """``n`` mixed resources: bare Pods and Deployments whose template is
    such a Pod (the PSS policies reach those through their autogen rules;
    the Pod-only policies do not match them at all)."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        pod = make_config4_pod(rng, i)
        if rng.random() < deployment_share:
            meta = pod['metadata']
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


def pod_spec(resource: dict) -> dict:
    """The Pod spec a resource of this cluster carries."""
    spec = resource['spec']
    return spec['template']['spec'] if resource['kind'] == 'Deployment' \
        else spec
