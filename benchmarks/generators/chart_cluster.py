"""Seeded cluster of the kinds the Helm chart's policies autogen for: bare
Pods and the seven Pod controllers, each carrying a Pod of
``mixed_cluster``'s ``make_config4_pod`` (imported, not copied), with the
fields the chart's rules read and that generator never writes.

``generate(seed, **params)`` gives the resources; ``pod_template(resource)``
the Pod a resource of any of the kinds is or carries (``metadata`` and
``spec``) and ``pod_spec(resource)`` its spec.  The kind of each resource is
drawn by the seed with the shares of ``KIND_SHARES``; every name is unique
across kinds (``<kind>-<i>``: a report is stored under namespace and name).

What is added to a Pod, each on about a tenth of the Pods and half of those
compliant with the chart's rule that reads it: an init container with a
security context of its own, the pod-level security context
(``runAsNonRoot``, ``runAsUser``, ``runAsGroup``, ``fsGroup``,
``supplementalGroups``, ``seccompProfile``, ``seLinuxOptions``), a
container's ``seccompProfile`` and ``procMount``, ``capabilities.drop`` in
lower case, an AppArmor annotation, volumes of the allowed types and of
others.  One Pod in ``BREAK_EVERY`` breaks nearly every rule at once
(``_break_everything``).
ConfigMaps, Secrets and Services are left out on purpose: the reports
controller watches only kinds a policy matches.
"""

import random

import benchlib

_mixed = benchlib.load_module('generators', 'mixed_cluster')

#: kind, share, apiVersion
KIND_SHARES = (
    ('Pod', 0.50, 'v1'),
    ('ReplicaSet', 0.14, 'apps/v1'),
    ('Deployment', 0.12, 'apps/v1'),
    ('Job', 0.08, 'batch/v1'),
    ('CronJob', 0.06, 'batch/v1'),
    ('StatefulSet', 0.05, 'apps/v1'),
    ('DaemonSet', 0.05, 'apps/v1'),
)
KINDS = tuple(k for k, _s, _v in KIND_SHARES)
#: resource ``i`` breaks everything where ``i % BREAK_EVERY == BREAK_AT``.
#: By index and not by a draw, and first at ``i = 2`` (three containers,
#: each adding two capabilities: the longest gathered list of the
#: cluster), so that any run of rows from the start, the warm rows of a
#: cell among them, has the lane widths of every later chunk and nothing
#: compiles inside a measured window
BREAK_EVERY = 50
BREAK_AT = 2
_APPARMOR = 'container.apparmor.security.beta.kubernetes.io/'
_ALLOWED_VOLUMES = (
    lambda i: {'configMap': {'name': f'cm-{i % 5}'}},
    lambda i: {'secret': {'secretName': f'secret-{i % 5}'}},
    lambda i: {'persistentVolumeClaim': {'claimName': f'data-{i % 9}'}},
    lambda i: {'projected': {'sources': []}},
    lambda i: {'downwardAPI': {'items': []}},
)
_OTHER_VOLUMES = (
    lambda i: {'nfs': {'server': 'nfs.internal', 'path': f'/export/{i % 4}'}},
    lambda i: {'hostPath': {'path': '/var/lib/kubelet'}},
)


def _tenth(rng) -> bool:
    return rng.random() < 0.1


def _half(rng) -> bool:
    return rng.random() < 0.5


def _add_chart_fields(rng, pod: dict, i: int) -> None:
    """The fields the chart's rules read, in place."""
    spec = pod['spec']
    containers = spec['containers']
    psc = {}
    if _tenth(rng):
        psc['runAsNonRoot'] = _half(rng)
    if _tenth(rng):
        psc['runAsUser'] = 1000 + i % 100 if _half(rng) else 0
    if _tenth(rng):
        psc['runAsGroup'] = 3000 if _half(rng) else 0
    if _tenth(rng):
        psc['fsGroup'] = 2000 if _half(rng) else 0
    if _tenth(rng):
        psc['supplementalGroups'] = [4000, 5000] if _half(rng) else [0]
    if _tenth(rng):
        psc['seccompProfile'] = {'type': 'RuntimeDefault' if _half(rng)
                                 else 'Unconfined'}
    if _tenth(rng):
        psc['seLinuxOptions'] = {'type': 'container_t'} if _half(rng) \
            else {'type': 'spc_t', 'user': 'system_u'}
    if psc:
        # make_pod's sysctls, where it set them, stay
        spec.setdefault('securityContext', {}).update(psc)
    if _tenth(rng):
        own = {'allowPrivilegeEscalation': False, 'runAsNonRoot': True,
               'capabilities': {'drop': ['ALL']}} if _half(rng) \
            else {'privileged': True, 'runAsUser': 0}
        spec['initContainers'] = [{'name': 'init', 'image': 'busybox:1.36',
                                   'securityContext': own}]
    if _tenth(rng):
        rng.choice(containers).setdefault('securityContext', {})[
            'seccompProfile'] = {'type': 'Localhost',
                                 'localhostProfile': 'profiles/audit.json'} \
            if _half(rng) else {'type': 'Unconfined'}
    if _tenth(rng):
        rng.choice(containers).setdefault('securityContext', {})[
            'procMount'] = 'Default' if _half(rng) else 'Unmasked'
    if _tenth(rng):
        # the restricted profile upper-cases what it reads (to_upper)
        rng.choice(containers).setdefault('securityContext', {}) \
            .setdefault('capabilities', {})['drop'] = \
            ['all'] if _half(rng) else ['net_raw']
    if _tenth(rng):
        pod['metadata'].setdefault('annotations', {})[
            _APPARMOR + containers[0]['name']] = \
            rng.choice(['runtime/default', 'localhost/k8s-default']) \
            if _half(rng) else 'unconfined'
    if _tenth(rng):
        kinds = _ALLOWED_VOLUMES if _half(rng) else _OTHER_VOLUMES
        volumes = spec.setdefault('volumes', [])
        volumes.append(dict(rng.choice(kinds)(i), name=f'v{len(volumes)}'))


def _break_everything(pod: dict) -> None:
    """A Pod that breaks nearly every rule of the packs at once, in
    place (the chart's, and of the best practices the image tag, the
    resources, the probes, the hostPath and the sysctl): the row whose
    failing cells can outrun the fail-detail budget."""
    spec = pod['spec']
    spec.update(hostNetwork=True, hostPID=True, hostIPC=True)
    spec['securityContext'] = {
        'runAsNonRoot': False, 'runAsUser': 0, 'runAsGroup': 0,
        'fsGroup': 0, 'supplementalGroups': [0],
        'seccompProfile': {'type': 'Unconfined'},
        'seLinuxOptions': {'type': 'spc_t', 'user': 'system_u',
                           'role': 'system_r'},
        'sysctls': [{'name': 'kernel.msgmax', 'value': '65536'}]}
    spec['volumes'] = [{'name': 'v0', 'hostPath': {'path': '/'}}]
    for cont in spec['containers']:
        cont['securityContext'] = {
            'privileged': True, 'allowPrivilegeEscalation': True,
            'runAsNonRoot': False, 'runAsUser': 0, 'runAsGroup': 0,
            'procMount': 'Unmasked',
            'capabilities': {'add': ['SYS_ADMIN', 'NET_ADMIN']},
            'seccompProfile': {'type': 'Unconfined'},
            'seLinuxOptions': {'type': 'spc_t', 'role': 'system_r'},
            'windowsOptions': {'hostProcess': True}}
        cont['ports'] = [{'containerPort': 80, 'hostPort': 80}]
        cont['image'] = cont['image'].split('@')[0].split(':')[0] + ':latest'
        cont.pop('resources', None)
        cont.pop('livenessProbe', None)
    pod['metadata'].setdefault('annotations', {})[
        _APPARMOR + spec['containers'][0]['name']] = 'unconfined'


def _wrap(kind: str, api_version: str, pod: dict, i: int) -> dict:
    """``pod`` as it is, or the controller of ``kind`` whose template it
    is: the template takes the Pod's labels and annotations."""
    meta = pod['metadata']
    if kind == 'Pod':
        return pod
    template = {'metadata': {'labels': dict(meta['labels'])},
                'spec': pod['spec']}
    if meta.get('annotations'):
        template['metadata']['annotations'] = dict(meta['annotations'])
    selector = {'matchLabels': {'app': meta['labels']['app']}}
    if kind == 'CronJob':
        spec = {'schedule': f'*/{5 + i % 7} * * * *',
                'jobTemplate': {'spec': {'template': template}}}
    elif kind == 'Job':
        spec = {'backoffLimit': i % 4, 'template': template}
    elif kind == 'DaemonSet':
        spec = {'selector': selector, 'template': template}
    elif kind == 'StatefulSet':
        spec = {'replicas': 1 + i % 3, 'serviceName': meta['labels']['app'],
                'selector': selector, 'template': template}
    else:  # Deployment, ReplicaSet
        spec = {'replicas': 1 + i % 3, 'selector': selector,
                'template': template}
    return {'apiVersion': api_version, 'kind': kind,
            'metadata': {'name': f'{kind.lower()}-{i}',
                         'namespace': meta['namespace'],
                         'labels': dict(meta['labels'])},
            'spec': spec}


def generate(seed: int, n: int) -> list:
    """``n`` resources of the seven kinds, in ``mixed_cluster``'s seven
    namespaces."""
    rng = random.Random(seed)
    kinds = rng.choices(KIND_SHARES,
                        weights=[s for _k, s, _v in KIND_SHARES], k=n)
    out = []
    for i in range(n):
        pod = _mixed.make_config4_pod(rng, i)
        _add_chart_fields(rng, pod, i)
        if i % BREAK_EVERY == BREAK_AT:
            _break_everything(pod)
        kind, _share, api_version = kinds[i]
        out.append(_wrap(kind, api_version, pod, i))
    return out


def pod_template(resource: dict) -> dict:
    """The Pod a resource of this cluster is or carries: its ``metadata``
    and ``spec``."""
    kind = resource['kind']
    if kind == 'Pod':
        return resource
    if kind == 'CronJob':
        return resource['spec']['jobTemplate']['spec']['template']
    return resource['spec']['template']


def pod_spec(resource: dict) -> dict:
    """The Pod spec a resource of this cluster carries."""
    return pod_template(resource)['spec']
