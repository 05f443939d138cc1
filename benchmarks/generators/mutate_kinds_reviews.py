"""Seeded AdmissionReview bodies of the seven kinds a Pod policy's autogen
reaches, for a cluster that writes per-container defaults as ``foreach``
mutations (``packs/mutate-foreach.yaml``).

The objects are those of ``chart_cluster.py`` (imported, not copied), by
``KIND_SHARES``: bare Pods and the six controllers, each carrying a Pod of
``mixed_cluster``'s ``make_config4_pod``.  Bodies ``0`` to ``len(KINDS) - 1``
take one resource of each kind in turn, each with 5 containers, so that the
warm writes of a cell's set-up reach every kind's scanners at both element
widths the validate encoder sizes a batch to (4, and 8 past four elements:
``compiler/encode.py``) and nothing compiles in the window; after them
body ``i`` is the cluster's resource ``i``.  Every ``compliant_every``-th Pod
(spec) is replaced by the Pod every validate policy admits
(``admission_reviews.compliant_pod``), wrapped in the resource's kind.  Three in four are CREATE, the rest UPDATE with an
``oldObject`` that differs; the user is drawn by Zipf over the same user model.

Each Pod spec is then shaped, from the seed, in the fields the pack's
per-element conditions read, so that every condition is sometimes met and
sometimes not:

- about half of the containers lack ``imagePullPolicy`` (the others carry
  ``Always``, ``IfNotPresent`` or ``Never``), ``resources.requests.memory``
  and ``securityContext.allowPrivilegeEscalation``; one container in ten of
  the cluster's own Pods is ``privileged``;
- 1 Pod spec in ``init_every`` has one or two ``initContainers`` (index
  ``i % init_every == 1``; the cluster's own init containers are dropped),
  half without ``imagePullPolicy`` and some with a value outside the three,
  their ``seccompProfile.type`` absent, ``Unconfined``, ``RuntimeDefault`` or
  ``Localhost``;
- 1 Pod spec in ``five_every`` has 5 containers (index ``i % five_every ==
  0``, and the first body of each kind; never a compliant one), more than
  the device's element slots: the device hands that row's chain to the
  host engine.

The i-th body is a pure function of ``(seed, i)`` and the cluster.
"""

import bisect
import copy
import json
import random

import benchlib

_reviews = benchlib.load_module('generators', 'admission_reviews')
_chart = benchlib.load_module('generators', 'chart_cluster')

FIVE = 5
_PULL = ('Always', 'IfNotPresent', 'Never')
_INIT_PULL = ('Always', 'IfNotPresent', 'always', 'IfNotPresent ')
_SECCOMP = ('Unconfined', 'RuntimeDefault', 'Localhost')


def _init_containers(rng, i: int) -> list:
    out = []
    for j in range(1 + (i // 10) % 2):
        init = {'name': f'init-{j}', 'image': 'ghcr.io/org/setup:v1.4',
                'securityContext': {'allowPrivilegeEscalation': False,
                                    'runAsNonRoot': True,
                                    'capabilities': {'drop': ['ALL']}}}
        if rng.random() < 0.5:
            init['imagePullPolicy'] = rng.choice(_INIT_PULL)
        if rng.random() < 0.6:
            profile = {'type': rng.choice(_SECCOMP)}
            if profile['type'] == 'Localhost':
                profile['localhostProfile'] = 'profiles/setup.json'
            init['securityContext']['seccompProfile'] = profile
        out.append(init)
    return out


def shape(rng, template: dict, i: int, good: bool, five: bool,
          init: bool) -> None:
    """The Pod (``metadata`` and ``spec``) a resource is or carries, shaped
    in place; ``good`` keeps a compliant Pod compliant after mutation."""
    spec = template['spec']
    containers = spec['containers']
    if five:
        for j in range(len(containers), FIVE):
            extra = copy.deepcopy(containers[j % len(containers)])
            extra['name'] = f'c{j}'
            containers.append(extra)
    for container in containers:
        container.pop('imagePullPolicy', None)
        if rng.random() < 0.5:
            container['imagePullPolicy'] = rng.choice(_PULL)
        requests = (container.get('resources') or {}).get('requests')
        if requests is not None and rng.random() < 0.5:
            requests.pop('memory', None)
        context = container.get('securityContext')
        if context is not None and rng.random() < 0.5:
            context.pop('allowPrivilegeEscalation', None)
        if not good and rng.random() < 0.1:
            container.setdefault('securityContext', {})['privileged'] = True
    spec.pop('initContainers', None)
    if init:
        spec['initContainers'] = _init_containers(rng, i)


def _first_of_each_kind(cluster: list) -> list:
    seen = {}
    for k, resource in enumerate(cluster):
        seen.setdefault(resource['kind'], k)
    missing = [kind for kind in _chart.KINDS if kind not in seen]
    if missing:
        raise ValueError(f'the cluster holds no {missing}')
    return [seen[kind] for kind in _chart.KINDS]


def _compliant(resource: dict, i: int) -> dict:
    """The resource with the compliant Pod in its place."""
    pod = _reviews.compliant_pod(i)
    if resource['kind'] == 'Pod':
        return pod
    template = _chart.pod_template(resource)
    template['metadata'] = {'labels': dict(pod['metadata']['labels'])}
    template['spec'] = pod['spec']
    return resource


def request(i: int, doc: dict, info: dict, update_every: int) -> dict:
    """The i-th request for ``doc`` of any kind: CREATE, or UPDATE with an
    ``oldObject`` that differs."""
    group, _, version = doc['apiVersion'].rpartition('/')
    out = {'uid': f'bench-{i}',
           'operation': 'UPDATE' if i % update_every == update_every - 1
           else 'CREATE',
           'kind': {'group': group, 'version': version, 'kind': doc['kind']},
           'namespace': doc['metadata']['namespace'],
           'name': doc['metadata']['name'],
           'object': doc,
           'userInfo': info}
    if out['operation'] == 'UPDATE':
        old = json.loads(json.dumps(doc))
        old['metadata'].setdefault('labels', {})['rev'] = 'old'
        out['oldObject'] = old
    return out


def generate(seed: int, cluster: list, count: int, users: int = 200,
             teams: int = 12, zipf_s: float = 1.1, compliant_every: int = 3,
             update_every: int = 4, five_every: int = 25,
             init_every: int = 10) -> list:
    """``count`` AdmissionReview bodies (bytes) for ``/mutate``."""
    firsts = _first_of_each_kind(cluster)
    cum = _reviews._zipf_cum(users, zipf_s)
    bodies = []
    for i in range(count):
        rng = random.Random((seed << 20) ^ i)
        user = min(bisect.bisect_left(cum, rng.random() * cum[-1]),
                   users - 1)
        five = i % five_every == 0 or i < len(firsts)
        good = i % compliant_every == compliant_every - 1 and not five
        source = cluster[firsts[i] if i < len(firsts)
                         else i % len(cluster)]
        doc = copy.deepcopy(source)
        if good:
            doc = _compliant(doc, i)
        shape(rng, _chart.pod_template(doc), i, good, five,
              i % init_every == 1)
        bodies.append(json.dumps({
            'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
            'request': request(i, doc, _reviews.user_info(user, teams),
                               update_every)}).encode())
    return bodies
