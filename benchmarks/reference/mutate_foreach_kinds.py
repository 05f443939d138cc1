"""The plain reference of the foreach pack (``packs/mutate-foreach.yaml``) over
the seven kinds its autogen reaches: what each policy does to a resource,
straight from the policy's meaning over plain dicts and lists; the order of
the chain; and which resources the device is expected to hand to the host
engine.  It imports nothing of the program; the RFC 6902 applier and the
canonical JSON are ``mutate_defaults.py``'s.

Autogen: a Pod rule also runs on the Pod template of a DaemonSet, Deployment,
Job, StatefulSet or ReplicaSet (``spec.template``) and of a CronJob
(``spec.jobTemplate.spec.template``), and reads and writes the template's
``metadata`` and ``spec`` where it reads and writes the Pod's.  A policy with
a JSON patch is not converted (upstream ``CanAutoGen``), so
``annotate-revision`` writes Pods only.  The chain is cumulative and runs in
the order the policy cache hands the policies out, by name (upstream
``pkg/webhooks/resource/mutation.go`` ``applyMutations``); no two policies
write a field another reads or writes, so the end is the same in any order.

A ``foreach`` runs over the elements of its list, each a container that is a
map with a name of its own: an element whose conditions hold gets the
overlay's fields; the others are left alone.  A condition key
``element.<path> || ''`` reads the field and takes ``''`` where it is absent,
null, false or empty.  ``request.object.spec.initContainers || `[]``` is no
element where a Pod has no init containers.

What this file knows it does differently from upstream Kyverno: a missing
map on the way to a written field is created (upstream does the same through
the merge, and through ``EnsurePathExistsOnAdd`` for the JSON patch); names
that are no string, empty or repeated are not generated, and are not this
file's to know.
"""

import copy

import benchlib

_defaults = benchlib.load_module('reference', 'mutate_defaults')
apply_patch = _defaults.apply_patch
canonical = _defaults.canonical

#: element slots of the device's list sites: a resource whose Pod has more
#: containers (or init containers) is one the device hands to the host
#: engine (``kyverno_tpu/mutate/plan.py`` ``MAX_ELEMENTS``, restated here
#: because this file imports nothing)
DEVICE_ELEMENT_SLOTS = 4
#: the kinds whose Pod sits at ``spec.template``
TEMPLATE_KINDS = ('DaemonSet', 'Deployment', 'Job', 'StatefulSet',
                  'ReplicaSet', 'ReplicationController')


def pod_of(resource: dict):
    """The Pod a resource is or carries (its ``metadata`` and ``spec``), or
    None for a kind no policy of the pack reaches."""
    kind = resource.get('kind')
    if kind == 'Pod':
        return resource
    spec = resource.get('spec') or {}
    if kind in TEMPLATE_KINDS:
        return spec.get('template')
    if kind == 'CronJob':
        return ((spec.get('jobTemplate') or {}).get('spec') or {}).get(
            'template')
    return None


def _map(parent: dict, key: str) -> dict:
    if parent.get(key) is None:
        parent[key] = {}
    return parent[key]


def _field(element: dict, *path):
    """``element.<path> || ''``."""
    value = element
    for key in path:
        value = value.get(key) if isinstance(value, dict) else None
    if value is None or value is False or \
            (isinstance(value, (str, list, dict)) and not value):
        return ''
    return value


def _elements(pod: dict, key: str) -> list:
    return [e for e in (pod.get('spec') or {}).get(key) or []
            if isinstance(e, dict)]


def add_managed_labels(pod: dict) -> None:
    labels = _map(_map(pod, 'metadata'), 'labels')
    labels['managed'] = 'true'
    labels.setdefault('costcenter', 'unassigned')


def annotate_revision(pod: dict) -> None:
    _map(_map(pod, 'metadata'), 'annotations')['policy.io/revision'] = 'r1'


def pull_policy_foreach(pod: dict) -> None:
    for container in _elements(pod, 'containers'):
        if _field(container, 'imagePullPolicy') == '':
            container['imagePullPolicy'] = 'IfNotPresent'


def default_memory_request(pod: dict) -> None:
    for container in _elements(pod, 'containers'):
        if _field(container, 'resources', 'requests', 'memory') == '':
            _map(_map(container, 'resources'), 'requests')['memory'] = '64Mi'


def no_privilege_escalation(pod: dict) -> None:
    for container in _elements(pod, 'containers'):
        if _field(container, 'securityContext', 'privileged') is not True \
                and _field(container, 'securityContext',
                           'allowPrivilegeEscalation') == '':
            _map(container, 'securityContext')[
                'allowPrivilegeEscalation'] = False


def init_pull_policy(pod: dict) -> None:
    for init in _elements(pod, 'initContainers'):
        if _field(init, 'imagePullPolicy') not in ('Always', 'IfNotPresent',
                                                   'Never'):
            init['imagePullPolicy'] = 'IfNotPresent'


def init_seccomp_runtime_default(pod: dict) -> None:
    for init in _elements(pod, 'initContainers'):
        if _field(init, 'securityContext', 'seccompProfile', 'type') in (
                '', 'Unconfined'):
            _map(_map(init, 'securityContext'), 'seccompProfile')[
                'type'] = 'RuntimeDefault'


#: policy name → (what it does to the Pod, whether autogen converts it), in
#: the order of the chain
CHAIN = {
    'add-managed-labels': (add_managed_labels, True),
    'annotate-revision': (annotate_revision, False),
    'default-memory-request': (default_memory_request, True),
    'init-pull-policy': (init_pull_policy, True),
    'init-seccomp-runtime-default': (init_seccomp_runtime_default, True),
    'no-privilege-escalation': (no_privilege_escalation, True),
    'pull-policy-foreach': (pull_policy_foreach, True),
}


def mutate(resource: dict) -> dict:
    """The resource after the whole chain; ``resource`` is left as it
    was."""
    out = copy.deepcopy(resource)
    pod = pod_of(out)
    if pod is None:
        return out
    for policy, converted in CHAIN.values():
        if converted or out.get('kind') == 'Pod':
            policy(pod)
    return out


def expects_host(resource: dict) -> bool:
    """Whether the device hands this resource's chain to the host engine:
    one of its Pod's container lists is longer than the element slots."""
    pod = pod_of(resource)
    spec = (pod or {}).get('spec') or {}
    return any(isinstance(spec.get(key), list) and
               len(spec[key]) > DEVICE_ELEMENT_SLOTS
               for key in ('containers', 'initContainers'))
