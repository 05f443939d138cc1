"""The plain reference of the defaults pack (``packs/mutate-defaults.yaml``):
what each of its seven policies does to a Pod, straight from the policy's
meaning over plain dicts and lists; the order of the chain; an RFC 6902
applier for the JSONPatch a ``/mutate`` answer carries, as the API server
applies it; and which Pods the device is expected to hand to the host engine.
It imports nothing of the program.

The chain is cumulative and runs in the order the policy cache hands the
policies out, by name (upstream ``pkg/webhooks/resource/mutation.go``
``applyMutations``).  No two of the seven write the same field, so the
document at the end is the same in any order; ``CHAIN`` keeps the cache's.

What this file knows it does differently from upstream Kyverno:

- ``+(key)`` is "add if the key is absent", whatever the present value is,
  null included (upstream ``handleAddIfNotPresentAnchor`` looks for the key).
- A container is patched when it is a map with a ``name`` that is a
  non-empty string: for such names ``(name): "*"`` and ``(name): "?*"`` take
  the same containers (upstream processes an empty name under ``*`` and then
  finds no merge key for it).  Other names are not generated, and are not
  this file's to know.
- A missing map on the way to a written field is created, for the JSON patch
  of ``stamp-annotations`` too (upstream applies it with
  ``EnsurePathExistsOnAdd``); every generated Pod carries
  ``metadata.annotations`` anyway.
"""

import copy
import json

#: element slots of the device's list sites: a Pod with more containers is
#: one the device hands to the host engine (``kyverno_tpu/mutate/plan.py``
#: ``MAX_ELEMENTS``, restated here because this file imports nothing)
DEVICE_CONTAINER_SLOTS = 4


def _map(parent: dict, key: str) -> dict:
    """``parent[key]`` as a map, created where it is absent or null."""
    if parent.get(key) is None:
        parent[key] = {}
    return parent[key]


def _add_if_absent(target: dict, key: str, value) -> None:
    if key not in target:
        target[key] = value


def _named_containers(pod: dict) -> list:
    containers = (pod.get('spec') or {}).get('containers') or []
    return [c for c in containers
            if isinstance(c, dict) and isinstance(c.get('name'), str)
            and c['name']]


def add_default_resources(pod: dict) -> None:
    for container in _named_containers(pod):
        requests = _map(_map(container, 'resources'), 'requests')
        _add_if_absent(requests, 'memory', '100Mi')
        _add_if_absent(requests, 'cpu', '100m')


def add_default_securitycontext(pod: dict) -> None:
    context = _map(_map(pod, 'spec'), 'securityContext')
    _add_if_absent(context, 'runAsNonRoot', True)
    _add_if_absent(context, 'runAsUser', 1000)
    _add_if_absent(context, 'runAsGroup', 3000)
    _add_if_absent(context, 'fsGroup', 2000)


def add_labels(pod: dict) -> None:
    labels = _map(_map(pod, 'metadata'), 'labels')
    labels['managed-by'] = 'platform'
    _add_if_absent(labels, 'cost-center', 'eng-42')


def add_nodeselector(pod: dict) -> None:
    _add_if_absent(_map(_map(pod, 'spec'), 'nodeSelector'),
                   'kubernetes.io/os', 'linux')


def always_pull_images(pod: dict) -> None:
    for container in _named_containers(pod):
        container['imagePullPolicy'] = 'Always'


def disable_service_discovery(pod: dict) -> None:
    spec = _map(pod, 'spec')
    spec['dnsPolicy'] = 'ClusterFirst'
    _add_if_absent(spec, 'enableServiceLinks', False)


def stamp_annotations(pod: dict) -> None:
    _map(_map(pod, 'metadata'), 'annotations')['managed-by'] = 'kyverno-tpu'


#: policy name → what it does, in the order of the chain
CHAIN = {
    'add-default-resources': add_default_resources,
    'add-default-securitycontext': add_default_securitycontext,
    'add-labels': add_labels,
    'add-nodeselector': add_nodeselector,
    'always-pull-images': always_pull_images,
    'disable-service-discovery': disable_service_discovery,
    'stamp-annotations': stamp_annotations,
}


def mutate(pod: dict) -> dict:
    """The Pod after the whole chain; ``pod`` is left as it was."""
    out = copy.deepcopy(pod)
    for policy in CHAIN.values():
        policy(out)
    return out


def expects_host(pod: dict) -> bool:
    """Whether the device hands this Pod's chain to the host engine: its
    container list is longer than the element slots."""
    containers = (pod.get('spec') or {}).get('containers')
    return isinstance(containers, list) and \
        len(containers) > DEVICE_CONTAINER_SLOTS


def canonical(doc) -> bytes:
    """Canonical JSON: what two documents are compared by, byte for byte."""
    return json.dumps(doc, sort_keys=True, separators=(',', ':'),
                      ensure_ascii=False).encode('utf-8')


# -- RFC 6902 -----------------------------------------------------------------

class PatchError(Exception):
    """The patch does not apply to the document."""


def _tokens(pointer: str) -> list:
    if pointer == '':
        return []
    if not pointer.startswith('/'):
        raise PatchError(f'not a JSON pointer: {pointer!r}')
    return [t.replace('~1', '/').replace('~0', '~')
            for t in pointer[1:].split('/')]


def _index(token: str, length: int, appending: bool) -> int:
    if appending and token == '-':
        return length
    if not token.isdigit() or (len(token) > 1 and token[0] == '0'):
        raise PatchError(f'not an array index: {token!r}')
    at = int(token)
    if at > length or (at == length and not appending):
        raise PatchError(f'index {at} is outside an array of {length}')
    return at


def _parent(doc, tokens: list):
    node = doc
    for token in tokens[:-1]:
        if isinstance(node, dict):
            if token not in node:
                raise PatchError(f'no member {token!r} on the way')
            node = node[token]
        elif isinstance(node, list):
            node = node[_index(token, len(node), False)]
        else:
            raise PatchError(f'{token!r} is looked up in a scalar')
    return node


def apply_patch(doc, ops: list):
    """``doc`` after the ``add`` / ``replace`` / ``remove`` operations of
    RFC 6902, in order; ``doc`` is left as it was.  An operation that does
    not apply (a missing parent, a ``replace`` or ``remove`` of what is not
    there, an index outside the array, another ``op``) raises."""
    doc = copy.deepcopy(doc)
    for op in ops:
        kind, tokens = op.get('op'), _tokens(op.get('path', ''))
        if kind not in ('add', 'replace', 'remove'):
            raise PatchError(f'operation {kind!r} is not add, replace or '
                             f'remove')
        if kind != 'remove' and 'value' not in op:
            raise PatchError(f'{kind} without a value')
        if not tokens:
            if kind == 'remove':
                raise PatchError('the whole document cannot be removed')
            doc = copy.deepcopy(op['value'])
            continue
        parent, last = _parent(doc, tokens), tokens[-1]
        if isinstance(parent, dict):
            if kind != 'add' and last not in parent:
                raise PatchError(f'{kind} of the missing member {last!r}')
            if kind == 'remove':
                del parent[last]
            else:
                parent[last] = copy.deepcopy(op['value'])
        elif isinstance(parent, list):
            at = _index(last, len(parent), kind == 'add')
            if kind == 'add':
                parent.insert(at, copy.deepcopy(op['value']))
            elif kind == 'remove':
                del parent[at]
            else:
                parent[at] = copy.deepcopy(op['value'])
        else:
            raise PatchError(f'{last!r} is written into a scalar')
    return doc
