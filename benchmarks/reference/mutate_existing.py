"""The plain reference of a mutate-existing install (``packs/
mutate-existing.yaml``): what installing policies with
``mutateExistingOnPolicyUpdate: true`` on a running cluster does to it, read
from the policies' own documents and applied over plain dicts and lists.  It
imports nothing of the program.

As Kyverno's documentation ("Mutate Existing resources") describes it: the
policy controller, on install, makes one UpdateRequest for each policy and
each existing resource its rule matches (the *trigger*); the background
controller substitutes ``{{request.object.metadata.name}}`` and
``{{request.object.metadata.namespace}}`` of the trigger into each target
spec (``apiVersion``, ``kind``, ``namespace``, ``name``), lists the targets
(an absent or wildcard name lists the namespace, an absent namespace every
namespace) and applies the rule's strategic-merge overlay to each.  The
overlay vocabulary is this pack's: scalar leaves, ``+(key)`` ("add if
absent"), one list of a single map anchored ``(name): "*"`` whose leaves
apply to every element of the live list, and ``{{request.object.metadata.
name|namespace}}`` in a value.

What this file knows it does differently from upstream Kyverno:

- It gives each target's final document, not the write sequence: no
  ``resourceVersion`` is assigned and no
  ``policies.kyverno.io/last-applied-patches`` annotation (the JSON patch of
  the last write) is written; a comparison leaves both out, and the
  controller's own host path is the check of those bytes.
- The policies are applied in install order, trigger after trigger in the
  order a listing gives (namespace, then name); the controller takes the
  UpdateRequests by name.  No two policies of the pack write one field, so
  the final documents are the same in either order.
- A rule matches by ``kinds`` (and ``namespaces``, where given) alone: the
  pack's rules match on nothing else.
- ``+(key)`` is "add if the key is absent", whatever the present value is;
  an element is patched when it is a map with a ``name`` that is a non-empty
  string (upstream processes an empty name under ``*`` and then finds no
  merge key for it).
- A wildcard name is matched with ``fnmatch`` (``*`` and ``?``; a ``[`` in a
  target name is not this pack's).
"""

import copy
import fnmatch
import json
import re

#: element slots of the device's list sites: a target with more containers
#: is one whose row the device hands to the host engine
#: (``kyverno_tpu/mutate/plan.py`` ``MAX_ELEMENTS``, restated here because
#: this file imports nothing)
DEVICE_CONTAINER_SLOTS = 4

_VARIABLE = re.compile(r'\{\{\s*request\.object\.metadata\.(name|namespace)'
                       r'\s*\}\}')
_ADD = re.compile(r'^\+\((.+)\)$')


def key_of(doc: dict) -> tuple:
    meta = doc.get('metadata') or {}
    return (doc.get('apiVersion', ''), doc.get('kind', ''),
            meta.get('namespace', '') or '', meta.get('name', ''))


def substitute(value, trigger: dict):
    """``value`` with the trigger's name and namespace in place of their
    variables (strings only; anything else as it is)."""
    if not isinstance(value, str):
        return value
    meta = trigger.get('metadata') or {}
    return _VARIABLE.sub(lambda m: meta.get(m.group(1), ''), value)


def _rules(policy: dict) -> list:
    return [r for r in (policy.get('spec') or {}).get('rules') or []
            if ((r.get('mutate') or {}).get('targets'))]


def _matches(rule: dict, doc: dict) -> bool:
    meta = doc.get('metadata') or {}
    for block in ((rule.get('match') or {}).get('any') or []) + \
            ([rule['match']] if 'resources' in (rule.get('match') or {})
             else []):
        res = block.get('resources') or {}
        kinds = [k.split('/')[-1] for k in res.get('kinds') or []]
        namespaces = res.get('namespaces')
        if doc.get('kind') in kinds and (
                not namespaces or meta.get('namespace') in namespaces):
            return True
    return False


class Cluster:
    """The documents of a cluster by key, with their listing order."""

    def __init__(self, docs: list):
        self.docs = {key_of(d): d for d in docs}
        self.order = sorted(self.docs, key=lambda k: (k[2], k[3]))
        self._by_kind = {}
        for k in self.order:
            by_ns = self._by_kind.setdefault(k[:2], {})
            by_ns.setdefault('', []).append(k)
            by_ns.setdefault(k[2], []).append(k)

    def listing(self, api_version: str, kind: str, namespace: str) -> list:
        return self._by_kind.get((api_version, kind), {}).get(namespace, [])


def targets(spec: dict, trigger: dict, cluster: Cluster) -> list:
    """The keys of the targets one spec names for one trigger."""
    api, kind = spec.get('apiVersion', ''), spec.get('kind', '')
    ns = substitute(spec.get('namespace', ''), trigger)
    name = substitute(spec.get('name', ''), trigger)
    if name and '*' not in name:
        key = (api, kind, ns, name)
        return [key] if key in cluster.docs else []
    return [k for k in cluster.listing(api, kind, ns)
            if not name or fnmatch.fnmatchcase(k[3], name)]


def merge(doc: dict, overlay: dict, trigger: dict) -> None:
    """Apply one overlay of the pack's vocabulary to ``doc`` in place."""
    for raw_key, value in overlay.items():
        m = _ADD.match(raw_key)
        key = m.group(1) if m else raw_key
        if isinstance(value, dict):
            if not isinstance(doc.get(key), dict):
                doc[key] = {}
            merge(doc[key], value, trigger)
        elif isinstance(value, list):
            element = dict(value[0])
            if element.pop('(name)', None) != '*':
                raise ValueError(f'list overlay outside the pack: {value}')
            for item in doc.get(key) or []:
                if isinstance(item, dict) and isinstance(
                        item.get('name'), str) and item['name']:
                    merge(item, element, trigger)
        elif m:
            if key not in doc:
                doc[key] = substitute(value, trigger)
        else:
            doc[key] = substitute(value, trigger)


def install(policies: list, docs: list):
    """``(final, requests)`` of installing ``policies`` (plain documents,
    in order) on a cluster of ``docs`` (as stored): ``final`` maps the key
    of every target to its final document; ``requests`` lists, for each
    (policy, trigger) pair, ``(policy name, trigger key, [target keys])``."""
    cluster = Cluster(docs)
    final = {}
    requests = []
    for policy in policies:
        name = policy['metadata']['name']
        rules = _rules(policy)
        for tkey in list(cluster.order):
            trigger = cluster.docs[tkey]
            applied = [r for r in rules if _matches(r, trigger)]
            if not applied:
                continue
            keys = []
            for rule in applied:
                mutation = rule['mutate']
                for spec in mutation['targets']:
                    for key in targets(spec, trigger, cluster):
                        doc = final.get(key)
                        if doc is None:
                            doc = final[key] = copy.deepcopy(
                                cluster.docs[key])
                        merge(doc, mutation['patchStrategicMerge'], trigger)
                        keys.append(key)
            requests.append((name, tkey, keys))
    return final, requests


def expects_host(doc: dict) -> bool:
    """Whether a target's row of a container-list overlay is one the device
    hands to the host engine: a template with more containers than its
    element slots."""
    spec = ((doc.get('spec') or {}).get('template') or {}).get('spec') or {}
    return len(spec.get('containers') or []) > DEVICE_CONTAINER_SLOTS


def comparable(doc: dict) -> str:
    """Canonical JSON of a document with what only the write sequence
    gives left out: ``resourceVersion`` and the last-applied annotation (an
    annotations map that holds nothing else goes with it)."""
    doc = copy.deepcopy(doc)
    meta = doc.get('metadata') or {}
    meta.pop('resourceVersion', None)
    annotations = meta.get('annotations')
    if isinstance(annotations, dict):
        annotations.pop('policies.kyverno.io/last-applied-patches', None)
        if not annotations:
            del meta['annotations']
    return json.dumps(doc, sort_keys=True, separators=(',', ':'))
