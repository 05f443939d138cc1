"""Packs and generators that no cell of the benchmark runs yet.

The mutate pack and its Pods are what ``chip_smoke.py``'s device mutate
phase and ``tests/test_tpu_compile.py`` run; the config-5 pack and its
resource dump (``BASELINE.json`` ``configs[4]``) are what
``tests/test_baseline_configs.py`` and ``tests/test_mutate_compile.py``
run.  The packs a cell runs live under ``benchmarks/packs/`` as data and
their generators under ``benchmarks/generators/``; this module imports
nothing from there (the package never reaches up).  The ``model_config``
PR that adds the cell of one of these (ROADMAP R3d, R7) moves it there
and deletes it here.
"""

from __future__ import annotations

import json

# mutate-heavy pack for the device-side mutate path
# (kyverno_tpu/mutate/): every policy lowers to edit-site programs —
# the set is all-or-nothing (plan.py), so one unlowerable rule would
# zero the ratio — while a fraction of the generated pods trips the
# per-row FALLBACK paths (json6902 replace on a missing path, non-map
# intermediates), keeping the attributed-host machinery honest.
MUTATE_PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: add-default-labels
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: add-team
      match: {any: [{resources: {kinds: [Pod]}}]}
      mutate:
        patchStrategicMerge:
          metadata:
            labels:
              "+(team)": platform
              "+(cost-center)": eng-42
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: set-dns-policy
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: dns
      match: {any: [{resources: {kinds: [Pod]}}]}
      mutate:
        patchStrategicMerge:
          spec:
            dnsPolicy: ClusterFirst
            "+(enableServiceLinks)": false
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: stamp-annotations
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: stamp
      match: {any: [{resources: {kinds: [Pod]}}]}
      mutate:
        patchesJson6902: |-
          - op: add
            path: /metadata/annotations/managed-by
            value: kyverno-tpu
          - op: replace
            path: /metadata/annotations/tier
            value: gold
"""


# --------------------------------------------------------------------------
# BASELINE config 5: mutate + generate with foreach over a resource dump.

CONFIG5_PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: add-managed-labels
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: managed-label
      match: {any: [{resources: {kinds: [Pod]}}]}
      mutate:
        patchStrategicMerge:
          metadata:
            labels:
              managed: "true"
              +(costcenter): "unassigned"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: pull-policy-foreach
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: set-pull-policy
      match: {any: [{resources: {kinds: [Pod]}}]}
      mutate:
        foreach:
          - list: "request.object.spec.containers"
            preconditions:
              all:
                - key: "{{ element.imagePullPolicy || '' }}"
                  operator: Equals
                  value: ""
            patchStrategicMerge:
              spec:
                containers:
                  - name: "{{ element.name }}"
                    imagePullPolicy: IfNotPresent
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: annotate-revision
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: revision-annotation
      match: {any: [{resources: {kinds: [Pod]}}]}
      mutate:
        patchesJson6902: |-
          - op: add
            path: /metadata/annotations/policy.io~1revision
            value: "r1"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: default-deny-netpol
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: default-deny
      match: {any: [{resources: {kinds: [Namespace]}}]}
      generate:
        apiVersion: networking.k8s.io/v1
        kind: NetworkPolicy
        name: default-deny
        namespace: "{{ request.object.metadata.name }}"
        data:
          spec:
            podSelector: {}
            policyTypes: [Ingress, Egress]
"""


def make_config5_resource(rng, i: int, make_pod) -> dict:
    """The i-th resource of a cluster dump; ``make_pod(rng, i)`` is the
    cluster's Pod generator (``benchmarks/generators/mixed_cluster.py``
    ``make_pod`` wherever this is called today)."""
    # ~1 Namespace per 50 Pods, like a real dump
    if i % 50 == 49:
        return {'apiVersion': 'v1', 'kind': 'Namespace',
                'metadata': {'name': f'team-{i // 50}'}}
    pod = make_pod(rng, i)
    if rng.random() < 0.3:
        for cont in pod['spec']['containers']:
            cont['imagePullPolicy'] = 'Always'
    return pod


def make_mutate_pod(rng, i: int) -> dict:
    """Pods for the mutate-heavy pack: ~90% carry the ``tier``
    annotation the json6902 replace needs (the rest FALLBACK per row,
    attributed ``replace_path_missing``), half already carry a ``team``
    label (the add-only anchor skips), and dnsPolicy varies so the
    strategic merge sometimes edits, sometimes SKIPs."""
    meta = {'name': f'pod-{i}', 'namespace': f'ns-{i % 7}'}
    annotations = {'owner': f'team-{i % 5}'}
    if rng.random() < 0.9:
        annotations['tier'] = rng.choice(['bronze', 'silver', 'gold'])
    meta['annotations'] = annotations
    if rng.random() < 0.5:
        meta['labels'] = {'team': rng.choice(['red', 'blue'])}
    spec = {'containers': [{'name': 'c', 'image': 'nginx:1.25.3'}]}
    if rng.random() < 0.5:
        spec['dnsPolicy'] = 'Default'
    return {'apiVersion': 'v1', 'kind': 'Pod', 'metadata': meta,
            'spec': spec}


def load_mutate_pack():
    import yaml
    from kyverno_tpu.api.policy import Policy
    return [Policy(d) for d in yaml.safe_load_all(MUTATE_PACK) if d]


def check_mutate_row(engine, policies, pod: dict, row, what: str) -> None:
    """Hold one MutateScanner row ``(steps, patched)`` to the host
    engine's cumulative mutate chain over ``pod``: the patched document
    and every rule response, byte for byte."""
    from kyverno_tpu.engine.api import PolicyContext
    pctx = PolicyContext(None, new_resource=json.loads(json.dumps(pod)))
    host = []
    for pol in policies:
        ctx = pctx.copy()
        ctx.policy = pol
        er = engine.mutate(ctx)
        host.append((pol.name, er))
        if not er.is_successful():
            break
        pctx = pctx.copy()
        pctx.new_resource = er.patched_resource or pctx.new_resource
        pctx.json_context.add_resource(pctx.new_resource)
    steps, patched = row
    if json.dumps(patched, sort_keys=True) != \
            json.dumps(pctx.new_resource, sort_keys=True):
        raise AssertionError(f'{what}: patched doc diverged from the '
                             f'host oracle')
    if len(steps) != len(host):
        raise AssertionError(f'{what}: {len(steps)} policy steps, the '
                             f'host chain has {len(host)}')
    for (hname, her), (_dpol, der) in zip(host, steps):
        hcells = [(r.name, str(r.status), r.message, r.patches)
                  for r in her.policy_response.rules]
        dcells = [(r.name, str(r.status), r.message, r.patches)
                  for r in der.policy_response.rules]
        if hcells != dcells:
            raise AssertionError(f'{what} policy {hname}: device cells '
                                 f'diverged from the host oracle')

