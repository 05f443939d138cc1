"""Packs and generators that no cell of the benchmark runs yet, and the
host-chain oracle of a device mutate row.

The config-5 pack and its resource dump (``BASELINE.json`` ``configs[4]``)
are what ``tests/test_baseline_configs.py`` and
``tests/test_mutate_compile.py`` run.  The packs a cell runs live under
``benchmarks/packs/`` as data and their generators under
``benchmarks/generators/`` (the mutate pack and its Pods moved there with
the cell ``admission_mutate_open``); this module imports nothing from
there (the package never reaches up).  The ``model_config`` PR that adds
the cell of the config-5 pack (ROADMAP R7) moves it there and deletes it
here.  ``check_mutate_row`` is what ``chip_smoke.py``'s device mutate phase
and ``tests/test_device_mutate.py`` hold a ``MutateScanner`` row to.
"""

from __future__ import annotations

import json

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

