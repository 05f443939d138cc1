#!/usr/bin/env python
"""Background-scan throughput benchmark on the committed policy pack.

Measures the north-star workload (BASELINE.md): background-scan of
synthetic Pods against the pack this repo commits (``load_policy_pack``:
Pod Security Standards baseline + restricted, ``PACK`` and
``CONFIG4_PACK``) — reporting absolute decisions/sec on the available
accelerator and the ratio vs the pure-host Python engine.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "decisions/s", "vs_baseline": N}
vs_baseline is measured against the BASELINE.json north star of 50k
decisions/s on a v5e-4 slice -> 12.5k/s per chip.

The default backend is probed in a subprocess first (backend init
failures are sticky in-process).  A run that finds no backend fails;
``BENCH_PLATFORM=cpu`` is the explicit CPU choice.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List, Optional

_T0 = time.time()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_LOG = None


def _progress(msg: str) -> None:
    """Structured progress logging via observability.logging (stderr,
    key/value), replacing the old raw '[bench +Ns]' prints."""
    global _LOG
    if _LOG is None:
        import logging
        from kyverno_tpu.observability.logging import setup
        setup()  # text handler on stderr for the 'kyverno' root
        _LOG = logging.getLogger('kyverno.bench')
    from kyverno_tpu.observability.logging import with_values
    with_values(_LOG, msg, elapsed_s=round(time.time() - _T0, 1))

PER_CHIP_TARGET = 50_000 / 4  # north star: 50k/s on v5e-4

# BASELINE.json config 3: Pod Security Standards at both levels.  The
# default autogen adds the Deployment-family and CronJob rules, so these
# two policies are six rule programs (compiler/pss_compile.py).
PSS_PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: podsecurity-baseline
spec:
  background: true
  validationFailureAction: Audit
  rules:
    - name: baseline
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        podSecurity:
          level: baseline
          version: latest
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: podsecurity-restricted
spec:
  background: true
  validationFailureAction: Audit
  rules:
    - name: restricted
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        podSecurity:
          level: restricted
          version: latest
"""

# a small self-contained pack + pod generator
PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: disallow-latest-tag
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: require-image-tag
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "An image tag is required."
        pattern:
          spec:
            containers:
              - image: "!*:latest"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: require-resources
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: validate-resources
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "resource requests and limits required"
        pattern:
          spec:
            containers:
              - resources:
                  requests:
                    memory: "?*"
                    cpu: "?*"
"""

# mutate-heavy pack for the device-side mutate ratchet
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

#: device-coverage ratchet for ``bench.py --mutate-pack``: the mutate
#: rows' device ratio must not regress below this committed floor
#: (~10% of generated pods deliberately trip per-row FALLBACK)
MUTATE_DEVICE_RATIO_FLOOR = 0.75

#: warm-up ratchet (mirrors MUTATE_DEVICE_RATIO_FLOOR): a fresh process
#: sweeping row counts from 1 past the chunk may compile/load at most
#: this many evaluator executables for the policy set — the canonical
#: shape table (compiler/shapes.py) guarantees 2; the power-of-two
#: bucket ladder this replaced minted up to 9 (BENCH r03-r05 measured
#: that zoo at warm_s 49-93s / cache_warm_s 92.7s against ~28s of scan)
WARM_EXECUTABLES_MAX = 2

#: heterogeneous-traffic ratchet for ``bench.py --admission-concurrency``:
#: mean batch occupancy under the synthetic cluster generator (zipfian
#: users/namespaces, mixed verbs, exception tenants —
#: conformance/loadgen.py) must EXCEED this floor at the highest thread
#: count.  The batch key is the policy set alone (per-row admission
#: lanes); before that change heterogeneous traffic degenerated to
#: batch-of-one, so this committed floor is what keeps it fixed.
HET_OCCUPANCY_FLOOR = 2.0

#: chaos ratchets for ``bench.py --admission-chaos`` (graceful
#: degradation under injected faults — kyverno_tpu/faults/): every
#: response across every chaos wave must be HTTP 200 with a verdict
#: bit-identical to the fault-free oracle, the ``poison_row`` shed
#: count must equal EXACTLY the number of injected poison rows (the
#: quarantine isolates rows, it does not shed batch-sized groups), and
#: the tripped circuit breaker must complete the open → half-open →
#: closed round trip visible on /debug/breakers and the state gauge.
CHAOS_MAX_NON_200 = 0

#: policy-churn ratchet for ``bench.py --policy-churn``: a mid-traffic
#: edit of ONE policy in the replicated enforce set may compile at most
#: this many NEW executables — the touched partition's admission shape
#: (warm-up + live traffic share one canonical small-batch capacity).
#: The partition-level assertion is exact (the recompiled pids must
#: equal the churn differ's touched set); this count is the belt over
#: the compile-cache census — a whole-world recompile storm (the
#: pre-partition behavior: every executable of a 1k-policy set reminted
#: for a one-line edit) fails the bench even if the differ lies.
CHURN_RECOMPILED_EXECUTABLES_MAX = 2

#: admission-latency SLO ratchet for the full bench: p99 of the
#: /validate samples through the device-served chain at ~1k policies
#: must stay under this ceiling.  Seeded at ~2x the BENCH_r06
#: measurement (p50=12.62ms / p99=346.96ms on CPU) so machine noise
#: cannot flap it while a real serving regression (lost batching, shed
#: storm, everything on the host loop) fails the bench.  The same
#: value is the objective the bench-run SLO engine burns against, so
#: the ``slo`` block's burn rate is directly comparable across runs.
ADMISSION_P99_MS_MAX = 700.0

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


# --------------------------------------------------------------------------
# BASELINE config 4: JMESPath-heavy precondition/deny policies.  Every
# condition key is a real JMESPath program (filters, functions, ||
# defaults) evaluated per resource at encode time, then decided on
# device — the workload BASELINE.md row 4 describes.

CONFIG4_PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: limit-containers
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: max-3-containers
      match: {any: [{resources: {kinds: [Pod]}}]}
      preconditions:
        all:
          - key: "{{ request.object.metadata.labels.tier || 'none' }}"
            operator: AnyIn
            value: [web, api]
      validate:
        message: "tiered pods are limited to 3 containers"
        deny:
          conditions:
            any:
              - key: "{{ length(request.object.spec.containers) }}"
                operator: GreaterThan
                value: 3
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: require-tagged-images
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: no-latest-or-untagged
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "images must carry a non-latest tag"
        deny:
          conditions:
            any:
              - key: "{{ length(request.object.spec.containers[?contains(image, ':latest')]) }}"
                operator: GreaterThan
                value: 0
              - key: "{{ length(request.object.spec.containers[?!contains(image, ':')]) }}"
                operator: GreaterThan
                value: 0
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: require-probes
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: liveness-required
      match: {any: [{resources: {kinds: [Pod]}}]}
      preconditions:
        all:
          - key: "{{ request.object.metadata.labels.app || '' }}"
            operator: NotEquals
            value: ""
      validate:
        message: "app pods need liveness probes on every container"
        deny:
          conditions:
            any:
              - key: "{{ length(request.object.spec.containers[?livenessProbe == null]) }}"
                operator: GreaterThan
                value: 0
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: digest-pin-prod
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: prod-pins-digests
      match: {any: [{resources: {kinds: [Pod]}}]}
      preconditions:
        all:
          - key: "{{ request.object.metadata.labels.env || '' }}"
            operator: Equals
            value: prod
      validate:
        message: "prod images must be pinned by digest"
        deny:
          conditions:
            any:
              - key: "{{ length(request.object.spec.containers[?!contains(image, '@sha256:')]) }}"
                operator: GreaterThan
                value: 0
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: hostpath-quarantine
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: no-hostpath-outside-system
      match: {any: [{resources: {kinds: [Pod]}}]}
      preconditions:
        all:
          - key: "{{ request.object.metadata.namespace }}"
            operator: AnyNotIn
            value: [kube-system]
      validate:
        message: "hostPath volumes are quarantined to kube-system"
        deny:
          conditions:
            any:
              - key: "{{ length(request.object.spec.volumes[?hostPath] || `[]`) }}"
                operator: GreaterThan
                value: 0
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: sysctl-allowlist
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: net-sysctls-only
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "only net.* sysctls are allowed"
        deny:
          conditions:
            any:
              - key: "{{ length(request.object.spec.securityContext.sysctls[?!starts_with(name, 'net.')] || `[]`) }}"
                operator: GreaterThan
                value: 0
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: resource-budget
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: cpu-annotation-budget
      match: {any: [{resources: {kinds: [Pod]}}]}
      preconditions:
        all:
          - key: "{{ request.object.metadata.annotations.\\"budget.io/max-cpu\\" || '0' }}"
            operator: NotEquals
            value: '0'
      validate:
        message: "declared cpu budget exceeds the cluster cap of 16"
        deny:
          conditions:
            any:
              - key: "{{ to_number(request.object.metadata.annotations.\\"budget.io/max-cpu\\") }}"
                operator: GreaterThan
                value: 16
"""


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


def run_config4(n: int, platform: str) -> dict:
    """BASELINE config 4 (scaled): JMESPath-heavy pack over n Pods."""
    import random
    from kyverno_tpu.api.policy import load_policies_from_yaml
    from kyverno_tpu.compiler.scan import BatchScanner

    policies = load_policies_from_yaml(CONFIG4_PACK)
    rng = random.Random(7)
    resources = [make_config4_pod(rng, i) for i in range(n)]
    t0 = time.time()
    scanner = BatchScanner(policies)
    compile_s = time.time() - t0
    t_warm = time.time()
    scanner.scan(resources[:min(n, scanner.CHUNK + 1)])
    warm_s = time.time() - t_warm
    t1 = time.time()
    out = scanner.scan(resources)
    scan_s = time.time() - t1
    decisions = sum(len(r.policy_response.rules)
                    for responses in out for r in responses)
    return {
        'metric': 'config4_jmespath_decisions_per_sec_per_chip',
        'value': round(decisions / scan_s, 1) if scan_s else 0.0,
        'unit': 'decisions/s',
        'vs_baseline': round(decisions / scan_s / PER_CHIP_TARGET, 3)
        if scan_s else 0.0,
        'platform': platform, 'n_resources': n,
        'n_policies': len(policies),
        'n_compiled_rules': len(scanner.cps.programs),
        'n_host_rules': len(scanner.cps.host_rules),
        'decisions': decisions,
        'compile_s': round(compile_s, 2), 'warm_s': round(warm_s, 2),
        'scan_s': round(scan_s, 2),
    }


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


def make_config5_resource(rng, i: int) -> dict:
    # ~1 Namespace per 50 Pods, like a real dump
    if i % 50 == 49:
        return {'apiVersion': 'v1', 'kind': 'Namespace',
                'metadata': {'name': f'team-{i // 50}'}}
    pod = make_pod(rng, i)
    if rng.random() < 0.3:
        for cont in pod['spec']['containers']:
            cont['imagePullPolicy'] = 'Always'
    return pod


def run_config5(n: int, platform: str) -> dict:
    """BASELINE config 5 (scaled): mutate+generate foreach over a dump,
    fanned over a host process pool; generate URs feed the real
    background pipeline."""
    import random
    from kyverno_tpu.api.policy import load_policies_from_yaml
    from kyverno_tpu.compiler.apply import BatchApplier

    policies = load_policies_from_yaml(CONFIG5_PACK)
    rng = random.Random(11)
    resources = [make_config5_resource(rng, i) for i in range(n)]
    applier = BatchApplier(policies)
    if applier.processes > 1:
        # spawn the pool + per-worker engine builds outside the timing
        applier.apply(resources[:64], parallel=True)
    else:
        applier.apply(resources[:64])
    t0 = time.time()
    results = applier.apply(resources)
    apply_s = time.time() - t0
    applications = sum(len(r.rule_results) for r in results)
    ur_specs = [spec for r in results for spec in r.ur_specs]
    # drive a sample of the generate URs through the real background
    # controller to include the generate cost in the reported rate
    from kyverno_tpu.background.update_request_controller import \
        UpdateRequestController
    from kyverno_tpu.background.updaterequest import UpdateRequestGenerator
    from kyverno_tpu.dclient.client import FakeClient
    from kyverno_tpu.engine.engine import Engine
    client = FakeClient()
    by_name = {p.name: p for p in policies}
    for res in resources:
        if res.get('kind') == 'Namespace':
            client.create_resource('v1', 'Namespace', '', res)
    ctrl = UpdateRequestController(client, Engine(),
                                   policy_getter=by_name.get)
    gen = UpdateRequestGenerator(client)
    t1 = time.time()
    for spec in ur_specs:
        gen.apply(spec)
    processed = ctrl.process_pending()
    generate_s = time.time() - t1
    netpols = client.list_resource('networking.k8s.io/v1',
                                   'NetworkPolicy')
    total_s = apply_s + generate_s
    return {
        'metric': 'config5_mutate_generate_applies_per_sec',
        'value': round((applications + processed) / total_s, 1)
        if total_s else 0.0,
        'unit': 'applies/s',
        'vs_baseline': round(len(resources) / total_s / PER_CHIP_TARGET, 3)
        if total_s else 0.0,
        'platform': platform, 'n_resources': n,
        'n_policies': len(policies),
        'rule_applications': applications,
        'resources_per_sec': round(len(resources) / total_s, 1)
        if total_s else 0.0,
        'ur_processed': processed,
        'netpols_generated': len(netpols),
        'apply_s': round(apply_s, 2), 'generate_s': round(generate_s, 2),
        'processes': applier.processes,
    }


def probe_platform() -> str:
    """Probe the default JAX backend in a subprocess (init failures are
    sticky in-process); returns its platform.  The child exits, and
    lets go of the chip, before this process touches JAX.  No backend
    is an error, never a CPU run: BENCH_PLATFORM=cpu asks for that."""
    code = 'import jax; print(jax.default_backend())'
    tail = ''
    for attempt in range(2):
        try:
            out = subprocess.run([sys.executable, '-c', code],
                                 env=dict(os.environ),
                                 capture_output=True, text=True, timeout=180)
            if out.returncode == 0 and out.stdout.strip():
                return out.stdout.strip().splitlines()[-1]
            tail = out.stderr.strip()[-400:]
        except subprocess.TimeoutExpired:
            tail = 'probe timed out after 180s'
        time.sleep(3)
    raise RuntimeError(f'no JAX backend came up: {tail}')


def load_policy_pack():
    """The committed pack: Pod Security Standards baseline + restricted
    (BASELINE.json config 3), ``PACK`` and ``CONFIG4_PACK`` — 11
    policies, 15 rule programs, all compiled for the device.  Nothing
    outside the repo is read."""
    from kyverno_tpu.api.policy import load_policies_from_yaml
    return [p for pack in (PSS_PACK, PACK, CONFIG4_PACK)
            for p in load_policies_from_yaml(pack)]


def cache_probe(platform: str) -> float:
    """Second-process warm-up with the persistent XLA compilation cache
    populated: build the full-pack scanner and run one chunk-shaped scan.
    Returns the compile+warm seconds the fresh process paid."""
    code = (
        'import sys, time, random; sys.path.insert(0, %r)\n'
        'import bench\n'
        'from kyverno_tpu.compiler.scan import BatchScanner\n'
        't0 = time.time()\n'
        'scanner = BatchScanner(bench.load_policy_pack())\n'
        'rng = random.Random(0)\n'
        'pods = [bench.make_pod(rng, i) for i in range(scanner.CHUNK)]\n'
        'scanner.scan_statuses(pods)\n'
        'print(f"CACHEPROBE {time.time() - t0:.2f}")\n'
    ) % os.path.dirname(os.path.abspath(__file__))
    try:
        out = subprocess.run([sys.executable, '-c', code],
                             capture_output=True, text=True, timeout=600)
        for line in out.stdout.splitlines():
            if line.startswith('CACHEPROBE'):
                return float(line.split()[1])
    except Exception:  # noqa: BLE001 - probe is informational
        pass
    return -1.0


def warm_probe(platform: str) -> dict:
    """Fresh-process warm block: time-to-first-decision plus the
    executable census, in a new interpreter (cold jit caches, whatever
    is on disk from this run).  The subprocess scans ONE pod (ttfd —
    what a restarting webhook pod pays before its first verdict), then
    sweeps the boundary row counts {1, small+1, chunk+1} so every
    canonical shape (and the multi-chunk spill) is exercised, and
    reports how many executables that took.  THE RATCHET: more than
    ``WARM_EXECUTABLES_MAX`` compiles+loads per policy set fails the
    bench — the bucket zoo must not regrow."""
    code = (
        'import json, random, sys, time\n'
        't0 = time.time()\n'
        'sys.path.insert(0, %r)\n'
        'import bench\n'
        'from kyverno_tpu.observability import device as devtel\n'
        'from kyverno_tpu.observability.metrics import MetricsRegistry\n'
        'reg = devtel.configure(MetricsRegistry())\n'
        'from kyverno_tpu.compiler.scan import BatchScanner\n'
        'scanner = BatchScanner(bench.load_policy_pack())\n'
        'rng = random.Random(0)\n'
        'scanner.scan([bench.make_pod(rng, 0)])\n'
        'ttfd = time.time() - t0\n'
        'for n in (scanner.SMALL_BATCH + 1, scanner.CHUNK + 1):\n'
        '    scanner.scan_statuses('
        '[bench.make_pod(rng, i) for i in range(n)])\n'
        'C = "kyverno_tpu_compile_cache_requests_total"\n'
        'print("WARMPROBE " + json.dumps({\n'
        '    "ttfd_s": round(ttfd, 2),\n'
        '    "sweep_s": round(time.time() - t0, 2),\n'
        '    "executables_compiled": int(reg.counter_value('
        'C, result="miss")),\n'
        '    "executables_loaded": int(reg.counter_value('
        'C, result="aot_load")),\n'
        '}))\n'
    ) % os.path.dirname(os.path.abspath(__file__))
    probe: dict = {'error': 'probe produced no WARMPROBE line'}
    try:
        out = subprocess.run([sys.executable, '-c', code],
                             capture_output=True, text=True, timeout=900)
        for line in out.stdout.splitlines():
            if line.startswith('WARMPROBE'):
                probe = json.loads(line[len('WARMPROBE '):])
    except Exception as e:  # noqa: BLE001 - report, ratchet below
        probe = {'error': f'{type(e).__name__}: {e}'}
    probe['row_counts_swept'] = '1, small+1, chunk+1'
    probe['ratchet_max_executables'] = WARM_EXECUTABLES_MAX
    executables = probe.get('executables_compiled', 0) + \
        probe.get('executables_loaded', 0)
    if 'error' not in probe and executables > WARM_EXECUTABLES_MAX:
        raise AssertionError(
            f'fresh-process warm-up used {executables} executables '
            f'(> committed max {WARM_EXECUTABLES_MAX}) — the canonical '
            f'batch-shape table is not holding')
    return probe


def _peak_rss_mb() -> float:
    import resource as _resource
    return _resource.getrusage(_resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _current_rss_mb() -> float:
    """Instantaneous resident set (``/proc/self/statm``; falls back to
    the kernel's peak counter off Linux)."""
    try:
        with open('/proc/self/statm') as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf('SC_PAGE_SIZE') / (1024.0 * 1024.0)
    except Exception:  # noqa: BLE001 - non-Linux fallback
        return _peak_rss_mb()


#: committed ratchet — RSS GROWTH ceiling (peak during streaming minus
#: RSS before the scan) for the north-star streaming block at ≥100k
#: rows.  The pre-streaming 1M run grew ~19.5GB (NORTHSTAR_1M.json:
#: 21.6GB peak vs 2.1GB before scan) because the host built 1M decoded
#: rows before writing anything; the bounded pipeline holds growth at
#: O(chunk slots), measured ~0.2GB at 100k rows on CPU.  A regression
#: toward monolithic buffering fails the bench here.
NORTHSTAR_RSS_MB_MAX = float(os.environ.get('NORTHSTAR_RSS_MB_MAX',
                                            '4096'))
#: rows below which the RSS/sieve ratchets stay informational (fixed
#: process overheads dominate tiny runs)
NORTHSTAR_RATCHET_MIN_ROWS = 100_000
#: committed ratchet — streaming e2e decisions/s must reach the same
#: run's in-scan sieve rate (the ROADMAP target: report assembly fully
#: overlapped, the report path no longer loses to the raw status path).
#: The ratchet arms only where the overlap premise physically holds
#: (>1 CPU: the pipeline legs need a second core to run concurrently —
#: on a 1-core host total work is serial and e2e ⊃ sieve by
#: construction); 1-core runs still record the ratio.
E2E_VS_SIEVE_FLOOR = float(os.environ.get('BENCH_E2E_SIEVE_FLOOR',
                                          '1.0'))
E2E_VS_SIEVE_ARMS = (os.cpu_count() or 1) > 1


class RssSampler:
    """Background thread sampling resident-set size during a streaming
    block: peak + a bounded time series (downsampled 2× whenever it
    would exceed ~240 points), feeding the ``rss`` bench block and the
    NORTHSTAR_RSS_MB_MAX ratchet."""

    def __init__(self, interval_s: float = 0.25):
        import threading
        self.interval_s = interval_s
        self.samples: list = []  # (t_offset_s, rss_mb)
        self.peak_mb = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name='bench-rss-sampler',
                                        daemon=True)
        self._t0 = time.monotonic()

    def _run(self) -> None:
        step = self.interval_s
        while not self._stop.is_set():
            rss = _current_rss_mb()
            self.peak_mb = max(self.peak_mb, rss)
            self.samples.append(
                (round(time.monotonic() - self._t0, 2), round(rss, 1)))
            if len(self.samples) > 240:
                self.samples = self.samples[::2]
                step *= 2
            self._stop.wait(step)

    def __enter__(self) -> 'RssSampler':
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=2)
        rss = _current_rss_mb()
        self.peak_mb = max(self.peak_mb, rss)

    def block(self, before_mb: float, n_rows: int) -> dict:
        """The ``rss`` bench block (+ the committed growth ratchet)."""
        growth = max(self.peak_mb - before_mb, 0.0)
        out = {
            'before_mb': round(before_mb, 1),
            'peak_during_stream_mb': round(self.peak_mb, 1),
            'growth_mb': round(growth, 1),
            'rss_per_1k_rows_mb': round(growth / max(n_rows / 1000.0, 1e-9),
                                        3),
            'samples': [list(s) for s in self.samples[:240]],
            'ratchet_growth_mb_max': NORTHSTAR_RSS_MB_MAX,
            'ratchet_applies': n_rows >= NORTHSTAR_RATCHET_MIN_ROWS,
        }
        if out['ratchet_applies'] and growth > NORTHSTAR_RSS_MB_MAX:
            raise AssertionError(
                f'streaming RSS grew {growth:.0f}MB over the scan '
                f'(> committed NORTHSTAR_RSS_MB_MAX='
                f'{NORTHSTAR_RSS_MB_MAX:.0f}MB at {n_rows} rows) — the '
                'scan path is regressing toward monolithic buffering')
        return out


def _stage_totals() -> dict:
    """Per-stage busy seconds snapshot (from the stage histogram)."""
    from kyverno_tpu.observability import device as device_telemetry
    return {stage: d['total_s']
            for stage, d in device_telemetry.stage_breakdown().items()}


def _overlap_block(before: dict, after: dict, wall_s: float) -> dict:
    """Per-stage overlap ratio (stage busy-time ÷ streaming wall) over
    one measured window.  Ratios sum past 1.0 exactly when pipeline
    legs ran concurrently; the '_total' entry is that sum."""
    out = {}
    total = 0.0
    for stage, t1 in after.items():
        busy = t1 - before.get(stage, 0.0)
        if busy <= 0 or wall_s <= 0:
            continue
        total += busy
        out[stage] = round(busy / wall_s, 4)
    out['_total'] = round(total / wall_s, 4) if wall_s > 0 else 0.0
    return out


def _critical_path_block(blame_before: dict, wall_s: float,
                         trace_name: str = 'northstar'):
    """Critical-path blame delta over one measured window: exclusive
    per-stage blame seconds (they sum to the scans' wall, unlike the
    overlap ratios), the bottleneck verdict, and the advisor's knob
    suggestion.  Also drops a Perfetto-loadable Chrome trace of the
    recorder's recent scans (path in ``trace_file``).  None when the
    timeline recorder is off (``KTPU_TIMELINE=0``)."""
    from kyverno_tpu.observability import timeline as _timeline
    rec = _timeline.recorder()
    if rec is None:
        return None
    blame = {}
    for stage, t1 in rec.blame_totals().items():
        d = t1 - blame_before.get(stage, 0.0)
        if d > 0:
            blame[stage] = d
    total = sum(blame.values())
    frac = {s: round(v / total, 4) for s, v in blame.items()} \
        if total > 0 else {}
    bound_by = max(blame, key=lambda s: blame[s]) if blame else ''
    suggest, note = _timeline.advise(bound_by, frac.get(bound_by, 0.0))
    path = os.environ.get('BENCH_TIMELINE_TRACE') or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), '.cache', 'timeline',
        f'{trace_name}-trace.json')
    try:
        trace_file = _timeline.dump_chrome_trace(path)
    except OSError:
        trace_file = None
    return {
        'blame_s': {s: round(v, 4) for s, v in blame.items()},
        'blame_frac': frac,
        'wall_s': round(wall_s, 2),
        'wall_coverage': round(total / wall_s, 4) if wall_s > 0 else 0.0,
        'bound_by': bound_by,
        'suggest': suggest,
        'note': note,
        'scans': rec.n_scans,
        'trace_file': trace_file,
    }


def run_bench(n: int, platform: str, budget_s: float) -> dict:
    """Time-boxed north-star run: stream synthetic Pods through the
    report path until ``budget_s`` of measured streaming wall-clock is
    spent (or ``n`` Pods are done, whichever first), then report the
    measured steady-state rate and the N actually processed — the bench
    must always finish inside the driver's budget, never extrapolate,
    and never default to a fixed N it can't complete."""
    import random
    from kyverno_tpu.compiler.scan import BatchScanner
    from kyverno_tpu.compiler.ir import STATUS_HOST, STATUS_PASS

    _progress('loading policy pack')
    policies = load_policy_pack()
    rng = random.Random(42)

    # executable ledger over the whole run: every compile / AOT load /
    # dispatch the bench triggers lands in the census block below
    from kyverno_tpu.observability import executables as _exec
    _exec.configure(ledger_n=256)

    # per-chunk stage timeline + critical-path blame over the streaming
    # window (the critical_path block below); KTPU_TIMELINE=0 disables
    from kyverno_tpu.observability import timeline as _timeline
    if _timeline.recorder() is None:
        _timeline.configure()

    t0 = time.time()
    _progress('compiling policy set')
    scanner = BatchScanner(policies)
    compile_s = time.time() - t0
    n_rules = len(scanner.cps.programs) + len(scanner.cps.host_rules)

    # warm the jit cache at the chunk shape — the ONLY device shape bulk
    # scans use (multi-chunk scans pad the tail chunk to CHUNK too).
    # Reported separately; a fresh process skips the compile via the AOT
    # executable cache (cache_warm_s below).
    t_warm = time.time()
    _progress('warming chunk-shape executable')
    warm_rng = random.Random(7)
    scanner.scan([make_pod(warm_rng, i) for i in range(scanner.CHUNK)])
    warm_s = time.time() - t_warm
    _progress(f'warm done in {warm_s:.1f}s; streaming (budget {budget_s}s)')

    # count host materializations to keep the device-decided fraction
    # honest: every cell NOT synthesized from device outputs re-runs the
    # host engine and caps throughput
    materialized = [0]
    inner_materialize = scanner._materialize

    def counting_materialize(prog, doc):
        materialized[0] += 1
        return inner_materialize(prog, doc)
    scanner._materialize = counting_materialize

    # HEADLINE: the report-producing path — full EngineResponses with
    # host-identical messages, with BackgroundScanReport construction
    # streamed through the scan pipeline (what reports/controllers.py
    # BackgroundScanController.reconcile runs; reference scan loop:
    # pkg/controllers/report/utils/scanner.go:60).  Pods stream in slabs
    # generated outside the timed region (cluster LIST stands in for the
    # harness); reports are sunk incrementally so RSS stays bounded.
    host_policy_names = {scanner.policies[i].name
                         for i in scanner._host_policy_idx}
    rss_before_mb = _current_rss_mb()
    stage_before = _stage_totals()
    blame_before = _timeline.blame_totals()  # excludes the warm scan
    slab = 4 * scanner.CHUNK
    decisions = 0
    compiled_decisions = 0
    n_reports = 0
    report_results = 0
    n_done = 0
    e2e_s = 0.0
    from kyverno_tpu.reports.types import build_fused_report
    with RssSampler() as rss_sampler:
        while n_done < n and e2e_s < budget_s:
            m = min(slab, n - n_done)
            pods = [make_pod(rng, i) for i in range(n_done, n_done + m)]
            t1 = time.time()
            slab_done = 0
            deadline = t1 + max(budget_s - e2e_s, 5.0)
            for resource, (results, summary, row_policies) in zip(
                    pods, scanner.scan_report_results(pods)):
                report = build_fused_report(resource, results, summary,
                                            row_policies)
                n_reports += 1
                report_results += len(results)
                decisions += len(results)
                if host_policy_names:
                    for r in results:
                        if r.get('policy') not in host_policy_names:
                            compiled_decisions += 1
                else:
                    compiled_decisions += len(results)
                slab_done += 1
                # the budget must bind even when a degraded path makes
                # one slab slow — check inside the slab, count only
                # what finished
                if slab_done % 512 == 0 and time.time() > deadline:
                    break
            e2e_s += time.time() - t1
            n_done += slab_done
            # slabs are ephemeral: collect the dict cycles eagerly so
            # the north-star 1M run holds RSS flat
            import gc
            gc.collect()
            _progress(f'streamed {n_done} pods, {decisions} decisions, '
                      f'{e2e_s:.1f}s spent')
    peak_rss_mb = _peak_rss_mb()
    rate = decisions / e2e_s if e2e_s > 0 else 0.0
    rss_block = rss_sampler.block(rss_before_mb, n_done)
    overlap_block = _overlap_block(stage_before, _stage_totals(), e2e_s)
    cp_block = _critical_path_block(blame_before, e2e_s)

    # the raw status sieve (no response objects) on a bounded sample —
    # the ROADMAP ratchet pins streaming e2e ≥ this in-scan sieve rate
    _progress('sieve sample')
    sieve_n = min(n_done, 20_000)
    sieve_rng = random.Random(42)
    sieve_pods = [make_pod(sieve_rng, i) for i in range(sieve_n)]
    t3 = time.time()
    status, detail, match = scanner.scan_statuses(sieve_pods)
    sieve_s = time.time() - t3
    sieve_rate = int(match.sum()) / sieve_s if sieve_s > 0 else 0.0
    e2e_vs_sieve = rate / sieve_rate if sieve_rate else None
    if E2E_VS_SIEVE_ARMS and n_done >= NORTHSTAR_RATCHET_MIN_ROWS and \
            e2e_vs_sieve is not None and \
            e2e_vs_sieve < E2E_VS_SIEVE_FLOOR:
        raise AssertionError(
            f'streaming e2e rate {rate:.0f}/s fell below the in-scan '
            f'sieve rate {sieve_rate:.0f}/s (ratio {e2e_vs_sieve:.3f} < '
            f'committed floor {E2E_VS_SIEVE_FLOOR}) — report assembly '
            'is no longer hidden behind the device pipeline')

    if os.environ.get('BENCH_SKIP_EXTRAS') == '1':
        # north-star mode: the streaming number IS the artifact; skip
        # the host/admission/cache-probe extras
        device_decided_frac = \
            1.0 - materialized[0] / max(compiled_decisions, 1)
        exec_block = _exec.census()
        _exec.disable()
        return {
            'executables': exec_block,
            'metric': 'bg_scan_e2e_decisions_per_sec_per_chip',
            'value': round(rate, 1),
            'unit': 'decisions/s',
            'vs_baseline': round(rate / PER_CHIP_TARGET, 3),
            'platform': platform, 'n_resources': n_done, 'n_cap': n,
            'budget_s': budget_s, 'n_policies': len(policies),
            'n_rules': n_rules,
            'n_compiled_rules': len(scanner.cps.programs),
            'decisions': decisions, 'n_reports': n_reports,
            'report_results': report_results,
            'device_decided_frac': round(device_decided_frac, 4),
            'materialized': materialized[0],
            'compile_s': round(compile_s, 2), 'warm_s': round(warm_s, 2),
            'e2e_s': round(e2e_s, 2),
            'peak_rss_mb': round(peak_rss_mb, 1),
            'rss_before_scan_mb': round(rss_before_mb, 1),
            'rss': rss_block,
            'streaming_overlap': overlap_block,
            'critical_path': cp_block,
            'sieve_n': sieve_n,
            'sieve_decisions_per_sec': round(sieve_rate, 1),
            'e2e_vs_sieve': round(e2e_vs_sieve, 3)
            if e2e_vs_sieve is not None else None,
            'e2e_vs_sieve_floor': E2E_VS_SIEVE_FLOOR,
            'e2e_vs_sieve_armed': E2E_VS_SIEVE_ARMS,
        }

    host_status_frac = int((match & (status == STATUS_HOST)).sum()) / \
        max(int(match.sum()), 1)
    nonpass = int(match.sum()) - int((match & (status == STATUS_PASS)).sum())

    device_decided_frac = 1.0 - materialized[0] / max(compiled_decisions, 1)
    warning = None
    if device_decided_frac < 0.95:
        warning = (f'device_decided_frac dropped to '
                   f'{device_decided_frac:.3f} — host materialization is '
                   f'capping throughput')
        print(f'WARNING: {warning}', file=sys.stderr)

    # host-engine baseline on a sample (the pure-Python interpreter this
    # repo would use without the device path; the reference Go engine is
    # not runnable here -- no Go toolchain)
    sample = min(100, n_done)
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.engine.api import PolicyContext
    engine = Engine()
    t4 = time.time()
    host_dec = 0
    for doc in sieve_pods[:sample]:
        for policy in policies:
            resp = engine.apply_background_checks(
                PolicyContext(policy, new_resource=doc))
            host_dec += len(resp.policy_response.rules)
    host_s = time.time() - t4
    host_rate = host_dec / host_s if host_s > 0 else 0.0

    # admission latency through the full serving chain at ~1k policies
    # (BASELINE metric: 'p50 webhook latency @1k policies').  The SLO
    # engine runs over this section so the bench exercises the real
    # burn-rate pipeline: handlers feed slo.record, the block below is
    # its snapshot, and the committed ADMISSION_P99_MS_MAX is both the
    # engine's objective and the ratchet.
    _progress('admission latency @1k policies')
    from kyverno_tpu.observability import slo as _slo
    _slo.configure(window_s=600.0, p99_ms=ADMISSION_P99_MS_MAX,
                   target=0.99)
    adm_ctx = _admission_server(policies, sieve_pods)
    lat_p50_ms, lat_p99_ms, lat_n_policies, adm_device = admission_latency(
        policies, sieve_pods, ctx=adm_ctx)

    # concurrent admission through the micro-batcher (KTPU_SERVING=batch):
    # decisions/s and batch occupancy vs client thread count, on the
    # same compiled serving chain
    _progress('concurrent admission (batch serving)')
    adm_concurrency = admission_concurrency(adm_ctx, sieve_pods)

    # heterogeneous traffic from the synthetic cluster generator: the
    # scanner-only batch key is what this block tracks (and ratchets)
    _progress('heterogeneous admission (synthetic cluster load)')
    adm_hetero = admission_heterogeneous(adm_ctx)
    adm_ctx[1].shutdown()

    # SLO block: the burn-rate engine's view of every admission section
    # above (latency + concurrency + heterogeneous all fed slo.record
    # through the handlers).  The p99 ratchet arms only when the
    # samples rode the compiled path — host-loop latencies are ~10x and
    # would flap it on build-starved machines.
    slo_block = _slo.snapshot()
    slo_block['p99_ms_max'] = ADMISSION_P99_MS_MAX
    slo_block['ratchet_armed'] = bool(adm_device)
    _slo.disable()
    if adm_device and lat_p99_ms > ADMISSION_P99_MS_MAX:
        raise AssertionError(
            f'admission p99 {lat_p99_ms:.1f}ms exceeded the committed '
            f'ceiling ADMISSION_P99_MS_MAX={ADMISSION_P99_MS_MAX:.0f}ms '
            f'on the device-served chain (BENCH_r06 seed: p50=12.62ms / '
            f'p99=346.96ms)')

    # rescan churn block (CI-sized; the O(churn) verdict-cache claim —
    # full scale runs standalone via `bench.py --churn-ticks`)
    rescan_block = None
    if os.environ.get('BENCH_RESCAN', '1') == '1':
        _progress('rescan churn bench')
        try:
            rescan_block = run_rescan_churn(
                platform,
                n=min(n_done, int(os.environ.get('BENCH_RESCAN_N',
                                                 '20000'))),
                ticks=3)
        except Exception as e:  # noqa: BLE001 - block is additive
            rescan_block = {'error': f'{type(e).__name__}: {e}'}

    # the two fresh-process probes start children that need the
    # backend.  Off the CPU this process holds the chip and cannot lend
    # it: they are skipped (chip_smoke.py run twice is the warm reading
    # there; bench.py --warm-probe runs the second one on its own)
    children_ok = platform == 'cpu'

    # fresh-process warm time with the persistent compilation cache
    _progress('fresh-process cache probe')
    cache_warm_s = cache_probe(platform) \
        if children_ok and os.environ.get('BENCH_CACHE_PROBE', '1') == '1' \
        else -1.0

    # fresh-process warm block: time-to-first-decision + the executable
    # census across the boundary row counts, ratcheted at
    # WARM_EXECUTABLES_MAX (a regrown bucket zoo fails the bench)
    _progress('fresh-process warm probe')
    warm_block = None
    if os.environ.get('BENCH_WARM_PROBE', '1') == '1':
        warm_block = warm_probe(platform) if children_ok else \
            {'skipped': 'this process holds the chip'}

    # executable census over the whole run (this process only — the
    # warm/cache probes above run their own fresh processes)
    exec_block = _exec.census()
    _exec.disable()
    _progress('done')

    result = {
        'metric': 'bg_scan_e2e_decisions_per_sec_per_chip',
        'value': round(rate, 1),
        'unit': 'decisions/s',
        'vs_baseline': round(rate / PER_CHIP_TARGET, 3),
        'platform': platform,
        'n_resources': n_done,
        'n_cap': n,
        'budget_s': budget_s,
        'n_policies': len(policies),
        'n_rules': n_rules,
        'n_compiled_rules': len(scanner.cps.programs),
        'decisions': decisions,
        'n_reports': n_reports,
        'report_results': report_results,
        'device_decided_frac': round(device_decided_frac, 4),
        'materialized': materialized[0],
        'host_status_frac': round(host_status_frac, 4),
        'nonpass_frac': round(nonpass / max(int(match.sum()), 1), 4),
        'compile_s': round(compile_s, 2),
        'warm_s': round(warm_s, 2),
        'e2e_s': round(e2e_s, 2),
        'peak_rss_mb': round(peak_rss_mb, 1),
        'rss_before_scan_mb': round(rss_before_mb, 1),
        'cache_warm_s': round(cache_warm_s, 2),
        'warm': warm_block,
        'rss': rss_block,
        'streaming_overlap': overlap_block,
        'critical_path': cp_block,
        'sieve_n': sieve_n,
        'sieve_decisions_per_sec': round(sieve_rate, 1),
        'e2e_vs_sieve': round(e2e_vs_sieve, 3)
        if e2e_vs_sieve is not None else None,
        'e2e_vs_sieve_floor': E2E_VS_SIEVE_FLOOR,
        'e2e_vs_sieve_armed': E2E_VS_SIEVE_ARMS,
        'host_engine_decisions_per_sec': round(host_rate, 1),
        'speedup_vs_host_engine': round(rate / host_rate, 2)
        if host_rate else None,
        'admission_p50_ms': lat_p50_ms,
        'admission_p99_ms': lat_p99_ms,
        'admission_n_policies': lat_n_policies,
        'admission_device_served': adm_device,
        'admission_concurrency': adm_concurrency,
        'admission_heterogeneous': adm_hetero,
        'slo': slo_block,
        'executables': exec_block,
        'rescan': rescan_block,
    }
    if warning:
        result['warning'] = warning
    return result


def replicate_enforce(policies, target_policies=1000):
    """``policies`` copied round after round under new names, every copy
    in Enforce mode, until there are ``target_policies`` of them."""
    import copy
    from kyverno_tpu.api.policy import Policy
    if not policies:
        raise ValueError('empty policy pack: nothing to replicate')
    replicated = []
    i = 0
    while len(replicated) < target_policies:
        for p in policies:
            doc = copy.deepcopy(p.raw)
            doc['metadata']['name'] = f"{doc['metadata']['name']}-r{i}"
            doc.setdefault('spec', {})['validationFailureAction'] = 'Enforce'
            replicated.append(Policy(doc))
            if len(replicated) >= target_policies:
                break
        i += 1
    return replicated


def _admission_server(policies, resources, target_policies=1000):
    """Replicated-enforce serving chain shared by the admission latency
    and concurrency benches (one ~1k-policy scanner compile serves
    both).  Returns ``(server, handlers, n_replicated, device_served)``;
    the device-path build wait is bounded (BENCH_ADMISSION_WAIT_S) so
    the bench always finishes."""
    from kyverno_tpu.policycache.cache import Cache
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer

    replicated = replicate_enforce(policies, target_policies)
    cache = Cache()
    cache.warm_up(replicated)
    handlers = ResourceHandlers(cache)
    server = WebhookServer(handlers)
    # scanner builds happen on a background thread (requests host-loop
    # meanwhile); the steady-state figures want the compiled path, so
    # wait for it — but bounded, so a slow build degrades the reported
    # numbers instead of timing out the bench
    from kyverno_tpu.policycache import cache as pcache
    ns0 = resources[0]['metadata'].get('namespace', '')
    enforce = cache.get_policies(pcache.VALIDATE_ENFORCE, 'Pod', ns0)
    device_served = False
    if enforce:
        wait_s = float(os.environ.get('BENCH_ADMISSION_WAIT_S', '90'))
        device_served = handlers.wait_device_ready(enforce,
                                                   timeout=wait_s)
    return server, handlers, len(replicated), device_served


def _admission_review(doc: dict, uid: str) -> bytes:
    import json as _json
    return _json.dumps({
        'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
        'request': {
            'uid': uid, 'operation': 'CREATE',
            'kind': {'group': '', 'version': 'v1',
                     'kind': doc.get('kind', '')},
            'namespace': doc['metadata'].get('namespace', ''),
            'name': doc['metadata'].get('name', ''),
            'object': doc, 'userInfo': {'username': 'bench'},
        }}).encode()


def admission_latency(policies, resources, target_policies=1000,
                      samples=120, ctx=None):
    """p50/p99 latency of /validate through the full handler chain with
    the pack replicated to ~1k policies (enforce mode); ``device_served``
    records whether the sampled latencies rode the compiled path.
    ``ctx`` reuses a prebuilt ``_admission_server`` tuple."""
    import statistics
    server, _handlers, n_replicated, device_served = \
        ctx if ctx is not None else _admission_server(
            policies, resources, target_policies)
    if not device_served:
        samples = min(samples, 30)  # host-loop latencies are ~10x — keep
        # the degraded sampling inside the bench budget
    lat = []
    for k in range(samples):
        doc = resources[k % len(resources)]
        review = _admission_review(doc, f'u{k}')
        t0 = time.time()
        server.handle('/validate/fail', review)
        lat.append((time.time() - t0) * 1000)
    lat.sort()
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    return (round(statistics.median(lat), 2), round(p99, 2),
            n_replicated, device_served)


def admission_concurrency(ctx, resources, thread_counts=None,
                          requests_per_thread=25):
    """Concurrent-admission serving bench: switch the shared handler
    chain to ``KTPU_SERVING=batch`` and drive it with N client threads
    — the micro-batcher coalesces their scans into shared device
    dispatches.  One block per thread count:
    ``{threads, decisions_per_s, batch_occupancy_p50,
    queue_wait_p50_ms, shed_total, decision_breakdown}`` — the
    breakdown (per-path p50/p95 + device-share histogram from the
    decision-provenance flight recorder) is the tracked number for the
    homogeneous-vs-heterogeneous occupancy gap (ROADMAP)."""
    import threading
    from kyverno_tpu.observability import provenance
    server, handlers, _n_replicated, device_served = ctx
    if thread_counts is None:
        spec = os.environ.get('BENCH_ADMISSION_THREADS', '1,8,32')
        thread_counts = [int(t) for t in spec.split(',') if t.strip()]
    prior_mode = handlers.serving_mode
    handlers.serving_mode = 'batch'
    recorder = provenance.recorder()
    prov_owned = recorder is None
    if prov_owned:
        # ring must hold every decision of the largest run so the
        # one-record-per-decision invariant below is checkable
        recorder = provenance.configure(
            flight_n=max(16384,
                         2 * max(thread_counts) * requests_per_thread))
    blocks = []
    try:
        for n_threads in thread_counts:
            batcher = handlers._get_batcher()
            batcher.reset_stats()
            if recorder is not None:
                recorder.reset()
            barrier = threading.Barrier(n_threads + 1)

            def work(tid, n_threads=n_threads):
                barrier.wait()
                for k in range(requests_per_thread):
                    doc = resources[(tid * requests_per_thread + k)
                                    % len(resources)]
                    server.handle('/validate/fail',
                                  _admission_review(doc, f't{tid}k{k}'))

            threads = [threading.Thread(target=work, args=(tid,))
                       for tid in range(n_threads)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.time()
            for t in threads:
                t.join()
            elapsed = time.time() - t0
            stats = batcher.stats()
            decisions = n_threads * requests_per_thread
            breakdown = provenance.breakdown()
            if breakdown:
                # provenance invariant: one DecisionRecord per decision
                assert breakdown['decisions'] == decisions, \
                    (breakdown['decisions'], decisions)
            blocks.append({
                'threads': n_threads,
                'decisions_per_s': round(decisions / elapsed, 1)
                if elapsed > 0 else 0.0,
                'batch_occupancy_p50': stats['occupancy_p50'],
                'batch_occupancy_mean': round(stats['occupancy_mean'], 2),
                'queue_wait_p50_ms': round(stats['queue_wait_p50_ms'], 3),
                'shed_total': stats['shed_total'],
                'device_served': device_served,
                'decision_breakdown': breakdown,
            })
            _progress(f'admission concurrency: {n_threads} threads -> '
                      f"{blocks[-1]['decisions_per_s']}/s, occupancy "
                      f"p50 {blocks[-1]['batch_occupancy_p50']}")
    finally:
        handlers.serving_mode = prior_mode
        if prov_owned:
            provenance.disable()
    return blocks


def admission_heterogeneous(ctx, thread_counts=None,
                            requests_per_thread=25):
    """Heterogeneous-traffic serving bench: drive the batch-mode chain
    with the synthetic cluster generator (zipfian users/namespaces,
    mixed CREATE/UPDATE verbs, exception-holding tenants —
    kyverno_tpu/conformance/loadgen.py).  The batch key is the policy
    set alone, so mean occupancy under MIXED admission tuples is the
    tracked number; THE RATCHET: at the highest thread count it must
    exceed ``HET_OCCUPANCY_FLOOR`` (before per-row admission lanes this
    traffic was batch-of-one by construction).  A paced single-client
    ``trickle`` pass closes the block as the occupancy-1 sanity
    anchor."""
    import threading
    from kyverno_tpu.conformance.loadgen import SyntheticCluster
    from kyverno_tpu.observability import provenance
    server, handlers, _n_replicated, device_served = ctx
    if thread_counts is None:
        spec = os.environ.get('BENCH_ADMISSION_THREADS', '1,8,32')
        thread_counts = [int(t) for t in spec.split(',') if t.strip()]
    cluster = SyntheticCluster(seed=1234)
    exc_docs = cluster.exception_docs()
    prior_mode = handlers.serving_mode
    handlers.serving_mode = 'batch'
    pc_builder = handlers.pc_builder
    prior_build = pc_builder.build

    def build(request, policy=None):
        pctx = prior_build(request, policy)
        ui = request.get('userInfo') or {}
        if cluster.is_exception_tenant(ui.get('username', '')):
            # exception-holding tenants ride the host engine loop (the
            # placeholder exceptions match no policy, so every verdict
            # is unchanged — only the serving path shifts)
            pctx.exceptions = list(exc_docs)
        return pctx

    pc_builder.build = build
    recorder = provenance.recorder()
    prov_owned = recorder is None
    if prov_owned:
        recorder = provenance.configure(flight_n=max(
            16384, 2 * max(thread_counts) * requests_per_thread))
    blocks = []
    try:
        base = 0
        for n_threads in thread_counts:
            reviews = [cluster.review_bytes(base + k)
                       for k in range(n_threads * requests_per_thread)]
            base += len(reviews)
            batcher = handlers._get_batcher()
            batcher.reset_stats()
            if recorder is not None:
                recorder.reset()
            barrier = threading.Barrier(n_threads + 1)

            def work(tid, reviews=reviews):
                barrier.wait()
                for k in range(requests_per_thread):
                    server.handle(
                        '/validate/fail',
                        reviews[tid * requests_per_thread + k])

            threads = [threading.Thread(target=work, args=(tid,))
                       for tid in range(n_threads)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.time()
            for t in threads:
                t.join()
            elapsed = time.time() - t0
            stats = batcher.stats()
            decisions = n_threads * requests_per_thread
            blocks.append({
                'threads': n_threads,
                'decisions_per_s': round(decisions / elapsed, 1)
                if elapsed > 0 else 0.0,
                'batch_occupancy_mean': round(stats['occupancy_mean'],
                                              2),
                'batch_occupancy_p50': stats['occupancy_p50'],
                'hetero_dispatches': stats['hetero_dispatches'],
                'hetero_occupancy_mean': round(
                    stats['hetero_occupancy_mean'], 2),
                'queue_wait_p50_ms': round(stats['queue_wait_p50_ms'],
                                           3),
                'shed_total': stats['shed_total'],
                'device_served': device_served,
                'decision_breakdown': provenance.breakdown(),
            })
            _progress(
                f'admission hetero: {n_threads} threads -> '
                f"{blocks[-1]['decisions_per_s']}/s, occupancy mean "
                f"{blocks[-1]['batch_occupancy_mean']} "
                f"(hetero dispatches {blocks[-1]['hetero_dispatches']})")
        # batch-of-one baseline: the SAME heterogeneous traffic at the
        # top thread count with per-request dispatches (sync mode) —
        # what every mixed-tuple request paid before the batch key
        # collapsed to the policy set
        top = max(thread_counts)
        reviews = [cluster.review_bytes(base + k)
                   for k in range(top * requests_per_thread)]
        base += len(reviews)
        handlers.serving_mode = 'sync'
        try:
            barrier = threading.Barrier(top + 1)

            def sync_work(tid, reviews=reviews):
                barrier.wait()
                for k in range(requests_per_thread):
                    server.handle('/validate/fail',
                                  reviews[tid * requests_per_thread + k])

            threads = [threading.Thread(target=sync_work, args=(tid,))
                       for tid in range(top)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.time()
            for t in threads:
                t.join()
            sync_elapsed = time.time() - t0
        finally:
            handlers.serving_mode = 'batch'
        baseline = {
            'threads': top,
            'decisions_per_s': round(
                top * requests_per_thread / sync_elapsed, 1)
            if sync_elapsed > 0 else 0.0,
        }
        top_block = max(blocks, key=lambda b: b['threads'])
        baseline['batched_speedup'] = round(
            top_block['decisions_per_s'] / baseline['decisions_per_s'],
            2) if baseline['decisions_per_s'] else None
        _progress(f"admission hetero baseline (sync, {top} threads): "
                  f"{baseline['decisions_per_s']}/s -> batched speedup "
                  f"{baseline['batched_speedup']}x")
        # trickle anchor: one paced client must flush batches of one
        batcher = handlers._get_batcher()
        batcher.reset_stats()
        for delay, body in cluster.arrivals(40, pattern='trickle',
                                            rate_per_s=200.0,
                                            start=base):
            time.sleep(delay)
            server.handle('/validate/fail', body)
        tstats = batcher.stats()
        trickle = {
            'requests': 40,
            'batch_occupancy_p50': tstats['occupancy_p50'],
            'batch_occupancy_mean': round(tstats['occupancy_mean'], 2),
        }
        floor_block = max(blocks, key=lambda b: b['threads'])
        ratchet_checked = bool(device_served and
                               floor_block['threads'] >= 8)
        if ratchet_checked:
            occ = floor_block['batch_occupancy_mean']
            # THE RATCHET: heterogeneous coalescing must not regress to
            # batch-of-one
            if occ <= HET_OCCUPANCY_FLOOR:
                raise AssertionError(
                    f'heterogeneous mean batch occupancy {occ} at '
                    f"{floor_block['threads']} threads is at/below the "
                    f'committed floor {HET_OCCUPANCY_FLOOR}')
        return {'blocks': blocks, 'trickle': trickle,
                'batch_of_one_baseline': baseline,
                'generator': {'seed': cluster.seed,
                              'users': len(cluster.users),
                              'namespaces': len(cluster.namespaces),
                              'exception_tenants':
                                  len(cluster.exception_users)},
                'ratchet_floor': HET_OCCUPANCY_FLOOR,
                'ratchet_checked': ratchet_checked}
    finally:
        pc_builder.build = prior_build
        handlers.serving_mode = prior_mode
        if prov_owned:
            provenance.disable()


# --------------------------------------------------------------------------
# Chaos block: graceful degradation under injected faults.  Three
# synthetic-cluster waves run against the batch-mode serving chain with
# KTPU_FAULTS armed (marker-poisoned rows that kill any shared dispatch
# carrying them), bracketed by policy churn mid-stream, then a breaker
# drill trips the policy set's circuit and drives the open → half-open
# → closed round trip.  Every response is replayed against a fault-free
# sequential oracle: the committed ratchets are zero non-200s, verdict
# bit-identity, and shed(poison_row) == EXACTLY the injected poison
# rows (isolation, not batch-sized collateral).


def admission_chaos(ctx, threads: int = 6,
                    requests_per_thread: int = 8) -> dict:
    import copy
    import threading
    from kyverno_tpu import faults
    from kyverno_tpu.api.policy import Policy as _Policy
    from kyverno_tpu.conformance.loadgen import SyntheticCluster
    from kyverno_tpu.observability.metrics import global_registry
    from kyverno_tpu.policycache import cache as pcache
    from kyverno_tpu.serving import breaker as breaker_mod

    server, handlers, _n_replicated, device_served = ctx
    cluster = SyntheticCluster(seed=4321, poison_ratio=1 / 8)
    exc_docs = cluster.exception_docs()
    prior_mode = handlers.serving_mode
    handlers.serving_mode = 'batch'
    pc_builder = handlers.pc_builder
    prior_build = pc_builder.build

    def build(request, policy=None):
        pctx = prior_build(request, policy)
        ui = request.get('userInfo') or {}
        if cluster.is_exception_tenant(ui.get('username', '')):
            # exception churn: verdict-neutral placeholder exceptions
            # keep a tenant slice on the host loop mid-chaos
            pctx.exceptions = list(exc_docs)
        return pctx

    pc_builder.build = build
    total = threads * requests_per_thread
    batcher = handlers._get_batcher()
    ns0 = cluster.namespaces[0]

    def enforce_policies():
        return handlers.cache.get_policies(pcache.VALIDATE_ENFORCE,
                                           'Pod', ns0)

    def send(i):
        body, status = server.handle_request('/validate/fail',
                                             cluster.review_bytes(i))
        return status, json.loads(body.decode('utf-8')).get('response')

    def run_wave(start):
        out = [None] * total
        barrier = threading.Barrier(threads + 1)

        def work(tid):
            barrier.wait()
            # strided partition (thread tid serves k ≡ tid mod threads):
            # poison rows land mid-stream of several threads instead of
            # piling up as every thread's final request, so dispatches
            # mix poison with healthy riders the way real traffic does
            for j in range(requests_per_thread):
                k = tid + j * threads
                out[k] = send(start + k)

        workers = [threading.Thread(target=work, args=(tid,))
                   for tid in range(threads)]
        for t in workers:
            t.start()
        barrier.wait()
        for t in workers:
            t.join()
        return out

    def shed_counts():
        return dict(batcher.stats()['shed'])

    def check(name, got, start, expect_poison=None, before=None):
        non200 = sum(1 for s, _r in got if s != 200)
        mismatched = sum(1 for k, (_s, r) in enumerate(got)
                         if r != oracle[start + k])
        block = {'wave': name, 'requests': len(got), 'non_200': non200,
                 'verdict_mismatches': mismatched}
        if non200 > CHAOS_MAX_NON_200:
            raise AssertionError(
                f'chaos wave {name}: {non200} non-200 responses — '
                f'degradation must never surface as an error')
        if mismatched:
            raise AssertionError(
                f'chaos wave {name}: {mismatched} verdicts diverged '
                f'from the fault-free oracle')
        if expect_poison is not None:
            after = shed_counts()
            got_poison = after.get('poison_row', 0) - \
                before.get('poison_row', 0)
            block['poison_rows_injected'] = expect_poison
            block['poison_rows_shed'] = got_poison
            if got_poison != expect_poison:
                raise AssertionError(
                    f'chaos wave {name}: shed(poison_row)={got_poison} '
                    f'!= injected poison rows {expect_poison} — '
                    f'quarantine must isolate rows, not groups')
        result['waves'].append(block)
        _progress(f'chaos wave {name}: non_200={non200} '
                  f'mismatches={mismatched} '
                  + (f'poison {block["poison_rows_shed"]}/'
                     f'{expect_poison}' if expect_poison is not None
                     else ''))

    result: dict = {'device_served': device_served, 'waves': [],
                    'ratchet_checked': bool(device_served)}
    recovery_n = 8
    try:
        # fault-free oracle: same requests, sequential, no injection
        faults.disable()
        oracle = {}
        for i in range(3 * total + recovery_n):
            status, resp = send(i)
            if status != 200:
                raise AssertionError(
                    f'oracle request {i} returned HTTP {status}')
            oracle[i] = resp
        if not device_served:
            # without a compiled scanner nothing dispatches, so the
            # fault sites never arm: report, don't pretend
            return result

        # wave A: poison markers under concurrency
        faults.configure(cluster.fault_spec())
        before = shed_counts()
        got = run_wave(0)
        check('A:poison', got, 0,
              expect_poison=cluster.poison_count(total), before=before)

        # policy churn mid-stream: byte-identical docs re-put as fresh
        # Policy objects — new id()-tuple batch key, scanner rebuild
        # (the AOT content-hash cache serves the compile) — wave B
        # flows DURING the rebuild and host-serves without a single
        # non-200 or verdict change
        fresh = [_Policy(copy.deepcopy(p.raw))
                 for p in enforce_policies()]
        handlers.cache.warm_up(fresh)
        got = run_wave(total)
        check('B:churn', got, total)

        # wave C: rebuild settled, poison isolation must be exact again
        handlers.wait_device_ready(enforce_policies(), timeout=float(
            os.environ.get('BENCH_ADMISSION_WAIT_S', '90')))
        before = shed_counts()
        got = run_wave(2 * total)
        check('C:poison-after-churn', got, 2 * total,
              expect_poison=cluster.poison_count(total, start=2 * total),
              before=before)

        # breaker drill: six nth batcher_dispatch faults = three
        # dispatch failures (original + quarantine solo retry each),
        # tripping the set's breaker; requests then shed breaker_open;
        # after the backoff a single probe recovers the device path
        result['breaker'] = _chaos_breaker_drill(
            server, handlers, cluster, oracle, 3 * total,
            enforce_policies, breaker_mod, shed_counts, send,
            global_registry())
        return result
    finally:
        faults.disable()
        pc_builder.build = prior_build
        handlers.serving_mode = prior_mode


def _chaos_breaker_drill(server, handlers, cluster, oracle, base,
                         enforce_policies, breaker_mod, shed_counts,
                         send, registry) -> dict:
    from kyverno_tpu import faults
    policies = enforce_policies()
    key = handlers._policy_key(policies)
    handlers.wait_device_ready(policies, timeout=float(
        os.environ.get('BENCH_ADMISSION_WAIT_S', '90')))
    drill: dict = {'states': []}

    def note(stage):
        state = handlers._breakers.state(key)
        drill['states'].append({'stage': stage, 'state': state})
        return state

    # clean entry: one healthy dispatch pops any wave-residue breaker
    # entry and zeroes the consecutive-failure strike count, so the
    # drill's trip arithmetic starts from a known state
    i = base
    status, resp = send(i)
    if status != 200 or resp != oracle[i]:
        raise AssertionError('breaker drill warm-up request failed')
    i += 1
    if note('entry') != breaker_mod.CLOSED:
        raise AssertionError('breaker not closed entering the drill')
    # trip sequence: each request's dispatch fails twice (original +
    # quarantine solo retry) with a retry-exhausted error — wholesale
    # evidence, so every request counts ONE breaker failure; each
    # failure drops the scanner, so wait for the rebuild between
    # failures to keep the dispatches flowing.  Requests still answer
    # 200 with the oracle verdict via the host loop throughout.
    faults.configure(';'.join(
        f'site={faults.SITE_BATCHER_DISPATCH},nth={n},exhaust=1'
        for n in range(1, 2 * handlers.DEVICE_FAILURE_LIMIT + 1)))
    def breaker_failures():
        for row in handlers._breakers.report():
            if row['key'] == repr(key):
                return row['failures']
        return 0

    for k in range(handlers.DEVICE_FAILURE_LIMIT):
        status, resp = send(i)
        if status != 200 or resp != oracle[i]:
            raise AssertionError(
                f'breaker drill trip request {k} degraded wrong: '
                f'status={status}')
        i += 1
        # the rider sheds (and send() returns) before the batcher
        # thread delivers its failure verdict; the scanner pop happens
        # before the count ticks, so once the count reads k+1 the next
        # wait_device_ready is guaranteed to see the rebuild
        poll_deadline = time.time() + 10.0
        while breaker_failures() < k + 1 and time.time() < poll_deadline:
            time.sleep(0.01)
        if breaker_failures() < k + 1:
            raise AssertionError(
                f'breaker drill trip request {k} never recorded its '
                f'device failure')
        if k + 1 < handlers.DEVICE_FAILURE_LIMIT:
            handlers.wait_device_ready(policies, timeout=float(
                os.environ.get('BENCH_ADMISSION_WAIT_S', '90')))
    faults.disable()
    if note('tripped') != breaker_mod.OPEN:
        raise AssertionError(
            'three dispatch failures did not open the breaker')
    report = breaker_mod.debug_report()
    if not any(row['state'] == breaker_mod.OPEN
               for row in report['breakers']):
        raise AssertionError('/debug/breakers shows no open breaker '
                             'after the trip')
    if registry is not None:
        drill['open_gauge'] = registry.gauge_value(
            breaker_mod.BREAKER_STATE, state=breaker_mod.OPEN)
        if drill['open_gauge'] < 1:
            raise AssertionError('breaker_state{state="open"} gauge '
                                 'did not register the trip')
    # while open: requests shed breaker_open and host-serve
    before = shed_counts()
    status, resp = send(i)
    if status != 200 or resp != oracle[i]:
        raise AssertionError('open-breaker request degraded wrong')
    i += 1
    after = shed_counts()
    drill['breaker_open_sheds'] = after.get('breaker_open', 0) - \
        before.get('breaker_open', 0)
    if drill['breaker_open_sheds'] < 1:
        raise AssertionError('no breaker_open shed was recorded while '
                             'the breaker was open')
    # recovery: sleep past the backoff, let the half-open probe spawn
    # the rebuild, then ride it to a recorded success
    entry_backoff = max((row.get('reopens_in_s', 0.0)
                         for row in report['breakers']), default=0.0)
    time.sleep(entry_backoff + 0.1)
    status, resp = send(i)  # grants the probe; spawns the rebuild
    if status != 200 or resp != oracle[i]:
        raise AssertionError('half-open probe request degraded wrong')
    i += 1
    if not handlers.wait_device_ready(policies, timeout=float(
            os.environ.get('BENCH_ADMISSION_WAIT_S', '90'))):
        raise AssertionError('device path did not rebuild during the '
                             'half-open window')
    note('half_open')
    status, resp = send(i)  # the probe that closes the breaker
    if status != 200 or resp != oracle[i]:
        raise AssertionError('recovery request degraded wrong')
    i += 1
    deadline = time.time() + 10.0
    while handlers._breakers.state(key) != breaker_mod.CLOSED and \
            time.time() < deadline:
        time.sleep(0.02)
    if note('recovered') != breaker_mod.CLOSED:
        raise AssertionError(
            'probe success did not close the breaker (no recovery)')
    if registry is not None and registry.gauge_value(
            breaker_mod.BREAKER_STATE, state=breaker_mod.OPEN) > 0:
        raise AssertionError('breaker_state{state="open"} gauge still '
                             'non-zero after recovery')
    chain = ' -> '.join(s['state'] for s in drill['states'])
    _progress(f'chaos breaker drill: {chain}')
    return drill


# --------------------------------------------------------------------------
# Policy-churn serving bench: the partitioned-compilation claim
# (kyverno_tpu/partition/).  A mid-traffic edit of ONE policy in the
# replicated enforce set must (a) enforce the new text immediately (the
# host loop serves the updated set while the touched partition
# recompiles in the background), (b) recompile ONLY the touched
# partition — every other partition's evaluator is reused verbatim and
# the hot-swap carries breaker state — and (c) never surface as a
# non-200 or a shed(breaker_open), with post-churn verdicts
# bit-identical to a monolithic (KTPU_PARTITIONS=0) oracle rebuilt over
# the same policy set.


def admission_policy_churn(ctx, pods, threads: int = 4,
                           requests_per_thread: int = 24) -> dict:
    import copy as _copy
    import dataclasses
    import threading as _threading
    from kyverno_tpu.api.policy import Policy as _Policy
    from kyverno_tpu.conformance.loadgen import (SyntheticCluster,
                                                 apply_churn)
    from kyverno_tpu.observability.metrics import global_registry
    from kyverno_tpu.partition.plan import diff_plans
    from kyverno_tpu.policycache import cache as pcache

    server, handlers, _n_replicated, device_served = ctx
    reg = global_registry()
    result: dict = {'device_served': device_served,
                    'n_partitions_env': int(os.environ.get(
                        'KTPU_PARTITIONS', '0') or 0),
                    'ratchet_checked': bool(device_served)}
    if not device_served:
        # without a compiled scanner there is nothing to hot-swap;
        # report, don't pretend
        return result

    ns0 = pods[0]['metadata'].get('namespace', '')

    def enforce_policies():
        return handlers.cache.get_policies(pcache.VALIDATE_ENFORCE,
                                           'Pod', ns0)

    live = enforce_policies()
    old_scanner = handlers._device_scanner(live)
    if old_scanner is None or getattr(old_scanner, '_pset', None) is None:
        result['error'] = 'partitioned scanner not serving ' \
            '(KTPU_PARTITIONS unset or fallback tripped)'
        return result
    old_plan = old_scanner._pset.plan

    # probe: a pod that violates at least one live policy — the edit
    # targets that policy, so its marker is observable in denials
    probe_doc, target_idx = None, None
    for doc in pods[:16]:
        body = server.handle('/validate/fail',
                             _admission_review(doc, 'churn-probe'))
        resp = json.loads(body).get('response') or {}
        if resp.get('allowed') is False:
            msg = ((resp.get('status') or {}).get('message')) or ''
            hits = [i for i, p in enumerate(live)
                    if p.name and p.name in msg]
            if hits:
                # longest matching name wins: replicated names share
                # prefixes (-r1 is a substring of -r10)
                probe_doc = doc
                target_idx = max(hits, key=lambda i: len(live[i].name))
                break
    if probe_doc is None:
        raise AssertionError('policy churn: no probe pod is denied — '
                             'enforcement is unobservable')

    cluster = SyntheticCluster(seed=2026)
    total = threads * requests_per_thread
    event = cluster.churn_schedule(total, len(live))[0]
    # retarget the scheduled edit onto the violated policy: same tick,
    # same marker — the bench needs a target it can SEE enforced
    event = dataclasses.replace(event, policy_index=target_idx)
    result['churn_event'] = event.to_dict()
    new_raws = apply_churn([_copy.deepcopy(p.raw) for p in live], event)

    prior_mode = handlers.serving_mode
    handlers.serving_mode = 'batch'
    batcher = handlers._get_batcher()
    shed_before = dict(batcher.stats()['shed'])
    C = 'kyverno_tpu_compile_cache_requests_total'

    def counter(name, **labels):
        return reg.counter_value(name, **labels) if reg is not None \
            else 0.0

    miss0 = counter(C, result='miss')
    load0 = counter(C, result='aot_load')
    swaps0 = counter('kyverno_tpu_scanner_hot_swaps_total',
                     kind='validate')
    non200 = 0
    t_edit = t_enforce = None

    def send_raw(body_bytes):
        nonlocal non200
        body, status = server.handle_request('/validate/fail',
                                             body_bytes)
        if status != 200:
            non200 += 1
        return body

    try:
        # steady stream with the scheduled mid-burst edit: enforcement
        # flips the instant the cache re-warms (host loop serves the
        # new set while the touched partition recompiles behind it)
        for i in range(total):
            if i == event.tick:
                t_edit = time.time()
                handlers.cache.warm_up([_Policy(d) for d in new_raws])
            if t_edit is not None and t_enforce is None and i % 2:
                body = send_raw(_admission_review(probe_doc,
                                                  f'churn-p{i}'))
                if event.marker() in body.decode('utf-8', 'replace'):
                    t_enforce = time.time()
            else:
                send_raw(cluster.review_bytes(i))
        deadline = time.time() + 30.0
        while t_enforce is None and time.time() < deadline:
            body = send_raw(_admission_review(probe_doc, 'churn-late'))
            if event.marker() in body.decode('utf-8', 'replace'):
                t_enforce = time.time()
        if t_enforce is None:
            raise AssertionError('policy churn: edit never enforced '
                                 '(marker absent from denials)')
        # background hot-swap: the touched partition's recompile lands
        new_live = enforce_policies()
        swapped = handlers.wait_device_ready(new_live, timeout=float(
            os.environ.get('BENCH_ADMISSION_WAIT_S', '90')))
        t_swap = time.time()
        # concurrent wave on the swapped-in scanner: churn must not
        # surface as errors or breaker sheds under parallel load
        barrier = _threading.Barrier(threads + 1)

        def work(tid):
            barrier.wait()
            for j in range(requests_per_thread):
                send_raw(cluster.review_bytes(
                    total + tid + j * threads))

        workers = [_threading.Thread(target=work, args=(tid,))
                   for tid in range(threads)]
        for t in workers:
            t.start()
        barrier.wait()
        for t in workers:
            t.join()
    finally:
        handlers.serving_mode = prior_mode

    shed_after = dict(batcher.stats()['shed'])
    breaker_shed = shed_after.get('breaker_open', 0) - \
        shed_before.get('breaker_open', 0)
    fresh_executables = int(counter(C, result='miss') - miss0)
    new_scanner = handlers._device_scanner(new_live)
    if not swapped or new_scanner is None or \
            getattr(new_scanner, '_pset', None) is None:
        raise AssertionError('policy churn: hot-swap did not land a '
                             'partitioned scanner')
    diff = diff_plans(old_plan, new_scanner._pset.plan)
    recompiled = sorted(new_scanner._pset.recompiled())
    result.update({
        'requests': 2 * total, 'non_200': non200,
        'shed_breaker_open': breaker_shed,
        'enforcement_ms': round((t_enforce - t_edit) * 1000, 1),
        'device_swap_s': round(t_swap - t_edit, 2),
        'touched_partitions': sorted(diff.touched),
        'unchanged_partitions': len(diff.unchanged),
        'recompiled_partitions': recompiled,
        'fresh_executables': fresh_executables,
        'aot_loaded_executables': int(counter(C, result='aot_load')
                                      - load0),
        'ratchet_max_fresh_executables':
            CHURN_RECOMPILED_EXECUTABLES_MAX,
        'hot_swaps': int(counter('kyverno_tpu_scanner_hot_swaps_total',
                                 kind='validate') - swaps0),
    })
    if non200 > CHAOS_MAX_NON_200:
        raise AssertionError(
            f'policy churn: {non200} non-200 responses — churn must '
            f'never surface as an error')
    if breaker_shed:
        raise AssertionError(
            f'policy churn: {breaker_shed} requests shed breaker_open '
            f'— the hot-swap must never put churn on the shed path')
    if len(diff.touched) != 1:
        raise AssertionError(
            f'policy churn: one-policy edit touched partitions '
            f'{sorted(diff.touched)} — expected exactly one')
    if recompiled != sorted(diff.touched):
        raise AssertionError(
            f'policy churn: recompiled partitions {recompiled} != '
            f'differ touched set {sorted(diff.touched)} — untouched '
            f'evaluators must be reused verbatim')
    if fresh_executables > CHURN_RECOMPILED_EXECUTABLES_MAX:
        raise AssertionError(
            f'policy churn: {fresh_executables} fresh executables '
            f'(> committed max {CHURN_RECOMPILED_EXECUTABLES_MAX}) — '
            f'the one-partition recompile is not holding')
    _progress(f'policy churn: enforcement '
              f"{result['enforcement_ms']}ms, swap "
              f"{result['device_swap_s']}s, recompiled {recompiled} "
              f'of {len(new_scanner._pset.runtimes)} partitions')

    # monolithic oracle over the SAME post-churn set: partitioned
    # serving must be bit-identical, churn or not
    sample = [cluster.review_bytes(10000 + k) for k in range(48)]
    sample.append(_admission_review(probe_doc, 'oracle-probe'))
    part_resp = [json.loads(server.handle('/validate/fail', b)
                            ).get('response') for b in sample]
    saved_parts = os.environ.get('KTPU_PARTITIONS')
    os.environ['KTPU_PARTITIONS'] = '0'
    try:
        from kyverno_tpu.policycache.cache import Cache as _Cache
        from kyverno_tpu.webhooks.handlers import \
            ResourceHandlers as _Handlers
        from kyverno_tpu.webhooks.server import WebhookServer as _Server
        ocache = _Cache()
        ocache.warm_up([_Policy(_copy.deepcopy(d)) for d in new_raws])
        ohandlers = _Handlers(ocache)
        oserver = _Server(ohandlers)
        oracle_served = ohandlers.wait_device_ready(
            ocache.get_policies(pcache.VALIDATE_ENFORCE, 'Pod', ns0),
            timeout=float(os.environ.get('BENCH_ADMISSION_WAIT_S',
                                         '90')))
        mismatches = sum(
            1 for b, want in zip(sample, part_resp)
            if json.loads(oserver.handle('/validate/fail', b)
                          ).get('response') != want)
        ohandlers.shutdown()
    finally:
        if saved_parts is None:
            os.environ.pop('KTPU_PARTITIONS', None)
        else:
            os.environ['KTPU_PARTITIONS'] = saved_parts
    result['oracle_device_served'] = oracle_served
    result['oracle_mismatches'] = mismatches
    if mismatches:
        raise AssertionError(
            f'policy churn: {mismatches} verdicts diverged from the '
            f'monolithic (KTPU_PARTITIONS=0) oracle')
    return result


# --------------------------------------------------------------------------
# Rescan churn bench: the O(churn) claim for the digest-keyed verdict
# cache (kyverno_tpu/verdictcache/).  Steady state: every tick demands a
# full report rebuild over N rows of which only churn_ratio changed —
# rows scanned per tick must track the churn, not N.


class _NullReportClient:
    """Report sink for the churn bench: reconcile's cost should be the
    scan + cache work, not FakeClient CR bookkeeping over 100k rows."""

    def get_resource(self, *a, **k):
        raise KeyError('null client')

    def create_resource(self, api_version, kind, ns, obj):
        return obj

    def update_resource(self, api_version, kind, ns, obj):
        return obj

    def delete_resource(self, *a, **k):
        return None

    def list_resource(self, *a, **k):
        raise KeyError('null client')


def _churn_controller(policies, resources, cache_dir, enabled):
    from kyverno_tpu.reports.controllers import (BackgroundScanController,
                                                 MetadataCache)
    saved = {k: os.environ.get(k)
             for k in ('KTPU_VERDICT_CACHE', 'KTPU_VERDICT_CACHE_DIR')}
    os.environ['KTPU_VERDICT_CACHE'] = '1' if enabled else '0'
    os.environ['KTPU_VERDICT_CACHE_DIR'] = cache_dir
    try:
        ctrl = BackgroundScanController(_NullReportClient(), policies,
                                        cache=MetadataCache())
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    for r in resources:
        ctrl.cache.update(r)
    return ctrl


def run_rescan_churn(platform: str, n: Optional[int] = None,
                     ticks: Optional[int] = None,
                     ratio: Optional[float] = None) -> dict:
    """N-row steady state with ``ratio`` mutation per tick: every tick
    forgets resumability (the restart/report-rebuild demand), enqueues
    all N rows, and reconciles — the verdict cache replays unchanged
    rows and ships only changed digests to the device.  The dense
    baseline (``KTPU_VERDICT_CACHE=0``) scans all N rows per tick."""
    import random
    import statistics
    import tempfile

    n = int(os.environ.get('BENCH_RESCAN_N', '100000')) if n is None else n
    ticks = 5 if ticks is None else ticks
    ratio = 0.01 if ratio is None else ratio
    dense_ticks = min(ticks, int(os.environ.get(
        'BENCH_RESCAN_DENSE_TICKS', '1')))
    policies = load_policy_pack()
    rng = random.Random(23)
    resources = [make_pod(rng, i) for i in range(n)]
    cache_dir = tempfile.mkdtemp(prefix='ktpu-vcache-')

    def mutate(ctrl, tick):
        idx = rng.sample(range(n), max(1, int(n * ratio)))
        for i in idx:
            resources[i]['spec']['containers'][0]['image'] = \
                f'registry/churn:{tick}-{i}'
            ctrl.cache.update(resources[i])
        return len(idx)

    def run_ticks(ctrl, count):
        lat, scanned, replayed = [], [], []
        for t in range(count):
            mutate(ctrl, t)
            ctrl.reset_scan_state()
            ctrl.enqueue_all()
            t0 = time.time()
            ctrl.reconcile()
            lat.append(time.time() - t0)
            scanned.append(ctrl.rescan_stats['rows_scanned'])
            replayed.append(ctrl.rescan_stats['rows_replayed'])
            _progress(f'rescan tick {t}: scanned '
                      f'{scanned[-1]}/{scanned[-1] + replayed[-1]} rows '
                      f'in {lat[-1]:.2f}s')
        return lat, scanned, replayed

    def pctile(values, q):
        s = sorted(values)
        return round(s[min(len(s) - 1, int(len(s) * q))], 3)

    _progress(f'rescan churn bench: {n} rows, {ticks} ticks @ {ratio}')
    from kyverno_tpu.observability import timeline as _timeline
    if _timeline.recorder() is None:
        _timeline.configure()
    ctrl = _churn_controller(policies, resources, cache_dir, enabled=True)
    rss_before = _current_rss_mb()
    with RssSampler() as rss_sampler:
        t0 = time.time()
        ctrl.enqueue_all()
        ctrl.reconcile()  # cold tick: populate the cache
        cold_s = time.time() - t0
        blame_before = _timeline.blame_totals()  # delta = cached ticks
        lat, scanned, replayed = run_ticks(ctrl, ticks)
    total = [s + r for s, r in zip(scanned, replayed)]
    scanned_ratio = sum(scanned) / max(sum(total), 1)
    # the fake client retains every written report, so rescan growth is
    # O(reports) by design — the ratchet still bounds regression toward
    # re-materializing all N decoded rows per tick
    rss_block = rss_sampler.block(rss_before, n)
    # blame the cached ticks only — snapshot before the dense baseline
    cp_block = _critical_path_block(blame_before, sum(lat),
                                    trace_name='rescan')

    _progress(f'rescan dense baseline: {dense_ticks} tick(s)')
    dense = _churn_controller(policies, resources, cache_dir,
                              enabled=False)
    dense.enqueue_all()
    dense.reconcile()  # cold tick: warm jit shapes like the cached run
    dense_lat, _ds, _dr = run_ticks(dense, dense_ticks)

    block = {
        'n_rows': n, 'churn_ticks': ticks, 'churn_ratio': ratio,
        'platform': platform,
        'rss': rss_block,
        'rows_scanned_per_tick': scanned,
        'rows_replayed_per_tick': replayed,
        'scanned_rows_ratio': round(scanned_ratio, 4),
        'tick_p50_s': pctile(lat, 0.50),
        'tick_p95_s': pctile(lat, 0.95),
        'cold_tick_s': round(cold_s, 2),
        'dense_tick_p50_s': pctile(dense_lat, 0.50),
        'speedup_vs_dense': round(
            statistics.median(dense_lat) / max(statistics.median(lat),
                                               1e-9), 2),
        'cache': dict(ctrl.verdict_cache.stats())
        if ctrl.verdict_cache is not None else None,
        'critical_path': cp_block,
    }
    from kyverno_tpu.observability import device as device_telemetry
    reg = device_telemetry.registry()
    if reg is not None:
        from kyverno_tpu.verdictcache import (VERDICT_CACHE_EVICTIONS,
                                              VERDICT_CACHE_HITS,
                                              VERDICT_CACHE_MISSES)
        block['hits'] = int(reg.counter_value(VERDICT_CACHE_HITS))
        block['misses'] = int(reg.counter_value(VERDICT_CACHE_MISSES))
        block['evictions'] = int(reg.counter_value(VERDICT_CACHE_EVICTIONS))
    return block


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
    import json as _json
    from kyverno_tpu.engine.api import PolicyContext
    pctx = PolicyContext(None, new_resource=_json.loads(_json.dumps(pod)))
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
    if _json.dumps(patched, sort_keys=True) != \
            _json.dumps(pctx.new_resource, sort_keys=True):
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


def run_mutate_bench(n: int, platform: str) -> dict:
    """``bench.py --mutate-pack``: the device-side mutate ratchet.

    Scans ``n`` pods through the compiled mutate edit-list path with
    the host engine chain as the byte-identity oracle on a sample,
    drives the /mutate webhook with concurrent batch-mode clients
    (occupancy must exceed 1 — mutate requests coalesce), and asserts
    ``device_coverage_ratio`` over the mutate rows never regresses
    below ``MUTATE_DEVICE_RATIO_FLOOR``."""
    import json as _json
    import random
    import threading
    from kyverno_tpu.engine.api import PolicyContext
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.mutate import MutateScanner
    from kyverno_tpu.observability import coverage as coverage_ledger

    policies = load_mutate_pack()
    rng = random.Random(7)
    pods = [make_mutate_pod(rng, i) for i in range(n)]
    scanner = MutateScanner(policies)
    if not scanner.ok:
        raise AssertionError(
            'mutate pack failed to lower: '
            + '; '.join(f'{p.policy}/{p.rule}: {p.reason}'
                        for p in scanner.program.placements
                        if p.reason))
    t0 = time.time()
    rows = scanner.scan([dict(p) for p in pods])
    scan_s = time.time() - t0

    # host-oracle: the engine's cumulative chain, byte for byte
    engine = Engine()
    sample = rng.sample(range(n), min(64, n))
    for i in sample:
        check_mutate_row(engine, policies, pods[i], rows[i], f'row {i}')
    _progress(f'mutate oracle: {len(sample)} rows byte-identical')

    # concurrent /mutate webhook drive: batch serving must coalesce
    from kyverno_tpu.policycache.cache import Cache
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer
    from kyverno_tpu.policycache import cache as pcache
    cache = Cache()
    cache.warm_up(policies)
    handlers = ResourceHandlers(cache, serving_mode='batch')
    server = WebhookServer(handlers)
    mut_policies = cache.get_policies(pcache.MUTATE, 'Pod', 'ns-0')
    deadline = time.time() + float(
        os.environ.get('BENCH_ADMISSION_WAIT_S', '90'))
    msc = None
    while time.time() < deadline:
        msc = handlers._device_scanner(mut_policies, kind='mutate')
        if msc is not None:
            break
        time.sleep(0.05)
    device_served = bool(msc is not None and msc.ok)
    n_threads, per_thread = 8, 8
    barrier = threading.Barrier(n_threads)
    statuses: List[int] = []

    def work(tid):
        barrier.wait()
        for k in range(per_thread):
            doc = pods[(tid * per_thread + k) % len(pods)]
            review = _json.loads(_admission_review(doc, f'm{tid}-{k}'))
            review['request']['namespace'] = \
                doc['metadata'].get('namespace', '')
            _out, status = server.handle_request(
                '/mutate', _json.dumps(review).encode())
            statuses.append(status)

    threads = [threading.Thread(target=work, args=(tid,))
               for tid in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    stats = handlers._get_batcher().stats()
    handlers.shutdown()
    if any(s != 200 for s in statuses):
        raise AssertionError(f'non-200 mutate responses: {statuses}')

    cov = coverage_ledger.bench_block() or {}
    ledger = coverage_ledger.ledger()
    mutate_device = mutate_host = 0
    if ledger is not None:
        for rec in ledger.report()['rules']:
            if rec['path'] == 'mutate':
                mutate_device += rec['device_rows']
                mutate_host += rec['host_rows']
    mutate_rows = mutate_device + mutate_host
    ratio = (mutate_device / mutate_rows) if mutate_rows else 0.0
    # THE RATCHET: device coverage of mutate rows must not regress
    if ratio < MUTATE_DEVICE_RATIO_FLOOR:
        raise AssertionError(
            f'mutate device_coverage_ratio {ratio:.4f} regressed below '
            f'the committed floor {MUTATE_DEVICE_RATIO_FLOOR}')
    return {
        'metric': 'mutate_device_scan_rows_per_sec',
        'value': round(n / scan_s, 1) if scan_s > 0 else 0.0,
        'unit': 'rows/s', 'platform': platform, 'n': n,
        'n_policies': len(policies),
        'oracle_rows': len(sample),
        'mutate_webhook': {
            'device_served': device_served,
            'batch_occupancy_mean': round(stats['occupancy_mean'], 2),
            'batch_occupancy_p50': stats['occupancy_p50'],
            'shed_total': stats['shed_total'],
            'requests': stats['requests'],
        },
        'coverage': dict(
            cov, mutate_rows=mutate_rows,
            mutate_device_rows=mutate_device,
            mutate_host_rows=mutate_host,
            mutate_device_coverage_ratio=round(ratio, 4),
            ratchet_floor=MUTATE_DEVICE_RATIO_FLOOR),
    }


def mutate_bench_main(platform: str) -> int:
    """``bench.py --mutate-pack [N]``: run only the device-side mutate
    ratchet (CI-sized; BENCH_MUTATE_N rows, default 2000)."""
    n = int(os.environ.get('BENCH_MUTATE_N', '2000'))
    result = run_mutate_bench(n, platform)
    print(json.dumps(result))
    return 0


def rescan_churn_main(platform: str, args: List[str]) -> int:
    """``bench.py --churn-ticks N [--churn-ratio R]``: run only the
    rescan churn bench (full scale: BENCH_RESCAN_N rows, default
    100k)."""
    def flag(name, cast, default):
        if name in args:
            return cast(args[args.index(name) + 1])
        return default
    block = run_rescan_churn(platform,
                             ticks=flag('--churn-ticks', int, 5),
                             ratio=flag('--churn-ratio', float, 0.01))
    print(json.dumps({'metric': 'rescan_churn', 'platform': platform,
                      'rescan': block}))
    return 0


def admission_concurrency_main(platform: str) -> int:
    """``bench.py --admission-concurrency``: run only the
    concurrent-admission serving block (CI-sized; scale the policy set
    with BENCH_ADMISSION_POLICIES, threads with
    BENCH_ADMISSION_THREADS)."""
    import random
    policies = load_policy_pack()
    rng = random.Random(42)
    pods = [make_pod(rng, i) for i in range(256)]
    target = int(os.environ.get('BENCH_ADMISSION_POLICIES', '1000'))
    _progress(f'admission serving chain @{target} policies')
    ctx = _admission_server(policies, pods, target_policies=target)
    blocks = admission_concurrency(ctx, pods)
    _progress('heterogeneous admission (synthetic cluster load)')
    hetero = admission_heterogeneous(ctx)
    ctx[1].shutdown()
    print(json.dumps({
        'metric': 'admission_concurrency', 'platform': platform,
        'n_policies': ctx[2], 'device_served': ctx[3],
        'admission_concurrency': blocks,
        'admission_heterogeneous': hetero,
    }))
    return 0


def policy_churn_main(platform: str) -> int:
    """``bench.py --policy-churn``: mid-traffic one-policy edit against
    the partitioned serving chain — survive policy churn without
    recompiling the world (CI-sized; scale the policy set with
    BENCH_CHURN_POLICIES, the plan with KTPU_PARTITIONS)."""
    import random
    os.environ.setdefault('KTPU_PARTITIONS', '8')
    policies = load_policy_pack()
    rng = random.Random(42)
    pods = [make_pod(rng, i) for i in range(256)]
    target = int(os.environ.get('BENCH_CHURN_POLICIES', '200'))
    _progress(f'policy-churn serving chain @{target} policies, '
              f"KTPU_PARTITIONS={os.environ['KTPU_PARTITIONS']}")
    ctx = _admission_server(policies, pods, target_policies=target)
    block = admission_policy_churn(ctx, pods)
    ctx[1].shutdown()
    print(json.dumps({
        'metric': 'policy_churn', 'platform': platform,
        'n_policies': ctx[2], 'device_served': ctx[3],
        'policy_churn': block,
    }))
    return 0


def admission_chaos_main(platform: str) -> int:
    """``bench.py --admission-chaos``: run only the chaos block —
    synthetic-cluster waves under injected faults plus the breaker
    round-trip drill (CI-sized; scale the policy set with
    BENCH_CHAOS_POLICIES)."""
    import random
    # CI-sized breaker backoff: the drill sleeps through one open
    # window on purpose, so the default 1s base would dominate the
    # bench wall clock; explicit env still wins
    os.environ.setdefault('KTPU_BREAKER_BACKOFF_MS', '300')
    policies = load_policy_pack()
    rng = random.Random(42)
    pods = [make_pod(rng, i) for i in range(256)]
    target = int(os.environ.get('BENCH_CHAOS_POLICIES', '200'))
    _progress(f'admission chaos chain @{target} policies')
    ctx = _admission_server(policies, pods, target_policies=target)
    block = admission_chaos(ctx)
    ctx[1].shutdown()
    print(json.dumps({
        'metric': 'admission_chaos', 'platform': platform,
        'n_policies': ctx[2], 'device_served': ctx[3],
        'admission_chaos': block,
    }))
    return 0


# -- multichip mesh bench (bench.py --multichip) ------------------------------

#: THE RATCHET: windowed mean shard skew (max-shard wall / mean-shard
#: wall, averaged over the analyzer window) on a real multi-device run
#: must stay under this — a fleet whose slowest chip runs at half the
#: mean is losing that capacity on every step.  The forced-CPU mesh
#: (8 virtual devices on one host) walks its shard waits serially, so
#: shard 0 absorbs the whole compute wall and the ratio is meaningless
#: there; the ratchet only arms off the forced-CPU path.  The measured
#: value is always recorded.
MESH_SKEW_RATIO_MAX = float(os.environ.get('MESH_SKEW_RATIO_MAX', '1.5'))

#: rows per mesh step in the multichip block
MULTICHIP_ROWS = int(os.environ.get('BENCH_MULTICHIP_N', '1024'))
MULTICHIP_STEPS = int(os.environ.get('BENCH_MULTICHIP_STEPS', '3'))

MULTICHIP_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             'MULTICHIP_r06.json')


def _fleet_child(path: str, rows: int) -> None:
    """One federation 'host': run a small mesh workload under its own
    fleet registry and leave a JSONL snapshot behind.  Top-level so
    multiprocessing spawn can import it from a fresh interpreter.
    Always on the CPU backend: the round trip is about merging metrics,
    and the parent may hold the chip."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import random
    from kyverno_tpu.api.policy import load_policies_from_yaml
    from kyverno_tpu.compiler.compile import compile_policies
    from kyverno_tpu.observability import fleet
    from kyverno_tpu.observability.metrics import MetricsRegistry
    from kyverno_tpu.parallel.mesh import distributed_scan_step, make_mesh
    reg = MetricsRegistry()
    # no auto-profile in the drill child: the capture thread holds the
    # jax profiler across interpreter teardown
    fleet.configure(reg, window=2, profile_trigger=lambda: None)
    cps = compile_policies(load_policies_from_yaml(PACK))
    mesh = make_mesh()
    rng = random.Random(os.getpid())
    pods = [make_pod(rng, i) for i in range(rows)]
    for _ in range(2):
        distributed_scan_step(cps, mesh, pods)
    fleet.write_snapshot(path, reg)
    # skip interpreter teardown: the spawned XLA CPU client segfaults
    # in its destructor and the snapshot is already on disk
    os._exit(0)


def _federation_roundtrip(tmpdir: str) -> dict:
    """Spawn two single-host processes, merge their JSONL snapshots
    offline, and check the merge is lossless: every counter's merged
    total equals the sum of the per-host totals."""
    import multiprocessing as mp
    from kyverno_tpu.observability import fleet
    paths = [os.path.join(tmpdir, f'bench_host{i}.jsonl')
             for i in range(2)]
    for p in paths:
        if os.path.exists(p):
            os.remove(p)
    ctx = mp.get_context('spawn')
    procs = [ctx.Process(target=_fleet_child, args=(p, 64))
             for p in paths]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=240)
    rcs = [p.exitcode for p in procs]
    docs = fleet.read_snapshot_files([p for p in paths
                                      if os.path.exists(p)])
    merged = fleet.FleetRegistry.merge(docs)
    merged_totals = fleet.FleetRegistry.counter_totals(merged)
    per_host = [fleet.FleetRegistry.counter_totals(d) for d in docs]
    names = sorted({n for t in per_host for n in t})
    lossless = len(docs) == 2 and all(
        abs(sum(t.get(n, 0.0) for t in per_host)
            - merged_totals.get(n, 0.0)) <= 1e-9 * max(
                1.0, abs(merged_totals.get(n, 0.0)))
        for n in names)
    return {
        'hosts': len(docs), 'child_exitcodes': rcs,
        'counters_checked': len(names), 'lossless': bool(lossless),
        'merged_counter_totals': {n: merged_totals.get(n, 0.0)
                                  for n in names},
    }


def multichip_main() -> int:
    """``bench.py --multichip``: the mesh block — decisions/s vs device
    count, per-shard skew + straggler verdict, collective share,
    padding waste, and the two-process federation round-trip; written
    to MULTICHIP_r06.json (replacing the dryrun-only r01–r05 series)."""
    platform = os.environ.get('BENCH_PLATFORM') or probe_platform()
    forced_cpu = platform == 'cpu'
    if forced_cpu:
        # 8 virtual CPU devices — must land before backend init
        flags = os.environ.get('XLA_FLAGS', '')
        if '--xla_force_host_platform_device_count' not in flags:
            os.environ['XLA_FLAGS'] = (
                flags + ' --xla_force_host_platform_device_count=8').strip()
        os.environ['JAX_PLATFORMS'] = 'cpu'
    import random
    import jax
    from kyverno_tpu.observability import fleet
    from kyverno_tpu.observability.metrics import MetricsRegistry
    from kyverno_tpu.parallel.mesh import distributed_scan_step, make_mesh
    devices = jax.devices()
    rng = random.Random(7)
    pods = [make_pod(rng, i) for i in range(MULTICHIP_ROWS)]
    # each mesh size is its own compile, so the default pack is the
    # small self-contained one; BENCH_MULTICHIP_PACK=full opts into the
    # whole committed pack
    if os.environ.get('BENCH_MULTICHIP_PACK', '') == 'full':
        policies = load_policy_pack()
    else:
        from kyverno_tpu.api.policy import load_policies_from_yaml
        policies = load_policies_from_yaml(PACK)
    from kyverno_tpu.compiler.compile import compile_policies
    cps = compile_policies(policies)
    scaling = []
    verdict = None
    collective_share = 0.0
    padding_rows = 0.0
    counts = [k for k in (1, 2, 4, 8) if k <= len(devices)]
    for k in counts:
        reg = MetricsRegistry()
        # forced-CPU meshes sustain 'skew' by construction (shard 0
        # absorbs the serial compute) — a real auto-profile capture
        # here would sample for seconds inside the timed loop
        fleet.configure(reg, window=max(2, MULTICHIP_STEPS),
                        profile_trigger=lambda: None)
        mesh = make_mesh(devices[:k])
        _progress(f'multichip: mesh data{k} warmup')
        distributed_scan_step(cps, mesh, pods)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(MULTICHIP_STEPS):
            distributed_scan_step(cps, mesh, pods)
        wall = time.perf_counter() - t0
        per_s = MULTICHIP_ROWS * MULTICHIP_STEPS * len(cps.programs) / wall
        snap = reg.snapshot(fleet.identity())
        totals = fleet.FleetRegistry.counter_totals(snap)
        coll = totals.get(fleet.MESH_COLLECTIVE_SECONDS, 0.0)
        scaling.append({
            'n_devices': k, 'rows': MULTICHIP_ROWS,
            'steps': MULTICHIP_STEPS,
            'decisions_per_s': round(per_s, 1),
            'wall_s': round(wall, 4),
            'collective_share': round(coll / wall, 4) if wall else 0.0,
        })
        analyzer = fleet.analyzer()
        if k == counts[-1] and analyzer is not None:
            verdict = analyzer.verdict()
            collective_share = round(coll / wall, 4) if wall else 0.0
            padding_rows = totals.get(fleet.MESH_PADDING_ROWS, 0.0)
    fed_dir = os.path.join(os.path.dirname(MULTICHIP_OUT), '.cache',
                           'fleet')
    os.makedirs(fed_dir, exist_ok=True)
    federation = _federation_roundtrip(fed_dir)
    skew = float((verdict or {}).get('window_mean_skew', 1.0))
    armed = not forced_cpu and len(devices) > 1
    ok = federation['lossless'] and \
        (not armed or skew <= MESH_SKEW_RATIO_MAX)
    result = {
        'metric': 'multichip_mesh',
        'platform': platform,
        'forced_cpu_mesh': forced_cpu,
        'n_devices': len(devices),
        'mesh': {
            'scaling': scaling,
            'skew': verdict,
            'window_mean_skew': skew,
            'collective_share': collective_share,
            'padding_rows_total': padding_rows,
            'federation': federation,
        },
        'ratchet': {
            'mesh_skew_ratio_max': MESH_SKEW_RATIO_MAX,
            'armed': armed,
            'measured': skew,
        },
        'ok': bool(ok),
    }
    with open(MULTICHIP_OUT, 'w') as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write('\n')
    print(json.dumps(result))
    return 0 if ok else 1


def main() -> int:
    # --multichip runs before any backend / telemetry setup: the forced
    # 8-virtual-device XLA_FLAGS must land before jax initializes
    if '--multichip' in sys.argv[1:]:
        try:
            return multichip_main()
        except Exception as e:  # noqa: BLE001 - always emit a JSON line
            import traceback
            traceback.print_exc()
            print(json.dumps({'metric': 'multichip_mesh',
                              'error': f'{type(e).__name__}: {e}'}))
            return 1
    # the BASELINE.md north star is a 1M-Pod background scan; BENCH_N
    # caps the pods, BENCH_BUDGET_S caps the measured streaming time —
    # whichever hits first ends the run, so the bench ALWAYS finishes
    # and reports the N it actually processed (no silent extrapolation)
    n = int(os.environ.get('BENCH_N', '1000000'))
    budget_s = float(os.environ.get('BENCH_BUDGET_S', '150'))
    t_start = time.time()
    platform = os.environ.get('BENCH_PLATFORM') or probe_platform()
    if platform == 'cpu':
        os.environ.setdefault('JAX_PLATFORMS', 'cpu')
        import jax
        jax.config.update('jax_platforms', 'cpu')
    # device-pipeline telemetry: per-stage histograms feed the
    # stage_breakdown block of the JSON line; BENCH_TRACE_JSONL=<path>
    # additionally streams every stage span as OTLP-shaped JSON lines
    from kyverno_tpu.observability import coverage as coverage_ledger
    from kyverno_tpu.observability import device as device_telemetry
    from kyverno_tpu.observability import tracing as _tracing
    jsonl_path = os.environ.get('BENCH_TRACE_JSONL', '')
    if jsonl_path:
        _tracing.configure(memory=False, jsonl_path=jsonl_path)
    reg = device_telemetry.configure()
    # the verdict cache (and the AOT store gauges) emit through the
    # process-global registry the daemons wire in cmd/internal.Setup —
    # point it at the bench registry so those series land in the blocks
    from kyverno_tpu.observability.metrics import (global_registry,
                                                   set_global_registry)
    if global_registry() is None:
        set_global_registry(reg)
    # device-coverage ledger: the `coverage` block below tracks how much
    # of the measured traffic actually ran on device (and why the rest
    # fell back) alongside the latency numbers
    coverage_ledger.configure(reg)
    if '--admission-concurrency' in sys.argv[1:]:
        try:
            return admission_concurrency_main(platform)
        except Exception as e:  # noqa: BLE001 - always emit a JSON line
            import traceback
            traceback.print_exc()
            print(json.dumps({
                'metric': 'admission_concurrency', 'platform': platform,
                'error': f'{type(e).__name__}: {e}'}))
            return 1
    if '--admission-chaos' in sys.argv[1:]:
        try:
            return admission_chaos_main(platform)
        except Exception as e:  # noqa: BLE001 - always emit a JSON line
            import traceback
            traceback.print_exc()
            print(json.dumps({
                'metric': 'admission_chaos', 'platform': platform,
                'error': f'{type(e).__name__}: {e}'}))
            return 1
    if '--policy-churn' in sys.argv[1:]:
        try:
            return policy_churn_main(platform)
        except Exception as e:  # noqa: BLE001 - always emit a JSON line
            import traceback
            traceback.print_exc()
            print(json.dumps({
                'metric': 'policy_churn', 'platform': platform,
                'error': f'{type(e).__name__}: {e}'}))
            return 1
    if '--warm-probe' in sys.argv[1:]:
        # standalone warm block: fresh-process time-to-first-decision +
        # executable census with the WARM_EXECUTABLES_MAX ratchet
        try:
            print(json.dumps(dict(warm_probe(platform),
                                  metric='warm_probe',
                                  platform=platform)))
            return 0
        except Exception as e:  # noqa: BLE001 - always emit a JSON line
            import traceback
            traceback.print_exc()
            print(json.dumps({'metric': 'warm_probe',
                              'platform': platform,
                              'error': f'{type(e).__name__}: {e}'}))
            return 1
    if '--mutate-pack' in sys.argv[1:]:
        try:
            return mutate_bench_main(platform)
        except Exception as e:  # noqa: BLE001 - always emit a JSON line
            import traceback
            traceback.print_exc()
            print(json.dumps({
                'metric': 'mutate_device_scan_rows_per_sec',
                'platform': platform,
                'error': f'{type(e).__name__}: {e}'}))
            return 1
    if '--churn-ticks' in sys.argv[1:] or '--churn-ratio' in sys.argv[1:]:
        try:
            return rescan_churn_main(platform, sys.argv[1:])
        except Exception as e:  # noqa: BLE001 - always emit a JSON line
            import traceback
            traceback.print_exc()
            print(json.dumps({
                'metric': 'rescan_churn', 'platform': platform,
                'error': f'{type(e).__name__}: {e}'}))
            return 1
    # BENCH_CONFIG=4|5 runs the scaled BASELINE configs; default is the
    # north-star background scan
    config = os.environ.get('BENCH_CONFIG', '')
    try:
        if config == '4':
            result = run_config4(min(n, 50_000), platform)
        elif config == '5':
            result = run_config5(min(n, 20_000), platform)
        else:
            result = run_bench(n, platform, budget_s)
        result['stage_breakdown'] = device_telemetry.stage_breakdown()
        # per-stage overlap ratio (streaming busy-time ÷ streaming
        # wall) measured over the headline window: >1 total means the
        # pipeline legs genuinely ran concurrently
        for stage, ratio in (result.get('streaming_overlap') or {}).items():
            if stage == '_total':
                result['stage_breakdown']['_overall'] = {
                    'overlap_ratio': ratio}
            elif stage in result['stage_breakdown']:
                result['stage_breakdown'][stage]['overlap_ratio'] = ratio
        # executable-cache outcomes + persisted AOT store state: warm_s
        # regressions are diagnosable from the JSON line alone (was the
        # store cold, disabled, or bypassed?)
        reg = device_telemetry.registry()
        if reg is not None:
            from kyverno_tpu.aotcache import default_store
            counter = 'kyverno_tpu_compile_cache_requests_total'
            result['compile_cache'] = {
                r: int(reg.counter_value(counter, result=r))
                for r in ('hit', 'miss', 'aot_load', 'aot_store')}
            result['aot_store'] = dict(default_store().stats(),
                                       enabled=default_store().enabled)
        cov = coverage_ledger.bench_block()
        if cov is not None:
            # ledger invariant: every evaluated row is attributed to
            # exactly one side.  A mis-attributed fallback site (a host
            # branch that forgot to record) fails the bench run here
            # instead of silently skewing the coverage trajectory.
            if cov['device_rows'] + cov['host_rows'] != cov['total_rows']:
                raise AssertionError(
                    'coverage ledger out of balance: '
                    f"device_rows={cov['device_rows']} + "
                    f"host_rows={cov['host_rows']} != "
                    f"total_rows={cov['total_rows']} — a fallback site "
                    'is unattributed')
            result['coverage'] = cov
    except Exception as e:  # noqa: BLE001 - always emit a JSON line
        import traceback
        traceback.print_exc()
        print(json.dumps({
            'metric': 'bg_scan_decisions_per_sec_per_chip', 'value': 0,
            'unit': 'decisions/s', 'vs_baseline': 0.0,
            'platform': platform, 'error': f'{type(e).__name__}: {e}'}))
        return 1
    result['total_wall_s'] = round(time.time() - t_start, 1)
    print(json.dumps(result))
    return 0


if __name__ == '__main__':
    sys.exit(main())
