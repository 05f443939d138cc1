"""Ragged canonical batch shapes (ISSUE 9).

Mask-boundary correctness: row counts {1, capacity-1, capacity,
capacity+1 (spill)} must be bit-identical to the dense host oracle
across validate AND mutate; padding rows must be invisible to every
cross-row consumer (compact fail-detail selection, mesh verdict
summary, mutate edit bitmasks).  Plus: the canonical capacity table
itself, AOT load-rejection accounting, and the second-process probe
asserting a fresh scan across row counts loads ≤ 2 executables per
policy set.  CPU-only, tier-1.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from kyverno_tpu.api.policy import Policy
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.compiler.shapes import (canonical_capacity, canonical_caps,
                                         small_capacity)
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.engine import Engine
from kyverno_tpu.observability.metrics import (MetricsRegistry,
                                               set_global_registry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def policy(name, rule):
    return Policy({'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
                   'metadata': {'name': name, 'annotations': {
                       'pod-policies.kyverno.io/autogen-controllers':
                           'none'}},
                   'spec': {'rules': [rule]}})


def validate_pack():
    return [
        policy('require-app', {
            'name': 'check-app',
            'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
            'validate': {'message': 'app label required',
                         'pattern': {'metadata': {
                             'labels': {'app': '?*'}}}}}),
        policy('limit-replicas', {
            'name': 'max-containers',
            'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
            'validate': {
                'message': 'too many containers',
                'deny': {'conditions': {'any': [
                    {'key': '{{ length(request.object.spec.containers) }}',
                     'operator': 'GreaterThan', 'value': 3}]}}}}),
    ]


def pod(i):
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': f'p{i}', 'namespace': 'default',
                         'labels': {'app': f'a{i}'} if i % 3 else {}},
            'spec': {'containers': [
                {'name': f'c{k}', 'image': 'nginx:1'}
                for k in range(1 + i % 4)]}}


# ---------------------------------------------------------------------------
# the canonical capacity table


class TestShapeTable:
    def test_default_table_is_small_and_chunk(self, monkeypatch):
        for name in ('KTPU_CANONICAL_CAPS', 'KTPU_SCAN_CHUNK',
                     'KTPU_SMALL_BATCH'):
            monkeypatch.delenv(name, raising=False)
        assert canonical_caps() == (64, 8192)
        assert (BatchScanner.SMALL_BATCH, BatchScanner.CHUNK) == (64, 8192)
        caps = canonical_caps(chunk=8192, small=64)
        assert caps == (64, 8192)
        assert canonical_capacity(1, chunk=8192, small=64) == 64
        assert canonical_capacity(64, chunk=8192, small=64) == 64
        assert canonical_capacity(65, chunk=8192, small=64) == 8192
        # spill: the top entry also serves row counts beyond it
        # (callers chunk above it)
        assert canonical_capacity(99999, chunk=8192, small=64) == 8192

    def test_env_override_is_the_whole_table(self, monkeypatch):
        monkeypatch.setenv('KTPU_CANONICAL_CAPS', '32, 512,4096')
        assert canonical_caps() == (32, 512, 4096)
        assert canonical_capacity(33) == 512
        monkeypatch.setenv('KTPU_CANONICAL_CAPS', 'bogus')
        assert canonical_caps(chunk=128, small=8) == (8, 128)

    def test_small_capacity(self):
        assert small_capacity(small=16) == 16

    def test_batcher_default_max_is_small_capacity(self, monkeypatch):
        monkeypatch.delenv('KTPU_BATCH_MAX', raising=False)
        from kyverno_tpu.serving.batcher import AdmissionBatcher
        b = AdmissionBatcher(window_ms=1, queue_cap=4)
        try:
            assert b.max_batch == small_capacity()
        finally:
            b.stop(drain=False, timeout=5)


# ---------------------------------------------------------------------------
# encoder row-validity lane


class TestRowValidLane:
    def test_rowvalid_marks_capacity_padding(self):
        from kyverno_tpu.compiler.encode import encode_batch
        scanner = BatchScanner(validate_pack())
        cap = canonical_capacity(3, chunk=scanner.CHUNK,
                                 small=scanner.SMALL_BATCH)
        batch = encode_batch([pod(i) for i in range(3)], scanner.cps,
                             padded_n=cap)
        t = batch.tensors()
        rv = t['__rowvalid__']
        assert rv.shape == (cap,)
        assert rv[:3].all() and not rv[3:].any()

    def test_mutate_valid_lane_and_kernel_mask(self):
        from kyverno_tpu.mutate import MutateScanner
        from kyverno_tpu.mutate.encode import encode_mutate_batch
        from kyverno_tpu.mutate.kernel import MUT_SKIP, MutateKernel
        pol = policy('add-label', {
            'name': 'r',
            'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
            'mutate': {'patchStrategicMerge': {
                'metadata': {'labels': {'team': 'x'}}}}})
        scanner = MutateScanner([pol])
        assert scanner.ok
        cap = canonical_capacity(2)
        lanes = encode_mutate_batch([pod(0), pod(1)], scanner.program,
                                    padded_n=cap)
        assert lanes['valid'][:2].all() and not lanes['valid'][2:].any()
        status, edits, reason = MutateKernel(scanner.program)(lanes)
        # live rows edit (label absent); padding rows — which encode as
        # all-MISSING and would otherwise read "every edit applies" —
        # are masked to SKIP / empty bitmask / no reason in-kernel
        assert (status[:2] != MUT_SKIP).any()
        assert (status[2:] == MUT_SKIP).all()
        assert (edits[2:] == 0).all()
        assert (reason[2:] == 0).all()


# ---------------------------------------------------------------------------
# mask-boundary bit-identity: validate


class TestValidateMaskBoundaries:
    def _host(self, policies, resource):
        engine = Engine()
        host = {}
        for pol in policies:
            resp = engine.apply_background_checks(
                PolicyContext(pol, new_resource=resource))
            if resp.policy_response.rules:
                host[pol.name] = {r.name: (r.status, r.message)
                                  for r in resp.policy_response.rules}
        return host

    def test_boundary_row_counts_match_dense_host_oracle(self):
        policies = validate_pack()
        scanner = BatchScanner(policies)
        # shrink the chunk so the spill (capacity+1) case streams two
        # canonically-shaped parts instead of a bulk-capacity pad
        scanner.CHUNK = 128
        cap = scanner.SMALL_BATCH  # the small canonical capacity
        for n in (1, cap - 1, cap, cap + 1, 129):
            resources = [pod(i) for i in range(n)]
            rows = scanner.scan([json.loads(json.dumps(r))
                                 for r in resources])
            assert len(rows) == n
            for resource, responses in zip(resources, rows):
                got = {resp.policy_response.policy_name:
                       {r.name: (r.status, r.message)
                        for r in resp.policy_response.rules}
                       for resp in responses
                       if resp.policy_response.rules}
                assert got == self._host(policies, resource), \
                    f'divergence at n={n} on {resource["metadata"]["name"]}'

    def test_boundary_counts_compile_canonical_shapes_only(self):
        from kyverno_tpu.observability import device as devtel
        reg = devtel.configure(MetricsRegistry())
        try:
            scanner = BatchScanner(validate_pack())
            scanner.CHUNK = 128
            for n in (1, 63, 64, 65, 128, 129):
                scanner.scan_statuses([pod(i) for i in range(n)])
            c = 'kyverno_tpu_compile_cache_requests_total'
            compiled = reg.counter_value(c, result='miss') + \
                reg.counter_value(c, result='aot_load')
            assert compiled <= 2, \
                f'{compiled} executables for one policy set'
        finally:
            devtel.configure(None)

    def test_warmup_shapes_covers_the_table(self):
        scanner = BatchScanner(validate_pack())
        scanner.CHUNK = 128
        timings = scanner.warmup_shapes()
        assert sorted(timings) == [64, 128]
        assert all(v >= 0 for v in timings.values())
        # warmed executables serve a real scan without a fresh compile
        from kyverno_tpu.observability import device as devtel
        reg = devtel.configure(MetricsRegistry())
        try:
            scanner.scan_statuses([pod(i) for i in range(65)])
            c = 'kyverno_tpu_compile_cache_requests_total'
            assert reg.counter_value(c, result='miss') == 0
            assert reg.counter_value(c, result='hit') >= 1
        finally:
            devtel.configure(None)


# ---------------------------------------------------------------------------
# mask-boundary bit-identity: mutate


class TestMutateMaskBoundaries:
    def _pack(self):
        return [
            policy('add-team', {
                'name': 'team',
                'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
                'mutate': {'patchStrategicMerge': {
                    'metadata': {'labels': {'+(team)': 'core'}}}}}),
            policy('dns-policy', {
                'name': 'dns',
                'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
                'mutate': {'patchStrategicMerge': {
                    'spec': {'dnsPolicy': 'ClusterFirst'}}}}),
        ]

    @staticmethod
    def _host_chain(policies, doc):
        engine = Engine()
        pctx = PolicyContext(None,
                             new_resource=json.loads(json.dumps(doc)))
        steps = []
        for pol in policies:
            ctx = pctx.copy()
            ctx.policy = pol
            er = engine.mutate(ctx)
            steps.append((pol.name,
                          [(r.name, str(r.status), r.message, r.patches)
                           for r in er.policy_response.rules]))
            if not er.is_successful():
                break
            pctx = pctx.copy()
            pctx.new_resource = er.patched_resource or pctx.new_resource
            pctx.json_context.add_resource(pctx.new_resource)
        return steps, pctx.new_resource

    def test_boundary_row_counts_match_host_chain(self, monkeypatch):
        # a small canonical table keeps the spill case fast
        monkeypatch.setenv('KTPU_CANONICAL_CAPS', '16,64')
        from kyverno_tpu.mutate import MutateScanner
        policies = self._pack()
        scanner = MutateScanner(policies)
        assert scanner.ok
        for n in (1, 15, 16, 17):
            docs = [pod(i) for i in range(n)]
            rows = scanner.scan([json.loads(json.dumps(d)) for d in docs])
            assert len(rows) == n
            for doc, (steps, patched) in zip(docs, rows):
                h_steps, h_patched = self._host_chain(policies, doc)
                assert patched == h_patched, f'n={n}'
                got = [(pol.name,
                        [(r.name, str(r.status), r.message, r.patches)
                         for r in er.policy_response.rules])
                       for pol, er in steps]
                assert got == h_steps, f'n={n}'


# ---------------------------------------------------------------------------
# mesh verdict summary ignores padding rows


class TestMeshRowMask:
    def test_summary_counts_only_live_rows(self):
        import jax
        from kyverno_tpu.parallel.mesh import (distributed_scan_step,
                                               make_mesh)
        policies = validate_pack()
        scanner = BatchScanner(policies)
        mesh = make_mesh(jax.devices()[:1])
        resources = [pod(i) for i in range(5)]
        statuses, summary = distributed_scan_step(
            scanner.cps, mesh, resources)
        assert statuses.shape[0] == 5
        # the canonical capacity padded well past 5 rows; the summary
        # histogram must still total live rows × programs exactly
        assert int(summary.sum()) == 5 * len(scanner.cps.programs)


# ---------------------------------------------------------------------------
# AOT load rejection accounting


class TestAotLoadRejection:
    @pytest.fixture(autouse=True)
    def _store(self, tmp_path, monkeypatch):
        from kyverno_tpu.aotcache.store import reset_default_store
        monkeypatch.setenv('KTPU_AOT_CACHE_DIR', str(tmp_path / 'aot'))
        reset_default_store()
        self.registry = MetricsRegistry()
        set_global_registry(self.registry)
        yield
        set_global_registry(None)
        reset_default_store()

    def _reason_count(self, reason):
        return self.registry.counter_value(
            'kyverno_tpu_aot_load_rejected_total', reason=reason)

    def test_feature_mismatch_rejected_and_dropped(self):
        from kyverno_tpu.compiler import aot
        store = aot.default_store()
        key = 'f' * 32
        meta = aot._compile_meta()
        meta['host_features'] = 'not-this-machine'
        store.put(key, aot._pack_blob(b'payload', None, None, meta))
        assert aot.load_executable(key) is None
        assert self._reason_count('feature_mismatch') == 1
        assert store.load(key) is None  # dropped, not retried

    def test_env_scope_mismatch_rejected(self):
        from kyverno_tpu.compiler import aot
        store = aot.default_store()
        key = 'e' * 32
        meta = aot._compile_meta()
        meta['env_scope'] = 'compiled-beside-a-live-tpu'
        store.put(key, aot._pack_blob(b'payload', None, None, meta))
        assert aot.load_executable(key) is None
        assert self._reason_count('env_mismatch') == 1

    def test_undecodable_blob_rejected(self):
        from kyverno_tpu.compiler import aot
        store = aot.default_store()
        key = 'u' * 32
        store.put(key, b'Xnot-a-codec')
        assert aot.load_executable(key) is None
        assert self._reason_count('undecodable') == 1

    def test_matching_meta_reaches_deserialize(self):
        # a well-framed entry with THIS process's meta proceeds to XLA
        # deserialization; garbage payload then fails there and is
        # rejected with deserialize_failed (never raised)
        from kyverno_tpu.compiler import aot
        store = aot.default_store()
        key = 'd' * 32
        store.put(key, aot._pack_blob(b'garbage', None, None,
                                      aot._compile_meta()))
        assert aot.load_executable(key) is None
        assert self._reason_count('deserialize_failed') == 1

    def test_legacy_three_tuple_frame_is_undecodable(self):
        import pickle
        import zlib
        from kyverno_tpu.compiler import aot
        store = aot.default_store()
        key = 'l' * 32
        raw = pickle.dumps((b'payload', None, None))
        store.put(key, b'D' + zlib.compress(raw, 3))
        assert aot.load_executable(key) is None
        assert self._reason_count('undecodable') == 1


# ---------------------------------------------------------------------------
# acceptance: second process loads ≤ 2 executables across row counts


_PROBE_SCRIPT = r'''
import json, sys
from kyverno_tpu.api.policy import Policy
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability.metrics import MetricsRegistry

POLICY = {
    'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
    'metadata': {'name': 'require-labels', 'annotations': {
        'pod-policies.kyverno.io/autogen-controllers': 'none'}},
    'spec': {'validationFailureAction': 'Enforce', 'rules': [
        {'name': 'check-app',
         'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
         'validate': {'message': 'app label required',
                      'pattern': {'metadata': {'labels': {'app': '?*'}}}}},
    ]}}


def pod(i):
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': f'p{i}', 'namespace': 'default',
                         'labels': {'app': 'x'} if i % 2 else {}},
            'spec': {'containers': [{'name': 'c', 'image': 'nginx:1'}]}}


reg = devtel.configure(MetricsRegistry())
from kyverno_tpu.compiler.scan import BatchScanner
scanner = BatchScanner([Policy(POLICY)])
out = {}
# the acceptance sweep: row counts from 1 through past the chunk —
# every size must reuse one of the ≤2 canonical executables
for n in (1, 63, 64, 65, 256, 300):
    status, detail, match = scanner.scan_statuses(
        [pod(i) for i in range(n)])
    out[str(n)] = status.tolist()
from kyverno_tpu.compiler import aot
aot.flush_stores()
C = 'kyverno_tpu_compile_cache_requests_total'
print(json.dumps({
    'miss': reg.counter_value(C, result='miss'),
    'aot_load': reg.counter_value(C, result='aot_load'),
    'rows': out,
}))
'''


def _run_probe(cache_dir, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')}
    env.update({
        'JAX_PLATFORMS': 'cpu',
        'PYTHONPATH': REPO,
        'KTPU_SCAN_CHUNK': '256',
        'KTPU_SMALL_BATCH': '64',
        'KTPU_ENCODE_PROCS': '0',
        'KTPU_AOT': '1',
        'KTPU_AOT_CACHE_DIR': os.path.join(str(cache_dir), 'aot'),
        'JAX_COMPILATION_CACHE_DIR': os.path.join(str(cache_dir), 'xla'),
    })
    out = subprocess.run([sys.executable, '-c', _PROBE_SCRIPT],
                         env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_second_process_loads_at_most_two_executables(tmp_path):
    """ISSUE 9 acceptance: scanning every boundary row count from 1 to
    past the chunk, a fresh process against a warm store performs zero
    fresh compiles and loads ≤ 2 executables for the policy set — the
    power-of-two bucket zoo (one per size class) is gone — with
    bit-identical status matrices."""
    first = _run_probe(tmp_path)
    assert first['miss'] <= 2, first
    second = _run_probe(tmp_path)
    assert second['miss'] == 0, second
    assert 1 <= second['aot_load'] <= 2, second
    assert second['rows'] == first['rows']
