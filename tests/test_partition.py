"""Partitioned policy-set compilation (``kyverno_tpu/partition/``):
plan stability + the churn differ, partitioned-scan bit-identity
against the monolithic oracle, live scanner hot-swap with breaker
migration, per-partition verdict-cache generations, and the ISSUE
acceptance: a second process editing 1 of ~100 policies recompiles
exactly the touched partition (everything else AOT-loads) with
bit-identical output."""

import copy
import json
import os
import subprocess
import sys

import pytest

from kyverno_tpu.api.policy import Policy
from kyverno_tpu.partition import census
from kyverno_tpu.partition.plan import (ChurnDiff, PartitionError,
                                        build_plan, coupling_signature,
                                        diff_plans, env_partitions)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KINDS = ['Pod', 'ConfigMap', 'Service']


def policy_raw(i, message=None, kind=None, name=None):
    return {
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': name or f'require-l{i}', 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {'validationFailureAction': 'audit', 'rules': [
            {'name': f'l{i}-label',
             'match': {'any': [{'resources': {
                 'kinds': [kind or KINDS[i % 3]]}}]},
             'validate': {'message': message or f'label l{i} required',
                          'pattern': {'metadata': {'labels': {
                              f'l{i}': '?*'}}}}},
        ]}}


def policies_of(n):
    return [Policy(policy_raw(i)) for i in range(n)]


def pod(name, labels):
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': name, 'namespace': 'default',
                         'uid': f'uid-{name}', 'labels': labels},
            'spec': {'containers': [{'name': 'c', 'image': 'nginx'}]}}


# ---------------------------------------------------------------------------
# plan + differ


class TestPlan:
    def test_env_partitions_parsing(self, monkeypatch):
        monkeypatch.delenv('KTPU_PARTITIONS', raising=False)
        assert env_partitions() == 0
        monkeypatch.setenv('KTPU_PARTITIONS', '8')
        assert env_partitions() == 8
        monkeypatch.setenv('KTPU_PARTITIONS', '-3')
        assert env_partitions() == 0
        monkeypatch.setenv('KTPU_PARTITIONS', 'nope')
        assert env_partitions() == 0

    def test_build_plan_rejects_zero(self):
        with pytest.raises(PartitionError):
            build_plan(policies_of(3), 0)

    def test_plan_is_deterministic(self):
        pols = policies_of(20)
        a = build_plan(pols, 4)
        b = build_plan([Policy(policy_raw(i)) for i in range(20)], 4)
        assert a.assignment == b.assignment
        assert [p.fingerprint for p in a.partitions] == \
            [p.fingerprint for p in b.partitions]
        # every policy lands in exactly one partition
        covered = sorted(i for part in a.partitions
                         for i in part.policy_indices)
        assert covered == list(range(20))

    def test_coupling_signature_tracks_vocabulary(self):
        a = Policy(policy_raw(0, kind='Pod'))
        b = Policy(policy_raw(0, kind='Pod', name='other'))
        c = Policy(policy_raw(0, kind='Service'))
        assert coupling_signature(a) == coupling_signature(b)
        assert coupling_signature(a) != coupling_signature(c)

    def test_edit_touches_exactly_one_partition(self):
        raws = [policy_raw(i) for i in range(30)]
        old = build_plan([Policy(r) for r in raws], 5)
        edited = copy.deepcopy(raws)
        edited[7]['spec']['rules'][0]['validate']['message'] = 'edited'
        new = build_plan([Policy(r) for r in edited], 5)
        diff = diff_plans(old, new)
        assert diff.touched == (new.assignment[7],)
        assert len(diff.touched) + len(diff.unchanged) == \
            len(new.partitions)

    def test_insert_leaves_other_buckets_unchanged(self):
        raws = [policy_raw(i) for i in range(30)]
        old = build_plan([Policy(r) for r in raws], 5)
        # prepend: every existing policy's GLOBAL index shifts, but
        # the fingerprints hash content in set order, so only the new
        # policy's bucket is touched
        grown = [policy_raw(99, name='zz-new')] + raws
        new_pols = [Policy(r) for r in grown]
        new = build_plan(new_pols, 5)
        diff = diff_plans(old, new)
        assert diff.touched == (new.assignment[0],)

    def test_delete_touches_only_its_bucket(self):
        raws = [policy_raw(i) for i in range(30)]
        pols = [Policy(r) for r in raws]
        old = build_plan(pols, 5)
        victim = 11
        shrunk = [p for i, p in enumerate(pols) if i != victim]
        new = build_plan(shrunk, 5)
        diff = diff_plans(old, new)
        assert diff.touched == (old.assignment[victim],)

    def test_first_build_touches_everything(self):
        plan = build_plan(policies_of(10), 3)
        diff = diff_plans(None, plan)
        assert diff.unchanged == ()
        assert sorted(diff.touched) == sorted(
            p.pid for p in plan.partitions)
        assert isinstance(diff, ChurnDiff)
        assert diff.to_dict()['unchanged'] == []


# ---------------------------------------------------------------------------
# partitioned scan = monolithic oracle, bit for bit


class TestPartitionedScan:
    def _statuses(self, policies, resources):
        from kyverno_tpu.compiler.scan import BatchScanner
        return BatchScanner(policies), \
            BatchScanner(policies).scan_statuses(resources)

    def test_bit_identity_vs_monolithic(self, monkeypatch):
        import numpy as np
        from kyverno_tpu.compiler.scan import BatchScanner
        pols = policies_of(12)
        resources = [pod(f'p{j}', {f'l{j % 12}': 'x'} if j % 2 else {})
                     for j in range(9)]
        monkeypatch.setenv('KTPU_PARTITIONS', '0')
        mono = BatchScanner(policies_of(12))
        assert mono._pset is None
        ms, md, mm = mono.scan_statuses(copy.deepcopy(resources))
        monkeypatch.setenv('KTPU_PARTITIONS', '4')
        census.reset()
        part = BatchScanner(pols)
        assert part._pset is not None and part._composer is not None
        # partitioned dispatches never ship whole-set admission lanes:
        # the host matcher decides rows (plan=None semantics)
        assert part._adm is None
        ps, pd, pm = part.scan_statuses(copy.deepcopy(resources))
        assert np.array_equal(ms, ps)
        assert np.array_equal(md, pd)
        assert np.array_equal(mm, pm)
        # the plan registered with the census under the set fingerprint
        rep = census.report()
        assert any(s['set_fingerprint'] == part.fingerprint
                   for s in rep['sets'])

    def test_census_report_shape(self, monkeypatch):
        monkeypatch.setenv('KTPU_PARTITIONS', '3')
        census.reset()
        plan = build_plan(policies_of(6), 3)
        census.record_plan('fp-x', plan, serial=7,
                           diff=diff_plans(None, plan))
        census.record_swap('validate', 1, 2, breaker_state='open',
                           touched=[0])
        rep = census.report()
        assert rep['sets'][0]['serial'] == 7
        assert rep['sets'][0]['last_diff']['unchanged'] == []
        assert rep['swaps'][-1]['breaker_state'] == 'open'
        assert rep['swaps'][-1]['touched_partitions'] == [0]
        census.reset()


# ---------------------------------------------------------------------------
# hot-swap under live traffic: breaker state migrates, never resets


class TestHotSwap:
    def test_install_scanner_swaps_and_migrates_breaker(self, monkeypatch):
        from types import SimpleNamespace
        from kyverno_tpu.observability import metrics as metrics_mod
        from kyverno_tpu.observability.metrics import MetricsRegistry
        from kyverno_tpu.policycache.cache import Cache
        from kyverno_tpu.serving import breaker as breaker_mod
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        reg = MetricsRegistry()
        monkeypatch.setattr(metrics_mod, '_GLOBAL', reg)
        census.reset()
        handlers = ResourceHandlers(Cache())
        pols_a = [Policy(policy_raw(i)) for i in range(3)]
        base_a = tuple(id(p) for p in pols_a)
        key_a = ('validate',) + base_a
        handlers._install_scanner(key_a, base_a, 'validate', pols_a,
                                  SimpleNamespace(serial=101, _pset=None))
        # trip the breaker on the predecessor's key
        for _ in range(50):
            state = handlers._breakers.record_failure(
                base_a, pols_a, 'backend fault')
            if state == breaker_mod.OPEN:
                break
        assert handlers._breakers.state(base_a) == breaker_mod.OPEN
        # churn: same logical set (same names), new Policy objects
        pols_b = [Policy(policy_raw(i, message='edited'))
                  for i in range(3)]
        base_b = tuple(id(p) for p in pols_b)
        key_b = ('validate',) + base_b
        handlers._install_scanner(key_b, base_b, 'validate', pols_b,
                                  SimpleNamespace(serial=102, _pset=None))
        assert key_a not in handlers._scanners
        assert key_b in handlers._scanners
        # the fault is NOT forgiven by the recompile...
        assert handlers._breakers.state(base_b) == breaker_mod.OPEN
        # ...and the retired key no longer holds it
        assert handlers._breakers.state(base_a) == breaker_mod.CLOSED
        assert reg.counter_value('kyverno_tpu_scanner_hot_swaps_total',
                                 kind='validate') == 1
        assert reg.counter_value(
            'kyverno_tpu_breaker_migrations_total') == 1
        swap = census.report()['swaps'][-1]
        assert (swap['old_serial'], swap['new_serial']) == (101, 102)
        assert swap['breaker_state'] == breaker_mod.OPEN
        census.reset()

    def test_unrelated_set_does_not_swap(self):
        from types import SimpleNamespace
        from kyverno_tpu.policycache.cache import Cache
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        handlers = ResourceHandlers(Cache())
        pols_a = [Policy(policy_raw(i)) for i in range(3)]
        pols_b = [Policy(policy_raw(i + 50)) for i in range(3)]
        for n, pols in ((1, pols_a), (2, pols_b)):
            base = tuple(id(p) for p in pols)
            handlers._install_scanner(
                ('validate',) + base, base, 'validate', pols,
                SimpleNamespace(serial=n, _pset=None))
        # zero name overlap: both scanners stay live
        assert len(handlers._scanners) == 2

    def test_migrate_without_entry_is_closed(self):
        from kyverno_tpu.serving import breaker as breaker_mod
        from kyverno_tpu.serving.breaker import BreakerRegistry
        reg = BreakerRegistry()
        assert reg.migrate(('old',), ('new',)) == breaker_mod.CLOSED


# ---------------------------------------------------------------------------
# per-partition verdict-cache generations


class TestPartitionedVerdictCache:
    def _cache(self, n_pols=8, n_parts=3):
        from kyverno_tpu.verdictcache.partitioned import \
            PartitionedVerdictCache
        pols = policies_of(n_pols)
        plan = build_plan(pols, n_parts)
        return PartitionedVerdictCache(plan, pols), plan, pols

    def _row(self, pols, indexes, result='pass'):
        return [{'policy': pols[i].get_kind_and_name(),
                 'rule': f'l{i}-label', 'result': result,
                 'scored': True} for i in indexes]

    def test_store_lookup_roundtrip(self):
        vc, plan, pols = self._cache()
        results = self._row(pols, range(8))
        vc.store('d1', 'uid-1', results,
                 {'pass': 8, 'fail': 0, 'warn': 0, 'error': 0,
                  'skip': 0}, list(range(8)))
        row = vc.lookup('d1')
        assert row is not None
        assert [r['policy'] for r in row['r']] == \
            sorted(r['policy'] for r in results)
        assert row['s']['pass'] == 8 and row['s']['fail'] == 0
        assert row['p'] == list(range(8))
        assert vc.stats()['hits'] == 1

    def test_lookup_requires_every_partition(self):
        vc, plan, pols = self._cache()
        # a row missing from even one generation must miss whole
        sub = next(iter(vc._parts.values()))
        vc.store('d2', 'u', self._row(pols, [0]),
                 {'pass': 1, 'fail': 0, 'warn': 0, 'error': 0,
                  'skip': 0}, [0])
        sub._rows.clear()
        assert vc.lookup('d2') is None
        assert vc.stats()['misses'] == 1

    def test_partial_and_merge_scoped(self):
        vc, plan, pols = self._cache()
        results = self._row(pols, range(8))
        vc.store('d3', 'uid-3', results,
                 {'pass': 8, 'fail': 0, 'warn': 0, 'error': 0,
                  'skip': 0}, list(range(8)))
        scoped_pid = plan.partitions[0].pid
        scoped_globals = list(plan.partitions[0].policy_indices)
        # evict the scoped partition's generation (the churn)
        vc._parts[scoped_pid]._rows.clear()
        assert vc.lookup('d3') is None
        cached = vc.partial('d3', frozenset([scoped_pid]))
        assert cached is not None and scoped_pid not in cached
        assert vc.stats()['partial_hits'] == 1
        # re-scan ONLY the scoped partition's members, fail this time
        rescan = self._row(pols, scoped_globals, result='fail')
        merged, summary, gidx = vc.merge_scoped(
            'd3', 'uid-3', cached, rescan, None, scoped_globals,
            ts=1754000000)
        assert gidx == list(range(8))
        assert summary['fail'] == len(scoped_globals)
        assert summary['pass'] == 8 - len(scoped_globals)
        assert [r['policy'] for r in merged] == \
            sorted(r['policy'] for r in results)
        # the digest is whole again: full lookup hits
        assert vc.lookup('d3') is not None

    def test_generation_carries_over_by_fingerprint(self):
        from kyverno_tpu.verdictcache.partitioned import \
            PartitionedVerdictCache
        vc, plan, pols = self._cache()
        vc.store('d4', 'u4', self._row(pols, range(8)),
                 {'pass': 8, 'fail': 0, 'warn': 0, 'error': 0,
                  'skip': 0}, list(range(8)))
        raws = [policy_raw(i) for i in range(8)]
        edited = plan.partitions[0].policy_indices[0]
        raws[edited]['spec']['rules'][0]['validate']['message'] = 'x'
        pols2 = [Policy(r) for r in raws]
        plan2 = build_plan(pols2, 3)
        vc2 = PartitionedVerdictCache(plan2, pols2, prev=vc)
        touched = diff_plans(plan, plan2).touched
        for part in plan2.partitions:
            sub = vc2._parts[part.pid]
            if part.pid in touched:
                assert len(sub) == 0  # fresh generation
            else:
                assert sub is vc._parts[part.pid]  # adopted in place


# ---------------------------------------------------------------------------
# controller flow: dense scan -> replay -> churn -> scoped rescan -> replay


class TestControllerChurn:
    NOW = 1754000000.0

    def _controller(self, policies):
        from kyverno_tpu.dclient.client import FakeClient
        from kyverno_tpu.reports.controllers import (
            BackgroundScanController, MetadataCache)
        ctrl = BackgroundScanController(FakeClient(), policies,
                                        cache=MetadataCache())
        return ctrl

    def _reports(self, ctrl):
        out = []
        for r in sorted(ctrl.client.list_resource(
                'kyverno.io/v1alpha2', 'BackgroundScanReport', 'default',
                None), key=lambda r: r['metadata']['name']):
            meta = {k: v for k, v in r['metadata'].items()
                    if k not in ('resourceVersion', 'uid')}
            out.append(dict(r, metadata=meta))
        return out

    def test_churn_scoped_rescan_and_bit_identity(self, monkeypatch,
                                                  tmp_path):
        monkeypatch.setenv('KTPU_VERDICT_CACHE', '1')
        monkeypatch.setenv('KTPU_VERDICT_CACHE_DIR',
                           str(tmp_path / 'vc'))
        monkeypatch.setenv('KTPU_PARTITIONS', '4')
        raws = [policy_raw(i) for i in range(12)]
        pods = [pod(f'p{j}', {f'l{j % 12}': 'x'}) for j in range(20)]
        ctrl = self._controller([Policy(r) for r in raws])
        for p in pods:
            ctrl.enqueue(p)
        ctrl.reconcile(now=self.NOW)
        assert ctrl.rescan_stats['rows_scanned'] == 20
        # warm replay: zero scans
        ctrl.reset_scan_state()
        ctrl.enqueue_all()
        ctrl.reconcile(now=self.NOW + 60)
        assert ctrl.rescan_stats['rows_replayed'] == 20
        # churn: edit one policy -> scoped pids = its partition only
        raws2 = copy.deepcopy(raws)
        raws2[5]['spec']['rules'][0]['validate']['message'] = 'edited'
        ctrl.set_policies([Policy(r) for r in raws2])
        assert ctrl._scoped_pids is not None
        assert len(ctrl._scoped_pids) < ctrl._partition_plan.n_parts
        ctrl.enqueue_all()
        ctrl.reconcile(now=self.NOW + 120)
        # every row re-scanned ONLY against the touched partitions
        assert ctrl.rescan_stats['rows_scoped'] == 20
        # scoped fills completed the generations: full replay again
        ctrl.reset_scan_state()
        ctrl.enqueue_all()
        ctrl.reconcile(now=self.NOW + 180)
        assert ctrl.rescan_stats['rows_replayed'] == 20
        # oracle: monolithic scan, cache off, same final policy set
        monkeypatch.setenv('KTPU_PARTITIONS', '0')
        monkeypatch.setenv('KTPU_VERDICT_CACHE', '0')
        oracle = self._controller([Policy(r) for r in raws2])
        for p in pods:
            oracle.enqueue(p)
        oracle.reconcile(now=self.NOW + 180)
        assert self._reports(ctrl) == self._reports(oracle)

    def test_second_process_generations_replay(self, monkeypatch,
                                               tmp_path):
        monkeypatch.setenv('KTPU_VERDICT_CACHE', '1')
        monkeypatch.setenv('KTPU_VERDICT_CACHE_DIR',
                           str(tmp_path / 'vc'))
        monkeypatch.setenv('KTPU_PARTITIONS', '3')
        raws = [policy_raw(i) for i in range(9)]
        pods = [pod(f'p{j}', {f'l{j % 9}': 'x'}) for j in range(10)]
        ctrl = self._controller([Policy(r) for r in raws])
        for p in pods:
            ctrl.enqueue(p)
        ctrl.reconcile(now=self.NOW)
        ctrl.verdict_cache.flush()
        # a fresh controller (second process): the per-partition
        # snapshots on disk warm every row
        ctrl2 = self._controller([Policy(r) for r in raws])
        for p in pods:
            ctrl2.enqueue(p)
        ctrl2.reconcile(now=self.NOW + 60)
        assert ctrl2.rescan_stats['rows_replayed'] == 10


# ---------------------------------------------------------------------------
# ISSUE acceptance: second-process incremental warm.  Fresh interpreters
# (cold jit caches, no forced 8-device mesh so the AOT store is live):
# process 1 compiles + persists every partition executable; process 2
# serves entirely from aot_load; process 3 edits 1 of 100 policies and
# recompiles EXACTLY the touched partition, with bit-identical verdict
# matrices throughout.

_WARM_SCRIPT = r'''
import json, os, sys
from kyverno_tpu.api.policy import Policy
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability.metrics import MetricsRegistry

N = 100


def policy(i, message=None):
    return {
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': f'require-l{i}', 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {'validationFailureAction': 'audit', 'rules': [
            {'name': f'l{i}',
             'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
             'validate': {'message': message or f'label l{i} required',
                          'pattern': {'metadata': {'labels': {
                              f'l{i}': '?*'}}}}},
        ]}}


raws = [policy(i) for i in range(N)]
churn = os.environ.get('KTPU_TEST_CHURN_INDEX')
if churn is not None:
    k = int(churn)
    raws[k] = policy(k, message=f'label l{k} required [edited]')
policies = [Policy(r) for r in raws]

from kyverno_tpu.partition.plan import build_plan, diff_plans
n_parts = int(os.environ['KTPU_PARTITIONS'])
orig = build_plan([Policy(policy(i)) for i in range(N)], n_parts)
diff = diff_plans(orig, build_plan(policies, n_parts))


def pod(i):
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': f'p{i}', 'namespace': 'default',
                         'labels': {f'l{i}': 'x'} if i % 2 else {}},
            'spec': {'containers': [{'name': 'c', 'image': 'nginx:1'}]}}


reg = devtel.configure(MetricsRegistry())
from kyverno_tpu.compiler.scan import BatchScanner
scanner = BatchScanner(policies)
status, detail, match = scanner.scan_statuses([pod(i) for i in range(4)])
from kyverno_tpu.compiler import aot
aot.flush_stores()
C = 'kyverno_tpu_compile_cache_requests_total'
print(json.dumps({
    'n_partitions': len(scanner._pset.runtimes),
    'touched': sorted(diff.touched),
    'miss': reg.counter_value(C, result='miss'),
    'aot_load': reg.counter_value(C, result='aot_load'),
    'aot_store': reg.counter_value(C, result='aot_store'),
    'xla_hits': aot.xla_cache_hits(),
    'status': status.tolist(),
    'detail': detail.tolist(),
    'match': match.tolist(),
}))
'''


def _run_partitioned_process(cache_dir, churn_index=None, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')}
    env.update({
        'JAX_PLATFORMS': 'cpu',
        'PYTHONPATH': REPO,
        'KTPU_AOT': '1',
        'KTPU_AOT_CACHE_DIR': os.path.join(str(cache_dir), 'aot'),
        'JAX_COMPILATION_CACHE_DIR': os.path.join(str(cache_dir), 'xla'),
        'KTPU_PARTITIONS': '5',
    })
    if churn_index is not None:
        env['KTPU_TEST_CHURN_INDEX'] = str(churn_index)
    out = subprocess.run([sys.executable, '-c', _WARM_SCRIPT],
                         env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_incremental_warm_recompiles_only_touched_partition(tmp_path):
    # a miss is kept by exactly one cache: the AOT store takes what XLA
    # compiled here; what the persistent XLA cache handed back (two
    # partitions can lower to one HLO, and an edited message changes
    # the key but not the program) stays there and is not stored again
    first = _run_partitioned_process(tmp_path)
    assert first['touched'] == []
    assert first['miss'] == first['n_partitions']
    assert first['aot_store'] + first['xla_hits'] == first['n_partitions']
    assert first['aot_load'] == 0

    second = _run_partitioned_process(tmp_path)
    assert second['miss'] == first['xla_hits']
    assert second['aot_load'] == first['aot_store']

    churn = _run_partitioned_process(tmp_path, churn_index=17)
    # a single-policy edit touches exactly one bucket...
    assert len(churn['touched']) == 1
    # ...which is the ONLY fresh compile; the rest warm-load
    assert churn['miss'] + churn['aot_load'] == churn['n_partitions']
    assert first['aot_store'] - churn['aot_load'] in (0, 1)
    assert churn['aot_store'] + churn['xla_hits'] == churn['miss']
    if first['xla_hits'] == 0:
        assert churn['miss'] == 1
        assert churn['aot_load'] == churn['n_partitions'] - 1

    # the edit changed a message, not a pattern: verdict matrices are
    # bit-identical across all three processes
    for field in ('status', 'detail', 'match'):
        assert first[field] == second[field] == churn[field], field
