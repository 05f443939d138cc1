"""Digest-keyed verdict cache (ISSUE 6): spec-digest stability, store
hit/miss/invalidation/eviction semantics, controller integration
(replay vs scan partition, delete invalidation, policy-set flush), the
KTPU_VERDICT_CACHE=off bit-identity oracle, second-process
disk-store reuse, the snapshot table (each distinct result written
once, reloaded to what the KTVC1 codec gave), and the KTVC3 log: a base
frame and appended deltas that reload to what one full snapshot of the
same memory reloads to, a torn tail that loads the last complete flush,
and the compaction rule."""

import copy
import hashlib
import json
import os
import struct
import sys
import zlib

import pytest
import yaml

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from kyverno_tpu.api.policy import Policy  # noqa: E402
from kyverno_tpu.dclient.client import FakeClient  # noqa: E402
from kyverno_tpu.observability.metrics import (MetricsRegistry,  # noqa: E402
                                               set_global_registry)
from kyverno_tpu.reports.controllers import (  # noqa: E402
    BackgroundScanController, MetadataCache)
from kyverno_tpu.verdictcache import (VerdictCache, engine_rev,  # noqa: E402
                                      generation_key, spec_digest)

POLICY = yaml.safe_load("""
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: require-team
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  validationFailureAction: audit
  rules:
    - name: team-label
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: team label required
        pattern:
          metadata:
            labels:
              team: "?*"
""")

OTHER_POLICY = yaml.safe_load("""
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: require-owner
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  validationFailureAction: audit
  rules:
    - name: owner-label
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: owner label required
        pattern:
          metadata:
            labels:
              owner: "?*"
""")

NOW = 1754000000.0


def pod(name, team=None, uid=None):
    labels = {'team': team} if team else {}
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': name, 'namespace': 'default',
                         'uid': uid or f'uid-{name}', 'labels': labels},
            'spec': {'containers': [{'name': 'c', 'image': 'nginx'}]}}


@pytest.fixture(autouse=True)
def _registry():
    reg = MetricsRegistry()
    set_global_registry(reg)
    yield reg
    set_global_registry(None)


def make_ctrl(tmp_path, monkeypatch, enabled=True, policies=None,
              client=None):
    monkeypatch.setenv('KTPU_VERDICT_CACHE', '1' if enabled else '0')
    monkeypatch.setenv('KTPU_VERDICT_CACHE_DIR', str(tmp_path / 'vc'))
    return BackgroundScanController(
        client or FakeClient(),
        [Policy(p) for p in (policies or [POLICY])], cache=MetadataCache())


def reports_of(ctrl):
    """Stored reports with the fake API server's own write bookkeeping
    (metadata.resourceVersion bumps per update, server-assigned
    metadata.uid) normalized away — the bit-identity contract is about
    report *content*."""
    out = []
    for r in sorted(ctrl.client.list_resource(
            'kyverno.io/v1alpha2', 'BackgroundScanReport', 'default',
            None), key=lambda r: r['metadata']['name']):
        r = dict(r, metadata={k: v for k, v in r['metadata'].items()
                              if k not in ('resourceVersion', 'uid')})
        out.append(r)
    return out


# ---------------------------------------------------------------------------
# spec digest


class TestSpecDigest:
    def test_key_order_and_volatile_metadata_irrelevant(self):
        a = pod('p', team='infra')
        # same content, different key order + server-side bookkeeping
        b = {
            'kind': 'Pod', 'apiVersion': 'v1',
            'spec': {'containers': [{'image': 'nginx', 'name': 'c'}]},
            'metadata': {
                'labels': {'team': 'infra'}, 'uid': 'uid-p',
                'namespace': 'default', 'name': 'p',
                'resourceVersion': '123456',
                'generation': 7,
                'creationTimestamp': '2026-01-01T00:00:00Z',
                'managedFields': [{'manager': 'kubectl',
                                   'operation': 'Apply'}],
            },
        }
        assert spec_digest(a) == spec_digest(b)

    def test_changed_content_misses(self):
        base = pod('p', team='infra')
        changed = pod('p', team='other')
        assert spec_digest(base) != spec_digest(changed)
        with_status = pod('p', team='infra')
        with_status['status'] = {'phase': 'Running'}
        assert spec_digest(base) != spec_digest(with_status)

    def test_recreated_uid_misses(self):
        # a deleted-then-recreated resource gets a fresh uid, so even
        # identical content never aliases the predecessor's entries
        assert spec_digest(pod('p', uid='u1')) != \
            spec_digest(pod('p', uid='u2'))

    def test_digest_does_not_mutate_the_resource(self):
        p = pod('p')
        p['metadata']['resourceVersion'] = '42'
        spec_digest(p)
        assert p['metadata']['resourceVersion'] == '42'


# ---------------------------------------------------------------------------
# store


ROW = ([{'source': 'kyverno', 'policy': 'require-team',
         'rule': 'team-label', 'message': 'ok', 'result': 'pass',
         'scored': True, 'timestamp': {'seconds': 1}}],
       {'pass': 1, 'fail': 0, 'warn': 0, 'error': 0, 'skip': 0}, [0])


class TestStore:
    def test_hit_miss_and_replay_stamps_timestamp(self, tmp_path,
                                                  _registry):
        vc = VerdictCache('fp', root=str(tmp_path))
        assert vc.lookup('d1') is None
        results, summary, idx = ROW
        vc.store('d1', 'u1', results, summary, idx)
        row = vc.lookup('d1')
        assert row is not None
        policies = [Policy(POLICY)]
        r2, s2, p2 = vc.replay(row, policies, ts=99)
        assert r2[0]['timestamp'] == {'seconds': 99}
        assert {k: v for k, v in r2[0].items() if k != 'timestamp'} == \
            {k: v for k, v in results[0].items() if k != 'timestamp'}
        assert s2 == summary and p2 == policies
        assert _registry.counter_value(
            'kyverno_tpu_verdict_cache_hits_total') == 1.0
        assert _registry.counter_value(
            'kyverno_tpu_verdict_cache_misses_total') == 1.0

    def test_uid_invalidation_drops_entries(self, tmp_path):
        vc = VerdictCache('fp', root=str(tmp_path))
        vc.store('d1', 'u1', *ROW)
        vc.store('d2', 'u1', *ROW)
        vc.store('d3', 'u2', *ROW)
        assert vc.invalidate_uid('u1') == 2
        assert vc.lookup('d1') is None and vc.lookup('d2') is None
        assert vc.lookup('d3') is not None

    def test_memory_lru_eviction_counts(self, tmp_path, _registry):
        vc = VerdictCache('fp', root=str(tmp_path), max_entries=2)
        vc.store('d1', 'u1', *ROW)
        vc.store('d2', 'u2', *ROW)
        vc.lookup('d1')  # refresh: d2 becomes LRU
        vc.store('d3', 'u3', *ROW)
        assert vc.lookup('d2') is None and vc.lookup('d1') is not None
        assert _registry.counter_value(
            'kyverno_tpu_verdict_cache_evictions_total') == 1.0

    def test_snapshot_roundtrip_and_corruption(self, tmp_path):
        vc = VerdictCache('fp', root=str(tmp_path))
        vc.store('d1', 'u1', *ROW)
        assert vc.flush()
        assert not vc.flush()  # clean: nothing to write
        again = VerdictCache('fp', root=str(tmp_path))
        assert again.lookup('d1') is not None
        assert again.invalidate_uid('u1') == 1  # uid index rebuilt
        # a bit-flipped snapshot is dropped and loaded as empty
        path = vc.path()
        raw = bytearray(open(path, 'rb').read())
        raw[-1] ^= 0xFF
        open(path, 'wb').write(bytes(raw))
        fresh = VerdictCache('fp', root=str(tmp_path))
        assert len(fresh) == 0
        assert not os.path.exists(path)

    def test_generation_isolation_and_disk_eviction(self, tmp_path):
        old = VerdictCache('fp-old', root=str(tmp_path), max_bytes=1)
        old.store('d1', 'u1', *ROW)
        old.flush()
        # different fingerprint = different generation: no aliasing
        new = VerdictCache('fp-new', root=str(tmp_path), max_bytes=1)
        assert new.lookup('d1') is None
        os.utime(old.path(), (1, 1))  # age the old generation
        new.store('d1', 'u1', *ROW)
        new.flush()  # budget of 1 byte: the old generation is evicted
        assert not os.path.exists(old.path())
        assert os.path.exists(new.path())

    def test_engine_rev_scopes_generation(self, tmp_path, monkeypatch):
        a = VerdictCache('fp', root=str(tmp_path), rev='rev-a')
        a.store('d1', 'u1', *ROW)
        a.flush()
        b = VerdictCache('fp', root=str(tmp_path), rev='rev-b')
        assert b.lookup('d1') is None  # code change never replays
        assert generation_key('fp', 'rev-a') != generation_key(
            'fp', 'rev-b')
        assert engine_rev()  # derivable in this tree


# ---------------------------------------------------------------------------
# controller integration


def seed(ctrl, pods):
    for p in pods:
        ctrl.enqueue(p)


class TestControllerIntegration:
    def test_warm_rescan_replays_without_scanning(self, tmp_path,
                                                  monkeypatch):
        ctrl = make_ctrl(tmp_path, monkeypatch)
        pods = [pod('good', team='infra'), pod('bad')]
        seed(ctrl, pods)
        assert len(ctrl.reconcile(now=NOW)) == 2
        assert ctrl.rescan_stats == {
            'rows_pending': 2, 'rows_scanned': 2, 'rows_replayed': 0}
        first = reports_of(ctrl)
        # a full report-rebuild demand (restart semantics) replays from
        # the cache — the device scanner must not run at all
        monkeypatch.setattr(
            ctrl.scanner, 'scan_report_results',
            lambda *a, **k: pytest.fail('warm rescan must not scan'))
        ctrl.reset_scan_state()
        ctrl.enqueue_all()
        assert len(ctrl.reconcile(now=NOW)) == 2
        assert ctrl.rescan_stats == {
            'rows_pending': 2, 'rows_scanned': 0, 'rows_replayed': 2}
        assert reports_of(ctrl) == first

    def test_churn_scans_only_changed_rows(self, tmp_path, monkeypatch,
                                           _registry):
        ctrl = make_ctrl(tmp_path, monkeypatch)
        pods = [pod(f'p{i}', team='infra') for i in range(8)]
        seed(ctrl, pods)
        ctrl.reconcile(now=NOW)
        pods[3]['metadata']['labels'] = {}  # churn one row
        ctrl.cache.update(pods[3])
        ctrl.reset_scan_state()
        ctrl.enqueue_all()
        ctrl.reconcile(now=NOW + 30)
        assert ctrl.rescan_stats == {
            'rows_pending': 8, 'rows_scanned': 1, 'rows_replayed': 7}
        assert _registry.gauge_value(
            'kyverno_tpu_rescan_rows_scanned') == 1.0
        assert _registry.gauge_value(
            'kyverno_tpu_rescan_rows_replayed') == 7.0
        # the churned row's report reflects the new content
        failed = [r for r in reports_of(ctrl)
                  if r['metadata']['ownerReferences'][0]['name'] == 'p3']
        assert failed[0]['spec']['summary']['fail'] == 1

    def test_delete_drops_verdict_entries(self, tmp_path, monkeypatch):
        ctrl = make_ctrl(tmp_path, monkeypatch)
        p = pod('gone', team='infra')
        seed(ctrl, [p])
        ctrl.reconcile(now=NOW)
        assert len(ctrl.verdict_cache) == 1
        ctrl.cache.remove(p)
        assert len(ctrl.verdict_cache) == 0

    def test_policy_change_opens_new_generation(self, tmp_path,
                                                monkeypatch):
        ctrl = make_ctrl(tmp_path, monkeypatch)
        seed(ctrl, [pod('p', team='infra')])
        ctrl.reconcile(now=NOW)
        gen_before = ctrl.verdict_cache.fingerprint
        ctrl.set_policies([Policy(OTHER_POLICY)])
        assert ctrl.verdict_cache.fingerprint != gen_before
        ctrl.enqueue(pod('p', team='infra'))
        ctrl.reconcile(now=NOW + 60)
        assert ctrl.rescan_stats['rows_scanned'] == 1
        assert ctrl.rescan_stats['rows_replayed'] == 0

    def test_off_switch_bit_identical_reports(self, tmp_path,
                                              monkeypatch):
        """ISSUE 6 acceptance: cached-rescan output is pinned against a
        fresh dense scan — KTPU_VERDICT_CACHE=off produces bit-identical
        BackgroundScanReports for the same (resources, policies, now)."""
        pods = [pod('good', team='infra'), pod('bad'), pod('mid')]
        cached = make_ctrl(tmp_path, monkeypatch, enabled=True)
        seed(cached, [pod('good', team='infra'), pod('bad'), pod('mid')])
        cached.reconcile(now=NOW)       # populate the cache
        cached.reset_scan_state()
        seed(cached, pods)
        cached.reconcile(now=NOW + 30)  # replayed pass
        assert cached.rescan_stats['rows_replayed'] == 3
        dense = make_ctrl(tmp_path / 'dense', monkeypatch, enabled=False)
        assert dense.verdict_cache is None
        seed(dense, [pod('good', team='infra'), pod('bad'), pod('mid')])
        dense.reconcile(now=NOW + 30)
        assert dense.rescan_stats['rows_replayed'] == 0
        assert reports_of(cached) == reports_of(dense)

    def test_second_process_disk_store_reuse(self, tmp_path,
                                             monkeypatch):
        """A fresh controller (new process: cold memory, same cache dir
        and policy set) replays from the persisted snapshot with zero
        device scans — a base and the deltas two churn ticks appended
        to it."""
        first = make_ctrl(tmp_path, monkeypatch)
        pods = [pod(f'p{i}', team='x') for i in range(6)] + [pod('b')]
        seed(first, pods)
        first.reconcile(now=NOW)
        pods[2]['metadata']['labels'] = {}  # tick 1: one row changed
        seed(first, [pods[2]])
        first.reconcile(now=NOW)
        pods.append(pod('c', team='y'))     # tick 2: one row added
        seed(first, [pods[-1]])
        first.reconcile(now=NOW)
        first.close()  # daemon-shutdown flush: nothing left to write
        frames, tail = frames_of(first.verdict_cache.path())
        assert [k for k, _ in frames] == ['B', 'D', 'D'] and tail == 0
        second = make_ctrl(tmp_path, monkeypatch)
        monkeypatch.setattr(
            second.scanner, 'scan_report_results',
            lambda *a, **k: pytest.fail('disk-warm rescan must not scan'))
        seed(second, copy.deepcopy(pods))
        assert len(second.reconcile(now=NOW)) == len(pods)
        assert second.rescan_stats == {
            'rows_pending': len(pods), 'rows_scanned': 0,
            'rows_replayed': len(pods)}
        assert reports_of(second) == reports_of(first)


# ---------------------------------------------------------------------------
# the snapshot table (KTVC2)


def _result(policy, rule, result='pass', message='ok', ts=1, **extra):
    return dict({'source': 'kyverno', 'policy': policy, 'rule': rule,
                 'message': message, 'result': result, 'scored': True,
                 'timestamp': {'seconds': ts}}, **extra)


def _summary(results):
    out = {'pass': 0, 'fail': 0, 'warn': 0, 'error': 0, 'skip': 0}
    for r in results:
        out[r['result']] += 1
    return out


def shared_rows():
    """Rows as the fused path hands them: flyweights shared across
    rows, two flyweights of one content from two seconds, and one
    per-cell FAIL dict no other row shares."""
    a = _result('p-a', 'r1')
    b = _result('p-b', 'r2', 'fail', 'bad', category='Best Practices',
                properties={'standard': 'baseline', 'version': 'latest',
                            'controls': 'hostPort'})
    a_later = _result('p-a', 'r1', ts=2)   # a's content, a's next second
    rows = []
    for i in range(6):
        results = [a if i % 2 else a_later, b]
        if i == 3:
            results.append(_result('p-c', 'r3', 'fail',
                                   f'container "c{i}" \u00e9 fails'))
        rows.append((f'd{i}', f'default/pod-{i}', results,
                     _summary(results), [0, 1] if i != 3 else [0, 1, 2]))
    return rows


def parent_rows(rows):
    """The KTVC1 codec's reload of the same stores: its ``store`` copied
    each result without ``timestamp`` and its ``flush`` dumped the rows
    as they were."""
    stored = {d: {'u': u, 'r': [{k: v for k, v in r.items()
                                 if k != 'timestamp'} for r in res],
                  's': dict(s), 'p': list(p)}
              for d, u, res, s, p in rows}
    return json.loads(json.dumps(stored, separators=(',', ':')))


def frames_of(path):
    """The snapshot's frames, read without the cache's own reader:
    ``[(kind, payload JSON)]``, and the bytes after the last whole one."""
    raw = open(path, 'rb').read()
    assert raw.startswith(b'KTVC3\n')
    pos, frames = len(b'KTVC3\n'), []
    while pos + 41 <= len(raw):
        kind, length, digest = struct.unpack_from('>cQ32s', raw, pos)
        payload = raw[pos + 41:pos + 41 + length]
        if len(payload) != length or \
                hashlib.sha256(payload).digest() != digest:
            break
        frames.append((kind.decode(), json.loads(zlib.decompress(payload))))
        pos += 41 + length
    return frames, len(raw) - pos


def payload_of(vc):
    """The base frame of a snapshot that holds nothing else."""
    frames, tail = frames_of(vc.path())
    assert [k for k, _ in frames] == ['B'] and tail == 0
    return frames[0][1]


POLICIES3 = [Policy(POLICY), Policy(OTHER_POLICY), Policy(POLICY)]


class TestSnapshotTable:
    def test_shared_results_flush_to_a_table_once(self, tmp_path,
                                                   _registry):
        vc = VerdictCache('fp', root=str(tmp_path))
        rows = shared_rows()
        for row in rows:
            vc.store(*row)
        assert vc.flush()
        doc = payload_of(vc)
        # a, b and the FAIL dict: a_later has a's bytes
        assert len(doc['t']) == 3
        assert all('timestamp' not in r for r in doc['t'])
        refs = sum(len(r[2]) for r in rows)
        assert sum(len(r['r']) for r in doc['r'].values()) == refs
        assert doc['r']['d0']['r'] == doc['r']['d1']['r']
        assert set(doc['r']['d0']) == {'u', 'r', 's', 'p'}
        assert _registry.counter_value(
            'kyverno_tpu_verdict_snapshot_results_total',
            entry='ref') == float(refs)
        assert _registry.counter_value(
            'kyverno_tpu_verdict_snapshot_results_total',
            entry='table') == 3.0
        assert vc.last_flush == (refs, 3)
        assert not vc.flush() and vc.last_flush == (0, 0)

    def test_reload_equals_the_parent_codecs(self, tmp_path):
        vc = VerdictCache('fp', root=str(tmp_path))
        rows = shared_rows()
        for row in rows:
            vc.store(*row)
        vc.flush()
        again = VerdictCache('fp', root=str(tmp_path))
        want = parent_rows(rows)
        assert len(again) == len(want)
        for digest, row in want.items():
            got = again.lookup(digest)
            assert {k: got[k] for k in ('u', 'r', 's', 'p')} == row
        # one dict per table entry, shared by the rows that refer to it
        assert again.lookup('d0')['r'][0] is again.lookup('d1')['r'][0]
        assert again.lookup('d0')['r'][1] is again.lookup('d5')['r'][1]
        assert again.invalidate_uid('default/pod-2') == 1

    def test_replay_after_reload_equals_replay_before_flush(self,
                                                           tmp_path):
        vc = VerdictCache('fp', root=str(tmp_path))
        rows = shared_rows()
        for row in rows:
            vc.store(*row)
        before = {}
        for d, *_ in rows:
            results, summary, policies = vc.replay(vc.peek(d), POLICIES3, 77)
            before[d] = (copy.deepcopy(results), summary, policies)
        vc.flush()
        again = VerdictCache('fp', root=str(tmp_path))
        for d, *_ in rows:
            assert again.replay(again.peek(d), POLICIES3, 77) == before[d]

    @pytest.mark.parametrize('magic', [b'KTVC1\n', b'KTVC2\n'],
                             ids=['KTVC1', 'KTVC2'])
    def test_ktvc1_snapshot_loads_as_empty(self, tmp_path, magic):
        """A snapshot of an older codec, whole and rightly hashed, fails
        the magic check: it is deleted, loads as empty, and costs one
        rescan.  KTVC1 held per-row copies, KTVC2 one table and no
        frames."""
        vc = VerdictCache('fp', root=str(tmp_path))
        rows = parent_rows(shared_rows())
        if magic == b'KTVC2\n':
            table = [r for row in rows.values() for r in row['r']]
            rows = {'t': table, 'r': {d: dict(row, r=[table.index(r) for r
                                                       in row['r']])
                                      for d, row in rows.items()}}
        payload = zlib.compress(json.dumps(
            rows, separators=(',', ':')).encode(), 3)
        with open(vc.path(), 'wb') as f:
            f.write(magic + hashlib.sha256(payload).digest() + payload)
        old = VerdictCache('fp', root=str(tmp_path))
        assert len(old) == 0
        assert not os.path.exists(vc.path())

    def test_store_neither_copies_per_row_nor_mutates(self, tmp_path):
        vc = VerdictCache('fp', root=str(tmp_path))
        rows = shared_rows()
        handed = copy.deepcopy([r[2] for r in rows])
        for row in rows:
            vc.store(*row)
        assert [r[2] for r in rows] == handed
        assert all('timestamp' in r for res in handed for r in res)
        # two rows handed one dict share one stored, timestamp-free dict
        r1, r3 = vc.peek('d1')['r'], vc.peek('d3')['r']
        assert r1[0] is r3[0] and r1[1] is r3[1]
        assert 'timestamp' not in r1[0]
        assert vc.peek('d0')['r'][1] is r1[1]

    def test_replayed_row_flushes_and_reloads_to_the_same_replay(
            self, tmp_path):
        vc = VerdictCache('fp', root=str(tmp_path))
        rows = shared_rows()
        for row in rows:
            vc.store(*row)
        row = vc.peek('d3')
        results, summary, policies = vc.replay(row, POLICIES3, 90)
        first = (copy.deepcopy(results), summary, policies)
        assert row['t'] == 90 and 'timestamp' not in row['r'][0]
        assert vc.replay(row, POLICIES3, 90)[0] is row['rt']
        vc.store('d9', 'default/pod-9', *ROW)  # dirty again
        vc.flush()
        doc = payload_of(vc)
        assert set(doc['r']['d3']) == {'u', 'r', 's', 'p'}
        again = VerdictCache('fp', root=str(tmp_path))
        assert again.replay(again.peek('d3'), POLICIES3, 90) == first
        later = again.replay(again.peek('d3'), POLICIES3, 91)
        assert [r['timestamp'] for r in later[0]] == \
            [{'seconds': 91}] * len(first[0])


class TestSnapshotEndToEnd:
    def test_pss_pack_replays_bit_identical_from_the_table(
            self, tmp_path, monkeypatch):
        """A real reconcile of the PSS pack over generated Pods and
        Deployments: a second controller loads the KTVC2 snapshot,
        replays every row without a scan, and stores the first
        controller's reports bit for bit."""
        import benchlib
        policies = benchlib.load_policies(['pss', 'pack', 'config4'])
        cluster = benchlib.load_module('generators', 'mixed_cluster') \
            .generate(2 ** 31 + 39, 192, deployment_share=0.3)

        def run(scan_ok):
            monkeypatch.setenv('KTPU_VERDICT_CACHE', '1')
            monkeypatch.setenv('KTPU_VERDICT_CACHE_DIR', str(tmp_path / 'vc'))
            ctrl = BackgroundScanController(FakeClient(), policies,
                                            cache=MetadataCache())
            if not scan_ok:
                monkeypatch.setattr(
                    ctrl.scanner, 'scan_report_results',
                    lambda *a, **k: pytest.fail('warm: must not scan'))
            for r in cluster:
                ctrl.cache.update(r)
            ctrl.enqueue_all()
            ctrl.reconcile(now=NOW)
            ctrl.close()
            out = {}
            for ns in {r['metadata']['namespace'] for r in cluster}:
                for rep in ctrl.client.list_resource(
                        'kyverno.io/v1alpha2', 'BackgroundScanReport', ns,
                        None):
                    out[(ns, rep['metadata']['name'])] = dict(
                        rep, metadata={
                            k: v for k, v in rep['metadata'].items()
                            if k not in ('resourceVersion', 'uid')})
            return ctrl, out

        first, reports = run(True)
        assert first.rescan_stats['rows_scanned'] == len(cluster)
        doc = payload_of(first.verdict_cache)
        assert len(doc['r']) == len(cluster)
        refs = sum(len(r['r']) for r in doc['r'].values())
        assert len(doc['t']) < refs / 2  # the flyweights, once each
        second, replayed = run(False)
        assert second.rescan_stats['rows_replayed'] == len(cluster)
        assert len(reports) == len(cluster)
        assert replayed == reports


# ---------------------------------------------------------------------------
# the snapshot log (KTVC3): a base frame, then one delta frame a flush


SHARED = _result('p-a', 'r1')


def log_row(i, variant=0):
    """Resource ``i`` at content ``variant``, as the fused path hands
    its row: a flyweight every row shares and a result of its own."""
    own = _result('p-b', 'r2', 'fail' if (i + variant) % 3 else 'pass',
                  f'row {i} v{variant}')
    results = [SHARED, own]
    return (f'd{i}-{variant}', f'uid-{i}', results, _summary(results),
            [0, 1])


def run_ops(vc, ops):
    for op, *args in ops:
        if op == 'put':
            vc.store(*log_row(*args))
        elif op == 'drop':
            vc.invalidate_uid(f'uid-{args[0]}')
        elif op == 'look':
            assert vc.lookup(log_row(*args)[0]) is not None
        else:
            vc.flush()


def puts(ids, variant=0):
    return [('put', i, variant) for i in ids]


def rows_of(vc):
    """The rows a cache holds, by value, and its uid index."""
    with vc._lock:
        rows = {d: {k: row[k] for k in ('u', 'r', 's', 'p')}
                for d, row in vc._rows.items()}
        by_uid = {u: set(ds) for u, ds in vc._by_uid.items()}
    return json.loads(json.dumps(rows)), by_uid


def full_reload(vc, root):
    """What a reload of ONE full snapshot of ``vc``'s memory holds: the
    rows stored into a fresh cache whose single flush writes them as
    one base frame."""
    one = VerdictCache(vc.fingerprint, root=str(root),
                       max_entries=vc.max_entries)
    with vc._lock:
        rows = list(vc._rows.items())
    for d, row in rows:
        one.store(d, row['u'], row['r'], row['s'], row['p'])
    assert one.flush()
    assert [k for k, _ in frames_of(one.path())[0]] == ['B']
    return VerdictCache(vc.fingerprint, root=str(root),
                        max_entries=vc.max_entries)


#: (memory cap, operations, the frames the file holds at the end)
LOG_SEQUENCES = {
    'one_delta': (None, puts(range(10)) + [
        ('flush',), ('drop', 3), ('put', 3, 1), ('put', 10, 0),
        ('flush',)], 'BD'),
    'many_deltas': (None, puts(range(20)) + [('flush',)] + [
        op for t in range(1, 6) for op in (
            ('drop', t), ('put', t, 1), ('put', 20 + t, 0), ('flush',))],
        'BDDDDD'),
    # 2 entries a tick against a base of 6 rows: the fourth tick's log
    # would hold 8, so it compacts, and the fifth appends to that base
    'deltas_then_compaction': (None, puts(range(6)) + [('flush',)] + [
        op for t in range(5) for op in (
            ('drop', t), ('put', t, 1), ('flush',))], 'BD'),
    # a digest dropped and stored again in one interval is stored; one
    # stored and dropped in one interval is dropped; a delta of drops
    # alone; a digest dropped by an earlier delta stored again
    'restore_dropped': (None, puts(range(6)) + [
        ('flush',), ('drop', 2), ('put', 2, 0), ('put', 7, 0),
        ('drop', 7), ('flush',), ('drop', 4), ('flush',), ('put', 4, 0),
        ('flush',)], 'BDDD'),
    # the memory front's LRU evicts d1 and d2 (d0 was refreshed), then
    # d3: a reload must not bring them back
    'memory_lru_eviction': (6, puts(range(6)) + [
        ('flush',), ('look', 0, 0), ('put', 6, 0), ('put', 7, 0),
        ('flush',), ('put', 8, 0), ('flush',)], 'BDD'),
}


class TestSnapshotLog:
    @pytest.mark.parametrize('name', list(LOG_SEQUENCES))
    def test_reload_of_the_log_equals_a_reload_of_one_snapshot(
            self, tmp_path, name):
        cap, ops, kinds = LOG_SEQUENCES[name]
        vc = VerdictCache('fp', root=str(tmp_path / 'log'),
                          max_entries=cap or 1000)
        run_ops(vc, ops)
        frames, tail = frames_of(vc.path())
        assert ''.join(k for k, _ in frames) == kinds and tail == 0
        # a frame's rows and drops are disjoint: the last change wins
        assert not [d for _, doc in frames
                    for d in doc.get('x', []) if d in doc['r']]
        again = VerdictCache('fp', root=str(tmp_path / 'log'),
                             max_entries=vc.max_entries)
        want = rows_of(full_reload(vc, tmp_path / 'one'))
        assert rows_of(again) == want == rows_of(vc)
        if name == 'memory_lru_eviction':
            assert not {'d1-0', 'd2-0', 'd3-0'} & set(want[0])
        # the rows of a frame share one dict per table entry of it
        with again._lock:
            shared = {id(row['r'][0]) for row in again._rows.values()}
        assert len(shared) <= len(frames)

    def test_a_tick_writes_what_it_changed(self, tmp_path):
        """1,000 rows resident, 10 of them changed: the delta holds the
        10 new rows and the 10 digests they replace, and each flush
        records exactly one of the two stages."""
        from kyverno_tpu.observability import device as devtel
        reg = devtel.configure(MetricsRegistry())
        try:
            vc = VerdictCache('fp', root=str(tmp_path))
            for i in range(1000):
                vc.store(*log_row(i))
            assert vc.flush()
            assert vc.last_flush == (2000, 1001)
            changed = range(0, 1000, 100)
            for i in changed:
                vc.invalidate_uid(f'uid-{i}')
                vc.store(*log_row(i, 1))
            assert vc.flush()
            assert vc.last_flush == (20, 11)
            assert not vc.flush()   # nothing changed: nothing written
            counts = {s: reg.histogram_count(
                devtel.SCAN_STAGE_DURATION, stage=s)
                for s in ('flush_compact', 'flush_delta')}
        finally:
            devtel.disable()
        assert counts == {'flush_compact': 1, 'flush_delta': 1}
        frames, _ = frames_of(vc.path())
        assert [k for k, _ in frames] == ['B', 'D']
        delta = frames[1][1]
        assert sorted(delta['r']) == sorted(f'd{i}-1' for i in changed)
        assert sorted(delta['x']) == sorted(f'd{i}-0' for i in changed)
        assert 'x' not in frames[0][1]

    @pytest.mark.parametrize('damage', ['cut', 'flipped'])
    def test_a_torn_tail_loads_the_last_complete_flush(self, tmp_path,
                                                       damage):
        vc = VerdictCache('fp', root=str(tmp_path / 'log'))
        run_ops(vc, puts(range(8)) + [('flush',), ('drop', 1),
                                      ('put', 1, 1), ('flush',)])
        want = rows_of(full_reload(vc, tmp_path / 'one'))
        run_ops(vc, [('drop', 2), ('put', 2, 1), ('put', 9, 0),
                     ('flush',)])
        raw = bytearray(open(vc.path(), 'rb').read())
        if damage == 'cut':   # the last append stopped part-way
            raw = raw[:-7]
        else:                 # a bit of the last frame's payload flipped
            raw[-3] ^= 0x40
        open(vc.path(), 'wb').write(bytes(raw))
        again = VerdictCache('fp', root=str(tmp_path / 'log'))
        assert rows_of(again) == want
        # nothing is appended after a torn tail: the next flush compacts
        run_ops(again, [('put', 10, 0), ('flush',)])
        frames, tail = frames_of(again.path())
        assert [k for k, _ in frames] == ['B'] and tail == 0
        assert rows_of(VerdictCache('fp', root=str(tmp_path / 'log'))) \
            == rows_of(again)

    def test_a_corrupt_base_loads_as_empty(self, tmp_path):
        vc = VerdictCache('fp', root=str(tmp_path))
        run_ops(vc, puts(range(8)) + [('flush',), ('drop', 1),
                                      ('put', 1, 1), ('flush',)])
        raw = bytearray(open(vc.path(), 'rb').read())
        raw[len(b'KTVC3\n') + 41 + 5] ^= 0x01   # inside the base payload
        open(vc.path(), 'wb').write(bytes(raw))
        fresh = VerdictCache('fp', root=str(tmp_path))
        assert len(fresh) == 0
        assert not os.path.exists(vc.path())

    def test_a_file_another_cache_evicted_is_rewritten_whole(self,
                                                             tmp_path):
        """Two live generations share a directory (the partitioned
        cache's members do): one's flush can evict the other's file by
        the byte budget, and the other's next flush must not append a
        delta to nothing."""
        a = VerdictCache('fp-a', root=str(tmp_path))
        run_ops(a, puts(range(10)) + [('flush',)])
        os.utime(a.path(), (1, 1))
        b = VerdictCache('fp-b', root=str(tmp_path), max_bytes=1)
        run_ops(b, puts(range(3)) + [('flush',)])
        assert not os.path.exists(a.path())
        run_ops(a, [('drop', 1), ('put', 1, 1), ('flush',)])
        frames, tail = frames_of(a.path())
        assert [k for k, _ in frames] == ['B'] and tail == 0
        assert rows_of(VerdictCache('fp-a', root=str(tmp_path))) == \
            rows_of(a)

    @pytest.mark.parametrize('budget', ['base_fits', 'log_fits'])
    def test_disk_eviction_counts_the_log(self, tmp_path, budget):
        """An older generation is evicted by its whole size, log
        included: a budget its base alone fits evicts it."""
        old = VerdictCache('fp-old', root=str(tmp_path))
        run_ops(old, puts(range(40)) + [('flush',)])
        base_size = os.path.getsize(old.path())
        run_ops(old, [op for t in range(3) for op in (
            ('drop', t), ('put', t, 1), ('flush',))])
        frames, _ = frames_of(old.path())
        assert [k for k, _ in frames] == ['B', 'D', 'D', 'D']
        old_size = os.path.getsize(old.path())
        assert old.stats()['snapshot_bytes'] == old_size > base_size
        os.utime(old.path(), (1, 1))  # age the old generation
        new = VerdictCache('fp-new', root=str(tmp_path))
        run_ops(new, puts(range(2)) + [('flush',)])
        new_size = os.path.getsize(new.path())
        new.max_bytes = new_size + (
            (base_size + old_size) // 2 if budget == 'base_fits'
            else old_size)
        new._evict_disk(keep=new.path())
        assert os.path.exists(old.path()) == (budget == 'log_fits')
        assert os.path.exists(new.path())
