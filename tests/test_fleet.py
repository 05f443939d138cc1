"""Fleet observatory unit surface (ISSUE 18): federation merge rules,
the registry series-cardinality guard, skew-analyzer rate limiting,
the persistent-cache feature guard, and the straggler note.

The mesh-integration half (bit-identity under KTPU_FLEET=0, injected
straggler blame, endpoint/CLI agreement) lives in
tests/test_distributed.py against the conftest 8-device mesh.
"""

import os

import pytest

from kyverno_tpu.observability import fleet
from kyverno_tpu.observability.metrics import (SERIES_DROPPED,
                                               MetricsRegistry,
                                               global_registry,
                                               set_global_registry)


# -- series-cardinality guard (KTPU_METRIC_SERIES_MAX) ------------------------

class TestSeriesCardinalityGuard:
    def test_new_series_beyond_cap_refused_and_counted(self, monkeypatch):
        monkeypatch.setenv('KTPU_METRIC_SERIES_MAX', '3')
        reg = MetricsRegistry()
        for i in range(5):
            reg.inc('kyverno_tpu_test_total', path=str(i))
        snap = reg.snapshot()
        assert len(snap['counters']['kyverno_tpu_test_total']) == 3
        assert reg.counter_value(
            SERIES_DROPPED, metric='kyverno_tpu_test_total') == 2.0
        # existing series keep updating after the cap is hit
        reg.inc('kyverno_tpu_test_total', path='0')
        assert reg.counter_value('kyverno_tpu_test_total', path='0') == 2.0
        # no further drops for the update
        assert reg.counter_value(
            SERIES_DROPPED, metric='kyverno_tpu_test_total') == 2.0

    def test_guard_covers_gauges_and_histograms(self, monkeypatch):
        monkeypatch.setenv('KTPU_METRIC_SERIES_MAX', '2')
        reg = MetricsRegistry()
        for i in range(4):
            reg.set_gauge('kyverno_tpu_test_ratio', 1.0, shard=str(i))
            reg.observe('kyverno_tpu_test_seconds', 0.1, shard=str(i))
        snap = reg.snapshot()
        assert len(snap['gauges']['kyverno_tpu_test_ratio']) == 2
        assert len(snap['hists']['kyverno_tpu_test_seconds']['series']) == 2
        assert reg.counter_value(
            SERIES_DROPPED, metric='kyverno_tpu_test_ratio') == 2.0
        assert reg.counter_value(
            SERIES_DROPPED, metric='kyverno_tpu_test_seconds') == 2.0

    def test_drop_counter_bypasses_its_own_cap(self, monkeypatch):
        monkeypatch.setenv('KTPU_METRIC_SERIES_MAX', '1')
        reg = MetricsRegistry()
        # overflow three different metrics: the drop counter needs one
        # series per overflowed metric, beyond its own cap of 1
        for name in ('kyverno_tpu_a_total', 'kyverno_tpu_b_total',
                     'kyverno_tpu_c_total'):
            reg.inc(name, k='0')
            reg.inc(name, k='1')
        assert len(reg.snapshot()['counters'][SERIES_DROPPED]) == 3


# -- federation merge rules ---------------------------------------------------

def _snap(ident, counters=(), gauges=(), residency=(), hists=()):
    reg = MetricsRegistry()
    for name, value, labels in counters:
        reg.inc(name, value, **labels)
    for name, value, labels in gauges:
        reg.set_gauge(name, value, **labels)
    for name in residency:
        reg.mark_reset_on_close(name)
    for name, buckets, samples in hists:
        reg.register_histogram(name, buckets)
        for value, labels in samples:
            reg.observe(name, value, **labels)
    return reg.snapshot(ident)


class TestFederationMerge:
    def test_counters_sum_gauges_follow_residency(self):
        a = _snap({'host': 'a', 'pid': 1, 'process_index': 0},
                  counters=[('c_total', 2.0, {'path': 'x'})],
                  gauges=[('queue_depth', 3.0, {}), ('ratio', 0.5, {})],
                  residency=['queue_depth'])
        b = _snap({'host': 'b', 'pid': 2, 'process_index': 1},
                  counters=[('c_total', 5.0, {'path': 'x'})],
                  gauges=[('queue_depth', 4.0, {}), ('ratio', 0.9, {})],
                  residency=['queue_depth'])
        merged = fleet.FleetRegistry.merge([a, b])
        totals = fleet.FleetRegistry.counter_totals(merged)
        assert totals['c_total'] == 7.0
        gauges = {name: sum(v for _k, v in entries)
                  for name, entries in merged['gauges'].items()}
        # residency gauge: fleet occupancy is the sum of per-host
        # occupancy; state gauge: max (an average describes no process)
        assert gauges['queue_depth'] == 7.0
        assert gauges['ratio'] == 0.9
        assert merged['reset_on_close'] == ['queue_depth']
        assert len(merged['identities']) == 2

    def test_histograms_merge_bucketwise(self):
        buckets = (0.1, 1.0)
        a = _snap({'host': 'a', 'pid': 1, 'process_index': 0},
                  hists=[('h_seconds', buckets,
                          [(0.05, {'shard': '0'}), (0.5, {'shard': '0'})])])
        b = _snap({'host': 'b', 'pid': 2, 'process_index': 1},
                  hists=[('h_seconds', buckets,
                          [(0.05, {'shard': '0'})])])
        merged = fleet.FleetRegistry.merge([a, b])
        h = merged['hists']['h_seconds']
        assert h['bucket_conflict'] is False
        [entry] = h['series']
        assert entry[1] == 3          # count
        assert entry[2] == pytest.approx(0.6)
        assert entry[3] == [2, 3]     # cumulative bucket counts summed

    def test_bucket_conflict_flagged_not_fabricated(self):
        a = _snap({'host': 'a', 'pid': 1, 'process_index': 0},
                  hists=[('h_seconds', (0.1, 1.0), [(0.5, {})])])
        b = _snap({'host': 'b', 'pid': 2, 'process_index': 1},
                  hists=[('h_seconds', (0.2, 2.0, 5.0), [(0.5, {})])])
        merged = fleet.FleetRegistry.merge([a, b])
        h = merged['hists']['h_seconds']
        assert h['bucket_conflict'] is True
        # count/sum still compose even when buckets cannot
        [entry] = h['series']
        assert entry[1] == 2 and entry[2] == pytest.approx(1.0)

    def test_merge_is_associative_over_merged_docs(self):
        docs = [
            _snap({'host': h, 'pid': p, 'process_index': i},
                  counters=[('c_total', v, {})],
                  gauges=[('g', g, {})],
                  hists=[('h_seconds', (0.1, 1.0), [(v / 10.0, {})])])
            for h, p, i, v, g in (('a', 1, 0, 1.0, 0.2),
                                  ('b', 2, 1, 2.0, 0.4),
                                  ('c', 3, 2, 4.0, 0.8))]
        flat = fleet.FleetRegistry.merge(docs)
        nested = fleet.FleetRegistry.merge(
            [fleet.FleetRegistry.merge(docs[:2]), docs[2]])
        assert nested == flat

    def test_add_snapshot_is_idempotent_per_identity(self):
        fr = fleet.FleetRegistry()
        doc = _snap({'host': 'a', 'pid': 1, 'process_index': 0},
                    counters=[('c_total', 3.0, {})])
        fr.add_snapshot(doc)
        fr.add_snapshot(dict(doc))  # re-announce: replaces, not doubles
        merged = fr.merged()
        assert fleet.FleetRegistry.counter_totals(merged) == \
            {'c_total': 3.0}
        assert len(merged['identities']) == 1

    def test_snapshot_file_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.inc('c_total', 2.0, mesh='data8')
        path = str(tmp_path / 'host.jsonl')
        fleet.write_snapshot(path, reg)
        fleet.write_snapshot(path, reg)  # JSONL appends
        docs = fleet.read_snapshot_files([path])
        assert len(docs) == 2
        assert all(fleet.FleetRegistry.counter_totals(d) ==
                   {'c_total': 2.0} for d in docs)


# -- skew analyzer ------------------------------------------------------------

class TestSkewAnalyzer:
    DEVICES = [f'dev{i}' for i in range(4)]

    def test_balanced_walls_never_sustain(self):
        an = fleet.SkewAnalyzer(window=2,
                                profile_trigger=lambda: None)
        for _ in range(4):
            v = an.fold('data4', [0.1, 0.1, 0.1, 0.1], self.DEVICES)
        assert v['skew'] == 1.0
        assert v['sustained'] is False
        assert 'bound_by' not in v

    def test_sustained_fire_is_rate_limited(self):
        clock = [0.0]
        fired = []
        an = fleet.SkewAnalyzer(window=2, now=lambda: clock[0],
                                profile_trigger=lambda: fired.append(1))
        skewed = [0.9, 0.1, 0.1, 0.1]
        balanced = [0.1, 0.1, 0.1, 0.1]
        for _ in range(2):
            v = an.fold('data4', skewed, self.DEVICES)
        assert v['sustained'] and v['slow_shard'] == 0
        assert v['device'] == 'dev0'
        # the capture thread is synchronous enough to join via verdict
        import time
        deadline = time.monotonic() + 5.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fired == [1]
        # drop to balanced (sustained falls), re-skew inside the
        # rate-limit interval: no second capture
        for _ in range(2):
            an.fold('data4', balanced, self.DEVICES)
        for _ in range(2):
            an.fold('data4', skewed, self.DEVICES)
        assert fired == [1]
        # past the interval the next False->True transition fires again
        clock[0] = fleet.PROFILE_MIN_INTERVAL_S + 1.0
        for _ in range(2):
            an.fold('data4', balanced, self.DEVICES)
        for _ in range(2):
            an.fold('data4', skewed, self.DEVICES)
        deadline = time.monotonic() + 5.0
        while len(fired) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fired == [1, 1]
        assert an.auto_profiles == 2

    def test_windows_are_per_mesh_shape(self):
        an = fleet.SkewAnalyzer(window=2, profile_trigger=lambda: None)
        an.fold('data4', [0.9, 0.1, 0.1, 0.1], self.DEVICES)
        # one skewed step on another mesh must not inherit data4's
        # window history
        v = an.fold('data8', [0.9] + [0.1] * 7,
                    [f'dev{i}' for i in range(8)])
        assert v['sustained'] is False

    def test_window_knob_floor(self, monkeypatch):
        monkeypatch.setenv('KTPU_FLEET_SKEW_WINDOW', '0')
        an = fleet.SkewAnalyzer(profile_trigger=lambda: None)
        assert an.window == 2
        monkeypatch.setenv('KTPU_FLEET_SKEW_WINDOW', 'junk')
        assert fleet.SkewAnalyzer(profile_trigger=lambda: None).window == 16


# -- persistent-cache feature guard -------------------------------------------

class TestCacheFeatureGuard:
    def test_mismatched_hostkey_rejects_and_rescopes(self, tmp_path):
        from kyverno_tpu.aotcache import keys
        prev = global_registry()
        reg = MetricsRegistry()
        set_global_registry(reg)
        try:
            cache_dir = str(tmp_path / 'xla')
            os.makedirs(cache_dir)
            fp = keys.host_fingerprint()
            # fresh dir: marker written, dir accepted as-is
            used, rejected = keys.verify_cache_feature_scope(cache_dir)
            assert (used, rejected) == (cache_dir, False)
            marker = os.path.join(cache_dir, keys.HOSTKEY_FILE)
            assert open(marker).read().strip() == fp
            # matching marker: accepted again, nothing counted
            assert keys.verify_cache_feature_scope(cache_dir) == \
                (cache_dir, False)
            assert reg.counter_total(keys.AOT_LOAD_REJECTED) == 0.0
            # a dir populated by a different CPU feature set: rejected,
            # counted, and re-scoped to a feat-<digest> subdir with its
            # own matching marker
            with open(marker, 'w') as f:
                f.write('feedface00')
            used3, rejected3 = keys.verify_cache_feature_scope(cache_dir)
            assert rejected3 is True
            assert used3 == os.path.join(cache_dir, f'feat-{fp}')
            assert reg.counter_value(
                keys.AOT_LOAD_REJECTED,
                reason='feature_mismatch') == 1.0
            assert open(os.path.join(
                used3, keys.HOSTKEY_FILE)).read().strip() == fp
            # the re-scoped dir now verifies clean
            assert keys.verify_cache_feature_scope(used3) == \
                (used3, False)
        finally:
            set_global_registry(prev)


# -- straggler note -----------------------------------------------------------

class TestStragglerAdvice:
    def test_straggler_branch_names_the_shard(self):
        an = fleet.SkewAnalyzer(window=2, now=lambda: 0.0,
                                profile_trigger=lambda: None)
        for _ in range(2):
            v = an.fold('2x2', [1.0, 1.0, 1.0, 4.0],
                        ['TPU_0', 'TPU_1', 'TPU_2', 'TPU_3'])
        assert v['sustained']
        assert v['bound_by'] == 'straggler'
        assert v['suggest'] == {}  # no host-pipeline knob fixes a slow chip
        # the wording operators alert on, held to the letter
        assert v['note'] == (
            'mesh straggler: shard 3 (TPU_3) carries 56% excess '
            'device-eval wall over the skew window — rebalance or drain '
            'that device/host; deepening the host pipeline cannot help '
            'a slow shard')
