"""Streaming scan pipeline: bounded overlapped stages + columnar encode
+ incremental report assembly.

Pins the tentpole contracts of the streaming rebuild:

* streaming output is byte-identical to the dense oracle at every chunk
  boundary shape (1, cap−1, cap, cap+1, 3·cap+1);
* host memory stays bounded while a 50k-row synthetic scan streams
  (tracemalloc, not RSS — allocator noise-free);
* a slow d2h leg BACKPRESSURES the pipeline (bounded queues, counted on
  kyverno_tpu_scan_backpressure_seconds_total) instead of buffering;
* the d2h stall watchdog and the flight-recorder dump still fire when
  the readback runs on a pipeline worker thread;
* verdict-cache replays interleave with miss chunks through the
  streaming reconcile;
* closing the row generator early drains the pipeline clean, an
  injected stage fault is retried without losing a row, and forked
  encode workers ship their stage time home.
"""

import json
import os
import random
import sys
import time
import tracemalloc

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import benchlib  # noqa: E402
from kyverno_tpu import faults  # noqa: E402
from kyverno_tpu.compiler.scan import BatchScanner  # noqa: E402
from kyverno_tpu.observability import device as devtel  # noqa: E402
from kyverno_tpu.observability import provenance  # noqa: E402
from kyverno_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from kyverno_tpu.reports.types import build_fused_report  # noqa: E402

CAP = 16  # tiny chunk capacity so a handful of pods spans many chunks
mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')


def pods(n, seed=5):
    rng = random.Random(seed)
    return [mixed_cluster.make_pod(rng, i) for i in range(n)]


@pytest.fixture(scope='module')
def policies():
    return benchlib.load_policies(['pack'])


@pytest.fixture()
def small_chunk_scanner(policies):
    scanner = BatchScanner(policies)
    scanner.CHUNK = CAP
    return scanner


def reports_of(scanner, docs, now=1234.0):
    return [build_fused_report(doc, *row)
            for doc, row in zip(docs, scanner.scan_report_results(
                docs, now=now))]


class TestChunkBoundaryIdentity:
    @pytest.mark.parametrize('n', [1, CAP - 1, CAP, CAP + 1, 3 * CAP + 1])
    def test_streaming_matches_dense_oracle(self, policies,
                                            small_chunk_scanner, n):
        """The multi-chunk pipeline at a tiny capacity produces reports
        byte-identical to the dense single-chunk oracle, in input
        order, at every boundary shape."""
        docs = pods(n)
        dense = BatchScanner(policies)   # default CHUNK: one chunk
        assert n <= dense.CHUNK
        expect = reports_of(dense, docs)
        got = reports_of(small_chunk_scanner, docs)
        assert len(got) == n
        assert got == expect

    def test_streaming_matches_unfused_responses(self, policies,
                                                 small_chunk_scanner):
        """Fused streaming rows == the unfused scan_stream +
        set_responses path across a chunk boundary (the report-fusion
        oracle, exercised through the pipeline)."""
        from kyverno_tpu.reports.results import set_responses
        from kyverno_tpu.reports.types import new_background_scan_report
        docs = pods(2 * CAP + 3)
        unfused = []
        for doc, responses in zip(docs,
                                  small_chunk_scanner.scan_stream(docs)):
            report = new_background_scan_report(doc)
            relevant = [r for r in responses if r.policy_response.rules]
            set_responses(report, *relevant)
            unfused.append(report)
        fused = reports_of(small_chunk_scanner, docs)
        assert len(fused) == len(unfused)

        def strip_ts(results):
            return [{k: v for k, v in r.items() if k != 'timestamp'}
                    for r in results]
        for f, u in zip(fused, unfused):
            assert f['metadata'].get('labels') == \
                u['metadata'].get('labels')
            assert f['spec']['summary'] == u['spec']['summary']
            assert strip_ts(f['spec']['results']) == \
                strip_ts(u['spec']['results'])


class TestBoundedMemory:
    def test_50k_scan_streams_in_bounded_memory(self, policies):
        """Python-heap growth while 50k rows stream through the report
        path stays at O(chunk), not O(n): the arena recycles lane
        tensors and rows flush as chunks land."""
        scanner = BatchScanner(policies)
        scanner.CHUNK = 4096
        docs = pods(50_000, seed=11)
        # warm: compile + allocate the arena outside the measurement
        for _ in scanner.scan_report_results(docs[:8192]):
            pass
        tracemalloc.start()
        base, _ = tracemalloc.get_traced_memory()
        n_rows = 0
        for _row in scanner.scan_report_results(docs):
            n_rows += 1
        _cur, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert n_rows == len(docs)
        growth_mb = (peak - base) / 1e6
        # 50k decoded rows at ~2KB each would be ≥100MB; the streaming
        # path must hold only a few chunks of lanes + one flush window
        assert growth_mb < 64, f'heap grew {growth_mb:.1f}MB over stream'


class _SlowReadback:
    """Wraps a jax output array; np.array() pays an injected delay —
    an artificially slowed d2h leg."""

    def __init__(self, arr, delay_s):
        self._arr = arr
        self._delay_s = delay_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self._delay_s)
        out = np.asarray(self._arr)
        return out.astype(dtype) if dtype is not None else out


def _slow_d2h(scanner, delay_s):
    inner = scanner._evaluator

    def slow(t, layout):
        return [_SlowReadback(o, delay_s) for o in inner(t, layout)]
    for attr in ('adm_cols', 'n_uniq', 'any_meta', 'n_cols_u', 'uniq_idx',
                 'expand_idx', 'expand_identity', 'adm_table'):
        setattr(slow, attr, getattr(inner, attr, None))
    slow.n_adm = getattr(inner, 'n_adm', 0)
    scanner._evaluator = slow
    return inner


class TestBackpressure:
    def test_slow_d2h_backpressures_intake(self, policies):
        """With the d2h leg artificially slowed, the bounded queues
        push back on the upstream stages: blocked time lands on the
        backpressure counter, the in-flight gauge tops out at
        KTPU_PIPELINE_DEPTH, and output is still complete and
        in-order."""
        registry = MetricsRegistry()
        devtel.configure(registry)
        try:
            scanner = BatchScanner(policies)
            scanner.CHUNK = CAP
            docs = pods(8 * CAP)
            for _ in scanner.scan_report_results(docs[:CAP]):
                pass  # warm the executable so the slow run measures d2h
            _slow_d2h(scanner, 0.05)
            rows = list(scanner.scan_report_results(docs))
            assert len(rows) == len(docs)
            total_bp = registry.counter_total(
                'kyverno_tpu_scan_backpressure_seconds_total')
            assert total_bp > 0.0, \
                'slow d2h produced no backpressure accounting'
            # the gauge always resets when the stream ends
            assert registry.gauge_value(
                'kyverno_tpu_scan_pipeline_inflight_chunks') == 0.0
        finally:
            devtel.disable()


class TestWatchdogFromWorkers:
    def test_stall_watchdog_fires_on_pipeline_thread(self, policies,
                                                     tmp_path):
        """A stalled readback inside the pipeline's d2h worker still
        trips the watchdog AND the flight-recorder dump — the
        provenance capture and event-sink chain survive the move onto
        worker threads."""
        registry = MetricsRegistry()
        devtel.configure(registry, stall_threshold_s=0.02)
        recorder = provenance.configure(registry, flight_n=8,
                                        dump_dir=str(tmp_path))
        events = []
        devtel.add_event_sink(events.append)
        try:
            scanner = BatchScanner(policies)
            scanner.CHUNK = CAP
            docs = pods(3 * CAP)
            for _ in scanner.scan_report_results(docs[:CAP]):
                pass  # warm compile outside the stall window
            _slow_d2h(scanner, 0.2)
            cap = devtel.ScanCapture()
            with devtel.install_capture(cap):
                rows = list(scanner.scan_report_results(docs))
            assert len(rows) == len(docs)
            stalls = [e for e in events if e.get('type') == 'd2h_stall']
            assert stalls, 'watchdog never fired from the worker thread'
            assert registry.counter_total(
                'kyverno_tpu_d2h_stalls_total') >= 1
            # the flight recorder dumped on the same event chain
            assert recorder.dump_paths, 'no flight-recorder dump'
            lines = [json.loads(x) for x in open(recorder.dump_paths[0])]
            assert lines[0]['trigger'] == 'd2h_stall'
            # stage time kept flowing into the installed capture from
            # the worker threads (provenance threading preserved)
            assert cap.stage_s('d2h') > 0.0
            assert cap.stage_s('encode') > 0.0
        finally:
            devtel.remove_event_sink(events.append)
            provenance.disable()
            devtel.disable()


class TestReplayInterleavedWithMisses:
    def test_reconcile_replays_between_miss_chunks(self, tmp_path,
                                                   monkeypatch):
        """A reconcile whose pending set mixes cache hits and misses
        spanning several device chunks replays the hits inline and
        streams the misses — reports byte-identical to a cache-off
        dense reconcile."""
        from kyverno_tpu.dclient.client import FakeClient
        from kyverno_tpu.reports.controllers import (
            BackgroundScanController)
        monkeypatch.setenv('KTPU_VERDICT_CACHE_DIR', str(tmp_path / 'vc'))
        policies = benchlib.load_policies(['pack'])
        docs = pods(3 * CAP + 5, seed=17)
        for i, d in enumerate(docs):
            d['metadata']['uid'] = f'uid-{i}'

        def build(enabled):
            monkeypatch.setenv('KTPU_VERDICT_CACHE',
                               '1' if enabled else '0')
            ctrl = BackgroundScanController(FakeClient(), policies)
            ctrl.scanner.CHUNK = CAP
            return ctrl

        ctrl = build(True)
        for d in docs:
            ctrl.enqueue(d)
        ctrl.reconcile(now=2000.0)  # cold tick: populate the cache
        # mutate a slice spread across chunk boundaries → misses, the
        # rest replays
        changed = list(range(0, len(docs), 3))
        for i in changed:
            docs[i]['spec']['containers'][0]['image'] = f'churn:{i}'
        ctrl.reset_scan_state()
        for d in docs:
            ctrl.enqueue(d)
        reports = ctrl.reconcile(now=2031.0)
        assert ctrl.rescan_stats['rows_scanned'] == len(changed)
        assert ctrl.rescan_stats['rows_replayed'] == \
            len(docs) - len(changed)

        dense = build(False)
        for d in docs:
            dense.enqueue(d)
        dense_reports = dense.reconcile(now=2031.0)

        def content(r):
            # strip fake-server bookkeeping (resourceVersion/uid differ
            # between create and update writes); everything the scan
            # produced must match exactly
            meta = {k: v for k, v in r['metadata'].items()
                    if k not in ('resourceVersion', 'uid')}
            return dict(r, metadata=meta)

        key = lambda r: r['metadata']['name']  # noqa: E731
        assert [content(r) for r in sorted(reports, key=key)] == \
            [content(r) for r in sorted(dense_reports, key=key)]


class TestEarlyClose:
    # the arena only holds buffers of chunks encoded in this process:
    # a chunk a pool worker encoded comes home as pickled tensors and
    # has nothing to return, so the two placements are pinned apart
    # (the default follows the host's CPU count)
    @pytest.mark.parametrize('procs', [0, 2],
                             ids=['inprocess', 'workers'])
    def test_early_generator_close_drains_clean(self, small_chunk_scanner,
                                                procs):
        scanner = small_chunk_scanner
        registry = MetricsRegistry()
        devtel.configure(registry)
        scanner._encoder_pool.procs = procs
        released = []
        inner_release = scanner._arena.release

        def counting_release(batch):
            released.append(1)
            return inner_release(batch)
        scanner._arena.release = counting_release
        try:
            docs = pods(4 * CAP)
            gen = scanner.scan_report_results(docs)
            next(gen)
            gen.close()
            if procs == 0:
                assert released, \
                    'early close returned no buffers to arena'
            else:
                assert not scanner._encoder_pool._broken
                assert not released, \
                    'worker-encoded chunks own no arena buffers'
            assert registry.gauge_value(
                'kyverno_tpu_scan_pipeline_inflight_chunks') == 0.0
            # the scanner is fully reusable after the abort
            rows = list(scanner.scan_report_results(docs))
            assert len(rows) == len(docs)
        finally:
            scanner._arena.release = inner_release
            scanner._encoder_pool.close()
            devtel.disable()


class TestRetries:
    def test_injected_fault_lands_as_retry_event(self,
                                                 small_chunk_scanner):
        scanner = small_chunk_scanner
        docs = pods(3 * CAP)
        # warm first so compile/jit noise stays out of the fault scan
        for _ in scanner.scan_report_results(docs[:CAP]):
            pass
        registry = MetricsRegistry()
        devtel.configure(registry)
        # second device_eval dispatch of the scan below fails once; the
        # pipeline's per-chunk retry budget absorbs it
        faults.configure('site=device_eval,nth=2')
        try:
            rows = list(scanner.scan_report_results(docs))
        finally:
            faults.disable()
            devtel.disable()
        assert len(rows) == len(docs), 'retry did not recover the chunk'
        assert registry.counter_value(devtel.STAGE_RETRIES,
                                      stage='device_eval') >= 1


class TestForkedEncodeAttribution:
    def test_forked_workers_ship_stage_time_home(self, policies,
                                                 monkeypatch):
        """KTPU_ENCODE_PROCS workers encode in their own process; their
        measured encode seconds must land in the ambient ScanCapture,
        not silently vanish (the regression this pins re-installed
        capture context on the process side)."""
        monkeypatch.setenv('KTPU_ENCODE_PROCS', '1')
        registry = MetricsRegistry()
        devtel.configure(registry)
        scanner = BatchScanner(policies)
        scanner.CHUNK = CAP
        scanner.ENCODE_TIMEOUT_S = 60
        try:
            docs = pods(3 * CAP)
            cap = devtel.ScanCapture()
            with devtel.install_capture(cap):
                rows = list(scanner.scan_report_results(docs))
            assert len(rows) == len(docs)
            assert not scanner._encoder_pool._broken, \
                'encode pool fell back to in-process'
            # capture attribution survived the process boundary
            assert cap.stage_s('encode') > 0.0
        finally:
            scanner._encoder_pool.close()
            devtel.disable()
