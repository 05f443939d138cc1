"""Pipeline critical-path observatory (ISSUE 16).

Pins the observatory's contracts:

* with the recorder off (unconfigured or ``KTPU_TIMELINE=0``) the scan
  path is bit-identical to an armed run — zero-cost off;
* a multi-chunk scan leaves a fully-closed event timeline whose blame
  seconds sum to the scan wall (±5%), a registered ``bound_by``
  verdict, and the ``kyverno_tpu_pipeline_blame_seconds_total``
  counter;
* early generator close drains clean: no orphan open intervals, encode
  buffers return to the arena, the inflight gauge resets, and the next
  scan is unaffected;
* an injected stage fault surfaces as a ``retry`` event while rows
  stay complete;
* the Chrome-trace export validates against the trace-event schema
  subset (planted violations are caught) and
  ``scripts/timeline_report.py --check`` consumes the dumped file;
* encode workers (``KTPU_ENCODE_PROCS``) ship their stage
  timing home — capture, histogram and timeline all see the encode leg
  (the satellite-1 attribution fix).
"""

import importlib.util
import json
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import benchlib  # noqa: E402
from kyverno_tpu import faults  # noqa: E402
from kyverno_tpu.compiler.scan import BatchScanner  # noqa: E402
from kyverno_tpu.observability import device as devtel  # noqa: E402
from kyverno_tpu.observability import timeline as tlmod  # noqa: E402
from kyverno_tpu.observability.catalog import PIPELINE_STAGES  # noqa: E402
from kyverno_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from kyverno_tpu.reports.types import build_fused_report  # noqa: E402

CAP = 16  # tiny chunk capacity so a handful of pods spans many chunks
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')


def pods(n, seed=5):
    rng = random.Random(seed)
    return [mixed_cluster.make_pod(rng, i) for i in range(n)]


@pytest.fixture(scope='module')
def policies():
    return benchlib.load_policies(['pack'])


@pytest.fixture()
def scanner(policies):
    s = BatchScanner(policies)
    s.CHUNK = CAP
    return s


@pytest.fixture()
def recorder():
    rec = tlmod.configure(max_events=4096)
    assert rec is not None
    yield rec
    tlmod.disable()


def reports_of(scanner, docs, now=1234.0):
    return [build_fused_report(doc, *row)
            for doc, row in zip(docs, scanner.scan_report_results(
                docs, now=now))]


class TestOffIsFree:
    def test_disabled_timeline_is_bit_identical(self, scanner,
                                                monkeypatch):
        """Reports from an armed run match an unconfigured run match a
        ``KTPU_TIMELINE=0`` run byte-for-byte — the off branch really
        is the pre-observatory scan path."""
        docs = pods(2 * CAP + 3)
        tlmod.disable()
        baseline = reports_of(scanner, docs)
        rec = tlmod.configure(max_events=1024)
        try:
            armed = reports_of(scanner, docs)
            assert rec.n_scans >= 1  # the recorder did observe the scan
        finally:
            tlmod.disable()
        monkeypatch.setenv('KTPU_TIMELINE', '0')
        assert tlmod.configure() is None  # the env gate wins
        assert tlmod.recorder() is None
        gated = reports_of(scanner, docs)
        assert armed == baseline
        assert gated == baseline


class TestBlameAccounting:
    def test_multichunk_blame_sums_to_wall(self, scanner, recorder):
        registry = MetricsRegistry()
        devtel.configure(registry)
        try:
            docs = pods(3 * CAP + 1)
            rows = list(scanner.scan_report_results(docs))
        finally:
            devtel.disable()
        assert len(rows) == len(docs)
        assert recorder.n_scans == 1
        tl = recorder.scans()[-1]
        assert tl.open_count() == 0, 'orphan open exec intervals'
        summary = tl.summary
        assert summary is recorder.last_summary
        assert summary['bound_by'] in PIPELINE_STAGES
        assert set(summary['blame_s']) <= set(tlmod.STAGE_ORDER)
        total = sum(summary['blame_s'].values())
        # the walk bottoms out at the scan origin: blame ≈ wall
        assert total == pytest.approx(summary['wall_s'], rel=0.05)
        # executing + waiting partition each stage's blame
        for s, v in summary['blame_s'].items():
            assert summary['executing_s'][s] + summary['waiting_s'][s] \
                == pytest.approx(v, abs=1e-6)
        # exec events carry worker-thread identity across the legs
        threads = {e.thread for e in tl.events if e.kind == 'exec'}
        assert any(t.startswith('ktpu-pipe-') for t in threads)
        stages = {e.stage for e in tl.events if e.kind == 'exec'}
        for s in ('encode', 'device_eval', 'd2h'):
            assert s in stages, f'no exec interval for {s}'
        # the blame counter saw the same seconds
        assert registry.counter_total(tlmod.PIPELINE_BLAME) == \
            pytest.approx(total, rel=1e-6)


class TestEarlyClose:
    # the arena only holds buffers of chunks encoded in this process:
    # a chunk a pool worker encoded comes home as pickled tensors and
    # has nothing to return, so the two placements are pinned apart
    # (the default follows the host's CPU count)
    @pytest.mark.parametrize('procs', [0, 2],
                             ids=['inprocess', 'workers'])
    def test_early_generator_close_drains_clean(self, scanner, recorder,
                                                procs):
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
            assert recorder.n_scans == 1
            tl = recorder.scans()[-1]
            assert tl.open_count() == 0, \
                'early close left open exec intervals'
            assert tl.summary is not None  # finalized despite the abort
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
            assert recorder.scans()[-1].open_count() == 0
        finally:
            scanner._arena.release = inner_release
            scanner._encoder_pool.close()
            devtel.disable()


class TestRetries:
    def test_injected_fault_lands_as_retry_event(self, scanner,
                                                 recorder):
        docs = pods(3 * CAP)
        # warm first so compile/jit noise stays out of the fault scan
        for _ in scanner.scan_report_results(docs[:CAP]):
            pass
        # second device_eval dispatch of the scan below fails once; the
        # pipeline's per-chunk retry budget absorbs it
        faults.configure('site=device_eval,nth=2')
        try:
            rows = list(scanner.scan_report_results(docs))
        finally:
            faults.disable()
        assert len(rows) == len(docs), 'retry did not recover the chunk'
        tl = recorder.scans()[-1]
        retries = [e for e in tl.events if e.kind == 'retry']
        assert retries, 'injected fault produced no retry event'
        assert retries[0].stage == 'device_eval'
        assert retries[0].attempt >= 1
        assert tl.open_count() == 0
        total = sum(tl.summary['blame_s'].values())
        assert total == pytest.approx(tl.summary['wall_s'], rel=0.05)


class TestChromeTrace:
    def test_export_validates_and_roundtrips(self, scanner, recorder,
                                             tmp_path):
        docs = pods(2 * CAP + 1)
        rows = list(scanner.scan_report_results(docs))
        assert len(rows) == len(docs)
        trace = recorder.chrome_trace()
        assert tlmod.validate_chrome_trace(trace) == []
        names = {e['name'] for e in trace['traceEvents']
                 if e.get('ph') == 'X'}
        assert 'device_eval' in names and 'encode' in names
        # the offline analyzer reconstructs blame from the trace alone
        offline = tlmod.blame_from_chrome(trace)
        assert offline['bound_by'] in PIPELINE_STAGES
        assert offline['wall_s'] > 0
        path = str(tmp_path / 'trace.json')
        assert tlmod.dump_chrome_trace(path) == path
        with open(path) as fh:
            loaded = json.load(fh)
        assert tlmod.validate_chrome_trace(loaded) == []

    def test_validator_catches_planted_violations(self):
        ok = [{'ph': 'M', 'pid': 1, 'tid': 0, 'name': 'process_name',
               'args': {'name': 's'}},
              {'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 0.0, 'dur': 5.0,
               'name': 'encode'},
              {'ph': 'B', 'pid': 1, 'tid': 2, 'ts': 1.0, 'name': 'w'},
              {'ph': 'E', 'pid': 1, 'tid': 2, 'ts': 2.0, 'name': 'w'}]
        assert tlmod.validate_chrome_trace({'traceEvents': ok}) == []
        assert tlmod.validate_chrome_trace(
            [{'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 0.0,
              'name': 'encode'}])  # X without dur
        assert tlmod.validate_chrome_trace(
            [{'ph': 'X', 'pid': 1, 'tid': 1, 'ts': -1.0, 'dur': 1.0,
              'name': 'x'}])  # negative ts
        assert tlmod.validate_chrome_trace(
            [{'ph': 'E', 'pid': 1, 'tid': 1, 'ts': 1.0,
              'name': 'w'}])  # E without B
        assert tlmod.validate_chrome_trace(
            [{'ph': 'B', 'pid': 1, 'tid': 1, 'ts': 1.0,
              'name': 'w'}])  # unclosed B
        backwards = [{'ph': 'B', 'pid': 1, 'tid': 1, 'ts': 5.0,
                      'name': 'a'},
                     {'ph': 'E', 'pid': 1, 'tid': 1, 'ts': 1.0,
                      'name': 'a'}]
        assert any('monotonic' in e
                   for e in tlmod.validate_chrome_trace(backwards))
        assert tlmod.validate_chrome_trace({'nope': 1})  # no traceEvents

    def test_report_script_check_mode(self, scanner, recorder,
                                      tmp_path):
        docs = pods(CAP + 1)
        list(scanner.scan_report_results(docs))
        path = str(tmp_path / 'trace.json')
        assert tlmod.dump_chrome_trace(path) == path
        spec = importlib.util.spec_from_file_location(
            'timeline_report',
            os.path.join(REPO, 'scripts', 'timeline_report.py'))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.main([path, '--check']) == 0
        assert mod.main([path, '--json']) == 0
        assert mod.main([path]) == 0
        bad = str(tmp_path / 'bad.json')
        with open(bad, 'w') as fh:
            json.dump({'traceEvents': [{'ph': 'X', 'ts': 0.0,
                                        'name': 'x'}]}, fh)
        assert mod.main([bad, '--check']) == 1
        assert mod.main([str(tmp_path / 'missing.json'),
                         '--check']) == 2


class TestForkedEncodeAttribution:
    def test_forked_workers_ship_stage_time_home(self, policies,
                                                 recorder, monkeypatch):
        """KTPU_ENCODE_PROCS workers encode in their own process; their
        measured encode seconds must land in the ambient ScanCapture,
        the stage histogram and the timeline — not silently vanish
        (the regression this pins re-installed capture context on the
        process side)."""
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
            # the timeline shows the worker-process encode interval
            tl = recorder.scans()[-1]
            enc_threads = {e.thread for e in tl.events
                           if e.kind == 'exec' and e.stage == 'encode'}
            assert any(t.startswith('ktpu-encproc-')
                       for t in enc_threads), enc_threads
            # and the scan's critical path landed on the capture
            assert cap.critical_path is not None
            assert cap.critical_path['bound_by'] in PIPELINE_STAGES
        finally:
            scanner._encoder_pool.close()
            devtel.disable()
