"""Device-pipeline telemetry: stage spans over the batched scan path,
compile-cache counters, the d2h stall watchdog, and the zero-overhead
no-op guarantees when tracing/metrics are unconfigured."""

import threading
import time

import pytest

from kyverno_tpu.api.policy import Policy
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability import tracing
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


@pytest.fixture
def telemetry():
    mem = tracing.configure()
    reg = devtel.configure(MetricsRegistry())
    yield mem, reg
    devtel.disable()
    tracing.disable()


@pytest.fixture
def scanner():
    from kyverno_tpu.compiler.scan import BatchScanner
    return BatchScanner([Policy(POLICY)])


def _watchdog_threads():
    return [t for t in threading.enumerate()
            if t.name == 'ktpu-d2h-watchdog']


class TestStageSpans:
    def test_scan_emits_all_stages(self, telemetry, scanner):
        mem, reg = telemetry
        # first scan pays the compile stage; the second hits the cached
        # executable and runs as device_eval
        scanner.scan([pod(i) for i in range(8)])
        scanner.scan([pod(i) for i in range(8)])
        names = {s.name for s in mem.spans()}
        assert 'kyverno/device/compile' in names
        assert reg.histogram_count(
            'kyverno_tpu_scan_stage_duration_seconds',
            stage='compile') >= 1
        for stage in ('encode', 'pack', 'h2d', 'device_eval', 'd2h',
                      'report'):
            assert f'kyverno/device/{stage}' in names, stage
            assert reg.histogram_count(
                'kyverno_tpu_scan_stage_duration_seconds',
                stage=stage) >= 1, stage

    def test_stage_spans_join_one_trace(self, telemetry, scanner):
        """request root → chunk wrapper → device stage spans all carry
        one trace id (the single-trace requirement of the pipeline)."""
        mem, _reg = telemetry
        scanner.scan([pod(i) for i in range(4)])  # warm the executable
        with tracing.start_span('request-root') as root:
            scanner.scan([pod(i) for i in range(4)])
        by_name = {}
        for s in mem.spans():
            by_name.setdefault(s.name, []).append(s)
        [chunk] = [s for s in by_name['kyverno/device/chunk']
                   if s.trace_id == root.trace_id]
        # the chunk wrapper nests under the per-chunk scan span, which
        # nests under the request root
        parents = {s.span_id: s for s in mem.spans()}
        scan_span = parents[chunk.parent_id]
        assert scan_span.name == 'kyverno/device/scan'
        assert scan_span.parent_id == root.span_id
        for stage in ('pack', 'h2d', 'device_eval', 'd2h'):
            stage_spans = [s for s in by_name[f'kyverno/device/{stage}']
                           if s.trace_id == root.trace_id]
            assert stage_spans, stage
            assert all(s.parent_id == chunk.span_id
                       for s in stage_spans), stage

    def test_compile_cache_counters(self, telemetry):
        _mem, reg = telemetry
        from kyverno_tpu.compiler.scan import BatchScanner
        fresh = BatchScanner([Policy(POLICY)])
        fresh.scan([pod(i) for i in range(4)])   # compiles or aot-loads
        fresh.scan([pod(i) for i in range(4)])   # memory hit
        total = reg.counter_total(
            'kyverno_tpu_compile_cache_requests_total')
        hits = reg.counter_value(
            'kyverno_tpu_compile_cache_requests_total', result='hit')
        assert total >= 2
        assert hits >= 1
        text = reg.render()
        assert 'kyverno_tpu_compile_cache_requests_total' in text
        assert 'result="hit"' in text

    def test_batch_size_and_d2h_bytes(self, telemetry, scanner):
        _mem, reg = telemetry
        scanner.scan([pod(i) for i in range(8)])
        assert reg.gauge_value('kyverno_tpu_device_batch_size') == 8.0
        assert reg.counter_total('kyverno_tpu_d2h_bytes_total') > 0


class TestWatchdog:
    def test_fires_on_delayed_d2h(self):
        fired = []
        tracing.disable()
        reg = devtel.configure(MetricsRegistry(), stall_threshold_s=0.05,
                               event_sink=fired.append)
        try:
            with devtel.d2h_guard({'chunk_start': 0}):
                time.sleep(0.25)  # artificially delayed readback
            deadline = time.time() + 2.0
            while not fired and time.time() < deadline:
                time.sleep(0.01)
            assert reg.counter_total('kyverno_tpu_d2h_stalls_total') == 1
            [event] = fired
            assert event['type'] == 'd2h_stall'
            assert event['elapsed_s'] >= 0.05
            assert event['chunk_start'] == 0
            assert devtel.watchdog().stall_events
        finally:
            devtel.disable()

    def test_silent_under_threshold(self):
        fired = []
        reg = devtel.configure(MetricsRegistry(), stall_threshold_s=0.5,
                               event_sink=fired.append)
        try:
            for _ in range(3):
                with devtel.d2h_guard():
                    time.sleep(0.01)
            time.sleep(0.2)  # give the monitor a chance to misfire
            assert reg.counter_total('kyverno_tpu_d2h_stalls_total') == 0
            assert not fired
        finally:
            devtel.disable()

    def test_fires_once_per_stall(self):
        reg = devtel.configure(MetricsRegistry(), stall_threshold_s=0.03)
        try:
            with devtel.d2h_guard():
                time.sleep(0.2)
            time.sleep(0.1)
            assert reg.counter_total('kyverno_tpu_d2h_stalls_total') == 1
        finally:
            devtel.disable()

    def test_env_default_threshold(self, monkeypatch):
        monkeypatch.setenv('KTPU_D2H_STALL_S', '7.5')
        devtel.configure(MetricsRegistry())
        try:
            assert devtel.watchdog().threshold_s == 7.5
        finally:
            devtel.disable()

    def test_thread_stops_on_disable(self):
        devtel.configure(MetricsRegistry(), stall_threshold_s=10.0)
        token = devtel.watchdog().arm()
        assert _watchdog_threads()
        devtel.watchdog().disarm(token)
        devtel.disable()
        deadline = time.time() + 2.0
        while _watchdog_threads() and time.time() < deadline:
            time.sleep(0.01)
        assert not _watchdog_threads()

    def test_stop_clears_thread_under_lock(self):
        """`stop()` must write `_thread` under the condition variable
        (arm() reads and writes it there); after stop the slot is
        cleared, a second stop is a no-op, and a post-stop arm is
        refused without resurrecting the thread."""
        from kyverno_tpu.observability.device import D2HWatchdog
        wd = D2HWatchdog(threshold_s=10.0)
        token = wd.arm()
        assert token >= 0 and wd._thread is not None
        wd.disarm(token)
        wd.stop()
        assert wd._thread is None
        wd.stop()  # idempotent
        assert wd.arm() == -1  # stopped watchdogs refuse new arms
        assert wd._thread is None


class TestNoopWhenUnconfigured:
    def test_scan_allocates_nothing(self, scanner):
        tracing.disable()
        devtel.disable()
        before = set(threading.enumerate())
        scanner.scan([pod(i) for i in range(8)])
        assert tracing.memory_exporter() is None
        assert devtel.registry() is None
        assert devtel.watchdog() is None
        assert not _watchdog_threads()
        # only the scan pipeline's own executor threads may appear —
        # no telemetry thread survives the call
        after = {t for t in threading.enumerate() if t not in before}
        assert not any(t.name == 'ktpu-d2h-watchdog' for t in after)
        assert devtel.stage_breakdown() == {}
        # ... and the reports of a scan are the same, bit for bit, with
        # every sink of the stages on (histogram, spans, the profiler's
        # marks) as with all of them off
        docs = [pod(i) for i in range(8)]
        off = list(scanner.scan_report_results(docs, now=1234.0))
        tracing.configure()
        devtel.configure(MetricsRegistry())
        try:
            on = list(scanner.scan_report_results(docs, now=1234.0))
            assert devtel.stage_breakdown()['store']['count'] == 1
        finally:
            devtel.disable()
            tracing.disable()
        assert on == off

    def test_stage_returns_shared_noop(self):
        tracing.disable()
        devtel.disable()
        s1 = devtel.stage('pack')
        s2 = devtel.stage('d2h')
        g = devtel.d2h_guard()
        mark = devtel.annotation('store', chunk=0)
        assert s1 is s2 is g is mark  # one shared no-op, no allocation
        with s1:
            s1.set_attribute('k', 'v')
            s1.add_d2h_bytes(10)
        devtel.record_stage('store', 1.0)  # no registry, no capture
        assert devtel.stage_breakdown() == {}

    def test_a_stage_never_loads_jax(self):
        """The encoder workers' fork server imports compiler/encode.py
        and nothing of jax; a stage opened there (a capture is
        installed, so it is a real one) must not change that: the
        profiler's mark is looked up only where jax is already loaded."""
        import os
        import subprocess
        import sys
        done = subprocess.run(
            [sys.executable, '-c',
             'import sys\n'
             'import kyverno_tpu.compiler.encode\n'
             'from kyverno_tpu.observability import device as d\n'
             'with d.install_capture(d.ScanCapture()) as cap:\n'
             '    with d.stage("encode"):\n'
             '        pass\n'
             'print("jax" in sys.modules, sorted(cap.stages))\n'],
            capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert done.stdout.strip() == "False ['encode']", done.stderr

    def test_tracing_only_emits_spans_not_series(self, scanner):
        devtel.disable()
        mem = tracing.configure()
        try:
            scanner.scan([pod(i) for i in range(4)])
            assert any(s.name.startswith('kyverno/device/')
                       for s in mem.spans())
            assert devtel.registry() is None
        finally:
            tracing.disable()


# -- the stages on the profiler's clock -------------------------------------

CAP = 16     # rows a chunk, so a few dozen pods span several chunks
WINDOW = 8   # rows a report window, so a chunk spans several windows

#: the consumer thread's stages: with ``unnamed`` they are the reconcile
OWN_STAGES = ('filter', 'chunk_wait', 'report', 'store', 'flush')
#: (stage, the identifier its events carry) of a pipelined reconcile ...
SCAN_MARKS = [('filter', None), ('flush', None), ('chunk_wait', 'chunk'),
              ('report', 'chunk'), ('store', 'chunk'), ('match', 'chunk'),
              ('encode_wait', 'chunk'), ('h2d', 'chunk'),
              ('device_eval', 'chunk'), ('d2h', 'chunk'),
              ('device_wait', 'chunk'), ('expand', 'chunk')]
#: ... and of an admission dispatch, whose one chunk runs inline
BATCH_MARKS = ['prepare', 'match', 'encode', 'h2d', 'device_eval', 'd2h',
               'device_wait', 'expand', 'report', 'resolve']
#: stats() fields that split batch_ms, and the two beside them
SPLIT_FIELDS = ['batch_prepare_ms', 'batch_match_ms', 'batch_encode_ms',
                'batch_pack_ms', 'batch_h2d_ms', 'batch_dispatch_ms',
                'batch_d2h_ms', 'batch_expand_ms', 'batch_report_ms',
                'batch_resolve_ms', 'batch_unnamed_ms']
TIMING_FIELDS = ['batch_ms'] + SPLIT_FIELDS + ['batch_device_wait_ms',
                                               'handler_self_ms',
                                               'handler_message_ms']


def review_bytes(resource, uid):
    import json
    return json.dumps({
        'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
        'request': {
            'uid': uid, 'operation': 'CREATE',
            'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
            'namespace': 'default', 'name': resource['metadata']['name'],
            'object': resource,
            'userInfo': {'username': 'alice', 'groups': []}}}).encode()


def marks(path):
    """The ``ktpu/`` events of a trace: ``(line, stage, start, end, ids)``
    with ``line`` one thread of the ``/host:CPU`` plane."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith('ktpu/'):
                    assert plane.name == '/host:CPU', plane.name
                    out.append(((plane.name, n), ev.name[len('ktpu/'):],
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                {k: v for k, v in list(ev.stats)}))
    return out


@pytest.fixture(scope='module')
def profiled(tmp_path_factory):
    """A warmed multi-chunk reconcile and a few admission requests, with
    the stage histogram on, then the same again under ``jax.profiler``
    with the options ``benchmarks/run.py`` traces with.  Telemetry is off
    again before the first test runs."""
    import glob
    import os

    import jax

    from kyverno_tpu.config.config import Configuration
    from kyverno_tpu.dclient.client import FakeClient
    from kyverno_tpu.policycache import cache as pcache
    from kyverno_tpu.reports.controllers import BackgroundScanController
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer
    patch = pytest.MonkeyPatch()
    patch.setenv('KTPU_VERDICT_CACHE_DIR',
                 str(tmp_path_factory.mktemp('verdicts')))
    patch.setenv('KTPU_ENCODE_PROCS', '2')
    tracing.disable()
    reg = devtel.configure(MetricsRegistry())
    try:
        docs = [pod(i) for i in range(12 * CAP + 5)]
        for i, d in enumerate(docs):
            d['metadata']['uid'] = f'uid-{i}'
        ctrl = BackgroundScanController(FakeClient(), [Policy(POLICY)])
        ctrl.scanner.CHUNK = CAP
        ctrl.scanner.REPORT_FLUSH_ROWS = WINDOW
        cache = pcache.Cache()
        cache.warm_up([Policy(POLICY)])
        handlers = ResourceHandlers(cache, configuration=Configuration(),
                                    serving_mode='batch')
        server = WebhookServer(handlers, configuration=Configuration())
        enforce = cache.get_policies(pcache.VALIDATE_ENFORCE, 'Pod',
                                     'default')
        assert handlers.wait_device_ready(enforce, timeout=600)
        batcher = handlers._get_batcher()
        fresh = batcher.stats()

        def reconcile(now):
            # a new image on every pod: the verdict cache misses, so
            # every pass scans
            ctrl.reset_scan_state()
            for d in docs:
                d['spec']['containers'][0]['image'] = f'nginx:{now}'
                ctrl.enqueue(d)
            assert len(ctrl.reconcile(now=now)) == len(docs)
            assert ctrl.rescan_stats['rows_scanned'] == len(docs)

        def admit(n):
            for i in range(n):
                server.handle('/validate/fail',
                              review_bytes(pod(i), f'u{i}'))

        def histogram():
            # stage_breakdown()'s numbers, unrounded
            return {dict(key)['stage']: (count, total)
                    for key, count, total in reg.histogram_series(
                        devtel.SCAN_STAGE_DURATION)}

        # warm: the executables, the encoder pool, the batcher's thread
        reconcile(1000.0)
        admit(2)
        before = histogram()
        batcher.reset_stats()
        reconcile(2000.0)
        stages = {name: {'count': count - before.get(name, (0, 0))[0],
                         'total_s': total - before.get(name, (0, 0))[1]}
                  for name, (count, total) in histogram().items()}
        denials = histogram().get('deny_message', (0, 0.0))[0]
        admit(6)
        stats = batcher.stats()
        denials = histogram()['deny_message'][0] - denials
        batcher.reset_stats()
        after_reset = batcher.stats()
        for i in (1, 3, 5):  # the pods with the label: all allowed
            server.handle('/validate/fail', review_bytes(pod(i), f'a{i}'))
        all_allowed = batcher.stats()
        batcher.reset_stats()

        out_dir = str(tmp_path_factory.mktemp('trace'))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            reconcile(3000.0)
            admit(3)
        finally:
            jax.profiler.stop_trace()
        handlers.shutdown()
        [path] = glob.glob(os.path.join(out_dir, '**', '*.xplane.pb'),
                           recursive=True)
        return {'marks': marks(path), 'stages': stages, 'fresh': fresh,
                'stats': stats, 'after_reset': after_reset,
                'denials': denials, 'all_allowed': all_allowed,
                'chunks': -(-len(docs) // CAP), 'rows': len(docs)}
    finally:
        devtel.disable()
        patch.undo()


class TestStagesOnTheProfilersClock:
    @pytest.mark.parametrize('stage, key', SCAN_MARKS)
    def test_reconcile_stage_is_in_the_trace(self, profiled, stage, key):
        """Each leaf stage of a pipelined reconcile is an event on a
        host thread's line, carrying the chunk it belongs to."""
        found = [m for m in profiled['marks']
                 if m[1] == stage and 'batch' not in m[4]]
        assert found, stage
        if key is not None:
            assert {m[4][key] for m in found} <= \
                set(range(profiled['chunks'])), stage
        # once a chunk or a window, never once a row
        assert len(found) <= -(-profiled['rows'] // WINDOW) + \
            profiled['chunks'], stage

    @pytest.mark.parametrize('stage', BATCH_MARKS)
    def test_admission_stage_is_in_the_trace(self, profiled, stage):
        """Each leaf stage of a dispatch is an event on the batcher's
        line, carrying the dispatch's serial and its rows."""
        found = [m for m in profiled['marks']
                 if m[1] == stage and 'batch' in m[4]]
        assert found, stage
        assert all(m[4]['rows'] >= 1 for m in found)
        assert len({m[0] for m in found}) == 1  # one thread: the batcher
        assert len({m[4]['batch'] for m in found}) == len(found)

    def test_only_d2h_encloses_another_stage(self, profiled):
        """Leaf stages only: ``label_gap`` names a gap by the one host
        event that covers most of it, so an enclosing event would give
        its name to every gap."""
        by_line = {}
        for line, stage, start, end, _ids in profiled['marks']:
            by_line.setdefault(line, []).append((start, end, stage))
        enclosing = {(a[2], b[2])
                     for events in by_line.values()
                     for a in events for b in events
                     if a is not b and a[0] <= b[0] and b[1] <= a[1]}
        assert enclosing == {('d2h', 'device_wait')}

    def test_reconcile_wall_is_the_sum_of_this_threads_stages(
            self, profiled):
        stages = profiled['stages']
        assert stages['reconcile']['count'] == 1
        assert stages['store']['count'] == stages['report']['count'] \
            == -(-CAP // WINDOW) * (profiled['chunks'] - 1) + 1
        assert stages['chunk_wait']['count'] == profiled['chunks']
        wall = stages['reconcile']['total_s']
        parts = sum(stages[s]['total_s']
                    for s in OWN_STAGES + ('unnamed',))
        assert wall == pytest.approx(parts, rel=1e-9)
        assert 0 <= stages['unnamed']['total_s'] < 0.10 * wall

    @pytest.mark.parametrize('field', TIMING_FIELDS)
    def test_batcher_stats_field(self, profiled, field):
        """Flat numbers (the benchmark's webhook driver drops anything
        else), 0.0 on a fresh batcher and after ``reset_stats``."""
        assert profiled['fresh'][field] == 0.0
        assert profiled['after_reset'][field] == 0.0
        value = profiled['stats'][field]
        assert isinstance(value, float) and value >= 0.0
        if field not in ('batch_prepare_ms', 'batch_unnamed_ms'):
            assert value > 0.0

    def test_the_denial_message_is_part_of_the_handlers_own_time(
            self, profiled):
        """``handler_message_ms`` has ``handler_self_ms``'s denominator
        (every handled request, denied or not), so the two subtract; a
        window of allowed requests reads 0.0; the stage is sampled once
        a denied request (of ``admit(6)``, the three pods without the
        label), never once a policy."""
        stats = profiled['stats']
        assert 0.0 < stats['handler_message_ms'] <= stats['handler_self_ms']
        allowed = profiled['all_allowed']
        assert allowed['handler_self_ms'] > 0.0
        assert allowed['handler_message_ms'] == 0.0
        assert 'deny_message' in devtel.STAGES
        assert profiled['denials'] == 3

    def test_batcher_stats_split_the_dispatch(self, profiled):
        stats = profiled['stats']
        assert stats['dispatches'] >= 1
        assert sum(stats[f] for f in SPLIT_FIELDS) == \
            pytest.approx(stats['batch_ms'], rel=1e-9)
        assert stats['batch_device_wait_ms'] <= stats['batch_d2h_ms']
        # no share is asked of batch_unnamed_ms here: with one rule a
        # dispatch takes a millisecond or two, most of it fixed cost


# -- the device mutate scan's stages ------------------------------------------

MUTATE_STAGES = ('mutate_match', 'mutate_encode', 'mutate_eval',
                 'mutate_decode', 'mutate_pre', 'mutate_post')


@pytest.fixture(scope='module')
def mutated(tmp_path_factory):
    """A few ``/mutate`` requests through a batch-mode server with the
    stage histogram on, the last of them under ``jax.profiler``."""
    import glob
    import os

    import jax

    from kyverno_tpu.policycache import cache as pcache
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer
    policy = Policy({
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': 'always-pull', 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {'rules': [{
            'name': 'always-pull',
            'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
            'mutate': {'patchStrategicMerge': {'spec': {'containers': [
                {'(name)': '*', 'imagePullPolicy': 'Always'}]}}}}]}})
    tracing.disable()
    reg = devtel.configure(MetricsRegistry())
    try:
        cache = pcache.Cache()
        cache.warm_up([policy])
        handlers = ResourceHandlers(cache, serving_mode='batch')
        server = WebhookServer(handlers)
        mutate_set = cache.get_policies(pcache.MUTATE, 'Pod', 'default')
        assert handlers.wait_device_ready(mutate_set, timeout=300,
                                          kind='mutate')
        scanner = handlers._device_scanner(mutate_set, kind='mutate')
        assert scanner.ok
        for i in range(4):
            server.handle('/mutate/fail', review_bytes(pod(i), f'm{i}'))
        out_dir = str(tmp_path_factory.mktemp('mutate-trace'))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            for i in range(3):
                server.handle('/mutate/fail', review_bytes(pod(i), f't{i}'))
        finally:
            jax.profiler.stop_trace()
        stats = handlers._get_batcher().stats()
        handlers.shutdown()
        [path] = glob.glob(os.path.join(out_dir, '**', '*.xplane.pb'),
                           recursive=True)
        return {'marks': marks(path), 'stats': stats, 'scanner': scanner,
                'counts': {dict(key)['stage']: count
                           for key, count, _total in reg.histogram_series(
                               devtel.SCAN_STAGE_DURATION)}}
    finally:
        devtel.disable()


class TestMutateStages:
    @pytest.mark.parametrize('stage', MUTATE_STAGES)
    def test_the_stage_is_registered_and_sampled(self, mutated, stage):
        """The four of a scan once a dispatch (the scanner's warm-up is
        one more), the handler's two once a request that rode a batch."""
        from kyverno_tpu.observability.catalog import PIPELINE_STAGES
        assert stage in devtel.STAGES and stage in PIPELINE_STAGES
        dispatches = mutated['stats']['mutate_dispatches']
        assert dispatches == 7    # one request at a time
        want = 7 if stage in ('mutate_pre', 'mutate_post') \
            else dispatches + 1
        assert mutated['counts'][stage] == want

    @pytest.mark.parametrize('stage', MUTATE_STAGES[:4])
    def test_a_scans_stage_is_in_the_trace_with_its_batch(self, mutated,
                                                          stage):
        found = [m for m in mutated['marks'] if m[1] == stage]
        assert len(found) == 3
        assert all(m[4]['rows'] == 1 for m in found)
        assert len({m[4]['batch'] for m in found}) == 3
        assert len({m[0] for m in found}) == 1  # one thread: the batcher

    def test_the_handlers_two_are_in_the_histogram_only(self, mutated):
        assert not [m for m in mutated['marks']
                    if m[1] in ('mutate_pre', 'mutate_post')]

    def test_the_older_histograms_are_retired(self):
        from kyverno_tpu.observability import catalog
        assert not [name for name in catalog.METRICS
                    if 'patch_emit' in name or 'mutate_decode' in name]

    def test_the_device_program_is_named_for_a_trace_reader(self, mutated):
        """``jit_mutate_eval``: what ``trace_module_ms`` looks for."""
        import jax
        from kyverno_tpu.compiler.scan import WARM_POD
        from kyverno_tpu.mutate.encode import encode_mutate_batch
        scanner = mutated['scanner']
        lanes = encode_mutate_batch([WARM_POD], scanner.program,
                                    padded_n=64)
        with jax.enable_x64(True):
            text = scanner._kernel._jitted.lower(lanes).as_text()
        assert 'jit_mutate_eval' in text.split('\n', 1)[0]
