"""Device-pipeline telemetry: stage spans, TPU metrics, d2h watchdog.

The batched scan path (``compiler/scan.py`` + ``ops/eval.py``) runs as
a pipeline — host feature extraction (encode), pack, h2d
transfer, XLA trace/compile, device eval dispatch, d2h readback, report
assembly.  This module gives each stage an OTel-shaped child span (via
``observability.tracing``) and a matching Prometheus series
(``kyverno_tpu_scan_stage_duration_seconds{stage=...}``), plus cache
hit/miss counters and a **d2h stall watchdog**: a monitor thread that
fires a structured event, an ERROR log line, and a
``kyverno_tpu_d2h_stalls_total`` increment whenever a device→host
readback blocks longer than ``KTPU_D2H_STALL_S`` (default 30s) — a
stalled readback leaves a trace instead of silently starving the
pipeline.

Everything here is a no-op until :func:`configure` runs (and spans
additionally require ``tracing.configure``): unconfigured processes
allocate no spans, create no series, and start no threads, so tier-1
timings and bit-identical PolicyReport output are unaffected.
"""

from __future__ import annotations

import collections
import contextvars
import logging
import os
import pickle
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import tracing
from .metrics import (WIDE_BUCKETS, MetricsRegistry, global_registry)

SCAN_STAGE_DURATION = 'kyverno_tpu_scan_stage_duration_seconds'
COMPILE_CACHE_REQUESTS = 'kyverno_tpu_compile_cache_requests_total'
DEVICE_BATCH_SIZE = 'kyverno_tpu_device_batch_size'
D2H_BYTES = 'kyverno_tpu_d2h_bytes_total'
D2H_STALLS = 'kyverno_tpu_d2h_stalls_total'
PIPELINE_INFLIGHT = 'kyverno_tpu_scan_pipeline_inflight_chunks'
BACKPRESSURE = 'kyverno_tpu_scan_backpressure_seconds_total'
ENCODE_WORKER_CHUNKS = 'kyverno_tpu_encode_worker_chunks_total'
ENCODE_RESULT_BYTES = 'kyverno_tpu_encode_result_bytes_total'
STAGE_RETRIES = 'kyverno_tpu_scan_stage_retries_total'
PACK_BATCHES = 'kyverno_tpu_pack_batches_total'
CONTEXT_LOOKUPS = 'kyverno_tpu_context_lookups_total'
CONTEXT_LOADS = 'kyverno_tpu_context_loads_total'
FAIL_MESSAGE_MEMO = 'kyverno_tpu_fail_message_memo_total'

#: canonical stage labels.  The pipeline's, in order: ``match`` (host
#: match sieve), ``encode`` (in a worker process or inline),
#: ``encode_wait`` (the h2d thread waiting out a worker's encode),
#: ``context`` (the encode thread, while a worker encodes: the chunk's
#: distinct context inputs resolved, loader calls included, and the
#: value lanes and the load-outcome mask written),
#: ``pack``, ``h2d``, ``compile``, ``device_eval`` (the dispatch: it
#: times the ENQUEUE), ``d2h`` (wait + copy), ``device_wait`` (nested in
#: ``d2h``: blocked until the evaluator's outputs are ready),
#: ``expand``.  The consumer thread's: ``filter``, ``chunk_wait``,
#: ``report``, ``store``, ``flush``, and per reconcile ``reconcile``
#: (its wall) and ``unnamed`` (that wall minus the five before it).
#: The admission batch's own: ``prepare``, ``resolve``, and on the
#: request's thread ``candidates`` (before ``handler_pre``: the
#: policies that apply to the request and the installed set's key),
#: ``handler_pre``, ``handler_post``, and per denied request
#: ``deny_message`` (inside ``handler_post`` where it rode a batch).
#: The device mutate scan's (``mutate/scanner.py``, one batch on the
#: batcher's thread): ``mutate_match``, ``mutate_encode``,
#: ``mutate_eval`` (the jitted call to its results on the host: it is
#: synchronous), ``mutate_decode``; and on the request's thread
#: ``mutate_pre`` (``mutate()``'s entry to the batcher's submit) and
#: ``mutate_post`` (resolved ticket to return).
STAGES = ('match', 'encode', 'encode_wait', 'pack', 'h2d', 'compile',
          'device_eval', 'd2h', 'device_wait', 'expand', 'filter',
          'chunk_wait', 'report', 'store', 'flush', 'reconcile',
          'unnamed', 'prepare', 'resolve', 'candidates', 'handler_pre',
          'handler_post', 'deny_message', 'mutate_match',
          'mutate_encode', 'mutate_eval', 'mutate_decode', 'mutate_pre',
          'mutate_post', 'context')

_log = logging.getLogger('kyverno.device')

_registry: Optional[MetricsRegistry] = None
_watchdog: Optional['D2HWatchdog'] = None
_event_sink: Optional[Callable[[dict], None]] = None
#: additional watchdog-event listeners (the flight recorder registers
#: its dump trigger here); independent of configure()'s event_sink so
#: provenance and a caller-supplied sink compose
_extra_sinks: List[Callable[[dict], None]] = []


def add_event_sink(fn: Callable[[dict], None]) -> None:
    if fn not in _extra_sinks:
        _extra_sinks.append(fn)


def remove_event_sink(fn: Callable[[dict], None]) -> None:
    try:
        _extra_sinks.remove(fn)
    except ValueError:
        pass


def _stall_threshold_default() -> float:
    try:
        return float(os.environ.get('KTPU_D2H_STALL_S', '30'))
    except ValueError:
        return 30.0


def configure(registry: Optional[MetricsRegistry] = None,
              stall_threshold_s: Optional[float] = None,
              event_sink: Optional[Callable[[dict], None]] = None
              ) -> MetricsRegistry:
    """Enable device-pipeline metrics (and the stall watchdog).

    ``registry`` defaults to the process-global registry, else a fresh
    one.  Returns the registry in use.  Idempotent; ``disable`` undoes
    it (and stops the watchdog thread)."""
    global _registry, _watchdog, _event_sink
    reg = registry or global_registry() or MetricsRegistry()
    reg.register_histogram(SCAN_STAGE_DURATION, WIDE_BUCKETS)
    # in-flight chunks is a residency gauge: once the pipeline drains
    # it must export 0 (swept by cmd/internal.Setup.shutdown)
    reg.mark_reset_on_close(PIPELINE_INFLIGHT)
    _event_sink = event_sink
    threshold = stall_threshold_s if stall_threshold_s is not None \
        else _stall_threshold_default()
    if _watchdog is not None:
        _watchdog.stop()
    _watchdog = D2HWatchdog(threshold)
    _registry = reg
    return reg


def disable() -> None:
    global _registry, _watchdog, _event_sink
    wd, _watchdog = _watchdog, None
    _registry = None
    _event_sink = None
    if wd is not None:
        wd.stop()


def registry() -> Optional[MetricsRegistry]:
    return _registry


def watchdog() -> Optional['D2HWatchdog']:
    return _watchdog


def enabled() -> bool:
    """True when any instrumentation would record (metrics configured
    or tracing on) — the zero-overhead gate for the scan hot path."""
    return _registry is not None or tracing.tracer().enabled


# -- per-scan capture -------------------------------------------------------

#: the decision-provenance accumulator for the scan running on this
#: thread/context (None almost always — one contextvar read per stage)
_capture_var: contextvars.ContextVar[Optional['ScanCapture']] = \
    contextvars.ContextVar('ktpu_scan_capture', default=None)


class ScanCapture:
    """Per-scan stage-time accumulator for decision provenance:
    installed around one ``scanner.scan`` / ``scan_report_results``
    call, it collects the scan's own stage durations (``device_eval``
    drives the amortized per-rider device-time share), the AOT
    executable-cache outcome, and the scan's device-coverage ratio —
    without attributing concurrent scans' stages to each other the way
    a registry-sum delta would."""

    __slots__ = ('stages', 'aot', 'coverage_ratio', 'critical_path',
                 'pack_views', '_lock')

    def __init__(self):
        self.stages: Dict[str, float] = {}
        self.aot = ''
        #: batches of this scan that ``pack_batch`` handed over as the
        #: buffers their lanes were encoded into (:func:`record_pack`)
        self.pack_views = 0
        self.coverage_ratio: Optional[float] = None
        #: critical-path blame summary for this scan, filled by the
        #: timeline recorder (observability/timeline.py) when armed
        self.critical_path: Optional[Dict[str, Any]] = None
        self._lock = threading.Lock()

    def add(self, stage: str, seconds: float) -> None:
        with self._lock:
            self.stages[stage] = self.stages.get(stage, 0.0) + seconds

    def stage_s(self, stage: str) -> float:
        with self._lock:
            return self.stages.get(stage, 0.0)


class _VarScope:
    """Sets one contextvar for a with-block (no-op for None)."""

    __slots__ = ('var', 'value', '_token')

    def __init__(self, var: contextvars.ContextVar, value):
        self.var = var
        self.value = value
        self._token = None

    def __enter__(self):
        if self.value is not None:
            self._token = self.var.set(self.value)
        return self.value

    def __exit__(self, *exc) -> None:
        if self._token is not None:
            self.var.reset(self._token)


def install_capture(capture: Optional[ScanCapture]) -> _VarScope:
    """Context manager making ``capture`` the ambient scan accumulator
    (no-op for None).  The scan pipeline re-installs it on its worker
    threads (``compiler/scan.py`` encode/dispatch closures), the same
    way stage spans re-parent through ``tel_parent``."""
    return _VarScope(_capture_var, capture)


def current_capture() -> Optional[ScanCapture]:
    return _capture_var.get()


#: what the stages opened on this thread/context belong to: ``chunk``
#: (the chunk's sequence number in its scan) on the scan path, ``batch``
#: (the batcher's dispatch serial) and ``rows`` on the admission path
_ids_var: contextvars.ContextVar[Optional[Dict[str, Any]]] = \
    contextvars.ContextVar('ktpu_trace_ids', default=None)


def trace_ids(**ids) -> _VarScope:
    """Context manager adding ``ids`` to the identifiers every stage
    opened inside it writes into the profiler's trace, so the spans of
    one chunk or one batch can be joined across threads."""
    return _VarScope(_ids_var, {**(_ids_var.get() or {}), **ids})


def merge_worker_stages(stages: Dict[str, float]) -> None:
    """Fold stage seconds measured inside a forked encode worker into
    the parent's telemetry: the stage histogram and the ambient
    ScanCapture.  Worker processes inherit telemetry globals at fork
    but their metric increments and contextvars die with them — the
    measured times ride home with the encoded tensors and are
    re-attributed here, on the pipeline thread that resolved them."""
    for name, seconds in stages.items():
        record_stage(name, seconds)


# -- stage timers -----------------------------------------------------------

class _NoopStage:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attribute(self, key, value):
        pass

    def add_d2h_bytes(self, n):
        pass


_NOOP_STAGE = _NoopStage()


class _Stage:
    __slots__ = ('stage', 'span', '_t0', '_mark')

    def __init__(self, stage: str, span, t0: float, mark):
        self.stage = stage
        self.span = span
        self._t0 = t0
        self._mark = mark

    def set_attribute(self, key, value):
        self.span.set_attribute(key, value)

    def add_d2h_bytes(self, n: int) -> None:
        add_d2h_bytes(n)

    def __enter__(self):
        self.span.__enter__()
        self._mark.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._mark.__exit__(exc_type, exc, tb)
        self.span.__exit__(exc_type, exc, tb)
        record_stage(self.stage, time.monotonic() - self._t0)
        return False


def _off() -> bool:
    """The one rule that keeps every stage primitive a no-op."""
    return _registry is None and _capture_var.get() is None and \
        not tracing.tracer().enabled


#: ``jax.profiler.TraceAnnotation``, looked up once
_annotation = None


def _mark(name: str, ids: Optional[Dict[str, Any]]):
    global _annotation
    if _annotation is None:
        # a process that has not loaded jax (an encoder worker) takes
        # no trace, and must not load jax for this
        jax = sys.modules.get('jax')
        if jax is None:
            return _NOOP_STAGE
        _annotation = jax.profiler.TraceAnnotation
    return _annotation('ktpu/' + name,
                       **{**(_ids_var.get() or {}), **(ids or {})})


def annotation(name: str, **ids):
    """Context manager that writes a ``ktpu/<name>`` event, with ``ids``
    and the ambient :func:`trace_ids`, into the profiler's own trace
    while one is being taken: on the ``/host:CPU`` plane, on this
    thread's line, on the device trace's clock."""
    return _NOOP_STAGE if _off() else _mark(name, ids)


def record_stage(name: str, seconds: float) -> None:
    """One sample of ``name`` measured by the caller — a stage timed
    row by row and observed once, or the wall of a whole reconcile —
    into the stage histogram and the ambient ScanCapture; nothing goes
    to the trace."""
    if _registry is not None:
        _registry.observe(SCAN_STAGE_DURATION, seconds, stage=name)
    capture = _capture_var.get()
    if capture is not None:
        capture.add(name, seconds)


def stage(name: str, attributes: Optional[Dict[str, Any]] = None,
          parent=None):
    """Context manager timing one leaf stage, with four sinks: a
    ``kyverno/device/<name>`` span (child of ``parent`` or the context
    span), a ``ktpu/<name>`` event carrying ``attributes`` in the
    profiler's trace (:func:`annotation`), a histogram sample and a line
    in the active ScanCapture (:func:`record_stage`).  Spans are wall
    time: a wait for the GIL is inside them.  Returns a shared no-op
    when telemetry is unconfigured."""
    if _off():
        return _NOOP_STAGE
    span = tracing.tracer().start_span(f'kyverno/device/{name}',
                                       attributes, parent=parent)
    return _Stage(name, span, time.monotonic(), _mark(name, attributes))


# -- counters / gauges ------------------------------------------------------

def record_cache(result: str) -> None:
    """Executable-cache outcome: hit | miss | aot_load | aot_store."""
    if _registry is not None:
        _registry.inc(COMPILE_CACHE_REQUESTS, result=result)
    capture = _capture_var.get()
    if capture is not None and result != 'aot_store':
        # the scan's lookup outcome (aot_store is the async write-back
        # that follows a miss, not a distinct lookup result)
        capture.aot = result


def set_batch_size(n: int) -> None:
    if _registry is not None:
        # ktpu: noqa[KTPU603] -- the canonical batch capacity is
        # configuration, not occupancy; it stays meaningful after a
        # drain and resetting it to 0 would misreport the shape table
        _registry.set_gauge(DEVICE_BATCH_SIZE, float(n))


def add_d2h_bytes(n: int) -> None:
    if _registry is not None and n:
        _registry.inc(D2H_BYTES, float(n))


def set_pipeline_inflight(n: int) -> None:
    """Chunks currently resident in the streaming scan pipeline
    (bounded by KTPU_PIPELINE_DEPTH; reset to 0 when a scan ends)."""
    if _registry is not None:
        _registry.set_gauge(PIPELINE_INFLIGHT, float(n))


def add_backpressure(stage: str, seconds: float) -> None:
    """Time a pipeline stage spent blocked handing its chunk to a full
    downstream queue (or the intake waiting for a free chunk slot) —
    the direct measure of which leg bounds the stream."""
    if _registry is not None and seconds > 0:
        _registry.inc(BACKPRESSURE, float(seconds), stage=stage)


def record_stage_retry(stage: str) -> None:
    """A pipeline stage raised and is re-run on the same chunk
    (KTPU_STAGE_RETRIES): the scan may still succeed, so this counter
    is the only place a transient stage error — a device error among
    them — stays readable."""
    if _registry is not None:
        _registry.inc(STAGE_RETRIES, stage=stage)


def record_encode_worker(result: str) -> None:
    """One outcome of the encoder worker pool: ``ok`` per chunk whose
    lanes a worker left in a shared-memory block, ``presumed_dead`` when
    a chunk's worker did not answer inside ENCODE_TIMEOUT_S,
    ``pool_failed`` when the pool could not start or take a task, or a
    block could not be had.  Anything but ``ok`` means the scanner
    dropped to in-process encoding."""
    if _registry is not None:
        _registry.inc(ENCODE_WORKER_CHUNKS, result=result)


def record_pack(via: str) -> None:
    """One batch through ``ops/eval.py`` ``pack_batch``: ``view`` where
    its lanes were views of the packed buffers already and those were
    handed over, ``copy`` where every lane was copied into new ones."""
    if _registry is not None:
        _registry.inc(PACK_BATCHES, via=via)
    capture = _capture_var.get()
    if capture is not None and via == 'view':
        with capture._lock:
            capture.pack_views += 1


def record_context(lookups: int, loads_ok: int, loads_failed: int) -> None:
    """One chunk's context fill (``compiler/context_lanes.py``): a
    lookup is a (row, context) pair that asked for its context's
    outcome, a load is a call of the engine's loader; lookups − loads
    are the memo's hits."""
    if _registry is not None:
        if lookups:
            _registry.inc(CONTEXT_LOOKUPS, float(lookups))
        if loads_ok:
            _registry.inc(CONTEXT_LOADS, float(loads_ok), result='ok')
        if loads_failed:
            _registry.inc(CONTEXT_LOADS, float(loads_failed),
                          result='failed')


def record_fail_message_memo(hits: int, misses: int) -> None:
    """One assembled window's FAIL cells of programs whose message has
    variables and a plan (``compiler/scan.py`` ``_fail_memoized``): a
    miss is a cell the Validator worded, a hit one that took the
    response worded for an earlier row of the same key in the pass."""
    if _registry is not None:
        if hits:
            _registry.inc(FAIL_MESSAGE_MEMO, float(hits), result='hit')
        if misses:
            _registry.inc(FAIL_MESSAGE_MEMO, float(misses), result='miss')


def record_encode_result_bytes(lanes, answer) -> None:
    """What one chunk's encode brought home from its worker, by the way
    it came: the bytes of ``lanes`` in the shared-memory block, and
    through the pipe ``answer``, which places them (pickled: the batch's
    shape key and the packed buffers' offsets).
    Both are sized here, so only where metrics are on."""
    if _registry is not None:
        _registry.inc(ENCODE_RESULT_BYTES,
                      float(sum(v.nbytes for v in lanes.values())),
                      via='block')
        _registry.inc(ENCODE_RESULT_BYTES,
                      float(len(pickle.dumps(answer))), via='pipe')


# -- d2h stall watchdog -----------------------------------------------------

class D2HWatchdog:
    """Monitor thread flagging device→host readbacks that exceed a
    threshold.  ``arm`` registers a readback; if it is still armed past
    its deadline the watchdog fires ONCE for it: structured event +
    ERROR log line + ``kyverno_tpu_d2h_stalls_total`` increment.  The
    thread starts lazily on the first ``arm`` and exits on ``stop`` —
    an unconfigured or idle process runs no thread."""

    def __init__(self, threshold_s: float):
        self.threshold_s = threshold_s
        self._cv = threading.Condition()
        self._entries: Dict[int, list] = {}  # token -> [start, attrs, fired]
        self._seq = 0
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        self.stall_events: 'collections.deque[dict]' = \
            collections.deque(maxlen=256)

    def arm(self, attrs: Optional[Dict[str, Any]] = None) -> int:
        with self._cv:
            if self._stopped:
                return -1
            token = self._seq
            self._seq += 1
            self._entries[token] = [time.monotonic(), dict(attrs or {}),
                                    False]
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name='ktpu-d2h-watchdog',
                    daemon=True)
                self._thread.start()
            self._cv.notify()
        return token

    def disarm(self, token: int) -> float:
        with self._cv:
            entry = self._entries.pop(token, None)
        if entry is None:
            return 0.0
        return time.monotonic() - entry[0]

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._entries.clear()
            self._cv.notify()
            t = self._thread
        if t is not None:
            t.join(timeout=2)
            # arm() reads/writes _thread under the condition variable;
            # clearing it outside raced a concurrent arm (join must
            # stay outside — _run holds the cv between waits)
            with self._cv:
                self._thread = None

    def _run(self) -> None:
        with self._cv:
            while not self._stopped:
                now = time.monotonic()
                next_deadline: Optional[float] = None
                for entry in self._entries.values():
                    start, attrs, fired = entry
                    if fired:
                        continue
                    deadline = start + self.threshold_s
                    if deadline <= now:
                        entry[2] = True
                        self._fire(now - start, attrs)
                    elif next_deadline is None or deadline < next_deadline:
                        next_deadline = deadline
                timeout = None if next_deadline is None \
                    else max(next_deadline - now, 0.01)
                self._cv.wait(timeout)

    def _fire(self, elapsed_s: float, attrs: Dict[str, Any]) -> None:
        event = {
            'type': 'd2h_stall',
            'threshold_s': self.threshold_s,
            'elapsed_s': round(elapsed_s, 3),
            'ts': time.time(),
            **attrs,
        }
        self.stall_events.append(event)
        if _registry is not None:
            _registry.inc(D2H_STALLS)
        from .logging import with_values
        with_values(_log, 'd2h readback stalled', level=logging.ERROR,
                    **{k: v for k, v in event.items() if k != 'type'})
        sinks = ([_event_sink] if _event_sink is not None else []) \
            + list(_extra_sinks)
        for sink in sinks:
            try:
                sink(event)
            except Exception:  # noqa: BLE001 - sinks must not break d2h
                pass


class _D2HGuard:
    """Stage timer for a readback with the watchdog armed around it."""

    __slots__ = ('_stage', '_token')

    def __init__(self, stage_cm, token: int):
        self._stage = stage_cm
        self._token = token

    def set_attribute(self, key, value):
        self._stage.set_attribute(key, value)

    def add_d2h_bytes(self, n: int) -> None:
        add_d2h_bytes(n)

    def __enter__(self):
        self._stage.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        wd = _watchdog
        if wd is not None and self._token >= 0:
            wd.disarm(self._token)
        return self._stage.__exit__(exc_type, exc, tb)


def d2h_guard(attributes: Optional[Dict[str, Any]] = None, parent=None):
    """``stage('d2h')`` with the stall watchdog armed for its duration."""
    if _off():
        return _NOOP_STAGE
    # the stage's clock starts first: arming wakes the monitor thread,
    # and that hand-over is the readback's own cost
    timer = stage('d2h', attributes, parent=parent)
    token = _watchdog.arm(attributes) if _watchdog is not None else -1
    return _D2HGuard(timer, token)


def stage_breakdown() -> Dict[str, Dict[str, float]]:
    """Per-stage {total_s, count, mean_s} from the stage histogram —
    tests only; the benchmark's reports driver reads the same histogram
    unrounded (``benchmarks/drivers/reports_controller.py``
    ``_snapshot``)."""
    if _registry is None:
        return {}
    out: Dict[str, Dict[str, float]] = {}
    for key, count, total in _registry.histogram_series(
            SCAN_STAGE_DURATION):
        labels = dict(key)
        stage_name = labels.get('stage', '')
        out[stage_name] = {
            'total_s': round(total, 4),
            'count': count,
            'mean_s': round(total / count, 6) if count else 0.0,
        }
    return out
