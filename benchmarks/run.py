#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one to touch the chip.  The cell's configuration
(``benchmarks/configs/<config>.json``), its traffic mix
(``benchmarks/traffic/<traffic>.json``), the driver of the configuration's
entry point (``benchmarks/drivers/<entry>.py``) and, in a traced run, each
per-layer metric (``benchmarks/layers/<metric>.json`` and the reader it names
under ``benchmarks/readers/``) are found by the names in ``BENCHMARK.json``:
this file knows no cell, configuration or metric by name.

Set-up (generate from ``--seed``, build, warm the cell's own shapes), then the
measured window, then the check against the plain reference.  Progress goes to
earlier lines; the last line of standard output is the one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``.  Without a
TPU, or with fewer chips than the cell asks for, the exit code is non-zero
and no result line is printed.

``--rehearse`` (never a measurement) lets the run pass on the CPU at the
sizes of the ``rehearse`` blocks of the configuration and traffic files.
``--sweep 2,4,6 --step-seconds 15`` offers each rate in turn after one set-up
and prints a row for each: the tool that found the open loop's rate.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

import benchlib  # noqa: E402
from benchlib import say  # noqa: E402


class Tracer:
    """Holds ``jax.profiler`` on for ``seconds`` from ``start_s`` after
    ``arm()``, from a timer thread, and reduces what it wrote."""

    def __init__(self, out_dir: str, start_s: float, seconds: float):
        self.out_dir, self.start_s, self.seconds = out_dir, start_s, seconds
        self.on_at = self.off_at = self._summary = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def arm(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax
        if self._stop.wait(self.start_s):
            return
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        shutil.rmtree(self.out_dir, ignore_errors=True)
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.on_at = time.monotonic()
        self._stop.wait(self.seconds)
        self.off_at = time.monotonic()
        jax.profiler.stop_trace()

    def finish(self, keep: bool):
        """Stop if still tracing, wait, reduce; None where nothing was
        traced."""
        import trace_reduce
        self._stop.set()
        self._thread.join()
        path = trace_reduce.find_xplane(self.out_dir) \
            if self.on_at is not None else None
        if path is not None and self._summary is None:
            say(f'trace: {os.path.getsize(path)} bytes in '
                f'{os.path.relpath(path, ROOT)}')
            self._summary = trace_reduce.summarise(
                trace_reduce.read_xplane(path), self.off_at - self.on_at)
        if not keep:
            shutil.rmtree(self.out_dir, ignore_errors=True)
        return self._summary


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench['workloads']:
        if cell['name'] == name:
            return cell
    raise benchlib.MissingFile(
        f'BENCHMARK.json has no workload {name!r} (it has: '
        f'{", ".join(c["name"] for c in bench["workloads"])})')


def applies(metric: dict, cell: dict) -> bool:
    return 'workloads' not in metric or cell['name'] in metric['workloads']


def device_block(devices: list) -> dict:
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use', 0)
             for d in devices]
    return {'platform': devices[0].platform, 'kind': devices[0].device_kind,
            'count': len(devices), 'memory_peak_bytes': max(peaks)}


def layer_metrics(bench: dict, cell: dict, config: dict, counters: dict,
                  trace, device_kind: str) -> dict:
    """Each per-layer metric of this cell through its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    with open(os.path.join(BENCH_DIR, 'peaks.json')) as f:
        peaks = json.load(f)['devices']
    reading = {'counters': counters, 'trace': trace, 'config': config,
               'peaks': peaks.get(device_kind), 'device_kind': device_kind}
    out = {}
    for metric in bench['per_layer']:
        if not applies(metric, cell):
            continue
        layer = benchlib.load_data('layers', metric['name'])
        reader = benchlib.load_module('readers', layer['reader'])
        value = reader.read(reading, **layer.get('args', {}))
        if value is None:
            say(f'layer metric {metric["name"]}: nothing to read')
        else:
            out[metric['name']] = {'value': value, 'unit': metric['unit']}
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--seconds', type=float, default=None)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    parser.add_argument('--keep-trace', action='store_true',
                        help='leave the trace under chiprun_out/traces/')
    parser.add_argument('--rehearse', action='store_true',
                        help='CPU allowed, rehearsal sizes; not a measurement')
    parser.add_argument('--sweep', default='',
                        help='comma-separated rates to offer in turn')
    parser.add_argument('--step-seconds', type=float, default=15.0)
    args = parser.parse_args()

    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cell = find_cell(bench, args.workload)
    config = benchlib.load_data('configs', cell['config'])
    traffic = benchlib.load_data('traffic', cell['traffic'])
    if args.rehearse:
        config = benchlib.overlay(config, config.get('rehearse', {}))
        traffic = benchlib.overlay(traffic, traffic.get('rehearse', {}))
    seconds = args.seconds if args.seconds is not None \
        else float(bench['run_seconds'])

    # the environment the configuration lists, and nothing else, reaches the
    # program; a directory that has to be fresh is made for this run and
    # removed after it (under TMPDIR, which the driver gives each side)
    fresh = []
    for key, value in config.get('env', {}).items():
        os.environ[key] = str(value)
    for key in config.get('fresh_dirs', []):
        os.environ[key] = tempfile.mkdtemp(prefix='ktpu-bench-')
        fresh.append(os.environ[key])

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != 'tpu' and not args.rehearse:
        print(f'the first JAX device is {platform!r} '
              f'({devices[0].device_kind}), not a TPU', file=sys.stderr)
        return 1
    if len(devices) < cell['chips']:
        print(f'{cell["name"]} needs {cell["chips"]} chips, JAX reports '
              f'{len(devices)}', file=sys.stderr)
        return 1
    say(f'{len(devices)} x {devices[0].device_kind} ({platform}), jax '
        f'{jax.__version__}; cell {cell["name"]} = {cell["config"]} x '
        f'{cell["traffic"]}, seed {args.seed}, {seconds:g} s'
        f'{", REHEARSAL" if args.rehearse else ""}')

    from kyverno_tpu.aotcache import enable_persistent_compilation_cache
    from kyverno_tpu.compiler.scan import stop_encoder_processes
    from kyverno_tpu.observability import device as devtel
    events = benchlib.CacheEvents()
    cache_dir = enable_persistent_compilation_cache()
    say(f'compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR '
        f'{"set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset"})')
    registry = benchlib.program_telemetry()

    driver_mod = benchlib.load_module('drivers', config['entry'])
    driver = driver_mod.Driver(config=config, traffic=traffic, seed=args.seed,
                               seconds=seconds, platform=platform,
                               registry=registry)
    tracer = trace = None
    try:
        driver.setup()
        if args.sweep:
            driver.sweep([float(r) for r in args.sweep.split(',')],
                         args.step_seconds)
            return 0
        if args.trace:
            spec = traffic.get('trace', {})
            start_s = min(spec.get('start_s', 10.0), 0.25 * seconds)
            tracer = Tracer(
                os.path.join(ROOT, 'chiprun_out', 'traces',
                             f'{cell["name"]}-{args.seed}'),
                start_s, min(spec.get('seconds', 15.0), seconds - start_s))
            tracer.arm()
        setup_s = time.monotonic() - _T0
        say(f'set-up took {setup_s:.1f}s; window opens')
        window = driver.measure()
        if tracer is not None:
            trace = tracer.finish(args.keep_trace)
        problems = driver.check()
        counters = driver.counters()
    finally:
        if tracer is not None:
            tracer.finish(args.keep_trace)
        driver.close()
        devtel.disable()
        stop_encoder_processes()
        for path in fresh:
            shutil.rmtree(path, ignore_errors=True)

    left = benchlib.descendants()
    if left:
        print(f'processes this run started are still alive: {left}',
              file=sys.stderr)
        return 1
    requests = events.count(events.REQUEST)
    say(f'compile cache: persistent hits={events.count(events.HIT)} of '
        f'{requests} compile requests in this run')
    in_window = events.count(events.REQUEST, window['start'], window['end'])
    if in_window:
        problems.append(f'{in_window} compile requests fell inside the '
                        f'measured window')
    for problem in problems:
        say(f'NOT CORRECT: {problem}')

    end_to_end = dict(window['metrics'], setup_s=setup_s)
    device = device_block(devices)
    if args.trace:
        metrics = layer_metrics(bench, cell, config, counters, trace,
                                devices[0].device_kind)
        if trace is not None and trace['busy_s']:
            device['busy_s'] = trace['busy_s']
            device['window_s'] = trace['window_s']
    else:
        metrics = {m['name']: {'value': end_to_end[m['name']],
                               'unit': m['unit']}
                   for m in bench['end_to_end']
                   if applies(m, cell) and m['name'] in end_to_end}
    result = {'correct': not problems and driver.failed == 0,
              'attempted': driver.attempted, 'failed': driver.failed,
              'metrics': metrics, 'device': device}
    if trace is not None:
        result['breakdown'] = {'device_ops': trace['device_ops'],
                               'idle_gaps': trace['idle_gaps']}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    try:
        sys.exit(main())
    except benchlib.MissingFile as e:
        print(f'benchmarks/run.py: {e}', file=sys.stderr)
        sys.exit(1)
