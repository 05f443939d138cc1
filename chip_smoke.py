#!/usr/bin/env python
"""Chip smoke: the quickest proof that the system still starts on the chip.

One process, the only one to touch the chip, drives the two normal entry
points at the size of BASELINE.json's third configuration and holds every
answer to the host engine (``kyverno_tpu/engine``, the plain reference):

  (a) the first JAX device must be a TPU;
  (b) the reports controller's background scan: 100,000 mixed
      Pod/Deployment resources through ``BackgroundScanController.reconcile``
      cold, then again after touching 1% of them;
  (c) the admission webhook in batch serving mode at 1,000 enforce policies:
      a few hundred AdmissionReviews from a handful of threads through
      ``WebhookServer.handle``;
  (d) one batch through the device mutate scanner.

The policies are the pack this repo commits (``benchmarks/packs/``), the
cluster and the requests come from the benchmark's generators and
``--seed``, and the report store, the comparison with the host engine and the
instruments are the benchmark's too (``benchmarks/benchlib.py``,
``benchmarks/drivers/reports_controller.py``): this program keeps the
phases and what only it runs on the chip (the rescan after churn, the device
mutate batch, ``--mesh``).  Nothing outside the checkout is read.  Timings,
counts, compile seconds and the compile-cache directory go on earlier lines;
the last line of standard output is the one JSON object the driver reads.  Any failed phase raises: the exit code is then non-zero
and no result line is printed.  The encoder workers and multiprocessing's
two helpers are the only processes the program starts; they are stopped
and waited for before the result line, which is refused while any
descendant of this process is alive.

``--mesh`` (four chips) runs only the sharded scan step over the same
cluster on every device and the one-chip step it is compared with.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T0 = time.monotonic()
ROOT = os.path.dirname(os.path.abspath(__file__))
for _path in (os.path.join(ROOT, 'benchmarks'), ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import benchlib  # noqa: E402

mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')
admission_reviews = benchlib.load_module('generators', 'admission_reviews')
mutate_reviews = benchlib.load_module('generators', 'mutate_reviews')
reports_driver = benchlib.load_module('drivers', 'reports_controller')

#: the committed pack, in ``benchmarks/packs/``
PACKS = ['pss', 'pack', 'config4']

#: BASELINE.json configuration 3
N_RESOURCES = 100_000
#: resources whose report rows are compared with the host engine's
N_SAMPLE = 2_000
#: share of the cluster touched between the two reconciles
CHURN = 0.01
#: enforce policies behind the webhook (the pack, replicated)
N_ADMISSION_POLICIES = 1_000
N_ADMISSION_REQUESTS = 240
N_ADMISSION_THREADS = 6
N_MUTATE_ROWS = 2_048


def say(msg: str) -> None:
    print(f'[smoke +{time.monotonic() - _T0:6.1f}s] {msg}', flush=True)


def require(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def require_none(problems: list, what: str) -> None:
    """The benchmark's checks return what is wrong, one line each."""
    require(not problems, f'{what}: ' + '; '.join(problems[:5]))


# -- instruments -------------------------------------------------------------

def cache_entries(cache_dir, prefix: str) -> int:
    """Entries of one jitted function in the compile cache directory."""
    try:
        return sum(1 for f in os.listdir(cache_dir)
                   if f.startswith(prefix) and f.endswith('-cache'))
    except OSError:
        return 0


def worker_counts(registry) -> dict:
    from kyverno_tpu.observability import device as devtel
    return {r: int(registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                          result=r))
            for r in ('ok', 'presumed_dead', 'pool_failed')}


# -- phase (b): the background scan ------------------------------------------

def scan_phase(policies, cluster, platform: str, seed: int, registry,
               n_sample: int) -> dict:
    import random
    import shutil
    import tempfile
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.observability import coverage
    from kyverno_tpu.observability import device as devtel
    from kyverno_tpu.reports.controllers import (BackgroundScanController,
                                                 MetadataCache)
    rng = random.Random(seed + 1)
    n = len(cluster)
    # the verdict cache persists between processes; a second run of this
    # script must scan on the device again, not replay the first run
    vdir = tempfile.mkdtemp(prefix='ktpu-smoke-verdicts-')
    os.environ['KTPU_VERDICT_CACHE_DIR'] = vdir
    store = reports_driver.ReportStore()
    t0 = time.monotonic()
    cache = MetadataCache()
    ctrl = BackgroundScanController(store, policies, cache=cache)
    try:
        for resource in cluster:
            cache.update(resource)
        ctrl.enqueue_all()
        say(f'scan: {n} resources in the metadata cache, scanner built '
            f'({len(ctrl.scanner.cps.programs)} programs, '
            f'{len(ctrl.scanner.cps.host_rules)} host rules) '
            f'in {time.monotonic() - t0:.1f}s')
        t0 = time.monotonic()
        reports = ctrl.reconcile()
        cold_s = time.monotonic() - t0
        say(f'scan: cold reconcile {cold_s:.1f}s, {len(reports)} reports '
            f'({n / cold_s:.0f} resources/s, compile included)')
        require(len(reports) == n and len(store.reports) == n,
                f'cold reconcile wrote {len(reports)} reports '
                f'({len(store.reports)} stored) for {n} resources')

        engine = Engine()
        t0 = time.monotonic()
        sample = rng.sample(range(n), min(n_sample, n))
        require_none(reports_driver.compare_reports(
            store, engine, policies, [cluster[i] for i in sample]),
            'cold scan')
        say(f'scan: {len(sample)} sampled reports equal the host '
            f'engine\'s ({time.monotonic() - t0:.1f}s)')

        touched = rng.sample(range(n), max(1, int(n * CHURN)))
        for i in touched:
            mixed_cluster.pod_spec(cluster[i])['containers'][0]['image'] = \
                f'registry/churn:{i}'
            ctrl.enqueue(cluster[i])
        t0 = time.monotonic()
        reports = ctrl.reconcile()
        warm_s = time.monotonic() - t0
        say(f'scan: reconcile after touching {len(touched)} resources '
            f'{warm_s:.2f}s, {len(reports)} reports, '
            f'rows_scanned={ctrl.rescan_stats["rows_scanned"]}')
        require(len(reports) == len(touched) and len(store.reports) == n,
                f'second reconcile wrote {len(reports)} reports for '
                f'{len(touched)} touched resources')
        require_none(reports_driver.compare_reports(
            store, engine, policies,
            [cluster[i] for i in touched[:max(1, n_sample // 4)]]),
            'rescan')

        cov = coverage.bench_block()
        require(cov['device_rows'] + cov['host_rows'] == cov['total_rows']
                and cov['total_rows'] > 0,
                f'coverage ledger out of balance: {cov}')
        say(f'scan: coverage device_rows={cov["device_rows"]} '
            f'host_rows={cov["host_rows"]} '
            f'total_rows={cov["total_rows"]} device_share='
            f'{cov["device_rows"] / cov["total_rows"]:.4f} '
            f'by_reason={json.dumps(cov.get("by_reason", {}))}')
        require_none(benchlib.executables_problems(
            benchlib.executables(ctrl.scanner.fingerprint), platform,
            'scan'), 'the executable ledger')
        workers = worker_counts(registry)
        retries = int(registry.counter_total(devtel.STAGE_RETRIES))
        say(f'scan: encoder workers {workers} '
            f'(pool of {ctrl.scanner._encoder_pool.procs}), '
            f'stage retries {retries}')
        require(workers['presumed_dead'] == 0 and
                workers['pool_failed'] == 0,
                f'encoder workers were given up: {workers}')
        require(ctrl.scanner._encoder_pool.procs == 0 or workers['ok'] > 0
                or n <= ctrl.scanner.CHUNK,
                'the encoder pool never encoded a chunk')
        require(retries == 0, f'{retries} pipeline stage retries')
        return {'cold_s': cold_s, 'warm_s': warm_s}
    finally:
        ctrl.close()
        shutil.rmtree(vdir, ignore_errors=True)


# -- phase (c): the admission webhook ----------------------------------------

def admission_phase(policies, cluster, platform: str, seed: int,
                    n_policies: int, n_requests: int) -> dict:
    import statistics
    import threading
    from kyverno_tpu.policycache import cache as pcache
    from kyverno_tpu.serving import breaker
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer

    replicated = benchlib.replicate_enforce(policies, n_policies)
    # the cluster's own Pods nearly all break some enforce policy, so
    # every third request carries one that must be admitted; three in
    # four CREATE, the rest UPDATE, the user drawn by Zipf from the seed
    bodies = admission_reviews.generate(seed, cluster, n_requests)

    failures = benchlib.FailureLog()
    cache = pcache.Cache()
    cache.warm_up(replicated)
    # a request leaves its batch for the host loop once it has waited
    # this long (default 500 ms).  At 1,000 policies the host's own
    # share of a batch is near that, so the smoke serves with half of
    # Kyverno's 10 s webhook timeout: a shed then means the device path
    # did not answer, not that the host was busy
    os.environ['KTPU_SHED_DEADLINE_MS'] = '5000'
    handlers = ResourceHandlers(cache, serving_mode='batch')
    server = WebhookServer(handlers)
    enforce = cache.get_policies(
        pcache.VALIDATE_ENFORCE, 'Pod',
        json.loads(bodies[0])['request']['namespace'])
    require(len(enforce) == n_policies,
            f'{len(enforce)} enforce policies apply to a Pod, '
            f'not {n_policies}')
    t0 = time.monotonic()
    require(handlers.wait_device_ready(enforce, timeout=600),
            'the compiled admission path did not come up: ' + '; '.join(
                f'{b["state"]} after {b["failures"]} failures, last: '
                f'{b.get("last_error", "")}'
                for b in breaker.debug_report()['breakers']))
    say(f'admission: {n_policies} enforce policies ready on the device '
        f'in {time.monotonic() - t0:.1f}s')
    scanner = handlers._device_scanner(enforce)

    answers = [None] * len(bodies)
    latency = [0.0] * len(bodies)
    barrier = threading.Barrier(N_ADMISSION_THREADS)

    def client(tid: int) -> None:
        barrier.wait()
        for k in range(tid, len(bodies), N_ADMISSION_THREADS):
            t = time.monotonic()
            answers[k] = server.handle('/validate/fail', bodies[k])
            latency[k] = time.monotonic() - t

    threads = [threading.Thread(target=client, args=(tid,))
               for tid in range(N_ADMISSION_THREADS)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    stats = handlers._get_batcher().stats()
    server.stop()

    # the reference: the same handler chain with the device path off
    host = WebhookServer(ResourceHandlers(cache, device=False))
    t0 = time.monotonic()
    denied = 0
    for k, body in enumerate(bodies):
        want = json.loads(host.handle('/validate/fail', body))['response']
        got = json.loads(answers[k])['response']
        require(got == want, f'admission answer {k} differs from the '
                             f'host engine\'s: {got} != {want}')
        denied += not want['allowed']
    say(f'admission: {len(bodies)} answers equal the host engine\'s '
        f'({denied} denied; reference took {time.monotonic() - t0:.1f}s)')
    require(0 < denied < len(bodies),
            f'{denied} of {len(bodies)} requests denied: the traffic '
            f'does not exercise both answers')

    ms = sorted(x * 1000.0 for x in latency)
    p50 = statistics.median(ms)
    p99 = ms[min(len(ms) - 1, int(len(ms) * 0.99))]
    say(f'admission: p50_ms={p50} p99_ms={p99} over {len(ms)} requests '
        f'from {N_ADMISSION_THREADS} threads in {wall:.2f}s; batcher '
        f'dispatches={stats["dispatches"]} '
        f'occupancy_mean={stats["occupancy_mean"]:.2f} '
        f'quarantine_dispatches={stats["quarantine_dispatches"]} '
        f'shed={json.dumps(stats["shed"])}')
    report = breaker.debug_report()
    say(f'admission: breakers={json.dumps(report["breakers"])} '
        f'failures_total={report["failures_total"]} '
        f'device_path_failure_lines='
        f'{failures.count("device path failure")}')
    require(stats['requests'] == len(bodies),
            f'{stats["requests"]} of {len(bodies)} requests rode a batch')
    require(stats['shed_total'] == 0 and
            stats['quarantine_dispatches'] == 0,
            f'requests were shed to the host: {stats}')
    require(report['failures_total'] == 0 and
            failures.count('device path failure') == 0 and
            all(b['state'] == breaker.CLOSED for b in report['breakers']),
            f'the device path failed: {report}')
    require(handlers.device, 'the device path was switched off')
    records = benchlib.executables(scanner.fingerprint)
    require_none(benchlib.executables_problems(records, platform,
                                               'admission'),
                 'the executable ledger')
    require(any(r['capacity'] == scanner.SMALL_BATCH and r['dispatches'] > 0
                for r in records),
            'the admission batch executable was never dispatched')
    return {'p50_ms': p50, 'p99_ms': p99}


# -- phase (d): device mutate ------------------------------------------------

def mutate_phase(seed: int, n_rows: int) -> None:
    from kyverno_tpu.conformance import corpus
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.mutate import MutateScanner
    policies = benchlib.load_policies(['mutate-defaults'])
    pods = [json.loads(body)['request']['object']
            for body in mutate_reviews.generate(
                seed + 2, mixed_cluster.generate(seed + 2, n=512), n_rows)]
    scanner = MutateScanner(policies)
    require(scanner.ok, 'the mutate pack did not lower to the device')
    t0 = time.monotonic()
    rows = scanner.scan([json.loads(json.dumps(p)) for p in pods])
    scan_s = time.monotonic() - t0
    engine = Engine()
    for i, pod in enumerate(pods):
        corpus.check_mutate_row(engine, policies, pod, rows[i],
                                f'mutate row {i}')
    edited = sum(patched != pod for pod, (_steps, patched)
                 in zip(pods, rows))
    say(f'mutate: one batch of {n_rows} rows in {scan_s:.2f}s (compile '
        f'included), {edited} documents edited, all byte-identical to '
        f'the host chain')
    require(edited > 0, 'the mutate batch edited nothing')


# -- --mesh: the path across chips -------------------------------------------

def mesh_phase(policies, cluster, n_devices: int) -> None:
    import jax
    import numpy as np
    from kyverno_tpu.compiler.compile import compile_policies
    from kyverno_tpu.compiler.ir import N_STATUS_CODES
    from kyverno_tpu.compiler.shapes import canonical_caps
    from kyverno_tpu.ops.eval import shard_batch
    from kyverno_tpu.parallel.mesh import distributed_scan_step, make_mesh
    devices = jax.devices()
    require(len(devices) == n_devices,
            f'--mesh needs {n_devices} devices, JAX reports '
            f'{len(devices)}')
    cps = compile_policies(policies)
    chunk = canonical_caps()[-1]
    mesh = make_mesh(devices)
    one = make_mesh(devices[:1])

    # where the input shards sit: one per device, no two on the same
    probe, _layout = shard_batch(
        {'probe': np.zeros((chunk, 1), np.int32)}, mesh)
    homes = [s.device for s in probe['pk_int32'].addressable_shards]
    say(f'mesh: input shards on {[str(d) for d in homes]}')
    require(len(set(homes)) == n_devices,
            f'input shards sit on {len(set(homes))} distinct devices')

    t_mesh = t_one = 0.0
    rows = 0
    for start in range(0, len(cluster), chunk):
        part = cluster[start:start + chunk]
        t0 = time.monotonic()
        statuses, summary = distributed_scan_step(cps, mesh, part)
        t1 = time.monotonic()
        want, _summary = distributed_scan_step(cps, one, part)
        t2 = time.monotonic()
        t_mesh += t1 - t0
        t_one += t2 - t1
        require(statuses.shape == want.shape and
                bool((statuses == want).all()),
                f'mesh statuses of rows {start}.. differ from the '
                f'one-chip step\'s')
        hist = np.stack([(want == code).sum(axis=0)
                         for code in range(N_STATUS_CODES)], axis=1)
        require(bool((summary == hist).all()),
                f'mesh summary of rows {start}.. is not the histogram '
                f'of the one-chip statuses')
        rows += len(part)
    say(f'mesh: {rows} rows in chunks of {chunk}: statuses equal row for '
        f'row, summaries equal the one-chip histograms; {n_devices}-chip '
        f'steps {t_mesh:.1f}s, one-chip steps {t_one:.1f}s (encode and '
        f'compile included in both)')


# -- entry -------------------------------------------------------------------

def run(seed: int = 0, mesh: bool = False, platform: str = 'tpu',
        n_resources: int = N_RESOURCES, n_sample: int = N_SAMPLE,
        n_policies: int = N_ADMISSION_POLICIES,
        n_requests: int = N_ADMISSION_REQUESTS,
        n_mutate: int = N_MUTATE_ROWS, n_mesh_devices: int = 4) -> dict:
    """Every phase, in order; returns the result object.  The arguments
    after ``mesh`` are for rehearsals off the chip (tests/, a scratch
    script): the driver's run takes the defaults."""
    import jax
    device = jax.devices()[0]
    require(device.platform == platform,
            f'(a) the first JAX device is {device.platform!r} '
            f'({device.device_kind}), not {platform!r}')
    say(f'(a) {len(jax.devices())} x {device.device_kind} '
        f'({device.platform}), jax {jax.__version__}')

    from kyverno_tpu.aotcache import enable_persistent_compilation_cache
    from kyverno_tpu.compiler.scan import stop_encoder_processes
    from kyverno_tpu.observability import device as devtel
    events = benchlib.CacheEvents()
    cache_dir = enable_persistent_compilation_cache()
    require(cache_dir and jax.config.jax_compilation_cache_dir == cache_dir,
            f'the compile cache is at '
            f'{jax.config.jax_compilation_cache_dir!r}, not {cache_dir!r}')
    before = cache_entries(cache_dir, 'jit_evaluate_packed-')
    say(f'compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR '
        f'{"set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset"}'
        f'), {before} evaluator entries before this run')
    registry = benchlib.program_telemetry()

    policies = benchlib.load_policies(PACKS)
    t0 = time.monotonic()
    cluster = mixed_cluster.generate(seed, n_resources)
    kinds = {}
    for r in cluster:
        kinds[r['kind']] = kinds.get(r['kind'], 0) + 1
    say(f'cluster: {len(cluster)} resources {kinds} from seed {seed} in '
        f'{time.monotonic() - t0:.1f}s; {len(policies)} policies')

    try:
        if mesh:
            mesh_phase(policies, cluster, n_mesh_devices)
        else:
            say('(b) background scan')
            scan_phase(policies, cluster, platform, seed, registry,
                       n_sample)
            say('(c) admission webhook')
            admission_phase(policies, cluster, platform, seed,
                            n_policies, n_requests)
            say('(d) device mutate')
            mutate_phase(seed, n_mutate)
    finally:
        devtel.disable()
        # the encoder pool, its fork server and the resource tracker are
        # the only processes this program starts
        stop_encoder_processes()
    left = benchlib.descendants()
    require(not left, f'processes this run started are still alive: {left}')
    say('processes: encoder workers, fork server and resource tracker '
        'stopped and waited for; no descendant of this process is alive')
    after = cache_entries(cache_dir, 'jit_evaluate_packed-')
    say(f'compile cache: persistent hits={events.count(events.HIT)}'
        f' of {events.count(events.REQUEST)} '
        f'compile requests; evaluator entries {before} -> {after} '
        f'({after - before} compiled fresh)')
    say(f'all phases passed in {time.monotonic() - _T0:.1f}s')
    return {'ok': True,
            'device': {'platform': device.platform,
                       'kind': device.device_kind,
                       'count': len(jax.devices())}}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--mesh', action='store_true',
                        help='four chips: only the sharded scan step and '
                             'the one-chip step it is compared with')
    args = parser.parse_args()
    result = run(seed=args.seed, mesh=args.mesh)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
