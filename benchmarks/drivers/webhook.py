"""Driver of the admission webhook: AdmissionReviews through
``WebhookServer.handle('/validate/fail', body)`` with
``ResourceHandlers(serving_mode='batch')``, as ``chip_smoke.py``'s admission
phase drives it, at the policy count of the configuration.

The traffic file's ``loop`` chooses the load:

``open``    independent writers.  ``round(rate_per_s x seconds)`` arrival
            times, the same set of gaps for every seed in an order the seed
            chooses; a dispatcher hands each request to a pool of waiting
            threads at its absolute due time, and each is **timed from when
            it was due**.  Every request due inside the window is waited
            for, up to the timeout.
``closed``  ``clients`` threads, each sending its next request the moment the
            last is answered, until ``seconds`` have passed; requests in
            flight at the deadline are finished and not counted.

A request not answered within the timeout, or answered unlike the reference
(the same handler chain with ``device=False``), is failed.  A shed request is
answered by the host engine and is right: it is counted, not failed.
"""

from __future__ import annotations

import json
import os
import queue
import random
import threading
import time

import benchlib
import bytes as dispatch_bytes
from benchlib import say


def arrivals(seed: int, rate_per_s: float, seconds: float,
             gap_seed: int = 0) -> list:
    """Due times, in seconds from the window's start, of an open loop: a pure
    function of its arguments.  ``round(rate x seconds)`` exponential gaps
    are drawn once from ``gap_seed`` (a Poisson process conditioned on its
    count) and scaled to fill the window; ``seed`` only shuffles them, so
    every seed offers the same set of gaps in another order."""
    n = int(round(rate_per_s * seconds))
    if n <= 0:
        return []
    draw = random.Random(gap_seed)
    gaps = [draw.expovariate(1.0) for _ in range(n + 1)]
    random.Random(seed).shuffle(gaps)
    scale = seconds / sum(gaps)
    due, at = [], 0.0
    for gap in gaps[:n]:
        at += gap * scale
        due.append(at)
    return due


class Driver:
    def __init__(self, config, traffic, seed, seconds, platform, registry):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = seed, seconds
        self.platform, self.registry = platform, registry
        self.attempted = self.failed = 0
        self.server = None
        self.timeout_s = float(config['guarantees']['answer_within_s'])

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from kyverno_tpu.policycache import cache as pcache
        from kyverno_tpu.serving import breaker
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        from kyverno_tpu.webhooks.server import WebhookServer
        t0 = time.monotonic()
        n_policies = self.config['replicate_to']
        policies = benchlib.replicate_enforce(
            benchlib.load_policies(self.config['packs']), n_policies)
        spec = self.config['cluster']
        cluster = benchlib.load_module(
            'generators', spec['generator']).generate(self.seed,
                                                      **spec['params'])
        spec = self.config['requests']
        self.bodies = benchlib.load_module(
            'generators', spec['generator']).generate(
                self.seed, cluster, spec['pool'], **spec['params'])
        say(f'set-up/generate: {len(policies)} enforce policies, '
            f'{len(self.bodies)} request bodies from a cluster of '
            f'{len(cluster)} in {time.monotonic() - t0:.1f}s')

        t0 = time.monotonic()
        self.failures = benchlib.FailureLog()
        self.cache = pcache.Cache()
        self.cache.warm_up(policies)
        self.handlers = ResourceHandlers(self.cache, serving_mode='batch')
        self.server = WebhookServer(self.handlers)
        enforce = self.cache.get_policies(pcache.VALIDATE_ENFORCE, 'Pod',
                                          'ns-0')
        if len(enforce) != n_policies:
            raise RuntimeError(f'{len(enforce)} enforce policies apply to a '
                               f'Pod, not {n_policies}')
        if not self.handlers.wait_device_ready(enforce, timeout=900):
            raise RuntimeError(
                'the compiled admission path did not come up: ' + '; '.join(
                    f'{b["state"]} after {b["failures"]} failures, last: '
                    f'{b.get("last_error", "")}'
                    for b in breaker.debug_report()['breakers']))
        self.scanner = self.handlers._device_scanner(enforce)
        say(f'set-up/build: {n_policies} enforce policies ready on the '
            f'device in {time.monotonic() - t0:.1f}s')

        # the first requests of the pool, unmeasured; the window starts
        # behind them
        t0 = time.monotonic()
        self.next_index = self.traffic['warm_requests']
        for body in self.bodies[:self.next_index]:
            self.server.handle('/validate/fail', body)
        self.handlers._get_batcher().reset_stats()
        say(f'set-up/warm: {self.next_index} requests in '
            f'{time.monotonic() - t0:.1f}s')

    # -- the loops ------------------------------------------------------------

    def _send(self, k: int, due: float, record: dict) -> None:
        body = self.bodies[k % len(self.bodies)]
        sent = time.monotonic()
        answer = self.server.handle('/validate/fail', body)
        done = time.monotonic()
        record[k] = (due, sent, done, answer)

    def _open(self, rate_per_s: float, seconds: float, first: int) -> dict:
        """Offer ``rate_per_s`` for ``seconds``; returns ``{index: (due,
        sent, done, answer)}`` in absolute monotonic seconds, for every
        request answered inside the timeout."""
        due_in = arrivals(self.seed, rate_per_s, seconds,
                          self.traffic.get('gap_seed', 0))
        record, jobs = {}, queue.Queue()

        def worker() -> None:
            while True:
                job = jobs.get()
                if job is None:
                    return
                self._send(job[0], job[1], record)

        pool = [threading.Thread(target=worker, daemon=True)
                for _ in range(self.traffic['threads'])]
        for t in pool:
            t.start()
        start = time.monotonic()
        for j, offset in enumerate(due_in):
            due = start + offset
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            jobs.put((first + j, due))
        for _ in pool:
            jobs.put(None)
        deadline = start + seconds + self.timeout_s
        for t in pool:
            t.join(max(0.0, deadline - time.monotonic()))
        self._window = (start, start + seconds)
        self._due = {first + j: start + off for j, off in enumerate(due_in)}
        return dict(record)

    def _closed(self, clients: int, seconds: float, first: int) -> dict:
        record, lock = {}, threading.Lock()
        counter = [first]
        barrier = threading.Barrier(clients + 1)
        stop_at = [0.0]

        def client() -> None:
            barrier.wait()
            while time.monotonic() < stop_at[0]:
                with lock:
                    k = counter[0]
                    counter[0] += 1
                self._send(k, time.monotonic(), record)

        pool = [threading.Thread(target=client, daemon=True)
                for _ in range(clients)]
        for t in pool:
            t.start()
        start = time.monotonic()
        stop_at[0] = start + seconds
        barrier.wait()
        for t in pool:
            t.join(max(0.0, stop_at[0] + self.timeout_s - time.monotonic()))
        self._window = (start, start + seconds)
        self._due = {}
        return dict(record)

    def measure(self) -> dict:
        loop = self.traffic['loop']
        first = self.next_index
        if loop == 'open':
            record = self._open(self.traffic['rate_per_s'], self.seconds,
                                first)
            return self._reduce_open(record)
        if loop == 'closed':
            record = self._closed(self.traffic['clients'], self.seconds,
                                  first)
            return self._reduce_closed(record)
        raise ValueError(f'unknown loop {loop!r} for the webhook driver')

    def _reduce_open(self, record: dict) -> dict:
        start, end = self._window
        timeout_ms = self.timeout_s * 1000.0
        latency, late = [], []
        for k, due in self._due.items():
            got = record.get(k)
            ms = (got[2] - due) * 1000.0 if got else timeout_ms
            if got:
                late.append((got[1] - due) * 1000.0)
            if ms >= timeout_ms:
                ms = timeout_ms
                self.failed += 1
            latency.append(ms)
        self.attempted = len(self._due)
        self.record = record
        self._samples = {'latency_ms': latency, 'late_ms': late}
        say(f'window: {len(latency)} requests offered, '
            f'{sum(k in record for k in self._due)} answered, '
            f'{self.failed} past the {self.timeout_s:g}s timeout')
        return {'start': start, 'end': max(end, max(
                    (r[2] for r in record.values()), default=end)),
                'metrics': benchlib.yields(self.traffic, {
                    'p50_ms': benchlib.quantile(latency, 0.50),
                    'p95_ms': benchlib.quantile(latency, 0.95)})}

    def _reduce_closed(self, record: dict) -> dict:
        start, end = self._window
        inside = {k: r for k, r in record.items() if r[2] <= end}
        slow = sum((r[2] - r[1]) >= self.timeout_s for r in inside.values())
        self.attempted = len(inside)
        self.failed += slow
        self.record = inside
        self._samples = {'latency_ms': [(r[2] - r[1]) * 1000.0
                                        for r in inside.values()],
                         'late_ms': []}
        say(f'window: {len(inside)} requests answered inside '
            f'{self.seconds:g}s ({len(record) - len(inside)} in flight at '
            f'the deadline, not counted), {slow} past the timeout')
        return {'start': start, 'end': end, 'metrics': benchlib.yields(self.traffic, {
            'rate': (len(inside) - slow) / self.seconds})}

    # -- the sweep (the builder's tool, not a measurement) --------------------

    def sweep(self, rates: list, step_seconds: float) -> None:
        batcher = self.handlers._get_batcher()
        first = self.next_index
        for rate in rates:
            batcher.reset_stats()
            self.failed = 0
            record = self._open(rate, step_seconds, first)
            self._reduce_open(record)
            start = self._window[0]
            first += len(self._due)
            lat, late = self._samples['latency_ms'], self._samples['late_ms']

            def in_flight(at: float) -> int:
                return sum(due <= at and (k not in record or
                                          record[k][2] > at)
                           for k, due in self._due.items())

            stats = batcher.stats()
            flight = [in_flight(start + f * step_seconds)
                      for f in (1 / 3, 2 / 3, 1.0)]
            timeouts = self.failed
            sustained = not stats['shed_total'] and not timeouts and \
                flight[2] <= max(flight[0], 2)
            say('sweep: ' + json.dumps({
                'rate_per_s': rate, 'offered': len(lat),
                'p50_ms': benchlib.quantile(lat, 0.5),
                'p95_ms': benchlib.quantile(lat, 0.95), 'max_ms': max(lat),
                'late_p95_ms': benchlib.quantile(late, 0.95) if late else None,
                'in_flight_at_1/3_2/3_end': flight,
                'shed': stats['shed_total'], 'timeouts': timeouts,
                'occupancy_mean': stats['occupancy_mean'],
                'dispatches': stats['dispatches'],
                'sustained': sustained}))
            # let the queue drain before the next rate is offered
            time.sleep(min(5.0, step_seconds / 3))

    # -- the check ------------------------------------------------------------

    def check(self) -> list:
        from kyverno_tpu.serving import breaker
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        from kyverno_tpu.webhooks.server import WebhookServer
        problems = []
        self._stats = self.handlers._get_batcher().stats()
        answered = sorted(self.record)
        denied = sum(not json.loads(self.record[k][3])['response']['allowed']
                     for k in answered)
        if not 0 < denied < len(answered):
            problems.append(f'{denied} of {len(answered)} requests denied: '
                            f'the traffic does not exercise both answers')
        # the reference: the same handler chain with the device path off
        host = WebhookServer(ResourceHandlers(self.cache, device=False))
        t0 = time.monotonic()
        sample = random.Random(self.seed + 1).sample(
            answered, min(self.config['check']['sample'], len(answered)))
        differing = 0
        for k in sample:
            body = self.bodies[k % len(self.bodies)]
            want = json.loads(host.handle('/validate/fail', body))['response']
            got = json.loads(self.record[k][3])['response']
            if got != want:
                differing += 1
                problems.append(f'answer {k} differs from the host '
                                f'engine\'s: {got} != {want}')
        host.stop()
        say(f'check: {len(sample) - differing} of {len(sample)} sampled '
            f'answers equal the host chain\'s ({denied} of {len(answered)} '
            f'denied; reference took {time.monotonic() - t0:.1f}s)')
        self.failed += differing
        del problems[5:]

        stats, report = self._stats, breaker.debug_report()
        lines = self.failures.count('device path failure')
        say(f'check: batcher {json.dumps(stats)}; breakers='
            f'{json.dumps(report["breakers"])} failures_total='
            f'{report["failures_total"]} device_path_failure_lines={lines}')
        if stats['quarantine_dispatches']:
            problems.append(f'{stats["quarantine_dispatches"]} quarantine '
                            f'dispatches')
        if report['failures_total'] or lines or not all(
                b['state'] == breaker.CLOSED for b in report['breakers']):
            problems.append(f'the device path failed: {report}')
        if not self.handlers.device:
            problems.append('the device path was switched off')
        records = benchlib.executables(self.scanner.fingerprint)
        problems += benchlib.executables_problems(records, self.platform,
                                                  'admission')
        if not any(r['capacity'] == self.scanner.SMALL_BATCH
                   and r['dispatches'] for r in records):
            problems.append('the admission batch executable was never '
                            'dispatched')
        return problems

    def counters(self) -> dict:
        out = {'batcher': {k: v for k, v in self._stats.items()
                           if isinstance(v, (int, float))},
               'requests': {'offered': self.attempted},
               'samples': self._samples}
        ev = self.scanner._evaluator
        layout = ev.layout_holder['layout']
        if layout:
            out['dispatch'] = dispatch_bytes.describe(
                layout, self.scanner.SMALL_BATCH, ev.n_uniq, ev.n_cols_u,
                ev.n_adm, benchlib.executables(self.scanner.fingerprint),
                int(os.environ.get('KTPU_FDET_K', '32')))
        return out

    def close(self) -> None:
        if self.server is not None:
            self.server.stop()
