"""Driver of the reports controller: ``BackgroundScanController.reconcile``
over a cluster held in the ``MetadataCache``, driven as
``kyverno_tpu/cmd/reports_controller.py`` ``tick`` drives it
(``enqueue``/``enqueue_all`` then ``reconcile``).

The traffic file's ``loop`` chooses what the window holds:

``scan_all``   ``enqueue_all()`` and one ``reconcile()``.  A reconcile cannot
               be cut off, so the benchmark's own report store stamps every
               report it is handed, and the rate is the count of reports
               stamped in the first ``seconds`` of the reconcile over that
               time (over the reconcile's wall if it ended sooner).
``churn``      ticks back to back until ``seconds`` have passed: each touches
               ``churn_share`` of the cluster (a new image on the first
               container), enqueues what it touched and reconciles.  The rate
               is the reports written over the time from the first tick's
               start to the last completed tick's end.

The store, the reference (``host_report``) and the comparison
(``compare_reports``) are ``chip_smoke.py``'s.
"""

from __future__ import annotations

import collections
import os
import random
import time

import benchlib
import bytes as dispatch_bytes
from benchlib import say


class ReportStore:
    """The report sink the controller writes through: the client verbs it
    calls, kept in one dict so every report can be read back, with the time
    each report was handed over."""

    def __init__(self):
        self.reports = {}
        self.stamps = []

    def _put(self, kind, ns, obj):
        self.stamps.append(time.monotonic())
        self.reports[(kind, ns, obj['metadata']['name'])] = obj
        return obj

    def get_resource(self, api_version, kind, ns, name):
        return self.reports[(kind, ns, name)]

    def create_resource(self, api_version, kind, ns, obj):
        return self._put(kind, ns, obj)

    def update_resource(self, api_version, kind, ns, obj):
        return self._put(kind, ns, obj)

    def delete_resource(self, api_version, kind, ns, name):
        self.reports.pop((kind, ns, name), None)

    def list_resource(self, *a, **k):
        return []  # no PolicyExceptions in this cluster


def host_report(engine, policies, resource):
    """The BackgroundScanReport the host engine gives one resource."""
    from kyverno_tpu.engine.api import PolicyContext
    from kyverno_tpu.reports.results import set_responses
    from kyverno_tpu.reports.types import new_background_scan_report
    responses = [engine.apply_background_checks(
        PolicyContext(p, new_resource=resource)) for p in policies]
    report = new_background_scan_report(resource)
    set_responses(report, *[r for r in responses
                            if r.policy_response.rules])
    return report


def _sans_timestamp(results):
    return [{k: v for k, v in r.items() if k != 'timestamp'}
            for r in results or []]


def compare_reports(store, engine, policies, resources) -> list:
    """What differs between the stored reports of ``resources`` and the host
    engine's, one line each; empty when they are equal."""
    differing = []
    for resource in resources:
        want = host_report(engine, policies, resource)
        name = want['metadata']['name']
        ns = resource['metadata'].get('namespace', '')
        got = store.reports.get((want['kind'], ns, name))
        if got is None:
            differing.append(f'no report for {resource["kind"]} {name}')
        elif got['spec']['summary'] != want['spec']['summary'] or \
                _sans_timestamp(got['spec']['results']) != \
                _sans_timestamp(want['spec']['results']):
            differing.append(f'report rows of {resource["kind"]} {name} '
                             f'differ from the host engine\'s')
    return differing


class Driver:
    def __init__(self, config, traffic, seed, seconds, platform, registry):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = seed, seconds
        self.platform, self.registry = platform, registry
        self.attempted = self.failed = 0
        self.ctrl = None
        self._checked = []   # resources whose reports the check compares
        self._rows = 0       # resources scanned inside the counted time

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from kyverno_tpu.reports.controllers import (BackgroundScanController,
                                                     MetadataCache)
        t0 = time.monotonic()
        self.policies = benchlib.load_policies(self.config['packs'])
        spec = self.config['cluster']
        generator = benchlib.load_module('generators', spec['generator'])
        self.generator = generator
        self.cluster = generator.generate(self.seed, **spec['params'])
        kinds = {}
        for r in self.cluster:
            kinds[r['kind']] = kinds.get(r['kind'], 0) + 1
        say(f'set-up/generate: {len(self.cluster)} resources {kinds}, '
            f'{len(self.policies)} policies in {time.monotonic() - t0:.1f}s')

        t0 = time.monotonic()
        self.store = ReportStore()
        cache = MetadataCache()
        self.ctrl = BackgroundScanController(self.store, self.policies,
                                             cache=cache)
        for resource in self.cluster:
            cache.update(resource)
        scanner = self.ctrl.scanner
        say(f'set-up/build: metadata cache filled, scanner built '
            f'({len(scanner.cps.programs)} programs, '
            f'{len(scanner.cps.host_rules)} host rules) in '
            f'{time.monotonic() - t0:.1f}s')

        # more than the small batch, so that the bulk executable is built or
        # loaded and the encoder pool is up before the window
        t0 = time.monotonic()
        warm = self.cluster[:self.traffic['warm_rows']]
        for resource in warm:
            self.ctrl.enqueue(resource)
        reports = self.ctrl.reconcile()
        say(f'set-up/warm: reconcile of {len(warm)} resources, '
            f'{len(reports)} reports in {time.monotonic() - t0:.1f}s')
        if self.traffic.get('first_scan'):
            t0 = time.monotonic()
            self.ctrl.enqueue_all()
            reports = self.ctrl.reconcile()
            say(f'set-up/first scan: {len(reports)} reports in '
                f'{time.monotonic() - t0:.1f}s')

    # -- the window -----------------------------------------------------------

    def _snapshot(self) -> dict:
        from kyverno_tpu.observability import coverage
        from kyverno_tpu.observability import device as devtel
        # stage_breakdown()'s numbers unrounded: (labels, count, total)
        stages = {dict(key).get('stage', ''): {'total_s': total,
                                               'count': count}
                  for key, count, total in self.registry.histogram_series(
                      devtel.SCAN_STAGE_DURATION)}
        records = benchlib.executables(self.ctrl.scanner.fingerprint)
        return {'stages': stages,
                'coverage': {k: v for k, v in
                             (coverage.bench_block() or {}).items()
                             if isinstance(v, (int, float))},
                'dispatches': {str(r['capacity']): r['dispatches']
                               for r in records}}

    def measure(self) -> dict:
        before = self._snapshot()
        loop = getattr(self, '_loop_' + self.traffic['loop'])
        start = time.monotonic()
        metrics = loop(start)
        end = time.monotonic()
        self._counters = benchlib.delta(before, self._snapshot())
        self._counters['rows_scanned'] = self._rows
        return {'start': start, 'end': end, 'metrics': metrics}

    def _loop_scan_all(self, start: float) -> dict:
        n_before = len(self.store.stamps)
        self.ctrl.enqueue_all()
        reports = self.ctrl.reconcile()
        wall = time.monotonic() - start
        stamps = self.store.stamps[n_before:]
        span = min(self.seconds, wall)
        in_window = sum(t <= start + span for t in stamps)
        self.attempted = len(self.cluster) - self.traffic['warm_rows']
        self.failed += self.attempted - len(reports)
        self._rows = len(reports)
        say(f'window: reconcile wrote {len(reports)} reports in {wall:.1f}s; '
            f'{in_window} of them in the first {span:.1f}s')
        per_5s = dict(sorted(collections.Counter(
            int((t - start) // 5) * 5 for t in stamps).items()))
        edge = start + span
        say(f'window: reports by 5 s of the reconcile: {per_5s}; last one '
            f'inside the window at '
            f'{max((t for t in stamps if t <= edge), default=start) - start:.2f}s,'
            f' first one outside at '
            f'{min((t for t in stamps if t > edge), default=edge) - start:.2f}s')
        rng = random.Random(self.seed + 1)
        self._checked = rng.sample(
            self.cluster, min(self.config['check']['sample'],
                              len(self.cluster)))
        return benchlib.yields(self.traffic, {'rate': in_window / span})

    def _loop_churn(self, start: float) -> dict:
        n = len(self.cluster)
        per_tick = max(1, int(n * self.traffic['churn_share']))
        written = ticks = 0
        touched = []
        while time.monotonic() - start < self.seconds:
            rng = random.Random((self.seed << 12) + ticks)
            touched = rng.sample(range(n), per_tick)
            for i in touched:
                spec = self.generator.pod_spec(self.cluster[i])
                spec['containers'][0]['image'] = \
                    f'registry/churn-{ticks}:{i}'
                self.ctrl.enqueue(self.cluster[i])
            t0 = time.monotonic()
            reports = self.ctrl.reconcile()
            say(f'window: tick {ticks} reconciled {len(reports)} of '
                f'{len(touched)} touched in {time.monotonic() - t0:.2f}s')
            self.attempted += len(touched)
            self.failed += len(touched) - len(reports)
            written += len(reports)
            ticks += 1
        self._rows = written
        self._checked = [self.cluster[i] for i in
                         touched[:self.config['check']['sample_churn']]]
        return benchlib.yields(
            self.traffic, {'rate': written / (time.monotonic() - start)})

    # -- the check ------------------------------------------------------------

    def check(self) -> list:
        from kyverno_tpu.engine.engine import Engine
        from kyverno_tpu.observability import device as devtel
        problems = []
        n = len(self.cluster)
        if len(self.store.reports) != n:
            problems.append(f'{len(self.store.reports)} reports are stored '
                            f'for {n} resources')
        t0 = time.monotonic()
        differing = compare_reports(self.store, Engine(), self.policies,
                                    self._checked)
        say(f'check: {len(self._checked) - len(differing)} of '
            f'{len(self._checked)} sampled reports equal the host '
            f'engine\'s ({time.monotonic() - t0:.1f}s)')
        self.failed += len(differing)
        problems += differing[:5]

        cov = self._counters['coverage']
        if not cov.get('total_rows') or \
                cov['device_rows'] + cov['host_rows'] != cov['total_rows']:
            problems.append(f'coverage ledger out of balance: {cov}')
        records = benchlib.executables(self.ctrl.scanner.fingerprint)
        problems += benchlib.executables_problems(records, self.platform,
                                                  'scan')
        if not any(r['dispatches'] for r in records):
            problems.append('no evaluator executable was dispatched')
        workers = {r: int(self.registry.counter_value(
            devtel.ENCODE_WORKER_CHUNKS, result=r))
            for r in ('ok', 'presumed_dead', 'pool_failed')}
        retries = int(self.registry.counter_total(devtel.STAGE_RETRIES))
        say(f'check: coverage {cov}; encoder workers {workers}; stage '
            f'retries {retries}')
        if workers['presumed_dead'] or workers['pool_failed']:
            problems.append(f'encoder workers were given up: {workers}')
        if retries:
            problems.append(f'{retries} pipeline stage retries')
        return problems

    def counters(self) -> dict:
        ev = self.ctrl.scanner._evaluator
        layout = ev.layout_holder['layout']
        capacity = max((int(c) for c, d in
                        self._counters['dispatches'].items() if d),
                       default=0)
        if layout and capacity:
            self._counters['dispatch'] = dispatch_bytes.describe(
                layout, capacity, ev.n_uniq, ev.n_cols_u, 0,
                benchlib.executables(self.ctrl.scanner.fingerprint),
                int(os.environ.get('KTPU_FDET_K', '32')))
        return self._counters

    def close(self) -> None:
        if self.ctrl is not None:
            self.ctrl.close()
