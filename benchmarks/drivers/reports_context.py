"""Driver of the reports controller over a cluster whose policies read
ConfigMaps: ``drivers/reports_controller.py``'s ``Driver`` (loaded, not
copied) with

* a store that also answers ``get_resource('v1', 'ConfigMap', ns, name)``
  from the generator's ``context_objects`` and raises as a cluster client
  does for one that is not there: the controller builds its context loader
  from the client it is given;
* a set-up that refuses, at once, a program that leaves a rule of the packs
  on the host (a guarantee of the configuration: 0 host rules);
* a check that holds **every** stored report's rows of the context policies
  to the plain reference (``reference/context_rules.py``), the cells
  materialised for a failed context load to exactly those the reference
  names, and the sampled reports to the host engine with the same loader;
* the context counters in the snapshot the layer files read.
"""

from __future__ import annotations

import time

import benchlib
from benchlib import say

_base = benchlib.load_module('drivers', 'reports_controller')
_reference = benchlib.load_module('reference', 'context_rules')

CONTEXT_POLICIES = ('allowed-pod-priorities', 'cm-array-example',
                    'exclude-namespaces-dynamically', 'tenant-allowed-tiers')


class ContextStore(_base.ReportStore):
    """The report sink, and the cluster's ConfigMaps behind the same
    client verbs."""

    def __init__(self, config_maps: list):
        super().__init__()
        self.config_maps = {
            (c['metadata']['namespace'], c['metadata']['name']): c
            for c in config_maps}

    def get_resource(self, api_version, kind, ns, name):
        if kind != 'ConfigMap':
            return super().get_resource(api_version, kind, ns, name)
        from kyverno_tpu.dclient.client import NotFoundError
        found = self.config_maps.get((ns, name))
        if found is None:
            raise NotFoundError(_reference.absent_text(ns, name))
        return found


class Driver(_base.Driver):

    def setup(self) -> None:
        from kyverno_tpu.compiler.compile import compile_policies
        host = compile_policies(
            benchlib.load_policies(self.config['packs'])).host_rules
        if host:
            names = [f'{p.name}/{rule.get("name")}' for _i, rule, p in host]
            raise SystemExit(
                f'this program leaves {len(host)} rules of the packs on the '
                f'host ({", ".join(names)}): the configuration guarantees 0 '
                f'host rules')
        spec = self.config['cluster']
        generator = benchlib.load_module('generators', spec['generator'])
        self.config_maps = generator.context_objects(self.seed,
                                                     **spec['params'])
        # the base class builds the controller around the store it makes
        # itself, by this name: for the length of its set-up the name
        # gives a store that has the ConfigMaps too
        plain = _base.ReportStore
        _base.ReportStore = lambda: ContextStore(self.config_maps)
        try:
            super().setup()
        finally:
            _base.ReportStore = plain
        say(f'set-up/context: {len(self.config_maps)} ConfigMaps behind '
            f'the controller\'s client')

    def _snapshot(self) -> dict:
        from kyverno_tpu.observability import coverage
        from kyverno_tpu.observability import device as devtel
        snap = super()._snapshot()
        value = self.registry.counter_value
        lookups = value(getattr(devtel, 'CONTEXT_LOOKUPS', ''))
        loads = {r: value(getattr(devtel, 'CONTEXT_LOADS', ''), result=r)
                 for r in ('ok', 'failed')}
        by_reason = (coverage.bench_block() or {}).get('by_reason', {}) \
            .get('validate', {})
        cells = 0
        report = coverage.ledger().report() if coverage.ledger() else {}
        for rule in report.get('rules', []):
            if rule.get('policy') in CONTEXT_POLICIES:
                cells += rule.get('device_rows', 0) + rule.get('host_rows', 0)
        snap['context'] = {
            'lookups': lookups, 'loads': loads['ok'] + loads['failed'],
            'loads_failed': loads['failed'],
            'memo_hits': lookups - loads['ok'] - loads['failed'],
            'cells': cells,
            'load_failed_cells': by_reason.get('context_load_failed', 0)}
        snap['by_reason'] = dict(by_reason)
        return snap

    def check(self) -> list:
        from kyverno_tpu.engine.apicall import make_context_loader
        from kyverno_tpu.engine.engine import Engine
        # the base check's sample is compared here, by an engine that has
        # the cluster's ConfigMaps too
        sample, self._checked = self._checked, []
        problems = super().check()
        t0 = time.monotonic()
        engine = Engine(context_loader=make_context_loader(
            dclient=self.store))
        differing = _base.compare_reports(self.store, engine, self.policies,
                                          sample)
        say(f'check: {len(sample) - len(differing)} of {len(sample)} '
            f'sampled reports equal the host engine\'s '
            f'({time.monotonic() - t0:.1f}s)')
        self.failed += len(differing)
        problems += differing[:5]

        t0 = time.monotonic()
        maps = _reference.index(self.config_maps)
        wrong = in_window = 0
        tally = {}
        # the warm rows were scanned in set-up: the window's share of the
        # reference's failed loads is that of the rows after them
        warm = self.traffic['warm_rows']
        for i, resource in enumerate(self.cluster):
            want = _reference.rows(resource, maps)
            meta = resource['metadata']
            # the report of a namespaced resource without a uid is named
            # after it (reports/types.py new_background_scan_report)
            report = self.store.reports.get(
                ('BackgroundScanReport', meta['namespace'], meta['name']))
            got = [(r['policy'], r['rule'], r['result'],
                    r.get('message') if r['result'] == 'error' else None)
                   for r in (report or {}).get('spec', {}).get('results', [])
                   if r['policy'] in CONTEXT_POLICIES]
            if report is None or sorted(got) != sorted(want):
                wrong += 1
                if wrong <= 3:
                    problems.append(
                        f'context rows of {resource["kind"]} '
                        f'{meta["name"]} are {got}, the reference says '
                        f'{want}')
            for _p, _r, result, _m in want:
                tally[result] = tally.get(result, 0) + 1
                in_window += result == 'error' and i >= warm
        say(f'check: context rows of {len(self.cluster) - wrong} of '
            f'{len(self.cluster)} reports equal the plain reference\'s '
            f'({tally}; {time.monotonic() - t0:.1f}s)')
        self.failed += wrong
        if wrong > 3:
            problems.append(f'{wrong} reports differ from the reference in '
                            f'their context rows')
        got_failed = self._counters['context']['load_failed_cells']
        share = in_window / max(1, len(self.cluster) - warm)
        say(f'check: {got_failed} cells materialised for a failed context '
            f'load, the reference names {in_window} in the window '
            f'({100 * share:.2f}% of its rows); context counters '
            f'{self._counters["context"]}; host rows by reason '
            f'{self._counters.get("by_reason")}')
        if got_failed != in_window:
            problems.append(
                f'{got_failed} cells were materialised for a failed context '
                f'load, the reference names {in_window}')
        return problems
