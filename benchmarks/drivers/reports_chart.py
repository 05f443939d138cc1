"""Driver of the reports controller over a cluster that installed the Helm
chart ``kyverno-policies``: ``drivers/reports_controller.py``'s ``Driver``
(loaded, not copied) with

* a set-up that refuses, at once, a program that compiles the packs to
  anything but what the configuration guarantees (``expect``: 27 policies,
  75 rule programs, 0 rules left on the host);
* a check that holds **every** stored report's rows of the chart's policies
  (rule name and pass / fail / skip) to the plain reference
  (``reference/chart_rules.py``), beside the base check's sampled reports
  against the host engine, messages included;
* the counts of matched cells and of FAIL cells lost to the fail-detail
  budget in the snapshot the layer files read.
"""

from __future__ import annotations

import time

import benchlib
from benchlib import say

_base = benchlib.load_module('drivers', 'reports_controller')
_reference = benchlib.load_module('reference', 'chart_rules')


class Driver(_base.Driver):

    def setup(self) -> None:
        from kyverno_tpu.compiler.compile import compile_policies
        policies = benchlib.load_policies(self.config['packs'])
        cps = compile_policies(policies)
        got = {'policies': len(policies), 'programs': len(cps.programs),
               'host_rules': len(cps.host_rules)}
        if got != self.config['expect']:
            names = [f'{p.name}/{rule.get("name")}'
                     for _i, rule, p in cps.host_rules]
            raise SystemExit(
                f'this program compiles the packs to {got} (on the host: '
                f'{", ".join(names) or "none"}); the configuration '
                f'guarantees {self.config["expect"]}')
        super().setup()

    def _snapshot(self) -> dict:
        from kyverno_tpu.observability import coverage
        from kyverno_tpu.observability import device as devtel
        snap = super()._snapshot()
        # a program without the counter or the reason has neither key, and
        # the metric that reads it is left out of the line
        name = getattr(devtel, 'MATCH_CELLS', None)
        if name is not None:
            matched, unmatched = (
                self.registry.counter_value(name, result=r)
                for r in ('matched', 'unmatched'))
            snap['match'] = {'matched': matched,
                             'cells': matched + unmatched}
        by_reason = (coverage.bench_block() or {}).get('by_reason', {}) \
            .get('validate', {})
        reason = getattr(coverage, 'REASON_FAIL_DETAIL_BUDGET', None)
        if reason is not None:
            snap['budget'] = {'cells': by_reason.get(reason, 0)}
        snap['by_reason'] = dict(by_reason)
        return snap

    def check(self) -> list:
        problems = super().check()
        t0 = time.monotonic()
        wrong = 0
        tally, kinds = {}, {}
        for resource in self.cluster:
            want = _reference.rows(resource)
            meta = resource['metadata']
            # the report of a namespaced resource without a uid is named
            # after it (reports/types.py new_background_scan_report)
            report = self.store.reports.get(
                ('BackgroundScanReport', meta['namespace'], meta['name']))
            got = sorted((r['policy'], r['rule'], r['result'])
                         for r in (report or {}).get('spec', {})
                         .get('results', [])
                         if r['policy'] in _reference.POLICIES)
            if report is None or got != want:
                wrong += 1
                if wrong <= 3:
                    differing = sorted(set(got) ^ set(want))
                    problems.append(
                        f'chart rows of {resource["kind"]} {meta["name"]} '
                        f'differ from the reference\'s in {differing[:6]}')
            kinds[resource['kind']] = kinds.get(resource['kind'], 0) + 1
            for _p, _r, result in want:
                tally[result] = tally.get(result, 0) + 1
        say(f'check: chart rows of {len(self.cluster) - wrong} of '
            f'{len(self.cluster)} reports equal the plain reference\'s '
            f'({tally} over {kinds}; {time.monotonic() - t0:.1f}s)')
        self.failed += wrong
        if wrong > 3:
            problems.append(f'{wrong} reports differ from the reference in '
                            f'their chart rows')
        say(f'check: matched cells {self._counters.get("match")}; FAIL '
            f'cells beyond the fail-detail budget '
            f'{self._counters.get("budget")}; host rows by reason '
            f'{self._counters.get("by_reason")}')
        return problems
