"""Device-coverage ledger: attributed host-fallback telemetry across
compile time (per-rule placement) and runtime (host-replay counters,
per-scan coverage ratio), the /debug/coverage endpoint, the CLI report,
and the no-op-until-configured contract."""

import json
import os
import sys
import threading

import pytest

from kyverno_tpu.api.policy import Policy
from kyverno_tpu.observability import coverage
from kyverno_tpu.observability import tracing
from kyverno_tpu.observability.metrics import (MetricsRegistry,
                                               set_global_registry)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    'scripts'))

NO_AUTOGEN = {'pod-policies.kyverno.io/autogen-controllers': 'none'}

#: fully device-compiled pattern rule
DEVICE_POL = {
    'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
    'metadata': {'name': 'dev-pol', 'annotations': dict(NO_AUTOGEN)},
    'spec': {'rules': [
        {'name': 'check-app',
         'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
         'validate': {'message': 'app label required',
                      'pattern': {'metadata': {'labels': {'app': '?*'}}}}},
    ]}}

#: device-compiled, but the general-wildcard DP is only exact inside the
#: 64-byte string window — longer label values read STATUS_HOST
DP_POL = {
    'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
    'metadata': {'name': 'dp-pol', 'annotations': dict(NO_AUTOGEN)},
    'spec': {'rules': [
        {'name': 'dp-rule',
         'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
         'validate': {'message': 'tier must thread x then y',
                      'pattern': {'metadata': {'labels':
                                               {'tier': '*x*y*'}}}}},
    ]}}

#: deprecated In operator → CompileError(unsupported_operator) → the
#: whole policy runs on the host engine
HOST_POL = {
    'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
    'metadata': {'name': 'host-pol', 'annotations': dict(NO_AUTOGEN)},
    'spec': {'rules': [
        {'name': 'legacy-in',
         'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
         'validate': {'message': 'ns check', 'deny': {'conditions': [
             {'key': '{{ request.object.metadata.namespace }}',
              'operator': 'In', 'value': ['kube-system']}]}}},
    ]}}

MUTATE_REPLACE_POL = {
    'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
    'metadata': {'name': 'mutate-replace',
                 'annotations': dict(NO_AUTOGEN)},
    'spec': {'rules': [
        {'name': 'replace-app',
         'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
         'mutate': {'patchesJson6902':
                    '- op: replace\n  path: /metadata/labels/app\n'
                    '  value: fixed\n'}},
    ]}}

MUTATE_FOREACH_POL = {
    'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
    'metadata': {'name': 'pull-policy', 'annotations': dict(NO_AUTOGEN)},
    'spec': {'rules': [
        {'name': 'set-pull-policy',
         'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
         'mutate': {'foreach': [
             {'list': 'request.object.spec.containers',
              'patchStrategicMerge': {'spec': {'containers': [
                  {'name': '{{ element.name }}',
                   'imagePullPolicy': 'IfNotPresent'}]}}}]}}
    ]}}


def pod(i, tier=None, names=('c0',), app=True):
    labels = {}
    if app and i % 2:
        labels['app'] = 'x'
    if tier is not None:
        labels['tier'] = tier
    meta = {'name': f'p{i}', 'namespace': 'default'}
    if labels:
        meta['labels'] = labels
    return {'apiVersion': 'v1', 'kind': 'Pod', 'metadata': meta,
            'spec': {'containers': [{'name': n, 'image': 'nginx:1'}
                                    for n in names]}}


def mixed_resources():
    out = [pod(i) for i in range(6)]
    out.append(pod(10, tier='axby'))            # DP decidable in-window
    out.append(pod(11, tier='a' * 80 + 'xzy'))  # overflows → STATUS_HOST
    return out


@pytest.fixture
def ledger():
    reg = MetricsRegistry()
    led = coverage.configure(reg)
    yield led, reg
    coverage.disable()


def mixed_scanner():
    from kyverno_tpu.compiler.scan import BatchScanner
    return BatchScanner([Policy(DEVICE_POL), Policy(DP_POL),
                         Policy(HOST_POL)])


class TestMixedScan:
    def test_attributed_coverage(self, ledger):
        led, reg = ledger
        scanner = mixed_scanner()
        scanner.scan(mixed_resources())
        # ratio strictly inside (0, 1): some rows device, some host
        ratio = reg.gauge_value('kyverno_tpu_device_coverage_ratio')
        assert 0.0 < ratio < 1.0
        # the overflowing DP row is attributed as status_host …
        assert reg.counter_value(
            'kyverno_tpu_host_fallback_total', path='validate',
            reason='status_host') >= 1
        # … and the host policy's replayed rows as unsupported_operator
        assert reg.counter_value(
            'kyverno_tpu_host_fallback_total', path='validate',
            reason='unsupported_operator') >= 1
        # no reason escapes the taxonomy for the exercised sites
        text = reg.render()
        assert 'reason="unknown"' not in text
        from kyverno_tpu.observability.catalog import METRICS
        for (path, reason), _rows in led._fallbacks.items():
            assert reason in coverage.REASONS, (path, reason)
        assert 'kyverno_tpu_host_fallback_total' in METRICS
        # ledger invariant (what chip_smoke.py requires of its scan)
        totals = led.totals()
        assert totals['device_rows'] + totals['host_rows'] == \
            totals['total_rows']

    def test_placement_records(self, ledger):
        led, reg = ledger
        scanner = mixed_scanner()
        scanner.scan(mixed_resources())
        rules = {(r['policy'], r['rule']): r
                 for r in led.report()['rules']}
        assert rules[('dev-pol', 'check-app')]['placement'] == 'device'
        assert rules[('dev-pol', 'check-app')]['effective'] == 'device'
        dp = rules[('dp-pol', 'dp-rule')]
        assert dp['placement'] == 'device'
        assert dp['effective'] == 'partial'  # observed host rows
        assert dp['host_rows'] >= 1 and dp['device_rows'] >= 1
        host = rules[('host-pol', 'legacy-in')]
        assert host['placement'] == 'host'
        assert host['reason'] == 'unsupported_operator'
        assert 'not vectorized' in host['detail']
        # placement gauge series exist with the effective placement
        assert reg.gauge_value(
            'kyverno_tpu_rule_placement_info', policy='dp-pol',
            rule='dp-rule', path='validate', placement='partial',
            reason='') == 1.0
        assert reg.gauge_value(
            'kyverno_tpu_rule_placement_info', policy='host-pol',
            rule='legacy-in', path='validate', placement='host',
            reason='unsupported_operator') == 1.0

    def test_policy_coupling_override(self, ledger):
        """A device-compilable rule sharing a policy with a host rule is
        placed host with reason=policy_coupling."""
        led, _reg = ledger
        coupled = {
            'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
            'metadata': {'name': 'coupled',
                         'annotations': dict(NO_AUTOGEN)},
            'spec': {'rules': [
                dict(DEVICE_POL['spec']['rules'][0]),
                dict(HOST_POL['spec']['rules'][0]),
            ]}}
        from kyverno_tpu.compiler.scan import BatchScanner
        BatchScanner([Policy(coupled)])
        rules = {(r['policy'], r['rule']): r
                 for r in led.report()['rules']}
        rec = rules[('coupled', 'check-app')]
        assert rec['placement'] == 'host'
        assert rec['reason'] == 'policy_coupling'

    def test_report_span_carries_ratio(self, ledger):
        _led, _reg = ledger
        from kyverno_tpu.observability import device as devtel
        mem = tracing.configure()
        devtel.configure(MetricsRegistry())
        try:
            scanner = mixed_scanner()
            scanner.scan(mixed_resources())
            spans = [s for s in mem.spans()
                     if s.name == 'kyverno/device/report'
                     and 'device_coverage_ratio' in s.attributes]
            assert spans, 'report span missing device_coverage_ratio'
            ratio = spans[-1].attributes['device_coverage_ratio']
            assert 0.0 < ratio < 1.0
        finally:
            devtel.disable()
            tracing.disable()

    def test_bit_identical_with_ledger_on_vs_off(self):
        """The ledger only observes: responses (statuses AND messages)
        are byte-identical with coverage enabled vs disabled."""
        resources = mixed_resources()

        def snapshot():
            out = mixed_scanner().scan(resources)
            return [[(resp.policy_response.policy_name, rr.name,
                      str(rr.status), rr.message)
                     for resp in row for rr in resp.policy_response.rules]
                    for row in out]

        coverage.disable()
        baseline = snapshot()
        coverage.configure(MetricsRegistry())
        try:
            with_ledger = snapshot()
        finally:
            coverage.disable()
        assert with_ledger == baseline


class TestPssDirectMessage:
    """A podSecurity FAIL is a host row (the host words it), booked
    under ``pss_direct_message`` where the check library is called
    directly and under ``unsynthesizable_message`` where the rule still
    needs the Validator (preconditions)."""

    GATED = {
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': 'pss-gated', 'annotations': dict(NO_AUTOGEN)},
        'spec': {'rules': [{
            'name': 'gated',
            'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
            'preconditions': {'all': [{
                'key': '{{request.object.metadata.name}}',
                'operator': 'NotEquals', 'value': 'skipme'}]},
            'validate': {'podSecurity': {'level': 'restricted',
                                         'version': 'latest'}}}]}}

    def test_reason_is_in_the_taxonomy(self):
        assert coverage.REASON_PSS_DIRECT == 'pss_direct_message'
        assert coverage.REASON_PSS_DIRECT in coverage.REASONS

    @pytest.mark.parametrize('how', ['rows', 'columns', 'fused'])
    def test_failing_pods_are_host_rows_under_the_new_reason(self, ledger,
                                                             how):
        import benchlib
        from kyverno_tpu.compiler.scan import BatchScanner
        led, reg = ledger
        scanner = BatchScanner(benchlib.load_policies(['pss']) +
                               [Policy(self.GATED)])
        # no securityContext at all: every Pod fails restricted, in the
        # pack's rule and in the gated one; baseline passes on the device
        pods = [pod(i) for i in range(
            4 if how == 'rows' else scanner.SMALL_BATCH + 8)]
        if how == 'fused':
            list(scanner.scan_report_results(pods))
        else:
            scanner.scan(pods)
        n = len(pods)
        by_reason = led.totals()['by_reason']['pss']
        assert by_reason == {'pss_direct_message': n,
                             'unsynthesizable_message': n}
        assert reg.counter_value(
            'kyverno_tpu_host_fallback_total', path='pss',
            reason='pss_direct_message') == n
        rules = {(r['policy'], r['rule']): r
                 for r in led.report()['rules']}
        direct = rules[('podsecurity-restricted', 'restricted')]
        assert (direct['host_rows'], direct['device_rows']) == (n, 0)
        assert direct['effective'] == 'partial'
        gated = rules[('pss-gated', 'gated')]
        assert (gated['host_rows'], gated['device_rows']) == (n, 0)
        passed = rules[('podsecurity-baseline', 'baseline')]
        assert (passed['host_rows'], passed['device_rows']) == (0, n)
        totals = led.totals()
        assert totals['host_rows'] == 2 * n
        assert totals['device_rows'] + totals['host_rows'] == \
            totals['total_rows'] == 3 * n
        assert 'reason="unknown"' not in reg.render()


class TestMutateFallbacks:
    def test_attributed_reasons(self, ledger):
        led, reg = ledger
        from kyverno_tpu.compiler.apply import BatchApplier
        applier = BatchApplier([Policy(MUTATE_REPLACE_POL),
                                Policy(MUTATE_FOREACH_POL)], processes=0)
        docs = [pod(0, app=False), pod(1),   # no labels → replace missing
                pod(2, names=('a', 'a'))]    # duplicate element names
        applier.apply(docs, parallel=False)
        assert reg.counter_value(
            'kyverno_tpu_host_fallback_total', path='mutate',
            reason='replace_path_missing') >= 1
        assert reg.counter_value(
            'kyverno_tpu_host_fallback_total', path='mutate',
            reason='duplicate_element_names') >= 1
        assert 'reason="unknown"' not in reg.render()
        rules = {(r['policy'], r['rule'], r['path']): r
                 for r in led.report()['rules']}
        rec = rules[('mutate-replace', 'replace-app', 'mutate')]
        assert rec['placement'] == 'device'   # compiled fast applier
        assert rec['effective'] == 'partial'  # observed escapes
        assert rec['host_rows'] >= 1


class TestEndpointAndCli:
    def test_debug_coverage_agrees_with_cli(self, ledger, tmp_path):
        import urllib.request
        import yaml
        from kyverno_tpu.observability.profiling import ProfilingServer
        scanner = mixed_scanner()
        scanner.scan(mixed_resources())
        server = ProfilingServer(port=0)
        port = server.start()
        try:
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/debug/coverage',
                    timeout=10) as resp:
                live = json.loads(resp.read().decode())
        finally:
            server.stop()
        assert live['enabled'] is True
        pack = tmp_path / 'pack.yaml'
        pack.write_text(yaml.safe_dump_all(
            [DEVICE_POL, DP_POL, HOST_POL]))
        import coverage_report
        cli = coverage_report.compile_report(
            coverage_report.load_policies([str(pack)]))
        cli_rules = {(r['policy'], r['rule'], r['path']):
                     (r['placement'], r['reason']) for r in cli['rules']}
        live_rules = {(r['policy'], r['rule'], r['path']):
                      (r['placement'], r['reason'])
                      for r in live['rules']}
        # compile-time placement must agree exactly, rule for rule
        assert cli_rules == live_rules
        # and the live view additionally carries runtime row counts
        dp = [r for r in live['rules'] if r['rule'] == 'dp-rule'][0]
        assert dp['effective'] == 'partial'

    def test_endpoint_reports_disabled(self):
        import urllib.request
        from kyverno_tpu.observability.profiling import ProfilingServer
        coverage.disable()
        server = ProfilingServer(port=0)
        port = server.start()
        try:
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/debug/coverage',
                    timeout=10) as resp:
                body = json.loads(resp.read().decode())
        finally:
            server.stop()
        assert body == {'enabled': False}


class TestNoopWhenUnconfigured:
    def test_mixed_scan_creates_nothing(self):
        """The acceptance no-op contract: an unconfigured process doing
        a mixed device/host scan creates zero coverage series, spans,
        or threads."""
        coverage.disable()
        tracing.disable()
        sentinel = MetricsRegistry()
        set_global_registry(sentinel)
        before = set(threading.enumerate())
        try:
            scanner = mixed_scanner()
            scanner.scan(mixed_resources())
            from kyverno_tpu.compiler.apply import BatchApplier
            applier = BatchApplier([Policy(MUTATE_REPLACE_POL)],
                                   processes=0)
            applier.apply([pod(0, app=False)], parallel=False)
        finally:
            set_global_registry(None)
        assert coverage.ledger() is None
        assert coverage.last_ratio() is None
        assert coverage.scan_tally() is None
        text = sentinel.render()
        assert 'kyverno_tpu_host_fallback_total' not in text
        assert 'kyverno_tpu_device_coverage_ratio' not in text
        assert 'kyverno_tpu_rule_placement_info' not in text
        assert tracing.memory_exporter() is None
        # no coverage-owned thread survives (the ledger never spawns
        # any; only the scan pipeline's own executors may appear)
        after = {t for t in threading.enumerate() if t not in before}
        assert not any('coverage' in t.name for t in after)


class TestRenderHelp:
    def test_help_lines_from_catalog(self):
        reg = MetricsRegistry()
        reg.inc('kyverno_tpu_host_fallback_total', path='validate',
                reason='status_host')
        reg.set_gauge('kyverno_tpu_device_coverage_ratio', 0.5)
        text = reg.render()
        from kyverno_tpu.observability.catalog import METRICS
        assert ('# HELP kyverno_tpu_host_fallback_total '
                + METRICS['kyverno_tpu_host_fallback_total'].help) in text
        # HELP precedes TYPE for the same metric (prometheus convention)
        lines = text.splitlines()
        h = lines.index('# HELP kyverno_tpu_device_coverage_ratio '
                        + METRICS['kyverno_tpu_device_coverage_ratio'].help)
        assert lines[h + 1] == \
            '# TYPE kyverno_tpu_device_coverage_ratio gauge'
