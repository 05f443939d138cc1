"""Executable lifecycle ledger (ISSUE 14 tentpole a).

Every compiled program gets a lifecycle record: acquisition source
(fresh_compile | aot_load | persistent_xla), build cost, cumulative
dispatch/device-time accounting, eviction marking.  Pins the ledger
unit behavior, the metric gauges, the zero-duration lifecycle spans,
the scan-path bit-identity with the ledger off, and the second-process
AOT acceptance: a fresh process against a warm store registers its
executables as ``aot_load`` with zero fresh compiles.  CPU-only,
tier-1.
"""

import json
import os
import subprocess
import sys

from kyverno_tpu.observability import executables, tracing
from kyverno_tpu.observability.executables import (EXEC_COUNT,
                                                   EXEC_DEVICE_SECONDS,
                                                   EXEC_DISPATCHES,
                                                   ExecutableLedger)
from kyverno_tpu.observability.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


import pytest


@pytest.fixture(autouse=True)
def _clean_modules():
    yield
    executables.disable()
    tracing.disable()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class TestLedger:
    def test_build_dispatch_evict_roundtrip(self):
        reg = MetricsRegistry()
        led = ExecutableLedger(8, registry=reg, now=FakeClock())
        led.record_build('k1', fingerprint='f' * 20, capacity=64,
                         source='fresh_compile', build_s=2.5)
        led.record_dispatch('k1', 0.25)
        led.record_dispatch('k1', 0.25)
        rec = led.records()[0]
        assert rec.dispatches == 2
        assert abs(rec.device_s - 0.5) < 1e-9
        assert reg.gauge_value(EXEC_COUNT, source='fresh_compile') == 1.0
        assert reg.counter_value(EXEC_DISPATCHES,
                                 source='fresh_compile') == 2.0
        assert abs(reg.counter_value(EXEC_DEVICE_SECONDS,
                                     source='fresh_compile') - 0.5) < 1e-9
        led.record_eviction('k1', 'execute_failed')
        rec = led.records()[0]
        assert rec.evicted and rec.evict_reason == 'execute_failed'
        # evicted records leave the live gauge but stay in the table
        assert reg.gauge_value(EXEC_COUNT, source='fresh_compile') == 0.0
        assert led.report()['executables'][0]['evicted'] is True

    def test_unknown_key_dispatch_and_eviction_are_noops(self):
        led = ExecutableLedger(8, registry=None)
        led.record_dispatch('nope', 1.0)
        led.record_eviction('nope', 'whatever')
        assert led.records() == []

    def test_lru_bound(self):
        led = ExecutableLedger(2, registry=None)
        for k in ('a', 'b', 'c'):
            led.record_build(k, source='fresh_compile')
        keys = [r.key for r in led.records()]
        assert keys == ['b', 'c']
        # a dispatch refreshes recency: 'b' survives the next insert
        led.record_dispatch('b', 0.1)
        led.record_build('d', source='fresh_compile')
        assert [r.key for r in led.records()] == ['b', 'd']

    def test_reacquisition_keeps_dispatch_history(self):
        led = ExecutableLedger(8, registry=None)
        led.record_build('k', source='fresh_compile', build_s=3.0)
        led.record_dispatch('k', 0.5)
        led.record_build('k', source='aot_load', build_s=0.2)
        rec = led.records()[0]
        assert rec.source == 'aot_load'
        assert rec.build_s == 0.2
        assert rec.dispatches == 1  # cumulative history survives

    def test_census_and_report(self):
        led = ExecutableLedger(8, registry=None)
        led.record_build('k1', source='fresh_compile', build_s=2.0)
        led.record_build('k2', source='aot_load', build_s=0.5)
        led.record_dispatch('k1', 0.125)
        led.record_eviction('k2', 'feature_mismatch')
        c = led.census()
        assert c['live'] == 1
        assert c['by_source'] == {'fresh_compile': 1}
        assert c['dispatches'] == 1
        # evicted records drop out of the live build_s sum
        assert abs(c['build_s'] - 2.0) < 1e-9
        rep = led.report()
        assert rep['enabled'] is True and rep['capacity'] == 8
        assert len(rep['executables']) == 2
        table = led.render_table()
        assert 'fresh_compile' in table
        assert 'evicted:feature_mismatch' in table

    def test_cost_analysis_shapes(self):
        class Compiled:
            def cost_analysis(self):
                return [{'flops': 12.0, 'bytes accessed': 34.0}]

        class Broken:
            def cost_analysis(self):
                raise RuntimeError('no backend')

        assert executables.cost_analysis(Compiled()) == {
            'flops': 12.0, 'bytes_accessed': 34.0}
        assert executables.cost_analysis(Broken()) == {}

    def test_lifecycle_events_ride_the_tracer(self):
        exporter = tracing.configure()
        led = ExecutableLedger(8, registry=None)
        led.record_build('k1', fingerprint='abc', capacity=64,
                         source='aot_load', build_s=0.7)
        led.record_eviction('k1', 'execute_failed')
        names = [s.name for s in exporter.spans()]
        assert names == ['kyverno/executable/build',
                         'kyverno/executable/evict']
        evict = exporter.spans()[-1]
        assert evict.attributes['evict_reason'] == 'execute_failed'
        assert evict.attributes['source'] == 'aot_load'
        # zero-duration: the span ends at start (lifecycle event, not
        # a timed region)
        assert evict.end_ns >= evict.start_ns


class TestModuleState:
    def test_noop_until_configured(self):
        assert not executables.enabled()
        executables.record_build('k', source='fresh_compile')
        executables.record_dispatch('k', 1.0)
        executables.record_eviction('k', 'x')
        assert executables.census() == {}

    def test_env_zero_disables(self, monkeypatch):
        monkeypatch.setenv('KTPU_EXEC_LEDGER_N', '0')
        assert executables.configure() is None
        assert not executables.enabled()

    def test_configure_roundtrip(self):
        led = executables.configure(registry=MetricsRegistry(),
                                    ledger_n=4)
        assert executables.enabled() and executables.ledger() is led
        executables.record_build('k', source='persistent_xla')
        assert executables.census()['live'] == 1
        executables.disable()
        assert executables.census() == {}


# -- second-process AOT acceptance -------------------------------------------
#
# A fresh process against a warm AOT store must register every
# executable as source=aot_load with ZERO fresh compiles — the ledger
# is how a cache regression becomes visible.  Single canonical
# capacity (row counts 1 and 63 both pad to the small capacity 64) so
# the probe pays one compile, and the census stays at two live
# executables or fewer.

_PROBE_SCRIPT = r'''
import json
from kyverno_tpu.api.policy import Policy
from kyverno_tpu.observability import executables
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


executables.configure(registry=MetricsRegistry(), ledger_n=16)
from kyverno_tpu.compiler.scan import BatchScanner
scanner = BatchScanner([Policy(POLICY)])
rows = {}
for n in (1, 63):
    status, detail, match = scanner.scan_statuses(
        [pod(i) for i in range(n)])
    rows[str(n)] = status.tolist()
from kyverno_tpu.compiler import aot
aot.flush_stores()
print(json.dumps({'census': executables.census(), 'rows': rows}))
'''


def _run_probe(cache_dir, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')}
    env.update({
        'JAX_PLATFORMS': 'cpu',
        'PYTHONPATH': REPO,
        'KTPU_SCAN_CHUNK': '256',
        'KTPU_SMALL_BATCH': '64',
        'KTPU_ENCODE_PROCS': '0',
        'KTPU_AOT': '1',
        'KTPU_AOT_CACHE_DIR': os.path.join(str(cache_dir), 'aot'),
        'JAX_COMPILATION_CACHE_DIR': os.path.join(str(cache_dir), 'xla'),
    })
    out = subprocess.run([sys.executable, '-c', _PROBE_SCRIPT],
                         env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_second_process_census_is_all_aot_load(tmp_path):
    """ISSUE 14 acceptance: the ledger of a second AOT-warm process
    shows source=aot_load with zero fresh compiles, bit-identical
    statuses, and a census of at most two live executables."""
    first = _run_probe(tmp_path)
    assert first['census']['live'] >= 1, first
    assert first['census']['live'] <= 2, first
    assert set(first['census']['by_source']) == {'fresh_compile'}, first
    second = _run_probe(tmp_path)
    assert second['census']['by_source'].get('fresh_compile', 0) == 0, \
        second
    assert second['census']['by_source'].get('aot_load', 0) >= 1, second
    assert second['census']['live'] <= 2, second
    assert second['census']['dispatches'] >= 2, second  # both scans
    assert second['rows'] == first['rows']
