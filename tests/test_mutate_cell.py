"""The cell ``admission_mutate_open`` rehearsed in this process: the
benchmark's driver at the rehearsal sizes on the CPU, and what its check
says when the plain reference or the kernel is spoiled.

``benchmarks/tests/test_rehearse.py`` runs the whole command in a
subprocess (outside tier-1); this file holds the comparison that decides
``correct`` to its word: one wrong policy of the reference, or one dead
element slot of the kernel, and the run is not correct.
"""

import json
import os

import pytest

import benchlib


@pytest.fixture(scope='module')
def driver():
    """One set-up, as ``benchmarks/run.py --rehearse`` makes it."""
    with open(os.path.join(benchlib.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    [cell] = [w for w in bench['workloads']
              if w['name'] == 'admission_mutate_open']
    config = benchlib.load_data('configs', cell['config'])
    traffic = benchlib.load_data('traffic', cell['traffic'])
    config = benchlib.overlay(config, config['rehearse'])
    traffic = benchlib.overlay(traffic, traffic['rehearse'])
    patch = pytest.MonkeyPatch()
    for key, value in config['env'].items():
        patch.setenv(key, str(value))
    registry = benchlib.program_telemetry()
    module = benchlib.load_module('drivers', config['entry'])
    made = module.Driver(config=config, traffic=traffic, seed=2**31 + 77,
                         seconds=3.0, platform='cpu', registry=registry)
    try:
        made.setup()
        yield made
    finally:
        from kyverno_tpu.observability import coverage
        from kyverno_tpu.observability import device as devtel
        from kyverno_tpu.observability import executables as exectel
        from kyverno_tpu.observability.metrics import set_global_registry
        made.close()
        devtel.disable()
        coverage.disable()
        exectel.disable()
        set_global_registry(None)
        patch.undo()


def run(driver) -> list:
    driver.failed = 0
    driver.handlers._get_batcher().reset_stats()
    driver._stages_before = driver._stages()
    driver.measure()
    driver.next_index += driver.attempted
    return driver.check()


def test_the_rehearsal_is_correct_and_reads_every_counter(driver):
    assert run(driver) == [] and driver.failed == 0
    assert driver.attempted == 30
    counters = driver.counters()
    stats = counters['batcher']
    assert stats['mutate_device_path_requests'] == 30
    assert stats['mutate_rows'] == 30
    assert stats['mutate_host_loop_requests'] == 0
    assert counters['requests'] == {'offered': 30, 'mutate_offered': 30}
    assert len(counters['samples']['mutate_ms']) == 30
    assert len(counters['samples']['validate_ms']) == 30
    assert counters['mutate_dispatch']['bytes'] == 43968 + 4480
    assert counters['mutate_handler']['count'] == 30
    for stage in ('mutate_match', 'mutate_encode', 'mutate_eval',
                  'mutate_decode'):
        assert counters['stages'][stage]['count'] == \
            stats['mutate_dispatches']


def test_one_spoiled_policy_of_the_reference_is_not_correct(driver,
                                                            monkeypatch):
    reference = benchlib.load_module('reference', 'mutate_defaults')
    monkeypatch.setitem(reference.CHAIN, 'add-nodeselector',
                        lambda pod: None)
    problems = run(driver)
    assert driver.failed == driver.attempted
    assert any('the patched document is not the reference\'s' in p
               for p in problems)


def test_one_dead_element_slot_of_the_kernel_is_not_correct(driver):
    """Slot 1 of ``always-pull-images`` never edits: every Pod with a
    second container that lacked ``imagePullPolicy: Always`` keeps it."""
    from kyverno_tpu.mutate.plan import split_element_path
    kernel = driver.mutate_scanner._kernel
    sites = [site for prog in driver.mutate_scanner.program.programs
             for site in prog.sites]
    [dead] = [k for k, site in enumerate(sites)
              if site.path[-1] == 'imagePullPolicy'
              and split_element_path(site.path)[1] == 1]
    bit = kernel._bit_w[dead]
    kernel._bit_w[dead] = 0
    kernel._jitted = None       # the constants are baked in at the trace
    try:
        problems = run(driver)
    finally:
        kernel._bit_w[dead] = bit
        kernel._jitted = None
    assert 0 < driver.failed < driver.attempted
    assert any('the patched document is not the reference\'s' in p
               for p in problems)
