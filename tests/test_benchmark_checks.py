"""The benchmark's own fast checks, run by tier-1.

``benchmarks/tests/`` is outside ``pytest tests/``, so nothing held
``BENCHMARK.json``, the layer files, the open-loop schedule, the bytes table
or the trace reduction until a chip run failed.  This file takes the tests
and fixtures of the six files that need no subprocess run of a cell (about a
second together) into its own namespace, as they are; ``tests/conftest.py``
has put ``benchmarks/`` on ``sys.path``, which is all their own
``conftest.py`` does.  ``test_rehearse.py`` (two minutes of CPU rehearsals)
stays outside: ``python -m pytest benchmarks/tests -q`` runs everything,
and rewrites the asserts of a failing check, which this file's import does
not.
"""

import importlib.util

import benchlib

FILES = ['test_bytes', 'test_bytes_mutate', 'test_files',
         'test_layer_sources', 'test_schedule', 'test_trace_reduce']


def _collect(name: str) -> dict:
    path = benchlib.data_path('tests', name, '.py')
    spec = importlib.util.spec_from_file_location(f'bench_tests_{name}', path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {key: value for key, value in vars(module).items()
            if key.startswith('test_')
            or hasattr(value, '_fixture_function_marker')}  # a fixture


for _name in FILES:
    _found = _collect(_name)
    _twice = sorted(set(_found) & set(globals()))
    assert not _twice, f'{_name}.py defines {_twice} a second time'
    globals().update(_found)


# what the fast files above do not hold: a layer file that reads the coverage
# block (the reports drivers snapshot every number of ``coverage.totals()``
# under 'coverage') names a number the ledger has, so that a renamed counter
# fails here and does not read as "nothing to read" on the chip

import glob  # noqa: E402
import os  # noqa: E402

import pytest  # noqa: E402


def _coverage_keys(name: str) -> list:
    args = benchlib.load_data('layers', name).get('args', {})
    return [value[len('coverage.'):] for value in args.values()
            if isinstance(value, str) and value.startswith('coverage.')]


COVERAGE_LAYERS = [
    name for name in sorted(
        os.path.basename(path)[:-len('.json')] for path in glob.glob(
            os.path.join(benchlib.BENCH_DIR, 'layers', '*.json')))
    if _coverage_keys(name)]


@pytest.mark.parametrize('name', COVERAGE_LAYERS)
def test_a_coverage_layer_names_a_number_of_the_ledger(name):
    from kyverno_tpu.observability import coverage
    from kyverno_tpu.observability.metrics import MetricsRegistry
    totals = coverage.CoverageLedger(MetricsRegistry()).totals()
    for key in _coverage_keys(name):
        assert isinstance(totals.get(key), (int, float)), key


def test_the_pss_mask_layers_are_among_them():
    assert {'pss_mask_share', 'pss_mask_share.ctx', 'pss_checks_per_cell',
            'pss_checks_per_cell.ctx', 'host_rows_share'} <= \
        set(COVERAGE_LAYERS)
