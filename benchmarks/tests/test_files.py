"""BENCHMARK.json against the contract's limits and against the data files
that its names stand for."""

import json
import os
import re
import subprocess
import sys

import pytest

import benchlib

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}


@pytest.fixture(scope='module')
def bench():
    with open(os.path.join(benchlib.ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def metrics(bench):
    return bench['end_to_end'] + bench['per_layer']


def bad_names(bench):
    names = [m['name'] for m in metrics(bench)] + \
        [c['name'] for c in bench['configs']] + \
        [k for c in bench['configs'] for k in c['reduced']] + \
        [x for w in bench['workloads']
         for x in (w['name'], w['config'], w['traffic'])]
    return [n for n in names if not NAME.match(n)] + \
        [m['unit'] for m in metrics(bench) if not UNIT.match(m['unit'])]


def test_every_name_and_unit_is_made_of_the_allowed_characters(bench):
    assert bad_names(bench) == []
    spoiled = json.loads(json.dumps(bench))
    spoiled['end_to_end'][0]['unit'] = 'resources per s'
    spoiled['workloads'][0]['name'] = 'bg,scan'
    spoiled['per_layer'][0]['unit'] = 'µs'
    assert bad_names(spoiled) == ['bg,scan', 'resources per s', 'µs']


def test_the_keys_and_limits_of_the_contract(bench):
    assert set(bench) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= bench['run_seconds'] <= 51
    for c in bench['configs']:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert c['file'].startswith(tuple(p + '/' for p in bench['paths']))
        assert os.path.isfile(os.path.join(benchlib.ROOT, c['file']))
    for w in bench['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert w['chips'] in (1, 4)
    for m in bench['end_to_end']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert 0.01 <= m['bound'] <= 0.25
        assert m['source'] in ('host_clock', 'device_trace')
    for m in bench['per_layer']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
        assert m['source'] in SOURCES
    for m in metrics(bench):
        assert m['better'] in ('lower', 'higher')
    for text in [w['why'] for w in bench['workloads']] + \
            [c[k] for c in bench['configs'] for k in ('why', 'source')] + \
            [m['layer'] for m in bench['per_layer']] + bench['command']:
        assert 1 <= len(text) <= 200 and '\n' not in text and '\t' not in text
    assert len({m['name'] for m in metrics(bench)}) == len(metrics(bench))
    assert len({(w['config'], w['traffic']) for w in bench['workloads']}) \
        == len(bench['workloads'])
    assert 'setup_s' in {m['name'] for m in bench['end_to_end']}
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_name_has_its_file_and_the_config_file_says_the_same(bench):
    for c in bench['configs']:
        data = benchlib.load_data('configs', c['name'])
        assert c['file'] == f'benchmarks/configs/{c["name"]}.json'
        assert data['reduced'] == c['reduced']
        benchlib.load_module('drivers', data['entry'])
        for pack in data['packs']:
            assert os.path.isfile(benchlib.data_path('packs', pack, '.yaml'))
    for w in bench['workloads']:
        benchlib.load_data('traffic', w['traffic'])
        assert w['config'] in {c['name'] for c in bench['configs']}
    for m in bench['per_layer']:
        layer = benchlib.load_data('layers', m['name'])
        assert hasattr(benchlib.load_module('readers', layer['reader']),
                       'read')


def layer_file(name: str) -> dict:
    return benchlib.load_data('layers', name)


def test_what_a_cells_traffic_yields_is_what_it_reports(bench):
    end_to_end = {m['name']: m for m in bench['end_to_end']}
    for w in bench['workloads']:
        yields = set(benchlib.load_data('traffic', w['traffic'])['yields'])
        assert yields == {n for n, m in end_to_end.items() if n != 'setup_s'
                          and w['name'] in m.get('workloads',
                                                 [w['name']])}


def placed_right(bench, name: str, cell: str) -> bool:
    """The rule that places a per-layer metric, which only its
    ``workloads`` in BENCHMARK.json does: a cell it lists is a cell of the
    benchmark, with a driver, whose traffic yields the end-to-end metric the
    metric moves.  A cell joins a metric that other cells read by adding its
    name there; no layer file names a cell or a driver."""
    metric = next(m for m in bench['per_layer'] if m['name'] == name)
    work = next((w for w in bench['workloads'] if w['name'] == cell), None)
    if work is None:
        return False
    config = benchlib.load_data('configs', work['config'])
    yields = benchlib.load_data('traffic', work['traffic'])['yields']
    return metric['moves'] in yields and hasattr(
        benchlib.load_module('drivers', config['entry']), 'Driver')


with open(os.path.join(benchlib.ROOT, 'BENCHMARK.json')) as _f:
    PLACED = [(m['name'], cell) for m in json.load(_f)['per_layer']
              for cell in m['workloads']]


@pytest.mark.parametrize('name, cell', PLACED)
def test_a_layer_metric_is_read_in_a_cell_that_yields_what_it_moves(
        bench, name, cell):
    assert placed_right(bench, name, cell)


def test_a_metric_listed_in_a_cell_that_yields_something_else_is_misplaced(
        bench):
    yields = {w['name']: benchlib.load_data('traffic', w['traffic'])['yields']
              for w in bench['workloads']}
    name, cell = next((m['name'], cell) for m in bench['per_layer']
                      for cell in yields if m['moves'] not in yields[cell])
    metric = next(m for m in bench['per_layer'] if m['name'] == name)
    spoiled = dict(bench, per_layer=[
        dict(metric, workloads=metric['workloads'] + [cell])])
    assert not placed_right(spoiled, name, cell)
    assert not placed_right(bench, name, 'no_such_cell')


def twins(bench, layer=layer_file) -> list:
    """Pairs of metrics that move the same end-to-end metric through the
    same reader with the same arguments: one metric read in more cells is
    one metric whose ``workloads`` lists more cells."""
    seen, found = {}, []
    for m in bench['per_layer']:
        spec = layer(m['name'])
        key = (m['moves'], spec['reader'],
               json.dumps(spec.get('args', {}), sort_keys=True))
        if key in seen:
            found.append((seen[key], m['name']))
        else:
            seen[key] = m['name']
    return found


# twins kept apart while tests/test_benchmark_checks.py names their files
KEPT_TWINS = [('pss_mask_share', 'pss_mask_share.ctx'),
              ('pss_checks_per_cell', 'pss_checks_per_cell.ctx')]


def test_no_two_metrics_that_move_one_number_read_it_alike(bench):
    assert twins(bench) == KEPT_TWINS
    first = bench['per_layer'][0]['name']
    spoiled = dict(bench, per_layer=bench['per_layer'] + [
        dict(bench['per_layer'][0], name='a_copy')])
    assert twins(spoiled, lambda n: layer_file(first if n == 'a_copy'
                                               else n)) == \
        KEPT_TWINS + [(first, 'a_copy')]


@pytest.mark.parametrize('kind, spoil', [
    ('configs', lambda b: b['workloads'][0].update(config='no-such-config')),
    ('traffic', lambda b: b['workloads'][0].update(traffic='no_such_mix')),
])
def test_a_missing_file_is_an_error_that_names_it(bench, tmp_path, kind,
                                                  spoil):
    """A checkout whose BENCHMARK.json names a file that is not there."""
    import shutil
    shutil.copytree(benchlib.BENCH_DIR, tmp_path / 'benchmarks',
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    spoiled = json.loads(json.dumps(bench))
    spoil(spoiled)
    (tmp_path / 'BENCHMARK.json').write_text(json.dumps(spoiled))
    done = subprocess.run(
        [sys.executable, str(tmp_path / 'benchmarks' / 'run.py'),
         '--workload', spoiled['workloads'][0]['name'], '--seed', '1',
         '--seconds', '1', '--trace', '0'],
        capture_output=True, text=True, timeout=60)
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert f'benchmarks/{kind}/no' in done.stderr


def test_a_missing_layer_file_is_named_when_the_traced_run_reads_it(bench):
    import run
    spoiled = json.loads(json.dumps(bench))
    spoiled['per_layer'][0]['name'] = 'no_such_metric'
    with pytest.raises(benchlib.MissingFile,
                       match='benchmarks/layers/no_such_metric.json'):
        run.layer_metrics(spoiled, spoiled['workloads'][0], {}, {}, None,
                          'TPU v5 lite')
