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


def test_a_layer_metric_is_reported_where_its_entry_yields_what_it_moves(
        bench):
    """The rule that places a per-layer metric: every cell of its entry
    point that reports the end-to-end metric it moves, and no other."""
    end_to_end = {m['name']: m for m in bench['end_to_end']}
    cells = {}
    for w in bench['workloads']:
        entry = benchlib.load_data('configs', w['config'])['entry']
        yields = set(benchlib.load_data('traffic', w['traffic'])['yields'])
        cells[w['name']] = (entry, yields)
        # what the traffic yields is what BENCHMARK.json says the cell reports
        assert yields == {n for n, m in end_to_end.items() if n != 'setup_s'
                          and w['name'] in m.get('workloads',
                                                 [w['name']])}
    for m in bench['per_layer']:
        entry = benchlib.load_data('layers', m['name'])['entry']
        want = sorted(name for name, (e, y) in cells.items()
                      if e == entry and m['moves'] in y)
        assert sorted(m['workloads']) == want, m['name']


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
