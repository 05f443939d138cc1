"""``--rehearse`` of every cell: the whole run on the CPU at a small size,
ending in a result line with the contract's keys and leaving no process."""

import json
import os
import subprocess
import sys

import pytest

import benchlib

with open(os.path.join(benchlib.ROOT, 'BENCHMARK.json')) as _f:
    BENCH = json.load(_f)
# the cells that are specified and not yet proven on the chip are rehearsed
# too, so that the loops and data files they need stay in working order
with open(os.path.join(os.path.dirname(__file__),
                       'proposed_cells.json')) as _f:
    _PROPOSED = json.load(_f)
for _m in BENCH['end_to_end'] + BENCH['per_layer']:
    if 'workloads' in _m:
        _m['workloads'] = _m['workloads'] + [
            cell for cell, names in _PROPOSED['joins'].items()
            if _m['name'] in names]
for _key in ('workloads', 'end_to_end', 'per_layer'):
    BENCH[_key] = BENCH[_key] + _PROPOSED[_key]
CELLS = [w['name'] for w in BENCH['workloads']]


@pytest.fixture(scope='module')
def checkout(tmp_path_factory):
    """The benchmark's files beside a BENCHMARK.json that also has the
    proposed cells; the program is found through PYTHONPATH."""
    import shutil
    root = tmp_path_factory.mktemp('checkout')
    shutil.copytree(benchlib.BENCH_DIR, root / 'benchmarks',
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    (root / 'BENCHMARK.json').write_text(json.dumps(BENCH))
    return root


def rehearse(checkout, cell: str, trace: int):
    """Run the cell as the leader of a new session, and probe its process
    group the moment it has been waited for."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=benchlib.ROOT)
    env.pop('BENCH_RUN', None)
    proc = subprocess.Popen(
        [sys.executable, str(checkout / 'benchmarks' / 'run.py'),
         '--workload', cell, '--seed', str(2**31 + 12345), '--seconds', '4',
         '--trace', str(trace), '--rehearse'],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=checkout, start_new_session=True)
    out, err = proc.communicate(timeout=300)
    try:
        os.killpg(proc.pid, 0)
        left = True
    except ProcessLookupError:
        left = False
    return proc.returncode, out, err, left


@pytest.mark.parametrize('cell', CELLS)
@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_ends_in_a_result_line_and_leaves_no_process(
        checkout, cell, trace):
    code, out, err, left = rehearse(checkout, cell, trace)
    assert code == 0, err[-2000:]
    assert not left, 'a process of the run was alive when it had been waited for'
    result = json.loads(out.strip().splitlines()[-1])
    assert {'correct', 'attempted', 'failed', 'metrics', 'device'} <= \
        set(result)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    # a rehearsal says where it ran and is never a measurement
    assert result['device']['platform'] == 'cpu'
    kind = 'per_layer' if trace else 'end_to_end'
    allowed = {m['name'] for m in BENCH[kind]
               if cell in m.get('workloads', [cell])}
    assert set(result['metrics']) <= allowed and result['metrics']
    if not trace:
        assert set(result['metrics']) == allowed
    for m in result['metrics'].values():
        assert isinstance(m['value'], (int, float)) and m['unit']


def test_without_a_tpu_and_without_rehearse_there_is_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, os.path.join(benchlib.BENCH_DIR, 'run.py'),
         '--workload', CELLS[0], '--seed', '1', '--seconds', '1',
         '--trace', '0'], capture_output=True, text=True, env=env,
        cwd=benchlib.ROOT, timeout=120)
    assert done.returncode != 0
    assert not any(line.startswith('{') for line in done.stdout.splitlines())
    assert 'not a TPU' in done.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    import shutil
    shutil.copytree(benchlib.BENCH_DIR, tmp_path / 'benchmarks',
                    ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(os.path.join(benchlib.ROOT, 'BENCHMARK.json'), tmp_path)
    done = subprocess.run(
        [sys.executable, 'benchmarks/run.py', '--workload', CELLS[0],
         '--seed', '1', '--seconds', '1', '--trace', '0', '--rehearse'],
        capture_output=True, text=True, cwd=tmp_path, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert done.returncode != 0
    assert not any(line.startswith('{') for line in done.stdout.splitlines())
