"""Tier-1 gate: the ktpu-lint analyzer runs clean over the tree.

``python scripts/analyze.py --strict`` must exit 0 — any new
trace-safety / retrace / taxonomy / knob / catalog violation fails CI
here, before a TPU ever sees the code.  The committed baseline must be
minimal (no stale entries) and every entry justified."""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

BASELINE = os.path.join(REPO_ROOT, '.ktpu-baseline.json')


def _run_analyzer(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'scripts',
                                      'analyze.py'), *args],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'})


def test_tree_is_clean_in_strict_mode():
    # the budget is the analyzer's own CPU time: under the six test
    # workers its wall clock also counts what the others are doing
    t0 = os.times()
    proc = _run_analyzer('--strict', '--json')
    t1 = os.times()
    elapsed = (t1.children_user - t0.children_user) + \
        (t1.children_system - t0.children_system)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report['counts']['active'] == 0, report['active']
    assert report['counts']['stale_baseline'] == 0, \
        report['stale_baseline']
    assert not report['errors'], report['errors']
    # CPU-only CI budget: the whole tree must analyze fast
    assert elapsed < 10.0, \
        f'analyzer took {elapsed:.1f}s of CPU (budget 10s)'


def test_baseline_is_minimal_and_justified():
    """Every committed baseline entry still matches a real finding
    (in-process re-run, so a stale entry names itself) and carries a
    non-placeholder justification."""
    from kyverno_tpu.analysis import Analyzer
    with open(BASELINE, encoding='utf-8') as f:
        entries = json.load(f)['entries']
    for e in entries:
        reason = str(e.get('reason', '')).strip()
        assert reason and not reason.startswith('TODO'), \
            f'unjustified baseline entry: {e}'
    analyzer = Analyzer(['kyverno_tpu', 'scripts'], REPO_ROOT,
                        baseline_path=BASELINE)
    report = analyzer.run()
    assert not report.stale_baseline, report.stale_baseline
    assert not report.active, [f.render() for f in report.active]
    # the baseline is exercised, not vestigial: each entry matched
    assert len(report.baselined) >= len(entries)


def test_analyzer_catches_planted_violation(tmp_path):
    """End-to-end through the driver: a rogue file with a host sync in
    a jit function must flip --strict to nonzero."""
    rogue = os.path.join(REPO_ROOT, 'kyverno_tpu', '_rogue_lint.py')
    with open(rogue, 'w') as f:
        f.write('import jax\n\n'
                'def _f(t):\n'
                '    return t.item()\n\n'
                '_jf = jax.jit(_f)\n')
    try:
        proc = _run_analyzer('--strict')
        assert proc.returncode != 0
        assert 'KTPU101' in proc.stdout
    finally:
        os.unlink(rogue)


def test_graph_dump_debug_mode():
    """--graph-dump prints resolved callees + taint facts for a named
    function, and --json emits a machine-readable dump."""
    proc = _run_analyzer('--graph-dump', 'ChunkPipeline._worker')
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert 'ChunkPipeline._worker' in proc.stdout
    assert 'callees:' in proc.stdout
    assert 'install_capture' in proc.stdout  # resolved cross-module
    proc = _run_analyzer('--graph-dump', 'ChunkPipeline._worker',
                         '--json')
    assert proc.returncode == 0
    dumps = json.loads(proc.stdout)
    assert dumps and dumps[0]['class'] == 'ChunkPipeline'
    assert any(c['qualname'].endswith('install_capture')
               for c in dumps[0]['callees'])
    # unknown names are a distinct exit code, not a crash
    proc = _run_analyzer('--graph-dump', 'no_such_function_xyz')
    assert proc.returncode == 2


def test_knob_table_matches_registry():
    """--knob-table output covers every registered knob, and the README
    carries the generated table (docs cannot drift from the registry)."""
    from kyverno_tpu.analysis.knobs import KNOBS
    proc = _run_analyzer('--knob-table')
    assert proc.returncode == 0
    readme = open(os.path.join(REPO_ROOT, 'README.md'),
                  encoding='utf-8').read()
    for name in KNOBS:
        assert f'`{name}`' in proc.stdout, name
        assert name in readme, f'{name} missing from README knob table'


def test_rule_ids_documented_in_readme():
    from kyverno_tpu.analysis import RULES
    readme = open(os.path.join(REPO_ROOT, 'README.md'),
                  encoding='utf-8').read()
    for rid in RULES:
        assert rid in readme, f'{rid} missing from README rule table'
