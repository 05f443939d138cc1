"""chip_smoke.py off the chip: it refuses to run, and its traffic is what
its checks assume.  The phases themselves only mean something on the chip
(``python chip_smoke.py`` through the chip tool); nothing here runs them.
The cluster, the requests and ``descendants`` are the benchmark's
(``benchmarks/generators/``, ``benchmarks/benchlib.py``), which the smoke
and the cells both run."""

import json
import os
import subprocess
import sys

import benchlib
import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
mixed_cluster = chip_smoke.mixed_cluster
admission_reviews = chip_smoke.admission_reviews


def test_no_accelerator_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run([sys.executable, 'chip_smoke.py'], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "is 'cpu'" in out.stderr
    assert not any(line.startswith('{') for line in
                   out.stdout.splitlines())


def test_cluster_is_seeded_and_mixed():
    a = mixed_cluster.generate(0, 400)
    assert a == mixed_cluster.generate(0, 400)
    assert a != mixed_cluster.generate(1, 400)
    kinds = {r['kind'] for r in a}
    assert kinds == {'Pod', 'Deployment'}
    names = {(r['kind'], r['metadata']['namespace'], r['metadata']['name'])
             for r in a}
    assert len(names) == len(a)
    deploy = next(r for r in a if r['kind'] == 'Deployment')
    assert deploy['spec']['template']['spec']['containers']


def test_admission_traffic_has_both_answers_and_both_verbs():
    """Every enforce policy of the pack admits the compliant Pod, and
    the host engine refuses at least one of the cluster's own."""
    from kyverno_tpu.engine.api import PolicyContext
    from kyverno_tpu.engine.engine import Engine
    engine = Engine()
    policies = benchlib.load_policies(chip_smoke.PACKS)

    def failures(doc):
        return [r.name for p in policies
                for r in engine.validate(PolicyContext(
                    p, new_resource=doc)).policy_response.rules
                if str(r.status) == 'fail']

    assert failures(admission_reviews.compliant_pod(5)) == []
    cluster = mixed_cluster.generate(0, 40)
    assert any(failures(r) for r in cluster if r['kind'] == 'Pod')
    bodies = admission_reviews.generate(0, cluster, 8)
    assert bodies == admission_reviews.generate(0, cluster, 8)
    requests = [json.loads(body)['request'] for body in bodies]
    assert [r['name'].startswith('ok-') for r in requests] == \
        [i % 3 == 2 for i in range(8)]
    assert {r['operation'] for r in requests} == {'CREATE', 'UPDATE'}
    assert all('oldObject' in r for r in requests
               if r['operation'] == 'UPDATE')
    assert len({r['userInfo']['username'] for r in requests}) > 1


def test_descendants_lists_live_children_only():
    # an earlier test of this worker may have left a fork server up, which
    # may exit at any moment: only the child started here is asked about
    child = subprocess.Popen(['sleep', '60'])
    try:
        assert (child.pid, 'sleep 60') in benchlib.descendants()
    finally:
        child.kill()
        child.wait()
    assert child.pid not in [pid for pid, _cmd in benchlib.descendants()]


SCAN_WITH_WORKERS = '''
import os, random, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, 'benchmarks'))
import benchlib
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability.metrics import MetricsRegistry

if __name__ == '__main__':
    registry = MetricsRegistry()
    devtel.configure(registry)
    scanner = BatchScanner(benchlib.load_policies(['pack']))
    make_pod = benchlib.load_module('generators', 'mixed_cluster').make_pod
    scanner.CHUNK = 16
    scanner._encoder_pool.procs = 2
    rng = random.Random(3)
    docs = [make_pod(rng, i) for i in range(48)]
    assert len(list(scanner.scan_report_results(docs))) == len(docs)
    print('worker chunks',
          int(registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                     result='ok')), flush=True)
    {ending}
'''


def _group_outlives(script_path) -> tuple:
    """Run the script as the leader of a new session; the instant it has
    been waited for, ask whether its process group still has a member."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.Popen([sys.executable, str(script_path)], env=env,
                            stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    out, _ = proc.communicate(timeout=300)
    try:
        os.killpg(proc.pid, 0)
        alive = True
    except ProcessLookupError:
        alive = False
    return proc.returncode, out, alive


def test_a_process_that_scanned_with_workers_leaves_none_behind(tmp_path):
    """The fork server and the resource tracker only notice that their
    parent is gone after it has exited; the exit hook the first pool
    registers stops them and waits, whether the pool was closed or not
    and whether the process ends in a return or in an error."""
    for name, ending in (('returns', 'pass'),
                         ('raises', 'raise SystemExit(3)')):
        script = tmp_path / f'{name}.py'
        script.write_text(SCAN_WITH_WORKERS.format(repo=REPO,
                                                   ending=ending))
        rc, out, alive = _group_outlives(script)
        assert rc == (3 if name == 'raises' else 0), out
        assert 'worker chunks 3' in out, out
        assert not alive, f'{name}: a process outlived the scanning one'
