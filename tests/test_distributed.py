"""Multi-process jax.distributed validation (SURVEY §5.8).

The single-host virtual mesh (conftest's 8 CPU devices) exercises the
sharding math; this test exercises the actual multi-HOST path: two
separate processes join one jax.distributed coordination service, form
a global mesh spanning both, run the sharded scan step on the same
batch, and must agree on the psum-reduced verdict summary — exactly how
a v5e multi-host slice runs (one process per host, collectives over
the global mesh).  Process 0 is the convention leader
(controllers/leaderelection.py mesh_is_leader).
"""

import json
import os
import socket
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r'''
import json, os, sys
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, 'benchmarks'))
import jax
jax.config.update('jax_platforms', 'cpu')
jax.distributed.initialize(coordinator_address=%(coord)r,
                           num_processes=2,
                           process_id=int(sys.argv[1]))
assert jax.process_count() == 2
import numpy as np
import benchlib
from kyverno_tpu.compiler.compile import compile_policies
from kyverno_tpu.parallel.mesh import distributed_scan_step, make_mesh
from kyverno_tpu.controllers.leaderelection import mesh_is_leader

mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')
policies = benchlib.load_policies(['pack'])
cps = compile_policies(policies)
import random
rng = random.Random(0)
resources = [mixed_cluster.make_pod(rng, i) for i in range(24)]
mesh = make_mesh()   # global devices across both processes
assert mesh.devices.size == jax.device_count() == 4  # 2 per process
statuses, summary = distributed_scan_step(cps, mesh, resources)

# streamed REPORT path across the same multi-host mesh: >= 3 chunks
# (KTPU_SCAN_CHUNK=16 over 40 resources), reports must be identical on
# every host and equal to the single-process run (timestamps pinned)
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.reports.results import set_responses
from kyverno_tpu.reports.types import new_background_scan_report
stream_resources = [mixed_cluster.make_pod(rng, 1000 + i) for i in range(40)]
scanner = BatchScanner(policies, mesh=mesh)
report_dump = []
for resource, responses in zip(stream_resources,
                               scanner.scan_stream(stream_resources)):
    report = new_background_scan_report(resource)
    relevant = [r for r in responses if r.policy_response.rules]
    set_responses(report, *relevant, now=0)
    # result dicts are shared flyweights: sanitize into copies
    from kyverno_tpu.reports.results import get_results
    report.setdefault('spec', {})['results'] = [
        {k: v for k, v in res.items() if k != 'timestamp'}
        for res in get_results(report)]
    report_dump.append(report)
import hashlib
report_hash = hashlib.sha256(
    json.dumps(report_dump, sort_keys=True).encode()).hexdigest()

print('RESULT ' + json.dumps({
    'process': jax.process_index(),
    'leader': mesh_is_leader(),
    'devices': jax.device_count(),
    'local_devices': jax.local_device_count(),
    'summary': np.asarray(summary).tolist(),
    'status_sum': int(np.asarray(statuses).sum()),
    'n_stream_reports': len(report_dump),
    'report_hash': report_hash,
}))
'''


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def test_two_process_distributed_scan_agrees():
    coord = f'127.0.0.1:{_free_port()}'
    code = WORKER % {'repo': REPO, 'coord': coord}
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
    env['KTPU_SCAN_CHUNK'] = '16'   # 40 resources -> 3 streamed chunks
    env.pop('JAX_NUM_PROCESSES', None)
    procs = [subprocess.Popen([sys.executable, '-c', code, str(i)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for i in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=540)
        assert p.returncode == 0, f'worker failed:\n{err[-3000:]}'
        [line] = [ln for ln in out.splitlines() if ln.startswith('RESULT ')]
        outs.append(json.loads(line[len('RESULT '):]))
    by_proc = {o['process']: o for o in outs}
    assert set(by_proc) == {0, 1}
    # process-0 leader convention, 2 local / 4 global devices each
    assert by_proc[0]['leader'] is True
    assert by_proc[1]['leader'] is False
    for o in outs:
        assert o['devices'] == 4 and o['local_devices'] == 2
    # the psum-reduced verdict summary is identical on every process,
    # and both processes reconstruct identical full status matrices
    assert by_proc[0]['summary'] == by_proc[1]['summary']
    assert by_proc[0]['status_sum'] == by_proc[1]['status_sum']
    # the streamed report path ran >= 3 chunks and produced identical
    # reports on both hosts
    assert by_proc[0]['n_stream_reports'] == 40
    assert by_proc[0]['report_hash'] == by_proc[1]['report_hash']

    # ground truth: the same batch on a single-process evaluator
    import random

    import numpy as np

    import benchlib
    from kyverno_tpu.compiler.compile import compile_policies
    from kyverno_tpu.compiler.encode import encode_batch
    from kyverno_tpu.ops.eval import build_evaluator, shard_batch

    mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')
    policies = benchlib.load_policies(['pack'])
    cps = compile_policies(policies)
    rng = random.Random(0)
    resources = [mixed_cluster.make_pod(rng, i) for i in range(24)]
    batch = encode_batch(resources, cps, padded_n=24)
    t, layout = shard_batch(batch.tensors(), None)
    evaluator = build_evaluator(cps)
    s, d, fd = evaluator(t, layout)
    assert int(np.asarray(s).sum()) == by_proc[0]['status_sum']

    # single-process ground truth for the streamed report path
    import hashlib
    import json as _json

    from kyverno_tpu.compiler.scan import BatchScanner
    from kyverno_tpu.reports.results import set_responses
    from kyverno_tpu.reports.types import new_background_scan_report

    stream_resources = [mixed_cluster.make_pod(rng, 1000 + i)
                        for i in range(40)]
    scanner = BatchScanner(policies)
    dump = []
    for resource, responses in zip(stream_resources,
                                   scanner.scan_stream(stream_resources)):
        report = new_background_scan_report(resource)
        relevant = [r for r in responses if r.policy_response.rules]
        set_responses(report, *relevant, now=0)
        from kyverno_tpu.reports.results import get_results
        report.setdefault('spec', {})['results'] = [
            {k: v for k, v in res.items() if k != 'timestamp'}
            for res in get_results(report)]
        dump.append(report)
    want = hashlib.sha256(
        _json.dumps(dump, sort_keys=True).encode()).hexdigest()
    assert want == by_proc[0]['report_hash']


# -- fleet observatory on the virtual mesh (ISSUE 18) -------------------------
#
# Mesh-step telemetry, straggler blame and federation against the
# conftest 8-device mesh: the KTPU_FLEET=0 path must be bit-identical,
# an injected per-shard delay must be *named* as the straggler, and the
# /debug/fleet endpoint must agree with the offline CLI merge.

import time as _time

import numpy as np
import pytest
import yaml

from kyverno_tpu import faults
from kyverno_tpu.api.policy import Policy
from kyverno_tpu.compiler.compile import compile_policies
from kyverno_tpu.observability import fleet
from kyverno_tpu.observability.metrics import MetricsRegistry
from kyverno_tpu.parallel.mesh import distributed_scan_step, make_mesh

FLEET_PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: fleet-pack
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: no-latest
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: no latest
        pattern:
          spec:
            containers:
              - image: "!*:latest"
"""


def _fleet_pods(n):
    return [{'apiVersion': 'v1', 'kind': 'Pod',
             'metadata': {'name': f'p{i}'},
             'spec': {'containers': [
                 {'name': 'c',
                  'image': 'nginx:latest' if i % 2 else 'nginx:1.25'}]}}
            for i in range(n)]


@pytest.fixture
def mesh8():
    import jax
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip('needs the 8-device virtual mesh')
    return make_mesh(devices[:8])


@pytest.fixture
def fleet_teardown():
    yield
    fleet.disable()
    faults.disable()


def _fleet_cps():
    return compile_policies(
        [Policy(d) for d in yaml.safe_load_all(FLEET_PACK) if d])


class TestFleetMesh:
    def test_ktpu_fleet_0_bit_identity(self, mesh8, monkeypatch,
                                       fleet_teardown):
        cps = _fleet_cps()
        resources = _fleet_pods(13)
        fleet.disable()
        base_s, base_sum = distributed_scan_step(cps, mesh8, resources)
        # KTPU_FLEET=0 refuses configuration outright
        monkeypatch.setenv('KTPU_FLEET', '0')
        assert fleet.configure(MetricsRegistry()) is None
        assert not fleet.enabled()
        off_s, off_sum = distributed_scan_step(cps, mesh8, resources)
        # armed: same outputs, telemetry on the side
        monkeypatch.delenv('KTPU_FLEET')
        reg = MetricsRegistry()
        assert fleet.configure(
            reg, profile_trigger=lambda: None) is not None
        on_s, on_sum = distributed_scan_step(cps, mesh8, resources)
        np.testing.assert_array_equal(base_s, off_s)
        np.testing.assert_array_equal(base_sum, off_sum)
        np.testing.assert_array_equal(base_s, on_s)
        np.testing.assert_array_equal(base_sum, on_sum)
        snap = reg.snapshot(fleet.identity())
        assert fleet.MESH_COLLECTIVE_SECONDS in snap['counters']
        assert fleet.MESH_PADDING_ROWS in snap['counters']
        assert fleet.MESH_STEP_DURATION in snap['hists']
        # per-shard series (0..7) plus the shard=all whole-step series
        shards = {dict(key)['shard'] for key, *_rest
                  in snap['hists'][fleet.MESH_STEP_DURATION]['series']
                  for key in [tuple(map(tuple, key))]}
        assert shards == {str(i) for i in range(8)} | {'all'}

    def test_injected_delay_names_straggler(self, mesh8,
                                            fleet_teardown):
        cps = _fleet_cps()
        resources = _fleet_pods(16)
        fleet.disable()
        distributed_scan_step(cps, mesh8, resources)  # compile warm
        fired = []
        reg = MetricsRegistry()
        fleet.configure(reg, window=2,
                        profile_trigger=lambda: fired.append(1))
        # 8 mesh_shard checks per step, batch-axis order: the 3rd and
        # 11th checks are shard 2 of steps 1 and 2 — a sustained
        # straggler on shard 2 across the whole window
        faults.configure('site=mesh_shard,nth=3,delay_ms=150;'
                         'site=mesh_shard,nth=11,delay_ms=150')
        try:
            distributed_scan_step(cps, mesh8, resources)
            distributed_scan_step(cps, mesh8, resources)
        finally:
            faults.disable()
        verdict = fleet.analyzer().verdict()
        assert verdict['slow_shard'] == 2
        assert verdict['sustained'] is True
        assert verdict['bound_by'] == 'straggler'
        assert 'shard 2' in verdict['note']
        assert verdict['device']  # names the blamed device
        assert verdict['skew'] > 2.0
        # the deep-profile trigger fires exactly once (rate-limited,
        # single-fire on the False->True transition), on a worker
        # thread — wait for it
        deadline = _time.monotonic() + 5.0
        while not fired and _time.monotonic() < deadline:
            _time.sleep(0.02)
        assert fired == [1]
        # the skew gauge carries the mesh identity label
        assert reg.gauge_value(fleet.MESH_SHARD_SKEW,
                               mesh='data8') > 2.0

    def test_endpoint_and_cli_agree(self, mesh8, tmp_path,
                                    fleet_teardown):
        import subprocess
        import urllib.request
        from kyverno_tpu.observability.profiling import ProfilingServer
        cps = _fleet_cps()
        reg = MetricsRegistry()
        fr = fleet.configure(reg, profile_trigger=lambda: None)
        distributed_scan_step(cps, mesh8, _fleet_pods(9))
        srv = ProfilingServer(port=0)
        srv.start()
        try:
            url = f'http://127.0.0.1:{srv.port}/debug/fleet'
            with urllib.request.urlopen(url, timeout=10) as resp:
                doc = json.loads(resp.read().decode())
            with urllib.request.urlopen(url + '?format=table',
                                        timeout=10) as resp:
                table = resp.read().decode()
        finally:
            srv.stop()
        assert doc['enabled'] is True
        assert doc['skew'] is not None
        assert 'merged counter' in table
        endpoint_totals = fleet.FleetRegistry.counter_totals(
            doc['merged'])
        # offline CLI over the JSONL snapshot artifact must agree
        snap_path = tmp_path / 'host0.jsonl'
        fleet.write_snapshot(str(snap_path), reg)
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, 'scripts', 'fleet_report.py'),
             '--json', str(snap_path)],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        cli_doc = json.loads(out.stdout)
        cli_totals = fleet.FleetRegistry.counter_totals(
            cli_doc['merged'])
        for name in set(endpoint_totals) | set(cli_totals):
            assert cli_totals.get(name) == pytest.approx(
                endpoint_totals.get(name)), name
        assert fr.report()['processes']
