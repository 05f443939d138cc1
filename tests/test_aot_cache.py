"""Persistent AOT executable cache + background warm-up (ISSUE 2).

Store semantics (hit/miss, corruption tolerance, LRU eviction, atomic
writes), cache-key scoping (policy set / version mismatch / multi-
device refusal), warmer lifecycle (including the KTPU_WARM=0 no-op),
and the acceptance criterion: a second process starting against a
populated cache performs ZERO fresh XLA compiles for the cached policy
set (asserted via the kyverno_tpu_compile_cache aot_load/miss
counters), with bit-identical scan output vs the uncached path.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from kyverno_tpu.aotcache import keys as aot_keys
from kyverno_tpu.aotcache.store import AotStore, reset_default_store
from kyverno_tpu.aotcache.warmer import Warmer
from kyverno_tpu.observability.metrics import (MetricsRegistry,
                                               set_global_registry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_default_store():
    reset_default_store()
    yield
    reset_default_store()
    set_global_registry(None)


# ---------------------------------------------------------------------------
# store


class TestStore:
    def test_miss_then_hit(self, tmp_path):
        store = AotStore(root=str(tmp_path))
        assert store.load('k' * 32) is None          # miss
        assert store.put('k' * 32, b'payload-bytes')
        assert store.load('k' * 32) == b'payload-bytes'  # hit
        st = store.stats()
        assert st['entries'] == 1 and st['bytes'] > len(b'payload-bytes')

    def test_corrupt_entry_dropped_not_crashed(self, tmp_path):
        store = AotStore(root=str(tmp_path))
        store.put('deadbeef', b'x' * 256)
        path = store.path('deadbeef')
        raw = bytearray(open(path, 'rb').read())
        raw[-1] ^= 0xFF  # flip a payload bit under the digest
        open(path, 'wb').write(bytes(raw))
        assert store.load('deadbeef') is None
        assert not os.path.exists(path), 'corrupt entry must be deleted'
        # truncated-below-header entries are equally a miss
        open(store.path('cafe'), 'wb').write(b'KT')
        assert store.load('cafe') is None
        assert not os.path.exists(store.path('cafe'))

    def test_lru_eviction_respects_byte_budget(self, tmp_path):
        blob = b'z' * 1000
        frame = 38  # magic + sha256
        store = AotStore(root=str(tmp_path),
                         max_bytes=3 * (len(blob) + frame))
        now = time.time()
        for i, key in enumerate(('old', 'mid', 'new')):
            store.put(key, blob)
            os.utime(store.path(key), (now - 100 + i, now - 100 + i))
        store.put('newest', blob)  # over budget: LRU ('old') evicted
        assert store.load('old') is None
        assert store.load('mid') is not None
        assert store.load('newest') is not None
        assert store.stats()['entries'] == 3

    def test_load_refreshes_lru_position(self, tmp_path):
        blob = b'z' * 1000
        store = AotStore(root=str(tmp_path), max_bytes=3 * 1100)
        now = time.time()
        for i, key in enumerate(('a', 'b', 'c')):
            store.put(key, blob)
            os.utime(store.path(key), (now - 100 + i, now - 100 + i))
        store.load('a')  # touch: 'a' becomes most-recent, 'b' is LRU
        store.put('d', blob)
        assert store.load('b') is None
        assert store.load('a') is not None

    def test_atomic_writes_leave_no_tmp(self, tmp_path):
        store = AotStore(root=str(tmp_path))
        for i in range(5):
            store.put(f'key{i}', os.urandom(2048))
        assert not [n for n in os.listdir(tmp_path) if n.endswith('.tmp')]

    def test_env_knobs(self, tmp_path, monkeypatch):
        monkeypatch.setenv('KTPU_AOT_CACHE_DIR', str(tmp_path / 'via-env'))
        store = AotStore()
        assert store.root == str(tmp_path / 'via-env')
        monkeypatch.setenv('KTPU_AOT', '0')
        assert not AotStore().enabled

    def test_publishes_size_gauges(self, tmp_path):
        reg = MetricsRegistry()
        set_global_registry(reg)
        store = AotStore(root=str(tmp_path))
        store.put('k1', b'x' * 100)
        assert reg.gauge_value('kyverno_tpu_aot_cache_entries') == 1.0
        assert reg.gauge_value('kyverno_tpu_aot_cache_size_bytes') > 100

    def test_undecodable_blob_is_evicted_by_loader(self, tmp_path):
        from kyverno_tpu.compiler import aot
        store = AotStore(root=str(tmp_path))
        store.put('badcodec', b'Qnot-a-real-codec-blob')
        assert aot.load_executable('badcodec', store=store) is None
        assert store.load('badcodec') is None, 'bad entry must be dropped'


# ---------------------------------------------------------------------------
# keys


def _single_device(monkeypatch):
    monkeypatch.setattr(aot_keys.jax, 'local_devices',
                        lambda backend=None: [object()])


class TestKeys:
    PACKED = {'pk_int8': np.zeros((4, 8), np.int8),
              'pk_float64': np.zeros((4, 2), np.float64)}

    def test_key_scopes_policy_set_and_version(self, monkeypatch):
        _single_device(monkeypatch)
        k1 = aot_keys.executable_cache_key('fp-one', self.PACKED)
        k2 = aot_keys.executable_cache_key('fp-two', self.PACKED)
        assert k1 and k2 and k1 != k2
        # version-key mismatch: a format bump invalidates every entry
        monkeypatch.setattr(aot_keys, 'AOT_VERSION',
                            aot_keys.AOT_VERSION + 1)
        k1_v2 = aot_keys.executable_cache_key('fp-one', self.PACKED)
        assert k1_v2 and k1_v2 != k1

    def test_version_mismatch_misses_in_store(self, tmp_path, monkeypatch):
        _single_device(monkeypatch)
        store = AotStore(root=str(tmp_path))
        k_old = aot_keys.executable_cache_key('fp', self.PACKED)
        store.put(k_old, b'serialized-under-old-version')
        monkeypatch.setattr(aot_keys, 'AOT_VERSION',
                            aot_keys.AOT_VERSION + 1)
        k_new = aot_keys.executable_cache_key('fp', self.PACKED)
        assert store.load(k_new) is None    # stale entry never loads
        assert store.load(k_old) is not None  # ...but is not destroyed

    def test_key_scopes_batch_layout(self, monkeypatch):
        _single_device(monkeypatch)
        other = {'pk_int8': np.zeros((8, 8), np.int8),
                 'pk_float64': np.zeros((8, 2), np.float64)}
        assert aot_keys.executable_cache_key('fp', self.PACKED) != \
            aot_keys.executable_cache_key('fp', other)

    def test_multi_device_host_refuses_key(self):
        # the tier-1 env forces 8 virtual CPU devices; deserialize_and_
        # load would mis-load a 1-device executable as 8-shard SPMD
        import jax
        if len(jax.local_devices(backend='cpu')) == 1:
            pytest.skip('env has a single CPU device')
        assert aot_keys.executable_cache_key('fp', self.PACKED) is None

    def test_fingerprint_stable(self):
        fp = aot_keys.policy_set_fingerprint
        a = [{'spec': {'rules': [1]}, 'metadata': {'name': 'x'}}]
        b = [{'metadata': {'name': 'x'}, 'spec': {'rules': [1]}}]
        assert fp(a) == fp(b)          # key order never matters
        assert fp(a) != fp([{'metadata': {'name': 'y'}}])


# ---------------------------------------------------------------------------
# warmer


class TestWarmer:
    def test_noop_when_disabled(self, monkeypatch):
        monkeypatch.setenv('KTPU_WARM', '0')
        calls = []
        w = Warmer(lambda: calls.append(1))
        assert w.start() is False
        assert w.state == 'disabled'
        assert w.wait(0.1) is True       # never blocks callers
        assert not calls, 'warm_fn must not run when disabled'
        assert not [t for t in threading.enumerate()
                    if t.name.startswith('ktpu-aot-warmer')]

    def test_ready_records_duration_histogram(self):
        reg = MetricsRegistry()
        w = Warmer(lambda: 'warmed 3 executables', registry=reg,
                   enabled=True)
        assert w.start() is True
        assert w.wait(10.0)
        assert w.state == 'ready' and w.ready
        assert w.detail == 'warmed 3 executables'
        assert reg.histogram_count('kyverno_tpu_aot_warm_duration_seconds',
                                   target='admission', state='ready') == 1

    def test_failure_is_contained(self):
        reg = MetricsRegistry()

        def boom():
            raise RuntimeError('no backend')
        w = Warmer(boom, name='scan', registry=reg, enabled=True)
        w.run_sync()
        assert w.state == 'failed' and not w.ready
        assert 'no backend' in w.error
        assert reg.histogram_count('kyverno_tpu_aot_warm_duration_seconds',
                                   target='scan', state='failed') == 1

    def test_start_is_idempotent(self):
        calls = []
        w = Warmer(lambda: calls.append(1) or 'ok', enabled=True)
        assert w.start() and w.start()
        w.wait(10.0)
        assert calls == [1]

    def test_setup_starts_warmer(self):
        from kyverno_tpu.cmd.internal import Setup
        setup = Setup('t', args=['--disable-metrics'])
        w = setup.start_aot_warmer(lambda: 'scanner serving')
        assert setup.aot_warmer is w
        assert w.wait(10.0) and w.state == 'ready'
        assert w.detail == 'scanner serving'

    def test_webhook_warmup_status(self):
        from types import SimpleNamespace
        from kyverno_tpu.webhooks.server import WebhookServer
        status = WebhookServer.warmup_status
        body, code = status(SimpleNamespace(warmer=None))
        assert (body['state'], code) == ('disabled', 200)
        w = Warmer(lambda: 'ok', enabled=True)
        body, code = status(SimpleNamespace(warmer=w))
        assert (body['state'], code) == ('pending', 503)
        w.run_sync()
        body, code = status(SimpleNamespace(warmer=w))
        assert (body['state'], code) == ('ready', 200)
        assert 'duration_s' in body


# ---------------------------------------------------------------------------
# acceptance: second process = zero fresh compiles, bit-identical output

_SECOND_PROC_SCRIPT = r'''
import json, sys
from kyverno_tpu.api.policy import Policy
from kyverno_tpu.observability import device as devtel
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


reg = devtel.configure(MetricsRegistry())
from kyverno_tpu.compiler.scan import BatchScanner
scanner = BatchScanner([Policy(POLICY)])
status, detail, match = scanner.scan_statuses([pod(i) for i in range(4)])
from kyverno_tpu.compiler import aot
aot.flush_stores()
C = 'kyverno_tpu_compile_cache_requests_total'
print(json.dumps({
    'miss': reg.counter_value(C, result='miss'),
    'aot_load': reg.counter_value(C, result='aot_load'),
    'aot_store': reg.counter_value(C, result='aot_store'),
    'status': status.tolist(),
    'detail': detail.tolist(),
    'match': match.tolist(),
}))
'''


def _fresh_python(script, timeout=240, **extra_env):
    """Run ``script`` in a new one-device CPU interpreter; its last
    line of output is JSON."""
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS',
                        'JAX_COMPILATION_CACHE_DIR')}
    env.update({'JAX_PLATFORMS': 'cpu', 'PYTHONPATH': os.pathsep.join(
        (REPO, os.path.join(REPO, 'benchmarks')))}, **extra_env)
    out = subprocess.run([sys.executable, '-c', script],
                         env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _run_fresh_process(cache_dir, aot_enabled=True, timeout=240,
                       script=None, aot_dir='aot'):
    return _fresh_python(
        script or _SECOND_PROC_SCRIPT, timeout=timeout,
        KTPU_AOT='1' if aot_enabled else '0',
        KTPU_AOT_CACHE_DIR=os.path.join(str(cache_dir), aot_dir),
        JAX_COMPILATION_CACHE_DIR=os.path.join(str(cache_dir), 'xla'))


def test_second_process_zero_fresh_compiles(tmp_path):
    """ISSUE 2 acceptance: process 1 compiles + persists; process 2
    (fresh interpreter, cold jit caches, same policy set) serves
    entirely from aot_load with zero misses; a third process with the
    cache disabled recompiles and produces bit-identical matrices."""
    first = _run_fresh_process(tmp_path)
    assert first['miss'] >= 1, first
    assert first['aot_store'] >= 1, first
    second = _run_fresh_process(tmp_path)
    assert second['miss'] == 0, second
    assert second['aot_load'] >= 1, second
    uncached = _run_fresh_process(tmp_path, aot_enabled=False)
    assert uncached['miss'] >= 1, uncached
    for field in ('status', 'detail', 'match'):
        assert second[field] == first[field] == uncached[field], field


# ---------------------------------------------------------------------------
# where the XLA compile cache goes (ISSUE 22 step 6)

_CACHE_DIR_SCRIPT = r'''
import json, os
import jax
from kyverno_tpu.aotcache import keys
used = keys.enable_persistent_compilation_cache()
print(json.dumps({
    'used': used,
    'config': jax.config.jax_compilation_cache_dir,
    'min_entry': jax.config.jax_persistent_cache_min_entry_size_bytes,
    'min_secs': jax.config.jax_persistent_cache_min_compile_time_secs,
}))
'''


def _cache_dir_process(**extra_env):
    return _fresh_python(_CACHE_DIR_SCRIPT, timeout=120, **extra_env)


class TestCompileCachePlacement:
    def test_standard_variable_is_left_alone(self, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set, no code path sets another
        directory — not even the feature guard's re-scoped
        sub-directory, though the marker there names another host."""
        given = tmp_path / 'from-outside'
        given.mkdir()
        (given / aot_keys.HOSTKEY_FILE).write_text('feedface00')
        got = _cache_dir_process(JAX_COMPILATION_CACHE_DIR=str(given))
        assert got['used'] == got['config'] == str(given)
        assert sorted(os.listdir(given)) == [aot_keys.HOSTKEY_FILE]
        # the two thresholds are still the repo's
        assert got['min_entry'] == -1 and got['min_secs'] == 0.5

    def test_default_is_one_fixed_directory_per_platform(self):
        """Unset, the directory is inside the checkout and does not
        move with the flags: JAX's own cache key covers those."""
        plain = _cache_dir_process()
        flagged = _cache_dir_process(
            XLA_FLAGS='--xla_force_host_platform_device_count=2',
            JAX_ENABLE_X64='1')
        assert plain['used'] == plain['config'] == flagged['used'] \
            == flagged['config']
        fixed = aot_keys.default_compile_cache_dir('cpu')
        assert fixed == os.path.join(REPO, '.cache', 'xla-cpu')
        # the feature guard may re-scope the CPU directory of a shared
        # checkout, but only ever below the fixed one
        assert plain['used'] == fixed or \
            os.path.dirname(plain['used']) == fixed


# ---------------------------------------------------------------------------
# small batches go to the default device (ISSUE 22 step 5)

def test_one_row_batch_asks_for_no_cpu_device(monkeypatch):
    """With a non-CPU default backend the scanner used to place every
    batch of 64 rows or fewer — all admission traffic — on the host's
    XLA:CPU backend.  The routing is gone: a 1-row scan on a (stub)
    accelerator backend never asks for a CPU device, and its tensors
    sit on the default device."""
    import inspect
    import jax
    from kyverno_tpu.api.policy import Policy
    from kyverno_tpu.compiler import scan as scan_mod
    from kyverno_tpu.ops import eval as eval_mod
    assert not hasattr(scan_mod.BatchScanner, '_small_device')
    assert 'device' not in inspect.signature(
        eval_mod.shard_batch).parameters

    policy = Policy({
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': 'require-labels', 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {'rules': [
            {'name': 'check-app',
             'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
             'validate': {'message': 'app label required',
                          'pattern': {'metadata': {
                              'labels': {'app': '?*'}}}}}]}})
    pod = {'apiVersion': 'v1', 'kind': 'Pod',
           'metadata': {'name': 'p', 'namespace': 'default'},
           'spec': {'containers': [{'name': 'c', 'image': 'nginx:1'}]}}
    scanner = scan_mod.BatchScanner([policy])

    default = jax.devices()[0]
    real_local_devices = jax.local_devices
    scanner_asks = []

    def local_devices(*args, **kwargs):
        caller = sys._getframe(1).f_code.co_filename
        if caller == scan_mod.__file__:
            scanner_asks.append((args, kwargs))
        return real_local_devices(*args, **kwargs)

    placed = []
    real_shard_batch = eval_mod.shard_batch

    def shard_batch(tensors, *args, **kwargs):
        packed, layout = real_shard_batch(tensors, *args, **kwargs)
        # read now: the scanner frees its inputs after the readback
        placed.append([(int(arr.shape[0]), arr.devices())
                       for arr in packed.values()])
        return packed, layout

    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(jax, 'local_devices', local_devices)
    monkeypatch.setattr(eval_mod, 'shard_batch', shard_batch)
    [responses] = scanner.scan([pod])
    assert [r.status for er in responses
            for r in er.policy_response.rules] == ['fail']
    assert scanner_asks == []
    assert placed, 'the 1-row batch never reached shard_batch'
    for packed in placed:
        for rows, devices in packed:
            assert rows == scanner.SMALL_BATCH
            assert devices == {default}


# ---------------------------------------------------------------------------
# an executable the XLA cache handed back is not stored a second time

_XLA_THEN_AOT_SCRIPT = r'''
import json, os, random, sys
import benchlib
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability.metrics import MetricsRegistry
reg = devtel.configure(MetricsRegistry())
from kyverno_tpu.compiler.scan import BatchScanner
scanner = BatchScanner(benchlib.load_policies(['pack']))
make_pod = benchlib.load_module('generators', 'mixed_cluster').make_pod
rng = random.Random(0)
status, detail, match = scanner.scan_statuses(
    [make_pod(rng, i) for i in range(4)])
from kyverno_tpu.compiler import aot
aot.flush_stores()
C = 'kyverno_tpu_compile_cache_requests_total'
print(json.dumps({
    'miss': reg.counter_value(C, result='miss'),
    'aot_load': reg.counter_value(C, result='aot_load'),
    'aot_store': reg.counter_value(C, result='aot_store'),
    'xla_hits': aot.xla_cache_hits(),
    'status': status.tolist(),
}))
'''


def test_xla_cache_served_executable_is_not_stored_again(tmp_path):
    """jax 0.9 on XLA:CPU: an executable that the persistent XLA cache
    handed back serializes into a blob that loads and then fails at
    execution (``Function ... not found``).  So a process that misses
    the AOT store but hits the XLA cache stores nothing, and the
    process after it still runs."""
    def run(aot_dir):
        return _run_fresh_process(tmp_path, script=_XLA_THEN_AOT_SCRIPT,
                                  aot_dir=aot_dir)

    first = run('aot-1')
    assert first['miss'] == 1 and first['aot_store'] == 1, first
    if not any(f.startswith('jit_evaluate_packed-')
               for f in os.listdir(tmp_path / 'xla')):
        pytest.skip('the compile was under the XLA cache\'s 0.5 s '
                    'threshold on this machine: nothing to hand back')
    # same XLA cache, an AOT store that has never seen this policy set
    second = run('aot-2')
    assert second['miss'] == 1 and second['xla_hits'] >= 1, second
    assert second['aot_store'] == 0, second
    assert not os.path.isdir(tmp_path / 'aot-2') or \
        not os.listdir(tmp_path / 'aot-2')
    third = run('aot-2')
    assert third['status'] == second['status'] == first['status']
