"""ProfilingServer debug surface (ISSUE 14 satellites 1 + 4).

The endpoints existed for five PRs with zero coverage.  Pins: the
self-registering route table (one source for dispatch, the ``/debug/``
index, the 404-with-index response, and the README table — drift
checked here), the pprof analogues (thread stacks, bounded sampling
profile), ``/metrics`` content-type, the new ledger/SLO/deep-profile
routes, and concurrent GETs through the threading server.  CPU-only,
tier-1.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from kyverno_tpu.observability import executables, profiling, slo
from kyverno_tpu.observability.metrics import MetricsRegistry
from kyverno_tpu.observability.profiling import (PROFILE_KEEP,
                                                 ProfilingServer,
                                                 deep_profile,
                                                 render_debug_index,
                                                 render_debug_table,
                                                 routes, sample_profile,
                                                 thread_stacks)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_modules():
    yield
    executables.disable()
    slo.disable()


@pytest.fixture()
def server():
    srv = ProfilingServer(port=0)
    srv.start()
    yield srv
    srv.stop()


def get(srv, path):
    """(status, content_type, body) for a GET against the server —
    HTTPError carries the 4xx/5xx responses."""
    url = f'http://127.0.0.1:{srv.port}{path}'
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.headers.get('Content-Type'), \
                resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get('Content-Type'), e.read().decode()


class TestPprofSurface:
    def test_thread_stacks_names_live_threads(self):
        done = threading.Event()
        t = threading.Thread(target=done.wait,
                             name='ktpu-test-sleeper', daemon=True)
        t.start()
        try:
            stacks = thread_stacks()
            assert 'ktpu-test-sleeper' in stacks
            assert 'thread ' in stacks
        finally:
            done.set()
            t.join()

    def test_sample_profile_is_time_bounded(self):
        done = threading.Event()
        t = threading.Thread(target=done.wait, name='busy', daemon=True)
        t.start()
        try:
            t0 = time.monotonic()
            out = sample_profile(0.1, hz=200)
            elapsed = time.monotonic() - t0
            assert elapsed < 2.0  # bounded: ~0.1s of sampling
            assert out.strip()  # folded stacks (or the idle marker)
        finally:
            done.set()
            t.join()

    def test_goroutine_endpoint(self, server):
        code, ctype, body = get(server, '/debug/pprof/goroutine')
        assert code == 200 and 'thread ' in body

    def test_profile_endpoint_rejects_bad_seconds(self, server):
        code, _, body = get(server, '/debug/pprof/profile?seconds=zap')
        assert code == 400 and 'seconds' in body

    def test_profile_endpoint_samples(self, server):
        code, ctype, body = get(server,
                                '/debug/pprof/profile?seconds=0.05')
        assert code == 200 and body


class TestDeepProfile:
    def test_capture_writes_bounded_artifacts(self, tmp_path):
        root = str(tmp_path / 'profiles')
        out = deep_profile(seconds=0.02, trigger='test', out_dir=root)
        # py.folded always lands; a jax/ trace rides along only when a
        # backend is live (depends on what ran earlier in the process)
        assert 'py.folded' in out['artifacts']
        assert ('jax' in out['artifacts']) == out['jax_trace']
        assert os.path.isfile(os.path.join(out['dir'], 'py.folded'))
        assert os.path.basename(out['dir']).startswith('profile-test-')
        # seconds clamp floor
        assert out['seconds'] == 0.02

    def test_prune_keeps_newest(self, tmp_path):
        root = str(tmp_path / 'profiles')
        for _ in range(PROFILE_KEEP + 3):
            deep_profile(seconds=0.01, trigger='t', out_dir=root)
        kept = [e for e in os.listdir(root) if e.startswith('profile-')]
        assert len(kept) == PROFILE_KEEP

    def test_endpoint_and_env_dir(self, server, tmp_path, monkeypatch):
        monkeypatch.setenv('KTPU_PROFILE_DIR', str(tmp_path / 'p'))
        code, ctype, body = get(server, '/debug/profile?seconds=0.02')
        assert code == 200 and ctype.startswith('application/json')
        out = json.loads(body)
        assert out['trigger'] == 'manual'
        assert out['dir'].startswith(str(tmp_path / 'p'))
        code, _, _ = get(server, '/debug/profile?seconds=nope')
        assert code == 400


class TestRouteRegistry:
    def test_index_served_at_debug_root(self, server):
        for path in ('/debug', '/debug/'):
            code, _, body = get(server, path)
            assert code == 200
            for route in routes():
                assert route in body

    def test_unknown_debug_path_404s_with_index(self, server):
        code, _, body = get(server, '/debug/nope')
        assert code == 404
        assert 'not found' in body
        assert '/debug/slo' in body  # the index rides the 404

    def test_unknown_non_debug_path_is_plain_404(self, server):
        code, _, body = get(server, '/nope')
        assert code == 404 and 'debug endpoints' not in body

    def test_trailing_slash_is_equivalent(self, server):
        a = get(server, '/debug/pprof')
        b = get(server, '/debug/pprof/')
        assert a == b

    def test_readme_table_does_not_drift(self):
        """The README endpoint table is generated
        (`scripts/analyze.py --debug-table`); every generated row must
        appear verbatim — a route added without regenerating fails."""
        table = render_debug_table()
        readme = open(os.path.join(REPO, 'README.md'),
                      encoding='utf-8').read()
        for line in table.splitlines():
            assert line in readme, f'README debug table drifted: {line}'
        assert render_debug_index().startswith('debug endpoints:')


class TestDataRoutes:
    def test_metrics_content_type_and_body(self, server):
        reg = MetricsRegistry()
        reg.inc('kyverno_tpu_scan_backpressure_seconds_total',
                0.5, stage='encode')
        from kyverno_tpu.observability.metrics import (global_registry,
                                                       set_global_registry)
        prev = global_registry()
        set_global_registry(reg)
        try:
            code, ctype, body = get(server, '/metrics')
        finally:
            set_global_registry(prev)
        assert code == 200
        assert ctype == 'text/plain; version=0.0.4'
        assert 'kyverno_tpu_scan_backpressure_seconds_total' in body

    def test_executables_route_disabled_then_live(self, server):
        code, _, body = get(server, '/debug/executables')
        assert code == 200 and json.loads(body) == {'enabled': False}
        executables.configure(registry=MetricsRegistry(), ledger_n=8)
        executables.record_build('k1', fingerprint='f1', capacity=64,
                                 source='aot_load', build_s=0.5)
        code, _, body = get(server, '/debug/executables')
        out = json.loads(body)
        assert out['enabled'] is True
        assert out['census']['by_source'] == {'aot_load': 1}
        code, ctype, body = get(server,
                                '/debug/executables?format=table')
        assert code == 200 and ctype.startswith('text/plain')
        assert 'aot_load' in body and 'KEY' in body

    def test_slo_route_disabled_then_live(self, server):
        code, _, body = get(server, '/debug/slo')
        assert code == 200 and json.loads(body) == {'enabled': False}
        slo.configure(registry=MetricsRegistry(), window_s=60.0,
                      p99_ms=100.0, target=0.9)
        slo.record('batch', 0.005)
        code, _, body = get(server, '/debug/slo')
        out = json.loads(body)
        assert out['enabled'] is True
        assert out['paths']['batch']['count'] == 1

    def test_concurrent_gets(self, server):
        """The threading server answers parallel requests — a slow
        sampling profile must not block the index."""
        results = []

        def fetch(path):
            results.append(get(server, path))

        threads = [threading.Thread(
            target=fetch, args=(p,), daemon=True) for p in (
                '/debug/pprof/profile?seconds=0.3',
                '/debug/', '/metrics', '/debug/pprof/goroutine',
                '/debug/slo')]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15)
        assert len(results) == 5
        assert all(code in (200,) for code, _, _ in results)
        assert time.monotonic() - t0 < 10.0
