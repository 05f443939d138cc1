"""Admission micro-batching scheduler (kyverno_tpu/serving/).

Pins the serving contract: with ``KTPU_SERVING=batch`` every response
is bit-identical to the sync path's, overflow/deadline/failure traffic
sheds to the host engine loop (never an error to the API server), and
shutdown drains pending futures.  CPU-only, tier-1.
"""

import json
import threading
import time

import pytest
import yaml

from kyverno_tpu.api.policy import Policy
from kyverno_tpu.config.config import Configuration
from kyverno_tpu.policycache import cache as pcache
from kyverno_tpu.policycache.cache import Cache
from kyverno_tpu.serving import shed as shed_policy
from kyverno_tpu.serving.batcher import AdmissionBatcher
from kyverno_tpu.serving.queue import (QueueFull, RequestQueue, Stopped,
                                       Ticket)
from kyverno_tpu.webhooks.handlers import ResourceHandlers
from kyverno_tpu.webhooks.server import WebhookServer

ENFORCE_POLICY = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: require-team
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  validationFailureAction: enforce
  rules:
    - name: require-team
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "label 'team' is required"
        pattern:
          metadata:
            labels:
              team: "?*"
"""


def pod(labels, name):
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': name, 'namespace': 'default',
                         'labels': labels},
            'spec': {'containers': [{'name': 'c', 'image': 'nginx'}]}}


def review_bytes(resource, uid, user_info=None):
    return json.dumps({
        'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
        'request': {
            'uid': uid, 'operation': 'CREATE',
            'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
            'namespace': 'default',
            'name': resource['metadata']['name'],
            'object': resource,
            'userInfo': user_info or {'username': 'alice', 'groups': []},
        }}).encode()


@pytest.fixture(scope='module')
def chain():
    """One compiled serving chain for the whole module (the scanner
    compile is the expensive part; every test shares it)."""
    cache = Cache()
    cache.warm_up([Policy(d) for d in yaml.safe_load_all(ENFORCE_POLICY)])
    handlers = ResourceHandlers(cache, configuration=Configuration(),
                                serving_mode='batch')
    server = WebhookServer(handlers, configuration=Configuration())
    enforce = cache.get_policies(pcache.VALIDATE_ENFORCE, 'Pod', 'default')
    assert handlers.wait_device_ready(enforce, timeout=600)
    yield server, handlers
    handlers.shutdown()


@pytest.fixture
def restore_batcher(chain):
    """Let a test swap in a custom batcher; the module batcher comes
    back (and batch mode is restored) afterwards."""
    _server, handlers = chain
    prior = handlers._batcher
    prior_mode = handlers.serving_mode
    yield handlers
    custom = handlers._batcher
    if custom is not None and custom is not prior:
        custom.stop(drain=True)
    handlers._batcher = prior
    handlers.serving_mode = prior_mode


def mixed_requests(n):
    # alternate violating / compliant pods so both verdict paths batch
    return [(f'u{i}', pod({'team': 'infra'} if i % 2 else {}, f'p{i}'))
            for i in range(n)]


def sync_responses(server, handlers, requests):
    prior = handlers.serving_mode
    handlers.serving_mode = 'sync'
    try:
        return {uid: server.handle('/validate/fail', review_bytes(p, uid))
                for uid, p in requests}
    finally:
        handlers.serving_mode = prior


class TestBatchedServing:
    def test_stress_bit_identity_and_occupancy(self, chain):
        """32 client threads: batched responses are byte-identical to
        the sync path's, and coalescing actually happens (mean
        occupancy > 1)."""
        server, handlers = chain
        handlers._get_batcher().reset_stats()
        requests = mixed_requests(32 * 8)
        per_thread = 8
        results = {}
        errors = []
        barrier = threading.Barrier(32)

        def work(tid):
            barrier.wait()
            for uid, p in requests[tid * per_thread:
                                   (tid + 1) * per_thread]:
                try:
                    out, status = server.handle_request(
                        '/validate/fail', review_bytes(p, uid))
                    assert status == 200
                    results[uid] = out
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors
        assert len(results) == len(requests)
        stats = handlers._get_batcher().stats()
        assert stats['requests'] + stats['shed_total'] >= len(requests)
        assert stats['occupancy_mean'] > 1.0, stats
        expected = sync_responses(server, handlers, requests)
        for uid, _p in requests:
            assert results[uid] == expected[uid]

    def test_deadline_flush_under_trickle(self, chain):
        """A lone request must not wait for riders: the window deadline
        flushes a batch of one, bit-identical to sync."""
        server, handlers = chain
        batcher = handlers._get_batcher()
        batcher.reset_stats()
        requests = mixed_requests(5)
        got = {uid: server.handle('/validate/fail', review_bytes(p, uid))
               for uid, p in requests}
        stats = batcher.stats()
        assert stats['dispatches'] >= 5
        assert stats['occupancy_p50'] == 1
        expected = sync_responses(server, handlers, requests)
        for uid, _p in requests:
            assert got[uid] == expected[uid]

    def test_queue_full_sheds_to_host_no_500s(self, restore_batcher,
                                              chain):
        """Overflowing a capacity-2 queue sheds to the host engine loop:
        every response stays HTTP 200 and correct, and the shed ledger
        records queue_full."""
        server, handlers = chain
        handlers._batcher = AdmissionBatcher(
            window_ms=50, queue_cap=2,
            on_success=handlers._batch_scan_ok,
            on_failure=handlers._batch_scan_failed)
        requests = mixed_requests(24)
        statuses = []
        results = {}
        errors = []
        barrier = threading.Barrier(12)

        def work(tid):
            barrier.wait()
            for uid, p in requests[tid * 2:(tid + 1) * 2]:
                try:
                    out, status = server.handle_request(
                        '/validate/fail', review_bytes(p, uid))
                    statuses.append(status)
                    results[uid] = out
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors
        assert statuses == [200] * len(requests)
        sheds = handlers._batcher.sheds.counts()
        assert sheds.get(shed_policy.REASON_QUEUE_FULL, 0) >= 1, sheds
        expected = sync_responses(server, handlers, requests)
        for uid, _p in requests:
            assert results[uid] == expected[uid]

    def test_drain_on_stop_resolves_pending(self, restore_batcher,
                                            chain):
        """shutdown() drains: tickets parked behind a huge window get
        real batched responses, and post-stop requests still serve
        (host loop, shed reason shutdown)."""
        server, handlers = chain
        batcher = AdmissionBatcher(
            window_ms=60_000, queue_cap=64, shed_deadline_ms=30_000,
            on_success=handlers._batch_scan_ok,
            on_failure=handlers._batch_scan_failed)
        handlers._batcher = batcher
        requests = mixed_requests(3)
        results = {}

        def work(uid, p):
            results[uid] = server.handle('/validate/fail',
                                         review_bytes(p, uid))

        threads = [threading.Thread(target=work, args=r)
                   for r in requests]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10
        while batcher.queue.depth() < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert batcher.queue.depth() == 3
        handlers.shutdown()
        for t in threads:
            t.join(30)
        assert len(results) == 3
        stats = batcher.stats()
        assert stats['requests'] == 3 and stats['shed_total'] == 0, stats
        # the stopped batcher sheds new submissions to the host loop
        uid, p = 'u-after-stop', pod({}, 'p-after-stop')
        out, status = server.handle_request('/validate/fail',
                                            review_bytes(p, uid))
        assert status == 200
        assert json.loads(out)['response']['allowed'] is False
        assert batcher.sheds.counts().get(
            shed_policy.REASON_SHUTDOWN, 0) >= 1
        expected = sync_responses(server, handlers, requests)
        for r_uid, _p in requests:
            assert results[r_uid] == expected[r_uid]


class _FakeScanner:
    """Scanner WITHOUT per-row admission support: the batcher must key
    its tickets on (serial, canonical admission tuple) — the residual
    fallback path."""

    def __init__(self, fail=False):
        self.fail = fail
        self.calls = []

    def scan(self, resources, contexts=None, admission=None,
             pctx_factory=None):
        self.calls.append(len(resources))
        if self.fail:
            raise RuntimeError('device gone')
        return [[('row', r['metadata']['name'])] for r in resources]


class _RowAdmScanner(_FakeScanner):
    """Scanner WITH per-row admission support: the batcher keys on the
    serial alone and threads each rider's tuple through ``admissions``."""

    def __init__(self):
        super().__init__()
        from kyverno_tpu.compiler.scan import next_scanner_serial
        self.serial = next_scanner_serial()
        self.supports_row_admissions = True
        self.seen_admissions = []

    def scan(self, resources, contexts=None, admission=None,
             pctx_factory=None, admissions=None, old_resources=None):
        self.seen_admissions.append(admissions)
        return super().scan(resources, contexts, admission,
                            pctx_factory)


def _submit(batcher, scanner, name, policies=('pol',)):
    return batcher.submit(
        resource=pod({}, name), context=None, pctx=None,
        admission=({'userInfo': {'username': 'a'}}, [], {}, 'CREATE'),
        scanner=scanner, policies=list(policies))


class TestBatcherUnit:
    def test_scan_error_quarantines_riders_breaker_neutral(self):
        """A persistently failing dispatch quarantines: every rider is
        bisected down to a solo re-dispatch and sheds ``poison_row``
        (row-attributed — each row failed twice in isolation), and one
        all-failed batch fires NEITHER breaker callback (see
        ALL_FAILED_BREAKER_AFTER for the escalation rule)."""
        failures = []
        batcher = AdmissionBatcher(
            window_ms=60_000, max_batch=3, queue_cap=16,
            on_failure=lambda policies, e: failures.append(str(e)))
        try:
            scanner = _FakeScanner(fail=True)
            tickets = [_submit(batcher, scanner, f'p{i}')
                       for i in range(3)]
            rows = [t.wait(shed_after_s=5.0) for t in tickets]
            assert rows == [None, None, None]
            assert all(t.shed_reason == shed_policy.REASON_POISON_ROW
                       for t in tickets)
            counts = batcher.sheds.counts()
            assert counts.get(shed_policy.REASON_POISON_ROW) == 3
            assert shed_policy.REASON_SCAN_ERROR not in counts
            time.sleep(0.05)  # the (absent) verdict would land late
            assert failures == []
        finally:
            batcher.stop(drain=False)

    def test_occupancy_cap_flushes_full_batch(self):
        batcher = AdmissionBatcher(window_ms=60_000, max_batch=4,
                                   queue_cap=64)
        try:
            scanner = _FakeScanner()
            tickets = [_submit(batcher, scanner, f'p{i}')
                       for i in range(4)]
            rows = [t.wait(shed_after_s=10.0) for t in tickets]
            # the window was huge: only the occupancy cap can have
            # flushed this batch
            assert all(r is not None for r in rows)
            assert scanner.calls == [4]
        finally:
            batcher.stop(drain=False)

    def test_residual_scanner_keeps_per_tuple_isolation(self):
        """A scanner without per-row admission support must never mix
        distinct admission tuples in one dispatch (the residual key
        appends the canonical tuple)."""
        batcher = AdmissionBatcher(window_ms=30, queue_cap=64)
        try:
            scanner = _FakeScanner()
            t1 = batcher.submit(
                resource=pod({}, 'a'), context=None, pctx=None,
                admission=({'userInfo': {'username': 'alice'}}, [], {},
                           'CREATE'),
                scanner=scanner, policies=['pol'])
            t2 = batcher.submit(
                resource=pod({}, 'b'), context=None, pctx=None,
                admission=({'userInfo': {'username': 'bob'}}, [], {},
                           'CREATE'),
                scanner=scanner, policies=['pol'])
            assert t1.wait(5.0) is not None
            assert t2.wait(5.0) is not None
            assert scanner.calls == [1, 1]
        finally:
            batcher.stop(drain=False)

    def test_row_admission_scanner_coalesces_distinct_tuples(self):
        """The tentpole contract: with per-row admission support the
        batch key is the scanner serial alone — distinct users share
        ONE dispatch and each rider's tuple rides as a row."""
        batcher = AdmissionBatcher(window_ms=60_000, max_batch=2,
                                   queue_cap=64)
        try:
            scanner = _RowAdmScanner()
            adm_a = ({'userInfo': {'username': 'alice'}}, [], {},
                     'CREATE')
            adm_b = ({'userInfo': {'username': 'bob'}}, [], {},
                     'UPDATE')
            t1 = batcher.submit(resource=pod({}, 'a'), context=None,
                                pctx=None, admission=adm_a,
                                scanner=scanner, policies=['pol'])
            t2 = batcher.submit(resource=pod({}, 'b'), context=None,
                                pctx=None, admission=adm_b,
                                scanner=scanner, policies=['pol'])
            assert t1.wait(5.0) is not None
            assert t2.wait(5.0) is not None
            # the huge window proves only the occupancy cap (2) could
            # have flushed: both tuples rode one dispatch
            assert scanner.calls == [2]
            assert scanner.seen_admissions == [[adm_a, adm_b]]
            stats = batcher.stats()
            assert stats['hetero_dispatches'] == 1
            assert stats['hetero_occupancy_mean'] == 2.0
        finally:
            batcher.stop(drain=False)

    MUTATE_FIELDS = ('mutate_dispatches', 'mutate_occupancy_mean',
                     'mutate_batch_ms', 'mutate_queue_wait_p50_ms',
                     'validate_dispatches', 'validate_occupancy_mean',
                     'validate_batch_ms', 'mutate_device_path_requests',
                     'mutate_host_loop_requests', 'mutate_rows',
                     'mutate_fallback_rows')

    def test_a_fresh_batcher_has_the_fields_of_both_kinds_at_zero(self):
        batcher = AdmissionBatcher(window_ms=5)
        try:
            stats = batcher.stats()
            for field in self.MUTATE_FIELDS:
                assert stats[field] == 0 and \
                    isinstance(stats[field], (int, float)), field
        finally:
            batcher.stop(drain=False)

    def test_dispatches_are_split_by_the_kind_of_scanner_they_served(self):
        """Mutate and validate tickets take turns on the one thread: the
        fields there were keep counting every dispatch, the new ones
        tell the two kinds apart, and ``reset_stats`` clears both."""
        from kyverno_tpu.observability import device as devtel
        from kyverno_tpu.observability.metrics import MetricsRegistry
        devtel.configure(MetricsRegistry())    # dispatches are timed
        batcher = AdmissionBatcher(window_ms=60_000, max_batch=3,
                                   queue_cap=64)
        try:
            validate = _RowAdmScanner()
            mutate = _RowAdmScanner()
            mutate.kind = 'mutate'
            mutate.last_fallback_rows = 2
            tickets = [_submit(batcher, mutate, f'm{i}') for i in range(3)]
            tickets += [_submit(batcher, validate, f'v{i}')
                        for i in range(3)]
            tickets += [_submit(batcher, mutate, f'n{i}') for i in range(3)]
            assert all(t.wait(10.0) is not None for t in tickets)
            batcher.record_mutate_path(True)
            batcher.record_mutate_path(True)
            batcher.record_mutate_path(False)
            stats = batcher.stats()
            assert mutate.calls == [3, 3] and validate.calls == [3]
            assert (stats['dispatches'], stats['requests']) == (3, 9)
            assert stats['occupancy_mean'] == 3.0
            assert stats['mutate_dispatches'] == 2
            assert stats['validate_dispatches'] == 1
            assert stats['mutate_occupancy_mean'] == 3.0
            assert stats['validate_occupancy_mean'] == 3.0
            assert stats['mutate_rows'] == 6
            assert stats['mutate_fallback_rows'] == 4
            assert stats['mutate_device_path_requests'] == 2
            assert stats['mutate_host_loop_requests'] == 1
            assert stats['mutate_batch_ms'] > 0.0
            assert stats['validate_batch_ms'] > 0.0
            assert stats['batch_ms'] * 3 == pytest.approx(
                stats['mutate_batch_ms'] * 2 + stats['validate_batch_ms'])
            assert stats['mutate_queue_wait_p50_ms'] >= 0.0
            batcher.reset_stats()
            stats = batcher.stats()
            assert all(stats[f] == 0 for f in self.MUTATE_FIELDS)
        finally:
            batcher.stop(drain=False)
            devtel.disable()

    def test_canonical_admission_key_coalesces_reordered_lists(self):
        """Equivalent tuples differing only in list order produce one
        residual key (deterministic canonicalization)."""
        batcher = AdmissionBatcher(window_ms=60_000, max_batch=2,
                                   queue_cap=64)
        try:
            scanner = _FakeScanner()  # residual path
            base = {'userInfo': {'username': 'u',
                                 'groups': ['a', 'b']}, 'roles': ['r1',
                                                                  'r2']}
            flip = {'userInfo': {'username': 'u',
                                 'groups': ['b', 'a']}, 'roles': ['r2',
                                                                  'r1']}
            t1 = batcher.submit(resource=pod({}, 'a'), context=None,
                                pctx=None,
                                admission=(base, [], {}, 'CREATE'),
                                scanner=scanner, policies=['pol'])
            t2 = batcher.submit(resource=pod({}, 'b'), context=None,
                                pctx=None,
                                admission=(flip, [], {}, 'CREATE'),
                                scanner=scanner, policies=['pol'])
            assert t1.wait(5.0) is not None
            assert t2.wait(5.0) is not None
            assert scanner.calls == [2]
        finally:
            batcher.stop(drain=False)

    def test_deadline_shed_vs_claim_is_exclusive(self):
        sheds = []
        ticket = Ticket(key='k', resource={}, context=None, pctx=None,
                        admission=(), scanner=None, policies=[],
                        on_shed=sheds.append)
        assert ticket.wait(shed_after_s=0.01) is None
        assert ticket.shed_reason == shed_policy.REASON_DEADLINE
        assert sheds == [shed_policy.REASON_DEADLINE]
        # the loser of the CAS cannot claim a shed ticket
        assert not ticket.claim()

    def test_queue_capacity_and_stop(self):
        q = RequestQueue(capacity=2)
        t1 = Ticket('k', {}, None, None, (), None, [])
        t2 = Ticket('k', {}, None, None, (), None, [])
        q.put(t1)
        q.put(t2)
        with pytest.raises(QueueFull):
            q.put(Ticket('k', {}, None, None, (), None, []))
        # a deadline-shed ticket no longer counts against capacity
        assert t1._try_shed(shed_policy.REASON_DEADLINE)
        q.put(Ticket('k', {}, None, None, (), None, []))
        q.stop()
        with pytest.raises(Stopped):
            q.put(Ticket('k', {}, None, None, (), None, []))

    def test_metrics_emission(self):
        from kyverno_tpu.observability.metrics import (MetricsRegistry,
                                                       set_global_registry)
        from kyverno_tpu.serving.batcher import (BATCH_OCCUPANCY,
                                                 QUEUE_WAIT)
        from kyverno_tpu.serving.shed import ADMISSION_SHED
        registry = MetricsRegistry()
        set_global_registry(registry)
        try:
            batcher = AdmissionBatcher(window_ms=5, queue_cap=8)
            try:
                scanner = _FakeScanner()
                tickets = [_submit(batcher, scanner, f'p{i}')
                           for i in range(2)]
                for t in tickets:
                    assert t.wait(5.0) is not None
                batcher.record_shed(shed_policy.REASON_QUEUE_FULL)
                assert registry.histogram_count(
                    BATCH_OCCUPANCY) >= 1
                assert registry.histogram_count(QUEUE_WAIT) >= 2
                assert registry.counter_value(
                    ADMISSION_SHED,
                    reason=shed_policy.REASON_QUEUE_FULL) == 1
            finally:
                batcher.stop(drain=False)
        finally:
            set_global_registry(None)


# ---------------------------------------------------------------------------
# full-verb batching: UPDATE validate rows and mutate requests ride the
# same queue/coalescing loop (PR 8) — the batch key no longer excludes
# verbs, and the host engine loop stays the bit-identity oracle.

MUTATE_POLICY = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: add-team-label
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: add-team
      match: {any: [{resources: {kinds: [Pod]}}]}
      mutate:
        patchStrategicMerge:
          metadata:
            labels:
              "+(team)": platform
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: stamp-managed
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: stamp
      match: {any: [{resources: {kinds: [Pod]}}]}
      mutate:
        patchesJson6902: |-
          - op: add
            path: /metadata/annotations/managed
            value: kyverno-tpu
"""

# the selector only matches the OLD object of some UPDATE requests —
# the engine's old-match retry must survive batching
LEGACY_POLICY = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: legacy-team
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  validationFailureAction: enforce
  rules:
    - name: legacy-team
      match:
        any:
          - resources:
              kinds: [Pod]
              selector: {matchLabels: {legacy: "yes"}}
      validate:
        message: "legacy pods must be marked migrated"
        pattern:
          metadata:
            labels:
              migrated: "?*"
"""


def update_review_bytes(resource, old_resource, uid):
    return json.dumps({
        'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
        'request': {
            'uid': uid, 'operation': 'UPDATE',
            'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
            'namespace': 'default',
            'name': resource['metadata']['name'],
            'object': resource, 'oldObject': old_resource,
            'userInfo': {'username': 'alice', 'groups': []},
        }}).encode()


@pytest.fixture(scope='module')
def verb_chain():
    """Validate (incl. a selector rule exercising the old-match retry)
    + mutate policies on one compiled chain in batch serving mode."""
    docs = list(yaml.safe_load_all(ENFORCE_POLICY)) + \
        list(yaml.safe_load_all(LEGACY_POLICY)) + \
        list(yaml.safe_load_all(MUTATE_POLICY))
    cache = Cache()
    cache.warm_up([Policy(d) for d in docs if d])
    handlers = ResourceHandlers(cache, configuration=Configuration(),
                                serving_mode='batch')
    server = WebhookServer(handlers, configuration=Configuration())
    enforce = cache.get_policies(pcache.VALIDATE_ENFORCE, 'Pod', 'default')
    assert handlers.wait_device_ready(enforce, timeout=600)
    mut = cache.get_policies(pcache.MUTATE, 'Pod', 'default')
    deadline = time.time() + 120
    scanner = None
    while time.time() < deadline:
        scanner = handlers._device_scanner(mut, kind='mutate')
        if scanner is not None:
            break
        time.sleep(0.02)
    assert scanner is not None and scanner.ok
    yield server, handlers
    handlers.shutdown()


def mixed_verb_requests(n):
    """CREATE/UPDATE mixed validate traffic; some UPDATE rows match the
    legacy selector only through their old object."""
    out = []
    for i in range(n):
        labels = {'team': 'infra'} if i % 2 else {}
        new = pod(dict(labels), f'p{i}')
        if i % 3 == 0:
            old = pod({'legacy': 'yes', **labels}, f'p{i}')
            out.append((f'u{i}', 'UPDATE', new, old))
        elif i % 3 == 1:
            out.append((f'u{i}', 'UPDATE', new, pod(dict(labels), f'p{i}')))
        else:
            out.append((f'u{i}', 'CREATE', new, None))
    return out


def _verb_bytes(entry):
    uid, op, new, old = entry
    if op == 'UPDATE':
        return update_review_bytes(new, old, uid)
    return review_bytes(new, uid)


class TestFullVerbBatching:
    def test_mixed_verb_batched_bit_identity(self, verb_chain):
        """16 threads of UPDATE+CREATE validate traffic: batched
        responses byte-identical to sync, coalescing observed."""
        server, handlers = verb_chain
        handlers._get_batcher().reset_stats()
        requests = mixed_verb_requests(16 * 8)
        results = {}
        errors = []
        barrier = threading.Barrier(16)

        def work(tid):
            barrier.wait()
            for entry in requests[tid * 8:(tid + 1) * 8]:
                try:
                    out, status = server.handle_request(
                        '/validate/fail', _verb_bytes(entry))
                    assert status == 200
                    results[entry[0]] = out
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors
        stats = handlers._get_batcher().stats()
        assert stats['occupancy_mean'] > 1.0, stats
        prior = handlers.serving_mode
        handlers.serving_mode = 'sync'
        try:
            expected = {e[0]: server.handle('/validate/fail',
                                            _verb_bytes(e))
                        for e in requests}
        finally:
            handlers.serving_mode = prior
        for entry in requests:
            assert results[entry[0]] == expected[entry[0]]

    def test_update_old_match_retry_identical_to_host(self, verb_chain):
        """An UPDATE whose old object alone matches the legacy selector
        must deny exactly like the pure host engine loop."""
        server, handlers = verb_chain
        # new passes require-team but is not 'migrated'; only the OLD
        # object carries the legacy selector label, so the rule applies
        # to this UPDATE solely through the old-match retry
        new = pod({'team': 'infra'}, 'retry-pod')
        old = pod({'legacy': 'yes', 'team': 'infra'}, 'retry-pod')
        body = update_review_bytes(new, old, 'u-retry')
        batched = server.handle('/validate/fail', body)
        prior_mode, prior_device = handlers.serving_mode, handlers.device
        handlers.serving_mode = 'sync'
        try:
            synced = server.handle('/validate/fail', body)
            handlers.device = False
            host = server.handle('/validate/fail', body)
        finally:
            handlers.serving_mode, handlers.device = \
                prior_mode, prior_device
        assert batched == synced == host
        assert json.loads(batched)['response']['allowed'] is False
        # the same new object on CREATE passes (selector never matches)
        create = json.loads(server.handle(
            '/validate/fail', review_bytes(new, 'u-retry-create')))
        assert create['response']['allowed'] is True

    def test_batched_mutate_byte_identical_to_host_engine(self,
                                                          verb_chain):
        """Mutate responses through the batched device path are
        byte-identical to the host engine loop, and concurrent mutate
        requests coalesce (occupancy > 1)."""
        server, handlers = verb_chain
        handlers._get_batcher().reset_stats()
        requests = []
        for i in range(48):
            labels = {'team': 'x'} if i % 2 else {}
            new = pod(dict(labels), f'm{i}')
            if i % 3 == 0:
                requests.append((f'mu{i}', 'UPDATE', new,
                                 pod(dict(labels), f'm{i}')))
            else:
                requests.append((f'mu{i}', 'CREATE', new, None))
        results = {}
        errors = []
        barrier = threading.Barrier(12)

        def work(tid):
            barrier.wait()
            for entry in requests[tid * 4:(tid + 1) * 4]:
                try:
                    out, status = server.handle_request(
                        '/mutate', _verb_bytes(entry))
                    assert status == 200
                    results[entry[0]] = out
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors
        stats = handlers._get_batcher().stats()
        assert stats['occupancy_mean'] > 1.0, stats
        # oracle: the pure host engine loop (device mutate off)
        prior = handlers.mutate_device
        handlers.mutate_device = False
        try:
            expected = {e[0]: server.handle('/mutate', _verb_bytes(e))
                        for e in requests}
        finally:
            handlers.mutate_device = prior
        for entry in requests:
            assert results[entry[0]] == expected[entry[0]]
        # and patches actually flowed
        sample = json.loads(results['mu1'])
        assert sample['response'].get('patch')

    def test_shed_to_host_never_500_on_new_verb_paths(
            self, restore_batcher, verb_chain):
        """Overflowing a tiny queue with mixed UPDATE validate + mutate
        traffic sheds to the host loop: all 200s, identical bytes."""
        server, handlers = verb_chain
        handlers._batcher = AdmissionBatcher(
            window_ms=50, queue_cap=2,
            on_success=handlers._batch_scan_ok,
            on_failure=handlers._batch_scan_failed)
        requests = mixed_verb_requests(24)
        statuses = []
        results = {}
        errors = []
        barrier = threading.Barrier(12)

        def work(tid):
            barrier.wait()
            for entry in requests[tid * 2:(tid + 1) * 2]:
                route = '/mutate' if int(entry[0][1:]) % 2 else \
                    '/validate/fail'
                try:
                    out, status = server.handle_request(
                        route, _verb_bytes(entry))
                    statuses.append(status)
                    results[(route, entry[0])] = out
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors
        assert statuses == [200] * len(requests)
        prior_mode = handlers.serving_mode
        prior_mut = handlers.mutate_device
        handlers.serving_mode = 'sync'
        handlers.mutate_device = False
        try:
            for (route, uid), got in results.items():
                entry = next(e for e in requests if e[0] == uid)
                assert got == server.handle(route, _verb_bytes(entry))
        finally:
            handlers.serving_mode = prior_mode
            handlers.mutate_device = prior_mut


# ---------------------------------------------------------------------------
# heterogeneous-traffic batching (PR 10): the batch key is the policy
# set alone — N threads with DISTINCT users/groups/roles + mixed verbs
# coalesce into shared dispatches, each response pinned identical to
# that request's own sync scan (and to the pure host engine loop).

ADMIN_GATE_POLICY = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: admins-only-hetero
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  validationFailureAction: enforce
  rules:
    - name: admins-only
      match:
        any:
          - resources: {kinds: [Pod]}
            subjects:
              - {kind: Group, name: system:masters}
              - {kind: User, name: root-user}
      validate:
        message: "admin-gated pods need a ticket label"
        pattern:
          metadata: {labels: {ticket: "?*"}}
"""


@pytest.fixture(scope='module')
def hetero_chain():
    """Plain + subject-gated validate policies on one batch-mode chain:
    the subject rule's match depends on each request's userInfo, so
    correctness under coalescing requires the per-row admission lanes."""
    docs = list(yaml.safe_load_all(ENFORCE_POLICY)) + \
        list(yaml.safe_load_all(ADMIN_GATE_POLICY))
    cache = Cache()
    cache.warm_up([Policy(d) for d in docs if d])
    handlers = ResourceHandlers(cache, configuration=Configuration(),
                                serving_mode='batch')
    server = WebhookServer(handlers, configuration=Configuration())
    enforce = cache.get_policies(pcache.VALIDATE_ENFORCE, 'Pod', 'default')
    assert handlers.wait_device_ready(enforce, timeout=600)
    yield server, handlers
    handlers.shutdown()


def hetero_requests(n):
    """Mixed users (some admins), mixed verbs, mixed verdicts — every
    request carries a DISTINCT admission tuple."""
    out = []
    for i in range(n):
        user = {'username': f'user-{i}',
                'groups': ['system:authenticated'] +
                          (['system:masters'] if i % 4 == 0 else []) +
                          [f'team-{i % 5}']}
        if i % 7 == 0:
            user = {'username': 'root-user', 'groups': [f'team-{i % 5}']}
        labels = {}
        if i % 2:
            labels['team'] = 'infra'
        if i % 3 == 0:
            labels['ticket'] = f'T-{i}'
        new = pod(dict(labels), f'h{i}')
        if i % 5 == 2:
            out.append((f'h{i}', 'UPDATE', new, pod(dict(labels), f'h{i}'),
                        user))
        else:
            out.append((f'h{i}', 'CREATE', new, None, user))
    return out


def _hetero_bytes(entry):
    uid, op, new, old, user = entry
    if op == 'UPDATE':
        body = json.loads(update_review_bytes(new, old, uid))
        body['request']['userInfo'] = user
        return json.dumps(body).encode()
    return review_bytes(new, uid, user_info=user)


class TestHeterogeneousBatching:
    def test_mixed_tuple_bit_identity_and_occupancy(self, hetero_chain):
        """16 threads × distinct users/groups/verbs in one window:
        occupancy > 1 with heterogeneous dispatches observed, every
        response byte-identical to that request's own sync scan AND to
        the pure host engine loop."""
        server, handlers = hetero_chain
        handlers._get_batcher().reset_stats()
        requests = hetero_requests(16 * 8)
        results = {}
        errors = []
        barrier = threading.Barrier(16)

        def work(tid):
            barrier.wait()
            for entry in requests[tid * 8:(tid + 1) * 8]:
                try:
                    out, status = server.handle_request(
                        '/validate/fail', _hetero_bytes(entry))
                    assert status == 200
                    results[entry[0]] = out
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors
        assert len(results) == len(requests)
        stats = handlers._get_batcher().stats()
        # the tentpole: DISTINCT admission tuples coalesced
        assert stats['occupancy_mean'] > 1.0, stats
        assert stats['hetero_dispatches'] >= 1, stats
        # oracle 1: per-request sync scans (same scanner, occupancy 1)
        prior = handlers.serving_mode
        handlers.serving_mode = 'sync'
        try:
            expected = {e[0]: server.handle('/validate/fail',
                                            _hetero_bytes(e))
                        for e in requests}
        finally:
            handlers.serving_mode = prior
        for entry in requests:
            assert results[entry[0]] == expected[entry[0]], entry[0]
        # oracle 2: the pure host engine loop on a verdict-bearing mix
        prior_device = handlers.device
        handlers.device = False
        try:
            for entry in requests[:24]:
                host = server.handle('/validate/fail',
                                     _hetero_bytes(entry))
                assert results[entry[0]] == host, entry[0]
        finally:
            handlers.device = prior_device

    def test_admin_gate_verdicts_depend_on_row_user(self, hetero_chain):
        """Same pod, different users, one batch window: the subject-
        gated rule must deny only the admin-group rows — per-row lanes,
        not the lead rider's tuple, decide each row."""
        server, handlers = hetero_chain
        doc = pod({'team': 'infra'}, 'gate-pod')  # no ticket label
        admin = {'username': 'boss', 'groups': ['system:masters']}
        human = {'username': 'dev-1', 'groups': ['system:authenticated']}
        results = {}
        barrier = threading.Barrier(2)

        def work(uid, user):
            barrier.wait()
            results[uid] = server.handle(
                '/validate/fail', review_bytes(doc, uid, user_info=user))

        threads = [threading.Thread(target=work, args=a)
                   for a in [('adm', admin), ('hum', human)]]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert json.loads(results['adm'])['response']['allowed'] is False
        assert json.loads(results['hum'])['response']['allowed'] is True
