"""Mutate-existing rules through the device mutate path
(``kyverno_tpu/background/mutate_existing.py`` ``process_pass``): a policy
installed with ``PolicyController.add_policy`` spawns its UpdateRequests,
and ``UpdateRequestController.process_pending`` applies them as one batch,
the policies that lower through the device mutate kernel at the bulk
capacity and the rest on the host engine, in the order and with the bytes
the one-UR-at-a-time host path (``device=False``) gives.

The cell ``mutate_existing_install_100k``'s pack
(``benchmarks/packs/mutate-existing.yaml``), generator
(``generators/existing_cluster.py``) and plain reference
(``benchmarks/reference/mutate_existing.py``) at about 600 resources in 60
namespaces.  CPU-only, tier-1.
"""

import itertools
import json

import pytest

import benchlib
import bytes_mutate
from kyverno_tpu.api.policy import Policy
from kyverno_tpu.background import updaterequest
from kyverno_tpu.background.update_request_controller import \
    UpdateRequestController
from kyverno_tpu.dclient.client import FakeClient
from kyverno_tpu.engine.api import PolicyContext, RuleStatus
from kyverno_tpu.engine.engine import Engine
from kyverno_tpu.mutate import LowerError, compile_mutate_set
from kyverno_tpu.mutate.plan import lower_mutate_rule
from kyverno_tpu.observability import coverage
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability import tracing
from kyverno_tpu.observability.metrics import MetricsRegistry
from kyverno_tpu.policy.controller import PolicyController

PACK = benchlib.load_policies(['mutate-existing'])
generator = benchlib.load_module('generators', 'existing_cluster')
reference = benchlib.load_module('reference', 'mutate_existing')
SEED = 2**31 + 4646
HOST_POLICY = 'label-existing-namespaces'


def cluster(n=600, namespaces=60, seed=SEED):
    return generator.generate(seed, n, namespaces=namespaces)


def install(docs, policies=PACK, device=True, monkeypatch=None):
    """A client holding ``docs``, ``policies`` installed through the policy
    controller and one ``process_pending``; UR names from 1, so that two
    runs take their URs in the same order."""
    monkeypatch.setattr(updaterequest, '_counter', itertools.count(1))
    client = FakeClient()
    for doc in docs:
        client.create_resource(doc['apiVersion'], doc['kind'],
                               doc['metadata'].get('namespace', ''), doc)
    by_name = {p.name: p for p in policies}
    policy_ctrl = PolicyController(client, Engine())
    ur_ctrl = UpdateRequestController(client, Engine(),
                                      policy_getter=by_name.get,
                                      device=device)
    for policy in policies:
        policy_ctrl.add_policy(policy.raw)
    ur_ctrl.process_pending()
    return client, ur_ctrl


def stored(client) -> dict:
    return {key: json.dumps(doc, sort_keys=True, separators=(',', ':'))
            for key, doc in client._store.items()}


def differing(a: dict, b: dict) -> list:
    return sorted(k for k in set(a) | set(b) if a.get(k) != b.get(k))


@pytest.fixture(scope='module')
def docs():
    return cluster()


@pytest.fixture
def telemetry():
    registry = MetricsRegistry()
    devtel.configure(registry)
    coverage.configure(registry)
    mem = tracing.configure()
    yield registry, mem
    tracing.disable()
    coverage.disable()
    devtel.disable()


class TestAgainstTheHostPath:
    def test_every_object_and_ur_equals_the_host_paths_byte_for_byte(
            self, docs, monkeypatch):
        device, _ = install(docs, monkeypatch=monkeypatch)
        host, _ = install(docs, device=False, monkeypatch=monkeypatch)
        got, want = stored(device), stored(host)
        assert differing(got, want) == []
        urs = [json.loads(v) for k, v in got.items()
               if k[1] == 'UpdateRequest']
        assert len(urs) > 500
        assert {u['status']['state'] for u in urs} == {'Completed'}

    def test_every_target_is_what_the_plain_reference_gives(
            self, docs, monkeypatch):
        client, ur_ctrl = install(docs, monkeypatch=monkeypatch)
        before = FakeClient()
        for doc in docs:
            before.create_resource(doc['apiVersion'], doc['kind'],
                                   doc['metadata'].get('namespace', ''), doc)
        final, requests = reference.install(
            [p.raw for p in PACK], list(before._store.values()))
        assert len(final) == len(docs)
        assert [k for k, doc in final.items()
                if reference.comparable(doc) !=
                reference.comparable(client._store[k])] == []
        assert len(ur_ctrl.list_urs()) == len(requests)

    def test_a_pass_of_many_chunks_gives_what_one_chunk_gives(
            self, docs, monkeypatch):
        whole = stored(install(docs, monkeypatch=monkeypatch)[0])
        monkeypatch.setenv('KTPU_SCAN_CHUNK', '128')
        from kyverno_tpu.compiler.shapes import canonical_caps
        assert canonical_caps()[-1] == 128
        chunked = stored(install(docs, monkeypatch=monkeypatch)[0])
        assert differing(whole, chunked) == []


class TestPlacement:
    def test_label_existing_namespaces_alone_stays_on_the_host(self):
        program = compile_mutate_set(PACK, existing=True)
        assert program.device_ok
        placed = {p.policy: (p.placement, p.reason)
                  for p in program.placements}
        assert placed.pop(HOST_POLICY) == (
            coverage.PLACEMENT_HOST, coverage.REASON_UNSUPPORTED_OPERATOR)
        assert set(placed.values()) == {(coverage.PLACEMENT_DEVICE, None)}
        assert [p.policy_name for p in program.programs] == \
            [p.name for p in PACK if p.name != HOST_POLICY]

    def test_admission_keeps_the_set_whole(self):
        """At admission the chain is cumulative: one rule that does not
        lower keeps every policy of the set on the host."""
        program = compile_mutate_set(PACK)
        assert not program.device_ok and not program.programs

    def test_the_pass_counts_paths_and_records_the_placements(
            self, docs, monkeypatch, telemetry):
        registry, mem = telemetry
        client, _ = install(docs, monkeypatch=monkeypatch)
        counts = {path: int(registry.counter_value(
            devtel.MUTATE_EXISTING_TARGETS, path=path))
            for path in ('device', 'fallback', 'host_policy')}
        namespaces = sum(d['kind'] == 'Namespace' for d in docs)
        five = sum(reference.expects_host(d) for d in docs)
        assert five > 0
        assert counts['host_policy'] == namespaces
        assert counts['fallback'] == five
        assert counts['device'] > 5 * namespaces
        block = coverage.bench_block()
        assert block['by_reason']['mutate'] == {
            coverage.REASON_LIST_SHAPE: five}
        assert block['device_rows'] == counts['device']
        stages = {dict(key).get('stage') for key, _n, _t in
                  registry.histogram_series(devtel.SCAN_STAGE_DURATION)}
        assert {'mx_spawn', 'mx_resolve', 'mx_store', 'mutate_match',
                'mutate_encode', 'mutate_eval', 'mutate_decode'} <= stages
        spans = [s for s in mem.spans()
                 if s.name == 'kyverno/background/mutate_existing_pass']
        assert len(spans) == 1
        assert spans[0].attributes['targets'] == sum(counts.values())
        assert spans[0].attributes['urs'] == len(
            [k for k in client._store if k[1] == 'UpdateRequest'])


def _deployment(name, ns, containers):
    return {'apiVersion': 'apps/v1', 'kind': 'Deployment',
            'metadata': {'name': name, 'namespace': ns},
            'spec': {'template': {'spec': {'containers': [
                {'name': f'c{i}', 'image': 'app'}
                for i in range(containers)]}}}}


def _namespace(name):
    return {'apiVersion': 'v1', 'kind': 'Namespace',
            'metadata': {'name': name}}


class TestRows:
    def test_a_five_container_deployment_falls_back_alone(
            self, monkeypatch, telemetry):
        docs = [_namespace('a'), _deployment('d1', 'a', 2),
                _deployment('d5', 'a', 5), _deployment('d3', 'a', 3)]
        device, _ = install(docs, monkeypatch=monkeypatch)
        assert coverage.bench_block()['by_reason']['mutate'] == {
            coverage.REASON_LIST_SHAPE: 1}
        host, _ = install(docs, device=False, monkeypatch=monkeypatch)
        assert differing(stored(device), stored(host)) == []
        five = device._store[('apps/v1', 'Deployment', 'a', 'd5')]
        assert all(c['imagePullPolicy'] == 'IfNotPresent'
                   for c in five['spec']['template']['spec']['containers'])

    def test_a_target_of_three_policies_carries_the_last_ones_patches(
            self, monkeypatch):
        docs = [_namespace('a'), _deployment('d', 'a', 1)]
        client, ur_ctrl = install(docs, monkeypatch=monkeypatch)
        touching = [u for u in ur_ctrl.list_urs()
                    if u.policy_key in ('annotate-existing-deployments',
                                        'retrofit-pull-policy',
                                        'retrofit-seccomp')]
        assert len(touching) == 3
        last = touching[-1].policy_key       # list_urs gives them by name
        patches = json.loads(client._store[
            ('apps/v1', 'Deployment', 'a', 'd')]['metadata']['annotations'][
                'policies.kyverno.io/last-applied-patches'])
        paths = {'annotate-existing-deployments':
                 '/metadata/annotations/policy.io~1revision',
                 'retrofit-pull-policy':
                 '/spec/template/spec/containers/0/imagePullPolicy',
                 'retrofit-seccomp': '/spec/template/spec/securityContext'}
        assert [p['path'] for p in patches] == [paths[last]]
        host, _ = install(docs, device=False, monkeypatch=monkeypatch)
        assert differing(stored(client), stored(host)) == []

    @pytest.mark.parametrize('name, want', [
        ('d-*', ['d-1', 'd-2']), ('d-2', ['d-2']), ('', ['d-1', 'd-2', 'x'])],
        ids=['wildcard', 'exact', 'absent'])
    def test_a_wildcard_an_exact_and_an_absent_name_resolve(
            self, monkeypatch, name, want):
        target = {'apiVersion': 'apps/v1', 'kind': 'Deployment',
                  'namespace': '{{request.object.metadata.name}}'}
        if name:
            target['name'] = name
        policy = Policy({
            'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
            'metadata': {'name': 'stamp'},
            'spec': {'mutateExistingOnPolicyUpdate': True, 'rules': [{
                'name': 'stamp',
                'match': {'any': [{'resources': {'kinds': ['Namespace']}}]},
                'mutate': {'targets': [target], 'patchStrategicMerge': {
                    'metadata': {'labels': {'stamped': 'yes'}}}}}]}})
        docs = [_namespace('a'), _namespace('b'), _deployment('d-1', 'a', 1),
                _deployment('d-2', 'a', 1), _deployment('x', 'a', 1),
                _deployment('d-1', 'b', 1)]
        client, _ = install(docs, [policy], monkeypatch=monkeypatch)
        got = sorted(k[3] for k, doc in client._store.items()
                     if k[2] == 'a' and ((doc['metadata'].get('labels') or {})
                                         .get('stamped') == 'yes'))
        assert got == want
        host, _ = install(docs, [policy], device=False,
                          monkeypatch=monkeypatch)
        assert differing(stored(client), stored(host)) == []

    def test_a_host_policy_that_writes_a_device_target_first(
            self, docs, monkeypatch):
        """A policy the device does not hold writes the Pods a device
        policy patches too, its URs taken in their turn: the device's
        URs over what it wrote go to the host path, and every byte is the
        host path's."""
        team = Policy({
            'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
            'metadata': {'name': 'a-team-label'},
            'spec': {'mutateExistingOnPolicyUpdate': True, 'rules': [{
                'name': 'team',
                'match': {'any': [{'resources': {'kinds': ['Namespace']}}]},
                'mutate': {'targets': [{
                    'apiVersion': 'v1', 'kind': 'Pod',
                    'namespace': '{{request.object.metadata.name}}'}],
                    'patchStrategicMerge': {'metadata': {'labels': {
                        'team': '{{request.object.metadata.name}}'}}}}}]}})
        policies = [team] + PACK
        small = docs[:200]
        device, _ = install(small, policies, monkeypatch=monkeypatch)
        host, _ = install(small, policies, device=False,
                          monkeypatch=monkeypatch)
        assert differing(stored(device), stored(host)) == []


    def test_a_device_that_fails_hands_its_chunk_to_the_host_path(
            self, monkeypatch):
        from kyverno_tpu.mutate.scanner import MutateScanner

        def broken(self, resources):
            raise RuntimeError('device lost')
        docs = cluster(n=120, namespaces=12)
        host, _ = install(docs, device=False, monkeypatch=monkeypatch)
        monkeypatch.setattr(MutateScanner, 'decide', broken)
        device, ur_ctrl = install(docs, monkeypatch=monkeypatch)
        assert differing(stored(device), stored(host)) == []
        assert {u.state for u in ur_ctrl.list_urs()} == {'Completed'}


class TestLowering:
    def rule(self, targets, overlay=None, **mutate):
        return Policy({
            'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
            'metadata': {'name': 'p'},
            'spec': {'rules': [{
                'name': 'r',
                'match': {'any': [{'resources': {'kinds': ['Namespace']}}]},
                'mutate': dict(targets=targets, patchStrategicMerge=overlay
                               or {'metadata': {'labels': {'a': 'b'}}},
                               **mutate)}]}}).rules[0]

    def test_the_triggers_name_and_namespace_lower(self):
        prog = lower_mutate_rule(self.rule([{
            'apiVersion': 'v1', 'kind': 'Pod',
            'namespace': '{{ request.object.metadata.name }}',
            'name': 'x-{{request.object.metadata.namespace}}'}]), 'p',
            existing=True)
        assert [s.path for s in prog.sites] == [('metadata', 'labels', 'a')]

    @pytest.mark.parametrize('targets, overlay', [
        ([{'apiVersion': 'v1', 'kind': 'Pod',
           'namespace': '{{request.object.metadata.labels.team}}'}], None),
        ([{'apiVersion': 'v1', 'kind': 'Pod', 'namespace': 'a',
           'selector': {'matchLabels': {'a': 'b'}}}], None),
        ([{'kind': 'Pod', 'namespace': 'a'}], None),
        ([{'apiVersion': 'v1', 'kind': 'Pod'}], {'metadata': {'annotations': {
            'policies.kyverno.io/last-applied-patches': 'x'}}}),
    ], ids=['other-variable', 'selector', 'no-apiversion',
            'controller-annotation'])
    def test_what_the_controller_cannot_resolve_keeps_the_host(
            self, targets, overlay):
        with pytest.raises(LowerError) as caught:
            lower_mutate_rule(self.rule(targets, overlay), 'p',
                              existing=True)
        assert caught.value.reason == coverage.REASON_UNSUPPORTED_OPERATOR

    def test_at_admission_variables_in_targets_keep_the_host(self):
        rule = self.rule([{'apiVersion': 'v1', 'kind': 'Pod',
                           'namespace': '{{request.object.metadata.name}}'}])
        with pytest.raises(LowerError) as caught:
            lower_mutate_rule(rule, 'p')
        assert caught.value.reason == coverage.REASON_UNSUPPORTED_OPERATOR
        static = self.rule([{'apiVersion': 'v1', 'kind': 'Pod',
                             'namespace': 'a'}])
        assert lower_mutate_rule(static, 'p').sites

    def test_a_foreach_on_a_target_keeps_the_host(self):
        rule = self.rule([{'apiVersion': 'v1', 'kind': 'Pod'}], None,
                         foreach=[{'list': 'request.object.spec.containers',
                                   'patchStrategicMerge': {}}])
        rule.raw['mutate'].pop('patchStrategicMerge')
        with pytest.raises(LowerError):
            lower_mutate_rule(rule, 'p', existing=True)


class TestSpawn:
    def test_the_sieve_gives_the_engines_triggers(self, docs):
        client = FakeClient()
        for doc in docs:
            client.create_resource(doc['apiVersion'], doc['kind'],
                                   doc['metadata'].get('namespace', ''), doc)
        ctrl = PolicyController(client, Engine())
        for policy in PACK:
            triggers = ctrl._triggers(policy)
            assert triggers
            sieved = ctrl._applied(policy, triggers)
            engine = [any(r.status == RuleStatus.PASS for r in
                          Engine().filter_background_rules(PolicyContext(
                              policy, new_resource=t.obj))
                          .policy_response.rules) for t in triggers]
            assert sieved == engine and all(sieved)

    def test_a_rule_the_sieve_cannot_decide_asks_the_engine(self):
        policy = Policy({
            'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
            'metadata': {'name': 'named'},
            'spec': {'rules': [{
                'name': 'r',
                'match': {'any': [{'resources': {'kinds': ['Namespace'],
                                                 'names': ['a']}}]},
                'mutate': {'targets': [{'apiVersion': 'v1', 'kind': 'Pod'}],
                           'patchStrategicMerge': {'metadata': {
                               'labels': {'a': 'b'}}}}}]}})
        client = FakeClient()
        for name in ('a', 'b'):
            client.create_resource('v1', 'Namespace', '', _namespace(name))
        ctrl = PolicyController(client, Engine())
        assert ctrl._applied(policy, ctrl._triggers(policy)) == [True, False]
        ctrl.add_policy(policy.raw)
        urs = client.list_resource('kyverno.io/v1beta1', 'UpdateRequest',
                                   'kyverno')
        assert [u['spec']['resource']['name'] for u in urs] == ['a']

    def test_a_second_install_reuses_the_pending_urs(self, monkeypatch):
        monkeypatch.setattr(updaterequest, '_counter', itertools.count(1))
        client = FakeClient()
        for doc in [_namespace('a'), _deployment('d', 'a', 1)]:
            client.create_resource(doc['apiVersion'], doc['kind'],
                                   doc['metadata'].get('namespace', ''), doc)
        ctrl = PolicyController(client, Engine())
        for _ in range(2):
            for policy in PACK:
                ctrl.add_policy(policy.raw)
        urs = client.list_resource('kyverno.io/v1beta1', 'UpdateRequest',
                                   'kyverno')
        assert len(urs) == len(PACK)
        assert {u['status']['state'] for u in urs} == {'Pending'}


def test_the_bytes_of_one_dispatch_at_the_bulk_capacity():
    """``benchmarks/bytes_mutate.py`` at capacity 8,192, the dispatch of a
    mutate-existing pass: every lane of the pack's four device rules."""
    from kyverno_tpu.mutate.encode import encode_mutate_batch
    program = compile_mutate_set(PACK, existing=True)
    docs = cluster(n=20, namespaces=2)
    lanes = encode_mutate_batch(docs, program, padded_n=8192)
    told = bytes_mutate.describe(lanes, len(program.programs))
    sites, width = program.n_sites, lanes['sbytes'].shape[2]
    assert told['capacity'] == 8192
    assert told['lane_bytes'] == \
        8192 * sites * (1 + 1 + 8 + 1 + 4 + width) + \
        8192 * (1 + 4 * program.n_list_columns)
    assert told['output_bytes'] == 8192 * 4 * 10


class TestTheCellsCheck:
    """The cell's driver (``benchmarks/drivers/background_mutate_existing.py``)
    at its rehearsal sizes, in this process.  A pass takes its URs in the
    order of their names, so UR names that cross a power of ten (``ur-9999``
    sorts after ``ur-10000``) list out of creation order: the check's
    one-UR-at-a-time replay of a sample has to take the run's own URs, in
    the run's order, or a target that several policies touch carries
    another policy's patches in its annotation there."""

    @pytest.fixture(scope='class')
    def driver(self):
        config = benchlib.load_data('configs', 'mutate-existing-100k')
        traffic = benchlib.load_data('traffic', 'policy_install')
        config = benchlib.overlay(config, config['rehearse'])
        traffic = benchlib.overlay(traffic, traffic['rehearse'])
        patch = pytest.MonkeyPatch()
        patch.setattr(updaterequest, '_counter', itertools.count(9_800))
        registry = benchlib.program_telemetry()
        made = benchlib.load_module('drivers', config['entry']).Driver(
            config=config, traffic=traffic, seed=2**31 + 4647, seconds=30.0,
            platform='cpu', registry=registry)
        try:
            made.setup()
            made.measure()
            yield made
        finally:
            from kyverno_tpu.observability import executables as exectel
            from kyverno_tpu.observability.metrics import \
                set_global_registry
            made.close()
            devtel.disable()
            coverage.disable()
            exectel.disable()
            set_global_registry(None)
            patch.undo()

    def test_names_that_cross_a_power_of_ten_check_correct(self, driver):
        names = [u.name for u in driver.ur_ctrl.list_urs()]
        assert names != sorted(names, key=lambda name: int(name[3:]))
        driver.failed = 0
        assert driver.check() == [] and driver.failed == 0

    def test_a_spoiled_target_is_not_correct(self, driver):
        [key] = [k for k, doc in driver.client._store.items()
                 if k[1] == 'Deployment'][:1]
        doc = driver.client._store[key]
        saved = json.loads(json.dumps(doc))
        doc['metadata'].setdefault('labels', {})['spoiled'] = 'yes'
        try:
            driver.failed = 0
            problems = driver.check()
        finally:
            driver.client._store[key] = saved
        assert problems and driver.failed > 0
