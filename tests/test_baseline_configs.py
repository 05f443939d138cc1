"""BASELINE.md configs 4 and 5, scaled down for CI.

Config 4: JMESPath-heavy precondition/deny policies — device-vs-host
differential over a mixed pod population, and a floor on how much of
the pack actually compiles to device (the point of the workload).

Config 5: mutate + generate with foreach over a resource dump via
``BatchApplier`` — serial vs process-pool equality, cumulative mutation
semantics vs the engine loop, and the generate URs feeding the real
background controller.
"""

import random

import pytest

import benchlib
from kyverno_tpu.api.policy import load_policies_from_yaml
from kyverno_tpu.compiler.apply import BatchApplier
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.conformance import corpus
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.engine import Engine

mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')


class TestConfig4JMESPathHeavy:
    @pytest.fixture(scope='class')
    def policies(self):
        return benchlib.load_policies(['config4'])

    @pytest.fixture(scope='class')
    def pods(self):
        rng = random.Random(7)
        return [mixed_cluster.make_config4_pod(rng, i) for i in range(160)]

    def test_pack_mostly_compiles(self, policies):
        scanner = BatchScanner(policies)
        n_rules = sum(len(p.rules) for p in policies)
        # the workload exists to exercise device-compiled JMESPath
        # conditions; host fallback for most rules would defeat it
        assert len(scanner.cps.programs) >= n_rules - 1, \
            [(r, err) for _, r, err in scanner.cps.host_rules]

    def test_device_matches_host(self, policies, pods):
        scanner = BatchScanner(policies)
        device = scanner.scan(pods)
        engine = Engine()
        for doc, responses in zip(pods, device):
            by_policy = {r.policy_response.policy_name: r
                         for r in responses}
            for policy in policies:
                host = engine.apply_background_checks(
                    PolicyContext(policy, new_resource=doc))
                dev = by_policy.get(policy.name)
                host_rules = [(r.name, r.status, r.message)
                              for r in host.policy_response.rules]
                dev_rules = [(r.name, r.status, r.message)
                             for r in dev.policy_response.rules] \
                    if dev is not None else []
                assert dev_rules == host_rules, \
                    f'{policy.name} diverged on {doc["metadata"]["name"]}'

    def test_verdict_mix_is_nontrivial(self, policies, pods):
        """The synthetic population must actually trip the JMESPath
        conditions both ways, or the bench measures nothing."""
        scanner = BatchScanner(policies)
        out = scanner.scan(pods)
        statuses = {str(r.status) for rs in out
                    for r in rs for r in r.policy_response.rules}
        assert 'pass' in statuses and 'fail' in statuses and \
            'skip' in statuses


class TestConfig5MutateGenerate:
    @pytest.fixture(scope='class')
    def policies(self):
        return load_policies_from_yaml(corpus.CONFIG5_PACK)

    @pytest.fixture(scope='class')
    def dump(self):
        rng = random.Random(11)
        return [corpus.make_config5_resource(rng, i, mixed_cluster.make_pod)
                for i in range(300)]

    def test_applier_matches_engine_loop(self, policies, dump):
        applier = BatchApplier(policies, processes=0)
        results = applier.apply(dump)
        engine = Engine()
        for doc, result in zip(dump, results):
            patched = doc
            for policy in applier.mutate_policies:
                ctx = PolicyContext(policy, new_resource=patched)
                resp = engine.mutate(ctx)
                if resp.patched_resource is not None:
                    patched = resp.patched_resource
            assert result.patched == patched

    def test_parallel_matches_serial(self, policies, dump):
        applier = BatchApplier(policies, processes=2)
        serial = applier.apply(dump, parallel=False)
        par = applier.apply(dump, parallel=True)
        for s, p in zip(serial, par):
            assert s.patched == p.patched
            assert s.rule_results == p.rule_results
            assert s.ur_specs == p.ur_specs

    def test_mutations_applied(self, policies, dump):
        applier = BatchApplier(policies, processes=0)
        results = applier.apply(dump)
        pods = [(d, r) for d, r in zip(dump, results)
                if d.get('kind') == 'Pod']
        assert pods
        for doc, r in pods:
            labels = r.patched['metadata'].get('labels') or {}
            assert labels.get('managed') == 'true'
            anns = r.patched['metadata'].get('annotations') or {}
            assert anns.get('policy.io/revision') == 'r1'
            for cont in r.patched['spec']['containers']:
                assert cont.get('imagePullPolicy') in \
                    ('IfNotPresent', 'Always')

    def test_foreach_preserves_existing_pull_policy(self, policies):
        doc = {'apiVersion': 'v1', 'kind': 'Pod',
               'metadata': {'name': 'p', 'namespace': 'default'},
               'spec': {'containers': [
                   {'name': 'a', 'image': 'nginx:1',
                    'imagePullPolicy': 'Always'},
                   {'name': 'b', 'image': 'redis:7'}]}}
        applier = BatchApplier(policies, processes=0)
        [r] = applier.apply([doc])
        conts = {c['name']: c for c in r.patched['spec']['containers']}
        assert conts['a']['imagePullPolicy'] == 'Always'
        assert conts['b']['imagePullPolicy'] == 'IfNotPresent'

    def test_generate_urs_feed_background_pipeline(self, policies, dump):
        from kyverno_tpu.background.update_request_controller import \
            UpdateRequestController
        from kyverno_tpu.background.updaterequest import \
            UpdateRequestGenerator
        from kyverno_tpu.dclient.client import FakeClient
        applier = BatchApplier(policies, processes=0)
        results = applier.apply(dump)
        ur_specs = [s for r in results for s in r.ur_specs]
        namespaces = [d for d in dump if d.get('kind') == 'Namespace']
        assert len(ur_specs) == len(namespaces) > 0
        client = FakeClient()
        for ns in namespaces:
            client.create_resource('v1', 'Namespace', '', ns)
        by_name = {p.name: p for p in policies}
        ctrl = UpdateRequestController(client, Engine(),
                                       policy_getter=by_name.get)
        gen = UpdateRequestGenerator(client)
        for spec in ur_specs:
            gen.apply(spec)
        ctrl.process_pending()
        netpols = client.list_resource('networking.k8s.io/v1',
                                       'NetworkPolicy')
        assert len(netpols) == len(namespaces)
        for np_ in netpols:
            assert np_['spec']['policyTypes'] == ['Ingress', 'Egress']
