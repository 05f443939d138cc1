"""Device-side mutate (kyverno_tpu/mutate/): lowering, kernel
decisions, and the bit-identity contract against the host engine.

The host mutate chain is the oracle: every device-decided row must be
byte-identical to what the engine loop would have produced — statuses,
messages, patches, and the patched document — and every row the device
cannot decide must FALLBACK to that same engine with its reason on the
coverage ledger.  CPU-only, tier-1.
"""

import json

import pytest

from kyverno_tpu.api.policy import Policy
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.engine import Engine
from kyverno_tpu.mutate import (LowerError, MutateScanner,
                                compile_mutate_set, lower_mutate_rule)
from kyverno_tpu.mutate.encode import encode_mutate_batch, exact_milli
from kyverno_tpu.mutate.kernel import (MUT_FALLBACK, MUT_PASS, MUT_SKIP,
                                       MutateKernel)
from kyverno_tpu.observability import coverage


def policy(name, rule):
    return Policy({'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
                   'metadata': {'name': name},
                   'spec': {'rules': [rule]}})


def sm_policy(name, overlay, rule_name='r'):
    return policy(name, {
        'name': rule_name,
        'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
        'mutate': {'patchStrategicMerge': overlay}})


def j6_policy(name, ops, rule_name='r'):
    return policy(name, {
        'name': rule_name,
        'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
        'mutate': {'patchesJson6902': json.dumps(ops)}})


def pod(i=0, **over):
    doc = {'apiVersion': 'v1', 'kind': 'Pod',
           'metadata': {'name': f'p{i}', 'namespace': 'default'},
           'spec': {'containers': [{'name': 'c', 'image': 'nginx'}]}}
    doc.update(over)
    return doc


def host_chain(policies, doc):
    """The handler's cumulative host mutate loop: ordered
    (policy_name, cells) steps + the final patched document."""
    engine = Engine()
    pctx = PolicyContext(None, new_resource=json.loads(json.dumps(doc)))
    steps = []
    for pol in policies:
        ctx = pctx.copy()
        ctx.policy = pol
        er = engine.mutate(ctx)
        steps.append((pol.name, er))
        if not er.is_successful():
            break
        pctx = pctx.copy()
        pctx.new_resource = er.patched_resource or pctx.new_resource
        pctx.json_context.add_resource(pctx.new_resource)
    return steps, pctx.new_resource


def cells(er):
    return [(r.name, str(r.status), r.message, r.patches)
            for r in er.policy_response.rules]


def assert_identical(policies, docs):
    scanner = MutateScanner(policies)
    assert scanner.ok, [
        (p.rule, p.reason, p.detail) for p in scanner.program.placements]
    rows = scanner.scan([json.loads(json.dumps(d)) for d in docs])
    for doc, (steps, patched) in zip(docs, rows):
        h_steps, h_patched = host_chain(policies, doc)
        # Python semantic equality, the established applier contract:
        # the compiled host fast path (mutate_compile) leaves a leaf
        # whose live value ==-equals the patch constant untouched
        # (3.0 stays 3.0 under an overlay of 3), and generate_patches
        # agrees, so patches/statuses/messages are exact either way
        assert patched == h_patched
        assert len(steps) == len(h_steps)
        for (dpol, der), (hname, her) in zip(steps, h_steps):
            assert dpol.name == hname
            assert cells(der) == cells(her)
    return scanner


# ---------------------------------------------------------------------------
# lowering


class TestLowering:
    def test_strategic_merge_lowers_to_edit_sites(self):
        p = sm_policy('p', {'metadata': {'labels': {'+(team)': 'x'}},
                            'spec': {'dnsPolicy': 'ClusterFirst'}})
        prog = lower_mutate_rule(p.rules[0], 'p')
        assert prog.kind == 'strategic'
        by_path = {s.path: s for s in prog.sites}
        assert by_path[('metadata', 'labels', 'team')].add_only
        assert not by_path[('spec', 'dnsPolicy')].add_only

    def test_json6902_replace_guard(self):
        p = j6_policy('p', [
            {'op': 'add', 'path': '/metadata/labels/a', 'value': 'x'},
            {'op': 'replace', 'path': '/spec/dnsPolicy', 'value': 'None'}])
        prog = lower_mutate_rule(p.rules[0], 'p')
        assert prog.kind == 'json6902'
        by_path = {s.path: s for s in prog.sites}
        assert not by_path[('metadata', 'labels', 'a')].replace
        assert by_path[('spec', 'dnsPolicy')].replace

    @pytest.mark.parametrize('rule,reason', [
        ({'name': 'r', 'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
          'context': [{'name': 'c', 'configMap': {'name': 'x'}}],
          'mutate': {'patchStrategicMerge': {'metadata': {}}}},
         coverage.REASON_API_CALL),
        ({'name': 'r', 'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
          'preconditions': {'all': []},
          'mutate': {'patchStrategicMerge': {'metadata': {}}}},
         coverage.REASON_UNSUPPORTED_OPERATOR),
        ({'name': 'r', 'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
          'mutate': {'foreach': [{'list': 'request.object.spec.containers',
                                  'patchStrategicMerge': {}}]}},
         coverage.REASON_UNSUPPORTED_OPERATOR),
        ({'name': 'r', 'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
          'mutate': {'targets': [{'kind': 'ConfigMap'}],
                     'patchStrategicMerge': {'metadata': {}}}},
         coverage.REASON_HOST_CLOSURE),
        # roles make the match non-simple: the cumulative chain
        # re-matches per policy, so only kind/ns/op matches lower
        ({'name': 'r', 'match': {'any': [{'subjects': [
            {'kind': 'User', 'name': 'bob'}]}]},
          'mutate': {'patchStrategicMerge': {'metadata': {}}}},
         coverage.REASON_UNSUPPORTED_OPERATOR),
        # null overlay values are RFC-7386 deletes
        ({'name': 'r', 'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
          'mutate': {'patchStrategicMerge': {
              'metadata': {'labels': {'drop-me': None}}}}},
         coverage.REASON_UNSUPPORTED_OPERATOR),
        # variables leave the static vocabulary
        ({'name': 'r', 'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
          'mutate': {'patchStrategicMerge': {
              'metadata': {'labels': {'a': '{{request.object.kind}}'}}}}},
         coverage.REASON_UNSUPPORTED_OPERATOR),
        # edits to identity fields could flip later rules' matches
        ({'name': 'r', 'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
          'mutate': {'patchStrategicMerge': {
              'metadata': {'namespace': 'prod'}}}},
         coverage.REASON_UNSUPPORTED_OPERATOR),
    ])
    def test_unlowerable_rules_carry_reasons(self, rule, reason):
        p = policy('p', rule)
        with pytest.raises(LowerError) as ei:
            lower_mutate_rule(p.rules[0], 'p')
        assert ei.value.reason == reason

    def test_set_is_all_or_nothing(self):
        """One unlowerable rule places the whole set on the host (the
        cumulative chain invalidates original-document decisions)."""
        good = sm_policy('good', {'metadata': {'labels': {'a': 'x'}}})
        bad = policy('bad', {
            'name': 'f',
            'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
            'mutate': {'foreach': [{
                'list': 'request.object.spec.containers',
                'patchStrategicMerge': {}}]}})
        prog = compile_mutate_set([good, bad])
        assert not prog.device_ok
        by_rule = {(p.policy, p.rule): p for p in prog.placements}
        assert by_rule[('good', 'r')].placement == coverage.PLACEMENT_HOST
        assert by_rule[('good', 'r')].reason == \
            coverage.REASON_POLICY_COUPLING
        assert by_rule[('bad', 'f')].reason == \
            coverage.REASON_UNSUPPORTED_OPERATOR

    def test_overlapping_edit_sites_conflict(self):
        a = sm_policy('a', {'spec': {'dnsPolicy': 'ClusterFirst'}})
        b = sm_policy('b', {'spec': {'dnsPolicy': 'None'}})
        prog = compile_mutate_set([a, b])
        assert not prog.device_ok
        reasons = {p.reason for p in prog.placements}
        assert coverage.REASON_SITE_CONFLICT in reasons

    def test_prefix_overlap_conflicts_too(self):
        # one rule writes under spec/a, another writes spec/a itself
        a = sm_policy('a', {'spec': {'a': {'b': 'x'}}})
        b = j6_policy('b', [{'op': 'add', 'path': '/spec/a', 'value': 'y'}])
        prog = compile_mutate_set([a, b])
        assert not prog.device_ok

    def test_apply_rules_one_couples(self):
        p = Policy({'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
                    'metadata': {'name': 'one'},
                    'spec': {'applyRules': 'One', 'rules': [
                        {'name': 'r1',
                         'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
                         'mutate': {'patchStrategicMerge': {
                             'metadata': {'labels': {'a': 'x'}}}}},
                        {'name': 'r2',
                         'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
                         'mutate': {'patchStrategicMerge': {
                             'metadata': {'labels': {'b': 'y'}}}}}]}})
        prog = compile_mutate_set([p])
        assert not prog.device_ok
        assert all(pl.reason == coverage.REASON_POLICY_COUPLING
                   for pl in prog.placements)


# ---------------------------------------------------------------------------
# kernel decisions


class TestKernel:
    def _one(self, site_policy, doc):
        prog = compile_mutate_set([site_policy])
        assert prog.device_ok
        kernel = MutateKernel(prog)
        lanes = encode_mutate_batch([doc], prog)
        status, edits, reason = kernel(lanes)
        return int(status[0, 0]), int(edits[0, 0]), int(reason[0, 0])

    def test_missing_leaf_applies(self):
        st, ed, _ = self._one(
            sm_policy('p', {'spec': {'dnsPolicy': 'ClusterFirst'}}), pod())
        assert st == MUT_PASS and ed == 1

    def test_equal_value_skips(self):
        st, ed, _ = self._one(
            sm_policy('p', {'spec': {'dnsPolicy': 'ClusterFirst'}}),
            pod(spec={'dnsPolicy': 'ClusterFirst'}))
        assert st == MUT_SKIP and ed == 0

    def test_add_only_skips_present(self):
        st, _, _ = self._one(
            sm_policy('p', {'metadata': {'labels': {'+(t)': 'x'}}}),
            pod(metadata={'name': 'p', 'labels': {'t': 'other'}}))
        assert st == MUT_SKIP

    def test_non_map_intermediate_falls_back(self):
        st, _, rc = self._one(
            sm_policy('p', {'spec': {'a': {'b': 'x'}}}),
            pod(spec={'a': 'not-a-map'}))
        assert st == MUT_FALLBACK and rc != 0

    def test_replace_missing_falls_back(self):
        st, _, _ = self._one(
            j6_policy('p', [{'op': 'replace', 'path': '/spec/tier',
                             'value': 'gold'}]), pod())
        assert st == MUT_FALLBACK

    def test_numeric_outside_milli_window_undecidable(self):
        # 1e300 cannot ride the exact i64 milli lane; equality with the
        # numeric patch constant is undecidable on device
        st, _, _ = self._one(
            sm_policy('p', {'spec': {'replicas': 3}}),
            pod(spec={'replicas': 1e300}))
        assert st == MUT_FALLBACK

    def test_exact_milli_window(self):
        assert exact_milli(True) == 1000
        assert exact_milli(3) == 3000
        assert exact_milli(0.25) == 250
        assert exact_milli(float('inf')) is None
        assert exact_milli(0.1234567) is None  # sub-milli precision
        assert exact_milli((1 << 62)) is None  # overflows ×1000


# ---------------------------------------------------------------------------
# bit-identity against the host engine


class TestBitIdentity:
    def test_strategic_and_json6902_matrix(self):
        policies = [
            sm_policy('labels', {'metadata': {'labels': {
                '+(team)': 'platform', 'stage': 'prod'}}}),
            sm_policy('dns', {'spec': {'dnsPolicy': 'ClusterFirst',
                                       '+(enableServiceLinks)': False}}),
            j6_policy('ann', [
                {'op': 'add', 'path': '/metadata/annotations/managed',
                 'value': 'yes'}]),
        ]
        docs = [
            pod(0),
            pod(1, metadata={'name': 'p1', 'namespace': 'default',
                             'labels': {'team': 'blue', 'stage': 'dev'}}),
            pod(2, metadata={'name': 'p2', 'namespace': 'default',
                             'annotations': {'managed': 'yes'}}),
            pod(3, spec={'dnsPolicy': 'ClusterFirst',
                         'enableServiceLinks': True}),
            pod(4, metadata={'name': 'p4', 'namespace': 'default',
                             'labels': {'stage': 'prod'},
                             'annotations': {'other': 'x'}}),
        ]
        assert_identical(policies, docs)

    def test_fallback_rows_rerun_host_engine(self):
        """A row the kernel cannot decide reruns the faulting policy —
        and every later one — on the engine; output stays identical."""
        policies = [
            j6_policy('rep', [{'op': 'replace', 'path': '/spec/tier',
                               'value': 'gold'}]),
            sm_policy('after', {'metadata': {'labels': {'a': 'x'}}}),
        ]
        docs = [pod(0, spec={'tier': 'bronze'}),   # replace applies
                pod(1)]                            # path missing: FALLBACK
        scanner = assert_identical(policies, docs)
        # the fallback row's engine rerun produced a FAIL on the host
        steps, _ = scanner.scan([json.loads(json.dumps(docs[1]))])[0]
        assert not steps[0][1].is_successful()

    def test_non_map_intermediate_row_identical(self):
        policies = [sm_policy('deep', {'spec': {'a': {'b': 'x'}}})]
        assert_identical(policies, [pod(0, spec={'a': 'scalar'}),
                                    pod(1, spec={'a': {'b': 'x'}}),
                                    pod(2, spec={'a': {'b': 'y'}}),
                                    pod(3, spec={})])

    def test_numeric_and_bool_values_identical(self):
        policies = [sm_policy('num', {'spec': {
            'replicas': 3, '+(hostNetwork)': False}})]
        assert_identical(policies, [
            pod(0, spec={'replicas': 3}),
            pod(1, spec={'replicas': 4}),
            pod(2, spec={'replicas': 3.0}),   # 3.0 == 3 in the milli lane
            pod(3, spec={'hostNetwork': True}),
            pod(4),
        ])

    def test_device_decode_byte_identical_to_host_applier(self):
        """The decode stage IS the compiled host applier: for every row
        the device decides, the patched JSON must be byte-identical to
        ``compile_strategic_merge(...).apply`` on the same document —
        including the numeric-tower case where the applier deliberately
        leaves an ==-equal leaf untouched."""
        from kyverno_tpu.compiler.mutate_compile import \
            compile_strategic_merge
        overlay = {'spec': {'replicas': 3, 'hostNetwork': False}}
        cm = compile_strategic_merge(overlay, 'r', 'num')
        scanner = MutateScanner([sm_policy('num', overlay)])
        assert scanner.ok
        docs = [pod(0, spec={'replicas': 3.0}),
                pod(1, spec={'replicas': 7}),
                pod(2, spec={'replicas': 3, 'hostNetwork': False})]
        rows = scanner.scan([json.loads(json.dumps(d)) for d in docs])
        for doc, (steps, patched) in zip(docs, rows):
            result = cm.apply(json.loads(json.dumps(doc)))
            _status, _msg, changed, host_doc = result
            if changed:
                assert json.dumps(patched, sort_keys=True) == \
                    json.dumps(host_doc, sort_keys=True)
            else:
                assert json.dumps(patched, sort_keys=True) == \
                    json.dumps(doc, sort_keys=True)

    def test_unmatched_namespace_policy_skips(self):
        ns_pol = Policy({'apiVersion': 'kyverno.io/v1', 'kind': 'Policy',
                         'metadata': {'name': 'nsp', 'namespace': 'other'},
                         'spec': {'rules': [{
                             'name': 'r',
                             'match': {'any': [{'resources': {
                                 'kinds': ['Pod']}}]},
                             'mutate': {'patchStrategicMerge': {
                                 'metadata': {'labels': {'x': 'y'}}}}}]}})
        assert_identical([ns_pol], [pod(0)])


# ---------------------------------------------------------------------------
# coverage ledger attribution


class TestCoverageAttribution:
    @pytest.fixture(autouse=True)
    def ledger(self):
        from kyverno_tpu.observability.metrics import MetricsRegistry
        led = coverage.configure(MetricsRegistry())
        yield led
        coverage.disable()

    def test_device_rows_land_as_mutate_path(self, ledger):
        scanner = MutateScanner([
            sm_policy('p', {'metadata': {'labels': {'a': 'x'}}})])
        scanner.scan([pod(0)])
        report = ledger.report()
        recs = [r for r in report['rules'] if r['path'] == 'mutate']
        assert recs and recs[0]['device_rows'] >= 1

    def test_fallback_attributed_with_reason(self, ledger):
        scanner = MutateScanner([
            j6_policy('rep', [{'op': 'replace', 'path': '/spec/tier',
                               'value': 'gold'}])])
        scanner.scan([pod(0)])
        report = ledger.report()
        assert report['fallbacks'].get('mutate', {}).get(
            coverage.REASON_REPLACE_PATH_MISSING, 0) >= 1

    def test_undecidable_reason_recorded(self, ledger):
        scanner = MutateScanner([
            sm_policy('num', {'spec': {'replicas': 3}})])
        scanner.scan([pod(0, spec={'replicas': 1e300})])
        report = ledger.report()
        assert report['fallbacks'].get('mutate', {}).get(
            coverage.REASON_PATCH_UNDECIDABLE, 0) >= 1

    def test_unlowered_set_placements_recorded(self, ledger):
        a = sm_policy('a', {'spec': {'dnsPolicy': 'ClusterFirst'}})
        b = sm_policy('b', {'spec': {'dnsPolicy': 'None'}})
        scanner = MutateScanner([a, b])
        assert not scanner.ok
        report = ledger.report()
        hosts = [r for r in report['rules'] if r['path'] == 'mutate']
        assert hosts and all(
            r['placement'] == coverage.PLACEMENT_HOST for r in hosts)
        assert {r['reason'] for r in hosts} == \
            {coverage.REASON_SITE_CONFLICT}


# ---------------------------------------------------------------------------
# webhook integration (KTPU_MUTATE_DEVICE)


class TestWebhookIntegration:
    @pytest.fixture(scope='class')
    def chain(self):
        from kyverno_tpu.policycache.cache import Cache
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        from kyverno_tpu.webhooks.server import WebhookServer
        pack = [
            sm_policy('add-labels', {'metadata': {'labels': {
                '+(team)': 'platform'}}}),
            j6_policy('ann', [{'op': 'add',
                               'path': '/metadata/annotations/m',
                               'value': 'y'}]),
        ]
        cache = Cache()
        cache.warm_up(pack)
        handlers = ResourceHandlers(cache)
        server = WebhookServer(handlers)
        yield server, handlers
        handlers.shutdown()

    def _review(self, doc, uid, op='CREATE'):
        return json.dumps({
            'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
            'request': {
                'uid': uid, 'operation': op,
                'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
                'namespace': 'default',
                'name': doc['metadata']['name'], 'object': doc,
                'userInfo': {'username': 'alice'}}}).encode()

    def test_device_mutate_bytes_equal_host_loop(self, chain):
        server, handlers = chain
        from kyverno_tpu.policycache import cache as pcache
        mut = handlers.cache.get_policies(pcache.MUTATE, 'Pod', 'default')
        deadline = __import__('time').time() + 120
        while __import__('time').time() < deadline:
            sc = handlers._device_scanner(mut, kind='mutate')
            if sc is not None:
                break
            __import__('time').sleep(0.02)
        assert sc is not None and sc.ok
        docs = [pod(0), pod(1, metadata={
            'name': 'p1', 'namespace': 'default',
            'labels': {'team': 'red'}, 'annotations': {'m': 'y'}})]
        for op in ('CREATE', 'UPDATE'):
            for i, doc in enumerate(docs):
                handlers.mutate_device = True
                dev = server.handle('/mutate',
                                    self._review(doc, f'd{op}{i}', op))
                handlers.mutate_device = False
                host = server.handle('/mutate',
                                     self._review(doc, f'd{op}{i}', op))
                handlers.mutate_device = True
                assert dev == host

    def test_knob_off_serves_host_loop(self, chain):
        _server, handlers = chain
        handlers.mutate_device = False
        try:
            assert handlers._device_mutate_steps(
                {'operation': 'CREATE'}, None, ['x']) is None
        finally:
            handlers.mutate_device = True

    def test_delete_keeps_host_loop(self, chain):
        _server, handlers = chain
        assert handlers._device_mutate_steps(
            {'operation': 'DELETE'}, None, ['x']) is None


# ---------------------------------------------------------------------------
# element sites: one map anchored (name): "*" | "?*" over a list of maps


def pull_policy(pattern='?*', name='pull'):
    return sm_policy(name, {'spec': {'containers': [
        {'(name)': pattern, 'imagePullPolicy': 'Always'}]}})


def requests_policy(pattern='*', name='res'):
    return sm_policy(name, {'spec': {'containers': [
        {'(name)': pattern, 'resources': {'requests': {
            '+(memory)': '100Mi', '+(cpu)': '100m'}}}]}})


def containers_pod(i, containers, **spec):
    return pod(i, spec=dict(spec, containers=containers))


def named(n, **fields):
    return [dict({'name': f'c{j}', 'image': 'nginx'}, **fields)
            for j in range(n)]


ELEMENT_DOCS = {
    **{f'{n}-containers': containers_pod(n, named(n)) for n in range(7)},
    'leaf-present-equal': containers_pod(
        10, named(2, imagePullPolicy='Always')),
    'leaf-present-other': containers_pod(
        11, named(3, imagePullPolicy='IfNotPresent')),
    'leaf-null': containers_pod(12, named(1, imagePullPolicy=None)),
    'requests-partly-there': containers_pod(13, [
        {'name': 'a', 'resources': {'requests': {'cpu': '1'}}},
        {'name': 'b', 'resources': {'limits': {'cpu': '2'}}},
        {'name': 'c', 'resources': {'requests': {
            'memory': '1Gi', 'cpu': '1'}}},
        {'name': 'd', 'resources': None}]),
    'requests-not-a-map': containers_pod(14, [
        {'name': 'a', 'resources': {'requests': 'lots'}}]),
    'resources-not-a-map': containers_pod(15, [
        {'name': 'a', 'resources': 'lots'}, {'name': 'b'}]),
    'missing-name': containers_pod(16, [
        {'image': 'x'}, {'name': 'b', 'image': 'y'}]),
    'empty-name': containers_pod(17, [
        {'name': '', 'image': 'x'}, {'name': 'b', 'image': 'y'}]),
    'null-name': containers_pod(18, [
        {'name': None, 'image': 'x'}, {'name': 'b'}]),
    'number-for-a-name': containers_pod(19, [{'name': 7}, {'name': 'b'}]),
    'two-of-one-name': containers_pod(20, [
        {'name': 'a'}, {'name': 'a', 'imagePullPolicy': 'Never'},
        {'name': 'b'}]),
    'element-not-a-map': containers_pod(21, [{'name': 'a'}, 'stray']),
    'element-null': containers_pod(22, [None, {'name': 'a'}]),
    'containers-not-a-list': pod(23, spec={'containers': {'name': 'a'}}),
    'containers-a-string': pod(24, spec={'containers': 'a'}),
    'containers-null': pod(25, spec={'containers': None}),
    'no-containers': pod(26, spec={'dnsPolicy': 'Default'}),
    'spec-not-a-map': pod(27, spec='nothing'),
    'no-spec': {'apiVersion': 'v1', 'kind': 'Pod',
                'metadata': {'name': 'p28', 'namespace': 'default'}},
}


class TestElementSites:
    def test_lowers_to_a_site_per_leaf_and_slot(self):
        from kyverno_tpu.mutate.plan import MAX_ELEMENTS, split_element_path
        prog = lower_mutate_rule(requests_policy().rules[0], 'res')
        assert [s.path for s in prog.sites] == [
            ('spec', 'containers', i, 'resources', 'requests', leaf)
            for i in range(MAX_ELEMENTS) for leaf in ('memory', 'cpu')]
        assert all(s.add_only and not s.replace for s in prog.sites)
        assert split_element_path(prog.sites[3].path) == (
            ('spec', 'containers'), 1, ('resources', 'requests', 'cpu'))
        assert split_element_path(('spec', 'dnsPolicy')) is None
        assert compile_mutate_set([requests_policy()]).lists == \
            [('spec', 'containers')]

    @pytest.mark.parametrize('pattern', ['*', '?*'])
    @pytest.mark.parametrize('case', sorted(ELEMENT_DOCS))
    def test_byte_identical_to_the_host_engine(self, pattern, case):
        """Both container policies, then a plain one: the chain the
        defaults pack runs, over one shape of container list."""
        policies = [pull_policy(pattern), requests_policy(pattern),
                    sm_policy('dns', {'spec': {
                        'dnsPolicy': 'ClusterFirst'}})]
        assert_identical(policies, [ELEMENT_DOCS[case]])

    @pytest.mark.parametrize('case, status, reason', [
        ('0-containers', MUT_SKIP, 0),
        ('4-containers', MUT_PASS, 0),
        ('5-containers', MUT_FALLBACK, 4),
        ('6-containers', MUT_FALLBACK, 4),
        ('leaf-present-equal', MUT_SKIP, 0),
        ('missing-name', MUT_PASS, 0),
        ('empty-name', MUT_PASS, 0),
        ('number-for-a-name', MUT_FALLBACK, 4),
        ('two-of-one-name', MUT_FALLBACK, 4),
        ('element-not-a-map', MUT_FALLBACK, 4),
        ('containers-not-a-list', MUT_FALLBACK, 4),
        ('containers-null', MUT_SKIP, 0),
        ('no-containers', MUT_SKIP, 0),
        ('spec-not-a-map', MUT_FALLBACK, 2),
    ])
    def test_kernel_decisions(self, case, status, reason):
        prog = compile_mutate_set([pull_policy()])
        lanes = encode_mutate_batch([ELEMENT_DOCS[case]], prog)
        st, _edits, rc = MutateKernel(prog)(lanes)
        assert (int(st[0, 0]), int(rc[0, 0])) == (status, reason)

    def test_the_edit_mask_has_a_bit_per_patched_slot(self):
        prog = compile_mutate_set([pull_policy()])
        doc = containers_pod(0, [
            {'name': 'a'}, {'name': 'b', 'imagePullPolicy': 'Always'},
            {'image': 'nameless'}, {'name': 'd', 'imagePullPolicy': 'Never'}])
        lanes = encode_mutate_batch([doc], prog)
        assert lanes['llen'].tolist() == [[4]]
        assert lanes['istate'][0].tolist() == [0, 0, 3, 0]
        _st, edits, _rc = MutateKernel(prog)(lanes)
        assert int(edits[0, 0]) == 0b1001

    def test_list_shape_fallback_is_on_the_ledger_with_its_reason(self):
        from kyverno_tpu.observability.metrics import MetricsRegistry
        ledger = coverage.configure(MetricsRegistry())
        try:
            scanner = MutateScanner([pull_policy()])
            scanner.scan([ELEMENT_DOCS['5-containers'],
                          ELEMENT_DOCS['2-containers']])
            assert scanner.last_fallback_rows == 1
            assert ledger.report()['fallbacks']['mutate'] == {
                coverage.REASON_LIST_SHAPE: 1}
        finally:
            coverage.disable()

    def test_the_conflict_rule_compares_element_paths_slot_by_slot(self):
        from kyverno_tpu.mutate.plan import _paths_conflict
        assert compile_mutate_set([pull_policy(),
                                   requests_policy()]).device_ok
        third = sm_policy('limits', {'spec': {'containers': [
            {'(name)': '*', 'resources': 'none'}]}})
        prog = compile_mutate_set([pull_policy(), requests_policy(), third])
        assert not prog.device_ok
        by_policy = {p.policy: p.reason for p in prog.placements}
        assert by_policy == {
            'pull': coverage.REASON_POLICY_COUPLING,
            'res': coverage.REASON_SITE_CONFLICT,
            'limits': coverage.REASON_SITE_CONFLICT}
        # a plain rule that writes the list itself conflicts with every slot
        whole = j6_policy('whole', [{'op': 'add', 'path': '/spec/containers',
                                     'value': 'none'}])
        assert not compile_mutate_set([pull_policy(), whole]).device_ok
        at = ('spec', 'containers')
        assert _paths_conflict(at + (0, 'resources'),
                               at + (0, 'resources', 'requests', 'cpu'))
        assert not _paths_conflict(at + (0, 'resources'),
                                   at + (1, 'resources'))
        assert not _paths_conflict(at + (0, 'imagePullPolicy'),
                                   at + (0, 'resources', 'requests', 'cpu'))

    @pytest.mark.parametrize('containers', [
        [{'(name)': '*', 'tty': True}, {'(name)': '?*', 'stdin': True}],
        [{'name': 'sidecar', 'image': 'proxy'}],        # merge by key
        [{'(name)': 'c*', 'tty': True}],                # another pattern
        [{'(image)': '*', 'tty': True}],                # another anchor
        [{'(name)': '*', '<(image)': '*:latest', 'tty': True}],
        [{'(name)': '*', '=(tty)': True}],
        [{'(name)': '*', 'name': 'renamed'}],
        [{'(name)': '*'}],                              # nothing to write
        [{'(name)': '*', 'args': ['--flag']}],          # a scalar list
        [{'(name)': '*', 'ports': [{'(name)': '*', 'protocol': 'TCP'}]}],
        [{'(name)': '*', 'env': None}],                 # RFC 7386 delete
        [{'(name)': '*', 'image': '{{ request.object.metadata.name }}'}],
        ['nginx'],
        [],
    ])
    def test_every_other_list_shape_still_raises_with_a_reason(
            self, containers):
        p = sm_policy('p', {'spec': {'containers': containers}})
        with pytest.raises(LowerError) as ei:
            lower_mutate_rule(p.rules[0], 'p')
        assert ei.value.reason == coverage.REASON_UNSUPPORTED_OPERATOR
        assert ei.value.detail

    def test_the_host_appliers_keep_lists_on_the_engine_loop(self):
        """``_compile_overlay`` lowers the list shape for the device
        alone: the bulk appliers have no element walk."""
        from kyverno_tpu.compiler.mutate_compile import (
            _compile_overlay, compile_strategic_merge)
        overlay = pull_policy().rules[0].raw['mutate'][
            'patchStrategicMerge']
        assert _compile_overlay(overlay) is None
        assert compile_strategic_merge(overlay) is None
        assert len(_compile_overlay(overlay, 4)) == 4


# ---------------------------------------------------------------------------
# the defaults pack of the cell admission_mutate_open, on the CPU


class TestDefaultsPack:
    """``benchmarks/packs/mutate-defaults.yaml`` over the Pods of
    ``benchmarks/generators/mutate_reviews.py``: the program is held to
    the host engine, and the benchmark's plain reference to it too."""

    @pytest.fixture(scope='class')
    def policies(self):
        import benchlib
        return benchlib.load_policies(['mutate-defaults'])

    @pytest.fixture(scope='class')
    def bodies(self):
        import benchlib
        cluster = benchlib.load_module(
            'generators', 'mixed_cluster').generate(32, n=512)
        return benchlib.load_module(
            'generators', 'mutate_reviews').generate(32, cluster, 300)

    def test_the_whole_set_lowers(self, policies):
        prog = compile_mutate_set(policies)
        assert prog.device_ok and len(prog.programs) == 7
        assert prog.n_sites == 22 and prog.lists == [('spec', 'containers')]
        assert all(p.placement == coverage.PLACEMENT_DEVICE
                   for p in prog.placements)

    def test_300_generated_pods_against_the_host_chain(self, policies,
                                                       bodies):
        from kyverno_tpu.conformance.corpus import check_mutate_row
        pods = [json.loads(b)['request']['object'] for b in bodies]
        scanner = MutateScanner(policies)
        rows = scanner.scan([json.loads(json.dumps(p)) for p in pods])
        engine = Engine()
        for i, (doc, row) in enumerate(zip(pods, rows)):
            check_mutate_row(engine, policies, doc, row, f'pod {i}')
        # the generator's share of five-container Pods, and no other row
        five = sum(len(p['spec']['containers']) > 4 for p in pods)
        assert scanner.last_fallback_rows == five == 12
        assert sum(patched != doc for doc, (_s, patched)
                   in zip(pods, rows)) == len(pods)

    def test_the_plain_reference_gives_the_host_chains_document(
            self, policies, bodies):
        import benchlib
        reference = benchlib.load_module('reference', 'mutate_defaults')
        assert list(reference.CHAIN) == sorted(p.name for p in policies)
        for body in bodies:
            doc = json.loads(body)['request']['object']
            _steps, patched = host_chain(policies, doc)
            assert reference.canonical(reference.mutate(doc)) == \
                reference.canonical(patched)

    def test_mutate_then_validate_in_batch_mode_byte_for_byte(
            self, bodies, monkeypatch):
        """Every write through ``/mutate/fail`` and then, patched,
        ``/validate/fail`` of a batch-mode server, against the same
        server built with ``device=False``."""
        # the first write compiles the evaluator's admission shape, for
        # seconds on this CPU: it must not shed the ones behind it
        monkeypatch.setenv('KTPU_SHED_DEADLINE_MS', '120000')
        import copy
        from concurrent.futures import ThreadPoolExecutor
        import benchlib
        from kyverno_tpu.policycache import cache as pcache
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        from kyverno_tpu.webhooks.server import WebhookServer
        driver = benchlib.load_module('drivers', 'webhook_mutate')
        enforce = []
        for p in benchlib.load_policies(['pss', 'pack', 'config4']):
            doc = copy.deepcopy(p.raw)
            doc['spec']['validationFailureAction'] = 'Enforce'
            enforce.append(Policy(doc))
        cache = pcache.Cache()
        cache.warm_up(enforce + benchlib.load_policies(['mutate-defaults']))
        handlers = ResourceHandlers(cache, serving_mode='batch')
        server = WebhookServer(handlers)
        host = WebhookServer(ResourceHandlers(cache, device=False))
        try:
            assert handlers.wait_device_ready(
                cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod'),
                timeout=300)
            mutate_set = cache.get_policies(pcache.MUTATE, 'Pod', 'ns-0')
            assert handlers.wait_device_ready(mutate_set, timeout=120,
                                              kind='mutate')
            assert handlers._device_scanner(mutate_set, kind='mutate').ok

            def write(body):
                mutated = server.handle('/mutate/fail', body)
                allowed, review = driver.patched_review(body, mutated)
                assert allowed
                return mutated, review, server.handle('/validate/fail',
                                                      review)

            write(bodies[48])
            handlers._get_batcher().reset_stats()
            with ThreadPoolExecutor(8) as pool:
                answers = list(pool.map(write, bodies[:48]))
            verdicts = set()
            for body, (mutated, review, validated) in zip(bodies, answers):
                assert mutated == host.handle('/mutate/fail', body)
                assert validated == host.handle('/validate/fail', review)
                verdicts.add(json.loads(validated)['response']['allowed'])
            assert verdicts == {True, False}
            stats = handlers._get_batcher().stats()
            assert stats['mutate_device_path_requests'] == 48
            assert stats['mutate_host_loop_requests'] == 0
            assert stats['mutate_rows'] == 48
            assert stats['mutate_fallback_rows'] == sum(
                len(json.loads(b)['request']['object']['spec'][
                    'containers']) > 4 for b in bodies[:48])
            assert stats['device_path_requests'] == 48
            assert stats['mutate_dispatches'] + \
                stats['validate_dispatches'] == stats['dispatches']
        finally:
            handlers.shutdown()
            host.stop()
