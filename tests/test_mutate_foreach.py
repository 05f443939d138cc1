"""Device ``foreach`` mutations over every kind autogen reaches
(``kyverno_tpu/mutate/``): the single-entry ``foreach`` that
``compiler/mutate_compile.py`` ``parse_foreach`` reads, lowered to element
sites gated by per-element condition lanes, on the Pod's lists and on
autogen's ``spec.template`` and ``spec.jobTemplate.spec.template`` ones.

The host engine's cumulative mutate chain is the oracle, response for
response (status, message, patches in their order) and the patched
document; the cell ``admission_mutate_kinds_open``'s plain reference
(``benchmarks/reference/mutate_foreach_kinds.py``) is held to it too.
CPU-only, tier-1.
"""

import copy
import hashlib
import json

import pytest

import benchlib
from kyverno_tpu.api.policy import Policy
from kyverno_tpu.conformance.corpus import check_mutate_row
from kyverno_tpu.engine.engine import Engine
from kyverno_tpu.mutate import LowerError, MutateScanner, compile_mutate_set
from kyverno_tpu.mutate.encode import encode_mutate_batch
from kyverno_tpu.mutate.kernel import MUT_FALLBACK, MutateKernel
from kyverno_tpu.mutate.plan import lower_mutate_rule
from kyverno_tpu.observability import coverage
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability.metrics import MetricsRegistry
from kyverno_tpu.policycache import cache as pcache

KINDS = benchlib.load_module('generators', 'chart_cluster').KINDS
reference = benchlib.load_module('reference', 'mutate_foreach_kinds')


def foreach_policy(name, preconditions, overlay, list_expr=None,
                   autogen=True, **entry):
    """One Pod rule: a foreach over ``spec.containers`` merging
    ``overlay`` into each element whose ``preconditions`` hold."""
    meta = {'name': name}
    if not autogen:
        meta['annotations'] = {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}
    body = {'list': list_expr or 'request.object.spec.containers',
            'patchStrategicMerge': {'spec': {'containers': [
                dict({'name': '{{ element.name }}'}, **overlay)]}},
            **entry}
    if preconditions is not None:
        body['preconditions'] = preconditions
    return Policy({'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
                   'metadata': meta,
                   'spec': {'rules': [{
                       'name': f'{name}-rule',
                       'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
                       'mutate': {'foreach': [body]}}]}})


def pod(containers, **spec):
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': 'p', 'namespace': 'default'},
            'spec': dict(containers=containers, **spec)}


def scan_against_the_engine(policies, docs):
    """Every row of a device scan held to the engine's chain; the
    scanner, for its counts."""
    scanner = MutateScanner(policies)
    assert scanner.ok, [(p.rule, p.reason, p.detail)
                        for p in scanner.program.placements if p.reason]
    rows = scanner.scan([copy.deepcopy(d) for d in docs])
    engine = Engine()
    for i, (doc, row) in enumerate(zip(docs, rows)):
        check_mutate_row(engine, policies, doc, row, f'doc {i}')
    return scanner


@pytest.fixture(scope='module')
def pack():
    return benchlib.load_policies(['mutate-foreach'])


@pytest.fixture(scope='module')
def writes():
    """About 300 writes of the cell's generator, all seven kinds."""
    cluster = benchlib.load_module('generators', 'chart_cluster').generate(
        41, n=512)
    bodies = benchlib.load_module('generators',
                                  'mutate_kinds_reviews').generate(
        41, cluster, 300)
    return [json.loads(b)['request']['object'] for b in bodies]


def test_the_pack_lowers_whole_on_autogen_paths(pack):
    program = compile_mutate_set(pack)
    assert program.device_ok and not [p for p in program.placements
                                      if p.reason]
    # seven policies; six of them autogen to two more programs each
    assert len(program.programs) == 19
    assert [path for path, _default in program.foreach_lists] == [
        prefix + (key,) for key in ('containers', 'initContainers')
        for prefix in (('spec',), ('spec', 'template', 'spec'),
                       ('spec', 'jobTemplate', 'spec', 'template',
                        'spec'))]
    assert len(program.conditions) == 18


@pytest.mark.parametrize('kind', KINDS)
def test_every_kind_against_the_host_chain_and_the_reference(pack, writes,
                                                             kind):
    """The generator's writes of one kind through the compiled set that
    kind keys, at capacity 64: the device's chain is the engine's, and
    the reference's document is the engine's."""
    cache = pcache.Cache()
    cache.warm_up(pack)
    docs = [d for d in writes if d['kind'] == kind]
    policies = cache.get_policies(pcache.MUTATE, kind, 'ns-0')
    assert len(policies) == (7 if kind == 'Pod' else 6)
    assert len(docs) >= 8
    scanner = MutateScanner(policies)
    rows = scanner.scan([copy.deepcopy(d) for d in docs])
    engine = Engine()
    for i, (doc, row) in enumerate(zip(docs, rows)):
        check_mutate_row(engine, policies, doc, row, f'{kind} {i}')
        assert reference.canonical(reference.mutate(doc)) == \
            reference.canonical(row[1])
    assert scanner.last_fallback_rows == sum(map(reference.expects_host,
                                                 docs))
    counts = scanner.last_counts
    assert counts['pod' if kind == 'Pod' else
                  'cronjob' if kind == 'CronJob' else 'template'] == len(docs)
    assert counts['edited'] and counts['unmet'] and not counts['escaped']


@pytest.mark.parametrize('key, op, value, elements', [
    ("{{ element.imagePullPolicy || '' }}", 'Equals', '',
     [{'imagePullPolicy': 'Always'}, {}, {'imagePullPolicy': ''},
      {'imagePullPolicy': None}]),
    ("{{ element.securityContext.privileged || '' }}", 'NotEquals', True,
     [{'securityContext': {'privileged': True}}, {'securityContext': 'x'},
      {'securityContext': {'privileged': 'true'}},
      {'securityContext': {'privileged': 1}}]),
    ("{{ element.tier || 'web' }}", 'AnyIn', ['web', 'api'],
     [{'tier': 'api'}, {'tier': 'db'}, {'tier': 7}, {'tier': False}]),
    ("{{ element.tier || '' }}", 'AnyNotIn', ['web', 'api'],
     [{'tier': 'api'}, {'tier': 'db'}, {'tier': 7}, {'tier': True}]),
    ("{{ element.tier }}", 'AnyNotIn', ['web'],
     [{'tier': 'web'}, {'tier': {'a': 1}}, {'tier': 0}, {'tier': 'web2'}]),
])
def test_each_operator_on_every_key_type(key, op, value, elements):
    policy = foreach_policy('p', {'all': [{'key': key, 'operator': op,
                                           'value': value}]},
                            {'marked': 'yes'}, autogen=False)
    containers = [dict({'name': f'c{i}'}, **e)
                  for i, e in enumerate(elements)]
    scan_against_the_engine([policy], [pod(containers),
                                       pod(containers[::-1])])


def test_an_any_block_and_an_all_block():
    policy = foreach_policy('p', {
        'all': [{'key': "{{ element.image || '' }}", 'operator': 'NotEquals',
                 'value': ''}],
        'any': [{'key': "{{ element.tier || '' }}", 'operator': 'Equals',
                 'value': 'web'},
                {'key': "{{ element.team || '' }}", 'operator': 'AnyIn',
                 'value': ['a', 'b']}]},
        {'resources': {'requests': {'memory': '1Gi'}}}, autogen=False)
    containers = [{'name': 'w', 'image': 'i', 'tier': 'web'},
                  {'name': 'x', 'image': 'i', 'team': 'b'},
                  {'name': 'y', 'image': 'i', 'team': 'c'},
                  {'name': 'z', 'tier': 'web'}]
    scanner = scan_against_the_engine([policy], [pod(containers)])
    assert scanner.last_counts['edited'] == 2
    assert scanner.last_counts['unmet'] == 2


def test_a_key_that_does_not_resolve_falls_back_with_its_reason():
    """``{{ element.imagePullPolicy }}`` without a default on a container
    that lacks the field: the engine reports a substitution error, so the
    device hands the row to it (``precondition_escape``)."""
    policy = foreach_policy('p', {'all': [{
        'key': '{{ element.imagePullPolicy }}', 'operator': 'Equals',
        'value': 'Never'}]}, {'imagePullPolicy': 'IfNotPresent'},
        autogen=False)
    docs = [pod([{'name': 'a', 'imagePullPolicy': 'Never'}, {'name': 'b'}]),
            pod([{'name': 'a', 'imagePullPolicy': 'Never'}])]
    program = compile_mutate_set([policy])
    status, _edits, reason, elem = MutateKernel(program)(
        encode_mutate_batch(docs, program))
    assert status[0, 0] == MUT_FALLBACK and reason[0, 0] == 5
    assert status[1, 0] != MUT_FALLBACK and elem[1, 0] == 1
    registry = MetricsRegistry()
    coverage.configure(registry)
    try:
        scanner = scan_against_the_engine([policy], docs)
        by_reason = coverage.bench_block()['by_reason']
    finally:
        coverage.disable()
    assert scanner.last_fallback_rows == 1
    assert scanner.last_counts['escaped'] == 1
    assert by_reason['mutate'][coverage.REASON_PRECONDITION_ESCAPE] == 1


@pytest.mark.parametrize('containers', [
    [{'name': 'a'}, {'name': 'a', 'image': 'x'}],           # a name twice
    [{'name': 'a'}, {'image': 'x'}],                        # a nameless one
    [{'name': f'c{i}'} for i in range(6)],                  # a sixth
], ids=['duplicate-names', 'nameless-element', 'six-containers'])
def test_a_list_the_lanes_cannot_stand_for_falls_back(pack, containers):
    doc = pod(containers)
    scanner = scan_against_the_engine(pack, [doc])
    assert scanner.last_fallback_rows == 1


def test_a_condition_that_reads_an_earlier_rules_write_is_refused():
    """The device decides on the original document while the chain is
    cumulative: a condition reading a leaf another rule writes is
    order-dependent, so the set stays with the host."""
    writer = Policy({'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
                     'metadata': {'name': 'a-writer'},
                     'spec': {'rules': [{
                         'name': 'w', 'match': {'any': [{'resources': {
                             'kinds': ['Pod']}}]},
                         'mutate': {'patchStrategicMerge': {'spec': {
                             'containers': [{'(name)': '*',
                                             'imagePullPolicy':
                                                 'Always'}]}}}}]}})
    reader = foreach_policy('b-reader', {'all': [{
        'key': "{{ element.imagePullPolicy || '' }}", 'operator': 'Equals',
        'value': ''}]}, {'resources': {'requests': {'cpu': '1'}}})
    program = compile_mutate_set([writer, reader])
    assert not program.device_ok
    assert {p.reason for p in program.placements} == {
        coverage.REASON_SITE_CONFLICT}
    # alone, each lowers
    assert compile_mutate_set([reader]).device_ok


def test_the_defaults_set_compiles_what_it_compiled_before():
    """``admission-mutate-defaults`` has no foreach: the same lanes, no
    condition lane, and the same device program (the jaxpr of
    ``mutate_eval``) as before foreach lowered, hashes taken at that
    commit."""
    import jax
    program = compile_mutate_set(benchlib.load_policies(['mutate-defaults']))
    cluster = benchlib.load_module('generators', 'mixed_cluster').generate(
        32, n=512)
    bodies = benchlib.load_module('generators', 'mutate_reviews').generate(
        32, cluster, 64)
    pods = [json.loads(b)['request']['object'] for b in bodies]
    lanes = encode_mutate_batch(pods[:40], program, padded_n=64)
    assert sorted(lanes) == ['istate', 'llen', 'milli', 'milli_ok',
                             'sbytes', 'slen', 'tag', 'valid']
    digest = hashlib.sha256()
    for name in sorted(lanes):
        digest.update(f'{name}:{lanes[name].dtype}:'
                      f'{lanes[name].shape};'.encode())
        digest.update(lanes[name].tobytes())
    assert digest.hexdigest() == \
        'e2379b948b4a1097928e6c0cacaaac68873b0a8f0bbc70b0f2214c7ccfbb0034'
    kernel = MutateKernel(program)
    assert not kernel.has_foreach
    with jax.enable_x64(True):
        jaxpr = str(jax.make_jaxpr(kernel.mutate_eval)(lanes))
    assert hashlib.sha256(jaxpr.encode()).hexdigest() == \
        '16a44fc1f9409af0351eeee996dd2983a220112d5903d541e7a09f6cc0dea51b'


@pytest.mark.parametrize('rule_change, entry_change', [
    ({'preconditions': {'all': []}}, {}),
    ({'context': [{'name': 'c', 'configMap': {'name': 'x'}}]}, {}),
    ({}, {'context': [{'name': 'c', 'variable': {'value': 1}}]}),
    ({}, {'foreach': [{'list': 'element.ports'}]}),
    ({}, {'patchesJson6902': '[]'}),
    ({}, {'patchStrategicMerge': {'spec': {'containers': [
        {'name': '{{ element.name }}',
         'image': "{{ regex_replace_all('^x', '{{element.image}}', 'y') }}"}
    ]}}}),
    ({}, {'patchStrategicMerge': {'spec': {'containers': [
        {'name': '{{ element.name }}', '+(imagePullPolicy)': 'Always'}]}}}),
    ({}, {'preconditions': {'all': [{'key': '{{ request.operation }}',
                                     'operator': 'Equals',
                                     'value': 'CREATE'}]}}),
    ({}, {'preconditions': {'all': [{'key': "{{ element.image || '' }}",
                                     'operator': 'Equals',
                                     'value': 'nginx:*'}]}}),
    ({}, {'preconditions': {'all': [{'key': "{{ element.cpu || '' }}",
                                     'operator': 'GreaterThan',
                                     'value': '1'}]}}),
    ({}, {'preconditions': {'all': [{'key': "{{ element.mem || '' }}",
                                     'operator': 'Equals',
                                     'value': '1Gi'}]}}),
], ids=['rule-preconditions', 'rule-context', 'entry-context',
        'nested-foreach', 'json6902-in-foreach', 'computed-value',
        'add-anchor', 'key-outside-element', 'wildcard-value',
        'other-operator', 'quantity-value'])
def test_outside_the_vocabulary_stays_with_the_host(rule_change,
                                                    entry_change):
    policy = foreach_policy('p', None, {'imagePullPolicy': 'Always'},
                            autogen=False)
    rule = copy.deepcopy(policy.raw['spec']['rules'][0])
    rule.update(rule_change)
    rule['mutate']['foreach'][0].update(entry_change)
    with pytest.raises(LowerError) as raised:
        lower_mutate_rule(Policy(dict(policy.raw, spec={'rules': [rule]}))
                          .rules[0], 'p')
    assert raised.value.reason in (coverage.REASON_UNSUPPORTED_OPERATOR,
                                   coverage.REASON_API_CALL)


def test_the_counters_are_recorded_once_a_dispatch_and_only_when_on(pack,
                                                                    writes):
    docs = writes[:40]
    scanner = MutateScanner(pack)
    scanner.scan([copy.deepcopy(d) for d in docs])   # metrics off
    counts = dict(scanner.last_counts)
    registry = devtel.configure(MetricsRegistry())
    try:
        scanner.scan([copy.deepcopy(d) for d in docs])
        for result in devtel.MUTATE_ELEMENT_RESULTS:
            assert registry.counter_value(devtel.MUTATE_ELEMENT_CELLS,
                                          result=result) == counts[result]
        for path in devtel.MUTATE_ROW_PATHS:
            assert registry.counter_value(devtel.MUTATE_ROWS,
                                          path=path) == counts[path]
    finally:
        devtel.disable()
    assert sum(counts[p] for p in devtel.MUTATE_ROW_PATHS) == len(docs)
    assert counts['template'] and counts['cronjob'] and counts['kept']


def test_seven_kinds_mutate_then_validate_in_batch_mode_byte_for_byte(
        pack, writes, monkeypatch):
    """Writes of every kind through ``/mutate/fail`` and then, patched,
    ``/validate/fail`` of a batch-mode server, against the same server
    built with ``device=False``: the four compiled sets the kinds key are
    built once and none displaces another."""
    monkeypatch.setenv('KTPU_SHED_DEADLINE_MS', '120000')
    from concurrent.futures import ThreadPoolExecutor
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer
    driver = benchlib.load_module('drivers', 'webhook_mutate')
    enforce = []
    for p in benchlib.load_policies(['pss', 'pack', 'config4']):
        doc = copy.deepcopy(p.raw)
        doc['spec']['validationFailureAction'] = 'Enforce'
        enforce.append(Policy(doc))
    cache = pcache.Cache()
    cache.warm_up(enforce + pack)
    handlers = ResourceHandlers(cache, serving_mode='batch')
    server = WebhookServer(handlers)
    host = WebhookServer(ResourceHandlers(cache, device=False))
    try:
        sets = []
        for kind in KINDS:
            sets += [('validate', cache.get_installed(
                pcache.VALIDATE_ENFORCE, kind)),
                ('mutate', cache.get_policies(pcache.MUTATE, kind, 'ns-0'))]
        for what, policies in sets:
            assert handlers.wait_device_ready(policies, timeout=300,
                                              kind=what)
        scanners = {id(handlers._device_scanner(p, what))
                    for what, p in sets}
        assert len(scanners) == 4 and None not in scanners

        bodies = [json.dumps({'apiVersion': 'admission.k8s.io/v1',
                              'kind': 'AdmissionReview',
                              'request': {
                                  'uid': f'u{i}', 'operation': 'CREATE',
                                  'kind': {'kind': d['kind']},
                                  'namespace': d['metadata']['namespace'],
                                  'name': d['metadata']['name'],
                                  'object': d,
                                  'userInfo': {'username': 'u'}}}).encode()
                  for i, d in enumerate(writes[:42])]

        def write(body):
            mutated = server.handle('/mutate/fail', body)
            allowed, review = driver.patched_review(body, mutated)
            assert allowed
            return mutated, review, server.handle('/validate/fail', review)

        handlers._get_batcher().reset_stats()
        with ThreadPoolExecutor(8) as pool:
            answers = list(pool.map(write, bodies))
        for body, (mutated, review, validated) in zip(bodies, answers):
            assert mutated == host.handle('/mutate/fail', body)
            assert validated == host.handle('/validate/fail', review)
        stats = handlers._get_batcher().stats()
        assert stats['mutate_device_path_requests'] == len(bodies)
        assert stats['mutate_host_loop_requests'] == 0
        assert stats['mutate_rows_template'] + \
            stats['mutate_rows_cronjob'] > 0
        assert stats['mutate_element_cells'] == sum(
            stats[f'mutate_element_{r}']
            for r in devtel.MUTATE_ELEMENT_RESULTS)
        assert {id(handlers._device_scanner(p, what))
                for what, p in sets} == scanners
    finally:
        handlers.shutdown()
        host.stop()
