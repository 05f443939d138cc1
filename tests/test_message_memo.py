"""A FAIL whose message has variables is worded once per key (``compiler/
scan.py`` ``_fail_memoized``, the plan in ``compiler/compile.py``
``_message_inputs``).

The device decides the verdict; where the rule's ``message`` carries
``{{…}}`` the host engine words it.  With a plan (``RuleProgram.
message_inputs``: the ``request.object`` expressions and the context inputs
the message is a function of) the Validator words the first row of each
(program, fail site, input values) in a scan pass and every other row of
that key takes its response.  The host engine (``kyverno_tpu/engine``) is
the reference, message for message:

* the cell's pack (``benchmarks/packs/context.yaml``) over seeded resources
  of the cell's generator, on the report-window path and on ``scan``, with
  the hits and misses counted;
* one case per shape of message and of fail site;
* one case per message the plan refuses: the Validator words every cell;
* nothing read from a ConfigMap outlives a pass;
* the webhook in batch mode against ``device=False``.
"""

import copy
import json

import pytest
import yaml

import benchlib
from kyverno_tpu.api.policy import Policy
from kyverno_tpu.compiler.compile import compile_policies
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.dclient.client import FakeClient
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.apicall import make_context_loader
from kyverno_tpu.engine.engine import Engine
from kyverno_tpu.observability import coverage
from kyverno_tpu.observability import device as devtel

context_cluster = benchlib.load_module('generators', 'context_cluster')

SEED = 2 ** 31 + 3535
PARAMS = dict(n=360, namespaces=20, cronjob_share=0.06)
PACK = ('allowed-pod-priorities', 'cm-array-example',
        'exclude-namespaces-dynamically', 'tenant-allowed-tiers')


def client_of(config_maps):
    client = FakeClient()
    for cm in config_maps:
        client.create_resource('v1', 'ConfigMap',
                               cm['metadata']['namespace'], cm)
    return client


def engine_rows(engine, policies, doc):
    """``{policy: [(rule, status, message)]}`` as the host engine answers."""
    out = {}
    for policy in policies:
        resp = engine.apply_background_checks(
            PolicyContext(policy, new_resource=doc))
        if resp.policy_response.rules:
            out[policy.name] = [(r.name, r.status, r.message)
                                for r in resp.policy_response.rules]
    return out


def report_rows(results):
    out = {}
    for r in results:
        out.setdefault(r['policy'], []).append(
            (r['rule'], r['result'], r['message']))
    return out


def response_rows(responses):
    return {r.policy.name: [(x.name, x.status, x.message)
                            for x in r.policy_response.rules]
            for r in responses if r.policy_response.rules}


class Scanned:
    """One pass of each path over ``docs`` with the counters on."""

    def __init__(self, policies, docs, engine):
        self.policies = policies
        self.docs = docs
        registry = benchlib.program_telemetry()
        try:
            self.scanner = BatchScanner(policies, engine=engine)
            assert not self.scanner.cps.host_rules
            self.report = [report_rows(results) for results, _s, _p
                           in self.scanner.scan_report_results(docs)]
            self.counts = self.memo_counts(registry)
            self.keys = len(self.scanner._msg_memo)
            self.ledger = coverage.ledger().report()
            self.stream = [response_rows(responses)
                           for responses in self.scanner.scan(docs)]
            both = self.memo_counts(registry)
            self.stream_counts = {k: both[k] - self.counts[k] for k in both}
        finally:
            coverage.disable()
            devtel.disable()
            from kyverno_tpu.observability import executables as exectel
            exectel.disable()
        self.want = [engine_rows(engine, policies, doc) for doc in docs]

    @staticmethod
    def memo_counts(registry):
        return {r: int(registry.counter_value(devtel.FAIL_MESSAGE_MEMO,
                                              result=r))
                for r in ('hit', 'miss')}

    def fails(self, policy=None):
        return [row for want in self.want for name, rows in want.items()
                if policy in (None, name) for row in rows
                if row[1] == 'fail']

    def host_rows(self, policy):
        return sum(r['host_rows'] for r in self.ledger['rules']
                   if r['policy'] == policy)


# -- the cell's pack over the cell's generator --------------------------------

@pytest.fixture(scope='module')
def cell():
    docs = context_cluster.generate(SEED, **PARAMS)
    assert len(docs) >= 300
    client = client_of(context_cluster.context_objects(SEED, **PARAMS))
    engine = Engine(context_loader=make_context_loader(dclient=client))
    return Scanned(benchlib.load_policies(['context']), docs, engine)


@pytest.mark.parametrize('path', ['report', 'stream'])
@pytest.mark.parametrize('policy', PACK)
def test_the_cells_pack_is_worded_as_the_engine_words_it(cell, policy, path):
    got = getattr(cell, path)
    differing = [(doc['kind'], doc['metadata']['name'], want.get(policy),
                  rows.get(policy))
                 for doc, want, rows in zip(cell.docs, cell.want, got)
                 if want.get(policy) != rows.get(policy)]
    assert not differing, differing[:3]
    assert cell.fails(policy), f'{policy} failed nothing'


def test_the_validator_words_one_cell_a_distinct_key(cell):
    fails = len(cell.fails())
    assert cell.counts['hit'] > 0
    assert cell.counts['miss'] == cell.keys < fails
    assert cell.counts['hit'] + cell.counts['miss'] == fails
    # scan() is a pass of its own: it words each key again, once
    assert cell.stream_counts == cell.counts


def test_a_hit_is_a_device_row_and_a_miss_a_host_row(cell):
    by_reason = cell.ledger['fallbacks'].get('validate', {})
    assert by_reason.get('unsynthesizable_message') == cell.counts['miss']
    for policy in PACK:
        rows = [r for r in cell.ledger['rules'] if r['policy'] == policy]
        assert sum(r['device_rows'] + r['host_rows'] for r in rows) == \
            sum(len(want.get(policy, [])) for want in cell.want)


@pytest.mark.parametrize('policy', PACK)
def test_every_program_of_the_cells_pack_has_a_plan(cell, policy):
    programs = [p for p in cell.scanner.cps.programs
                if p.policy_name == policy]
    assert programs
    for prog in programs:
        assert prog.message_inputs is not None
        assert set(prog.context_inputs) <= set(prog.message_inputs)
        assert any(e.startswith('request.object')
                   and e not in prog.context_inputs
                   for e in prog.message_inputs)


# -- one case a shape ---------------------------------------------------------

def policy_of(name, validate, context=None, preconditions=None):
    rule = {'name': name,
            'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
            'validate': validate}
    if context is not None:
        rule['context'] = context
    if preconditions is not None:
        rule['preconditions'] = preconditions
    return Policy({
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': name, 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {'background': True, 'validationFailureAction': 'Audit',
                 'rules': [rule]}})


def pod(name, namespace, labels=None, priority=None, annotations=None,
        images=('nginx:1',)):
    meta = {'name': name, 'namespace': namespace}
    if labels is not None:
        meta['labels'] = labels
    if annotations is not None:
        meta['annotations'] = annotations
    spec = {'containers': [{'name': f'c{i}', 'image': image}
                           for i, image in enumerate(images)]}
    if priority is not None:
        spec['priorityClassName'] = priority
    return {'apiVersion': 'v1', 'kind': 'Pod', 'metadata': meta,
            'spec': spec}


ALLOWED = {'apiVersion': 'v1', 'kind': 'ConfigMap',
           'metadata': {'name': 'allowed', 'namespace': 'default'},
           'data': {'ns-a': '["high"]', 'ns-b': '["low", "mid"]'}}
ALLOWED_CONTEXT = [{'name': 'allowed', 'configMap': {
    'name': 'allowed', 'namespace': 'default'}}]
IN_NS_A = {'any': [{'key': '{{request.object.metadata.namespace}}',
                    'operator': 'AnyIn', 'value': ['ns-a', 'ns-b']}]}
LABELLED = {'metadata': {'labels': {'foo': '?*', 'bar': '?*'}}}

# name -> (policy, pods, distinct messages, misses)
CASES = {
    # one priority class, two namespaces whose lists differ
    'two-lists': (
        policy_of('two-lists', {
            'message': 'class {{ request.object.spec.priorityClassName }} '
                       'is not among {{ allowed.data.'
                       '"{{request.object.metadata.namespace}}" }}.',
            'deny': {'conditions': {'any': [{
                'key': "{{ request.object.spec.priorityClassName || '' }}",
                'operator': 'AnyNotIn',
                'value': '{{ allowed.data.'
                         '"{{request.object.metadata.namespace}}" }}'}]}}},
            context=ALLOWED_CONTEXT),
        [pod(f'p{i}', ns, priority='gold')
         for ns in ('ns-a', 'ns-b') for i in range(4)], 2, 2),
    # a pattern's message ends with the path the walk failed at
    'pattern-path': (
        policy_of('pattern-path', {
            'message': 'pods of {{request.object.metadata.namespace}} '
                       'carry foo and bar',
            'pattern': LABELLED}),
        [pod(f'p{i}', 'ns-a', labels=labels) for i, labels in enumerate(
            [None, None, {'x': 'y'}, {'x': 'z'}, {'bar': '1'},
             {'bar': '2'}, {'foo': '1', 'bar': '2'}])], 3, 3),
    # the engine leaves an anyPattern's message as it is written and
    # appends every child's path: the memo hands back what it says
    'any-pattern': (
        policy_of('any-pattern', {
            'message': '{{request.object.metadata.namespace}} wants foo '
                       'or bar.',
            'anyPattern': [{'metadata': {'labels': {'foo': '?*'}}},
                           {'metadata': {'labels': {'bar': '?*'}}}]}),
        [pod(f'p{i}', ns, labels=labels)
         for ns in ('ns-a', 'ns-b') for i, labels in enumerate(
            [None, None, {'x': 'y'}, {'x': 'z'}])]
        + [pod('passes', 'ns-a', labels={'bar': '1'})], 2, 4),
    # the whole message is one variable that is a map: the engine's own
    # "didn't resolve to a string", from the memo too
    'not-a-string': (
        policy_of('not-a-string', {
            'message': '{{ request.object.metadata.labels }}',
            'deny': {'conditions': IN_NS_A}}),
        [pod(f'p{i}', 'ns-a', labels={'app': 'web'}) for i in range(4)]
        + [pod('q', 'ns-b', labels={'app': 'api'})], 1, 2),
    # a variable that resolves to nothing: the engine hands the raw
    # message back, from the memo too
    'substitution-fails': (
        policy_of('substitution-fails', {
            'message': '{{ request.object.metadata.annotations.owner }} '
                       'owns {{request.object.metadata.namespace}}',
            'deny': {'conditions': IN_NS_A}}),
        [pod(f'p{i}', 'ns-a') for i in range(3)]
        + [pod('q', 'ns-a', annotations={'owner': 'ops'})], 2, 2),
    # a message that names the resource never hits
    'names-the-resource': (
        policy_of('names-the-resource', {
            'message': '{{request.object.metadata.name}} is not welcome',
            'deny': {'conditions': IN_NS_A}}),
        [pod(f'p{i}', 'ns-a') for i in range(12)], 12, 12),
    # the message reads the context and nothing of the row
    'context-alone': (
        policy_of('context-alone', {
            'message': 'ns-a allows {{ allowed.data."ns-a" }} only',
            'deny': {'conditions': IN_NS_A}}, context=ALLOWED_CONTEXT),
        [pod(f'p{i}', 'ns-a') for i in range(5)], 1, 1),
    # 1, true and "1" print differently
    'typed-values': (
        policy_of('typed-values', {
            'message': 'flag {{ request.object.metadata.annotations.flag }}',
            'deny': {'conditions': IN_NS_A}}),
        [pod(f'p{i}', 'ns-a', annotations={'flag': flag})
         for i, flag in enumerate([1, True, '1', 1.0, 1, '1'])], 3, 4),
}


@pytest.fixture(scope='module')
def shapes():
    """Every case in one scanner, over all the cases' pods."""
    policies = [case[0] for case in CASES.values()]
    docs, seen = [], set()
    for _policy, pods, _m, _k in CASES.values():
        for doc in pods:
            text = json.dumps(doc, sort_keys=True)
            if text not in seen:
                seen.add(text)
                docs.append(doc)
    engine = Engine(context_loader=make_context_loader(
        dclient=client_of([ALLOWED])))
    return Scanned(policies, docs, engine)


@pytest.mark.parametrize('path', ['report', 'stream'])
@pytest.mark.parametrize('name', list(CASES))
def test_a_shape_is_worded_as_the_engine_words_it(shapes, name, path):
    got = getattr(shapes, path)
    for doc, want, rows in zip(shapes.docs, shapes.want, got):
        assert rows.get(name) == want.get(name), doc['metadata']
    (prog,) = [p for p in shapes.scanner.cps.programs
               if p.policy_name == name]
    assert prog.message_inputs is not None


def _own_pass(name):
    policy, pods, _m, _k = CASES[name]
    engine = Engine(context_loader=make_context_loader(
        dclient=client_of([ALLOWED])))
    return Scanned([policy], pods, engine)


@pytest.mark.parametrize('name', list(CASES))
def test_a_shape_pays_the_validator_once_a_key(name):
    _policy, pods, messages, misses = CASES[name]
    scanned = _own_pass(name)
    fails = scanned.fails()
    assert len(pods) - 1 <= len(fails) <= len(pods)
    assert len({row[2] for row in fails}) == messages
    assert scanned.counts == {'hit': len(fails) - misses, 'miss': misses}
    assert scanned.host_rows(name) == misses
    assert len(scanned.scanner._msg_memo) <= misses


def test_the_engines_own_fallbacks_come_back():
    rows = _own_pass('not-a-string').fails()
    assert {r[2] for r in rows} == {
        "the produced message didn't resolve to a string, check your "
        "policy definition."}
    raw = CASES['substitution-fails'][0].raw['spec']['rules'][0][
        'validate']['message']
    assert raw in {r[2] for r in _own_pass('substitution-fails').fails()}


def test_the_memo_is_bounded(monkeypatch):
    """Past its bound the memo starts over: right answers, more misses."""
    from kyverno_tpu.compiler import scan as scan_mod
    policy, pods, _m, _k = CASES['names-the-resource']
    monkeypatch.setattr(scan_mod, '_MESSAGE_CACHE_MAX', 3)
    engine = Engine()
    scanner = BatchScanner([policy], engine=engine)
    got = [report_rows(results) for results, _s, _p
           in scanner.scan_report_results(pods)]
    assert got == [engine_rows(engine, [policy], doc) for doc in pods]
    assert 0 < len(scanner._msg_memo) <= 4 < len(pods)


# -- what the plan refuses ----------------------------------------------------

FOREACH = {'message': '{{request.object.metadata.name}} runs untagged '
                      'images',
           'foreach': [{'list': 'request.object.spec.containers',
                        'deny': {'conditions': {'all': [{
                            'key': '{{ element.image }}',
                            'operator': 'Equals',
                            'value': 'nginx:1'}]}}}]}
REFUSED = {
    'request.operation': policy_of('r-operation', {
        'message': "{{ request.operation || 'BACKGROUND' }} of "
                   '{{request.object.metadata.namespace}} denied',
        'deny': {'conditions': IN_NS_A}}),
    'images': policy_of('r-images', {
        'message': 'images {{ images.containers.c0.name }}',
        'deny': {'conditions': IN_NS_A}}),
    'element': policy_of('r-element', {
        'message': 'element {{ element.name }}',
        'deny': {'conditions': IN_NS_A}}),
    'variable-entry': policy_of('r-variable', {
        'message': 'tier {{ tier }} in '
                   '{{request.object.metadata.namespace}}',
        'deny': {'conditions': IN_NS_A}},
        context=[{'name': 'tier', 'variable': {'value': 'gold'}}]),
    'stateful-function': policy_of('r-stateful', {
        'message': "up {{ time_since('', '2020-01-01T00:00:00Z', "
                   "'2020-01-02T00:00:00Z') }}",
        'deny': {'conditions': IN_NS_A}}),
    'reference': policy_of('r-reference', {
        'message': 'see $(metadata.name) of '
                   '{{request.object.metadata.namespace}}',
        'deny': {'conditions': IN_NS_A}}),
    'unknown-root': policy_of('r-unknown', {
        'message': 'nobody {{ nobody.home }}',
        'deny': {'conditions': IN_NS_A}}),
    'nested-in-the-object': policy_of('r-nested', {
        'message': 'label {{ request.object.metadata.labels.'
                   '"{{request.object.metadata.name}}" }}',
        'deny': {'conditions': IN_NS_A}}),
    'foreach': policy_of('r-foreach', FOREACH),
}


@pytest.mark.parametrize('shape', list(REFUSED))
def test_a_refused_shape_has_no_plan_and_the_validator_words_every_cell(
        shape):
    policy = REFUSED[shape]
    pods = [pod(f'p{i}', 'ns-a', labels={f'p{i}': 'x'}) for i in range(6)]
    scanned = Scanned([policy], pods, Engine())
    (prog,) = scanned.scanner.cps.programs
    assert prog.message_inputs is None
    assert not scanned.scanner._msg_plans
    assert scanned.report == scanned.want == scanned.stream
    assert len(scanned.fails()) == len(pods)
    assert scanned.counts == {'hit': 0, 'miss': 0}
    assert scanned.host_rows(policy.name) == len(pods)


@pytest.mark.parametrize('packs', [['pss'], ['pack'], ['config4'],
                                   ['mutate-defaults']])
def test_no_program_outside_the_context_pack_has_a_plan(packs):
    cps = compile_policies(benchlib.load_policies(packs))
    assert all(p.message_inputs is None for p in cps.programs)


def test_a_static_message_has_no_plan():
    policy = policy_of('static', {'message': 'no.',
                                  'deny': {'conditions': IN_NS_A}})
    (prog,) = compile_policies([policy]).programs
    assert prog.message_inputs is None and prog.deny_fail_message == 'no.'


# -- nothing read from a ConfigMap outlives a pass ----------------------------

def test_an_edited_configmap_is_worded_anew_in_the_next_pass():
    policy, pods, _m, _k = CASES['two-lists']
    client = client_of([copy.deepcopy(ALLOWED)])
    engine = Engine(context_loader=make_context_loader(dclient=client))
    scanner = BatchScanner([policy], engine=engine)

    def scan():
        return [report_rows(results) for results, _s, _p
                in scanner.scan_report_results(pods)]

    first = scan()
    assert first == [engine_rows(engine, [policy], doc) for doc in pods]
    edited = client.get_resource('v1', 'ConfigMap', 'default', 'allowed')
    edited['data']['ns-a'] = '["platinum"]'
    client.update_resource('v1', 'ConfigMap', 'default', edited)
    second = scan()
    assert second == [engine_rows(engine, [policy], doc) for doc in pods]
    assert second != first
    assert 'platinum' in second[0]['two-lists'][0][2]
    assert 'platinum' not in first[0]['two-lists'][0][2]


# -- the webhook --------------------------------------------------------------

def test_the_webhook_in_batch_mode_answers_as_the_host_chain():
    from kyverno_tpu.policycache import cache as pcache
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer
    docs = context_cluster.generate(SEED, **PARAMS)
    client = client_of(context_cluster.context_objects(SEED, **PARAMS))
    raw = next(d for d in yaml.safe_load_all(
        open(benchlib.data_path('packs', 'context', '.yaml')))
        if d['metadata']['name'] == 'allowed-pod-priorities')
    raw = copy.deepcopy(raw)
    raw['spec']['validationFailureAction'] = 'Enforce'
    cache = pcache.Cache()
    cache.warm_up([Policy(raw)])
    handlers = ResourceHandlers(cache, serving_mode='batch', client=client)
    server = WebhookServer(handlers)
    host = WebhookServer(ResourceHandlers(cache, device=False,
                                          client=client))
    try:
        assert handlers.wait_device_ready(
            cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod'), timeout=600)
        pods = [r for r in docs if r['kind'] == 'Pod'][:60]
        seen = set()
        for i, doc in enumerate(pods):
            body = json.dumps({
                'apiVersion': 'admission.k8s.io/v1',
                'kind': 'AdmissionReview',
                'request': {
                    'uid': f'u-{i}', 'operation': 'CREATE',
                    'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
                    'namespace': doc['metadata']['namespace'],
                    'name': doc['metadata']['name'], 'object': doc,
                    'userInfo': {'username': 'dev'}}}).encode()
            got = server.handle('/validate/fail', body)
            assert got == host.handle('/validate/fail', body)
            seen.add(json.loads(got)['response']['allowed'])
        assert seen == {True, False}
        assert handlers._get_batcher().stats()['device_path_requests'] > 0
    finally:
        server.stop()
        host.stop()
