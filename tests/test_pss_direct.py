"""A podSecurity cell the host must phrase is answered by one call of the
function the Validator itself answers with (engine.pod_security_response),
with no PolicyContext, no Validator and no Rule built for the cell
(compiler/scan.py ``BatchScanner._materialize``).  The direct answer has
to be the Validator's, field by field, wherever it engages; a rule that
reads more than the resource (context, preconditions), an empty document
and every non-PSS program keep the Validator."""

import pytest
import yaml

import benchlib
from kyverno_tpu.api.policy import Policy, Rule
from kyverno_tpu.compiler import scan as scan_mod
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.engine import api as engine_api
from kyverno_tpu.engine.api import PolicyContext, RuleStatus, RuleType
from kyverno_tpu.engine.engine import (Engine, Validator,
                                       pod_security_response)
from kyverno_tpu.pss.evaluate import evaluate_pod_security
from kyverno_tpu.reports.results import _policy_static, _rule_result

mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')

FIELDS = ('name', 'rule_type', 'message', 'status', 'pod_security_checks')

EXTRA = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: pss-preconditions
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: gated
      match: {any: [{resources: {kinds: [Pod]}}]}
      preconditions:
        all:
          - key: "{{request.object.metadata.name}}"
            operator: NotEquals
            value: skipme
      validate:
        podSecurity: {level: baseline, version: latest}
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: pss-context
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: loaded
      match: {any: [{resources: {kinds: [Pod]}}]}
      context:
        - name: tier
          variable: {value: gold}
      validate:
        podSecurity: {level: baseline, version: latest}
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: needs-app-label
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: app-label
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "pod {{request.object.metadata.name}} must set app"
        pattern:
          metadata:
            labels:
              app: "?*"
"""


def host_answer(scanner, prog, resource, engine=None):
    policy = scanner.policies[prog.policy_index]
    return Validator(engine or scanner.engine,
                     PolicyContext(policy, new_resource=resource),
                     Rule(prog.rule_raw)).validate()


def assert_same_response(direct, host):
    assert (direct is None) == (host is None)
    if host is None:
        return
    for field in FIELDS:
        assert getattr(direct, field) == getattr(host, field), field
    assert vars(direct) == vars(host)
    assert direct.to_dict() == host.to_dict()


def program(scanner, rule_name):
    [prog] = [p for p in scanner.cps.programs if p.rule_name == rule_name]
    return prog


@pytest.fixture(scope='module')
def pss_scanner():
    return BatchScanner(benchlib.load_policies(['pss']))


@pytest.fixture(scope='module')
def extra_scanner():
    return BatchScanner([Policy(d) for d in yaml.safe_load_all(EXTRA) if d])


@pytest.fixture(scope='module')
def cluster():
    return mixed_cluster.generate(7, 2048)


@pytest.fixture
def validators(monkeypatch):
    """Counts the Validators the scanner builds."""
    built = []

    class Counting(Validator):
        def __init__(self, *args, **kwargs):
            built.append(args[2].name)
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(scan_mod, 'Validator', Counting)
    return built


@pytest.fixture
def no_policy_context(monkeypatch):
    """Any PolicyContext made while this is on fails the test."""
    def refuse(self, *args, **kwargs):
        raise AssertionError('a PolicyContext was constructed')

    monkeypatch.setattr(engine_api.PolicyContext, '__init__', refuse)
    monkeypatch.setattr(engine_api.PolicyContext, 'copy', refuse)


@pytest.mark.parametrize('level', ['baseline', 'restricted'])
@pytest.mark.parametrize('kind, prefix', [('Pod', ''),
                                          ('Deployment', 'autogen-')])
def test_direct_answer_is_the_validators(pss_scanner, cluster, validators,
                                         level, kind, prefix):
    prog = program(pss_scanner, prefix + level)
    policy = pss_scanner.policies[prog.policy_index]
    key, scored, category, severity = _policy_static(policy)
    stamp = {'seconds': 1}
    docs = [r for r in cluster if r['kind'] == kind]
    assert len(docs) > 500
    statuses = set()
    for doc in docs:
        direct = pss_scanner._materialize(prog, doc)
        host = host_answer(pss_scanner, prog, doc)
        assert_same_response(direct, host)
        assert _rule_result(direct, key, scored, category, severity,
                            stamp, 1) == \
            _rule_result(host, key, scored, category, severity, stamp, 1)
        statuses.add(direct.status)
    assert RuleStatus.FAIL in statuses
    if level == 'baseline':
        assert RuleStatus.PASS in statuses
    assert validators == []


@pytest.mark.parametrize('level', ['baseline', 'restricted'])
def test_cronjob_template(pss_scanner, cluster, validators, level):
    prog = program(pss_scanner, f'autogen-cronjob-{level}')
    for i, dep in enumerate(
            [r for r in cluster if r['kind'] == 'Deployment'][:64]):
        doc = {'apiVersion': 'batch/v1', 'kind': 'CronJob',
               'metadata': {'name': f'cj{i}', 'namespace': 'default'},
               'spec': {'schedule': '* * * * *', 'jobTemplate': {
                   'spec': {'template': dep['spec']['template']}}}}
        direct = pss_scanner._materialize(prog, doc)
        assert_same_response(direct, host_answer(pss_scanner, prog, doc))
        # the template's own verdict, read where a CronJob keeps it
        assert direct.status == pss_scanner._materialize(
            program(pss_scanner, f'autogen-{level}'), dep).status
    assert validators == []


def test_unsupported_kind_is_the_error_response(pss_scanner, validators):
    prog = program(pss_scanner, 'baseline')
    doc = {'apiVersion': 'v1', 'kind': 'Service',
           'metadata': {'name': 's', 'namespace': 'default'},
           'spec': {'ports': [{'port': 80}]}}
    direct = pss_scanner._materialize(prog, doc)
    assert direct.status == RuleStatus.ERROR
    assert direct.rule_type == RuleType.VALIDATION
    assert direct.message.startswith('Error while getting new resource: ')
    assert direct.pod_security_checks is None
    assert_same_response(direct, host_answer(pss_scanner, prog, doc))
    assert validators == []


def test_invalid_version_is_the_error_response(cluster):
    """Such a rule never compiles (no program carries it): the shared
    function is asked itself, and the Validator beside it."""
    block = {'level': 'baseline', 'version': 'v9x'}
    doc = next(r for r in cluster if r['kind'] == 'Pod')
    direct = pod_security_response('bad', block, doc, evaluate_pod_security)
    assert direct.status == RuleStatus.ERROR
    assert direct.message.startswith(
        'failed to parse pod security api version: ')
    policy = Policy({'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
                     'metadata': {'name': 'p'}, 'spec': {'rules': []}})
    rule = Rule({'name': 'bad', 'validate': {'podSecurity': block}})
    host = Validator(Engine(), PolicyContext(policy, new_resource=doc),
                     rule).validate()
    assert_same_response(direct, host)
    scanner = BatchScanner([Policy({
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': 'p', 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {'rules': [dict(rule.raw, match={'any': [
            {'resources': {'kinds': ['Pod']}}]})]}})])
    assert not scanner.cps.programs and scanner.cps.host_rules


def test_empty_document_is_none_and_the_validators(pss_scanner, validators):
    prog = program(pss_scanner, 'restricted')
    assert pss_scanner._materialize(prog, {}) is None
    assert validators == ['restricted']


@pytest.mark.parametrize('outcome', ['allowed', 'forbidden', 'raises'])
def test_the_engines_own_evaluator_is_called_and_obeyed(cluster, outcome):
    calls = []
    check = {'id': 'mine', 'checkResult': {
        'allowed': False, 'forbiddenReason': 'because',
        'forbiddenDetail': 'of this'}}

    def evaluator(block, pod):
        calls.append((block, pod))
        if outcome == 'raises':
            raise ValueError('no such version')
        return (True, []) if outcome == 'allowed' else (False, [check])

    engine = Engine(pss_evaluator=evaluator)
    scanner = BatchScanner(benchlib.load_policies(['pss']), engine=engine)
    prog = program(scanner, 'baseline')
    doc = next(r for r in cluster if r['kind'] == 'Pod')
    direct = scanner._materialize(prog, doc)
    assert len(calls) == 1
    assert calls[0][0] == {'level': 'baseline', 'version': 'latest'}
    assert calls[0][1]['spec'] is doc['spec']
    assert direct.status == {'allowed': RuleStatus.PASS,
                             'forbidden': RuleStatus.FAIL,
                             'raises': RuleStatus.ERROR}[outcome]
    if outcome == 'forbidden':
        assert direct.pod_security_checks['checks'] == [check]
        assert 'because' in direct.message
    assert_same_response(direct, host_answer(scanner, prog, doc, engine))
    assert len(calls) == 2


@pytest.mark.parametrize('rule_name', ['gated', 'loaded'])
def test_a_rule_that_reads_more_than_the_resource_keeps_the_validator(
        extra_scanner, cluster, validators, rule_name):
    prog = program(extra_scanner, rule_name)
    assert prog.pss is not None
    docs = [r for r in cluster if r['kind'] == 'Pod'][:32]
    docs[0] = dict(docs[0], metadata=dict(docs[0]['metadata'],
                                          name='skipme'))
    for doc in docs:
        assert_same_response(extra_scanner._materialize(prog, doc),
                             host_answer(extra_scanner, prog, doc))
    assert validators == [rule_name] * len(docs)
    if rule_name == 'gated':
        assert extra_scanner._materialize(prog, docs[0]).status == \
            RuleStatus.SKIP


def test_a_non_pss_program_is_untouched(extra_scanner, cluster, validators):
    prog = program(extra_scanner, 'app-label')
    assert prog.pss is None
    docs = [r for r in cluster if r['kind'] == 'Pod'][:32]
    for doc in docs:
        assert_same_response(extra_scanner._materialize(prog, doc),
                             host_answer(extra_scanner, prog, doc))
    assert validators == ['app-label'] * len(docs)


def test_no_policy_context_on_the_direct_path(pss_scanner, cluster,
                                              no_policy_context):
    for prog in pss_scanner.cps.programs:
        for doc in cluster[:64]:
            assert pss_scanner._materialize(prog, doc) is not None
    with pytest.raises(AssertionError, match='PolicyContext'):
        pss_scanner._materialize(program(pss_scanner, 'baseline'), {})


def test_an_admission_cell_reads_the_requests_own_context(
        pss_scanner, cluster, validators, monkeypatch):
    """With a factory the Validator reads ``pctx.new_resource``: so does
    the direct call, from the context as the batcher hands it over."""
    prog = program(pss_scanner, 'restricted')
    policy = pss_scanner.policies[prog.policy_index]
    row, other = [r for r in cluster if r['kind'] == 'Pod'][:2]
    pctx = PolicyContext(policy, new_resource=other)
    monkeypatch.setattr(pss_scanner, '_pctx_factory', lambda doc: pctx,
                        raising=False)
    monkeypatch.setattr(engine_api.PolicyContext, 'copy', None)
    direct = pss_scanner._materialize(prog, row)
    assert_same_response(direct, host_answer(pss_scanner, prog, other))
    assert validators == []


def test_every_cell_pays_its_own_call(cluster):
    """Nothing is kept of a check's result: not between the copies of a
    policy, not between levels, not between equal rows, not between
    scans."""
    calls = []

    def evaluator(block, pod):
        calls.append(block['level'])
        return evaluate_pod_security(block, pod)

    policies = benchlib.replicate_enforce(benchlib.load_policies(['pss']), 6)
    scanner = BatchScanner(policies, engine=Engine(pss_evaluator=evaluator))
    doc = next(r for r in cluster if r['kind'] == 'Pod')
    progs = [p for p in scanner.cps.programs
             if (p.rule_raw['match']['any'][0]['resources']['kinds']
                 == ['Pod'])]
    assert len(progs) == 6
    answers = [scanner._materialize(p, doc) for p in progs for _ in (0, 1)]
    assert len(calls) == 12
    assert len({id(a) for a in answers}) == 12
    assert len({id(a.pod_security_checks) for a in answers}) == 12


def test_program_constants_are_made_once_a_scanner(pss_scanner, cluster,
                                                   monkeypatch):
    made = []
    real = Rule.__init__

    def counting(self, raw):
        made.append(raw.get('name'))
        real(self, raw)

    monkeypatch.setattr(Rule, '__init__', counting)
    for prog in pss_scanner.cps.programs:
        for doc in cluster[:16]:
            pss_scanner._materialize(prog, doc)
    pss_scanner._materialize(program(pss_scanner, 'baseline'), {})
    assert made == []
