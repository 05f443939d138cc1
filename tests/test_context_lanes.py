"""Context values as lanes (``compiler/context_lanes.py``, CondCheck mode C).

A condition whose ``value`` is one ``{{ expr }}`` over the rule's own
configMap / apiCall entries is a program, its verdict the device's: the
parent process resolves the value once per distinct tuple of the rule's
context inputs and ships it as per-row lanes, the load outcomes travel with
the chunk as a mask, and the host engine (``kyverno_tpu/engine``) is the
reference, row for row and message for message.

* the cell's pack (``benchmarks/packs/context.yaml``) and one variant per
  operator and value shape of the stop rule, over seeded resources of the
  cell's generator (Pods, Deployments, CronJobs);
* every "still host" shape under its own reason, at compile time and per
  cell;
* the plain reference of the cell against the engine;
* the webhook in batch mode against ``device=False``;
* a verdict does not outlive the ConfigMap it read.
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

context_cluster = benchlib.load_module('generators', 'context_cluster')
reference = benchlib.load_module('reference', 'context_rules')

SEED = 2 ** 31 + 4242
PARAMS = dict(n=360, namespaces=20, cronjob_share=0.06)
PACK = ('allowed-pod-priorities', 'cm-array-example',
        'exclude-namespaces-dynamically', 'tenant-allowed-tiers')

# the values the variants read: ConfigMap ``shapes`` in ``default``
SHAPES = {
    'list': '["web", "api"]',
    'scalar': 'web',
    'number': '2',
    'float': '2.5',
    'wild': '["w*", "api"]',
    'range': '1-3',
    'wide': json.dumps([f'tier-{i}' for i in range(20)]),
    'names': '["c0", "c1"]',
    'picked': '["pod-3", "pod-11", "deploy-7", "cron-5", "pod-20"]',
    'duration': '1h',
    'long': '["' + 'x' * 40 + '", "web"]',
}


def variant(name, key, operator, value, kinds=('Pod',), context=None,
            precondition=False):
    """One policy of the variants' pack: ``key operator value`` as a deny
    condition (or as the precondition of a rule that then denies), over
    the ConfigMap ``shapes``."""
    cond = {'key': key, 'operator': operator, 'value': value}
    rule = {
        'name': name,
        'match': {'any': [{'resources': {'kinds': list(kinds)}}]},
        'context': context or [{'name': 'shapes', 'configMap': {
            'name': 'shapes', 'namespace': 'default'}}],
        'validate': {'message': f'{name} denied',
                     'deny': {'conditions': {'any': [cond]}}}}
    if precondition:
        rule['preconditions'] = {'all': [cond]}
        rule['validate']['deny'] = {}
    return Policy({
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': name, 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {'background': True, 'validationFailureAction': 'Audit',
                 'rules': [rule]}})


TIER = "{{ request.object.metadata.labels.tier || '' }}"
VARIANTS = [
    variant('v-anyin-list', TIER, 'AnyIn', '{{ shapes.data.list }}'),
    variant('v-anynotin-list', TIER, 'AnyNotIn', '{{ shapes.data.list }}'),
    variant('v-allin-list', TIER, 'AllIn', '{{ shapes.data.list }}'),
    variant('v-allnotin-list', TIER, 'AllNotIn', '{{ shapes.data.list }}'),
    variant('v-anyin-scalar', TIER, 'AnyIn', '{{ shapes.data.scalar }}'),
    variant('v-equals-scalar', TIER, 'Equals', '{{ shapes.data.scalar }}'),
    variant('v-notequals-scalar', TIER, 'NotEquals',
            '{{ shapes.data.scalar }}'),
    # a number-like string: Equals reads it as a number (the host's cell),
    # the comparisons take it to the device
    variant('v-equals-numlike', '{{ length(request.object.spec.containers) }}',
            'Equals', '{{ shapes.data.number }}'),
    variant('v-greater-numlike',
            '{{ length(request.object.spec.containers) }}', 'GreaterThan',
            '{{ shapes.data.number }}'),
    variant('v-lessorequal-float',
            '{{ length(request.object.spec.containers) }}',
            'LessThanOrEquals', '{{ shapes.data.float }}'),
    variant('v-greaterorequal-duration',
            '{{ length(request.object.spec.containers) }}',
            'GreaterThanOrEquals', '{{ shapes.data.duration }}'),
    variant('v-lessthan-replicas', '{{ request.object.spec.replicas }}',
            'LessThan', '{{ shapes.data.number }}', kinds=('Deployment',)),
    # a key the ConfigMap does not have: no default, then the default
    variant('v-absent-key', TIER, 'AnyNotIn', '{{ shapes.data.nosuchkey }}'),
    variant('v-absent-default', TIER, 'AnyNotIn',
            "{{ shapes.data.nosuchkey || '' }}"),
    variant('v-wild', TIER, 'AnyIn', '{{ shapes.data.wild }}'),
    variant('v-range', '{{ length(request.object.spec.containers) }}',
            'AnyIn', '{{ shapes.data.range }}'),
    variant('v-wide', TIER, 'AnyNotIn', '{{ shapes.data.wide }}'),
    variant('v-long', TIER, 'AnyIn', '{{ shapes.data.long }}'),
    # a list key against the value's elements
    variant('v-listkey-anyin', '{{ request.object.spec.containers[].name }}',
            'AnyIn', '{{ shapes.data.names }}'),
    variant('v-listkey-allin', '{{ request.object.spec.containers[].name }}',
            'AllIn', '{{ shapes.data.names }}'),
    variant('v-listkey-allnotin',
            '{{ request.object.spec.containers[].name }}', 'AllNotIn',
            '{{ shapes.data.names }}'),
    # more distinct strings in a chunk than any static table held: every
    # resource's own name
    variant('v-names', '{{ request.object.metadata.name }}', 'AnyIn',
            '{{ shapes.data.picked }}', kinds=('Pod', 'Deployment',
                                               'CronJob')),
    # the value in a precondition
    variant('v-precondition', TIER, 'AnyIn', '{{ shapes.data.list }}',
            precondition=True),
    # an apiCall entry read by a condition: a real list, per namespace
    variant('v-apicall', TIER, 'AnyNotIn', '{{ quota.tiers }}', context=[
        {'name': 'quota', 'apiCall': {
            'urlPath': '/apis/example.io/v1/namespaces/'
                       '{{request.object.metadata.namespace}}/quota'}}]),
    # context whose values feed nothing: the mask alone
    Policy({'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
            'metadata': {'name': 'v-mask-only', 'annotations': {
                'pod-policies.kyverno.io/autogen-controllers': 'none'}},
            'spec': {'background': True, 'rules': [{
                'name': 'v-mask-only',
                'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
                'context': [{'name': 'tenantpolicy', 'configMap': {
                    'name': 'tenant-policy',
                    'namespace': '{{request.object.metadata.namespace}}'}}],
                'validate': {'message': 'needs an app label',
                             'pattern': {'metadata': {'labels': {
                                 'app': '?*'}}}}}]}}),
]
VARIANT_NAMES = [p.name for p in VARIANTS]


class Cluster:
    """The generator's cluster, its ConfigMaps behind a client's verbs,
    and an engine whose loader reads them."""

    def __init__(self):
        self.resources = context_cluster.generate(SEED, **PARAMS)
        self.config_maps = context_cluster.context_objects(SEED, **PARAMS)
        self.config_maps.append({
            'apiVersion': 'v1', 'kind': 'ConfigMap',
            'metadata': {'name': 'shapes', 'namespace': 'default'},
            'data': dict(SHAPES)})
        self.client = FakeClient()
        for cm in self.config_maps:
            self.client.create_resource('v1', 'ConfigMap',
                                        cm['metadata']['namespace'], cm)
        self.client.raw_abs_path = self.raw_abs_path
        self.engine = Engine(
            context_loader=make_context_loader(dclient=self.client))

    @staticmethod
    def raw_abs_path(path: str) -> bytes:
        # two namespaces with different lists, and one the API refuses
        ns = path.split('/namespaces/')[1].split('/')[0]
        n = int(ns.rsplit('-', 1)[1])
        if n % 7 == 3:
            raise RuntimeError(f'quota of {ns} is forbidden')
        return json.dumps({'tiers': ['web', 'cache'] if n % 2
                           else ['api', 'batch', 'web']}).encode()

    def host_rows(self, policies, doc):
        out = {}
        for policy in policies:
            resp = self.engine.apply_background_checks(
                PolicyContext(policy, new_resource=doc))
            if resp.policy_response.rules:
                out[policy.name] = [(r.name, r.status, r.message)
                                    for r in resp.policy_response.rules]
        return out


@pytest.fixture(scope='module')
def cluster():
    return Cluster()


@pytest.fixture(scope='module')
def scanned(cluster):
    """One scan of the cluster under the cell's pack and the variants,
    with the ledger on; ``(policies, device rows, host rows, ledger)``."""
    policies = benchlib.load_policies(['context']) + VARIANTS
    registry = benchlib.program_telemetry()
    try:
        scanner = BatchScanner(policies, engine=cluster.engine)
        assert not scanner.cps.host_rules
        got = []
        for responses in scanner.scan(cluster.resources):
            got.append({r.policy.name: [(x.name, x.status, x.message)
                                        for x in r.policy_response.rules]
                        for r in responses if r.policy_response.rules})
        want = [cluster.host_rows(policies, doc)
                for doc in cluster.resources]
        ledger = coverage.ledger().report()
        loads = {r: registry.counter_value(
            'kyverno_tpu_context_loads_total', result=r)
            for r in ('ok', 'failed')}
        lookups = registry.counter_value('kyverno_tpu_context_lookups_total')
    finally:
        coverage.disable()
        from kyverno_tpu.observability import device as devtel
        from kyverno_tpu.observability import executables as exectel
        devtel.disable()
        exectel.disable()
    return {'policies': policies, 'got': got, 'want': want,
            'ledger': ledger, 'loads': loads, 'lookups': lookups,
            'scanner': scanner}


def test_the_cluster_has_every_shape(cluster):
    kinds = {r['kind'] for r in cluster.resources}
    assert kinds == {'Pod', 'Deployment', 'CronJob'}
    assert len(cluster.resources) >= 300


@pytest.mark.parametrize('name', list(PACK) + VARIANT_NAMES)
def test_the_device_path_answers_as_the_engine(scanned, cluster, name):
    differing = [
        (doc['kind'], doc['metadata']['name'], want.get(name), got.get(name))
        for doc, want, got in zip(cluster.resources, scanned['want'],
                                  scanned['got'])
        if want.get(name) != got.get(name)]
    assert not differing, differing[:3]
    statuses = {row[1] for want in scanned['want']
                for row in want.get(name, [])}
    assert statuses, f'{name} matched nothing'


@pytest.mark.parametrize('name,statuses', [
    # the case has to show both answers, or it shows nothing
    ('allowed-pod-priorities', {'pass', 'fail'}),
    ('cm-array-example', {'pass', 'fail'}),
    ('exclude-namespaces-dynamically', {'pass', 'fail', 'skip'}),
    ('tenant-allowed-tiers', {'pass', 'fail', 'skip', 'error'}),
    ('v-anyin-list', {'pass', 'fail'}),
    ('v-allnotin-list', {'pass', 'fail'}),
    ('v-equals-scalar', {'pass', 'fail'}),
    ('v-greater-numlike', {'pass', 'fail'}),
    ('v-lessorequal-float', {'pass', 'fail'}),
    ('v-lessthan-replicas', {'pass', 'fail'}),
    ('v-absent-key', {'error'}),
    ('v-absent-default', {'pass', 'fail'}),
    ('v-listkey-anyin', {'fail'}),
    ('v-listkey-allin', {'pass', 'fail'}),
    ('v-names', {'pass', 'fail'}),
    ('v-precondition', {'fail', 'skip'}),
    ('v-apicall', {'pass', 'fail', 'error'}),
    ('v-mask-only', {'pass', 'error'}),
])
def test_the_case_shows_each_answer(scanned, name, statuses):
    seen = {row[1] for want in scanned['want'] for row in want.get(name, [])}
    assert seen == statuses


def _rule_rows(scanned, policy):
    return [r for r in scanned['ledger']['rules'] if r['policy'] == policy]


@pytest.mark.parametrize('name', [
    'v-anyin-list', 'v-anynotin-list', 'v-allin-list', 'v-allnotin-list',
    'v-anyin-scalar', 'v-equals-scalar', 'v-notequals-scalar',
    'v-greater-numlike', 'v-lessorequal-float', 'v-lessthan-replicas',
    'v-absent-default', 'v-listkey-anyin', 'v-listkey-allin',
    'v-listkey-allnotin', 'v-names', 'v-precondition', 'v-long'])
def test_the_verdicts_of_the_zone_are_the_devices(scanned, name):
    """A static message and a value inside the zone: no cell of the rule
    is the host's."""
    (row,) = _rule_rows(scanned, name)
    assert row['device_rows'] > 0 and row['host_rows'] == 0, row


@pytest.mark.parametrize('name,reason', [
    ('v-absent-key', 'context_value_unresolved'),
    ('v-wild', 'context_value_shape'),
    ('v-range', 'context_value_shape'),
    ('v-equals-numlike', 'context_value_shape'),
    ('v-greaterorequal-duration', 'context_value_shape'),
    ('v-wide', 'context_value_wide'),
    ('v-mask-only', 'context_load_failed'),
    ('tenant-allowed-tiers', 'context_load_failed'),
    ('v-apicall', 'context_load_failed'),
])
def test_what_the_lanes_cannot_carry_is_the_hosts_under_its_reason(
        scanned, name, reason):
    reasons = {}
    for row in _rule_rows(scanned, name):
        for key, rows in (row.get('fallback_reasons') or {}).items():
            reasons[key] = reasons.get(key, 0) + rows
    if not reasons:
        # the ledger keeps reasons per path, not per rule: the rule's host
        # rows and the path's reason have to be there
        assert sum(r['host_rows'] for r in _rule_rows(scanned, name)) > 0
        reasons = scanned['ledger']['fallbacks'].get('validate', {})
    assert reasons.get(reason, 0) > 0, reasons


def test_the_failed_loads_are_exactly_the_references(scanned, cluster):
    maps = reference.index(cluster.config_maps)
    named = sum(len(reference.load_failed(doc, maps))
                for doc in cluster.resources)
    got = sum(1 for rows in scanned['got']
              for r in rows.get('tenant-allowed-tiers', [])
              if r[1] == 'error')
    assert named == got > 0


def test_a_load_is_paid_once_a_distinct_input_tuple(scanned):
    # 20 namespaces, a dozen groups: the loads are a small share of the
    # (row, context) lookups, and the failed ones are among them
    assert scanned['lookups'] > 10 * (scanned['loads']['ok']
                                      + scanned['loads']['failed'])
    assert scanned['loads']['failed'] > 0
    groups = scanned['scanner']._ctx.groups
    assert scanned['loads']['ok'] + scanned['loads']['failed'] <= \
        len(groups) * PARAMS['namespaces']


@pytest.mark.parametrize('policy', PACK)
def test_the_plain_reference_answers_as_the_engine(scanned, cluster, policy):
    maps = reference.index(cluster.config_maps)
    for doc, want in zip(cluster.resources, scanned['want']):
        ours = sorted((rule, result, message)
                      for p, rule, result, message
                      in reference.rows(doc, maps) if p == policy)
        theirs = sorted((rule, status,
                         message if status == 'error' else None)
                        for rule, status, message in want.get(policy, []))
        assert ours == theirs, (doc['kind'], doc['metadata']['name'])


# -- still host, each shape under its own reason ------------------------------

def _rule(context, **body):
    return Policy({
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': 'p', 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {'background': True, 'rules': [dict(
            {'name': 'r', 'context': context,
             'match': {'any': [{'resources': {'kinds': ['Pod']}}]}},
            **body)]}})


_CM = [{'name': 'dict', 'configMap': {'name': 'shapes',
                                      'namespace': 'default'}}]


def _deny(key, value, operator='AnyIn'):
    return {'validate': {'deny': {'conditions': {'any': [
        {'key': key, 'operator': operator, 'value': value}]}}}}


STILL_HOST = {
    'context_in_pattern': _rule(_CM, validate={'pattern': {'metadata': {
        'labels': {'tier': '{{ dict.data.scalar }}'}}}}),
    'context_in_foreach': _rule(_CM, validate={'foreach': [{
        'list': 'request.object.spec.containers',
        'deny': {'conditions': {'any': [{
            'key': '{{ element.name }}', 'operator': 'AnyIn',
            'value': '{{ dict.data.names }}'}]}}}]}),
    'context_in_key': _rule(_CM, **_deny('{{ dict.data.scalar }}',
                                         ['web'])),
    'context_entry_kind': _rule(
        _CM + [{'name': 'v', 'variable': {'value': 'web'}}],
        **_deny(TIER, '{{ dict.data.list }}')),
    'api_call': _rule([{'name': 'img', 'imageRegistry': {
        'reference': 'nginx'}}], **_deny(TIER, '{{ img.manifest }}')),
    'context_value_expr': _rule(_CM, **_deny(
        TIER, 'tier-{{ dict.data.scalar }}')),
    'context_value_inputs': _rule(_CM, **_deny(
        TIER, '{{ dict.data."{{request.namespace}}" }}')),
}


@pytest.mark.parametrize('reason', sorted(STILL_HOST))
def test_a_shape_outside_the_stop_rule_stays_host_under_its_reason(reason):
    cps = compile_policies([STILL_HOST[reason]])
    assert not cps.programs and len(cps.host_rules) == 1
    (placement,) = cps.placements
    assert placement.placement == coverage.PLACEMENT_HOST
    assert placement.reason == reason and reason in coverage.REASONS


def test_a_bare_row_read_in_the_expression_stays_host():
    cps = compile_policies([_rule(_CM, **_deny(
        TIER, '{{ dict.data.list || request.object.metadata.name }}'))])
    assert cps.placements[0].reason == 'context_value_inputs'


def test_the_committed_packs_compile_to_no_host_rule():
    cps = compile_policies(
        benchlib.load_policies(['pss', 'pack', 'config4', 'context']))
    assert not cps.host_rules
    assert len(cps.programs) == 23 and len(cps.ctx_values) == 4
    assert sum(1 for p in cps.programs if p.ctx_values) == 8


def test_a_set_without_such_a_condition_gets_no_lane():
    from kyverno_tpu.compiler.context_lanes import ContextLanes
    cps = compile_policies(benchlib.load_policies(['pss', 'pack', 'config4']))
    lanes = ContextLanes(cps)
    assert not lanes and not lanes.signature and not cps.ctx_values
    assert lanes.zero_lanes(64) == {}


def _device_program(policies):
    """What the device runs for ``policies``: the packed layout of one
    admission-capacity batch and the evaluator's lowered text."""
    import jax
    import numpy as np
    from kyverno_tpu.compiler import admission
    from kyverno_tpu.compiler.context_lanes import ContextLanes
    from kyverno_tpu.compiler.encode import encode_batch
    from kyverno_tpu.compiler.scan import WARM_POD
    from kyverno_tpu.ops.eval import build_evaluator, pack_batch
    cps = compile_policies(policies)
    evaluator = build_evaluator(cps)
    tensors = encode_batch([WARM_POD], cps, padded_n=64).tensors()
    tensors['__match__'] = np.zeros((64, evaluator.n_uniq), np.uint8)
    if evaluator.adm_table is not None:
        tensors.update(admission.zero_lanes(evaluator.adm_table, 64))
    tensors.update(ContextLanes(cps).zero_lanes(64))
    packed, layout = pack_batch(tensors)
    with evaluator.compile_lock, jax.enable_x64(True):
        evaluator.layout_holder['layout'] = layout
        text = evaluator.jitted.lower(packed).as_text()
    return cps, layout, text


def _configuration_policies(name):
    packs = benchlib.load_policies(['pss', 'pack', 'config4'])
    if name == 'admission-1k-tenants':
        tenants = benchlib.load_module('generators', 'tenant_policies')
        return packs + [Policy(raw) for raw in
                        tenants.generate(SEED, namespaces=6)]
    if name == 'context-configmap-100k':
        return packs + benchlib.load_policies(['context'])
    return packs


@pytest.mark.parametrize('name', [
    # three configurations validate with the committed packs alone
    'pss-mixed-100k+admission-1k-enforce+admission-mutate-defaults',
    'admission-1k-tenants', 'context-configmap-100k'])
def test_a_messages_plan_changes_nothing_the_device_runs(name, monkeypatch):
    """``RuleProgram.message_inputs`` is the host's: compiled with it and
    without it (the parent commit's compiler), each of the five
    configurations' sets has the same lanes in the same layout and the
    same lowered program, and outside ``packs/context.yaml`` no program
    has a plan at all."""
    from kyverno_tpu.compiler import compile as compile_mod
    policies = _configuration_policies(name)
    cps, layout, text = _device_program(policies)
    planned = {p.policy_name for p in cps.programs
               if p.message_inputs is not None}
    assert planned == (set(PACK) if name == 'context-configmap-100k'
                       else set())
    monkeypatch.setattr(compile_mod, '_message_inputs',
                        lambda *a, **k: None)
    bare, bare_layout, bare_text = _device_program(policies)
    assert all(p.message_inputs is None for p in bare.programs)
    assert layout == bare_layout and list(layout) == list(bare_layout)
    assert text == bare_text


# -- chunks encoded by worker processes ---------------------------------------

def test_the_lanes_join_chunks_that_worker_processes_encoded(cluster,
                                                             monkeypatch):
    """A scan longer than one chunk: the workers (which have no client) lay
    the encoder's lanes over shared-memory blocks with the value lanes'
    columns kept free, the parent fills those, and packing is still a
    hand-over.  The reports are those of a one-chunk scan in process."""
    from kyverno_tpu.observability import device as devtel
    policies = benchlib.load_policies(['pack', 'context'])
    docs = cluster.resources

    def reports(scanner):
        return [(results, summary) for results, summary, _p
                in scanner.scan_report_results(docs, now=1234.0)]

    monkeypatch.setenv('KTPU_ENCODE_PROCS', '0')
    want = reports(BatchScanner(policies, engine=cluster.engine))
    monkeypatch.setenv('KTPU_ENCODE_PROCS', '2')
    registry = benchlib.program_telemetry()
    try:
        scanner = BatchScanner(policies, engine=cluster.engine)
        scanner.CHUNK = 128
        got = reports(scanner)
        chunks = registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                        result='ok')
        views = registry.counter_value(devtel.PACK_BATCHES, via='view')
    finally:
        scanner._encoder_pool.close()
        coverage.disable()
        devtel.disable()
        from kyverno_tpu.observability import executables as exectel
        exectel.disable()
    assert got == want
    assert chunks == views == -(-len(docs) // 128)


# -- the webhook --------------------------------------------------------------

def test_the_webhook_in_batch_mode_answers_as_the_host_chain(cluster):
    from kyverno_tpu.policycache import cache as pcache
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer
    doc = yaml.safe_load_all(
        open(benchlib.data_path('packs', 'context', '.yaml')))
    raw = next(d for d in doc if d['metadata']['name']
               == 'tenant-allowed-tiers')
    raw = copy.deepcopy(raw)
    raw['spec']['validationFailureAction'] = 'Enforce'
    cache = pcache.Cache()
    cache.warm_up([Policy(raw)])
    handlers = ResourceHandlers(cache, serving_mode='batch',
                                client=cluster.client)
    server = WebhookServer(handlers)
    host = WebhookServer(ResourceHandlers(cache, device=False,
                                          client=cluster.client))
    try:
        assert handlers.wait_device_ready(
            cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod'), timeout=600)
        pods = [r for r in cluster.resources if r['kind'] == 'Pod'][:60]
        seen = set()
        for i, pod in enumerate(pods):
            body = json.dumps({
                'apiVersion': 'admission.k8s.io/v1',
                'kind': 'AdmissionReview',
                'request': {
                    'uid': f'u-{i}', 'operation': 'CREATE',
                    'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
                    'namespace': pod['metadata']['namespace'],
                    'name': pod['metadata']['name'], 'object': pod,
                    'userInfo': {'username': 'dev'}}}).encode()
            got = server.handle('/validate/fail', body)
            assert got == host.handle('/validate/fail', body)
            seen.add(json.loads(got)['response']['allowed'])
        assert seen == {True, False}
        assert handlers._get_batcher().stats()['device_path_requests'] > 0
    finally:
        server.stop()
        host.stop()


# -- a verdict must not outlive the ConfigMap it read -------------------------

def test_an_edited_configmap_changes_the_reports_of_untouched_resources(
        cluster):
    from kyverno_tpu.reports.controllers import (BackgroundScanController,
                                                 MetadataCache)

    class Store(FakeClient):
        pass

    store = Store()
    maps = copy.deepcopy(cluster.config_maps)
    for cm in maps:
        store.create_resource('v1', 'ConfigMap',
                              cm['metadata']['namespace'], cm)
    policies = benchlib.load_policies(['context'])
    cache = MetadataCache()
    ctrl = BackgroundScanController(store, policies, cache=cache)
    deployments = [r for r in cluster.resources
                   if r['kind'] == 'Deployment'][:40]
    for r in deployments:
        cache.update(r)
    ctrl.enqueue_all()
    first = {r['metadata']['name']: copy.deepcopy(r['spec']['results'])
             for r in ctrl.reconcile()}
    assert len(first) == len(deployments)

    # nothing changed: every row is the version that was scanned
    ctrl.enqueue_all()
    assert ctrl.reconcile() == []

    # every role is allowed from now on; no resource is touched
    roles = store.get_resource('v1', 'ConfigMap', 'default',
                               'roles-dictionary')
    roles['data']['allowed-roles'] = json.dumps(context_cluster.ROLES + [''])
    store.update_resource('v1', 'ConfigMap', 'default', roles)
    ctrl.enqueue_all()
    second = {r['metadata']['name']: r['spec']['results']
              for r in ctrl.reconcile()}
    assert set(second) == set(first)

    def role_rows(results):
        return [r['result'] for r in results
                if r['policy'] == 'cm-array-example']
    assert any(role_rows(first[n]) == ['fail'] for n in first)
    assert all(role_rows(second[n]) == ['pass'] for n in second)
    ctrl.close()
