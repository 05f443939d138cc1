"""Multi-tenant admission: namespaced ``Policy`` objects beside the
cluster-wide pack, served by ONE compiled validate set.

The installed set of a kind (``policycache.Cache.get_installed``: cluster
policies and every namespace's own) is what the webhook compiles and keys
its scanner, breaker and batch on; the policies that apply to a request
(``get_policies(kind, namespace)``) decide which responses exist for it.
The ``device=False`` chain is the reference, byte for byte.  Small: the
committed pack and 12 tenant namespaces x 4 policies from the benchmark's
generator (``benchmarks/generators/tenant_policies.py``).
"""

import copy
import json
import os
import sys
import threading

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (ROOT, os.path.join(ROOT, 'benchmarks')):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import benchlib  # noqa: E402
from kyverno_tpu.api.policy import Policy  # noqa: E402
from kyverno_tpu.policycache import cache as pcache  # noqa: E402
from kyverno_tpu.webhooks.handlers import ResourceHandlers  # noqa: E402
from kyverno_tpu.webhooks.server import WebhookServer  # noqa: E402

NAMESPACES = 12
PACKS = ['pss', 'pack', 'config4']

tenant_policies = benchlib.load_module('generators', 'tenant_policies')
tenant_reviews = benchlib.load_module('generators', 'tenant_reviews')
mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')
reference = benchlib.load_module('reference', 'tenants')


def cluster_pack() -> list:
    out = []
    for policy in benchlib.load_policies(PACKS):
        doc = copy.deepcopy(policy.raw)
        doc.setdefault('spec', {})['validationFailureAction'] = 'Enforce'
        out.append(Policy(doc))
    return out


def answer(server, body) -> dict:
    return json.loads(server.handle('/validate/fail', body))['response']


def review(i: int, pod: dict, operation: str = 'CREATE') -> bytes:
    request = {'uid': f'u-{i}', 'operation': operation,
               'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
               'namespace': pod['metadata'].get('namespace', ''),
               'name': pod['metadata']['name'], 'object': pod,
               'userInfo': {'username': 'dev', 'groups': ['team-1']}}
    return json.dumps({'apiVersion': 'admission.k8s.io/v1',
                       'kind': 'AdmissionReview',
                       'request': request}).encode()


class Chain:
    """A batch-mode webhook over ``policies`` with its compiled set up,
    and the same chain with the device path off."""

    def __init__(self, policies):
        self.cache = pcache.Cache()
        self.cache.warm_up(policies)
        self.handlers = ResourceHandlers(self.cache, serving_mode='batch')
        self.server = WebhookServer(self.handlers)
        self.host = WebhookServer(ResourceHandlers(self.cache, device=False))
        self.wait()

    def installed(self):
        return self.cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod')

    def wait(self):
        assert self.handlers.wait_device_ready(self.installed(), timeout=600)

    def stats(self):
        return self.handlers._get_batcher().stats()

    def close(self):
        self.server.stop()
        self.host.stop()


@pytest.fixture(scope='module')
def tenant_docs():
    return tenant_policies.generate(0, NAMESPACES, 4)


@pytest.fixture(scope='module')
def tenants(tenant_docs):
    chain = Chain(cluster_pack() + [Policy(copy.deepcopy(d))
                                    for d in tenant_docs])
    yield chain
    chain.close()


@pytest.fixture(scope='module')
def bodies():
    """200 seeded requests over tenant and platform namespaces, and every
    seventh moved out of any namespace."""
    cluster = mixed_cluster.generate(7, n=256, deployment_share=0.3)
    out = tenant_reviews.generate(7, cluster, 200, namespaces=NAMESPACES,
                                  platform_namespaces=3, platform_share=0.2)
    for i in range(0, len(out), 7):
        doc = json.loads(out[i])
        doc['request']['namespace'] = ''
        doc['request']['object']['metadata'].pop('namespace', None)
        (doc['request'].get('oldObject') or {'metadata': {}})[
            'metadata'].pop('namespace', None)
        out[i] = json.dumps(doc).encode()
    return out


# -- (a) the answers ----------------------------------------------------------

@pytest.mark.parametrize('part', range(8))
def test_answers_equal_the_host_chains_byte_for_byte(tenants, bodies, part):
    before = tenants.stats()['device_path_requests']
    kinds = set()
    for body in bodies[part::8]:
        got = tenants.server.handle('/validate/fail', body)
        assert got == tenants.host.handle('/validate/fail', body)
        ns = json.loads(body)['request']['namespace']
        kinds.add('none' if not ns else ns.split('-')[0])
    assert kinds == {'tenant', 'platform', 'none'}
    assert tenants.stats()['device_path_requests'] - before == \
        len(bodies[part::8])


def test_both_answers_occur_in_tenant_and_platform_namespaces(tenants,
                                                              bodies):
    seen = set()
    for body in bodies:
        ns = json.loads(body)['request']['namespace']
        seen.add((ns.split('-')[0], answer(tenants.server, body)['allowed']))
    assert {('tenant', True), ('tenant', False), ('platform', True),
            ('platform', False)} <= seen


@pytest.mark.parametrize('operation', ['CREATE', 'UPDATE'])
def test_another_tenants_registry_is_denied_here_and_admitted_there(
        tenants, operation):
    """The case that proves scoping: the image comes from tenant-003's
    registry; tenant-003's policy admits it, tenant-005's must deny it."""
    def pod_in(ns):
        return tenant_reviews.tenant_pod(
            1, ns, ns, tenant_policies.registry('tenant-003'), '128Mi')

    def send(ns):
        pod = pod_in(ns)
        doc = json.loads(review(1, pod, operation))
        if operation == 'UPDATE':
            old = copy.deepcopy(pod)
            old['metadata']['labels']['rev'] = 'old'
            doc['request']['oldObject'] = old
        body = json.dumps(doc).encode()
        got = tenants.server.handle('/validate/fail', body)
        assert got == tenants.host.handle('/validate/fail', body)
        return json.loads(got)['response']

    assert send('tenant-003')['allowed'] is True
    denied = send('tenant-005')
    assert denied['allowed'] is False
    message = denied['status']['message']
    assert 'Images in tenant-005 must come from' in message
    assert 'tenant-003 must' not in message
    assert send('platform-0')['allowed'] is True


# -- (b) one scanner, mixed batches -------------------------------------------

def test_one_scanner_is_built_however_many_namespaces_send(tenants, bodies):
    namespaces = {json.loads(b)['request']['namespace'] for b in bodies}
    assert len(namespaces) > NAMESPACES
    for body in bodies[:60]:
        tenants.server.handle('/validate/fail', body)
    stats = tenants.stats()
    assert stats['scanner_builds'] == 1
    assert stats['host_loop_requests'] == 0 and stats['shed_total'] == 0
    assert [k[0] for k in tenants.handlers._scanners] == ['validate']


def test_two_namespaces_ride_one_dispatch(tenants):
    from kyverno_tpu.serving.batcher import AdmissionBatcher
    prior = tenants.handlers._batcher
    tenants.handlers._batcher = AdmissionBatcher(
        window_ms=400, on_success=tenants.handlers._batch_scan_ok,
        on_failure=tenants.handlers._batch_scan_failed)
    try:
        pods = [tenant_reviews.tenant_pod(
            i, ns, ns, tenant_policies.registry('tenant-001'), '128Mi')
            for i, ns in enumerate(['tenant-001', 'tenant-002',
                                    'platform-1'])]
        answers = {}

        def send(i):
            answers[i] = answer(tenants.server, review(i, pods[i]))

        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(len(pods))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        stats = tenants.stats()
        assert (stats['dispatches'], stats['requests']) == (1, 3)
        assert stats['device_path_requests'] == 3
        # each row got the policies of its own namespace in that dispatch
        assert [answers[i]['allowed'] for i in range(3)] == \
            [True, False, True]
    finally:
        tenants.handlers._batcher.stop(drain=True)
        tenants.handlers._batcher = prior


# -- (c) the candidates -------------------------------------------------------

@pytest.mark.parametrize('namespace, own', [
    ('tenant-004', 4), ('platform-2', 0), ('', 0)])
def test_candidates_are_the_cluster_policies_and_the_namespaces_own(
        tenants, namespace, own):
    pod = tenant_reviews.tenant_pod(3, namespace, namespace,
                                    'registry.example.com/x', '128Mi')
    if not namespace:
        del pod['metadata']['namespace']
    batcher = tenants.handlers._get_batcher()
    batcher.reset_stats()
    got = tenants.server.handle('/validate/fail', review(3, pod))
    assert got == tenants.host.handle('/validate/fail', review(3, pod))
    stats = batcher.stats()
    cluster = len(cluster_pack())
    assert stats['candidate_policies'] == cluster + own
    assert stats['installed_policies'] == cluster + 4 * NAMESPACES
    assert stats['device_path_requests'] == 1
    scanner = tenants.handlers._device_scanner(tenants.installed())
    can = scanner._candidates(namespace)
    policies_of = {scanner.cps.programs[j].policy_index
                   for j in can.nonzero()[0]}
    assert len(policies_of) == cluster + own


def test_the_sieve_walks_the_candidates_and_not_the_installed_set(tenants):
    """A namespace the scanner has not seen costs one ``_match_one`` for
    each program that can apply there, not one for each program."""
    scanner = tenants.handlers._device_scanner(tenants.installed())
    calls = []
    real = scanner._match_one

    def counted(j, res, admission=None):
        calls.append(j)
        return real(j, res, admission)

    scanner._match_one = counted
    try:
        with scanner._match_cache_lock:
            scanner._match_cache.clear()
        pod = tenant_reviews.tenant_pod(5, 'tenant-009', 'tenant-009',
                                        'registry.example.com/x', '1Gi')
        old = copy.deepcopy(pod)
        old['metadata']['labels']['rev'] = 'old'
        [responses] = scanner.scan(
            [pod], admissions=[(None, [], {}, 'UPDATE')],
            old_resources=[old], pctx_factory=lambda doc: None)
    finally:
        del scanner._match_one
    can = int(scanner._candidates('tenant-009').sum())
    assert 0 < len(calls) <= can == 15 + 4
    assert len(scanner.cps.programs) == 15 + 4 * NAMESPACES
    assert {r.policy.namespace for r in responses} == {'', 'tenant-009'}


def test_a_set_without_namespaced_policies_assembles_every_policy():
    """``admission-1k-enforce``'s shape: every policy applies to every
    request, the candidates are the installed set, one build."""
    chain = Chain(benchlib.replicate_enforce(
        benchlib.load_policies(PACKS), 22))
    try:
        scanner = chain.handlers._device_scanner(chain.installed())
        assert scanner._candidates('ns-0') is None
        cluster = mixed_cluster.generate(3, n=64, deployment_share=0.0)
        sent = 0
        for i, pod in enumerate(cluster[:12]):
            body = review(i, pod)
            assert chain.server.handle('/validate/fail', body) == \
                chain.host.handle('/validate/fail', body)
            sent += 1
        stats = chain.stats()
        assert stats['device_path_requests'] == sent
        assert stats['host_loop_requests'] == 0
        assert stats['host_loop'] == {'building': 0, 'breaker': 0,
                                      'shed': 0}
        assert stats['candidate_policies'] == \
            stats['installed_policies'] == 22 * sent
        assert stats['scanner_builds'] == 1
    finally:
        chain.close()


# -- (d) a policy added and removed mid-stream --------------------------------

def test_a_policy_added_to_and_removed_from_one_namespace(tenant_docs):
    docs = [d for d in tenant_docs
            if d['metadata']['namespace'] in ('tenant-000', 'tenant-001')]
    extra = next(d for d in docs if d['metadata']['namespace'] ==
                 'tenant-001' and d['metadata']['name'] == 'limit-memory')
    docs = [d for d in docs if d is not extra]
    chain = Chain(cluster_pack() + [Policy(copy.deepcopy(d)) for d in docs])
    over = tenant_reviews.tenant_pod(
        8, 'tenant-001', 'tenant-001',
        tenant_policies.registry('tenant-001'), '1.5Gi')
    elsewhere = tenant_reviews.tenant_pod(
        9, 'tenant-000', 'tenant-000',
        tenant_policies.registry('tenant-000'), '128Mi')

    def both(i, pod, allowed):
        body = review(i, pod)
        got = chain.server.handle('/validate/fail', body)
        assert got == chain.host.handle('/validate/fail', body)
        assert json.loads(got)['response']['allowed'] is allowed

    try:
        both(0, over, True)            # tenant-001 has no memory cap yet
        both(1, elsewhere, True)
        assert chain.stats()['device_path_requests'] == 2

        policy = Policy(copy.deepcopy(extra))
        chain.cache.set(policy.get_kind_and_name(), policy)
        both(2, over, False)           # the host loop, or the successor
        both(3, elsewhere, True)
        chain.wait()
        before = chain.stats()['device_path_requests']
        both(4, over, False)           # the successor
        both(5, elsewhere, True)
        assert chain.stats()['device_path_requests'] - before == 2
        assert chain.stats()['scanner_builds'] == 2

        chain.cache.unset(policy.get_kind_and_name())
        both(6, over, True)
        chain.wait()
        before = chain.stats()['device_path_requests']
        both(7, over, True)
        both(8, elsewhere, True)
        assert chain.stats()['device_path_requests'] - before == 2
        stats = chain.stats()
        assert stats['scanner_builds'] == 3
        assert stats['host_loop']['breaker'] == stats['shed_total'] == 0
        # the predecessors were swapped out, not kept beside the successor
        assert len(chain.handlers._scanners) == 1
    finally:
        chain.close()


def test_the_predecessor_serves_while_the_successor_compiles(
        tenant_docs, monkeypatch):
    """A policy added to one namespace changes the installed set for every
    namespace; until the successor is installed the predecessor's scanner
    answers every request whose own policies it holds (the other
    namespaces, and the changed one after a removal), and only a request
    whose list holds the new policy is left to the host loop."""
    from kyverno_tpu.compiler.scan import BatchScanner
    docs = [d for d in tenant_docs
            if d['metadata']['namespace'] in ('tenant-000', 'tenant-001')]
    extra = next(d for d in docs if d['metadata']['namespace'] ==
                 'tenant-001' and d['metadata']['name'] == 'limit-memory')
    docs = [d for d in docs if d is not extra]
    chain = Chain(cluster_pack() + [Policy(copy.deepcopy(d)) for d in docs])
    over = tenant_reviews.tenant_pod(
        8, 'tenant-001', 'tenant-001',
        tenant_policies.registry('tenant-001'), '1.5Gi')
    elsewhere = tenant_reviews.tenant_pod(
        9, 'tenant-000', 'tenant-000',
        tenant_policies.registry('tenant-000'), '128Mi')
    latest = copy.deepcopy(elsewhere)
    latest['spec']['containers'][0]['image'] = \
        tenant_policies.registry('tenant-000') + '/app:latest'
    gate = threading.Event()
    warmup = BatchScanner.warmup

    def held(self, *args, **kwargs):
        assert gate.wait(120)
        return warmup(self, *args, **kwargs)

    def both(i, pod, allowed):
        body = review(i, pod)
        got = chain.server.handle('/validate/fail', body)
        assert got == chain.host.handle('/validate/fail', body)
        assert json.loads(got)['response']['allowed'] is allowed

    def paths():
        stats = chain.stats()
        return stats['device_path_requests'], stats['host_loop']['building']

    try:
        first = chain.handlers._device_scanner(chain.installed())
        monkeypatch.setattr(BatchScanner, 'warmup', held)
        policy = Policy(copy.deepcopy(extra))
        chain.cache.set(policy.get_kind_and_name(), policy)
        device, building = paths()
        both(0, over, False)           # its list holds the new policy
        assert paths() == (device, building + 1)
        both(1, elsewhere, True)       # the predecessor holds all of its own
        both(2, latest, False)
        assert paths() == (device + 2, building + 1)
        assert chain.handlers._device_scanner(chain.installed()) is None
        gate.set()
        chain.wait()
        second = chain.handlers._device_scanner(chain.installed())
        assert second is not first
        both(3, over, False)
        assert paths() == (device + 3, building + 1)

        gate.clear()
        chain.cache.unset(policy.get_kind_and_name())
        both(4, over, True)            # a removal: the predecessor, kept to
        both(5, elsewhere, True)       # the request's own list
        assert paths() == (device + 5, building + 1)
        assert chain.handlers._device_scanner(chain.installed()) is None
        gate.set()
        chain.wait()
        both(6, over, True)
        stats = chain.stats()
        assert stats['scanner_builds'] == 3
        assert stats['host_loop']['breaker'] == stats['shed_total'] == 0
        assert len(chain.handlers._scanners) == 1 == \
            len(chain.handlers._scanner_sets)
    finally:
        gate.set()
        chain.close()


def test_an_override_takes_an_installed_policy_out_of_enforce_elsewhere():
    """A cluster policy that an override turns to Audit in one namespace
    is part of the installed (compiled) set and not of that namespace's
    own list: its response does not exist there."""
    doc = {
        'apiVersion': 'kyverno.io/v1', 'kind': 'ClusterPolicy',
        'metadata': {'name': 'no-latest-in-prod', 'annotations': {
            'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {
            'validationFailureAction': 'Enforce',
            'validationFailureActionOverrides': [
                {'action': 'Audit', 'namespaces': ['dev']}],
            'rules': [{
                'name': 'tag', 'match': {'any': [
                    {'resources': {'kinds': ['Pod']}}]},
                'validate': {'message': 'no latest in prod', 'pattern': {
                    'spec': {'containers': [{'image': '!*:latest'}]}}}}]}}
    plain = copy.deepcopy(doc)
    plain['metadata']['name'] = 'needs-app-label'
    plain['spec'] = {'validationFailureAction': 'Enforce', 'rules': [{
        'name': 'app', 'match': doc['spec']['rules'][0]['match'],
        'validate': {'message': 'app label', 'pattern': {
            'metadata': {'labels': {'app': '?*'}}}}}]}
    chain = Chain([Policy(doc), Policy(plain)])
    try:
        assert len(chain.installed()) == 2
        for i, (ns, allowed) in enumerate([('prod', False), ('dev', True)]):
            pod = {'apiVersion': 'v1', 'kind': 'Pod',
                   'metadata': {'name': 'p', 'namespace': ns,
                                'labels': {'app': 'a'}},
                   'spec': {'containers': [{'name': 'c',
                                            'image': 'nginx:latest'}]}}
            body = review(i, pod)
            got = chain.server.handle('/validate/fail', body)
            assert got == chain.host.handle('/validate/fail', body)
            assert json.loads(got)['response']['allowed'] is allowed
        stats = chain.stats()
        assert stats['device_path_requests'] == 2
        assert (stats['candidate_policies'],
                stats['installed_policies']) == (3, 4)
        assert stats['scanner_builds'] == 1
    finally:
        chain.close()


# -- (e) the plain reference against the engine -------------------------------

def _engine_admits(policy_doc: dict, pod: dict) -> bool:
    from kyverno_tpu.engine.api import PolicyContext, RuleStatus
    from kyverno_tpu.engine.engine import Engine
    response = Engine().validate(
        PolicyContext(Policy(copy.deepcopy(policy_doc)), new_resource=pod))
    statuses = [r.status for r in response.policy_response.rules]
    assert statuses and set(statuses) <= {RuleStatus.PASS, RuleStatus.FAIL}
    return statuses == [RuleStatus.PASS]


def _pod(images, memories, team='tenant-002') -> dict:
    containers = []
    for k, (image, memory) in enumerate(zip(images, memories)):
        c = {'name': f'c{k}'}
        if image is not None:
            c['image'] = image
        if memory is not None:
            c['resources'] = {'limits': {'memory': memory}}
        containers.append(c)
    labels = {'app': 'a'}
    if team is not None:
        labels['team'] = team
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': 'p', 'namespace': 'tenant-002',
                         'labels': labels},
            'spec': {'containers': containers}}


_OWN = 'registry.example.com/tenant-002'
REFERENCE_CASES = {
    # tenant-002's cap is 2Gi
    'limit-memory': [
        _pod([f'{_OWN}/a:v1'], [m]) for m in (
            '128Mi', '2Gi', '2048Mi', '2049Mi', '2.5Gi', '2G', '3G',
            '2147483648', '2147483649', '0.5Gi', '1e9', '100m', None)
    ] + [_pod([f'{_OWN}/a:v1', f'{_OWN}/b:v1'], ['1Gi', '4Gi'])],
    'restrict-image-registries': [
        _pod([image], ['1Gi']) for image in (
            f'{_OWN}/a:v1', f'{_OWN}/a', f'{_OWN}/team/a@sha256:00',
            'registry.example.com/tenant-003/a:v1',
            'registry.example.com/tenant-0020/a:v1', f'x{_OWN}/a:v1',
            'docker.io/library/busybox', _OWN, f'{_OWN}/')
    ] + [_pod([f'{_OWN}/a:v1', 'nginx:1.25'], ['1Gi', '1Gi'])],
    'disallow-latest-tag': [
        _pod([image], ['1Gi']) for image in (
            'nginx:latest', 'nginx', 'nginx:1.25', 'nginx:latest-alpine',
            'registry:5000/nginx', 'registry:5000/nginx:latest',
            'nginx@sha256:00')
    ] + [_pod(['nginx:1', 'redis:latest'], ['1Gi', '1Gi'])],
    'require-team-label': [
        _pod([f'{_OWN}/a:v1'], ['1Gi'], team=team) for team in (
            'tenant-002', 'tenant-003', 'tenant-0020', 'Tenant-002', '',
            None)],
}


@pytest.mark.parametrize('template, k', [
    (t, k) for t, pods in REFERENCE_CASES.items() for k in range(len(pods))])
def test_the_plain_reference_agrees_with_the_engine(tenant_docs, template,
                                                    k):
    policy = next(d for d in tenant_docs
                  if d['metadata']['namespace'] == 'tenant-002'
                  and d['metadata']['name'] == template)
    pod = REFERENCE_CASES[template][k]
    assert reference.applies(policy, 'tenant-002')
    assert not reference.applies(policy, 'tenant-003')
    assert not reference.applies(policy, '')
    assert reference.admits(policy, pod) == _engine_admits(policy, pod)


def test_the_reference_reads_kubernetes_quantities():
    q = reference.quantity
    assert q('1Gi') == 2 ** 30 and q('512Mi') == 2 ** 29
    assert q('0.5Gi') == q('512Mi') and q('1G') == 10 ** 9
    assert q('100m') * 10 == 1 and q('1e3') == q('1k') == 1000
    assert q('2049Mi') > q('2Gi') > q('2G')


# -- the benchmark's check of the denial message ------------------------------

_HEAD = '\n\npolicy Pod/tenant-001/p for resource violations: \n\n'
_OWN = ("restrict-image-registries:\n  validate-registries: 'validation "
        "error: Images in tenant-001 must come from\n    registry.example."
        "com/tenant-001/. rule validate-registries failed at path /spec/'\n")
_CLUSTER = ('disallow-latest-tag:\n  require-image-tag: An image tag is '
            'required.\n')


@pytest.mark.parametrize('message, failing, wrong', [
    (_HEAD + _CLUSTER + _OWN, ['tenant-001/restrict-image-registries'],
     None),
    (_HEAD + _CLUSTER, [], None),
    (_HEAD + _CLUSTER, ['tenant-001/restrict-image-registries'], 'names'),
    (_HEAD + _CLUSTER + _OWN, [], 'names'),
    (_HEAD + _OWN.replace('tenant-001', 'tenant-002'),
     ['tenant-001/restrict-image-registries'], 'word'),
    (_HEAD + _OWN + "limit-memory:\n  validate-memory-limit: Containers in "
     "tenant-002 need a memory limit of at most 2Gi.\n",
     ['tenant-001/restrict-image-registries'], 'names'),
    (_HEAD + _OWN + 'other:\n  rule: set by tenant-002\n',
     ['tenant-001/restrict-image-registries'], 'speaks of'),
])
def test_the_benchmark_holds_a_denial_to_the_failing_tenant_policies(
        tenant_docs, message, failing, wrong):
    driver = benchlib.load_module('drivers', 'webhook_tenants')
    texts = {f'{d["metadata"]["namespace"]}/{d["metadata"]["name"]}':
             (d['spec']['rules'][0]['name'],
              d['spec']['rules'][0]['validate']['message'])
             for d in tenant_docs}
    got = driver.message_problem(message, 'tenant-001', failing, texts)
    assert (got is None) if wrong is None else (wrong in got), got


def test_the_installed_set_holds_every_namespaces_list_in_its_order(
        tenant_docs):
    cache = pcache.Cache()
    cache.warm_up(cluster_pack() + [Policy(copy.deepcopy(d))
                                    for d in tenant_docs])
    installed = cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod')
    assert installed is cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod')
    assert len(installed) == 11 + 4 * NAMESPACES
    place = {id(p): k for k, p in enumerate(installed)}
    for ns in ('', 'platform-0', 'tenant-000', 'tenant-011'):
        own = cache.get_policies(pcache.VALIDATE_ENFORCE, 'Pod', ns)
        assert len(own) == 11 + (4 if ns.startswith('tenant') else 0)
        at = [place[id(p)] for p in own]
        assert at == sorted(at)
    policy = installed[-1]
    cache.unset(policy.get_kind_and_name())
    assert len(cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod')) == \
        len(installed) - 1
