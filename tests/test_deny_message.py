"""The denial message of ``get_blocked_messages``, byte for byte.

``_dump_failures`` has two branches, and the branch decides the bytes: a
control character in any rule name or message has the whole map written as
the YAML emitter writes it (``_dump_as_emitter``, the emitter's rules for a
map of strings without its walk over every character), which must give
what ``yaml.safe_dump`` gives, with and without libyaml; every other map
takes the direct lines, held here to strings frozen at the commit before
the emitter branch changed
(``tests/fixtures/deny_message_direct.json``).  One denied request end to
end is held to ``tests/fixtures/deny_message_pss40.txt``, written at that
commit too.  The benchmark's answer check compares the device chain with a
host chain that shares this code, so it cannot see a changed message:
these tests are what holds it.
"""

import copy
import hashlib
import json
import os
import re

import pytest
import yaml
from hypothesis import given, settings, strategies as st

from kyverno_tpu.api.policy import Policy
from kyverno_tpu.policycache.cache import Cache
from kyverno_tpu.pss.evaluate import format_checks_print
from kyverno_tpu.webhooks import handlers
from kyverno_tpu.webhooks.handlers import ResourceHandlers
from kyverno_tpu.webhooks.server import WebhookServer

FIXTURES = os.path.join(os.path.dirname(__file__), 'fixtures')

PSS_MESSAGE = format_checks_print([
    {'id': 'privileged', 'checkResult': {
        'allowed': False, 'forbiddenReason': 'privileged',
        'forbiddenDetail': 'container "c0" must not set '
                           'securityContext.privileged=true'}},
    {'id': 'hostNamespaces', 'checkResult': {
        'allowed': False, 'forbiddenReason': 'host namespaces',
        'forbiddenDetail': 'hostNetwork=true'}}])

FIVE_BODIES = [
    {'baseline': 'Validation rule \'baseline\' failed. ' + PSS_MESSAGE},
    {'restricted': PSS_MESSAGE * 2},
    {'require-requests-limits': 'validation error: CPU and memory '
                                'resource requests and limits are '
                                'required. rule require-requests-limits '
                                'failed at path /spec/containers/0/\n'},
    {'validate-image-tag': "validation error: Using a mutable image tag "
                           "e.g. 'latest' is not allowed.\trule failed\n",
     'require-image-tag': 'validation error: An image tag is required.\n'},
    {'autogen-check': 'short\n'},
]

#: every case reaches the emitter branch: some name or message of the
#: map holds a control character
EMITTER_CASES = {
    'pss_two_checks': {'podsecurity-baseline-r0': {'baseline': PSS_MESSAGE}},
    'tab_and_cr': {'pol': {'rule': 'a\tb\rc', 'rule2': 'ends in cr\r'}},
    'quotes': {'pol': {
        'rule': 'it\'s a "quoted" \'\'doubled\'\' message\n',
        "r'q": '"'}},
    'colon_and_hash': {'pol': {'rule': 'key: value #not a comment\n',
                               'rule #2': 'a: b\n', 'k: v': ' # x\n'}},
    'edge_spaces': {'pol': {'rule': '  leading and trailing  \n',
                            'rule2': ' \n', 'rule3': 'trailing \n\n'}},
    'non_ascii': {'pol-é': {'règle': 'naïve café '
                                 '☃ 日本語 \U0001f600\n'}},
    'long_token': {'pol': {'rule': 'x' * 300 + '\n',
                           'rule2': 'y' * 300 + ' ' + 'z' * 300 + '\n'}},
    'empty_message': {'pol': {'rule': '', 'rule2': '\n'},
                      'pol2': {'': 'empty rule name\n'}},
    'long_rule_name': {'pol': {'r' * 140: 'long key\n',
                               'q' * 129 + ' with spaces': PSS_MESSAGE},
                       'p' * 200: {'rule': 'long policy name\n'}},
    'yaml_words_as_names': {
        'null': {'true': 'a\n', '1e3': 'b\n'},
        '0x1f': {'2001-01-01': 'c\n', 'null': 'd\n', '~': 'e\n'},
        '1e3': {'no': 'f\n', '123': 'null\n', '0o17': 'true\n'},
        'true': {'1_000': '1e3\n', '.inf': '2001-01-01\n'}},
    'thousand_policies_five_bodies': {
        # a dict of its own for each policy, as get_blocked_messages
        # builds them (safe_dump writes an alias for a shared object)
        f'policy-{i % 11}-r{i // 11}': dict(FIVE_BODIES[i % 5])
        for i in range(1000)},
    # every policy words its own message, as an install's own policies do
    'distinct_messages': {
        f'require-label-{i}': {
            f'check-label-{i}': f'validation error: label team-{i} is '
            f'required on every Pod of namespace ns-{i}, see '
            f'https://example.org/policies/{i}. rule check-label-{i} '
            f'failed at path /metadata/labels/team-{i}/\n'}
        for i in range(300)},
    # a simple key is under 128 characters with its !!str: 122 at most
    'simple_key_limit': {
        'p' * 122: {'r' * 122: 'a\n', 'r' * 123: 'b\n'},
        'p' * 123: {'r' * 122: 'c\n', 'é' * 122: 'd\n'},
        'two\nlines': {'rule': 'e\n', 'two\nlines': 'f\n'}},
    # lines fold at the first lone space past column 80; a double-quoted
    # one also right after an escape, with an escaped break
    'folds': {'pol': {
        'plain': ('word ' * 40).strip(),
        'single': 'it\'s ' * 40 + '\n\n' + 'b ' * 60 + 'end\n',
        'double': 'tab\there ' * 20 + 'é' * 30 + ' x' * 30 + '\n',
        'double-runs': 'a' * 79 + '\t\t\t  b  ' + 'c' * 90 + ' d\r',
        'wide  gaps': 'a' * 85 + '  b ' + 'c' * 85 + ' ' + 'd' * 5 + ' e\n'}},
    'out_of_order': {
        'zeta': {'z-rule': 'z\n', 'a-rule': 'a\n', 'm-rule': 'm\n'},
        'alpha': {'b': 'second\n', 'a': 'first\n'},
        'Mid': {'rule': 'capital sorts first\n'},
        'alpha-0': {'rule': '0\n'}},
}

_CONTROL = re.compile(r'[\x00-\x1f]')


def without_control(failures: dict) -> dict:
    """The same map with every control character taken out, so that it
    takes the direct branch."""
    strip = lambda s: _CONTROL.sub('', s)  # noqa: E731
    return {strip(pol): {strip(rule): strip(message)
                         for rule, message in rules.items()}
            for pol, rules in failures.items()}


DIRECT_CASES = {name: without_control(failures)
                for name, failures in EMITTER_CASES.items()}


def assert_same_bytes(got: str, expected: str) -> None:
    """``got == expected``, saying where they part: pytest's own diff of
    two strings of 200 kB does not end."""
    if got != expected:
        at = next((i for i, (a, b) in enumerate(zip(got, expected))
                   if a != b), min(len(got), len(expected)))
        pytest.fail(f'lengths {len(got)} and {len(expected)}, first '
                    f'difference at {at}: {got[at - 40:at + 40]!r} '
                    f'against {expected[at - 40:at + 40]!r}')


dump = handlers._dump_failures


def frozen(text: str) -> dict:
    """How the fixture keeps one string: whole when short, else by its
    digest and length."""
    if len(text) <= 4096:
        return {'text': text}
    return {'sha256': hashlib.sha256(text.encode('utf-8')).hexdigest(),
            'length': len(text)}


@pytest.mark.parametrize('name', sorted(EMITTER_CASES))
def test_emitter_branch_gives_safe_dumps_bytes(name):
    failures = EMITTER_CASES[name]
    assert any(_CONTROL.search(s) for rules in failures.values()
               for pair in rules.items() for s in pair)
    before = copy.deepcopy(failures)
    assert_same_bytes(dump(failures),
                      yaml.safe_dump(failures, default_flow_style=False))
    assert failures == before


def test_emitter_branch_without_libyaml(monkeypatch):
    """PyYAML built without libyaml has no ``CSafeDumper``: the message
    does not lean on it, same bytes."""
    for attr in ('CSafeDumper', 'CDumper', 'CEmitter'):
        monkeypatch.delattr(yaml, attr, raising=False)
    failures = EMITTER_CASES['pss_two_checks']
    assert_same_bytes(dump(failures),
                      yaml.safe_dump(failures, default_flow_style=False))


@pytest.mark.skipif(not yaml.__with_libyaml__,
                    reason='PyYAML was built without libyaml')
@pytest.mark.parametrize('name', ['empty_message', 'folds'])
def test_libyaml_would_change_the_bytes(name):
    """Why the emitter branch is not ``CSafeDumper``: libyaml folds a
    double-quoted line without the escaped break and writes an empty
    key as a simple one.  Should a later libyaml agree with PyYAML here,
    this says so."""
    failures = EMITTER_CASES[name]
    assert not (yaml.dump(failures, Dumper=yaml.CSafeDumper,
                          default_flow_style=False) ==
                yaml.safe_dump(failures, default_flow_style=False))


def test_a_map_met_twice_is_written_twice():
    """The one place ``_dump_as_emitter`` parts from ``safe_dump``, which
    writes an alias for a dict object it meets again;
    ``get_blocked_messages`` builds each policy's map afresh."""
    rules = {'rule': 'shared\n'}
    text = dump({'a': rules, 'b': rules})
    assert '&id001' in yaml.safe_dump({'a': rules, 'b': rules})
    assert text == dump({'a': dict(rules), 'b': dict(rules)})
    assert yaml.safe_load(text) == {'a': rules, 'b': rules}


@pytest.mark.parametrize('name', sorted(DIRECT_CASES))
def test_direct_branch_gives_the_frozen_bytes(name):
    failures = DIRECT_CASES[name]
    assert not any(_CONTROL.search(s) for rules in failures.values()
                   for pair in rules.items() for s in pair)
    with open(os.path.join(FIXTURES, 'deny_message_direct.json')) as f:
        expected = json.load(f)[name]
    assert frozen(dump(failures)) == expected


@pytest.mark.parametrize('name', [
    pytest.param(name, marks=pytest.mark.xfail(
        strict=True, reason='the direct branch writes the name 0x1f '
        'plain and YAML 1.1 reads it back as 31: a fidelity question '
        '(PERF.md section 7), the bytes are kept'))
    if name == 'yaml_words_as_names' else name
    for name in sorted(DIRECT_CASES)])
def test_direct_branch_parses_back(name):
    """The direct lines are YAML: a parser gives the map back."""
    failures = DIRECT_CASES[name]
    assert yaml.safe_load(dump(failures)) == failures


# -- one denied request, end to end -------------------------------------------

PSS_PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: podsecurity-baseline
spec:
  background: true
  validationFailureAction: Audit
  rules:
    - name: baseline
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        podSecurity:
          level: baseline
          version: latest
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: podsecurity-restricted
spec:
  background: true
  validationFailureAction: Audit
  rules:
    - name: restricted
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        podSecurity:
          level: restricted
          version: latest
"""

DENIED_POD = {
    'apiVersion': 'v1', 'kind': 'Pod',
    'metadata': {'name': 'web-0', 'namespace': 'ns-3',
                 'labels': {'app': 'web'}},
    'spec': {'hostNetwork': True,
             'containers': [
                 {'name': 'c0', 'image': 'nginx:latest',
                  'securityContext': {'privileged': True}},
                 {'name': 'c1', 'image': 'ghcr.io/org/app:v2.1',
                  'securityContext': {
                      'capabilities': {'add': ['NET_ADMIN']}}}]}}


def pss_enforce_policies(count: int) -> list:
    """The PSS pack copied round after round under new names, every copy
    in Enforce mode (as the benchmark replicates its pack)."""
    pack = list(yaml.safe_load_all(PSS_PACK))
    policies = []
    for i in range(count):
        doc = copy.deepcopy(pack[i % len(pack)])
        doc['metadata']['name'] += f'-r{i // len(pack)}'
        doc['spec']['validationFailureAction'] = 'Enforce'
        policies.append(Policy(doc))
    return policies


def denied_review_message(policies: int = 40) -> str:
    cache = Cache()
    cache.warm_up(pss_enforce_policies(policies))
    server = WebhookServer(ResourceHandlers(cache, device=False))
    body = json.dumps({
        'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
        'request': {
            'uid': 'golden-0', 'operation': 'CREATE',
            'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
            'namespace': 'ns-3', 'name': 'web-0', 'object': DENIED_POD,
            'userInfo': {'username': 'user-1',
                         'groups': ['system:authenticated']}}}).encode()
    response = json.loads(server.handle('/validate/fail', body))['response']
    assert response['allowed'] is False
    return response['status']['message']


def test_a_denied_review_carries_the_golden_message():
    with open(os.path.join(FIXTURES, 'deny_message_pss40.txt'),
              encoding='utf-8', newline='') as f:
        golden = f.read()
    message = denied_review_message()
    assert_same_bytes(message, golden)
    # the message is the emitter branch's: 40 policies, each with the
    # PSS print that ends in a newline
    assert message.count('podsecurity-') == 40
    assert '\\n' in message or '\n    ' in message


# -- any map at all -------------------------------------------------------------

_ALPHABET = list('ab-._01 :#\'"\n\t\r{}[],&*!|>%@`~?é☃') + [
    'null', 'true', 'no', 'yes', 'y', 'N', 'On', 'OFF', 'False', 'Null',
    '1e3', '0x1f', '2001-01-01', '---',
    '...', 'x' * 90, 'q' * 130, '- ', ': ', ' #', 'k' * 122, 'k' * 123,
    'a' * 77 + ' ', 'word word ', "it's ", 'caf\xe9 ', '\n\n', '\\', '\x85',
    '\u2028', '\ufeff', '\U0001f600', '=', '<<', '.inf', '12:30:45']
_TEXT = st.lists(st.sampled_from(_ALPHABET), max_size=6).map(''.join)
_BLOCKS = st.lists(st.dictionaries(_TEXT, _TEXT, min_size=1, max_size=3),
                   min_size=1, max_size=3)


@settings(max_examples=400, deadline=None)
@given(names=st.lists(_TEXT, min_size=1, max_size=8, unique=True),
       blocks=_BLOCKS, picks=st.lists(st.integers(0, 2), min_size=8,
                                      max_size=8))
def test_any_map_on_the_emitter_branch_is_safe_dumps(names, blocks, picks):
    failures = {name: dict(blocks[picks[i] % len(blocks)])
                for i, name in enumerate(names)}
    # make sure of the emitter branch, whatever was drawn
    failures[names[0]]['newline'] = 'ends in one\n'
    assert_same_bytes(dump(failures),
                      yaml.safe_dump(failures, default_flow_style=False))


def test_the_message_is_one_sample_of_its_stage_not_one_a_policy():
    from kyverno_tpu.observability import device as devtel
    from kyverno_tpu.observability.metrics import MetricsRegistry
    reg = devtel.configure(MetricsRegistry())
    try:
        denied_review_message(policies=12)
        samples = {dict(key)['stage']: count
                   for key, count, _total in reg.histogram_series(
                       devtel.SCAN_STAGE_DURATION)}
    finally:
        devtel.disable()
    assert samples['deny_message'] == 1
