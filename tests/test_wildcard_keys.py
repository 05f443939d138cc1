"""Device compilation of wildcard pattern KEYS under metadata
labels/annotations (reference: pkg/engine/wildcards/wildcards.go:62
ExpandInMetadata — the restrict-apparmor-profiles shape).

The device resolves the first matching map key at encode time; FAIL
messages embed the resolved key, so they re-materialize on the host —
statuses and messages must stay bit-identical to the host engine.
"""

import random

import pytest

from kyverno_tpu.api.policy import Policy, load_policies_from_yaml
from kyverno_tpu.compiler.compile import compile_policies
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.engine import Engine

APPARMOR = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: restrict-apparmor-profiles
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  background: true
  rules:
    - name: app-armor
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: >-
          Specifying other AppArmor profiles is disallowed.
        pattern:
          =(metadata):
            =(annotations):
              =(container.apparmor.security.beta.kubernetes.io/*): "runtime/default | localhost/*"
"""

LABEL_WILD = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: team-label
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  background: true
  rules:
    - name: team-label
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "team-* labels must name a platform team"
        pattern:
          metadata:
            labels:
              team-*: "platform | infra"
"""

AA_KEY = 'container.apparmor.security.beta.kubernetes.io'


def pod(name, annotations=None, labels=None, spec=None):
    meta = {'name': name, 'namespace': 'default'}
    if annotations is not None:
        meta['annotations'] = annotations
    if labels is not None:
        meta['labels'] = labels
    return {'apiVersion': 'v1', 'kind': 'Pod', 'metadata': meta,
            'spec': spec or {'containers': [{'name': 'c', 'image': 'i'}]}}


def host_results(policies, docs):
    engine = Engine()
    out = []
    for doc in docs:
        row = {}
        for policy in policies:
            resp = engine.apply_background_checks(
                PolicyContext(policy, new_resource=doc))
            row[policy.name] = {
                r.name: (str(r.status), r.message)
                for r in resp.policy_response.rules}
        out.append(row)
    return out


def device_results(policies, docs):
    scanner = BatchScanner(policies)
    out = []
    for responses in scanner.scan(docs):
        row = {}
        for er in responses:
            row[er.policy_response.policy_name] = {
                r.name: (str(r.status), r.message)
                for r in er.policy_response.rules}
        out.append(row)
    return out, scanner


class TestWildcardKeyCompile:
    def test_apparmor_rule_compiles_to_device(self):
        policies = load_policies_from_yaml(APPARMOR)
        cps = compile_policies(policies)
        assert not cps.host_rules, \
            'wildcard-key apparmor rule must compile to the device'
        assert len(cps.programs) == 1

    def test_full_pack_zero_host_rules(self):
        """The committed pack compiles with no apparmor rule left on
        the host."""
        import benchlib
        cps = compile_policies(
            benchlib.load_policies(['pss', 'pack', 'config4']))
        names = {r.get('name') for _, r, _ in cps.host_rules}
        assert all('app-armor' not in (n or '') for n in names), \
            f'apparmor rules still host-bound: {names}'

    def test_statuses_match_host(self):
        policies = load_policies_from_yaml(APPARMOR)
        docs = [
            pod('no-annotations'),
            pod('unrelated', annotations={'foo': 'bar'}),
            pod('ok-default', annotations={f'{AA_KEY}/c': 'runtime/default'}),
            pod('ok-localhost', annotations={f'{AA_KEY}/c': 'localhost/prof'}),
            pod('bad', annotations={f'{AA_KEY}/c': 'unconfined'}),
            pod('bad-second-key', annotations={
                'foo': 'bar', f'{AA_KEY}/x': 'unconfined'}),
            pod('first-match-wins', annotations={
                f'{AA_KEY}/a': 'runtime/default',
                f'{AA_KEY}/b': 'unconfined'}),
            pod('empty-annotations', annotations={}),
        ]
        host = host_results(policies, docs)
        dev, scanner = device_results(policies, docs)
        assert dev == host
        # sanity: the interesting rows actually exercise both outcomes
        assert host[4]['restrict-apparmor-profiles']['app-armor'][0] == 'fail'
        assert host[2]['restrict-apparmor-profiles']['app-armor'][0] == 'pass'

    def test_first_match_resolution_matches_host(self):
        """ExpandInMetadata picks the FIRST matching key in document
        order; later violating keys are invisible (host quirk kept)."""
        policies = load_policies_from_yaml(APPARMOR)
        doc = pod('first-wins', annotations={
            f'{AA_KEY}/a': 'runtime/default',
            f'{AA_KEY}/b': 'unconfined'})
        host = host_results(policies, [doc])
        dev, _ = device_results(policies, [doc])
        assert dev == host
        assert host[0]['restrict-apparmor-profiles']['app-armor'][0] == 'pass'

    def test_plain_wildcard_label_key(self):
        policies = load_policies_from_yaml(LABEL_WILD)
        cps = compile_policies(policies)
        assert not cps.host_rules
        docs = [
            pod('team-ok', labels={'team-a': 'platform'}),
            pod('team-bad', labels={'team-a': 'marketing'}),
            pod('no-match', labels={'app': 'x'}),
            pod('no-labels'),
        ]
        host = host_results(policies, docs)
        dev, _ = device_results(policies, docs)
        assert dev == host

    def test_fuzz_against_host(self):
        policies = load_policies_from_yaml(APPARMOR + '---\n' + LABEL_WILD)
        rng = random.Random(3)
        profiles = ['runtime/default', 'localhost/x', 'unconfined',
                    'docker/default', '']
        docs = []
        for i in range(200):
            annotations = {}
            labels = {}
            if rng.random() < 0.7:
                for k in range(rng.randint(0, 3)):
                    annotations[f'{AA_KEY}/c{k}'] = rng.choice(profiles)
            if rng.random() < 0.3:
                annotations['other/key'] = 'x'
            if rng.random() < 0.6:
                labels[f'team-{rng.randint(0, 2)}'] = rng.choice(
                    ['platform', 'infra', 'sales'])
            docs.append(pod(f'p{i}',
                            annotations=annotations or None,
                            labels=labels or None))
        host = host_results(policies, docs)
        dev, _ = device_results(policies, docs)
        assert dev == host

    def test_wildcard_outside_metadata_stays_host(self):
        yaml_doc = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: wild-spec
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: wild-spec
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: m
        pattern:
          spec:
            node*: "worker-*"
"""
        cps = compile_policies(load_policies_from_yaml(yaml_doc))
        assert len(cps.host_rules) == 1

    def test_multi_key_map_stays_host(self):
        """Sibling ordering under resolved keys is data-dependent —
        maps with >1 key alongside a wildcard key stay on the host."""
        yaml_doc = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: two-keys
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: two-keys
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: m
        pattern:
          metadata:
            annotations:
              =(x-*): "a"
              other: "b"
"""
        cps = compile_policies(load_policies_from_yaml(yaml_doc))
        assert len(cps.host_rules) == 1
