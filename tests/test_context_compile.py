"""Context-entry rules compile to device (VERDICT r4 #3): ConfigMap/
apiCall context entries whose values feed no compiled lane run on the
device path, with the host engine's load-failure semantics enforced per
resource (reference: pkg/engine/jsonContext.go:126,304)."""

import random

import pytest

from kyverno_tpu.api.policy import load_policies_from_yaml
from kyverno_tpu.compiler.compile import compile_policies
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.dclient.client import FakeClient
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.apicall import make_context_loader
from kyverno_tpu.engine.engine import Engine

CTX_PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: cm-context
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: needs-team-cm
      match: {any: [{resources: {kinds: [Pod]}}]}
      context:
        - name: teamcfg
          configMap:
            name: team-config
            namespace: "{{request.object.metadata.namespace}}"
      validate:
        message: "image tag required"
        pattern:
          spec:
            containers:
              - image: "*:*"
"""


def pod(name, ns, image='nginx:1.25'):
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': name, 'namespace': ns},
            'spec': {'containers': [{'name': 'c', 'image': image}]}}


def test_pack_fully_compiles():
    """The committed pack (benchmarks/packs: pss — baseline +
    restricted with their autogen rules —, pack, config4): 11
    policies, every rule compiled for the device."""
    import benchlib
    policies = benchlib.load_policies(['pss', 'pack', 'config4'])
    cps = compile_policies(policies)
    assert len(policies) == 11
    assert len(cps.host_rules) == 0
    assert len(cps.programs) == 15


def test_value_feeding_a_pattern_leaf_stays_host():
    # a context value in a pattern leaf keeps the rule on the host, under
    # its own reason (a condition's value is a lane: test_context_lanes.py)
    pack = CTX_PACK.replace('"*:*"', '"{{teamcfg.data.team}}:*"')
    cps = compile_policies(load_policies_from_yaml(pack))
    assert len(cps.host_rules) == 1
    assert len(cps.programs) == 0
    assert cps.placements[0].reason == 'context_in_pattern'


def test_value_in_the_message_compiles_and_the_host_words_the_fail():
    # the message keeps its {{...}}: the verdict is the device's, a FAIL
    # cell is phrased by the host with the context loaded
    pack = CTX_PACK.replace('image tag required',
                            'team is {{teamcfg.data.team}}')
    client = FakeClient()
    client.create_resource('v1', 'ConfigMap', 'has-cm', {
        'apiVersion': 'v1', 'kind': 'ConfigMap',
        'metadata': {'name': 'team-config', 'namespace': 'has-cm'},
        'data': {'team': 'a'}})
    policies = load_policies_from_yaml(pack)
    engine = Engine(context_loader=make_context_loader(dclient=client))
    scanner = BatchScanner(policies, engine=engine)
    assert not scanner.cps.host_rules and len(scanner.cps.programs) == 1
    (responses,) = scanner.scan([pod('bad', 'has-cm', 'nginx')])
    (rule,) = responses[0].policy_response.rules
    assert rule.status == 'fail' and 'team is a' in rule.message


def test_device_matches_host_across_load_outcomes():
    client = FakeClient()
    client.create_resource('v1', 'Namespace', '', {
        'apiVersion': 'v1', 'kind': 'Namespace',
        'metadata': {'name': 'has-cm'}})
    client.create_resource('v1', 'ConfigMap', 'has-cm', {
        'apiVersion': 'v1', 'kind': 'ConfigMap',
        'metadata': {'name': 'team-config', 'namespace': 'has-cm'},
        'data': {'team': 'a'}})
    policies = load_policies_from_yaml(CTX_PACK)
    engine = Engine(context_loader=make_context_loader(dclient=client))
    scanner = BatchScanner(policies, engine=engine)
    assert not scanner.cps.host_rules

    pods = [pod('ok', 'has-cm'),            # cm exists, pattern passes
            pod('bad', 'has-cm', 'nginx'),  # cm exists, pattern fails
            pod('nocm', 'missing-ns')]      # cm load fails -> host error
    out = scanner.scan(pods)
    for doc, responses in zip(pods, out):
        host = engine.apply_background_checks(
            PolicyContext(policies[0], new_resource=doc))
        got = {r.name: (r.status, r.message)
               for resp in responses for r in resp.policy_response.rules}
        want = {r.name: (r.status, r.message)
                for r in host.policy_response.rules}
        assert got == want, doc['metadata']['name']
    # sanity: the three outcomes genuinely differ
    statuses = [resp.policy_response.rules[0].status
                for responses in out for resp in responses
                if resp.policy_response.rules]
    assert 'pass' in statuses and 'fail' in statuses
    assert len(set(statuses)) >= 2
