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


def test_value_feeding_context_stays_host():
    # a rule whose validate references the entry name must stay host
    pack = CTX_PACK.replace('image tag required',
                            'team is {{teamcfg.data.team}}')
    cps = compile_policies(load_policies_from_yaml(pack))
    assert len(cps.host_rules) == 1
    assert len(cps.programs) == 0


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
