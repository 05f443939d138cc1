"""Device FAIL-message synthesis vs the host engine.

The evaluator's third output (``fdet``) identifies the walk position the
host would report for each FAIL; the scanner re-builds the exact
``validation error: … failed at path …`` message from compile-time
templates (reference formats: pkg/engine/validation.go:722
buildErrorMessage, :746 buildAnyPatternErrorMessage, :460 getDenyMessage).
These tests assert bit-identical messages against a pure host run across
the tricky walk shapes: array-of-maps element indices, parent-path ``*``
shortcuts, anchors, anyPattern multi-child messages, foreach deny fails,
and message-dot/empty/variable corner cases.
"""

import random

import yaml

from kyverno_tpu.api.policy import Policy
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.engine import Engine

PACK = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: elem-paths
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: image-tag
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "An image tag is required"
        pattern:
          spec:
            containers:
              - image: "!*:latest"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: nested-elem
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: no-host-ports
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "host ports are forbidden."
        pattern:
          spec:
            containers:
              - ports:
                  - hostPort: 0
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: star-parent-path
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: require-requests
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: no message dot here
        pattern:
          spec:
            containers:
              - resources:
                  requests:
                    memory: "?*"
                    cpu: "*"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: no-message
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: empty-msg-rule
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        pattern:
          metadata:
            labels:
              app: "?*"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: anchors
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: no-host-network-when-labeled
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "hostNetwork must be false for labeled pods."
        pattern:
          spec:
            =(hostNetwork): false
    - name: negation-host-pid
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "hostPID is not allowed"
        pattern:
          spec:
            X(hostPID): "null"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: any-pattern-msgs
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: run-as-nonroot
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: >-
          Running as root is not allowed. The fields
          spec.securityContext.runAsNonRoot must be true.
        anyPattern:
          - spec:
              securityContext:
                runAsNonRoot: true
          - spec:
              containers:
                - securityContext:
                    runAsNonRoot: true
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: foreach-caps
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: drop-all-caps
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: Containers must drop ALL capabilities.
        foreach:
          - list: request.object.spec.containers[]
            deny:
              conditions:
                all:
                  - key: ALL
                    operator: AnyNotIn
                    value: "{{ element.securityContext.capabilities.drop[] || '' }}"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: variable-message
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: var-msg-rule
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "pod {{request.object.metadata.name}} must set app"
        pattern:
          metadata:
            labels:
              app: "?*"
"""


def load_pack():
    return [Policy(d) for d in yaml.safe_load_all(PACK) if d]


def make_pod(rng):
    containers = []
    for i in range(rng.randint(1, 3)):
        c = {'name': f'c{i}',
             'image': rng.choice(['nginx:latest', 'nginx:1.25', 'app',
                                  'ghcr.io/x/y:v1'])}
        if rng.random() < 0.6:
            c['resources'] = {'requests': {
                k: v for k, v in
                [('memory', '64Mi'), ('cpu', '100m')][:rng.randint(0, 2)]}}
        if rng.random() < 0.5:
            sc = {}
            if rng.random() < 0.6:
                sc['runAsNonRoot'] = rng.random() < 0.5
            if rng.random() < 0.5:
                sc['capabilities'] = {'drop': rng.choice(
                    [['ALL'], ['KILL'], [], ['ALL', 'KILL']])}
            c['securityContext'] = sc
        if rng.random() < 0.4:
            c['ports'] = [{'containerPort': 80,
                           'hostPort': rng.choice([0, 80, 9000])}
                          for _ in range(rng.randint(1, 2))]
        containers.append(c)
    pod = {'apiVersion': 'v1', 'kind': 'Pod',
           'metadata': {'name': f'p{rng.randint(0, 999)}',
                        'namespace': 'default'},
           'spec': {'containers': containers}}
    if rng.random() < 0.4:
        pod['metadata']['labels'] = rng.choice(
            [{'app': 'x'}, {'app': ''}, {'other': 'y'}])
    if rng.random() < 0.3:
        pod['spec']['hostNetwork'] = rng.choice([True, False])
    if rng.random() < 0.3:
        pod['spec']['hostPID'] = True
    if rng.random() < 0.3:
        pod['spec']['securityContext'] = {
            'runAsNonRoot': rng.random() < 0.5}
    return pod


def host_results(engine, policies, resource):
    host = {}
    for policy in policies:
        resp = engine.apply_background_checks(
            PolicyContext(policy, new_resource=resource))
        if resp.policy_response.rules:
            host[policy.name] = {r.name: (r.status, r.message)
                                 for r in resp.policy_response.rules}
    return host


class TestFailSynthesis:
    def test_sites_compiled(self):
        scanner = BatchScanner(load_pack())
        by_name = {p.rule_name: p for p in scanner.cps.programs}
        assert by_name['image-tag'].fail_sites is not None
        assert by_name['image-tag'].fail_prefix is not None
        assert by_name['no-host-ports'].fail_sites is not None
        assert by_name['run-as-nonroot'].any_fail_sites is not None
        assert by_name['drop-all-caps'].deny_fail_message == \
            'validation failure: Containers must drop ALL capabilities.'
        # variable messages cannot be synthesized
        assert by_name['var-msg-rule'].fail_sites is None
        assert by_name['var-msg-rule'].fail_prefix is None

    def test_path_templates(self):
        scanner = BatchScanner(load_pack())
        by_name = {p.rule_name: p for p in scanner.cps.programs}
        assert '/spec/containers/{e0}/image/' in by_name['image-tag'].fail_sites
        assert '/spec/containers/{e0}/ports/{e1}/hostPort/' in \
            by_name['no-host-ports'].fail_sites
        # the map-level '*' shortcut reports the PARENT path
        assert '/spec/containers/{e0}/resources/requests/' in \
            by_name['require-requests'].fail_sites

    def test_device_vs_host_messages_fuzz(self):
        policies = load_pack()
        engine = Engine()
        rng = random.Random(7)
        resources = [make_pod(rng) for _ in range(200)]
        scanner = BatchScanner(policies)
        scanned = scanner.scan(resources)
        for resource, responses in zip(resources, scanned):
            host = host_results(engine, policies, resource)
            got = {}
            for er in responses:
                if er.policy_response.rules:
                    got[er.policy_response.policy_name] = {
                        r.name: (r.status, r.message)
                        for r in er.policy_response.rules}
            assert got == host, f'divergence on {resource}'

    def test_synthesis_actually_used(self):
        """The fuzz above must exercise synthesized FAILs, not just fall
        back to host materialization for everything."""
        policies = load_pack()
        rng = random.Random(7)
        resources = [make_pod(rng) for _ in range(200)]
        scanner = BatchScanner(policies)
        calls = [0]
        inner = scanner._materialize

        def counting(prog, doc, *cell):
            calls[0] += 1
            return inner(prog, doc, *cell)
        scanner._materialize = counting
        out = scanner.scan(resources)
        decisions = sum(len(r.policy_response.rules)
                        for rs in out for r in rs)
        fails = sum(1 for rs in out for r in rs
                    for x in r.policy_response.rules if x.status == 'fail')
        assert fails > 100, 'fuzz produced too few FAILs to be meaningful'
        # only the variable-message rule's fails need the host
        assert calls[0] < fails / 2, \
            f'{calls[0]} materializations for {fails} fails: synthesis idle'

    def test_pss_pack_messages_equal_the_host_engines(self):
        """A podSecurity FAIL has no site to synthesize from (fd -1): the
        scanner words it with the check library, called directly, and the
        message, status and checks are the host engine's, in the
        responses and in the reports."""
        import benchlib
        from kyverno_tpu.reports.results import set_fused_results
        from kyverno_tpu.reports.types import new_background_scan_report
        reports_driver = benchlib.load_module('drivers',
                                              'reports_controller')
        mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')
        policies = benchlib.load_policies(['pss'])
        engine = Engine()
        resources = mixed_cluster.generate(13, 200)
        scanner = BatchScanner(policies)
        direct = [0]
        inner = scanner._materialize

        def counting(prog, doc, *cell):
            direct[0] += 1
            return inner(prog, doc, *cell)
        scanner._materialize = counting
        fails = 0
        for resource, responses in zip(resources,
                                       scanner.scan(resources)):
            got = {}
            for er in responses:
                if er.policy_response.rules:
                    got[er.policy_response.policy_name] = {
                        r.name: (r.status, r.message)
                        for r in er.policy_response.rules}
                    checks = {r.name: r.pod_security_checks
                              for r in er.policy_response.rules}
                    want = engine.apply_background_checks(PolicyContext(
                        er.policy, new_resource=resource))
                    assert checks == {
                        r.name: r.pod_security_checks
                        for r in want.policy_response.rules}
            assert got == host_results(engine, policies, resource)
            fails += sum(1 for rules in got.values()
                         for st, _ in rules.values() if st == 'fail')
        assert fails > 200
        # every FAIL was worded on the host, none twice
        assert direct[0] == fails
        for resource, (results, summary, pols) in zip(
                resources, scanner.scan_report_results(resources)):
            report = new_background_scan_report(resource)
            set_fused_results(report, results, summary, pols)
            want = reports_driver.host_report(engine, policies, resource)
            strip = reports_driver._sans_timestamp
            assert strip(report['spec']['results']) == \
                strip(want['spec']['results'])
            assert report['spec']['summary'] == want['spec']['summary']
        assert direct[0] == 2 * fails
