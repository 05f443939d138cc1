"""The fused report path (BatchScanner.scan_report_results +
set_fused_results) must be bit-identical to the unfused path
(scan_stream → set_responses) — it only skips the intermediate
EngineResponse objects, never changes report content."""

import random

import pytest

import benchlib
from kyverno_tpu.api.policy import load_policies_from_yaml
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.reports.results import set_fused_results, set_responses
from kyverno_tpu.reports.types import new_background_scan_report

mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')

with open(benchlib.data_path('packs', 'pack', '.yaml')) as _f:
    PACK = _f.read() + """
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: psp-restricted
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: restricted
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        podSecurity:
          level: baseline
          version: latest
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: no-background
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  background: false
  rules:
    - name: never-in-scan
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "x"
        pattern:
          metadata:
            name: "?*"
---
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: one-rule-mode
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  applyRules: One
  rules:
    - name: first
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate:
        message: "needs app label"
        pattern:
          metadata:
            labels:
              app: "?*"
"""


def _strip_ts(results):
    return [{k: v for k, v in r.items() if k != 'timestamp'}
            for r in results]


@pytest.fixture(scope='module')
def scanner():
    return BatchScanner(load_policies_from_yaml(PACK))


def test_fused_matches_unfused(scanner):
    rng = random.Random(3)
    pods = [mixed_cluster.make_pod(rng, i) for i in range(96)]

    unfused = []
    for pod, responses in zip(pods, scanner.scan_stream(pods)):
        report = new_background_scan_report(pod)
        relevant = [r for r in responses if r.policy_response.rules]
        set_responses(report, *relevant)
        unfused.append(report)

    fused = []
    for pod, (results, summary, policies) in zip(
            pods, scanner.scan_report_results(pods)):
        report = new_background_scan_report(pod)
        set_fused_results(report, results, summary, policies)
        fused.append(report)

    assert len(fused) == len(unfused)
    for f, u in zip(fused, unfused):
        assert f['metadata'].get('labels') == u['metadata'].get('labels')
        fs, us = f['spec'], u['spec']
        assert fs['summary'] == us['summary']
        assert _strip_ts(fs['results']) == _strip_ts(us['results'])


def test_fused_results_are_sorted(scanner):
    rng = random.Random(5)
    pods = [mixed_cluster.make_pod(rng, i) for i in range(8)]
    for results, _summary, _p in scanner.scan_report_results(pods):
        keys = [(r.get('policy', ''), r.get('rule', '')) for r in results]
        assert keys == sorted(keys)


def test_pss_pack_fused_unfused_and_host():
    """The cell's PSS pack over its own cluster: every podSecurity FAIL
    is worded by the check library called directly
    (BatchScanner._materialize), and the fused report, the unfused one
    and the host engine's still agree to the byte."""
    reports_driver = benchlib.load_module('drivers', 'reports_controller')
    policies = benchlib.load_policies(['pss'])
    scanner = BatchScanner(policies)
    docs = mixed_cluster.generate(11, 160)
    assert {d['kind'] for d in docs} == {'Pod', 'Deployment'}

    unfused = []
    for doc, responses in zip(docs, scanner.scan_stream(docs)):
        report = new_background_scan_report(doc)
        set_responses(report, *[r for r in responses
                                if r.policy_response.rules])
        unfused.append(report)
    fused = []
    for doc, (results, summary, pols) in zip(
            docs, scanner.scan_report_results(docs)):
        report = new_background_scan_report(doc)
        set_fused_results(report, results, summary, pols)
        fused.append(report)

    pss_fails = 0
    for doc, f, u in zip(docs, fused, unfused):
        h = reports_driver.host_report(scanner.engine, policies, doc)
        for other in (u, h):
            assert f['metadata'].get('labels') == \
                other['metadata'].get('labels')
            assert f['spec']['summary'] == other['spec']['summary']
            assert _strip_ts(f['spec']['results']) == \
                _strip_ts(other['spec']['results'])
        pss_fails += sum(1 for r in f['spec']['results']
                         if r['policy'].startswith('podsecurity-')
                         and r['result'] == 'fail')
    assert pss_fails > 160
