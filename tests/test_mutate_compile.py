"""Precompiled bulk-mutation appliers must be bit-identical to the
engine loop (statuses, messages, patched docs, UR specs) — VERDICT r4
#4's exactness requirement."""

import random

import pytest

import benchlib
from kyverno_tpu.compiler.apply import BatchApplier

make_resource = benchlib.load_module('generators', 'config5_dump').make_resource


@pytest.fixture(scope='module')
def policies():
    return benchlib.load_policies(['config5'])


def _run(policies, resources, fast, monkey):
    monkey.setenv('KTPU_FAST_MUTATE', '1' if fast else '0')
    applier = BatchApplier(policies, processes=0)
    if fast:
        assert applier._fast_mutate, 'config5 pack should fast-compile'
    return applier.apply(resources, parallel=False)


def test_config5_pack_compiles_fast(policies, monkeypatch):
    monkeypatch.setenv('KTPU_FAST_MUTATE', '1')
    applier = BatchApplier(policies, processes=0)
    # all three mutate policies of the config-5 pack take the fast path
    assert len(applier._fast_mutate) == 3


def test_fast_matches_engine_bit_identical(policies, monkeypatch):
    rng = random.Random(23)
    resources = [make_resource(rng, i)
                 for i in range(400)]
    # shape escapes: labels as non-dict, containers missing
    resources.append({'apiVersion': 'v1', 'kind': 'Pod',
                      'metadata': {'name': 'weird', 'namespace': 'x',
                                   'labels': 'not-a-dict'},
                      'spec': {}})
    resources.append({'apiVersion': 'v1', 'kind': 'Pod',
                      'metadata': {'name': 'already',
                                   'namespace': 'x',
                                   'labels': {'managed': 'true',
                                              'costcenter': 'c9'},
                                   'annotations': {
                                       'policy.io/revision': 'r1'}},
                      'spec': {'containers': [
                          {'name': 'c', 'image': 'i',
                           'imagePullPolicy': 'Always'}]}})
    fast = _run(policies, resources, True, monkeypatch)
    slow = _run(policies, resources, False, monkeypatch)
    assert len(fast) == len(slow)
    for i, (f, s) in enumerate(zip(fast, slow)):
        assert f.rule_results == s.rule_results, (
            i, resources[i]['metadata']['name'],
            f.rule_results, s.rule_results)
        assert f.patched == s.patched, (
            i, resources[i]['metadata']['name'])
        assert f.ur_specs == s.ur_specs


def test_fast_rate_improvement(policies, monkeypatch):
    import time
    rng = random.Random(7)
    resources = [make_resource(rng, i)
                 for i in range(1500)]
    monkeypatch.setenv('KTPU_FAST_MUTATE', '1')
    applier = BatchApplier(policies, processes=0)
    applier.apply(resources[:32], parallel=False)
    t0 = time.time()
    applier.apply(resources, parallel=False)
    fast_s = time.time() - t0
    monkeypatch.setenv('KTPU_FAST_MUTATE', '0')
    slow_applier = BatchApplier(policies, processes=0)
    slow_applier.apply(resources[:32], parallel=False)
    t0 = time.time()
    slow_applier.apply(resources, parallel=False)
    slow_s = time.time() - t0
    # the precompiled path must be dramatically faster on this pack
    assert fast_s * 3 < slow_s, (fast_s, slow_s)


# ---------------------------------------------------------------------------
# fast-path escape hatches: shapes where the engine's semantics diverge
# from the compiled applier must FALLBACK (and stay bit-identical)

def test_json6902_replace_on_missing_path_falls_back():
    """`replace` must FALLBACK when the leaf or any intermediate is
    absent — the engine FAILs with 'replace path not found'; only `add`
    may create paths.  The old fast path silently PASSed and mutated."""
    import json as _json
    from kyverno_tpu.compiler.mutate_compile import (FALLBACK,
                                                     compile_json6902)
    from kyverno_tpu.engine.api import RuleStatus
    patch = _json.dumps([{'op': 'replace',
                          'path': '/metadata/labels/app',
                          'value': 'patched'}])
    fast = compile_json6902(patch)
    assert fast is not None
    # leaf absent
    assert fast.apply({'metadata': {'labels': {}}}) is FALLBACK
    # intermediate absent
    assert fast.apply({'metadata': {}}) is FALLBACK
    assert fast.apply({}) is FALLBACK
    # present: replaces in place, engine-identical
    status, _msg, changed, patched = fast.apply(
        {'metadata': {'labels': {'app': 'old'}}})
    assert status == RuleStatus.PASS and changed
    assert patched['metadata']['labels']['app'] == 'patched'
    # the engine really does FAIL on the shapes we defer
    from kyverno_tpu.engine.mutate.mutate import _apply_json6902
    resp = _apply_json6902(patch, {'metadata': {}})
    assert resp.status == RuleStatus.FAIL
    assert 'not found' in resp.message


def test_json6902_add_still_creates_paths():
    import json as _json
    from kyverno_tpu.compiler.mutate_compile import compile_json6902
    from kyverno_tpu.engine.api import RuleStatus
    patch = _json.dumps([{'op': 'add', 'path': '/metadata/labels/app',
                          'value': 'x'}])
    fast = compile_json6902(patch)
    status, _msg, changed, patched = fast.apply({'metadata': {}})
    assert status == RuleStatus.PASS and changed
    assert patched['metadata']['labels']['app'] == 'x'


def test_foreach_duplicate_element_names_fall_back():
    """Strategic merge coalesces duplicate-named list elements onto the
    first occurrence; the fast path patches independently, so duplicate
    names must take the engine path."""
    from kyverno_tpu.compiler.mutate_compile import (FALLBACK,
                                                     compile_foreach)
    rule = {'name': 'set-pull-policy', 'mutate': {'foreach': [
        {'list': 'request.object.spec.containers',
         'patchStrategicMerge': {'spec': {'containers': [
             {'name': '{{element.name}}',
              'imagePullPolicy': 'IfNotPresent'}]}}}]}}
    fast = compile_foreach(rule['mutate']['foreach'], rule)
    assert fast is not None

    def doc(names):
        return {'apiVersion': 'v1', 'kind': 'Pod',
                'metadata': {'name': 'p', 'namespace': 'd'},
                'spec': {'containers': [
                    {'name': n, 'image': 'i'} for n in names]}}
    assert fast.apply(doc(['a', 'a'])) is FALLBACK
    assert fast.apply(doc(['a', None])) is FALLBACK  # non-string name
    out = fast.apply(doc(['a', 'b']))
    assert out is not FALLBACK
    _status, _msg, changed, patched = out
    assert changed
    assert all(c['imagePullPolicy'] == 'IfNotPresent'
               for c in patched['spec']['containers'])
