"""Replay of the reference kuttl conformance corpus
(/root/reference/test/conformance/kuttl — SURVEY.md §4) through the
in-memory cluster + real daemons (kyverno_tpu/conformance/kuttl.py).
Suites are consumed IN PLACE from the read-only reference checkout —
nothing is vendored.

Every case directory in the corpus is parametrized; directories the
hermetic environment cannot replay are listed in DIVERGENT with the
reason and skipped explicitly — never silently."""

import os

import pytest

from kyverno_tpu.conformance.kuttl import (KuttlFailure, Unsupported,
                                           run_suite)

ROOT = '/root/reference/test/conformance/kuttl'

if not os.path.isdir(ROOT):
    pytest.skip(f'the reference corpus {ROOT} is not on this machine',
                allow_module_level=True)

#: suites this environment cannot replay, with reasons (zero-egress
#: sandbox: no live registry; no kubelet: no exec/eviction; the
#: harness does not execute arbitrary shell scripts)
DIVERGENT = {
    # live-cluster shell scripts
    'mutate/clusterpolicy/standard/existing/mutate-existing-node-status':
        'modifies the controller resource filters + node status via '
        'shell scripts against a live node',
    'mutate/clusterpolicy/standard/mutate-node-status':
        'modifies node status via shell scripts against a live node',
    'mutate/clusterpolicy/standard/userInfo-roles-clusterRoles':
        'creates client certificates against a live cluster CA',
    'validate/clusterpolicy/standard/enforce/api-initiated-pod-eviction':
        'drives the eviction subresource via a shell script',
    'validate/clusterpolicy/standard/enforce/block-pod-exec-requests':
        'kubectl exec against a live kubelet',
    # network-bound image verification (zero-egress sandbox; the
    # signature *crypto* is covered offline by tests/test_cosign_crypto)
    'validate/e2e/trusted-images':
        'imageData context entry needs a live registry',
    'verifyImages/clusterpolicy/standard/imageExtractors-complex':
        'verifies live ghcr.io signatures',
    'verifyImages/clusterpolicy/standard/imageExtractors-simple':
        'verifies live ghcr.io signatures',
    'verifyImages/clusterpolicy/standard/'
    'keyless-attestations-multiple-subjects-1':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-attestations-multiple-subjects-2':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-attestations-multiple-subjects-3':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-attestations-multiple-subjects-4':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-attestations-multiple-subjects-counts-1':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-attestations-multiple-subjects-counts-2':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-attestations-multiple-subjects-counts-3':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-mutatedigest-verifydigest-required':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-nomutatedigest-noverifydigest-norequired':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'keyless-nomutatedigest-noverifydigest-required':
        'keyless verification against the public Fulcio/Rekor instances',
    'verifyImages/clusterpolicy/standard/'
    'mutateDigest-noverifyDigest-norequired':
        'digest mutation resolves tags against a live registry',
    'verifyImages/clusterpolicy/standard/noconfigmap-diffimage-success':
        'verifies live ghcr.io signatures',
    'verifyImages/clusterpolicy/standard/'
    'nomutateDigest-verifyDigest-norequired':
        'verifies live ghcr.io signatures',
}


def _case_dirs():
    cases = []
    for dirpath, _dirnames, filenames in os.walk(ROOT):
        rel = os.path.relpath(dirpath, ROOT)
        if rel.startswith('_aaa'):
            continue
        if any(f[0].isdigit() and f.endswith('.yaml') for f in filenames):
            cases.append(rel)
    return sorted(cases)


CASES = _case_dirs()


def test_corpus_discovered():
    """The corpus walk must keep finding the reference suites."""
    assert len(CASES) >= 100, CASES


def test_divergent_paths_exist():
    missing = [rel for rel in DIVERGENT
               if not os.path.isdir(os.path.join(ROOT, rel))]
    assert not missing, f'divergence list drifted: {missing}'


@pytest.mark.parametrize('rel', CASES)
def test_kuttl_suite(rel):
    if rel in DIVERGENT:
        pytest.skip(f'divergent: {DIVERGENT[rel]}')
    try:
        run_suite(os.path.join(ROOT, rel))
    except Unsupported as e:
        pytest.fail(f'unsupported kuttl feature (not divergence-listed): '
                    f'{e}')
    except KuttlFailure as e:
        raise AssertionError(f'{rel}: {e}') from e
