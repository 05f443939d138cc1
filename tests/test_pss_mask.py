"""A podSecurity program's FAIL carries, in its fail-detail cell, the mask of
the checks that failed (ops/eval.py ``eval_status``, the ``seq`` branch over
the ``pss_bit`` leaves of compiler/pss_compile.py), and the host's check
library then runs those checks alone (pss/evaluate.py
``evaluate_failed_checks``, handed the mask by compiler/scan.py
``_materialize``).

The mask has to name the library's failing checks exactly, check by check,
and the response worded from it has to be the response of the full run; and
wherever the mask cannot be trusted (a check undecided on the device, a cell
beyond the fail-detail budget, a cell the device did not decide, an
evaluator the engine was given, a mask the library does not confirm) every
check runs, as before."""

import functools
import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest
import yaml

import benchlib
import test_pss_compile as fuzz
from kyverno_tpu.api.policy import Policy, Rule
from kyverno_tpu.api.unstructured import Resource
from kyverno_tpu.compiler import scan as scan_mod
from kyverno_tpu.compiler.ir import STATUS_FAIL, STATUS_HOST, STATUS_PASS
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.engine.api import PolicyContext, RuleStatus
from kyverno_tpu.engine.engine import (Engine, Validator,
                                       pod_security_response)
from kyverno_tpu.observability import coverage
from kyverno_tpu.pss import evaluate as pss_evaluate
from kyverno_tpu.pss.checks import DEFAULT_CHECKS, LEVEL_BASELINE
from kyverno_tpu.pss.evaluate import (evaluate_failed_checks,
                                      evaluate_pod_security, evaluate_pss,
                                      extract_pod_spec)

mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')
context_cluster = benchlib.load_module('generators', 'context_cluster')
admission_reviews = benchlib.load_module('generators', 'admission_reviews')

BIT = {check.id: bit for bit, check in enumerate(DEFAULT_CHECKS)}
LEVELS = ('baseline', 'restricted')
#: the rule of the ``pss`` pack (and of its autogen) for each kind
PREFIX = {'Pod': '', 'Deployment': 'autogen-', 'CronJob': 'autogen-cronjob-'}


def bits_of(mask: int) -> set:
    return {bit for bit in range(mask.bit_length()) if mask >> bit & 1}


def failing_bits(level: str, doc: dict) -> set:
    return {BIT[r['id']] for r in evaluate_pss(level, extract_pod_spec(doc))}


# -- the corpora ---------------------------------------------------------------

def _fuzz_docs() -> list:
    rng = random.Random(23)
    return [fuzz.make_pod(rng) for _ in range(150)] + \
        [fuzz.make_deployment(rng) for _ in range(50)]


def _review_pods() -> list:
    bodies = admission_reviews.generate(5, mixed_cluster.generate(7, 256),
                                        160)
    return [json.loads(body)['request']['object'] for body in bodies]


CORPORA = {
    'mixed_cluster': lambda: mixed_cluster.generate(7, 512),
    'context_cluster': lambda: context_cluster.generate(
        11, 384, cronjob_share=0.2),
    'admission_reviews': _review_pods,
    'pss_compile': _fuzz_docs,
}
KINDS = {'mixed_cluster': ('Pod', 'Deployment'),
         'context_cluster': ('Pod', 'Deployment', 'CronJob'),
         'admission_reviews': ('Pod',),
         'pss_compile': ('Pod', 'Deployment')}
CASES = [(corpus, kind, level) for corpus in CORPORA
         for kind in KINDS[corpus] for level in LEVELS]


@functools.lru_cache(maxsize=None)
def pss_scanner() -> BatchScanner:
    return BatchScanner(benchlib.load_policies(['pss']))


def device(scanner, docs):
    """``(status, fdet, match)`` of the scanner's programs over ``docs``."""
    match = scanner.match_matrix(docs, [Resource(d) for d in docs])
    status, _detail, fdet = scanner._device_statuses(docs, match=match)
    return status, fdet, match


@functools.lru_cache(maxsize=None)
def evaluated(corpus: str):
    docs = CORPORA[corpus]()
    return (docs,) + device(pss_scanner(), docs)


def column(scanner, rule_name: str) -> int:
    [j] = [j for j, p in enumerate(scanner.cps.programs)
           if p.rule_name == rule_name]
    return j


def fail_cells(corpus: str, kind: str, level: str):
    """``(doc, mask)`` for the cells of the kind's rule that the device
    failed."""
    docs, status, fdet, match = evaluated(corpus)
    j = column(pss_scanner(), PREFIX[kind] + level)
    cells = []
    for k, doc in enumerate(docs):
        if doc['kind'] != kind:
            assert not match[k, j]
            continue
        assert match[k, j]
        if status[k, j] == STATUS_FAIL:
            cells.append((doc, int(fdet[k, j])))
        else:
            assert status[k, j] in (STATUS_PASS, STATUS_HOST)
            if status[k, j] == STATUS_PASS:
                assert failing_bits(level, doc) == set()
    return cells


# -- the mask is the library's failing checks ------------------------------------

@pytest.mark.parametrize('corpus, kind, level', CASES)
def test_the_mask_names_the_checks_the_library_fails(corpus, kind, level):
    cells = fail_cells(corpus, kind, level)
    assert len(cells) >= 20
    undecided = 0
    for doc, mask in cells:
        if mask == -1:
            undecided += 1
            continue
        assert mask > 0
        assert bits_of(mask) == failing_bits(level, doc), doc
    # a mask that is mostly -1 is a fault, not a result
    assert undecided <= 0.05 * len(cells)


def _pod(spec_extra=None, container=None, meta=None, pod_sc=None) -> dict:
    """A Pod that passes ``restricted`` but for what the arguments add."""
    c = {'name': 'c0', 'image': 'app:v1', 'securityContext': {
        'allowPrivilegeEscalation': False, 'runAsNonRoot': True,
        'capabilities': {'drop': ['ALL']}}}
    for key, value in (container or {}).items():
        if key == 'securityContext':
            c['securityContext'] = {**c['securityContext'], **value}
        else:
            c[key] = value
    spec = {'securityContext': {'runAsNonRoot': True, 'seccompProfile': {
        'type': 'RuntimeDefault'}, **(pod_sc or {})}, 'containers': [c]}
    spec.update(spec_extra or {})
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': 'p', 'namespace': 'd', **(meta or {})},
            'spec': spec}


def _sc(**fields) -> dict:
    return {'container': {'securityContext': fields}}


#: a pod for every check, failing that check alone
ONE_CHECK = {
    'hostNamespaces': _pod({'hostNetwork': True}),
    'privileged': _pod(**_sc(privileged=True)),
    'capabilities_baseline': _pod(**_sc(
        capabilities={'drop': ['ALL'], 'add': ['NET_BIND_SERVICE',
                                               'NET_ADMIN']})),
    'hostPathVolumes': _pod({'volumes': [
        {'name': 'v', 'hostPath': {'path': '/x'}}]}),
    'hostPorts': _pod(container={'ports': [
        {'containerPort': 80, 'hostPort': 80}]}),
    'appArmorProfile': _pod(meta={'annotations': {
        'container.apparmor.security.beta.kubernetes.io/c0': 'unconfined'}}),
    'seLinuxOptions': _pod(**_sc(seLinuxOptions={'type': 'spc_t'})),
    'procMount': _pod(**_sc(procMount='Unmasked')),
    'seccompProfile_baseline': _pod(**_sc(
        seccompProfile={'type': 'Unconfined'})),
    'sysctls': _pod(pod_sc={'sysctls': [
        {'name': 'kernel.msgmax', 'value': '1'}]}),
    'windowsHostProcess': _pod(**_sc(
        windowsOptions={'hostProcess': True})),
    'restrictedVolumes': _pod({'volumes': [
        {'name': 'v', 'nfs': {'server': 's', 'path': '/'}}]}),
    'allowPrivilegeEscalation': _pod(**_sc(allowPrivilegeEscalation=True)),
    'runAsNonRoot': _pod(**_sc(runAsNonRoot=False)),
    'runAsUser': _pod(**_sc(runAsUser=0)),
    'seccompProfile_restricted': _pod(**_sc(
        seccompProfile={'type': 'Other'})),
    'capabilities_restricted': _pod(**_sc(capabilities={'drop': []})),
}
#: beside ``restricted``'s own, what else the pod cannot help failing
ALSO = {'hostPathVolumes': {'restrictedVolumes'},
        'seccompProfile_baseline': {'seccompProfile_restricted'},
        'capabilities_baseline': {'capabilities_restricted'},
        'appArmorProfile.beside.seccomp-1.0': {'seccompProfile_baseline'}}
#: the variants: the seccomp annotations of before 1.19 (latest passes), and
#: a Windows pod, which the three 1.25 variants exempt (the older fail)
VARIANTS = {
    'seccompProfile_baseline@1.0.pod': (
        'seccompProfile_baseline', _pod(meta={'annotations': {
            'seccomp.security.alpha.kubernetes.io/pod': 'unconfined'}})),
    'seccompProfile_baseline@1.0.container': (
        'seccompProfile_baseline', _pod(meta={'annotations': {
            'container.seccomp.security.alpha.kubernetes.io/c0':
                'unconfined',
            'container.apparmor.security.beta.kubernetes.io/c0':
                'runtime/default'}})),
    'appArmorProfile.beside.seccomp-1.0': (
        'appArmorProfile', _pod(meta={'annotations': {
            'seccomp.security.alpha.kubernetes.io/pod': 'unconfined',
            'container.apparmor.security.beta.kubernetes.io/c0':
                'unconfined'}})),
    'allowPrivilegeEscalation@windows': (
        'allowPrivilegeEscalation', _pod(
            {'os': {'name': 'windows'}},
            **_sc(allowPrivilegeEscalation=True))),
    'seccompProfile_restricted@windows': (
        'seccompProfile_restricted', _pod(
            {'os': {'name': 'windows'}},
            **_sc(seccompProfile={'type': 'Other'}))),
    'capabilities_restricted@windows': (
        'capabilities_restricted', _pod(
            {'os': {'name': 'windows'}}, **_sc(capabilities={'drop': []}))),
}
WINDOWS_EXEMPT = ('allowPrivilegeEscalation', 'seccompProfile_restricted',
                  'capabilities_restricted')
HAND_MADE = [(check_id, check_id, pod) for check_id, pod in ONE_CHECK.items()] \
    + [(name, check_id, pod) for name, (check_id, pod) in VARIANTS.items()]


@functools.lru_cache(maxsize=None)
def hand_made():
    docs = [pod for _name, _check_id, pod in HAND_MADE]
    return device(pss_scanner(), docs)


@pytest.mark.parametrize('index', range(len(HAND_MADE)),
                         ids=[name for name, _, _ in HAND_MADE])
def test_a_pod_that_fails_one_check_sets_its_bit(index):
    name, check_id, pod = HAND_MADE[index]
    status, fdet, match = hand_made()
    scanner = pss_scanner()
    check = DEFAULT_CHECKS[BIT[check_id]]
    want = {check_id} | ALSO.get(name, set())
    results = evaluate_pss('restricted', extract_pod_spec(pod))
    assert {r['id'] for r in results} == want
    # a Linux pod fails both variants of a check that has a 1.25 variant
    # for Windows; every other pod here fails one variant of its check
    assert [r['id'] for r in results].count(check_id) == \
        (2 if check_id in WINDOWS_EXEMPT and '@' not in name else 1)
    assert len(check.fns) == 2 or '@' not in name
    for level in LEVELS:
        j = column(scanner, level)
        assert match[index, j]
        mine = {c for c in want
                if level != LEVEL_BASELINE
                or DEFAULT_CHECKS[BIT[c]].level == LEVEL_BASELINE}
        if not mine:
            assert status[index, j] == STATUS_PASS
            continue
        assert status[index, j] == STATUS_FAIL
        assert bits_of(int(fdet[index, j])) == {BIT[c] for c in mine}
        masked = evaluate_failed_checks(
            level, extract_pod_spec(pod), int(fdet[index, j]))
        assert masked == evaluate_pss(level, extract_pod_spec(pod))


# -- a program that is not podSecurity keeps its fail detail ---------------------

@functools.lru_cache(maxsize=None)
def plain_and_mixed():
    docs = mixed_cluster.generate(7, 384)
    plain = BatchScanner(benchlib.load_policies(['pack', 'config4']))
    mixed = BatchScanner(benchlib.load_policies(['pss', 'pack', 'config4']))
    return docs, plain, device(plain, docs), mixed, device(mixed, docs)


@pytest.mark.parametrize('pack', ['pack', 'config4'])
def test_a_plain_programs_fail_detail_is_its_site(pack):
    """Column for column what the same program ships in a set without a
    podSecurity rule, and the site the host engine's message names."""
    docs, plain, (p_status, p_fdet, p_match), mixed, \
        (m_status, m_fdet, m_match) = plain_and_mixed()
    names = {p.name for p in benchlib.load_policies([pack])}
    engine = Engine()
    checked = 0
    for j, prog in enumerate(plain.cps.programs):
        if prog.policy_name not in names:
            continue
        assert prog.pss is None
        [jm] = [i for i, p in enumerate(mixed.cps.programs)
                if (p.policy_name, p.rule_name) ==
                (prog.policy_name, prog.rule_name)]
        rows = np.flatnonzero(p_match[:, j] & (p_status[:, j] == STATUS_FAIL))
        assert np.array_equal(p_match[:, j], m_match[:, jm])
        assert np.array_equal(p_status[:, j], m_status[:, jm])
        assert np.array_equal(p_fdet[rows, j], m_fdet[rows, jm])
        policy = plain.policies[prog.policy_index]
        for k in rows[:32].tolist():
            message = plain._fail_message(prog, j, p_fdet[k])
            if message is None:
                continue
            assert p_fdet[k, j] >= 0
            host = Validator(engine, PolicyContext(
                policy, new_resource=docs[k]), Rule(prog.rule_raw)).validate()
            assert host.status == RuleStatus.FAIL
            assert message == host.message
            checked += 1
    assert checked >= 50


# -- the response worded from the mask is the full run's -------------------------

def same_response(masked, full):
    assert masked.message == full.message
    assert masked.status == full.status
    assert masked.pod_security_checks == full.pod_security_checks
    assert vars(masked) == vars(full)


@pytest.mark.parametrize('corpus, kind, level', CASES)
def test_the_masked_response_is_the_full_response(corpus, kind, level):
    block = {'level': level, 'version': 'latest'}
    tally = coverage.ScanTally(None)
    doubled = 0
    for doc, mask in fail_cells(corpus, kind, level):
        full = pod_security_response('r', block, doc, evaluate_pod_security)
        masked = pod_security_response(
            'r', block, doc, scan_mod._masked_evaluator(mask, tally)
            if mask > 0 else evaluate_pod_security)
        same_response(masked, full)
        assert full.status == RuleStatus.FAIL
        ids = [c['id'] for c in full.pod_security_checks['checks']]
        doubled += len(ids) != len(set(ids))
    assert tally.pss_mask_mismatch == 0
    assert tally.pss_masked_cells > 0
    assert tally.pss_checks_run >= tally.pss_masked_cells
    if level == 'restricted':
        # a check whose two variants both fail is reported twice
        assert doubled > 0


def test_the_scanner_words_from_the_mask_and_counts_it():
    docs, status, fdet, match = evaluated('mixed_cluster')
    scanner = pss_scanner()
    tally = coverage.ScanTally(None)
    checks = 0
    for level, kind in (('baseline', 'Pod'), ('restricted', 'Deployment')):
        j = column(scanner, PREFIX[kind] + level)
        prog = scanner.cps.programs[j]
        for k in np.flatnonzero(match[:, j] &
                                (status[:, j] == STATUS_FAIL)).tolist():
            rr = scanner._materialize(prog, docs[k], int(fdet[k, j]), tally)
            same_response(rr, scanner._materialize(prog, docs[k]))
            checks += len(failing_bits(level, docs[k]))
    assert tally.pss_worded_cells == tally.pss_masked_cells > 100
    assert tally.pss_checks_run == checks
    assert tally.pss_mask_mismatch == 0


# -- every way back to the full run ----------------------------------------------

@pytest.fixture
def full_runs(monkeypatch):
    """Counts the calls of ``evaluate_pss``: the full run."""
    calls = []
    plain = pss_evaluate.evaluate_pss

    def counting(level, pod):
        calls.append(level)
        return plain(level, pod)

    monkeypatch.setattr(pss_evaluate, 'evaluate_pss', counting)
    return calls


def failing_pod() -> dict:
    return ONE_CHECK['privileged']


def test_a_forged_mask_is_counted_and_the_library_wins(full_runs):
    """A bit whose check passes: never a FAIL with that check missing or
    with no checks at all."""
    scanner = pss_scanner()
    prog = scanner.cps.programs[column(scanner, 'baseline')]
    pod = failing_pod()
    right = 1 << BIT['privileged']
    want = scanner._materialize(prog, pod)
    assert want.status == RuleStatus.FAIL and len(full_runs) == 1
    for forged in (right | 1 << BIT['hostPorts'], 1 << BIT['hostPorts'],
                   right | 1 << BIT['runAsUser'], 1 << 17, 1 << 30):
        del full_runs[:]
        tally = coverage.ScanTally(None)
        rr = scanner._materialize(prog, pod, forged, tally)
        same_response(rr, want)
        assert rr.pod_security_checks['checks']
        assert len(full_runs) == 1
        assert (tally.pss_worded_cells, tally.pss_masked_cells,
                tally.pss_checks_run, tally.pss_mask_mismatch) == (1, 0, 0, 1)
    # a pod that passes, with a mask that says it fails
    ok = _pod()
    tally = coverage.ScanTally(None)
    rr = scanner._materialize(prog, ok, right, tally)
    assert rr.status == RuleStatus.PASS
    assert tally.pss_mask_mismatch == 1
    # and the right mask runs the one check
    del full_runs[:]
    tally = coverage.ScanTally(None)
    same_response(scanner._materialize(prog, pod, right, tally), want)
    assert full_runs == []
    assert (tally.pss_worded_cells, tally.pss_masked_cells,
            tally.pss_checks_run, tally.pss_mask_mismatch) == (1, 1, 1, 0)


@pytest.mark.parametrize('detail', [-1, 0, None],
                         ids=['undecided', 'empty', 'not-a-device-fail'])
def test_a_cell_without_a_mask_runs_every_check(full_runs, detail):
    scanner = pss_scanner()
    prog = scanner.cps.programs[column(scanner, 'restricted')]
    tally = coverage.ScanTally(None)
    rr = scanner._materialize(prog, failing_pod(), detail, tally)
    assert rr.status == RuleStatus.FAIL
    assert len(full_runs) == 1
    assert tally.pss_worded_cells == (0 if detail is None else 1)
    assert tally.pss_masked_cells == tally.pss_mask_mismatch == 0


def test_an_undecided_check_voids_the_mask(full_runs):
    """A HOST child beside a FAIL: twenty containers overflow the element
    axis, so the checks that walk them are undecided on the device, while
    ``hostNamespaces`` fails there.  The cell reads -1 and every check
    runs; the same pod with one container ships its mask."""
    narrow = _pod({'hostNetwork': True})
    wide = _pod({'hostNetwork': True})
    wide['spec']['containers'] = [
        dict(wide['spec']['containers'][0], name=f'c{i}') for i in range(20)]
    scanner = pss_scanner()
    status, fdet, match = device(scanner, [narrow, wide])
    for level in LEVELS:
        j = column(scanner, level)
        assert status[0, j] == status[1, j] == STATUS_FAIL
        assert int(fdet[0, j]) == 1 << BIT['hostNamespaces']
        assert int(fdet[1, j]) == -1
        del full_runs[:]
        tally = coverage.ScanTally(None)
        rr = scanner._materialize(scanner.cps.programs[j], wide,
                                  int(fdet[1, j]), tally)
        assert rr.status == RuleStatus.FAIL and len(full_runs) == 1
        assert (tally.pss_worded_cells, tally.pss_masked_cells) == (1, 0)


def test_the_leaves_carry_their_bits_in_walk_order():
    scanner = pss_scanner()
    for level, count in (('baseline', 11), ('restricted', 17)):
        for prefix in PREFIX.values():
            tree = scanner.cps.programs[
                column(scanner, prefix + level)].status
            assert tree.kind == 'seq'
            assert [c.pss_bit for c in tree.children] == list(range(count))
    plain = plain_and_mixed()[1]
    stack = [p.status for p in plain.cps.programs]
    while stack:
        node = stack.pop()
        assert node.pss_bit is None
        stack.extend(node.children)
        if node.sub is not None:
            stack.append(node.sub)


def test_a_cell_beyond_the_fail_detail_budget_runs_every_check(tmp_path):
    """``KTPU_FDET_K=1``: a row's second FAIL cell reads -1, and the scan
    still gives the host engine's reports, with the cell counted as
    worded without a mask."""
    script = tmp_path / 'budget.py'
    script.write_text(f'''
import json, os, sys
sys.path[:0] = [{os.path.dirname(os.path.dirname(__file__))!r},
                {benchlib.BENCH_DIR!r}]
import benchlib
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.engine import Engine
from kyverno_tpu.observability import coverage
if __name__ == '__main__':
    benchlib.program_telemetry()
    policies = benchlib.load_policies(['pss'])
    docs = benchlib.load_module('generators', 'mixed_cluster').generate(7, 96)
    docs = [d for d in docs if d['kind'] == 'Pod']
    scanner = BatchScanner(policies)
    engine = Engine()
    for doc, responses in zip(docs, scanner.scan(docs)):
        got = {{r.name: (r.status, r.message, r.pod_security_checks)
               for resp in responses for r in resp.policy_response.rules}}
        want = {{}}
        for policy in policies:
            resp = engine.apply_background_checks(
                PolicyContext(policy, new_resource=doc))
            for r in resp.policy_response.rules:
                want[r.name] = (r.status, r.message, r.pod_security_checks)
        assert got == want, doc
    print(json.dumps(coverage.bench_block()))
''')
    env = dict(os.environ, KTPU_FDET_K='1', JAX_PLATFORMS='cpu')
    done = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    totals = json.loads(done.stdout.strip().splitlines()[-1])
    # every Pod fails ``restricted`` and most fail ``baseline``: the
    # second of a row's two FAIL cells is beyond a budget of one
    assert totals['pss_worded_cells'] == \
        totals['by_reason']['pss']['pss_direct_message']
    assert 0 < totals['pss_masked_cells'] < totals['pss_worded_cells']
    assert totals['pss_mask_mismatch'] == 0
    assert totals['device_rows'] + totals['host_rows'] == \
        totals['total_rows']


def test_an_engines_own_evaluator_never_sees_a_mask():
    """``tests/test_pss_direct.py`` holds the call count; here the mask is
    there to be taken and is not."""
    calls = []

    def evaluator(block, pod):
        calls.append(block)
        return evaluate_pod_security(block, pod)

    scanner = BatchScanner(benchlib.load_policies(['pss']),
                           engine=Engine(pss_evaluator=evaluator))
    prog = scanner.cps.programs[column(scanner, 'baseline')]
    tally = coverage.ScanTally(None)
    rr = scanner._materialize(prog, failing_pod(), 1 << BIT['privileged'],
                              tally)
    assert rr.status == RuleStatus.FAIL and len(calls) == 1
    assert (tally.pss_worded_cells, tally.pss_masked_cells) == (1, 0)


EXTRA = """
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata:
  name: pss-preconditions
  annotations: {pod-policies.kyverno.io/autogen-controllers: none}
spec:
  rules:
    - name: gated
      match: {any: [{resources: {kinds: [Pod]}}]}
      preconditions:
        all:
          - key: "{{request.object.metadata.name}}"
            operator: NotEquals
            value: skipme
      validate:
        podSecurity: {level: baseline, version: latest}
"""


def test_a_rule_with_preconditions_ships_the_mask_and_keeps_the_validator(
        full_runs):
    """The gate joins the program's ``seq`` unmarked: the mask is the
    checks' alone, and the Validator words the cell by the full run."""
    scanner = BatchScanner([Policy(d) for d in yaml.safe_load_all(EXTRA)])
    skipped = dict(failing_pod(), metadata={'name': 'skipme',
                                            'namespace': 'd'})
    docs = [failing_pod(), skipped]
    status, fdet, match = device(scanner, docs)
    assert status[0, 0] == STATUS_FAIL
    assert int(fdet[0, 0]) == 1 << BIT['privileged']
    assert status[1, 0] != STATUS_FAIL
    tally = coverage.ScanTally(None)
    rr = scanner._materialize(scanner.cps.programs[0], docs[0],
                              int(fdet[0, 0]), tally)
    assert rr.status == RuleStatus.FAIL and len(full_runs) == 1
    assert tally.pss_worded_cells == 0


# -- the ledger carries the counters ---------------------------------------------

def test_the_ledger_sums_the_tallies_and_stays_in_balance():
    from kyverno_tpu.observability.metrics import MetricsRegistry
    before = coverage.ledger()
    try:
        ledger = coverage.configure(MetricsRegistry())
        docs = mixed_cluster.generate(7, 128)
        scanner = pss_scanner()
        list(scanner.scan_report_results(docs))
        once = ledger.totals()
        scanner.scan(docs)
        twice = ledger.totals()
    finally:
        coverage._ledger = before
    for name in coverage.PSS_COUNTERS:
        assert isinstance(once[name], int)
        assert twice[name] == 2 * once[name]
    assert once['pss_worded_cells'] == \
        once['by_reason']['pss']['pss_direct_message'] == once['host_rows']
    assert once['pss_masked_cells'] == once['pss_worded_cells'] > 0
    assert once['pss_mask_mismatch'] == 0
    assert once['device_rows'] + once['host_rows'] == once['total_rows']
