"""The Helm chart's own policies over every kind they autogen for: the pack
``benchmarks/packs/chart.yaml`` (18 policies, 22 rules, autogen on) beside
``pack`` and ``config4``, over the cell ``bgscan_chart_kinds``'s generator.

* the packs compile to 75 rule programs and leave no rule on the host;
* the device path (``BatchScanner``) answers as the host engine
  (``kyverno_tpu/engine``), status and message, rule for rule, kind by kind
  and policy by policy, on the row path and on the report path;
* the cell's plain reference (``benchmarks/reference/chart_rules.py``)
  says pass / fail / skip as the engine does;
* a row whose failing cells outrun the fail-detail budget
  (``KTPU_FDET_K``, 32 columns a row) is still answered as the engine
  answers it and its lost cells are counted under ``fail_detail_budget``,
  and a row that fills the budget exactly loses none;
* ``kyverno_tpu_match_cells_total`` is the count made by hand.
"""

import copy

import numpy as np
import pytest

import benchlib
from kyverno_tpu.api.unstructured import Resource
from kyverno_tpu.compiler.compile import compile_policies
from kyverno_tpu.compiler.ir import FDET_BEYOND_BUDGET, STATUS_FAIL
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.engine.api import PolicyContext
from kyverno_tpu.engine.engine import Engine
from kyverno_tpu.observability import coverage
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability import executables as exectel
from kyverno_tpu.reports.results import set_fused_results, set_responses
from kyverno_tpu.reports.types import new_background_scan_report

chart_cluster = benchlib.load_module('generators', 'chart_cluster')
reference = benchlib.load_module('reference', 'chart_rules')

SEED = 2 ** 31 + 3838
PACKS = ['chart', 'pack', 'config4']
PER_KIND = 50
BUDGET = 32


def is_broken(doc: dict) -> bool:
    """A row of the generator's that breaks everything."""
    return bool(chart_cluster.pod_spec(doc).get('hostIPC'))


def host_rows(engine, policies, doc) -> dict:
    out = {}
    for policy in policies:
        resp = engine.apply_background_checks(
            PolicyContext(policy, new_resource=doc))
        if resp.policy_response.rules:
            out[policy.name] = [(r.name, r.status, r.message)
                                for r in resp.policy_response.rules]
    return out


def rows_of(scanner, docs) -> list:
    """Per resource ``{policy: [(rule, status, message)]}`` of a scan."""
    return [{r.policy.name: [(x.name, x.status, x.message)
                             for x in r.policy_response.rules]
             for r in responses if r.policy_response.rules}
            for responses in scanner.scan(docs)]


def reports_of(scanner, docs) -> list:
    """The reports of a scan by the report path."""
    out = []
    for doc, (results, summary, pols) in zip(
            docs, scanner.scan_report_results(docs)):
        report = new_background_scan_report(doc)
        set_fused_results(report, results, summary, pols)
        out.append(report)
    return out


def engine_report(engine, policies, doc) -> dict:
    report = new_background_scan_report(doc)
    set_responses(report, *[
        r for r in (engine.apply_background_checks(
            PolicyContext(p, new_resource=doc)) for p in policies)
        if r.policy_response.rules])
    return report


def telemetry_off() -> None:
    coverage.disable()
    devtel.disable()
    exectel.disable()


@pytest.fixture(scope='module')
def policies():
    return benchlib.load_policies(PACKS)


@pytest.fixture(scope='module')
def generated():
    return chart_cluster.generate(SEED, 2400)


@pytest.fixture(scope='module')
def cluster(generated):
    """Fifty resources of each of the seven kinds, rows that break
    everything among each kind's."""
    out = []
    for kind in chart_cluster.KINDS:
        of_kind = [d for d in generated if d['kind'] == kind]
        broken = [d for d in of_kind if is_broken(d)][:2]
        plain = [d for d in of_kind if not is_broken(d)]
        assert broken, kind
        out += broken + plain[:PER_KIND - len(broken)]
    assert len(out) == PER_KIND * len(chart_cluster.KINDS)
    return out


@pytest.fixture(scope='module')
def scanned(policies, cluster):
    """One scan of the cluster by rows and one by reports, the engine's
    answers beside them."""
    engine = Engine()
    scanner = BatchScanner(policies, engine=engine)
    return {'scanner': scanner, 'engine': engine,
            'got': rows_of(scanner, cluster),
            'want': [host_rows(engine, policies, doc) for doc in cluster],
            'fused': reports_of(scanner, cluster)}


def test_the_packs_compile_to_75_programs_and_no_host_rule(policies):
    chart = compile_policies(benchlib.load_policies(['chart']))
    assert (len(chart.programs), len(chart.host_rules)) == (66, 0)
    cps = compile_policies(policies)
    assert len(policies) == 27
    assert (len(cps.programs), len(cps.host_rules)) == (75, 0)
    # a rule is three programs: the Pod's, the controllers', the CronJob's
    names = {p.rule_name for p in chart.programs}
    for _policy, rule, _passes in reference.verdicts({}, {}):
        assert {rule, 'autogen-' + rule, 'autogen-cronjob-' + rule} <= names


def test_the_evaluator_has_more_detail_columns_than_the_budget(scanned):
    ev = scanned['scanner']._evaluator
    # one column a tree and one a child of each of the nine anyPattern
    # programs: the first pack whose columns outnumber the budget
    assert (ev.n_uniq, ev.n_cols_u) == (75, 93)
    assert ev.n_cols_u > BUDGET


def test_the_cluster_has_every_kind_and_the_row_that_breaks_everything(
        cluster):
    for kind in chart_cluster.KINDS:
        of_kind = [d for d in cluster if d['kind'] == kind]
        assert len(of_kind) == PER_KIND
        assert any(is_broken(d) for d in of_kind)
    names = [(d['metadata']['namespace'], d['metadata']['name'])
             for d in cluster]
    assert len(set(names)) == len(names)


@pytest.mark.parametrize('kind', chart_cluster.KINDS)
def test_the_device_path_answers_as_the_engine_kind_by_kind(
        scanned, cluster, kind):
    rows = 0
    for doc, want, got in zip(cluster, scanned['want'], scanned['got']):
        if doc['kind'] != kind:
            continue
        assert got == want, (doc['metadata']['name'], [
            (name, want[name], got.get(name)) for name in want
            if want[name] != got.get(name)][:2])
        rows += sum(len(v) for v in want.values())
    # a Pod gets the chart's 22 rows and the best practices', a controller
    # the chart's 22 under autogen's names
    assert rows >= 22 * PER_KIND


@pytest.mark.parametrize('policy', reference.POLICIES)
def test_the_device_path_answers_as_the_engine_policy_by_policy(
        scanned, cluster, policy):
    statuses = set()
    prefixes = set()
    for doc, want, got in zip(cluster, scanned['want'], scanned['got']):
        assert got.get(policy) == want[policy], doc['metadata']['name']
        for name, status, message in want[policy]:
            statuses.add(str(getattr(status, 'value', status)))
            prefixes.add(name.split('-')[0] + (
                '-cronjob' if name.startswith('autogen-cronjob-') else ''))
            assert message
    # the case shows both answers, through all three of autogen's programs
    assert {'pass', 'fail'} <= statuses
    assert {'autogen', 'autogen-cronjob'} <= prefixes


@pytest.mark.parametrize('kind', chart_cluster.KINDS)
def test_the_report_path_writes_the_engines_report(scanned, policies,
                                                   cluster, kind):
    strip = lambda results: [{k: v for k, v in r.items()  # noqa: E731
                              if k != 'timestamp'} for r in results]
    engine = scanned['engine']
    for doc, fused in zip(cluster, scanned['fused']):
        if doc['kind'] != kind:
            continue
        report = engine_report(engine, policies, doc)
        assert fused['spec']['summary'] == report['spec']['summary']
        assert strip(fused['spec']['results']) == \
            strip(report['spec']['results']), doc['metadata']['name']


@pytest.mark.parametrize('kind', chart_cluster.KINDS)
def test_the_plain_reference_says_what_the_engine_says(scanned, cluster,
                                                       kind):
    for doc, want in zip(cluster, scanned['want']):
        if doc['kind'] != kind:
            continue
        engine_rows = sorted(
            (policy, name, str(getattr(status, 'value', status)))
            for policy in reference.POLICIES
            for name, status, _message in want.get(policy, []))
        assert reference.rows(doc) == engine_rows, doc['metadata']['name']
        assert len(engine_rows) == 22


def test_the_reference_has_no_row_for_a_kind_the_chart_does_not_reach():
    assert reference.rows({'kind': 'ConfigMap', 'metadata': {'name': 'x'},
                           'data': {}}) == []


# -- the fail-detail budget ---------------------------------------------------

def relevant_columns(scanner, docs) -> list:
    """Per row, by hand from the device's statuses and the match: the
    unique-space columns the evaluator's compaction calls relevant (a
    matched FAIL's own, and with it the child columns of an anyPattern
    program), in order, and the programs they belong to."""
    ev = scanner._evaluator
    match = scanner.match_matrix(docs, [Resource(d) for d in docs])
    status, _detail, _fdet = scanner._device_statuses(docs, match=match)
    n = len(scanner.cps.programs)
    out = []
    for k in range(len(docs)):
        columns = {}
        for j in np.flatnonzero((status[k] == STATUS_FAIL) & match[k]):
            j = int(j)
            columns[int(ev.uniq_idx[j])] = j
            base, count = ev.any_meta.get(j, (0, 0))
            for c in range(count):
                columns[int(ev.expand_idx[n + base + c])] = j
        out.append(sorted(columns.items()))
    return out


@pytest.fixture(scope='module')
def budget_rows(generated):
    """Four Pods built on the generator's row that breaks everything:
    26 programs fail every such Pod and six anyPattern child columns ride
    with them, which fills the 32 columns exactly; each best-practice rule
    it breaks besides takes one more."""
    base = next(d for d in generated if d['kind'] == 'Pod'
                and is_broken(d) and len(d['spec']['containers']) == 3)
    base = copy.deepcopy(base)
    labels = base['metadata']['labels']
    labels.pop('env', None)
    labels.pop('tier', None)
    base['metadata']['annotations'].pop('budget.io/max-cpu', None)

    def variant(name, env=None, cpu=None, tier=None, containers=3):
        doc = copy.deepcopy(base)
        doc['metadata']['name'] = name
        if env:
            doc['metadata']['labels']['env'] = env
        if tier:
            doc['metadata']['labels']['tier'] = tier
        if cpu:
            doc['metadata']['annotations']['budget.io/max-cpu'] = cpu
        while len(doc['spec']['containers']) < containers:
            extra = copy.deepcopy(doc['spec']['containers'][0])
            extra['name'] = f'c{len(doc["spec"]["containers"])}'
            doc['spec']['containers'].append(extra)
        return doc

    return {
        'fills': variant('fills-32'),
        'one-over': variant('one-over', env='prod'),
        'two-over': variant('two-over', env='prod', cpu='24'),
        'three-over': variant('three-over', env='prod', cpu='24',
                              tier='web', containers=4),
    }


#: columns a row asks for, and the (policy, rule) cells it loses: the
#: columns ship lowest first, the anyPattern children lie above every
#: program's own, so the last anyPattern rule's children go first
BUDGET_CASES = {
    'fills': (32, []),
    'one-over': (33, [('require-non-root-groups', 'check-runasgroup')]),
    'two-over': (34, [('require-non-root-groups', 'check-runasgroup')]),
    'three-over': (35, [('require-non-root-groups', 'check-runasgroup'),
                        ('restrict-seccomp-strict',
                         'check-seccomp-strict')]),
}


def scan_with_ledger(scanner, docs, by_report: bool):
    """``(rows or reports, ledger report)`` of one scan with the coverage
    ledger on for its length."""
    benchlib.program_telemetry()
    try:
        out = reports_of(scanner, docs) if by_report \
            else rows_of(scanner, docs)
        return out, coverage.ledger().report()
    finally:
        telemetry_off()


@pytest.mark.parametrize('case', list(BUDGET_CASES))
def test_the_rows_ask_for_the_columns_the_case_says(scanned, budget_rows,
                                                    case):
    columns, lost = BUDGET_CASES[case]
    scanner = scanned['scanner']
    (relevant,) = relevant_columns(scanner, [budget_rows[case]])
    assert len(relevant) == columns
    progs = scanner.cps.programs
    beyond = {(progs[j].policy_name, progs[j].rule_name)
              for _column, j in relevant[BUDGET:]}
    assert sorted(beyond) == sorted(lost)


@pytest.mark.parametrize('by_report', [False, True],
                         ids=['rows', 'reports'])
@pytest.mark.parametrize('case', list(BUDGET_CASES))
def test_a_row_beyond_the_budget_is_answered_as_the_engine_and_counted(
        scanned, policies, budget_rows, case, by_report):
    _columns, lost = BUDGET_CASES[case]
    # beside a row that fills the budget and loses nothing
    docs = [budget_rows['fills'], budget_rows[case]]
    scanner = scanned['scanner']
    out, ledger = scan_with_ledger(scanner, docs, by_report)
    engine = scanner.engine
    if by_report:
        for d, report in zip(docs, out):
            want = engine_report(engine, policies, d)
            assert [(r['policy'], r['rule'], r['result'], r['message'])
                    for r in report['spec']['results']] == \
                [(r['policy'], r['rule'], r['result'], r['message'])
                 for r in want['spec']['results']]
    else:
        assert out == [host_rows(engine, policies, d) for d in docs]
    by_reason = ledger['fallbacks'].get('validate', {})
    assert by_reason.get(coverage.REASON_FAIL_DETAIL_BUDGET, 0) == len(lost)
    counted = sorted(
        (r['policy'], r['rule']) for r in ledger['rules'] if r['host_rows']
        and (r['policy'], r['rule']) in lost)
    assert counted == sorted(lost)
    totals = ledger['totals']
    assert totals['device_rows'] + totals['host_rows'] == \
        totals['total_rows']


def test_expand_compact_marks_only_what_lies_above_a_full_rows_last_slot():
    from types import SimpleNamespace
    from kyverno_tpu.ops.eval import expand_compact
    ev = SimpleNamespace(n_cols_u=6, n_adm=0, expand_identity=True)
    out8 = np.zeros((3, 4), np.int8)  # two unique trees, statuses|details
    # k = 2 slots: [columns | fail details]; 6 is "no column"
    out32 = np.array([[1, 6, 70, 0],    # one relevant column: not full
                      [0, 3, 71, 72],   # full: 4 and 5 may have been lost
                      [6, 6, 0, 0]], np.int32)
    _s, _d, dense, _adm = expand_compact(out8, out32, ev)
    B = FDET_BEYOND_BUDGET
    assert dense.tolist() == [[-1, 70, -1, -1, -1, -1],
                              [71, -1, -1, 72, B, B],
                              [-1, -1, -1, -1, -1, -1]]


def test_a_budget_no_narrower_than_the_columns_marks_nothing(scanned):
    """The cells that were in the benchmark before this pack: 15 and 23
    columns under a budget of 32."""
    scanner = BatchScanner(benchlib.load_policies(['pss', 'pack',
                                                   'config4']))
    assert scanner._evaluator.n_cols_u < BUDGET
    docs = [d for d in chart_cluster.generate(SEED, 200)
            if d['kind'] in ('Pod', 'Deployment')][:40]
    match = scanner.match_matrix(docs, [Resource(d) for d in docs])
    _status, _detail, fdet = scanner._device_statuses(docs, match=match)
    assert not (fdet == FDET_BEYOND_BUDGET).any()


# -- matched cells ------------------------------------------------------------

def test_match_cells_total_is_the_count_made_by_hand(scanned, cluster):
    docs = [next(d for d in cluster if d['kind'] == kind)
            for kind in chart_cluster.KINDS]
    assert len(docs) == 7
    scanner = scanned['scanner']
    registry = benchlib.program_telemetry()
    try:
        list(scanner.scan_report_results(docs))
        got = {r: int(registry.counter_value(devtel.MATCH_CELLS, result=r))
               for r in ('matched', 'unmatched')}
    finally:
        telemetry_off()
    # by hand: a Pod is matched by every rule's own program and by the
    # nine best-practice programs, a CronJob by the autogen-cronjob-
    # programs, the five other controllers by the autogen- programs
    by_hand = 0
    for doc in docs:
        for prog in scanner.cps.programs:
            name = prog.rule_name
            wants = 'CronJob' if name.startswith('autogen-cronjob-') else \
                'controller' if name.startswith('autogen-') else 'Pod'
            kind = doc['kind'] if doc['kind'] in ('Pod', 'CronJob') \
                else 'controller'
            by_hand += wants == kind
    assert by_hand == 31 + 6 * 22
    assert got == {'matched': by_hand,
                   'unmatched': 7 * 75 - by_hand}


def test_without_telemetry_no_cell_is_counted():
    telemetry_off()
    devtel.record_match_cells(np.ones((2, 3), bool))  # no registry: no-op
    registry = benchlib.program_telemetry()
    try:
        devtel.record_match_cells(None)
        devtel.record_match_cells(np.zeros((0, 75), bool))
        assert registry.counter_value(devtel.MATCH_CELLS,
                                      result='matched') == 0
        devtel.record_match_cells(np.array([[True, False, True]]))
        assert registry.counter_value(devtel.MATCH_CELLS,
                                      result='matched') == 2
        assert registry.counter_value(devtel.MATCH_CELLS,
                                      result='unmatched') == 1
    finally:
        telemetry_off()
