"""A scan's reports do not depend on the bulk chunk capacity.

The bulk canonical capacity (``KTPU_SCAN_CHUNK``, ``compiler/shapes.py``)
decides how many rows a streamed chunk and one bulk dispatch hold, so it
moves where chunk boundaries fall, how many chunks the encoder workers
share and which rows one dispatch pads.  None of that may reach a report.
Here the reports controller reconciles the same cluster at three
capacities, each scan several chunks long: the committed PSS packs
(``pss`` + ``pack`` + ``config4``) over ``generators/mixed_cluster.py``
and the Helm chart's pack (``chart`` + ``pack`` + ``config4``) over
``generators/chart_cluster.py``, the sets of the cells
``bgscan_pss_100k`` and ``bgscan_chart_kinds``.  Every stored report, and
the coverage ledger's totals, must be the same at every capacity.
"""

import pytest

import benchlib
from kyverno_tpu.compiler.scan import BatchScanner
from kyverno_tpu.observability import coverage
from kyverno_tpu.observability import device as devtel
from kyverno_tpu.observability import executables as exectel

SEED = 2 ** 31 + 4404
#: a whole number of chunks at every capacity
ROWS = 2048
CAPACITIES = (256, 512, 1024)
SETS = {
    'pss': (['pss', 'pack', 'config4'], 'mixed_cluster'),
    'chart': (['chart', 'pack', 'config4'], 'chart_cluster'),
}
#: the cells' report sink: every report the controller writes, by name
ReportStore = benchlib.load_module('drivers', 'reports_controller').ReportStore


@pytest.fixture(scope='module')
def clusters():
    out = {}
    for name, (packs, generator) in SETS.items():
        docs = benchlib.load_module('generators', generator).generate(
            SEED, ROWS)
        for i, doc in enumerate(docs):
            doc['metadata'].setdefault('uid', f'uid-{name}-{i}')
        out[name] = (benchlib.load_policies(packs), docs)
    return out


def scan(clusters, name, capacity, tmp_path_factory):
    """One reconcile of the set's cluster at ``capacity``: the stored
    reports, the coverage totals and the number of chunks."""
    from kyverno_tpu.reports.controllers import (BackgroundScanController,
                                                 MetadataCache)
    policies, docs = clusters[name]
    patch = pytest.MonkeyPatch()
    patch.setenv('KTPU_SCAN_CHUNK', str(capacity))
    patch.setattr(BatchScanner, 'CHUNK', capacity)
    patch.setenv('KTPU_VERDICT_CACHE_DIR',
                 str(tmp_path_factory.mktemp(f'verdicts-{name}')))
    ctrl = None
    try:
        registry = benchlib.program_telemetry()
        store, cache = ReportStore(), MetadataCache()
        ctrl = BackgroundScanController(store, policies, cache=cache)
        for doc in docs:
            cache.update(doc)
        ctrl.enqueue_all()
        written = ctrl.reconcile(now=4404.0)
        return {
            'written': len(written),
            'reports': store.reports,
            'coverage': {k: v for k, v in coverage.bench_block().items()
                         if isinstance(v, (int, float))},
            'chunks': sum(
                count for labels, count, _total in
                registry.histogram_series(devtel.SCAN_STAGE_DURATION)
                if dict(labels).get('stage') == 'd2h'),
        }
    finally:
        if ctrl is not None:
            ctrl.close()
        coverage.disable()
        devtel.disable()
        exectel.disable()
        patch.undo()


@pytest.fixture(scope='module')
def scans(clusters, tmp_path_factory):
    """``(set, capacity) -> scan``, each scanned once for the module."""
    done = {}

    def get(name, capacity):
        if (name, capacity) not in done:
            done[name, capacity] = scan(clusters, name, capacity,
                                        tmp_path_factory)
        return done[name, capacity]
    return get


@pytest.mark.parametrize('capacity', CAPACITIES)
@pytest.mark.parametrize('name', sorted(SETS))
def test_reports_do_not_depend_on_the_chunk_capacity(clusters, scans, name,
                                                     capacity):
    got = scans(name, capacity)
    n = len(clusters[name][1])
    assert got['written'] == n and len(got['reports']) == n
    assert got['chunks'] == n // capacity > 1
    assert got['coverage']['total_rows'] == \
        got['coverage']['device_rows'] + got['coverage']['host_rows']
    for other in CAPACITIES:
        if other == capacity:
            continue
        want = scans(name, other)
        assert got['coverage'] == want['coverage'], (capacity, other)
        assert got['reports'].keys() == want['reports'].keys()
        differing = [k for k, report in got['reports'].items()
                     if report != want['reports'][k]]
        assert not differing, (capacity, other, differing[:3])
