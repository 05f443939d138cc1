"""Every layer file that reads a span or a field of the program names one
the program has, so that a renamed stage or ``stats()`` field fails here and
does not turn its metric into "nothing to read" on the chip."""

import glob
import os

import pytest

import benchlib

LAYERS = sorted(os.path.basename(p)[:-len('.json')] for p in glob.glob(
    os.path.join(benchlib.BENCH_DIR, 'layers', '*.json')))


def keys_under(args: dict, prefix: str) -> list:
    return [v[len(prefix):] for v in args.values()
            if isinstance(v, str) and v.startswith(prefix)]


@pytest.fixture(scope='module')
def fresh_batcher_stats():
    from kyverno_tpu.serving.batcher import AdmissionBatcher
    batcher = AdmissionBatcher()
    try:
        return batcher.stats()
    finally:
        batcher.stop(drain=False)


@pytest.mark.parametrize('name', LAYERS)
def test_the_layer_file_names_what_the_program_has(name,
                                                   fresh_batcher_stats):
    from kyverno_tpu.observability import device as devtel
    spec = benchlib.load_data('layers', name)
    args = spec.get('args', {})
    if spec['reader'] == 'stage_mean':
        assert args['stage'] in devtel.STAGES
    if spec['reader'] in ('counter_value', 'counter_ratio'):
        # the reports driver snapshots the whole stage histogram under
        # 'stages', the webhook driver every number of stats() under
        # 'batcher'
        for key in keys_under(args, 'batcher.'):
            assert isinstance(fresh_batcher_stats.get(key), (int, float)), key
        for key in keys_under(args, 'stages.'):
            assert key.split('.')[0] in devtel.STAGES, key


def test_the_check_sees_the_layers_it_is_meant_for():
    readers = {benchlib.load_data('layers', name)['reader']
               for name in LAYERS}
    assert {'stage_mean', 'counter_value', 'counter_ratio'} <= readers
