"""Lanes are born packed (ISSUE 33).

An arena's encode writes a batch's lanes as views of one ``[R, W]``
buffer a dtype, with the columns of the lanes that join later
(``__match__``, the admission lanes) kept free, and ``pack_batch`` hands
those buffers to the transfer instead of copying every lane.  Pinned
here:

* what the evaluator is handed is what the parent commit's
  ``pack_batch`` (kept below, verbatim) gives over loose copies of the
  same lanes, byte for byte and offset for offset: for the committed
  pack, the tenants' set and a set with admission lanes, at the
  admission capacity and at a scan's chunk capacity, through the
  scanner's arena and through a worker's block;
* a recycled arena batch holds nothing of its last chunk, the joining
  lanes' columns included;
* loose lanes, and a set that lacks a joining lane, still take the
  copy, in the layout of their own signature;
* ``kyverno_tpu_pack_batches_total{via}`` and ``AdmissionBatcher.stats()``
  ``pack_view_dispatches`` say which way a batch went;
* a batch goes back to its arena only after its device inputs were
  freed: the transfer reads the arena's memory.
"""

import json
import os
import sys

import numpy as np
import pytest
import yaml

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import benchlib  # noqa: E402
import test_admission_lanes as admtests  # noqa: E402
import test_encode_blocks as blocktests  # noqa: E402
from kyverno_tpu.api.policy import Policy  # noqa: E402
from kyverno_tpu.compiler import admission as admlanes  # noqa: E402
from kyverno_tpu.compiler.encode import (LaneArena,  # noqa: E402
                                         encode_batch)
from kyverno_tpu.compiler.packing import (PackedLanes,  # noqa: E402
                                          PackedSet, plan_layout)
from kyverno_tpu.compiler.scan import BatchScanner  # noqa: E402
from kyverno_tpu.observability import device as devtel  # noqa: E402
from kyverno_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from kyverno_tpu.ops import eval as eval_mod  # noqa: E402

ADMISSION_CAP = 64
CHUNK = 128  # a scan's chunk capacity here (8,192 on the chip)
tenant_policies = benchlib.load_module('generators', 'tenant_policies')


def parent_layout(tensors):
    """The plan of ``ops/eval.py`` ``pack_batch`` as the parent commit
    has it: the layout every executable was built for."""
    groups = {}
    for name, arr in sorted(tensors.items()):
        groups.setdefault(str(arr.dtype), []).append((name, arr))
    layout = {}
    group_names = []
    for dt, members in sorted(groups.items()):
        off = 0
        names = []
        for name, arr in members:
            w = int(np.prod(arr.shape[1:], dtype=np.int64)) \
                if arr.ndim > 1 else 1
            layout[name] = (f'pk_{dt}', off, w, arr.shape[1:])
            names.append(name)
            off += w
        group_names.append((f'pk_{dt}', names))
    return layout, group_names


def parent_pack_batch(tensors):
    """And its ``pack_batch`` (the memo apart)."""
    layout, group_names = parent_layout(tensors)
    packed = {}
    for buf_name, names in group_names:
        r = tensors[names[0]].shape[0]
        parts = [tensors[n].reshape(r, -1) for n in names]
        packed[buf_name] = parts[0] if len(parts) == 1 \
            else np.concatenate(parts, axis=1)
    return packed, layout


def policy_set(name):
    pack = benchlib.load_policies(['pss', 'pack', 'config4'])
    if name == 'pack':
        return pack
    if name == 'tenants':
        return pack + [Policy(doc) for doc in
                       tenant_policies.generate(7, namespaces=6)]
    assert name == 'admission'
    return pack + [Policy(doc) for doc in
                   yaml.safe_load_all(admtests.POLICIES)]


@pytest.fixture(scope='module', params=['pack', 'tenants', 'admission'])
def scanners(request):
    """``(in-process, with one worker)`` scanners of one set, with a
    small chunk."""
    patch = pytest.MonkeyPatch()
    made = []
    for procs in (0, 1):
        patch.setenv('KTPU_ENCODE_PROCS', str(procs))
        scanner = BatchScanner(policy_set(request.param))
        scanner.CHUNK = CHUNK
        made.append(scanner)
    patch.undo()
    assert (made[0]._adm is not None) == (request.param == 'admission')
    yield made
    made[1]._encoder_pool.close()


@pytest.fixture()
def registry():
    reg = devtel.configure(MetricsRegistry())
    yield reg
    devtel.disable()


@pytest.fixture()
def packs(monkeypatch):
    """Every batch through ``pack_batch``: loose copies of the lanes as
    they came, what was handed to the transfer, and which way."""
    seen = []
    real = eval_mod._pack_batch

    def spy(tensors):
        loose = {name: np.array(lane) for name, lane in tensors.items()}
        packed, layout, via = real(tensors)
        assert all(buf.flags.c_contiguous for buf in packed.values())
        # copies: an arena's buffers are the next batch's too
        seen.append((loose, {name: buf.copy()
                             for name, buf in packed.items()},
                     layout, via))
        return packed, layout, via
    monkeypatch.setattr(eval_mod, '_pack_batch', spy)
    return seen


def assert_the_parents_pack(loose, packed, layout):
    want, want_layout = parent_pack_batch(loose)
    assert layout == want_layout
    assert list(layout) == list(want_layout)  # unpack's order, too
    assert list(packed) == list(want)
    for name, buf in want.items():
        got = packed[name]
        assert got.dtype == buf.dtype and got.shape == buf.shape, name
        assert got.tobytes() == buf.tobytes(), name


def admissions(n):
    users = [{'userInfo': {'username': 'alice', 'groups': []}},
             {'userInfo': {'username': 'bob',
                           'groups': ['system:masters']},
              'clusterRoles': ['bot-role']},
             {'userInfo': {'username': 'eve', 'groups': ['trusted-bots']},
              'roles': ['ns:dev-role']}]
    return [(users[i % 3], [], {}, 'CREATE') for i in range(n)]


class TestWhatTheEvaluatorIsHanded:
    def test_at_the_admission_capacity(self, scanners, packs, registry):
        scanner = scanners[0]
        for n, seed in ((3, 1), (1, 2), (ADMISSION_CAP, 3)):
            docs = blocktests.pods(n, seed=seed)
            scanner.scan(docs, admissions=admissions(n))
        assert len(packs) == 3
        for loose, packed, layout, via in packs:
            assert via == 'view'
            assert '__match__' in loose and \
                loose['__rowvalid__'].shape[0] == ADMISSION_CAP
            assert ('__adm_user__' in loose) == (scanner._adm is not None)
            assert_the_parents_pack(loose, packed, layout)
        assert registry.counter_value(devtel.PACK_BATCHES, via='view') == 3
        assert registry.counter_value(devtel.PACK_BATCHES, via='copy') == 0

    @pytest.mark.parametrize('procs', [0, 1])
    def test_at_a_scans_chunk_capacity(self, scanners, packs, registry,
                                       procs):
        scanner = scanners[procs]
        docs = blocktests.pods(3 * CHUNK + 9, seed=11)
        rows = list(scanner.scan_report_results(docs, now=1.0))
        assert len(rows) == len(docs)
        assert len(packs) == 4
        for loose, packed, layout, via in packs:
            assert via == 'view'
            assert loose['__rowvalid__'].shape[0] == CHUNK
            assert_the_parents_pack(loose, packed, layout)
        assert registry.counter_value(devtel.PACK_BATCHES, via='view') == 4
        if procs:
            assert registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                          result='ok') == 4
            assert not scanner._encoder_pool._broken


@pytest.mark.parametrize('rows', [64, 8192, 16384])
def test_the_committed_pack_at_the_chips_capacities(rows):
    """The cells' batch (elements, gathers and element gathers 4 wide)
    at the capacities the chip runs (64 and the bulk default 8,192; 16,384
    a ``KTPU_SCAN_CHUNK`` a deployment may set): planned offset for offset
    as the parent plans it.  Zero-stride
    stand-ins: a plan reads dtype and shape, and 16,384 rows of lanes
    are 272 MB."""
    from kyverno_tpu.compiler.encode import lane_signature
    scanner = BatchScanner(policy_set('pack'))
    joining = scanner._arena.joining
    assert list(joining) == ['__match__']
    full = dict(lane_signature(scanner.cps, (rows, 4, 4, 4)))
    full['__match__'] = (np.dtype(np.uint8),
                         (rows,) + joining['__match__'][1])
    assert len(full) > 1000  # 1,226 lanes
    want, want_groups = parent_layout({
        name: np.broadcast_to(np.zeros((), dtype), shape)
        for name, (dtype, shape) in full.items()})
    layout, groups = plan_layout(full)
    assert layout == want and list(layout) == list(want)
    assert [(buf, names) for buf, _d, _w, names in groups] == want_groups
    assert {buf: width for buf, _d, width, _n in groups} == {
        buf: sum(want[n][2] for n in names) for buf, names in want_groups}


class TestARecycledBatch:
    def test_holds_nothing_of_its_last_chunk(self):
        cps = BatchScanner(policy_set('admission')).cps
        table = admlanes.compile_admission(cps)
        joining = {'__match__': (np.uint8, (5,)),
                   **admlanes.lane_signature(table)}
        arena = LaneArena(joining=joining)
        first = encode_batch(blocktests.pods(CHUNK, seed=1), cps,
                             padded_n=CHUNK, arena=arena)
        lanes = first.tensors().copy()
        lanes['__match__'] = np.full((CHUNK, 5), 7, np.uint8)
        lanes.update(admlanes.zero_lanes(table, CHUNK))  # ids of -1
        packed, layout = eval_mod.pack_batch(lanes)
        assert all(buf.any() for buf in packed.values())
        buffers = first.packed.buffers
        arena.release(first)
        docs = blocktests.pods(2, seed=2)
        again = encode_batch(docs, cps, padded_n=CHUNK, arena=arena)
        assert again is first and again.packed.buffers is buffers
        want = encode_batch(docs, cps, padded_n=CHUNK).tensors()
        blocktests.assert_same_lanes(again.tensors(), want)
        for name in joining:
            assert not again.packed.views[name].any(), name
        # and the whole of it is what a fresh pack of the lanes gives
        want['__match__'] = np.zeros((CHUNK, 5), np.uint8)
        want.update({name: np.zeros_like(lane) for name, lane in
                     admlanes.zero_lanes(table, CHUNK).items()})
        assert_the_parents_pack(want, again.packed.buffers,
                                again.packed.layout)

    def test_a_reused_block_holds_nothing_in_the_joining_columns(self):
        cps = BatchScanner(policy_set('admission')).cps
        table = admlanes.compile_admission(cps)
        joining = {'__match__': (np.uint8, (5,)),
                   **admlanes.lane_signature(table)}
        from kyverno_tpu.compiler.scan import _EncoderPool
        pool = _EncoderPool(cps, 1, joining)
        others = blocktests.segments()  # of this module's scanners
        assert pool.start()
        try:
            block = pool.blocks.acquire()
            lanes, home = blocktests.through_a_block(
                pool, block, blocktests.pods(CHUNK, seed=1), CHUNK)
            first = lanes.copy()
            first['__match__'] = np.full((CHUNK, 5), 7, np.uint8)
            first.update(admlanes.zero_lanes(table, CHUNK))
            packed, _layout, via = eval_mod._pack_batch(first)
            assert via == 'view' and all(b.any() for b in packed.values())
            del lanes, first, packed
            docs = blocktests.pods(2, seed=2)
            lanes, home2 = blocktests.through_a_block(pool, block, docs,
                                                      CHUNK)
            assert home2[0] == home[0]
            owner = lanes.owner
            for name in joining:
                assert not owner.views[name].any(), name
            loose = {name: np.array(lane) for name, lane in lanes.items()}
            blocktests.assert_same_lanes(
                lanes, encode_batch(docs, cps, padded_n=CHUNK).tensors())
            joined = lanes.copy()
            mm = np.arange(CHUNK * 5, dtype=np.uint8).reshape(CHUNK, 5)
            joined['__match__'] = loose['__match__'] = mm
            adm = admlanes.zero_lanes(table, CHUNK)
            joined.update(adm)
            loose.update(adm)
            packed, layout, via = eval_mod._pack_batch(joined)
            assert via == 'view'
            assert_the_parents_pack(loose, packed, layout)
            del lanes, joined, packed, owner
            pool.blocks.release(block)
        finally:
            pool.close()
        assert blocktests.segments() == others


class TestWhatStillTakesTheCopy:
    def test_loose_lanes(self, registry):
        cps = BatchScanner(policy_set('pack')).cps
        lanes = encode_batch(blocktests.pods(5), cps,
                             padded_n=ADMISSION_CAP).tensors()
        assert isinstance(lanes, PackedLanes) and lanes.owner is None
        lanes['__match__'] = np.ones((ADMISSION_CAP, 3), np.uint8)
        packed, layout, via = eval_mod._pack_batch(lanes)
        assert via == 'copy'
        assert_the_parents_pack(dict(lanes), packed, layout)
        # a plain dict of arrays, as tests and tools pass
        packed, layout, via = eval_mod._pack_batch(dict(lanes))
        assert via == 'copy'
        assert_the_parents_pack(dict(lanes), packed, layout)
        for lane in packed.values():
            assert not any(np.shares_memory(lane, x)
                           for x in lanes.values())

    def test_a_set_that_is_not_the_arenas_any_more(self):
        cps = BatchScanner(policy_set('pack')).cps
        arena = LaneArena(joining={'__match__': (np.uint8, (3,))})
        batch = encode_batch(blocktests.pods(5), cps,
                             padded_n=ADMISSION_CAP, arena=arena)
        mm = np.ones((ADMISSION_CAP, 3), np.uint8)

        def packs_as(change):
            lanes = batch.tensors().copy()
            lanes['__match__'] = mm
            change(lanes)
            loose = {k: np.array(v) for k, v in lanes.items()}
            packed, layout, via = eval_mod._pack_batch(lanes)
            assert_the_parents_pack(loose, packed, layout)
            return via
        assert packs_as(lambda lanes: None) == 'view'
        # no match plane (a scan that ships none): another signature
        assert packs_as(lambda lanes: lanes.pop('__match__')) == 'copy'
        # a match plane of another width than the columns kept for it
        assert packs_as(lambda lanes: lanes.update(
            __match__=np.ones((ADMISSION_CAP, 4), np.uint8))) == 'copy'
        # one lane replaced by an equal array that is not the view
        assert packs_as(lambda lanes: lanes.update(
            s0_tag=np.array(lanes['s0_tag']))) == 'copy'
        # one lane more than the batch has
        assert packs_as(lambda lanes: lanes.update(
            zz=np.zeros(ADMISSION_CAP, np.int8))) == 'copy'
        # a plain dict of the very views: nobody to ask
        lanes = dict(batch.tensors(), __match__=mm)
        assert eval_mod._pack_batch(lanes)[2] == 'copy'
        # and after all that the arena's own set is still handed over
        assert packs_as(lambda lanes: None) == 'view'

    def test_warm_up_dispatches_and_the_mesh_step_encode_loose(self,
                                                               packs):
        scanner = BatchScanner(policy_set('pack'))
        scanner.warmup_shapes([ADMISSION_CAP])
        assert [p[3] for p in packs] == ['copy']
        assert_the_parents_pack(*packs[0][:3])


def test_a_lane_is_a_run_of_its_buffers_columns():
    sig = {'a': (np.dtype(np.int8), (4, 2, 3)),
           'b': (np.dtype(np.int8), (4,)),
           'c': (np.dtype(np.int64), (4, 2))}
    packed = PackedSet(sig, {'j': (np.int8, (2,))}, lambda specs: [
        np.zeros(shape, dtype) for _buf, shape, dtype in specs])
    assert packed.layout == {'c': ('pk_int64', 0, 2, (2,)),
                             'a': ('pk_int8', 0, 6, (2, 3)),
                             'b': ('pk_int8', 6, 1, ()),
                             'j': ('pk_int8', 7, 2, (2,))}
    assert list(packed.lanes()) == ['a', 'b', 'c']
    packed.views['a'][1, 1, 2] = 5
    packed.views['b'][3] = 6
    assert packed.buffers['pk_int8'][1, 5] == 5
    assert packed.buffers['pk_int8'][3, 6] == 6
    lanes = packed.lanes().copy()
    lanes['j'] = np.full((4, 2), 9, np.int8)
    assert packed.takes(lanes)
    assert (packed.buffers['pk_int8'][:, 7:] == 9).all()
    packed.clear()
    assert not any(buf.any() for buf in packed.buffers.values())


class TestLifetime:
    """``jnp.asarray`` may read the host buffer until the transfer is
    done, and on XLA:CPU the device array IS that memory: the arena's
    batch, or the pool's block, goes back only after ``_free_inputs``."""

    @pytest.mark.parametrize('procs', [0, 1])
    def test_released_only_after_the_device_inputs_were_freed(
            self, scanners, monkeypatch, procs):
        scanner = scanners[procs]
        events = []
        free = scanner._free_inputs

        def freed(t, out):
            free(t, out)
            assert all(arr.is_deleted() for arr in t.values())
            events.append('freed')
        monkeypatch.setattr(scanner, '_free_inputs', freed)
        # a chunk's buffers are a block where a worker encoded it, else
        # a batch of the arena's (a scan of one chunk encodes inline)
        for holder in (scanner._encoder_pool.blocks, scanner._arena):
            def released(what, release=holder.release):
                events.append('released')
                release(what)
            monkeypatch.setattr(holder, 'release', released)
        docs = blocktests.pods(3 * CHUNK + 9, seed=5)
        assert len(list(scanner.scan_report_results(docs))) == len(docs)
        assert events == ['freed', 'released'] * 4
        del events[:]
        scanner.scan(blocktests.pods(2), admissions=admissions(2))
        assert events == ['freed', 'released']

    def test_a_batch_recycled_under_its_transfer_would_show(self, packs):
        """Why the order matters, on XLA:CPU where ``jnp.asarray`` of a
        host buffer is that buffer: the handed-over buffers alias the
        device inputs, so zeroing the batch under them changes what the
        evaluator would read."""
        import jax
        scanner = BatchScanner(policy_set('pack'))
        batch = encode_batch(blocktests.pods(ADMISSION_CAP), scanner.cps,
                             padded_n=ADMISSION_CAP, arena=scanner._arena)
        lanes = batch.tensors().copy()
        lanes['__match__'] = np.ones(
            (ADMISSION_CAP, scanner._evaluator.n_uniq), np.uint8)
        t, _layout = eval_mod.shard_batch(lanes, None)
        assert packs[-1][3] == 'view'
        jax.block_until_ready(t)
        before = np.array(t['pk_int8'])
        assert before.any()
        batch.clear()  # what a recycled batch's next encode starts with
        aliased = not np.array(t['pk_int8']).any()
        assert aliased == np.shares_memory(
            np.asarray(t['pk_int8']), batch.packed.buffers['pk_int8'])


def test_the_batcher_counts_the_dispatches_handed_over(monkeypatch):
    from kyverno_tpu.config.config import Configuration
    from kyverno_tpu.policycache import cache as pcache
    from kyverno_tpu.webhooks.handlers import ResourceHandlers
    from kyverno_tpu.webhooks.server import WebhookServer
    devtel.configure(MetricsRegistry())
    try:
        cache = pcache.Cache()
        cache.warm_up(benchlib.replicate_enforce(policy_set('pack'), 11))
        handlers = ResourceHandlers(cache, configuration=Configuration(),
                                    serving_mode='batch')
        server = WebhookServer(handlers, configuration=Configuration())
        assert handlers.wait_device_ready(
            cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod'),
            timeout=600)
        batcher = handlers._get_batcher()
        assert batcher.stats()['pack_view_dispatches'] == 0
        for i, doc in enumerate(blocktests.pods(3, seed=4)):
            body = json.dumps({
                'apiVersion': 'admission.k8s.io/v1',
                'kind': 'AdmissionReview',
                'request': {
                    'uid': f'u{i}', 'operation': 'CREATE',
                    'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
                    'namespace': doc['metadata'].get('namespace',
                                                     'default'),
                    'name': doc['metadata']['name'], 'object': doc,
                    'userInfo': {'username': 'alice',
                                 'groups': []}}}).encode()
            server.handle('/validate/fail', body)
        stats = batcher.stats()
        assert stats['dispatches'] >= 1
        assert stats['pack_view_dispatches'] == stats['dispatches']
        batcher.reset_stats()
        assert batcher.stats()['pack_view_dispatches'] == 0
        batcher.stop(drain=False)
    finally:
        devtel.disable()
