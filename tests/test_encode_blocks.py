"""An encoder worker's lanes come home in a shared-memory block (ISSUE 29),
as views of the packed buffers the evaluator takes (ISSUE 33).

A worker encodes a chunk in place into a block this process owns, its
lanes views of one ``[R, W]`` buffer a dtype laid over the block, and
returns what places the buffers; the h2d thread maps the block and finds
the lanes in them.  Pinned here:

* the lanes through a block are ``encode_batch``'s in-process lanes, name
  for name and byte for byte, in every lane family, also when the block
  is reused after a larger batch and when the batch outgrows the block;
* the block's buffers, once the joining lanes are in their columns, are
  ``pack_batch``'s over loose copies of the same lanes, byte for byte and
  offset for offset, and are handed over, not copied; a reused block
  holds nothing of its last chunk in those columns either;
* a block goes back for the next chunk only after its chunk's device
  inputs were freed;
* a multi-chunk scan through two blocks gives the in-process scan's
  reports (on XLA:CPU ``jnp.asarray`` of a numpy view is zero-copy: a
  block recycled too early would show);
* no segment of this process is left in ``/dev/shm`` after any ending:
  ``close()``, ``stop_encoder_processes()``, a worker that died with its
  chunk, a host with no room for a block, a generator closed early (and
  a block whose chunk died while its worker was still at work is never
  handed to another chunk);
* a worker's exit does not unlink a block this process still uses;
* ``kyverno_tpu_encode_result_bytes_total`` reads the lanes under
  ``via="block"`` and a few kB under ``via="pipe"``.
"""

import errno
import glob
import os
import random
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import benchlib  # noqa: E402
import encode_block_helpers as helpers  # noqa: E402
import test_foreach_compile as foreach  # noqa: E402
from kyverno_tpu.compiler import encode as encode_mod  # noqa: E402
from kyverno_tpu.compiler import scan as scan_mod  # noqa: E402
from kyverno_tpu.compiler.compile import compile_policies  # noqa: E402
from kyverno_tpu.compiler.encode import encode_batch  # noqa: E402
from kyverno_tpu.compiler.scan import BatchScanner, _EncoderPool  # noqa: E402
from kyverno_tpu.observability import device as devtel  # noqa: E402
from kyverno_tpu.ops import eval as eval_mod  # noqa: E402
from kyverno_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from kyverno_tpu.reports.types import build_fused_report  # noqa: E402

CAP = 16  # rows a chunk, so a few dozen pods span several chunks
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
mixed_cluster = benchlib.load_module('generators', 'mixed_cluster')


def segments():
    """This process's encode blocks in ``/dev/shm``, by name."""
    return sorted(os.path.basename(p) for p in
                  glob.glob('/dev/shm/ktpu-enc-%d-*' % os.getpid()))


def pods(n, seed=5, containers=None):
    """PSS-shaped pods (string heads, gathers) with the foreach pack's
    capabilities on some containers (element gathers); ``containers``
    fixes the count, which sets the element axis and so the batch's
    bytes."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        pod = mixed_cluster.make_config4_pod(rng, i)
        donor = foreach.make_pod(rng)['spec'].get('containers') or []
        for c, d in zip(pod['spec']['containers'], donor):
            if 'securityContext' in d:
                c.setdefault('securityContext', {}).update(
                    d['securityContext'])
        if containers is not None:
            first = pod['spec']['containers'][0]
            pod['spec']['containers'] = [
                dict(first, name=f'c{k}') for k in range(containers)]
        out.append(pod)
    return out


@pytest.fixture(scope='module')
def wide_cps():
    """Every lane family: slots with string heads, array metadata,
    gathers and element gathers."""
    cps = compile_policies(benchlib.load_policies(['pss', 'config4'])
                           + foreach.load_pack())
    assert cps.slots and cps.gathers and cps.elem_gathers
    return cps


@pytest.fixture(scope='module')
def policies():
    return benchlib.load_policies(['pack']) + foreach.load_pack()


@pytest.fixture(scope='module', autouse=True)
def no_other_pools():
    """Blocks live as long as their scanner's pool, and scanners of
    files this process ran before may still be alive: close their
    pools, so that every segment seen here is one of these tests'."""
    for other in list(scan_mod._LIVE_POOLS):
        other.close()
    assert segments() == []


@pytest.fixture()
def pool(wide_cps):
    p = _EncoderPool(wide_cps, 1)
    assert p.start()
    yield p
    p.close()
    assert segments() == []


@pytest.fixture()
def registry():
    reg = devtel.configure(MetricsRegistry())
    yield reg
    devtel.disable()


def through_a_block(pool, block, docs, padded_n):
    """One chunk the way ``stage_encode`` and ``stage_h2d`` move it."""
    home = pool.submit(docs, None, padded_n, block).get(timeout=120)
    name, (key, layout), stages, span = home
    assert stages['encode'] > 0 and span[0] <= span[1]
    assert key[0] == padded_n
    return pool.lanes(block, home), home


def assert_same_lanes(got, want):
    assert list(got) == list(want)
    for name, lane in want.items():
        assert got[name].dtype == lane.dtype, name
        assert got[name].shape == lane.shape, name
        assert got[name].tobytes() == lane.tobytes(), name


def scanner_with(policies, procs, monkeypatch):
    monkeypatch.setenv('KTPU_ENCODE_PROCS', str(procs))
    s = BatchScanner(policies)
    s.CHUNK = CAP
    return s


def reports_of(scanner, docs, now=1234.0):
    return [build_fused_report(doc, *row)
            for doc, row in zip(docs, scanner.scan_report_results(
                docs, now=now))]


class TestLanes:
    def test_lanes_through_a_block_are_the_in_process_lanes(self, pool,
                                                            wide_cps):
        docs = pods(CAP - 3)
        want = encode_batch(docs, wide_cps, padded_n=CAP).tensors()
        for family in ('__rowvalid__', 's0_tag', 'a0_count', 'g0_kind',
                       'e0_kind'):
            assert family in want
        assert any(n.endswith('_str_head') for n in want)
        block = pool.blocks.acquire()
        got, home = through_a_block(pool, block, docs, CAP)
        assert_same_lanes(got, want)
        # what came through the pipe places one buffer a dtype, laid
        # one after the other inside the one segment, each start
        # aligned; the lanes are views of them
        placed = home[1][1]
        assert [name for name, *_ in placed] == [
            'pk_bool', 'pk_int32', 'pk_int64', 'pk_int8', 'pk_uint8']
        ends = 0
        for _name, dtype, shape, at in placed:
            assert at % 64 == 0 and at >= ends and shape[0] == CAP
            ends = at + np.dtype(dtype).itemsize * shape[0] * shape[1]
        assert ends <= block.shm.size < ends + 64
        assert sum(lane.nbytes for lane in got.values()) == sum(
            np.dtype(dtype).itemsize * shape[0] * shape[1]
            for _name, dtype, shape, _at in placed)
        whole = np.frombuffer(block.shm.buf, np.uint8)
        for lane in got.values():
            assert np.shares_memory(lane, whole)
        del whole
        assert segments() == [home[0]]
        del got
        pool.blocks.release(block)

    def test_a_reused_block_holds_nothing_of_its_last_chunk(self, pool,
                                                            wide_cps):
        block = pool.blocks.acquire()
        first, home = through_a_block(pool, block, pods(CAP, seed=1), CAP)
        del first
        docs = pods(3, seed=2)  # 13 rows of padding where lanes were
        again, home2 = through_a_block(pool, block, docs, CAP)
        assert_same_lanes(
            again, encode_batch(docs, wide_cps, padded_n=CAP).tensors())
        assert home2[0] == home[0] and segments() == [home[0]]
        del again
        pool.blocks.release(block)

    def test_a_batch_larger_than_the_offered_block(self, pool, wide_cps):
        """The worker creates a block of the right size under the spare
        name it was given; this process adopts it and unlinks the one
        it had offered."""
        block = pool.blocks.acquire()
        small, home = through_a_block(pool, block, pods(CAP, containers=2),
                                      CAP)
        size = block.shm.size
        del small
        docs = pods(CAP, seed=9, containers=11)  # element axis 4 -> 16
        large, home2 = through_a_block(pool, block, docs, CAP)
        assert_same_lanes(
            large, encode_batch(docs, wide_cps, padded_n=CAP).tensors())
        assert home2[0] != home[0] and block.shm.size > size
        assert segments() == [home2[0]]
        del large
        # and the larger block serves a small batch again
        docs = pods(CAP, seed=10, containers=2)
        small, home3 = through_a_block(pool, block, docs, CAP)
        assert_same_lanes(
            small, encode_batch(docs, wide_cps, padded_n=CAP).tensors())
        assert home3[0] == home2[0] and segments() == [home2[0]]
        del small
        pool.blocks.release(block)

    def test_no_room_in_dev_shm_is_an_error_not_a_dead_worker(self):
        """Every page of a new block is reserved at creation: one that
        tmpfs cannot back is an ``OSError`` there and leaves no segment,
        where a sparse one would kill the worker at some later write."""
        room = os.statvfs('/dev/shm')
        spare = 'ktpu-enc-%d-never' % os.getpid()
        with pytest.raises(OSError) as e:
            encode_mod.open_block((None, 0, spare),
                                  (room.f_blocks + 1) * room.f_frsize)
        assert e.value.errno == errno.ENOSPC
        assert segments() == []

    def test_a_block_still_out_with_a_worker_is_given_up_not_reused(
            self, pool, wide_cps):
        """The chunk dies (the generator is closed) while its worker is
        still encoding into the block: no later chunk may be offered
        it, what it held is unlinked, and the larger one the worker
        goes on to create is unlinked by a later sweep."""
        blocks = pool.blocks
        block = blocks.acquire()
        held, home = through_a_block(pool, block, pods(CAP), CAP)
        del held

        class Task:
            done = False

            def ready(self):
                return self.done
        name, size, spare = block.offer()  # out with a worker again
        block.task = Task()
        assert (name, size) == (home[0], block.shm.size)
        blocks.release(block)
        assert segments() == [] and list(blocks._lost) == [spare]
        assert blocks._all == [] and blocks._free == []
        encode_mod.open_block((None, 0, spare), size + 64).close()
        other = blocks.acquire()
        assert other is not block
        assert segments() == [spare]  # its worker has not answered yet
        Task.done = True
        blocks.release(other)
        # a block that is home goes back for the next chunk
        assert blocks.acquire() is other
        assert segments() == [] and blocks._lost == {}


class TestScan:
    def test_many_chunks_through_two_blocks_give_the_in_process_reports(
            self, policies, registry, monkeypatch):
        docs = pods(7 * CAP + 5)
        want = reports_of(scanner_with(policies, 0, monkeypatch), docs)
        assert registry.counter_total(devtel.ENCODE_RESULT_BYTES) == 0
        scanner = scanner_with(policies, 2, monkeypatch)
        try:
            seen = set()
            got = []
            for doc, row in zip(docs, scanner.scan_report_results(
                    docs, now=1234.0)):
                got.append(build_fused_report(doc, *row))
                seen.update(segments())
            assert got == want
            # as many blocks as the pipeline has slots, whatever the
            # number of chunks
            assert len(seen) == 2
            chunks = 8
            assert registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                          result='ok') == chunks
            lanes = encode_batch(docs[:CAP], scanner.cps,
                                 padded_n=CAP).tensors()
            assert registry.counter_value(
                devtel.ENCODE_RESULT_BYTES, via='block') == \
                chunks * sum(v.nbytes for v in lanes.values())
            assert 0 < registry.counter_value(
                devtel.ENCODE_RESULT_BYTES, via='pipe') < chunks * 65536
            assert not scanner._encoder_pool._broken
        finally:
            scanner._encoder_pool.close()
        assert segments() == []

    def test_a_worker_that_dies_with_its_chunk_leaks_nothing(
            self, policies, registry, monkeypatch):
        """``presumed_dead``: the chunk is redone in-process and
        counted, and the block the dead worker had created goes with
        the pool."""
        docs = pods(4 * CAP)
        want = reports_of(scanner_with(policies, 0, monkeypatch), docs)
        monkeypatch.setattr(scan_mod, 'encode_worker',
                            helpers.die_holding_a_block)
        scanner = scanner_with(policies, 1, monkeypatch)
        scanner.ENCODE_TIMEOUT_S = 3
        # the first chunk is out with the worker: see the block it
        # creates before it dies
        created, done = set(), threading.Event()

        def watch():
            while not done.is_set():
                created.update(segments())
                time.sleep(0.005)
        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        try:
            got = reports_of(scanner, docs)
        finally:
            done.set()
            watcher.join()
        assert created, 'the worker never created its block'
        assert got == want
        assert registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                      result='presumed_dead') == 1
        assert registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                      result='ok') == 0
        assert scanner._encoder_pool._broken
        assert segments() == []

    def test_no_block_to_be_had_is_pool_failed(self, policies, registry,
                                               monkeypatch):
        docs = pods(3 * CAP)
        want = reports_of(scanner_with(policies, 0, monkeypatch), docs)
        monkeypatch.setattr(scan_mod, 'encode_worker',
                            helpers.no_block_to_be_had)
        scanner = scanner_with(policies, 1, monkeypatch)
        assert reports_of(scanner, docs) == want
        assert registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                      result='pool_failed') == 1
        assert registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                      result='presumed_dead') == 0
        assert scanner._encoder_pool._broken
        assert segments() == []

    def test_a_retried_h2d_starts_from_the_lanes_it_has(
            self, policies, registry, monkeypatch):
        """The stage fails once after the lanes were home: the retry
        starts from them and the chunk is not counted twice."""
        docs = pods(4 * CAP)
        want = reports_of(scanner_with(policies, 0, monkeypatch), docs)
        shard, calls = eval_mod.shard_batch, []

        def fails_once(tensors, mesh):
            calls.append(len(tensors))
            if len(calls) == 2:
                raise RuntimeError('the transfer failed')
            return shard(tensors, mesh)
        monkeypatch.setattr(eval_mod, 'shard_batch', fails_once)
        scanner = scanner_with(policies, 2, monkeypatch)
        try:
            assert reports_of(scanner, docs) == want
            assert len(calls) == 5
            assert registry.counter_value(devtel.STAGE_RETRIES,
                                          stage='h2d') == 1
            assert registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                          result='ok') == 4
            assert registry.counter_value(devtel.ENCODE_WORKER_CHUNKS,
                                          result='pool_failed') == 0
            assert not scanner._encoder_pool._broken
            assert scanner._encoder_pool.blocks._unmap_later == []
        finally:
            scanner._encoder_pool.close()

    def test_a_generator_closed_early_gives_its_blocks_back(
            self, policies, monkeypatch):
        """The pipeline's cleanup hook releases the chunks that die in
        flight: a block whose lanes were home is free for the next
        scan, one still out with a worker is given up, and the next
        scan needs no more than two."""
        docs = pods(6 * CAP)
        want = reports_of(scanner_with(policies, 0, monkeypatch), docs)
        scanner = scanner_with(policies, 2, monkeypatch)
        try:
            rows = scanner.scan_report_results(docs, now=1234.0)
            next(rows)
            rows.close()
            blocks = scanner._encoder_pool.blocks
            assert len(blocks._all) <= 2
            assert sorted(map(id, blocks._free)) == \
                sorted(map(id, blocks._all))
            assert all(b.spare is None for b in blocks._all)
            assert reports_of(scanner, docs) == want
            assert len(blocks._all) == 2 and blocks._lost == {}
            assert segments() == sorted(
                b.shm.name.lstrip('/') for b in blocks._all)
        finally:
            scanner._encoder_pool.close()
        assert segments() == []

    def test_stop_encoder_processes_unlinks_every_pools_blocks(
            self, policies, monkeypatch):
        scanners = [scanner_with(policies, 1, monkeypatch)
                    for _ in range(2)]
        docs = pods(3 * CAP)
        for s in scanners:
            assert len(list(s.scan_report_results(docs))) == len(docs)
        assert len(segments()) >= 2
        scan_mod.stop_encoder_processes()
        assert segments() == []
        # a later scan starts the pool, and its blocks, again
        assert len(list(scanners[0].scan_report_results(docs))) \
            == len(docs)
        assert not scanners[0]._encoder_pool._broken and segments()
        scan_mod.stop_encoder_processes()
        assert segments() == []


# -- a chunk's life through the pool, on the parent's clock (ISSUE 36) --------

LIFE_CAP = 64      # rows a chunk: an encode long enough to hold to 5%
PHASES = ('encode_gather', 'encode_clear', 'encode_walk', 'encode_column',
          'encode_fill')


@pytest.fixture(scope='module')
def chunk_life(policies):
    """One seeded cluster of five chunks and a tail, scanned by one
    scanner through its pool of two workers (twice: the pool is built
    once), by another in-process, both with the stage histogram on and
    under a capture that asks for the CPU twins as a reconcile's does,
    and through the pool once more with every sink off."""
    patch = pytest.MonkeyPatch()
    docs = pods(5 * LIFE_CAP + 7, seed=36)
    runs = {}
    try:
        for case, procs in (('worker', 2), ('inline', 0)):
            patch.setenv('KTPU_ENCODE_PROCS', str(procs))
            scanner = BatchScanner(policies)
            scanner.CHUNK = LIFE_CAP
            reg = devtel.configure(MetricsRegistry())
            try:
                with devtel.install_capture(devtel.ScanCapture(cpu=True)):
                    reports = reports_of(scanner, docs)
                    if procs:
                        assert reports_of(scanner, docs) == reports
                if procs:
                    devtel.disable()
                    runs['off'] = reports_of(scanner, docs)
                    assert not scanner._encoder_pool._broken
            finally:
                devtel.disable()
                scanner._encoder_pool.close()
            runs[case] = (reports, {
                dict(key)['stage']: (count, total)
                for key, count, total in reg.histogram_series(
                    devtel.SCAN_STAGE_DURATION)})
    finally:
        patch.undo()
    assert segments() == []
    return runs


class TestChunkLife:
    CHUNKS = 6

    @pytest.mark.parametrize('case, scans', [('worker', 2), ('inline', 1)])
    def test_the_five_phases_are_the_encode(self, chunk_life, case, scans):
        """In a worker (they ride home with ``encode`` in its capture)
        and inline: one sample of each a chunk, and together ``encode``
        to within a twentieth."""
        stages = chunk_life[case][1]
        count, encode_s = stages['encode']
        assert count == scans * self.CHUNKS
        for phase in PHASES:
            assert stages[phase][0] == count and stages[phase][1] >= 0.0
        assert sum(stages[p][1] for p in PHASES) == pytest.approx(
            encode_s, rel=0.05)
        assert stages['encode_walk'][1] > 0.0 < stages['encode_column'][1]

    @pytest.mark.parametrize('case, scans', [('worker', 2), ('inline', 1)])
    def test_the_encodes_cpu_twin(self, chunk_life, case, scans,
                                  cpu_tick):
        stages = chunk_life[case][1]
        assert stages['encode_cpu'][0] == scans * self.CHUNKS
        assert 0.0 < stages['encode_cpu'][1] <= \
            stages['encode'][1] * 1.02 + \
            2 * cpu_tick * stages['encode'][0]

    @pytest.mark.parametrize('stage', ['encode_submit', 'encode_idle',
                                       'encode_return'])
    def test_the_way_there_and_back_is_never_negative(self, chunk_life,
                                                      stage):
        """Clocks of two processes subtracted: the same monotonic clock.
        One sample a chunk; ``encode_idle`` none for a worker's first
        task.  An inline encode has none of the three."""
        count, total = chunk_life['worker'][1][stage]
        chunks = 2 * self.CHUNKS
        if stage == 'encode_idle':
            assert chunks - 2 <= count < chunks
        else:
            assert count == chunks
        assert total >= 0.0
        assert stage not in chunk_life['inline'][1]

    def test_encode_return_is_a_part_of_encode_wait(self, chunk_life):
        stages = chunk_life['worker'][1]
        assert stages['encode_wait'][0] == 2 * self.CHUNKS
        assert stages['encode_return'][1] <= stages['encode_wait'][1]

    def test_the_pool_is_started_once_a_scanner(self, chunk_life):
        """Two scans of one scanner: one ``pool_start`` (no twin: no
        metric reads one); a scanner that encodes in-process has none."""
        stages = chunk_life['worker'][1]
        assert stages['pool_start'][0] == 1
        assert 'pool_start_cpu' not in stages
        assert stages['pool_start'][1] > 0.0
        assert 'pool_start' not in chunk_life['inline'][1]

    def test_the_reports_do_not_know_who_watched(self, chunk_life):
        """Worker or in-process, every sink on or all of them off."""
        assert chunk_life['worker'][0] == chunk_life['inline'][0] == \
            chunk_life['off']


def test_a_block_is_never_out_with_two_chunks_at_once():
    """The encode thread acquires, the d2h thread and the pipeline's
    cleanup release: more threads than cores, a short switch interval,
    and no block may be handed out while it is out."""
    blocks = scan_mod._Blocks()
    out, clashes = set(), []
    guard = threading.Lock()
    threads_n = 4 * (os.cpu_count() or 2)
    stop_at = time.monotonic() + 2.0

    def churn():
        while time.monotonic() < stop_at and not clashes:
            block = blocks.acquire()
            with guard:
                if id(block) in out:
                    clashes.append(id(block))
                out.add(id(block))
            time.sleep(0)
            with guard:
                out.discard(id(block))
            blocks.release(block)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=churn) for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert clashes == []
    assert 1 <= len(blocks._all) <= threads_n
    assert sorted(map(id, blocks._free)) == sorted(map(id, blocks._all))
    blocks.drop_all()
    assert blocks._all == [] and blocks._free == []


def test_result_bytes_are_not_sized_with_metrics_off():
    """The lanes are summed and the answer pickled again only to size
    them: not on a scanner without a registry."""
    class Unsizable:
        def values(self):
            raise AssertionError('summed with no registry to tell')

        def __reduce__(self):
            raise AssertionError('pickled with no registry to tell')
    devtel.disable()
    devtel.record_encode_result_bytes(Unsizable(), Unsizable())
    reg = devtel.configure(MetricsRegistry())
    try:
        lanes = {'a': np.zeros((4, 8), np.int64), 'b': np.zeros(3, bool)}
        devtel.record_encode_result_bytes(
            lanes, ('ktpu-enc-1-1', ((4, 4, 4, 4),
                                     [('pk_int64', '<i8', (4, 8), 0)]),
                    {}, None))
        assert reg.counter_value(devtel.ENCODE_RESULT_BYTES,
                                 via='block') == 4 * 8 * 8 + 3
        assert 0 < reg.counter_value(devtel.ENCODE_RESULT_BYTES,
                                     via='pipe') < 1024
    finally:
        devtel.disable()


A_WORKER_EXITS = '''
import glob, os, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, 'tests'))
sys.path.insert(0, os.path.join({repo!r}, 'benchmarks'))
import benchlib
import test_encode_blocks as t
from kyverno_tpu.compiler.compile import compile_policies
from kyverno_tpu.compiler.scan import _EncoderPool

if __name__ == '__main__':
    cps = compile_policies(benchlib.load_policies(['pack']))
    pool = _EncoderPool(cps, 1)
    assert pool.start()
    block = pool.blocks.acquire()
    docs = t.pods(16)
    home = pool.submit(docs, None, 16, block).get(timeout=120)
    name = home[0]
    workers = [w.pid for w in pool._pool._pool]
    lanes = pool.lanes(block, home)
    before = {{k: v.tobytes() for k, v in lanes.items()}}
    # the worker that created the block exits; the block is this
    # process's and stays
    pool._pool.terminate()
    pool._pool.join()
    for pid in workers:
        try:
            os.kill(pid, 0)
            raise SystemExit('the worker is still there')
        except ProcessLookupError:
            pass
    assert t.segments() == [name], t.segments()
    assert {{k: v.tobytes() for k, v in lanes.items()}} == before
    print('live block outlived its worker', flush=True)
    {ending}
'''


@pytest.mark.parametrize('ending', ['pool.close()', 'pass',
                                    'raise SystemExit(3)'])
def test_a_workers_exit_does_not_unlink_a_live_block(tmp_path, ending):
    """Python 3.12 registers a segment with the resource tracker in every
    process that attaches it; the workers share this process's tracker,
    so a worker's exit unlinks nothing, and the process's own end (the
    pool closed, or only the exit hook) leaves no segment and no
    complaint from the tracker."""
    script = tmp_path / 'exits.py'
    script.write_text(A_WORKER_EXITS.format(repo=REPO, ending=ending))
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    out, err = proc.communicate(timeout=300)
    assert 'live block outlived its worker' in out, err
    assert proc.returncode == (3 if 'SystemExit' in ending else 0), err
    assert 'resource_tracker' not in err and 'leaked' not in err, err
    assert 'Traceback' not in err and 'BufferError' not in err, err
    assert glob.glob('/dev/shm/ktpu-enc-%d-*' % proc.pid) == []
