"""Batch scanner: the TPU-backed background-scan path.

This is the TPU-native replacement for the reference's per-resource scan
loop (reference: pkg/controllers/report/background/controller.go +
pkg/controllers/report/utils/scanner.go:60 ScanResource):

1. compile the policy set once (``compile_policies``)
2. project each resource onto the slot table (``encode_batch``)
3. run the jitted evaluator — a verdict sieve over [resources × rules]
4. synthesize responses for PASS / precondition-SKIP verdicts from
   compile-time templates; re-materialize FAIL / anchor-SKIP / HOST
   results with the host engine so messages and statuses are always
   bit-identical to a pure host run

Match/exclude is evaluated once per (kind, apiVersion, namespace) group
for rules whose match blocks only reference those fields — the common
case for background-scan policies — instead of once per (resource, rule)
pair (reference match semantics: pkg/engine/utils.go:185).
"""

from __future__ import annotations

import multiprocessing
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..api.policy import Policy, Rule
from ..api.unstructured import Resource
from ..engine.api import (EngineResponse, PolicyContext, RuleResponse,
                          RuleStatus, RuleType)
from ..engine.engine import Engine, Validator, pod_security_response
from ..engine.match import matches_resource_description
from ..engine.validate_pattern import PatternError, match_pattern
from ..observability import coverage
from ..pss.evaluate import (evaluate_failed_checks, evaluate_pod_security,
                            parse_version)
from .. import faults
from . import admission as admission_lanes
from .compile import compile_policies
from .encode import encode_batch, encode_worker, encode_worker_init
from .shapes import canonical_capacity, canonical_caps
from .context_lanes import ContextLanes, _input_walker, _row_key
from .ir import (FDET_BEYOND_BUDGET, STATUS_CTX_LOAD, STATUS_CTX_SHAPE,
                 STATUS_CTX_UNRESOLVED, STATUS_CTX_WIDE, STATUS_FAIL,
                 STATUS_HOST, STATUS_PASS, STATUS_SKIP, STATUS_SKIP_PRECOND,
                 STATUS_VAR_ERR, CompiledPolicySet, RuleProgram)

_SIMPLE_MATCH_KEYS = {'kinds', 'namespaces', 'operations'}

#: the admission-shape warm resource: XLA compiles the evaluator once
#: per canonical batch capacity (compiler/shapes.py) and the element
#: axis clamps to a minimum of 4, so one ≤4-container warm pod covers
#: every ≤4-container admission request (the common case); larger pods
#: lazily compile their element width
WARM_POD = {
    'apiVersion': 'v1', 'kind': 'Pod',
    'metadata': {'name': 'warm', 'namespace': 'default'},
    'spec': {'containers': [
        {'name': f'c{i}', 'image': 'warm:1'} for i in range(2)]},
}

PRECONDITIONS_SKIP_MESSAGE = 'preconditions not met'

# sentinel: a device cell that must be re-run on the host engine
_HOST_MARKER = object()

#: entries a scanner's message caches hold before they start over
_MESSAGE_CACHE_MAX = 65536


def _masked_evaluator(mask: int, tally):
    """``evaluate_pod_security`` for one cell whose failed checks the
    device named in ``mask``: those checks alone, where each of them
    fails in the library too; else the mask was wrong, the mismatch is
    counted and every check runs.  For a rule without ``exclude`` (one
    with it never compiles)."""
    def evaluate(pod_security: dict, pod: dict):
        level, _version = parse_version(pod_security)
        checks = evaluate_failed_checks(level, pod, mask)
        if checks is None:
            if tally is not None:
                tally.pss_mask_mismatch += 1
            return evaluate_pod_security(pod_security, pod)
        if tally is not None:
            tally.pss_masked_cells += 1
            tally.pss_checks_run += mask.bit_count()
        return False, checks
    return evaluate

#: the ledger's reason for each status the context fill writes over the
#: device's (compiler/context_lanes.py): the cell is the host's
_CTX_STATUS_REASON = {
    STATUS_CTX_LOAD: coverage.REASON_CONTEXT_LOAD,
    STATUS_CTX_UNRESOLVED: coverage.REASON_CONTEXT_VALUE_UNRESOLVED,
    STATUS_CTX_WIDE: coverage.REASON_CONTEXT_VALUE_WIDE,
    STATUS_CTX_SHAPE: coverage.REASON_CONTEXT_VALUE_SHAPE,
}

#: process-unique monotonic scanner ids for batch coalescing keys —
#: ``id()`` can be reused after GC/eviction, which would let a fresh
#: scanner's tickets coalesce with a dead scanner's batch
_SCANNER_SERIALS = __import__('itertools').count(1)


def next_scanner_serial() -> int:
    """Next monotonic scanner serial (itertools.count: atomic in
    CPython).  Shared by BatchScanner and MutateScanner so the two
    program kinds can never collide on a serving key."""
    return next(_SCANNER_SERIALS)

# ---------------------------------------------------------------------------
# Encoder process pool: encode_batch is pure numpy/Python (no jax), so
# chunks encode in worker processes off the main interpreter's GIL — the
# assembly loop and the encoder no longer serialize against each other.
# Workers come from a fork SERVER, never from a fork of this process:
# by the time a scan starts the pool this process holds an initialised
# device runtime and a dozen threads, and a child forked from that can
# deadlock on a lock some other thread held (jax warns on every such
# fork).  The server is a clean single-threaded interpreter that has
# only imported compiler/encode.py — where the worker's code lives, so
# neither the server nor a worker ever imports jax.

#: pools with live workers (weak: a dropped scanner's finalizer has
#: already terminated its pool)
_LIVE_POOLS = __import__('weakref').WeakSet()
_stop_at_exit = False  # stop_encoder_processes is registered with atexit


def stop_encoder_processes() -> None:
    """Terminate every encoder pool and unlink its blocks, then stop
    the fork server and multiprocessing's resource tracker, and wait for
    each.  Left to themselves the two helpers only notice that this
    process is gone after it has exited, so whoever waited for it finds
    them still running; registered with ``atexit`` by the first pool, so
    a process that has scanned leaves none behind.  A later scan starts
    them again."""
    from multiprocessing import forkserver, resource_tracker
    for pool in list(_LIVE_POOLS):
        pool.close()
    # a pool shut with a task unanswered is in a cycle with that task:
    # collect it now, while the tracker still knows its semaphores
    __import__('gc').collect()
    forkserver._forkserver._stop()
    resource_tracker._resource_tracker._stop()


# A worker's lanes come home in a shared-memory block (compiler/encode.py
# has the worker's half and the reason).  This process owns every block:
# it chooses each name before a worker can create anything under it, so
# whatever becomes of the worker, the names to unlink are known here.

_BLOCK_SERIALS = __import__('itertools').count(1)


def _unlink_segment(name: str) -> None:
    """Unlink segment ``name`` if a worker got as far as creating it.
    The attach is what lets ``unlink()`` take it off the resource
    tracker's list as well, where its creator put it."""
    from multiprocessing import shared_memory
    try:
        made = shared_memory.SharedMemory(name=name)
    except (FileNotFoundError, ValueError):  # never, or empty
        return
    made.unlink()
    made.close()


class _Block:
    """One block of a chunk's encoded lanes.  ``shm`` is this process's
    mapping of the segment it holds (None before the first chunk);
    ``spare`` is the name a worker may create a larger one under and
    ``task`` the pool's handle on that worker's answer, both set for as
    long as the block is out."""

    __slots__ = ('shm', 'spare', 'task')

    def __init__(self):
        self.shm = None
        self.spare: Optional[str] = None
        self.task = None

    def offer(self) -> tuple:
        """What a worker is told: ``(name, size, spare)``."""
        self.spare = 'ktpu-enc-%d-%d' % (__import__('os').getpid(),
                                         next(_BLOCK_SERIALS))
        if self.shm is None:
            return None, 0, self.spare
        return self.shm.name, self.shm.size, self.spare


class _Blocks:
    """The blocks of one encoder pool: as many as chunks were ever in
    flight at once, which the pipeline's depth bounds."""

    def __init__(self):
        self._lock = __import__('threading').Lock()
        self._free: List[_Block] = []
        self._all: List[_Block] = []
        #: spare name -> task of blocks given up while their worker was
        #: still at work: unlinked once it has answered, by a later sweep
        self._lost: Dict[str, Any] = {}
        #: mappings unlinked while lanes over them were still referenced
        #: (a chunk that died in flight): closed once those are gone, by
        #: a later sweep
        self._unmap_later: List[Any] = []

    def acquire(self) -> _Block:
        self._sweep()
        with self._lock:
            if self._free:
                return self._free.pop()
            block = _Block()
            self._all.append(block)
            return block

    def buffers(self, block: _Block, name: str,
                layout) -> Dict[str, np.ndarray]:
        """The packed buffers a worker left in segment ``name``: the
        one offered, or the spare it had to create, which replaces
        it."""
        from multiprocessing import shared_memory
        from .encode import block_buffers
        if name == block.spare:
            old, block.shm = block.shm, shared_memory.SharedMemory(name=name)
            if old is not None:
                old.unlink()
                self._unmap(old)
        elif block.shm is None or name != block.shm.name:
            raise ValueError(f'a worker answered from block {name!r}, '
                             f'which it was not offered')
        block.spare = block.task = None
        return block_buffers(block.shm.buf, layout)

    def release(self, block: _Block) -> None:
        """Free for the next chunk, if its lanes had come home.  A block
        still out with a worker (its chunk died first) is given up: the
        worker may yet write into it, so no other chunk may have it.  So
        is one whose pool was given up meanwhile."""
        with self._lock:
            mine = any(block is b for b in self._all)
            if mine and block.spare is None:
                self._free.append(block)
                return
            if mine:
                self._all.remove(block)
        self._drop(block)

    def drop_all(self) -> None:
        """No worker is left: unlink whatever one holds or created."""
        with self._lock:
            blocks, self._all, self._free = self._all, [], []
        for block in blocks:
            self._drop(block)
        self._sweep(final=True)

    def _drop(self, block: _Block) -> None:
        """Unlink what ``block`` holds and may hold; idempotent."""
        shm, block.shm = block.shm, None
        spare, block.spare = block.spare, None
        task, block.task = block.task, None
        if shm is not None:
            try:
                shm.unlink()
            except FileNotFoundError:
                pass
        self._unmap(shm)
        if spare is not None:
            with self._lock:
                self._lost[spare] = task
            self._sweep()

    def _sweep(self, final: bool = False) -> None:
        """Unlink the spare segments of the blocks given up, each once
        its worker has answered and so creates nothing any more, or, at
        the ``final`` sweep, is gone."""
        with self._lock:
            for name, task in list(self._lost.items()):
                if final or task is None or task.ready():
                    _unlink_segment(name)
                    del self._lost[name]

    def _unmap(self, shm=None) -> None:
        """Close ``shm``'s mapping, and every earlier one that had to
        wait: a mapping cannot close under a numpy view of it."""
        with self._lock:
            todo, self._unmap_later = self._unmap_later, []
        if shm is not None:
            todo.append(shm)
        for m in todo:
            try:
                m.close()
            except BufferError:
                with self._lock:
                    self._unmap_later.append(m)


def _shut_pool(pool, blocks: _Blocks) -> None:
    """Workers first, so that none is left to create a block after the
    names were unlinked."""
    pool.terminate()
    blocks.drop_all()


class _EncoderPool:
    """Lazy fork-server pool; falls back to in-process encoding on
    failure, counted on ``kyverno_tpu_encode_worker_chunks_total``."""

    def __init__(self, cps, procs: int, joining=None):
        self.cps = cps
        self.procs = procs
        #: the lanes the scanner adds to a batch after the encode: a
        #: worker keeps their columns free (encode.py ``LaneArena``)
        self.joining = dict(joining or {})
        self._pool = None
        self._broken = False
        self.blocks = _Blocks()

    def start(self) -> bool:
        if self._broken or self.procs <= 0:
            return False
        if self._pool is None:
            from ..observability import device as devtel
            try:
                import weakref
                # ``pool_start``: the fork server (it preloads the
                # encoder's module) and the workers forked; a worker's
                # own ``encode_worker_init`` runs after this returns and
                # shows in its first chunk's ``encode_submit``
                with devtel.stage('pool_start', {'procs': self.procs}):
                    ctx = multiprocessing.get_context('forkserver')
                    ctx.set_forkserver_preload([encode_worker.__module__])
                    pool = ctx.Pool(self.procs,
                                    initializer=encode_worker_init,
                                    initargs=(self.cps, self.joining))
                # weakref.finalize runs at collection OR interpreter exit
                # (atexit=True default), so workers are reaped and blocks
                # unlinked when the scanner is dropped and
                # mp.Pool.__del__ never races the shutdown pickler
                self._finalizer = weakref.finalize(self, _shut_pool, pool,
                                                   self.blocks)
                self._pool = pool
                _LIVE_POOLS.add(self)
                global _stop_at_exit
                if not _stop_at_exit:
                    __import__('atexit').register(stop_encoder_processes)
                    _stop_at_exit = True
            except Exception:  # noqa: BLE001 - pool is an optimization
                self.mark_broken('pool_failed')
                return False
        return True

    def mark_broken(self, result: str) -> None:
        """Give the pool up for this scanner and count why."""
        from ..observability import device as devtel
        devtel.record_encode_worker(result)
        self.close()
        self._broken = True

    def submit(self, docs, contexts, padded_n, block: _Block):
        block.task = self._pool.apply_async(
            encode_worker, ((docs, contexts, padded_n, block.offer()),))
        return block.task

    def lanes(self, block: _Block, home):
        """The lanes of a worker's answer ``home``, as views of the
        packed buffers it left in ``block``."""
        from .encode import block_lanes
        name, (key, layout) = home[:2]
        return block_lanes(self.cps, self.joining, key,
                           self.blocks.buffers(block, name, layout))

    def close(self) -> None:
        if self._pool is not None:
            self._finalizer()  # idempotent: shuts the pool once
            self._pool = None
            _LIVE_POOLS.discard(self)


_LABEL_MATCH_KEYS = _SIMPLE_MATCH_KEYS | {'selector'}


def _rule_match_is_simple(rule: dict, keys=_SIMPLE_MATCH_KEYS) -> bool:
    """True when match/exclude depend only on kind/apiVersion/namespace."""
    def block_simple(block: dict) -> bool:
        for f in [block] + (block.get('any') or []) + (block.get('all') or []):
            res = f.get('resources') or {}
            if any(k not in keys for k in res):
                return False
            if f.get('roles') or f.get('clusterRoles') or f.get('subjects'):
                return False
        return True
    return block_simple(rule.get('match') or {}) and \
        block_simple(rule.get('exclude') or {})


def _rule_match_is_label_simple(rule: dict) -> bool:
    """True when match/exclude additionally reference only the resource's
    label selector — the decision is a function of (group key, labels),
    so selector-heavy policies cache per distinct label set instead of
    per resource (the adversarial regime for the group cache)."""
    return _rule_match_is_simple(rule, _LABEL_MATCH_KEYS)


def policy_namespace_gate(policy: Policy, res: Resource) -> bool:
    """Namespaced policies only apply inside their own namespace
    (engine.py:230-236, reference: pkg/engine/validation.go:117).
    Shared by the scan and bulk-apply match sieves."""
    if not policy.is_namespaced:
        return True
    return bool(res.namespace) and res.namespace == policy.namespace


def _group_key(doc: dict) -> Tuple[str, str, str]:
    meta = doc.get('metadata') or {}
    return (str(doc.get('kind', '')), str(doc.get('apiVersion', '')),
            str(meta.get('namespace', '') or ''))


class BatchScanner:
    """Compiles a policy set once and evaluates resource batches on device.

    ``scan`` returns the full per-resource engine responses (bit-identical
    to the host engine); ``scan_statuses`` returns just the raw device
    verdict matrices for throughput-critical callers.
    """

    def __init__(self, policies: List[Policy], engine: Optional[Engine] = None,
                 mesh=None):
        self.policies = policies
        self.engine = engine or Engine()
        self.cps: CompiledPolicySet = compile_policies(policies)
        self.mesh = mesh
        # policies needing the host engine for at least one rule, plus
        # applyRules=One policies (early-exit coupling between rules)
        self._host_policy_idx = sorted(
            {i for i, _, _ in self.cps.host_rules} |
            {i for i, p in enumerate(policies)
             if (p.apply_rules or 'All') == 'One'})
        host_set = set(self._host_policy_idx)
        # device-synthesizable programs (their whole policy compiled)
        self.device_programs: List[Tuple[int, RuleProgram]] = [
            (j, prog) for j, prog in enumerate(self.cps.programs)
            if prog.policy_index not in host_set]
        self._dev_mask = np.zeros(len(self.cps.programs), bool)
        for _j, _ in self.device_programs:
            self._dev_mask[_j] = True
        # final per-rule placement (compile placements + the policy-
        # coupling override above); feeds the coverage ledger and the
        # host-run fallback attribution below
        self._placements = coverage.compile_placements(policies, self.cps)
        self._host_rule_reason = {
            (pl.policy, pl.rule): (pl.reason or
                                   coverage.REASON_POLICY_COUPLING,
                                   pl.path)
            for pl in self._placements
            if pl.placement == coverage.PLACEMENT_HOST}
        if coverage.enabled():
            coverage.record_placements(self._placements)
        # the AOT-cache fingerprint of this scanner's policy set —
        # decision-provenance records carry it so a flight-recorder
        # line names exactly which compiled set served the decision
        from ..aotcache.keys import policy_set_fingerprint
        self.fingerprint = policy_set_fingerprint(policies)
        from ..ops.eval import build_evaluator
        self._evaluator = build_evaluator(self.cps)
        # per-row admission lanes (compiler/admission.py): the serving
        # batch key is the scanner alone, so mixed-user/mixed-verb
        # bursts share one dispatch; the evaluator owns the compiled
        # table (single source — the lane signature and the in-graph
        # decision can never disagree)
        self.serial = next_scanner_serial()
        self.supports_row_admissions = True
        self._adm = getattr(self._evaluator, 'adm_table', None)
        self._adm_cols = self._evaluator.adm_cols \
            if self._adm is not None else None
        # partitioned compile (KTPU_PARTITIONS > 0, non-mesh): one
        # evaluator per policy-group partition, AOT-keyed by the
        # partition fingerprint (kyverno_tpu/partition/), per-partition
        # outputs merged back into the whole-set verdict contract by the
        # composer.  Any structural mismatch falls back to the
        # monolithic evaluator above — never a wrong verdict.  The
        # whole-set evaluator stays as assembly metadata (any_meta,
        # n_cols, dev masks); jax.jit is lazy, so it never compiles
        # unless the fallback actually dispatches it.
        self._pset = None
        self._composer = None
        from ..partition.plan import PartitionError, env_partitions
        _n_parts = env_partitions()
        # (a set whose conditions read context values stays monolithic:
        # the value lanes join a batch here, in whole-set space)
        if _n_parts > 0 and mesh is None and self.cps.programs and \
                not self.cps.ctx_values:
            try:
                from ..partition import census as _census
                from ..partition.compose import Composer
                from ..partition.runtime import build_runtime
                _pset = build_runtime(policies, self.cps, _n_parts,
                                      set_fingerprint=self.fingerprint)
                self._composer = Composer(self._evaluator,
                                          _pset.runtimes)
                self._pset = _pset
            except PartitionError:
                from ..observability.metrics import global_registry
                from ..partition.runtime import PARTITION_FALLBACKS
                _reg = global_registry()
                if _reg is not None:
                    _reg.inc(PARTITION_FALLBACKS)
            else:
                # partitioned dispatches ship no whole-set in-graph
                # admission lanes: with self._adm None no
                # AdmissionRowPlan is ever built and the host matcher
                # decides admission rows exactly — plan=None semantics,
                # bit-identical to the monolithic oracle
                self._adm = None
                self._adm_cols = None
                _census.record_plan(self.fingerprint, _pset.plan,
                                    serial=self.serial)
        # what host materialization needs of each program, worked out
        # once a scanner: its Rule, and for a podSecurity rule that
        # reads nothing but the resource (no context, no
        # preconditions) the block the check library takes — such a
        # cell is phrased by engine.pod_security_response alone, with
        # no PolicyContext and no Validator built around it
        self._host_rule: Dict[RuleProgram, Tuple[Rule, Optional[dict]]] = {}
        for _prog in self.cps.programs:
            _rule = Rule(_prog.rule_raw or {})
            _direct = None
            if _prog.pss is not None and not _rule.context and \
                    _rule.preconditions is None:
                _direct = _rule.validation.get('podSecurity')
            self._host_rule[_prog] = (_rule, _direct)
        from collections import OrderedDict
        self._simple_match = [
            _rule_match_is_simple(p.rule_raw or {}) for p in self.cps.programs]
        self._label_match = [
            not s and _rule_match_is_label_simple(p.rule_raw or {})
            for s, p in zip(self._simple_match, self.cps.programs)]
        # LRU-bounded: one row per (kind, apiVersion, namespace, operation)
        # group — long-lived admission scanners in many-namespace clusters
        # must not grow without bound.  Locked: webhook threads share one
        # scanner and race get/evict/move_to_end otherwise.
        self._match_cache: 'OrderedDict[Tuple, np.ndarray]' = OrderedDict()
        self._match_cache_max = 4096
        self._match_cache_lock = __import__('threading').Lock()
        self._rules = [Rule(p.rule_raw or {}) for p in self.cps.programs]
        # which programs can match a resource of one namespace at all:
        # those of the cluster-wide policies and of the namespace's own
        # (policy_namespace_gate).  Built once, so that the sieve walks
        # the programs that can apply to a row and not the whole set; a
        # set without namespaced policies has no index and walks it all
        self._ns_programs: Dict[str, List[int]] = {}
        for j, prog in enumerate(self.cps.programs):
            policy = policies[prog.policy_index]
            if policy.is_namespaced:
                self._ns_programs.setdefault(policy.namespace,
                                             []).append(j)
        self._candidate_masks: Dict[str, np.ndarray] = {}
        if self._ns_programs:
            cluster = np.ones(len(self.cps.programs), bool)
            for js in self._ns_programs.values():
                cluster[js] = False
            self._candidate_masks[''] = cluster
        self._fail_msg_cache: Dict[Tuple, Optional[str]] = {}
        # the programs whose message has variables and a plan
        # (ir.py message_inputs): the walkers of its inputs and, for a
        # pattern or an anyPattern, the patterns the engine walks; and
        # the FAIL responses the engine worded for them in this scan
        # pass, by (program, fail site, the inputs' values): see
        # _fail_memoized
        self._msg_plans = {
            j: (tuple(_input_walker(e) for e in prog.message_inputs),
                self._walked_patterns(self._host_rule[prog][0].validation))
            for j, prog in enumerate(self.cps.programs)
            if prog.message_inputs is not None}
        self._msg_memo: Dict[Tuple, RuleResponse] = {}
        self._msg_memo_hits = self._msg_memo_misses = 0
        # encode workers only pay off with spare cores: on a host of
        # one or two the worker's encode and this process's report
        # assembly take turns on the same core
        _os = __import__('os')
        _default_procs = '2' if (_os.cpu_count() or 1) > 2 else '0'
        # the lanes stage_h2d adds to every batch of this set after the
        # encode (the mesh step adds none): known before any batch
        # exists, so the encoder keeps their columns free in the packed
        # buffers it fills and packing is a hand-over
        joining: Dict[str, Tuple[Any, Tuple[int, ...]]] = {}
        # the context side of the set: which programs share a context,
        # and the value lanes their conditions read; nothing of it
        # exists for a set without a context
        self._ctx = ContextLanes(self.cps)
        self._host_reads_context = any(
            (rule or {}).get('context')
            for _i, rule, _p in self.cps.host_rules)
        if mesh is None and self.cps.programs:
            joining['__match__'] = (np.uint8, (self._evaluator.n_uniq,))
            if self._adm is not None:
                joining.update(admission_lanes.lane_signature(self._adm))
            joining.update(self._ctx.signature)
        self._encoder_pool = _EncoderPool(
            self.cps,
            int(_os.environ.get('KTPU_ENCODE_PROCS', _default_procs)),
            joining)
        # static per-policy response header fields (avoids re-deriving
        # them from the raw policy dict per (resource, policy) pair)
        self._policy_header = [
            (p, p.name, p.namespace, p.validation_failure_action,
             p.validation_failure_action_overrides) for p in policies]
        # reusable encode buffers + cross-chunk value palettes for the
        # streaming pipeline (compiler/encode.py LaneArena): chunk lane
        # tensors recycle instead of reallocating ~100MB per chunk
        from .encode import LaneArena
        self._arena = LaneArena(joining=joining)

    def warmup(self, resources: Optional[List[dict]] = None) -> float:
        """Bring the admission-shape executable to serving readiness.

        Runs one scan over ``resources`` (default: the shared
        ``WARM_POD``), which walks the whole pipeline — encode, pack,
        h2d, executable lookup, device eval, d2h, assembly.  The
        executable lookup consults the persistent AOT store first
        (``aot_load`` instead of ``miss`` when a prior process already
        compiled this policy set), so a warm cache makes this seconds
        instead of a fresh multi-second XLA compile.  Returns the
        elapsed wall-clock seconds."""
        import copy
        t0 = time.monotonic()
        self.scan([copy.deepcopy(r) for r in (resources or [WARM_POD])])
        return time.monotonic() - t0

    def warmup_shapes(self, caps: Optional[List[int]] = None
                      ) -> Dict[int, float]:
        """Bring EVERY canonical batch capacity to serving readiness.

        One warm dispatch per capacity in the canonical shape table
        (``compiler/shapes.py``), run on a small thread pool: each
        dispatch drives the evaluator with exactly the tensor signature
        a real scan at that capacity produces (lanes + ``__rowvalid__``
        + the unique-space ``__match__`` plane), so the executable
        lookup — persistent AOT store first, fresh compile otherwise —
        is the one live traffic will hit.  Deserializes don't hold the
        evaluator's compile lock, so a warm disk cache loads the whole
        table in ~max(entry) instead of sum(entries).  Returns
        {capacity: seconds}."""
        import copy
        from concurrent.futures import ThreadPoolExecutor
        from ..ops.eval import shard_batch
        if not self.cps.programs:
            return {}
        table = sorted(set(caps if caps is not None else canonical_caps(
            chunk=self.CHUNK, small=self.SMALL_BATCH)))

        def warm_partitions(cap: int) -> float:
            # partitioned mode warms each partition's evaluator with
            # the exact tensor signature the partitioned scan path
            # produces (per-partition lanes + __rowvalid__ + the
            # partition-local unique-space __match__ plane + the
            # partition's admission lanes when it has any)
            t0 = time.monotonic()
            for rt in self._pset.runtimes:
                batch = encode_batch([copy.deepcopy(WARM_POD)],
                                     rt.sub_cps, padded_n=cap)
                tensors = batch.tensors()
                tensors['__match__'] = np.zeros(
                    (cap, rt.evaluator.n_uniq), np.uint8)
                if rt.adm is not None:
                    tensors.update(admission_lanes.zero_lanes(
                        rt.adm, cap))
                t, layout = shard_batch(tensors, None)
                out = rt.evaluator(t, layout)
                for arr in out:
                    np.asarray(arr)
                self._free_inputs(t, out)
            return time.monotonic() - t0

        def warm_one(cap: int) -> float:
            if self._composer is not None:
                return warm_partitions(cap)
            t0 = time.monotonic()
            batch = encode_batch([copy.deepcopy(WARM_POD)], self.cps,
                                 padded_n=cap)
            tensors = batch.tensors()
            if self.mesh is None:
                # mirror dispatch_work: non-mesh dispatches always ship
                # the unique-space match plane (values are irrelevant
                # for warming; the SIGNATURE selects the executable)
                tensors['__match__'] = np.zeros(
                    (cap, self._evaluator.n_uniq), np.uint8)
                if self._adm is not None:
                    # admission lanes are part of the signature too
                    tensors.update(admission_lanes.zero_lanes(
                        self._adm, cap))
            tensors.update(self._ctx.zero_lanes(cap))
            t, layout = shard_batch(tensors, self.mesh)
            out = self._evaluator(t, layout)
            for arr in out:
                np.asarray(arr)  # materialize before freeing inputs
            self._free_inputs(t, out)
            return time.monotonic() - t0

        if len(table) <= 1:
            return {cap: warm_one(cap) for cap in table}
        with ThreadPoolExecutor(
                max_workers=min(4, len(table)),
                thread_name_prefix='ktpu-shape-warm') as pool:
            futs = [(cap, pool.submit(warm_one, cap)) for cap in table]
            return {cap: f.result() for cap, f in futs}

    # -- match --------------------------------------------------------------

    def _policy_gate(self, policy: Policy, res: Resource) -> bool:
        return policy_namespace_gate(policy, res)

    def _candidates(self, namespace: str) -> Optional[np.ndarray]:
        """bool[P]: the programs that can match a resource of
        ``namespace`` (the cluster-wide policies' and that namespace's
        own); None where the set has no namespaced policy and every
        program can."""
        masks = self._candidate_masks
        if not masks:
            return None
        if namespace not in self._ns_programs:
            return masks['']
        mask = masks.get(namespace)
        if mask is None:
            mask = masks[''].copy()
            mask[self._ns_programs[namespace]] = True
            masks[namespace] = mask
        return mask

    def _of_namespace(self, js: np.ndarray, namespace: str) -> List[int]:
        """Those of the program indexes ``js`` that can match a
        resource of ``namespace``."""
        can = self._candidates(namespace)
        return (js if can is None else js[can[js]]).tolist()

    def _match_one(self, j: int, res: Resource,
                   admission: Optional[tuple] = None) -> bool:
        prog = self.cps.programs[j]
        policy = self.policies[prog.policy_index]
        if not self._policy_gate(policy, res):
            return False
        info, roles, ns_labels = admission or (None, [], {})
        return matches_resource_description(
            res, self._rules[j], info, roles, ns_labels, '') is None

    def _mcache_get(self, key):
        with self._match_cache_lock:
            hit = self._match_cache.get(key)
            if hit is not None:
                self._match_cache.move_to_end(key)
            return hit

    def _mcache_put(self, key, value):
        with self._match_cache_lock:
            while len(self._match_cache) >= self._match_cache_max:
                self._match_cache.popitem(last=False)
            self._match_cache[key] = value

    def _adm_res_atoms(self, resources: List[dict],
                       wrapped: List[Resource]) -> np.ndarray:
        """[R, F] uint8 resource-shape atoms for the admission-eligible
        filters (compiler/admission.py), group-cached: eligible filters
        only reference kinds/namespaces/operations plus the policy
        namespace gate, all functions of the resource group."""
        table = self._adm
        n = len(resources)
        out = np.zeros((n, len(table.atoms)), np.uint8)
        groups: Dict[Tuple, List[int]] = {}
        for i, doc in enumerate(resources):
            groups.setdefault(_group_key(doc), []).append(i)
        for key, idxs in groups.items():
            ck = ('admres',) + key
            cached = self._mcache_get(ck)
            if cached is None:
                rep = wrapped[idxs[0]]
                cached = np.array([
                    1 if admission_lanes.atom_ok(
                        a, self.policies[a.policy_index], rep) else 0
                    for a in table.atoms], np.uint8)
                self._mcache_put(ck, cached)
            out[idxs, :] = cached
        return out

    def match_matrix(self, resources: List[dict], wrapped: List[Resource],
                     admission: Optional[tuple] = None,
                     adm_rows: Optional[List[Optional[tuple]]] = None,
                     plan: Optional[Any] = None) -> np.ndarray:
        """``_match_rows``, timed as the ``match`` stage: once a chunk
        on the scan path (from ``match_fn``, on the pipeline's encode
        thread)."""
        from ..observability import device as devtel
        with devtel.stage('match', {'rows': len(resources)}):
            return self._match_rows(resources, wrapped, admission,
                                    adm_rows, plan)

    def _match_rows(self, resources: List[dict], wrapped: List[Resource],
                    admission: Optional[tuple] = None,
                    adm_rows: Optional[List[Optional[tuple]]] = None,
                    plan: Optional[Any] = None) -> np.ndarray:
        """[R, P] bool match mask, group-cached for simple-match rules.
        ``admission`` carries one scan-wide (admission_info,
        exclude_group_roles, namespace_labels, operation) tuple;
        ``adm_rows`` carries one PER ROW (heterogeneous webhook
        batches).  Simple-match rules only reference
        kinds/namespaces/operations, so the group cache stays valid
        across mixed users with each row's operation folded into its
        own key.  ``plan`` (AdmissionRowPlan) marks rows whose
        admission-eligible columns the jitted evaluator will decide
        in-graph: those cells hold the conservative upper bound here
        and are replaced with the exact device decision before
        assembly; non-valid rows (unencodable admission values, UPDATE
        rows) fall back to the host matcher per row."""
        n = len(resources)
        p = len(self.cps.programs)
        match = np.zeros((n, p), bool)
        if p == 0:
            return match
        simple = np.asarray(self._simple_match)
        simple_js = np.flatnonzero(simple)
        if adm_rows is None and admission is not None:
            adm_rows = [admission] * n
        if adm_rows is not None:
            ops = [a[3] if isinstance(a, tuple) and len(a) > 3 else ''
                   for a in adm_rows]
            adm3s = [tuple(a[:3]) if isinstance(a, tuple) else None
                     for a in adm_rows]
        else:
            ops = [''] * n
            adm3s: List[Optional[tuple]] = [None] * n
        # group resources by (kind, apiVersion, namespace, operation) —
        # per-row operations, so mixed-verb batches group correctly
        groups: Dict[Tuple, List[int]] = {}
        for i, doc in enumerate(resources):
            groups.setdefault(_group_key(doc) + (ops[i],), []).append(i)
        for key, idxs in groups.items():
            cached = self._mcache_get(key)
            if cached is None:
                rep = wrapped[idxs[0]]
                rep_adm = adm3s[idxs[0]]
                cached = np.zeros(p, bool)
                for j in self._of_namespace(simple_js, rep.namespace):
                    cached[j] = self._match_one(j, rep, rep_adm)
                self._mcache_put(key, cached)
            match[idxs, :] = cached
        # label-selector rules: the decision depends only on (group,
        # labels) — cache per distinct label set (cardinality of label
        # combinations, not of resources)
        label_js = np.nonzero(np.asarray(self._label_match))[0]
        if label_js.size:
            for i, doc in enumerate(resources):
                labels = (doc.get('metadata') or {}).get('labels') or {}
                lkey = (_group_key(doc), ops[i],
                        tuple(sorted(labels.items())))
                cached = self._mcache_get(lkey)
                if cached is None:
                    row = np.zeros(p, bool)
                    for j in self._of_namespace(label_js,
                                                wrapped[i].namespace):
                        row[j] = self._match_one(j, wrapped[i], adm3s[i])
                    cached = row[label_js]
                    self._mcache_put(lkey, cached)
                match[i, label_js] = cached
        # remaining non-simple rules (names, annotations, wildcard
        # namespaces, roles): evaluate per resource with that row's own
        # admission tuple — except admission-eligible columns of device-
        # valid rows, which the evaluator decides in-graph
        rest = ~simple & ~np.asarray(self._label_match)
        dev_cols: Dict[int, int] = {}
        if plan is not None and self._adm_cols is not None:
            dev_cols = {int(j): c for c, j in enumerate(self._adm_cols)}
        rest_js = np.flatnonzero(rest)
        for i in range(n if rest_js.size else 0):
            for j in self._of_namespace(rest_js, wrapped[i].namespace):
                c = dev_cols.get(j)
                if c is not None and plan.valid[i]:
                    match[i, j] = plan.upper[i, c]
                else:
                    match[i, j] = self._match_one(j, wrapped[i], adm3s[i])
        return match

    def _fold_old_matches(self, match: np.ndarray,
                          wrapped: List[Resource],
                          adm_rows: Optional[List[Optional[tuple]]],
                          old_resources) -> np.ndarray:
        """UPDATE-verb match semantics folded into the sieve: the engine
        retries a failed new-object match against the old object
        (engine.py:303 ``_matches``), and a namespaced policy applies
        only when BOTH objects sit in its namespace (engine.py:239).
        The old objects run through ``_match_rows`` themselves, so the
        group cache amortizes the retry across a batch exactly like the
        new-object sieve (the per-(row, program) host walk this
        replaced dominated mixed-verb batches at 1k policies)."""
        rows = [i for i, old in enumerate(old_resources) if old]
        if not rows:
            return match
        old_docs = [old_resources[i] for i in rows]
        old_wrapped = [Resource(d) for d in old_docs]
        sub_adm = [adm_rows[i] for i in rows] if adm_rows is not None \
            else None
        om = self._match_rows(old_docs, old_wrapped, adm_rows=sub_adm)
        match = match.copy()
        ridx = np.asarray(rows)
        match[ridx] |= om
        if self._ns_programs:
            # the both-object gate, vacuous for cluster-wide policies:
            # a row keeps the programs both of its objects' namespaces
            # admit, which are those of one namespace or of none
            for k, i in enumerate(rows):
                match[i] &= self._candidates(wrapped[i].namespace) & \
                    self._candidates(old_wrapped[k].namespace)
        return match

    # -- device evaluation --------------------------------------------------

    #: fixed device-chunk size: XLA compiles the evaluator once per
    #: distinct batch shape, so large scans stream fixed-size chunks
    CHUNK = int(__import__('os').environ.get('KTPU_SCAN_CHUNK', '8192'))
    #: the admission batch capacity: batches at or below this size pad
    #: to it (compiler/shapes.py) and run on the default device like
    #: every other batch
    SMALL_BATCH = int(__import__('os').environ.get(
        'KTPU_SMALL_BATCH', '64'))
    #: upper bound on one forked-encoder chunk (normal: ~2s); beyond this
    #: the worker is presumed dead and the chunk re-encodes in-process
    ENCODE_TIMEOUT_S = float(__import__('os').environ.get(
        'KTPU_ENCODE_TIMEOUT', '120'))

    @staticmethod
    def _free_inputs(t, out) -> None:
        """Free each chunk's device input (and consumed output) buffers
        eagerly instead of waiting for the garbage collector, so a long
        stream holds ~one pipeline depth of chunks on the device —
        outputs are already materialized as numpy copies by the
        callers."""
        try:
            for arr in t.values():
                if hasattr(arr, 'delete'):
                    arr.delete()
            for arr in out:
                if hasattr(arr, 'delete'):
                    arr.delete()
        except Exception:  # noqa: BLE001 - freeing is best-effort
            pass

    def _device_status_chunks(self, resources: List[dict],
                              contexts: Optional[List[dict]] = None,
                              match: Optional[np.ndarray] = None,
                              adm_plan: Optional[Any] = None,
                              match_fn=None):
        """Yield ``(start, status, detail, fdet, adm, chunk_match)`` per
        fixed-size chunk; ``adm`` is the device's per-row
        admission-match decision for the eligible program columns (None
        off the compact path or when the policy set has none).

        The chunks stream through a bounded overlapped pipeline
        (``compiler/pipeline.py``): encode → h2d → device_eval → d2h
        each run on their own worker thread with at most
        ``KTPU_PIPELINE_DEPTH`` chunks in flight, so end-to-end rate ≈
        max(stage) instead of sum(stage) and a slow leg backpressures
        intake instead of buffering.  Encode lane tensors are recycled
        through the scanner's :class:`LaneArena`, or where worker
        processes encode through the pool's shared-memory blocks — a
        chunk's buffers return to either when its d2h lands, so RSS
        stays flat in ``n_resources``.

        ``match`` (the host-side [R, P] match mask) rides to the device
        with each chunk so fail details compact to the (matched, FAIL)
        cells — ~3× fewer d2h bytes.
        ``match_fn(start, part)`` computes the mask per chunk inside
        the encode stage instead (streaming callers avoid holding the
        full [R, P] matrix)."""
        n = len(resources)
        if not self.cps.programs or not resources:
            z = np.zeros((n, len(self.cps.programs)), np.int8)
            zm = match[:n] if match is not None \
                else np.zeros((n, len(self.cps.programs)), bool)
            yield 0, z, z, z.astype(np.int32), None, zm
            return
        if self._composer is not None:
            yield from self._partitioned_status_chunks(
                resources, contexts, match, match_fn)
            return
        import jax
        from ..observability import device as devtel
        from ..observability import tracing
        from ..ops.eval import expand_compact, shard_batch
        from .pipeline import ChunkPipeline
        chunk = self.CHUNK
        # pipeline stages run on worker threads where the contextvar
        # span is absent — capture the request/scan span here so every
        # stage span joins the caller's trace (and the provenance
        # capture, so multi-chunk scans attribute worker-thread stage
        # time to the right scan)
        tel_parent = tracing.current_span()
        tel_capture = devtel.current_capture()
        arena = self._arena if self.mesh is None else None

        # multi-chunk scans encode in worker processes (off-GIL); small
        # scans stay in-process
        use_procs = n > chunk and self._encoder_pool.start()

        def inline_encode(part, part_ctx, bucket):
            with devtel.stage('encode', {'rows': len(part)}):
                batch = encode_batch(part, self.cps, padded_n=bucket,
                                     contexts=part_ctx, arena=arena)
                return batch.tensors(), batch

        def release_chunk(p):
            """Return a chunk's encode buffers — the arena's batch, or
            the encoder pool's block — exactly once: after d2h frees its
            device inputs on the success path, or via the pipeline's
            cleanup hook when the chunk dies mid-flight (stage crash,
            aborted stream).  The order carries weight: ``pack_batch``
            hands these very buffers to the transfer (the lanes are
            views of them, ops/eval.py), and the transfer may read them
            until the device has its copy — on XLA:CPU the device array
            IS this memory.  So the device references are dropped
            first, and a buffer is never zeroed and re-encoded under a
            dispatch that still reads it
            (tests/test_pack_views.py TestLifetime)."""
            if not isinstance(p, dict):
                return
            p['t'] = p['out'] = p['enc'] = None
            batch = p.get('batch')
            p['batch'] = None
            if arena is not None and batch is not None:
                arena.release(batch)
            block = p.get('block')
            p['block'] = None
            if block is not None:
                self._encoder_pool.blocks.release(block)

        def stage_encode(start):
            faults.check(faults.SITE_ENCODE)
            part = resources[start:start + chunk]
            part_ctx = contexts[start:start + chunk] \
                if contexts is not None else None
            cm = match[start:start + len(part)] if match is not None \
                else (match_fn(start, part) if match_fn is not None
                      else None)
            devtel.record_match_cells(cm)
            # canonical capacity padding (compiler/shapes.py): every
            # part pads to one of the few canonical row shapes and the
            # evaluator masks the tail rows via the __rowvalid__ lane,
            # so XLA never sees a new shape whatever the occupancy.
            # Multi-chunk scans pin every part (tail included) to the
            # chunk capacity, so a canonically-small tail never adds a
            # second shape to a bulk scan.
            bucket = chunk if n > chunk else canonical_capacity(
                len(part), chunk=chunk, small=self.SMALL_BATCH)
            enc = batch = block = None
            t_submit = 0.0
            if use_procs and not self._encoder_pool._broken:
                # the worker lays the chunk's lanes over this block; it
                # is the chunk's until release_chunk
                block = self._encoder_pool.blocks.acquire()
                try:
                    # from here to the worker's start on the chunk is
                    # ``encode_submit``
                    t_submit = time.monotonic()
                    enc = self._encoder_pool.submit(part, part_ctx,
                                                    bucket, block)
                except Exception:  # noqa: BLE001 - fall back in-process
                    # giving the pool up drops its blocks, this one too
                    self._encoder_pool.mark_broken('pool_failed')
                    enc = block = None
            if enc is None:
                enc, batch = inline_encode(part, part_ctx, bucket)
            ctx_lanes = ctx_marks = None
            if self._ctx:
                # beside the worker's encode: the chunk's distinct
                # context inputs resolved once each, the value lanes and
                # the load-outcome mask written.  Admission rows carry
                # their own request, so each is resolved on its own
                with devtel.stage('context', {'chunk': start // chunk,
                                              'rows': len(part)}):
                    ctx_lanes, ctx_marks = self._ctx.fill(
                        part, cm, bucket, self,
                        memo=getattr(self, '_pctx_factory', None) is None)
            return {'start': start, 'ln': len(part), 'part': part,
                    'part_ctx': part_ctx, 'bucket': bucket, 'enc': enc,
                    'batch': batch, 'block': block, 'cm': cm,
                    'ctx_lanes': ctx_lanes, 'ctx_marks': ctx_marks,
                    't_submit': t_submit}

        def stage_h2d(p):
            faults.check(faults.SITE_H2D)
            start, ln = p['start'], p['ln']
            tensors = p['enc']
            devtel.set_batch_size(ln)
            if not isinstance(tensors, dict):
                # AsyncResult from the worker pool: a dead/OOM-killed
                # worker never resolves its task, so bound the wait and
                # redo the chunk in-process rather than wedging the whole
                # scan
                if self._encoder_pool._broken:
                    # pool already declared dead: don't wait another
                    # timeout per in-flight chunk
                    tensors, p['batch'] = inline_encode(
                        p['part'], p['part_ctx'], p['bucket'])
                else:
                    try:
                        with devtel.stage('encode_wait'):
                            t_wait = time.monotonic()
                            home = tensors.get(
                                timeout=self.ENCODE_TIMEOUT_S)
                            t_home = time.monotonic()
                        wstages, wspan = home[2:]
                        tensors = self._encoder_pool.lanes(p['block'],
                                                           home)
                    except Exception as e:  # noqa: BLE001 - no answer
                        # inside the timeout: the worker is presumed
                        # dead; an answer that is an error, or lanes
                        # that cannot be mapped: no block could be had
                        self._encoder_pool.mark_broken(
                            'presumed_dead' if isinstance(
                                e, multiprocessing.TimeoutError)
                            else 'pool_failed')
                        tensors, p['batch'] = inline_encode(
                            p['part'], p['part_ctx'], p['bucket'])
                    else:
                        devtel.record_encode_result_bytes(tensors, home)
                        # stage seconds measured inside the worker:
                        # fold into the parent's histogram and the
                        # ambient ScanCapture (installed on this
                        # pipeline thread); processes of one host share the
                        # monotonic clock on Linux
                        devtel.record_encode_worker('ok')
                        devtel.merge_worker_stages(wstages)
                        # the chunk's way there and back, on that
                        # clock: handed to the pool until the worker
                        # started on it, and the part of the wait above
                        # in which the worker had already finished
                        devtel.record_stage(
                            'encode_submit',
                            max(0.0, wspan[0] - p['t_submit']))
                        devtel.record_stage(
                            'encode_return',
                            max(0.0, t_home - max(wspan[1], t_wait)))
                # home once: a retry of this stage starts from the lanes
                p['enc'] = tensors
            cm = p['cm']
            if cm is not None and self.mesh is None and tensors:
                from ..ops.eval import fold_match_unique
                padded = next(iter(tensors.values())).shape[0]
                # host-policy program columns are never read from fdet
                # (_assemble_chunk ANDs with _dev_mask) — keep their
                # FAIL cells out of the per-row compaction budget; the
                # mask rides in UNIQUE-program space (duplicate columns
                # OR-folded) so the device graph and d2h stay O(unique)
                mm_p = (cm & self._dev_mask).astype(np.uint8)
                mm_u = fold_match_unique(mm_p, self._evaluator)
                mm = np.zeros((padded, mm_u.shape[1]), np.uint8)
                mm[:ln] = mm_u
                # a copy that still knows whose views the lanes are
                # (packing.py PackedLanes): p['enc'] stays as encoded
                tensors = tensors.copy()
                tensors['__match__'] = mm
            if self._adm is not None and self.mesh is None and tensors:
                # admission lanes ride EVERY non-mesh dispatch of this
                # policy set (zero-filled when the scan carries no
                # admission data) so the executable signature — and the
                # fresh-process census — never depends on traffic mix
                padded = next(iter(tensors.values())).shape[0]
                tensors = tensors.copy()
                if adm_plan is not None:
                    tensors.update(admission_lanes.slice_lanes(
                        adm_plan.lanes, start, ln, padded))
                else:
                    tensors.update(admission_lanes.zero_lanes(
                        self._adm, padded))
            if p['ctx_lanes']:
                tensors = tensors.copy()
                tensors.update(p['ctx_lanes'])
            t, layout = shard_batch(tensors, self.mesh)
            p['enc'] = p['part'] = p['part_ctx'] = p['ctx_lanes'] = None
            p['t'], p['layout'] = t, layout
            return p

        def stage_eval(p):
            faults.check(faults.SITE_DEVICE_EVAL)
            p['out'] = self._evaluator(p['t'], p['layout'])
            return p

        def stage_d2h(p):
            faults.check(faults.SITE_D2H)
            start, ln, t, out = p['start'], p['ln'], p['t'], p['out']
            if len(out) == 2:
                # np.array COPIES: np.asarray of a host-backend jax
                # array is zero-copy, and _free_inputs is about to
                # release the backing buffers
                with devtel.d2h_guard({'chunk_start': start,
                                       'rows': ln}) as g:
                    # the dispatch only enqueued: the host's wait for
                    # the device is here, named apart from the copies
                    with devtel.stage('device_wait'):
                        jax.block_until_ready(out)
                    o8 = np.array(out[0])
                    o32 = np.array(out[1])
                    g.add_d2h_bytes(o8.nbytes + o32.nbytes)
                with devtel.stage('expand', {'rows': ln}):
                    s, d, fd, adm = expand_compact(o8, o32,
                                                   self._evaluator)
                    self._free_inputs(t, out)
                    cm = p['cm']
                    self._mark_context(s, p['ctx_marks'])
                    release_chunk(p)
                return (start, s[:ln], d[:ln], fd[:ln],
                        adm[:ln] if adm is not None else None, cm)
            s, d, fd = out
            if self.mesh is not None:
                from ..observability import fleet
                shard_walls = None
                t_coll = 0.0
                padded_rows = int(s.shape[0])
                if fleet.enabled():
                    # mesh-path telemetry (fleet observatory): time
                    # each shard's readback wait, then the collective
                    # leg — pure timing, the values are untouched
                    from ..parallel.mesh import shard_wait_splits
                    shard_walls = shard_wait_splits(s)
                    t_coll = time.perf_counter()
                if jax.process_count() > 1:
                    # multi-host mesh: each process only holds its
                    # local shards of the batch axis — gather the
                    # full matrices so every host assembles
                    # identical reports (the reference replicates
                    # this work per replica)
                    from jax.experimental import multihost_utils
                    s = multihost_utils.process_allgather(s, tiled=True)
                    d = multihost_utils.process_allgather(d, tiled=True)
                    fd = multihost_utils.process_allgather(fd,
                                                           tiled=True)
                if shard_walls is not None:
                    from ..parallel.mesh import record_sharded_dispatch
                    record_sharded_dispatch(
                        self.mesh, 'data', ln, padded_rows, shard_walls,
                        time.perf_counter() - t_coll)
            with devtel.d2h_guard({'chunk_start': start,
                                   'rows': ln}) as g:
                with devtel.stage('device_wait'):
                    jax.block_until_ready((s, d, fd))
                s, d, fd = (np.array(s)[:ln], np.array(d)[:ln],
                            np.array(fd)[:ln])
                g.add_d2h_bytes(s.nbytes + d.nbytes + fd.nbytes)
            if self.mesh is None:
                self._free_inputs(t, out)
            cm = p['cm']
            self._mark_context(s, p['ctx_marks'])
            release_chunk(p)
            return start, s, d, fd, None, cm

        if n <= chunk:
            # single-chunk fast path: pipeline thread spawn/join costs
            # more than it hides for one chunk (admission latency
            # floor).  The chunk span closes BEFORE the yield — holding
            # it across a yield would leak the current-span contextvar
            # into the consumer
            with devtel.install_capture(tel_capture), \
                    tracing.tracer().start_span(
                        'kyverno/device/chunk', {'chunk_start': 0},
                        parent=tel_parent):
                p = None
                try:
                    p = stage_encode(0)
                    p = stage_h2d(p)
                    p = stage_eval(p)
                    result = stage_d2h(p)
                except BaseException:
                    # the inline path has no pipeline cleanup hook: a
                    # stage crash must still hand the chunk's encode
                    # buffers back before the error surfaces
                    release_chunk(p)
                    raise
            yield result
            return

        # a mesh across processes: h2d (device_put checks its input is
        # the same on every process) and d2h (the allgathers) both run
        # collectives, from two threads.  With two chunks in flight each
        # process picks its own order between chunk k's d2h and chunk
        # k+1's h2d, and two processes that pick differently wait on
        # each other for ever.  One chunk in flight fixes the order.
        depth = 1 if self.mesh is not None and jax.process_count() > 1 \
            else None
        pipe = ChunkPipeline(
            [('encode', stage_encode), ('h2d', stage_h2d),
             ('device_eval', stage_eval), ('d2h', stage_d2h)],
            depth=depth, capture=tel_capture, parent_span=tel_parent,
            cleanup=release_chunk)
        yield from pipe.run(range(0, n, chunk))

    def context_digest(self, resource: dict) -> Optional[tuple]:
        """What the rules of this set would read of their contexts for
        ``resource`` now (``ContextLanes.row_digest``); None where that
        cannot be told from the resource: a rule that loads context on
        the host."""
        if self._host_reads_context:
            return None
        return self._ctx.row_digest(resource, self)

    @property
    def reads_context(self) -> bool:
        """Whether any rule of this set loads context, compiled or on
        the host: its verdicts are no function of the resource alone."""
        return bool(self._ctx) or self._host_reads_context

    @staticmethod
    def _mark_context(status: np.ndarray, marks) -> None:
        """The chunk's load-outcome mask, laid over the device's
        statuses: a cell whose context load failed, or whose value the
        lanes could not carry, reads ``STATUS_CTX_*`` from here on and
        assembly hands exactly those cells to the host."""
        for j, rows, st in marks or ():
            status[rows, j] = st

    def _partitioned_status_chunks(self, resources: List[dict],
                                   contexts: Optional[List[dict]] = None,
                                   match: Optional[np.ndarray] = None,
                                   match_fn=None):
        """Partitioned twin of ``_device_status_chunks``: each chunk
        encodes and dispatches once per partition runtime (the
        partition's own slot vocabulary, match plane and executable),
        then the composer scatters the per-partition buffers back into
        whole-set ``(status, detail, fdet)`` — the yield contract is
        identical, so assembly downstream never knows partitions exist.

        Differences from the monolithic path, all deliberate:

        * no forked encode pool and no :class:`LaneArena` — both are
          bound to the whole-set ``cps`` vocabulary, and per-partition
          lane sets are smaller (the arena would fragment across
          heterogeneous vocabularies);
        * no in-graph admission output — ``self._adm`` is None in
          partitioned mode, so admission rows were already decided
          exactly by the host matcher (the yielded ``adm`` is None);
        * per-partition evaluators dispatch serially within a chunk
          (one accelerator; the chunk pipeline still overlaps encode /
          h2d / eval / d2h across chunks)."""
        n = len(resources)
        import jax
        from ..observability import device as devtel
        from ..observability import tracing
        from ..ops.eval import (expand_compact, fold_match_unique,
                                shard_batch)
        from .pipeline import ChunkPipeline
        chunk = self.CHUNK
        tel_parent = tracing.current_span()
        tel_capture = devtel.current_capture()
        rts = self._pset.runtimes

        def stage_encode(start):
            faults.check(faults.SITE_ENCODE)
            part = resources[start:start + chunk]
            part_ctx = contexts[start:start + chunk] \
                if contexts is not None else None
            cm = match[start:start + len(part)] if match is not None \
                else (match_fn(start, part) if match_fn is not None
                      else None)
            devtel.record_match_cells(cm)
            bucket = chunk if n > chunk else canonical_capacity(
                len(part), chunk=chunk, small=self.SMALL_BATCH)
            encs = []
            with devtel.stage('encode', {'rows': len(part),
                                         'partitions': len(rts)}):
                for rt in rts:
                    batch = encode_batch(part, rt.sub_cps,
                                         padded_n=bucket,
                                         contexts=part_ctx)
                    encs.append(batch.tensors())
            return {'start': start, 'ln': len(part), 'bucket': bucket,
                    'encs': encs, 'cm': cm}

        def stage_h2d(p):
            faults.check(faults.SITE_H2D)
            ln = p['ln']
            devtel.set_batch_size(ln)
            cm = p['cm']
            dev_m = (cm & self._dev_mask).astype(np.uint8) \
                if cm is not None else None
            shipped = []
            for rt, tensors in zip(rts, p['encs']):
                padded = next(iter(tensors.values())).shape[0]
                tensors = dict(tensors)
                if dev_m is not None:
                    # slice the global device-mask'd match down to this
                    # partition's program columns, then fold to ITS
                    # unique space — each executable sees exactly the
                    # plane the monolithic path would have shown for
                    # those columns
                    mm_u = fold_match_unique(
                        np.ascontiguousarray(dev_m[:, rt.prog_cols]),
                        rt.evaluator)
                    mm = np.zeros((padded, mm_u.shape[1]), np.uint8)
                    mm[:ln] = mm_u
                    tensors['__match__'] = mm
                if rt.adm is not None:
                    # zero lanes keep the executable signature stable
                    # (the in-graph decision is discarded; the host
                    # matcher already decided admission rows)
                    tensors.update(admission_lanes.zero_lanes(
                        rt.adm, padded))
                shipped.append(shard_batch(tensors, None))
            p['encs'] = None
            p['shipped'] = shipped
            return p

        def stage_eval(p):
            faults.check(faults.SITE_DEVICE_EVAL)
            p['outs'] = [rt.evaluator(t, layout)
                         for rt, (t, layout) in zip(rts, p['shipped'])]
            return p

        def stage_d2h(p):
            faults.check(faults.SITE_D2H)
            start, ln = p['start'], p['ln']
            parts_out = []
            with devtel.d2h_guard({'chunk_start': start,
                                   'rows': ln}) as g:
                with devtel.stage('device_wait'):
                    jax.block_until_ready(p['outs'])
                for rt, (t, _layout), out in zip(rts, p['shipped'],
                                                 p['outs']):
                    if len(out) == 2:
                        o8 = np.array(out[0])
                        o32 = np.array(out[1])
                        g.add_d2h_bytes(o8.nbytes + o32.nbytes)
                        s_k, d_k, fd_k, _adm = expand_compact(
                            o8, o32, rt.evaluator)
                    else:
                        s_k, d_k, fd_k = (np.array(out[0]),
                                          np.array(out[1]),
                                          np.array(out[2]))
                        g.add_d2h_bytes(s_k.nbytes + d_k.nbytes +
                                        fd_k.nbytes)
                    self._free_inputs(t, out)
                    parts_out.append((s_k[:ln], d_k[:ln], fd_k[:ln]))
            p['shipped'] = p['outs'] = None
            s, d, fd = self._composer.compose(parts_out, ln)
            return start, s, d, fd, None, p['cm']

        if n <= chunk:
            with devtel.install_capture(tel_capture), \
                    tracing.tracer().start_span(
                        'kyverno/device/chunk', {'chunk_start': 0},
                        parent=tel_parent):
                result = stage_d2h(stage_eval(stage_h2d(stage_encode(0))))
            yield result
            return

        pipe = ChunkPipeline(
            [('encode', stage_encode), ('h2d', stage_h2d),
             ('device_eval', stage_eval), ('d2h', stage_d2h)],
            capture=tel_capture, parent_span=tel_parent)
        yield from pipe.run(range(0, n, chunk))

    def _next_chunk(self, chunks, n: int, at: int):
        """``next(chunks)``, timed as ``chunk_wait``: what the consuming
        thread spends getting its next chunk.  Where the chunks come
        through the pipeline that is a wait, and it is marked in the
        profiler's trace; a single chunk runs its stages inline on this
        thread, under their own names, so only the histogram and the
        capture see the sum."""
        from ..observability import device as devtel
        t0 = time.monotonic()
        try:
            if n > self.CHUNK:
                with devtel.annotation('chunk_wait',
                                       chunk=at // max(self.CHUNK, 1)):
                    return next(chunks)
            return next(chunks)
        finally:
            devtel.record_stage('chunk_wait', time.monotonic() - t0)

    def _device_statuses(self, resources: List[dict],
                         contexts: Optional[List[dict]] = None,
                         match: Optional[np.ndarray] = None):
        parts = list(self._device_status_chunks(resources, contexts, match))
        if len(parts) == 1:
            return parts[0][1:4]
        return tuple(np.concatenate([p[i] for p in parts])
                     for i in range(1, 4))

    def scan_statuses(self, resources: List[dict]):
        """Raw (status, detail, match) matrices over all compiled programs
        — the allocation-free fast path for throughput measurement and
        report aggregation."""
        wrapped = [Resource(r) for r in resources]
        match = self.match_matrix(resources, wrapped)
        status, detail, _ = self._device_statuses(resources, match=match)
        return status, detail, match

    # -- full responses -----------------------------------------------------

    def scan(self, resources: List[dict],
             contexts: Optional[List[dict]] = None,
             admission: Optional[tuple] = None,
             pctx_factory=None,
             old_resources: Optional[List[Optional[dict]]] = None,
             admissions: Optional[List[Optional[tuple]]] = None
             ) -> List[List[EngineResponse]]:
        """Return, per resource, the engine responses of all policies with
        at least one applicable rule (host-identical).

        Webhook scans pass ``contexts`` (the admission JSON context per
        resource), ``admission`` (admission_info, exclude_group_roles,
        namespace_labels, operation) for match semantics, and
        ``pctx_factory(doc)`` so host materialization sees the same
        PolicyContext the engine loop would build.  Heterogeneous
        batches pass ``admissions`` — one admission tuple PER ROW —
        instead: rules whose match depends on the tuple are decided
        in-graph from per-row admission lanes when the policy set
        lowered them (compiler/admission.py), per-row on the host
        otherwise.  UPDATE-verb rows additionally carry their
        ``oldObject`` in ``old_resources`` (row-aligned, None for rows
        without one): the engine retries a failed new-object match
        against the old object, so the host match sieve must too —
        evaluation itself stays on the new object, exactly like the
        engine."""
        return list(self.scan_stream(resources, contexts, admission,
                                     pctx_factory, old_resources,
                                     admissions))

    def scan_stream(self, resources: List[dict],
                    contexts: Optional[List[dict]] = None,
                    admission: Optional[tuple] = None,
                    pctx_factory=None,
                    old_resources: Optional[List[Optional[dict]]] = None,
                    admissions: Optional[List[Optional[tuple]]] = None):
        """Generator form of ``scan``: yields each resource's responses
        in order as its device chunk completes.  Consumers that do
        per-resource work (report construction, CR writes) overlap it
        with the next chunk's encode/transfer/device stages instead of
        paying it serially after the whole scan."""
        if not resources:
            return
        yield from self._scan_inner(resources, contexts, admission,
                                    pctx_factory, old_resources,
                                    admissions)

    def _scan_inner(self, resources, contexts, admission, pctx_factory,
                    old_resources=None, admissions=None):
        n = len(resources)
        self._pctx_factory = pctx_factory
        self._begin_pass()
        # admission scans evaluate every policy; the background gate
        # (engine.py:174 apply_background_checks) only applies to scans
        background_mode = admission is None and admissions is None and \
            pctx_factory is None
        from ..observability import device as devtel
        with devtel.stage('prepare', {'rows': n}):
            wrapped = [Resource(r) for r in resources]
            adm_rows = admissions if admissions is not None else (
                [admission] * n if admission is not None else None)
            # per-row admission lanes: encode once per scan; rows whose
            # tuples do not intern exactly fall back to the host matcher
            # alone (taxonomy: admission_unencodable), never the batch
            plan = None
            if adm_rows is not None and self._adm is not None and \
                    self.mesh is None:
                old_flags = [bool(o) for o in old_resources] \
                    if old_resources is not None else None
                plan = admission_lanes.encode_rows(self._adm, adm_rows,
                                                   old_flags)
                atoms = self._adm_res_atoms(resources, wrapped)
                plan.lanes['__admres__'] = atoms
                plan.upper = admission_lanes.match_upper(self._adm, atoms)
                bad = int(plan.unencodable.sum())
                if bad:
                    coverage.record_fallback(
                        'validate', coverage.REASON_ADMISSION_UNENCODABLE,
                        rows=bad)
        # one ``match`` sample a scan: the sieve, the old objects'
        # retry and the host policies' screen
        with devtel.stage('match', {'rows': n}):
            match = self._match_rows(resources, wrapped,
                                     adm_rows=adm_rows, plan=plan)
            if old_resources is not None and any(old_resources):
                match = self._fold_old_matches(match, wrapped, adm_rows,
                                               old_resources)
            # which host policies could match each resource at all
            # (group screen over their simple rules; non-simple rules
            # force a run).  The screen is valid for admission scans
            # too: simple-match rules only reference kinds/namespaces
            # (the matcher ignores operations entirely, and
            # roles/subjects rules are non-simple), and a screened-out
            # policy contributes the same empty response the engine
            # would produce.
            host_maybe = self._host_policy_maybe(resources, wrapped,
                                                 old_resources)
        now = time.time()
        ts = int(now)

        progs = self.cps.programs
        background_ok = getattr(self, '_background_ok', None)
        if background_ok is None:
            background_ok = self._background_ok = np.array([
                self.policies[p.policy_index].background for p in progs])

        # the device chunks stream through while this loop assembles —
        # three pipeline stages (encode / device / assemble) overlap;
        # assembly strategy details live in _assemble_chunk.
        # each span covers one chunk's device wait + host assembly and
        # opens/closes within a single generator step (no yield inside
        # the with-block): holding one span across yields would leak the
        # current-span contextvar into the consumer and record a bogus
        # error when the consumer stops iterating early
        from ..observability import tracing
        chunk_cap = max(self.CHUNK, 1)
        chunks = self._device_status_chunks(resources, contexts, match,
                                            adm_plan=plan)
        tally = coverage.scan_tally()
        start = 0
        try:
            while start < n:
                with tracing.start_span(
                        'kyverno/device/scan',
                        {'chunk_start': start,
                         'programs': len(progs)}) as span:
                    try:
                        start, status, detail, fdet, adm_out, _cm = \
                            self._next_chunk(chunks, n, start)
                    except StopIteration:
                        return
                    if adm_out is not None and plan is not None:
                        # the exact in-graph admission-match decision
                        # replaces the conservative upper bound for
                        # device-valid rows before assembly reads it
                        vr = np.flatnonzero(
                            plan.valid[start:start + status.shape[0]])
                        if vr.size:
                            match[np.ix_(start + vr, self._adm_cols)] = \
                                adm_out[vr].astype(bool)
                    span.set_attribute('resources', status.shape[0])
                    with devtel.stage('report',
                                      {'rows': status.shape[0],
                                       'chunk': start // chunk_cap}
                                      ) as rstage:
                        chunk_rows = self._assemble_chunk(
                            resources, wrapped, match, start, status,
                            detail, fdet, now, ts, background_mode,
                            background_ok, host_maybe, tally)
                        self._record_fail_memo()
                        if tally is not None:
                            ratio = tally.ratio()
                            if ratio is not None:
                                # cumulative within this scan — the
                                # fallback-attribution view of the chunk
                                rstage.set_attribute(
                                    'device_coverage_ratio',
                                    round(ratio, 4))
                                span.set_attribute(
                                    'device_coverage_ratio',
                                    round(ratio, 4))
                start += status.shape[0]
                yield from chunk_rows
        finally:
            # flush even when the consumer abandons the stream early —
            # partial scans still land in the ledger and set the
            # per-scan coverage-ratio gauge
            if tally is not None:
                tally.finish()
                cap = devtel.current_capture()
                if cap is not None:
                    cap.coverage_ratio = tally.ratio()
            chunks.close()

    def _assemble_chunk(self, resources, wrapped, match, start, status,
                        detail, fdet, now, ts, background_mode,
                        background_ok, host_maybe, tally=None
                        ) -> List[List[EngineResponse]]:
        """Assemble one device chunk into per-resource engine responses.

        Large chunks assemble column-wise (per program over the whole
        chunk): the status branch, message lookup and int casts
        amortize over all rows of a column.  Small batches (admission:
        one resource) assemble row-wise — a column sweep would pay one
        numpy call per program for a single resource.  Identical
        device-synthesized cells share one flyweight RuleResponse
        (treat rule responses from scan() as immutable — every
        downstream consumer only reads)."""
        _HOST = _HOST_MARKER
        progs = self.cps.programs
        m = status.shape[0]
        sub_match = match[start:start + m]
        # per-row [(policy_index, RuleResponse|None), ...] in j order
        acc: List[list] = [[] for _ in range(m)]
        fly: Dict[Tuple, Any] = {}
        if m <= self.SMALL_BATCH:
            for k in range(m):
                row_js = np.flatnonzero(sub_match[k] & self._dev_mask)
                st_row = status[k]
                det_row = detail[k]
                for j in row_js.tolist():
                    prog = progs[j]
                    if background_mode and not background_ok[j]:
                        acc[k].append((prog.policy_index, None))
                        continue
                    st = int(st_row[j])
                    rr = self._cell(prog, j, st, int(det_row[j]), fdet[k],
                                    ts, fly, tally, resources[start + k])
                    if rr is _HOST:
                        rr = self._materialize(
                            prog, resources[start + k],
                            int(fdet[k, j]) if st == STATUS_FAIL else None,
                            tally)
                        if rr is not None:
                            rr.timestamp = ts
                    acc[k].append((prog.policy_index,
                                   None if rr is None or rr is _HOST
                                   else rr))
        else:
            # the columns some row of the chunk matched: with namespaced
            # policies most of the set matches nothing here
            live = np.flatnonzero(sub_match.any(axis=0) & self._dev_mask)
            for j in live.tolist():
                prog = progs[j]
                rows = np.flatnonzero(sub_match[:, j])
                p_idx = prog.policy_index
                if background_mode and not background_ok[j]:
                    # background-disabled policies contribute an empty
                    # response (engine.py:174 apply_background_checks)
                    for k in rows.tolist():
                        acc[k].append((p_idx, None))
                    continue
                st_col = status[rows, j].tolist()
                det_col = detail[rows, j].tolist()
                for k, st, det in zip(rows.tolist(), st_col, det_col):
                    rr = self._cell(prog, j, st, det, fdet[k], ts, fly,
                                    tally, resources[start + k])
                    if rr is _HOST:
                        # anchor-SKIP / HOST / unsynthesizable FAIL:
                        # re-run on the host for exact status+message
                        rr = self._materialize(
                            prog, resources[start + k],
                            int(fdet[k, j]) if st == STATUS_FAIL else None,
                            tally)
                        if rr is not None:
                            rr.timestamp = ts
                    acc[k].append((p_idx, None if rr is None or
                                   rr is _HOST else rr))
        chunk_rows: List[List[EngineResponse]] = []
        for k in range(m):
            i = start + k
            res_doc = resources[i]
            responses: Dict[int, EngineResponse] = {}
            for p_idx, rr in acc[k]:
                resp = responses.get(p_idx)
                if resp is None:
                    resp = self._new_response(p_idx, res_doc, now,
                                              wrapped[i])
                    responses[p_idx] = resp
                if rr is None:
                    continue
                pr = resp.policy_response
                pr.rules.append(rr)
                st = rr.status
                if st == RuleStatus.PASS or st == RuleStatus.FAIL:
                    pr.rules_applied_count += 1
                elif st == RuleStatus.ERROR:
                    pr.rules_error_count += 1
            for p_idx in self._host_policy_idx:
                if background_mode and not self._policy_header[p_idx][0].background:
                    # background-disabled policy: empty response without
                    # a host-engine round trip (engine.py:174
                    # apply_background_checks short-circuit)
                    responses[p_idx] = self._new_response(
                        p_idx, res_doc, now, wrapped[i])
                elif host_maybe[p_idx] is None or host_maybe[p_idx][i]:
                    responses[p_idx] = self._host_run(p_idx, res_doc)
                    if tally is not None:
                        self._tally_host_policy(tally, p_idx,
                                                responses[p_idx])
                else:
                    responses[p_idx] = self._new_response(
                        p_idx, res_doc, now, wrapped[i])
            chunk_rows.append([responses[q] for q in sorted(responses)])
        return chunk_rows

    def _tally_host_policy(self, tally, p_idx: int, resp) -> None:
        """Attribute every rule response of a whole-policy host run to
        its compile-time fallback reason (policy_coupling for rules that
        compiled but ride host with their policy)."""
        pol = self._policy_header[p_idx][1]
        for rr in resp.policy_response.rules:
            reason, path = self._host_rule_reason.get(
                (pol, rr.name),
                (coverage.REASON_POLICY_COUPLING, 'validate'))
            tally.host_rule(pol, rr.name, reason, path)

    #: rows per incremental report-assembly window: each device chunk
    #: assembles (and yields) in sub-windows of at most this many rows,
    #: so the resident decoded-result footprint is bounded by the knob,
    #: not the chunk capacity
    REPORT_FLUSH_ROWS = int(__import__('os').environ.get(
        'KTPU_REPORT_FLUSH_ROWS', '8192'))

    def _report_order(self):
        """Device programs in report-result sort order with their static
        report fields: ``(j, prog, p_idx, policy_key, scored, category,
        severity)``.  Report results sort on (policy key, rule name,
        0, (), ts) and one scan shares one ts, so emitting columns in
        this precomputed order yields each row's results already sorted
        — no per-row sort on the streaming path (stable order matches
        the unfused path's stable sort)."""
        cached = getattr(self, '_report_order_cache', None)
        if cached is None:
            from ..reports.results import _policy_static
            entries = []
            for j, prog in self.device_programs:
                policy = self.policies[prog.policy_index]
                key, scored, category, severity = _policy_static(policy)
                entries.append((key, prog.rule_name, j, prog,
                                prog.policy_index, scored, category,
                                severity))
            entries.sort(key=lambda e: (e[0], e[1]))
            cached = self._report_order_cache = [
                (j, prog, p_idx, key, scored, category, severity)
                for key, _rn, j, prog, p_idx, scored, category, severity
                in entries]
        return cached

    _SUMMARY_BUCKETS = ('pass', 'fail', 'warn', 'error', 'skip')
    _BUCKET_IDX = {b: i for i, b in enumerate(_SUMMARY_BUCKETS)}

    def _assemble_report_window(self, resources, base, m, status, detail,
                                fdet, sub_match, background_ok, ts,
                                stamp, tally):
        """Columnar assembly of one chunk window: per ordered program
        column, group cells by (status, detail) and append the shared
        flyweight result dict to each matched row — one result-dict
        build per DISTINCT cell value, one numpy pass per column.
        Returns (rows, row_policies, counts, dirty) where ``counts`` is
        the [m, 5] summary matrix and ``dirty`` marks rows needing a
        sort-merge (host-policy rows)."""
        from ..reports.results import _rule_result
        rows: List[list] = [[] for _ in range(m)]
        row_pols: List[list] = [[] for _ in range(m)]
        counts = np.zeros((m, 5), np.int32)
        fly: Dict[Tuple, Any] = {}
        bucket_idx = self._BUCKET_IDX
        for j, prog, p_idx, key, scored, category, severity in \
                self._report_order():
            if not background_ok[j]:
                continue
            rows_j = np.flatnonzero(sub_match[:, j])
            if rows_j.size == 0:
                continue
            if tally is not None:
                tally.total_rows += int(rows_j.size)
            st_col = status[rows_j, j].astype(np.int32)
            det_col = detail[rows_j, j].astype(np.int32)
            # a context program's load outcomes came with the chunk, as
            # statuses of their own (_mark_context), so its cells group
            # like any other's
            combined = st_col * 1024 + (det_col + 512)
            uniq, inv = np.unique(combined, return_inverse=True)
            groups = [(int(u) // 1024 , int(u) % 1024 - 512,
                       rows_j[inv == gi])
                      for gi, u in enumerate(uniq)]
            for st, det, sub in groups:
                if st == STATUS_FAIL:
                    # FAIL messages hang off the per-row fail-detail
                    # buffer — but the relevant fdet columns take few
                    # distinct values, so group rows by them and
                    # synthesize one message per distinct detail
                    self._assemble_fail_groups(
                        prog, j, p_idx, key, scored, category, severity,
                        sub, fdet, resources, base, ts, stamp, fly,
                        rows, row_pols, counts, tally)
                    continue
                cell_key = (j, st, det)
                cell = fly.get(cell_key)
                if cell is None:
                    rr = self._synth_rule(prog, st, det, ts)
                    if rr is _HOST_MARKER:
                        cell = (_HOST_MARKER, 0)
                    else:
                        result = _rule_result(rr, key, scored, category,
                                              severity, stamp, ts)
                        cell = (result, bucket_idx[result['result']])
                    fly[cell_key] = cell
                result, bucket = cell
                if result is _HOST_MARKER:
                    if tally is not None:
                        tally.fallback_n(prog, self._host_reason(prog, st),
                                         int(sub.size))
                    for k in sub.tolist():
                        rr = self._materialize(prog, resources[base + k])
                        if rr is None:
                            continue
                        rr.timestamp = ts
                        res = _rule_result(rr, key, scored, category,
                                           severity, stamp, ts)
                        rows[k].append(res)
                        row_pols[k].append(p_idx)
                        counts[k, bucket_idx[res['result']]] += 1
                    continue
                if tally is not None:
                    tally.device_n(prog, int(sub.size))
                for k in sub.tolist():
                    rows[k].append(result)
                    row_pols[k].append(p_idx)
                counts[sub, bucket] += 1
        return rows, row_pols, counts

    def _assemble_fail_groups(self, prog, j, p_idx, key, scored,
                              category, severity, sub, fdet, resources,
                              base, ts, stamp, fly, rows, row_pols,
                              counts, tally):
        """Columnar FAIL assembly: rows group by the fail-detail
        columns the message synthesis actually reads (column j, or the
        anyPattern child block), one message per distinct detail."""
        from ..reports.results import _rule_result
        bucket_idx = self._BUCKET_IDX
        meta = self._evaluator.any_meta.get(j) \
            if prog.any_fail_sites is not None else None
        if meta is None:
            fds = fdet[sub, j]
            uf, inv = np.unique(fds, return_inverse=True)
            subgroups = [sub[inv == t] for t in range(uf.size)]
        else:
            p = len(self.cps.programs)
            block = fdet[sub, p + meta[0]:p + meta[0] + meta[1]]
            uf, inv = np.unique(block, axis=0, return_inverse=True)
            subgroups = [sub[inv == t] for t in range(uf.shape[0])]
        for sg in subgroups:
            msg = self._fail_message_cached(prog, j, fdet[sg[0]])
            if msg is None:
                # the host words these: the check library, the
                # Validator, or for a message with a plan the response
                # the Validator gave the first row of the same key
                hits = 0
                for k in sg.tolist():
                    rr, hit = self._fail_memoized(
                        prog, j, fdet[k], resources[base + k], ts, tally)
                    if rr is None:
                        continue
                    if hit:
                        hits += 1
                        cell_key = (j, STATUS_FAIL, rr.message)
                        cell = fly.get(cell_key)
                        if cell is None:
                            res = _rule_result(rr, key, scored, category,
                                               severity, stamp, ts)
                            cell = fly[cell_key] = (
                                res, bucket_idx[res['result']])
                        res, bucket = cell
                    else:
                        res = _rule_result(rr, key, scored, category,
                                           severity, stamp, ts)
                        bucket = bucket_idx[res['result']]
                    rows[k].append(res)
                    row_pols[k].append(p_idx)
                    counts[k, bucket] += 1
                if tally is not None:
                    if hits:
                        tally.device_n(prog, hits)
                    if hits < sg.size:
                        tally.fallback_n(
                            prog, self._message_reason(prog, j,
                                                       fdet[sg[0]]),
                            int(sg.size) - hits)
                continue
            cell_key = (j, STATUS_FAIL, msg)
            cell = fly.get(cell_key)
            if cell is None:
                rr = RuleResponse(prog.rule_name, RuleType.VALIDATION,
                                  msg, RuleStatus.FAIL)
                rr.timestamp = ts
                result = _rule_result(rr, key, scored, category,
                                      severity, stamp, ts)
                cell = (result, bucket_idx[result['result']])
                fly[cell_key] = cell
            result, bucket = cell
            if tally is not None:
                tally.device_n(prog, int(sg.size))
            for k in sg.tolist():
                rows[k].append(result)
                row_pols[k].append(p_idx)
            counts[sg, bucket] += 1

    def scan_report_results(self, resources: List[dict],
                            now: Optional[float] = None):
        """Yield ``(results, summary, policies)`` per resource — the
        report-path fusion of ``scan_stream``: report-result dicts are
        built straight from the shared device-cell flyweights, skipping
        the per-(resource, policy) EngineResponse objects entirely
        (reference scanner.go:60 only ever turns EngineResponses into
        report results; bit-identity with the unfused path is pinned by
        tests/test_report_fusion.py).

        Fully streaming: the per-chunk match mask is computed inside
        the pipeline's encode stage (``match_fn``), verdict buffers are
        consumed chunk-by-chunk as each d2h lands, and rows assemble
        column-wise in ``KTPU_REPORT_FLUSH_ROWS`` windows — nothing is
        ever materialized at ``n_resources`` scale.

        ``results`` are shared flyweight dicts (never mutate);
        ``policies`` is the list of Policy objects contributing at least
        one rule (for report policy labels)."""
        from ..reports.results import engine_response_to_report_results
        if not resources:
            return
        n = len(resources)
        now = time.time() if now is None else now
        ts = int(now)
        ts_key = str(ts)
        stamp = {'seconds': ts}
        self._begin_pass()
        progs = self.cps.programs
        background_ok = getattr(self, '_background_ok', None)
        if background_ok is None:
            background_ok = self._background_ok = np.array([
                self.policies[p.policy_index].background for p in progs])

        def match_fn(start, part):
            # runs inside the pipeline's encode stage: the full [R, P]
            # mask and Resource list never exist
            return self.match_matrix(part, [Resource(r) for r in part])

        from ..observability import device as devtel
        chunk_cap = max(self.CHUNK, 1)
        chunks = self._device_status_chunks(resources, None,
                                            match_fn=match_fn)
        tally = coverage.scan_tally()
        flush = max(1, self.REPORT_FLUSH_ROWS)
        host_idx = [p_idx for p_idx in self._host_policy_idx
                    if self._policy_header[p_idx][0].background]
        done = 0
        try:
            while done < n:
                try:
                    start, status, detail, fdet, _adm, cm = \
                        self._next_chunk(chunks, n, done)
                except StopIteration:
                    return
                m = status.shape[0]
                seq = start // chunk_cap
                host_maybe = None
                part_docs = resources[start:start + m]
                if host_idx:
                    with devtel.stage('match', {'chunk': seq, 'rows': m}):
                        part_wrapped = [Resource(r) for r in part_docs]
                        host_maybe = self._host_policy_maybe(
                            part_docs, part_wrapped)
                for w0 in range(0, m, flush):
                    w1 = min(w0 + flush, m)
                    wm = w1 - w0
                    ids = {'chunk': seq, 'rows': wm}
                    with devtel.stage('report', ids) as rstage:
                        rows, row_pols, counts = \
                            self._assemble_report_window(
                                resources, start + w0, wm,
                                status[w0:w1], detail[w0:w1],
                                fdet[w0:w1], cm[w0:w1], background_ok,
                                ts, stamp, tally)
                        self._record_fail_memo()
                        if tally is not None:
                            ratio = tally.ratio()
                            if ratio is not None:
                                rstage.set_attribute(
                                    'device_coverage_ratio',
                                    round(ratio, 4))
                    # ``store``: what the consumer of the rows does
                    # with the thread between the rows of this window
                    # (the reports controller: build, label, cache and
                    # write each report), timed around the yield and
                    # observed once; the mark spans the row loop.  A
                    # consumer that zips the rows against a list closes
                    # the stream AT its last yield, hence the finally
                    store_s = t_row = 0.0
                    mark = devtel.annotation('store', **ids)
                    mark.__enter__()
                    try:
                        for k in range(wm):
                            i = start + w0 + k
                            results = rows[k]
                            pols = row_pols[k]
                            dirty = False
                            for p_idx in host_idx:
                                if host_maybe[p_idx] is not None and \
                                        not host_maybe[p_idx][w0 + k]:
                                    continue
                                resp = self._host_run(p_idx, resources[i])
                                if tally is not None:
                                    self._tally_host_policy(tally, p_idx,
                                                            resp)
                                if not resp.policy_response.rules:
                                    continue
                                pols.append(p_idx)
                                dirty = True
                                for result in \
                                        engine_response_to_report_results(
                                            resp, now=ts):
                                    results.append(result)
                                    counts[k, self._BUCKET_IDX[
                                        result['result']]] += 1
                            if dirty:
                                # host-policy results interleave by sort
                                # key; device results arrived pre-sorted,
                                # so only these rows pay a sort-merge
                                results.sort(key=lambda r: (
                                    r.get('policy', ''),
                                    r.get('rule', ''), 0, (), ts_key))
                            c = counts[k]
                            summary = {
                                'pass': int(c[0]), 'fail': int(c[1]),
                                'warn': int(c[2]), 'error': int(c[3]),
                                'skip': int(c[4])}
                            seen: Dict[int, None] = dict.fromkeys(pols)
                            row = (results, summary,
                                   [self.policies[p] for p in sorted(seen)])
                            t_row = time.monotonic()
                            yield row
                            store_s += time.monotonic() - t_row
                            t_row = 0.0
                    finally:
                        if t_row:
                            store_s += time.monotonic() - t_row
                        mark.__exit__(None, None, None)
                        devtel.record_stage('store', store_s)
                done += m
        finally:
            if tally is not None:
                tally.finish()
                cap = devtel.current_capture()
                if cap is not None:
                    cap.coverage_ratio = tally.ratio()
            chunks.close()

    def _cell(self, prog, j: int, st: int, det: int, fdet_row, ts: int,
              fly: Dict[Tuple, Any], tally=None, doc=None):
        """Flyweight RuleResponse for one device cell (or _HOST_MARKER).

        FAIL cells key on the synthesized message — the fail-site detail
        row carries anyPattern metadata beyond column j and
        ``_fail_message_cached`` is itself memoized on the relevant
        columns.  ``tally`` (coverage.ScanTally or None) attributes
        every host decision: each branch that returns _HOST_MARKER must
        name its reason, so no fallback is ever silent."""
        if tally is not None:
            tally.total_rows += 1
        if st == STATUS_FAIL:
            msg = self._fail_message_cached(prog, j, fdet_row)
            if msg is None:
                if doc is not None and j in self._msg_plans:
                    rr, hit = self._fail_memoized(prog, j, fdet_row, doc,
                                                  ts, tally)
                    if tally is not None:
                        if hit:
                            tally.device(prog)
                        else:
                            tally.fallback(prog, self._message_reason(
                                prog, j, fdet_row))
                    return rr
                if tally is not None:
                    tally.fallback(prog, self._message_reason(
                        prog, j, fdet_row))
                return _HOST_MARKER
            key = (j, STATUS_FAIL, msg)
            rr = fly.get(key)
            if rr is None:
                rr = RuleResponse(prog.rule_name, RuleType.VALIDATION,
                                  msg, RuleStatus.FAIL)
                rr.timestamp = ts
                fly[key] = rr
            if tally is not None:
                tally.device(prog)
            return rr
        key = (j, st, det)
        rr = fly.get(key)
        if rr is None:
            rr = self._synth_rule(prog, st, det, ts)
            fly[key] = rr
        if tally is not None:
            if rr is _HOST_MARKER:
                tally.fallback(prog, self._host_reason(prog, st))
            else:
                tally.device(prog)
        return rr

    def _synth_rule(self, prog, st: int, det: int, ts: int):
        """Build the shared (flyweight) RuleResponse for one device-
        synthesizable non-FAIL (program, status, detail) cell, or the
        _HOST_MARKER when the cell needs host materialization."""
        if st == STATUS_PASS:
            rr = RuleResponse(prog.rule_name, RuleType.VALIDATION,
                              prog.pass_messages[det], RuleStatus.PASS)
            if prog.pss is not None:
                rr.pod_security_checks = {
                    'level': prog.pss[0], 'version': prog.pss[1],
                    'checks': []}
        elif st == STATUS_SKIP_PRECOND:
            rr = RuleResponse(prog.rule_name, RuleType.VALIDATION,
                              PRECONDITIONS_SKIP_MESSAGE, RuleStatus.SKIP)
        elif st == STATUS_VAR_ERR:
            rr = RuleResponse(prog.rule_name, RuleType.VALIDATION,
                              prog.error_messages[det], RuleStatus.ERROR)
        elif st == STATUS_SKIP and prog.skip_message is not None:
            # foreach 'rule skipped' is a static message
            rr = RuleResponse(prog.rule_name, RuleType.VALIDATION,
                              prog.skip_message, RuleStatus.SKIP)
        else:
            # ktpu: noqa[KTPU302] -- the sole caller (_cell) attributes
            # status_host / unsynthesizable_message on its tally
            return _HOST_MARKER
        rr.timestamp = ts
        return rr

    def _host_policy_rules(self):
        """Per host policy: its autogen-expanded Rule objects when every
        rule is simple-match, else None (always run).  Autogen expansion
        deep-copies rule trees, so computing it per scan call dominated
        single-request admission latency — the policy set is immutable
        for a scanner's lifetime, compute once."""
        cached = getattr(self, '_host_rules_cache', None)
        if cached is None:
            from ..autogen.autogen import compute_rules
            cached = {}
            for p_idx in self._host_policy_idx:
                rules = compute_rules(self.policies[p_idx])
                cached[p_idx] = [Rule(r) for r in rules] \
                    if all(_rule_match_is_simple(r) for r in rules) else None
            self._host_rules_cache = cached
        return cached

    def _host_policy_maybe(self, resources, wrapped, old_resources=None):
        """Per host policy: bool[R] 'any rule may match', or None when the
        policy has non-simple rules (always run).  UPDATE rows OR in the
        old object's screen — the engine's old-match retry means a rule
        matching only the old object still runs, so screening it out
        would drop a response the engine would have produced (the screen
        may only over-approximate)."""
        maybe: Dict[int, Optional[np.ndarray]] = {}
        group_of = [_group_key(doc) for doc in resources]
        old_wrapped = {
            i: Resource(o) for i, o in enumerate(old_resources or [])
            if o}
        host_rules = self._host_policy_rules()
        for p_idx in self._host_policy_idx:
            policy = self.policies[p_idx]
            robj = host_rules[p_idx]
            if robj is None:
                maybe[p_idx] = None
                continue
            cache: Dict[Tuple, bool] = {}

            def screen(res, _policy=policy, _robj=robj):
                return self._policy_gate(_policy, res) and any(
                    matches_resource_description(
                        res, r, None, [], {}, '') is None
                    for r in _robj)

            flags = np.zeros(len(resources), bool)
            for i, key in enumerate(group_of):
                hit = cache.get(key)
                if hit is None:
                    hit = screen(wrapped[i])
                    cache[key] = hit
                if not hit and i in old_wrapped:
                    hit = screen(old_wrapped[i])
                flags[i] = hit
            maybe[p_idx] = flags
        return maybe

    @staticmethod
    def _site_path(sites: Tuple[str, ...], fd: int) -> Optional[str]:
        tmpl = sites[fd >> 16]
        if tmpl.startswith('\x00'):
            # DYNAMIC_SITE: the path embeds a per-resource resolved
            # wildcard key — host materialization produces the message
            return None
        if '{' in tmpl:
            tmpl = tmpl.replace('{e0}', str(fd & 0xFF)) \
                       .replace('{e1}', str((fd >> 8) & 0xFF))
        return tmpl

    def _detail_columns(self, prog: RuleProgram, j: int) -> slice:
        """The fail-detail columns program ``j``'s FAIL message is made
        from: its own, or its anyPattern children's block."""
        meta = self._evaluator.any_meta.get(j) \
            if prog.any_fail_sites is not None else None
        if meta is None:
            return slice(j, j + 1)
        p = len(self.cps.programs)
        return slice(p + meta[0], p + meta[0] + meta[1])

    def _fail_message_cached(self, prog: RuleProgram, j: int,
                             fdet_row) -> Optional[str]:
        """Memoized message synthesis: distinct (program, fail-detail)
        combinations are few, so scans hit the cache almost always."""
        if prog.pss is not None:
            # no site, no static message: its fail detail is the mask of
            # failed checks, for the check library (_materialize)
            return None
        key = (j,) + tuple(
            int(x) for x in fdet_row[self._detail_columns(prog, j)])
        cache = self._fail_msg_cache
        if key in cache:
            return cache[key]
        v = self._fail_message(prog, j, fdet_row)
        if len(cache) > _MESSAGE_CACHE_MAX:
            cache.clear()
        cache[key] = v
        return v

    def _fail_message(self, prog: RuleProgram, j: int,
                      fdet_row) -> Optional[str]:
        """Synthesize the exact host FAIL message from compile-time
        templates, or None when this FAIL needs host materialization.
        (reference formats: pkg/engine/validation.go:722 buildErrorMessage,
        validation.go:460 getDenyMessage, validation.go:746
        buildAnyPatternErrorMessage)."""
        if prog.any_fail_sites is not None:
            meta = self._evaluator.any_meta.get(j)
            if meta is None:
                return None
            base, n_children = meta
            p = len(self.cps.programs)
            parts = []
            for c in range(n_children):
                fd_c = int(fdet_row[p + base + c])
                if fd_c == -2:
                    continue  # skipped sub-pattern: omitted from message
                if fd_c < 0:
                    return None
                path = self._site_path(prog.any_fail_sites[c], fd_c)
                if path is None:
                    return None
                parts.append(f'rule {prog.rule_name}[{c}] failed at '
                             f'path {path}')
            if not parts or prog.any_fail_prefix is None:
                return None
            return prog.any_fail_prefix + ' '.join(parts)
        fd = int(fdet_row[j])
        if fd < 0:
            return None
        if prog.deny_fail_message is not None:
            return prog.deny_fail_message
        if prog.fail_prefix is None or prog.fail_sites is None:
            return None
        site = self._site_path(prog.fail_sites, fd)
        if site is None:
            return None
        return prog.fail_prefix + site

    def _begin_pass(self) -> None:
        """What was read from the cluster is kept within one scan pass
        only — context-load outcomes, and the FAIL responses worded
        from them: the host engine loads for every evaluation, so
        nothing of a ConfigMap may outlive a pass."""
        self._ctx.begin_pass()
        self._msg_memo = {}

    def _record_fail_memo(self) -> None:
        """The memo's hits and misses since the last call, to the
        counter; once an assembled window."""
        if self._msg_memo_hits or self._msg_memo_misses:
            from ..observability import device as devtel
            devtel.record_fail_message_memo(self._msg_memo_hits,
                                            self._msg_memo_misses)
            self._msg_memo_hits = self._msg_memo_misses = 0

    def _fail_memoized(self, prog: RuleProgram, j: int, fdet_row,
                       doc: dict, ts: int, tally=None
                       ) -> Tuple[Optional[RuleResponse], bool]:
        """``(response, hit)`` for a FAIL the device decided and the
        host words (``_fail_message_cached`` gave None).

        Where the program's message has a plan, the response is a
        function of the fail site and of the values of
        ``message_inputs``, so the Validator words it for the first row
        of each such key in a scan pass and every later row takes that
        response, shared (a flyweight: never mutate).  The site is the
        evaluator's fail detail where it recorded one (a deny), else
        what the engine's own pattern walk raises for the row.  A row
        with no key (a walk that raises, a site that cannot be told),
        a program with no plan, a response that is not a FAIL, and
        every cell of an admission scan — its variables resolve in the
        request's own context, not in the document's — go through
        ``_materialize`` alone and nothing of them is kept."""
        key = None
        plan = self._msg_plans.get(j)
        if plan is not None and \
                getattr(self, '_pctx_factory', None) is None:
            walkers, patterns = plan
            site = int(fdet_row[j])
            if site < 0:
                site = self._walk_site(patterns, doc)
            values = _row_key(walkers, doc) if site is not None else None
            if values is not None:
                key = (j, site, values)
                rr = self._msg_memo.get(key)
                if rr is not None:
                    self._msg_memo_hits += 1
                    return rr, True
            self._msg_memo_misses += 1
        rr = self._materialize(prog, doc, int(fdet_row[j]), tally)
        if rr is not None:
            rr.timestamp = ts
            if key is not None and rr.status == RuleStatus.FAIL:
                if len(self._msg_memo) > _MESSAGE_CACHE_MAX:
                    self._msg_memo.clear()
                self._msg_memo[key] = rr
        return rr, False

    @staticmethod
    def _walked_patterns(validation: dict) -> Optional[list]:
        """The patterns the Validator walks for a rule: one, an
        anyPattern's, or None for a rule that is neither."""
        if validation.get('deny') is not None:
            return None
        if validation.get('pattern') is not None:
            return [validation['pattern']]
        patterns = validation.get('anyPattern')
        return patterns if isinstance(patterns, list) else None

    @staticmethod
    def _walk_site(patterns: Optional[list], doc: dict):
        """Where and how the engine's pattern walk fails on ``doc``, one
        entry a pattern, as a key: a compiled pattern has no variables,
        so the walk the Validator would make is this one.  None for a
        rule without patterns, and for a document that one accepts."""
        if patterns is None:
            return None
        site = []
        for pattern in patterns:
            try:
                match_pattern(doc, pattern)
            except PatternError as pe:
                site.append((pe.skip, pe.path, str(pe)))
                continue
            return None
        return tuple(site)

    def _pctx(self, policy: Policy, resource: dict) -> PolicyContext:
        factory = getattr(self, '_pctx_factory', None)
        if factory is not None:
            pctx = factory(resource)
            pctx = pctx.copy()
            pctx.policy = policy
            return pctx
        return PolicyContext(policy, new_resource=resource)

    def _materialize(self, prog: RuleProgram, resource: dict,
                     fail_detail: Optional[int] = None,
                     tally=None) -> Optional[RuleResponse]:
        """Produce the exact host-engine rule response for one rule.

        A podSecurity rule with neither context nor preconditions reads
        the resource and nothing else (Validator.validate loads an
        empty context, passes an empty precondition and calls
        _validate_pod_security), so its answer is one call of the
        function the Validator itself answers with.  Nothing of it is
        kept: every cell pays its own call.  An empty document (a
        DELETE, which the Validator answers with None) and every other
        rule go through the Validator.

        ``fail_detail`` is the cell's fail detail where the device
        decided a FAIL, else None.  For such a podSecurity cell it is
        the mask of the checks that failed (ops/eval.py ``eval_status``),
        and the library's own evaluator then runs those checks alone
        (:func:`_masked_evaluator`); -1 (a check undecided on the
        device, a cell beyond the fail-detail budget) and an evaluator
        the engine was given run them all.  The mask chooses what runs,
        never what is said: every check it names words its own result
        from the document, and one that passes there voids it."""
        rule, pod_security = self._host_rule[prog]
        if pod_security is not None:
            # the Validator reads pctx.new_resource: with a factory
            # that is the request's own context, handed over as it is
            factory = getattr(self, '_pctx_factory', None)
            doc = resource if factory is None \
                else factory(resource).new_resource
            if doc:
                evaluator = self.engine.pss_evaluator
                if fail_detail is not None:
                    if tally is not None:
                        tally.pss_worded_cells += 1
                    if fail_detail > 0 and \
                            evaluator is evaluate_pod_security:
                        evaluator = _masked_evaluator(fail_detail, tally)
                return pod_security_response(
                    rule.name, pod_security, doc, evaluator)
        pctx = self._pctx(self.policies[prog.policy_index], resource)
        return Validator(self.engine, pctx, rule).validate()

    def _host_reason(self, prog: RuleProgram, st: int) -> str:
        """The ledger's reason for a cell of status ``st`` that the host
        phrases: undecided on the device, marked by the context fill
        (a failed load must surface the host's exact error response), or
        decided there and worded here."""
        if st == STATUS_HOST:
            return coverage.REASON_STATUS_HOST
        return _CTX_STATUS_REASON.get(st) or self._message_reason(prog)

    def _message_reason(self, prog: RuleProgram, j: int = -1,
                        fdet_row=None) -> str:
        """The ledger's reason for a cell whose verdict the device
        decided and whose message the host words: the check library
        called directly, or the Validator; and of the Validator's, told
        apart, a FAIL (its ``fdet_row`` given) whose fail detail was
        lost to the per-row budget, in the columns its message is made
        from (column ``j``, or the anyPattern child block)."""
        if self._host_rule[prog][1] is not None:
            return coverage.REASON_PSS_DIRECT
        if fdet_row is not None and FDET_BEYOND_BUDGET in \
                fdet_row[self._detail_columns(prog, j)]:
            return coverage.REASON_FAIL_DETAIL_BUDGET
        return coverage.REASON_UNSYNTHESIZABLE

    def _new_response(self, policy_index: int, resource: dict,
                      now: float,
                      wrapped: Optional[Resource] = None) -> EngineResponse:
        # template-dict fast path: the per-policy header fields are
        # static for the scanner's lifetime, and scans build one
        # response per (resource, policy) pair — instantiating via
        # __new__ + a C-level dict copy of a prebuilt template is ~4x
        # cheaper than copy.copy (which routes through __reduce_ex__)
        from ..engine.api import PolicyResponse
        templates = getattr(self, '_resp_templates', None)
        if templates is None:
            templates = self._resp_templates = {}
        tmpl = templates.get(policy_index)
        if tmpl is None:
            policy, name, namespace, vfa, vfa_overrides = \
                self._policy_header[policy_index]
            pr0 = PolicyResponse()
            pr0.policy_name = name
            pr0.policy_namespace = namespace
            pr0.validation_failure_action = vfa
            pr0.validation_failure_action_overrides = vfa_overrides
            tmpl = (policy, dict(pr0.__dict__))
            templates[policy_index] = tmpl
        policy, pr_dict = tmpl
        r = wrapped if wrapped is not None else Resource(resource)
        pr = PolicyResponse.__new__(PolicyResponse)
        d = dict(pr_dict)
        d['rules'] = []
        d['resource_name'] = r.name
        d['resource_namespace'] = r.namespace
        d['resource_kind'] = r.kind
        d['resource_api_version'] = r.api_version
        d['timestamp'] = int(now)
        pr.__dict__ = d
        resp = EngineResponse.__new__(EngineResponse)
        resp.__dict__ = {'policy': policy, 'patched_resource': resource,
                         'policy_response': pr, 'namespace_labels': {}}
        return resp

    def _host_run(self, policy_index: int, resource: dict) -> EngineResponse:
        policy = self.policies[policy_index]
        factory = getattr(self, '_pctx_factory', None)
        if factory is not None:
            pctx = self._pctx(policy, resource)
            return self.engine.validate(pctx)
        return self.engine.apply_background_checks(
            PolicyContext(policy, new_resource=resource))
