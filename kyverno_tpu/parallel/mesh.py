"""Device mesh + sharded evaluation step.

The scaling model (SURVEY.md §2.6): policy evaluation is embarrassingly
data-parallel over the resource batch axis — the TPU-native equivalent of
the reference's horizontally replicated webhook pods. The compiled check
program is a trace-time constant (replicated), the batch is sharded over a
1-D ``data`` mesh axis, and the only cross-chip communication is the
verdict-summary reduction (``psum``), which rides ICI.

Multi-host: the same code runs under ``jax.distributed`` — the mesh spans
all slices and GSPMD inserts DCN collectives for the summary only.
"""

from __future__ import annotations

import time
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compiler.ir import CompiledPolicySet


def make_mesh(devices: Optional[List] = None, axis: str = 'data') -> Mesh:
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def pad_to_multiple(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def build_sharded_evaluator(cps: CompiledPolicySet, mesh: Mesh,
                            axis: str = 'data'):
    """A jitted, mesh-sharded evaluation step.

    Returns ``(statuses [R, P] sharded over R, summary [P, 3] replicated)``
    where summary counts pass/fail/skip per rule across all shards — the
    all-reduce that replaces the reference's report aggregation fan-in
    (reference: pkg/controllers/report/aggregate/controller.go).
    """
    from ..aotcache import enable_persistent_compilation_cache
    from ..compiler.ir import N_STATUS_CODES
    from ..ops.eval import build_evaluator, unpack_batch
    # sharded executables embed the mesh's device assignment, so the
    # AOT executable store cannot persist them; the XLA persistent
    # compilation cache (keyed on the computation fingerprint) still
    # skips the backend compile for a fresh process on the same mesh
    enable_persistent_compilation_cache()
    evaluator = build_evaluator(cps)
    n_codes = N_STATUS_CODES

    def step(packed: Dict[str, jnp.ndarray]):
        t = unpack_batch(packed, evaluator.layout_holder['layout'])
        # the encoder's row-validity lane: canonical-capacity padding
        # rows must not count in the cross-shard verdict summary
        rowmask = t.pop('__rowvalid__', None)
        # fdet is dropped here: the distributed summary path never
        # synthesizes messages, and leaving it out of the jit outputs
        # lets XLA DCE the whole fail-site computation
        statuses, details, _fdet = evaluator.raw(t)
        # per-rule verdict histogram over the status codes; with GSPMD
        # the partial sums are psum-reduced over ICI automatically
        one_hot = jax.nn.one_hot(statuses, n_codes, dtype=jnp.int32)
        if rowmask is not None:
            one_hot = one_hot * (rowmask != 0).astype(
                jnp.int32)[:, None, None]
        summary = jnp.sum(one_hot, axis=0)
        return statuses, details, summary

    out_shardings = (NamedSharding(mesh, P(axis)),
                     NamedSharding(mesh, P(axis)),
                     NamedSharding(mesh, P()))
    # input shardings propagate from the device_put placement in
    # shard_tensors; only outputs are constrained here
    jitted = jax.jit(step, out_shardings=out_shardings)
    # signatures this sharded jit has traced, mirroring the evaluator's
    # own hit/miss telemetry so the mesh path's compiles show up in the
    # kyverno_tpu_compile_cache counters too
    jit_seen: set = set()

    def run(tensors, layout):
        from ..observability import device as devtel
        # layout_holder is shared with the single-device evaluator's
        # traces — take its compile lock so a concurrent call cannot
        # bake this layout into the wrong executable
        with evaluator.compile_lock:
            evaluator.layout_holder['layout'] = layout
            with jax.enable_x64(True):
                if devtel.enabled():
                    sig = tuple((k, str(v.dtype), tuple(v.shape))
                                for k, v in sorted(tensors.items()))
                    if sig not in jit_seen:
                        jit_seen.add(sig)
                        devtel.record_cache('miss')
                        with devtel.stage('compile') as st:
                            st.set_attribute('cache', 'miss')
                            st.set_attribute('mesh', True)
                            return jitted(tensors)
                    devtel.record_cache('hit')
                return jitted(tensors)

    run.jitted = jitted
    run.evaluator = evaluator
    return run


def shard_tensors(tensors: Dict[str, np.ndarray], mesh: Mesh,
                  axis: str = 'data') -> Dict[str, Any]:
    """Place batch tensors with the leading axis sharded over the mesh."""
    from ..ops.eval import shard_batch
    return shard_batch(tensors, mesh, axis)


# (cps id, mesh, axis) -> sharded evaluator. LRU with single-entry
# eviction; the cps entry keeps a strong reference to the keyed object so
# ids cannot be recycled while cached.
from collections import OrderedDict

_SHARDED_CACHE: 'OrderedDict[Tuple[int, Mesh, str], Tuple[CompiledPolicySet, Any]]' = OrderedDict()
_SHARDED_CACHE_MAX = 16


def _cached_sharded_evaluator(cps: CompiledPolicySet, mesh: Mesh, axis: str):
    key = (id(cps), mesh, axis)
    hit = _SHARDED_CACHE.get(key)
    if hit is not None and hit[0] is cps:
        _SHARDED_CACHE.move_to_end(key)
        return hit[1]
    step = build_sharded_evaluator(cps, mesh, axis)
    while len(_SHARDED_CACHE) >= _SHARDED_CACHE_MAX:
        _SHARDED_CACHE.popitem(last=False)
    _SHARDED_CACHE[key] = (cps, step)
    return step


def shard_wait_splits(array) -> List[float]:
    """Per-shard readback-wait splits: block on each addressable shard
    of a just-dispatched sharded array in batch-axis order and time
    each wait separately.  The split attributes wall to the shard the
    host was actually waiting on (with all shards in flight, the shard
    you block longest on IS the straggler); the ``mesh_shard`` fault
    site is checked inside each timed split, so an injected
    ``delay_ms`` clause inflates exactly one shard's wall."""
    from .. import faults

    def _order(sh):
        try:
            return sh.index[0].start or 0
        except Exception:  # noqa: BLE001 - fall back to device ids
            return getattr(sh.device, 'id', 0)

    walls: List[float] = []
    for sh in sorted(array.addressable_shards, key=_order):
        t0 = time.perf_counter()
        faults.check(faults.SITE_MESH_SHARD)
        sh.data.block_until_ready()
        walls.append(time.perf_counter() - t0)
    return walls


def record_sharded_dispatch(mesh: Mesh, axis: str, n_rows: int,
                            padded_rows: int,
                            shard_walls: List[float],
                            collective_s: float,
                            step_wall: Optional[float] = None,
                            span=None):
    """Publish one sharded dispatch's telemetry: per-shard device-eval
    walls, skew verdict, collective wall and padding waste — on the
    fleet-scoped mesh metrics (KTPU509 holds these write sites to
    their shard/mesh identity labels) and the ``kyverno/mesh/step``
    span when the caller passes one.  Returns the skew verdict."""
    from ..observability import fleet
    n_dev = mesh.devices.size
    mesh_key = f'{axis}{n_dev}'
    devices = [str(d) for d in mesh.devices.flat]
    verdict = fleet.record_step(mesh_key, shard_walls, devices)
    registry = fleet.registry()
    if registry is not None:
        for i, wall_s in enumerate(shard_walls):
            registry.observe(fleet.MESH_STEP_DURATION, wall_s,
                             shard=str(i))
        if step_wall is not None:
            registry.observe(fleet.MESH_STEP_DURATION, step_wall,
                             shard='all')
        # skew describes the mesh step in flight — reset-on-close so a
        # drained host doesn't export its last imbalance forever
        registry.mark_reset_on_close(fleet.MESH_SHARD_SKEW)
        registry.set_gauge(fleet.MESH_SHARD_SKEW, verdict['skew'],
                           mesh=mesh_key)
        registry.inc(fleet.MESH_COLLECTIVE_SECONDS, collective_s,
                     mesh=mesh_key)
        registry.inc(fleet.MESH_PADDING_ROWS,
                     float(max(0, padded_rows - n_rows)), mesh=mesh_key)
    if span is not None:
        per = padded_rows // max(1, len(shard_walls))
        occupancy = [min(max(n_rows - i * per, 0), per)
                     for i in range(len(shard_walls))]
        span.set_attribute('mesh', mesh_key)
        span.set_attribute('rows', n_rows)
        span.set_attribute('padding_rows', max(0, padded_rows - n_rows))
        span.set_attribute('shard_rows', ','.join(map(str, occupancy)))
        span.set_attribute('skew', verdict['skew'])
        span.set_attribute('slow_shard', verdict['slow_shard'])
        span.set_attribute('collective_s', round(collective_s, 6))
        if verdict.get('sustained'):
            span.set_attribute('bound_by', 'straggler')
    return verdict


def distributed_scan_step(cps: CompiledPolicySet, mesh: Mesh,
                          resources: List[dict], axis: str = 'data'):
    """Encode + evaluate a batch across the mesh; returns (statuses, summary).

    The batch pads to the canonical capacity (``compiler/shapes.py``),
    rounded up to a multiple of the mesh size so every shard gets
    identical shapes; the encoder's ``__rowvalid__`` lane keeps the
    padding rows out of the verdict summary.

    With the fleet observatory armed (``observability/fleet.py``;
    ``KTPU_FLEET=0`` pins it off) every dispatch additionally records
    per-shard readback-wait splits, the collective wall and padding
    waste under a ``kyverno/mesh/step`` span — the timing never
    touches the computed values, so output stays bit-identical.
    """
    from ..compiler.encode import encode_batch
    from ..compiler.shapes import canonical_capacity
    from ..observability import fleet
    fl = fleet.enabled()
    n = len(resources)
    n_dev = mesh.devices.size
    padded = pad_to_multiple(
        max(canonical_capacity(max(n, n_dev)), n), n_dev)
    span_cm = nullcontext()
    if fl:
        from ..observability import tracing
        span_cm = tracing.start_span('kyverno/mesh/step')
    with span_cm as span:
        t_start = time.perf_counter() if fl else 0.0
        if cps.ctx_values:
            # the value lanes join a batch in a scanner, which has the
            # engine's loader (compiler/context_lanes.py); this step has
            # only the documents
            raise ValueError('conditions of this policy set read context '
                             'values: scan it through a BatchScanner')
        batch = encode_batch(resources, cps, padded_n=padded)
        raw = batch.tensors()
        tensors, layout = shard_tensors(raw, mesh, axis)
        step = _cached_sharded_evaluator(cps, mesh, axis)
        statuses, details, summary = step(tensors, layout)
        shard_walls = None
        t_coll = 0.0
        if fl:
            shard_walls = shard_wait_splits(statuses)
            t_coll = time.perf_counter()
        if jax.process_count() > 1:
            # multi-host: each process only holds its local shards of
            # the batch axis — gather the full status matrix across
            # hosts (the psum'd summary is already replicated on every
            # device)
            from jax.experimental import multihost_utils
            statuses = multihost_utils.process_allgather(statuses,
                                                         tiled=True)
        collective_s = 0.0
        if fl:
            # the psum'd summary readback (plus the multi-host
            # allgather above) is the step's cross-shard collective
            summary.block_until_ready()
            collective_s = time.perf_counter() - t_coll
        statuses_np = np.asarray(statuses)[:n]
        summary_np = np.asarray(summary)
        if fl:
            record_sharded_dispatch(
                mesh, axis, n, padded, shard_walls, collective_s,
                step_wall=time.perf_counter() - t_start, span=span)
    from ..observability import coverage
    if coverage.enabled():
        # the padded rows are already masked out of the summary, so the
        # STATUS_HOST column IS the host-replay row count of this step
        from ..compiler.ir import STATUS_HOST
        total = int(summary_np.sum())
        host = int(summary_np[:, STATUS_HOST].sum())
        coverage.record_scan(total - host, host)
    return statuses_np, summary_np
