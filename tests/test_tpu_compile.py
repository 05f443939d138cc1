"""The device path's programs compile for the TPU v5e, without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  Nothing runs, so
these say nothing about results or times: they refuse what the chip's
compiler would refuse — a program that does not fit, an op it cannot lower —
and they print what each program needs.  What has to compile is what
``chip_smoke.py`` runs: the jitted evaluator for the committed pack at both
canonical capacities, the mutate kernel, and the sharded step across four
chips.  All of them carry i64 lanes, which the chip only emulates, so the
memory analysis is looked at, not only success.

The topology is described inside a fixture of this file, after a test has
started: only one process may hold the TPU's library, and every xdist worker
imports every test file.
"""

import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: one chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def pack():
    """The smoke's pack and one encoded admission-capacity batch of it:
    ``(cps, evaluator, packed, layout)``.  The packed layout does not depend on the
    capacity, so other capacities only change the leading dimension."""
    import benchlib
    from kyverno_tpu.compiler import admission
    from kyverno_tpu.compiler.compile import compile_policies
    from kyverno_tpu.compiler.encode import encode_batch
    from kyverno_tpu.compiler.scan import WARM_POD
    from kyverno_tpu.ops.eval import build_evaluator, pack_batch
    cps = compile_policies(
        benchlib.load_policies(['pss', 'pack', 'config4']))
    assert len(cps.programs) == 15 and not cps.host_rules
    evaluator = build_evaluator(cps)
    cap = 64
    tensors = encode_batch([WARM_POD], cps, padded_n=cap).tensors()
    # what every one-chip dispatch ships beside the lanes (scan.py
    # stage_h2d): the unique-space match plane and the admission lanes
    tensors['__match__'] = np.zeros((cap, evaluator.n_uniq), np.uint8)
    if evaluator.adm_table is not None:
        tensors.update(admission.zero_lanes(evaluator.adm_table, cap))
    packed, layout = pack_batch(tensors)
    return cps, evaluator, packed, layout


def _shapes(packed, capacity, sharding):
    import jax
    return {k: jax.ShapeDtypeStruct((capacity,) + v.shape[1:], v.dtype,
                                    sharding=sharding)
            for k, v in packed.items()}


def _report(what, compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
             mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    print(f'{what}: arguments={mem.argument_size_in_bytes} '
          f'outputs={mem.output_size_in_bytes} '
          f'temporaries={mem.temp_size_in_bytes} '
          f'code={mem.generated_code_size_in_bytes}')
    assert total < V5E_HBM_BYTES, f'{what} needs {total} bytes per chip'
    return mem


@pytest.mark.parametrize('capacity', [64, 16384])
def test_evaluator_compiles_for_one_chip(pack, one_chip,
                                         no_persistent_cache, capacity):
    import jax
    _cps, evaluator, packed, layout = pack
    assert any(v.dtype == np.int64 for v in packed.values()), \
        'the pack no longer carries the i64 lanes this test is about'
    with evaluator.compile_lock, jax.enable_x64(True):
        evaluator.layout_holder['layout'] = layout
        compiled = evaluator.jitted.lower(
            _shapes(packed, capacity, one_chip)).compile()
    mem = _report(f'evaluator@{capacity}', compiled)
    rows_bytes = sum(int(np.prod(v.shape[1:], dtype=np.int64)) *
                     v.dtype.itemsize for v in packed.values())
    assert mem.argument_size_in_bytes >= capacity * rows_bytes


def test_context_evaluator_compiles_for_one_chip(one_chip,
                                                 no_persistent_cache):
    """The cell ``bgscan_context_100k``'s set: the committed packs and
    ``packs/context.yaml``, whose eight context programs compare gather
    lanes with the value lanes that join in the parent
    (``compiler/context_lanes.py``), at the bulk capacity."""
    import jax
    import benchlib
    from kyverno_tpu.compiler.compile import compile_policies
    from kyverno_tpu.compiler.context_lanes import ContextLanes
    from kyverno_tpu.compiler.encode import encode_batch
    from kyverno_tpu.compiler.scan import WARM_POD
    from kyverno_tpu.ops.eval import build_evaluator, pack_batch
    cps = compile_policies(
        benchlib.load_policies(['pss', 'pack', 'config4', 'context']))
    assert len(cps.programs) == 23 and not cps.host_rules
    evaluator = build_evaluator(cps)
    tensors = encode_batch([WARM_POD], cps, padded_n=64).tensors()
    tensors['__match__'] = np.zeros((64, evaluator.n_uniq), np.uint8)
    lanes = ContextLanes(cps).zero_lanes(64)
    assert len(lanes) == 8
    tensors.update(lanes)
    packed, layout = pack_batch(tensors)
    with evaluator.compile_lock, jax.enable_x64(True):
        evaluator.layout_holder['layout'] = layout
        compiled = evaluator.jitted.lower(
            _shapes(packed, 16384, one_chip)).compile()
    _report('context evaluator@16384', compiled)


def test_mutate_kernel_compiles_for_one_chip(one_chip,
                                             no_persistent_cache):
    import jax
    import benchlib
    from kyverno_tpu.compiler.scan import WARM_POD
    from kyverno_tpu.mutate.encode import (encode_mutate_batch,
                                           string_window)
    from kyverno_tpu.mutate.kernel import MutateKernel
    from kyverno_tpu.mutate.plan import compile_mutate_set
    program = compile_mutate_set(
        benchlib.load_policies(['mutate-defaults']))
    assert program.device_ok and len(program.programs) == 7
    assert program.lists == [('spec', 'containers')]
    kernel = MutateKernel(program)
    lanes = encode_mutate_batch([WARM_POD], program, padded_n=64,
                                width=string_window(program))
    assert lanes['milli'].dtype == np.int64
    for capacity in (64, 16384):
        with jax.enable_x64(True):
            compiled = jax.jit(kernel.mutate_eval).lower(
                _shapes(lanes, capacity, one_chip)).compile()
        _report(f'mutate@{capacity}', compiled)


def test_sharded_step_compiles_for_the_2x2_mesh(pack, topo,
                                                no_persistent_cache):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from kyverno_tpu.compiler.encode import encode_batch
    from kyverno_tpu.compiler.scan import WARM_POD
    from kyverno_tpu.ops.eval import pack_batch
    from kyverno_tpu.parallel.mesh import (build_sharded_evaluator,
                                           make_mesh)
    cps = pack[0]
    assert len(topo.devices) == 4
    mesh = make_mesh(list(topo.devices))
    step = build_sharded_evaluator(cps, mesh)
    # the mesh step ships the lanes and the row-validity lane only
    # (parallel/mesh.py distributed_scan_step)
    packed, layout = pack_batch(
        encode_batch([WARM_POD], cps, padded_n=64).tensors())
    capacity = 16384
    rows = NamedSharding(mesh, P('data', None))
    with step.evaluator.compile_lock, jax.enable_x64(True):
        step.evaluator.layout_holder['layout'] = layout
        compiled = step.jitted.lower(
            _shapes(packed, capacity, rows)).compile()
    mem = _report(f'sharded step@{capacity} over 4 chips', compiled)
    # memory_analysis counts one device: a quarter of the rows each
    whole = sum(capacity * int(np.prod(v.shape[1:], dtype=np.int64)) *
                v.dtype.itemsize for v in packed.values())
    assert mem.argument_size_in_bytes < whole / 2
    statuses, details, summary = compiled.output_shardings
    assert statuses.spec == P('data') and details.spec == P('data')
    assert summary.is_fully_replicated
    # the verdict summary is the step's one cross-chip reduction (the
    # v5e compiler gathers the per-chip partial sums and adds them)
    assert re.search(r'\ball-(reduce|gather)', compiled.as_text())
