"""Bytes of one mutate dispatch from hand-made lanes, and from the lanes the
program's encoder returns for the defaults pack at capacity 64."""

import numpy as np

import benchlib
import bytes_mutate


def test_lane_bytes_are_every_lanes_shape_times_its_itemsize():
    lanes = {'tag': ((64, 22), np.int8), 'milli': ((64, 22), np.int64),
             'sbytes': ((64, 22, 16), np.uint8), 'valid': ((64,), bool),
             'llen': ((64, 1), np.int32)}
    assert bytes_mutate.lane_bytes(lanes) == \
        64 * 22 * (1 + 8 + 16) + 64 + 64 * 4


def test_output_bytes_are_status_edits_and_reason_per_row_and_rule():
    assert bytes_mutate.output_bytes(64, 7) == 64 * 7 * (1 + 8 + 1)


def test_the_defaults_pack_at_capacity_64():
    from kyverno_tpu.compiler.scan import WARM_POD
    from kyverno_tpu.mutate.encode import encode_mutate_batch
    from kyverno_tpu.mutate.plan import compile_mutate_set
    program = compile_mutate_set(benchlib.load_policies(['mutate-defaults']))
    assert program.device_ok and len(program.programs) == 7
    lanes = encode_mutate_batch([WARM_POD], program, padded_n=64)
    told = bytes_mutate.describe(lanes, len(program.programs))
    sites, width = program.n_sites, lanes['sbytes'].shape[2]
    # per (row, site): tag, istate, milli, milli_ok, slen, the string window;
    # per row: valid and one list's length
    assert told['lane_bytes'] == \
        64 * sites * (1 + 1 + 8 + 1 + 4 + width) + 64 * (1 + 4)
    assert told['output_bytes'] == 64 * 7 * 10
    assert told['bytes'] == told['lane_bytes'] + told['output_bytes']
    assert told['capacity'] == 64
