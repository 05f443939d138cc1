"""Bytes one dispatch of the mutate kernel has to move, from its lanes.

The kernel (``kyverno_tpu/mutate/kernel.py``, ``jit_mutate_eval`` in a trace)
is element-wise compares and a few masked reduces over the lanes
``mutate/encode.py`` ``encode_mutate_batch`` returns at the dispatch's
capacity, so its roofline is HBM bytes: every lane byte is read once and
every byte of its three outputs written once.  That is the least the chip
could do, and what ``mutate_kernel_roofline`` divides by the peak.  The
outputs are per (row, rule): ``status`` i8, ``edits`` i64, ``reason`` i8.  The
patch constants the kernel compares with are compiled into the program and
are not counted.
"""

from __future__ import annotations

import numpy as np

from benchlib import say

#: itemsize of the three outputs, per (row, rule)
OUTPUTS = {'status': np.dtype(np.int8), 'edits': np.dtype(np.int64),
           'reason': np.dtype(np.int8)}


def lane_bytes(lanes: dict) -> int:
    """``lanes``: name → ``(shape, dtype)``, the whole batch's."""
    return sum(int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
               for shape, dtype in lanes.values())


def output_bytes(capacity: int, n_rules: int) -> int:
    return capacity * n_rules * sum(d.itemsize for d in OUTPUTS.values())


def describe(lanes: dict, n_rules: int) -> dict:
    """The bytes of one dispatch of ``lanes`` (name → array, as the encoder
    returns them at the dispatch's capacity)."""
    shapes = {name: (a.shape, a.dtype) for name, a in lanes.items()}
    capacity = len(next(iter(lanes.values())))
    args, outs = lane_bytes(shapes), output_bytes(capacity, n_rules)
    say(f'bytes: one capacity-{capacity} mutate dispatch moves {args} lane '
        f'+ {outs} output bytes ({args / capacity:.0f} B/row of lanes, '
        f'{n_rules} rules)')
    return {'capacity': capacity, 'lane_bytes': args, 'output_bytes': outs,
            'bytes': args + outs}
