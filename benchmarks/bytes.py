"""Bytes one dispatch of the evaluator has to move, from the packed layout.

The evaluator is integer gather/compare, so its roofline is HBM bytes: every
packed argument byte is read once and every output byte written once.  That
is the least the chip could do, and what ``<kernel>_roofline`` divides by the
peak.  The layout is the program's (``ops/eval.py`` ``pack_batch``): each lane
is ``(buffer, offset, width, tail)``, one flat ``[capacity, W]`` buffer per
dtype named ``pk_<dtype>``.  The outputs are ``out8`` = statuses and details
of the unique status trees (and the admission match columns when admission
lanes ride along) and ``out32`` = the compacted fail-detail cells, columns
and values (``evaluate_packed``).
"""

from __future__ import annotations

import numpy as np

from benchlib import say


def row_widths(layout: dict) -> dict:
    """Elements per row of each packed buffer."""
    widths = {}
    for _lane, (buf, off, width, _tail) in layout.items():
        widths[buf] = max(widths.get(buf, 0), off + width)
    return widths


def argument_bytes(layout: dict, capacity: int) -> int:
    return sum(capacity * w * np.dtype(buf[len('pk_'):]).itemsize
               for buf, w in row_widths(layout).items())


def output_bytes(capacity: int, n_uniq: int, n_cols_u: int, n_adm: int,
                 fdet_k: int) -> int:
    out8 = capacity * (2 * n_uniq + n_adm)
    out32 = capacity * 2 * min(fdet_k, n_cols_u) * 4
    return out8 + out32


def describe(layout: dict, capacity: int, n_uniq: int, n_cols_u: int,
             n_adm: int, records: list, fdet_k: int = 32) -> dict:
    """The bytes of one dispatch at ``capacity``, with XLA's own estimate for
    the same executable printed beside it as a cross-check."""
    args = argument_bytes(layout, capacity)
    outs = output_bytes(capacity, n_uniq, n_cols_u, n_adm, fdet_k)
    xla = next((r['bytes_accessed'] for r in records
                if r['capacity'] == capacity), None)
    say(f'bytes: one capacity-{capacity} dispatch moves {args} argument + '
        f'{outs} output bytes ({args / capacity:.0f} B/row packed); XLA\'s '
        f'bytes_accessed estimate for it: {xla}')
    return {'capacity': capacity, 'argument_bytes': args,
            'output_bytes': outs, 'bytes': args + outs,
            'xla_bytes_accessed': xla}
