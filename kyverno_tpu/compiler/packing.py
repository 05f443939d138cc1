"""The packed layout of a batch's lanes: one ``[R, W]`` buffer a dtype.

The evaluator takes a batch as five buffers, one a dtype, each lane a
run of columns in its dtype's buffer (``ops/eval.py`` ``pack_batch`` /
``unpack_batch``).  This module is the one definition of that layout,
and of a batch whose lanes are *born* in it: the encoder's arenas
(``compiler/encode.py``) allocate the buffers and hand the encoder
views of them, so that packing such a batch is a hand-over and not a
copy of every lane.  It imports no jax: an encoder worker imports it.
"""

from __future__ import annotations

import math
import operator
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import numpy as np

#: lane name -> (buffer, first column, columns, the lane's shape past
#: the row axis)
Layout = Dict[str, Tuple[str, int, int, Tuple[int, ...]]]
#: lane name -> (dtype, shape)
Signature = Mapping[str, Tuple[np.dtype, Tuple[int, ...]]]


def plan_layout(signature: Signature):
    """Where each lane of ``signature`` lies: per dtype (by its name)
    one buffer, the dtype's lanes in the order of their names, each
    ``prod(shape[1:])`` columns wide.  Returns ``(layout, groups)``;
    ``groups`` lists ``(buffer, dtype, columns, lane names)`` in the
    buffers' order."""
    by_dtype: Dict[str, List[str]] = {}
    for name in sorted(signature):
        by_dtype.setdefault(str(signature[name][0]), []).append(name)
    layout: Layout = {}
    groups = []
    for dt, names in sorted(by_dtype.items()):
        off = 0
        for name in names:
            tail = tuple(signature[name][1][1:])
            width = math.prod(tail)
            layout[name] = (f'pk_{dt}', off, width, tail)
            off += width
        groups.append((f'pk_{dt}', np.dtype(dt), off, names))
    return layout, groups


def unpack_batch(packed: Dict[str, Any], layout: Layout) -> Dict[str, Any]:
    """Each lane of ``layout`` as its run of columns of its buffer,
    reshaped: views of numpy buffers (an arena's lanes), static slices
    of traced ones (the evaluator's)."""
    out: Dict[str, Any] = {}
    for name, (g, off, width, tail) in layout.items():
        buf = packed[g]
        sl = buf[:, off:off + width]
        out[name] = sl.reshape((buf.shape[0],) + tuple(tail))
    return out


class PackedLanes(dict):
    """A batch's lanes by name.  ``owner`` is the :class:`PackedSet`
    whose views they are, where they are (``None``: loose arrays);
    ``pack_batch`` asks it, lane by lane, whether that is still so."""

    __slots__ = ('owner',)

    def __init__(self, *args, owner: Optional['PackedSet'] = None):
        super().__init__(*args)
        self.owner = owner

    def copy(self) -> 'PackedLanes':
        return PackedLanes(self, owner=self.owner)


class PackedSet:
    """The packed buffers of one batch and every lane as a view of
    them: the encoder's lanes (``signature``), and room for the lanes
    that join a batch after the encode (``joining``: name -> (dtype,
    shape past the row axis); ``__match__`` and the admission lanes),
    whose columns every later lane's offset depends on.

    ``allocate([(buffer, shape, dtype), ...])`` gives the buffers'
    memory, all at once and in that order: C-contiguous and zeroed."""

    def __init__(self, signature: Signature,
                 joining: Mapping[str, Tuple[np.dtype, Tuple[int, ...]]],
                 allocate: Callable):
        rows = next(iter(signature.values()))[1][0]
        full = dict(signature)
        for name, (dtype, tail) in joining.items():
            full[name] = (np.dtype(dtype), (rows,) + tuple(tail))
        self.layout, groups = plan_layout(full)
        specs = [(buf, (rows, width), dtype)
                 for buf, dtype, width, _names in groups]
        self.buffers: Dict[str, np.ndarray] = dict(
            zip((spec[0] for spec in specs), allocate(specs)))
        # a run of a row's columns reshapes without a copy
        self.views: Dict[str, np.ndarray] = unpack_batch(self.buffers,
                                                         self.layout)
        self.names = tuple(signature)
        self.joining = tuple(joining)
        self._own = tuple(self.views[name] for name in self.names)

    def lanes(self) -> PackedLanes:
        """The encoder's lanes, in the encoder's order."""
        return PackedLanes(zip(self.names, self._own), owner=self)

    def clear(self) -> None:
        """Zero every lane, the joining lanes' columns too."""
        for buf in self.buffers.values():
            buf.fill(0)

    def takes(self, tensors: Mapping[str, np.ndarray]) -> bool:
        """Whether ``tensors`` is this set: the encoder's lanes these
        very views, and each joining lane present with the shape its
        columns were planned for.  Then the joining lanes are copied
        into their columns, and the buffers are the batch, packed."""
        if len(tensors) != len(self.views):
            return False
        try:
            if not all(map(operator.is_,
                           map(tensors.__getitem__, self.names),
                           self._own)):
                return False
            joined = [(self.views[name], tensors[name])
                      for name in self.joining]
        except KeyError:
            return False
        for view, arr in joined:
            if arr is not view and (arr.dtype != view.dtype or
                                    arr.shape != view.shape):
                return False
        for view, arr in joined:
            if arr is not view:
                view[...] = arr
        return True
