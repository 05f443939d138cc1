"""Context values as lanes: what a rule's ``context`` loaded, resolved on
the host and joined to a chunk in the parent process.

A condition whose ``value`` is one ``{{ expr }}`` over the rule's own
configMap / apiCall entries compiles to a mode-C check
(``compiler/ir.py`` ``CondCheck.ctx_value``): the device compares the
key's gather lanes with per-row value lanes.  This module fills those
lanes.  An encoder worker has no cluster client and must not get one,
so the lanes join the batch in the parent, as ``__match__`` and the
admission lanes do.

Per chunk and per group of programs that share a context (autogen's
copies of one rule), the rows the group matched are keyed on the values
of the rule's ``context_inputs`` (500 namespaces, not 100,000 rows); each
distinct tuple is resolved once a scan pass by the engine's own loader
and variable substitution, so that a JSON-array string, a scalar, a
missing key and a ``|| ''`` default mean what they mean to
``kyverno_tpu/engine``.  What comes back is

* the value lanes of the chunk: ``cv<i>_len`` ``[R, CTX_WIDTH + 1]``
  int32 and ``cv<i>_head`` ``[R, CTX_WIDTH + 1, CTX_HEAD]`` uint8 — slot
  0 a string value itself, slots 1.. its elements, each the length of
  its string form and that form's first ``CTX_HEAD`` bytes, −1 for no
  element — or ``cv<i>_milli`` ``[R]`` int64 for a numeric comparison;
* the load outcomes as a mask: ``(program, rows, status)`` for every cell
  whose context load failed (``STATUS_CTX_LOAD``) or whose value cannot
  ride the lanes (unresolved, wider than ``CTX_WIDTH``, outside the exact
  zone).  The scanner writes these over the device's statuses when the
  chunk comes back and assembly materializes exactly those cells on the
  host.  A context program whose values feed nothing gets the mask alone.

No jax here.
"""

from __future__ import annotations

import json
import math
import re
from fractions import Fraction
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .ir import (CTX_HEAD, CTX_WIDTH, STATUS_CTX_LOAD, STATUS_CTX_SHAPE,
                 STATUS_CTX_UNRESOLVED, STATUS_CTX_WIDE, CompiledPolicySet,
                 CtxValue)

_SLOTS = CTX_WIDTH + 1
_MISSING = ('\x00missing',)
_DOTTED = re.compile(
    r'^request\.object((?:\.(?:[A-Za-z_][A-Za-z0-9_]*|"[^"\\]+"))+)$')


def lane_signature(cps: CompiledPolicySet) -> Dict[str, Tuple[Any, tuple]]:
    """``{lane: (dtype, shape past the row axis)}`` of the value lanes of
    ``cps``; empty for a set without a mode-C check."""
    out: Dict[str, Tuple[Any, tuple]] = {}
    for i, v in enumerate(cps.ctx_values):
        if v.family == 'num':
            out[f'cv{i}_milli'] = (np.int64, ())
        else:
            out[f'cv{i}_len'] = (np.int32, (_SLOTS,))
            out[f'cv{i}_head'] = (np.uint8, (_SLOTS, CTX_HEAD))
    return out


# -- one resolved value -> its lanes, or the reason it cannot ride them -----

def _sprint(v: Any) -> Optional[str]:
    from ..engine.operators import _sprint as host_sprint
    if isinstance(v, str):
        return v
    if isinstance(v, (bool, int, float)):
        return host_sprint(v)
    return None


def _plain_string(v: str) -> bool:
    """Equal to a key only as the same bytes: neither side of
    ``operators._equal`` reads it as a number, a duration or a
    quantity."""
    from ..engine.operators import _try_duration, _try_quantity
    if _try_duration(v) is not None or _try_quantity(v) is not None:
        return False
    try:
        float(v)
    except ValueError:
        return True
    return False


def encode_value(value: Any, family: str):
    """``(status, lens, heads, milli)``: status 0 and the lanes' rows, or
    the ``STATUS_CTX_*`` code under which the host takes the cell."""
    from ..engine import pattern as leaf_pattern
    from ..engine.operators import _try_duration, _value_as_string_list
    lens = np.full(_SLOTS, -1, np.int32)
    heads = np.zeros((_SLOTS, CTX_HEAD), np.uint8)
    milli = 0
    if family != 'num':
        strings: List[Optional[str]] = [None]
        if isinstance(value, str):
            strings[0] = value
            if family == 'eq' and not _plain_string(value):
                return STATUS_CTX_SHAPE, None, None, 0
            if family == 'in':
                if leaf_pattern.get_operator_from_string_pattern(value) \
                        == leaf_pattern.OP_IN_RANGE:
                    return STATUS_CTX_SHAPE, None, None, 0
                arr = _value_as_string_list(value)
                strings += arr if arr is not None else [value]
        elif isinstance(value, list) and family == 'in':
            for x in value:
                s = _sprint(x)
                if s is None:
                    return STATUS_CTX_SHAPE, None, None, 0
                strings.append(s)
        else:
            # a number, a bool, null or a map where a string or a list
            # is wanted: every host path has its own answer for it
            return STATUS_CTX_SHAPE, None, None, 0
        if any(s is not None and ('*' in s or '?' in s) for s in strings):
            return STATUS_CTX_SHAPE, None, None, 0
        if len(strings) > _SLOTS:
            return STATUS_CTX_WIDE, None, None, 0
        for k, s in enumerate(strings):
            if s is None:
                continue
            b = s.encode('utf-8')
            lens[k] = len(b)
            head = b[:CTX_HEAD]
            heads[k, :len(head)] = np.frombuffer(head, np.uint8)
    else:
        if isinstance(value, bool) or \
                not isinstance(value, (int, float, str)):
            return STATUS_CTX_SHAPE, None, None, 0
        if isinstance(value, str) and _try_duration(value) is not None:
            return STATUS_CTX_SHAPE, None, None, 0
        try:
            if not math.isfinite(float(value)):
                return STATUS_CTX_SHAPE, None, None, 0
            frac = Fraction(value.strip() if isinstance(value, str)
                            else repr(value)) * 1000
        except (ValueError, OverflowError):
            return STATUS_CTX_SHAPE, None, None, 0
        if frac.denominator != 1 or abs(frac.numerator) > (1 << 53):
            return STATUS_CTX_SHAPE, None, None, 0
        milli = int(frac)
    return 0, lens, heads, milli


# -- the resolver -----------------------------------------------------------

class _Group:
    """The programs that share one context, its inputs and its values."""

    __slots__ = ('gid', 'spec', 'js', 'values', 'inputs', 'walkers',
                 'policy_index', 'policy_name', 'rule_name')

    def __init__(self, gid: int, prog):
        self.gid = gid
        self.spec = list(prog.context_spec)
        self.js: List[int] = []
        self.values: List[Tuple[int, CtxValue]] = []
        self.inputs = prog.context_inputs
        self.walkers = None if self.inputs is None else \
            [_input_walker(expr) for expr in self.inputs]
        self.policy_index = prog.policy_index
        self.policy_name = prog.policy_name
        self.rule_name = prog.rule_name


def _hashable(v: Any):
    """A string as it is, anything else with its type: two values that
    the engine would print differently (1, true, "1", the text of a map
    and the map) never make the same key."""
    return v if type(v) is str or v is _MISSING \
        else (type(v).__name__, repr(v))


def _input_walker(expr: str):
    """``doc -> hashable`` for one ``request.object``-rooted input; raises
    where the expression does (that row is then resolved on its own)."""
    m = _DOTTED.match(expr)
    if m:
        keys = [k.strip('"') for k in
                re.findall(r'\.([A-Za-z_][A-Za-z0-9_]*|"[^"\\]+")',
                           m.group(1))]

        def walk(doc):
            cur = doc
            for k in keys:
                if not isinstance(cur, dict):
                    return _MISSING
                cur = cur.get(k, _MISSING)
            return _hashable(cur)
        return walk
    from ..engine.jmespath import compile as jp_compile
    compiled = jp_compile(expr)

    def search(doc):
        return _hashable(compiled.search({'request': {'object': doc}}))
    return search


def _row_key(walkers, doc: dict) -> Optional[tuple]:
    try:
        return tuple(w(doc) for w in walkers)
    except Exception:  # noqa: BLE001 - no key: the row is resolved alone
        return None


class ContextLanes:
    """The context side of one scanner: groups, the per-pass memo, the
    fill of a chunk."""

    def __init__(self, cps: CompiledPolicySet):
        self.cps = cps
        self.signature = lane_signature(cps)
        groups: Dict[tuple, _Group] = {}
        for j, prog in enumerate(cps.programs):
            if prog.context_spec is None:
                continue
            key = (json.dumps(prog.context_spec, sort_keys=True),
                   prog.context_inputs, prog.ctx_values)
            g = groups.get(key)
            if g is None:
                g = groups[key] = _Group(len(groups), prog)
                g.values = [(cps.ctx_value_index[v], v)
                            for v in prog.ctx_values]
            g.js.append(j)
        self.groups = list(groups.values())
        self._memo: Dict[tuple, tuple] = {}

    def __bool__(self) -> bool:
        return bool(self.groups)

    def begin_pass(self) -> None:
        """Outcomes are kept within one scan pass only: the host engine
        loads for every evaluation, so staleness must not outlive a
        pass."""
        self._memo = {}

    def zero_lanes(self, padded: int) -> Dict[str, np.ndarray]:
        """The value lanes of a batch that reads none of them (a warm-up
        dispatch): the executable's signature is the set's, not the
        traffic's."""
        out = {}
        for name, (dtype, tail) in self.signature.items():
            out[name] = np.full((padded,) + tail, -1, dtype) \
                if name.endswith('_len') else \
                np.zeros((padded,) + tail, dtype)
        return out

    def fill(self, resources: List[dict], cm: Optional[np.ndarray],
             padded: int, scanner, memo: bool = True):
        """``(lanes, overrides)`` for one chunk: the value lanes at
        ``padded`` rows, and ``[(program, rows, status)]`` for the cells
        the host takes.  ``cm`` is the chunk's match mask (None: every
        row counts for every program)."""
        from ..observability import device as devtel
        lanes = self.zero_lanes(padded)
        marks: Dict[Tuple[int, int], List[np.ndarray]] = {}
        lookups = loads_ok = loads_failed = 0
        n = len(resources)
        row_keys: Dict[tuple, list] = {}
        for g in self.groups:
            if cm is not None:
                hit = cm[:n, g.js]
                rows = np.flatnonzero(hit.any(axis=1))
            else:
                hit = None
                rows = np.arange(n)
            if rows.size == 0:
                continue
            lookups += int(rows.size)
            by_key: Dict[Any, List[int]] = {}
            alone: List[int] = []
            if not memo or g.walkers is None:
                alone = rows.tolist()
            elif not g.walkers:
                by_key[()] = rows.tolist()  # one outcome for every row
            else:
                # the rows' keys, read once a chunk for all the groups
                # that have the same inputs (None: resolved alone)
                keys = row_keys.get(g.inputs)
                if keys is None:
                    keys = row_keys[g.inputs] = [
                        _row_key(g.walkers, doc) for doc in resources]
                for r in rows.tolist():
                    if keys[r] is None:
                        alone.append(r)
                    else:
                        by_key.setdefault(keys[r], []).append(r)
            todo = [(key, idxs) for key, idxs in by_key.items()] + \
                [(None, [r]) for r in alone]
            for key, idxs in todo:
                outcome = self._memo.get((g.gid, key)) \
                    if key is not None else None
                if outcome is None:
                    outcome = self._resolve(g, resources[idxs[0]], scanner)
                    if outcome[0]:
                        loads_ok += 1
                    else:
                        loads_failed += 1
                    if key is not None:
                        self._memo[(g.gid, key)] = outcome
                ok, values, _digest = outcome
                ix = np.asarray(idxs, np.intp)
                if not ok:
                    for c, j in enumerate(g.js):
                        self._mark(marks, j, STATUS_CTX_LOAD, ix, hit, c)
                    continue
                for (vi, v), (st, lens, heads, milli) in \
                        zip(g.values, values):
                    if st:
                        for c, j in enumerate(g.js):
                            self._mark(marks, j, st, ix, hit, c)
                    elif v.family == 'num':
                        lanes[f'cv{vi}_milli'][ix] = milli
                    else:
                        lanes[f'cv{vi}_len'][ix] = lens
                        lanes[f'cv{vi}_head'][ix] = heads
        devtel.record_context(lookups, loads_ok, loads_failed)
        overrides = [(j, np.concatenate(parts), st)
                     for (j, st), parts in marks.items()]
        return lanes, overrides

    @staticmethod
    def _mark(marks, j: int, status: int, ix: np.ndarray, hit,
              col: int) -> None:
        """Mark the rows of ``ix`` that program ``j`` matched."""
        if hit is not None:
            ix = ix[hit[ix, col]]
        if ix.size:
            marks.setdefault((j, status), []).append(ix)

    def _resolve(self, g: _Group, doc: dict, scanner):
        """One load and one substitution per value, the host engine's
        way (reference: pkg/engine/jsonContext.go:126 LoadContext):
        ``(loaded, [(status, lens, heads, milli), ...], digest)``, the
        digest what was read, as text."""
        from ..engine import variables as vars_mod
        pctx = scanner._pctx(scanner.policies[g.policy_index], doc)
        ctx = pctx.json_context
        ctx.checkpoint()
        try:
            try:
                scanner.engine.context_loader.load(
                    list(g.spec), ctx, policy_name=g.policy_name,
                    rule_name=g.rule_name)
            except Exception as e:  # noqa: BLE001 - exact failure via host path
                return False, (), (False, str(e))
            values = []
            seen = [True]
            for _vi, v in g.values:
                try:
                    resolved = vars_mod.substitute_all(ctx, v.value)
                except Exception as e:  # noqa: BLE001 - the host words it
                    values.append((STATUS_CTX_UNRESOLVED, None, None, 0))
                    seen.append(str(e))
                    continue
                values.append(encode_value(resolved, v.family))
                seen.append(repr(resolved))
            return True, tuple(values), tuple(seen)
        finally:
            ctx.restore()

    def row_digest(self, doc: dict, scanner) -> Optional[tuple]:
        """What one resource's rules would read of their contexts now: per
        group the load's outcome and the values it resolved, from the
        pass's memo.  The reports controller keeps it beside a scanned
        row, so that a verdict does not outlive the ConfigMap it read;
        None where a context's outcome is no function of its inputs."""
        out = []
        for g in self.groups:
            if g.walkers is None:
                return None
            key = _row_key(g.walkers, doc)
            if key is None:
                return None
            key = (g.gid, key)
            outcome = self._memo.get(key)
            if outcome is None:
                outcome = self._memo[key] = self._resolve(g, doc, scanner)
            out.append(outcome[2])
        return tuple(out)
