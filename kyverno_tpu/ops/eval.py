"""Batched rule evaluation on device (IR v2: tri-state status programs).

``build_evaluator(cps)`` returns a jitted function mapping the encoded
batch tensors to ``(status [R, P], detail [R, P], fdet [R, P])`` matrices for the
compiled programs, where status is one of

  0 PASS   1 FAIL   2 SKIP   3 HOST   4 SKIP_PRECOND

``HOST`` marks (resource, rule) pairs the device could not decide exactly
(Kleene UNKNOWN anywhere in the tree); the scanner re-runs just those on
the host engine, so exactness is never lost.  ``detail`` carries the
anyPattern index that passed (for the pass-message template).

The program structure is baked in at trace time: XLA sees straight-line
fused elementwise ops over ``[R]`` / ``[R, E]`` tensors — the policy set
is *compiled*, not interpreted (reference's per-resource tree walk:
pkg/engine/validate/validate.go).

Boolean facts are tracked as Kleene pairs ``(t, f)`` (known-true,
known-false); any value the encoder could not represent exactly simply
never sets either bit and surfaces as HOST.
"""

from __future__ import annotations

import json as _json
import os
from fractions import Fraction
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..compiler.encode import _needs_cached
from ..compiler.packing import plan_layout, unpack_batch  # noqa: F401
from ..compiler.ir import (STR_LEN, TAG_ARRAY, TAG_BOOL, TAG_FLOAT, TAG_INT,
                           TAG_MAP, TAG_MISSING, TAG_NULL, TAG_STRING,
                           TAIL_LEN, BoolExpr, CompiledPolicySet, CondCheck,
                           Leaf, RuleProgram, StatusExpr)
from ..compiler.ir import (FDET_BEYOND_BUDGET, STATUS_FAIL, STATUS_HOST,
                           STATUS_PASS, STATUS_SKIP, STATUS_SKIP_PRECOND,
                           STATUS_VAR_ERR)
from ..engine import pattern as leaf_pattern
from ..engine.operators import _sprint
from ..utils.duration import parse_duration
from ..utils.quantity import Quantity

_I64_MAX = (1 << 63) - 1


def _const_bytes(s: str) -> bytes:
    return s.encode('utf-8')


class _K:
    """Kleene pair of known-true / known-false boolean arrays."""

    __slots__ = ('t', 'f')

    def __init__(self, t, f):
        self.t = t
        self.f = f

    @staticmethod
    def known(v):
        return _K(v, ~v)

    @staticmethod
    def const(shape, value: bool):
        ones = jnp.ones(shape, bool)
        return _K(ones, ~ones) if value else _K(~ones, ones)

    @staticmethod
    def false_const(shape):
        return _K.const(shape, False)

    def negate(self) -> '_K':
        return _K(self.f, self.t)

    def __and__(self, other: '_K') -> '_K':
        return _K(self.t & other.t, self.f | other.f)

    def __or__(self, other: '_K') -> '_K':
        return _K(self.t | other.t, self.f & other.f)

    def unknown(self):
        return ~(self.t | self.f)


def _k_all(parts: List[_K]) -> _K:
    out = parts[0]
    for p in parts[1:]:
        out = out & p
    return out


def _k_any(parts: List[_K]) -> _K:
    out = parts[0]
    for p in parts[1:]:
        out = out | p
    return out


def _cmp_arr(value, operand, cmp: str):
    if cmp == '>':
        return value > operand
    if cmp == '>=':
        return value >= operand
    if cmp == '<':
        return value < operand
    if cmp == '<=':
        return value <= operand
    if cmp == '==':
        return value == operand
    if cmp == '!=':
        return value != operand
    raise ValueError(cmp)


def _frac_thresholds(cmp: str, target: Fraction) -> Tuple[str, int]:
    """Rewrite ``milli cmp target`` (target rational ×1000) as an integer
    comparison on the milli lane (exact for any rational threshold)."""
    import math
    if target.denominator == 1:
        return cmp, int(target)
    if cmp == '>':
        return '>=', math.floor(target) + 1
    if cmp == '>=':
        return '>=', math.ceil(target)
    if cmp == '<':
        return '<=', math.ceil(target) - 1
    if cmp == '<=':
        return '<=', math.floor(target)
    if cmp == '==':
        return '==', None  # never equal — caller handles
    if cmp == '!=':
        return '!=', None  # always unequal
    raise ValueError(cmp)


class _View:
    """Accessor for one lane bundle (slot or gather elements) in the flat
    tensor dict, plus tag predicates shared by all ops."""

    _BYTE_LANES = frozenset({'str_head', 'str_tail'})

    def __init__(self, t: Dict[str, Any], prefix: str, elem: int = None):
        self._t = t
        self._p = prefix
        # gather element index — the LAST gather axis, so the same view
        # works for [R, G] gathers and [R, FE, EG] per-foreach gathers
        self._elem = elem

    def lane(self, name: str):
        arr = self._t[f'{self._p}_{name}']
        if self._elem is not None:
            if name in self._BYTE_LANES:
                arr = arr[..., self._elem, :]
            else:
                arr = arr[..., self._elem]
        return arr

    def has(self, name: str) -> bool:
        return f'{self._p}_{name}' in self._t

    @property
    def tag(self):
        return self.lane('tag')

    def is_tag(self, *tags):
        tag = self.tag
        r = tag == tags[0]
        for x in tags[1:]:
            r = r | (tag == x)
        return r

    @property
    def convertible(self):
        return self.is_tag(TAG_STRING, TAG_INT, TAG_FLOAT, TAG_BOOL)

    @property
    def numish(self):
        return self.is_tag(TAG_INT, TAG_FLOAT)

    @property
    def nullish(self):
        # missing keys validate as nil (anchor.py handle_element default:
        # resource_map.get(key) → None)
        return self.is_tag(TAG_NULL, TAG_MISSING)

    @property
    def arrayish(self):
        return self.tag == TAG_ARRAY

    @property
    def milli(self):
        return self.lane('milli')

    @property
    def milli_ok(self):
        # missing == nil: _number_to_string(None) == '0' → 0 exactly
        return self.lane('milli_ok') | (self.tag == TAG_MISSING)

    @property
    def nanos(self):
        return self.lane('nanos')

    @property
    def nanos_ok(self):
        return self.lane('nanos_ok') | (self.tag == TAG_MISSING)

    @property
    def str_len(self):
        return self.lane('str_len')

    @property
    def is_zero_str(self):
        """The literal string '0' (excluded from operator duration parse,
        reference: pkg/engine/variables/operator/operator.go:80)."""
        return self.lane('lit_zero')

    # duration usable under LEAF semantics (pattern.py _compare_duration:
    # the plain string form parses, '0' included).  The encoder sets
    # nanos_ok for int 0 ('0' parses) and nulls; floats never parse
    # ('0.000000' has no unit).
    @property
    def dur_leaf(self):
        return (((self.tag == TAG_STRING) & self.lane('str_is_dur')) |
                ((self.tag == TAG_INT) & self.lane('nanos_ok')) |
                self.nullish)

    # string equality / prefix / suffix against a constant ---------------

    def eq_const(self, s: str) -> _K:
        b = _const_bytes(s)
        conv = self.convertible
        head = self.lane('str_head')
        w = head.shape[-1]
        if len(b) <= w:
            # value bytes past str_len are zero, so a full-window compare
            # against the zero-padded constant is exact string equality
            const = np.zeros(w, np.uint8)
            const[:len(b)] = np.frombuffer(b, np.uint8)
            hit = (conv & (self.str_len == len(b)) &
                   jnp.all(head == const, axis=-1))
            return _K(hit, ~hit & ~self.arrayish)
        # constant longer than the head window: equal length + matching
        # prefix is undecidable (analysis sizes windows so this is rare)
        maybe = conv & (self.str_len == len(b)) & \
            jnp.all(head == np.frombuffer(b[:w], np.uint8), axis=-1)
        return _K(jnp.zeros_like(maybe), ~maybe & ~self.arrayish)

    def prefix_const(self, s: str) -> _K:
        b = _const_bytes(s)
        conv = self.convertible
        head = self.lane('str_head')
        w = head.shape[-1]
        if len(b) <= w:
            const = np.frombuffer(b, np.uint8)
            hit = conv & (self.str_len >= len(b)) & \
                jnp.all(head[..., :len(b)] == const, axis=-1)
            return _K(hit, ~hit & ~self.arrayish)
        maybe = conv & (self.str_len >= len(b)) & \
            jnp.all(head == np.frombuffer(b[:w], np.uint8), axis=-1)
        return _K(jnp.zeros_like(maybe), ~maybe & ~self.arrayish)

    def suffix_const(self, s: str) -> _K:
        b = _const_bytes(s)
        conv = self.convertible
        tail = self.lane('str_tail')[..., TAIL_LEN - len(b):]
        const = np.frombuffer(b, np.uint8)
        hit = conv & (self.str_len >= len(b)) & jnp.all(tail == const, axis=-1)
        return _K(hit, ~hit & ~self.arrayish)

    def wildcard_const(self, pattern: str) -> _K:
        """Glob ``pattern`` (utils/wildcard.py semantics) vs the value's
        string form; undecidable when the value exceeds the byte window or
        '?' meets non-ASCII bytes (rune vs byte width)."""
        conv = self.convertible
        head = self.lane('str_head')
        w = head.shape[-1]
        vlen = jnp.minimum(self.str_len, w)
        pb = _const_bytes(pattern)
        # dp[j]: pattern consumed so far matches value[:j]
        shape = head.shape[:-1]
        dp = jnp.zeros(shape + (w + 1,), bool)
        dp = dp.at[..., 0].set(True)
        pos_valid = jnp.arange(w) < vlen[..., None]
        for ch in pb:
            if ch == ord('*'):
                dp = jnp.cumsum(dp.astype(jnp.int32), axis=-1) > 0
            elif ch == ord('?'):
                step = dp[..., :-1] & pos_valid
                dp = jnp.concatenate(
                    [jnp.zeros(shape + (1,), bool), step], axis=-1)
            else:
                step = dp[..., :-1] & (head == ch) & pos_valid
                dp = jnp.concatenate(
                    [jnp.zeros(shape + (1,), bool), step], axis=-1)
        matched = jnp.take_along_axis(dp, vlen[..., None], axis=-1)[..., 0]
        in_window = self.str_len <= w
        if b'?' in bytes(pb):
            ascii_ok = jnp.all((head < 0x80) | ~pos_valid, axis=-1)
        else:
            ascii_ok = jnp.ones(shape, bool)
        decid = in_window & ascii_ok
        t = conv & decid & matched
        f = (~self.arrayish) & (~conv | (decid & ~matched))
        return _K(t, f)

    def match_const_pattern(self, s: str) -> _K:
        """wildcard.match(const_pattern, value_string) — classified into
        the cheapest lane comparison (ir.classify_wildcard, shared with
        the compiler and the lane-need analysis)."""
        from ..compiler.ir import classify_wildcard
        kind, parts = classify_wildcard(s)
        if kind == 'eq':
            return self.eq_const(s)
        if kind == 'any':
            return _K(self.convertible, ~self.convertible & ~self.arrayish)
        if kind == 'nonempty':
            t = (self.is_tag(TAG_INT, TAG_FLOAT, TAG_BOOL) |
                 ((self.tag == TAG_STRING) & (self.str_len > 0)))
            return _K(t, ~t & ~self.arrayish)
        if kind == 'prefix':
            return self.prefix_const(parts[0])
        if kind == 'suffix':
            return self.suffix_const(parts[0])
        if kind == 'prefix_suffix':
            min_len = (len(parts[0].encode('utf-8')) +
                       len(parts[1].encode('utf-8')))
            ok = self.convertible & (self.str_len >= min_len)
            conv_len = _K(ok, ~ok & ~self.arrayish)
            return (self.prefix_const(parts[0]) &
                    self.suffix_const(parts[1]) & conv_len)
        return self.wildcard_const(s)


# ---------------------------------------------------------------------------
# leaf (pattern) ops over a view — semantics: kyverno_tpu/engine/pattern.py
# (reference: pkg/engine/pattern/pattern.go)

def leaf_op_tf(v: _View, op: str, operand: Any) -> _K:
    arr = v.arrayish

    if op == 'true':
        return _K.const(v.tag.shape, True)
    if op == 'absent':
        return _K.known(v.tag == TAG_MISSING)
    if op == 'present':
        return _K.known(v.tag != TAG_MISSING)
    if op == 'star':
        # anchor default-key "*": passes on any non-nil value
        return _K.known(~v.nullish)
    if op == 'is_map':
        return _K.known(v.tag == TAG_MAP)
    if op == 'is_array':
        return _K.known(v.tag == TAG_ARRAY)
    if op == 'any_str':
        return _K(v.convertible, ~v.convertible & ~arr)
    if op == 'nonempty':
        t = (v.is_tag(TAG_INT, TAG_FLOAT, TAG_BOOL) |
             ((v.tag == TAG_STRING) & (v.str_len > 0)))
        return _K(t, ~t & ~arr)
    if op == 'convertible':
        return _K(v.convertible, ~v.convertible & ~arr)
    if op == 'eq_bool':
        t = (v.tag == TAG_BOOL) & ((v.milli != 0) == bool(operand))
        return _K(t, ~t & ~arr)
    if op == 'eq_null':
        t = (v.nullish |
             (v.is_tag(TAG_BOOL, TAG_INT, TAG_FLOAT) & v.milli_ok &
              (v.milli == 0)) |
             ((v.tag == TAG_STRING) & (v.str_len == 0)))
        return _K(t, ~t & ~arr)
    if op in ('eq_int', 'eq_float'):
        target = (int(operand) * 1000 if op == 'eq_int'
                  else int(Fraction(str(operand)) * 1000))
        flag = 'str_is_int' if op == 'eq_int' else 'str_is_float'
        cand = v.numish | ((v.tag == TAG_STRING) & v.lane(flag))
        mok = v.lane('milli_ok')
        t = cand & mok & (v.milli == target)
        u = cand & ~mok
        return _K(t, ~t & ~u & ~arr)
    if op == 'cmp_qty':
        cmp, target = operand
        cand = (v.numish | v.nullish |
                ((v.tag == TAG_STRING) & v.lane('str_is_qty')))
        mok = v.milli_ok
        t = cand & mok & _cmp_arr(v.milli, target, cmp)
        u = cand & ~mok
        return _K(t, ~t & ~u & ~arr)
    if op == 'cmp_dur':
        cmp, target = operand
        cand = v.dur_leaf
        t = cand & v.nanos_ok & _cmp_arr(v.nanos, target, cmp)
        # parsed-but-overflowed durations are undecidable
        u = (v.tag == TAG_STRING) & v.lane('str_is_dur') & \
            ~v.lane('nanos_ok')
        return _K(t, ~t & ~u & ~arr)
    if op == 'eq_str':
        return v.eq_const(operand)
    if op == 'prefix':
        return v.prefix_const(operand)
    if op == 'suffix':
        return v.suffix_const(operand)
    if op == 'min_len':
        t = v.convertible & (v.str_len >= int(operand))
        return _K(t, ~t & ~arr)
    if op == 'wildcard':
        return v.wildcard_const(operand)
    if op == 'truthy':
        # Python bool(value): maps/arrays are truthy only when non-empty,
        # which the lanes can't see → unknown
        mok = v.lane('milli_ok')
        num = v.is_tag(TAG_BOOL, TAG_INT, TAG_FLOAT)
        t = (num & ((v.milli != 0) | ~mok)) | \
            ((v.tag == TAG_STRING) & (v.str_len > 0))
        f = v.nullish | (num & mok & (v.milli == 0)) | \
            ((v.tag == TAG_STRING) & (v.str_len == 0))
        return _K(t, f)
    if op == 'is_true':
        # `value is True` — identity, so every non-bool is known-False
        t = (v.tag == TAG_BOOL) & (v.milli != 0)
        return _K(t, ~t)
    if op == 'is_false':
        t = (v.tag == TAG_BOOL) & (v.milli == 0)
        return _K(t, ~t)
    if op == 'is_zero_num':
        # Python ==: 0 == 0.0 == False; strings/maps/arrays never equal 0
        num = v.is_tag(TAG_BOOL, TAG_INT, TAG_FLOAT)
        t = num & v.lane('milli_ok') & (v.milli == 0)
        return _K(t, ~t)
    raise ValueError(f'unknown leaf op {op!r}')


# ---------------------------------------------------------------------------
# string-term evaluation for condition values that are range / pattern
# strings (leaf_pattern.validate semantics over a lane view)

def string_term_tf(v: _View, term: str) -> _K:
    op = leaf_pattern.get_operator_from_string_pattern(term)
    if op == leaf_pattern.OP_IN_RANGE:
        m = leaf_pattern.IN_RANGE_RE.match(term)
        return (string_term_tf(v, f'>= {m.group(1)}') &
                string_term_tf(v, f'<= {m.group(2)}'))
    if op == leaf_pattern.OP_NOT_IN_RANGE:
        m = leaf_pattern.NOT_IN_RANGE_RE.match(term)
        return (string_term_tf(v, f'< {m.group(1)}') |
                string_term_tf(v, f'> {m.group(2)}'))
    operand = term[len(op):].strip(' ') if op else term
    cmp = {leaf_pattern.OP_MORE: '>', leaf_pattern.OP_MORE_EQUAL: '>=',
           leaf_pattern.OP_LESS: '<', leaf_pattern.OP_LESS_EQUAL: '<=',
           leaf_pattern.OP_EQUAL: '==',
           leaf_pattern.OP_NOT_EQUAL: '!='}[op or leaf_pattern.OP_EQUAL]
    alts: List[_K] = []
    try:
        nanos = parse_duration(operand)
        alts.append(leaf_op_tf(v, 'cmp_dur', (cmp, nanos)))
    except (ValueError, TypeError):
        pass
    try:
        q = Quantity.parse(operand)
        m = q.value * 1000
        if m.denominator == 1 and abs(m.numerator) <= _I64_MAX:
            alts.append(leaf_op_tf(v, 'cmp_qty', (cmp, int(m))))
        else:
            cand = (v.numish | v.nullish |
                    ((v.tag == TAG_STRING) & v.lane('str_is_qty')))
            decided = cand & v.milli_ok
            if cmp in ('==', '!='):
                # a milli-exact value can never equal a sub-milli constant
                hit = decided if cmp == '!=' else jnp.zeros_like(decided)
                alts.append(_K(hit, (decided & ~hit) | (~cand & ~v.arrayish)))
            else:
                c2, thr = _frac_thresholds(cmp, m)
                alts.append(leaf_op_tf(v, 'cmp_qty', (c2, thr)))
    except ValueError:
        pass
    if cmp in ('==', '!='):
        s = v.match_const_pattern(operand)
        if cmp == '!=':
            conv = _K(v.convertible, ~v.convertible & ~v.arrayish)
            s = conv & s.negate()
        alts.append(s)
    if not alts:
        return _K.false_const(v.tag.shape)
    return _k_any(alts)


def string_pattern_tf(v: _View, pattern: str) -> _K:
    """leaf_pattern._validate_string_patterns over a view."""
    parts = [v.eq_const(pattern)]  # value == pattern literal short-circuit
    for condition in pattern.split('|'):
        ands = [string_term_tf(v, t.strip(' '))
                for t in condition.strip(' ').split('&')]
        parts.append(_k_all(ands))
    return _k_any(parts)


# ---------------------------------------------------------------------------
# condition (deny / precondition) checks over gathers — semantics:
# kyverno_tpu/engine/operators.py (reference: pkg/engine/variables/operator)

def _scalar_eq_const(sv: _View, value: Any) -> _K:
    """operators._equal(key=<scalar gather>, value=<const>)."""
    shape = sv.tag.shape
    if isinstance(value, bool):
        t = (sv.tag == TAG_BOOL) & ((sv.milli != 0) == value)
        return _K(t, ~t)
    if isinstance(value, (int, float)):
        # key bool→False; key num → exact numeric eq; key str → duration
        # pair only (operators.py:141-162,180-192)
        target = Fraction(str(value)) * 1000
        mok = sv.lane('milli_ok')
        if target.denominator == 1 and abs(target) <= _I64_MAX:
            num_t = sv.numish & mok & (sv.milli == int(target))
        else:
            num_t = jnp.zeros(shape, bool)
        num_u = sv.numish & ~mok
        dur_key = ((sv.tag == TAG_STRING) & sv.lane('str_is_dur') &
                   ~sv.is_zero_str)
        # host truncates via float: _duration_pair does int(value * 1e9)
        # (operators.py:111-117)
        vd = int(value * 1e9)
        if abs(vd) <= _I64_MAX:
            dur_t = dur_key & sv.lane('nanos_ok') & (sv.nanos == vd)
        else:
            dur_t = jnp.zeros(shape, bool)
        dur_u = dur_key & ~sv.lane('nanos_ok')
        t = num_t | dur_t
        u = num_u | dur_u
        return _K(t, ~t & ~u)
    if isinstance(value, str):
        return _scalar_eq_str_const(sv, value)
    if value is None:
        return _K.false_const(shape)  # _equal(key, None) is always False
    if isinstance(value, list):
        return _K.false_const(shape)  # scalar key vs list value → False
    return _K.false_const(shape)


def _scalar_eq_str_const(sv: _View, value: str) -> _K:
    shape = sv.tag.shape
    # key num: float(value) == float(key)  (operators.py:157-177) —
    # replicated as the identical float64 comparison on device
    try:
        fv = float(value)
        mok = sv.lane('milli_ok') & (jnp.abs(sv.milli) <= (1 << 53))
        key_f = sv.milli.astype(jnp.float64) / 1000.0
        num_t = sv.numish & mok & (key_f == jnp.float64(fv))
        num_u = sv.numish & ~mok
    except ValueError:
        num_t = jnp.zeros(shape, bool)
        num_u = jnp.zeros(shape, bool)
    # key str (operators.py:180 _equal_string): duration pair first
    is_str = sv.tag == TAG_STRING
    dur_key = is_str & sv.lane('str_is_dur') & ~sv.is_zero_str
    try:
        vnanos: Optional[int] = (parse_duration(value)
                                 if value != '0' else None)
    except (ValueError, TypeError):
        vnanos = None
    if vnanos is not None:
        dur_t = dur_key & sv.lane('nanos_ok') & (sv.nanos == vnanos)
        dur_decided = dur_key
        dur_u = dur_key & ~sv.lane('nanos_ok')
    else:
        # value not a duration and not numeric → pair=None → quantity next
        dur_t = jnp.zeros(shape, bool)
        dur_decided = jnp.zeros(shape, bool)
        dur_u = jnp.zeros(shape, bool)
    # quantity: key parses as quantity → decided by quantity compare alone
    qty_key = is_str & sv.lane('str_is_qty') & ~dur_decided
    try:
        vq = Quantity.parse(value)
        vm = vq.value * 1000
        if vm.denominator == 1 and abs(vm.numerator) <= _I64_MAX:
            qty_t = qty_key & sv.lane('milli_ok') & (sv.milli == int(vm))
        else:
            qty_t = jnp.zeros(shape, bool)
        qty_u = qty_key & ~sv.lane('milli_ok')
    except ValueError:
        # value not a quantity → quantity-keyed compare is False
        qty_t = jnp.zeros(shape, bool)
        qty_u = jnp.zeros(shape, bool)
    # wildcard string match for plain-string keys
    wild_key = is_str & ~dur_decided & ~qty_key
    wk = sv.match_const_pattern(value)
    wild_t = wild_key & wk.t
    wild_u = wild_key & wk.unknown()
    t = num_t | dur_t | qty_t | wild_t
    u = num_u | dur_u | qty_u | wild_u
    return _K(t, ~t & ~u)


def _list_eq_const(ev: _View, count, overflow, values: Tuple[Any, ...]) -> _K:
    """list key == list const (Python ``==`` semantics, elementwise)."""
    shape = count.shape
    gwidth = ev.lane('tag').shape[-1]
    if len(values) > gwidth:
        # visible lists are shorter → known unequal; overflowed lists have
        # an unknown true length → undecidable
        return _K(jnp.zeros(shape, bool), ~overflow)
    n = len(values)
    mismatch = (count != n) | overflow
    t_all = jnp.ones(shape, bool)
    f_any = jnp.zeros(shape, bool)
    u_any = jnp.zeros(shape, bool)
    for i, cv in enumerate(values):
        el = _View(ev._t, ev._p, i)
        if cv is None:
            ek = _K.known(el.tag == TAG_NULL)
        elif isinstance(cv, (bool, int, float)):
            # Python numeric equality spans bool/int/float: True == 1 == 1.0
            target = Fraction(str(cv if not isinstance(cv, bool)
                                  else (1 if cv else 0))) * 1000
            numish = el.is_tag(TAG_BOOL, TAG_INT, TAG_FLOAT)
            mok = el.lane('milli_ok')
            if target.denominator == 1 and abs(target) <= _I64_MAX:
                et = numish & mok & (el.milli == int(target))
            else:
                et = jnp.zeros(shape, bool)
            ek = _K(et, ~et & ~(numish & ~mok))
        elif isinstance(cv, str):
            is_str = el.tag == TAG_STRING
            e = el.eq_const(cv)
            ek = _K(is_str & e.t, ~is_str | (is_str & e.f))
        else:  # nested list consts are rejected at compile time
            ek = _K(jnp.zeros(shape, bool), jnp.zeros(shape, bool))
        t_all = t_all & ek.t
        f_any = f_any | ek.f
        u_any = u_any | ek.unknown()
    t = ~mismatch & t_all
    f = mismatch | f_any
    return _K(t, f & ~t)


def _both_dir_member(view: _View, values: Tuple[Any, ...]) -> _K:
    """∃ const v: wildcard.match(sprint(v), k) or wildcard.match(k,
    sprint(v)) — the list-value membership of the In family
    (operators.py:228,327-330)."""
    hw = view.lane('has_wild') if view.has('has_wild') else None
    parts: List[_K] = []
    for cv in values:
        vs = cv if isinstance(cv, str) else _sprint(cv)
        m1 = view.match_const_pattern(vs)  # match(vs_as_pattern, key)
        if hw is None:
            parts.append(m1)
            continue
        # match(key_as_pattern, vs): for wildcard-free keys this is plain
        # equality; wildcard keys are undecidable unless m1 already hit
        eqc = view.eq_const(vs) if ('*' in vs or '?' in vs) else m1
        parts.append(_K(m1.t | (eqc.t & ~hw), m1.f & eqc.f & ~hw))
    return _k_any(parts)


def _arr_member(view: _View, value: str) -> _K:
    """k ∈ (json-list(value) or [value]) — plain string-form equality
    (operators.py:339-345)."""
    arr = _try_json_str_list(value)
    if arr is None:
        arr = [value]
    return _k_any([view.eq_const(x) for x in arr])


def _scalar_str_member(view: _View, value: str) -> _K:
    """_key_in_array(k, value_str, allow_range=True) (operators.py:222):
    wildcard match, else range validation, else set membership."""
    m = view.match_const_pattern(value)
    if leaf_pattern.get_operator_from_string_pattern(value) == \
            leaf_pattern.OP_IN_RANGE:
        return m | string_pattern_tf(view, value)
    return m | _arr_member(view, value)


def _try_json_str_list(value: str) -> Optional[List[str]]:
    try:
        arr = _json.loads(value)
    except ValueError:
        return None
    if isinstance(arr, list) and all(isinstance(x, str) for x in arr):
        return arr
    return None


def _quantify(quant: str, em: _K, valid, overflow):
    """Reduce elementwise Kleene membership over a list key.  Returns
    (known-true, known-false) for the quantified statement."""
    if quant == 'any':          # ∃ member
        lt = jnp.any(valid & em.t, axis=-1)
        lf = jnp.all(~valid | em.f, axis=-1) & ~overflow
    elif quant == 'all':        # ∀ member (vacuously true when empty)
        lt = jnp.all(~valid | em.t, axis=-1) & ~overflow
        lf = jnp.any(valid & em.f, axis=-1)
    elif quant == 'any_not':    # ∃ non-member
        lt = jnp.any(valid & em.f, axis=-1)
        lf = jnp.all(~valid | em.t, axis=-1) & ~overflow
    elif quant == 'all_not':    # ∀ non-member
        lt = jnp.all(~valid | em.f, axis=-1) & ~overflow
        lf = jnp.any(valid & em.t, axis=-1)
    else:
        raise ValueError(quant)
    return lt, lf


def _in_family_tf(t: Dict[str, Any], prefix: str, check: CondCheck) -> _K:
    """AnyIn / AllIn and their negations (operators.py:299-395).  The
    deprecated In/NotIn are host-only (rejected at compile time)."""
    op = check.op
    kind = t[f'{prefix}_kind']
    count = t[f'{prefix}_count']
    overflow = t[f'{prefix}_overflow']
    shape = kind.shape
    negate = op in ('anynotin', 'allnotin')

    if not check.list_value and not isinstance(check.values[0], str):
        # invalid value type: every host path returns False
        return _K(jnp.zeros(shape, bool), jnp.ones(shape, bool))

    sv = _View(t, prefix, 0)
    ev = _View(t, prefix)

    # ---- scalar key (str or num; bool/map/null → False) ----
    scalar = kind == 1
    scalar_ok = sv.is_tag(TAG_STRING, TAG_INT, TAG_FLOAT)
    if check.list_value:
        member = _both_dir_member(sv, check.values)
    else:
        member = _scalar_str_member(sv, check.values[0])
    if negate:
        member = member.negate()
    scal_t = scalar & scalar_ok & member.t
    scal_f = scalar & (~scalar_ok | member.f)

    # ---- list key: per-element membership, then quantify ----
    gwidth = t[f'{prefix}_tag'].shape[-1]
    elem_valid = jnp.arange(gwidth) < count[..., None]
    shortcut = None
    if check.list_value:
        em = _both_dir_member(ev, check.values)
        quant = {'anyin': 'any', 'allin': 'all',
                 'anynotin': 'any_not', 'allnotin': 'all_not'}[op]
    else:
        value = check.values[0]
        is_range = leaf_pattern.get_operator_from_string_pattern(value) == \
            leaf_pattern.OP_IN_RANGE
        # single-element lists equal to the literal value string hit the
        # keys[0]==value shortcut before range/JSON handling
        # (operators.py:332-345,383-394)
        eq0 = _View(t, prefix, 0).eq_const(value)
        shortcut = (count == 1) & eq0.t
        if is_range:
            if op == 'anynotin':
                em = string_pattern_tf(ev, value.replace('-', '!-', 1))
                quant = 'any'
            elif op == 'allnotin':
                em = string_pattern_tf(ev, value)
                quant = 'all_not'
            else:
                em = string_pattern_tf(ev, value)
                quant = {'anyin': 'any', 'allin': 'all'}[op]
        else:
            # JSON-list / plain string values run the same bidirectional
            # wildcard membership as list values (anyin.go:168-183
            # isAnyIn/isAnyNotIn over the parsed array)
            arr = _try_json_str_list(value)
            em = _both_dir_member(ev, tuple(arr if arr is not None
                                            else [value]))
            quant = {'anyin': 'any', 'allin': 'all',
                     'anynotin': 'any_not', 'allnotin': 'all_not'}[op]
    lt, lf = _quantify(quant, em, elem_valid, overflow)
    if shortcut is not None:
        if negate:
            lt, lf = lt & ~shortcut, lf | shortcut
        else:
            lt, lf = lt | shortcut, lf & ~shortcut
    lst = kind == 2
    list_t = lst & lt
    list_f = lst & lf

    null_f = kind == 0
    t_out = scal_t | list_t
    f_out = (scal_f | list_f | null_f) & ~t_out
    return _K(t_out, f_out)


def _numeric_tf(t: Dict[str, Any], prefix: str, check: CondCheck) -> _K:
    """GreaterThan / LessThan family (operators.py:413 _numeric).

    The host compares through float64 (``_cmp(op, float(key),
    float(value))``, duration pairs via ``int(x * 1e9)`` then ``/ 1e9``);
    the device replicates those float64 computations bit-for-bit (IEEE
    semantics are identical), guarded to the ranges where the lanes
    reconstruct the host's floats exactly.
    """
    op = check.op
    kind = t[f'{prefix}_kind']
    shape = kind.shape
    sv = _View(t, prefix, 0)
    value = check.values[0]
    cmp = {'greaterthan': '>', 'greaterthanorequals': '>=',
           'lessthan': '<', 'lessthanorequals': '<='}[op]
    zeros = jnp.zeros(shape, bool)
    scalar = kind == 1

    # f64(milli)/1000 == the host's float(key) whenever milli is exact and
    # within 2^53 (single correctly-rounded division; see encode milli)
    f53 = 1 << 53
    mok = sv.lane('milli_ok') & (jnp.abs(sv.milli) <= f53)
    key_f = sv.milli.astype(jnp.float64) / 1000.0

    def cmp_float(valid, ok, target_f):
        """valid & host-float comparison against a float64 constant."""
        return (valid & ok & _cmp_arr(key_f, jnp.float64(target_f), cmp),
                valid & ~ok)

    def cmp_duration_pair(valid, ok, vd: int):
        """_duration_pair semantics: int(key*1e9)/1e9 cmp vd/1e9."""
        kd = jnp.trunc(key_f * 1e9)
        return (valid & ok & _cmp_arr(kd / 1e9, jnp.float64(vd / 1e9), cmp),
                valid & ~ok)

    # value-side constants, computed exactly as the host does
    vd: Optional[int] = None        # duration nanos (int(value * 1e9))
    vf: Optional[float] = None      # float(value)
    vq = None                       # Quantity
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        vf = float(value)
    if isinstance(value, str):
        vd = _op_duration(value)
        try:
            vq = Quantity.parse(value)
        except ValueError:
            vq = None
        if vd is None:
            try:
                vf = float(value)
            except ValueError:
                vf = None

    # ---- numeric key (operators.py:442 _numeric_num_key) ----
    num_key = sv.numish
    if isinstance(value, bool):
        num_t, num_u = zeros, zeros
    elif isinstance(value, (int, float)):
        num_t, num_u = cmp_float(num_key, mok, vf)
    elif isinstance(value, str) and vd is not None:
        num_t, num_u = cmp_duration_pair(num_key, mok, vd)
    elif isinstance(value, str) and vf is not None:
        num_t, num_u = cmp_float(num_key, mok, vf)
    else:
        num_t, num_u = zeros, zeros

    # ---- string key (operators.py:418-437) ----
    is_str = sv.tag == TAG_STRING
    dur_key = is_str & sv.lane('str_is_dur') & ~sv.is_zero_str
    # duration pair: needs a duration/numeric value; kd is the parsed
    # nanos (exact int) pushed through the host's / 1e9
    if isinstance(value, str):
        pair_vd = vd
    elif isinstance(value, (int, float)) and not isinstance(value, bool):
        pair_vd = int(value * 1e9)
    else:
        pair_vd = None
    if pair_vd is not None:
        nok = sv.lane('nanos_ok') & (jnp.abs(sv.nanos) <= f53)
        kd_f = sv.nanos.astype(jnp.float64) / 1e9
        dur_t = dur_key & nok & _cmp_arr(kd_f, jnp.float64(pair_vd / 1e9),
                                         cmp)
        dur_u = dur_key & ~nok
        dur_decided = dur_key
    else:
        dur_t, dur_u = zeros, zeros
        dur_decided = zeros
    # quantity stage: exact rational compare (Quantity.cmp) via milli
    qty_key = is_str & sv.lane('str_is_qty') & ~dur_decided
    if isinstance(value, str) and vq is not None:
        c2, thr = _frac_thresholds(cmp, vq.value * 1000)
        qty_t = qty_key & sv.lane('milli_ok') & _cmp_arr(sv.milli, thr, c2)
        qty_u = qty_key & ~sv.lane('milli_ok')
        qty_decided = qty_key
    else:
        qty_t, qty_u = zeros, zeros
        qty_decided = zeros
    # float(key) fallback: _numeric_num_key with the parsed float
    float_key = (is_str & sv.lane('str_is_float') & ~dur_decided &
                 ~qty_decided)
    if isinstance(value, bool):
        f_t, f_u = zeros, zeros
    elif isinstance(value, (int, float)):
        f_t, f_u = cmp_float(float_key, mok, float(value))
    elif isinstance(value, str) and vd is not None:
        f_t, f_u = cmp_duration_pair(float_key, mok, vd)
    elif isinstance(value, str) and vf is not None:
        f_t, f_u = cmp_float(float_key, mok, vf)
    else:
        f_t, f_u = zeros, zeros
    # semver stage: undecidable on device when the const side is semver
    semver_const = isinstance(value, str) and _is_semverish(value)
    rest = is_str & ~dur_decided & ~qty_decided & ~float_key
    semver_u = rest if semver_const else zeros

    t_true = scalar & (num_t | dur_t | qty_t | f_t)
    u = scalar & (num_u | dur_u | qty_u | f_u | semver_u)
    return _K(t_true, ~t_true & ~u)


def _op_duration(v: str) -> Optional[int]:
    """operators._try_duration: duration strings except literal '0'."""
    if isinstance(v, str) and v != '0':
        try:
            return parse_duration(v)
        except (ValueError, TypeError):
            return None
    return None


def _is_op_num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _is_semverish(v: str) -> bool:
    from ..engine.operators import _try_semver
    return _try_semver(v) is not None


def _suspicious_scalar(view: _View) -> Any:
    """Scalar string values that might trigger the host's runtime range
    or JSON handling (contains '-', leads with '[' after optional
    whitespace — json.loads tolerates leading whitespace — has wildcards,
    or exceeds the head window): undecidable beyond plain equality."""
    head = view.lane('str_head')
    w = head.shape[-1]
    pos_valid = jnp.arange(w) < jnp.minimum(view.str_len, w)[..., None]
    has_dash = jnp.any((head == ord('-')) & pos_valid, axis=-1)
    is_space = (head == ord(' ')) | (head == ord('\t')) | \
        (head == ord('\n')) | (head == ord('\r'))
    # all-whitespace prefix up to (exclusive) each position
    space_prefix = jnp.cumprod(is_space.astype(jnp.int32), axis=-1) > 0
    before_ok = jnp.concatenate(
        [jnp.ones(head.shape[:-1] + (1,), bool), space_prefix[..., :-1]],
        axis=-1)
    leads_bracket = jnp.any(
        before_ok & (head == ord('[')) & pos_valid, axis=-1)
    hw = view.lane('has_wild') if view.has('has_wild') else \
        jnp.zeros(view.tag.shape, bool)
    return has_dash | leads_bracket | hw | (view.str_len > w)


def _cond_b_tf(t: Dict[str, Any], prefix: str, check: CondCheck) -> _K:
    """Mode-B checks: constant key vs gathered value (foreach conditions
    like ``key: ALL, value: {{element...drop[]}}``; operators.py with the
    runtime side on the right)."""
    op = check.op
    key = check.key_const
    kind = t[f'{prefix}_kind']
    count = t[f'{prefix}_count']
    overflow = t[f'{prefix}_overflow']
    notfound = t[f'{prefix}_notfound']
    shape = kind.shape
    sv = _View(t, prefix, 0)
    ev = _View(t, prefix)
    zeros = jnp.zeros(shape, bool)

    if op in ('equal', 'equals', 'notequal', 'notequals'):
        res = _b_equals(t, prefix, key, sv, kind, count, overflow)
        if op in ('notequal', 'notequals'):
            res = res.negate()
    else:  # anyin / allin / anynotin / allnotin with a scalar const key
        negate = op in ('anynotin', 'allnotin')
        if key is None or isinstance(key, bool):
            # host: key not str/num/list → False for every variant
            res = _K(zeros, jnp.ones(shape, bool))
        else:
            ks = key if isinstance(key, str) else _sprint(key)
            # value list: ∃ element matching either direction
            # (_key_in_array(K, value) — the key is scalar, so every op
            # reduces to one membership test; operators.py:299-369)
            m_eq = ev.eq_const(ks)
            m_pat = ev.match_const_pattern(ks)
            hw = ev.lane('has_wild') if ev.has('has_wild') else None
            et = m_eq.t | m_pat.t
            ef = m_eq.f & m_pat.f
            if hw is not None:
                ef = ef & ~hw  # wildcard elements may match as patterns
            gw = ev.lane('tag').shape[-1]
            valid = jnp.arange(gw) < count[..., None]
            lt = jnp.any(valid & et, axis=-1)
            lf = jnp.all(~valid | ef, axis=-1) & ~overflow
            # value scalar string: match(value, K) → equality unless the
            # value could be a wildcard/range/JSON form at runtime
            s_eq = sv.eq_const(ks)
            s_susp = _suspicious_scalar(sv)
            st_ = (sv.tag == TAG_STRING) & s_eq.t
            sf_ = (sv.tag == TAG_STRING) & s_eq.f & ~s_susp
            scalar_str = (kind == 1) & (sv.tag == TAG_STRING)
            scalar_other = (kind == 1) & (sv.tag != TAG_STRING)
            r_t = ((kind == 2) & lt) | (scalar_str & st_)
            r_f = ((kind == 2) & lf) | (scalar_str & sf_) | \
                scalar_other | (kind == 0)
            res = _K(r_t, r_f & ~r_t)
            if negate:
                # r=None (invalid value types) stays False, not True
                inv = scalar_other | (kind == 0)
                res = _K(res.f & ~inv, (res.t | inv) & ~(res.f & ~inv))
    bad = notfound | ((kind == 0) & overflow)
    return _K(res.t & ~bad, res.f & ~bad)


def _b_equals(t, prefix: str, key, sv: _View, kind, count, overflow) -> _K:
    """operators._equal(const_key, gathered_value)."""
    shape = kind.shape
    zeros = jnp.zeros(shape, bool)
    scalar = kind == 1
    if isinstance(key, bool):
        tv = scalar & (sv.tag == TAG_BOOL) & ((sv.milli != 0) == key)
        return _K(tv, ~tv)
    if isinstance(key, (int, float)):
        # value num → exact numeric equality; value str → float compare
        kf = Fraction(str(key)) * 1000
        if kf.denominator == 1 and abs(kf) <= _I64_MAX:
            num_t = sv.numish & sv.lane('milli_ok') & (sv.milli == int(kf))
        else:
            num_t = zeros  # out of the milli lane → never equal exactly
        mok53 = sv.lane('milli_ok') & (jnp.abs(sv.milli) <= (1 << 53))
        key_f = sv.milli.astype(jnp.float64) / 1000.0
        str_t = (sv.tag == TAG_STRING) & sv.lane('str_is_float') & mok53 & \
            (key_f == jnp.float64(float(key)))
        str_u = (sv.tag == TAG_STRING) & sv.lane('str_is_float') & ~mok53
        num_u = sv.numish & ~sv.lane('milli_ok')
        tv = scalar & (num_t | str_t)
        uv = scalar & (num_u | str_u)
        return _K(tv, ~tv & ~uv)
    if isinstance(key, str):
        is_str = sv.tag == TAG_STRING
        try:
            kd = parse_duration(key) if key != '0' else None
        except (ValueError, TypeError):
            kd = None
        if kd is not None:
            # duration pair: value duration-string or numeric
            v_dur = is_str & sv.lane('str_is_dur') & ~sv.is_zero_str
            if abs(kd) <= _I64_MAX:
                dur_t = v_dur & sv.lane('nanos_ok') & (sv.nanos == kd)
                dur_u = v_dur & ~sv.lane('nanos_ok')
                mok53 = sv.lane('milli_ok') & \
                    (jnp.abs(sv.milli) <= (1 << 53))
                key_f = sv.milli.astype(jnp.float64) / 1000.0
                vd = jnp.trunc(key_f * 1e9)
                num_t = sv.numish & mok53 & (vd == jnp.float64(kd))
                num_u = sv.numish & ~mok53
            else:
                # constant beyond the nanos lane: duration-pair outcomes
                # are undecidable on device
                dur_t = num_t = zeros
                dur_u = v_dur
                num_u = sv.numish
            decided = v_dur | sv.numish
            rest = is_str & ~v_dur
        else:
            dur_t = dur_u = num_t = num_u = zeros
            decided = zeros
            rest = is_str
        try:
            kq = Quantity.parse(key)
        except ValueError:
            kq = None
        if kq is not None:
            m = kq.value * 1000
            if m.denominator == 1 and abs(m.numerator) <= _I64_MAX:
                qty_t = rest & sv.lane('str_is_qty') & \
                    sv.lane('milli_ok') & (sv.milli == int(m))
            else:
                qty_t = zeros
            qty_u = rest & sv.lane('str_is_qty') & ~sv.lane('milli_ok')
            # a quantity-keyed compare is decided for every string value
            qty_f_zone = rest
            wild_zone = zeros
        else:
            qty_t = qty_u = zeros
            qty_f_zone = zeros
            wild_zone = rest
        # wildcard: match(value_as_pattern, K) — equality unless wild
        w_eq = sv.eq_const(key)
        hw = sv.lane('has_wild') if sv.has('has_wild') else zeros
        wild_t = wild_zone & w_eq.t
        wild_u = wild_zone & ~w_eq.t & hw
        tv = scalar & (dur_t | num_t | qty_t | wild_t)
        uv = scalar & (dur_u | num_u | qty_u | wild_u)
        return _K(tv, ~tv & ~uv)
    # None / list / dict const keys: _equal returns False for gathered
    # scalars; list-vs-list is not compiled in mode B
    return _K(zeros, jnp.ones(shape, bool))


def _ctx_member(view: _View, vlen, vhead) -> _K:
    """Whether the string form of each key element is one of a value
    lane's elements: ``vlen`` [R, S] (−1: no such element) and ``vhead``
    [R, S, CTX_HEAD] against the key's own length and head window.  Two
    strings of one length that agree on the window and do not fit it
    are undecidable.  The parent process hands the device no value with
    a wildcard or a range in it (compiler/context_lanes.py), so what is
    left of ``wildcard.match`` in either direction is equality, except
    for a key that has a wildcard itself."""
    head = view.lane('str_head')
    w = min(head.shape[-1], vhead.shape[-1])
    klen = view.str_len
    # the value's axis comes last: [R, S] beside a scalar key's [R],
    # [R, 1, S] beside a list key's [R, G]
    mid = (1,) * (klen.ndim - 1)
    vlen = vlen.reshape(vlen.shape[:1] + mid + vlen.shape[1:])
    vhead = vhead.reshape(vhead.shape[:1] + mid + vhead.shape[1:])
    same = (vlen >= 0) & (klen[..., None] == vlen) & \
        jnp.all(head[..., None, :w] == vhead[..., :w], axis=-1)
    fits = (klen <= w)[..., None]
    conv = view.convertible
    hit = conv & jnp.any(same & fits, axis=-1)
    maybe = conv & jnp.any(same & ~fits, axis=-1)
    unknown = maybe | view.arrayish
    if view.has('has_wild'):
        unknown = unknown | view.lane('has_wild')
    return _K(hit, ~hit & ~unknown)


def _cond_ctx_tf(t: Dict[str, Any], prefix: str, vprefix: str,
                 check: CondCheck) -> _K:
    """Mode-C checks: the gathered key against a value that varies by
    row, lane with lane (operators.py with the value side at run time).
    Exact inside the zone the parent process admits a value to — a
    string or a list of scalars without wildcard or range for the In
    family, a plain string for Equals, a number for the comparisons —
    and for every other value the parent marks the cell for the host
    before the device's answer is read."""
    op = check.op
    kind = t[f'{prefix}_kind']
    count = t[f'{prefix}_count']
    overflow = t[f'{prefix}_overflow']
    sv = _View(t, prefix, 0)
    scalar = kind == 1
    raised = ((kind == 0) & overflow) | t[f'{prefix}_notfound']
    family = check.ctx_value.family
    if family == 'num':
        # numeric key against a number (operators._numeric_num_key):
        # both sides through float64 as the host has them; a string key
        # (duration, quantity, semver) is the host's
        cmp = {'greaterthan': '>', 'greaterthanorequals': '>=',
               'lessthan': '<', 'lessthanorequals': '<='}[op]
        mok = sv.lane('milli_ok') & (jnp.abs(sv.milli) <= (1 << 53))
        key_f = sv.milli.astype(jnp.float64) / 1000.0
        val_f = t[f'{vprefix}_milli'].astype(jnp.float64) / 1000.0
        tt = scalar & sv.numish & mok & _cmp_arr(key_f, val_f, cmp)
        uu = scalar & ((sv.numish & ~mok) | (sv.tag == TAG_STRING))
        return _K(tt & ~raised, ~tt & ~uu & ~raised)
    vlen = t[f'{vprefix}_len']
    vhead = t[f'{vprefix}_head']
    if family == 'eq':
        # a plain string value: equal to a string key of the same bytes
        # and to nothing else (operators._equal_string falls through to
        # wildcard.match(value, key), the value without wildcards)
        eq = _ctx_member(sv, vlen[:, :1], vhead[:, :1])
        is_str = scalar & (sv.tag == TAG_STRING)
        tt = is_str & eq.t
        uu = is_str & eq.unknown() & ~sv.arrayish
        res = _K(tt, ~tt & ~uu)
        if op in ('notequal', 'notequals'):
            res = res.negate()
        return _K(res.t & ~raised, res.f & ~raised)
    # AnyIn / AllIn and their negations (operators.py:299-395): slot 0
    # holds a string value itself, slots 1.. its elements (the JSON
    # array it spells, or the one string it is; a list value's elements)
    negate = op in ('anynotin', 'allnotin')
    member = _ctx_member(sv, vlen, vhead)
    if negate:
        member = member.negate()
    scalar_ok = sv.is_tag(TAG_STRING, TAG_INT, TAG_FLOAT)
    scal_t = scalar & scalar_ok & member.t
    scal_f = scalar & (~scalar_ok | member.f)
    ev = _View(t, prefix)
    gwidth = t[f'{prefix}_tag'].shape[-1]
    elem_valid = jnp.arange(gwidth) < count[..., None]
    em = _ctx_member(ev, vlen[:, 1:], vhead[:, 1:])
    quant = {'anyin': 'any', 'allin': 'all',
             'anynotin': 'any_not', 'allnotin': 'all_not'}[op]
    lt, lf = _quantify(quant, em, elem_valid, overflow)
    # a one-element list key that is the string value itself decides
    # before the value is parsed (operators.py:332,383)
    one = _ctx_member(sv, vlen[:, :1], vhead[:, :1])
    short = (count == 1) & one.t
    short_u = (count == 1) & one.unknown()
    if negate:
        lt, lf = lt & ~short, lf | short
    else:
        lt, lf = lt | short, lf & ~short
    lst = kind == 2
    list_t = lst & lt & ~short_u
    list_f = lst & lf & ~short_u
    t_out = scal_t | list_t
    f_out = (scal_f | list_f | (kind == 0)) & ~t_out
    return _K(t_out & ~raised, f_out & ~raised)


def cond_tf(t: Dict[str, Any], prefix: str, check: CondCheck) -> _K:
    op = check.op
    kind = t[f'{prefix}_kind']
    overflow = t[f'{prefix}_overflow']
    shape = kind.shape
    if op in ('equal', 'equals', 'notequal', 'notequals'):
        sv = _View(t, prefix, 0)
        scalar = kind == 1
        if check.list_value:
            eq_scal = _K.false_const(shape)  # scalar key vs list → False
        else:
            eq_scal = _scalar_eq_const(sv, check.values[0])
        count = t[f'{prefix}_count']
        if check.list_value:
            eq_list = _list_eq_const(_View(t, prefix), count, overflow,
                                     check.values)
        else:
            eq_list = _K.false_const(shape)  # list key vs scalar → False
        eq_t = (scalar & eq_scal.t) | ((kind == 2) & eq_list.t)
        eq_u = (scalar & eq_scal.unknown()) | ((kind == 2) & eq_list.unknown())
        res = _K(eq_t, ~eq_t & ~eq_u)
        if op in ('notequal', 'notequals'):
            res = res.negate()
        # raised queries (overflow on kind 0) and unresolvable paths
        # (notfound → STATUS_VAR_ERR preempts at the precond/deny node)
        # are undecidable at the condition level
        raised = ((kind == 0) & overflow) | t[f'{prefix}_notfound']
        return _K(res.t & ~raised, res.f & ~raised)
    raised = ((kind == 0) & overflow) | t[f'{prefix}_notfound']
    if op in ('in', 'anyin', 'allin', 'notin', 'anynotin', 'allnotin'):
        res = _in_family_tf(t, prefix, check)
        return _K(res.t & ~raised, res.f & ~raised)
    if op in ('greaterthan', 'greaterthanorequals', 'lessthan',
              'lessthanorequals'):
        res = _numeric_tf(t, prefix, check)
        return _K(res.t & ~raised, res.f & ~raised)
    raise ValueError(f'condition op {op!r} not supported on device')


# ---------------------------------------------------------------------------
# evaluator assembly.  Compiled-executable persistence lives in the
# aotcache subsystem (kyverno_tpu/aotcache + compiler/aot.py): every
# jit site below consults the disk store before paying a fresh trace +
# XLA compile, and stores what it compiled for the next process.  The
# cache-key helpers are re-exported here because this module
# historically owned them (and the evaluator is their main consumer).

from ..aotcache.keys import (enable_persistent_compilation_cache,  # noqa: E402,F401
                             policy_set_fingerprint)


#: per-row admission lane names (compiler/admission.py contract); the
#: lanes ride every non-mesh dispatch of a policy set with at least one
#: admission-dependent eligible rule, zero-filled when the scan carries
#: no admission data, so they add inputs — never executables
ADM_LANES = ('__admres__', '__adm_user__', '__adm_groups__',
             '__adm_roles__', '__adm_croles__', '__adm_hasinfo__',
             '__adm_excluded__')


def _adm_member2(lanes2d, ids):
    """∃ lane value ∈ ids over a [R, W] id lane (ids are static interned
    operand ids ≥ 0; -1 marks absent/out-of-vocabulary lane slots)."""
    ops = jnp.asarray(list(ids), dtype=jnp.int32)
    return jnp.any(lanes2d[:, :, None] == ops[None, None, :], axis=(1, 2))


def _adm_member1(lane1d, ids):
    ops = jnp.asarray(list(ids), dtype=jnp.int32)
    return jnp.any(lane1d[:, None] == ops[None, :], axis=1)


def _adm_match_graph(table, lanes):
    """[R, n_elig] bool: the jitted half of matches_resource_description
    for admission-eligible programs — the static filter tree
    (compiler/admission.py AdmProgram) over host-computed resource-shape
    atoms (``__admres__``) and the per-row user-info id lanes.  Exactly
    mirrors engine/match.py's _check_filter / _check_user_info /
    check_subjects semantics for the lowered vocabulary."""
    atoms = lanes['__admres__'] != 0
    user = lanes['__adm_user__']
    groups = lanes['__adm_groups__']
    roles = lanes['__adm_roles__']
    croles = lanes['__adm_croles__']
    hasinfo = lanes['__adm_hasinfo__'] != 0
    excluded = lanes['__adm_excluded__'] != 0
    false = jnp.zeros(user.shape, bool)

    def ui_ok(f):
        # excluded users skip role gates entirely, and ride the
        # exclude-group-roles Group subjects the host matcher appends
        ok = None
        if f.has_roles:
            hit = _adm_member2(roles, f.roles) if f.roles else false
            ok = excluded | hit
        if f.has_croles:
            hit = _adm_member2(croles, f.cluster_roles) \
                if f.cluster_roles else false
            ok = (excluded | hit) if ok is None else ok & (excluded | hit)
        if f.has_subjects:
            hit = false
            if f.subjects_ug:
                # User/Group names match any of groups ∪ {username}
                hit = hit | _adm_member2(groups, f.subjects_ug) | \
                    _adm_member1(user, f.subjects_ug)
            if f.subjects_sa:
                hit = hit | _adm_member1(user, f.subjects_sa)
            sub = hit | excluded
            ok = sub if ok is None else ok & sub
        return ok if ok is not None else ~false

    def filter_ok(f, mode):
        res_ok = atoms[:, f.atom]
        if mode == 'match':
            # without admission info the matcher drops user info: a
            # filter reduced to nothing is 'match cannot be empty'
            if not f.has_ui:
                return res_ok if f.has_res else false
            with_ui = res_ok & ui_ok(f)
            without = res_ok if f.has_res else false
            return jnp.where(hasinfo, with_ui, without)
        # exclude mode: user info always applies; an empty filter
        # never excludes (folded to 'none' at compile time)
        if not f.has_ui and not f.has_res:
            return false
        ok = res_ok
        if f.has_ui:
            ok = ok & ui_ok(f)
        return ok

    def combine(kind, oks):
        if kind == 'none' or not oks:
            return false
        acc = oks[0]
        for o in oks[1:]:
            acc = (acc & o) if kind == 'all' else (acc | o)
        return acc

    cols = []
    for p in table.programs:
        m = combine(p.match_kind,
                    [filter_ok(f, 'match') for f in p.match_filters])
        e = combine(p.exclude_kind,
                    [filter_ok(f, 'exclude') for f in p.exclude_filters])
        cols.append(m & ~e)
    return jnp.stack(cols, axis=1)


def build_evaluator(cps: CompiledPolicySet):
    enable_persistent_compilation_cache()
    from ..compiler.admission import compile_admission
    # frozen NamedTuple-of-tuples: trace-static by construction, so the
    # jitted closure below can never drift under a cached executable
    adm_table = compile_admission(cps)
    slot_prefix = {slot: f's{i}' for i, slot in enumerate(cps.slots)}
    gather_prefix = {g: f'g{k}' for k, g in enumerate(cps.gathers)}
    elem_prefix = {g: f'e{k}' for k, g in enumerate(cps.elem_gathers)}
    ctx_prefix = {v: f'cv{k}' for k, v in enumerate(cps.ctx_values)}
    _, _, _, array_paths = _needs_cached(cps)
    array_prefix = {path: f'a{j}' for j, path in enumerate(array_paths)}

    def check_prefix(check: CondCheck) -> str:
        if check.value_gather is not None:
            return elem_prefix[check.value_gather]
        return elem_prefix.get(check.gather) or gather_prefix[check.gather]

    dims: Dict[str, int] = {}

    def broadcast(arr, depth: int):
        """Append trailing element axes so arr has depth element dims."""
        # ktpu: noqa[KTPU203] -- deliberate: rank pads to the element
        # depth baked into this executable (one trace per depth)
        while arr.ndim < depth + 1:
            arr = arr[..., None]
        tgt = (arr.shape[0],) + (dims['E'],) * depth
        return jnp.broadcast_to(arr, tgt)

    leaf_cache: Dict[Tuple[Leaf, int], _K] = {}
    cond_cache: Dict[CondCheck, _K] = {}
    # per-trace accumulator of anyPattern child fail channels; the static
    # column map (program index → (aux base, n children)) is derived from
    # the programs so callers can index the fdet output past the P main
    # columns without waiting for a trace
    aux_acc: List[Any] = []
    any_meta: Dict[int, Tuple[int, int]] = {}
    _aux_cols = 0
    for _j, _prog in enumerate(cps.programs):
        _units = _prog.status.children if _prog.status.kind == 'seq' \
            else (_prog.status,)
        for _u in _units:
            if _u.kind == 'any':
                any_meta[_j] = (_aux_cols, len(_u.children))
                _aux_cols += len(_u.children)

    def eval_leaf(t, leaf: Leaf, depth: int) -> _K:
        key = (leaf, depth)
        if key in leaf_cache:
            return leaf_cache[key]
        if leaf.op == 'true':
            n = t[next(iter(t))].shape[0]
            shape = (n,) + (dims['E'],) * depth
            out = _K.const(shape, True)
        else:
            view = _View(t, slot_prefix[leaf.slot])
            out = leaf_op_tf(view, leaf.op, leaf.operand)
            sd = leaf.slot.depth
            if sd < depth:
                out = _K(broadcast(out.t, depth), broadcast(out.f, depth))
            elif sd > depth:
                # reduce ALL over valid elements (trackfail guards): true
                # iff every element satisfies; overflow blocks known-true
                tt, ff = out.t, out.f
                path = leaf.slot.path
                for lvl in range(sd, depth, -1):
                    prefix_path = _nth_star_prefix(path, lvl)
                    ap = array_prefix.get(prefix_path)
                    if ap is None:
                        # container not tracked: cannot reduce exactly
                        shape = tt.shape[:-1]
                        tt = jnp.zeros(shape, bool)
                        ff = jnp.zeros(shape, bool)
                        continue
                    count = t[f'{ap}_count']
                    ovf = t[f'{ap}_overflow']
                    valid = jnp.arange(tt.shape[-1]) < count[..., None]
                    tt = jnp.all(tt | ~valid, axis=-1) & ~ovf
                    ff = jnp.any(ff & valid, axis=-1)
                out = _K(tt, ff)
        leaf_cache[key] = out
        return out

    def _nth_star_prefix(path: Tuple[str, ...], lvl: int) -> Tuple[str, ...]:
        seen = 0
        for i, p in enumerate(path):
            if p == '*':
                seen += 1
                if seen == lvl:
                    return path[:i]
        raise AssertionError('bad star level')

    def eval_expr(t, expr: BoolExpr, depth: int) -> _K:
        if expr.kind == 'leaf':
            return eval_leaf(t, expr.leaf, depth)
        if expr.kind == 'cond':
            check = expr.cond
            if check in cond_cache:
                out = cond_cache[check]
            else:
                if check.value_gather is not None:
                    out = _cond_b_tf(t, check_prefix(check), check)
                elif check.ctx_value is not None:
                    out = _cond_ctx_tf(t, check_prefix(check),
                                       ctx_prefix[check.ctx_value], check)
                else:
                    out = cond_tf(t, check_prefix(check), check)
                cond_cache[check] = out
            # ktpu: noqa[KTPU203] -- deliberate rank specialization:
            # const-folded conditions broadcast to the element depth
            if depth > 0 and out.t.ndim == 1:
                out = _K(broadcast(out.t, depth), broadcast(out.f, depth))
            return out
        if expr.kind in ('any_elem', 'all_elem'):
            sub = eval_expr(t, expr.children[0], depth + 1)
            ap = array_prefix[expr.slot.path]
            arr_tag = t[f'{ap}_tag']
            count = t[f'{ap}_count']
            ovf = t[f'{ap}_overflow']
            valid = jnp.arange(sub.t.shape[-1]) < count[..., None]
            # missing/null arrays walk as [] (pss/checks.py `or []`);
            # map/scalar values would crash the host walk → undecidable
            known_arr = (arr_tag == TAG_ARRAY) | (arr_tag == TAG_MISSING) | \
                (arr_tag == TAG_NULL)
            if expr.kind == 'any_elem':
                tt = jnp.any(valid & sub.t, axis=-1)
                ff = jnp.all(~valid | sub.f, axis=-1) & ~ovf
            else:
                tt = jnp.all(~valid | sub.t, axis=-1) & ~ovf
                ff = jnp.any(valid & sub.f, axis=-1)
            return _K(known_arr & tt, known_arr & ff)
        parts = [eval_expr(t, c, depth) for c in expr.children]
        nd = max(p.t.ndim for p in parts)
        # ktpu: noqa[KTPU203] -- deliberate rank specialization: scalar
        # parts broadcast against element-scoped parts per trace
        if any(p.t.ndim != nd for p in parts):
            # scalar parts (const-folded conditions) broadcast against
            # element-scoped [R, FE] parts via trailing axes
            parts = [p if p.t.ndim == nd else
                     _K(p.t.reshape(p.t.shape + (1,) * (nd - p.t.ndim)),
                        p.f.reshape(p.f.shape + (1,) * (nd - p.f.ndim)))
                     for p in parts]
        if expr.kind == 'and':
            return _k_all(parts)
        if expr.kind == 'or':
            return _k_any(parts)
        if expr.kind == 'not':
            return parts[0].negate()
        raise ValueError(expr.kind)

    PASS, FAIL, SKIP = STATUS_PASS, STATUS_FAIL, STATUS_SKIP
    HOST, SKIPP = STATUS_HOST, STATUS_SKIP_PRECOND

    def from_k(k: _K, true_code: int, false_code: int):
        return jnp.where(k.t, jnp.int8(true_code),
                         jnp.where(k.f, jnp.int8(false_code),
                                   jnp.int8(HOST))).astype(jnp.int8)

    def site_fd(node: StatusExpr, ref):
        """Constant fail-detail plane for a node with a static fail site
        (site id in the high bits, element bytes zeroed)."""
        if node.fail_site is None:
            return jnp.full(ref.shape, -1, jnp.int32)
        return jnp.full(ref.shape, node.fail_site << 16, jnp.int32)

    def eval_status(t, node: StatusExpr, depth: int):
        """Returns (status int8, detail int8, fdet int32), each
        [R]+[E]*depth.  ``fdet`` identifies, for FAIL statuses, the walk
        position the host would report: site id in bits 16+, the
        outer/inner element indices in bytes 0/1; -1 = a FAIL here has no
        synthesizable message (host re-run).  A podSecurity program's
        FAIL carries the mask of its failed checks there instead (the
        ``seq`` branch)."""
        def zd(ref):
            return jnp.zeros(ref.shape, jnp.int8)

        def nofd(ref):
            return jnp.full(ref.shape, -1, jnp.int32)

        kind = node.kind
        if kind == 'const':
            n = t[next(iter(t))].shape[0]
            shape = (n,) + (dims['E'],) * depth
            s = jnp.full(shape, node.operand, jnp.int8)
            return s, jnp.zeros(shape, jnp.int8), nofd(s)
        if kind == 'leaf':
            s = from_k(eval_expr(t, node.expr, depth), PASS, FAIL)
            return s, zd(s), site_fd(node, s)
        if kind in ('precond', 'deny'):
            if kind == 'precond':
                s = from_k(eval_expr(t, node.expr, depth), PASS, SKIPP)
            else:
                s = from_k(eval_expr(t, node.expr, depth), FAIL, PASS)
            d = zd(s)
            # unresolvable condition variables preempt evaluation with the
            # host's substitution-error ERROR; the first missing variable
            # in traversal order picks the message (engine.py:388,431)
            for gather, msg_idx in (node.operand or ()):
                nf = t[f'{gather_prefix[gather]}_notfound']
                hit = nf & (s != STATUS_VAR_ERR)
                s = jnp.where(hit, jnp.int8(STATUS_VAR_ERR), s)
                d = jnp.where(hit, jnp.int8(msg_idx), d)
            # deny FAILs carry a static message (site-free): fdet 0 marks
            # 'synthesizable'; preconditions never FAIL
            fd = jnp.zeros(s.shape, jnp.int32) if kind == 'deny' else nofd(s)
            return s, d, fd
        if kind == 'failguard':
            # fdet-only guard: sub status unchanged; the fail path/message
            # is synthesizable only while every tracked anchor key is
            # present (else the host reports the empty-path message form)
            sub_s, sub_d, sub_fd = eval_status(t, node.sub, depth)
            g = eval_expr(t, node.expr, depth)
            return sub_s, sub_d, jnp.where(g.t, sub_fd, jnp.int32(-1))
        if kind == 'seq':
            s, d, fd = eval_status(t, node.children[0], depth)
            statuses = [s]
            for c in node.children[1:]:
                cs, cd, cfd = eval_status(t, c, depth)
                take = s == PASS
                s = jnp.where(take, cs, s)
                d = jnp.where(take, cd, d)
                fd = jnp.where(take, cfd, fd)
                statuses.append(cs)
            checks = [(c.pss_bit, cs)
                      for c, cs in zip(node.children, statuses)
                      if c.pss_bit is not None]
            if checks:
                # a podSecurity program (its leaves have no fail site,
                # and nothing else in its seq can FAIL): a FAIL's detail
                # is the mask of ALL the checks that failed, which the
                # host's check library then runs alone; -1 where one of
                # them is undecided here, and the library runs them all
                mask = jnp.zeros(s.shape, jnp.int32)
                decided = jnp.ones(s.shape, bool)
                for bit, cs in checks:
                    mask = mask | jnp.where(cs == FAIL, jnp.int32(1 << bit),
                                            jnp.int32(0))
                    decided = decided & ((cs == PASS) | (cs == FAIL))
                fd = jnp.where(s == FAIL,
                               jnp.where(decided, mask, jnp.int32(-1)), fd)
            return s, d, fd
        if kind == 'any':
            evals = [eval_status(t, c, depth) for c in node.children]
            stats = [e[0] for e in evals]
            ref = stats[0]
            taken = jnp.zeros(ref.shape, bool)
            pending_host = jnp.zeros(ref.shape, bool)
            all_skip = jnp.ones(ref.shape, bool)
            detail = jnp.zeros(ref.shape, jnp.int8)
            for i, s_i in enumerate(stats):
                this = (s_i == PASS) & ~taken & ~pending_host
                detail = jnp.where(this, jnp.int8(i), detail)
                taken = taken | this
                pending_host = pending_host | (s_i == HOST)
                all_skip = all_skip & (s_i == SKIP)
            out = jnp.where(
                taken, jnp.int8(PASS),
                jnp.where(pending_host, jnp.int8(HOST),
                          jnp.where(all_skip, jnp.int8(SKIP),
                                    jnp.int8(FAIL)))).astype(jnp.int8)
            # per-child fail channels for anyPattern message synthesis:
            # on an overall FAIL every child is FAIL or SKIP; -2 marks a
            # skipped child (omitted from the message), -1 an
            # unsynthesizable child failure
            for s_i, _, fd_i in evals:
                aux_acc.append(jnp.where(
                    s_i == SKIP, jnp.int32(-2),
                    jnp.where(s_i == FAIL, fd_i, jnp.int32(-1))))
            return out, detail, nofd(out)
        if kind in ('cond', 'global', 'equality', 'negation'):
            view = _View(t, slot_prefix[node.slot])
            present = view.tag != TAG_MISSING
            # ktpu: noqa[KTPU203] -- deliberate: slot rank vs node depth
            # is a compile-time program property, not a batch shape
            if view.tag.ndim - 1 < depth:
                present = broadcast(present, depth)
            if kind == 'negation':
                s = jnp.where(present, jnp.int8(FAIL),
                              jnp.int8(PASS)).astype(jnp.int8)
                return s, zd(s), site_fd(node, s)
            sub_s, sub_d, sub_fd = eval_status(t, node.sub, depth)
            if kind == 'equality':
                s = jnp.where(present, sub_s, jnp.int8(PASS)).astype(jnp.int8)
                return s, sub_d, sub_fd
            # cond: absent→SKIP; sub FAIL/SKIP→SKIP; HOST→HOST
            # global: absent→PASS; sub FAIL/SKIP→SKIP; HOST→HOST
            absent_code = SKIP if kind == 'cond' else PASS
            nonpass = jnp.where(sub_s == HOST, jnp.int8(HOST),
                                jnp.int8(SKIP))
            s = jnp.where(
                ~present, jnp.int8(absent_code),
                jnp.where(sub_s == PASS, jnp.int8(PASS),
                          nonpass)).astype(jnp.int8)
            return s, zd(s), nofd(s)
        if kind in ('forall', 'exists', 'scalars'):
            ap = array_prefix[node.slot.path]
            arr_tag = t[f'{ap}_tag']
            count = t[f'{ap}_count']
            ovf = t[f'{ap}_overflow']
            valid = jnp.arange(dims['E']) < count[..., None]
            if kind == 'scalars':
                # scalar-vs-array failures report the ARRAY's path
                # (validate_pattern.py:61-66), so fdet needs no element
                k = eval_expr(t, node.expr, depth + 1)
                any_fail = jnp.any(valid & k.f, axis=-1)
                any_unk = jnp.any(valid & k.unknown(), axis=-1) | ovf
                s = jnp.where(
                    arr_tag != TAG_ARRAY, jnp.int8(FAIL),
                    jnp.where(any_fail, jnp.int8(FAIL),
                              jnp.where(any_unk, jnp.int8(HOST),
                                        jnp.int8(PASS)))).astype(jnp.int8)
                return s, zd(s), site_fd(node, s)
            sub_s, _, sub_fd = eval_status(t, node.sub, depth + 1)
            if kind == 'exists':
                # reference: pkg/engine/anchor/handlers.go:228 — missing
                # key passes, non-list fails, ≥1 element must validate;
                # both failure modes report the anchored key's path
                satisfied = jnp.any(valid & (sub_s == PASS), axis=-1)
                maybe = jnp.any(valid & (sub_s == HOST), axis=-1) | ovf
                s = jnp.where(
                    arr_tag == TAG_MISSING, jnp.int8(PASS),
                    jnp.where(arr_tag != TAG_ARRAY, jnp.int8(FAIL),
                              jnp.where(satisfied, jnp.int8(PASS),
                                        jnp.where(maybe, jnp.int8(HOST),
                                                  jnp.int8(FAIL)))))
                return s.astype(jnp.int8), zd(s), site_fd(node, s)
            # forall (validateArrayOfMaps, validate.go:218)
            fail_at = valid & (sub_s == FAIL)
            any_fail = jnp.any(fail_at, axis=-1)
            any_host = jnp.any(valid & (sub_s == HOST), axis=-1) | ovf
            any_skip = jnp.any(valid & (sub_s == SKIP), axis=-1)
            any_pass = jnp.any(valid & (sub_s == PASS), axis=-1)
            s = jnp.where(
                arr_tag != TAG_ARRAY, jnp.int8(FAIL),
                jnp.where(any_fail, jnp.int8(FAIL),
                          jnp.where(any_host, jnp.int8(HOST),
                                    jnp.where(any_skip & ~any_pass,
                                              jnp.int8(SKIP),
                                              jnp.int8(PASS)))))
            # the host raises on the FIRST failing element in index order
            # (validate_pattern.py:136); an undecidable element BEFORE it
            # could itself be the true first failure → path ambiguous
            idx = jnp.argmax(fail_at, axis=-1)
            before = jnp.arange(dims['E']) < idx[..., None]
            ambiguous = jnp.any(before & valid & (sub_s == HOST), axis=-1)
            sel = jnp.take_along_axis(
                sub_fd, idx[..., None].astype(jnp.int32), axis=-1)[..., 0]
            elem_fd = jnp.where(
                ambiguous | (sel < 0), jnp.int32(-1),
                sel | (idx.astype(jnp.int32) << (8 * depth)))
            fd = jnp.where(arr_tag != TAG_ARRAY, site_fd(node, s), elem_fd)
            return s.astype(jnp.int8), zd(s), fd
        if kind == 'foreach':
            # engine.py:611 _validate_foreach: entries in order; the
            # first non-pass element outcome decides; zero applied
            # elements overall → 'rule skipped'
            n = t[next(iter(t))].shape[0]
            nonpass = jnp.zeros(n, bool)
            unknown = jnp.zeros(n, bool)
            apply_any = jnp.zeros(n, bool)
            # fd_ok: the FIRST entry with any non-pass/unknown outcome
            # decided by a deny-condition element fail — its message is the
            # static 'validation failure: …'; a last-index ERROR element or
            # an earlier undecidable entry makes the outcome/message
            # ambiguous (engine.py:663 error-continue semantics)
            fd_ok = jnp.zeros(n, bool)
            for entry in node.operand:
                lp = gather_prefix[entry.list_gather]
                lkind = t[f'{lp}_kind']
                lcount = t[f'{lp}_count']
                lovf = t[f'{lp}_overflow']
                # list query failures (NotFound / interpreter errors) skip
                # the entry silently (engine.py:615-618) → kind 0
                active = lkind != 0
                lview = _View(t, lp)
                gw = lview.tag.shape[-1]
                valid = (jnp.arange(gw) < lcount[:, None]) & \
                    (lview.tag != TAG_NULL)  # null elements are skipped
                # element variable errors (first missing var → ERROR elem)
                elem_err = jnp.zeros((n, gw), bool)
                for eg in entry.err_gathers:
                    elem_err = elem_err | t[f'{elem_prefix[eg]}_notfound']
                def at_elem(k: _K) -> _K:
                    # ktpu: noqa[KTPU203] -- deliberate rank
                    # specialization for const-folded conditions
                    if k.t.ndim == 1:
                        return _K(k.t[:, None], k.f[:, None])
                    return k
                if entry.precond is not None:
                    pre = at_elem(eval_expr(t, entry.precond, 0))
                else:
                    pre = _K.const((n, gw), True)
                deny = at_elem(eval_expr(t, entry.deny, 0))
                e_fail = ~elem_err & pre.t & deny.t
                e_pass = ~elem_err & pre.t & deny.f
                e_unknown = ~elem_err & (pre.unknown() |
                                         (pre.t & deny.unknown()))
                any_fail = jnp.any(valid & e_fail, axis=-1)
                # an ERROR element returns only at the true last index
                # (engine.py:663-665); overflow hides the true length
                last_err = jnp.take_along_axis(
                    elem_err & valid,
                    jnp.maximum(lcount - 1, 0)[:, None],
                    axis=-1)[..., 0] & ~lovf
                entry_nonpass = active & (any_fail | last_err)
                entry_unknown = active & (
                    jnp.any(valid & e_unknown, axis=-1) | lovf) & \
                    ~entry_nonpass
                entry_apply = active & jnp.any(valid & e_pass, axis=-1)
                fd_ok = fd_ok | (~(nonpass | unknown) & active & any_fail)
                nonpass = nonpass | entry_nonpass
                unknown = unknown | entry_unknown
                apply_any = apply_any | entry_apply
            s = jnp.where(
                nonpass, jnp.int8(FAIL),
                jnp.where(unknown, jnp.int8(HOST),
                          jnp.where(apply_any, jnp.int8(PASS),
                                    jnp.int8(SKIP)))).astype(jnp.int8)
            fd = jnp.where(fd_ok, jnp.int32(0), jnp.int32(-1))
            return s, jnp.zeros(n, jnp.int8), fd
        if kind == 'trackfail':
            sub_s, sub_d, sub_fd = eval_status(t, node.sub, depth)
            guard = eval_expr(t, node.expr, depth)
            s = jnp.where(sub_s == FAIL,
                          jnp.where(guard.t, jnp.int8(FAIL),
                                    jnp.int8(HOST)),
                          sub_s).astype(jnp.int8)
            return s, sub_d, sub_fd
        raise ValueError(f'unknown status kind {kind!r}')

    # whole-program dedup, computed STATICALLY: replicated/near-duplicate
    # policies (the common case in large real policy sets — and the
    # 1k-policy admission benchmark) compile identical status trees.
    # Each unique tree is traced ONCE; the compact (match-carrying) path
    # keeps the whole device graph AND the d2h readback in unique space
    # — duplicate columns are expanded on the host with one numpy
    # gather, so a 1000-policy replicated set compiles and ships like
    # its ~30 unique rules.
    uniq_idx_list: List[int] = []
    uniq_trees: List[Any] = []
    _memo: Dict[Any, int] = {}
    for _prog in cps.programs:
        try:
            _u = _memo.get(_prog.status)
            _memo_key = _prog.status
        except TypeError:  # unhashable operand somewhere in the tree
            _u = None
            _memo_key = None
        if _u is None:
            _u = len(uniq_trees)
            uniq_trees.append(_prog.status)
            if _memo_key is not None:
                _memo[_memo_key] = _u
        uniq_idx_list.append(_u)
    n_uniq = len(uniq_trees)
    uniq_idx_np = np.asarray(uniq_idx_list, np.int64) if uniq_idx_list \
        else np.zeros(0, np.int64)
    # aux channels per unique tree (anyPattern child fail channels; at
    # most one 'any' unit per program — a rule has one validate form)
    uniq_aux_base: List[int] = []
    uniq_any: List[Tuple[int, int]] = []  # (unique idx, n children)
    _aux_u_total = 0
    for _u, _tree in enumerate(uniq_trees):
        uniq_aux_base.append(_aux_u_total)
        _units = _tree.children if _tree.kind == 'seq' else (_tree,)
        for _unit in _units:
            if _unit.kind == 'any':
                uniq_any.append((_u, len(_unit.children)))
                _aux_u_total += len(_unit.children)
    # frozen before any trace closes over it: a tuple can never drift
    # under a cached executable (ktpu-lint KTPU201)
    uniq_any = tuple(uniq_any)
    n_cols = len(cps.programs) + _aux_cols
    n_cols_u = n_uniq + _aux_u_total
    # program-space column -> unique-space column, for host expansion
    expand_idx_np = np.zeros(n_cols, np.int64)
    expand_idx_np[:len(cps.programs)] = uniq_idx_np
    for _j in sorted(any_meta, key=lambda jj: any_meta[jj][0]):
        _base, _cnt = any_meta[_j]
        _ub = uniq_aux_base[uniq_idx_list[_j]]
        for _c in range(_cnt):
            expand_idx_np[len(cps.programs) + _base + _c] = \
                n_uniq + _ub + _c
    expand_identity = bool(
        n_cols == n_cols_u and
        np.array_equal(expand_idx_np, np.arange(n_cols)))
    # program columns sharing one unique tree, for host match folding
    uniq_groups: List[np.ndarray] = [
        np.flatnonzero(uniq_idx_np == u) for u in range(n_uniq)]

    def evaluate_unique(t: Dict[str, jnp.ndarray]):
        """Trace the unique status trees only; returns unique-space
        (s_u, d_u, fdet_u) with aux channels appended past n_uniq."""
        leaf_cache.clear()
        cond_cache.clear()
        aux_acc.clear()
        # element width of this batch (dynamic; see encode._measure_elems)
        # — probed from slot ('sN_') or array ('aN_') tags, not gathers
        dims['E'] = next(
            (arr.shape[1] for name, arr in sorted(t.items())
             if name.endswith('_tag') and arr.ndim >= 2
             and name[0] in 'sa'), 0)
        cols, dets, fds = [], [], []
        for tree in uniq_trees:
            s, d, fd = eval_status(t, tree, 0)
            cols.append(s)
            dets.append(d)
            fds.append(fd)
        if not cols:
            n = t[next(iter(t))].shape[0] if t else 0
            z = jnp.zeros((n, 0), jnp.int8)
            return z, z, jnp.zeros((n, 0), jnp.int32)
        s_u = jnp.stack(cols, axis=1)
        d_u = jnp.stack(dets, axis=1)
        fd_u = jnp.stack(fds, axis=1)
        if aux_acc:
            fd_u = jnp.concatenate(
                [fd_u, jnp.stack(list(aux_acc), axis=1)], axis=1)
        return s_u, d_u, fd_u

    def evaluate(t: Dict[str, jnp.ndarray]):
        """Program-space evaluation (mesh path / raw consumers): unique
        results expanded by a device-side column gather."""
        s_u, d_u, fdet_u = evaluate_unique(t)
        if n_uniq == 0:
            return s_u, d_u, fdet_u
        if expand_identity:
            return s_u, d_u, fdet_u
        pid = uniq_idx_np
        statuses = s_u[:, pid]
        details = d_u[:, pid]
        fdet = fdet_u[:, expand_idx_np]
        return statuses, details, fdet

    layout_holder: Dict[str, Any] = {'layout': None}

    #: fixed per-row budget of fail-detail cells shipped back to the
    #: host.  fdet is ~75% of the chunk's device→host bytes; only
    #: (matched, FAIL) cells are ever read, so the device compacts them
    #: to the first K relevant columns.  Overflow rows keep exactness: their
    #: missing cells read FDET_BEYOND_BUDGET → host materialization.
    fdet_k = int(os.environ.get('KTPU_FDET_K', '32'))

    def evaluate_packed(packed: Dict[str, jnp.ndarray]):
        # ktpu: noqa[KTPU201] -- layout is trace-static by contract:
        # compile_lock serializes every trace, and the AOT cache key
        # bakes the batch layout into the executable's identity
        t = unpack_batch(packed, layout_holder['layout'])
        # ragged batches: rows past the live row count are canonical-
        # capacity padding.  Per-row outputs for them are sliced off on
        # the host; everything that selects or reduces ACROSS rows in
        # the graph masks them here so one compiled capacity serves
        # every occupancy with bit-identical output.
        rowvalid = t.pop('__rowvalid__', None)
        match = t.pop('__match__', None)
        adm_in = {name: t.pop(name) for name in ADM_LANES if name in t}
        if not t and rowvalid is not None:
            # slot-free policy sets (e.g. pure deny-by-subject rules —
            # exactly the admission-lane vocabulary) still need one
            # reference array for constant-tree row shapes
            t = {'__rowref__': rowvalid}
        if match is None:
            return evaluate(t)
        # compact form, all in UNIQUE space (match arrives pre-folded to
        # [R, n_uniq]): ship (statuses|details) as one int8 buffer and
        # the (matched & FAIL) fail-detail cells as [cols | fds]; the
        # host expands duplicates with one gather (expand_compact)
        s_u, d_u, fdet_u = evaluate_unique(t)
        rel_main = (s_u == FAIL) & (match != 0)
        if rowvalid is not None:
            rel_main = rel_main & (rowvalid != 0)[:, None]
        parts = [rel_main]
        for u, cnt in uniq_any:
            parts.append(jnp.broadcast_to(rel_main[:, u:u + 1],
                                          (s_u.shape[0], cnt)))
        rel = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        c = fdet_u.shape[1]
        # fixed budget: rows overflowing it degrade to exact host
        # materialization, never wrong answers
        k = min(fdet_k, c)
        col_idx = jnp.arange(c, dtype=jnp.int32)
        keys = jnp.where(rel, col_idx, jnp.int32(c))
        order = jnp.sort(keys, axis=1)[:, :k]
        fds = jnp.take_along_axis(
            fdet_u, jnp.minimum(order, c - 1).astype(jnp.int32), axis=1)
        out32 = jnp.concatenate([order, fds.astype(jnp.int32)], axis=1)
        out8 = jnp.concatenate([s_u, d_u], axis=1)
        if adm_table is not None and len(adm_in) == len(ADM_LANES):
            # per-row admission match for eligible programs, decided
            # in-graph and shipped back as extra int8 columns (the host
            # replaces its conservative match upper bound with these
            # before assembly; rows the encoder marked non-valid are
            # ignored there)
            adm = _adm_match_graph(adm_table, adm_in).astype(jnp.int8)
            out8 = jnp.concatenate([out8, adm], axis=1)
        return out8, out32

    jitted = jax.jit(evaluate_packed)
    # compile/AOT keys derive from the fingerprint of the policies THIS
    # evaluator compiles — the whole set in monolithic mode, one
    # partition's members under KTPU_PARTITIONS (partition/keys.py is
    # the sanctioned source; ktpu-lint KTPU508 keeps whole-set
    # fingerprints out of executable cache keys elsewhere)
    from ..partition.keys import compile_fingerprint
    fingerprint = compile_fingerprint(cps)
    exec_cache: Dict[str, Any] = {}
    # id(compiled) -> ledger key: dispatch-site attribution for the
    # executable lifecycle ledger without re-deriving the cache key per
    # call (entries live exactly as long as exec_cache holds them)
    exec_keys: Dict[int, str] = {}
    # input signatures the jitted fallback has already traced — mirrors
    # jax.jit's own cache key well enough for hit/miss telemetry on the
    # paths where the AOT executable cache is unavailable (mesh, >1
    # local device)
    jit_seen: set = set()
    # one lock covers exec_cache AND every trace of evaluate_packed:
    # the trace reads layout_holder, so an unsynchronized concurrent
    # call could bake another batch shape's layout into the executable
    # (and the AOT store would persist the poisoned artifact to disk)
    compile_lock = __import__('threading').RLock()

    def _compiled_for(packed, layout) -> Optional[Any]:
        """Executable for this input signature: memory → AOT disk →
        trace+compile (and populate both).  None → mesh-sharded inputs
        or AOT disabled; caller falls back to the jitted path."""
        import time as _time
        from ..compiler import aot
        from ..observability import device as devtel
        from ..observability import executables as exectel
        key = aot.executable_cache_key(fingerprint, packed,
                                       extra=(str(fdet_k),))
        if key is None:
            return None
        with compile_lock:
            hit = exec_cache.get(key)
            if hit is not None:
                devtel.record_cache('hit')
                return hit
        # the packed buffers all lead with the resource axis, so any
        # buffer's first dim is the canonical row capacity (ledger
        # attribute; pack_batch coalesces per dtype, capacity-invariant)
        capacity = next((int(v.shape[0]) for v in packed.values()
                         if getattr(v, 'ndim', 0) >= 1), 0) \
            if exectel.enabled() else 0
        # the disk deserialize runs OUTSIDE the compile lock: it never
        # touches layout_holder, and the shape warmer loads the
        # canonical capacities on a thread pool — serializing the
        # (tens-of-seconds) deserializes here would make warm-up a sum
        # instead of a max.  Two racers on ONE key at worst both
        # deserialize; setdefault keeps a single winner.
        with devtel.stage('compile') as st:
            t0 = _time.monotonic()
            loaded = aot.load_executable(key)
            if loaded is not None:
                devtel.record_cache('aot_load')
                st.set_attribute('cache', 'aot_load')
                with compile_lock:
                    winner = exec_cache.setdefault(key, loaded)
                    if winner is loaded and exectel.enabled():
                        exec_keys[id(winner)] = key
                        exectel.record_build(
                            key, fingerprint=fingerprint,
                            capacity=capacity, source='aot_load',
                            build_s=_time.monotonic() - t0,
                            compiled=winner)
                    return winner
            with compile_lock:
                hit = exec_cache.get(key)
                if hit is not None:
                    devtel.record_cache('hit')
                    return hit
                layout_holder['layout'] = layout
                t0 = _time.monotonic()
                xla_hits = aot.xla_cache_hits()
                loaded = jitted.lower(packed).compile()
                fresh = aot.xla_cache_hits() == xla_hits
                devtel.record_cache('miss')
                st.set_attribute('cache', 'miss')
                if exectel.enabled():
                    exec_keys[id(loaded)] = key
                    exectel.record_build(
                        key, fingerprint=fingerprint, capacity=capacity,
                        source='fresh_compile' if fresh
                        else 'persistent_xla',
                        build_s=_time.monotonic() - t0, compiled=loaded)
                if fresh:
                    # only what XLA compiled here: an executable the
                    # persistent XLA cache handed back is not stored
                    # again (aot.xla_cache_hits says why)
                    aot.store_executable_async(key, loaded)
                    devtel.record_cache('aot_store')
                exec_cache[key] = loaded
                return loaded

    def _evict_aot(packed) -> None:
        """Drop a poisoned AOT entry (memory + disk) so the next call
        recompiles instead of re-failing."""
        from ..compiler import aot
        key = aot.executable_cache_key(fingerprint, packed,
                                       extra=(str(fdet_k),))
        if key is None:
            return
        with compile_lock:
            dropped = exec_cache.pop(key, None)
            if dropped is not None:
                exec_keys.pop(id(dropped), None)
        aot.evict_executable(key, reason='execute_failed')

    def call(packed: Dict[str, Any],
             layout: Dict[str, Tuple[str, int, int, Tuple[int, ...]]]):
        # i64 lanes are required: quantity milli-values span past 2^31.
        # Scope x64 to this call instead of flipping the process-global
        # flag at import time.
        import time as _time
        from ..observability import device as devtel
        from ..observability import executables as exectel
        with jax.enable_x64(True):
            # a compile error surfaces from here: the AOT store's own
            # load/store failures are handled inside compiler/aot.py
            compiled = _compiled_for(packed, layout)
            if compiled is not None:
                try:
                    with devtel.stage('device_eval') as st:
                        _stamp_coverage(st)
                        if exectel.enabled():
                            t0 = _time.monotonic()
                            out = compiled(packed)
                            exectel.record_dispatch(
                                exec_keys.get(id(compiled), ''),
                                _time.monotonic() - t0, outputs=out)
                            return out
                        return compiled(packed)
                except Exception:  # noqa: BLE001 - a deserialized
                    # executable can fail at EXECUTE time (e.g. machine-
                    # feature mismatch); evict it and fall through to a
                    # fresh trace+compile rather than surfacing a device
                    # failure to the circuit breaker
                    _evict_aot(packed)
            with compile_lock:
                layout_holder['layout'] = layout
                exec_on = exectel.enabled()
                pkey = ''
                if devtel.enabled() or exec_on:
                    sig = tuple(
                        (k, str(v.dtype), tuple(v.shape))
                        for k, v in sorted(packed.items()))
                    if exec_on:
                        # no AOT cache key on this path (mesh / AOT
                        # off): a process-local pseudo-key names the
                        # jit-backed executable in the ledger
                        pkey = f'jit:{fingerprint[:12]}:' \
                               f'{abs(hash(sig)):x}'
                    if sig not in jit_seen:
                        # first call at this signature pays jit trace +
                        # XLA compile inside the dispatch — time it as
                        # the compile stage (jit caches internally, so
                        # a separate lower().compile() would double-pay)
                        jit_seen.add(sig)
                        devtel.record_cache('miss')
                        with devtel.stage('compile') as st:
                            st.set_attribute('cache', 'miss')
                            t0 = _time.monotonic()
                            from ..compiler import aot
                            xla_hits = aot.xla_cache_hits()
                            out = jitted(packed)
                            if exec_on:
                                exectel.record_build(
                                    pkey, fingerprint=fingerprint,
                                    capacity=next(
                                        (int(v.shape[0])
                                         for v in packed.values()
                                         if getattr(v, 'ndim', 0) >= 1),
                                        0),
                                    source='persistent_xla'
                                    if aot.xla_cache_hits() > xla_hits
                                    else 'fresh_compile',
                                    build_s=_time.monotonic() - t0,
                                    outputs=out)
                            return out
                    devtel.record_cache('hit')
                with devtel.stage('device_eval') as st:
                    _stamp_coverage(st)
                    if pkey:
                        t0 = _time.monotonic()
                        out = jitted(packed)
                        exectel.record_dispatch(
                            pkey, _time.monotonic() - t0, outputs=out)
                        return out
                    return jitted(packed)

    call.jitted = jitted
    call.raw = evaluate
    call.layout_holder = layout_holder
    call.compile_lock = compile_lock
    call.any_meta = any_meta
    call.fingerprint = fingerprint
    call.n_cols = n_cols
    call.n_programs = len(cps.programs)
    call.n_uniq = n_uniq
    call.n_cols_u = n_cols_u
    call.uniq_idx = uniq_idx_np
    call.expand_idx = expand_idx_np
    call.expand_identity = expand_identity
    call.uniq_groups = uniq_groups
    call.adm_table = adm_table
    call.n_adm = len(adm_table.programs) if adm_table is not None else 0
    call.adm_cols = adm_table.program_cols() if adm_table is not None \
        else np.zeros(0, np.int64)
    return call


def _stamp_coverage(st) -> None:
    """Attribute the device-coverage ratio of the most recently
    completed scan onto a device_eval stage span (the assembly that
    decides THIS dispatch's ratio runs after it; the ledger's last
    ratio is the freshest attributable value)."""
    from ..observability import coverage
    ratio = coverage.last_ratio()
    if ratio is not None:
        st.set_attribute('device_coverage_ratio', round(ratio, 4))


def fold_match_unique(mm: np.ndarray, evaluator) -> np.ndarray:
    """Fold a program-space [R, P] match mask to unique-program space
    [R, U] (OR over duplicate columns) for the compact device path."""
    if evaluator.n_uniq == len(evaluator.uniq_idx) or mm.shape[1] == 0:
        return mm
    out = np.zeros((mm.shape[0], evaluator.n_uniq), mm.dtype)
    for u, cols in enumerate(evaluator.uniq_groups):
        if cols.size == 1:
            out[:, u] = mm[:, cols[0]]
        else:
            out[:, u] = mm[:, cols].max(axis=1)
    return out


def expand_compact(out8: np.ndarray, out32: np.ndarray, evaluator):
    """Reconstruct program-space (statuses, details, dense fdet,
    admission-match) from the unique-space compact device outputs.
    Cells beyond the per-row budget read ``FDET_BEYOND_BUDGET``, which
    downstream message synthesis treats like -1, as 'materialize on
    host' — exactness is never lost.  The trailing admission columns
    (None when the policy set has no admission-eligible rules) are the
    in-graph per-row match decisions for ``evaluator.adm_cols``."""
    n_adm = getattr(evaluator, 'n_adm', 0)
    width = out8.shape[1] - n_adm
    n_uniq = width // 2
    s_u = out8[:, :n_uniq]
    d_u = out8[:, n_uniq:n_uniq * 2]
    adm = out8[:, width:] if n_adm else None
    k = out32.shape[1] // 2
    cols = out32[:, :k]
    fds = out32[:, k:]
    dense_u = np.full((out8.shape[0], evaluator.n_cols_u), -1, np.int32)
    rr, kk = np.nonzero(cols < evaluator.n_cols_u)
    dense_u[rr, cols[rr, kk]] = fds[rr, kk]
    if 0 < k < evaluator.n_cols_u:
        # the budget can bind: a row whose last slot is used shipped its
        # k lowest relevant columns, so every relevant one above that
        # slot's was lost.  Those read FDET_BEYOND_BUDGET and not -1, so
        # that the ledger can tell them from a program without a site
        # (a row with exactly k relevant columns has none above)
        full = np.flatnonzero(cols[:, k - 1] < evaluator.n_cols_u)
        if full.size:
            above = np.arange(evaluator.n_cols_u) > \
                cols[full, k - 1][:, None]
            dense_u[full] = np.where(above, FDET_BEYOND_BUDGET,
                                     dense_u[full])
    if evaluator.expand_identity:
        return s_u, d_u, dense_u, adm
    pid = evaluator.uniq_idx
    return (s_u[:, pid], d_u[:, pid], dense_u[:, evaluator.expand_idx],
            adm)


#: the layouts of loose lane sets, memoized by their signature: the
#: paths that still copy (warm-up dispatches, the partitions' scanners,
#: a scan that ships no match plane) repeat a few signatures, and the
#: grouping costs more than a small batch's concatenation
_PACK_PLANS: Dict[Tuple, Tuple] = {}


def pack_batch(tensors: Dict[str, np.ndarray]):
    """All lanes as ONE flat [R, W] buffer per dtype, and their layout.

    Transferring hundreds of lanes one by one costs a host→device
    transfer apiece, and per-transfer latency — not bandwidth — then
    bounds the pipeline.  Every lane has the resource axis leading, so
    each is a run of columns of its dtype's buffer
    (``compiler/packing.py`` ``plan_layout``); the evaluator unpacks
    with static slices + reshapes that XLA folds away.  Five dtypes →
    five host→device transfers per chunk.

    Lanes that an arena's encode wrote (``compiler/encode.py``
    ``LaneArena``) are views of such buffers already: those are handed
    over as they are, after the few lanes that joined the batch since
    (``__match__``, the admission lanes) were copied into the columns
    kept for them.  Whether they are is decided lane by lane, by
    identity (``PackedSet.takes``).  Anything else — loose arrays, or a
    set that lacks a joining lane — is concatenated into new buffers,
    in the same layout.  Returns ``(packed, layout)``."""
    return _pack_batch(tensors)[:2]


def _pack_batch(tensors: Dict[str, np.ndarray]):
    """:func:`pack_batch`, and which way it went: ``'view'`` or
    ``'copy'``."""
    owner = getattr(tensors, 'owner', None)
    if owner is not None and owner.takes(tensors):
        return dict(owner.buffers), owner.layout, 'view'
    sig = tuple((name, arr.dtype.num, arr.shape)
                for name, arr in sorted(tensors.items()))
    plan = _PACK_PLANS.get(sig)
    if plan is None:
        plan = plan_layout({name: (arr.dtype, arr.shape)
                            for name, arr in tensors.items()})
        if len(_PACK_PLANS) > 256:
            _PACK_PLANS.clear()
        _PACK_PLANS[sig] = plan
    layout, groups = plan
    packed: Dict[str, np.ndarray] = {}
    for buf_name, _dtype, _width, names in groups:
        r = tensors[names[0]].shape[0]
        parts = [tensors[n].reshape(r, -1) for n in names]
        packed[buf_name] = parts[0] if len(parts) == 1 \
            else np.concatenate(parts, axis=1)
    return packed, layout, 'copy'


def shard_batch(tensors: Dict[str, np.ndarray], mesh=None,
                axis: str = 'data') -> Dict[str, Any]:
    """Pack + place batch tensors on the default device, or sharded
    over a 1-D mesh (the resource axis of packed buffers is axis 0).
    int64 inputs are transferred inside an x64 scope so they are not
    downcast.  Returns (packed_device_dict, layout)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..observability import device as devtel
    with devtel.stage('pack') as st:
        packed, layout, via = _pack_batch(tensors)
        st.set_attribute('via', via)
        devtel.record_pack(via)
    with jax.enable_x64(True), devtel.stage('h2d') as st:
        st.set_attribute('bytes', sum(v.nbytes for v in packed.values()))
        if mesh is None:
            return {k: jnp.asarray(v) for k, v in packed.items()}, layout
        out = {}
        for k, v in packed.items():
            spec = P(axis, *([None] * (v.ndim - 1)))
            out[k] = jax.device_put(v, NamedSharding(mesh, spec))
        return out, layout
