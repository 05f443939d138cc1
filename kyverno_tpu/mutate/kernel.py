"""The device mutate evaluator: lanes → (status, edit bitmask, reason).

One jitted straight-line program per lowered policy set, batched over
resources and edit sites.  Per (resource, site) it decides whether the
edit applies — leaf missing → apply; add-only anchors skip present
leaves; an element site with no element to patch (``istate`` 3) never
applies; otherwise apply iff the encoded value differs from the patch
constant (Python equality semantics: bool/int/float compare through the
exact milli lane, strings through length + byte window; cross-kind
never equal except the numeric tower) — then reduces sites to per-rule
outputs:

  status  i8 [R, NR]   0 = SKIP (no edits), 1 = PASS (edit list
                       non-empty), 2 = FALLBACK (host applies)
  edits   i64 [R, NR]  bitmask over the rule's sites (bit k = site k
                       applies); the host decodes it into a (slot,
                       value) edit list and patches the JSON
  reason  i8 [R, NR]   first-fault attribution for FALLBACK rows, in
                       the host fast path's check order: 1 = a
                       json6902 replace path is missing, 2 = a non-map
                       intermediate, 3 = equality undecidable in the
                       encoded lanes, 4 = a list of an element site is
                       longer than ``MAX_ELEMENTS`` or of a shape the
                       lanes cannot stand for (``llen`` -1), 5 = a
                       per-element condition the device cannot decide
                       (``CTAG_ESCAPE``; the engine answers the row)

A program with ``foreach`` rules compares each per-element condition's
key lanes with its static values, ANDs the ``all`` block (and ORs the
``any`` block) per (row, rule, slot), gates every element site of the
rule at that slot on the result, and returns a fourth output:

  elem    i32 [R, NR]  of a foreach rule, bit i: its conditions held at
                       slot i; bit ``MAX_ELEMENTS`` + i: one of them
                       could not be decided there (0 for other rules)

A program without them computes exactly what it did before foreach
lowered, and returns three.

The kernel is intentionally tiny (a few element-wise ops and one
masked reduction per output) — it is not AOT-persisted; XLA
compiles it once per canonical batch capacity (compiler/shapes.py).
Rows past the live row count (the ``valid`` lane) are capacity
padding: their statuses, edit bitmasks, and reasons are forced to
SKIP/0 inside the jitted program so no cross-row consumer can ever
observe them.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from ..compiler.ir import TAG_BOOL, TAG_FLOAT, TAG_INT, TAG_MISSING, \
    TAG_STRING
from .encode import (CTAG_ESCAPE, ISTATE_NO_ELEMENT, condition_window,
                     exact_milli, string_window)
from .plan import (CONDITION_OPERATORS, MAX_ELEMENTS, MutateSetProgram,
                   split_element_path)

#: per-(resource, rule) device statuses
MUT_SKIP = 0
MUT_PASS = 1
MUT_FALLBACK = 2

#: FALLBACK reason codes (decoded to taxonomy slugs in scanner.py)
RC_NONE = 0
RC_REPLACE_MISSING = 1
RC_NON_DICT = 2
RC_UNDECIDABLE = 3
RC_LIST_SHAPE = 4
RC_PRECONDITION_ESCAPE = 5


class MutateKernel:
    """Compile-time constants + the jitted evaluator for one program."""

    def __init__(self, program: MutateSetProgram):
        sites = [(ri, k, site)
                 for ri, prog in enumerate(program.programs)
                 for k, site in enumerate(prog.sites)]
        self.n_rules = len(program.programs)
        self.n_sites = len(sites)
        self.width = string_window(program)
        s, w = self.n_sites, self.width
        self._t_is_num = np.zeros(s, bool)
        self._t_milli = np.zeros(s, np.int64)
        self._t_len = np.zeros(s, np.int32)
        self._t_bytes = np.zeros((s, w), np.uint8)
        self._add_only = np.zeros(s, bool)
        self._replace = np.zeros(s, bool)
        # site → rule selector and the site's bit weight in its rule's
        # edit mask; both feed the per-rule reductions
        self._onehot = np.zeros((s, self.n_rules), bool)
        self._bit_w = np.zeros(s, np.int64)
        # list → rule selector: the rules with an element site on the
        # list of each ``llen`` column
        self._list_rules = np.zeros((program.n_list_columns,
                                     self.n_rules), bool)
        for idx, (ri, k, site) in enumerate(sites):
            v = site.value
            if isinstance(v, str) and not isinstance(v, bool):
                b = v.encode('utf-8')
                self._t_len[idx] = len(b)
                self._t_bytes[idx, :min(len(b), w)] = \
                    np.frombuffer(b[:w], np.uint8)
            else:
                self._t_is_num[idx] = True
                m = exact_milli(v)
                # lowering guarantees representable constants
                self._t_milli[idx] = 0 if m is None else m
            self._add_only[idx] = site.add_only
            self._replace[idx] = site.replace
            self._onehot[idx, ri] = True
            self._bit_w[idx] = np.int64(1) << np.int64(k)
            split = split_element_path(site.path)
            if split is not None:
                self._list_rules[program.list_column(
                    program.programs[ri], split[0]), ri] = True
        self.has_foreach = program.has_foreach
        if self.has_foreach:
            self._init_conditions(program, sites)
        self._jitted = None

    def _init_conditions(self, program: MutateSetProgram, sites) -> None:
        """The compile-time side of the per-element conditions: the
        static values each compares with, which rule's ``all`` / ``any``
        block it is in, and for each site of a ``foreach`` rule the
        (rule, slot) whose conditions gate it."""
        e, nr = MAX_ELEMENTS, self.n_rules
        conds = program.conditions
        c = len(conds)
        k = max([len(cond.values) for _p, cond in conds] + [1])
        cw = condition_window(program)
        self._c_op = np.zeros((c, 1), np.int8)
        self._c_is_bool = np.zeros((c, 1), bool)
        self._c_milli = np.zeros((c, 1), np.int64)
        self._c_len = np.full((c, 1, k), -1, np.int32)
        self._c_bytes = np.zeros((c, 1, k, cw), np.uint8)
        self._c_all = np.zeros((c, nr, 1), bool)
        self._c_any = np.zeros((c, nr, 1), bool)
        for i, (prog, cond) in enumerate(conds):
            self._c_op[i] = CONDITION_OPERATORS.index(cond.operator)
            for j, value in enumerate(cond.values):
                if isinstance(value, bool):
                    self._c_is_bool[i] = True
                    self._c_milli[i] = exact_milli(value)
                else:
                    b = value.encode('utf-8')
                    self._c_len[i, 0, j] = len(b)
                    self._c_bytes[i, 0, j, :len(b)] = np.frombuffer(
                        b, np.uint8)
            block = self._c_all if cond.mode == 'all' else self._c_any
            block[i, prog.rule_index] = True
        self._has_any = self._c_any.any(axis=0)[None]       # [1, NR, 1]
        self._foreach_rule = np.array(
            [p.foreach is not None for p in program.programs])
        self._site_gated = np.array(
            [program.programs[ri].foreach is not None
             for ri, _k, _s in sites], bool)
        self._site_cell = np.array(
            [ri * e + (split_element_path(site.path)[1]
                       if program.programs[ri].foreach is not None else 0)
             for ri, _k, site in sites], np.int32)
        self._slot_w = (np.int32(1) << np.arange(e, dtype=np.int32))

    def _conditions(self, lanes, n: int):
        """``(met, escaped)`` per (row, rule, slot): the rule's conditions
        all held at the element of that slot (and one of its ``any``
        block, where it has one), and whether one of them could not be
        decided here."""
        import jax.numpy as jnp
        e, nr = MAX_ELEMENTS, self.n_rules
        if not self._c_op.size:
            return (jnp.ones((n, nr, e), bool), jnp.zeros((n, nr, e), bool))
        ctag = lanes['ctag']
        is_str = ctag == TAG_STRING
        same = jnp.all(lanes['csbytes'][:, :, :, None, :] ==
                       self._c_bytes[None], axis=-1)
        member = jnp.any(is_str[..., None] &
                         (lanes['cslen'][..., None] == self._c_len[None]) &
                         same, axis=-1)                      # [n, C, E]
        eq = jnp.where(self._c_is_bool,
                       (ctag == TAG_BOOL) &
                       (lanes['cmilli'] == self._c_milli), member)
        str_or_num = is_str | (ctag == TAG_INT) | (ctag == TAG_FLOAT)
        op = self._c_op
        held = jnp.where(op == 0, eq, jnp.where(
            op == 1, ~eq, jnp.where(op == 2, member, str_or_num & ~member)))
        esc = ctag == CTAG_ESCAPE
        # [n, C, 1, E] against the blocks [C, NR, 1]: reduce over C
        held4, esc4 = held[:, :, None, :], esc[:, :, None, :]
        all_ok = ~jnp.any(self._c_all & ~held4, axis=1)
        any_ok = jnp.any(self._c_any & held4, axis=1) | ~self._has_any
        escaped = jnp.any((self._c_all | self._c_any) & esc4, axis=1)
        return all_ok & any_ok, escaped

    def mutate_eval(self, lanes):
        import jax.numpy as jnp
        tag = lanes['tag']
        istate = lanes['istate']
        milli = lanes['milli']
        milli_ok = lanes['milli_ok']
        slen = lanes['slen']
        sbytes = lanes['sbytes']
        # an element site with no element to patch is inert: neither
        # missing (an edit) nor present (a comparison) nor bad
        inert = istate == ISTATE_NO_ELEMENT
        missing = (tag == TAG_MISSING) & ~inert
        bad = istate == 2
        present = (~missing) & (~bad) & ~inert
        num_tag = (tag == TAG_BOOL) | (tag == TAG_INT) | \
            (tag == TAG_FLOAT)
        eq_num = self._t_is_num & present & num_tag & milli_ok & \
            (milli == self._t_milli)
        undec = self._t_is_num & present & num_tag & (~milli_ok) & \
            (~self._add_only)
        eq_str = (~self._t_is_num) & present & (tag == TAG_STRING) & \
            (slen == self._t_len) & \
            jnp.all(sbytes == self._t_bytes, axis=-1)
        eq = eq_num | eq_str
        edit = jnp.where(missing & ~bad, True,
                         jnp.where(self._add_only, False,
                                   present & ~eq))
        rep_bad = self._replace & ((istate != 0) | missing)
        if self.has_foreach:
            # a foreach rule's site is decided only at an element whose
            # conditions held: the engine leaves the others alone
            n = tag.shape[0]
            met, escaped = self._conditions(lanes, n)
            gate = jnp.where(
                self._site_gated,
                met.reshape(n, -1)[:, self._site_cell], True)
            edit, bad, undec = edit & gate, bad & gate, undec & gate

        # per-rule reductions over the rule's own sites, as masked
        # reduces: the TPU has no s64 matrix product (its 64-bit
        # rewrite refuses an s64 dot), and [R, S, NR] is tiny
        def per_rule(flag):
            return jnp.any(flag[:, :, None] & self._onehot, axis=1)

        edits = jnp.sum(
            jnp.where(self._onehot, (edit * self._bit_w)[:, :, None],
                      jnp.int64(0)), axis=1)
        rep_any = per_rule(rep_bad)
        bad_any = per_rule(bad)
        undec_any = per_rule(undec)
        llen = lanes['llen']
        list_any = jnp.any(
            ((llen < 0) | (llen > MAX_ELEMENTS))[:, :, None] &
            self._list_rules, axis=1)
        fb = rep_any | list_any | bad_any | undec_any
        if self.has_foreach:
            esc_any = jnp.any(escaped, axis=2)
            fb = fb | esc_any
        status = jnp.where(
            fb, MUT_FALLBACK,
            jnp.where(edits != 0, MUT_PASS, MUT_SKIP)).astype(jnp.int8)
        # first-fault reason in the host fast path's check order:
        # replace guard, then the walk (a list's shape before its
        # elements' conditions, and those before the maps under them),
        # then equality
        walk = jnp.where(bad_any, RC_NON_DICT,
                         jnp.where(undec_any, RC_UNDECIDABLE, RC_NONE))
        if self.has_foreach:
            walk = jnp.where(esc_any, RC_PRECONDITION_ESCAPE, walk)
        reason = jnp.where(
            rep_any, RC_REPLACE_MISSING,
            jnp.where(list_any, RC_LIST_SHAPE, walk)).astype(jnp.int8)
        # ragged batches: capacity-padding rows (all-MISSING leaves
        # would otherwise read as "every edit applies") are masked to
        # SKIP / empty-bitmask / no-reason inside the program
        valid = lanes.get('valid')
        vcol = valid[:, None] if valid is not None else True
        if valid is not None:
            status = jnp.where(vcol, status, MUT_SKIP).astype(jnp.int8)
            edits = jnp.where(vcol, edits, 0)
            reason = jnp.where(vcol, reason, RC_NONE).astype(jnp.int8)
        if not self.has_foreach:
            return status, edits, reason
        # a foreach rule's elements: bit i, the conditions held at slot
        # i; bit MAX_ELEMENTS + i, one could not be decided there
        elem = jnp.sum(jnp.where(met, self._slot_w, 0), axis=2) + \
            (jnp.sum(jnp.where(escaped, self._slot_w, 0), axis=2)
             << MAX_ELEMENTS)
        elem = jnp.where(vcol & self._foreach_rule, elem,
                         0).astype(jnp.int32)
        return status, edits, reason, elem

    def __call__(self, lanes: Dict[str, np.ndarray]) -> Tuple[np.ndarray,
                                                              ...]:
        n = lanes['tag'].shape[0]
        if self.n_sites == 0:
            return (np.zeros((n, self.n_rules), np.int8),
                    np.zeros((n, self.n_rules), np.int64),
                    np.zeros((n, self.n_rules), np.int8))
        import jax
        with jax.enable_x64(True):
            if self._jitted is None:
                from ..aotcache import enable_persistent_compilation_cache
                enable_persistent_compilation_cache()
                # the method's name is the device program's in a
                # profiler trace: ``jit_mutate_eval``
                self._jitted = jax.jit(self.mutate_eval)
            out = self._jitted(lanes)
            return tuple(np.asarray(o) for o in out)
