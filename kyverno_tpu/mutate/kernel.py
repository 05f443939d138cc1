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
                       lanes cannot stand for (``llen`` -1)

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
from .encode import ISTATE_NO_ELEMENT, exact_milli, string_window
from .plan import MAX_ELEMENTS, MutateSetProgram, split_element_path

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
        self._list_rules = np.zeros((max(1, len(program.lists)),
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
                self._list_rules[program.lists.index(split[0]), ri] = True
        self._jitted = None

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
        status = jnp.where(
            fb, MUT_FALLBACK,
            jnp.where(edits != 0, MUT_PASS, MUT_SKIP)).astype(jnp.int8)
        # first-fault reason in the host fast path's check order:
        # replace guard, then the walk (a list's shape before the maps
        # under it), then equality
        reason = jnp.where(
            rep_any, RC_REPLACE_MISSING,
            jnp.where(list_any, RC_LIST_SHAPE,
                      jnp.where(bad_any, RC_NON_DICT,
                                jnp.where(undec_any, RC_UNDECIDABLE,
                                          RC_NONE)))).astype(jnp.int8)
        # ragged batches: capacity-padding rows (all-MISSING leaves
        # would otherwise read as "every edit applies") are masked to
        # SKIP / empty-bitmask / no-reason inside the program
        valid = lanes.get('valid')
        if valid is not None:
            vcol = valid[:, None]
            status = jnp.where(vcol, status, MUT_SKIP).astype(jnp.int8)
            edits = jnp.where(vcol, edits, 0)
            reason = jnp.where(vcol, reason, RC_NONE).astype(jnp.int8)
        return status, edits, reason

    def __call__(self, lanes: Dict[str, np.ndarray]
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
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
