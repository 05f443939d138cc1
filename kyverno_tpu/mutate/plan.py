"""Lowering: mutate rules → fixed device edit-site programs.

A mutate rule lowers when its whole patch is expressible as a fixed set
of **edit sites** — (slot path, static scalar value) pairs with an
optional add-if-absent anchor or json6902 ``replace`` existence guard —
over the same wildcard-free slot-path vocabulary the validate encoder
resolves at encode time (``compiler/encode.py``).  One list shape is in
the vocabulary: a strategic-merge list holding a single map anchored
``(name): "*"`` (or ``"?*"``) patches every named element of the live
list, and lowers to **element sites**, one per leaf and element slot
``i < MAX_ELEMENTS``, whose path carries the slot as an int
(``('spec', 'containers', 2, 'imagePullPolicy')``).  The device program
then decides, per (resource, site), whether the edit applies, and emits
a compact per-rule edit bitmask the host decodes back into patched JSON
(``scanner.py``).  A ``foreach`` lowers where
``compiler/mutate_compile.py`` ``parse_foreach`` reads it (one entry over
``request.object.<list>``, optionally ``|| `[]```, merging static leaves
by ``{{element.name}}``) and its per-element conditions are in the
device's vocabulary (keys ``element.<path>`` with an optional ``||
'<static>'`` default, ``Equals`` / ``NotEquals`` against a plain string
or a bool, ``AnyIn`` / ``AnyNotIn`` against plain strings): its leaves
become element sites of the list, gated on the device by the
conditions at each slot (``ForeachProgram``).  The rules are the ones
the engine runs, autogen's included, so a Pod rule also lowers on
``spec.template`` and ``spec.jobTemplate.spec.template``.  Anything
outside that vocabulary — other foreach shapes, contexts, rule-level
preconditions, variables, anchors needing live lookups, any other list
patch (several maps, other anchors, scalars, nested lists, appends),
null values (RFC-7386 deletes), non-scalar values — does NOT lower and
keeps the host engine, attributed on the coverage ledger.

A rule with ``targets`` (mutate-existing) lowers for the background
controller (``existing=True``): its match is decided on the trigger, its
overlay on each target, and each target spec is resolved on the host,
so a spec may read only the trigger's name and namespace
(``TARGET_VARIABLE_RE``).  There each policy is placed on its own
(``MutateSetProgram``).

Set-level coupling: the admission mutate chain is CUMULATIVE (policy
k+1 sees policy k's patched output — handlers.py Mutate loop), while
the device decides every rule against the ORIGINAL document.  The two
agree exactly when (a) every lowered rule's match block is simple
(kinds/namespaces/operations — unaffected by scalar edits that cannot
touch identity fields) and (b) no two rules' edit sites overlap in the
prefix-or-equal sense, nor one rule's sites the paths another's
conditions read.  ``compile_mutate_set`` enforces both; a set
that violates them places every mutate rule on the host with reason
``edit_site_conflict`` / ``policy_coupling``.
"""

from __future__ import annotations

import re
from typing import Any, List, NamedTuple, Optional, Tuple

from ..api.policy import Policy, Rule
from ..autogen.autogen import compute_rules
from ..observability import coverage
from ..compiler.mutate_compile import (_compile_overlay, parse_foreach,
                                       parse_json6902_sets)

#: edit bitmask budget: one i32 lane per (resource, rule)
MAX_SITES = 32

#: element slots of a list of maps: a live list longer than this is a
#: per-row FALLBACK (``RC_LIST_SHAPE``)
MAX_ELEMENTS = 4

#: resource-identity paths no lowered edit may write: match/exclude and
#: namespace gating read them, so a rule that mutates them could change
#: a later rule's match decision mid-chain
_IDENTITY_PATHS = (('kind',), ('apiVersion',), ('metadata', 'name'),
                   ('metadata', 'namespace'))


class LowerError(Exception):
    """A mutate rule cannot lower; carries its taxonomy reason."""

    def __init__(self, reason: str, detail: str):
        super().__init__(detail)
        self.reason = reason
        self.detail = detail


class EditSite(NamedTuple):
    path: tuple             # slot path of the written leaf; an element
    #                         site's carries its slot as an int
    add_only: bool          # ``+(key)`` anchor: write only when absent
    value: Any              # static scalar (str | bool | int | float)
    replace: bool           # json6902 replace: whole path must exist


#: the operators a per-element condition may use on the device
CONDITION_OPERATORS = ('equals', 'notequals', 'anyin', 'anynotin')
#: static strings an ``AnyIn`` / ``AnyNotIn`` value may list
MAX_CONDITION_VALUES = 8


class DeviceCondition(NamedTuple):
    """A per-element condition the device decides: the key's element
    path and its ``|| '<static>'`` default (None: the key has none), the
    operator in lower case, and the static values compared with (one for
    ``equals`` / ``notequals``, a str or a bool; the strings of the list
    for the ``In`` pair).  ``mode`` is its block, ``all`` or ``any``."""
    mode: str
    path: tuple
    default: Optional[str]
    operator: str
    values: tuple


class ForeachProgram(NamedTuple):
    """What a lowered ``foreach`` adds to its rule's element sites: the
    list it walks, whether an absent list is no element (``|| `[]```)
    or the engine's error, and its per-element conditions."""
    list_path: tuple
    list_default: bool
    conditions: Tuple[DeviceCondition, ...]


class RuleMutateProgram:
    """One lowered mutate rule: its edit sites + response metadata.

    ``path``/``pss`` satisfy the coverage ledger's program duck type
    (``ScanTally`` reads both), so mutate rows land on the ledger as
    ``path="mutate"`` next to validate/pss rows.  A ``foreach`` rule
    carries its :class:`ForeachProgram`; its sites are element sites
    of that list, one per leaf and slot.
    """

    pss = None
    path = 'mutate'

    __slots__ = ('policy_name', 'rule_name', 'rule', 'kind', 'sites',
                 'policy_index', 'rule_index', 'foreach')

    def __init__(self, policy_name: str, rule_name: str, rule: Rule,
                 kind: str, sites: Tuple[EditSite, ...],
                 foreach: Optional[ForeachProgram] = None):
        self.policy_name = policy_name
        self.rule_name = rule_name
        self.rule = rule
        self.kind = kind              # 'strategic' | 'json6902' | 'foreach'
        self.sites = sites
        self.policy_index = -1        # filled by compile_mutate_set
        self.rule_index = -1
        self.foreach = foreach

    def read_paths(self) -> List[tuple]:
        """The paths its conditions read, one per condition and slot."""
        if self.foreach is None:
            return []
        return [self.foreach.list_path + (i,) + cond.path
                for cond in self.foreach.conditions
                for i in range(MAX_ELEMENTS)]


def _identity_site(path: tuple) -> bool:
    return any(path[:len(idp)] == idp for idp in _IDENTITY_PATHS)


def split_element_path(path: tuple):
    """``(list path, slot, leaf path)`` of an element site's path; None
    for a plain site's."""
    for k, part in enumerate(path):
        if isinstance(part, int):
            return path[:k], part, path[k + 1:]
    return None


def _path_text(path: tuple) -> str:
    return '/'.join(map(str, path))


def _check_sites(sites: List[EditSite]) -> Tuple[EditSite, ...]:
    if len(sites) > MAX_SITES:
        raise LowerError(
            coverage.REASON_UNSUPPORTED_OPERATOR,
            f'{len(sites)} edit sites exceed the {MAX_SITES}-bit '
            f'per-rule edit bitmask')
    for site in sites:
        if site.value is None:
            raise LowerError(
                coverage.REASON_UNSUPPORTED_OPERATOR,
                'null patch values delete keys under RFC-7386 — '
                'outside the device edit vocabulary')
        if not isinstance(site.value, (str, bool, int, float)):
            raise LowerError(
                coverage.REASON_UNSUPPORTED_OPERATOR,
                f'non-scalar patch value at {_path_text(site.path)}')
        if _identity_site(site.path):
            raise LowerError(
                coverage.REASON_UNSUPPORTED_OPERATOR,
                f'edit writes the identity field {_path_text(site.path)} '
                f'— later rules\' match decisions could change '
                f'mid-chain')
    return tuple(sites)


#: a target spec's fields, and the variables its namespace and name may
#: hold: the trigger's own name and namespace, which the controller
#: substitutes as it resolves the spec (``background/mutate_existing.py``)
TARGET_FIELDS = ('apiVersion', 'kind', 'namespace', 'name')
TARGET_VARIABLE_RE = re.compile(
    r'\{\{\s*request\.object\.metadata\.(name|namespace)\s*\}\}')

#: what the background controller writes into a patched target after the
#: patch (``background/mutate_existing.py``): no lowered edit may write
#: them, or the patches of a later policy would not be the host's
_CONTROLLER_PATHS = (
    ('metadata', 'annotations', 'policies.kyverno.io/last-applied-patches'),
    ('metadata', 'resourceVersion'))


def _check_targets(targets: Any, existing: bool) -> None:
    """A rule with ``targets``: for the background controller every spec
    must be static but for the trigger's name and namespace in its
    ``namespace`` and ``name``; at admission the engine applies the
    overlay to the admitted object after substituting the whole rule,
    targets included, so a variable there (which can fail to resolve
    against that object) keeps the host."""
    from ..engine.variables import tree_has_variables
    if not existing:
        if tree_has_variables(targets):
            raise LowerError(
                coverage.REASON_UNSUPPORTED_OPERATOR,
                'variables in targets: the engine substitutes them '
                'against the admitted object')
        return
    ok = isinstance(targets, list) and all(
        isinstance(t, dict) and set(t) <= set(TARGET_FIELDS) and
        all(isinstance(t.get(f), str) and t[f] and
            not tree_has_variables(t[f]) for f in ('apiVersion', 'kind')) and
        all(isinstance(t.get(f, ''), str) and not tree_has_variables(
            TARGET_VARIABLE_RE.sub('', t.get(f, '')))
            for f in ('namespace', 'name'))
        for t in targets)
    if not ok:
        raise LowerError(
            coverage.REASON_UNSUPPORTED_OPERATOR,
            'target spec outside static apiVersion / kind and a namespace '
            'and name that read only {{request.object.metadata.name}} or '
            '{{request.object.metadata.namespace}}')


def lower_mutate_rule(rule: Rule, policy_name: str,
                      existing: bool = False) -> RuleMutateProgram:
    """Lower one mutate rule or raise :class:`LowerError` with the
    taxonomy reason the placement record carries.  ``existing``: the
    rule is applied by the background controller to the targets its
    ``targets`` name, the match decided on the trigger."""
    prog = _lower(rule, policy_name, existing)
    if existing:
        for site in prog.sites:
            if any(site.path[:len(p)] == p for p in _CONTROLLER_PATHS):
                raise LowerError(
                    coverage.REASON_UNSUPPORTED_OPERATOR,
                    f'edit writes {_path_text(site.path)}, which the '
                    f'background controller stamps after the patch')
    return prog


def _lower(rule: Rule, policy_name: str, existing: bool) -> RuleMutateProgram:
    raw = rule.raw
    if raw.get('context'):
        raise LowerError(coverage.REASON_API_CALL,
                         'rule context entries need live loads')
    if raw.get('preconditions') is not None:
        raise LowerError(coverage.REASON_UNSUPPORTED_OPERATOR,
                         'preconditions keep the engine path')
    mutation = raw.get('mutate') or {}
    if mutation.get('targets'):
        _check_targets(mutation['targets'], existing)
    from ..compiler.scan import _rule_match_is_simple
    if not _rule_match_is_simple(raw):
        raise LowerError(
            coverage.REASON_UNSUPPORTED_OPERATOR,
            'non-simple match: the cumulative chain re-matches per '
            'policy, so only kind/namespace/operation matches are '
            'stable under device edits')
    if mutation.get('foreach') is not None:
        if existing:
            raise LowerError(
                coverage.REASON_UNSUPPORTED_OPERATOR,
                'foreach in a rule with targets: the background controller '
                'applies only an overlay or a JSON patch to a target')
        return _lower_foreach(rule, mutation['foreach'], policy_name)
    overlay = mutation.get('patchStrategicMerge')
    json6902 = mutation.get('patchesJson6902')
    if overlay is not None and not json6902:
        sets = _compile_overlay(overlay, MAX_ELEMENTS)
        if sets is None:
            raise LowerError(
                coverage.REASON_UNSUPPORTED_OPERATOR,
                'overlay outside the static scalar vocabulary '
                '(anchors needing live lookups, variables, or a list '
                'other than one map anchored (name): "*" | "?*")')
        sites = _check_sites([EditSite(path, add_only, value, False)
                              for path, add_only, value in sets])
        return RuleMutateProgram(policy_name, str(raw.get('name', '')),
                                 rule, 'strategic', sites)
    if json6902 and overlay is None:
        parsed = parse_json6902_sets(json6902)
        if parsed is None:
            raise LowerError(
                coverage.REASON_UNSUPPORTED_OPERATOR,
                'json6902 patch outside the static add/replace '
                'object-path vocabulary')
        sets, replace_paths = parsed
        rset = set(replace_paths)
        sites = _check_sites([EditSite(path, False, value, path in rset)
                              for path, _ao, value in sets])
        return RuleMutateProgram(policy_name, str(raw.get('name', '')),
                                 rule, 'json6902', sites)
    raise LowerError(coverage.REASON_UNSUPPORTED_OPERATOR,
                     'empty or mixed patch document')


def _comparable_string(value: Any) -> bool:
    """A static string the engine's ``Equals`` and ``In`` operators
    compare with a string key by plain equality: no wildcard, and not a
    duration, a quantity or a number (each compares by value there)."""
    from ..engine import operators
    if not isinstance(value, str) or '*' in value or '?' in value or \
            operators._try_duration(value) is not None or \
            operators._try_quantity(value) is not None:
        return False
    try:
        float(value)
    except ValueError:
        return True
    return False


def _device_condition(mode: str, cond) -> DeviceCondition:
    """One parsed ``ElementCondition`` in the device's vocabulary, or a
    :class:`LowerError`."""
    op = cond.operator.lower()
    if cond.path is None:
        raise LowerError(
            coverage.REASON_UNSUPPORTED_OPERATOR,
            f'condition key {cond.expr!r} is not element.<path> '
            f"[|| '<static>']")
    if op not in CONDITION_OPERATORS:
        raise LowerError(coverage.REASON_UNSUPPORTED_OPERATOR,
                         f'condition operator {cond.operator!r}')
    value = cond.value
    if op in ('equals', 'notequals'):
        ok = isinstance(value, bool) or _comparable_string(value)
        values = (value,)
    else:
        ok = isinstance(value, list) and \
            0 < len(value) <= MAX_CONDITION_VALUES and \
            all(_comparable_string(v) for v in value)
        values = tuple(value) if ok else ()
    if not ok:
        raise LowerError(
            coverage.REASON_UNSUPPORTED_OPERATOR,
            f'condition value {value!r} of {cond.operator} is not a '
            f'plain string or a bool (a list of such strings for the In '
            f'operators)')
    return DeviceCondition(mode, cond.path, cond.default, op, values)


def _lower_foreach(rule: Rule, foreach_list: Any,
                   policy_name: str) -> RuleMutateProgram:
    """A ``foreach`` that :func:`parse_foreach` reads, with conditions
    in the device's vocabulary, lowered to element sites of its list."""
    raw = rule.raw
    shape = parse_foreach(foreach_list, raw)
    if shape is None or not shape.elem_sets:
        raise LowerError(
            coverage.REASON_UNSUPPORTED_OPERATOR,
            'foreach outside one entry over request.object.<list> with a '
            'merge-by-name overlay of static leaves and element '
            'conditions')
    conditions = []
    for mode, conds in shape.blocks:
        if mode == 'any' and not conds:
            raise LowerError(coverage.REASON_UNSUPPORTED_OPERATOR,
                             'an empty any block is never met')
        conditions.extend(_device_condition(mode, c) for c in conds)
    sites = _check_sites([
        EditSite(shape.list_path + (i,) + leaf, add_only, value, False)
        for i in range(MAX_ELEMENTS)
        for leaf, add_only, value in shape.elem_sets])
    return RuleMutateProgram(
        policy_name, str(raw.get('name', '')), rule, 'foreach', sites,
        ForeachProgram(shape.list_path, shape.list_default,
                       tuple(conditions)))


def _paths_conflict(a: tuple, b: tuple) -> bool:
    """Prefix-or-equal overlap.  An element slot is one more path part,
    so two element paths are compared slot by slot, and a plain path
    that writes the list (or above it) conflicts with every slot."""
    n = min(len(a), len(b))
    return a[:n] == b[:n]


class MutateSetProgram:
    """A whole mutate policy set lowered (or not) for device serving.

    At admission ``device_ok`` is all-or-nothing: the cumulative
    admission chain means one unlowered or conflicting rule invalidates
    original-document decisions for everything after it, so a set either
    serves entirely on device (with per-row host fallback) or entirely
    on the host engine.

    ``existing``: the set's mutate-existing rules as the background
    controller applies them (``background/mutate_existing.py``: a
    policy's own ``spec.rules`` with ``targets``, no autogen, every rule
    whatever ``applyRules`` says).  There each policy is placed on its
    own: one whose rule does not lower, or whose edit sites overlap
    another device policy's, keeps the engine path with its reason while
    its siblings run on the device, and ``device_ok`` says that some
    policy does.  The controller applies a host policy's requests in
    their turn, so no device decision is taken against a document a
    host policy wrote (it watches what the engine writes).
    """

    def __init__(self, policies: List[Policy], existing: bool = False):
        self.policies = list(policies)
        self.existing = existing
        self.programs: List[RuleMutateProgram] = []
        self.per_policy: List[List[RuleMutateProgram]] = []
        self.placements: List[coverage.RulePlacement] = []
        self.device_ok = True
        failures: List[Tuple[int, Rule, LowerError]] = []
        lowered: List[Tuple[int, RuleMutateProgram]] = []
        for pi, policy in enumerate(self.policies):
            if existing:
                from ..engine.background import is_mutate_existing
                mutate_rules = [r for r in policy.rules
                                if is_mutate_existing(r)]
            else:
                # the rules the engine runs: autogen's too, on their
                # template paths (spec.template, spec.jobTemplate.spec.
                # template), in the engine's order
                mutate_rules = [r for r in map(Rule, compute_rules(policy))
                                if r.has_mutate()]
            if not existing and (policy.apply_rules or 'All') == 'One' \
                    and len(mutate_rules) > 1:
                self.device_ok = False
                for r in mutate_rules:
                    failures.append((pi, r, LowerError(
                        coverage.REASON_POLICY_COUPLING,
                        'applyRules=One early-exits between rules')))
                continue
            for r in mutate_rules:
                try:
                    prog = lower_mutate_rule(r, policy.name, existing)
                except LowerError as e:
                    self.device_ok = False
                    failures.append((pi, r, e))
                    continue
                prog.policy_index = pi
                lowered.append((pi, prog))
        # cross-rule conflicts: a prefix-or-equal overlap of one rule's
        # edit sites with another's sites, or with the paths another's
        # conditions read, makes original-document decisions
        # order-dependent (the chain is cumulative)
        host_policies = {pi for pi, _r, _e in failures}
        candidates = [(pi, prog) for pi, prog in lowered
                      if not existing or pi not in host_policies]
        conflicted: set = set()
        for i in range(len(candidates)):
            for j in range(i + 1, len(candidates)):
                pa, a = candidates[i]
                pb, b = candidates[j]
                if a is b:
                    continue
                a_writes = [s.path for s in a.sites]
                b_writes = [s.path for s in b.sites]
                pairs = [(x, y) for x in a_writes
                         for y in b_writes + b.read_paths()] + \
                    [(x, y) for x in a.read_paths() for y in b_writes]
                if any(_paths_conflict(x, y) for x, y in pairs):
                    conflicted.add(id(a))
                    conflicted.add(id(b))
        if existing:
            host_policies |= {pi for pi, prog in lowered
                              if id(prog) in conflicted}
            self.device_ok = len(host_policies) < len(self.policies)
        elif conflicted:
            self.device_ok = False
        # placements: device across the board (at admission) or policy by
        # policy (for the background controller), else host with the most
        # specific reason each rule earned
        for pi, policy in enumerate(self.policies):
            progs = [prog for ppi, prog in lowered if ppi == pi]
            on_device = pi not in host_policies if existing \
                else self.device_ok
            self.per_policy.append(progs if on_device else [])
            for prog in progs:
                if on_device:
                    prog.rule_index = len(self.programs)
                    self.programs.append(prog)
                    self.placements.append(coverage.RulePlacement(
                        policy.name, prog.rule_name, 'mutate',
                        coverage.PLACEMENT_DEVICE, None, '', pi))
                elif id(prog) in conflicted:
                    self.placements.append(coverage.RulePlacement(
                        policy.name, prog.rule_name, 'mutate',
                        coverage.PLACEMENT_HOST,
                        coverage.REASON_SITE_CONFLICT,
                        'edit sites overlap another lowered rule — '
                        'cumulative ordering leaves the device '
                        'vocabulary', pi))
                else:
                    self.placements.append(coverage.RulePlacement(
                        policy.name, prog.rule_name, 'mutate',
                        coverage.PLACEMENT_HOST,
                        coverage.REASON_POLICY_COUPLING,
                        f'rule lowered but a sibling mutate rule keeps '
                        f'the {"policy" if existing else "set"} on the '
                        f'host engine', pi))
        for pi, r, e in failures:
            self.placements.append(coverage.RulePlacement(
                self.policies[pi].name, str(r.raw.get('name', '')),
                'mutate', coverage.PLACEMENT_HOST, e.reason, e.detail,
                pi))

        #: the distinct list paths the anchored overlays' element sites
        #: walk, in first-seen order: one ``llen`` lane column each
        self.lists: List[tuple] = []
        #: the same of the ``foreach`` rules, with whether an absent list
        #: is no element: their columns follow, since a foreach walks a
        #: list by its own rules (an element without a name is the
        #: engine's error, not an element to leave alone)
        self.foreach_lists: List[Tuple[tuple, bool]] = []
        #: every per-element condition, ``(program, condition)``: one
        #: row of condition lanes each
        self.conditions: List[Tuple[RuleMutateProgram,
                                    DeviceCondition]] = []
        for prog in self.programs:
            if prog.foreach is not None:
                key = (prog.foreach.list_path, prog.foreach.list_default)
                if key not in self.foreach_lists:
                    self.foreach_lists.append(key)
                self.conditions.extend((prog, cond)
                                       for cond in prog.foreach.conditions)
                continue
            for site in prog.sites:
                split = split_element_path(site.path)
                if split is not None and split[0] not in self.lists:
                    self.lists.append(split[0])

    @property
    def n_sites(self) -> int:
        return sum(len(p.sites) for p in self.programs)

    @property
    def has_foreach(self) -> bool:
        return bool(self.foreach_lists)

    @property
    def n_list_columns(self) -> int:
        """Columns of the ``llen`` lane (one zero column where no site
        walks a list)."""
        return max(1, len(self.lists) + len(self.foreach_lists))

    def list_column(self, prog: RuleMutateProgram, list_path: tuple) -> int:
        """The ``llen`` column of the list ``prog``'s element sites on
        ``list_path`` walk."""
        if prog.foreach is None:
            return self.lists.index(list_path)
        return len(self.lists) + self.foreach_lists.index(
            (prog.foreach.list_path, prog.foreach.list_default))


def compile_mutate_set(policies: List[Policy],
                       existing: bool = False) -> MutateSetProgram:
    return MutateSetProgram(policies, existing)
