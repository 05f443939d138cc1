"""Mutate-existing processing (reference: pkg/background/mutate/mutate.go).

Applies mutate rules carrying ``targets:`` to already-admitted cluster
resources when a trigger event fires.

Two ways through, with one result.  ``process_ur`` applies one
UpdateRequest (UR) on the host engine, target after target.
``process_pass`` applies a pass's pending URs in their order as a batch:
the policies whose rules lower (``mutate/plan.py``, each policy placed on
its own) have their targets resolved against one listing of each kind,
de-duplicated, and decided by the device mutate kernel at the bulk
capacity (``MutateScanner.decide``); the edits are decoded on the host
and each patched target is stamped and written back, and each UR's
status set, in the order and with the bytes the one-UR-at-a-time path
gives.  A UR of a policy the device does not hold, a generate UR, and one
whose trigger or targets a host-path UR of the same pass wrote (the
client the host path writes through keeps their keys: ``WriteLog``) are
processed by the host path in their turn; a target row the kernel hands
back (``MUTATE_FALLBACK``, or the same rule twice on one target) is
patched by the engine, row by row.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from ..api.policy import Policy, Rule
from ..engine.api import PolicyContext, RuleStatus
from ..engine.background import is_mutate_existing
from ..engine.context import Context
from ..dclient.client import NotFoundError
from ..engine.variables import substitute_all
from .common import get_policy, get_trigger_resource, new_background_context
from .updaterequest import (STATE_COMPLETED, STATE_FAILED, UR_MUTATE,
                            UpdateRequest)

MUTATE_LAST_APPLIED_ANNOTATION = 'policies.kyverno.io/last-applied-patches'

_log = logging.getLogger('kyverno.background')

#: the paths a target's row takes in a batched pass (the counter
#: ``kyverno_tpu_mutate_existing_targets_total``): decided by the device,
#: handed back by it to the engine, or of a policy the device does not hold
TARGET_PATHS = ('device', 'fallback', 'host_policy')


def stamp(patched: dict, patches: list, target_obj: dict) -> dict:
    """The document the controller writes for a patched target: the
    patches it applied in the last-applied annotation and the
    ``resourceVersion`` it read the target at (reference: mutate.go
    applies the rule's patches and updates the target).  Copies what it
    changes: ``patched`` may share its maps with the target it came
    from."""
    out = dict(patched)
    meta = out['metadata'] = dict(patched.get('metadata') or {})
    if patches:
        annotations = meta['annotations'] = dict(meta.get('annotations')
                                                 or {})
        annotations[MUTATE_LAST_APPLIED_ANNOTATION] = json.dumps(
            patches, separators=(',', ':'), sort_keys=True)
    meta['resourceVersion'] = \
        (target_obj.get('metadata') or {}).get('resourceVersion', '')
    return out


def _key_of(doc: dict) -> tuple:
    meta = doc.get('metadata') or {}
    return (doc.get('apiVersion', ''), doc.get('kind', ''),
            meta.get('namespace', '') or '', meta.get('name', ''))


class WriteLog:
    """The client the host path writes through: every verb passes on to
    ``inner``, and the keys of what was written are kept, so that a batch
    knows which of the documents it read are no longer the cluster's.
    ``updated``: ``(apiVersion, kind, namespace, name)`` of every object
    written; ``listed``: ``(apiVersion, kind, namespace)`` of every
    object created or deleted (a listing there changed)."""

    def __init__(self, inner):
        self.inner = inner
        self.updated: set = set()
        self.listed: set = set()
        #: writes through it, since it was made
        self.writes = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def clear(self) -> None:
        self.updated.clear()
        self.listed.clear()

    def _wrote(self, api_version: str, kind: str, namespace: str,
               resource: dict, listing: bool = False) -> None:
        meta = resource.get('metadata') or {}
        api = resource.get('apiVersion') or api_version
        kind = resource.get('kind') or kind
        ns = '' if kind == 'Namespace' else \
            (meta.get('namespace') or namespace or '')
        self.writes += 1
        self.updated.add((api, kind, ns, meta.get('name', '')))
        if listing:
            self.listed.add((api, kind, ns))

    def create_resource(self, api_version, kind, namespace, resource,
                        *args, **kwargs):
        out = self.inner.create_resource(api_version, kind, namespace,
                                         resource, *args, **kwargs)
        self._wrote(api_version, kind, namespace, resource, listing=True)
        return out

    def update_resource(self, api_version, kind, namespace, resource,
                        *args, **kwargs):
        out = self.inner.update_resource(api_version, kind, namespace,
                                         resource, *args, **kwargs)
        self._wrote(api_version, kind, namespace, resource)
        return out

    def update_status_resource(self, api_version, kind, namespace, resource,
                               *args, **kwargs):
        out = self.inner.update_status_resource(
            api_version, kind, namespace, resource, *args, **kwargs)
        self._wrote(api_version, kind, namespace, resource)
        return out

    def delete_resource(self, api_version, kind, namespace, name, *args,
                        **kwargs):
        out = self.inner.delete_resource(api_version, kind, namespace, name,
                                         *args, **kwargs)
        self._wrote(api_version, kind, namespace,
                    {'metadata': {'name': name}}, listing=True)
        return out


class _Cluster:
    """What a pass knows of the cluster: one listing of each (apiVersion,
    kind) it needs, taken at first use, and each document as the pass
    itself last wrote it."""

    def __init__(self, client):
        self.client = client
        self.docs: Dict[tuple, dict] = {}
        self._keys: Dict[tuple, List[tuple]] = {}
        self._by_ns: Dict[tuple, Dict[str, List[tuple]]] = {}

    def _load(self, api_version: str, kind: str) -> None:
        if (api_version, kind) in self._keys:
            return
        keys, by_ns = [], {}
        for obj in self.client.list_resource(api_version, kind, ''):
            key = _key_of(obj)
            self.docs[key] = obj
            keys.append(key)
            by_ns.setdefault(key[2], []).append(key)
        self._keys[(api_version, kind)] = keys
        self._by_ns[(api_version, kind)] = by_ns

    def get(self, key: tuple) -> Optional[dict]:
        self._load(key[0], key[1])
        return self.docs.get(key)

    def listing(self, api_version: str, kind: str,
                namespace: str) -> List[tuple]:
        """The keys ``list_resource(api_version, kind, namespace)`` gives,
        in its order (namespace, then name)."""
        self._load(api_version, kind)
        if not namespace:
            return self._keys[(api_version, kind)]
        return self._by_ns[(api_version, kind)].get(namespace, [])


class _Unresolved(Exception):
    """A target spec's variable the trigger has no string for: the host
    path gives the UR's answer."""


class _Plan(NamedTuple):
    """One UR the device decides: its trigger, the (rule program, target
    key) steps in the host path's order, and the listings and documents
    its answer rests on."""
    ur: UpdateRequest
    policy: Policy
    trigger: tuple
    steps: List[tuple]
    listings: List[tuple]


class MutateExistingController:
    """reference: pkg/background/mutate/mutate.go:46"""

    def __init__(self, client, engine, policy_getter=None):
        self.client = client if isinstance(client, WriteLog) \
            else WriteLog(client)
        self.engine = engine
        self.policy_getter = policy_getter or (
            lambda key: get_policy(self.client, key))

    def process_ur(self, ur: UpdateRequest) -> Optional[Exception]:
        """reference: mutate.go:73 ProcessUR"""
        errs: List[str] = []
        try:
            policy = self.policy_getter(ur.policy_key)
        except Exception as exc:  # noqa: BLE001
            ur.set_status(STATE_FAILED, str(exc))
            return exc
        if policy is None:
            err = NotFoundError(f'policy {ur.policy_key!r} not found')
            ur.set_status(STATE_FAILED, str(err))
            return err
        rules = [r for r in (policy.spec.get('rules') or [])
                 if is_mutate_existing(Rule(r))]
        pctx = None
        if rules:
            try:
                trigger = get_trigger_resource(self.client, ur)
            except Exception as exc:  # noqa: BLE001
                ur.set_status(STATE_FAILED, str(exc))
                return exc
            if trigger is None:
                # DELETE triggers resolve from the admission request's
                # old object (reference: pkg/background/common/
                # context.go:50 — trigger = &old when nil)
                old = (ur.admission_request or {}).get('oldObject')
                if isinstance(old, dict) and old:
                    trigger = old
            if trigger is not None:
                pctx = new_background_context(self.client, ur, policy, trigger)
        if pctx is not None:
            from ..api.unstructured import Resource
            from ..engine.match import matches_resource_description
            from ..engine.mutate.mutate import _check_preconditions
            for raw_rule in rules:
                rule = Rule(raw_rule)
                # the trigger must actually select this rule before any
                # target is touched (reference: mutate.go:80 ProcessUR →
                # engine.Mutate, whose rule loop match/precondition-gates)
                if matches_resource_description(
                        Resource(pctx.new_resource), rule,
                        pctx.admission_info, pctx.exclude_group_roles,
                        pctx.namespace_labels,
                        policy.namespace) is not None:
                    continue
                try:
                    # rule context loads BEFORE preconditions, exactly
                    # like the engine mutate loop (mutate.py:185)
                    self.engine.context_loader.load(
                        rule.context, pctx.json_context,
                        policy_name=policy.name, rule_name=rule.name)
                    if not _check_preconditions(pctx, rule.preconditions):
                        continue
                except Exception as exc:  # noqa: BLE001
                    errs.append(f'{rule.name}: failed to evaluate '
                                f'preconditions: {exc}')
                    continue
                errs.extend(
                    self._mutate_targets(pctx, rule, raw_rule, policy, ur))
        return _finish_status(ur, errs)

    def _mutate_targets(self, pctx: PolicyContext, rule: Rule,
                        raw_rule: dict, policy: Policy,
                        ur: UpdateRequest) -> List[str]:
        """Resolve each target spec, run the mutation against the target
        with ``target`` bound in the JSON context, and persist the patched
        object (reference: mutate.go:102-170 + engine mutate target
        loading)."""
        errs: List[str] = []
        ctx = pctx.json_context
        for target in rule.mutation.get('targets') or []:
            ctx.checkpoint()
            try:
                resolved = substitute_all(ctx, dict(target))
                api_version = resolved.get('apiVersion', '')
                kind = resolved.get('kind', '')
                name = resolved.get('name', '')
                namespace = resolved.get('namespace', '')
                candidates = self._resolve_targets(
                    api_version, kind, namespace, name)
                for obj in candidates:
                    err = self._mutate_one(pctx, rule, raw_rule, policy, obj)
                    if err:
                        errs.append(err)
            except Exception as exc:  # noqa: BLE001
                errs.append(f'{rule.name}: {exc}')
            finally:
                ctx.restore()
        return errs

    def _resolve_targets(self, api_version: str, kind: str, namespace: str,
                         name: str) -> List[dict]:
        if name and '*' not in name:
            try:
                return [self.client.get_resource(
                    api_version, kind, namespace, name)]
            except Exception:  # noqa: BLE001 — missing target is not fatal
                return []
        from ..utils.wildcard import match as wildcard_match
        out = []
        for obj in self.client.list_resource(api_version, kind, namespace):
            obj_name = (obj.get('metadata') or {}).get('name', '')
            if not name or wildcard_match(name, obj_name):
                out.append(obj)
        return out

    def _mutate_one(self, pctx: PolicyContext, rule: Rule, raw_rule: dict,
                    policy: Policy, target_obj: dict) -> Optional[str]:
        ctx = pctx.json_context
        ctx.checkpoint()
        try:
            patched, err = self._patch_one(ctx, raw_rule, target_obj)
            if patched is not None:
                self.client.update_resource(
                    patched.get('apiVersion', ''), patched.get('kind', ''),
                    (patched.get('metadata') or {}).get('namespace', ''),
                    patched)
            return err
        except Exception as exc:  # noqa: BLE001
            return f'{rule.name}: {exc}'
        finally:
            ctx.restore()

    @staticmethod
    def _patch_one(ctx: Context, raw_rule: dict,
                   target_obj: dict) -> Tuple[Optional[dict], Optional[str]]:
        """``(document to write, error)`` of one rule on one target, on
        the host engine: None to write where the rule did not patch."""
        from ..engine.mutate.mutate import mutate_rule
        ctx.add_target_resource(target_obj)
        resp = mutate_rule(raw_rule, ctx, target_obj)
        if resp.status == RuleStatus.FAIL or resp.status == RuleStatus.ERROR:
            return None, (f'failed to mutate existing resource, rule '
                          f'response {resp.status}: {resp.message}')
        if resp.status != RuleStatus.PASS or resp.patched_resource is None:
            return None, None
        return stamp(resp.patched_resource, resp.patches, target_obj), None

    # -- the batched pass -------------------------------------------------

    def scanner(self, policies: List[Policy]):
        """The compiled mutate-existing set of ``policies``
        (``MutateScanner(existing=True)``): one a set of rules in the
        process, whichever cluster the policies were read from (the
        store's ``uid`` and ``resourceVersion`` are no part of the key),
        the last ``_SCANNER_SETS`` sets kept."""
        from ..aotcache.keys import policy_set_fingerprint
        from ..mutate.scanner import MutateScanner
        key = policy_set_fingerprint([
            {'kind': p.raw.get('kind'), 'name': p.name,
             'namespace': p.namespace, 'spec': p.spec} for p in policies])
        with _scanners_lock:
            scanner = _scanners.pop(key, None)
            if scanner is None:
                scanner = MutateScanner(policies, self.engine, existing=True)
            _scanners[key] = scanner
            while len(_scanners) > _SCANNER_SETS:
                _scanners.pop(next(iter(_scanners)))
        return scanner

    def process_pass(self, urs: List[UpdateRequest],
                     host: Callable[[UpdateRequest], None],
                     finish: Callable[[UpdateRequest, Optional[Exception]],
                                      None]) -> None:
        """Apply ``urs`` in their order, the mutate URs of device policies
        as a batch.  ``host(ur)`` processes one UR on the host path and
        stores its status; ``finish(ur, err)`` stores the status of a UR
        this pass answered.  One span a pass,
        ``kyverno/background/mutate_existing_pass``."""
        from ..observability import tracing
        if not any(ur.type == UR_MUTATE for ur in urs):
            for ur in urs:
                host(ur)
            return
        with tracing.start_span('kyverno/background/mutate_existing_pass',
                                {'urs': len(urs)}) as span:
            counts = _Pass(self, urs, host, finish).run()
            span.set_attribute('targets', sum(counts.values()))
            for path, n in counts.items():
                span.set_attribute(f'targets_{path}', n)


#: compiled mutate-existing sets of the process, by their rules, oldest
#: first
_scanners: Dict[str, object] = {}
_scanners_lock = threading.Lock()
_SCANNER_SETS = 8


def _finish_status(ur: UpdateRequest, errs: List[str]) -> Optional[Exception]:
    """The status ``process_ur`` gives a UR from its errors."""
    if errs:
        msg = '; '.join(errs)
        ur.set_status(STATE_FAILED, msg)
        return RuntimeError(msg)
    ur.set_status(STATE_COMPLETED)
    return None


class _Pass:
    """One batched pass of :meth:`MutateExistingController.process_pass`:
    URs are taken in order into chunks of at most the bulk capacity's
    distinct targets (a UR with more is a chunk of its own), and a chunk
    is resolved, decided, decoded and stored before the next is planned,
    the host path's URs in their turn in its store."""

    def __init__(self, ctrl: MutateExistingController,
                 urs: List[UpdateRequest], host, finish):
        self.ctrl, self.urs, self.host, self.finish = ctrl, urs, host, finish
        self.log: WriteLog = ctrl.client
        self.cluster = _Cluster(self.log.inner)
        self.counts = dict.fromkeys(TARGET_PATHS, 0)
        #: documents whose copy here is no longer the cluster's: a write
        #: of this pass failed
        self.stale: set = set()
        #: (rule, target) pairs decided in this pass: the same rule a
        #: second time on a target is decided against the first's result
        self.seen: set = set()
        self._match_cache: Dict[tuple, bool] = {}
        self._contexts: Dict[int, PolicyContext] = {}
        #: the resourceVersion each target got from this pass's last write
        self.versions: Dict[tuple, str] = {}
        self._tally = None

    def run(self) -> Dict[str, int]:
        from ..compiler.shapes import canonical_caps
        from ..observability import device as devtel
        self.log.clear()
        policies = self._policies()
        self.placed: Dict[str, int] = {}
        self.scanner = None
        device = sorted((k, p) for k, p in policies.items()
                        if p is not None)
        if device:
            self.scanner = self.ctrl.scanner([p for _k, p in device])
            program = self.scanner.program
            from ..observability import coverage
            if coverage.enabled():
                coverage.record_placements(program.placements)
            for pi, (key, _policy) in enumerate(device):
                if program.per_policy[pi]:
                    self.placed[key] = pi
        self.policies = policies
        cap = canonical_caps()[-1]
        chunk: List[object] = []
        keys: set = set()
        self._timing = {'mx_resolve': 0.0, 'mutate_match': 0.0}
        for ur in self.urs:
            plan = self._plan(ur) if self.scanner is not None and \
                ur.type == UR_MUTATE else None
            if plan is None:
                chunk.append(ur)
                continue
            new = {key for _prog, key in plan.steps} - keys
            if keys and len(keys) + len(new) > cap:
                self._flush(chunk)
                chunk, keys = [], set()
                new = {key for _prog, key in plan.steps}
            chunk.append(plan)
            keys |= new
        self._flush(chunk)
        devtel.record_mutate_existing_targets(self.counts)
        return self.counts

    # -- plan -------------------------------------------------------------

    def _policies(self) -> Dict[str, Optional[Policy]]:
        """Each mutate UR's policy, read once a pass; None where the
        getter fails or finds none (the host path says why)."""
        out: Dict[str, Optional[Policy]] = {}
        for ur in self.urs:
            if ur.type != UR_MUTATE or ur.policy_key in out:
                continue
            try:
                out[ur.policy_key] = self.ctrl.policy_getter(ur.policy_key)
            except Exception:  # noqa: BLE001
                out[ur.policy_key] = None
        return out

    def _plan(self, ur: UpdateRequest) -> Optional[_Plan]:
        """The device's plan of ``ur``, or None where the host path
        answers it: a policy the device does not hold, a UR that carries
        an admission request or a user, a trigger that is gone or going,
        a target spec whose variable does not resolve to a string."""
        pi = self.placed.get(ur.policy_key)
        if pi is None or ur.spec.get('context'):
            return None
        t0 = time.monotonic()
        try:
            return self._resolve(ur, pi)
        except _Unresolved:
            return None
        finally:
            self._timing['mx_resolve'] += time.monotonic() - t0

    def _resolve(self, ur: UpdateRequest, pi: int) -> Optional[_Plan]:
        from ..mutate.plan import TARGET_VARIABLE_RE
        from ..utils.wildcard import match as wildcard_match
        res = ur.resource
        kind = res.get('kind', '')
        if not res.get('apiVersion') or not kind:
            return None
        trigger_key = (res['apiVersion'], kind,
                       '' if kind == 'Namespace' else
                       res.get('namespace', '') or '', res.get('name', ''))
        trigger = self.cluster.get(trigger_key)
        if trigger is None or \
                (trigger.get('metadata') or {}).get('deletionTimestamp'):
            return None
        policy = self.policies[ur.policy_key]
        meta = trigger.get('metadata') or {}

        def substitute(value: str) -> str:
            def one(m):
                got = meta.get(m.group(1))
                if not isinstance(got, str):
                    raise _Unresolved(m.group(0))
                return got
            return TARGET_VARIABLE_RE.sub(one, value)

        steps, listings = [], []
        for prog in self.scanner.program.per_policy[pi]:
            if not self._matches(prog, policy, trigger):
                continue
            for spec in prog.rule.mutation.get('targets') or []:
                api, t_kind = spec['apiVersion'], spec['kind']
                ns = substitute(spec.get('namespace', ''))
                name = substitute(spec.get('name', ''))
                listings.append((api, t_kind, ns))
                if name and '*' not in name:
                    key = (api, t_kind, ns, name)
                    found = [key] if self.cluster.get(key) is not None \
                        else []
                else:
                    found = [k for k in self.cluster.listing(api, t_kind, ns)
                             if not name or wildcard_match(name, k[3])]
                steps.extend((prog, key) for key in found)
        return _Plan(ur, policy, trigger_key, steps, listings)

    def _matches(self, prog, policy: Policy, trigger: dict) -> bool:
        """Whether the trigger selects the rule: the host path's call, once
        a (rule, kind, apiVersion, namespace), since a lowered rule's match
        reads no more (``_rule_match_is_simple``)."""
        from ..api.unstructured import Resource
        from ..compiler.scan import _group_key
        from ..engine.match import matches_resource_description
        key = (prog.rule_index,) + _group_key(trigger)
        got = self._match_cache.get(key)
        if got is None:
            t0 = time.monotonic()
            got = self._match_cache[key] = matches_resource_description(
                Resource(trigger), prog.rule, None, [], {},
                policy.namespace) is None
            self._timing['mutate_match'] += time.monotonic() - t0
        return got

    # -- one chunk --------------------------------------------------------

    def _flush(self, chunk: list) -> None:
        from ..observability import device as devtel
        if not chunk:
            return
        plans = [p for p in chunk if isinstance(p, _Plan)]
        if plans:
            devtel.record_stage('mx_resolve', self._timing['mx_resolve'])
            devtel.record_stage('mutate_match', self._timing['mutate_match'])
        self._timing = dict.fromkeys(self._timing, 0.0)
        outcomes: Dict[int, list] = {}
        if plans:
            rows: Dict[tuple, int] = {}
            for plan in plans:
                for _prog, key in plan.steps:
                    rows.setdefault(key, len(rows))
            docs = [self.cluster.docs[key] for key in rows]
            try:
                status, edits, reason = self.scanner.decide(docs)
            except Exception:  # noqa: BLE001 - the host path answers
                _log.exception('device mutate-existing chunk failed; its '
                               'URs go to the host path')
                plans = []
        if plans:
            from ..observability import coverage
            with devtel.stage('mutate_decode', {'rows': len(docs)}):
                self._tally = coverage.scan_tally()
                for plan in plans:
                    outcomes[id(plan)] = [
                        self._decode(plan, prog, key, status[rows[key]],
                                     edits[rows[key]], reason[rows[key]])
                        for prog, key in plan.steps]
                if self._tally is not None:
                    self._tally.finish()
        store_s = 0.0
        for item in chunk:
            if not isinstance(item, _Plan):
                self._host(item, 'host_policy'
                           if item.type == UR_MUTATE else None)
            elif id(item) in outcomes and self._clean(item):
                t0 = time.monotonic()
                self._store(item, outcomes[id(item)])
                store_s += time.monotonic() - t0
            else:
                self._host(item.ur, 'fallback')
        if plans:
            devtel.record_stage('mx_store', store_s)
        self._contexts.clear()

    def _decode(self, plan: _Plan, prog, key: tuple, status, edits,
                reason) -> tuple:
        """``(rule name, document to write or None, error or None)`` of
        one step, the document also kept as the target's new state."""
        from ..compiler.mutate_compile import apply_edit_list
        from ..engine.mutate.jsonpatch import generate_patches
        from ..mutate.kernel import MUT_FALLBACK, MUT_SKIP
        from ..mutate.scanner import _RC_REASON
        from ..observability import coverage
        doc = self.cluster.docs[key]
        j = prog.rule_index
        st = int(status[j])
        if (j, key) in self.seen:
            # the same rule again on a target: the device decided it
            # against the document before the first time
            why = coverage.REASON_SITE_CONFLICT
        elif st == MUT_FALLBACK:
            why = _RC_REASON.get(int(reason[j]), coverage.REASON_NON_DICT)
        else:
            why = None
        self.seen.add((j, key))
        tally = self._tally
        if why is None:
            out = None
            if st != MUT_SKIP:
                patched = apply_edit_list(
                    doc, self.scanner.edit_list(prog, int(edits[j])))
                if patched is None:
                    raise RuntimeError('edit list failed to apply')
                out = stamp(patched, generate_patches(doc, patched), doc)
                self.cluster.docs[key] = out
            self.counts['device'] += 1
            if tally is not None:
                tally.total_rows += 1
                tally.device(prog)
            return prog.rule_name, out, None
        # the engine's answer for this row, under its reason on the
        # coverage ledger
        self.counts['fallback'] += 1
        if tally is not None:
            tally.host_rule(prog.policy_name, prog.rule_name, why,
                            path='mutate')
        ctx = self._context(plan).json_context
        ctx.checkpoint()
        try:
            out, err = self.ctrl._patch_one(ctx, prog.rule.raw, doc)
        except Exception as exc:  # noqa: BLE001
            out, err = None, f'{prog.rule_name}: {exc}'
        finally:
            ctx.restore()
        if out is not None:
            self.cluster.docs[key] = out
        return prog.rule_name, out, err

    def _context(self, plan: _Plan) -> PolicyContext:
        """The UR's background context, as the host path builds it, for
        the rows the engine patches."""
        pctx = self._contexts.get(id(plan))
        if pctx is None:
            pctx = new_background_context(
                self.log.inner, plan.ur, plan.policy,
                self.cluster.docs[plan.trigger])
            self._contexts[id(plan)] = pctx
        return pctx

    # -- store ------------------------------------------------------------

    def _clean(self, plan: _Plan) -> bool:
        """No write of this pass outside the plan touched what it read: its
        targets and the listings it resolved them from.  Of its trigger it
        read no more than what a write cannot change (kind, apiVersion,
        namespace, name) and that it is not being deleted, which is read
        again where the trigger was written."""
        written = self.log.updated
        if any(key in written or key in self.stale
               for _p, key in plan.steps):
            return False
        listed = self.log.listed
        if any((api, kind, ns) in listed if ns else
               any(entry[:2] == (api, kind) for entry in listed)
               for api, kind, ns in plan.listings):
            return False
        if plan.trigger in written:
            api, kind, ns, name = plan.trigger
            try:
                now = self.log.inner.get_resource(api, kind, ns, name)
            except Exception:  # noqa: BLE001 - gone: the host path says so
                return False
            return not (now.get('metadata') or {}).get('deletionTimestamp')
        return True

    def _store(self, plan: _Plan, outcomes: list) -> None:
        """Write each patched target in order, then the UR's status, as
        ``process_ur`` and the UR controller do."""
        client = self.log.inner
        errs = []
        for (rule_name, doc, err), (_prog, key) in zip(outcomes, plan.steps):
            if doc is not None:
                meta = doc['metadata']
                if key in self.versions:
                    # decoded before this pass's last write of the target
                    meta['resourceVersion'] = self.versions[key]
                try:
                    out = client.update_resource(
                        doc.get('apiVersion', ''), doc.get('kind', ''),
                        meta.get('namespace', ''), doc)
                    meta['resourceVersion'] = self.versions[key] = \
                        (out.get('metadata') or {}).get('resourceVersion',
                                                        '')
                except Exception as exc:  # noqa: BLE001
                    self.stale.add(key)
                    errs.append(f'{rule_name}: {exc}')
            if err:
                errs.append(err)
        self.finish(plan.ur, _finish_status(plan.ur, errs))

    def _host(self, ur: UpdateRequest, path: Optional[str]) -> None:
        """The host path for one UR, in its turn; its target writes are
        counted under ``path``."""
        before = self.log.writes
        self.host(ur)
        if path is not None:
            self.counts[path] += self.log.writes - before
