"""Resource admission handlers + middleware chain.

The serving pipeline mirrors the reference's handler composition
(reference: pkg/webhooks/handlers/*.go, pkg/webhooks/resource/handlers.go):
``with_admission`` decodes/encodes AdmissionReview JSON, ``with_filter``
drops config-excluded resources, ``with_protection`` denies edits to
kyverno-managed resources, ``with_dump`` keeps a debug ring buffer; the
terminal handlers run the engine over the policy cache.
"""

from __future__ import annotations

import collections
import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import yaml

from ..api.unstructured import Resource
from ..engine.api import EngineResponse, RuleStatus
from ..engine.engine import Engine
from ..engine.match import matches_resource_description
from ..policycache import cache as pcache
from . import admission

Handler = Callable[[dict], dict]  # AdmissionRequest -> AdmissionResponse

SCANNER_HOT_SWAPS = 'kyverno_tpu_scanner_hot_swaps_total'
BREAKER_MIGRATIONS = 'kyverno_tpu_breaker_migrations_total'


# ---------------------------------------------------------------------------
# block / warning assembly (reference: pkg/webhooks/utils/block.go,
# warning.go; pkg/utils/engine/response.go:21)

def block_request(responses: List[EngineResponse],
                  failure_policy: str) -> bool:
    for er in responses:
        if er.is_failed() and _enforce(er):
            return True
        if er.is_error() and failure_policy == 'Fail':
            return True
    return False


def _enforce(er: EngineResponse) -> bool:
    action = er.get_validation_failure_action()
    return str(action).lower() == 'enforce'


import re as _re

_PLAIN_SCALAR_RE = _re.compile(r'^[A-Za-z0-9][A-Za-z0-9 _./()\[\]-]*$')
_NUMBERISH_RE = _re.compile(r'^[+-]?[0-9][0-9_.eE+-]*$')


def _yaml_scalar(s: str) -> str:
    """Block-style scalar: plain when unambiguous, single-quoted
    otherwise.  PyYAML's emitter costs ~0.5ms per rule message
    (analyze_scalar); deny messages at 1k policies made it the single
    largest admission-latency term, so the common map-of-strings shape
    is emitted directly."""
    if _PLAIN_SCALAR_RE.match(s) and not s.endswith(' ') and \
            not _NUMBERISH_RE.match(s) and \
            s.lower() not in ('null', 'true', 'false', 'yes', 'no', 'on',
                              'off'):
        return s
    return "'" + s.replace("'", "''") + "'"


_CTRL_CHAR_RE = _re.compile(r'[\x00-\x1f]')


# -- a map of strings as yaml.safe_dump writes it ----------------------------
# PyYAML's emitter (yaml/emitter.py) walks every scalar a character at a
# time, ~0.2ms a rule message; these are its rules for strings (block
# context, 80 columns, ASCII output), a regex search where it has a loop.
# tests/test_deny_message.py holds them to its bytes.

_WIDTH = 80  # the emitter's best_width
#: only a double-quoted scalar carries these: a character outside
#: printable ASCII and ``\n``, a space before a break or after one
#: (analyze_scalar: special_characters, space_break, break_space)
_DOUBLE_ONLY_RE = _re.compile(r'[^\n\x20-\x7e]| \n|\n ')
#: what keeps a scalar that may be single-quoted from being plain
#: (analyze_scalar: block_indicators, leading and trailing spaces, breaks)
_NOT_PLAIN_RE = _re.compile(
    r"""\A(?:---|\.\.\.|[#,\[\]{}&*!|>'"%@`]|[?-](?: |\Z)| )"""
    r"""|:(?: |\Z)| #| \Z|\n""")
#: where a plain or single-quoted line may fold: one space between words
_FOLD_RE = _re.compile(r'(?<=[^ ]) (?=[^ ])')
#: in a double-quoted scalar, a space or what is written escaped
_DQ_EVENT_RE = _re.compile(r'[^\x21-\x7e]|["\\]')
_BREAK_RE = _re.compile('[\n\x85\u2028\u2029]')
_ESCAPES = yaml.emitter.Emitter.ESCAPE_REPLACEMENTS
_IMPLICIT = yaml.SafeDumper.yaml_implicit_resolvers


def _reads_as_str(s: str) -> bool:
    """Whether YAML 1.1 reads plain ``s`` back as a string (the
    resolver's own table: not a bool, a number, a null, a date)."""
    return not any(regexp.match(s) for _tag, regexp in
                   _IMPLICIT.get(s[:1], []) + _IMPLICIT.get(None, []))


def _is_simple_key(s: str) -> bool:
    """check_simple_key: on one line, and short with its ``!!str``."""
    return 0 < len(s) < 123 and not _BREAK_RE.search(s)


def _fold(line: str, col: int, indent: int) -> str:
    """``line`` (no break in it) from column ``col`` on, folded as
    write_plain and write_single_quoted fold: at the first lone space
    past the width, onto a line indented by ``indent``."""
    if col + len(line) <= _WIDTH + 1:
        return line
    lines = []
    pos = 0
    while True:
        space = _FOLD_RE.search(line, max(pos, pos + _WIDTH + 1 - col))
        if space is None:
            lines.append(line[pos:])
            return ('\n' + ' ' * indent).join(lines)
        lines.append(line[pos:space.start()])
        pos = space.end()
        col = indent


def _double_quoted(text: str, col: int, indent: int, split: bool) -> str:
    """write_double_quoted, with its loop's ``start``, ``end`` and
    column; ``end`` skips what the loop passes over untouched."""
    out = ['"']
    col += 1
    n = len(text)
    start = end = 0
    while end <= n:
        ch = text[end] if end < n else None
        if ch is None or ch in '"\\' or not ' ' <= ch <= '~':
            if start < end:
                out.append(text[start:end])
                col += end - start
                start = end
            if ch is not None:
                if ch in _ESCAPES:
                    data = '\\' + _ESCAPES[ch]
                elif ch <= '\xFF':
                    data = '\\x%02X' % ord(ch)
                elif ch <= '\uFFFF':
                    data = '\\u%04X' % ord(ch)
                else:
                    data = '\\U%08X' % ord(ch)
                out.append(data)
                col += len(data)
                start = end + 1
        if 0 < end < n - 1 and (ch == ' ' or start >= end) \
                and col + (end - start) > _WIDTH and split:
            out.append(text[start:end] + '\\\n' + ' ' * indent)
            if start < end:
                start = end
            col = indent
            if text[start] == ' ':
                out.append('\\')
                col += 1
        end += 1
        if start < end < n:
            # nothing is written before the next space, escape or the end
            event = _DQ_EVENT_RE.search(text, end)
            end = event.start() if event else n
    out.append('"')
    return ''.join(out)


def _yaml_str(s: str, col: int, indent: int, split: bool = True) -> str:
    """The string ``s`` as the emitter writes it from column ``col`` on:
    plain if it may be (choose_scalar_style), else single-quoted, else
    double-quoted; folded onto lines indented by ``indent`` unless it is
    a simple key (``split`` false)."""
    if _DOUBLE_ONLY_RE.search(s):
        return _double_quoted(s, col, indent, split)
    if not s:
        return "''"
    if not _NOT_PLAIN_RE.search(s) and _reads_as_str(s):
        return _fold(s, col, indent) if split else s
    s = s.replace("'", "''")
    if not split:
        return f"'{s}'"
    col += 1
    out = ["'"]
    # write_single_quoted: a run of k breaks is written as k+1 and the
    # indent (no space stands next to a break here)
    for i, part in enumerate(_re.split('(\n+)', s)):
        if i % 2:
            out.append(part + '\n' + ' ' * indent)
            col = indent
        else:
            out.append(_fold(part, col, indent))
    out.append("'")
    return ''.join(out)


def _dump_as_emitter(failures: Dict[str, Dict[str, str]]) -> str:
    """``yaml.safe_dump(failures, default_flow_style=False)``, byte for
    byte, where every policy has a map of its own with a rule in it (a
    map met twice would be written as an alias, an empty one as ``{}``).
    A name that is no simple key (empty, of several lines, long) is
    written ``? name`` with its value on the next line, after ``: ``."""
    out = []
    for pol in sorted(failures):
        if _is_simple_key(pol):
            out.append(_yaml_str(pol, 0, 2, split=False) + ':\n')
            lead = '  '
        else:
            out.append(f'? {_yaml_str(pol, 2, 2)}\n')
            lead = ': '
        rules = failures[pol]
        for rule in sorted(rules):
            if _is_simple_key(rule):
                key = _yaml_str(rule, 2, 4, split=False)
                value = _yaml_str(rules[rule], len(key) + 4, 4)
                out.append(f'{lead}{key}: {value}\n')
            else:
                out.append(f'{lead}? {_yaml_str(rule, 4, 4)}\n'
                           f'  : {_yaml_str(rules[rule], 4, 4)}\n')
            lead = '  '
    return ''.join(out)


def _dump_failures(failures: Dict[str, Dict[str, str]]) -> str:
    # multi-line / control-character scalars need real YAML escaping:
    # the whole map is written as the YAML emitter writes it then.  Every
    # PSS message ends in a newline (pss/evaluate.py format_checks_print),
    # so a pack with a podSecurity rule always comes this way.  The branch
    # decides the bytes (only the emitter folds long lines), so it stays
    search = _CTRL_CHAR_RE.search
    for rules in failures.values():
        for k, v in rules.items():
            if search(k) or search(v):
                return _dump_as_emitter(failures)
    lines = []
    for pol in sorted(failures):
        lines.append(f'{_yaml_scalar(pol)}:')
        rules = failures[pol]
        for rule in sorted(rules):
            lines.append(f'  {_yaml_scalar(rule)}: {_yaml_scalar(rules[rule])}')
    return '\n'.join(lines) + '\n'


def get_blocked_messages(responses: List[EngineResponse]) -> str:
    """reference: pkg/webhooks/utils/block.go:38 GetBlockedMessages"""
    if not responses:
        return ''
    failures: Dict[str, Dict[str, str]] = {}
    has_violations = False
    for er in responses:
        rule_to_reason: Dict[str, str] = {}
        for rule in er.policy_response.rules:
            if rule.status != RuleStatus.PASS:
                rule_to_reason[rule.name] = rule.message
                if rule.status == RuleStatus.FAIL:
                    has_violations = True
        if rule_to_reason:
            failures[er.policy_response.policy_name] = rule_to_reason
    if not failures:
        return ''
    pr = responses[0].policy_response
    resource_name = f'{pr.resource_kind}/{pr.resource_namespace}/' \
                    f'{pr.resource_name}'
    action = 'violation' if has_violations else 'error'
    if len(failures) > 1:
        action += 's'
    results = _dump_failures(failures)
    return f'\n\npolicy {resource_name} for resource {action}: ' \
           f'\n\n{results}'


def get_warning_messages(responses: List[EngineResponse]) -> List[str]:
    """reference: pkg/webhooks/utils/warning.go:9 GetWarningMessages"""
    warnings = []
    for er in responses:
        for rule in er.policy_response.rules:
            if rule.status not in (RuleStatus.PASS, RuleStatus.SKIP):
                warnings.append(
                    f'policy {er.policy_response.policy_name}.{rule.name}: '
                    f'{rule.message}')
    return warnings


# ---------------------------------------------------------------------------
# middleware (reference: pkg/webhooks/handlers/{filter,protect,dump}.go)

def with_filter(configuration, inner: Handler) -> Handler:
    """Skip resources excluded by the dynamic configuration
    (reference: pkg/webhooks/handlers/filter.go)."""
    def handler(request: dict) -> dict:
        if configuration is not None:
            kind = (request.get('kind') or {}).get('kind', '')
            ns = request.get('namespace', '')
            name = request.get('name', '') or \
                Resource(admission.request_resource(request)).name
            if configuration.to_filter(kind, ns, name):
                return admission.response(request.get('uid', ''), True)
        return inner(request)
    return handler


def with_protection(enabled: bool, inner: Handler) -> Handler:
    """Deny user modifications of kyverno-managed resources
    (reference: pkg/webhooks/handlers/protect.go)."""
    def handler(request: dict) -> dict:
        if enabled:
            new = admission.request_resource(request)
            old = admission.request_old_resource(request)
            for obj in (new, old):
                labels = (obj.get('metadata') or {}).get('labels') or {}
                if labels.get('app.kubernetes.io/managed-by') == 'kyverno':
                    username = (request.get('userInfo') or {}).get(
                        'username', '')
                    if not username.startswith(
                            'system:serviceaccount:kyverno:'):
                        return admission.response(
                            request.get('uid', ''), False,
                            'A kyverno managed resource can only be '
                            'modified by kyverno')
        return inner(request)
    return handler


class DumpBuffer:
    """Debug payload ring buffer (reference: handlers/dump.go)."""

    def __init__(self, size: int = 20):
        self._items = collections.deque(maxlen=size)
        self._lock = threading.Lock()

    def add(self, item: dict) -> None:
        with self._lock:
            self._items.append(item)

    def items(self) -> List[dict]:
        with self._lock:
            return list(self._items)


def with_dump(buffer: Optional[DumpBuffer], inner: Handler) -> Handler:
    def handler(request: dict) -> dict:
        resp = inner(request)
        if buffer is not None:
            buffer.add({'request': {
                'uid': request.get('uid'),
                'kind': request.get('kind'),
                'namespace': request.get('namespace'),
                'name': request.get('name'),
                'operation': request.get('operation'),
            }, 'response': {k: v for k, v in resp.items() if k != 'patch'},
                'timestamp': time.time()})
        return resp
    return handler


def with_admission(inner: Handler) -> Callable[[bytes], bytes]:
    """AdmissionReview JSON decode/encode wrapper
    (reference: pkg/webhooks/handlers/admission.go:18)."""
    def handler(body: bytes) -> bytes:
        review = json.loads(body)
        request = admission.parse_review(review)
        resp = inner(request)
        return json.dumps(
            admission.review_response(request, resp)).encode('utf-8')
    return handler


# ---------------------------------------------------------------------------
# resource handlers (reference: pkg/webhooks/resource/handlers.go)

class ResourceHandlers:
    """Terminal Validate / Mutate admission handlers.

    ``audit_sink`` receives (request, responses) for async audit-report
    construction; ``ur_sink`` receives UpdateRequest specs spawned for
    generate / mutate-existing policies (reference: handlers.go:146-155).
    """

    # consecutive device-scan failures before the set's circuit
    # breaker opens and the host loop serves it for an exponential
    # backoff window (each failure already pays a scanner rebuild; a
    # persistently broken backend must not recompile the policy set on
    # every request).  A half-open probe after the backoff decides
    # between recovery and a re-trip (serving/breaker.py)
    DEVICE_FAILURE_LIMIT = 3
    # ceiling on simultaneous background scanner compiles (jax trace +
    # XLA compile are memory-heavy; a burst across many policy sets
    # serves the host loop rather than forking a compile per set)
    MAX_CONCURRENT_BUILDS = 2
    # distinct policy sets whose breakers are simultaneously open
    # before the failure is treated as systemic and the device path
    # disables globally
    GLOBAL_DEAD_LIMIT = 3

    def __init__(self, cache: 'pcache.Cache', engine: Optional[Engine] = None,
                 pc_builder: Optional[admission.PolicyContextBuilder] = None,
                 configuration=None,
                 namespace_labels: Optional[Callable[[str], dict]] = None,
                 audit_sink: Optional[Callable] = None,
                 ur_sink: Optional[Callable] = None,
                 event_sink: Optional[Callable] = None,
                 registry_client=None,
                 device: bool = True,
                 openapi_manager=None,
                 client=None,
                 serving_mode: Optional[str] = None):
        if openapi_manager is None:
            from ..openapi.manager import Manager
            openapi_manager = Manager()
        self.openapi_manager = openapi_manager
        self.cache = cache
        if engine is None and client is not None:
            # wire the engine's context loaders (ConfigMap resolution +
            # APICall urlPath entries) to the cluster client the daemon
            # serves (reference: cmd/kyverno/main.go engine construction
            # → pkg/engine/jsonContext.go:23 ContextLoaderFactory)
            from ..engine.apicall import make_context_loader
            engine = Engine(context_loader=make_context_loader(
                dclient=client, registry_client=registry_client))
        self.engine = engine or Engine()
        if pc_builder is None and client is not None:
            # short-TTL cache: the reference serves exceptions from an
            # informer cache — per-request LIST round trips would hammer
            # the API server under admission load
            _exc_cache = {'at': 0.0, 'items': []}

            def _list_exceptions():
                now = time.time()
                if now - _exc_cache['at'] > 1.0:
                    out = []
                    for api_version in ('kyverno.io/v2alpha1',
                                        'kyverno.io/v2beta1'):
                        try:
                            out += client.list_resource(
                                api_version, 'PolicyException')
                        except Exception:  # noqa: BLE001
                            pass
                    _exc_cache['items'] = out
                    _exc_cache['at'] = now
                return _exc_cache['items']
            pc_builder = admission.PolicyContextBuilder(
                configuration, exception_lister=_list_exceptions)
        self.pc_builder = pc_builder or admission.PolicyContextBuilder(
            configuration)
        self.configuration = configuration
        if namespace_labels is None and client is not None:
            # namespaceSelector match needs the live namespace's labels
            # (reference: pkg/utils/kube GetNamespaceSelectorsFromNamespaceLister
            # wired through the resource handlers)
            namespace_labels = client.get_namespace_labels
        self.namespace_labels = namespace_labels or (lambda ns: {})
        self.audit_sink = audit_sink
        self.ur_sink = ur_sink
        self.event_sink = event_sink
        self.registry_client = registry_client
        # the compiled device evaluator handles enforce validation for
        # CREATE/UPDATE requests; rebuilt when the cached policy set
        # changes
        self.device = device
        self._scanner_lock = threading.Lock()
        # LRU of compiled scanners keyed per (kind, policy set): a
        # policy set can compile both a validate BatchScanner and a
        # mutate MutateScanner, and admission traffic alternating
        # kinds/namespaces yields different policy lists which must not
        # rebuild (compile!) per request
        self._scanners: 'collections.OrderedDict[tuple, Any]' = \
            collections.OrderedDict()
        self._scanners_max = 8
        # per cached scanner key, what it was compiled for: the
        # (namespace, name) identity set, the policy list and the ids of
        # its objects.  Policy churn replaces the Policy OBJECTS (so the
        # id()-tuple key never matches), but the logical set persists —
        # the hot-swap predecessor search matches on identity overlap;
        # and while a successor compiles, the predecessor serves the
        # requests whose own policies it holds (_serving_predecessor)
        self._scanner_sets: Dict[tuple, Tuple[frozenset, Any,
                                              frozenset]] = {}
        self._building: set = set()
        # per-policy-set circuit breakers (serving/breaker.py): a set
        # that keeps failing (build or scan) opens and serves the host
        # loop for an exponential backoff window, then a single
        # half-open probe decides between recovery — the set is
        # re-admitted to the device path — and a re-trip with doubled
        # backoff.  Per key, so one broken set cannot disable (nor
        # reset the counter of) a healthy one; entries pin their
        # policy objects (keys are id() tuples, so CPython id reuse
        # must not circuit-break a healthy set) and the registry is
        # size-bounded with counted evictions.  When several distinct
        # sets are open at once the failure is systemic (broken
        # backend): _breaker_opened turns the global device switch off
        # so policy churn cannot spawn an endless stream of doomed
        # compiles.
        from ..serving.breaker import BreakerRegistry
        self._breakers = BreakerRegistry(
            failure_limit=self.DEVICE_FAILURE_LIMIT,
            on_open=self._breaker_opened)
        # admission serving mode: 'batch' routes CREATE/UPDATE-path
        # validate AND mutate scans through the micro-batching scheduler
        # (serving/), 'sync' keeps the per-request dispatch
        import os as _os
        self.serving_mode = serving_mode or \
            _os.environ.get('KTPU_SERVING', 'sync')
        # device-side mutate (kyverno_tpu/mutate/): lowered strategic-
        # merge / json6902 policy sets serve the admission mutate chain
        # as batched device dispatches; 0 keeps every mutate request on
        # the host engine loop (the bit-identity oracle)
        self.mutate_device = _os.environ.get(
            'KTPU_MUTATE_DEVICE', '1') not in ('0', 'false', 'off')
        self._batcher = None
        self._batcher_lock = threading.Lock()
        self._key_memo: Tuple[Any, tuple] = (None, ())

    def _policy_key(self, policies):
        """The identity of a policy set: the ids of its objects, in
        order.  ``Cache.get_installed`` hands out one list object until
        the set changes, so the last key is kept beside its list (a
        request asks for it several times over a thousand policies)."""
        memo = self._key_memo
        if memo[0] is policies:
            return memo[1]
        key = tuple(id(p) for p in policies)
        self._key_memo = (policies, key)
        return key

    def _device_scanner(self, policies, kind: str = 'validate'):
        """Scanner for ``policies``, or None while one is still compiling.

        The validate path passes the installed set of the request's
        kind (``Cache.get_installed``: cluster-wide policies and every
        namespace's own), so one scanner serves every namespace and the
        scanner, the breaker and the batch are keyed on that set, not
        on the list that applies to one request.  ``kind`` selects the
        program: ``validate`` builds a
        ``BatchScanner``, ``mutate`` a ``MutateScanner`` (a mutate set
        that does not lower is cached too — callers check ``.ok`` — so
        the lowering never re-runs per request).  Building pays jax
        trace + XLA compile (seconds to minutes on a policy-set change);
        doing that on the request path would blow the webhook timeout
        (reference: 10s cap, spec_types.go:95).  The build runs on a
        background thread; until the compiled path is ready the validate
        path asks the predecessor (``_serving_predecessor``) and, where
        that does not hold the request's policies, serves the host
        engine loop — identical verdicts.  The
        circuit breaker is keyed per policy set (kindless): a backend
        broken for one program kind is broken for the other."""
        from ..observability import coverage
        from ..serving import breaker as breaker_mod
        base = self._policy_key(policies)
        key = (kind,) + base
        decision = self._breakers.allow(base)
        if decision == breaker_mod.OPEN:
            # circuit open: host loop serves until the backoff elapses
            # (or this window's single probe is already in flight)
            coverage.record_fallback('serving',
                                     coverage.REASON_BREAKER_OPEN)
            return None
        with self._scanner_lock:
            scanner = self._scanners.get(key)
            if scanner is not None:
                self._scanners.move_to_end(key)
                # a PROBE grant rides this scanner: the caller's scan
                # outcome reaches record_success/_record_key_failure
                # downstream and resolves the half-open window
                return scanner
            if key in self._building:
                if decision == breaker_mod.PROBE:
                    # the probe cannot scan until the rebuild lands;
                    # free the slot so the next window re-probes
                    self._breakers.probe_abort(base)
                return None  # still compiling; host loop serves meanwhile
            if len(self._building) >= self.MAX_CONCURRENT_BUILDS:
                # a compile burst across many policy sets must not fork
                # unbounded trace+compile threads; later requests retry
                if decision == breaker_mod.PROBE:
                    self._breakers.probe_abort(base)
                return None
            self._building.add(key)

        def build():
            try:
                if kind == 'mutate':
                    from ..mutate import MutateScanner
                    scanner = MutateScanner(policies, engine=self.engine)
                    if scanner.ok:
                        scanner.warmup()
                else:
                    from ..compiler.scan import BatchScanner
                    scanner = BatchScanner(policies, engine=self.engine)
                    # pre-warm the small-batch shape an admission request
                    # hits (AOT-loads from the persistent executable store
                    # when a prior process already compiled this set)
                    scanner.warmup()
                    if self.serving_mode == 'batch':
                        self._get_batcher().record_build()
                self._install_scanner(key, base, kind, policies,
                                      scanner)
            except Exception as e:  # noqa: BLE001
                # a policy set that cannot compile must trip the circuit
                # breaker, or every request re-spawns a doomed
                # multi-second compile
                self._record_key_failure(base, policies,
                                         f'build failed ({kind}): {e}')
            finally:
                with self._scanner_lock:
                    self._building.discard(key)
        threading.Thread(target=build, name='ktpu-scanner-build',
                         daemon=True).start()
        if decision == breaker_mod.PROBE:
            # the probe's real verdict is the rebuild just spawned: a
            # build failure re-trips via _record_key_failure; success
            # caches the scanner for the next probe to ride.  Either
            # way this caller serves the host loop now, so the slot
            # frees for the next window
            self._breakers.probe_abort(base)
        return None

    def _install_scanner(self, key: tuple, base: tuple, kind: str,
                         policies, scanner) -> None:
        """Insert a freshly built scanner, hot-swapping any live
        predecessor serving the same logical policy set.

        Policy churn replaces the Policy objects, so the successor's
        id()-tuple key never matches the predecessor's — the logical
        set is matched by (namespace, name) identity overlap instead.
        The swap is atomic under the scanner lock AFTER the successor
        is fully built and warmed: requests keep riding the predecessor
        (or the host loop, with identical verdicts) until the flip, so
        a churn event never sheds and never 500s.  In-flight batches
        hold direct references to the predecessor and drain naturally.
        Breaker state migrates to the successor's key instead of
        resetting to closed — a backend fault that tripped the old
        serial must not be forgiven by recompiling the policy set."""
        from ..observability.metrics import global_registry
        ident = frozenset((p.namespace, p.name) for p in policies)
        swapped = None
        with self._scanner_lock:
            best, best_ratio = None, 0.0
            for k in self._scanners:
                if k[0] != key[0] or k == key:
                    continue
                prev = self._scanner_sets.get(k, ((),))[0]
                if not prev:
                    continue
                ratio = len(ident & prev) / max(len(ident), len(prev), 1)
                if ratio > best_ratio:
                    best, best_ratio = k, ratio
            if best is not None and best_ratio >= 0.5:
                old = self._scanners.pop(best)
                self._scanner_sets.pop(best, None)
                state = self._breakers.migrate(best[1:], base,
                                               policies=policies)
                swapped = (old, state)
            while len(self._scanners) >= self._scanners_max:
                evicted, _ = self._scanners.popitem(last=False)
                self._scanner_sets.pop(evicted, None)
            self._scanners[key] = scanner
            self._scanner_sets[key] = (ident, policies, frozenset(base))
        if swapped is None:
            return
        old, state = swapped
        reg = global_registry()
        if reg is not None:
            reg.inc(SCANNER_HOT_SWAPS, kind=kind)
            reg.inc(BREAKER_MIGRATIONS)
        touched = None
        old_pset = getattr(old, '_pset', None)
        new_pset = getattr(scanner, '_pset', None)
        if old_pset is not None and new_pset is not None:
            from ..partition.plan import diff_plans
            touched = diff_plans(old_pset.plan, new_pset.plan).touched
        from ..partition import census as partition_census
        partition_census.record_swap(
            kind, getattr(old, 'serial', None),
            getattr(scanner, 'serial', None),
            breaker_state=state, touched=touched)
        import logging
        from ..observability.logging import with_values
        with_values(logging.getLogger('kyverno.webhooks'),
                    'scanner hot-swap', kind=kind,
                    old_serial=getattr(old, 'serial', None),
                    new_serial=getattr(scanner, 'serial', None),
                    breaker_state=state)

    def _serving_predecessor(self, policies):
        """While the installed set's successor compiles: ``(scanner, its
        policy list, its key)`` of a validate scanner that holds every
        policy of ``policies`` (a request's own list), the very objects,
        and whose breaker is closed; None where there is none.

        A change to one namespace's policies changes the installed set
        and so the key every request asks for, but the objects of every
        other namespace stay the ones the predecessor compiled.  Its
        answers, kept to the request's own list, are right for each of
        them, and for the changed namespace after a removal; a request
        whose list holds an added or edited policy finds no such scanner
        and is answered by the host loop until the swap."""
        from ..serving import breaker as breaker_mod
        want = set(map(id, policies))
        with self._scanner_lock:
            for key in reversed(self._scanners):
                held = self._scanner_sets.get(key)
                if key[0] == 'validate' and held is not None and \
                        want <= held[2] and self._breakers.state(
                            key[1:]) == breaker_mod.CLOSED:
                    return self._scanners[key], held[1], key[1:]
        return None

    def _record_key_failure(self, key: tuple, policies, reason: str) -> None:
        import logging
        from ..observability.logging import with_values
        from ..serving import breaker as breaker_mod
        log = logging.getLogger('kyverno.webhooks')
        state = self._breakers.record_failure(key, policies, reason)
        with_values(log, 'device path failure', level=logging.ERROR,
                    error=reason, breaker_state=state)
        if state == breaker_mod.OPEN:
            with_values(log, 'circuit open: policy set quarantined to '
                        'the host loop until the backoff elapses',
                        level=logging.ERROR)

    def _breaker_opened(self, open_count: int) -> None:
        """BreakerRegistry trip callback: several distinct policy sets
        open at once means the backend itself is broken — flip the
        global device switch off so churn cannot spawn an endless
        stream of doomed compiles (individual breakers still recover
        per set if the operator re-enables the device path)."""
        if open_count >= self.GLOBAL_DEAD_LIMIT and self.device:
            import logging
            from ..observability.logging import with_values
            self.device = False
            with_values(logging.getLogger('kyverno.webhooks'),
                        'device path disabled globally: multiple '
                        'policy sets failing (systemic backend failure)',
                        level=logging.ERROR)

    def wait_device_ready(self, policies, timeout: float = 600.0,
                          kind: str = 'validate') -> bool:
        """Block until the compiled scanner of ``kind`` for ``policies``
        is serving (benchmarks / tests measuring steady-state latency;
        a mutate set that does not lower is "serving" too: ask its
        scanner's ``ok``).  Returns False immediately while the set's
        circuit breaker is open."""
        from ..serving import breaker as breaker_mod
        key = self._policy_key(policies)
        deadline = time.time() + timeout
        while time.time() < deadline:
            if not self.device:
                return False
            if self._breakers.state(key) == breaker_mod.OPEN:
                return False
            if self._device_scanner(policies, kind) is not None:
                # readiness polling never scans: release any half-open
                # probe slot the allow() check granted on our behalf
                self._breakers.probe_abort(key)
                return True
            time.sleep(0.05)
        return False

    # -- admission micro-batching (serving/) -------------------------------

    def _get_batcher(self):
        batcher = self._batcher
        if batcher is None:
            with self._batcher_lock:
                batcher = self._batcher
                if batcher is None:
                    from ..serving.batcher import AdmissionBatcher
                    batcher = AdmissionBatcher(
                        on_success=self._batch_scan_ok,
                        on_failure=self._batch_scan_failed)
                    self._batcher = batcher
        return batcher

    def _batch_scan_ok(self, policies) -> None:
        # mirror of the sync path's success bookkeeping: a successful
        # dispatch closes the set's breaker (half-open probe recovery)
        # or forgets its consecutive-failure count
        self._breakers.record_success(self._policy_key(policies))

    def _batch_scan_failed(self, policies, error) -> None:
        # mirror of the sync path's failure recovery: drop the broken
        # scanner so the next request rebuilds it, and count one breaker
        # failure for the set (the whole batch sheds on one dispatch, so
        # a broken backend trips the breaker per dispatch, not per
        # rider).  Both program kinds are dropped — the callback only
        # knows the policy set, and a rebuild of the innocent kind is
        # cheap next to a broken backend
        base = self._policy_key(policies)
        with self._scanner_lock:
            for kind in ('validate', 'mutate'):
                self._scanners.pop((kind,) + base, None)
                self._scanner_sets.pop((kind,) + base, None)
        self._record_key_failure(
            base, policies,
            f'batched scan failed, shedding to host engine: {error}')

    def _batched_scan(self, scanner, policies, request, pctx,
                      old_resource: Optional[dict] = None,
                      resource: Optional[dict] = None):
        """Route one validate or mutate scan through the micro-batcher.

        The ticket key is the scanner's monotonic serial alone:
        validate and mutate compile distinct scanners so those
        dispatches never mix, while distinct users, roles, namespaces
        AND verbs coalesce — each rider's admission tuple rides to the
        scanner as a per-row column (compiler/admission.py), so a
        shared dispatch stays bit-identical to every request's own
        sync scan.  Returns ``(responses, prov)``: this request's result
        rows (None when the request shed to the host engine loop —
        queue full, deadline blown, dispatch failed, or batcher stopped
        — the caller then serves the identical-verdict host path, never
        a 500) and the decision-provenance fields of whatever happened:
        ``path`` is ``batch`` with the batcher-filled batch id /
        occupancy / amortized device share on success, or
        ``shed:<reason>`` with the time spent waiting otherwise."""
        import time as _time
        from ..serving import shed as shed_policy
        from ..serving.queue import QueueFull, Stopped
        batcher = self._get_batcher()
        if resource is None:
            resource = admission.request_resource(request)
        adm = (pctx.admission_info, pctx.exclude_group_roles,
               pctx.namespace_labels, request.get('operation') or 'CREATE')
        try:
            ticket = batcher.submit(
                resource=resource, context=pctx.json_context._data,
                pctx=pctx, admission=adm, scanner=scanner,
                policies=policies, old_resource=old_resource)
        except QueueFull:
            batcher.record_shed(shed_policy.REASON_QUEUE_FULL)
            return None, {'path':
                          f'shed:{shed_policy.REASON_QUEUE_FULL}'}
        except Stopped:
            batcher.record_shed(shed_policy.REASON_SHUTDOWN)
            return None, {'path': f'shed:{shed_policy.REASON_SHUTDOWN}'}
        deadline_s = batcher.shed_deadline_s
        ts = request.get('timeoutSeconds')
        if ts:
            # the API server aborts the whole call at the webhook's own
            # timeoutSeconds (reference: spec_types.go:95): shed at half
            # that budget so the host-loop fallback still fits in the
            # remainder, never loosening the KTPU_SHED_DEADLINE_MS cap
            try:
                deadline_s = min(deadline_s, max(0.01, float(ts) / 2.0))
            except (TypeError, ValueError):
                pass
        responses = ticket.wait(deadline_s)
        if responses is None:
            reason = ticket.shed_reason or shed_policy.REASON_DEADLINE
            return None, {
                'path': f'shed:{reason}',
                'queue_wait_s': _time.monotonic() - ticket.enqueued_at}
        prov = dict(ticket.prov) if ticket.prov is not None else {}
        prov['path'] = 'batch'
        return responses, prov

    def shutdown(self) -> None:
        """Drain and stop the admission batcher: pending futures get
        their batched responses before the process exits (wired through
        WebhookServer.stop and cmd/internal.Setup shutdown hooks)."""
        batcher = self._batcher
        if batcher is not None:
            batcher.stop(drain=True)

    # -- validate ---------------------------------------------------------

    def validate(self, request: dict,
                 failure_policy: str = 'Fail') -> dict:
        """reference: pkg/webhooks/resource/handlers.go:110 Validate"""
        uid = request.get('uid', '')
        kind = (request.get('kind') or {}).get('kind', '')
        ns = request.get('namespace', '')
        from ..observability import device as devtel
        from ..observability import provenance
        from ..observability import slo
        t_candidates = time.monotonic()
        # the policies that apply to this request decide which responses
        # exist for it; the installed set of the kind (they are part of
        # it, in its order) is what is compiled and what the scanner,
        # the breaker and the batch are keyed on
        policies = self.cache.get_policies(pcache.VALIDATE_ENFORCE, kind, ns)
        installed = self.cache.get_installed(pcache.VALIDATE_ENFORCE, kind)
        installed_key = self._policy_key(installed)
        # the set whose scanner answers: the installed one, or its
        # predecessor while the installed one compiles
        served, served_key = installed, installed_key
        devtel.record_stage('candidates', time.monotonic() - t_candidates)
        generate_policies = self.cache.get_policies(pcache.GENERATE, kind, ns)
        prov_on = provenance.enabled()
        slo_on = slo.enabled()
        t_start = time.monotonic()
        # where the compiled path was asked and the host loop answered
        # (batch mode counts it: AdmissionBatcher.stats)
        host_reason: Optional[str] = None
        # where the request rode a batch, the handler's own time around
        # the batcher: (seconds from entry to submit, when the resolved
        # ticket came back)
        own: Optional[Tuple[float, float]] = None
        # decision provenance: which serving path answered this request
        # (batch | sync | shed:<reason> | host_fallback) plus the
        # batch/cache attribution that path produced
        prov_path = 'host_fallback'
        prov_extra: Dict[str, Any] = {}
        try:
            pctx = self.pc_builder.build(request)
        except Exception as e:  # noqa: BLE001
            if prov_on or slo_on:
                duration_s = time.monotonic() - t_start
                slo.record('host_fallback', duration_s)
                if prov_on:
                    provenance.record_decision(
                        path='host_fallback', uid=uid, kind=kind,
                        namespace=ns,
                        name=request.get('name', '') or '',
                        operation=request.get('operation', '') or '',
                        duration_s=duration_s,
                        error=f'policy context build failed: {e}')
            return admission.response(uid, False,
                                      f'failed to build policy context: {e}')
        pctx.namespace_labels = self.namespace_labels(ns)

        responses: List[EngineResponse] = []
        # device fast path: CREATE and UPDATE requests with no policy
        # exceptions run through the compiled batch evaluator (exact via
        # host fallback); UPDATE rows carry oldObject for the scanner's
        # old-match retry; DELETE keeps the engine loop (no new object)
        operation = request.get('operation') or ''
        use_device = (self.device and policies and
                      operation in ('CREATE', 'UPDATE') and
                      not pctx.exceptions)
        old_doc = (admission.request_old_resource(request) or None) \
            if operation == 'UPDATE' else None
        if use_device:
            try:
                from .. import faults
                faults.check(faults.SITE_WEBHOOK_HANDLER)
                scanner = self._device_scanner(installed)
                if scanner is None:
                    from ..serving import breaker as breaker_mod
                    building = self._breakers.state(
                        installed_key) == breaker_mod.CLOSED
                    before = self._serving_predecessor(policies) \
                        if building else None
                    if before is not None:
                        scanner, served, served_key = before
                if scanner is None:
                    # compiled path still building and no predecessor
                    # holds this request's policies — or the set's
                    # circuit breaker is open: host loop this request
                    host_reason = 'building'
                    if not building:
                        from ..serving import shed as shed_policy
                        host_reason = 'breaker'
                        prov_path = \
                            f'shed:{shed_policy.REASON_BREAKER_OPEN}'
                        if self.serving_mode == 'batch':
                            self._get_batcher().record_shed(
                                shed_policy.REASON_BREAKER_OPEN)
                    use_device = False
                elif self.serving_mode == 'batch':
                    # micro-batching scheduler: this request coalesces
                    # with the concurrent requests of its kind, whatever
                    # their namespace, user or verb, into one shared
                    # device dispatch of the installed set's scanner
                    # (serving/batcher.py); a shed comes back as None
                    # and the host loop serves
                    t_submit = time.monotonic()
                    batched, bprov = self._batched_scan(
                        scanner, served, request, pctx,
                        old_resource=old_doc)
                    if batched is not None:
                        own = (t_submit - t_start, time.monotonic())
                    prov_path = bprov.pop('path')
                    prov_extra = bprov
                    prov_extra['fingerprint'] = getattr(
                        scanner, 'fingerprint', '')
                    if batched is None:
                        host_reason = 'shed'
                        use_device = False
                    else:
                        responses = batched
                else:
                    resource = admission.request_resource(request)
                    cap = devtel.ScanCapture() if prov_on else None
                    with devtel.install_capture(cap):
                        [responses] = scanner.scan(
                            [resource],
                            contexts=[pctx.json_context._data],
                            admission=(pctx.admission_info,
                                       pctx.exclude_group_roles,
                                       pctx.namespace_labels, operation),
                            pctx_factory=lambda doc: pctx,
                            old_resources=[old_doc] if old_doc else None)
                    prov_path = 'sync'
                    if cap is not None:
                        device_eval_s = cap.stage_s('device_eval')
                        prov_extra = {
                            'occupancy': 1,
                            'device_share_s': device_eval_s,
                            'device_eval_s': device_eval_s,
                            'aot_cache': cap.aot,
                            'coverage_ratio': cap.coverage_ratio,
                            'fingerprint': getattr(scanner,
                                                   'fingerprint', ''),
                        }
                    # success closes the set's breaker (recovery) or
                    # forgets its consecutive-failure count
                    self._breakers.record_success(served_key)
                if use_device and len(policies) != len(served):
                    # the compiled set answers for every policy whose
                    # rules match the resource; the responses that exist
                    # for this request are those of its own list
                    # (another namespace's never match; an override can
                    # take an installed policy out of Enforce here)
                    applies = set(map(id, policies))
                    responses = [r for r in responses
                                 if id(r.policy) in applies]
            except Exception as e:  # noqa: BLE001
                # device failure must not turn into a 500: drop to the
                # host engine loop and discard the broken scanner so the
                # next request rebuilds it (failure recovery, SURVEY §5.3).
                # Repeated failures trip the per-set circuit breaker —
                # otherwise every request would pay a full policy-set
                # recompile before falling back.
                with self._scanner_lock:
                    self._scanners.pop(('validate',) + served_key, None)
                    self._scanner_sets.pop(('validate',) + served_key,
                                           None)
                self._record_key_failure(
                    served_key, served,
                    f'scan failed, falling back to host engine: {e}')
                provenance.notify_scan_error(e)
                host_reason = 'breaker'
                use_device = False
                responses = []
                prov_path = 'host_fallback'
                prov_extra = {'error': f'scan failed: {e}'}
        if self.serving_mode == 'batch' and (use_device or host_reason):
            self._get_batcher().record_path(
                host_reason, len(policies), len(served))
        if not use_device:
            for policy in policies:
                ctx = pctx.copy()
                ctx.policy = policy
                responses.append(self.engine.validate(ctx))
        # annotate the handler span with the serving path so a trace
        # distinguishes compiled-device requests from host-loop ones
        from ..observability import tracing
        span = tracing.current_span()
        if span is not None:
            span.set_attribute('device_path', bool(use_device))
        if prov_on or slo_on:
            duration_s = time.monotonic() - t_start
            # feed the admission-latency SLO digest (shed:<reason>
            # folds to the shed path inside record); no-op when the
            # engine is off (KTPU_SLO_WINDOW_S=0)
            slo.record(prov_path, duration_s)
            if prov_on:
                provenance.record_decision(
                    path=prov_path, uid=uid, kind=kind, namespace=ns,
                    name=request.get('name', '') or '',
                    operation=request.get('operation', '') or '',
                    duration_s=duration_s, **prov_extra)
        blocked = block_request(responses, failure_policy)
        if self.event_sink is not None and responses:
            # reference: handlers.go Validate -> webhooks/utils/event.go
            # GenerateEvents fed to the event controller
            self.event_sink(responses, blocked)
        if blocked:
            return self._answered(own, *self._denied(uid, responses))
        # async hand-offs: audit-mode policies and generate URs
        if self.audit_sink is not None:
            self.audit_sink(request, responses)
        if self.ur_sink is not None and generate_policies:
            self._create_update_requests(request, pctx, generate_policies)
        if self.ur_sink is not None:
            # mutate-existing policies ride UpdateRequests too
            # (reference: pkg/webhooks/resource/updaterequest.go:20
            # handleMutateExisting; DELETE triggers use the old object)
            trigger_doc = admission.request_resource(request) or \
                admission.request_old_resource(request)
            trigger_res = Resource(trigger_doc)
            mutate_existing = [
                p for p in self.cache.get_policies(pcache.MUTATE, kind, ns)
                if any((r.raw.get('mutate') or {}).get('targets') and
                       matches_resource_description(
                           trigger_res, r, pctx.admission_info,
                           pctx.exclude_group_roles, pctx.namespace_labels,
                           p.namespace) is None
                       for r in p.rules)]
            if mutate_existing:
                self._create_update_requests(request, pctx,
                                             mutate_existing,
                                             ur_type='mutate')
        warnings = get_warning_messages(responses)
        return self._answered(own, admission.response(uid, True, '',
                                                      warnings))

    @staticmethod
    def _denied(uid: str,
                responses: List[EngineResponse]) -> Tuple[dict, float]:
        """The denial that ``responses`` block, and the seconds its
        message took on this thread (stage ``deny_message``, one sample
        a denied request)."""
        from ..observability import device as devtel
        t0 = time.monotonic()
        message = get_blocked_messages(responses)
        message_s = time.monotonic() - t0
        devtel.record_stage('deny_message', message_s)
        return admission.response(uid, False, message), message_s

    def _answered(self, own: Optional[Tuple[float, float]], response: dict,
                  message_s: float = 0.0) -> dict:
        """``response``, on its way out of ``validate``: a request that
        rode a batch reports the handler's own time around the batcher
        — ``handler_pre`` (entry to submit) and ``handler_post`` (from
        the resolved ticket to here) in the stage histogram, their sum
        to the batcher (``handler_self_ms``), and with it what a
        denial's message took of it (``handler_message_ms``)."""
        if own is not None:
            from ..observability import device as devtel
            pre_s, t_back = own
            post_s = time.monotonic() - t_back
            devtel.record_stage('handler_pre', pre_s)
            devtel.record_stage('handler_post', post_s)
            self._get_batcher().record_handler(pre_s + post_s, message_s)
        return response

    def audit_responses(self, request: dict) -> List[EngineResponse]:
        """Audit-mode engine responses for report construction
        (reference: validation.go:156 buildAuditResponses)."""
        kind = (request.get('kind') or {}).get('kind', '')
        ns = request.get('namespace', '')
        policies = self.cache.get_policies(pcache.VALIDATE_AUDIT, kind, ns)
        pctx = self.pc_builder.build(request)
        pctx.namespace_labels = self.namespace_labels(ns)
        out = []
        for policy in policies:
            ctx = pctx.copy()
            ctx.policy = policy
            out.append(self.engine.validate(ctx))
        return out

    def _create_update_requests(self, request: dict, pctx, policies,
                                ur_type: str = 'generate') -> None:
        """Spawn UpdateRequests for generate / mutate-existing policies
        on admission (reference: pkg/webhooks/resource/updaterequest.go:20)."""
        resource = admission.request_resource(request)
        if not resource and request.get('operation') == 'DELETE':
            resource = admission.request_old_resource(request)
        r = Resource(resource)
        for policy in policies:
            policy_key = f'{policy.namespace}/{policy.name}' \
                if policy.namespace else policy.name
            self.ur_sink({
                'type': ur_type,
                'policy': policy_key,
                'resource': {
                    'kind': r.kind, 'apiVersion': r.api_version,
                    'namespace': r.namespace, 'name': r.name,
                },
                'context': {
                    'userInfo': request.get('userInfo') or {},
                    'admissionRequestInfo': {
                        'operation': request.get('operation', ''),
                        # the background processors rebuild the admission
                        # context — DELETE triggers resolve from oldObject
                        # (reference: pkg/background/common/context.go:32)
                        'admissionRequest': {
                            'operation': request.get('operation', ''),
                            'object': request.get('object'),
                            'oldObject': request.get('oldObject'),
                            'userInfo': request.get('userInfo') or {},
                        },
                    },
                },
            })

    # -- mutate -----------------------------------------------------------

    @staticmethod
    def _canonicalize_context_images(pctx) -> None:
        from ..engine.mutate.jsonpatch import apply_patch
        from ..utils.image_extract import extract_images_from_resource
        try:
            infos = extract_images_from_resource(pctx.new_resource, None)
        except Exception:  # noqa: BLE001 - no images is the common case
            return
        ops = [{'op': 'replace', 'path': info.pointer, 'value': str(info)}
               for group in infos.values() for info in group.values()
               if info.pointer]
        if not ops:
            return
        import copy as _copy
        try:
            patched = apply_patch(_copy.deepcopy(pctx.new_resource), ops)
            pctx.json_context.add_resource(patched)
        except Exception:  # noqa: BLE001 - context stays unpatched
            pass

    def _post_mutate_policy(self, uid: str, policy, er: EngineResponse,
                            patches: List[dict],
                            responses: List[EngineResponse],
                            failure_policy: str) -> Optional[dict]:
        """Per-policy admission bookkeeping shared by the host mutate
        loop and the device fast path: deny on failure, collect patches,
        schema-validate the patched resource.  Returns the deny response
        or None to continue the chain."""
        if not er.is_successful():
            # a failed/errored mutate rule fails the admission —
            # failurePolicy only covers webhook transport failures
            # (reference: mutation.go:163 applyMutation →
            # mutation.go:112 'mutation policy %s error')
            failed = er.get_failed_rules()
            return admission.response(
                uid, False,
                f'mutation policy {policy.name} error: failed to '
                f'apply policy {policy.name} rules {failed}')
        policy_patches = [p for rr in er.policy_response.rules
                          for p in (rr.patches or [])]
        if policy_patches:
            patches.extend(policy_patches)
            # the mutated resource must stay schema-valid
            # (reference: mutation.go → openapi.ValidateResource,
            # pkg/openapi/manager.go:88)
            if self.openapi_manager is not None and er.patched_resource:
                from ..openapi.manager import ValidationError
                try:
                    self.openapi_manager.validate_resource(
                        er.patched_resource)
                except ValidationError as e:
                    return admission.response(
                        uid, False,
                        f'mutated resource failed schema validation: '
                        f'{e}')
        responses.append(er)
        if er.is_error() and failure_policy == 'Fail':
            return self._denied(uid, responses)[0]
        return None

    def _device_mutate_steps(self, request: dict, pctx, mutate_policies,
                             t_start: float = 0.0) -> Optional[tuple]:
        """The device mutate chain for one request, or None when the
        host engine loop must serve it (knob off, verb outside
        CREATE/UPDATE, exceptions/subresource in play, set not lowered,
        scanner still building, shed, or scan failure — never a 500).
        Returns ``(steps, patched, t_back)``: the ordered ``[(policy,
        EngineResponse), ...]`` steps, bit-identical to the host loop by
        construction (kyverno_tpu/mutate/scanner.py), the cumulative
        document, and when the resolved ticket came back where the
        request rode a batch (else None).  Such a request leaves a
        sample of stage ``mutate_pre``, ``t_start`` (``mutate()``'s
        entry) to its submit; and in batch mode every request that gets
        past the first gate is counted by the batcher as answered by
        the compiled path or by the host loop."""
        from ..observability import device as devtel
        operation = request.get('operation') or ''
        if not (self.device and self.mutate_device and mutate_policies and
                operation in ('CREATE', 'UPDATE') and
                not pctx.exceptions and not request.get('subResource')):
            return None
        row = None
        try:
            scanner = self._device_scanner(mutate_policies, kind='mutate')
            # None: still lowering; not ok: the set does not lower (the
            # placement records on the coverage ledger name why)
            if scanner is not None and scanner.ok:
                if self.serving_mode == 'batch':
                    t_submit = time.monotonic()
                    batched, _prov = self._batched_scan(
                        scanner, mutate_policies, request, pctx,
                        resource=pctx.new_resource)
                    if batched is not None:  # None on shed -> host loop
                        row = batched + (time.monotonic(),)
                        devtel.record_stage('mutate_pre',
                                            t_submit - t_start)
                else:
                    [scanned] = scanner.scan(
                        [pctx.new_resource],
                        admission=(pctx.admission_info,
                                   pctx.exclude_group_roles,
                                   pctx.namespace_labels, operation),
                        pctx_factory=lambda doc: pctx)
                    self._breakers.record_success(
                        self._policy_key(mutate_policies))
                    row = scanned + (None,)
        except Exception as e:  # noqa: BLE001
            # identical never-500 recovery to the validate path: drop
            # the broken scanner, count one breaker failure, host loop
            base = self._policy_key(mutate_policies)
            with self._scanner_lock:
                self._scanners.pop(('mutate',) + base, None)
                self._scanner_sets.pop(('mutate',) + base, None)
            self._record_key_failure(
                base, mutate_policies,
                f'mutate scan failed, falling back to host engine: {e}')
        if self.serving_mode == 'batch':
            self._get_batcher().record_mutate_path(row is not None)
        return row

    def mutate(self, request: dict, failure_policy: str = 'Fail') -> dict:
        """reference: pkg/webhooks/resource/handlers.go:157 Mutate +
        mutation.go:80 applyMutations (sequential, cumulative)."""
        from ..observability import device as devtel
        t_start = time.monotonic()
        uid = request.get('uid', '')
        kind = (request.get('kind') or {}).get('kind', '')
        ns = request.get('namespace', '')
        mutate_policies = self.cache.get_policies(pcache.MUTATE, kind, ns)
        verify_policies = self.cache.get_policies(
            pcache.VERIFY_IMAGES_MUTATE, kind, ns)
        try:
            pctx = self.pc_builder.build(request)
        except Exception as e:  # noqa: BLE001
            return admission.response(uid, False,
                                      f'failed to build policy context: {e}')
        pctx.namespace_labels = self.namespace_labels(ns)
        # canonicalize images in the JSON context's request.object so
        # {{request.object...image}} variables resolve to the full
        # registry form; the stored resource and emitted patches keep the
        # original spelling (reference: handlers.go:174 →
        # pkg/engine/context/imageutils.go:12 MutateResourceWithImageInfo)
        self._canonicalize_context_images(pctx)

        patches: List[dict] = []
        responses: List[EngineResponse] = []
        # device fast path: a lowered mutate policy set evaluates its
        # whole cumulative chain as one batched device dispatch
        # (kyverno_tpu/mutate/) whose rows coalesce with concurrent
        # mutate requests in batch serving mode
        device_row = self._device_mutate_steps(request, pctx,
                                               mutate_policies, t_start)
        # when the resolved ticket came back, where the request rode a
        # batch: from there to the return is stage ``mutate_post``
        t_back = None

        def answered(response: dict) -> dict:
            if t_back is not None:
                devtel.record_stage('mutate_post',
                                    time.monotonic() - t_back)
            return response

        if device_row is not None:
            steps, patched, t_back = device_row
            for policy, er in steps:
                deny = self._post_mutate_policy(uid, policy, er, patches,
                                                responses, failure_policy)
                if deny is not None:
                    return answered(deny)
            if steps:
                # verify-images policies see the chain's cumulative
                # output, exactly as the host loop threads it
                pctx = pctx.copy()
                pctx.new_resource = patched or pctx.new_resource
                pctx.json_context.add_resource(pctx.new_resource)
        else:
            for policy in mutate_policies:
                if not any(r.has_mutate() for r in policy.rules):
                    continue
                ctx = pctx.copy()
                ctx.policy = policy
                er = self.engine.mutate(ctx)
                deny = self._post_mutate_policy(uid, policy, er, patches,
                                                responses, failure_policy)
                if deny is not None:
                    return deny
                # mutations apply cumulatively: the patched resource
                # re-enters the context for the next policy
                # (mutation.go:123)
                pctx = pctx.copy()
                pctx.new_resource = er.patched_resource or \
                    pctx.new_resource
                pctx.json_context.add_resource(pctx.new_resource)
        for policy in verify_policies:
            ctx = pctx.copy()
            ctx.policy = policy
            er, _meta = self.engine.verify_and_patch_images(
                ctx, self.registry_client)
            iv_patches = [p for rr in er.policy_response.rules
                          for p in (rr.patches or [])]
            patches.extend(iv_patches)
            responses.append(er)
            if er.is_failed():
                return answered(self._denied(uid, responses)[0])
        warnings = get_warning_messages(responses)
        return answered(admission.mutation_response(uid, patches,
                                                    warnings))
