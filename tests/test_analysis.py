"""ktpu-lint framework tests: one positive and one negative fixture
per rule id (deleting a rule's implementation fails its fixture test),
plus suppression semantics and baseline round-trips."""

import json
import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from kyverno_tpu.analysis import Analyzer, RULES, write_baseline  # noqa: E402
from kyverno_tpu.analysis.knobs import KNOBS  # noqa: E402
from kyverno_tpu.observability.catalog import METRICS  # noqa: E402
from kyverno_tpu.observability.coverage import REASONS  # noqa: E402


def run(tmp_path, sources, rules=None, baseline=None):
    """Write {relpath: source} under tmp_path and analyze it."""
    for rel, src in sources.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    a = Analyzer([str(tmp_path)], str(tmp_path),
                 baseline_path=baseline, rules=rules)
    return a.run()


def rule_ids(report):
    return {f.rule_id for f in report.active}


JIT_PRELUDE = """\
    import jax
    import jax.numpy as jnp
"""


# -- KTPU1xx: trace safety ---------------------------------------------------

def test_ktpu101_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        x = jnp.sum(t)
        return x.item()
    jf = jax.jit(f)
    """}, rules=['KTPU101'])
    assert rule_ids(rep) == {'KTPU101'}
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        return jnp.sum(t)
    jf = jax.jit(f)

    def host_only(t):
        return t.item()
    """}, rules=['KTPU101'])
    assert not rep.active  # .item() outside the jit graph is fine


def test_ktpu101_transitive_reachability(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def helper(t):
        return t.tolist()

    def f(t):
        return helper(t)
    jf = jax.jit(f)
    """}, rules=['KTPU101'])
    assert rule_ids(rep) == {'KTPU101'}


def test_ktpu102_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        return int(jnp.sum(t))
    jf = jax.jit(f)
    """}, rules=['KTPU102'])
    assert rule_ids(rep) == {'KTPU102'}
    # a *static* jit arg is a plain python value — casting it is fine;
    # without static_argnames the param is a tracer and the cast is a
    # finding (see test_taint_entry_param below)
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t, n):
        return t * int(n)
    jf = jax.jit(f, static_argnames='n')
    """}, rules=['KTPU102'])
    assert not rep.active  # cast of a static python value


def test_ktpu103_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        y = jnp.sum(t)
        if y > 0:
            return t
        return -t
    jf = jax.jit(f)
    """}, rules=['KTPU103'])
    assert rule_ids(rep) == {'KTPU103'}
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t, mask):
        if mask is None:
            return t
        return jnp.where(mask, t, 0)
    jf = jax.jit(f)
    """}, rules=['KTPU103'])
    assert not rep.active  # `is None` gates optionality, not tracers


# -- KTPU2xx: retrace hazards ------------------------------------------------

def test_ktpu201_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    STATE = {}

    def f(t):
        return t + len(STATE)
    jf = jax.jit(f)
    """}, rules=['KTPU201'])
    assert rule_ids(rep) == {'KTPU201'}
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    STATE = (1, 2)

    def f(t):
        return t + len(STATE)
    jf = jax.jit(f)
    """}, rules=['KTPU201'])
    assert not rep.active  # tuples cannot drift under the executable


def test_ktpu201_enclosing_scope(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def build():
        holder = {'k': None}

        def f(t):
            return t + len(holder)
        return jax.jit(f)
    """}, rules=['KTPU201'])
    assert rule_ids(rep) == {'KTPU201'}


def test_ktpu202_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def g(x, cfg=[1]):
        return x
    jg = jax.jit(g, static_argnums=1)
    """}, rules=['KTPU202'])
    assert rule_ids(rep) == {'KTPU202'}
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def g(x, cfg=(1,)):
        return x
    jg = jax.jit(g, static_argnums=1)
    """}, rules=['KTPU202'])
    assert not rep.active


def test_ktpu203_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        if t.ndim == 1:
            return t[:, None]
        return t
    jf = jax.jit(f)
    """}, rules=['KTPU203'])
    assert rule_ids(rep) == {'KTPU203'}
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        return jnp.expand_dims(t, -1)
    jf = jax.jit(f)
    """}, rules=['KTPU203'])
    assert not rep.active


def test_ktpu204_positive_negative(tmp_path):
    # the retired power-of-two bucket ladder regrowing: flagged
    rep = run(tmp_path, {'a.py': """\
    from .encode import encode_batch

    def work(docs, cps, n):
        bucket = max(64, 1 << (n - 1).bit_length())
        return encode_batch(docs, cps, padded_n=bucket)
    """}, rules=['KTPU204'])
    assert rule_ids(rep) == {'KTPU204'}
    # a hard-coded row count is a shape too
    rep = run(tmp_path, {'a.py': """\
    from .encode import encode_mutate_batch

    def work(docs, program):
        return encode_mutate_batch(docs, program, padded_n=4096)
    """}, rules=['KTPU204'])
    assert rule_ids(rep) == {'KTPU204'}
    # canonical-table provenance: clean
    rep = run(tmp_path, {'a.py': """\
    from .encode import encode_batch
    from .shapes import canonical_capacity

    def work(docs, cps, n):
        bucket = canonical_capacity(n)
        return encode_batch(docs, cps, padded_n=bucket)
    """}, rules=['KTPU204'])
    assert not rep.active
    # unpadded (padded_n absent / 0) encodes are not shape decisions
    rep = run(tmp_path, {'a.py': """\
    from .encode import encode_batch

    def work(docs, cps):
        return encode_batch(docs, cps, padded_n=0)
    """}, rules=['KTPU204'])
    assert not rep.active


def test_ktpu205_positive_negative(tmp_path):
    # per-row context dicts in the encode entry itself: flagged
    rep = run(tmp_path, {'a.py': """\
    def encode_batch(docs, cps):
        bases = [{'request': {'object': d}} for d in docs]
        return bases
    """}, rules=['KTPU205'])
    assert rule_ids(rep) == {'KTPU205'}
    # one-level callee on the hot path: flagged (dict() and deepcopy
    # and json.dumps all count)
    rep = run(tmp_path, {'a.py': """\
    import copy
    import json

    def _ctx_rows(docs):
        out = []
        for d in docs:
            out.append(copy.deepcopy(d))
            out.append(json.dumps(d))
        return out

    def encode_mutate_batch(docs, program, padded_n=0):
        return _ctx_rows(docs)
    """}, rules=['KTPU205'])
    assert rule_ids(rep) == {'KTPU205'}
    assert len(rep.active) == 2
    # allocation hoisted out of the loop: clean
    rep = run(tmp_path, {'a.py': """\
    def encode_batch(docs, cps):
        shared = {'request': {'object': None}}
        out = []
        for d in docs:
            shared['request']['object'] = d
            out.append(len(shared))
        return out
    """}, rules=['KTPU205'])
    assert not rep.active
    # dict-in-loop in a function NOT reachable from an encode entry
    rep = run(tmp_path, {'a.py': """\
    def encode_batch(docs, cps):
        return len(docs)

    def unrelated(docs):
        return [{'k': d} for d in docs]
    """}, rules=['KTPU205'])
    assert not rep.active
    # two-level call chains are out of scope (one-level resolution,
    # like KTPU204)
    rep = run(tmp_path, {'a.py': """\
    def _deep(docs):
        return [{'k': d} for d in docs]

    def _mid(docs):
        return _deep(docs)

    def encode_batch(docs, cps):
        return _mid(docs)
    """}, rules=['KTPU205'])
    assert not rep.active
    # suppression with a reason works like every other rule
    rep = run(tmp_path, {'a.py': """\
    def encode_batch(docs, cps):
        # ktpu: noqa[KTPU205] -- test fixture: deliberate per-row dict
        return [{'request': {'object': d}} for d in docs]
    """}, rules=['KTPU205'])
    assert not rep.active
    assert len(rep.suppressed) == 1


# -- KTPU3xx: fallback taxonomy ----------------------------------------------

def test_ktpu301_positive_negative(tmp_path):
    rep = run(tmp_path, {'compiler/c.py': """\
    from ..compiler.ir import CompileError

    def compile_rule(rule):
        raise CompileError('nope', reason='not_a_real_reason')
    """}, rules=['KTPU301'])
    assert rule_ids(rep) == {'KTPU301'}
    rep = run(tmp_path, {'compiler/c.py': """\
    from ..compiler.ir import CompileError

    def compile_rule(rule):
        raise CompileError('nope', reason='host_closure')
    """}, rules=['KTPU301'])
    assert not rep.active


def test_ktpu302_positive_negative(tmp_path):
    rep = run(tmp_path, {'compiler/c.py': """\
    FALLBACK = object()

    def bad(doc):
        if not isinstance(doc, dict):
            return FALLBACK
        return doc
    """}, rules=['KTPU302'])
    assert rule_ids(rep) == {'KTPU302'}
    rep = run(tmp_path, {'compiler/c.py': """\
    FALLBACK = object()

    def good(doc, record_fallback):
        if not isinstance(doc, dict):
            record_fallback('mutate', 'non_dict_intermediate')
            return FALLBACK
        return doc
    """}, rules=['KTPU302'])
    assert not rep.active


def test_ktpu302_scoped_to_compiler(tmp_path):
    rep = run(tmp_path, {'engine/c.py': """\
    FALLBACK = object()

    def bad(doc):
        return FALLBACK
    """}, rules=['KTPU302'])
    assert not rep.active


def test_ktpu302_covers_device_mutate_package(tmp_path):
    """The device-side mutate package shares the FALLBACK discipline;
    engine/mutate/ (the host oracle) stays out of scope."""
    pos = tmp_path / 'pos'
    pos.mkdir()
    rep = run(pos, {'mutate/m.py': """\
    FALLBACK = object()

    def bad(doc):
        return FALLBACK
    """}, rules=['KTPU302'])
    assert rule_ids(rep) == {'KTPU302'}
    neg = tmp_path / 'neg'
    neg.mkdir()
    rep = run(neg, {'engine/mutate/m.py': """\
    FALLBACK = object()

    def bad(doc):
        return FALLBACK
    """}, rules=['KTPU302'])
    assert not rep.active


def test_ktpu303_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': 'X = 1\n'}, rules=['KTPU303'])
    # no reference site anywhere → every taxonomy reason is dead
    assert rule_ids(rep) == {'KTPU303'}
    assert len(rep.active) == len(REASONS)
    refs = ''.join(
        f"    raise CompileError('x', reason='{slug}')\n"
        for slug in sorted(REASONS))
    rep = run(tmp_path, {'a.py': 'def f():\n' + refs},
              rules=['KTPU303'])
    assert not rep.active


def test_ktpu304_positive_negative(tmp_path):
    # a serving-path handler that swallows Exception without shedding
    # or re-raising hides a degradation from every ledger
    rep = run(tmp_path, {'serving/a.py': """\
    def f():
        try:
            g()
        except Exception:
            return None
    """}, rules=['KTPU304'])
    assert rule_ids(rep) == {'KTPU304'}
    # recording a shed reason, re-raising, or narrowing the class —
    # and any handler OUTSIDE serving/ or pipeline.py — are all fine
    rep = run(tmp_path, {'serving/a.py': """\
    def f(ledger):
        try:
            g()
        except Exception:
            ledger.record_shed('scan_error')
        try:
            g()
        except Exception:
            raise
        try:
            g()
        except ValueError:
            return None
    """, 'elsewhere/a.py': """\
    def f():
        try:
            g()
        except Exception:
            return None
    """}, rules=['KTPU304'])
    assert not rep.active
    # pipeline.py is in scope wherever it lives
    rep = run(tmp_path, {'compiler/pipeline.py': """\
    def f():
        try:
            g()
        except BaseException:
            pass
    """}, rules=['KTPU304'])
    assert rule_ids(rep) == {'KTPU304'}


# -- KTPU4xx: env-knob registry ----------------------------------------------

def test_ktpu401_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    import os
    V = os.environ.get('KTPU_NOT_A_KNOB', '1')
    """}, rules=['KTPU401'])
    assert rule_ids(rep) == {'KTPU401'}
    rep = run(tmp_path, {'a.py': """\
    import os
    V = os.environ.get('KTPU_WARM', '1')
    W = __import__('os').environ.get('KTPU_SCAN_CHUNK', '16384')
    """}, rules=['KTPU401'])
    assert not rep.active


def test_ktpu402_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': 'X = 1\n'}, rules=['KTPU402'])
    assert rule_ids(rep) == {'KTPU402'}
    assert len(rep.active) == len(KNOBS)
    reads = 'import os\n' + ''.join(
        f"V{i} = os.environ.get('{name}')\n"
        for i, name in enumerate(sorted(KNOBS)))
    rep = run(tmp_path, {'a.py': reads}, rules=['KTPU402'])
    assert not rep.active


# -- KTPU5xx: metric catalog -------------------------------------------------

def test_ktpu501_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    def emit(reg):
        reg.inc('kyverno_tpu_not_in_catalog_total')
    """}, rules=['KTPU501'])
    assert rule_ids(rep) == {'KTPU501'}
    rep = run(tmp_path, {'a.py': """\
    def emit(reg):
        reg.inc('kyverno_tpu_host_fallback_total')
    """}, rules=['KTPU501'])
    assert not rep.active


def test_ktpu502_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, name):
        reg.inc(name)
    """}, rules=['KTPU502'])
    assert rule_ids(rep) == {'KTPU502'}
    rep = run(tmp_path, {'a.py': """\
    METRIC = 'kyverno_tpu_host_fallback_total'

    def emit(reg):
        reg.inc(METRIC)
    """}, rules=['KTPU502'])
    assert not rep.active


def test_ktpu503_positive_negative(tmp_path):
    from kyverno_tpu.analysis.catalog_pass import DEAD_METRIC_ALLOWLIST
    rep = run(tmp_path, {'a.py': 'X = 1\n'}, rules=['KTPU503'])
    assert rule_ids(rep) == {'KTPU503'}
    # a write site for every non-allowlisted metric is the clean state
    # (an allowlisted metric with a write site is a *stale* allowlist
    # entry — covered below)
    writes = 'def emit(reg):\n' + ''.join(
        f"    reg.inc('{name}')\n" for name in sorted(METRICS)
        if name not in DEAD_METRIC_ALLOWLIST)
    rep = run(tmp_path, {'a.py': writes}, rules=['KTPU503'])
    assert not rep.active


def test_ktpu503_stale_allowlist_entry(tmp_path):
    """An allowlist entry whose metric gained a write site is itself a
    finding — the allowlist stays minimal by construction, and newly
    landed subsystems can't hide behind it."""
    from kyverno_tpu.analysis.catalog_pass import DEAD_METRIC_ALLOWLIST
    allowlisted = sorted(DEAD_METRIC_ALLOWLIST)[0]
    writes = 'def emit(reg):\n' + ''.join(
        f"    reg.inc('{name}')\n" for name in sorted(METRICS))
    rep = run(tmp_path, {'a.py': writes}, rules=['KTPU503'])
    assert rule_ids(rep) == {'KTPU503'}
    assert any(allowlisted in f.message and 'stale' in f.message
               for f in rep.active)


def test_ktpu506_ms_into_seconds_metric(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, elapsed_ms):
        reg.observe('kyverno_tpu_scan_duration_seconds', elapsed_ms)
    """}, rules=['KTPU506'])
    assert rule_ids(rep) == {'KTPU506'}
    assert any('elapsed_ms' in f.message for f in rep.active)
    # a /1000 conversion anywhere in the expression is the fix
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, elapsed_ms):
        reg.observe('kyverno_tpu_scan_duration_seconds',
                    elapsed_ms / 1000.0)
    """}, rules=['KTPU506'])
    assert not rep.active
    # ... as is * 0.001
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, elapsed_ms):
        reg.observe('kyverno_tpu_scan_duration_seconds',
                    elapsed_ms * 0.001)
    """}, rules=['KTPU506'])
    assert not rep.active


def test_ktpu506_one_level_binding_resolution(tmp_path):
    # the ms value hides behind one local assignment (KTPU204 depth)
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, lat_ms):
        value = lat_ms
        reg.observe('kyverno_tpu_scan_duration_seconds', value)
    """}, rules=['KTPU506'])
    assert rule_ids(rep) == {'KTPU506'}
    # the binding carries the conversion: clean
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, lat_ms):
        value = lat_ms / 1000
        reg.observe('kyverno_tpu_scan_duration_seconds', value)
    """}, rules=['KTPU506'])
    assert not rep.active
    # a metric name flowing through a module constant still resolves
    rep = run(tmp_path, {'a.py': """\
    METRIC = 'kyverno_tpu_scan_duration_seconds'

    def emit(reg, lat_ms):
        reg.observe(METRIC, lat_ms)
    """}, rules=['KTPU506'])
    assert rule_ids(rep) == {'KTPU506'}


def test_ktpu506_len_of_str_into_bytes_metric(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    def emit(reg):
        body = 'x'.join(['a', 'b'])
        reg.inc('kyverno_tpu_response_bytes_total', len(body))
    """}, rules=['KTPU506'])
    assert rule_ids(rep) == {'KTPU506'}
    assert any('characters' in f.message for f in rep.active)
    # len of the encoded payload measures the wire size: clean
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, body):
        reg.inc('kyverno_tpu_response_bytes_total',
                len(body.encode()))
    """}, rules=['KTPU506'])
    assert not rep.active
    # an unresolvable bare name is not assumed to be a str
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, payload):
        reg.inc('kyverno_tpu_response_bytes_total', len(payload))
    """}, rules=['KTPU506'])
    assert not rep.active
    # json.dumps gives a str, pickle.dumps gives bytes
    for module, flagged in (('json', True), ('pickle', False)):
        rep = run(tmp_path, {'a.py': f"""\
        import {module}

        def emit(reg, answer):
            reg.inc('kyverno_tpu_response_bytes_total',
                    len({module}.dumps(answer)))
        """}, rules=['KTPU506'])
        assert bool(rep.active) == flagged, module


def test_ktpu506_ignores_unitless_metrics_and_bucket_args(tmp_path):
    # no unit suffix — nothing to check
    rep = run(tmp_path, {'a.py': """\
    def emit(reg, lat_ms):
        reg.set_gauge('kyverno_tpu_admission_queue_depth', lat_ms)
    """}, rules=['KTPU506'])
    assert not rep.active
    # register_histogram's second arg is buckets, not a measurement
    rep = run(tmp_path, {'a.py': """\
    def setup(reg, buckets_ms):
        reg.register_histogram(
            'kyverno_tpu_scan_duration_seconds', buckets_ms)
    """}, rules=['KTPU506'])
    assert not rep.active


# -- KTPU504/505: span catalog -----------------------------------------------

def test_ktpu504_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    def f(tracing):
        with tracing.start_span('kyverno/not/cataloged'):
            pass
    """}, rules=['KTPU504'])
    assert rule_ids(rep) == {'KTPU504'}
    rep = run(tmp_path, {'a.py': """\
    def f(tracing):
        with tracing.start_span('kyverno/rescan'):
            pass
    """}, rules=['KTPU504'])
    assert not rep.active


def test_ktpu504_dynamic_and_stage_sites(tmp_path):
    # a route-templated f-string name is checked by literal prefix
    rep = run(tmp_path, {'a.py': """\
    def f(tracing, path):
        with tracing.start_span(f'webhooks{path}'):
            pass
    """}, rules=['KTPU504'])
    assert not rep.active
    # device stage timers map to kyverno/device/<stage>
    rep = run(tmp_path, {'a.py': """\
    def f(devtel):
        with devtel.stage('encode'):
            pass
    """}, rules=['KTPU504'])
    assert not rep.active
    rep = run(tmp_path, {'a.py': """\
    def f(devtel):
        with devtel.stage('not_a_stage'):
            pass
    """}, rules=['KTPU504'])
    assert rule_ids(rep) == {'KTPU504'}
    # a name flowing through a variable is uncheckable
    rep = run(tmp_path, {'a.py': """\
    def f(tracing, name):
        with tracing.start_span(name):
            pass
    """}, rules=['KTPU504'])
    assert rule_ids(rep) == {'KTPU504'}


def test_ktpu505_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': 'X = 1\n'}, rules=['KTPU505'])
    assert rule_ids(rep) == {'KTPU505'}
    # one dynamic site per prefix family marks the whole catalog used
    rep = run(tmp_path, {'a.py': """\
    def f(tracing, x):
        with tracing.start_span(f'kyverno/{x}'):
            pass
        with tracing.start_span(f'webhooks{x}'):
            pass
    """}, rules=['KTPU505'])
    assert not rep.active


def _stage_registry_uses():
    """One ``stage('<s>')`` site per registered pipeline stage — the
    clean-state floor for KTPU507 fixtures (mirrors how the KTPU503
    negative writes every cataloged metric)."""
    from kyverno_tpu.analysis.catalog_pass import load_stage_registry
    return 'def _uses(devtel):\n' + ''.join(
        f"    devtel.stage('{name}')\n"
        for name in sorted(load_stage_registry()))


def test_ktpu507_unregistered_stage_in_compiler(tmp_path):
    rep = run(tmp_path, {
        'compiler/c.py': """\
        def f(devtel):
            with devtel.stage('warp'):
                pass
        """,
        'u.py': _stage_registry_uses(),
    }, rules=['KTPU507'])
    assert rule_ids(rep) == {'KTPU507'}
    assert any("'warp'" in f.message for f in rep.active)
    # the same label registered (plus a use per registry entry) is clean
    rep = run(tmp_path, {'compiler/c.py': _stage_registry_uses()},
              rules=['KTPU507'])
    assert not rep.active


def test_ktpu507_outside_compiler_is_not_flagged(tmp_path):
    # engine-side stage timers are not pipeline stages — the
    # unregistered check is scoped to compiler/; the registry floor
    # still applies tree-wide
    rep = run(tmp_path, {
        'engine/e.py': """\
        def f(devtel):
            with devtel.stage('warp'):
                pass
        """,
        'u.py': _stage_registry_uses(),
    }, rules=['KTPU507'])
    assert not rep.active


def test_ktpu507_chunk_pipeline_stage_list(tmp_path):
    rep = run(tmp_path, {
        'compiler/c.py': """\
        def build(fn):
            return ChunkPipeline([('warp', fn), ('encode', fn)])
        """,
        'u.py': _stage_registry_uses(),
    }, rules=['KTPU507'])
    assert rule_ids(rep) == {'KTPU507'}
    assert any("'warp'" in f.message for f in rep.active)


def test_ktpu507_dead_stage_entries(tmp_path):
    # a tree with no stage sites at all: every registry entry is dead
    rep = run(tmp_path, {'a.py': 'X = 1\n'}, rules=['KTPU507'])
    assert rule_ids(rep) == {'KTPU507'}
    from kyverno_tpu.analysis.catalog_pass import load_stage_registry
    assert len(rep.active) == len(load_stage_registry())


# -- KTPU508: partition key hygiene ------------------------------------------

def test_ktpu508_direct_whole_set_fingerprint(tmp_path):
    rep = run(tmp_path, {'ops/e.py': """\
    def build(cps, aot, packed):
        key = aot.executable_cache_key(
            policy_set_fingerprint(cps.policies), packed)
        return key
    """}, rules=['KTPU508'])
    assert rule_ids(rep) == {'KTPU508'}


def test_ktpu508_resolves_binding_in_enclosing_scope(tmp_path):
    # the ops/eval.py shape: the fingerprint binds in the builder
    # function, the cache-key call sits in a nested closure
    rep = run(tmp_path, {'ops/e.py': """\
    def build_evaluator(cps, aot):
        fingerprint = policy_set_fingerprint(cps.policies)

        def _compiled_for(packed):
            return aot.executable_cache_key(fingerprint, packed)
        return _compiled_for
    """}, rules=['KTPU508'])
    assert rule_ids(rep) == {'KTPU508'}


def test_ktpu508_compile_fingerprint_is_clean(tmp_path):
    rep = run(tmp_path, {'ops/e.py': """\
    def build_evaluator(cps, aot):
        from ..partition.keys import compile_fingerprint
        fingerprint = compile_fingerprint(cps)

        def _compiled_for(packed):
            return aot.executable_cache_key(fingerprint, packed)
        return _compiled_for
    """}, rules=['KTPU508'])
    assert not rep.active


def test_ktpu508_partition_package_is_exempt(tmp_path):
    # partition/ IS the sanctioned fingerprint authority: the
    # degenerate whole-set spelling inside it is the oracle path
    rep = run(tmp_path, {'partition/keys.py': """\
    def compile_fingerprint(cps, aot, packed):
        return aot.executable_cache_key(
            policy_set_fingerprint(cps.policies), packed)
    """}, rules=['KTPU508'])
    assert not rep.active


def test_ktpu508_parameter_fingerprint_undecidable(tmp_path):
    # a fingerprint arriving as a parameter resolves nowhere — the
    # one-level pass stays silent instead of guessing
    rep = run(tmp_path, {'ops/e.py': """\
    def lookup(aot, fingerprint, packed):
        return aot.executable_cache_key(fingerprint, packed)
    """}, rules=['KTPU508'])
    assert not rep.active


# every catalog fleet_scope'd metric written from parallel/ with its
# identity label — the clean state for the KTPU509 fixtures (a partial
# set would trip the dead-scope check for the missing metrics)
KTPU509_CLEAN = """\
def emit(reg, wall):
    reg.observe('kyverno_tpu_mesh_step_duration_seconds', wall,
                shard='0')
    reg.set_gauge('kyverno_tpu_mesh_shard_skew_ratio', 1.0,
                  mesh='data8')
    reg.inc('kyverno_tpu_mesh_collective_seconds_total', wall,
            mesh='data8')
    reg.inc('kyverno_tpu_mesh_padding_rows_total', 1.0, mesh='data8')
"""


def test_ktpu509_clean_mesh_writes(tmp_path):
    rep = run(tmp_path, {'parallel/mesh.py': KTPU509_CLEAN},
              rules=['KTPU509'])
    assert not rep.active


def test_ktpu509_parallel_write_without_scope(tmp_path):
    # an unscoped metric written from parallel/ loses per-process
    # attribution in the federation merge
    rep = run(tmp_path, {'parallel/mesh.py': KTPU509_CLEAN + """\

def bad(reg):
    reg.inc('kyverno_tpu_host_fallback_total')
"""}, rules=['KTPU509'])
    assert rule_ids(rep) == {'KTPU509'}
    assert any('no fleet_scope' in f.message for f in rep.active)


def test_ktpu509_scoped_write_missing_identity_label(tmp_path):
    missing = KTPU509_CLEAN.replace(
        "reg.inc('kyverno_tpu_mesh_collective_seconds_total', wall,\n"
        "            mesh='data8')",
        "reg.inc('kyverno_tpu_mesh_collective_seconds_total', wall)")
    rep = run(tmp_path, {'parallel/mesh.py': missing},
              rules=['KTPU509'])
    assert rule_ids(rep) == {'KTPU509'}
    assert any('mesh=' in f.message and 'collective' in f.message
               for f in rep.active)


def test_ktpu509_scoped_write_outside_parallel_still_needs_label(
        tmp_path):
    rep = run(tmp_path, {
        'parallel/mesh.py': KTPU509_CLEAN,
        'observability/x.py': """\
def leak(reg):
    reg.set_gauge('kyverno_tpu_mesh_shard_skew_ratio', 1.0)
"""}, rules=['KTPU509'])
    assert rule_ids(rep) == {'KTPU509'}


def test_ktpu509_label_splat_is_uncheckable_not_flagged(tmp_path):
    # **labels keys are unknowable statically — the pass must not guess
    splat = KTPU509_CLEAN + """\

def forward(reg, wall, labels):
    reg.inc('kyverno_tpu_mesh_collective_seconds_total', wall,
            **labels)
"""
    rep = run(tmp_path, {'parallel/mesh.py': splat}, rules=['KTPU509'])
    assert not rep.active


def test_ktpu509_dead_scope(tmp_path):
    # a declared fleet_scope with no parallel/ write site: the scope
    # promises identity labels nothing emits
    rep = run(tmp_path, {'a.py': KTPU509_CLEAN}, rules=['KTPU509'])
    assert rule_ids(rep) == {'KTPU509'}
    assert all('no parallel/ write site' in f.message
               for f in rep.active)
    assert len(rep.active) == 4  # one per scoped catalog metric


def test_ktpu509_module_constant_resolution(tmp_path):
    # names resolve through UPPER_CASE constants, including the
    # fleet.MESH_* attribute spelling used by parallel/mesh.py
    rep = run(tmp_path, {'parallel/mesh.py': """\
MESH_STEP_DURATION = 'kyverno_tpu_mesh_step_duration_seconds'
MESH_SHARD_SKEW = 'kyverno_tpu_mesh_shard_skew_ratio'
MESH_COLLECTIVE_SECONDS = 'kyverno_tpu_mesh_collective_seconds_total'
MESH_PADDING_ROWS = 'kyverno_tpu_mesh_padding_rows_total'


def emit(reg, fleet, wall):
    reg.observe(fleet.MESH_STEP_DURATION, wall, shard='1')
    reg.set_gauge(MESH_SHARD_SKEW, 1.0, mesh='data8')
    reg.inc(MESH_COLLECTIVE_SECONDS, wall, mesh='data8')
    reg.inc(MESH_PADDING_ROWS, 2.0, mesh='data8')
"""}, rules=['KTPU509'])
    assert not rep.active


# -- KTPU00x: suppression hygiene (meta rules) -------------------------------

def test_ktpu001_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    X = 1  # ktpu: noqa[KTPU101]
    """}, rules=['KTPU001'])
    assert rule_ids(rep) == {'KTPU001'}
    rep = run(tmp_path, {'a.py': """\
    X = 1  # ktpu: noqa[KTPU101] -- justified example
    """}, rules=['KTPU001'])
    assert not rep.active


def test_ktpu002_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    X = 1  # ktpu: noqa[KTPU101] -- suppresses nothing
    """}, rules=['KTPU002'])
    assert rule_ids(rep) == {'KTPU002'}
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        return t.item()  # ktpu: noqa[KTPU101] -- fixture host sync
    jf = jax.jit(f)
    """}, rules=['KTPU101', 'KTPU002'])
    assert not rep.active
    assert [f.rule_id for f in rep.suppressed] == ['KTPU101']


# -- suppression semantics ---------------------------------------------------

def test_noqa_suppresses_only_listed_rule(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        return t.item()  # ktpu: noqa[KTPU203] -- wrong rule id
    jf = jax.jit(f)
    """}, rules=['KTPU101'])
    assert rule_ids(rep) == {'KTPU101'}


def test_noqa_comment_block_above(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t):
        # ktpu: noqa[KTPU101] -- wrapped reason text continues on
        # the next comment line without breaking the suppression
        return t.item()
    jf = jax.jit(f)
    """}, rules=['KTPU101'])
    assert not rep.active
    assert len(rep.suppressed) == 1


def test_noqa_in_docstring_is_inert(tmp_path):
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + '''\
    def f(t):
        """Docs may quote `# ktpu: noqa[KTPU101] -- like so`."""
        return t.item()
    jf = jax.jit(f)
    '''}, rules=['KTPU101'])
    assert rule_ids(rep) == {'KTPU101'}


# -- baseline round-trip -----------------------------------------------------

BAD_SRC = """\
import jax
import jax.numpy as jnp

def f(t):
    return t.item()
jf = jax.jit(f)
"""

FIXED_SRC = """\
import jax
import jax.numpy as jnp

def f(t):
    return jnp.sum(t)
jf = jax.jit(f)
"""

DRIFTED_SRC = """\
import jax
import jax.numpy as jnp

PAD = 1

def f(t):
    return t.item()
jf = jax.jit(f)
"""


def test_baseline_round_trip(tmp_path):
    bl = str(tmp_path / 'baseline.json')
    rep = run(tmp_path, {'a.py': BAD_SRC}, rules=['KTPU101'])
    assert len(rep.active) == 1
    write_baseline(bl, rep.active, reason='grandfathered in the test')
    rep2 = run(tmp_path, {'a.py': BAD_SRC}, rules=['KTPU101'],
               baseline=bl)
    assert not rep2.active
    assert len(rep2.baselined) == 1
    assert not rep2.stale_baseline
    assert not rep2.errors


def test_baseline_stale_entry_detected(tmp_path):
    bl = str(tmp_path / 'baseline.json')
    rep = run(tmp_path, {'a.py': BAD_SRC}, rules=['KTPU101'])
    write_baseline(bl, rep.active, reason='grandfathered in the test')
    rep2 = run(tmp_path, {'a.py': FIXED_SRC}, rules=['KTPU101'],
               baseline=bl)
    assert not rep2.active
    assert len(rep2.stale_baseline) == 1


def test_baseline_requires_justification(tmp_path):
    bl = tmp_path / 'baseline.json'
    bl.write_text(json.dumps({'entries': [
        {'rule': 'KTPU101', 'path': 'a.py', 'match': 'return t.item()',
         'reason': ''}]}))
    rep = run(tmp_path, {'a.py': BAD_SRC}, rules=['KTPU101'],
              baseline=str(bl))
    assert rep.errors  # unjustified entry is an error even if it matches


def test_baseline_survives_line_drift(tmp_path):
    bl = str(tmp_path / 'baseline.json')
    rep = run(tmp_path, {'a.py': BAD_SRC}, rules=['KTPU101'])
    write_baseline(bl, rep.active, reason='grandfathered in the test')
    rep2 = run(tmp_path, {'a.py': DRIFTED_SRC}, rules=['KTPU101'],
               baseline=bl)
    assert not rep2.active
    assert len(rep2.baselined) == 1


# -- registry hygiene --------------------------------------------------------

def test_rule_registry_complete():
    expected = {'KTPU001', 'KTPU002', 'KTPU101', 'KTPU102', 'KTPU103',
                'KTPU201', 'KTPU202', 'KTPU203', 'KTPU204', 'KTPU205',
                'KTPU301', 'KTPU302', 'KTPU303', 'KTPU304',
                'KTPU401', 'KTPU402',
                'KTPU501', 'KTPU502', 'KTPU503', 'KTPU504', 'KTPU505',
                'KTPU506', 'KTPU507', 'KTPU508', 'KTPU509',
                'KTPU601', 'KTPU602', 'KTPU603', 'KTPU604'}
    assert set(RULES) == expected
    for rid, rule in RULES.items():
        assert rule.summary.strip(), rid


def test_knob_table_renders_every_knob():
    from kyverno_tpu.analysis.knobs import render_knob_table
    table = render_knob_table()
    for name in KNOBS:
        assert f'`{name}`' in table


# -- v2 call graph: qualified resolution -------------------------------------

def test_callgraph_alias_import(tmp_path):
    """`import helpers as h; h.helper(t)` resolves across files — the
    finding lands in the helper's module."""
    rep = run(tmp_path, {
        'helpers.py': """\
    def helper(t):
        return t.tolist()
    """,
        'entry.py': JIT_PRELUDE + """\
    import helpers as h

    def f(t):
        return h.helper(t)
    jf = jax.jit(f)
    """}, rules=['KTPU101'])
    assert rule_ids(rep) == {'KTPU101'}
    assert {f.path for f in rep.active} == {'helpers.py'}


def test_callgraph_class_method_dispatch(tmp_path):
    """`self.m()` and assignment-typed receivers dispatch to the
    owning class's method, one level deep."""
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    class Evaluator:
        def prep(self, t):
            return t.tolist()

        def run(self, t):
            return self.prep(t)

    ev = Evaluator()

    def f(t):
        return ev.run(t)
    jf = jax.jit(f)
    """}, rules=['KTPU101'])
    assert rule_ids(rep) == {'KTPU101'}
    # per-class dispatch is authoritative: a same-name method on an
    # unrelated class must NOT be pulled into the graph
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    class A:
        def go(self, t):
            return t

    class B:
        def go(self, t):
            return t.tolist()

    a = A()

    def f(t):
        return a.go(t)
    jf = jax.jit(f)
    """}, rules=['KTPU101'])
    assert not rep.active


def test_callgraph_diamond_chain(tmp_path):
    """f -> a -> d and f -> b -> d: the shared sink is analyzed (and
    reported) exactly once."""
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def d(t):
        return t.item()

    def a(t):
        return d(t)

    def b(t):
        return d(t)

    def f(t):
        return a(t) + b(t)
    jf = jax.jit(f)
    """}, rules=['KTPU101'])
    assert len(rep.active) == 1
    assert rep.active[0].rule_id == 'KTPU101'


# -- v2 param-rooted taint ---------------------------------------------------

def test_taint_entry_param(tmp_path):
    """A non-static jit entry param is a tracer: casting it anywhere
    is a finding, and static_argnums exempts exactly that param."""
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t, n):
        return t * int(n)
    jf = jax.jit(f)
    """}, rules=['KTPU102'])
    assert rule_ids(rep) == {'KTPU102'}
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def f(t, n):
        return t * int(n)
    jf = jax.jit(f, static_argnums=(1,))
    """}, rules=['KTPU102'])
    assert not rep.active


def test_taint_depth_boundary(tmp_path):
    """Default KTPU_LINT_TAINT_DEPTH=3: a cast of a param three call
    edges below the entry fires; four edges down, taint has stopped."""
    chain = JIT_PRELUDE + """\
    def h3(x):
        return int(x)

    def h2(x):
        return h3(x)

    def h1(x):
        return h2(x)

    def f(t):
        return h1(t)
    jf = jax.jit(f)
    """
    rep = run(tmp_path, {'a.py': chain}, rules=['KTPU102'])
    assert rule_ids(rep) == {'KTPU102'}
    assert 'call chain' in rep.active[0].message
    deeper = chain.replace('def h3(x):\n        return int(x)',
                           'def h4(x):\n'
                           '        return int(x)\n\n'
                           '    def h3(x):\n'
                           '        return h4(x)')
    rep = run(tmp_path, {'a.py': deeper}, rules=['KTPU102'])
    assert not rep.active


def test_taint_depth_knob(tmp_path, monkeypatch):
    """KTPU_LINT_TAINT_DEPTH tightens the propagation bound."""
    monkeypatch.setenv('KTPU_LINT_TAINT_DEPTH', '1')
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def h2(x):
        return int(x)

    def h1(x):
        return h2(x)

    def f(t):
        return h1(t)
    jf = jax.jit(f)
    """}, rules=['KTPU102'])
    assert not rep.active  # the cast sits at depth 2, past the bound


def test_callgraph_real_world_miss(tmp_path):
    """Planted miss modeled on ops/eval.py before the tuple-freeze fix
    (PR 4): the tracer-concretizing branch lives two helpers below the
    jit entry, where the old one-level pass could not see it."""
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    def _threshold(counts):
        if counts > 0:
            return counts
        return 0

    def _score(batch):
        return _threshold(batch)

    def eval_batch(batch):
        return _score(batch)
    jf = jax.jit(eval_batch)
    """}, rules=['KTPU103'])
    assert rule_ids(rep) == {'KTPU103'}
    [f] = rep.active
    assert '_threshold' in f.message
    assert 'call chain' in f.message


def test_ktpu201_self_attr_closure(tmp_path):
    """A jitted *method* closing over a mutable `self.X` container is
    the same stale-closure hazard as a module global (the old pass
    only saw bare names)."""
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    class Model:
        def __init__(self):
            self.table = {}

        def step(self, t):
            return t + len(self.table)

    m = Model()
    jstep = jax.jit(m.step)
    """}, rules=['KTPU201'])
    assert rule_ids(rep) == {'KTPU201'}
    assert 'self.table' in rep.active[0].message
    rep = run(tmp_path, {'a.py': JIT_PRELUDE + """\
    class Model:
        def __init__(self):
            self.table = (1, 2)

        def step(self, t):
            return t + len(self.table)

    m = Model()
    jstep = jax.jit(m.step)
    """}, rules=['KTPU201'])
    assert not rep.active  # a tuple attribute cannot drift


# -- KTPU6xx: concurrency discipline -----------------------------------------

def test_ktpu601_positive_negative(tmp_path):
    pos = """\
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            threading.Thread(target=self._run).start()

        def _run(self):
            self.n = 1

        def bump(self):
            with self._lock:
                self.n = 2
    """
    rep = run(tmp_path, {'a.py': pos}, rules=['KTPU601'])
    assert rule_ids(rep) == {'KTPU601'}
    rep = run(tmp_path, {'a.py': pos.replace(
        '        def _run(self):\n            self.n = 1',
        '        def _run(self):\n'
        '            with self._lock:\n'
        '                self.n = 1')}, rules=['KTPU601'])
    assert not rep.active


def test_ktpu602_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    import threading

    def worker():
        with stage('encode'):
            pass

    def start():
        t = threading.Thread(target=worker)
        t.start()
    """}, rules=['KTPU602'])
    assert rule_ids(rep) == {'KTPU602'}
    rep = run(tmp_path, {'a.py': """\
    import threading

    def worker():
        install_capture(None)
        with stage('encode'):
            pass

    def start():
        t = threading.Thread(target=worker)
        t.start()
    """}, rules=['KTPU602'])
    assert not rep.active


def test_ktpu603_positive_negative(tmp_path):
    pos = """\
    G = 'kyverno_tpu_queue_depth'

    def loop(reg, q):
        while True:
            reg.set_gauge(G, float(len(q)))
    """
    rep = run(tmp_path, {'a.py': pos}, rules=['KTPU603'])
    assert rule_ids(rep) == {'KTPU603'}
    rep = run(tmp_path, {'a.py': pos + """\

    def setup(reg):
        reg.mark_reset_on_close(G)
    """}, rules=['KTPU603'])
    assert not rep.active


def test_ktpu604_positive_negative(tmp_path):
    rep = run(tmp_path, {'a.py': """\
    import threading

    A = threading.Lock()
    B = threading.Lock()

    def f():
        with A:
            with B:
                pass

    def g():
        with B:
            with A:
                pass
    """}, rules=['KTPU604'])
    assert rule_ids(rep) == {'KTPU604'}
    rep = run(tmp_path, {'a.py': """\
    import threading

    A = threading.Lock()
    B = threading.Lock()

    def f():
        with A:
            with B:
                pass

    def g():
        with A:
            with B:
                pass
    """}, rules=['KTPU604'])
    assert not rep.active
