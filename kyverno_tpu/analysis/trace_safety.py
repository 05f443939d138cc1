"""Trace-safety passes (KTPU1xx): host syncs inside jit regions.

A host sync inside a jitted region either crashes the trace
(``TracerArrayConversionError``) or — worse — silently forces a
device→host readback per call and caps the pipeline at PCIe
latency.  These passes flag the constructs on any function reachable
from the ``jax.jit`` / ``pjit`` sites in the tree (``ops/eval.py``,
``parallel/mesh.py``, and whatever future modules grow jit entries).

Since the v2 engine these passes are **interprocedural**: KTPU102/103
consult the param-rooted taint lattice, so a helper three call edges
below the entry that casts or branches on a value derived from a
traced *argument* is a finding at the helper's own site, with the
entry→helper call chain in the message.  Purely local evidence (a
``jnp.*`` call in the expression, a local assigned from one) still
counts exactly as before.

* **KTPU101** — explicit host-sync calls: ``.item()``, ``.tolist()``,
  ``.block_until_ready()``, ``np.asarray`` / ``np.array`` /
  ``jax.device_get`` on anything jit-reachable.
* **KTPU102** — Python scalar casts (``float`` / ``int`` / ``bool``)
  over a traced expression: one whose subtree calls into ``jnp`` /
  ``jax``, or a local assigned from such a call, or a
  **tracer-tainted parameter** (static jit args excluded).
* **KTPU103** — Python ``if`` / ``while`` control flow on a traced
  expression (``is None`` identity tests excluded — those gate
  Python-level optionality, not array values).
"""

from __future__ import annotations

import ast
from typing import Iterable, Set

from .core import Context, Finding, register
from .jitgraph import jit_graph

#: attribute calls that force a device→host transfer wherever they run
SYNC_METHODS = {'item', 'tolist', 'block_until_ready'}

#: ``module.func`` spellings that materialize a host array
SYNC_MODULE_CALLS = {
    ('np', 'asarray'), ('np', 'array'), ('numpy', 'asarray'),
    ('numpy', 'array'), ('jax', 'device_get'),
}

#: roots whose attribute-calls produce traced values
_TRACED_ROOTS = {'jnp', 'jax'}


def _attr_root(node: ast.AST):
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _traced_names(fn: ast.AST) -> Set[str]:
    """Names assigned (anywhere in ``fn``) from a ``jnp.*``/``jax.*``
    call — the local-evidence layer under the interprocedural taint."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = node.value
            if value is None or not _contains_traced_call(value, set()):
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def _contains_traced_call(expr: ast.AST, traced_names: Set[str]) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            root = _attr_root(node.func)
            if root in _TRACED_ROOTS:
                return True
        elif isinstance(node, ast.Name) and node.id in traced_names:
            return True
    return False


def _is_none_test(test: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` (possibly under ``not``)."""
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _is_none_test(test.operand)
    if isinstance(test, ast.Compare):
        return any(isinstance(op, (ast.Is, ast.IsNot))
                   for op in test.ops)
    return False


def _chain_suffix(graph, mi, fn) -> str:
    chain = graph.chain_for(mi, fn)
    return f' (call chain: {chain})' if chain else ''


@register('KTPU101', 'host-sync call (.item()/.tolist()/'
                     '.block_until_ready()/np.asarray/jax.device_get) '
                     'inside a jit-reachable function')
def _check_host_sync(ctx: Context) -> Iterable[Finding]:
    graph = jit_graph(ctx)
    for sf, mi, fn in graph.reachable_functions():
        for node in graph.scope_nodes(mi, fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr in SYNC_METHODS and not node.args:
                    yield sf.finding(
                        'KTPU101', node,
                        f'`.{f.attr}()` forces a device sync inside '
                        f'jit-reachable `{fn.name}` — keep the value '
                        f'on device or hoist to the host side'
                        f'{_chain_suffix(graph, mi, fn)}')
                    continue
                base = f.value
                if isinstance(base, ast.Name) and \
                        (base.id, f.attr) in SYNC_MODULE_CALLS:
                    yield sf.finding(
                        'KTPU101', node,
                        f'`{base.id}.{f.attr}` materializes a host '
                        f'array inside jit-reachable `{fn.name}` — '
                        f'use jnp, or move the conversion outside the '
                        f'traced region'
                        f'{_chain_suffix(graph, mi, fn)}')


@register('KTPU102', 'Python scalar cast (float/int/bool) over a '
                     'traced or tracer-tainted expression inside a '
                     'jit-reachable function')
def _check_scalar_cast(ctx: Context) -> Iterable[Finding]:
    graph = jit_graph(ctx)
    for sf, mi, fn in graph.reachable_functions():
        traced = _traced_names(fn)
        tainted = graph.tainted_names_for(mi, fn)
        for node in graph.scope_nodes(mi, fn):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Name) and
                    node.func.id in ('float', 'int', 'bool') and
                    len(node.args) == 1):
                continue
            arg = node.args[0]
            local_hit = _contains_traced_call(arg, traced)
            taint_hit = bool(tainted) and \
                graph.expr_tainted(mi, fn, arg, tainted)
            if local_hit or taint_hit:
                why = 'a traced expression' if local_hit else \
                    'a tracer-tainted argument'
                yield sf.finding(
                    'KTPU102',
                    node,
                    f'`{node.func.id}(...)` over {why} '
                    f'in jit-reachable `{fn.name}` leaks the tracer '
                    f'to the host — keep it as a jnp array'
                    f'{_chain_suffix(graph, mi, fn)}')


@register('KTPU103', 'Python if/while branching on a traced or '
                     'tracer-tainted expression inside a '
                     'jit-reachable function')
def _check_tracer_branch(ctx: Context) -> Iterable[Finding]:
    graph = jit_graph(ctx)
    for sf, mi, fn in graph.reachable_functions():
        traced = _traced_names(fn)
        tainted = graph.tainted_names_for(mi, fn)
        for node in graph.scope_nodes(mi, fn):
            if not isinstance(node, (ast.If, ast.While)) or \
                    _is_none_test(node.test):
                continue
            local_hit = _contains_traced_call(node.test, traced)
            taint_hit = bool(tainted) and \
                graph.expr_tainted(mi, fn, node.test, tainted)
            if local_hit or taint_hit:
                kw = 'if' if isinstance(node, ast.If) else 'while'
                yield sf.finding(
                    'KTPU103', node,
                    f'Python `{kw}` on a traced expression in '
                    f'jit-reachable `{fn.name}` — the branch '
                    f'concretizes the tracer; use jnp.where / lax.cond'
                    f'{_chain_suffix(graph, mi, fn)}')
