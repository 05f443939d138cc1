"""The host-chain oracle of a device mutate row.

``check_mutate_row`` is what ``chip_smoke.py``'s device mutate phase and
the device mutate tests hold a ``MutateScanner`` row to.  The packs and
generators the tests run live under ``benchmarks/packs/`` and
``benchmarks/generators/`` (the config-5 pack of ``BASELINE.json``
``configs[4]`` and its resource dump are ``config5.yaml`` and
``config5_dump.py`` there); this module imports nothing from there (the
package never reaches up).
"""

from __future__ import annotations

import json


def check_mutate_row(engine, policies, pod: dict, row, what: str) -> None:
    """Hold one MutateScanner row ``(steps, patched)`` to the host
    engine's cumulative mutate chain over ``pod``: the patched document
    and every rule response, byte for byte."""
    from kyverno_tpu.engine.api import PolicyContext
    pctx = PolicyContext(None, new_resource=json.loads(json.dumps(pod)))
    host = []
    for pol in policies:
        ctx = pctx.copy()
        ctx.policy = pol
        er = engine.mutate(ctx)
        host.append((pol.name, er))
        if not er.is_successful():
            break
        pctx = pctx.copy()
        pctx.new_resource = er.patched_resource or pctx.new_resource
        pctx.json_context.add_resource(pctx.new_resource)
    steps, patched = row
    if json.dumps(patched, sort_keys=True) != \
            json.dumps(pctx.new_resource, sort_keys=True):
        raise AssertionError(f'{what}: patched doc diverged from the '
                             f'host oracle')
    if len(steps) != len(host):
        raise AssertionError(f'{what}: {len(steps)} policy steps, the '
                             f'host chain has {len(host)}')
    for (hname, her), (_dpol, der) in zip(host, steps):
        hcells = [(r.name, str(r.status), r.message, r.patches)
                  for r in her.policy_response.rules]
        dcells = [(r.name, str(r.status), r.message, r.patches)
                  for r in der.policy_response.rules]
        if hcells != dcells:
            raise AssertionError(f'{what} policy {hname}: device cells '
                                 f'diverged from the host oracle')

