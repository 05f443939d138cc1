"""What every driver and reader of the benchmark shares: finding the data
files by name, the instruments copied from ``chip_smoke.py`` (where they
passed on the chip in PR 22), and a little arithmetic.

Nothing here names a cell, a configuration or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import logging
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f'[bench +{time.monotonic() - _T0:6.1f}s] {msg}', flush=True)


class MissingFile(Exception):
    """A name in BENCHMARK.json or a data file has no file behind it."""


def data_path(kind: str, name: str, ext: str = '.json') -> str:
    return os.path.join(BENCH_DIR, kind, name + ext)


def load_data(kind: str, name: str) -> dict:
    """``benchmarks/<kind>/<name>.json``; a missing file is an error that
    names it."""
    path = data_path(kind, name)
    if not os.path.isfile(path):
        raise MissingFile(f'{name!r} has no file '
                          f'{os.path.relpath(path, ROOT)}')
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module of its own."""
    path = data_path(kind, name, '.py')
    if not os.path.isfile(path):
        raise MissingFile(f'{name!r} has no file '
                          f'{os.path.relpath(path, ROOT)}')
    mod_name = f'bench_{kind}_{name}'
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it, nested objects key by key."""
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = overlay(out[key], value)
        else:
            out[key] = value
    return out


def load_policies(pack_names: list) -> list:
    """The policies of ``benchmarks/packs/<name>.yaml``, in order."""
    from kyverno_tpu.api.policy import load_policies_from_yaml
    policies = []
    for name in pack_names:
        path = data_path('packs', name, '.yaml')
        if not os.path.isfile(path):
            raise MissingFile(f'{name!r} has no file '
                              f'{os.path.relpath(path, ROOT)}')
        with open(path) as f:
            policies += load_policies_from_yaml(f.read())
    return policies


def replicate_enforce(policies: list, target: int) -> list:
    """``policies`` copied round after round under new names, every copy in
    Enforce mode, until there are ``target`` of them (``bench.py``'s)."""
    import copy
    from kyverno_tpu.api.policy import Policy
    if not policies:
        raise ValueError('empty policy pack: nothing to replicate')
    replicated, i = [], 0
    while len(replicated) < target:
        for p in policies:
            doc = copy.deepcopy(p.raw)
            doc['metadata']['name'] = f"{doc['metadata']['name']}-r{i}"
            doc.setdefault('spec', {})['validationFailureAction'] = 'Enforce'
            replicated.append(Policy(doc))
            if len(replicated) >= target:
                break
        i += 1
    return replicated


# -- arithmetic ---------------------------------------------------------------

def quantile(values: list, q: float) -> float:
    """The ``q`` quantile of ``values`` by linear interpolation between the
    two nearest ranks (numpy's default)."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError('no sample')
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def delta(before, after):
    """``after - before`` through nested dicts of numbers; what only
    ``after`` has is kept as it is."""
    if isinstance(after, dict):
        before = before if isinstance(before, dict) else {}
        return {k: delta(before.get(k), v) for k, v in after.items()}
    if isinstance(after, bool) or not isinstance(after, (int, float)):
        return after
    if isinstance(before, (int, float)) and not isinstance(before, bool):
        return after - before
    return after


def yields(traffic: dict, values: dict) -> dict:
    """The traffic file names the end-to-end metric that each quantity of
    its loop is reported under: ``{"<metric>": "<quantity>"}``."""
    return {name: values[quantity]
            for name, quantity in traffic['yields'].items()}


def lookup(tree: dict, dotted: str):
    """``tree['a']['b']`` for ``'a.b'``; None where a step is missing."""
    node = tree
    for key in dotted.split('.'):
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return node


# -- instruments (chip_smoke.py's) --------------------------------------------

class CacheEvents:
    """JAX's own persistent-compilation-cache events, counted, each with the
    time it came at, so that compiles inside a window can be told."""

    PREFIX = '/jax/compilation_cache/'
    REQUEST = 'compile_requests_use_cache'
    HIT = 'cache_hits'

    def __init__(self):
        import jax.monitoring
        self.times = {}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event.startswith(self.PREFIX):
            self.times.setdefault(event[len(self.PREFIX):], []).append(
                time.monotonic())

    def count(self, key: str, since: float = float('-inf'),
              until: float = float('inf')) -> int:
        return sum(since <= t <= until for t in self.times.get(key, ()))


class FailureLog(logging.Handler):
    """Keeps the webhook's ERROR log lines ('device path failure')."""

    def __init__(self):
        super().__init__(logging.ERROR)
        self.lines = []
        logging.getLogger('kyverno.webhooks').addHandler(self)

    def emit(self, record):
        self.lines.append(record.getMessage())

    def count(self, needle: str) -> int:
        return sum(needle in line for line in self.lines)


def executables(fingerprint: str) -> list:
    """The executable ledger's records for one policy set, as dicts."""
    from kyverno_tpu.observability import executables as exectel
    return [{'capacity': r.capacity, 'source': r.source,
             'build_s': r.build_s, 'dispatches': r.dispatches,
             'enqueue_s': r.device_s, 'platform': r.platform,
             'bytes_accessed': r.bytes_accessed}
            for r in exectel.ledger().records()
            if r.fingerprint == fingerprint]


def executables_problems(records: list, platform: str, what: str) -> list:
    """Every record has to keep its outputs on ``platform``."""
    problems = [] if records else [f'{what}: no executable was registered']
    for r in records:
        say(f'{what}: executable capacity={r["capacity"]} '
            f'source={r["source"]} build_s={r["build_s"]:.2f} '
            f'dispatches={r["dispatches"]} enqueue_s={r["enqueue_s"]:.3f} '
            f'outputs_on={r["platform"] or "?"}')
        if r['dispatches'] and r['platform'] != platform:
            problems.append(
                f'{what}: outputs of the capacity-{r["capacity"]} executable '
                f'live on {r["platform"]!r}, not on {platform!r}')
    return problems


def descendants() -> list:
    """Every live process below this one, as ``(pid, command line)``."""
    parent_of, cmd = {}, {}
    for pid in filter(str.isdigit, os.listdir('/proc')):
        try:
            with open(f'/proc/{pid}/stat') as f:
                state, ppid = f.read().rsplit(')', 1)[1].split()[:2]
            with open(f'/proc/{pid}/cmdline') as f:
                cmd[int(pid)] = f.read().replace('\0', ' ').strip()
        except OSError:
            continue  # gone between the listing and the read
        if state != 'Z':
            parent_of[int(pid)] = int(ppid)
    below = {os.getpid()}
    while True:
        more = {p for p, pp in parent_of.items() if pp in below} - below
        if not more:
            return sorted((p, cmd[p]) for p in below - {os.getpid()})
        below |= more


def program_telemetry():
    """Turn on the program's own counters (stage histogram, coverage and
    executable ledgers) on a registry of this run; returns the registry."""
    from kyverno_tpu.observability import coverage
    from kyverno_tpu.observability import device as devtel
    from kyverno_tpu.observability import executables as exectel
    from kyverno_tpu.observability.metrics import (MetricsRegistry,
                                                   set_global_registry)
    registry = MetricsRegistry()
    set_global_registry(registry)
    devtel.configure(registry)
    coverage.configure(registry)
    exectel.configure(registry)
    return registry
