"""AOT executable codec: serialize compiled evaluators to/from the
persistent store.

The persistent XLA compilation cache only skips the backend compile; a
fresh process still pays ~10s re-tracing the evaluator (the jaxpr for a
full policy pack lowers to ~4MB of StableHLO) plus the cache
deserialize.  Serializing the *compiled executable*
(``jax.experimental.serialize_executable``) keyed by
:func:`kyverno_tpu.aotcache.keys.executable_cache_key` skips trace AND
compile: a second process reaches device-served scans with zero fresh
XLA compiles for a cached policy set.

Blobs are ``codec byte + compressed pickle((payload, in_tree,
out_tree, meta))``; zstandard when available, stdlib zlib otherwise
(the seed's hard zstandard dependency silently disabled the disk path
on hosts without it).  ``meta`` records the compile-time environment
(host CPU-feature fingerprint, codegen env scope, jax versions):
XLA:CPU AOT artifacts embed the compile machine's instruction-set
features and can SIGILL when loaded on a host missing them — the cache
*key* already scopes on these axes, but containerized fleets can mask
``/proc/cpuinfo`` into a collision, so the load path re-checks the
recorded meta and REJECTS mismatched entries (fresh compile via the
persistent XLA cache instead of a possibly-lethal load), counting each
rejection on ``kyverno_tpu_aot_load_rejected_total{reason}``.
Integrity framing and eviction live one layer down in
:class:`kyverno_tpu.aotcache.store.AotStore` — a corrupt or
stale-codec entry decodes as a miss and is dropped, never raised.
"""

from __future__ import annotations

import logging
import os
import pickle
import threading
from typing import Any, Optional, Tuple

import jax.monitoring

from ..aotcache import keys as _keys
from ..aotcache.keys import executable_cache_key  # noqa: F401 (re-export)
from ..aotcache.store import AotStore, default_store

_log = logging.getLogger('kyverno.aotcache')

AOT_LOAD_REJECTED = 'kyverno_tpu_aot_load_rejected_total'

_CODEC_ZSTD = b'Z'
_CODEC_ZLIB = b'D'


def _zstd():
    try:
        import zstandard
        return zstandard
    except ImportError:
        return None


def _compile_meta() -> dict:
    """The environment axes an executable is only loadable under."""
    import jax
    return {
        'host_features': _keys.host_fingerprint(),
        'env_scope': repr(_keys.env_scope()),
        'jax': (jax.__version__, jax.lib.__version__),
    }


def encode_executable(compiled) -> bytes:
    """compiled executable → compressed blob (raises on failure)."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree = se.serialize(compiled)
    return _pack_blob(payload, in_tree, out_tree, _compile_meta())


def _pack_blob(payload, in_tree, out_tree, meta: dict) -> bytes:
    raw = pickle.dumps((payload, in_tree, out_tree, meta))
    zstd = _zstd()
    if zstd is not None:
        return _CODEC_ZSTD + zstd.ZstdCompressor(level=3).compress(raw)
    import zlib
    return _CODEC_ZLIB + zlib.compress(raw, 3)


def _unpack_blob(blob: bytes) -> Tuple[Any, Any, Any, dict]:
    """blob → (payload, in_tree, out_tree, meta); raises on any codec
    or framing mismatch (callers treat that as ``undecodable``)."""
    codec, body = blob[:1], blob[1:]
    if codec == _CODEC_ZSTD:
        import zstandard
        raw = zstandard.ZstdDecompressor().decompress(body)
    elif codec == _CODEC_ZLIB:
        import zlib
        raw = zlib.decompress(body)
    else:
        raise ValueError(f'unknown aot codec {codec!r}')
    parts = pickle.loads(raw)
    if len(parts) == 3:  # pre-meta frame: treat as stale
        raise ValueError('legacy aot frame without compile meta')
    return parts


def _meta_mismatch(meta: dict) -> Optional[str]:
    """Rejection reason when ``meta`` does not match this process."""
    import jax
    current = {
        'host_features': ('feature_mismatch', _keys.host_fingerprint()),
        'env_scope': ('env_mismatch', repr(_keys.env_scope())),
        'jax': ('jax_mismatch',
                (jax.__version__, jax.lib.__version__)),
    }
    for field, (reason, want) in current.items():
        got = meta.get(field)
        if got is None:
            continue  # older frame missing this axis: key scoping holds
        if isinstance(want, tuple):
            got = tuple(got)
        if got != want:
            return reason
    return None


def decode_executable(blob: bytes) -> Any:
    """blob → loaded executable (raises on any mismatch — callers
    treat that as a miss and drop the entry)."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree, _meta = _unpack_blob(blob)
    return se.deserialize_and_load(payload, in_tree, out_tree)


# -- store orchestration ------------------------------------------------------

def _count_rejection(reason: str) -> None:
    from ..observability.metrics import global_registry
    reg = global_registry()
    if reg is not None:
        reg.inc(AOT_LOAD_REJECTED, reason=reason)


def _reject(store: AotStore, key: str, reason: str) -> None:
    """Drop an unloadable entry and account for it: the caller falls
    back to a fresh compile (persistent-XLA-cache assisted), which is
    always safe — a forced load of a feature-mismatched executable can
    SIGILL the process."""
    from ..observability import executables
    _log.warning('aot entry %s rejected at load (%s); dropping',
                 key[:12], reason)
    store.delete(key)
    _count_rejection(reason)
    executables.record_eviction(key, reason)


def load_executable(key: str, store: Optional[AotStore] = None) -> Any:
    """Loaded executable for ``key`` or None.  A blob that fails to
    decode (stale jax, torn write below the framing's resolution), was
    compiled under a different CPU-feature set / codegen env, or fails
    XLA deserialization is deleted and counted on
    ``aot_load_rejected_total`` so the next process recompiles instead
    of re-failing (or worse, SIGILLing mid-request)."""
    from jax.experimental import serialize_executable as se
    from .. import faults
    store = store or default_store()
    blob = store.load(key)
    if blob is None:
        return None
    try:
        # injected aot_load faults exercise the real rejection path: a
        # load that dies mid-decode counts a rejection and recompiles
        faults.check(faults.SITE_AOT_LOAD)
        payload, in_tree, out_tree, meta = _unpack_blob(blob)
    except Exception:  # noqa: BLE001 - stale/corrupt entry: recompile
        _reject(store, key, 'undecodable')
        return None
    reason = _meta_mismatch(meta if isinstance(meta, dict) else {})
    if reason is not None:
        _reject(store, key, reason)
        return None
    try:
        return se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception:  # noqa: BLE001 - backend refused the artifact
        _reject(store, key, 'deserialize_failed')
        return None


# -- what the persistent XLA cache handed back ---------------------------------

_XLA_CACHE_HITS = 0


def _on_jax_event(event: str, **_kw) -> None:
    global _XLA_CACHE_HITS
    if event == '/jax/compilation_cache/cache_hits':
        _XLA_CACHE_HITS += 1


jax.monitoring.register_event_listener(_on_jax_event)


def xla_cache_hits() -> int:
    """Compiles this process has had answered by JAX's persistent
    compilation cache so far (JAX's own monitoring event).  Read it
    before and after a compile to learn where the executable came from:
    one that the cache handed back does not survive being serialized a
    second time on XLA:CPU under jax 0.9 — the copy loads, then fails
    at execution with ``Function ... not found`` — so only a fresh
    compile is stored (the XLA cache holds the other kind already)."""
    return _XLA_CACHE_HITS


#: in-flight background stores; flush_stores() joins them (tests, and
#: warmers that want the entry on disk before declaring readiness)
_STORE_THREADS: set = set()
_STORE_THREADS_LOCK = threading.Lock()


def store_executable_async(key: str, compiled,
                           store: Optional[AotStore] = None) -> None:
    """Serialize + write in a daemon thread (~40MB compressed for a
    full-pack chunk executable; must not block the scan path)."""
    store = store or default_store()
    if not store.enabled:
        return

    def work():
        try:
            store.put(key, encode_executable(compiled))
        except Exception:  # noqa: BLE001 - cache write is best-effort
            pass
        finally:
            with _STORE_THREADS_LOCK:
                _STORE_THREADS.discard(threading.current_thread())

    t = threading.Thread(target=work, daemon=True,
                         name=f'aot-store-{key[:8]}')
    with _STORE_THREADS_LOCK:
        _STORE_THREADS.add(t)
    t.start()


def flush_stores(timeout: float = 120.0) -> None:
    """Join outstanding background stores (bounded per thread)."""
    with _STORE_THREADS_LOCK:
        threads = list(_STORE_THREADS)
    for t in threads:
        t.join(timeout)


def evict_executable(key: str, store: Optional[AotStore] = None,
                     reason: Optional[str] = None) -> None:
    """Drop a poisoned entry from disk so the next call recompiles.
    ``reason`` (e.g. ``execute_failed`` for artifacts that loaded but
    died at dispatch — the machine-feature SIGILL class) also counts
    the eviction on ``aot_load_rejected_total``."""
    (store or default_store()).delete(key)
    if reason is not None:
        from ..observability import executables
        _count_rejection(reason)
        executables.record_eviction(key, reason)


def warm_cache_dir() -> Optional[str]:
    """The active store directory (diagnostics / README numbers)."""
    s = default_store()
    return s.root


def aot_enabled() -> bool:
    return default_store().enabled and \
        os.environ.get('KTPU_AOT', '1') == '1'
