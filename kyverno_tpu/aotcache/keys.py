"""Cache-key derivation for persisted executables + the XLA
persistent-compilation-cache hookup.

An AOT entry is only loadable in a process that matches the one that
compiled it, so the key covers every axis that changes the generated
code: the policy-set fingerprint, the evaluator/compiler source digest,
jax + jaxlib versions, the backend platform and device identity
(kind/topology), the host CPU feature set, the ambient XLA environment
(flags, platform selection, which backends are live), and the batch
input signature (name/dtype/shape per lane — the batch layout).
"""

from __future__ import annotations

import hashlib
import os
from typing import Any, Dict, Optional, Tuple

import jax

#: bump to invalidate every persisted executable (framing/codec changes)
#: v3: blobs carry compile-time meta (host features / env scope / jax
#: versions) re-checked at load; batch layouts moved to the canonical
#: capacity table (compiler/shapes.py), retiring the pow-2 bucket zoo
AOT_VERSION = 3

_SOURCE_DIGEST: Optional[str] = None


def source_digest() -> str:
    """Digest of the compiler/evaluator sources: any code change
    invalidates AOT entries (the executable bakes in their semantics)."""
    global _SOURCE_DIGEST
    if _SOURCE_DIGEST is None:
        h = hashlib.sha256()
        base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for rel in ('ops/eval.py', 'compiler/compile.py',
                    'compiler/encode.py', 'compiler/ir.py',
                    'compiler/pss_compile.py'):
            try:
                with open(os.path.join(base, rel), 'rb') as f:
                    h.update(f.read())
            except OSError:
                h.update(rel.encode())
        _SOURCE_DIGEST = h.hexdigest()[:16]
    return _SOURCE_DIGEST


def policy_set_fingerprint(policies) -> str:
    """Stable digest of a policy set's raw documents (the evaluator HLO
    is a deterministic function of them — verified cross-process)."""
    import json
    payload = json.dumps([getattr(p, 'raw', p) for p in policies],
                         sort_keys=True, separators=(',', ':'),
                         default=str)
    return hashlib.sha256(payload.encode()).hexdigest()[:20]


def host_fingerprint() -> str:
    """Short hash of the host CPU feature set.  XLA:CPU AOT artifacts
    embed the compile machine's features and can SIGILL when loaded on a
    host missing them; scoping the cache dir per feature set keeps a
    shared checkout safe across heterogeneous machines."""
    try:
        with open('/proc/cpuinfo') as f:
            for line in f:
                if line.startswith('flags'):
                    return hashlib.sha256(
                        ' '.join(sorted(line.split())).encode()
                    ).hexdigest()[:10]
    except OSError:
        pass
    import platform
    return hashlib.sha256(platform.machine().encode()).hexdigest()[:10]


def initialized_platforms() -> Tuple[str, ...]:
    """The backends live in this process.  A live accelerator backend
    changes XLA:CPU codegen preferences (prefer-no-gather/scatter), so
    CPU executables compiled beside one are not loadable in a CPU-only
    process — AOT keys must separate them."""
    try:
        return tuple(sorted(jax._src.xla_bridge.backends().keys()))
    except Exception:  # noqa: BLE001 - never block caching on this
        try:
            return (jax.default_backend(),)
        except Exception:  # noqa: BLE001
            return ()


def env_scope() -> Tuple:
    """The codegen-relevant process environment: host CPU features plus
    everything that steers XLA's machine-feature preferences."""
    return (host_fingerprint(), os.environ.get('XLA_FLAGS', ''),
            os.environ.get('JAX_PLATFORMS', ''), initialized_platforms())


def executable_cache_key(fingerprint: str, packed: Dict[str, Any],
                         extra: Tuple = ()) -> Optional[str]:
    """Cache key for one (policy set, input signature, platform) combo.

    Returns None when the entry could not be persisted safely:

    * inputs sharded across >1 device (mesh path: executables embed the
      device assignment — not portable);
    * >1 local device on the backend (``deserialize_and_load`` reloads
      executables across ALL local devices, so a 1-device executable
      mis-loads as an N-shard SPMD program — verified on the
      8-virtual-device CPU test env);
    * non-CPU backends (accelerator recompiles ride the persistent
      XLA compilation cache instead).
    """
    try:
        sig = []
        backend = jax.default_backend()
        platform = backend
        for name in sorted(packed):
            v = packed[name]
            sharding = getattr(v, 'sharding', None)
            if sharding is not None:
                devs = getattr(sharding, 'device_set', None)
                if devs is not None:
                    if len(devs) != 1:
                        return None
                    d = next(iter(devs))
                    backend = d.platform
                    # device kind + identity, not just the platform
                    # name: topology/generation changes the executable
                    platform = (f'{d.platform}:{getattr(d, "id", 0)}:'
                                f'{getattr(d, "device_kind", "")}')
            sig.append((name, str(v.dtype), tuple(v.shape)))
        if len(jax.local_devices(backend=backend)) != 1:
            return None
        if backend != 'cpu':
            return None
        payload = repr((AOT_VERSION, source_digest(), jax.__version__,
                        jax.lib.__version__, platform, fingerprint, sig,
                        env_scope(), extra))
        return hashlib.sha256(payload.encode()).hexdigest()[:32]
    except Exception:  # noqa: BLE001 - cache is an optimization only
        return None


# -- XLA persistent compilation cache ---------------------------------------

_PERSISTENT_CACHE_ON = False
_PERSISTENT_CACHE_DIR: Optional[str] = None

#: counted when the feature guard refuses a cache directory (same
#: series the AOT executable store uses for its load rejections)
AOT_LOAD_REJECTED = 'kyverno_tpu_aot_load_rejected_total'

#: marker file recording which host CPU feature set populated a
#: persistent-cache directory
HOSTKEY_FILE = 'HOSTKEY'


def verify_cache_feature_scope(cache_dir: str) -> Tuple[str, bool]:
    """Feature guard for the default CPU persistent-XLA-cache directory.

    XLA:CPU entries embed the compile host's CPU features, so a
    checkout shared across heterogeneous machines risks SIGILL when one
    loads what another compiled.  A ``HOSTKEY`` marker records which
    feature set populated the directory; on mismatch the dir is
    re-scoped to a ``feat-<digest>`` subdirectory and the rejection
    counts on ``kyverno_tpu_aot_load_rejected_total{reason=
    feature_mismatch}``.  Returns ``(usable_dir, rejected)``."""
    fp = host_fingerprint()
    marker = os.path.join(cache_dir, HOSTKEY_FILE)
    recorded: Optional[str] = None
    try:
        with open(marker) as f:
            recorded = f.read().strip()
    except OSError:
        pass
    if recorded is not None and recorded != fp:
        from ..observability.metrics import global_registry
        registry = global_registry()
        if registry is not None:
            registry.inc(AOT_LOAD_REJECTED, reason='feature_mismatch')
        cache_dir = os.path.join(cache_dir, f'feat-{fp}')
        rejected = True
    else:
        rejected = False
    if recorded != fp:
        try:
            os.makedirs(cache_dir, exist_ok=True)
            with open(os.path.join(cache_dir, HOSTKEY_FILE), 'w') as f:
                f.write(fp)
        except OSError:
            pass
    return cache_dir, rejected


def default_compile_cache_dir(platform: str) -> str:
    """The in-checkout cache directory for one backend platform.  Fixed:
    JAX's own cache key already covers the compile options and every
    flag that changes code (jax/_src/cache_key.py), so nothing about
    the environment belongs in the path."""
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), '.cache',
        f'xla-{platform}')


def enable_persistent_compilation_cache() -> Optional[str]:
    """Turn on XLA's persistent compilation cache so a fresh process
    re-serving the same policy set skips the (multi-second) backend
    compile even where AOT executables can't persist (mesh,
    accelerators).  ``JAX_COMPILATION_CACHE_DIR`` places the cache when
    set — JAX reads it itself and no directory is set in code;
    otherwise the cache lives in one fixed directory per backend
    platform inside the checkout.  Idempotent; returns the cache dir
    (or None when the runtime lacks the knobs)."""
    global _PERSISTENT_CACHE_ON, _PERSISTENT_CACHE_DIR
    if _PERSISTENT_CACHE_ON:
        return _PERSISTENT_CACHE_DIR
    try:
        cache_dir = os.environ.get('JAX_COMPILATION_CACHE_DIR')
        if not cache_dir:
            platform = jax.default_backend()
            cache_dir = default_compile_cache_dir(platform)
            os.makedirs(cache_dir, exist_ok=True)
            if platform == 'cpu':
                # a dir populated by a different CPU feature set (a
                # shared checkout) is re-scoped, not trusted — its
                # entries could SIGILL this host
                cache_dir, _rejected = verify_cache_feature_scope(
                    cache_dir)
            jax.config.update('jax_compilation_cache_dir', cache_dir)
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.5)
    except Exception:  # noqa: BLE001 - cache is an optimization only
        return None
    _PERSISTENT_CACHE_ON = True
    _PERSISTENT_CACHE_DIR = cache_dir
    return cache_dir
