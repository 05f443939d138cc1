import os
import sys

# Force a virtual 8-device CPU mesh for all sharding tests; must happen
# before any jax backend initialization.  The tests never touch an
# accelerator: the platform is pinned in the environment and in the jax
# config, whatever the caller's environment says.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The checkout for ``kyverno_tpu`` and ``chip_smoke``; ``benchmarks/`` for
# ``benchlib``, through which the tests read the packs and generators the
# benchmark's cells run (``benchlib.load_policies``, ``benchlib.load_module``).
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (os.path.join(ROOT, 'benchmarks'), ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)
