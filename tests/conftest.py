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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
