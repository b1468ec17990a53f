import os

# Tests run on the single real CPU device (the 512-device override is
# dry-run-only, set inside repro.launch.dryrun before jax init).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_enable_x64", False)
