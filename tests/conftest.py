import os

# Tests run single-device (the dry-run owns the 512-device setting).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_enable_x64", False)
