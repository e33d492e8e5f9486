import os
import sys
from pathlib import Path

# The benchmark's tests run on the CPU, at toy widths.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
# the harness turns JAX's persistent cache on in the checkout; its CPU
# programs have no place there
jax.config.update("jax_enable_compilation_cache", False)
