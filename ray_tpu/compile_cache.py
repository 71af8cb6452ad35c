"""Where XLA's persistent compilation cache lives.

A cold ``LLMServer`` warm-up or train step compiles for minutes; a
machine that is thrown away after every run (the chip tool's) pays that
each time unless the cache can be PLACED from outside.  The contract:

- ``JAX_COMPILATION_CACHE_DIR`` set → this module sets nothing.  jax
  reads the variable itself, and so does every child process.
- unset → one fixed directory inside the checkout, derived from this
  package's location (never a tempdir, a pid or a time: the path must
  be the same for every process and every run of one checkout, or the
  next run never finds what this one compiled).  It is exported through
  the same variable so that children inherit it.
- unset in a process held to the CPU (``JAX_PLATFORMS=cpu``: the test
  suite, cluster worker nodes) → nothing: there is no chip compile to
  keep, and XLA:CPU logs a machine-feature warning on every cached
  executable it loads.

Call sites: Runtime boot and the top of each chip script — one per
process kind.  jax-free unless the process has already imported jax.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def place_compile_cache() -> Optional[str]:
    """Make this process and its children use the placed cache
    directory (see module docstring); returns it, or None where none
    is placed."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    os.environ[ENV_VAR] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        # Imported before us: its config already read the (then unset)
        # variable, so tell it directly.
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
