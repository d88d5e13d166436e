"""Where JAX's persistent compilation cache lives.

Every step program is a ``jax.jit`` that XLA compiles at its first
dispatch — seconds each on the TPU — and every entry point is its own
process (the server, ``benchmark/run.py``, ``chip_smoke.py``, the test
workers).  A persistent cache lets the second process skip the compile,
but only if the directory does not move: its path is part of the cache
key, so a temp name, a pid or a timestamp in it means it never hits.

The rule, the same for every entry point: where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set in code; otherwise the cache
is ``<checkout>/.jax_cache`` (git-ignored), derived from this package's
location.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache — three levels up from ksql_tpu/runtime/
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place() -> str:
    """Give this process a persistent compilation cache at a path that does
    not move; returns the directory.  Call before the first compile."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
