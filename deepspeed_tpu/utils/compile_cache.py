"""Where the persistent XLA compile cache lives — the one place that says.

Called by the executables (``chip_smoke.py``,
``python -m deepspeed_tpu.serving``), never from ``initialize()``: the CPU
test suite runs without a persistent cache on purpose
(``tests/conftest.py``).  The launcher exports the variable to its
children instead of calling this in the parent, which must stay off JAX.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — a fixed path, because the directory is part
#: of the cache key: a cache that moves never hits
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache somewhere durable and return
    the directory in use.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX
    reads the variable itself and nothing is set in code; without it the
    cache goes to :data:`DEFAULT_DIR`."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

