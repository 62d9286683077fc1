"""Where JAX's persistent compilation cache lives.

One rule for every command-line entry point (``python -m
repro.launch.train``, ``python -m repro.launch.serve``,
``chip_smoke.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here.
* otherwise: ``<repo>/.jax_cache`` (gitignored). The path is fixed — no
  temporary directory, pid or time in it — so a later run of the same
  checkout finds what an earlier one compiled.

Only the ``__main__`` blocks call this: library code and tests (which
call ``main()`` directly) never turn the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on under the rule above; returns the
    directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
