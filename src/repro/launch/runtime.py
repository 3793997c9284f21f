"""Process-level JAX set-up shared by every entry point.

* :func:`enable_compile_cache` — the persistent compilation cache.  Every
  entry point (``chip_smoke.py``, ``repro.launch.serve``,
  ``benchmarks.run`` / ``benchmarks.serve_bench``) calls it before its
  first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
  reads it and this sets nothing; otherwise the cache lives at the fixed
  ``<repo>/.jax_cache`` (the directory is part of the cache key, so it
  never moves: no temp dir, pid or time stamp).
* :func:`cpu_child_env` / :func:`refuse_if_chip_held` — a TPU chip belongs
  to one process.  Code that starts JAX children pins them to the CPU
  backend and refuses to run when the parent itself holds a TPU, instead
  of letting a child hang on the chip its parent owns.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def refuse_if_chip_held(what: str) -> None:
    """Raise when this process has JAX on a TPU: a child started now could
    never open the chip the parent holds.  A process that has not imported
    JAX holds nothing and passes."""
    if "jax" not in sys.modules:
        return
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} starts JAX child processes, but this process already "
            f"holds the TPU and only one process can; run it from a "
            f"process that has not initialised JAX on the chip")


def cpu_child_env(**extra: str) -> dict:
    """This process' environment plus ``extra``, for a JAX child process
    that must stay off the chip."""
    return dict(os.environ, **extra, JAX_PLATFORMS="cpu")
