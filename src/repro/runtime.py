"""Runtime policy derived from the platform and the checkout, in one place.

* Pallas kernels run under the interpreter exactly when JAX's default
  backend is the CPU; on a TPU they lower to Mosaic. An explicit
  ``interpret=True`` stays the caller's choice on any platform.
* Every file the program keeps between runs lives inside its checkout
  (:data:`CHECKOUT`): the compiled-program cache under ``.repro-cache``
  and JAX's persistent compilation cache under ``.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["CHECKOUT", "on_cpu", "resolve_interpret", "setup_compile_cache"]

# src/repro/runtime.py -> the checkout root.
CHECKOUT = Path(__file__).resolve().parents[2]


def on_cpu() -> bool:
    """Whether JAX's default backend is the CPU (no accelerator)."""
    import jax
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if given, else ``True`` only on the CPU platform."""
    return on_cpu() if interpret is None else bool(interpret)


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and no other directory is set. Otherwise the cache lives
    at ``<checkout>/.jax_cache``: a fixed path, since the path is part
    of the cache key. Called from the entry points' ``main()`` only —
    never at import, never in tests.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
