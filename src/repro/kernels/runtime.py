"""Where the Pallas kernels run, and where compiled programs are cached.

Every kernel wrapper declares ``interpret: bool | None = None`` and resolves
it with :func:`resolve_interpret` right before ``pallas_call``. The default
follows the backend: kernels are compiled by Mosaic on a TPU and run under
the Pallas interpreter everywhere else (the CPU test suite). An explicit
``True``/``False`` at a call site still wins. ``tests/test_runtime.py``
asserts no kernel wrapper regresses to a hardcoded default.
:func:`round_apart` makes an interpreted kernel's float output end where a
compiled one's does.

:func:`enable_compile_cache` is the one place the persistent compilation
cache is configured; the launchers and ``chip_smoke.py`` call it before
their first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax
import jax.numpy as jnp

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, because the cache key includes it
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(value: bool | None) -> bool:
    """Resolve a wrapper's ``interpret`` argument: an explicit ``True`` /
    ``False`` wins; ``None`` (the signature default everywhere) compiles on
    the TPU and interprets on any other backend."""
    if value is None:
        return not on_tpu()
    return bool(value)


def round_apart(out, interpret: bool):
    """A kernel's output, rounded apart from whatever consumes it when the
    kernel ran interpreted. A compiled kernel is a custom call that nothing
    fuses into; an interpreted one of a single grid step is inlined into
    the caller's program, where the CPU backend may contract its dequant
    multiply with a consumer's add (a bias add) into an FMA. A sum with
    zeros over a new axis is a reduction, which XLA does not fuse into its
    consumer. Integer outputs (raw accumulators) pass as they are."""
    if not interpret or not jnp.issubdtype(out.dtype, jnp.floating):
        return out
    return jnp.stack([out, jnp.zeros_like(out)]).sum(axis=0, dtype=out.dtype)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it and
    nothing else is configured. Otherwise the cache goes to the fixed
    ``.jax_cache`` directory of this checkout."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
