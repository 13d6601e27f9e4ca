"""Pallas execution mode and the persistent compile cache.

The kernels in this package target TPU (Mosaic); GPU lowers via Triton.  On
CPU there is no compiled Pallas backend, so the same kernel bodies execute
under the Pallas interpreter (bit-identical semantics, jittable, but paying
a grid-loop emulation tax).  The mode follows the backend and nothing else:
a backend with a Pallas compiler always compiles, so a kernel that does not
lower fails loudly instead of being interpreted on the chip.

``resolve_interpret(None)`` is the contract every kernel wrapper follows:
an explicit ``interpret=`` argument is honored verbatim, ``None`` means
"auto".
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# backends with a compiled Pallas lowering (mosaic / triton)
_COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")

# the checkout this package runs from (src/repro/kernels/runtime.py)
_CHECKOUT = Path(__file__).resolve().parents[3]


def has_compiled_backend() -> bool:
    """True when the default JAX backend can compile Pallas kernels."""
    return jax.default_backend() in _COMPILED_BACKENDS


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret only where no compiled Pallas backend exists;
    an explicit flag passes through."""
    return not has_compiled_backend() if interpret is None else bool(interpret)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (git-ignored): the path is part of JAX's
    cache key, so it must never be a temp name.  Every compiled program is
    kept, not only the slow ones, so a second run compiles nothing.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
