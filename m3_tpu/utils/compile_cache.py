"""Where JAX's persistent compilation cache lives.

One rule for every process of this repo (services, chip_smoke.py, the
test suite, the multichip dry run): when ``JAX_COMPILATION_CACHE_DIR``
is set the operator has placed the cache and JAX reads the variable
itself — no directory is set in code; otherwise the cache sits at the
fixed path ``<checkout>/.jax_cache`` (the path is part of the cache
key, so it must not move between runs).
"""

from __future__ import annotations

import os
import pathlib

import jax

_CHECKOUT = pathlib.Path(__file__).resolve().parent.parent.parent


def configure() -> str | None:
    """Apply the rule above; returns the directory in use, None = off.

    ``M3_NO_COMPILE_CACHE=1`` opts out: XLA's executable serializer has
    segfaulted on specific programs during long fuzz soaks that mint
    many fresh shapes — those sessions trade cache hits for not
    crashing."""
    if os.environ.get("M3_NO_COMPILE_CACHE") == "1":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(_CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir
