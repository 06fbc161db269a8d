"""Where the entry scripts keep JAX's persistent compilation cache.

`chip_smoke.py` and `benchmarks/hf7b_decode.py` call
`enable_compile_cache()` before anything compiles. The cache key includes the
directory, so the directory never moves: `JAX_COMPILATION_CACHE_DIR` when
the environment sets it (then no directory is set in code), otherwise
`<checkout>/.jax_cache` (git-ignored) — never a temporary, pid- or
time-derived name. A chip call starts from an empty cache unless the
machine came with that variable set, so what this buys is a warm second
process inside ONE call; put runs that share programs into one command.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # Half JAX's 1 s default, so that every step program and kernel case is
    # stored. Not 0: the sub-second utility programs that would add (the
    # identity `device_put` relayouts with) came back WRONG from the cache
    # on the chip — see deepspeed_tpu/utils/layouts.py:no_persistent_cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
