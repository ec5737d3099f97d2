"""Persistent XLA/Mosaic compilation cache.

First compile of each Pallas kernel variant costs tens of seconds and a
train step minutes; JAX's persistent compilation cache (keyed by backend +
HLO + flags + the cache path itself) lets every later process reuse the
executables. Placement is the caller's: where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX's own reading of it is the only setting and this module sets no
other; where it is not, the cache lives at the fixed ``<checkout>/.jax_cache``
(git-ignored) — a path that moves never hits.

Reference analogue: the JIT build cache (magi_attention/common/jit/core.py,
keyed by env snapshot env/ffa.py:125) — same role, compiler-level.
"""

from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache() -> str:
    """Turn on the JAX persistent compilation cache (idempotent) and return
    its directory. Call before the first jit/pallas compilation."""
    import jax

    from ..env.general import jax_compilation_cache_dir

    path = jax_compilation_cache_dir()
    if not path:
        path = _DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: a cold train step is hundreds of sub-second
    # XLA programs around a few long Mosaic kernels, and with JAX's
    # default 1 s floor the small ones are recompiled by every process.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
