"""Where the persistent XLA compile cache lives.

Compiling the federation programs takes seconds (the MLP round) to minutes
(the 22-layer LoRA round); a machine that keeps nothing between runs except
one directory pays that every time unless the cache is in that directory.
Every entry point (``chip_smoke.py``, ``bench*.py``, ``python -m
p2pfl_tpu``) calls :func:`configure_compile_cache` before its first jit.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the default cache directory, ``<checkout>/.jax_cache`` — derived from the
#: package's own location so every process of one checkout agrees on it
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and no
    directory is set in code, so whoever runs the program decides where the
    cache lives. Unset: :data:`DEFAULT_CACHE_DIR`.

    Every program is cached, not only those that took JAX's default 1 s to
    compile. Measured with ``chip_smoke.py`` on a v5e (PR 21): of 136
    backend compiles per process the default kept 16 — the rest, setup
    programs of 0.05–0.3 s each, cost a warm process 11 s (35.7 s vs 24.6 s
    total) — and a program compiling in about 1 s was kept in some runs and
    not others, so one warm run's phase compiled for longer than the cold
    run's. At 0 a cold run costs the same and the cache holds 2 MB more.

    The key includes the program's metadata. JAX's default strips locations
    before hashing — and ``jax.named_scope`` names live there — so two
    programs that differ only in their scopes share one entry and the later
    one is handed the earlier one's executable, names and all (seen at PR
    24: a program scoped ``beta`` came back with every ``op_name`` reading
    ``alpha``). A profiler trace is read by those names
    (``management/profiling.DEVICE_SCOPES``), so a cache shared between
    two versions of this code must not mix them. The price: an edit that
    moves traced lines misses the cache once.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir
