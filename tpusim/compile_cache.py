"""The one rule for JAX's persistent compilation cache.

Every entry point that compiles a replay (chip_smoke.py, bench.py,
bench_scale.py, experiments/run.py, `tpusim apply`, the service worker)
calls `enable_compile_cache()` before its first compile. Where the
environment sets JAX_COMPILATION_CACHE_DIR, JAX has already read it and
nothing is set in code; where it does not, the cache is
`<checkout>/.jax_cache`. The directory must not move between runs, so it
is never derived from a temporary name, a pid or the clock.

JAX's entry-size floor stays. Its compile-time floor (1 s) does not,
unless the environment sets JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS.
On the TPU v5e of PR 22 the floor cut through the replay executables: of
the 86 executables one chip_smoke.py run compiles, 75 took under a second,
one of the four fused-kernel `jit(replay)` executables among them
(0.69 s). With the floor, three runs of identical code kept 11, 12 and 8
entries and a second run under one cache directory wrote a `jit_replay`
entry the first had compiled and dropped. Without it the first run wrote
86 entries and the second none: all 71 of its compile requests loaded.

Importing any tpusim module starts no backend and compiles nothing, so a
call placed right after argument parsing precedes every compile.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the directory the rule
    above names, drop the compile-time floor, and return the directory."""
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
