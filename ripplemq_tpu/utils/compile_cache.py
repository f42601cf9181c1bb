"""Where JAX's persistent compilation cache lives.

A controller boot compiles 2 x len(all_buckets()) + 1 engine programs
plus the vote round (broker/dataplane.py warm), and a machine that is
thrown away after every run pays that cold each time unless the cache
sits at a path that is the same on the next run — the directory is part
of the cache key, so a temp name, pid or timestamp never hits.

The rule, in one place: where `JAX_COMPILATION_CACHE_DIR` is set the
cache was placed from outside and JAX reads the variable itself — no
directory is configured in code. Otherwise the cache goes to ONE fixed,
git-ignored directory inside the checkout. Every entry point that can
own a device calls this before its first compile (broker/__main__.py,
parallel/worker.py, chip_smoke.py's kernel child). A process
started with `JAX_PLATFORMS=cpu` owns no device by the process rule
(standbys, clients, every test child): its programs build in
milliseconds, and the brokers of one cluster would only race each other
for the same cache files — it is left alone.

Wherever the cache lives, JAX's default keeps out any program whose
backend compile took under one second — and on the chip EVERY engine
program does (0.45-1.0 s each, PERF.md): with the default the directory
stays empty and the second boot is as cold as the first. So the
threshold goes to zero here unless the environment set one. What the
cache can save is that backend compile only, a tenth of a boot; the
tracing and the Pallas-to-Mosaic lowering in front of it (~4 s a round
program) are what utils/program_store.py saves: the first boot writes
each finished round program under `programs/` in the directory this
rule resolves (`cache_dir()`), later boots load it. Programs outside
that store (`_init`, `_read_many`, the RS encode) come through here.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_MIN_SECS_ENV_VAR = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"

# <checkout>/.jax_cache — next to the package, listed in .gitignore.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def cache_dir() -> Optional[str]:
    """The directory the rule above resolves, for whatever is kept beside
    JAX's cache (utils/program_store.py); None for a process pinned to
    the CPU backend."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    return os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR


def configure_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at the in-checkout
    directory unless the environment already placed it. Returns the
    directory configured here; None when the variable is set (then no
    cache directory is touched in code) or the process is pinned to
    the CPU backend (then nothing is)."""
    if cache_dir() is None:
        return None
    import jax

    if not os.environ.get(_MIN_SECS_ENV_VAR):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
