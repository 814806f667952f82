"""Persistent XLA compilation cache.

Compiling the flagship round programs takes minutes on a cold chip
(PERF.md section 6: 322 s for the per-round set, 130 s scanned, 2-3 s
from a warm cache), and every driver restart and every (W, B, span)
shape change pays it again. JAX ships a disk-backed executable cache but leaves it off
unless it is given a directory. Drivers and benches call this before
building any jitted program.

Where the cache lives: `JAX_COMPILATION_CACHE_DIR`, when the
environment sets it — JAX reads that variable itself, so nothing is
set in code. Otherwise `.jax_cache/` at the root of the checkout,
resolved from this file's own location: the path is part of the
cache's key, so it is the same whatever the working directory. So are
the programs' op names and source locations (see below): an edit that
moves a traced line, or another entry script, compiles anew.
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_compilation_cache(path: str | None = None) -> str:
    """Turn the persistent compilation cache on and return its
    directory. An explicit `path` wins (tests); otherwise see the
    module docstring. Safe to call more than once."""
    import jax

    if path is None and os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = jax.config.jax_compilation_cache_dir
    else:
        path = path or DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    # op names and source lines are part of the key: the layer scopes
    # (commefficient_tpu/scopes.py) live in that metadata and a device
    # trace reads them back, so a program cached under other names
    # must not answer for this one (JAX's default strips them)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    # (JAX's locations hold up to ten caller frames, so the same
    # program reached through another entry script compiles anew: 200 s
    # + 128 s a script for the benchmark's cells on the chip. Do not
    # shorten them with jax_include_full_tracebacks_in_locations=False:
    # on jax 0.9.0 that also drops the name stack from every compiled
    # op_name, scopes included; tests/test_cache.py pins both.)
    # cache everything that took noticeable compile time; entry-size
    # floor stays 0 so the scanned round programs always qualify
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
