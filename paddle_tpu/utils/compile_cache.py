"""ONE home for the persistent XLA compile-cache wiring.

Reference analog: the autotune/program caches the reference persists
across runs (paddle/phi/kernels/autotune/cache.cc:1) — here the cached
artifact is the XLA executable itself. A model-sized step compiles in
tens of seconds to minutes, and every process of one command should pay
that once, so every entry point that compiles on the chip
(chip_smoke.py, bench.py, tools/bench_serving.py, __graft_entry__'s
compile checks) calls these helpers before its first compile instead of
hand-rolling the wiring.

Where the cache lives is decided OUTSIDE the program: when
`JAX_COMPILATION_CACHE_DIR` is set, jax already uses that directory and
this module sets no other and never turns it off. Only when it is unset
does the program choose, and then it chooses `perf/xla_cache` inside the
checkout (ignored by git) — a fixed path, because the path is part of
the cache key — and only for the TPU: XLA:CPU's AOT reload warns about
machine-feature mismatches even on the same host, so on the CPU the
program's own choice is withdrawn again once the backend is known.
"""
from __future__ import annotations

import contextlib
import os
import sys

__all__ = ["xla_cache_dir", "seed_cache_env", "sync_compile_cache_for",
           "bytecode_cache"]

_PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perf")
_CHECKOUT_CACHE = os.path.join(_PERF, "xla_cache")


@contextlib.contextmanager
def bytecode_cache():
    """Imports made inside read and write their bytecode under
    `perf/pycache` of the checkout (ignored by git), whatever the
    interpreter was told about bytecode; the two settings are put back
    on the way out. For ONE import the serving path cannot do without
    and cannot make smaller: `jax.experimental.pallas` is some 140
    modules (Mosaic for GPUs among them), and an installation that
    ships no `.pyc` and runs with PYTHONDONTWRITEBYTECODE compiles
    their source in every process — 0.8 of the import's 1.35 s on the
    chip's host, inside every engine's set-up (PERF.md section 6,
    PR 34). Like the XLA cache beside it, only a checkout's first
    process pays."""
    prior = sys.pycache_prefix, sys.dont_write_bytecode
    sys.pycache_prefix = os.path.join(_PERF, "pycache")
    sys.dont_write_bytecode = False
    try:
        yield
    finally:
        sys.pycache_prefix, sys.dont_write_bytecode = prior


def xla_cache_dir() -> str:
    """The directory the program falls back to when nothing outside
    names one: perf/xla_cache of the checkout."""
    os.makedirs(_CHECKOUT_CACHE, exist_ok=True)
    return _CHECKOUT_CACHE


def seed_cache_env() -> None:
    """Call before the first compile (backend known or not). With
    JAX_COMPILATION_CACHE_DIR set this changes nothing; unset, it names
    the checkout's directory in the environment, so that child processes
    share it, and in jax's config. Pair with sync_compile_cache_for once
    the platform is known."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    os.environ["JAX_COMPILATION_CACHE_DIR"] = xla_cache_dir()
    import jax
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)


def sync_compile_cache_for(platform: str) -> None:
    """Once the backend is known: on the TPU make sure a cache directory
    is set (the checkout's, if nothing else is); elsewhere withdraw the
    checkout's directory if that is what is set. A directory that came
    from outside is never replaced and never turned off."""
    import jax
    current = jax.config.jax_compilation_cache_dir
    if platform == "tpu":
        if current is None:
            jax.config.update("jax_compilation_cache_dir", xla_cache_dir())
    elif current == _CHECKOUT_CACHE:
        jax.config.update("jax_compilation_cache_dir", None)
        if os.environ.get("JAX_COMPILATION_CACHE_DIR") == _CHECKOUT_CACHE:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
