"""Global RNG state.

Reference analog: phi::Generator (/root/reference/paddle/phi/core/generator.cc)
and paddle.seed (python/paddle/framework/random.py).

TPU-native design: JAX's counter-based PRNG (threefry) instead of stateful
Philox generators. Eager random ops draw a fresh subkey from this global state
and pass it as an *array input* to the op, so (a) the op's compiled executable
is reused across calls, and (b) tape recompute in backward sees the identical
key — dropout masks are bitwise-reproducible in backward. The TP-aware
RNGStatesTracker (reference fleet/layers/mpu/random.py:34) lives in
paddle_tpu.parallel.random and builds on the same mechanism.
"""
from __future__ import annotations

import threading

import jax
import numpy as np


class _RNGState(threading.local):
    def __init__(self):
        # lazily materialized: creating a PRNGKey initializes the jax
        # backend, which must not happen at import time (an import must
        # not claim the chip)
        self.key = None
        self.seed_value = 0


# host-only stream for data-prep entropy: deliberately NOT thread-local —
# DataLoader producer threads must continue the user's seeded stream, not
# restart an unseeded one. Guarded by a lock; forked workers additionally
# mix in their worker id (see next_host_seed).
_host_state = {"seed": 0, "counter": 0}
_host_lock = threading.Lock()


_state = _RNGState()


def _current_key():
    if _state.key is None:
        _state.key = jax.random.PRNGKey(_state.seed_value)
    return _state.key


def seed(s: int):
    """paddle.seed analog — resets the global generator."""
    _state.seed_value = int(s)
    _state.key = jax.random.PRNGKey(int(s))
    with _host_lock:
        _host_state["seed"] = int(s)
        _host_state["counter"] = 0
    return _state


def get_rng_state():
    return _current_key()


def set_rng_state(key):
    _state.key = key


def next_key():
    """Split one subkey off the global stream. Under a to_static trace, the
    key is threaded through the compiled program as an input instead (see
    jit.trace_context.TraceRngContext) so every call of the compiled step
    gets fresh randomness."""
    from ..jit.trace_context import active_rng
    ctx = active_rng()
    if ctx is not None:
        return ctx.next_key()
    _state.key, sub = jax.random.split(_current_key())
    return sub


def default_seed() -> int:
    return _state.seed_value


def next_host_seed() -> tuple:
    """Host-side analog of next_key for data-prep ops (graph sampling,
    loader shuffles): a (seed, counter, worker_id) entropy tuple that
    replays under paddle.seed without touching the jax backend (a
    forked loader worker must not, and a device dispatch per minibatch
    is a sync the host path does not need). The state is process-global (not thread-local) so loader
    producer threads continue the user's stream; forked DataLoader
    workers inherit the counter snapshot but mix in their worker id, so
    their streams are decorrelated yet reproducible (the loader's batch
    order is deterministic)."""
    from ..io import get_worker_info
    with _host_lock:
        c = _host_state["counter"]
        _host_state["counter"] = c + 1
        s = _host_state["seed"]
    info = get_worker_info()
    # SeedSequence entropy must be non-negative: 0 = trainer process,
    # workers are 1-based
    wid = 0 if info is None else int(info.id) + 1
    return (s, c, wid)
