"""Shared timing helpers for the measurement tools (ablate_step,
autotune_kernels, bench_int8). One copy of the forcing rule: dispatch is
asynchronous, so a timed span ends in `jax.block_until_ready`."""
from __future__ import annotations

import time

import jax

# The roofline plausibility gate moved into the package
# (paddle_tpu/kernels/registry.py) so the kernel-selection registry's
# adoption path and the tools share ONE rule; re-exported here for the
# existing tool callers.
from paddle_tpu.kernels.registry import (  # noqa: F401
    FLOOR_GBS, FLOOR_TFLOPS, gate_ms, plausible_ms)


def force(out):
    """Wait for `out`. A device runs what it is given in order, so
    waiting on the last submission bounds the whole timed span."""
    jax.block_until_ready(out)


def timeit(fn, *args, iters=10, warmup=1):
    """Steady-state ms per call of fn(*args).

    ONLY sound when one call's device time well exceeds the host's
    per-dispatch cost — i.e. model-step-sized work. For kernel-sized
    work use chained_ms."""
    for _ in range(warmup):
        force(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    force(out)
    return (time.perf_counter() - t0) / iters * 1e3


def mix_grads(grads, dtype):
    """Fold a (dq, dk, dv) triple into one dq-shaped carry for
    chained_ms. Summing all three defeats jaxpr DCE — a dq-only carry
    lets the dkv kernel (a separate pallas_call / scan) be dropped from
    the timed chain. Assumes Sq == Skv so the shapes line up."""
    dq, dk, dv = grads
    return (dq + 1e-3 * dk + 1e-3 * dv).astype(dtype)


def chained_ms(step, carry, length=64, iters=3):
    """ms per application of `step`, amortizing dispatch latency.

    Runs `length` applications inside ONE jit as a lax.scan whose carry
    is the step's own output (data dependence defeats CSE), so per-call
    device time is length x kernel-time >> dispatch cost; `iters` outer
    calls then pipeline like the model-step benches. `step` must map
    carry -> same shape/dtype carry."""
    run = jax.jit(lambda c: jax.lax.scan(
        lambda c, _: (step(c), None), c, None, length=length)[0])
    force(run(carry))                      # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        carry = run(carry)
    force(carry)
    return (time.perf_counter() - t0) / (iters * length) * 1e3
