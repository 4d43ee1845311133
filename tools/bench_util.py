"""Shared timing helpers for the measurement tools (autotune_kernels,
bench_int8). One copy of the forcing rule: dispatch is asynchronous, so
a timed span ends in `jax.block_until_ready`."""
from __future__ import annotations

import time

import jax

# Roofline anchors for the plausibility gate: the peaks-table row
# (paddle_tpu.device.CHIP_PEAKS) of the one chip these tools have timed
# kernels on. The gate prices that chip, not the device it runs on.
GATE_CHIP = "TPU v5 lite"
# Below these effective rates a kernel-sized timing is measuring the
# host, not the chip — a sweep once persisted CE rows at 3.4-7.9 s for a
# ~15 ms kernel, which this floor rejects.
FLOOR_TFLOPS = 0.5
FLOOR_GBS = 20.0


def plausible_ms(flops: float = 0.0, bytes_moved: float = 0.0):
    """Physical window (lo_ms, hi_ms) for ONE application of a kernel of
    known arithmetic/memory volume. lo = half the roofline time (nothing
    runs 2x faster than the roofline); hi = the time implied by the
    FLOOR_* effective rates (anything slower is a measurement artifact,
    not a slow kernel)."""
    from paddle_tpu.device import chip_peaks
    peaks = chip_peaks(GATE_CHIP)
    lo_s = max(flops / peaks.flops, bytes_moved / peaks.hbm_bw) / 2.0
    hi_s = max(flops / (FLOOR_TFLOPS * 1e12),
               bytes_moved / (FLOOR_GBS * 1e9), 1e-6)
    return lo_s * 1e3, hi_s * 1e3


def gate_ms(ms: float, flops: float = 0.0, bytes_moved: float = 0.0):
    """None if `ms` is physically plausible for the given volumes, else a
    short reason string for the record."""
    lo, hi = plausible_ms(flops, bytes_moved)
    if ms < lo:
        return f"implausibly fast: {ms:.3f} ms < {lo:.3f} ms (2x roofline)"
    if ms > hi:
        return (f"implausibly slow: {ms:.3f} ms > {hi:.1f} ms "
                "(sub-floor effective rate; likely host-bound)")
    return None


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
