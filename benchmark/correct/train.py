"""`correct` for a training cell: the program's first three steps (the very
object the window then drives) against the plain reference following the
same three batches from the same seed.

Numbers compared (each has a limit in benchmark/limits/<cell>.json):
  loss_gap_step1..3  |program - reference| / |reference|, each step's loss
  grad_norm_gap      worst leaf of the FIRST gradient as the optimizer got
                     it (program: Adam's m after one step / (1 - beta1)):
                     | ||g_prog|| - ||g_ref|| | / max(||g_ref|| of the
                     leaf, of the median leaf)
  update_norm_gap    the same measure on the parameters' change after the
                     three steps, over the leaves whose reference gradient
                     is at least a thousandth of the median leaf's (a leaf
                     with no gradient moves under Adam by round-off alone)
  grad_direction_gap worst such leaf of ||g_prog - g_ref|| / ||g_ref|| over
                     a fixed strided sample of each leaf's elements of the
                     first gradient. Norms do not see element-wise noise
                     (it adds in quadrature); this number does, and is the
                     one that tells 8-bit matmul operands from bf16
"""
from __future__ import annotations

import functools
import statistics

import numpy as np

from ..reference import gpt as ref
from ..weights import make_gpt_params

STEPS = 3
DEAD_LEAF = 1e-3        # of the median leaf's gradient norm
SAMPLE = 1 << 18        # elements of each leaf kept for the direction gap


@functools.lru_cache(maxsize=None)
def _leaf_norms_fn():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda tree: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in tree.items()})


def leaf_norms(tree) -> dict:
    import jax
    return {k: float(v)
            for k, v in jax.device_get(_leaf_norms_fn()(tree)).items()}


@functools.lru_cache(maxsize=None)
def _sample_fn():
    import jax

    def sample(tree):
        out = {}
        for k, v in tree.items():
            flat = v.reshape(-1)
            out[k] = flat[::max(1, flat.shape[0] // SAMPLE)][:SAMPLE]
        return out

    return jax.jit(sample)


def leaf_samples(tree, scale: float = 1.0) -> dict:
    """The same strided sample of every leaf's elements, on the host."""
    import jax
    return {k: np.asarray(v, np.float32) * scale
            for k, v in jax.device_get(_sample_fn()(tree)).items()}


def update_norms(params, model: dict, seed: int) -> dict:
    """||params - the seed's initial parameters|| per leaf (the initial
    tree is made anew here: the donated one is gone)."""
    import jax
    init = make_gpt_params(model, seed)
    diff = jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(params, init)
    del init
    return leaf_norms(diff)


def reference_readings(config: dict, seed: int, batches, *,
                       precision: str = "float32", fault: str | None = None,
                       block_rows: int = 2) -> dict:
    """Losses of the three steps, the first gradient's leaf norms and the
    three-step update's leaf norms, by the plain reference in `precision`.
    `fault` plants a fault that a limit is read against: "half_batch" —
    the second half of every batch left out, the mean taken over the rest;
    "state_unchanged" — every step returns its state as it got it."""
    import jax
    import jax.numpy as jnp
    model, opt = config["model"], config["optimizer"]
    kw = dict(num_heads=model["num_heads"], eps=model["layer_norm_eps"],
              precision=precision)
    grad_block = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss_sum(p, t, **kw)))
    add = jax.jit(lambda a, b: {k: a[k] + b[k] for k in a})
    scale = jax.jit(lambda a, s: {k: v * s for k, v in a.items()})
    hyper = dict(lr=opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                 eps=opt["eps"], weight_decay=opt["weight_decay"])
    update = jax.jit(functools.partial(ref.adamw, **hyper))

    params = make_gpt_params(model, seed)
    zeros = jax.jit(lambda p: {k: jnp.zeros_like(v) for k, v in p.items()})
    m, v = zeros(params), zeros(params)
    losses, grad_norms, grad_samples = [], None, None
    for i, batch in enumerate(batches[:STEPS]):
        batch = np.asarray(batch)
        if fault == "half_batch":
            batch = batch[:len(batch) // 2]
        total, grads = 0.0, None
        for r in range(0, len(batch), block_rows):
            l, g = grad_block(params, jnp.asarray(batch[r:r + block_rows]))
            total += float(l)
            grads = g if grads is None else add(grads, g)
        positions = batch.shape[0] * (batch.shape[1] - 1)
        grads = scale(grads, 1.0 / positions)
        losses.append(total / positions)
        if i == 0:
            grad_norms, grad_samples = leaf_norms(grads), leaf_samples(grads)
        if fault != "state_unchanged":
            params, m, v = update(params, grads, m, v, float(i + 1))
    del m, v, grads
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples,
            "update_norms": update_norms(params, model, seed)}


def leaf_gaps(got: dict, want: dict, leaves) -> dict:
    """Per leaf: the gap between the two norms over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    median = statistics.median(want.values())
    return {k: abs(got[k] - want[k]) / max(want[k], median) for k in leaves}


def _worst_leaf(got: dict, want: dict, leaves) -> float:
    return max(leaf_gaps(got, want, leaves).values())


def compare(program: dict, reference: dict) -> dict:
    """The numbers `correct` is decided on (smaller is closer)."""
    numbers = {}
    for i in range(STEPS):
        lp, lr = program["losses"][i], reference["losses"][i]
        gap = abs(lp - lr) / abs(lr)
        numbers[f"loss_gap_step{i + 1}"] = gap if np.isfinite(gap) \
            else float("inf")
    ref_g = reference["grad_norms"]
    numbers["grad_norm_gap"] = _worst_leaf(program["grad_norms"], ref_g,
                                           list(ref_g))
    floor = DEAD_LEAF * statistics.median(ref_g.values())
    alive = [k for k in ref_g if ref_g[k] >= floor]
    numbers["update_norm_gap"] = _worst_leaf(
        program["update_norms"], reference["update_norms"], alive)
    got, want = program["grad_samples"], reference["grad_samples"]
    numbers["grad_direction_gap"] = max(
        float(np.linalg.norm(got[k] - want[k]) / np.linalg.norm(want[k]))
        for k in alive)
    return numbers

