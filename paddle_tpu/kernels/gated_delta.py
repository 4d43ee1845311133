"""The gated delta rule of a GatedDeltaNet linear-attention layer (Yang,
Kautz & Hatamizadeh 2024, arXiv:2412.06464), in plain `jax.numpy`,
float32. Per value head, with a state S [dk, dv]:

    S'  = exp(g_t) S_{t-1}                           (decay, g_t <= 0)
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T       (rank-1 correction)
    o_t = S_t^T q_t

Where Mamba's state (kernels/selective_scan.py) decays each entry on its
own and adds an outer product of the input, this one is a matrix the
step READS (S'^T k) before it writes it: the correction is what the state
predicted for k_t against what v_t says. A diagonal scan cannot carry it.

Three forms, by caller:

- `gated_delta_step`: one step for every row of a batch — the serving
  tick, a row a slot, in `jax.numpy`. The output is decay * S^T q +
  (k . q) u, so the new state is never read back; but on the chip XLA
  compiles the step on a layer of the stacked pool to THREE passes over
  the state: one reduction fusion that reads S for S^T k and S^T q, and
  one select + dynamic-update-slice fusion that reads S again and writes
  the new layer into the pool. It is the tick's form off a TPU and the
  kernel's reference.
- `gated_delta_tick`: the same step as one Pallas kernel on the stacked
  pool itself, in place: a grid step loads `TICK_HEADS` value heads of
  one slot's state into VMEM once, reduces, updates and writes them back
  — two passes, where the select has nothing of its own to read. Where
  it engages is `tick_kernel_engages`, by what the call shows.
- `gated_delta_chunked`: a run of T positions of ONE sequence — a prompt.
  In chunks of `chunk` positions (the WY / UT form): inside a chunk the
  rank-1 corrections are one lower-triangular solve, (I + L) U~ = rhs,
  with L[t, s] = beta_t exp(G_t - G_s) k_t . k_s for s < t (G the
  cumulative log-decay in the chunk), and the chunks are walked in order
  carrying S, a handful of [C, dk] x [dk, dv] matmuls a head each. A
  T-trip loop of one step each would move the whole state T times. Each
  chunk's arrays are made inside its step: made for every chunk at once
  ahead of the walk they held 0.78 GB more of a 4,096-token prompt's
  temporaries at the published widths (XLA's count for a v5e), which the
  128-slot cell of the benchmark does not have to spare.

`length` is what makes a padded run harmless: positions at or past it
get g = 0 and beta = 0, so the decay is 1 and the correction 0 — the
state handed back is the one after position `length - 1`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .latent_attention import SCOPED_VMEM   # imports Pallas, bytecode kept

from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402

__all__ = ["CHUNK", "TICK_HEADS", "gated_delta_step", "tick_kernel_engages",
           "gated_delta_tick", "gated_delta_chunked"]

# positions a chunk of a prompt's delta rule holds: the side of the
# triangular solve and of the [C, C] score blocks
CHUNK = 64
# value heads a grid step of the tick kernel holds (at most); the sizes
# timed on the chip are in docs/kernel_selection.md
TICK_HEADS = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(s, q, k, v, g, beta, live=None):
    """One step for R rows: s [R, H, dk, dv] float32, q / k [R, H, dk],
    v [R, H, dv], g / beta [R, H] -> (o [R, H, dv] float32, the new
    state). A row that `live` [R] marks False keeps its state as it was
    (its o is computed and means nothing)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    decay = jnp.exp(g)[..., None]                         # [R, H, 1]
    sk = jnp.sum(s * k[..., None], axis=-2)               # S^T k
    sq = jnp.sum(s * q[..., None], axis=-2)               # S^T q
    u = beta[..., None] * (v - decay * sk)
    o = decay * sq + jnp.sum(k * q, axis=-1, keepdims=True) * u
    new = decay[..., None] * s + k[..., :, None] * u[..., None, :]
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, s)
    return o, new


def _tick_heads(H: int) -> int:
    """Value heads a grid step holds: all H up to `TICK_HEADS`, else
    `TICK_HEADS` (the kernel engages only where that divides H)."""
    return min(H, TICK_HEADS)


def tick_kernel_engages(H: int, dk: int, dv: int, dtype) -> bool:
    """Whether a tick's step on a state pool of H value heads of [dk, dv]
    in `dtype` runs `gated_delta_tick` — by what the call can observe, no
    option (the rule of `latent_attention.absorbed_engages`): a TPU, no
    ambient mesh of several devices (GSPMD cannot partition a Mosaic
    kernel), dk and dv whole 128-lane groups, a float32 state, and a
    head block that divides H. The family's tick counts ask the same
    question on the host."""
    from ..device import is_tpu
    from ..parallel.mesh import get_mesh
    mesh = get_mesh()
    return (is_tpu() and (mesh is None or mesh.size == 1)
            and dk % 128 == 0 and dv % 128 == 0
            and jnp.dtype(dtype) == jnp.dtype(jnp.float32)
            and H % _tick_heads(H) == 0)


def _tick_kernel(at_ref, live_ref, s_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                 o_ref, new_ref):
    """One grid step: heads [hb] of one slot. `gated_delta_step`'s
    arithmetic on the block that was loaded once; the new state is
    written over the block it came from (the pool is aliased), the old
    bits where the slot is idle."""
    s = s_ref[...]                                        # [hb, dk, dv]
    q, k, v = q_ref[...], k_ref[...], v_ref[...]          # [hb, dk | dv]
    decay = jnp.exp(g_ref[...])                           # [hb, 1]
    sk = jnp.sum(s * k[:, :, None], axis=1)               # S^T k
    sq = jnp.sum(s * q[:, :, None], axis=1)               # S^T q
    u = b_ref[...] * (v - decay * sk)
    o_ref[...] = decay * sq + jnp.sum(k * q, axis=-1, keepdims=True) * u
    new = decay[:, :, None] * s + k[:, :, None] * u[:, None, :]
    new_ref[...] = jnp.where(live_ref[pl.program_id(0)] > 0, new, s)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_tick(state, at, q, k, v, g, beta, live,
                     interpret: bool = False):
    """`gated_delta_step` for R rows on layer `at` (a traced index) of the
    stacked pool state [L, R, H, dk, dv] float32, in place: q / k
    [R, H, dk], v [R, H, dv], g / beta [R, H], live [R] -> (o [R, H, dv]
    float32, the pool with layer `at`'s rows stepped and every other bit
    as it was). The pool is aliased onto the output and addressed by
    [at, row, head block] in the BlockSpecs (`at` a scalar-prefetch
    operand): no layer is sliced out or put back, and a grid step reads
    a block once and writes it once. Only the order of the 128-term sums
    may differ from the `jnp` form. One jit of the arrays' shapes: every
    linear layer of a tick shares one traced and lowered body."""
    L, R, H, dk, dv = state.shape
    hb = _tick_heads(H)
    f32 = jnp.float32
    pool = pl.BlockSpec((None, None, hb, dk, dv),
                        lambda r, h, at, live: (at[0], r, h, 0, 0))

    def rows(width):
        return pl.BlockSpec((None, hb, width), lambda r, h, *_: (r, h, 0))

    return pl.pallas_call(
        _tick_kernel,
        out_shape=(jax.ShapeDtypeStruct((R, H, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R, H // hb),
            in_specs=[pool, rows(dk), rows(dk), rows(dv), rows(1), rows(1)],
            out_specs=[rows(dv), pool]),
        input_output_aliases={2: 1},
        name="gated_delta_tick",
        interpret=interpret, **_tick_vmem(hb, dk, dv),
    )(jnp.reshape(at, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      state, q.astype(f32), k.astype(f32), v.astype(f32),
      g.astype(f32)[..., None], beta.astype(f32)[..., None])


def _tick_vmem(hb: int, dk: int, dv: int) -> dict:
    """A grid step's state block, in and out, each double-buffered (four
    [hb, dk, dv] float32 buffers), beside the step's intermediates: past
    the scoped default it asks for the buffers plus that default, below
    it for nothing."""
    held = 4 * hb * dk * dv * 4
    if held < SCOPED_VMEM:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=held + SCOPED_VMEM)}


def _chunks(a, n: int, chunk: int):
    """[n * chunk, H, ...] -> [n, H, chunk, ...]."""
    return jnp.swapaxes(a.reshape((n, chunk) + a.shape[1:]), 1, 2)


def gated_delta_chunked(q, k, v, g, beta, s0, length, chunk: int = CHUNK):
    """T positions of ONE sequence from state s0: q / k [T, H, dk],
    v [T, H, dv], g / beta [T, H], s0 [H, dk, dv] float32, `length` the
    true number of positions (traced; the rest is padding) -> (o [T, H,
    dv] float32, the state after position length - 1)."""
    f32 = jnp.float32
    T, H, dk = k.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    real = (jnp.arange(T) < length)[:, None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    n = (T + pad) // chunk
    q, k, v, g, beta = (_chunks(a, n, chunk) for a in (q, k, v, g, beta))
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))       # s <= t
    strict = seen & ~jnp.eye(chunk, dtype=bool)

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)

    def step(s, xs):
        q, k, v, g, beta = xs                             # [H, C, ...]
        G = jnp.cumsum(g, axis=-1)                        # [H, C]
        # exp(G_t - G_s) where s <= t (never above 1), 0 above the diagonal
        decay = jnp.where(seen, jnp.exp(jnp.where(
            seen, G[:, :, None] - G[:, None, :], 0.0)), 0.0)
        lower = jnp.where(strict, beta[:, :, None] * decay
                          * mm("htd,hsd->hts", k, k), 0.0)
        rhs = jnp.concatenate([beta[..., None] * v,
                               (beta * jnp.exp(G))[..., None] * k], -1)
        solved = jax.lax.linalg.triangular_solve(
            jnp.eye(chunk, dtype=f32) + lower, rhs, left_side=True,
            lower=True, unit_diagonal=True)
        # U = u_free - w S: what each position writes, given the chunk's S
        u = solved[..., :dv] - mm("htk,hkv->htv", solved[..., dv:], s)
        o = mm("htk,hkv->htv", q * jnp.exp(G)[..., None], s) \
            + mm("hts,hsv->htv", mm("htd,hsd->hts", q, k) * decay, u)
        k_to_end = k * jnp.exp(G[:, -1:] - G)[..., None]
        s = jnp.exp(G[:, -1])[:, None, None] * s \
            + mm("htk,htv->hkv", k_to_end, u)
        return s, o

    s_end, o = jax.lax.scan(step, s0.astype(f32), (q, k, v, g, beta))
    o = jnp.swapaxes(o, 1, 2).reshape(n * chunk, H, dv)
    return o[:T], s_end
