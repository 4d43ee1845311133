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

Two forms, one per caller:

- `gated_delta_step`: one step for every row of a batch — the serving
  tick, a row a slot. S is read once for both S^T k and S^T q (the output
  is decay * S^T q + (k . q) u, so the new state is never read back) and
  written once.
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

import jax
import jax.numpy as jnp

__all__ = ["CHUNK", "gated_delta_step", "gated_delta_chunked"]

# positions a chunk of a prompt's delta rule holds: the side of the
# triangular solve and of the [C, C] score blocks
CHUNK = 64
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
