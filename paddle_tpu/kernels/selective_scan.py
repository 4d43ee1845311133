"""The selective scan of a Mamba-1 state-space layer (Gu & Dao 2023,
arXiv:2312.00752, Algorithm 2), in plain `jax.numpy`, float32:

    s_t[n, c] = exp(delta_t[c] * A[n, c]) * s_{t-1}[n, c]
                + delta_t[c] * B_t[n] * x_t[c]
    y_t[c]    = sum_n C_t[n] * s_t[n, c] + D[c] * x_t[c]

per channel c of `d_inner` and state n of `d_state`. The channels sit on
the LAST axis everywhere (`A` is [N, Di], a state [N, Di]): a last axis
of 16 states would fill an eighth of a vector register.

Two forms, one per caller:

- `selective_state_update`: one step for every row of a batch — the
  serving tick, a row a slot.
- `selective_scan`: a run of T positions of one sequence — a prompt. It
  is CHUNKED: sequential over chunks of `chunk` positions carrying the
  state, and inside a chunk the steps are written out one after the
  other (`lax.scan(unroll=chunk)`), so the compiler fuses a chunk into
  one pass that keeps the state on the chip's vector unit between its
  steps and stores it once a chunk. Neither a T-trip loop of one step
  each (a step is 80k elements of work under ~3 us of loop: 12.4 ms for
  4,096 positions of one layer on a v5e against 3.0 ms in chunks of 8)
  nor the [T, N, Di] tensor of every state (328 KB a token a layer; an
  associative scan over it read 63 ms) — PERF.md, PR 35, has the
  microbenchmark that chose this form and the chunk.

`length` is what makes a padded run harmless: positions at or past it
get delta = 0, so exp(0) = 1 keeps the state and the input term is 0 —
the state handed back is the one after position `length - 1`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["selective_scan", "selective_state_update"]


def selective_state_update(s, x, delta, A, B, C, D, live=None):
    """One step for R rows: s [R, N, Di] float32, x / delta [R, Di],
    B / C [R, N], A [N, Di], D [Di] -> (y [R, Di] float32, the new
    state). A row that `live` [R] marks False keeps its state as it was
    (its y is computed and means nothing)."""
    f32 = jnp.float32
    x, delta = x.astype(f32), delta.astype(f32)
    new = jnp.exp(delta[:, None, :] * A[None]) * s \
        + (delta * x)[:, None, :] * B.astype(f32)[:, :, None]
    y = jnp.sum(C.astype(f32)[:, :, None] * new, axis=1) + D * x
    if live is not None:
        new = jnp.where(live[:, None, None], new, s)
    return y, new


def selective_scan(x, delta, A, B, C, D, s0, length, chunk: int):
    """T positions of ONE sequence from state s0: x / delta [T, Di],
    B / C [T, N], A [N, Di], D [Di], s0 [N, Di] float32, `length` the
    true number of positions (traced; the rest is padding) ->
    (y [T, Di] float32, the state after position length - 1)."""
    f32 = jnp.float32
    x, delta, B, C = (a.astype(f32) for a in (x, delta, B, C))
    delta = jnp.where(jnp.arange(x.shape[0])[:, None] < length, delta, 0.0)

    def step(s, at):
        d, dx, b, c = at                            # [Di], [Di], [N], [N]
        s = jnp.exp(d[None, :] * A) * s + dx[None, :] * b[:, None]
        return s, jnp.sum(c[:, None] * s, axis=0)

    s_end, y = jax.lax.scan(step, s0.astype(f32), (delta, delta * x, B, C),
                            unroll=max(1, min(chunk, x.shape[0])))
    return y + D * x, s_end
