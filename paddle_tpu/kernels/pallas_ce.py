"""Hand-tiled Pallas softmax-cross-entropy over a large vocab.

Reference analog: the fused softmax_with_cross_entropy kernel
(paddle/phi/kernels/gpu/cross_entropy_kernel.cu) — per-row loss without
materializing the probability tensor.

TPU-native design: tokens tile the grid's outer axis, vocab tiles the
inner axis with the online-logsumexp state (m, l) and the gathered
target logit living in VMEM scratch across vocab tiles — HBM reads the
bf16 logits exactly ONCE and never writes an f32 [T, V] intermediate
(the jax-level fused CE upcasts the whole logits tensor to f32 first).
Backward is one pass: d_logits tile = (softmax − onehot) · g, rebuilt
from the saved per-row logsumexp.

Wired via jax.custom_vjp behind losses.fused_softmax_ce when the
backend is TPU and shapes tile; the jax-level form remains the
fallback and the numerics oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .primitives import (NEG_INF as _NEG_INF, ROW_SCALAR_LANES as _LANES,
                         bounds_mask, logsumexp_finalize,
                         online_softmax_update, pad_to as _pad_dim,
                         tile_positions)


def _fwd_kernel(x_ref, tgt_ref, loss_ref, lse_ref, m_ref, l_ref, t_ref,
                *, block_t, block_v, n_valid_v):
    j = pl.program_id(1)                   # vocab tile (innermost)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    s = x_ref[...].astype(jnp.float32)                    # (BT, BV)
    vpos = tile_positions(j, block_v, (block_t, block_v), 1)
    s = jnp.where(bounds_mask(vpos, n_valid_v), s, _NEG_INF)  # pad tiles

    m_new, l_new, _p, _corr = online_softmax_update(
        m_ref[:, :1], l_ref[:, :1], s)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # target logit: exactly one tile holds it per row
    tgt = tgt_ref[:, :1]                                   # (BT, 1) int32
    hit = (vpos == tgt)
    t_ref[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True),
        t_ref.shape)

    @pl.when(j == nv - 1)
    def _finalize():
        lse = logsumexp_finalize(m_ref[:, :1], l_ref[:, :1])
        loss_ref[...] = jnp.broadcast_to(lse - t_ref[:, :1],
                                         loss_ref.shape)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _bwd_kernel(x_ref, tgt_ref, lse_ref, g_ref, dx_ref,
                *, block_t, block_v, n_valid_v):
    j = pl.program_id(1)
    s = x_ref[...].astype(jnp.float32)
    vpos = tile_positions(j, block_v, (block_t, block_v), 1)
    p = jnp.exp(s - lse_ref[:, :1])
    p = jnp.where(bounds_mask(vpos, n_valid_v), p, 0.0)
    onehot = (vpos == tgt_ref[:, :1]).astype(jnp.float32)
    dx_ref[...] = ((p - onehot) * g_ref[:, :1]).astype(dx_ref.dtype)


def _fused_kernel(x_ref, tgt_ref, loss_ref, dx_ref, m_ref, l_ref, t_ref,
                  lse_ref, *, block_t, block_v, n_valid_v):
    """One-pass CE+grad: grid (tokens, PHASE, vocab). Phase 0 is the
    online-logsumexp sweep (exactly _fwd_kernel), finalizing the row lse
    into VMEM scratch; phase 1 re-streams the same vocab tiles and emits
    d_logits = softmax − onehot directly — the training-path backward
    (_bwd_kernel) collapses into this launch, so the VJP never re-reads
    the logits or saves the lse residual. The dx BlockSpec maps phase 0
    onto column block 0: that window is rewritten by phase 1's j=0 step
    before any flush, so no garbage reaches HBM."""
    ph = pl.program_id(1)
    j = pl.program_id(2)
    nv = pl.num_programs(2)

    @pl.when((ph == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    s = x_ref[...].astype(jnp.float32)                    # (BT, BV)
    vpos = tile_positions(j, block_v, (block_t, block_v), 1)
    inb = bounds_mask(vpos, n_valid_v)
    tgt = tgt_ref[:, :1]                                  # (BT, 1) int32

    @pl.when(ph == 0)
    def _accumulate():
        sm = jnp.where(inb, s, _NEG_INF)                  # pad tiles
        m_new, l_new, _p, _corr = online_softmax_update(
            m_ref[:, :1], l_ref[:, :1], sm)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        hit = (vpos == tgt)
        t_ref[...] += jnp.broadcast_to(
            jnp.sum(jnp.where(hit, sm, 0.0), axis=-1, keepdims=True),
            t_ref.shape)

        @pl.when(j == nv - 1)
        def _finalize():
            lse = logsumexp_finalize(m_ref[:, :1], l_ref[:, :1])
            loss_ref[...] = jnp.broadcast_to(lse - t_ref[:, :1],
                                             loss_ref.shape)
            lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)

    @pl.when(ph == 1)
    def _grad():
        p = jnp.exp(s - lse_ref[:, :1])
        p = jnp.where(inb, p, 0.0)
        onehot = (vpos == tgt).astype(jnp.float32)
        dx_ref[...] = (p - onehot).astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_v",
                                             "interpret"))
def _ce_fused(logits2d, targets, block_t=128, block_v=512,
              interpret=False):
    """loss [T] f32 AND unit-cotangent d_logits [T, V] in one launch."""
    T, V = logits2d.shape
    x = _pad_dim(_pad_dim(logits2d, 0, block_t), 1, block_v)
    tg = _pad_dim(targets.astype(jnp.int32), 0, block_t, value=-1)
    tg = jnp.broadcast_to(tg[:, None], (x.shape[0], _LANES))
    grid = (x.shape[0] // block_t, 2, x.shape[1] // block_v)

    loss, dx = pl.pallas_call(
        functools.partial(_fused_kernel, block_t=block_t,
                          block_v=block_v, n_valid_v=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda i, p, j: (i, j)),
            pl.BlockSpec((block_t, _LANES), lambda i, p, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, _LANES), lambda i, p, j: (i, 0)),
            # phase 0 parks the window on column block 0; phase 1
            # rewrites it at j=0 before the first flush
            pl.BlockSpec((block_t, block_v), lambda i, p, j: (i, p * j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], _LANES), jnp.float32),
            jax.ShapeDtypeStruct(x.shape, logits2d.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_t, 128), jnp.float32),
                        pltpu.VMEM((block_t, 128), jnp.float32),
                        pltpu.VMEM((block_t, 128), jnp.float32),
                        pltpu.VMEM((block_t, 128), jnp.float32)],
        name="ce_fused",
        interpret=interpret,
    )(x, tg)
    return loss[:T, 0], dx[:T, :V]


@functools.partial(jax.jit, static_argnames=("block_t", "block_v",
                                             "interpret"))
def _ce_fwd(logits2d, targets, block_t=128, block_v=512, interpret=False):
    T, V = logits2d.shape
    x = _pad_dim(_pad_dim(logits2d, 0, block_t), 1, block_v)
    tg = _pad_dim(targets.astype(jnp.int32), 0, block_t, value=0)
    tg = jnp.broadcast_to(tg[:, None], (x.shape[0], _LANES))
    grid = (x.shape[0] // block_t, x.shape[1] // block_v)

    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_t=block_t, block_v=block_v,
                          n_valid_v=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_t, _LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, _LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], _LANES), jnp.float32),
            jax.ShapeDtypeStruct((x.shape[0], _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_t, 128), jnp.float32),
                        pltpu.VMEM((block_t, 128), jnp.float32),
                        pltpu.VMEM((block_t, 128), jnp.float32)],
        name="ce_fwd",
        interpret=interpret,
    )(x, tg)
    return loss[:T, 0], lse[:T, 0]


@functools.partial(jax.jit, static_argnames=("block_t", "block_v",
                                             "interpret"))
def _ce_bwd(logits2d, targets, lse, g, block_t=128, block_v=512,
            interpret=False):
    T, V = logits2d.shape
    x = _pad_dim(_pad_dim(logits2d, 0, block_t), 1, block_v)
    tg = _pad_dim(targets.astype(jnp.int32), 0, block_t, value=-1)
    tg = jnp.broadcast_to(tg[:, None], (x.shape[0], _LANES))
    # padded rows: lse=+inf makes p=0 so their dx is 0
    lse2 = _pad_dim(lse, 0, block_t, value=3.4e38)
    lse2 = jnp.broadcast_to(lse2[:, None], (x.shape[0], _LANES))
    g2 = jnp.broadcast_to(_pad_dim(g, 0, block_t)[:, None],
                          (x.shape[0], _LANES))
    grid = (x.shape[0] // block_t, x.shape[1] // block_v)

    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, block_t=block_t, block_v=block_v,
                          n_valid_v=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_t, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, _LANES), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, logits2d.dtype),
        name="ce_bwd",
        interpret=interpret,
    )(x, tg, lse2, g2)
    return dx[:T, :V]


# ------------------------------------------------------------- public entry
def _tuned_ce_blocks(logits2d):
    """(block_t, block_v) from the persistent autotune cache (populated by
    tools/autotune_kernels.py; key matches its `ce::T{T}_V{V}_{dtype}`),
    else the shipped 128/512 defaults."""
    from .autotune import cached
    sig = f"T{logits2d.shape[0]}_V{logits2d.shape[1]}_{logits2d.dtype}"
    return cached("ce", sig) or (128, 512)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def ce_with_logits(logits2d, targets, interpret=False):
    """Per-row cross entropy: [T, V] float, [T] int → [T] f32 loss."""
    bt, bv = _tuned_ce_blocks(logits2d)
    loss, _ = _ce_fwd(logits2d, targets, block_t=bt, block_v=bv,
                      interpret=interpret)
    return loss


def _ce_vjp_fwd(logits2d, targets, interpret=False):
    bt, bv = _tuned_ce_blocks(logits2d)
    loss, lse = _ce_fwd(logits2d, targets, block_t=bt, block_v=bv,
                        interpret=interpret)
    return loss, (logits2d, targets, lse)


def _ce_vjp_bwd(interpret, res, g):
    logits2d, targets, lse = res
    bt, bv = _tuned_ce_blocks(logits2d)
    dx = _ce_bwd(logits2d, targets, lse, g.astype(jnp.float32),
                 block_t=bt, block_v=bv, interpret=interpret)
    return dx, None


ce_with_logits.defvjp(_ce_vjp_fwd, _ce_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def ce_fused_train(logits2d, targets, interpret=False):
    """The training-path flavor: per-row loss whose VJP costs ~nothing —
    the ONE-PASS fused kernel (_ce_fused) already emitted d_logits with
    the loss, so backward is a cotangent scale instead of a second
    kernel re-reading the logits. Select it only where the grad is
    always taken (models/losses.CE_FUSED_GRAD): a primal-only call
    computes and discards the d_logits half."""
    bt, bv = _tuned_ce_blocks(logits2d)
    loss, _ = _ce_fused(logits2d, targets, block_t=bt, block_v=bv,
                        interpret=interpret)
    return loss


def _ce_fused_vjp_fwd(logits2d, targets, interpret=False):
    bt, bv = _tuned_ce_blocks(logits2d)
    loss, dx = _ce_fused(logits2d, targets, block_t=bt, block_v=bv,
                         interpret=interpret)
    return loss, (dx,)


def _ce_fused_vjp_bwd(interpret, res, g):
    (dx,) = res
    out = (dx.astype(jnp.float32)
           * g.astype(jnp.float32)[:, None]).astype(dx.dtype)
    return out, None


ce_fused_train.defvjp(_ce_fused_vjp_fwd, _ce_fused_vjp_bwd)


def suitable(logits_shape) -> bool:
    """The kernel pays off when the vocab axis is large; tiny vocabs stay
    on the jax path (padding waste dominates below one tile)."""
    return logits_shape[-1] >= 512
