"""Hand-tiled Pallas TPU flash-attention forward kernel.

Reference analog: the external flash-attention CUDA library the reference
wires in via cmake/external/flashattn.cmake and exposes through
paddle/phi/kernels/gpu/flash_attn_kernel.cu. Here the kernel is written
TPU-first with Pallas: the score matmul and the PV matmul hit the MXU per
(block_q × block_k) tile, the online-softmax state (m, l, acc) lives in VMEM
scratch across the kv-block grid dimension, and HBM traffic is O(S·D) instead
of O(S²).

Layout convention matches the reference flash_attn API: [B, S, H, D].
The kernel internally works on [B*H, S, D].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .primitives import (NEG_INF as _NEG_INF,
                         ROW_SCALAR_LANES, bounds_mask, causal_block_live,
                         causal_mask, env_block as _env_block,
                         logsumexp_finalize, online_softmax_update,
                         pad_to, softmax_finalize, tile_positions)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, kv_len):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block (innermost: scratch carries over)
    nkv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        # operands stay in the input dtype (bf16 on the bench path) so
        # the MXU runs in its native mode; accumulation is f32 via
        # preferred_element_type, and the softmax scale is applied to the
        # f32 scores post-dot (exact, and off the matmul critical path)
        q = q_ref[0]                                        # (BQ, D)
        k = k_ref[0]                                        # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = tile_positions(j, block_k, (block_q, block_k), 1)
        valid = bounds_mask(kpos, kv_len)
        if causal:
            qpos = tile_positions(i, block_q, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, causal_mask(qpos, kpos))
        s = jnp.where(valid, s, _NEG_INF)

        m_new, l_new, p, corr = online_softmax_update(
            m_ref[:, :1], l_ref[:, :1], s)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip fully-masked kv blocks (upper-triangular block region)
        @pl.when(causal_block_live(i, j, block_q, block_k))
        def _():
            _body()
    else:
        _body()

    @pl.when(j == nkv - 1)
    def _finalize():
        o_ref[0] = softmax_finalize(acc_ref[...],
                                    l_ref[:, :1]).astype(o_ref.dtype)
        lse = logsumexp_finalize(m_ref[:, :1], l_ref[:, :1])
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])




def mha_fwd(q, k, v, causal=False, block_q=None, block_k=None,
            interpret=False, kv_len=None):
    """[B,S,H,D] → (out [B,S,H,D], lse [B,H,S]).  lse = m + log l, the
    softmax log-normalizer the jax-level flash backward recomputes p from.

    Thin non-jit wrapper: env block overrides resolve here so the jitted
    core's cache keys on the concrete block sizes."""
    bq = _env_block("PADDLE_TPU_FLASH_BLOCK_Q", 128) \
        if block_q is None else block_q
    bk = _env_block("PADDLE_TPU_FLASH_BLOCK_K", 128) \
        if block_k is None else block_k
    return _mha_fwd_jit(q, k, v, causal=causal, block_q=bq, block_k=bk,
                        interpret=interpret, kv_len=kv_len)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "kv_len"))
def _mha_fwd_jit(q, k, v, causal, block_q, block_k, interpret, kv_len):
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)

    # 128-aligned blocks: sublane/lane tiling is always legal and the
    # padding below absorbs any sequence length
    bq, bk = block_q, block_k
    q2 = pad_to(jnp.swapaxes(q, 1, 2).reshape(B * H, Sq, D), 1, bq)
    k2 = pad_to(jnp.swapaxes(k, 1, 2).reshape(B * H, Skv, D), 1, bk)
    v2 = pad_to(jnp.swapaxes(v, 1, 2).reshape(B * H, Skv, D), 1, bk)
    Sqp, Skp = q2.shape[1], k2.shape[1]
    grid = (B * H, Sqp // bq, Skp // bk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        kv_len=Skv if kv_len is None else min(int(kv_len), Skv))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, ROW_SCALAR_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sqp, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sqp, ROW_SCALAR_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),     # acc
            pltpu.VMEM((bq, 128), jnp.float32),   # m (lane-broadcast)
            pltpu.VMEM((bq, 128), jnp.float32),   # l
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q2, k2, v2)

    out = jnp.swapaxes(out[:, :Sq].reshape(B, H, Sq, D), 1, 2)
    lse = lse[:, :Sq, 0].reshape(B, H, Sq)
    return out, lse


def mha(q, k, v, causal=False, interpret=False):
    out, _ = mha_fwd(q, k, v, causal=causal, interpret=interpret)
    return out


# ---------------------------------------------------------------- backward
# Two-pass design (the standard TPU flash backward): a dq kernel iterating
# kv blocks innermost with dq accumulating in VMEM scratch, and a dk/dv
# kernel iterating q blocks innermost with dk/dv in scratch. p is rebuilt
# per tile from the saved log-normalizer (lse), so backward HBM traffic is
# O(S·D) like the forward. delta = rowsum(do ⊙ out) is computed at the jax
# level (one fused elementwise pass).

def _mask_p(p, i, j, block_q, block_k, kv_len, causal):
    kpos = tile_positions(j, block_k, p.shape, 1)
    valid = bounds_mask(kpos, kv_len)
    if causal:
        qpos = tile_positions(i, block_q, p.shape, 0)
        valid = jnp.logical_and(valid, causal_mask(qpos, kpos))
    return jnp.where(valid, p, 0.0)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, kv_len):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block (innermost: dq accumulates)
    nkv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body():
        # bf16 operands + f32 accumulation on every dot (MXU-native);
        # only the small elementwise ds/p math runs in f32 on the VPU
        q = q_ref[0]                                        # (BQ, D)
        k = k_ref[0]                                        # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, :, :1])
        p = _mask_p(p, i, j, block_q, block_k, kv_len, causal)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1])
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(causal_block_live(i, j, block_q, block_k))
        def _():
            _body()
    else:
        _body()

    @pl.when(j == nkv - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k, kv_len):
    j = pl.program_id(1)          # kv block
    i = pl.program_id(2)          # q block (innermost: dk/dv accumulate)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body():
        q = q_ref[0]                                        # (BQ, D)
        k = k_ref[0]                                        # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, :, :1])                  # (BQ, BK)
        p = _mask_p(p, i, j, block_q, block_k, kv_len, causal)
        do = do_ref[0]                                      # (BQ, D)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1])                 # (BQ, BK)
        # dk = scale · dsᵀ·q — scale folded in at finalize (f32, exact)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(causal_block_live(i, j, block_q, block_k))
        def _():
            _body()
    else:
        _body()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def mha_bwd(q, k, v, out, lse, do, causal=False, block_q=None, block_k=None,
            interpret=False, kv_len=None):
    """Flash-attention backward. q/k/v/out/do [B,S,H,D], lse [B,H,S] from
    mha_fwd → (dq, dk, dv) in the input dtypes. Env blocks resolve here,
    outside the jitted core (see _env_block)."""
    bq = _env_block("PADDLE_TPU_FLASH_BLOCK_BWD_Q", 128) \
        if block_q is None else block_q
    bk = _env_block("PADDLE_TPU_FLASH_BLOCK_BWD_K", 128) \
        if block_k is None else block_k
    return _mha_bwd_jit(q, k, v, out, lse, do, causal=causal, block_q=bq,
                        block_k=bk, interpret=interpret, kv_len=kv_len)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "kv_len"))
def _mha_bwd_jit(q, k, v, out, lse, do, causal, block_q, block_k,
                 interpret, kv_len):
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    bq, bk = block_q, block_k

    q2 = pad_to(jnp.swapaxes(q, 1, 2).reshape(B * H, Sq, D), 1, bq)
    do2 = pad_to(jnp.swapaxes(do, 1, 2).reshape(B * H, Sq, D), 1, bq)
    k2 = pad_to(jnp.swapaxes(k, 1, 2).reshape(B * H, Skv, D), 1, bk)
    v2 = pad_to(jnp.swapaxes(v, 1, 2).reshape(B * H, Skv, D), 1, bk)
    # delta = rowsum(do ⊙ out): one fused elementwise+reduce pass in XLA
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    delta = jnp.swapaxes(delta, 1, 2).reshape(B * H, Sq)  # via [B,S,H]->[B,H,S]
    # lse pad must kill padded q rows' p (exp(s - BIG) = 0) so they don't
    # pollute dk/dv; delta pad value is then irrelevant (ds = p * (...) = 0)
    lse2 = pad_to(lse.reshape(B * H, Sq, 1), 1, bq)
    lse2 = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, lse2.shape, 1) < Sq,
        lse2, jnp.float32(1e30))
    lse2 = jnp.broadcast_to(lse2, (B * H, lse2.shape[1], ROW_SCALAR_LANES))
    delta2 = jnp.broadcast_to(
        pad_to(delta.reshape(B * H, Sq, 1), 1, bq),
        (B * H, lse2.shape[1], ROW_SCALAR_LANES))

    Sqp, Skp = q2.shape[1], k2.shape[1]
    klen = Skv if kv_len is None else min(int(kv_len), Skv)

    common = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  kv_len=klen)
    in_arrs = (q2, k2, v2, do2, lse2, delta2)

    def _qspec(ix):
        return pl.BlockSpec((1, bq, D), ix)

    def _kspec(ix):
        return pl.BlockSpec((1, bk, D), ix)

    def _lspec(ix):
        return pl.BlockSpec((1, bq, ROW_SCALAR_LANES), ix)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(B * H, Sqp // bq, Skp // bk),
        in_specs=[
            _qspec(lambda b, i, j: (b, i, 0)),
            _kspec(lambda b, i, j: (b, j, 0)),
            _kspec(lambda b, i, j: (b, j, 0)),
            _qspec(lambda b, i, j: (b, i, 0)),
            _lspec(lambda b, i, j: (b, i, 0)),
            _lspec(lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(*in_arrs)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(B * H, Skp // bk, Sqp // bq),
        in_specs=[
            _qspec(lambda b, j, i: (b, i, 0)),
            _kspec(lambda b, j, i: (b, j, 0)),
            _kspec(lambda b, j, i: (b, j, 0)),
            _qspec(lambda b, j, i: (b, i, 0)),
            _lspec(lambda b, j, i: (b, i, 0)),
            _lspec(lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Skp, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Skp, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(*in_arrs)

    dq = jnp.swapaxes(dq[:, :Sq].reshape(B, H, Sq, D), 1, 2)
    dk = jnp.swapaxes(dk[:, :Skv].reshape(B, H, Skv, D), 1, 2)
    dv = jnp.swapaxes(dv[:, :Skv].reshape(B, H, Skv, D), 1, 2)
    return dq, dk, dv
