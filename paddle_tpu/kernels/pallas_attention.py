"""Hand-tiled Pallas TPU flash-attention kernels: the 128x128 forward /
dq / dkv kernels over [B*H, S, D] (on no path of the TPU's; ROADMAP D2),
and below them the tiled pair the training attention runs (`tiled_mha`).

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


# ------------------------------------------------------------- tiled flash
# The training attention (kernels/flash_attention._tiled_engages): one
# grid step owns a whole sequence of one 128-lane group of heads — the
# [B,S,H,D] arrays are read as [B,S,H*D] with no layout swap, two 64-wide
# heads (or one 128-wide) a lane group — and walks its (tile x tile) score
# tiles in loops INSIDE the kernel: causal tiles above the diagonal cost
# neither a grid step nor a DMA, and only the diagonal tile is masked.
# A head is picked out of its lane group by zeroing the other head's
# lanes of one matmul operand: the contraction then runs over all 128
# lanes (what the MXU does with a 64-deep one anyway) and adds exact
# zeros. Arithmetic as in the 128x128 kernels above: operands in the
# input dtype, f32 accumulation on every dot, f32 max / sum / lse, p and
# ds rounded only as dot operands, the scale applied to f32 scores.
LANES = 128
TILED_VMEM_BUDGET = 48 * 2 ** 20      # of a v5e core's 128 MiB of VMEM
_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_TN = (((0,), (0,)), ((), ()))        # a.T @ b


def tiled_vmem_bytes(S: int, tile: int, itemsize: int) -> int:
    """What the backward (the larger of the two kernels) holds in VMEM:
    four inputs and three outputs of [S, 128] double-buffered, the f32 dq
    accumulator, the lane-padded lse / delta rows, and a dozen (tile x
    tile) f32 temporaries of two heads in flight."""
    io = 2 * 7 * S * LANES * itemsize
    rows = 2 * 2 * 8 * S * 4
    return io + S * LANES * 4 + rows + 12 * tile * tile * 4


def tiled_tile(S: int, D: int, dtype) -> int | None:
    """The tile edge for a self-attention of length S, or None where the
    tiled kernels do not apply. One rule for every model: the largest of
    512 / 256 / 128 that divides S (a grid step needs hundreds of rows
    of work per matmul, and a (512 x 512) f32 score tile is 1 MB), as long
    as a whole sequence of one lane group fits the VMEM budget."""
    if D not in (64, 128) or jnp.dtype(dtype) not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    for tile in (512, 256, 128):
        if S % tile == 0:
            fits = tiled_vmem_bytes(S, tile, jnp.dtype(dtype).itemsize) \
                <= TILED_VMEM_BUDGET
            return tile if fits else None
    return None


def _by_head(vals, lane, D):
    """[rows, 1] per-head columns (or [rows, 128] per-head products) ->
    one [rows, 128] array that holds head h's value on head h's lanes."""
    out = vals[-1]
    for h in range(len(vals) - 2, -1, -1):
        out = jnp.where(lane < (h + 1) * D, vals[h], out)
    return out


def _head_operands(x, lane, D):
    """x [rows, 128] -> one copy a head with the other heads' lanes 0."""
    hp = LANES // D
    if hp == 1:
        return [x]
    return [jnp.where((lane >= h * D) & (lane < (h + 1) * D), x,
                      jnp.zeros_like(x)) for h in range(hp)]


def _tiled_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *o32_ref, scale,
                      causal, tile, D):
    S, t, hp = q_ref.shape[1], tile, LANES // D
    n = S // t
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, LANES), 1)
    lane8 = jax.lax.broadcasted_iota(jnp.int32, (t, ROW_SCALAR_LANES), 1)
    if causal:      # the diagonal tile's mask, [queries, keys]
        on_or_below = causal_mask(
            jax.lax.broadcasted_iota(jnp.int32, (t, t), 0),
            jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))

    def q_tile(qi, _):
        rows = pl.ds(pl.multiple_of(qi * t, t), t)
        qh = _head_operands(q_ref[0, rows, :], lane, D)

        def kv_tile(kj, carry, diagonal):
            m, l, acc = carry
            cols = pl.ds(pl.multiple_of(kj * t, t), t)
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            m_new, l_new, pv, corr = [], [], [], []
            for h in range(hp):
                s = jax.lax.dot_general(
                    qh[h], k, _NT, preferred_element_type=jnp.float32) * scale
                if diagonal:
                    s = jnp.where(on_or_below, s, _NEG_INF)
                mh, lh, p, ch = online_softmax_update(m[h], l[h], s)
                m_new.append(mh)
                l_new.append(lh)
                corr.append(ch)
                pv.append(jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            acc = acc * _by_head(corr, lane, D) + _by_head(pv, lane, D)
            return tuple(m_new), tuple(l_new), acc

        carry = (tuple(jnp.full((t, 1), _NEG_INF, jnp.float32)
                       for _ in range(hp)),
                 tuple(jnp.zeros((t, 1), jnp.float32) for _ in range(hp)),
                 jnp.zeros((t, LANES), jnp.float32))
        carry = jax.lax.fori_loop(
            0, qi if causal else n,
            lambda kj, c: kv_tile(kj, c, False), carry)
        if causal:
            carry = kv_tile(qi, carry, True)
        m, l, acc = carry
        out = softmax_finalize(acc, _by_head(l, lane, D))
        o_ref[0, rows, :] = out.astype(o_ref.dtype)
        if o32_ref:
            o32_ref[0][0, rows, :] = out
        lse = [logsumexp_finalize(m[h], l[h]) for h in range(hp)]
        lse_ref[0, 0, rows, :] = jnp.broadcast_to(
            _by_head(lse, lane8, 1), lane8.shape)

    jax.lax.fori_loop(0, n, q_tile, None)


def _tiled_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *, scale, causal,
                      tile, D):
    """One fused pass: a (k tile, q tile) pair makes its scores once, in
    the transposed orientation [keys, queries] — lse and delta are then
    rows, dk and dv plain dots, and only dq's dot contracts dim 0."""
    S, t, hp = q_ref.shape[1], tile, LANES // D
    n = S // t
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, LANES), 1)
    if causal:      # the diagonal tile's mask, [keys, queries]
        on_or_below = causal_mask(
            jax.lax.broadcasted_iota(jnp.int32, (t, t), 1),
            jax.lax.broadcasted_iota(jnp.int32, (t, t), 0))
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def kv_tile(kj, _):
        cols = pl.ds(pl.multiple_of(kj * t, t), t)
        kh = _head_operands(k_ref[0, cols, :], lane, D)
        vh = _head_operands(v_ref[0, cols, :], lane, D)

        def q_tile(qi, carry, diagonal):
            dk, dv = carry
            rows = pl.ds(pl.multiple_of(qi * t, t), t)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            dq, dkh, dvh = None, [], []
            for h in range(hp):
                s = jax.lax.dot_general(
                    kh[h], q, _NT, preferred_element_type=jnp.float32) * scale
                p = jnp.exp(s - lse_ref[0, 0, qi, h:h + 1, :])
                if diagonal:
                    p = jnp.where(on_or_below, p, 0.0)
                dp = jax.lax.dot_general(
                    vh[h], do, _NT, preferred_element_type=jnp.float32)
                ds = (p * (dp - delta_ref[0, 0, qi, h:h + 1, :])
                      ).astype(q.dtype)
                dvh.append(jax.lax.dot_general(
                    p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                dkh.append(jax.lax.dot_general(
                    ds, q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                # kh[h] is 0 off head h's lanes, so the heads' dq add up
                dqh = jax.lax.dot_general(
                    ds, kh[h], _TN, preferred_element_type=jnp.float32)
                dq = dqh if dq is None else dq + dqh
            dq_acc[rows, :] += dq
            return dk + _by_head(dkh, lane, D), dv + _by_head(dvh, lane, D)

        carry = (jnp.zeros((t, LANES), jnp.float32),) * 2
        if causal:
            carry = q_tile(kj, carry, True)
        carry = jax.lax.fori_loop(
            kj + 1 if causal else 0, n,
            lambda qi, c: q_tile(qi, c, False), carry)
        dk, dv = carry
        dk_ref[0, cols, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)

    jax.lax.fori_loop(0, n, kv_tile, None)
    dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _tiled_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=TILED_VMEM_BUDGET + 16 * 2 ** 20)


@functools.partial(jax.jit, static_argnames=("causal", "tile", "interpret",
                                             "unrounded"))
def tiled_mha_fwd(q, k, v, causal=False, tile=None, interpret=False,
                  unrounded=False):
    """[B,S,H,D] self-attention -> (out [B,S,H,D], lse [B,G,S,8]): lane h
    of lse is head h of lane group g (G = H*D/128 groups). `unrounded`
    adds out as the kernel had it, in float32, for the backward's delta
    (see tiled_mha_bwd)."""
    B, S, H, D = q.shape
    tile = tile or tiled_tile(S, D, q.dtype)
    G = H * D // LANES
    seq = pl.BlockSpec((1, S, LANES), lambda b, g: (b, 0, g))
    outs = pl.pallas_call(
        functools.partial(_tiled_fwd_kernel, scale=1.0 / math.sqrt(D),
                          causal=causal, tile=tile, D=D),
        grid=(B, G),
        in_specs=[seq, seq, seq],
        out_specs=[seq, pl.BlockSpec((1, 1, S, ROW_SCALAR_LANES),
                                     lambda b, g: (b, g, 0, 0))]
        + [seq] * unrounded,
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
            jax.ShapeDtypeStruct((B, G, S, ROW_SCALAR_LANES), jnp.float32)]
        + [jax.ShapeDtypeStruct((B, S, H * D), jnp.float32)] * unrounded,
        compiler_params=_tiled_params(),
        name="flash_tiled_fwd",
        interpret=interpret,
    )(*(x.reshape(B, S, H * D) for x in (q, k, v)))
    return tuple(x.reshape(B, S, H, D) if x.ndim == 3 else x for x in outs)


@functools.partial(jax.jit, static_argnames=("causal", "tile", "interpret"))
def tiled_mha_bwd(q, k, v, out, lse, do, causal=False, tile=None,
                  interpret=False):
    """(dq, dk, dv) of tiled_mha_fwd, from its (unrounded) out and lse."""
    B, S, H, D = q.shape
    tile = tile or tiled_tile(S, D, q.dtype)
    G, hp, n = H * D // LANES, LANES // D, S // tile
    # delta = rowsum(do * out) has to cancel rowsum(p * dp) as the kernel
    # makes it: what is left a query, (do' - do) . out' - do . (out' - out)
    # for any other do' and out', stays in the key's gradient where its
    # rows cancel exactly, and the key bias — whose gradient is that sum
    # and nothing else — walks under Adam. So out comes UNROUNDED from the
    # forward kernel, and do is pinned to what the kernel reads: XLA
    # otherwise hands this sum the cotangent as its producer had it in
    # float32 (excess precision across the bf16 cast). Chip runs, PR 36,
    # the key bias's gradient in the train cell and `update_norm_gap`
    # (limit 0.1): both 1.2e-6 and 0.015, as on the blockwise path (where
    # XLA keeps its own out in float32); rounded out 5.9e-6 and 0.095; no
    # barrier 4.9e-6 and 0.092; neither 7.2e-6 and 0.113
    do = jax.lax.optimization_barrier(do)
    # the per-row statistics as ROWS of a q tile, [B, G, n, 8, tile]
    # (sublane h = head h of the group): two small XLA passes
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    delta = jnp.moveaxis(delta.reshape(B, n, tile, G, hp), (3, 4), (1, 3))
    delta = pad_to(delta, 3, ROW_SCALAR_LANES)
    lse = jnp.swapaxes(lse.reshape(B, G, n, tile, ROW_SCALAR_LANES), 3, 4)
    seq = pl.BlockSpec((1, S, LANES), lambda b, g: (b, 0, g))
    row = pl.BlockSpec((1, 1, n, ROW_SCALAR_LANES, tile),
                       lambda b, g: (b, g, 0, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_tiled_bwd_kernel, scale=1.0 / math.sqrt(D),
                          causal=causal, tile=tile, D=D),
        grid=(B, G),
        in_specs=[seq, seq, seq, seq, row, row],
        out_specs=[seq, seq, seq],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * D), x.dtype)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((S, LANES), jnp.float32)],
        compiler_params=_tiled_params(),
        name="flash_tiled_bwd",
        interpret=interpret,
    )(*(x.reshape(B, S, H * D) for x in (q, k, v, do)), lse, delta)
    return tuple(x.reshape(B, S, H, D) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def tiled_mha(q, k, v, causal=False, interpret=False):
    """[B,S,H,D] self-attention on the tiled kernels, with their backward."""
    return tiled_mha_fwd(q, k, v, causal=causal, interpret=interpret)[0]


def _tiled_mha_fwd_rule(q, k, v, causal, interpret):
    out, lse, out32 = tiled_mha_fwd(q, k, v, causal=causal,
                                    interpret=interpret, unrounded=True)
    return out, (q, k, v, out32, lse)


def _tiled_mha_bwd_rule(causal, interpret, res, do):
    return tiled_mha_bwd(*res, do, causal=causal, interpret=interpret)


tiled_mha.defvjp(_tiled_mha_fwd_rule, _tiled_mha_bwd_rule)
