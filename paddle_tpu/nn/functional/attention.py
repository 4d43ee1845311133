"""Attention functionals.

Reference analog: python/paddle/nn/functional/flash_attention.py:125 and the
fused_attention CUDA ops (/root/reference/paddle/fluid/operators/fused/
fused_attention_op.cu). TPU-native: one fused jax op body that XLA maps onto
the MXU; the Pallas flash-attention kernel (paddle_tpu.kernels) plugs in
underneath `flash_attention` for long sequences.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ...framework.dispatch import defop
from ...framework.tensor import Tensor
from ...framework.random import next_key


@defop("sdpa_op")
def _sdpa(q, k, v, mask, key, dropout_p, causal, training, scale):
    # q,k,v: [B, S, H, D] (paddle flash-attn layout)
    qt = jnp.swapaxes(q, 1, 2)  # B,H,S,D
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhsd,bhtd->bhst", qt, kt) * scale
    scores = scores.astype(jnp.float32)
    if causal:
        s, t = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((s, t), bool))
        scores = jnp.where(cm, scores, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -jnp.inf)
        else:
            scores = scores + mask.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        keep = 1.0 - dropout_p
        dmask = jax.random.bernoulli(key, keep, probs.shape)
        probs = jnp.where(dmask, probs / keep, 0.0).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # B,S,H,D


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    return _sdpa(query, key, value, attn_mask, next_key(), float(dropout_p),
                 bool(is_causal), bool(training), None)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """paddle.nn.functional.flash_attention analog.

    Dispatches to the Pallas TPU kernel for the no-dropout fast path
    (paddle_tpu/kernels/flash_attention.py); falls back to the fused XLA
    body otherwise.
    """
    from ...kernels import flash_attention as fa_kernel
    if fa_kernel.available() and dropout == 0.0 and not return_softmax:
        out = fa_kernel.flash_attention(query, key, value, causal=causal)
        if return_softmax:
            return out, None
        return out, None
    out = _sdpa(query, key, value, None, next_key(), float(dropout),
                bool(causal), bool(training), None)
    return out, None


# dense varlen is only used when the probs matrix must exist anyway
# (dropout / return_softmax) or the packing is small enough that the
# [H, total_q, total_k] buffer is cheaper than a scan — the threshold is
# on that buffer's ELEMENT count so head count is priced in
_VARLEN_DENSE_MAX = 16 * 1024 * 1024   # H * total_q * total_k
_VARLEN_BLOCK_KV = 512


def _varlen_impl(n_elements: int) -> str:
    """'blockwise' | 'dense' for a packing whose probs buffer would hold
    n_elements (= H * total_q * total_k): dense only while that buffer
    stays under the memory guard."""
    return "blockwise" if n_elements > _VARLEN_DENSE_MAX else "dense"


def _varlen_segments(cu, total):
    """Segment id and within-segment position for each packed row."""
    cu = cu.astype(jnp.int32)
    seg = jnp.searchsorted(cu, jnp.arange(total), side="right") - 1
    pos = jnp.arange(total) - cu[seg]
    return seg, pos


def _varlen_blockwise(q, k, v, seg_q, pos_q, seg_k, pos_k, scale, causal):
    """Online-softmax over KV blocks for the packed form: memory is
    O(H * total_q * block) instead of the dense O(H * total_q * total_k)
    — the varlen analog of kernels.flash_attention._blockwise_attention_lse
    with the block-diagonal segment mask folded into each block."""
    total_q, H, D = q.shape
    total_k = k.shape[0]
    blk = min(_VARLEN_BLOCK_KV, total_k)
    pad = (-total_k) % blk
    if pad:
        k = jnp.concatenate([k, jnp.zeros((pad, H, D), k.dtype)], 0)
        v = jnp.concatenate([v, jnp.zeros((pad, H, D), v.dtype)], 0)
        # padding rows get segment -1: never equal to any real seg_q >= 0
        seg_k = jnp.concatenate(
            [seg_k, jnp.full((pad,), -1, seg_k.dtype)], 0)
        pos_k = jnp.concatenate([pos_k, jnp.zeros((pad,), pos_k.dtype)], 0)
    nblk = (total_k + pad) // blk
    kb = k.reshape(nblk, blk, H, D)
    vb = v.reshape(nblk, blk, H, D)
    sb = seg_k.reshape(nblk, blk)
    pb = pos_k.reshape(nblk, blk)

    def step(carry, inputs):
        m, l, acc = carry
        kblk, vblk, segs, poss = inputs
        scores = jnp.einsum("qhd,khd->hqk", q, kblk,
                            preferred_element_type=jnp.float32) * scale
        valid = seg_q[:, None] == segs[None, :]
        if causal:
            valid = jnp.logical_and(valid,
                                    pos_q[:, None] >= poss[None, :])
        scores = jnp.where(valid[None], scores, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(jnp.isneginf(scores), 0.0, p)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
        corr = jnp.where(jnp.isneginf(m), 0.0, corr)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "hqk,khd->hqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((H, total_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((H, total_q), jnp.float32)
    acc0 = jnp.zeros((H, total_q, D), jnp.float32)
    # reverse-mode AD over a plain scan saves every block's residuals
    # (p, scores: O(H·total_q·blk) EACH, × nblk = the dense blowup this
    # path exists to avoid); checkpointing the body stores only the
    # (m, l, acc) carry per block and rebuilds p in the backward — the
    # same recompute trade the flash backward makes
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, acc0),
                                  (kb, vb, sb, pb))
    # rows whose segment has zero kv tokens stay all-masked: l == 0 → 0
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return jnp.swapaxes(out, 0, 1).astype(q.dtype)   # [total_q, H, D]


@defop("flash_attn_unpadded_op")
def _flash_attn_unpadded(q, k, v, cu_q, cu_k, key, scale, dropout_p,
                         causal, training, want_softmax):
    # packed varlen: q/k/v [total, H, D]; cu_* [B+1] cumulative lengths.
    # TPU-native form: segment ids from searchsorted give a static-shape
    # block-diagonal mask — the data-dependent raggedness lives in the
    # mask VALUES, not the shapes, so one compiled graph serves every
    # packing (XLA requires static shapes; a CUDA varlen kernel indexes
    # ragged rows instead).
    total_q, total_k = q.shape[0], k.shape[0]
    seg_q, pos_q = _varlen_segments(cu_q, total_q)
    seg_k, pos_k = _varlen_segments(cu_k, total_k)
    dense_needed = want_softmax or (dropout_p > 0.0 and training)
    if (not dense_needed
            and _varlen_impl(q.shape[1] * total_q * total_k)
            == "blockwise"):
        return _varlen_blockwise(q, k, v, seg_q, pos_q, seg_k, pos_k,
                                 scale, causal)
    valid = seg_q[:, None] == seg_k[None, :]
    if causal:
        valid = jnp.logical_and(valid, pos_q[:, None] >= pos_k[None, :])
    scores = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(valid[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows whose segment has zero kv tokens: all-masked → force 0
    probs = jnp.where(valid[None], probs, 0.0).astype(q.dtype)
    if dropout_p > 0.0 and training:
        keep = 1.0 - dropout_p
        dmask = jax.random.bernoulli(key, keep, probs.shape)
        probs = jnp.where(dmask, probs / keep, 0.0).astype(q.dtype)
    out = jnp.einsum("hqk,khd->qhd", probs, v.astype(probs.dtype))
    out = out.astype(q.dtype)
    # want_softmax is a static (literal-baked) arg: the O(H*total^2)
    # probs buffer is only a compiled output when asked for — returned
    # op outputs can't be DCE'd by XLA
    return (out, probs) if want_softmax else out


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen (packed, unpadded) attention: query/key/value
    [total_seq_len, num_heads, head_dim] with cu_seqlens_* [batch+1]
    boundaries; returns the packed [total_seq_len, num_heads, head_dim]
    output (reference flash_attention.py:269). Sequences attend only
    within their own segment.

    Large packings run the blockwise online-softmax path (O(total*block)
    memory, flash-style); the dense O(total^2) scores buffer is built
    only for small inputs or when dropout / return_softmax force the
    full probs matrix to exist."""
    args = (query, key, value, cu_seqlens_q, cu_seqlens_k, next_key(),
            float(scale), float(dropout), bool(causal), bool(training))
    if return_softmax:
        return _flash_attn_unpadded(*args, True)
    return _flash_attn_unpadded(*args, False), None


@defop("memory_efficient_attention_op")
def _mea(q, k, v, bias, scale, causal):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = (jnp.einsum("bhsd,bhtd->bhst", qt, kt) * scale).astype(jnp.float32)
    if causal:
        s, t = scores.shape[-2], scores.shape[-1]
        scores = jnp.where(jnp.tril(jnp.ones((s, t), bool)), scores, -jnp.inf)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def memory_efficient_attention(query, key, value, attn_bias=None, p=0.0,
                               scale=None, training=True):
    """reference: python/paddle/incubate/nn/memory_efficient_attention.py"""
    return _mea(query, key, value, attn_bias,
                None if scale is None else float(scale), False)


@defop("sparse_attention_op")
def _sparse_attention(q, k, v, offset, columns, kp_mask, attn_mask):
    # q/k/v [B, H, S, D]; offset [B, H, S+1] CSR row starts; columns
    # [B, H, nnz] allowed column ids. TPU-native: the CSR layout
    # scatters into a static [S, S] boolean mask per (b, h) — ragged
    # row lengths live in mask VALUES, keeping shapes static for XLA —
    # then one masked-softmax attention body runs on the MXU.
    B, H, S, D = q.shape
    nnz = columns.shape[-1]
    offset = offset.astype(jnp.int32).reshape(B * H, S + 1)
    columns = columns.astype(jnp.int32).reshape(B * H, nnz)

    def one_mask(off, cols):
        row = jnp.searchsorted(off, jnp.arange(nnz), side="right") - 1
        live = jnp.arange(nnz) < off[-1]       # entries past nnz tail
        row = jnp.clip(row, 0, S - 1)
        m = jnp.zeros((S, S), bool)
        return m.at[row, cols].max(live)

    mask = jax.vmap(one_mask)(offset, columns).reshape(B, H, S, S)
    scale = 1.0 / math.sqrt(D)
    # accumulate in the input precision when it exceeds f32 (the
    # reference supports float64); otherwise f32
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    scores = jnp.einsum("bhsd,bhtd->bhst", q.astype(acc_dt),
                        k.astype(acc_dt)) * scale
    if kp_mask is not None:
        # [B, S] key-padding mask, 0 = masked (reference contract)
        mask = jnp.logical_and(mask,
                               (kp_mask != 0)[:, None, None, :])
    if attn_mask is not None:
        # [S, S], 0 = masked
        mask = jnp.logical_and(mask, (attn_mask != 0)[None, None])
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(mask, probs, 0.0)        # all-masked rows → 0
    return jnp.einsum("bhst,bhtd->bhsd", probs,
                      v.astype(acc_dt)).astype(q.dtype)


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """CSR block-sparse attention (reference
    python/paddle/nn/functional/sparse_attention.py:19): each query row
    attends only to its CSR row's columns.

    Correct-but-dense fallback: the CSR pattern is scattered into a full
    [B, H, S, S] mask and scores are computed densely, so compute/memory
    are O(S^2) regardless of sparsity — fine for the reference's
    moderate S, not a long-context kernel (use flash/splash paths for
    that)."""
    return _sparse_attention(query, key, value, sparse_csr_offset,
                             sparse_csr_columns, key_padding_mask,
                             attn_mask)
