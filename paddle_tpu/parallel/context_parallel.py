"""Context / sequence parallelism: ring attention and Ulysses all-to-all.

This capability is ABSENT in the reference snapshot (SURVEY.md §2.5: no
sequence_parallel/ring/ulysses anywhere in python/paddle) — it is designed
fresh for TPU:

- **Ring attention**: the sequence axis is sharded over a mesh axis; each
  step computes blockwise online-softmax attention against the currently
  held KV chunk, then rotates KV to the next device with
  `jax.lax.ppermute` (XLA collective-permute → ICI neighbor hops). HBM and
  VMEM hold only O(S/n) of K/V at any time, so context length scales with
  the ring size. The backward is a custom second ring pass that rotates
  (k, v, dk, dv) together so each chunk's gradient arrives back at its home
  device after a full cycle — no gather of the global sequence ever happens.

- **Ulysses**: `jax.lax.all_to_all` re-shards [B, S/n, H, D] → [B, S, H/n, D]
  (heads sharded instead of sequence), runs ordinary local flash attention,
  and transposes back. One all-to-all each way; good when H ≥ ring size.

Both run inside `jax.shard_map` over a named mesh axis and compose with the
dp/fsdp/mp axes of the same mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _chunk_update(carry, q, k, v, q_off, k_off, causal, kv_len=None):
    """One online-softmax update of (m, l, acc) against a KV chunk.

    q: [B,H,Sq,D] (f32, pre-scaled by 1/sqrt(D) at the call site),
    k/v: [B,H,Sc,D] (f32);
    q_off/k_off: global position offsets of the local chunks (traced ints).
    """
    m, l, acc = carry
    s = jnp.einsum("bhsd,bhtd->bhst", q, k)
    Sq, Sc = q.shape[2], k.shape[2]
    kpos = k_off + jnp.arange(Sc)[None, :]
    if kv_len is not None:
        s = jnp.where(kpos < kv_len, s, -jnp.inf)
    if causal:
        qpos = q_off + jnp.arange(Sq)[:, None]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    corr = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
    corr = jnp.where(jnp.isneginf(m), 0.0, corr)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("bhst,bhtd->bhsd", p, v)
    return (m_new, l_new, acc_new)


def _ring_fwd_local(q, k, v, axis_name, causal, kv_len=None):
    """Forward ring pass. q,k,v local [B,Sl,H,D] → (out local, lse [B,H,Sl])."""
    B, Sl, H, D = q.shape
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(D)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    q_off = idx * Sl
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        m, l, acc, k_cur, v_cur = carry
        src = (idx - s) % n            # home device of the chunk we hold
        carry2 = _chunk_update((m, l, acc), qt, k_cur, v_cur,
                               q_off, src * Sl, causal, kv_len)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (*carry2, k_nxt, v_nxt)

    # derive initial carries from the (device-varying) inputs so shard_map's
    # varying-manual-axes tracking matches the loop outputs
    m0 = jnp.full_like(qt[..., 0], -jnp.inf)
    l0 = jnp.zeros_like(qt[..., 0])
    acc0 = jnp.zeros_like(qt)
    m, l, acc, _, _ = jax.lax.fori_loop(
        0, n, step, (m0, l0, acc0, kt, vt))
    l_safe = jnp.maximum(l, 1e-37)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = jnp.where(jnp.isneginf(m), -jnp.inf, m + jnp.log(l_safe))
    return jnp.swapaxes(out, 1, 2), lse


def _ring_bwd_local(q, k, v, out, lse, do, axis_name, causal,
                    kv_len=None):
    """Backward ring pass; rotates (k, v, dk, dv) together so dk/dv land on
    their home device after the full cycle."""
    B, Sl, H, D = q.shape
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(D)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    ot = jnp.swapaxes(out, 1, 2).astype(jnp.float32)
    dot_ = jnp.swapaxes(do, 1, 2).astype(jnp.float32)
    delta = jnp.sum(dot_ * ot, axis=-1)                 # B,H,Sl
    q_off = idx * Sl
    q_pos = q_off + jnp.arange(Sl)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (idx - s) % n
        sc = jnp.einsum("bhsd,bhtd->bhst", qt, k_cur) * scale
        p = jnp.exp(sc - lse[..., None])
        kpos = src * Sl + jnp.arange(Sl)
        if kv_len is not None:
            p = jnp.where(kpos[None, :] < kv_len, p, 0.0)
        if causal:
            p = jnp.where(q_pos[:, None] >= kpos[None, :], p, 0.0)
        dv_cur = dv_cur + jnp.einsum("bhst,bhsd->bhtd", p, dot_)
        dp = jnp.einsum("bhsd,bhtd->bhst", dot_, v_cur)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhst,bhtd->bhsd", ds, k_cur)
        dk_cur = dk_cur + jnp.einsum("bhst,bhsd->bhtd", ds, qt)
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        return (dq, k_cur, v_cur, dk_cur, dv_cur)

    dq0 = jnp.zeros_like(qt)
    dkv0 = jnp.zeros_like(kt)
    dq, _, _, dk, dv = jax.lax.fori_loop(
        0, n, step, (dq0, kt, vt, dkv0, dkv0))
    return (jnp.swapaxes(dq, 1, 2).astype(q.dtype),
            jnp.swapaxes(dk, 1, 2).astype(k.dtype),
            jnp.swapaxes(dv, 1, 2).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention_local(q, k, v, axis_name, causal=False, kv_len=None):
    """Per-shard ring attention; call inside shard_map with the sequence axis
    sharded over `axis_name`. q,k,v local: [B, S_local, H, D]."""
    out, _ = _ring_fwd_local(q, k, v, axis_name, causal, kv_len)
    return out


def _ring_vjp_fwd(q, k, v, axis_name, causal, kv_len):
    out, lse = _ring_fwd_local(q, k, v, axis_name, causal, kv_len)
    return out, (q, k, v, out, lse)


def _ring_vjp_bwd(axis_name, causal, kv_len, res, do):
    q, k, v, out, lse = res
    return _ring_bwd_local(q, k, v, out, lse, do, axis_name, causal, kv_len)


ring_attention_local.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ulysses_attention_local(q, k, v, axis_name, causal=False, kv_len=None):
    """Per-shard Ulysses attention: all_to_all seq-shard → head-shard, local
    flash attention over the full sequence, all_to_all back.

    q,k,v local: [B, S/n, H, D]; requires H % n == 0."""
    B, Sl, H, D = q.shape
    n = jax.lax.psum(1, axis_name)

    def seq2head(x):
        # [B, Sl, H, D] → gather seq / scatter heads → [B, Sl*n, H/n, D]
        x = jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                               tiled=True)
        return x

    def head2seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    from ..kernels.flash_attention import _flash_mha
    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    out = _flash_mha(qh, kh, vh, causal, kv_len)
    return head2seq(out)


def _pad_seq(x, mult):
    pad = (-x.shape[1]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)])


def _cp_call(local_fn, q, k, v, mesh, axis, causal):
    """Shared wrapper: pad S to a multiple of the axis size, run the sharded
    local fn with kv_len masking, slice the padding back off."""
    n = mesh.shape[axis] if axis in mesh.axis_names else 1
    S = q.shape[1]
    qp, kp, vp = _pad_seq(q, n), _pad_seq(k, n), _pad_seq(v, n)
    kv_len = k.shape[1] if kp.shape[1] != k.shape[1] else None
    pspec = P(None, axis, None, None)
    fn = jax.shard_map(
        functools.partial(local_fn, axis_name=axis, causal=causal,
                          kv_len=kv_len),
        mesh=mesh, in_specs=(pspec, pspec, pspec), out_specs=pspec)
    out = fn(qp, kp, vp)
    return out[:, :S]


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal=False):
    """Array-level entry: q,k,v [B,S,H,D] with S sharded over `axis`;
    any sequence length (padded internally to the ring size)."""
    return _cp_call(ring_attention_local, q, k, v, mesh, axis, causal)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal=False):
    n = mesh.shape[axis] if axis in mesh.axis_names else 1
    H = q.shape[2]
    if H % max(n, 1) != 0:
        raise ValueError(
            f"ulysses_attention needs num_heads ({H}) to be a multiple of "
            f"the '{axis}' axis size ({n}); use ring attention for this "
            f"head count")
    return _cp_call(ulysses_attention_local, q, k, v, mesh, axis, causal)
