"""The decode tick's ABSORBED latent attention (multi-head latent
attention, MLA: models/joyai_llm_flash.py) over the live blocks of the
latent pools.

Reference analog: the masked single-step branch of the
FusedMultiTransformer decode attention
(paddle/fluid/operators/fused/fused_multi_transformer_op.cu:29), which
walks one cache up to the step's own length; here the cache is the
attention's latent — `ckv` [L, B, S, C], which is BOTH the key and the
value of the absorbed form, and `kpe` [L, B, S, R], the one rotated key
all heads share — so a position has no head axis and H heads meet one
key.

The einsum form (`joyai_llm_flash._masked_einsums`) reads every position of
every slot and reads `ckv` twice, once for the scores and once for the
output. The kernel here walks `decode_attention.work_list` — per live row
the blocks of LATENT_BLOCK positions it has written, nothing for an idle
row — and computes scores, running softmax and output from the ONE copy
of each block it fetched: a live `ckv` block leaves HBM once a layer.
The heads are the rows of two MXU dots a block (scores `q . ckv^T`,
output `p . ckv`), where the GPT kernel
(`decode_attention.length_aware_attention`) reduces per head on the VPU:
the two share the work list and nothing else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .decode_attention import work_list   # imports Pallas, bytecode kept
from .primitives import NEG_INF

from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402

__all__ = ["LATENT_BLOCK", "absorbed_engages", "live_latent_plan",
           "absorbed_attention_live_blocks"]

# Positions a block of the kernel holds: what one DMA fetches, and the
# grain a slot's read is rounded up to. A context here is thousands of
# positions; docs/kernel_selection.md has the sizes timed on the chip.
LATENT_BLOCK = 1024


def absorbed_engages(T: int, ckv) -> bool:
    """Whether a call on the stacked latent pool `ckv` [L, B, S, C] takes
    the kernel — by what the call can observe, no option (the rule of
    `decode_attention.length_aware`): one query token a row, a TPU, no
    ambient mesh of several devices, and a pool the kernel's tiles fit:
    S a whole number of blocks and whole lanes of C."""
    from ..device import is_tpu
    from ..parallel.mesh import get_mesh
    mesh = get_mesh()
    S, C = ckv.shape[2:]
    return (T == 1 and is_tpu() and (mesh is None or mesh.size == 1)
            and S % LATENT_BLOCK == 0 and C % 128 == 0)


def live_latent_plan(T: int, ckv, pos, live=None):
    """The kernel's work list, made ONCE ahead of the layer scans, where
    the step's attention is the kernel (`absorbed_engages`), else None —
    the einsums. `live` [B, T] is the forward's mask of real tokens."""
    if not absorbed_engages(T, ckv):
        return None
    return work_list(pos, None if live is None else live[:, 0],
                     ckv.shape[1], ckv.shape[2], LATENT_BLOCK)


def _kernel(layer_ref, len_ref, slot_ref, blk_ref, total_ref,
            ql_ref, qp_ref, ckv_hbm, kpe_hbm, o_ref, cbuf, kbuf, sem,
            m_ref, l_ref, acc_ref, *, scale: float):
    """One pass over the work list (slot_ref[t], blk_ref[t]), t < total.
    Block t+1's `ckv` [block, C] and `kpe` [R, block] are in flight from
    the pools in HBM while block t is computed (two buffers); a row's
    running max, sum and latent output sit in scratch from its first
    block to its last, which writes the row out. Rows with nothing live
    keep the zeros written first."""
    layer, total = layer_ref[0], total_ref[0]
    block = cbuf.shape[1]

    def fetch(t, buf):
        b = slot_ref[t]
        at = pl.ds(pl.multiple_of(blk_ref[t] * block, block), block)
        return (pltpu.make_async_copy(ckv_hbm.at[layer, b, at],
                                      cbuf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(kpe_hbm.at[layer, b, :, at],
                                      kbuf.at[buf], sem.at[1, buf]))

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(total > 0)
    def _():
        for c in fetch(0, 0):
            c.start()

    def step(t, _):
        buf = t % 2
        b, j = slot_ref[t], blk_ref[t]
        n, first = len_ref[b], j * block

        @pl.when(t + 1 < total)
        def _():
            for c in fetch(t + 1, 1 - buf):
                c.start()

        for c in fetch(t, buf):
            c.wait()

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # what lies past the row's length is another request's, or
        # nothing yet: as a value it must not reach the output even as
        # 0 * nan (as a key the mask below replaces its score)
        @pl.when(first + block > n)
        def _():
            rows = first + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            cbuf[buf] = jnp.where(rows < n, cbuf[buf], 0)

        ckv = cbuf[buf]                                   # [block, C]
        s = jax.lax.dot_general(
            ql_ref[b], ckv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + jnp.dot(
            qp_ref[b], kbuf[buf], preferred_element_type=jnp.float32)
        seen = first + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1) < n
        s = jnp.where(seen, s / scale, NEG_INF)           # [H, block]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        shrink = jnp.exp(m_prev - m_new)                  # [H, 1]
        l_ref[...] = l_ref[...] * shrink + jnp.sum(p, axis=1,
                                                   keepdims=True)
        acc_ref[...] = acc_ref[...] * shrink + jnp.dot(
            p.astype(ckv.dtype), ckv, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(first + block >= n)
        def _():
            o_ref[b] = acc_ref[...] / l_ref[...]

    jax.lax.fori_loop(0, total, step, None)


def absorbed_attention_live_blocks(q_lat, q_pe, ckv, kpe, layer, plan,
                                   qk_head_dim: int,
                                   interpret: bool = False):
    """The tick's absorbed attention for ONE layer: q_lat [B, H, C] (the
    query with `W_kvb`'s key half absorbed) and q_pe [B, H, R] (rotated)
    against layer `layer` (a traced index) of the stacked pools ckv
    [L, B, S, C] / kpe [L, B, S, R], reading per row only the blocks
    `plan` (`live_latent_plan`) lists for it -> o_lat [B, H, C] float32,
    the probabilities' sum over the latent (`W_kvb`'s value half is the
    caller's to apply); zeros for a row that is no request.

    The arithmetic of the einsums: operands in the pools' dtype on both
    dots (q_lat, q_pe and the probabilities rounded to it), float32
    scores scaled by `sqrt(qk_head_dim)`, statistics and accumulation —
    in blocks with a running softmax, so only the order of summation
    differs. The pools stay where they are (HBM; `layer` and the plan are
    scalar-prefetch operands, the kernel addresses [layer, row, block]
    itself). `kpe` is handed over as its [L, B, R, S] view: the TPU holds
    the pool with the POSITION axis minor (64 lanes of 128 would be
    padding), so the view is the buffer as it lies and a block of it is
    the right operand of `q_pe [H, R] . [R, block]` as fetched."""
    B, H, C = q_lat.shape
    R = q_pe.shape[-1]
    whole = functools.partial(pl.BlockSpec, index_map=lambda i, *_: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, scale=math.sqrt(qk_head_dim)),
        out_shape=jax.ShapeDtypeStruct((B, H, C), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(1,),
            in_specs=[whole((B, H, C)), whole((B, H, R)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole((B, H, C)),
            scratch_shapes=[
                pltpu.VMEM((2, LATENT_BLOCK, C), ckv.dtype),
                pltpu.VMEM((2, R, LATENT_BLOCK), kpe.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, C), jnp.float32)]),
        name="mla_absorbed_live_blocks",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *plan,
      q_lat.astype(ckv.dtype), q_pe.astype(kpe.dtype), ckv,
      jnp.swapaxes(kpe, 2, 3))
