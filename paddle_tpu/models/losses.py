"""Shared model-loss kernels.

fused_softmax_ce is the one fused cross-entropy implementation the model
zoo uses (gpt_loss, bert MLM/classification): loss_i = logsumexp(logits_i)
− logits_i[target_i], mathematically identical to −log_softmax[target]
but never materializing the [.., V] f32 log-prob tensor — the reference's
fused softmax_with_cross_entropy kernel
(phi/kernels/gpu/cross_entropy_kernel.cu) made the same HBM trade.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Whether the loss runs pallas_ce.ce_fused_train, the one-pass CE+grad
# kernel, instead of ce_with_logits (fused_softmax_ce says what that
# costs). ROADMAP S7 times it once in the train cell, then flips this or
# deletes the kernel.
CE_FUSED_GRAD = False


def _pallas_ce_enabled() -> bool:
    import os
    # ONE kill-switch family: the attention module's gate covers the
    # global PADDLE_TPU_DISABLE_PALLAS env AND the use_pallas module
    # global (the documented escape for Mosaic compile failures); the CE
    # kernel adds only its own targeted env on top
    from ..kernels.flash_attention import _pallas_enabled
    if not _pallas_enabled():
        return False
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS_CE", "") in (
            "1", "true", "True"):
        return False
    from ..device import is_tpu
    return is_tpu()


def _ce_rows_over_mesh(ce_fn, logits2d, targets):
    """Run the per-row Pallas CE under whatever mesh is ambient. GSPMD
    cannot partition a Mosaic kernel ("wrap the call in a shard_map"),
    so on a multi-device mesh the kernel runs inside a shard_map that is
    manual over EVERY mesh axis: the rows split over all of them, the
    vocab axis whole on each device. A vocab-parallel head hands over
    [rows/(dp·fsdp), V/tp] logits; the partitioner moves them to
    [rows/n, V] with one all-to-all over tp, the same bytes per device,
    and the backward moves d_logits back the same way. Rows are padded
    up to a multiple of the device count and the padding sliced off.
    check_vma=False: the region holds no collective to type, and a
    pallas_call's out_shape carries no vma for the checker to read."""
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return ce_fn(logits2d, targets)
    axes = tuple(mesh.axis_names)
    rows = logits2d.shape[0]
    pad = -rows % mesh.size
    if pad:
        logits2d = jnp.pad(logits2d, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
    per_row = jax.shard_map(
        ce_fn, mesh=mesh, in_specs=(P(axes, None), P(axes)),
        out_specs=P(axes), check_vma=False)(logits2d, targets)
    return per_row[:rows]


def fused_softmax_ce(logits, targets, valid_mask=None):
    """logits [..., V] (any float dtype), targets [...] int. valid_mask
    [...] (bool/0-1) selects which positions count; None = all. Returns
    the mean loss over counted positions.

    On TPU with a large vocab the per-position loss runs through the
    hand-tiled Pallas kernel (kernels/pallas_ce.py): bf16 logits stream
    through VMEM once with online logsumexp — no [T, V] f32
    materialization. Elsewhere (and as the numerics oracle) the jax-level
    form computes the same logsumexp − target gather in f32."""
    from ..kernels import pallas_ce
    lead = logits.shape[:-1]
    V = logits.shape[-1]
    if _pallas_ce_enabled() and pallas_ce.suitable(logits.shape):
        # the one-pass flavour (backward folded into the forward launch)
        # rides the SAME enablement gate; a primal-only caller would pay
        # for its discarded d_logits, so it is off unless the constant
        # says otherwise
        if CE_FUSED_GRAD:
            ce_fn = pallas_ce.ce_fused_train
        else:
            ce_fn = pallas_ce.ce_with_logits
        per_pos = _ce_rows_over_mesh(
            ce_fn, logits.reshape(-1, V),
            targets.reshape(-1).astype(jnp.int32)).reshape(lead)
    else:
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        tgt = jnp.take_along_axis(
            lf, targets[..., None].astype(jnp.int32), -1)[..., 0]
        per_pos = lse - tgt
    if valid_mask is None:
        return jnp.mean(per_pos)
    m = valid_mask.astype(jnp.float32)
    return jnp.sum(per_pos * m) / jnp.maximum(jnp.sum(m), 1.0)
