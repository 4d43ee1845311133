"""Hand-tiled Pallas fused AdamW/AMP master update.

Reference analog: the fused Adam(W) multi-tensor kernel
(paddle/phi/kernels/gpu/adamw_kernel.cu — one pass reading p/g/m/v and
writing p'/m'/v' with f32 master math over low-precision params).

TPU-native design: the optimizer update is pure elementwise traffic —
7 HBM streams (p, g, m, v in; p', m', v' out) and ~10 flops/element —
so the only thing that matters is touching each byte exactly once. XLA
usually fuses the jax-level update well, but splits it around dtype
casts and the per-leaf loop; this kernel is ONE launch per leaf with
the f32 master math (m/v kept f32, the param read in its storage dtype,
updated in f32, written back in storage dtype — the AMP master-weight
pattern without materializing a separate master copy) and its numerics
are rule-for-rule the models.gpt.apply_adamw oracle.

Wired behind gpt.apply_adamw by the FUSED_UPDATE constant below (off);
PADDLE_TPU_DISABLE_PALLAS (global) vetoes it. The jax-level form is the
default and the parity oracle (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .primitives import pad_to as _pad_dim

_LANES = 128      # elementwise: everything reshapes to [rows, 128]
_BLOCK_R = 256    # rows per grid step (256*128 f32 = 128 KiB/operand)


def _update_kernel(s_ref, p_ref, g_ref, m_ref, v_ref,
                   po_ref, mo_ref, vo_ref):
    """One (BLOCK_R, 128) tile of the AdamW update. `s_ref` carries the
    step hyperparameters broadcast down lane 0: [lr, b1, b2, eps, wd,
    bc1, bc2] — traced values (bc1/bc2 depend on the step counter), so
    they ride as a tiny input block rather than compile-time
    constants."""
    lr = s_ref[0, 0]
    b1 = s_ref[0, 1]
    b2 = s_ref[0, 2]
    eps = s_ref[0, 3]
    wd = s_ref[0, 4]
    bc1 = s_ref[0, 5]
    bc2 = s_ref[0, 6]
    gf = g_ref[...].astype(jnp.float32)
    m_new = b1 * m_ref[...] + (1 - b1) * gf
    v_new = b2 * v_ref[...] + (1 - b2) * jnp.square(gf)
    den = jnp.sqrt(v_new / bc2) + eps
    p_new = p_ref[...].astype(jnp.float32) * (1.0 - lr * wd) - \
        lr * (m_new / bc1) / den
    po_ref[...] = p_new.astype(po_ref.dtype)
    mo_ref[...] = m_new
    vo_ref[...] = v_new


def _to_tiles(a, dtype=None):
    """Flatten to [rows, 128] padded to the row block (zeros: the pad
    lanes update harmlessly — den >= eps > 0 — and are sliced away)."""
    flat = a.reshape(-1)
    if dtype is not None:
        flat = flat.astype(dtype)
    flat = _pad_dim(flat, 0, _LANES)
    rows = flat.reshape(-1, _LANES)
    return _pad_dim(rows, 0, _BLOCK_R)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _leaf_update(p, g, m, v, scal, interpret=False):
    """AdamW-update ONE leaf: returns (p', m', v') with p' in p.dtype
    and the moments in f32. `scal` is the packed [7] f32 hyperparameter
    vector (see _update_kernel)."""
    shape, n = p.shape, p.size
    pt = _to_tiles(p)
    gt = _to_tiles(g)
    mt = _to_tiles(m, jnp.float32)
    vt = _to_tiles(v, jnp.float32)
    srow = jnp.zeros((1, _LANES), jnp.float32).at[0, :7].set(
        scal.astype(jnp.float32))
    grid = (pt.shape[0] // _BLOCK_R,)
    row_spec = pl.BlockSpec((_BLOCK_R, _LANES), lambda i: (i, 0))
    p2, m2, v2 = pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, _LANES), lambda i: (0, 0)),
                  row_spec, row_spec, row_spec, row_spec],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(pt.shape, p.dtype),
                   jax.ShapeDtypeStruct(pt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(pt.shape, jnp.float32)],
        name="adamw_update",
        interpret=interpret,
    )(srow, pt, gt, mt, vt)
    unpad = lambda t: t.reshape(-1)[:n].reshape(shape)
    return unpad(p2), unpad(m2), unpad(v2)


def fused_apply_adamw(grads, params, opt_state, lr, beta1=0.9,
                      beta2=0.95, eps=1e-8, weight_decay=0.1,
                      interpret=False):
    """Drop-in for models.gpt.apply_adamw running every leaf through the
    Pallas kernel — same tree plumbing, same contract, same math."""
    step = opt_state["step"] + 1.0
    bc1 = 1.0 - beta1 ** step
    bc2 = 1.0 - beta2 ** step
    scal = jnp.stack([jnp.asarray(x, jnp.float32) for x in
                      (lr, beta1, beta2, eps, weight_decay, bc1, bc2)])

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(opt_state["m"])
    flat_v = treedef.flatten_up_to(opt_state["v"])
    new = [_leaf_update(p, g, m, v, scal, interpret=interpret)
           for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree_util.tree_unflatten(treedef,
                                              [n[0] for n in new])
    new_m = jax.tree_util.tree_unflatten(treedef, [n[1] for n in new])
    new_v = jax.tree_util.tree_unflatten(treedef, [n[2] for n in new])
    return new_params, {"m": new_m, "v": new_v, "step": step}


# Whether gpt.apply_adamw runs this kernel instead of its jax form.
# ROADMAP S7 times it once in the train cell, then flips this or deletes
# the kernel.
FUSED_UPDATE = False


def fused_update_enabled() -> bool:
    """The gpt.apply_adamw consult: the constant, on the TPU backend,
    with Pallas alive (the global kill switch)."""
    from ..device import is_tpu
    from .flash_attention import _pallas_enabled
    return FUSED_UPDATE and is_tpu() and _pallas_enabled()
