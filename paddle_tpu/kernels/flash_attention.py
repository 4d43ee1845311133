"""Flash attention for TPU.

Reference analog: the external flashattn CUDA lib wired via
cmake/external/flashattn.cmake + phi flash_attn kernels
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu).

Two paths behind one entry:
- on a TPU, the tiled Pallas pair (pallas_attention.tiled_mha: forward and
  one fused backward kernel, scores never in HBM) for the calls
  `_tiled_engages` admits — whole-sequence self-attention, no mesh of
  several devices;
- a blockwise online-softmax lax.scan path that XLA fuses, for every other
  call (the CPU, kv_len, ragged lengths, a multi-device mesh).

Both keep the softmax log-normalizer (lse); the blockwise path's backward
is the standard flash-attention recompute pass written at the jax level
(scan over kv blocks, f32): p is rebuilt from lse, so no O(S²) tensor is
ever saved. Wired via jax.custom_vjp, so the eager tape, jit.to_static and
grad transforms all pick up the memory-efficient backward.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..device import is_tpu
from ..framework.dispatch import defop

_BLOCK_KV = 512


def available() -> bool:
    return True


def _dense_attention_lse(q, k, v, causal, kv_len=None):
    """O(S²) dense softmax attention. [B,S,H,D] → (out, lse [B,H,S]).
    kv_len: number of valid kv positions (suffix is masked), default all."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhsd,bhtd->bhst", qt, kt)
    if kv_len is not None and kv_len < Skv:
        s = jnp.where(jnp.arange(Skv)[None, :] < kv_len, s, -jnp.inf)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((Sq, Skv), bool)), s, -jnp.inf)
    m = jnp.max(s, -1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, -1)
    out = jnp.einsum("bhst,bhtd->bhsd", p / l[..., None], vt)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), m + jnp.log(l)


def _dense_reference(q, k, v, causal, kv_len=None):
    """O(S²) reference (testing / tiny shapes). [B,S,H,D]."""
    return _dense_attention_lse(q, k, v, causal, kv_len)[0]


def _blockwise_attention_lse(q, k, v, causal, kv_len=None):
    """Online-softmax attention over KV blocks. [B,S,H,D] → (out, lse)."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    # operands keep their dtype (bf16 stays MXU-native); scores/state
    # accumulate in f32 via preferred_element_type
    qt = jnp.swapaxes(q, 1, 2)                              # B,H,Sq,D
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    blk = min(_BLOCK_KV, Skv)
    if Skv % blk != 0:
        return _dense_attention_lse(q, k, v, causal, kv_len)

    nblk = Skv // blk
    kb = kt.reshape(B, H, nblk, blk, D)
    vb = vt.reshape(B, H, nblk, blk, D)
    q_pos = jnp.arange(Sq)

    def step(carry, inputs):
        m, l, acc = carry
        kblk, vblk, blk_idx = inputs
        scores = jnp.einsum("bhsd,bhtd->bhst", qt, kblk,
                            preferred_element_type=jnp.float32) * scale
        kv_pos = blk_idx * blk + jnp.arange(blk)
        if kv_len is not None and kv_len < Skv:
            scores = jnp.where(kv_pos[None, :] < kv_len, scores, -jnp.inf)
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
            scores = jnp.where(mask, scores, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(jnp.isneginf(scores), 0.0, p)
        correction = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
        correction = jnp.where(jnp.isneginf(m), 0.0, correction)
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + \
            jnp.einsum("bhst,bhtd->bhsd", p.astype(vblk.dtype), vblk,
                       preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    # carries derive from inputs so shard_map varying-axes tracking
    # matches; m/l/acc state is f32 regardless of input dtype
    m0 = jnp.full_like(qt[..., 0], -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros_like(qt[..., 0], dtype=jnp.float32)
    acc0 = jnp.zeros_like(qt, dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), jnp.arange(nblk)))
    l_safe = jnp.maximum(l, 1e-37)
    out = acc / l_safe[..., None]
    lse = jnp.where(jnp.isneginf(m), -jnp.inf, m + jnp.log(l_safe))
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), lse


# Kernel selection: the Pallas path runs on the TPU backend unless
# disabled, compiled by Mosaic (interpret mode is a parameter the CPU
# parity tests pass, never something a gate turns on). A Mosaic compile
# failure is an error the caller sees; selection is an explicit gate, not
# a fallback:
# - module global `use_pallas = False` (programmatic), or
# - env PADDLE_TPU_DISABLE_PALLAS=1 (operational escape hatch, re-read per
#   trace so a failed compile can be retried without editing code).
use_pallas = True


def _pallas_enabled() -> bool:
    import os
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS", "") in ("1", "true",
                                                           "True"):
        return False
    return use_pallas


def _pallas_attn_enabled() -> bool:
    """Gate of the 128x128 kernels (mha_fwd / mha_bwd), layered on the
    global one (CE kernel unaffected — it gates through _pallas_enabled
    directly): shut where `_attn_impl` names another implementation, or
    by PADDLE_TPU_DISABLE_PALLAS_ATTN."""
    import os
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS_ATTN", "") in (
            "1", "true", "True"):
        return False
    if _attn_impl() in ("xla", "tiled"):
        return False
    return _pallas_enabled()


def _flash_sig(q, k, causal):
    B, Sq, H, D = q.shape
    return f"B{B}_Sq{Sq}_Sk{k.shape[1]}_H{H}_D{D}_c{int(causal)}_{q.dtype}"


def _env_blocks_set(*names) -> bool:
    """Explicit PADDLE_TPU_FLASH_BLOCK_* env overrides outrank the
    autotune cache — they are the operator's (and the block sweep's) way
    of forcing a size the cache would otherwise shadow."""
    import os
    return any(os.environ.get(n) for n in names)


def _tuned_blocks_bwd(q, k, causal):
    """Backward block sizes from the cache (populated by the offline
    sweep); batch-agnostic fallback; None = env/defaults."""
    if _env_blocks_set("PADDLE_TPU_FLASH_BLOCK_BWD_Q",
                       "PADDLE_TPU_FLASH_BLOCK_BWD_K"):
        return None
    from .autotune import cached_any_batch
    return cached_any_batch("flash_bwd", _flash_sig(q, k, causal))


def _tuned_blocks(q, k, causal):
    """Pick flash forward block sizes through the autotune cache
    (kernels/autotune.py — reference autotune/cache.cc); cache hits apply
    always, a timed tuning pass additionally runs when autotune is
    enabled; None = kernel defaults / env overrides."""
    from . import autotune
    if _env_blocks_set("PADDLE_TPU_FLASH_BLOCK_Q",
                       "PADDLE_TPU_FLASH_BLOCK_K"):
        return None
    sig = _flash_sig(q, k, causal)
    hit = autotune.cached_any_batch("flash_fwd", sig)
    if hit is not None:
        return hit
    if not autotune.enabled():
        return None
    from .pallas_attention import mha_fwd
    B, Sq, H, D = q.shape
    if isinstance(q, jax.core.Tracer):
        # Inside a trace (the normal path: eager dispatch jits every op,
        # and models run under jit) the tracers can't be timed — but
        # CONCRETE dummies of the same shape/dtype can: timing them here
        # runs eagerly while the outer trace is being built, i.e. tuning
        # happens once at compile time per signature (the reference's
        # switch_autotune does the same one-off timed pass). Shapes under
        # jit are static ints; bail to defaults if not (shape-polymorphic
        # export).
        try:
            shape_q = tuple(int(s) for s in q.shape)
            shape_k = tuple(int(s) for s in k.shape)
        except TypeError:
            return None       # polymorphic shape: cache already missed
        q_c = jnp.zeros(shape_q, q.dtype)
        k_c = jnp.zeros(shape_k, k.dtype)
    else:
        q_c, k_c = q, k

    def runner(cand):
        bq, bk = cand
        out, lse = mha_fwd(q_c, k_c, k_c, causal=causal, block_q=bq,
                           block_k=bk)
        jax.block_until_ready(out)
    return autotune.pick(
        "flash_fwd", sig, autotune.flash_block_candidates(Sq, k.shape[1]),
        runner, default=(128, 128))


def _fwd_with_lse(q, k, v, causal, kv_len=None):
    if _pallas_attn_enabled() and is_tpu():
        from .pallas_attention import mha_fwd
        blocks = _tuned_blocks(q, k, causal)
        if blocks is not None:
            return mha_fwd(q, k, v, causal=causal, kv_len=kv_len,
                           block_q=blocks[0], block_k=blocks[1])
        return mha_fwd(q, k, v, causal=causal, kv_len=kv_len)
    return _blockwise_attention_lse(q, k, v, causal, kv_len)


def _flash_bwd(q, k, v, out, lse, do, causal, kv_len=None):
    """Flash-attention backward: recompute p per kv block from lse.

    delta = rowsum(do ⊙ out);  ds = p ⊙ (do·vᵀ − delta) · scale
    dq = Σ_j ds_j k_j ;  dk_j = ds_jᵀ q ;  dv_j = p_jᵀ do
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    # operands keep their dtype (bf16 stays MXU-native); every einsum
    # accumulates f32 via preferred_element_type, and ds drops back to
    # the input dtype before its two dots — the standard mixed-precision
    # flash backward
    qt = jnp.swapaxes(q, 1, 2)                              # B,H,Sq,D
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = jnp.swapaxes(out, 1, 2).astype(jnp.float32)
    dot_ = jnp.swapaxes(do, 1, 2)
    delta = jnp.sum(dot_.astype(jnp.float32) * ot, axis=-1)  # B,H,Sq

    blk = min(_BLOCK_KV, Skv)
    if Skv % blk != 0:
        blk = Skv
    nblk = Skv // blk
    kb = jnp.moveaxis(kt.reshape(B, H, nblk, blk, D), 2, 0)
    vb = jnp.moveaxis(vt.reshape(B, H, nblk, blk, D), 2, 0)
    q_pos = jnp.arange(Sq)

    def step(dq, inputs):
        kblk, vblk, blk_idx = inputs
        s = jnp.einsum("bhsd,bhtd->bhst", qt, kblk,
                       preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[..., None])                     # B,H,Sq,blk
        kv_pos = blk_idx * blk + jnp.arange(blk)
        if kv_len is not None and kv_len < Skv:
            p = jnp.where(kv_pos[None, :] < kv_len, p, 0.0)
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
            p = jnp.where(mask, p, 0.0)
        dv_j = jnp.einsum("bhst,bhsd->bhtd", p.astype(dot_.dtype), dot_,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhsd,bhtd->bhst", dot_, vblk,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None]) * scale).astype(qt.dtype)
        dq = dq + jnp.einsum("bhst,bhtd->bhsd", ds, kblk,
                             preferred_element_type=jnp.float32)
        dk_j = jnp.einsum("bhst,bhsd->bhtd", ds, qt,
                          preferred_element_type=jnp.float32)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros_like(qt, dtype=jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        step, dq0, (kb, vb, jnp.arange(nblk)))
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(B, H, Skv, D)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(B, H, Skv, D)
    return (jnp.swapaxes(dq, 1, 2).astype(q.dtype),
            jnp.swapaxes(dk, 1, 2).astype(k.dtype),
            jnp.swapaxes(dv, 1, 2).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_mha(q, k, v, causal, kv_len=None):
    out, _ = _fwd_with_lse(q, k, v, causal, kv_len)
    return out


def _flash_mha_fwd(q, k, v, causal, kv_len=None):
    out, lse = _fwd_with_lse(q, k, v, causal, kv_len)
    return out, (q, k, v, out, lse)


def _pallas_bwd_enabled() -> bool:
    import os
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS_BWD", "") in ("1", "true",
                                                               "True"):
        return False
    return _pallas_attn_enabled()


def _flash_mha_bwd(causal, kv_len, res, do):
    q, k, v, out, lse = res
    if _pallas_bwd_enabled() and is_tpu():
        from .pallas_attention import mha_bwd
        blocks = _tuned_blocks_bwd(q, k, causal)
        if blocks is not None:
            return mha_bwd(q, k, v, out, lse, do, causal=causal,
                           kv_len=kv_len, block_q=blocks[0],
                           block_k=blocks[1])
        return mha_bwd(q, k, v, out, lse, do, causal=causal, kv_len=kv_len)
    return _flash_bwd(q, k, v, out, lse, do, causal, kv_len)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def _attn_impl() -> str:
    """Which attention implementation runs:
    - 'tiled'    pallas_attention.tiled_mha where the call engages it
      (`_tiled_engages`), the blockwise scan for every other call
    - 'pallas'   the 128x128 homegrown kernels + the gates above
    - 'jax_flash' jax.experimental.pallas.ops.tpu.flash_attention — the
      upstream TPU kernel with its own fwd+bwd Pallas passes
    - 'splash'   jax.experimental splash attention (block-sparse mask
      pipeline)
    - 'xla'      the blockwise lax.scan path (same as the ATTN kill)
    On the TPU 'tiled'. One chip command on a v5e (PR 36, the train
    cell's attention: B=8 S=1024 H=16 D=64 bf16 causal, [B,S,H,D] in and
    out, 16 calls chained in one jit), ms forward / ms forward+backward:
      tiled, tile 512 (this rule's)          0.516 / 1.415
      tiled, tile 256                        0.772 / 1.601
      tiled, tile 128                        1.105 / 2.709
      splash, blocks 512, fused backward     0.558 / 1.879
      splash, blocks 512, dq + dkv kernels   0.555 / 2.387
      splash, blocks 1024 (compute 512), f.  0.565 / 1.963
      splash, blocks 512 (compute 256), f.   0.676 / 2.045
      jax_flash, every block 512             0.499 / 3.555
      jax_flash, every block 1024            0.511 / 3.538
      xla (the blockwise scan)               3.165 / 6.979
      pallas (128x128 tiles, as it was)      3.654 / 11.562
    and the train step 414.3 -> 212.6 ms with it (docs/kernel_selection.md).
    Elsewhere 'pallas', so that the CPU suite keeps exercising the
    128x128 kernels' path (interpret-mode parity coverage would silently
    vanish if the CPU followed the TPU's choice); the tiled kernels'
    parity cases pass `interpret=True` themselves. ROADMAP D2 deletes the
    arms that lost."""
    return "tiled" if is_tpu() else "pallas"


def _jax_flash_mha(q, k, v, causal):
    """The upstream TPU flash kernel ([B,H,S,D] layout, own custom_vjp —
    backward runs its dq/dkv Pallas kernels, not ours)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as tpu_flash)
    D = q.shape[-1]
    out = tpu_flash(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                    jnp.swapaxes(v, 1, 2), causal=causal,
                    sm_scale=1.0 / math.sqrt(D))
    return jnp.swapaxes(out, 1, 2)


@functools.lru_cache(maxsize=16)
def _splash_kernel(num_heads, seq_q, seq_k, causal, interpret=False):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    mk = (sm.CausalMask if causal else sm.FullMask)
    mask = sm.MultiHeadMask(
        [mk((seq_q, seq_k)) for _ in range(num_heads)])
    return sk.make_splash_mha_single_device(mask=mask, interpret=interpret)


def _splash_mha(q, k, v, causal, interpret=False):
    """The upstream splash-attention kernel: block-sparse mask pipeline
    that skips masked tiles at the grid level (newer than flash_attention
    and usually faster on long causal sequences). Single-device form,
    vmapped over batch; q is pre-scaled (splash has no sm_scale)."""
    B, S, H, D = q.shape
    kernel = _splash_kernel(H, S, k.shape[1], causal, interpret)
    scaled_q = jnp.swapaxes(q, 1, 2) * (1.0 / math.sqrt(D))
    out = jax.vmap(kernel)(scaled_q, jnp.swapaxes(k, 1, 2),
                           jnp.swapaxes(v, 1, 2))
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _tiled_engages(q, k, v) -> bool:
    """Whether a call takes the tiled kernels (pallas_attention.tiled_mha)
    — by what the call shows, no option: a TPU, no ambient mesh of
    several devices (GSPMD cannot partition a Mosaic kernel), a
    self-attention over whole sequences (q, k and v of one shape and
    dtype), whole 128-lane groups of heads, and a length and head size
    `tiled_tile` has a tile for. Every other call keeps the blockwise
    scan and its jax-level backward — as do context_parallel's `kv_len`
    calls, which enter at `_flash_mha` and never get here."""
    from ..parallel.mesh import get_mesh
    from .pallas_attention import LANES, tiled_tile
    mesh = get_mesh()
    _, S, H, D = q.shape
    return (is_tpu() and _pallas_enabled()
            and (mesh is None or mesh.size == 1)
            and q.shape == k.shape == v.shape
            and q.dtype == k.dtype == v.dtype
            and (H * D) % LANES == 0
            and tiled_tile(S, D, q.dtype) is not None)


def _dispatch_mha(q, k, v, causal):
    # the upstream kernel is still Pallas: the global and attention kill
    # switches outrank the impl selector, preserving the documented
    # global > attention-only > impl layering
    impl = _attn_impl()
    if impl == "tiled" and _tiled_engages(q, k, v):
        from .pallas_attention import tiled_mha
        return tiled_mha(q, k, v, causal)
    if (impl in ("jax_flash", "splash") and _pallas_attn_enabled()
            and is_tpu()):
        fn = _splash_mha if impl == "splash" else _jax_flash_mha
        return fn(q, k, v, causal)
    # 'xla' needs no branch here: _pallas_attn_enabled() reads the impl
    # and routes _flash_mha onto the blockwise fwd + jax-level bwd
    return _flash_mha(q, k, v, causal)


@defop("flash_attention_kernel")
def _flash_attention_op(q, k, v, causal):
    return _dispatch_mha(q, k, v, causal)


def flash_attention(q, k, v, causal=False):
    """[B,S,H,D] attention. Tensor-level entry used by nn.functional."""
    return _flash_attention_op(q, k, v, bool(causal))


def flash_attention_fn(q, k, v, causal=False):
    """Raw jax-level entry (for models that work on arrays, e.g. models.gpt)."""
    return _dispatch_mha(q, k, v, bool(causal))

