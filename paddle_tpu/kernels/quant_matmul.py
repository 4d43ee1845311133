"""Fused dequant-matmul for weight-only int8 serving.

Reference analog: the int8 kernel-substitution pass's matmul
(quant2_int8_mkldnn_pass.py:1 — int8 weights, fp activations, dequant
fused into the kernel epilogue), restricted to the WEIGHT-ONLY form the
serving engines use (quantization/serving.py): activations stay in the
compute dtype, weights stream from HBM as int8 with per-output-channel
fp32 scales, and the dequantization never materializes an fp copy of
the weight in HBM — that copy not existing IS the feature (weight HBM
traffic halves vs bf16, quarters vs f32, which is what a bandwidth-
bound decode tick actually pays for).

Two implementations; which one a site runs is QUANT_MATMUL_IMPL below
(`quant_matmul(impl=)` overrides it for one call):

- 'xla'    jax dot_general on the fp activations against the int8
           weight upcast IN THE FUSION (XLA keeps the convert fused
           into the dot's operand stream), per-output-channel scale
           applied to the f32 accumulator as the epilogue. The
           portable fallback — CPU tests exercise this real path.
- 'pallas' hand-tiled TPU kernel: x tiles [bm, K] and int8 w tiles
           [K, bn] stage through VMEM, the int8->f32 convert happens
           in registers inside the matmul tile (the Pallas-guide
           quantization pattern), the f32 accumulator picks up the
           scale tile in the epilogue. Interpret-mode parity vs the
           'xla' impl is EXACT (same contraction, same f32
           accumulation order — tests/test_quant_serving.py pins it).

Both impls compute (x @ w_q) * scale with an f32 accumulator and cast
back to x.dtype. The per-output-channel scale commutes with the
contraction, so this equals the dequant-first oracle
x @ (w_q.astype(f32) * scale) up to one fp rounding per product —
the parity tests hold the impls bitwise-identical to EACH OTHER and
allclose to the dequant-first oracle.

Whether an engine quantizes at all is its `quant=` argument
("auto" | "off" | "int8"; auto is off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import is_tpu
from .primitives import pad_to as _pad_to, round_up as _round_up

__all__ = ["resolve_quant", "matmul_impl", "quant_matmul", "leaf_matmul"]

_IMPL_VALUES = frozenset({"xla", "pallas"})

# The matmul a quantized engine's sites run, 'xla' | 'pallas' (module
# docstring). ROADMAP S2 times int8 in the GPT serving cells with each,
# then flips this or deletes the Pallas kernel.
QUANT_MATMUL_IMPL = "xla"


def resolve_quant(knob: str) -> bool:
    """The engines' `quant=` argument ('auto' | 'off' | 'int8') as a
    bool; anything else raises. 'auto' is off."""
    if knob not in ("auto", "off", "int8"):
        raise ValueError(f"quant {knob!r} (auto|off|int8)")
    return knob == "int8"


def matmul_impl() -> str:
    """Which implementation a quant_matmul SITE runs: 'pallas' when
    QUANT_MATMUL_IMPL says so AND the backend is TPU (the compiled
    kernel targets Mosaic; off-TPU callers get the numerically-identical
    'xla' form — interpret-mode coverage lives in the parity tests) AND
    Pallas is alive (the global PADDLE_TPU_DISABLE_PALLAS escape every
    Pallas kernel honors), else 'xla'."""
    if QUANT_MATMUL_IMPL == "pallas" and is_tpu():
        from .flash_attention import _pallas_enabled
        if _pallas_enabled():
            return "pallas"
    return "xla"


# ------------------------------------------------------------ xla impl
def _xla_quant_matmul(x2d, w_q, scale):
    """(x @ w_q) * scale with an f32 accumulator: the int8 weight
    upcasts inside the dot's fusion (no fp weight copy in HBM), the
    per-output-channel scale lands on the accumulator."""
    y = jax.lax.dot_general(
        x2d.astype(jnp.float32), w_q.astype(jnp.float32),
        (((1,), (0,)), ((), ())))
    return y * scale.astype(jnp.float32)


# --------------------------------------------------------- pallas impl
def _qmm_kernel(x_ref, w_ref, s_ref, o_ref):
    """One [bm, bn] output tile: the int8 weight tile converts to f32
    IN REGISTERS (never touching HBM as fp), the full-K dot accumulates
    in f32, and the scale tile is the epilogue."""
    acc = jnp.dot(x_ref[...].astype(jnp.float32),
                  w_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[...] = acc * s_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def _pallas_quant_matmul(x2d, w_q, scale, block_m=128, block_n=128,
                         interpret=False):
    from jax.experimental import pallas as pl

    M, K = x2d.shape
    N = w_q.shape[1]
    # K is x's lane axis (128-mult) AND the int8 w's sublane axis
    # (32-mult) — pad to 128 covers both; zero-padding contributes an
    # exact 0.0 to every accumulator, so parity with the xla impl holds
    bm = min(block_m, _round_up(M, 16))
    bn = min(block_n, _round_up(N, 128))
    x = _pad_to(_pad_to(x2d, 0, bm), 1, 128)
    w = _pad_to(_pad_to(w_q, 0, 128), 1, bn)
    s = _pad_to(scale.astype(jnp.float32), 0, bn).reshape(1, -1)
    grid = (x.shape[0] // bm, w.shape[1] // bn)

    y = pl.pallas_call(
        _qmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, x.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((w.shape[0], bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], w.shape[1]),
                                       jnp.float32),
        name="quant_matmul",
        interpret=interpret,
    )(x, w, s)
    return y[:M, :N]


# --------------------------------------------------------- public entry
def quant_matmul(x, w_q, scale, impl: str | None = None,
                 interpret: bool = False):
    """y = x @ dequant(w_q): x [..., K] float, w_q [K, N] int8, scale
    [N] f32 per-output-channel. Returns [..., N] in x.dtype. `impl`
    overrides matmul_impl() (tests); `interpret` runs the Pallas kernel
    in interpreter mode (CPU parity tests)."""
    impl = impl or matmul_impl()
    if impl not in _IMPL_VALUES:
        raise ValueError(f"unknown quant_matmul impl {impl!r} "
                         "(xla|pallas)")
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    if impl == "pallas":
        y = _pallas_quant_matmul(x2d, w_q, scale, interpret=interpret)
    else:
        y = _xla_quant_matmul(x2d, w_q, scale)
    return y.reshape(*lead, w_q.shape[1]).astype(x.dtype)


def leaf_matmul(x, leaves, name: str):
    """x [B, T, K] @ leaf `name` [K, N]: the fp einsum when the tree
    holds the fp weight, the fused dequant-matmul when it holds the
    int8 serving pair (`<name>_q` + `<name>_scale` —
    quantization/serving.quantize_serving_params). THE seam the cached
    forwards route every block matmul through (models/gpt.py,
    models/llama.py), so dense/paged/spec-draft/tp paths all pick the
    quantized weights up from the params tree itself."""
    w_q = leaves.get(name + "_q")
    if w_q is not None:
        return quant_matmul(x, w_q, leaves[name + "_scale"])
    return jnp.einsum("btk,kn->btn", x, leaves[name].astype(x.dtype))
