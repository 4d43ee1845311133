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

Two implementations, selected through the kernels/registry.py seam
(kernel "quant_matmul", impls off|xla|pallas):

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

Selection and the kill switch (the spec_decode pattern — env beats
everything, unrecognized values fail SAFE to off):

- env PADDLE_TPU_QUANT: 'off'/'0'/'false'/'no'/'fp'/'dense' disable
  weight-only quant even for engines built with quant="int8";
  'xla'/'pallas' enable it AND pin the matmul impl; '1'/'on'/'true'/
  'yes'/'int8' enable it with the portable 'xla' impl; anything else
  warns on stderr and counts as OFF (a typo must kill, not enable).
- registry: winner("quant_matmul") — written only by the evidence-
  gated sweep (tools/bench_serving.py --quant --adopt, which refuses
  adoption unless weight bytes <= 0.55x fp AND tokens/s >= 0.95x fp).
- default: off.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..device import is_tpu
from .primitives import pad_to as _pad_to, round_up as _round_up

__all__ = ["ENV_QUANT", "quant_impl", "resolve_quant", "matmul_impl",
           "quant_matmul", "leaf_matmul"]

ENV_QUANT = "PADDLE_TPU_QUANT"

_OFF_VALUES = frozenset({"0", "off", "false", "no", "fp", "dense"})
_ON_VALUES = frozenset({"1", "on", "true", "yes", "int8"})
_IMPL_VALUES = frozenset({"xla", "pallas"})


def _env_value() -> str:
    """Read + classify PADDLE_TPU_QUANT: '' (unset), 'off', 'xla' or
    'pallas'. Unrecognized values are OFF with a stderr warning — this
    env var is the kill switch, and a typo that silently enabled
    quantized serving would do the exact opposite of what the operator
    reached for (the spec_decode fail-safe rule)."""
    env = os.environ.get(ENV_QUANT, "").strip().lower()
    if not env:
        return ""
    if env in _IMPL_VALUES:
        return env
    if env in _ON_VALUES:
        return "xla"
    if env not in _OFF_VALUES:
        import sys
        print(f"[quant_matmul] {ENV_QUANT}={env!r} is not one of "
              f"{sorted(_IMPL_VALUES | _ON_VALUES)} / "
              f"{sorted(_OFF_VALUES)}; treating as 'off' (the kill "
              "switch fails safe)", file=sys.stderr, flush=True)
    return "off"


def quant_impl() -> str:
    """Selector: env PADDLE_TPU_QUANT > registry winner
    ('quant_matmul', current backend class) > 'off'. Re-read per
    engine build like the other kill switches."""
    env = _env_value()
    if env:
        return env
    from . import registry
    win = registry.winner("quant_matmul",
                          backend=registry.backend_class(
                              jax.default_backend()))
    return win or "off"


def resolve_quant(knob: str) -> bool:
    """Engine-build resolution of the quant knob ('auto' | 'off' |
    'int8') against the selector. The env kill switch is absolute: an
    off value disables quantization even for knob='int8' (same
    asymmetry as PADDLE_TPU_SPEC_DECODE — docs/serving.md)."""
    if _env_value() == "off":
        return False
    if knob == "off":
        return False
    if knob == "int8":
        return True
    if knob == "auto":
        return quant_impl() != "off"
    raise ValueError(f"quant {knob!r} (auto|off|int8)")


def matmul_impl() -> str:
    """Which implementation a quant_matmul SITE runs: 'pallas' when
    selected AND the backend is TPU (the compiled kernel targets
    Mosaic; off-TPU callers get the numerically-identical 'xla' form —
    interpret-mode coverage lives in the parity tests) AND the global
    PADDLE_TPU_DISABLE_PALLAS escape hatch is not set (the CLAUDE.md
    kill-switch convention every Pallas kernel honors), else 'xla'.
    'off' here still resolves to 'xla': an engine that already
    quantized its weights at build must keep serving them — the kill
    switch stops NEW engines from quantizing (resolve_quant), it
    cannot un-quantize a live tree."""
    sel = quant_impl()
    if (sel == "pallas"
            and is_tpu()
            and os.environ.get("PADDLE_TPU_DISABLE_PALLAS", "")
            not in ("1", "true", "True")):
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
    overrides the selector (tests); `interpret` runs the Pallas kernel
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
