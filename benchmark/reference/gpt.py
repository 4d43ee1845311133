"""The plain reference: a GPT-3 decoder (Brown et al. 2020, section 2.1 —
pre-norm blocks, learned positions, GELU MLP, tied output embedding) in
straightforward `jax.numpy`, float32, `precision="highest"`; no cache, no
kernels, no batching tricks. It imports nothing of the program and is what
`correct` is judged against; `benchmark/correct/` drives it in blocks of
rows so that it fits beside nothing else on the chip.

`precision` selects the arithmetic of every matmul operand:
  "float32"  the reference proper
  "bfloat16" operands rounded to bf16 (what the configurations state)
  "fp8"      operands rounded to float8_e4m3 with a per-tensor scale —
             the CONTROL: the nearest precision below the stated bf16,
             the step that would tempt a later PR
Departures from the paper, as the upstream Paddle GPT trains it: GELU in
its tanh form, the fused q|k|v projection, AdamW with decoupled decay on
every leaf.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _round_operand(x, precision: str):
    """`x` rounded to `precision`, with the gradient passed straight
    through: a cotangent sent back through a cast to float8 would itself
    be rounded to float8 and flush to zero."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        rounded = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round_operand(a, precision),
                      _round_operand(b, precision), precision=_HIGHEST)


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


_BLOCK_LEAVES = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "qkv_w",
                 "qkv_b", "attn_out_w", "attn_out_b", "mlp_up_w", "mlp_up_b",
                 "mlp_down_w", "mlp_down_b")


def _block(x, p, *, num_heads: int, eps: float, precision: str):
    b, s, d = x.shape
    hd = d // num_heads
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
    qkv = _mm("bsd,de->bse", h, p["qkv_w"], precision) + p["qkv_b"]
    q, k, v = (t.reshape(b, s, num_heads, hd)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, d)
    x = x + _mm("bsd,de->bse", ctx, p["attn_out_w"], precision) \
        + p["attn_out_b"]
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
    up = _gelu(_mm("bsd,df->bsf", h, p["mlp_up_w"], precision)
               + p["mlp_up_b"])
    return x + _mm("bsf,fd->bsd", up, p["mlp_down_w"], precision) \
        + p["mlp_down_b"]


def forward(params, tokens, *, num_heads: int, eps: float = 1e-5,
            precision: str = "float32"):
    """tokens [B, S] int32 -> logits [B, S, V] float32. The layers run
    under a scan whose body is rematerialised in the backward pass: that
    bounds memory and changes no value."""
    s = tokens.shape[1]
    x = jnp.take(params["wte"], tokens, axis=0) + params["wpe"][:s][None]
    body = jax.checkpoint(functools.partial(
        _block, num_heads=num_heads, eps=eps, precision=precision))
    x, _ = jax.lax.scan(lambda h, p: (body(h, p), None), x,
                        {k: params[k] for k in _BLOCK_LEAVES})
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], eps)
    return _mm("bsd,vd->bsv", x, params["wte"], precision)


def loss_sum(params, tokens, **kw):
    """Summed next-token cross-entropy of tokens [B, S+1] over B*S
    positions (the caller divides: blocks of rows add up)."""
    logits = forward(params, tokens[:, :-1], **kw)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(lse - tgt)


def adamw(params, grads, m, v, step, *, lr, beta1, beta2, eps, weight_decay):
    """One AdamW update (Loshchilov & Hutter), bias-corrected, decay
    decoupled and applied to every leaf; `step` counts from 1."""
    bc1, bc2 = 1.0 - beta1 ** step, 1.0 - beta2 ** step

    def one(p, g, m_, v_):
        m_ = beta1 * m_ + (1.0 - beta1) * g
        v_ = beta2 * v_ + (1.0 - beta2) * jnp.square(g)
        p = p * (1.0 - lr * weight_decay) \
            - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
        return p, m_, v_

    out = {k: one(params[k], grads[k], m[k], v[k]) for k in params}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})
