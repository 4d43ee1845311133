"""The plain reference for `model_type: jamba` (AI21-Jamba2-3B,
ai21labs/AI21-Jamba2-3B config.json): the full forward of ONE sequence in
straightforward `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`; the recurrence is ONE
sequential `lax.scan` over positions. No cache, no chunks, no padding,
no slots. It imports nothing of `paddle_tpu`; the parameter tree's leaf
names and shapes are the interface (models/jamba.py `param_shapes`).

Layer i of `num_layers`, h the residual stream [T, D]:
    h = h + Mixer_i(RMSNorm(h; norm_in_i))
    h = h + W_down(silu(W_gate u) * (W_up u)),  u = RMSNorm(h; norm_ff_i)
    logits = RMSNorm(h; norm_f) @ wte^T
RMSNorm: x * rsqrt(mean(x^2) + eps) * scale.

The mixer is attention where i % attn_layer_period == attn_layer_offset
(causal softmax(q k^T / sqrt(hd)) v, `num_heads` query heads over
`num_kv_heads` K/V heads, no bias, NO positional embedding) and Mamba-1
everywhere else, on u [T, D]:
  1. [x, z] = u @ in_w
  2. x_t <- silu(conv_b + sum_k conv_w[k] * x_{t-(K-1)+k}), zeros before
     the first position
  3. [dt, B, C] = x @ x_w, split dt_rank / d_state / d_state, then Jamba's
     inner norms: RMSNorm of each with its own scale
  4. delta = softplus(dt @ dt_w + dt_b);  A = -exp(a_log)
  5. s_t = exp(delta_t * A) * s_{t-1} + delta_t * B_t * x_t   (s_0 = 0)
     y_t = sum_n C_t[n] * s_t[n] + d * x_t
  6. out = (y * silu(z)) @ out_w

Departures from the published code, none of which changes a result:
`a_log` and the state are held [d_state, d_inner] (published
[d_inner, d_state]); the convolution's weight is [d_conv, d_inner]
(published [d_inner, 1, d_conv]); head size is hidden / heads where the
config's `head_dim` is null.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_types(num_layers: int, period: int, offset: int):
    return tuple("attention" if i % period == offset else "mamba"
                 for i in range(num_layers))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def mamba(u, p, eps):
    """u [T, D] -> the mixer's output [T, D]; p holds ONE layer's leaves."""
    T = u.shape[0]
    x, z = jnp.split(u @ p["in_w"], 2, axis=-1)
    K = p["conv_w"].shape[0]
    rows = jnp.concatenate([jnp.zeros((K - 1, x.shape[1])), x])
    x = jax.nn.silu(p["conv_b"] + sum(p["conv_w"][k] * rows[k:k + T]
                                      for k in range(K)))
    N, R = p["a_log"].shape[0], p["dt_norm"].shape[0]
    dbc = x @ p["x_w"]
    dt = rms_norm(dbc[:, :R], p["dt_norm"], eps)
    B = rms_norm(dbc[:, R:R + N], p["b_norm"], eps)
    C = rms_norm(dbc[:, R + N:], p["c_norm"], eps)
    delta = jax.nn.softplus(dt @ p["dt_w"] + p["dt_b"])
    A = -jnp.exp(p["a_log"])                               # [N, Di]

    def step(s, at):
        d, xt, b, c = at
        s = jnp.exp(d[None, :] * A) * s + (d * xt)[None, :] * b[:, None]
        return s, jnp.sum(c[:, None] * s, axis=0) + p["d"] * xt

    _, y = jax.lax.scan(step, jnp.zeros_like(A), (delta, x, B, C))
    return (y * jax.nn.silu(z)) @ p["out_w"]


def attention(u, p, num_heads: int, num_kv_heads: int):
    T = u.shape[0]
    q = (u @ p["q_w"]).reshape(T, num_kv_heads, num_heads // num_kv_heads, -1)
    k = (u @ p["k_w"]).reshape(T, num_kv_heads, -1)
    v = (u @ p["v_w"]).reshape(T, num_kv_heads, -1)
    s = jnp.einsum("ikgd,jkd->kgij", q, k) / math.sqrt(q.shape[-1])
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("kgij,jkd->ikgd", pr, v).reshape(T, -1) @ p["o_w"]


_MAMBA = ("in_w", "conv_w", "conv_b", "x_w", "dt_norm", "b_norm", "c_norm",
          "dt_w", "dt_b", "a_log", "d", "out_w")
_ATTENTION = ("q_w", "k_w", "v_w", "o_w")


def forward(params, tokens, *, num_heads: int, num_kv_heads: int,
            period: int, offset: int, eps: float):
    """tokens [T] -> logits [T, V] float32."""
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(jnp.float32) for k, v in params.items()}
        h = jnp.take(p["wte"], tokens, axis=0)
        seen = {"mamba": 0, "attention": 0}
        for i, kind in enumerate(layer_types(p["norm_in"].shape[0], period,
                                             offset)):
            m = seen[kind]
            seen[kind] += 1
            u = rms_norm(h, p["norm_in"][i], eps)
            if kind == "mamba":
                h = h + mamba(u, {k: p[k][m] for k in _MAMBA}, eps)
            else:
                h = h + attention(u, {k: p[k][m] for k in _ATTENTION},
                                  num_heads, num_kv_heads)
            u = rms_norm(h, p["norm_ff"][i], eps)
            h = h + (jax.nn.silu(u @ p["gate_w"][i])
                     * (u @ p["up_w"][i])) @ p["down_w"][i]
        return rms_norm(h, p["norm_f"], eps) @ p["wte"].T
