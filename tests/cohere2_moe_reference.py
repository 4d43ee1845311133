"""The plain reference for `model_type: cohere2_moe` (Command A+), for the
program's tests: the full forward of ONE sequence in straightforward
jax.numpy, float32, `jax.default_matmul_precision("highest")`. No cache,
no kernels, no batching, and nothing imported from the program or from
the benchmark (which keeps a copy of its own).

It follows the published description (config.json keys in backticks):

- block (`use_parallel_block`): h = LN(x); x' = x + Attn(h) + Experts(h);
  LN centres, divides by sqrt(var + `layer_norm_eps`), scales, no bias;
  a final LN, then logits = x . wte^T x `logit_scale`, embeddings tied;
- attention: 128 -> `num_heads` query heads over `num_kv_heads` K/V
  heads, no bias; `layer_types` says per layer: a sliding layer rotates
  q and k (`rope_gptj`: ADJACENT pairs, `rope_theta`) and query i sees
  key j iff 0 <= i - j < `sliding_window`; a full layer is causal over
  everything;
- experts: router over `num_experts` in float32, sigmoid scores
  (`expert_selection_fn`), the `num_experts_per_tok` largest, weights
  normalised over the chosen (`norm_topk_prob`); each expert is
  Wdown(silu(Wgate h) * Wup h); `num_shared_experts` shared experts see
  every token and are averaged (`shared_expert_combination_strategy`).

Departures, each an inference the configuration file lists as `assumed`:
a full layer applies NO positional embedding (the family's convention;
the config has no key for it); the expert width is `intermediate_size`
and each shared expert has it too; "average" is the mean over the
shared experts added to the routed sum; no routing bias, no routed
scaling factor. And the cut: the tree holds the experts
`first_expert .. first_expert + held - 1` of the published count, the
router still scores them all, and what the absent experts would add is
left out.

The parameter tree has the program's leaf names and shapes (that is the
system's interface): per-layer leaves stacked on a leading layer axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"


def layer_norm(x, scale, eps):
    x = x - x.mean(-1, keepdims=True)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_interleaved(x, theta):
    """x [T, heads, hd] at positions 0..T-1: pair (2i, 2i+1) is rotated
    by position x theta^(-2i/hd) (`rope_gptj`)."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def rope_split_half(x, theta):
    """The OTHER convention (halves rotated against each other), kept
    for the test that tells the two apart: not the model's."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(h, lp, kind, *, num_heads, num_kv_heads, window, theta):
    T = h.shape[0]
    q = (h @ lp["q_w"]).reshape(T, num_heads, -1)
    k = (h @ lp["k_w"]).reshape(T, num_kv_heads, -1)
    v = (h @ lp["v_w"]).reshape(T, num_kv_heads, -1)
    hd = q.shape[-1]
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    if kind == SLIDING:
        q, k = rope_interleaved(q, theta), rope_interleaved(k, theta)
        mask &= i - j < window
    group = num_heads // num_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hij,jhd->ihd", p, v).reshape(T, -1) @ lp["o_w"]


def expert(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def route(h, router_w, per_token):
    """-> (chosen experts [T, k], their normalised weights [T, k])."""
    scores = jax.nn.sigmoid(h @ router_w)
    top, chosen = jax.lax.top_k(scores, per_token)
    return chosen, top / top.sum(-1, keepdims=True)


def experts(h, lp, *, per_token, first_expert):
    chosen, weight = route(h, lp["router_w"], per_token)
    held = lp["gate_w"].shape[0]
    out = jnp.zeros_like(h)
    for e in range(held):                   # this chip's experts only
        w = jnp.where(chosen == first_expert + e, weight, 0.0).sum(-1)
        out += w[:, None] * expert(h, lp["gate_w"][e], lp["up_w"][e],
                                   lp["down_w"][e])
    shared = lp["shared_gate_w"].shape[0]
    for s in range(shared):
        out += expert(h, lp["shared_gate_w"][s], lp["shared_up_w"][s],
                      lp["shared_down_w"][s]) / shared
    return out


def forward(params, tokens, *, layer_types, num_heads, num_kv_heads,
            window, theta, eps, per_token, first_expert=0,
            logit_scale=1.0):
    """tokens [T] -> logits [T, V] float32."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = params["wte"][tokens]
        for n, kind in enumerate(layer_types):
            lp = {k: v[n] for k, v in params.items()
                  if k not in ("wte", "norm_f")}
            h = layer_norm(x, lp["norm"], eps)
            x = x + attention(h, lp, kind, num_heads=num_heads,
                              num_kv_heads=num_kv_heads, window=window,
                              theta=theta) \
                + experts(h, lp, per_token=per_token,
                          first_expert=first_expert)
        x = layer_norm(x, params["norm_f"], eps)
        return (x @ params["wte"].T) * logit_scale
