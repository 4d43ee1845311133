"""The plain reference for `model_type: cohere2_moe` (Command A+,
CohereLabs/command-a-plus-05-2026 config.json): the full forward of ONE
sequence in straightforward `jax.numpy`, float32, `precision="highest"`;
no cache, no kernels, no batching. It imports nothing of the program (and
nothing of the program's tests, which keep a reference of their own) and
is what `correct` is judged against in the command-a-plus cells.

The layer, by the config's keys:
- `use_parallel_block`: h = LN(x); x' = x + Attn(h) + Experts(h). LN
  centres, divides by sqrt(var + `layer_norm_eps`), scales; no bias. A
  final LN, then logits = x . wte^T x `logit_scale`; embeddings tied.
- attention: `num_attention_heads` query heads over
  `num_key_value_heads` K/V heads of `head_dim`, no bias, no q/k norm.
  `layer_types`: a `sliding_attention` layer rotates q and k
  (`rope_gptj`: ADJACENT pairs, `rope_theta`) and query i sees key j iff
  0 <= i - j < `sliding_window`; a `full_attention` layer is causal over
  everything.
- experts (`first_k_dense_replace` 0: every layer): router logits in
  float32, sigmoid (`expert_selection_fn`), the `num_experts_per_tok`
  largest, their scores divided by their sum (`norm_topk_prob`); an
  expert is Wdown(silu(Wgate h) * Wup h) (`use_gated_activation`,
  `hidden_act` silu); `num_shared_experts` shared experts see every token
  and are averaged (`shared_expert_combination_strategy`).

Departures from the published description, each listed under `assumed`
in the configuration file: a full layer applies NO positional embedding;
the expert width is `intermediate_size`, for shared experts too;
"average" is the mean over the shared experts, added to the routed sum;
no routing bias and no routed scaling factor. And the cut (model-configs
guide, section 4): the tree holds the routed experts `first_expert ..
first_expert + held - 1` of the published count and `vocab_size` rows of
the embedding; the router scores every published expert, and what the
absent experts would add is left out.

On the chip the weights stay as the seed made them, in bfloat16 (float32
would be 18.9 GB), and each is widened where it is used; attention runs
over blocks of query rows and the experts one at a time, so that 16,384
positions fit.

`precision` is the arithmetic of every matmul operand: "float32" the
reference proper, "bfloat16", and "fp8" (float8_e4m3 with a per-tensor
scale) — the CONTROL, the nearest precision below the stated bf16.
`window` and `rope` are what the reference is TOLD: the configuration's
by default, and anything else is a planted fault (half the window,
`rope="split_half"`) that `correct` must catch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
SLIDING = "sliding_attention"
QUERY_ROWS = 64           # a block of query rows sees every key at once
ATTENTION = ("q_w", "k_w", "v_w", "o_w")


def _round_operand(x, precision: str):
    x = x.astype(jnp.float32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round_operand(a, precision),
                      _round_operand(b, precision), precision=_HIGHEST)


def _layer_norm(x, scale, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta: float, rope: str):
    """x [T, heads, hd] at `positions` [T]. "interleaved" rotates the
    pairs (2i, 2i+1) — the model's `rope_gptj`; "split_half" rotates
    (i, i + hd/2) — another model's, here only as a planted fault."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if rope == "split_half":
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    if rope != "interleaved":
        raise ValueError(f"unknown rope {rope!r}")
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _attention(h, p, kind: str, arch: dict, precision: str, window: int,
               rope: str):
    """Keys and values of the whole sequence at once (8 K/V heads: small);
    queries, their scores against every key and their softmax one block
    of QUERY_ROWS rows at a time — the same sums as all rows at once, and
    what lets 16,384 positions of 128 heads fit."""
    T = h.shape[0]
    H, KV = arch["num_heads"], arch["num_kv_heads"]
    k = _mm("td,dh->th", h, p["k_w"], precision).reshape(T, KV, -1)
    v = _mm("td,dh->th", h, p["v_w"], precision).reshape(T, KV, -1)
    hd = k.shape[-1]
    if kind == SLIDING:
        k = _rope(k, jnp.arange(T), arch["rope_theta"], rope)
    rows = min(QUERY_ROWS, T)
    keys = jnp.arange(T)[None, :]

    def block(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, rows, axis=0)
        at = start + jnp.arange(rows)
        q = _mm("td,dh->th", hb, p["q_w"], precision).reshape(rows, H, hd)
        if kind == SLIDING:
            q = _rope(q, at, arch["rope_theta"], rope)
        # query head h reads K/V head h // (H / KV)
        q = q.reshape(rows, KV, H // KV, hd)
        mask = keys <= at[:, None]
        if kind == SLIDING:
            mask &= at[:, None] - keys < window
        s = _mm("ikgd,jkd->kgij", q, k, precision) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf),
                            axis=-1)
        ctx = _mm("kgij,jkd->ikgd", pr, v, precision).reshape(rows, -1)
        return _mm("th,hd->td", ctx, p["o_w"], precision)

    return jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, -1)


def _expert(h, gate_w, up_w, down_w, precision: str):
    g = jax.nn.silu(_mm("td,df->tf", h, gate_w, precision)) \
        * _mm("td,df->tf", h, up_w, precision)
    return _mm("tf,fd->td", g, down_w, precision)


def route(h, router_w, per_token: int):
    """-> (the chosen experts [T, k], their normalised weights [T, k]):
    float32 at the highest precision whatever `precision` the matmuls of
    the control use — the router is float32 in the model."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h, router_w.astype(jnp.float32), precision=_HIGHEST))
    top, chosen = jax.lax.top_k(scores, per_token)
    return chosen, top / jnp.sum(top, -1, keepdims=True)


def _one_of(stack, n, e):
    """Expert e of layer n out of a stacked leaf [L, E, ...]: one slice
    of the whole stack, so that no layer's 400 MB is copied out first."""
    flat = stack.reshape((-1,) + stack.shape[2:])
    return jax.lax.dynamic_index_in_dim(flat, n * stack.shape[1] + e, 0,
                                        keepdims=False)


def _experts(h, params, n: int, arch: dict, precision: str):
    chosen, weight = route(h, params["router_w"][n],
                           arch["experts_per_token"])

    def one(prefix, e):
        return _expert(h, *(_one_of(params[prefix + name], n, e)
                            for name in ("gate_w", "up_w", "down_w")),
                       precision)

    def held(total, e):           # this chip's experts, one at a time
        w = jnp.sum(jnp.where(chosen == arch["first_expert"] + e, weight,
                              0.0), -1)
        return total + w[:, None] * one("", e), None

    def shared(total, s):
        return total + one("shared_", s), None

    routed, _ = jax.lax.scan(held, jnp.zeros_like(h),
                             jnp.arange(params["gate_w"].shape[1]))
    n_shared = params["shared_gate_w"].shape[1]
    mean, _ = jax.lax.scan(shared, jnp.zeros_like(h), jnp.arange(n_shared))
    return routed + mean / n_shared


def hidden(params, tokens, arch: dict, *, precision: str = "float32",
           window: int | None = None, rope: str = "interleaved"):
    """tokens [T] -> the final-normed hidden state [T, D] float32. `arch`
    holds layer_types, num_heads, num_kv_heads, rope_theta,
    sliding_window, layer_norm_eps, experts_per_token, first_expert,
    logit_scale."""
    window = arch["sliding_window"] if window is None else window
    x = jnp.take(params["wte"], tokens, axis=0).astype(jnp.float32)
    for n, kind in enumerate(arch["layer_types"]):
        p = {k: params[k][n] for k in ATTENTION}
        h = _layer_norm(x, params["norm"][n], arch["layer_norm_eps"])
        x = x + _attention(h, p, kind, arch, precision, window, rope) \
            + _experts(h, params, n, arch, precision)
    return _layer_norm(x, params["norm_f"], arch["layer_norm_eps"])


def logits_at(params, tokens, first, count: int, arch: dict, **kw):
    """Logits [count, V] at positions first .. first + count - 1 of the
    sequence `tokens` [T] (`first` may be traced)."""
    x = jax.lax.dynamic_slice_in_dim(hidden(params, tokens, arch, **kw),
                                     first, count, axis=0)
    return _mm("td,vd->tv", x, params["wte"],
               kw.get("precision", "float32")) * arch["logit_scale"]


def forward(params, tokens, arch: dict, **kw):
    """tokens [T] -> logits [T, V] float32."""
    return logits_at(params, tokens, 0, tokens.shape[0], arch, **kw)
