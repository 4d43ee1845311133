"""The plain reference for `model_type: joyai_llm_flash` (JoyAI-LLM Flash
48B-A2.7B, jdopensource/JoyAI-LLM-Flash config.json): the full forward of
ONE sequence in straightforward `jax.numpy`, float32, every matmul at
`precision="highest"`; no cache, no absorption, no kernels, no batching. It
imports nothing of the program and is what `correct` is judged against in
the joyai-llm-flash cells.

The layer, by the config's keys (pre-norm, sequential residual):
`x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))`, RMSNorm with `rms_norm_eps`,
a final RMSNorm, then logits = x . head^T (`tie_word_embeddings` false: the
head is its own matrix).

- attention, every layer (latent, MLA): c_q = RMSNorm(x W_qa)
  (`q_lora_rank`); q = c_q W_qb -> `num_attention_heads` heads of
  `qk_nope_head_dim` | `qk_rope_head_dim`. x W_kva -> `kv_lora_rank` |
  `qk_rope_head_dim`: c_kv = RMSNorm(first part), k_pe = the rest, ONE
  rotated key all heads share. RoPE (`rope_theta`, `rope_interleave`: the
  pairs (2i, 2i+1); `rope_scaling` null) on q_pe and k_pe only. Here the
  latent is DECOMPRESSED at every position: k_nope = c_kv W_kb, v = c_kv
  W_vb (the two halves of the published kv_b_proj, per head), score =
  (q_nope . k_nope + q_pe . k_pe) / sqrt(qk_nope + qk_rope), causal
  softmax, o = P v, out = o W_o.
- FFN: the first `first_k_dense_replace` layers a gated-SiLU MLP of
  `intermediate_size`; the others `n_routed_experts` routed experts of
  `moe_intermediate_size`, `num_experts_per_tok` a token, plus
  `n_shared_experts` shared (one MLP of their summed width) added to the
  routed sum. Routing `noaux_tc`: s = sigmoid(x W_g) in float32; the
  chosen are the largest of s + `e_score_correction_bias` (`n_group` =
  `topk_group` = 1: no group limit); their weights are s (NOT s + b),
  divided by their sum (`norm_topk_prob`), times `routed_scaling_factor`.
- multi-token prediction (`num_nextn_predict_layers`), `mtp_logits`:
  h' = W_eh [RMSNorm_e(Emb(t_{i+1})); RMSNorm_h(h_i)], one more whole block
  (latent attention + experts), its own final norm, the SHARED head ->
  logits for t_{i+2}. It does not enter the next-token logits.

Departures from the published description, each listed under `assumed` in
the configuration file: both compressions are RMS-normed; the shared
expert's width is `moe_intermediate_size` x `n_shared_experts`; the order
of the two halves under W_eh; h_i is the main model's final-normed state.
And the cut (model-configs guide, section 4): the tree holds the routed
experts `first_expert .. first_expert + held - 1` of the published count;
the router scores every published expert, and what the absent experts
would add is left out.

On the chip the weights stay as the seed made them, in bfloat16, and each
is widened where it is used; the layers of one kind run as one `lax.scan`
over their stacked leaves, attention over blocks of query rows and the
experts one at a time, so that 16,384 positions fit beside nothing.

`precision` is the arithmetic of every matmul operand: "float32" the
reference proper, "bfloat16", and "fp8" (float8_e4m3 with a per-tensor
scale) — the CONTROL, the nearest precision below the stated bf16.
`latent` is the precision the normed latent and the shared key are HELD
in ("float32" | "fp8": a cache rounded below what the configuration
states). The others are what the reference is TOLD, the configuration's
by default, anything else a planted fault that `correct` must catch:
`rope` ("interleaved" | "split_half"), `rope_on_nope` (the rotation on
the un-rotated parts as well), `latent_norm` (False: c_kv left
un-normalised), `selection_bias` (False: the chosen are the largest of s
alone), `scaling` (False: `routed_scaling_factor` left out).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
QUERY_ROWS = 128          # a block of query rows sees every key at once
ATTENTION = ("norm_attn", "norm_ffn", "q_a_w", "q_a_norm", "q_b_w",
             "kv_a_w", "kv_a_norm", "k_b_w", "v_b_w", "o_w")
DENSE = ("gate_w", "up_w", "down_w")
EXPERTS = ("router_w", "router_bias", "shared_gate_w", "shared_up_w",
           "shared_down_w", "exp_gate_w", "exp_up_w", "exp_down_w")


def _to_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _round_operand(x, precision: str):
    x = x.astype(jnp.float32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        # as `reduce_precision`: the chip's compiler takes a pair of
        # converts out (benchmark/reference/jamba.py)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if precision == "fp8":
        return _to_fp8(x)
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round_operand(a, precision),
                      _round_operand(b, precision), precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta: float, rope: str):
    """x [T, heads, hd] at `positions` [T]. "interleaved" rotates the
    pairs (2i, 2i+1) — the model's `rope_interleave`; "split_half" rotates
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


def _attention(u, p, positions, arch: dict, precision: str, rope: str,
               rope_on_nope: bool, latent_norm: bool, latent: str):
    """Every position's latent decompressed into each head's key and value
    at once; queries, their scores against every key and their softmax one
    block of QUERY_ROWS rows at a time — the same sums as all rows at
    once, and what lets 16,384 positions of 32 heads fit."""
    T = u.shape[0]
    H, C = arch["num_heads"], arch["kv_lora_rank"]
    dn, dr = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    eps, theta = arch["layer_norm_eps"], arch["rope_theta"]
    kv = _mm("td,dc->tc", u, p["kv_a_w"], precision)
    c_kv, k_pe = kv[:, :C], _rope(kv[:, None, C:], positions, theta, rope)
    if latent_norm:
        c_kv = _rms_norm(c_kv, p["kv_a_norm"], eps)
    if latent == "fp8":
        c_kv, k_pe = _to_fp8(c_kv), _to_fp8(k_pe)
    elif latent != "float32":
        raise ValueError(f"unknown latent precision {latent!r}")
    k_nope = _mm("tc,ch->th", c_kv, p["k_b_w"], precision).reshape(T, H, dn)
    v = _mm("tc,ch->th", c_kv, p["v_b_w"], precision).reshape(T, H, -1)
    if rope_on_nope:
        k_nope = _rope(k_nope, positions, theta, rope)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (T, H, dr))], -1)
    rows = min(QUERY_ROWS, T)
    if T % rows:
        raise ValueError(f"{T} positions are no whole blocks of {rows}")
    keys = jnp.arange(T)[None, :]

    def block(start):
        ub = jax.lax.dynamic_slice_in_dim(u, start, rows, axis=0)
        at = jax.lax.dynamic_slice_in_dim(positions, start, rows)
        c_q = _rms_norm(_mm("td,dr->tr", ub, p["q_a_w"], precision),
                        p["q_a_norm"], eps)
        q = _mm("tr,rh->th", c_q, p["q_b_w"], precision).reshape(
            rows, H, dn + dr)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], at, theta, rope)
        if rope_on_nope:
            q_nope = _rope(q_nope, at, theta, rope)
        q = jnp.concatenate([q_nope, q_pe], -1)
        mask = keys <= (start + jnp.arange(rows))[:, None]
        s = _mm("ihd,jhd->hij", q, k, precision) / math.sqrt(dn + dr)
        pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        ctx = _mm("hij,jhd->ihd", pr, v, precision).reshape(rows, -1)
        return _mm("th,hd->td", ctx, p["o_w"], precision)

    return jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, -1)


def _mlp(u, gate_w, up_w, down_w, precision: str):
    g = jax.nn.silu(_mm("td,df->tf", u, gate_w, precision)) \
        * _mm("td,df->tf", u, up_w, precision)
    return _mm("tf,fd->td", g, down_w, precision)


def route(u, router_w, bias, arch: dict, selection_bias: bool = True,
          scaling: bool = True):
    """-> (the chosen experts [T, k], their weights [T, k]): float32 at the
    highest precision whatever `precision` the matmuls of the control use
    — the router is float32 in the model."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", u, router_w.astype(jnp.float32), precision=_HIGHEST))
    ranked = scores + bias.astype(jnp.float32) if selection_bias else scores
    _, chosen = jax.lax.top_k(ranked, arch["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if arch["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    if scaling:
        weight = weight * arch["routed_scaling_factor"]
    return chosen, weight


def _experts(u, p, arch: dict, precision: str, selection_bias: bool,
             scaling: bool):
    chosen, weight = route(u, p["router_w"], p["router_bias"], arch,
                           selection_bias, scaling)

    def held(total, e):           # this chip's experts, one at a time
        w = jnp.sum(jnp.where(chosen == arch["first_expert"] + e, weight,
                              0.0), -1)
        one = _mlp(u, *(jax.lax.dynamic_index_in_dim(
            p["exp_" + name], e, 0, keepdims=False) for name in DENSE),
            precision)
        return total + w[:, None] * one, None

    routed, _ = jax.lax.scan(held, jnp.zeros_like(u),
                             jnp.arange(p["exp_gate_w"].shape[0]))
    return routed + _mlp(u, p["shared_gate_w"], p["shared_up_w"],
                         p["shared_down_w"], precision)


def _stack(tree, at: int, ffn: tuple, ffn_at: int, rows: int):
    """`rows` blocks' leaves as a `lax.scan`'s xs: the attention leaves
    from layer `at` of their stacks on, the `ffn` leaves from `ffn_at`."""
    return {**{k: tree[k][at:at + rows] for k in ATTENTION},
            **{k: tree[k][ffn_at:ffn_at + rows] for k in ffn}}


def _blocks(tree, h, positions, at: int, dense: int, experts: int,
            arch: dict, *, expert_at: int = 0, precision: str = "float32",
            rope: str = "interleaved", rope_on_nope: bool = False,
            latent_norm: bool = True, latent: str = "float32",
            selection_bias: bool = True, scaling: bool = True):
    """`dense` dense blocks then `experts` expert blocks on h [T, D], the
    attention leaves from layer `at` of `tree`'s stacks on, the expert
    leaves from `expert_at`; the blocks of one kind are one `lax.scan`
    over their stacked leaves."""
    eps = arch["layer_norm_eps"]

    def block(ffn):
        def one(h, p):
            u = _rms_norm(h, p["norm_attn"], eps)
            h = h + _attention(u, p, positions, arch, precision, rope,
                               rope_on_nope, latent_norm, latent)
            return h + ffn(_rms_norm(h, p["norm_ffn"], eps), p), None
        return one

    if dense:
        h, _ = jax.lax.scan(
            block(lambda u, p: _mlp(u, p["gate_w"], p["up_w"], p["down_w"],
                                    precision)),
            h, _stack(tree, at, DENSE, 0, dense))
    if experts:
        h, _ = jax.lax.scan(
            block(lambda u, p: _experts(u, p, arch, precision,
                                        selection_bias, scaling)),
            h, _stack(tree, at + dense, EXPERTS, expert_at, experts))
    return h


def hidden(params, tokens, arch: dict, **kw):
    """tokens [T] -> the final-normed hidden state [T, D] float32. `arch`
    holds num_layers, first_k_dense_replace, num_heads, kv_lora_rank,
    qk_nope_head_dim, qk_rope_head_dim, rope_theta, layer_norm_eps (the
    config's `rms_norm_eps`), num_experts_per_tok, first_expert,
    norm_topk_prob, routed_scaling_factor."""
    h = jnp.take(params["wte"], tokens, axis=0).astype(jnp.float32)
    dense = arch["first_k_dense_replace"]
    h = _blocks(params, h, jnp.arange(tokens.shape[0]), 0, dense,
                arch["num_layers"] - dense, arch, **kw)
    return _rms_norm(h, params["norm_f"], arch["layer_norm_eps"])


def logits_at(params, tokens, first, count: int, arch: dict, **kw):
    """Logits [count, V] at positions first .. first + count - 1 of the
    sequence `tokens` [T] (`first` may be traced)."""
    x = jax.lax.dynamic_slice_in_dim(hidden(params, tokens, arch, **kw),
                                     first, count, axis=0)
    return _mm("td,vd->tv", x, params["head_w"],
               kw.get("precision", "float32"))


def forward(params, tokens, arch: dict, **kw):
    """tokens [T] -> logits [T, V] float32."""
    return logits_at(params, tokens, 0, tokens.shape[0], arch, **kw)


def mtp_logits(params, hidden_states, next_tokens, positions, arch: dict,
               depth: int = 0, **kw):
    """The multi-token-prediction module `depth` (the tree's `mtp_*`
    leaves) on one sequence: hidden_states [T, D] (the main model's
    final-normed h_i), next_tokens [T] (t_{i+1}), positions [T] -> logits
    [T, V] for t_{i+2}."""
    tree = {k[len("mtp_"):]: v for k, v in params.items()
            if k.startswith("mtp_")}
    eps = arch["layer_norm_eps"]
    precision = kw.get("precision", "float32")
    emb = jnp.take(params["wte"], next_tokens, axis=0).astype(jnp.float32)
    both = jnp.concatenate(
        [_rms_norm(emb, tree["norm_e"][depth], eps),
         _rms_norm(hidden_states.astype(jnp.float32), tree["norm_h"][depth],
                   eps)], axis=-1)
    h = _mm("te,ed->td", both, tree["eh_w"][depth], precision)
    h = _blocks(tree, h, positions, depth, 0, 1, arch, expert_at=depth, **kw)
    return _mm("td,vd->tv", _rms_norm(h, tree["norm_f"][depth], eps),
               params["head_w"], precision)
