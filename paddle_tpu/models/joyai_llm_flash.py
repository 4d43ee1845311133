"""JoyAI-LLM-Flash decoder (`model_type: joyai_llm_flash`, JoyAI-LLM Flash
48B-A2.7B): pre-norm blocks `x += Attn(RMSNorm x); x += FFN(RMSNorm x)`
whose attention is LATENT (MLA) on every layer, whose FFN is a dense
gated-SiLU MLP on the first `first_k_dense_replace` layers and sigmoid-
routed experts plus a shared expert on the rest, and which carries a
multi-token-prediction module beside its untied head.

Serving only (inference/serving.py `family="joyai_llm_flash"`): ONE cached
forward for prefill (T = bucket) and decode (T = 1), the cache factory and
the bucketed prefill into a slot. What differs from the other families:

- the cache holds the attention's LATENT, not keys and values: `ckv`
  [L, slots, positions, kv_lora_rank] (the normed compression every head's
  key and value are made from) and `kpe` [L, slots, positions,
  qk_rope_head_dim] (the ONE rotated key all heads share). Held by position
  like K/V but with no head axis: 576 values a position a layer where
  per-head K/V would be 32 x (192 + 128). Two pools and not one 576-wide
  one, because the two are read differently: `ckv` is both the key and the
  value of the absorbed form (scores and output contract over it), `kpe`
  only ever meets the rotated part of the query;
- TWO ATTENTION PATHS, chosen by the static T of the call and nothing else.
  A prompt (T > 1) DECOMPRESSES: `ckv W_kvb` gives every head its 128-wide
  key and value, and causal blocked attention runs at 192-wide q/k and
  128-wide v over the prompt's own positions (per position it costs
  2 x 32 x (192 + 128) a key; the absorbed form 2 x 32 x (576 + 512)).
  The tick (T = 1) ABSORBS: `W_kvb`'s key half goes into the query
  (`q_lat[h] = q_nope[h] W_kvb^K[h]`), its value half onto the output, and
  32 heads attend over the latent pool itself — no per-head key or value
  of a cached position ever exists. On a TPU that attention is the Pallas
  kernel of kernels/latent_attention.py over each live row's blocks, every
  `ckv` block read once for scores and output; elsewhere two masked
  einsums over the whole layer (`absorbed_engages` says which, by what
  the call shows);
- the layer scan is ONE scan per kind of layer (the leading dense layers,
  then the expert layers), both with the two pools in the carry, every
  layer's leaves read straight out of the whole stacks inside the body;
- the experts are parallel/moe.py's dropless layer under `noaux_tc`
  routing: float32 sigmoid scores over all published experts, the k chosen
  by score + `e_score_correction_bias`, weighted by the scores alone,
  normalised, times `routed_scaling_factor`; this chip computes the
  experts it HOLDS (`first_expert .. first_expert + experts_held - 1`) and
  what the absent ones would add is left out; the shared expert is
  computed once and added;
- `mtp_logits` is the multi-token-prediction module (a second head in the
  tree, the `mtp_*` leaves): it reuses the block's code, does not enter the
  next-token logits and is not run by the engine (drafting with it inside
  the tick is `spec_decode`, which the family refuses for now);
- the forward counts what it did (`COUNTS`) into the cache's "stats" leaf,
  which rides the engine's one pull.

Layout: the published `kv_b_proj` [H x (128 + 128), 512] is held as its two
halves, `k_b_w` / `v_b_w` [L, 512, H x 128], so that the absorbed form
reads each without slicing the other out.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..kernels.decode_attention import (_query_positions, blocked_attention,
                                        causal_pairs, layer_view,
                                        prompt_kernel_engages, write_kv)
from ..kernels.latent_attention import (LATENT_BLOCK,
                                        absorbed_attention_live_blocks,
                                        live_latent_plan)
from ..parallel.moe import dropless_experts, sigmoid_topk
from .llama import _apply_rope

__all__ = ["JoyaiLlmFlashConfig", "init_joyai_llm_flash_params", "init_cache",
           "joyai_llm_flash_forward_cached", "prefill_into_slot",
           "mtp_logits", "COUNTS", "span_counts", "prompt_counts"]

# a whole prompt's attention runs in blocks of this many positions
ATTENTION_BLOCK = 512
# prefix of the multi-token-prediction module's leaves
MTP = "mtp_"

# what one forward counts, in the order of the cache's "stats" leaf
# (int32): live (token, choice) pairs on held experts summed over the
# expert layers, the busiest held expert's pairs (largest over layers);
# latent positions ONE layer's tick attention may touch (the kernel's
# live blocks; every position under the einsums) and its pool holds
# (`span_counts` multiplies by the layers and the bytes)
COUNTS = ("expert_tokens", "expert_max_load", "kv_read_layer",
          "kv_pool_layer")


@dataclass
class JoyaiLlmFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_layers: int = 40                # dense and expert layers together
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_hidden: int = 7168              # a dense layer's MLP
    moe_ffn_hidden: int = 768           # ONE routed expert
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256         # published: the router's width
    experts_held: Optional[int] = None  # None -> all of them
    first_expert: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 1
    max_seq_len: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32000000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    prefill_chunk: int = 2048           # tokens a prompt's FFN runs at once

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        for ok, what in (
                (0 <= self.first_k_dense_replace <= self.num_layers,
                 "first_k_dense_replace lies within num_layers"),
                (self.qk_rope_head_dim % 2 == 0, "qk_rope_head_dim is even"),
                (0 <= self.first_expert and self.first_expert
                 + self.experts_held <= self.n_routed_experts,
                 "the held experts lie among the published ones"),
                (self.num_experts_per_tok <= self.n_routed_experts,
                 "num_experts_per_tok is at most n_routed_experts")):
            if not ok:
                raise ValueError(f"JoyaiLlmFlashConfig: {what}")

    @property
    def dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values ONE position of one layer holds in the cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def _attention_shapes(cfg, n: int) -> Dict[str, tuple]:
    d, h = cfg.hidden_size, cfg.num_heads
    return {
        "norm_attn": (n, d), "norm_ffn": (n, d),
        "q_a_w": (n, d, cfg.q_lora_rank), "q_a_norm": (n, cfg.q_lora_rank),
        "q_b_w": (n, cfg.q_lora_rank, h * cfg.qk_head_dim),
        "kv_a_w": (n, d, cfg.latent_width),
        "kv_a_norm": (n, cfg.kv_lora_rank),
        "k_b_w": (n, cfg.kv_lora_rank, h * cfg.qk_nope_head_dim),
        "v_b_w": (n, cfg.kv_lora_rank, h * cfg.v_head_dim),
        "o_w": (n, h * cfg.v_head_dim, d),
    }


def _expert_shapes(cfg, n: int) -> Dict[str, tuple]:
    d, f, e = cfg.hidden_size, cfg.moe_ffn_hidden, cfg.experts_held
    fs = f * cfg.n_shared_experts
    return {
        "router_w": (n, d, cfg.n_routed_experts),
        "router_bias": (n, cfg.n_routed_experts),
        "shared_gate_w": (n, d, fs), "shared_up_w": (n, d, fs),
        "shared_down_w": (n, fs, d),
        "exp_gate_w": (n, e, d, f), "exp_up_w": (n, e, d, f),
        "exp_down_w": (n, e, f, d),
    }


def param_shapes(cfg: JoyaiLlmFlashConfig, mtp: bool = True):
    """Leaf name -> shape. Attention leaves are stacked over all layers,
    a dense MLP's over the dense layers, an expert layer's over the expert
    layers; the `mtp_*` leaves are the multi-token-prediction module's (its
    blocks stacked over `num_nextn_predict_layers`)."""
    d = cfg.hidden_size
    ld, le, nm = cfg.dense_layers, cfg.expert_layers, \
        cfg.num_nextn_predict_layers
    shapes = {
        "wte": (cfg.vocab_size, d), "head_w": (cfg.vocab_size, d),
        "norm_f": (d,), **_attention_shapes(cfg, cfg.num_layers),
        "gate_w": (ld, d, cfg.ffn_hidden), "up_w": (ld, d, cfg.ffn_hidden),
        "down_w": (ld, cfg.ffn_hidden, d), **_expert_shapes(cfg, le),
    }
    if mtp and nm:
        module = {"norm_e": (nm, d), "norm_h": (nm, d),
                  "eh_w": (nm, 2 * d, d), "norm_f": (nm, d),
                  **_attention_shapes(cfg, nm), **_expert_shapes(cfg, nm)}
        shapes.update({MTP + k: v for k, v in module.items()})
    return shapes


# leaves kept in float32 whatever `param_dtype` is
F32_LEAVES = ("router_bias",)
_ATTENTION = tuple(_attention_shapes(JoyaiLlmFlashConfig(), 1))
_OUT = ("o_w", "down_w", "shared_down_w", "exp_down_w")
# the spread `e_score_correction_bias` is drawn at: non-zero, so that the
# choice by score + bias differs from the choice by score (at the published
# widths on 86% of tokens: benchmark/weights_joyai_llm_flash.py)
ROUTER_BIAS_STD = 0.02


def init_joyai_llm_flash_params(cfg: JoyaiLlmFlashConfig, key,
                                mtp: bool = True) -> Dict[str, Any]:
    """Seeded random parameters in `param_dtype` (norm scales near 1, the
    routing bias float32 and non-zero)."""
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    params = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg, mtp).items())):
        leaf = jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)
        own = name.removeprefix(MTP)
        if "norm" in own:
            leaf = 1.0 + 0.02 * leaf
        elif own == "router_bias":
            leaf = ROUTER_BIAS_STD * leaf
        else:
            leaf = (out_std if own in _OUT else 0.02) * leaf
        params[name] = leaf.astype(
            jnp.float32 if own in F32_LEAVES else cfg.param_dtype)
    return params


def init_cache(cfg: JoyaiLlmFlashConfig, batch: int, max_len: int):
    """-> the two latent pools, the slot on axis 1, in the activation
    dtype: `ckv` [L, B, max_len, kv_lora_rank], `kpe` [L, B, max_len,
    qk_rope_head_dim]; and the forward's counts, "stats"."""
    n = cfg.num_layers
    return {"ckv": jnp.zeros((n, batch, max_len, cfg.kv_lora_rank),
                             cfg.dtype),
            "kpe": jnp.zeros((n, batch, max_len, cfg.qk_rope_head_dim),
                             cfg.dtype),
            "stats": jnp.zeros((len(COUNTS),), jnp.int32)}


def span_counts(cfg: JoyaiLlmFlashConfig, stats) -> Dict[str, int]:
    """A pulled "stats" row as the counts the engine sets on its spans
    (ModelFamily.counts), in Python integers: the held experts' live pairs
    and the busiest's; for a tick, the latent positions its attention may
    touch — each live row's whole blocks under the kernel, every position
    of every slot under the einsums — over those the pools hold, and the
    bytes behind the former."""
    tokens, busiest, read, pool = (int(v) for v in stats)
    counts = {"expert_tokens": tokens, "expert_max_load": busiest}
    if pool:
        position = cfg.latent_width * jnp.dtype(cfg.dtype).itemsize
        counts.update(kv_positions_read=read * cfg.num_layers,
                      kv_positions_pool=pool * cfg.num_layers,
                      latent_bytes=read * cfg.num_layers * position)
    return counts


def prompt_counts(cfg: JoyaiLlmFlashConfig, bucket: int) -> Dict[str, int]:
    """What a prompt bucket's attention does, from its shapes alone
    (ModelFamily.prompt_counts): the (query, key) pairs the decompressed
    attention of every layer admits, summed over the layers, and those of
    them computed by the Pallas prompt kernel (`prompt_kernel_engages`,
    asked on the host of the same shapes: q/k 192 wide, v 128)."""
    pairs = cfg.num_layers * causal_pairs(0, bucket)
    kernel = prompt_kernel_engages(bucket, bucket, cfg.num_heads,
                                   cfg.num_heads, cfg.qk_head_dim,
                                   cfg.v_head_dim, cfg.dtype)
    return {"attn_pairs": pairs, "attn_pairs_kernel": pairs if kernel else 0}


def _at_layer(stack, at):
    """Layer `at` (a traced index) of a stacked leaf: a read XLA fuses
    into its consumer."""
    return jax.lax.dynamic_index_in_dim(stack, at, 0, keepdims=False)


def _rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return xf * r * scale.astype(jnp.float32)


def _rope_angles(positions, hd: int, theta: float):
    """(cos, sin) [..., hd/2] float32 at absolute `positions` [...]."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _project(lp, u, cos, sin, cfg: JoyaiLlmFlashConfig):
    """The latent attention's projections of u [B, T, D] -> (q_nope
    [B, T, H, 128], q_pe [B, T, H, 64] rotated, c_kv [B, T, 512] normed,
    k_pe [B, T, 64] rotated). RoPE (interleaved pairs) touches the `pe`
    parts only; both compressions are RMS-normed."""
    B, T, _ = u.shape
    f32, eps = jnp.float32, cfg.rms_norm_eps
    c_q = _rms_norm(jnp.einsum("btd,dr->btr", u, lp["q_a_w"],
                               preferred_element_type=f32),
                    lp["q_a_norm"], eps).astype(u.dtype)
    q = jnp.einsum("btr,rh->bth", c_q, lp["q_b_w"]).reshape(
        B, T, cfg.num_heads, cfg.qk_head_dim)
    kv = jnp.einsum("btd,dc->btc", u, lp["kv_a_w"],
                    preferred_element_type=f32)
    c_kv = _rms_norm(kv[..., :cfg.kv_lora_rank], lp["kv_a_norm"],
                     eps).astype(u.dtype)
    k_pe = _apply_rope(kv[..., None, cfg.kv_lora_rank:], cos, sin)
    q_pe = _apply_rope(q[..., cfg.qk_nope_head_dim:], cos, sin)
    return (q[..., :cfg.qk_nope_head_dim], q_pe, c_kv,
            k_pe[:, :, 0].astype(u.dtype))


def _decompressed(lp, q_nope, q_pe, c_kv, k_pe, cfg: JoyaiLlmFlashConfig):
    """The prompt path: every position's latent is decompressed into each
    head's key and value, and the queries attend the prompt's own
    positions causally in blocks -> ctx [B, T, H, v_head_dim]."""
    B, T = c_kv.shape[:2]
    H = cfg.num_heads
    k_nope = jnp.einsum("btc,ch->bth", c_kv, lp["k_b_w"]).reshape(
        B, T, H, cfg.qk_nope_head_dim)
    v = jnp.einsum("btc,ch->bth", c_kv, lp["v_b_w"]).reshape(
        B, T, H, cfg.v_head_dim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None], (B, T, H, cfg.qk_rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    return blocked_attention(q, k, v, block=ATTENTION_BLOCK)


def _masked_einsums(q_lat, q_pe, ckv, kpe, pos, cfg: JoyaiLlmFlashConfig):
    """Absorbed attention over ONE layer's whole latent rows ckv [B, S, C]
    / kpe [B, S, R] under the position mask: scores from both parts of
    the query, a float32 softmax over the positions 0 .. pos[b], the
    probabilities' sum over the latent -> o_lat [B, H, C] float32. Reads
    every position of every slot, `ckv` twice."""
    f32 = jnp.float32
    s = jnp.einsum("bhc,bsc->bhs", q_lat, ckv, preferred_element_type=f32) \
        + jnp.einsum("bhr,bsr->bhs", q_pe.astype(kpe.dtype), kpe,
                     preferred_element_type=f32)
    s = s / math.sqrt(cfg.qk_head_dim)
    seen = jnp.arange(ckv.shape[1], dtype=jnp.int32)[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p.astype(ckv.dtype), ckv,
                      preferred_element_type=f32)


def _absorbed(lp, q_nope, q_pe, ckv, kpe, at, pos, plan,
              cfg: JoyaiLlmFlashConfig):
    """The tick's path: one query a row, q_nope / q_pe [B, H, .], against
    layer `at` of the latent pools ckv [L, B, S, 512] / kpe [L, B, S, 64],
    of which row b sees positions 0 .. pos[b]. `W_kvb`'s key half is
    absorbed into the query and its value half applied to the output, so
    the H heads attend the latent itself (one shared 576-wide key) -> ctx
    [B, H, v_head_dim] float32. With a `plan` (`live_latent_plan`: a
    single-token step on a TPU) the attention between the two absorptions
    is the Pallas kernel over each row's live blocks, every block read
    once; without one, `_masked_einsums` over the whole layer."""
    f32 = jnp.float32
    H, C = cfg.num_heads, cfg.kv_lora_rank
    k_b = lp["k_b_w"].reshape(C, H, cfg.qk_nope_head_dim)
    v_b = lp["v_b_w"].reshape(C, H, cfg.v_head_dim)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, k_b,
                       preferred_element_type=f32).astype(ckv.dtype)
    if plan is not None:
        o_lat = absorbed_attention_live_blocks(q_lat, q_pe, ckv, kpe, at,
                                               plan, cfg.qk_head_dim)
    else:
        o_lat = _masked_einsums(q_lat, q_pe, layer_view(ckv, at),
                                layer_view(kpe, at), pos, cfg)
    return jnp.einsum("bhc,chv->bhv", o_lat.astype(ckv.dtype), v_b,
                      preferred_element_type=f32)


def _write_latent(pool, rows, pos, at, live):
    """The step's latent rows [B, T, W] into layer `at` of `pool`
    [L, B, S, W] at `pos`. A token that is not real (`live` [B, T] False:
    a prompt's padding, an idle slot's row) writes nothing: padding lands
    as zeros in the prompt's own cache, and an idle row's write is aimed
    past the end, where the scatter drops it (reading the row back to keep
    it made the compiler re-lay the whole pool out, every tick)."""
    if rows.shape[1] > 1:
        return write_kv(pool, jnp.where(live[..., None], rows, 0), pos, at)
    B, S = rows.shape[0], pool.shape[2]
    to = jnp.where(live[:, 0], jnp.broadcast_to(pos, (B,)), S)
    return pool.at[at, jnp.arange(B, dtype=jnp.int32), to].set(
        rows[:, 0].astype(pool.dtype), mode="drop")


def _attention(lp, u, ckv, kpe, at, pos, cos, sin, live, plan,
               cfg: JoyaiLlmFlashConfig):
    """Latent attention on u [B, T, D]: the step's latent goes into layer
    `at` of the pools, then the queries attend — a prompt (T > 1) its own
    decompressed positions, the tick (T = 1) the pool's latent, absorbed
    (`plan`: `_absorbed`) -> (out [B, T, D] float32, the pools)."""
    B, T, _ = u.shape
    q_nope, q_pe, c_kv, k_pe = _project(lp, u, cos, sin, cfg)
    ckv = _write_latent(ckv, c_kv, pos, at, live)
    kpe = _write_latent(kpe, k_pe, pos, at, live)
    if T > 1:
        with jax.named_scope("mla_prefill"):
            ctx = _decompressed(lp, q_nope, q_pe, c_kv, k_pe, cfg)
    else:
        with jax.named_scope("mla_absorbed"):
            ctx = _absorbed(lp, q_nope[:, 0], q_pe[:, 0], ckv, kpe, at,
                            jnp.broadcast_to(pos, (B,)), plan, cfg)[:, None]
    out = jnp.einsum("bth,hd->btd", ctx.astype(u.dtype).reshape(B, T, -1),
                     lp["o_w"], preferred_element_type=jnp.float32)
    return out, ckv, kpe


def _mlp(u, gate_w, up_w, down_w):
    g = jax.nn.silu(jnp.einsum("...d,df->...f", u, gate_w)) \
        * jnp.einsum("...d,df->...f", u, up_w)
    return jnp.einsum("...f,fd->...d", g, down_w,
                      preferred_element_type=jnp.float32)


def _experts(tree, j, h, live, cfg: JoyaiLlmFlashConfig):
    """Experts(h) for rows h [R, D] of expert layer `j` (a traced index
    into the stacks of `tree`) -> ([R, D] float32, load [Eh]): the held
    routed experts' part under `noaux_tc` routing plus the shared expert.
    The routed leaves stay whole stacks (dropless_experts picks layer j)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(h.astype(jnp.float32),
                         _at_layer(tree["router_w"], j).astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        choice, weight = sigmoid_topk(
            logits, cfg.num_experts_per_tok, normalize=cfg.norm_topk_prob,
            bias=_at_layer(tree["router_bias"], j),
            scale=cfg.routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        routed, load = dropless_experts(
            h, choice, weight, tree["exp_gate_w"], tree["exp_up_w"],
            tree["exp_down_w"], first=cfg.first_expert, live=live, layer=j)
    with jax.named_scope("shared_expert"):
        shared = _mlp(h, *(_at_layer(tree["shared_" + n], j)
                           for n in ("gate_w", "up_w", "down_w")))
    return routed + shared, load


def _residual(x, out):
    return (x.astype(jnp.float32) + out).astype(x.dtype)


def _block(tree, at, ffn, x, ckv, kpe, cache_at, pos, cos, sin, live,
           cfg: JoyaiLlmFlashConfig, plan=None):
    """One pre-norm block on x [B, T, D]: attention from the leaves at
    index `at` of `tree`'s stacks into layer `cache_at` of the pools (the
    tick's over `plan`, `_absorbed`), then `ffn(rows [R, D], live [R]) ->
    ([R, D] float32, extra)` on chunks of `prefill_chunk` tokens ->
    (x, ckv, kpe, the chunks' extras)."""
    B, T, D = x.shape
    lp = {k: _at_layer(tree[k], at) for k in _ATTENTION}
    u = _rms_norm(x, lp["norm_attn"], cfg.rms_norm_eps).astype(x.dtype)
    out, ckv, kpe = _attention(lp, u, ckv, kpe, cache_at, pos, cos, sin,
                               live, plan, cfg)
    x = _residual(x, out)
    u = _rms_norm(x, lp["norm_ffn"], cfg.rms_norm_eps).astype(x.dtype)
    chunk = min(cfg.prefill_chunk, T)
    # unrolled, not a loop: a loop's body would hold its own copy of every
    # weight it reads
    outs, extras = zip(*(
        ffn(u[:, s:s + chunk].reshape(-1, D), live[:, s:s + chunk].reshape(-1))
        for s in range(0, T, chunk)))
    out = jnp.concatenate([o.reshape(B, -1, D) for o in outs], axis=1)
    return _residual(x, out), ckv, kpe, extras


def _scan_layers(fn, carry, n: int):
    """`fn(carry, i) -> (carry, None)` over i = 0 .. n - 1: one scan for
    the kind of layer, or the body itself where there is one layer (a loop,
    even of one trip, keeps its own copy of each weight its body reads)."""
    if n == 0:
        return carry
    if n == 1:
        return fn(carry, 0)[0]
    return jax.lax.scan(fn, carry, jnp.arange(n, dtype=jnp.int32))[0]


def _hidden(params, tokens, cache, pos, cfg: JoyaiLlmFlashConfig, live=None):
    """tokens [B, T] -> (the final-normed hidden state [B, T, D], the
    updated cache). `pos` as in models/llama.py; `live` [B, T] marks the
    rows that are real tokens (None: all)."""
    B, T = tokens.shape
    if T > 1 and not (isinstance(pos, int) and pos == 0):
        raise ValueError(
            "joyai_llm_flash: a run of several tokens is a whole prompt "
            "into an empty cache (the engine's prefill); a run that "
            "continues a cache — prefill_chunk, spec_decode, multi_tick — "
            "the family refuses")
    if T % min(cfg.prefill_chunk, T) or T % min(ATTENTION_BLOCK, T):
        raise ValueError(f"prompt bucket {T} is no multiple of "
                         f"prefill_chunk {cfg.prefill_chunk} and the "
                         f"attention block {ATTENTION_BLOCK}")
    if live is None:
        live = jnp.ones((B, T), bool)
    cos, sin = _rope_angles(_query_positions(pos, B, T), cfg.qk_rope_head_dim,
                            cfg.rope_theta)
    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)
    n_dense = cfg.dense_layers
    # once, ahead of both scans: inside them XLA would redo the small
    # index arithmetic every layer
    plan = live_latent_plan(T, cache["ckv"], pos, live)

    def dense_layer(carry, i):
        x, ckv, kpe = carry

        def ffn(rows, _):
            with jax.named_scope("dense_mlp"):
                return _mlp(rows, *(_at_layer(params[n], i) for n in
                                    ("gate_w", "up_w", "down_w"))), None
        x, ckv, kpe, _ = _block(params, i, ffn, x, ckv, kpe, i, pos, cos,
                                sin, live, cfg, plan)
        return (x, ckv, kpe), None

    def expert_layer(carry, j):
        x, ckv, kpe, on_held, busiest = carry
        x, ckv, kpe, loads = _block(
            params, n_dense + j, lambda rows, lv: _experts(params, j, rows,
                                                           lv, cfg),
            x, ckv, kpe, n_dense + j, pos, cos, sin, live, cfg, plan)
        load = sum(loads)
        return (x, ckv, kpe, on_held + load.sum(),
                jnp.maximum(busiest, load.max())), None

    zero = jnp.zeros((), jnp.int32)
    carry = _scan_layers(dense_layer, (x, cache["ckv"], cache["kpe"]),
                         n_dense)
    x, ckv, kpe, on_held, busiest = _scan_layers(
        expert_layer, carry + (zero, zero), cfg.expert_layers)
    x = _rms_norm(x, params["norm_f"], cfg.rms_norm_eps).astype(cfg.dtype)
    # the tick's einsums read every position of every slot, whatever is
    # live; the kernel the blocks its work list names
    held = B * ckv.shape[2] if T == 1 else 0
    read = held if plan is None else plan[3][0] * LATENT_BLOCK
    stats = jnp.stack([on_held, busiest, jnp.int32(read), jnp.int32(held)])
    return x, {"ckv": ckv, "kpe": kpe, "stats": stats}


def _head(params, x):
    """Untied head: logits = x . head_w^T, float32."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,vd->btv", x, params["head_w"],
                          preferred_element_type=jnp.float32)


def joyai_llm_flash_forward_cached(params, tokens, cache, pos,
                                   cfg: JoyaiLlmFlashConfig, live=None):
    """Forward tokens [B, T] against a cache holding `pos` tokens ->
    (logits [B, T, V] float32, updated cache): the families' contract
    (models/llama.py), over the latent pools of `init_cache`. T = 1 with
    per-row `pos` is the decode tick (absorbed attention over the pool);
    T > 1 at the literal position 0 is a whole prompt into an empty cache
    (decompressed attention over its own positions). `live` [B, T] bool
    marks the real tokens: the others write nothing into the pools and are
    left out of the counts in the cache's "stats"."""
    x, cache = _hidden(params, tokens, cache, pos, cfg, live)
    return _head(params, x), cache


def prefill_into_slot(params, cache, padded, true_len, slot,
                      cfg: JoyaiLlmFlashConfig):
    """The engine's bucketed prefill of ONE request (padded [1, bucket],
    `true_len` real tokens) into slot `slot` of the pools -> (the last
    real position's logits [1, V] float32, the pools). The prompt runs
    through an empty cache of its own, whose rows (zeros past `true_len`)
    replace the slot's up to the bucket; the rest is behind the position
    mask. Only the one row of logits a token is sampled from is ever
    computed."""
    T = padded.shape[1]
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < true_len
    x, mini = _hidden(params, padded, init_cache(cfg, 1, T), 0, cfg, live)
    last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
    out = {"stats": mini["stats"]}
    for name in ("ckv", "kpe"):
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], mini[name], (0, slot, 0, 0))
    return _head(params, last)[:, 0], out


def mtp_logits(params, hidden, next_tokens, positions,
               cfg: JoyaiLlmFlashConfig, depth: int = 0):
    """The multi-token-prediction module `depth` on whole sequences:
    hidden [B, T, D] (the main model's final-normed state at positions
    i = 0 .. T - 1), next_tokens [B, T] (t_{i+1}), positions [B, T] ->
    logits [B, T, V] float32 for t_{i+2}:

        h' = W_eh [RMSNorm_e(Emb(t_{i+1})); RMSNorm_h(h_i)]
        h' -> one whole block (latent attention + experts) -> RMSNorm_f
        -> the SHARED head.

    It reuses the block's code (the prompt path over a cache of its own)
    and never enters the next-token logits."""
    tree = {k.removeprefix(MTP): v for k, v in params.items()
            if k.startswith(MTP)}
    B, T = next_tokens.shape
    eps = cfg.rms_norm_eps
    emb = jnp.take(params["wte"], next_tokens, axis=0).astype(cfg.dtype)
    both = jnp.concatenate(
        [_rms_norm(emb, tree["norm_e"][depth], eps),
         _rms_norm(hidden, tree["norm_h"][depth], eps)],
        axis=-1).astype(cfg.dtype)
    x = jnp.einsum("bte,ed->btd", both, tree["eh_w"][depth],
                   preferred_element_type=jnp.float32).astype(cfg.dtype)
    cos, sin = _rope_angles(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    # the block writes its latent into a one-layer cache of its own
    x, _, _, _ = _block(
        tree, depth, lambda rows, lv: _experts(tree, depth, rows, lv, cfg),
        x, jnp.zeros((1, B, T, cfg.kv_lora_rank), cfg.dtype),
        jnp.zeros((1, B, T, cfg.qk_rope_head_dim), cfg.dtype), 0, 0, cos,
        sin, jnp.ones((B, T), bool), cfg)
    x = _rms_norm(x, tree["norm_f"][depth], eps).astype(cfg.dtype)
    return _head(params, x)
