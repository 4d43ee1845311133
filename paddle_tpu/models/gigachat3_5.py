"""GigaChat3.5 decoder (`model_type: gigachat3_5`, GigaChat3.5-432B-A28B):
a HYBRID stack whose mixer is gated delta-rule linear attention
(GatedDeltaNet) on three of every four layers and latent attention (MLA)
on the `full_attention_layers`, whose FFN is a dense gated-SiLU MLP on the
first `first_k_dense_replace` layers and sigmoid-routed experts plus a
shared expert on the rest, with "sandwich" norms around both:

    h = x + N2(Mixer(N1 x));   y = h + N4(FFN(N3 h))

every N a ZERO-CENTRED RMSNorm, x / rms(x) * (1 + w).

Serving only (inference/serving.py `family="gigachat3_5"`): ONE cached
forward for prefill (T = bucket) and decode (T = 1), the cache factory and
the bucketed prefill into a slot. What differs from the other families:

- the cache holds TWO KINDS OF STATE. The MLA layers keep joyai_llm_flash's
  latent pools by position (`ckv` [La, slots, positions, kv_lora_rank],
  `kpe` [La, slots, positions, qk_rope_head_dim]). A linear-attention layer
  keeps, whatever the context, a MATRIX per value head (`dn_state`
  [Ll, slots, value heads, key dim, value dim] float32) and the last
  `linear_conv_kernel_dim - 1` rows that entered its convolution
  (`dn_conv` [Ll, slots, K - 1, conv channels]). These have no position
  axis: a step overwrites them, so (as in models/jamba.py) a padded
  position and an idle row must not advance them, and admission replaces
  a slot's rows whole;
- the linear layer (`_gated_delta`): q, k [32 key heads x 128] and v, z
  [64 value heads x 128] from the input; a causal depthwise convolution
  of width 4, no bias, then SiLU over [q; k; v]; q and k L2-normed, q
  scaled by 1/sqrt(key dim); value head j reads key head j // 2; beta =
  sigmoid(x W_b), g = -exp(A_log) softplus(x W_a + dt_bias); the delta
  rule of kernels/gated_delta.py (chunked for a prompt, one step for the
  tick); each head's output RMS-normed (one zero-centred scale shared by
  the heads) and gated by `linear_sigmoid_gate_scale` x sigmoid(z);
- the MLA layer is joyai_llm_flash's — its projections, both attention
  paths, the latent writes and the absorbed Pallas kernel, imported — with
  YaRN frequencies on the 64 rotated dims, the YaRN softmax factor mscale^2
  folded into the query (into the scale of its compression's norm, so the
  shared attention code keeps its 1/sqrt(qk_head_dim)), and an output gate
  ctx * sigmoid(x W_g) before o_proj;
- the FFN is joyai_llm_flash's (`noaux_tc` routing over all published
  experts, this chip computing the ones it holds, the shared expert added
  unweighted) with every gated product clamped at `swiglu_limit`
  (parallel/moe.swiglu);
- the layer scan is one scan per kind of FFN (the leading dense layers,
  then the expert layers), each over PERIODS of its mixer pattern, with all
  four pools in the carry and every layer's leaves read straight out of
  the whole stacks inside the body; a kind with one period is the body
  itself;
- the forward counts what it did (`COUNTS`) into the cache's "stats" leaf,
  which rides the engine's one pull.

Not here: the multi-token-prediction modules (`num_nextn_predict_layers`),
which serving drops unless it drafts, and drafting is `spec_decode`, which
the family refuses.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.decode_attention import (_query_positions, causal_pairs,
                                        prompt_kernel_engages)
from ..kernels.gated_delta import (CHUNK, gated_delta_chunked,
                                   gated_delta_step)
from ..kernels.latent_attention import LATENT_BLOCK, live_latent_plan
from . import joyai_llm_flash as mla
from .jamba import _put_layer

__all__ = ["GigaChat35Config", "init_gigachat3_5_params", "init_cache",
           "gigachat3_5_forward_cached", "prefill_into_slot", "COUNTS",
           "span_counts", "prompt_counts", "slot_state_bytes",
           "yarn_inv_freq"]

LINEAR, FULL = "linear_attention", "full_attention"
# a whole prompt's MLA runs in blocks of this many positions
ATTENTION_BLOCK = mla.ATTENTION_BLOCK

# what one forward counts, in the order of the cache's "stats" leaf
# (int32): live (token, choice) pairs on held experts summed over the
# expert layers, the busiest held expert's pairs (largest over layers);
# latent positions ONE MLA layer's tick may touch and its pool holds (as
# joyai_llm_flash.COUNTS); rows whose linear-attention state the tick
# read and wrote
COUNTS = ("expert_tokens", "expert_max_load", "kv_read_layer",
          "kv_pool_layer", "state_rows")


@dataclass
class GigaChat35Config:
    vocab_size: int = 128256
    hidden_size: int = 7168
    num_layers: int = 40
    full_attention_layers: Tuple[int, ...] = tuple(range(3, 40, 4))
    # latent attention (MLA), joyai_llm_flash's names
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # gated delta-rule linear attention
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_sigmoid_gate_scale: float = 2.0
    linear_attn_o_norm_eps: float = 1e-6
    # FFN
    ffn_hidden: int = 18432             # a dense layer's MLP
    moe_ffn_hidden: int = 2048          # ONE routed expert
    first_k_dense_replace: int = 3
    n_routed_experts: int = 256         # published: the router's width
    experts_held: Optional[int] = None  # None -> all of them
    first_expert: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    swiglu_limit: Optional[float] = 10.0
    # positions: RoPE with YaRN frequencies and softmax factor
    max_seq_len: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 100000.0
    rope_factor: float = 8.0
    original_max_position_embeddings: int = 32768
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0
    use_mla_scaling_factor: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    prefill_chunk: int = 1024           # tokens a prompt's FFN runs at once

    def __post_init__(self):
        self.full_attention_layers = tuple(self.full_attention_layers)
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        for ok, what in (
                (0 <= self.first_k_dense_replace <= self.num_layers,
                 "first_k_dense_replace lies within num_layers"),
                (self.qk_rope_head_dim % 2 == 0, "qk_rope_head_dim is even"),
                (self.linear_num_value_heads % self.linear_num_key_heads
                 == 0, "linear key heads divide the value heads"),
                (self.linear_conv_kernel_dim >= 2,
                 "linear_conv_kernel_dim is at least 2"),
                (0 <= self.first_expert and self.first_expert
                 + self.experts_held <= self.n_routed_experts,
                 "the held experts lie among the published ones"),
                (self.num_experts_per_tok <= self.n_routed_experts,
                 "num_experts_per_tok is at most n_routed_experts")):
            if not ok:
                raise ValueError(f"GigaChat35Config: {what}")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(FULL if i in self.full_attention_layers else LINEAR
                     for i in range(self.num_layers))

    def layers_of(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

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
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the linear layer's convolution: [q; k; v]."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def query_scale(self) -> float:
        """The YaRN softmax factor mscale^2 (`use_mla_scaling_factor`):
        mscale = 0.1 mscale_all_dim ln(factor) + 1, 1.2079 at factor 8."""
        if not self.use_mla_scaling_factor or self.rope_factor <= 1:
            return 1.0
        m = 0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0
        return m * m


def _norm_shapes(n: int, d: int) -> Dict[str, tuple]:
    return {"norm_mix_in": (n, d), "norm_mix_out": (n, d),
            "norm_ffn_in": (n, d), "norm_ffn_out": (n, d)}


def _linear_shapes(cfg: GigaChat35Config, n: int) -> Dict[str, tuple]:
    d, hv = cfg.hidden_size, cfg.linear_num_value_heads
    qk = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    vd = hv * cfg.linear_value_head_dim
    return {"dn_q_w": (n, d, qk), "dn_k_w": (n, d, qk), "dn_v_w": (n, d, vd),
            "dn_z_w": (n, d, vd), "dn_b_w": (n, d, hv), "dn_a_w": (n, d, hv),
            "dn_conv_w": (n, cfg.linear_conv_kernel_dim, cfg.conv_dim),
            "dn_a_log": (n, hv), "dn_dt_bias": (n, hv),
            "dn_o_norm": (n, cfg.linear_value_head_dim),
            "dn_o_w": (n, vd, d)}


def _full_shapes(cfg: GigaChat35Config, n: int) -> Dict[str, tuple]:
    shapes = {k: v for k, v in mla._attention_shapes(cfg, n).items()
              if not k.startswith("norm_")}
    shapes["attn_gate_w"] = (n, cfg.hidden_size,
                             cfg.num_heads * cfg.v_head_dim)
    return shapes


def param_shapes(cfg: GigaChat35Config) -> Dict[str, tuple]:
    """Leaf name -> shape. The four norms are stacked over all layers, a
    mixer's leaves over the layers of its kind, a dense MLP's over the
    dense layers, an expert layer's over the expert layers (the held
    experts on the next axis)."""
    d, ld = cfg.hidden_size, cfg.dense_layers
    return {
        "wte": (cfg.vocab_size, d), "head_w": (cfg.vocab_size, d),
        "norm_f": (d,), **_norm_shapes(cfg.num_layers, d),
        **_linear_shapes(cfg, cfg.layers_of(LINEAR)),
        **_full_shapes(cfg, cfg.layers_of(FULL)),
        "gate_w": (ld, d, cfg.ffn_hidden), "up_w": (ld, d, cfg.ffn_hidden),
        "down_w": (ld, cfg.ffn_hidden, d),
        **mla._expert_shapes(cfg, cfg.expert_layers),
    }


# leaves kept in float32 whatever `param_dtype` is
F32_LEAVES = ("router_bias", "dn_a_log", "dn_dt_bias")
_NORMS = tuple(_norm_shapes(1, 1))
_LINEAR = tuple(_linear_shapes(GigaChat35Config(), 1))
_FULL = tuple(_full_shapes(GigaChat35Config(), 1))
_OUT = ("o_w", "dn_o_w", "down_w", "shared_down_w", "exp_down_w")


def init_gigachat3_5_params(cfg: GigaChat35Config, key) -> Dict[str, Any]:
    """Seeded random parameters in `param_dtype`: matrices normal, the
    zero-centred norm scales near 0, the routing bias float32 and
    non-zero, and what decides whether the linear state remembers as the
    GatedDeltaNet paper draws it (A uniform in [0, 16], dt log-uniform in
    [0.001, 0.1] under its inverse softplus), float32."""
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    params = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name == "dn_a_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              1e-4, 16.0))
        elif name == "dn_dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "dn_conv_w":
            bound = 1.0 / math.sqrt(cfg.linear_conv_kernel_dim)
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            leaf = jax.random.normal(k, shape, jnp.float32)
            if name in ("q_a_norm", "kv_a_norm"):
                leaf = 1.0 + 0.02 * leaf
            elif "norm" in name:
                leaf = 0.02 * leaf
            elif name == "router_bias":
                leaf = mla.ROUTER_BIAS_STD * leaf
            else:
                leaf = (out_std if name in _OUT else 0.02) * leaf
        params[name] = leaf.astype(
            jnp.float32 if name in F32_LEAVES else cfg.param_dtype)
    return params


def init_cache(cfg: GigaChat35Config, batch: int, max_len: int):
    """-> the pools by kind of state, the slot on axis 1: the MLA layers'
    latent `ckv` [La, B, max_len, kv_lora_rank] and `kpe` [La, B, max_len,
    qk_rope_head_dim] and the linear layers' convolution rows `dn_conv`
    [Ll, B, K - 1, conv channels] in the activation dtype, their delta-rule
    state `dn_state` [Ll, B, value heads, key dim, value dim] float32; and
    the forward's counts, "stats". An all-zero row is a slot no token has
    entered."""
    la, ll = cfg.layers_of(FULL), cfg.layers_of(LINEAR)
    return {"ckv": jnp.zeros((la, batch, max_len, cfg.kv_lora_rank),
                             cfg.dtype),
            "kpe": jnp.zeros((la, batch, max_len, cfg.qk_rope_head_dim),
                             cfg.dtype),
            "dn_state": jnp.zeros((ll, batch, cfg.linear_num_value_heads,
                                   cfg.linear_key_head_dim,
                                   cfg.linear_value_head_dim), jnp.float32),
            "dn_conv": jnp.zeros((ll, batch, cfg.linear_conv_kernel_dim - 1,
                                  cfg.conv_dim), cfg.dtype),
            "stats": jnp.zeros((len(COUNTS),), jnp.int32)}


def slot_state_bytes(cfg: GigaChat35Config) -> int:
    """Delta-rule state and convolution rows ONE slot holds, every linear
    layer, whatever its context."""
    return cfg.layers_of(LINEAR) * (
        cfg.linear_num_value_heads * cfg.linear_key_head_dim
        * cfg.linear_value_head_dim * 4
        + (cfg.linear_conv_kernel_dim - 1) * cfg.conv_dim
        * jnp.dtype(cfg.dtype).itemsize)


def span_counts(cfg: GigaChat35Config, stats) -> Dict[str, int]:
    """A pulled "stats" row as the counts the engine sets on its spans
    (ModelFamily.counts), in Python integers: the held experts' live pairs
    and the busiest's; for a tick, the bytes of linear-attention state and
    convolution rows its live rows read and wrote, the latent positions
    its MLA may touch (each live row's whole blocks under the kernel,
    every position of every slot under the einsums) over those the pools
    hold, and the latent bytes behind the former."""
    tokens, busiest, read, pool, rows = (int(v) for v in stats)
    counts = {"expert_tokens": tokens, "expert_max_load": busiest}
    if pool:
        layers = cfg.layers_of(FULL)
        position = cfg.latent_width * jnp.dtype(cfg.dtype).itemsize
        counts.update(state_bytes=2 * rows * slot_state_bytes(cfg),
                      kv_bytes=read * layers * position,
                      kv_positions_read=read * layers,
                      kv_positions_pool=pool * layers)
    return counts


def prompt_counts(cfg: GigaChat35Config, bucket: int) -> Dict[str, int]:
    """What a prompt bucket does, from its shapes alone
    (ModelFamily.prompt_counts): the chunks the delta rule of every linear
    layer walks, summed over the layers; the (query, key) pairs the MLA
    layers admit and those of them computed by the Pallas prompt kernel
    (`prompt_kernel_engages`, asked on the host of the same shapes)."""
    pairs = cfg.layers_of(FULL) * causal_pairs(0, bucket)
    kernel = prompt_kernel_engages(bucket, bucket, cfg.num_heads,
                                   cfg.num_heads, cfg.qk_head_dim,
                                   cfg.v_head_dim, cfg.dtype)
    return {"scan_chunks": cfg.layers_of(LINEAR) * -(-bucket // CHUNK),
            "attn_pairs": pairs, "attn_pairs_kernel": pairs if kernel else 0}


def yarn_inv_freq(cfg: GigaChat35Config) -> np.ndarray:
    """RoPE's inverse frequencies [qk_rope_head_dim / 2] under YaRN
    (DeepSeek-V3's form): the fast dimensions keep theta's frequencies,
    the slow ones are divided by `rope_factor`, a linear ramp between the
    dimensions that turn `beta_fast` and `beta_slow` times over the
    original context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)

    def turns_at(rotations):
        return dim * math.log(cfg.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turns_at(cfg.beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    keep = 1.0 - ramp
    return (extra / cfg.rope_factor * ramp + extra * keep).astype(np.float32)


def _rope_angles(positions, cfg: GigaChat35Config):
    """(cos, sin) [..., rope dims / 2] float32 at absolute `positions`."""
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(yarn_inv_freq(cfg))
    return jnp.cos(ang), jnp.sin(ang)


def _zc_norm(x, w, eps):
    """Zero-centred RMSNorm: x / rms(x) * (1 + w), float32."""
    return mla._rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _gated_delta(lp, u, conv, state, at, lengths, cfg: GigaChat35Config):
    """The linear-attention mixer on u [B, T, D] (one step of B rows, or
    B = 1 and a run of T) from the rows layer `at` of the pools `conv`
    [Ll, B, K-1, C] and `state` [Ll, B, Hv, dk, dv] carry; `lengths` [B]
    of the T tokens are real -> (out [B, T, D] float32, the pools with
    that layer's rows as they stand after the last real one, written in
    place)."""
    B, T, _ = u.shape
    f32 = jnp.float32
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv, K = (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                 cfg.linear_conv_kernel_dim)
    with jax.named_scope("gated_delta/proj"):
        qkv = jnp.concatenate(
            [jnp.einsum("btd,dc->btc", u, lp[n])
             for n in ("dn_q_w", "dn_k_w", "dn_v_w")], axis=-1)
        z = jnp.einsum("btd,dc->btc", u, lp["dn_z_w"],
                       preferred_element_type=f32)
        b = jnp.einsum("btd,dh->bth", u, lp["dn_b_w"],
                       preferred_element_type=f32)
        a = jnp.einsum("btd,dh->bth", u, lp["dn_a_w"],
                       preferred_element_type=f32)
    with jax.named_scope("gated_delta/conv"):
        held = mla._at_layer(conv, at)
        rows = jnp.concatenate([held, qkv], axis=1)      # [B, K-1+T, C]
        w = lp["dn_conv_w"].astype(f32)
        x = jax.nn.silu(sum(w[i] * rows[:, i:i + T].astype(f32)
                            for i in range(K)))
        if T == 1:
            held = jnp.where((lengths > 0)[:, None, None], rows[:, 1:], held)
        else:
            # the K-1 rows before each row's true end
            held = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
                r, n, K - 1, axis=0))(rows, lengths)
        conv = _put_layer(conv, held, at)
    q, k, v = jnp.split(x, [hk * dk, 2 * hk * dk], axis=-1)
    q = jnp.repeat(_l2(q.reshape(B, T, hk, dk)) / math.sqrt(dk), hv // hk,
                   axis=2)
    k = jnp.repeat(_l2(k.reshape(B, T, hk, dk)), hv // hk, axis=2)
    v = v.reshape(B, T, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(lp["dn_a_log"]) * jax.nn.softplus(a + lp["dn_dt_bias"])
    held = mla._at_layer(state, at)
    if T == 1:
        with jax.named_scope("gated_delta/step"):
            o, held = gated_delta_step(held, q[:, 0], k[:, 0], v[:, 0],
                                       g[:, 0], beta[:, 0], live=lengths > 0)
            o = o[:, None]
            state = _put_layer(state, held, at)
    else:
        with jax.named_scope("gated_delta/chunked"):
            # one sequence, unbatched (as jamba's scan)
            o, held = gated_delta_chunked(q[0], k[0], v[0], g[0], beta[0],
                                          held[0], lengths[0])
            o, held = o[None], held[None]
            state = _put_layer(state, held, at)
    with jax.named_scope("gated_delta/out"):
        gate = cfg.linear_sigmoid_gate_scale * jax.nn.sigmoid(
            z.reshape(B, T, hv, dv))
        o = _zc_norm(o, lp["dn_o_norm"], cfg.linear_attn_o_norm_eps) * gate
        out = jnp.einsum("btc,cd->btd", o.astype(u.dtype).reshape(B, T, -1),
                         lp["dn_o_w"], preferred_element_type=f32)
    return out, conv, state


def _latent_attention(lp, u, ckv, kpe, at, pos, cos, sin, live, plan,
                      cfg: GigaChat35Config):
    """joyai_llm_flash's latent attention on u [B, T, D] into layer `at`
    of the latent pools, with the YaRN softmax factor folded into the
    query and the output gate before o_proj -> (out [B, T, D] float32,
    the pools)."""
    B, T, _ = u.shape
    f32 = jnp.float32
    if cfg.query_scale != 1.0:
        lp = {**lp, "q_a_norm": lp["q_a_norm"].astype(f32)
              * cfg.query_scale}
    q_nope, q_pe, c_kv, k_pe = mla._project(lp, u, cos, sin, cfg)
    ckv = mla._write_latent(ckv, c_kv, pos, at, live)
    kpe = mla._write_latent(kpe, k_pe, pos, at, live)
    if T > 1:
        with jax.named_scope("mla_prefill"):
            ctx = mla._decompressed(lp, q_nope, q_pe, c_kv, k_pe, cfg)
    else:
        with jax.named_scope("mla_absorbed"):
            ctx = mla._absorbed(lp, q_nope[:, 0], q_pe[:, 0], ckv, kpe, at,
                                jnp.broadcast_to(pos, (B,)), plan,
                                cfg)[:, None]
    gate = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", u, lp["attn_gate_w"],
                                     preferred_element_type=f32))
    ctx = ctx.astype(f32).reshape(B, T, -1) * gate
    out = jnp.einsum("bth,hd->btd", ctx.astype(u.dtype), lp["o_w"],
                     preferred_element_type=f32)
    return out, ckv, kpe


def _ffn(ffn, u, live, chunk: int):
    """`ffn(rows [R, D], live [R]) -> ([R, D] float32, extra)` over u
    [B, T, D] in chunks of `chunk` tokens, unrolled (a loop's body would
    hold its own copy of every weight it reads) -> ([B, T, D] float32,
    the chunks' extras)."""
    B, T, D = u.shape
    chunk = min(chunk, T)
    outs, extras = zip(*(
        ffn(u[:, s:s + chunk].reshape(-1, D), live[:, s:s + chunk].reshape(-1))
        for s in range(0, T, chunk)))
    return jnp.concatenate([o.reshape(B, -1, D) for o in outs], 1), extras


def _period(types: Tuple[str, ...]) -> Tuple[str, ...]:
    """The shortest pattern `types` repeats."""
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and types == types[:p] * (len(types) // p):
            return types[:p]
    return types


def _hidden(params, tokens, cache, pos, cfg: GigaChat35Config, live=None):
    """tokens [B, T] -> (the final-normed hidden state [B, T, D], the
    updated cache). `pos` as in models/llama.py; `live` [B, T] marks the
    real tokens, a prefix of each row (None: all)."""
    B, T = tokens.shape
    if T > 1 and (B > 1 or not (isinstance(pos, int) and pos == 0)):
        raise ValueError(
            "gigachat3_5: a run of several tokens is ONE whole prompt into "
            "an empty cache (the engine's prefill); a run that continues a "
            "cache or batches prompts — prefill_chunk, spec_decode, "
            "multi_tick — the family refuses")
    if T % min(cfg.prefill_chunk, T) or T % min(ATTENTION_BLOCK, T):
        raise ValueError(f"prompt bucket {T} is no multiple of "
                         f"prefill_chunk {cfg.prefill_chunk} and the "
                         f"attention block {ATTENTION_BLOCK}")
    if live is None:
        live = jnp.ones((B, T), bool)
    lengths = jnp.sum(live, axis=1).astype(jnp.int32)
    eps = cfg.rms_norm_eps
    cos, sin = _rope_angles(_query_positions(pos, B, T), cfg)
    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)
    # once, ahead of the scans: inside them XLA would redo the small
    # index arithmetic every layer
    plan = live_latent_plan(T, cache["ckv"], pos, live)
    types = cfg.layer_types

    def layer(carry, at, kind, kind_at, ffn):
        x, ckv, kpe, conv, state, on_held, busiest = carry
        lp = {k: mla._at_layer(params[k], at) for k in _NORMS}
        u = _zc_norm(x, lp["norm_mix_in"], eps).astype(x.dtype)
        if kind == LINEAR:
            lp.update({k: mla._at_layer(params[k], kind_at)
                       for k in _LINEAR})
            out, conv, state = _gated_delta(lp, u, conv, state, kind_at,
                                            lengths, cfg)
        else:
            lp.update({k: mla._at_layer(params[k], kind_at) for k in _FULL})
            out, ckv, kpe = _latent_attention(lp, u, ckv, kpe, kind_at, pos,
                                              cos, sin, live, plan, cfg)
        x = mla._residual(x, _zc_norm(out, lp["norm_mix_out"], eps))
        u = _zc_norm(x, lp["norm_ffn_in"], eps).astype(x.dtype)
        out, loads = _ffn(ffn, u, live, cfg.prefill_chunk)
        x = mla._residual(x, _zc_norm(out, lp["norm_ffn_out"], eps))
        if loads[0] is not None:
            load = sum(loads)
            on_held, busiest = on_held + load.sum(), jnp.maximum(
                busiest, load.max())
        return x, ckv, kpe, conv, state, on_held, busiest

    def segment(carry, first: int, n: int, dense: bool):
        """Layers first .. first + n - 1, all dense or all experts, as a
        scan over the periods of their mixer pattern."""
        period = _period(types[first:first + n])
        before = {kind: types[:first].count(kind) for kind in (LINEAR, FULL)}
        per = {kind: period.count(kind) for kind in (LINEAR, FULL)}

        def body(carry, i):
            seen = {LINEAR: 0, FULL: 0}
            for j, kind in enumerate(period):
                at = i * len(period) + j          # within the segment
                kind_at = before[kind] + i * per[kind] + seen[kind]
                seen[kind] += 1
                if dense:
                    def ffn(rows, _, at=at):
                        with jax.named_scope("dense_mlp"):
                            return mla._mlp(rows, *(
                                mla._at_layer(params[k], at)
                                for k in ("gate_w", "up_w", "down_w")),
                                cfg.swiglu_limit), None
                else:
                    def ffn(rows, lv, at=at):
                        return mla._experts(params, at, rows, lv, cfg,
                                            cfg.swiglu_limit)
                carry = layer(carry, first + at, kind, kind_at, ffn)
            return carry, None

        return mla._scan_layers(body, carry, n // len(period) if n else 0)

    zero = jnp.zeros((), jnp.int32)
    carry = (x, cache["ckv"], cache["kpe"], cache["dn_conv"],
             cache["dn_state"], zero, zero)
    carry = segment(carry, 0, cfg.dense_layers, True)
    carry = segment(carry, cfg.dense_layers, cfg.expert_layers, False)
    x, ckv, kpe, conv, state, on_held, busiest = carry
    x = _zc_norm(x, params["norm_f"], eps).astype(cfg.dtype)
    # the tick's einsums read every position of every slot, whatever is
    # live; the kernel the blocks its work list names
    held = B * ckv.shape[2] if T == 1 else 0
    read = held if plan is None else plan[3][0] * LATENT_BLOCK
    rows = jnp.sum(lengths > 0) if T == 1 else 0
    stats = jnp.stack([on_held, busiest, jnp.int32(read), jnp.int32(held),
                       jnp.int32(rows)])
    return x, {"ckv": ckv, "kpe": kpe, "dn_state": state, "dn_conv": conv,
               "stats": stats}


def gigachat3_5_forward_cached(params, tokens, cache, pos,
                               cfg: GigaChat35Config, live=None):
    """Forward tokens [B, T] against a cache holding `pos` tokens ->
    (logits [B, T, V] float32, updated cache): the families' contract
    (models/llama.py), over the pools of `init_cache`. T = 1 with per-row
    `pos` is the decode tick; T > 1 at the literal position 0 is ONE whole
    prompt (B = 1) into an empty cache. `live` [B, T] bool marks the real
    tokens, a prefix of each row: the linear state a row is left with is
    the one after its last real token, a row with none (an idle slot)
    keeps its rows as they were, and neither writes a latent."""
    x, cache = _hidden(params, tokens, cache, pos, cfg, live)
    return mla._head(params, x), cache


def prefill_into_slot(params, cache, padded, true_len, slot,
                      cfg: GigaChat35Config):
    """The engine's bucketed prefill of ONE request (padded [1, bucket],
    `true_len` real tokens) into slot `slot` of the pools -> (the last
    real position's logits [1, V] float32, the pools). The prompt runs
    through an empty cache of its own; every row of every kind the slot
    holds is then replaced: the latent by position up to the bucket (the
    rest is behind the position mask), the delta-rule state and the
    convolution rows WHOLE, as they stood after position true_len - 1 —
    nothing of the slot's last occupant is left to read. Only the one row
    of logits a token is sampled from is ever computed."""
    T = padded.shape[1]
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < true_len
    x, mini = _hidden(params, padded, init_cache(cfg, 1, T), 0, cfg, live)
    last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
    out = {"stats": mini["stats"]}
    for name in ("ckv", "kpe", "dn_state", "dn_conv"):
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], mini[name], (0, slot) + (0,) * (mini[name].ndim - 2))
    return mla._head(params, last)[:, 0], out
