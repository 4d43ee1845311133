"""Llama-family decoder: RMSNorm + RoPE + GQA + SwiGLU on the
stacked-scan functional core.

The reference snapshot predates this family (its llm/ zoo arrived
later); it is included because a modern framework's flagship decoder is
table stakes, and every building block here is the shared machinery:
stacked per-layer params scanned with lax.scan (models/gpt.py design),
PARAM_SPECS declarative sharding over (dp, fsdp, pp, mp), the selectable
flash-attention kernels (paddle_tpu.kernels), the fused CE head
(models/losses.py), and the same fused AdamW step shape. Reference
analogs for the pieces: rotary embeddings mirror
incubate/fused_multi_transformer's RotaryKernel semantics; the fused CE
head matches phi/kernels/gpu/cross_entropy_kernel.cu's trade.

Grouped-query attention: num_kv_heads < num_heads shares each KV head
across num_heads // num_kv_heads query heads (the KV projections and
cache shrink by that factor — the modern decode-bandwidth trade).

Reference analogs, checkable: rotary semantics as
paddle/fluid/operators/fused/fused_multi_transformer_op.cu:29 (the
RotaryKernel) via incubate/fused_multi_transformer.py:243; fused CE head
as paddle/phi/kernels/gpu/cross_entropy_kernel.cu:1 via
models/losses.py; sharding rules as models/gpt.py:105 PARAM_SPECS.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import constraint as mesh_constraint
from .facade import FacadeModel

__all__ = ["LlamaConfig", "PARAM_SPECS", "init_llama_params",
           "llama_forward", "llama_loss", "train_step", "LlamaModel"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None        # None -> MHA
    ffn_hidden: Optional[int] = None          # None -> 8/3 * D, mult of 256
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True                        # checkpoint each block
    # layer-scan unroll for the cached decode path (see
    # models/gpt.py GPTConfig.decode_scan_unroll — same trade,
    # bit-identical numerics; the serving engine auto-raises it)
    decode_scan_unroll: int = 1

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.ffn_hidden is None:
            self.ffn_hidden = ((8 * self.hidden_size // 3 + 255)
                               // 256) * 256
        assert self.hidden_size % self.num_heads == 0
        assert self.num_heads % self.num_kv_heads == 0

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


# leaf name -> PartitionSpec over (dp, fsdp, pp, mp); stacked block
# params carry the leading layer axis on 'pp' (same rules as
# models/gpt.py PARAM_SPECS: column-parallel up/qkv, row-parallel down/o)
PARAM_SPECS: Dict[str, P] = {
    "wte":          P("mp", "fsdp"),
    "norm_f":       P(None),
    "attn_norm":    P("pp", None),
    "q_w":          P("pp", "fsdp", "mp"),
    "k_w":          P("pp", "fsdp", "mp"),
    "v_w":          P("pp", "fsdp", "mp"),
    "o_w":          P("pp", "mp", "fsdp"),
    "ffn_norm":     P("pp", None),
    "gate_w":       P("pp", "fsdp", "mp"),
    "up_w":         P("pp", "fsdp", "mp"),
    "down_w":       P("pp", "mp", "fsdp"),
}

_BLOCK_KEYS = ("attn_norm", "q_w", "k_w", "v_w", "o_w",
               "ffn_norm", "gate_w", "up_w", "down_w")

# serving/decode tensor-parallel specs (same derivation as
# models/gpt.py SERVING_PARAM_SPECS: the training TP split remapped
# onto the serving mesh's 'tp' axis; inference/serving.py `mesh=`)
from ..parallel.mesh import tp_specs as _tp_specs
SERVING_PARAM_SPECS: Dict[str, P] = _tp_specs(PARAM_SPECS)


def init_llama_params(cfg: LlamaConfig, key) -> Dict[str, jax.Array]:
    D, F, L = cfg.hidden_size, cfg.ffn_hidden, cfg.num_layers
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    pd = cfg.param_dtype

    def norm(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(pd)

    return {
        "wte": norm(ks[0], (cfg.vocab_size, D), 0.02),
        "norm_f": jnp.ones((D,), pd),
        "attn_norm": jnp.ones((L, D), pd),
        "q_w": norm(ks[1], (L, D, H * hd), 0.02),
        "k_w": norm(ks[2], (L, D, KV * hd), 0.02),
        "v_w": norm(ks[3], (L, D, KV * hd), 0.02),
        "o_w": norm(ks[4], (L, H * hd, D), 0.02 / math.sqrt(2 * L)),
        "ffn_norm": jnp.ones((L, D), pd),
        "gate_w": norm(ks[5], (L, D, F), 0.02),
        "up_w": norm(ks[6], (L, D, F), 0.02),
        "down_w": norm(ks[7], (L, F, D), 0.02 / math.sqrt(2 * L)),
    }


def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * r * scale.astype(jnp.float32)).astype(x.dtype)


def _rope_tables(seq: int, hd: int, theta: float):
    """(cos, sin) [S, hd/2] f32 — the half-dim frequency ladder."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rope(x, cos, sin):
    """x [B, S, H, hd]; rotate interleaved pairs by the position angle.
    cos/sin are [S, hd/2] (shared positions) or [B, S, hd/2] (per-row
    positions — the serving engine's slot decode)."""
    B, S, H, hd = x.shape
    xf = x.astype(jnp.float32).reshape(B, S, H, hd // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    if cos.ndim == 2:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    else:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    rot = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    return rot.reshape(B, S, H, hd).astype(x.dtype)


def _data_constraint(x):
    return mesh_constraint(x, P(("dp", "fsdp"), None, None))


def _block(lp, x, cfg: LlamaConfig, cos, sin):
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    h = _rmsnorm(x, lp["attn_norm"], cfg.rms_eps)
    q = (h @ lp["q_w"].astype(h.dtype)).reshape(B, S, H, hd)
    k = (h @ lp["k_w"].astype(h.dtype)).reshape(B, S, KV, hd)
    v = (h @ lp["v_w"].astype(h.dtype)).reshape(B, S, KV, hd)
    q = _apply_rope(q, cos, sin)
    k = _apply_rope(k, cos, sin)
    if KV != H:
        # GQA: each KV head serves H//KV query heads
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    from ..kernels.flash_attention import flash_attention_fn
    ctx = flash_attention_fn(q, k, v, causal=True)
    x = x + (ctx.reshape(B, S, H * hd)
             @ lp["o_w"].astype(x.dtype))

    h = _rmsnorm(x, lp["ffn_norm"], cfg.rms_eps)
    gated = jax.nn.silu(h @ lp["gate_w"].astype(h.dtype)) * (
        h @ lp["up_w"].astype(h.dtype))
    x = x + gated @ lp["down_w"].astype(x.dtype)
    return _data_constraint(x)


def llama_forward(params, tokens, cfg: LlamaConfig):
    """tokens [B, S] int32 -> logits [B, S, V] in cfg.dtype."""
    B, S = tokens.shape
    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)
    x = _data_constraint(x)
    cos, sin = _rope_tables(S, cfg.head_dim, cfg.rope_theta)

    stacked = {k: params[k] for k in _BLOCK_KEYS}
    body = functools.partial(_block, cfg=cfg, cos=cos, sin=sin)
    if cfg.remat:
        body = jax.checkpoint(body)

    def step(h, lp):
        return body(lp, h), None

    x, _ = jax.lax.scan(step, x, stacked)
    x = _rmsnorm(x, params["norm_f"], cfg.rms_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(x.dtype))
    return mesh_constraint(logits, P(("dp", "fsdp"), None, "mp"))


def llama_loss(params, batch, cfg: LlamaConfig):
    """Causal LM loss over tokens [B, S+1] (input = [:, :-1],
    target = [:, 1:]); the fused CE head streams the logits once."""
    from .losses import fused_softmax_ce
    tokens = batch["tokens"] if isinstance(batch, dict) else batch
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    return fused_softmax_ce(llama_forward(params, inp, cfg), tgt)


def train_step(params, opt_state, batch, cfg: LlamaConfig, lr=3e-4,
               **adamw_kw):
    """Fused fwd + bwd + AdamW, sharing the GPT step's update rule
    (gpt.apply_adamw) so the two flagships cannot drift."""
    from .gpt import apply_adamw
    loss, grads = jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg))(params)
    new_params, new_opt = apply_adamw(grads, params, opt_state, lr,
                                      **adamw_kw)
    return loss, new_params, new_opt


# --------------------------------------------------------------------------
# KV-cache decode (same design as models/gpt.py — one stacked [L, ...]
# cache carried whole through the layer scan and written in place at
# [layer, ...]; dense masked attention over the cache at decode). The
# GQA payoff lands here: the cache holds KV heads, not query heads,
# shrinking HBM traffic per decoded token by H/KV.
# --------------------------------------------------------------------------
def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int):
    """-> {"k","v": [L, B, max_len, KV, hd]} in the activation dtype."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def llama_forward_cached(params, tokens, cache, pos, cfg: LlamaConfig,
                         layers: Optional[int] = None, live=None):
    """Forward tokens [B,T] against a cache holding `pos` tokens ->
    (logits [B,T,V], updated cache). Prefill (pos=0) and decode (T=1)
    share the graph; RoPE is applied at the absolute positions. `pos`
    is a traced scalar (whole-batch decode) or a [B] vector of per-row
    slot positions (inference/serving.py). The cache write and the
    grouped masked attention (KV heads in the cache, never-materialized
    query groups — the GQA decode-bandwidth payoff) go through the
    selectable seam in kernels/decode_attention.py. `layers` (static)
    truncates the stacked scan to the first `layers` blocks with the
    final RMSNorm + tied head on top — the speculative self-draft pass
    (inference/spec_decode.py; the cache must be the matching
    first-`layers` view, same contract as models/gpt.py). Cache
    layouts: dense {"k","v": [L, B, max_len, KV, hd]} or the serving
    engine's paged pool {"k","v": [L, P, page_size, KV, hd], "pt":
    [B, max_pages]} — same contract as models/gpt.py, bit-identical
    across layouts. `live` [B, T] as in models/gpt.py: which rows are
    requests, for the dense pool's decode attention alone."""
    B, T = tokens.shape
    pt = cache.get("pt")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)
    # rope positions span the logical cache: dense = the cache axis,
    # paged = max_pages * page_size (the re-linearized view length)
    s_cache = (cache["k"].shape[2] if pt is None
               else pt.shape[1] * cache["k"].shape[2])
    cos_full, sin_full = _rope_tables(s_cache, hd, cfg.rope_theta)
    if jnp.ndim(pos) == 0:
        cos = jax.lax.dynamic_slice_in_dim(cos_full, pos, T, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(sin_full, pos, T, axis=0)
    else:
        # mode="clip": the serving decode tick parks inactive rows at
        # an out-of-table sentinel position (their K/V scatters to the
        # scratch page); the default "fill" would rope them to NaN,
        # and NaN written to scratch poisons every later gather of it
        idx = pos[:, None] + jnp.arange(T)
        cos = jnp.take(cos_full, idx, axis=0, mode="clip")  # [B,T,hd/2]
        sin = jnp.take(sin_full, idx, axis=0, mode="clip")

    # weight-only int8 serving (quantization/serving.py): quantized
    # trees drop the fp matmul leaves and carry <name>_q/<name>_scale
    # instead — both stacked on the same leading layer axis, so they
    # ride the scan (and the layers= draft slice) like the fp weights
    block_keys = _BLOCK_KEYS + tuple(
        k2 for k in _BLOCK_KEYS for k2 in (k + "_q", k + "_scale"))
    stacked = {k: params[k] for k in block_keys if k in params}
    n_layers = cfg.num_layers
    if layers is not None:
        stacked = {k: v[:layers] for k, v in stacked.items()}
        n_layers = int(layers)
    from ..kernels.decode_attention import (cached_attention, layer_view,
                                            live_block_plan, write_kv,
                                            write_kv_paged)
    from ..kernels.quant_matmul import leaf_matmul, quant_matmul
    plan = None if pt is not None else live_block_plan(
        T, cache["k"], pos, live)

    def scan_fn(carry, layer_in):
        x, kc, vc = carry
        lp, layer = layer_in
        h = _rmsnorm(x, lp["attn_norm"], cfg.rms_eps)
        q = leaf_matmul(h, lp, "q_w").reshape(B, T, H, hd)
        k = leaf_matmul(h, lp, "k_w").reshape(B, T, KV, hd)
        v = leaf_matmul(h, lp, "v_w").reshape(B, T, KV, hd)
        q = _apply_rope(q, cos, sin)
        k = _apply_rope(k, cos, sin)
        if pt is None:
            kc = write_kv(kc, k, pos, layer)
            vc = write_kv(vc, v, pos, layer)
        else:
            kc = write_kv_paged(kc, pt, k, pos, layer)
            vc = write_kv_paged(vc, pt, v, pos, layer)
        if pt is None:
            ctx = cached_attention(q, kc, vc, pos, layer=layer, plan=plan)
        else:
            ctx = cached_attention(q, layer_view(kc, layer, pt),
                                   layer_view(vc, layer, pt), pos)
        ctx = ctx.reshape(B, T, H * hd).astype(x.dtype)
        x = x + leaf_matmul(ctx, lp, "o_w")
        h = _rmsnorm(x, lp["ffn_norm"], cfg.rms_eps)
        gated = jax.nn.silu(leaf_matmul(h, lp, "gate_w")) * \
            leaf_matmul(h, lp, "up_w")
        return (x + leaf_matmul(gated, lp, "down_w"), kc, vc), None

    # the pools ride the carry and are written in place at [layer, ...]
    # (models/gpt.py gpt_forward_cached — same form, same reason)
    (x, kcs, vcs), _ = jax.lax.scan(
        scan_fn, (x, cache["k"], cache["v"]),
        (stacked, jnp.arange(n_layers, dtype=jnp.int32)),
        unroll=max(1, min(getattr(cfg, "decode_scan_unroll", 1),
                          n_layers)))
    x = _rmsnorm(x, params["norm_f"], cfg.rms_eps)
    if "head_q" in params:
        # quantized tied head (transposed int8 copy + per-vocab scales;
        # `wte` stays fp for the embedding — quantization/serving.py)
        logits = quant_matmul(x, params["head_q"], params["head_scale"])
    else:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["wte"].astype(x.dtype))
    out = {"k": kcs, "v": vcs}
    if pt is not None:
        out["pt"] = pt
    return logits, out


def greedy_generate(params, prompt, cfg: LlamaConfig,
                    max_new_tokens: int,
                    max_len: Optional[int] = None):
    """Greedy decode through the grouped KV cache (shared driver:
    models/decode.py). prompt [B, T0] -> [B, T0 + max_new_tokens]."""
    from .decode import greedy_generate_with
    return greedy_generate_with(llama_forward_cached, init_kv_cache,
                                params, prompt, cfg, max_new_tokens,
                                max_len)


class LlamaModel(FacadeModel):
    """Paddle-shaped facade over the functional core (parameters /
    state_dict / tape-recorded forward as ONE differentiable op)."""

    _fwd_op_name = "llama_forward"
    _serving_family = "llama"

    def __init__(self, cfg: LlamaConfig, seed: int = 0):
        super().__init__(cfg, init_llama_params, PARAM_SPECS, seed)

    def forward(self, tokens):
        cfg = self.cfg
        return self._dispatch(
            self._fwd_op_name,
            lambda params, toks: llama_forward(params, toks, cfg),
            tokens)

    __call__ = forward
