"""Cohere2-MoE decoder (`model_type: cohere2_moe`, Command A+): a
PARALLEL block — one LayerNorm, then attention and experts side by side
on the same normed input, `x' = x + Attn(h) + Experts(h)` — over a
PERIODIC stack: sliding-window layers with interleaved RoPE among
full-attention layers with no positional embedding at all.

Serving only (inference/serving.py `family="cohere2_moe"`): a cached
forward for prefill (T = bucket) and decode (T = 1), the cache factory,
and the bucketed prefill into a slot. What differs from models/gpt.py
and models/llama.py, by part:

- the cache is TWO pools, one per layer kind: window layers keep a ring
  of `sliding_window` positions a slot (`k_win`/`v_win`
  [Lw, slots, window, KV, hd]; position p lives on row p mod window),
  full layers keep `max_len` (`k`/`v` [Lf, slots, max_len, KV, hd]).
  The layer scan runs over PERIODS of the layer pattern with both pools
  in its carry, rows written in place (models/gpt.py's form);
- the experts are parallel/moe.py's dropless layer: the router scores
  all published experts (float32 sigmoid, the k largest, normalised),
  this chip computes the part of the experts it HOLDS
  (`first_expert .. first_expert + experts_held - 1`), and what the
  absent ones would add is left out; the shared experts see every
  token and are averaged;
- parameters are stored in `param_dtype` (bf16) and read as they are:
  nothing is converted per tick;
- a prompt's attention runs in blocks
  (kernels/decode_attention.blocked_attention) and its layer in chunks
  of `prefill_chunk` tokens, so that a 16k bucket of 128 heads fits;
- the forward counts what it did (`COUNTS`) into the cache's "stats"
  leaf, which rides the engine's one pull.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.decode_attention import (blocked_attention, cached_attention,
                                        causal_pairs, layer_view,
                                        prompt_kernel_engages, ring_rows,
                                        write_kv)
from ..parallel.moe import dropless_experts, sigmoid_topk
from .llama import _apply_rope

__all__ = ["Cohere2MoeConfig", "init_cohere2_moe_params", "init_kv_cache",
           "cohere2_moe_forward_cached", "prefill_into_slot", "COUNTS",
           "span_counts", "prompt_counts"]

SLIDING, FULL = "sliding_attention", "full_attention"

# what one forward counts, in the order of the cache's "stats" leaf
# (int32): expert pairs routed / on held experts, the busiest held
# expert's rows (largest over layers), held experts with no row (summed
# over layers); cache positions the attention admitted, for ONE layer
# of each kind (`span_counts` multiplies by the layer counts)
COUNTS = ("expert_choices", "expert_tokens", "expert_max_load",
          "experts_idle", "kv_window_layer", "kv_full_layer")


def span_counts(cfg, stats) -> Dict[str, int]:
    """A pulled "stats" row as the counts the engine sets on its spans
    (ModelFamily.counts). The two per-layer position counts become the
    totals over the layers of each kind, beside what ONE uniform pool
    (every layer at full length) would have admitted — on the host, in
    Python integers: a 16k prompt through 32 layers passes int32."""
    counts = {k: int(v) for k, v in zip(COUNTS, stats)}
    window, full = counts.pop("kv_window_layer"), counts.pop("kv_full_layer")
    counts["kv_positions_window"] = window * cfg.layers_of(SLIDING)
    counts["kv_positions_full"] = full * cfg.layers_of(FULL)
    counts["kv_positions_uniform"] = full * cfg.num_layers
    return counts


def prompt_counts(cfg, bucket: int) -> Dict[str, int]:
    """What a prompt bucket's attention does, from its shapes alone
    (ModelFamily.prompt_counts): the (query, key) pairs its layers admit,
    summed over the layers, and those of them whose `blocked_attention`
    call — one a chunk of `prefill_chunk` rows a layer — runs the Pallas
    prompt kernel (`prompt_kernel_engages`, asked on the host of the same
    shapes)."""
    chunk = min(cfg.prefill_chunk, bucket)
    hd = cfg.head_dim
    counts = {"attn_pairs": 0, "attn_pairs_kernel": 0}
    for kind, window in ((SLIDING, cfg.sliding_window), (FULL, None)):
        for start in range(0, bucket, chunk):
            pairs = cfg.layers_of(kind) * causal_pairs(start, start + chunk,
                                                       window)
            counts["attn_pairs"] += pairs
            if prompt_kernel_engages(chunk, bucket, cfg.num_heads,
                                     cfg.num_kv_heads, hd, hd, cfg.dtype,
                                     start):
                counts["attn_pairs_kernel"] += pairs
    return counts


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144          # rows of the embedding held here
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    ffn_hidden: int = 4096            # width of ONE expert, routed or shared
    max_seq_len: int = 200000
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    sliding_window: int = 4096
    # None -> three sliding layers then a full one, cycled to num_layers
    layer_types: Optional[Tuple[str, ...]] = None
    num_experts: int = 128            # published: the router's width
    experts_held: Optional[int] = None    # None -> all of them
    first_expert: int = 0
    experts_per_token: int = 8
    num_shared_experts: int = 4
    logit_scale: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    decode_scan_unroll: int = 1       # layers (see models/gpt.py)
    prefill_chunk: int = 2048         # tokens a prompt's layer runs at once

    def __post_init__(self):
        if self.layer_types is None:
            cycle = (SLIDING, SLIDING, SLIDING, FULL)
            self.layer_types = tuple(cycle[i % 4]
                                     for i in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        if self.experts_held is None:
            self.experts_held = self.num_experts
        for ok, what in (
                (len(self.layer_types) == self.num_layers,
                 "layer_types has one entry a layer"),
                (set(self.layer_types) <= {SLIDING, FULL},
                 f"a layer type is {SLIDING!r} or {FULL!r}"),
                (self.num_heads % self.num_kv_heads == 0,
                 "num_kv_heads divides num_heads"),
                (self.head_dim % 2 == 0, "head_dim is even"),
                (0 <= self.first_expert and self.first_expert
                 + self.experts_held <= self.num_experts,
                 "the held experts lie among the published ones"),
                (self.experts_per_token <= self.num_experts,
                 "experts_per_token is at most num_experts")):
            if not ok:
                raise ValueError(f"Cohere2MoeConfig: {what}")

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern the layer types repeat."""
        types = self.layer_types
        for p in range(1, len(types) + 1):
            if len(types) % p == 0 and types == types[:p] * (len(types) // p):
                return types[:p]
        return types

    def layers_of(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)


def param_shapes(cfg: Cohere2MoeConfig) -> Dict[str, tuple]:
    """Leaf name -> shape; per-layer leaves stacked on a leading axis."""
    n, d, f = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden
    hq, hkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    s, e = cfg.num_shared_experts, cfg.experts_held
    return {
        "wte": (cfg.vocab_size, d), "norm_f": (d,), "norm": (n, d),
        "q_w": (n, d, hq), "k_w": (n, d, hkv), "v_w": (n, d, hkv),
        "o_w": (n, hq, d), "router_w": (n, d, cfg.num_experts),
        "shared_gate_w": (n, s, d, f), "shared_up_w": (n, s, d, f),
        "shared_down_w": (n, s, f, d),
        "gate_w": (n, e, d, f), "up_w": (n, e, d, f), "down_w": (n, e, f, d),
    }


_ROUTED = ("gate_w", "up_w", "down_w")
_BLOCK_KEYS = tuple(k for k in param_shapes(Cohere2MoeConfig(num_layers=1))
                    if k not in ("wte", "norm_f"))


def init_cohere2_moe_params(cfg: Cohere2MoeConfig, key) -> Dict[str, Any]:
    """Seeded random parameters in `param_dtype` (norm scales near 1)."""
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    params = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        draw = jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)
        if name.startswith("norm"):
            leaf = 1.0 + 0.02 * draw
        elif name in ("o_w", "down_w", "shared_down_w"):
            leaf = out_std * draw
        else:
            leaf = 0.02 * draw
        params[name] = leaf.astype(cfg.param_dtype)
    return params


def init_kv_cache(cfg: Cohere2MoeConfig, batch: int, max_len: int,
                  ring: bool = True):
    """-> the pools by layer kind, in the activation dtype: window
    layers' `k_win`/`v_win` [Lw, B, min(window, max_len), KV, hd] (a
    ring), full layers' `k`/`v` [Lf, B, max_len, KV, hd], and the
    forward's counts, "stats". `ring=False` is a prefill's own cache:
    the window layers keep every position of the bucket in order, for
    the slot write to pick from."""
    tail = (cfg.num_kv_heads, cfg.head_dim)
    win = min(cfg.sliding_window, max_len) if ring else max_len
    w = (cfg.layers_of(SLIDING), batch, win) + tail
    f = (cfg.layers_of(FULL), batch, max_len) + tail
    return {"k": jnp.zeros(f, cfg.dtype), "v": jnp.zeros(f, cfg.dtype),
            "k_win": jnp.zeros(w, cfg.dtype),
            "v_win": jnp.zeros(w, cfg.dtype),
            "stats": jnp.zeros((len(COUNTS),), jnp.int32)}


def _layer_norm(x, scale, eps):
    """The Cohere LayerNorm: centre, divide by the deviation, scale; no
    bias. Float32 inside."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, -1, keepdims=True)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * r * scale.astype(jnp.float32)).astype(x.dtype)


def _rope_angles(positions, hd: int, theta: float):
    """(cos, sin) [..., hd/2] float32 at absolute `positions` [...]."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _experts(lp, h, live, cfg: Cohere2MoeConfig, layer=None):
    """Experts(h) for rows h [R, D] -> ([R, D] float32, load [Eh]): the
    held routed experts' part plus the mean of the shared experts. With
    `layer`, the routed experts' leaves are a stack and that layer's are
    meant (parallel/moe.py dropless_experts)."""
    with jax.named_scope("experts/route"):
        logits = jnp.dot(h.astype(jnp.float32),
                         lp["router_w"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        choice, weight = sigmoid_topk(logits, cfg.experts_per_token)
    with jax.named_scope("experts/grouped_matmul"):
        routed, load = dropless_experts(
            h, choice, weight, lp["gate_w"], lp["up_w"], lp["down_w"],
            first=cfg.first_expert, live=live, layer=layer)
    with jax.named_scope("experts/shared"):
        g = jax.nn.silu(jnp.einsum("rd,sdf->rsf", h, lp["shared_gate_w"])) \
            * jnp.einsum("rd,sdf->rsf", h, lp["shared_up_w"])
        shared = jnp.einsum("rsf,sfd->rd", g, lp["shared_down_w"],
                            preferred_element_type=jnp.float32)
    return routed + shared / cfg.num_shared_experts, load


def _hidden(params, tokens, cache, pos, cfg: Cohere2MoeConfig, live=None):
    """tokens [B, T] -> (the final-normed hidden state [B, T, D], the
    updated cache). `pos` as in models/llama.py; `live` [B, T] marks the
    rows that are real tokens (None: all), for the counts alone."""
    B, T = tokens.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    W = cfg.sliding_window
    period = cfg.period
    n_periods = cfg.num_layers // len(period)
    in_period = {kind: sum(1 for t in period if t == kind)
                 for kind in (SLIDING, FULL)}
    # a whole prompt into an empty cache: attention over the step's own
    # keys in blocks, the layer in chunks of tokens. Anything else
    # (decode, T = 1) reads the pools
    whole_prompt = T > 1 and isinstance(pos, int) and pos == 0
    chunk = min(cfg.prefill_chunk, T)
    if whole_prompt and T % chunk:
        raise ValueError(f"prompt bucket {T} is no multiple of "
                         f"prefill_chunk {chunk}")
    if live is None:
        live = jnp.ones((B, T), bool)
    offs = jnp.arange(T, dtype=jnp.int32)
    qpos = (pos + offs)[None, :] if jnp.ndim(pos) == 0 \
        else pos[:, None] + offs                              # [B|1, T]
    cos, sin = _rope_angles(qpos, hd, cfg.rope_theta)
    if cos.shape[0] != B:
        cos, sin = (jnp.broadcast_to(a, (B,) + a.shape[1:])
                    for a in (cos, sin))
    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)

    def attend_and_mix(lp, xr, h, q_rope, attend, live_r, j):
        """One run of rows through the parallel block, given how its
        queries attend: -> (rows out, expert load)."""
        R = h.shape[0] * h.shape[1]
        q = jnp.einsum("btd,dh->bth", h, lp["q_w"]).reshape(
            h.shape[0], h.shape[1], H, hd)
        ctx = attend(q_rope(q)).astype(h.dtype)
        attn = jnp.einsum("bth,hd->btd", ctx.reshape(*h.shape[:2], H * hd),
                          lp["o_w"], preferred_element_type=jnp.float32)
        mixed, load = _experts(lp, h.reshape(R, -1), live_r.reshape(R), cfg,
                               layer=j)
        out = xr.astype(jnp.float32) + attn + mixed.reshape(attn.shape)
        return out.astype(xr.dtype), load

    def layer(lp, j, x, kind, kc, vc, at):
        sliding = kind == SLIDING
        h = _layer_norm(x, lp["norm"], cfg.layer_norm_eps)
        k = jnp.einsum("btd,dh->bth", h, lp["k_w"]).reshape(B, T, KV, hd)
        v = jnp.einsum("btd,dh->bth", h, lp["v_w"]).reshape(B, T, KV, hd)
        if sliding:
            k = _apply_rope(k, cos, sin)
        ring = sliding and not whole_prompt
        kc = write_kv(kc, k, pos, at, ring=ring)
        vc = write_kv(vc, v, pos, at, ring=ring)
        window = W if sliding else None
        scope = "attention/window" if sliding else "attention/full"
        if not whole_prompt:
            def attend(q):
                with jax.named_scope(scope):
                    return cached_attention(
                        q, layer_view(kc, at), layer_view(vc, at), pos,
                        impl="native",
                        window=window if ring else None)
            rope = (lambda q: _apply_rope(q, cos, sin)) if sliding \
                else (lambda q: q)
            x, load = attend_and_mix(lp, x, h, rope, attend, live, j)
            return x, kc, vc, load

        def rows(start):
            cut = lambda a: a[:, start:start + chunk]

            def attend(q):
                with jax.named_scope(scope):
                    return blocked_attention(q, k, v, window=window,
                                             q_offset=start)
            rope = (lambda q: _apply_rope(q, cut(cos), cut(sin))) \
                if sliding else (lambda q: q)
            return attend_and_mix(lp, cut(x), cut(h), rope, attend,
                                  cut(live), j)

        # unrolled, not a loop: a loop's body would hold its own copy of
        # every weight it reads (3.8 GB of them a layer)
        outs, loads = zip(*(rows(start) for start in range(0, T, chunk)))
        return jnp.concatenate(outs, axis=1), kc, vc, sum(loads)

    def period_fn(carry, xs):
        x, kf, vf, kw, vw, counts = carry
        lps, i = xs
        seen = {SLIDING: 0, FULL: 0}
        for j, kind in enumerate(period):
            # the routed experts' leaves stay a stack of the period's
            # layers (dropless_experts picks layer j inside)
            lp = {name: leaf if name in _ROUTED else leaf[j]
                  for name, leaf in lps.items()}
            at = i * in_period[kind] + seen[kind]
            seen[kind] += 1
            if kind == SLIDING:
                x, kw, vw, load = layer(lp, j, x, kind, kw, vw, at)
            else:
                x, kf, vf, load = layer(lp, j, x, kind, kf, vf, at)
            counts = (counts[0] + load.sum(),
                      jnp.maximum(counts[1], load.max()),
                      counts[2] + jnp.sum(load == 0).astype(jnp.int32))
        return (x, kf, vf, kw, vw, counts), None

    stacked = {k: params[k].reshape((n_periods, len(period))
                                    + params[k].shape[1:])
               for k in _BLOCK_KEYS}
    zero = jnp.zeros((), jnp.int32)
    carry = (x, cache["k"], cache["v"], cache["k_win"], cache["v_win"],
             (zero, zero, zero))
    unroll = max(1, min(cfg.decode_scan_unroll // len(period), n_periods))
    if unroll == n_periods:
        # every period written out: a loop, even of one trip, keeps its
        # own copy of each weight slice its body reads
        for i in range(n_periods):
            carry, _ = period_fn(
                carry, ({k: v[i] for k, v in stacked.items()}, i))
    else:
        carry, _ = jax.lax.scan(
            period_fn, carry,
            (stacked, jnp.arange(n_periods, dtype=jnp.int32)),
            unroll=unroll)
    x, kf, vf, kw, vw, (on_held, busiest, idle) = carry
    x = _layer_norm(x, params["norm_f"], cfg.layer_norm_eps)
    seen = jnp.where(live, qpos + 1, 0)
    stats = jnp.stack([
        jnp.sum(live).astype(jnp.int32) * cfg.experts_per_token
        * cfg.num_layers, on_held, busiest, idle,
        jnp.sum(jnp.minimum(seen, W)).astype(jnp.int32),
        jnp.sum(seen).astype(jnp.int32)])
    return x, {"k": kf, "v": vf, "k_win": kw, "v_win": vw, "stats": stats}


def _head(params, x, cfg: Cohere2MoeConfig):
    """Tied embeddings: logits = x . wte^T x logit_scale, float32."""
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("btd,vd->btv", x, params["wte"],
                            preferred_element_type=jnp.float32)
        return logits * cfg.logit_scale


def cohere2_moe_forward_cached(params, tokens, cache, pos,
                               cfg: Cohere2MoeConfig, live=None):
    """Forward tokens [B, T] against a cache holding `pos` tokens ->
    (logits [B, T, V] float32, updated cache): the families' contract
    (models/llama.py), over the two pools of `init_kv_cache`. T = 1 with
    per-row `pos` is the decode tick; T > 1 at the literal position 0 is
    a whole prompt into a `ring=False` cache. `live` [B, T] bool marks
    real tokens for the counts in the cache's "stats"."""
    x, cache = _hidden(params, tokens, cache, pos, cfg, live)
    return _head(params, x, cfg), cache


def prefill_into_slot(params, cache, padded, true_len, slot,
                      cfg: Cohere2MoeConfig):
    """The engine's bucketed prefill of ONE request (padded [1, bucket],
    `true_len` real tokens) into slot `slot` of the pools -> (the last
    real position's logits [1, V] float32, the pools). The prompt runs
    through a position-ordered cache of its own; the full layers' rows
    are copied into the slot as they are, and of a window layer the slot
    keeps the last `window` positions, each on its ring row. Only the
    one row of logits a token is sampled from is ever computed."""
    T = padded.shape[1]
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < true_len
    x, mini = _hidden(params, padded, init_kv_cache(cfg, 1, T, ring=False),
                      0, cfg, live)
    last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
    out = {"stats": mini["stats"]}
    rows = ring_rows(true_len, cache["k_win"].shape[2])
    # a bucket shorter than the ring fills the ring's head only
    rows = rows[:min(T, rows.shape[0])]
    for name in ("k", "v"):
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], mini[name], (0, slot, 0, 0, 0))
        kept = jnp.take(mini[name + "_win"], jnp.minimum(rows, T - 1),
                        axis=2)
        out[name + "_win"] = jax.lax.dynamic_update_slice(
            cache[name + "_win"], kept, (0, slot, 0, 0, 0))
    return _head(params, last, cfg)[:, 0], out
