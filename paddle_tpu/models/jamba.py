"""Jamba decoder (`model_type: jamba`, AI21-Jamba2-3B): a PERIODIC stack
of pre-norm blocks `x = x + Mixer(RMSNorm(x))`, `x = x + MLP(RMSNorm(x))`
whose mixer is a Mamba-1 state-space layer everywhere but on the layers
`i % attn_layer_period == attn_layer_offset`, where it is multi-query
attention with NO positional embedding (the state-space layers carry the
order). `num_experts` is 1 in this model, so every MLP is the dense
SwiGLU.

Serving only (inference/serving.py `family="jamba"`): ONE cached forward
for prefill (T = bucket) and decode (T = 1), the cache factory, and the
bucketed prefill into a slot. What differs from the other families:

- the cache holds TWO KINDS OF STATE. The attention layers keep keys and
  values by position (`k`/`v` [La, slots, max_len, KV, hd], written and
  read through kernels/decode_attention.py like every family's). A Mamba
  layer keeps, whatever the context, a recurrent state (`ssm`
  [Lm, slots, d_state, d_inner] float32) and the last `d_conv - 1` rows
  that entered its convolution (`conv` [Lm, slots, d_conv - 1, d_inner]).
  These have no position axis: a step OVERWRITES them, a padded position
  must not advance them (nothing masks a recurrence at read time), and
  admission replaces a slot's rows whole;
- the forward is told how many of a row's T tokens are real (`live`),
  and the state it leaves is the one after the last real token;
- the layer scan runs over PERIODS of the layer pattern with all four
  pools in its carry, each layer's rows written in place;
- the recurrence is kernels/selective_scan.py: chunked for a prompt, one
  step over all slots for the tick;
- matrices are stored in `param_dtype` (bf16) and read as they are;
  `a_log`, `d`, `dt_b` and the norm scales are float32;
- the forward counts what it moved (`COUNTS`) into the cache's "stats"
  leaf, which rides the engine's one pull.

Layout: d_inner is the last axis of `a_log` [Lm, d_state, d_inner] and of
the state (the published tensor is [d_inner, d_state]).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels.decode_attention import (blocked_attention, cached_attention,
                                        write_kv)
from ..kernels.selective_scan import selective_scan, selective_state_update

__all__ = ["JambaConfig", "init_jamba_params", "init_cache",
           "jamba_forward_cached", "prefill_into_slot", "COUNTS",
           "span_counts", "slot_state_bytes"]

MAMBA, ATTENTION = "mamba", "attention"
# positions a chunk of the prompt's selective scan runs as one fused pass:
# of 4 / 8 / 16 / 32 / 64 the quickest at the published widths on a v5e
# (3.0 ms for 4,096 positions of one layer; PERF.md, PR 35)
SCAN_CHUNK = 8
# a whole prompt's attention runs in blocks of this many positions
ATTENTION_BLOCK = 512

# what one forward counts, in the order of the cache's "stats" leaf
# (int32): rows whose recurrent state the tick's bodies read and wrote;
# cache positions ONE attention layer read and holds (`span_counts`
# multiplies by the layers and the bytes); chunks a prompt's scans ran
COUNTS = ("state_rows", "kv_read_layer", "kv_pool_layer", "scan_chunks")


@dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    ffn_hidden: int = 8192
    max_seq_len: int = 262144
    rms_norm_eps: float = 1e-6
    attn_layer_period: int = 14     # an offset past the period: no
    attn_layer_offset: int = 7      # attention layer at all
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for ok, what in (
                (self.attn_layer_period >= 1 and self.attn_layer_offset >= 0,
                 "attn_layer_period >= 1 and attn_layer_offset >= 0"),
                (self.num_heads % self.num_kv_heads == 0,
                 "num_kv_heads divides num_heads"),
                (self.mamba_d_conv >= 2, "mamba_d_conv is at least 2")):
            if not ok:
                raise ValueError(f"JambaConfig: {what}")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.num_layers))

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


def param_shapes(cfg: JambaConfig) -> Dict[str, tuple]:
    """Leaf name -> shape. A leaf every layer has is stacked over all
    layers, a mixer's leaf over the layers of its kind."""
    n, d, f = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden
    nm, na = cfg.layers_of(MAMBA), cfg.layers_of(ATTENTION)
    di, ns, r = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    hq, hkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    return {
        "wte": (cfg.vocab_size, d), "norm_f": (d,),
        "norm_in": (n, d), "norm_ff": (n, d),
        "gate_w": (n, d, f), "up_w": (n, d, f), "down_w": (n, f, d),
        "in_w": (nm, d, 2 * di), "conv_w": (nm, cfg.mamba_d_conv, di),
        "conv_b": (nm, di), "x_w": (nm, di, r + 2 * ns),
        "dt_norm": (nm, r), "b_norm": (nm, ns), "c_norm": (nm, ns),
        "dt_w": (nm, r, di), "dt_b": (nm, di), "a_log": (nm, ns, di),
        "d": (nm, di), "out_w": (nm, di, d),
        "q_w": (na, d, hq), "k_w": (na, d, hkv), "v_w": (na, d, hkv),
        "o_w": (na, hq, d),
    }


# leaves kept in float32 whatever `param_dtype` is
F32_LEAVES = ("norm_f", "norm_in", "norm_ff", "dt_norm", "b_norm", "c_norm",
              "dt_b", "a_log", "d")
_EVERY = ("norm_in", "norm_ff", "gate_w", "up_w", "down_w")
_MAMBA = ("in_w", "conv_w", "conv_b", "x_w", "dt_norm", "b_norm", "c_norm",
          "dt_w", "dt_b", "a_log", "d", "out_w")
_ATTENTION = ("q_w", "k_w", "v_w", "o_w")


def init_jamba_params(cfg: JambaConfig, key) -> Dict[str, Any]:
    """Seeded random parameters. Matrices are normal draws in
    `param_dtype`; what decides whether the state remembers is the Mamba
    paper's: a_log = log(1..d_state) on every channel, dt_b the inverse
    softplus of a log-uniform step in [0.001, 0.1], d = 1, the
    convolution uniform within 1 / sqrt(d_conv)."""
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    params = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name == "a_log":
            leaf = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32))[None, :, None], shape)
        elif name == "dt_b":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "d":
            leaf = jnp.ones(shape, jnp.float32)
        elif name in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            draw = jax.random.normal(k, shape, jnp.float32)
            if "norm" in name:
                leaf = 1.0 + 0.02 * draw
            elif name in ("out_w", "o_w", "down_w"):
                leaf = out_std * draw
            else:
                leaf = 0.02 * draw
        params[name] = leaf.astype(
            jnp.float32 if name in F32_LEAVES else cfg.param_dtype)
    return params


def init_cache(cfg: JambaConfig, batch: int, max_len: int):
    """-> the pools by kind of state, the slot on axis 1: the attention
    layers' `k`/`v` [La, B, max_len, KV, hd] and the Mamba layers' `conv`
    [Lm, B, d_conv - 1, d_inner] in the activation dtype, their `ssm`
    [Lm, B, d_state, d_inner] float32, and the forward's counts, "stats".
    An all-zero row is a slot no token has entered."""
    nm, na = cfg.layers_of(MAMBA), cfg.layers_of(ATTENTION)
    kv = (na, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "ssm": jnp.zeros((nm, batch, cfg.mamba_d_state, cfg.d_inner),
                             jnp.float32),
            "conv": jnp.zeros((nm, batch, cfg.mamba_d_conv - 1, cfg.d_inner),
                              cfg.dtype),
            "stats": jnp.zeros((len(COUNTS),), jnp.int32)}


def slot_state_bytes(cfg: JambaConfig) -> int:
    """Recurrent state and convolution rows ONE slot holds, every Mamba
    layer, whatever its context."""
    di = cfg.d_inner
    return cfg.layers_of(MAMBA) * (
        cfg.mamba_d_state * di * 4
        + (cfg.mamba_d_conv - 1) * di * jnp.dtype(cfg.dtype).itemsize)


def span_counts(cfg: JambaConfig, stats) -> Dict[str, int]:
    """A pulled "stats" row as the counts the engine sets on its spans
    (ModelFamily.counts), in Python integers. A tick: the bytes of
    recurrent state its bodies read and wrote against the bytes of keys
    and values its attention read, and the positions behind the latter
    (every position of every slot while the masked einsum runs). A
    prompt: the chunks its scans ran."""
    rows, read, pool, chunks = (int(v) for v in stats)
    if not rows:
        return {"scan_chunks": chunks}
    layers = cfg.layers_of(ATTENTION)
    position = 2 * cfg.num_kv_heads * cfg.head_dim \
        * jnp.dtype(cfg.dtype).itemsize
    return {"state_bytes": 2 * rows * slot_state_bytes(cfg),
            "kv_bytes": read * layers * position,
            "kv_positions_read": read * layers,
            "kv_positions_pool": pool * layers}


def _at_layer(stack, at):
    """Layer `at` (a traced index) of a stacked pool or leaf: a read XLA
    fuses into its consumer."""
    return jax.lax.dynamic_index_in_dim(stack, at, 0, keepdims=False)


def _put_layer(pool, rows, at):
    """`rows` written over layer `at` of `pool`, in place on a carried or
    donated buffer."""
    return jax.lax.dynamic_update_slice(
        pool, rows[None].astype(pool.dtype), (at,) + (0,) * rows.ndim)


def _rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return xf * r * scale


def _mamba(lp, u, conv, ssm, at, lengths, cfg: JambaConfig):
    """The Mamba mixer on u [B, T, D] (one step of B rows, or B = 1 and
    a run of T) from the rows layer `at` of the pools `conv`
    [Lm, B, K-1, Di] and `ssm` [Lm, B, N, Di] carry; `lengths` [B] of
    the T tokens are real -> (out [B, T, D] float32, the pools with that
    layer's rows as they stand after the last real one, written in
    place)."""
    T = u.shape[1]
    K, N, R = cfg.mamba_d_conv, cfg.mamba_d_state, cfg.mamba_dt_rank
    f32, eps = jnp.float32, cfg.rms_norm_eps
    with jax.named_scope("mamba/in_proj"):
        x, z = jnp.split(jnp.einsum("btd,de->bte", u, lp["in_w"]), 2, -1)
    with jax.named_scope("mamba/conv"):
        held = _at_layer(conv, at)
        rows = jnp.concatenate([held, x], axis=1)          # [B, K-1+T, Di]
        w = lp["conv_w"].astype(f32)
        acc = lp["conv_b"].astype(f32) + sum(
            w[k] * rows[:, k:k + T].astype(f32) for k in range(K))
        xc = jax.nn.silu(acc).astype(u.dtype)
        if T == 1:
            held = jnp.where((lengths > 0)[:, None, None], rows[:, 1:], held)
        else:
            # the K-1 rows before each row's true end
            held = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
                r, n, K - 1, axis=0))(rows, lengths)
        conv = _put_layer(conv, held, at)
    with jax.named_scope("mamba/x_proj"):
        dbc = jnp.einsum("bte,er->btr", xc, lp["x_w"],
                         preferred_element_type=f32)
        dt = _rms_norm(dbc[..., :R], lp["dt_norm"], eps)
        b = _rms_norm(dbc[..., R:R + N], lp["b_norm"], eps)
        c = _rms_norm(dbc[..., R + N:], lp["c_norm"], eps)
        delta = jax.nn.softplus(
            jnp.einsum("btr,re->bte", dt.astype(u.dtype), lp["dt_w"],
                       preferred_element_type=f32) + lp["dt_b"])
    a = -jnp.exp(lp["a_log"])
    with jax.named_scope("mamba/state_update" if T == 1 else "mamba/scan"):
        state = _at_layer(ssm, at)
        if T == 1:
            y, state = selective_state_update(
                state, xc[:, 0], delta[:, 0], a, b[:, 0], c[:, 0], lp["d"],
                live=lengths > 0)
            y = y[:, None]
        else:
            # one sequence, unbatched: with a unit batch axis inside them
            # the scan's rows are no whole tiles, and the chip re-laid
            # them out on every trip
            y, state = selective_scan(
                xc[0], delta[0], a, b[0], c[0], lp["d"], state[0],
                lengths[0], chunk=SCAN_CHUNK)
            y, state = y[None], state[None]
        ssm = _put_layer(ssm, state, at)
    with jax.named_scope("mamba/out_proj"):
        gated = (y * jax.nn.silu(z.astype(f32))).astype(u.dtype)
        out = jnp.einsum("bte,ed->btd", gated, lp["out_w"],
                         preferred_element_type=f32)
    return out, conv, ssm


def _attention(lp, u, kc, vc, at, pos, whole_prompt, cfg: JambaConfig):
    """Multi-query attention, no positional embedding, on u [B, T, D]:
    the step's keys and values go into layer `at` of the pools, then the
    queries read them back — or, for a whole prompt into an empty cache,
    attend the step's own keys in blocks."""
    B, T, _ = u.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("btd,dh->bth", u, lp["q_w"]).reshape(B, T, H, hd)
    k = jnp.einsum("btd,dh->bth", u, lp["k_w"]).reshape(B, T, KV, hd)
    v = jnp.einsum("btd,dh->bth", u, lp["v_w"]).reshape(B, T, KV, hd)
    with jax.named_scope("attention/full"):
        kc, vc = write_kv(kc, k, pos, at), write_kv(vc, v, pos, at)
        if whole_prompt:
            ctx = blocked_attention(q, k, v, block=ATTENTION_BLOCK)
        else:
            ctx = cached_attention(q, kc, vc, pos, impl="native", layer=at)
    out = jnp.einsum("bth,hd->btd", ctx.astype(u.dtype).reshape(B, T, H * hd),
                     lp["o_w"], preferred_element_type=jnp.float32)
    return out, kc, vc


def _mlp(lp, u):
    with jax.named_scope("mlp"):
        g = jax.nn.silu(jnp.einsum("btd,df->btf", u, lp["gate_w"])) \
            * jnp.einsum("btd,df->btf", u, lp["up_w"])
        return jnp.einsum("btf,fd->btd", g, lp["down_w"],
                          preferred_element_type=jnp.float32)


def _hidden(params, tokens, cache, pos, cfg: JambaConfig, live=None):
    """tokens [B, T] -> (the final-normed hidden state [B, T, D], the
    updated cache). `pos` as in models/llama.py; `live` [B, T] marks the
    real tokens, a prefix of each row (None: all)."""
    B, T = tokens.shape
    if T > 1 and B > 1:
        raise ValueError(
            f"jamba: a run of {T} tokens is ONE sequence, not {B} (the "
            "engine's prefill; what would batch runs — prefill_chunk, "
            "spec_decode, multi_tick — the family refuses)")
    period = cfg.period
    n_periods = cfg.num_layers // len(period)
    in_period = {kind: sum(1 for t in period if t == kind)
                 for kind in (MAMBA, ATTENTION)}
    lengths = jnp.full((B,), T, jnp.int32) if live is None \
        else jnp.sum(live, axis=1).astype(jnp.int32)
    # a whole prompt into an empty cache attends its own keys in blocks;
    # anything else (decode, a run that continues a cache) reads the pool
    whole_prompt = (T > 1 and isinstance(pos, int) and pos == 0
                    and T % min(ATTENTION_BLOCK, T) == 0)
    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)

    def residual(x, out):
        return (x.astype(jnp.float32) + out).astype(x.dtype)

    def period_fn(carry, i):
        x, kc, vc, conv, ssm = carry
        seen = {MAMBA: 0, ATTENTION: 0}
        for j, kind in enumerate(period):
            at = i * in_period[kind] + seen[kind]
            seen[kind] += 1
            # a layer's leaves are read straight out of the whole stacks
            # (as xs the scan would copy each period's 2.9 GB of weights
            # out of them before its body ran)
            lp = {k: _at_layer(params[k], i * len(period) + j)
                  for k in _EVERY}
            u = _rms_norm(x, lp["norm_in"], cfg.rms_norm_eps).astype(x.dtype)
            if kind == MAMBA:
                lp.update({k: _at_layer(params[k], at) for k in _MAMBA})
                out, conv, ssm = _mamba(lp, u, conv, ssm, at, lengths, cfg)
            else:
                lp.update({k: _at_layer(params[k], at) for k in _ATTENTION})
                out, kc, vc = _attention(lp, u, kc, vc, at, pos,
                                         whole_prompt, cfg)
            x = residual(x, out)
            u = _rms_norm(x, lp["norm_ff"], cfg.rms_norm_eps).astype(x.dtype)
            x = residual(x, _mlp(lp, u))
        return (x, kc, vc, conv, ssm), None

    carry = (x, cache["k"], cache["v"], cache["conv"], cache["ssm"])
    if n_periods == 1:
        carry, _ = period_fn(carry, 0)
    else:
        carry, _ = jax.lax.scan(period_fn, carry,
                                jnp.arange(n_periods, dtype=jnp.int32))
    x, kc, vc, conv, ssm = carry
    x = _rms_norm(x, params["norm_f"], cfg.rms_norm_eps).astype(cfg.dtype)
    held = B * cache["k"].shape[2]
    if T == 1:
        stats = (B, held, held, 0)
    else:
        stats = (0, 0, 0, B * -(-T // SCAN_CHUNK) * cfg.layers_of(MAMBA))
    return x, {"k": kc, "v": vc, "conv": conv, "ssm": ssm,
               "stats": jnp.asarray(stats, jnp.int32)}


def _head(params, x):
    """Tied embeddings: logits = x . wte^T, float32."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,vd->btv", x, params["wte"],
                          preferred_element_type=jnp.float32)


def jamba_forward_cached(params, tokens, cache, pos, cfg: JambaConfig,
                         live=None):
    """Forward tokens [B, T] against a cache holding `pos` tokens ->
    (logits [B, T, V] float32, updated cache): the families' contract
    (models/llama.py), over the pools of `init_cache`. T = 1 with per-row
    `pos` is the decode tick; T > 1 is ONE sequence (B = 1), continued
    from the state its cache rows hold (all zeros: a fresh prompt). `live`
    [B, T] bool marks the real tokens, a prefix of each row: the
    recurrent state a row is left with is the one after its last real
    token, and a row with none (an idle slot) keeps its rows as they
    were."""
    x, cache = _hidden(params, tokens, cache, pos, cfg, live)
    return _head(params, x), cache


def prefill_into_slot(params, cache, padded, true_len, slot,
                      cfg: JambaConfig):
    """The engine's bucketed prefill of ONE request (padded [1, bucket],
    `true_len` real tokens) into slot `slot` of the pools -> (the last
    real position's logits [1, V] float32, the pools). The prompt runs
    through an empty cache of its own; every row of every kind the slot
    holds is then replaced: keys and values by position up to the bucket
    (the rest is behind the position mask), the recurrent state and the
    convolution rows WHOLE, as they stood after position true_len - 1 —
    nothing of the slot's last occupant is left to read."""
    T = padded.shape[1]
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < true_len
    x, mini = _hidden(params, padded, init_cache(cfg, 1, T), 0, cfg, live)
    last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
    out = {"stats": mini["stats"]}
    for name in ("k", "v", "conv", "ssm"):
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], mini[name], (0, slot) + (0,) * (mini[name].ndim - 2))
    return _head(params, last)[:, 0], out
