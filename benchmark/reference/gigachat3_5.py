"""The plain reference for `model_type: gigachat3_5` (GigaChat3.5-432B-A28B,
ai-sage/GigaChat3.5-432B-A28B config.json): the full forward of ONE
sequence in straightforward `jax.numpy`, float32, every matmul at
`precision="highest"`; the delta rule is ONE sequential `lax.scan` over
positions (no chunks), latent attention is decompressed at every position;
no cache, no slots, no kernels. It imports nothing of the program and is
what `correct` is judged against in the gigachat3.5 cells.

Layer i, h the residual stream [T, D] (`layernorm_type: pre_post`):
    h = h + N2(Mixer_i(N1 h));   h = h + N4(FFN_i(N3 h))
    logits = N_f(h) @ head^T                (`tie_word_embeddings` false)
N(x; w) = x / sqrt(mean(x^2) + `rms_norm_eps`) * (1 + w): zero-centred.

Mixer: latent attention (MLA) on the `full_attention_layers`, gated
delta-rule linear attention (GatedDeltaNet) on every other layer:
- linear, on u [T, D]: q, k = u W_q, u W_k (`linear_num_key_heads` x
  `linear_key_head_dim`); v, z = u W_v, u W_z (`linear_num_value_heads` x
  `linear_value_head_dim`). Over [q; k; v] a causal depthwise convolution
  of width `linear_conv_kernel_dim`, no bias, zeros before the first
  position, then SiLU. q <- L2(q) / sqrt(dk), k <- L2(k); value head j
  reads key head j // (value heads / key heads). beta = sigmoid(u W_b),
  g = -exp(A_log) softplus(u W_a + dt_bias). Per value head, S [dk, dv]
  from 0:
      S' = exp(g_t) S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
      o_t = S_t^T q_t
  o <- RMSNorm(o per head; 1 + w_o, `linear_attn_o_norm_eps`) x
  `linear_sigmoid_gate_scale` x sigmoid(z); out = o W_o.
- latent (DeepSeek-V3's convention, JoyAI's reference): c_q =
  RMSNorm(u W_qa); q = c_q W_qb -> heads of nope | rope; u W_kva ->
  `kv_lora_rank` | rope: c_kv = RMSNorm(first), k_pe the rest, one rotated
  key all heads share. RoPE interleaved (`rope_interleave`) at
  `rope_theta` with YaRN frequencies (`rope_scaling`: factor,
  original_max_position_embeddings, beta_fast, beta_slow). k_nope = c_kv
  W_kb, v = c_kv W_vb; score = q . k / sqrt(qk_head_dim) x mscale^2
  (`use_mla_scaling_factor`; mscale = 0.1 mscale_all_dim ln(factor) + 1);
  causal softmax; ctx = P v; ctx <- ctx x sigmoid(u W_g) (`gated_attention`);
  out = ctx W_o.

FFN: the first `first_k_dense_replace` layers a gated-SiLU MLP of
`intermediate_size`; the others routed experts (`noaux_tc`: sigmoid
scores float32, chosen by score + bias, weighted by the scores, normalised,
times `routed_scaling_factor`) plus one shared expert added unweighted.
Every gated product is clamped at `swiglu_limit`:
silu(min(g, L)) * clip(u, -L, L).

And the cut (model-configs guide, section 4): the tree holds the routed
experts `first_expert .. first_expert + held - 1` and the rows of the
vocabulary it serves; the router scores every published expert, and what
the absent experts would add is left out.

On the chip the weights stay as the seed made them, in bfloat16, and each
is widened where it is used; attention runs over blocks of query rows and
the experts one at a time.

`precision` is the arithmetic of every matmul operand: "float32" the
reference proper, "bfloat16", and "fp8" (float8_e4m3 with a per-tensor
scale) — the CONTROL, the nearest precision below the stated bf16; the
convolution, the norms and the recurrence stay float32 in all three.
`state="bfloat16"` is the control of the OTHER precision the configuration
states, `recurrent_state: float32`: the delta-rule state is rounded to
bfloat16 after every step (a step's own output is read before the
rounding). The other keywords are PLANTED FAULTS, each a way the serving
program could be wrong that `correct` must catch:
  decay=False         g left out: the state never decays
  beta=False          beta left out (1 everywhere)
  qk_norm=False       q and k not L2-normed (q still / sqrt(dk))
  head_map="modulo"   value head j reads key head j % key heads
  gate_scale=False    the output gate without its scale 2
  mscale=False        the YaRN softmax factor left out
  latent_shift=n      latent attention reads the latent of positions n
                      too early (a work list off by a block of n)
  hidden_keys=(a, b)  positions a .. b - 1 are padding that the linear
                      layers consume like tokens while latent attention
                      neither sees them nor counts them as positions (a
                      padded prompt allowed to advance the state)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .joyai_llm_flash import _mm, _rms_norm, route

QUERY_ROWS = 128          # a block of query rows sees every key at once
NORMS = ("norm_mix_in", "norm_mix_out", "norm_ffn_in", "norm_ffn_out")
LINEAR = ("dn_q_w", "dn_k_w", "dn_v_w", "dn_z_w", "dn_b_w", "dn_a_w",
          "dn_conv_w", "dn_a_log", "dn_dt_bias", "dn_o_norm", "dn_o_w")
FULL = ("q_a_w", "q_a_norm", "q_b_w", "kv_a_w", "kv_a_norm", "k_b_w",
        "v_b_w", "o_w", "attn_gate_w")
DENSE = ("gate_w", "up_w", "down_w")
EXPERTS = ("router_w", "router_bias", "shared_gate_w", "shared_up_w",
           "shared_down_w", "exp_gate_w", "exp_up_w", "exp_down_w")


def _to_bf16(x):
    """float32 rounded to bfloat16's bits, as `reduce_precision` (a pair of
    converts is taken out by the chip's compiler: reference/jamba.py)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _zc_norm(x, w, eps):
    return _rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _swiglu(g, u, limit):
    if limit is None:
        return jax.nn.silu(g) * u
    return jax.nn.silu(jnp.minimum(g, limit)) * jnp.clip(u, -limit, limit)


def _mlp(u, gate_w, up_w, down_w, arch: dict, precision: str):
    return _mm("tf,fd->td", _swiglu(_mm("td,df->tf", u, gate_w, precision),
                                    _mm("td,df->tf", u, up_w, precision),
                                    arch["swiglu_limit"]),
               down_w, precision)


def yarn_frequencies(arch: dict) -> np.ndarray:
    """The rotated dims' inverse frequencies under YaRN: theta's for the
    dims that turn more than `beta_fast` times over the original context,
    theta's / factor for those that turn fewer than `beta_slow` times, a
    linear ramp between (the dims' indices floored and ceiled)."""
    dim, base = arch["qk_rope_head_dim"], arch["rope_theta"]
    theta = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = arch["rope_factor"]
    if factor <= 1:
        return theta.astype(np.float32)
    original = arch["original_max_position_embeddings"]

    def dim_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(dim_of(arch["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(arch["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (theta * (1.0 - ramp) + theta / factor * ramp).astype(np.float32)


def _rope(x, positions, inv):
    """x [T, heads, hd] at `positions` [T], interleaved pairs."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _linear(u, p, arch: dict, precision: str, state: str, decay: bool,
            beta: bool, qk_norm: bool, head_map: str, gate_scale: bool,
            keep=None):
    """-> (the mixer's output [T, D], the state [hv, dk, dv] as it is held
    after the first `keep` positions (traced; None: the last one's))."""
    T = u.shape[0]
    hk, hv = arch["linear_num_key_heads"], arch["linear_num_value_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    qkv = jnp.concatenate([_mm("td,dc->tc", u, p[n], precision)
                           for n in ("dn_q_w", "dn_k_w", "dn_v_w")], -1)
    w = p["dn_conv_w"].astype(jnp.float32)
    K = w.shape[0]
    rows = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv])
    x = jax.nn.silu(sum(w[i] * rows[i:i + T] for i in range(K)))
    q = x[:, :hk * dk].reshape(T, hk, dk)
    k = x[:, hk * dk:2 * hk * dk].reshape(T, hk, dk)
    v = x[:, 2 * hk * dk:].reshape(T, hv, dv)
    if qk_norm:
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q / math.sqrt(dk)
    heads = np.arange(hv)
    if head_map == "interleave":
        heads = heads // (hv // hk)
    elif head_map == "modulo":
        heads = heads % hk
    else:
        raise ValueError(f"unknown head_map {head_map!r}")
    q, k = q[:, heads], k[:, heads]
    b = jax.nn.sigmoid(_mm("td,dh->th", u, p["dn_b_w"], precision)) \
        if beta else jnp.ones((T, hv))
    g = -jnp.exp(p["dn_a_log"]) * jax.nn.softplus(
        _mm("td,dh->th", u, p["dn_a_w"], precision) + p["dn_dt_bias"]) \
        if decay else jnp.zeros((T, hv))
    if state not in ("float32", "bfloat16"):
        raise ValueError(f"unknown state precision {state!r}")
    held = _to_bf16 if state == "bfloat16" else (lambda s: s)

    last = T if keep is None else keep

    def step(carry, at):                              # s [hv, dk, dv]
        s, kept = carry
        qt, kt, vt, gt, bt, t = at
        s = jnp.exp(gt)[:, None, None] * s
        pred = jnp.einsum("hkv,hk->hv", s, kt, precision="highest")
        s = s + kt[:, :, None] * (bt[:, None] * (vt - pred))[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, qt, precision="highest")
        s = held(s)
        return (s, jnp.where(t == last - 1, s, kept)), o

    zero = jnp.zeros((hv, dk, dv))
    (_, kept), o = jax.lax.scan(step, (zero, zero),
                                (q, k, v, g, b, jnp.arange(T)))
    z = _mm("td,dc->tc", u, p["dn_z_w"], precision).reshape(T, hv, dv)
    scale = arch["linear_sigmoid_gate_scale"] if gate_scale else 1.0
    o = _zc_norm(o, p["dn_o_norm"], arch["linear_attn_o_norm_eps"]) \
        * scale * jax.nn.sigmoid(z)
    return _mm("tc,cd->td", o.reshape(T, -1), p["dn_o_w"],
               precision), kept


def _latent(u, p, positions, seen, arch: dict, precision: str,
            mscale: bool, latent_shift: int = 0):
    """Every position's latent decompressed into each head's key and value
    at once; queries, their scores against every key and their softmax one
    block of QUERY_ROWS rows at a time. `seen` [T] marks the keys a query
    may see at all (False: hidden padding); key j is read from the latent
    of position j - `latent_shift` (zeros before the first)."""
    T = u.shape[0]
    H, C = arch["num_heads"], arch["kv_lora_rank"]
    dn, dr = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    eps, inv = arch["layer_norm_eps"], yarn_frequencies(arch)
    scale = 1.0 / math.sqrt(dn + dr)
    if mscale and arch["use_mla_scaling_factor"] and arch["rope_factor"] > 1:
        m = 0.1 * arch["mscale_all_dim"] * math.log(arch["rope_factor"]) + 1
        scale *= m * m
    kv = _mm("td,dc->tc", u, p["kv_a_w"], precision)
    c_kv = _rms_norm(kv[:, :C], p["kv_a_norm"], eps)
    k_pe = _rope(kv[:, None, C:], positions, inv)
    if latent_shift:
        c_kv, k_pe = (jnp.concatenate([jnp.zeros_like(a[:latent_shift]),
                                       a[:-latent_shift]])
                      for a in (c_kv, k_pe))
    k_nope = _mm("tc,ch->th", c_kv, p["k_b_w"], precision).reshape(T, H, dn)
    v = _mm("tc,ch->th", c_kv, p["v_b_w"], precision).reshape(T, H, -1)
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
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], at, inv)], -1)
        me = start + jnp.arange(rows)
        # a row always sees itself: a hidden (padding) row has an answer,
        # which nothing reads
        mask = (keys <= me[:, None]) & (seen[None, :] | (keys == me[:, None]))
        s = _mm("ihd,jhd->hij", q, k, precision) * scale
        pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        ctx = _mm("hij,jhd->ihd", pr, v, precision).reshape(rows, -1)
        ctx = ctx * jax.nn.sigmoid(_mm("td,dh->th", ub, p["attn_gate_w"],
                                       precision))
        return _mm("th,hd->td", ctx, p["o_w"], precision)

    return jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, -1)


def _experts(u, p, arch: dict, precision: str):
    chosen, weight = route(u, p["router_w"], p["router_bias"], arch)

    def held(total, e):           # this chip's experts, one at a time
        w = jnp.sum(jnp.where(chosen == arch["first_expert"] + e, weight,
                              0.0), -1)
        one = _mlp(u, *(jax.lax.dynamic_index_in_dim(
            p["exp_" + name], e, 0, keepdims=False) for name in DENSE),
            arch, precision)
        return total + w[:, None] * one, None

    routed, _ = jax.lax.scan(held, jnp.zeros_like(u),
                             jnp.arange(p["exp_gate_w"].shape[0]))
    return routed + _mlp(u, p["shared_gate_w"], p["shared_up_w"],
                         p["shared_down_w"], arch, precision)


def layer_types(arch: dict):
    return tuple("full" if i in arch["full_attention_layers"] else "linear"
                  for i in range(arch["num_layers"]))


def hidden(params, tokens, arch: dict, *, precision: str = "float32",
           state: str = "float32", decay: bool = True, beta: bool = True,
           qk_norm: bool = True, head_map: str = "interleave",
           gate_scale: bool = True, mscale: bool = True, hidden_keys=None,
           latent_shift: int = 0, keep=None):
    """tokens [T] -> (the final-normed hidden state [T, D] float32, the
    linear layers' delta-rule states [linear layers, hv, dk, dv] after the
    first `keep` positions, traced; None: after the last). The layers run
    one after the other, each reading its leaves out of the stacks of its
    kind."""
    T = tokens.shape[0]
    eps = arch["layer_norm_eps"]
    at = jnp.arange(T)
    seen = jnp.ones((T,), bool)
    if hidden_keys is not None:
        seen = (at < hidden_keys[0]) | (at >= hidden_keys[1])
    # latent attention counts only the positions it sees
    positions = jnp.cumsum(seen) - 1
    h = jnp.take(params["wte"], tokens, axis=0).astype(jnp.float32)
    seen_kind = {"linear": 0, "full": 0}
    states = []
    dense = arch["first_k_dense_replace"]
    for i, kind in enumerate(layer_types(arch)):
        j = seen_kind[kind]
        seen_kind[kind] += 1
        p = {k: params[k][i] for k in NORMS}
        p.update({k: params[k][j] for k in (LINEAR if kind == "linear"
                                            else FULL)})
        u = _zc_norm(h, p["norm_mix_in"], eps)
        if kind == "linear":
            out, kept = _linear(u, p, arch, precision, state, decay, beta,
                                qk_norm, head_map, gate_scale, keep)
            states.append(kept)
        else:
            out = _latent(u, p, positions, seen, arch, precision, mscale,
                          latent_shift)
        h = h + _zc_norm(out, p["norm_mix_out"], eps)
        u = _zc_norm(h, p["norm_ffn_in"], eps)
        if i < dense:
            out = _mlp(u, *(params[k][i] for k in DENSE), arch, precision)
        else:
            out = _experts(u, {k: params[k][i - dense] for k in EXPERTS},
                           arch, precision)
        h = h + _zc_norm(out, p["norm_ffn_out"], eps)
    if not states:                      # a model with no linear layer
        return _zc_norm(h, params["norm_f"], eps), jnp.zeros((0,))
    return _zc_norm(h, params["norm_f"], eps), jnp.stack(states)


def logits_rows(params, tokens, at, arch: dict, **kw):
    """(Logits [len(at), V] at the positions `at` (traced indices) of the
    sequence `tokens` [T], the delta-rule states of `hidden`)."""
    h, states = hidden(params, tokens, arch, **kw)
    return _mm("td,vd->tv", jnp.take(h, at, axis=0), params["head_w"],
               kw.get("precision", "float32")), states


def forward(params, tokens, arch: dict, **kw):
    """tokens [T] -> logits [T, V] float32."""
    return logits_rows(params, tokens, jnp.arange(tokens.shape[0]), arch,
                       **kw)[0]
