"""The plain reference for `model_type: jamba` (AI21-Jamba2-3B,
ai21labs/AI21-Jamba2-3B config.json): the full forward of ONE sequence in
straightforward `jax.numpy`, float32, `precision="highest"`; the
recurrence is ONE sequential `lax.scan` over positions; no cache, no
chunks, no slots, no kernels. It imports nothing of the program (and
nothing of the program's tests, which keep a reference of their own) and
is what `correct` is judged against in the jamba2 cells.

Layer i of `num_layers`, h the residual stream [T, D]:
    h = h + Mixer_i(RMSNorm(h; norm_in_i))
    h = h + W_down(silu(W_gate u) * (W_up u)),  u = RMSNorm(h; norm_ff_i)
    logits = RMSNorm(h; norm_f) @ wte^T         (`tie_word_embeddings`)
RMSNorm(x; w) = x * rsqrt(mean(x^2) + `rms_norm_eps`) * w, float32.
`num_experts` is 1: every MLP is the dense SwiGLU (`hidden_act` silu) and
the `expert_layer_*` keys do nothing.

The mixer is attention where i % `attn_layer_period` ==
`attn_layer_offset` — `num_attention_heads` query heads over
`num_key_value_heads` K/V heads, causal softmax(q k^T / sqrt(hd)) v, no
bias, NO positional embedding — and Mamba-1 everywhere else, on
u [T, D]:
  1. [x, z] = u @ in_w                          (`mamba_proj_bias` false)
  2. x_t <- silu(conv_b + sum_k conv_w[k] * x_{t-(K-1)+k}), K =
     `mamba_d_conv`, zeros before the first position
  3. [dt, B, C] = x @ x_w, split `mamba_dt_rank` / `mamba_d_state` /
     `mamba_d_state`, then Jamba's inner norms: RMSNorm of each
  4. delta = softplus(dt @ dt_w + dt_b);  A = -exp(a_log)
  5. s_t = exp(delta_t * A) * s_{t-1} + delta_t * B_t * x_t   (s_0 = 0)
     y_t = sum_n C_t[n] * s_t[n] + d * x_t
  6. out = (y * silu(z)) @ out_w

Departures from the published code, each listed under `assumed` in the
configuration file where it is a choice: head size hidden / heads
(`head_dim` is null); the three inner norms have no config key; `a_log`
and the state are held [d_state, d_inner] and the convolution's weight
[d_conv, d_inner] (published [d_inner, d_state] and [d_inner, 1, d_conv]:
the same numbers in another order).

On the chip the matrices stay as the seed made them, in bfloat16
(float32 would be 12 GB), and each is widened where it is used;
attention runs over blocks of query rows, so that 7k positions fit.

`precision` is the arithmetic of every matmul operand: "float32" the
reference proper, "bfloat16", and "fp8" (float8_e4m3 with a per-tensor
scale) — the CONTROL, the nearest precision below the stated bf16; the
convolution, the norms and the recurrence stay float32 in all three.
`state="bfloat16"` is the control of the OTHER precision the
configuration states, `ssm_state: float32`: the recurrent state is
rounded to bfloat16 after every step, as a pool held in bfloat16 would
hand it to the next one (a step's own y is read before the rounding).
The other keywords are PLANTED FAULTS, each a way the serving program
could be wrong that `correct` must catch:
  inner_norms=False   step 3 without its three norms
  conv_shift=1        the convolution reads one row too early
                      (x_{t-K+k}: a carried window shifted by one)
  frozen_from=P       from position P on, every step starts from the
                      state after position P - 1 (a tick's state is
                      never written back)
  hidden_keys=(a, b)  positions a .. b - 1 are padding that the Mamba
                      layers consume like tokens while attention does
                      not see them (a padded prompt allowed to advance
                      the state)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
QUERY_ROWS = 64           # a block of query rows sees every key at once
MAMBA = ("in_w", "conv_w", "conv_b", "x_w", "dt_norm", "b_norm", "c_norm",
         "dt_w", "dt_b", "a_log", "d", "out_w")
ATTENTION = ("q_w", "k_w", "v_w", "o_w")
EVERY = ("norm_in", "norm_ff", "gate_w", "up_w", "down_w")


def layer_types(arch: dict):
    return tuple(
        "attention" if i % arch["attn_layer_period"]
        == arch["attn_layer_offset"] else "mamba"
        for i in range(arch["num_layers"]))


def _to_bf16(x):
    """float32 rounded to bfloat16's 8 exponent and 7 mantissa bits, as
    `reduce_precision` and not as a pair of converts: the chip's compiler
    takes f32 -> bf16 -> f32 out (excess precision is allowed), and a
    control made of the pair moved no logit by a bit there (PERF.md,
    PR 35)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _round_operand(x, precision: str):
    x = x.astype(jnp.float32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return _to_bf16(x)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round_operand(a, precision),
                      _round_operand(b, precision), precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def _mamba(u, p, eps, precision: str, state: str, inner_norms: bool,
           conv_shift: int, frozen_from):
    T = u.shape[0]
    x, z = jnp.split(_mm("td,de->te", u, p["in_w"], precision), 2, axis=-1)
    w, bias = p["conv_w"].astype(jnp.float32), p["conv_b"].astype(jnp.float32)
    K = w.shape[0]
    rows = jnp.concatenate([jnp.zeros((K - 1 + conv_shift, x.shape[1])), x])
    x = jax.nn.silu(bias + sum(w[k] * rows[k:k + T] for k in range(K)))
    N, R = p["a_log"].shape[0], p["dt_norm"].shape[0]
    dbc = _mm("te,er->tr", x, p["x_w"], precision)
    dt, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if inner_norms:
        dt, B, C = (_rms_norm(a, p[k], eps) for a, k in (
            (dt, "dt_norm"), (B, "b_norm"), (C, "c_norm")))
    delta = jax.nn.softplus(_mm("tr,re->te", dt, p["dt_w"], precision)
                            + p["dt_b"])
    A = -jnp.exp(p["a_log"])                               # [N, Di]
    frozen_from = T if frozen_from is None else frozen_from
    if state not in ("float32", "bfloat16"):
        raise ValueError(f"unknown state precision {state!r}")
    held = _to_bf16 if state == "bfloat16" else (lambda s: s)

    def step(carry, at):
        s, kept = carry               # kept: the state a frozen step reads
        t, d, xt, b, c = at
        prev = jnp.where(t < frozen_from, s, kept)
        s = jnp.exp(d[None, :] * A) * prev + (d * xt)[None, :] * b[:, None]
        kept = jnp.where(t < frozen_from, s, kept)
        y = jnp.sum(c[:, None] * s, axis=0) + p["d"] * xt
        return (held(s), kept), y

    zero = jnp.zeros_like(A)
    _, y = jax.lax.scan(step, (zero, zero),
                        (jnp.arange(T), delta, x, B, C))
    return _mm("te,ed->td", y * jax.nn.silu(z), p["out_w"], precision)


def _attention(u, p, arch: dict, precision: str, hidden_keys):
    """Keys and values of the whole sequence at once (one K/V head: small);
    queries, their scores against every key and their softmax one block
    of QUERY_ROWS rows at a time — the same sums as all rows at once."""
    T = u.shape[0]
    H, KV = arch["num_heads"], arch["num_kv_heads"]
    k = _mm("td,dh->th", u, p["k_w"], precision).reshape(T, KV, -1)
    v = _mm("td,dh->th", u, p["v_w"], precision).reshape(T, KV, -1)
    hd = k.shape[-1]
    rows = min(QUERY_ROWS, T)
    keys = jnp.arange(T)[None, :]
    seen = jnp.ones((1, T), bool) if hidden_keys is None else \
        (keys < hidden_keys[0]) | (keys >= hidden_keys[1])

    def block(start):
        ub = jax.lax.dynamic_slice_in_dim(u, start, rows, axis=0)
        at = start + jnp.arange(rows)
        q = _mm("td,dh->th", ub, p["q_w"], precision).reshape(
            rows, KV, H // KV, hd)
        # a row always sees itself: a hidden (padding) row has an answer,
        # which nothing reads
        mask = (keys <= at[:, None]) & (seen | (keys == at[:, None]))
        s = _mm("ikgd,jkd->kgij", q, k, precision) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf),
                            axis=-1)
        ctx = _mm("kgij,jkd->ikgd", pr, v, precision).reshape(rows, -1)
        return _mm("th,hd->td", ctx, p["o_w"], precision)

    if T % rows:
        raise ValueError(f"{T} positions are no whole blocks of {rows}")
    return jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, -1)


def _runs(types):
    """(kind, first layer, how many) for each run of layers of one kind."""
    runs = []
    for i, kind in enumerate(types):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1])
    return runs


def hidden(params, tokens, arch: dict, *, precision: str = "float32",
           state: str = "float32", inner_norms: bool = True, conv_shift: int = 0, frozen_from=None,
           hidden_keys=None):
    """tokens [T] -> the final-normed hidden state [T, D] float32. `arch`
    holds num_layers, num_heads, num_kv_heads, attn_layer_period,
    attn_layer_offset, layer_norm_eps (the config's `rms_norm_eps`). The
    layers run one after the other; each run of layers of one kind is a
    `lax.scan` over its stacked leaves (28 layers written out took the
    chip's compiler 100 s a sequence length)."""
    eps = arch["layer_norm_eps"]

    def layer(kind):
        def one(h, p):
            u = _rms_norm(h, p["norm_in"], eps)
            if kind == "mamba":
                h = h + _mamba(u, p, eps, precision, state, inner_norms,
                               conv_shift, frozen_from)
            else:
                h = h + _attention(u, p, arch, precision, hidden_keys)
            u = _rms_norm(h, p["norm_ff"], eps)
            g = jax.nn.silu(_mm("td,df->tf", u, p["gate_w"], precision)) \
                * _mm("td,df->tf", u, p["up_w"], precision)
            return h + _mm("tf,fd->td", g, p["down_w"], precision), None
        return one

    h = jnp.take(params["wte"], tokens, axis=0).astype(jnp.float32)
    seen = {"mamba": 0, "attention": 0}
    for kind, first, count in _runs(layer_types(arch)):
        m = seen[kind]
        seen[kind] += count
        stack = {k: params[k][first:first + count] for k in EVERY}
        stack.update({k: params[k][m:m + count]
                      for k in (MAMBA if kind == "mamba" else ATTENTION)})
        h, _ = jax.lax.scan(layer(kind), h, stack)
    return _rms_norm(h, params["norm_f"], eps)


def logits_rows(params, tokens, at, arch: dict, **kw):
    """Logits [len(at), V] at the positions `at` (traced indices) of the
    sequence `tokens` [T]."""
    x = jnp.take(hidden(params, tokens, arch, **kw), at, axis=0)
    return _mm("td,vd->tv", x, params["wte"], kw.get("precision", "float32"))


def forward(params, tokens, arch: dict, **kw):
    """tokens [T] -> logits [T, V] float32."""
    return logits_rows(params, tokens, jnp.arange(tokens.shape[0]), arch,
                       **kw)
