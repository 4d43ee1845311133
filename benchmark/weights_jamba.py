"""Weights of the jamba family from `--seed`, made by the benchmark on the
device in the types the configuration stores them in — the matrices in
`precision.parameters` (bfloat16), one slice of a leaf at a time as
weights_cohere2_moe makes them; `a_log`, `d`, `dt_b` and the norm scales
float32.

The tree has the leaf names and shapes the program's family takes
(`paddle_tpu.models.jamba`: a leaf every layer has stacked over all
layers, a mixer's leaf over the layers of its kind; d_inner the last
axis of `a_log`) — that is the system's interface, not its code: nothing
here imports the program, so the plain reference is handed the same
weights.

The draws (the configuration file's `assumed.random_weights`): matrices
normal std 0.02, the three output projections 0.02 / sqrt(2 x layers),
norm scales and `d` 1 +- 0.02, the tied embedding std 0.002
(weights_cohere2_moe.EMBEDDING_STD says why), the convolution uniform
within 1 / sqrt(d_conv) as a Conv1d is born, and what decides whether the
state remembers as the Mamba paper sets it: a_log = log(1..d_state) on
every channel, dt_b the inverse softplus of a log-uniform step in
[0.001, 0.1].
"""
from __future__ import annotations

import functools
import math

from .weights import _key
from .weights_cohere2_moe import EMBEDDING_STD, _leaf_maker

F32_LEAVES = ("norm_f", "norm_in", "norm_ff", "dt_norm", "b_norm", "c_norm",
              "dt_b", "a_log", "d")


def layers_of(a: dict, kind: str) -> int:
    attention = sum(1 for i in range(a["num_layers"])
                    if i % a["attn_layer_period"] == a["attn_layer_offset"])
    return attention if kind == "attention" else a["num_layers"] - attention


def shapes(a: dict) -> dict:
    """`a`: the sizes `runners/serve_jamba.architecture` returns."""
    n, d, f = a["num_layers"], a["hidden_size"], a["ffn_hidden"]
    nm, na = layers_of(a, "mamba"), layers_of(a, "attention")
    di, ns, r = a["mamba_expand"] * d, a["mamba_d_state"], a["mamba_dt_rank"]
    hq, hkv = a["num_heads"] * a["head_dim"], a["num_kv_heads"] * a["head_dim"]
    return {
        "wte": (a["vocab_size"], d), "norm_f": (d,),
        "norm_in": (n, d), "norm_ff": (n, d),
        "gate_w": (n, d, f), "up_w": (n, d, f), "down_w": (n, f, d),
        "in_w": (nm, d, 2 * di), "conv_w": (nm, a["mamba_d_conv"], di),
        "conv_b": (nm, di), "x_w": (nm, di, r + 2 * ns),
        "dt_norm": (nm, r), "b_norm": (nm, ns), "c_norm": (nm, ns),
        "dt_w": (nm, r, di), "dt_b": (nm, di), "a_log": (nm, ns, di),
        "d": (nm, di), "out_w": (nm, di, d),
        "q_w": (na, d, hq), "k_w": (na, d, hkv), "v_w": (na, d, hkv),
        "o_w": (na, hq, d),
    }


@functools.lru_cache(maxsize=None)
def _small_maker(name: str, shape: tuple, bound: float, dtype: str):
    """The leaves that are no normal draw: small, made whole."""
    import jax
    import jax.numpy as jnp

    def make(key):
        if name == "a_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32))[None, :, None], shape)
        if name == "dt_b":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    return jax.jit(make)


def make_params(a: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The parameter tree for sizes `a` under `seed`, the matrices in
    `dtype`. Every leaf is non-zero, so that the comparison covers it."""
    import jax
    import jax.numpy as jnp
    key = _key(seed)
    out_std = 0.02 / math.sqrt(2 * a["num_layers"])
    params = {}
    for i, (name, shape) in enumerate(sorted(shapes(a).items())):
        k = jax.random.fold_in(key, i)
        if 0 in shape:                  # a model with no layer of a kind
            params[name] = jnp.zeros(shape, dtype)
            continue
        if name in ("a_log", "dt_b", "conv_w", "conv_b"):
            params[name] = _small_maker(
                name, tuple(shape), 1.0 / math.sqrt(a["mamba_d_conv"]),
                dtype)(k)
            continue
        if "norm" in name or name == "d":
            std, offset = 0.02, 1.0
        elif name in ("out_w", "o_w", "down_w"):
            std, offset = out_std, 0.0
        elif name == "wte":
            std, offset = EMBEDDING_STD, 0.0
        else:
            std, offset = 0.02, 0.0
        params[name] = _leaf_maker(
            tuple(shape), std, offset,
            "float32" if name in F32_LEAVES else dtype)(k)
    return params
