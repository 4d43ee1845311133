"""Weights of the cohere2_moe family from `--seed`, made by the benchmark on
the device in the type the configuration stores them in (bfloat16), one
jitted call a leaf and one slice of a leaf at a time: the float32 draws of
a whole 1.6 GB leaf would not fit beside the rest.

The tree has the leaf names and shapes the program's family takes
(`paddle_tpu.models.cohere2_moe`: per-layer leaves stacked on a leading
layer axis, the held experts on the next) — that is the system's
interface, not its code: nothing here imports the program, so the plain
reference is handed the same weights.
"""
from __future__ import annotations

import functools
import math

from .weights import _key


# The tied embedding is drawn a tenth as wide as the other matrices. At
# 0.02 a token's own logit gets |wte|^2 / sigma(residual) ~ 2.6 sigma of
# the logits over its rivals, one token in fourteen is then a fixed point
# of greedy decoding, and within some fourteen steps every request repeats
# one token for good (measured on the chip, PR 31: 97-99% of the served
# tokens repeated the one before, mean top-2 margin 0.96). No fault moves
# an argmax there, so `correct` would see nothing. At 0.002 the bonus is
# 0.26 sigma and every served token is a near-tie that a fault can flip.
EMBEDDING_STD = 0.002


def shapes(a: dict) -> dict:
    """`a`: the sizes `runners/serve_cohere2_moe.architecture` returns."""
    n, d, f = a["num_layers"], a["hidden_size"], a["ffn_hidden"]
    hq, hkv = a["num_heads"] * a["head_dim"], a["num_kv_heads"] * a["head_dim"]
    s, e = a["num_shared_experts"], a["experts_held"]
    return {
        "wte": (a["vocab_size"], d), "norm_f": (d,), "norm": (n, d),
        "q_w": (n, d, hq), "k_w": (n, d, hkv), "v_w": (n, d, hkv),
        "o_w": (n, hq, d), "router_w": (n, d, a["num_experts"]),
        "shared_gate_w": (n, s, d, f), "shared_up_w": (n, s, d, f),
        "shared_down_w": (n, s, f, d),
        "gate_w": (n, e, d, f), "up_w": (n, e, d, f), "down_w": (n, e, f, d),
    }


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape: tuple, std: float, offset: float, dtype: str):
    import jax
    import jax.numpy as jnp
    tail = shape[-2:]
    count = math.prod(shape[:-2])

    def piece(key):
        draw = jax.random.normal(key, tail, jnp.float32)
        return (offset + std * draw).astype(dtype)

    def make(key):
        if len(shape) <= 2:
            return (offset + std * jax.random.normal(
                key, shape, jnp.float32)).astype(dtype)
        keys = jax.vmap(lambda j: jax.random.fold_in(key, j))(
            jnp.arange(count))
        return jax.lax.map(piece, keys).reshape(shape)

    return jax.jit(make)


def make_params(a: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The parameter tree for sizes `a` under `seed`, in `dtype`. Norm
    scales are drawn near 1 and every matrix non-zero, so that the
    comparison covers them."""
    import jax
    key = _key(seed)
    out_std = 0.02 / math.sqrt(2 * a["num_layers"])
    params = {}
    for i, (name, shape) in enumerate(sorted(shapes(a).items())):
        if name.startswith("norm"):
            std, offset = 0.02, 1.0
        elif name in ("o_w", "down_w", "shared_down_w"):
            std, offset = out_std, 0.0
        elif name == "wte":
            std, offset = EMBEDDING_STD, 0.0
        else:
            std, offset = 0.02, 0.0
        params[name] = _leaf_maker(tuple(shape), std, offset, dtype)(
            jax.random.fold_in(key, i))
    return params
