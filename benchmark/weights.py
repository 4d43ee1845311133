"""Weights from `--seed`, made by the benchmark on the device in ONE jitted
call, in the type the program stores them in (float32 master weights).

The tree has the leaf names and shapes the program's GPT family takes
(`paddle_tpu.models.gpt`: per-block weights stacked on a leading layer
axis) — that is the system's interface, not its code: nothing here imports
the program, so the plain reference can be handed the same weights without
taking anything the program has made. Biases and norm offsets are drawn
non-zero so that the comparison covers them.
"""
from __future__ import annotations

import functools
import math


def model_shapes(m: dict) -> dict:
    d, f, n = m["hidden_size"], m["ffn_hidden"], m["num_layers"]
    v, s = m["vocab_size"], m["max_seq_len"]
    return {
        "wte": (v, d), "wpe": (s, d),
        "ln_f_scale": (d,), "ln_f_bias": (d,),
        "ln1_scale": (n, d), "ln1_bias": (n, d),
        "ln2_scale": (n, d), "ln2_bias": (n, d),
        "qkv_w": (n, d, 3 * d), "qkv_b": (n, 3 * d),
        "attn_out_w": (n, d, d), "attn_out_b": (n, d),
        "mlp_up_w": (n, d, f), "mlp_up_b": (n, f),
        "mlp_down_w": (n, f, d), "mlp_down_b": (n, d),
    }


def _key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


@functools.lru_cache(maxsize=None)
def _maker(items: tuple):
    import jax
    import jax.numpy as jnp
    m = dict(items)
    shapes = model_shapes(m)
    out_std = 0.02 / math.sqrt(2 * m["num_layers"])
    std = {"wte": 0.02, "wpe": 0.01, "qkv_w": 0.02, "mlp_up_w": 0.02,
           "attn_out_w": out_std, "mlp_down_w": out_std}

    def make(key):
        params = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            draw = jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32)
            if name.endswith("_scale"):
                params[name] = 1.0 + 0.02 * draw
            else:
                params[name] = std.get(name, 0.02) * draw
        return params

    return jax.jit(make)


def make_gpt_params(m: dict, seed: int):
    """The float32 parameter tree for model sizes `m` under `seed`."""
    sizes = tuple(sorted((k, m[k]) for k in (
        "hidden_size", "ffn_hidden", "num_layers", "vocab_size",
        "max_seq_len")))
    return _maker(sizes)(_key(seed))
