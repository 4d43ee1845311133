"""Weights of the gigachat3_5 family from `--seed`, made by the benchmark
on the device in the type the configuration stores them in (bfloat16; the
routing bias, A_log and dt_bias float32), one slice of a leaf at a time as
weights_cohere2_moe makes them.

The tree has the leaf names and shapes the program's family takes
(`paddle_tpu.models.gigachat3_5`: the four sandwich norms stacked over all
layers; a linear-attention layer's leaves (`dn_*`) over the linear
layers, a latent-attention layer's (joyai_llm_flash's names and
`attn_gate_w`) over the full-attention layers; a dense MLP's over the
dense layers; an expert layer's over the expert layers with the held
experts on the next axis) — that is the system's interface, not its code:
nothing here imports the program, so the plain reference is handed the
same weights.

The draws (the configuration file's `assumed.random_weights`): matrices
normal std 0.02, the output projections 0.02 / sqrt(2 x layers), the
zero-centred norm scales 0 +- 0.02 (the latent compressions' plain RMSNorm
scales 1 +- 0.02), the untied head std HEAD_STD, the routing bias
ROUTER_BIAS_STD, the latent attention's query up-projection
QUERY_UP_STD; and what decides whether the linear state remembers, as
the GatedDeltaNet paper initialises it: A = exp(A_log) uniform in
[0, 16], dt = softplus(u W_a + dt_bias) with softplus(dt_bias)
log-uniform in [0.001, 0.1] and W_a std DECAY_IN_STD, the convolution
uniform within 1 / sqrt(kernel width).
"""
from __future__ import annotations

import math

from .weights import _key
from .weights_cohere2_moe import _leaf_maker

# The head is drawn so that its logits have a deviation near 0.09 (0.001 x
# sqrt(7168) = 0.085), as weights_joyai_llm_flash.HEAD_STD gives over 2048
# dims: the best two of 16,032 then lie a few hundredths apart, and every
# served token is a near-tie a fault can flip
HEAD_STD = 0.001
# `e_score_correction_bias`, as weights_joyai_llm_flash draws it
ROUTER_BIAS_STD = 0.02
# The latent attention's scores spread with a std near 5 (a trained head
# attends to a few positions): q_b_w at 0.02 gives ~1.2, a softmax nearly
# flat over a thousand keys, which neither its temperature (the YaRN
# factor) nor which positions it reads can move far
QUERY_UP_STD = 0.08
# W_a at 0.02 would give u W_a a std of 1.7 over 7,168 dims, which swamps
# dt_bias: every head's dt near softplus(N(0, 1.7)) and a memory of a few
# positions. At 0.001 dt is dt_bias's draw within ~10%, and the heads'
# memories span one to thousands of positions as the paper intends
DECAY_IN_STD = 0.001
F32_LEAVES = ("router_bias", "dn_a_log", "dn_dt_bias")
OUT = ("o_w", "dn_o_w", "down_w", "shared_down_w", "exp_down_w")
PLAIN_NORMS = ("q_a_norm", "kv_a_norm")


def layer_kinds(a: dict):
    """"full" on the `full_attention_layers` of the cut, "linear" else."""
    return ["full" if i in a["full_attention_layers"] else "linear"
            for i in range(a["num_layers"])]


def shapes(a: dict) -> dict:
    """`a`: the sizes `runners/serve_gigachat3_5.architecture` returns."""
    d, h = a["hidden_size"], a["num_heads"]
    kinds = layer_kinds(a)
    nl, nf = kinds.count("linear"), kinds.count("full")
    dense = a["first_k_dense_replace"]
    ne = a["num_layers"] - dense
    hk, hv = a["linear_num_key_heads"], a["linear_num_value_heads"]
    qk, vd = hk * a["linear_key_head_dim"], hv * a["linear_value_head_dim"]
    c, dn, dr = a["kv_lora_rank"], a["qk_nope_head_dim"], \
        a["qk_rope_head_dim"]
    f, fe = a["ffn_hidden"], a["moe_ffn_hidden"]
    fs = fe * a["n_shared_experts"]
    e, n = a["experts_held"], a["n_routed_experts"]
    return {
        "wte": (a["vocab_size"], d), "head_w": (a["vocab_size"], d),
        "norm_f": (d,),
        **{k: (a["num_layers"], d) for k in (
            "norm_mix_in", "norm_mix_out", "norm_ffn_in", "norm_ffn_out")},
        "dn_q_w": (nl, d, qk), "dn_k_w": (nl, d, qk), "dn_v_w": (nl, d, vd),
        "dn_z_w": (nl, d, vd), "dn_b_w": (nl, d, hv), "dn_a_w": (nl, d, hv),
        "dn_conv_w": (nl, a["linear_conv_kernel_dim"], 2 * qk + vd),
        "dn_a_log": (nl, hv), "dn_dt_bias": (nl, hv),
        "dn_o_norm": (nl, a["linear_value_head_dim"]), "dn_o_w": (nl, vd, d),
        "q_a_w": (nf, d, a["q_lora_rank"]), "q_a_norm": (nf, a["q_lora_rank"]),
        "q_b_w": (nf, a["q_lora_rank"], h * (dn + dr)),
        "kv_a_w": (nf, d, c + dr), "kv_a_norm": (nf, c),
        "k_b_w": (nf, c, h * dn), "v_b_w": (nf, c, h * a["v_head_dim"]),
        "o_w": (nf, h * a["v_head_dim"], d),
        "attn_gate_w": (nf, d, h * a["v_head_dim"]),
        "gate_w": (dense, d, f), "up_w": (dense, d, f),
        "down_w": (dense, f, d),
        "router_w": (ne, d, n), "router_bias": (ne, n),
        "shared_gate_w": (ne, d, fs), "shared_up_w": (ne, d, fs),
        "shared_down_w": (ne, fs, d),
        "exp_gate_w": (ne, e, d, fe), "exp_up_w": (ne, e, d, fe),
        "exp_down_w": (ne, e, fe, d),
    }


def _uniform(shape, lo: float, hi: float, then):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda key: then(jax.random.uniform(
        key, shape, jnp.float32, lo, hi)))


def _special(name: str, shape: tuple, conv_width: int):
    """The makers of the leaves that are no normal draw."""
    import jax.numpy as jnp
    if name == "dn_a_log":
        return _uniform(shape, 1e-4, 16.0, jnp.log)
    if name == "dn_dt_bias":
        def inverse_softplus(u):
            dt = jnp.exp(u)
            return dt + jnp.log(-jnp.expm1(-dt))
        return _uniform(shape, math.log(1e-3), math.log(1e-1),
                        inverse_softplus)
    bound = 1.0 / math.sqrt(conv_width)
    return _uniform(shape, -bound, bound, lambda u: u)


def make_params(a: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The parameter tree for sizes `a` under `seed`, the matrices in
    `dtype`. Every leaf is non-zero, so that the comparison covers it."""
    import jax
    import jax.numpy as jnp
    key = _key(seed)
    out_std = 0.02 / math.sqrt(2 * a["num_layers"])
    params = {}
    for i, (name, shape) in enumerate(sorted(shapes(a).items())):
        kind = "float32" if name in F32_LEAVES else dtype
        k = jax.random.fold_in(key, i)
        if 0 in shape:                  # a model with no layer of a kind
            params[name] = jnp.zeros(shape, kind)
            continue
        if name in ("dn_a_log", "dn_dt_bias", "dn_conv_w"):
            params[name] = _special(name, tuple(shape),
                                    a["linear_conv_kernel_dim"])(k).astype(
                                        kind)
            continue
        if name in PLAIN_NORMS:
            std, offset = 0.02, 1.0
        elif "norm" in name:
            std, offset = 0.02, 0.0
        elif name in OUT:
            std, offset = out_std, 0.0
        elif name == "head_w":
            std, offset = HEAD_STD, 0.0
        elif name == "router_bias":
            std, offset = ROUTER_BIAS_STD, 0.0
        elif name == "q_b_w":
            std, offset = QUERY_UP_STD, 0.0
        elif name == "dn_a_w":
            std, offset = DECAY_IN_STD, 0.0
        else:
            std, offset = 0.02, 0.0
        params[name] = _leaf_maker(tuple(shape), std, offset, kind)(k)
    return params
