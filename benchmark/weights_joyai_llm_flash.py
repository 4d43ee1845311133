"""Weights of the joyai_llm_flash family from `--seed`, made by the benchmark
on the device in the type the configuration stores them in (bfloat16; the
routing bias float32), one slice of a leaf at a time as weights_cohere2_moe
makes them.

The tree has the leaf names and shapes the program's family takes
(`paddle_tpu.models.joyai_llm_flash`: attention leaves stacked over all
layers, a dense MLP's over the dense layers, an expert layer's over the
expert layers with the held experts on the next axis; the published
kv_b_proj as its two halves `k_b_w` / `v_b_w`; the multi-token-prediction
module as the `mtp_*` leaves) — that is the system's interface, not its
code: nothing here imports the program, so the plain reference is handed
the same weights.

The draws (the configuration file's `assumed.random_weights`): matrices
normal std 0.02, the output projections 0.02 / sqrt(2 x layers), norm
scales 1 +- 0.02, the untied head std HEAD_STD, the routing bias
ROUTER_BIAS_STD.
"""
from __future__ import annotations

import math

from .weights import _key
from .weights_cohere2_moe import _leaf_maker

# The head is drawn a tenth as wide as the other matrices: its logits then
# have a deviation of 0.002 x sqrt(2048) = 0.09 and the best two of 129,280
# lie a few hundredths apart, so that every served token is a near-tie a
# fault can flip (weights_cohere2_moe.EMBEDDING_STD: at 0.02 `correct` saw
# nothing). The embedding only ever meets an RMSNorm, which takes its
# scale out.
HEAD_STD = 0.002
# `e_score_correction_bias`: zero would hide the mechanism. The sigmoid
# scores of a 0.02-std router over a unit-RMS input have a deviation near
# 0.2, but the 8th and 9th largest of 256 lie about 0.01 apart: at this
# spread the bias changes the chosen 8 on 86% of tokens and replaces 1.3
# of them on average (seeded draws at the published widths, PR 37)
ROUTER_BIAS_STD = 0.02
F32_LEAVES = ("router_bias",)
OUT = ("o_w", "down_w", "shared_down_w", "exp_down_w")


def _attention_shapes(a: dict, n: int) -> dict:
    d, h = a["hidden_size"], a["num_heads"]
    c, dn, dr = a["kv_lora_rank"], a["qk_nope_head_dim"], \
        a["qk_rope_head_dim"]
    return {
        "norm_attn": (n, d), "norm_ffn": (n, d),
        "q_a_w": (n, d, a["q_lora_rank"]), "q_a_norm": (n, a["q_lora_rank"]),
        "q_b_w": (n, a["q_lora_rank"], h * (dn + dr)),
        "kv_a_w": (n, d, c + dr), "kv_a_norm": (n, c),
        "k_b_w": (n, c, h * dn), "v_b_w": (n, c, h * a["v_head_dim"]),
        "o_w": (n, h * a["v_head_dim"], d),
    }


def _expert_shapes(a: dict, n: int) -> dict:
    d, f, e = a["hidden_size"], a["moe_ffn_hidden"], a["experts_held"]
    fs = f * a["n_shared_experts"]
    return {
        "router_w": (n, d, a["n_routed_experts"]),
        "router_bias": (n, a["n_routed_experts"]),
        "shared_gate_w": (n, d, fs), "shared_up_w": (n, d, fs),
        "shared_down_w": (n, fs, d),
        "exp_gate_w": (n, e, d, f), "exp_up_w": (n, e, d, f),
        "exp_down_w": (n, e, f, d),
    }


def shapes(a: dict, mtp: bool = False) -> dict:
    """`a`: the sizes `runners/serve_joyai_llm_flash.architecture` returns.
    `mtp` adds the multi-token-prediction module's leaves, which the
    serving cell does not load."""
    d, f = a["hidden_size"], a["ffn_hidden"]
    dense = a["first_k_dense_replace"]
    out = {
        "wte": (a["vocab_size"], d), "head_w": (a["vocab_size"], d),
        "norm_f": (d,), **_attention_shapes(a, a["num_layers"]),
        "gate_w": (dense, d, f), "up_w": (dense, d, f),
        "down_w": (dense, f, d),
        **_expert_shapes(a, a["num_layers"] - dense),
    }
    nm = a["num_nextn_predict_layers"] if mtp else 0
    if nm:
        module = {"norm_e": (nm, d), "norm_h": (nm, d),
                  "eh_w": (nm, 2 * d, d), "norm_f": (nm, d),
                  **_attention_shapes(a, nm), **_expert_shapes(a, nm)}
        out.update({"mtp_" + k: v for k, v in module.items()})
    return out


def make_params(a: dict, seed: int, dtype: str = "bfloat16",
                mtp: bool = False) -> dict:
    """The parameter tree for sizes `a` under `seed`, the matrices in
    `dtype`. Norm scales are drawn near 1 and every other leaf non-zero, so
    that the comparison covers them."""
    import jax
    import jax.numpy as jnp
    key = _key(seed)
    out_std = 0.02 / math.sqrt(2 * a["num_layers"])
    params = {}
    for i, (name, shape) in enumerate(sorted(shapes(a, mtp).items())):
        own = name.removeprefix("mtp_")
        kind = "float32" if own in F32_LEAVES else dtype
        if 0 in shape:                  # a model with no layer of a kind
            params[name] = jnp.zeros(shape, kind)
            continue
        if "norm" in own:
            std, offset = 0.02, 1.0
        elif own in OUT:
            std, offset = out_std, 0.0
        elif own == "head_w":
            std, offset = HEAD_STD, 0.0
        elif own == "router_bias":
            std, offset = ROUTER_BIAS_STD, 0.0
        else:
            std, offset = 0.02, 0.0
        params[name] = _leaf_maker(tuple(shape), std, offset, kind)(
            jax.random.fold_in(key, i))
    return params
