"""Operations and bytes the ALGORITHM needs for the gigachat3_5 family,
from a configuration's shapes and the benchmark's own record of lengths —
only what THIS chip's share computes, never what the program dispatches or
counts. What `serve_step_mfu`, `serve_hbm_share`,
`ssm_state_hbm_share.serve` and `latent_cache_hbm_share.serve` divide by
the chip's published peak in the gigachat3.5 cells.

`a` is the dict `runners/serve_gigachat3_5.architecture` returns. A token
meets 2 x every matmul parameter on its way; of the routed experts
`num_experts_per_tok x experts_held / n_routed_experts` in expectation
(8 x 16 / 256 = half an expert at the published cut). A linear-attention
layer's delta rule is counted in its one-step form, 6 x dk x dv a value
head a token (S^T k, the rank-1 write and S^T q), its convolution
2 x kernel width a channel; the chunked form a prompt runs does more and
counts no more. Latent attention is counted as weights_joyai_llm_flash's
cells count it, in the cheaper form for each shape. A tick's bytes are the
held weights once (the routed experts by the fraction some token is
expected to choose), the live latent positions x (512 + 64) x 2 B of each
latent layer, and each decoded row's delta-rule state and convolution rows,
read and written. Bucket padding, idle slots, dead positions and
recomputation count for nothing, so a share cannot pass 100%.
"""
from __future__ import annotations


def kinds(a: dict):
    """(linear layers, latent layers, dense layers, expert layers)."""
    full = sum(1 for i in range(a["num_layers"])
               if i in a["full_attention_layers"])
    dense = a["first_k_dense_replace"]
    return a["num_layers"] - full, full, dense, a["num_layers"] - dense


def linear_params(a: dict) -> int:
    """A linear layer's matmuls: q, k, v, z, beta, g and out."""
    d, hv = a["hidden_size"], a["linear_num_value_heads"]
    qk = a["linear_num_key_heads"] * a["linear_key_head_dim"]
    vd = hv * a["linear_value_head_dim"]
    return d * (2 * qk + 2 * vd + 2 * hv) + vd * d


def conv_channels(a: dict) -> int:
    return 2 * a["linear_num_key_heads"] * a["linear_key_head_dim"] \
        + a["linear_num_value_heads"] * a["linear_value_head_dim"]


def latent_params(a: dict) -> int:
    """A latent layer's matmuls outside the decompression: q_a, q_b, kv_a,
    the output gate and o."""
    d, h = a["hidden_size"], a["num_heads"]
    return d * a["q_lora_rank"] \
        + a["q_lora_rank"] * h * (a["qk_nope_head_dim"]
                                  + a["qk_rope_head_dim"]) \
        + d * (a["kv_lora_rank"] + a["qk_rope_head_dim"]) \
        + 2 * h * a["v_head_dim"] * d


def kv_b_params(a: dict) -> int:
    return a["kv_lora_rank"] * a["num_heads"] * (a["qk_nope_head_dim"]
                                                 + a["v_head_dim"])


def expert_params(a: dict) -> int:
    return 3 * a["hidden_size"] * a["moe_ffn_hidden"]


def dense_mlp_params(a: dict) -> int:
    return 3 * a["hidden_size"] * a["ffn_hidden"]


def expert_layer_fixed_params(a: dict) -> int:
    """An expert layer's FFN matmuls every token uses: the router and the
    shared expert."""
    return a["hidden_size"] * a["n_routed_experts"] \
        + a["n_shared_experts"] * expert_params(a)


def routed_experts_per_token(a: dict) -> float:
    return a["num_experts_per_tok"] * a["experts_held"] \
        / a["n_routed_experts"]


def head_params(a: dict) -> int:
    return a["vocab_size"] * a["hidden_size"]


def n_params(a: dict) -> int:
    """Every parameter this chip stores (embedding and head untied; the
    multi-token-prediction modules are not loaded)."""
    d = a["hidden_size"]
    linear, full, dense, experts = kinds(a)
    hv = a["linear_num_value_heads"]
    per_linear = linear_params(a) + a["linear_conv_kernel_dim"] \
        * conv_channels(a) + 2 * hv + a["linear_value_head_dim"]
    per_full = latent_params(a) + kv_b_params(a) + a["q_lora_rank"] \
        + a["kv_lora_rank"]
    expert_layer = expert_layer_fixed_params(a) + a["n_routed_experts"] \
        + a["experts_held"] * expert_params(a)
    return linear * per_linear + full * per_full \
        + a["num_layers"] * 4 * d + dense * dense_mlp_params(a) \
        + experts * expert_layer + 2 * head_params(a) + d


def delta_rule_flops(a: dict) -> float:
    """One token of one linear layer: the convolution and the delta rule
    in its one-step form."""
    return 2.0 * a["linear_conv_kernel_dim"] * conv_channels(a) \
        + 6.0 * a["linear_num_value_heads"] * a["linear_key_head_dim"] \
        * a["linear_value_head_dim"]


def token_flops(a: dict) -> float:
    """One token through every layer outside the latent attention's
    positions: the body matmuls (the decompression or the absorptions: the
    same count), the convolutions and the delta rule."""
    linear, full, dense, experts = kinds(a)
    matmuls = linear * linear_params(a) \
        + full * (latent_params(a) + kv_b_params(a)) \
        + dense * dense_mlp_params(a) + experts * (
            expert_layer_fixed_params(a)
            + routed_experts_per_token(a) * expert_params(a))
    return 2.0 * matmuls + linear * delta_rule_flops(a)


def prefill_flops(a: dict, prompt_len: int) -> float:
    """Forward of a whole prompt: every position through the body, causal
    latent attention in the decompressed form, the head on the last
    position only."""
    t = prompt_len
    _, full, _, _ = kinds(a)
    pair = 2.0 * a["num_heads"] * (a["qk_nope_head_dim"]
                                   + a["qk_rope_head_dim"] + a["v_head_dim"])
    return token_flops(a) * t + full * pair * t * (t + 1) / 2.0 \
        + 2.0 * head_params(a)


def decode_flops(a: dict, context: int) -> float:
    """Forward of ONE token whose latent attention spans `context`
    positions (itself included), in the absorbed form, head included."""
    _, full, _, _ = kinds(a)
    position = 2.0 * a["num_heads"] * (2 * a["kv_lora_rank"]
                                       + a["qk_rope_head_dim"])
    return token_flops(a) + 2.0 * head_params(a) + full * position * context


def latent_bytes_per_position(a: dict, bytes_per_value: int = 2) -> int:
    """What ONE cached position holds across the latent layers."""
    _, full, _, _ = kinds(a)
    return full * (a["kv_lora_rank"] + a["qk_rope_head_dim"]) \
        * bytes_per_value


def slot_state_bytes(a: dict, bytes_per_value: int = 2) -> int:
    """Delta-rule state (float32) and convolution rows ONE slot holds,
    every linear layer, whatever its context."""
    linear, _, _, _ = kinds(a)
    return linear * (
        a["linear_num_value_heads"] * a["linear_key_head_dim"]
        * a["linear_value_head_dim"] * 4
        + (a["linear_conv_kernel_dim"] - 1) * conv_channels(a)
        * bytes_per_value)


def tick_state_bytes(a: dict, rows: int) -> int:
    """The state a tick of `rows` decoding rows reads and writes."""
    return 2 * rows * slot_state_bytes(a)


def tick_weight_bytes(a: dict, tokens: int, bytes_per_value: int = 2):
    """The weights a step of `tokens` tokens reads once: every layer's
    mixer, the dense MLPs, each expert layer's router and shared expert,
    of its held experts the fraction some token is expected to choose,
    1 - (1 - k / E) ** tokens, and the head (the embedding is a gather of
    `tokens` rows)."""
    linear, full, dense, experts = kinds(a)
    touched = 1.0 - (1.0 - a["num_experts_per_tok"]
                     / a["n_routed_experts"]) ** tokens
    body = linear * linear_params(a) \
        + full * (latent_params(a) + kv_b_params(a)) \
        + dense * dense_mlp_params(a) + experts * (
            expert_layer_fixed_params(a)
            + touched * a["experts_held"] * expert_params(a))
    return (body + head_params(a)) * bytes_per_value
