"""Operations and bytes the ALGORITHM needs for the joyai_llm_flash family,
from a configuration's shapes and the benchmark's own record of lengths —
only what THIS chip's share computes, never what the program dispatches or
counts. What `serve_step_mfu`, `serve_hbm_share` and
`latent_cache_hbm_share.serve` divide by the chip's published peak in the
joyai-llm-flash cells.

`a` is the dict `runners/serve_joyai_llm_flash.architecture` returns. A
token meets 2 x every matmul parameter on its way; of the routed experts
`num_experts_per_tok x experts_held / n_routed_experts` in expectation
(8 x 16 / 256 = half an expert at the published cut). Attention is counted
in the CHEAPER of the two forms for each shape, whatever the program runs:
a prompt's in the decompressed form, 2 x heads x (192 + 128) a causal pair
(plus the decompression of its own positions, a matmul of 2 x 512 x heads
x (128 + 128) a position); a tick's in the absorbed form, 2 x heads x
(576 + 512) a live position (plus the two absorptions, 2 x heads x 512 x
(128 + 128) a token) — decompressing a context of thousands of positions
for one query would cost more. A tick's bytes are the held weights once
(the routed experts by the fraction some token is expected to choose) and
the live latent positions x (512 + 64) x 2 B a layer. Bucket padding, idle
slots, dead positions and recomputation count for nothing, so a share
cannot pass 100%.
"""
from __future__ import annotations


def attention_params(a: dict) -> int:
    """A layer's attention matmuls outside the decompression: q_a, q_b,
    kv_a, o."""
    d, h = a["hidden_size"], a["num_heads"]
    return d * a["q_lora_rank"] \
        + a["q_lora_rank"] * h * (a["qk_nope_head_dim"]
                                  + a["qk_rope_head_dim"]) \
        + d * (a["kv_lora_rank"] + a["qk_rope_head_dim"]) \
        + h * a["v_head_dim"] * d


def kv_b_params(a: dict) -> int:
    """kv_b_proj: the latent into every head's key and value."""
    return a["kv_lora_rank"] * a["num_heads"] * (a["qk_nope_head_dim"]
                                                 + a["v_head_dim"])


def expert_params(a: dict) -> int:
    """One routed expert: gate, up and down."""
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


def _layers(a: dict):
    dense = a["first_k_dense_replace"]
    return dense, a["num_layers"] - dense


def n_params(a: dict) -> int:
    """Every parameter this chip stores (embedding and head untied; the
    multi-token-prediction module is not loaded)."""
    d = a["hidden_size"]
    dense, experts = _layers(a)
    attention = attention_params(a) + kv_b_params(a) + a["q_lora_rank"] \
        + a["kv_lora_rank"] + 2 * d
    expert_layer = expert_layer_fixed_params(a) + a["n_routed_experts"] \
        + a["experts_held"] * expert_params(a)
    return a["num_layers"] * attention + dense * dense_mlp_params(a) \
        + experts * expert_layer + 2 * head_params(a) + d


def token_matmul_flops(a: dict) -> float:
    """The body matmuls of one token through every layer, the
    decompression (or the absorptions: the same count) included."""
    dense, experts = _layers(a)
    per_token = a["num_layers"] * (attention_params(a) + kv_b_params(a)) \
        + dense * dense_mlp_params(a) + experts * (
            expert_layer_fixed_params(a)
            + routed_experts_per_token(a) * expert_params(a))
    return 2.0 * per_token


def prefill_flops(a: dict, prompt_len: int) -> float:
    """Forward of a whole prompt: body matmuls on every position, causal
    attention in the decompressed form, the head on the last position
    only."""
    t = prompt_len
    pair = 2.0 * a["num_heads"] * (a["qk_nope_head_dim"]
                                   + a["qk_rope_head_dim"] + a["v_head_dim"])
    return token_matmul_flops(a) * t \
        + a["num_layers"] * pair * t * (t + 1) / 2.0 + 2.0 * head_params(a)


def decode_flops(a: dict, context: int) -> float:
    """Forward of ONE token whose attention spans `context` positions
    (itself included), in the absorbed form, head included."""
    position = 2.0 * a["num_heads"] * (2 * a["kv_lora_rank"]
                                       + a["qk_rope_head_dim"])
    return token_matmul_flops(a) + 2.0 * head_params(a) \
        + a["num_layers"] * position * context


def latent_bytes_per_position(a: dict, bytes_per_value: int = 2) -> int:
    """What ONE cached position holds across all layers."""
    return a["num_layers"] * (a["kv_lora_rank"] + a["qk_rope_head_dim"]) \
        * bytes_per_value


def tick_weight_bytes(a: dict, tokens: int, bytes_per_value: int = 2):
    """The weights a step of `tokens` tokens reads once: every layer's
    attention, the dense MLPs, each expert layer's router and shared
    expert, of its held experts the fraction some token is expected to
    choose, 1 - (1 - k / E) ** tokens, and the head (the embedding is a
    gather of `tokens` rows)."""
    dense, experts = _layers(a)
    touched = 1.0 - (1.0 - a["num_experts_per_tok"]
                     / a["n_routed_experts"]) ** tokens
    body = a["num_layers"] * (attention_params(a) + kv_b_params(a)) \
        + dense * dense_mlp_params(a) + experts * (
            expert_layer_fixed_params(a)
            + touched * a["experts_held"] * expert_params(a))
    return (body + head_params(a)) * bytes_per_value
