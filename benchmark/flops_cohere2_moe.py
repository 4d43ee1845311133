"""Operations and bytes the ALGORITHM needs for the cohere2_moe family, from
a configuration's shapes and the benchmark's own record of lengths — only
what THIS chip's share computes, never what the program dispatches or
counts. What `serve_step_mfu` and `serve_hbm_share` divide by the chip's
published peak in the command-a-plus cells (flops.py is the GPT family's).

`a` is the dict `runners/serve_cohere2_moe.architecture` returns. A token
meets `experts_per_token x experts_held / num_experts` held routed experts
in expectation (8 x 16 / 128 = 1 at the published cut); a window layer's
attention spans min(context, sliding_window) positions; bucket padding,
rows of the row budget that hold no pair, and recomputation count for
nothing, so a share cannot pass 100%.
"""
from __future__ import annotations

SLIDING = "sliding_attention"


def expert_params(a: dict) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * a["hidden_size"] * a["ffn_hidden"]


def dense_layer_params(a: dict) -> int:
    """A layer's matmul weights every token uses: q, k, v, o, the router
    and the shared experts."""
    d = a["hidden_size"]
    hq = a["num_heads"] * a["head_dim"]
    hkv = a["num_kv_heads"] * a["head_dim"]
    return 2 * d * hq + 2 * d * hkv + d * a["num_experts"] \
        + a["num_shared_experts"] * expert_params(a)


def routed_experts_per_token(a: dict) -> float:
    return a["experts_per_token"] * a["experts_held"] / a["num_experts"]


def head_params(a: dict) -> int:
    return a["vocab_size"] * a["hidden_size"]


def n_params(a: dict) -> int:
    """Every parameter this chip stores."""
    d = a["hidden_size"]
    per_layer = dense_layer_params(a) + d \
        + a["experts_held"] * expert_params(a)
    return a["num_layers"] * per_layer + head_params(a) + d


def _layers(a: dict):
    window = sum(1 for t in a["layer_types"] if t == SLIDING)
    return window, len(a["layer_types"]) - window


def token_matmul_flops(a: dict) -> float:
    """The body matmuls of one token through every layer."""
    per_layer = dense_layer_params(a) \
        + routed_experts_per_token(a) * expert_params(a)
    return 2.0 * a["num_layers"] * per_layer


def attended(a: dict, context: int):
    """(positions a window layer's query at the end of `context` positions
    attends, positions a full layer's does), itself included."""
    return min(context, a["sliding_window"]), context


def prefill_flops(a: dict, prompt_len: int) -> float:
    """Forward of a whole prompt: body matmuls on every position, causal
    attention under each layer kind's mask, the head on the last
    position only."""
    t, w = prompt_len, a["sliding_window"]
    n_window, n_full = _layers(a)
    full_pairs = t * (t + 1) / 2.0
    head = min(t, w)
    window_pairs = head * (head + 1) / 2.0 + (t - head) * w
    attn = 4.0 * a["num_heads"] * a["head_dim"] * (
        n_full * full_pairs + n_window * window_pairs)
    return token_matmul_flops(a) * t + attn + 2.0 * head_params(a)


def decode_flops(a: dict, context: int) -> float:
    """Forward of ONE token whose attention spans `context` positions
    (itself included), head included."""
    n_window, n_full = _layers(a)
    in_window, in_full = attended(a, context)
    attn = 4.0 * a["num_heads"] * a["head_dim"] * (
        n_window * in_window + n_full * in_full)
    return token_matmul_flops(a) + 2.0 * head_params(a) + attn


def kv_bytes(a: dict, context: int, bytes_per_value: int = 2) -> float:
    """K and V one decoded token reads across all layers."""
    n_window, n_full = _layers(a)
    in_window, in_full = attended(a, context)
    return 2.0 * a["num_kv_heads"] * a["head_dim"] * bytes_per_value * (
        n_window * in_window + n_full * in_full)


def tick_weight_bytes(a: dict, tokens: int, bytes_per_value: int = 2):
    """The weights a tick of `tokens` tokens reads once: every layer's
    dense part, the head, and of each layer's held experts the fraction
    some token is expected to choose, 1 - (1 - k / E) ** tokens."""
    touched = 1.0 - (1.0 - a["experts_per_token"] / a["num_experts"]) \
        ** tokens
    per_layer = dense_layer_params(a) \
        + touched * a["experts_held"] * expert_params(a)
    return (a["num_layers"] * per_layer + head_params(a)) * bytes_per_value
