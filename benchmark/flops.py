"""Operations and bytes the ALGORITHM needs, from a configuration's shapes
alone. The benchmark's own copy (the program's cost_model may move): what a
`*_mfu` or `*_hbm_share` metric divides by the chip's published peak.
Recomputation (remat) is never counted, so a share cannot pass 100%."""
from __future__ import annotations


def body_matmul_params(m: dict) -> int:
    """Parameters of the per-layer matmuls (qkv, attention out, MLP up and
    down), all layers; no embeddings, biases or norms."""
    d, f, n = m["hidden_size"], m["ffn_hidden"], m["num_layers"]
    return n * (3 * d * d + d * d + 2 * d * f)


def head_params(m: dict) -> int:
    """The tied LM head: one [V, D] matmul per position that needs logits."""
    return m["vocab_size"] * m["hidden_size"]


def n_params(m: dict) -> int:
    """Every parameter of the model as the program stores it."""
    d, f, n = m["hidden_size"], m["ffn_hidden"], m["num_layers"]
    v, s = m["vocab_size"], m["max_seq_len"]
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * f + f) \
        + (f * d + d) + 4 * d
    return v * d + s * d + 2 * d + n * per_layer


def train_flops_per_token(m: dict, seq: int) -> float:
    """6N + 12*L*d*S (cost_model.train_flops_per_token's arithmetic):
    forward and backward over every parameter, plus causal-unaware
    attention scores and values. No recomputation."""
    return 6.0 * n_params(m) + 12.0 * m["num_layers"] * m["hidden_size"] * seq


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Forward of a whole prompt: body matmuls on every position, causal
    attention (position c attends c keys), the LM head on the last
    position only (the one a token is sampled from)."""
    t = prompt_len
    attn = 4.0 * m["num_layers"] * m["hidden_size"] * t * (t + 1) / 2.0
    return 2.0 * body_matmul_params(m) * t + attn + 2.0 * head_params(m)


def decode_flops(m: dict, context: int) -> float:
    """Forward of ONE token whose attention spans `context` positions
    (itself included), LM head included."""
    attn = 4.0 * m["num_layers"] * m["hidden_size"] * context
    return 2.0 * (body_matmul_params(m) + head_params(m)) + attn


def kv_bytes_per_position(m: dict, bytes_per_value: int = 2) -> int:
    """K and V of one position across all layers at the compute width."""
    return 2 * m["num_layers"] * m["hidden_size"] * bytes_per_value


def tick_weight_bytes(m: dict, bytes_per_value: int = 2) -> int:
    """The matmul weights (body + head) read once, at the compute width."""
    return (body_matmul_params(m) + head_params(m)) * bytes_per_value
