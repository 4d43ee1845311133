"""Operations and bytes the ALGORITHM needs for the jamba family, from a
configuration's shapes and the benchmark's own record of lengths and live
slots — never what the program dispatches or counts. What
`serve_step_mfu`, `serve_hbm_share` and `ssm_state_hbm_share.serve`
divide by the chip's published peak in the jamba2 cells (flops.py is the
GPT family's).

`a` is the dict `runners/serve_jamba.architecture` returns. A token costs
two operations a matmul parameter, the selective scan 9 a channel and
state (exp, two products and a sum into the state; a product and a sum
out of it; delta x, and the skip term's pair shared over the states,
counted as one more) and the convolution 2 a tap a channel in each Mamba
layer, and attention 4 a head element a position it attends. A tick
reads every weight once, the live requests' keys and values, and reads
AND writes each live slot's recurrent state and convolution rows. Bucket
padding, idle slots and the dead positions the masked einsum reads count
for nothing, so a share cannot pass 100%.
"""
from __future__ import annotations

from .weights_jamba import F32_LEAVES, layers_of, shapes

SCAN_OPS = 9            # per channel and state, a position


def d_inner(a: dict) -> int:
    return a["mamba_expand"] * a["hidden_size"]


def mamba_mixer_params(a: dict) -> int:
    """One Mamba mixer's matmul weights: in, x, dt and out projections."""
    d, di = a["hidden_size"], d_inner(a)
    r, n = a["mamba_dt_rank"], a["mamba_d_state"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def attention_mixer_params(a: dict) -> int:
    hq = a["num_heads"] * a["head_dim"]
    hkv = a["num_kv_heads"] * a["head_dim"]
    return 2 * a["hidden_size"] * (hq + hkv)


def mlp_params(a: dict) -> int:
    return 3 * a["hidden_size"] * a["ffn_hidden"]


def head_params(a: dict) -> int:
    return a["vocab_size"] * a["hidden_size"]


def n_params(a: dict) -> int:
    """Every parameter the chip stores."""
    total = 0
    for shape in shapes(a).values():
        size = 1
        for s in shape:
            size *= s
        total += size
    return total


def weight_bytes(a: dict) -> int:
    """Every parameter once, as stored: float32 for `a_log`, `d`, `dt_b`
    and the norm scales, two bytes for the rest."""
    total = 0
    for name, shape in shapes(a).items():
        size = 1
        for s in shape:
            size *= s
        total += size * (4 if name in F32_LEAVES else 2)
    return total


def token_flops(a: dict) -> float:
    """One token through every layer, attention's scores and the head
    apart: the matmuls, the convolution and the scan."""
    nm, na = layers_of(a, "mamba"), layers_of(a, "attention")
    di = d_inner(a)
    mamba = 2.0 * mamba_mixer_params(a) + 2.0 * a["mamba_d_conv"] * di \
        + SCAN_OPS * di * a["mamba_d_state"]
    return nm * mamba + na * 2.0 * attention_mixer_params(a) \
        + a["num_layers"] * 2.0 * mlp_params(a)


def _attention_flops(a: dict, pairs: float) -> float:
    return 4.0 * a["num_heads"] * a["head_dim"] \
        * layers_of(a, "attention") * pairs


def prefill_flops(a: dict, prompt_len: int) -> float:
    """Forward of a whole prompt: every position through every layer,
    causal attention, the head on the last position only."""
    t = prompt_len
    return token_flops(a) * t + _attention_flops(a, t * (t + 1) / 2.0) \
        + 2.0 * head_params(a)


def decode_flops(a: dict, context: int) -> float:
    """Forward of ONE token whose attention spans `context` positions
    (itself included), head included."""
    return token_flops(a) + 2.0 * head_params(a) \
        + _attention_flops(a, context)


def kv_bytes_per_position(a: dict, bytes_per_value: int = 2) -> int:
    """K and V of one position across the attention layers."""
    return 2 * layers_of(a, "attention") * a["num_kv_heads"] \
        * a["head_dim"] * bytes_per_value


def slot_state_bytes(a: dict, conv_bytes: int = 2) -> int:
    """What ONE slot's Mamba layers hold whatever its context: the
    recurrent state in float32 and the convolution's d_conv - 1 rows."""
    di = d_inner(a)
    return layers_of(a, "mamba") * (
        a["mamba_d_state"] * di * 4
        + (a["mamba_d_conv"] - 1) * di * conv_bytes)


def tick_state_bytes(a: dict, live_slots: int) -> int:
    """A tick reads and writes each live slot's state."""
    return 2 * live_slots * slot_state_bytes(a)
