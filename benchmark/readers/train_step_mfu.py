"""The whole training step's share of the chip's peak: tokens/s over the
whole window x (6N + 12 L d S) / (chips x peak bf16 FLOP/s). The
benchmark's own arithmetic and peaks; recomputation is not counted."""
from .. import flops


def read(record):
    if not record.get("peaks") or not record.get("tokens"):
        return None
    per_token = flops.train_flops_per_token(record["model"],
                                            record["seq_len"])
    achieved = record["tokens"] / record["window_s"] * per_token
    return 100.0 * achieved / (record["chips"] * record["peaks"]["flops_bf16"])
