"""What the runners need of the program's GPT family, in one place: the
program's config object from a configuration file's sizes. Every field the
file does not state stays at the program's default."""
from __future__ import annotations


def program_config(config: dict):
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig
    if config.get("family") != "gpt":
        raise ValueError(f"runner knows the gpt family, not "
                         f"{config.get('family')!r}")
    m = config["model"]
    return GPTConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_layers"], num_heads=m["num_heads"],
        ffn_hidden=m["ffn_hidden"], max_seq_len=m["max_seq_len"],
        layer_norm_eps=m["layer_norm_eps"],
        dtype=jnp.dtype(config["precision"]["compute"]).type)
