"""Greedy text generation through the KV cache (prefill + single-token
decode steps under one jit) — the inference decoder path.

    python examples/generate_with_kv_cache.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# examples demo on CPU devices by default, at toy sizes;
# run with PADDLE_TPU_EXAMPLE_BACKEND=native for real chips
if os.environ.get("PADDLE_TPU_EXAMPLE_BACKEND", "cpu") == "cpu":
    from paddle_tpu.device import pin_cpu
    assert pin_cpu(1), "could not pin the CPU backend"

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                   greedy_generate)


def main():
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=4,
                    num_heads=8, max_seq_len=64, dtype=jnp.float32,
                    sequence_parallel=False, remat=False)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (2, 8)), jnp.int32)
    out = greedy_generate(params, prompt, cfg, max_new_tokens=16)
    print("prompt :", np.asarray(prompt))
    print("decoded:", np.asarray(out[:, 8:]))


if __name__ == "__main__":
    main()
