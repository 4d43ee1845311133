"""Continuous-batching serving: mixed-length requests stream through a
fixed slot pool, joining and leaving mid-decode (inference/serving.py
— slot-pool KV cache, bucketed prefill, one jitted decode step).

    python examples/serving_engine.py
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

from paddle_tpu.inference import ServingEngine
from paddle_tpu.models.gpt import GPTConfig, init_gpt_params


def main():
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=4,
                    num_heads=8, max_seq_len=128, dtype=jnp.float32,
                    sequence_parallel=False, remat=False)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, family="gpt", num_slots=4,
                        max_len=128, max_top_k=16)

    rng = np.random.RandomState(0)
    # 8 requests, mixed prompt lengths and budgets, one sampled
    reqs = [eng.submit(rng.randint(0, 256, L).astype(np.int32),
                       max_new_tokens=g)
            for L, g in ((5, 12), (23, 8), (9, 16), (40, 6),
                         (3, 10), (17, 9), (11, 7), (6, 14))]
    reqs.append(eng.submit(rng.randint(0, 256, 8).astype(np.int32),
                           max_new_tokens=10, temperature=0.8,
                           top_k=16))

    tick = 0
    while eng.has_work():
        emitted = eng.step()
        tick += 1
        print(f"tick {tick:2d}: "
              + "  ".join(f"r{r.id}->{tok}" for r, tok in emitted))
    for r in reqs:
        print(f"req {r.id}: prompt_len={len(r.prompt)} "
              f"finish={r.finish_reason} tokens={r.tokens}")
    print("traces (decode, prefill):", eng.trace_counts())


if __name__ == "__main__":
    main()
