"""Train the GPT flagship under hybrid parallelism (dp × mp) on a device
mesh — the fleet API end-to-end.

Run on any host (8 virtual CPU devices by default):
    python examples/train_gpt_hybrid.py
On a TPU pod slice the same code uses the real chips; scale the degrees
in `hybrid_configs` to the topology.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# examples demo on CPU devices by default, at toy sizes;
# run with PADDLE_TPU_EXAMPLE_BACKEND=native for real chips
if os.environ.get("PADDLE_TPU_EXAMPLE_BACKEND", "cpu") == "cpu":
    from paddle_tpu.device import pin_cpu
    assert pin_cpu(8), "could not pin the CPU backend"

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.parallel.mesh import build_mesh, use_mesh, shard_value
from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                   init_opt_state, train_step,
                                   shard_gpt_params)


def main():
    # 1) topology: dp=2 × mp=4 over 8 devices (pp/sp/ep available too)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    mesh = hcg.mesh
    print("mesh:", dict(mesh.shape))

    # 2) the functional core: stacked params, declarative shardings
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                    num_heads=8, max_seq_len=128, dtype=jnp.bfloat16,
                    remat=False, sequence_parallel=True)
    with use_mesh(mesh):
        params = shard_gpt_params(init_gpt_params(
            cfg, jax.random.PRNGKey(0)), mesh)
        opt_state = init_opt_state(params)
        from paddle_tpu.models.facade import make_train_step
        step = make_train_step(train_step, cfg=cfg, lr=1e-3)
        rng = np.random.RandomState(0)
        for it in range(5):
            tokens = jnp.asarray(rng.randint(
                0, cfg.vocab_size, (8, cfg.max_seq_len + 1)))
            loss, params, opt_state = step(params, opt_state, tokens)
            print(f"step {it}: loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
