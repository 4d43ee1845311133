"""The BASELINE north-star config (GPT-3 6.7B, fleet-style hybrid
dp x fsdp x tp x pp over a pod mesh) must LOWER shape-level on a
SIMULATED v5p-64 — no 27 GB of weights materialized, just the abstract
trace + partitioned HLO of the full planner-driven training step, with
its collective plan audited against the expected schedule (reference
analog: the fleet hybrid topo in
python/paddle/distributed/fleet/meta_parallel/ driving the 6.7B GPT
benchmark configs).

The audit runs in a FRESH subprocess pinned to 64 virtual CPU devices
(paddle_tpu.device.pin_cpu — the conftest pin is process-wide and fixed
at 8, and a 6.7B lowering inside the loaded full-suite process was
exactly the memory-pressure flake that parked this id in
tests/baseline_failures_tier1.txt for two PRs). Process isolation is
what makes it pass ROUTINELY: the child holds only this one trace.

This is the compile-side half of what a v5p-64 run would do; it catches
sharding-spec mismatches, pipeline/microbatch shape bugs, remat policy
breakage, and — through profiler/hlo_audit.py — involuntary GSPMD
resharding at the production scale the single-chip bench can't reach.
(Execution correctness at small scale is dryrun_multichip's job.)
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
    import json
    import sys

    from paddle_tpu.device import pin_cpu
    assert pin_cpu(64), "could not pin 64 virtual CPU devices"

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import (GPTConfig, PARAM_SPECS,
                                       init_gpt_params)
    from paddle_tpu.parallel.planner import plan_train
    from paddle_tpu.profiler import hlo_audit

    # GPT-3 6.7B: 32L x 4096d x 32 heads, S=2048 (BASELINE.json row 3)
    cfg = GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=32,
                    num_heads=32, max_seq_len=2048,
                    sequence_parallel=True, remat=True,
                    remat_policy="dots", dtype=jnp.bfloat16)

    # really 6.7B-class, without materializing a byte
    import math
    p_shapes = jax.eval_shape(
        lambda k: init_gpt_params(cfg, k), jax.random.PRNGKey(0))
    n_params = sum(math.prod(v.shape) for v in p_shapes.values())

    # the flagship hybrid over the simulated v5p-64:
    # dp2 x fsdp2 x tp4 x pp4 = 64 chips, 4 microbatches (1F1B)
    plan = plan_train(cfg, 64, 16, dp=2, fsdp=2, tp=4, pp=4,
                      microbatches=4, param_specs=PARAM_SPECS)
    audit = hlo_audit.audit_train_step(cfg, plan, 16, seq=2048)
    print(json.dumps({"n_params": n_params, "plan": audit["plan"],
                      "n_devices": audit["n_devices"],
                      "counts": audit["counts"],
                      "findings": audit["findings"],
                      "compile_ms": audit["compile_ms"]}))
"""


def test_gpt_6p7b_hybrid_step_lowers(tmp_path):
    script = tmp_path / "lower_67b.py"
    script.write_text(textwrap.dedent(_WORKER))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the child pins its own 64 virtual CPU devices before jax loads
    env.pop("JAX_PLATFORMS", None)
    res = subprocess.run([sys.executable, str(script)], cwd=REPO,
                         env=env, timeout=600,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE)
    assert res.returncode == 0, (
        f"6.7B lowering subprocess failed:\n{res.stderr.decode()[-4000:]}")
    doc = json.loads(res.stdout.decode().strip().splitlines()[-1])

    assert 6.3e9 < doc["n_params"] < 7.3e9, doc["n_params"]
    assert doc["n_devices"] == 64
    assert doc["compile_ms"] > 0

    # the collective plan the flagship hybrid pays, and nothing else:
    counts = doc["counts"]
    assert counts.get("collective-permute", 0) > 0    # 1F1B pp ring
    assert counts.get("all-gather", 0) > 0            # ZeRO-3 params
    assert counts.get("reduce-scatter", 0) > 0        # grad shards
    assert counts.get("all-reduce", 0) > 0            # tp/dp reductions
    # zero involuntary-resharding findings at production scale — the
    # same contract tools/audit_gate.py pins for the small plans
    assert doc["findings"] == [], doc["findings"]
