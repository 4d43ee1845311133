"""A/B: SPMD scan pipeline vs host-driven 1F1B. A CPU-only tool.

Races the two pipeline formulations on the virtual 8-device CPU mesh
(pp=4) with a transformer-block-shaped stage body, at interleave 1 and
2, checking gradient parity between them first. Writes the measured
table to perf/pipeline_ab.json; the shipped default follows the winner
(see parallel/pipeline.py + parallel/host_pipeline.py docstrings).

Run: python tools/ab_pipeline.py
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a CPU-mesh A/B by design: it pins eight virtual CPU devices and never
# sees a chip, so its times rank the two formulations and are not speed
from paddle_tpu.device import pin_cpu

pin_cpu(8)

import jax
import jax.numpy as jnp
import numpy as np

P_STAGES = 4
D = 256
FFN = 1024
LAYERS_TOTAL = 8           # constant across interleave settings
M = 8                      # microbatches
MB = 4                     # rows per microbatch
S = 64


def stage_fn(chunk_params, x):
    """One transformer-ish block per chunk layer: x [mb, S, D]."""
    def body(h, lp):
        w1, b1, w2, b2 = lp
        h = h + jnp.tanh(h @ w1 + b1) @ w2 + b2
        return h, None
    x, _ = jax.lax.scan(body, x, chunk_params)
    return x


def make_params(n_chunks, key):
    ks = jax.random.split(key, 4)
    # same total model at every interleave: finer chunks, fewer layers each
    shape = (n_chunks, LAYERS_TOTAL // n_chunks)
    return (
        jax.random.normal(ks[0], shape + (D, FFN), jnp.float32) * 0.02,
        jnp.zeros(shape + (FFN,), jnp.float32),
        jax.random.normal(ks[1], shape + (FFN, D), jnp.float32) * 0.02,
        jnp.zeros(shape + (D,), jnp.float32),
    )


def loss_fn(y):
    return jnp.mean(jnp.square(y))


def run_spmd(mesh, params, x, interleave):
    from paddle_tpu.parallel.pipeline import pipeline_forward

    # dict-shaped params for parity with the host path
    pd = {"w1": params[0], "b1": params[1],
          "w2": params[2], "b2": params[3]}

    def sfn(chunk, h):
        return stage_fn((chunk["w1"], chunk["b1"], chunk["w2"],
                         chunk["b2"]), h)

    def step(pd, x_mb):
        y = pipeline_forward(sfn, pd, x_mb, P_STAGES, M,
                             mesh=mesh, interleave=interleave,
                             remat=True)
        return jnp.mean(jax.vmap(loss_fn)(y))

    g = jax.jit(jax.value_and_grad(step))
    x_mb = x.reshape((M, MB) + x.shape[1:])
    out = g(pd, x_mb)
    jax.block_until_ready(out)              # compile + warm
    t0 = time.perf_counter()
    for _ in range(5):
        out = g(pd, x_mb)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / 5
    return float(out[0]), out[1], dt


def run_host(mesh, params, x, interleave):
    from paddle_tpu.parallel.host_pipeline import HostPipeline
    pd = {"w1": params[0], "b1": params[1],
          "w2": params[2], "b2": params[3]}

    def sfn(chunk, h):
        return stage_fn((chunk["w1"], chunk["b1"], chunk["w2"],
                         chunk["b2"]), h)

    pipe = HostPipeline(sfn, loss_fn, P_STAGES, M,
                        interleave=interleave, mesh=mesh)
    placed = pipe.place(pd)
    x_mb = x.reshape((M, MB) + x.shape[1:])
    out = pipe.grads(placed, x_mb)
    jax.block_until_ready(out)              # compile + warm
    t0 = time.perf_counter()
    for _ in range(5):
        out = pipe.grads(placed, x_mb)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / 5
    return float(out[0]), pipe.gather_stacked(out[1]), dt


def main():
    from paddle_tpu.parallel.mesh import build_mesh
    mesh = build_mesh({"pp": P_STAGES})
    x = jax.random.normal(jax.random.PRNGKey(1), (M * MB, S, D),
                          jnp.float32)
    results = {}
    for v in (1, 2):
        params = make_params(P_STAGES * v, jax.random.PRNGKey(0))
        sl = st = None
        print(f"[ab] spmd v={v} compiling...", file=sys.stderr,
              flush=True)
        try:
            sl, sg, st = run_spmd(mesh, params, x, v)
            print(f"[ab] spmd v={v}: {st * 1e3:.1f} ms",
                  file=sys.stderr, flush=True)
        except ValueError as e:
            # ONLY the designed interleave>1 rejection is expected (the
            # A/B below is WHY it was removed); any other ValueError is
            # a real harness/pipeline break and must surface
            if "HostPipeline" not in str(e):
                raise
            print(f"[ab] spmd v={v} rejected: {e}", file=sys.stderr,
                  flush=True)
        print(f"[ab] host v={v} compiling...", file=sys.stderr,
              flush=True)
        hl, hg, ht = run_host(mesh, params, x, v)
        print(f"[ab] host v={v}: {ht * 1e3:.1f} ms", file=sys.stderr,
              flush=True)
        if sl is not None:
            # parity: same loss, same grads (host divides by m, spmd
            # means through vmap — both the mean-microbatch gradient)
            assert abs(sl - hl) < 1e-5, (sl, hl)
            for k in sg:
                np.testing.assert_allclose(np.asarray(sg[k]),
                                           np.asarray(hg[k]),
                                           rtol=1e-4, atol=1e-5)
        results[f"interleave{v}"] = {
            "spmd_ms": round(st * 1e3, 2) if st is not None
            else "rejected (interleave>1 removed from spmd_pipeline)",
            "host_ms": round(ht * 1e3, 2),
            "loss": round(hl, 6),
        }
        print(json.dumps({"interleave": v,
                          "spmd_ms": results[f"interleave{v}"]["spmd_ms"],
                          "host_ms": results[f"interleave{v}"]["host_ms"]}),
              flush=True)

    r1, r2 = results["interleave1"], results["interleave2"]
    results["notes"] = {
        "config": f"pp={P_STAGES} m={M} mb={MB} S={S} D={D} ffn={FFN}",
        "winner_v1": ("spmd" if isinstance(r1["spmd_ms"], float)
                      and r1["spmd_ms"] < r1["host_ms"] else "host"),
        "host_interleave_helps": r2["host_ms"] < r1["host_ms"],
        "historical_spmd_v2_ms": 2030.45,   # measured before removal
    }
    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf", "pipeline_ab.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results["notes"]))


if __name__ == "__main__":
    main()
