"""Fused step-kernel A/B bench + the evidence-gated registry writer.

The two PR 16 Pallas step kernels ship OFF by default; this tool is the
ONLY path that turns them on (ISSUE 16 "adoption only via the
evidence-gated writer"):

- `ce`: two-pass `pallas_ce.ce_with_logits` (fwd kernel + bwd kernel)
  vs the one-pass `pallas_ce.ce_fused_train` (d_logits produced in the
  forward launch; backward is an elementwise scale) at the flagship
  head shape — adopt writes `ce -> pallas_fused`;
- `fused_update`: the tree-level `models.gpt.apply_adamw` oracle vs
  `pallas_update.fused_apply_adamw` (one launch per leaf, f32 master
  math in VMEM) over a model-scaled param tree — adopt writes
  `fused_update -> pallas`.

Each row is kernel-registry evidence format (ms + flops/bytes_moved +
knobs); `--adopt` persists a winner through `registry.adopt`, which
re-runs the roofline plausibility gate — a host-bound timing
cannot become the shipped default. Parity versus the jax oracle is
checked IN-RUN before any timing counts; a parity miss refuses
adoption no matter the speedup.

On CPU (default; the 8-virtual-device pin is unconditional) the Pallas
legs run in interpret mode: parity is meaningful, timings are not —
adoption is refused off the TPU. Usage:

  python tools/bench_fused_step.py            # CPU parity + oracle rows
  python tools/bench_fused_step.py --tpu      # chip A/B rows
  python tools/bench_fused_step.py --tpu --adopt
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

# adoption refused below this measured speedup of fused over the
# incumbent (same bar as the serving writers: a within-noise "win"
# must not flip the default)
MIN_SPEEDUP = 1.03


def log(m):
    print(f"[fused-step] {m}", file=sys.stderr, flush=True)


def emit(rec):
    print(json.dumps(rec), flush=True)
    return rec


def bench_ce(T, V, iters, interpret):
    """CE value+grad A/B: two-pass kernel pair vs one-pass fused.
    The chained carry is a gradient-descent-on-logits loop, so every
    scan iteration pays exactly one fwd+bwd of the measured impl."""
    import jax
    import jax.numpy as jnp
    from bench_util import chained_ms
    from paddle_tpu.kernels import pallas_ce

    dtype = jnp.bfloat16
    x = jax.random.normal(jax.random.PRNGKey(0), (T, V), dtype)
    tgt = jax.random.randint(jax.random.PRNGKey(1), (T,), 0, V,
                             jnp.int32)

    def sgd_step(ce_fn):
        def loss(xx):
            return jnp.mean(ce_fn(xx, tgt, interpret=interpret))
        g = jax.grad(loss)
        return lambda xx: (xx - 1e-3 * g(xx)).astype(dtype)

    # parity first: fused value+grad vs the f32 jax oracle
    def oracle(xx):
        lf = xx.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(
            lf, tgt[:, None], -1)[:, 0])

    want_l, want_g = jax.value_and_grad(oracle)(x)
    got_l, got_g = jax.value_and_grad(lambda xx: jnp.mean(
        pallas_ce.ce_fused_train(xx, tgt, interpret=interpret)))(x)
    err = max(float(jnp.abs(want_l - got_l)),
              float(jnp.max(jnp.abs(want_g.astype(jnp.float32)
                                    - got_g.astype(jnp.float32)))))
    parity_ok = err < 2e-2        # bf16 logits; grads are O(1/V)
    log(f"ce parity max_abs_err={err:.2e} ok={parity_ok}")

    length = 4 if interpret else 32
    ms_two = chained_ms(sgd_step(pallas_ce.ce_with_logits), x,
                        length=length, iters=iters)
    ms_fused = chained_ms(sgd_step(pallas_ce.ce_fused_train), x,
                          length=length, iters=iters)
    nb = x.dtype.itemsize
    # one application = fwd logits stream + dx produce/consume
    bytes_moved = 3.0 * T * V * nb
    common = {"flops": 0.0, "bytes_moved": bytes_moved,
              "knobs": {"T": T, "V": V, "dtype": "bf16",
                        "interpret": interpret},
              "parity_max_abs_err": round(err, 6)}
    emit({"variant": "ce_two_pass", "ms": round(ms_two, 3), **common})
    emit({"variant": "ce_fused", "ms": round(ms_fused, 3), **common})
    return {"kernel": "ce", "impl": "pallas_fused",
            "ms": ms_fused, "ms_incumbent": ms_two,
            "bytes_moved": bytes_moved, "flops": 0.0,
            "parity_ok": parity_ok}


def bench_update(n_rows, iters, interpret):
    """AdamW master-update A/B over a model-scaled tree: the jax
    tree-level oracle vs the fused per-leaf kernel. The chained carry
    is (params, m, v) under a fixed grad — each iteration is exactly
    one full optimizer application."""
    import jax
    import jax.numpy as jnp
    from bench_util import force
    from paddle_tpu.kernels import pallas_update
    from paddle_tpu.models.gpt import apply_adamw

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    params = {f"w{i}": jax.random.normal(k, (n_rows, 1024),
                                         jnp.float32) * 0.02
              for i, k in enumerate(ks)}
    grads = {k: jnp.full_like(v, 1e-4) for k, v in params.items()}
    opt = {"m": {k: jnp.zeros_like(v) for k, v in params.items()},
           "v": {k: jnp.zeros_like(v) for k, v in params.items()},
           "step": jnp.zeros((), jnp.float32)}

    # parity first (the dedicated interpret tests pin this rule for
    # rule; here is the in-run gate adoption depends on). The jax legs
    # pin the oracle path explicitly: after a successful --adopt,
    # apply_adamw itself would route to the fused kernel and the A/B
    # would compare the kernel with itself.
    os.environ["PADDLE_TPU_DISABLE_PALLAS_UPDATE"] = "1"
    want = apply_adamw(grads, params, opt, 1e-3)
    os.environ.pop("PADDLE_TPU_DISABLE_PALLAS_UPDATE", None)
    got = pallas_update.fused_apply_adamw(grads, params, opt, 1e-3,
                                          interpret=interpret)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree_util.tree_leaves(want[:2]),
                              jax.tree_util.tree_leaves(got[:2])))
    parity_ok = err < 1e-5
    log(f"update parity max_abs_err={err:.2e} ok={parity_ok}")

    length = 2 if interpret else 32

    def run(update_fn):
        fused = update_fn is pallas_update.fused_apply_adamw
        kw = {"interpret": interpret} if fused else {}
        if not fused:
            os.environ["PADDLE_TPU_DISABLE_PALLAS_UPDATE"] = "1"

        @jax.jit
        def chained(params, opt):
            def body(carry, _):
                p, o = carry
                p, o = update_fn(grads, p, o, 1e-3, **kw)
                return (p, o), None
            (p, o), _ = jax.lax.scan(body, (params, opt), None,
                                     length=length)
            return p, o
        try:
            force(chained(params, opt))
            t0 = time.perf_counter()
            out = chained(params, opt)
            force(out)
            return (time.perf_counter() - t0) / length * 1e3
        finally:
            os.environ.pop("PADDLE_TPU_DISABLE_PALLAS_UPDATE", None)

    ms_jax = min(run(apply_adamw) for _ in range(iters))
    ms_fused = min(run(pallas_update.fused_apply_adamw)
                   for _ in range(iters))
    n_params = sum(int(v.size) for v in params.values())
    # p rw + m rw + v rw + g read, all f32 master math
    bytes_moved = 7.0 * n_params * 4
    common = {"flops": 0.0, "bytes_moved": bytes_moved,
              "knobs": {"n_params": n_params, "interpret": interpret},
              "parity_max_abs_err": round(err, 9)}
    emit({"variant": "adamw_jax", "ms": round(ms_jax, 3), **common})
    emit({"variant": "adamw_fused", "ms": round(ms_fused, 3), **common})
    return {"kernel": "fused_update", "impl": "pallas",
            "ms": ms_fused, "ms_incumbent": ms_jax,
            "bytes_moved": bytes_moved, "flops": 0.0,
            "parity_ok": parity_ok}


def maybe_adopt(res, window: str) -> None:
    from paddle_tpu.kernels import registry
    import jax
    doc = {"metric": "fused_step_adopt", "kernel": res["kernel"],
           "impl": res["impl"]}
    speedup = (res["ms_incumbent"] / res["ms"]
               if res["ms"] > 0 else 0.0)
    doc["speedup"] = round(speedup, 3)
    if registry.backend_class(jax.default_backend()) != "tpu":
        doc["adopt"] = "refused: not a TPU-class backend"
    elif not res["parity_ok"]:
        doc["adopt"] = "refused: parity gate failed"
    elif speedup < MIN_SPEEDUP:
        doc["adopt"] = (f"refused: speedup {speedup:.3f}x < "
                        f"{MIN_SPEEDUP}x over incumbent")
    else:
        problem = registry.adopt(
            res["kernel"], res["impl"], res["ms"],
            flops=res["flops"], bytes_moved=res["bytes_moved"],
            backend="tpu", source="tools/bench_fused_step.py",
            window=window)
        doc["adopt"] = problem or "adopted"
    emit(doc)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tpu", action="store_true",
                    help="run on the default (TPU) backend; otherwise "
                         "pin CPU and run Pallas legs in interpret mode")
    ap.add_argument("--adopt", action="store_true",
                    help="persist winners through registry.adopt "
                         "(TPU-class backends only)")
    ap.add_argument("--ce-shape", default="8192x32768",
                    help="TxV for the CE rows (flagship head shape)")
    ap.add_argument("--rows", type=int, default=4096,
                    help="rows per [rows,1024] f32 leaf, 3 leaves")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--window", default="")
    args = ap.parse_args()

    if not args.tpu:
        from paddle_tpu.device import pin_cpu
        if not pin_cpu(8):
            log("could not pin the CPU platform")
            return 17
    import jax
    platform = jax.devices()[0].platform
    interpret = platform != "tpu"
    log(f"backend {platform} interpret={interpret}")
    if args.tpu and interpret:
        log("wanted TPU, got CPU; abandoning")
        return 17

    T, V = (int(v) for v in args.ce_shape.split("x"))
    if interpret:
        # interpret-mode walls are minutes/MB — shrink to parity-scale
        T, V, rows = 256, 2048, 512
    else:
        rows = args.rows
    results = [bench_ce(T, V, args.iters, interpret),
               bench_update(rows, args.iters, interpret)]
    if args.adopt:
        for res in results:
            maybe_adopt(res, args.window)
    return 0


if __name__ == "__main__":
    sys.exit(main())
