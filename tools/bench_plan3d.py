"""plan3d rung: the planner-driven dp×fsdp×tp sharded train step, timed.

Run `parallel.planner.plan_train`'s chosen (or an
explicitly requested) 3D assignment end to end — GSPMD train step with
pinned shardings, donation on — and report steady-state ms/step,
tokens/s and (on a chip) MFU as one JSON line per leg
({"n_devices", "rc", "ok", "skipped", "tail", ...}).

The orchestrator stays off jax and runs each leg in a fresh subprocess
under a hard timeout, one after another, so a leg that needs the chip
is the only process holding it. The CPU legs pin the 8-virtual-device
platform; the TPU leg runs only with --tpu and fails (rc 17) when its
process finds no TPU — there is no probe and no fallback. CPU legs check
the path and its counts; their times are not speed.

Usage:
  python tools/bench_plan3d.py            # CPU 8-virtual-device leg
  python tools/bench_plan3d.py --tpu      # + the TPU leg
  python tools/bench_plan3d.py --run cpu8 # one leg, in-process (driver)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def log(m):
    print(f"[plan3d] {m}", file=sys.stderr, flush=True)


# leg -> (want_tpu, n_devices (0 = all), model kw, batch, seq, iters,
#         timeout_s, explicit degrees or None). CPU shapes are bench.py's
# --cpu check scaled to the 8-device mesh and PIN the canonical
# dp2×fsdp2×tp2 layout (the cost model would rightly pick pure dp for
# shapes this small — the rung's job is to exercise the 3D path); the
# TPU leg uses bench.py's shapes with the SEARCHED plan so its MFU is
# comparable with bench.py's line.
LEGS = {
    "cpu8": (False, 8, dict(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=4, max_seq_len=128, remat=False,
                            dtype="float32"), 8, 64, 3, 600,
             dict(dp=2, fsdp=2, tp=2)),
    # the 4D rung: the full-manual pipelined
    # step on a pinned dp2×tp2×pp2 grid, microbatches = 2·pp — reports
    # bubble_fraction next to ms/step (ISSUE 15)
    "cpu8_pp": (False, 8, dict(vocab_size=512, hidden_size=128,
                               num_layers=2, num_heads=4,
                               max_seq_len=128, remat=False,
                               dtype="float32"), 8, 64, 3, 600,
                dict(dp=2, fsdp=1, tp=2, pp=2, microbatches=4)),
    # overlap A/B legs (ISSUE 16): the SAME grids with the latency-
    # hiding collective schedule on (plan_train(..., overlap=True) —
    # double-buffered ZeRO-3 gather on pp plans, XLA async-collective/
    # collective-matmul flags on the GSPMD path; TPU-only there, so the
    # cpu8 A/B pins parity + trace count while the tpu A/B measures)
    "cpu8_overlap": (False, 8,
                     dict(vocab_size=512, hidden_size=128, num_layers=2,
                          num_heads=4, max_seq_len=128, remat=False,
                          dtype="float32"), 8, 64, 3, 600,
                     dict(dp=2, fsdp=2, tp=2, overlap=True)),
    "cpu8_pp_overlap": (False, 8,
                        dict(vocab_size=512, hidden_size=128,
                             num_layers=2, num_heads=4, max_seq_len=128,
                             remat=False, dtype="float32"), 8, 64, 3,
                        600, dict(dp=2, fsdp=1, tp=2, pp=2,
                                  microbatches=4, overlap=True)),
    "tpu": (True, 0, dict(vocab_size=32768, hidden_size=1024,
                          num_layers=24, num_heads=16, max_seq_len=1024,
                          remat=True, remat_policy="dots",
                          dtype="bfloat16"), 8, 1024, 10, 2100, None),
    "tpu_overlap": (True, 0, dict(vocab_size=32768, hidden_size=1024,
                                  num_layers=24, num_heads=16,
                                  max_seq_len=1024, remat=True,
                                  remat_policy="dots",
                                  dtype="bfloat16"), 8, 1024, 10, 2100,
                    dict(overlap=True)),
}


def run_leg(name: str) -> None:
    """One leg, in-process: measure and print the inner JSON line."""
    want_tpu, n_dev, kw, batch, seq, iters, _t, degrees = LEGS[name]
    if not want_tpu:
        from paddle_tpu.device import pin_cpu
        if not pin_cpu(n_dev):
            log("could not pin the virtual CPU platform")
            sys.exit(17)
    from paddle_tpu.utils.compile_cache import (seed_cache_env,
                                                sync_compile_cache_for)
    seed_cache_env()

    import jax
    import jax.numpy as jnp
    import numpy as np
    devs = jax.devices()
    platform = devs[0].platform
    if want_tpu and platform != "tpu":
        log(f"wanted TPU, got {platform}; abandoning leg")
        sys.exit(17)
    n = n_dev or len(devs)
    sync_compile_cache_for(platform)

    from paddle_tpu.models.facade import make_train_step
    from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                       init_opt_state, train_step)
    from paddle_tpu.parallel.planner import plan_train
    kw = dict(kw)
    kw["dtype"] = jnp.bfloat16 if kw["dtype"] == "bfloat16" else jnp.float32
    cfg = GPTConfig(sequence_parallel=False, **kw)
    plan = plan_train(cfg, n, batch, **(degrees or {}))
    log(f"leg={name} n={n} plan={plan.name} "
        f"({cfg.num_layers}L x {cfg.hidden_size}d, B={batch}, S={seq})")
    mesh = plan.build_mesh()
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    toks = np.random.RandomState(1).randint(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    step = make_train_step(train_step, cfg=cfg, lr=1e-4, mesh=mesh,
                           plan=plan)
    t0 = time.perf_counter()
    loss, params, opt = step(params, opt, toks)
    jax.block_until_ready(loss)
    log(f"  compile+first {time.perf_counter() - t0:.1f}s "
        f"(loss={float(loss):.4f})")
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, opt = step(params, opt, toks)
    jax.block_until_ready((loss, params, opt))
    dt = (time.perf_counter() - t0) / iters
    n_params = sum(int(v.size) for v in params.values())
    tps = batch * seq / dt
    rec = {
        "metric": ("gpt_train_plan4d" if plan.pp > 1
                   else "gpt_train_plan3d"),
        "n_devices": n,
        "plan": plan.name,
        "backend": platform,
        "ms_per_step": round(dt * 1e3, 2),
        "device_kind": devs[0].device_kind,
        "tokens_per_sec": round(tps, 1),
        "traces_after_warmup": step.trace_count,
        "batch": batch, "seq": seq,
        "overlap": bool(getattr(plan, "overlap", False)),
    }
    if plan.pp > 1:
        rec["microbatches"] = plan.microbatches
        rec["bubble_fraction"] = round(
            float(getattr(step, "bubble_fraction", 0.0) or 0.0), 4)
    if platform == "tpu":
        # MFU against the WHOLE mesh's peak (n chips); only a chip in
        # the peaks table has one
        from paddle_tpu.cost_model import train_flops_per_token
        from paddle_tpu.device import chip_peaks
        flops_per_token = train_flops_per_token(
            n_params, cfg.num_layers, cfg.hidden_size, seq)
        rec["mfu"] = round(flops_per_token * tps / (
            chip_peaks(devs[0].device_kind).flops * n), 4)
    print(json.dumps(rec), flush=True)


def orchestrate(want_tpu: bool, want_pp: bool = False,
                want_overlap: bool = False) -> int:
    """Run the legs in subprocesses; print ONE MULTICHIP-format JSON
    line per leg ({"n_devices", "rc", "ok", "skipped", "tail"} + the
    measured record when the leg produced one)."""
    legs = ["cpu8"]
    if want_overlap:
        legs.append("cpu8_overlap")
    if want_pp:
        legs.append("cpu8_pp")
        if want_overlap:
            legs.append("cpu8_pp_overlap")
    if want_tpu:
        legs.append("tpu")
        if want_overlap:
            legs.append("tpu_overlap")
    worst = 0
    for name in legs:
        _wt, n_dev, _kw, _b, _s, _i, timeout_s, _deg = LEGS[name]
        try:
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--run", name],
                cwd=HERE, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                timeout=timeout_s)
            rc, out, err = res.returncode, res.stdout, res.stderr
        except subprocess.TimeoutExpired as te:
            rc = -9
            out = te.stdout or b""
            err = (te.stderr or b"") + f"\n[timeout {timeout_s}s]".encode()
        tail = err.decode(errors="replace")[-2000:]
        line = next((ln for ln in reversed(
            out.decode(errors="replace").splitlines())
            if ln.startswith("{")), None)
        rec = {"n_devices": n_dev or 1, "rc": rc, "ok": False,
               "skipped": False, "tail": tail}
        if line:
            try:
                inner = json.loads(line)
                rec.update(inner)
                rec["ok"] = rc == 0
            except json.JSONDecodeError:
                pass
        print(json.dumps(rec), flush=True)
        if not rec["ok"] and not rec["skipped"]:
            worst = 1
    return worst


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tpu", action="store_true",
                    help="also run the TPU leg (fails without a TPU)")
    ap.add_argument("--pp", action="store_true",
                    help="also run the cpu8_pp 4D (dp2×tp2×pp2) leg")
    ap.add_argument("--overlap", action="store_true",
                    help="also run the overlap A/B legs (same grids, "
                         "latency-hiding collective schedule on)")
    ap.add_argument("--run", default=None, choices=sorted(LEGS),
                    help="run ONE leg in-process (orchestrator internal)")
    args = ap.parse_args()
    if args.run:
        run_leg(args.run)
        return 0
    return orchestrate(args.tpu, args.pp, args.overlap)


if __name__ == "__main__":
    sys.exit(main())
