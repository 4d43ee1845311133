"""Ledger-vs-compiled HBM attribution per canonical plan.

The memory sibling of tools/train_attrib.py / serving_attrib.py:
instead of joining measured ms against the FLOPs roofline, this joins
the analytical memory ledger (cost_model.train_memory_ledger /
serving_memory_ledger — the SAME formula the planner's HBM gate
consumes) against XLA's compiled memory accounting for the executable
that actually lowers (profiler/mem_audit.py), one row per plan:

- train rows: the canonical observability plans (dp2_fsdp2_tp2, fsdp8,
  dp2_tp2_pp2_mb4) on the 8-virtual-device CPU mesh, plus the 6.7B
  AOT lowering (--x67b: the tests/test_67b_lowering.py config on a
  64-virtual-device mesh, subprocess-isolated like the test);
- serving rows: the dense_fp vs paged_int8 layouts of the chaos-drill
  model (the serving_attrib A/B pair), audited through the live
  engine's own decode tick.

Each row names the ledger components, the compiled temp/argument/
output/alias split, the relative gap, and any hbm_underestimate /
hbm_overestimate findings — the evidence table BASELINE.md §Memory
observability publishes and tools/mem_gate.py pins.

Usage:
  python tools/mem_attrib.py --pretty              # all canonical rows
  python tools/mem_attrib.py --plans fsdp8 --json
  python tools/mem_attrib.py --x67b                # add the 6.7B row
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
TOOLS = os.path.dirname(os.path.abspath(__file__))
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

# a CPU-only tool in script mode unless --tpu is given: it pins virtual
# CPU devices before jax initializes; the 6.7B worker (CPU-only too, so
# it needs no chip the parent holds) pins 64 in its own process
from paddle_tpu.device import pin_cpu            # noqa: E402
if __name__ == "__main__" and "--tpu" not in sys.argv:
    pin_cpu(64 if "--_x67b-worker" in sys.argv else 8)

CANONICAL_TRAIN = ("dp2_fsdp2_tp2", "fsdp8", "dp2_tp2_pp2_mb4")
CANONICAL_SERVING = ("dense_fp", "paged_int8")
TOLERANCE = 0.5


def _log(msg):
    print(f"[mem_attrib] {msg}", file=sys.stderr, flush=True)


def attrib_row(res: dict) -> dict:
    """One audit result -> the mem_attrib row (the train_attrib row
    format, memory flavored). Importable so recorded docs re-join
    offline (tests/test_mem_observability.py)."""
    led, comp = res["ledger"], res["compiled"]
    return {
        "plan": res["plan"],
        "ledger_bytes": round(led["total"]),
        "components": {k: round(v)
                       for k, v in led["components"].items()},
        "compiled_peak_bytes": comp.get("peak_bytes"),
        "compiled": {k: v for k, v in comp.items()
                     if k != "peak_bytes"},
        "gap_fraction": res["gap_fraction"],
        "findings": res["findings"],
    }


def measure_train_plan(name: str, tolerance: float = TOLERANCE) -> dict:
    """Audit ONE canonical train plan on the small observability
    config — the same cfg/batch/seq train_attrib and audit_gate lower,
    so every evidence table describes the same executable."""
    import train_attrib

    from paddle_tpu.models.gpt import PARAM_SPECS
    from paddle_tpu.parallel.planner import plan_train
    from paddle_tpu.profiler import mem_audit

    class _Args:
        vocab, hidden, layers, seq = 512, 128, 2, 32

    cfg = train_attrib.build_cfg(_Args)
    deg = train_attrib.parse_plan_name(name)
    n_devices = deg["dp"] * deg["fsdp"] * deg["tp"] * deg.get("pp", 1)
    plan = plan_train(cfg, n_devices, 8, param_specs=PARAM_SPECS, **deg)
    return attrib_row(mem_audit.audit_train_memory(
        cfg, plan, 8, seq=_Args.seq, tolerance=tolerance))


def measure_serving_layout(name: str,
                           tolerance: float = TOLERANCE) -> dict:
    """Audit ONE canonical serving layout (dense_fp | paged_int8) on
    the chaos-drill model through the live engine's decode tick."""
    import jax

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
    from paddle_tpu.profiler import mem_audit

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=64, dtype="float32")
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    kw = ({} if name == "dense_fp"
          else {"kv_layout": "paged", "page_size": 8, "quant": "int8"})
    eng = ServingEngine(params, cfg, family="gpt", num_slots=3,
                        max_len=64, **kw)
    return attrib_row(mem_audit.audit_serving_memory(
        eng, tolerance=tolerance))


def x67b_row_inproc(tolerance: float = TOLERANCE) -> dict:
    """The 6.7B AOT row (worker process: 64 virtual CPU devices
    already pinned). tests/test_67b_lowering.py's exact config/plan —
    abstract avals only, no 6.7B params materialize."""
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import GPTConfig, PARAM_SPECS
    from paddle_tpu.parallel.planner import plan_train
    from paddle_tpu.profiler import mem_audit

    cfg = GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=32,
                    num_heads=32, max_seq_len=2048, dtype=jnp.bfloat16,
                    remat="dots", sequence_parallel=True)
    plan = plan_train(cfg, 64, 16, dp=2, fsdp=2, tp=4, pp=4,
                      microbatches=4, param_specs=PARAM_SPECS)
    return attrib_row(mem_audit.audit_train_memory(
        cfg, plan, 16, seq=2048, tolerance=tolerance))


def x67b_row(tolerance: float = TOLERANCE, timeout: int = 900) -> dict:
    """Run the 6.7B lowering in a subprocess (its 64-device pin and
    multi-minute GSPMD compile must not contaminate this process)."""
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_x67b-worker",
         "--tolerance", str(tolerance)],
        cwd=REPO, capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError(f"6.7B worker failed (rc={res.returncode}): "
                           f"{res.stderr[-2000:]}")
    return json.loads(res.stdout.strip().splitlines()[-1])


def render_table(rows) -> str:
    """The human-readable ledger-vs-compiled table."""
    lines = []
    hdr = (f"{'plan':<18} {'ledger MB':>10} {'compiled MB':>12} "
           f"{'gap':>7} {'findings':>22}  top components")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for r in rows:
        total = max(r["ledger_bytes"], 1)
        comps = "  ".join(
            f"{k}={v / 1e6:.2f}M"
            for k, v in sorted(r["components"].items(),
                               key=lambda kv: -kv[1])
            if v / total >= 0.02)
        peak = r["compiled_peak_bytes"]
        gap = r["gap_fraction"]
        kinds = ",".join(sorted({f["kind"] for f in r["findings"]})) \
            or "-"
        lines.append(
            f"{r['plan']:<18} {r['ledger_bytes'] / 1e6:>10.2f} "
            f"{(peak or 0) / 1e6:>12.2f} "
            f"{gap if gap is not None else float('nan'):>+7.0%} "
            f"{kinds:>22}  {comps}")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--plans",
                    default=",".join(CANONICAL_TRAIN
                                     + CANONICAL_SERVING),
                    help="comma-separated plan/layout names")
    ap.add_argument("--tolerance", type=float, default=TOLERANCE,
                    help="relative gap beyond which a finding is named")
    ap.add_argument("--x67b", action="store_true",
                    help="add the 6.7B AOT lowering row (subprocess, "
                         "64 virtual devices, minutes of compile)")
    ap.add_argument("--_x67b-worker", action="store_true",
                    dest="x67b_worker", help=argparse.SUPPRESS)
    ap.add_argument("--tpu", action="store_true",
                    help="run on the default (TPU) backend")
    ap.add_argument("--pretty", action="store_true")
    args = ap.parse_args()

    if args.x67b_worker:
        print(json.dumps(x67b_row_inproc(args.tolerance)), flush=True)
        return 0

    rows = []
    for name in [n for n in args.plans.split(",") if n]:
        _log(f"auditing {name} ...")
        if name in CANONICAL_SERVING:
            rows.append(measure_serving_layout(name, args.tolerance))
        else:
            rows.append(measure_train_plan(name, args.tolerance))
    if args.x67b:
        _log("auditing 6.7B AOT lowering (subprocess) ...")
        rows.append(x67b_row(args.tolerance))
    import jax
    doc = {"metric": "mem_attribution",
           "backend": jax.devices()[0].platform,
           "tolerance": args.tolerance, "plans": rows}
    print(json.dumps(doc), flush=True)
    if args.pretty:
        print(render_table(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
