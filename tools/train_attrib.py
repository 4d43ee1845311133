"""Roofline attribution for the planned train step: measured ms/step vs
the cost-model train ledger, per phase, per plan — plus the GSPMD
collective audit of the step that actually compiled.

The training-side sibling of tools/serving_attrib.py, joining three
sources per dp×fsdp×tp plan:
- measured ms/step from the batched telemetry stream
  (profiler/telemetry with the MFU_FIELDS `tokens` extension — step
  timing comes from flush-to-flush wall deltas, the first window
  excluded as the compile window, exactly telemetry_report's rule),
  and the `train.mfu` gauge the flush computes;
- the analytical per-phase price of that step
  (cost_model.train_step_ledger: fwd matmuls/attention, bwd at 2x,
  remat recompute, optimizer, head/loss, per-axis collective phases
  against ChipSpec.ici_bw) rooflined by cost_model.roofline_attribution
  (predicted step ms, bound phase, peak MFU per plan);
- the HLO collective audit (profiler/hlo_audit): which collectives
  GSPMD REALLY inserted vs the plan's expected schedule — surprise
  resharding collectives are named findings, not a slow step.

On the CPU rung the achieved fraction calibrates the harness (the
roofline prices a TPU chip); run with --tpu on a live window for the
real MFU rows. Each measured row is also appended to the telemetry
JSONL as a {"kind": "train_attrib"} record so telemetry_report's
`train_attrib` block can replay the join offline.

Usage:
  python tools/train_attrib.py                     # dp2x fsdp2x tp2 + fsdp8
  python tools/train_attrib.py --plans dp2_fsdp2_tp2,dp4_tp2,fsdp8
  python tools/train_attrib.py --pretty --steps 16
  python tools/train_attrib.py --from-jsonl RUN.jsonl --plans fsdp8
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# a CPU-only tool unless --tpu is given: it pins 8 virtual CPU devices
# (the mesh the plans need) before jax initializes, and a CPU run's
# milliseconds are not device numbers. Script-mode only: importers
# (tests) own their backend and must not have it re-pinned at import
# time.
from paddle_tpu.device import pin_cpu            # noqa: E402
if __name__ == "__main__" and "--tpu" not in sys.argv:
    pin_cpu(8)

import numpy as np                               # noqa: E402
import jax                                       # noqa: E402
import jax.numpy as jnp                          # noqa: E402


def _log(msg):
    print(f"[train_attrib] {msg}", file=sys.stderr, flush=True)


def parse_plan_name(name: str) -> dict:
    """'dp2_fsdp2_tp2' / 'dp4_tp2' / 'fsdp8' -> explicit degrees.
    'pp'/'mb' tokens select the pipelined step ('dp2_tp2_pp2_mb4');
    an 'overlap' token turns on the latency-hiding collective schedule
    (docs/parallel_training.md §Collective overlap)."""
    deg = {"dp": 1, "fsdp": 1, "tp": 1}
    for axis, n in re.findall(r"(dp|fsdp|tp|pp|mb)(\d+)", name):
        deg[axis] = int(n)
    if deg.pop("mb", None):
        deg["microbatches"] = int(re.search(r"mb(\d+)", name).group(1))
    if deg.get("pp", 1) == 1:
        deg.pop("pp", None)
    if "overlap" in name:
        deg["overlap"] = True
    return deg


def build_cfg(args):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                     num_layers=args.layers,
                     num_heads=max(args.hidden // 32, 1),
                     max_seq_len=2 * args.seq, dtype=jnp.float32,
                     remat=False, sequence_parallel=False)


def attrib_row(summary: dict, ledger: dict, roof: dict,
               audit: dict = None, plan_name: str = "") -> dict:
    """Join a telemetry_report.summarize() doc with a train ledger's
    roofline (and optionally an HLO audit) into one achieved-vs-
    roofline row — the serving_attrib row format, train flavored.
    Importable so recorded JSONLs can be re-joined offline
    (tests/test_train_observability.py)."""
    st = summary.get("step_time") or {}
    measured_ms = st.get("p50_ms")
    roof_ms = roof["roofline_s"] * 1e3
    row = {
        "plan": plan_name or (ledger["config"]["plan"]
                              if "config" in ledger else ""),
        "steps": st.get("steps"),
        "measured_ms_per_step_p50": measured_ms,
        "compile_window_ms_per_step":
            summary.get("compile_window_ms_per_step"),
        "roofline_ms_per_step": round(roof_ms, 6),
        "achieved_vs_roofline": round(roof_ms / measured_ms, 6)
        if measured_ms else None,
        "peak_mfu": roof.get("peak_mfu"),
        "achieved_mfu": (summary.get("mfu") or {}).get("mfu"),
        "tokens_per_s": (summary.get("mfu") or {}).get("tokens_per_s"),
        "model_flops_per_step": round(ledger.get("model_flops", 0)),
        "phases": {
            p: {"share": v["share"], "bound": v["bound"],
                "flops": round(v["flops"]), "bytes": round(v["bytes"])}
            for p, v in roof["per_phase"].items()},
    }
    if audit is not None:
        row["audit"] = {
            "counts": audit["counts"],
            "findings": [
                {"kind": f["kind"], "op": f["op"], "axes": f["axes"],
                 "count": f["count"], "bytes": f["bytes"]}
                for f in audit["findings"]],
            "compile_ms": audit["compile_ms"],
        }
    return row


def measure_plan(name, cfg, args, peak_flops, hbm_bw, ici_bw):
    """One plan: plan, ledger, instrumented telemetry run, report join,
    HLO audit."""
    from paddle_tpu.cost_model import (train_step_ledger,
                                       roofline_attribution)
    from paddle_tpu.models.gpt import (init_gpt_params, init_opt_state,
                                       train_step)
    from paddle_tpu.parallel.planner import plan_train, ChipSpec
    from paddle_tpu.profiler import hlo_audit
    from paddle_tpu.profiler.telemetry import (TelemetryPipeline,
                                               instrument_train_step,
                                               MFU_FIELDS)
    from telemetry_report import summarize

    deg = parse_plan_name(name)
    if getattr(args, "overlap", False):
        deg["overlap"] = True
    n_devices = (deg["dp"] * deg["fsdp"] * deg["tp"]
                 * deg.get("pp", 1))
    plan = plan_train(cfg, n_devices, args.batch, **deg)
    mesh = plan.build_mesh()
    ledger = train_step_ledger(cfg, plan=plan, global_batch=args.batch,
                               seq=args.seq)
    roof = roofline_attribution(ledger, peak_flops=peak_flops,
                                hbm_bw=hbm_bw, ici_bw=ici_bw)
    chip_peak = peak_flops or ChipSpec().peak_flops
    path = f"{args.jsonl_prefix}.{name}.jsonl"
    if os.path.exists(path):
        os.remove(path)
    tele = TelemetryPipeline(
        path, every=args.every, fields=MFU_FIELDS,
        meta={"samples_per_step": args.batch, "plan": name},
        flops_per_token=ledger["model_flops"] / ledger["tokens"],
        peak_flops=chip_peak * n_devices)
    step = instrument_train_step(train_step, tele, cfg=cfg, lr=1e-3,
                                 mesh=mesh, plan=plan)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (args.batch, args.seq + 1)), jnp.int32)
    tstate = tele.device_init()
    t0 = time.perf_counter()
    for i in range(args.steps):
        loss, params, opt, tstate = step(params, opt, toks, tstate)
        tstate = tele.tick(i, tstate)
    float(loss)
    _log(f"{name}: {args.steps} steps in "
         f"{time.perf_counter() - t0:.1f}s, traces={step.trace_count}")
    tele.close()
    audit = hlo_audit.audit_train_step(cfg, plan, args.batch,
                                       seq=args.seq)
    row = attrib_row(summarize(path), ledger, roof, audit=audit,
                     plan_name=plan.name)
    # embed the join in the stream for offline replay
    # (telemetry_report's train_attrib block)
    with open(path, "a") as f:
        f.write(json.dumps({"kind": "train_attrib", **row}) + "\n")
    return row


def join_jsonl(path, name, cfg, args, peak_flops, hbm_bw, ici_bw):
    """--from-jsonl: re-join a recorded telemetry stream with the
    ledger (no execution, no audit)."""
    from paddle_tpu.cost_model import (train_step_ledger,
                                       roofline_attribution)
    from telemetry_report import summarize
    ledger = train_step_ledger(cfg, plan=parse_plan_name(name),
                               global_batch=args.batch, seq=args.seq)
    roof = roofline_attribution(ledger, peak_flops=peak_flops,
                                hbm_bw=hbm_bw, ici_bw=ici_bw)
    return attrib_row(summarize(path), ledger, roof, plan_name=name)


def render_table(rows) -> str:
    """The human-readable achieved-vs-roofline table."""
    lines = []
    hdr = (f"{'plan':<16} {'ms/step':>9} {'roofline':>10} "
           f"{'achieved':>9} {'peakMFU':>8} {'findings':>8}  "
           f"top phases (bound)")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for r in rows:
        shares = "  ".join(
            f"{p}={v['share']:.0%}({v['bound'][0]})"
            for p, v in sorted(r["phases"].items(),
                               key=lambda kv: -kv[1]["share"])
            if v["share"] >= 0.02)
        nf = len((r.get("audit") or {}).get("findings", []))
        meas = r["measured_ms_per_step_p50"]
        ach = r["achieved_vs_roofline"]
        lines.append(
            f"{r['plan']:<16} "
            f"{meas if meas is not None else float('nan'):>9.3f} "
            f"{r['roofline_ms_per_step']:>10.4f} "
            f"{ach if ach is not None else float('nan'):>9.2%} "
            f"{r['peak_mfu'] or 0:>8.1%} {nf:>8}  {shares}")
    return "\n".join(lines)


def load_rows(path) -> list:
    """All train_attrib rows a JSONL file carries — either the main()
    stdout doc ({"metric": "train_roofline_attribution", "plans": [..]})
    or a telemetry stream with embedded {"kind": "train_attrib"}
    records (measure_plan appends one per run)."""
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if d.get("metric") == "train_roofline_attribution":
                rows.extend(d.get("plans") or [])
            elif d.get("kind") == "train_attrib":
                rows.append(d)
    return rows


def compare_rows(before: list, after: list) -> list:
    """Join two row sets by plan name into delta rows: measured
    ms/step, achieved MFU, and per-phase roofline share deltas
    (after − before). The before/after evidence format for the overlap
    and fused-kernel campaigns (BASELINE.md §MFU campaign)."""
    out = []
    bmap = {r.get("plan"): r for r in before}
    for a in after:
        b = bmap.get(a.get("plan"))
        if b is None:
            continue

        def _d(key):
            av, bv = a.get(key), b.get(key)
            return (round(av - bv, 6)
                    if av is not None and bv is not None else None)
        phases = sorted(set(b.get("phases") or {})
                        | set(a.get("phases") or {}))
        share = {
            p: round(((a.get("phases") or {}).get(p) or {})
                     .get("share", 0.0)
                     - ((b.get("phases") or {}).get(p) or {})
                     .get("share", 0.0), 6)
            for p in phases}
        out.append({
            "plan": a.get("plan"),
            "measured_ms_before": b.get("measured_ms_per_step_p50"),
            "measured_ms_after": a.get("measured_ms_per_step_p50"),
            "measured_ms_delta": _d("measured_ms_per_step_p50"),
            "achieved_mfu_before": (b.get("achieved_mfu")),
            "achieved_mfu_after": (a.get("achieved_mfu")),
            "achieved_mfu_delta": _d("achieved_mfu"),
            "findings_before": len((b.get("audit") or {})
                                   .get("findings", [])),
            "findings_after": len((a.get("audit") or {})
                                  .get("findings", [])),
            "phase_share_delta": share,
        })
    return out


def render_compare(cmp_rows) -> str:
    """The human-readable before/after delta table."""
    lines = []
    hdr = (f"{'plan':<18} {'ms b':>9} {'ms a':>9} {'Δms':>8} "
           f"{'MFU b':>7} {'MFU a':>7} {'ΔMFU':>7}  "
           f"phase-share deltas (|Δ| >= 1%)")
    lines.append(hdr)
    lines.append("-" * len(hdr))

    def fm(v, spec, dash="      --"):
        return format(v, spec) if v is not None else dash
    for r in cmp_rows:
        shares = "  ".join(
            f"{p}{d:+.0%}" for p, d in sorted(
                r["phase_share_delta"].items(), key=lambda kv: kv[1])
            if abs(d) >= 0.01)
        lines.append(
            f"{r['plan']:<18} {fm(r['measured_ms_before'], '>9.3f')} "
            f"{fm(r['measured_ms_after'], '>9.3f')} "
            f"{fm(r['measured_ms_delta'], '>+8.3f')} "
            f"{fm(r['achieved_mfu_before'], '>7.2%')} "
            f"{fm(r['achieved_mfu_after'], '>7.2%')} "
            f"{fm(r['achieved_mfu_delta'], '>+7.2%')}  {shares}")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--plans", default="dp2_fsdp2_tp2,fsdp8",
                    help="comma-separated plan names (dpN_fsdpN_tpN)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--every", type=int, default=4,
                    help="telemetry flush cadence (>=2 windows needed "
                         "for post-compile step timing)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--jsonl-prefix", default="/tmp/train_attrib",
                    help="per-plan telemetry JSONL path prefix")
    ap.add_argument("--from-jsonl", default=None,
                    help="join THIS recorded telemetry JSONL with the "
                         "ledger instead of running (uses the first "
                         "--plans name)")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the default (TPU) backend")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="per-chip roofline FLOP/s (default: "
                         "planner.ChipSpec)")
    ap.add_argument("--hbm-bw", type=float, default=None)
    ap.add_argument("--ici-bw", type=float, default=None)
    ap.add_argument("--pretty", action="store_true")
    ap.add_argument("--overlap", action="store_true",
                    help="plan every --plans entry with the "
                         "latency-hiding collective overlap knob on")
    ap.add_argument("--compare", nargs=2, metavar=("BEFORE", "AFTER"),
                    default=None,
                    help="diff two recorded train_attrib JSONLs "
                         "(stdout docs or telemetry streams) instead "
                         "of running; prints per-plan ms/MFU/"
                         "phase-share deltas")
    args = ap.parse_args()

    if args.compare:
        cmp_rows = compare_rows(load_rows(args.compare[0]),
                                load_rows(args.compare[1]))
        print(json.dumps({"metric": "train_attrib_compare",
                          "before": args.compare[0],
                          "after": args.compare[1],
                          "plans": cmp_rows}), flush=True)
        print(render_compare(cmp_rows), flush=True)
        return 0

    cfg = build_cfg(args)
    names = [n for n in args.plans.split(",") if n]
    rows = []
    if args.from_jsonl:
        rows.append(join_jsonl(args.from_jsonl, names[0], cfg, args,
                               args.peak_flops, args.hbm_bw,
                               args.ici_bw))
    else:
        for name in names:
            _log(f"measuring {name} ...")
            rows.append(measure_plan(name, cfg, args, args.peak_flops,
                                     args.hbm_bw, args.ici_bw))
    doc = {"metric": "train_roofline_attribution",
           "backend": jax.devices()[0].platform,
           "model": f"{args.layers}Lx{args.hidden}d",
           "batch": args.batch, "seq": args.seq, "steps": args.steps,
           "plans": rows}
    print(json.dumps(doc), flush=True)
    if args.pretty:
        print(render_table(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
