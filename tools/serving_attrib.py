"""Roofline attribution for the serving tick: measured ms vs the
cost-model ledger, per phase, per layout.

Joins the two halves this PR's observability layer provides:
- measured per-tick milliseconds + per-tick workload (active slots,
  attended cache tokens) from the in-tick telemetry stream
  (profiler/serving_telemetry — the fields ride the tick's one host
  pull, so the measurement perturbs nothing);
- the analytical per-phase FLOPs/bytes price of that workload
  (paddle_tpu.cost_model.serving_tick_ledger: attention math vs KV
  gather vs matmuls vs dequant epilogue vs LM head).

For each layout it reports the roofline lower bound per tick (each
phase at max(flops/peak, bytes/bw), the binding side named), the
measured p50 tick, the achieved-vs-roofline fraction, and the phase
attribution shares: the ledger and attribution math are
platform-free, and on the CPU the "achieved" column only calibrates the
harness (the fraction means something only against the chip the
roofline describes; --tpu runs on the default backend).

Usage:
  python tools/serving_attrib.py                  # dense-fp + paged-int8
  python tools/serving_attrib.py --pretty         # + human table
  python tools/serving_attrib.py --spec           # add a spec layout
  python tools/serving_attrib.py --peak-flops 2e14 --hbm-bw 8e11
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# a CPU-only tool unless --tpu is given: it pins the CPU before jax
# initializes, and a CPU run's milliseconds are not device numbers
from paddle_tpu.device import pin_cpu            # noqa: E402
if "--tpu" not in sys.argv:
    pin_cpu(1)

import numpy as np                               # noqa: E402
import jax                                       # noqa: E402
import jax.numpy as jnp                          # noqa: E402


def _log(msg):
    print(f"[serving_attrib] {msg}", flush=True)


def _pct(ordered, q):
    import math
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def build_model(hidden, layers, vocab, max_len):
    from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=max(hidden // 32, 1),
                    ffn_hidden=4 * hidden, max_seq_len=2 * max_len,
                    sequence_parallel=False, remat=False,
                    dtype=jnp.float32)
    return init_gpt_params(cfg, jax.random.PRNGKey(0)), cfg


def measure_layout(name, params, cfg, prompts, gen, max_len,
                   engine_kw, peak_flops, hbm_bw):
    """One layout: warm, run measured, join tick telemetry with the
    ledger into the attribution row."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.cost_model import (serving_tick_ledger,
                                       roofline_attribution)
    eng = ServingEngine(params, cfg, family="gpt", max_len=max_len,
                        telemetry="on", **engine_kw)
    eng.generate(prompts, gen)                 # warm (compiles)
    n0 = len(eng.tick_records())
    t0 = time.perf_counter()
    eng.generate(prompts, gen)
    wall_s = time.perf_counter() - t0
    recs = eng.tick_records()[n0:]
    ticks = [r for r in recs if r["kind"] == "serving_tick"]
    if not ticks:
        raise RuntimeError(f"{name}: no serving_tick records — "
                           "telemetry off?")
    dur = sorted(r["dur_ms"] for r in ticks)
    mean_active = float(np.mean([r["active"] for r in ticks]))
    mean_attended = float(np.mean([r["attended"] for r in ticks]))
    tokens = sum(r["tokens"] for r in ticks)

    ledger = serving_tick_ledger(
        cfg, family="gpt",
        layout="paged" if eng.paged else "dense",
        quant="int8" if eng.quant else "off",
        spec=bool(eng.spec),
        gamma=eng.spec_gamma if eng.spec else 0,
        draft_layers=eng.spec_draft_layers if eng.spec else 0,
        active=mean_active, attended=mean_attended,
        num_slots=eng.num_slots,     # the tick computes EVERY row
        max_len=eng.max_len, page_size=eng.page_size,
        max_pages=getattr(eng, "max_pages", 0),
        length_aware=eng.length_aware_tick())
    roof = roofline_attribution(ledger, peak_flops=peak_flops,
                                hbm_bw=hbm_bw)
    measured_ms = _pct(dur, 50)
    roof_ms = roof["roofline_s"] * 1e3
    row = {
        "layout": name,
        "ticks": len(ticks),
        "tokens": tokens,
        "tokens_per_s": round(tokens / wall_s, 1),
        "measured_ms_per_tick_p50": round(measured_ms, 3),
        "measured_ms_per_tick_p95": round(_pct(dur, 95), 3),
        "mean_active_slots": round(mean_active, 2),
        "mean_attended_tokens": round(mean_attended, 1),
        "tick_flops": round(ledger["total"]["flops"]),
        "tick_bytes": round(ledger["total"]["bytes"]),
        "roofline_ms_per_tick": round(roof_ms, 6),
        "achieved_vs_roofline": round(roof_ms / measured_ms, 6)
        if measured_ms else None,
        "phases": {
            p: {"share": v["share"], "bound": v["bound"],
                "flops": round(v["flops"]),
                "bytes": round(v["bytes"])}
            for p, v in roof["per_phase"].items()},
        "kv_masked_waste": round(
            1.0 - (ledger["phases"]["kv_gather"]["bytes_ideal"]
                   / ledger["phases"]["kv_gather"]["bytes"]), 4)
        if ledger["phases"]["kv_gather"]["bytes"] else 0.0,
        # dispatched vs useful attention flops: occupancy + mask waste
        "attn_useful_fraction": round(
            ledger["phases"]["attention"]["flops_useful"]
            / ledger["phases"]["attention"]["flops"], 4)
        if ledger["phases"]["attention"]["flops"] else 0.0,
    }
    return row


def render_table(rows) -> str:
    """The human-readable achieved-vs-roofline table."""
    lines = []
    hdr = (f"{'layout':<14} {'ms/tick':>9} {'roofline':>10} "
           f"{'achieved':>9}  phase shares (bound)")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for r in rows:
        shares = "  ".join(
            f"{p}={v['share']:.0%}({v['bound'][0]})"
            for p, v in sorted(r["phases"].items(),
                               key=lambda kv: -kv[1]["share"])
            if v["share"] > 0)
        lines.append(
            f"{r['layout']:<14} {r['measured_ms_per_tick_p50']:>9.3f} "
            f"{r['roofline_ms_per_tick']:>10.4f} "
            f"{r['achieved_vs_roofline']:>9.2%}  {shares}")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--spec", action="store_true",
                    help="add a speculative layout (gamma=4)")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the default (TPU) backend")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="roofline peak FLOP/s (default: "
                         "planner.ChipSpec)")
    ap.add_argument("--hbm-bw", type=float, default=None,
                    help="roofline bytes/s (default: planner.ChipSpec)")
    ap.add_argument("--pretty", action="store_true")
    args = ap.parse_args()

    params, cfg = build_model(args.hidden, args.layers, args.vocab,
                              args.max_len)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, args.vocab,
                           rng.randint(8, 48)).astype(np.int32)
               for _ in range(args.requests)]
    layouts = [
        ("dense_fp", {"num_slots": args.slots, "kv_layout": "dense",
                      "quant": "off"}),
        ("paged_int8", {"num_slots": args.slots, "kv_layout": "paged",
                        "page_size": args.page_size, "quant": "int8"}),
    ]
    if args.spec:
        layouts.append(
            ("dense_fp_spec", {"num_slots": args.slots,
                               "kv_layout": "dense", "quant": "off",
                               "spec_decode": "spec", "gamma": 4}))
    rows = []
    for name, kw in layouts:
        _log(f"measuring {name} ...")
        rows.append(measure_layout(name, params, cfg, prompts,
                                   args.gen, args.max_len, kw,
                                   args.peak_flops, args.hbm_bw))
    doc = {"metric": "serving_roofline_attribution",
           "backend": jax.devices()[0].platform,
           "model": f"{args.layers}Lx{args.hidden}d",
           "requests": args.requests, "gen": args.gen,
           "layouts": rows}
    print(json.dumps(doc), flush=True)
    if args.pretty:
        print(render_table(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
