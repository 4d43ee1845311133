#!/usr/bin/env python3
"""Read, on the chip at a cell's own size, the two readings every limit in
benchmark/limits/<cell>.json is set between (PERF.md section 2 has them):

  lower   what sound runs of the program give against the reference,
          over many seeds
  upper   what the CONTROL gives — the reference in the nearest precision
          below the stated one (fp8 matmul operands for bf16), and, for
          serving, the program's own int8 path — and what each planted
          fault gives (training: half of the batch left out)

    python3 benchmark/read_limits.py --workload <cell> --seeds 1,2,3 \
        --controls 3 [--own-path 2] [--seconds 10]

Many seeds share one process (one compile, one start-up). One JSON line
per reading on standard output and in chiprun_out/limits-<cell>.jsonl.
The benchmark's own runs never call this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def train_readings(cell: dict, seeds, controls: int, devices, emit) -> None:
    from benchmark.correct import train as correct
    from benchmark.runners import train as runner
    config, traffic = cell["config"], cell["traffic"]
    step = None
    for i, seed in enumerate(seeds):
        gen = harness.load_generator(traffic).make(traffic, config, seed, 0)
        built, params, opt = runner.build(cell, seed, devices)
        step = step or built                 # one compile serves every seed
        batches = [gen.next_batch() for _ in range(correct.STEPS)]
        program, params, opt = runner.first_steps(
            step, params, opt, batches, config, seed)
        del params, opt, built
        gc.collect()
        t = time.perf_counter()
        reference = correct.reference_readings(config, seed, batches)
        worst = {}
        for what in ("grad_norms", "update_norms"):
            gaps = correct.leaf_gaps(program[what], reference[what],
                                     list(reference[what]))
            worst[what] = sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
        emit({"seed": seed, "who": "program", "losses": program["losses"],
              "reference_s": time.perf_counter() - t, "worst_leaves": worst,
              **correct.compare(program, reference)})
        if i < controls:
            for who, kw in (("control_fp8", dict(precision="fp8")),
                            ("fault_half_batch", dict(fault="half_batch")),
                            ("fault_state_unchanged",
                             dict(fault="state_unchanged"))):
                other = correct.reference_readings(config, seed, batches, **kw)
                emit({"seed": seed, "who": who, "losses": other["losses"],
                      **correct.compare(other, reference)})
                del other
        del reference
        gc.collect()


def serve_readings(cell: dict, seeds, controls: int, own_path: int,
                   seconds: float, devices, emit) -> None:
    from benchmark.runners import serve as runner
    for i, seed in enumerate(seeds):
        control = "fp8" if i < controls else None
        out = runner.run(cell, seed, seconds, False, devices,
                         time.perf_counter(), control=control)
        emit({"seed": seed, "who": "program", "attempted": out["attempted"],
              "failed": out["failed"], **out["numbers"], **out["notes"]})
        if i < own_path:
            gc.collect()
            try:
                out = runner.run(cell, seed, seconds, False, devices,
                                 time.perf_counter(),
                                 engine_kw={"quant": "int8"})
            except Exception as e:   # noqa: BLE001 — a control that crashes
                emit({"seed": seed, "who": "program_int8",   # has failed
                      "crashed": f"{type(e).__name__}: {str(e)[:300]}"})
                gc.collect()
                continue
            emit({"seed": seed, "who": "program_int8",
                  "attempted": out["attempted"], "failed": out["failed"],
                  **out["numbers"]})
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=3,
                    help="how many of the seeds also read the controls")
    ap.add_argument("--own-path", type=int, default=0,
                    help="how many seeds also run the program's own "
                         "lower-precision path (serving: quant='int8')")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        cell = harness.load_cell(args.workload)
        harness.configure_compile_cache()
        devices = harness.require_chips(cell["chips"])
    except harness.BenchmarkError as e:
        harness.log(str(e))
        return 1
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"limits-{args.workload}.jsonl"),
              "a") as f:
        def emit(row: dict) -> None:
            line = json.dumps({"workload": args.workload, **row})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
        if cell["config"]["runner"] == "train":
            train_readings(cell, seeds, args.controls, devices, emit)
        else:
            serve_readings(cell, seeds, args.controls, args.own_path,
                           args.seconds, devices, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
