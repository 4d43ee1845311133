#!/usr/bin/env python3
"""One run of one cell of the benchmark:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by the names in BENCHMARK.json, makes weights and
traffic from --seed, warms up every shape the cell uses (set-up), measures
for --seconds, checks what the timed path produced against the plain
reference, and prints ONE JSON object as the last line of standard output.
It needs the accelerator the cell asks for: without it the exit code is
not 0 and no result is printed. See benchmark/README.md.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def result_line(cell: dict, out: dict, trace: bool, devices) -> dict:
    """The contract's last line from a runner's output."""
    from benchmark.correct import judge
    metrics = harness.read_metrics(
        cell["per_layer"] if trace else cell["end_to_end"], out["record"])
    compared = judge.judge(out["numbers"], cell["limits"])
    correct = bool(out["attempted"] > 0 and out["failed"] == 0
                   and all(c["ok"] for c in compared.values()))
    line = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": harness.device_block(
                devices, out["memory_peak_bytes"],
                out.get("trace") if trace else None)}
    if trace and out.get("trace"):
        line["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                             "idle_gaps": out["trace"]["idle_gaps"]}
    line["notes"] = out.get("notes", {})
    line["compared"] = {k: [c["value"], c["limit"]]
                        for k, c in compared.items()}
    return line


def print_compared(line: dict) -> None:
    """Each number compared beside its limit: the last lines on stderr."""
    for name, (value, limit) in line["compared"].items():
        verdict = "ok" if value <= limit else "OVER"
        print(f"compared {name} = {value:.6g} (limit {limit:.6g}) {verdict}",
              file=sys.stderr)
    print(f"correct = {line['correct']} (attempted {line['attempted']}, "
          f"failed {line['failed']})", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        harness.configure_compile_cache()
        devices = harness.require_chips(cell["chips"])
    except harness.BenchmarkError as e:
        harness.log(str(e))
        return 1
    return drive(cell, args.seed, args.seconds, bool(args.trace), devices)


def drive(cell: dict, seed: int, seconds: float, trace: bool, devices,
          **runner_kw) -> int:
    """Everything after the look for a chip: run the cell, print the
    result. (The tests enter here with a tiny cell on the CPU.)"""
    runner = harness.load_runner(cell["config"])
    out = runner.run(cell, seed, seconds, trace, devices, T_PROCESS,
                     **runner_kw)
    line = result_line(cell, out, trace, devices)
    harness.log(f"notes {json.dumps(line['notes'])}")
    print_compared(line)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
