"""What every run shares: finding a cell's files by the names in
BENCHMARK.json, the device check, the table of peaks, the compile cache,
the compile counter, percentiles, and the result line."""
from __future__ import annotations

import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


class BenchmarkError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, a name
    that resolves to no file)."""


def _load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"no file {os.path.relpath(path, ROOT)}") \
            from None


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, cells_of_moved: dict) -> bool:
    """Whether `cell` reports `metric`: its own `workloads` list, or, with
    no list, every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return cell in cells_of_moved[metric["moves"]]
    return True


def load_cell(name: str, bench: dict | None = None) -> dict:
    """Everything one cell is made of, each part found by its name."""
    bench = bench or load_benchmark()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise BenchmarkError(
            f"no workload {name!r} in BENCHMARK.json (known: "
            f"{[w['name'] for w in bench['workloads']]})")
    entry = entries[0]
    all_cells = [w["name"] for w in bench["workloads"]]
    e2e_cells = {m["name"]: m.get("workloads", all_cells)
                 for m in bench["end_to_end"]}
    return {
        "name": name,
        "chips": int(entry["chips"]),
        "config": _load_json("configs", entry["config"] + ".json"),
        "traffic": _load_json("traffic", entry["traffic"] + ".json"),
        "limits": _load_json("limits", name + ".json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if _reports(m, name, e2e_cells)],
        "per_layer": [m for m in bench["per_layer"]
                      if _reports(m, name, e2e_cells)],
    }


def load_reader(metric_name: str):
    """A metric's reader: `benchmark/metrics/<name>.json` names a module of
    `benchmark/readers/` and the parameters its `read(record, **params)`
    takes. A reader that finds nothing to read returns None."""
    spec = _load_json("metrics", metric_name + ".json")
    module = importlib.import_module("benchmark.readers." + spec["reader"])
    return module.read, spec.get("params", {})


def read_metrics(metrics: list, record: dict) -> dict:
    out = {}
    for m in metrics:
        read, params = load_reader(m["name"])
        value = read(record, **params)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def load_runner(config: dict):
    return importlib.import_module("benchmark.runners." + config["runner"])


def load_generator(traffic: dict):
    return importlib.import_module(
        "benchmark.generators." + traffic["generator"])


def load_peaks(device_kind: str) -> dict:
    peaks = _load_json("peaks.json")
    if device_kind not in peaks:
        raise BenchmarkError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(peaks)}); add a sourced row to benchmark/peaks.json")
    return peaks[device_kind]


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache at a FIXED path (the path is part
    of the cache's key): where JAX_COMPILATION_CACHE_DIR says if it is
    set, else `benchmark/_cache/xla` inside the checkout. Every program is
    stored, however quickly it compiled, so that only a checkout's first
    run of a cell compiles. The program's own entry points take the same
    directory (paddle_tpu.utils.compile_cache leaves a set one alone)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, "_cache", "xla")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int) -> list:
    """The accelerator devices this cell runs on, or BenchmarkError: there
    is no fallback to the CPU and no run on fewer chips than asked."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(
            f"no TPU: jax found {devices[0].platform!r}; nothing was run")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chips, jax found {len(devices)}")
    load_peaks(devices[0].device_kind)
    return devices[:chips]


class CompileCounter:
    """Counts the executables XLA builds (jax's backend-compile events), as
    chip_smoke.Run does: a cache hit still 'compiles' by this count only
    if the backend really compiled, so inside a warmed window it reads 0."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += seconds


def percentile(values, q: float):
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def device_block(devices, peak_bytes, trace_summary=None) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    if trace_summary:
        out["busy_s"] = trace_summary["busy_s"]
        out["window_s"] = trace_summary["window_s"]
    return out


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of `devices` (0 where the backend
    reports none, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
