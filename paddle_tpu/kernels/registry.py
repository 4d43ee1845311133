"""Evidence-gated kernel selection registry.

Reference analog: the autotune subsystem's cached algorithm choice
(paddle/phi/kernels/autotune/cache.cc:1 AlgorithmsCache +
switch_autotune.cc:1), generalized from per-shape block sizes
(kernels/autotune.py) to WHICH IMPLEMENTATION a selectable kernel ships
with: a persistent per-(kernel, backend-class, shape-bucket) winner
table in perf/kernel_registry.json.

Why a registry and not a fallback chain: the round-5 verdict found the
TPU attention default silently resolving to the homegrown Pallas kernel
— the one implementation the only hardware ablation measured as a net
loss (399.7 ms/step for xla vs 427.6+ for every Pallas forward) —
because the evidence lived in window artifacts nothing consulted. Here
the evidence IS the table: every `measured` entry carries the ms and
the arithmetic/memory volume that justify it, and `adopt()` refuses to
persist a row the roofline plausibility gate rejects — a single
host-bound or broken-clock sweep timing can never become the shipped
default (the round-4 failure mode BASELINE.md disavows).

Entry kinds:
- `measured`: impl + ms + flops/bytes evidence; must sit inside the
  physical window (`gate_ms` returns None) to load OR to be adopted.
- `policy`: impl + human reason, no perf claim — e.g. CPU keeps the
  homegrown Pallas attention so interpret-mode parity coverage keeps
  running in the test suite.

Selection precedence at the consult sites stays: explicit env override
> freshly-adopted sweep winner (attention, TPU only) > registry winner
> hardcoded default.

The roofline gate (plausible_ms / gate_ms) lives HERE so the package's
adoption path and the measurement tools share one rule;
tools/bench_util.py re-exports it for the existing tool callers.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

# ---------------------------------------------------------------- gate
# Roofline anchors for the plausibility gate: the peaks-table row
# (paddle_tpu.device.CHIP_PEAKS) of the one chip every 'tpu' row of this
# registry has been measured on. The gate also validates the file on a
# CPU, so it prices the chip the rows name, not the device it runs on.
REGISTRY_CHIP = "TPU v5 lite"
# Below these effective rates a kernel-sized timing is measuring the
# host, not the chip — the round-4 sweep persisted CE rows at
# 3.4-7.9 s for a ~15 ms kernel, which this floor rejects.
FLOOR_TFLOPS = 0.5
FLOOR_GBS = 20.0


def plausible_ms(flops: float = 0.0, bytes_moved: float = 0.0):
    """Physical window (lo_ms, hi_ms) for ONE application of a kernel of
    known arithmetic/memory volume. lo = half the roofline time (nothing
    runs 2x faster than the roofline); hi = the time implied by the
    FLOOR_* effective rates (anything slower is a measurement artifact,
    not a slow kernel)."""
    from ..device import chip_peaks
    peaks = chip_peaks(REGISTRY_CHIP)
    lo_s = max(flops / peaks.flops, bytes_moved / peaks.hbm_bw) / 2.0
    hi_s = max(flops / (FLOOR_TFLOPS * 1e12),
               bytes_moved / (FLOOR_GBS * 1e9), 1e-6)
    return lo_s * 1e3, hi_s * 1e3


def gate_ms(ms: float, flops: float = 0.0, bytes_moved: float = 0.0):
    """None if `ms` is physically plausible for the given volumes, else a
    short reason string for the record."""
    lo, hi = plausible_ms(flops, bytes_moved)
    if ms < lo:
        return f"implausibly fast: {ms:.3f} ms < {lo:.3f} ms (2x roofline)"
    if ms > hi:
        return (f"implausibly slow: {ms:.3f} ms > {hi:.1f} ms "
                "(sub-floor effective rate; likely host-bound)")
    return None


# ------------------------------------------------------------- registry
REGISTRY_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "perf", "kernel_registry.json")

# selectable kernels and their legal impl names — an entry naming
# anything else is invalid (catches a hand-edit typo before it silently
# falls through to the hardcoded default)
KNOWN_IMPLS: Dict[str, tuple] = {
    "attention": ("pallas", "jax_flash", "splash", "xla"),
    # 'pallas_fused' = the one-pass CE+grad kernel (pallas_ce.ce_fused_
    # train: backward collapses into the forward launch) — training
    # paths only; select via evidence-gated adoption, never by default
    "ce": ("pallas", "jax", "pallas_fused"),
    # fused AdamW/AMP master-update (kernels/pallas_update.py): 'jax' =
    # the models.gpt.apply_adamw tree-level form (default + oracle),
    # 'pallas' = the one-launch-per-leaf kernel;
    # tools/bench_fused_step.py --adopt is the evidence-gated writer
    "fused_update": ("jax", "pallas"),
    "varlen_attention": ("blockwise", "dense"),
    # decode-path attention over the KV cache (greedy decode + the
    # serving engine's slot pool): 'dense' = f32 scores/context (the
    # bit-parity default), 'mixed' = cache-dtype QK^T and P.V with an
    # f32 softmax (halves bf16 decode HBM traffic) — see
    # kernels/decode_attention.py
    "decode_attention": ("dense", "mixed"),
    # speculative decoding inside the serving tick (self-draft propose
    # + one-pass verify, inference/spec_decode.py): 'off' = one target
    # token per tick (the PR-4 shape), 'spec' = gamma-draft/verify
    # ticks. Env PADDLE_TPU_SPEC_DECODE overrides AND kill-switches;
    # tools/bench_serving.py --spec --adopt is the evidence-gated
    # writer
    "spec_decode": ("off", "spec"),
    # weight-only int8 serving (fused dequant-matmul over the stacked
    # serving weights, kernels/quant_matmul.py): 'off' = fp weights,
    # 'xla'/'pallas' = quantize at engine build and run the named
    # matmul impl. Env PADDLE_TPU_QUANT overrides AND kill-switches
    # (unrecognized values fail safe to off);
    # tools/bench_serving.py --quant --adopt is the evidence-gated
    # writer (refuses unless weight bytes <= 0.55x fp AND tokens/s
    # >= 0.95x fp)
    "quant_matmul": ("off", "xla", "pallas"),
    # fused multi-tick decode (inference/multi_tick.py): 'off' = one
    # decode tick per dispatch, 'scan' = K ticks inside one jitted
    # lax.scan with a device-side early-exit mask (one dispatch + one
    # host pull per K tokens — the chained_ms amortization in the
    # product path). Env PADDLE_TPU_MULTI_TICK overrides AND
    # kill-switches (an int >= 2 sets K; unrecognized fails safe to
    # off); tools/bench_serving.py --multi-tick --adopt is the
    # evidence-gated writer
    "multi_tick": ("off", "scan"),
}

_DOCS: Dict[str, Optional[dict]] = {}   # path -> parsed doc (memoized)


def backend_class(platform: Optional[str] = None) -> str:
    """'tpu' for the TPU platform and nothing else, 'cpu' for everything
    else — the two classes the registry's rows are keyed by."""
    if platform is None:
        import jax
        platform = jax.default_backend()
    return "tpu" if platform == "tpu" else "cpu"


def seq_bucket(n: int) -> str:
    """Power-of-two shape bucket for sequence-sized dims ('S1024').
    Winners generalize within a bucket; an exact-shape table would never
    get a hit outside the swept shapes."""
    b = 1
    while b < max(int(n), 1):
        b *= 2
    return f"S{b}"


def _key(kernel: str, backend: str, bucket: str) -> str:
    return f"{kernel}::{backend}::{bucket}"


def _load(path: Optional[str] = None) -> dict:
    path = path or REGISTRY_PATH
    if path not in _DOCS:
        try:
            with open(path) as f:
                _DOCS[path] = json.load(f)
        except (OSError, ValueError):
            _DOCS[path] = {}
    return _DOCS[path] or {}


def _reset() -> None:
    """Drop the memoized file reads (tests; a registry landing mid-process
    otherwise applies from the next process, like the sweep winner)."""
    _DOCS.clear()


def _entry_problem(key: str, ent) -> Optional[str]:
    """One entry's validation verdict: None when well-formed AND
    evidence-gated, else the reason. ONE rule for load-time trust,
    adopt-time gating and the CI check."""
    parts = key.split("::")
    if len(parts) != 3:
        return f"{key}: key is not kernel::backend::bucket"
    kernel, backend, _bucket = parts
    if backend not in ("tpu", "cpu"):
        return f"{key}: unknown backend class {backend!r}"
    if not isinstance(ent, dict):
        return f"{key}: entry is not an object"
    impl = ent.get("impl")
    legal = KNOWN_IMPLS.get(kernel)
    if legal is not None and impl not in legal:
        return f"{key}: impl {impl!r} not one of {legal}"
    kind = ent.get("kind")
    if kind == "policy":
        if not ent.get("reason"):
            return f"{key}: policy entry with no reason"
        return None
    if kind != "measured":
        return f"{key}: kind {kind!r} is neither measured nor policy"
    ms = ent.get("ms")
    flops = float(ent.get("flops", 0.0) or 0.0)
    bytes_moved = float(ent.get("bytes_moved", 0.0) or 0.0)
    if not isinstance(ms, (int, float)) or ms <= 0:
        return f"{key}: measured entry with no ms"
    if flops <= 0 and bytes_moved <= 0:
        return (f"{key}: measured entry carries no arithmetic/memory "
                "volume, so plausibility cannot be checked")
    reason = gate_ms(float(ms), flops=flops, bytes_moved=bytes_moved)
    if reason:
        return f"{key}: {reason}"
    return None


def validate(doc: Optional[dict] = None,
             path: Optional[str] = None) -> list:
    """Every problem in the registry file (empty list = clean). The CI
    check and the load path share this; an entry that fails here is
    never served by winner()."""
    if doc is None:
        doc = _load(path)
    return [p for key, ent in (doc.get("entries") or {}).items()
            for p in [_entry_problem(key, ent)] if p]


def winner(kernel: str, backend: Optional[str] = None,
           bucket: str = "*", path: Optional[str] = None) -> Optional[str]:
    """The registered impl for (kernel, backend-class, bucket), falling
    back from the exact bucket to the '*' wildcard; None when the table
    has no trustworthy row. Entries that fail validation are skipped —
    a hand-edited or corrupted row degrades to the hardcoded default
    instead of shipping."""
    from ..profiler import monitor
    backend = backend or backend_class()
    entries = _load(path).get("entries") or {}
    for b in dict.fromkeys((bucket, "*")):
        ent = entries.get(_key(kernel, backend, b))
        if ent is not None and _entry_problem(_key(kernel, backend, b),
                                              ent) is None:
            # which impl the registry actually served, per kernel — the
            # observable that caught the round-5 silent-default regression
            monitor.counter(
                f"kernel_registry_resolution.{kernel}."
                f"{ent.get('impl')}").add()
            return ent.get("impl")
    monitor.counter(f"kernel_registry_miss.{kernel}").add()
    return None


def entry(kernel: str, backend: str, bucket: str = "*",
          path: Optional[str] = None) -> Optional[dict]:
    """Raw entry read (inspection/tests); no validation applied."""
    return (_load(path).get("entries") or {}).get(
        _key(kernel, backend, bucket))


def adopt(kernel: str, impl: str, ms: float, flops: float = 0.0,
          bytes_moved: float = 0.0, backend: Optional[str] = None,
          bucket: str = "*", source: str = "", window: str = "",
          path: Optional[str] = None) -> Optional[str]:
    """Persist a measured winner — THE only write path, and it refuses
    anything the plausibility gate rejects. Returns None on success or
    the rejection reason (the caller logs it; the file is untouched).
    Atomic tmp+rename write, like the autotune cache."""
    backend = backend or backend_class()
    path = path or REGISTRY_PATH
    ent = {"impl": impl, "kind": "measured", "ms": round(float(ms), 3),
           "flops": float(flops), "bytes_moved": float(bytes_moved),
           "source": source, "window": window}
    key = _key(kernel, backend, bucket)
    problem = _entry_problem(key, ent)
    if problem:
        return problem
    doc = dict(_load(path))
    entries = dict(doc.get("entries") or {})
    entries[key] = ent
    doc["entries"] = entries
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        return f"registry write failed: {e}"
    _DOCS[path] = doc
    return None


def sweep_step_flops(spec: dict, row: dict) -> float:
    """Approximate train-step arithmetic volume for one sweep row — the
    input the plausibility gate needs. Analytic param count from the
    spec's model dims (6N flops/token + the attention score/context
    matmul terms, cost_model.train_flops_per_token's accounting);
    precision well inside the gate's 2x-roofline..sub-floor window."""
    from ..cost_model import train_flops_per_token
    m = spec["model"]
    h, L = m["hidden_size"], m["num_layers"]
    seq = int(spec["seq"])
    batch = int(row.get("batch") or spec["batch"])
    n_params = m["vocab_size"] * h + m["max_seq_len"] * h + 12 * L * h * h
    return train_flops_per_token(n_params, L, h, seq) * batch * seq


def adopt_sweep_winner(rows: list, window: str, specs: list,
                       perf_dir: Optional[str] = None) -> str:
    """When a train-step sweep lands (tools/sweep_gpt_step.py), persist
    its best tokens/sec TPU row, with the spec that produced it, to
    perf/sweep_winner.json AND to the registry's attention row.
    kernels.flash_attention._attn_impl consults both, so the measured
    winner becomes the shipped default without a code edit. `specs` are
    the sweep's self-contained spec dicts (name, env, remat, policy,
    batch, model, seq).

    Adoption is evidence-gated: the winning row's ms_per_step must sit
    inside the physical window implied by the step's arithmetic volume
    (gate_ms), so a broken-clock or host-bound timing never ships.
    Returns a one-line account of what was done."""
    from .flash_attention import impl_from_winner_env
    perf_dir = perf_dir or os.path.dirname(REGISTRY_PATH)
    rows = [r for r in rows
            if isinstance(r, dict) and r.get("tokens_per_sec")
            and r.get("platform") == "tpu"]
    if not rows:
        return "no TPU rows; nothing adopted"
    best = max(rows, key=lambda r: r["tokens_per_sec"])
    spec = next((s for s in specs if s["name"] == best["name"]), None)
    if spec is None:
        return f"sweep winner {best['name']} matches no spec; not adopted"
    flops = sweep_step_flops(spec, best)
    reason = gate_ms(float(best["ms_per_step"]), flops=flops)
    if reason:
        return (f"sweep winner {best['name']} REJECTED by the "
                f"plausibility gate ({reason}); not adopted")
    doc = {
        "name": best["name"],
        "tokens_per_sec": best["tokens_per_sec"],
        "ms_per_step": best["ms_per_step"],
        "batch": best.get("batch"),
        "env": spec.get("env", {}),
        "remat": spec.get("remat"),
        "policy": spec.get("policy"),
        "window": window,
        "gate": {"flops": flops, "passed": True},
    }
    os.makedirs(perf_dir, exist_ok=True)
    path = os.path.join(perf_dir, "sweep_winner.json")
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    note = (f"adopted sweep winner {best['name']} "
            f"({best['tokens_per_sec']} tok/s) -> {path}")
    # the durable per-backend-class table, consulted when no fresh sweep
    # file is around; adopt() re-runs the same gate before writing
    impl = impl_from_winner_env(spec.get("env", {}))
    if impl:
        err = adopt(
            "attention", impl, ms=float(best["ms_per_step"]), flops=flops,
            backend="tpu", bucket=seq_bucket(int(spec["seq"])),
            source=f"sweep {best['name']} "
                   f"({best['tokens_per_sec']} tok/s)",
            window=window,
            path=os.path.join(perf_dir, "kernel_registry.json"))
        note += (f"; registry attention::tpu -> {impl}"
                 + (f" REJECTED ({err})" if err else ""))
    return note
