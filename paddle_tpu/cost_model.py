"""paddle_tpu.cost_model — static cost estimation.

Reference analog: python/paddle/cost_model/cost_model.py (op-benchmark
-table driven CostModel.profile_measure over a Program) + the C++
framework/ir/cost_model.cc. TPU-native: XLA's own cost analysis IS the
benchmark table — per-computation flops/bytes come from the compiler
(profiler.cost_analysis), and a static Program's cost is measured on its
composed function.

Serving-tick ledger (`serving_tick_ledger`): the analytical per-phase
FLOPs/bytes price of ONE decode tick — attention math vs KV gather vs
matmuls vs dequant epilogue vs LM head — parameterized by the engine's
layout (dense/paged), quantization, and speculative config. Unlike
`cost_analysis` (which needs a lowered computation and undercounts
scan bodies) the ledger is closed-form over the model dims, so it
prices exactly the work the serving tick dispatches and splits it into
the phases an operator can act on. tools/serving_attrib.py joins it
with measured per-tick milliseconds (the in-tick telemetry stream,
profiler/serving_telemetry) into the achieved-vs-roofline report. The
ledger's FLOPs and bytes are computed from shapes and hold anywhere;
the measured milliseconds are device numbers only when the tick ran on
a chip.

Train-step ledger (`train_step_ledger`): the training-side analog for
ONE planned dp×fsdp×tp train step (parallel/planner.plan_train) —
forward matmuls/attention, backward at 2x, remat recompute as its own
phase, the AdamW/AMP update over the stacked params, LM head + loss,
PLUS one collective phase per mesh axis (fsdp all-gather/reduce-
scatter, dp grad all-reduce, tp per-layer activation all-reduces)
priced against ChipSpec.ici_bw instead of HBM bandwidth (phases carry
`channel: "ici"`; `roofline_attribution` picks the right denominator).
The collective byte formulas mirror parallel/planner._estimate exactly
(same _ring_factor model), so a plan's ledger cross-checks against the
planner's breakdown — and `train_flops_per_token` lives HERE as the
one home of the 6N MFU accounting (bench.py, the profiler/telemetry
`train.mfu` gauge and tools/train_attrib.py price against it).

Memory ledgers (`train_memory_ledger` / `serving_memory_ledger`): the
HBM half of the same attribution stack — per-chip bytes attributed to
named components (train: the f32 master state, remat activation
working set, logits chunk, overlap prefetch buffers; serving: weights
incl. quantized pairs, the KV pool, decode scratch). These are the ONE
home of the planner's memory gates (parallel/planner._estimate and
plan_serving_tp consume them) and the analytical side
profiler/mem_audit diffs against XLA's `compiled.memory_analysis()`.
"""
from __future__ import annotations

import math

class CostModel:
    """Reference CostModel shape: profile_measure(program) → cost dict."""

    def profile_measure(self, main_program=None, startup_program=None,
                        device="tpu", fetch_cost_list=("time",)):
        import jax
        from .profiler import cost_analysis
        from .static.program import (default_main_program, _replay,
                                     _replay_guard)
        program = main_program or default_main_program()
        block = program.global_block()
        feeds = [v for v in block.vars.values() if v.is_feed]
        params = [v for v in block.vars.values() if v.is_parameter]

        def composed(*vals):
            env = {v.name: x for v, x in zip(feeds + params, vals)}
            with _replay_guard():
                _replay(block, env)
            # ALL outputs must be live: returning only the last would let
            # XLA dead-code-eliminate every other branch and undercount
            outs = [env[nm] for op in block.ops for nm in op.out_names
                    if nm in env]
            return tuple(outs)

        avals = [jax.ShapeDtypeStruct(
            tuple(8 if i in v._dyn_dims else s
                  for i, s in enumerate(v._value.shape)), v._value.dtype)
            for v in feeds + params]
        dummies = [jax.numpy.zeros(a.shape, a.dtype) for a in avals]
        return cost_analysis(composed, *dummies)


def estimate_cost(fn, *example_args):
    """Cost of any jax-traceable callable (flops, bytes, memory sizes) —
    the functional entry the Program-less paths use."""
    from .profiler import cost_analysis
    return cost_analysis(fn, *example_args)


# --------------------------------------------------------------------
# serving-tick ledger (tools/serving_attrib.py's pricing half)
# --------------------------------------------------------------------
def _family_dims(cfg, family: str) -> dict:
    """Model dims + per-layer matmul structure for the two serving
    families. `mats` lists every stacked matmul as (in, out) so the
    matmul/dequant phases can price FLOPs, weight bytes and epilogue
    work leaf-accurately (mirrors models/gpt.py qkv/attn_out/mlp and
    models/llama.py q/k/v/o/gate/up/down — and
    quantization/serving.py's QUANT_LEAVES)."""
    D = int(cfg.hidden_size)
    L = int(cfg.num_layers)
    V = int(cfg.vocab_size)
    H = int(cfg.num_heads)
    KV = int(getattr(cfg, "num_kv_heads", H) or H)
    F = int(getattr(cfg, "ffn_hidden", 0) or 4 * D)
    hd = D // H
    if family == "gpt":
        mats = [(D, 3 * D), (D, D), (D, F), (F, D)]
    elif family == "llama":
        kvd = KV * hd
        mats = [(D, D), (D, kvd), (D, kvd), (D, D),
                (D, F), (D, F), (F, D)]
    else:
        raise ValueError(f"unknown family {family!r} (gpt|llama)")
    return {"D": D, "L": L, "V": V, "H": H, "KV": KV, "F": F,
            "hd": hd, "mats": mats,
            "layer_params": sum(i * o for i, o in mats),
            "layer_out_features": sum(o for _, o in mats)}


def serving_tick_ledger(cfg, family: str = "gpt",
                        layout: str = "dense", quant: str = "off",
                        spec: bool = False, gamma: int = 0,
                        draft_layers: int = 0, active: float = 1.0,
                        attended: float = 1.0,
                        num_slots: Optional[float] = None,
                        max_len: int = 0, page_size: int = 16,
                        max_pages: int = 0,
                        dtype_bytes: int = 4,
                        length_aware: bool = False) -> dict:
    """Per-phase FLOPs/bytes for ONE serving decode tick.

    The tick is FIXED-SHAPE: every one of the engine's `num_slots`
    rows computes whether active or not (serving._decode_tick —
    "inactive slots compute too"), and the attention einsum runs over
    the FULL cache view under the mask. The ledger therefore prices
    DISPATCHED work by `num_slots` and the view extent (that is what
    measured milliseconds pay for), and carries the USEFUL-work
    numbers — from the telemetry stream's `active` slots and
    `attended` cache tokens (kernels/decode_attention.attended_tokens)
    — as the `*_useful`/`*_ideal` columns whose gap is the occupancy/
    masked-waste overhead an operator can act on. `num_slots` defaults
    to `active` (a fully-occupied tick). `length_aware` says the tick
    being priced takes the dense pool's length-aware attention kernel
    (kernels/decode_attention.length_aware — a TPU's plain tick): its
    attention then runs over the ACTIVE rows alone, each over its mean
    context rounded up to whole blocks (`kv_view_extent(context=)`),
    and the masked-waste gap shrinks to that rounding. Phases:

    - matmuls:  the stacked block matmuls — FLOPs scale with rows
      computed this tick; BYTES are the weight read (per device pass
      all L layers stream once; each spec draft pass streams the
      first draft_layers), which is what makes the small-batch decode
      tick weight-bandwidth bound (parallel/planner.plan_serving_tp's
      premise, priced per phase here);
    - attention: QK^T + PV — dispatched FLOPs run over the full view
      for every row; `flops_useful` counts only mask-admitted tokens
      of active rows (the `attended` tap);
    - kv_gather: the cache read — bytes price the full view (dense:
      max_len; paged: the max_pages*page_size gathered view —
      decode_attention.kv_view_extent) across all rows; `bytes_ideal`
      prices only the attended tokens — the gap is the masked-waste
      column of the attribution report;
    - dequant:  (quant="int8") the scale-multiply epilogue per matmul
      output element, plus the int8->f32 widening read already
      reflected in the matmul phase's smaller weight bytes;
    - head:     the LM-head projection for every scored row.

    `tokens computed` per row = gamma+1 under spec (the verify pass
    scores every draft) plus gamma single-token draft passes."""
    dims = _family_dims(cfg, family)
    if layout not in ("dense", "paged"):
        raise ValueError(f"layout {layout!r} (dense|paged)")
    if quant not in ("off", "int8"):
        raise ValueError(f"quant {quant!r} (off|int8)")
    D, L, V = dims["D"], dims["L"], dims["V"]
    KV, hd = dims["KV"], dims["hd"]
    max_len = int(max_len or cfg.max_seq_len)
    from .kernels.decode_attention import kv_view_extent
    if not max_pages:
        max_pages = -(-max_len // page_size)
    view = kv_view_extent(layout == "paged", max_len, max_pages,
                          page_size)
    rows = float(num_slots) if num_slots else float(active)
    # the rows whose cache the attention reads, and how far
    kv_rows = rows
    if length_aware and layout == "dense" and not spec:
        kv_rows = float(active)
        view = kv_view_extent(False, max_len, context=math.ceil(
            attended / max(active, 1e-9)))

    T = (gamma + 1) if spec else 1            # verify-pass tokens/slot
    dL = int(draft_layers or max(1, L // 2)) if spec else 0
    full_tokens = rows * T                    # full-depth pass
    draft_tokens = rows * gamma if spec else 0.0   # x dL layers each

    # weight bytes: int8 drops the fp matmul weights to 1 byte + an
    # f32 scale per output channel (quantization/serving.py)
    if quant == "int8":
        w_layer = (dims["layer_params"]
                   + 4 * dims["layer_out_features"])
        w_head = D * V + 4 * V
    else:
        w_layer = dims["layer_params"] * dtype_bytes
        w_head = D * V * dtype_bytes

    n_draft_passes = gamma if spec else 0
    matmul = {
        "flops": 2.0 * dims["layer_params"]
                 * (L * full_tokens + dL * draft_tokens),
        # one weight stream per device pass: the full-depth pass reads
        # all L layers, each draft pass its first dL
        "bytes": w_layer * (L + dL * n_draft_passes),
    }
    # attention math: QK^T (2*S*D) + PV (2*S*D) per query per layer,
    # queries folded over the GQA group so the einsum runs at D = H*hd
    # regardless of KV. Dispatched S = the full view, every row;
    # useful S = the mask-admitted tokens of active rows.
    layer_passes = T + gamma * (dL / max(L, 1))
    attention = {
        "flops": 4.0 * D * L * view * kv_rows * layer_passes,
        "bytes": 0.0,
        "flops_useful": 4.0 * D * L * attended * layer_passes,
    }
    # cache read: k+v over the full view per row per layer per pass
    # (drafts read their dL-layer slice of the same pool)
    kv_bytes_pass = 2.0 * view * KV * hd * dtype_bytes * kv_rows
    kv_gather = {
        "flops": 0.0,
        "bytes": kv_bytes_pass * (L + dL * n_draft_passes),
        "bytes_ideal": 2.0 * attended * KV * hd * dtype_bytes
                       * (L + dL * n_draft_passes),
    }
    dequant = {"flops": 0.0, "bytes": 0.0}
    if quant == "int8":
        dequant["flops"] = (dims["layer_out_features"]
                            * (L * full_tokens + dL * draft_tokens)
                            + V * full_tokens)      # head epilogue
    head = {
        "flops": 2.0 * D * V * (full_tokens + draft_tokens),
        "bytes": w_head * (1 + n_draft_passes),
    }
    phases = {"matmuls": matmul, "attention": attention,
              "kv_gather": kv_gather, "dequant": dequant, "head": head}
    total = {"flops": sum(p["flops"] for p in phases.values()),
             "bytes": sum(p["bytes"] for p in phases.values())}
    return {"phases": phases, "total": total,
            "config": {"family": family, "layout": layout,
                       "quant": quant, "spec": bool(spec),
                       "gamma": gamma, "draft_layers": dL,
                       "active": active, "attended": attended,
                       "num_slots": rows,
                       "kv_view": view, "max_len": max_len,
                       "dtype_bytes": dtype_bytes}}


def roofline_attribution(ledger: dict, peak_flops: float = None,
                         hbm_bw: float = None, ici_bw: float = None,
                         chip=None) -> dict:
    """Price a serving_tick_ledger or train_step_ledger against a chip
    roofline: per phase, the bound time is max(flops/peak, bytes/bw)
    and the binding side names itself; the attribution column is each
    phase's share of the summed bound time. Phases carrying
    `channel: "ici"` (the train ledger's collective phases) price their
    bytes against the interconnect bandwidth instead of HBM. `chip`
    defaults to parallel.planner.ChipSpec (the same numbers
    plan_serving_tp / plan_train price with).

    Train ledgers additionally report `predicted_step_ms` (the summed
    per-chip bound time) and `peak_mfu` — the MFU ceiling of the plan:
    useful model FLOPs per chip (ledger `model_flops` / n_devices) over
    predicted time, as a fraction of `peak_flops`. That ceiling is what
    the measured `train.mfu` gauge is chased against."""
    if peak_flops is None or hbm_bw is None or ici_bw is None:
        from .parallel.planner import ChipSpec
        chip = chip or ChipSpec()
        peak_flops = peak_flops or chip.peak_flops
        hbm_bw = hbm_bw or chip.hbm_bw
        ici_bw = ici_bw or chip.ici_bw
    per_phase = {}
    for name, p in ledger["phases"].items():
        bw = ici_bw if p.get("channel") == "ici" else hbm_bw
        t_c = p["flops"] / peak_flops
        t_b = p["bytes"] / bw
        per_phase[name] = {
            "flops": p["flops"], "bytes": p["bytes"],
            "bound_s": max(t_c, t_b),
            "bound": "compute" if t_c >= t_b else (
                "ici" if p.get("channel") == "ici" else "bandwidth")}
    total_s = sum(p["bound_s"] for p in per_phase.values())
    for p in per_phase.values():
        p["share"] = round(p["bound_s"] / total_s, 4) if total_s else 0.0
    out = {"per_phase": per_phase, "roofline_s": total_s,
           "peak_flops": peak_flops, "hbm_bw": hbm_bw, "ici_bw": ici_bw}
    model_flops = ledger.get("model_flops")
    if model_flops:
        n_dev = (ledger.get("config") or {}).get("n_devices", 1)
        out["predicted_step_ms"] = total_s * 1e3
        out["peak_mfu"] = round(
            model_flops / n_dev / total_s / peak_flops, 6) if total_s \
            else None
    return out


# --------------------------------------------------------------------
# train-step ledger (tools/train_attrib.py's pricing half)
# --------------------------------------------------------------------
def train_flops_per_token(n_params: int, num_layers: int,
                          hidden_size: int, seq: int) -> float:
    """ONE home for the train-step MFU accounting: 6N matmul FLOPs per
    token (fwd+bwd) plus the attention score/context matmul term.
    bench.py, the plan3d rung (tools/bench_plan3d.py) and the telemetry
    `train.mfu` gauge all price against THIS formula, so their MFU rows
    stay comparable — adjust it here and every consumer moves
    together."""
    return 6.0 * n_params + 12.0 * num_layers * hidden_size * seq


# fraction of the FORWARD flops recomputed in the backward, by remat
# policy (mirrors parallel/planner._estimate's remat_extra table)
_REMAT_RECOMPUTE = {"full": 1.0 / 3.0, "dots": 0.15, "dots_flash": 0.1,
                    "offload_dots": 0.2, "all_but_mlp": 0.12,
                    "none": 0.0}


def _plan_degrees(plan) -> dict:
    """Normalize a plan argument — parallel.planner.TrainPlan, Plan,
    a {axis: degree} dict, or None (single device) — to the 3D/4D
    degrees the train ledger prices (+ `mb`, the pp microbatch count,
    defaulting to 2·pp when the plan carries none)."""
    if plan is None:
        return {"dp": 1, "fsdp": 1, "tp": 1, "pp": 1, "mb": 1,
                "overlap": False}
    def _mb(pp: int, raw) -> int:
        # a pp>1 plan must microbatch (plan_train never emits mb<2);
        # mb<=1 therefore means "the plan carries no real count"
        # (TrainPlan.microbatches and the Plan dataclass both default
        # to 1) — fall back to the documented 2·pp
        raw = int(raw or 0)
        if pp <= 1:
            return 1
        return raw if raw > 1 else 2 * pp

    if hasattr(plan, "axes"):                      # TrainPlan
        axes = dict(plan.axes)
        deg = {"dp": int(axes.get("dp", 1)),
               "fsdp": int(axes.get("fsdp", 1)),
               "tp": int(axes.get("tp", axes.get("mp", 1))),
               "pp": int(axes.get("pp", 1))}
        deg["mb"] = _mb(deg["pp"], getattr(plan, "microbatches", 0))
        deg["overlap"] = bool(getattr(plan, "overlap", False))
        return deg
    if hasattr(plan, "dp"):                        # priced Plan row
        pp = int(getattr(plan, "pp", 1))
        return {"dp": int(plan.dp), "fsdp": int(plan.fsdp),
                "tp": int(plan.mp), "pp": pp,
                "mb": _mb(pp, getattr(plan, "microbatches", 0)),
                "overlap": bool(getattr(plan, "overlap", False))}
    axes = dict(plan)
    pp = int(axes.get("pp", 1))
    return {"dp": int(axes.get("dp", 1)),
            "fsdp": int(axes.get("fsdp", 1)),
            "tp": int(axes.get("tp", axes.get("mp", 1))),
            "pp": pp,
            "mb": _mb(pp, axes.get("microbatches", 0)),
            "overlap": bool(axes.get("overlap", False))}


def train_step_ledger(cfg, family: str = "gpt", plan=None,
                      global_batch: int = 8, seq: int = 0,
                      remat=None, amp: bool = False,
                      dtype_bytes: int = 0) -> dict:
    """Per-chip, per-phase FLOPs/bytes for ONE planned train step.

    The serving ledger's design carried to training: closed-form over
    the model dims (cost_analysis undercounts the layer scan), split
    into the phases an operator can act on, and priced for the work
    each CHIP dispatches under the plan's dp×fsdp×tp degrees — the
    batch shards over dp×fsdp (`tok_local`), the head/ffn dims over tp,
    the optimizer state over fsdp×tp, and fsdp's gathered weights still
    STREAM full-size per tp shard (ZeRO shards storage, not compute).
    Phases:

    - fwd_matmul:    2·P_layer FLOPs/token over the stacked block
      matmuls (_family_dims mats); bytes = one weight stream per step
      in the compute dtype;
    - fwd_attention: QK^T + PV (4·D·S per token per layer, heads
      folded — the planner's non-causal form);
    - bwd:           2x the forward (dgrad + wgrad), weight stream
      re-read twice;
    - remat:         the recompute fraction of the forward by policy
      (_REMAT_RECOMPUTE) as its OWN phase — recompute adds FLOPs, not
      bytes, which is the whole point of remat and a pinned test
      property;
    - optimizer:     the fused AdamW update over this chip's param
      shard (f32 master math, ~12 FLOPs/elem; +2 under `amp` for the
      master-cast + scale epilogue); bytes = read p/m/v/grad + write
      p/m/v, all f32;
    - head_loss:     LM head fwd+bwd (vocab-parallel over tp) + the
      fused-CE logit stream (f32, two passes: lse + target gather);
    - coll_tp / coll_dp / coll_fsdp / coll_pp: one phase PER MESH
      AXIS, bytes from the planner's exact formulas (_ring_factor
      model: tp = 4 activation all-reduces per layer, dp = one grad
      all-reduce of the f32 shard, fsdp = ~3 all-gather-sized moves,
      pp = boundary activations each way per microbatch), `channel:
      "ici"` so roofline_attribution prices them against
      ChipSpec.ici_bw. Degree-1 axes price to zero.
    - pp_bubble (pp>1 only): the 1F1B schedule's (pp-1)/m idle slots
      as idle-equivalent FLOPs of the pipelined phases — zero bytes,
      the schedule burns time, not bandwidth. The per-chip stacked-
      block phases divide by pp (each chip runs its L/pp stage chunk)
      while head_loss stays undivided (the manual step computes the
      vocab-parallel head on every pp rank — see
      parallel/pipeline_train.py).

    `remat` overrides the config's policy (True/False or a policy
    name); `dtype_bytes` is the compute/activation width (default 2
    under `amp`, else the cfg dtype's width, else 4). `model_flops`
    carries the 6N useful-work numerator (train_flops_per_token ·
    global tokens) for the MFU columns downstream."""
    dims = _family_dims(cfg, family)
    D, L, V, F = dims["D"], dims["L"], dims["V"], dims["F"]
    S = int(seq or cfg.max_seq_len)
    deg = _plan_degrees(plan)
    dp, fsdp, tp = deg["dp"], deg["fsdp"], deg["tp"]
    pp, mb = deg["pp"], deg["mb"]
    n_devices = dp * fsdp * tp * pp
    if remat is None:
        policy = (getattr(cfg, "remat_policy", "full") or "full") \
            if getattr(cfg, "remat", False) else "none"
    elif isinstance(remat, str):
        policy = remat
    else:
        policy = ((getattr(cfg, "remat_policy", "full") or "full")
                  if remat else "none")
    if policy not in _REMAT_RECOMPUTE:
        raise ValueError(f"unknown remat policy {policy!r} "
                         f"({sorted(_REMAT_RECOMPUTE)})")
    if not dtype_bytes:
        dtype_bytes = 2 if amp else jnp_dtype_bytes(
            getattr(cfg, "dtype", None))

    tokens = float(global_batch) * S
    # integer clamp mirrors planner._estimate's b_local exactly — a
    # non-divisible or oversharded batch must price the same tokens the
    # planner (and the padded execution) pays, not a fractional row
    tok_local = float(max(int(global_batch) // (dp * fsdp), 1) * S)
    # total params: stacked blocks + embeddings (wte + wpe) — matches
    # planner.ModelSpec.total_params so the collective cross-check is
    # exact
    n_params = (dims["layer_params"] * L
                + (V + int(cfg.max_seq_len)) * D)
    # per-chip stacked-block work: the layer stack shards over tp AND
    # (pp>1) over the stage axis — each chip holds and streams L/pp
    # layers' weights and computes L/pp layers' matmuls per microbatch
    w_stream = dims["layer_params"] * L * dtype_bytes / (tp * pp)

    fwd_matmul = {
        "flops": 2.0 * dims["layer_params"] * L * tok_local / (tp * pp),
        "bytes": w_stream,
    }
    fwd_attention = {
        "flops": 4.0 * D * S * L * tok_local / (tp * pp),
        "bytes": 0.0,
    }
    fwd_flops = fwd_matmul["flops"] + fwd_attention["flops"]
    bwd = {"flops": 2.0 * fwd_flops, "bytes": 2.0 * w_stream}
    remat_phase = {"flops": _REMAT_RECOMPUTE[policy] * fwd_flops,
                   "bytes": 0.0}
    # pipeline bubble as its OWN phase (pp>1 only): (pp-1)/m of the
    # pipelined compute is idle-equivalent slots — the planner's
    # compute_s multiplier, broken out so the attribution table shows
    # the schedule's cost next to the work (flops, no bytes: the
    # bubble burns time, not bandwidth)
    bubble_phase = {
        "flops": ((pp - 1) / max(mb, 1)
                  * (fwd_flops + bwd["flops"] + remat_phase["flops"])
                  if pp > 1 else 0.0),
        "bytes": 0.0,
    }
    opt_elems = n_params / (tp * fsdp * pp)
    optimizer = {
        "flops": (14.0 if amp else 12.0) * opt_elems,
        "bytes": 28.0 * opt_elems,      # r p/m/v/grad + w p/m/v, f32
    }
    head_loss = {
        "flops": 3.0 * 2.0 * D * V * tok_local / tp,
        "bytes": (3.0 * D * V * dtype_bytes + 2.0 * tok_local * V * 4.0)
                 / tp,
    }
    # ---- collective phases (planner._estimate formulas, per chip) ----
    from .parallel.planner import _ring_factor
    coll_tp = {
        "flops": 0.0, "channel": "ici",
        "bytes": (_ring_factor(tp) * 4.0 * L * tok_local * D
                  * dtype_bytes if tp > 1 else 0.0),
    }
    coll_dp = {
        "flops": 0.0, "channel": "ici",
        "bytes": _ring_factor(dp) * (n_params / (tp * fsdp * pp)) * 4.0,
    }
    # overlap (plan.overlap): the double-buffered ZeRO-3 gather hides
    # all but FSDP_OVERLAP_EXPOSED of the fsdp volume behind layer
    # compute — the SAME constant planner._estimate discounts with, so
    # tools/train_attrib's ledger shares and the planner's priced
    # breakdown agree phase for phase
    from .parallel.planner import FSDP_OVERLAP_EXPOSED
    fsdp_exposed = FSDP_OVERLAP_EXPOSED if deg.get("overlap") else 1.0
    coll_fsdp = {
        "flops": 0.0, "channel": "ici",
        "bytes": (3.0 * (fsdp - 1) / fsdp * (n_params / (tp * pp))
                  * dtype_bytes * fsdp_exposed if fsdp > 1 else 0.0),
    }
    # pp: boundary activations each way per microbatch — the planner's
    # pp_bytes formula exactly (2·m·(tok_local/m)·D·(pp-1)/pp; the
    # microbatch count cancels out of the volume, not the bubble)
    coll_pp = {
        "flops": 0.0, "channel": "ici",
        "bytes": (2.0 * tok_local * D * dtype_bytes * (pp - 1) / pp
                  if pp > 1 else 0.0),
    }
    phases = {"fwd_matmul": fwd_matmul, "fwd_attention": fwd_attention,
              "bwd": bwd, "remat": remat_phase,
              "pp_bubble": bubble_phase, "optimizer": optimizer,
              "head_loss": head_loss, "coll_tp": coll_tp,
              "coll_dp": coll_dp, "coll_fsdp": coll_fsdp,
              "coll_pp": coll_pp}
    total = {
        "flops": sum(p["flops"] for p in phases.values()),
        "bytes": sum(p["bytes"] for p in phases.values()
                     if p.get("channel") != "ici"),
        "coll_bytes": sum(p["bytes"] for p in phases.values()
                          if p.get("channel") == "ici"),
    }
    return {
        "phases": phases, "total": total,
        "model_flops": train_flops_per_token(n_params, L, D, S) * tokens,
        "tokens": tokens,
        "config": {"family": family, "plan": dict(deg),
                   "n_devices": n_devices, "global_batch": global_batch,
                   "seq": S, "remat": policy, "amp": bool(amp),
                   "dtype_bytes": dtype_bytes, "n_params": n_params}}


# --------------------------------------------------------------------
# memory ledgers (profiler/mem_audit.py's analytical half)
# --------------------------------------------------------------------
def train_memory_ledger(cfg, plan=None, global_batch: int = 8,
                        seq: int = 0) -> dict:
    """Per-chip HBM bytes for ONE planned train step, attributed to
    named components.

    THE one home of the planner's HBM model: parallel/planner._estimate
    consumes `total` for its mem_bytes/fits gate (the cross-check test
    pins the equality), and profiler/mem_audit diffs the same total
    against XLA's compiled accounting (`compiled.memory_analysis()`) so
    estimate drift becomes a named finding instead of a silent mis-gate.
    Components:

    - params / grads / adam_m / adam_v: the f32 master state, each
      4 bytes/elem over this chip's tp×pp×fsdp param shard (the
      planner's `state_bytes = shard_params*16`, split four ways);
    - activations: the remat residual / activation working set —
      _ACT_BUFFERS[policy] residual-sized buffers per local layer
      (L/pp), sharded over tp under sequence parallelism;
    - logits: the f32 logits working set, vocab-parallel over tp and
      divided by the microbatch count (pp runs one microbatch's head
      at a time);
    - overlap_prefetch: plan.overlap's double-buffered ZeRO-3 gather
      holds two gathered layers' worth of bf16 weights in flight
      (zero when overlap is off or fsdp == 1 — the buffer only exists
      when there is a gather to hide).

    `cfg` is a model config or a planner.ModelSpec; `plan` anything
    _plan_degrees takes. `seq` defaults to the spec's sequence length
    (what _estimate prices)."""
    from .parallel.planner import _ACT_BUFFERS, _coerce_spec
    spec = _coerce_spec(cfg)
    deg = _plan_degrees(plan)
    dp, fsdp, tp, pp = deg["dp"], deg["fsdp"], deg["tp"], deg["pp"]
    # the plan's OWN microbatch count when it carries one (enumerate_
    # plans clamps mb to the local batch, possibly down to 1 — the
    # ledger must price the same logits chunk _estimate always did,
    # not _plan_degrees' 2·pp fallback for count-less dict plans)
    raw_mb = int(getattr(plan, "microbatches", 0) or 0) \
        if plan is not None else 0
    mb = raw_mb if raw_mb >= 1 else deg["mb"]
    L, D = spec.num_layers, spec.hidden_size
    V = spec.vocab_size
    S = int(seq or spec.seq_len)
    b_local = max(int(global_batch) // (dp * fsdp), 1)
    tok_local = b_local * S
    abytes = spec.act_bytes_per_elem
    shard_params = spec.total_params / (tp * pp * fsdp)
    state_each = shard_params * 4.0              # f32, one of p/g/m/v
    seq_shard = tp if (spec.sequence_parallel and tp > 1) else 1
    act_bytes = (_ACT_BUFFERS.get(spec.remat_policy, 2.0)
                 * (L / pp) * tok_local * D * abytes / seq_shard)
    logit_bytes = tok_local * V * 4.0 / tp / max(mb, 1)
    prefetch = (2.0 * (spec.block_params / L) * abytes
                if deg.get("overlap") and fsdp > 1 else 0.0)
    components = {
        "params": state_each, "grads": state_each,
        "adam_m": state_each, "adam_v": state_each,
        "activations": act_bytes, "logits": logit_bytes,
        "overlap_prefetch": prefetch,
    }
    # summed in the planner's historical order (state first) so the
    # non-overlap total is bit-identical to the pre-ledger _estimate
    total = state_each * 4.0 + act_bytes + logit_bytes + prefetch
    return {"components": components, "total": total,
            "config": {"plan": dict(deg, mb=mb),
                       "n_devices": dp * fsdp * tp * pp,
                       "global_batch": int(global_batch), "seq": S,
                       "remat": spec.remat_policy,
                       "act_bytes_per_elem": abytes,
                       "n_params": spec.total_params}}


def serving_memory_ledger(cfg, family: str = "gpt",
                          layout: str = "dense", quant: str = "off",
                          num_slots: int = 8, max_len: int = 0,
                          page_size: int = 16, num_pages: int = 0,
                          cache_bytes_per_elem: int = 2,
                          dtype_bytes: int = 0, tp: int = 1,
                          host_kv_bytes: int = 0) -> dict:
    """Per-chip HBM bytes for a serving-engine configuration,
    attributed to named components — the serving sibling of
    train_memory_ledger and the formula home for
    parallel/planner.plan_serving_tp's memory gate (its dense-fp
    envelope is exactly `weights + kv_pool` here; the cross-check test
    pins it). Components:

    - weights: the fp parameter payload (every param for quant="off";
      just the embeddings for "int8" — the block matmul leaves and the
      tied LM head move to the quantized pair below, `wte` stays fp
      for the gather — quantization/serving.py);
    - weights_quant / weights_quant_scales: the int8 payloads
      (L stacked layers + the transposed head copy) and their f32
      per-output-channel scales — the "quantized pairs";
    - kv_pool_device: dense — k+v for every slot at full max_len;
      paged — the page pool ([L, num_pages, page_size] k+v, engine
      default num_slots*max_pages + 1 pages) plus the i32 page table.
      DEVICE HBM only: pages spilled to the host tier are priced in
      kv_pool_host, never here (spilled pages are NOT device-resident);
    - kv_pool_host: the host-tier KV bytes (inference/host_kv.py) —
      host RAM, so it is EXCLUDED from `total`/`unsharded` (which are
      device-HBM envelopes) and reported separately as `host_total`;
      the host copy is whole (not tp-sharded);
    - decode_scratch: the per-tick working set — f32 logits for every
      scored row plus the hidden/residual activations.

    Sharding: weights and the KV pool shard over `tp` (head-sharded
    attention, vocab/ffn-sharded matmuls) — `total` is per chip,
    `unsharded` the tp=1 envelope. `dtype_bytes` is the serving
    compute dtype width (default: the cfg dtype via jnp_dtype_bytes)."""
    dims = _family_dims(cfg, family)
    if layout not in ("dense", "paged"):
        raise ValueError(f"layout {layout!r} (dense|paged)")
    if quant not in ("off", "int8"):
        raise ValueError(f"quant {quant!r} (off|int8)")
    D, L, V, KV, hd = (dims["D"], dims["L"], dims["V"], dims["KV"],
                       dims["hd"])
    embed_seq = int(getattr(cfg, "max_seq_len", 0)
                    or getattr(cfg, "seq_len", 0) or max_len)
    max_len = int(max_len or embed_seq)
    if not dtype_bytes:
        dtype_bytes = jnp_dtype_bytes(getattr(cfg, "dtype", None))
    n_params = dims["layer_params"] * L + (V + embed_seq) * D
    embed_params = (V + embed_seq) * D
    if quant == "int8":
        weights = float(embed_params * dtype_bytes)
        w_quant = float(dims["layer_params"] * L + D * V)
        w_scales = 4.0 * (dims["layer_out_features"] * L + V)
    else:
        weights = float(n_params * dtype_bytes)
        w_quant = w_scales = 0.0
    max_pages = -(-max_len // page_size)
    if layout == "paged":
        n_pages = int(num_pages or num_slots * max_pages + 1)
        kv_pool = (2.0 * L * n_pages * page_size * KV * hd
                   * cache_bytes_per_elem
                   + 4.0 * num_slots * max_pages)      # i32 page table
    else:
        n_pages = 0
        kv_pool = (2.0 * L * num_slots * max_len * KV * hd
                   * cache_bytes_per_elem)
    scratch = num_slots * (V * 4.0 + 2.0 * D * dtype_bytes)
    components = {"weights": weights, "weights_quant": w_quant,
                  "weights_quant_scales": w_scales,
                  "kv_pool_device": kv_pool,
                  "decode_scratch": scratch}
    unsharded = sum(components.values())
    tp = max(int(tp), 1)
    sharded = {k: v / tp for k, v in components.items()}
    # the host tier is host RAM: added AFTER the tp division (every
    # host holds its whole copy) and excluded from the device totals
    sharded["kv_pool_host"] = float(host_kv_bytes)
    return {"components": sharded,
            "total": unsharded / tp, "unsharded": unsharded,
            "host_total": float(host_kv_bytes),
            "config": {"family": family, "layout": layout,
                       "quant": quant, "num_slots": int(num_slots),
                       "max_len": max_len, "page_size": int(page_size),
                       "num_pages": n_pages, "tp": tp,
                       "cache_bytes_per_elem": cache_bytes_per_elem,
                       "dtype_bytes": dtype_bytes,
                       "n_params": n_params,
                       "host_kv_bytes": int(host_kv_bytes)}}


def jnp_dtype_bytes(dtype, default: int = 4) -> int:
    """Byte width of a jnp/np dtype-ish, without importing jax at module
    load (cost_model must stay import-light for the tools)."""
    if dtype is None:
        return default
    try:
        import numpy as np
        return int(np.dtype(dtype).itemsize)
    except Exception:
        return default


def rank_parallel_plans(model, n_devices, global_batch, **kw):
    """Rank hybrid-parallel assignments for a transformer spec — the
    consumer the reference's cost model exists to feed
    (auto_parallel/static/cost/base_cost.py pricing parallel_tuner.py
    candidates). Delegates to parallel.planner's analytical model
    (compute + collective volumes + pipeline bubble + HBM pruning);
    `model` is a models.gpt.GPTConfig or parallel.planner.ModelSpec.
    Returns plans sorted best-first."""
    from .parallel.planner import enumerate_plans
    return enumerate_plans(model, n_devices, global_batch, **kw)
