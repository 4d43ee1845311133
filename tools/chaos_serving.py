"""Serving chaos drill: the continuous-batching engine under faults.

The executable acceptance test for the serving SLO guardrails
(docs/serving.md "Robustness") — the serving sibling of
tools/chaos_drill.py, in-process because the engine is a single-host
runtime (no launcher/mesh in the loop). Every scenario drives the REAL
ServingEngine over a mixed-length workload with a declared fault
(paddle_tpu.testing.faults serving kinds) and asserts the three
guardrail invariants:

1. every submitted request ends in EXACTLY ONE terminal finish_reason
   (TERMINAL_REASONS — no request in limbo, ever);
2. surviving streams are BIT-IDENTICAL to the fault-free run, and
   early-terminated streams (poisoned/cancelled/timeout/evicted) are
   exact PREFIXES of it — per-request isolation inside the shared
   batch, the Orca/vLLM correctness requirement;
3. eventful faults leave a parseable flight-recorder dump, and the
   trace-count ceilings hold (decode <= 2; prefill <= 2*log2(max_len))
   — the guardrails cost no recompiles.

Scenarios:
  nan_logits@T:S   in-jit poisoned logit row -> only slot S's request
                   ends "poisoned"; co-batched survivors exact
  tick_stall@T:MS  host pull stalls mid-drill -> watchdog backoff
                   recovers, serving.retries > 0, streams exact
  prefill_raise@T  device call raises during admission -> slot rolled
                   back, retry succeeds, streams exact
  decode_raise@T   device call raises during the tick -> _dstate
                   resyncs from mirrors, retry re-runs idempotently
  queue_flood      max_queue overflow -> BackpressureError (reject) /
                   oldest evicted (shed_oldest); admitted streams exact
  cancel_deadline  mid-decode cancel + tick deadline -> "cancelled" /
                   "timeout", survivors exact

Speculative-decode scenarios (docs/serving.md "Speculative decoding"):
  spec_draft_nan@T:S nan injected into slot S's DRAFT logits on a spec
                   engine -> the slot DEGRADES to non-spec decode for
                   that tick (acceptance 0), is NEVER quarantined, and
                   every stream stays bit-identical to the non-spec
                   baseline; exactly-once + trace ceilings hold
  spec_nan_logits@T:S nan in the TARGET logits on a spec engine -> the
                   quarantine verdict still rides the emission matrix:
                   only slot S poisons, survivors exact

Quantized-engine scenario (weight-only int8 serving, docs/serving.md
"Quantized serving"):
  quant_nan_logits@T:S nan_logits on a quant="int8" engine -> only
                   slot S's request ends "poisoned", survivors are
                   bit-identical to the fault-free QUANT baseline
                   (the quant engine's own parity class), the
                   serving.quant_matmuls counter moved (the int8 path
                   actually served), exactly-once + trace ceilings

Router scenario (the replicated-engine router, inference/router.py;
docs/serving.md "Sharded serving & routing"):
  router_replica_death 2 engine replicas, one killed mid-decode ->
                   its un-terminal requests requeue and REPLAY on the
                   survivor; every request still resolves exactly
                   once, final streams are bit-identical to the
                   fault-free run (at-least-once delivery, exactly-
                   once resolution), the survivor holds its trace
                   ceilings, and the death leaves a flight dump

Fleet scenarios (autoscaling + live migration + preemption tolerance,
inference/autoscale.py; docs/serving.md "Autoscaling & live
migration"):
  autoscale_flood  a request flood on a 1-replica fleet under the
                   Autoscaler -> replicas scale out toward max, then
                   drain back to min when idle; every request resolves
                   exactly once, streams bit-identical, scale
                   decisions leave parseable flight dumps
  live_migration   kill a paged-KV replica mid-decode with migration
                   ON -> every live stream moves through a host KV
                   snapshot (ZERO re-prefill: the survivor's prefill
                   trace count does not move), zero replays, streams
                   bit-identical to the fault-free run
  serving_device_loss a tp=2 engine under EnginePreemptGuard loses a
                   device (replica_preempt fault) -> tp degrades via
                   the planner, the engine rebuilds on the survivor
                   mesh with live streams migrated in place, streams
                   stay bit-identical and the trace ceilings hold

Disaggregation scenarios (host-tier KV + prefill/decode role split,
inference/host_kv.py + router roles; docs/serving.md
"Disaggregation"):
  host_spill_flood shared-prefix families oversubscribe a tiny paged
                   pool on a host-tiered engine -> evicted registered
                   pages SPILL to host ndarrays and SWAP back in on
                   the next family hit (spills > 0, swapins > 0),
                   streams bit-identical to a tier-less twin, and the
                   memory ledger's kv_pool_host row tracks the tier's
                   live bytes
  prefill_role_death a roles=["prefill","decode"] fleet loses its
                   only prefill replica AFTER handoffs started -> new
                   submissions still admit (roles are placement
                   preferences, availability beats specialization:
                   the decode survivor picks up prefill duty), every
                   stream resolves "length"/"eos" bit-identical, and
                   the death leaves a router_replica_death flight dump

Paged-KV scenarios (the block-pool layout, docs/serving.md "Paged KV
cache"):
  paged_pool_flood more demand than pages -> later requests WAIT for
                   pages (never a wedged slot), every stream completes
                   bit-identical, zero pages/reservations leak
  paged_nan_poison nan_logits on the paged engine -> the poisoned
                   slot's pages free (pages_in_use drains to 0),
                   survivors exact
  cow_raise@T      the copy-on-write page copy raises -> admission
                   rolls back (shared refcounts released), retry
                   succeeds, the sharer's stream stays exact

Overload-resilience scenarios (multi-tenant admission + brownout +
the request journal, inference/admission.py / brownout.py /
journal.py; docs/serving.md "Tenancy, brownout & durability"):
  tenant_flood     a rate-limited tenant floods (quota_flood fault:
                   the router self-injects low-priority flood
                   submissions mid-drill) -> the flood is quota-
                   rejected past its burst, every paying-tenant
                   stream completes bit-identical, and every
                   rejection resolves terminally (no limbo, no trace
                   leak)
  brownout_ladder  a sustained SLO burn on an injected clock drives
                   the full 0 -> 3 escalation (spec drafts off,
                   lowest class suspended to host KV, oldest pending
                   shed) and the clear drives 3 -> 0 level-by-level;
                   streams stay bit-identical (the ladder degrades
                   capacity, never correctness) and every transition
                   leaves a brownout_escalate / brownout_recover
                   flight dump
  process_crash_replay a subprocess builds a JOURNALED router, is
                   SIGKILLed mid-decode (sigkill fault: a real
                   os.kill, no flush, no atexit), and the parent
                   recovers a fresh router over the same journal_dir
                   -> every journal-accepted request reaches EXACTLY
                   one terminal event across both processes
                   (at-least-once prefill, exactly-once resolution),
                   and every replayed greedy stream is bit-identical
                   to the fault-free run

Observability requirements (every scenario, the PR-3 "parseable black
box" pattern extended to serving): a parseable serving-telemetry JSONL
with >= 1 serving_tick record (profiler/serving_telemetry — engines in
scenarios stream to <scenario>/telemetry.jsonl) and >= 1 COMPLETE
request trace (queue + prefill + decode + exactly one terminal span,
profiler/tracing). The nan_logits and router_replica_death scenarios
additionally feed their outcome into an SLO burn-rate monitor
(profiler/slo) with a tight error budget and require the alert to fire
AND leave a parseable slo_burn_alert flight dump.

Usage:
  python tools/chaos_serving.py            # the full drill
  python tools/chaos_serving.py --quick    # smaller workload (CI)
  python tools/chaos_serving.py --bench    # guardrail overhead JSON
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# a CPU-only tool: it pins the CPU before jax initializes; the drill's
# assertions are platform-free.
# 4 virtual devices: serving_device_loss needs a tp-sharded mesh to
# preempt; the single-engine scenarios just run on device 0.
from paddle_tpu.device import pin_cpu            # noqa: E402
pin_cpu(4)

import numpy as np                               # noqa: E402
import jax                                       # noqa: E402
import jax.numpy as jnp                          # noqa: E402


def _log(msg):
    print(f"[chaos_serving] {msg}", flush=True)


# ------------------------------------------------------------- fixture
def build_model(hidden=32, layers=2, vocab=64):
    from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=max(hidden // 16, 1),
                    ffn_hidden=2 * hidden, max_seq_len=128,
                    sequence_parallel=False, remat=False,
                    dtype=jnp.float32)
    return init_gpt_params(cfg, jax.random.PRNGKey(0)), cfg


def build_workload(n, lo, hi, vocab, seed=0):
    rng = np.random.RandomState(seed)
    lens = rng.randint(lo, hi + 1, n)
    return [rng.randint(0, vocab, L).astype(np.int32) for L in lens]


# per-scenario observability context: every engine a scenario builds
# streams its serving_tick JSONL into the scenario dir and emits
# request-scoped traces, and the drill REQUIRES both to be present and
# parseable (the PR-3 "chaos requires a parseable black box" pattern
# extended to serving telemetry + traces)
_SCEN = {"tele": None, "engines": []}


def make_engine(params, cfg, max_len, **kw):
    from paddle_tpu.inference.serving import ServingEngine
    kw.setdefault("num_slots", 3)
    kw.setdefault("telemetry_jsonl", _SCEN["tele"])
    kw.setdefault("tracing", True)
    eng = ServingEngine(params, cfg, family="gpt", max_len=max_len, **kw)
    _SCEN["engines"].append(eng)
    return eng


def make_router(params, cfg, max_len, **kw):
    from paddle_tpu.inference.router import create_router
    router = create_router(params, cfg, max_len=max_len, tracing=True,
                           telemetry_jsonl=_SCEN["tele"], **kw)
    for rep in router.replicas:
        _SCEN["engines"].append(rep.eng)
    return router


# ------------------------------------------------------------ checking
def check_terminal(reqs):
    """Invariant 1: exactly-once terminal resolution."""
    from paddle_tpu.inference.serving import TERMINAL_REASONS
    for r in reqs:
        if not r.done:
            return f"request {r.id} not done (limbo)"
        if r.finish_reason not in TERMINAL_REASONS:
            return (f"request {r.id} finish_reason "
                    f"{r.finish_reason!r} not terminal")
        if r.slot is not None:
            return f"request {r.id} resolved but still owns slot {r.slot}"
    return None

def check_streams(reqs, baseline, full_reasons=("length", "eos")):
    """Invariant 2: survivors bit-identical, early exits exact
    prefixes. `baseline[i]` is request i's fault-free stream."""
    for i, r in enumerate(reqs):
        got = np.asarray(r.tokens, np.int32)
        want = baseline[i]
        if r.finish_reason in full_reasons:
            if not np.array_equal(got, want):
                return (f"request {i} ({r.finish_reason}) diverged: "
                        f"{got.tolist()} vs {want.tolist()}")
        else:
            if not np.array_equal(got, want[:len(got)]):
                return (f"request {i} ({r.finish_reason}) is not a "
                        f"prefix of its fault-free stream: "
                        f"{got.tolist()} vs {want.tolist()}")
    return None


def check_traces(eng):
    """Invariant 3b: guardrails cost no recompiles."""
    dec, pre = eng.trace_counts()
    ceiling = 2 * max(int(math.log2(eng.max_len)), 1)
    if dec > 2:
        return f"decode traces {dec} > 2"
    if pre > ceiling:
        return f"prefill traces {pre} > {ceiling}"
    return None


def check_flight(fdir, want_reason=None):
    """Invariant 3a: eventful faults leave a parseable black box.
    `want_reason` additionally requires a dump whose reason matches
    (e.g. the SLO monitor's "slo_burn_alert")."""
    from paddle_tpu.profiler.flight_recorder import load_dump
    names = sorted(f for f in (os.listdir(fdir) if os.path.isdir(fdir)
                               else []) if f.endswith(".json"))
    if not names:
        return f"no flight dump under {fdir}"
    reasons = set()
    for name in names:
        try:
            doc = load_dump(os.path.join(fdir, name))
        except (OSError, ValueError) as e:
            return f"flight dump {name} unparseable: {e}"
        if "monitor" not in doc:
            return f"flight dump {name}: no monitor snapshot"
        reasons.add(doc.get("reason"))
    if want_reason is not None and want_reason not in reasons:
        return (f"no {want_reason!r} flight dump (reasons: "
                f"{sorted(r for r in reasons if r)})")
    return None


def check_telemetry(tele_path):
    """Observability invariant A: every scenario leaves a parseable
    serving-telemetry JSONL with >= 1 serving_tick record (router
    scenarios fan out to <path>.r<i> — any replica's file counts)."""
    import glob
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from telemetry_report import summarize
    paths = sorted(glob.glob(tele_path + "*"))
    if not paths:
        return f"no serving-telemetry JSONL at {tele_path}*"
    ticks = 0
    for p in paths:
        try:
            doc = summarize(p)
        except Exception as e:                     # noqa: BLE001
            return f"telemetry JSONL {p} unparseable: {e}"
        ticks += (doc.get("serving_ticks") or {}).get("ticks", 0)
    if ticks == 0:
        return f"no serving_tick records under {tele_path}*"
    return None


def check_request_trace():
    """Observability invariant B: >= 1 COMPLETE request trace — a
    span tree with queue + prefill + decode spans and EXACTLY one
    terminal span (profiler/tracing; the scenario cleared the tracer
    on entry, so these traces are its own)."""
    from paddle_tpu.profiler import tracing
    tr = tracing.tracer()
    seen = 0
    for tid in tr.trace_ids():
        spans = tr.spans(tid)
        names = {s.name for s in spans}
        terms = [s for s in spans if s.kind == "terminal"]
        if len(terms) > 1:
            return f"trace {tid} has {len(terms)} terminal spans"
        if len(terms) == 1 and {"queue", "prefill", "decode"} <= names:
            seen += 1
    if not seen:
        return ("no complete request trace "
                "(queue+prefill+decode+terminal)")
    return None


def check_burn_alert(fdir, stream, bad, total):
    """Observability invariant C (nan_logits / router_replica_death):
    feeding the scenario's outcome into an SLO burn-rate monitor with
    a tight error budget fires an alert, and the alert leaves a
    parseable slo_burn_alert flight dump."""
    from paddle_tpu.profiler.slo import BurnRateMonitor, Objective
    mon = BurnRateMonitor(
        [Objective(f"{stream}_rate", stream, "event", budget=0.001)],
        pairs=((60.0, 5.0),), cooldown_s=0.0)
    mon.observe_events(stream, bad=bad, total=total)
    alerts = mon.check()
    if not alerts:
        return (f"burn-rate monitor fired no alert for {bad}/{total} "
                f"bad {stream} events at budget 0.001")
    return check_flight(fdir, want_reason="slo_burn_alert")


# ------------------------------------------------------------ the drill
def run_drill(quick: bool = False, keep_root: bool = False) -> int:
    from paddle_tpu.inference.serving import BackpressureError
    from paddle_tpu.profiler import flight_recorder, monitor
    from paddle_tpu.testing import faults

    t_start = time.time()
    n_req, gen = (6, 6) if quick else (10, 10)
    params, cfg = build_model()
    max_len = 64
    prompts = build_workload(n_req, 3, 20, cfg.vocab_size)
    root = tempfile.mkdtemp(prefix="chaos_serving_")
    failures = []

    # fault-free baseline: per-request streams (bit-parity makes these
    # independent of pool size / join order, which is exactly what the
    # scenarios below re-assert under faults)
    eng = make_engine(params, cfg, max_len)
    base_reqs = [eng.submit(p, gen) for p in prompts]
    eng.drain()
    err = check_terminal(base_reqs) or check_traces(eng)
    if err:
        _log(f"baseline FAILED: {err}")
        return 2
    baseline = [np.asarray(r.tokens, np.int32) for r in base_reqs]
    _log(f"baseline: {n_req} requests x {gen} tokens ok")

    rec = flight_recorder.recorder()

    def scenario(name, body, spec=None, want_flight=True):
        from paddle_tpu.profiler import tracing
        sdir = os.path.join(root, name)
        fdir = os.path.join(sdir, "flight")
        os.makedirs(fdir, exist_ok=True)
        rec.clear()
        rec.set_dir(fdir)
        tracing.clear()
        _SCEN["tele"] = os.path.join(sdir, "telemetry.jsonl")
        _SCEN["engines"] = []
        if spec:
            faults.install(spec, once_dir=os.path.join(sdir, "once"))
        t0 = time.time()
        try:
            err = body()
        finally:
            if spec:
                faults.uninstall()
            for eng in _SCEN["engines"]:
                try:
                    eng.flush_telemetry(timeout=10)
                except Exception:                  # noqa: BLE001
                    pass
            tele_path, _SCEN["tele"] = _SCEN["tele"], None
            _SCEN["engines"] = []
            rec.set_dir(None)
        if err is None and want_flight:
            err = check_flight(fdir)
        # every scenario must leave a parseable serving-telemetry
        # JSONL and >= 1 complete request trace (the PR-3 black-box
        # requirement extended to the serving observability layer)
        if err is None:
            err = check_telemetry(tele_path) or check_request_trace()
        tag = "FAIL" if err else "ok"
        _log(f"{name:<28} {tag}  ({time.time() - t0:.1f}s)")
        if err:
            failures.append(f"{name}: {err}")

    # --- nan_logits: poisoned-slot quarantine isolation -------------
    def nan_body():
        eng = make_engine(params, cfg, max_len)
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.drain()
        reasons = [r.finish_reason for r in reqs]
        if reasons.count("poisoned") != 1:
            return f"expected exactly one poisoned request: {reasons}"
        err = (check_terminal(reqs) or check_streams(reqs, baseline)
               or check_traces(eng))
        if err:
            return err
        # the poisoned finish burns the error budget: the SLO monitor
        # must alert and leave a parseable slo_burn_alert flight dump
        fdir = os.path.join(root, "nan_logits@2:1", "flight")
        return check_burn_alert(fdir, "errors",
                                reasons.count("poisoned"), len(reqs))
    scenario("nan_logits@2:1", nan_body, spec="nan_logits@2:1")

    # --- tick_stall: watchdog budget/backoff recovery ----------------
    def stall_body():
        r0 = monitor.counter("serving.retries").value
        eng = make_engine(params, cfg, max_len, watchdog_timeout=0.1,
                          retries=3, backoff_base=0.2)
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.drain()
        if monitor.counter("serving.retries").value <= r0:
            return "watchdog never retried (stall not exercised)"
        return (check_terminal(reqs) or check_streams(reqs, baseline)
                or check_traces(eng))
    scenario("tick_stall@2:400", stall_body, spec="tick_stall@2:400")

    # --- raise-mid-prefill / raise-mid-decode: self-healing tick -----
    def raise_body(spec_kind):
        def body():
            f0 = monitor.counter("serving.faults").value
            eng = make_engine(params, cfg, max_len)
            reqs = [eng.submit(p, gen) for p in prompts]
            eng.drain()
            if monitor.counter("serving.faults").value <= f0:
                return "fault never fired"
            err = check_terminal(reqs) or check_traces(eng)
            if err:
                return err
            # the retry makes the fault fully transparent: EVERY
            # stream completes and matches
            if any(r.finish_reason != "length" for r in reqs):
                return ("retry was not transparent: "
                        f"{[r.finish_reason for r in reqs]}")
            return check_streams(reqs, baseline)
        return body
    scenario("prefill_raise@0", raise_body("prefill"),
             spec="prefill_raise@0")
    scenario("decode_raise@2", raise_body("decode"),
             spec="decode_raise@2")

    # --- oom: forensics black box + transparent recovery -------------
    def oom_body():
        o0 = monitor.counter("serving.oom_forensics").value
        eng = make_engine(params, cfg, max_len)
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.drain()
        if monitor.counter("serving.oom_forensics").value <= o0:
            return "oom fault never fired (no forensics dump)"
        err = check_terminal(reqs) or check_traces(eng)
        if err:
            return err
        # the injected RESOURCE_EXHAUSTED rides the decode retry path,
        # so recovery is transparent (exactly-once fire + bit-exact
        # streams) — the forensics dump is pure observation
        if any(r.finish_reason != "length" for r in reqs):
            return ("oom recovery was not transparent: "
                    f"{[r.finish_reason for r in reqs]}")
        err = check_streams(reqs, baseline)
        if err:
            return err
        # the black box itself: parseable, with a non-empty live-array
        # census AND a component-attributed ledger
        fdir = os.path.join(root, "oom@2", "flight")
        err = check_flight(fdir, want_reason="oom_forensics")
        if err:
            return err
        from paddle_tpu.profiler.flight_recorder import load_dump
        for name in sorted(os.listdir(fdir)):
            doc = load_dump(os.path.join(fdir, name))
            if doc.get("reason") != "oom_forensics":
                continue
            oom = (doc.get("config") or {}).get("oom_forensics") or {}
            if not oom.get("census"):
                return "oom_forensics dump has an empty census"
            led = oom.get("ledger") or {}
            if not led.get("components") or not led.get("total"):
                return "oom_forensics dump has an empty ledger"
            return None
        return "no oom_forensics dump under the scenario flight dir"
    scenario("oom@2", oom_body, spec="oom@2")

    # --- queue flood: backpressure under both policies ---------------
    def flood_reject():
        eng = make_engine(params, cfg, max_len, num_slots=2, max_queue=2)
        accepted, rejected = [], 0
        for i, p in enumerate(prompts):
            try:
                accepted.append((i, eng.submit(p, gen)))
            except BackpressureError as e:
                rejected += 1
                if e.queue_depth < 2:
                    return f"rejected at depth {e.queue_depth} < max_queue"
        if rejected == 0:
            return "queue flood never tripped backpressure"
        eng.drain()
        reqs = [r for _, r in accepted]
        err = check_terminal(reqs) or check_traces(eng)
        if err:
            return err
        for i, r in accepted:
            if not np.array_equal(np.asarray(r.tokens, np.int32),
                                  baseline[i]):
                return f"accepted request {i} diverged under flood"
        return None
    scenario("queue_flood_reject", flood_reject, want_flight=False)

    def flood_shed():
        eng = make_engine(params, cfg, max_len, num_slots=2, max_queue=2,
                          queue_policy="shed_oldest")
        reqs = [eng.submit(p, gen) for p in prompts]  # never raises
        eng.drain()
        err = check_terminal(reqs) or check_traces(eng)
        if err:
            return err
        shed = [r for r in reqs if r.finish_reason == "evicted"]
        if not shed:
            return "shed_oldest never shed"
        return check_streams(reqs, baseline)
    scenario("queue_flood_shed", flood_shed, want_flight=False)

    # --- paged KV: pool exhaustion under flood -----------------------
    def paged_flood():
        # ~3 requests' worth of pages for the whole flood: later
        # requests must WAIT for pages (head-of-line), admit as
        # earlier ones free, and complete bit-identical — never a
        # wedged slot, never a leaked page
        eng = make_engine(params, cfg, max_len, num_slots=4,
                          kv_layout="paged", page_size=8, num_pages=13)
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.drain()
        err = check_terminal(reqs) or check_traces(eng)
        if err:
            return err
        st = eng.pool_stats()
        if st["pages_in_use"] or st["pages_reserved"]:
            return f"pool leaked after flood: {st}"
        if any(r.finish_reason not in ("length", "eos") for r in reqs):
            return ("flood evicted instead of queueing: "
                    f"{[r.finish_reason for r in reqs]}")
        return check_streams(reqs, baseline)
    scenario("paged_pool_flood", paged_flood, want_flight=False)

    # --- paged KV: poisoned slot frees its pages ---------------------
    def paged_poison():
        eng = make_engine(params, cfg, max_len, kv_layout="paged",
                          page_size=8)
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.drain()
        reasons = [r.finish_reason for r in reqs]
        if reasons.count("poisoned") != 1:
            return f"expected exactly one poisoned request: {reasons}"
        st = eng.pool_stats()
        if st["pages_in_use"] or st["pages_reserved"]:
            return f"poisoned slot leaked pages: {st}"
        return (check_terminal(reqs) or check_streams(reqs, baseline)
                or check_traces(eng))
    scenario("paged_nan_poison@2:1", paged_poison, spec="nan_logits@2:1")

    # --- paged KV: COW page-copy fault -------------------------------
    # the dense reference runs OUTSIDE the fault window (its ticks
    # would consume the once-only fault marker)
    aligned = build_workload(1, 16, 16, cfg.vocab_size, seed=99)[0]
    aligned_want = make_engine(params, cfg, max_len).generate(
        [aligned], gen)[0]

    def cow_fault():
        want = aligned_want
        f0 = monitor.counter("serving.faults").value
        eng = make_engine(params, cfg, max_len, kv_layout="paged",
                          page_size=8)
        donor = eng.submit(aligned, gen)
        eng.drain()                  # donor registers its full pages
        sharer = eng.submit(aligned, gen)   # aligned full match -> COW
        eng.drain()
        if monitor.counter("serving.faults").value <= f0:
            return "cow fault never fired"
        err = check_terminal([donor, sharer]) or check_traces(eng)
        if err:
            return err
        if sharer.finish_reason != "length":
            return ("cow retry was not transparent: "
                    f"{sharer.finish_reason!r}")
        for r in (donor, sharer):
            if not np.array_equal(np.asarray(r.tokens, np.int32), want):
                return "stream diverged across the cow fault"
        st = eng.pool_stats()
        if st["pages_reserved"]:
            return f"cow fault leaked reservations: {st}"
        return None
    scenario("cow_raise@0", cow_fault, spec="cow_raise@0")

    # --- speculative decode: draft nan degrades, never quarantines ---
    def spec_draft_nan():
        eng = make_engine(params, cfg, max_len, spec_decode="spec",
                          gamma=3, draft_layers=cfg.num_layers)
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.drain()
        if any(r.finish_reason == "poisoned" for r in reqs):
            return ("draft nan quarantined the target stream: "
                    f"{[r.finish_reason for r in reqs]}")
        err = check_terminal(reqs) or check_traces(eng)
        if err:
            return err
        if any(r.finish_reason != "length" for r in reqs):
            return ("degrade was not transparent: "
                    f"{[r.finish_reason for r in reqs]}")
        # full-depth self-draft accepts everything EXCEPT the poisoned
        # tick — a clean acceptance ledger means the fault never bit
        if eng._spec_acc_total >= eng._spec_prop_total:
            return "draft fault never degraded acceptance"
        # streams equal the NON-SPEC baseline: speculation's bit-parity
        # AND the degrade in one assertion
        return check_streams(reqs, baseline)
    scenario("spec_draft_nan@2:1", spec_draft_nan,
             spec="draft_nan@2:1", want_flight=False)

    # --- speculative decode: target nan still quarantines exactly ----
    def spec_target_nan():
        eng = make_engine(params, cfg, max_len, spec_decode="spec",
                          gamma=3, draft_layers=cfg.num_layers)
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.drain()
        reasons = [r.finish_reason for r in reqs]
        if reasons.count("poisoned") != 1:
            return f"expected exactly one poisoned request: {reasons}"
        return (check_terminal(reqs) or check_streams(reqs, baseline)
                or check_traces(eng))
    scenario("spec_nan_logits@2:1", spec_target_nan,
             spec="nan_logits@2:1")

    # --- quantized engine: quarantine + exactly-once still hold ------
    # the quantized engine's streams are its OWN parity class (weight-
    # only dequant shifts logits vs fp by the recorded budget), so the
    # survivors compare against a fault-free QUANT baseline, not the
    # fp one — the guardrail claim is isolation, not fp equality
    quant_want = make_engine(params, cfg, max_len,
                             quant="int8").generate(prompts, gen)

    def quant_nan():
        from paddle_tpu.profiler import monitor
        q0 = monitor.counter("serving.quant_matmuls").value
        eng = make_engine(params, cfg, max_len, quant="int8")
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.drain()
        reasons = [r.finish_reason for r in reqs]
        if reasons.count("poisoned") != 1:
            return f"expected exactly one poisoned request: {reasons}"
        if monitor.counter("serving.quant_matmuls").value <= q0:
            return "quant_matmuls counter never moved (fp path served?)"
        return (check_terminal(reqs)
                or check_streams(reqs, quant_want)
                or check_traces(eng))
    scenario("quant_nan_logits@2:1", quant_nan, spec="nan_logits@2:1")

    # --- router: replica death mid-decode ----------------------------
    def replica_death():
        from paddle_tpu.inference.serving import TERMINAL_REASONS
        r0 = monitor.counter("serving.router.requeues").value
        router = make_router(params, cfg, max_len, replicas=2,
                             family="gpt", num_slots=3,
                             concurrent=False)     # deterministic drill
        reqs = [router.submit(p, gen) for p in prompts]
        for _ in range(3):
            router.step()                 # streams mid-decode on BOTH
        killed = router.kill_replica(0)
        if killed == 0:
            return "kill_replica(0) found nothing to requeue"
        if monitor.counter("serving.router.requeues").value <= r0:
            return "requeues counter never moved"
        router.drain()
        # invariant 1 on the OUTER requests (exactly-once terminal)
        for r in reqs:
            if not r.done:
                return f"request {r.id} not done (limbo)"
            if r.finish_reason not in TERMINAL_REASONS:
                return (f"request {r.id} finish_reason "
                        f"{r.finish_reason!r} not terminal")
            if r.slot is not None:
                return f"request {r.id} resolved but owns a slot"
        # migration semantics: every request COMPLETES (requeued ones
        # replay from scratch on the survivor) and final streams are
        # bit-identical to the fault-free run — at-least-once token
        # delivery, exactly-once resolution, exact final streams
        if any(r.finish_reason not in ("length", "eos") for r in reqs):
            return ("death was not transparent: "
                    f"{[r.finish_reason for r in reqs]}")
        if not any(r.requeues for r in reqs):
            return "no surviving request records a requeue"
        err = check_streams(reqs, baseline)
        if err:
            return err
        st = router.stats()
        if st["replicas_live"] != 1:
            return f"expected 1 live replica: {st}"
        # the survivor's engine must hold its trace ceilings through
        # the requeue wave (migration costs no recompiles)
        err = check_traces(router.replicas[1].eng)
        if err:
            return err
        # trace-context propagation across the death: the replayed
        # requests' traces carry a severed subtree + a replay link and
        # still end in EXACTLY one terminal span
        from paddle_tpu.profiler import tracing as _tracing
        tr = _tracing.tracer()
        replayed = [r for r in reqs if r.requeues]
        for r in replayed:
            spans = tr.spans(r.trace.trace_id)
            names = [s.name for s in spans]
            if "severed" not in names or "replay" not in names:
                return (f"request {r.id}: replayed trace lacks "
                        f"severed/replay marks: {sorted(set(names))}")
            terms = [s for s in spans if s.kind == "terminal"]
            if len(terms) != 1:
                return (f"request {r.id}: {len(terms)} terminal "
                        "spans after replay")
        # the requeue churn burns the budget: alert + parseable dump
        fdir = os.path.join(root, "router_replica_death", "flight")
        return check_burn_alert(fdir, "requeues", killed, len(reqs))
    scenario("router_replica_death", replica_death)

    # --- cancel + deadlines ------------------------------------------
    def cancel_deadline():
        eng = make_engine(params, cfg, max_len)
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(eng.submit(
                p, gen, deadline_ticks=3 if i == 1 else None))
        eng.step()
        eng.step()
        victim = next(r for r in reqs if r.slot is not None
                      and r.finish_reason is None and r is not reqs[1])
        if not victim.cancel():
            return "cancel() returned False on a live request"
        eng.drain()
        err = check_terminal(reqs) or check_streams(reqs, baseline)
        if err:
            return err
        if victim.finish_reason != "cancelled":
            return f"victim finished {victim.finish_reason!r}"
        if reqs[1].finish_reason != "timeout":
            return f"deadline request finished {reqs[1].finish_reason!r}"
        return None
    scenario("cancel_deadline", cancel_deadline, want_flight=False)

    # --- autoscaler: flood scales out, idle drains back to min -------
    def autoscale_flood():
        from paddle_tpu.inference.autoscale import (AutoscaleConfig,
                                                    Autoscaler)
        t = [0.0]
        router = make_router(params, cfg, max_len, replicas=1,
                             family="gpt", num_slots=2,
                             concurrent=False, clock=lambda: t[0])
        scaler = Autoscaler(
            router, spawn=lambda: make_engine(params, cfg, max_len,
                                              num_slots=2),
            cfg=AutoscaleConfig(min_replicas=1, max_replicas=3,
                                breach_ticks=2, idle_ticks=3,
                                cooldown_s=1.0),
            clock=lambda: t[0])
        reqs = [router.submit(p, gen) for p in prompts]
        peak = 1
        for _ in range(200):
            if not router.has_work():
                break
            router.step()
            t[0] += 2.0
            scaler.tick()
            peak = max(peak, len(router.dispatchable()))
        if router.has_work():
            return "flood never drained"
        if peak < 2:
            return f"flood never scaled out (peak {peak})"
        for _ in range(30):                  # idle: drain back to min
            if len(router.dispatchable()) == 1:
                break
            router.step()
            t[0] += 2.0
            scaler.tick()
        if len(router.dispatchable()) != 1:
            return (f"idle fleet never scaled back to min "
                    f"({len(router.dispatchable())} dispatchable)")
        err = (check_terminal(reqs) or check_streams(reqs, baseline))
        if err:
            return err
        if any(r.finish_reason not in ("length", "eos") for r in reqs):
            return ("scaling was not transparent: "
                    f"{[r.finish_reason for r in reqs]}")
        for rep in router.replicas:
            if rep.alive:
                err = check_traces(rep.eng)
                if err:
                    return err
        fdir = os.path.join(root, "autoscale_flood", "flight")
        return (check_flight(fdir, want_reason="autoscale_scale_out")
                or check_flight(fdir, want_reason="autoscale_scale_in"))
    scenario("autoscale_flood", autoscale_flood, want_flight=False)

    # --- live migration: replica death moves streams, ZERO re-prefill
    def live_migration():
        mig0 = monitor.counter("serving.autoscale.migrations").value
        fb0 = monitor.counter(
            "serving.autoscale.migrate_fallbacks").value
        router = make_router(params, cfg, max_len, replicas=2,
                             family="gpt", num_slots=6,
                             concurrent=False, kv_layout="paged",
                             page_size=8)
        reqs = [router.submit(p, gen) for p in prompts]
        for _ in range(3):
            router.step()                 # streams mid-decode on BOTH
        victim = max(router.replicas,
                     key=lambda rep: sum(1 for o in rep.inner.values()
                                         if not o.done)).idx
        live = sum(1 for o in router.replicas[victim].inner.values()
                   if not o.done)
        if live == 0:
            return "nothing live on the victim (drill too short)"
        survivor = router.replicas[1 - victim].eng
        pre_prefills = survivor.trace_counts()[1]
        replayed = router.kill_replica(victim)
        if replayed != 0:
            return (f"{replayed} requests fell back to replay "
                    "(every stream should migrate)")
        moved = (monitor.counter("serving.autoscale.migrations").value
                 - mig0)
        if moved < live:
            return f"only {moved}/{live} live streams migrated"
        if monitor.counter(
                "serving.autoscale.migrate_fallbacks").value != fb0:
            return "migrate_fallbacks moved on the migration-only path"
        router.drain()
        # THE migration claim: zero re-prefilled tokens — the survivor
        # ran no prefill for the adopted streams (its prefill trace
        # count is unchanged), and no request records a requeue
        if survivor.trace_counts()[1] != pre_prefills:
            return (f"survivor re-prefilled: {pre_prefills} -> "
                    f"{survivor.trace_counts()[1]} prefill traces")
        if any(r.requeues for r in reqs):
            return "a migrated stream recorded a requeue (replay path)"
        err = (check_terminal(reqs) or check_streams(reqs, baseline)
               or check_traces(survivor))
        if err:
            return err
        if any(r.finish_reason not in ("length", "eos") for r in reqs):
            return ("migration was not transparent: "
                    f"{[r.finish_reason for r in reqs]}")
        fdir = os.path.join(root, "live_migration", "flight")
        return check_flight(fdir, want_reason="router_replica_death")
    scenario("live_migration", live_migration, want_flight=False)

    # --- device loss: tp degrade + in-place stream migration ---------
    def device_loss():
        from paddle_tpu.inference.autoscale import EnginePreemptGuard
        from paddle_tpu.parallel.mesh import build_mesh
        devs = jax.devices()
        if len(devs) < 2:
            return f"need >= 2 devices for a tp mesh, got {len(devs)}"
        mesh = build_mesh({"tp": 2}, devices=devs[:2])
        eng = make_engine(params, cfg, max_len, num_slots=4, mesh=mesh)
        guard = EnginePreemptGuard(eng, lease_timeout_s=0.05)
        reqs = [eng.submit(p, gen) for p in prompts]
        new_tp = 0
        for _ in range(200):
            if not eng.has_work():
                break
            eng.step()
            new_tp = max(new_tp, guard.poll())
        if eng.has_work():
            return "engine never drained after the preemption"
        if new_tp != 1:
            return f"guard never degraded tp (poll() -> {new_tp})"
        if int(np.prod(eng.mesh.devices.shape)) != 1:
            return f"engine not rebuilt on the survivor mesh: {eng.mesh}"
        err = (check_terminal(reqs) or check_streams(reqs, baseline)
               or check_traces(eng))
        if err:
            return err
        if any(r.finish_reason not in ("length", "eos") for r in reqs):
            return ("preemption was not transparent: "
                    f"{[r.finish_reason for r in reqs]}")
        fdir = os.path.join(root, "serving_device_loss", "flight")
        return check_flight(fdir, want_reason="serving_preempt")
    scenario("serving_device_loss", device_loss,
             spec="replica_preempt@3:1", want_flight=False)

    # --- host_spill_flood: prefix reuse beyond the device pool -------
    def host_spill_flood():
        # shared-prefix families deliberately oversubscribe a tiny
        # device pool: every evicted REGISTERED page must spill to the
        # host tier and come back as a swap-in on the next family hit,
        # with streams bit-identical to a tier-less engine
        rng = np.random.RandomState(11)
        fam_prompts = []
        for _ in range(3):
            head = rng.randint(1, cfg.vocab_size - 1, 16).astype(np.int32)
            for _ in range(2):
                fam_prompts.append(np.concatenate(
                    [head, rng.randint(1, cfg.vocab_size - 1,
                                       4).astype(np.int32)]))
        kw = dict(num_slots=1, kv_layout="paged", page_size=8,
                  num_pages=6, prefix_sharing=True)
        plain = make_engine(params, cfg, max_len, **kw)
        tiered = make_engine(params, cfg, max_len,
                             host_kv_bytes=1 << 20, **kw)
        local_base = None
        for _ in range(2):                    # round 2 re-hits the tier
            base_reqs = [plain.submit(p, gen) for p in fam_prompts]
            plain.drain()
            local_base = [np.asarray(r.tokens, np.int32)
                          for r in base_reqs]
            reqs = [tiered.submit(p, gen) for p in fam_prompts]
            tiered.drain()
            err = (check_terminal(reqs)
                   or check_streams(reqs, local_base)
                   or check_traces(tiered))
            if err:
                return err
        st = tiered.pool_stats()["host_tier"]
        if st["spills"] == 0:
            return f"device pool never spilled to host: {st}"
        if st["swapins"] == 0:
            return f"host tier never served a swap-in: {st}"
        led = tiered.memory_ledger()
        if led["components"]["kv_pool_host"] != st["bytes"]:
            return ("ledger kv_pool_host "
                    f"{led['components']['kv_pool_host']} != tier "
                    f"bytes {st['bytes']}")
        return None
    scenario("host_spill_flood", host_spill_flood, want_flight=False)

    # --- prefill_role_death: disagg fleet loses its prefill replica --
    def prefill_role_death():
        h0 = monitor.counter("serving.router.handoffs").value
        router = make_router(params, cfg, max_len, replicas=2,
                             family="gpt", num_slots=4,
                             concurrent=False,
                             roles=["prefill", "decode"])
        half = len(prompts) // 2
        reqs = [router.submit(p, gen) for p in prompts[:half]]
        for _ in range(60):            # prefill + first handoffs land
            router.step()
            if monitor.counter("serving.router.handoffs").value > h0:
                break
        if monitor.counter("serving.router.handoffs").value <= h0:
            return "no prefill->decode handoff before the death"
        router.kill_replica(0, reason="chaos")     # the prefill replica
        # NEW work arriving after the death must still admit: role
        # purity degrades to shared duty on the survivor, never to a
        # stuck router queue
        reqs += [router.submit(p, gen) for p in prompts[half:]]
        router.drain(max_ticks=400)
        err = check_terminal(reqs) or check_streams(reqs, baseline)
        if err:
            return err
        if any(r.finish_reason not in ("length", "eos") for r in reqs):
            return ("prefill-role death was not transparent: "
                    f"{[r.finish_reason for r in reqs]}")
        st = router.stats()
        if st["replicas_live"] != 1:
            return f"expected 1 live replica: {st}"
        err = check_traces(router.replicas[1].eng)
        if err:
            return err
        fdir = os.path.join(root, "prefill_role_death", "flight")
        return check_flight(fdir, want_reason="router_replica_death")
    scenario("prefill_role_death", prefill_role_death,
             want_flight=False)

    # --- tenant_flood: quota-rejected flood, paying streams exact ---
    def tenant_flood():
        from paddle_tpu.inference.admission import TenantQuota
        rej0 = monitor.counter(
            "serving.admission.rejected.flood").value
        # the flood tenant's bucket covers ONE injected request
        # (cost 3 prompt + 4 gen = 7 tokens); the default (paying)
        # tenant stays unmetered
        router = make_router(
            params, cfg, max_len, replicas=1, family="gpt",
            num_slots=4, concurrent=False,
            admission={"flood": TenantQuota(tokens_per_s=0.5,
                                            burst=7.0)})
        reqs = [router.submit(p, gen) for p in prompts]
        router.drain(max_ticks=400)
        err = check_terminal(reqs) or check_streams(reqs, baseline)
        if err:
            return err
        rej = monitor.counter(
            "serving.admission.rejected.flood").value - rej0
        if rej < 1:
            return f"flood tenant was never quota-rejected (rej={rej})"
        if any(r.finish_reason not in ("length", "eos") for r in reqs):
            return ("the flood touched a paying stream: "
                    f"{[r.finish_reason for r in reqs]}")
        return check_traces(router.replicas[0].eng)
    scenario("tenant_flood", tenant_flood, spec="quota_flood@2:6",
             want_flight=False)

    # --- brownout_ladder: full 0->3->0 on an injected clock ---------
    def brownout_ladder():
        from paddle_tpu.inference.brownout import (BrownoutConfig,
                                                   BrownoutController)

        class _Obj:
            name = "ttft"

        class _SLO:
            pairs = [(3600.0, 60.0)]
            objectives = [_Obj()]
            burn = 0.0

            def burn_rate(self, name, window, now=None):
                return self.burn

        t = [0.0]
        router = make_router(params, cfg, max_len, replicas=1,
                             family="gpt", num_slots=4,
                             concurrent=False, admission={})
        slo = _SLO()
        ctrl = BrownoutController(
            router, slo=slo,
            cfg=BrownoutConfig(breach_ticks=2, recover_ticks=2,
                               cooldown_s=0.0),
            clock=lambda: t[0])
        # two priority classes in flight so level 2 has a victim
        reqs = [router.submit(p, gen, priority=i % 2)
                for i, p in enumerate(prompts)]
        up = []
        slo.burn = 2.0
        for _ in range(8):
            router.step()
            t[0] += 1.0
            if ctrl.tick():
                up.append(ctrl.level)
        if up != [1, 2, 3]:
            return f"escalation trajectory {up}, wanted [1, 2, 3]"
        down = []
        slo.burn = 0.0
        for _ in range(8):
            router.step()
            t[0] += 1.0
            if ctrl.tick():
                down.append(ctrl.level)
        if down != [2, 1, 0]:
            return f"recovery trajectory {down}, wanted [2, 1, 0]"
        router.drain(max_ticks=400)
        # the ladder degrades CAPACITY, never correctness: every
        # stream (including the suspended-and-resumed victims)
        # completes bit-identical
        err = check_terminal(reqs) or check_streams(reqs, baseline)
        if err:
            return err
        if any(r.finish_reason not in ("length", "eos") for r in reqs):
            return ("brownout was not transparent: "
                    f"{[r.finish_reason for r in reqs]}")
        fdir = os.path.join(root, "brownout_ladder", "flight")
        return (check_flight(fdir, want_reason="brownout_escalate")
                or check_flight(fdir, want_reason="brownout_recover"))
    scenario("brownout_ladder", brownout_ladder, want_flight=False)

    # --- process_crash_replay: SIGKILL + journaled recovery ---------
    def process_crash_replay():
        import signal
        import subprocess
        sdir = os.path.join(root, "process_crash_replay")
        jdir = os.path.join(sdir, "journal")
        os.makedirs(jdir, exist_ok=True)
        env = dict(os.environ)
        env.pop(faults.ENV_SPEC, None)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--crash-child", jdir, "--crash-n", str(n_req),
             "--crash-gen", str(gen)],
            capture_output=True, text=True, timeout=600, env=env)
        if proc.returncode != -signal.SIGKILL:
            return (f"child exited {proc.returncode}, wanted SIGKILL "
                    f"(-{signal.SIGKILL}); stderr tail: "
                    f"{proc.stderr[-500:]}")
        replays0 = monitor.counter("serving.journal.replays").value
        router = make_router(params, cfg, max_len, replicas=1,
                             family="gpt", num_slots=4,
                             concurrent=False, journal_dir=jdir)
        if monitor.counter(
                "serving.journal.replays").value == replays0:
            return "recovery replayed nothing (sigkill too late?)"
        streams = {}
        ticks = 0
        while router.has_work() and ticks < 400:
            for req, tok in router.step():
                streams.setdefault(req.id, []).append(int(tok))
            ticks += 1
        j = router.stats()["journal"]
        if j["replayable"] != 0:
            return f"{j['replayable']} requests still un-terminal"
        router.close()
        # the WAL across BOTH processes: every admitted id reaches
        # EXACTLY one terminal event, duplicate-free
        from paddle_tpu.inference.journal import RequestJournal
        wal = RequestJournal(jdir, fsync=False)
        admits, ends = set(), {}
        with open(wal.path, "rb") as f:
            for line in f:
                rec = wal._parse(line.rstrip(b"\n"))
                if rec is None:
                    return "torn record in a cleanly-recovered WAL"
                if rec["ev"] == "admit":
                    admits.add(rec["id"])
                else:
                    ends[rec["id"]] = ends.get(rec["id"], 0) + 1
        wal.close()
        if not admits:
            return "child journaled no admits"
        missing = [i for i in admits if ends.get(i, 0) != 1]
        if missing:
            return (f"admits without exactly one terminal: {missing} "
                    f"(ends={ends})")
        # replayed greedy streams are bit-identical to the fault-free
        # baseline (the child used the drill's own workload)
        for rid, toks in streams.items():
            got = np.asarray(toks, np.int32)
            want = baseline[rid]
            if not np.array_equal(got, want[:len(got)]):
                return (f"replayed stream {rid} diverged: "
                        f"{got.tolist()} vs {want.tolist()}")
        if not streams:
            return "no streams replayed in the parent"
        return None
    scenario("process_crash_replay", process_crash_replay,
             want_flight=False)

    rec.clear()          # don't leak scenario records into the caller's
    #                      process-global ring (in-process test usage)
    dt = time.time() - t_start
    if keep_root:
        _log(f"artifacts kept under {root}")
    if failures:
        _log(f"{len(failures)} FAILURES in {dt:.1f}s:")
        for f in failures:
            _log(f"  - {f}")
        return 1
    _log(f"ALL SCENARIOS PASSED (quick={quick}) in {dt:.1f}s")
    return 0


# ------------------------------------------------------- crash child
def crash_child_main(jdir: str, n_req: int, gen: int) -> int:
    """--crash-child: the sacrificial process of process_crash_replay.
    Builds a JOURNALED router over `jdir`, submits the drill's own
    deterministic workload, and drains under a sigkill fault — the
    process dies mid-decode with no flush and no atexit; the fsynced
    request WAL is all that survives for the parent to recover."""
    from paddle_tpu.inference.router import create_router
    from paddle_tpu.testing import faults
    params, cfg = build_model()
    prompts = build_workload(n_req, 3, 20, cfg.vocab_size)
    # gen+2 ticks in: the first wave is mid-decode (some streams may
    # already be terminal — both replay classes get exercised)
    faults.install(f"sigkill@{gen + 2}",
                   once_dir=os.path.join(jdir, os.pardir, "once"))
    router = create_router(params, cfg, replicas=1, family="gpt",
                           num_slots=4, max_len=64, concurrent=False,
                           journal_dir=jdir)
    for p in prompts:
        router.submit(p, gen)
    router.drain(max_ticks=400)      # SIGKILL fires mid-drain
    _log("crash child survived its own sigkill fault")
    return 3                         # a working drill never gets here


# ------------------------------------------------------------ bench mode
def bench_main(requests=16, gen=32, slots=8, repeats=5) -> int:
    """Measure the guardrail overhead on serving throughput: the same
    workload through an engine with guardrails OFF (PR-4 shape: no
    in-jit isfinite/poison, no watchdog, no deadlines) and ON (the
    default: quarantine guard + watchdog + per-request deadlines that
    never fire). Timed passes ALTERNATE between the two warm engines
    and each side reports its best — on the loaded 1-core build host
    run-to-run noise exceeds the effect, so paired best-of-N is the
    honest estimator. One JSON line — the BASELINE.md "Serving SLO"
    row."""
    from paddle_tpu.models.decode import next_pow2
    from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
    from paddle_tpu.inference.serving import ServingEngine

    hidden, layers, vocab = 128, 2, 512
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=hidden // 32,
                    max_seq_len=2 * next_pow2(96 + gen),
                    sequence_parallel=False, remat=False,
                    dtype=jnp.float32)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    max_len = next_pow2(96 + gen)
    prompts = build_workload(requests, 8, 96, vocab)
    total = requests * gen

    def build(**kw):
        sub = dict(kw.pop("_submit", {}))
        eng = ServingEngine(params, cfg, family="gpt", num_slots=slots,
                            max_len=max_len, **kw)
        warm = eng.generate(prompts, gen, **sub)     # compile everything
        return eng, sub, warm

    def timed(eng, sub):
        t0 = time.perf_counter()
        outs = eng.generate(prompts, gen, **sub)
        return time.perf_counter() - t0, outs

    eng_off, sub_off, warm_off = build(guardrails=False)
    eng_on, sub_on, warm_on = build(
        guardrails=True, watchdog_timeout=5.0,
        _submit=dict(deadline_s=300.0, deadline_ticks=100_000))
    mismatch = sum(1 for a, b in zip(warm_off, warm_on)
                   if not np.array_equal(a, b))
    best_off = best_on = 1e18
    for _ in range(repeats):
        dt, outs = timed(eng_off, sub_off)
        best_off = min(best_off, dt)
        mismatch += sum(1 for a, b in zip(warm_off, outs)
                        if not np.array_equal(a, b))
        dt, outs = timed(eng_on, sub_on)
        best_on = min(best_on, dt)
        mismatch += sum(1 for a, b in zip(warm_off, outs)
                        if not np.array_equal(a, b))
    tps_off, tps_on = total / best_off, total / best_on
    traces_off, traces_on = eng_off.trace_counts(), eng_on.trace_counts()
    overhead = (tps_off - tps_on) / tps_off * 100.0
    print(json.dumps({
        "metric": "serving_guardrail_overhead",
        "value": round(overhead, 2),
        "unit": "%",
        "backend": jax.devices()[0].platform,
        "tokens_per_sec_guardrails_off": round(tps_off, 1),
        "tokens_per_sec_guardrails_on": round(tps_on, 1),
        "requests": requests, "gen": gen, "slots": slots,
        "repeats": repeats,
        "model": f"{layers}Lx{hidden}d",
        "decode_traces": [traces_off[0], traces_on[0]],
        "stream_mismatches": mismatch,
    }), flush=True)
    return 0 if mismatch == 0 else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller workload (CI-sized)")
    ap.add_argument("--bench", action="store_true",
                    help="measure guardrail overhead, print one JSON")
    ap.add_argument("--keep", action="store_true",
                    help="keep scenario artifacts")
    ap.add_argument("--crash-child", metavar="JOURNAL_DIR",
                    help="internal: process_crash_replay's sacrificial "
                         "child (journaled router + sigkill fault)")
    ap.add_argument("--crash-n", type=int, default=6,
                    help="internal: crash-child workload size")
    ap.add_argument("--crash-gen", type=int, default=6,
                    help="internal: crash-child tokens per request")
    args = ap.parse_args()
    if args.crash_child:
        return crash_child_main(args.crash_child, args.crash_n,
                                args.crash_gen)
    if args.bench:
        return bench_main()
    return run_drill(quick=args.quick, keep_root=args.keep)


if __name__ == "__main__":
    sys.exit(main())
