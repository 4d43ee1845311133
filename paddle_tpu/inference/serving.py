"""Continuous-batching serving engine: slot-pool KV cache, bucketed
prefill, one jitted decode step.

Reference analog: the dedicated serving runtime — AnalysisPredictor
(inference/api/analysis_predictor.h:94) driving the
FusedMultiTransformer decode loops
(incubate/nn/layer/fused_transformer.py:1022) — generalized to
iteration-level scheduling (cf. Orca's continuous batching, OSDI '22,
and vLLM's paged KV cache, SOSP '23): requests join and leave the
running batch between decode steps instead of start-and-finish
together.

TPU-native design (everything jit-shaped, nothing dynamic on device):

- **Slot pool.** A fixed pool of N decode slots backed by one donated
  stacked KV cache ({"k","v"} buffers of [L, N, max_len, KV, hd] — the
  k/v pair realizes the single [L, 2, N, ...] buffer of the design
  with per-leaf donation, so XLA aliases both across ticks and the
  cache never leaves the device). All writes are in place and touch
  only the step's rows (kernels/decode_attention.write_kv: the cached
  forwards carry the pool through their layer scan and write at
  [layer, slot, position]).
- **One jitted mixed decode step.** Every tick advances ALL slots one
  token under per-slot position/active masks: the per-row-position
  `forward_cached` (models/gpt.py, models/llama.py) runs the N tokens
  as one batch, and greedy + temperature/top-k sampling happens inside
  the jit (per-request PRNG streams derived by folding the request id
  and token index into the engine key, so sampled streams are
  reproducible regardless of slot placement or batch composition).
  The tick's signature is shape-stable -> one trace per sampling mode
  (greedy-only ticks skip the sampling machinery via a static flag)
  for the engine's lifetime.
- **Bucketed prefill.** Prompts pad to the power-of-two bucket
  (models/decode.prompt_bucket — the same policy as the bucketed
  greedy driver, which is what makes engine token streams
  bit-identical to per-request `greedy_generate`); the true length and
  target slot ride through the trace as scalars, so any prompt length
  hits one of ~log(max_len) compiled executables.
- **Python-side scheduler.** Admission queue, slot allocation,
  EOS/max-token/cache-full eviction, and mid-decode join of new
  requests into freed slots all happen on the host between ticks; the
  device only ever sees the fixed-shape tick.

Stale cache contents (a freed slot's previous request, bucket-pad
garbage) are never attended: the decode-attention mask admits cache
slots <= the query's own position only, and decode writes overwrite
the pad region in order (kernels/decode_attention.py).

SLO guardrails (the robustness layer around the scheduler — the
serving analog of parallel/resilience.py's skip/rollback/watchdog, cf.
the reference's predictor error handling and the per-request isolation
requirement of Orca/vLLM-class serving stacks):

- **Admission control.** `max_queue` bounds the queue; an over-full
  submit raises a typed `BackpressureError` (policy "reject") or sheds
  the oldest queued request (policy "shed_oldest"); `queue_ttl_s`
  expires requests that wait too long. `Request.cancel()` frees the
  slot mid-decode.
- **Deadlines.** Per-request wall (`deadline_s`) and engine-tick
  (`deadline_ticks`) deadlines are enforced by the scheduler; every
  submitted request resolves EXACTLY ONCE with a terminal
  `finish_reason` from {eos, length, timeout, cancelled, poisoned,
  evicted} (`TERMINAL_REASONS`) — `_finish` is the one place the
  transition happens.
- **Poisoned-slot quarantine.** With `guardrails=True` (default) the
  decode tick checks `isfinite` over each slot's logit row IN-JIT and
  folds the verdict into the sampled token (`-1` sentinel — real ids
  are never negative), so the one-host-pull-per-tick invariant and the
  trace-count ceilings are untouched. The host evicts only the
  poisoned slot (`finish_reason="poisoned"`); co-batched streams are
  bit-identical because per-slot attention and per-request PRNG
  streams never mix rows. Prefill guards its first-token logits the
  same way.
- **Self-healing tick.** The two device calls (+ the one host pull)
  run under bounded retry/backoff; a failed tick resyncs `_dstate`
  from the host mirrors (`_dirty=True`) — the mirrors only advance
  AFTER a successful pull, so a re-run of the tick is idempotent
  (same state -> same KV writes) and engine state can never desync. A
  hung pull (watchdog — parallel/resilience.WatchdogPuller, the
  persistent-thread variant of the trainer's pull guard) or an
  exhausted retry budget triggers `_hard_reset`: every in-flight
  request terminates as "evicted" and the cache is reallocated.
  Every serving fault dumps a flight-recorder black box
  (profiler/flight_recorder.py).

Paged KV cache (kv_layout="paged"; "auto" is dense — the capacity
layer, cf. vLLM's PagedAttention SOSP '23 and SGLang's
RadixAttention):

- **Block pool.** K/V live in fixed-size pages ({"k","v"} buffers of
  [L, num_pages, page_size, KV, hd]) instead of one dense
  [L, N, max_len, ...] block; a device-resident per-slot page table
  ("pt" [N, max_pages] int32, riding the donated cache dict) maps
  logical positions to physical pages. HBM scales with TOKENS HELD,
  not num_slots * max_len — the concurrent-stream capacity lever.
  Page 0 is reserved scratch: freed slots and out-of-range positions
  write there, and the position mask keeps its garbage at an exact
  softmax 0. All allocation/refcount/free runs on the host scheduler
  (`_PagePool`) between ticks; the jitted tick only ever sees
  gather/scatter indexing (kernels/decode_attention.gather_pages /
  write_kv_paged) — bit-identical streams vs the dense layout.
- **Prefix sharing + copy-on-write.** Admission hashes the prompt per
  page (a rolled prefix hash: page j's key covers tokens
  [0, (j+1)*page_size)) and maps already-materialized pages instead
  of recomputing them, bumping refcounts; the suffix (always >= 1
  token, so the first-token logits are always computed) prefills
  normally. A slot that must WRITE into a shared/registered page
  first materializes a private copy (`_ensure_private` — the COW
  seam, one jitted in-pool page copy). Finished requests' registered
  pages linger in an LRU "cached" state (refcount 0, evictable on
  demand), so a system prompt's pages survive across request
  lifetimes — the RadixAttention-style cross-request reuse.
- **Chunked prefill.** Prompts whose un-shared suffix exceeds
  `prefill_chunk` split into chunks run ONE PER TICK, interleaved
  with the decode tick, so a max-length prompt can never stall
  co-batched streams past their inter-token deadline. Chunks reuse
  the bucketed-prefill trace policy (power-of-two chunk buckets,
  traced true_len/start/slot), so the prefill executable ceiling is
  unchanged.
- **Pool-exhaustion admission.** Every admission RESERVES its
  worst-case page need (minus shared credit) up front; a request
  that cannot reserve stays queued (never a wedged slot), and one
  that could never fit the configured pool raises the typed
  `PoolExhaustedError` at submit.

Speculative decoding (spec_decode="spec" — the single-stream latency
layer, cf. Leviathan et al. 2023; OFF by default):

- **Self-draft propose + one-pass verify, one tick.** Each tick runs
  `gamma` truncated-depth draft steps (the first `draft_layers` layers
  of the stacked scan, sharing the target's params and KV cache/pages
  — inference/spec_decode.py) and ONE full-depth verify pass over all
  gamma+1 positions, accepting drafts by the greedy rule
  (models/decode.greedy_accept). A tick emits 1..gamma+1 tokens, every
  one of them the TARGET model's own argmax — greedy streams are
  bit-identical to the non-spec engine on both cache layouts.
- **Invariants preserved.** Still ONE host pull per tick (the
  [N, gamma+1] emission matrix: col 0 = token or -1 quarantine
  sentinel, accepted tokens, then the SPEC_PAD fill); still <= 2
  decode traces (gamma/draft_layers baked per engine, `sampling` the
  only static flag); exactly-once unchanged (mid-block EOS/length
  finishes drop the unconsumed tail, exactly what non-spec would
  never have generated). Sampled slots ride the same tick, emitting
  one reproducible token from verify row 0 (mixed spec/non-spec
  batches) — multi-token rejection sampling is deliberately not
  implemented (it would change sampled streams vs non-spec).
- **Paged interplay.** The tick's write span (gamma+1 positions)
  prepares pages up front, clamped to the request's envelope;
  rejected drafts' pages roll back to the pool after acceptance
  (`_rollback_spec_pages`), so speculation never inflates a slot's
  page footprint between ticks. Draft positions past the envelope
  scatter to the scratch page through the unmapped table.
- **Degradation, not quarantine, on draft failure.** Non-finite DRAFT
  logits force acceptance 0 for that slot (verify row 0 — the
  target's own logits — still emits); only target-model non-finite
  logits quarantine, and only over emitted rows. testing/faults.py
  `draft_nan` + tools/chaos_serving.py drill this.

Tensor-parallel serving (mesh= — the scale-UP layer, cf. the
reference's hybrid-parallel fleet topology
fleet/base/topology.py:54,140 applied to the decode path, and
SNIPPETS.md [3]'s PartitionSpec layout):

- **One engine, one mesh.** `mesh=build_mesh({'tp': N})` shards THIS
  engine's jitted bodies (decode tick, bucketed/chunked prefill, COW
  copy, spec tick) over the mesh's `tp` axis: params per the family's
  module-level SERVING_PARAM_SPECS (the training PARAM_SPECS TP split
  remapped by parallel.mesh.tp_specs — column-parallel qkv/up,
  row-parallel out/down, vocab-parallel embedding), the KV cache/page
  pool head-sharded per kernels/decode_attention.cache_pspecs (the
  page table replicated; a KV-head count the tp degree doesn't divide
  degrades that leaf to replicated — deep GQA), the per-slot decode
  state replicated. GSPMD inserts the two activation all-reduces per
  layer the reference's mp_ops issues by hand.
- **Invariants per mesh.** Still ONE host pull per tick (the pulled
  token array is replicated — one small fetch); zero recompiles after
  warmup (`_pin_cache` pins every jitted body's returned cache leaves
  to their input NamedShardings, so donation aliases exactly and
  propagation heuristics can't shift layouts between calls); every
  host->device upload routes through `_rep` (replicated device_put)
  so placements are mesh-consistent by construction. Token streams
  are BIT-IDENTICAL to the unsharded engine (greedy argmax and the
  partitionable-threefry sampled path both survive sharding — the
  8-virtual-device CPU-mesh suite tests/test_tp_serving.py pins it).
- **Composition.** tp composes with everything above: paged pool,
  chunked prefill, speculative decode (the draft's first-K-layers
  cache view inherits the head sharding). Horizontal scaling stacks
  on top via the replicated-engine router (inference/router.py):
  data-parallel engine replicas behind least-loaded admission —
  dp(router) x tp(engine). parallel.planner.plan_serving_tp prices
  when tp pays (the decode tick is weight-bandwidth bound; a model
  bigger than one chip forces tp > 1).

Held weights: the engine keeps its params at the COMPUTE dtype. What
the caller hands in (float32 leaves, as a checkpoint stores them) is
rounded once at build by quantization/serving.round_serving_params —
the leaves the family's cached forward would otherwise `astype` inside
every tick and every prefill; norm leaves, which it reads in float32,
stay. No knob: the effect follows from the leaves' dtypes against
cfg.dtype, the logits are bit-identical, and the gauges
serving.weights_bytes / serving.weights_given_bytes say what it did.

Quantized serving (quant="int8" — the weight-HBM layer, cf. the
reference PTQ driver's channel_wise_abs_max weight path; OFF by
default):

- **Weight-only int8, quantize-at-build.** The engine rewrites its
  params tree once at construction (quantization/serving.py): every
  stacked matmul weight in the family's QUANT_LEAVES (attention
  qkv/proj, MLP in/out) becomes an int8 `<name>_q` plus per-output-
  channel fp32 `<name>_scale` (abs-max/127, the ready dequant
  multiplier), the tied LM head gets a transposed int8 copy
  (`head_q`/`head_scale`) while `wte` stays fp for the embedding
  gather, and the fp leaves are DROPPED — weight HBM falls to ~0.26x
  (f32) / ~0.52x (bf16) for the block weights, which compounds with
  the paged pool (more KV pages at fixed HBM) and tp (bigger models
  per chip).
- **Dequant inside the matmul.** The cached forwards route every
  block matmul through kernels/quant_matmul.leaf_matmul, which picks
  the int8 pair up FROM THE TREE — no flag reaches the jitted bodies,
  so the tick invariants (one host pull, trace ceilings, donation)
  are untouched and dense/paged/spec-draft/tp compose for free. The
  fused dequant-matmul runs as 'xla' (portable, the CPU-tested real
  path) or 'pallas' (hand-tiled, int8->f32 in registers):
  kernels/quant_matmul.QUANT_MATMUL_IMPL, 'xla'.
- **Determinism tiers.** Weight-only dequant is deterministic: a
  quantized engine's streams are BIT-IDENTICAL across layouts and
  meshes (dense/paged, spec on/off, tp degrees), and the Pallas and
  XLA impls are bitwise-identical to each other. Versus the fp
  engine, streams carry a measured logit-error budget instead
  (BASELINE.md "Quantized serving"); greedy streams may diverge —
  that is the accuracy/HBM trade, recorded, not hidden.

Observability: serving.* monitor counters/gauges (slot occupancy,
queue depth, tokens emitted, prefills, decode ticks, plus
rejected/timeout/cancelled/poisoned/evicted/retries/faults, the
queue_wait_ms HISTOGRAM (bounded reservoir, p50/p95/p99 in
snapshots), the kv-pool surface: pages_in_use /
pages_shared gauges, cow_copies / prefill_chunks counters, and the
speculative surface: spec_proposed / spec_accepted counters + the
per-engine spec_accept_rate gauge), in-tick DEVICE telemetry
(telemetry= — the TICK_FIELDS int32 row computed in-jit and riding
the tick's one host pull; profiler/serving_telemetry, records via
tick_records() / telemetry_jsonl=), request-scoped tracing
(tracing= — parented spans submit -> prefill chunks -> decode ->
the exactly-once terminal _finish; profiler/tracing) and
RecordEvent spans through the tick (serving.tick > admit > prefill,
upload, decode_tick > decode_dispatch + decode_pull, emit —
docs/observability.md has the table) —
tools/telemetry_report.py summarizes them (including TTFT /
inter-token-latency percentiles from `export_slo_jsonl` and a
"kv pool" block), tools/bench_serving.py measures the engine against
sequential per-request decode (--capacity races paged vs dense at
equal HBM), and tools/chaos_serving.py is the executable acceptance
test for the guardrails.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import json
import sys
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..models.decode import prompt_bucket
from ..profiler import RecordEvent, monitor

__all__ = ["ServingEngine", "Request", "ModelFamily", "family_for",
           "create_serving_engine", "BackpressureError",
           "PoolExhaustedError", "ServingFaultError", "TERMINAL_REASONS"]

# every submitted request ends in exactly one of these (the
# finish-reason state machine — docs/serving.md "Robustness")
TERMINAL_REASONS = frozenset(
    {"eos", "length", "timeout", "cancelled", "poisoned", "evicted"})

# fault-injection seam (paddle_tpu.testing.faults.install wires it):
# called with the tick index about to run, returns an action dict
# ({"poison_slot": i} | {"draft_poison_slot": i} | {"stall_s": s} |
# {"raise_prefill": True} | {"raise_decode": True} |
# {"raise_cow": True} | {"raise_migrate": True}). Production code
# never sets it.
_FAULT_HOOK: Optional[Callable[[int], dict]] = None


class BackpressureError(RuntimeError):
    """submit() refused: the admission queue is at max_queue (policy
    "reject"). Carries .queue_depth so callers can report/shed."""

    def __init__(self, msg: str, queue_depth: int = 0):
        super().__init__(msg)
        self.queue_depth = queue_depth


class PoolExhaustedError(RuntimeError):
    """submit() refused: the request's worst-case page need exceeds
    the ENTIRE configured pool — it could never be admitted. Requests
    that merely have to wait for pages are queued, not refused."""

    def __init__(self, msg: str, pages_needed: int = 0,
                 pages_total: int = 0):
        super().__init__(msg)
        self.pages_needed = pages_needed
        self.pages_total = pages_total


class ServingFaultError(RuntimeError):
    """An injected serving fault (testing.faults prefill_raise /
    decode_raise / cow_raise) — raised at the device-call seam so the
    retry path exercises exactly what an organic dispatch failure
    would."""


# --------------------------------------------------------------- families
class UnsupportedOptionError(ValueError):
    """An engine option that the model family cannot run yet was asked
    for (`.option` names it, `.family` the family): the engine refuses
    at construction rather than serve wrongly."""

    def __init__(self, family: str, option: str, why: str = ""):
        super().__init__(
            f"family {family!r} does not support {option}"
            + (f": {why}" if why else ""))
        self.family = family
        self.option = option


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    """The seam a model family exposes to the engine: a cached forward
    that accepts per-row positions (slot-indexed writes) and a cache
    factory. Any family that implements the contract plugs in here.

    The cache is a dict of POOLS BY KIND OF STATE, each
    [layers of that kind, slots, ...] with the slot on axis 1. Keys and
    values are held by position, [layers, slots, positions, KV, hd]: the
    uniform families have one such kind, `{"k", "v"}` over `max_len`
    positions; a family with window layers adds a ring pool
    (`{"k_win", "v_win"}` over `window` positions). A LATENT kind is held
    by position too but has no head axis (joyai_llm_flash's `"ckv"`
    [layers, slots, positions, kv_lora_rank] and `"kpe"` [layers, slots,
    positions, qk_rope_head_dim]: what every head's key and value are
    made from, and the one rotated key they share). A recurrent kind has
    NO position axis (jamba's `"ssm"` [layers, slots, d_state, d_inner]
    float32 and `"conv"` [layers, slots, d_conv - 1, d_inner]): a step
    overwrites a slot's rows, so such a family's forward must leave a
    padded position and an idle row's state alone (it is told which
    tokens are real, `live=`), and its `prefill` replaces a slot's rows
    whole at admission. The engine allocates, donates and counts the
    dict whole; only the family reads a pool. (The paged layout and the
    snapshot paths still assume the uniform `{"k", "v"}` and are among
    what such a family `refuses`.)

    `serving_specs` is the family's module-level tensor-parallel spec
    table (leaf name -> PartitionSpec over the serving mesh's 'tp' axis
    — models/gpt.py / models/llama.py SERVING_PARAM_SPECS); None means
    the family cannot shard (mesh= is then refused). `prefill` replaces
    the engine's own bucketed prefill (a fresh `init_cache(cfg, 1,
    bucket)`, the forward, the row copied into the slot) where a pool
    is not written that way. `counts` is for a family whose forward
    leaves a row of int32 counts in the cache's "stats" leaf: it turns
    the pulled row into span counts by name. The forward then takes
    `live=` (which rows are real tokens), and the row rides the tick's
    one pull onto the `serving.decode_tick` / `serving.prefill` spans. `refuses` names the engine options (`REFUSABLE`) that raise
    UnsupportedOptionError for this family."""
    name: str
    forward_cached: Callable    # (params, tokens[B,T], cache, pos, cfg)
    init_cache: Callable        # (cfg, batch, max_len) -> pools by kind
    serving_specs: Optional[dict] = None
    prefill: Optional[Callable] = None   # (params, cache, padded [1,Tb],
    #                 true_len, slot, cfg) -> (last logits [1,V], cache)
    counts: Optional[Callable] = None    # (cfg, stats row) -> dict
    refuses: Tuple[str, ...] = ()


# engine options a family may refuse, as UnsupportedOptionError names them
REFUSABLE = ("kv_layout='paged'", "prefill_chunk", "spec_decode",
             "multi_tick", "mesh", "quant", "host_kv_bytes", "migration",
             "journal_dir")


def _gpt_family() -> ModelFamily:
    from ..models import gpt
    return ModelFamily("gpt", gpt.gpt_forward_cached, gpt.init_kv_cache,
                       gpt.SERVING_PARAM_SPECS)


def _llama_family() -> ModelFamily:
    from ..models import llama
    return ModelFamily("llama", llama.llama_forward_cached,
                       llama.init_kv_cache, llama.SERVING_PARAM_SPECS)


def _cohere2_moe_family() -> ModelFamily:
    from ..models import cohere2_moe as m
    return ModelFamily("cohere2_moe", m.cohere2_moe_forward_cached,
                       m.init_kv_cache, None, prefill=m.prefill_into_slot,
                       counts=m.span_counts, refuses=REFUSABLE)


def _jamba_family() -> ModelFamily:
    from ..models import jamba as m
    return ModelFamily("jamba", m.jamba_forward_cached, m.init_cache, None,
                       prefill=m.prefill_into_slot, counts=m.span_counts,
                       refuses=REFUSABLE)


def _joyai_llm_flash_family() -> ModelFamily:
    from ..models import joyai_llm_flash as m
    return ModelFamily("joyai_llm_flash", m.joyai_llm_flash_forward_cached,
                       m.init_cache, None, prefill=m.prefill_into_slot,
                       counts=m.span_counts, refuses=REFUSABLE)


# name -> factory; a family's module is imported when it is asked for
_FAMILIES = {"gpt": _gpt_family, "llama": _llama_family,
             "cohere2_moe": _cohere2_moe_family, "jamba": _jamba_family,
             "joyai_llm_flash": _joyai_llm_flash_family}


def family_for(name: str) -> ModelFamily:
    if name not in _FAMILIES:
        raise ValueError(f"unknown model family {name!r} "
                         f"({'|'.join(_FAMILIES)})")
    return _FAMILIES[name]()


def _pool_bytes(cache) -> int:
    """Device bytes of the pools of a cache dict, every kind of state
    (keys and values by position, recurrent rows)."""
    return sum(int(v.nbytes) for k, v in cache.items()
               if k not in ("pt", "stats"))


# -------------------------------------------------------------- page pool
class _PagePool:
    """Host-side allocator for the paged KV pool (the scheduler half of
    the vLLM block manager). Every page is in exactly one state:

    - free      never registered; on the free list;
    - live      refcount > 0 (mapped by >= 1 slot page tables);
    - cached    refcount == 0 but registered under a prompt-prefix key
                (LRU; evictable on demand — cross-request prefix reuse).

    Page 0 is the reserved scratch page (permanently live, never
    handed out): freed slots' table rows and out-of-range positions
    point at it, so stray scatter writes land in garbage the position
    mask never admits.

    `reserved` tracks admission-time worst-case reservations not yet
    turned into allocations — `available()` is what a NEW admission
    may claim without starving an already-admitted slot."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is "
                             f"reserved scratch); got {num_pages}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.ref = np.zeros(num_pages, np.int64)
        self.ref[0] = 1                      # scratch: pinned forever
        # pop() takes from the end -> low page ids hand out first
        self.free: List[int] = list(range(num_pages - 1, 0, -1))
        self.cached: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()        # page_id -> key, LRU order
        self.by_key: dict = {}               # prefix key -> page_id
        self.key_of: dict = {}               # page_id -> prefix key
        self.reserved = 0                    # admission reservations
        # eviction tap (host-tier KV offload): called as on_evict(pid,
        # key) just before a registered page's LRU eviction drops its
        # prefix-map entry — the engine's spill hook copies the page to
        # host there, so "evicted from device" means "demoted to the
        # host tier", not "gone"
        self.on_evict = None

    def available(self) -> int:
        """Pages a new admission may still reserve: free + evictable
        cached, minus what prior admissions already reserved."""
        return len(self.free) + len(self.cached) - self.reserved

    def alloc(self) -> int:
        """One private page (ref=1), evicting the LRU cached page (and
        its prefix-map entry) when the free list is dry. Raises
        PoolExhaustedError when nothing is evictable — unreachable for
        reserved admissions by construction."""
        if self.free:
            pid = self.free.pop()
        elif self.cached:
            pid, key = self.cached.popitem(last=False)     # LRU
            if self.on_evict is not None:
                self.on_evict(pid, key)
            del self.by_key[key]
            del self.key_of[pid]
        else:
            raise PoolExhaustedError(
                "page pool exhausted (no free or evictable page)",
                pages_needed=1, pages_total=self.num_pages)
        self.ref[pid] = 1
        return pid

    def retain(self, pid: int) -> None:
        """One more reference (prefix sharing): a cached page comes
        back live; its prefix-map registration survives."""
        if self.ref[pid] == 0:
            self.cached.pop(pid, None)
        self.ref[pid] += 1

    def release(self, pid: int) -> None:
        """Drop one reference. At zero a registered page parks in the
        LRU cache (prefix reuse across request lifetimes); an
        unregistered one returns to the free list."""
        if pid == 0:
            return                           # scratch never releases
        self.ref[pid] -= 1
        assert self.ref[pid] >= 0, f"refcount underflow on page {pid}"
        if self.ref[pid] == 0:
            key = self.key_of.get(pid)
            if key is not None:
                self.cached[pid] = key
                self.cached.move_to_end(pid)
            else:
                self.free.append(pid)

    def register(self, pid: int, key) -> None:
        """Publish `pid` under the prompt-prefix `key` (first writer
        wins — a racing identical prefix keeps its private copy)."""
        if key not in self.by_key and pid not in self.key_of:
            self.by_key[key] = pid
            self.key_of[pid] = key

    def lookup(self, key) -> Optional[int]:
        return self.by_key.get(key)

    def is_frozen(self, pid: int) -> bool:
        """True when writing `pid` requires a private copy first:
        shared (ref > 1) or published in the prefix map (another slot
        may map it at any moment)."""
        return self.ref[pid] > 1 or pid in self.key_of

    def stats(self) -> dict:
        live = int((self.ref[1:] > 0).sum())
        return {"num_pages": self.num_pages,
                "page_size": self.page_size,
                "pages_in_use": live,
                "pages_free": len(self.free),
                "pages_cached": len(self.cached),
                "pages_shared": int((self.ref[1:] > 1).sum()),
                "pages_reserved": int(self.reserved)}


def _prefix_key(prompt: np.ndarray, n: int) -> tuple:
    """The rolled prompt-prefix hash for the page ending at token `n`:
    identical token prefixes -> identical K/V bits (causality), so the
    digest of tokens [0, n) keys a reusable page. Length rides in the
    key so a digest collision across lengths cannot alias."""
    return (n, hashlib.blake2b(prompt[:n].tobytes(),
                               digest_size=16).digest())


# --------------------------------------------------------------- requests
class Request:
    """One generation request riding through the engine."""

    __slots__ = ("id", "prompt", "max_new_tokens", "temperature",
                 "top_k", "eos_id", "tokens", "done", "finish_reason",
                 "slot", "deadline_s", "deadline_ticks", "t_submit",
                 "_tick_submit", "_t_last", "_engine", "_pf_next",
                 "shared_tokens", "_pfx_keys", "trace", "_sp_queue",
                 "_sp_decode", "tenant", "priority")

    def __init__(self, req_id, prompt, max_new_tokens, temperature,
                 top_k, eos_id, deadline_s=None, deadline_ticks=None,
                 tenant="default", priority=0):
        self.id = req_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        # multi-tenant admission labels (inference/admission.py): the
        # ENGINE carries them untouched — quotas/fairness/preemption
        # are router policy; they ride snapshots so a suspended or
        # migrated stream keeps its class
        self.tenant = tenant
        self.priority = int(priority)
        self.deadline_s = deadline_s       # wall seconds from submit
        self.deadline_ticks = deadline_ticks  # engine ticks from submit
        self.tokens: List[int] = []     # generated ids, in order
        self.done = False
        self.finish_reason: Optional[str] = None
        self.slot: Optional[int] = None
        self.t_submit = 0.0
        self._tick_submit = 0
        self._t_last = 0.0              # last emission (SLO samples)
        self._engine = None
        self._pf_next = None            # next chunked-prefill position
        self._pfx_keys = None           # memoized per-page prefix hashes
        self.shared_tokens = 0          # prompt tokens served from
        #                                 shared pages (prefix reuse)
        self.trace = None               # RequestTrace (tracing=True /
        #                                 router-passed; profiler/tracing)
        self._sp_queue = None           # open queue-span id
        self._sp_decode = None          # open decode-span id

    def cancel(self) -> bool:
        """Terminate this request NOW (finish_reason "cancelled"):
        dequeues it if still waiting, frees its slot if mid-decode.
        Returns False when the request already resolved."""
        eng = self._engine
        return False if eng is None else eng.cancel(self)

    def __repr__(self):
        return (f"Request(id={self.id}, len={len(self.prompt)}, "
                f"gen={len(self.tokens)}/{self.max_new_tokens}, "
                f"done={self.done})")


# ------------------------------------------------------- in-jit sampling
def _slot_keys(base_key, req_ids, gen_idx):
    """Per-slot PRNG keys: fold (request id, token index) into the
    engine key — streams depend on the request, never on slot placement
    or batch composition."""
    def one(rid, gi):
        return jax.random.fold_in(jax.random.fold_in(base_key, rid), gi)
    return jax.vmap(one)(req_ids, gen_idx)


def _sample(lg, temps, top_ks, keys, max_top_k: int):
    """lg [N,V] f32 -> next token [N] int32. Greedy where temp <= 0
    (bit-identical to the greedy driver's argmax); otherwise
    temperature softmax sampling, truncated to the request's top_k
    (<= the engine's static max_top_k) when top_k > 0."""
    greedy = jnp.argmax(lg, axis=-1)
    safe_t = jnp.maximum(temps, 1e-6)[:, None]
    full = jax.vmap(jax.random.categorical)(keys, lg / safe_t)
    sampled = full
    if max_top_k > 0:
        vals, idx = jax.lax.top_k(lg, max_top_k)           # [N,K]
        k_eff = jnp.minimum(jnp.where(top_ks <= 0, max_top_k, top_ks),
                            max_top_k)
        masked = jnp.where(jnp.arange(max_top_k)[None, :] < k_eff[:, None],
                           vals, -jnp.inf)
        choice = jax.vmap(jax.random.categorical)(keys, masked / safe_t)
        trunc = jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]
        sampled = jnp.where(top_ks > 0, trunc, full)
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


def _traced_on(fwd, mesh):
    """`fwd` with `mesh` ambient (parallel.mesh.use_mesh) while it is
    traced: how a tensor-parallel engine's single-token forwards tell
    the decode attention that their pool is sharded — GSPMD cannot
    partition its Pallas kernel, so it keeps the einsum there."""
    from ..parallel.mesh import use_mesh

    @functools.wraps(fwd)
    def on_mesh(*args, **kw):
        with use_mesh(mesh):
            return fwd(*args, **kw)
    return on_mesh


def _pin_cache(cache, pin):
    """Pin the returned cache leaves to their input NamedShardings
    (tensor-parallel serving, `mesh=`): GSPMD would usually propagate
    the same layout, but pinning makes it a contract — the donated
    buffers alias exactly (out sharding == in sharding) and the tick's
    executable count cannot drift with propagation heuristics. `pin`
    is a {leaf: NamedSharding} dict closed over the jit (hashable,
    non-traced); None/missing leaves pass through untouched."""
    if not pin:
        return cache
    return {k: (jax.lax.with_sharding_constraint(v, pin[k])
                if pin.get(k) is not None else v)
            for k, v in cache.items()}


# --------------------------------------------------------- jitted bodies
# slot-state tuple riding through the decode tick (all [N], device-
# resident and DONATED alongside the cache — the host only downloads
# the sampled tokens, one small pull per tick)
#   (cur_tok, positions, active, temps, top_ks, req_ids, gen_idx)
def _decode_tick(params, cache, state, base_key, poison, *, fwd, cfg,
                 max_top_k, sampling, guard, oor_pos=None,
                 cache_pin=None, tele=False):
    """THE mixed step: all N slots advance one token. Each slot's
    current token is written at its own position; sampling runs in-jit;
    inactive slots compute too (fixed shape) but their output is masked
    and their slot region is overwritten at the next prefill.
    `sampling` is STATIC: greedy-only ticks skip the key-fold +
    categorical machinery entirely (~0.4 ms/tick on the CPU rung), so
    the tick has at most two traces for the engine's lifetime.
    `guard` is baked per engine (guardrails=): the per-row isfinite
    quarantine verdict folds into the token as a -1 sentinel (real ids
    are never negative), so flagging costs no extra host pull and no
    extra trace. `poison` [N] is the fault-injection multiplier
    (all-ones in production; testing.faults nan_logits sets one lane to
    nan INSIDE the jit so injected and organic non-finite logits
    exercise the exact same guard); multiplying by 1.0 is exact in
    IEEE fp, so guarded greedy/sampled streams stay bit-identical.
    `tele` (static, baked per engine) additionally returns the
    TICK_FIELDS int32 row (profiler/serving_telemetry) computed from
    values the tick already holds — it rides the same host pull as
    the token array and never touches the stream math. The forward
    is handed the active mask as `live=`: a family's counts leave idle
    slots out, and the dense pool's decode attention reads nothing for
    them (kernels/decode_attention.py) — no output of a live row
    depends on it."""
    toks, positions, active, temps, top_ks, req_ids, gen_idx = state
    # under the paged layout the pool is SHARED across rows, so an
    # inactive row (mid-chunked-prefill, its table already mapping
    # real — possibly shared — pages) must not scatter its garbage
    # K/V through the table: route its write past the table, onto the
    # scratch page (oor_pos = max_pages * page_size; dense rows own
    # their cache row outright, so oor_pos stays None there)
    fpos = (positions if oor_pos is None
            else jnp.where(active, positions, oor_pos))
    logits, cache = fwd(params, toks[:, None], cache, fpos, cfg,
                        live=active[:, None])
    lg = logits[:, 0].astype(jnp.float32)
    if guard:
        lg = lg * poison[:, None]
    with jax.named_scope("sample"):
        if sampling:
            keys = _slot_keys(base_key, req_ids, gen_idx)
            nxt = _sample(lg, temps, top_ks, keys, max_top_k)
        else:
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    nxt = jnp.where(active, nxt, 0).astype(jnp.int32)
    bad = jnp.zeros_like(active)
    if guard:
        row_ok = jnp.all(jnp.isfinite(lg), axis=-1)
        bad = active & ~row_ok
        nxt = jnp.where(bad, -1, nxt)
    inc = active.astype(jnp.int32)
    state = (nxt, positions + inc, active, temps, top_ks, req_ids,
             gen_idx + inc)
    if not tele:
        return nxt, _pin_cache(cache, cache_pin), state
    # in-tick telemetry row riding the SAME host pull as `nxt` (zero
    # extra transfers — profiler/serving_telemetry): what the tick
    # emitted/advanced/flagged, plus the attention tap
    from ..kernels.decode_attention import attended_tokens
    from ..profiler.serving_telemetry import pack_tick_fields
    trow = pack_tick_fields(
        tokens=jnp.sum(active & ~bad), active=jnp.sum(active),
        poisoned=jnp.sum(bad),
        attended=attended_tokens(positions, active))
    return nxt, trow, _pin_cache(cache, cache_pin), state


def _prefill_slot(params, cache, padded, true_len, slot, temps, top_ks,
                  req_ids, base_key, *, fwd, init_cache, cfg, max_top_k,
                  sampling, guard, cache_pin=None, family_prefill=None):
    """Bucketed prefill of ONE request into slot `slot`: run the padded
    prompt through a fresh single-row BUCKET-length cache (bit-identical
    K/V and logits to the greedy driver's full-length prefill — the
    masked softmax gives padded/absent positions an exact 0), sample the
    first token from the last REAL position's logits, and write the row
    into the pool, wiping the slot's previous occupant up to the bucket
    (anything staler is masked until decode overwrites it). Trace key:
    the bucket length only (true_len/slot are traced scalars). With
    `guard` (static, baked per engine) a non-finite first-token logit
    row folds into a -1 sentinel token — the quarantine verdict rides
    the pull the admission already makes. `family_prefill`
    (ModelFamily.prefill) stands in for the forward and the row copy
    where the family's pools are not written that way."""
    if family_prefill is not None:
        last, cache = family_prefill(params, cache, padded, true_len, slot,
                                     cfg)
        mini = None
    else:
        mini = init_cache(cfg, 1, padded.shape[1])
        logits, mini = fwd(params, padded, mini, 0, cfg)
        last = jax.lax.dynamic_slice_in_dim(
            logits, true_len - 1, 1, axis=1)[:, 0].astype(jnp.float32)
    if sampling:
        keys = _slot_keys(base_key, req_ids, jnp.zeros((1,), jnp.int32))
        first = _sample(last, temps, top_ks, keys, max_top_k)[0]
    else:
        first = jnp.argmax(last, axis=-1).astype(jnp.int32)[0]
    if guard:
        first = jnp.where(jnp.all(jnp.isfinite(last)), first, -1)
    if mini is not None:
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], mini["k"], (0, slot, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], mini["v"], (0, slot, 0, 0, 0)),
        }
    return first, _pin_cache(cache, cache_pin)


def _prefill_chunk(params, cache, padded, true_len, start, slot, temps,
                   top_ks, req_ids, base_key, *, fwd, cfg, max_top_k,
                   sampling, guard, cache_pin=None):
    """Paged/chunked prefill of ONE chunk into slot `slot`: run the
    padded chunk [1, cb] at absolute positions start.. against the
    slot's single-row paged view (its page-table row sliced out of the
    pool's "pt"), scattering the chunk's K/V into the pool pages, and
    sample a token from the chunk's LAST REAL position — meaningful
    only for the prompt's final chunk (logits at t0-1); the host
    ignores it (and skips the pull entirely) for earlier chunks.
    Trace key: the chunk bucket length only (true_len/start/slot are
    traced scalars), so chunking reuses the bucketed-prefill
    executable ceiling. Bit-parity: per-position K/V and the masked
    softmax are bit-identical whether the prompt runs as one pass or
    as chunks (pad/absent positions contribute an exact 0)."""
    row = jax.lax.dynamic_slice_in_dim(cache["pt"], slot, 1, axis=0)
    sub = {"k": cache["k"], "v": cache["v"], "pt": row}
    posv = jnp.reshape(start, (1,)).astype(jnp.int32)
    logits, sub = fwd(params, padded, sub, posv, cfg)
    last = jax.lax.dynamic_slice_in_dim(
        logits, true_len - 1, 1, axis=1)[:, 0].astype(jnp.float32)
    if sampling:
        keys = _slot_keys(base_key, req_ids, jnp.zeros((1,), jnp.int32))
        first = _sample(last, temps, top_ks, keys, max_top_k)[0]
    else:
        first = jnp.argmax(last, axis=-1).astype(jnp.int32)[0]
    if guard:
        first = jnp.where(jnp.all(jnp.isfinite(last)), first, -1)
    out = {"k": sub["k"], "v": sub["v"], "pt": cache["pt"]}
    return first, _pin_cache(out, cache_pin)


def _cow_copy(cache, src, dst, *, cache_pin=None):
    """Copy page `src` onto page `dst` across every layer of the pool
    (both k and v) — THE copy-on-write materialization, one jitted
    in-pool dynamic slice/update on the donated buffers; src/dst are
    traced scalars so the engine holds exactly one trace of this."""
    out = dict(cache)
    for key in ("k", "v"):
        pg = jax.lax.dynamic_slice_in_dim(cache[key], src, 1, axis=1)
        out[key] = jax.lax.dynamic_update_slice(
            cache[key], pg, (0, dst, 0, 0, 0))
    return _pin_cache(out, cache_pin)


# ----------------------------------------------------------- the engine
class ServingEngine:
    """Iteration-level scheduler over a fixed slot pool.

    >>> eng = ServingEngine(params, cfg, family="gpt", num_slots=8)
    >>> req = eng.submit(prompt_ids, max_new_tokens=32)
    >>> while eng.has_work():
    ...     for r, tok in eng.step():   # (request, token) emissions
    ...         ...
    >>> req.tokens

    `generate(prompts, ...)` wraps submit+drain for batch use.
    """

    def __init__(self, params, cfg, family="gpt", num_slots: int = 8,
                 max_len: Optional[int] = None, max_top_k: int = 0,
                 seed: int = 0, bucket_lo: int = 8,
                 decode_unroll: int = 0, max_queue: int = 0,
                 queue_policy: str = "reject", queue_ttl_s: float = 0.0,
                 watchdog_timeout: float = 0.0, retries: int = 2,
                 backoff_base: float = 0.05, backoff_max: float = 2.0,
                 guardrails: bool = True, kv_layout: str = "auto",
                 page_size: int = 16, num_pages: int = 0,
                 prefill_chunk: int = 0, prefix_sharing: bool = True,
                 spec_decode: str = "auto", gamma: int = 4,
                 draft_layers: int = 0, mesh=None, tp_axis: str = "tp",
                 quant: str = "auto", telemetry: str = "auto",
                 telemetry_jsonl: Optional[str] = None,
                 telemetry_every: int = 32, tracing: bool = False,
                 multi_tick: int = 0, host_kv_bytes: int = 0,
                 _given_params: Optional[dict] = None):
        self.family = (family_for(family) if isinstance(family, str)
                       else family)
        self.cfg = cfg
        self.num_slots = int(num_slots)
        # what the family cannot run yet is refused by name; an option
        # left at 'auto' resolves to off for it instead
        refuses = self.family.refuses
        if mesh is not None:
            self._refuse("mesh")
        if "spec_decode" in refuses and spec_decode == "auto":
            spec_decode = "off"
        if "multi_tick" in refuses and multi_tick in (0, "auto"):
            multi_tick = 1
        if "kv_layout='paged'" in refuses and kv_layout == "auto":
            kv_layout = "dense"
        if "quant" in refuses and quant == "auto":
            quant = "off"
        if prefill_chunk:
            self._refuse("prefill_chunk")
        if host_kv_bytes:
            self._refuse("host_kv_bytes")
        # --------------------------------------- tensor-parallel serving
        # mesh= shards THIS engine's decode tick over `tp_axis`: params
        # per the family's module-level SERVING_PARAM_SPECS (the
        # training TP split remapped — parallel.mesh.tp_specs), the KV
        # cache/page pool per kernels/decode_attention.cache_pspecs
        # (head-sharded, shape-aware degrade to replicated), page
        # tables and the per-slot decode state replicated. Every
        # host->device upload goes through _rep so the jitted bodies
        # only ever see mesh-consistent placements; the host pull stays
        # ONE small (replicated) array per tick per mesh.
        self.mesh = mesh
        self.tp_axis = str(tp_axis)
        if mesh is not None:
            if self.tp_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh {dict(mesh.shape)} has no {self.tp_axis!r} "
                    "axis (build it via parallel.mesh.build_mesh("
                    "{'tp': N}) or pass tp_axis=)")
            if self.family.serving_specs is None:
                raise ValueError(
                    f"family {self.family.name!r} has no "
                    "SERVING_PARAM_SPECS — it cannot run tensor-"
                    "parallel (see models/gpt.py)")
        self.tp = int(mesh.shape[self.tp_axis]) if mesh is not None else 1
        self._rep_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._rep_sharding = NamedSharding(mesh, PartitionSpec())
        # ------------------------------------------- speculative decode
        from .spec_decode import resolve_spec
        self.spec = resolve_spec(spec_decode)
        # whether drafts CAN run: set_spec_drafts (brownout) may flip
        # self.spec live, but only back up to this construction-time cap
        self._spec_capable = self.spec
        if self.spec:
            self._refuse("spec_decode")
        n_layers = int(getattr(cfg, "num_layers", 0))
        self.spec_gamma = int(gamma)
        self.spec_draft_layers = int(draft_layers) or max(1, n_layers // 2)
        if self.spec:
            if self.spec_gamma < 1:
                raise ValueError(f"gamma must be >= 1; got {gamma}")
            if not 1 <= self.spec_draft_layers <= max(n_layers, 1):
                raise ValueError(
                    f"draft_layers ({self.spec_draft_layers}) must be in "
                    f"1..num_layers ({n_layers})")
            import inspect
            try:
                sig = inspect.signature(self.family.forward_cached)
            except (TypeError, ValueError):
                sig = None
            if sig is not None and "layers" not in sig.parameters:
                raise ValueError(
                    f"family {self.family.name!r}: forward_cached does "
                    "not accept layers= — the truncated-depth self-draft "
                    "needs it (see models/gpt.py gpt_forward_cached)")
        # --------------------------------------------- fused multi-tick
        # K is BAKED into the decode executable (a lax.scan of length
        # K), so the jit cache keys of engines with different K never
        # collide.
        from .multi_tick import resolve_multi_tick
        self.mt_k = resolve_multi_tick(multi_tick)
        if self.mt_k > 1:
            self._refuse("multi_tick")
        # per-dispatch emission width: how many tokens one host pull
        # may carry per slot (spec emits gamma+1 columns per tick)
        self._tick_span = self.mt_k * ((self.spec_gamma + 1)
                                       if self.spec else 1)
        # ------------------------------------------------- cache layout
        if kv_layout not in ("auto", "dense", "paged"):
            raise ValueError(f"kv_layout {kv_layout!r} "
                             "(auto|dense|paged)")
        self.paged = kv_layout == "paged"      # 'auto' is dense
        if self.paged:
            self._refuse("kv_layout='paged'")
        self.page_size = int(page_size)
        self.prefill_chunk = int(prefill_chunk)
        self.prefix_sharing = bool(prefix_sharing)
        # ------------------------------------------------ SLO guardrails
        if queue_policy not in ("reject", "shed_oldest"):
            raise ValueError(f"queue_policy {queue_policy!r} "
                             "(reject|shed_oldest)")
        self.max_queue = int(max_queue)       # 0 = unbounded
        self.queue_policy = queue_policy
        self.queue_ttl_s = float(queue_ttl_s)  # 0 = no TTL
        self.watchdog_timeout = float(watchdog_timeout)  # 0 = no watchdog
        self.retries = int(retries)           # device-call retry budget
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        self.guardrails = bool(guardrails)    # in-jit isfinite quarantine
        self.max_len = int(max_len or cfg.max_seq_len)
        if self.max_len > getattr(cfg, "max_seq_len", self.max_len):
            # positions past the table (gpt wpe / llama rope cache) would
            # CLAMP, silently corrupting every later token
            raise ValueError(
                f"max_len ({self.max_len}) exceeds the model's "
                f"max_seq_len ({cfg.max_seq_len}): position embeddings "
                "beyond the table would clamp, not error")
        self.max_top_k = int(max_top_k)
        self.bucket_lo = int(bucket_lo)
        # --------------------------------------- weight-only int8 quant
        # Quantization is a LEAF REWRITE at build: the fp matmul weights
        # become <name>_q/<name>_scale pairs (plus the transposed head
        # copy), the cached forwards pick them up from the tree through
        # kernels/quant_matmul.leaf_matmul, and the jitted bodies /
        # tick invariants are untouched — same state tuple, same one
        # pull per tick, same trace ceilings.
        from ..kernels.quant_matmul import resolve_quant
        self.quant = resolve_quant(quant)
        if self.quant:
            self._refuse("quant")
        self._serving_specs = self.family.serving_specs
        self._quant_info = None
        from ..quantization.serving import (quantize_serving_params,
                                            round_serving_params,
                                            tree_bytes)
        given = params if _given_params is None else _given_params
        if self.quant:
            params, qspecs, self._quant_info = quantize_serving_params(
                params, self.family.name, self._serving_specs)
            if self._serving_specs is not None:
                self._serving_specs = qspecs
        # what stays floating point is rounded to the compute dtype
        # here, once, and not by the forward's `astype` inside every
        # tick and prefill: the second leaf rewrite, by the leaves'
        # dtypes and the family's table alone (a no-op on a tree the
        # router has rounded already). Only the rounded tree is kept.
        params = round_serving_params(params, self.family.name, cfg)
        self._weights_info = {
            "weights_bytes": tree_bytes(params),
            "weights_given_bytes": tree_bytes(given),
            "weights_rounded_leaves": sum(
                1 for n, v in params.items()
                if n in given and v.dtype != given[n].dtype)}
        self._params = (self._shard_params(params) if mesh is not None
                        else params)
        self._cache_pin = None        # leaf -> NamedSharding under mesh=
        if self.paged:
            if self.page_size < 1:
                raise ValueError(f"page_size must be >= 1; "
                                 f"got {self.page_size}")
            ps = self.page_size
            self.max_pages = -(-self.max_len // ps)      # ceil
            # dense-equivalent capacity by default (+1 scratch); a
            # smaller num_pages is the capacity lever (bench_serving
            # --capacity races paged vs dense at equal HBM)
            self.num_pages = int(num_pages) or \
                self.num_slots * self.max_pages + 1
            self._pool = _PagePool(self.num_pages, ps)
            self._ptab = np.zeros((self.num_slots, self.max_pages),
                                  np.int32)
            self._pt_dirty = False
        self._cache = self._new_cache()
        self._base_key = self._rep(jax.random.PRNGKey(seed))

        # fully unroll shallow stacks: at T=1 the matvecs are tiny and
        # the loop's own per-layer steps weigh in (bit-identical numerics
        # — models/gpt.py decode_scan_unroll; the KV pool rides the
        # scan's carry either way and is never sliced or restacked).
        # 0 = auto, 1 = keep the scan.
        # Auto only applies when the config still carries the field's
        # default (1): an explicitly tuned cfg.decode_scan_unroll wins.
        cfg_unroll = getattr(cfg, "decode_scan_unroll", None)
        if decode_unroll == 0:
            if cfg_unroll not in (None, 1):
                decode_unroll = cfg_unroll
            else:
                layers = getattr(cfg, "num_layers", 0)
                decode_unroll = layers if 0 < layers <= 8 else 1
        run_cfg = cfg
        if cfg_unroll not in (None, decode_unroll):
            try:
                run_cfg = dataclasses.replace(
                    cfg, decode_scan_unroll=decode_unroll)
            except TypeError:        # non-dataclass custom family config
                run_cfg = cfg

        n = self.num_slots
        # host MIRRORS of the slot state (scheduling reads these); the
        # device copies ride donated through the tick and are rebuilt
        # from the mirrors only when admission/eviction dirties them
        self._positions = np.zeros(n, np.int32)   # tokens in each slot
        self._active = np.zeros(n, bool)
        self._cur_tok = np.zeros(n, np.int32)     # last sampled token
        self._temps = np.zeros(n, np.float32)
        self._top_ks = np.zeros(n, np.int32)
        self._req_ids = np.zeros(n, np.int32)
        self._gen_idx = np.zeros(n, np.int32)     # next sample index
        # multi-tick early-exit inputs (EOS id, -1 = none; token
        # budget): host mirrors here, device copies in _daux, rebuilt
        # alongside the state tuple when _dirty (multi_tick.py scans
        # retire slots ON DEVICE by these rules)
        self._eos_ids = np.full(n, -1, np.int32)
        self._max_new = np.zeros(n, np.int32)
        self._daux = None
        self._dstate = None                       # device state tuple
        self._tick_rows = None         # request of each row in the last tick
        self._last_pull_end = 0.0      # perf_counter when its pull returned
        self._dirty = True
        self._slot_req: List[Optional[Request]] = [None] * n
        self._queue: collections.deque = collections.deque()
        self._next_id = 0
        self._ticks = 0                  # step() calls (fault/deadline clock)
        self._poison_ones = self._rep(np.ones(n, np.float32))  # reused:
        #                  the steady-state tick uploads NO poison array
        # SLO samples (host wall-clock, ms): TTFT includes queue wait;
        # inter-token latency is per-emission, quantized to tick times
        self._slo_ttft: collections.deque = collections.deque(maxlen=8192)
        self._slo_itl: collections.deque = collections.deque(maxlen=8192)

        # ----------------------------------------- in-tick telemetry
        # the decode tick computes the TICK_FIELDS int32 row in-jit and
        # returns it NEXT TO the token array; both ride the ONE host
        # pull the tick already makes (profiler/serving_telemetry —
        # zero extra pulls, zero extra traces, kill switch
        # PADDLE_TPU_SERVING_TELEMETRY). The host joins scheduler-side
        # fields (queue depth, prefilling, pages in use) + tick wall ms
        # into serving_tick records: a bounded in-memory ring
        # (`tick_records()`) and optionally a JSONL stream
        # (`telemetry_jsonl=`, flushed every `telemetry_every` records
        # on a background writer).
        from ..profiler.serving_telemetry import (ServingTelemetry,
                                                  resolve_serving_telemetry)
        self._tick_tele = resolve_serving_telemetry(telemetry)
        self._tick_log = None
        if self._tick_tele:
            self._tick_log = ServingTelemetry(
                path=telemetry_jsonl, every=telemetry_every,
                meta={"family": self.family.name,
                      "layout": "paged" if self.paged else "dense",
                      "spec": bool(self.spec),
                      "quant": "int8" if self.quant else "off",
                      "multi_tick": self.mt_k,
                      "tp": self.tp, "num_slots": self.num_slots,
                      "max_len": self.max_len},
                on_flush=self._publish_tier_gauges)
        # ---------------------------------------- request-scoped traces
        # opt-in (tracing=True): submit() mints a RequestTrace
        # (profiler/tracing) and the scheduler emits parented spans
        # through queue -> prefill chunks -> decode -> the terminal
        # _finish; a router passes its own trace down via submit(_trace=)
        # so routed requests keep ONE tree across dispatch and replay.
        self._tracer = None
        if tracing:
            from ..profiler import tracing as _tracing
            self._tracer = _tracing.tracer()

        self._run_cfg = run_cfg       # the unroll-resolved config the
        #                               jitted bodies close over — kept
        #                               so rebuild_on_mesh re-jits the
        #                               SAME computation on a new mesh
        if self.paged:
            self._slot_reserve = np.zeros(self.num_slots, np.int64)
            self._prefilling: collections.deque = collections.deque()
            self._raise_cow = False          # injected cow_raise fault
        self._raise_migrate = False          # injected migrate_raise fault
        self._make_executables()

        from ..profiler import flight_recorder
        self._flight = flight_recorder.recorder()
        self._puller = None            # lazy persistent watchdog worker

        self._m_occ = monitor.gauge("serving.slot_occupancy")
        self._m_queue = monitor.gauge("serving.queue_depth")
        # queue wait is a DISTRIBUTION (the admission-latency half of
        # TTFT): a last-write-wins gauge hid the tail, the bounded-
        # reservoir histogram snapshots p50/p95/p99
        self._m_qwait = monitor.histogram("serving.queue_wait_ms")
        self._m_tok = monitor.counter("serving.tokens_emitted")
        self._m_pre = monitor.counter("serving.prefills")
        self._m_tick = monitor.counter("serving.decode_ticks")
        self._m_sub = monitor.counter("serving.requests_submitted")
        self._m_done = monitor.counter("serving.requests_completed")
        self._m_rej = monitor.counter("serving.rejected")
        self._m_retry = monitor.counter("serving.retries")
        self._m_fault = monitor.counter("serving.faults")
        self._reason_ctr = {
            "timeout": monitor.counter("serving.timeout"),
            "cancelled": monitor.counter("serving.cancelled"),
            "poisoned": monitor.counter("serving.poisoned"),
            "evicted": monitor.counter("serving.evicted"),
        }
        # kv-pool surface (stay 0 under the dense layout)
        self._m_pages = monitor.gauge("serving.pages_in_use")
        self._m_shared = monitor.gauge("serving.pages_shared")
        self._m_cow = monitor.counter("serving.cow_copies")
        self._m_chunks = monitor.counter("serving.prefill_chunks")
        # kv-pool HBM in bytes, next to pages_in_use: dense = the full
        # preallocated cache (constant, set once); paged = pages_in_use
        # x per-page bytes, republished with the page gauges
        self._m_kv_bytes = monitor.gauge("serving.kv_pool_bytes")
        self._m_oom = monitor.counter("serving.oom_forensics")
        if self.paged:
            self._page_bytes = _pool_bytes(self._cache) // self.num_pages
            self._publish_pool_gauges()
        else:
            self._m_kv_bytes.set(_pool_bytes(self._cache))
        # ------------------------------------------ host-tier KV offload
        # paged + prefix_sharing only: the pool's LRU eviction demotes
        # registered pages to host ndarrays instead of dropping them,
        # and admission swaps them back (inference/host_kv.py). 0 = off.
        self.host_kv_bytes = int(host_kv_bytes or 0)
        if self.host_kv_bytes < 0:
            raise ValueError(
                f"host_kv_bytes must be >= 0; got {host_kv_bytes}")
        self._host_tier = None
        self._host_stage: dict = {}    # prefix key -> (dk, dv) prefetch
        if self.paged and self.prefix_sharing and self.host_kv_bytes > 0:
            from .host_kv import HostKVTier
            self._host_tier = HostKVTier(self.host_kv_bytes)
            self._pool.on_evict = self._spill_page
        # disaggregation surface: gauges ride the telemetry flush
        # cadence via on_flush (zero extra device pulls)
        self._m_kv_host = monitor.gauge("serving.kv_host_bytes")
        self._m_ticks_pull = monitor.gauge("serving.ticks_per_pull")
        self._m_ticks_pull.set(self.mt_k)
        self._m_spill = monitor.counter("serving.host_spills")
        self._m_swapin = monitor.counter("serving.host_swapins")
        # speculative-decode surface (stay 0 with spec off): proposed =
        # gamma per greedy slot per tick, accepted = drafts the verify
        # kept; the rate gauge is THIS ENGINE's cumulative
        # accepted/proposed (the counters are process-global)
        self._m_spec_prop = monitor.counter("serving.spec_proposed")
        self._m_spec_acc = monitor.counter("serving.spec_accepted")
        self._m_spec_rate = monitor.gauge("serving.spec_accept_rate")
        self._spec_prop_total = 0
        self._spec_acc_total = 0
        # what the engine holds after its two leaf rewrites, and what
        # it was handed (gauges: the rewrites engage once, at build)
        monitor.gauge("serving.weights_bytes").set(
            self._weights_info["weights_bytes"])
        monitor.gauge("serving.weights_given_bytes").set(
            self._weights_info["weights_given_bytes"])
        # weight-only quant surface (stays 0/unset with quant off):
        # the bytes gauges report THIS engine's weight tree before and
        # after the int8 rewrite (the HBM halving observable); the
        # counter advances by the number of fused dequant-matmuls each
        # device pass executes (per_layer quantized leaves x depth +
        # the head — a full pass per decode tick / prefill chunk, plus
        # gamma truncated draft passes per spec tick)
        self._m_qw = monitor.gauge("serving.quant_weights_bytes")
        self._m_fpw = monitor.gauge("serving.fp_weights_bytes")
        self._m_qmm = monitor.counter("serving.quant_matmuls")
        self._qmm_full = self._qmm_draft = 0
        if self._quant_info:
            self._m_qw.set(self._quant_info["quant_bytes"])
            self._m_fpw.set(self._quant_info["fp_bytes"])
            self._qmm_full = (self._quant_info["per_layer"] * n_layers
                              + self._quant_info["head"])
            self._qmm_draft = (self._quant_info["per_layer"]
                               * self.spec_draft_layers
                               + self._quant_info["head"])

    def _refuse(self, option: str) -> None:
        """Raise UnsupportedOptionError if the family refuses `option`."""
        if option in self.family.refuses:
            raise UnsupportedOptionError(self.family.name, option)

    # -------------------------------------------------------- page pool
    def _init_paged_cache(self):
        """The paged pool buffers: {"k","v": [L, P, page_size, KV, hd]}
        in the family's cache dtype (probed shape-only via eval_shape —
        no dense allocation) + the device page table "pt"."""
        probe = jax.eval_shape(
            lambda: self.family.init_cache(self.cfg, 1, 1))
        shp = probe["k"].shape                 # [L, 1, 1, KV, hd]
        pages = (shp[0], self.num_pages, self.page_size) + shp[3:]
        return {"k": jnp.zeros(pages, probe["k"].dtype),
                "v": jnp.zeros(pages, probe["v"].dtype),
                "pt": jnp.asarray(self._ptab)}

    # --------------------------------------------- tensor-parallel seams
    def _rep(self, x, dtype=None):
        """Upload one host value to the device(s): plain jnp.asarray on
        a single-device engine; REPLICATED over the serving mesh under
        mesh= (a committed single-device array mixed into a sharded jit
        would be a placement error). Every host->device upload in the
        engine routes here, so the tick's inputs are mesh-consistent by
        construction."""
        if self._rep_sharding is None:
            return (jnp.asarray(x) if dtype is None
                    else jnp.asarray(x, dtype))
        a = np.asarray(x, dtype) if dtype is not None else np.asarray(x)
        return jax.device_put(a, self._rep_sharding)

    def _shard_params(self, params):
        """device_put the param tree per the family's module-level
        SERVING_PARAM_SPECS (heads/ffn column-row split on the tp
        axis, embeddings vocab-parallel, norms replicated); leaves the
        table doesn't name — and dims the tp degree doesn't divide —
        replicate (parallel.mesh.sharding_for's shape-aware degrade)."""
        from jax.sharding import PartitionSpec
        from ..parallel.mesh import sharding_for
        specs = self._serving_specs or {}
        return {name: jax.device_put(
                    v, sharding_for(specs.get(name, PartitionSpec()),
                                    self.mesh, shape=np.shape(v)))
                for name, v in params.items()}

    def _new_cache(self):
        """Allocate the pool cache (dense slot pool or paged block
        pool), sharded over the serving mesh when one is set — the KV-
        head axis per kernels/decode_attention.cache_pspecs, the page
        table replicated. Shared by __init__ and _hard_reset so a
        recovery reallocation can never come back with a different
        layout (the jitted tick would silently recompile). Under mesh=
        the pool is born sharded — jit with out_shardings, shapes from
        eval_shape — so no device ever holds the WHOLE pool, even
        transiently: the point of tp is a KV pool bigger than one
        chip's HBM, and a full-pool staging allocation would OOM at
        construction exactly when tp matters. (Params take the same
        no-staging path for free: _shard_params device_puts the host
        tree straight to its NamedShardings.)"""
        def mk():
            if self.paged:
                return self._init_paged_cache()
            return self.family.init_cache(self.cfg, self.num_slots,
                                          self.max_len)
        if self.mesh is None:
            return mk()
        if self._cache_pin is None:
            from ..kernels.decode_attention import cache_pspecs
            from ..parallel.mesh import sharding_for
            from jax.sharding import PartitionSpec
            # tp == 1 (a replica placed on one device): nothing to
            # split, and a spec that names a size-one axis is not the
            # spec jit hands back (it returns P()), so the fresh pool
            # and the donated one would key two jit cache entries
            specs = (cache_pspecs(self.paged, self.tp_axis)
                     if self.tp > 1 else {})
            shapes = jax.eval_shape(mk)
            self._cache_pin = {
                k: sharding_for(specs.get(k, PartitionSpec()),
                                self.mesh, shape=v.shape)
                for k, v in shapes.items()}
        return jax.jit(mk, out_shardings=self._cache_pin)()

    def _build_decode(self, spec: bool):
        """The decode-tick jit for `spec` drafts on or off, cached per
        flag in `_decode_variants` (reset by _make_executables on mesh
        rebuild). Four bodies: multi-tick x spec crossed — all share
        the donation/static signature, so `_decode_guarded` only varies
        its ARG assembly (keyed off self.spec / self.mt_k)."""
        cached = self._decode_variants.get(bool(spec))
        if cached is not None:
            return cached
        run_cfg = self._run_cfg
        _oor = (self.max_pages * self.page_size if self.paged else None)
        fwd = self.family.forward_cached
        if self.tp > 1:
            fwd = _traced_on(fwd, self.mesh)
        if self.mt_k > 1 and spec:
            from .multi_tick import multi_tick_spec_scan
            fn = jax.jit(
                functools.partial(multi_tick_spec_scan,
                                  fwd=fwd,
                                  cfg=run_cfg, max_top_k=self.max_top_k,
                                  guard=self.guardrails,
                                  gamma=self.spec_gamma,
                                  draft_layers=self.spec_draft_layers,
                                  k_ticks=self.mt_k,
                                  max_len=self.max_len,
                                  oor_pos=_oor,
                                  cache_pin=self._cache_pin,
                                  tele=self._tick_tele),
                donate_argnums=(1, 2), static_argnames=("sampling",))
        elif self.mt_k > 1:
            from .multi_tick import multi_tick_scan
            fn = jax.jit(
                functools.partial(multi_tick_scan,
                                  fwd=fwd,
                                  cfg=run_cfg, max_top_k=self.max_top_k,
                                  guard=self.guardrails,
                                  k_ticks=self.mt_k,
                                  max_len=self.max_len,
                                  oor_pos=_oor,
                                  cache_pin=self._cache_pin,
                                  tele=self._tick_tele),
                donate_argnums=(1, 2), static_argnames=("sampling",))
        elif spec:
            from .spec_decode import spec_tick
            fn = jax.jit(
                functools.partial(spec_tick,
                                  fwd=fwd,
                                  cfg=run_cfg, max_top_k=self.max_top_k,
                                  guard=self.guardrails,
                                  gamma=self.spec_gamma,
                                  draft_layers=self.spec_draft_layers,
                                  oor_pos=_oor,
                                  cache_pin=self._cache_pin,
                                  tele=self._tick_tele),
                donate_argnums=(1, 2), static_argnames=("sampling",))
        else:
            fn = jax.jit(
                functools.partial(_decode_tick,
                                  fwd=fwd,
                                  cfg=run_cfg, max_top_k=self.max_top_k,
                                  guard=self.guardrails, oor_pos=_oor,
                                  cache_pin=self._cache_pin,
                                  tele=self._tick_tele),
                donate_argnums=(1, 2), static_argnames=("sampling",))
        self._decode_variants[bool(spec)] = fn
        return fn

    def set_spec_drafts(self, enabled: bool) -> bool:
        """Toggle speculative-decode drafts live (the brownout ladder's
        level-1 lever): flipping OFF swaps the decode jit to the plain
        tick — drafts burn extra FLOPs for latency, and greedy streams
        are bit-identical with or without them, so the switch frees
        capacity with nothing user-visible. Only an engine BUILT with
        spec on can re-enable (`enabled=True` is a no-op otherwise);
        the first flip in each direction compiles the other variant
        once (a warmup-class recompile — the zero-recompile invariant
        counts steady-state ticks, and each variant's trace cache
        persists across later flips). Returns the live spec flag."""
        want = bool(enabled) and self._spec_capable
        if want == self.spec:
            return self.spec
        self.spec = want
        self._tick_span = self.mt_k * ((self.spec_gamma + 1) if want
                                       else 1)
        self._decode = self._build_decode(want)
        return self.spec

    def _make_executables(self) -> None:
        """Build (or REBUILD) the jitted bodies — decode tick, bucketed/
        chunked prefill, COW page copy — from the engine's current mesh
        state. Extracted from __init__ so `rebuild_on_mesh` (preemption
        recovery) can re-jit on the surviving mesh: the partials close
        over `self._cache_pin`, which a mesh change invalidates. Must
        run AFTER `_new_cache` has pinned the cache layout (the pin
        dict is closed over by identity). Fresh jits start with empty
        trace caches — one warmup recompile per body, then the
        trace-count ceilings hold exactly as at first construction."""
        run_cfg = self._run_cfg
        self._repin = None      # lazy identity re-pin (see _pin_cache_host)
        # the decode jit is keyed by the LIVE spec flag: brownout's
        # set_spec_drafts swaps between the spec and non-spec variants
        # without touching prefill/COW, and a mesh rebuild resets the
        # cache (the partials close over a pin the new mesh invalidates)
        self._decode_variants = {}
        self._decode = self._build_decode(self.spec)
        if self.paged:
            self._prefill = jax.jit(
                functools.partial(_prefill_chunk,
                                  fwd=self.family.forward_cached,
                                  cfg=run_cfg, max_top_k=self.max_top_k,
                                  guard=self.guardrails,
                                  cache_pin=self._cache_pin),
                donate_argnums=(1,), static_argnames=("sampling",))
            self._cow = jax.jit(
                functools.partial(_cow_copy,
                                  cache_pin=self._cache_pin),
                donate_argnums=(0,))
        else:
            self._prefill = jax.jit(
                functools.partial(_prefill_slot,
                                  fwd=self.family.forward_cached,
                                  init_cache=self.family.init_cache,
                                  cfg=run_cfg, max_top_k=self.max_top_k,
                                  guard=self.guardrails,
                                  cache_pin=self._cache_pin,
                                  family_prefill=self.family.prefill),
                donate_argnums=(1,), static_argnames=("sampling",))

    def pool_stats(self) -> dict:
        """The kv-pool observable (paged layout only): page states,
        shared/COW/chunk counters, and the HBM the pool holds vs what
        the dense layout would."""
        if not self.paged:
            return {"layout": "dense"}
        st = self._pool.stats()
        st["layout"] = "paged"
        st["cow_copies"] = self._m_cow.value
        st["prefill_chunks"] = self._m_chunks.value
        if self._host_tier is not None:
            st["host_tier"] = self._host_tier.stats()
        return st

    def quant_stats(self) -> dict:
        """The weight-only quant observable: fp vs int8 weight bytes
        and the per-pass fused-matmul counts (quantization/serving.py
        info dict), or {"quant": "off"}."""
        if not self._quant_info:
            return {"quant": "off"}
        return {"quant": "int8", **self._quant_info}

    def weights_stats(self) -> dict:
        """Bytes of the params tree this engine holds (`weights_bytes`)
        against the tree it was handed (`weights_given_bytes`), and how
        many leaves the build rounded to the compute dtype
        (`weights_rounded_leaves`; quantization/serving.py
        round_serving_params)."""
        return dict(self._weights_info)

    def _publish_pool_gauges(self) -> None:
        if not self.paged:
            return
        pages = int((self._pool.ref[1:] > 0).sum())
        self._m_pages.set(pages)
        self._m_shared.set(int((self._pool.ref[1:] > 1).sum()))
        self._m_kv_bytes.set(pages * self._page_bytes)

    def _publish_tier_gauges(self) -> None:
        """Disaggregation gauges: host-side bookkeeping only (zero
        extra device pulls), published on the telemetry FLUSH cadence
        (ServingTelemetry on_flush=) and with the per-step pool gauges.
        The spill/swap-in COUNTERS advance at event time instead
        (_spill_page / _admit_paged) — process-global counters can't
        take last-writer deltas with several engines alive."""
        if self._host_tier is not None:
            self._m_kv_host.set(self._host_tier.bytes)

    # ---------------------------------------------- host-tier KV offload
    def _spill_page(self, pid: int, key) -> None:
        """_PagePool.on_evict tap: demote the evicting registered page
        to the host tier before its prefix-map entry drops. The page is
        FROZEN (registered => COW-immutable), so the copy taken here is
        bit-identical to what a device hit would read; the engine is
        single-threaded, so the pool never evicts mid-write. Skips keys
        the tier already holds (a page that round-tripped host ->
        device -> eviction again)."""
        if self._host_tier is None or key in self._host_tier:
            return
        k_np = np.asarray(self._cache["k"][:, pid])
        v_np = np.asarray(self._cache["v"][:, pid])
        if self._host_tier.put(key, k_np, v_np):
            self._m_spill.add()

    def _prefetch_host(self, req: "Request") -> None:
        """Asynchronous swap-in ahead of admission: while the head-of-
        line request WAITS for capacity, start `jax.device_put` uploads
        of the host-tier pages its prefix walk will hit, so by the time
        `_admit_paged` maps them the transfers have overlapped the
        wait. Staged uploads park in `_host_stage` (key -> (dk, dv))
        and are consumed (or dropped) by the next admission of that
        key; idempotent per key."""
        if self._host_tier is None or not self.prefix_sharing:
            return
        ps = self.page_size
        toks = req.prompt
        for j in range(len(toks) // ps):
            key = _prefix_key(toks, (j + 1) * ps)
            if key in self._host_stage or key in self._pool.by_key:
                continue
            pair = self._host_tier.get(key)
            if pair is None:
                break        # tier walk stops at the first miss too
            self._host_stage[key] = (self._rep(pair[0]),
                                     self._rep(pair[1]))

    # ------------------------------------------------- memory observability
    def memory_ledger(self) -> dict:
        """This engine's `cost_model.serving_memory_ledger` — per-chip
        HBM attribution (weights / quantized pairs / kv pool / decode
        scratch) from the LIVE configuration, with `weights_stats()`
        under "held". The analytical half that
        `profiler.mem_audit.audit_serving_memory` diffs against the
        compiled decode tick, and the first page of an oom_forensics
        dump."""
        if self.family.refuses:
            # no cost-model dims for this family: what the device holds
            weights = self._weights_info["weights_bytes"]
            kv = _pool_bytes(self._cache)
            return {"weights": weights, "kv_pool_device": kv,
                    "total": weights + kv, "held": self.weights_stats()}
        from ..cost_model import jnp_dtype_bytes, serving_memory_ledger
        ledger = serving_memory_ledger(
            self.cfg, family=self.family.name,
            layout="paged" if self.paged else "dense",
            quant="int8" if self._quant_info else "off",
            num_slots=self.num_slots, max_len=self.max_len,
            page_size=self.page_size,
            num_pages=self.num_pages if self.paged else 0,
            cache_bytes_per_elem=int(self._cache["k"].dtype.itemsize),
            dtype_bytes=jnp_dtype_bytes(getattr(self.cfg, "dtype", None)),
            tp=self.tp,
            host_kv_bytes=(int(self._host_tier.bytes)
                           if self._host_tier is not None else 0))
        # beside the analytical bytes, what the tree really holds
        ledger["held"] = self.weights_stats()
        return ledger

    def compiled_memory_stats(self, sampling: bool = False) -> dict:
        """XLA's compiled memory accounting for THIS engine's decode
        tick: re-lower `self._decode` over the avals of the live state
        (shapes/dtypes only — no tick dispatched, no host pull, no
        device transfer) and read `memory_analysis()` through the
        profiler.mem_audit seam. The jit's trace cache makes the
        compile a warm no-op when the tick already ran with the same
        sampling mode."""
        from ..profiler.mem_audit import compiled_memory_stats
        aval = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        cache = jax.tree_util.tree_map(aval, self._cache)
        if self.paged and "pt" not in cache:
            cache["pt"] = jax.ShapeDtypeStruct(
                self._ptab.shape, self._ptab.dtype)
        # the tick's dstate tuple, aval'd from the HOST mirrors so a
        # dirty (not-yet-replicated) state needs no device round-trip
        dstate = tuple(jax.ShapeDtypeStruct(m.shape, m.dtype)
                       for m in (self._cur_tok, self._positions,
                                 self._active, self._temps,
                                 self._top_ks, self._req_ids,
                                 self._gen_idx))
        args = [jax.tree_util.tree_map(aval, self._params), cache,
                dstate, aval(self._base_key), aval(self._poison_ones)]
        if self.spec:
            args.append(aval(self._poison_ones))
        if self.mt_k > 1:
            args += [jax.ShapeDtypeStruct(self._eos_ids.shape,
                                          self._eos_ids.dtype),
                     jax.ShapeDtypeStruct(self._max_new.shape,
                                          self._max_new.dtype)]
        compiled = self._decode.lower(
            *args, sampling=bool(sampling)).compile()
        return compiled_memory_stats(compiled)

    def _dump_oom_forensics(self, where: str, exc) -> None:
        """The OOM black box: when a dispatch seam sees
        RESOURCE_EXHAUSTED, dump ledger + live-array census (summarized
        by shape/dtype/sharding, byte-sorted) + pool/quant stats +
        active config to the flight dir BEFORE the retry/reset
        machinery runs, so the post-mortem names the tenant instead of
        guessing. Forensics must never mask the original failure —
        every step is best-effort."""
        try:
            from ..profiler.mem_audit import live_array_census
            census = live_array_census()
            self._m_oom.add()
            self._flight.configure(oom_forensics={
                "where": where, "tick": self._ticks,
                "error": repr(exc)[:500],
                "ledger": self.memory_ledger(),
                "census": census["rows"],
                "live_bytes": census["total_bytes"],
                "pool": self.pool_stats(), "quant": self.quant_stats(),
                "config": {"layout": "paged" if self.paged else "dense",
                           "num_slots": self.num_slots,
                           "max_len": self.max_len, "tp": self.tp}})
            self._flight.note(oom_forensics=where, tick=self._ticks)
            self._flight.dump("oom_forensics")
        except Exception:                      # noqa: BLE001
            pass

    # ------------------------------------------------------- observables
    def trace_counts(self):
        """(decode traces, prefill traces) — the zero-recompile
        acceptance observable: decode holds at one trace per sampling
        mode (<= 2 forever); prefill grows only with NEW (prompt
        bucket, sampling mode) pairs — ceiling 2·log2(max_len)."""
        return self._decode._cache_size(), self._prefill._cache_size()

    def tick_records(self) -> list:
        """The in-tick telemetry ring (profiler/serving_telemetry
        serving_tick / serving_prefill records, newest-last); empty
        with telemetry off. tools/serving_attrib.py joins these with
        the cost-model ledger."""
        return [] if self._tick_log is None else self._tick_log.records()

    def flush_telemetry(self, timeout: Optional[float] = None) -> None:
        """Block until every pending serving_tick record is on disk
        (no-op without telemetry_jsonl=)."""
        if self._tick_log is not None:
            self._tick_log.flush(timeout=timeout)

    def has_work(self) -> bool:
        # a slot mid-chunked-prefill holds a request but is not yet
        # active for decode — still work
        return (bool(self._queue) or bool(self._active.any())
                or any(r is not None for r in self._slot_req))

    @property
    def active_requests(self):
        return [r for r in self._slot_req if r is not None]

    # --------------------------------------------------------- admission
    def submit(self, prompt, max_new_tokens: int, temperature: float = 0.0,
               top_k: int = 0, eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               deadline_ticks: Optional[int] = None,
               tenant: str = "default", priority: int = 0,
               _trace=None) -> Request:
        """Queue one request. prompt: 1-D int token ids. Returns the
        live Request; its .tokens fills in as the engine steps.
        `deadline_s` / `deadline_ticks` bound the request's TOTAL
        lifetime (queue wait included) in wall seconds / engine ticks —
        exceeding either resolves it with finish_reason "timeout".
        Raises BackpressureError when the queue is at max_queue under
        the "reject" policy; under "shed_oldest" the oldest queued
        request is evicted to make room. `_trace` lets a router thread
        ITS RequestTrace through so a dispatched (or replayed) request
        keeps one span tree; with tracing=True and no _trace the
        engine mints its own."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        t0 = prompt.shape[0]
        if t0 < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1; "
                             f"got {max_new_tokens}")
        if t0 + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the engine's max_len ({self.max_len})")
        if top_k > 0 and self.max_top_k <= 0:
            raise ValueError(
                "engine was built with max_top_k=0 (greedy/temperature "
                "only); rebuild with max_top_k >= the largest top_k "
                "you will request")
        if top_k > self.max_top_k:
            raise ValueError(f"top_k={top_k} exceeds the engine's "
                             f"static max_top_k={self.max_top_k}")
        if self.paged:
            need = self._pages_needed(t0, max_new_tokens)
            if need > self.num_pages - 1:
                raise PoolExhaustedError(
                    f"request needs {need} pages worst-case but the "
                    f"pool holds {self.num_pages - 1} allocatable "
                    f"pages (page_size={self.page_size})",
                    pages_needed=need, pages_total=self.num_pages - 1)
        if self.max_queue > 0 and len(self._queue) >= self.max_queue:
            if self.queue_policy == "shed_oldest":
                self._finish(self._queue.popleft(), "evicted")
            else:
                self._m_rej.add()
                raise BackpressureError(
                    f"admission queue full ({len(self._queue)} waiting, "
                    f"max_queue={self.max_queue})",
                    queue_depth=len(self._queue))
        req = Request(self._next_id, prompt, int(max_new_tokens),
                      float(temperature), int(top_k), eos_id,
                      deadline_s=(None if deadline_s is None
                                  else float(deadline_s)),
                      deadline_ticks=(None if deadline_ticks is None
                                      else int(deadline_ticks)),
                      tenant=tenant, priority=priority)
        req.t_submit = time.perf_counter()
        req._tick_submit = self._ticks
        req._engine = self
        if _trace is not None:
            req.trace = _trace
        elif self._tracer is not None:
            req.trace = self._tracer.trace(
                f"request-{req.id}", request_id=req.id,
                prompt_len=t0, max_new_tokens=int(max_new_tokens))
        if req.trace is not None:
            req._sp_queue = req.trace.begin(
                "queue", queue_depth=len(self._queue),
                attempt=req.trace.attempt)
        self._next_id += 1
        self._queue.append(req)
        self._m_sub.add()
        self._m_queue.set(len(self._queue))
        return req

    # --------------------------------------------------------- the tick
    def step(self):
        """One engine tick: expire queued requests past their TTL or
        deadline, advance ONE mid-prefill slot by one chunk (the
        chunked-prefill interleave), admit queued requests into free
        slots (reserving their worst-case page need first under the
        paged layout — a request that cannot reserve stays queued),
        advance all active slots one token through the single jitted
        decode step (quarantining poisoned rows), then enforce
        deadlines on the survivors. Returns this tick's
        (request, token) emissions in slot order."""
        with RecordEvent("serving.tick"):
            return self._step()

    def _step(self):
        events: List[tuple] = []
        actions = {}
        if _FAULT_HOOK is not None:
            actions = _FAULT_HOOK(self._ticks) or {}
        if self.paged and actions.pop("raise_cow", None):
            self._raise_cow = True
        if actions.pop("raise_migrate", None):
            self._raise_migrate = True       # next snapshot raises once
        now = time.perf_counter()
        self._expire_queued(now)
        if self.paged:
            self._advance_prefill(events, actions)
        while self._queue:
            slot = self._free_slot()
            if slot is None:
                break
            head = self._queue[0]
            if self._deadline_expired(head, now):
                self._queue.popleft()
                self._finish(head, "timeout")
                continue
            if (self.paged
                    and self._plan_admission(head)[4]
                    > self._pool.available()):
                # overlap the wait: start device_put uploads of the
                # host-tier pages this head's prefix walk will hit, so
                # admission maps already-transferred buffers
                self._prefetch_host(head)
                break       # head-of-line waits for pages (FCFS); live
                #             slots free pages as they finish
            self._queue.popleft()
            with RecordEvent("serving.admit", request=head.id):
                self._admit_guarded(slot, head, events, actions)

        if self._active.any():
            self._decode_guarded(events, actions)
        # outside the decode branch: a slot mid-chunked-prefill must
        # honor its deadline even when no stream is decoding yet
        self._enforce_deadlines(time.perf_counter())

        self._ticks += 1
        self._m_occ.set(int(self._active.sum()))
        self._m_queue.set(len(self._queue))
        self._publish_pool_gauges()
        self._publish_tier_gauges()
        return events

    def drain(self, max_ticks: Optional[int] = None):
        """Step until idle (or max_ticks); returns all emissions.
        NOTE: with max_ticks the engine may still hold live requests on
        return — call `abort_pending()` (or use `generate(...,
        max_ticks=)`, which does) when partial delivery must still
        resolve every request."""
        events = []
        ticks = 0
        while self.has_work():
            events.extend(self.step())
            ticks += 1
            if max_ticks is not None and ticks >= max_ticks:
                break
        return events

    def abort_pending(self, reason: str = "evicted") -> int:
        """Resolve EVERY live request (queued and in-slot) with the
        terminal `reason` — after this no request is in limbo. Returns
        the number aborted."""
        if reason not in TERMINAL_REASONS:
            raise ValueError(f"reason {reason!r} not in "
                             f"{sorted(TERMINAL_REASONS)}")
        n = 0
        while self._queue:
            self._finish(self._queue.popleft(), reason)
            n += 1
        for req in list(self._slot_req):
            if req is not None:
                self._finish(req, reason)
                n += 1
        self._m_occ.set(int(self._active.sum()))
        self._m_queue.set(len(self._queue))
        return n

    def generate(self, prompts: Sequence, max_new_tokens: int,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 deadline_ticks: Optional[int] = None,
                 max_ticks: Optional[int] = None) -> List[np.ndarray]:
        """Batch convenience: submit every prompt, drain, return each
        request's generated ids (submission order). Never returns with
        a request in limbo: whatever `max_ticks` (or a deadline) left
        undelivered is resolved with a terminal finish_reason
        ("evicted") before returning, so `.done` is True for every
        request this call submitted."""
        reqs = [self.submit(p, max_new_tokens, temperature=temperature,
                            top_k=top_k, eos_id=eos_id,
                            deadline_s=deadline_s,
                            deadline_ticks=deadline_ticks)
                for p in prompts]
        self.drain(max_ticks)
        for r in reqs:
            if not r.done:
                if r.slot is None:
                    try:
                        self._queue.remove(r)
                    except ValueError:
                        pass
                self._finish(r, "evicted")
        self._m_queue.set(len(self._queue))
        return [np.asarray(r.tokens, np.int32) for r in reqs]

    # ------------------------------------------------------ terminality
    def _clear_slot(self, slot: int) -> None:
        """Return a slot to the free pool: registry, every host mirror,
        and the device-state dirty flag (the ONE place a slot's mirrors
        reset — _finish and _rollback_slot both route here). Under the
        paged layout this is also where the slot's pages release:
        refcounts drop, registered pages park in the LRU cache, the
        table row snaps back to scratch, and any un-spent admission
        reservation returns to the pool."""
        self._slot_req[slot] = None
        self._active[slot] = False
        self._positions[slot] = 0
        self._cur_tok[slot] = 0
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._gen_idx[slot] = 0
        self._eos_ids[slot] = -1
        self._max_new[slot] = 0
        self._dirty = True
        if self.paged:
            row = self._ptab[slot]
            for j in np.nonzero(row)[0]:
                self._pool.release(int(row[j]))
            row[:] = 0
            self._pool.reserved -= int(self._slot_reserve[slot])
            self._slot_reserve[slot] = 0
            self._pt_dirty = True
            try:
                self._prefilling.remove(slot)
            except ValueError:
                pass

    def _finish(self, req: Request, reason: str) -> None:
        """THE terminal transition: exactly-once by construction (a
        resolved request is never re-finished), frees the slot and
        dirties the device mirror when the request was mid-decode."""
        if req.done:
            return
        if req.slot is not None:
            self._clear_slot(req.slot)
        req.slot = None
        req.done = True
        req.finish_reason = reason
        if req.trace is not None:
            # the ONE terminal span — exactly-once because _finish is
            # the one terminal seam AND RequestTrace.finish is once-
            # only (a router's own _finish then no-ops)
            req.trace.finish(reason, tokens=len(req.tokens))
        self._m_done.add()
        ctr = self._reason_ctr.get(reason)
        if ctr is not None:
            ctr.add()

    def cancel(self, req: Request) -> bool:
        """Resolve `req` with finish_reason "cancelled" right now:
        dequeues a waiting request, frees the slot of a mid-decode one.
        Returns False when it already resolved."""
        if req.done:
            return False
        if req.slot is None:
            try:
                self._queue.remove(req)
            except ValueError:
                pass                   # not ours / already dequeued
        self._finish(req, "cancelled")
        self._m_queue.set(len(self._queue))
        return True

    # -------------------------------------------------------- deadlines
    def _deadline_expired(self, req: Request, now: float) -> bool:
        if (req.deadline_s is not None
                and now - req.t_submit >= req.deadline_s):
            return True
        if (req.deadline_ticks is not None
                and self._ticks - req._tick_submit >= req.deadline_ticks):
            return True
        return False

    def _expire_queued(self, now: float) -> None:
        if not self._queue:
            return
        keep: collections.deque = collections.deque()
        for req in self._queue:
            ttl_hit = (self.queue_ttl_s > 0.0
                       and now - req.t_submit >= self.queue_ttl_s)
            if ttl_hit or self._deadline_expired(req, now):
                self._finish(req, "timeout")
            else:
                keep.append(req)
        self._queue = keep

    def _enforce_deadlines(self, now: float) -> None:
        for req in list(self._slot_req):
            if req is not None and self._deadline_expired(req, now):
                self._finish(req, "timeout")

    # ----------------------------------------------- self-healing calls
    def _on_fault(self, kind: str, exc: BaseException) -> None:
        """Every serving fault leaves a black box (no-op without
        $PADDLE_TPU_FLIGHT_DIR) and a counter bump."""
        self._m_fault.add()
        self._flight.configure(last_serving_fault=f"{kind}: {exc}")
        self._flight.note(serving_fault=kind, tick=self._ticks,
                          error=str(exc))
        self._flight.dump(f"serving_{kind}_fault")
        print(f"[serving] {kind} fault at tick {self._ticks}: {exc}",
              file=sys.stderr, flush=True)

    def _backoff(self, attempt: int) -> None:
        self._m_retry.add()
        time.sleep(min(self.backoff_base * (2.0 ** attempt),
                       self.backoff_max))

    def _rollback_slot(self, slot: int, req: Request, n_tok: int) -> None:
        """Undo a partially-applied admission: host mirrors, the slot
        registry (and under the paged layout the slot's pages and
        reservation) and the request's token list return to their
        pre-admit state, and the device mirror is marked stale."""
        self._clear_slot(slot)
        req.slot = None
        req._pf_next = None
        req.shared_tokens = 0
        del req.tokens[n_tok:]

    def _cache_dead(self) -> bool:
        """True when the pool cache's buffers were consumed by a FAILED
        donated dispatch (execution died after donation — possible on a
        real accelerator; CPU ignores donation): re-dispatching would
        only raise 'array deleted', so the caller must hard-reset."""
        try:
            return any(getattr(leaf, "is_deleted", lambda: False)()
                       for leaf in jax.tree_util.tree_leaves(self._cache))
        except Exception:                          # noqa: BLE001
            return False

    def _hard_reset(self, reason: str) -> None:
        """Last-resort recovery after an exhausted retry budget or a
        hung pull (re-dispatching donated buffers is illegal): every
        in-flight request terminates as "evicted" and the pool cache is
        reallocated; queued requests stay queued — if the fault was
        transient they admit cleanly into the fresh pool."""
        for req in list(self._slot_req):
            if req is not None:
                self._finish(req, "evicted")
        if self.paged:
            # prefix-map contents died with the buffers: fresh pool.
            # The host tier SURVIVES (its pages are deterministic
            # functions of prompt + params, still bit-valid) — only
            # the eviction tap re-attaches
            self._pool = _PagePool(self.num_pages, self.page_size)
            if self._host_tier is not None:
                self._pool.on_evict = self._spill_page
            self._ptab[:] = 0
            self._slot_reserve[:] = 0
            self._prefilling.clear()
            self._pt_dirty = False
        self._cache = self._new_cache()
        self._dstate = None
        self._tick_rows = None          # no row carries across a reset
        self._dirty = True
        self._flight.configure(last_serving_fault=f"hard_reset: {reason}")
        self._flight.dump("serving_hard_reset")
        print(f"[serving] hard reset at tick {self._ticks} ({reason}): "
              f"pool cache reallocated", file=sys.stderr, flush=True)

    def _pull(self, value, stall_s: float = 0.0):
        """The one device->host pull, optionally under the resilience
        watchdog (re-polls the SAME future with backoff — donated
        buffers cannot be re-dispatched). The persistent WatchdogPuller
        is the ~2 ms-tick-rate variant of the trainer's per-step pull
        thread. `value` may be a TUPLE of device arrays (the tick's
        token array + the in-tick telemetry row): the pair fetches in
        this ONE call, so the pull count the invariant tests wrap stays
        one per tick with telemetry on. `stall_s` is the injected
        tick_stall: it sleeps INSIDE the watchdog-monitored pull so the
        drill exercises the real budget/backoff path."""
        def src():
            if stall_s > 0.0:
                time.sleep(stall_s)
            if isinstance(value, tuple):
                return tuple(np.asarray(v)
                             for v in jax.device_get(list(value)))
            return np.asarray(value)
        if self.watchdog_timeout > 0.0:
            if self._puller is None:
                from ..parallel.resilience import WatchdogPuller
                self._puller = WatchdogPuller(label="serving tick")
            return self._puller.pull(
                src, self.watchdog_timeout, self.retries,
                self.backoff_base, self.backoff_max,
                on_retry=self._on_stall_retry)
        return src()

    def _on_stall_retry(self, attempt: int) -> None:
        """Watchdog backoff observer: count it, and leave a black box
        on the FIRST stall of a tick — a pull that needed backoff is
        worth a post-mortem even when it recovers."""
        self._m_retry.add()
        self._flight.note(serving_stall_attempt=attempt,
                          tick=self._ticks)
        if attempt == 0:
            self._flight.dump("serving_stall")

    def _admit_guarded(self, slot: int, req: Request, events: list,
                       actions: dict) -> None:
        """Admission under the fault guard: a raising prefill rolls the
        slot back and retries with backoff; an exhausted budget resolves
        the request as "evicted" (never limbo). A hung pull or a cache
        lost to a failed donated dispatch is NOT retryable — re-waiting
        the watchdog budget / re-dispatching deleted buffers can only
        fail again — and escalates to `_hard_reset` like the tick's."""
        n_tok = len(req.tokens)
        from ..parallel.resilience import StepHungError
        for attempt in range(self.retries + 1):
            try:
                if actions.pop("raise_prefill", None):
                    raise ServingFaultError("injected prefill fault")
                self._admit(slot, req, events)
                return
            except StepHungError as e:
                self._rollback_slot(slot, req, n_tok)
                self._on_fault("prefill_hang", e)
                self._finish(req, "evicted")
                self._hard_reset("prefill watchdog hang")
                return
            except Exception as e:                 # noqa: BLE001
                self._rollback_slot(slot, req, n_tok)
                if "RESOURCE_EXHAUSTED" in str(e):
                    self._dump_oom_forensics("prefill", e)
                self._on_fault("prefill", e)
                dead = self._cache_dead()
                if dead or attempt >= self.retries:
                    self._finish(req, "evicted")
                    if dead:
                        self._hard_reset("prefill lost the donated cache")
                    return
                self._backoff(attempt)

    def _decode_guarded(self, events: list, actions: dict) -> None:
        """One decode tick under the fault guard. Mirrors advance only
        after a successful pull, so a failed attempt resyncs `_dstate`
        from them and re-runs the tick idempotently (same state -> same
        KV writes). A hung pull or exhausted budget hard-resets."""
        poison_slot = actions.pop("poison_slot", None)
        draft_slot = actions.pop("draft_poison_slot", None)
        stall_s = actions.pop("stall_s", 0.0)
        from ..parallel.resilience import StepHungError
        for attempt in range(self.retries + 1):
            try:
                if actions.pop("raise_decode", None):
                    raise ServingFaultError("injected decode fault")
                if actions.pop("raise_oom", None):
                    # the injected message carries the real backend's
                    # marker so the forensics trigger below is the SAME
                    # path a true allocation failure takes
                    raise ServingFaultError(
                        "injected allocation failure: "
                        "RESOURCE_EXHAUSTED: simulated out of memory")
                if self.paged:
                    # every active slot's write page must exist and be
                    # private before the scatter (idempotent: a retry
                    # finds them already allocated)
                    self._prepare_tick_pages()
                if self._dirty or (self.paged and self._pt_dirty):
                    self._upload_dirty()
                sampling = bool(np.any(self._temps[self._active] > 0.0))
                poison = self._poison_ones
                if poison_slot is not None and self.guardrails:
                    p = np.ones(self.num_slots, np.float32)
                    p[int(poison_slot) % self.num_slots] = np.nan
                    poison = self._rep(p)
                poison_slot = None        # injected at most once
                with RecordEvent("serving.decode_tick",
                                 active=int(self._active.sum()),
                                 slots=self.num_slots,
                                 **self._kv_read_counts()) as dev:
                    rows = self._since_last_tick(dev)
                    if self.spec:
                        dpoison = self._poison_ones
                        if draft_slot is not None:
                            dp = np.ones(self.num_slots, np.float32)
                            dp[int(draft_slot) % self.num_slots] = np.nan
                            dpoison = self._rep(dp)
                        draft_slot = None     # injected at most once
                        args = (self._params, self._cache, self._dstate,
                                self._base_key, poison, dpoison)
                    else:
                        args = (self._params, self._cache, self._dstate,
                                self._base_key, poison)
                    if self.mt_k > 1:
                        args += self._daux
                    # the HOST enqueueing the program: it returns when
                    # the program is queued, not when it ran
                    with RecordEvent("serving.decode_dispatch"):
                        out = self._decode(*args, sampling=sampling)
                    # ONE host pull per tick ([N] non-spec; the
                    # [N, gamma+1] emission matrix under spec) — with
                    # in-tick telemetry the TICK_FIELDS row rides the
                    # SAME pull (a tuple fetch through the one _pull)
                    # — and so do a counting family's counts, onto the span
                    if self._tick_tele:
                        nxt, trow, self._cache, self._dstate = out
                        fetch = (nxt, trow)
                    else:
                        nxt, self._cache, self._dstate = out
                        fetch = (nxt,)
                    if self.family.counts:
                        fetch += (self._cache["stats"],)
                    # the host blocked on the program and on the
                    # result coming back
                    with RecordEvent("serving.decode_pull") as pull:
                        got = self._pull(fetch if len(fetch) > 1 else nxt,
                                         stall_s)
                    # only a tick whose pull came back is "the last tick"
                    self._last_pull_end, self._tick_rows = pull.end_s, rows
                    got = got if len(fetch) > 1 else (got,)
                    toks = got[0]
                    tele_row = got[1] if self._tick_tele else None
                    if self.family.counts:
                        dev.set(**self.family.counts(self.cfg, got[-1]))
                tick_ms = dev.dur_s * 1e3
                stall_s = 0.0
                break
            except StepHungError as e:
                # the future may still land later; re-polling already
                # exhausted the budget and re-dispatch is illegal
                self._on_fault("decode_hang", e)
                self._hard_reset("watchdog hang")
                return
            except Exception as e:                 # noqa: BLE001
                self._dirty = True        # resync _dstate from mirrors
                if "RESOURCE_EXHAUSTED" in str(e):
                    self._dump_oom_forensics("decode", e)
                self._on_fault("decode", e)
                dead = self._cache_dead()
                if dead or attempt >= self.retries:
                    self._hard_reset("decode lost the donated cache"
                                     if dead else
                                     "decode retries exhausted")
                    return
                self._backoff(attempt)

        self._m_tick.add()
        if self._quant_info:
            self._m_qmm.add(self._qmm_full
                            + (self.spec_gamma * self._qmm_draft
                               if self.spec else 0))
        with RecordEvent("serving.emit"):
            self._emit_tick(toks, tele_row, tick_ms, events)

    def _since_last_tick(self, span: RecordEvent):
        """Set on the just-opened `serving.decode_tick` span the two
        counts of what a decoding row waited through since the previous
        tick: `since_last_ms`, from that tick's pull returning to the span
        opening (prefills, uploads, emission, the router, the caller's
        loop), and `carried`, the rows active in both ticks under the same
        request — host mirrors only. Every reader of the two reads traced
        spans, so with no profiler session recording nothing is computed
        and the next traced tick, like an engine's first, carries
        neither. -> the request of each row in this tick (None untraced),
        which the caller keeps once the tick's pull has come back."""
        if not span.in_trace:
            return None
        rows = np.where(self._active, self._req_ids, -1)
        if self._tick_rows is not None:
            span.set(
                since_last_ms=(span.start_s - self._last_pull_end) * 1e3,
                carried=int(((rows == self._tick_rows)
                             & self._active).sum()))
        return rows

    def _emit_tick(self, toks, tele_row, tick_ms: float,
                   events: list) -> None:
        """The per-row Python after the tick's pull (`serving.emit`): the
        telemetry record, then mirrors, SLO samples and finish checks of
        every active row, in the tick's form."""
        if self._tick_log is not None:
            host = {"queue_depth": len(self._queue)}
            if self.paged:
                host["prefilling"] = len(self._prefilling)
                host["pages_in_use"] = int((self._pool.ref[1:] > 0).sum())
            self._tick_log.record_tick(self._ticks, tele_row, host,
                                       tick_ms)
        tick_now = time.perf_counter()
        if self.spec:
            self._apply_spec_emissions(toks, events, tick_now)
            return
        if self.mt_k > 1:
            self._apply_multi_emissions(toks, events, tick_now)
            return
        for i in np.nonzero(self._active)[0]:
            req = self._slot_req[i]
            tok = int(toks[i])
            if tok < 0:
                # in-jit quarantine verdict: evict ONLY this slot; the
                # device state is stale (its row advanced) -> _finish
                # dirties it, co-batched rows rebuild from their clean
                # mirrors and stay bit-identical
                self._on_fault("poisoned", RuntimeError(
                    f"non-finite logits in slot {i} (request {req.id})"))
                self._finish(req, "poisoned")
                continue
            # mirror exactly what the tick did on device (positions
            # and gen_idx advanced under the active mask) — no
            # download, and the device state stays clean unless an
            # eviction dirties it
            self._emit_token(i, req, tok, events, tick_now)

    def _kv_read_counts(self) -> dict:
        """The `serving.decode_tick` span's two KV counts for a family
        with the one uniform pool: `kv_positions_pool`, the positions
        its layers hold (layers x slots x the view a row attends), and
        `kv_positions_read`, those the tick's attention may touch —
        where the plain tick's forward takes the length-aware kernel
        (`decode_attention.length_aware`) each live row's blocks, from
        the mirrors of the positions and the mask the tick is handed;
        on the einsum path (paged, a `tp` mesh, off a TPU, and the spec and
        multi-tick bodies, whose verify pass and later steps this does
        not follow) the whole pool. A family that counts its own pools
        (`ModelFamily.counts`) reports those instead."""
        if self.family.counts:
            return {}
        from ..kernels.decode_attention import kv_positions_read
        view = (self.max_pages * self.page_size if self.paged
                else self.max_len)
        layers = self._cache["k"].shape[0]
        return {"kv_positions_read": layers * kv_positions_read(
                    self._positions, self._active, view,
                    self.length_aware_tick()),
                "kv_positions_pool": layers * self.num_slots * view}

    def length_aware_tick(self) -> bool:
        """Whether this engine's decode tick is the plain one over the
        dense pool whose attention takes the length-aware kernel
        (`decode_attention.length_aware`): what the span's counts and
        tools/serving_attrib.py price the tick's KV read by."""
        from ..kernels.decode_attention import length_aware
        return (not self.paged and self.tp == 1 and not self.spec
                and self.mt_k == 1 and not self.family.counts
                and length_aware(1, self._cache["k"]))

    def _upload_dirty(self) -> None:
        """Rebuild whatever the host mirrors dirtied since the last tick
        (page table, slot-state tuple, the multi-tick scan's aux pair):
        the uploads that stand between two device programs."""
        with RecordEvent("serving.upload"):
            if self.paged and self._pt_dirty:
                self._cache["pt"] = self._rep(self._ptab)
                self._pt_dirty = False
            if self._dirty:
                self._dstate = (
                    self._rep(self._cur_tok),
                    self._rep(self._positions),
                    self._rep(self._active),
                    self._rep(self._temps),
                    self._rep(self._top_ks),
                    self._rep(self._req_ids),
                    self._rep(self._gen_idx))
                if self.mt_k > 1:
                    # the scan's early-exit inputs ride the same
                    # dirty-rebuild cadence as the state tuple
                    self._daux = (self._rep(self._eos_ids),
                                  self._rep(self._max_new))
                self._dirty = False

    def _emit_token(self, i: int, req: Request, tok: int,
                    events: list, tick_now: float,
                    itl_ms: Optional[float] = None) -> None:
        """The per-token bookkeeping both decode paths share: advance
        the host mirrors (positions/_cur_tok/_gen_idx), record the
        token + SLO sample, and run the finish checks. The non-spec
        tick is the cut=1 case of the spec loop — one seam so a future
        accounting change can't silently miss one copy. `itl_ms`
        overrides the wall-clock inter-token sample: a multi-tick pull
        delivers K tokens at once, and attributing the whole dispatch
        gap to each would K-fold-inflate the ITL histogram — the
        caller amortizes the gap across the tokens it carried."""
        self._positions[i] += 1
        self._cur_tok[i] = tok
        self._gen_idx[i] += 1
        if req.trace is not None:
            req.trace.instant("decode.tick", parent=req._sp_decode,
                              tick=self._ticks, token=tok)
        req.tokens.append(tok)
        events.append((req, tok))
        self._m_tok.add()
        self._slo_itl.append((tick_now - req._t_last) * 1e3
                             if itl_ms is None else itl_ms)
        req._t_last = tick_now
        self._maybe_finish(req)

    def _apply_spec_emissions(self, toks, events: list,
                              tick_now: float) -> None:
        """Spec-mode post-pull bookkeeping: `toks` is the [N, gamma+1]
        emission matrix (column 0 = the always-emitted token or the -1
        quarantine sentinel; SPEC_PAD beyond the accepted prefix). The
        device advanced each active slot by its accepted count + 1;
        the mirrors advance identically UNLESS the request finishes
        mid-block (EOS / max_new_tokens inside the accepted prefix) —
        then _finish/_clear_slot dirties the device mirror, exactly
        the non-spec eviction path, and the unconsumed tail tokens are
        dropped (the non-spec engine would never have generated them).
        Under the paged layout, pages past every surviving slot's new
        position are speculative only and roll back to the pool."""
        from .spec_decode import SPEC_PAD
        width = self.spec_gamma + 1
        for i in np.nonzero(self._active)[0]:
            req = self._slot_req[i]
            flat = [int(t) for t in np.asarray(toks[i]).reshape(-1)]
            # the pull is `mt_k` blocks of gamma+1 columns (one block
            # under the single-dispatch spec tick); an all-PAD block
            # marks "retired in an earlier scan step" — stop there
            blocks = []
            for b in range(len(flat) // width):
                row = flat[b * width:(b + 1) * width]
                if b > 0 and row[0] == SPEC_PAD:
                    break       # dead block: the scan retired this slot
                if row[0] < -1:                  # defensive: never PAD
                    row[0] = -1
                blocks.append(row)
            poisoned = False
            emit: List[int] = []
            for row in blocks:
                if row[0] < 0:
                    poisoned = True
                    break
                cut = (row.index(SPEC_PAD) if SPEC_PAD in row
                       else len(row))
                if self._temps[i] <= 0.0:
                    # acceptance telemetry counts GREEDY slots only —
                    # sampled slots never propose
                    self._spec_prop_total += self.spec_gamma
                    self._spec_acc_total += cut - 1
                    self._m_spec_prop.add(self.spec_gamma)
                    self._m_spec_acc.add(cut - 1)
                emit.extend(row[:cut])
            if not blocks or (poisoned and not emit):
                self._on_fault("poisoned", RuntimeError(
                    f"non-finite logits in slot {i} (request {req.id})"))
                self._finish(req, "poisoned")
                continue
            # a multi-block pull amortizes the dispatch gap across the
            # tokens it carried (see _emit_token); the single-block
            # path keeps the wall-clock sample bit-for-bit as before
            share = ((tick_now - req._t_last) * 1e3 / max(len(emit), 1)
                     if len(blocks) > 1 else None)
            # mirror the device advance TOKEN BY TOKEN, not as one
            # block: _maybe_finish's cache-full eviction check reads
            # the position mirror, and advancing the whole block up
            # front would let `positions >= max_len` fire mid-block on
            # a boundary-legal request (prompt + max_new within gamma
            # of max_len), dropping accepted tokens the non-spec
            # engine would emit. A surviving slot's mirror still lands
            # exactly at the device's pos + cut; a mid-block finish
            # dirties the device state as before.
            for tok in emit:
                self._emit_token(i, req, tok, events, tick_now,
                                 itl_ms=share)
                if req.done:
                    break
            if poisoned and not req.done:
                # a later scan step hit the quarantine after this slot
                # already emitted real tokens this dispatch: deliver
                # them, then resolve exactly like the single-tick path
                self._on_fault("poisoned", RuntimeError(
                    f"non-finite logits in slot {i} (request {req.id})"))
                self._finish(req, "poisoned")
        if self._spec_prop_total:
            self._m_spec_rate.set(
                self._spec_acc_total / self._spec_prop_total)
        if self.paged:
            for i in np.nonzero(self._active)[0]:
                self._rollback_spec_pages(int(i))

    def _apply_multi_emissions(self, toks, events: list,
                               tick_now: float) -> None:
        """Multi-tick (non-spec) post-pull bookkeeping: `toks` is the
        [N, K] emission matrix from multi_tick_scan — column j = the
        token scan step j emitted, MT_PAD after the slot's device-side
        retirement, -1 the quarantine verdict. The host replays the
        columns through `_emit_token` (same exactly-once terminal seam
        as the single-tick loop), amortizing the dispatch gap across
        the K tokens for the ITL histogram; host finish rules fire on
        the same token the device retired on, so mirrors land exactly
        where the device state did for surviving slots."""
        from .multi_tick import MT_PAD
        for i in np.nonzero(self._active)[0]:
            req = self._slot_req[i]
            row = [int(t) for t in np.asarray(toks[i]).reshape(-1)]
            cut = row.index(MT_PAD) if MT_PAD in row else len(row)
            row = row[:cut]
            n_real = sum(1 for t in row if t >= 0)
            share = (tick_now - req._t_last) * 1e3 / max(n_real, 1)
            if not row or row[0] < 0:
                self._on_fault("poisoned", RuntimeError(
                    f"non-finite logits in slot {i} (request {req.id})"))
                self._finish(req, "poisoned")
                continue
            for tok in row:
                if tok < 0:
                    self._on_fault("poisoned", RuntimeError(
                        f"non-finite logits in slot {i} "
                        f"(request {req.id})"))
                    self._finish(req, "poisoned")
                    break
                self._emit_token(i, req, tok, events, tick_now,
                                 itl_ms=share)
                if req.done:
                    break

    # ---------------------------------------------------------- plumbing
    def _free_slot(self) -> Optional[int]:
        for i in range(self.num_slots):
            if self._slot_req[i] is None:
                return i
        return None

    def _admit(self, slot: int, req: Request, events: list) -> None:
        if self.paged:
            return self._admit_paged(slot, req, events)
        t0 = len(req.prompt)
        tb = prompt_bucket(t0, self.max_len, self.bucket_lo)
        padded = np.zeros((1, tb), np.int32)
        padded[0, :t0] = req.prompt
        if req.trace is not None:
            req.trace.end(req._sp_queue)
            req._sp_queue = None
            sp_pf = req.trace.begin("prefill", slot=slot, true_len=t0,
                                    bucket=tb, attempt=req.trace.attempt)
        # the uploads are the admission's host work (serving.admit);
        # serving.prefill is the program alone: dispatch through the
        # first token's pull returning
        args = (self._rep(padded), self._rep(t0, np.int32),
                self._rep(slot, np.int32),
                self._rep([req.temperature], np.float32),
                self._rep([req.top_k], np.int32),
                self._rep([req.id], np.int32))
        with RecordEvent("serving.prefill", request=req.id, true_len=t0,
                         bucket=tb) as pf:
            first, self._cache = self._prefill(
                self._params, self._cache, *args, self._base_key,
                sampling=req.temperature > 0.0)
            # first generated token — the admission's one host pull,
            # under the same watchdog as the tick's
            if self.family.counts:
                first, stats = self._pull((first, self._cache["stats"]))
                pf.set(**self.family.counts(self.cfg, stats))
            else:
                first = self._pull(first)
            tok = int(first)
        if req.trace is not None:
            req.trace.end(sp_pf, final=True)
        if self._tick_log is not None:
            self._tick_log.record_prefill(self._ticks, pf.dur_s * 1e3, t0,
                                          tb, True, slot)
        self._m_pre.add()
        if self._quant_info:
            self._m_qmm.add(self._qmm_full)
        if tok < 0:
            # prefill quarantine: the slot was never activated — its
            # (possibly non-finite) cache row is masked stale garbage
            # until the next occupant's prefill overwrites it
            self._on_fault("poisoned", RuntimeError(
                f"non-finite prefill logits (request {req.id})"))
            self._finish(req, "poisoned")
            return
        self._activate_slot(slot, req, tok, events)

    def _activate_slot(self, slot: int, req: Request, tok: int,
                       events: list) -> None:
        """Prefill complete: emit the first token, arm every host
        mirror, and hand the slot to the decode tick (shared by the
        dense admission and the paged final chunk)."""
        now = time.perf_counter()
        self._m_qwait.observe((now - req.t_submit) * 1e3)
        self._slo_ttft.append((now - req.t_submit) * 1e3)
        req._t_last = now
        req.slot = slot
        self._slot_req[slot] = req
        self._positions[slot] = len(req.prompt)
        self._active[slot] = True
        self._cur_tok[slot] = tok
        self._temps[slot] = req.temperature
        self._top_ks[slot] = req.top_k
        self._req_ids[slot] = req.id
        self._gen_idx[slot] = 1
        self._eos_ids[slot] = (-1 if req.eos_id is None
                               else int(req.eos_id))
        self._max_new[slot] = int(req.max_new_tokens)
        self._dirty = True
        if req.trace is not None:
            req._sp_decode = req.trace.begin(
                "decode", slot=slot, attempt=req.trace.attempt)
            req.trace.instant("decode.tick", parent=req._sp_decode,
                              tick=self._ticks, token=tok)
        req.tokens.append(tok)
        events.append((req, tok))
        self._m_tok.add()
        self._maybe_finish(req)

    # ------------------------------------------------- paged scheduling
    def _pages_needed(self, t0: int, max_new: int) -> int:
        """Worst-case page envelope for one request: positions
        0 .. t0 + max_new - 2 get written (the final sampled token
        never is), so ceil((t0 + max_new - 1) / page_size)."""
        return -(-(t0 + max_new - 1) // self.page_size)

    def _plan_admission(self, req: Request):
        """The admission plan: (matched shared page ids, aligned_full,
        suffix_start, need, gross). `need` is the worst-case pages the
        request will still allocate privately (envelope minus
        kept-shared credit); `gross` additionally counts cached pages
        the match pulls back live — they stop being evictable for
        other admissions' reservations the moment we retain them. The
        suffix always re-runs >= 1 prompt token (the first-token
        logits must be computed), so a fully page-aligned match COWs
        its last matched page (aligned_full) and recomputes the last
        prompt token into the private copy.

        The match is a CHAIN of ("dev", page_id) | ("host", key)
        entries: the walk consults the device prefix map first, then
        the host tier (inference/host_kv.py) — a host hit costs one
        page allocation at admission (the swap-in) but zero recomputed
        prompt tokens, so `need` credits only device entries."""
        t0 = len(req.prompt)
        ps = self.page_size
        matched: List[tuple] = []        # ("dev", pid) | ("host", key)
        n_dev = 0
        if self.prefix_sharing:
            for key in self._prefix_keys(req):
                pid = self._pool.lookup(key)
                if pid is not None:
                    matched.append(("dev", pid))
                    n_dev += 1
                elif (self._host_tier is not None
                      and (key in self._host_stage
                           or key in self._host_tier)):
                    matched.append(("host", key))
                else:
                    break
        aligned_full = (bool(matched) and len(matched) == t0 // ps
                        and t0 % ps == 0)
        suffix_start = (t0 - 1) if aligned_full else len(matched) * ps
        need = (self._pages_needed(t0, req.max_new_tokens) - n_dev
                + (1 if aligned_full else 0))
        gross = need + sum(1 for kind, pid in matched
                           if kind == "dev" and self._pool.ref[pid] == 0)
        if gross > self.num_pages - 1:
            # an aligned-full match costs one page over the bare
            # envelope (the COW of its last matched page); in a pool
            # sized exactly to the envelope that can NEVER be
            # satisfied and the request would queue forever — admit
            # unshared instead (submit() guaranteed the envelope fits)
            matched, aligned_full, suffix_start = [], False, 0
            need = gross = self._pages_needed(t0, req.max_new_tokens)
        return matched, aligned_full, suffix_start, need, gross

    def _prefix_keys(self, req: Request):
        """The request's per-page rolled prefix hashes, memoized on the
        Request (the prompt is immutable) — the head-of-line plan runs
        every tick while it waits for pages, and must not re-hash
        O(len(prompt)^2 / page_size) bytes each time."""
        if req._pfx_keys is None:
            ps = self.page_size
            req._pfx_keys = [
                _prefix_key(req.prompt, (j + 1) * ps)
                for j in range(len(req.prompt) // ps)]
        return req._pfx_keys

    def _admit_paged(self, slot: int, req: Request, events: list) -> None:
        """Paged admission: map the shared prompt-prefix pages (bumping
        refcounts), reserve the worst-case remainder, then prefill the
        un-shared suffix — inline when it fits one chunk, otherwise one
        chunk per tick through `_advance_prefill`. The caller
        (`step()`) already checked the reservation fits."""
        matched, aligned_full, suffix_start, need, _ = \
            self._plan_admission(req)
        # capture host-tier page data BEFORE any allocation: alloc()'s
        # device eviction cascades into the host tier's own LRU, which
        # could drop a key this very admission still needs. Prefetched
        # uploads (_prefetch_host) are consumed here; cold hits upload
        # synchronously.
        staged = {}
        for kind, key in matched:
            if kind != "host" or key in staged:
                continue
            pair = self._host_stage.pop(key, None)
            if pair is None and self._host_tier is not None:
                hp = self._host_tier.get(key)
                if hp is not None:
                    pair = (self._rep(hp[0]), self._rep(hp[1]))
            if pair is not None:
                staged[key] = pair
                continue
            # defensive: the tier dropped the key since planning —
            # degrade to an unshared suffix from this page on
            cutoff = matched.index((kind, key))
            matched = matched[:cutoff]
            n_dev = sum(1 for k, _ in matched if k == "dev")
            aligned_full = False
            suffix_start = len(matched) * self.page_size
            need = self._pages_needed(
                len(req.prompt), req.max_new_tokens) - n_dev
            break
        self._pool.reserved += need
        self._slot_reserve[slot] = need
        swapped = False
        for j, (kind, val) in enumerate(matched):
            if kind == "dev":
                self._pool.retain(val)
                self._ptab[slot, j] = val
                continue
            # host swap-in: promote the page back to the device pool,
            # re-register it under its prefix key (future sharers hit
            # device again), and map it shared for this slot
            dk, dv = staged[val]
            pid = self._alloc_slot_page(slot, j)
            self._cache["k"] = self._cache["k"].at[:, pid].set(dk)
            self._cache["v"] = self._cache["v"].at[:, pid].set(dv)
            self._pool.register(pid, val)
            if self._host_tier is not None:
                self._host_tier.swapins += 1
            self._m_swapin.add()
            swapped = True
        if swapped and self._cache_pin:
            # the eager .at[].set writes ran outside the jitted bodies —
            # re-assert the pinned layouts (same seam as _restore_into)
            self._cache = self._pin_cache_host(self._cache)
        if matched:
            self._pt_dirty = True
        req.slot = slot
        self._slot_req[slot] = req
        req.shared_tokens = suffix_start
        req._pf_next = suffix_start
        if req.trace is not None:
            req.trace.end(req._sp_queue, shared_tokens=suffix_start)
            req._sp_queue = None
        if aligned_full:
            # the suffix rewrites the last prompt token's K/V into the
            # last matched page — materialize a private copy first
            self._ensure_private(slot, (len(req.prompt) - 1)
                                 // self.page_size)
        t0 = len(req.prompt)
        if self.prefill_chunk <= 0 or t0 - suffix_start <= \
                self.prefill_chunk:
            self._run_chunk(slot, req, events)
        else:
            self._prefilling.append(slot)

    def _run_chunk(self, slot: int, req: Request, events: list) -> None:
        """One prefill chunk for `slot`: allocate/privatize the pages
        its real tokens land in, run the jitted paged chunk prefill,
        and — on the prompt's FINAL chunk — pull the first token,
        register the full prompt pages for future sharers, and
        activate the slot. Non-final chunks make no host pull."""
        t0 = len(req.prompt)
        ps = self.page_size
        start = req._pf_next
        end = (t0 if self.prefill_chunk <= 0
               else min(start + self.prefill_chunk, t0))
        clen = end - start
        for j in range(start // ps, (end - 1) // ps + 1):
            self._ensure_private(slot, j)
        cb = prompt_bucket(clen, self.max_len, self.bucket_lo)
        padded = np.zeros((1, cb), np.int32)
        padded[0, :clen] = req.prompt[start:end]
        if self._pt_dirty:
            self._cache["pt"] = self._rep(self._ptab)
            self._pt_dirty = False
        final = end == t0
        sp_pf = None
        if req.trace is not None:
            sp_pf = req.trace.begin("prefill", slot=slot,
                                    chunk_start=start, chunk_len=clen,
                                    bucket=cb, final=final,
                                    attempt=req.trace.attempt)
        args = (self._rep(padded), self._rep(clen, np.int32),
                self._rep(start, np.int32), self._rep(slot, np.int32),
                self._rep([req.temperature], np.float32),
                self._rep([req.top_k], np.int32),
                self._rep([req.id], np.int32))
        with RecordEvent("serving.prefill", request=req.id, true_len=clen,
                         bucket=cb) as pf:
            first, self._cache = self._prefill(
                self._params, self._cache, *args, self._base_key,
                sampling=final and req.temperature > 0.0)
            tok = int(self._pull(first)) if final else None
        if req.trace is not None:
            req.trace.end(sp_pf)
        if self._tick_log is not None:
            self._tick_log.record_prefill(self._ticks, pf.dur_s * 1e3,
                                          clen, cb, final, slot)
        self._m_chunks.add()
        if self._quant_info:
            self._m_qmm.add(self._qmm_full)
        if not final:
            req._pf_next = end
            return
        req._pf_next = None
        self._m_pre.add()
        if tok < 0:
            # prefill quarantine BEFORE registration: a poisoned
            # prompt's pages are never published to the prefix map
            self._on_fault("poisoned", RuntimeError(
                f"non-finite prefill logits (request {req.id})"))
            self._finish(req, "poisoned")
            return
        if self.prefix_sharing:
            for j, key in enumerate(self._prefix_keys(req)):
                self._pool.register(int(self._ptab[slot, j]), key)
        self._activate_slot(slot, req, tok, events)

    def _advance_prefill(self, events: list, actions: dict) -> None:
        """The chunked-prefill interleave: at most ONE chunk runs per
        tick (FCFS across mid-prefill slots), so co-batched decode
        streams pay at most one chunk of latency per token no matter
        how long a joining prompt is."""
        while self._prefilling:
            slot = self._prefilling[0]
            req = self._slot_req[slot]
            if req is None or req.done or req._pf_next is None:
                self._prefilling.popleft()     # evicted/cancelled
                continue
            self._chunk_guarded(slot, req, events, actions)
            if req.done or req._pf_next is None:
                if self._prefilling and self._prefilling[0] == slot:
                    self._prefilling.popleft()
            return

    def _chunk_guarded(self, slot: int, req: Request, events: list,
                       actions: dict) -> None:
        """One chunk under the fault guard. A chunk re-run is
        idempotent (the same pages re-scatter the same K/V), so a
        raising device call just retries with backoff; an exhausted
        budget evicts the request (its pages free via _clear_slot) and
        a hung pull / dead donated cache hard-resets."""
        from ..parallel.resilience import StepHungError
        for attempt in range(self.retries + 1):
            try:
                if actions.pop("raise_prefill", None):
                    raise ServingFaultError("injected prefill fault")
                self._run_chunk(slot, req, events)
                return
            except StepHungError as e:
                self._on_fault("prefill_hang", e)
                self._finish(req, "evicted")
                self._hard_reset("prefill watchdog hang")
                return
            except Exception as e:                 # noqa: BLE001
                self._on_fault("prefill", e)
                dead = self._cache_dead()
                if dead or attempt >= self.retries:
                    self._finish(req, "evicted")
                    if dead:
                        self._hard_reset("prefill lost the donated cache")
                    return
                self._backoff(attempt)

    def _alloc_slot_page(self, slot: int, j: int) -> int:
        """Allocate a private page for table entry (slot, j),
        consuming the slot's admission reservation when one remains."""
        pid = self._pool.alloc()
        if self._slot_reserve[slot] > 0:
            self._slot_reserve[slot] -= 1
            self._pool.reserved -= 1
        self._ptab[slot, j] = pid
        self._pt_dirty = True
        return pid

    def _ensure_private(self, slot: int, j: int) -> int:
        """THE copy-on-write seam: make table entry (slot, j) safe to
        write. Unmapped -> allocate; mapped but frozen (shared refcount
        or prefix-registered) -> allocate a fresh page, jitted-copy the
        frozen page's contents into it, swap the table entry, and drop
        the reference; already private -> no-op."""
        pid = int(self._ptab[slot, j])
        if pid != 0 and not self._pool.is_frozen(pid):
            return pid
        if pid != 0 and self._raise_cow:
            self._raise_cow = False
            raise ServingFaultError("injected cow fault")
        new = self._alloc_slot_page(slot, j)
        if pid != 0:
            self._cache = self._cow(self._cache,
                                    self._rep(pid, np.int32),
                                    self._rep(new, np.int32))
            self._pool.release(pid)
            self._m_cow.add()
        return new

    def _prepare_tick_pages(self) -> None:
        """Paged pre-tick: every active slot's write page (where its
        position lands this tick) must exist and be private before the
        jitted scatter runs. Allocation draws on the slot's admission
        reservation, so it cannot fail mid-decode. Under speculative
        decode the tick writes gamma+1 positions, so the whole span's
        pages prepare — CLAMPED to the request's write envelope
        (position t0 + max_new - 2 is the last ever written; draft
        positions past it scatter to the scratch page through the
        unmapped table instead of drawing pages the admission never
        reserved)."""
        span = self._tick_span     # K ticks x (gamma+1 under spec)
        for i in np.nonzero(self._active)[0]:
            pos = int(self._positions[i])
            last = pos + span - 1
            req = self._slot_req[int(i)]
            if req is not None:
                last = min(last,
                           len(req.prompt) + req.max_new_tokens - 2)
            # positions pos..last are contiguous -> iterate the pages
            # they cover once each (<= ceil(span/ps)+1), not once per
            # position: _ensure_private is a host table read + set
            # lookup on the scheduler hot path
            for j in range(pos // self.page_size,
                           last // self.page_size + 1):
                if j < self.max_pages:
                    self._ensure_private(int(i), j)

    def _rollback_spec_pages(self, slot: int) -> None:
        """Undo speculative page allocation: after acceptance, any
        page mapped past the slot's live position holds ONLY rejected
        drafts' K/V — release it to the pool and restore the slot's
        admission reservation, so between ticks the pool accounting is
        byte-identical to the single-token path's (speculation can
        never starve other admissions of pages). Decode-range pages
        are always private and unregistered (registration happens at
        prefill, for prompt pages, which all sit below the live
        position), so release() returns them straight to the free
        list."""
        pos = int(self._positions[slot])
        ps = self.page_size
        row = self._ptab[slot]
        first = -(-pos // ps)        # page j holds a token iff j*ps < pos
        # only THIS tick's prepared span can be mapped past `first`
        # (rollback restores the invariant every tick, and positions
        # only grow): its last write position is pos_before + gamma
        # <= pos - 1 + gamma, so the scan is O(gamma/page_size), not
        # O(max_pages), per slot per tick
        last = min((pos + self._tick_span - 2) // ps + 1, self.max_pages)
        for j in range(first, last):
            pid = int(row[j])
            if pid == 0:
                continue
            self._pool.release(pid)
            self._slot_reserve[slot] += 1
            self._pool.reserved += 1
            row[j] = 0
            self._pt_dirty = True

    # ---------------------------------------- live migration + rebuild
    def _pin_cache_host(self, cache):
        """Re-assert the pinned layouts after an EAGER cache update
        (the migration restore writes run outside the jitted bodies).
        A jitted identity with the SAME out_shardings `_new_cache`
        allocates under — not a bare device_put — because jit
        NORMALIZES PartitionSpec spellings (trailing Nones stripped):
        a device_put'd leaf would carry an equivalent-but-differently-
        spelled sharding, and the next decode tick would silently
        compile a second executable for it. No-op off-mesh."""
        if not self._cache_pin:
            return cache
        if self._repin is None:
            # Strip trailing Nones from the pin specs: jit OUTPUTS carry
            # the trimmed spelling, and equivalent-but-longer spellings
            # are DIFFERENT pjit cache keys — without this the first
            # post-restore tick compiles against a spelling no later
            # tick ever reproduces (a permanent extra executable).
            norm = {}
            for k, s in self._cache_pin.items():
                if s is None:
                    norm[k] = None
                    continue
                parts = list(s.spec)
                while parts and parts[-1] is None:
                    parts.pop()
                norm[k] = jax.sharding.NamedSharding(
                    s.mesh, jax.sharding.PartitionSpec(*parts))
            self._repin = jax.jit(lambda c: c, out_shardings=norm)
        return self._repin(cache)

    def snapshot_request(self, req: Request) -> Optional[dict]:
        """Host-snapshot a mid-decode request's LIVE state for cross-
        engine migration: the already-computed K/V of every written
        position (dense: the slot row's prefix; paged: the mapped
        pages, flattened to one contiguous [L, pos, KV, hd] block —
        layout-neutral, so a dense engine can restore a paged
        snapshot and vice versa) plus the decode-state mirror (pos /
        cur_tok / gen_idx and the PRNG id, so sampled streams continue
        bit-identically). Returns None when there is nothing to
        migrate — the request is terminal, still queued, or mid-
        chunked-prefill (no first token yet; a replay costs the same
        prefill it would need anyway). Call BETWEEN ticks only (the
        scheduler's context — the same contract as submit/cancel).
        Raises ServingFaultError under the injected migrate_raise
        fault so drills exercise the fallback-to-replay path."""
        self._refuse("migration")
        slot = req.slot
        if (req.done or slot is None or req._pf_next is not None
                or not self._active[slot]):
            return None
        if self._raise_migrate:
            self._raise_migrate = False
            raise ServingFaultError("injected migrate fault")
        pos = int(self._positions[slot])
        if self.paged:
            ps = self.page_size
            npg = -(-pos // ps)
            pids = np.asarray(self._ptab[slot, :npg], np.int32)
            # gather the mapped pages -> [L, npg, ps, KV, hd], flatten
            # the (page, in-page) axes (already position-ordered), and
            # truncate to the written prefix
            k = np.asarray(self._cache["k"][:, pids])
            v = np.asarray(self._cache["v"][:, pids])
            k = k.reshape(k.shape[0], npg * ps, *k.shape[3:])[:, :pos]
            v = v.reshape(v.shape[0], npg * ps, *v.shape[3:])[:, :pos]
        else:
            k = np.asarray(self._cache["k"][:, slot, :pos])
            v = np.asarray(self._cache["v"][:, slot, :pos])
        return {"prompt": np.asarray(req.prompt, np.int32),
                "tokens": list(req.tokens),
                "max_new_tokens": int(req.max_new_tokens),
                "temperature": float(req.temperature),
                "top_k": int(req.top_k),
                "eos_id": req.eos_id,
                "tenant": req.tenant,
                "priority": req.priority,
                "pos": pos,
                "cur_tok": int(self._cur_tok[slot]),
                "gen_idx": int(self._gen_idx[slot]),
                "prng_id": int(self._req_ids[slot]),
                "kv_k": k, "kv_v": v,
                "kv_bytes": int(k.nbytes + v.nbytes)}

    def restore_request(self, snap: dict,
                        deadline_s: Optional[float] = None,
                        deadline_ticks: Optional[int] = None,
                        _trace=None) -> Optional[Request]:
        """Admit a migrated snapshot into THIS engine, bypassing the
        queue (the request is already mid-flight — queueing would
        re-order it behind cold admissions): a free slot is claimed
        directly, the paged restore reserves the request's REMAINING
        worst-case page envelope through the same admission-
        reservation accounting as submit (pages already holding the
        snapshot allocate now; the rest reserve), and the K/V block
        uploads with ZERO re-prefilled tokens. Deadlines are the
        REMAINING budget (the caller re-scopes — see
        EngineRouter._remaining_budget). Returns the new live Request
        (its .tokens pre-seeded with the already-generated ids so
        eos/length checks continue where the source left off), or None
        when this engine cannot take it (no free slot / pages / shape
        limits) — the caller falls back to requeue-replay."""
        self._refuse("migration")
        prompt = np.asarray(snap["prompt"], np.int32).reshape(-1)
        t0 = prompt.shape[0]
        max_new = int(snap["max_new_tokens"])
        if t0 + max_new > self.max_len:
            return None
        if snap["top_k"] > self.max_top_k:
            return None
        slot = self._free_slot()
        if slot is None:
            return None
        pos = int(snap["pos"])
        if self.paged:
            need = self._pages_needed(t0, max_new)
            if need > self._pool.available():
                return None
        req = Request(self._next_id, prompt, max_new,
                      float(snap["temperature"]), int(snap["top_k"]),
                      snap["eos_id"],
                      deadline_s=(None if deadline_s is None
                                  else float(deadline_s)),
                      deadline_ticks=(None if deadline_ticks is None
                                      else int(deadline_ticks)),
                      tenant=str(snap.get("tenant", "default")),
                      priority=int(snap.get("priority", 0)))
        self._next_id += 1
        req.t_submit = time.perf_counter()
        req._tick_submit = self._ticks
        req._engine = self
        req.tokens = list(snap["tokens"])
        req.trace = _trace
        self._restore_into(req, snap, slot)
        self._m_sub.add()
        return req

    def _restore_into(self, req: Request, snap: dict, slot: int) -> None:
        """Write a snapshot's K/V into `slot` and arm every host
        mirror — the shared tail of cross-engine restore and the
        in-place mesh rebuild. The writes are EAGER in-pool updates
        (migration is rare; the jitted tick bodies and their trace
        caches are untouched), re-pinned to the mesh layout so the
        next donated tick aliases exactly. The PRNG id mirror carries
        the SOURCE engine's id — `_slot_keys` folds the mirror, not
        the Request, into the stream, so sampled continuations are
        bit-identical to the undisturbed engine."""
        self._tick_rows = None     # a restored row did not tick HERE before
        pos = int(snap["pos"])
        kv_k, kv_v = snap["kv_k"], snap["kv_v"]
        if self.paged:
            ps = self.page_size
            npg = -(-pos // ps)
            need = self._pages_needed(len(req.prompt),
                                      req.max_new_tokens)
            L = kv_k.shape[0]
            pad = np.zeros((L, npg * ps) + kv_k.shape[2:], kv_k.dtype)
            padv = np.zeros_like(pad)
            pad[:, :pos] = kv_k
            padv[:, :pos] = kv_v
            for j in range(npg):
                pid = self._pool.alloc()
                self._ptab[slot, j] = pid
                self._cache["k"] = self._cache["k"].at[:, pid].set(
                    self._rep(pad[:, j * ps:(j + 1) * ps]))
                self._cache["v"] = self._cache["v"].at[:, pid].set(
                    self._rep(padv[:, j * ps:(j + 1) * ps]))
            reserve = max(need - npg, 0)
            self._slot_reserve[slot] = reserve
            self._pool.reserved += reserve
            self._pt_dirty = True
        else:
            self._cache["k"] = self._cache["k"].at[
                :, slot, :pos].set(self._rep(kv_k))
            self._cache["v"] = self._cache["v"].at[
                :, slot, :pos].set(self._rep(kv_v))
        self._cache = self._pin_cache_host(self._cache)
        now = time.perf_counter()
        req.slot = slot
        req._t_last = now
        self._slot_req[slot] = req
        self._positions[slot] = pos
        self._active[slot] = True
        self._cur_tok[slot] = int(snap["cur_tok"])
        self._temps[slot] = req.temperature
        self._top_ks[slot] = req.top_k
        self._req_ids[slot] = int(snap["prng_id"])
        self._gen_idx[slot] = int(snap["gen_idx"])
        self._eos_ids[slot] = (-1 if req.eos_id is None
                               else int(req.eos_id))
        self._max_new[slot] = int(req.max_new_tokens)
        self._dirty = True
        if req.trace is not None:
            req._sp_decode = req.trace.begin(
                "decode", slot=slot, migrated=True,
                attempt=req.trace.attempt)

    def detach_request(self, req: Request) -> bool:
        """Non-terminal release — the live-migration seam. Drops `req`
        from THIS engine (slot, pages, reservation, queue) WITHOUT the
        terminal transition: the request continues on another engine,
        so its trace stays OPEN (only the open decode span closes) and
        no terminal-reason counter fires. finish_reason is the
        sentinel "migrated" — deliberately NOT in TERMINAL_REASONS,
        because for this engine the request did not terminate, it
        left. requests_completed still advances so submitted-completed
        stays a true in-flight gauge. Returns False when the request
        already resolved."""
        self._refuse("migration")
        if req.done:
            return False
        if req.slot is not None:
            self._clear_slot(req.slot)
        else:
            try:
                self._queue.remove(req)
            except ValueError:
                pass
        req.slot = None
        req.done = True
        req.finish_reason = "migrated"
        if req.trace is not None and req._sp_decode is not None:
            req.trace.end(req._sp_decode)
            req._sp_decode = None
        self._m_done.add()
        self._m_occ.set(int(self._active.sum()))
        self._m_queue.set(len(self._queue))
        return True

    def rebuild_on_mesh(self, mesh) -> int:
        """Preemption recovery: re-host THIS engine on a (typically
        smaller) mesh without dropping its live streams. Every active
        slot host-snapshots (`snapshot_request`), params re-host
        through device_get -> `_shard_params` onto the new mesh (the
        simulated-loss drill's seam — a production loss would re-read
        weights from their source), the pool cache reallocates via
        `_new_cache` under a FRESH `_cache_pin` (sharded-birth
        discipline: no device ever stages the whole pool), the jitted
        bodies re-make (`_make_executables` — one warmup recompile
        each, then the trace ceilings hold), and the snapshots restore
        IN PLACE onto the SAME Request objects — callers' handles keep
        filling, zero re-prefilled tokens, streams bit-identical.
        Requests that cannot snapshot (mid-chunked-prefill) resolve
        "evicted"; queued requests stay queued and prefill on the new
        mesh. Returns the number of live streams migrated."""
        if self.tp_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has no {self.tp_axis!r} axis")
        if self.family.serving_specs is None:
            raise ValueError(
                f"family {self.family.name!r} has no "
                "SERVING_PARAM_SPECS — it cannot run tensor-parallel")
        snaps = []
        for req in list(self._slot_req):
            if req is None:
                continue
            try:
                snap = self.snapshot_request(req)
            except Exception as e:             # noqa: BLE001
                self._on_fault("migrate", e)
                snap = None
            if snap is None:
                self._finish(req, "evicted")
            else:
                slot = req.slot
                self._clear_slot(slot)         # old pool's accounting
                req.slot = None
                snaps.append((req, snap))
        # host copies BEFORE the old mesh state is dropped
        params_host = jax.device_get(self._params)
        key_host = np.asarray(jax.device_get(self._base_key))
        from jax.sharding import NamedSharding, PartitionSpec
        self.mesh = mesh
        self.tp = int(mesh.shape[self.tp_axis])
        self._rep_sharding = NamedSharding(mesh, PartitionSpec())
        self._cache_pin = None
        self._params = self._shard_params(params_host)
        if self.paged:
            self._pool = _PagePool(self.num_pages, self.page_size)
            self._ptab[:] = 0
            self._slot_reserve[:] = 0
            self._prefilling.clear()
            self._pt_dirty = False
        self._cache = self._new_cache()        # re-pins the layout
        self._base_key = self._rep(key_host)
        self._poison_ones = self._rep(np.ones(self.num_slots,
                                              np.float32))
        self._dstate = None
        self._dirty = True
        self._make_executables()
        for req, snap in snaps:
            slot = self._free_slot()
            self._restore_into(req, snap, slot)
        self._flight.note(serving_rebuild=dict(mesh.shape),
                          tick=self._ticks, migrated=len(snaps))
        self._flight.dump("serving_rebuild")
        print(f"[serving] rebuilt on mesh {dict(mesh.shape)} at tick "
              f"{self._ticks}: {len(snaps)} live stream(s) migrated",
              file=sys.stderr, flush=True)
        return len(snaps)

    def _maybe_finish(self, req: Request) -> None:
        slot = req.slot
        if req.eos_id is not None and req.tokens[-1] == req.eos_id:
            self._finish(req, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length")
        elif slot is not None and self._positions[slot] >= self.max_len:
            self._finish(req, "evicted")  # cache full — unreachable via
            #                               submit's length check

    # --------------------------------------------------------- SLO stats
    def slo_snapshot(self) -> dict:
        """The raw SLO samples (ms): time-to-first-token (queue wait
        included) and inter-token latency, bounded rings."""
        return {"ttft_ms": [round(v, 3) for v in self._slo_ttft],
                "itl_ms": [round(v, 3) for v in self._slo_itl]}

    def export_slo_jsonl(self, path: str) -> None:
        """Append one serving_slo record to a telemetry JSONL file and
        DRAIN the sample rings: each record covers the window since the
        previous export, so a periodic exporter (the natural cadence,
        alongside monitor.export_jsonl) never double-counts —
        tools/telemetry_report.py merges all records' samples into the
        serving section's TTFT / inter-token p50/p95/p99."""
        rec = {"kind": "serving_slo", "t": time.time(),
               **self.slo_snapshot()}
        self._slo_ttft.clear()
        self._slo_itl.clear()
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")


def create_serving_engine(model_or_params, cfg=None, **kw) -> ServingEngine:
    """Build a ServingEngine from a facade model (GPTModel/LlamaModel —
    family and params are inferred) or from a raw (params, cfg) pair
    plus family=..."""
    from ..models.facade import FacadeModel
    if isinstance(model_or_params, FacadeModel):
        model = model_or_params
        family = kw.pop("family", getattr(model, "_serving_family", None))
        if family is None:
            raise ValueError(f"{type(model).__name__} does not name a "
                             "_serving_family; pass family=...")
        from ..framework.dispatch import raw_value
        params = {n: raw_value(p) for n, p in model._params.items()}
        return ServingEngine(params, model.cfg, family=family, **kw)
    if cfg is None:
        raise ValueError("create_serving_engine(params, cfg, ...) needs "
                         "the model config")
    return ServingEngine(model_or_params, cfg, **kw)
