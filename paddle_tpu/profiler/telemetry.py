"""Batched step-metrics pipeline: in-jit scalars, one host pull per K steps.

Reference analog: the profiler/monitor export loops that stream scalar
training stats (python/paddle/profiler/profiler.py:340 stats pipeline +
the paddle/fluid/platform/monitor.h:1 registries the fleet trainers
publish into). The reference logs from host code; here a device->host
pull per step is a sync per step — the host stops enqueueing ahead of
the device — so per-step scalar logging would stretch step time.

TPU-native design: the jitted step computes its scalars (loss, grad/
update global-norm, param global-norm, non-finite count, lr) into a
small `(every, n_fields)` float32 device accumulator that is DONATED
through the step like the params/opt buffers. The accumulator carries
its own int32 write cursor ON DEVICE, so recording needs no per-step
host->device step-index transfer either. Every `every` steps the host
pulls the whole block in ONE explicit `jax.device_get` (routed through
the `_host_pull` seam so tests can count transfers) and hands it to a
background JSONL writer thread — the step loop never blocks on JSON
encoding or disk.

The contract "zero extra host syncs between flush boundaries" is
enforced by tests/test_telemetry.py: the whole loop runs under
`jax.transfer_guard("disallow")` (explicit transfers — the flush — stay
legal; any implicit per-step pull or push trips the guard on backends
with real transfers) and the `_host_pull` seam must fire exactly
steps/every times.

JSONL schema (tools/telemetry_report.py is the consumer):
  {"kind": "run",     "t", "pid", "every", "fields", ...meta}
  {"kind": "step",    "step", <field>: float, ...}   # one per step
  {"kind": "flush",   "t", "step", "n"}              # one per pull
  {"kind": "monitor", "t", "pid", "stats": {...}}    # one per pull
  {"kind": "event",   "name", "t", "dur_s"}          # optional spans
"""
from __future__ import annotations

import json
import math
import os
import queue
import threading
import time
from typing import Dict, Optional, Sequence

from . import monitor

DEFAULT_FIELDS = ("loss", "grad_norm", "param_norm", "nonfinite", "lr")
# the MFU-observatory field set: + tokens trained per step, so the
# flush can turn flush-to-flush wall time into an achieved-MFU gauge
# (train.mfu) against the cost-model ledger's FLOPs/token
MFU_FIELDS = DEFAULT_FIELDS + ("tokens",)


# ------------------------------------------------------------ in-jit helpers
def global_norm(tree):
    """sqrt(sum of squares) over every inexact leaf — the grad/param
    global-norm scalar, computed in-jit."""
    import jax
    import jax.numpy as jnp
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf = jnp.asarray(leaf)
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            total += jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return jnp.sqrt(total)


def nonfinite_count(tree):
    """Number of non-finite elements across every inexact leaf (in-jit)."""
    import jax
    import jax.numpy as jnp
    total = jnp.zeros((), jnp.int32)
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf = jnp.asarray(leaf)
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            total += jnp.sum(~jnp.isfinite(leaf)).astype(jnp.int32)
    return total


def grad_norm_from_moments(opt_old, opt_new, beta1=0.9, beta2=0.95):
    """Exact gradient global-norm recovered from an Adam-family moment
    update — the step functions in this repo return (loss, params',
    opt') without exposing grads, but the moments preserve them.

    Preferred path (opt state carries second moments under "v", as
    models.gpt.init_opt_state does): `new_v = b2*v + (1-b2)*g^2`, and
    the global norm only needs SUMS, which are linear —
    `sum(g^2) = (sum(new_v) - b2*sum(old_v)) / (1-b2)`. Crucially the
    old tree is consumed by a scalar reduction, not an elementwise
    combine with the new tree, so XLA can reduce-then-overwrite and the
    donated opt buffers stay donated (the elementwise first-moment
    recovery `g = (new_m - b1*m)/(1-b1)` needs both trees live at once
    — measured ~10% extra on the CPU bench rung vs ~0 for this form).

    Fallback (only "m" present): the elementwise recovery above, exact
    but donation-breaking. No moments at all -> nan."""
    import jax
    import jax.numpy as jnp
    if isinstance(opt_old, dict) and "v" in opt_old and "v" in opt_new:
        s_old = jnp.zeros((), jnp.float32)
        s_new = jnp.zeros((), jnp.float32)
        for lo, ln in zip(jax.tree_util.tree_leaves(opt_old["v"]),
                          jax.tree_util.tree_leaves(opt_new["v"])):
            s_old += jnp.sum(jnp.asarray(lo, jnp.float32))
            s_new += jnp.sum(jnp.asarray(ln, jnp.float32))
        sq = (s_new - beta2 * s_old) / (1.0 - beta2)
        return jnp.sqrt(jnp.maximum(sq, 0.0))
    m_old = opt_old.get("m") if isinstance(opt_old, dict) else None
    m_new = opt_new.get("m") if isinstance(opt_new, dict) else None
    if m_old is None or m_new is None:
        return jnp.asarray(jnp.nan, jnp.float32)
    total = jnp.zeros((), jnp.float32)
    for lo, ln in zip(jax.tree_util.tree_leaves(m_old),
                      jax.tree_util.tree_leaves(m_new)):
        g = (jnp.asarray(ln, jnp.float32) - beta1
             * jnp.asarray(lo, jnp.float32)) / (1.0 - beta1)
        total += jnp.sum(jnp.square(g))
    return jnp.sqrt(total)


# ------------------------------------------------------- host pull seam
def _host_pull(x):
    """THE device->host transfer of the pipeline — explicit, so it stays
    legal under `jax.transfer_guard("disallow")`. One seam so the
    flush-cadence test can count every pull the pipeline makes."""
    import jax
    return jax.device_get(x)


# ------------------------------------------------------- background writer
class TelemetryWriter:
    """Append-only JSONL writer draining a queue on a daemon thread, so
    flush boundaries enqueue host arrays and return without touching
    json.dumps or the filesystem."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._run, name="paddle-telemetry-writer", daemon=True)
        self._thread.start()

    def put(self, records) -> None:
        self._q.put(list(records))

    def _run(self) -> None:
        while True:
            recs = self._q.get()
            try:
                if recs is None:
                    return
                try:
                    with open(self.path, "a") as f:
                        for r in recs:
                            f.write(json.dumps(r) + "\n")
                except (OSError, TypeError, ValueError) as e:
                    # a full disk or unserializable record must not kill
                    # the drain thread (flush()/close() would then hang) —
                    # but the loss must be VISIBLE: counted in the monitor
                    # registry and reported once on stderr
                    n = monitor.counter("telemetry_write_errors").add()
                    if n == 1:
                        import sys
                        print(f"[telemetry] dropping records: {e}",
                              file=sys.stderr, flush=True)
            finally:
                self._q.task_done()

    def flush(self, timeout: Optional[float] = None) -> None:
        """Block until every enqueued record is on disk."""
        deadline = None if timeout is None else time.time() + timeout
        while not self._q.unfinished_tasks == 0:
            if deadline is not None and time.time() > deadline:
                raise TimeoutError("telemetry writer did not drain")
            time.sleep(0.005)

    def close(self) -> None:
        if self._thread.is_alive():
            self._q.put(None)
            self._thread.join(timeout=10)


# ------------------------------------------------------------- the pipeline
class TelemetryPipeline:
    """Owns the field layout, the device accumulator protocol, and the
    flush cadence.

    Usage (plain loop; `instrument_train_step` packages this for
    facade-style steps):

        tele = TelemetryPipeline(path, every=8)
        state = tele.device_init()
        @jax.jit                       # donate params/opt/state
        def step(params, opt, batch, tstate):
            ...
            tstate = tele.device_record(tstate, loss=loss,
                                        grad_norm=global_norm(grads))
            return loss_dev, new_params, new_opt, tstate
        for i in range(n):
            _, params, opt, state = step(params, opt, batch, state)
            state = tele.tick(i, state)    # ONE pull every `every` steps
        tele.close()
    """

    def __init__(self, path: str, every: int = 8,
                 fields: Sequence[str] = DEFAULT_FIELDS,
                 meta: Optional[dict] = None,
                 flops_per_token: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        if every < 1:
            raise ValueError("every must be >= 1")
        self.path = path
        self.every = int(every)
        self.fields = tuple(fields)
        # achieved-MFU wiring (docs/observability.md "Training
        # observability"): with `flops_per_token` (the cost-model
        # ledger's model_flops / tokens — cost_model.
        # train_flops_per_token) and `peak_flops` (TOTAL across the
        # plan's chips), each flush past the first (the compile window)
        # computes mfu = flops_per_token · tokens/s ÷ peak_flops from
        # the recorded `tokens` field and the flush-to-flush wall delta
        # — no extra pulls, no per-step clocks — and publishes the
        # `train.mfu` / `train.tokens_per_s` gauges into the same
        # monitor snapshot the flush already writes.
        if flops_per_token and "tokens" not in self.fields:
            raise ValueError(
                "flops_per_token= needs a 'tokens' field "
                "(fields=telemetry.MFU_FIELDS)")
        self._flops_per_token = flops_per_token
        self._peak_flops = peak_flops
        self._prev_flush_t: Optional[float] = None
        self._writer = TelemetryWriter(path)
        self._pulls = 0
        self._floor = 0        # lowest cursor value this process wrote
        header = {"kind": "run", "t": time.time(), "pid": os.getpid(),
                  "every": self.every, "fields": list(self.fields)}
        if meta:
            header.update(meta)
        self._writer.put([header])

    # ------------------------------------------------------------- device
    def device_init(self, start: int = 0):
        """Fresh accumulator: {"buf": (every, n_fields) f32 nan, "n": i32
        cursor}. `start` seeds the cursor so a resumed trainer's records
        continue from its restored step instead of colliding with the
        pre-crash process's ids in a shared JSONL."""
        import jax.numpy as jnp
        self._floor = int(start)
        return {"buf": jnp.full((self.every, len(self.fields)), jnp.nan,
                                jnp.float32),
                "n": jnp.full((), int(start), jnp.int32)}

    def device_record(self, tstate, **scalars):
        """In-jit: write one row at the device-side cursor and advance it.
        Unknown field names raise; missing fields record nan."""
        import jax
        import jax.numpy as jnp
        unknown = set(scalars) - set(self.fields)
        if unknown:
            raise ValueError(f"unknown telemetry fields {sorted(unknown)}; "
                             f"declared fields are {self.fields}")
        row = jnp.stack([
            jnp.asarray(scalars.get(f, jnp.nan), jnp.float32)
            for f in self.fields])
        idx = jnp.mod(tstate["n"], self.every)
        buf = jax.lax.dynamic_update_slice(tstate["buf"], row[None, :],
                                           (idx, 0))
        return {"buf": buf, "n": tstate["n"] + 1}

    # --------------------------------------------------------------- host
    def due(self, step: int) -> bool:
        """True when the host loop (0-based step just run) is at a flush
        boundary."""
        return (int(step) + 1) % self.every == 0

    def flush(self, tstate) -> None:
        """Pull the accumulator to host (ONE explicit transfer) and hand
        the block to the background writer."""
        host = _host_pull(tstate)
        self._pulls += 1
        self._enqueue(host)

    def _enqueue(self, host, count: Optional[int] = None) -> None:
        import numpy as np
        buf = np.asarray(host["buf"])
        n = int(host["n"])
        now = time.time()
        # rows [first, n) are valid BY CONSTRUCTION of the device cursor —
        # no in-band sentinel, so a step whose every field is nan (the
        # diverged step an operator most needs) is still emitted. The
        # floor clamp keeps a resume-seeded cursor (device_init(start=S)
        # with S % every != 0) from emitting the nan-filled slots below S
        # as phantom records on its first flush.
        first = max(self._floor,
                    n - (self.every if count is None else count))
        records = []
        for step in range(first, n):
            row = buf[step % self.every]
            rec = {"kind": "step", "step": step}
            for f, v in zip(self.fields, row):
                rec[f] = None if math.isnan(float(v)) else float(v)
            records.append(rec)
        records.append({"kind": "flush", "t": now, "step": n - 1,
                        "n": len(records)})
        # achieved MFU: from the SECOND flush on (the first window
        # absorbs the jit compile — telemetry_report's exclusion rule),
        # turn this window's recorded tokens + wall delta into the
        # train.mfu / train.tokens_per_s gauges. Gauges are set BEFORE
        # the snapshot below so the same flush's monitor record carries
        # them into the JSONL.
        if (self._flops_per_token and "tokens" in self.fields
                and self._prev_flush_t is not None
                and now > self._prev_flush_t):
            tok_i = self.fields.index("tokens")
            window_tokens = float(sum(
                0.0 if math.isnan(float(buf[s % self.every][tok_i]))
                else float(buf[s % self.every][tok_i])
                for s in range(first, n)))
            if window_tokens > 0:
                peak = self._peak_flops
                if not peak:
                    # the recorded tokens are GLOBAL, so the default
                    # denominator must be too: the live device's row of
                    # the peaks table per visible device (a device that
                    # is not in the table raises — MFU against a guessed
                    # peak is not a measurement) — pass peak_flops=
                    # explicitly when the mesh spans a subset of the
                    # backend
                    import jax
                    from ..device import chip_peaks
                    peak = self._peak_flops = (
                        chip_peaks(jax.devices()[0].device_kind).flops
                        * jax.device_count())
                tps = window_tokens / (now - self._prev_flush_t)
                monitor.gauge("train.tokens_per_s").set(round(tps, 1))
                monitor.gauge("train.mfu").set(
                    round(self._flops_per_token * tps / peak, 6))
        self._prev_flush_t = now
        # live memory gauges ride the same flush (host-side PJRT /
        # proc reads, zero device pulls) so the monitor record below
        # carries hbm.bytes_in_use / hbm.peak_bytes into the JSONL
        from .mem_audit import publish_hbm_gauges
        publish_hbm_gauges()
        records.append({"kind": "monitor", "t": now, "pid": os.getpid(),
                        "stats": monitor.snapshot()})
        self._writer.put(records)

    def tick(self, step: int, tstate):
        """Per-step host hook: flush when due, else a no-op. Returns the
        (possibly reused) device state — rows are overwritten in place on
        the next cycle, so no re-zeroing transfer is needed."""
        if self.due(step):
            self.flush(tstate)
        return tstate

    def event(self, name: str, t: Optional[float] = None,
              dur_s: float = 0.0) -> None:
        """Append a host-side event line (launcher phases, checkpoint
        saves, ...) to the same stream."""
        self._writer.put([{"kind": "event", "name": name,
                           "t": time.time() if t is None else t,
                           "dur_s": dur_s}])

    @property
    def pulls(self) -> int:
        """Device->host transfers performed so far (test observability)."""
        return self._pulls

    def close(self, final_state=None) -> None:
        """Flush a trailing partial window (if given) and stop the
        writer after the queue drains."""
        if final_state is not None:
            host = _host_pull(final_state)
            self._pulls += 1
            tail = int(host["n"]) % self.every
            if tail:    # rows since the last flush boundary, no re-emits
                self._enqueue(host, count=tail)
        self._writer.flush(timeout=30)
        self._writer.close()


# --------------------------------------------------- facade-style wrapper
def instrument_train_step(step_fn, pipeline: TelemetryPipeline, cfg=None,
                          lr=None, beta1: float = 0.9, beta2: float = 0.95,
                          donate: bool = True, mesh=None, plan=None,
                          **step_kw):
    """Wrap a facade-contract step (`step_fn(params, opt_state, batch,
    ...) -> (loss, new_params, new_opt)`) with in-jit telemetry.

    Returns a jitted `fn(params, opt_state, batch, tstate) -> (loss,
    new_params, new_opt, tstate')` with params/opt/tstate donated (the
    same facade builder, so the donation policy cannot drift). Recorded
    scalars: loss; grad global-norm (recovered exactly from Adam-family
    second moments under "v" via the donation-preserving sum identity,
    falling back to the elementwise first-moment delta when only "m"
    exists, nan with neither — see grad_norm_from_moments); param
    global-norm; non-finite count over the updated params; lr.

    `lr` is FORWARDED to the wrapped step exactly like
    make_train_step's kwargs (and recorded); `beta1`/`beta2` are
    recorder-only — they must DESCRIBE the optimizer the step already
    uses, they do not configure it. `mesh`/`plan` pass through to the
    facade builder: the accumulator rides the planner-driven GSPMD
    step as a replicated donated leaf (docs/parallel_training.md), and
    the recorded scalars — global norms, the moment-sum identity — are
    full-tree reductions, so their values match the unsharded step's."""
    from ..models.facade import make_train_step, plan_step_cell
    if lr is not None:
        step_kw["lr"] = lr
    # pp>1 plans swap the family step for the full-manual pipelined one
    # (models/facade.plan_step_cell — the same seam the resilient guard
    # routes through, incl. the elastic rebuild hook's fresh-identity
    # subtlety); pp=1 keeps the historical partial
    inner, _outer, _plan_rebuild = plan_step_cell(
        step_fn, cfg=cfg, mesh=mesh, plan=plan, **step_kw)

    def instrumented(params, opt_state, batch, tstate):
        loss, new_params, new_opt = inner(params, opt_state, batch)
        scalars = {
            "loss": loss,
            "grad_norm": grad_norm_from_moments(
                opt_state, new_opt, beta1=beta1, beta2=beta2)
            if isinstance(opt_state, dict) else float("nan"),
            "param_norm": global_norm(new_params),
            "nonfinite": nonfinite_count(new_params),
        }
        if lr is not None and "lr" in pipeline.fields:
            scalars["lr"] = lr
        if "tokens" in pipeline.fields:
            # trained tokens this step, from the STATIC batch shape
            # ([B, S+1] next-token batches train B·S tokens) — a trace
            # constant, so the accumulator row costs nothing extra and
            # the loss math is untouched (bit-identical trajectories,
            # tests/test_train_observability.py)
            toks = batch["tokens"] if isinstance(batch, dict) else batch
            shape = getattr(toks, "shape", ())
            scalars["tokens"] = (
                float(shape[0] * (shape[1] - 1)) if len(shape) >= 2
                else float("nan"))
        scalars = {k: v for k, v in scalars.items()
                   if k in pipeline.fields}
        tstate = pipeline.device_record(tstate, **scalars)
        return loss, new_params, new_opt, tstate

    instrumented._plan_resolved = True
    instrumented._plan_rebuild = _plan_rebuild
    _outer["fn"] = instrumented
    return make_train_step(instrumented, donate=donate, extra_donate=(3,),
                           mesh=mesh, plan=plan)
