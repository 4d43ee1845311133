"""Resilient training step loop: non-finite skip, rollback, watchdog.

Reference analog: the ElasticManager fault watch + restart protocol
(/root/reference/python/paddle/distributed/fleet/elastic/manager.py:124,
exit codes manager.py:30-31) and the AMP GradScaler's found_inf
skip-update semantics (amp/grad_scaler.py here generalizes the same
guard to ANY train step, not just scaled ones). The reference has no
step-level watchdog or automatic rollback; this module exceeds it
because on a long run a hung dispatch (a wedged device, a lost host in
a multi-host job) is an expected fault, not an anomaly.

Three guards compose around `models.facade.make_train_step`:

- **skip-step**: the jitted step returns `(loss, params', opt', ok)`
  where `ok = isfinite(loss)`; when not ok the new params/opt trees are
  replaced IN-JIT by the old ones (`jnp.where` select, so donation stays
  legal), i.e. a non-finite step is a no-op update — the GradScaler
  found_inf pattern without a scaler.
- **rollback**: after `rollback_after` consecutive skipped steps the
  trainer reloads the newest intact snapshot from its CheckpointManager
  (checksum-verified, falls back past corrupt ones) and rewinds its step
  counter — divergence that a skip cannot absorb gets cut at the last
  good state.
- **watchdog**: host pulls of the step's results run under a wall-clock
  budget with bounded retry + exponential backoff (a hung dispatch
  stalls ANY pull; re-polling the same future is the only safe
  retry since donated buffers cannot be re-dispatched). When the budget
  is exhausted the worker exits with ELASTIC_EXIT_CODE (101, the
  reference's elastic protocol) so the launcher restarts the pod and
  the restarted process resumes from the LATEST pointer.
"""
from __future__ import annotations

import functools
import sys
import threading
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

from .checkpoint import _UNSET, CheckpointManager
from ..distributed.launch.heartbeat import ELASTIC_EXIT_CODE  # noqa: F401

# Fault-injection seam (paddle_tpu.testing.faults): called with the step
# index about to run; returns a loss multiplier (1.0, or nan to poison)
# and may side-effect (kill the process, stall the heartbeat). Production
# code never sets it.
_STEP_HOOK: Optional[Callable[[int], float]] = None


class StepHungError(RuntimeError):
    """A device->host pull outlived the watchdog budget (hung
    dispatch)."""


def plan_state_specs(plan):
    """The restore-layout tree for a TrainPlan's trainer state: params
    per the plan's remapped PARAM_SPECS, Adam m/v mirroring them leaf
    for leaf (the facade pin rule). ONE home — ResilientTrainer's
    ctor/rebuild, the elastic controller's reshard-restore and the
    chaos drill all derive the layout here, so an optimizer-state
    shape change cannot drift between them. None when the plan carries
    no spec table."""
    if plan is None or not getattr(plan, "specs", None):
        return None
    return {"params": plan.specs,
            "opt_state": {"m": plan.specs, "v": plan.specs}}


@dataclass
class ResilienceConfig:
    """Knobs for ResilientTrainer (defaults are safe-but-lenient)."""
    rollback_after: int = 3        # consecutive skipped steps -> rollback
    max_rollbacks: int = 5         # give up (raise) after this many
    checkpoint_every: int = 0      # steps between snapshots (0 = manual)
    async_checkpoint: bool = False  # save via manager.save_async: the
    #                                 disk write leaves the step path
    #                                 (docs/parallel_training.md)
    watchdog_timeout: float = 0.0  # seconds per host pull (0 = no watchdog)
    retries: int = 3               # extra backoff waits after the timeout
    backoff_base: float = 2.0      # first retry wait, doubling each retry
    backoff_max: float = 60.0      # per-retry wait ceiling
    exit_on_hang: bool = False     # sys.exit(ELASTIC_EXIT_CODE) on hang


def make_resilient_step(step_fn, cfg=None, donate: bool = True,
                        telemetry=None, mesh=None, plan=None, **step_kw):
    """Build the guarded jitted step:
    `(params, opt_state, batch, poison) -> (loss, params', opt', ok)`.

    `mesh`/`plan` (parallel.planner.plan_train) pass straight through to
    models.facade.make_train_step: the guard (select + ok flag) and the
    telemetry accumulator ride the planner-driven GSPMD step unchanged —
    the select is elementwise (sharding-preserving) and the ok/loss
    scalars replicate, so the sharded pins hold leaf for leaf.

    `step_fn(params, opt_state, batch, ...) -> (loss, new_params,
    new_opt)` is the same contract `models.facade.make_train_step` takes;
    params/opt buffers are donated identically. `poison` is a loss
    multiplier (normally 1.0) that the chaos harness sets to nan —
    multiplying INSIDE the jit means injected and organic non-finite
    losses exercise the exact same guard. `ok` requires the loss AND
    every updated param/opt leaf to be finite (a backward pass can
    overflow while the loss is still finite — committing, let alone
    snapshotting, NaN params would defeat rollback); when not ok the
    returned trees are the (unchanged) inputs and the returned loss is
    nan, so ONE host pull of the loss communicates both values.

    With `telemetry` (a profiler.telemetry.TelemetryPipeline) the step
    additionally takes and returns the donated device accumulator —
    `(params, opt_state, batch, poison, tstate) -> (loss, params',
    opt', ok, tstate')` — recording the RAW (pre-select) loss, update
    global-norm, param global-norm and non-finite count in-jit, so a
    diverging run's telemetry shows the actual blow-up, not the
    nan-folded skip."""
    import jax
    import jax.numpy as jnp
    from ..models.facade import make_train_step, plan_step_cell
    # pp>1 plans swap the family step for the full-manual pipelined one
    # HERE (the guard wraps the resolved fn, so the select + ok flag
    # ride the 4D step exactly like the 3D one); the cell's
    # _plan_rebuild hook lets the elastic rebuild seam re-resolve the
    # pipelined inner against a degraded mesh (a pp closure bakes the
    # stage grid in; 3D closures are mesh-agnostic) — see
    # models/facade.plan_step_cell for the fresh-identity subtlety
    inner, _outer, _plan_rebuild = plan_step_cell(
        step_fn, cfg=cfg, mesh=mesh, plan=plan, **step_kw)

    def tree_finite(tree):
        fin = jnp.asarray(True)
        for leaf in jax.tree_util.tree_leaves(tree):
            if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
                fin &= jnp.all(jnp.isfinite(leaf))
        return fin

    def guard(params, opt_state, batch, poison):
        loss, new_params, new_opt = inner(params, opt_state, batch)
        loss = loss * poison
        ok = (jnp.isfinite(loss) & tree_finite(new_params)
              & tree_finite(new_opt))

        def keep(new, old):
            return jnp.where(ok, new, old)

        kept_params = jax.tree_util.tree_map(keep, new_params, params)
        kept_opt = jax.tree_util.tree_map(keep, new_opt, opt_state)
        return loss, new_params, kept_params, kept_opt, ok

    def guarded(params, opt_state, batch, poison):
        loss, _raw_params, kept_params, kept_opt, ok = guard(
            params, opt_state, batch, poison)
        return jnp.where(ok, loss, jnp.nan), kept_params, kept_opt, ok

    guarded._plan_resolved = True
    guarded._plan_rebuild = _plan_rebuild
    _outer["fn"] = guarded
    if telemetry is None:
        # the facade owns the jit/donation policy (ONE home — see
        # models/facade.py); the guard only adds the select + ok flag
        return make_train_step(guarded, donate=donate, mesh=mesh,
                               plan=plan)

    from ..profiler.telemetry import global_norm, nonfinite_count

    def guarded_telemetry(params, opt_state, batch, poison, tstate):
        loss, raw_params, kept_params, kept_opt, ok = guard(
            params, opt_state, batch, poison)
        scalars = {
            "loss": loss,                      # raw: shows the divergence
            "update_norm": global_norm(jax.tree_util.tree_map(
                lambda n, o: jnp.asarray(n, jnp.float32)
                - jnp.asarray(o, jnp.float32), raw_params, params)),
            "param_norm": global_norm(kept_params),
            "nonfinite": nonfinite_count(raw_params),
            "ok": ok,
        }
        tstate = telemetry.device_record(
            tstate, **{k: v for k, v in scalars.items()
                       if k in telemetry.fields})
        return (jnp.where(ok, loss, jnp.nan), kept_params, kept_opt, ok,
                tstate)

    guarded_telemetry._plan_resolved = True
    guarded_telemetry._plan_rebuild = _plan_rebuild
    _outer["fn"] = guarded_telemetry
    return make_train_step(guarded_telemetry, donate=donate,
                           extra_donate=(4,), mesh=mesh, plan=plan)


# telemetry field layout for the resilient trainer's pipeline (the
# default DEFAULT_FIELDS carries grad_norm/lr, which the guarded step
# cannot see — pass these to TelemetryPipeline(fields=...))
RESILIENT_FIELDS = ("loss", "update_norm", "param_norm", "nonfinite", "ok")


def pull_with_watchdog(value, timeout: float, retries: int = 3,
                       backoff_base: float = 2.0,
                       backoff_max: float = 60.0,
                       label: str = "step",
                       on_retry=None) -> np.ndarray:
    """Force `value` to a host array under a wall-clock budget.

    The caller needs the value on the host anyway, so forcing is an
    `np.asarray` pull, run in a worker thread. The
    first wait is `timeout`; each of `retries` further waits doubles from
    `backoff_base` (capped at `backoff_max`) — re-polling the SAME pending
    future, because with donated input buffers a re-dispatch is illegal.
    Raises StepHungError when the budget is exhausted.

    `value` may be a zero-arg callable producing the array — the whole
    call then runs under the watchdog clock (the serving engine wraps
    its pull this way so injected stalls are monitored too). `on_retry`
    (if given) observes each backoff attempt index — the serving
    engine's retries counter hangs off it."""
    def force():
        return np.asarray(value() if callable(value) else value)

    if timeout <= 0:
        return force()
    box: dict = {}

    def work():
        try:
            box["val"] = force()
        except BaseException as e:          # surfaced to the caller
            box["err"] = e

    t = threading.Thread(target=work, name="paddle-watchdog-pull",
                         daemon=True)
    t.start()
    waited = 0.0
    for attempt in range(retries + 1):
        grace = timeout if attempt == 0 else min(
            backoff_base * (2.0 ** (attempt - 1)), backoff_max)
        t.join(grace)
        waited += grace
        if not t.is_alive():
            break
        if attempt < retries:
            print(f"[resilience] {label} pull stalled {waited:.1f}s "
                  f"(attempt {attempt + 1}/{retries + 1}); backing off",
                  file=sys.stderr, flush=True)
            if on_retry is not None:
                on_retry(attempt)
    if t.is_alive():
        raise StepHungError(
            f"{label} result did not arrive within {waited:.1f}s "
            f"(watchdog {timeout}s + {retries} backoff retries) — hung "
            f"dispatch")
    if "err" in box:
        raise box["err"]
    return box["val"]


class WatchdogPuller:
    """Persistent-thread variant of `pull_with_watchdog` for
    high-frequency callers (the serving engine's ~2 ms decode tick:
    spawning a fresh pull thread per tick costs more than the guard
    protects). ONE daemon worker is reused across pulls; each pull is
    a queue round-trip under the same budget/backoff semantics.
    Responses are sequence-tagged so a pull that outlives its budget
    (StepHungError) cannot deliver its late result to a later call."""

    def __init__(self, label: str = "pull"):
        import queue
        self._label = label
        self._req: "queue.SimpleQueue" = queue.SimpleQueue()
        self._res: "queue.SimpleQueue" = queue.SimpleQueue()
        self._seq = 0
        self._thread: Optional[threading.Thread] = None

    def _ensure(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, name=f"paddle-watchdog-{self._label}",
                daemon=True)
            self._thread.start()

    def _loop(self) -> None:
        while True:
            seq, value = self._req.get()
            try:
                res = value() if callable(value) else value
                # tuple results pass through element-wise (the serving
                # tick's token + telemetry pair rides ONE pull); a
                # ragged tuple must not collapse into an object array
                arr = (tuple(np.asarray(v) for v in res)
                       if isinstance(res, tuple)
                       else np.asarray(res))
                self._res.put((seq, "ok", arr))
            except BaseException as e:      # surfaced to the caller
                self._res.put((seq, "err", e))

    def pull(self, value, timeout: float, retries: int = 3,
             backoff_base: float = 2.0, backoff_max: float = 60.0,
             on_retry=None) -> np.ndarray:
        """Same contract as `pull_with_watchdog` (callable values run
        under the clock; `on_retry` observes backoffs; StepHungError
        on an exhausted budget)."""
        import queue
        if timeout <= 0:
            res = value() if callable(value) else value
            return (tuple(np.asarray(v) for v in res)
                    if isinstance(res, tuple) else np.asarray(res))
        self._ensure()
        self._seq += 1
        seq = self._seq
        self._req.put((seq, value))
        waited, attempt = 0.0, 0
        while attempt <= retries:
            grace = timeout if attempt == 0 else min(
                backoff_base * (2.0 ** (attempt - 1)), backoff_max)
            try:
                rseq, kind, payload = self._res.get(timeout=grace)
            except queue.Empty:
                waited += grace
                if attempt < retries:
                    print(f"[resilience] {self._label} pull stalled "
                          f"{waited:.1f}s (attempt {attempt + 1}/"
                          f"{retries + 1}); backing off",
                          file=sys.stderr, flush=True)
                    if on_retry is not None:
                        on_retry(attempt)
                attempt += 1
                continue
            if rseq != seq:
                continue       # late result of a previously hung pull
            if kind == "err":
                raise payload
            return payload
        # the worker is wedged in the hung pull: abandon it (fresh
        # queues + a fresh thread on the next call) so ONE dead dispatch
        # cannot queue-block every later, healthy pull behind it — the
        # old daemon thread leaks until its pull resolves, same as a
        # pull_with_watchdog thread would
        self._thread = None
        self._req = queue.SimpleQueue()
        self._res = queue.SimpleQueue()
        raise StepHungError(
            f"{self._label} result did not arrive within {waited:.1f}s "
            f"(watchdog {timeout}s + {retries} backoff retries) — hung "
            f"dispatch")


class ResilientTrainer:
    """Owns (params, opt_state, step) and runs guarded steps with
    skip/rollback/watchdog + heartbeat + periodic snapshots.

    Typical wiring (the chaos drill's worker is the executable version):

        mgr = CheckpointManager(ckpt_root, max_to_keep=3)
        tr = ResilientTrainer(train_step, params, opt_state, cfg=cfg,
                              manager=mgr,
                              config=ResilienceConfig(checkpoint_every=1))
        tr.maybe_resume()            # restart -> continue from LATEST
        while tr.step < total:
            loss, ok = tr.train_step(batch_for(tr.step))
    """

    def __init__(self, step_fn, params, opt_state, *, cfg=None,
                 manager: Optional[CheckpointManager] = None,
                 config: Optional[ResilienceConfig] = None,
                 step: int = 0, donate: bool = True, mesh=_UNSET,
                 specs=None, telemetry=None, plan=None, **step_kw):
        self.config = config or ResilienceConfig()
        # restore layout: rollback must reload onto the SAME mesh/specs
        # the trainer resumed/trained with, not whatever mesh is ambient
        # at rollback time
        self._mesh = mesh
        self._specs = specs
        self.telemetry = telemetry
        # a real mesh + plan makes the guarded step the planner-driven
        # GSPMD one (docs/parallel_training.md); restore then reloads
        # onto that same mesh via the layout fields above. With a plan
        # and no explicit specs, rollbacks/resume re-slice per the
        # plan's remapped PARAM_SPECS so the restored trees come back
        # in the executing layout. GATED ON plan: mesh= alone keeps its
        # historical meaning (restore layout ONLY, the step a plain jit
        # honoring caller-committed shardings) — without a spec table
        # the sharded builder would pin every leaf REPLICATED, silently
        # un-sharding an fsdp-laid-out trainer.
        step_mesh = mesh if (plan is not None
                             and mesh not in (_UNSET, None)) else None
        if plan is not None and specs is None and plan.specs:
            self._specs = plan_state_specs(plan)
        self._guarded = make_resilient_step(step_fn, cfg=cfg,
                                            donate=donate,
                                            telemetry=telemetry,
                                            mesh=step_mesh, plan=plan,
                                            **step_kw)
        # created lazily at the first step so the device cursor seeds
        # from the RESUMED step (maybe_resume runs after __init__): a
        # restarted worker's records then continue the shared JSONL's id
        # space instead of re-emitting step 0.. over the pre-crash ones
        self._tstate = None
        self.params = params
        self.opt_state = opt_state
        self.step = int(step)
        self.manager = manager
        self.skipped = 0
        self.rollbacks = 0
        self._bad_streak = 0
        # liveness: no-op unless the launcher exported the contract
        from ..distributed.launch import heartbeat
        heartbeat.start_from_env()
        self._heartbeat = heartbeat
        # observability: monitor counters + the crash flight recorder
        # (dumps are no-ops until $PADDLE_TPU_FLIGHT_DIR is set — the
        # launcher exports it per worker)
        from ..profiler import monitor
        from ..profiler import flight_recorder
        self._mon_skip = monitor.counter("resilience_skip_step")
        self._mon_rollback = monitor.counter("resilience_rollback")
        self._mon_hang = monitor.counter("resilience_watchdog_hang")
        self._mon_steps = monitor.counter("resilience_steps")
        self._mon_step_ms = monitor.gauge("resilience_step_ms")
        self._flight = flight_recorder.recorder()
        self._flight.install_exit_hooks()
        c = self.config
        self._flight.configure(
            trainer="ResilientTrainer", start_step=self.step,
            rollback_after=c.rollback_after, max_rollbacks=c.max_rollbacks,
            checkpoint_every=c.checkpoint_every,
            watchdog_timeout=c.watchdog_timeout)

    # ------------------------------------------------------------- resume
    def maybe_resume(self, mesh=_UNSET, specs=None) -> bool:
        """Load the newest intact snapshot (LATEST-pointed first) if one
        exists; returns True when state was restored. An explicit
        `mesh`/`specs` here also becomes the layout rollbacks reload
        onto."""
        if self.manager is None:
            return False
        if mesh is not _UNSET:
            self._mesh = mesh
        if specs is not None:
            self._specs = specs
        state, step = self.manager.restore(mesh=self._mesh,
                                           specs=self._specs)
        if state is None:
            return False
        self.params = state["params"]
        self.opt_state = state.get("opt_state", self.opt_state)
        saved = state.get("step")
        self.step = int(saved) if saved is not None else int(step or 0)
        return True

    # ------------------------------------------------------------- replan
    def rebuild_plan(self, mesh, plan, *, params=None, opt_state=None,
                     step=None) -> None:
        """Elastic replan seam (parallel/elastic.py): re-target the
        guarded step at a degraded mesh/plan via the facade's
        `_ShardedTrainStep.rebuild` (same step object, fresh pins, one
        new executable — no cache-key bifurcation), swap the restore
        layout to the new plan's specs, and optionally install the
        reshard-restored state. The telemetry device accumulator lived
        on the OLD mesh, so it resets and re-initializes lazily at the
        next step, seeded from the (restored) step counter — exactly
        the maybe_resume continuation semantics."""
        if not hasattr(self._guarded, "rebuild"):
            raise TypeError(
                "rebuild_plan needs the planner-driven sharded step "
                "(make_resilient_step with mesh= and plan=); the plain "
                "jitted step has no mesh to re-target")
        self._guarded.rebuild(mesh=mesh, plan=plan)
        self._mesh = mesh
        if plan is not None and plan.specs:
            self._specs = plan_state_specs(plan)
        self._tstate = None
        if params is not None:
            self.params = params
        if opt_state is not None:
            self.opt_state = opt_state
        if step is not None:
            self.step = int(step)
        self._bad_streak = 0

    # --------------------------------------------------------------- save
    def save(self) -> Optional[str]:
        """Snapshot the live state. With config.async_checkpoint the
        host snapshot is taken here (the donated buffers are about to be
        consumed by the next step) and the commit happens off the step
        path — manager.wait() is the barrier; rollback/restore take it
        implicitly."""
        if self.manager is None:
            return None
        state = {"params": self.params, "opt_state": self.opt_state,
                 "step": np.int64(self.step)}
        if self.config.async_checkpoint:
            return self.manager.save_async(state, self.step)
        return self.manager.save(state, self.step)

    # --------------------------------------------------------------- step
    def train_step(self, batch) -> tuple:
        """Run one guarded step on `batch`. Returns `(loss, ok)` with
        `loss` a host float (nan on a skipped step). Raises StepHungError
        when the watchdog budget is exhausted and `exit_on_hang` is off;
        exits with ELASTIC_EXIT_CODE when it is on. After a hang the
        trainer's buffers are donated-away — a restarted process must
        resume via `maybe_resume()`."""
        import time as _time
        c = self.config
        t0 = _time.perf_counter()
        poison = 1.0
        if _STEP_HOOK is not None:
            poison = _STEP_HOOK(self.step)
        if self.telemetry is not None:
            if self._tstate is None:
                self._tstate = self.telemetry.device_init(start=self.step)
            loss, params, opt, ok, self._tstate = self._guarded(
                self.params, self.opt_state, batch, poison, self._tstate)
        else:
            loss, params, opt, ok = self._guarded(
                self.params, self.opt_state, batch, poison)
        del ok                 # the guarded step folds every badness
        #                        (non-finite loss OR params OR opt) into a
        #                        nan loss, so ok derives from the one loss
        #                        pull — a second device->host pull would
        #                        be a second sync per step and a second
        #                        place to hang
        try:
            loss_host = float(pull_with_watchdog(
                loss, c.watchdog_timeout, c.retries, c.backoff_base,
                c.backoff_max, label=f"step {self.step}"))
        except StepHungError as e:
            self._mon_hang.add()
            self._flight.configure(last_error=str(e))
            if c.exit_on_hang:
                self._flight.dump("watchdog_elastic_exit")
                print(f"[resilience] {e}; exiting "
                      f"{ELASTIC_EXIT_CODE} for elastic restart",
                      file=sys.stderr, flush=True)
                sys.exit(ELASTIC_EXIT_CODE)
            self._flight.dump("watchdog_hang")
            raise
        ok_host = bool(np.isfinite(loss_host))
        self.params, self.opt_state = params, opt
        self._heartbeat.pulse()
        self.step += 1
        dur_s = _time.perf_counter() - t0
        self._mon_steps.add()
        self._mon_step_ms.set(dur_s * 1e3)
        self._flight.note(step=self.step - 1, loss=loss_host, ok=ok_host,
                          dur_s=round(dur_s, 6))
        if self.telemetry is not None:
            self._tstate = self.telemetry.tick(self.step - 1, self._tstate)
        if ok_host:
            self._bad_streak = 0
            if (self.manager is not None and c.checkpoint_every > 0
                    and self.step % c.checkpoint_every == 0):
                self.save()
        else:
            self.skipped += 1
            self._bad_streak += 1
            self._mon_skip.add()
            print(f"[resilience] non-finite loss at step "
                  f"{self.step - 1}: update skipped "
                  f"({self._bad_streak}/{c.rollback_after} before "
                  f"rollback)", file=sys.stderr, flush=True)
            if self._bad_streak >= c.rollback_after:
                self._rollback()
        return loss_host, ok_host

    def _rollback(self) -> None:
        self._mon_rollback.add()
        # the black box captures the bad streak BEFORE the state rewinds
        self._flight.dump("rollback")
        if self.manager is None:
            # nothing to roll back to: reset the streak so training can
            # limp on with skips alone
            self._bad_streak = 0
            return
        if self.rollbacks >= self.config.max_rollbacks:
            raise RuntimeError(
                f"resilience: {self.rollbacks} rollbacks exhausted and "
                f"the loss is still non-finite — giving up")
        state, step = self.manager.restore(mesh=self._mesh,
                                           specs=self._specs)
        if state is None:
            # non-finite before the FIRST snapshot (bad init/LR, or a
            # fault injected at step 0): dying here would turn a
            # recoverable run into a crash that burns the launcher's
            # restart budget — limp on with skips like the manager-less
            # path and let max_rollbacks bound organic divergence later
            print("[resilience] rollback requested but no snapshot "
                  "exists yet; continuing with skip-only recovery",
                  file=sys.stderr, flush=True)
            self._bad_streak = 0
            return
        self.params = state["params"]
        self.opt_state = state.get("opt_state", self.opt_state)
        saved = state.get("step")
        self.step = int(saved) if saved is not None else int(step or 0)
        self.rollbacks += 1
        self._bad_streak = 0
        print(f"[resilience] rolled back to step {self.step} "
              f"(rollback {self.rollbacks}/{self.config.max_rollbacks})",
              file=sys.stderr, flush=True)


def run_resilient(trainer: ResilientTrainer, batch_fn, total_steps: int,
                  on_step: Optional[Callable[[int, float, bool], Any]]
                  = None):
    """Drive `trainer` to `total_steps`, fetching `batch_fn(step)` per
    step (deterministic batches keyed by step index make post-rollback
    re-runs bit-identical — the chaos drill relies on this). `on_step`
    observes `(step_just_run, loss, ok)`."""
    while trainer.step < total_steps:
        step = trainer.step
        loss, ok = trainer.train_step(batch_fn(step))
        if on_step is not None:
            on_step(step, loss, ok)
    return trainer
