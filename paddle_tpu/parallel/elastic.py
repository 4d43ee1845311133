"""Elastic 3D training: device loss -> plan degrade -> reshard-restore
-> resume.

Reference analog: the elastic fleet manager
(/root/reference/python/paddle/distributed/fleet/elastic/manager.py:124
— etcd leases per worker, the master watches for expiry, exit-code-101
restart protocol at manager.py:30). The reference restarts the SAME
world; this controller exceeds it by making plan identity itself
mutable at runtime: when devices disappear mid-run the surviving world
is re-planned (`planner.degrade_plan`: dp gives way first, then fsdp,
tp held), the pinned GSPMD step re-targets through the facade's
`_ShardedTrainStep.rebuild` seam, and the state reshard-restores from
the latest `CheckpointManager` snapshot — the manifest's global
windows re-slice onto the degraded mesh, so the resumed loss
trajectory is bit-consistent with a clean run restored from the same
step onto the same degraded plan (the PR-10 dp2×fsdp2×tp2 -> fsdp8
round trip, applied in anger).

Detection layers (docs/fault_tolerance.md "Elastic 3D training"):

- **device-lease staleness**: every device in the executing mesh holds
  a liveness lease (`DeviceLeases`), pulsed after each committed step.
  In production the pulse is fed by per-host heartbeats (the launcher
  contract); on the 8-virtual-device CPU mesh the fault injector
  (`testing/faults.py` ``device_loss``) WEDGES a lease — backdated, so
  staleness detection fires at the next step boundary without waiting
  out the timeout in real time. Detection is always the staleness
  check; injection only kills the lease.
- **collective-hang watchdog**: the whole guarded step (dispatch +
  loss pull) runs under a `resilience.WatchdogPuller` budget — a
  sharded step whose collective can never complete (a dead peer chip)
  hangs the pull, and the expired budget is read as device loss. The
  ``collective_hang`` fault stalls inside the watched callable (the
  serving tick_stall pattern) so injected and organic hangs exercise
  the same budget; ``straggler`` stalls WITHIN budget and must NOT
  trigger a replan.
- **injectable mesh faults**: `testing/faults.py` consults
  `_FAULT_HOOK` at the `step` and `restore` phase boundaries, so a
  drill can kill a device mid-step, mid-async-save (a pending writer
  at the loss boundary), or mid-restore (a second loss while the
  first replan's restore is running — the controller re-degrades and
  restarts the restore).

Replan protocol (in-process): flight dump -> survivors = world minus
stale leases -> `degrade_plan` (raises NoFeasiblePlanError naming the
violated constraint when nothing fits — never hangs) -> new mesh over
the survivors -> reshard-restore from the newest intact snapshot ->
step rebuild (same `_ShardedTrainStep` object re-pinned for lease
losses; a FRESH trainer for watchdog hangs, because the abandoned
watchdog thread may still hold the old trainer object and must only
ever mutate an orphan — one additionally detached from the shared
CheckpointManager, so a zombie step completing late cannot save an
abandoned-timeline checkpoint into the restored run's root) ->
resume at the restored step. Multi-process
runs route through `request_degraded_restart` instead: the world spec
rides the exit-101 protocol (heartbeat.write_world_spec) and the
launcher re-forms the pod on the surviving world.

Observability: the `train.elastic.*` monitor family — `replans`,
`device_loss`, `collective_hang` counters; `world_size`, `replan_ms`,
`reshard_bytes` gauges — rides the telemetry flush into the JSONL and
surfaces as the `elastic` block in tools/telemetry_report.py.
"""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from .checkpoint import CheckpointManager
from .mesh import build_mesh, device_keys
from .planner import NoFeasiblePlanError, TrainPlan, degrade_plan, \
    plan_train
from .resilience import (ResilienceConfig, ResilientTrainer,
                         StepHungError, WatchdogPuller,
                         plan_state_specs)
from ..distributed.launch.heartbeat import (ELASTIC_EXIT_CODE,
                                            degraded_world,
                                            write_world_spec)

__all__ = ["DeviceLossError", "ElasticConfig", "DeviceLeases",
           "ElasticTrainer", "run_elastic", "request_degraded_restart",
           "NoFeasiblePlanError"]

# Fault-injection seam (paddle_tpu.testing.faults): called with
# (phase, step) at the elastic phase boundaries — phase is "step"
# (before each step) or "restore" (at the start of each reshard-
# restore attempt) — and returns an action dict: {"lose": K} wedges
# the last K device leases (detection then fires as staleness),
# {"stall_s": S} stalls the next watched step for S seconds (inside
# the watchdog clock). Production code never sets it.
_FAULT_HOOK: Optional[Callable[[str, int], dict]] = None


class DeviceLossError(RuntimeError):
    """Devices left the executing mesh. `lost` carries their
    device_keys; raised by the detection layers and consumed by the
    replan loop (a mid-restore loss restarts the degrade with the
    shrunken survivor set)."""

    def __init__(self, msg: str, lost: Optional[List[str]] = None):
        super().__init__(msg)
        self.lost = list(lost or [])


class _Superseded(RuntimeError):
    """An abandoned watchdog dispatch woke up after a replan already
    superseded it; the zombie must not run a step against the orphaned
    trainer (its result would be discarded, but its side effects —
    periodic checkpoint saves at steps the restored run has not
    reached — would corrupt the trajectory)."""


@dataclass
class ElasticConfig:
    """Knobs for ElasticTrainer (detection + replan policy)."""
    heartbeat_timeout: float = 60.0   # lease staleness -> device lost
    step_timeout: float = 0.0         # collective-hang budget per step
    #                                   (0 = no step watchdog)
    warmup_factor: float = 20.0       # budget multiplier for a step
    #                                   whose executable is not built
    #                                   yet (trace_count == 0): the
    #                                   first call after build/replan
    #                                   pays the GSPMD compile, which
    #                                   dwarfs a steady step — without
    #                                   this the watchdog reads every
    #                                   warmup as a hang and the world
    #                                   degrades to nothing
    hang_retries: int = 0             # backoff retries before a hang
    #                                   is declared a loss
    hang_shrink: int = 1              # devices to drop on a hang with
    #                                   no stale lease (the hung chip
    #                                   is unidentifiable from here)
    max_replans: int = 4              # give up (raise) after this many
    restart_on_loss: bool = False     # multi-process mode: instead of
    #                                   replanning in-process, write the
    #                                   degraded world spec and exit 101
    #                                   (request_degraded_restart)


class DeviceLeases:
    """Per-device liveness leases over the executing world. `pulse()`
    refreshes every live lease (the trainer calls it after each
    committed step); `wedge(keys)` marks devices dead — their leases
    stop refreshing AND backdate, so `stale(timeout)` detects them at
    the very next boundary instead of waiting the timeout out in real
    time (the injector simulates a dead chip, the detector still runs
    the real staleness rule). Also the SERVING preemption detector:
    inference/autoscale.EnginePreemptGuard runs the same
    pulse/wedge/stale cycle per engine tick over a tp mesh's
    devices."""

    def __init__(self, devices):
        self._t: Dict[str, float] = {}
        self._wedged: set = set()
        self.reset(devices)

    def reset(self, devices) -> None:
        now = time.monotonic()
        self._t = {k: now for k in device_keys(devices)}
        self._wedged = {k for k in self._wedged if k in self._t}

    def pulse(self) -> None:
        now = time.monotonic()
        for k in self._t:
            if k not in self._wedged:
                self._t[k] = now

    def wedge(self, keys) -> None:
        backdated = time.monotonic() - 1e9
        for k in keys:
            if k in self._t:
                self._wedged.add(k)
                self._t[k] = backdated

    def stale(self, timeout: float) -> List[str]:
        if timeout <= 0:
            return []
        now = time.monotonic()
        return [k for k, t in self._t.items() if now - t > timeout]


def _tree_nbytes(tree) -> int:
    import jax
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "nbytes"))


def request_degraded_restart(spec: dict, reason: str = "device_loss"
                             ) -> None:
    """Multi-process device loss: write the degraded world spec for the
    launcher (heartbeat.write_world_spec) and exit with the elastic
    protocol code — the restarted pod re-forms on the SURVIVING world
    (launch/main.py re-exports the spec; `heartbeat.degraded_world()`
    hands it to the restarted worker) and resumes from LATEST. Flight-
    dumps 'elastic_degraded_exit' first so the dying pod leaves a black
    box naming what it lost."""
    from ..profiler import flight_recorder
    rec = flight_recorder.recorder()
    rec.configure(elastic_world_spec=spec, elastic_reason=reason)
    rec.dump("elastic_degraded_exit")
    path = write_world_spec(dict(spec, reason=reason))
    print(f"[elastic] {reason}: requesting degraded restart "
          f"(world spec {spec}"
          + (f" -> {path}" if path else "; NO launcher world-file "
                                        "contract — old world restart")
          + f"); exiting {ELASTIC_EXIT_CODE}",
          file=sys.stderr, flush=True)
    sys.exit(ELASTIC_EXIT_CODE)


class ElasticTrainer:
    """Owns the world (devices + plan + mesh) around a ResilientTrainer
    and survives device loss by replanning onto the survivors.

    Typical wiring (tools/chaos_drill.py --elastic is the executable
    version):

        plan = plan_train(cfg, n_devices, global_batch)   # or let the
        et = ElasticTrainer(train_step, params, opt,      # ctor plan
                            cfg=cfg, global_batch=B, manager=mgr,
                            config=ElasticConfig(step_timeout=30),
                            resilience=ResilienceConfig(
                                checkpoint_every=1))
        et.maybe_resume()
        run_elastic(et, batch_fn, total_steps)

    `train_step(batch)` returns `(loss, ok)` like the resilient
    trainer, or **None when a replan rewound the run** (the caller
    must re-fetch the batch for the restored step — `run_elastic`
    does). A fresh start (no checkpoint yet) that loses devices
    re-shards the LIVE state onto the degraded mesh instead (only
    sound while the lost devices' shards are still addressable — true
    in the virtual-device simulation and for scale-down events; a
    physically dead chip needs a checkpoint, which is why
    checkpoint_every=1 is the drill default)."""

    def __init__(self, step_fn, params, opt_state, *, cfg, global_batch,
                 manager: Optional[CheckpointManager] = None,
                 plan: Optional[TrainPlan] = None, devices=None,
                 chip=None, config: Optional[ElasticConfig] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 telemetry=None, param_specs=None, **step_kw):
        import jax
        self.config = config or ElasticConfig()
        self._step_fn = step_fn
        self._cfg = cfg
        self._gb = int(global_batch)
        self._chip = chip
        self._param_specs = param_specs
        self._rcfg = resilience or ResilienceConfig()
        self._telemetry = telemetry
        self._step_kw = step_kw
        self.manager = manager
        devices = list(devices if devices is not None else jax.devices())
        # a restarted worker may have been granted a degraded world by
        # the launcher (the exit-101 handshake): honor its device count
        granted = degraded_world()
        if granted and granted.get("n_devices"):
            devices = devices[:int(granted["n_devices"])]
        if plan is None:
            plan = plan_train(cfg, len(devices), self._gb,
                              chip=self._chip, param_specs=param_specs)
        self.plan = plan
        self.world = devices[:plan.plan.n_devices]
        self.mesh = plan.build_mesh(devices=self.world)
        self.leases = DeviceLeases(self.world)
        self.replans = 0
        # the step of the snapshot the last replan reshard-restored
        # from (None before any replan) — the chaos drill's replay
        # anchor: a clean restore of ckpt-<last_restore_step> on the
        # degraded plan must reproduce the post-replan trajectory
        # bit for bit
        self.last_restore_step: Optional[int] = None
        self._gen = 0
        self._pending_stall = 0.0
        self._puller = WatchdogPuller(label="elastic-step")
        self._trainer = self._make_trainer(params, opt_state, step=0)
        from ..profiler import flight_recorder, monitor
        self._mon_replans = monitor.counter("train.elastic.replans")
        self._mon_loss = monitor.counter("train.elastic.device_loss")
        self._mon_hang = monitor.counter("train.elastic.collective_hang")
        self._mon_world = monitor.gauge("train.elastic.world_size")
        self._mon_replan_ms = monitor.gauge("train.elastic.replan_ms")
        self._mon_reshard = monitor.gauge("train.elastic.reshard_bytes")
        self._mon_world.set(len(self.world))
        self._flight = flight_recorder.recorder()

    def _make_trainer(self, params, opt_state, step, mesh=None,
                      plan=None) -> ResilientTrainer:
        return ResilientTrainer(
            self._step_fn, params, opt_state, cfg=self._cfg,
            manager=self.manager, config=self._rcfg, step=step,
            mesh=mesh if mesh is not None else self.mesh,
            plan=plan if plan is not None else self.plan,
            telemetry=self._telemetry, **self._step_kw)

    # ------------------------------------------------------- delegation
    @property
    def step(self) -> int:
        return self._trainer.step

    @property
    def params(self):
        return self._trainer.params

    @property
    def opt_state(self):
        return self._trainer.opt_state

    @property
    def trace_count(self) -> int:
        """The executing step's compiled-executable count (resets to 0
        at a replan; 1 after the post-replan warmup = the
        zero-recompiles-after-replan gate)."""
        return getattr(self._trainer._guarded, "trace_count", -1)

    def maybe_resume(self) -> bool:
        return self._trainer.maybe_resume()

    def save(self):
        return self._trainer.save()

    # -------------------------------------------------------- detection
    def _consult_faults(self, phase: str) -> dict:
        if _FAULT_HOOK is None:
            return {}
        return _FAULT_HOOK(phase, self.step) or {}

    def _apply_actions(self, act: dict, candidates) -> None:
        """Apply an injected action dict: lease wedging here (so
        detection = staleness, always); stalls park until the next
        watched step."""
        k = int(act.get("lose", 0))
        if k > 0:
            keys = device_keys(candidates)[-k:]
            self.leases.wedge(keys)
        if act.get("stall_s"):
            self._pending_stall = float(act["stall_s"])

    # ------------------------------------------------------------- step
    def train_step(self, batch):
        """One guarded step on `batch`, or None when a replan rewound
        the run (the restored step counter may be earlier than this
        batch's index — the caller re-fetches; see run_elastic)."""
        c = self.config
        self._apply_actions(self._consult_faults("step"), self.world)
        lost = self.leases.stale(c.heartbeat_timeout)
        if lost and len(lost) >= len(self.world):
            # EVERY lease stale at once is indistinguishable from the
            # monitoring clock having stalled (host suspend, a
            # minutes-long compile) — re-pulse and re-check:
            # organically stale leases recover, wedged (truly dead)
            # ones stay stale and the replan proceeds (to a
            # NoFeasiblePlanError naming the constraint if the whole
            # world is really gone)
            self.leases.pulse()
            lost = self.leases.stale(c.heartbeat_timeout)
        if lost:
            self._mon_loss.add()
            self._replan(lost, reason="heartbeat_stale")
            return None
        stall, self._pending_stall = self._pending_stall, 0.0
        if c.step_timeout <= 0:
            if stall:
                time.sleep(stall)
            out = self._trainer.train_step(batch)
            self.leases.pulse()
            return out
        gen = self._gen
        trainer = self._trainer
        budget = c.step_timeout
        if self.trace_count == 0:          # warmup: the call compiles
            budget *= max(c.warmup_factor, 1.0)

        def watched():
            if stall:
                time.sleep(stall)
            if gen != self._gen:
                raise _Superseded("replan superseded this dispatch")
            return trainer.train_step(batch)

        try:
            loss, ok = self._puller.pull(watched, budget,
                                         retries=c.hang_retries)
        except StepHungError:
            self._mon_hang.add()
            lost = self.leases.stale(c.heartbeat_timeout)
            if not lost:
                # the hung chip is unidentifiable from a wedged
                # collective; shrink the world from the tail
                lost = device_keys(self.world)[-max(c.hang_shrink, 1):]
                self.leases.wedge(lost)
            self._replan(lost, reason="collective_hang")
            return None
        self.leases.pulse()
        return float(loss), bool(ok)

    # ----------------------------------------------------------- replan
    def _replan(self, lost: List[str], reason: str) -> None:
        """Degrade onto the survivors and reshard-restore. A further
        device loss injected/detected DURING the restore shrinks the
        survivor set and retries, up to config.max_replans."""
        c = self.config
        if self.replans >= max(c.max_replans, 1):
            raise RuntimeError(
                f"elastic: {self.replans} replans exhausted "
                f"(max_replans={c.max_replans}) and devices are still "
                f"being lost — giving up")
        t0 = time.perf_counter()
        self._gen += 1          # supersede any abandoned hung dispatch
        print(f"[elastic] device loss ({reason}): lost {sorted(lost)} "
              f"of {len(self.world)}; replanning", file=sys.stderr,
              flush=True)
        self._flight.configure(elastic_reason=reason,
                               elastic_lost=sorted(lost))
        self._flight.dump("elastic_device_loss")
        survivors = [d for d in self.world if str(d) not in set(lost)]
        if c.restart_on_loss:
            new_plan = degrade_plan(self._cfg, self.plan,
                                    len(survivors), self._gb,
                                    chip=self._chip,
                                    param_specs=self._param_specs)
            request_degraded_restart(
                {"n_devices": new_plan.plan.n_devices,
                 "cpu_devices": new_plan.plan.n_devices,
                 "axes": new_plan.axes}, reason=reason)
        for attempt in range(max(c.max_replans, 1)):
            new_plan = degrade_plan(self._cfg, self.plan,
                                    len(survivors), self._gb,
                                    chip=self._chip,
                                    param_specs=self._param_specs)
            new_world = survivors[:new_plan.plan.n_devices]
            new_mesh = build_mesh(new_plan.axes, devices=new_world)
            try:
                self._restore_onto(new_mesh, new_plan, reason)
            except DeviceLossError as e:
                # killed mid-restore: shrink and re-degrade
                print(f"[elastic] device loss DURING restore "
                      f"(attempt {attempt + 1}): lost {sorted(e.lost)}; "
                      f"re-degrading", file=sys.stderr, flush=True)
                self._flight.dump("elastic_device_loss")
                survivors = [d for d in survivors
                             if str(d) not in set(e.lost)]
                continue
            break
        else:
            raise RuntimeError(
                f"elastic: {c.max_replans} replans exhausted and "
                f"devices are still being lost — giving up")
        self.plan, self.world, self.mesh = new_plan, new_world, new_mesh
        self.leases.reset(self.world)
        self.replans += 1
        self._mon_replans.add()
        self._mon_world.set(len(self.world))
        ms = (time.perf_counter() - t0) * 1e3
        self._mon_replan_ms.set(round(ms, 3))
        self._flight.configure(elastic_plan=new_plan.name,
                               elastic_world=len(self.world))
        self._flight.note(event="elastic_replan", plan=new_plan.name,
                          step=self.step, replan_ms=round(ms, 3))
        print(f"[elastic] replanned onto {new_plan.name} "
              f"({len(self.world)} devices) at step {self.step} "
              f"in {ms:.0f} ms", file=sys.stderr, flush=True)

    def _restore_onto(self, new_mesh, new_plan: TrainPlan,
                      reason: str) -> None:
        """Reshard-restore the newest intact snapshot onto the degraded
        mesh and re-target the step. The restore phase consults the
        fault seam first — a `device_loss` queued behind the one that
        triggered this replan fires HERE, which is exactly the
        killed-mid-restore drill phase."""
        act = self._consult_faults("restore")
        if act.get("lose"):
            k = int(act["lose"])
            lost = device_keys(new_mesh)[-k:]
            self.leases.wedge(lost)
            raise DeviceLossError(
                f"{k} device(s) lost during restore", lost=lost)
        specs = plan_state_specs(new_plan)
        state = step = None
        if self.manager is not None:
            state, step = self.manager.restore(mesh=new_mesh,
                                               specs=specs)
        if state is not None:
            self._mon_reshard.set(_tree_nbytes(state))
            params = state["params"]
            opt = state.get("opt_state", self._trainer.opt_state)
            saved = state.get("step")
            step = int(saved) if saved is not None else int(step or 0)
            self.last_restore_step = step
        else:
            # no snapshot yet: re-shard the live state (the scale-down /
            # simulation case — see the class docstring caveat). The
            # step pins commit the host/old-mesh arrays onto the new
            # layout at the first call.
            params, opt = self._trainer.params, self._trainer.opt_state
            step = self._trainer.step
            self._mon_reshard.set(_tree_nbytes(params)
                                  + _tree_nbytes(opt))
        if reason == "collective_hang":
            # an abandoned watchdog thread may still hold the OLD
            # trainer object; a fresh trainer guarantees the zombie
            # only ever mutates an orphan — and the orphan must also
            # lose its handle on the SHARED CheckpointManager, or a
            # zombie step completing late would save a checkpoint from
            # the abandoned timeline into the restored run's root
            # (newest-wins restore would then resume a divergent
            # trajectory)
            orphan = self._trainer
            self._trainer = self._make_trainer(params, opt, step=step,
                                               mesh=new_mesh,
                                               plan=new_plan)
            orphan.manager = None
        else:
            # clean boundary detection: retarget the SAME step object
            # (facade rebuild — fresh pins, one new executable, no
            # cache-key bifurcation)
            self._trainer.rebuild_plan(new_mesh, new_plan,
                                       params=params, opt_state=opt,
                                       step=step)


def run_elastic(trainer: ElasticTrainer, batch_fn, total_steps: int,
                on_step=None) -> ElasticTrainer:
    """Drive `trainer` to `total_steps` with deterministic batches
    keyed by step index (the run_resilient contract — replans rewind
    the step counter and the SAME batches re-run on the degraded plan,
    which is what makes the resumed trajectory comparable bit-for-bit
    against a clean restore). A train_step that returns None performed
    a replan instead of a step: loop around and re-fetch at the
    restored step."""
    while trainer.step < total_steps:
        step = trainer.step
        out = trainer.train_step(batch_fn(step))
        if out is None:
            continue
        loss, ok = out
        if on_step is not None:
            on_step(step, loss, ok)
    return trainer
