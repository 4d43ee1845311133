"""Chaos drill: run training under injected faults, assert recovery.

The executable acceptance test for the fault-tolerance runtime
(docs/fault_tolerance.md). The reference stack has nothing like it
(SURVEY.md: "no systematic fault-injection harness") — here every
scenario spawns the REAL elastic-lite launcher on the 8-virtual-device
CPU mesh, injects a declared fault (paddle_tpu.testing.faults), and
asserts the restarted/resumed run's loss trajectory matches an
uninterrupted baseline step for step.

Scenarios:
  kill@S          worker hard-killed before step S; restart resumes LATEST
  crash_shard@S:K worker dies mid-save_sharded; torn staging dir ignored
  nan@S:2         two poisoned steps -> skip, skip, rollback, clean re-run
  elastic_exit@S  worker exits 101; launcher's elastic budget restarts it
  hb_stale@S      heartbeat wedge; launcher hang watchdog kills + restarts
  corrupt         newest snapshot truncated/bit-flipped between two legs;
                  resume must fall back to the previous intact snapshot

Elastic scenario group (--elastic; ISSUE 14): an 8-virtual-device
dp2×fsdp2×tp2 GPT train run loses a device at every phase — mid-step,
mid-async-save (a background writer in flight at the loss boundary),
mid-restore (a second loss DURING the replan's reshard-restore) — plus
a collective hang, a within-budget straggler (must NOT replan), and an
exit-101 restart that carries a DEGRADED world spec through the
launcher. Each scenario asserts: resumed on a degraded plan, the
post-restore loss trajectory BIT-identical to a clean restore of the
same checkpoint on the same degraded plan (the worker replays it
in-process), zero recompiles after the replan warmup (trace_count), a
parseable flight dump AND telemetry JSONL with the train.elastic.*
counters moved.

Usage:
  python tools/chaos_drill.py --quick          # representative phases
  python tools/chaos_drill.py --full           # kill/crash at EVERY step
  python tools/chaos_drill.py --elastic        # device-loss scenarios
  python tools/chaos_drill.py --serving        # serving chaos drill
                                               # (chaos_serving --quick)
  python tools/chaos_drill.py --bench          # save/verify overhead JSON
  python tools/chaos_drill.py --gate [T1LOG]   # pre-commit robustness
                                               # gate: quick+elastic+
                                               # serving drills green
                                               # AND diff_failures clean
(The launcher re-enters this file with --worker; not for direct use.)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

STEPS_ENV = "PADDLE_TPU_DRILL_STEPS"
CKPT_ENV = "PADDLE_TPU_DRILL_CKPT"
OUT_ENV = "PADDLE_TPU_DRILL_OUT"
TELE_ENV = "PADDLE_TPU_DRILL_TELEMETRY"
MODE_ENV = "PADDLE_TPU_DRILL_MODE"           # "" | "elastic"
ASYNC_ENV = "PADDLE_TPU_DRILL_ASYNC"         # "1" -> async checkpoints
EXIT101_ENV = "PADDLE_TPU_DRILL_EXIT101"     # "1" -> restart_on_loss
STEP_TO_ENV = "PADDLE_TPU_DRILL_STEP_TIMEOUT"  # watchdog budget (s)
SUMMARY_ENV = "PADDLE_TPU_DRILL_SUMMARY"     # elastic summary JSON path

DIM_IN, DIM_H = 16, 32
BATCH = 8


# =========================================================== worker side
def _batch(step: int):
    import numpy as np
    rng = np.random.RandomState(10_000 + step)
    x = rng.randn(BATCH, DIM_IN).astype(np.float32)
    y = rng.randn(BATCH).astype(np.float32)
    return x, y


def worker_main() -> int:
    from paddle_tpu.testing import faults
    faults.install()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.mesh import build_mesh, use_mesh, \
        shard_value, P
    from paddle_tpu.parallel.checkpoint import CheckpointManager
    from paddle_tpu.parallel.resilience import (ResilientTrainer,
                                                ResilienceConfig,
                                                run_resilient)

    steps = int(os.environ[STEPS_ENV])
    mgr = CheckpointManager(os.environ[CKPT_ENV], max_to_keep=3)
    out = open(os.environ[OUT_ENV], "a")
    telemetry = None
    if os.environ.get(TELE_ENV):
        from paddle_tpu.profiler.telemetry import TelemetryPipeline
        from paddle_tpu.parallel.resilience import RESILIENT_FIELDS
        telemetry = TelemetryPipeline(os.environ[TELE_ENV], every=4,
                                      fields=RESILIENT_FIELDS,
                                      meta={"samples_per_step": BATCH})

    def init_params(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (DIM_IN, DIM_H)) * 0.3,
                "w2": jax.random.normal(k2, (DIM_H,)) * 0.3}

    def train_step(params, opt_state, batch, lr=0.05, mu=0.9):
        x, y = batch

        def loss_fn(p):
            h = jnp.maximum(x @ p["w1"], 0.0)
            return jnp.mean((h @ p["w2"] - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_opt = jax.tree_util.tree_map(
            lambda m, g: mu * m + g, opt_state, grads)
        new_params = jax.tree_util.tree_map(
            lambda p, m: p - lr * m, params, new_opt)
        return loss, new_params, new_opt

    mesh = build_mesh({"dp": 2, "mp": 4})
    specs = {"w1": P(None, "mp"), "w2": P("mp")}
    with use_mesh(mesh):
        params = {k: shard_value(v, specs[k], mesh)
                  for k, v in init_params(jax.random.PRNGKey(0)).items()}
        opt_state = jax.tree_util.tree_map(jnp.zeros_like, params)
        tr = ResilientTrainer(
            train_step, params, opt_state, manager=mgr,
            config=ResilienceConfig(checkpoint_every=1, rollback_after=2,
                                    max_rollbacks=5),
            telemetry=telemetry)
        if tr.maybe_resume():
            print(f"[drill-worker] resumed at step {tr.step}",
                  file=sys.stderr, flush=True)

        def record(step, loss, ok):
            out.write(json.dumps(
                {"step": step, "loss": loss, "ok": ok}) + "\n")
            out.flush()
            os.fsync(out.fileno())

        def sharded_batch(step):
            x, y = _batch(step)
            return (shard_value(jnp.asarray(x), P("dp", None), mesh),
                    shard_value(jnp.asarray(y), P("dp"), mesh))

        run_resilient(tr, sharded_batch, steps, on_step=record)
    if telemetry is not None:
        telemetry.close(tr._tstate)
    print(f"[drill-worker] done: {tr.step} steps, {tr.skipped} skipped, "
          f"{tr.rollbacks} rollbacks", file=sys.stderr, flush=True)
    return 0


# ==================================================== elastic worker side
def elastic_worker_main() -> int:
    """The ISSUE-14 elastic drill worker: a tiny dp2×fsdp2×tp2 GPT
    train run under the ElasticTrainer. After the run it REPLAYS the
    post-replan trajectory from the restored checkpoint on the same
    degraded plan (a fresh step, a clean restore) and writes a summary
    JSON the driver asserts bit-identity/trace-count/world from."""
    from paddle_tpu.testing import faults
    faults.install()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.facade import make_train_step
    from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                       init_opt_state, train_step)
    from paddle_tpu.parallel.checkpoint import (CheckpointManager,
                                                load_sharded)
    from paddle_tpu.parallel.elastic import (ElasticConfig,
                                             ElasticTrainer,
                                             run_elastic)
    from paddle_tpu.parallel.planner import plan_train
    from paddle_tpu.parallel.resilience import (RESILIENT_FIELDS,
                                                ResilienceConfig)
    from paddle_tpu.distributed.launch.heartbeat import degraded_world

    steps = int(os.environ[STEPS_ENV])
    mgr = CheckpointManager(os.environ[CKPT_ENV], max_to_keep=0)
    out = open(os.environ[OUT_ENV], "a")
    telemetry = None
    if os.environ.get(TELE_ENV):
        from paddle_tpu.profiler.telemetry import TelemetryPipeline
        telemetry = TelemetryPipeline(os.environ[TELE_ENV], every=2,
                                      fields=RESILIENT_FIELDS,
                                      meta={"samples_per_step": BATCH})

    B, S = 8, 8
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=16, dtype=jnp.float32,
                    remat=False, sequence_parallel=False)

    def batch(step):
        return np.random.RandomState(4242 + step).randint(
            0, 128, (B, S + 1)).astype(np.int32)

    # a restarted worker granted a degraded world plans onto it
    # EXPLICITLY (the spec's axes), so the resumed plan is the one the
    # dying worker degraded to — not whatever the search would pick
    granted = degraded_world()
    if granted and granted.get("axes"):
        ax = granted["axes"]
        plan = plan_train(cfg, int(granted["n_devices"]), B,
                          dp=ax.get("dp", 1), fsdp=ax.get("fsdp", 1),
                          tp=ax.get("tp", 1))
        print(f"[elastic-worker] degraded world granted: {granted}",
              file=sys.stderr, flush=True)
    else:
        plan = plan_train(cfg, 8, B, dp=2, fsdp=2, tp=2)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    ecfg = ElasticConfig(
        heartbeat_timeout=60.0,
        step_timeout=float(os.environ.get(STEP_TO_ENV, "0") or 0),
        hang_retries=0,
        restart_on_loss=os.environ.get(EXIT101_ENV) == "1")
    rcfg = ResilienceConfig(
        checkpoint_every=1,
        async_checkpoint=os.environ.get(ASYNC_ENV) == "1")
    et = ElasticTrainer(train_step, params, opt, cfg=cfg,
                        global_batch=B, manager=mgr, plan=plan,
                        config=ecfg, resilience=rcfg,
                        telemetry=telemetry, lr=1e-3)
    resumed_at = None
    if et.maybe_resume():
        resumed_at = et.step
        print(f"[elastic-worker] resumed at step {et.step}",
              file=sys.stderr, flush=True)

    losses = {}

    def record(step, loss, ok):
        losses[step] = loss
        out.write(json.dumps(
            {"step": step, "loss": loss, "ok": ok}) + "\n")
        out.flush()
        os.fsync(out.fileno())

    run_elastic(et, batch, steps, on_step=record)
    mgr.wait()                       # flush any in-flight async save
    if telemetry is not None:
        telemetry.close(et._trainer._tstate)

    # ---- post-run self-check: clean restore on the degraded plan ----
    # in-process replan records last_restore_step; an exit-101 restart
    # resumed at `resumed_at` on the granted world — same anchor
    anchor = et.last_restore_step if et.last_restore_step is not None \
        else resumed_at
    summary = {
        "replans": et.replans,
        "world": len(et.world),
        "axes": et.plan.axes,
        "trace_count": et.trace_count,
        "restored_step": anchor,
        "degraded": len(et.world) < 8 or bool(granted),
        "steps_recorded": sorted(losses),
    }
    if anchor is not None:
        from paddle_tpu.parallel.resilience import plan_state_specs
        mesh_d = et.plan.build_mesh(devices=et.world)
        specs = plan_state_specs(et.plan)
        state = load_sharded(
            os.path.join(os.environ[CKPT_ENV], f"ckpt-{anchor}"),
            mesh=mesh_d, specs=specs)
        step2 = make_train_step(train_step, cfg=cfg, lr=1e-3,
                                mesh=mesh_d, plan=et.plan)
        p2, o2 = state["params"], state["opt_state"]
        mism = []
        for s in range(int(anchor), steps):
            loss, p2, o2 = step2(p2, o2, batch(s))
            if float(loss) != losses.get(s):
                mism.append((s, float(loss), losses.get(s)))
        summary["replay_identical"] = not mism
        summary["replay_mismatches"] = mism[:5]
    with open(os.environ[SUMMARY_ENV], "w") as f:
        json.dump(summary, f)
    print(f"[elastic-worker] done: {et.step} steps, "
          f"{et.replans} replans, world {len(et.world)}, "
          f"axes {et.plan.axes}", file=sys.stderr, flush=True)
    return 0


# =========================================================== driver side
def _check_flight(scenario_dir: str, min_steps: int = 1):
    """A killed/restarted worker must leave at least one parseable
    flight-recorder dump carrying step records and a monitor snapshot
    (the PR-3 acceptance criterion). Returns an error string or None."""
    fdir = os.path.join(scenario_dir, "flight")
    dumps = sorted(f for f in (os.listdir(fdir) if os.path.isdir(fdir)
                               else []) if f.endswith(".json"))
    if not dumps:
        return f"no flight-recorder dump under {fdir}"
    for name in dumps:
        try:
            with open(os.path.join(fdir, name)) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            return f"flight dump {name} unparseable: {e}"
        if doc.get("kind") != "flight_recorder":
            return f"flight dump {name}: wrong kind {doc.get('kind')!r}"
        if "monitor" not in doc:
            return f"flight dump {name}: no monitor snapshot"
    best = 0
    for name in dumps:
        with open(os.path.join(fdir, name)) as f:
            best = max(best, len(json.load(f).get("steps") or []))
    if best < min_steps:
        return (f"flight dumps under {fdir} carry {best} step records "
                f"(< {min_steps})")
    return None


def _check_telemetry(scenario_dir: str):
    """The scenario's telemetry JSONL must summarize cleanly and carry
    step records (torn tails from kills are tolerated by the parser)."""
    path = os.path.join(scenario_dir, "telemetry.jsonl")
    if not os.path.exists(path):
        return f"no telemetry JSONL at {path}"
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from telemetry_report import summarize
    try:
        doc = summarize(path)
    except Exception as e:
        return f"telemetry summary failed for {path}: {e}"
    if doc.get("steps_recorded", 0) < 1:
        return f"telemetry JSONL {path} has no step records"
    return None


def _trajectory(out_path: str):
    """results.jsonl -> {step: last recorded loss} (re-runs after a
    restart/rollback overwrite earlier occurrences)."""
    traj = {}
    if not os.path.exists(out_path):
        return traj
    with open(out_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            traj[rec["step"]] = rec["loss"]
    return traj


def _launch(scenario_dir: str, steps: int, fault_spec: str,
            hang_watch: bool, max_restart: int = 10,
            timeout: int = 600, extra_env=None):
    ckpt = os.path.join(scenario_dir, "ckpt")
    outp = os.path.join(scenario_dir, "out.jsonl")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)   # workers pin CPU via the boot shim
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env[STEPS_ENV] = str(steps)
    env[CKPT_ENV] = ckpt
    env[OUT_ENV] = outp
    env[SUMMARY_ENV] = os.path.join(scenario_dir, "summary.json")
    if extra_env:
        env.update(extra_env)
    # observability riders: every worker leaves a crash flight recorder
    # black box + a batched-telemetry JSONL the driver parses back
    env["PADDLE_TPU_FLIGHT_DIR"] = os.path.join(scenario_dir, "flight")
    env[TELE_ENV] = os.path.join(scenario_dir, "telemetry.jsonl")
    if fault_spec:
        env["PADDLE_TPU_FAULTS"] = fault_spec
        env["PADDLE_TPU_FAULTS_ONCE_DIR"] = os.path.join(
            scenario_dir, "once")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--devices", "cpu", "--cpus_per_proc", "8",
           "--max_restart", str(max_restart),
           "--max_elastic_restart", "8"]
    if hang_watch:
        # generous: worker boot (paddle_tpu + jax import) takes >5s on a
        # loaded 1-core host and a false hang burns the restart budget
        cmd += ["--hang_timeout", "15", "--heartbeat_interval", "0.5"]
    cmd += [os.path.join(REPO, "tools", "chaos_drill.py"), "--worker"]
    res = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT)
    return res, _trajectory(outp)


def _compare(name: str, base: dict, got: dict, steps: int,
             atol: float = 1e-10):
    missing = [s for s in range(steps) if s not in got]
    if missing:
        return f"{name}: steps never recorded: {missing[:10]}"
    for s in range(steps):
        d = abs(base[s] - got[s])
        if not (d <= atol):
            return (f"{name}: loss diverged at step {s}: baseline "
                    f"{base[s]!r} vs {got[s]!r} (|d|={d:g})")
    return None


def run_drill(steps: int, full: bool, keep_logs: bool = False) -> int:
    root = tempfile.mkdtemp(prefix="chaos_drill_")
    failures = []
    t0 = time.time()

    def scenario(name: str, spec: str, hang: bool = False):
        sdir = os.path.join(root, name.replace("@", "_").replace(":", "_"))
        os.makedirs(sdir, exist_ok=True)
        t = time.time()
        res, traj = _launch(sdir, steps, spec, hang)
        dt = time.time() - t
        err = None
        if res.returncode != 0:
            err = f"{name}: launcher rc={res.returncode}"
        else:
            err = _compare(name, baseline, traj, steps)
        if err is None and spec.startswith(("kill@", "crash_shard@")):
            # the killed leg must have left a readable black box
            err = _check_flight(sdir) or _check_telemetry(sdir)
            if err:
                err = f"{name}: {err}"
        tag = "FAIL" if err else "ok"
        print(f"[drill] {name:<24} {tag}  ({dt:.1f}s)", flush=True)
        if err:
            failures.append(err)
            tail = res.stdout.decode(errors="replace")[-2000:]
            print(tail, flush=True)
        elif keep_logs:
            print(res.stdout.decode(errors="replace")[-800:], flush=True)
        return res, traj

    # baseline: uninterrupted run
    bdir = os.path.join(root, "baseline")
    os.makedirs(bdir)
    res, baseline = _launch(bdir, steps, "", hang_watch=False)
    if res.returncode != 0 or len(baseline) != steps:
        print(res.stdout.decode(errors="replace")[-3000:])
        print(f"[drill] baseline failed (rc={res.returncode}, "
              f"{len(baseline)}/{steps} steps)")
        return 2
    print(f"[drill] baseline: {steps} steps ok "
          f"({time.time() - t0:.1f}s)", flush=True)

    kill_phases = range(steps) if full else \
        sorted({0, 1, steps // 2, steps - 1})
    crash_phases = range(steps) if full else sorted({1, steps // 2})
    for s in kill_phases:
        scenario(f"kill@{s}", f"kill@{s}")
    for s in crash_phases:
        # die after 3 of the 9 shard files of a snapshot (w1:4, w2:4,
        # scalars in manifest) — squarely mid-save
        scenario(f"crash_shard@{s}", f"crash_shard@{s}:3")
    scenario(f"nan@{max(1, steps // 3)}",
             f"nan@{max(1, steps // 3)}:2")
    scenario(f"elastic_exit@{max(1, steps // 2)}",
             f"elastic_exit@{max(1, steps // 2)}")
    scenario(f"hb_stale@{max(1, steps // 2)}",
             f"hb_stale@{max(1, steps // 2)}", hang=True)

    # corrupt-newest: two legs with driver-side file damage in between —
    # resume must CRC-reject the newest snapshot and fall back
    for mode in ("truncate", "bitflip"):
        name = f"corrupt_{mode}"
        sdir = os.path.join(root, name)
        os.makedirs(sdir, exist_ok=True)
        leg1 = steps // 2
        res, _ = _launch(sdir, leg1, "", hang_watch=False)
        if res.returncode != 0:
            failures.append(f"{name}: leg1 rc={res.returncode}")
            continue
        ckpt = os.path.join(sdir, "ckpt")
        with open(os.path.join(ckpt, "LATEST")) as f:
            newest = os.path.join(ckpt, f.read().strip())
        # the corruptors pull in paddle_tpu (and transitively jax) into
        # the DRIVER process, which must not claim a chip its workers
        # need — pin CPU first
        from paddle_tpu.device import pin_cpu
        pin_cpu(1)
        from paddle_tpu.testing import faults as fmod
        if mode == "truncate":
            fmod.truncate_shard(newest, index=0)
        else:
            fmod.bitflip_shard(newest, index=0)
        res, traj = _launch(sdir, steps, "", hang_watch=False)
        err = None
        if res.returncode != 0:
            err = f"{name}: leg2 rc={res.returncode}"
        else:
            err = _compare(name, baseline, traj, steps)
        print(f"[drill] {name:<24} {'FAIL' if err else 'ok'}", flush=True)
        if err:
            failures.append(err)
            print(res.stdout.decode(errors="replace")[-2000:], flush=True)

    dt = time.time() - t0
    if failures:
        print(f"[drill] {len(failures)} FAILURES in {dt:.1f}s:")
        for f in failures:
            print("  -", f)
        return 1
    print(f"[drill] ALL SCENARIOS PASSED ({steps}-step run, "
          f"full={full}) in {dt:.1f}s")
    return 0


# ====================================================== elastic scenarios
def run_elastic_drill(steps: int = 10, keep_logs: bool = False) -> int:
    """Device-loss-at-every-phase drill (ISSUE 14 acceptance): each
    scenario spawns the REAL launcher running the elastic GPT worker
    on the 8-virtual-device CPU mesh; the worker replays the
    post-replan trajectory from the restored checkpoint in-process and
    the driver asserts the summary + flight dump + telemetry."""
    import tempfile
    root = tempfile.mkdtemp(prefix="chaos_elastic_")
    failures = []
    t0 = time.time()

    def tele_doc(sdir):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from telemetry_report import summarize
        return summarize(os.path.join(sdir, "telemetry.jsonl"))

    def scenario(name, spec, env=None, expect_replan=True,
                 require_elastic_block=True):
        sdir = os.path.join(root, name)
        os.makedirs(sdir, exist_ok=True)
        t = time.time()
        env = dict(env or {}, **{MODE_ENV: "elastic"})
        res, traj = _launch(sdir, steps, spec, hang_watch=False,
                            extra_env=env)
        dt = time.time() - t
        err = None
        summary = {}
        spath = os.path.join(sdir, "summary.json")
        if res.returncode != 0:
            err = f"launcher rc={res.returncode}"
        elif not os.path.exists(spath):
            err = "no summary.json from the worker"
        else:
            with open(spath) as f:
                summary = json.load(f)
        if err is None and expect_replan:
            if not summary.get("degraded"):
                err = f"run never degraded: {summary}"
            elif summary.get("world", 8) >= 8:
                err = f"world not reduced: {summary}"
            elif summary.get("trace_count") != 1:
                # zero recompiles after the replan warmup
                err = f"trace_count {summary.get('trace_count')} != 1"
            elif summary.get("restored_step") is None:
                err = "no reshard-restore anchor recorded"
            elif not summary.get("replay_identical"):
                err = (f"post-restore trajectory NOT bit-identical to "
                       f"a clean restore on the degraded plan: "
                       f"{summary.get('replay_mismatches')}")
        if err is None and not expect_replan:
            if summary.get("replans", 0) != 0 \
                    or summary.get("world") != 8:
                err = f"unexpected replan: {summary}"
        if err is None:
            # completeness from the trajectory file, not the summary —
            # an exit-101 scenario's pre-restart steps were recorded by
            # the FIRST process (out.jsonl spans restarts; the summary
            # is written by the last one)
            missing = [s for s in range(steps) if s not in traj]
            if missing:
                err = f"steps never recorded: {missing[:10]}"
        if err is None and expect_replan:
            err = _check_flight(sdir)
        if err is None:
            err = _check_telemetry(sdir)
        if err is None and require_elastic_block:
            doc = tele_doc(sdir)
            blk = doc.get("elastic") or {}
            if blk.get("replans", 0) < 1:
                err = (f"telemetry elastic block missing/empty: "
                       f"{blk} (train.elastic.* not surfaced)")
        tag = "FAIL" if err else "ok"
        print(f"[drill] elastic_{name:<18} {tag}  ({dt:.1f}s)",
              flush=True)
        if err:
            failures.append(f"elastic_{name}: {err}")
            print(res.stdout.decode(errors="replace")[-2500:],
                  flush=True)
        elif keep_logs:
            print(res.stdout.decode(errors="replace")[-800:],
                  flush=True)
        return traj, summary

    loss_at = steps // 2
    # baseline: the same worker, uninterrupted (for the straggler's
    # bit-identity check — replan scenarios compare against their OWN
    # clean-restore replay, not the 8-device baseline, because a
    # degraded plan legally reorders reductions)
    bdir = os.path.join(root, "baseline")
    os.makedirs(bdir)
    res, baseline = _launch(bdir, steps, "", hang_watch=False,
                            extra_env={MODE_ENV: "elastic"})
    if res.returncode != 0 or len(baseline) != steps:
        print(res.stdout.decode(errors="replace")[-3000:])
        print(f"[drill] elastic baseline failed (rc={res.returncode})")
        return 2
    print(f"[drill] elastic baseline: {steps} steps ok "
          f"({time.time() - t0:.1f}s)", flush=True)

    # the three kill phases
    scenario("midstep", f"device_loss@{loss_at}:1")
    scenario("midsave", f"device_loss@{loss_at}:1",
             env={ASYNC_ENV: "1"})
    scenario("midrestore",
             f"device_loss@{loss_at}:1,device_loss@{loss_at}:1")
    # collective hang -> watchdog -> replan
    scenario("hang", f"collective_hang@{loss_at}:30000",
             env={STEP_TO_ENV: "3"})
    # straggler within budget: NO replan, trajectory == baseline
    traj, _ = scenario("straggler", f"straggler@{loss_at}:500",
                       env={STEP_TO_ENV: "10"}, expect_replan=False,
                       require_elastic_block=False)
    err = _compare("elastic_straggler", baseline, traj, steps, atol=0.0)
    if err:
        failures.append(err)
    # exit-101 with a degraded world spec through the REAL launcher
    scenario("exit101", f"device_loss@{loss_at}:1",
             env={EXIT101_ENV: "1"}, require_elastic_block=False)

    dt = time.time() - t0
    if failures:
        print(f"[drill] {len(failures)} ELASTIC FAILURES in {dt:.1f}s:")
        for f in failures:
            print("  -", f)
        return 1
    print(f"[drill] ALL ELASTIC SCENARIOS PASSED ({steps}-step run) "
          f"in {dt:.1f}s")
    return 0


# =============================================================== gate mode
def run_serving_drill(keep_logs: bool = False) -> int:
    """The serving leg: tools/chaos_serving.py --quick in a fresh
    subprocess (it pins its own CPU device count before jax init, so
    it cannot share this process's backend)."""
    cmd = [sys.executable, os.path.join(REPO, "tools",
                                        "chaos_serving.py"), "--quick"]
    if keep_logs:
        cmd.append("--keep")
    t0 = time.time()
    res = subprocess.run(cmd, cwd=REPO, timeout=2400)
    tag = "ok" if res.returncode == 0 else "FAIL"
    print(f"[drill] serving_quick          {tag}  "
          f"({time.time() - t0:.1f}s)", flush=True)
    return res.returncode


def gate_main(steps: int, elastic_steps: int, tier1_log: str,
              keep_logs: bool = False) -> int:
    """The pre-commit robustness gate (CLAUDE.md testing section): ONE
    exit code = quick drill green AND elastic drill green AND the
    serving chaos drill green (chaos_serving.py --quick — autoscale/
    live-migration/device-loss scenarios included) AND the
    HLO-audit regression gate green (tools/audit_gate.py vs
    perf/audit_baseline.json — no new resharding) AND the
    compiled-memory gate green (tools/mem_gate.py vs
    perf/mem_baseline.json — no peak-HBM growth) AND
    tools/diff_failures.py clean against the stored tier-1 baseline
    (skipped with a note when no tier-1 log exists yet)."""
    rc = run_drill(steps, full=False, keep_logs=keep_logs)
    if rc != 0:
        print("[gate] quick drill FAILED", flush=True)
        return rc
    rc = run_elastic_drill(elastic_steps, keep_logs=keep_logs)
    if rc != 0:
        print("[gate] elastic drill FAILED", flush=True)
        return rc
    rc = run_serving_drill(keep_logs=keep_logs)
    if rc != 0:
        print("[gate] serving drill FAILED", flush=True)
        return rc
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "audit_gate.py")],
        cwd=REPO)
    if res.returncode != 0:
        print("[gate] HLO audit gate FAILED (new resharding findings "
              "vs perf/audit_baseline.json)", flush=True)
        return res.returncode
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mem_gate.py")],
        cwd=REPO)
    if res.returncode != 0:
        print("[gate] compiled-memory gate FAILED (peak HBM grew vs "
              "perf/mem_baseline.json)", flush=True)
        return res.returncode
    if tier1_log and os.path.exists(tier1_log):
        res = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "diff_failures.py"),
             tier1_log], cwd=REPO)
        if res.returncode != 0:
            print(f"[gate] diff_failures found NEW failures in "
                  f"{tier1_log}", flush=True)
            return res.returncode
    else:
        print(f"[gate] no tier-1 log at {tier1_log or '<unset>'}; "
              f"drills green — run the ROADMAP tier-1 command for the "
              f"full gate", flush=True)
    print("[gate] ROBUSTNESS GATE GREEN", flush=True)
    return 0


# ============================================================ bench mode
def bench_main(repeats: int = 5) -> int:
    """Measure checkpoint save/verify overhead (the BASELINE.md
    Robustness numbers) on the 8-virtual-device CPU mesh."""
    from paddle_tpu.device import pin_cpu
    assert pin_cpu(8), "could not pin the CPU platform"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.mesh import build_mesh, use_mesh, \
        shard_value, P
    from paddle_tpu.parallel.checkpoint import (save_sharded,
                                                verify_checkpoint,
                                                CheckpointManager)

    mesh = build_mesh({"dp": 2, "mp": 4})
    rng = np.random.RandomState(0)
    with use_mesh(mesh):
        # ~8 MB of fp32 state: a model-scaled-down-but-not-trivial tree
        state = {
            "params": {
                "emb": shard_value(jnp.asarray(
                    rng.randn(1024, 512).astype(np.float32)),
                    P(None, "mp"), mesh),
                "w": shard_value(jnp.asarray(
                    rng.randn(512, 2048).astype(np.float32)),
                    P("mp", None), mesh),
            },
            "opt_state": {
                "m": shard_value(jnp.asarray(
                    rng.randn(1024, 512).astype(np.float32)),
                    P(None, "mp"), mesh),
            },
            "step": np.int64(1),
        }
        nbytes = (1024 * 512 * 2 + 512 * 2048) * 4
        with tempfile.TemporaryDirectory() as td:
            mgr = CheckpointManager(td, max_to_keep=3)
            save_ms, verify_ms = [], []
            for i in range(repeats):
                t = time.time()
                path = mgr.save(state, i)
                save_ms.append((time.time() - t) * 1e3)
                t = time.time()
                verify_checkpoint(path)
                verify_ms.append((time.time() - t) * 1e3)
        line = {
            "bench": "checkpoint_overhead",
            "state_mb": round(nbytes / 2 ** 20, 2),
            "save_ms_median": round(sorted(save_ms)[len(save_ms) // 2], 2),
            "verify_ms_median": round(
                sorted(verify_ms)[len(verify_ms) // 2], 2),
            "repeats": repeats,
        }
        print(json.dumps(line))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="internal: run as the training worker")
    ap.add_argument("--full", action="store_true",
                    help="kill/crash at EVERY step phase (slow)")
    ap.add_argument("--quick", action="store_true",
                    help="representative phases only (default)")
    ap.add_argument("--bench", action="store_true",
                    help="measure save/verify overhead, print one JSON")
    ap.add_argument("--elastic", action="store_true",
                    help="device-loss-at-every-phase scenario group "
                         "(ISSUE 14); composes with --quick")
    ap.add_argument("--gate", action="store_true",
                    help="pre-commit robustness gate: quick + elastic "
                         "+ serving drills AND tools/diff_failures.py "
                         "vs the stored tier-1 baseline, one exit code")
    ap.add_argument("--serving", action="store_true",
                    help="serving chaos drill only "
                         "(chaos_serving.py --quick subprocess)")
    ap.add_argument("--tier1-log", default="/tmp/_t1.log",
                    help="tier-1 pytest log for the --gate "
                         "diff_failures leg")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--elastic-steps", type=int, default=10)
    ap.add_argument("--keep-logs", action="store_true")
    args = ap.parse_args()
    if args.worker:
        if os.environ.get(MODE_ENV) == "elastic":
            return elastic_worker_main()
        return worker_main()
    if args.bench:
        return bench_main()
    if args.gate:
        return gate_main(args.steps, args.elastic_steps,
                         args.tier1_log, keep_logs=args.keep_logs)
    if args.serving:
        return run_serving_drill(keep_logs=args.keep_logs)
    if args.elastic:
        rc = 0
        if args.quick or args.full:
            rc = run_drill(args.steps, full=args.full,
                           keep_logs=args.keep_logs)
        return rc or run_elastic_drill(args.elastic_steps,
                                       keep_logs=args.keep_logs)
    return run_drill(args.steps, full=args.full, keep_logs=args.keep_logs)


if __name__ == "__main__":
    sys.exit(main())
