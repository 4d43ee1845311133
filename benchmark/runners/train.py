"""The training runner: plan_train -> make_train_step(train_step, ...) at
the program's defaults, a fresh seeded batch every step, the loss pulled
every `sync_every_steps` steps, the window closed by block_until_ready.

Set-up builds ONE object — the compiled step with its state — drives it
through its first three steps (which compile, warm up, and are what
`correct` compares with the reference) and hands that same object to the
window.
"""
from __future__ import annotations

import gc
import math
import time

from .. import harness
from ..correct import train as correct
from ..trace.capture import Capture, span
from ..weights import make_gpt_params
from .gpt_family import program_config


def build(cell: dict, seed: int, devices):
    """-> (step, params, opt_state): the program's planned train step and
    the state it starts from."""
    from paddle_tpu.models.facade import make_train_step
    from paddle_tpu.models.gpt import init_opt_state, train_step
    from paddle_tpu.parallel.planner import plan_train
    config = cell["config"]
    cfg = program_config(config)
    plan = plan_train(cfg, len(devices), config["sizing"]["global_batch"])
    mesh = plan.build_mesh(devices=list(devices))
    opt = config["optimizer"]
    step = make_train_step(
        train_step, cfg=cfg, mesh=mesh, plan=plan, lr=opt["lr"],
        beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"])
    params = make_gpt_params(config["model"], seed)
    harness.log(f"plan {plan.name}")
    return step, params, init_opt_state(params)


def first_steps(step, params, opt, batches, config: dict, seed: int):
    """Drive the step through `batches` by the window's own call. ->
    (program readings for `correct`, params, opt)."""
    import jax
    losses, grad_norms, grad_samples = [], None, None
    for i, batch in enumerate(batches):
        loss, params, opt = step(params, opt, batch)
        losses.append(float(loss))
        if i == 0:                # Adam's m after one step is (1 - beta1) g
            scale = 1.0 / (1.0 - config["optimizer"]["beta1"])
            grad_norms = {k: v * scale
                          for k, v in correct.leaf_norms(opt["m"]).items()}
            grad_samples = correct.leaf_samples(opt["m"], scale)
    jax.block_until_ready(params)
    readings = {"losses": losses, "grad_norms": grad_norms,
                "grad_samples": grad_samples, "update_norms": correct.update_norms(
                    params, config["model"], seed)}
    return readings, params, opt


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        t_process: float, tamper=None) -> dict:
    import jax
    config, traffic = cell["config"], cell["traffic"]
    counter = harness.CompileCounter()
    gen = harness.load_generator(traffic).make(traffic, config, seed, seconds)
    t_entry = time.perf_counter()
    step, params, opt = build(cell, seed, devices)
    t_built = time.perf_counter()
    if tamper is not None:
        step = tamper(step)
    first = [gen.next_batch() for _ in range(correct.STEPS)]
    program, params, opt = first_steps(step, params, opt, first, config,
                                       seed)
    sync_every = int(traffic["sync_every_steps"])
    setup_compiles = counter.count
    gc.collect()
    gc.disable()
    capture = Capture(trace)
    setup_s = time.perf_counter() - t_process

    # ------------------------------------------------------- the window
    def chunk():
        """`sync_every` steps, then the loss pulled: one sync point."""
        nonlocal params, opt
        pending = []
        for _ in range(sync_every):
            with span("next_batch"):
                batch = gen.next_batch()
            with span("step"):
                loss, params, opt = step(params, opt, batch)
            pending.append(loss)
        with span("sync"):
            return [float(x) for x in jax.device_get(pending)]

    chunks, losses = [], []
    t0 = mark = time.perf_counter()
    while mark - t0 < seconds:
        losses.extend(chunk())
        now = time.perf_counter()
        chunks.append((now - mark, sync_every))
        mark = now
    with span("sync"):
        jax.block_until_ready((params, opt))
    window_s = time.perf_counter() - t0
    steps = sync_every * len(chunks)
    window_compiles = counter.count - setup_compiles
    # the trace: one more chunk of the same loop, past the window's close,
    # so that starting and stopping the profiler stalls nothing measured
    capture.start()
    if capture.running:
        chunk()
        jax.block_until_ready((params, opt))
    trace_summary = capture.stop()
    gc.enable()

    peak = harness.memory_peak_bytes(devices)
    del params, opt, step
    gc.collect()

    # ---------------------------------------------------------- correct
    t_ref = time.perf_counter()
    reference = correct.reference_readings(config, seed, first)
    numbers = correct.compare(program, reference)
    harness.log(f"reference took {time.perf_counter() - t_ref:.1f} s")
    failed = sum(1 for x in losses if not math.isfinite(x))
    seq = config["sizing"]["seq_len"]
    record = {
        "kind": "train", "model": config["model"], "chips": len(devices),
        "window_s": window_s, "steps": steps,
        "tokens": steps * config["sizing"]["global_batch"] * seq,
        "seq_len": seq, "chunks": chunks, "setup_s": setup_s,
        "trace": trace_summary,
        "peaks": harness.load_peaks(devices[0].device_kind)
        if devices[0].platform == "tpu" else None,
    }
    return {"record": record, "attempted": steps, "failed": failed,
            "numbers": numbers, "memory_peak_bytes": peak,
            "trace": trace_summary,
            "notes": {"setup_s": setup_s,
                      "setup_parts_s": {
                          "start_to_runner": t_entry - t_process,
                          "plan_and_weights": t_built - t_entry,
                          "first_steps": setup_s - (t_built - t_process)},
                      "compiles_in_setup": setup_compiles,
                      "compiles_in_window": window_compiles,
                      "losses_first_steps": program["losses"],
                      "loss_last": losses[-1]}}
