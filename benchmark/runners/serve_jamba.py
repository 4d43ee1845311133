"""The serving runner of the jamba family (AI21-Jamba2-3B):
create_router(params, cfg, replicas=1, family="jamba", num_slots, max_len)
with every other option at the program's default, driven by
runners/serve.py's own loop, clock and warm-up. What differs from that
runner is what it builds and what it counts: this family's weights
(weights_jamba), its operations and bytes (flops_jamba) — among them the
recurrent state a tick reads and writes, `state_bytes`, kept apart for
`ssm_state_hbm_share.serve` — and its own reference (correct/serve_jamba).

`architecture(config)` is the one place a configuration file becomes
sizes: the seven keys of `model` (which benchmark/tiny.py replaces for the
CPU tests) and the published keys beside them (which it does not), made
to fit each other — the period no longer than the depth and the offset
inside it, `dt_rank` no more than the hidden size, the head size hidden /
heads where the config's `head_dim` is null.
"""
from __future__ import annotations

import gc
import time

from .. import flops_jamba as flops
from .. import harness
from ..correct import serve_jamba as correct
from ..trace.capture import Capture
from ..weights_jamba import make_params
from . import serve

SAMPLE_REQUESTS = serve.SAMPLE_REQUESTS


def architecture(config: dict) -> dict:
    m = config["model"]
    period = min(config["attn_layer_period"], m["num_layers"])
    return {
        **{k: m[k] for k in ("vocab_size", "hidden_size", "num_layers",
                             "num_heads", "ffn_hidden", "max_seq_len",
                             "layer_norm_eps")},
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"]
        or m["hidden_size"] // m["num_heads"],
        "attn_layer_period": period,
        "attn_layer_offset": config["attn_layer_offset"] % period,
        "mamba_d_state": config["mamba_d_state"],
        "mamba_d_conv": config["mamba_d_conv"],
        "mamba_expand": config["mamba_expand"],
        "mamba_dt_rank": min(config["mamba_dt_rank"], m["hidden_size"]),
    }


def program_config(config: dict, arch: dict):
    """The program's config object: every field the file does not state
    stays at the program's default."""
    import jax.numpy as jnp
    from paddle_tpu.models.jamba import JambaConfig
    if config.get("family") != "jamba":
        raise ValueError(f"runner knows the jamba family, not "
                         f"{config.get('family')!r}")
    precision = config["precision"]
    sizes = {k: v for k, v in arch.items() if k != "layer_norm_eps"}
    return JambaConfig(
        **sizes, rms_norm_eps=arch["layer_norm_eps"],
        dtype=jnp.dtype(precision["compute"]).type,
        param_dtype=jnp.dtype(precision["parameters"]).type)


def build(cell: dict, seed: int, **engine_kw):
    """-> the program's router over one engine, holding the only reference
    to the seed's weights. A program without the family fails here, before
    a weight is made."""
    from paddle_tpu.inference.router import create_router
    config = cell["config"]
    arch = architecture(config)
    cfg = program_config(config, arch)
    params = make_params(arch, seed, config["precision"]["parameters"])
    return create_router(
        params, cfg, replicas=1, family="jamba",
        num_slots=config["sizing"]["num_slots"],
        max_len=config["sizing"]["max_len"], **engine_kw)


class Loop(serve.Loop):
    """serve.Loop with this family's arithmetic: `model` is the dict of
    `architecture`; `state_bytes` is the part of `model_bytes` that is
    recurrent state. What a decoded token costs is linear in its context,
    so a tick of 128 of them is charged in one sum."""

    def __init__(self, router, stream, slots: int, model: dict, clock=None):
        super().__init__(router, stream, slots, model, clock)
        self.state_bytes = 0.0
        self._token = flops.decode_flops(model, 0)
        self._position = flops.decode_flops(model, 1) - self._token
        self._weights = flops.weight_bytes(model)
        self._kv_position = flops.kv_bytes_per_position(model)
        self._slot_state = flops.tick_state_bytes(model, 1)

    def _stamp(self, ts: float, te: float, counting: bool) -> None:
        a, decoded, kv_positions, still = self.model, 0, 0, []
        for l in self.live:
            n = len(l.req.tokens)
            if n > l.seen:
                if l.seen == 0:
                    l.t_first, l.t_admit = te, ts
                    if counting:
                        self.model_flops += flops.prefill_flops(
                            a, l.prompt_len)
                        self.model_bytes += self._weights
                first = max(l.seen, 1)                  # decoded tokens
                decoded += n - first
                kv_positions += (n - first) * l.prompt_len \
                    + (first + n - 1) * (n - first) // 2
                if counting:
                    self.output_tokens += n - l.seen
                l.t_last, l.seen = te, n
            if l.req.done:
                self.ended.append(l)
            else:
                still.append(l)
        self.live = still
        if counting:
            self.tick_s.append(te - ts)
            if decoded:
                state = decoded * self._slot_state
                self.state_bytes += state
                self.model_flops += decoded * self._token \
                    + kv_positions * self._position
                self.model_bytes += self._weights + state \
                    + kv_positions * self._kv_position


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        t_process: float, tamper=None, engine_kw: dict | None = None,
        control: str | None = None, told: dict | None = None) -> dict:
    """One run of the cell (runners/serve.run's order of events). `told`
    is what the reference is told beside the configuration, `control` a
    second forward read at the same positions (correct/serve_jamba)."""
    from ..generators.requests import load_lengths
    config, traffic = cell["config"], cell["traffic"]
    arch = architecture(config)
    counter = harness.CompileCounter()
    stream = harness.load_generator(traffic).make(traffic, config, seed,
                                                  seconds)
    stream.periodic = trace
    t_entry = time.perf_counter()
    router = build(cell, seed, **(engine_kw or {}))
    t_built = time.perf_counter()
    if tamper is not None:
        router = tamper(router)
    serve.warm_up(router, load_lengths(traffic["lengths"]),
                  arch["vocab_size"], seed)
    setup_compiles = counter.count
    loop = Loop(router, stream, config["sizing"]["num_slots"], arch)
    capture = Capture(trace)
    gc.collect()
    gc.disable()
    setup_s = time.perf_counter() - t_process

    loop.t0 = loop.clock()
    window_s = loop.run_until(seconds)
    window_compiles = counter.count - setup_compiles
    measured = list(loop.ended)             # a backlog: ended inside
    capture.start()
    if capture.running:
        loop.run_until(window_s + serve.TRACE_SECONDS, counting=False)
    trace_summary = capture.stop()
    if not stream.backlog:
        loop.drain(serve.DRAIN_LIMIT_S)
        measured = [l for l in loop.all if l.due < seconds]
    gc.enable()
    late = [l for l in measured if not l.req.done]
    failed = sum(1 for l in measured
                 if not (l.req.done and l.req.finish_reason == "length"
                         and l.seen == l.max_new))
    rows = serve.request_rows(loop, measured)
    finished = [{"prompt": l.prompt, "tokens": list(l.req.tokens),
                 "max_new": l.max_new} for l in measured
                if l.req.done and l.req.finish_reason == "length"]

    peak = harness.memory_peak_bytes(devices)
    attempted, unfinished = len(measured), len(late)
    # a request keeps its engine alive, and the engine 8 GB of weights and
    # pools: the reference's weights fit only once every one is gone
    router.close()
    del router, loop.router, stream, measured, late
    loop.live, loop.all, loop.ended = [], [], []
    gc.collect()
    import jax
    harness.log(f"window {window_s:.1f} s after {setup_s:.1f} s of set-up: "
                f"{attempted} requests ended, {len(loop.tick_s)} ticks, "
                f"{loop.output_tokens} tokens; {failed} failed; "
                f"{sum(a.nbytes for a in jax.live_arrays()) / 1e9:.2f} GB "
                "still live on the device before the reference")

    t_ref = time.perf_counter()
    sample = correct.draw_sample(finished, seed, SAMPLE_REQUESTS)
    numbers = correct.reference_numbers(arch, seed, sample, control=control,
                                        told=told)
    harness.log(f"reference took {time.perf_counter() - t_ref:.1f} s over "
                f"{numbers['served_tokens_compared']} served tokens")
    record = {
        "kind": "serve", "model": arch, "chips": len(devices),
        "window_s": window_s, "setup_s": setup_s, "requests": rows,
        "output_tokens": loop.output_tokens, "tick_s": loop.tick_s,
        "model_flops": loop.model_flops, "model_bytes": loop.model_bytes,
        "state_bytes": loop.state_bytes,
        "trace": trace_summary,
        "peaks": harness.load_peaks(devices[0].device_kind)
        if devices[0].platform == "tpu" else None,
    }
    return {"record": record, "attempted": attempted, "failed": failed,
            "numbers": numbers, "memory_peak_bytes": peak,
            "trace": trace_summary,
            "notes": {"setup_s": setup_s,
                      "setup_parts_s": {
                          "start_to_runner": t_entry - t_process,
                          "weights_and_engine": t_built - t_entry,
                          "warm_up": setup_s - (t_built - t_process)},
                      "compiles_in_setup": setup_compiles,
                      "compiles_in_window": window_compiles,
                      "ticks": len(loop.tick_s),
                      # the longest step fills every slot (128 prefills);
                      # after it, a stall of the machine shows as a step
                      # far past the longest prefill, or outside the steps
                      "tick_s_top3": sorted(loop.tick_s)[-3:][::-1],
                      "outside_ticks_s": window_s - sum(loop.tick_s),
                      "requests_per_s": attempted / window_s,
                      "prompt_tokens": sum(r["prompt_len"] for r in rows),
                      "unfinished_after_drain": unfinished,
                      "sample_requests": len(sample),
                      "served_tokens_compared":
                          numbers["served_tokens_compared"],
                      "logit_gap_max": numbers["logit_gap_max"],
                      **{k: v for k, v in numbers.items()
                         if k.startswith(("control_", "reference_margin",
                                          "served_repeat"))}}}
