"""The serving runner of the joyai_llm_flash family (JoyAI-LLM-Flash):
create_router(params, cfg, replicas=1, family="joyai_llm_flash", num_slots,
max_len) with every other option at the program's default, driven by
runners/serve.py's own loop, clock and warm-up. What differs from that
runner is what it builds and what it counts: bf16 weights of this family
(weights_joyai_llm_flash, without the multi-token-prediction module, which
serving drops), this family's operations and bytes (flops_joyai_llm_flash:
only what this chip's share computes) — among them the latent a tick reads,
`latent_bytes`, kept apart for `latent_cache_hbm_share.serve` — and its own
reference (correct/serve_joyai_llm_flash).

`architecture(config)` is the one place a configuration file becomes
sizes: the seven keys of `model` (which benchmark/tiny.py replaces for the
CPU tests; `ffn_hidden` is the DENSE layer's width) and the published keys
beside them (which it does not: the latent ranks, the head sizes and the
expert width are the published ones even over a 64-wide hidden state),
made to fit each other — no more dense layers than the depth leaves an
expert layer beside, experts held no more than published.
"""
from __future__ import annotations

import gc
import time

from .. import flops_joyai_llm_flash as flops
from .. import harness
from ..correct import serve_joyai_llm_flash as correct
from ..trace.capture import Capture
from ..weights_joyai_llm_flash import make_params
from . import serve

SAMPLE_REQUESTS = serve.SAMPLE_REQUESTS
PUBLISHED = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
             "n_shared_experts", "norm_topk_prob",
             "num_nextn_predict_layers")


def architecture(config: dict) -> dict:
    m = config["model"]
    published = config["published"]["n_routed_experts"]
    return {
        **{k: m[k] for k in ("vocab_size", "hidden_size", "num_layers",
                             "num_heads", "ffn_hidden", "max_seq_len",
                             "layer_norm_eps")},
        **{k: config[k] for k in PUBLISHED},
        "moe_ffn_hidden": config["moe_intermediate_size"],
        "first_k_dense_replace": min(config["first_k_dense_replace"],
                                     m["num_layers"] - 1),
        "n_routed_experts": published,
        "experts_held": min(config["n_routed_experts"], published),
        "first_expert": config["share"]["first_expert"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "rope_theta": float(config["rope_theta"]),
    }


def program_config(config: dict, arch: dict):
    """The program's config object: every field the file does not state
    stays at the program's default."""
    import jax.numpy as jnp
    from paddle_tpu.models.joyai_llm_flash import JoyaiLlmFlashConfig
    if config.get("family") != "joyai_llm_flash":
        raise ValueError(f"runner knows the joyai_llm_flash family, not "
                         f"{config.get('family')!r}")
    precision = config["precision"]
    sizes = {k: v for k, v in arch.items() if k != "layer_norm_eps"}
    return JoyaiLlmFlashConfig(
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
        params, cfg, replicas=1, family="joyai_llm_flash",
        num_slots=config["sizing"]["num_slots"],
        max_len=config["sizing"]["max_len"], **engine_kw)


class Loop(serve.Loop):
    """serve.Loop with this family's arithmetic: `model` is the dict of
    `architecture`; `latent_bytes` is the part of `model_bytes` that is
    the cached latent. What a decoded token costs is linear in its
    context, so a tick of 32 of them is charged in one sum."""

    def __init__(self, router, stream, slots: int, model: dict, clock=None):
        super().__init__(router, stream, slots, model, clock)
        self.latent_bytes = 0.0
        self._token = flops.decode_flops(model, 0)
        self._position = flops.decode_flops(model, 1) - self._token
        self._latent_position = flops.latent_bytes_per_position(model)

    def _stamp(self, ts: float, te: float, counting: bool) -> None:
        a, decoded, positions, still = self.model, 0, 0, []
        for l in self.live:
            n = len(l.req.tokens)
            if n > l.seen:
                if l.seen == 0:
                    l.t_first, l.t_admit = te, ts
                    if counting:
                        self.model_flops += flops.prefill_flops(
                            a, l.prompt_len)
                        self.model_bytes += flops.tick_weight_bytes(
                            a, l.prompt_len)
                first = max(l.seen, 1)                  # decoded tokens
                decoded += n - first
                # token k of the answer attends prompt_len + k positions
                positions += (n - first) * l.prompt_len \
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
                latent = positions * self._latent_position
                self.latent_bytes += latent
                self.model_flops += decoded * self._token \
                    + positions * self._position
                self.model_bytes += flops.tick_weight_bytes(a, decoded) \
                    + latent


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        t_process: float, tamper=None, engine_kw: dict | None = None,
        control: str | None = None, told: dict | None = None) -> dict:
    """One run of the cell (runners/serve.run's order of events). `told`
    is what the reference is told beside the configuration, `control` a
    second forward read at the same positions
    (correct/serve_joyai_llm_flash)."""
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
    # a request keeps its engine alive, and the engine 12 GB of weights
    # and pools: the reference's weights fit only once every one is gone
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
    expert_layers = arch["num_layers"] - arch["first_k_dense_replace"]
    record = {
        "kind": "serve", "model": arch, "chips": len(devices),
        "window_s": window_s, "setup_s": setup_s, "requests": rows,
        "output_tokens": loop.output_tokens, "tick_s": loop.tick_s,
        "model_flops": loop.model_flops, "model_bytes": loop.model_bytes,
        "latent_bytes": loop.latent_bytes,
        "held_expert_layers": arch["experts_held"] * expert_layers,
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
                      # the longest step fills every slot (32 prefills);
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
