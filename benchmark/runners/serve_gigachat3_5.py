"""The serving runner of the gigachat3_5 family (GigaChat3.5-432B-A28B):
create_router(params, cfg, replicas=1, family="gigachat3_5", num_slots,
max_len) with every other option at the program's default, driven by
runners/serve.py's own loop, clock and warm-up. What differs from that
runner is what it builds and what it counts: bf16 weights of this family
(weights_gigachat3_5), this family's operations and bytes
(flops_gigachat3_5: only what this chip's share computes) — among them the
delta-rule state and convolution rows a tick reads and writes,
`state_bytes`, kept apart for `ssm_state_hbm_share.serve`, and the live
latent it reads, `latent_bytes`, for `latent_cache_hbm_share.serve` — and
its own reference (correct/serve_gigachat3_5), which is also handed the
delta-rule state the engine's prefill program leaves for each sampled
prompt (`program_states`).

`architecture(config)` is the one place a configuration file becomes
sizes: the seven keys of `model` (which benchmark/tiny.py replaces for the
CPU tests; `ffn_hidden` is the DENSE layer's width) and the published keys
beside them (which it does not: the linear heads' widths, the latent
ranks, the head sizes and the expert width are the published ones even
over a 64-wide hidden state; the linear heads follow `num_heads`,
`linear_heads`). The layers are the published ones the
file's `cut.layers` names, in order: their kinds (linear or latent
attention, dense or experts) are the published model's at those indices.
A model cut shallower than that list keeps its first layer and the last
ones (two layers: the leading dense linear layer and the latent one).
"""
from __future__ import annotations

import gc
import time

from .. import flops_gigachat3_5 as flops
from .. import harness
from ..correct import serve_gigachat3_5 as correct
from ..trace.capture import Capture
from ..weights_gigachat3_5 import make_params
from . import serve

SAMPLE_REQUESTS = serve.SAMPLE_REQUESTS
PUBLISHED = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
             "n_shared_experts", "norm_topk_prob", "linear_key_head_dim",
             "linear_value_head_dim", "linear_conv_kernel_dim",
             "linear_attn_o_norm_eps", "use_mla_scaling_factor")


def kept_layers(config: dict) -> list:
    """The published indices of the layers this model runs."""
    kept, n = list(config["cut"]["layers"]), config["model"]["num_layers"]
    if not 2 <= n <= len(kept):
        raise ValueError(f"{n} layers: the cut names {len(kept)}, and a "
                         "model needs the first and at least one more")
    return kept[:1] + kept[len(kept) - (n - 1):]


def linear_heads(config: dict) -> dict:
    """The linear layers' value heads are as many as the latent layers'
    heads (`num_attention_heads` 64 = `linear_num_value_heads` 64 as
    published) and their key heads the published share of them, so they
    follow `model.num_heads`: the published 64 / 32 at the cell's size,
    4 / 2 over the CPU tests' 4 heads (a 64-head state in every slot of a
    64-wide model outlasts the tests' one-second window on a loaded
    machine)."""
    heads = config["model"]["num_heads"]
    value, key = (config["linear_num_value_heads"],
                  config["linear_num_key_heads"])
    if value % config["num_attention_heads"] or heads * key % value:
        raise ValueError("the linear heads do not follow the latent heads")
    return {"linear_num_value_heads": heads * value
            // config["num_attention_heads"],
            "linear_num_key_heads": heads * key // value}


def architecture(config: dict) -> dict:
    m = config["model"]
    kept = kept_layers(config)
    published = config["published"]["n_routed_experts"]
    scaling = config["rope_scaling"]
    return {
        **{k: m[k] for k in ("vocab_size", "hidden_size", "num_layers",
                             "num_heads", "ffn_hidden", "max_seq_len",
                             "layer_norm_eps")},
        **{k: config[k] for k in PUBLISHED},
        **linear_heads(config),
        "full_attention_layers": tuple(
            j for j, i in enumerate(kept)
            if i in config["full_attention_layers"]),
        "first_k_dense_replace": sum(
            1 for i in kept if i < config["first_k_dense_replace"]),
        "moe_ffn_hidden": config["moe_intermediate_size"],
        "n_routed_experts": published,
        "experts_held": min(config["n_routed_experts"], published),
        "first_expert": config["share"]["first_expert"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "linear_sigmoid_gate_scale": float(
            config["linear_sigmoid_gate_scale"]),
        "swiglu_limit": float(config["swiglu_limit"]),
        "rope_theta": float(config["rope_theta"]),
        "rope_factor": float(scaling["factor"]),
        "original_max_position_embeddings":
            scaling["original_max_position_embeddings"],
        "beta_fast": float(scaling["beta_fast"]),
        "beta_slow": float(scaling["beta_slow"]),
        "mscale_all_dim": float(scaling["mscale_all_dim"]),
    }


def program_config(config: dict, arch: dict):
    """The program's config object: every field the file does not state
    stays at the program's default."""
    import jax.numpy as jnp
    from paddle_tpu.models.gigachat3_5 import GigaChat35Config
    if config.get("family") != "gigachat3_5":
        raise ValueError(f"runner knows the gigachat3_5 family, not "
                         f"{config.get('family')!r}")
    precision = config["precision"]
    sizes = {k: v for k, v in arch.items() if k != "layer_norm_eps"}
    return GigaChat35Config(
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
        params, cfg, replicas=1, family="gigachat3_5",
        num_slots=config["sizing"]["num_slots"],
        max_len=config["sizing"]["max_len"], **engine_kw)


def program_states(router, prompts) -> list:
    """The delta-rule state [linear layers, value heads, dk, dv] float32
    each prompt leaves in a slot, run through the engine's own prefill
    program — the one the window timed, compiled in set-up for every
    bucket the mix reaches — into slot 0, whose occupant is then lost: call
    it once nothing more is read from the engine."""
    import numpy as np
    from paddle_tpu.models.decode import prompt_bucket
    eng = router.replicas[0].eng
    out = []
    for prompt in prompts:
        n = len(prompt)
        padded = np.zeros((1, prompt_bucket(n, eng.max_len, eng.bucket_lo)),
                          np.int32)
        padded[0, :n] = prompt
        args = (eng._rep(padded), eng._rep(n, np.int32),
                eng._rep(0, np.int32), eng._rep([0.0], np.float32),
                eng._rep([0], np.int32), eng._rep([0], np.int32))
        _, eng._cache = eng._prefill(eng._params, eng._cache, *args,
                                     eng._base_key, sampling=False)
        out.append(np.asarray(eng._cache["dn_state"][:, 0]))
    return out


class Loop(serve.Loop):
    """serve.Loop with this family's arithmetic: `model` is the dict of
    `architecture`; `state_bytes` is the part of `model_bytes` that is
    delta-rule state and convolution rows, read and written by the rows
    that decoded, and `latent_bytes` the part that is their live latent
    positions. What a decoded token costs is linear in its context, so a
    tick of 128 of them is charged in one sum."""

    def __init__(self, router, stream, slots: int, model: dict, clock=None):
        super().__init__(router, stream, slots, model, clock)
        self.state_bytes = self.latent_bytes = 0.0
        self._token = flops.decode_flops(model, 0)
        self._position = flops.decode_flops(model, 1) - self._token
        self._latent_position = flops.latent_bytes_per_position(model)
        self._row_state = flops.tick_state_bytes(model, 1)

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
                state = decoded * self._row_state
                latent = positions * self._latent_position
                self.state_bytes += state
                self.latent_bytes += latent
                self.model_flops += decoded * self._token \
                    + positions * self._position
                self.model_bytes += flops.tick_weight_bytes(a, decoded) \
                    + latent + state


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        t_process: float, tamper=None, engine_kw: dict | None = None,
        control: str | None = None, told: dict | None = None) -> dict:
    """One run of the cell (runners/serve.run's order of events). `told`
    is what the reference is told beside the configuration, `control` a
    second forward read at the same positions
    (correct/serve_gigachat3_5)."""
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
    sample = correct.draw_sample(finished, seed, SAMPLE_REQUESTS)
    states = program_states(router, [r["prompt"] for r in sample])
    # a request keeps its engine alive, and the engine 13 GB of weights
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
    numbers = correct.reference_numbers(arch, seed, sample, control=control,
                                        told=told, states=states)
    harness.log(f"reference took {time.perf_counter() - t_ref:.1f} s over "
                f"{numbers['served_tokens_compared']} served tokens")
    expert_layers = arch["num_layers"] - arch["first_k_dense_replace"]
    record = {
        "kind": "serve", "model": arch, "chips": len(devices),
        "window_s": window_s, "setup_s": setup_s, "requests": rows,
        "output_tokens": loop.output_tokens, "tick_s": loop.tick_s,
        "model_flops": loop.model_flops, "model_bytes": loop.model_bytes,
        "state_bytes": loop.state_bytes, "latent_bytes": loop.latent_bytes,
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
