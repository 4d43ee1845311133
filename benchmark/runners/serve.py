"""The serving runner: create_router(params, cfg, replicas=1, num_slots,
max_len) with every other option at the program's default, driven through
submit() / step() by the benchmark's own loop and clock.

One loop serves both kinds of mix. A backlog mix keeps `queued_per_slot` x
slots requests waiting at all times; an open-loop mix submits each request
before the router.step() that follows its due time and times it FROM THE
DUE TIME. The benchmark stamps its own times: a token is seen when the
router.step() that emitted it returns.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import flops, harness
from ..correct import serve as correct
from ..trace.capture import Capture, span
from ..weights import make_gpt_params
from .gpt_family import program_config

TRACE_SECONDS = 3.0       # traced past the window's close (--trace 1)
DRAIN_LIMIT_S = 60.0      # past the close, for requests due inside
SAMPLE_REQUESTS = 6       # judged by the reference after the window


def build(cell: dict, seed: int, **engine_kw):
    """-> the program's router over one engine, holding the only reference
    to the seed's weights."""
    from paddle_tpu.inference.router import create_router
    config = cell["config"]
    params = make_gpt_params(config["model"], seed)
    router = create_router(
        params, program_config(config), replicas=1,
        num_slots=config["sizing"]["num_slots"],
        max_len=config["sizing"]["max_len"], **engine_kw)
    return router


class _Live:
    """One request in flight, as the benchmark sees it."""
    __slots__ = ("req", "due", "prompt_len", "max_new", "seen", "t_first",
                 "t_last", "t_admit", "prompt")

    def __init__(self, req, due, prompt, max_new):
        self.req, self.due = req, due
        self.prompt, self.prompt_len, self.max_new = prompt, len(prompt), \
            max_new
        self.seen = 0
        self.t_first = self.t_last = self.t_admit = None


class Loop:
    """submit -> router.step -> stamp, on one clock (seconds since t0)."""

    def __init__(self, router, stream, slots: int, model: dict, clock=None):
        self.router, self.stream, self.slots = router, stream, slots
        self.model = model
        self.clock = clock or time.perf_counter
        self.live: list = []
        self.ended: list = []
        self.all: list = []
        self.tick_s: list = []
        self.model_flops = 0.0
        self.model_bytes = 0.0
        self.output_tokens = 0
        self.t0 = None

    def now(self) -> float:
        return self.clock() - self.t0

    def _submit(self, now: float) -> None:
        s = self.stream
        if s.backlog:
            waiting = sum(1 for l in self.live if l.seen == 0)
            for _ in range(s.queued_per_slot * self.slots - waiting):
                self._submit_one(now)
        else:
            while not s.exhausted() and s.peek_due() <= now:
                self._submit_one(None)

    def _submit_one(self, now) -> None:
        due, prompt, max_new = self.stream.next()
        live = _Live(self.router.submit(prompt, max_new),
                     now if due is None else due, prompt, max_new)
        self.live.append(live)
        self.all.append(live)

    def _stamp(self, ts: float, te: float, counting: bool) -> None:
        """After a router.step() that ran ts..te: note what each live
        request gained, and charge the tick its algorithmic work."""
        m, kv_positions, worked, still = self.model, 0, False, []
        for l in self.live:
            n = len(l.req.tokens)
            if n > l.seen:
                worked = True
                if l.seen == 0:
                    l.t_first, l.t_admit = te, ts
                    if counting:
                        self.model_flops += flops.prefill_flops(
                            m, l.prompt_len)
                for k in range(max(l.seen, 1), n):      # decoded tokens
                    context = l.prompt_len + k
                    kv_positions += context
                    if counting:
                        self.model_flops += flops.decode_flops(m, context)
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
            if worked:
                self.model_bytes += flops.tick_weight_bytes(m) \
                    + kv_positions * flops.kv_bytes_per_position(m)

    def tick(self, counting: bool = True) -> None:
        with span("submit"):
            self._submit(self.now())
        ts = self.now()
        with span("router.step"):
            self.router.step()
        te = self.now()
        with span("stamp"):
            self._stamp(ts, te, counting)

    def run_until(self, stop_s: float, counting: bool = True) -> float:
        """Tick until the clock passes `stop_s`; -> the clock then (a
        window closes when the tick in flight at `stop_s` returns)."""
        s = self.stream
        while True:
            now = self.now()
            if now >= stop_s:
                return now
            if (not s.backlog and not self.router.has_work()
                    and (s.exhausted() or s.peek_due() > now)):
                nxt = stop_s if s.exhausted() else min(s.peek_due(), stop_s)
                with span("wait"):
                    time.sleep(max(0.0, min(nxt - now, 0.005)))
                continue
            self.tick(counting)

    def drain(self, limit_s: float) -> None:
        """Past the close: finish what was due inside; nothing is submitted
        and nothing counts toward the window's rates."""
        stop = self.now() + limit_s
        while self.live and self.router.has_work() and self.now() < stop:
            ts = self.now()
            self.router.step()
            self._stamp(ts, self.now(), counting=False)


def warm_up(router, stream_lengths: dict, vocab: int, seed: int) -> None:
    """One request at each of the mix's warm prompt lengths, through the
    same submit/step path: every prefill bucket the mix reaches and the
    decode tick compile (or load from the cache) here, in set-up."""
    rng = np.random.default_rng([int(seed), 29])
    reqs = [router.submit(rng.integers(0, vocab, n, dtype=np.int32), 4)
            for n in stream_lengths["warm_prompt_lengths"]]
    ticks = 0
    while router.has_work():
        router.step()
        ticks += 1
        if ticks > 1000:
            raise RuntimeError("warm-up did not drain")
    bad = [r for r in reqs if r.finish_reason != "length"]
    if bad:
        raise RuntimeError(f"warm-up requests ended {bad}")


def request_rows(loop: Loop, measured: list) -> list:
    rows = []
    for l in measured:
        n = l.seen
        rows.append({
            "ttft_ms": None if l.t_first is None
            else 1e3 * (l.t_first - l.due),
            "tpot_ms": None if n < 2
            else 1e3 * (l.t_last - l.t_first) / (n - 1),
            "queue_wait_ms": None if l.t_admit is None
            else 1e3 * max(l.t_admit - l.due, 0.0),
            "tokens": n, "prompt_len": l.prompt_len,
            "finish_reason": l.req.finish_reason})
    return rows


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        t_process: float, tamper=None,
        engine_kw: dict | None = None, control: str | None = None) -> dict:
    from ..generators.requests import load_lengths
    config, traffic = cell["config"], cell["traffic"]
    model = config["model"]
    counter = harness.CompileCounter()
    stream = harness.load_generator(traffic).make(traffic, config, seed,
                                                  seconds)
    # a traced run goes on past the window's close at the same load, so
    # that starting and stopping the profiler stalls nothing measured
    stream.periodic = trace
    t_entry = time.perf_counter()
    router = build(cell, seed, **(engine_kw or {}))
    t_built = time.perf_counter()
    if tamper is not None:
        router = tamper(router)
    warm_up(router, load_lengths(traffic["lengths"]), model["vocab_size"],
            seed)
    setup_compiles = counter.count
    loop = Loop(router, stream, config["sizing"]["num_slots"], model)
    capture = Capture(trace)
    gc.collect()
    gc.disable()
    setup_s = time.perf_counter() - t_process

    loop.t0 = loop.clock()
    window_s = loop.run_until(seconds)
    window_compiles = counter.count - setup_compiles
    ended_inside = list(loop.ended)
    capture.start()
    if capture.running:
        loop.run_until(window_s + TRACE_SECONDS, counting=False)
    trace_summary = capture.stop()
    if stream.backlog:
        measured = ended_inside             # ended inside the window
    else:
        loop.drain(DRAIN_LIMIT_S)
        measured = [l for l in loop.all if l.due < seconds]   # due inside
    gc.enable()
    late = [l for l in measured if not l.req.done]
    failed = sum(1 for l in measured
                 if not (l.req.done and l.req.finish_reason == "length"
                         and l.seen == l.max_new))
    rows = request_rows(loop, measured)
    finished = [{"prompt": l.prompt, "tokens": list(l.req.tokens),
                 "max_new": l.max_new} for l in measured
                if l.req.done and l.req.finish_reason == "length"]

    peak = harness.memory_peak_bytes(devices)
    router.close()
    del router, loop.router, stream
    loop.live, loop.all, loop.ended = [], [], []
    gc.collect()

    # ---------------------------------------------------------- correct
    t_ref = time.perf_counter()
    sample = correct.draw_sample(finished, seed, SAMPLE_REQUESTS)
    numbers = correct.reference_numbers(config, seed, sample,
                                        control=control)
    harness.log(f"reference took {time.perf_counter() - t_ref:.1f} s over "
                f"{numbers['served_tokens_compared']} served tokens")
    record = {
        "kind": "serve", "model": model, "chips": len(devices),
        "window_s": window_s, "setup_s": setup_s, "requests": rows,
        "output_tokens": loop.output_tokens, "tick_s": loop.tick_s,
        "model_flops": loop.model_flops, "model_bytes": loop.model_bytes,
        "trace": trace_summary,
        "peaks": harness.load_peaks(devices[0].device_kind)
        if devices[0].platform == "tpu" else None,
    }
    return {"record": record, "attempted": len(measured), "failed": failed,
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
                      "requests_per_s": len(measured) / window_s,
                      "unfinished_after_drain": len(late),
                      "sample_requests": len(sample),
                      "served_tokens_compared":
                          numbers["served_tokens_compared"],
                      "logit_gap_max": numbers["logit_gap_max"]}}
