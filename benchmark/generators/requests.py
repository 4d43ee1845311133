"""Serving requests: ONE general generator that a traffic file of
parameters drives (lengths, arrivals, backlog) — a new mix is a new file.

Steadiness by construction: the multiset of (prompt, output) lengths and
of inter-arrival gaps comes from the file's `shape_seed`, so it is the same
in every run of a cell; `--seed` draws the token ids and, with `"order":
"seeded"` (the default), the order. Every seed then offers the same work in
another order. `"order": "fixed"` keeps the file's own order for every
seed: an open loop's tail turns on which long requests meet which burst,
so under a seeded order its p95 is the seed's, not the system's (PERF.md).
"""
from __future__ import annotations

import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lognormal(rng, n: int, spec: dict) -> np.ndarray:
    draw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(draw), spec["min"], spec["max"]).astype(np.int64)


def draw_lengths(lengths: dict, n: int, shape_seed: int) -> np.ndarray:
    """[n, 2] of (prompt, output) lengths: lognormal, clipped, and
    prompt + output held to the model's positions by cutting the output."""
    rng = np.random.default_rng([int(shape_seed), 11])
    prompt = _lognormal(rng, n, lengths["prompt"])
    output = _lognormal(rng, n, lengths["output"])
    output = np.minimum(output, lengths["max_positions"] - prompt)
    if (output < 1).any():
        raise ValueError("a prompt leaves no position for an output token")
    return np.stack([prompt, output], axis=1)


def draw_gaps(arrival: dict, n: int, shape_seed: int) -> np.ndarray:
    """n inter-arrival gaps (seconds) of a gamma renewal process with the
    file's rate and coefficient of variation (cv 1 = Poisson)."""
    rng = np.random.default_rng([int(shape_seed), 13])
    cv2 = float(arrival.get("cv", 1.0)) ** 2
    return rng.gamma(1.0 / cv2, cv2 / float(arrival["rate_per_s"]), n)


class RequestStream:
    """`next()` -> (due_s, prompt ids, max_new_tokens). An open-loop mix
    holds `n_open` requests, all due inside the window; with `periodic`
    set it goes on past the window by repeating its schedule (a traced run
    keeps the same load up while the profiler runs). A backlog mix (`due_s`
    None) never runs dry: its pool of lengths cycles."""

    def __init__(self, traffic: dict, lengths: dict, vocab: int, seed: int,
                 seconds: float):
        arrival = traffic["arrival"]
        self.backlog = arrival["process"] == "backlog"
        self.periodic = False
        self.seconds = float(seconds)
        self.queued_per_slot = int(arrival.get("queued_per_slot", 0))
        self.vocab = int(vocab)
        self._ids = np.random.default_rng([int(seed), 17])
        rng = np.random.default_rng([int(seed), 19])
        fixed = traffic.get("order", "seeded") == "fixed"

        def order(n: int):
            return np.arange(n) if fixed else rng.permutation(n)

        shape_seed = traffic["shape_seed"]
        if self.backlog:
            self.n_open = None
            pool = draw_lengths(lengths, int(traffic["pool"]), shape_seed)
            self._lengths = pool[order(len(pool))]
            self._due = None
        else:
            n = max(1, int(round(arrival["rate_per_s"] * seconds)))
            self.n_open = n
            self._lengths = draw_lengths(lengths, n, shape_seed)[order(n)]
            gaps = draw_gaps(arrival, n, shape_seed)
            gaps = gaps[order(n)] * (seconds / gaps.sum())
            self._due = np.cumsum(gaps) - gaps[0]
        self._i = 0

    def exhausted(self) -> bool:
        return (not self.backlog and not self.periodic
                and self._i >= self.n_open)

    def _due_of(self, i: int) -> float:
        return (i // self.n_open) * self.seconds \
            + float(self._due[i % self.n_open])

    def peek_due(self):
        return None if self.backlog else self._due_of(self._i)

    def next(self):
        i = self._i
        self._i += 1
        prompt_len, out_len = self._lengths[i % len(self._lengths)]
        prompt = self._ids.integers(0, self.vocab, int(prompt_len),
                                    dtype=np.int32)
        due = None if self.backlog else self._due_of(i)
        return due, prompt, int(out_len)


def load_lengths(name) -> dict:
    """The lengths file a mix names (a dict is taken as it is: the tests'
    tiny mixes carry theirs inline)."""
    if isinstance(name, dict):
        return name
    with open(os.path.join(_HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def make(traffic: dict, config: dict, seed: int, seconds: float):
    lengths = load_lengths(traffic["lengths"])
    if lengths["max_positions"] > config["sizing"]["max_len"]:
        raise ValueError("the mix's lengths pass the engine's max_len")
    return RequestStream(traffic, lengths, config["model"]["vocab_size"],
                         seed, seconds)
