"""`correct` for a serving cell of the jamba family: correct/serve.py's rule
(a sample of the window's finished requests, the longest among them, each
run ONCE through the plain reference with its served tokens; the gap of
every served token's reference logit below the reference's best) over
benchmark/reference/jamba.py.

`told` is what the reference is told beside the configuration: nothing for
the cell's own runs; a test plants a fault there and expects `correct`
false. `control` (one name of CONTROLS, or several joined by "+") reads, at
the same positions, the gap of the token ANOTHER forward would have served,
as `control_<name>_logit_gap_mean` (and, as `control_<name>_logit_move_*`,
how far its logits lie from the reference's): "fp8" is the reference with float8 matmul
operands (the nearest precision below the stated bf16) and "state_bf16" the
reference with its recurrent state rounded to bfloat16 after every step
(below the float32 stated for `ssm_state`); the others are the
reference with a fault in it that the serving program could have — the
recurrent state not carried from one tick to the next, a padded prompt
allowed to advance the state (padded to the engine's power-of-two bucket),
the three inner norms left out, the convolution's carried rows shifted by
one — each of which the cell's limit must refuse.
"""
from __future__ import annotations

import functools

import numpy as np

from ..reference import jamba as ref
from ..weights_jamba import make_params
from .serve import draw_sample, gaps  # noqa: F401  (the runner draws with it)

PAD_TO = 8192           # sequence lengths the reference compiles for
ROWS = 3072             # logits rows a call returns (the longest answer)
CONTROLS = {
    "fp8": {"precision": "fp8"},
    "state_bf16": {"state": "bfloat16"},
    "state_not_carried": {"frozen": True},
    "padding_advances": {"padded": True},
    "no_inner_norms": {"inner_norms": False},
    "conv_shifted": {"conv_shift": 1},
}


def bucket(n: int, lo: int = 8) -> int:
    """The engine's prompt bucket: the next power of two, from `lo`."""
    b = lo
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=None)
def _rows_fn(arch_items: tuple, static_items: tuple):
    import jax
    arch, static = dict(arch_items), dict(static_items)

    def fn(params, padded, at, prompt_len, hidden_keys):
        kw = dict(static)
        if kw.pop("frozen", False):
            kw["frozen_from"] = prompt_len
        if kw.pop("padded", False):
            kw["hidden_keys"] = hidden_keys
        return ref.logits_rows(params, padded, at, arch, **kw)

    return jax.jit(fn)


def served_rows(params, arch: dict, prompt, tokens, **told):
    """Reference logits [n, V] at the n positions that predicted the n
    served tokens, given the prompt and the tokens served before each.
    One program a padded length: the positions are handed as ROWS
    indices, the last repeated."""
    import jax.numpy as jnp
    prompt, tokens = np.asarray(prompt), np.asarray(tokens)
    n, p = len(tokens), len(prompt)
    pads = bucket(p) - p if told.get("padded") else 0
    seq = np.concatenate([prompt, np.zeros(pads, prompt.dtype), tokens])[:-1]
    pad = min(PAD_TO, arch["max_seq_len"])
    padded = np.zeros((-(-len(seq) // pad) * pad,), np.int32)
    padded[:len(seq)] = seq             # causal: the padding changes nothing
    # the last prompt token's answer is the prefill's, before any padding
    at = np.full((ROWS,), p + pads + n - 2, np.int32)
    at[:n] = np.concatenate([[p - 1], p + pads + np.arange(n - 1)])
    logits = _rows_fn(tuple(sorted(arch.items())),
                      tuple(sorted(told.items())))(
        params, jnp.asarray(padded), jnp.asarray(at), jnp.int32(p),
        jnp.asarray([p, p + pads], jnp.int32))
    return logits[:n]


def reference_numbers(arch: dict, seed: int, sample: list, *,
                      control: str | None = None,
                      told: dict | None = None) -> dict:
    """The cell's numbers for `sample` (dicts with "prompt", "tokens",
    "max_new"); with `control` also that forward's reading."""
    told = told or {}
    params = make_params(arch, seed)
    controls = control.split("+") if control else []
    seen, margins, repeats, broken = [], [], [], False
    control_seen = {name: [] for name in controls}
    control_moved = {name: [] for name in controls}
    for req in sample:
        tokens = np.asarray(req["tokens"], np.int64)
        if (len(tokens) != req["max_new"] or len(tokens) > ROWS
                or tokens.min() < 0 or tokens.max() >= arch["vocab_size"]):
            broken = True
            continue
        rows = served_rows(params, arch, req["prompt"], tokens, **told)
        seen.append(gaps(rows, tokens))
        top2 = np.partition(np.asarray(rows), -2, axis=-1)[:, -2:]
        margins.append(top2[:, 1] - top2[:, 0])
        repeats.append(tokens[1:] == tokens[:-1])
        for name in controls:
            other = served_rows(params, arch, req["prompt"], tokens,
                                **CONTROLS[name])
            control_seen[name].append(
                gaps(rows, np.asarray(other.argmax(axis=-1))))
            control_moved[name].append(
                np.asarray(abs(other - rows).max(axis=-1)))

    def numbers(parts, prefix=""):
        if broken or not parts:
            return {prefix + "logit_gap_mean": float("inf"),
                    prefix + "logit_gap_max": float("inf")}
        every = np.concatenate(parts)
        return {prefix + "logit_gap_mean": float(every.mean()),
                prefix + "logit_gap_max": float(every.max())}

    out = {**numbers(seen),
           "served_tokens_compared": int(sum(len(g) for g in seen))}
    if seen:
        # how far a fault has to move a logit before a token flips, and
        # whether greedy decoding of random weights fell into repeating a
        # token: what the gap can and cannot see on this seed (notes only)
        out["reference_margin_mean"] = float(np.concatenate(margins).mean())
        out["served_repeat_share"] = float(np.concatenate(repeats).mean())
    for name in controls:
        out.update(numbers(control_seen[name], f"control_{name}_"))
        if control_moved[name]:
            # how far that forward's logits lie from the reference's, the
            # widest over the vocabulary at each position: what a control
            # that flips no token still shows (the program's own logits
            # never reach the benchmark, so `correct` compares no such
            # number)
            moved = np.concatenate(control_moved[name])
            out[f"control_{name}_logit_move_mean"] = float(moved.mean())
            out[f"control_{name}_logit_move_max"] = float(moved.max())
    return out
