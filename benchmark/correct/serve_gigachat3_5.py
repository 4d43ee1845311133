"""`correct` for a serving cell of the gigachat3_5 family: correct/serve.py's
rule (a sample of the window's finished requests, the longest among them,
each run ONCE through the plain reference with its served tokens; the gap
of every served token's reference logit below the reference's best) over
benchmark/reference/gigachat3_5.py and the share the configuration holds:
prefill then ticks through the latent pool and the delta-rule state
against the reference's one full forward with its sequential recurrence.

Beside the logits, the delta-rule STATE a sampled prompt leaves: the
runner runs each sampled request's prompt once more through the engine's
timed prefill program and hands this module the state rows the slot then
holds (`states`, [linear layers, value heads, dk, dv] float32 a request);
the reference's scan hands back its own after the same positions.
`state_gap` is the worst value head of the FIRST linear layer, over the
sampled requests, of |S_program - S_reference| / |S_reference|
(Frobenius over the head's dk x dv): the layer whose input the program and
the reference share up to the rounding of one norm, so that what the
number sees is the state's own arithmetic — a state rounded at every step
accumulates its rounding over the head's memory, the program's bf16
projections do not.

`told` is what the reference is told beside the configuration: nothing for
the cell's own runs; a test plants a fault there and expects `correct`
false. `control` (one name of CONTROLS, or several joined by "+") reads, at
the same positions, the gap of the token ANOTHER forward would have
served, as `control_<name>_logit_gap_mean` (and, as
`control_<name>_logit_move_*`, how far its logits lie from the
reference's): "fp8" is the reference with float8 matmul operands (the
nearest precision below the stated bf16) and "state_bf16" the reference
with its delta-rule state rounded to bfloat16 after every step (below the
float32 stated for `recurrent_state`); the others are the reference with a
fault in it that the serving program could have — the decay left out,
beta left out, q and k not L2-normed, value head j reading key head
j % 32, the output gate's scale 2 left out, the YaRN softmax factor left
out, latent attention reading the latent of positions a block of 128
too early, a padded prompt allowed to advance the state (padded to the
engine's power-of-two bucket). Each control's states give
`control_<name>_state_gap`.
"""
from __future__ import annotations

import functools

import numpy as np

from ..reference import gigachat3_5 as ref
from ..weights_gigachat3_5 import make_params
from .serve import draw_sample, gaps  # noqa: F401  (the runner draws with it)
from .serve_jamba import bucket

PAD_TO = 8192           # sequence lengths the reference compiles for
ROWS = 4096             # logits rows a call returns (the longest answer)
CONTROLS = {
    "fp8": {"precision": "fp8"},
    "state_bf16": {"state": "bfloat16"},
    "no_decay": {"decay": False},
    "no_beta": {"beta": False},
    "qk_not_normed": {"qk_norm": False},
    "head_modulo": {"head_map": "modulo"},
    "no_gate_scale": {"gate_scale": False},
    "no_mscale": {"mscale": False},
    "latent_shift": {"latent_shift": 128},
    "padding_advances": {"padded": True},
}


@functools.lru_cache(maxsize=None)
def _rows_fn(arch_items: tuple, static_items: tuple):
    import jax
    arch, static = dict(arch_items), dict(static_items)

    def fn(params, padded, at, hidden_keys):
        kw = dict(static)
        if kw.pop("padded", False):
            kw["hidden_keys"] = hidden_keys
        return ref.logits_rows(params, padded, at, arch,
                               keep=hidden_keys[1], **kw)

    return jax.jit(fn)


def served_rows(params, arch: dict, prompt, tokens, **told):
    """(Reference logits [m, V] at the positions that predicted the first
    m served tokens, given the prompt and the tokens served before each;
    the linear layers' states [layers, value heads, dk, dv] after the
    prompt, its padding included where the fault pads it): m = n, but
    where a planted fault's padding would pass the one length the
    reference compiles for (the model's positions), m is what fits. The
    positions are handed as ROWS indices, the last repeated."""
    import jax.numpy as jnp
    prompt, tokens = np.asarray(prompt), np.asarray(tokens)
    p = len(prompt)
    pads = bucket(p) - p if told.get("padded") else 0
    width = min(PAD_TO, arch["max_seq_len"])
    tokens = tokens[:width - p - pads + 1]
    n = len(tokens)
    seq = np.concatenate([prompt, np.zeros(pads, prompt.dtype), tokens])[:-1]
    padded = np.zeros((width,), np.int32)
    padded[:len(seq)] = seq             # causal: the padding changes nothing
    rows = min(ROWS, arch["max_seq_len"])
    # the last prompt token's answer is the prefill's, before any padding
    at = np.full((rows,), p + pads + n - 2, np.int32)
    at[:n] = np.concatenate([[p - 1], p + pads + np.arange(n - 1)])
    logits, states = _rows_fn(tuple(sorted(arch.items())),
                              tuple(sorted(told.items())))(
        params, jnp.asarray(padded), jnp.asarray(at),
        jnp.asarray([p, p + pads], jnp.int32))
    return logits[:n], np.asarray(states)


def head_gaps(states, reference) -> np.ndarray:
    """|S - S_ref| / |S_ref| of each layer's each value head [layers,
    heads], Frobenius over the head's dk x dv."""
    states = np.asarray(states, np.float64)
    reference = np.asarray(reference, np.float64)
    apart = np.sqrt(np.sum((states - reference) ** 2, axis=(-2, -1)))
    return apart / np.maximum(np.sqrt(np.sum(reference ** 2,
                                             axis=(-2, -1))), 1e-30)


def reference_numbers(arch: dict, seed: int, sample: list, *,
                      control: str | None = None,
                      told: dict | None = None,
                      states: list | None = None) -> dict:
    """The cell's numbers for `sample` (dicts with "prompt", "tokens",
    "max_new") and `states`, the program's delta-rule state after each
    one's prompt (None: no state numbers, which `correct` reads as
    missing); with `control` also those forwards' readings."""
    told = told or {}
    params = make_params(arch, seed)
    controls = control.split("+") if control else []
    seen, margins, repeats, broken = [], [], [], False
    state_seen = []
    control_seen = {name: [] for name in controls}
    control_moved = {name: [] for name in controls}
    control_states = {name: [] for name in controls}
    for i, req in enumerate(sample):
        tokens = np.asarray(req["tokens"], np.int64)
        if (len(tokens) != req["max_new"] or len(tokens) > ROWS
                or tokens.min() < 0 or tokens.max() >= arch["vocab_size"]):
            broken = True
            continue
        rows, held = served_rows(params, arch, req["prompt"], tokens, **told)
        seen.append(gaps(rows, tokens[:len(rows)]))
        top2 = np.partition(np.asarray(rows), -2, axis=-1)[:, -2:]
        margins.append(top2[:, 1] - top2[:, 0])
        repeats.append(tokens[1:] == tokens[:-1])
        if states is not None:
            state_seen.append(head_gaps(states[i], held)[0].max())
        for name in controls:
            other, other_held = served_rows(params, arch, req["prompt"],
                                            tokens, **CONTROLS[name])
            m = len(other)
            control_seen[name].append(
                gaps(rows[:m], np.asarray(other.argmax(axis=-1))))
            control_moved[name].append(
                np.asarray(abs(other - rows[:m]).max(axis=-1)))
            control_states[name].append(head_gaps(other_held, held)[0].max())

    def state_numbers(worst, prefix=""):
        return {prefix + "state_gap": float(max(worst))} if worst else {}

    def numbers(parts, prefix=""):
        if broken or not parts:
            return {prefix + "logit_gap_mean": float("inf"),
                    prefix + "logit_gap_max": float("inf")}
        every = np.concatenate(parts)
        return {prefix + "logit_gap_mean": float(every.mean()),
                prefix + "logit_gap_max": float(every.max())}

    out = {**numbers(seen), **state_numbers(state_seen),
           "served_tokens_compared": int(sum(len(g) for g in seen))}
    if seen:
        # how far a fault has to move a logit before a token flips, and
        # whether greedy decoding of random weights fell into repeating a
        # token: what the gap can and cannot see on this seed (notes only)
        out["reference_margin_mean"] = float(np.concatenate(margins).mean())
        out["served_repeat_share"] = float(np.concatenate(repeats).mean())
    for name in controls:
        out.update(numbers(control_seen[name], f"control_{name}_"))
        out.update(state_numbers(control_states[name], f"control_{name}_"))
        if control_moved[name]:
            # how far that forward's logits lie from the reference's, the
            # widest over the vocabulary at each position: what a control
            # that flips no token still shows
            moved = np.concatenate(control_moved[name])
            out[f"control_{name}_logit_move_mean"] = float(moved.mean())
            out[f"control_{name}_logit_move_max"] = float(moved.max())
    return out
