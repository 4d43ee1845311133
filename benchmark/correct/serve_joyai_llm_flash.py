"""`correct` for a serving cell of the joyai_llm_flash family:
correct/serve.py's rule (a sample of the window's finished requests, the
longest among them, each run ONCE through the plain reference with its
served tokens; the gap of every served token's reference logit below the
reference's best) over benchmark/reference/joyai_llm_flash.py and the share
the configuration holds: prefill then ticks through the latent pool against
the reference's one full, decompressed forward.

`told` is what the reference is told beside the configuration: nothing for
the cell's own runs; a test plants a fault there and expects `correct`
false. `control` (one name of CONTROLS, or several joined by "+") reads, at
the same positions, the gap of the token ANOTHER forward would have served,
as `control_<name>_logit_gap_mean` (and, as `control_<name>_logit_move_*`,
how far its logits lie from the reference's): "fp8" is the reference with
float8 matmul operands (the nearest precision below the stated bf16) and
"latent_fp8" the reference with the cached latent and shared key rounded to
float8 (below the bf16 stated for `kv_cache`); the others are the reference
with a fault in it that the serving program could have — the selection bias
left out, `routed_scaling_factor` left out, split-half RoPE in place of
interleaved, RoPE on the un-rotated parts as well, the latent left
un-normalised — each of which the cell's limit must refuse.
"""
from __future__ import annotations

import functools

import numpy as np

from ..reference import joyai_llm_flash as ref
from ..weights_joyai_llm_flash import make_params
from .serve import draw_sample, gaps  # noqa: F401  (the runner draws with it)

PAD_TO = 2048           # sequence lengths the reference compiles for
ROWS = 1024             # logits rows a call returns (the longest answer)
CONTROLS = {
    "fp8": {"precision": "fp8"},
    "latent_fp8": {"latent": "fp8"},
    "no_selection_bias": {"selection_bias": False},
    "no_scaling": {"scaling": False},
    "split_half": {"rope": "split_half"},
    "rope_on_nope": {"rope_on_nope": True},
    "latent_not_normed": {"latent_norm": False},
}


@functools.lru_cache(maxsize=None)
def _rows_fn(arch_items: tuple, rows: int, told_items: tuple):
    import jax
    arch, told = dict(arch_items), dict(told_items)

    def fn(params, padded, first):
        return ref.logits_at(params, padded, first, rows, arch, **told)

    return jax.jit(fn)


def served_rows(params, arch: dict, prompt, tokens, **told):
    """Reference logits [n, V] at the n positions that predicted the n
    served tokens, given the prompt and the tokens served before each."""
    import jax.numpy as jnp
    seq = np.concatenate([np.asarray(prompt), np.asarray(tokens)])[:-1]
    pad = min(PAD_TO, arch["max_seq_len"])
    width = min(-(-len(seq) // pad) * pad, arch["max_seq_len"])
    padded = np.zeros((width,), np.int32)
    padded[:len(seq)] = seq             # causal: the padding changes nothing
    rows = min(ROWS, width)
    first = len(prompt) - 1
    start = min(first, width - rows)
    logits = _rows_fn(tuple(sorted(arch.items())), rows,
                      tuple(sorted(told.items())))(
        params, jnp.asarray(padded), jnp.int32(start))
    return logits[first - start:first - start + len(tokens)]


def reference_numbers(arch: dict, seed: int, sample: list, *,
                      control: str | None = None,
                      told: dict | None = None) -> dict:
    """The cell's numbers for `sample` (dicts with "prompt", "tokens",
    "max_new"); with `control` also those forwards' readings."""
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
            # that flips no token still shows
            moved = np.concatenate(control_moved[name])
            out[f"control_{name}_logit_move_mean"] = float(moved.mean())
            out[f"control_{name}_logit_move_max"] = float(moved.max())
    return out
