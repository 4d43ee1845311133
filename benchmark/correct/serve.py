"""`correct` for a serving cell: a sample, drawn from the seed, of the
requests the window finished (the longest among them), each run ONCE
through the plain reference with its served tokens (teacher forcing).

At every served token the gap is how far its reference logit lies below
the reference's best at that position (0 where the engine served the
reference's own argmax); valid for greedy decoding, which every mix here
uses. Numbers (limits in benchmark/limits/<cell>.json):
  logit_gap_mean  the mean gap over every served token of the sample: it
                  grows with the square of the logits' error, so it tells
                  8-bit matmul operands from bf16 on every seed
  logit_gap_max   the widest gap: it grows only linearly and swings with
                  the one worst near-tie, so it is reported, not compared
A request whose tokens leave the vocabulary, or whose count is not what
was asked, reads as an infinite gap.
"""
from __future__ import annotations

import functools

import numpy as np

from ..reference import gpt as ref
from ..weights import make_gpt_params

PAD_TO = 128


def draw_sample(finished: list, seed: int, k: int) -> list:
    """`k` of the finished requests (dicts with "prompt", "tokens"): the
    longest (prompt + served) first, the rest drawn from the seed."""
    if len(finished) <= k:
        return list(finished)
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    rng = np.random.default_rng([int(seed), 23])
    rest = rng.permutation(order[1:])[:k - 1]
    return [finished[order[0]]] + [finished[int(i)] for i in rest]


@functools.lru_cache(maxsize=None)
def _rows_fn(num_heads: int, eps: float, precision: str):
    """jit: (params, padded tokens [1, T]) -> the reference's logits
    [T, V]; one program per padded length."""
    import jax

    def rows(params, padded):
        return ref.forward(params, padded, num_heads=num_heads, eps=eps,
                           precision=precision)[0]

    return jax.jit(rows)


def served_rows(params, model: dict, prompt, tokens, precision="float32"):
    """Reference logits [n, V] at the n positions that predicted the n
    served tokens, given the prompt and the tokens served before each."""
    import jax.numpy as jnp
    seq = np.concatenate([np.asarray(prompt), np.asarray(tokens)])[:-1]
    width = min(-(-len(seq) // PAD_TO) * PAD_TO, model["max_seq_len"])
    padded = np.zeros((1, width), np.int32)
    padded[0, :len(seq)] = seq          # causal: the padding changes nothing
    logits = _rows_fn(model["num_heads"], model["layer_norm_eps"],
                      precision)(params, jnp.asarray(padded))
    first = len(prompt) - 1
    return logits[first:first + len(tokens)]


def gaps(rows, tokens) -> np.ndarray:
    """Per position: best reference logit - the reference logit of `tokens`."""
    import jax.numpy as jnp
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    chosen = jnp.take_along_axis(rows, tokens[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(rows, axis=-1) - chosen)


def reference_numbers(config: dict, seed: int, sample: list, *,
                      control: str | None = None) -> dict:
    """The cell's numbers for `sample`. With `control` (a lower precision
    of benchmark/reference) also the control's reading: the gap of the
    token that precision puts first at each of the same positions."""
    model = config["model"]
    params = make_gpt_params(model, seed)
    seen, control_seen, broken = [], [], False
    for req in sample:
        tokens = np.asarray(req["tokens"], np.int64)
        if (len(tokens) != req["max_new"] or tokens.min() < 0
                or tokens.max() >= model["vocab_size"]):
            broken = True
            continue
        rows = served_rows(params, model, req["prompt"], tokens)
        seen.append(gaps(rows, tokens))
        if control:
            low = served_rows(params, model, req["prompt"], tokens, control)
            control_seen.append(gaps(rows, np.asarray(low.argmax(axis=-1))))

    def numbers(parts, prefix=""):
        if broken or not parts:
            return {prefix + "logit_gap_mean": float("inf"),
                    prefix + "logit_gap_max": float("inf")}
        every = np.concatenate(parts)
        return {prefix + "logit_gap_mean": float(every.mean()),
                prefix + "logit_gap_max": float(every.max())}

    out = {**numbers(seen),
           "served_tokens_compared": int(sum(len(g) for g in seen))}
    if control:
        out.update(numbers(control_seen, "control_"))
    return out
