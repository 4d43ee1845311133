"""Tiny float32 cells for the CPU tests and rehearsals: the same runners,
generators, readers and comparisons as the real cells, at sizes a test run
can hold. They enter through run.drive(), the test-only entry that skips
the look for a chip; the command itself has no CPU mode."""
from __future__ import annotations

import copy

from . import harness

MODEL = {"vocab_size": 640, "hidden_size": 64, "num_layers": 2,
         "num_heads": 4, "ffn_hidden": 256, "max_seq_len": 64,
         "layer_norm_eps": 1e-5}
LENGTHS = {"prompt": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
           "output": {"median": 6, "sigma": 0.7, "min": 2, "max": 16},
           "max_positions": 64, "warm_prompt_lengths": [8, 16, 32, 40]}


def tiny_cell(name: str, rate_per_s: float = 20.0) -> dict:
    """The real cell `name` with its model, sizing and lengths cut down and
    its compute in float32; its limits are the real cell's."""
    cell = copy.deepcopy(harness.load_cell(name))
    config, traffic = cell["config"], cell["traffic"]
    config["model"] = dict(MODEL)
    config["precision"]["compute"] = "float32"
    if config["runner"] == "train":
        config["sizing"] = {"global_batch": 4, "seq_len": MODEL["max_seq_len"]}
        traffic["sync_every_steps"] = 3
    else:
        config["sizing"] = {"num_slots": 4, "max_len": MODEL["max_seq_len"]}
        traffic["lengths"] = dict(LENGTHS)
        if traffic["arrival"]["process"] != "backlog":
            traffic["arrival"]["rate_per_s"] = rate_per_s
    return cell
