"""Numbers against their limits: one rule for every kind of cell."""
from __future__ import annotations


def judge(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "ok"}} for every number the cell's limits
    file (benchmark/limits/<cell>.json) holds. A number the run could not
    produce is not ok."""
    out = {}
    for name, spec in limits["numbers"].items():
        value = numbers.get(name, float("inf"))
        out[name] = {"value": float(value), "limit": float(spec["limit"]),
                     "ok": bool(value <= spec["limit"])}
    return out
