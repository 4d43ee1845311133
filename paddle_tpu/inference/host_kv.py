"""Host-tier KV: spill cold prefix pages to host RAM, swap back on hit.

Reference analog: the sharding-stages offload machinery
(distributed/fleet/meta_parallel/sharding/group_sharded_optimizer_stage2.py:322
keeps cold optimizer state on host and round-trips it per step) — the
same device-HBM-is-the-scarce-tier economics applied to the serving
engine's paged KV pool. The device pool's LRU cache (serving._PagePool)
stays the hot tier; this module is the warm tier behind it: when
`alloc()` evicts a REGISTERED page (a prompt-prefix page some future
request could hit), the engine's `on_evict` tap copies the page's K/V
to host ndarrays here before the prefix-map entry drops. Admission's
prefix walk (`_plan_admission`) then consults device first, host
second — a host hit swaps the page back in (one `.at[pid].set` per
page, amortized across the request's lifetime) instead of re-running
prefill over those tokens, so prefix-cache CAPACITY is bounded by host
RAM (this cap), not device HBM.

Correctness leans on the pool's copy-on-write discipline: a REGISTERED
page's content is immutable (writers go through `_ensure_private`
which copies first), so the host copy taken at eviction time is
bit-identical to what a device hit would have read — streams cannot
diverge on tier placement. Eviction from THIS tier (LRU over the byte
cap) is also safe: a dropped key simply re-prefills later, trading
compute for memory, never correctness.

Accounting: `serving_memory_ledger` prices the tier as the
`kv_pool_host` component (host RAM, NOT device HBM — excluded from the
device total); gauges `serving.kv_host_bytes` /
`serving.host_spills` / `serving.host_swapins` ride the telemetry
flush cadence. The engine's `host_kv_bytes=` argument is the byte cap
(0 = no tier).
"""
from __future__ import annotations

import collections

import numpy as np

__all__ = ["HostKVTier"]


class HostKVTier:
    """LRU map of prompt-prefix key -> (k, v) host ndarrays (one page
    each, [L, page_size, KV, hd] in the cache dtype). `put` copies (the
    caller may hand a view of a transfer buffer); `get` touches LRU
    order; inserts evict this tier's own LRU entries past `max_bytes`.
    Single-threaded like the engine that owns it."""

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._d: "collections.OrderedDict[object, tuple]" = \
            collections.OrderedDict()
        self.bytes = 0
        self.spills = 0      # pages demoted device -> host (lifetime)
        self.swapins = 0     # pages promoted host -> device (lifetime)
        self.drops = 0       # pages this tier itself evicted (lifetime)

    def __contains__(self, key) -> bool:
        return key in self._d

    def __len__(self) -> int:
        return len(self._d)

    def put(self, key, k_np, v_np) -> bool:
        if key in self._d:
            self._d.move_to_end(key)
            return False
        k_np = np.ascontiguousarray(k_np)
        v_np = np.ascontiguousarray(v_np)
        cost = k_np.nbytes + v_np.nbytes
        if cost > self.max_bytes:
            return False                 # page bigger than the tier
        while self.bytes + cost > self.max_bytes and self._d:
            _, (ek, ev) = self._d.popitem(last=False)    # tier's own LRU
            self.bytes -= ek.nbytes + ev.nbytes
            self.drops += 1
        self._d[key] = (k_np, v_np)
        self.bytes += cost
        self.spills += 1
        return True

    def get(self, key):
        """(k, v) host pair or None; a hit refreshes LRU order. The
        entry STAYS in the tier after a swap-in — registered-page
        content is immutable under COW, so the host copy remains valid
        if the device pool evicts the page again."""
        pair = self._d.get(key)
        if pair is not None:
            self._d.move_to_end(key)
        return pair

    def pop(self, key) -> None:
        pair = self._d.pop(key, None)
        if pair is not None:
            self.bytes -= pair[0].nbytes + pair[1].nbytes

    def clear(self) -> None:
        self._d.clear()
        self.bytes = 0

    def stats(self) -> dict:
        return {"entries": len(self._d), "bytes": self.bytes,
                "spills": self.spills, "swapins": self.swapins,
                "drops": self.drops}
