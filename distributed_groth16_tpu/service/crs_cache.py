"""Packed-CRS LRU cache.

`pack_proving_key` is the dominant fixed cost of an MPC proof on a warm
circuit (the r4 profile put CRS packing at 84% of million-2^13 wall-clock
before the scalar route): it depends only on the stored proving key and
the packing params, not on the witness — so repeat proofs on a hot
circuit can skip it entirely. Entries are keyed by (circuit_id, packing
params); distinct packing factors on one circuit are distinct entries.

Thread-safety + single-flight: worker threads race on a hot key, and
packing is seconds-to-minutes, so the first thread to miss becomes the
leader (computes outside the lock) while followers wait on a per-key
event and then read the cached value — N concurrent proofs on one
circuit cost exactly one pack. A leader failure wakes followers, which
retry leadership so one transient fault doesn't poison the key.

Hit/miss/eviction counters feed `/stats`.

The class is a key -> factory single-flight LRU and nothing in it knows
a CRS: the service's resident circuits (`service/worker.py`: the parsed
circuit, its compiled matrices, the device-resident proving key) are a
second instance, under the counter family `circuit_cache_*`. That
instance uses the two things the packed-CRS one leaves off: a `version`
a caller holds its entry to (the identity of the files it was built
from; another version is a miss that replaces the entry), and a `weigh`
function with a `budget` (device bytes), which bounds the entries
beside their count.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable

from ..telemetry import metrics as _tm

# Process-wide counters (docs/OBSERVABILITY.md) — the /metrics view of the
# per-instance ints below. A process runs one service cache of a family, so
# summing across instances (tests build throwaways) is the intended
# semantics. An instance of another family brings its own three.
_REG = _tm.registry()
CRS_COUNTERS = (
    _REG.counter("crs_cache_hits_total", "Packed-CRS cache hits"),
    _REG.counter("crs_cache_misses_total", "Packed-CRS cache misses"),
    _REG.counter(
        "crs_cache_evictions_total", "Packed-CRS cache LRU evictions"
    ),
)


class CrsCache:
    def __init__(
        self,
        capacity: int = 8,
        counters=CRS_COUNTERS,
        weigh: Callable[[Any], int] | None = None,
        budget: Callable[[], int | None] | None = None,
    ):
        self.capacity = capacity
        self.weigh = weigh
        self.budget = budget
        self._hits, self._misses, self._evictions = counters
        # key -> (version, value, weight)
        self._data: OrderedDict[Any, tuple[Any, Any, int]] = OrderedDict()
        self._pending: dict[Any, threading.Event] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_pack(
        self, key: Any, factory: Callable[[], Any], version: Any = None
    ) -> Any:
        """Return the cached value for `key`, computing it with `factory`
        on a miss. Concurrent callers on one missing key run `factory`
        once. An entry made under another `version` is a miss, and what
        the factory gives replaces it. With capacity 0, caching is
        disabled and every call packs."""
        if self.capacity <= 0:
            with self._lock:
                self.misses += 1
            self._misses.inc()
            return factory()
        while True:
            with self._lock:
                entry = self._data.get(key)
                if entry is not None and entry[0] == version:
                    self._data.move_to_end(key)
                    self.hits += 1
                    self._hits.inc()
                    return entry[1]
                ev = self._pending.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._pending[key] = ev
                    self.misses += 1
                    self._misses.inc()
                    break  # we are the leader
            # follower: wait for the leader, then re-check (a dead leader
            # leaves the key absent and we retry for leadership)
            ev.wait()
        try:
            value = factory()
            weight = self.weigh(value) if self.weigh is not None else 0
            budget = self.budget() if self.budget is not None else None
        except BaseException:
            with self._lock:
                del self._pending[key]
            ev.set()
            raise
        with self._lock:
            # whatever was there is of another version: it goes either way
            self._data.pop(key, None)
            # an entry heavier than the whole budget is served, not kept:
            # keeping it would turn every other entry out for nothing
            if budget is None or weight <= budget:
                self._data[key] = (version, value, weight)
                while len(self._data) > self.capacity or (
                    budget is not None and self._bytes() > budget
                ):
                    self._data.popitem(last=False)
                    self.evictions += 1
                    self._evictions.inc()
            del self._pending[key]
        ev.set()
        return value

    def _bytes(self) -> int:
        return sum(w for _, _, w in self._data.values())

    def clear(self) -> None:
        with self._lock:
            self._data.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def __contains__(self, key: Any) -> bool:
        with self._lock:
            return key in self._data

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            out = {
                "entries": len(self._data),
                "capacity": self.capacity,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "hitRate": (self.hits / total) if total else None,
            }
            if self.weigh is not None:
                out["bytes"] = self._bytes()
            return out
