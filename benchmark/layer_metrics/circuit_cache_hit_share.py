"""The share of the window's circuit lookups that the program served from
its resident circuits (the parsed circuit, its compiled matrices and the
device-resident proving key, kept per circuit since PR 27): hits / (hits +
misses) of the program's counters `circuit_cache_hits_total` and
`circuit_cache_misses_total`, between the /metrics text taken after the
warm-up and the one taken after the window. 1.0 where every job of the
window found its circuit resident (the warm-up's first proof takes the
miss); None where the program has no such counter, or looked nothing up.

The counters carry no label, and `checks.counter` reads labelled series
only: hence the pattern here."""

import re

LAYER, UNIT, MOVES = "host preparation", "share", "proof_p50_s"


def _total(text, family):
    """The family's series summed, or None where the text has none."""
    found = re.findall(
        rf"^{family}(?:\{{[^}}]*\}})?\s+([-+0-9.eE]+)$", text or "", re.M
    )
    return sum(map(float, found)) if found else None


def read(run):
    rec = run.get("records") or {}
    moved = []
    for family in ("circuit_cache_hits_total", "circuit_cache_misses_total"):
        after = _total(rec.get("metrics_after"), family)
        if after is None:
            return None
        moved.append(after - (_total(rec.get("metrics_before"), family) or 0))
    hits, misses = moved
    return hits / (hits + misses) if hits + misses > 0 else None
