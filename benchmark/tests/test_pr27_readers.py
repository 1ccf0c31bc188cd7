"""The per-layer reader PR 27 added, on hand-made runs: what it reads
where the program counts its resident-circuit lookups, and that it
returns None (the metric is left out of the line) where the program has
no such counter, as the parent of that PR has not, or nothing moved."""

import pytest

from benchmark.layer_metrics import circuit_cache_hit_share

PARENT_TEXT = '''# TYPE crs_cache_hits_total counter
crs_cache_hits_total 0
# TYPE crs_cache_misses_total counter
crs_cache_misses_total 0
# TYPE jax_compiles_total counter
jax_compiles_total 41
'''


def _text(hits, misses):
    return PARENT_TEXT + f'''# TYPE circuit_cache_hits_total counter
circuit_cache_hits_total {hits}
# TYPE circuit_cache_misses_total counter
circuit_cache_misses_total {misses}
# TYPE circuit_cache_evictions_total counter
circuit_cache_evictions_total 0
'''


def _run(before, after):
    return {"records": {"metrics_before": before, "metrics_after": after}}


@pytest.mark.parametrize("before,after,want", [
    # the warm-up took the miss; 100 jobs of the window all hit
    (_text(1, 1), _text(101, 1), 1.0),
    # a window that missed once in four lookups
    (_text(0, 1), _text(3, 2), 0.75),
    # the parent's /metrics text: no such counter
    (PARENT_TEXT, PARENT_TEXT, None),
    # the counters are there and nothing was looked up in the window
    (_text(1, 1), _text(1, 1), None),
    # no records at all
    (None, None, None),
])
def test_hit_share_is_hits_over_lookups_or_nothing(before, after, want):
    got = circuit_cache_hit_share.read(_run(before, after))
    assert got == (pytest.approx(want) if want is not None else None)


def test_a_run_without_records_reads_nothing():
    assert circuit_cache_hit_share.read({}) is None
