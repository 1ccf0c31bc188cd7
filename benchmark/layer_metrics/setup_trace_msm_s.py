"""Seconds jax spent tracing and lowering the tree-MSM programs before the
window: the program's counter `jax_trace_seconds_total{fn}` (its own
`jax.monitoring` listener, PR 23) summed over the functions named
`_msm_tree_jit*`, read from the /metrics text taken after the warm-up. The
part of `setup_trace_s` that padding before the jit boundary would cut
(ROADMAP S3). 0 where the counter is there and no such program was traced;
None where the program has no such counter."""

from ..checks import counter

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(run):
    text = (run.get("records") or {}).get("metrics_before") or ""
    series = counter(text, "jax_trace_seconds_total")
    if not series:
        return None
    return sum(v for (fn,), v in series.items() if "_msm_tree_jit" in fn)
