"""JAX compile-cost telemetry: make "it's compile-bound" measurable.

The m=32768 mesh prover is dominated by XLA compilation on some backends,
but until now that showed up only as an unexplained slow first call. `timed_jit` wraps a jitted callable and keys calls by the
argument signature (shapes + dtypes): the first call per signature is a
compile miss — timed to full materialisation (`block_until_ready`, so the
number is compile + first execution; for a compile-bound program that IS
the compile cost, and it is an upper bound otherwise) and observed into
`compile_seconds{fn}` — subsequent calls are cache hits. The hit/miss
counters make jit-cache churn (e.g. an accidentally varying shape
re-compiling per round) visible as a ratio instead of folklore.
"""

from __future__ import annotations

import time

from . import metrics as _tm
from . import tracing as _tracing

_REG = _tm.registry()
_COMPILE_SECONDS = _REG.histogram(
    "compile_seconds",
    "First-call (trace+compile+run, host-synced) seconds per jitted fn "
    "and argument signature",
    ("fn",),
)
_HITS = _REG.counter(
    "compile_cache_hits_total",
    "Calls served by an already-compiled signature, per fn",
    ("fn",),
)
_MISSES = _REG.counter(
    "compile_cache_misses_total",
    "Calls that triggered a trace+compile (new signature), per fn",
    ("fn",),
)


def _signature(args: tuple) -> tuple:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            sig.append((tuple(shape), str(getattr(leaf, "dtype", ""))))
        else:
            sig.append(repr(leaf))
    return (treedef, tuple(sig))


def timed_jit(fn_name: str, jitted):
    """Wrap a jitted callable with compile-cost accounting (see module
    docstring). The wrapper is transparent for positional-array call
    sites — the shape every mesh prover entry point uses."""
    seen: set = set()
    hits = _HITS.labels(fn=fn_name)
    misses = _MISSES.labels(fn=fn_name)
    hist = _COMPILE_SECONDS.labels(fn=fn_name)

    def wrapper(*args):
        key = _signature(args)
        if key in seen:
            hits.inc()
            return jitted(*args)
        import jax

        with _tracing.span("compile", attrs={"fn": fn_name}):
            t0 = time.perf_counter()
            out = jax.block_until_ready(jitted(*args))
            dt = time.perf_counter() - t0
        seen.add(key)
        misses.inc()
        hist.observe(dt)
        return out

    wrapper.__wrapped__ = jitted
    wrapper.__name__ = f"timed_jit({fn_name})"
    return wrapper
