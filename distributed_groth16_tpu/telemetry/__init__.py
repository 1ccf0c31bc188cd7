"""Telemetry spine: the process-wide metrics registry (metrics.py), span
tracing with Chrome trace-event export (tracing.py), the star-wide
aggregation plane — clock alignment, cross-party trace merging, critical
path (aggregate.py) — the fault flight recorder (flight.py), and jax's
own compile and trace clocks as counters and spans (compile.py:
importing this package registers the program's one `jax.monitoring`
listener), and the host's work outside a job's phases, Python's collector
and the server's background ticks (host.py: the one `gc.callbacks`
hook). Every layer — transport,
distributed kernels, prover, service, API — records through here;
docs/OBSERVABILITY.md is the catalog and naming convention.

The device observatory (docs/OBSERVABILITY.md "Device observatory")
rides the same spine: devmem.py (HBM gauges/snapshots), transfer.py
(host<->device boundary accounting), profiler.py (on-demand XLA capture)
and buildinfo.py. devmem/transfer register their families here;
profiler.py is loaded by the server that offers `POST /profile`, so
importing the spine stays cheap. A speed comes from `benchmark/` alone
(PERF.md).
"""

from . import (  # noqa: F401
    aggregate,
    compile,
    devmem,
    flight,
    host,
    metrics,
    tracing,
    transfer,
)
from .metrics import registry  # noqa: F401
from .tracing import TraceBuffer, collect, span  # noqa: F401
