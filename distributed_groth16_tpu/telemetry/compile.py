"""jax's own compile and trace clocks, as metrics.

A cold proof is mostly tracing and compiling, and until a process has
traced every program it uses nothing runs at speed. jax times each of
these steps itself and reports them as `jax.monitoring` time-span events
(`jax/_src/dispatch.py`), keyed by the jitted function's name:

    /jax/core/compile/jaxpr_trace_duration            tracing to a jaxpr
    /jax/core/compile/jaxpr_to_mlir_module_duration   lowering to StableHLO
    /jax/core/compile/backend_compile_duration        XLA / Mosaic compile,
                                                      or the fetch of a
                                                      persistent-cache hit

This module is the program's one listener for them. It feeds

    jax_trace_seconds_total{fn}     tracing + lowering seconds (what no
                                    cache keeps)
    jax_compile_seconds_total{fn}   backend compile or cache-fetch seconds
    jax_compiles_total              backend compiles begun, all functions

Sums, not merged wall time: a jitted function traced inside another
reports both, so add series of one `fn`, not across them.

Each event also becomes a span, `jax.trace`, `jax.lower` or `jax.compile`
with attribute `fn`: jax reports the start as a scalar (its value the
start time) and the end as the time span, so the scalar listener opens
the span and the time-span listener closes it, on a per-thread stack (a
function traced inside another nests). The spans only annotate a live
capture (`tracing.host_span`): a job's span tree does not hold them, and
with no capture they are the no-op. After `/readyz`
a rise of `jax_compiles_total` is the recompile alarm: a shape or a static
argument is varying per job, and `jax_compile_seconds_total{fn}` says
whose. The listener is registered when the telemetry package is imported
and costs nothing between compiles.
"""

from __future__ import annotations

import threading
import types

import jax

from . import metrics as _tm
from . import tracing as _tracing

_EV_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_TRACE = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_SPAN = {
    _EV_TRACE[0]: "jax.trace",
    _EV_TRACE[1]: "jax.lower",
    _EV_COMPILE: "jax.compile",
}
_open = threading.local()  # .spans: this thread's open spans, innermost last

_REG = _tm.registry()
_TRACE_SECONDS = _REG.counter(
    "jax_trace_seconds_total",
    "Seconds jax spent tracing and lowering a jitted function "
    "(jax.monitoring), per function name",
    ("fn",),
)
_COMPILE_SECONDS = _REG.counter(
    "jax_compile_seconds_total",
    "Seconds of backend compilation, or of fetching a persistent-cache "
    "hit (jax.monitoring), per function name",
    ("fn",),
)
_COMPILES = _REG.counter(
    "jax_compiles_total",
    "Backend compilations (or persistent-cache fetches) begun; a rise "
    "after warm-up means a program is recompiling",
)


def _fn(kw: dict) -> str:
    """The event's function name; the lowering and compile events say
    `jit(<name>)` where the trace event says `<name>`."""
    name = str(kw.get("fun_name", "?"))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return name


def _on_start(event: str, value: float, **kw) -> None:
    name = _SPAN.get(event)
    if name is None:
        return
    span = _tracing.host_span(name, {"fn": _fn(kw)}, record=False)
    try:
        span.__enter__()
    except Exception:  # noqa: BLE001 — profiling must never fail a trace
        span = _tracing.NOOP
    stack = getattr(_open, "spans", None)
    if stack is None:
        stack = _open.spans = []
    stack.append(span)


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    if event == _EV_COMPILE:
        _COMPILE_SECONDS.labels(fn=_fn(kw)).inc(end - start)
        _COMPILES.inc()
    elif event in _EV_TRACE:
        _TRACE_SECONDS.labels(fn=_fn(kw)).inc(end - start)
    stack = getattr(_open, "spans", None)
    if event in _SPAN and stack:
        try:
            stack.pop().__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — profiling must never fail a trace
            pass


def named_jit(name: str, fn, **jit_kwargs):
    """`jax.jit(fn)` under a program name of its own: jax's events carry
    `name` as `fun_name`, XLA names the module `jit_<name>`, and that is
    what a device trace shows for a launch. `fn` is a plain Python
    function; the jitted one is a copy of it under the new name (same
    code, globals and closure), so no wrapper frame enters the traced
    stack."""
    named = types.FunctionType(
        fn.__code__, fn.__globals__, name, fn.__defaults__, fn.__closure__
    )
    named.__qualname__ = name
    named.__kwdefaults__ = fn.__kwdefaults__
    named.__doc__ = fn.__doc__
    return jax.jit(named, **jit_kwargs)


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_time_span_listener(_on_time_span)
