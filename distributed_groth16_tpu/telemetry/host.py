"""The host's work outside a job's phases: Python's collector and the
server's background ticks, as counters that are always on and as spans
that reach the device trace while a capture is live.

A chip left idle while no job phase enqueues anything is named, in the
benchmark's trace reduction, by the innermost span covering the gap; what
runs outside the phases had no span until these:

    gc         a collection of generation 1 or 2 (attribute `generation`),
               on the thread whose allocation set it off. It holds the
               interpreter lock, so every Python thread waits for it. A
               generation-2 collection also records into the job's (and
               the global) span buffer, so `GET /jobs/{id}` shows it.
               Generation 0 gets no span: it runs hundreds of times a
               second and each takes microseconds, so its spans would
               fill a capture with events that name no gap.
    bg.<task>  one tick of a periodic task the server starts
               (`bg.devmem`, `bg.slo`), on the thread that does the work

    python_gc_collections_total{generation}  collections
    python_gc_seconds_total{generation}      their wall, start to stop
    background_seconds_total{task}           the ticks' wall

All three are bound at import for every label, so they print 0 unraised.
Importing the telemetry package registers the one `gc.callbacks` hook
(beside compile.py's `jax.monitoring` listener); with no capture and no
span buffer a collection costs two clock reads and two counter adds.
"""

from __future__ import annotations

import gc
import time
from contextlib import contextmanager

from . import metrics as _tm
from . import tracing as _tracing

_REG = _tm.registry()
_GC_COUNT = _REG.counter(
    "python_gc_collections_total",
    "Collections of Python's cyclic garbage collector, per generation",
    ("generation",),
)
_GC_SECONDS = _REG.counter(
    "python_gc_seconds_total",
    "Wall seconds inside Python's cyclic garbage collector (every thread "
    "waits for it), per generation",
    ("generation",),
)
_BG_SECONDS = _REG.counter(
    "background_seconds_total",
    "Wall seconds of the server's periodic background ticks, per task",
    ("task",),
)

_GENERATIONS = tuple(
    (_GC_COUNT.labels(generation=g), _GC_SECONDS.labels(generation=g))
    for g in ("0", "1", "2")
)
TASKS = ("devmem", "slo")
_BG = {task: _BG_SECONDS.labels(task=task) for task in TASKS}

# One collection runs at a time in a process (the interpreter's
# `collecting` flag spans both callbacks), and "stop" follows "start" on
# the same thread with no bytecode of that thread between: module state
# suffices.
_gc_t0 = 0.0
_gc_span = _tracing.NOOP


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0, _gc_span
    try:
        generation = info["generation"]
        if phase == "start":
            span = _tracing.NOOP
            if generation:
                span = _tracing.host_span(
                    "gc", {"generation": generation}, record=generation == 2
                )
                span.__enter__()
            _gc_span = span
            _gc_t0 = time.perf_counter()
        elif phase == "stop":
            dt = time.perf_counter() - _gc_t0
            span, _gc_span = _gc_span, _tracing.NOOP
            span.__exit__(None, None, None)
            count, seconds = _GENERATIONS[generation]
            count.inc()
            seconds.inc(dt)
    except Exception:  # noqa: BLE001 — a gc callback must never raise
        pass


@contextmanager
def background(task: str):
    """One tick of the periodic task `task` (one of `TASKS`): a span
    `bg.<task>` on the calling thread and its wall added to
    `background_seconds_total{task}`."""
    t0 = time.perf_counter()
    try:
        with _tracing.span("bg." + task):
            yield
    finally:
        _BG[task].inc(time.perf_counter() - t0)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
