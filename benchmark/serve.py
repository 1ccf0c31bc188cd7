"""The process that holds the chip: the backend gate, jax's own compile and
trace clocks, and the program's `ApiServer` started in this process on a
loopback port. Copied from `chip_smoke.py` (PR 21), which stays the smoke.
"""

from __future__ import annotations

import asyncio
import importlib.util
import threading

# jax.monitoring time-span events (jax/_src/dispatch.py); start and end are
# time.time() seconds
_EV_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_TRACE = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)


class Tally:
    """Wall seconds during which jax was compiling (a persistent-cache hit
    counts its retrieval time) and tracing or lowering, from jax's own
    monitoring events. Spans are merged before they are summed: a jitted
    function traced inside another reports both, and two worker threads
    may compile at once."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans = {"compile": [], "trace": []}

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        with self._lock:
            if event == _EV_COMPILE:
                self._spans["compile"].append((start, end))
            elif event in _EV_TRACE:
                self._spans["trace"].append((start, end))

    def seconds(self, kind: str, until: float = float("inf")) -> float:
        """Merged seconds of `kind` spans that began before `until`."""
        with self._lock:
            spans = sorted(s for s in self._spans[kind] if s[0] < until)
        total, cur_end = 0.0, float("-inf")
        for start, end in spans:
            total += max(0.0, end - max(start, cur_end))
            cur_end = max(cur_end, end)
        return total

    def compiles_between(self, start: float, end: float) -> int:
        """Backend compilations (or cache retrievals) begun in a window."""
        with self._lock:
            return sum(start <= s < end for s, _ in self._spans["compile"])


def require_package() -> None:
    if importlib.util.find_spec("distributed_groth16_tpu") is None:
        raise SystemExit(
            "benchmark: the distributed_groth16_tpu package is not beside "
            "benchmark/; nothing was run"
        )


def require_tpu(chips: int):
    """jax and its devices, or a non-zero exit before anything compiles.
    JAX_PLATFORMS is never set here and there is no other path."""
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu" or devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, jax found backend={backend!r} "
            f"device={devices[0].platform!r}; nothing was run"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} TPU devices, jax found "
            f"{len(devices)}; nothing was run"
        )
    return jax, devices


def quiet_python_tracer(jax) -> None:
    """The program starts its captures with jax's default options, under
    which the profiler also records every Python and C call of every
    thread: millions of events a second on this host path, a trace of
    hundreds of megabytes for a slice of seconds, and a host slowed to a
    fraction of its speed while the slice runs. The program's spans reach
    the trace as `TraceAnnotation`s, which the host tracer keeps without
    the Python tracer. So captures started without options in this process
    get the default options with the Python tracer off. (The program's
    `telemetry/profiler.py` should pass such options itself: PERF.md, Open
    questions.)"""
    real = jax.profiler.start_trace

    def start_trace(log_dir, *args, profiler_options=None, **kw):
        if profiler_options is None:
            profiler_options = jax.profiler.ProfileOptions()
            profiler_options.python_tracer_level = 0
        return real(log_dir, *args, profiler_options=profiler_options, **kw)

    jax.profiler.start_trace = start_trace


def serve_and_drive(store_dir: str, workers: int, drive):
    """Run the program's ApiServer on this thread's event loop and
    `drive(url, store)` on another thread; return what `drive` returns.
    The service takes its defaults, not the environment's: two runs of a
    cell must not differ by a variable somebody exported."""

    async def main():
        from aiohttp import web

        from distributed_groth16_tpu.api.server import ApiServer
        from distributed_groth16_tpu.api.store import CircuitStore
        from distributed_groth16_tpu.utils.config import (
            SchedulerConfig,
            ServiceConfig,
            SLOConfig,
        )

        store = CircuitStore(store_dir)
        server = ApiServer(
            store=store,
            cfg=ServiceConfig(workers=workers),
            sched_cfg=SchedulerConfig(),
            slo_cfg=SLOConfig(),
        )
        runner = web.AppRunner(server.app())
        await runner.setup()
        try:
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = runner.addresses[0][1]
            return await asyncio.to_thread(
                drive, f"http://127.0.0.1:{port}", server
            )
        finally:
            await runner.cleanup()

    return asyncio.run(main())
