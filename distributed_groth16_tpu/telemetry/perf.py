"""Per-kernel performance observatory: declarative bench registry + runner.

ROADMAP item 1 is a kernel problem (the MSM/NTT gap), but until now the
only measurement plane was bench.py's monolithic MSM sweep — try a
GLV/NAF/batched-affine variant and there was no way to see WHICH kernel
bent, by how much, or whether XLA even compiled what the model assumed.
This module is the measurement half of that loop:

  * `@perf_kernel("msm_g1", sizes=(12, 14, 16), ...)` registers a case
    builder; the builder gets a log2-size and returns a `KernelCase`
    (a jitted callable + concrete args + items-per-call). Builders run
    their setup (random bases, twiddle tables) OUTSIDE the timed region.
  * `run_kernel` executes one case: the first call's trace and compile
    seconds are read from jax's own clocks (`telemetry/compile.py`), so
    compile cost is measured separately and excluded from the warm reps;
    warm throughput is reported as median + IQR over K host-synced reps.
  * Each record also carries XLA's own accounting — `cost_analysis()`
    flops / bytes-accessed (roofline context) and `memory_analysis()`
    argument/temp/output bytes, plus per-device `memory_stats()` peak
    where the backend provides it (TPU yes, CPU no).
  * Every record is mirrored into the process metrics registry
    (`perf_kernel_*`, docs/OBSERVABILITY.md) and serialized under the
    versioned `dg16-perf/1` JSON schema that bench.py's `kernels` section
    and `tools/benchgate` both speak — one record shape, three emitters.

The registered default cases live in `telemetry/perf_kernels.py` (they
import ops/ and are loaded lazily so importing the telemetry spine stays
cheap). `tools/benchgate` is the CLI + regression gate over this runner.
"""

from __future__ import annotations

import statistics
import time
from dataclasses import dataclass
from typing import Callable, Sequence

from . import compile as _compile
from . import metrics as _tm
from . import roofline as _roofline
from ..utils import config as _config

PERF_SCHEMA = "dg16-perf/1"

_REG = _tm.registry()
_KERNEL_SECONDS = _REG.histogram(
    "perf_kernel_seconds",
    "Warm (compile-excluded) wall seconds per registered kernel rep",
    ("kernel", "size"),
    buckets=_tm.DEFAULT_KERNEL_BUCKETS,
)
_KERNEL_RATE = _REG.gauge(
    "perf_kernel_items_per_sec",
    "Median warm throughput of the last run, per kernel and size",
    ("kernel", "size"),
)
_KERNEL_COMPILE = _REG.gauge(
    "perf_kernel_compile_seconds",
    "First-call trace+compile seconds of the last run, per kernel "
    "and size",
    ("kernel", "size"),
)
_KERNEL_FLOPS = _REG.gauge(
    "perf_kernel_flops",
    "XLA cost_analysis flop estimate for the compiled kernel",
    ("kernel", "size"),
)
_KERNEL_BYTES = _REG.gauge(
    "perf_kernel_bytes",
    "XLA cost_analysis bytes-accessed estimate for the compiled kernel",
    ("kernel", "size"),
)
_KERNEL_UTIL = _REG.gauge(
    "perf_kernel_utilization",
    "Fraction of the binding roofline roof the kernel achieved in the "
    "last run (telemetry/roofline.py; DG16_PEAK_FLOPS/DG16_PEAK_BW)",
    ("kernel", "size"),
)


@dataclass
class KernelCase:
    """One concrete benchmarkable instance of a registered kernel.

    fn:    the callable to time. Device cases MUST hand a jitted callable
           (it needs `.lower(*args)` for the XLA introspection); host
           cases hand any callable.
    args:  concrete, already-materialized arguments — setup cost (random
           bases, tables, host->device transfer) stays outside the timed
           region.
    items: work items per call (scalar-muls, coefficients, pairings) —
           the throughput denominator.
    """

    fn: Callable
    args: tuple
    items: int


@dataclass(frozen=True)
class KernelSpec:
    """A registered kernel: builder + the sizes it runs at."""

    name: str
    builder: Callable[[int], KernelCase]
    sizes: tuple
    quick_sizes: tuple
    unit: str
    host: bool


_KERNELS: dict[str, KernelSpec] = {}


def perf_kernel(
    name: str,
    sizes: Sequence[int],
    quick: Sequence[int] | None = None,
    unit: str = "items/sec",
    host: bool = False,
):
    """Register a kernel-case builder under `name`.

    sizes: log2 work sizes for the full suite (TPU-scale); `quick` is the
    CPU-smoke subset (default: the smallest full size). `host=True` marks
    pure-Python kernels (GLV, the Miller loop): they are timed the same
    way but carry no compile cost and no XLA introspection.
    """

    def deco(builder):
        q = tuple(quick) if quick is not None else (min(sizes),)
        _KERNELS[name] = KernelSpec(
            name, builder, tuple(sizes), q, unit, host
        )
        return builder

    return deco


def kernels() -> dict[str, KernelSpec]:
    """Registered specs (default set loaded on first use)."""
    _ensure_defaults()
    return dict(_KERNELS)


def _ensure_defaults() -> None:
    from . import perf_kernels  # noqa: F401 — registers on import


def size_key(kernel: str, log2n: int) -> str:
    return f"{kernel}@2e{log2n}"


# -- XLA introspection -------------------------------------------------------


def _xla_introspect(fn, args) -> tuple[dict | None, dict | None]:
    """(cost, memory) from the compiled executable; (None, None) when the
    callable can't be lowered (host fns, exotic wrappers). Best-effort by
    design: introspection must never fail a bench run."""
    try:
        compiled = fn.lower(*args).compile()
    except Exception:  # noqa: BLE001 — introspection is optional context
        return None, None
    cost = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if ca:
            cost = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
    except Exception:  # noqa: BLE001
        cost = None
    memory = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            memory = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "generated_code_bytes": int(ma.generated_code_size_in_bytes),
            }
    except Exception:  # noqa: BLE001
        memory = None
    peak = _device_peak_bytes()
    if peak is not None or memory is not None:
        memory = dict(memory or {})
        memory["peak_bytes"] = peak
    return cost, memory


def _device_peak_bytes() -> int | None:
    """Per-device peak allocation where the backend exposes it (TPU/GPU
    `memory_stats()`; XLA:CPU returns None)."""
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
    except Exception:  # noqa: BLE001
        return None
    if not stats:
        return None
    v = stats.get("peak_bytes_in_use")
    return int(v) if v is not None else None


# -- the runner --------------------------------------------------------------


def default_reps(quick: bool = False) -> int:
    return _config.env_int("DG16_PERF_REPS", 3 if quick else 5)


def run_kernel(spec: KernelSpec, log2n: int, reps: int | None = None) -> dict:
    """Execute one registered case at one size and return its record."""
    import jax

    reps = reps if reps is not None else default_reps()
    case = spec.builder(log2n)
    label = size_key(spec.name, log2n)
    if spec.host:
        case.fn(*case.args)  # warm (allocator, functools caches)
        compile_s = 0.0
        cost = memory = None
        call = case.fn
    else:
        # the first call's cost before it ran, on jax's own clocks: the
        # delta of the trace + compile counters (telemetry/compile.py), so
        # the record and the /metrics series can never disagree
        before = _compile.seconds_total()
        jax.block_until_ready(case.fn(*case.args))
        compile_s = max(0.0, _compile.seconds_total() - before)
        raw = case.fn

        def call(*a):
            return jax.block_until_ready(raw(*a))

    times = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        call(*case.args)
        times.append(time.perf_counter() - t0)
    if not spec.host:
        cost, memory = _xla_introspect(case.fn, case.args)
    return make_record(
        kernel=spec.name,
        size=log2n,
        items=case.items,
        unit=spec.unit,
        seconds=times,
        compile_seconds=compile_s,
        cost=cost,
        memory=memory,
        host=spec.host,
    )


def make_record(
    *,
    kernel: str,
    size: int,
    items: int,
    unit: str,
    seconds,
    compile_seconds: float | None = None,
    cost: dict | None = None,
    memory: dict | None = None,
    host: bool = False,
    extra: dict | None = None,
) -> dict:
    """Build one standardized per-kernel record and mirror it into the
    metrics registry — the single record shape `run_suite`, bench.py's
    `kernels` section, and `tools/benchgate` all share, so the emitters
    cannot drift. `seconds` is a list of warm rep timings (or a single
    float for marginal-cost emitters like bench.py)."""
    times = [float(seconds)] if isinstance(seconds, (int, float)) \
        else [float(t) for t in seconds]
    med = statistics.median(times)
    iqr = 0.0
    if len(times) >= 4:
        q = statistics.quantiles(times, n=4)
        iqr = q[2] - q[0]
    rate = items / med if med > 0 else 0.0
    rec = {
        "schema": PERF_SCHEMA,
        "kernel": kernel,
        "size": size,
        "key": size_key(kernel, size),
        "items": items,
        "unit": unit,
        "reps": len(times),
        "median_seconds": med,
        "iqr_seconds": iqr,
        "min_seconds": min(times),
        "items_per_sec": rate,
        "compile_seconds": compile_seconds,
        "cost": cost,
        "memory": memory,
        "host": host,
    }
    # roofline attribution (telemetry/roofline.py): device records with a
    # cost model also say which roof they lean on and how hard — the
    # device/host split the bench line's "kernels" section reports
    rec["roofline"] = (
        _roofline.attribute(cost, med) if not host else None
    )
    if extra:
        rec.update(extra)
    sz = f"2e{size}"
    hist = _KERNEL_SECONDS.labels(kernel=kernel, size=sz)
    for t in times:
        hist.observe(t)
    _KERNEL_RATE.labels(kernel=kernel, size=sz).set(rate)
    if compile_seconds is not None:
        _KERNEL_COMPILE.labels(kernel=kernel, size=sz).set(compile_seconds)
    if cost is not None:
        _KERNEL_FLOPS.labels(kernel=kernel, size=sz).set(cost["flops"])
        _KERNEL_BYTES.labels(kernel=kernel, size=sz).set(
            cost["bytes_accessed"]
        )
    if rec["roofline"] is not None:
        _KERNEL_UTIL.labels(kernel=kernel, size=sz).set(
            rec["roofline"]["utilization"]
        )
    return rec


def run_suite(
    quick: bool = False,
    select: Sequence[str] | None = None,
    reps: int | None = None,
) -> dict:
    """Run every registered kernel (or the `select` subset) at its
    configured sizes and return the versioned suite document. A kernel
    that raises records an `error` entry instead of killing the suite —
    benchgate decides whether that's a regression (it had a baseline) or
    an advisory (it never worked here)."""
    import jax

    _ensure_defaults()
    if select:
        unknown = sorted(set(select) - set(_KERNELS))
        if unknown:
            raise KeyError(
                f"unknown perf kernel(s) {unknown}; "
                f"registered: {sorted(_KERNELS)}"
            )
    out = {
        "schema": PERF_SCHEMA,
        "platform": jax.default_backend(),
        "quick": bool(quick),
        # the peak table this run's roofline attribution used, so a
        # recorded document is self-describing (and re-attributable)
        "peaks": _roofline.peaks(),
        "kernels": {},
    }
    reps = reps if reps is not None else default_reps(quick)
    for name in sorted(_KERNELS):
        spec = _KERNELS[name]
        if select and name not in select:
            continue
        for log2n in (spec.quick_sizes if quick else spec.sizes):
            key = size_key(name, log2n)
            try:
                out["kernels"][key] = run_kernel(spec, log2n, reps=reps)
            except Exception as e:  # noqa: BLE001 — isolate per kernel
                out["kernels"][key] = {
                    "schema": PERF_SCHEMA,
                    "kernel": name,
                    "size": log2n,
                    "key": key,
                    "error": f"{type(e).__name__}: {e}",
                }
    return out
