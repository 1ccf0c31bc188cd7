"""Roofline attribution: name each kernel compute- or memory-bound.

The perf registry already records XLA's own work accounting per compiled
kernel (`cost_analysis()` flops and bytes-accessed) next to measured warm
seconds; this module closes the loop against per-backend peak tables so
BENCH records and `dg16-cli perf roofline` can say not just *that* a
kernel is slow but *which wall it leans on* — the framing both "Enabling
AI ASICs for Zero Knowledge Proof" and the Versal MSM paper (PAPERS.md)
use for kernel optimization:

    arithmetic intensity  AI   = flops / bytes_accessed
    ridge intensity             = peak_flops / peak_bw
    bound                       = compute if AI >= ridge else memory
    utilization                 = achieved / roof-at-AI  (fraction of the
                                  binding roof, the honest "how much of
                                  the machine are we using" number)

Peaks come from `DG16_PEAK_FLOPS` / `DG16_PEAK_BW` when set, else a
device-kind table (TPU datasheet numbers). XLA:CPU (device kind "cpu")
gets a deliberately conservative host-class default, labelled
`default:cpu` — CPU utilization numbers are for TREND, the table is the
TPU contract. Any other device kind missing from the table is an error,
never a default. Attribution lands in every
device perf record (`record["roofline"]`), in the
`perf_kernel_utilization{kernel,size}` gauge, and in the
`dg16-cli perf roofline` table (docs/PERF.md "Roofline workflow").
"""

from __future__ import annotations

from ..utils import config as _config

# (device_kind prefix, peak flops/sec, peak memory bytes/sec) — datasheet
# numbers; matched by prefix against jax's device_kind string. The flops
# column is the dense-compute peak (bf16 for TPU): our u32 limb kernels
# cannot reach it, which is exactly what the utilization gauge should say.
PEAKS_BY_DEVICE_KIND: tuple = (
    ("TPU v5p", 459e12, 2.77e12),
    ("TPU v5 lite", 197e12, 8.2e11),  # v5e
    ("TPU v5e", 197e12, 8.2e11),
    ("TPU v4", 275e12, 1.2e12),
    ("TPU v3", 123e12, 9.0e11),
    ("TPU v2", 46e12, 7.0e11),
)

# host-class peaks for XLA:CPU only (device kind "cpu"): a few-core x86
# container — utilization against it is a trend signal, not a contract
CPU_DEVICE_KIND = "cpu"
CPU_PEAK_FLOPS = 1e11
CPU_PEAK_BW = 5e10


def device_kind() -> str:
    import jax

    return str(jax.devices()[0].device_kind)


def peaks(kind: str | None = None) -> dict:
    """The peak table one attribution run uses:
    {flops, bw, deviceKind, source} with source one of `env`,
    `device:<kind>`, `default:cpu`. Env knobs override per-field. Raises
    LookupError for an accelerator the table does not know."""
    kind = kind if kind is not None else device_kind()
    for prefix, flops, bw in PEAKS_BY_DEVICE_KIND:
        if kind.startswith(prefix):
            source = f"device:{prefix}"
            break
    else:
        if kind != CPU_DEVICE_KIND:
            raise LookupError(
                f"no peak-table row for device kind {kind!r} "
                "(telemetry/roofline.py PEAKS_BY_DEVICE_KIND)"
            )
        flops, bw = CPU_PEAK_FLOPS, CPU_PEAK_BW
        source = "default:cpu"
    env_flops = _config.env_float("DG16_PEAK_FLOPS", 0.0)
    env_bw = _config.env_float("DG16_PEAK_BW", 0.0)
    if env_flops > 0 or env_bw > 0:
        source = "env"
        if env_flops > 0:
            flops = env_flops
        if env_bw > 0:
            bw = env_bw
    return {"flops": flops, "bw": bw, "deviceKind": kind, "source": source}


def attribute(
    cost: dict | None, median_seconds: float, peak: dict | None = None
) -> dict | None:
    """One kernel's roofline attribution from its XLA cost_analysis and
    measured warm seconds; None when there is nothing to attribute (host
    kernel, no cost model, zero time)."""
    if not cost or median_seconds <= 0:
        return None
    flops = float(cost.get("flops") or 0.0)
    nbytes = float(cost.get("bytes_accessed") or 0.0)
    if flops <= 0 and nbytes <= 0:
        return None
    pk = peak if peak is not None else peaks()
    achieved_flops = flops / median_seconds
    achieved_bw = nbytes / median_seconds
    ridge = pk["flops"] / pk["bw"]
    if nbytes <= 0:
        bound = "compute"
        utilization = achieved_flops / pk["flops"]
    elif flops <= 0:
        bound = "memory"
        utilization = achieved_bw / pk["bw"]
    else:
        ai = flops / nbytes
        bound = "compute" if ai >= ridge else "memory"
        # the roof at this AI: min(peak_flops, AI * peak_bw) flops/sec
        roof = min(pk["flops"], ai * pk["bw"])
        utilization = achieved_flops / roof
    out = {
        "flops_per_sec": achieved_flops,
        "bytes_per_sec": achieved_bw,
        "arithmetic_intensity": (flops / nbytes) if nbytes > 0 else None,
        "ridge_intensity": ridge,
        "bound": bound,
        "utilization": utilization,
        "peak_flops": pk["flops"],
        "peak_bw": pk["bw"],
        "peak_source": pk["source"],
    }
    return out


def _fmt_rate(v: float | None, unit: str) -> str:
    if v is None:
        return "-"
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if v >= scale:
            return f"{v / scale:.2f}{suffix}{unit}"
    return f"{v:.2f}{unit}"


_COLUMNS = (
    "KERNEL", "SECONDS", "FLOP/S", "B/S", "AI", "UTIL%", "BOUND",
)


def format_table(run: dict, peak: dict | None = None) -> str:
    """The `dg16-cli perf roofline` table from a dg16-perf/1 run document.
    Pure string building (unit-testable): device records with a cost model
    get attribution rows (re-derived against `peak`, so a recorded run can
    be re-attributed under different peak tables); host/errored/costless
    records are footnoted, never silently dropped."""
    pk = peak if peak is not None else peaks()
    rows = [list(_COLUMNS)]
    skipped: list[str] = []
    for key in sorted(run.get("kernels", {})):
        rec = run["kernels"][key]
        if "error" in rec:
            skipped.append(f"{key} (errored)")
            continue
        if rec.get("host"):
            skipped.append(f"{key} (host kernel, no XLA cost model)")
            continue
        att = attribute(rec.get("cost"), rec.get("median_seconds", 0.0), pk)
        if att is None:
            skipped.append(f"{key} (no cost model)")
            continue
        ai = att["arithmetic_intensity"]
        rows.append([
            key,
            f"{rec['median_seconds']:.6g}",
            _fmt_rate(att["flops_per_sec"], ""),
            _fmt_rate(att["bytes_per_sec"], ""),
            f"{ai:.2f}" if ai is not None else "-",
            f"{att['utilization'] * 100:.3g}",
            att["bound"],
        ])
    widths = [max(len(r[i]) for r in rows) for i in range(len(_COLUMNS))]
    lines = [
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in rows
    ]
    lines.append(
        f"peaks: {_fmt_rate(pk['flops'], 'FLOP/s')} / "
        f"{_fmt_rate(pk['bw'], 'B/s')} "
        f"(ridge {pk['flops'] / pk['bw']:.2f} flop/byte, "
        f"{pk['source']}, device {pk['deviceKind']})"
    )
    for s in skipped:
        lines.append(f"  - {s}")
    return "\n".join(lines)
