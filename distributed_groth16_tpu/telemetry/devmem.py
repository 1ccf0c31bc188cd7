"""Device memory telemetry: HBM state as gauges, snapshots, and deltas.

Nothing else in the repo can say what device memory looked like while a
job OOMed or a batch peaked: this module is the one reader of the backend
memory API everything else goes through:

  * `sample()` — read `memory_stats()` per device and export
    `device_memory_bytes{device,kind=in_use|peak|limit}` gauges. A
    background sampler (ApiServer, `DG16_DEVMEM_SAMPLE_S`) keeps the
    gauges fresh for scrapes.
  * `snapshot()` — the same read as a JSON-able document, never raising:
    attached to every flight-recorder post-mortem so an OOM post-mortem
    carries the HBM state.
  * `limit_bytes()` — the least `bytes_limit` of any device; the
    service's resident circuits are bounded by a share of it.
  * `peak_bytes()` — summed `peak_bytes_in_use`; the executor and batch
    prover bracket a job with it and stamp the peak DELTA into the
    ProofJob DTO (`metrics.deviceMemory`).

Every reader is None-safe by contract: XLA:CPU has no `memory_stats()`
(returns None), so CPU records carry nulls and nothing downstream may
assume numbers (docs/OBSERVABILITY.md "Device observatory").
"""

from __future__ import annotations

from . import metrics as _tm

_REG = _tm.registry()
_DEVICE_MEMORY = _REG.gauge(
    "device_memory_bytes",
    "Backend memory_stats() per device: bytes in use, process peak, and "
    "the allocator limit (absent on XLA:CPU, which reports no stats)",
    ("device", "kind"),
)

# gauge `kind` label -> memory_stats() key
_KINDS = (
    ("in_use", "bytes_in_use"),
    ("peak", "peak_bytes_in_use"),
    ("limit", "bytes_limit"),
)


def _devices():
    try:
        import jax

        return jax.devices()
    except Exception:  # noqa: BLE001 — no backend is "no data", not a fault
        return []


def _stats_of(dev) -> dict | None:
    try:
        return dev.memory_stats()
    except Exception:  # noqa: BLE001 — some backends raise instead of None
        return None


def device_label(dev) -> str:
    return f"{getattr(dev, 'platform', '?')}:{getattr(dev, 'id', 0)}"


def sample(devices=None) -> dict:
    """Read every device's memory stats, set the gauges, and return
    `{device_label: {inUseBytes, peakBytes, limitBytes} | None}` — None
    per device whose backend reports nothing (XLA:CPU)."""
    out: dict = {}
    for dev in (devices if devices is not None else _devices()):
        label = device_label(dev)
        stats = _stats_of(dev)
        if not stats:
            out[label] = None
            continue
        doc = {}
        for kind, key in _KINDS:
            v = stats.get(key)
            if v is None:
                continue
            doc[f"{_CAMEL[kind]}Bytes"] = int(v)
            _DEVICE_MEMORY.labels(device=label, kind=kind).set(float(v))
        out[label] = doc or None
    return out


_CAMEL = {"in_use": "inUse", "peak": "peak", "limit": "limit"}


def snapshot() -> dict:
    """`sample()` that never raises — the flight-dump attachment."""
    try:
        return sample()
    except Exception:  # noqa: BLE001 — telemetry must not become the fault
        return {}


def peak_bytes(devices=None) -> int | None:
    """Summed `peak_bytes_in_use` across devices; None when no backend
    reports it (the CPU answer). Bracket a job with two calls and the
    difference is how much the job RAISED the process peak — zero for a
    job that fit inside already-reached headroom."""
    total = None
    for dev in (devices if devices is not None else _devices()):
        stats = _stats_of(dev)
        if not stats:
            continue
        v = stats.get("peak_bytes_in_use")
        if v is not None:
            total = (total or 0) + int(v)
    return total


def limit_bytes(devices=None) -> int | None:
    """The least `bytes_limit` any device reports: what one chip's
    allocator will hand out. None when no backend reports it (the CPU
    answer), and then nothing may be sized by it."""
    limits = [
        int(stats["bytes_limit"])
        for stats in map(
            _stats_of, devices if devices is not None else _devices()
        )
        if stats and stats.get("bytes_limit") is not None
    ]
    return min(limits) if limits else None


def peak_delta(before: int | None, after: int | None) -> dict | None:
    """The per-job stamp: {peakBytes, peakDeltaBytes} or None when the
    backend reports nothing (None-safe on XLA:CPU by construction)."""
    if after is None:
        return None
    return {
        "peakBytes": after,
        "peakDeltaBytes": after - (before or 0),
    }
