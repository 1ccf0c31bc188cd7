"""Device observatory tests (telemetry/profiler.py, devmem.py,
transfer.py, buildinfo.py; docs/OBSERVABILITY.md "Device
observatory").

Covers the ISSUE 14 ladder: on-demand XLA capture lifecycle (single
flight, bounded duration, downloadable artifact, the span ->
TraceAnnotation bridge and its zero-overhead-off guard), device-memory
sampling (None-safe on XLA:CPU, gauge export with a fake stats-bearing
device), transfer accounting, build-info exposure on /metrics + /readyz,
the flight-dump memory snapshot, and the job-DTO deviceMemory stamp.

The registry is process-wide: numeric checks compare deltas, not
absolutes.
"""

import asyncio
import io
import json
import tarfile
import time

import pytest

from distributed_groth16_tpu.telemetry import (
    buildinfo,
    devmem,
    flight,
    profiler,
    tracing,
    transfer,
)
from distributed_groth16_tpu.telemetry import metrics as tm

REG = tm.registry()


# -- profiler lifecycle ------------------------------------------------------


def test_capture_produces_downloadable_artifact(tmp_path):
    import jax.numpy as jnp

    p = profiler.Profiler(str(tmp_path))
    cap = p.start(duration_s=0)  # manual stop
    with tracing.span("obs.work"):
        (jnp.arange(256.0) * 2).sum().block_until_ready()
    done = p.stop()
    assert done is cap and cap.state == "done"
    assert cap.artifact and cap.artifact_bytes > 0
    with tarfile.open(cap.artifact, "r:gz") as tar:
        names = tar.getnames()
    # the jax trace payload is inside: the xplane, and nothing converted
    # from it
    files = [n for n in names if "." in n.rsplit("/", 1)[-1]]
    assert len(files) == 1 and files[0].endswith(".xplane.pb"), names


def test_stop_writes_the_xplane_where_jax_would_and_frees_its_slot(tmp_path):
    """The stop keeps `plugins/profile/<run>/<host>.xplane.pb` (what
    TensorBoard and `benchmark/trace_reduce.py` open), readable and with
    the spans in it, and leaves jax's one profiler slot free."""
    import glob

    import jax
    import jax.numpy as jnp

    p = profiler.Profiler(str(tmp_path))
    cap = p.start(duration_s=0)
    with tracing.span("job", job="j-7", attrs={"kind": "prove"}):
        (jnp.arange(256.0) * 2).sum().block_until_ready()
    assert p.stop().state == "done"
    (found,) = glob.glob(f"{cap.directory}/plugins/profile/*/*")
    assert found.endswith(".xplane.pb")
    names = {
        ev.name
        for plane in jax.profiler.ProfileData.from_file(found).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    assert "job" in names
    # jax's own start works again at once: the session was let go
    jax.profiler.start_trace(str(tmp_path / "again"))
    jax.profiler.stop_trace()


def _wait_done(p: profiler.Profiler, cap_id: str, timeout: float = 15.0):
    """Poll until the capture leaves 'running' — the slot frees before
    the artifact pack finishes (exactly what GET /profile/{id}'s 202
    models), so tests poll the state like the CLI does."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        cap = p.get(cap_id)
        if cap is not None and cap.state != "running":
            return cap
        time.sleep(0.05)
    raise AssertionError(f"capture {cap_id} never finished")


def test_capture_single_flight_and_timer_stop(tmp_path):
    p = profiler.Profiler(str(tmp_path))
    cap = p.start(duration_s=0.3)
    with pytest.raises(profiler.ProfileBusyError):
        p.start(duration_s=0.3)
    assert _wait_done(p, cap.id).state == "done"  # the timer stopped it
    assert p.active() is None
    # the slot is free again
    cap2 = p.start(duration_s=0)
    assert p.stop().id == cap2.id


def test_capture_duration_clamped_to_max(tmp_path):
    p = profiler.Profiler(str(tmp_path), max_s=0.2)
    cap = p.start(duration_s=999.0)
    assert cap.duration_s == 0.2
    assert _wait_done(p, cap.id).state == "done"


# -- the span -> TraceAnnotation bridge --------------------------------------


class _FakeAnnotation:
    entered: list = []

    def __init__(self, name, attrs=None):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


def test_annotator_bridges_spans_and_idles_to_noop():
    # off: the PR 3 zero-overhead contract — a bare span is the shared
    # no-op singleton
    assert tracing.span("obs.idle") is tracing.NOOP
    _FakeAnnotation.entered.clear()
    tracing.set_annotator(_FakeAnnotation)
    try:
        s = tracing.span("obs.bridged")
        assert s is not tracing.NOOP
        with s:
            pass
        assert _FakeAnnotation.entered == ["obs.bridged"]
    finally:
        tracing.set_annotator(None)
    assert tracing.span("obs.idle2") is tracing.NOOP


def test_profiler_installs_and_removes_annotator(tmp_path):
    p = profiler.Profiler(str(tmp_path))
    p.start(duration_s=0)
    try:
        assert tracing._annotator is not None
        assert tracing.span("obs.live") is not tracing.NOOP
    finally:
        p.stop()
    assert tracing._annotator is None
    assert tracing.span("obs.after") is tracing.NOOP


# -- device memory -----------------------------------------------------------


class _FakeDevice:
    platform = "tpu"
    id = 0

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_devmem_sample_cpu_is_none_safe():
    # the real backend under tests is XLA:CPU: no stats, honest nulls
    doc = devmem.sample()
    assert doc and all(v is None for v in doc.values())
    assert devmem.peak_bytes() is None
    assert devmem.peak_delta(None, devmem.peak_bytes()) is None


def test_devmem_sample_exports_gauges_for_stats_backends():
    dev = _FakeDevice({
        "bytes_in_use": 100, "peak_bytes_in_use": 250, "bytes_limit": 1000,
    })
    doc = devmem.sample(devices=[dev])
    assert doc["tpu:0"] == {
        "inUseBytes": 100, "peakBytes": 250, "limitBytes": 1000,
    }
    snap = REG.snapshot()
    assert snap['device_memory_bytes{device="tpu:0",kind="in_use"}'] == 100
    assert snap['device_memory_bytes{device="tpu:0",kind="peak"}'] == 250
    assert snap['device_memory_bytes{device="tpu:0",kind="limit"}'] == 1000
    assert devmem.peak_bytes(devices=[dev, dev]) == 500


def test_devmem_peak_delta_math():
    assert devmem.peak_delta(100, 150) == {
        "peakBytes": 150, "peakDeltaBytes": 50,
    }
    assert devmem.peak_delta(None, 150)["peakDeltaBytes"] == 150
    assert devmem.peak_delta(100, None) is None


def test_job_dto_carries_device_memory_stamp():
    from distributed_groth16_tpu.service.jobs import ProofJob

    job = ProofJob(kind="prove", circuit_id="c", fields={})
    assert job.to_dict()["metrics"]["deviceMemory"] is None
    job.note_device_memory(None)  # the CPU answer: stays None
    assert job.to_dict()["metrics"]["deviceMemory"] is None
    job.note_device_memory({"peakBytes": 9, "peakDeltaBytes": 4})
    assert job.to_dict()["metrics"]["deviceMemory"]["peakDeltaBytes"] == 4


def test_flight_dump_attaches_device_memory_snapshot(tmp_path):
    rec = flight.configure(str(tmp_path))
    try:
        path = rec.dump("obs_test")
        assert path is not None
        doc = json.loads(open(path).read())
        assert "deviceMemory" in doc
        # CPU backend: per-device nulls, never fabricated zeros
        assert all(v is None for v in doc["deviceMemory"].values())
    finally:
        flight.disable()


# -- transfer accounting -----------------------------------------------------


def test_transfer_account_counts_bytes_and_seconds():
    import jax.numpy as jnp

    snap0 = REG.snapshot()
    x = jnp.arange(1024, dtype=jnp.uint32)
    with transfer.account("h2d") as t:
        t.add_tree((x, [x, x]))
    snap1 = REG.snapshot()
    key = 'device_transfer_bytes_total{direction="h2d"}'
    assert snap1[key] - snap0.get(key, 0) == 3 * x.nbytes
    ckey = 'transfer_seconds_count{direction="h2d"}'
    assert snap1[ckey] - snap0.get(ckey, 0) == 1
    # the nbytes hint path (no .add call)
    with transfer.account("d2h", nbytes=128):
        pass
    snap2 = REG.snapshot()
    dkey = 'device_transfer_bytes_total{direction="d2h"}'
    assert snap2[dkey] - snap1.get(dkey, 0) == 128


def test_tree_nbytes_ignores_non_arrays():
    import jax.numpy as jnp

    x = jnp.zeros((4, 16), dtype=jnp.uint32)
    assert transfer.tree_nbytes({"a": x, "b": [x, "str", 3]}) == 2 * x.nbytes
    assert transfer.tree_nbytes(None) == 0


# -- build info --------------------------------------------------------------


def test_build_info_doc_and_gauge():
    from distributed_groth16_tpu import __version__

    doc = buildinfo.build_info()
    assert doc["version"] == __version__
    assert doc["backend"] == "cpu"
    assert buildinfo.build_info() is doc  # resolved once
    text = REG.render_prometheus()
    assert f'dg16_build_info{{version="{__version__}"' in text


def test_fleet_top_renders_version_column():
    from distributed_groth16_tpu.api.cli import format_fleet_top

    stats = {
        "replicas": [
            {"replicaId": "r1", "state": "active", "score": 1.0,
             "queueDepth": 0, "running": 0, "maxBurnRate": 0.0,
             "openBreakers": 0, "version": "0.1.0"},
            {"replicaId": "r2", "state": "active", "score": 1.0,
             "queueDepth": 0, "running": 0, "maxBurnRate": 0.0,
             "openBreakers": 0, "version": "0.2.0"},
        ],
        "pending": 0, "handoffs": 0,
    }
    table = format_fleet_top(stats, "")
    lines = table.splitlines()
    assert "VER" in lines[0]
    assert "0.1.0" in lines[1] and "0.2.0" in lines[2]


# -- HTTP surface ------------------------------------------------------------


def test_profile_routes_and_readyz_build_info(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from distributed_groth16_tpu.api.server import ApiServer
    from distributed_groth16_tpu.api.store import CircuitStore
    from distributed_groth16_tpu.utils.config import ServiceConfig

    async def run():
        server = ApiServer(
            CircuitStore(str(tmp_path)), ServiceConfig(workers=1)
        )
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            ready = await (await client.get("/readyz")).json()
            assert ready["buildInfo"]["backend"] == "cpu"
            assert ready["buildInfo"]["version"]

            resp = await client.post("/profile", json={"durationS": 0.4})
            assert resp.status == 202
            cap_id = (await resp.json())["id"]
            # single-flight: a second POST is 409
            busy = await client.post("/profile", json={"durationS": 0.4})
            assert busy.status == 409
            # still running: 202 JSON, not bytes
            poll = await client.get(f"/profile/{cap_id}")
            assert poll.status == 202
            assert (await poll.json())["state"] == "running"
            # bounded: the timer stops it without another request
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                done = await client.get(f"/profile/{cap_id}")
                if done.status == 200 and not done.headers[
                    "Content-Type"
                ].startswith("application/json"):
                    break
                await asyncio.sleep(0.1)
            data = await done.read()
            assert data[:2] == b"\x1f\x8b"  # gzip magic
            with tarfile.open(fileobj=io.BytesIO(data), mode="r:gz") as tar:
                assert tar.getnames()
            # unknown id
            assert (await client.get("/profile/nope")).status == 404
            # history + stats
            status = await (await client.get("/profile")).json()
            assert any(c["id"] == cap_id for c in status["captures"])
            stats = await (await client.get("/stats")).json()
            assert stats["profiler"]["running"] is None
            # the background devmem sampler task is alive
            assert server._devmem_task is not None
            assert not server._devmem_task.done()
            text = await (await client.get("/metrics")).text()
            assert "profiler_captures_total" in text
            assert "dg16_build_info" in text
        finally:
            await client.close()

    asyncio.run(run())


def test_profile_bad_requests(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from distributed_groth16_tpu.api.server import ApiServer
    from distributed_groth16_tpu.api.store import CircuitStore
    from distributed_groth16_tpu.utils.config import ServiceConfig

    async def run():
        server = ApiServer(
            CircuitStore(str(tmp_path)), ServiceConfig(workers=1)
        )
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            resp = await client.post("/profile", json={"durationS": -1})
            assert resp.status == 400
            resp = await client.post(
                "/profile", data=b"{not json",
                headers={"Content-Type": "application/json"},
            )
            assert resp.status == 400
            # non-numeric / non-object payloads are 400s too, never a
            # 500 traceback (review regression)
            resp = await client.post("/profile", json={"durationS": None})
            assert resp.status == 400
            resp = await client.post("/profile", json=[1, 2])
            assert resp.status == 400
        finally:
            await client.close()

    asyncio.run(run())
