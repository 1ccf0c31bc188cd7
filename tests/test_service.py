"""Proof-job service layer tests (service/ + the jobs API; docs/SERVICE.md).

Covers the acceptance ladder: (a) 8 concurrent submissions through a
2-worker pool all complete and verify, (b) admission control rejects past
the queue bound with HTTP 429 + retryAfter, (c) a cancelled QUEUED job
never runs, (d) repeat proofs on one circuit hit the packed-CRS cache
(exactly one pack_proving_key call) — plus unit tests for the LRU cache,
thread-safe PhaseTimings, the JobQueue, and the CLI's 429 surfacing; and
the SLO burn-rate plane (service/slo.py; docs/OBSERVABILITY.md "SLO
monitoring"): budget math, window expiry, exhaustion -> one flight dump an
episode, /stats + /slo + /metrics exposure.
"""

import asyncio
import json
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer
from test_telemetry import parse_prometheus

from distributed_groth16_tpu.api.server import ApiServer
from distributed_groth16_tpu.api.store import CircuitStore
from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
from distributed_groth16_tpu.service import (
    CrsCache,
    JobQueue,
    ProofJob,
    QueueFullError,
)
from distributed_groth16_tpu.telemetry import flight
from distributed_groth16_tpu.telemetry import metrics as tm
from distributed_groth16_tpu.utils.config import ServiceConfig, SLOConfig
from distributed_groth16_tpu.utils.timers import PhaseTimings

POLL_DEADLINE_S = 300.0


@pytest.fixture(scope="module")
def circuit(tmp_path_factory):
    """One saved circuit shared by every service test in this module."""
    cs = mult_chain_circuit(9, 7)  # the test_api e2e shape — MPC-proven
    r1cs, z = cs.finish()
    root = str(tmp_path_factory.mktemp("svc_store"))
    cid = CircuitStore(root).save_circuit("svc", write_r1cs(r1cs), b"")
    publics = [str(x) for x in z[1 : r1cs.num_instance]]
    return root, cid, write_wtns(z), publics


def _server(root, **cfg_kw) -> ApiServer:
    defaults = dict(workers=2, queue_bound=64, crs_cache_size=8)
    defaults.update(cfg_kw)
    return ApiServer(CircuitStore(root), ServiceConfig(**defaults))


async def _poll_terminal(client, job_id: str) -> dict:
    deadline = time.monotonic() + POLL_DEADLINE_S
    while time.monotonic() < deadline:
        resp = await client.get(f"/jobs/{job_id}")
        body = await resp.json()
        assert resp.status == 200, body
        if body["state"] in ("DONE", "FAILED", "CANCELLED"):
            return body
        await asyncio.sleep(0.05)
    raise AssertionError(f"job {job_id} never reached a terminal state")


def _run(coro):
    asyncio.run(coro)


# -- (a) concurrent submissions all complete and verify ----------------------


def test_eight_concurrent_jobs_two_workers(circuit):
    root, cid, wtns, publics = circuit

    async def run():
        server = _server(root, workers=2)
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            async def submit():
                resp = await client.post(
                    "/jobs/prove",
                    data={"circuit_id": cid, "witness_file": wtns},
                )
                body = await resp.json()
                assert resp.status == 202, body
                assert body["state"] == "QUEUED"
                return body["jobId"]

            job_ids = await asyncio.gather(*[submit() for _ in range(8)])
            assert len(set(job_ids)) == 8

            for jid in job_ids:
                status = await _poll_terminal(client, jid)
                assert status["state"] == "DONE", status
                resp = await client.get(f"/jobs/{jid}/result")
                result = await resp.json()
                assert resp.status == 200, result
                resp = await client.post(
                    "/verify_proof",
                    json={
                        "circuitId": cid,
                        "proof": result["proof"],
                        "publicInputs": publics,
                    },
                )
                body = await resp.json()
                assert resp.status == 200 and body["isValid"], body

            resp = await client.get("/stats")
            stats = await resp.json()
            # 8 prove jobs + 8 verify jobs: /verify_proof is now a
            # submit-and-await wrapper over the same queue (docs/VERIFY.md)
            assert stats["queue"]["completed"] == 16
            assert stats["queue"]["failed"] == 0
            assert stats["queue"]["phases"]  # aggregate timings merged

            resp = await client.get("/healthz")
            health = await resp.json()
            assert health["status"] == "ok" and health["workers"] == 2
        finally:
            await client.close()

    _run(run())


# -- (b)+(c) backpressure and cancellation -----------------------------------


class _BlockingExecutor:
    """Stands in for ProofExecutor: first job blocks until released, and
    every execution is counted — making queue/cancel states deterministic."""

    def __init__(self):
        self.started = threading.Event()
        self.release = threading.Event()
        self.ran: list[str] = []

    def run(self, job: ProofJob) -> dict:
        self.ran.append(job.id)
        self.started.set()
        assert self.release.wait(timeout=60)
        return {"circuitId": job.circuit_id, "proof": [], "phases": {}}


def test_queue_full_gets_429_with_retry_after(circuit):
    root, cid, wtns, _ = circuit

    async def run():
        server = _server(root, workers=1, queue_bound=2)
        blocker = _BlockingExecutor()
        server.pool.executor = blocker
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            async def submit():
                return await client.post(
                    "/jobs/prove",
                    data={"circuit_id": cid, "witness_file": wtns},
                )

            # first job occupies the single worker...
            resp = await submit()
            assert resp.status == 202
            await asyncio.to_thread(blocker.started.wait, 60)
            # ...two more fill the queue to its bound...
            for _ in range(2):
                assert (await submit()).status == 202
            # ...and the next submission is rejected with a hint
            resp = await submit()
            body = await resp.json()
            assert resp.status == 429, body
            assert body["retryAfter"] > 0
            assert body["queueBound"] == 2
            assert "Retry-After" in resp.headers

            # the legacy sync route funnels through the same queue
            resp = await client.post(
                "/create_proof_without_mpc",
                data={"circuit_id": cid, "witness_file": wtns},
            )
            assert resp.status == 429
            assert (await resp.json())["retryAfter"] > 0

            blocker.release.set()
        finally:
            await client.close()

    _run(run())


def test_cancelled_queued_job_never_runs(circuit):
    root, cid, wtns, _ = circuit

    async def run():
        server = _server(root, workers=1)
        blocker = _BlockingExecutor()
        server.pool.executor = blocker
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            data = {"circuit_id": cid, "witness_file": wtns}
            resp = await client.post("/jobs/prove", data=data)
            first = (await resp.json())["jobId"]
            await asyncio.to_thread(blocker.started.wait, 60)
            resp = await client.post("/jobs/prove", data=data)
            queued = (await resp.json())["jobId"]

            resp = await client.delete(f"/jobs/{queued}")
            body = await resp.json()
            assert resp.status == 200 and body["state"] == "CANCELLED"

            blocker.release.set()
            status = await _poll_terminal(client, first)
            assert status["state"] == "DONE"
            status = await _poll_terminal(client, queued)
            assert status["state"] == "CANCELLED"
            # the cancelled job's executor never fired
            assert blocker.ran == [first]
            resp = await client.get(f"/jobs/{queued}/result")
            assert resp.status == 410

            # unknown ids are 404s
            assert (await client.get("/jobs/nope")).status == 404
            assert (await client.delete("/jobs/nope")).status == 404
        finally:
            await client.close()

    _run(run())


# -- (d) packed-CRS cache ----------------------------------------------------


def test_crs_cache_packs_once_across_repeat_proofs(circuit, monkeypatch):
    root, cid, wtns, publics = circuit
    from distributed_groth16_tpu.service import worker as worker_mod

    calls = []
    real_pack = worker_mod.pack_proving_key

    def counting_pack(pk, pp, strip=False):
        calls.append(pp.l)
        return real_pack(pk, pp, strip=strip)

    monkeypatch.setattr(worker_mod, "pack_proving_key", counting_pack)

    async def run():
        server = _server(root, workers=2)
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            data = {"circuit_id": cid, "witness_file": wtns, "l": "2"}
            # N sequential proofs through the legacy sync route...
            proofs = []
            for _ in range(2):
                resp = await client.post(
                    "/create_proof_with_naive_mpc", data=data
                )
                body = await resp.json()
                assert resp.status == 200, body
                proofs.append(bytes(body["proof"]))
            assert proofs[0] == proofs[1]  # deterministic r = s = 0

            # ...and N concurrent via the jobs API, same circuit
            async def submit():
                resp = await client.post(
                    "/jobs/prove", data={**data, "mpc": "1"}
                )
                return (await resp.json())["jobId"]

            job_ids = await asyncio.gather(*[submit() for _ in range(3)])
            for jid in job_ids:
                status = await _poll_terminal(client, jid)
                assert status["state"] == "DONE", status

            assert calls == [2], f"pack_proving_key calls: {calls}"
            resp = await client.get("/stats")
            cache = (await resp.json())["crsCache"]
            assert cache["misses"] == 1 and cache["hits"] == 4
        finally:
            await client.close()

    _run(run())


def test_crs_cache_lru_eviction_and_key_isolation():
    cache = CrsCache(capacity=2)
    packs = []

    def mk(key):
        return lambda: packs.append(key) or f"packed-{key}"

    assert cache.get_or_pack(("c1", 2), mk(("c1", 2))) == "packed-('c1', 2)"
    # distinct packing params on one circuit are distinct entries
    assert cache.get_or_pack(("c1", 4), mk(("c1", 4))) == "packed-('c1', 4)"
    assert len(packs) == 2 and len(cache) == 2
    # hit refreshes recency
    cache.get_or_pack(("c1", 2), mk(("c1", 2)))
    assert len(packs) == 2
    # third key evicts the LRU entry — ("c1", 4), not the refreshed one
    cache.get_or_pack(("c2", 2), mk(("c2", 2)))
    assert ("c1", 2) in cache and ("c2", 2) in cache
    assert ("c1", 4) not in cache
    s = cache.stats()
    assert s["evictions"] == 1 and s["hits"] == 1 and s["misses"] == 3


def test_crs_cache_single_flight_under_threads():
    cache = CrsCache(capacity=4)
    calls = []

    def factory():
        calls.append(1)
        time.sleep(0.05)  # widen the race window
        return "value"

    results = []
    threads = [
        threading.Thread(
            target=lambda: results.append(
                cache.get_or_pack("hot", factory)
            )
        )
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == ["value"] * 8
    assert len(calls) == 1  # leader packed; followers waited
    assert cache.stats()["hits"] >= 7 or cache.stats()["misses"] == 1


def test_crs_cache_capacity_zero_disables_caching():
    cache = CrsCache(capacity=0)
    calls = []
    for _ in range(3):
        cache.get_or_pack("k", lambda: calls.append(1) or "v")
    assert len(calls) == 3 and len(cache) == 0


# -- shutdown + history ------------------------------------------------------


def test_pool_stop_preserves_finished_proof_and_fails_queued():
    from distributed_groth16_tpu.service import WorkerPool
    from distributed_groth16_tpu.service.jobs import JobState

    async def run():
        q = JobQueue(bound=10, workers=1)
        blocker = _BlockingExecutor()
        pool = WorkerPool(q, blocker, workers=1)
        await pool.start()
        j_running = q.submit(ProofJob(kind="prove", circuit_id="c", fields={}))
        await asyncio.to_thread(blocker.started.wait, 60)
        j_queued = q.submit(ProofJob(kind="prove", circuit_id="c", fields={}))

        stop_task = asyncio.ensure_future(pool.stop())
        await asyncio.sleep(0.1)  # let the cancellation reach the worker
        blocker.release.set()  # the running proof now completes
        await stop_task

        # the proof that finished during shutdown is a result, not a failure
        assert j_running.state is JobState.DONE
        assert j_running.result is not None
        # the job that never got a worker is terminal, not QUEUED forever
        assert j_queued.state is JobState.FAILED
        assert "shutting down" in j_queued.error["message"]
        assert blocker.ran == [j_running.id]

    asyncio.run(run())


def test_job_registry_evicts_old_terminal_jobs():
    async def run():
        q = JobQueue(bound=100, workers=1, history_bound=2)
        jobs = [
            q.submit(ProofJob(kind="prove", circuit_id="c", fields={"w": b"x"}))
            for _ in range(3)
        ]
        for job in jobs:
            await q.get()
            job.mark_running()
            q.on_started(job)
            job.mark_done({"proof": []})
            q.on_finished(job)
        # only the 2 most recent terminal jobs stay addressable...
        assert jobs[0].id not in q.jobs
        assert jobs[1].id in q.jobs and jobs[2].id in q.jobs
        # ...and terminal jobs drop their submission payload
        assert jobs[1].fields == {}

    asyncio.run(run())


# -- queue + timers units ----------------------------------------------------


def test_job_queue_admission_control():
    async def run():
        q = JobQueue(bound=2, workers=1, retry_after_s=7.0)
        for _ in range(2):
            q.submit(ProofJob(kind="prove", circuit_id="c", fields={}))
        with pytest.raises(QueueFullError) as ei:
            q.submit(ProofJob(kind="prove", circuit_id="c", fields={}))
        assert ei.value.retry_after_s == 7.0  # no runtime data yet
        assert ei.value.bound == 2 and ei.value.depth == 2
        assert q.stats()["rejected"] == 1

    asyncio.run(run())


def test_phase_timings_concurrent_record_and_merge():
    t = PhaseTimings()

    def hammer():
        for _ in range(1000):
            t.record("phase", 0.001)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t.snapshot()["phase"] == pytest.approx(8.0)

    agg = PhaseTimings()
    a = PhaseTimings({"pack": 1.0, "prove": 2.0})
    b = PhaseTimings({"prove": 0.5, "verify": 0.25})
    agg.merge(a).merge(b)
    assert agg.snapshot() == {"pack": 1.0, "prove": 2.5, "verify": 0.25}
    assert a.snapshot() == {"pack": 1.0, "prove": 2.0}  # sources untouched


# -- CLI 429 surfacing -------------------------------------------------------


class _FakeResp:
    def __init__(self, status_code, body):
        self.status_code = status_code
        self._body = body
        self.text = str(body)

    def json(self):
        return self._body


def test_cli_body_surfaces_429_retry_after():
    from distributed_groth16_tpu.api.cli import _body

    with pytest.raises(SystemExit) as ei:
        _body(
            _FakeResp(
                429, {"error": "job queue full (2/2 queued)", "retryAfter": 7.5}
            )
        )
    msg = str(ei.value)
    assert "busy" in msg and "7.5" in msg

    # 202 (job accepted) passes through; 500 still raises the error body
    assert _body(_FakeResp(202, {"jobId": "j"})) == {"jobId": "j"}
    with pytest.raises(SystemExit, match="boom"):
        _body(_FakeResp(500, {"error": "boom"}))


# -- SLO burn-rate plane -----------------------------------------------------


def _observe_jobs(kind: str, seconds: float, n: int) -> None:
    # the SAME registration the queue makes (idempotent by name/labels)
    fam = tm.registry().histogram(
        "job_seconds", "End-to-end job runtime (RUNNING to terminal), "
        "per kind", ("kind",),
    )
    child = fam.labels(kind=kind)
    for _ in range(n):
        child.observe(seconds)


def test_slo_targets_parse():
    t = SLOConfig.parse_targets("prove=30, mpc_prove=120")
    assert t == (("prove", 30.0), ("mpc_prove", 120.0))
    assert SLOConfig.parse_targets("") == ()
    with pytest.raises(ValueError):
        SLOConfig.parse_targets("prove")
    cfg = SLOConfig(target_s=10.0, targets=(("prove", 5.0),))
    assert cfg.target_for("prove") == 5.0
    assert cfg.target_for("other") == 10.0
    assert cfg.enabled
    assert not SLOConfig().enabled


def test_slo_burn_rate_math():
    from distributed_groth16_tpu.service.slo import SloMonitor

    clock = [0.0]
    cfg = SLOConfig(target_s=0.05, objective=0.9, window_s=1000.0,
                    sample_s=1.0)
    mon = SloMonitor(cfg, now=lambda: clock[0])  # baseline excludes history
    _observe_jobs("prove", 0.001, 9)
    clock[0] = 1.0
    doc = mon.sample()
    k = doc["kinds"]["prove"]
    assert k["windowTotal"] == 9 and k["windowBad"] == 0
    assert k["burnRate"] == 0.0 and k["budgetRemaining"] == 1.0
    assert not k["exhausted"]
    _observe_jobs("prove", 1.0, 1)  # misses the 50 ms target
    clock[0] = 2.0
    k = mon.sample()["kinds"]["prove"]
    assert k["windowTotal"] == 10 and k["windowBad"] == 1
    assert k["burnRate"] == pytest.approx(1.0)  # exactly on the 10% budget
    assert k["budgetRemaining"] == pytest.approx(0.0) and k["exhausted"]
    snap = tm.registry().snapshot()
    assert snap['slo_burn_rate{kind="prove"}'] == pytest.approx(1.0)


def test_slo_window_expires_old_samples():
    from distributed_groth16_tpu.service.slo import SloMonitor

    clock = [0.0]
    cfg = SLOConfig(target_s=0.05, objective=0.9, window_s=10.0,
                    sample_s=1.0)
    mon = SloMonitor(cfg, now=lambda: clock[0])
    _observe_jobs("mpc_prove", 1.0, 5)  # all bad
    clock[0] = 1.0
    assert mon.sample()["kinds"]["mpc_prove"]["windowBad"] == 5
    # the bad burst ages out of the window with no new traffic
    clock[0] = 50.0
    mon.sample()
    clock[0] = 51.0
    k = mon.sample()["kinds"]["mpc_prove"]
    assert k["windowBad"] == 0 and k["burnRate"] == 0.0


def test_slo_budget_exhaustion_writes_one_flight_dump(tmp_path):
    from distributed_groth16_tpu.service.slo import SloMonitor

    flight.configure(str(tmp_path))
    try:
        clock = [0.0]
        cfg = SLOConfig(target_s=0.05, objective=0.5, window_s=1000.0)
        mon = SloMonitor(cfg, now=lambda: clock[0])
        _observe_jobs("prove", 1.0, 4)  # 100% bad, 50% allowed -> overdrawn
        clock[0] = 1.0
        assert mon.sample()["kinds"]["prove"]["exhausted"]
        dumps = list(tmp_path.glob("*slo_budget_exhausted*.json"))
        assert len(dumps) == 1
        record = json.loads(dumps[0].read_text())
        assert record["extra"]["kind"] == "prove"
        assert record["extra"]["windowBad"] == 4
        # still exhausted on the next tick: same episode, no second dump
        clock[0] = 2.0
        mon.sample()
        assert len(list(tmp_path.glob("*slo_budget_exhausted*.json"))) == 1
        # recovery re-arms: budget heals, then a fresh burst dumps again
        _observe_jobs("prove", 0.001, 100)
        clock[0] = 3.0
        assert not mon.sample()["kinds"]["prove"]["exhausted"]
        _observe_jobs("prove", 1.0, 200)
        clock[0] = 4.0
        assert mon.sample()["kinds"]["prove"]["exhausted"]
        assert len(list(tmp_path.glob("*slo_budget_exhausted*.json"))) == 2
    finally:
        flight.disable()


def test_slo_routes_and_metrics_exposure(tmp_path):
    async def run():
        server = ApiServer(
            CircuitStore(str(tmp_path)),
            ServiceConfig(workers=1),
            slo_cfg=SLOConfig(target_s=30.0, targets=(("prove", 30.0),),
                              objective=0.99, sample_s=0.05),
        )
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            stats = await (await client.get("/stats")).json()
            assert stats["slo"]["enabled"] is True
            assert stats["slo"]["objective"] == 0.99
            slo = await (await client.get("/slo")).json()
            assert "prove" in slo["kinds"]
            assert slo["kinds"]["prove"]["targetS"] == 30.0
            text = await (await client.get("/metrics")).text()
            assert 'slo_burn_rate{kind="prove"}' in text
            assert "slo_budget_remaining" in text
            # the background sampler task is alive between requests
            await asyncio.sleep(0.1)
            assert server._slo_task is not None and not server._slo_task.done()
        finally:
            await client.close()

    asyncio.run(run())


def test_slo_disabled_by_default(tmp_path):
    async def run():
        server = ApiServer(
            CircuitStore(str(tmp_path)), ServiceConfig(workers=1),
            slo_cfg=SLOConfig(),
        )
        assert server.slo is None
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            stats = await (await client.get("/stats")).json()
            assert stats["slo"] == {"enabled": False}
            slo = await (await client.get("/slo")).json()
            assert slo == {"enabled": False}
        finally:
            await client.close()

    asyncio.run(run())


# -- the round's two shapes: one mesh program over several devices, the
#    star round on one ------------------------------------------------------


def _series(text: str, family: str, **labels) -> float:
    _, samples = parse_prometheus(text)
    return samples.get((family, tuple(labels.items())), 0.0)


def _party_spans(spans: list) -> list:
    out, stack = [], list(spans)
    while stack:
        node = stack.pop()
        if node["name"] == "prove.party":
            out.append(node)
        stack.extend(node.get("children", ()))
    return out


def _mpc_jobs_through_server(circuit, devices, jobs=2):
    """`jobs` l = 2 MPC jobs and one single-node job on one circuit,
    through the jobs API, with the executor holding `devices` (None: the
    executor's own inventory). Returns the MPC jobs' status DTOs and
    results, the single-node proof, and /metrics + /stats before/after."""
    root, cid, wtns, _ = circuit
    out = {}

    async def run():
        server = _server(root, workers=1)
        if devices is not None:
            server.executor.devices = list(devices)
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            async def one(**extra):
                resp = await client.post(
                    "/jobs/prove",
                    data={"circuit_id": cid, "witness_file": wtns, **extra},
                )
                jid = (await resp.json())["jobId"]
                status = await _poll_terminal(client, jid)
                assert status["state"] == "DONE", status
                result = await (await client.get(f"/jobs/{jid}/result")).json()
                return status, bytes(result["proof"])

            async def snapshot():
                metrics = await (await client.get("/metrics")).text()
                stats = await (await client.get("/stats")).json()
                return metrics, stats["crsCache"]

            out["single"] = (await one())[1]
            out["before"] = await snapshot()
            out["mpc"] = []
            for _ in range(jobs):
                out["mpc"].append(await one(mpc="1", l="2"))
                out.setdefault("after_first", await snapshot())
            out["after"] = await snapshot()
        finally:
            await client.close()

    _run(run())
    return out


def test_mpc_job_runs_one_mesh_program_over_four_devices(circuit):
    """Four devices in the executor's inventory, eight parties: two a
    device. The proof is the single-node proof, byte for byte; the job's
    phases hold the placement, its spans one `prove.party` a party on the
    device that holds it, and the mesh route moves once a job. The
    second job finds the CRS shares placed: no miss, nothing packed."""
    import jax

    devices = jax.devices()[:4]
    out = _mpc_jobs_through_server(circuit, devices)
    before, first, after = (out[k] for k in ("before", "after_first", "after"))
    for status, proof in out["mpc"]:
        assert proof == out["single"]
        assert {"packing.qap", "packing.crs", "packing.witness",
                "packing.check", "packing.place", "MPC Proof.round",
                "MPC Proof.reassemble"} <= set(status["phases"])
        parties = [p["attrs"] for p in _party_spans(status["metrics"]["spans"])]
        assert sorted(p["party"] for p in parties) == list(range(8))
        assert {p["party"]: p["device"] for p in parties} == {
            j: devices[j // 2].id for j in range(8)
        }

    def moved(a, b, **labels):
        return (_series(b[0], "kernel_route_total", **labels)
                - _series(a[0], "kernel_route_total", **labels))

    assert moved(before, after, kernel="mpc_round", path="mesh") == 2
    assert moved(before, after, kernel="mpc_round", path="star") == 0
    gathered = (_series(after[0], "mesh_gather_bytes_total")
                - _series(before[0], "mesh_gather_bytes_total"))
    assert gathered > 0 and gathered == 2 * (
        _series(first[0], "mesh_gather_bytes_total")
        - _series(before[0], "mesh_gather_bytes_total"))
    assert first[1]["misses"] == before[1]["misses"] + 1
    assert after[1]["misses"] == first[1]["misses"]
    assert after[1]["hits"] == first[1]["hits"] + 1


def test_mpc_job_on_one_device_takes_the_star_round(circuit):
    """On XLA:CPU the executor holds one device of the eight virtual ones:
    the same job runs the asyncio star round, with the same proof."""
    out = _mpc_jobs_through_server(circuit, None, jobs=1)
    (status, proof), = out["mpc"]
    assert proof == out["single"]
    assert "packing.place" not in status["phases"]
    assert len(_party_spans(status["metrics"]["spans"])) == 8
    a, b = out["before"][0], out["after"][0]
    assert (_series(b, "kernel_route_total", kernel="mpc_round", path="star")
            - _series(a, "kernel_route_total", kernel="mpc_round",
                      path="star")) == 1
    assert _series(b, "kernel_route_total", kernel="mpc_round",
                   path="mesh") == _series(
        a, "kernel_route_total", kernel="mpc_round", path="mesh")
