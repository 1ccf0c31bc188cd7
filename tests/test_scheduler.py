"""Batching-scheduler tests (scheduler/ + the rewired worker pool;
docs/SCHEDULER.md).

Covers the acceptance ladder: (a) 8 concurrent same-circuit jobs through
POST /jobs/prove complete in <= 2 batched mesh executions and every proof
verifies, (b) a batch of 8 distinct witnesses demuxes proofs that
byte-match the sequential path, (c) two circuits interleaved never share
a batch (no cross-bucket batching), (d) a job cancelled while lingering
in a bucket never executes — plus unit tests for the Bucketer's
size/linger release rules, the DevicePool's lease accounting (including
mixed party counts over one inventory), and the jitted-prover LRU.
"""

import asyncio
import time
from types import SimpleNamespace

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from distributed_groth16_tpu.api.server import ApiServer
from distributed_groth16_tpu.api.store import CircuitStore
from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
from distributed_groth16_tpu.models.groth16 import (
    CompiledR1CS,
    pack_proving_key,
    setup,
    verify,
)
from distributed_groth16_tpu.models.groth16.prove import prove_single
from distributed_groth16_tpu.ops.constants import R
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.parallel.mesh import make_mesh
from distributed_groth16_tpu.parallel.pss import PackedSharingParams
from distributed_groth16_tpu.scheduler import (
    BatchScheduler,
    Bucketer,
    BucketKey,
    DevicePool,
    ProverCache,
    prove_batch,
)
from distributed_groth16_tpu.scheduler.batch_prover import _next_pow2
from distributed_groth16_tpu.service import JobQueue, ProofJob
from distributed_groth16_tpu.service.jobs import JobState
from distributed_groth16_tpu.utils.config import SchedulerConfig, ServiceConfig

POLL_DEADLINE_S = 300.0
CHAIN_LEN = 7


def _key(cid="c1", kind="prove", m=16, ni=2, l=2):
    return BucketKey(
        kind=kind, circuit_id=cid, curve="bn254",
        domain_size=m, num_inputs=ni, l=l,
    )


def _job(cid="c1", kind="prove", l=2):
    return ProofJob(kind=kind, circuit_id=cid, fields={}, l=l)


def chain_witness(x0: int, length: int = CHAIN_LEN) -> list[int]:
    """A satisfying assignment for mult_chain_circuit(<any>, length) with
    chain start x0 — the SAME r1cs admits every chain start, which is how
    one circuit gets many distinct witnesses."""
    vals = [x0 % R]
    for _ in range(length):
        v = vals[-1]
        vals.append((v * v + v) % R)
    return [1, vals[-1]] + vals[:-1]


# -- bucketer units ----------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_bucketer_releases_full_batch_and_keeps_buckets_apart():
    clk = _Clock()
    b = Bucketer(batch_max=3, linger_s=5.0, clock=clk)
    k1, k2 = _key("c1"), _key("c2")
    assert b.add(_job("c1"), k1) is None
    assert b.add(_job("c2"), k2) is None
    assert b.add(_job("c1"), k1) is None
    assert len(b) == 3
    batch = b.add(_job("c1"), k1)  # third c1 job fills the bucket
    assert batch is not None and batch.reason == "full"
    assert len(batch.jobs) == 3
    assert all(j.circuit_id == "c1" for j in batch.jobs)
    # c2's lone job still lingers — full release never crosses buckets
    assert len(b) == 1 and b.occupancy() == {k2.label: 1}


def test_bucketer_distinct_shapes_never_share_a_bucket():
    b = Bucketer(batch_max=2, linger_s=5.0, clock=_Clock())
    # same circuit id but different kind / l / domain size: all distinct
    assert b.add(_job("c1", kind="prove"), _key("c1", kind="prove")) is None
    assert b.add(_job("c1", kind="mpc_prove"),
                 _key("c1", kind="mpc_prove")) is None
    assert b.add(_job("c1", l=4), _key("c1", l=4)) is None
    assert b.add(_job("c1"), _key("c1", m=32)) is None
    assert len(b) == 4 and len(b.occupancy()) == 4


def test_bucketer_linger_deadline_and_flush():
    clk = _Clock()
    b = Bucketer(batch_max=8, linger_s=2.0, clock=clk)
    b.add(_job("c1"), _key("c1"))
    clk.t += 1.0
    b.add(_job("c2"), _key("c2"))
    assert b.next_deadline() == pytest.approx(1002.0)
    assert b.pop_expired() == []  # nothing expired yet
    clk.t = 1002.5  # c1 past its deadline, c2 not
    released = b.pop_expired()
    assert len(released) == 1 and released[0].reason == "linger"
    assert released[0].jobs[0].circuit_id == "c1"
    assert b.next_deadline() == pytest.approx(1003.0)
    flushed = b.flush()
    assert len(flushed) == 1 and flushed[0].reason == "flush"
    assert len(b) == 0 and b.next_deadline() is None


def test_bucketer_slo_shortens_linger_for_aged_jobs():
    """Deadline-aware release (docs/FLEET.md): with an SLO target, a job
    that already burned queue-wait lingers LESS — the bucket may only
    wait while the oldest member's age stays under half the target. Both
    clocks are injected, so no sleeping."""
    clk = _Clock()  # the bucketer's monotonic clock (deadline units)
    wall = {"t": 5000.0}  # job-age clock

    def age_of(job):
        return wall["t"] - job.created_at

    b = Bucketer(
        batch_max=8, linger_s=10.0, clock=clk,
        slo_target_s=60.0, age_of=age_of,
    )
    # a FRESH job gets the full linger: wait budget 30s >> linger 10s
    fresh = _job("c1")
    fresh.created_at = wall["t"]
    assert b.add(fresh, _key("c1")) is None
    assert b.next_deadline() == pytest.approx(1010.0)

    # an AGED job (28s old, 2s of wait budget left) joining the SAME
    # bucket tightens the shared deadline to its remaining budget
    aged = _job("c1")
    aged.created_at = wall["t"] - 28.0
    assert b.add(aged, _key("c1")) is None
    assert b.next_deadline() == pytest.approx(1002.0)
    assert b.pop_expired() == []
    clk.t = 1002.5
    released = b.pop_expired()
    assert len(released) == 1 and len(released[0].jobs) == 2

    # an OVERDUE job (past half the target) gets zero linger: it
    # releases on the very next tick instead of waiting out the linger
    overdue = _job("c2")
    overdue.created_at = wall["t"] - 45.0
    assert b.add(overdue, _key("c2")) is None
    assert b.next_deadline() == pytest.approx(clk.t)
    assert len(b.pop_expired()) == 1

    # without an SLO target the aged job would have lingered fully —
    # the pre-fleet behavior is preserved when the knob is off
    b_off = Bucketer(batch_max=8, linger_s=10.0, clock=clk, age_of=age_of)
    b_off.add(aged, _key("c1"))
    assert b_off.next_deadline() == pytest.approx(clk.t + 10.0)


# -- placement units ---------------------------------------------------------


def test_device_pool_lease_accounting_and_waiting():
    async def run():
        pool = DevicePool(devices=[object() for _ in range(8)])
        assert pool.capacity(4) == 2 and pool.capacity(8) == 1
        a = await pool.acquire(4)
        c = await pool.acquire(4)
        assert {id(d) for d in a.devices}.isdisjoint(
            {id(d) for d in c.devices}
        )
        waiter = asyncio.ensure_future(pool.acquire(4))
        await asyncio.sleep(0.02)
        assert not waiter.done()  # both slices busy — third lease parks
        a.release()
        lease = await asyncio.wait_for(waiter, 5)
        assert lease.slot == a.slot
        lease.release()
        c.release()
        assert pool.stats()["leasesInUse"] == 0

    asyncio.run(run())


def test_device_pool_mixed_party_counts_never_overlap():
    async def run():
        pool = DevicePool(devices=[object() for _ in range(8)])
        small = await pool.acquire(4)  # holds devices 0-3
        big = asyncio.ensure_future(pool.acquire(8))
        await asyncio.sleep(0.02)
        # an 8-party mesh needs ALL devices — it must wait, not overlap
        assert not big.done()
        small.release()
        lease = await asyncio.wait_for(big, 5)
        assert len(lease.devices) == 8
        lease.release()

    asyncio.run(run())


def test_device_pool_max_meshes_caps_concurrency():
    async def run():
        pool = DevicePool(devices=[object() for _ in range(8)], max_meshes=1)
        assert pool.capacity(4) == 1
        a = await pool.acquire(4)
        waiter = asyncio.ensure_future(pool.acquire(4))
        await asyncio.sleep(0.02)
        assert not waiter.done()  # free devices exist, but the cap binds
        a.release()
        (await asyncio.wait_for(waiter, 5)).release()

    asyncio.run(run())


def test_prover_cache_lru_and_next_pow2():
    cache = ProverCache(capacity=2)
    built = []
    for key in ("a", "b", "a", "c"):
        cache.get_or_build(key, lambda k=key: built.append(k) or f"fn-{k}")
    assert built == ["a", "b", "c"]  # "a" reused; "c" evicted "b"
    assert cache.hits == 1 and cache.misses == 3
    cache.get_or_build("b", lambda: built.append("b2") or "fn-b2")
    assert built[-1] == "b2"
    assert [_next_pow2(x) for x in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]


# -- scheduler plumbing (stub prover — no JAX work) --------------------------


class _StubExecutor:
    class _Store:
        def load(self, cid):
            return (SimpleNamespace(num_instance=2),
                    SimpleNamespace(domain_size=16))

    store = _Store()


class _StubBatchProver:
    def __init__(self):
        self.batches = []
        self.provers = ProverCache()

    def run_batch(self, jobs, key, mesh):
        self.batches.append((key.circuit_id, [j.id for j in jobs]))
        return [
            (j, {"circuitId": j.circuit_id, "proof": [], "phases": {}})
            for j in jobs
        ]


def _stub_scheduler(queue, **cfg_kw):
    cfg = SchedulerConfig(**{"batch_max": 4, "batch_linger_ms": 60000.0,
                             **cfg_kw})
    sched = BatchScheduler(
        _StubExecutor(), queue, cfg, devices=[object() for _ in range(8)]
    )
    sched.batch_prover = _StubBatchProver()
    return sched


async def _settle(sched):
    while sched._batch_tasks:
        await asyncio.gather(*list(sched._batch_tasks),
                             return_exceptions=True)


def test_scheduler_interleaved_circuits_never_share_a_batch():
    async def run():
        q = JobQueue(bound=64, workers=2)
        sched = _stub_scheduler(q)
        await sched.start()
        try:
            jobs = []
            for i in range(8):  # c1, c2, c1, c2, ... interleaved
                job = _job(cid=f"c{i % 2 + 1}")
                q.submit(job)
                await q.get()
                jobs.append(job)
            for job in jobs:
                await sched.offer(job)
            await _settle(sched)
            batches = sched.batch_prover.batches
            assert len(batches) == 2  # each bucket filled exactly once
            for cid, ids in batches:
                members = [j for j in jobs if j.id in ids]
                assert len(members) == 4
                assert all(j.circuit_id == cid for j in members)
            assert all(j.state is JobState.DONE for j in jobs)
            assert sched.jobs_batched == 8
        finally:
            await sched.stop()

    asyncio.run(run())


def test_job_cancelled_while_lingering_never_enters_a_batch():
    async def run():
        q = JobQueue(bound=64, workers=2)
        sched = _stub_scheduler(q)
        await sched.start()
        try:
            victim = _job()
            q.submit(victim)
            await q.get()
            await sched.offer(victim)
            assert len(sched.bucketer) == 1  # lingering, far from full
            # DELETE while lingering: QUEUED flips to CANCELLED at once
            assert q.cancel(victim.id).state is JobState.CANCELLED
            # the bucket now fills and releases — WITHOUT the victim
            rest = []
            for _ in range(3):
                job = _job()
                q.submit(job)
                await q.get()
                await sched.offer(job)
                rest.append(job)
            await _settle(sched)
            assert len(sched.batch_prover.batches) == 1
            _, ids = sched.batch_prover.batches[0]
            assert victim.id not in ids and len(ids) == 3
            assert victim.state is JobState.CANCELLED
            assert all(j.state is JobState.DONE for j in rest)
        finally:
            await sched.stop()

    asyncio.run(run())


def test_scheduler_stop_fails_lingering_jobs_terminally():
    async def run():
        q = JobQueue(bound=64, workers=2)
        sched = _stub_scheduler(q)
        await sched.start()
        job = _job()
        q.submit(job)
        await q.get()
        await sched.offer(job)
        await sched.stop()
        assert job.state is JobState.FAILED
        assert "shutting down" in job.error["message"]

    asyncio.run(run())


# -- batched proving correctness (needs the 8-device virtual mesh) -----------


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_prove_batch_of_8_byte_matches_sequential_path():
    """The satellite correctness bar: 8 same-circuit jobs with DISTINCT
    witnesses proved as ONE batch must each verify and byte-match the
    sequential (prove_single) proof for the same witness."""
    cs = mult_chain_circuit(3, CHAIN_LEN)
    r1cs, _ = cs.finish()
    pp = PackedSharingParams(2)
    pk = setup(r1cs, seed=5)
    comp = CompiledR1CS(r1cs)
    crs = pack_proving_key(pk, pp)
    F = fr()
    witnesses = [chain_witness(x0) for x0 in range(3, 11)]
    for z in witnesses:
        assert r1cs.is_satisfied(z)
    mesh = make_mesh(pp.n)
    proofs = prove_batch(
        pk, comp, pp, mesh, crs, [F.encode(z) for z in witnesses]
    )
    assert len(proofs) == 8
    ni = r1cs.num_instance
    for z, proof in zip(witnesses, proofs):
        assert verify(pk.vk, proof, z[1:ni])
        oracle = prove_single(pk, comp, F.encode(z))
        assert proof.a == oracle.a
        assert proof.b == oracle.b
        assert proof.c == oracle.c
    # distinct witnesses produce distinct proofs — no demux mix-up
    assert len({(p.a, p.b) for p in proofs}) == 8


# -- full stack: the acceptance criterion ------------------------------------


@pytest.fixture(scope="module")
def circuit(tmp_path_factory):
    cs = mult_chain_circuit(9, CHAIN_LEN)
    r1cs, z = cs.finish()
    root = str(tmp_path_factory.mktemp("sched_store"))
    cid = CircuitStore(root).save_circuit("sched", write_r1cs(r1cs), b"")
    publics = [str(x) for x in z[1:r1cs.num_instance]]
    return root, cid, write_wtns(z), publics


async def _poll_terminal(client, job_id):
    deadline = time.monotonic() + POLL_DEADLINE_S
    while time.monotonic() < deadline:
        resp = await client.get(f"/jobs/{job_id}")
        body = await resp.json()
        assert resp.status == 200, body
        if body["state"] in ("DONE", "FAILED", "CANCELLED"):
            return body
        await asyncio.sleep(0.05)
    raise AssertionError(f"job {job_id} never reached a terminal state")


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_eight_jobs_complete_in_at_most_two_batched_executions(circuit):
    root, cid, wtns, publics = circuit

    async def run():
        server = ApiServer(
            CircuitStore(root),
            ServiceConfig(workers=2, queue_bound=64, crs_cache_size=8),
            SchedulerConfig(batch_max=4, batch_linger_ms=500.0),
        )
        assert server.scheduler is not None
        runs = []
        real = server.scheduler.batch_prover.run_batch

        def counting(jobs, key, mesh):
            runs.append((key.circuit_id, [j.id for j in jobs]))
            return real(jobs, key, mesh)

        server.scheduler.batch_prover.run_batch = counting
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
                return body["jobId"]

            job_ids = await asyncio.gather(*[submit() for _ in range(8)])
            proofs = set()
            for jid in job_ids:
                status = await _poll_terminal(client, jid)
                assert status["state"] == "DONE", status
                resp = await client.get(f"/jobs/{jid}/result")
                result = await resp.json()
                assert resp.status == 200, result
                proofs.add(bytes(result["proof"]))
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

            # the acceptance bar: <= 2 batched mesh executions for 8 jobs
            assert len(runs) <= 2, runs
            assert sum(len(ids) for _, ids in runs) == 8
            assert all(c == cid for c, _ in runs)  # homogeneous batches
            assert len(proofs) == 1  # deterministic: same witness, 1 proof

            resp = await client.get("/stats")
            stats = await resp.json()
            sched = stats["scheduler"]
            # 8 proves in <= 2 mesh executions (the `runs` bar above); the
            # 8 /verify_proof wrapper jobs ride their own verify buckets
            # and add at most one dispatch each (docs/VERIFY.md)
            assert sched["enabled"] and sched["batchesDispatched"] <= 10
            assert sched["jobsBatched"] == 16
            assert stats["queue"]["completed"] == 16
            # each batch took its circuit from the executor's resident
            # entries: read from disk once, whatever the batches
            resident = stats["circuitCache"]
            assert resident["misses"] == 1
            assert resident["hits"] == len(runs) - 1

            # the batch-size histogram is live on /metrics
            resp = await client.get("/metrics")
            text = await resp.text()
            assert "scheduler_batch_size_count" in text
            assert "scheduler_batch_amortized_seconds" in text
        finally:
            await client.close()

    asyncio.run(run())


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_batching_disabled_keeps_per_job_funnel(circuit):
    """DG16_BATCH_MAX <= 1 must leave PR 2's per-job path untouched: no
    scheduler object, /stats reports it disabled, and proofs still flow."""
    root, cid, wtns, _ = circuit

    async def run():
        server = ApiServer(
            CircuitStore(root),
            ServiceConfig(workers=1),
            SchedulerConfig(batch_max=1),
        )
        assert server.scheduler is None
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            resp = await client.post(
                "/jobs/prove",
                data={"circuit_id": cid, "witness_file": wtns},
            )
            jid = (await resp.json())["jobId"]
            status = await _poll_terminal(client, jid)
            assert status["state"] == "DONE", status
            stats = await (await client.get("/stats")).json()
            assert stats["scheduler"] == {"enabled": False}
        finally:
            await client.close()

    asyncio.run(run())
