"""Fleet-plane tests (fleet/ + the router door; docs/FLEET.md).

Covers the acceptance ladder: (a) CHAOS — an in-process 3-replica fleet
behind the router serves 26 concurrent jobs across 3 tenants, one
replica is killed mid-flight (listener torn down, no cleanup — a crash,
not a drain), and every accepted job still completes with a VERIFYING
proof via journal-backed handoff; (b) per-tenant quotas reject excess
submissions with 429 + retryAfter (both in-flight and rate-bucket
flavors); (c) weighted-fair dequeue never starves the bulk class; plus
units for the replica registry's ejection breaker and scoring, the
/readyz capacity document + POST /drain admin path, replica-side
idempotent submission by job id, and the CLI fleet table.
"""

import asyncio
import json
import os
import threading
import time

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer
from test_telemetry import parse_prometheus

from distributed_groth16_tpu.api.server import ApiServer
from distributed_groth16_tpu.api.store import CircuitStore
from distributed_groth16_tpu.fleet import (
    FleetRouter,
    MetricsFederator,
    ReplicaRegistry,
    TenantAdmission,
    TenantQuotaError,
    TokenBucket,
    WeightedFairQueue,
)
from distributed_groth16_tpu.fleet.registry import ACTIVE, DRAINING, EJECTED
from distributed_groth16_tpu.fleet.router import ROUTER_PID
from distributed_groth16_tpu.service.journal import read_journal
from distributed_groth16_tpu.telemetry.metrics import MetricsRegistry
from distributed_groth16_tpu.frontend.ark_serde import proof_from_bytes
from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
from distributed_groth16_tpu.models.groth16 import verify
from distributed_groth16_tpu.service import ProofJob
from distributed_groth16_tpu.telemetry import logbus
from distributed_groth16_tpu.utils.config import (
    FleetConfig,
    ServiceConfig,
    TenantConfig,
)

POLL_DEADLINE_S = 300.0


@pytest.fixture(scope="module")
def circuit(tmp_path_factory):
    """One saved circuit in ONE store shared by every replica — the
    fleet topology's shared circuit store."""
    cs = mult_chain_circuit(9, 7)
    r1cs, z = cs.finish()
    root = str(tmp_path_factory.mktemp("fleet_store"))
    cid = CircuitStore(root).save_circuit("fleet", write_r1cs(r1cs), b"")
    publics = [int(x) for x in z[1 : r1cs.num_instance]]
    return root, cid, write_wtns(z), publics


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class _BlockingExecutor:
    """A replica that accepts but never finishes: every job it starts
    blocks until released — the doomed replica of the chaos scenario."""

    def __init__(self):
        self.release = threading.Event()
        self.started = 0

    def run(self, job: ProofJob) -> dict:
        self.started += 1
        assert self.release.wait(timeout=600)
        raise RuntimeError("doomed replica released at teardown")


class _ReplicaHarness:
    """One in-process replica on a real TCP port whose listener can be
    torn down WITHOUT running app cleanup — a crash, not a shutdown (no
    journal checkpoint, no drain, workers simply orphaned)."""

    def __init__(self, server, runner, site, url):
        self.server = server
        self.runner = runner
        self.site = site
        self.url = url

    async def kill_listener(self) -> None:
        await self.site.stop()

    async def cleanup(self) -> None:
        await self.runner.cleanup()


async def _start_replica(
    root, jdir, rid, workers=2, executor=None
) -> _ReplicaHarness:
    server = ApiServer(
        CircuitStore(root),
        ServiceConfig(
            workers=workers,
            journal_dir=str(jdir),
            journal_fsync=False,  # same-process chaos: flush suffices
            replica_id=rid,
        ),
    )
    if executor is not None:
        server.pool.executor = executor
    runner = web.AppRunner(server.app())
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = runner.addresses[0][1]
    return _ReplicaHarness(server, runner, site, f"http://127.0.0.1:{port}")


async def _poll_terminal(client, job_id: str) -> dict:
    deadline = time.monotonic() + POLL_DEADLINE_S
    while time.monotonic() < deadline:
        resp = await client.get(f"/jobs/{job_id}")
        body = await resp.json()
        if resp.status == 503:
            # the documented mid-outage answer ("replica unreachable,
            # handoff will re-route the job"): transient, poll on
            await asyncio.sleep(0.1)
            continue
        assert resp.status == 200, body
        if body["state"] in ("DONE", "FAILED", "CANCELLED"):
            return body
        await asyncio.sleep(0.1)
    raise AssertionError(f"job {job_id} never reached a terminal state")


# -- (a) the chaos acceptance scenario ---------------------------------------


def test_fleet_kill_replica_mid_flight_loses_no_accepted_job(
    circuit, tmp_path
):
    root, cid, wtns, publics = circuit
    _, pk = CircuitStore(root).load(cid)

    async def run():
        doomed_exec = _BlockingExecutor()
        jdirs = [tmp_path / f"j{i}" for i in range(3)]
        replicas = [
            await _start_replica(root, jdirs[0], "r-a", workers=2),
            await _start_replica(root, jdirs[1], "r-b", workers=2),
            await _start_replica(
                root, jdirs[2], "r-doomed", workers=2, executor=doomed_exec
            ),
        ]
        router = FleetRouter(
            FleetConfig(
                replicas=tuple(
                    (h.url, str(j)) for h, j in zip(replicas, jdirs)
                ),
                poll_s=0.2,
                eject_threshold=2,
                eject_cooldown_s=60.0,  # no readmission during the test
            ),
            # tenant t0 capped at 10 in-flight jobs; t1/t2 unlimited
            TenantConfig(limits=(("t0", None, None, 10),)),
        )
        client = TestClient(TestServer(router.app()))
        await client.start_server()
        try:
            async def submit(tenant, priority="interactive"):
                return await client.post(
                    "/jobs/prove",
                    data={"circuit_id": cid, "witness_file": wtns},
                    headers={
                        "X-DG16-Tenant": tenant,
                        "X-DG16-Priority": priority,
                    },
                )

            # 12 submissions for the quota'd tenant: exactly 10 admitted,
            # the excess 429s with a retryAfter hint (nothing can have
            # finished yet — proving takes seconds)
            accepted: list[str] = []
            quota_rejections = 0
            for i in range(12):
                resp = await submit("t0", "batch" if i % 3 else "interactive")
                body = await resp.json()
                if resp.status == 202:
                    accepted.append(body["jobId"])
                else:
                    assert resp.status == 429, body
                    assert body["reason"] == "inflight"
                    assert body["retryAfter"] > 0
                    assert "Retry-After" in resp.headers
                    quota_rejections += 1
            assert len(accepted) == 10 and quota_rejections == 2

            # two more tenants, mixed priorities — 26 accepted total
            for tenant in ("t1", "t2"):
                for i in range(8):
                    resp = await submit(
                        tenant, ("interactive", "batch", "bulk")[i % 3]
                    )
                    body = await resp.json()
                    assert resp.status == 202, body
                    accepted.append(body["jobId"])
            assert len(accepted) == 26

            # one MPC job alongside them: the acceptance trace must show
            # all THREE tiers (router / replica service / MPC parties),
            # which a single-prover job cannot (tracked separately — its
            # proof blob differs from the sequential path's)
            resp = await client.post(
                "/jobs/prove",
                data={"circuit_id": cid, "witness_file": wtns,
                      "mpc": "1", "l": "2"},
                headers={"X-DG16-Tenant": "t1"},
            )
            body = await resp.json()
            assert resp.status == 202, body
            mpc_jid, mpc_trace_id = body["jobId"], body["traceId"]
            assert mpc_trace_id

            # wait until the doomed replica owns dispatched jobs (its
            # blocking executor guarantees they cannot finish there)
            doomed = router.registry.replicas[2]
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                owned = [
                    j for j in router.jobs.values()
                    if j.replica is doomed and not j.terminal
                ]
                if owned:
                    break
                await asyncio.sleep(0.05)
            assert owned, "router never routed a job to the doomed replica"

            # CRASH: tear the listener down — no cleanup, no checkpoint,
            # journal left exactly as the crash left it
            await replicas[2].kill_listener()

            # discovery fails twice -> ejection -> journal handoff; every
            # accepted job must still finish DONE on a healthy replica
            for jid in accepted:
                status = await _poll_terminal(client, jid)
                assert status["state"] == "DONE", status
            mpc_status = await _poll_terminal(client, mpc_jid)
            assert mpc_status["state"] == "DONE", mpc_status
            # the trace id propagated router -> replica -> DTO
            assert mpc_status["traceId"] == mpc_trace_id

            # the STITCHED end-to-end trace: one Chrome trace, all three
            # tiers, one rebased clock (the acceptance criterion)
            resp = await client.get(f"/fleet/jobs/{mpc_jid}/trace")
            stitched = await resp.json()
            assert resp.status == 200, stitched
            # CI uploads the stitched trace next to the flight dumps on
            # failure — write it BEFORE asserting on its contents, so a
            # stitching regression leaves the artifact that debugs it
            art_dir = os.environ.get("DG16_FLIGHT_ARTIFACT_DIR")
            if art_dir:
                os.makedirs(art_dir, exist_ok=True)
                with open(
                    os.path.join(art_dir, f"fleet-trace-{mpc_jid}.json"),
                    "w",
                ) as fh:
                    json.dump(stitched, fh)
            assert stitched["traceId"] == mpc_trace_id
            evs = [e for e in stitched["traceEvents"]
                   if e.get("ph", "X") == "X"]
            names = {e["name"] for e in evs}
            # tier 1: the router's own spans
            router_evs = [e for e in evs if e.get("pid") == ROUTER_PID]
            assert {"fleet.admission", "fleet.queue",
                    "fleet.dispatch"} <= {e["name"] for e in router_evs}
            # tier 2: replica service phases (pid 0 harness spans)
            assert "job" in names and "load" in names
            # tier 3: MPC-party rounds on their own tracks
            party_pids = {int(e.get("pid", 0)) for e in evs} - {ROUTER_PID}
            assert len(party_pids) > 1 and max(party_pids) >= 1
            # one common clock: in-process tiers share the perf_counter
            # epoch, so the rebased spans must all land inside the test's
            # own lifetime window, not hours apart
            spread_us = (
                max(e["ts"] + e.get("dur", 0) for e in evs)
                - min(e["ts"] for e in evs)
            )
            assert spread_us < 30 * 60 * 1e6, spread_us
            # track metadata names every tier
            meta_names = {
                m["args"]["name"]
                for m in stitched["traceEvents"]
                if m.get("ph") == "M"
            }
            assert "fleet router" in meta_names
            assert any(n.startswith("replica ") for n in meta_names)

            # metrics federation: replica-labeled series + merged-
            # histogram rollups through a STRICT 0.0.4 parser (the
            # acceptance criterion's other half)
            resp = await client.get("/fleet/metrics")
            text = await resp.text()
            assert resp.status == 200
            types, samples = parse_prometheus(text)
            assert types["job_seconds"] == "histogram"
            scraped = {
                dict(labels).get("replica")
                for (name, labels) in samples
                if name == "job_seconds_count"
            }
            assert {"r-a", "r-b"} <= scraped  # the ejected one dropped out
            assert types["fleet_job_seconds"] == "histogram"
            fleet_count = sum(
                v for (name, labels), v in samples.items()
                if name == "fleet_job_seconds_count"
            )
            assert fleet_count >= len(accepted)
            assert samples[("fleet_replicas_scraped", ())] == 2.0
            assert ("fleet_jobs_per_second", ()) in samples
            assert types["fleet_job_quantile_seconds"] == "gauge"

            # zero lost, and the handoff actually moved work
            assert router.handoffs >= len(owned)
            assert doomed.state == EJECTED

            # EVERY accepted job's proof verifies: prove_single is
            # deterministic (r = s = 0) over one circuit + witness, so
            # all 26 blobs must be byte-identical — fetch them all,
            # then verify the blob cryptographically once (which covers
            # the handed-off jobs' proofs byte-for-byte)
            blobs = set()
            for jid in accepted:
                resp = await client.get(f"/jobs/{jid}/result")
                result = await resp.json()
                assert resp.status == 200, result
                blobs.add(bytes(result["proof"]))
            assert len(blobs) == 1
            proof = proof_from_bytes(blobs.pop())
            assert verify(pk.vk, proof, publics)

            # the fleet table tells the story
            resp = await client.get("/fleet/stats")
            stats = await resp.json()
            states = {r["replicaId"]: r["state"] for r in stats["replicas"]}
            assert states["r-doomed"] == "ejected"
            assert states["r-a"] == "active" and states["r-b"] == "active"
            assert stats["handoffs"] == router.handoffs
            # in-flight quota fully released once everything finished
            assert stats["tenants"]["inflightByTenant"] == {}
        finally:
            doomed_exec.release.set()
            await client.close()
            for h in replicas:
                await h.cleanup()

    asyncio.run(run())


# -- (b) tenant quotas at the door -------------------------------------------


def test_router_tenant_rate_limit_429_and_refill():
    async def run():
        router = FleetRouter(
            FleetConfig(replicas=(("http://127.0.0.1:1", None),)),
            TenantConfig(rate=1.0, burst=2),
        )
        client = TestClient(TestServer(router.app()))
        await client.start_server()
        try:
            async def submit(tenant):
                return await client.post(
                    "/jobs/prove",
                    data={"circuit_id": "c", "witness_file": b"x"},
                    headers={"X-DG16-Tenant": tenant},
                )

            # burst of 2 admitted, the third rejected by the rate bucket
            assert (await submit("acme")).status == 202
            assert (await submit("acme")).status == 202
            resp = await submit("acme")
            body = await resp.json()
            assert resp.status == 429, body
            assert body["reason"] == "rate" and body["retryAfter"] > 0
            # another tenant has its own bucket — unaffected
            assert (await submit("other")).status == 202
        finally:
            await client.close()

    asyncio.run(run())


def test_tenant_admission_units_and_release():
    clk = _Clock()
    adm = TenantAdmission(
        TenantConfig(rate=2.0, burst=2, inflight=3), clock=clk
    )
    adm.admit("t")
    adm.admit("t")
    with pytest.raises(TenantQuotaError) as ei:
        adm.admit("t")  # bucket empty (burst 2)
    assert ei.value.reason == "rate" and ei.value.retry_after_s > 0
    clk.t += 0.5  # one token refilled at 2/s
    adm.admit("t")  # 3 in flight now
    clk.t += 10.0  # bucket full again — the IN-FLIGHT quota bites next
    with pytest.raises(TenantQuotaError) as ei:
        adm.admit("t")
    assert ei.value.reason == "inflight"
    assert adm.stats()["inflightByTenant"]["t"] == 3
    # a finished job frees a slot; per-tenant overrides stay isolated
    adm.release("t")
    adm.admit("t")
    adm.admit("u")  # fresh tenant: own bucket, own slot count
    assert adm.stats()["inflightByTenant"] == {"t": 3, "u": 1}


def test_token_bucket_refill_math():
    clk = _Clock()
    b = TokenBucket(rate=2.0, burst=4, clock=clk)
    assert all(b.take() for _ in range(4))
    assert not b.take()
    assert b.retry_after_s() == pytest.approx(0.5)
    clk.t += 1.0  # 2 tokens back
    assert b.take() and b.take() and not b.take()
    clk.t += 100.0  # refill clamps at burst
    assert sum(b.take() for _ in range(10)) == 4


# -- (c) priority scheduling: weighted-fair, never starving ------------------


def test_weighted_fair_queue_no_starvation_and_tenant_rotation():
    q = WeightedFairQueue((("interactive", 8), ("batch", 3), ("bulk", 1)))
    for i in range(60):
        q.push("heavy", "interactive", ("interactive", "heavy", i))
    for i in range(12):
        q.push("small", "bulk", ("bulk", "small", i))
    for i in range(12):
        q.push("other", "bulk", ("bulk", "other", i))

    popped = [q.pop() for _ in range(36)]
    bulk_seen = [p for p in popped if p[0] == "bulk"]
    # weight 1 of 9 total: bulk gets ~1/9 of dispatches — throttled but
    # NEVER starved (the regression this test pins)
    assert len(bulk_seen) >= 3
    assert any(p[0] == "bulk" for p in popped[:10])
    # tenants inside a class alternate (round-robin), so one tenant's
    # backlog never shadows another's
    bulk_tenants = [p[1] for p in bulk_seen]
    assert "small" in bulk_tenants and "other" in bulk_tenants
    for a, b in zip(bulk_tenants, bulk_tenants[1:]):
        assert a != b

    # drain order: everything comes out, counts preserved
    rest = q.drain()
    assert len(rest) == 84 - 36 and len(q) == 0


def test_weighted_fair_queue_single_class_is_fifo_per_tenant():
    q = WeightedFairQueue()
    q.push("t", "batch", 1)
    q.push("t", "batch", 2)
    q.push("u", "batch", 3)
    assert q.pop() == 1
    assert q.pop() == 3  # tenant rotation
    assert q.pop() == 2
    assert q.pop() is None


# -- registry: scoring + ejection breaker ------------------------------------


def test_registry_scoring_prefers_low_load_and_low_burn():
    reg = ReplicaRegistry(
        (("http://a", None), ("http://b", None)), clock=_Clock()
    )
    a, b = reg.replicas
    reg.note_doc(a, {"replicaId": "a", "workers": 2, "queueDepth": 4,
                     "running": 2, "maxBurnRate": 0.0})
    reg.note_doc(b, {"replicaId": "b", "workers": 2, "queueDepth": 0,
                     "running": 1, "maxBurnRate": 0.0})
    assert reg.pick() is b
    # same load, but b is burning SLO budget 3x: a wins
    reg.note_doc(a, {"replicaId": "a", "workers": 2, "queueDepth": 1,
                     "running": 1, "maxBurnRate": 0.0})
    reg.note_doc(b, {"replicaId": "b", "workers": 2, "queueDepth": 1,
                     "running": 1, "maxBurnRate": 3.0})
    assert reg.pick() is a
    # a draining replica is out of rotation and flagged for handoff
    reg.note_doc(a, {"replicaId": "a", "draining": True})
    assert reg.pick() is b
    assert a.state == DRAINING and a in reg.needs_handoff()


def test_registry_ejection_breaker_cooldown_and_probe():
    clk = _Clock()
    reg = ReplicaRegistry(
        (("http://a", None),), eject_threshold=3, eject_cooldown_s=10.0,
        clock=clk,
    )
    (a,) = reg.replicas
    assert not reg.note_failure(a)
    assert not reg.note_failure(a)
    assert reg.note_failure(a)  # third consecutive failure ejects
    assert a.state == EJECTED and reg.pick() is None
    assert a in reg.needs_handoff()
    # cooling down: not polled, not routable
    assert reg.pollable() == []
    clk.t += 10.5
    # cooldown lapsed: exactly ONE probe poll
    assert reg.pollable() == [a] and a.probing
    assert reg.pollable() == []  # no second probe while one is out
    # failed probe re-opens the cooldown
    reg.note_failure(a)
    assert a.state == EJECTED and reg.pollable() == []
    clk.t += 10.5
    assert reg.pollable() == [a]
    # successful probe readmits and re-arms handoff for the NEXT outage
    reg.note_doc(a, {"replicaId": "a", "workers": 2})
    assert a.state == ACTIVE and not a.handoff_done
    assert reg.pick() is a


def test_registry_pre_adoption_ejection_count_migrates():
    """A replica ejected BEFORE its first successful poll counts its
    ejection under the config-URL label; first contact must carry that
    count to the adopted id, not split one replica across two series."""
    from distributed_groth16_tpu.telemetry import metrics as tm

    clk = _Clock()
    url = "http://migrate-me:1"
    reg = ReplicaRegistry(((url, None),), eject_threshold=2, clock=clk)
    (a,) = reg.replicas
    reg.note_failure(a)
    reg.note_failure(a)  # ejected under the URL label
    ej = tm.registry().family("fleet_replica_ejections_total")
    assert dict(ej.items())[(url,)].value == 1
    reg.note_doc(a, {"replicaId": "r-migrated", "workers": 1})
    series = dict(ej.items())
    assert (url,) not in series
    assert series[("r-migrated",)].value == 1


# -- /readyz capacity document + POST /drain ---------------------------------


def test_readyz_capacity_document_and_admin_drain(circuit):
    root, cid, wtns, _ = circuit

    async def run():
        server = ApiServer(
            CircuitStore(root),
            ServiceConfig(workers=2, replica_id="r-test"),
        )
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            resp = await client.get("/readyz")
            doc = await resp.json()
            assert resp.status == 200
            assert doc["replicaId"] == "r-test"
            assert doc["draining"] is False
            assert doc["workers"] == 2 and doc["queueDepth"] == 0
            assert doc["running"] == 0 and doc["queueBound"] == 64
            assert doc["maxBurnRate"] == 0.0
            assert doc["devices"] == 0 and doc["openBreakers"] == 0
            # no echo param -> no clock block (capacity doc stays lean)
            assert "clockEcho" not in doc

            # the clock echo: ?echo=<t0> answers {t0 echoed, t1 receipt,
            # t2 send} over perf_counter_ns — one NTP-style sample per
            # poll for the router's per-replica ClockSync
            resp = await client.get("/readyz", params={"echo": "12345"})
            echo = (await resp.json())["clockEcho"]
            assert echo["t0"] == 12345
            assert isinstance(echo["t1"], int)
            assert isinstance(echo["t2"], int)
            assert echo["t1"] <= echo["t2"]
            # a malformed echo is ignored, not a 500
            resp = await client.get("/readyz", params={"echo": "bogus"})
            assert resp.status == 200
            assert "clockEcho" not in await resp.json()

            # /healthz body keeps its pre-fleet shape exactly
            resp = await client.get("/healthz")
            health = await resp.json()
            assert set(health) == {"status", "workers", "queueDepth",
                                   "running"}

            # operator drain without SIGTERM: admission closes, readyz
            # 503s with the drain flag, healthz stays 200 (liveness)
            resp = await client.post("/drain")
            body = await resp.json()
            assert resp.status == 200 and body["status"] == "draining"
            assert body["alreadyDraining"] is False
            resp = await client.post("/drain")  # idempotent
            assert (await resp.json())["alreadyDraining"] is True

            resp = await client.get("/readyz")
            doc = await resp.json()
            assert resp.status == 503 and doc["draining"] is True
            assert (await client.get("/healthz")).status == 200
            resp = await client.post(
                "/jobs/prove",
                data={"circuit_id": cid, "witness_file": wtns},
            )
            assert resp.status == 503
        finally:
            await client.close()

    asyncio.run(run())


def test_fleet_drain_by_config_url_and_label_migration(circuit, tmp_path):
    """`dg16-cli fleet drain` accepts the config URL too — the route is
    `{replica:.+}` so the slashes match — and first contact migrates the
    URL-labeled gauge series to the replica's self-reported id (no
    phantom always-active series left behind)."""
    root, cid, wtns, _ = circuit

    async def run():
        h = await _start_replica(root, tmp_path / "j", "r-drain", workers=1)
        router = FleetRouter(
            FleetConfig(replicas=((h.url, str(tmp_path / "j")),), poll_s=0.1)
        )
        client = TestClient(TestServer(router.app()))
        await client.start_server()
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if router.registry.replicas[0].replica_id == "r-drain":
                    break
                await asyncio.sleep(0.05)
            assert router.registry.replicas[0].replica_id == "r-drain"
            from distributed_groth16_tpu.telemetry import metrics as tm

            states = dict(tm.registry().family("fleet_replica_state").items())
            assert (h.url,) not in states
            assert ("r-drain",) in states

            # drain by the CONFIG URL, slashes and all
            resp = await client.post(f"/fleet/drain/{h.url}")
            body = await resp.json()
            assert resp.status == 200, body
            assert body["replica"] == "r-drain"
            assert body["state"] == "draining"
            # unknown names still 404 through the wildcard route
            resp = await client.post("/fleet/drain/http://no/such")
            assert resp.status == 404
        finally:
            await client.close()
            await h.cleanup()

    asyncio.run(run())


# -- replica-side idempotent submission (the handoff-race guarantee) ---------


def test_submission_idempotent_by_job_id(circuit):
    root, cid, wtns, publics = circuit

    async def run():
        server = ApiServer(CircuitStore(root), ServiceConfig(workers=1))
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            data = {
                "circuit_id": cid,
                "witness_file": wtns,
                "job_id": "fixed-id-1",
            }
            resp = await client.post(
                "/jobs/prove", data=data,
                headers={"X-DG16-Tenant": "acme",
                         "X-DG16-Priority": "batch"},
            )
            body = await resp.json()
            assert resp.status == 202 and body["jobId"] == "fixed-id-1"
            # the re-submission race (router handoff vs the replica's own
            # replay): same id returns the SAME job, no second execution
            resp = await client.post("/jobs/prove", data=data)
            body2 = await resp.json()
            assert resp.status == 202 and body2["jobId"] == "fixed-id-1"
            assert server.queue.stats()["submitted"] == 1

            status = await _poll_terminal(client, "fixed-id-1")
            assert status["state"] == "DONE"
            assert status["tenant"] == "acme"
            assert status["priority"] == "batch"
        finally:
            await client.close()

    asyncio.run(run())


# -- CLI fleet table ----------------------------------------------------------


def test_cli_fleet_table_formatting():
    from distributed_groth16_tpu.api.cli import format_fleet_table

    table = format_fleet_table(
        {
            "replicas": [
                {
                    "replicaId": "r-a", "url": "http://a:8000",
                    "state": "active", "score": 1.25, "queueDepth": 3,
                    "running": 2, "workers": 4, "devices": 8,
                    "openBreakers": 0, "maxBurnRate": 0.1,
                },
                {
                    "replicaId": "r-b", "url": "http://b:8000",
                    "state": "ejected", "score": None, "queueDepth": None,
                    "running": None, "workers": None, "devices": None,
                    "openBreakers": None, "maxBurnRate": None,
                },
            ],
            "tenants": {"admitted": 30, "rejected": 2},
            "pending": 1,
            "handoffs": 5,
        }
    )
    lines = table.splitlines()
    assert lines[0].split()[:3] == ["REPLICA", "STATE", "SCORE"]
    assert "r-a" in lines[1] and "active" in lines[1]
    assert "r-b" in lines[2] and "ejected" in lines[2] and "-" in lines[2]
    assert "handoffs=5" in lines[-1] and "rejected=2" in lines[-1]


# -- fleet observatory: trace-id propagation through handoff ------------------


def test_trace_id_survives_journal_backed_handoff(circuit, tmp_path):
    """The satellite guarantee: a job re-submitted from a dead replica's
    journal keeps the router-minted trace_id — the WAL carries it, the
    handoff re-dispatch sends it in X-DG16-Trace, and the re-proving
    replica's DTO reports it."""
    root, cid, wtns, _ = circuit

    async def run():
        doomed_exec = _BlockingExecutor()
        jdirs = [tmp_path / "ja", tmp_path / "jb"]
        doomed = await _start_replica(
            root, jdirs[0], "r-x", workers=2, executor=doomed_exec
        )
        healthy = await _start_replica(root, jdirs[1], "r-y", workers=2)
        router = FleetRouter(
            FleetConfig(
                replicas=(
                    (doomed.url, str(jdirs[0])),
                    (healthy.url, str(jdirs[1])),
                ),
                poll_s=0.2,
                eject_threshold=2,
                eject_cooldown_s=60.0,
            )
        )
        client = TestClient(TestServer(router.app()))
        await client.start_server()
        try:
            traces = {}
            for _ in range(6):
                resp = await client.post(
                    "/jobs/prove",
                    data={"circuit_id": cid, "witness_file": wtns},
                )
                body = await resp.json()
                assert resp.status == 202, body
                assert body["traceId"]
                traces[body["jobId"]] = body["traceId"]

            # wait until the doomed replica owns a dispatched job (its
            # blocking executor guarantees it cannot finish there)
            drep = router.registry.replicas[0]
            victim = None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and victim is None:
                victim = next(
                    (
                        j for j in router.jobs.values()
                        if j.replica is drep and not j.terminal
                    ),
                    None,
                )
                await asyncio.sleep(0.05)
            assert victim is not None, "no job landed on the doomed replica"

            # the journaled submit record carries the trace id
            entry = None
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and entry is None:
                entry = {
                    e.id: e for e in read_journal(str(jdirs[0]))
                }.get(victim.id)
                await asyncio.sleep(0.05)
            assert entry is not None
            assert entry.trace_id == traces[victim.id]

            # crash the owner: ejection -> handoff -> re-prove elsewhere
            await doomed.kill_listener()
            status = await _poll_terminal(client, victim.id)
            assert status["state"] == "DONE", status
            # the re-submitted job kept its ORIGINAL trace_id
            assert status["traceId"] == traces[victim.id]
            # and the router-side handoff span is in the stitched trace
            resp = await client.get(f"/fleet/jobs/{victim.id}/trace")
            stitched = await resp.json()
            assert resp.status == 200, stitched
            names = {
                e.get("name") for e in stitched["traceEvents"]
                if e.get("pid") == ROUTER_PID
            }
            assert "fleet.handoff" in names
        finally:
            doomed_exec.release.set()
            await client.close()
            await doomed.cleanup()
            await healthy.cleanup()

    asyncio.run(run())


async def _job_logs_with_replica_error(client, jid, deadline_s=30.0):
    """GET /fleet/jobs/{id}/logs until it holds the replica's ERROR record
    of the job, or the deadline passes (then the asserts below say what
    is missing)."""
    deadline = time.monotonic() + deadline_s
    while True:
        resp = await client.get(f"/fleet/jobs/{jid}/logs")
        doc = await resp.json()
        assert resp.status == 200, doc
        if time.monotonic() > deadline or any(
            r["level"] == "ERROR" and r["source"].startswith("replica")
            for r in doc["records"]
        ):
            return doc
        await asyncio.sleep(0.2)


def test_fleet_job_logs_federates_router_and_replica_records(
    circuit, tmp_path
):
    """The logging-spine cross-tier case (docs/OBSERVABILITY.md "Logging
    spine"): a routed MPC job dies on the replica, and ONE query —
    `GET /fleet/jobs/{id}/logs` — returns the whole story under the
    router-minted trace id: the router's dispatch breadcrumb AND the
    replica's ERROR, every record rebased onto the router clock.

    The spine is the process's: a fresh one here, so that the storm
    suppressor's buckets do not carry the failures of the tests that ran
    before in this process (a template that logged ten jobs' failures
    within seconds drops the next one's ERROR)."""
    logbus.reset_for_tests()
    root, cid, _, _ = circuit
    cs = mult_chain_circuit(9, 7)
    r1cs, z = cs.finish()
    bad = list(z)
    bad[-1] += 1  # breaks the last constraint -> witness-phase failure
    bad_wtns = write_wtns(bad)

    async def run():
        replica = await _start_replica(
            root, tmp_path / "jlogs", "r-logs", workers=1
        )
        router = FleetRouter(
            FleetConfig(
                replicas=((replica.url, str(tmp_path / "jlogs")),),
                poll_s=0.2,
            )
        )
        client = TestClient(TestServer(router.app()))
        await client.start_server()
        try:
            resp = await client.post(
                "/jobs/prove",
                data={"circuit_id": cid, "witness_file": bad_wtns,
                      "mpc": "1"},
                headers={"X-DG16-Tenant": "acme"},
            )
            body = await resp.json()
            assert resp.status == 202, body
            jid, trace = body["jobId"], body["traceId"]
            status = await _poll_terminal(client, jid)
            assert status["state"] == "FAILED", status

            doc = await _job_logs_with_replica_error(client, jid)
            assert doc["jobId"] == jid and doc["traceId"] == trace
            assert "warning" not in doc, doc
            recs = doc["records"]
            sources = {r["source"] for r in recs}
            assert "router" in sources, recs
            assert "replica r-logs" in sources, recs
            # every router-tier record is fleet-logged; the replica ERROR
            # carries the full correlation tuple
            for r in recs:
                if r["source"] == "router":
                    assert r["logger"].startswith("fleet")
            errors = [r for r in recs if r["level"] == "ERROR"]
            assert errors, recs
            err = errors[0]
            assert err["source"] == "replica r-logs"
            assert err["trace"] == trace
            assert err["job"] == jid
            assert err["replica"] == "r-logs"
            assert err["tenant"] == "acme"
            # the merge is one causally-ordered story on the router clock
            ts = [r["tsRouterNs"] for r in recs]
            assert ts == sorted(ts)

            # ?level= filters both tiers
            resp = await client.get(
                f"/fleet/jobs/{jid}/logs", params={"level": "ERROR"}
            )
            doc = await resp.json()
            assert all(r["levelNo"] >= 40 for r in doc["records"])
            assert any(r["trace"] == trace for r in doc["records"])

            resp = await client.get("/fleet/jobs/nope/logs")
            assert resp.status == 404
            resp = await client.get(
                f"/fleet/jobs/{jid}/logs", params={"level": "LOUD"}
            )
            assert resp.status == 400
        finally:
            await client.close()
            await replica.cleanup()

    asyncio.run(run())


# -- router /metrics + front-door middleware ----------------------------------


def test_router_metrics_route_and_http_middleware():
    """The router's own /metrics (satellite): strict 0.0.4, the fleet_*
    families present, and the middleware histogram keyed by ROUTE
    template (bounded cardinality), with unmatched paths folded into
    one label value."""

    async def run():
        router = FleetRouter(
            FleetConfig(
                replicas=(("http://127.0.0.1:1", None),), poll_s=30.0
            )
        )
        client = TestClient(TestServer(router.app()))
        await client.start_server()
        try:
            assert (await client.get("/healthz")).status == 200
            assert (await client.get("/no/such/route")).status == 404
            resp = await client.get("/metrics")
            assert resp.status == 200
            types, samples = parse_prometheus(await resp.text())
            assert types["fleet_replica_state"] == "gauge"
            assert types["fleet_http_seconds"] == "histogram"
            assert types["fleet_proxy_errors_total"] == "counter"
            assert types["fleet_anomalies_total"] == "counter"
            routes = {
                (dict(labels).get("route"), dict(labels).get("code"))
                for (name, labels) in samples
                if name == "fleet_http_seconds_count"
            }
            assert ("/healthz", "200") in routes
            assert ("unmatched", "404") in routes
        finally:
            await client.close()

    asyncio.run(run())


# -- metrics federation units -------------------------------------------------


def _replica_exposition(
    n_jobs, runtime=0.5, burn=0.0, breaker_open=False
) -> str:
    """Render a plausible replica /metrics body from a fresh registry."""
    reg = MetricsRegistry()
    h = reg.histogram("job_seconds", "x", ("kind",), buckets=(1.0, 10.0))
    for _ in range(n_jobs):
        h.labels(kind="prove").observe(runtime)
    reg.counter("jobs_finished_total", "x", ("state",)).labels(
        state="DONE"
    ).inc(n_jobs)
    if burn:
        reg.gauge("slo_burn_rate", "x", ("kind",)).labels(
            kind="prove"
        ).set(burn)
    reg.gauge("mesh_breaker_state", "x", ("slice",)).labels(
        slice="4p0"
    ).set(2 if breaker_open else 0)
    return reg.render_prometheus()


def test_metrics_federator_replica_labels_and_rollups():
    clk = _Clock()
    fed = MetricsFederator(clock=clk)
    fed.note_scrape("r-a", _replica_exposition(4))
    fed.note_scrape("r-b", _replica_exposition(6, runtime=5.0, burn=1.5,
                                               breaker_open=True))
    fed.tick()
    clk.t += 2.0
    fed.note_scrape("r-b", _replica_exposition(10, runtime=5.0, burn=1.5,
                                               breaker_open=True))
    fed.tick()

    types, samples = parse_prometheus(fed.render())
    # federation label rule: same name/type, one new label
    assert types["jobs_finished_total"] == "counter"
    assert samples[
        ("jobs_finished_total", (("state", "DONE"), ("replica", "r-a")))
    ] == 4.0
    assert samples[
        ("job_seconds_count", (("kind", "prove"), ("replica", "r-b")))
    ] == 10.0
    # rollups: merged histogram, summed counters, rate over the tick
    assert types["fleet_job_seconds"] == "histogram"
    assert samples[
        ("fleet_job_seconds_count", (("kind", "prove"),))
    ] == 14.0
    assert samples[
        ("fleet_jobs_finished_total", (("state", "DONE"),))
    ] == 14.0
    # 10 -> 14 finished over the 2 s tick
    assert samples[("fleet_jobs_per_second", ())] == pytest.approx(2.0)
    assert samples[("fleet_max_burn_rate", ())] == 1.5
    assert samples[("fleet_open_breakers", ())] == 1.0
    assert samples[("fleet_replicas_scraped", ())] == 2.0
    # merged p95 lands in r-b's 5 s bucket range, not r-a's sub-second
    q95 = samples[
        ("fleet_job_quantile_seconds", (("kind", "prove"), ("q", "0.95")))
    ]
    assert 1.0 < q95 <= 10.0

    # ejection drops a replica out of the federated view
    fed.retain({"r-a"})
    types, samples = parse_prometheus(fed.render())
    assert not any(
        dict(labels).get("replica") == "r-b" for (_, labels) in samples
    )
    assert samples[("fleet_replicas_scraped", ())] == 1.0

    # garbage never lands: counted, not half-ingested
    before = fed.scrapes_failed
    fed.note_scrape("r-c", "job_seconds{kind=unquoted} 1\n")
    assert fed.scrapes_failed == before + 1
    assert "r-c" not in fed.replicas()


# -- fleet anomaly hook -------------------------------------------------------


def test_fleet_anomaly_hook_dumps_once_per_episode(tmp_path):
    from distributed_groth16_tpu.telemetry import flight
    from distributed_groth16_tpu.telemetry import metrics as tm

    router = FleetRouter(
        FleetConfig(
            replicas=(
                ("http://a", None), ("http://b", None), ("http://c", None)
            ),
            anomaly_factor=2.0,
        )
    )
    fast = _replica_exposition(6, runtime=0.5)
    slow = _replica_exposition(6, runtime=50.0)
    router.federator.note_scrape("r-1", fast)
    router.federator.note_scrape("r-2", fast)
    router.federator.note_scrape("r-3", slow)
    anom = tm.registry().family("fleet_anomalies_total")

    def count():
        child = dict(anom.items()).get(("r-3", "p95_seconds"))
        return child.value if child is not None else 0.0

    flight.configure(str(tmp_path))
    try:
        base = count()
        router._anomaly_pass()
        dumps = sorted(tmp_path.glob("*fleet_anomaly*"))
        assert len(dumps) == 1
        assert count() == base + 1
        post = json.loads(dumps[0].read_text())
        assert post["trigger"] == "fleet_anomaly"
        assert post["extra"]["replica"] == "r-3"
        assert post["extra"]["signal"] == "p95_seconds"
        assert post["extra"]["value"] > post["extra"]["fleetMedian"] * 2.0
        # latched: the same episode never dumps twice
        router._anomaly_pass()
        assert len(list(tmp_path.glob("*fleet_anomaly*"))) == 1
        assert count() == base + 1
        # recovery re-arms; the next deviation is a new episode
        router.federator.note_scrape("r-3", fast)
        router._anomaly_pass()
        router.federator.note_scrape("r-3", slow)
        router._anomaly_pass()
        assert len(list(tmp_path.glob("*fleet_anomaly*"))) == 2
        assert count() == base + 2
        # VANISHING re-arms too: an ejected replica's scrape drops out
        # of the signal dict entirely (retain), and its next anomaly
        # after rejoining must be a fresh episode, not a stale latch
        router.federator.retain({"r-1", "r-2"})
        router._anomaly_pass()
        assert ("r-3", "p95_seconds") not in router._anomaly_latched
        router.federator.note_scrape("r-3", slow)
        router._anomaly_pass()
        assert len(list(tmp_path.glob("*fleet_anomaly*"))) == 3
        assert count() == base + 3
    finally:
        flight.disable()


def test_fleet_anomaly_needs_quorum_and_knob_off():
    from distributed_groth16_tpu.telemetry import flight

    router = FleetRouter(
        FleetConfig(
            replicas=(("http://a", None), ("http://b", None)),
            anomaly_factor=2.0,
        )
    )
    router.federator.note_scrape("r-1", _replica_exposition(6, runtime=0.5))
    router.federator.note_scrape("r-2", _replica_exposition(6, runtime=50.0))
    router._anomaly_pass()  # only 2 replicas: median is meaningless, no-op
    assert not router._anomaly_latched
    # factor <= 0 disables the hook entirely
    router.cfg = FleetConfig(replicas=router.cfg.replicas, anomaly_factor=0.0)
    router.federator.note_scrape("r-3", _replica_exposition(6, runtime=0.5))
    router._anomaly_pass()
    assert not router._anomaly_latched
    assert not flight.enabled()


# -- journal trace-id round-trip ----------------------------------------------


def test_journal_submit_record_carries_trace_id(tmp_path):
    from distributed_groth16_tpu.service.journal import JobJournal

    j = JobJournal(str(tmp_path / "wal"), fsync=False)
    job = ProofJob(
        kind="prove", circuit_id="c", fields={"witness_file": b"x"},
        trace_id="trace-123",
    )
    j.append_submit(job)
    j.close()
    (entry,) = read_journal(str(tmp_path / "wal"))
    assert entry.trace_id == "trace-123"
    assert entry.replayable


# -- CLI: fleet top -----------------------------------------------------------


def test_cli_fleet_top_formatting():
    from distributed_groth16_tpu.api.cli import format_fleet_top

    reg = MetricsRegistry()
    h = reg.histogram(
        "job_seconds", "x", ("kind", "replica"), buckets=(1.0, 10.0)
    )
    for _ in range(4):
        h.labels(kind="prove", replica="r-a").observe(0.5)
    st = reg.counter("party_straggler_total", "x", ("party", "replica"))
    st.labels(party="3", replica="r-a").inc(7)
    st.labels(party="1", replica="r-a").inc(2)
    reg.gauge("fleet_jobs_per_second", "x").set(1.25)
    q = reg.gauge("fleet_job_quantile_seconds", "x", ("kind", "q"))
    q.labels(kind="prove", q="0.5").set(0.4)
    q.labels(kind="prove", q="0.95").set(0.9)
    table = format_fleet_top(
        {
            "replicas": [
                {
                    "replicaId": "r-a", "state": "active", "score": 1.0,
                    "queueDepth": 2, "running": 1, "workers": 2,
                    "openBreakers": 0, "maxBurnRate": 0.2,
                },
                {
                    "replicaId": "r-gone", "state": "ejected",
                    "score": None, "queueDepth": None, "running": None,
                    "workers": None, "openBreakers": None,
                    "maxBurnRate": None,
                },
            ],
            "pending": 3,
            "handoffs": 1,
        },
        reg.render_prometheus(),
    )
    lines = table.splitlines()
    assert lines[0].split()[:4] == ["REPLICA", "VER", "STATE", "SCORE"]
    assert "r-a" in lines[1] and "active" in lines[1]
    # the most-straggling party (argmax of the counter) shows per replica
    assert lines[1].rstrip().endswith("3")
    assert "r-gone" in lines[2] and "-" in lines[2]
    footer = lines[-1]
    assert "p50=0.4s" in footer and "p95=0.9s" in footer
    assert "jobs/s=1.25" in footer
    assert "pending=3" in footer and "handoffs=1" in footer
    # an empty metrics body still renders the stats half
    table = format_fleet_top({"replicas": [], "pending": 0}, "")
    assert table.splitlines()[0].startswith("REPLICA")
