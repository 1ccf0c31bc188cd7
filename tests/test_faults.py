"""Chaos suite for the fault-tolerant star transport.

Every scenario injects a transport fault (via FaultyIO, an abrupt close,
or plain silence) and asserts the collective either completes or raises a
structured MpcNetError naming the offending party — within its deadline,
never hanging. Each async body is bounded by an outer asyncio.wait_for so
a regression shows up as a test failure, not a wedged suite.

Since the telemetry subsystem landed, key scenarios also assert the fault
COUNTERS increment (net_timeouts_total, net_peer_deaths_total,
net_err_frames_total, net_round_retries_total — docs/OBSERVABILITY.md):
the counters are process-lifetime, so every check compares deltas.

FaultyIO write indices are deterministic here because the test NetConfig
disables heartbeats: a client's write #0 is its SYNACK, so DATA frames
start at write #1 (see faults.py docstring).
"""

import asyncio
import time

import pytest

from distributed_groth16_tpu.parallel.faults import FaultyIO
from distributed_groth16_tpu.parallel.net import (
    MpcDisconnectError,
    MpcNetError,
    MpcTimeoutError,
    run_round_with_retries,
)
from distributed_groth16_tpu.parallel.prodnet import ChannelIO, ProdNet
from distributed_groth16_tpu.telemetry import metrics as telemetry_metrics
from distributed_groth16_tpu.utils.config import NetConfig


def _counter(name: str, **labels) -> float:
    """Current value of a registry counter (0.0 if the series is new)."""
    fam = telemetry_metrics.registry().counter(
        name, labelnames=tuple(labels)
    )
    return (fam.labels(**labels) if labels else fam).value

# fast deadlines, no heartbeats: deterministic frame indices for FaultyIO
FAST = NetConfig(
    op_timeout_s=2.0,
    connect_timeout_s=5.0,
    connect_base_delay_s=0.05,
    connect_max_delay_s=0.5,
    heartbeat_interval_s=0.0,
)
SUITE_BOUND_S = 30.0  # no single scenario may run (or hang) longer


def _bounded(coro):
    return asyncio.run(asyncio.wait_for(coro, SUITE_BOUND_S))


async def _channel_star(n, cfg=FAST, wrap=None):
    """king + clients over ChannelIO pairs; `wrap` maps client id -> a
    function wrapping that client's IO (fault injection point)."""
    pairs = {i: ChannelIO.pair() for i in range(1, n)}
    client_ios = {i: pairs[i][1] for i in pairs}
    for i, w in (wrap or {}).items():
        client_ios[i] = w(client_ios[i])
    king_task = asyncio.create_task(
        ProdNet.king_from_ios({i: pairs[i][0] for i in pairs}, n, cfg)
    )
    peer_tasks = [
        asyncio.create_task(ProdNet.peer_from_io(i, client_ios[i], n, cfg))
        for i in range(1, n)
    ]
    king = await king_task
    peers = [await t for t in peer_tasks]
    return [king] + peers


async def _close_all(nets):
    for n in nets:
        await n.close()


async def _sum_ids(nets, timeout=None):
    out = await asyncio.gather(
        *(
            n.king_compute(
                n.party_id,
                lambda ids: [sum(ids)] * n.n_parties,
                timeout=timeout,
            )
            for n in nets
        )
    )
    return out


# -- deadlines ---------------------------------------------------------------


def test_recv_deadline_raises_structured_timeout():
    before = _counter("net_timeouts_total", op="recv_from")

    async def run():
        nets = await _channel_star(2)
        t0 = time.monotonic()
        with pytest.raises(MpcTimeoutError) as ei:
            await nets[0].recv_from(1, sid=1, timeout=0.3)
        assert time.monotonic() - t0 < 2.0
        e = ei.value
        assert (e.party, e.peer, e.sid, e.op) == (0, 1, 1, "recv_from")
        await _close_all(nets)

    _bounded(run())
    assert _counter("net_timeouts_total", op="recv_from") == before + 1


def test_gather_deadline_names_silent_party():
    async def run():
        nets = await _channel_star(4)
        king, clients = nets[0], nets[1:]

        async def client(net):
            if net.party_id == 1:
                return  # party 1 never contributes
            await net.send_to(0, net.party_id)

        async def king_side():
            with pytest.raises(MpcTimeoutError) as ei:
                await king.gather_to_king(0, timeout=0.5)
            assert ei.value.peer == 1
            assert ei.value.op == "gather_to_king"

        await asyncio.gather(king_side(), *(client(c) for c in clients))
        await _close_all(nets)

    _bounded(run())


def test_scatter_deadline_on_client():
    async def run():
        nets = await _channel_star(2)
        with pytest.raises(MpcTimeoutError) as ei:
            await nets[1].scatter_from_king(None, timeout=0.3)
        assert ei.value.op == "scatter_from_king"
        assert ei.value.peer == 0
        await _close_all(nets)

    _bounded(run())


def test_config_default_timeout_applies_without_per_op_override():
    cfg = NetConfig(
        op_timeout_s=0.3, connect_timeout_s=5.0, heartbeat_interval_s=0.0
    )

    async def run():
        nets = await _channel_star(2, cfg)
        t0 = time.monotonic()
        with pytest.raises(MpcTimeoutError):
            await nets[0].recv_from(1)  # no per-op timeout passed
        assert time.monotonic() - t0 < 2.0
        await _close_all(nets)

    _bounded(run())


# -- injected faults ---------------------------------------------------------


def test_delay_fault_completes_within_deadline():
    wrap = {
        i: (lambda i: lambda io: FaultyIO(
            io, seed=i, delay_p=1.0, max_delay_s=0.02
        ))(i)
        for i in range(1, 4)
    }

    async def run():
        nets = await _channel_star(4, wrap=wrap)
        out = await _sum_ids(nets, timeout=5.0)
        assert out == [6] * 4
        await _close_all(nets)

    _bounded(run())


def test_drop_fault_surfaces_as_timeout():
    # SYNACK (write #0) passes; every DATA frame after is swallowed
    wrap = {1: lambda io: FaultyIO(io, drop_writes_from=1)}

    async def run():
        nets = await _channel_star(3, wrap=wrap)
        king = nets[0]
        await nets[1].send_to(0, 11)  # silently dropped on the wire
        await nets[2].send_to(0, 22)
        assert await king.recv_from(2, timeout=1.0) == 22
        with pytest.raises(MpcTimeoutError) as ei:
            await king.recv_from(1, timeout=0.5)
        assert ei.value.peer == 1
        await _close_all(nets)

    _bounded(run())


def test_corrupt_length_prefix_fails_fast_not_hangs():
    wrap = {1: lambda io: FaultyIO(io, corrupt_len_at=1)}

    async def run():
        nets = await _channel_star(2, wrap=wrap)
        king = nets[0]
        await nets[1].send_to(0, 123)  # length prefix corrupted in flight
        t0 = time.monotonic()
        with pytest.raises(MpcDisconnectError) as ei:
            await king.recv_from(1, timeout=5.0)
        # detection is by frame validation, well before the deadline
        assert time.monotonic() - t0 < 2.0
        assert "bad frame length" in str(ei.value)
        # the queues stay poisoned: a second recv also fails, instantly
        with pytest.raises(MpcDisconnectError):
            await king.recv_from(1, timeout=5.0)
        await _close_all(nets)

    _bounded(run())


def test_truncated_frame_fails_fast():
    wrap = {1: lambda io: FaultyIO(io, truncate_write_at=1)}

    async def run():
        nets = await _channel_star(2, wrap=wrap)
        king = nets[0]
        await nets[1].send_to(0, [1, 2, 3])  # half a frame, then EOF
        with pytest.raises(MpcDisconnectError):
            await king.recv_from(1, timeout=5.0)
        await _close_all(nets)

    _bounded(run())


def test_mid_collective_disconnect_both_sides_fail_clean():
    wrap = {1: lambda io: FaultyIO(io, disconnect_write_at=1)}

    async def run():
        nets = await _channel_star(3, wrap=wrap)
        king = nets[0]
        # the failing client's own send surfaces as MpcNetError, not a raw
        # ConnectionResetError
        with pytest.raises(MpcDisconnectError) as ei:
            await nets[1].send_to(0, 99)
        assert ei.value.peer == 0
        # the king sees EOF and names the dead party, fast
        t0 = time.monotonic()
        with pytest.raises(MpcDisconnectError) as ei:
            await king.recv_from(1, timeout=5.0)
        assert time.monotonic() - t0 < 2.0
        assert ei.value.peer == 1
        # the surviving client hears about it via the king's ERR relay —
        # the whole star fails fast so the round can be retried, rather
        # than rank 2 idling out its own deadline
        t0 = time.monotonic()
        with pytest.raises(MpcDisconnectError) as ei:
            await nets[2].recv_from(0, timeout=5.0)
        assert time.monotonic() - t0 < 2.0
        assert "party 1" in str(ei.value)
        await _close_all(nets)

    _bounded(run())


def test_abort_relays_death_to_other_clients():
    err_before = _counter("net_err_frames_total", peer="1")
    deaths_before = _counter("net_peer_deaths_total", peer="1")

    async def run():
        nets = await _channel_star(4)
        king, c1, c2, c3 = nets
        await c1.abort("simulated fatal app error")
        # king names party 1; the other clients hear it via the ERR relay
        # instead of waiting out their own deadlines
        with pytest.raises(MpcDisconnectError) as ei:
            await king.recv_from(1, timeout=5.0)
        assert ei.value.peer == 1
        for c in (c2, c3):
            t0 = time.monotonic()
            with pytest.raises(MpcDisconnectError) as ei:
                await c.recv_from(0, timeout=5.0)
            assert time.monotonic() - t0 < 2.0
            assert "party 1" in str(ei.value)
        await _close_all(nets)

    _bounded(run())
    # the king counted party 1's ERR frame and declared it dead
    assert _counter("net_err_frames_total", peer="1") == err_before + 1
    assert _counter("net_peer_deaths_total", peer="1") >= deaths_before + 1


def test_failed_gather_reaps_sibling_recvs():
    """When gather fails on one peer, the in-flight recvs for the OTHER
    peers must be cancelled — a leaked sibling task would steal those
    peers' next frames and silently desync every later collective. Shown
    at the BaseNet level: peer 1's recv fails instantly while peers 2/3
    carry a long deadline, then 2/3's values must reach a FRESH recv."""
    from distributed_groth16_tpu.parallel.net import LocalSimNet, make_local_nets

    class FailOn1Net(LocalSimNet):
        async def _recv_impl(self, frm, sid):
            if frm == 1:
                raise MpcDisconnectError(
                    "injected dead link", party=self.party_id, peer=1
                )
            return await super()._recv_impl(frm, sid)

    async def run():
        nets = make_local_nets(4, FAST)
        king = FailOn1Net(0, 4, nets[1]._fabric, FAST)
        with pytest.raises(MpcNetError) as ei:
            await king.gather_to_king(0, timeout=5.0)
        assert ei.value.peer == 1
        await nets[2].send_to(0, 222)
        await nets[3].send_to(0, 333)
        assert await king.recv_from(2, timeout=1.0) == 222
        assert await king.recv_from(3, timeout=1.0) == 333

    _bounded(run())


def test_failed_barrier_does_not_leak_tasks():
    """A node whose Syn/SynAck barrier fails must tear down its pumps,
    heartbeats, and IOs — a launcher retrying bring-up would otherwise
    accumulate leaked tasks and sockets per attempt."""
    cfg = NetConfig(
        connect_timeout_s=0.4, heartbeat_interval_s=0.1, idle_timeout_s=5.0
    )

    async def run():
        before = asyncio.all_tasks()
        a, _b = ChannelIO.pair()  # no peer ever answers the barrier
        with pytest.raises(MpcTimeoutError):
            await ProdNet.king_from_ios({1: a}, 2, cfg)
        await asyncio.sleep(0.05)  # let cancellations settle
        leaked = [t for t in asyncio.all_tasks() - before if not t.done()]
        assert not leaked, f"leaked tasks: {leaked}"

    _bounded(run())


# -- heartbeats / liveness ---------------------------------------------------


def test_heartbeats_keep_idle_link_alive():
    cfg = NetConfig(
        op_timeout_s=5.0, connect_timeout_s=5.0,
        heartbeat_interval_s=0.05, idle_timeout_s=0.3,
    )

    async def run():
        nets = await _channel_star(3, cfg)
        await asyncio.sleep(0.6)  # > idle_timeout_s of pure silence
        out = await _sum_ids(nets, timeout=2.0)  # no false positive
        assert out == [3] * 3
        await _close_all(nets)

    _bounded(run())


def test_idle_peer_detected_and_pending_recv_released():
    cfg = NetConfig(
        op_timeout_s=10.0, connect_timeout_s=5.0,
        heartbeat_interval_s=0.05, idle_timeout_s=0.3,
    )
    # client 1 goes silent after its SYNACK: no data, no heartbeats
    wrap = {1: lambda io: FaultyIO(io, drop_writes_from=1)}

    async def run():
        nets = await _channel_star(2, cfg, wrap=wrap)
        king = nets[0]
        t0 = time.monotonic()
        # recv is already pending when the idle detector fires — the
        # poisoned queue must release it, well before the 10s op deadline
        with pytest.raises(MpcDisconnectError) as ei:
            await king.recv_from(1, timeout=10.0)
        assert time.monotonic() - t0 < 3.0
        assert "idle timeout" in str(ei.value)
        await _close_all(nets)

    _bounded(run())


# -- real sockets ------------------------------------------------------------


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_kill_client_mid_gather_over_real_sockets():
    """test_prodnet.py-style TCP star: one client dies abruptly mid-gather;
    the king fails fast with the offending party named (acceptance
    scenario)."""
    N = 4

    async def run():
        port = _free_port()
        king_task = asyncio.create_task(
            ProdNet.new_king(("127.0.0.1", port), N, net_cfg=FAST)
        )
        peers = await asyncio.gather(
            *(
                ProdNet.new_peer(i, ("127.0.0.1", port), N, net_cfg=FAST)
                for i in range(1, N)
            )
        )
        king = await king_task

        async def client(net):
            if net.party_id == 1:
                await net.close()  # crash: socket gone mid-collective
                return
            await net.send_to(0, net.party_id * 10)

        async def king_side():
            t0 = time.monotonic()
            with pytest.raises(MpcNetError) as ei:
                await king.gather_to_king(0, timeout=5.0)
            assert time.monotonic() - t0 < 3.0
            assert ei.value.peer == 1
            assert ei.value.op == "gather_to_king"

        await asyncio.gather(king_side(), *(client(p) for p in peers))
        await king.close()
        for p in peers:
            await p.close()

    _bounded(run())


def test_kill_mid_gather_leaves_flight_dump_and_merged_trace(tmp_path):
    """Telemetry-plane acceptance over real TCP: a mid-gather peer kill
    (a) leaves a flight-recorder post-mortem naming the dead peer with
    the last net events, and (b) the surviving clients' TELEMETRY frames
    still merge into a king-side trace with a critical-path breakdown
    (docs/OBSERVABILITY.md "Distributed tracing & flight recorder")."""
    import os

    from distributed_groth16_tpu.telemetry import aggregate, flight, tracing

    N = 4
    # CI points DG16_FLIGHT_ARTIFACT_DIR at a workspace path so the dumps
    # and the merged trace upload as a workflow artifact on failure
    art_dir = os.environ.get("DG16_FLIGHT_ARTIFACT_DIR") or str(tmp_path)
    flight.configure(art_dir)
    aggregate.set_enabled(True)
    agg = aggregate.reset_aggregator()

    async def run():
        port = _free_port()
        king_task = asyncio.create_task(
            ProdNet.new_king(("127.0.0.1", port), N, net_cfg=FAST)
        )
        peers = await asyncio.gather(
            *(
                ProdNet.new_peer(i, ("127.0.0.1", port), N, net_cfg=FAST)
                for i in range(1, N)
            )
        )
        king = await king_task

        async def client(net):
            if net.party_id == 1:
                await net.close()  # crash mid-collective
                return
            with tracing.span("client.compute", party=net.party_id):
                await asyncio.sleep(0.01)
            try:
                await net.send_to(0, net.party_id * 10)
            except MpcNetError:
                pass  # the star failed fast via the king's ERR relay
            # post-fault flush: the socket to the king is still healthy
            # even though the relay marked the star dead — the frames are
            # the post-mortem's raw material
            await net.flush_telemetry()

        async def king_side():
            with pytest.raises(MpcNetError) as ei:
                await king.gather_to_king(0, timeout=5.0)
            assert ei.value.peer == 1

        await asyncio.gather(king_side(), *(client(p) for p in peers))
        await king.flush_telemetry()
        # client frames arrive on the pump; wait for both survivors
        for _ in range(100):
            if {2, 3} <= set(agg.parties()):
                break
            await asyncio.sleep(0.02)
        await king.close()
        for p in peers:
            await p.close()

    try:
        _bounded(run())
        assert {2, 3} <= set(agg.parties())
        cp = agg.finish_round()
        if cp["parties"] == 0:
            # the king auto-closed the round when the last live party's
            # frame arrived — the decomposition is already recorded
            cp = agg.last_critical_path
        # NB: in this single-process harness all parties share one span
        # buffer, so the first survivor's flush ships the bulk of the
        # events under its own track — per-party attribution is exact
        # only with one process per party (the production shape; the
        # LocalTestNet tests in test_agg_trace.py cover multi-track
        # attribution). The breakdown must still be non-empty.
        assert cp["parties"] >= 1 and cp["wall"] > 0
        meta_pids = [
            e["pid"]
            for e in agg.chrome_trace()["traceEvents"]
            if e.get("ph") == "M"
        ]
        assert {2, 3} <= set(meta_pids)
        # the merged trace lands next to the dumps (CI artifact on failure)
        agg.dump(os.path.join(art_dir, "merged-trace.json"))
        # the post-mortem names the dead peer and keeps the lead-up
        import glob
        import json

        records = [
            json.load(open(f))
            for f in glob.glob(os.path.join(art_dir, "flight-*.json"))
        ]
        king_side_dumps = [
            r for r in records
            if r["trigger"] == "peer_death" and r["extra"].get("peer") == 1
        ]
        assert king_side_dumps, records
        assert any(
            e["kind"] == "peer_death"
            for e in king_side_dumps[0]["netEvents"]
        )
        assert king_side_dumps[0]["metrics"]
    finally:
        flight.disable()
        aggregate.set_enabled(False)
        aggregate.reset_aggregator()


def test_client_dials_before_king_listens():
    """Backoff-retry regression (acceptance): a client whose first dial
    lands before the king is listening connects once the king comes up."""

    async def run():
        port = _free_port()
        peer_task = asyncio.create_task(
            ProdNet.new_peer(1, ("127.0.0.1", port), 2, net_cfg=FAST)
        )
        await asyncio.sleep(0.4)  # let several dials fail first
        king = await ProdNet.new_king(("127.0.0.1", port), 2, net_cfg=FAST)
        peer = await peer_task
        out = await _sum_ids([king, peer], timeout=2.0)
        assert out == [1, 1]
        await _close_all([king, peer])

    _bounded(run())


def test_king_startup_deadline_names_missing_parties():
    cfg = NetConfig(connect_timeout_s=0.5, heartbeat_interval_s=0.0)

    async def run():
        port = _free_port()
        with pytest.raises(MpcTimeoutError) as ei:
            await ProdNet.new_king(("127.0.0.1", port), 3, net_cfg=cfg)
        assert "[1, 2]" in str(ei.value)

    _bounded(run())


# -- retryable rounds --------------------------------------------------------


def test_round_retry_recovers_from_transient_fault():
    state = {"round": 0}

    async def party(net, _):
        if net.party_id == 0:
            state["round"] += 1
        if net.party_id == 1 and state["round"] == 1:
            raise MpcTimeoutError(
                "injected transient fault", party=1, peer=0, op="recv_from"
            )
        return await net.king_compute(
            net.party_id, lambda ids: [sum(ids)] * net.n_parties
        )

    retried = []
    retries_before = _counter("net_round_retries_total")
    out = run_round_with_retries(
        3, party, retries=2, net_cfg=FAST,
        on_retry=lambda a, e: retried.append((a, str(e))),
    )
    assert out == [3] * 3
    assert state["round"] == 2
    assert len(retried) == 1 and "transient" in retried[0][1]
    assert _counter("net_round_retries_total") == retries_before + 1


def test_round_retry_exhaustion_propagates():
    async def party(net, _):
        raise MpcDisconnectError("permanently dead", party=net.party_id)

    failures_before = _counter("net_round_failures_total")
    with pytest.raises(MpcDisconnectError):
        run_round_with_retries(2, party, retries=1, net_cfg=FAST)
    assert _counter("net_round_failures_total") == failures_before + 1


def test_round_retry_does_not_swallow_application_errors():
    async def party(net, _):
        raise ValueError("not a transport fault")

    with pytest.raises(ValueError):
        run_round_with_retries(2, party, retries=3, net_cfg=FAST)


def test_round_retry_does_not_rerun_deterministic_protocol_misuse():
    """Plain MpcNetError (bad destination, wrong scatter length) is a
    programming bug that fails identically every run — it must surface
    immediately, not after re-running a multi-hour round."""
    state = {"rounds": 0}

    async def party(net, _):
        if net.party_id == 0:
            state["rounds"] += 1
            await net.scatter_from_king([1, 2, 3])  # wrong length for n=2
        else:
            await net.scatter_from_king(None, timeout=0.5)

    with pytest.raises(MpcNetError) as ei:
        run_round_with_retries(2, party, retries=3, net_cfg=FAST)
    assert not isinstance(ei.value, (MpcTimeoutError, MpcDisconnectError))
    assert state["rounds"] == 1, "deterministic failure must not be retried"


# -- service-plane chaos: a worker dying mid-batch ---------------------------


def test_kill_worker_mid_batch_jobs_survive(tmp_path):
    """Chaos scenario for the crash-safe service plane
    (docs/ROBUSTNESS.md): the batch prover's worker thread is killed
    mid-batch (SystemExit, as an OOM-killed or crashed worker surfaces).
    The scheduler must neither hang nor lose a job — the batch faults,
    bisection retries the members, and every job lands DONE with the
    journal holding no resurrectable state. Bounded like every other
    scenario: a regression is a failure, not a wedged suite."""
    from types import SimpleNamespace

    from distributed_groth16_tpu.scheduler import BatchScheduler, ProverCache
    from distributed_groth16_tpu.service import JobJournal, JobQueue, ProofJob
    from distributed_groth16_tpu.service.jobs import JobState
    from distributed_groth16_tpu.utils.config import SchedulerConfig

    class _Executor:
        class _Store:
            def load(self, cid):
                return (SimpleNamespace(num_instance=2),
                        SimpleNamespace(domain_size=16))

        store = _Store()

    class _DyingProver:
        """First execution dies ABRUPTLY (the kill), later ones work."""

        def __init__(self):
            self.provers = ProverCache()
            self.kills = 1
            self.runs = 0

        def run_batch(self, jobs, key, mesh):
            self.runs += 1
            if self.kills > 0:
                self.kills -= 1
                raise SystemExit("worker killed mid-batch")
            return [
                (j, {"circuitId": j.circuit_id, "proof": [], "phases": {}})
                for j in jobs
            ]

    async def scenario():
        jdir = str(tmp_path / "wal")
        q = JobQueue(bound=64, workers=2,
                     journal=JobJournal(jdir, fsync=False))
        sched = BatchScheduler(
            _Executor(), q,
            SchedulerConfig(batch_max=2, batch_linger_ms=60000.0,
                            poison_retries=3),
            devices=[object() for _ in range(8)],
        )
        prover = sched.batch_prover = _DyingProver()
        jobs = [ProofJob(kind="prove", circuit_id="c1", fields={})
                for _ in range(2)]
        await sched.start()
        try:
            for job in jobs:
                q.submit(job)
                await q.get()
                await sched.offer(job)
            while sched._batch_tasks:
                await asyncio.gather(*list(sched._batch_tasks),
                                     return_exceptions=True)
        finally:
            await sched.stop()
        # the kill cost one retry round, not the batch
        assert all(j.state is JobState.DONE for j in jobs), [
            (j.state, j.error) for j in jobs
        ]
        assert prover.runs > 1  # the batch really was re-driven
        # nothing resurrectable: a rebuilt journal replays zero jobs
        assert JobJournal(jdir, fsync=False).pending() == []

    _bounded(scenario())


def test_a_party_that_never_reaches_the_rendezvous_times_the_round_out():
    """Party 2 dies before the in-process rendezvous: the others wait
    out the net's deadline, no longer, and the round ends in
    MpcTimeoutError naming the op, which `run_round_with_retries` reruns
    on a fresh fabric and rendezvous."""
    state = {"round": 0, "died": None, "ended": None}

    async def party(net, _):
        if net.party_id == 0:
            state["round"] += 1
        if net.party_id == 2 and state["round"] == 1:
            state["died"] = time.monotonic()
            await asyncio.sleep(SUITE_BOUND_S)  # never arrives
        return await net.batch_local(
            net.party_id, lambda ids: [sum(ids)] * len(ids)
        )

    def on_retry(attempt, e):
        state["ended"] = time.monotonic()
        assert isinstance(e, MpcTimeoutError) and e.op == "batch_local"

    before = _counter("net_timeouts_total", op="batch_local")
    out = run_round_with_retries(
        3, party, retries=1, net_cfg=FAST, on_retry=on_retry
    )
    assert out == [3] * 3 and state["round"] == 2
    waited = state["ended"] - state["died"]
    assert FAST.op_timeout_s <= waited + 0.05 < FAST.op_timeout_s + 1.0
    assert _counter("net_timeouts_total", op="batch_local") >= before + 1
