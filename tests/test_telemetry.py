"""Telemetry subsystem tests (telemetry/ + its wiring; docs/OBSERVABILITY.md).

Covers: metrics primitives and the Prometheus text exposition (including a
golden scrape of a LIVE test server validated with a strict line-format
parser), the zero-allocation hot-path guard, span nesting + Chrome
trace-event export of a real 2-domain MPC proof (the DG16_TRACE_OUT
acceptance path), the per-job span tree in GET /jobs/{id}, the timers
double-emission regression, the retryAfter-EMA cold start, and
MpcNetError job-id correlation.

The registry is process-wide by design, so every numeric check compares
deltas, never absolutes.
"""

import asyncio
import gc
import json
import logging
import re
import sys

import pytest
from aiohttp.test_utils import TestClient, TestServer

from distributed_groth16_tpu.api.server import ApiServer
from distributed_groth16_tpu.api.store import CircuitStore
from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
from distributed_groth16_tpu.parallel.net import (
    MpcNetError,
    job_context,
    simulate_network_round,
)
from distributed_groth16_tpu.parallel.prodnet import ChannelIO, ProdNet
from distributed_groth16_tpu.service.jobs import ProofJob
from distributed_groth16_tpu.service.queue import JobQueue
from distributed_groth16_tpu.telemetry import metrics as tm
from distributed_groth16_tpu.telemetry import tracing
from distributed_groth16_tpu.utils import timers
from distributed_groth16_tpu.utils.config import NetConfig, ServiceConfig

REG = tm.registry()


@pytest.fixture(autouse=True)
def _no_global_trace():
    """Spans must not leak into a DG16_TRACE_OUT buffer another test (or
    the environment) installed — every test here starts idle."""
    tracing.disable_global()
    yield
    tracing.disable_global()


# -- metrics primitives ------------------------------------------------------


def test_counter_gauge_histogram_basics():
    c = REG.counter("t_basic_total", "basic", ("k",))
    child = c.labels(k="a")
    v0 = child.value
    child.inc()
    child.inc(2.5)
    assert child.value == v0 + 3.5
    assert c.labels(k="a") is child  # get-or-create returns the same child

    g = REG.gauge("t_basic_gauge", "basic")
    g.set(4.0)
    g.inc()
    g.dec(2.0)
    assert g.value == 3.0

    h = REG.histogram("t_basic_seconds", "basic", buckets=(0.1, 1.0, 10.0))
    hc = h._default
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert hc.count == 4
    assert hc.sum == pytest.approx(55.55)
    assert hc.counts == [1, 1, 1, 1]  # one per bucket incl. +Inf overflow


def test_registry_rejects_type_and_label_conflicts():
    REG.counter("t_conflict_total", "x", ("a",))
    with pytest.raises(ValueError):
        REG.gauge("t_conflict_total")
    with pytest.raises(ValueError):
        REG.counter("t_conflict_total", "x", ("b",))
    fam = REG.counter("t_conflict_total", "x", ("a",))
    with pytest.raises(ValueError):
        fam.labels(wrong="1")
    with pytest.raises(ValueError):
        fam.labels("1", "2")


def test_metrics_kill_switch():
    c = REG.counter("t_killswitch_total", "x")
    v0 = c.value
    tm.set_enabled(False)
    try:
        c.inc()
        assert c.value == v0
    finally:
        tm.set_enabled(True)
    c.inc()
    assert c.value == v0 + 1


# -- Prometheus exposition ---------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL_PAIR = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:\\.|[^"\\])*)"')
_SAMPLE = re.compile(
    rf"^(?P<name>{_NAME})"
    r"(?:\{(?P<labels>.*)\})?"
    r" (?P<value>[-+]?(?:Inf|NaN|[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?))$"
)
_TYPE = re.compile(rf"^# TYPE (?P<name>{_NAME}) (counter|gauge|histogram)$")
_HELP = re.compile(rf"^# HELP (?P<name>{_NAME}) .*$")


def _unescape(v: str) -> str:
    return v.replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")


def parse_prometheus(text: str):
    """Strict 0.0.4 line parser: every line must be a HELP, a TYPE, or a
    well-formed sample. Returns (types, samples) where samples maps
    (name, ((label, value), ...)) -> float."""
    types: dict[str, str] = {}
    samples: dict[tuple, float] = {}
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE"):
            m = _TYPE.match(line)
            assert m, f"bad TYPE line: {line!r}"
            types[m["name"]] = line.rsplit(" ", 1)[1]
            continue
        if line.startswith("#"):
            assert _HELP.match(line), f"bad comment line: {line!r}"
            continue
        m = _SAMPLE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        raw = m["labels"] or ""
        labels = tuple(
            (k, _unescape(v)) for k, v in _LABEL_PAIR.findall(raw)
        )
        # the label blob must be fully consumed by well-formed pairs
        assert not _LABEL_PAIR.sub("", raw).strip(',"'), (
            f"bad label syntax: {line!r}"
        )
        value = m["value"]
        samples[(m["name"], labels)] = (
            float("inf") if value in ("Inf", "+Inf")
            else float("-inf") if value == "-Inf"
            else float(value)
        )
    return types, samples


def test_render_escapes_labels_and_parses_back():
    c = REG.counter("t_escape_total", 'has "quotes" and \\slashes\\', ("p",))
    weird = 'a"b\\c\nnewline'
    c.labels(p=weird).inc(3)
    types, samples = parse_prometheus(REG.render_prometheus())
    assert types["t_escape_total"] == "counter"
    assert samples[("t_escape_total", (("p", weird),))] == 3.0


def test_histogram_exposition_is_cumulative_with_inf():
    h = REG.histogram("t_expo_seconds", "x", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 7.0):
        h.observe(v)
    types, samples = parse_prometheus(REG.render_prometheus())
    assert types["t_expo_seconds"] == "histogram"
    assert samples[("t_expo_seconds_bucket", (("le", "0.1"),))] == 1
    assert samples[("t_expo_seconds_bucket", (("le", "1"),))] == 3
    assert samples[("t_expo_seconds_bucket", (("le", "+Inf"),))] == 4
    assert samples[("t_expo_seconds_count", ())] == 4
    assert samples[("t_expo_seconds_sum", ())] == pytest.approx(8.05)


# -- hot-path allocation guard -----------------------------------------------


def test_hot_path_adds_no_allocations_when_idle():
    """The acceptance guard: with no telemetry knobs set, a pre-bound
    counter inc, a histogram observe, and a disabled span cost no per-call
    allocations (beyond the one dict lookup call sites do themselves)."""
    assert not tracing.active()
    c = REG.counter("t_guard_total", "g", ("peer",)).labels(peer="1")
    h = REG.histogram("t_guard_seconds", "g", ("op",)).labels(op="x")

    def hot():
        c.inc()
        h.observe(0.25)
        with tracing.span("t.guard"):
            pass

    for _ in range(64):  # warm up caches/freelists
        hot()
    gc.collect()
    gc.disable()
    try:
        before = sys.getallocatedblocks()
        for _ in range(2000):
            hot()
        after = sys.getallocatedblocks()
    finally:
        gc.enable()
    assert after - before < 50, f"hot path leaked {after - before} blocks"


# -- tracing -----------------------------------------------------------------


def test_span_noop_when_idle_and_records_when_collecting():
    with tracing.span("t.idle"):
        pass
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        with tracing.span("t.outer", party=3):
            with tracing.span("t.inner", sid=2):
                pass
    assert len(buf) == 2
    inner, outer = buf.events()  # children exit first
    assert (inner["name"], outer["name"]) == ("t.inner", "t.outer")
    assert inner["args"]["parent"] == outer["args"]["id"]
    assert inner["pid"] == 3  # inherited from parent
    assert inner["args"]["sid"] == 2
    assert outer["args"]["parent"] == 0
    tree = buf.span_tree()
    assert [n["name"] for n in tree] == ["t.outer"]
    assert [n["name"] for n in tree[0]["children"]] == ["t.inner"]


def test_span_note_adds_to_the_open_span_and_leaves_a_shared_dict_alone():
    """What the work inside a span learnt (`ops/msm.py`: the route it
    dispatched) lands on the innermost open span, opened with attrs or
    without; the dict the span was opened with may be shared."""
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        with tracing.span("t.shared", attrs=tracing.DISPATCH):
            tracing.current().note(route="tree")
        with tracing.span("t.bare"):
            tracing.current().note(route="tree_limb0")
            with tracing.span("t.child"):
                pass
    told = {e["name"]: e["args"] for e in buf.events()}
    assert told["t.shared"]["route"] == "tree"
    assert told["t.shared"]["clock"] == "dispatch"
    assert told["t.bare"]["route"] == "tree_limb0"
    assert "route" not in told["t.child"]
    assert tracing.DISPATCH == {"clock": "dispatch"}
    assert tracing.current() is None


def test_span_records_timings_without_buffer():
    t = timers.PhaseTimings()
    with timers.phase("t-phase", t):
        pass
    assert "t-phase" in t.snapshot()


def test_trace_buffer_bounds_and_counts_drops():
    buf = tracing.TraceBuffer(max_events=2)
    with tracing.collect(buf):
        for _ in range(4):
            with tracing.span("t.x"):
                pass
    assert len(buf) == 2 and buf.dropped == 2


def test_chrome_trace_of_distributed_proof(tmp_path, monkeypatch):
    """The DG16_TRACE_OUT acceptance path: a local multi-party proof
    writes a valid Chrome trace-event file with nested spans for the
    gather/scatter collectives under the A/B/C proof phases."""
    from distributed_groth16_tpu.models.groth16 import (
        CompiledR1CS,
        distributed_prove_party,
        pack_from_witness,
        pack_proving_key,
        reassemble_proof,
        setup,
        verify,
    )
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.parallel.pss import PackedSharingParams

    path = tmp_path / "trace.json"
    monkeypatch.setenv("DG16_TRACE_OUT", str(path))
    tracing.configure_from_env()
    try:
        cs = mult_chain_circuit(9, 7)
        r1cs, z = cs.finish()
        pk = setup(r1cs)
        pp = PackedSharingParams(2)
        z_mont = fr().encode(z)
        comp = CompiledR1CS(r1cs)
        qap_shares = comp.qap(z_mont).pss(pp)
        crs_shares = pack_proving_key(pk, pp, strip=True)
        a_sh = pack_from_witness(pp, z_mont[1:])
        ax_sh = pack_from_witness(pp, z_mont[r1cs.num_instance:])

        async def party(net, d):
            return await distributed_prove_party(
                pp, d[0], d[1], d[2], d[3], net
            )

        res = simulate_network_round(
            pp.n, party,
            [
                (crs_shares[i], qap_shares[i], a_sh[i], ax_sh[i])
                for i in range(pp.n)
            ],
        )
        proof = reassemble_proof(res[0], pk)
        assert verify(pk.vk, proof, z[1:r1cs.num_instance])
        assert tracing.flush_global() == str(path)
    finally:
        tracing.disable_global()

    data = json.loads(path.read_text())
    evs = data["traceEvents"]
    names = {e["name"] for e in evs}
    assert {
        "prove.A", "prove.B", "prove.C", "prove.h",
        "net.gather_to_king", "net.scatter_from_king",
    } <= names
    for e in evs:  # structurally valid complete events
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # nesting: a gather collective's parent chain reaches the A phase
    by_id = {e["args"]["id"]: e for e in evs}

    def ancestors(e):
        while e["args"]["parent"]:
            e = by_id.get(e["args"]["parent"])
            if e is None:
                return
            yield e["name"]

    gathers = [e for e in evs if e["name"] == "net.gather_to_king"]
    assert any("prove.A" in set(ancestors(e)) for e in gathers)
    assert any("prove.C" in set(ancestors(e)) for e in gathers)
    # every party's round is a prove.party root with its pid
    assert {e["pid"] for e in evs if e["name"] == "prove.party"} == set(
        range(pp.n)
    )


# -- timers (satellite: double-emission regression) --------------------------


class _ListHandler(logging.Handler):
    def __init__(self, sink):
        super().__init__()
        self.sink = sink

    def emit(self, record):
        self.sink.append(record.getMessage())


def _with_handlers(root_on: bool, pkg_on: bool):
    got: list[str] = []
    root = logging.getLogger()
    pkg = logging.getLogger("distributed_groth16_tpu")
    handlers = []
    if root_on:
        h = _ListHandler(got)
        root.addHandler(h)
        handlers.append((root, h))
    if pkg_on:
        h = _ListHandler(got)
        pkg.addHandler(h)
        handlers.append((pkg, h))
    return got, handlers


@pytest.mark.parametrize(
    "root_on,pkg_on", [(True, True), (True, False), (False, True)]
)
def test_emit_prints_exactly_once(root_on, pkg_on):
    """Regression: with handlers on BOTH the root and package loggers the
    old _emit printed twice (own handler + propagation)."""
    got, handlers = _with_handlers(root_on, pkg_on)
    pkg = logging.getLogger("distributed_groth16_tpu")
    old_level = pkg.level
    pkg.setLevel(logging.INFO)
    root_old = logging.getLogger().level
    logging.getLogger().setLevel(logging.INFO)
    try:
        timers._emit("hello %s", "world")
    finally:
        for logger, h in handlers:
            logger.removeHandler(h)
        pkg.setLevel(old_level)
        logging.getLogger().setLevel(root_old)
    assert got == ["hello world"]


def test_emit_falls_back_to_root_when_pkg_handlers_reject():
    """When the package logger's handlers all sit above INFO (e.g. a
    warnings-only sink), the record must still print once via root
    propagation — the single-emission fix must not silently drop it."""
    got: list[str] = []
    root = logging.getLogger()
    pkg = logging.getLogger("distributed_groth16_tpu")
    root_h = _ListHandler(got)
    pkg_h = _ListHandler(got)
    pkg_h.setLevel(logging.WARNING)  # rejects INFO records
    root.addHandler(root_h)
    pkg.addHandler(pkg_h)
    old_pkg, old_root = pkg.level, root.level
    pkg.setLevel(logging.INFO)
    root.setLevel(logging.INFO)
    try:
        timers._emit("fallthrough %s", "x")
    finally:
        root.removeHandler(root_h)
        pkg.removeHandler(pkg_h)
        pkg.setLevel(old_pkg)
        root.setLevel(old_root)
    assert got == ["fallthrough x"]


def test_emit_falls_back_to_stderr(capsys):
    # pytest's logging plugin keeps a capture handler on the root logger —
    # park all handlers so the genuinely-unconfigured path is exercised
    root = logging.getLogger()
    pkg = logging.getLogger("distributed_groth16_tpu")
    saved = (root.handlers[:], pkg.handlers[:])
    root.handlers[:], pkg.handlers[:] = [], []
    try:
        timers._emit("plain %d", 7)
    finally:
        root.handlers[:], pkg.handlers[:] = saved
    assert "plain 7" in capsys.readouterr().err


# -- queue EMA (satellite) ---------------------------------------------------


def test_retry_after_cold_start_falls_back_then_tracks_ema():
    async def run():
        q = JobQueue(bound=4, workers=2, retry_after_s=7.5)
        # cold start: nothing completed yet -> configured fallback, and
        # the EMA is explicitly absent from /stats
        assert q.retry_after_hint() == 7.5
        assert q.stats()["meanRuntimeS"] is None

        def finish(kind, circuit_id, runtime_s):
            job = ProofJob(kind=kind, circuit_id=circuit_id, fields={})
            q.submit(job)
            job.mark_running()
            q.on_started(job)
            job.mark_done({})
            job.finished_at = job.started_at + runtime_s  # deterministic
            q.on_finished(job)
            return job

        job = finish("prove", "c", 10.0)
        await q.get()
        assert q.stats()["meanRuntimeS"] == pytest.approx(10.0)
        # hint = ceil((depth + 1) / workers) * ema
        assert q.retry_after_hint(job.bucket) == pytest.approx(10.0)
        # unknown bucket falls back to the cross-bucket mean (so does the
        # bucket-less legacy spelling)
        assert q.retry_after_hint("prove:other:l2") == pytest.approx(10.0)
        assert q.retry_after_hint() == pytest.approx(10.0)
        # the EMA is exposed as a per-bucket gauge on the registry
        gauge = REG.gauge("job_runtime_ema_seconds", labelnames=("bucket",))
        assert gauge.labels(bucket=job.bucket).value == pytest.approx(10.0)

        # EMAs are KEYED by bucket: a slow big circuit must not inflate
        # the hint for a small one
        slow = finish("mpc_prove", "big", 100.0)
        await q.get()
        assert q.retry_after_hint(job.bucket) == pytest.approx(10.0)
        assert q.retry_after_hint(slow.bucket) == pytest.approx(100.0)
        by_bucket = q.stats()["runtimeEmaByBucket"]
        assert by_bucket[job.bucket] == pytest.approx(10.0)
        assert by_bucket[slow.bucket] == pytest.approx(100.0)
        assert gauge.labels(bucket=slow.bucket).value == pytest.approx(100.0)

    asyncio.run(run())


def test_terminal_job_compacts_trace_but_keeps_span_tree():
    """A terminal job must not retain its raw trace event dicts (1024
    retained jobs x 4096 events is real memory) — the span tree survives
    as compact JSON and the status DTO is unchanged."""
    job = ProofJob(kind="prove", circuit_id="c", fields={})
    with tracing.collect(job.trace):
        with tracing.span("outer"):
            with tracing.span("inner"):
                pass
    assert len(job.trace) == 2
    job.mark_running()
    job.mark_done({})
    assert len(job.trace) == 0  # raw events dropped
    spans = job.to_dict()["metrics"]["spans"]
    assert [s["name"] for s in spans] == ["outer"]
    assert [c["name"] for c in spans[0]["children"]] == ["inner"]


# -- MpcNetError correlation id (satellite) ----------------------------------


def test_mpc_net_error_carries_job_id_from_context():
    e_outside = MpcNetError("boom", party=0)
    assert e_outside.job_id is None
    with job_context("job-abc"):
        e = MpcNetError("boom", party=1, peer=0, sid=2, op="recv_from")
    assert e.job_id == "job-abc"
    assert "job=job-abc" in str(e)
    relabeled = e.with_op("gather_to_king")
    assert relabeled.job_id == "job-abc"

    # the contextvar flows into tasks spawned by an MPC round
    async def fail(net, _):
        if net.party_id == 1:
            raise MpcNetError("synthetic", party=1)
        await asyncio.sleep(0)

    with job_context("job-round"):
        with pytest.raises(MpcNetError) as ei:
            simulate_network_round(2, fail)
    assert ei.value.job_id == "job-round"


# -- /metrics golden scrape off a live server (satellite) --------------------


@pytest.fixture(scope="module")
def circuit(tmp_path_factory):
    cs = mult_chain_circuit(9, 7)
    r1cs, z = cs.finish()
    root = str(tmp_path_factory.mktemp("telemetry_store"))
    cid = CircuitStore(root).save_circuit("tel", write_r1cs(r1cs), b"")
    return root, cid, write_wtns(z)


async def _populate_prodnet_bytes():
    """One tiny ChannelIO star exchange so the wire-accounting series have
    samples to scrape."""
    cfg = NetConfig(
        op_timeout_s=5.0, connect_timeout_s=5.0, heartbeat_interval_s=0.0
    )
    a, b = ChannelIO.pair()
    king_t = asyncio.create_task(ProdNet.king_from_ios({1: a}, 2, cfg))
    peer_t = asyncio.create_task(ProdNet.peer_from_io(1, b, 2, cfg))
    king, peer = await king_t, await peer_t
    await peer.send_to(0, [1, 2, 3])
    assert await king.recv_from(1) == [1, 2, 3]
    await king.close()
    await peer.close()


def _net_frame_totals():
    out = {}
    for name in ("net_frames_sent_total", "net_frames_recv_total"):
        fam = REG.counter(name, labelnames=("peer", "sid"))
        out[name] = sum(c.value for _, c in fam._items())
    return out


def test_wire_accounting_reconciles_tx_vs_rx():
    """Every frame a healthy star writes (SYN/SYNACK handshake included)
    must be counted on BOTH sides: after a bring-up + one exchange, the
    process-wide sent and received frame totals advance identically."""
    before = _net_frame_totals()

    async def run():
        cfg = NetConfig(
            op_timeout_s=5.0, connect_timeout_s=5.0,
            heartbeat_interval_s=0.0,
        )
        a, b = ChannelIO.pair()
        king_t = asyncio.create_task(ProdNet.king_from_ios({1: a}, 2, cfg))
        peer_t = asyncio.create_task(ProdNet.peer_from_io(1, b, 2, cfg))
        king, peer = await king_t, await peer_t
        await peer.send_to(0, "ping")
        assert await king.recv_from(1) == "ping"
        await king.send_to(1, "pong")
        assert await peer.recv_from(0) == "pong"
        await king.close()
        await peer.close()

    asyncio.run(run())
    after = _net_frame_totals()
    sent = after["net_frames_sent_total"] - before["net_frames_sent_total"]
    recv = after["net_frames_recv_total"] - before["net_frames_recv_total"]
    assert sent == recv == 4  # SYN + SYNACK + 2 DATA


def test_metrics_endpoint_golden(circuit):
    """Scrape GET /metrics from a live test server and validate every line
    with the strict parser; the acceptance series must be present and
    well-typed, with real samples."""
    root, cid, wtns = circuit

    async def run():
        server = ApiServer(
            CircuitStore(root), ServiceConfig(workers=1, queue_bound=8)
        )
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            await _populate_prodnet_bytes()
            # one real job through the queue: job/cache series get samples
            resp = await client.post(
                "/jobs/prove",
                data={"circuit_id": cid, "witness_file": wtns},
            )
            body = await resp.json()
            assert resp.status == 202, body
            jid = body["jobId"]
            while True:
                resp = await client.get(f"/jobs/{jid}")
                st = await resp.json()
                if st["state"] in ("DONE", "FAILED", "CANCELLED"):
                    break
                await asyncio.sleep(0.05)
            assert st["state"] == "DONE", st
            # the job's span tree rides the status DTO
            spans = st["metrics"]["spans"]
            root_names = [s["name"] for s in spans]
            assert "job" in root_names
            job_span = spans[root_names.index("job")]
            assert job_span["attrs"]["job"] == jid
            assert [c["name"] for c in job_span["children"]]  # phases nest

            resp = await client.get("/metrics")
            assert resp.status == 200
            assert resp.content_type == "text/plain"
            return await resp.text(), st
        finally:
            await client.close()

    text, status = asyncio.run(run())
    types, samples = parse_prometheus(text)

    # acceptance series, correctly typed
    assert types["net_bytes_sent_total"] == "counter"
    assert types["collective_seconds"] == "histogram"
    assert types["crs_cache_hits_total"] == "counter"
    assert types["job_phase_seconds"] == "histogram"

    # real samples behind them
    assert samples[("net_bytes_sent_total", (("peer", "0"), ("sid", "0")))] > 0
    coll_buckets = [
        k for k in samples
        if k[0] == "collective_seconds_bucket"
        and ("op", "send_to") in k[1]
    ]
    assert coll_buckets, "collective_seconds has no bucket series"
    # cumulative buckets are monotone and end at the series count
    for name, labels in list(samples):
        if not name.endswith("_bucket"):
            continue
        base = dict(labels)
        le = base.pop("le")
        if le != "+Inf":
            continue
        count_key = (
            name[: -len("_bucket")] + "_count",
            tuple((k, v) for k, v in labels if k != "le"),
        )
        assert samples[(name, labels)] == samples[count_key]
    assert (
        samples[("jobs_finished_total", (("state", "DONE"),))] >= 1
    )
    assert samples[("job_queue_wait_seconds_count", ())] >= 1
    # the single-prover job missed the CRS cache at most; the counters
    # moved (hits + misses >= 1 over process lifetime)
    assert (
        samples.get(("crs_cache_hits_total", ()), 0)
        + samples.get(("crs_cache_misses_total", ()), 0)
    ) >= 0


# -- exposition parsing + federation snapshot math (fleet observatory) -------


def test_parse_exposition_roundtrips_the_renderer():
    reg = tm.MetricsRegistry()
    c = reg.counter("fx_total", "a counter", ("tenant",))
    c.labels(tenant='we"ird\\t').inc(3)
    reg.gauge("fx_gauge", "a gauge").set(-2.5)
    h = reg.histogram("fx_seconds", "a histogram", ("kind",),
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 9.0):
        h.labels(kind="prove").observe(v)
    fams = tm.parse_exposition(reg.render_prometheus())
    assert fams["fx_total"].kind == "counter"
    assert fams["fx_gauge"].kind == "gauge"
    assert fams["fx_seconds"].kind == "histogram"
    # escaped label values round-trip
    (sname, labels, value), = [
        s for s in fams["fx_total"].samples if s[0] == "fx_total"
    ]
    assert labels == {"tenant": 'we"ird\\t'} and value == 3.0
    # histogram suffixes attribute to the base family, +Inf parses
    names = {s[0] for s in fams["fx_seconds"].samples}
    assert names == {"fx_seconds_bucket", "fx_seconds_sum",
                     "fx_seconds_count"}
    inf_buckets = [
        s for s in fams["fx_seconds"].samples
        if s[0].endswith("_bucket") and s[1]["le"] == "+Inf"
    ]
    assert inf_buckets[0][2] == 3.0
    # a spec-legal trailing millisecond timestamp parses (and is
    # discarded) — exporters/sidecars append them
    fam = tm.parse_exposition("ts_total 5 1700000000000\n")["ts_total"]
    assert fam.samples == [("ts_total", {}, 5.0)]
    # a malformed line is loud, not silently dropped
    with pytest.raises(ValueError):
        tm.parse_exposition("fx_total{tenant=unquoted} 1\n")
    with pytest.raises(ValueError):
        tm.parse_exposition("fx_total 1 garbage\n")


def test_histogram_snapshots_merge_across_label_dims():
    reg = tm.MetricsRegistry()
    h = reg.histogram("js", "x", ("kind", "replica"), buckets=(1.0, 10.0))
    h.labels(kind="prove", replica="a").observe(0.5)
    h.labels(kind="prove", replica="b").observe(5.0)
    h.labels(kind="mpc", replica="a").observe(5.0)
    fam = tm.parse_exposition(reg.render_prometheus())["js"]
    # group by kind: replicas merge (cumulative counts add)
    by_kind = tm.histogram_snapshots(fam, group_by=("kind",))
    prove = by_kind[("prove",)]
    assert prove.count == 2 and prove.sum == pytest.approx(5.5)
    assert prove.cumulative == [1.0, 2.0, 2.0]
    # group by nothing: one fleet-wide snapshot
    (all_snap,) = tm.histogram_snapshots(fam).values()
    assert all_snap.count == 3 and all_snap.cumulative[-1] == 3.0


def test_histogram_quantile_interpolates_and_clamps():
    snap = tm.HistogramSnapshot(
        bounds=(1.0, 2.0, float("inf")),
        cumulative=[4.0, 8.0, 10.0],
        sum=0.0,
        count=10.0,
    )
    # rank 5 of 10 lands in the (1, 2] bucket: 1 + (5-4)/4
    assert tm.histogram_quantile(snap, 0.5) == pytest.approx(1.25)
    # ranks in the +Inf bucket answer the highest finite bound
    assert tm.histogram_quantile(snap, 0.99) == pytest.approx(2.0)
    # the empty snapshot is 0, not a crash
    empty = tm.HistogramSnapshot((), [], 0.0, 0.0)
    assert tm.histogram_quantile(empty, 0.95) == 0.0


def test_compile_listener_zero_delta_on_second_call():
    """On jax's own clocks since ISSUE 23: a call that jax serves from its
    jit cache must add NOTHING to the trace/compile counters — a
    function's series is the whole of what its programs cost (the
    benchmark's `setup_trace_msm_s` reads it), and `jax_compiles_total`
    only moves on a real (re)compile, which is what makes it an alarm."""
    import jax
    import jax.numpy as jnp

    from distributed_groth16_tpu.telemetry import compile as tcompile

    reg = tm.registry()
    trace = reg.family("jax_trace_seconds_total").labels(fn="_t_hit")
    comp = reg.family("jax_compile_seconds_total").labels(fn="_t_hit")
    compiles = reg.family("jax_compiles_total")
    tj = tcompile.named_jit("_t_hit", lambda v: (v * 5.0).sum())
    x = jnp.arange(32, dtype=jnp.float32)
    n0 = compiles.value
    jax.block_until_ready(tj(x))  # first call: traced, lowered, compiled
    assert trace.value > 0.0 and comp.value > 0.0
    assert compiles.value >= n0 + 1
    after = (trace.value, comp.value, compiles.value)
    jax.block_until_ready(tj(x))  # jit-cache hit: every delta exactly 0
    assert after == (trace.value, comp.value, compiles.value)


def test_kernel_buckets_are_sub_millisecond():
    assert min(tm.DEFAULT_KERNEL_BUCKETS) < 0.001
    assert list(tm.DEFAULT_KERNEL_BUCKETS) == sorted(
        tm.DEFAULT_KERNEL_BUCKETS
    )
    fam = tm.registry().family("transfer_seconds")
    assert fam is not None and fam.buckets == tuple(
        tm.DEFAULT_KERNEL_BUCKETS
    )
