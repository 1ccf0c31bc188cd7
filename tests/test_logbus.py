"""Logging-spine tests (telemetry/logbus.py; docs/OBSERVABILITY.md
"Logging spine").

Unit layer: ambient enrichment (span chain / job contextvar / bind /
replica id), explicit-extras precedence, ring bounds + query filters +
the since cursor, storm suppression (synthetic record + counters),
runtime secret redaction, WARN+ instant events, and setup() idempotence.

Service layer: `GET /logs` filters, the job DTO `logs` tail surviving
the terminal compaction, the ERROR instant event in the job's Chrome
trace, and the flight-recorder dump carrying the ring tail — one
injected failure exercising the whole correlation story.
"""

import asyncio
import json
import logging
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from distributed_groth16_tpu.api.server import ApiServer
from distributed_groth16_tpu.api.store import CircuitStore
from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
from distributed_groth16_tpu.parallel.net import job_context
from distributed_groth16_tpu.telemetry import flight, logbus, metrics, tracing
from distributed_groth16_tpu.utils.config import ServiceConfig

POLL_DEADLINE_S = 300.0


@pytest.fixture(autouse=True)
def fresh_spine():
    """Every test gets a pristine ring/handler (the spine is process-
    global by design; tests must not read each other's records)."""
    logbus.reset_for_tests()
    yield
    logbus.reset_for_tests()
    logbus.set_replica(None)


def _log(name="distributed_groth16_tpu.test.logbus"):
    return logging.getLogger(name)


# -- enrichment ---------------------------------------------------------------


def test_ambient_enrichment_from_span_chain_and_bind():
    logbus.setup(console=False)
    logbus.set_replica("r-test")
    log = _log()
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        with tracing.span("job", job="j1", attrs={"trace": "t-abc"},
                          party=2):
            with tracing.span("prove.A"):  # nested: walks to the parent
                with logbus.bind(tenant="acme", priority="batch"):
                    log.info("inside %s", "the proof")
    (r,) = logbus.ring().query(job="j1")
    assert r["trace"] == "t-abc"
    assert r["job"] == "j1"
    assert r["party"] == 2
    assert r["span"] == "prove.A"
    assert r["tenant"] == "acme"
    assert r["priority"] == "batch"
    assert r["replica"] == "r-test"
    assert r["logger"] == "test.logbus"
    assert r["msg"] == "inside the proof"
    assert r["template"] == "inside %s"
    assert isinstance(r["tsPcNs"], int)


def test_job_contextvar_enriches_without_spans():
    logbus.setup(console=False)
    with job_context("j-ctx"):
        _log().info("mid-collective")
    (r,) = logbus.ring().query(job="j-ctx")
    assert r["job"] == "j-ctx"
    assert "trace" not in r  # no span chain, no trace attr


def test_explicit_extras_beat_ambient():
    logbus.setup(console=False)
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        with tracing.span("job", job="ambient", attrs={"trace": "t-amb"}):
            _log().warning(
                "handled elsewhere",
                extra={"job": "explicit", "trace": "t-exp"},
            )
    (r,) = logbus.ring().query(job="explicit")
    assert r["trace"] == "t-exp"
    assert logbus.ring().query(job="ambient") == []


def test_exception_recorded_and_bind_filters_empty():
    logbus.setup(console=False)
    log = _log()
    with logbus.bind(tenant="", priority=None):
        try:
            raise RuntimeError("boom 123456789012345678901234")
        except RuntimeError:
            log.exception("it failed")
    (r,) = logbus.ring().query(level="ERROR")
    assert "tenant" not in r and "priority" not in r
    assert "RuntimeError" in r["exc"]
    assert "<bigint>" in r["exc"]  # redaction reaches tracebacks too


# -- ring bounds, query, cursor ----------------------------------------------


def test_ring_bounded_and_since_cursor():
    ring = logbus.LogRing(maxlen=8)
    for i in range(20):
        ring.append({"levelNo": 20, "logger": "x", "msg": str(i)})
    assert len(ring) == 8
    out = ring.query(limit=100)
    assert [r["msg"] for r in out] == [str(i) for i in range(12, 20)]
    assert out[0]["seq"] == 13  # seq keeps counting across overflow
    cursor = out[-3]["seq"]
    newer = ring.query(since=cursor)
    assert [r["msg"] for r in newer] == ["18", "19"]
    assert ring.query(since=out[-1]["seq"]) == []


def test_query_filters_level_logger_limit():
    logbus.setup(console=False)
    logging.getLogger("distributed_groth16_tpu.alpha").info("a-info")
    logging.getLogger("distributed_groth16_tpu.alpha.sub").warning("a-warn")
    logging.getLogger("distributed_groth16_tpu.beta").error("b-err")
    ring = logbus.ring()
    assert [r["msg"] for r in ring.query(level="WARNING")] == [
        "a-warn", "b-err",
    ]
    assert [r["msg"] for r in ring.query(logger="alpha")] == [
        "a-info", "a-warn",
    ]
    assert [r["msg"] for r in ring.query(limit=1)] == ["b-err"]


# -- storm suppression --------------------------------------------------------


def test_storm_suppression_emits_synthetic_record_and_counts(monkeypatch):
    monkeypatch.setenv("DG16_LOG_STORM_BURST", "5")
    # 100/s: the 50 sends below may take 50 ms and still leave 40 of them
    # suppressed. At 1000/s they had 5 ms, and a loop of 1 ms took 6-7
    # beside another worker's XLA:CPU compile (two whole runs, PR 29).
    monkeypatch.setenv("DG16_LOG_STORM_RATE", "100")
    logbus.setup(console=False)
    log = _log()
    before = metrics.registry().snapshot().get(
        'log_dropped_total{reason="storm"}', 0.0
    )
    for i in range(50):
        log.info("retrying peer %d", i)
    time.sleep(0.05)  # at 100/s the bucket is full again
    log.info("retrying peer %d", 99)
    records = logbus.ring().query(limit=1000)
    msgs = [r["msg"] for r in records]
    assert "retrying peer 0" in msgs and "retrying peer 4" in msgs
    assert "retrying peer 20" not in msgs  # suppressed mid-storm
    assert msgs[-1] == "retrying peer 99"
    synthetic = [r for r in records if r["msg"].startswith("suppressed ")]
    assert synthetic and all(
        "similar record" in r["msg"] for r in synthetic
    )
    # conservation: every one of the 51 sends was either admitted or
    # counted by a synthetic flush (token refill timing may split the
    # storm into several flushes — the totals still have to add up)
    suppressed_total = sum(r["suppressed"] for r in synthetic)
    admitted = len(records) - len(synthetic)
    assert admitted + suppressed_total == 51
    assert suppressed_total >= 40
    after = metrics.registry().snapshot().get(
        'log_dropped_total{reason="storm"}', 0.0
    )
    assert after - before == suppressed_total
    # a DIFFERENT template is its own bucket — never suppressed by the storm
    log.info("unrelated %s", "template")
    assert logbus.ring().query(limit=1)[0]["msg"] == "unrelated template"


def test_storm_suppression_off_with_nonpositive_rate(monkeypatch):
    monkeypatch.setenv("DG16_LOG_STORM_RATE", "0")
    logbus.setup(console=False)
    log = _log()
    for i in range(40):
        log.info("flood %d", i)
    assert len(logbus.ring().query(limit=1000)) == 40


# -- redaction ----------------------------------------------------------------


def test_secret_named_extras_never_reach_the_ring():
    logbus.setup(console=False)
    _log().error(
        "share mismatch",
        extra={"witness_share": 1234, "wtnsDigest": "abc", "rounds": 3},
    )
    (r,) = logbus.ring().query(level="ERROR")
    assert r["fields"]["witness_share"] == logbus.REDACTED
    assert r["fields"]["wtnsDigest"] == logbus.REDACTED
    assert r["fields"]["rounds"] == 3
    assert "1234" not in json.dumps(r)


def test_bigint_redaction_in_messages():
    logbus.setup(console=False)
    _log().warning("element %d leaked", 2**255 - 19)
    (r,) = logbus.ring().query(level="WARNING")
    assert "<bigint>" in r["msg"]
    assert str(2**255 - 19) not in r["msg"]


# -- instant events -----------------------------------------------------------


def test_warning_paints_instant_event_into_active_buffers():
    logbus.setup(console=False)
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        with tracing.span("job", job="j9", attrs={"trace": "t-9"}, party=1):
            _log().info("info stays off the timeline")
            _log().error("party died")
    instants = [e for e in buf.events() if e.get("ph") == "i"]
    assert len(instants) == 1
    (ev,) = instants
    assert ev["name"] == "log.ERROR"
    assert ev["args"]["msg"] == "party died"
    assert ev["args"]["trace"] == "t-9"
    assert ev["args"]["job"] == "j9"
    assert ev["pid"] == 1
    # the span tree ignores instants instead of KeyError-ing on "dur"
    tree = buf.span_tree()
    assert [n["name"] for n in tree] == ["job"]


def test_instant_noop_when_idle():
    assert not tracing.active()
    assert tracing.instant("log.ERROR", args={"x": 1}) is False


# -- setup() ------------------------------------------------------------------


def test_setup_idempotent_and_level_knob(monkeypatch):
    monkeypatch.setenv("DG16_LOG_LEVEL", "WARNING")
    logbus.setup(console=False)
    logbus.setup(console=False)
    pkg = logging.getLogger(logbus.PACKAGE_LOGGER)
    handlers = [
        h for h in pkg.handlers if isinstance(h, logbus.LogBusHandler)
    ]
    assert len(handlers) == 1
    _log().info("filtered out")
    _log().warning("kept")
    assert [r["msg"] for r in logbus.ring().query(limit=10)] == ["kept"]


def test_json_console_formatter_enriches():
    fmt = logbus.JsonFormatter()
    rec = logging.LogRecord(
        "distributed_groth16_tpu.x", logging.INFO, __file__, 1,
        "n=%d", (7,), None,
    )
    with tracing.span("job", job="j-json"):
        line = fmt.format(rec)
    doc = json.loads(line)
    assert doc["msg"] == "n=7"
    assert doc["level"] == "INFO"


# -- service layer: /logs, DTO tail, trace instant, flight dump ---------------


@pytest.fixture(scope="module")
def circuit(tmp_path_factory):
    cs = mult_chain_circuit(9, 7)
    r1cs, z = cs.finish()
    root = str(tmp_path_factory.mktemp("logbus_store"))
    cid = CircuitStore(root).save_circuit("lb", write_r1cs(r1cs), b"")
    bad = list(z)
    bad[-1] = (bad[-1] + 1) % 97  # breaks the last constraint
    return root, cid, write_wtns(bad)


def test_failed_job_correlates_logs_dto_trace_and_flight(circuit, tmp_path):
    root, cid, bad_wtns = circuit
    flight.configure(str(tmp_path))
    try:

        async def run():
            server = ApiServer(
                CircuitStore(root),
                ServiceConfig(workers=1, replica_id="r-logbus"),
            )
            client = TestClient(TestServer(server.app()))
            await client.start_server()
            try:
                resp = await client.post(
                    "/jobs/prove",
                    data={"circuit_id": cid, "witness_file": bad_wtns},
                    headers={"X-DG16-Trace": "t-injected",
                             "X-DG16-Tenant": "acme"},
                )
                body = await resp.json()
                assert resp.status == 202, body
                jid = body["jobId"]
                deadline = time.monotonic() + POLL_DEADLINE_S
                while time.monotonic() < deadline:
                    resp = await client.get(f"/jobs/{jid}")
                    dto = await resp.json()
                    if dto["state"] in ("DONE", "FAILED", "CANCELLED"):
                        break
                    await asyncio.sleep(0.05)
                assert dto["state"] == "FAILED", dto

                # (1) GET /logs filtered by the injected trace id
                resp = await client.get(
                    "/logs", params={"trace": "t-injected", "level": "ERROR"}
                )
                logs = await resp.json()
                assert resp.status == 200
                assert logs["replicaId"] == "r-logbus"
                recs = logs["records"]
                assert recs, "the executor ERROR must reach /logs"
                err = recs[-1]
                assert err["job"] == jid
                assert err["trace"] == "t-injected"
                assert err["replica"] == "r-logbus"
                assert err["tenant"] == "acme"  # bound by the worker
                assert "failed" in err["msg"]
                # the since cursor: nothing new past the tail
                resp = await client.get(
                    "/logs", params={"since": str(logs["nextSince"]),
                                     "trace": "t-injected",
                                     "level": "ERROR"}
                )
                assert (await resp.json())["records"] == []
                # bad level is a 400, not a 500
                resp = await client.get("/logs", params={"level": "LOUD"})
                assert resp.status == 400

                # (2) the DTO carries the job's log tail past compaction
                tail = dto["logs"]
                assert any(
                    r["level"] == "ERROR" and r.get("job") == jid
                    for r in tail
                ), tail

                # (3) the ERROR rides the job's Chrome trace as an instant
                resp = await client.get(f"/jobs/{jid}/trace")
                trace = await resp.json()
                instants = [
                    e for e in trace["traceEvents"]
                    if e.get("ph") == "i" and e["name"] == "log.ERROR"
                ]
                assert instants, "log.ERROR instant missing from the trace"
                assert instants[0]["args"]["trace"] == "t-injected"
            finally:
                await client.close()

        asyncio.run(run())

        # (4) a flight dump written after the fault carries the ring tail
        path = flight.dump("logbus_test")
        assert path is not None
        with open(path) as f:
            record = json.load(f)
        assert any(
            r.get("level") == "ERROR" and r.get("trace") == "t-injected"
            for r in record["logs"]
        ), "flight dump must carry the correlated log tail"
    finally:
        flight.disable()
