"""The host's work outside a job's phases (PR 34): Python's collector
(telemetry/host.py), jax's own tracing, lowering and compiling
(telemetry/compile.py), the server's background ticks and its front door
(api/server.py), each a span that reaches the device trace while a
capture is live, and a counter that is always on.

The registry is process-wide: numeric checks compare deltas."""

import asyncio
import gc
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_groth16_tpu.telemetry import host, tracing
from distributed_groth16_tpu.telemetry import metrics as tm

REG = tm.registry()


def _value(family: str, **labels) -> float:
    """A series of the /metrics text; 0 where the family has none yet."""
    inner = ",".join(f'{k}="{v}"' for k, v in labels.items())
    series = f"{family}{{{inner}}} "
    for line in REG.render_prometheus().splitlines():
        if line.startswith(series):
            return float(line.split()[-1])
    return 0.0


class _Recorder:
    """A fake annotator: every annotation's name, attrs, the thread that
    entered and left it, and the stats noted on it."""

    def __init__(self):
        self.events = []

    def __call__(self, name, attrs=None):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.events.append(
                    ("enter", name, dict(attrs or {}), threading.get_ident())
                )
                return self

            def __exit__(self, *exc):
                rec.events.append(("exit", name, None, threading.get_ident()))
                return False

            def set_metadata(self, **kw):
                rec.events.append(("meta", name, kw, threading.get_ident()))

        return _Ann()

    def named(self, prefix):
        return [e for e in self.events if e[1].startswith(prefix)]


@pytest.fixture
def annotator():
    rec = _Recorder()
    tracing.set_annotator(rec)
    try:
        yield rec
    finally:
        tracing.set_annotator(None)


# -- Python's collector --------------------------------------------------------


def test_a_full_collection_raises_its_generations_counters():
    n0 = _value("python_gc_collections_total", generation="2")
    s0 = _value("python_gc_seconds_total", generation="2")
    gc.collect(2)
    assert _value("python_gc_collections_total", generation="2") == n0 + 1
    assert _value("python_gc_seconds_total", generation="2") > s0


def test_the_counters_print_zero_unraised_for_every_generation_and_task():
    text = REG.render_prometheus()
    for g in ("0", "1", "2"):
        assert f'python_gc_collections_total{{generation="{g}"}} ' in text
        assert f'python_gc_seconds_total{{generation="{g}"}} ' in text
    for task in host.TASKS:
        assert f'background_seconds_total{{task="{task}"}} ' in text


def test_a_full_collection_is_one_gc_annotation_on_one_thread(annotator):
    gc.collect(2)
    evs = annotator.named("gc")
    assert [e[0] for e in evs] == ["enter", "exit"]
    assert evs[0][2] == {"generation": 2}
    assert evs[0][3] == evs[1][3] == threading.get_ident()


@pytest.mark.parametrize("generation,annotated", [(0, False), (1, True)])
def test_young_collections_annotate_from_generation_one(
        annotator, generation, annotated):
    gc.collect(generation)
    evs = annotator.named("gc")
    assert bool(evs) is annotated
    if annotated:
        assert evs[0][2] == {"generation": generation}


def test_with_no_annotator_and_no_buffer_the_span_stays_the_noop():
    assert tracing._annotator is None
    assert tracing.span("idle") is tracing.NOOP
    for record in (False, True):
        assert tracing.host_span(
            "gc", {"generation": 2}, record=record) is tracing.NOOP
    gc.collect(2)  # and the hook records nowhere
    assert tracing.span("idle") is tracing.NOOP


def test_a_full_collection_records_into_the_jobs_buffer_a_young_one_not():
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        with tracing.span("job", job="j-gc"):
            gc.collect(1)
            gc.collect(2)
    (job,) = buf.span_tree()
    names = [c["name"] for c in job["children"]]
    assert names == ["gc"]
    assert job["children"][0]["attrs"] == {"generation": 2}


def test_a_collection_inside_a_buffers_lock_does_not_deadlock():
    # the collector runs between any two bytecodes, also while this
    # thread holds the buffer's lock; the gc span then records there
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        with buf._lock:
            gc.collect(2)
    assert [e["name"] for e in buf.events()] == ["gc"]


# -- jax's tracing, lowering and compiling -------------------------------------


def test_an_annotation_only_span_never_becomes_the_current_one(annotator):
    # a span opened while jax traces keeps its real parent
    with tracing.host_span("jax.trace", {"fn": "f"}, record=False):
        assert tracing.current() is None
    assert [e[0] for e in annotator.named("jax.trace")] == ["enter", "exit"]


def test_a_fresh_jit_is_trace_lower_compile_nested_lifo(annotator):
    def fresh_fn_pr34(x):
        return x * 3 + 2

    f = jax.jit(fresh_fn_pr34)
    f(jnp.arange(7, dtype=jnp.int32)).block_until_ready()
    evs = annotator.named("jax.")
    # the stack: each exit closes the innermost open annotation
    stack, closed = [], []
    for kind, name, attrs, tid in evs:
        assert tid == threading.get_ident()
        if kind == "enter":
            stack.append((name, attrs["fn"]))
        else:
            closed.append(stack.pop())
    assert not stack
    mine = [c for c in closed if c[1] == "fresh_fn_pr34"]
    assert sorted(n for n, _ in mine) == ["jax.compile", "jax.lower",
                                          "jax.trace"]
    # each ran before the next began: trace, then lower, then compile
    order = [n for kind, n, attrs, _ in evs
             if kind == "enter" and attrs["fn"] == "fresh_fn_pr34"]
    assert order == ["jax.trace", "jax.lower", "jax.compile"]
    # a cache hit is none of them
    annotator.events.clear()
    f(jnp.arange(7, dtype=jnp.int32)).block_until_ready()
    assert annotator.named("jax.") == []


def test_a_jit_inside_a_job_annotates_and_leaves_the_jobs_tree_alone(
        annotator):
    # a cold job traces hundreds of programs: recorded, their spans would
    # crowd the round's own out of the job's bounded tree
    def fresh_fn_pr34_job(x):
        return x - 5

    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        with tracing.span("job", job="j-jax"):
            jax.jit(fresh_fn_pr34_job)(jnp.ones(5)).block_until_ready()
    (job,) = buf.span_tree()
    assert job["children"] == []
    assert [e[1] for e in annotator.events
            if e[0] == "enter" and e[2].get("fn") == "fresh_fn_pr34_job"] \
        == ["jax.trace", "jax.lower", "jax.compile"]


# -- background ticks ----------------------------------------------------------


def test_one_tick_of_each_loop_is_a_bg_span_and_its_seconds(tmp_path):
    from distributed_groth16_tpu.api.server import ApiServer
    from distributed_groth16_tpu.api.store import CircuitStore
    from distributed_groth16_tpu.utils.config import ServiceConfig, SLOConfig

    server = ApiServer(
        CircuitStore(str(tmp_path)), ServiceConfig(workers=1),
        slo_cfg=SLOConfig(target_s=10.0, sample_s=0.01),
    )
    server.devmem_sample_s = 0.01
    before = {t: _value("background_seconds_total", task=t)
              for t in host.TASKS}
    buf = tracing.enable_global()
    try:
        async def run():
            tasks = [asyncio.create_task(server._devmem_loop()),
                     asyncio.create_task(server._slo_loop())]
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                names = {e["name"] for e in buf.events()}
                if {"bg.devmem", "bg.slo"} <= names:
                    break
                await asyncio.sleep(0.01)
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

        asyncio.run(run())
        evs = buf.events()
    finally:
        tracing.disable_global()
    names = {e["name"] for e in evs}
    assert {"bg.devmem", "bg.slo"} <= names
    # the devmem tick's span is on the thread that read the stats
    loop_tids = {e["tid"] for e in evs if e["name"] == "bg.slo"}
    assert not loop_tids & {e["tid"] for e in evs if e["name"] == "bg.devmem"}
    for task in host.TASKS:
        assert _value("background_seconds_total", task=task) > before[task]


# -- the front door ------------------------------------------------------------


def test_the_front_door_counts_by_route_and_names_the_job(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from distributed_groth16_tpu.api.server import ApiServer
    from distributed_groth16_tpu.api.store import CircuitStore
    from distributed_groth16_tpu.utils.config import ServiceConfig

    prove, result = "/jobs/prove", "/jobs/{job_id}/result"
    before = {
        r: (_value("http_server_seconds_total", route=r),
            _value("http_server_requests_total", route=r))
        for r in (prove, result)
    }

    buf = tracing.enable_global()
    try:
        async def run():
            server = ApiServer(
                CircuitStore(str(tmp_path)), ServiceConfig(workers=1)
            )
            client = TestClient(TestServer(server.app()))
            await client.start_server()
            try:
                # a circuit nobody saved: the job is accepted, then fails
                resp = await client.post(
                    prove, data={"circuit_id": "nope", "witness_file": b"x"})
                assert resp.status == 202
                job_id = (await resp.json())["jobId"]
                got = await client.get(f"/jobs/{job_id}/result")
                return job_id, got.status
            finally:
                await client.close()

        job_id, status = asyncio.run(run())
        evs = [e for e in buf.events() if e["name"] == "http"]
    finally:
        tracing.disable_global()
    by_route = {e["args"]["route"]: e["args"] for e in evs}
    assert by_route[prove]["job"] == job_id
    assert by_route[prove]["status"] == 202
    assert by_route[prove]["method"] == "POST"
    assert by_route[result]["job"] == job_id
    assert by_route[result]["status"] == status
    for r in (prove, result):
        seconds = _value("http_server_seconds_total", route=r)
        count = _value("http_server_requests_total", route=r)
        s0, n0 = before[r]
        assert count == n0 + 1
        assert seconds > s0
