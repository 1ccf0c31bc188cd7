"""The proving job's time account (ISSUE 23; docs/OBSERVABILITY.md "Which
clock a span is on"): the direct children of `job` partition it and reach
the DTO's `phases`, `prove_single` carries the MPC path's stage names with
the dispatch clock marked, the profiler bridge starts captures with the
Python tracer off and puts the job id on the `job` annotation, and the
tree MSM and the limb NTT carry their names into what a device trace
shows (program names per group, `jax.named_scope` per stage).

CPU, tiny circuit: these check the account, never a speed.
"""

import asyncio
import re

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from distributed_groth16_tpu.api.server import ApiServer
from distributed_groth16_tpu.api.store import CircuitStore
from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
from distributed_groth16_tpu.ops import limb_kernels as lk
from distributed_groth16_tpu.ops import ntt_limb
from distributed_groth16_tpu.telemetry import profiler, tracing
from distributed_groth16_tpu.utils.config import ServiceConfig

TOP_LEVEL = ("load", "witness", "encode", "prove", "serialize")
CHILDREN = {
    "load": ("load.r1cs", "load.key"),
    "witness": ("witness.parse", "witness.check"),
    "prove": ("prove.r1cs",),
}
ENQUEUE_ONLY = ("prove.qap", "prove.h", "prove.A", "prove.B", "prove.C")


@pytest.fixture(scope="module")
def done_job(tmp_path_factory):
    """The status DTO of one DONE `prove` job, through the HTTP API."""
    r1cs, z = mult_chain_circuit(9, 7).finish()
    root = str(tmp_path_factory.mktemp("account_store"))
    cid = CircuitStore(root).save_circuit("acct", write_r1cs(r1cs), b"")

    async def run():
        server = ApiServer(
            CircuitStore(root), ServiceConfig(workers=1, queue_bound=4)
        )
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            resp = await client.post(
                "/jobs/prove",
                data={"circuit_id": cid, "witness_file": write_wtns(z)},
            )
            body = await resp.json()
            assert resp.status == 202, body
            while True:
                resp = await client.get(f"/jobs/{body['jobId']}")
                st = await resp.json()
                if st["state"] in ("DONE", "FAILED", "CANCELLED"):
                    return st
                await asyncio.sleep(0.02)
        finally:
            await client.close()

    st = asyncio.run(run())
    assert st["state"] == "DONE", st
    return st


def test_phases_hold_every_top_level_phase_and_child(done_job):
    want = set(TOP_LEVEL) | {c for cs in CHILDREN.values() for c in cs}
    assert want <= set(done_job["phases"])
    # top-level keys are those without a dot, and there is no other
    assert {k for k in done_job["phases"] if "." not in k} == set(TOP_LEVEL)


@pytest.mark.parametrize("parent", sorted(CHILDREN))
def test_a_phase_is_at_least_the_sum_of_its_children(done_job, parent):
    phases = done_job["phases"]
    children = sum(phases[c] for c in CHILDREN[parent])
    # as_millis rounds each phase to a microsecond
    assert phases[parent] >= children - 0.01


def test_top_level_phases_cover_the_job(done_job):
    """The account closes: what lies in no phase (the hand-over to the
    worker thread, the bookkeeping round `finishedAt`) is a small part of
    the job. Loose on purpose: it checks the account, not a speed."""
    job_ms = 1e3 * (done_job["finishedAt"] - done_job["startedAt"])
    named = sum(done_job["phases"][k] for k in TOP_LEVEL)
    assert named >= 0.9 * job_ms, (named, job_ms)
    assert named <= job_ms + 1.0


def _find(nodes, name):
    for n in nodes:
        if n["name"] == name:
            return n
    raise AssertionError(f"no span {name!r} among {[n['name'] for n in nodes]}")


def test_job_span_tree_is_the_phase_partition(done_job):
    job = _find(done_job["metrics"]["spans"], "job")
    assert [c["name"] for c in job["children"]] == list(TOP_LEVEL)
    for parent, kids in CHILDREN.items():
        got = [c["name"] for c in _find(job["children"], parent)["children"]]
        assert got[: len(kids)] == list(kids)


def test_prove_single_stages_and_their_clock(done_job):
    job = _find(done_job["metrics"]["spans"], "job")
    stages = _find(job["children"], "prove")["children"]
    # `prove.check` (ISSUE 33): the device's verdict on the witness is
    # read once the witness map is enqueued and before any MSM is
    at = ENQUEUE_ONLY.index("prove.h") + 1
    assert [s["name"] for s in stages] == [
        "prove.r1cs", *ENQUEUE_ONLY[:at], "prove.check", *ENQUEUE_ONLY[at:],
        "prove.decode",
    ]
    for name in ENQUEUE_ONLY:
        assert _find(stages, name)["attrs"]["clock"] == "dispatch"
    # host work, and the host's wait for the chip: wall time
    for name in ("prove.r1cs", "prove.check", "prove.decode"):
        assert "clock" not in _find(stages, name).get("attrs", {})


# -- the bridge to the device clock ------------------------------------------


class _Recorder:
    def __init__(self):
        self.starts, self.annotations = [], []

    def start_trace(self, log_dir, *args, **kw):
        self.starts.append((log_dir, args, kw))

    def stop_trace(self):
        pass

    def annotation(self, name, **kw):
        self.annotations.append((name, kw))
        return tracing.NOOP


def test_capture_starts_with_python_tracer_off_and_job_id_annotated(
    tmp_path, monkeypatch
):
    rec = _Recorder()
    monkeypatch.setattr(jax.profiler, "start_trace", rec.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", rec.stop_trace)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.annotation)
    p = profiler.Profiler(str(tmp_path))
    cap = p.start(duration_s=0)
    try:
        (log_dir, args, kw), = rec.starts
        assert log_dir == cap.directory and not args
        assert kw["profiler_options"].python_tracer_level == 0
        with tracing.span("job", job="j-123", attrs={"kind": "prove"}):
            with tracing.span("load"):
                pass
    finally:
        p.stop()
    assert rec.annotations == [("job", {"job_id": "j-123"}), ("load", {})]
    assert tracing.span("idle.after") is tracing.NOOP


# -- names in the device trace ------------------------------------------------

MSM_SCOPES = ("msm.sort", "msm.upsweep", "msm.fenwick", "msm.combine",
              "msm.horner")
# where a launch has a batch-affine level (ISSUE 29): its points normalised
# once, the affine levels, and the batched inversion inside both
MSM_AFFINE_SCOPES = ("msm.normalise", "msm.upsweep.affine", "msm.inverse")


def _lowered_msm(kind: str, *affine_min_adds) -> str:
    g, pts = {
        "g1": (lk.lg1(), (16, 3, 16)),
        "g2": (lk.lg2(), (16, 3, 2, 16)),
    }[kind]
    assert g.kind == kind
    return lk._MSM_TREE_JITS[kind].lower(
        g, jax.ShapeDtypeStruct(pts, jnp.uint32),
        jax.ShapeDtypeStruct((16, 16), jnp.uint32), 4, None,
        *affine_min_adds,
    ).as_text(debug_info=True)


def _module_name(text: str) -> str:
    return re.search(r"module @(\S+)", text).group(1)


@pytest.mark.parametrize("kind", ("g1", "g2"))
def test_tree_msm_program_is_named_by_group_and_scoped_by_stage(kind):
    text = _lowered_msm(kind)
    # what `XLA Modules` shows for a launch; kernel_groups/msm.json still
    # matches it by the substring
    assert _module_name(text) == f"jit__msm_tree_jit_{kind}"
    for scope in MSM_SCOPES:
        assert f"/{scope}/" in text, scope
    # 16 points: no level reaches the rule, no affine stage is named
    for scope in MSM_AFFINE_SCOPES:
        assert f"/{scope}/" not in text, scope


def test_tree_msm_with_affine_levels_names_their_stages_too():
    """The rule's constant set to 256 adds: the two widest levels of 64
    windows over 16 points (512 and 256 adds) are affine, the other two
    projective, so the program holds the five stages and the three more."""
    assert lk._affine_depth(64, 16, 256) == 2
    text = _lowered_msm("g1", 256)
    assert _module_name(text) == "jit__msm_tree_jit_g1"
    for scope in MSM_SCOPES + MSM_AFFINE_SCOPES:
        assert f"/{scope}/" in text, scope
    # the inversion sits inside the stage that called it
    assert "/msm.normalise/msm.inverse/" in text
    assert "/msm.upsweep.affine/msm.inverse/" in text


def test_tree_msm_programs_keep_their_names_and_count():
    """Six programs, two a form, each named for its group: the benchmark's
    `kernel_groups/*.json` and `setup_trace_msm_s` match these names by
    substring, so a renamed or a seventh program changes what they count."""
    names = sorted(
        jitted.__name__
        for jits in (lk._MSM_TREE_JITS, lk._MSM_LIMB0_JITS,
                     lk._MSM_LIMB0_FILL_JITS)
        for jitted in jits.values()
    )
    assert names == [
        "_msm_tree_jit_g1", "_msm_tree_jit_g1_limb0",
        "_msm_tree_jit_g1_limb0_fill", "_msm_tree_jit_g2",
        "_msm_tree_jit_g2_limb0", "_msm_tree_jit_g2_limb0_fill",
    ]
    assert lk._MSM_TREE_JITS["g1"].__wrapped__.__code__ is lk._msm_tree.__code__


def test_limb_ntt_steps_are_scoped():
    text = ntt_limb.ntt_limb.lower(
        jax.ShapeDtypeStruct((16, 4096), jnp.uint32), 4096, False
    ).as_text(debug_info=True)
    for scope in ("ntt.small", "ntt.twiddle", "ntt.transpose"):
        assert f"/{scope}/" in text, scope
