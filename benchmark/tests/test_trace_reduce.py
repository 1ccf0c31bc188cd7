"""The reduction from `.xplane.pb` to numbers: exact values on a trace
written out by hand in the test, and the same invariants on a small trace
recorded on a TPU v5e (`recorded_v5e.xplane.pb`, made by
`record_small_trace.py` in this directory)."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")

GROUPS = {"msm": ["_msm_tree_jit"], "ntt": ["_limb_ntt_route", "_ntt_core"]}
SPANS = ["job", "load", "witness", "prove.*", "dmsm"]


def _events(rows):
    """rows: (metadata id, start us, duration us)"""
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * 1e6)} "
        f"duration_ps: {int(d * 1e6)} }}" for m, s, d in rows
    )


def _meta(names):
    return "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items()
    )


# One chip, microseconds. Two whole jobs, [100, 500) and [600, 1000), and a
# launch before the first. Ops inside a launch overlap by 10 us once.
OPS = [(1, 20, 30),                                   # before any job
       (1, 200, 50), (2, 240, 60),                    # job 1: 200..300
       (1, 350, 100),                                 # job 1: 350..450
       (2, 700, 100), (1, 850, 100)]                  # job 2
MODULES = [(1, 20, 30), (1, 200, 100), (2, 350, 100),
           (1, 700, 100), (3, 850, 90)]
HOST = [(1, 100, 400), (2, 100, 90), (3, 300, 50), (4, 455, 40),
        (1, 600, 400), (2, 600, 95), (5, 610, 10)]
TEXT = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {_events(OPS)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {_events(MODULES)} }}
  lines {{ id: 3 name: "Steps" timestamp_ns: 0 {_events([(1, 0, 1000)])} }}
  {_meta({1: "jit__msm_tree_jit(123)", 2: "jit__limb_ntt_route(9)",
          3: "jit_convert_element_type(4)"})}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 7 name: "worker" timestamp_ns: 0 {_events(HOST)} }}
  {_meta({1: "job", 2: "load", 3: "prove.h", 4: "$worker.py:1 run",
          5: "PjitFunction(f)"})}
}}
"""


@pytest.fixture(scope="module")
def handmade(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "handmade.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TEXT))
    return str(path)


def test_union_and_merge():
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.merge([(5, 6), (0, 2), (2, 3)]) == [(0, 3), (5, 6)]
    assert tr.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]
    assert tr.program_name("jit__msm_tree_jit(123)") == "_msm_tree_jit"


def test_busy_union_and_idle_share(handmade):
    out = tr.reduce_trace(handmade, 1, GROUPS, SPANS)
    # 30 + (200..300) + 100 + 100 + 100, the overlap counted once
    assert out["busy_s"] == pytest.approx(430e-6)
    assert out["window_s"] == pytest.approx(980e-6)  # 20 .. 1000
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(550 / 980)
    assert out["launches"] == 5
    assert out["device_planes"] == ["/device:TPU:0"]


def test_per_job_numbers_lie_between_job_boundaries(handmade):
    pj = tr.reduce_trace(handmade, 1, GROUPS, SPANS)["per_job"]
    assert pj["jobs"] == 2
    assert pj["interval_s"] == pytest.approx(900e-6)  # 100 .. 1000
    assert pj["busy_s"] == pytest.approx(400e-6 / 2)  # not the launch at 20
    assert pj["launches"] == 2.0  # exact: 4 launches in 2 jobs


def test_grouping_by_program_name(handmade):
    out = tr.reduce_trace(handmade, 1, GROUPS, SPANS)
    pj = out["per_job"]
    assert pj["group_s"]["msm"] == pytest.approx(200e-6 / 2)
    assert pj["group_s"]["ntt"] == pytest.approx(100e-6 / 2)
    assert out["device_ops"][0] == ["_msm_tree_jit", pytest.approx(230e-6)]
    assert [n for n, _ in out["device_ops"]] == [
        "_msm_tree_jit", "_limb_ntt_route", "convert_element_type"]


def test_gap_attribution(handmade):
    gaps = tr.reduce_trace(handmade, 1, GROUPS, SPANS)["idle_gaps"]
    longest, totals = gaps[:5], dict(gaps[5:])
    # idle inside [100, 1000): 100..200 under load (90 of its 100 us),
    # 300..350 under prove.h, 450..700 across the pause between the two
    # jobs (neither covers half of it), 800..850 and 950..1000 under job
    assert longest[0] == ["no_span", pytest.approx(250e-6)]
    assert longest[1] == ["load", pytest.approx(100e-6)]
    assert totals["all:job"] == pytest.approx(100e-6)
    assert totals["all:prove.h"] == pytest.approx(50e-6)
    # the Python tracer's call and the runtime's event never name a gap
    assert not any("$" in n or "Pjit" in n for n, _ in gaps)


def test_a_gap_no_span_covers(handmade):
    out = tr.reduce_trace(handmade, 1, GROUPS, ["load"])  # no job spans
    assert out["per_job"] is None
    assert "no_span" in dict(out["idle_gaps"])


def test_no_device_plane_is_an_error(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 2 name: "/host:CPU" }'))
    with pytest.raises(ValueError, match="nothing ran on a chip"):
        tr.reduce_trace(str(path), 1, GROUPS, SPANS)


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recording")
def test_recorded_tpu_trace():
    """Three `job` spans, each a `load` sleep of 20 ms and then two launches
    of `bench_probe_program`, recorded on one TPU v5e chip."""
    out = tr.reduce_trace(
        RECORDED, 1, {"probe": ["bench_probe_program"]}, ["job", "load"])
    assert out["device_planes"] == ["/device:TPU:0"]
    assert 0 < out["busy_s"] < out["window_s"]
    pj = out["per_job"]
    assert pj["jobs"] == 3
    assert pj["launches"] == 2.0
    assert 0 < pj["group_s"]["probe"] <= pj["busy_s"] * 1.001
    assert out["device_ops"][0][0] == "bench_probe_program"
    # each job's sleep is the chip's longest idle stretch, under `load`
    assert [n for n, _ in out["idle_gaps"][:3]] == ["load"] * 3
    assert all(t > 0.015 for _, t in out["idle_gaps"][:3])
