"""The per-layer readers PR 23 added, each on a hand-made `run`: what it
reads where the program has the new spans, counters and program names, and
that it returns None (the metric is left out of the line) where the
program has none of them, as the parent of that PR has not."""

import importlib

import pytest

from benchmark import run as bench_run

PHASES = {
    "load": 200.0, "load.r1cs": 120.0, "load.key": 75.0,
    "witness": 300.0, "witness.parse": 40.0, "witness.check": 255.0,
    "encode": 90.0, "prove": 880.0, "prove.r1cs": 30.0, "serialize": 5.0,
}
OLD_PHASES = {"load": 200.0, "witness": 300.0, "prove": 950.0}

METRICS_TEXT = '''# TYPE jax_trace_seconds_total counter
jax_trace_seconds_total{fn="_msm_tree_jit_g1"} 120.5
jax_trace_seconds_total{fn="_msm_tree_jit_g2"} 50.25
jax_trace_seconds_total{fn="_limb_ntt_route"} 9.0
# TYPE jax_compiles_total counter
jax_compiles_total 41
'''


def _run(phases, group_s=None, metrics_before=None):
    dto = {"state": "DONE", "kind": "prove", "createdAt": 10.0,
           "startedAt": 10.5, "finishedAt": 12.0, "phases": phases}
    reqs = [{"kind": "prove", "job_id": f"j{i}", "valid": True, "ok": True}
            for i in range(3)]
    trace = None if group_s is None else {
        "per_job": {"jobs": 3, "busy_s": 0.9, "launches": 41.0,
                    "group_s": group_s}}
    return {"requests": reqs, "dtos": {r["job_id"]: dict(dto) for r in reqs},
            "trace": trace, "records": {"metrics_before": metrics_before}}


def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


@pytest.mark.parametrize("name,key", [
    ("load_r1cs_ms", "load.r1cs"), ("load_key_ms", "load.key"),
    ("witness_check_ms", "witness.check"), ("encode_ms", "encode"),
])
def test_a_phase_reader_reads_its_key_or_nothing(name, key):
    assert _read(name, _run(PHASES)) == PHASES[key]
    assert _read(name, _run(OLD_PHASES)) is None


def test_job_unnamed_is_the_job_less_its_top_level_phases():
    # 1500 ms of job, 200 + 300 + 90 + 880 + 5 in phases without a dot
    assert _read("job_unnamed_ms", _run(PHASES)) == pytest.approx(25.0)
    assert _read("job_unnamed_ms", _run(OLD_PHASES)) is None


@pytest.mark.parametrize("group", ["msm_g1", "msm_g2"])
def test_a_group_reader_reads_its_group_or_nothing(group):
    name = f"{group}_dev_ms_per_req"
    new = {"msm": 0.86, "msm_g1": 0.52, "msm_g2": 0.34, "ntt": 0.03}
    assert _read(name, _run(PHASES, new)) == pytest.approx(1e3 * new[group])
    # the parent's programs: every MSM launch is `_msm_tree_jit`, the new
    # groups match none and sum to 0
    old = {"msm": 0.86, "msm_g1": 0.0, "msm_g2": 0.0, "ntt": 0.03}
    assert _read(name, _run(OLD_PHASES, old)) is None
    assert _read(name, _run(PHASES)) is None  # untraced: no per-job block


def test_setup_trace_msm_sums_the_tree_msm_functions():
    run = _run(PHASES, metrics_before=METRICS_TEXT)
    assert _read("setup_trace_msm_s", run) == pytest.approx(170.75)
    none = "# TYPE kernel_route_total counter\nkernel_route_total{a=\"b\"} 1\n"
    assert _read("setup_trace_msm_s", _run(PHASES, metrics_before=none)) is None
    only_ntt = 'jax_trace_seconds_total{fn="_limb_ntt_route"} 9.0\n'
    assert _read("setup_trace_msm_s", _run(PHASES, metrics_before=only_ntt)) == 0.0
    assert _read("setup_trace_msm_s", {"requests": [], "dtos": {}}) is None


def test_the_line_of_a_parent_run_leaves_the_new_metrics_out():
    bench = bench_run.load_bench()
    cell, config, traffic = bench_run.load_cell(bench, "sha256_single_c1")
    run = _run(OLD_PHASES, {"msm": 0.86, "msm_g1": 0.0, "msm_g2": 0.0,
                            "ntt": 0.03}, metrics_before="")
    run.update(cell=cell, config=config, traffic=traffic, on_chip=False,
               window={"start": 100.0, "start_epoch": 1100.0},
               t0_epoch=1000.0, device_kind="TPU v5 lite",
               memory_peak_bytes=360000000, compiles_in_window=0,
               sizes={"wires": 27627, "instance": 3, "domain_size": 32768},
               setup={"trace_s": 180.0, "compile_s": 10.0,
                      "artefacts_s": 0.1, "warmup_s": 190.0})
    for r in run["requests"]:
        r.update(t_send=100.0, t_accepted=100.01, t_done=101.5)
    line = bench_run.result_line(run, bench, trace=True, device={}, faults=[])
    new = {"load_r1cs_ms", "load_key_ms", "witness_check_ms", "encode_ms",
           "job_unnamed_ms", "msm_g1_dev_ms_per_req", "msm_g2_dev_ms_per_req",
           "setup_trace_msm_s"}
    assert not new & set(line["metrics"])
    assert {"host_prep_ms", "msm_dev_ms_per_req", "job_run_s"} <= set(
        line["metrics"])
