"""The shape of the last line, from a run's records as `run.py` holds them."""

import json
import os

from benchmark import checks, run as bench_run

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 1330000000}


def _run(cell_name, requests, trace=None):
    bench = bench_run.load_bench()
    cell, config, traffic = bench_run.load_cell(bench, cell_name)
    dto = {"state": "DONE", "kind": config["prove"]["kind"],
           "createdAt": 10.0, "startedAt": 10.5, "finishedAt": 12.0,
           "phases": {"load": 200.0, "witness": 300.0}, "partySpans": 8}
    return bench, {
        "cell": cell, "config": config, "traffic": traffic, "on_chip": True,
        "requests": requests, "dtos": {r["job_id"]: dto for r in requests
                                       if "job_id" in r},
        "window": {"start": 100.0, "start_epoch": 1100.0},
        "t0_epoch": 1000.0, "trace": trace, "device_kind": "TPU v5 lite",
        "memory_peak_bytes": 1330000000, "compiles_in_window": 0,
        "sizes": {"wires": 27627, "instance": 3, "domain_size": 32768},
        "setup": {"trace_s": 50.0, "compile_s": 10.0, "artefacts_s": 0.1,
                  "warmup_s": 70.0},
    }


def _prove(i):
    return {"kind": "prove", "job_id": f"j{i}", "valid": True, "ok": True,
            "t_send": 100.0 + 2 * i, "t_accepted": 100.01 + 2 * i,
            "t_done": 101.5 + 2 * i}


TRACE = {"busy_s": 4.0, "window_s": 8.0,
         "device_ops": [["_msm_tree_jit", 3.0]], "idle_gaps": [["load", 0.2]],
         "per_job": {"jobs": 4, "interval_s": 6.0, "busy_s": 0.9,
                     "launches": 31.0, "group_s": {"msm": 0.7, "ntt": 0.1}}}


def test_untraced_line_has_the_end_to_end_metrics_and_no_other_key():
    bench, run = _run("sha256_single_c1", [_prove(i) for i in range(5)])
    line = bench_run.result_line(run, bench, trace=False, device=DEVICE,
                                 faults=[])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device"]
    assert (line["correct"], line["attempted"], line["failed"]) == (True, 5, 0)
    assert set(line["metrics"]) == {"proof_p50_s", "setup_s"}
    assert line["metrics"]["proof_p50_s"] == {"value": 1.5, "unit": "s"}
    assert line["metrics"]["setup_s"]["value"] == 100.0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)


def test_traced_line_has_per_layer_metrics_busy_time_and_a_breakdown():
    bench, run = _run("sha256_single_c1", [_prove(i) for i in range(5)], TRACE)
    line = bench_run.result_line(run, bench, trace=True, device=DEVICE,
                                 faults=[])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown"]
    want = {m["name"] for m in bench["per_layer"]
            if "sha256_single_c1" in m.get("workloads", ["sha256_single_c1"])}
    assert set(line["metrics"]) == want
    assert not set(line["metrics"]) & {m["name"] for m in bench["end_to_end"]}
    assert line["device"]["busy_s"] == 4.0 and line["device"]["window_s"] == 8.0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    m = line["metrics"]
    assert m["launches_per_req"]["value"] == 31.0
    assert m["host_prep_ms"]["value"] == 500.0
    assert m["job_run_s"]["value"] == 1.5
    # 34.9 MB at 819 GB/s over 0.7 s of MSM programs
    assert 0.005 < m["msm_hbm_roof_pct"]["value"] < 0.007


def test_a_reader_with_nothing_to_read_leaves_its_metric_out():
    trace = dict(TRACE, per_job=None)
    bench, run = _run("sha256_single_c1", [_prove(0)], trace)
    line = bench_run.result_line(run, bench, trace=True, device=DEVICE,
                                 faults=[])
    assert "dev_busy_ms_per_req" not in line["metrics"]
    assert "job_run_s" in line["metrics"]


def test_failures_and_faults_make_the_run_incorrect():
    reqs = [_prove(0), dict(_prove(1), valid=False)]
    bench, run = _run("sha256_single_c1", reqs)
    line = bench_run.result_line(run, bench, trace=False, device=DEVICE,
                                 faults=[])
    assert (line["correct"], line["attempted"], line["failed"]) == (False, 2, 1)
    bench, run = _run("sha256_single_c1", [_prove(0)])
    line = bench_run.result_line(run, bench, trace=False, device=DEVICE,
                                 faults=["1 compilation(s) inside the window"])
    assert line["correct"] is False and line["failed"] == 0


METRICS_TEXT = '''# TYPE kernel_route_total counter
kernel_route_total{kernel="msm",path="tree"} %d
kernel_route_total{kernel="ntt",path="limb"} %d
kernel_route_total{kernel="msm",path="pippenger"} %d
crs_cache_hits_total 4
'''


def test_device_path_faults_read_the_programs_counters():
    # the MPC configuration, whose file is kept although no cell runs it yet
    bench, run = _run("sha256_single_c1", [_prove(0)])
    with open(os.path.join(bench_run.HERE, "configs",
                           "sha256-bn254-mpc-n8l2.json")) as f:
        run["config"] = json.load(f)
    run["dtos"]["j0"]["kind"] = "mpc_prove"
    run["records"] = {
        "metrics_before": METRICS_TEXT % (4, 6, 0),
        "metrics_after": METRICS_TEXT % (36, 12, 0),
        "stats_before": {"crsCache": {"misses": 1}},
        "stats_after": {"crsCache": {"misses": 1}},
    }
    assert checks.device_path_faults(run) == []
    run["records"]["metrics_after"] = METRICS_TEXT % (4, 12, 2)
    run["records"]["stats_after"] = {"crsCache": {"misses": 2}}
    run["dtos"]["j0"]["partySpans"] = 7
    run["compiles_in_window"] = 3
    faults = " / ".join(checks.device_path_faults(run))
    for needle in ("3 compilation", "msm/tree did not advance",
                   "msm/pippenger advanced", "missed its cache",
                   "did not show 8 parties"):
        assert needle in faults
