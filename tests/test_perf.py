"""Performance observatory tests (telemetry/perf.py, telemetry/benchgate.py,
service/slo.py, the dg16-cli perf subcommands; docs/PERF.md,
docs/OBSERVABILITY.md "Performance observatory").

Covers the ISSUE 11 acceptance ladder: benchgate's gating math (regression
at threshold, noise floor suppressing jitter, missing/new-kernel advisory,
--write-baseline merge semantics, corrupt baseline exit 2 — mirroring
dg16lint's BaselineError contract), the kernel registry + runner record
shape (throughput / compile / cost_analysis / memory fields), the perf
CLI, and the SLO burn-rate plane (budget math, exhaustion -> flight dump,
/stats + /slo + /metrics exposure).
"""

import asyncio
import json

import pytest

from distributed_groth16_tpu.telemetry import benchgate, flight, perf
from distributed_groth16_tpu.telemetry import metrics as tm
from distributed_groth16_tpu.utils.config import SLOConfig


# -- synthetic run/baseline documents ----------------------------------------


def _rec(kernel="k", size=3, med=0.1, **over):
    rec = {
        "schema": perf.PERF_SCHEMA,
        "kernel": kernel,
        "size": size,
        "key": f"{kernel}@2e{size}",
        "items": 1 << size,
        "unit": "items/sec",
        "reps": 3,
        "median_seconds": med,
        "iqr_seconds": 0.0,
        "min_seconds": med,
        "items_per_sec": (1 << size) / med,
        "compile_seconds": 0.0,
        "cost": None,
        "memory": None,
        "host": True,
    }
    rec.update(over)
    return rec


def _run_doc(*recs):
    return {
        "schema": perf.PERF_SCHEMA,
        "platform": "cpu",
        "quick": True,
        "kernels": {r["key"]: r for r in recs},
    }


# -- benchgate gating math ---------------------------------------------------


def test_regression_detected_past_threshold():
    baseline = {"kernels": {"k@2e3": {"median_seconds": 0.1}}}
    run = _run_doc(_rec(med=0.16))
    rep = benchgate.compare(run, baseline, rel_threshold=0.5,
                            abs_floor_s=0.01)
    assert not rep["passed"]
    assert rep["regressions"][0]["key"] == "k@2e3"
    assert rep["regressions"][0]["ratio"] == 1.6


def test_at_threshold_is_not_a_regression():
    baseline = {"kernels": {"k@2e3": {"median_seconds": 0.1}}}
    run = _run_doc(_rec(med=0.15))  # exactly base * (1 + rel)
    rep = benchgate.compare(run, baseline, rel_threshold=0.5,
                            abs_floor_s=0.0)
    assert rep["passed"] and not rep["regressions"]


def test_noise_floor_suppresses_fast_kernel_jitter():
    # 3.5x relative blowup on a sub-ms kernel is jitter, not a regression
    baseline = {"kernels": {"k@2e3": {"median_seconds": 0.001}}}
    run = _run_doc(_rec(med=0.0035))
    rep = benchgate.compare(run, baseline, rel_threshold=0.5,
                            abs_floor_s=0.02)
    assert rep["passed"]
    # the same ratio above the floor IS a regression
    rep2 = benchgate.compare(
        _run_doc(_rec(med=0.35)),
        {"kernels": {"k@2e3": {"median_seconds": 0.1}}},
        rel_threshold=0.5, abs_floor_s=0.02,
    )
    assert not rep2["passed"]


def test_per_kernel_override_wins_over_global():
    baseline = {
        "kernels": {"k@2e3": {"median_seconds": 0.1, "rel_threshold": 5.0}}
    }
    run = _run_doc(_rec(med=0.4))  # 4x: over global 0.5, under override 5.0
    rep = benchgate.compare(run, baseline, rel_threshold=0.5,
                            abs_floor_s=0.01)
    assert rep["passed"]


def test_zero_override_means_never_regress_not_default():
    baseline = {
        "kernels": {"k@2e3": {"median_seconds": 0.1, "rel_threshold": 0.0,
                              "abs_floor_s": 0.0}}
    }
    run = _run_doc(_rec(med=0.13))  # 30% slower: under the 0.5 default
    rep = benchgate.compare(run, baseline, rel_threshold=0.5,
                            abs_floor_s=0.02)
    assert not rep["passed"]


def test_structurally_bad_run_record_exits_2(tmp_path, capsys):
    bad = tmp_path / "run.json"
    bad.write_text(json.dumps({"kernels": {"k@2e3": {"kernel": "k"}}}))
    assert benchgate.main(["--check", str(bad)]) == 2
    assert "k@2e3" in capsys.readouterr().err


def test_platform_mismatch_skips_gating_with_advisory():
    baseline = {"platform": "tpu",
                "kernels": {"k@2e3": {"median_seconds": 0.001}}}
    run = _run_doc(_rec(med=0.5))  # 500x "slower" — but it's the CPU path
    rep = benchgate.compare(run, baseline, rel_threshold=0.5,
                            abs_floor_s=0.01)
    assert rep["passed"] and rep["checked"] == 0
    assert "platform mismatch" in rep["advisories"][0]


def test_select_typo_exits_2_not_1(tmp_path, capsys):
    rc = benchgate.main(["--select", "msm_gl", "--baseline",
                         str(tmp_path / "nope.json")])
    assert rc == 2
    assert "msm_gl" in capsys.readouterr().err


def test_new_kernel_and_missing_entry_are_advisory():
    baseline = {"kernels": {"gone@2e3": {"median_seconds": 0.1}}}
    run = _run_doc(_rec(kernel="new"))
    rep = benchgate.compare(run, baseline, rel_threshold=0.5,
                            abs_floor_s=0.01)
    assert rep["passed"]
    joined = "\n".join(rep["advisories"])
    assert "new@2e3" in joined and "gone@2e3" in joined


def test_errored_kernel_with_baseline_regresses_without_is_advisory():
    err = {"schema": perf.PERF_SCHEMA, "kernel": "k", "size": 3,
           "key": "k@2e3", "error": "RuntimeError: boom"}
    run = {"schema": perf.PERF_SCHEMA, "platform": "cpu", "quick": True,
           "kernels": {"k@2e3": err}}
    with_base = benchgate.compare(
        run, {"kernels": {"k@2e3": {"median_seconds": 0.1}}},
        rel_threshold=0.5, abs_floor_s=0.01,
    )
    assert not with_base["passed"]
    without = benchgate.compare(run, {"kernels": {}}, rel_threshold=0.5,
                                abs_floor_s=0.01)
    assert without["passed"] and without["advisories"]


def test_improvement_is_reported_not_failed():
    baseline = {"kernels": {"k@2e3": {"median_seconds": 0.2}}}
    rep = benchgate.compare(_run_doc(_rec(med=0.05)), baseline,
                            rel_threshold=0.5, abs_floor_s=0.01)
    assert rep["passed"]
    assert rep["improvements"][0]["key"] == "k@2e3"


def test_write_baseline_merges_and_preserves_overrides(tmp_path):
    path = tmp_path / "baseline.json"
    existing = {
        "schema": benchgate.BASELINE_SCHEMA,
        "kernels": {
            # updated by this run, carries an operator override
            "k@2e3": {"median_seconds": 0.5, "rel_threshold": 4.0},
            # a TPU-size entry this (quick) run never exercised
            "k@2e20": {"median_seconds": 9.0},
        },
    }
    run = _run_doc(_rec(med=0.1), _rec(kernel="fresh", med=0.2))
    doc = benchgate.write_baseline(path, run, existing)
    assert doc["kernels"]["k@2e3"]["median_seconds"] == 0.1
    assert doc["kernels"]["k@2e3"]["rel_threshold"] == 4.0
    assert doc["kernels"]["k@2e20"]["median_seconds"] == 9.0
    assert doc["kernels"]["fresh@2e3"]["median_seconds"] == 0.2
    on_disk = json.loads(path.read_text())
    assert on_disk["schema"] == benchgate.BASELINE_SCHEMA
    # errored records never ratchet into the baseline
    run_err = {"schema": perf.PERF_SCHEMA, "kernels": {
        "boom@2e3": {"kernel": "boom", "size": 3, "key": "boom@2e3",
                     "error": "x"}}}
    doc2 = benchgate.write_baseline(path, run_err, on_disk)
    assert "boom@2e3" not in doc2["kernels"]


def test_corrupt_baseline_exits_2(tmp_path, capsys):
    run_path = tmp_path / "run.json"
    run_path.write_text(json.dumps(_run_doc(_rec())))
    bad = tmp_path / "baseline.json"
    bad.write_text("{not json")
    assert benchgate.main(
        ["--check", str(run_path), "--baseline", str(bad)]
    ) == 2
    bad.write_text(json.dumps({"kernels": {"k@2e3": {"median_seconds": "x"}}}))
    assert benchgate.main(
        ["--check", str(run_path), "--baseline", str(bad)]
    ) == 2
    # corrupt RUN file too — a mangled input must not silently gate nothing
    bad_run = tmp_path / "bad_run.json"
    bad_run.write_text("[]")
    assert benchgate.main(["--check", str(bad_run)]) == 2
    capsys.readouterr()


def test_gate_exit_codes_both_directions(tmp_path, capsys):
    """The acceptance regression test: the same baseline passes the
    honest run (exit 0) and fails the 2x-slowed one (exit 1)."""
    baseline = tmp_path / "baseline.json"
    good = _run_doc(_rec(med=0.1), _rec(kernel="other", med=0.3))
    benchgate.write_baseline(baseline, good, None)
    good_path = tmp_path / "good.json"
    good_path.write_text(json.dumps(good))
    assert benchgate.main(
        ["--check", str(good_path), "--baseline", str(baseline)]
    ) == 0
    slowed = json.loads(good_path.read_text())
    slowed["kernels"]["k@2e3"]["median_seconds"] *= 2  # inject 2x slowdown
    slow_path = tmp_path / "slow.json"
    slow_path.write_text(json.dumps(slowed))
    assert benchgate.main(
        ["--check", str(slow_path), "--baseline", str(baseline)]
    ) == 1
    out = capsys.readouterr().out
    assert "REGRESSION k@2e3" in out


def test_missing_baseline_file_is_advisory(tmp_path, capsys):
    run_path = tmp_path / "run.json"
    run_path.write_text(json.dumps(_run_doc(_rec())))
    rc = benchgate.main(
        ["--check", str(run_path), "--baseline", str(tmp_path / "nope.json")]
    )
    assert rc == 0
    assert "advisory" in capsys.readouterr().out


# -- the registry + runner ---------------------------------------------------


def test_default_registry_covers_the_hot_path():
    names = set(perf.kernels())
    assert {
        "msm_g1", "msm_g2", "msm_g1_tree", "ntt_fwd", "ntt_inv",
        "ntt_limb_fwd", "ntt_limb_inv", "fixedbase_g1",
        "glv_decompose", "pairing_miller_loop", "scalar_pack",
    } <= names
    device = [s for s in perf.kernels().values() if not s.host]
    assert len(device) >= 8  # the acceptance bar: 8 introspectable kernels


def test_run_kernel_device_record_shape():
    import jax
    import jax.numpy as jnp

    def build(log2n):
        n = 1 << log2n
        x = jnp.arange(n, dtype=jnp.float32)
        return perf.KernelCase(jax.jit(lambda v: (v * 2.0).sum()), (x,), n)

    spec = perf.KernelSpec("_t_dev", build, (6,), (6,), "items/sec", False)
    rec = perf.run_kernel(spec, 6, reps=3)
    assert rec["key"] == "_t_dev@2e6" and rec["reps"] == 3
    assert rec["median_seconds"] > 0 and rec["items_per_sec"] > 0
    assert rec["compile_seconds"] >= 0
    assert rec["cost"] is not None and rec["cost"]["flops"] >= 0
    assert rec["memory"] is not None
    assert "argument_bytes" in rec["memory"]
    assert "peak_bytes" in rec["memory"]  # None on CPU, populated on TPU
    # mirrored into the PR 3 registry with the same series names
    snap = tm.registry().snapshot()
    assert snap['perf_kernel_items_per_sec{kernel="_t_dev",size="2e6"}'] > 0
    assert snap['perf_kernel_seconds_count{kernel="_t_dev",size="2e6"}'] == 3


def test_run_kernel_without_memory_stats_keeps_record_shape():
    """ISSUE 14 satellite: XLA:CPU has no memory_stats() — the record must
    carry an explicit None peak (never a fabricated number) and every
    other field must stay intact, so benchgate and the dashboards read
    CPU runs without special-casing."""
    import jax
    import jax.numpy as jnp

    assert jax.default_backend() == "cpu"  # the backend under test
    assert jax.devices()[0].memory_stats() is None

    def build(log2n):
        n = 1 << log2n
        x = jnp.arange(n, dtype=jnp.float32)
        return perf.KernelCase(jax.jit(lambda v: (v + 1.0).sum()), (x,), n)

    spec = perf.KernelSpec("_t_nomem", build, (5,), (5,), "items/sec", False)
    rec = perf.run_kernel(spec, 5, reps=2)
    assert rec["memory"] is not None and rec["memory"]["peak_bytes"] is None
    assert rec["memory"]["argument_bytes"] >= 0
    assert rec["cost"] is not None  # XLA's cost model still answers on CPU
    assert rec["median_seconds"] > 0 and rec["items_per_sec"] > 0
    assert rec["roofline"] is not None  # attribution needs cost, not memory


def test_compile_listener_zero_delta_on_second_call():
    """ISSUE 14 satellite, on jax's own clocks since ISSUE 23: a call that
    jax serves from its jit cache must add NOTHING to the trace/compile
    counters — the delta perf.run_kernel reads round the first call is the
    whole compile cost, and `jax_compiles_total` only moves on a real
    (re)compile, which is what makes it an alarm."""
    import jax
    import jax.numpy as jnp

    from distributed_groth16_tpu.telemetry import compile as tcompile

    reg = tm.registry()
    trace = reg.family("jax_trace_seconds_total").labels(fn="_t_hit")
    comp = reg.family("jax_compile_seconds_total").labels(fn="_t_hit")
    compiles = reg.family("jax_compiles_total")
    tj = tcompile.named_jit("_t_hit", lambda v: (v * 5.0).sum())
    x = jnp.arange(32, dtype=jnp.float32)
    n0, total0 = compiles.value, tcompile.seconds_total()
    jax.block_until_ready(tj(x))  # first call: traced, lowered, compiled
    assert trace.value > 0.0 and comp.value > 0.0
    assert compiles.value >= n0 + 1
    assert tcompile.seconds_total() > total0
    after = (trace.value, comp.value, compiles.value, tcompile.seconds_total())
    jax.block_until_ready(tj(x))  # jit-cache hit: every delta exactly 0
    assert after == (
        trace.value, comp.value, compiles.value, tcompile.seconds_total()
    )


def test_run_kernel_host_record_shape():
    def build(log2n):
        return perf.KernelCase(lambda: sum(range(1 << log2n)), (), 1 << log2n)

    spec = perf.KernelSpec("_t_host", build, (10,), (10,), "items/sec", True)
    rec = perf.run_kernel(spec, 10, reps=2)
    assert rec["host"] is True and rec["compile_seconds"] == 0.0
    assert rec["cost"] is None and rec["memory"] is None
    assert rec["items_per_sec"] > 0


def test_run_suite_isolates_kernel_errors_and_rejects_unknown_select():
    def boom(log2n):
        raise RuntimeError("boom")

    perf.perf_kernel("_t_boom", sizes=(3,))(boom)
    try:
        out = perf.run_suite(select=["_t_boom"])
        assert out["schema"] == perf.PERF_SCHEMA
        assert out["kernels"]["_t_boom@2e3"]["error"].startswith(
            "RuntimeError"
        )
        with pytest.raises(KeyError):
            perf.run_suite(select=["_t_nope"])
    finally:
        perf._KERNELS.pop("_t_boom", None)


def test_kernel_buckets_are_sub_millisecond():
    assert min(tm.DEFAULT_KERNEL_BUCKETS) < 0.001
    assert list(tm.DEFAULT_KERNEL_BUCKETS) == sorted(
        tm.DEFAULT_KERNEL_BUCKETS
    )
    fam = tm.registry().family("perf_kernel_seconds")
    assert fam is not None and fam.buckets == tuple(
        tm.DEFAULT_KERNEL_BUCKETS
    )


# -- dg16-cli perf subcommands -----------------------------------------------


def _cli(argv, capsys) -> dict:
    from distributed_groth16_tpu.api import cli

    cli.main(argv)
    return json.loads(capsys.readouterr().out)


def test_cli_perf_top_and_diff(tmp_path, capsys):
    a = _run_doc(_rec(med=0.1), _rec(kernel="slow", med=2.0))
    b = _run_doc(_rec(med=0.2), _rec(kernel="slow", med=1.0))
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    baseline = tmp_path / "base.json"
    benchgate.write_baseline(baseline, a, None)

    top = _cli(
        ["perf", "top", "--run", str(pb), "--baseline", str(baseline),
         "-n", "1"],
        capsys,
    )
    assert top["top"][0]["key"] == "slow@2e3"
    assert top["top"][0]["vsBaseline"] == 0.5

    diff = _cli(["perf", "diff", str(pa), str(pb)], capsys)
    assert diff["kernels"]["k@2e3"]["ratio"] == 2.0
    assert diff["kernels"]["slow@2e3"]["ratio"] == 0.5
    assert diff["onlyInA"] == [] and diff["onlyInB"] == []


def test_cli_perf_run_select_host_kernels(tmp_path, capsys):
    out_path = tmp_path / "run.json"
    body = _cli(
        ["perf", "run", "--quick", "--select", "scalar_pack",
         "glv_decompose", "--reps", "1", "--out", str(out_path)],
        capsys,
    )
    assert set(body["kernels"]) == {"scalar_pack@2e12", "glv_decompose@2e10"}
    doc = json.loads(out_path.read_text())
    assert doc["schema"] == perf.PERF_SCHEMA
    for rec in doc["kernels"].values():
        assert rec["median_seconds"] > 0


# -- SLO burn-rate plane -----------------------------------------------------


def _observe_jobs(kind: str, seconds: float, n: int) -> None:
    # the SAME registration the queue makes (idempotent by name/labels)
    fam = tm.registry().histogram(
        "job_seconds", "End-to-end job runtime (RUNNING to terminal), "
        "per kind", ("kind",),
    )
    child = fam.labels(kind=kind)
    for _ in range(n):
        child.observe(seconds)


def test_slo_targets_parse():
    t = SLOConfig.parse_targets("prove=30, mpc_prove=120")
    assert t == (("prove", 30.0), ("mpc_prove", 120.0))
    assert SLOConfig.parse_targets("") == ()
    with pytest.raises(ValueError):
        SLOConfig.parse_targets("prove")
    cfg = SLOConfig(target_s=10.0, targets=(("prove", 5.0),))
    assert cfg.target_for("prove") == 5.0
    assert cfg.target_for("other") == 10.0
    assert cfg.enabled
    assert not SLOConfig().enabled


def test_slo_burn_rate_math():
    from distributed_groth16_tpu.service.slo import SloMonitor

    clock = [0.0]
    cfg = SLOConfig(target_s=0.05, objective=0.9, window_s=1000.0,
                    sample_s=1.0)
    mon = SloMonitor(cfg, now=lambda: clock[0])  # baseline excludes history
    _observe_jobs("prove", 0.001, 9)
    clock[0] = 1.0
    doc = mon.sample()
    k = doc["kinds"]["prove"]
    assert k["windowTotal"] == 9 and k["windowBad"] == 0
    assert k["burnRate"] == 0.0 and k["budgetRemaining"] == 1.0
    assert not k["exhausted"]
    _observe_jobs("prove", 1.0, 1)  # misses the 50 ms target
    clock[0] = 2.0
    k = mon.sample()["kinds"]["prove"]
    assert k["windowTotal"] == 10 and k["windowBad"] == 1
    assert k["burnRate"] == pytest.approx(1.0)  # exactly on the 10% budget
    assert k["budgetRemaining"] == pytest.approx(0.0) and k["exhausted"]
    snap = tm.registry().snapshot()
    assert snap['slo_burn_rate{kind="prove"}'] == pytest.approx(1.0)


def test_slo_window_expires_old_samples():
    from distributed_groth16_tpu.service.slo import SloMonitor

    clock = [0.0]
    cfg = SLOConfig(target_s=0.05, objective=0.9, window_s=10.0,
                    sample_s=1.0)
    mon = SloMonitor(cfg, now=lambda: clock[0])
    _observe_jobs("mpc_prove", 1.0, 5)  # all bad
    clock[0] = 1.0
    assert mon.sample()["kinds"]["mpc_prove"]["windowBad"] == 5
    # the bad burst ages out of the window with no new traffic
    clock[0] = 50.0
    mon.sample()
    clock[0] = 51.0
    k = mon.sample()["kinds"]["mpc_prove"]
    assert k["windowBad"] == 0 and k["burnRate"] == 0.0


def test_slo_budget_exhaustion_writes_one_flight_dump(tmp_path):
    from distributed_groth16_tpu.service.slo import SloMonitor

    flight.configure(str(tmp_path))
    try:
        clock = [0.0]
        cfg = SLOConfig(target_s=0.05, objective=0.5, window_s=1000.0)
        mon = SloMonitor(cfg, now=lambda: clock[0])
        _observe_jobs("prove", 1.0, 4)  # 100% bad, 50% allowed -> overdrawn
        clock[0] = 1.0
        assert mon.sample()["kinds"]["prove"]["exhausted"]
        dumps = list(tmp_path.glob("*slo_budget_exhausted*.json"))
        assert len(dumps) == 1
        record = json.loads(dumps[0].read_text())
        assert record["extra"]["kind"] == "prove"
        assert record["extra"]["windowBad"] == 4
        # still exhausted on the next tick: same episode, no second dump
        clock[0] = 2.0
        mon.sample()
        assert len(list(tmp_path.glob("*slo_budget_exhausted*.json"))) == 1
        # recovery re-arms: budget heals, then a fresh burst dumps again
        _observe_jobs("prove", 0.001, 100)
        clock[0] = 3.0
        assert not mon.sample()["kinds"]["prove"]["exhausted"]
        _observe_jobs("prove", 1.0, 200)
        clock[0] = 4.0
        assert mon.sample()["kinds"]["prove"]["exhausted"]
        assert len(list(tmp_path.glob("*slo_budget_exhausted*.json"))) == 2
    finally:
        flight.disable()


def test_slo_routes_and_metrics_exposure(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from distributed_groth16_tpu.api.server import ApiServer
    from distributed_groth16_tpu.api.store import CircuitStore
    from distributed_groth16_tpu.utils.config import ServiceConfig

    async def run():
        server = ApiServer(
            CircuitStore(str(tmp_path)),
            ServiceConfig(workers=1),
            slo_cfg=SLOConfig(target_s=30.0, targets=(("prove", 30.0),),
                              objective=0.99, sample_s=0.05),
        )
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            stats = await (await client.get("/stats")).json()
            assert stats["slo"]["enabled"] is True
            assert stats["slo"]["objective"] == 0.99
            slo = await (await client.get("/slo")).json()
            assert "prove" in slo["kinds"]
            assert slo["kinds"]["prove"]["targetS"] == 30.0
            text = await (await client.get("/metrics")).text()
            assert 'slo_burn_rate{kind="prove"}' in text
            assert "slo_budget_remaining" in text
            # the background sampler task is alive between requests
            await asyncio.sleep(0.1)
            assert server._slo_task is not None and not server._slo_task.done()
        finally:
            await client.close()

    asyncio.run(run())


def test_slo_disabled_by_default(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from distributed_groth16_tpu.api.server import ApiServer
    from distributed_groth16_tpu.api.store import CircuitStore
    from distributed_groth16_tpu.utils.config import ServiceConfig

    async def run():
        server = ApiServer(
            CircuitStore(str(tmp_path)), ServiceConfig(workers=1),
            slo_cfg=SLOConfig(),
        )
        assert server.slo is None
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            stats = await (await client.get("/stats")).json()
            assert stats["slo"] == {"enabled": False}
            slo = await (await client.get("/slo")).json()
            assert slo == {"enabled": False}
        finally:
            await client.close()

    asyncio.run(run())
