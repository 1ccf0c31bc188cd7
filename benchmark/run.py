#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the chip.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are files
found by the names BENCHMARK.json gives (`configs/`, `traffic/`,
`layer_metrics/`); nothing in this file knows a cell. In order:

  gate       exit non-zero before anything compiles unless
             jax.default_backend() is "tpu" with the cell's chips;
             JAX_PLATFORMS is never set and there is no other path
  artefacts  witnesses, saved circuit (the service's `setup`) and, for a
             verifying cell, proofs: built only where `.bench_cache/` in
             the checkout lacks them (`artefacts.py`)
  server     the program's ApiServer in this process, which alone holds
             the chip, on a loopback port
  load       a child process (`loadgen.py`: no jax, no program) warms up
             with one request of each of the cell's kinds, then runs the
             traffic for `--seconds` and drains; with `--trace 1` it takes
             a profiler slice through POST /profile inside the window
  judge      every answer against the oracle copy (`checks.py`), the
             program's route and cache counters, compilations in the window
  report     `info {...}` lines, then the one result line: with
             `--trace 0` the cell's end-to-end metrics, with `--trace 1`
             its per-layer metrics, the device's busy time and a breakdown
"""

from __future__ import annotations

import time

T0_EPOCH = time.time()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)

from benchmark import checks, end_to_end, loadgen, serve  # noqa: E402
from benchmark.artefacts import CACHE_ROOT, Artefacts  # noqa: E402
from benchmark.schedule import Request, Traffic  # noqa: E402

# a request may take this long, the first one's compilation included
JOB_TIMEOUT_S = 1000.0
POLL_S = 0.02
# the traced slice: this many times the first window request's time
PROFILE = {"factor": 2.5, "min_s": 8.0, "max_s": 20.0}


def info(**kw) -> None:
    print("info " + json.dumps(kw), flush=True)


def load_bench() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(the cell, its configuration, its traffic mix), each from the file
    that BENCHMARK.json names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(
            f"benchmark: no workload {name!r}; there are {sorted(cells)}"
        )
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(CHECKOUT, files[cell["config"]])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def save_circuit(url: str, name: str, r1cs: bytes) -> str:
    """POST /save_circuit, which runs the service's `setup`: set-up time."""
    import requests

    http = requests.Session()
    http.trust_env = False  # loopback: never through a proxy
    r = http.post(
        url + "/save_circuit",
        files={"circuit_name": ("circuit_name", name.encode()),
               "r1cs_file": ("r1cs_file", r1cs)},
        timeout=JOB_TIMEOUT_S,
    )
    if r.status_code != 200:
        raise RuntimeError(f"/save_circuit: HTTP {r.status_code} {r.text[:300]}")
    return r.json()["circuitId"]


def build_artefacts(arte: Artefacts, config: dict, traffic: Traffic,
                    plan: dict, server) -> None:
    """Everything the window needs on disk that is not there yet; fills in
    the plan's circuit ids. A verifying cell's proofs are made by the
    load generator's own client, with the configuration's proving job."""
    from benchmark.reference import groth16 as oracle

    def load_key(circuit_id: str):
        r1cs, pk = server.store.load(circuit_id)
        vk = oracle.VerifyingKey(
            pk.vk.alpha_g1, pk.vk.beta_g2, pk.vk.gamma_g2, pk.vk.delta_g2,
            list(pk.vk.gamma_abc_g1),
        )
        return vk, pk.domain_size, r1cs.num_constraints, r1cs.num_wires

    name = config["circuit"]["generator"].replace("_", "")
    plan["circuit_ids"] = arte.ensure_circuits(
        traffic.circuits,
        lambda r1cs: save_circuit(plan["url"], name, r1cs), load_key,
    )
    if "verify" not in traffic.kinds:
        return
    client = loadgen.Driver(plan)
    for i in range(traffic.witness_pool):
        if arte.proof(i) is not None:
            continue
        rec = client.send(Request("prove", 0, i, False), -1, i)
        if not rec["ok"]:
            raise RuntimeError(f"set-up proof {i}: {rec.get('error')}")
        proof = bytes.fromhex(rec["proof"])
        if not oracle.verify(arte.vk, proof, arte.publics(i)):
            raise RuntimeError(f"set-up proof of witness {i} is invalid")
        arte.keep_proof(i, proof, config["prove"]["kind"])


def run_loadgen(plan: dict, work: str) -> dict:
    plan_path = os.path.join(work, "plan.json")
    out_path = os.path.join(work, "records.json")
    if os.path.exists(out_path):
        os.remove(out_path)
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), plan_path, out_path]
    )
    try:
        rc = child.wait(timeout=JOB_TIMEOUT_S + plan["seconds"] + 600)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if not os.path.exists(out_path):
        raise RuntimeError(f"the load generator left no records (exit {rc})")
    with open(out_path) as f:
        records = json.load(f)
    if "fatal" in records:
        raise RuntimeError(f"load generator: {records['fatal']}")
    return records


def find_xplane(server, capture_id: str) -> str:
    found = glob.glob(os.path.join(
        server.profiler.directory, capture_id, "plugins", "profile", "*",
        "*.xplane.pb",
    ))
    if len(found) != 1:
        raise RuntimeError(f"capture {capture_id}: xplane files {found}")
    return found[0]


def run_cell(cell: dict, config: dict, traffic_doc: dict, bench: dict, *,
             seed: int, seconds: float, trace: bool, jax, devices,
             on_chip: bool = True, keep_trace: str | None = None,
             cache_root: str | None = None) -> dict:
    """Everything after the gate. `on_chip=False` and `cache_root` are
    rehearse.py's: the device-path counters are then not held to the TPU's
    routes, no device metric is printed, and the artefacts of the tiny
    circuit stay apart from the real ones."""
    tally = serve.Tally()
    jax.monitoring.register_event_time_span_listener(tally.on_span)
    serve.quiet_python_tracer(jax)
    import distributed_groth16_tpu  # noqa: F401 — places the compile cache

    traffic = Traffic.from_dict(traffic_doc)
    if traffic.witness_pool > config["circuit"]["pool"]:
        raise SystemExit("benchmark: the traffic wants a larger witness pool "
                         "than the configuration holds")
    arte = Artefacts(config["circuit"], cache_root or CACHE_ROOT)
    arte.ensure_witnesses(traffic.witness_pool)
    work = os.path.join(os.path.dirname(arte.root), "run", cell["name"])
    os.makedirs(work, exist_ok=True)

    def drive(url: str, server) -> dict:
        plan = {
            "url": url, "seed": seed, "seconds": seconds,
            "traffic": traffic_doc, "prove": config["prove"],
            "pool": [arte.pool_entry(i) for i in range(traffic.witness_pool)],
            "profile": PROFILE if trace else None,
            "poll_s": POLL_S, "job_timeout_s": JOB_TIMEOUT_S,
        }
        t = time.time()
        build_artefacts(arte, config, traffic, plan, server)
        got = {"artefacts_s": time.time() - t,
               "profile_dir": server.profiler.directory}
        got["records"] = rec = run_loadgen(plan, work)
        if trace:
            prof = rec["profile"] or {}
            if prof.get("state") != "done":
                raise RuntimeError(f"the profiler capture failed: {prof}")
            got["xplane"] = find_xplane(server, prof["id"])
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(got["xplane"], os.path.join(
                    keep_trace, cell["name"] + ".xplane.pb"))
        return got

    got = serve.serve_and_drive(
        arte.store_dir, int(config["service"]["workers"]), drive
    )
    rec = got["records"]
    window = rec["window"]
    win_end_epoch = window["start_epoch"] + window["last_done"] - window["start"]
    run = {
        "cell": cell, "config": config, "traffic": traffic_doc,
        "seed": seed, "seconds": seconds, "on_chip": on_chip,
        "t0_epoch": T0_EPOCH, "records": rec, "window": window,
        "requests": rec["requests"], "dtos": rec["dtos"],
        "sizes": {
            "wires": arte.manifest["wires"],
            "constraints": arte.manifest["constraints"],
            "domain_size": arte.manifest["domain_size"],
            "instance": len(arte.publics(0)) + 1,
        },
        "setup": {
            "artefacts_s": got["artefacts_s"],
            "warmup_s": sum(
                r["t_done"] - r["t_send"] for r in rec["warmup"]
            ),
            "trace_s": tally.seconds("trace", window["start_epoch"]),
            "compile_s": tally.seconds("compile", window["start_epoch"]),
            "built": arte.built,
        },
        "compiles_in_window": tally.compiles_between(
            window["start_epoch"], win_end_epoch
        ),
        "device_kind": devices[0].device_kind,
        "trace": None,
    }
    expect = config.get("expect", {})
    if any(run["sizes"][k] != v for k, v in expect.items()):
        raise RuntimeError(f"circuit sizes {run['sizes']} are not {expect}")

    judged = checks.judge_requests(run["requests"], arte, config["prove"]["kind"])
    faults = checks.device_path_faults(run)

    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    run["memory_peak_bytes"] = peak
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }

    if trace:
        from benchmark import trace_reduce

        t = time.time()
        try:
            run["trace"] = trace_reduce.reduce_trace(
                got["xplane"], cell["chips"]
            )
        except ValueError:
            if on_chip:  # a trace with no device plane is no result
                raise
        info(trace_file_bytes=os.path.getsize(got["xplane"]),
             reduce_s=round(time.time() - t, 2),
             **{k: v for k, v in (run["trace"] or {}).items()
                if k not in ("device_ops", "idle_gaps")})
        shutil.rmtree(got["profile_dir"], ignore_errors=True)

    line = result_line(run, bench, trace=trace, device=device, faults=faults)
    lat = sorted(end_to_end.latency_s(r) for r in run["requests"] if r["valid"])
    info(
        workload=cell["name"], seed=seed, seconds=seconds, trace=int(trace),
        samples=len(lat), attempted=line["attempted"], failed=line["failed"],
        latency_s_min=lat[0] if lat else None,
        latency_s_max=lat[-1] if lat else None,
        drained_s=window["last_done"] - window["start"],
        generator_late=rec["late"], judged=judged, faults=faults,
        routes_moved={"/".join(k): v for k, v in checks.routes_moved(
            rec["metrics_before"], rec["metrics_after"]).items()},
        setup=run["setup"], compiles_in_window=run["compiles_in_window"],
        errors=[r["error"] for r in run["requests"] if r.get("error")][:5],
    )

    return line


def read_metrics(run: dict, bench: dict, trace: bool) -> dict:
    """{name: {value, unit}}: with `trace` the cell's per-layer metrics,
    each from its own reader and only where the end-to-end metric it moves
    is reported; without, the cell's end-to-end metrics. A reader that has
    nothing to read returns None and its metric is left out."""
    cell = run["cell"]["name"]
    if not trace:
        found = {
            m["name"]: (end_to_end.METRICS[m["name"]](run), m["unit"])
            for m in bench["end_to_end"] if applies(m, cell)
        }
    else:
        reported = {m["name"] for m in bench["end_to_end"] if applies(m, cell)}
        found = {
            m["name"]: (importlib.import_module(
                f"benchmark.layer_metrics.{m['name']}").read(run), m["unit"])
            for m in bench["per_layer"]
            if applies(m, cell) and m["moves"] in reported
        }
    return {k: {"value": v, "unit": u} for k, (v, u) in found.items()
            if v is not None}


def result_line(run: dict, bench: dict, *, trace: bool, device: dict,
                faults: list) -> dict:
    """The last line of standard output, to the driver's contract."""
    attempted = len(run["requests"])
    failed = sum(not r["valid"] for r in run["requests"])
    line = {
        "correct": bool(attempted and not failed and not faults),
        "attempted": attempted, "failed": failed,
        "metrics": read_metrics(run, bench, trace),
        "device": dict(device),
    }
    if trace and run["on_chip"]:
        line["device"]["busy_s"] = run["trace"]["busy_s"]
        line["device"]["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_gaps"],
        }
    return line


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="also copy the slice's .xplane.pb here, to look at "
                         "by hand (it is deleted otherwise)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = load_bench()
    cell, config, traffic = load_cell(bench, args.workload)
    serve.require_package()
    jax, devices = serve.require_tpu(cell["chips"])
    line = run_cell(
        cell, config, traffic, bench, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), jax=jax, devices=devices,
        keep_trace=args.keep_trace,
    )
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
