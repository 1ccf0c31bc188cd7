#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served proving path still
starts, compiles and gives right answers on the TPU.

One process holds the chip: an `ApiServer` is started IN this process on a
loopback port and driven over real HTTP from a client thread, with the
routes `api/cli.py` uses. Default run (one chip), the flagship deployment
at its full size — the one-block SHA-256 circuit, BN254, m = 32768
(BASELINE.json configs[0]), circuit and witnesses generated from --seed:

  backend      jax.default_backend() == "tpu", else a non-zero exit before
               anything is compiled
  preflight    one G1 add, one G2 add, one 2^15 NTT and one 2^12 tree MSM,
               bit for bit against ops/refmath.py
  save/setup   POST /save_circuit (runs `setup` on the device)
  prove x2     POST /jobs/prove, kind=prove, two different witnesses
  mpc x2       kind=mpc_prove, l=2 (n=8 parties, t=1, asyncio star round);
               the second must hit the packed-CRS cache
  verify       every proof through POST /verify_proof AND the host pairing
               oracle; one corrupted proof must come back isValid:false;
               prove and mpc_prove proofs of one witness byte-identical
  device path  the in-process kernel_route_total shows msm/tree and
               ntt/limb advanced and the generic paths did not; /readyz
               names the TPU; each DONE job carries device-memory numbers

`--four-chip` (needs four devices) is the placement check instead: no
preflight, the batching scheduler on, two `mpc_prove` jobs with l=1 (n=4
parties, one per chip) riding one mesh program; both proofs checked; every
device must show memory use.

It never sets JAX_PLATFORMS, never forces interpret mode, and every failed
check is an exception and a non-zero exit; so is a directory that holds
this script without the package. The last two stdout lines of a passing
run are `report {...}` (versions, cache directory, per-request walls,
compile/trace seconds, peak HBM — smoke observations, not benchmark
results) and, last, exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
with the device as jax reports it. Nothing is left in the checkout: the
circuit store lives in a temporary directory that is removed at exit.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time
from importlib import metadata

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

T0 = time.perf_counter()
# seconds one request may take, cold compile included
JOB_TIMEOUT_S = 1000.0

# jax.monitoring time-span events (jax/_src/dispatch.py)
_EV_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_TRACE = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)


class Tally:
    """Wall seconds during which jax was compiling (a persistent-cache hit
    counts its retrieval time) and tracing/lowering, from jax's own
    monitoring events. Spans are merged before they are summed: a jitted
    function traced inside another reports both, and two worker threads
    may compile at once."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans = {"compile": [], "trace": []}
        self.compile_by_fn: dict[str, float] = collections.defaultdict(float)

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        with self._lock:
            if event == _EV_COMPILE:
                self._spans["compile"].append((start, end))
                self.compile_by_fn[str(kw.get("fun_name", "?"))] += end - start
            elif event in _EV_TRACE:
                self._spans["trace"].append((start, end))

    def _merged(self, kind: str) -> float:
        with self._lock:
            spans = sorted(self._spans[kind])
        total, cur_end = 0.0, float("-inf")
        for start, end in spans:
            total += max(0.0, end - max(start, cur_end))
            cur_end = max(cur_end, end)
        return total

    @property
    def compile_s(self) -> float:
        return self._merged("compile")

    @property
    def trace_s(self) -> float:
        return self._merged("trace")


TALLY = Tally()


def stage(name: str, **info) -> None:
    """One line per passed stage: elapsed, compile seconds so far."""
    extra = " ".join(f"{k}={v}" for k, v in info.items())
    print(
        f"[{time.perf_counter() - T0:8.1f}s] {name}: ok "
        f"compile_s={TALLY.compile_s:.1f} trace_s={TALLY.trace_s:.1f} "
        f"{extra}".rstrip(),
        flush=True,
    )


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# backend gate
# ---------------------------------------------------------------------------


def require_package() -> None:
    if importlib.util.find_spec("distributed_groth16_tpu") is None:
        raise SystemExit(
            "chip_smoke: the distributed_groth16_tpu package is not beside "
            "this script; nothing was run"
        )


def require_tpu(min_devices: int):
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu" or devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, jax found backend={backend!r} "
            f"device={devices[0].platform!r}; nothing was run"
        )
    if len(devices) < min_devices:
        raise SystemExit(
            f"chip_smoke: needs {min_devices} TPU devices, jax found "
            f"{len(devices)}; nothing was run"
        )
    return jax, devices


# ---------------------------------------------------------------------------
# preflight: kernels against ops/refmath.py, bit for bit
# ---------------------------------------------------------------------------


def _route_counts() -> dict:
    from distributed_groth16_tpu.telemetry import metrics

    fam = metrics.registry().family("kernel_route_total")
    return {values: child.value for values, child in fam.items()}


def _check_group_add(name, lgroup, curve, host, gen) -> None:
    """One full lane tile through the limb-major add kernel: generic adds,
    a doubling, both infinity operands, P + (-P) and inf + inf."""
    import jax.numpy as jnp
    import numpy as np

    pts = [None, gen]
    for _ in range(8):
        pts.append(host.add(pts[-1], gen))  # pts[i] = i * G
    lhs = [pts[1], pts[3], pts[4], None, pts[6], None, pts[7], pts[2]]
    rhs = [pts[2], pts[3], None, pts[5], host.neg(pts[6]), None, pts[8],
           pts[1]]
    want = [host.add(a, b) for a, b in zip(lhs, rhs)]
    reps = lgroup.tile // len(lhs)

    def tiled(a, xp):
        return xp.tile(a, (reps,) + (1,) * (a.ndim - 1))

    def lanes(points):
        return lgroup.from_rowmajor(tiled(curve.encode(points), jnp))

    out = np.asarray(lgroup.to_rowmajor(lgroup.add(lanes(lhs), lanes(rhs))))
    check(np.array_equal(out, tiled(out[: len(lhs)], np)),
          f"{name} add: lanes of one tile disagree")
    check(curve.decode(out[: len(lhs)]) == want,
          f"{name} add differs from refmath")


def preflight(seed: int) -> None:
    from distributed_groth16_tpu.ops import limb_kernels as lk
    from distributed_groth16_tpu.ops import refmath as rm
    from distributed_groth16_tpu.ops.constants import (
        G1_GENERATOR,
        G2_GENERATOR,
        R,
    )
    from distributed_groth16_tpu.ops.curve import g1, g2
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.ops.msm import encode_scalars_std, msm
    from distributed_groth16_tpu.ops.ntt import domain

    check(lk.use_pallas(), "use_pallas() is False on a TPU backend")
    rng = random.Random(seed)

    _check_group_add("G1", lk.lg1(), g1(), rm.G1, G1_GENERATOR)
    stage("preflight G1 add")
    _check_group_add("G2", lk.lg2(), g2(), rm.G2, G2_GENERATOR)
    stage("preflight G2 add")

    before = _route_counts()
    n = 1 << 15
    xs = [rng.randrange(R) for _ in range(n)]
    got = [int(v) for v in fr().decode(domain(n).fft(fr().encode(xs)))]
    check(got == rm.Domain(n).fft(xs), "2^15 NTT differs from refmath")
    n = 1 << 12
    pts = [G1_GENERATOR]
    for _ in range(n - 1):
        pts.append(rm.G1.add(pts[-1], G1_GENERATOR))  # pts[i] = (i+1) * G
    scalars = [rng.randrange(R) for _ in range(n)]
    out = msm(g1(), g1().encode(pts), encode_scalars_std(scalars))
    dlog = sum(s * (i + 1) for i, s in enumerate(scalars)) % R
    check(
        g1().decode(out) == rm.G1.scalar_mul(G1_GENERATOR, dlog),
        "2^12 tree MSM differs from refmath",
    )
    after = _route_counts()
    moved = {k: after[k] - before.get(k, 0) for k in after
             if after[k] != before.get(k, 0)}
    check(
        moved == {("ntt", "limb"): 1, ("msm", "tree"): 1},
        f"preflight took routes {moved}, want ntt/limb and msm/tree only",
    )
    stage("preflight 2^15 NTT + 2^12 tree MSM")


# ---------------------------------------------------------------------------
# the HTTP client (runs in a thread; same routes as api/cli.py)
# ---------------------------------------------------------------------------


class Client:
    def __init__(self, url: str):
        import requests

        self.http = requests.Session()
        self.url = url

    def get(self, path: str):
        r = self.http.get(self.url + path, timeout=60)
        r.raise_for_status()
        return r

    def post_multipart(self, path: str, fields: dict, ok=(200, 202)) -> dict:
        r = self.http.post(
            self.url + path,
            files={k: (k, v) for k, v in fields.items()},
            timeout=JOB_TIMEOUT_S,
        )
        check(r.status_code in ok,
              f"POST {path}: HTTP {r.status_code} {r.text[:300]}")
        return r.json()

    def submit(self, circuit_id: str, wtns: bytes, mpc: bool, l: int) -> str:
        fields = {"circuit_id": circuit_id.encode(), "witness_file": wtns}
        if mpc:
            fields.update(mpc=b"1", l=str(l).encode())
        return self.post_multipart("/jobs/prove", fields)["jobId"]

    def wait(self, job_id: str) -> dict:
        """Poll GET /jobs/{id} to a terminal state; return the DONE DTO."""
        deadline = time.monotonic() + JOB_TIMEOUT_S
        while True:
            doc = self.get(f"/jobs/{job_id}").json()
            if doc["state"] not in ("QUEUED", "RUNNING"):
                break
            check(time.monotonic() < deadline,
                  f"job {job_id} still {doc['state']} at the time limit")
            time.sleep(0.5)
        check(doc["state"] == "DONE",
              f"job {job_id} ended {doc['state']}: {doc.get('error')}")
        return doc

    def proof(self, job_id: str) -> bytes:
        return bytes(self.get(f"/jobs/{job_id}/result").json()["proof"])

    def verify(self, circuit_id: str, proof: bytes, publics: list) -> bool:
        r = self.http.post(
            self.url + "/verify_proof",
            json={
                "circuitId": circuit_id,
                "proof": list(proof),
                "publicInputs": [str(x) for x in publics],
            },
            timeout=600,
        )
        check(r.status_code == 200,
              f"/verify_proof: HTTP {r.status_code} {r.text[:300]}")
        return r.json()["isValid"]


def _timed(fn, *args):
    t = time.perf_counter()
    out = fn(*args)
    return out, round(time.perf_counter() - t, 3)


def drive(url: str, store, args, r1cs_bytes: bytes, cases: list,
          walls: dict) -> None:
    """cases: [(wtns bytes, public inputs)], one per witness."""
    from distributed_groth16_tpu.frontend.ark_serde import (
        proof_from_bytes,
        proof_to_bytes,
    )
    from distributed_groth16_tpu.models.groth16 import verify as oracle
    from distributed_groth16_tpu.models.groth16.keys import Proof
    from distributed_groth16_tpu.ops import refmath as rm
    from distributed_groth16_tpu.ops.constants import G1_GENERATOR

    c = Client(url)
    ready = c.get("/readyz").json()
    info = ready["buildInfo"]
    check(info["backend"] == "tpu"
          and info["deviceKind"] not in ("?", "none"),
          f"/readyz buildInfo does not name the TPU: {info}")
    routes0 = _route_counts()

    saved, walls["save_circuit"] = _timed(
        c.post_multipart, "/save_circuit",
        {"circuit_name": b"sha256", "r1cs_file": r1cs_bytes}, (200,),
    )
    cid = saved["circuitId"]
    _, pk = store.load(cid)
    check(pk.domain_size == 32768,
          f"QAP domain is {pk.domain_size}, want 32768")
    stage("save/setup", m=pk.domain_size, wall_s=walls["save_circuit"])

    def run_job(label, wtns, mpc, l):
        def go():
            doc = c.wait(c.submit(cid, wtns, mpc, l))
            return doc, c.proof(doc["jobId"])

        (doc, proof), walls[label] = _timed(go)
        check(doc["metrics"]["deviceMemory"] is not None,
              f"{label}: job DTO has no device memory (the XLA:CPU answer)")
        stage(label, wall_s=walls[label],
              phases_ms=json.dumps(doc["phases"]))
        return proof

    proofs = []  # (label, proof bytes, publics)
    if args.four_chip:
        # two l=1 jobs in flight together so they share one bucket, one
        # mesh lease and one batch program
        ids, t = [], time.perf_counter()
        for wtns, _ in cases:
            ids.append(c.submit(cid, wtns, True, 1))
        for i, (jid, (_, pubs)) in enumerate(zip(ids, cases)):
            doc = c.wait(jid)
            check(doc["metrics"]["deviceMemory"] is not None,
                  "batched job DTO has no device memory")
            proofs.append((f"mesh #{i + 1}", c.proof(jid), pubs))
        walls["mesh batch of 2"] = round(time.perf_counter() - t, 3)
        sched = c.get("/stats").json()["scheduler"]
        check(sched["batchesDispatched"] >= 1 and sched["jobsBatched"] == 2,
              f"jobs did not ride the batched mesh path: {sched}")
        stage("mesh batch of 2 (l=1, n=4)", wall_s=walls["mesh batch of 2"],
              batches=sched["batchesDispatched"])
    else:
        for i, (wtns, pubs) in enumerate(cases):
            proofs.append((f"prove #{i + 1}",
                           run_job(f"prove #{i + 1}", wtns, False, 2), pubs))
        for i, (wtns, pubs) in enumerate(cases):
            proofs.append((f"mpc_prove #{i + 1}",
                           run_job(f"mpc_prove #{i + 1}", wtns, True, 2),
                           pubs))
        crs = c.get("/stats").json()["crsCache"]
        check(crs["misses"] == 1 and crs["hits"] >= 1,
              f"second mpc_prove did not hit the packed-CRS cache: {crs}")
        k = len(cases)
        for i in range(k):
            check(proofs[i][1] == proofs[k + i][1],
                  f"prove and mpc_prove proofs of witness {i + 1} differ")

    t = time.perf_counter()
    for label, proof, pubs in proofs:
        check(c.verify(cid, proof, pubs),
              f"{label}: /verify_proof says invalid")
        check(oracle(pk.vk, proof_from_bytes(proof), pubs),
              f"{label}: host pairing oracle says invalid")
    good = proof_from_bytes(proofs[0][1])
    bad = proof_to_bytes(
        Proof(a=good.a, b=good.b, c=rm.G1.add(good.c, G1_GENERATOR))
    )
    check(not c.verify(cid, bad, proofs[0][2]),
          "/verify_proof accepted a corrupted proof")
    check(not oracle(pk.vk, proof_from_bytes(bad), proofs[0][2]),
          "host pairing oracle accepted a corrupted proof")
    walls["verify all"] = round(time.perf_counter() - t, 3)
    stage("verify", proofs=len(proofs), corrupted_rejected=True,
          identical=not args.four_chip)

    routes1 = _route_counts()
    moved = {k: v - routes0.get(k, 0) for k, v in routes1.items()}
    for generic in (("msm", "pippenger"), ("msm", "pippenger_chunked"),
                    ("msm_batched", "pippenger_vmap")):
        check(moved.get(generic, 0) == 0, f"route {generic} advanced: {moved}")
    if args.four_chip:
        check(moved.get(("msm_batched", "tree"), 0) > 0,
              f"mesh MSMs did not take the tree path: {moved}")
    else:
        # every JaxDomain transform of size >= 2048 counts under exactly one
        # of ntt/limb and ntt/row, and the path runs exactly 1 (setup's
        # h-query IFFT over 2m) + 6 per single-node proof: all took limb
        n_prove = len(cases)
        check(moved.get(("ntt", "limb"), 0) == 1 + 6 * n_prove,
              f"ntt/limb advanced {moved.get(('ntt', 'limb'))}, "
              f"want {1 + 6 * n_prove}: {moved}")
        # 4 MSMs per single-node proof: the three over the witness take
        # the limb-0 windows (the worker hands over the host's view of z),
        # the one over h all windows; 4 per party per MPC proof, all
        # full-width (the shares fill the field)
        check(moved.get(("msm", "tree_limb0"), 0) == 3 * n_prove,
              f"msm/tree_limb0 advanced {moved.get(('msm', 'tree_limb0'))}, "
              f"want {3 * n_prove}: {moved}")
        check(moved.get(("msm", "tree"), 0) >= n_prove + 4 * 8 * n_prove,
              f"msm/tree advanced too little: {moved}")
    stage("device path", routes=json.dumps(
        {"/".join(k): int(v) for k, v in sorted(moved.items()) if v}))


async def serve_and_drive(args, work: str, r1cs_bytes, cases, walls) -> None:
    from aiohttp import web

    from distributed_groth16_tpu.api.server import ApiServer
    from distributed_groth16_tpu.api.store import CircuitStore
    from distributed_groth16_tpu.utils.config import SchedulerConfig

    store = CircuitStore(os.path.join(work, "circuit_store"))
    sched = (
        SchedulerConfig(batch_max=2, batch_linger_ms=5000.0)
        if args.four_chip else None
    )
    runner = web.AppRunner(ApiServer(store=store, sched_cfg=sched).app())
    await runner.setup()
    try:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        await asyncio.to_thread(
            drive, f"http://127.0.0.1:{port}", store, args, r1cs_bytes,
            cases, walls,
        )
    finally:
        await runner.cleanup()


# ---------------------------------------------------------------------------


def device_as_jax_reports(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def result_line(device: dict) -> str:
    """The last stdout line of a passing run, to the driver's contract:
    the keys "ok" and "device" and no others."""
    return json.dumps({"ok": True, "device": device})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the messages hashed and the preflight data")
    ap.add_argument("--four-chip", action="store_true",
                    help="placement check on four devices instead of the "
                         "one-chip run (scheduler on, two l=1 mesh jobs)")
    args = ap.parse_args()

    require_package()
    jax, devices = require_tpu(4 if args.four_chip else 1)
    jax.monitoring.register_event_time_span_listener(TALLY.on_span)

    import jaxlib

    import distributed_groth16_tpu  # noqa: F401 — places the compile cache
    from distributed_groth16_tpu.frontend.readers import (
        write_r1cs,
        write_wtns,
    )
    from distributed_groth16_tpu.frontend.sha256 import sha256_circuit
    from distributed_groth16_tpu.telemetry import devmem

    try:
        libtpu_version = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu_version = "?"
    device = device_as_jax_reports(devices)
    cache_dir = jax.config.jax_compilation_cache_dir
    stage("backend", **device, jax=jax.__version__, cache_dir=cache_dir)

    if not args.four_chip:
        preflight(args.seed)

    cases, r1cs_bytes = [], None
    for i in range(2):
        cs, pubs = sha256_circuit(f"dg16 chip smoke {args.seed}/{i}".encode())
        r1cs, z = cs.finish()
        # the constraints do not depend on the message: one .r1cs serves all
        r1cs_bytes = r1cs_bytes or write_r1cs(r1cs)
        cases.append((write_wtns(z), pubs))
    stage("circuit", constraints=r1cs.num_constraints, wires=r1cs.num_wires)

    work = tempfile.mkdtemp(prefix="dg16-chip-smoke-")
    walls: dict = {}
    try:
        asyncio.run(serve_and_drive(args, work, r1cs_bytes, cases, walls))
    finally:
        shutil.rmtree(work, ignore_errors=True)

    mem = devmem.sample()
    check(all(m and m.get("peakBytes") for m in mem.values()),
          f"a device reports no memory use: {mem}")
    stage("device memory", **{k: v["peakBytes"] for k, v in mem.items()})

    by_fn = sorted(TALLY.compile_by_fn.items(), key=lambda kv: -kv[1])
    print("report " + json.dumps({
        "device": device,
        "mode": "four-chip" if args.four_chip else "one-chip",
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "cache_dir": cache_dir,
        "wall_seconds": round(time.perf_counter() - T0, 1),
        "request_wall_seconds": walls,
        "compile_seconds": round(TALLY.compile_s, 1),
        "trace_seconds": round(TALLY.trace_s, 1),
        "compile_seconds_top": {k: round(v, 1) for k, v in by_fn[:12]},
        "peak_hbm_bytes": {k: v["peakBytes"] for k, v in mem.items()},
        "note": "smoke observations, not benchmark results",
    }), flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
