"""Round benchmark — prints ONE JSON line. Runs on a TPU backend only: on
any other backend every mode exits non-zero before measuring anything, and
an exception in any stage is a non-zero exit, never a partial result.

Metric (BASELINE.json): "Groth16 prover wall-clock + MSM scalar-muls/sec
(SHA-256 circuit, BN254)". The headline number is the MSM kernel throughput
on the chip — the dominant per-party compute of the prover (five MSMs
per proof, dist-primitives/src/dmsm/mod.rs:82): BN254 G1 MSM via the
limb-major Pallas tree path (ops/limb_kernels.py), steady-state
scalar-muls/sec, measured as a staged 2^12 -> 2^16 -> 2^20 sweep (headline
= the largest size; per-size numbers are kept as msm_2e*_ keys).

Timing methodology: the benchmark runs K back-to-back MSMs *inside one
jitted program* (distinct scalars per iteration, checksummed output) and
reports the marginal cost (t_K - t_1) / (K - 1) with full host
materialisation as the fence. This measures on-device time, excluding
one-off host->device transfer. The MSM runs over n DISTINCT random bases
(k_i * G, generated by one windowed fixed-base batch mul at setup, excluded
from timing — ADVICE r5 #8), so the headline number needs no
broadcast-generator footnote.

vs_baseline: the reference publishes no numbers (SURVEY §6) and its Rust
toolchain is unavailable here, so the denominator remains the documented
ballpark of arkworks' parallel CPU MSM on a modern host, ~1.0e6
scalar-muls/sec at this size.
"""

from __future__ import annotations

import json
import os
import time

ARKWORKS_CPU_MSM_PER_SEC = 1.0e6  # documented ballpark, see module docstring


def _require_tpu() -> str:
    """The platform this run measures on — a TPU, or no run at all."""
    import jax

    platform = jax.devices()[0].platform
    if jax.default_backend() != "tpu" or platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU backend, jax found {platform!r}; "
            "nothing was measured"
        )
    return platform


def _emit(res: dict, stage_s: dict, platform: str) -> None:
    """Print the single JSON line."""
    from distributed_groth16_tpu.ops import limb_kernels
    from distributed_groth16_tpu.telemetry import devmem, metrics, roofline

    print(json.dumps({
        "metric": res["metric"],
        "value": res["value"],
        "unit": res.get("unit", "scalar-muls/sec"),
        # numeric always (driver-parsed); the metric name carries the
        # measured size, and the denominator stays the 2^16-2^20
        # arkworks ballpark documented in BASELINE.md
        "vs_baseline": round(res["value"] / ARKWORKS_CPU_MSM_PER_SEC, 4),
        "platform": platform,
        "method": "marginal (t3-t1)/2, jitted K-loop, host-sync",
        "stage_seconds": dict(stage_s),
        "pallas_roll": limb_kernels._ROLL_MODE,
        **{k: v for k, v in res.items() if k not in ("metric", "value")},
        # same series names as GET /metrics (docs/OBSERVABILITY.md), so
        # bench lines and service scrapes join on metric name
        "metrics": metrics.registry().snapshot(),
        # HBM state + the roofline peak table the run attributed against
        # (device observatory, docs/OBSERVABILITY.md)
        "deviceMemory": devmem.sample(),
        "peaks": roofline.peaks(),
    }), flush=True)


def batch_main(batch_n: int) -> None:
    """--batch N: the scheduler's batched proving path on an 8-device mesh.

    Measures the throughput lever docs/SCHEDULER.md describes: N
    same-circuit proofs as ONE SPMD batch program (l=2, so `make_mesh`
    needs 8 devices of the backend) over one shared packed CRS. Emits `proofs_per_sec` and
    `batch_amortized_seconds` (steady-state wall per proof, compile and
    setup excluded via one warm-up run) — sweep N (1, 2, 4, ...) and the
    amortized number should FALL as fixed per-batch dispatch/king costs
    spread over more proofs. Same one-JSON-line contract as the MSM
    bench; vs_baseline is not meaningful here and reports 0."""
    batch_n = max(1, batch_n)
    platform = _require_tpu()
    from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
    from distributed_groth16_tpu.models.groth16 import (
        CompiledR1CS,
        pack_proving_key,
        setup,
        verify,
    )
    from distributed_groth16_tpu.models.groth16.mesh_prover import (
        build_batch_mesh_prover,
    )
    from distributed_groth16_tpu.ops.constants import R as _FR
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.parallel.mesh import make_mesh
    from distributed_groth16_tpu.parallel.pss import PackedSharingParams
    from distributed_groth16_tpu.scheduler.batch_prover import (
        _next_pow2,
        prove_batch,
    )

    from distributed_groth16_tpu.telemetry.perf import make_record

    length = int(os.environ.get("DG16_BENCH_BATCH_CHAIN", "31"))
    stage_s: dict = {}
    # perf_counter, NOT time.time: stage timings are differences, and the
    # wall clock is not monotonic — an NTP step mid-stage corrupts
    # stage_seconds silently (ISSUE 11 satellite)
    t0 = time.perf_counter()
    cs = mult_chain_circuit(9, length)
    r1cs, _ = cs.finish()
    pp = PackedSharingParams(2)
    pk = setup(r1cs, seed=7)
    comp = CompiledR1CS(r1cs)
    crs = pack_proving_key(pk, pp)
    F = fr()

    def chain_witness(x0: int) -> list[int]:
        vals = [x0 % _FR]
        for _ in range(length):
            v = vals[-1]
            vals.append((v * v + v) % _FR)
        return [1, vals[-1]] + vals[:-1]

    z_monts = [F.encode(chain_witness(3 + i)) for i in range(batch_n)]
    mesh = make_mesh(pp.n)
    prover = build_batch_mesh_prover(
        pp, pk.domain_size, mesh, _next_pow2(batch_n)
    )
    stage_s["setup"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    proofs = prove_batch(pk, comp, pp, mesh, crs, z_monts, prover=prover)
    compile_s = time.perf_counter() - t0
    stage_s["warmup_compile"] = round(compile_s, 1)
    ni = r1cs.num_instance
    assert verify(pk.vk, proofs[0], chain_witness(3)[1:ni]), "bad proof"
    reps = int(os.environ.get("DG16_BENCH_BATCH_REPS", "3"))
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        prove_batch(pk, comp, pp, mesh, crs, z_monts, prover=prover)
        walls.append(time.perf_counter() - t0)
    wall = sum(walls) / reps
    stage_s[f"batch_{batch_n}x{reps}"] = round(sum(walls), 1)
    # the batch path emits the SAME per-kernel record shape as benchgate
    # and the MSM sweep (telemetry/perf.make_record), so the three
    # emitters cannot drift (size = log2 of the padded batch program)
    rec = make_record(
        kernel="batch_prover",
        size=_next_pow2(batch_n).bit_length() - 1,
        items=batch_n,
        unit="proofs/sec",
        seconds=walls,
        compile_seconds=compile_s,
        # the record's throughput is median-based (make_record); the
        # headline proofs_per_sec below stays the historical mean
        extra={"method": "warm reps, median"},
    )
    _emit(
        {
            "metric": f"batch_prover_proofs_per_sec_b{batch_n}",
            "value": round(batch_n / wall, 3),
            "unit": "proofs/sec",
            "vs_baseline": 0,
            "batch": batch_n,
            "proofs_per_sec": round(batch_n / wall, 3),
            "batch_amortized_seconds": round(wall / batch_n, 4),
            "batch_wall_seconds": round(wall, 4),
            "constraints": r1cs.num_constraints,
            "domain_size": pk.domain_size,
            "l": pp.l,
            "n_parties": pp.n,
            "kernels": {rec["key"]: rec},
        },
        stage_s,
        platform,
    )


def verify_main(verify_n: int) -> None:
    """--verify N: the verification plane's batched RLC check
    (docs/VERIFY.md).

    Proves N same-circuit proofs once at setup (outside timing), then
    measures `verify_batch` — N proofs folded into ONE multi-pairing of
    N+3 Miller loops plus the batched public-input MSM — over warm reps.
    Emits `verifies_per_sec` and the per-proof amortized wall; sweep N
    (1, 4, 16, ...) and the amortized number should FALL as the 3 fixed
    pairings and the device dispatch spread over more proofs. Same
    one-JSON-line contract; vs_baseline is not meaningful and reports 0."""
    verify_n = max(1, verify_n)
    platform = _require_tpu()
    from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
    from distributed_groth16_tpu.models.groth16 import CompiledR1CS, setup
    from distributed_groth16_tpu.models.groth16.prove import prove_single
    from distributed_groth16_tpu.ops.constants import R as _FR
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.telemetry.perf import make_record
    from distributed_groth16_tpu.verifier import (
        PreparedVerifyingKey,
        fresh_seed,
        verify_batch,
    )

    length = int(os.environ.get("DG16_BENCH_BATCH_CHAIN", "31"))
    stage_s: dict = {}
    t0 = time.perf_counter()
    cs = mult_chain_circuit(9, length)
    r1cs, _ = cs.finish()
    pk = setup(r1cs, seed=7)
    comp = CompiledR1CS(r1cs)
    F = fr()

    def chain_witness(x0: int) -> list[int]:
        vals = [x0 % _FR]
        for _ in range(length):
            v = vals[-1]
            vals.append((v * v + v) % _FR)
        return [1, vals[-1]] + vals[:-1]

    ni = r1cs.num_instance
    proofs, publics = [], []
    for i in range(verify_n):
        z = chain_witness(3 + i)
        proofs.append(prove_single(pk, comp, F.encode(z), r=11 + i, s=13 + i))
        publics.append([str(x) for x in z[1:ni]])
    pvk = PreparedVerifyingKey.prepare("bench", pk.vk)
    stage_s["setup"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    assert verify_batch(pvk, proofs, publics, seed=fresh_seed()), "bad batch"
    stage_s["warmup_compile"] = round(time.perf_counter() - t0, 1)
    reps = int(os.environ.get("DG16_BENCH_BATCH_REPS", "3"))
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        ok = verify_batch(pvk, proofs, publics, seed=fresh_seed())
        walls.append(time.perf_counter() - t0)
        assert ok, "bad batch"
    wall = sum(walls) / reps
    stage_s[f"verify_{verify_n}x{reps}"] = round(sum(walls), 1)
    rec = make_record(
        kernel="verify_batch",
        size=max(0, verify_n.bit_length() - 1),
        items=verify_n,
        unit="proofs/sec",
        seconds=walls,
        extra={"method": "warm reps, median"},
    )
    _emit(
        {
            "metric": f"verify_batch_verifies_per_sec_b{verify_n}",
            "value": round(verify_n / wall, 3),
            "unit": "verifies/sec",
            "vs_baseline": 0,
            "batch": verify_n,
            "verifies_per_sec": round(verify_n / wall, 3),
            "verify_amortized_seconds": round(wall / verify_n, 4),
            "verify_wall_seconds": round(wall, 4),
            "miller_loops": verify_n + 3,
            "pairings_saved": max(0, 3 * verify_n - 3),
            "constraints": r1cs.num_constraints,
            "domain_size": pk.domain_size,
            "kernels": {rec["key"]: rec},
        },
        stage_s,
        platform,
    )


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description="one-JSON-line benchmark")
    ap.add_argument(
        "--batch", type=int, default=0, metavar="N",
        help="run the batched-prover sweep at batch size N instead of "
             "the MSM bench (see docs/SCHEDULER.md)",
    )
    ap.add_argument(
        "--verify", type=int, default=0, metavar="N",
        help="run the batched-verification sweep at batch size N instead "
             "of the MSM bench (see docs/VERIFY.md)",
    )
    args, _ = ap.parse_known_args()
    if args.batch:
        return batch_main(args.batch)
    if args.verify:
        return verify_main(args.verify)
    platform = _require_tpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_groth16_tpu.ops.constants import R
    from distributed_groth16_tpu.ops.fixedbase import fixed_base_mul
    from distributed_groth16_tpu.ops.limb_kernels import _msm_tree_jit, lg1
    from distributed_groth16_tpu.ops.msm import encode_scalars_std

    from distributed_groth16_tpu.telemetry import metrics as telemetry_metrics
    from distributed_groth16_tpu.telemetry.perf import make_record
    from distributed_groth16_tpu.utils.benchtools import marginal_cost

    bench_stage_seconds = telemetry_metrics.registry().histogram(
        "bench_stage_seconds", "Wall-clock seconds per bench stage",
        ("stage",),
    )
    bench_msm_rate = telemetry_metrics.registry().gauge(
        "bench_msm_scalar_muls_per_sec",
        "Measured steady-state MSM throughput, per size",
        ("size",),
    )

    inner = _msm_tree_jit.__wrapped__
    rng = np.random.default_rng(0)

    def measure(log2n: int) -> tuple[float, float]:
        """(muls/sec, per-msm seconds) at n = 2^log2n."""
        n = 1 << log2n
        scalars = encode_scalars_std(
            [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
        )
        # n DISTINCT random bases (k_i * G via one windowed fixed-base
        # batch mul, ops/fixedbase.py), generated at setup OUTSIDE the
        # timed region — the headline number measures an MSM over real
        # point data, not a broadcast generator (ADVICE r5 #8)
        t0 = time.perf_counter()
        base_sc = encode_scalars_std(
            [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
        )
        points = jax.block_until_ready(fixed_base_mul("g1", base_sc))
        bench_stage_seconds.labels(stage=f"gen_bases_2e{log2n}").observe(
            time.perf_counter() - t0
        )

        # ONE compiled program for every K: the repeat count is a traced
        # fori_loop bound, so the K=3 timing costs no extra compile (the
        # old trace-time K-unroll tripled the graph of the already
        # compile-bound tree program).
        @jax.jit
        def run(points, scalars, k):
            def body(i, acc):
                sc = scalars ^ i.astype(jnp.uint32)  # distinct work per iter
                out = inner(lg1(), points, sc, 8, None)
                return acc + out.sum(dtype=jnp.uint32)

            return jax.lax.fori_loop(0, k, body, jnp.uint32(0))

        def make(k: int):
            return lambda points, scalars: run(points, scalars, k)

        per_msm = marginal_cost(make, (points, scalars))
        return n / per_msm, per_msm

    # smallest size first; perf_counter throughout — stage timings are
    # intervals, and the wall clock can step (NTP) mid-run
    res: dict = {}
    stage_s: dict = {}
    for log2n in (12, 16, 20):
        t0 = time.perf_counter()
        muls_per_sec, per_msm = measure(log2n)
        stage_dt = time.perf_counter() - t0
        stage_s[f"msm_2e{log2n}"] = round(stage_dt, 1)
        bench_stage_seconds.labels(stage=f"msm_2e{log2n}").observe(stage_dt)
        bench_msm_rate.labels(size=f"2e{log2n}").set(muls_per_sec)
        res["metric"] = f"msm_g1_scalar_muls_per_sec_2e{log2n}"
        res["value"] = round(muls_per_sec, 1)
        res["per_msm_ms"] = round(per_msm * 1e3, 1)
        res["measured_log2n"] = log2n
        res[f"msm_2e{log2n}_per_sec"] = round(muls_per_sec, 1)
        res[f"msm_2e{log2n}_ms"] = round(per_msm * 1e3, 1)
        # same record shape as benchgate's msm_g1_tree entries (this IS
        # the tree kernel, timed marginally) — the bench line's "kernels"
        # section and perf runs join on key and schema
        rec = make_record(
            kernel="msm_g1_tree",
            size=log2n,
            items=1 << log2n,
            unit="scalar-muls/sec",
            seconds=per_msm,
            extra={"method": "marginal (t3-t1)/2"},
        )
        res.setdefault("kernels", {})[rec["key"]] = rec
    # BASELINE config 3's kernel: radix-2 NTT over Fr (Pallas four-step
    # limb path), 2^20 coefficients
    from distributed_groth16_tpu.ops.ntt_limb import ntt_limb

    n_ntt = 1 << 20
    x = jnp.asarray(
        rng.integers(0, 1 << 16, size=(16, n_ntt), dtype=np.uint32)
    )

    @jax.jit
    def run_ntt(x, k):
        def body(i, acc):
            out = ntt_limb(x ^ i.astype(jnp.uint32), n_ntt, False)
            return acc + out.sum(dtype=jnp.uint32)

        return jax.lax.fori_loop(0, k, body, jnp.uint32(0))

    def make_ntt(k: int):
        return lambda x: run_ntt(x, k)

    t0 = time.perf_counter()
    per_ntt = marginal_cost(make_ntt, (x,))
    res["ntt_2e20_ms"] = round(per_ntt * 1e3, 1)
    stage_dt = time.perf_counter() - t0
    stage_s["ntt_2e20"] = round(stage_dt, 1)
    bench_stage_seconds.labels(stage="ntt_2e20").observe(stage_dt)
    rec = make_record(
        kernel="ntt_limb_fwd",
        size=20,
        items=1 << 20,
        unit="coeffs/sec",
        seconds=per_ntt,
        extra={"method": "marginal (t3-t1)/2"},
    )
    res["kernels"][rec["key"]] = rec
    _emit(res, stage_s, platform)


if __name__ == "__main__":
    main()
