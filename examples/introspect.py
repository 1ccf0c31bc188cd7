"""Circuit introspection + single-node prove — the reference's
groth16/examples/test.rs:1-261 analog.

test.rs loads the sha256 circom fixture, prints constraint-system
statistics (matrix row counts, assignment length, input/constraint
counts, struct sizes), builds a SECOND setup-only circuit from the same
config (no inputs pushed) and compares its stats, then times a proof
"without MPC" (create_proof_with_reduction_and_matrices, r = s = 0) and
pairing-verifies it twice (once through a reconstructed Proof struct).

This analog does the same over the mycircuit artifacts (the largest
circuit the reference ships with BOTH .wasm and .r1cs checked in;
test.rs's own sha256 fixture lacks a compiled .r1cs). Stats are byte
sizes of the device tensors rather than Rust mem::size_of, which is the
meaningful equivalent here.

Vector discovery: the artifact directory comes from $DG16_VECTORS
(default: the historical /root/reference/ark-circom/test-vectors). When
the artifacts are absent the example does NOT silently pass: it falls
back to the in-repo fixture — the same c <== a*b multiplier circuit
built natively (frontend/r1cs.py) — and runs the identical
introspect/prove/verify ladder, so a CI lane without the external repo
still proves and verifies. Set DG16_REQUIRE_VECTORS=1 to fail (exit 3)
instead of falling back.

Run: python examples/introspect.py [--a 3] [--b 11]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

VECTORS = os.environ.get(
    "DG16_VECTORS", "/root/reference/ark-circom/test-vectors"
)


def _nbytes(x) -> int:
    import numpy as np

    return np.asarray(x).nbytes


def _circom_circuits(args):
    """(r1cs, full_assignment, setup_only_r1cs) from the external circom
    artifacts — the test.rs builder/builder2 pair."""
    from distributed_groth16_tpu.frontend.builder import (
        CircomBuilder,
        CircomConfig,
    )

    wasm = f"{VECTORS}/mycircuit.wasm"
    r1cs_path = f"{VECTORS}/mycircuit.r1cs"
    cfg = CircomConfig(wasm, r1cs_path, sanity_check=True)
    builder = CircomBuilder(cfg)
    builder.push_input("a", args.a)
    builder.push_input("b", args.b)
    circuit = builder.build()

    # second, setup-only circuit from the same config (test.rs builder2:
    # no inputs pushed, no witness computed)
    builder2 = CircomBuilder(cfg)
    circuit2 = builder2.setup()
    assert circuit2.witness is None
    return circuit.r1cs, circuit.witness, circuit2.r1cs


def _fixture_circuits(args):
    """The in-repo fallback fixture: mycircuit's c <== a*b multiplier,
    built natively with the ConstraintSystem API — same instance/witness
    shape as the circom artifact, no external files needed."""
    from distributed_groth16_tpu.frontend.r1cs import ConstraintSystem
    from distributed_groth16_tpu.ops.constants import R

    def build():
        cs = ConstraintSystem()
        c = cs.new_instance(args.a * args.b % R)
        aw = cs.new_witness(args.a)
        bw = cs.new_witness(args.b)
        cs.enforce([(1, aw)], [(1, bw)], [(1, c)])
        return cs.finish()

    r1cs, z = build()
    r1cs2, _ = build()  # the setup-only twin
    return r1cs, z, r1cs2


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--a", type=int, default=3)
    ap.add_argument("--b", type=int, default=11)
    args = ap.parse_args()

    from distributed_groth16_tpu.models.groth16 import setup, verify
    from distributed_groth16_tpu.models.groth16.prove import prove_single
    from distributed_groth16_tpu.models.groth16.qap import CompiledR1CS
    from distributed_groth16_tpu.ops.field import fr

    have_vectors = os.path.exists(f"{VECTORS}/mycircuit.wasm") and (
        os.path.exists(f"{VECTORS}/mycircuit.r1cs")
    )
    if not have_vectors and os.environ.get("DG16_REQUIRE_VECTORS") == "1":
        print(
            f"introspect: FAIL — circom artifacts not found under "
            f"{VECTORS} and DG16_REQUIRE_VECTORS=1 (set DG16_VECTORS to "
            f"the ark-circom test-vectors directory)",
            file=sys.stderr,
        )
        return 3

    print(f"Current working directory: {os.getcwd()}")
    if have_vectors:
        print(f"using circom artifacts from {VECTORS}")
        r1cs, full_assignment, r1cs2 = _circom_circuits(args)
    else:
        print(
            f"circom artifacts not found under {VECTORS}; using the "
            f"in-repo multiplier fixture (set DG16_VECTORS to override)"
        )
        r1cs, full_assignment, r1cs2 = _fixture_circuits(args)

    pk = setup(r1cs, seed=42)

    # -- introspection block (test.rs:171-205) -----------------------------
    pk_bytes = sum(
        _nbytes(t)
        for t in (
            pk.a_query, pk.b_g1_query, pk.b_g2_query, pk.h_query, pk.l_query
        )
    )
    print(f"Size of pk (query tensors): {pk_bytes} bytes")
    print(f"Size of vk: {len(pk.vk.gamma_abc_g1)} gamma_abc points")
    print(f"Matrix A len: {len(r1cs.a)}")
    print(f"Matrix B len: {len(r1cs.b)}")
    print(f"Matrix C len: {len(r1cs.c)}")
    nnz = sum(len(row) for row in r1cs.a + r1cs.b + r1cs.c)
    print(f"Matrix nonzeros (A+B+C): {nnz}")
    print(f"Full assignment len: {len(full_assignment)}")
    print(f"Number of inputs: {r1cs.num_instance}")
    print(f"Number of constraints: {r1cs.num_constraints}")
    print(f"Number of inputs2: {r1cs2.num_instance}")
    print(f"Number of constraints2: {r1cs2.num_constraints}")

    # -- proof without MPC, r = s = 0 (test.rs:211-231) --------------------
    comp = CompiledR1CS(r1cs)
    z_mont = fr().encode(full_assignment)
    t0 = time.time()
    proof = prove_single(pk, comp, z_mont, r=0, s=0)
    dt = time.time() - t0
    print(f"Proof: a={proof.a} b={proof.b} c={proof.c}")
    print(f"Time taken to create proof without MPC: {dt:.3f}s")

    public = full_assignment[1 : r1cs.num_instance]
    ok1 = verify(pk.vk, proof, public)
    assert ok1, "Proof verification failed!"
    # reconstructed-proof second verification (test.rs:246-260)
    from distributed_groth16_tpu.models.groth16.keys import Proof

    proof2 = Proof(a=proof.a, b=proof.b, c=proof.c)
    ok2 = verify(pk.vk, proof2, public)
    assert ok2, "Reconstructed proof verification failed!"
    print("both verifications passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
