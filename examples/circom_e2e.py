"""End-to-end pipeline from REAL circom artifacts — the reference's
test.rs role (groth16/examples/test.rs:130-161): CircomConfig loads the
compiled .wasm + .r1cs pair, CircomBuilder computes the witness (native C
execution tier), then setup -> single-node zk prove -> n-party MPC
prove -> pairing verification of both proofs (exit code 0 iff both
verify).

Uses the mycircuit artifacts the reference ships (test.rs itself targets
the sha256 fixture, whose compiled .r1cs is not checked in — mycircuit is
the largest circuit with both artifacts present).

Run: python examples/circom_e2e.py [--a 3] [--b 11]
(runs on whatever backend JAX_PLATFORMS selects)
"""

from __future__ import annotations

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

VECTORS = "/root/reference/ark-circom/test-vectors"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--a", type=int, default=3)
    ap.add_argument("--b", type=int, default=11)
    ap.add_argument("--l", type=int, default=2)
    args = ap.parse_args()

    from distributed_groth16_tpu.frontend.builder import (
        CircomBuilder,
        CircomConfig,
    )
    from distributed_groth16_tpu.models.groth16 import (
        CompiledR1CS,
        distributed_prove_party,
        pack_from_witness,
        pack_proving_key,
        reassemble_proof,
        setup,
        verify,
    )
    from distributed_groth16_tpu.models.groth16.prove import prove_single
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.parallel.net import simulate_network_round
    from distributed_groth16_tpu.parallel.pss import PackedSharingParams

    t0 = time.time()
    cfg = CircomConfig(
        f"{VECTORS}/mycircuit.wasm", f"{VECTORS}/mycircuit.r1cs",
        sanity_check=True,
    )
    builder = CircomBuilder(cfg)
    builder.push_input("a", args.a)
    builder.push_input("b", args.b)
    circuit = builder.build()
    print(f"witness ({len(circuit.witness)} wires, C tier) in "
          f"{time.time()-t0:.2f}s; public = {circuit.public_inputs()}")

    r1cs = circuit.r1cs
    pk = setup(r1cs, seed=7)
    comp = CompiledR1CS(r1cs)
    z_mont = fr().encode(circuit.witness)

    t0 = time.time()
    proof = prove_single(pk, comp, z_mont, r=11, s=13)  # zk proof
    ok1 = verify(pk.vk, proof, circuit.public_inputs())
    print(f"single-node zk prove+verify in {time.time()-t0:.2f}s: {ok1}")

    # 8-party MPC prove over packed shares (the dsha256 template)
    pp = PackedSharingParams(args.l)
    qap_shares = comp.qap(z_mont).pss(pp)
    crs = pack_proving_key(pk, pp, strip=True)
    ni = r1cs.num_instance
    a_sh = pack_from_witness(pp, z_mont[1:])
    ax_sh = pack_from_witness(pp, z_mont[ni:])

    async def party(net, data):
        qs, crs_share = data
        return await distributed_prove_party(
            pp, crs_share, qs, a_sh[net.party_id], ax_sh[net.party_id], net
        )

    t0 = time.time()
    outs = simulate_network_round(
        pp.n, party, list(zip(qap_shares, crs))
    )
    mpc_proof = reassemble_proof(outs[0], pk)
    ok2 = verify(pk.vk, mpc_proof, circuit.public_inputs())
    print(f"{pp.n}-party MPC prove+verify in {time.time()-t0:.2f}s: {ok2}")
    return 0 if (ok1 and ok2) else 1


if __name__ == "__main__":
    sys.exit(main())
