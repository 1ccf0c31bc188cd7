"""Mesh-prover scale run ON THE CPU: the full SPMD proving step at a real
domain size (default m=4096, n=8 parties), checked against the host-oracle
proof core — the mesh path executing beyond toy shapes.

This script hard-forces XLA:CPU with 8 VIRTUAL devices. It is a
correctness and compile-time check of the mesh program, not a multi-chip
run: nothing it prints is a device number. The four-chip placement check
is `python chip_smoke.py --four-chip`.

Run:
    python scripts/mesh_scale_run.py [--m 4096] [--check]
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _ROOT)

flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags).strip()
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8"
).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--m", type=int, default=4096)
    p.add_argument("--check", action="store_true",
                   help="verify the proof cores against the host oracle")
    args = p.parse_args()

    from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
    from distributed_groth16_tpu.models.groth16 import (
        CompiledR1CS,
        pack_proving_key,
        setup,
        verify,
    )
    from distributed_groth16_tpu.models.groth16.mesh_prover import (
        MeshProverInputs,
        mesh_prove,
    )
    from distributed_groth16_tpu.models.groth16.prove import (
        pack_from_witness,
        prove_single,
    )
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.parallel.mesh import make_mesh
    from distributed_groth16_tpu.parallel.pss import PackedSharingParams
    from distributed_groth16_tpu.utils.timers import PhaseTimings, phase

    timings = PhaseTimings()
    l = 2
    pp = PackedSharingParams(l)
    nc = args.m - 2
    with phase("build circuit", timings):
        cs = mult_chain_circuit(999992, nc)
        r1cs, z = cs.finish()
    with phase("setup", timings):
        pk = setup(r1cs)
    m = pk.domain_size
    assert m >= args.m, (m, args.m)
    F = fr()
    z_mont = F.encode(z)
    comp = CompiledR1CS(r1cs)

    with phase("packing", timings):
        qap_shares = comp.qap(z_mont).pss(pp)
        crs = pack_proving_key(pk, pp)
        a_sh = pack_from_witness(pp, z_mont[1:])
        ax_sh = pack_from_witness(pp, z_mont[r1cs.num_instance:])

        def stack(get):
            return jnp.stack([get(i) for i in range(pp.n)])

        inp = MeshProverInputs(
            qap_a=stack(lambda i: qap_shares[i].a),
            qap_b=stack(lambda i: qap_shares[i].b),
            qap_c=stack(lambda i: qap_shares[i].c),
            a_share=a_sh,
            ax_share=ax_sh,
            s=stack(lambda i: crs[i].s),
            u=stack(lambda i: crs[i].u),
            v=stack(lambda i: crs[i].v),
            w=stack(lambda i: crs[i].w),
        )

    mesh = make_mesh(pp.n)
    with phase("mesh prove (compile+run)", timings):
        t0 = time.time()
        pa, pb, pc = mesh_prove(pp, m, mesh, inp)
        jax.block_until_ready((pa, pb, pc))
        total = time.time() - t0
    with phase("mesh prove (steady-state rerun)", timings):
        pa, pb, pc = mesh_prove(pp, m, mesh, inp)
        jax.block_until_ready((pa, pb, pc))

    print(f"mesh proving step ran at m={m}, n={pp.n} parties "
          f"(first call incl. compile: {total:.1f}s)")
    if args.check:
        with phase("host-oracle check", timings):
            single = prove_single(pk, comp, z_mont)
            from distributed_groth16_tpu.models.groth16.prove import (
                PartyProofShare,
                reassemble_proof,
            )
            share = PartyProofShare(a=pa, b=pb, c=pc)
            proof = reassemble_proof(share, pk)
            ok = verify(pk.vk, proof, z[1:r1cs.num_instance])
            match = (proof.a, proof.b, proof.c) == (
                single.a, single.b, single.c,
            )
            print(f"pairing verify: {ok}; matches single-node: {match}")
            if not (ok and match):
                return 1
    print("phase timings (ms):")
    for k, v in timings.as_millis().items():
        print(f"  {k:34s} {v:12.1f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
