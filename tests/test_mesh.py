"""SPMD mesh backend test: the full proving step as one shard_map program
over a virtual mesh must reproduce the async star backend's proof exactly
(and verify under the pairing check), whether the mesh gives each of the 8
parties a device of its own (k = 1), spans 4 devices with 2 parties each
(k = 2), or holds all 8 on one device (k = 8)."""

import random

import jax
import jax.numpy as jnp
import pytest

from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.models.groth16 import (
    CompiledR1CS,
    pack_from_witness,
    pack_proving_key,
    reassemble_proof,
    setup,
    verify,
)
from distributed_groth16_tpu.models.groth16.prove import PartyProofShare
from distributed_groth16_tpu.models.groth16.reference import prove_host
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.models.groth16.mesh_prover import (
    MeshProverInputs,
    mesh_prove,
    mesh_prove_zk,
)
from distributed_groth16_tpu.parallel.mesh import make_mesh
from distributed_groth16_tpu.parallel.pss import PackedSharingParams

L = 2
N = 4 * L


def _refuse(*args, **kwargs):
    raise AssertionError("the mesh d_msm unpacked in the exponent")


def _mesh(pp, k):
    """The parties mesh over the first n/k devices: k parties a shard."""
    mesh = make_mesh(pp.n, jax.devices()[: pp.n // k])
    assert mesh.devices.size == pp.n // k
    return mesh


@pytest.mark.skipif(len(jax.devices()) < N, reason="needs 8 devices")
@pytest.mark.parametrize("k", [1, 2, 8])
def test_mesh_prover_matches_oracle(k, monkeypatch):
    monkeypatch.setattr(PackedSharingParams, "unpackexp", _refuse)
    cs = mult_chain_circuit(5, 11)
    r1cs, z = cs.finish()
    pp = PackedSharingParams(L)
    pk = setup(r1cs, seed=3)
    comp = CompiledR1CS(r1cs)
    z_mont = fr().encode(z)
    qap = comp.qap(z_mont)
    qap_shares = qap.pss(pp)
    crs = pack_proving_key(pk, pp)
    ni = r1cs.num_instance
    a_sh = pack_from_witness(pp, z_mont[1:])
    ax_sh = pack_from_witness(pp, z_mont[ni:])

    inp = MeshProverInputs(
        qap_a=jnp.stack([s.a for s in qap_shares]),
        qap_b=jnp.stack([s.b for s in qap_shares]),
        qap_c=jnp.stack([s.c for s in qap_shares]),
        a_share=a_sh,
        ax_share=ax_sh,
        s=jnp.stack([c.s for c in crs]),
        u=jnp.stack([c.u for c in crs]),
        v=jnp.stack([c.v for c in crs]),
        w=jnp.stack([c.w for c in crs]),
    )
    pa, pb, pc = mesh_prove(pp, pk.domain_size, _mesh(pp, k), inp)
    proof = reassemble_proof(PartyProofShare(a=pa, b=pb, c=pc), pk)

    assert verify(pk.vk, proof, z[1:ni])
    oracle = prove_host(pk, r1cs, z)
    assert proof.a == oracle.a
    assert proof.b == oracle.b
    assert proof.c == oracle.c


@pytest.mark.skipif(len(jax.devices()) < N, reason="needs 8 devices")
def test_mesh_prover_zk_randomized_proof_verifies():
    """The SPMD path must emit r,s-randomized (zero-knowledge) proofs, like
    the async-star path (prove.rs:10-137) — and match the single-node zk
    prover bit-exactly for the same r,s. Two parties a shard: the fourth
    G1 row rides the same per-shard batch."""
    from distributed_groth16_tpu.models.groth16.prove import prove_single

    cs = mult_chain_circuit(5, 11)
    r1cs, z = cs.finish()
    pp = PackedSharingParams(L)
    pk = setup(r1cs, seed=3)
    comp = CompiledR1CS(r1cs)
    z_mont = fr().encode(z)
    qap_shares = comp.qap(z_mont).pss(pp)
    crs = pack_proving_key(pk, pp)
    ni = r1cs.num_instance
    inp = MeshProverInputs(
        qap_a=jnp.stack([s.a for s in qap_shares]),
        qap_b=jnp.stack([s.b for s in qap_shares]),
        qap_c=jnp.stack([s.c for s in qap_shares]),
        a_share=pack_from_witness(pp, z_mont[1:]),
        ax_share=pack_from_witness(pp, z_mont[ni:]),
        s=jnp.stack([c.s for c in crs]),
        u=jnp.stack([c.u for c in crs]),
        v=jnp.stack([c.v for c in crs]),
        w=jnp.stack([c.w for c in crs]),
        h=jnp.stack([c.h for c in crs]),
    )
    r_rand, s_rand = 0xDEADBEEF12345, 0xC0FFEE9876
    proof = mesh_prove_zk(
        pp, pk.domain_size, _mesh(pp, 2), inp, pk, r_rand, s_rand
    )

    assert verify(pk.vk, proof, z[1:ni])
    oracle = prove_single(pk, comp, z_mont, r=r_rand, s=s_rand)
    assert proof.a == oracle.a
    assert proof.b == oracle.b
    assert proof.c == oracle.c
    det = prove_host(pk, r1cs, z)
    assert proof.a != det.a  # actually randomized


@pytest.mark.skipif(len(jax.devices()) < N, reason="needs 8 devices")
def test_mesh_d_msm_sums_weighted_points_and_unpacks_nothing(monkeypatch):
    """A G1 d_msm on 4 devices, two parties a shard: each party weighs its
    shares by its public unpack weight, every shard adds the 8 gathered
    points, and the sum is the clear MSM. Nothing unpacks in the
    exponent; the program counts what its gathers bring each device."""
    from jax.sharding import PartitionSpec as P

    from distributed_groth16_tpu.ops import refmath as rm
    from distributed_groth16_tpu.ops.constants import G1_GENERATOR, R
    from distributed_groth16_tpu.ops.curve import g1
    from distributed_groth16_tpu.parallel.mesh import (
        AXIS,
        _mesh_dmsm,
        mesh_jit,
        shard_map,
    )
    from distributed_groth16_tpu.parallel.packing import pack_consecutive
    from distributed_groth16_tpu.parallel.pss import pack_host

    monkeypatch.setattr(PackedSharingParams, "unpackexp", _refuse)
    pp = PackedSharingParams(L)
    F, C = fr(), g1()
    rng = random.Random(36)
    m = 8
    ks = [rng.randrange(1, R) for _ in range(m)]
    scalars = [rng.randrange(R) for _ in range(m)]
    expected = rm.G1.scalar_mul(
        G1_GENERATOR, sum(a * b for a, b in zip(ks, scalars)) % R)
    chunks = [pack_host(pp, ks[c:c + L]) for c in range(0, m, L)]
    bases = jnp.stack([
        C.encode([rm.G1.scalar_mul(G1_GENERATOR, ch[j]) for ch in chunks])
        for j in range(N)
    ])  # (n, m/l, 3, 16)
    shares = pack_consecutive(pp, F.encode(scalars))  # (n, m/l, 16)

    mesh = _mesh(pp, 2)
    prog = mesh_jit("mesh_dmsm_test", shard_map(
        lambda b, s: _mesh_dmsm(C, b, s, pp)[None], mesh,
        in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS),
    ))
    out = prog(bases, shares)
    assert out.shape[0] == 4
    for row in out:
        assert C.decode(row) == expected
    # each device receives the other three shards' two points
    assert prog.gather_bytes == 6 * C.infinity().nbytes
