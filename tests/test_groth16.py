"""End-to-end Groth16 tests: device QAP, distributed h, full MPC proof vs
the host oracle and the pairing check — the reference's test ladder
(qap.rs tests, ext_wit.rs:103-191, sha256.rs:228-254) on a native circuit."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.models.groth16 import (
    CompiledR1CS,
    distributed_prove_party,
    pack_from_witness,
    pack_proving_key,
    reassemble_proof,
    setup,
    verify,
)
from distributed_groth16_tpu.models.groth16.ext_wit import h as ext_h
from distributed_groth16_tpu.models.groth16.keys import ProvingKey
from distributed_groth16_tpu.models.groth16.reference import (
    prove_host,
    qap_vectors_host,
    witness_map_host,
)
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.parallel.net import simulate_network_round
from distributed_groth16_tpu.parallel.packing import unpack_shares
from distributed_groth16_tpu.parallel.pss import PackedSharingParams

L = 2


@pytest.fixture(scope="module")
def world():
    cs = mult_chain_circuit(7, 13)  # nc=13, ni=2 -> m=16
    r1cs, z = cs.finish()
    pp = PackedSharingParams(L)
    pk = setup(r1cs)
    comp = CompiledR1CS(r1cs)
    z_mont = fr().encode(z)
    qap = comp.qap(z_mont)
    return dict(r1cs=r1cs, z=z, pp=pp, pk=pk, qap=qap, z_mont=z_mont)


def test_device_qap_matches_host(world):
    F = fr()
    a_h, b_h, c_h = qap_vectors_host(
        world["r1cs"], world["z"], world["pk"].domain_size
    )
    assert [int(v) for v in F.decode(world["qap"].a)] == a_h
    assert [int(v) for v in F.decode(world["qap"].b)] == b_h
    assert [int(v) for v in F.decode(world["qap"].c)] == c_h


def test_ext_wit_h_matches_circom_reduction(world):
    pp = world["pp"]
    qap_shares = world["qap"].pss(pp)

    async def party(net, share):
        return await ext_h(share, pp, net)

    outs = simulate_network_round(pp.n, party, qap_shares)
    got = [
        int(v)
        for v in fr().decode(unpack_shares(pp, jnp.stack(outs, 0)))
    ]
    assert got == witness_map_host(
        world["r1cs"], world["z"], world["pk"].domain_size
    )


def test_mpc_proof_verifies_and_matches_host(world):
    pp, pk, r1cs, z = world["pp"], world["pk"], world["r1cs"], world["z"]
    qap_shares = world["qap"].pss(pp)
    crs_shares = pack_proving_key(pk, pp)
    ni = r1cs.num_instance
    a_shares = pack_from_witness(pp, world["z_mont"][1:])
    ax_shares = pack_from_witness(pp, world["z_mont"][ni:])

    async def party(net, data):
        crs, qs, a_s, ax_s = data
        return await distributed_prove_party(pp, crs, qs, a_s, ax_s, net)

    data = [
        (crs_shares[i], qap_shares[i], a_shares[i], ax_shares[i])
        for i in range(pp.n)
    ]
    result = simulate_network_round(pp.n, party, data)
    proof = reassemble_proof(result[0], pk)

    publics = z[1:ni]
    assert verify(pk.vk, proof, publics), "MPC proof failed the pairing check"
    assert not verify(pk.vk, proof, [publics[0] + 1])

    oracle = prove_host(pk, r1cs, z)
    assert proof.a == oracle.a
    assert proof.b == oracle.b
    assert proof.c == oracle.c

    # every party broadcasts identical clear proof cores (d_msm semantics)
    p1 = reassemble_proof(result[1], pk)
    assert p1.a == proof.a and p1.c == proof.c


def test_proving_key_save_load(world, tmp_path):
    pk = world["pk"]
    path = str(tmp_path / "pk.npz")
    pk.save(path)
    pk2 = ProvingKey.load(path)
    assert pk2.domain_size == pk.domain_size
    assert pk2.vk.alpha_g1 == pk.vk.alpha_g1
    assert pk2.vk.gamma_abc_g1 == pk.vk.gamma_abc_g1
    assert jnp.array_equal(pk2.a_query, pk.a_query)
    assert jnp.array_equal(pk2.b_g2_query, pk.b_g2_query)


def test_zk_proof_r_s_nonzero_verifies(world):
    """Randomized (zero-knowledge) MPC proof: r, s != 0 exercises the
    N/K/A/M public terms and the H-query d_msm round (prove.rs:10-137 runs
    it unconditionally; here it only runs when r != 0)."""
    from distributed_groth16_tpu.models.groth16.prove import (
        public_prove_consts,
    )

    pp, pk, r1cs, z = world["pp"], world["pk"], world["r1cs"], world["z"]
    qap_shares = world["qap"].pss(pp)
    crs_shares = pack_proving_key(pk, pp)
    ni = r1cs.num_instance
    a_shares = pack_from_witness(pp, world["z_mont"][1:])
    ax_shares = pack_from_witness(pp, world["z_mont"][ni:])
    pub = public_prove_consts(pk)
    r, s = 123456789, 987654321

    async def party(net, data):
        crs, qs, a_s, ax_s = data
        return await distributed_prove_party(
            pp, crs, qs, a_s, ax_s, net, pub=pub, r=r, s=s
        )

    data = [
        (crs_shares[i], qap_shares[i], a_shares[i], ax_shares[i])
        for i in range(pp.n)
    ]
    result = simulate_network_round(pp.n, party, data)
    proof = reassemble_proof(result[0], pk)

    publics = z[1:ni]
    assert verify(pk.vk, proof, publics), "randomized proof failed pairing"
    det = prove_host(pk, r1cs, z)
    assert proof.a != det.a, "r != 0 must randomize A"


def test_scalar_route_pack_matches_point_route(world):
    # pack_proving_key's scalar route (field-NTT pack + fixed-base) must
    # produce the SAME GROUP ELEMENTS as the in-exponent point route —
    # projective representatives may differ, so compare affine decodes.
    from dataclasses import replace

    from distributed_groth16_tpu.ops.curve import g1, g2

    pk = world["pk"]
    pp = world["pp"]
    assert pk.query_scalars is not None  # in-process setup keeps scalars
    fast = pack_proving_key(pk, pp)
    slow = pack_proving_key(replace(pk, query_scalars=None), pp)
    C1, C2 = g1(), g2()
    for f, s in zip(fast, slow):
        for name, curve in (
            ("s", C1), ("u", C1), ("w", C1), ("h", C1), ("v", C2)
        ):
            a = curve.decode(getattr(f, name))
            b = curve.decode(getattr(s, name))
            assert list(a) == list(b), f"query {name} diverged"


def test_strip_clears_trapdoor_scalars(world):
    # strip() (and pack_proving_key(strip=True)) must destroy the
    # trapdoor-derived query scalars — keeping them alive on a pk object
    # that crosses a trust boundary breaks the CRS soundness assumption
    # (keys.py hazard note). Work on a shallow copy so the shared module
    # fixture keeps its scalars for other tests.
    from dataclasses import replace

    pk = replace(world["pk"])
    pp = world["pp"]
    assert pk.query_scalars is not None
    shares = pack_proving_key(pk, pp, strip=True)
    assert pk.query_scalars is None, "strip=True must clear the scalars"
    assert world["pk"].query_scalars is not None  # the fixture is untouched
    # a stripped key still packs — now via the in-exponent point route
    again = pack_proving_key(pk, pp)
    assert len(again) == len(shares) == pp.n
    assert pk.strip() is pk  # idempotent, chains


def _bits_circuit(nbits: int = 87, filled: bool = False):
    """A circuit shaped like the served SHA-256 one: every witness wire a
    bit, and two public wires that pack the bits into wide values (the
    digest halves there). 3 + nbits wires; the A query's 90 and the L
    query's 87 both pad to 128, with room for two wide scalars.

    `filled`: the same shape, row for row, with every witness wire free
    (w * 1 = w in place of w * w = w) and a value that fills the field."""
    from distributed_groth16_tpu.frontend.r1cs import ConstraintSystem

    rng = np.random.default_rng(24)
    if filled:
        bits = [
            int.from_bytes(rng.bytes(40), "little") % fr().p
            for _ in range(nbits)
        ]
    else:
        bits = [int(b) for b in rng.integers(0, 2, size=nbits)]
    half = nbits // 2
    weigh = lambda part: sum(b << (90 + i) for i, b in enumerate(part))
    cs = ConstraintSystem()
    lo = cs.new_instance(weigh(bits[:half]))
    hi = cs.new_instance(weigh(bits[half:]))
    wires = [cs.new_witness(b) for b in bits]
    for w in wires:
        cs.enforce([(1, w)], [(1, cs.ONE if filled else w)], [(1, w)])
    for out, part in ((lo, wires[:half]), (hi, wires[half:])):
        cs.enforce(
            [(1 << (90 + i), w) for i, w in enumerate(part)],
            [(1, cs.ONE)],
            [(1, out)],
        )
    return cs.finish()


def test_prove_single_follows_the_witness_occupancy(monkeypatch):
    """`prove_single` with the host's view of a witness of bits and two
    wide public wires takes the limb-0 route for its G1 MSMs over z (A and
    L, one tree program between them) and the full route for h; on values
    that fill the field, the full route for all. Either way the proof is
    the reference prover's. The G1 MSMs are steered onto the tree path
    (the TPU's) here; G2 stays on the CPU's generic one, whose tree
    compiles for minutes (tests/test_limb_kernels.py has its limb-0 form)."""
    from distributed_groth16_tpu.models.groth16.prove import prove_single
    from distributed_groth16_tpu.ops import limb_kernels as lk
    from distributed_groth16_tpu.ops import msm as msm_mod
    from distributed_groth16_tpu.telemetry import metrics, tracing

    monkeypatch.setattr(
        msm_mod, "_tree_group",
        lambda curve, n: lk.lg1() if len(curve.elem_shape) == 1 else None,
    )
    routes = metrics.registry().family("kernel_route_total")

    def moved(fn):
        before = {k: c.value for k, c in routes.items()}
        out = fn()
        return out, {
            k[1]: c.value - before.get(k, 0)
            for k, c in routes.items()
            if k[0] == "msm" and c.value != before.get(k, 0)
        }

    r1cs, z = _bits_circuit()
    assert sorted(i for i, v in enumerate(z) if v >> 16) == [1, 2]
    pk = setup(r1cs)
    comp = CompiledR1CS(r1cs)
    z_mont, view = msm_mod.encode_observed(fr(), z)
    limb0_programs = lk._MSM_LIMB0_JITS["g1"]._cache_size()
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        proof, took = moved(lambda: prove_single(pk, comp, z_mont, wide=view))
    assert proof == prove_host(pk, r1cs, z)
    assert verify(pk.vk, proof, z[1 : r1cs.num_instance])
    assert took == {"tree_limb0": 2, "tree": 1, "ladder": 1}
    assert lk._MSM_LIMB0_JITS["g1"]._cache_size() == limb0_programs + 1
    told = {
        e["name"]: e["args"].get("wide_scalars")
        for e in buf.events() if e["name"].startswith("prove.")
    }
    assert (told["prove.A"], told["prove.B"], told["prove.C"]) == (2, 2, 0)
    assert told["prove.h"] is None

    # no view: the parent's call
    proof, took = moved(lambda: prove_single(pk, comp, z_mont))
    assert proof == prove_host(pk, r1cs, z)
    assert took == {"tree": 3, "ladder": 1}

    # field-filling values on a circuit of the same shape that they
    # satisfy (`prove_single` makes no proof of a witness that does not,
    # ISSUE 33): every wire is wide, nothing fits, all windows run
    r1cs, zf = _bits_circuit(filled=True)
    pk, comp = setup(r1cs), CompiledR1CS(r1cs)
    zf_mont, viewf = msm_mod.encode_observed(fr(), zf)
    assert viewf.count == len(z) - 1
    proof, took = moved(lambda: prove_single(pk, comp, zf_mont, wide=viewf))
    assert proof == prove_host(pk, r1cs, zf)
    assert took == {"tree": 3, "ladder": 1}
