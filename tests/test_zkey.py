"""snarkjs `.zkey` round-trip: ProvingKey -> write_zkey -> read_zkey must
reproduce the key (and the A/B constraint matrices) exactly, and the
re-imported key must still prove. Binary spec: ark-circom/src/zkey.rs:53-385
(no .zkey fixture ships in the reference checkout — they are gitignored —
so the writer doubles as the fixture generator)."""

import os

import numpy as np
import pytest

from distributed_groth16_tpu.frontend.readers import read_r1cs
from distributed_groth16_tpu.frontend.zkey import read_zkey, write_zkey
from distributed_groth16_tpu.frontend.witness_calculator import (
    WitnessCalculator,
)
from distributed_groth16_tpu.models.groth16 import (
    CompiledR1CS,
    setup,
    verify,
)
from distributed_groth16_tpu.models.groth16.keys import ProvingKey
from distributed_groth16_tpu.models.groth16.prove import prove_single
from distributed_groth16_tpu.ops.field import fr

TV = "/root/reference/ark-circom/test-vectors"

pytestmark = pytest.mark.skipif(
    not os.path.exists(f"{TV}/mycircuit.r1cs"), reason="no fixture"
)


def _points_equal(curve, a, b):
    return bool(np.all(np.asarray(curve.eq(a, b))))


def test_zkey_roundtrip_exact():
    from distributed_groth16_tpu.ops.curve import g1, g2

    r1cs, _ = read_r1cs(f"{TV}/mycircuit.r1cs")
    pk = setup(r1cs)
    blob = write_zkey(pk, r1cs)
    pk2, mats = read_zkey(blob)

    # header parity
    assert pk2.domain_size == pk.domain_size
    assert pk2.num_instance == pk.num_instance
    assert pk2.num_wires == pk.num_wires

    # vk parity (host ints, exact)
    assert pk2.vk.alpha_g1 == pk.vk.alpha_g1
    assert pk2.vk.beta_g2 == pk.vk.beta_g2
    assert pk2.vk.gamma_g2 == pk.vk.gamma_g2
    assert pk2.vk.delta_g2 == pk.vk.delta_g2
    assert pk2.vk.gamma_abc_g1 == pk.vk.gamma_abc_g1

    # query arrays: projective equality (z normalizes through the file)
    for name, curve in (
        ("a_query", g1()),
        ("b_g1_query", g1()),
        ("h_query", g1()),
        ("l_query", g1()),
        ("b_g2_query", g2()),
    ):
        assert _points_equal(curve, getattr(pk, name), getattr(pk2, name)), name
    assert _points_equal(g1(), pk.beta_g1, pk2.beta_g1)
    assert _points_equal(g1(), pk.delta_g1, pk2.delta_g1)

    # constraint matrices: A/B nonzeros survive exactly; C is not stored
    assert mats.num_instance == r1cs.num_instance
    assert mats.num_witness == r1cs.num_witness
    assert len(mats.a) == r1cs.num_constraints
    for j in range(r1cs.num_constraints):
        assert sorted(mats.a[j]) == sorted(r1cs.a[j])
        assert sorted(mats.b[j]) == sorted(r1cs.b[j])


def test_zkey_reimported_key_proves():
    r1cs, _ = read_r1cs(f"{TV}/mycircuit.r1cs")
    pk = setup(r1cs)
    pk2 = ProvingKey.from_zkey(write_zkey(pk, r1cs))

    wc = WitnessCalculator.from_file(f"{TV}/mycircuit.wasm")
    z = wc.calculate_witness({"a": 3, "b": 11})
    proof = prove_single(pk2, CompiledR1CS(r1cs), fr().encode(z))
    assert verify(pk2.vk, proof, z[1 : r1cs.num_instance])
