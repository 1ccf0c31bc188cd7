"""`prove_single` on the tree path (the TPU's), G2 included, against the
plain reference prover, for both kinds of witness: the one body of a test
that two files parametrise, each over the kind whose G2 tree program it
has already compiled (a G2 tree program costs 100 s on XLA:CPU, and
`--dist loadfile` keeps a file on one worker):

  bits   tests/test_msm.py: 37 wires, every witness wire a bit, one wide
         public wire that packs them. Its B query takes the G2 limb-0
         programs of `test_msm_g2_limb0_windows_match_reference` (37
         points, room for one wide scalar).
  field  tests/test_limb_kernels.py: the chain x -> x^2 + x from a seeded
         x0, 35 constraints, 37 wires, 35 of them wider than 16 bits: far
         over `wide_capacity` (1). Its B query takes the full-width G2
         program of `test_msm_tree_g2_matches_reference` (37 points).

Both have 2 instance wires and domain 64, so the G1 shapes are 37 (A), 35
(L) and 64 (H) either way. Not a test file: pytest collects `test_*.py`.
"""

from distributed_groth16_tpu.frontend.r1cs import (
    ConstraintSystem,
    mult_chain_circuit,
)
from distributed_groth16_tpu.models.groth16 import CompiledR1CS, setup, verify
from distributed_groth16_tpu.models.groth16.prove import prove_single
from distributed_groth16_tpu.models.groth16.reference import prove_host
from distributed_groth16_tpu.ops import limb_kernels as lk
from distributed_groth16_tpu.ops import msm as msm_mod
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.telemetry import metrics, tracing

SEED = 30
# kind -> (routes a proof moves, limb-0 forms declined, the spans' `route`)
EXPECT = {
    "bits": ({"tree_limb0": 3, "tree": 1}, 0, "tree_limb0"),
    "field": ({"tree": 4}, 3, "tree"),
}


def bits_circuit(nbits: int = 35):
    """1 + 1 + nbits wires: the constant, one public wire that packs the
    bits from bit 20 up (wide), and nbits witness wires that are bits."""
    import random

    rng = random.Random(SEED)
    bits = [rng.randrange(2) for _ in range(nbits)]
    bits[-1] = 1  # whatever the seed, the packed value is wide
    cs = ConstraintSystem()
    out = cs.new_instance(sum(b << (20 + i) for i, b in enumerate(bits)))
    wires = [cs.new_witness(b) for b in bits]
    for w in wires:
        cs.enforce([(1, w)], [(1, w)], [(1, w)])
    cs.enforce(
        [(1 << (20 + i), w) for i, w in enumerate(wires)],
        [(1, cs.ONE)],
        [(1, out)],
    )
    return cs.finish()


def field_circuit(length: int = 35):
    """The benchmark's chain (`benchmark/circuits/mult_chain.py`) from a
    seeded x0 under 2^16: the values double in width each step."""
    return mult_chain_circuit(3 + 1000 * SEED + 7, length).finish()


def _moved(before: dict, family: str) -> dict:
    fam = metrics.registry().family(family)
    return {
        k: c.value - before.get((family, k), 0)
        for k, c in fam.items()
        if c.value != before.get((family, k), 0)
    }


def _snapshot() -> dict:
    return {
        (family, k): c.value
        for family in ("kernel_route_total", "msm_limb0_declined_total")
        for k, c in metrics.registry().family(family).items()
    }


def check_prove_single_routes(kind: str, monkeypatch) -> None:
    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    routes, declined, route = EXPECT[kind]
    r1cs, z = bits_circuit() if kind == "bits" else field_circuit()
    assert (r1cs.num_wires, r1cs.num_instance) == (37, 2)
    assert r1cs.is_satisfied(z)
    wide = [i for i, v in enumerate(z) if v >> 16]
    cap = lk.wide_capacity(lk.lg1(), r1cs.num_wires)
    if kind == "bits":
        assert wide == [1] and len(wide) <= cap
    else:
        assert len(wide) == 35 > cap  # all but the constant and x0
        assert sum(v >> 250 != 0 for v in z) > 20  # and most fill the field

    pk = setup(r1cs)
    assert pk.domain_size == 64
    comp = CompiledR1CS(r1cs)
    z_mont, view = msm_mod.encode_observed(fr(), z)
    assert view.count == len(wide)

    before = _snapshot()
    buf = tracing.TraceBuffer()
    with tracing.collect(buf):
        proof = prove_single(pk, comp, z_mont, wide=view)
    assert proof == prove_host(pk, r1cs, z)
    assert verify(pk.vk, proof, z[1 : r1cs.num_instance])
    assert {
        k[1]: v for k, v in _moved(before, "kernel_route_total").items()
        if k[0] == "msm"
    } == routes
    assert sum(_moved(before, "msm_limb0_declined_total").values()) == declined
    if declined:
        assert set(_moved(before, "msm_limb0_declined_total")) == {
            ("over_capacity",)
        }
    told = {
        e["name"]: e["args"] for e in buf.events()
        if e["name"] in ("prove.A", "prove.B", "prove.C", "prove.h")
    }
    assert [told[s].get("route") for s in ("prove.A", "prove.B", "prove.C")] \
        == [route] * 3
    assert [told[s]["wide_scalars"] for s in ("prove.A", "prove.B", "prove.C")] \
        == [len(wide), len(wide), len([i for i in wide if i >= 2])]
    assert "route" not in told["prove.h"]
