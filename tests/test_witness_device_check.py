"""The served witness check (ISSUE 33): what is left of it on the host is
the witness's form (`ProofExecutor.resolve_witness`: the length and
z[0] == 1), and the rows are decided on the device, from the QAP
evaluations the proof itself uses (`CompiledR1CS.satisfied`, read by
`require_satisfied`). `R1CS.is_satisfied` stays the oracle: the two halves
together give its verdict on every input below, bit for bit.

CPU, small circuits (and the SHA-256 frontend's one block, once): these
check the verdict, never a speed (the cells' own sizes come last, marked
slow: the cases a chip run held the flag to). The served paths that read the verdict
(`ProofExecutor.run` for both kinds, the batch prover) are held in
`tests/test_circuit_cache.py`, beside the compiled round they share.
"""

import pytest

from distributed_groth16_tpu.frontend.r1cs import (
    ConstraintSystem,
    mult_chain_circuit,
)
from distributed_groth16_tpu.frontend.sha256 import sha256_circuit
from distributed_groth16_tpu.models.groth16 import CompiledR1CS
from distributed_groth16_tpu.models.groth16.qap import (
    UNSATISFIED,
    require_satisfied,
)
from distributed_groth16_tpu.ops.constants import R
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.ops.msm import encode_observed
from distributed_groth16_tpu.service import ProofJob
from distributed_groth16_tpu.service.worker import ProofExecutor
from distributed_groth16_tpu.telemetry import metrics as tm

# wires of `_rows_circuit`, as `ConstraintSystem` numbers them
ONE, OUT, X, Y, P = range(5)


def _rows_circuit(origin_row_empty: bool):
    """Four rows that the sorted-COO form treats differently: one empty in
    A and in C, one with an empty C that pins x (its product has to be the
    zero that an empty row reads), and a public wire that enters C alone.
    x = y = r - 1. With `origin_row_empty` the empty row is row 0, so the
    row at the origin of A's and C's entries is row 1; else row 0 is."""
    x = y = R - 1
    p = x * y % R
    cs = ConstraintSystem()
    out = cs.new_instance((p + x) * (R - 1) * y % R)
    wx, wy, wp = cs.new_witness(x), cs.new_witness(y), cs.new_witness(p)
    assert (out, wx, wy, wp) == (OUT, X, Y, P)
    empty = ([], [(1, wy)], [])
    rows = [
        ([(1, wx)], [(1, wy)], [(1, wp)]),
        ([(1, wx), (-x % R, cs.ONE)], [(1, cs.ONE)], []),
        ([(1, wp), (1, wx)], [(R - 1, wy)], [(1, out)]),
    ]
    rows.insert(0 if origin_row_empty else 1, empty)
    for row in rows:
        cs.enforce(*row)
    return cs.finish()


CIRCUITS = {
    # wire 1 is the chain's public output: it enters C's last row alone
    "chain": lambda: mult_chain_circuit(7, 13).finish(),
    # x0 = r - 1: the chain reads r - 1, then 0 all the way
    "chain_r_minus_1": lambda: mult_chain_circuit(R - 1, 13).finish(),
    "rows": lambda: _rows_circuit(origin_row_empty=False),
    "rows_origin_empty": lambda: _rows_circuit(origin_row_empty=True),
}


def _bump(wire, by=1):
    def tamper(z):
        z = list(z)
        z[wire] = (z[wire] + by) % R
        return z
    return tamper


def _set(wire, value):
    def tamper(z):
        z = list(z)
        z[wire] = value
        return z
    return tamper


# (circuit, what is done to its satisfying witness, the verdict)
CASES = {
    "chain-as_made": ("chain", list, True),
    "chain-wire_in_a_and_b": ("chain", _bump(2), False),
    "chain-mid_wire": ("chain", _bump(8), False),
    "chain-wire_in_c_alone": ("chain", _bump(1), False),
    "chain-value_r_minus_1": ("chain", _set(5, R - 1), False),
    "chain-r_plus_k_is_k": ("chain", lambda z: [z[0]] + [v + R for v in z[1:]],
                            True),
    "chain-r_plus_k_is_not": ("chain", _set(3, R + 5), False),
    "chain-one_short": ("chain", lambda z: z[:-1], False),
    "chain-one_long": ("chain", lambda z: z + [0], False),
    "chain-empty": ("chain", lambda z: [], False),
    "chain-z0_is_2": ("chain", _set(0, 2), False),
    "chain-z0_is_0": ("chain", _set(0, 0), False),
    # 1 mod r, and refused by `is_satisfied` all the same: z[0] != 1
    "chain-z0_is_r_plus_1": ("chain", _set(0, R + 1), False),
    "chain_r_minus_1-as_made": ("chain_r_minus_1", list, True),
    "chain_r_minus_1-zero_for_it": ("chain_r_minus_1", _set(2, 0), True),
    "chain_r_minus_1-one_for_it": ("chain_r_minus_1", _set(2, 1), False),
    "rows-as_made": ("rows", list, True),
    "rows-wire_in_b": ("rows", _bump(Y), False),
    "rows-wire_pinned_by_an_empty_c_row": ("rows", _bump(X), False),
    "rows-wire_in_c_alone": ("rows", _bump(OUT), False),
    "rows-product_wire": ("rows", _bump(P, R - 1), False),
    "rows_origin_empty-as_made": ("rows_origin_empty", list, True),
    "rows_origin_empty-wire_in_b": ("rows_origin_empty", _bump(Y), False),
    "rows_origin_empty-wire_pinned": ("rows_origin_empty", _bump(X), False),
    "rows_origin_empty-wire_in_c_alone":
        ("rows_origin_empty", _bump(OUT), False),
}


@pytest.fixture(scope="module")
def compiled():
    made = {}

    def get(name):
        if name not in made:
            r1cs, z = CIRCUITS[name]()
            made[name] = (r1cs, CompiledR1CS(r1cs), z)
        return made[name]

    return get


def _served_verdict(r1cs, comp, z) -> bool:
    """What a served job decides, in the order it decides it: the form on
    the host, the upload, the rows on the device."""
    ex = ProofExecutor(store=None)
    ex._parse_witness = lambda job: list(z)
    try:
        z = ex.resolve_witness(ProofJob("prove", "c", fields={}), r1cs)
        z_mont, _ = encode_observed(fr(), z)
        require_satisfied(comp.satisfied(z_mont, comp.qap(z_mont)))
    except ValueError as e:
        assert str(e) == UNSATISFIED == "witness does not satisfy the circuit"
        return False
    return True


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_served_verdict_is_is_satisfieds(compiled, case):
    name, tamper, want = CASES[case]
    r1cs, comp, z = compiled(name)
    z = tamper(z)
    assert r1cs.is_satisfied(z) is want
    assert _served_verdict(r1cs, comp, z) is want


def test_the_circuits_have_the_rows_the_cases_name(compiled):
    """The cases above mean what their names say."""
    r1cs, comp, _ = compiled("chain")
    in_ab = {w for rows in (r1cs.a, r1cs.b) for row in rows for _, w in row}
    in_c = {w for row in r1cs.c for _, w in row}
    assert 1 in in_c - in_ab and {2, 8} <= in_ab
    for name, empty_row, origin in (("rows", 1, 0), ("rows_origin_empty", 0, 1)):
        r1cs, comp, _ = compiled(name)
        assert r1cs.a[empty_row] == [] and r1cs.c[empty_row] == []
        assert sum(row == [] for row in r1cs.c) == 2
        for m in (comp.A, comp.C):
            assert [bool(v) for v in m.nonempty].index(True) == origin
            assert bool(m.at_origin[origin])
        assert OUT in {w for row in r1cs.c for _, w in row}
        assert OUT not in {
            w for rows in (r1cs.a, r1cs.b) for row in rows for _, w in row
        }


def test_the_verdict_is_one_device_boolean_and_reading_it_counts(compiled):
    r1cs, comp, z = compiled("chain")
    fam = tm.registry().family("witness_device_checks_total")
    assert {k[0] for k, _ in fam.items()} == {"ok", "rejected"}  # at import
    before = {k[0]: c.value for k, c in fam.items()}
    F = fr()
    flags = []
    for zz in (z, _bump(4)(z)):
        z_mont = F.encode(zz)
        flags.append(comp.satisfied(z_mont, comp.qap(z_mont)))
    # nothing was read yet, so nothing counted
    assert {k[0]: c.value for k, c in fam.items()} == before
    assert all(f.shape == () and f.dtype == bool for f in flags)
    require_satisfied(flags[0])
    with pytest.raises(ValueError, match="^witness does not satisfy the circuit$"):
        require_satisfied(flags[1])
    moved = {k[0]: c.value - before[k[0]] for k, c in fam.items()}
    assert moved == {"ok": 1, "rejected": 1}


@pytest.fixture(scope="module")
def sha256():
    cs, _ = sha256_circuit(b"attack at dawn")
    r1cs, z = cs.finish()
    return r1cs, CompiledR1CS(r1cs), z


SHA256_CASES = {
    "as_made": (list, True),
    # the digest's high half: a public wire
    "forged_digest": (lambda z: _set(1, (z[1] + 1) % (1 << 128))(z), False),
    "flipped_bit": (lambda z: _set(500, 1 - z[500])(z), False),
    "last_wire": (lambda z: _set(len(z) - 1, 1 - z[-1])(z), False),
}


@pytest.mark.parametrize("case", sorted(SHA256_CASES))
def test_the_sha256_frontends_verdict_is_is_satisfieds(sha256, case):
    tamper, want = SHA256_CASES[case]
    r1cs, comp, z = sha256
    z = tamper(z)
    assert r1cs.is_satisfied(z) is want
    assert _served_verdict(r1cs, comp, z) is want


# The cells' own circuits, at the sizes they are served at (sha256's one
# block: 27,810 rows; the chain: 65,000 rows of values that fill the
# field). PR 33 ran these fourteen on the chip. `tests/conftest.py` holds
# JAX to the CPU; on a machine with a chip, leave it out:
#   python -m pytest --noconftest -m slow tests/test_witness_device_check.py
CELL_CIRCUITS = {
    "sha256": lambda: sha256_circuit(b"dg16 bench pool 0/0")[0].finish(),
    "chain": lambda: mult_chain_circuit(22003, 65000).finish(),
}
CELL_CASES = {
    "as_made": (list, True),
    "r_plus_k": (lambda z: [z[0]] + [v + R for v in z[1:]], True),
    "public_wire_1": (_bump(1), False),
    "wire_500": (_bump(500), False),
    "mid_wire": (lambda z: _bump(len(z) // 2)(z), False),
    "last_wire": (lambda z: _bump(len(z) - 1)(z), False),
    "last_wire_r_minus_1": (lambda z: _set(len(z) - 1, R - 1)(z), False),
}


@pytest.fixture(scope="module")
def cell_compiled():
    made = {}

    def get(name):
        if name not in made:
            r1cs, z = CELL_CIRCUITS[name]()
            made[name] = (r1cs, CompiledR1CS(r1cs), z)
        return made[name]

    return get


@pytest.mark.slow
@pytest.mark.parametrize("case", sorted(CELL_CASES))
@pytest.mark.parametrize("name", sorted(CELL_CIRCUITS))
def test_the_verdict_at_the_cells_sizes_is_is_satisfieds(cell_compiled, name, case):
    tamper, want = CELL_CASES[case]
    r1cs, comp, z = cell_compiled(name)
    z = tamper(z)
    assert r1cs.is_satisfied(z) is want
    assert _served_verdict(r1cs, comp, z) is want
