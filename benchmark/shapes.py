"""What a proof needs of the chip, computed from shapes alone: the
multi-scalar multiplications of one proof and the least bytes they must
move. First drafted from docs/PERF.md's "Work accounting"; kept here so
that a roofline share is computed the same way by every PR.

Device layouts (ops/constants.py, models/groth16/keys.py): a field element
is 16 uint32 limbs, a G1 point is projective (3, 16) uint32 = 192 bytes, a
G2 point (3, 2, 16) = 384 bytes, a scalar 64 bytes.
"""

from __future__ import annotations

POINT_BYTES = {"g1": 192, "g2": 384}
SCALAR_BYTES = 64


def proof_msms(cfg: dict, sizes: dict) -> list[tuple[str, int]]:
    """[(group, points)] of every MSM of one proof with r = s = 0.
    `sizes` holds the circuit's `wires`, `instance` (instance wires, the
    constant 1 among them) and `domain_size`.

    Single node (models/groth16/prove.py prove_single): A over a_query
    (G1, all wires), B over b_g2_query (G2, all wires), C over l_query
    (G1, the witness wires) and over h_query (G1, the domain).
    MPC (distributed_prove_party): every one of the n parties runs the
    same four over its packed shares, 1/l of the length each (the constant
    wire is added in the clear)."""
    wires, ni, m = sizes["wires"], sizes["instance"], sizes["domain_size"]
    parties = cfg.get("parties")
    if not parties:
        return [("g1", wires), ("g2", wires), ("g1", wires - ni), ("g1", m)]
    n, l = parties["n"], parties["l"]
    per_party = [
        ("g1", -(-(wires - 1) // l)),
        ("g2", -(-(wires - 1) // l)),
        ("g1", -(-(wires - ni) // l)),
        ("g1", m // l),
    ]
    return per_party * n


def msm_min_bytes(msms: list[tuple[str, int]]) -> int:
    """Every point and every scalar read once, the result aside: the least
    an MSM can move whatever its algorithm. The tree MSM moves bucket
    state besides, so its share of this bound is a floor, not a target."""
    return sum(n * (POINT_BYTES[g] + SCALAR_BYTES) for g, n in msms)
