"""Groth16 verification and the proof wire format, for the oracle.

THE BENCHMARK'S COPY of `models/groth16/verify.py` (the plain ladder path
only: the sha256 circuit has two public inputs) and of the decoding half of
`frontend/ark_serde.py`, as of PR 21 (commit c67d115). It imports only the
copies beside it, never the program.

  e(A, B) == e(alpha, beta) * e(L_pub, gamma) * e(C, delta)

checked as one multi-pairing. Proofs are ark-serialize 0.4 compressed:
a (G1, 32 bytes) || b (G2, 64 bytes) || c (G1, 32 bytes); x little-endian,
0x40 in the last byte = infinity, 0x80 = the larger root of y.
"""

from __future__ import annotations

from typing import NamedTuple

from . import refmath as rm
from .constants import G1_B, G1_GENERATOR, G2_B, Q, R
from .pairing import pairing_check

_HALF = (Q - 1) // 2


class Proof(NamedTuple):
    a: tuple | None
    b: tuple | None
    c: tuple | None


class VerifyingKey(NamedTuple):
    """Host affine points: G1 = (x, y) ints, G2 = ((c0, c1), (c0, c1))."""

    alpha_g1: tuple
    beta_g2: tuple
    gamma_g2: tuple
    delta_g2: tuple
    gamma_abc_g1: list  # one per instance wire, the constant 1 first

    def to_json(self) -> dict:
        return {k: _listify(v) for k, v in self._asdict().items()}

    @classmethod
    def from_json(cls, doc: dict) -> "VerifyingKey":
        return cls(
            alpha_g1=_g1(doc["alpha_g1"]),
            beta_g2=_g2(doc["beta_g2"]),
            gamma_g2=_g2(doc["gamma_g2"]),
            delta_g2=_g2(doc["delta_g2"]),
            gamma_abc_g1=[_g1(p) for p in doc["gamma_abc_g1"]],
        )


def _listify(v):
    if isinstance(v, (tuple, list)):
        return [_listify(x) for x in v]
    return None if v is None else str(int(v))


def _g1(p):
    return None if p is None else (int(p[0]), int(p[1]))


def _g2(p):
    if p is None:
        return None
    return ((int(p[0][0]), int(p[0][1])), (int(p[1][0]), int(p[1][1])))


# -- decoding ---------------------------------------------------------------


def _is_neg(y: int) -> bool:
    return y > _HALF


def _fq2_is_neg(y) -> bool:
    c0, c1 = y
    return _is_neg(c1) if c1 != 0 else _is_neg(c0)


def _sqrt_fq(a: int) -> int | None:
    r = pow(a, (Q + 1) // 4, Q)
    return r if r * r % Q == a else None


def _sqrt_fq2(a) -> tuple | None:
    a0, a1 = a[0] % Q, a[1] % Q
    if a1 == 0:
        r = _sqrt_fq(a0)
        if r is not None:
            return (r, 0)
        r = _sqrt_fq((-a0) % Q)
        return None if r is None else (0, r)
    n = _sqrt_fq((a0 * a0 + a1 * a1) % Q)
    if n is None:
        return None
    inv2 = pow(2, Q - 2, Q)
    for sign in (1, -1):
        x0 = _sqrt_fq((a0 + sign * n) % Q * inv2 % Q)
        if x0 is None or x0 == 0:
            continue
        x1 = a1 * pow(2 * x0 % Q, Q - 2, Q) % Q
        if rm.fq2_sq((x0, x1)) == (a0, a1):
            return (x0, x1)
    return None


def g1_from_bytes(b: bytes):
    if len(b) != 32:
        raise ValueError("G1 point must be 32 bytes")
    flags = b[31] & 0xC0
    x = int.from_bytes(bytes(b[:31]) + bytes([b[31] & 0x3F]), "little")
    if flags & 0x40:
        return None
    if x >= Q:
        raise ValueError("G1 x coordinate out of range")
    y = _sqrt_fq((pow(x, 3, Q) + G1_B) % Q)
    if y is None:
        raise ValueError("not a point on G1")
    if bool(flags & 0x80) != _is_neg(y):
        y = (Q - y) % Q
    return (x, y)


def g1_to_bytes(pt) -> bytes:
    if pt is None:
        out = bytearray(32)
        out[-1] = 0x40
        return bytes(out)
    x, y = pt
    out = bytearray(int(x).to_bytes(32, "little"))
    if _is_neg(y):
        out[-1] |= 0x80
    return bytes(out)


def g2_from_bytes(b: bytes):
    if len(b) != 64:
        raise ValueError("G2 point must be 64 bytes")
    flags = b[63] & 0xC0
    x0 = int.from_bytes(b[:32], "little")
    x1 = int.from_bytes(bytes(b[32:63]) + bytes([b[63] & 0x3F]), "little")
    if flags & 0x40:
        return None
    if x0 >= Q or x1 >= Q:
        raise ValueError("G2 x coordinate out of range")
    x = (x0, x1)
    y = _sqrt_fq2(rm.fq2_add(rm.fq2_mul(rm.fq2_sq(x), x), G2_B))
    if y is None:
        raise ValueError("not a point on G2")
    if bool(flags & 0x80) != _fq2_is_neg(y):
        y = ((Q - y[0]) % Q, (Q - y[1]) % Q)
    pt = (x, y)
    if rm.G2.scalar_mul(pt, R) is not None:
        raise ValueError("G2 point not in the prime-order subgroup")
    return pt


def proof_from_bytes(b: bytes) -> Proof:
    if len(b) != 128:
        raise ValueError(f"proof must be 128 bytes, got {len(b)}")
    return Proof(
        a=g1_from_bytes(b[:32]),
        b=g2_from_bytes(b[32:96]),
        c=g1_from_bytes(b[96:128]),
    )


def corrupt(proof_bytes: bytes) -> bytes:
    """The same proof with C replaced by C + G: it still decodes to points
    on the curves, and no verifier may accept it."""
    c = g1_from_bytes(proof_bytes[96:128])
    return proof_bytes[:96] + g1_to_bytes(rm.G1.add(c, G1_GENERATOR))


# -- the check --------------------------------------------------------------


def prepare_inputs(vk: VerifyingKey, public_inputs: list[int]):
    """L_pub = gamma_abc[0] + sum_i x_i * gamma_abc[i+1]."""
    if len(public_inputs) + 1 != len(vk.gamma_abc_g1):
        raise ValueError(
            f"{len(public_inputs)} public inputs for "
            f"{len(vk.gamma_abc_g1) - 1} instance wires"
        )
    acc = vk.gamma_abc_g1[0]
    for x, pt in zip(public_inputs, vk.gamma_abc_g1[1:]):
        acc = rm.G1.add(acc, rm.G1.scalar_mul(pt, int(x) % R))
    return acc


def verify(vk: VerifyingKey, proof_bytes: bytes, public_inputs) -> bool:
    """True iff `proof_bytes` decodes and satisfies the pairing equation
    for `public_inputs` under `vk`. Bytes that do not decode are False."""
    try:
        proof = proof_from_bytes(proof_bytes)
    except ValueError:
        return False
    l_pub = prepare_inputs(vk, [int(x) for x in public_inputs])
    return pairing_check(
        [
            (proof.b, proof.a),
            (vk.beta_g2, rm.G1.neg(vk.alpha_g1)),
            (vk.gamma_g2, rm.G1.neg(l_pub)),
            (vk.delta_g2, rm.G1.neg(proof.c)),
        ]
    )
