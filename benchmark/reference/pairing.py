"""THE BENCHMARK'S COPY of distributed_groth16_tpu/ops/pairing.py as of PR 21 (commit
c67d115), kept here so that no later change to the program can move the
yardstick. Do not import the program's module in its place, and do not
edit this file in a PR that claims a gain. Original docstring follows.

Host-side BN254 optimal ate pairing — the Groth16 verification oracle.

Verification is not the workload (the reference verifies through arkworks'
pairing, groth16/examples/sha256.rs:228-254); proofs are seconds of TPU
compute, the pairing check is milliseconds of host bigint math. This module
is therefore deliberately pure Python: simple, auditable, and the ground
truth our device-side prover is differentially tested against.

Tower: Fq2 = Fq[u]/(u^2+1) (ops/refmath.py), Fq12 = Fq2[w]/(w^6 - xi) with
xi = 9 + u (the D-type twist constant, ops/constants.py). G2 points live on
the twist E'(Fq2): y^2 = x^3 + b/xi; the untwist embedding into E(Fq12) is
(x, y) -> (x w^2, y w^3), which is where the sparse line-function shape
below comes from.
"""

from __future__ import annotations

from .constants import ATE_LOOP_COUNT, FQ2_NON_RESIDUE, Q, R
from .refmath import (
    FQ2_ONE,
    FQ2_ZERO,
    fq2_add,
    fq2_conj,
    fq2_inv,
    fq2_mul,
    fq2_neg,
    fq2_scalar,
    fq2_sq,
    fq2_sub,
    G2,
)

# ---------------------------------------------------------------------------
# Fq12 = Fq2[w]/(w^6 - xi): elements are 6-tuples of Fq2 coefficients
# (c0 + c1 w + ... + c5 w^5).
# ---------------------------------------------------------------------------

FQ12_ONE = (FQ2_ONE,) + (FQ2_ZERO,) * 5
FQ12_ZERO = (FQ2_ZERO,) * 6

_XI = FQ2_NON_RESIDUE


def fq12_mul(a, b):
    # schoolbook over w, then fold w^(6+k) = xi * w^k
    acc = [FQ2_ZERO] * 11
    for i in range(6):
        ai = a[i]
        if ai == FQ2_ZERO:
            continue
        for j in range(6):
            if b[j] == FQ2_ZERO:
                continue
            acc[i + j] = fq2_add(acc[i + j], fq2_mul(ai, b[j]))
    out = list(acc[:6])
    for k in range(5):
        out[k] = fq2_add(out[k], fq2_mul(acc[6 + k], _XI))
    return tuple(out)


def fq12_sq(a):
    return fq12_mul(a, a)


def fq12_conj(a):
    """Conjugation by w -> -w (the q^6 Frobenius): negate odd coefficients."""
    return (a[0], fq2_neg(a[1]), a[2], fq2_neg(a[3]), a[4], fq2_neg(a[5]))


def fq12_pow(a, e: int):
    acc, base = FQ12_ONE, a
    while e:
        if e & 1:
            acc = fq12_mul(acc, base)
        base = fq12_sq(base)
        e >>= 1
    return acc


# ---------------------------------------------------------------------------
# Line functions (affine, on the twist) — sparse Fq12 elements.
#
# Untwisted line through psi(T) evaluated at P = (xp, yp) in G1:
#     l = yp  -  (lambda * xp) w  +  (lambda * x_T - y_T) w^3
# with lambda the affine slope on the twist (an Fq2 element).
# ---------------------------------------------------------------------------


def _line(slope, x_t, y_t, xp: int, yp: int):
    c0 = (yp % Q, 0)
    c1 = fq2_neg(fq2_scalar(slope, xp))
    c3 = fq2_sub(fq2_mul(slope, x_t), y_t)
    return (c0, c1, FQ2_ZERO, c3, FQ2_ZERO, FQ2_ZERO)


def _dbl_step(t, p):
    """Returns (2T, line_{T,T}(P)). T = (x, y) affine on the twist."""
    x, y = t
    slope = fq2_mul(fq2_scalar(fq2_sq(x), 3), fq2_inv(fq2_scalar(y, 2)))
    x3 = fq2_sub(fq2_sq(slope), fq2_scalar(x, 2))
    y3 = fq2_sub(fq2_mul(slope, fq2_sub(x, x3)), y)
    return (x3, y3), _line(slope, x, y, p[0], p[1])


def _add_step(t, q, p):
    """Returns (T+Q, line_{T,Q}(P))."""
    x1, y1 = t
    x2, y2 = q
    slope = fq2_mul(fq2_sub(y2, y1), fq2_inv(fq2_sub(x2, x1)))
    x3 = fq2_sub(fq2_sub(fq2_sq(slope), x1), x2)
    y3 = fq2_sub(fq2_mul(slope, fq2_sub(x1, x3)), y1)
    return (x3, y3), _line(slope, x1, y1, p[0], p[1])


# Frobenius on the twist: pi(x, y) = (gamma12 * conj(x), gamma13 * conj(y)),
# gamma12 = xi^((q-1)/3), gamma13 = xi^((q-1)/2).
def _fq2_pow(a, e: int):
    acc, base = FQ2_ONE, a
    while e:
        if e & 1:
            acc = fq2_mul(acc, base)
        base = fq2_sq(base)
        e >>= 1
    return acc


_GAMMA12 = _fq2_pow(_XI, (Q - 1) // 3)
_GAMMA13 = _fq2_pow(_XI, (Q - 1) // 2)


def _frob_twist(t):
    x, y = t
    return (fq2_mul(_GAMMA12, fq2_conj(x)), fq2_mul(_GAMMA13, fq2_conj(y)))


def miller_loop(q2, p1):
    """Miller loop f_{6x+2, Q}(P) for Q on the twist (affine Fq2 pair) and
    P in G1 (affine int pair). Either None (infinity) gives f = 1."""
    if q2 is None or p1 is None:
        return FQ12_ONE
    f = FQ12_ONE
    t = q2
    for bit in bin(ATE_LOOP_COUNT)[3:]:
        t, l = _dbl_step(t, p1)
        f = fq12_mul(fq12_sq(f), l)
        if bit == "1":
            t, l = _add_step(t, q2, p1)
            f = fq12_mul(f, l)
    # the two Frobenius correction steps of the optimal ate pairing
    q1 = _frob_twist(q2)
    nq2 = _frob_twist(q1)
    nq2 = (nq2[0], fq2_neg(nq2[1]))
    t, l = _add_step(t, q1, p1)
    f = fq12_mul(f, l)
    _, l = _add_step(t, nq2, p1)
    f = fq12_mul(f, l)
    return f


_FINAL_EXP = (Q**12 - 1) // R


def final_exponentiation(f):
    """f^((q^12-1)/r). Easy part via conjugation/inversion-free identity is
    skipped — one big pow keeps this obviously correct; verification is
    host-side and rare."""
    return fq12_pow(f, _FINAL_EXP)


def pairing(q2, p1):
    """e(P, Q) with P in G1 (affine int pair or None), Q in G2 (affine Fq2
    pair or None). Returns an Fq12 element."""
    return final_exponentiation(miller_loop(q2, p1))


def multi_pairing(pairs):
    """prod_i e(P_i, Q_i) via one shared final exponentiation.

    pairs: iterable of (q2, p1). The product of Miller loops is finalized
    once — the standard batched-verification trick.
    """
    f = FQ12_ONE
    for q2, p1 in pairs:
        f = fq12_mul(f, miller_loop(q2, p1))
    return final_exponentiation(f)


def pairing_check(pairs) -> bool:
    """True iff prod_i e(P_i, Q_i) == 1."""
    return multi_pairing(pairs) == FQ12_ONE


__all__ = [
    "FQ12_ONE",
    "fq12_mul",
    "fq12_pow",
    "miller_loop",
    "final_exponentiation",
    "pairing",
    "multi_pairing",
    "pairing_check",
    "G2",
]
