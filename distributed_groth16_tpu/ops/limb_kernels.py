"""Limb-major Pallas TPU kernels for BN254 field + G1 arithmetic.

This is the TPU fast path for the prover's dominant kernel, the MSM (the
reference's per-party hot loop is arkworks `G::msm`,
dist-primitives/src/dmsm/mod.rs:82). The row-major (..., 16)-limb layout of
ops/field.py is right for host interop and XLA composition, but its per-op
`moveaxis` transposes and tiny carry scans cap batched curve adds at a few
M adds/s. Here field elements live **limb-major** — uint32 arrays of shape
(16, n): limb index on the sublane axis, batch on the lane axis — so every
field op is a dense (16, n) vector op with no transposes, and whole group-law
formulas (RCB16 complete add/double) compile to single Pallas kernels that
keep all intermediates in VMEM.

Representation: Montgomery form, *redundant* residues in [0, 2p). The
Montgomery product of inputs < 2p is < 2p (since 4p < 2^256), so `mul` is
closed with no conditional subtract; add/sub do one conditional -2p. Values
are canonicalised (single conditional -p) only at the boundary back to the
row-major world.

Everything here is generic over the modulus via `LimbField`, instantiated
for BN254 Fq; the same machinery can host BLS12-381's base field.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.compile import named_jit
from ..utils import config as _config
from .constants import LIMB_BITS, N_LIMBS, Q, to_limbs

MASK = 0xFFFF
NL = N_LIMBS

# Pallas lane-axis tile for the 48-row G1 kernels. 2048 compiles and runs
# on a v5e; its speed against 1024 / 4096 has not been measured with the
# fori bodies.
TILE = 2048


def _pallas_roll_mode() -> str:
    """How Pallas kernel bodies are built — a compile-time/runtime tradeoff.

    'unroll': trace-time flat bodies (~6k vector ops per group-law kernel).
        ~3x the Mosaic compile time of 'fori' per kernel instance (G1 add
        at TILE: 14.7 s vs 5.5 s, v5e / libtpu 0.0.34), and a tree-MSM
        program holds ~30 instances.
    'fori':   CIOS rounds + carry chains as lax.fori_loop with
        concat-rotate row access (carry a rotated copy, read row 0 by
        STATIC slice — dynamic_slice and lax.scan xs-slicing both fail
        Mosaic lowering, and masked iota-reduction extraction costs
        ~4 full-tile ops per access) — ~4x smaller StableHLO than
        'unroll' (2^14 tree program: 1.2 MB vs 4.7 MB).
    'scan':   the unroll=False lax.scan formulation. DOES NOT LOWER in
        jax 0.9.0's Mosaic (_scan_lowering_rule raises NotImplementedError
        for extensive inputs/outputs) — kept only as documentation of the
        measurement; selecting it fails at first kernel trace.

    All formulations are bit-identical on the XLA fallback
    (tests/test_limb_roll.py).

    The env var is read ONCE at module import: the chosen mode is baked
    into process-global caches (_SmallNTT cached properties,
    LimbGroup._horner functools.cache, jit caches), so a mid-process env
    change could not take effect anyway — capturing at import makes the
    knob honestly process-start-only.
    """
    return _ROLL_MODE


_ROLL_MODE = _config.env_str("DG16_PALLAS_ROLL", "fori")


def kernel_roll_mode():
    """unroll arg for Pallas kernel bodies, from DG16_PALLAS_ROLL."""
    m = _pallas_roll_mode()
    return True if m == "unroll" else (False if m == "scan" else "fori")


def _pl():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def use_pallas() -> bool:
    """Pallas path only on a real TPU backend; elsewhere the same body
    functions run as plain XLA (bit-identical math). A backend that fails
    to initialise raises here — it must not silently select the XLA
    bodies."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Field bodies (pure jnp, limb-major (16, n); trace-time unrolled)
# ---------------------------------------------------------------------------


def _rot(a):
    """Rotate rows up by one: row 0 moves to the bottom. Static slices +
    concat only — both lower in Mosaic (dynamic_slice and masked
    iota-reduction extraction do not / cost ~4 full-tile ops per access).
    fori bodies carry a rotated copy and always read row 0."""
    return jnp.concatenate([a[1:], a[0:1]], axis=0)


class LimbField:
    """Montgomery arithmetic on limb-major uint32[nl, n] in [0, 2p).

    nl defaults to 16 rows (BN254-class, radix 2^256); larger moduli pass
    their limb count (24 for BLS12-377/381 Fq, radix 2^384) and every
    body below derives its row count from self.nl / the input shape —
    same ops, same roll modes, wider tiles."""

    def __init__(self, modulus: int, nl: int = NL):
        assert 4 * modulus < 1 << (LIMB_BITS * nl), "lazy-carry redundancy"
        self.p = modulus
        self.nl = nl
        self.CR = nl  # coordinate rows: one Fq element = nl limb rows
        self.n0 = int((-pow(modulus, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS))
        self.p_col = np.array(to_limbs(modulus, nl), np.uint32).reshape(nl, 1)
        self.p2_col = np.array(
            to_limbs(2 * modulus, nl), np.uint32
        ).reshape(nl, 1)
        self.mont_r = (1 << (LIMB_BITS * nl)) % modulus

    # consts are passed in explicitly so the same bodies work inside Pallas
    # kernels (which reject captured device constants).

    # Each helper has THREE formulations with IDENTICAL op sequences (hence
    # identical numerics), selected by `unroll`: True = trace-time unrolled
    # (flat bodies, ~3x the Mosaic compile time per kernel instance);
    # False = `lax.scan`-rolled for the plain-XLA fallback (unrolled 3k-op
    # graphs made CPU test compiles minutes-long);
    # "fori" = `lax.fori_loop`-rolled with concat-rotate row access, the
    # Pallas compile-friendly middle ground (~10x smaller bodies).

    def carry(self, v, unroll=True):
        """(k, n) lazy rows -> (nl, n) carried limbs (value < radix).

        Rows beyond nl (the CIOS accumulator's top row, zero by the shift
        invariant) are dropped.
        """
        nl = self.nl
        v = v[:nl]
        if unroll == "fori":
            # out self-assembles by appending each carried row at the
            # bottom: after nl iterations rows sit in order 0..nl-1.
            def body(i, st):
                out, c, vr = st
                t = vr[0:1] + c
                return (
                    jnp.concatenate([out[1:], t & MASK], axis=0),
                    t >> LIMB_BITS,
                    _rot(vr),
                )

            out, _, _ = jax.lax.fori_loop(
                0, nl, body,
                (jnp.zeros_like(v), jnp.zeros_like(v[0:1]), v),
            )
            return out
        if not unroll:
            def step(c, row):
                t = row + c
                return t >> LIMB_BITS, t & MASK

            _, out = jax.lax.scan(step, jnp.zeros_like(v[0]), v)
            return out
        rows, c = [], jnp.zeros_like(v[0:1])
        for i in range(nl):
            t = v[i : i + 1] + c
            rows.append(t & MASK)
            c = t >> LIMB_BITS
        return jnp.concatenate(rows, axis=0)

    @staticmethod
    def _cond_sub(a, m_col, unroll=True):
        """a - m if a >= m else a; a carried, m a (nl,1) numpy/jnp column
        (row count derived from a — shared by every limb width)."""
        nl = a.shape[0]
        if unroll == "fori":
            m_col = jnp.asarray(m_col)

            def body(i, st):
                d, b, ar, mr = st
                t = ar[0:1] - mr[0:1] - b
                return (
                    jnp.concatenate([d[1:], t & MASK], axis=0),
                    t >> 31,
                    _rot(ar),
                    _rot(mr),
                )

            d, b, _, _ = jax.lax.fori_loop(
                0, nl, body,
                (jnp.zeros_like(a), jnp.zeros_like(a[0:1]), a, m_col),
            )
            return jnp.where(b == 0, d, a)
        if not unroll:
            def step(b, xs):
                ai, mi = xs
                t = ai - mi - b
                return t >> 31, t & MASK

            b, d = jax.lax.scan(
                step, jnp.zeros_like(a[0]), (a, m_col * jnp.ones_like(a))
            )
            return jnp.where(b == 0, d, a)
        rows, b = [], jnp.zeros_like(a[0:1])
        for i in range(nl):
            t = a[i : i + 1] - m_col[i] - b
            rows.append(t & MASK)
            b = t >> 31
        d = jnp.concatenate(rows, axis=0)
        return jnp.where(b == 0, d, a)

    def add(self, a, b, p2, unroll=True):
        """(a + b) mod* : inputs < 2p -> output < 2p."""
        return self._cond_sub(self.carry(a + b, unroll), p2, unroll)

    def neg(self, b, p2, unroll=True):
        """2p - b (the additive inverse in the redundant class), b < 2p."""
        if unroll == "fori":
            p2 = jnp.asarray(p2)

            def body(i, st):
                out, brw, br, pr = st
                t = pr[0:1] - br[0:1] - brw
                return (
                    jnp.concatenate([out[1:], t & MASK], axis=0),
                    t >> 31,
                    _rot(br),
                    _rot(pr),
                )

            out, _, _, _ = jax.lax.fori_loop(
                0, b.shape[0], body,
                (jnp.zeros_like(b), jnp.zeros_like(b[0:1]), b, p2),
            )
            return out
        if not unroll:
            def step(brw, xs):
                bi, pi = xs
                t = pi - bi - brw
                return t >> 31, t & MASK

            _, out = jax.lax.scan(
                step, jnp.zeros_like(b[0]), (b, p2 * jnp.ones_like(b))
            )
            return out
        rows, brw = [], jnp.zeros_like(b[0:1])
        for i in range(b.shape[0]):
            t = p2[i] - b[i : i + 1] - brw
            rows.append(t & MASK)
            brw = t >> 31
        return jnp.concatenate(rows, axis=0)

    def sub(self, a, b, p2, unroll=True):
        return self._cond_sub(
            self.carry(a + self.neg(b, p2, unroll), unroll), p2, unroll
        )

    def mul(self, a, b, p, unroll=True):
        """Montgomery product, CIOS with lazy carries; inputs < 2p (limbs
        <= 0xffff) -> output < 2p. nl rounds of dense (nl, n) ops, one
        final carry chain, no conditional subtract."""
        nl = self.nl
        n = a.shape[-1]
        z1 = jnp.zeros((1, n), jnp.uint32)

        def step(v, ai):
            prod = ai * b  # (nl, n); both operands <= 0xffff
            # rows 1..nl-1 receive lo[1:] + hi[:-1]: merge before widening
            mid = (prod[1:] & MASK) + (prod[:-1] >> LIMB_BITS)
            contrib = jnp.concatenate(
                [prod[0:1] & MASK, mid, prod[nl - 1 : nl] >> LIMB_BITS],
                axis=0,
            )
            v = v + contrib
            m = (v[0:1] * self.n0) & MASK
            qp = m * p
            qmid = (qp[1:] & MASK) + (qp[:-1] >> LIMB_BITS)
            qcontrib = jnp.concatenate(
                [qp[0:1] & MASK, qmid, qp[nl - 1 : nl] >> LIMB_BITS],
                axis=0,
            )
            v = v + qcontrib
            return jnp.concatenate(
                [v[1:2] + (v[0:1] >> LIMB_BITS), v[2:], z1], axis=0
            )

        v0 = jnp.zeros((nl + 1, n), jnp.uint32)
        if unroll == "fori":
            def body(i, st):
                v, ar = st
                return step(v, ar[0:1]), _rot(ar)

            v, _ = jax.lax.fori_loop(0, nl, body, (v0, a))
            return self.carry(v, unroll="fori")
        if not unroll:
            v, _ = jax.lax.scan(
                lambda v, ai: (step(v, ai[None]), None), v0, a[:nl]
            )
            return self.carry(v, unroll=False)
        v = v0
        for i in range(nl):
            v = step(v, a[i : i + 1])
        return self.carry(v)

    def canon(self, a):
        """[0, 2p) carried -> canonical [0, p)."""
        return self._cond_sub(a, jnp.asarray(self.p_col))

    def is_zero(self, a, p):
        """(1, n) uint32: 1 where a, (nl, n) in [0, 2p), is 0 mod p. The
        redundant class of zero holds two residues, 0 and p: both are
        looked for, by an OR over the rows of `a` and of `a ^ p` (what a
        compare after `canon` would say, without its borrow chain)."""
        z, zp = a[0:1], a[0:1] ^ p[0:1]
        for i in range(1, a.shape[0]):
            z = z | a[i : i + 1]
            zp = zp | (a[i : i + 1] ^ p[i : i + 1])
        return ((z == 0) | (zp == 0)).astype(jnp.uint32)

    @functools.cached_property
    def inv_digits(self) -> tuple:
        """p - 2 in 4-bit digits, the most significant first."""
        e, out = self.p - 2, []
        while e:
            out.append(e & 15)
            e >>= 4
        return tuple(out[::-1])

    def inv_body(self, x, base_ops, one, table):
        """x^(p-2), Fermat's inverse of a non-zero x (0 gives 0), by 4-bit
        windows: 14 products for x^2..x^15, then four squarings and one
        product a digit of p - 2. `base_ops` are this field's (mul, add,
        sub); `one` is Montgomery 1 at x's shape. The loop reads its i-th
        factor by a dynamic index on a leading axis, so the caller's
        `table(factors)` says where they are kept and returns the getter:
        a VMEM scratch in a kernel (dynamic_slice of a value does not
        lower in Mosaic), a stacked array outside one."""
        mul = base_ops[0]
        tab = [one, x]
        for _ in range(14):
            tab.append(mul(tab[-1], x))
        digits = self.inv_digits
        get = table([tab[d] for d in digits])

        def step(i, acc):
            for _ in range(4):
                acc = mul(acc, acc)
            return mul(acc, get(i))

        return jax.lax.fori_loop(1, len(digits), step, tab[digits[0]])

    # -- group-law plumbing --------------------------------------------------

    def make_ops(self, p, p2, unroll=True):
        """(mul, add, sub) closures over the consts blocks — the interface
        the group-law bodies are written against, shared with LimbFq2."""
        return (
            lambda x, y: self.mul(x, y, p, unroll),
            lambda x, y: self.add(x, y, p2, unroll),
            lambda x, y: self.sub(x, y, p2, unroll),
        )

    def neg_rows(self, a, p2, unroll=True):
        return self.neg(a, p2, unroll)

    def canon_rows(self, a):
        return self.canon(a)

    def b3_limbs(self, b) -> np.ndarray:
        """3*b Montgomery-encoded as a (nl, 1) limb column."""
        v = 3 * b * self.mont_r % self.p
        return np.array(to_limbs(v, self.nl), np.uint32).reshape(self.nl, 1)

    def one_limbs(self) -> np.ndarray:
        return np.array(to_limbs(self.mont_r, self.nl), np.uint32)


class LimbFq2:
    """Fq2 = Fq[u]/(u^2 + 1) on limb-major uint32[2*nl, n]: rows 0..nl-1
    c0, nl..2nl-1 c1. Karatsuba over LimbField's redundant-[0, 2p)
    Montgomery arithmetic — all component ops stay closed in [0, 2p)."""

    def __init__(self, base: LimbField):
        self.fq = base
        self.nl = base.nl
        self.CR = 2 * base.nl
        self.p = base.p
        self.p_col = base.p_col
        self.p2_col = base.p2_col
        self.mont_r = base.mont_r

    def make_ops(self, p, p2, unroll=True):
        F = self.fq
        nl = self.nl

        def mul(a, b):
            a0, a1 = a[0:nl], a[nl:]
            b0, b1 = b[0:nl], b[nl:]
            t0 = F.mul(a0, b0, p, unroll)
            t1 = F.mul(a1, b1, p, unroll)
            c0 = F.sub(t0, t1, p2, unroll)  # u^2 = -1
            sa = F.add(a0, a1, p2, unroll)
            sb = F.add(b0, b1, p2, unroll)
            c1 = F.sub(
                F.mul(sa, sb, p, unroll), F.add(t0, t1, p2, unroll),
                p2, unroll,
            )
            return jnp.concatenate([c0, c1], axis=0)

        def add(a, b):
            return jnp.concatenate(
                [
                    F.add(a[0:nl], b[0:nl], p2, unroll),
                    F.add(a[nl:], b[nl:], p2, unroll),
                ],
                axis=0,
            )

        def sub(a, b):
            return jnp.concatenate(
                [
                    F.sub(a[0:nl], b[0:nl], p2, unroll),
                    F.sub(a[nl:], b[nl:], p2, unroll),
                ],
                axis=0,
            )

        return mul, add, sub

    def neg_rows(self, a, p2, unroll=True):
        F, nl = self.fq, self.nl
        return jnp.concatenate(
            [F.neg(a[0:nl], p2, unroll), F.neg(a[nl:], p2, unroll)], axis=0
        )

    def canon_rows(self, a):
        F, nl = self.fq, self.nl
        return jnp.concatenate([F.canon(a[0:nl]), F.canon(a[nl:])], axis=0)

    def is_zero(self, a, p):
        nl = self.nl
        return self.fq.is_zero(a[0:nl], p) & self.fq.is_zero(a[nl:], p)

    def inv_body(self, a, base_ops, one, table):
        """1 / (a0 + a1 u) = (a0 - a1 u) / (a0^2 + a1^2): one inversion in
        the base field (the norm of a non-zero a is not zero: -1 is no
        square there). `base_ops` and `one` are the base field's."""
        mul, add, sub = base_ops
        nl = self.nl
        a0, a1 = a[0:nl], a[nl:]
        ninv = self.fq.inv_body(
            add(mul(a0, a0), mul(a1, a1)), base_ops, one, table
        )
        c1 = mul(a1, ninv)
        return jnp.concatenate(
            [mul(a0, ninv), sub(jnp.zeros_like(c1), c1)], axis=0
        )

    def b3_limbs(self, b) -> np.ndarray:
        """3*b' Montgomery-encoded as a (2*nl, 1) limb column (b' in Fq2)."""
        b0, b1 = b
        nl = self.nl
        return np.concatenate(
            [
                np.array(
                    to_limbs(3 * b0 * self.mont_r % self.p, nl), np.uint32
                ).reshape(nl, 1),
                np.array(
                    to_limbs(3 * b1 * self.mont_r % self.p, nl), np.uint32
                ).reshape(nl, 1),
            ],
            axis=0,
        )

    def one_limbs(self) -> np.ndarray:
        one = np.zeros((2 * self.nl,), np.uint32)
        one[: self.nl] = np.array(
            to_limbs(self.mont_r, self.nl), np.uint32
        )
        return one


@functools.cache
def lfq() -> LimbField:
    return LimbField(Q)


@functools.cache
def lfq2() -> LimbFq2:
    return LimbFq2(lfq())


# ---------------------------------------------------------------------------
# Group law bodies on limb-major points (3*CR, n): X rows then Y then Z
# (projective, RCB16 complete formulas, a = 0). CR = 16 (G1/Fq) or 32
# (G2/Fq2): the SAME formula code serves both via the field's make_ops.
# ---------------------------------------------------------------------------


class LimbGroup:
    """A short-Weierstrass group (a = 0) on limb-major uint32[3*CR, n]."""

    def __init__(self, field, b, tile: int | None = None):
        self.F = field
        # "g1" over a prime field, "g2" over its quadratic extension: the
        # tree MSM's program is named by it (`_msm_tree_jit`)
        self.kind = "g2" if isinstance(field, LimbFq2) else "g1"
        self.CR = field.CR
        self.ROWS = 3 * self.CR
        # base-field limb rows (== CR for Fq, CR/2 for Fq2) — the consts
        # block and kernel bodies slice by this, not a hardcoded 16
        self.base_nl = field.p_col.shape[0]
        # Pallas lane tile: scaled down as rows grow (VMEM budget is
        # rows x tile), floored to a power of two
        if tile is None:
            tile = max(256, TILE * (3 * NL) // self.ROWS)
            tile = 1 << (tile.bit_length() - 1)
        self.tile = tile
        # consts block handed to every kernel:
        # rows [0:bn] p, [bn:2bn] 2p, [2bn:2bn+CR] b3 (Montgomery)
        self.consts_np = np.concatenate(
            [field.p_col, field.p2_col, field.b3_limbs(b)], axis=0
        )
        one = np.zeros((self.CR, 1), np.uint32)
        one[: field.one_limbs().shape[0], 0] = field.one_limbs()
        self.one_col = one  # Montgomery 1
        zero = np.zeros_like(one)
        self.inf_col = np.concatenate([zero, one, zero], axis=0)
        # An affine point is (AROWS, n): x rows, y rows and one flag row,
        # 1 where the point is infinity (x and y are then any residues).
        self.AROWS = 2 * self.CR + 1
        # consts block of the field and affine kernels:
        # rows [0:bn] p, [bn:2bn] 2p, [2bn:2bn+CR] Montgomery 1
        self.fconsts_np = np.concatenate(
            [field.p_col, field.p2_col, one], axis=0
        )

    # -- bodies -------------------------------------------------------------

    def add_body(self, p3, q3, consts, unroll=True):
        CR, bn = self.CR, self.base_nl
        p, p2, b3c = consts[0:bn], consts[bn : 2 * bn], consts[2 * bn :]
        mul, add, sub = self.F.make_ops(p, p2, unroll)
        X1, Y1, Z1 = p3[0:CR], p3[CR : 2 * CR], p3[2 * CR :]
        X2, Y2, Z2 = q3[0:CR], q3[CR : 2 * CR], q3[2 * CR :]
        t0 = mul(X1, X2)
        t1 = mul(Y1, Y2)
        t2 = mul(Z1, Z2)
        t3 = sub(mul(add(X1, Y1), add(X2, Y2)), add(t0, t1))
        t4 = sub(mul(add(Y1, Z1), add(Y2, Z2)), add(t1, t2))
        ty = sub(mul(add(X1, Z1), add(X2, Z2)), add(t0, t2))
        t0_3 = add(add(t0, t0), t0)
        t2b = mul(t2, b3c)
        yb = mul(ty, b3c)
        Z3 = add(t1, t2b)
        t1m = sub(t1, t2b)
        X3 = sub(mul(t3, t1m), mul(t4, yb))
        Y3 = add(mul(yb, t0_3), mul(t1m, Z3))
        Z3o = add(mul(Z3, t4), mul(t0_3, t3))
        return jnp.concatenate([X3, Y3, Z3o], axis=0)

    def double_body(self, p3, consts, unroll=True):
        CR, bn = self.CR, self.base_nl
        p, p2, b3c = consts[0:bn], consts[bn : 2 * bn], consts[2 * bn :]
        mul, add, sub = self.F.make_ops(p, p2, unroll)
        X, Y, Z = p3[0:CR], p3[CR : 2 * CR], p3[2 * CR :]
        t0 = mul(Y, Y)
        t1 = mul(Y, Z)
        t2 = mul(Z, Z)
        txy = mul(X, Y)
        z8 = add(t0, t0)
        z8 = add(z8, z8)
        z8 = add(z8, z8)  # 8 Y^2
        t2b = mul(t2, b3c)
        y3a = add(t0, t2b)
        t0m = sub(t0, add(add(t2b, t2b), t2b))
        X3g = mul(t2b, z8)
        Z3 = mul(t1, z8)
        Y3m = mul(t0m, y3a)
        X3m = mul(t0m, txy)
        Y3 = add(X3g, Y3m)
        X3 = add(X3m, X3m)
        return jnp.concatenate([X3, Y3, Z3], axis=0)

    def neg_body(self, p3, consts):
        CR, bn = self.CR, self.base_nl
        p2 = consts[bn : 2 * bn]
        return jnp.concatenate(
            [
                p3[0:CR],
                self.F.neg_rows(p3[CR : 2 * CR], p2),
                p3[2 * CR :],
            ],
            axis=0,
        )

    # -- pallas / XLA dispatch ---------------------------------------------

    _kmode = staticmethod(kernel_roll_mode)

    def _consts(self):
        return jnp.asarray(self.consts_np)

    @functools.cached_property
    def _xla_add(self):
        return jax.jit(
            lambda p, q: self.add_body(p, q, self._consts(), unroll=False)
        )

    @functools.cached_property
    def _xla_double(self):
        return jax.jit(
            lambda p: self.double_body(p, self._consts(), unroll=False)
        )

    # The group law on one (ROWS, tile) block, jitted: a kernel is traced
    # anew for every lane count it is called at, though its block never
    # changes, and with the body behind a jit that trace binds one cached
    # call instead of running some 10^4 lines of field arithmetic again.
    # Mosaic lowers the call inline; the kernel is the same.

    @functools.cached_property
    def _add_block(self):
        def add_block(p, q, consts):
            return self.add_body(p, q, consts, unroll=self._kmode())

        return jax.jit(add_block)

    @functools.cached_property
    def _double_block(self):
        def double_block(p, consts):
            return self.double_body(p, consts, unroll=self._kmode())

        return jax.jit(double_block)

    @functools.cached_property
    def _pallas_add(self):
        pl, pltpu = _pl()
        RR, T, CROWS = self.ROWS, self.tile, self.consts_np.shape[0]

        def kern(p_ref, q_ref, c_ref, o_ref):
            o_ref[:] = self._add_block(p_ref[:], q_ref[:], c_ref[:])

        @jax.jit
        def run(p, q):
            n = p.shape[1]
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((RR, n), jnp.uint32),
                grid=(n // T,),
                in_specs=[
                    pl.BlockSpec((RR, T), lambda i: (0, i),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((RR, T), lambda i: (0, i),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((CROWS, 1), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((RR, T), lambda i: (0, i),
                                       memory_space=pltpu.VMEM),
            )(p, q, self._consts())

        return run

    @functools.cached_property
    def _pallas_double(self):
        pl, pltpu = _pl()
        RR, T, CROWS = self.ROWS, self.tile, self.consts_np.shape[0]

        def kern(p_ref, c_ref, o_ref):
            o_ref[:] = self._double_block(p_ref[:], c_ref[:])

        @jax.jit
        def run(p):
            n = p.shape[1]
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((RR, n), jnp.uint32),
                grid=(n // T,),
                in_specs=[
                    pl.BlockSpec((RR, T), lambda i: (0, i),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((CROWS, 1), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((RR, T), lambda i: (0, i),
                                       memory_space=pltpu.VMEM),
            )(p, self._consts())

        return run

    def _batched(self, fn_pallas, fn_xla, args):
        """Flatten trailing batch axes, pad the lane axis to a power-of-two
        width, run. Power-of-two padding bounds the number of distinct
        compiled shapes (the unrolled group-law graphs are large, so each
        extra shape is a real compile cost on both CPU and TPU). Each
        argument keeps its own row count; so does each output."""
        lanes = args[0].shape[1:]
        flat = [a.reshape(a.shape[0], -1) for a in args]
        n = flat[0].shape[1]
        npad = self.lane_pad(n)
        if npad != n:
            flat = [jnp.pad(a, ((0, 0), (0, npad - n))) for a in flat]
        out = (fn_pallas if use_pallas() else fn_xla)(*flat)
        cut = [
            o[:, :n].reshape((o.shape[0],) + lanes)
            for o in (out if isinstance(out, tuple) else (out,))
        ]
        return tuple(cut) if isinstance(out, tuple) else cut[0]

    def lane_pad(self, n: int) -> int:
        """The lane width `add` / `double` run n lanes at: the power of two
        at or above n, and at least one Pallas tile (256 on the XLA path)."""
        granule = self.tile if use_pallas() else 256
        return max(granule, 1 << max(n - 1, 0).bit_length())

    def add(self, p, q):
        """Complete add on (ROWS, ...) limb-major batches."""
        q = jnp.broadcast_to(q, p.shape)
        return self._batched(self._pallas_add, self._xla_add, (p, q))

    def double(self, p):
        return self._batched(self._pallas_double, self._xla_double, (p,))

    def neg(self, p):
        return self.neg_body(
            p.reshape(self.ROWS, -1), self._consts()
        ).reshape(p.shape)

    # -- affine points: the field product, the batched inversion, the add ---
    #
    # Where a level of the tree MSM's up-sweep is wide (`_AFFINE_MIN_ADDS`),
    # its adds are affine: 7 field products an add, three of them its share
    # of ONE inversion for the whole level, against the complete projective
    # add's 14. Exact on every input: the lanes that need no slope (an
    # operand at infinity, P + (-P), a doubling of a 2-torsion point) carry
    # 1 through the inversion and take their result from a select.

    def _fconsts(self):
        return jnp.asarray(self.fconsts_np)

    def _fops(self, consts, unroll):
        """The field's (mul, add, sub), p, and 1 from the field consts."""
        bn = self.base_nl
        p, p2 = consts[0:bn], consts[bn : 2 * bn]
        return self.F.make_ops(p, p2, unroll), p, consts[2 * bn :]

    @property
    def _base_field(self) -> LimbField:
        return getattr(self.F, "fq", self.F)

    def _base_ops(self, consts, unroll):
        """(mul, add, sub) of the BASE field (the field's own over Fq)."""
        bn = self.base_nl
        return self._base_field.make_ops(
            consts[0:bn], consts[bn : 2 * bn], unroll
        )

    def fmul_body(self, a, b, consts, unroll=True):
        return self._fops(consts, unroll)[0][0](a, b)

    def affine_pre_body(self, a1, a2, consts, unroll=True):
        """The part of P1 + P2 before the inversion: (num | code) and den,
        with the slope num / den. code: 0 take the slope's point, 1 take
        P2 (P1 is infinity), 2 take P1, 3 infinity (the two cancel). The
        zero tests are on residues in [0, 2p), where zero is 0 or p."""
        CR = self.CR
        (mul, add, sub), p, one = self._fops(consts, unroll)
        x1, y1, f1 = a1[0:CR], a1[CR : 2 * CR], a1[2 * CR :]
        x2, y2, f2 = a2[0:CR], a2[CR : 2 * CR], a2[2 * CR :]
        dx, dy = sub(x2, x1), sub(y2, y1)
        same_x = self.F.is_zero(dx, p)
        same_y = self.F.is_zero(dy, p)
        dbl = same_x & same_y

        def doubling():  # the tangent's slope, 3 x1^2 / 2 y1
            xx = mul(x1, x1)
            return (jnp.where(dbl != 0, add(add(xx, xx), xx), dy),
                    jnp.where(dbl != 0, add(y1, y1), dx))

        # a block that holds no doubling (nearly every one: a doubling is a
        # repeated point meeting itself) skips the tangent's product
        num, den = jax.lax.cond(
            jnp.max(dbl.astype(jnp.int32)) != 0, doubling, lambda: (dy, dx)
        )
        # equal x: the same point (a doubling, of a 2-torsion point when
        # y is zero) or its negative
        gone = same_x & ((same_y ^ 1) | self.F.is_zero(y1, p))
        code = jnp.where(
            f1 != 0, 1, jnp.where(f2 != 0, 2, jnp.where(gone != 0, 3, 0))
        ).astype(jnp.uint32)
        den = jnp.where(code == 0, den, one)
        return jnp.concatenate([num, code], axis=0), den

    def affine_post_body(self, a1, a2, nc, inv, consts, unroll=True):
        """The part after it: lambda = num / den, x3 = lambda^2 - x1 - x2,
        y3 = lambda (x1 - x3) - y1, then the selects of `code`."""
        CR = self.CR
        (mul, add, sub), _, _ = self._fops(consts, unroll)
        x1, y1 = a1[0:CR], a1[CR : 2 * CR]
        x2 = a2[0:CR]
        code = nc[CR:]
        lam = mul(nc[0:CR], inv)
        x3 = sub(mul(lam, lam), add(x1, x2))
        y3 = sub(mul(lam, sub(x1, x3)), y1)
        xy = jnp.where(
            code == 1, a2[0 : 2 * CR],
            jnp.where(code == 2, a1[0 : 2 * CR],
                      jnp.concatenate([x3, y3], axis=0)),
        )
        flag = jnp.where(
            code == 1, a2[2 * CR :], (code == 3).astype(jnp.uint32)
        )
        return jnp.concatenate([xy, flag], axis=0)

    def root_inverse_body(self, x, consts, one, table, kernel: bool):
        """The narrow end of the batched inversion, (CR, L) with L at most
        a lane tile: halves multiplied together down to the width of
        `one` (the base field's 1, (base_nl, `_INV_LANES`) in a kernel),
        one Fermat inversion there (`inv_body`, which `table` serves), and
        the halves' inverses multiplied back out. In a kernel the
        products are calls of the jitted blocks."""
        if kernel:
            _, badd, bsub = self._base_ops(consts, self._kmode())
            base_ops = (
                lambda a, b: self._bmul_block(a, b, consts), badd, bsub
            )
            fmul = lambda a, b: self._fmul_block(a, b, consts)  # noqa: E731
        else:
            base_ops = self._base_ops(consts, False)
            fmul = lambda a, b: self.fmul_body(  # noqa: E731
                a, b, consts, unroll=False
            )
        kept = []
        while x.shape[1] > one.shape[1]:
            h = x.shape[1] // 2
            kept.append(x)
            x = fmul(x[:, :h], x[:, h:])
        inv = self.F.inv_body(x, base_ops, one, table)
        for x in reversed(kept):
            h = x.shape[1] // 2
            inv = jnp.concatenate(
                [fmul(inv, x[:, h:]), fmul(inv, x[:, :h])], axis=1
            )
        return inv

    @functools.cached_property
    def _fmul_block(self):
        def fmul_block(a, b, consts):
            return self.fmul_body(a, b, consts, unroll=self._kmode())

        return jax.jit(fmul_block)

    @functools.cached_property
    def _bmul_block(self):
        """The base field's product on one block (Fq2's Fermat inversion
        runs in Fq)."""
        def bmul_block(a, b, consts):
            return self._base_ops(consts, self._kmode())[0](a, b)

        return jax.jit(bmul_block)

    @functools.cached_property
    def _affine_pre_block(self):
        def affine_pre_block(a1, a2, consts):
            return self.affine_pre_body(a1, a2, consts, unroll=self._kmode())

        return jax.jit(affine_pre_block)

    @functools.cached_property
    def _affine_post_block(self):
        def affine_post_block(a1, a2, nc, inv, consts):
            return self.affine_post_body(
                a1, a2, nc, inv, consts, unroll=self._kmode()
            )

        return jax.jit(affine_post_block)

    def _lane_kernel(self, block, in_rows: tuple, out_rows: tuple):
        """`block(*blocks, consts)` over lane tiles, as `_pallas_add` runs
        `_add_block`: one grid step a tile, every argument and output a
        (rows, tile) block of its own row count."""
        pl, pltpu = _pl()
        T, CROWS = self.tile, self.fconsts_np.shape[0]
        n_in = len(in_rows)

        def spec(rows):
            return pl.BlockSpec((rows, T), lambda i: (0, i),
                                memory_space=pltpu.VMEM)

        def kern(*refs):
            outs = block(*(r[:] for r in refs[: n_in + 1]))
            if len(out_rows) == 1:
                outs = (outs,)
            for o_ref, o in zip(refs[n_in + 1 :], outs):
                o_ref[:] = o

        @jax.jit
        def run(*args):
            n = args[0].shape[1]
            out = pl.pallas_call(
                kern,
                out_shape=[
                    jax.ShapeDtypeStruct((r, n), jnp.uint32) for r in out_rows
                ],
                grid=(n // T,),
                in_specs=[spec(r) for r in in_rows] + [
                    pl.BlockSpec((CROWS, 1), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM)
                ],
                out_specs=[spec(r) for r in out_rows],
            )(*args, self._fconsts())
            return out[0] if len(out_rows) == 1 else tuple(out)

        return run

    @functools.cached_property
    def _pallas_fmul(self):
        CR = self.CR
        return self._lane_kernel(self._fmul_block, (CR, CR), (CR,))

    @functools.cached_property
    def _pallas_affine_pre(self):
        CR, AR = self.CR, self.AROWS
        return self._lane_kernel(
            self._affine_pre_block, (AR, AR), (CR + 1, CR)
        )

    @functools.cached_property
    def _pallas_affine_post(self):
        CR, AR = self.CR, self.AROWS
        return self._lane_kernel(
            self._affine_post_block, (AR, AR, CR + 1, CR), (AR,)
        )

    @functools.cached_property
    def _pallas_root_inverse(self):
        pl, pltpu = _pl()
        bn = self.base_nl
        nd = len(self._base_field.inv_digits)

        def kern(x_ref, one_ref, c_ref, o_ref, tab_ref):
            def table(factors):
                for i, v in enumerate(factors):
                    tab_ref[i] = v
                return lambda i: tab_ref[i]

            o_ref[:] = self.root_inverse_body(
                x_ref[:], c_ref[:], one_ref[:], table, kernel=True
            )

        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)

        @jax.jit
        def run(x):
            one = jnp.broadcast_to(
                jnp.asarray(self.one_col[:bn]), (bn, _INV_LANES)
            )
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
                in_specs=[vmem, vmem, vmem],
                out_specs=vmem,
                scratch_shapes=[
                    pltpu.VMEM((nd, bn, _INV_LANES), jnp.uint32)
                ],
            )(x, one, self._fconsts())

        return run

    @functools.cached_property
    def _xla_fmul(self):
        return jax.jit(
            lambda a, b: self.fmul_body(a, b, self._fconsts(), unroll=False)
        )

    @functools.cached_property
    def _xla_affine_pre(self):
        return jax.jit(
            lambda a1, a2: self.affine_pre_body(
                a1, a2, self._fconsts(), unroll=False
            )
        )

    @functools.cached_property
    def _xla_affine_post(self):
        return jax.jit(
            lambda a1, a2, nc, inv: self.affine_post_body(
                a1, a2, nc, inv, self._fconsts(), unroll=False
            )
        )

    @functools.cached_property
    def _xla_root_inverse(self):
        bn = self.base_nl

        def table(factors):
            stack = jnp.stack(factors)
            return lambda i: stack[i]

        def run(x):
            one = jnp.broadcast_to(
                jnp.asarray(self.one_col[:bn]),
                (bn, min(x.shape[1], _INV_LANES)),
            )
            return self.root_inverse_body(
                x, self._fconsts(), one, table, kernel=False
            )

        return jax.jit(run)

    def fmul(self, a, b):
        """The field's product on (CR, ...) limb-major batches."""
        return self._batched(self._pallas_fmul, self._xla_fmul, (a, b))

    def batch_inverse(self, a):
        """1 / a for every lane of a (CR, ...) batch of NON-ZERO field
        elements (a caller's masked lane carries 1: one zero would zero
        every inverse), for three products an element and one Fermat
        inversion a call. A product tree up, halves against halves (lane
        slices, where the up-sweep's neighbours would be a stride-2
        shuffle; any pairing serves a product), each level one dense
        product kernel; at one lane tile the root kernel takes over; and
        the tree walked back down, inverse of a half = inverse of the
        pair times the other half."""
        shape = a.shape
        a = a.reshape(self.CR, -1)
        n = a.shape[1]
        pallas = use_pallas()
        width = max(_INV_LANES if pallas else 1,
                    1 << max(n - 1, 0).bit_length())
        if width != n:
            a = jnp.concatenate(
                [a, jnp.broadcast_to(jnp.asarray(self.one_col),
                                     (self.CR, width - n))],
                axis=1,
            )
        with jax.named_scope("msm.inverse"):
            kept, x = [], a
            while x.shape[1] > self.lane_pad(1):
                h = x.shape[1] // 2
                kept.append(x)
                x = self.fmul(x[:, :h], x[:, h:])
            inv = (
                self._pallas_root_inverse if pallas
                else self._xla_root_inverse
            )(x)
            for x in reversed(kept):
                h = x.shape[1] // 2
                inv = self.fmul(
                    jnp.concatenate([inv, inv], axis=1),
                    jnp.concatenate([x[:, h:], x[:, :h]], axis=1),
                )
        return inv[:, :n].reshape(shape)

    def affine_add(self, a1, a2):
        """P1 + P2 on (AROWS, ...) batches of affine points, exact for
        every pair: two kernels round one batched inversion."""
        nc, den = self._batched(
            self._pallas_affine_pre, self._xla_affine_pre, (a1, a2)
        )
        inv = self.batch_inverse(den)
        return self._batched(
            self._pallas_affine_post, self._xla_affine_post,
            (a1, a2, nc, inv),
        )

    def normalise(self, lm):
        """(ROWS, n) projective, any Z -> (AROWS, n) affine: X / Z, Y / Z
        and the flag Z == 0, with one batched inversion."""
        CR = self.CR
        inf = self.F.is_zero(lm[2 * CR :], jnp.asarray(self.F.p_col))
        z = jnp.where(inf != 0, jnp.asarray(self.one_col), lm[2 * CR :])
        zinv = self.batch_inverse(z)
        return jnp.concatenate(
            [self.fmul(lm[0:CR], zinv), self.fmul(lm[CR : 2 * CR], zinv),
             inf],
            axis=0,
        )

    def lift(self, a, axis: int = 0):
        """Affine (x, y, inf) -> projective (x, y, 1), or (0, 1, 0) under
        the flag; the rows lie along `axis`."""
        CR = self.CR
        axis %= a.ndim
        rows = [1] * a.ndim
        rows[axis] = -1
        xy = jax.lax.slice_in_dim(a, 0, 2 * CR, axis=axis)
        flag = jax.lax.slice_in_dim(a, 2 * CR, 2 * CR + 1, axis=axis)
        one = jnp.asarray(self.one_col).reshape(rows)
        one = jnp.broadcast_to(
            one, xy.shape[:axis] + (CR,) + xy.shape[axis + 1 :]
        )
        return jnp.where(
            flag != 0,
            jnp.asarray(self.inf_col).reshape(rows),
            jnp.concatenate([xy, one], axis=axis),
        )

    # -- window combine (Horner over c-bit windows), one fused kernel -------

    def horner_body(self, getcol, consts, c: int, W: int, kernel: bool):
        """acc = sum_w 2^(c*w) * S_w; getcol(w) -> (ROWS, 1) window sum.
        `kernel` says whose body this is: the Pallas kernel's (its c
        doublings and one add a step are calls of the jitted blocks) or
        the XLA fallback's."""
        RR = self.ROWS
        acc0 = jnp.broadcast_to(getcol(W - 1), (RR, 128))

        def double(a):
            if kernel:
                return self._double_block(a, consts)
            return self.double_body(a, consts, unroll=False)

        def add(a, b):
            if kernel:
                return self._add_block(a, b, consts)
            return self.add_body(a, b, consts, unroll=False)

        def step(i, acc):
            w = W - 2 - i
            for _ in range(c):
                acc = double(acc)
            return add(acc, jnp.broadcast_to(getcol(w), (RR, 128)))

        return jax.lax.fori_loop(0, W - 1, step, acc0)

    @functools.cache
    def _horner(self, c: int, W: int, lanes: bool = False):
        """The Horner program: on (ROWS, W) window sums of one MSM, or,
        with `lanes`, on (W, ROWS, 128) columns whose lanes are 128 MSMs'
        window sums (`horner_lanes`)."""
        RR = self.ROWS
        if not use_pallas():
            if lanes:
                return jax.jit(
                    lambda cols: self.horner_body(
                        lambda w: cols[w], self._consts(), c, W,
                        kernel=False,
                    )
                )
            return jax.jit(
                lambda s: self.horner_body(
                    lambda w: jax.lax.dynamic_slice(s, (0, w), (RR, 1)),
                    self._consts(), c, W, kernel=False,
                )[:, :1]
            )
        pl, pltpu = _pl()

        def kern(s_ref, c_ref, o_ref):
            # window w is a dynamic index on the LEADING (untiled) axis of
            # the pre-broadcast (W, ROWS, 128) block: a plain load. Picking
            # a column in-kernel (mask + lane-reduce + lane-broadcast) gave
            # the accumulator a lane-replicated layout that Mosaic cannot
            # carry through the fori_loop ("Invalid relayout").
            o_ref[:] = self.horner_body(
                lambda w: s_ref[w], c_ref[:], c, W, kernel=True
            )

        @jax.jit
        def run(s):
            cols = s if lanes else jnp.broadcast_to(
                jnp.transpose(s)[:, :, None], (W, RR, 128)
            )
            out = pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((RR, 128), jnp.uint32),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.VMEM),
                    pl.BlockSpec(memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            )(cols, self._consts())
            return out if lanes else out[:, :1]

        return run

    def horner(self, s, c: int):
        """Window sums s (ROWS, W), LSB window first -> one point column."""
        W = s.shape[1]
        if W == 1:
            return s
        return self._horner(c, W)(s)

    def horner_lanes(self, s, c: int):
        """Window sums of B MSMs, s (ROWS, B, W), LSB window first -> B
        point columns (ROWS, B): the same kernel, one MSM a lane where
        `horner` broadcasts one MSM over all 128."""
        RR, B, W = s.shape
        if W == 1:
            return s[..., 0]
        cols = jnp.transpose(s, (2, 0, 1))  # (W, RR, B)
        outs = []
        for b0 in range(0, B, 128):
            blk = cols[..., b0 : b0 + 128]
            k = blk.shape[-1]
            if k != 128:
                blk = jnp.concatenate(
                    [blk, jnp.broadcast_to(
                        jnp.asarray(self.inf_col)[None], (W, RR, 128 - k)
                    )],
                    axis=-1,
                )
            outs.append(self._horner(c, W, lanes=True)(blk)[:, :k])
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    # -- layout conversion ---------------------------------------------------

    @property
    def rm_shape(self) -> tuple:
        """Trailing row-major point shape: (3, nl) G1, (3, 2, nl) G2."""
        bn = self.base_nl
        return (3, bn) if self.CR == bn else (3, 2, bn)

    def from_rowmajor(self, pts):
        """(n,) + rm_shape row-major (canonical Montgomery) -> (ROWS, n)."""
        n = pts.shape[0]
        return jnp.transpose(pts.reshape(n, self.ROWS))

    def to_rowmajor(self, lm, canonical: bool = True):
        """(ROWS, n) -> (n,) + rm_shape row-major; canonicalises to [0, p)."""
        if canonical:
            lm = jnp.concatenate(
                [
                    self.F.canon_rows(lm[i * self.CR : (i + 1) * self.CR])
                    for i in range(3)
                ],
                axis=0,
            )
        return jnp.transpose(lm).reshape((-1,) + self.rm_shape)

    def infinity(self, n: int):
        return jnp.broadcast_to(jnp.asarray(self.inf_col), (self.ROWS, n))


# Back-compat name: the original G1-only class was called LimbG1.
LimbG1 = LimbGroup


@functools.cache
def lg1() -> LimbGroup:
    from .constants import G1_B

    return LimbGroup(lfq(), G1_B)


@functools.cache
def lg2() -> LimbGroup:
    from .constants import G2_B

    return LimbGroup(lfq2(), G2_B)


# BLS12-377/381 limb groups: same bodies/kernels at 24 base-field limb
# rows (radix 2^384). The PrimeField configs in ops/bls12_377.py /
# ops/bls12_381.py stay the row-major source of truth; these are the
# Pallas-path mirrors, keyed off the same derived constants.


@functools.cache
def lg1_377() -> LimbGroup:
    from .bls12_377 import G1_B377, Q377, fq377

    return LimbGroup(LimbField(Q377, fq377().nl), G1_B377)


@functools.cache
def lg1_381() -> LimbGroup:
    from .bls12_381 import G1_B381, Q381, fq381

    return LimbGroup(LimbField(Q381, fq381().nl), G1_B381)


@functools.cache
def lg2_381() -> LimbGroup:
    from .bls12_381 import G2_B381, Q381, fq381

    return LimbGroup(LimbFq2(LimbField(Q381, fq381().nl)), G2_B381)


# ---------------------------------------------------------------------------
# Tree MSM: sorted-digit buckets, pairwise sum tree + Fenwick prefix queries
# ---------------------------------------------------------------------------


def _digits(scalars_std, c: int):
    """(n, nl) standard-form u32 limbs -> (W, n) int32 c-bit digits, LSB
    window first, W = nl*16/c. c must divide 16. Width-aware: wider
    scalar layouts (17-limb r381 standard form) just produce more
    (all-zero) top windows — no truncation."""
    assert LIMB_BITS % c == 0
    per = LIMB_BITS // c
    nl_s = scalars_std.shape[1]
    parts = [
        ((scalars_std >> (k * c)) & ((1 << c) - 1)) for k in range(per)
    ]  # each (n, nl)
    inter = jnp.stack(parts, axis=-1).reshape(
        scalars_std.shape[0], nl_s * per
    )
    return jnp.transpose(inter).astype(jnp.int32)  # (W, n)


# A scalar is wide when a limb above limb 0 is set (value >= 2^16); limbs
# 1..15 are what the limb-0 form of the tree carries beside the points.
_UPPER_LIMBS = N_LIMBS - 1


@dataclasses.dataclass(frozen=True, eq=False)
class WideScalars:
    """What the host knows of an MSM's n scalars: which are wide (>= 2^16)
    and the upper limbs of those. Made by `observe` from the integers
    themselves (`ops/msm.py:encode_observed` makes it beside the device
    encoding of the same list), never from a caller's say-so: the limb-0
    tree drops the upper limbs of every scalar the view does not name.

    Its cost follows K, the number of wide scalars: one pass over the n
    integers finds them, and their limbs are read from one buffer of 32
    bytes each (two rows for a witness of bits, 2 MB for 65,000 wires
    that fill the field), never limb by limb in Python."""

    n: int
    idx: np.ndarray  # (K,) int32, ascending: positions of the wide scalars
    limbs: np.ndarray  # (K, 15) uint32: their standard-form limbs 1..15

    @classmethod
    def observe(cls, values) -> "WideScalars":
        """values: n Python ints in [0, 2^256), the scalars as encoded."""
        idx = [i for i, v in enumerate(values) if v >> LIMB_BITS]
        buf = b"".join(values[i].to_bytes(2 * N_LIMBS, "little") for i in idx)
        limbs = np.frombuffer(buf, "<u2").reshape(len(idx), N_LIMBS)
        return cls(
            len(values),
            np.asarray(idx, np.int32),
            limbs[:, 1:].astype(np.uint32),
        )

    @property
    def count(self) -> int:
        return int(self.idx.shape[0])

    def tail(self, start: int) -> "WideScalars":
        """The view of scalars[start:]."""
        keep = self.idx >= start
        return WideScalars(
            self.n - start, self.idx[keep] - np.int32(start), self.limbs[keep]
        )


def _tree_npad(n: int) -> int:
    return 1 << max(1, (n - 1).bit_length())


# A level of the tree's up-sweep runs affine adds (`LimbGroup.affine_add`)
# when it holds at least this many adds, and complete projective ones
# under it: an affine add is half the field work, and a level of them pays
# one Fermat inversion, serial on one block, whatever its width.
_AFFINE_MIN_ADDS = 1 << 16
# the lane width the one Fermat inversion of a batched inversion runs at
_INV_LANES = 128


def _tree_window_bits(n: int) -> int:
    # the Fenwick/combine stages scale with B = 2^c per window: a small
    # MSM with c=8 would spend everything on 255 empty buckets
    return 8 if n >= 4096 else 4


def _tree_window_group(g: "LimbGroup", npad: int, windows: int,
                       rows: int = 1) -> int:
    # bound live tree memory to ~8 * 48 * 2^20 * 4 * 2 ≈ 3.2 GB
    # (half the window count for G2's double-width rows); a launch of
    # `rows` MSMs holds rows * npad lanes a window, so it counts as one
    # MSM of that many points
    return (
        windows if rows * npad <= (1 << 17) else max(1, 8 * 48 // g.ROWS)
    )


def _affine_depth(windows: int, npad: int, min_adds: int) -> int:
    """How many levels of a window group's up-sweep are affine: level d
    holds `windows * npad >> (d + 1)` adds, the widest first."""
    return sum(
        (windows * npad) >> (d + 1) >= min_adds
        for d in range(npad.bit_length() - 1)
    )


def _affine_depths(windows: int, group: int, npad: int,
                   min_adds: int) -> list[int]:
    """`_affine_depth` of each window group of a launch, in order."""
    return [
        _affine_depth(min(group, windows - w0), npad, min_adds)
        for w0 in range(0, windows, group)
    ]


def tree_affine_levels(g: "LimbGroup", n: int, limbs: int,
                       rows: int = 1) -> int:
    """The affine levels a launch of `msm_tree` runs on n points with
    scalars of `limbs` 16-bit limbs (1 for the limb-0 form), or of
    `msm_tree_batched` on `rows` such MSMs, over all its window groups:
    what `msm_affine_levels_total` is raised by."""
    npad = _tree_npad(n)
    windows = rows * limbs * LIMB_BITS // _tree_window_bits(n)
    return sum(_affine_depths(
        windows, _tree_window_group(g, npad, windows, rows), npad,
        _AFFINE_MIN_ADDS,
    ))


def wide_capacity(g: "LimbGroup", n: int) -> int:
    """How many wide scalars the limb-0 tree carries beside n points: each
    takes 15 of the slots that padding n to its power of two leaves empty,
    and the ladder that makes their points runs at one lane tile."""
    return min((_tree_npad(n) - n) // _UPPER_LIMBS, g.tile)


def takes_limb0(g: "LimbGroup", n: int, wide: WideScalars | None) -> bool:
    """The rule of `msm_tree`'s window count: limb-0 windows when the host
    has seen the scalars and their wide ones fit, all windows otherwise."""
    return wide is not None and wide.count <= wide_capacity(g, n)


def msm_tree(points_rm, scalars_std, c: int | None = None,
             window_group: int | None = None, group: "LimbGroup" = None,
             wide: WideScalars | None = None):
    """sum_i scalars[i] * points[i], limb-major TPU path (any LimbGroup).

    points_rm: (n, 3, nl) G1 / (n, 3, 2, nl) G2 projective row-major
    (Montgomery, canonical) — BN254 groups are inferred from the rank
    when `group` is omitted; other curves pass their LimbGroup
    (lg1_377() / lg1_381() / lg2_381());
    scalars_std: (n, k) uint32 standard form (k*16 >= scalar bits).
    Returns the (3, ...) row-major canonical projective sum.

    Per window: points are ordered by digit (argsort), reduced by a pairwise
    sum tree (n-1 adds — vs 2n for an associative_scan — with every level a
    dense Pallas add over all windows at once), and the B-1 bucket prefix
    sums C_j are read off the tree Fenwick-style: C(pos) =
    sum_{d: bit d of pos} level_d[(pos >> d) - 1]. The weighted-bucket
    identity sum_b b*S_b = sum_j (total - C_j) then needs one batched
    neg+add and a small tree sum; windows combine in one fused Horner
    kernel. Matches the role of arkworks G::msm (dmsm/mod.rs:82).

    The whole computation is one jitted program: per-dispatch host latency
    would otherwise dominate the ~30 narrow query/combine steps.

    The price of an add follows the level's width. A level of the sum tree
    that holds at least `_AFFINE_MIN_ADDS` (2^16) adds over its window
    group runs them affine (`LimbGroup.affine_add`: 7 field products an
    add, three of them its share of one inversion for the whole level,
    against the complete projective add's 14 and twice the additions); a
    narrower level runs the complete add, because the level's one Fermat
    inversion is serial on one 128-lane block (1.2 ms on a v5e) whatever
    the width, and a launch with an affine level first makes its points
    affine (1.6 ms at 32,768). The widest levels come first, so the
    affine ones are a prefix: 32 windows of 32,768 points take four (94 %
    of the adds), the limb-0 form's 2 windows none. Both adds are exact
    on every input, so the sum is the same group element either way.

    The window count follows the occupancy of the scalars, as arkworks'
    MSM drops zero scalars and takes unit ones in its first window. Without
    `wide` (device scalars nobody has seen) every window of the k limbs is
    run. With `wide`, the host's view of the same scalars, and room for the
    wide ones (`wide_capacity`), the tree runs over limb 0 alone, 16/c
    windows in place of 16k/c: a witness of bits costs 2 windows, not 32.
    Each wide scalar's limbs 1..15 ride as 15 more 16-bit scalars on the
    points 2^(16j) P, which one doubling ladder makes
    (`_msm_tree_jit_*_limb0_fill`) and which sit in the slots that padding
    n to its power of two leaves empty: the same sum, exactly, from a
    second launch of this program text at one shape per padded length.
    Too many wide scalars, or no free slot, and the call runs all windows.
    """
    n = points_rm.shape[0]
    if c is None:
        c = _tree_window_bits(n)
    g = group or (lg2() if points_rm.ndim == 4 else lg1())
    if not takes_limb0(g, n, wide):
        return _MSM_TREE_JITS[g.kind](
            g, points_rm, scalars_std, c, window_group
        )
    assert wide.n == n == scalars_std.shape[0], (wide.n, n)
    # The shapes below follow n alone, never the number of wide scalars:
    # the view is padded to the capacity, and the ladder's trip count (the
    # highest limb any wide scalar uses) is a device scalar.
    cap = wide_capacity(g, n)
    idx = np.zeros((cap,), np.int32)
    limbs = np.zeros((cap, _UPPER_LIMBS), np.uint32)
    idx[: wide.count] = wide.idx
    limbs[: wide.count] = wide.limbs
    used = np.flatnonzero(limbs.any(axis=0))
    steps = np.int32(used[-1] + 1 if used.size else 0)
    points_pad, limb0_pad = _MSM_LIMB0_FILL_JITS[g.kind](
        g, points_rm, scalars_std, idx, limbs, steps
    )
    return _MSM_LIMB0_JITS[g.kind](g, points_pad, limb0_pad, c, window_group)


def msm_tree_batched(points_rm, scalars_std, group: "LimbGroup",
                     c: int | None = None, window_group: int | None = None):
    """B same-length MSMs as ONE launch of the tree program:
    points_rm (B, n) + rm_shape, scalars_std (B, n, k) standard form ->
    (B,) + rm_shape, row b the sum `msm_tree` gives for row b, all
    windows (no limb-0 form). The B * W windows run as one MSM's W do:
    what a launch pays whatever its width (the narrow levels' tiles, the
    affine levels' serial inversions, Horner) is paid once, while the
    Fenwick and combine stages grow with the windows as the up-sweep
    does; and the launch's B * W * npad lanes count toward the affine rule
    and the window groups as one MSM's W * npad do
    (`tree_affine_levels(..., rows=B)`). Rows of any length run at the
    power of two (`_batched_pad`), so one program serves every length."""
    n = points_rm.shape[1]
    if c is None:
        c = _tree_window_bits(n)
    if n != _tree_npad(n):
        points_rm, scalars_std = _MSM_TREE_BATCHED_PAD_JITS[group.kind](
            group, points_rm, scalars_std
        )
    return _MSM_TREE_BATCHED_JITS[group.kind](
        group, points_rm, scalars_std, c, window_group
    )


def _batched_pad(g: LimbGroup, points_rm, scalars_std):
    """The rows of `msm_tree_batched` padded to the power of two ahead of
    its jit, infinity with scalar 0, so that batches of different lengths
    share one tree program (as `_limb0_fill` does for the limb-0 form)."""
    rows, n = points_rm.shape[:2]
    extra = _tree_npad(n) - n
    inf_rm = jnp.asarray(g.inf_col)[:, 0].reshape(g.rm_shape)
    points = jnp.concatenate(
        [points_rm, jnp.broadcast_to(inf_rm, (rows, extra) + g.rm_shape)],
        axis=1,
    )
    return points, jnp.pad(scalars_std, ((0, 0), (0, extra), (0, 0)))


def _limb0_fill(g: LimbGroup, points_rm, scalars_std, idx, limbs, steps):
    """The limb-0 tree's inputs, padded to the power of two ahead of its
    jit (so that MSMs of different lengths share one tree program): the n
    points, then for each j < 15 the `cap` points 2^(16(j+1)) * points[idx]
    with `limbs[:, j]` as their scalars, then infinity with scalar 0.
    `steps` limbs of the ladder are run; the slots past it hold infinity
    (their limbs are zero by the caller's count)."""
    RR = g.ROWS
    n = points_rm.shape[0]
    cap = idx.shape[0]
    rest = _tree_npad(n) - n - _UPPER_LIMBS * cap
    inf_rm = jnp.asarray(g.inf_col)[:, 0].reshape(g.rm_shape)
    parts_p = [points_rm]
    parts_s = [scalars_std[:, :1]]
    if cap:
        with jax.named_scope("msm.wide"):
            lanes = g.lane_pad(cap)
            base = g.from_rowmajor(jnp.take(points_rm, idx, axis=0))
            # lanes past cap are cut off again below, as in `_batched`
            base = jnp.pad(base, ((0, 0), (0, lanes - cap)))

            def limb(j, carry):
                x, out = carry
                x = jax.lax.fori_loop(
                    0, LIMB_BITS, lambda _, y: g.double(y), x
                )
                return x, jax.lax.dynamic_update_slice(
                    out, x[None], (j, 0, 0)
                )

            out0 = jnp.broadcast_to(
                jnp.asarray(g.inf_col)[None], (_UPPER_LIMBS, RR, lanes)
            )
            _, out = jax.lax.fori_loop(0, steps, limb, (base, out0))
            # (15, ROWS, cap) -> slot j*cap + k, row-major; the tree takes
            # the ladder's [0, 2p) residues as it takes its own sums
            parts_p.append(
                jnp.transpose(out[:, :, :cap], (0, 2, 1)).reshape(
                    (_UPPER_LIMBS * cap,) + g.rm_shape
                )
            )
            parts_s.append(jnp.transpose(limbs).reshape(-1, 1))
    if rest:
        parts_p.append(jnp.broadcast_to(inf_rm, (rest,) + g.rm_shape))
        parts_s.append(jnp.zeros((rest, 1), jnp.uint32))
    return jnp.concatenate(parts_p, axis=0), jnp.concatenate(parts_s, axis=0)


def _msm_tree(g: LimbGroup, points_rm, scalars_std, c: int,
              window_group: int | None,
              affine_min_adds: int = _AFFINE_MIN_ADDS):
    """The tree MSM's body (see `msm_tree`). Its stages sit in
    `jax.named_scope`s (`msm.sort`, `msm.upsweep`, `msm.fenwick`,
    `msm.combine`, `msm.horner`; where a level is affine also
    `msm.normalise`, `msm.upsweep.affine` and, inside both, `msm.inverse`),
    so every device op of the program can be put down to a stage in a
    profiler trace. `affine_min_adds` is the rule's constant, an argument
    so that a test can run affine levels at 16 points."""
    n = points_rm.shape[0]
    npad = _tree_npad(n)
    with jax.named_scope("msm.sort"):  # its inputs: layout, padding, digits
        lm = g.from_rowmajor(points_rm)
        if npad != n:
            lm = jnp.concatenate([lm, g.infinity(npad - n)], axis=1)
        digits = _digits(scalars_std, c)  # (W, n)
        if npad != n:
            digits = jnp.pad(digits, ((0, 0), (0, npad - n)))
    s_all = _tree_window_sums(
        g, lm, digits, c, npad, 1, window_group, affine_min_adds
    )
    with jax.named_scope("msm.horner"):
        out = g.horner(s_all, c)  # (RR, 1)
        return g.to_rowmajor(out)[0]


def _msm_tree_batched(g: LimbGroup, points_rm, scalars_std, c: int,
                      window_group: int | None,
                      affine_min_adds: int = _AFFINE_MIN_ADDS):
    """B tree MSMs of one length as one program (see `msm_tree_batched`):
    points (B, n) + rm_shape, scalars (B, n, k) -> (B,) + rm_shape, n a
    power of two. The B * W windows are what `_msm_tree` runs as its W:
    row b's windows are b * W ... b * W + W - 1, each sorts over row b's
    points, and Horner combines each row's W sums in a lane of its own."""
    RR = g.ROWS
    rows, n = points_rm.shape[:2]
    assert n == _tree_npad(n), "rows come padded (`msm_tree_batched`)"
    with jax.named_scope("msm.sort"):
        lm = g.from_rowmajor(points_rm.reshape((rows * n,) + g.rm_shape))
        digits = _digits(scalars_std.reshape(rows * n, -1), c)  # (W, B*n)
        W = digits.shape[0]
        digits = jnp.transpose(
            digits.reshape(W, rows, n), (1, 0, 2)
        ).reshape(rows * W, n)
    s_all = _tree_window_sums(
        g, lm, digits, c, n, W, window_group, affine_min_adds
    )
    with jax.named_scope("msm.horner"):
        out = g.horner_lanes(s_all.reshape(RR, rows, W), c)  # (RR, B)
        return g.to_rowmajor(out)


def _tree_window_sums(g: LimbGroup, lm, digits, c: int, npad: int,
                      row_windows: int, window_group: int | None,
                      affine_min_adds: int):
    """Every window's sum sum_b b * S_b, (ROWS, windows): the body of
    `_msm_tree` and `_msm_tree_batched` between their inputs and Horner.
    lm: (ROWS, rows * npad) limb-major points, row r's at r * npad;
    digits: (windows, npad), window w over row w // row_windows's points
    (`row_windows` 1 for one MSM: every window over the one row)."""
    RR = g.ROWS
    W_all = digits.shape[0]
    rows = lm.shape[1] // npad
    B = 1 << c
    levels_n = npad.bit_length() - 1  # log2(npad)

    if window_group is None:
        window_group = _tree_window_group(g, npad, W_all, rows)
    # A window group's widest levels are affine (`_affine_depth`); a launch
    # that has one takes its points affine from the start, normalised once
    # (they may come with any Z: the limb-0 fill's ladder points do), so
    # that the sort gathers AROWS rows a point in place of ROWS. With no
    # such level nothing below differs from the all-projective program.
    depths = _affine_depths(W_all, window_group, npad, affine_min_adds)
    affine = any(depths)
    if affine:
        with jax.named_scope("msm.normalise"):
            lm = g.normalise(lm)

    def rows_last(x):  # (rows, Wg, K) -> (Wg * K, rows)
        return jnp.transpose(x, (1, 2, 0)).reshape(-1, x.shape[0])

    sums = []
    for w0, depth in zip(range(0, W_all, window_group), depths):
        dg = digits[w0 : w0 + window_group]  # (Wg, npad)
        Wg = dg.shape[0]
        with jax.named_scope("msm.sort"):
            order = jnp.argsort(dg, axis=-1)
            sortd = jnp.take_along_axis(dg, order, axis=-1)
            ends = jax.vmap(
                lambda row: jnp.searchsorted(
                    row, jnp.arange(B - 1), side="right"
                )
            )(sortd)  # (Wg, B-1)
            if rows > 1:  # each window gathers from its own row's points
                first = np.arange(w0, w0 + Wg) // row_windows * npad
                order = order + jnp.asarray(first, jnp.int32)[:, None]
            gathered = jnp.take(lm, order.reshape(-1), axis=1).reshape(
                lm.shape[0], Wg, npad
            )

        # Up-sweep; each level is also kept transposed to (Wg*K, rows) so
        # the Fenwick node lookups below are contiguous row gathers
        # (embedding-style) instead of rows-way strided minor-axis gathers.
        # Levels 0..depth of `lvls_t` hold affine points where the launch
        # has an affine level (level 0, the gathered points, whatever this
        # group's depth), the rest projective ones.
        with jax.named_scope(
            "msm.upsweep.affine" if depth else "msm.upsweep"
        ):
            lvls_t = [rows_last(gathered)]
            x = gathered
            for _ in range(depth):
                pair = x.reshape(g.AROWS, Wg, x.shape[-1] // 2, 2)
                x = g.affine_add(pair[..., 0], pair[..., 1])
                lvls_t.append(rows_last(x))
        with jax.named_scope("msm.upsweep"):
            if affine:
                x = g.lift(x)
            for _ in range(levels_n - depth):
                k = x.shape[-1]
                pair = x.reshape(RR, Wg, k // 2, 2)
                x = g.add(pair[..., 0], pair[..., 1])
                lvls_t.append(rows_last(x))
            total = x[..., 0:1]  # (RR, Wg, 1)

        # Fenwick prefix at the B-1 bucket boundaries: gather one node per
        # level per boundary, then sum the levels with a pairwise tree.
        with jax.named_scope("msm.fenwick"):
            inf_row = jnp.asarray(g.inf_col)[:, 0]  # (RR,)
            nodes = []
            for d in range(levels_n + 1):
                pd = ends >> d
                takebit = (pd & 1) == 1
                idx = jnp.maximum(pd - 1, 0)
                k = npad >> d
                flat = (jnp.arange(Wg)[:, None] * k + idx).reshape(-1)
                node = jnp.take(lvls_t[d], flat, axis=0).reshape(
                    Wg, B - 1, -1
                )
                if affine and d <= depth:  # an affine level's nodes
                    node = g.lift(node, axis=-1)
                node = jnp.where(takebit[..., None], node, inf_row)
                nodes.append(node)
            D = len(nodes)
            dpad = 1 << (D - 1).bit_length()
            stack = jnp.stack(nodes, axis=0)  # (D, Wg, B-1, RR)
            if dpad != D:
                stack = jnp.concatenate(
                    [
                        stack,
                        jnp.broadcast_to(
                            inf_row, (dpad - D, Wg, B - 1, RR)
                        ),
                    ],
                    axis=0,
                )
            stack = jnp.transpose(stack, (3, 0, 1, 2))  # (RR, dpad, Wg, B-1)
            while stack.shape[1] > 1:
                half = stack.shape[1] // 2
                stack = g.add(stack[:, :half], stack[:, half:])
            acc = stack[:, 0]  # (RR, Wg, B-1)

        # sum_b b * S_b = sum_{j=0..B-2} (total - C_j)
        with jax.named_scope("msm.combine"):
            terms = g.add(jnp.broadcast_to(total, acc.shape), g.neg(acc))
            k = B - 1
            while k > 1:
                if k % 2:
                    terms = jnp.concatenate(
                        [
                            terms,
                            jnp.broadcast_to(
                                jnp.asarray(g.inf_col)[:, :, None],
                                (RR, Wg, 1),
                            ),
                        ],
                        axis=-1,
                    )
                    k += 1
                pair = terms.reshape(RR, Wg, k // 2, 2)
                terms = g.add(pair[..., 0], pair[..., 1])
                k //= 2
            sums.append(terms[..., 0])  # (RR, Wg)
    return jnp.concatenate(sums, axis=1)  # (RR, W_all)


# One body, one program per (static) group as before; the group now also
# shows in the program's name, so a trace tells a G1 launch from a G2 one.
_MSM_TREE_JITS = {
    kind: named_jit(
        f"_msm_tree_jit_{kind}", _msm_tree, static_argnums=(0, 3, 4, 5)
    )
    for kind in ("g1", "g2")
}
# B MSMs of one length as one launch: `_msm_tree`'s window sums over a
# batch axis folded into the windows, and the program that pads its rows.
# Both names keep `_msm_tree_jit_<kind>`.
_MSM_TREE_BATCHED_JITS = {
    kind: named_jit(
        f"_msm_tree_jit_{kind}_batched", _msm_tree_batched,
        static_argnums=(0, 3, 4, 5),
    )
    for kind in ("g1", "g2")
}
_MSM_TREE_BATCHED_PAD_JITS = {
    kind: named_jit(
        f"_msm_tree_jit_{kind}_batched_pad", _batched_pad,
        static_argnums=(0,),
    )
    for kind in ("g1", "g2")
}
# The limb-0 form is the same body under a name of its own (a trace and
# `jax_trace_seconds_total{fn}` tell its launches from a full-width one),
# and the program that makes its padded inputs. Both names keep
# `_msm_tree_jit_<kind>`, which is what the MSM's launches are counted by.
_MSM_LIMB0_JITS = {
    kind: named_jit(
        f"_msm_tree_jit_{kind}_limb0", _msm_tree, static_argnums=(0, 3, 4, 5)
    )
    for kind in ("g1", "g2")
}
_MSM_LIMB0_FILL_JITS = {
    kind: named_jit(
        f"_msm_tree_jit_{kind}_limb0_fill", _limb0_fill, static_argnums=(0,)
    )
    for kind in ("g1", "g2")
}


# ---------------------------------------------------------------------------
# Fixed-scalar ladder application: out[..., o] = sum_k M[o][k] * pts[..., k]
# (the in-the-exponent PSS pack/unpack maps, parallel/pss.py). The ladder
# body is the same batched add/double/select sweep the row-major path runs,
# but on limb-major tensors the adds ride the Pallas kernels.
# ---------------------------------------------------------------------------


def ladder_apply(g: LimbGroup, pts_lm, bits, signs, nbits: int):
    """pts_lm: (ROWS, B, K) limb-major bases (already GLV-expanded when the
    caller uses the endomorphism); bits: (o, K, nbits) uint32; signs:
    (o, K) bool or None. Returns (ROWS, B, o) limb-major points."""
    RR = g.ROWS
    B, K = pts_lm.shape[1], pts_lm.shape[2]
    o = bits.shape[0]
    acc0 = jnp.broadcast_to(
        jnp.asarray(g.inf_col).reshape(RR, 1, 1, 1), (RR, B, o, K)
    )

    def body(i, state):
        acc, base = state
        bit = bits[..., i]  # (o, K)
        addend = base[:, :, None, :]  # (ROWS, B, 1, K)
        if signs is not None:
            # (o, K) broadcasts against (ROWS, B, 1, K) -> (ROWS, B, o, K)
            addend = jnp.where(signs, g.neg(addend), addend)
        cand = g.add(acc, jnp.broadcast_to(addend, acc.shape))
        acc = jnp.where(bit == 1, cand, acc)
        return acc, g.double(base)

    acc, _ = jax.lax.fori_loop(0, nbits, body, (acc0, pts_lm))
    # pairwise tree-sum over the K axis (K is a power of two in practice;
    # pad with infinity otherwise)
    k = K
    x = acc
    while k > 1:
        if k % 2:
            x = jnp.concatenate(
                [x, jnp.broadcast_to(
                    jnp.asarray(g.inf_col).reshape(RR, 1, 1, 1),
                    (RR, B, o, 1))],
                axis=-1,
            )
            k += 1
        pair = x.reshape(RR, B, o, k // 2, 2)
        x = g.add(pair[..., 0], pair[..., 1])
        k //= 2
    return x[..., 0]  # (ROWS, B, o)


# eager fori_loop dispatch is an XLA:CPU crash class in this environment
# (backend_compile_and_load segfault late in a long-lived process): always
# enter the ladder through this jitted wrapper
ladder_apply_jit = jax.jit(ladder_apply, static_argnums=(0, 4))
