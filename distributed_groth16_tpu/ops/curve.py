"""Branchless elliptic-curve arithmetic for BN254 G1/G2 on JAX/TPU.

Points live on device in homogeneous projective coordinates (X : Y : Z) as
uint32 limb tensors — G1: (..., 3, 16), G2: (..., 3, 2, 16) — using the
complete addition/doubling formulas of Renes–Costello–Batina 2016 for short
Weierstrass curves with a = 0 (algorithms 7 and 9). Complete formulas have no
data-dependent branches: one fused vector program handles generic addition,
doubling, and the point at infinity (0 : 1 : 0), which is exactly what XLA
wants — static shapes, no `lax.cond` per lane.

Replaces the reference's use of arkworks ark-ec short_weierstrass group ops
(consumed throughout dist-primitives/src/dmsm/mod.rs and groth16/src/prove.rs);
there is no reference file to translate — this layer is curve math designed
for the TPU VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .constants import G1_B, G2_B, N_LIMBS, Q, R
from .field import fq, fq2


class CurvePoints:
    """Vectorized projective point ops over a generic coordinate field.

    `F` is a PrimeField (G1) or Fq2Ops (G2); `elem_shape` is the trailing
    shape of one coordinate — (16,) for Fq, (2, 16) for Fq2. A point array
    has shape (..., 3) + elem_shape.
    """

    def __init__(self, field, b, elem_shape, glv=None, scalar_order=None):
        self.F = field
        # order of the scalar group (Fr); BN254 by default
        self.r = scalar_order if scalar_order is not None else R
        self.elem_shape = elem_shape
        self.coord_axes = len(elem_shape)
        b3_int = self._triple_int(b)
        self.b = self._const(b)  # b in Montgomery form, device const
        self.b3 = self._const(b3_int)  # 3*b in Montgomery form, device const
        z, o = field.consts(())
        self._zero_c, self._one_c = z, o
        # GLV endomorphism parameters (ops/glv.py), or None when the curve
        # has no cheap endomorphism wired up (G2): fixed-scalar ladders then
        # fall back to full-width double-and-add.
        self.glv = glv
        self._beta_c = self._const(glv.beta) if glv is not None else None
        # jit the big combinational kernels once per instance. The
        # scan-shaped ones (ladders, sequential sums) MUST be jitted:
        # eagerly-dispatched scan/fori executables are an XLA:CPU crash
        # class here (backend_compile_and_load segfault once enough
        # executables are live in a long-lived process).
        self.add = jax.jit(self.add)
        self.double = jax.jit(self.double)
        self.scalar_mul_bits = jax.jit(self.scalar_mul_bits)
        self.sum_sequential = jax.jit(
            self.sum_sequential, static_argnames=("axis",)
        )

    def _triple_int(self, b):
        p = self.F.p if hasattr(self.F, "p") else self.F.fq.p
        if isinstance(b, tuple):
            return tuple(3 * c % p for c in b)
        return 3 * b % p

    def _const(self, v):
        return self.F.encode([v])[0]

    # -- construction / conversion -------------------------------------------

    def encode(self, points) -> jnp.ndarray:
        """List of affine (x, y) tuples / None (infinity) -> device array.

        For G2, coordinates are themselves (c0, c1) pairs.
        """
        flat = []
        for p in points:
            if p is None:
                if self.coord_axes == 1:
                    flat.append((0, 1, 0))
                else:
                    flat.append(((0, 0), (1, 0), (0, 0)))
            else:
                x, y = p
                if self.coord_axes == 1:
                    flat.append((x, y, 1))
                else:
                    flat.append((x, y, (1, 0)))
        return self.F.encode(flat)

    def decode(self, pts):
        """Device projective points -> list of affine int tuples / None."""
        arr = self.F.decode(pts)
        arr = np.asarray(arr, dtype=object)
        batch = arr.shape[: arr.ndim - 1 - (self.coord_axes - 1)]
        # arr has shape batch + (3,) [+ (2,)]
        flat = arr.reshape((-1, 3) + ((2,) if self.coord_axes == 2 else ()))
        out = []
        from .refmath import finv

        # the curve's OWN base modulus (refmath's fq2_* are BN254-bound, so
        # the Fq2 normalization below is done locally mod p_mod — decoding
        # a BLS12-381 G2 point through BN254 ops silently garbled it)
        p_mod = self.F.p if hasattr(self.F, "p") else self.F.fq.p
        from .primemath import fq2_inv as f2inv, fq2_mul as f2mul

        for row in flat:
            if self.coord_axes == 1:
                x, y, z = int(row[0]), int(row[1]), int(row[2])
                if z == 0:
                    out.append(None)
                else:
                    zi = finv(z, p_mod)
                    out.append((x * zi % p_mod, y * zi % p_mod))
            else:
                x = (int(row[0][0]), int(row[0][1]))
                y = (int(row[1][0]), int(row[1][1]))
                z = (int(row[2][0]), int(row[2][1]))
                if z == (0, 0):
                    out.append(None)
                else:
                    zi = f2inv(z, p_mod)
                    out.append((f2mul(x, zi, p_mod), f2mul(y, zi, p_mod)))
        if batch == ():
            return out[0]
        if len(batch) == 1:
            return out
        obj = np.empty(len(out), dtype=object)
        for i, v in enumerate(out):
            obj[i] = v
        return obj.reshape(batch).tolist()

    def infinity(self, shape=()):
        """(0 : 1 : 0) broadcast to the given batch shape."""
        z = jnp.broadcast_to(self._zero_c, shape + (1,) + self.elem_shape)
        o = jnp.broadcast_to(self._one_c, shape + (1,) + self.elem_shape)
        return jnp.concatenate([z, o, z], axis=-1 - self.coord_axes)

    def _coords(self, p):
        ax = -1 - self.coord_axes
        x = jnp.take(p, 0, axis=ax)
        y = jnp.take(p, 1, axis=ax)
        z = jnp.take(p, 2, axis=ax)
        return x, y, z

    def _pack(self, x, y, z):
        return jnp.stack([x, y, z], axis=-1 - self.coord_axes)

    def is_infinity(self, p):
        _, _, z = self._coords(p)
        if self.coord_axes == 1:
            return jnp.all(z == 0, axis=-1)
        return jnp.all(z == 0, axis=(-1, -2))

    # -- group law (complete, branchless) ------------------------------------

    def _mul_many(self, lhs, rhs):
        """Stacked field muls: one mul call over a new leading axis.

        Independent products inside the group-law formulas are batched into a
        single Montgomery multiply so the compiled graph holds one CIOS loop
        per *round* of the formula instead of one per product — ~4x smaller
        graphs and better VPU utilization at small batch sizes.
        """
        shape = jnp.broadcast_shapes(*(x.shape for x in lhs), *(x.shape for x in rhs))
        lhs = [jnp.broadcast_to(x, shape) for x in lhs]
        rhs = [jnp.broadcast_to(x, shape) for x in rhs]
        return self.F.mul(jnp.stack(lhs, axis=0), jnp.stack(rhs, axis=0))

    def add(self, p, q):
        """Complete projective addition (RCB16 algorithm 7, a = 0),
        regrouped into 3 stacked multiply rounds."""
        F = self.F
        X1, Y1, Z1 = self._coords(p)
        X2, Y2, Z2 = self._coords(q)
        # round 1: all products of input coordinates
        r1 = self._mul_many(
            [X1, Y1, Z1, F.add(X1, Y1), F.add(Y1, Z1), F.add(X1, Z1)],
            [X2, Y2, Z2, F.add(X2, Y2), F.add(Y2, Z2), F.add(X2, Z2)],
        )
        t0, t1, t2 = r1[0], r1[1], r1[2]
        t3 = F.sub(r1[3], F.add(t0, t1))  # X1Y2 + X2Y1
        t4 = F.sub(r1[4], F.add(t1, t2))  # Y1Z2 + Y2Z1
        ty = F.sub(r1[5], F.add(t0, t2))  # X1Z2 + X2Z1
        t0 = F.add(F.add(t0, t0), t0)  # 3 X1X2
        # round 2: multiplications by the constant b3
        r2 = self._mul_many([t2, ty], [self.b3, self.b3])
        t2b, yb = r2[0], r2[1]
        Z3 = F.add(t1, t2b)
        t1 = F.sub(t1, t2b)
        # round 3: the six cross products forming the output coordinates
        r3 = self._mul_many(
            [t3, t4, yb, t1, t0, Z3], [t1, yb, t0, Z3, t3, t4]
        )
        X3 = F.sub(r3[0], r3[1])
        Y3 = F.add(r3[2], r3[3])
        Z3 = F.add(r3[5], r3[4])
        return self._pack(X3, Y3, Z3)

    def double(self, p):
        """Complete projective doubling (RCB16 algorithm 9, a = 0),
        regrouped into 3 stacked multiply rounds."""
        F = self.F
        X, Y, Z = self._coords(p)
        r1 = self._mul_many([Y, Y, Z, X], [Y, Z, Z, Y])
        t0, t1, t2, txy = r1[0], r1[1], r1[2], r1[3]
        z8 = F.add(t0, t0)
        z8 = F.add(z8, z8)
        z8 = F.add(z8, z8)  # 8 Y^2
        (t2b,) = self._mul_many([t2], [self.b3])
        y3a = F.add(t0, t2b)
        t0 = F.sub(t0, F.add(F.add(t2b, t2b), t2b))  # Y^2 - 3 b3 Z^2
        r3 = self._mul_many([t2b, t1, t0, t0], [z8, z8, y3a, txy])
        X3g, Z3, Y3m, X3m = r3[0], r3[1], r3[2], r3[3]
        Y3 = F.add(X3g, Y3m)
        X3 = F.add(X3m, X3m)
        return self._pack(X3, Y3, Z3)

    def neg(self, p):
        X, Y, Z = self._coords(p)
        return self._pack(X, self.F.neg(Y), Z)

    def endo(self, p):
        """The GLV endomorphism phi(X:Y:Z) = (beta*X : Y : Z) with
        phi(P) = lambda*P (ops/glv.py). Only for curves with `glv` set."""
        X, Y, Z = self._coords(p)
        return self._pack(self.F.mul(X, self._beta_c), Y, Z)

    def select(self, cond, p, q):
        """where(cond, p, q) with cond of batch shape."""
        c = cond
        for _ in range(self.coord_axes + 1):
            c = c[..., None]
        return jnp.where(c, p, q)

    # -- derived ops ----------------------------------------------------------

    def scalar_mul_bits(self, p, bits):
        """p * k with k given as a (..., nbits) uint32 bit array (LSB first),
        batch-broadcastable against p's batch shape. Double-and-add, fixed
        trip count — one compiled program for any scalar."""
        nbits = bits.shape[-1]
        acc = self.infinity(p.shape[: -1 - self.coord_axes])
        acc = jnp.broadcast_to(
            acc,
            jnp.broadcast_shapes(p.shape[: -1 - self.coord_axes], bits.shape[:-1])
            + (3,)
            + self.elem_shape,
        )
        p = jnp.broadcast_to(p, acc.shape)

        def body(i, state):
            acc, base = state
            bit = bits[..., i]
            acc = self.select(bit == 1, self.add(acc, base), acc)
            return acc, self.double(base)

        acc, _ = jax.lax.fori_loop(0, nbits, body, (acc, p))
        return acc

    def sum(self, pts, axis=0):
        """Tree-reduce point sum along a batch axis (log n add rounds)."""
        ax = axis % (pts.ndim - 1 - self.coord_axes)
        n = pts.shape[ax]
        pts = jnp.moveaxis(pts, ax, 0)
        while n > 1:
            half = n // 2
            lo = pts[: half]
            hi = pts[half : 2 * half]
            s = self.add(lo, hi)
            if n % 2:
                s = jnp.concatenate([s, pts[2 * half :][:1]], axis=0)
            pts = s
            n = pts.shape[0]
        return pts[0]

    def sum_sequential(self, pts, axis=0):
        """Point sum along an axis via fori_loop accumulation — ONE add
        instantiation versus the tree's log n. Each distinct add/double
        instance costs seconds of XLA:CPU compile (the mesh-prover dryrun
        blowup), so small-n reductions inside large
        traced programs should prefer this; large-n hot-path reductions
        keep the parallel tree of `sum`."""
        ax = axis % (pts.ndim - 1 - self.coord_axes)
        pts = jnp.moveaxis(pts, ax, 0)
        n = pts.shape[0]
        acc0 = jnp.broadcast_to(self.infinity(), pts.shape[1:])

        def body(i, acc):
            return self.add(acc, pts[i])

        return jax.lax.fori_loop(0, n, body, acc0)

    def to_affine(self, pts):
        """Projective -> affine (x, y) coords on device; infinity -> (0, 0).

        Returns (..., 2) + elem_shape. One batched (Montgomery-trick) field
        inversion over the flattened batch: ~3n muls + one Fermat exp.
        """
        X, Y, Z = self._coords(pts)
        batch = Z.shape[: Z.ndim - self.coord_axes]
        nl = self.elem_shape[-1]  # limb count is field-dependent (BN254=16,
        # BLS12-377=24); hard-coding N_LIMBS here silently garbled any
        # non-16-limb curve's coordinates
        if self.coord_axes == 1:
            zinv = self.F.batch_inv(Z.reshape((-1, nl))).reshape(Z.shape)
        else:
            # Fq2 batch inverse via the norm map: 1/(a0+a1 u) =
            # (a0 - a1 u) / (a0^2 + a1^2), with the Fq norms batch-inverted.
            f = self.F.fq
            a0 = Z[..., 0, :].reshape((-1, nl))
            a1 = Z[..., 1, :].reshape((-1, nl))
            norm = f.add(f.sqr(a0), f.sqr(a1))
            ninv = f.batch_inv(norm)
            zinv = jnp.stack(
                [f.mul(a0, ninv), f.neg(f.mul(a1, ninv))], axis=-2
            ).reshape(batch + (2, nl))
        x = self.F.mul(X, zinv)
        y = self.F.mul(Y, zinv)
        return jnp.stack([x, y], axis=-1 - self.coord_axes)

    def from_affine(self, aff, inf_mask=None):
        """(..., 2)+elem affine coords (+ optional infinity mask) -> projective."""
        ax = -1 - self.coord_axes
        x = jnp.take(aff, 0, axis=ax)
        y = jnp.take(aff, 1, axis=ax)
        one = jnp.broadcast_to(self._one_c, x.shape)
        p = self._pack(x, y, one)
        if inf_mask is not None:
            p = self.select(inf_mask, self.infinity(x.shape[: ax + 1 or None]), p)
        return p

    def is_on_curve(self, p):
        """Projective on-curve check: Y^2 Z == X^3 + b Z^3 (vacuous at inf)."""
        F = self.F
        X, Y, Z = self._coords(p)
        lhs = F.mul(F.mul(Y, Y), Z)
        z3 = F.mul(F.mul(Z, Z), Z)
        rhs = F.add(F.mul(F.mul(X, X), X), F.mul(self.b, z3))
        return F.eq(lhs, rhs)

    def eq(self, p, q):
        """Projective equality: X1 Z2 == X2 Z1 and Y1 Z2 == Y2 Z1."""
        F = self.F
        X1, Y1, Z1 = self._coords(p)
        X2, Y2, Z2 = self._coords(q)
        ex = F.eq(F.mul(X1, Z2), F.mul(X2, Z1))
        ey = F.eq(F.mul(Y1, Z2), F.mul(Y2, Z1))
        i1, i2 = self.is_infinity(p), self.is_infinity(q)
        both_inf = jnp.logical_and(i1, i2)
        one_inf = jnp.logical_xor(i1, i2)
        return jnp.logical_or(both_inf, jnp.logical_and(ex & ey, ~one_inf))


@functools.cache
def g1() -> CurvePoints:
    from .glv import bn254_g1_glv

    return CurvePoints(fq(), G1_B, (N_LIMBS,), glv=bn254_g1_glv())


@functools.cache
def g2() -> CurvePoints:
    return CurvePoints(fq2(), G2_B, (2, N_LIMBS))


def fixed_scalar_ladder_tensors(curve: CurvePoints, scalars):
    """Ladder tensors for a flat list of FIXED Fr scalars: (bits, signs, nbits).

    The shared precomputation of every fixed-scalar point transform
    (parallel/pss.py dense matrices, parallel/pointntt.py twiddles). Under
    GLV (curve.glv set) each scalar splits into two signed ~129-bit halves
    applied to {P, phi(P)}: bits (2, S, nbits) uint32, signs (2, S) bool,
    part 0 = k1 on P, part 1 = k2 on phi(P). Without GLV: bits
    (1, S, nbits=256), signs None.
    """
    from .constants import to_limbs

    def raw_limbs(vals):
        # NOT encode_scalars_std: that reduces mod BN254 Fr, which silently
        # corrupts scalars of a larger-order curve (r381 is 255-bit). The
        # values here are already reduced mod curve.r.
        return jnp.asarray(
            np.array([to_limbs(v) for v in vals], dtype=np.uint32)
        )

    s = [v % curve.r for v in scalars]
    n = len(s)
    if curve.glv is not None:
        nbits = curve.glv.max_bits
        halves = [curve.glv.decompose(v) for v in s]
        flat = [abs(h[p]) for p in (0, 1) for h in halves]
        sgn = [h[p] < 0 for p in (0, 1) for h in halves]
        bits = scalar_bits(raw_limbs(flat), nbits).reshape(2, n, nbits)
        signs = jnp.asarray(np.array(sgn, dtype=bool).reshape(2, n))
        return bits, signs, nbits
    bits = scalar_bits(raw_limbs(s), 256).reshape(1, n, 256)
    return bits, None, 256


def scalar_bits(scalars, nbits: int = 256) -> jnp.ndarray:
    """Standard-form scalar limb array (..., 16) -> bit array (..., nbits).

    Scalars must be in standard (non-Montgomery) form; the decomposition is
    pure limb shifting, independent of any field.
    """
    from .constants import LIMB_BITS

    limb = scalars[..., jnp.arange(nbits) // LIMB_BITS]
    return (limb >> (jnp.arange(nbits) % LIMB_BITS)) & 1
