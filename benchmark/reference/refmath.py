"""THE BENCHMARK'S COPY of distributed_groth16_tpu/ops/refmath.py as of PR 21 (commit
c67d115), kept here so that no later change to the program can move the
yardstick. Do not import the program's module in its place, and do not
edit this file in a PR that claims a gain. Original docstring follows.

Pure-Python reference math for BN254 — the ground truth every JAX/Pallas
kernel is differentially tested against (mirrors the reference's strategy of
checking each distributed kernel against its single-node arkworks counterpart,
e.g. dist-primitives/src/dfft/mod.rs:304, dist-primitives/examples/dmsm_test.rs).

Everything here is host-side Python bigint code: slow, simple, obviously
correct. Device code lives in ops/field.py, ops/ntt.py, ops/curve.py.
"""

from __future__ import annotations

from .constants import (
    FR_GENERATOR,
    FR_TWO_ADICITY,
    G1_B,
    G2_B,
    Q,
    R,
)

# ---------------------------------------------------------------------------
# Prime field helpers (work for any modulus)
# ---------------------------------------------------------------------------


def finv(x: int, p: int) -> int:
    return pow(x, p - 2, p)


def batch_inv(xs, p: int):
    """Montgomery batch inversion."""
    n = len(xs)
    prefix = [1] * (n + 1)
    for i, x in enumerate(xs):
        prefix[i + 1] = prefix[i] * x % p
    inv_all = finv(prefix[n], p)
    out = [0] * n
    for i in range(n - 1, -1, -1):
        out[i] = prefix[i] * inv_all % p
        inv_all = inv_all * xs[i] % p
    return out


# ---------------------------------------------------------------------------
# Radix-2 evaluation domain over Fr — ark-poly semantics
# ---------------------------------------------------------------------------


class Domain:
    """Mirror of ark-poly Radix2EvaluationDomain — over BN254 Fr by
    default, or any prime scalar field via (modulus, generator) (the
    reference instantiates domains over BLS12-377 Fr too,
    dist-primitives/examples/dmsm_bench.rs:46).

    fft(coeffs)  : evaluate at offset * w^i for i in 0..size
    ifft(evals)  : inverse; inputs shorter than size are zero-padded (ark
                   semantics: fft_in_place resizes with zeros).
    get_coset(g) : same group generator, offset multiplied in.
    """

    def __init__(self, size: int, offset: int = 1,
                 modulus: int = R, generator: int = FR_GENERATOR):
        assert size & (size - 1) == 0, "domain size must be a power of two"
        r = modulus
        two_adicity = ((r - 1) & -(r - 1)).bit_length() - 1
        assert size <= (1 << two_adicity)
        self.size = size
        self.r = r
        self.generator = generator
        self.offset = offset % r
        self.group_gen = pow(generator, (r - 1) // size, r)
        self.group_gen_inv = finv(self.group_gen, r)
        self.size_inv = finv(size, r)
        self.offset_inv = finv(self.offset, r) if offset != 1 else 1

    def get_coset(self, offset: int) -> "Domain":
        return Domain(self.size, offset * self.offset % self.r,
                      self.r, self.generator)

    def elements(self):
        w, acc = self.group_gen, self.offset
        out = []
        for _ in range(self.size):
            out.append(acc)
            acc = acc * w % self.r
        return out

    def _pad(self, v):
        v = [x % self.r for x in v]
        assert len(v) <= self.size
        return v + [0] * (self.size - len(v))

    def fft(self, coeffs):
        r = self.r
        c = self._pad(coeffs)
        if self.offset != 1:
            mul, off = 1, self.offset
            for i in range(self.size):
                c[i] = c[i] * mul % r
                mul = mul * off % r
        return _ntt(c, self.group_gen, r)

    def ifft(self, evals):
        r = self.r
        e = self._pad(evals)
        c = _ntt(e, self.group_gen_inv, r)
        c = [x * self.size_inv % r for x in c]
        if self.offset != 1:
            mul, off_inv = 1, self.offset_inv
            for i in range(self.size):
                c[i] = c[i] * mul % r
                mul = mul * off_inv % r
        return c


def bit_reverse_permute(v):
    n = len(v)
    logn = n.bit_length() - 1
    out = list(v)
    for i in range(n):
        j = int(format(i, f"0{logn}b")[::-1], 2) if logn else 0
        if j > i:
            out[i], out[j] = out[j], out[i]
    return out


def _ntt(v, w, r: int = R):
    """Iterative radix-2 Cooley-Tukey NTT (DIT, natural in/natural out)."""
    n = len(v)
    v = bit_reverse_permute(v)
    span = 1
    while span < n:
        wspan = pow(w, n // (2 * span), r)
        for start in range(0, n, 2 * span):
            wj = 1
            for j in range(span):
                a = v[start + j]
                b = v[start + j + span] * wj % r
                v[start + j] = (a + b) % r
                v[start + j + span] = (a - b) % r
                wj = wj * wspan % r
        span *= 2
    return v


# ---------------------------------------------------------------------------
# Fq2 arithmetic (for G2): Fq[u] / (u^2 + 1)
# ---------------------------------------------------------------------------


def fq2_add(a, b):
    return ((a[0] + b[0]) % Q, (a[1] + b[1]) % Q)


def fq2_sub(a, b):
    return ((a[0] - b[0]) % Q, (a[1] - b[1]) % Q)


def fq2_mul(a, b):
    # (a0 + a1 u)(b0 + b1 u) = a0b0 - a1b1 + (a0b1 + a1b0) u
    t0 = a[0] * b[0] % Q
    t1 = a[1] * b[1] % Q
    return ((t0 - t1) % Q, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % Q)


def fq2_sq(a):
    # (a0 + a1 u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u
    t = a[0] * a[1] % Q
    return ((a[0] + a[1]) * (a[0] - a[1]) % Q, 2 * t % Q)


def fq2_neg(a):
    return ((-a[0]) % Q, (-a[1]) % Q)


def fq2_scalar(a, k):
    return (a[0] * k % Q, a[1] * k % Q)


def fq2_inv(a):
    # 1/(a0 + a1 u) = (a0 - a1 u) / (a0^2 + a1^2)
    norm = (a[0] * a[0] + a[1] * a[1]) % Q
    ninv = finv(norm, Q)
    return (a[0] * ninv % Q, (-a[1]) * ninv % Q)


def fq2_conj(a):
    return (a[0], (-a[1]) % Q)


FQ2_ZERO = (0, 0)
FQ2_ONE = (1, 0)


# ---------------------------------------------------------------------------
# Short Weierstrass curve ops, generic over the coordinate field.
# Points are affine tuples (x, y) or None for infinity.
# ---------------------------------------------------------------------------


class _CurveOps:
    def __init__(self, add, sub, mul, sq, neg, inv, scalar, zero, one, b,
                 order=None):
        self.fadd, self.fsub, self.fmul, self.fsq = add, sub, mul, sq
        self.fneg, self.finv, self.fscalar = neg, inv, scalar
        self.zero, self.one, self.b = zero, one, b
        self.order = order if order is not None else R  # scalar group order

    def is_on_curve(self, p) -> bool:
        if p is None:
            return True
        x, y = p
        lhs = self.fsq(y)
        rhs = self.fadd(self.fmul(self.fsq(x), x), self.b)
        return lhs == rhs

    def add(self, p, q):
        if p is None:
            return q
        if q is None:
            return p
        x1, y1 = p
        x2, y2 = q
        if x1 == x2:
            if self.fadd(y1, y2) == self.zero:
                return None
            return self.double(p)
        lam = self.fmul(self.fsub(y2, y1), self.finv(self.fsub(x2, x1)))
        x3 = self.fsub(self.fsub(self.fsq(lam), x1), x2)
        y3 = self.fsub(self.fmul(lam, self.fsub(x1, x3)), y1)
        return (x3, y3)

    def double(self, p):
        if p is None:
            return None
        x, y = p
        if y == self.zero:
            return None
        lam = self.fmul(self.fscalar(self.fsq(x), 3), self.finv(self.fscalar(y, 2)))
        x3 = self.fsub(self.fsq(lam), self.fscalar(x, 2))
        y3 = self.fsub(self.fmul(lam, self.fsub(x, x3)), y)
        return (x3, y3)

    def neg(self, p):
        if p is None:
            return None
        return (p[0], self.fneg(p[1]))

    def scalar_mul(self, p, k: int):
        k %= self.order
        acc, base = None, p
        while k:
            if k & 1:
                acc = self.add(acc, base)
            base = self.double(base)
            k >>= 1
        return acc

    def msm(self, points, scalars):
        acc = None
        for p, s in zip(points, scalars):
            acc = self.add(acc, self.scalar_mul(p, s))
        return acc


def _fq_scalar(a, k):
    return a * k % Q


G1 = _CurveOps(
    add=lambda a, b: (a + b) % Q,
    sub=lambda a, b: (a - b) % Q,
    mul=lambda a, b: a * b % Q,
    sq=lambda a: a * a % Q,
    neg=lambda a: (-a) % Q,
    inv=lambda a: finv(a, Q),
    scalar=_fq_scalar,
    zero=0,
    one=1,
    b=G1_B,
)

G2 = _CurveOps(
    add=fq2_add,
    sub=fq2_sub,
    mul=fq2_mul,
    sq=fq2_sq,
    neg=fq2_neg,
    inv=fq2_inv,
    scalar=fq2_scalar,
    zero=FQ2_ZERO,
    one=FQ2_ONE,
    b=G2_B,
)
