"""PSS tests mirroring the reference's secret-sharing/src/pss.rs:152-241
(roundtrip, share-wise multiplication, randomized packing) plus the
group-element packing of dmsm/mod.rs:100-193."""

import random

import numpy as np
import pytest

from distributed_groth16_tpu.ops import refmath as rm
from distributed_groth16_tpu.ops.constants import G1_GENERATOR, R
from distributed_groth16_tpu.ops.curve import g1
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.parallel.pss import (
    PackedSharingParams,
    pack_host,
    unpack2_host,
    unpack_host,
)


@pytest.mark.parametrize("l", [2, 4])
def test_initialize(l):
    pp = PackedSharingParams(l)
    assert pp.t == l - 1 and pp.n == 4 * l
    assert pp.share.size == pp.n
    assert pp.secret.size == l + pp.t + 1
    assert pp.secret2.size == 2 * (l + pp.t + 1)


@pytest.mark.parametrize("l", [2, 4])
def test_pack_unpack_roundtrip_device(l):
    pp = PackedSharingParams(l)
    F = fr()
    rng = random.Random(17)
    batch = 3
    secrets = [[rng.randrange(R) for _ in range(l)] for _ in range(batch)]
    shares = pp.pack_from_public(F.encode(secrets))
    assert shares.shape == (batch, pp.n, 16)
    back = F.decode(pp.unpack(shares))
    assert [[int(x) for x in row] for row in back] == secrets
    # cross-check device pack against host ground truth
    host_shares = [pack_host(pp, s) for s in secrets]
    dev_shares = F.decode(shares)
    assert [[int(x) for x in row] for row in dev_shares] == host_shares


def test_sharewise_multiplication():
    """share(x) * share(y) unpacks (via unpack2) to x*y elementwise."""
    l = 2
    pp = PackedSharingParams(l)
    F = fr()
    rng = random.Random(5)
    xs = [rng.randrange(R) for _ in range(l)]
    ys = [rng.randrange(R) for _ in range(l)]
    sx = pp.pack_from_public(F.encode([xs]))
    sy = pp.pack_from_public(F.encode([ys]))
    prod = F.mul(sx, sy)
    back = F.decode(pp.unpack2(prod))[0]
    assert [int(v) for v in back] == [x * y % R for x, y in zip(xs, ys)]
    # host ground truth agrees
    hx, hy = pack_host(pp, xs), pack_host(pp, ys)
    hp = [a * b % R for a, b in zip(hx, hy)]
    assert unpack2_host(pp, hp) == [x * y % R for x, y in zip(xs, ys)]


def test_pack_rand_roundtrip():
    l = 2
    pp = PackedSharingParams(l)
    F = fr()
    rng = random.Random(23)
    xs = [rng.randrange(R) for _ in range(l)]
    shares = pp.pack_from_public_rand(
        F.encode([xs]), np.random.default_rng(42)
    )
    back = F.decode(pp.unpack(shares))[0]
    assert [int(v) for v in back] == xs
    # randomized packing differs from deterministic packing
    det = F.decode(pp.pack_from_public(F.encode([xs])))[0]
    assert [int(v) for v in F.decode(shares)[0]] != [int(v) for v in det]


def test_unpack_host_matches_device_unpack_of_host_shares():
    l = 4
    pp = PackedSharingParams(l)
    rng = random.Random(31)
    xs = [rng.randrange(R) for _ in range(l)]
    shares = pack_host(pp, xs)
    assert unpack_host(pp, shares) == xs


def test_packexp_unpackexp_group_elements():
    """Pack G1 points 'in the exponent' and unpack them back
    (dmsm/mod.rs packexp_from_public/unpackexp semantics)."""
    l = 2
    pp = PackedSharingParams(l)
    C = g1()
    rng = random.Random(77)
    ks = [rng.randrange(1, R) for _ in range(l)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks]
    packed = pp.packexp_from_public(C, C.encode(pts))
    assert packed.shape == (pp.n, 3, 16)
    # shares in the exponent match host-side scalar relation:
    # packed[p] = sum_i M[p][i] * pts[i]  <=>  g^(pack of exponents)
    exp_shares = pack_host(pp, ks)
    expect = [rm.G1.scalar_mul(G1_GENERATOR, e) for e in exp_shares]
    assert C.decode(packed) == expect
    back = pp.unpackexp(C, packed)
    assert C.decode(back) == pts


def test_packexp_unpackexp_ntt_matches_dense():
    """The point-domain NTT path (reference dmsm/mod.rs:7-68 algorithm)
    computes the same maps as the dense GLV ladder."""
    l = 2
    pp = PackedSharingParams(l)
    C = g1()
    rng = random.Random(99)
    ks = [rng.randrange(1, R) for _ in range(l)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks]
    packed = pp.packexp_from_public(C, C.encode(pts), method="ntt")
    exp_shares = pack_host(pp, ks)
    expect = [rm.G1.scalar_mul(G1_GENERATOR, e) for e in exp_shares]
    assert C.decode(packed) == expect
    back = pp.unpackexp(C, packed, method="ntt")
    assert C.decode(back) == pts
    # degree2 variant
    xs = [rng.randrange(R) for _ in range(l)]
    ys = [rng.randrange(R) for _ in range(l)]
    hx, hy = pack_host(pp, xs), pack_host(pp, ys)
    prod = [a * b % R for a, b in zip(hx, hy)]
    pts2 = [rm.G1.scalar_mul(G1_GENERATOR, e) for e in prod]
    back2 = pp.unpackexp(C, C.encode(pts2), degree2=True, method="ntt")
    expect2 = [
        rm.G1.scalar_mul(G1_GENERATOR, x * y % R) for x, y in zip(xs, ys)
    ]
    assert C.decode(back2) == expect2


def test_packexp_g2_no_glv():
    """G2 has no GLV wired up: the dense ladder falls back to full-width
    double-and-add and still packs/unpacks correctly in the exponent."""
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g2

    l = 2
    pp = PackedSharingParams(l)
    C = g2()
    rng = random.Random(111)
    ks = [rng.randrange(1, R) for _ in range(l)]
    pts = [rm.G2.scalar_mul(G2_GENERATOR, k) for k in ks]
    packed = pp.packexp_from_public(C, C.encode(pts))
    exp_shares = pack_host(pp, ks)
    expect = [rm.G2.scalar_mul(G2_GENERATOR, e) for e in exp_shares]
    assert C.decode(packed) == expect


def test_glv_decomposition():
    from distributed_groth16_tpu.ops.glv import bn254_g1_glv

    g = bn254_g1_glv()
    rng = random.Random(7)
    assert (g.lam * g.lam + g.lam + 1) % R == 0
    for _ in range(50):
        k = rng.randrange(R)
        k1, k2 = g.decompose(k)
        assert (k1 + k2 * g.lam - k) % R == 0
        assert abs(k1).bit_length() <= g.max_bits
        assert abs(k2).bit_length() <= g.max_bits
    # endomorphism really is multiplication by lambda on the curve
    p = rm.G1.scalar_mul(G1_GENERATOR, 12345)
    assert rm.G1.scalar_mul(p, g.lam) == (g.beta * p[0] % rm.Q, p[1])


def test_unpackexp_degree2():
    """unpackexp(degree2=True) inverts packing on the secret2 layout: a
    product of two degree-(t+l) sharings unpacks in the exponent."""
    l = 2
    pp = PackedSharingParams(l)
    C = g1()
    rng = random.Random(88)
    xs = [rng.randrange(R) for _ in range(l)]
    ys = [rng.randrange(R) for _ in range(l)]
    hx, hy = pack_host(pp, xs), pack_host(pp, ys)
    prod_shares = [a * b % R for a, b in zip(hx, hy)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, e) for e in prod_shares]
    back = pp.unpackexp(C, C.encode(pts), degree2=True)
    expect = [
        rm.G1.scalar_mul(G1_GENERATOR, x * y % R) for x, y in zip(xs, ys)
    ]
    assert C.decode(back) == expect


def test_packexp_limb_ladder_matches_rowmajor(monkeypatch):
    """The limb-major Pallas ladder path (DG16_FORCE_TREE_MSM routes it on
    CPU too) must equal the row-major dense ladder bit-for-bit — G1 (GLV,
    signed halves) and G2 (no GLV)."""
    from distributed_groth16_tpu.ops.curve import g2
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR

    l = 2
    pp = PackedSharingParams(l)
    rng = random.Random(99)
    ks = [rng.randrange(1, R) for _ in range(l)]

    C = g1()
    pts1 = C.encode([rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks])
    C2 = g2()
    pts2 = C2.encode([rm.G2.scalar_mul(G2_GENERATOR, k) for k in ks])

    base = pp.packexp_from_public(C, pts1, method="dense")
    base2 = pp.packexp_from_public(C2, pts2, method="dense")
    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    fast = pp.packexp_from_public(C, pts1, method="dense")
    fast2 = pp.packexp_from_public(C2, pts2, method="dense")
    assert C.decode(fast) == C.decode(base)
    assert C2.decode(fast2) == C2.decode(base2)
    # and unpacking the fast-packed shares returns the originals
    back = pp.unpackexp(C, fast, method="dense")
    assert C.decode(back) == C.decode(pts1)


@pytest.mark.parametrize("l", [1, 2, 4])
def test_unpack2_weights_sum_the_secrets_over_fr(l):
    """sum_j w_j * share_j is the sum of the l secrets of a degree-2(t+l)
    sharing, on the host and through the device's unpack2; and the
    weight's limbs take Montgomery shares to w_j * share_j in standard
    form in one product (d_msm's party side)."""
    pp = PackedSharingParams(l)
    F = fr()
    rng = random.Random(200 + l)
    shares = [rng.randrange(R) for _ in range(pp.n)]
    weighted = sum(w * s for w, s in zip(pp.unpack2_weights, shares)) % R
    assert sum(unpack2_host(pp, shares)) % R == weighted
    dev = F.decode(pp.unpack2(F.encode(shares)))
    assert sum(int(v) for v in dev) % R == weighted
    std = np.asarray(F.mul(
        F.encode(shares),
        np.stack([pp.unpack2_weight_limbs(F, j) for j in range(pp.n)]),
    ))
    assert [sum(int(x) << (16 * i) for i, x in enumerate(row))
            for row in std] == [w * s % R for w, s in
                                zip(pp.unpack2_weights, shares)]


@pytest.mark.parametrize("curve", ["g1", "g2"])
@pytest.mark.parametrize("l", [1, 2, 4])
def test_unpack2_weights_sum_what_unpackexp_unpacks(l, curve):
    """The king's weighted sum of n points is the sum of the l partials
    that unpacking them in the exponent gives (parallel/dmsm.py)."""
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g2

    pp = PackedSharingParams(l)
    C, host, gen = {
        "g1": (g1(), rm.G1, G1_GENERATOR),
        "g2": (g2(), rm.G2, G2_GENERATOR),
    }[curve]
    rng = random.Random(300 + l)
    ks = [rng.randrange(1, R) for _ in range(pp.n)]
    pts = C.encode([host.scalar_mul(gen, k) for k in ks])
    want = host.scalar_mul(
        gen, sum(w * k for w, k in zip(pp.unpack2_weights, ks)) % R)
    partials = pp.unpackexp(C, pts, degree2=True)
    assert C.decode(C.sum(partials, axis=0)) == want
