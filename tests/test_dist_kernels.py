"""Differential tests for the distributed kernels vs single-node ground
truth, n=8 parties, l=2 — exactly the reference's test matrix
(dfft/mod.rs:273-557, dmsm tests, dpp_test.rs, deg_red)."""

import random

import jax.numpy as jnp
import pytest

from distributed_groth16_tpu.ops import refmath as rm
from distributed_groth16_tpu.ops.constants import G1_GENERATOR, R
from distributed_groth16_tpu.ops.curve import g1
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.parallel.dfft import d_fft, d_ifft
from distributed_groth16_tpu.parallel.dmsm import d_msm
from distributed_groth16_tpu.parallel.degred import deg_red
from distributed_groth16_tpu.parallel.dpp import d_pp
from distributed_groth16_tpu.parallel.net import simulate_network_round
from distributed_groth16_tpu.parallel.packing import (
    pack_consecutive,
    pack_strided,
    unpack_shares,
)
from distributed_groth16_tpu.parallel.pss import PackedSharingParams, pack_host

L = 2
N = 4 * L
M = 32


def _ints(decoded):
    return [int(x) for x in decoded]


def test_d_fft_matches_domain_fft():
    """d_fft vs dom.fft ground truth (dfft_test.rs)."""
    pp = PackedSharingParams(L)
    F = fr()
    rng = random.Random(42)
    x = [rng.randrange(R) for _ in range(M)]
    dom = rm.Domain(M)
    expected = dom.fft(x)

    shares = pack_strided(pp, F.encode(x))  # (n, m/l, 16)

    async def party(net, data):
        from distributed_groth16_tpu.ops.ntt import domain

        return await d_fft(data, False, 1, False, domain(M), pp, net)

    outs = simulate_network_round(N, party, [shares[i] for i in range(N)])
    got = _ints(F.decode(unpack_shares(pp, jnp.stack(outs, 0))))
    assert got == expected


def test_d_ifft_matches_domain_ifft():
    pp = PackedSharingParams(L)
    F = fr()
    rng = random.Random(43)
    x = [rng.randrange(R) for _ in range(M)]
    dom = rm.Domain(M)
    expected = dom.ifft(x)

    shares = pack_strided(pp, F.encode(x))

    async def party(net, data):
        from distributed_groth16_tpu.ops.ntt import domain

        return await d_ifft(data, False, 1, False, domain(M), pp, net)

    outs = simulate_network_round(N, party, [shares[i] for i in range(N)])
    got = _ints(F.decode(unpack_shares(pp, jnp.stack(outs, 0))))
    assert got == expected


def test_d_ifft_then_d_fft_roundtrip_with_rearrange_and_pad():
    """The ext_wit::h composition: d_ifft(rearrange=True, pad=2) on domain m
    feeds d_fft on domain 2m; result must equal evaluating the degree-(m-1)
    polynomial on the 2m domain (dfft/mod.rs roundtrip test)."""
    pp = PackedSharingParams(L)
    F = fr()
    rng = random.Random(44)
    evals = [rng.randrange(R) for _ in range(M)]
    dom_m = rm.Domain(M)
    dom_2m = rm.Domain(2 * M)
    coeffs = dom_m.ifft(evals)
    expected = dom_2m.fft(coeffs)

    shares = pack_strided(pp, F.encode(evals))

    async def party(net, data):
        from distributed_groth16_tpu.ops.ntt import domain

        mid = await d_ifft(data, True, 2, False, domain(M), pp, net)
        return await d_fft(mid, False, 1, False, domain(2 * M), pp, net)

    outs = simulate_network_round(N, party, [shares[i] for i in range(N)])
    got = _ints(F.decode(unpack_shares(pp, jnp.stack(outs, 0))))
    assert got == expected


def test_d_fft_degree2_consumes_sharewise_products():
    """Share-wise product of two packed vectors is a degree-2(t+l) sharing;
    d_fft(degree2=True) must unpack it correctly on the king."""
    pp = PackedSharingParams(L)
    F = fr()
    rng = random.Random(45)
    a = [rng.randrange(R) for _ in range(M)]
    b = [rng.randrange(R) for _ in range(M)]
    prod = [x * y % R for x, y in zip(a, b)]
    dom = rm.Domain(M)
    expected = dom.fft(prod)

    sa = pack_strided(pp, F.encode(a))
    sb = pack_strided(pp, F.encode(b))
    sprod = F.mul(sa, sb)

    async def party(net, data):
        from distributed_groth16_tpu.ops.ntt import domain

        return await d_fft(data, False, 1, True, domain(M), pp, net)

    outs = simulate_network_round(N, party, [sprod[i] for i in range(N)])
    got = _ints(F.decode(unpack_shares(pp, jnp.stack(outs, 0))))
    assert got == expected


def test_d_msm_matches_local_msm():
    """d_msm vs plain MSM ground truth (dmsm_test.rs)."""
    pp = PackedSharingParams(L)
    F = fr()
    C = g1()
    rng = random.Random(46)
    m = 16
    ks = [rng.randrange(1, R) for _ in range(m)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks]
    scalars = [rng.randrange(R) for _ in range(m)]
    expected = rm.G1.msm(pts, scalars)

    # pack scalars consecutively; pack bases in the exponent the same way
    s_shares = pack_consecutive(pp, F.encode(scalars))  # (n, m/l, 16)
    base_chunks = C.encode(pts).reshape(m // pp.l, pp.l, 3, 16)
    b_shares = jnp.swapaxes(
        pp.packexp_from_public(C, base_chunks), 0, 1
    )  # (n, m/l, 3, 16)

    async def party(net, data):
        bases, scalars_sh = data
        return await d_msm(C, bases, scalars_sh, pp, net)

    outs = simulate_network_round(
        N, party, [(b_shares[i], s_shares[i]) for i in range(N)]
    )
    for o in outs:
        assert C.decode(o) == expected


def test_deg_red_preserves_secrets():
    pp = PackedSharingParams(L)
    F = fr()
    rng = random.Random(47)
    a = [rng.randrange(R) for _ in range(M)]
    b = [rng.randrange(R) for _ in range(M)]
    prod = [x * y % R for x, y in zip(a, b)]
    sa = pack_consecutive(pp, F.encode(a))
    sb = pack_consecutive(pp, F.encode(b))
    sprod = F.mul(sa, sb)

    async def party(net, data):
        return await deg_red(data, pp, net)

    outs = simulate_network_round(N, party, [sprod[i] for i in range(N)])
    got = _ints(
        F.decode(unpack_shares(pp, jnp.stack(outs, 0), degree2=False))
    )
    assert got == prod


def test_d_pp_all_ones():
    """All-ones num/den -> all-ones prefix products (dpp_test.rs)."""
    pp = PackedSharingParams(L)
    F = fr()
    ones = [1] * M
    s = pack_consecutive(pp, F.encode(ones))

    async def party(net, data):
        return await d_pp(data, data, pp, net)

    outs = simulate_network_round(N, party, [s[i] for i in range(N)])
    got = _ints(F.decode(unpack_shares(pp, jnp.stack(outs, 0))))
    assert got == ones


def test_d_pp_random():
    pp = PackedSharingParams(L)
    F = fr()
    rng = random.Random(48)
    num = [rng.randrange(1, R) for _ in range(M)]
    den = [rng.randrange(1, R) for _ in range(M)]
    ratio = [n * rm.finv(d, R) % R for n, d in zip(num, den)]
    expected = []
    acc = 1
    for x in ratio:
        acc = acc * x % R
        expected.append(acc)

    sn = pack_consecutive(pp, F.encode(num))
    sd = pack_consecutive(pp, F.encode(den))

    async def party(net, data):
        n_sh, d_sh = data
        return await d_pp(n_sh, d_sh, pp, net)

    outs = simulate_network_round(N, party, [(sn[i], sd[i]) for i in range(N)])
    got = _ints(F.decode(unpack_shares(pp, jnp.stack(outs, 0))))
    assert got == expected


def _g2_dmsm_round(seed):
    """A d_msm round over G2 with bases packed in the exponent on the host
    (share j of a chunk is pack_host(exponents)[j] * G2), against the host
    MSM of the clear bases and scalars."""
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g2

    pp = PackedSharingParams(L)
    F = fr()
    C = g2()
    rng = random.Random(seed)
    m = 8
    ks = [rng.randrange(1, R) for _ in range(m)]
    scalars = [rng.randrange(R) for _ in range(m)]
    expected = rm.G2.scalar_mul(
        G2_GENERATOR, sum(k * s for k, s in zip(ks, scalars)) % R)
    chunks = [pack_host(pp, ks[c:c + L]) for c in range(0, m, L)]
    b_shares = [
        C.encode([rm.G2.scalar_mul(G2_GENERATOR, ch[j]) for ch in chunks])
        for j in range(N)
    ]  # n x (m/l, 3, 2, 16)
    s_shares = pack_consecutive(pp, F.encode(scalars))

    async def party(net, data):
        return await d_msm(C, data[0], data[1], pp, net)

    outs = simulate_network_round(
        N, party, [(b_shares[i], s_shares[i]) for i in range(N)]
    )
    return C, outs, expected


def test_d_msm_g2_matches_host_msm():
    C, outs, expected = _g2_dmsm_round(49)
    for o in outs:
        assert C.decode(o) == expected


def test_d_msm_king_runs_no_unpack(monkeypatch):
    """The king sums the parties' weighted points: nothing unpacks in the
    exponent, and the result is still the clear MSM."""

    def refuse(*args, **kwargs):
        raise AssertionError("d_msm unpacked in the exponent")

    monkeypatch.setattr(PackedSharingParams, "unpackexp", refuse)
    C, outs, expected = _g2_dmsm_round(50)
    for o in outs:
        assert C.decode(o) == expected


def test_d_msm_raises_the_weighted_rounds_counter_once():
    from distributed_groth16_tpu.telemetry import metrics as tm

    counter = tm.registry().family("dmsm_weighted_rounds_total")
    before = counter.value
    _g2_dmsm_round(51)
    assert counter.value - before == 1
