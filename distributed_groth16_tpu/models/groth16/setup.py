"""Groth16 circuit-specific setup (trusted dealer) with CircomReduction
semantics, computed on device.

The reference delegates setup to the forked arkworks
`Groth16::<E, CircomReduction>::circuit_specific_setup` (seeded [42u8;32] in
the service, mpc-api/src/main.rs:148-152 — dev-grade, not a ceremony). This
module owns that algebra natively:

  * QAP polynomials at tau via Lagrange evaluation on the size-m domain
    (host bigint — O(m) with one batched inversion), including the
    input-consistency rows (same placement as qap.rs:69-73).
  * h_query uses the snarkjs/CircomReduction basis
    (ark-circom/src/circom/qap.rs:94-110): IFFT of delta^{-1} tau^i over the
    size-2m domain, odd coefficients — computed with the device NTT.
  * All query points are produced by one batched 256-step double-and-add
    ladder on device (ops/curve.py) — the TPU does the heavy lifting, the
    host only prepares scalars.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ...ops import refmath as rm
from ...ops.constants import R
from ...ops.curve import g1, g2
from ...ops.field import fr
from ...ops.msm import encode_scalars_std
from ...ops.ntt import domain
from ...frontend.r1cs import R1CS
from .keys import ProvingKey, VerifyingKey
from .qap import _next_pow2


def _lagrange_at(tau: int, m: int) -> list[int]:
    """L_j(tau) for the size-m domain: L_j = w^j (tau^m - 1) / (m (tau - w^j))."""
    dom = rm.Domain(m)
    zt = (pow(tau, m, R) - 1) % R
    els = dom.elements()
    denoms = [(tau - w) % R for w in els]
    invs = rm.batch_inv(denoms, R)
    zt_over_m = zt * rm.finv(m, R) % R
    return [els[j] * zt_over_m % R * invs[j] % R for j in range(m)]


def _qap_polys_at_tau(r1cs: R1CS, tau: int, m: int):
    """u_i(tau), v_i(tau), w_i(tau) for every wire i (host sparse eval)."""
    lag = _lagrange_at(tau, m)
    nw = r1cs.num_wires
    u = [0] * nw
    v = [0] * nw
    w = [0] * nw
    for j, row in enumerate(r1cs.a):
        lj = lag[j]
        for coeff, wire in row:
            u[wire] = (u[wire] + coeff * lj) % R
    for j, row in enumerate(r1cs.b):
        lj = lag[j]
        for coeff, wire in row:
            v[wire] = (v[wire] + coeff * lj) % R
    for j, row in enumerate(r1cs.c):
        lj = lag[j]
        for coeff, wire in row:
            w[wire] = (w[wire] + coeff * lj) % R
    # input-consistency rows (qap.rs:69-73): u_i += L_{nc+i} for instances
    for i in range(r1cs.num_instance):
        u[i] = (u[i] + lag[r1cs.num_constraints + i]) % R
    return u, v, w


def _h_query_scalars_device(tau: int, delta_inv: int, m: int) -> jnp.ndarray:
    """CircomReduction h basis (ark-circom qap.rs:94-110): IFFT over the
    2m domain of [delta_inv * tau^i, i < 2m-1], odd coefficients -> (m, 16)
    Montgomery scalars on device."""
    from ...ops.ntt import _powers_device

    F = fr()
    pows = _powers_device(tau, 2 * m)  # (2m, 16) Montgomery
    scal = F.mul(pows, F.encode([delta_inv])[0])
    # the reference builds 2*max_power+1 = 2m-1 scalars and lets the IFFT
    # zero-pad to 2m
    scal = scal.at[2 * m - 1].set(jnp.zeros(16, jnp.uint32))
    coeffs = domain(2 * m).ifft(scal)
    return coeffs[1::2]


def _g1_ladder(scalars: list[int]) -> jnp.ndarray:
    """(k,) ints -> (k, 3, 16) projective points scalar * G1 generator via
    the windowed fixed-base table (ops/fixedbase.py) — 31 batched adds per
    point instead of a 256-step ladder, the scaling fix for million-size
    setup."""
    from ...ops.fixedbase import fixed_base_mul

    return fixed_base_mul("g1", encode_scalars_std(scalars))


def _g2_ladder(scalars: list[int]) -> jnp.ndarray:
    from ...ops.fixedbase import fixed_base_mul

    return fixed_base_mul("g2", encode_scalars_std(scalars))


def setup(r1cs: R1CS, seed: int = 42) -> ProvingKey:
    """Circuit-specific setup; deterministic per seed (the service uses a
    fixed dev seed, mpc-api/src/main.rs:148-152)."""
    rng = np.random.default_rng(seed)

    def rand_fr() -> int:
        return int.from_bytes(rng.bytes(40), "little") % R

    alpha, beta, gamma, delta, tau = (rand_fr() for _ in range(5))
    gamma_inv = rm.finv(gamma, R)
    delta_inv = rm.finv(delta, R)

    from ...utils.timers import phase

    m = _next_pow2(r1cs.num_constraints + r1cs.num_instance)
    ni, nw = r1cs.num_instance, r1cs.num_wires
    with phase("setup: QAP polys at tau (host)"):
        u, v, w = _qap_polys_at_tau(r1cs, tau, m)

    l_query_s = [
        (beta * u[i] + alpha * v[i] + w[i]) % R * delta_inv % R
        for i in range(ni, nw)
    ]
    gamma_abc_s = [
        (beta * u[i] + alpha * v[i] + w[i]) % R * gamma_inv % R
        for i in range(ni)
    ]

    # one batched G1 ladder for every G1-side scalar
    g1_scalars = u + v + l_query_s + gamma_abc_s + [alpha, beta, delta]
    with phase("setup: G1 ladder"):
        g1_pts = _g1_ladder(g1_scalars)
        g1_pts.block_until_ready()
    ofs = 0
    a_query = g1_pts[ofs : ofs + nw]; ofs += nw
    b_g1_query = g1_pts[ofs : ofs + nw]; ofs += nw
    l_query = g1_pts[ofs : ofs + nw - ni]; ofs += nw - ni
    gamma_abc = g1_pts[ofs : ofs + ni]; ofs += ni
    alpha_g1_d, beta_g1_d, delta_g1_d = (
        g1_pts[ofs], g1_pts[ofs + 1], g1_pts[ofs + 2]
    )

    with phase("setup: G2 ladder"):
        g2_pts = _g2_ladder(v + [beta, gamma, delta])
        g2_pts.block_until_ready()
    b_g2_query = g2_pts[:nw]
    beta_g2_d, gamma_g2_d, delta_g2_d = g2_pts[nw], g2_pts[nw + 1], g2_pts[nw + 2]

    from ...ops.fixedbase import fixed_base_mul

    with phase("setup: h_query fixed-base"):
        h_scal = _h_query_scalars_device(tau, delta_inv, m)
        C1 = g1()
        h_query = fixed_base_mul("g1", fr().from_mont(h_scal))

    vk = VerifyingKey(
        alpha_g1=C1.decode(alpha_g1_d),
        beta_g2=g2().decode(beta_g2_d),
        gamma_g2=g2().decode(gamma_g2_d),
        delta_g2=g2().decode(delta_g2_d),
        gamma_abc_g1=list(C1.decode(gamma_abc)),
    )
    # The dealer keeps the query discrete logs: pack_proving_key then
    # shards the CRS in the FIELD (device NTT pack + windowed fixed-base,
    # proving_key.py) instead of point ladders — same shares, ~W/nbits
    # the curve work (the r4 84%-of-wall-clock bottleneck).
    from .proving_key import QueryScalars

    F = fr()
    with phase("setup: query scalar encode"):
        query_scalars = QueryScalars(
            a=F.encode(u),
            b=F.encode(v),
            l=F.encode(l_query_s),
            h=h_scal,
        )
    return ProvingKey(
        vk=vk,
        beta_g1=beta_g1_d,
        delta_g1=delta_g1_d,
        a_query=a_query,
        b_g1_query=b_g1_query,
        b_g2_query=b_g2_query,
        h_query=h_query,
        l_query=l_query,
        domain_size=m,
        num_instance=ni,
        query_scalars=query_scalars,
    )
