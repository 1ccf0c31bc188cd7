"""Distributed proof-element computation: A, B, C over packed shares.

Formula parity with groth16/src/prove.rs:

  A = L + r*N + dmsm_G1(S, a)          (prove.rs:10-49)
  B = Z + s*K + dmsm_G2(V, a)          (prove.rs:51-88)
  C = w + u + s*A + r*M + r*h  where
      w = dmsm_G1(W, ax), u = dmsm_G1(U, h_vec), h = dmsm_G1(H, a)
      launched concurrently on channels 0/1/2 (prove.rs:112-125)

plus the witness-packing helper (sha256.rs:97-121) and the proof reassembly
a += a_query[0] + alpha_g1, b += b_g2_query[0] + beta_g2 (sha256.rs:208-212).
d_msm broadcasts the clear MSM value to every party, so any party's
(A, B, C) triple is the clear proof core — the examples read result[0].
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass

import jax.numpy as jnp

from ...ops.curve import CurvePoints, g1, g2
from ...ops.field import fr
from ...parallel.dmsm import d_msm
from ...parallel.net import Net
from ...parallel.packing import pack_consecutive
from ...parallel.pss import PackedSharingParams
from ...telemetry import aggregate as _aggregate
from ...telemetry import tracing as _tracing
from .ext_wit import h as ext_wit_h
from .keys import Proof, ProvingKey
from .proving_key import PackedProvingKeyShare
from .qap import PackedQAPShare, require_satisfied


def _maybe_mul(curve: CurvePoints, p, k: int):
    """k * p for a host int k; None point or k == 0 contributes infinity.

    Single-point work runs on the HOST (refmath): a 256-step device ladder
    for one point is pure dispatch overhead, and the eager-dispatch scan it
    used to emit deterministically crashed this jax's XLA:CPU compiler late
    in a long-lived process (segfault in backend_compile_and_load after
    ~dozens of live executables)."""
    if p is None or k % fr().p == 0:
        return None
    from ...ops import refmath as rm
    from ...ops.constants import Q as _BN254_Q

    # the host ops below are BN254-only; dispatching by coord_axes alone
    # would silently compute garbage for another curve's points
    base_p = curve.F.p if curve.coord_axes == 1 else curve.F.fq.p
    if base_p != _BN254_Q:
        raise NotImplementedError("_maybe_mul host path is BN254-only")
    host = rm.G1 if curve.coord_axes == 1 else rm.G2
    aff = curve.decode(p)
    out = host.scalar_mul(aff, k)
    return curve.encode([out])[0]


def _acc(curve: CurvePoints, *pts):
    """Sum of optional device points (None = infinity)."""
    live = [p for p in pts if p is not None]
    if not live:
        return curve.infinity()
    out = live[0]
    for p in live[1:]:
        out = curve.add(out, p)
    return out


async def compute_A(
    pp: PackedSharingParams,
    S: jnp.ndarray,
    a_share: jnp.ndarray,
    net: Net,
    sid: int = 0,
    L=None,
    N=None,
    r: int = 0,
):
    with _tracing.span(
        "prove.A", party=net.party_id, sid=sid, attrs=_tracing.DISPATCH
    ):
        prod = await d_msm(g1(), S, a_share, pp, net, sid)
        return _acc(g1(), L, _maybe_mul(g1(), N, r), prod)


async def compute_B(
    pp: PackedSharingParams,
    V: jnp.ndarray,
    a_share: jnp.ndarray,
    net: Net,
    sid: int = 0,
    Z=None,
    K=None,
    s: int = 0,
):
    with _tracing.span(
        "prove.B", party=net.party_id, sid=sid, attrs=_tracing.DISPATCH
    ):
        prod = await d_msm(g2(), V, a_share, pp, net, sid)
        return _acc(g2(), Z, _maybe_mul(g2(), K, s), prod)


async def compute_C(
    pp: PackedSharingParams,
    W: jnp.ndarray,
    U: jnp.ndarray,
    H: jnp.ndarray,
    a_share: jnp.ndarray,
    ax_share: jnp.ndarray,
    h_share: jnp.ndarray,
    net: Net,
    A=None,
    M=None,
    r: int = 0,
    s: int = 0,
):
    with _tracing.span(
        "prove.C", party=net.party_id, attrs=_tracing.DISPATCH
    ):
        msms = [
            d_msm(g1(), W, ax_share, pp, net, 0),
            d_msm(g1(), U, h_share, pp, net, 1),
        ]
        # the H-query MSM only feeds the r-weighted term — skip the whole
        # distributed round when r == 0 (the deterministic-proof path of
        # the examples and service)
        if r % fr().p != 0:
            msms.append(d_msm(g1(), H, a_share, pp, net, 2))
        results = await asyncio.gather(*msms)
        w, u = results[0], results[1]
        h_msm = results[2] if len(results) > 2 else None
        return _acc(
            g1(),
            w,
            u,
            _maybe_mul(g1(), A, s),
            _maybe_mul(g1(), M, r),
            _maybe_mul(g1(), h_msm, r),
        )


def pack_from_witness(
    pp: PackedSharingParams, values: jnp.ndarray
) -> jnp.ndarray:
    """(k, 16) Montgomery vector -> (n, ceil(k/l), 16) consecutive-chunk
    shares, zero-padding the tail chunk (sha256.rs:97-121)."""
    k = values.shape[0]
    rem = (-k) % pp.l
    if rem:
        values = jnp.pad(values, [(0, rem), (0, 0)])
    return pack_consecutive(pp, values)


@dataclass
class PartyProofShare:
    a: jnp.ndarray  # (3, 16) G1 — clear values after d_msm broadcast
    b: jnp.ndarray  # (3, 2, 16) G2
    c: jnp.ndarray  # (3, 16) G1


def _a_completion(pk):
    """a_query[0] + alpha_g1 — the public term completing a party's S-MSM
    to the full A. Single definition shared by the zk C-term and
    reassemble_proof: they MUST agree or randomized proofs stop verifying
    (sha256.rs:208-212)."""
    C1 = g1()
    return C1.add(pk.a_query[0], C1.encode([pk.vk.alpha_g1])[0])


def public_prove_consts(pk) -> dict:
    """The clear CRS values every server receives for a randomized proof
    (prove.rs:9,51,90 — L/N/Z/K/A/M are public inputs to the per-party
    compute): N = delta_g1, K = delta_g2, and the constant-wire-completed
    alpha / beta terms that enter A and C."""
    C2 = g2()
    return {
        "N": pk.delta_g1,
        "K": C2.encode([pk.vk.delta_g2])[0],
        "A0": _a_completion(pk),
        # beta_g1 + b_g1_query[0]: with the H-query d_msm over
        # b_g1_query[1:], r*(M + h_msm) = r*B_g1 - r*s*delta exactly
        "M": g1().add(pk.beta_g1, pk.b_g1_query[0]),
    }


async def distributed_prove_party(
    pp: PackedSharingParams,
    crs_share: PackedProvingKeyShare,
    qap_share: PackedQAPShare,
    a_share: jnp.ndarray,
    ax_share: jnp.ndarray,
    net: Net,
    pub: dict | None = None,
    r: int = 0,
    s: int = 0,
) -> PartyProofShare:
    """One party's full proving round (the dsha256 template,
    sha256.rs:26-99): h, then A, B, C. For a zero-knowledge proof pass
    r, s != 0 together with `pub` = public_prove_consts(pk)."""
    zk = (r % fr().p, s % fr().p) != (0, 0)
    if zk and pub is None:
        raise ValueError("randomized proof needs pub=public_prove_consts(pk)")
    with _tracing.span(
        "prove.party", party=net.party_id, attrs=_tracing.DISPATCH
    ):
        with _tracing.span(
            "prove.h", party=net.party_id, attrs=_tracing.DISPATCH
        ):
            h_share = await ext_wit_h(qap_share, pp, net)
        # A and B are independent distributed rounds — overlap them on
        # separate channels (the reference runs them back-to-back on
        # channel Zero)
        pi_a, pi_b = await asyncio.gather(
            compute_A(pp, crs_share.s, a_share, net, 0,
                      N=pub["N"] if zk else None, r=r),
            compute_B(pp, crs_share.v, a_share, net, 1,
                      K=pub["K"] if zk else None, s=s),
        )
        pi_c = await compute_C(
            pp,
            crs_share.w,
            crs_share.u,
            crs_share.h,
            a_share,
            ax_share,
            h_share,
            net,
            A=g1().add(pi_a, pub["A0"]) if zk else None,
            M=pub["M"] if zk else None,
            r=r,
            s=s,
        )
        share = PartyProofShare(a=pi_a, b=pi_b, c=pi_c)
    # round boundary: ship this party's compacted spans to the king
    # (TELEMETRY frame on ProdNet; no-op in-process, where the round
    # harness merges — docs/OBSERVABILITY.md). Outside the prove.party
    # span so the flush itself never pollutes the round's timeline.
    if _aggregate.enabled():
        flush = getattr(net, "flush_telemetry", None)
        if flush is not None:
            await flush()
    return share


def prove_single(
    pk: ProvingKey, compiled, z_mont: jnp.ndarray, r: int = 0, s: int = 0,
    wide=None,
) -> Proof:
    """Single-node prove on device (r = s = 0 default) — the role the plain
    arkworks prover plays in the reference's service
    (mpc-api/src/main.rs:282-421) and examples (sha256.rs:158-169).

    h is the CircomReduction witness map computed with device NTTs: the
    odd-2m-th-root evaluations are one coset FFT (offset = the 2m-th root)
    of the m-domain coefficients.

    `wide` is the host's view of the witness that `encode_observed` made
    beside `z_mont` (`ops/msm.py`); the MSMs over z then run limb-0
    windows where its wide wires fit, and the proof is the same. The MSM
    over h always runs all windows: its scalars fill the field. Which of
    the two an MSM over z took, its dispatch notes as the `route` of the
    span it runs in (`prove.A/B/C`): a witness that fills the field reads
    "tree" on all three.

    A witness that does not satisfy the circuit makes no proof: the
    device decides it beside the QAP (`compiled.satisfied`), and the
    verdict is read once the witness map is queued behind it and before
    any MSM is, in `prove.check`. ValueError, as the service reports it.
    """
    from ...ops.msm import msm as _msm
    from ...ops.ntt import domain as _domain

    F = fr()
    C1, C2 = g1(), g2()
    # The stage spans carry the MPC path's names. With r = s = 0 (the
    # served path) every body up to `prove.decode` only enqueues device
    # work, so those spans are on the dispatch clock; a randomized proof's
    # `_maybe_mul` reads points back, which makes them wall time.
    zk = r % F.p != 0 or s % F.p != 0
    enqueue = None if zk else _tracing.DISPATCH

    def over_z(view):
        # the span says how many wide wires its MSM over z was told of
        if view is None:
            return enqueue
        return {**(enqueue or {}), "wide_scalars": view.count}

    with _tracing.span("prove.qap", attrs=enqueue):
        qap = compiled.qap(z_mont)
        ok = compiled.satisfied(z_mont, qap)
    with _tracing.span("prove.h", attrs=enqueue):
        m = pk.domain_size
        dom = _domain(m)
        shift = _domain(2 * m).group_gen
        dom_shift = _domain(m, offset=shift)
        p_ev = dom_shift.fft(dom.ifft(qap.a))
        q_ev = dom_shift.fft(dom.ifft(qap.b))
        w_ev = dom_shift.fft(dom.ifft(qap.c))
        h_vec = F.sub(F.mul(p_ev, q_ev), w_ev)  # (m, 16) Montgomery
    # the host waits for the upload and the QAP's products, with the NTTs
    # queued behind them to keep the chip fed: wall time, like the decode
    with _tracing.span("prove.check"):
        require_satisfied(ok)

    z_std = F.from_mont(z_mont)
    ni = pk.num_instance
    wide_l = None if wide is None else wide.tail(ni)
    with _tracing.span("prove.A", attrs=over_z(wide)):
        a_pt = C1.add(
            _msm(C1, pk.a_query, z_std, wide=wide),
            C1.encode([pk.vk.alpha_g1])[0],
        )
        if r % F.p != 0:
            a_pt = C1.add(a_pt, _maybe_mul(C1, pk.delta_g1, r))
    with _tracing.span("prove.B", attrs=over_z(wide)):
        b_pt = C2.add(
            _msm(C2, pk.b_g2_query, z_std, wide=wide),
            C2.encode([pk.vk.beta_g2])[0],
        )
        if s % F.p != 0:
            b_pt = C2.add(
                b_pt, _maybe_mul(C2, C2.encode([pk.vk.delta_g2])[0], s)
            )
    with _tracing.span("prove.C", attrs=over_z(wide_l)):
        c_pt = C1.add(
            _msm(C1, pk.l_query, z_std[ni:], wide=wide_l),
            _msm(C1, pk.h_query, F.from_mont(h_vec)),
        )
        if zk:
            # C += s*A + r*B1 - rs*delta; with B1 = beta + sum z v +
            # s*delta the delta terms cancel, leaving
            # s*A + r*(beta + sum z v)
            extra = _acc(
                C1,
                _maybe_mul(C1, a_pt, s),
                _maybe_mul(
                    C1,
                    C1.add(
                        pk.beta_g1,
                        _msm(C1, pk.b_g1_query, z_std, wide=wide),
                    ),
                    r,
                ),
            )
            c_pt = C1.add(c_pt, extra)
    # where the host waits for the chip: wall time, no `clock` attribute
    with _tracing.span("prove.decode"):
        return Proof(
            a=C1.decode(a_pt), b=C2.decode(b_pt), c=C1.decode(c_pt)
        )


def reassemble_proof(share: PartyProofShare, pk: ProvingKey) -> Proof:
    """Final client-side assembly (sha256.rs:208-212): add the constant-wire
    query terms and the vk offsets, decode to host affine."""
    C1, C2 = g1(), g2()
    a = C1.add(share.a, _a_completion(pk))
    b = C2.add(
        share.b, C2.add(pk.b_g2_query[0], C2.encode([pk.vk.beta_g2])[0])
    )
    return Proof(a=C1.decode(a), b=C2.decode(b), c=C1.decode(share.c))
