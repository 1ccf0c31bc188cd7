"""Distributed MSM over packed shares — hot kernel #2.

d_msm: every party runs one local MSM over its m/l packed-share (bases,
scalars) — the dominant compute, on-device via ops/msm.py — producing one
group element whose sharing polynomial has degree 2(t+l). The reference
(dist-primitives/src/dmsm/mod.rs:70-98) has the king gather the n points,
unpack them in the exponent (degree2) into l partial MSMs and sum those.

Unpacking is a fixed linear map M (l x n, `pp.unpack2_matrix`) and only the
sum of its l outputs is used, so

    sum_o sum_j M[o][j] * P_j  =  sum_j w_j * P_j,  w_j = sum_o M[o][j] mod r,

and w_j * MSM(bases_j, s_j) = MSM(bases_j, w_j * s_j). Each party weighs
its own scalars by its public column sum w_j (`pp.unpack2_weights`) in the
one Montgomery product that already takes them out of Montgomery form; the
king adds the n points. The group element is the same, and the king learns
w_j * P_j for a public w_j, no more than P_j.

Communication: O(1) group elements per party — d_msm is compute-bound.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from ..ops.curve import CurvePoints
from ..ops.field import fr
from ..ops.msm import msm
from ..telemetry import metrics as _tm
from ..telemetry import tracing as _tracing
from .net import Net, king_section
from .pss import PackedSharingParams

log = logging.getLogger(__name__)

# bound at import, so it prints 0 before any round
_WEIGHTED_ROUNDS = _tm.registry().counter(
    "dmsm_weighted_rounds_total",
    "d_msm rounds whose king summed the parties' weighted points (no "
    "unpack in the exponent)",
)


@functools.partial(jax.jit, static_argnums=0)
def _sum_points(curve: CurvePoints, stacked):
    """The king's sum of the n weighted points as one launch."""
    return curve.sum(stacked, axis=0)


async def d_msm(
    curve: CurvePoints,
    bases,
    scalar_shares,
    pp: PackedSharingParams,
    net: Net,
    sid: int = 0,
    scalar_field=None,
):
    """bases: (c, 3) + elem packed-in-the-exponent CRS shares;
    scalar_shares: (c, 16) Montgomery-form packed witness shares.
    Returns the clear MSM result (3,) + elem on every party.

    scalar_field: the PrimeField the shares live in — defaults to BN254
    Fr; pass ops.bls12_377.fr377() (with pp = bls12_377.pss377(l)) for the
    reference's BLS12-377 configuration (dmsm_bench.rs:42-50; d_msm itself
    is curve-generic there, dmsm/mod.rs:70)."""
    F = scalar_field or fr()
    log.debug("d_msm: party %d local MSM over %d bases (sid=%d)",
              net.party_id, bases.shape[0], sid)
    with _tracing.span(
        "dmsm", party=net.party_id, sid=sid, attrs=_tracing.DISPATCH
    ):
        # w_j * s_j in standard form: Montgomery shares times a
        # standard-form w_j < r. F.mul ends in a conditional subtraction
        # of r, so the product is fully reduced (< r).
        # Wide standard forms (r381 -> 17 limbs) pass through unchanged:
        # ops/msm.py's digit decomposition is width-aware as of r5
        weight = pp.unpack2_weight_limbs(F, net.party_id)
        std = F.mul(scalar_shares, weight)
        local = msm(curve, bases, std)

        def king(points):
            # one child span a statement: an idle chip inside the king
            # then names the statement the host sat in
            with king_section("dmsm"):
                with _tracing.span("dmsm.king.stack", party=0):
                    stacked = jnp.stack(points, axis=0)  # (n, 3) + elem
                with _tracing.span("dmsm.king.sum", party=0):
                    total = _sum_points(curve, stacked)
            _WEIGHTED_ROUNDS.inc()
            return [total] * pp.n

        return await net.king_compute(local, king, sid)
