"""Distributed MSM over packed shares — hot kernel #2.

d_msm (dist-primitives/src/dmsm/mod.rs:70-98): every party runs one local
Pippenger MSM over its m/l packed-share (bases, scalars) — the dominant
compute, on-device via ops/msm.py — producing one group element whose
sharing polynomial has degree 2(t+l). The king gathers the n points,
unpacks them in the exponent (degree2), sums the l recovered partial MSMs
and broadcasts the final value.

Communication: O(1) group elements per party — d_msm is compute-bound.
"""

from __future__ import annotations

import logging

from ..ops.curve import CurvePoints
from ..ops.field import fr
from ..ops.msm import msm
from ..telemetry import tracing as _tracing
from .net import Net, king_section
from .pss import PackedSharingParams

log = logging.getLogger(__name__)


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
        # wide standard forms (r381 -> 17 limbs) pass through unchanged:
        # ops/msm.py's digit decomposition is width-aware as of r5
        std = F.from_mont(scalar_shares)
        local = msm(curve, bases, std)

        def king(points):
            import jax.numpy as jnp

            # one child span a statement: an idle chip inside the king
            # then names the statement the host sat in
            with king_section("dmsm"):
                with _tracing.span("dmsm.king.stack", party=0):
                    stacked = jnp.stack(points, axis=0)  # (n, 3) + elem
                with _tracing.span("dmsm.king.unpack", party=0):
                    partials = pp.unpackexp(  # (l, 3) + elem
                        curve, stacked, degree2=True)
                with _tracing.span("dmsm.king.sum", party=0):
                    total = curve.sum(partials, axis=0)
            return [total] * pp.n

        return await net.king_compute(local, king, sid)
