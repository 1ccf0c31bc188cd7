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

Where the parties share a process and a chip (`LocalSimNet`, whose nets
offer a `Rendezvous`), the n local MSMs of one d_msm are one launch of the
batched tree program (`ops/msm.py:msm_batched`): each party hands in its
(bases, w_j s_j) and gets its own row back. Row j is the MSM party j would
have launched alone, so the king still receives n points. A net without a
rendezvous (`ProdNet`, a party per process) runs its MSM alone.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from ..ops.curve import CurvePoints
from ..ops.field import fr
from ..ops.msm import msm, msm_batched
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

_LOCAL_MSM = _tm.registry().counter(
    "dmsm_local_msm_total",
    "Parties' local d_msm MSMs, by whether the round's rendezvous ran them "
    "as rows of one batched launch or the party ran its own",
    ("path",),
)
_LOCAL_BATCHED = _LOCAL_MSM.labels(path="batched")
_LOCAL_ALONE = _LOCAL_MSM.labels(path="alone")


@jax.jit
def _stack_rows(rows):
    """The parties' (bases, scalars) as the batch's two stacked arrays."""
    return (jnp.stack([b for b, _ in rows]), jnp.stack([s for _, s in rows]))


@jax.jit
def _unstack_rows(points):
    """(n, 3)+elem -> the n rows, one launch for all."""
    return tuple(points[j] for j in range(points.shape[0]))


def _local_msms(curve: CurvePoints):
    """The rendezvous's work for one d_msm: the n parties' MSMs as one
    `msm_batched` launch, row j back to party j."""

    def run(rows):
        return list(_unstack_rows(msm_batched(curve, *_stack_rows(rows))))

    return run


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
        if getattr(net, "rendezvous", None) is None:
            _LOCAL_ALONE.inc()
            local = msm(curve, bases, std)
        else:
            _LOCAL_BATCHED.inc()
            local = await net.batch_local((bases, std), _local_msms(curve), sid)

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
