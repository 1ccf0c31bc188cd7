"""How many of a proof's tree MSMs were handed the host's view of their
scalars and ran all windows all the same (the rule `takes_limb0` said no:
more wide scalars than the padding has slots for; since PR 30): the
movement of the program's counter
`msm_limb0_declined_total{reason="over_capacity"}` between the /metrics
text taken after the warm-up and the one taken after the window, over the
window's completed proofs. 3 where a witness fills the field (A, B and L
are declined; `h_query` comes with no view and is not counted), 0 where
the witness is bit-level and the three take the limb-0 form (the program
binds the series when it is imported, so an unraised one still prints 0):
it says on which side of the rule a cell stood when a number moved. None
where the program has no such counter, as the parent of that PR has not,
or the window completed no proof.

Read the way `msm_affine_levels_per_req` is."""

from ._common import window_dtos
from .circuit_cache_hit_share import _total

LAYER, UNIT, MOVES = "kernels", "count", "proof_p50_s"
FAMILY = "msm_limb0_declined_total"


def read(run):
    rec = run.get("records") or {}
    after = _total(rec.get("metrics_after"), FAMILY)
    if after is None:
        return None
    proofs = sum(
        d["kind"] in ("prove", "mpc_prove")
        for d in (window_dtos(run) if run.get("requests") else ())
    )
    if not proofs:
        return None
    return (after - (_total(rec.get("metrics_before"), FAMILY) or 0)) / proofs
