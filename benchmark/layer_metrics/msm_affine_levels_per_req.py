"""How many levels of the tree MSM's up-sweep a proof ran as batch-affine
adds (one shared inversion a level, chosen by the level's lane count since
PR 29): the movement of the program's counter `msm_affine_levels_total`
between the /metrics text taken after the warm-up and the one taken after
the window, over the window's completed proofs. 4 where the one full-width
MSM of a served proof (`h_query`, 32,768 points, 32 windows) takes its four
widest levels affine and the limb-0 trees none; None where the program has
no such counter, as the parent of that PR has not, or the window completed
no proof.

The counter carries no label, and `checks.counter` reads labelled series
only: hence `circuit_cache_hit_share`'s pattern, which this borrows."""

from ._common import window_dtos
from .circuit_cache_hit_share import _total

LAYER, UNIT, MOVES = "kernels", "count", "proof_p50_s"
FAMILY = "msm_affine_levels_total"


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
