"""What the readers of a program counter's movement share (since PR 32): a
family of /metrics, all its series summed, between the text taken after
the warm-up and the one taken after the window, over the window's
completed proofs. None where the program has no such family (the parent
of the PR that brought it) or the window completed no proof; a missing
first text counts as 0, so the movement is then the whole of the second."""

from ._common import window_dtos
from .circuit_cache_hit_share import _total


def movement_per_proof(run, family):
    rec = run.get("records") or {}
    after = _total(rec.get("metrics_after"), family)
    if after is None:
        return None
    proofs = sum(
        d["kind"] in ("prove", "mpc_prove")
        for d in (window_dtos(run) if run.get("requests") else ())
    )
    if not proofs:
        return None
    return (after - (_total(rec.get("metrics_before"), family) or 0)) / proofs
