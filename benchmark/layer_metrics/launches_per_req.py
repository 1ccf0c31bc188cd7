"""Device trace: launches of jitted programs (events of `XLA Modules`)
between the first and the last `job` boundary of the slice, over the jobs
that lie there. An exact count in a one-client cell."""

from ._common import per_job

LAYER, UNIT, MOVES = "prover", "launches", "proof_p50_s"


def read(run):
    pj = per_job(run)
    return pj["launches"] if pj else None
