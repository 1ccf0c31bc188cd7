"""Device trace: union of the device-op intervals between the first and the
last `job` boundary of the slice, over the jobs that lie there."""

from ._common import per_job

LAYER, UNIT, MOVES = "prover", "ms", "proof_p50_s"


def read(run):
    pj = per_job(run)
    return 1e3 * pj["busy_s"] if pj else None
