"""Device trace: time of the launches whose program is of the group
`kernel_groups/ntt.json`, per job."""

from ._common import per_job

LAYER, UNIT, MOVES = "kernels", "ms", "proof_p50_s"


def read(run):
    pj = per_job(run)
    return 1e3 * pj["group_s"]["ntt"] if pj and "ntt" in pj["group_s"] else None
