"""Device trace: time of the launches whose program is of the group
`kernel_groups/msm_g2.json` (the tree MSM over G2, `_msm_tree_jit_g2`),
per job. Nothing to read where the program names no group in its MSM
programs (before PR 23)."""

from ._groups import group_ms

LAYER, UNIT, MOVES = "kernels", "ms", "proof_p50_s"


def read(run):
    return group_ms(run, "msm_g2")
