"""Device trace: time of the launches whose program is of the group
`kernel_groups/pss_ladder.json` (packed secret sharing in the exponent: the
fixed-scalar ladder, `_dense_ladder_jit`, that unpacks the eight points of
each of a proof's four d_msms on the king), per job. Nothing to read where
no such program ran (a `prove` job)."""

from ._groups import group_ms

LAYER, UNIT, MOVES = "kernels", "ms", "proof_p50_s"


def read(run):
    return group_ms(run, "pss_ladder")
