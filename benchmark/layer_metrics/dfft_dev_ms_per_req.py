"""Device trace: time of the launches whose program is of the group
`kernel_groups/dfft.json` (the distributed FFT's two jitted stages,
`_fft1_local` on every party and `_fft2_king` on the king), per job.
Nothing to read where no such program ran (a `prove` job)."""

from ._groups import group_ms

LAYER, UNIT, MOVES = "kernels", "ms", "proof_p50_s"


def read(run):
    return group_ms(run, "dfft")
