"""Status DTO phases["packing.qap"], median (since PR 32): inside `packing`,
`circ.comp.qap(z_mont).pss(pp)`: the three matrix-vector products over the
resident matrices and the packing of their results into the eight parties'
shares. The host's dispatch, as `packing` is. None where the program has no
such key, as the parent of that PR has not."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "prover", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "packing.qap")
