"""Status DTO phases["MPC Proof.reassemble"], median (since PR 32): inside
`MPC Proof`, `reassemble_proof`: three point additions and the decoding of
the proof to host integers, which is the first host read of a device value
in the job. WALL from the round's last enqueue to the decoded proof: what
the device still had queued when the host stopped issuing, drained. It is
not the whole of the host's wait for the chip: most of that is the
back-pressure inside `mpc_round_enqueue_ms`.
None where the program has no such key, as the parent of that PR has
not."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "prover", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "MPC Proof.reassemble")
