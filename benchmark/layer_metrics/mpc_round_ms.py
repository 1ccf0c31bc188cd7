"""Status DTO phases["MPC Proof"], median: the round itself, from the eight
parties' start to the decoded proof (`service/worker.py`). It ends in a
host read of a device value, so as a whole it is wall time: the wall up to
the round's last enqueue (`mpc_round_enqueue_ms`) and the drain after it
(`mpc_round_drain_ms`) partition it since PR 32. None where no job of the
window has the phase (a `prove` job)."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "prover", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "MPC Proof")
