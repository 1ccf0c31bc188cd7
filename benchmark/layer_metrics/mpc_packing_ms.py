"""Status DTO phases["packing"], median: the first of an `mpc_prove` job's
two phases of its own (`service/worker.py`), between `encode` and `MPC
Proof`: the QAP's three vectors made and shared, the packed proving key
taken from its cache (inside the window a hit: `checks.py` holds the cell
to that), the witness packed twice. It enqueues device work and reads none
back, so the span is the host's dispatch. None where no job of the window
has the phase (a `prove` job)."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "prover", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "packing")
