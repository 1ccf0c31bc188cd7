"""How many of a proof's witnesses had their satisfiability decided on the
device, from the QAP evaluations the proof itself uses (`C z` against
`a * b`, one flag; since PR 33): the movement of the program's counter
`witness_device_checks_total`, both verdicts summed ("ok" and "rejected":
a refused witness was checked there too), between the /metrics text taken
after the warm-up and the one taken after the window, over the window's
completed proofs (`_counters.movement_per_proof`). 1.0 where every served
witness took the device's verdict and `R1CS.is_satisfied`'s Python loop is
off the served path: it says which of the two a cell paid for when
`witness_check_ms` moved. A window that refused witnesses would read above
1.0, since those count as checks and not as proofs; the cells' pools hold
none. None where the program has no such counter, as the parent of that PR
has not, or the window completed no proof."""

from ._counters import movement_per_proof

LAYER, UNIT, MOVES = "host preparation", "count", "proof_p50_s"
FAMILY = "witness_device_checks_total"


def read(run):
    return movement_per_proof(run, FAMILY)
