"""The wall a proof's window spent inside Python's cyclic garbage collector
(since PR 34): the movement of the program's counter
`python_gc_seconds_total`, every generation, between the /metrics text
taken after the warm-up and the one taken after the window, over the
window's completed proofs (`_counters.movement_per_proof`), in ms. A
collection holds the interpreter lock, so every Python thread of the
process waits for it: the job's, the round's parties', the front door's.
None where the program has no such counter, as the parent of that PR has
not, or the window completed no proof."""

from ._counters import movement_per_proof

LAYER, UNIT, MOVES = "host runtime", "ms", "proof_p50_s"
FAMILY = "python_gc_seconds_total"


def read(run):
    per_proof = movement_per_proof(run, FAMILY)
    return None if per_proof is None else 1e3 * per_proof
