"""The wall a proof's round spent inside the king's own functions (since PR
32): the movement of the program's counter `mpc_king_seconds_total`, both
stages (`stage="dmsm"`: the in-exponent unpack and the sum of a d_msm's
eight points; `stage="dfft"`: the tail of a distributed transform), over
the window's completed proofs (`_counters.movement_per_proof`), in ms.
WALL, not the king's Python alone: the functions only enqueue device work,
but on the chip (PR 32) their eager ops return only as the device catches
up, so this holds the device's back-pressure (the chip was busy two thirds
of it, mostly with the parties' own launches queued ahead) as well as the
host's dispatching. It is the time the other seven parties'
`net.king_compute` and `net.scatter_from_king` wait; it is not what a
faster king's Python would save, which the device's idle share bounds.
None where the program has no such counter, as the parent of that PR has
not, or the window completed no proof."""

from ._counters import movement_per_proof

LAYER, UNIT, MOVES = "collectives", "ms", "proof_p50_s"
FAMILY = "mpc_king_seconds_total"


def read(run):
    per_proof = movement_per_proof(run, FAMILY)
    return None if per_proof is None else 1e3 * per_proof
