"""The wall of the server's periodic background ticks a proof's window
held (since PR 34; `bg.devmem`, the device-memory sampler's `memory_stats()`
on every device every 10 s, and `bg.slo` where an SLO target is set): the
movement of the program's counter `background_seconds_total`, every task,
between the /metrics text taken after the warm-up and the one taken after
the window, over the window's completed proofs
(`_counters.movement_per_proof`), in ms. It runs beside the jobs, so it is
their cost only where it holds what they need (the interpreter lock, the
device's runtime). None where the program has no such counter, as the
parent of that PR has not, or the window completed no proof."""

from ._counters import movement_per_proof

LAYER, UNIT, MOVES = "host runtime", "ms", "proof_p50_s"
FAMILY = "background_seconds_total"


def read(run):
    per_proof = movement_per_proof(run, FAMILY)
    return None if per_proof is None else 1e3 * per_proof
