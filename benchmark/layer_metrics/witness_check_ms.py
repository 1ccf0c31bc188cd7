"""Status DTO phases["witness.check"], median: `r1cs.is_satisfied` over every
constraint, in Python (`service/worker.py`). Host work, so the span time is
sound although nothing fences the device."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "host preparation", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "witness.check")
