"""Status DTO phases["encode"], median: `F.encode` of the witness, a Python
loop over the wires' bigints, and its upload (`service/worker.py`); a top-
level phase, between `witness` and `prove`. Host work, so the span time is
sound although nothing fences the device."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "host preparation", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "encode")
