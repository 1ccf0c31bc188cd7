"""Status DTO phases["load.r1cs"], median: the Python parse of the circuit's
`.r1cs` file, once per job (`api/store.py`). Host work, so the span time is
sound although nothing fences the device."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "host preparation", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "load.r1cs")
