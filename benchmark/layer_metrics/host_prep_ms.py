"""Status DTO phases.load + phases.witness, median: reading the key and the
circuit from the store, parsing and checking the witness. Host-only phases,
so the span times are sound although nothing fences the device."""

from ._common import median, window_dtos

LAYER, UNIT, MOVES = "host preparation", "ms", "proof_p50_s"


def read(run):
    return median(
        d["phases"]["load"] + d["phases"]["witness"]
        for d in window_dtos(run)
        if "load" in d["phases"] and "witness" in d["phases"]
    )
