"""Seconds of the warm-up request(s), on the client's clock: tracing,
compiling or fetching every program the cell uses, and for the MPC
configuration packing the CRS."""

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(run):
    return run["setup"]["warmup_s"]
