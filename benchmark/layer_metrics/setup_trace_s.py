"""Seconds before the window in which jax traced or lowered a program
(`jax.monitoring` spans, merged): what the persistent cache does not keep."""

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(run):
    return run["setup"]["trace_s"]
