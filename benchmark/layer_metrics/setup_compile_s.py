"""Seconds before the window in which XLA or Mosaic compiled, or a compiled
program was fetched from the persistent cache (`jax.monitoring` spans,
merged)."""

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(run):
    return run["setup"]["compile_s"]
