"""Backend compilations begun inside the window. Must be 0: the run is not
`correct` otherwise."""

LAYER, UNIT, MOVES = "set-up", "count", "setup_s"


def read(run):
    return run["compiles_in_window"]
