"""Seconds the run spent making what `.bench_cache/` lacked: the service's
`setup` through POST /save_circuit, and a verifying cell's proofs. Near 0
in every run but a checkout's first."""

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(run):
    return run["setup"]["artefacts_s"]
