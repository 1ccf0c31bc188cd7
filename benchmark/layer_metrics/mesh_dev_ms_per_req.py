"""Device trace: time of the launches of the group
`kernel_groups/mesh_prover.json`, the one SPMD program of an
`mpc_prove` round on several chips, per job and per chip. The reduction
adds up every chip's launches (`trace_reduce.reduce_trace`), so the sum is
divided by the cell's chips, as `busy_s` is. Nothing to read where no such
program ran: a round on one chip, or a program without the mesh round."""

from ._groups import group_ms

LAYER, UNIT, MOVES = "prover", "ms", "proof_p50_s"


def read(run):
    ms = group_ms(run, "mesh_prover")
    return None if ms is None else ms / run["cell"]["chips"]
