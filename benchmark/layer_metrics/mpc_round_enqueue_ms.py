"""Status DTO phases["MPC Proof.round"], median (since PR 32): inside `MPC
Proof`, `run_round_with_retries`: the asyncio round in which the host
issues the eight parties' work, 32 tree MSMs, the transforms and the king's
eagerly dispatched functions. WALL from the first enqueue to the last:
nothing in it reads a device value back, so it ends when the last launch
is enqueued, not when it has run; but on the chip (PR 32) an enqueue
returns only as the device catches up, so it holds the device's
back-pressure as well as the host's Python and is no measure of host
dispatch cost. Beside `mpc_round_drain_ms` it says how far ahead of the
chip the host ends (0.11 s), not whose work the request waits for:
`dev_busy_ms_per_req` against `mpc_round_ms` says that.
None where the program has no such key, as the parent of that PR has
not."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "prover", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "MPC Proof.round")
