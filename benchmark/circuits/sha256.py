"""The one-block SHA-256 circuit of upstream's groth16/examples/sha256.rs
(27,810 constraints, 27,627 wires, QAP domain 32768), built by the
program's `frontend/sha256.py` from a message drawn from the pool seed.
The constraints do not depend on the message."""

from __future__ import annotations

from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
from distributed_groth16_tpu.frontend.sha256 import sha256_circuit


def _message(pool_seed: int, i: int) -> bytes:
    # at most 55 bytes: one block after padding
    return f"dg16 bench pool {pool_seed}/{i}".encode()


def r1cs(params: dict) -> bytes:
    cs, _ = sha256_circuit(_message(0, 0))
    return write_r1cs(cs.finish()[0])


def witness(params: dict, pool_seed: int, i: int) -> tuple[bytes, list[int]]:
    cs, publics = sha256_circuit(_message(pool_seed, i))
    return write_wtns(cs.finish()[1]), [int(x) for x in publics]
