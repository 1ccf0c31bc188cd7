"""The multiplicative chain of upstream's fixtures/million/million.circom:
x_{i+1} = x_i^2 + x_i, `length` constraints, the last value public. At a
tiny length it is what `rehearse.py` swaps in; at 2^20 it is upstream's
`million` configuration (ROADMAP R2), which no cell runs yet."""

from __future__ import annotations

from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit
from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns


def _cs(params: dict, x0: int):
    return mult_chain_circuit(x0, int(params["length"]))


def r1cs(params: dict) -> bytes:
    return write_r1cs(_cs(params, 3).finish()[0])


def witness(params: dict, pool_seed: int, i: int) -> tuple[bytes, list[int]]:
    r1, z = _cs(params, 3 + 1000 * pool_seed + i).finish()
    return write_wtns(z), [int(x) for x in z[1:r1.num_instance]]
