"""Piece-wise timing of the tree MSM on the real chip: which stage owns the
per-MSM milliseconds (sort+gather / up-sweep / Fenwick+combine / Horner)?

Run on an idle machine (single TPU process):  python scripts/profile_msm.py
Prints one line per variant using the same marginal-cost methodology as
bench.py (jitted K-loop, host-sync fence).
"""

from __future__ import annotations

import os
import sys


sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_groth16_tpu.ops.constants import G1_GENERATOR, R
from distributed_groth16_tpu.ops.curve import g1
from distributed_groth16_tpu.ops import limb_kernels as lk
from distributed_groth16_tpu.ops.msm import encode_scalars_std

LOG2N = int(os.environ.get("PROF_LOG2N", "16"))
N = 1 << LOG2N
C = 8


from distributed_groth16_tpu.utils.benchtools import marginal_cost


def marginal(make_fn, *args):
    return marginal_cost(make_fn, args, reps=3)


def main():
    rng = np.random.default_rng(0)
    scalars = encode_scalars_std(
        [int.from_bytes(rng.bytes(40), "little") % R for _ in range(N)]
    )
    points = jnp.broadcast_to(g1().encode([G1_GENERATOR])[0], (N, 3, 16))
    g = lk.lg1()
    W = 256 // C

    def var_full(k):
        @jax.jit
        def run(points, scalars):
            acc = jnp.uint32(0)
            for i in range(k):
                acc += lk._msm_tree_jit.__wrapped__(
                    g, points, scalars ^ jnp.uint32(i), C, None
                ).sum(dtype=jnp.uint32)
            return acc

        return run

    def var_sort_gather(k):
        @jax.jit
        def run(points, scalars):
            lm = g.from_rowmajor(points)
            acc = jnp.uint32(0)
            for i in range(k):
                digits = lk._digits(scalars ^ jnp.uint32(i), C)  # (W, n)
                order = jnp.argsort(digits, axis=-1)
                gathered = jnp.take(lm, order.reshape(-1), axis=1)
                acc += gathered.sum(dtype=jnp.uint32)
            return acc

        return run

    def var_sort_only(k):
        @jax.jit
        def run(points, scalars):
            acc = jnp.uint32(0)
            for i in range(k):
                digits = lk._digits(scalars ^ jnp.uint32(i), C)
                order = jnp.argsort(digits, axis=-1)
                acc += order.sum(dtype=jnp.int32).astype(jnp.uint32)
            return acc

        return run

    def var_upsweep(k):
        # up-sweep only: tree adds over (48, W, n) without Fenwick/combine
        @jax.jit
        def run(points, scalars):
            lm = g.from_rowmajor(points)
            acc = jnp.uint32(0)
            for i in range(k):
                digits = lk._digits(scalars ^ jnp.uint32(i), C)
                order = jnp.argsort(digits, axis=-1)
                gathered = jnp.take(lm, order.reshape(-1), axis=1).reshape(
                    48, W, N
                )
                x = gathered
                while x.shape[-1] > 1:
                    half = x.shape[-1] // 2
                    pair = x.reshape(48, W, half, 2)
                    x = g.add(pair[..., 0], pair[..., 1])
                acc += x.sum(dtype=jnp.uint32)
            return acc

        return run

    full = marginal(var_full, points, scalars)
    sort_only = marginal(var_sort_only, points, scalars)
    sort_gather = marginal(var_sort_gather, points, scalars)
    upsweep = marginal(var_upsweep, points, scalars)
    print(f"n=2^{LOG2N} c={C}  (per-MSM marginal seconds)")
    print(f"full tree msm      : {full*1e3:9.1f} ms  ({N/full:,.0f} muls/s)")
    print(f"sort only          : {sort_only*1e3:9.1f} ms")
    print(f"sort+gather        : {sort_gather*1e3:9.1f} ms")
    print(f"sort+gather+upsweep: {upsweep*1e3:9.1f} ms")
    print(f"=> fenwick+combine+horner ≈ {(full-upsweep)*1e3:9.1f} ms")


if __name__ == "__main__":
    main()
