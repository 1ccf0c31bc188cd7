"""CRS in-the-exponent packing micro-bench (the million-workload CPU
bottleneck: 74-84% of wall-clock rode the row-major ladders; on TPU the
same packexp ladders ride the limb-major Pallas kernels).

Times pp.packexp_from_public over BN254 G1 at --log2-m points (the S-query
shape: m points packed l at a time into n-share groups), reporting
points/sec and the jit-compile split. Compare against the per-proof MSM
time at the same m: the done-bar is packing <= prove.

Usage: python scripts/profile_packing.py [--log2-m 15] [--n 8] [--l 2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--log2-m", type=int, default=15)
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--l", type=int, default=2)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_groth16_tpu.ops.constants import G1_GENERATOR
    from distributed_groth16_tpu.ops.curve import g1
    from distributed_groth16_tpu.parallel.pss import PackedSharingParams

    plat = jax.devices()[0].platform
    m = 1 << args.log2_m
    assert args.n == 4 * args.l, "PSS requires n = 4l"
    pp = PackedSharingParams(args.l)
    C1 = g1()

    # m points arranged (m/l, l) for pack-consecutive semantics
    base = C1.encode([G1_GENERATOR])[0]
    pts = jnp.broadcast_to(base, (m // args.l, args.l, 3, 16))

    t0 = time.time()
    out = pp.packexp_from_public(C1, pts)
    np.asarray(out)  # host sync = compile + first run
    cold = time.time() - t0

    t0 = time.time()
    out = pp.packexp_from_public(C1, pts)
    np.asarray(out)
    warm = time.time() - t0

    # scalar route (r5): what the same m costs when the dealer knows the
    # discrete logs — field-NTT pack + windowed fixed-base
    # (models/groth16/proving_key.py _pack_query_scalars)
    from distributed_groth16_tpu.models.groth16.proving_key import (
        _pack_query_scalars,
    )
    from distributed_groth16_tpu.ops.field import fr

    scal = fr().encode(list(range(2, m + 2)))
    t0 = time.time()
    outs = _pack_query_scalars("g1", pp, scal)
    np.asarray(outs)
    scalar_cold = time.time() - t0
    t0 = time.time()
    outs = _pack_query_scalars("g1", pp, scal)
    np.asarray(outs)
    scalar_warm = time.time() - t0

    print(
        json.dumps(
            {
                "metric": "crs_packexp_points_per_sec",
                "platform": plat,
                "log2_m": args.log2_m,
                "n": args.n,
                "l": args.l,
                "warm_s": round(warm, 2),
                "cold_s": round(cold, 2),
                "points_per_sec": round(m / warm, 1),
                "scalar_route_warm_s": round(scalar_warm, 2),
                "scalar_route_cold_s": round(scalar_cold, 2),
                "scalar_route_points_per_sec": round(m / scalar_warm, 1),
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
