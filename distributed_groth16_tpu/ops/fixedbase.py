"""Windowed fixed-base scalar multiplication — the setup/CRS workhorse.

Every scalar multiplication in Groth16 setup shares ONE base (the G1/G2
generator), so the 256-step double-and-add ladder is wasteful: precompute
T[w][d] = d * 2^(c*w) * G once (host affine arithmetic, ops/refmath.py),
then each scalar costs W-1 = 31 batched complete additions of table
gathers — 16x fewer curve ops than the ladder, and a single add
instantiation (compile-light).

Replaces the per-element generator ladders of the reference's
circuit_specific_setup (the reference leans on arkworks
`fixed_base::FixedBase::msm` which uses the same windowed-table idea —
role parity, independent implementation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import refmath as rm
from .constants import G1_GENERATOR, G2_GENERATOR, LIMB_BITS
from .curve import CurvePoints, g1, g2

WINDOW_C = 8  # digits per window; divides the 16-bit limb
N_WINDOWS = 256 // WINDOW_C


def _host_table(host_ops, base_affine):
    """(W, 2^c) affine host points: row w holds d * 2^(c*w) * B."""
    rows = []
    bw = base_affine
    for _ in range(N_WINDOWS):
        row = [None, bw]
        for _ in range(2, 1 << WINDOW_C):
            row.append(host_ops.add(row[-1], bw))
        rows.append(row)
        for _ in range(WINDOW_C):
            bw = host_ops.double(bw)
    return rows


@functools.cache
def generator_table(which: str) -> jnp.ndarray:
    """Device table (W, 2^c, 3) + elem for the G1/G2 generator."""
    if which == "g1":
        rows = _host_table(rm.G1, G1_GENERATOR)
        curve = g1()
    else:
        rows = _host_table(rm.G2, G2_GENERATOR)
        curve = g2()
    flat = [p for row in rows for p in row]
    enc = curve.encode(flat)
    return enc.reshape((N_WINDOWS, 1 << WINDOW_C) + enc.shape[1:])


def _digits(scalars_std: jnp.ndarray) -> jnp.ndarray:
    """(n, 16) standard-form u32 limbs -> (n, W) int32 c-bit digits."""
    w = np.arange(N_WINDOWS)
    limb_idx = (w * WINDOW_C) // LIMB_BITS
    shift = jnp.asarray((w * WINDOW_C) % LIMB_BITS, jnp.uint32)
    limbs = scalars_std[:, limb_idx]
    return ((limbs >> shift) & ((1 << WINDOW_C) - 1)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(0,))
def _fixed_base_jit(curve: CurvePoints, table, scalars_std):
    digits = _digits(scalars_std)  # (n, W)
    n = scalars_std.shape[0]
    acc0 = jnp.broadcast_to(curve.infinity(), (n, 3) + curve.elem_shape)

    def body(w, acc):
        pts = table[w][digits[:, w]]  # (n, 3)+elem gather
        return curve.add(acc, pts)

    return jax.lax.fori_loop(0, N_WINDOWS, body, acc0)


# -- host-side windowed mul for arbitrary fixed bases ------------------------
# The verifier's prepare_inputs fallback (models/groth16/verify.py): each
# gamma_abc base is fixed per circuit and re-multiplied on every
# verification, so the same table idea pays on pure host bigint math. A
# narrower window keeps the one-time table build cheap: c=4 costs
# 64 x 14 = 896 adds to build and <= 63 adds + 63 doublings-equivalent
# gathers per mul, vs ~384 adds/doubles for one 256-bit ladder — the
# table wins from the third multiplication on a base onward.

_HOST_WINDOW_C = 4
_HOST_N_WINDOWS = 256 // _HOST_WINDOW_C


@functools.lru_cache(maxsize=256)
def _host_mul_table(which: str, base_affine):
    """(W, 2^c) affine host rows for ANY base: row w holds
    d * 2^(c*w) * B. Cached per (group, base) — affine points are nested
    int tuples, hence hashable."""
    host_ops = rm.G1 if which == "g1" else rm.G2
    rows = []
    bw = base_affine
    for _ in range(_HOST_N_WINDOWS):
        row = [None, bw]
        for _ in range(2, 1 << _HOST_WINDOW_C):
            row.append(host_ops.add(row[-1], bw))
        rows.append(row)
        for _ in range(_HOST_WINDOW_C):
            bw = host_ops.double(bw)
    return rows


def host_windowed_mul(which: str, base_affine, k: int):
    """k * base on host ("g1" | "g2") through the cached windowed table.
    None base (infinity) and k == 0 mod order return None, matching the
    refmath ladder."""
    host_ops = rm.G1 if which == "g1" else rm.G2
    k %= host_ops.order
    if base_affine is None or k == 0:
        return None
    rows = _host_mul_table(which, base_affine)
    mask = (1 << _HOST_WINDOW_C) - 1
    acc = None
    for w in range(_HOST_N_WINDOWS):
        d = (k >> (w * _HOST_WINDOW_C)) & mask
        if d:
            acc = host_ops.add(acc, rows[w][d])
    return acc


def fixed_base_mul(which: str, scalars_std, chunk: int = 1 << 19):
    """scalars (n, 16) standard-form u32 -> (n, 3)+elem projective points
    scalar * G on the named generator ("g1" | "g2"). Chunked to bound peak
    memory at million scale."""
    curve = g1() if which == "g1" else g2()
    table = generator_table(which)
    n = scalars_std.shape[0]
    if n <= chunk:
        return _fixed_base_jit(curve, table, scalars_std)
    parts = [
        _fixed_base_jit(curve, table, scalars_std[s : s + chunk])
        for s in range(0, n, chunk)
    ]
    return jnp.concatenate(parts, axis=0)
