"""Multi-scalar multiplication (Pippenger) for BN254 G1/G2 on JAX/TPU.

Computes sum_i s_i * P_i — the dominant kernel of the Groth16 prover (the
reference's per-party hot loop is arkworks `G::msm` at
dist-primitives/src/dmsm/mod.rs:82, called five times per proof:
S*a, V*a, W*ax, U*h, H*a — groth16/src/prove.rs; the served single-node
prover runs four, `models/groth16/prove.py:prove_single`).

TPU-first design — no scatter, no data-dependent control flow on the
device. One choice is made on the host, from data the host already holds:
a caller that has the scalars as integers (`encode_observed`) hands `msm`
their `WideScalars` view, and the tree path then runs limb-0 windows only
(`limb_kernels.msm_tree`); which of two static programs runs depends on
how many scalars are wider than 16 bits, as in arkworks' own MSM. Nothing
else here looks at a value.

  * windowed digits: each 254-bit scalar is split into W = 256/c digits of
    c bits (c | 16 so digits never straddle the uint16 limbs of ops/field.py).
  * bucket accumulation WITHOUT scatter: per window, points are sorted by
    digit (one argsort of int32 keys) and an inclusive prefix sum of the
    sorted points is taken under the branchless group law
    (`lax.associative_scan` — log-depth, fully batched adds). The sum of
    bucket b is then prefix[end_b] - prefix[end_{b-1}], and the classic
    weighted-bucket identity
        sum_b b * S_b = sum_{k=1..B-1} (T - C_{k-1})
    (T = sum of all points, C_j = prefix sum through bucket j) turns the
    whole window reduction into B batched complete-adds + one tree sum.
  * window combine is Horner: c doublings + 1 add per window.

Complete RCB16 formulas (ops/curve.py) make every add branchless, so the
entire MSM is one `jit`-compiled program of static shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from ..telemetry import tracing as _tracing
from .constants import LIMB_BITS, N_LIMBS
from .curve import CurvePoints, g1, g2
from .limb_kernels import WideScalars

# total scalar bits covered (BN254 Fr fits in 254 < 256)
_SCALAR_BITS = 256

# which implementation actually ran (docs/OBSERVABILITY.md): production
# dashboards catch a TPU mesh silently falling back to the generic path.
# Counted at dispatch time — under an enclosing jit that is once per
# traced signature, not per execution.
_ROUTE = _tm.registry().counter(
    "kernel_route_total",
    "Kernel-path routing decisions at dispatch/trace time, per kernel "
    "and chosen implementation path",
    ("kernel", "path"),
)
# pre-bound children (the metrics.py hot-path contract: one dict lookup
# + add per record, no per-call label-tuple allocation)
_R_TREE = _ROUTE.labels(kernel="msm", path="tree")
_R_TREE_LIMB0 = _ROUTE.labels(kernel="msm", path="tree_limb0")
_R_LADDER = _ROUTE.labels(kernel="msm", path="ladder")
_R_PIPPENGER = _ROUTE.labels(kernel="msm", path="pippenger")
_R_CHUNKED = _ROUTE.labels(kernel="msm", path="pippenger_chunked")
_RB_TREE = _ROUTE.labels(kernel="msm_batched", path="tree")
_RB_LADDER = _ROUTE.labels(kernel="msm_batched", path="ladder")
_RB_VMAP = _ROUTE.labels(kernel="msm_batched", path="pippenger_vmap")
_WIDE_CARRIED = _tm.registry().counter(
    "msm_wide_scalars_total",
    "Scalars wider than 16 bits that limb-0 tree MSMs carried beside "
    "their points (15 ladder points each), summed over launches",
)
_AFFINE_LEVELS = _tm.registry().counter(
    "msm_affine_levels_total",
    "Up-sweep levels that tree MSMs ran as batch-affine adds (the levels "
    "of at least limb_kernels._AFFINE_MIN_ADDS adds), summed over launches",
)
_LIMB0_DECLINED = _tm.registry().counter(
    "msm_limb0_declined_total",
    "Tree MSMs that were handed the host's view of their scalars and ran "
    "all windows all the same, by the reason the limb-0 form was declined",
    ("reason",),
)
_DECLINED_OVER_CAPACITY = _LIMB0_DECLINED.labels(reason="over_capacity")


def _digits_for_window(scalars, w, c: int):
    """Extract the w-th c-bit digit of each scalar. scalars: (n, 16) standard
    form; w may be traced. Returns (n,) int32 in [0, 2^c)."""
    per_limb = LIMB_BITS // c
    limb_idx = w // per_limb
    shift = (w % per_limb) * c
    limb = jax.lax.dynamic_index_in_dim(scalars, limb_idx, axis=-1, keepdims=False)
    return ((limb >> shift) & ((1 << c) - 1)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _msm_jit(curve: CurvePoints, points, scalars, c: int):
    n = points.shape[0]
    B = 1 << c
    W = _SCALAR_BITS // c
    inf = curve.infinity()

    def window_sum(w):
        digits = _digits_for_window(scalars, w, c)
        order = jnp.argsort(digits)
        d_sorted = jnp.take(digits, order, axis=0)
        p_sorted = jnp.take(points, order, axis=0)
        prefix = jax.lax.associative_scan(curve.add, p_sorted, axis=0)
        total = prefix[n - 1]
        # C_j = sum of points with digit <= j, for j = 0..B-2
        ends = jnp.searchsorted(d_sorted, jnp.arange(B - 1), side="right")
        cum = curve.select(
            ends > 0,
            jnp.take(prefix, jnp.maximum(ends - 1, 0), axis=0),
            jnp.broadcast_to(inf, (B - 1,) + inf.shape),
        )
        # sum_b b*S_b = sum_{j=0..B-2} (total - C_j)
        terms = curve.add(jnp.broadcast_to(total, cum.shape), curve.neg(cum))
        return curve.sum(terms, axis=0)

    def body(i, acc):
        w = W - 1 - i

        def dbl(_, a):
            return curve.double(a)

        acc = jax.lax.fori_loop(0, c, dbl, acc)
        return curve.add(acc, window_sum(w))

    return jax.lax.fori_loop(0, W, body, inf)


# below this point count the one-ladder MSM wins on compile time (2 curve-op
# instantiations vs ~10 for a Pippenger window body — each instance costs
# seconds of XLA:CPU compile) and its 256·n runtime is negligible anyway
_LADDER_MSM_MAX_N = 128


@functools.partial(jax.jit, static_argnums=(0,))
def _msm_ladder_jit(curve: CurvePoints, points, scalars):
    """Small-n MSM as one batched double-and-add ladder + a sequential
    accumulation: the compile-light path (1 add + 1 double + 1 acc-add
    instantiation). Same results as _msm_jit."""
    from .curve import scalar_bits

    acc = curve.scalar_mul_bits(points, scalar_bits(scalars))
    return curve.sum_sequential(acc, axis=0)


@functools.partial(jax.jit, static_argnums=(0,))
def _msm_batched_ladder_jit(curve: CurvePoints, bases, scalars):
    """`_msm_ladder_jit` over a batch axis: B small MSMs as one program
    (an eager `fori_loop` would be traced and compiled anew each call)."""
    from .curve import scalar_bits

    acc = curve.scalar_mul_bits(bases, scalar_bits(scalars))
    return curve.sum_sequential(acc, axis=1)


def _limb_group_for(curve: CurvePoints):
    """The LimbGroup factory matching this curve's base field + extension
    degree, or None for unsupported configurations. BN254 and
    BLS12-377/381 all ride the same limb machinery (LimbField is
    limb-count-generic as of r5)."""
    from . import limb_kernels as lk
    from .constants import Q as _BN254_Q

    base_p = curve.F.p if hasattr(curve.F, "p") else curve.F.fq.p
    ext2 = len(curve.elem_shape) == 2
    if base_p == _BN254_Q:
        return lk.lg2 if ext2 else lk.lg1
    from .bls12_377 import Q377
    from .bls12_381 import Q381

    if base_p == Q377 and not ext2:
        return lk.lg1_377
    if base_p == Q381:
        return lk.lg2_381 if ext2 else lk.lg1_381
    return None


def _tree_group(curve: CurvePoints, n: int):
    """The LimbGroup to run this MSM's limb-major tree path on, or None
    for the generic row-major path. TPU backends route every supported
    curve here — the Pallas fast path; DG16_FORCE_TREE_MSM=1 forces it
    anywhere (tests exercise the identical XLA bodies on CPU)."""
    from ..utils import config as _config

    factory = _limb_group_for(curve)
    if factory is None:
        return None
    if _config.env_flag("DG16_FORCE_TREE_MSM"):
        return factory()
    from .limb_kernels import use_pallas

    return factory() if (use_pallas() and n >= 1024) else None


def msm(curve: CurvePoints, points, scalars, window_bits: int | None = None,
        chunk: int | None = None, wide: WideScalars | None = None):
    """sum_i scalars[i] * points[i].

    points:  (n, 3) + elem_shape projective device points.
    scalars: (n, 16) uint32 limbs in STANDARD (non-Montgomery) form.
    window_bits: Pippenger window c (must divide 16); default auto.
    chunk: process points in chunks of this size (bounds peak memory; MSM is
           linear so chunk results just add).
    wide: the host's view of these same scalars, from `encode_observed`
          (`.tail(k)` for scalars[k:]). Only the tree path reads it: with
          it, and room for the wide scalars, the MSM runs the limb-0
          windows alone (route `msm/tree_limb0`); without it, as for
          every scalar array that lives on the device only (h, the MPC
          round's shares), all windows (route `msm/tree`). With it and no
          room (a witness that fills the field), all windows too, and
          `msm_limb0_declined_total` says so. The result is the same point
          every way.

    Returns a single projective point (3,) + elem_shape.
    """
    n = points.shape[0]
    # scalar layouts wider than 16 limbs (r381's 17-limb standard form)
    # are accepted: every supported scalar order is < 2^256, so the extra
    # limbs are zero; the tree path's digit decomposition is width-aware
    # and the Pippenger/ladder paths read 256 bits
    assert scalars.shape[-1] >= N_LIMBS and scalars.shape[0] == n
    # explicit window_bits/chunk pin the generic path (chunk in particular
    # is a memory bound the tree path would silently drop)
    tree_g = (
        _tree_group(curve, n) if window_bits is None and chunk is None
        else None
    )
    if tree_g is not None:
        from .limb_kernels import msm_tree, takes_limb0, tree_affine_levels

        limb0 = takes_limb0(tree_g, n, wide)
        if wide is not None:
            # a view came: the open span (`prove.A/B/C`) says which side
            # of the rule it put this launch on
            span = _tracing.current()
            if span is not None:
                span.note(route="tree_limb0" if limb0 else "tree")
        if limb0:
            _R_TREE_LIMB0.inc()
            _WIDE_CARRIED.inc(wide.count)
            _AFFINE_LEVELS.inc(tree_affine_levels(tree_g, n, 1))
            return msm_tree(points, scalars, group=tree_g, wide=wide)
        if wide is not None:
            # a view came and the rule said no: more wide scalars than the
            # padding has slots for. A call with no view is not counted.
            _DECLINED_OVER_CAPACITY.inc()
        _R_TREE.inc()
        _AFFINE_LEVELS.inc(tree_affine_levels(tree_g, n, scalars.shape[-1]))
        return msm_tree(points, scalars, group=tree_g)
    if window_bits is None and chunk is None and n <= _LADDER_MSM_MAX_N:
        _R_LADDER.inc()
        return _msm_ladder_jit(curve, points, scalars)
    if window_bits is None:
        # the sort+scan bucketing costs ~n log n adds per window, so fewer,
        # wider windows win once n dwarfs the 2^c bucket-combine cost
        window_bits = 16 if n >= (1 << 14) else 8 if n >= 64 else 4
    assert LIMB_BITS % window_bits == 0, "window must divide the 16-bit limb"
    if chunk is None or chunk >= n:
        _R_PIPPENGER.inc()
        return _msm_jit(curve, points, scalars, window_bits)
    _R_CHUNKED.inc()
    acc = curve.infinity()
    for s in range(0, n, chunk):
        part = _msm_jit(curve, points[s : s + chunk], scalars[s : s + chunk],
                        window_bits)
        acc = curve.add(acc, part)
    return acc


def msm_batched(curve: CurvePoints, bases, scalars_std):
    """B same-length MSMs: (B, n, 3)+elem x (B, n, 16) std-form scalars ->
    (B, 3)+elem. Single routing point shared with msm() (incl. the
    DG16_FORCE_TREE_MSM override): on the tree route ONE launch of the
    batched tree program (`limb_kernels.msm_tree_batched`: the B MSMs'
    windows folded into one, row b the sum msm() gives for row b), one
    batched ladder at small n, ONE vmapped Pippenger otherwise (a Python
    loop of Pippengers put B bodies in the traced graph and the m=4096
    mesh-prover compile took 13+ minutes).

    Each of the B MSMs counts as `msm/tree`, as it would alone; the launch
    counts once as `msm_batched/tree`."""
    B, n = scalars_std.shape[0], scalars_std.shape[1]
    tree_g = _tree_group(curve, n)
    if tree_g is not None:
        from .limb_kernels import msm_tree_batched, tree_affine_levels

        _RB_TREE.inc()
        _R_TREE.inc(B)
        _AFFINE_LEVELS.inc(
            tree_affine_levels(tree_g, n, scalars_std.shape[-1], B)
        )
        return msm_tree_batched(bases, scalars_std, tree_g)
    if n <= _LADDER_MSM_MAX_N:
        _RB_LADDER.inc()
        return _msm_batched_ladder_jit(curve, bases, scalars_std)
    _RB_VMAP.inc()
    wbits = 16 if n >= (1 << 14) else 8 if n >= 64 else 4
    return jax.vmap(lambda bs, sc: _msm_jit(curve, bs, sc, wbits))(
        bases, scalars_std
    )


def msm_g1(points, scalars, **kw):
    return msm(g1(), points, scalars, **kw)


def msm_g2(points, scalars, **kw):
    return msm(g2(), points, scalars, **kw)


def encode_observed(F, values) -> tuple[jnp.ndarray, WideScalars]:
    """`F.encode(values)` and, from the same reduced integers, the view
    of them that `msm(..., wide=)` takes: the one place the two are made,
    so that a view cannot disagree with the scalars it describes.

    The job's `encode` phase is this call. It costs one reduction pass,
    `F.encode` (a `to_bytes` a value, one buffer) and the view (one pass
    that finds the wide values, one buffer of those): all three follow n,
    the view's buffer K, and none runs a Python loop per limb."""
    reduced = [int(v) % F.p for v in values]
    return F.encode(reduced), WideScalars.observe(reduced)


def encode_scalars_std(values) -> jnp.ndarray:
    """Python ints -> (n, 16) standard-form uint32 limb array (host-side)."""
    import numpy as np

    from .constants import R, to_limbs

    vals = [int(v) % R for v in values]
    out = np.array([to_limbs(v) for v in vals], dtype=np.uint32)
    return jnp.asarray(out)
