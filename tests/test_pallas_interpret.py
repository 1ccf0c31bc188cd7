"""Execute the REAL Pallas call-sites (grids, BlockSpecs, kernel bodies
with the fori-rolled formulations) under Mosaic interpret mode on CPU.

Everything else in the CPU suite exercises the plain-XLA fallback bodies;
the pallas_call plumbing itself (block slicing, grid iteration) had zero
coverage off-TPU — the NTT lane tile that could never have lowered (minor
dim 64 vs Mosaic's 128 requirement) survived three rounds that way. Interpret mode runs the
pallas_call semantics with numpy, so these tests catch BlockSpec/grid
logic bugs without a chip. Small shapes only: interpret mode is slow."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from distributed_groth16_tpu.ops import limb_kernels as lk  # noqa: E402
from distributed_groth16_tpu.ops.constants import (  # noqa: E402
    G1_GENERATOR,
    R,
)
from distributed_groth16_tpu.ops.curve import g1 as g1_rm  # noqa: E402


def _clear_trace_caches():
    """The pallas-vs-xla choice is baked into traced programs at trace
    time, and several live in process-global caches (the tree MSM programs'
    jit caches, the functools-cached LimbGroup._horner). Clear them on both
    sides of the fixture so (a) these tests don't silently reuse
    XLA-flavored traces from earlier suite files with the same shapes and
    (b) Pallas-flavored traces don't leak to later CPU tests."""
    for jitted in lk._MSM_TREE_JITS.values():
        jitted.clear_cache()
    try:
        lk.LimbGroup._horner.cache_clear()
    except Exception:
        pass


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Force the Pallas path (in BOTH consuming modules — ntt_limb binds
    use_pallas by from-import) and run under TPU interpret mode."""
    import distributed_groth16_tpu.ops.ntt_limb as nl

    monkeypatch.setattr(lk, "use_pallas", lambda: True)
    monkeypatch.setattr(nl, "use_pallas", lambda: True)
    _clear_trace_caches()
    with pltpu.force_tpu_interpret_mode():
        yield
    _clear_trace_caches()


def _points(n):
    """Host points (i+1)*G and their device encoding."""
    from distributed_groth16_tpu.ops import refmath as rm

    pts = [rm.G1.scalar_mul(G1_GENERATOR, i + 1) for i in range(n)]
    return pts, g1_rm().encode(pts)


def test_pallas_add_kernel_interpret(pallas_interpret):
    g = lk.lg1()
    n = g.tile  # one full tile = one grid step
    _, dev = _points(1)
    lm = g.from_rowmajor(jnp.broadcast_to(dev[0], (n, 3, 16)))
    out_pallas = np.asarray(g._pallas_add(lm, lm))
    out_xla = np.asarray(g._xla_add(lm, lm))
    assert (out_pallas == out_xla).all()


def test_pallas_double_kernel_interpret(pallas_interpret):
    g = lk.lg1()
    n = g.tile
    _, dev = _points(2)
    lm = g.from_rowmajor(jnp.broadcast_to(dev[1], (n, 3, 16)))
    assert (
        np.asarray(g._pallas_double(lm)) == np.asarray(g._xla_double(lm))
    ).all()


@pytest.mark.parametrize("kind", ["g1", "g2"])
def test_affine_level_kernels_interpret(pallas_interpret, kind):
    """The four kernels of a batch-affine level at one lane tile, against
    the XLA bodies of the same formulas: the field product, the affine
    add's two halves on lanes that hold every case (a generic pair, an
    operand at infinity, a doubling, a point and its negative) and the
    root of the batched inversion."""
    from distributed_groth16_tpu.ops import refmath as rm
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g2 as g2_rm

    g, C, host, gen = {
        "g1": (lk.lg1(), g1_rm(), rm.G1, G1_GENERATOR),
        "g2": (lk.lg2(), g2_rm(), rm.G2, G2_GENERATOR),
    }[kind]
    T, CR = g.tile, g.CR
    P, Q = host.scalar_mul(gen, 2), host.scalar_mul(gen, 9)
    left = [P, None, P, P, Q, None]
    right = [Q, Q, None, P, host.neg(Q), None]

    def lanes(pts):
        lm = g.from_rowmajor(C.encode(pts))
        return jnp.tile(lm, (1, T // len(pts) + 1))[:, :T]

    a1, a2 = g.normalise(lanes(left)), g.normalise(lanes(right))
    nc, den = g._pallas_affine_pre(a1, a2)
    nc_x, den_x = g._xla_affine_pre(a1, a2)
    assert (np.asarray(nc) == np.asarray(nc_x)).all()
    assert (np.asarray(den) == np.asarray(den_x)).all()
    assert sorted(set(np.asarray(nc[CR]).tolist())) == [0, 1, 2, 3]
    inv = g._pallas_root_inverse(den)
    assert (np.asarray(inv) == np.asarray(g._xla_root_inverse(den))).all()
    one = np.asarray(g._pallas_fmul(den, inv))
    assert (np.asarray(g.F.canon_rows(one)) == g.one_col).all()
    assert (one == np.asarray(g._xla_fmul(den, inv))).all()
    out = np.asarray(g._pallas_affine_post(a1, a2, nc, inv))
    assert (out == np.asarray(g._xla_affine_post(a1, a2, nc, inv))).all()
    got = C.decode(g.to_rowmajor(g.lift(jnp.asarray(out[:, : len(left)]))))
    assert got == [host.add(a, b) for a, b in zip(left, right)]


def test_msm_tree_interpret_matches_host(pallas_interpret):
    from distributed_groth16_tpu.ops import refmath as rm
    from distributed_groth16_tpu.ops.limb_kernels import msm_tree
    from distributed_groth16_tpu.ops.msm import encode_scalars_std

    rng = np.random.default_rng(11)
    n = 64
    pts, dev = _points(n)
    scal = [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
    out = msm_tree(dev, encode_scalars_std(scal))
    got = g1_rm().decode(np.asarray(out)[None])[0]
    assert got == rm.G1.msm(pts, scal)


def test_ntt_limb_pallas_interpret(pallas_interpret):
    import distributed_groth16_tpu.ops.ntt_limb as nl
    from distributed_groth16_tpu.ops import refmath as rm
    from distributed_groth16_tpu.ops.field import fr

    # batch wide enough to hit the Pallas lane-tile branch (L % 128 == 0)
    n, L = 64, 128
    rng = np.random.default_rng(12)
    coeffs = [
        [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
        for _ in range(L)
    ]
    small = nl._small(n, False)
    # (16, n, L) limb-major batched columns
    x = jnp.stack(
        [jnp.transpose(fr().encode(c)) for c in coeffs], axis=2
    )
    out = np.asarray(small(x))
    host = [rm.Domain(n).fft(c) for c in coeffs]
    F = nl.lfr()
    dec = np.asarray(
        jnp.transpose(F.canon(jnp.asarray(out).reshape(16, -1))).reshape(
            n, L, 16
        )
    )
    # decode column j, row i -> host[j][i]
    got = fr().decode(np.transpose(dec, (1, 0, 2)).reshape(-1, 16))
    want = [v for c in host for v in c]
    assert list(got) == want


def test_ntt_unaligned_lane_count_rides_the_kernel(pallas_interpret,
                                                   monkeypatch):
    """A lane count that is not a multiple of the 128-lane tile (small n
    at one four-step level) is padded into the Pallas kernel — there is
    no silent XLA substitute behind the ntt/limb route label."""
    import distributed_groth16_tpu.ops.ntt_limb as nl

    S, L = 8, 24
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.integers(0, 1 << 16, size=(16, S, L),
                                 dtype=np.uint32))
    small = nl._SmallNTT(S, False)
    want = np.asarray(small._xla(x))

    def no_xla(*a):
        raise AssertionError("XLA body used on the Pallas path")

    monkeypatch.setitem(small.__dict__, "_xla", no_xla)
    got = np.asarray(small(x))
    assert got.shape == (16, S, L) and (got == want).all()
