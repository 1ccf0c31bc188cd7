"""Differential tests for the Pippenger MSM kernel vs pure-Python ground
truth (mirrors the reference's dmsm_test.rs / msm_bench.rs strategy of
checking against arkworks G::msm)."""

import random

import numpy as np

import pytest

from distributed_groth16_tpu.ops import refmath as rm
from distributed_groth16_tpu.ops.constants import G1_GENERATOR, G2_GENERATOR, R
from distributed_groth16_tpu.ops.curve import g1, g2
from distributed_groth16_tpu.ops.msm import encode_scalars_std, msm


def _rand_points(ops, gen, n, rng):
    ks = [rng.randrange(1, R) for _ in range(n)]
    return [ops.scalar_mul(gen, k) for k in ks]


@pytest.mark.parametrize("n", [1, 7, 64])
def test_msm_g1_matches_reference(n):
    rng = random.Random(1234 + n)
    pts = _rand_points(rm.G1, G1_GENERATOR, n, rng)
    scalars = [rng.randrange(0, R) for _ in range(n)]
    expected = rm.G1.msm(pts, scalars)

    C = g1()
    out = msm(C, C.encode(pts), encode_scalars_std(scalars))
    assert C.decode(out) == expected


def test_msm_g2_matches_reference():
    rng = random.Random(99)
    n = 17
    pts = _rand_points(rm.G2, G2_GENERATOR, n, rng)
    scalars = [rng.randrange(0, R) for _ in range(n)]
    expected = rm.G2.msm(pts, scalars)

    C = g2()
    out = msm(C, C.encode(pts), encode_scalars_std(scalars))
    assert C.decode(out) == expected


def test_msm_edge_cases():
    C = g1()
    rng = random.Random(7)
    pts = _rand_points(rm.G1, G1_GENERATOR, 8, rng)
    # zero scalars, scalar 1, repeated points, infinity among inputs
    scalars = [0, 1, 2, 0, R - 1, 5, 5, 3]
    pts[3] = None  # infinity input
    pts[6] = pts[5]
    expected = rm.G1.msm(pts, scalars)
    out = msm(C, C.encode(pts), encode_scalars_std(scalars))
    assert C.decode(out) == expected


def test_msm_all_zero_scalars():
    C = g1()
    rng = random.Random(3)
    pts = _rand_points(rm.G1, G1_GENERATOR, 4, rng)
    out = msm(C, C.encode(pts), encode_scalars_std([0, 0, 0, 0]))
    assert C.decode(out) is None


def test_msm_chunked_matches_unchunked():
    C = g1()
    rng = random.Random(11)
    pts = _rand_points(rm.G1, G1_GENERATOR, 20, rng)
    scalars = [rng.randrange(0, R) for _ in range(20)]
    enc_p, enc_s = C.encode(pts), encode_scalars_std(scalars)
    a = C.decode(msm(C, enc_p, enc_s))
    b = C.decode(msm(C, enc_p, enc_s, chunk=6))
    assert a == b == rm.G1.msm(pts, scalars)


# msm_batched's cases over G1, each in a subprocess of its own: (B, n, the
# tree route forced, `affine_min_adds` where the case forces affine levels).
# "routes" is every route at B = 3: the ladder (16), the vmapped
# Pippenger (192) and the tree (64), each row against a per-call msm(); the
# others are the batched tree program alone, each row against the host's
# MSM. Every row's scalars hold 0, 1, r - 1, bits and values about 2^16.
# No G2 case: a G2 tree program costs 130-210 s of XLA:CPU compile
# whatever its size, and the batching is the same code for both groups;
# `tests/test_dmsm_rendezvous.py` proves a round through the G2 batched
# program (B = 4) against the single-node proof. n = 12 pads to 16.
_BATCHED_CASES = {
    "routes": (3, None, None, None),
    "tree_g1_b1": (1, 16, True, None),
    "tree_g1_b8": (8, 12, True, None),
    "tree_g1_b3_affine": (3, 16, True, 16),
}


@pytest.mark.parametrize("case", sorted(_BATCHED_CASES))
def test_msm_batched_matches_per_call(case):
    """msm_batched must agree with the MSM of each row on every routing
    path: ladder, vmapped Pippenger, and (via the force override) the tree
    path, which is one launch of the batched tree program for all B rows.
    Runs in a FRESH subprocess: this jax's XLA:CPU compiler segfaults
    compiling the vmapped Pippenger once enough executables are live in a
    long-lived process (the same state-dependent crash documented in
    utils/cache.py), so in-suite execution is not reliable."""
    import os
    import subprocess
    import sys

    script = r"""
import sys
sys.path.insert(0, "@@ROOT@@")
import numpy as np
import jax.numpy as jnp
from distributed_groth16_tpu.ops import limb_kernels as lk
from distributed_groth16_tpu.ops import refmath as rm
from distributed_groth16_tpu.ops.constants import G1_GENERATOR, R
from distributed_groth16_tpu.ops.curve import g1
from distributed_groth16_tpu.ops.msm import encode_scalars_std, msm, msm_batched
import os
B, n, force_tree, affine_min_adds = @@CASE@@
C = g1()
rng = np.random.default_rng(7)
EDGE = [0, 1, R - 1, 0, 1, (1 << 16) - 1, 1 << 16]


def rows(n):
    scal = [[EDGE[(i + b) % len(EDGE)] if i % 2 == 0
             else int.from_bytes(rng.bytes(40), "little") % R
             for i in range(n)] for b in range(B)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, 1 + int(rng.integers(1, 1 << 30)))
           for _ in range(B * n)]
    bases = C.encode(pts).reshape((B, n) + C.infinity().shape)
    std = jnp.stack([encode_scalars_std(s) for s in scal])
    return scal, pts, bases, std


if n is None:  # every route against a per-call msm()
    for n, force in ((16, False), (192, False), (64, True)):
        os.environ.pop("DG16_FORCE_TREE_MSM", None)
        if force:
            os.environ["DG16_FORCE_TREE_MSM"] = "1"
        scal, pts, bases, std = rows(n)
        out = msm_batched(C, bases, std)
        for b in range(B):
            exp = msm(C, bases[b], std[b])
            assert bool(jnp.all(C.eq(out[b], exp))), (n, b, force)
else:  # the batched tree program against the host's MSM of each row
    os.environ["DG16_FORCE_TREE_MSM"] = "1"
    scal, pts, bases, std = rows(n)
    if affine_min_adds is None:
        out = msm_batched(C, bases, std)
    else:
        c = lk._tree_window_bits(n)
        windows = B * 16 * 16 // c
        assert all(lk._affine_depths(
            windows, windows, lk._tree_npad(n), affine_min_adds
        ))
        out = lk._MSM_TREE_BATCHED_JITS["g1"](
            lk.lg1(), bases, std, c, None, affine_min_adds
        )
    assert out.shape == (B,) + C.infinity().shape
    for b in range(B):
        assert C.decode(out[b]) == rm.G1.msm(pts[b * n:(b + 1) * n], scal[b]), b
print("BATCHED_OK")
""".replace("@@ROOT@@", os.path.join(os.path.dirname(__file__), "..")).replace(
        "@@CASE@@", repr(_BATCHED_CASES[case])
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BATCHED_OK" in r.stdout


_ENCODE_OBSERVED_CASES = {
    "bits": lambda rng: [rng.randrange(2) for _ in range(300)] + [R - 3],
    "field-and-above-r": lambda rng: (
        [rng.randrange(R) for _ in range(500)] + [R, R + (1 << 16), 2 * R - 1]),
    "empty": lambda rng: [],
}


@pytest.mark.parametrize("case", sorted(_ENCODE_OBSERVED_CASES))
def test_encode_observed_uploads_what_encode_does_and_views_the_same_integers(
        case):
    """The witness-upload boundary: `z_mont` is `F.encode` of the same
    values limb for limb, and the view is of those values reduced."""
    from distributed_groth16_tpu.ops.constants import from_limbs
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.ops.msm import encode_observed

    vals = _ENCODE_OBSERVED_CASES[case](random.Random(31))
    z_mont, view = encode_observed(fr(), vals)
    assert z_mont.dtype == np.uint32 and z_mont.shape == (len(vals), 16)
    assert np.array_equal(np.asarray(z_mont), fr().encode_np(vals))
    reduced = [v % R for v in vals]
    assert view.n == len(vals)
    assert view.idx.tolist() == [i for i, v in enumerate(reduced) if v >> 16]
    for i, row in zip(view.idx, view.limbs):
        assert from_limbs(row) == reduced[i] >> 16


def test_msm_g2_limb0_windows_match_reference(monkeypatch):
    """The G2 case of tests/test_limb_kernels.py's limb-0 cases (here so
    that its minutes of XLA:CPU compile run beside that file's, not after
    them): bits and one wide scalar, the most that fits beside 37 points,
    through `msm` on the tree path."""
    from distributed_groth16_tpu.ops import limb_kernels as lk
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.ops.msm import encode_observed
    from distributed_groth16_tpu.telemetry import metrics

    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    rng = random.Random(24)
    n = 37
    pts = [rm.G2.scalar_mul(G2_GENERATOR, rng.randrange(1, 2**61))
           for _ in range(n)]
    vals = [rng.randrange(2) for _ in range(n)]
    vals[36] = R - 3
    z_mont, view = encode_observed(fr(), vals)
    assert view.count == lk.wide_capacity(lk.lg2(), n) == 1
    routes = metrics.registry().family("kernel_route_total")
    limb0 = routes.labels(kernel="msm", path="tree_limb0")
    before = limb0.value
    full_programs = lk._MSM_TREE_JITS["g2"]._cache_size()
    C = g2()
    out = msm(C, C.encode(pts), fr().from_mont(z_mont), wide=view)
    assert C.decode(out) == rm.G2.msm(pts, vals)
    assert limb0.value == before + 1
    assert lk._MSM_TREE_JITS["g2"]._cache_size() == full_programs


@pytest.mark.parametrize("kind", ["bits"])
def test_prove_single_takes_the_route_its_witness_asks_for(kind, monkeypatch):
    """A whole proof on the tree path, G2 included, byte for byte the plain
    reference prover's: a witness of bits and one wide public wire takes
    the limb-0 form for A, B and L and the full width for h (the cells of
    `sha256-bn254-single`). The `field` case of the same test is in
    tests/test_limb_kernels.py, beside the full-width G2 program it needs;
    this one takes the G2 limb-0 programs the test above compiled (37
    points). The body: tests/prove_routes.py."""
    from prove_routes import check_prove_single_routes

    check_prove_single_routes(kind, monkeypatch)
