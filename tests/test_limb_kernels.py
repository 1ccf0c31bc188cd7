"""Limb-major kernel path (ops/limb_kernels.py) vs the row-major reference
implementations. On CPU these exercise the exact jnp bodies the Pallas TPU
kernels compile; the math is identical on both backends."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_groth16_tpu.ops.constants import G1_GENERATOR, Q, R, to_limbs
from distributed_groth16_tpu.ops.curve import g1
from distributed_groth16_tpu.ops.field import fq
from distributed_groth16_tpu.ops.limb_kernels import lfq, lg1, msm_tree, _digits
from distributed_groth16_tpu.ops.msm import encode_scalars_std, msm
from distributed_groth16_tpu.ops import refmath as rm


def _rand_field(rng, n):
    return [int.from_bytes(rng.bytes(40), "little") % Q for _ in range(n)]


def test_limb_field_mul_add_sub():
    F = fq()
    L = lfq()
    rng = np.random.default_rng(1)
    av, bv = _rand_field(rng, 7), _rand_field(rng, 7)
    a = jnp.transpose(F.encode(av))  # (16, 7) limb-major Montgomery
    b = jnp.transpose(F.encode(bv))
    p = jnp.asarray(L.p_col)
    p2 = jnp.asarray(L.p2_col)
    got_mul = F.decode(jnp.transpose(L.canon(L.mul(a, b, p))))
    got_add = F.decode(jnp.transpose(L.canon(L.add(a, b, p2))))
    got_sub = F.decode(jnp.transpose(L.canon(L.sub(a, b, p2))))
    for i, (x, y) in enumerate(zip(av, bv)):
        assert got_mul[i] == x * y % Q
        assert got_add[i] == (x + y) % Q
        assert got_sub[i] == (x - y) % Q


def test_limb_g1_add_double_matches_curve():
    C = g1()
    g = lg1()
    rng = np.random.default_rng(2)
    ks = [int(x) for x in rng.integers(1, 2**60, size=5)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks]
    qts = [rm.G1.scalar_mul(G1_GENERATOR, k + 1) for k in ks]
    P = C.encode(pts)
    Qp = C.encode(qts)
    lmP = g.from_rowmajor(P)
    lmQ = g.from_rowmajor(Qp)
    got = C.decode(g.to_rowmajor(g.add(lmP, lmQ)))
    want = C.decode(C.add(P, Qp))
    assert got == want
    got2 = C.decode(g.to_rowmajor(g.double(lmP)))
    want2 = C.decode(C.double(P))
    assert got2 == want2


def test_limb_g1_add_handles_infinity_and_doubling():
    C = g1()
    g = lg1()
    P = C.encode([rm.G1.scalar_mul(G1_GENERATOR, 12345), None, G1_GENERATOR])
    Qp = C.encode([None, rm.G1.scalar_mul(G1_GENERATOR, 777), G1_GENERATOR])
    got = C.decode(g.to_rowmajor(g.add(g.from_rowmajor(P), g.from_rowmajor(Qp))))
    want = [
        rm.G1.scalar_mul(G1_GENERATOR, 12345),
        rm.G1.scalar_mul(G1_GENERATOR, 777),
        rm.G1.scalar_mul(G1_GENERATOR, 2),
    ]
    assert got == want


def test_digits_roundtrip():
    rng = np.random.default_rng(3)
    vals = [int.from_bytes(rng.bytes(31), "little") for _ in range(9)]
    sc = encode_scalars_std(vals)
    d = np.asarray(_digits(sc, 8))  # (32, 9)
    for j, v in enumerate(vals):
        rec = sum(int(d[w, j]) << (8 * w) for w in range(32))
        assert rec == v % R


def test_msm_tree_matches_reference():
    C = g1()
    g = lg1()
    rng = np.random.default_rng(4)
    n = 300  # non-power-of-two exercises padding
    ks = [int(x) for x in rng.integers(1, 2**61, size=n)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks]
    scs = [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
    P = C.encode(pts)
    sc = encode_scalars_std(scs)
    got = C.decode(msm_tree(P, sc)[None])[0]
    want = rm.G1.msm(pts, scs)
    assert got == want


def test_msm_tree_window_groups():
    """Explicit window_group < W exercises the grouped-window loop — the
    path the 2^20 bench takes (npad > 2^17 auto-selects groups of 8) but
    that the auto heuristic never triggers at test sizes."""
    C = g1()
    rng = np.random.default_rng(14)
    n = 96
    ks = [int(x) for x in rng.integers(1, 2**61, size=n)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks]
    scs = [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
    P = C.encode(pts)
    sc = encode_scalars_std(scs)
    want = rm.G1.msm(pts, scs)
    for wg in (32, 24):  # W=64 at c=4: even (2 groups) and ragged
        # (24/24/16) splits; small GROUP COUNTS matter — each group
        # repeats the whole tree subgraph, so wg=2 (32 groups) is a
        # pathological compile, not a useful test
        got = C.decode(msm_tree(P, sc, 4, wg)[None])[0]
        assert got == want, wg


def test_msm_routing_forced(monkeypatch):
    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    C = g1()
    rng = np.random.default_rng(5)
    n = 64
    ks = [int(x) for x in rng.integers(1, 2**50, size=n)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks]
    scs = [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
    P = C.encode(pts)
    sc = encode_scalars_std(scs)
    got = C.decode(msm(C, P, sc)[None])[0]
    assert got == rm.G1.msm(pts, scs)


def test_horner_combine():
    """Window-combine kernel: sum_w 2^(8w) S_w."""
    C = g1()
    g = lg1()
    rng = np.random.default_rng(6)
    ks = [int(x) for x in rng.integers(1, 2**40, size=4)]
    pts = [rm.G1.scalar_mul(G1_GENERATOR, k) for k in ks]
    s = g.from_rowmajor(C.encode(pts))  # (48, 4)
    got = C.decode(g.to_rowmajor(g.horner(s, 8)))[0]
    want = rm.G1.msm(pts, [1, 1 << 8, 1 << 16, 1 << 24])
    assert got == want


# -- G2 / Fq2 limb path ------------------------------------------------------


def test_limb_fq2_mul_add_sub():
    from distributed_groth16_tpu.ops.field import fq2
    from distributed_groth16_tpu.ops.limb_kernels import lfq2

    F2 = fq2()
    L2 = lfq2()
    rng = np.random.default_rng(7)
    n = 5
    av = [(r % Q, i % Q) for r, i in zip(_rand_field(rng, n), _rand_field(rng, n))]
    bv = [(r % Q, i % Q) for r, i in zip(_rand_field(rng, n), _rand_field(rng, n))]
    # limb-major (32, n): rows 0-15 c0, 16-31 c1
    enc_a = F2.encode(av)  # (n, 2, 16)
    enc_b = F2.encode(bv)
    a = jnp.transpose(enc_a.reshape(n, 32))
    b = jnp.transpose(enc_b.reshape(n, 32))
    p = jnp.asarray(L2.p_col)
    p2 = jnp.asarray(L2.p2_col)
    mul, add, sub = L2.make_ops(p, p2)
    got_mul = F2.decode(
        jnp.transpose(L2.canon_rows(mul(a, b))).reshape(n, 2, 16)
    )
    got_add = F2.decode(
        jnp.transpose(L2.canon_rows(add(a, b))).reshape(n, 2, 16)
    )
    got_sub = F2.decode(
        jnp.transpose(L2.canon_rows(sub(a, b))).reshape(n, 2, 16)
    )
    for i, (x, y) in enumerate(zip(av, bv)):
        assert tuple(got_mul[i]) == rm.fq2_mul(x, y)
        assert tuple(got_add[i]) == rm.fq2_add(x, y)
        assert tuple(got_sub[i]) == rm.fq2_sub(x, y)


def test_limb_g2_add_double_matches_curve():
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g2
    from distributed_groth16_tpu.ops.limb_kernels import lg2

    C = g2()
    g = lg2()
    rng = np.random.default_rng(8)
    ks = [int(x) for x in rng.integers(1, 2**60, size=3)]
    pts = [rm.G2.scalar_mul(G2_GENERATOR, k) for k in ks]
    qts = [rm.G2.scalar_mul(G2_GENERATOR, k + 1) for k in ks]
    P = C.encode(pts)
    Qp = C.encode(qts)
    got = C.decode(g.to_rowmajor(g.add(g.from_rowmajor(P), g.from_rowmajor(Qp))))
    want = C.decode(C.add(P, Qp))
    assert got == want
    got2 = C.decode(g.to_rowmajor(g.double(g.from_rowmajor(P))))
    want2 = C.decode(C.double(P))
    assert got2 == want2


def test_limb_g2_infinity_cases():
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g2
    from distributed_groth16_tpu.ops.limb_kernels import lg2

    C = g2()
    g = lg2()
    P = C.encode([rm.G2.scalar_mul(G2_GENERATOR, 99), None, G2_GENERATOR])
    Qp = C.encode([None, G2_GENERATOR, G2_GENERATOR])
    got = C.decode(
        g.to_rowmajor(g.add(g.from_rowmajor(P), g.from_rowmajor(Qp)))
    )
    want = [
        rm.G2.scalar_mul(G2_GENERATOR, 99),
        G2_GENERATOR,
        rm.G2.scalar_mul(G2_GENERATOR, 2),
    ]
    assert got == want


def test_msm_tree_g2_matches_reference():
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g2

    C = g2()
    rng = np.random.default_rng(9)
    n = 37  # non-power-of-two exercises padding
    ks = [int(x) for x in rng.integers(1, 2**61, size=n)]
    pts = [rm.G2.scalar_mul(G2_GENERATOR, k) for k in ks]
    scs = [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
    P = C.encode(pts)
    sc = encode_scalars_std(scs)
    got = C.decode(msm_tree(P, sc)[None])[0]
    want = rm.G2.msm(pts, scs)
    assert got == want


def test_msm_routing_forced_g2(monkeypatch):
    from distributed_groth16_tpu.ops.constants import G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g2

    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    C = g2()
    rng = np.random.default_rng(10)
    n = 37  # the length test_msm_tree_g2_matches_reference compiled
    ks = [int(x) for x in rng.integers(1, 2**50, size=n)]
    pts = [rm.G2.scalar_mul(G2_GENERATOR, k) for k in ks]
    scs = [int.from_bytes(rng.bytes(40), "little") % R for _ in range(n)]
    got = C.decode(msm(C, C.encode(pts), encode_scalars_std(scs))[None])[0]
    assert got == rm.G2.msm(pts, scs)


# ---------------------------------------------------------------------------
# The limb-0 form of the tree MSM: the window count follows the scalars'
# occupancy when the caller hands over the host's view of them
# ---------------------------------------------------------------------------


def _limb0_group(name):
    """(curve, limb group, host curve, generator, scalar order) of a case."""
    if name == "g1":
        return g1(), lg1(), rm.G1, G1_GENERATOR, R
    if name == "g2":
        from distributed_groth16_tpu.ops.constants import G2_GENERATOR
        from distributed_groth16_tpu.ops.curve import g2
        from distributed_groth16_tpu.ops.limb_kernels import lg2

        return g2(), lg2(), rm.G2, G2_GENERATOR, R
    from distributed_groth16_tpu.ops import bls12_381 as b
    from distributed_groth16_tpu.ops.limb_kernels import lg1_381

    return b.g1_381(), lg1_381(), b.G1_HOST, b.g1_generator_381(), b.R381


@functools.cache
def _limb0_points(name, n):
    C, _, host, gen, _ = _limb0_group(name)
    rng = np.random.default_rng(24)
    ks = [int(x) for x in rng.integers(1, 2**61, size=n)]
    pts = [host.scalar_mul(gen, k) for k in ks]
    return pts, C.encode(pts)


# (group, n, wide values at which positions, position of a point at
# infinity or None, whether the limb-0 form is expected). n = 300 pads to
# 512 and leaves room for 14 wide scalars; 37 pads to 64, room for 1; 17
# pads to 32, room for 1; 512 leaves no slot. The full-width programs of
# n = 300 (G1) and n = 37 (G2) are the ones the tests above compiled. The
# G2 case that takes the limb-0 form is in tests/test_msm.py: its programs
# compile for minutes on the CPU, and that file runs on another worker.
_WIDE_128 = (1 << 128) - 12345
_LIMB0_CASES = {
    "g1-bits-only": ("g1", 300, {}, None, True),
    "g1-one-wide": ("g1", 300, {1: _WIDE_128}, None, True),
    "g1-two-wide-as-sha256": ("g1", 300, {1: _WIDE_128, 2: 1 << 16}, None, True),
    "g1-most-that-fits": (
        "g1", 300, {7 * i + 3: R - 1 - i for i in range(14)}, None, True),
    "g1-one-past-what-fits": (
        "g1", 300, {7 * i + 3: R - 1 - i for i in range(15)}, None, False),
    "g1-infinity-among-the-wide": (
        "g1", 300, {5: _WIDE_128, 299: R - 2}, 5, True),
    "g1-power-of-two-no-slot": ("g1", 512, {}, None, True),
    "g2-one-past-what-fits": ("g2", 37, {0: 1 << 200, 36: R - 3}, None, False),
    "bls12-381-g1": ("381", 17, {2: (1 << 254) + 9}, None, True),
}


@pytest.mark.parametrize("case", sorted(_LIMB0_CASES))
def test_msm_tree_limb0_matches_full_width_and_reference(case):
    from distributed_groth16_tpu.ops import limb_kernels as lk
    from distributed_groth16_tpu.ops.scalar_pack import encode_scalars

    name, n, wide_at, inf_at, expect_limb0 = _LIMB0_CASES[case]
    C, g, host, _, order = _limb0_group(name)
    pts, P = _limb0_points(name, n)
    if inf_at is not None:
        pts = list(pts)
        pts[inf_at] = None
        P = C.encode(pts)
    rng = np.random.default_rng(len(case))
    vals = [int(b) for b in rng.integers(0, 2, size=n)]
    for i, v in wide_at.items():
        vals[i] = v % order
    sc = encode_scalars(vals, order)
    view = lk.WideScalars.observe(vals)
    assert view.count == len(wide_at)
    assert lk.takes_limb0(g, n, view) is expect_limb0

    full_jit = lk._MSM_TREE_JITS[g.kind]
    limb0_jit = lk._MSM_LIMB0_JITS[g.kind]
    before = full_jit._cache_size(), limb0_jit._cache_size()
    got = C.decode(msm_tree(P, sc, group=g, wide=view)[None])[0]
    after = full_jit._cache_size(), limb0_jit._cache_size()
    if expect_limb0:
        assert after[0] == before[0], "the limb-0 form ran a full program"
    else:
        assert after[1] == before[1], "the fallback ran a limb-0 program"
    assert got == host.msm(pts, vals)
    if (name, n) in (("g1", 300), ("g2", 37)):
        assert got == C.decode(msm_tree(P, sc, group=g)[None])[0]


def _field_filling(n):
    rng = np.random.default_rng(31)
    return [int.from_bytes(rng.bytes(32), "little") % R for _ in range(n)]


def _bits_wide_at_both_ends(n):
    vals = [int(b) for b in np.random.default_rng(32).integers(0, 2, size=n)]
    vals[0], vals[-1] = R - 2, _WIDE_128
    return vals


# What `WideScalars.observe` sees, from nothing to the chain cell's witness.
_VIEW_CASES = {
    "empty": lambda: [],
    "all-narrow": lambda: [0, 1, 65535, 2, 40000] * 7,
    "value-0": lambda: [0],
    "value-2^16-1": lambda: [(1 << 16) - 1],
    "value-2^16": lambda: [1 << 16],
    "value-r-1": lambda: [R - 1],
    "value-2^256-1": lambda: [(1 << 256) - 1],
    "bits-wide-first-and-last": lambda: _bits_wide_at_both_ends(27627),
    "field-65002": lambda: _field_filling(65002),
}


def _observe_by_definition(values):
    """The view as PR 24 defined it, a limb at a time: the reference."""
    idx = [i for i, v in enumerate(values) if v >> 16]
    limbs = [to_limbs(values[i] >> 16, 15) for i in idx]
    return len(values), idx, np.asarray(limbs, np.int64).reshape(len(idx), 15)


@pytest.mark.parametrize("case", sorted(_VIEW_CASES))
def test_wide_scalars_view_is_the_definitions_element_for_element(case):
    """Host only, no program compiled: the buffer-built view against the
    limb-at-a-time definition, and `tail(k)` against the view of
    `values[k:]`, in every field, dtype and shape."""
    from distributed_groth16_tpu.ops import limb_kernels as lk

    values = _VIEW_CASES[case]()
    view = lk.WideScalars.observe(values)
    for k in sorted({0, min(1, len(values)), len(values)}):
        n, idx, limbs = _observe_by_definition(values[k:])
        got = view.tail(k)
        assert got.n == n and got.count == len(idx)
        assert got.idx.dtype == np.int32 and got.idx.shape == (len(idx),)
        assert got.idx.tolist() == idx
        assert got.limbs.dtype == np.uint32 and got.limbs.shape == (len(idx), 15)
        assert np.array_equal(got.limbs, limbs)
    assert view.limbs.flags.c_contiguous


def test_wide_scalars_view_is_built_without_a_python_loop_per_limb(monkeypatch):
    """The mechanism: no `to_limbs` a wide value. A thousand wide values
    are observed with the limb-at-a-time helper made to raise."""
    from distributed_groth16_tpu.ops import limb_kernels as lk

    def raises(*a, **kw):
        raise AssertionError("observe split a value limb by limb")

    monkeypatch.setattr(lk, "to_limbs", raises)
    values = _field_filling(1000)
    view = lk.WideScalars.observe(values)
    assert view.count == sum(1 for v in values if v >> 16) >= 999
    assert view.limbs[0].tolist() == to_limbs(values[view.idx[0]] >> 16, 15)


def _route_counts():
    from distributed_groth16_tpu.telemetry import metrics

    fam = metrics.registry().family("kernel_route_total")
    out = {path: child.value for (kernel, path), child in fam.items()
           if kernel == "msm"}
    wide = metrics.registry().family("msm_wide_scalars_total")
    out["wide"] = sum(child.value for _, child in wide.items())
    return out


def test_msm_routes_by_the_hosts_view_and_counts_it(monkeypatch):
    """`msm` without a view is the parent's call: the full-width program's
    one cache entry for the shape, counted `msm/tree`. With the view of the
    same scalars it takes `msm/tree_limb0` and counts the wide scalars it
    carried; with a view that does not fit, `msm/tree` again."""
    from distributed_groth16_tpu.ops import limb_kernels as lk
    from distributed_groth16_tpu.ops.msm import encode_observed
    from distributed_groth16_tpu.ops.field import fr

    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    C, n = g1(), 300
    pts, P = _limb0_points("g1", n)
    rng = np.random.default_rng(3)
    vals = [int(b) for b in rng.integers(0, 2, size=n)]
    vals[1], vals[2] = _WIDE_128, R - 5
    z_mont, view = encode_observed(fr(), vals)
    sc = fr().from_mont(z_mont)
    assert view.n == n and list(view.idx) == [1, 2]
    assert view.tail(2).n == n - 2 and list(view.tail(2).idx) == [0]
    want = rm.G1.msm(pts, vals)
    full_jit = lk._MSM_TREE_JITS["g1"]
    limb0_jit = lk._MSM_LIMB0_JITS["g1"]

    c0 = _route_counts()
    assert C.decode(msm(C, P, sc)[None])[0] == want
    size = full_jit._cache_size(), limb0_jit._cache_size()
    assert C.decode(msm(C, P, sc)[None])[0] == want
    assert (full_jit._cache_size(), limb0_jit._cache_size()) == size
    c1 = _route_counts()
    assert c1["tree"] - c0["tree"] == 2
    assert c1["tree_limb0"] == c0["tree_limb0"]
    assert c1["wide"] == c0["wide"]

    assert C.decode(msm(C, P, sc, wide=view)[None])[0] == want
    c2 = _route_counts()
    assert c2["tree_limb0"] - c1["tree_limb0"] == 1
    assert c2["tree"] == c1["tree"] and c2["wide"] - c1["wide"] == 2
    assert full_jit._cache_size() == size[0]

    many = lk.WideScalars.observe([1 << 20] * n)
    assert not lk.takes_limb0(lk.lg1(), n, many)
    got = msm(C, P, encode_scalars_std([1 << 20] * n), wide=many)
    assert C.decode(got[None])[0] == rm.G1.msm(pts, [1 << 20] * n)
    c3 = _route_counts()
    assert c3["tree"] - c2["tree"] == 1
    assert c3["tree_limb0"] == c2["tree_limb0"] and c3["wide"] == c2["wide"]


@pytest.mark.parametrize("kind", ["field"])
def test_prove_single_takes_the_route_its_witness_asks_for(kind, monkeypatch):
    """A whole proof on the tree path, G2 included, byte for byte the plain
    reference prover's: a witness that fills the field is declined the
    limb-0 form three times and runs four full-width trees (the cell
    `million_chain_c1`). The `bits` case of the same test is in
    tests/test_msm.py, beside the G2 limb-0 programs it needs; this one
    takes the full-width G2 program `test_msm_tree_g2_matches_reference`
    compiled (37 points). The body: tests/prove_routes.py."""
    from prove_routes import check_prove_single_routes

    check_prove_single_routes(kind, monkeypatch)


def test_a_served_chain_proof_says_which_side_of_the_rule_it_stood_on(
    monkeypatch, tmp_path
):
    """The same chain through `ApiServer` (POST /save_circuit, POST
    /jobs/prove, GET /jobs/{id}/result): between two reads of /metrics one
    proof moves `msm/tree` by 4, `msm/tree_limb0` by 0 and
    `msm_limb0_declined_total{reason="over_capacity"}` by 3, the job's
    trace names the route on `prove.A/B/C`, and the proof is the reference
    prover's. The tree programs are the ones the case above compiled."""
    import asyncio
    import json
    import re

    from aiohttp.test_utils import TestClient, TestServer
    from prove_routes import field_circuit

    from distributed_groth16_tpu.api.server import ApiServer
    from distributed_groth16_tpu.api.store import CircuitStore
    from distributed_groth16_tpu.frontend.ark_serde import proof_to_bytes
    from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
    from distributed_groth16_tpu.models.groth16 import setup
    from distributed_groth16_tpu.models.groth16.reference import prove_host

    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    r1cs, z = field_circuit()

    def series(text):
        found = re.findall(
            r'^(kernel_route_total\{kernel="msm",path="(?:tree|tree_limb0)"\}'
            r'|msm_limb0_declined_total\{reason="over_capacity"\})\s+(\S+)$',
            text, re.M,
        )
        return {name: float(v) for name, v in found}

    async def run():
        server = ApiServer(CircuitStore(str(tmp_path)))
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            resp = await client.post("/save_circuit", data={
                "circuit_name": "chain", "r1cs_file": write_r1cs(r1cs)})
            assert resp.status == 200, await resp.text()
            cid = (await resp.json())["circuitId"]
            before = series(await (await client.get("/metrics")).text())
            resp = await client.post("/jobs/prove", data={
                "circuit_id": cid, "witness_file": write_wtns(z)})
            assert resp.status == 202, await resp.text()
            job = (await resp.json())["jobId"]
            for _ in range(3000):
                resp = await client.get(f"/jobs/{job}/result")
                if resp.status != 409:
                    break
                await asyncio.sleep(0.1)
            assert resp.status == 200, await resp.text()
            proof = bytes((await resp.json())["proof"])
            after = series(await (await client.get("/metrics")).text())
            trace = await (await client.get(f"/jobs/{job}/trace")).text()
            return proof, before, after, json.loads(trace)
        finally:
            await client.close()

    proof, before, after, trace = asyncio.run(run())
    assert proof == proof_to_bytes(prove_host(setup(r1cs), r1cs, z))
    moved = {k: after[k] - before.get(k, 0.0) for k in after}
    assert moved == {
        'kernel_route_total{kernel="msm",path="tree"}': 4.0,
        'kernel_route_total{kernel="msm",path="tree_limb0"}': 0.0,
        'msm_limb0_declined_total{reason="over_capacity"}': 3.0,
    }
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    told = {e["name"]: e.get("args", {}) for e in events
            if e.get("name", "").startswith("prove.")}
    assert [told[s]["route"] for s in ("prove.A", "prove.B", "prove.C")] \
        == ["tree"] * 3
    assert told["prove.A"]["wide_scalars"] == 35
    assert "route" not in told["prove.h"]
