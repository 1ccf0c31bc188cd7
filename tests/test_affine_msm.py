"""Batch-affine up-sweep levels of the tree MSM (ISSUE 29): the batched
field inversion, the affine add round it, and `_msm_tree` with affine
levels against the host reference and the all-projective program. On the
CPU these run the plain-XLA bodies of the same formulas the Pallas kernels
compile (`tests/test_pallas_interpret.py` runs the kernels themselves);
lane counts of 8-300 and the rule's constant set low: the sizes check the
arithmetic and the control flow, never a speed."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_groth16_tpu.ops import limb_kernels as lk
from distributed_groth16_tpu.ops import refmath as rm
from distributed_groth16_tpu.ops.constants import LIMB_BITS, to_limbs


@functools.cache
def _group(name):
    """(curve, limb group, host curve, generator) of a case: BN254 G1 and
    G2, and BLS12-377 G1 for a 24-limb base field."""
    if name == "g1":
        from distributed_groth16_tpu.ops.constants import G1_GENERATOR
        from distributed_groth16_tpu.ops.curve import g1

        return g1(), lk.lg1(), rm.G1, G1_GENERATOR
    if name == "g2":
        from distributed_groth16_tpu.ops.constants import G2_GENERATOR
        from distributed_groth16_tpu.ops.curve import g2

        return g2(), lk.lg2(), rm.G2, G2_GENERATOR
    from distributed_groth16_tpu.ops import bls12_377 as b

    return b.g1_377(), lk.lg1_377(), b.G1_HOST, b.g1_generator_377()


def _limb_major(g, values):
    """Field elements (ints, or (c0, c1) pairs over Fq2) -> (CR, n)
    limb-major Montgomery residues, canonical."""
    F = g.F
    parts = list(zip(*values)) if g.CR != g.base_nl else [values]
    return jnp.asarray(np.concatenate([
        np.array([to_limbs(v * F.mont_r % F.p, g.base_nl) for v in part],
                 np.uint32).T
        for part in parts
    ], axis=0))


def _from_limb_major(g, a):
    """The inverse of `_limb_major`, through `canon_rows`."""
    F, bn = g.F, g.base_nl
    rows = np.asarray(F.canon_rows(a)).astype(object)
    rinv = pow(F.mont_r, -1, F.p)
    ints = [
        [sum(int(rows[k * bn + i, j]) << (LIMB_BITS * i) for i in range(bn))
         * rinv % F.p for j in range(rows.shape[1])]
        for k in range(g.CR // bn)
    ]
    return ints[0] if len(ints) == 1 else list(zip(*ints))


# -- the batched inversion ----------------------------------------------------


@pytest.mark.parametrize("name,width", [
    ("g1", 1), ("g1", 5), ("g1", 300), ("g2", 3), ("g2", 130), ("377", 7),
])
def test_batch_inverse_is_fermats_inverse_of_every_lane(name, width):
    """Widths of 1, not a power of two, and past the 256 lanes at which the
    product tree gains a level on the XLA path; Fq2 through the norm."""
    _, g, _, _ = _group(name)
    p = g.F.p
    rng = np.random.default_rng(width)
    draw = lambda: int.from_bytes(rng.bytes(64), "little") % (p - 1) + 1  # noqa: E731
    if g.CR == g.base_nl:
        vals = [draw() for _ in range(width)]
        vals[0] = 1
        vals[-1] = p - 1
        want = [pow(v, p - 2, p) for v in vals]
    else:
        vals = [(draw(), draw()) for _ in range(width)]
        vals[0] = (0, 1)  # u itself: a zero real part
        vals[-1] = (p - 1, 0)

        def inv2(a):
            n = pow((a[0] * a[0] + a[1] * a[1]) % p, p - 2, p)
            return (a[0] * n % p, -a[1] * n % p)

        want = [inv2(v) for v in vals]
    got = _from_limb_major(g, g.batch_inverse(_limb_major(g, vals)))
    assert got == want


def test_batch_inverse_keeps_the_batch_shape():
    _, g, _, _ = _group("g1")
    vals = list(range(2, 14))
    a = _limb_major(g, vals).reshape(g.CR, 3, 4)
    inv = g.batch_inverse(a)
    assert inv.shape == a.shape
    p = g.F.p
    assert _from_limb_major(g, inv.reshape(g.CR, -1)) == [
        pow(v, p - 2, p) for v in vals
    ]


# -- the affine add -----------------------------------------------------------


def _other_residue(g, a, rows):
    """The same field elements with the listed coordinate rows moved to
    their second residue, r + p, still under 2p."""
    a = np.array(a)
    F, bn = g.F, g.base_nl
    for lo in rows:
        for j in range(a.shape[1]):
            v = sum(int(a[lo + i, j]) << (LIMB_BITS * i) for i in range(bn))
            a[lo : lo + bn, j] = to_limbs(v % F.p + F.p, bn)
    return jnp.asarray(a)


@pytest.mark.parametrize("name", ["g1", "g2"])
def test_affine_add_is_the_complete_add_on_every_exceptional_pair(name):
    C, g, host, gen = _group(name)
    P, Q = host.scalar_mul(gen, 5), host.scalar_mul(gen, 11)
    pairs = [
        (P, Q), (None, Q), (P, None), (None, None), (P, P),
        (P, host.neg(P)), (Q, P), (Q, Q),
    ]
    a1 = g.normalise(g.from_rowmajor(C.encode([a for a, _ in pairs])))
    a2 = g.normalise(g.from_rowmajor(C.encode([b for _, b in pairs])))
    want = [host.add(a, b) for a, b in pairs]
    assert want[3] is None and want[5] is None and want[4] is not None
    CR, bn = g.CR, g.base_nl
    coords = list(range(0, 2 * CR, bn))
    # equal x (and y) reached through the residues 0 and p of a difference:
    # canonical operands give 0; one operand at r + p gives p
    for tag, b1, b2 in (
        ("canonical", a1, a2),
        ("second operand at r + p", a1, _other_residue(g, a2, coords)),
        ("first operand at r + p", _other_residue(g, a1, coords), a2),
    ):
        got = C.decode(g.to_rowmajor(g.lift(g.affine_add(b1, b2))))
        assert got == want, tag
        # and it is what the complete projective add says of the same lanes
        assert got == C.decode(g.to_rowmajor(g.add(g.lift(b1), g.lift(b2))))


def test_lift_puts_infinity_under_the_flag_along_either_axis():
    C, g, host, gen = _group("g1")
    pts = [host.scalar_mul(gen, 3), None, host.scalar_mul(gen, 4)]
    aff = g.normalise(g.from_rowmajor(C.encode(pts)))
    assert np.asarray(aff[2 * g.CR]).tolist() == [0, 1, 0]
    assert C.decode(g.to_rowmajor(g.lift(aff))) == pts
    assert C.decode(g.to_rowmajor(
        jnp.transpose(g.lift(jnp.transpose(aff), axis=-1))
    )) == pts


# -- the tree with affine levels ----------------------------------------------

# n = 7 pads to 8: three levels of 16, 8 and 4 adds under 4 windows of c = 4
# (one scalar limb). Every level affine, the two widest, and none: the last
# is the parent's program and the yardstick of the other two.
_TREE_MODES = {"projective": lk._AFFINE_MIN_ADDS, "mixed": 8, "affine": 1}
_projective = {}


@functools.cache
def _tree_inputs(name):
    """Seven points that meet in the tree's first level when their scalars
    are equal (the sort is stable): a repeated point, a point and its
    negative, infinity, and a point with Z far from 1."""
    C, g, host, gen = _group(name)
    p = g.F.p
    P = [host.scalar_mul(gen, k) for k in (3, 7, 12, 19)]
    pts = [P[0], P[0], P[1], host.neg(P[1]), None, P[2], P[3]]
    dev = np.array(C.encode(pts))
    # point 5 as (7x : 7y : 7): the same point, Z far from 1
    if g.CR == g.base_nl:
        x, y = P[2]
        scaled = (7 * x % p, 7 * y % p, 7)
    else:
        scaled = tuple(
            tuple(7 * c % p for c in coord) for coord in (*P[2], (1, 0))
        )
    dev[5] = np.asarray(C.F.encode([scaled]))[0]
    assert C.decode(dev) == pts
    return pts, jnp.asarray(dev)


# A G2 tree program takes 100 s to compile on the CPU whatever its levels
# (103 s all-projective, 101 s mixed, at these 7 points; the Horner step
# alone lowers to 1,138 `while` loops, and
# `test_msm_tree_g2_matches_reference` pays the same), so tier-1 runs G2's
# mixed case, which goes through `normalise`, two affine levels, `lift`, a
# projective level and a Fenwick gather of both kinds of node; its
# all-affine and projective cases are marked slow.
def _tree_cases():
    for mode in _TREE_MODES:
        for name in ("g1", "g2", "377"):
            slow = name == "g2" and mode != "mixed"
            yield pytest.param(
                name, mode, marks=[pytest.mark.slow] if slow else []
            )


@pytest.mark.parametrize("name,mode", list(_tree_cases()))
def test_msm_tree_with_affine_levels_matches_host_and_projective(name, mode):
    C, g, host, _ = _group(name)
    pts, dev = _tree_inputs(name)
    n = len(pts)
    depth = lk._affine_depth(4, 8, _TREE_MODES[mode])
    assert depth == {"projective": 0, "mixed": 2, "affine": 3}[mode]
    rng = np.random.default_rng(29)
    scalars = {
        "equal": [0x5a5a] * n,
        "zero": [0] * n,
        "random": [int(v) for v in rng.integers(0, 1 << 16, size=n)],
        "bits": [int(v) for v in rng.integers(0, 2, size=n)],
    }
    run = lk._MSM_TREE_JITS[g.kind]
    for tag, vals in scalars.items():
        sc = jnp.asarray(np.array(vals, np.uint32).reshape(n, 1))
        got = C.decode(
            np.asarray(run(g, dev, sc, 4, None, _TREE_MODES[mode]))[None]
        )[0]
        assert got == host.msm(pts, vals), (tag, mode)
        if mode == "projective":
            _projective[name, tag] = got
        elif (name, tag) in _projective:
            assert got == _projective[name, tag], (tag, mode)


def test_under_the_constant_the_program_is_the_all_projective_one():
    """16 points, 64 windows: 512 adds at the widest level, far under the
    rule. The lowered text is the text of a program that could have no
    affine level at all, and names no affine scope."""
    g = lk.lg1()
    args = (
        g, jax.ShapeDtypeStruct((16, 3, 16), jnp.uint32),
        jax.ShapeDtypeStruct((16, 16), jnp.uint32), 4, None,
    )
    jit = lk._MSM_TREE_JITS["g1"]
    default = jit.lower(*args)
    assert default.as_text() == jit.lower(*args, 1 << 62).as_text()
    text = default.as_text(debug_info=True)
    for scope in ("msm.normalise", "msm.upsweep.affine", "msm.inverse"):
        assert scope not in text
    assert "msm.upsweep" in text


def test_the_served_h_query_program_is_the_accepted_one_text_for_text():
    """`sha256-bn254-single`'s one full-width launch: G1, 32,768 points, 32
    windows of c = 8, four affine levels. Its lowered text (the plain-XLA
    bodies, as every CPU test runs them) hashes to what it hashed to at PR
    29's accepted commit: a PR that supports another deployment through
    the same `_msm_tree` (PR 30: 65,536 points, five levels) leaves this
    program alone, and one that means to change it says so here."""
    import hashlib

    g = lk.lg1()
    lowered = lk._MSM_TREE_JITS["g1"].lower(
        g, jax.ShapeDtypeStruct((32768, 3, 16), jnp.uint32),
        jax.ShapeDtypeStruct((32768, 16), jnp.uint32), 8, None,
    )
    text = lowered.as_text()
    assert lk.tree_affine_levels(g, 32768, 16) == 4
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "5c4bf12016a5e3f25a38a2f1d6fe6c06ffd5dcd69c8ba17bacb50c975fc13171"
    )


# -- the rule and its counter -------------------------------------------------


@pytest.mark.parametrize("name,n,limbs,want", [
    # h_query of the served proof: 32 windows of 32,768 points: the levels
    # of 524,288, 262,144, 131,072 and 65,536 adds
    ("g1", 32768, 16, 4),
    # the limb-0 trees of A, B and L: 2 windows, 32,768 adds at the widest
    ("g1", 27627, 1, 0),
    ("g2", 27627, 1, 0),
    # a d_msm of the MPC round: 16,384 points, 32 windows
    ("g1", 16384, 16, 3),
    # under some 2,000 points (64 windows of c = 4) no level is wide enough
    ("g1", 2048, 16, 1),
    ("g1", 1024, 16, 0),
    # 2^18 points run in window groups of 8: 4 groups of 5 levels
    ("g1", 1 << 18, 16, 20),
    # the four MSMs of `million-chain-bn254-single` (a witness that fills
    # the field: all 32 windows): h, A, L and B pad to 65,536, the levels
    # of 2^20 ... 2^16 adds, in one window group
    ("g1", 65536, 16, 5),
    ("g1", 65002, 16, 5),
    ("g1", 65000, 16, 5),
    ("g2", 65002, 16, 5),
])
def test_the_rule_counts_levels_of_at_least_two_to_the_sixteen_adds(
    name, n, limbs, want
):
    assert lk._AFFINE_MIN_ADDS == 1 << 16
    assert lk.tree_affine_levels(_group(name)[1], n, limbs) == want


def test_msm_raises_the_affine_levels_counter_at_dispatch(monkeypatch):
    from distributed_groth16_tpu.ops import msm as msm_mod
    from distributed_groth16_tpu.ops.curve import g1
    from distributed_groth16_tpu.telemetry import metrics

    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    monkeypatch.setattr(lk, "msm_tree", lambda *a, **kw: "launched")

    def total():
        fam = metrics.registry().family("msm_affine_levels_total")
        return sum(child.value for _, child in fam.items())

    before = total()
    pts = jnp.zeros((32768, 3, 16), jnp.uint32)
    sc = jnp.zeros((32768, 16), jnp.uint32)
    assert msm_mod.msm(g1(), pts, sc) == "launched"
    assert total() - before == 4
    # the limb-0 form of the same length: two windows, no level wide enough
    view = lk.WideScalars.observe([1] * 32767)
    assert msm_mod.msm(g1(), pts[:32767], sc[:32767], wide=view) == "launched"
    assert total() - before == 4


@pytest.mark.parametrize("name,n,rows,want", [
    # a d_msm of the MPC round as one launch: eight parties' 16,384 points,
    # 256 windows: the levels of 2^21 ... 2^16 adds, in one window group
    ("g1", 16384, 8, 6),
    ("g2", 16384, 8, 6),
    # B's shares, 13,813 points, pad to the same 16,384
    ("g2", 13813, 8, 6),
    # one row is the unbatched launch
    ("g1", 16384, 1, 3),
    # 16 rows of 2^14 lanes pass 2^17 a window: the 512 windows go in
    # groups of 8 (G1), 2^17 lanes a group, one level of 2^16 adds each
    ("g1", 16384, 16, 64),
])
def test_a_batched_launch_counts_the_lanes_of_all_its_rows(
    name, n, rows, want
):
    g = _group(name)[1]
    windows = rows * 16 * LIMB_BITS // lk._tree_window_bits(n)
    group = lk._tree_window_group(g, lk._tree_npad(n), windows, rows)
    assert group == (windows if rows * lk._tree_npad(n) <= 1 << 17 else 8)
    assert lk.tree_affine_levels(g, n, 16, rows) == want


def test_msm_batched_raises_its_counters_once_a_launch(monkeypatch):
    """One launch for B rows: `msm/tree` by B (each row is a tree MSM),
    `msm_batched/tree` by one, the affine counter by the launch's levels."""
    from distributed_groth16_tpu.ops import msm as msm_mod
    from distributed_groth16_tpu.ops.curve import g2
    from distributed_groth16_tpu.telemetry import metrics

    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    monkeypatch.setattr(lk, "msm_tree_batched", lambda *a, **kw: "launched")
    routes = metrics.registry().family("kernel_route_total")
    levels = metrics.registry().family("msm_affine_levels_total")

    def read():
        got = {k: c.value for k, c in routes.items()}
        return (got.get(("msm", "tree"), 0), got.get(("msm_batched", "tree"), 0),
                sum(c.value for _, c in levels.items()))

    before = read()
    sc = np.zeros((8, 13813, 16), np.uint32)
    assert msm_mod.msm_batched(g2(), None, sc) == "launched"
    assert [a - b for a, b in zip(read(), before)] == [8, 1, 6]


@pytest.mark.parametrize("name", ["g1", "g2"])
def test_a_batch_of_rows_pads_with_infinity_and_zero_scalars(name):
    """`msm_tree_batched` runs rows of any length at the power of two: the
    padding adds points at infinity with scalar 0 and keeps the rows."""
    from distributed_groth16_tpu.ops.constants import G1_GENERATOR, G2_GENERATOR
    from distributed_groth16_tpu.ops.curve import g1, g2

    C, gen = (g1(), G1_GENERATOR) if name == "g1" else (g2(), G2_GENERATOR)
    pts = C.encode([gen] * 6)
    pts = pts.reshape((2, 3) + pts.shape[1:])
    sc = jnp.ones((2, 3, 16), jnp.uint32)
    padded, scp = lk._MSM_TREE_BATCHED_PAD_JITS[name](_group(name)[1], pts, sc)
    assert padded.shape[:2] == scp.shape[:2] == (2, 4)
    assert bool(jnp.all(padded[:, :3] == pts))
    assert bool(jnp.all(scp[:, :3] == 1)) and bool(jnp.all(scp[:, 3] == 0))
    assert [C.decode(padded[b, 3]) for b in range(2)] == [None, None]
