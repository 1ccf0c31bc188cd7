"""The tree MSM's limb-0 programs compiled for the TPU v5e by the chip's own
compiler (libtpu is installed; nothing runs): what Mosaic refuses, it
refuses here, for no chip time. The interpret-mode tests cannot show that.

The topology is described inside a fixture, never at import: one process
at a time may load libtpu, and every xdist worker imports this file."""

import jax
import jax.numpy as jnp
import pytest

from distributed_groth16_tpu.ops import limb_kernels as lk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    """The Pallas call sites, as on the chip. Fresh groups: the cached
    ones would keep Pallas programs for the CPU tests that follow."""
    from distributed_groth16_tpu.ops.constants import G1_B, G2_B

    monkeypatch.setattr(lk, "use_pallas", lambda: True)
    return {
        "g1": lk.LimbGroup(lk.lfq(), G1_B),
        "g2": lk.LimbGroup(lk.lfq2(), G2_B),
    }


def _shape(sharding, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kind", ["g1", "g2"])
def test_limb0_fill_compiles_for_v5e_at_the_served_size(one_chip, pallas, kind):
    """27,627 wires: the A and B queries of the benchmark's circuit. One
    `double` call site under two loops, the outer with a device trip count."""
    g, n = pallas[kind], 27627
    cap = lk.wide_capacity(g, n)
    assert cap == 342
    compiled = lk._MSM_LIMB0_FILL_JITS[kind].lower(
        g,
        _shape(one_chip, (n,) + g.rm_shape),
        _shape(one_chip, (n, 16)),
        _shape(one_chip, (cap,), jnp.int32),
        _shape(one_chip, (cap, 15)),
        _shape(one_chip, (), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_group_law_kernels_compile_with_their_bodies_behind_a_jit(
    one_chip, pallas
):
    """add, double and the two-window Horner of the limb-0 tree: each
    kernel binds the jitted block (`_add_block`, `_double_block`), which
    Mosaic has to lower inline."""
    g = pallas["g1"]
    tile = _shape(one_chip, (g.ROWS, g.tile))
    for fn, args in (
        (g._pallas_add, (tile, tile)),
        (g._pallas_double, (tile,)),
        (g._horner(8, 2), (_shape(one_chip, (g.ROWS, 2)),)),
    ):
        assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("kind", ["g1", "g2"])
def test_affine_level_kernels_compile_for_v5e_at_one_lane_tile(
    one_chip, pallas, kind
):
    """The kernels of a batch-affine up-sweep level (ISSUE 29), each at one
    lane tile: the field product, the affine add's two halves (the first
    holds a `lax.cond` on a lane reduction: a block with no doubling skips
    the tangent's product) and the root of the batched inversion (halves
    folded to 128 lanes, the Fermat loop reading its factors from a VMEM
    scratch by a dynamic leading index; Fq2 through the norm). The whole
    32,768-point program is a hundred kernel instances and compiles in
    some 25 s: by hand, before a chip run."""
    g = pallas[kind]
    CR, AR = g.CR, g.AROWS

    def tile(rows):
        return _shape(one_chip, (rows, g.tile))

    for fn, args in (
        (g._pallas_fmul, (tile(CR), tile(CR))),
        (g._pallas_affine_pre, (tile(AR), tile(AR))),
        (g._pallas_affine_post, (tile(AR), tile(AR), tile(CR + 1), tile(CR))),
        (g._pallas_root_inverse, (tile(CR),)),
    ):
        assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def test_mesh_prover_compiles_for_a_2x2_host_with_two_parties_a_chip(
    topo, pallas, monkeypatch
):
    """The served MPC round's one program, eight parties over the four
    chips of a v5e:2x2 (two a chip), at m = 64 with the tree MSMs forced
    (the served size takes them by itself): the compiler puts the
    parties' gathers in as `all-gather`s and keeps the Pallas kernels.
    The groups are the fixture's fresh ones, so no cached group keeps a
    Pallas program for the CPU tests that follow."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_groth16_tpu.models.groth16.mesh_prover import (
        build_mesh_prover,
    )
    from distributed_groth16_tpu.parallel.mesh import AXIS, make_mesh
    from distributed_groth16_tpu.parallel.pss import PackedSharingParams

    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    monkeypatch.setattr(lk, "lg1", lambda: pallas["g1"])
    monkeypatch.setattr(lk, "lg2", lambda: pallas["g2"])
    pp = PackedSharingParams(2)
    mesh = make_mesh(pp.n, topo.devices)
    assert mesh.devices.size == 4
    sh = NamedSharding(mesh, P(AXIS))
    n, m, c = pp.n, 64, 30  # c: the packed witness, under m/l = 32

    def arg(*shape):
        return _shape(sh, (n,) + shape)

    prog = build_mesh_prover(pp, m, mesh)
    text = prog.lower(
        arg(32, 16), arg(32, 16), arg(32, 16), arg(c, 16), arg(c, 16),
        arg(c, 3, 16), arg(32, 3, 16), arg(c, 3, 2, 16), arg(c, 3, 16),
    ).compile().as_text()
    assert "all-gather" in text and "tpu_custom_call" in text
    # the two d_fft gathers (3 vectors, 32 then 64 rows) and the two
    # d_msms' points (3 G1 and 1 G2 a party), 6 of 8 parties from afar
    fft = 3 * (32 + 64) * 16 * 4
    points = 3 * 3 * 16 * 4 + 3 * 2 * 16 * 4
    assert prog.gather_bytes == 6 * (fft + points)
