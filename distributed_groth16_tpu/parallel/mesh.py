"""SPMD mesh backend: MPC parties as shards of a jax.sharding.Mesh.

The TPU-native re-imagining of the reference's star topology for the
intra-slice case (SURVEY §2.1 "TPU equivalent"): inside one TPU slice the
n parties are shards along a "parties" mesh axis and the three star
collectives become XLA collectives over ICI —

  gather_to_king    -> lax.all_gather (every shard receives all shares)
  king computes     -> every shard runs the tiny king tail REDUNDANTLY
                       (cheaper than idling n-1 shards and avoids a
                       scatter; identical results by determinism)
  scatter_from_king -> each shard slices its own row by lax.axis_index

The whole proving round (h-poly FFTs + the A/B/C MSMs) is ONE jitted
shard_map program: no host round-trips, XLA overlaps the independent
pipelines that the async star backend runs on channels 0/1/2.

Privacy note: in-mesh mode all shards live in one trust domain (a single
TPU worker), so "king sees clear values" == "the worker sees clear values",
exactly the reference's king-node model. Cross-trust-domain deployments use
the async star backend over real transport instead.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map_fn
from jax.sharding import Mesh

from ..ops.field import fr
from ..telemetry.compile import named_jit
from .dfft import _fft1_local, _king_clear_array, _king_tail_array
from .pss import PackedSharingParams

AXIS = "parties"


def shard_map(f, mesh, in_specs, out_specs):
    return _shard_map_fn(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def mesh_jit(fn_name: str, fn):
    """jit a mesh program under the name `fn_name`, so that jax's own
    compile clocks (telemetry/compile.py: `jax_trace_seconds_total{fn}`,
    `jax_compile_seconds_total{fn}`) and a device trace's launches carry
    it — the m=32768 prover is compile-bound on some backends, and this
    makes that a measured number. Use for every whole-mesh jitted entry
    point."""
    return named_jit(fn_name, fn)


def make_mesh(n_parties: int) -> Mesh:
    devs = np.array(jax.devices()[:n_parties])
    if len(devs) < n_parties:
        raise RuntimeError(
            f"need {n_parties} devices, have {len(jax.devices())}"
        )
    return Mesh(devs, (AXIS,))


def make_mesh_from_devices(devices) -> Mesh:
    """A parties mesh over an EXPLICIT device slice — the scheduler's
    placement layer (scheduler/placement.py) partitions the inventory into
    disjoint slices so independent batches prove concurrently instead of
    serializing through jax.devices()[:n]."""
    devs = np.array(list(devices))
    if devs.size == 0:
        raise RuntimeError("empty device slice")
    return Mesh(devs, (AXIS,))


def _own_row(stacked):
    """Per-shard slice of a replicated (n, ...) tensor -> (1, ...)."""
    idx = jax.lax.axis_index(AXIS)
    return jax.lax.dynamic_slice_in_dim(stacked, idx, 1, axis=0)


def _mesh_dfft(
    x,
    pp: PackedSharingParams,
    logm: int,
    inverse: bool,
    rearrange: bool,
    pad: int,
    degree2: bool,
    king_clear: bool,
    wpows,
    size_inv,
):
    """x: (1, ..., m/l, 16) own share block (extra axes batch independent
    transforms). Returns (1, ..., c, 16) shares, or the replicated clear
    (..., m, 16) when king_clear."""
    F = fr()
    logl = pp.l.bit_length() - 1
    if inverse:
        x = F.mul(x, size_inv)
    local = _fft1_local(x, wpows, logm, logl, inverse)
    allg = jax.lax.all_gather(local, AXIS, axis=0, tiled=True)  # (n, ..., m/l, 16)
    if king_clear:
        return _king_clear_array(allg, pp, logm, degree2, inverse, wpows)
    out = _king_tail_array(
        allg, pp, logm, rearrange, pad, degree2, inverse, wpows
    )
    return _own_row(out)


def _mesh_dmsm(curve, bases_block, scalar_block, pp: PackedSharingParams):
    """bases: (1, c, 3)+elem, scalars: (1, c, 16) Montgomery ->
    replicated clear (3,)+elem group element."""
    return _mesh_dmsm_batched(
        curve, bases_block[:, None], scalar_block[:, None], pp
    )[0]


def _mesh_dmsm_batched(curve, bases_block, scalar_block, pp: PackedSharingParams):
    """B independent d_msms of identical length in ONE traced program.

    bases: (1, B, c, 3)+elem, scalars: (1, B, c, 16) Montgomery ->
    replicated clear (B, 3)+elem. Batching is the compile-time lever: each
    distinct curve-op instantiation costs seconds of XLA:CPU compile,
    so the prover's three same-length G1 MSMs share
    one ladder instead of instantiating three.
    """
    from ..ops.msm import msm_batched

    F = fr()
    std = F.from_mont(scalar_block[0])  # (B, c, 16)
    local = msm_batched(curve, bases_block[0], std)  # (B,)+point
    allg = jax.lax.all_gather(local, AXIS, axis=0, tiled=False)  # (n, B)+pt
    allg = jnp.moveaxis(allg, 0, 1)  # (B, n)+pt
    partials = pp.unpackexp(curve, allg, degree2=True)  # (B, l)+pt
    return curve.sum_sequential(partials, axis=1)  # (B,)+pt
