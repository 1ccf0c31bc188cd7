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

A shard may hold several parties: over d devices each shard holds the
k = n/d consecutive parties i·k … i·k + k − 1, a (k, ...) block of every
party-stacked input, and k is one more batch axis of its local work. The
program is the same text for every k (`make_mesh` picks d).

Privacy note: in-mesh mode all shards live in one trust domain (a single
TPU worker), so "king sees clear values" == "the worker sees clear values",
exactly the reference's king-node model. Cross-trust-domain deployments use
the async star backend over real transport instead.
"""

from __future__ import annotations

import contextvars

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


# while a mesh program is traced: the bytes its all_gathers bring each device
_GATHERED: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "mesh_gathered", default=None
)


class MeshProgram:
    """A whole-mesh program jitted under the name `fn_name`, so that jax's
    own compile clocks (telemetry/compile.py: `jax_trace_seconds_total{fn}`,
    `jax_compile_seconds_total{fn}`) and a device trace's launches carry
    it. `gather_bytes` is what the program's all_gathers bring each device
    in one launch, counted from their shapes when the program is traced
    (0 before its first call)."""

    def __init__(self, fn_name: str, fn):
        self.gather_bytes = 0

        def counted(*args):
            token = _GATHERED.set([0])
            try:
                out = fn(*args)
                self.gather_bytes = _GATHERED.get()[0]
            finally:
                _GATHERED.reset(token)
            return out

        self._jit = named_jit(fn_name, counted)

    def __call__(self, *args):
        return self._jit(*args)

    def lower(self, *args):
        return self._jit.lower(*args)


def mesh_jit(fn_name: str, fn) -> MeshProgram:
    """Use for every whole-mesh jitted entry point."""
    return MeshProgram(fn_name, fn)


def mesh_width(n_parties: int, n_devices: int) -> int:
    """How many devices a mesh of n parties spans: the largest divisor of
    n that is at most n_devices, so every shard holds n/width parties."""
    return max(d for d in range(1, min(n_parties, n_devices) + 1)
               if n_parties % d == 0)


def make_mesh(n_parties: int, devices=None) -> Mesh:
    """A parties mesh over the first `mesh_width` of `devices` (default
    `jax.devices()`): one party a device where there are enough, else
    n/width consecutive parties a device."""
    devs = list(jax.devices() if devices is None else devices)
    if not devs:
        raise RuntimeError("no devices")
    return Mesh(np.array(devs[: mesh_width(n_parties, len(devs))]), (AXIS,))


def make_mesh_from_devices(devices) -> Mesh:
    """A parties mesh over an EXPLICIT device slice — the scheduler's
    placement layer (scheduler/placement.py) partitions the inventory into
    disjoint slices so independent batches prove concurrently instead of
    serializing through jax.devices()[:n]."""
    devs = np.array(list(devices))
    if devs.size == 0:
        raise RuntimeError("empty device slice")
    return Mesh(devs, (AXIS,))


def parties_per_shard(pp: PackedSharingParams, mesh: Mesh) -> int:
    d = mesh.devices.size
    if pp.n % d:
        raise ValueError(f"{pp.n} parties do not divide over {d} devices")
    return pp.n // d


def _all_gather(x):
    """The shards' (k, ...) blocks -> (n, ...) in party order."""
    out = jax.lax.all_gather(x, AXIS, axis=0, tiled=True)
    tally = _GATHERED.get()
    if tally is not None:
        tally[0] += (out.size - x.size) * x.dtype.itemsize
    return out


def _own_row(stacked, k: int):
    """The shard's k parties of a replicated (n, ...) tensor -> (k, ...)."""
    idx = jax.lax.axis_index(AXIS) * k
    return jax.lax.dynamic_slice_in_dim(stacked, idx, k, axis=0)


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
    """x: (k, ..., m/l, 16) the shard's share blocks (extra axes batch
    independent transforms). Returns (k, ..., c, 16) shares, or the
    replicated clear (..., m, 16) when king_clear."""
    F = fr()
    logl = pp.l.bit_length() - 1
    if inverse:
        x = F.mul(x, size_inv)
    local = _fft1_local(x, wpows, logm, logl, inverse)
    allg = _all_gather(local)  # (n, ..., m/l, 16)
    if king_clear:
        return _king_clear_array(allg, pp, logm, degree2, inverse, wpows)
    out = _king_tail_array(
        allg, pp, logm, rearrange, pad, degree2, inverse, wpows
    )
    return _own_row(out, x.shape[0])


def _mesh_dmsm(curve, bases_block, scalar_block, pp: PackedSharingParams):
    """bases: (k, c, 3)+elem, scalars: (k, c, 16) Montgomery ->
    replicated clear (3,)+elem group element."""
    return _mesh_dmsm_batched(
        curve, bases_block[:, None], scalar_block[:, None], pp
    )[0]


def _mesh_dmsm_batched(curve, bases_block, scalar_block, pp: PackedSharingParams):
    """B independent d_msms of identical length in ONE traced program.

    bases: (k, B, c, 3)+elem, scalars: (k, B, c, 16) Montgomery ->
    replicated clear (B, 3)+elem. Batching is the compile-time lever: each
    distinct curve-op instantiation costs seconds of XLA:CPU compile,
    so the prover's three same-length G1 MSMs share
    one ladder instead of instantiating three.

    As in the star round (parallel/dmsm.py), each party weighs its shares
    by its public unpack weight w_j in the one Montgomery product that
    takes them to standard form, and every shard sums the n weighted
    points: nothing unpacks in the exponent.
    """
    from ..ops.msm import msm_batched

    F = fr()
    k, B = scalar_block.shape[:2]
    weights = np.stack(
        [pp.unpack2_weight_limbs(F, j) for j in range(pp.n)]
    )  # (n, 16) standard form
    own = _own_row(jnp.asarray(weights), k)[:, None, None, :]
    std = F.mul(scalar_block, own)  # (k, B, c, 16) w_j * s, standard
    local = msm_batched(
        curve,
        bases_block.reshape((k * B,) + bases_block.shape[2:]),
        std.reshape((k * B,) + std.shape[2:]),
    ).reshape((k, B) + curve.infinity().shape)
    allg = _all_gather(local)  # (n, B)+pt
    return curve.sum(allg, axis=0)  # (B,)+pt
