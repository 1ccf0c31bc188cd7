"""The Groth16 prover as ONE SPMD mesh program.

The whole distributed proving round of groth16/examples/sha256.rs:26-99 —
h-poly FFT pipelines + the A/B/C MSMs — jitted once over a "parties" mesh
axis (parallel/mesh.py collectives): the in-slice TPU execution mode where
the async star backend's network rounds become ICI collectives and XLA
overlaps everything the reference runs on channels 0/1/2.

Every party-stacked input is sharded along its leading n axis; a shard
holds the (k, ...) block of its k = n/d parties (`parallel.mesh`), and
the program is the same for every k.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ...ops.curve import g1, g2
from ...ops.ntt import domain
from ...parallel.mesh import (
    AXIS,
    _mesh_dfft,
    _mesh_dmsm,
    _mesh_dmsm_batched,
    _own_row,
    make_mesh,  # noqa: F401  (re-exported convenience)
    mesh_jit,
    parties_per_shard,
    shard_map,
)
from ...parallel.pss import PackedSharingParams
from .ext_wit import king_combine_h


@dataclass
class MeshProverInputs:
    """All-party stacked tensors, sharded along axis 0 (= parties)."""

    qap_a: jnp.ndarray  # (n, m/l, 16)
    qap_b: jnp.ndarray
    qap_c: jnp.ndarray
    a_share: jnp.ndarray  # (n, c_a, 16)
    ax_share: jnp.ndarray  # (n, c_w, 16)
    s: jnp.ndarray  # (n, c_a, 3, 16)
    u: jnp.ndarray  # (n, m/l, 3, 16)
    v: jnp.ndarray  # (n, c_a, 3, 2, 16)
    w: jnp.ndarray  # (n, c_w, 3, 16)
    h: jnp.ndarray | None = None  # (n, c_a, 3, 16) b_g1_query shares (zk)


def _pad_scalars(x, cmax: int):
    """(k, ..., c, 16) -> (k, ..., cmax, 16); a zero scalar is inert."""
    widths = [(0, 0)] * (x.ndim - 2) + [(0, cmax - x.shape[-2]), (0, 0)]
    return jnp.pad(x, widths)


def _pad_points(x, cmax: int):
    """(k, c, 3, 16) G1 points -> (k, cmax, 3, 16), padded with the
    INFINITY encoding (0,1,0) — all-zero rows are absorbing (not identity)
    under the RCB complete add, which would poison the Pallas tree-MSM
    path's pairwise sum tree."""
    extra = jnp.broadcast_to(
        g1().infinity(), (x.shape[0], cmax - x.shape[1], 3) + g1().elem_shape
    )
    return jnp.concatenate([x, extra], axis=1)


def build_mesh_prover(pp: PackedSharingParams, m: int, mesh: Mesh,
                      zk: bool = False):
    """Returns a jitted SPMD function computing the clear proof cores
    (pi_a, pi_b, pi_c) from MeshProverInputs.

    zk=True additionally computes the H-query MSM (b_g1_query shares ·
    a_share) as a 4th row of the batched G1 d_msm and returns it as a 4th
    output; it feeds the r-weighted C term. The r/s randomization itself is
    host-side arithmetic on the clear cores (mesh_prove_zk) — the cores are
    public after the king broadcast, exactly as in the async-star path
    (prove.rs:10-137 randomizes; sha256.rs:208-212 reassembles clear)."""
    logm = m.bit_length() - 1
    dom = domain(m)
    dom2 = domain(2 * m)
    wpows_m = dom._live_wpows()
    wpows_2m = dom2._live_wpows()
    size_inv_m = dom._size_inv

    k = parties_per_shard(pp, mesh)

    def step(qa, qb, qc, a_sh, ax_sh, s_q, u_q, v_q, w_q, h_q=None):
        # --- ext_wit::h -------------------------------------------------
        # the a/b/c pipelines are shape-identical: run them as ONE batched
        # transform (axis 1) — a third of the traced graph, and the
        # analog of the reference's three overlapped channels
        stacked = jnp.stack([qa, qb, qc], axis=1)  # (k, 3, m/l, 16)
        coeffs = _mesh_dfft(
            stacked, pp, logm, True, True, 2, False, False,
            wpows_m, size_inv_m,
        )
        evals = _mesh_dfft(
            coeffs, pp, logm + 1, False, False, 1, False, True,
            wpows_2m, None,
        )  # king_clear: (3, 2m, 16) clear, replicated
        p, q, w = evals[0], evals[1], evals[2]
        h_share = _own_row(king_combine_h(p, q, w, pp), k)  # (k, m/l, 16)

        # --- A, B, C ----------------------------------------------------
        # the three G1 MSMs run as ONE batched d_msm (zero-padded to a
        # common length): one curve-ladder instantiation instead of three,
        # the main compile-time lever. Zero-scalar / zero-point padding
        # contributes the identity.
        cmax = max(s_q.shape[1], w_q.shape[1], u_q.shape[1])
        g1_bases = [_pad_points(s_q, cmax), _pad_points(w_q, cmax),
                    _pad_points(u_q, cmax)]
        g1_scalars = [_pad_scalars(a_sh, cmax), _pad_scalars(ax_sh, cmax),
                      _pad_scalars(h_share, cmax)]
        if zk:
            g1_bases.append(_pad_points(h_q, cmax))
            g1_scalars.append(_pad_scalars(a_sh, cmax))
        out = _mesh_dmsm_batched(
            g1(),
            jnp.stack(g1_bases, axis=1),
            jnp.stack(g1_scalars, axis=1),
            pp,
        )
        pi_a, c_w, c_u = out[0], out[1], out[2]
        pi_b = _mesh_dmsm(g2(), v_q, a_sh, pp)
        pi_c = g1().add(c_w, c_u)
        if zk:
            return pi_a[None], pi_b[None], pi_c[None], out[3][None]
        return pi_a[None], pi_b[None], pi_c[None]

    sharded = P(AXIS)
    n_in = 10 if zk else 9
    n_out = 4 if zk else 3
    mapped = shard_map(
        step,
        mesh,
        in_specs=(sharded,) * n_in,
        out_specs=(sharded,) * n_out,
    )
    # compile cost is THE first-run number at m=32768 — the name makes it
    # readable (jax_trace_seconds_total{fn}, jax_compile_seconds_total{fn})
    return mesh_jit("mesh_prover_zk" if zk else "mesh_prover", mapped)


def build_batch_mesh_prover(pp: PackedSharingParams, m: int, mesh: Mesh,
                            batch: int):
    """B same-circuit proofs as ONE SPMD program (scheduler/batch_prover.py).

    The witness-dependent tensors carry a leading per-shard batch axis B
    while the CRS shares stay un-batched (one shared packed CRS per
    bucket): the FFT pipeline batches over (B, 3) through `_mesh_dfft`'s
    extra-axes support, and the A/B/C MSMs run as one `_mesh_dmsm_batched`
    of 3B rows against B broadcast copies of the three G1 query tables.
    Deterministic (r = s = 0) cores only — exactly the service's proving
    path, so each demuxed proof byte-matches the sequential route.

    Returns a jitted f(qabc, a_share, ax_share, s, u, v, w) with global
    shapes
        qabc     (n, B, 3, m/l, 16)   stacked per-job qap a/b/c shares
        a_share  (n, B, c_a, 16)      per-job packed witness shares
        ax_share (n, B, c_w, 16)
        s/u/v/w  as in MeshProverInputs (shared CRS, no batch axis)
    producing (d, B, ...) clear cores (pi_a, pi_b, pi_c), one copy a
    device of the d the mesh spans."""
    logm = m.bit_length() - 1
    dom = domain(m)
    dom2 = domain(2 * m)
    wpows_m = dom._live_wpows()
    wpows_2m = dom2._live_wpows()
    size_inv_m = dom._size_inv

    k = parties_per_shard(pp, mesh)

    def step(qabc, a_sh, ax_sh, s_q, u_q, v_q, w_q):
        # --- ext_wit::h, batched over (B, 3) ----------------------------
        coeffs = _mesh_dfft(
            qabc, pp, logm, True, True, 2, False, False,
            wpows_m, size_inv_m,
        )  # (k, B, 3, 2m/l, 16)
        evals = _mesh_dfft(
            coeffs, pp, logm + 1, False, False, 1, False, True,
            wpows_2m, None,
        )  # king_clear: (B, 3, 2m, 16) clear, replicated
        p, q, w = evals[:, 0], evals[:, 1], evals[:, 2]
        h_share = _own_row(king_combine_h(p, q, w, pp), k)  # (k, B, m/l, 16)

        # --- A, B, C: 3B G1 MSM rows over B copies of the shared bases --
        cmax = max(s_q.shape[1], w_q.shape[1], u_q.shape[1])
        bases3 = jnp.stack(
            [_pad_points(s_q, cmax), _pad_points(w_q, cmax),
             _pad_points(u_q, cmax)], axis=1,
        )  # (k, 3, cmax, 3)+elem
        g1_bases = jnp.broadcast_to(
            bases3[:, None], (k, batch) + bases3.shape[1:]
        ).reshape((k, 3 * batch) + bases3.shape[2:])
        g1_scalars = jnp.stack(
            [_pad_scalars(a_sh, cmax), _pad_scalars(ax_sh, cmax),
             _pad_scalars(h_share, cmax)], axis=2,
        ).reshape(k, 3 * batch, cmax, 16)
        out = _mesh_dmsm_batched(
            g1(), g1_bases, g1_scalars, pp
        ).reshape((batch, 3) + g1().infinity().shape)
        pi_a, c_w, c_u = out[:, 0], out[:, 1], out[:, 2]
        vb = jnp.broadcast_to(v_q[:, None], (k, batch) + v_q.shape[1:])
        pi_b = _mesh_dmsm_batched(g2(), vb, a_sh, pp)  # (B, 3, 2, 16)
        pi_c = g1().add(c_w, c_u)
        return pi_a[None], pi_b[None], pi_c[None]

    sharded = P(AXIS)
    mapped = shard_map(
        step,
        mesh,
        in_specs=(sharded,) * 7,
        out_specs=(sharded,) * 3,
    )
    return mesh_jit(f"mesh_prover_batch{batch}", mapped)


def mesh_prove(pp, m, mesh, inp: MeshProverInputs):
    """One-shot helper: build, run, return clear (pi_a, pi_b, pi_c) from
    shard 0 (every shard holds identical values)."""
    prover = build_mesh_prover(pp, m, mesh)
    pa, pb, pc = prover(
        inp.qap_a, inp.qap_b, inp.qap_c, inp.a_share, inp.ax_share,
        inp.s, inp.u, inp.v, inp.w,
    )
    return pa[0], pb[0], pc[0]


def mesh_prove_zk(pp, m, mesh, inp: MeshProverInputs, pk, r: int, s: int):
    """Full zero-knowledge mesh prove: SPMD cores + host r/s randomization.

    Same algebra as the async-star zk path (prove.rs:10-137):
        A = core_A + (a_query[0] + alpha) + r*delta_g1
        B = core_B + (b_g2_query[0] + beta)  + s*delta_g2
        C = core_C + s*A + r*(beta_g1 + b_g1_query[0]) + r*h_msm
    where core_C = w + u and h_msm = d_msm(b_g1_query[1:] shares, a_share)
    (the 4th batched MSM row). All completion terms are public CRS values
    and the cores are clear post-broadcast, so randomization is exact host
    bigint math — no extra device compile. r = s = 0 degenerates to the
    deterministic reassembly.
    """
    from ...ops import refmath as rm
    from ...ops.field import fr
    from .keys import Proof

    p = fr().p
    r, s = r % p, s % p
    C1, C2 = g1(), g2()
    if inp.h is None:
        raise ValueError("mesh_prove_zk needs MeshProverInputs.h "
                         "(b_g1_query shares)")
    prover = build_mesh_prover(pp, m, mesh, zk=True)
    pa, pb, pc, ph = prover(
        inp.qap_a, inp.qap_b, inp.qap_c, inp.a_share, inp.ax_share,
        inp.s, inp.u, inp.v, inp.w, inp.h,
    )
    a_core = C1.decode(pa[0])
    b_core = C2.decode(pb[0])
    c_core = C1.decode(pc[0])
    h_msm = C1.decode(ph[0])
    vk = pk.vk
    a0 = rm.G1.add(C1.decode(pk.a_query[0]), vk.alpha_g1)
    b0 = rm.G2.add(C2.decode(pk.b_g2_query[0]), vk.beta_g2)
    delta_g1 = C1.decode(pk.delta_g1)
    m_term = rm.G1.add(C1.decode(pk.beta_g1), C1.decode(pk.b_g1_query[0]))
    a_full = rm.G1.add(rm.G1.add(a_core, a0), rm.G1.scalar_mul(delta_g1, r))
    b_full = rm.G2.add(rm.G2.add(b_core, b0),
                       rm.G2.scalar_mul(vk.delta_g2, s))
    c_full = rm.G1.add(
        rm.G1.add(c_core, rm.G1.scalar_mul(a_full, s)),
        rm.G1.scalar_mul(rm.G1.add(m_term, h_msm), r),
    )
    return Proof(a=a_full, b=b_full, c=c_full)
