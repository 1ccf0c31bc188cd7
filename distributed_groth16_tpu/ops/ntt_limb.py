"""Limb-major radix-2 NTT over BN254 Fr — the Pallas fast path for the
d_fft / h-poly pipelines (the north star names "radix-2 NTT over Fr" as a
TPU kernel; reference substrate: dist-primitives/src/dfft/mod.rs:98-182).

Layout: an Fr vector lives limb-major as uint32[16, n] (limb rows on the
sublane axis, elements on lanes), in Montgomery form, redundant [0, 2p) —
the same representation as ops/limb_kernels.LimbField, instantiated here
for the SCALAR field r (limb_kernels uses the base field q).

Structure (four-step Cooley-Tukey, applied recursively):
  * n <= _S_MAX: one fused Pallas kernel — bitrev in XLA, then log2(n)
    butterfly stages entirely in VMEM with per-stage twiddle tables.
  * n > _S_MAX: n = A*B split with A = _S_MAX: batched NTT_A kernel over
    the B columns, one elementwise twiddle multiply w^{k1*j2} (table built
    device-side from the domain's dense root table), transpose, then the
    size-B transform the same way (a kernel if B <= _S_MAX, another split
    otherwise) — output lands in natural order without a final
    permutation (X[k1 + A*k2] = Z[k2, k1] and the (16, B, A) reshape IS
    that ordering).

Differentially tested against ops/ntt.JaxDomain (itself tested against the
pure-bigint refmath.Domain).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .constants import FR_GENERATOR, R, to_limbs
from .limb_kernels import NL, LimbField, _pl, kernel_roll_mode, use_pallas
from .ntt import bitrev_perm
from .refmath import finv

# max single-kernel NTT size. The lane tile must be a multiple of 128
# (Mosaic requires block minor dim % 128 == 0) and Mosaic unrolls every
# stage's field ops over the whole (16, S*128/2) block, so both the
# scoped-VMEM stack and the compile time grow with S. Compiled for a v5e
# (libtpu 0.0.34): S = 256 needs 16.01 MB of stack against the 16 MB
# default limit (RESOURCE_EXHAUSTED) and ~100 s per kernel once the limit
# is raised; S = 128 compiles in ~28 s; S = 64 in ~8 s with a ~4 MB stack.
# Larger transforms take one more four-step level instead.
_S_MAX = 64
_LANE_TILE = 128


@functools.cache
def lfr() -> LimbField:
    """Limb-major field ops for Fr (scalar field) — LimbField is generic
    over the modulus."""
    return LimbField(R)


def _w_root(n: int) -> int:
    return pow(FR_GENERATOR, (R - 1) // n, R)


@functools.cache
def _stage_twiddles(n: int, inverse: bool) -> np.ndarray:
    """(16, logn, n//2) per-stage butterfly twiddles, Montgomery limb rows.

    Stage s (span = 2^s) uses w_{2span}^t at hi-offset t in [0, span);
    entries beyond span are padding (never read)."""
    F = lfr()
    logn = n.bit_length() - 1
    w = _w_root(n)
    if inverse:
        w = finv(w, R)
    out = np.zeros((NL, logn, max(1, n // 2)), np.uint32)
    for s in range(logn):
        span = 1 << s
        wspan = pow(w, n // (2 * span), R)
        acc = 1
        for t in range(span):
            out[:, s, t] = to_limbs(acc * F.mont_r % R)
            acc = acc * wspan % R
    return out


def _ntt_body(x, tw, p_col, p2_col, logn: int, unroll: bool):
    """x: (16, S, L) bitrev-ordered; returns natural-order NTT along axis 1.
    All reshapes static; every field op flattens to (16, -1) 2D."""
    F = lfr()
    S, L = x.shape[1], x.shape[2]

    def fl(a):
        return a.reshape(NL, -1)

    for s in range(logn):
        span = 1 << s
        blocks = S // (2 * span)
        xr = x.reshape(NL, blocks, 2, span, L)
        lo, hi = xr[:, :, 0], xr[:, :, 1]  # (16, blocks, span, L)
        tws = jax.lax.slice_in_dim(tw, s, s + 1, axis=1)  # (16, 1, n//2)
        tws = jax.lax.slice_in_dim(tws, 0, span, axis=2)  # (16, 1, span)
        twb = jnp.broadcast_to(
            tws[:, :, None, :, None], (NL, 1, blocks, span, L)
        ).reshape(NL, blocks, span, L)
        t = F.mul(fl(hi), fl(twb), p_col, unroll).reshape(hi.shape)
        nlo = F.add(fl(lo), fl(t), p2_col, unroll).reshape(lo.shape)
        nhi = F.sub(fl(lo), fl(t), p2_col, unroll).reshape(lo.shape)
        x = jnp.stack([nlo, nhi], axis=2).reshape(NL, S, L)
    return x


class _SmallNTT:
    """Compiled size-S NTT (transform on axis 1, batch on axis 2)."""

    def __init__(self, S: int, inverse: bool):
        self.S = S
        self.logn = S.bit_length() - 1
        self.inverse = inverse
        self.tw_np = _stage_twiddles(S, inverse)
        # numpy, NOT jnp: __init__ may run inside a jit trace (functools
        # cache of _small), and jnp.asarray there yields a tracer that
        # poisons every later call
        self.perm = bitrev_perm(S)

    @functools.cached_property
    def _xla(self):
        F = lfr()

        @jax.jit
        def run(x):  # (16, S, L) natural order
            x = jnp.take(x, self.perm, axis=1)
            return _ntt_body(
                x, jnp.asarray(self.tw_np), jnp.asarray(F.p_col),
                jnp.asarray(F.p2_col), self.logn, unroll=False,
            )

        return run

    @functools.cached_property
    def _pallas(self):
        pl, pltpu = _pl()
        F = lfr()
        S, logn = self.S, self.logn
        TW = self.tw_np.shape[2]

        def kern(x_ref, tw_ref, c_ref, o_ref):
            consts = c_ref[:]
            o_ref[:] = _ntt_body(
                x_ref[:], tw_ref[:], consts[0:NL], consts[NL:],
                logn, unroll=kernel_roll_mode(),
            )

        consts = np.concatenate([F.p_col, F.p2_col], axis=0)

        @jax.jit
        def run(x):  # (16, S, L) natural order
            x = jnp.take(x, self.perm, axis=1)
            # every transform rides the kernel: lane counts that are not a
            # multiple of the 128-lane tile (n < 128 * _S_MAX at one
            # four-step level) are zero-padded up to it, not rerouted to
            # the XLA body
            L = x.shape[2]
            Lp = -(-L // _LANE_TILE) * _LANE_TILE
            if Lp != L:
                x = jnp.pad(x, ((0, 0), (0, 0), (0, Lp - L)))
            out = pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((NL, S, Lp), jnp.uint32),
                grid=(Lp // _LANE_TILE,),
                in_specs=[
                    pl.BlockSpec((NL, S, _LANE_TILE), lambda i: (0, 0, i),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((NL, logn, TW), lambda i: (0, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((2 * NL, 1), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((NL, S, _LANE_TILE),
                                       lambda i: (0, 0, i),
                                       memory_space=pltpu.VMEM),
            )(x, jnp.asarray(self.tw_np), jnp.asarray(consts))
            return out[:, :, :L]

        return run

    def __call__(self, x):
        """(16, S, L) natural-order columns -> NTT'd along axis 1."""
        return self._pallas(x) if use_pallas() else self._xla(x)


@functools.cache
def _small(S: int, inverse: bool) -> _SmallNTT:
    return _SmallNTT(S, inverse)


def _wpows_lm_traced(n: int, inverse: bool):
    """(16, n) limb-major Montgomery table of w^0..w^{n-1}, built with
    O(log n) TRACED device muls — deliberately not a host-side constant.

    The previous formulation cached a host numpy table and let jit embed
    it: at n = 2^20 that baked a 64 MB literal into the program (135 MB of
    StableHLO total), which is exactly the kind of monolith that wedged
    the remote Mosaic service. Building it in-trace costs ~log2(n) muls of
    (16, n) at runtime — negligible against the transform itself in the
    prover, and XLA CSE dedups the rebuild across back-to-back transforms
    in one program (the tables are pure functions of constants). Output is
    redundant [0, 2p), a valid mul operand downstream."""
    F = lfr()
    w = _w_root(n)
    if inverse:
        w = finv(w, R)
    logn = max(1, (n - 1).bit_length())
    k = jnp.arange(n, dtype=jnp.uint32)
    one = np.array(to_limbs(F.mont_r), np.uint32).reshape(NL, 1)
    tbl = jnp.broadcast_to(jnp.asarray(one), (NL, n))
    p_col = jnp.asarray(F.p_col)
    for b in range(logn):
        wb = np.array(
            to_limbs(pow(w, 1 << b, R) * F.mont_r % R), np.uint32
        ).reshape(NL, 1)
        hit = ((k >> b) & 1) == 1
        tbl = jnp.where(
            hit[None, :],
            F.mul(tbl, jnp.asarray(wb), p_col, unroll=False),
            tbl,
        )
    return tbl


def _ntt_rec(x, n: int, inverse: bool, L: int):
    """(16, n, L) batched NTT along axis 1, natural order in/out.

    Recursion: n = A*B with A = min(n, _S_MAX); NTT_A batched over (B, L),
    per-level twiddle w_n^{k1*j2}, transpose, recurse on B batched over
    (A, L). Output ordering X[k1 + A*k2] = Z[k2, k1] makes the final
    reshape natural order with no extra permutation.

    The steps of a level sit in `jax.named_scope`s, so a device op of the
    program can be put down to one in a profiler trace: `ntt.small` (the
    size-<=_S_MAX kernel, bit reversal included), `ntt.twiddle` (the root
    table, its gather and the multiply), `ntt.transpose`."""
    F = lfr()
    if n <= _S_MAX:
        with jax.named_scope("ntt.small"):
            return _small(n, inverse)(x)
    A = _S_MAX
    B = n // A
    with jax.named_scope("ntt.small"):
        m = x.reshape(NL, A, B * L)
        y = _small(A, inverse)(m).reshape(NL, A, B, L)
    with jax.named_scope("ntt.twiddle"):
        # twiddle w^{k1*j2}: indices into this level's dense root table
        # mod n
        k1 = jnp.arange(A, dtype=jnp.uint32)[:, None]
        j2 = jnp.arange(B, dtype=jnp.uint32)[None, :]
        idx = (k1 * j2) % jnp.uint32(n)  # (A, B)
        wp = _wpows_lm_traced(n, inverse)  # (16, n)
        tw = jnp.take(wp, idx.reshape(-1), axis=1).reshape(NL, A, B, 1)
        y = F.mul(
            y.reshape(NL, -1),
            jnp.broadcast_to(tw, y.shape).reshape(NL, -1),
            jnp.asarray(F.p_col),
            unroll=False,
        ).reshape(NL, A, B, L)
    with jax.named_scope("ntt.transpose"):
        yt = jnp.transpose(y, (0, 2, 1, 3)).reshape(NL, B, A * L)
    z = _ntt_rec(yt, B, inverse, A * L)
    return z.reshape(NL, n, L)


@functools.partial(jax.jit, static_argnums=(1, 2))
def ntt_limb(x, n: int, inverse: bool = False):
    """Full-size NTT: x (16, n) Montgomery limb-major, natural order in and
    out. No 1/n scaling on inverse (caller applies size_inv, matching the
    JaxDomain decomposition of ifft)."""
    return _ntt_rec(x[:, :, None], n, inverse, 1)[:, :, 0]


# -- row-major convenience wrappers (differential-test surface) -------------


@functools.partial(jax.jit, static_argnums=(1, 2))
def fft_rm(coeffs_rm, n: int, inverse: bool = False):
    """(n, 16) row-major Montgomery -> (n, 16); canonical output."""
    F = lfr()
    x = jnp.transpose(coeffs_rm)
    out = ntt_limb(x, n, inverse)
    if inverse:
        size_inv = jnp.asarray(
            np.array(to_limbs(finv(n, R) * F.mont_r % R), np.uint32)
        ).reshape(NL, 1)
        out = F.mul(out, size_inv, jnp.asarray(F.p_col), unroll=False)
    return jnp.transpose(F.canon(out))
