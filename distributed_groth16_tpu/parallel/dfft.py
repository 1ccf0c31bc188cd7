"""Distributed two-stage FFT over packed shares — hot kernel #1.

Protocol identical to the reference's d_fft/d_ifft
(dist-primitives/src/dfft/mod.rs:17-256); kernels re-designed for TPU:

  Stage 1 (every party, on device): `log m - log l` butterfly levels applied
  share-wise to the party's (m/l)-long share vector. One jitted
  `lax.fori_loop` whose body is a fully batched gather/mul/select — the
  traced graph is one butterfly regardless of m (same trick as ops/ntt.py).

  Stage 2 (king): gather all share vectors, batched-unpack every chunk
  (pp.unpack / pp.unpack2 on a (m/l, n, 16) tensor — one tiny-NTT kernel
  call), run the remaining `log l` butterfly levels + the rotate-right-by-1
  fixup in the clear, optionally zero-pad by `pad` and re-layout
  (`rearrange`) for the next transform, re-pack, scatter.

Layout contract (see parallel/packing.py): inputs arrive bit-reversed +
strided; rearrange=True produces the same layout on the (padded) output so
transforms chain; rearrange=False produces consecutive chunking.

The twiddle conventions are the reference's exactly — factor = w^(2^(i-1)*(k+1))
and the final rotate (dfft/mod.rs:142-182) — validated end-to-end against
plain `Domain.fft` ground truth, mirroring local_dfft_test.rs.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from ..ops.field import fr
from ..ops.ntt import bitrev_perm, domain
from ..telemetry import tracing as _tracing
from .net import Net, king_section
from .pss import PackedSharingParams

log = logging.getLogger(__name__)


@functools.partial(jax.jit, static_argnames=("logm", "logl", "inverse"))
def _fft1_local(v, wpows, logm: int, logl: int, inverse: bool):
    """Stage-1 butterflies on a (..., m/l, 16) share vector.

    Level t (t = 0 .. logm-logl-1) mirrors reference level i = logm - t:
    poly_size = 2^t, butterfly partners at stride poly_size inside blocks of
    2*poly_size, twiddle w^(2^(logm-t-1) * (k+1))."""
    F = fr()
    m = 1 << logm
    mbyl = v.shape[-2]
    o = jnp.arange(mbyl, dtype=jnp.int32)

    def level(t, v):
        ps = jnp.int32(1) << t
        j = o >> (t + 1)
        k = o & (ps - 1)
        b = (o >> t) & 1
        lo = (j << (t + 1)) + k
        hi = lo + ps
        e = (k + 1) << (logm - 1 - t)
        if inverse:
            e = (m - e) & (m - 1)
        w = jnp.take(wpows, e, axis=0)
        x = jnp.take(v, lo, axis=-2)
        y = F.mul(jnp.take(v, hi, axis=-2), w)
        return jnp.where((b == 0)[:, None], F.add(x, y), F.sub(x, y))

    return jax.lax.fori_loop(0, logm - logl, level, v)


@functools.partial(jax.jit, static_argnames=("logm", "logl", "inverse"))
def _fft2_king(s, wpows, logm: int, logl: int, inverse: bool):
    """Stage-2 butterflies + rotate on the full (m, 16) clear vector.

    Level i = logl .. 1 (descending): reads pairs s[k*2^i + 2j], writes
    x+y at k*2^(i-1)+j and x-y at (k+ps)*2^(i-1)+j, twiddle
    w^(2^(i-1)*(k+1)); ends with rotate_right(1) (dfft/mod.rs:177)."""
    F = fr()
    m = 1 << logm
    o = jnp.arange(m, dtype=jnp.int32)
    half = m >> 1

    def level(t, s):
        i = jnp.int32(logl - t)
        b = (o >= half).astype(jnp.int32)
        op = o - b * half
        k = op >> (i - 1)
        j = op & ((jnp.int32(1) << (i - 1)) - 1)
        lo = (k << i) + 2 * j
        e = (k + 1) << (i - 1)
        if inverse:
            e = (m - e) & (m - 1)
        w = jnp.take(wpows, e, axis=0)
        x = jnp.take(s, lo, axis=-2)
        y = F.mul(jnp.take(s, lo + 1, axis=-2), w)
        return jnp.where((b == 0)[:, None], F.add(x, y), F.sub(x, y))

    s = jax.lax.fori_loop(0, logl, level, s)
    return jnp.roll(s, 1, axis=-2)


def _king_clear_array(
    x,
    pp: PackedSharingParams,
    logm: int,
    degree2: bool,
    inverse: bool,
    wpows,
):
    """Unpack a stacked (n, ..., m/l, 16) share tensor and run the stage-2
    butterflies in the clear: the king-side head shared by the fused
    king_clear mode of both backends. Returns (..., m, 16) natural order."""
    chunks = jnp.moveaxis(x, 0, -2)  # (..., m/l, n, 16)
    secrets = pp.unpack2(chunks) if degree2 else pp.unpack(chunks)
    s1 = secrets.reshape(secrets.shape[:-3] + (1 << logm, 16))
    return _fft2_king(s1, wpows, logm, pp.l.bit_length() - 1, inverse)


def _king_tail_array(
    x,
    pp: PackedSharingParams,
    logm: int,
    rearrange: bool,
    pad: int,
    degree2: bool,
    inverse: bool,
    wpows,
):
    """King-side tail on a stacked (n, ..., m/l, 16) share tensor ->
    (n, ..., c, 16) per-party output shares (pure function — shared by the
    async star backend and the SPMD mesh backend; extra leading batch axes
    after the party axis run as one fused transform)."""
    m = 1 << logm
    s1 = _king_clear_array(x, pp, logm, degree2, inverse, wpows)
    batch = s1.shape[:-2]
    if pad > 1:
        widths = [(0, 0)] * len(batch) + [(0, (pad - 1) * m), (0, 0)]
        s1 = jnp.pad(s1, widths)
    mp = pad * m
    c = mp // pp.l
    if rearrange:
        s1 = jnp.take(s1, jnp.asarray(bitrev_perm(mp)), axis=-2)
        out_chunks = jnp.swapaxes(
            s1.reshape(batch + (pp.l, c, 16)), -3, -2
        )
    else:
        out_chunks = s1.reshape(batch + (c, pp.l, 16))
    out_shares = pp.pack_from_public(out_chunks)  # (..., c, n, 16)
    return jnp.moveaxis(out_shares, -2, 0)  # (n, ..., c, 16)


def _king_tail(shares_list, pp, logm, rearrange, pad, degree2, inverse, wpows):
    """List-of-shares wrapper for the async star backend."""
    per_party = _king_tail_array(
        jnp.stack(shares_list, axis=0), pp, logm, rearrange, pad, degree2,
        inverse, wpows,
    )
    return [per_party[i] for i in range(pp.n)]


async def _d_transform(
    share_vec,
    rearrange: bool,
    pad: int,
    degree2: bool,
    dom,
    pp: PackedSharingParams,
    net: Net,
    sid: int,
    inverse: bool,
    king_clear: bool = False,
):
    m = dom.size
    assert share_vec.shape[-2] * pp.l == m, (
        f"Mismatch of size in FFT: {share_vec.shape[-2] * pp.l} vs {m}"
    )
    assert dom.offset == 1, "d_fft runs on plain (non-coset) domains"
    logm = m.bit_length() - 1
    logl = pp.l.bit_length() - 1
    wpows = domain(m)._live_wpows()
    F = fr()
    log.debug("d_%sfft: party %d stage-1 m=%d (sid=%d)",
              "i" if inverse else "", net.party_id, m, sid)
    with _tracing.span(
        "dfft.ifft" if inverse else "dfft.fft", party=net.party_id, sid=sid,
        attrs=_tracing.DISPATCH,
    ):
        if inverse:
            share_vec = F.mul(share_vec, dom._size_inv)
        local = _fft1_local(share_vec, wpows, logm, logl, inverse)

        gathered = await net.gather_to_king(local, sid)
        if king_clear:
            # Fused mode: leave the clear natural-order result on the king
            # (the caller's next step is a king-side combine — re-packing
            # and scattering here would be immediately undone by a gather).
            if not net.is_king:
                return None
            with king_section("dfft"):
                return _king_clear_array(
                    jnp.stack(gathered, axis=0), pp, logm, degree2, inverse,
                    wpows,
                )
        out = None
        if net.is_king:
            with king_section("dfft"):
                out = _king_tail(
                    gathered, pp, logm, rearrange, pad, degree2, inverse,
                    wpows,
                )
        return await net.scatter_from_king(out, sid)


async def d_fft(
    pcoeff_share,
    rearrange: bool,
    pad: int,
    degree2: bool,
    dom,
    pp: PackedSharingParams,
    net: Net,
    sid: int = 0,
    king_clear: bool = False,
):
    """Packed shares of coefficients (bitrev+strided layout) -> packed shares
    of evaluations on `dom` (d_fft, dfft/mod.rs:17-54).

    king_clear=True skips the re-pack + scatter and returns the clear
    natural-order evaluations on the king (None on clients) — for callers
    whose next step is a king-side combine (ext_wit::h)."""
    return await _d_transform(
        pcoeff_share, rearrange, pad, degree2, dom, pp, net, sid,
        inverse=False, king_clear=king_clear,
    )


async def d_ifft(
    peval_share,
    rearrange: bool,
    pad: int,
    degree2: bool,
    dom,
    pp: PackedSharingParams,
    net: Net,
    sid: int = 0,
):
    """Packed shares of evaluations -> packed shares of coefficients
    (d_ifft, dfft/mod.rs:56-95): scale by 1/m, run with the inverse root."""
    return await _d_transform(
        peval_share, rearrange, pad, degree2, dom, pp, net, sid, inverse=True
    )
