"""Radix-2 NTT over BN254 Fr for JAX/TPU, matching ark-poly's
Radix2EvaluationDomain semantics (the reference's FFT substrate for both packed
secret sharing — secret-sharing/src/pss.rs:39-47 — and the distributed FFT,
dist-primitives/src/dfft/mod.rs).

A `JaxDomain(size, offset)` evaluates polynomials at offset * w^i where
w = g^((r-1)/size), g = 5 (arkworks Fr::GENERATOR). Data layout: coefficient /
evaluation vectors are (..., n, 16) uint32 Montgomery limb tensors.

XLA-friendliness: the transform is a single shape-uniform butterfly body run
under `lax.fori_loop` over the log2(n) stages — twiddles are looked up from one
dense table of the n-th roots of unity by index arithmetic — so the compiled
graph size is independent of n and a domain of any size reuses one compiled
butterfly per batch shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import metrics as _tm
from .constants import FR_GENERATOR, FR_TWO_ADICITY, N_LIMBS, R
from .field import fr
from .refmath import finv

# same family ops/msm.py registers (idempotent): which NTT path ran —
# dashboards catch a TPU backend silently on the row-major fallback
_ROUTE = _tm.registry().counter(
    "kernel_route_total",
    "Kernel-path routing decisions at dispatch/trace time, per kernel "
    "and chosen implementation path",
    ("kernel", "path"),
)
_R_LIMB = _ROUTE.labels(kernel="ntt", path="limb")
_R_ROW = _ROUTE.labels(kernel="ntt", path="row")


def _tracing_active() -> bool:
    """True when called under a jit/vmap trace: arithmetic on a concrete
    array yields a Tracer exactly then."""
    return isinstance(jnp.zeros((), dtype=jnp.int32) + 0, jax.core.Tracer)


def _bitrev(n: int, xp):
    """Bit-reversal permutation over array namespace xp (np for the host
    table, jnp for in-trace builds — one implementation for both paths)."""
    assert n > 0 and n & (n - 1) == 0, f"bitrev needs a power of two, got {n}"
    logn = n.bit_length() - 1
    idx = xp.arange(n, dtype=xp.int32)
    out = xp.zeros((n,), dtype=xp.int32)
    for b in range(logn):
        out = out | (((idx >> b) & 1) << (logn - 1 - b))
    return out


def bitrev_perm(n: int) -> np.ndarray:
    """Bit-reversal permutation indices (matches dfft/mod.rs:258-271)."""
    return _bitrev(n, np)


@functools.partial(jax.jit, static_argnames=("logn", "inverse"))
def _ntt_core(x, perm, wpows, logn: int, inverse: bool = False):
    """DIT radix-2 NTT with dense root table.

    x:     (..., n, 16) Montgomery uint32
    perm:  (n,) int32 bit-reversal permutation
    wpows: (n, 16) Montgomery powers w^0..w^{n-1} of the size-n FORWARD root;
           the inverse transform indexes it as w^{-k} = wpows[(n-k) mod n].
    """
    F = fr()
    n = x.shape[-2]
    x = jnp.take(x, perm, axis=-2)
    j = jnp.arange(n, dtype=jnp.int32)

    def stage(s, x):
        span = jnp.int32(1) << s
        # butterfly partners: lo has bit s clear, hi has bit s set
        lo_idx = j & ~span
        hi_idx = j | span
        # twiddle for lane j: wspan^(j mod span) with wspan = w^(n/(2*span))
        k = (j & (span - 1)) * (jnp.int32(n) >> (s + 1))
        if inverse:
            k = (jnp.int32(n) - k) & jnp.int32(n - 1)
        w = jnp.take(wpows, k, axis=0)
        lo = jnp.take(x, lo_idx, axis=-2)
        hi = jnp.take(x, hi_idx, axis=-2)
        t = F.mul(hi, w)
        is_lo = (j & span) == 0
        return jnp.where(is_lo[:, None], F.add(lo, t), F.sub(lo, t))

    return jax.lax.fori_loop(0, logn, stage, x)


class JaxDomain:
    """Device-side radix-2 evaluation domain over Fr (ark semantics)."""

    def __init__(self, size: int, offset: int = 1):
        assert size & (size - 1) == 0 and size > 0
        assert size <= (1 << FR_TWO_ADICITY)
        self.size = size
        self.logn = size.bit_length() - 1
        self.offset = offset % R
        self.group_gen = pow(FR_GENERATOR, (R - 1) // size, R)
        self.group_gen_inv = finv(self.group_gen, R)
        F = fr()
        # NUMPY, not jnp: domain() is functools-cached, and the first
        # construction may happen inside a jit trace — jnp.asarray under
        # an active trace yields a tracer-backed constant that would be
        # cached and poison every later eager fft/ifft. numpy arrays are
        # plain constants in both worlds (jnp.take accepts numpy indices;
        # F.mul accepts a numpy operand).
        self._perm = bitrev_perm(size)
        self._size_inv = F.encode_np([finv(size, R)])[0]
        # The device root/offset tables are built LAZILY, first time they
        # are needed outside a trace (_live_* below): domain() is
        # functools.cached, and if the first construction happened inside a
        # jit trace an eager _powers_device here would cache TRACERS that
        # poison every later call (the _SmallNTT "numpy, NOT jnp" lesson).
        self._wpows_cached = None
        self._perm_cached = None
        self._off_cached: dict[bool, jnp.ndarray] = {}

    def elements(self) -> list[int]:
        out, acc = [], self.offset
        for _ in range(self.size):
            out.append(acc)
            acc = acc * self.group_gen % R
        return out

    # -- trace-aware table access -------------------------------------------
    # Under an active trace the precomputed device tables would be captured
    # as jit CONSTANTS and baked into the lowered module as literals — at
    # n = 2^20 that is a 64 MB literal PER TABLE (observed: 135 MB of
    # StableHLO for one transform), the exact monolith class that wedged
    # the remote TPU compile service. Rebuilding in-trace costs O(log n)
    # muls of device work and keeps programs small; eager callers keep the
    # cached concrete tables.

    def _live_wpows(self):
        if _tracing_active():
            return _powers_device(self.group_gen, self.size)
        if self._wpows_cached is None:
            self._wpows_cached = _powers_device(self.group_gen, self.size)
        return self._wpows_cached

    def _live_perm(self):
        if not _tracing_active():
            if self._perm_cached is None:
                self._perm_cached = jnp.asarray(self._perm)
            return self._perm_cached
        return _bitrev_traced(self.size)

    def _live_off(self, inverse: bool):
        if self.offset == 1:
            return None
        base = finv(self.offset, R) if inverse else self.offset
        if _tracing_active():
            return _powers_device(base, self.size)
        if inverse not in self._off_cached:
            self._off_cached[inverse] = _powers_device(base, self.size)
        return self._off_cached[inverse]

    def fft(self, coeffs):
        """Evaluate: (..., k<=n, 16) coeffs -> (..., n, 16) evals."""
        F = fr()
        x = _zpad(coeffs, self.size)
        off = self._live_off(False)
        if off is not None:
            x = F.mul(x, off)
        if _limb_ntt_ok(self.size):
            _R_LIMB.inc()
            return _limb_ntt_route(x, self.size, False)
        _R_ROW.inc()
        return _ntt_core(x, self._live_perm(), self._live_wpows(), self.logn)

    def ifft(self, evals):
        """Interpolate: (..., k<=n, 16) evals -> (..., n, 16) coeffs."""
        F = fr()
        x = _zpad(evals, self.size)
        if _limb_ntt_ok(self.size):
            _R_LIMB.inc()
            x = _limb_ntt_route(x, self.size, True)
        else:
            _R_ROW.inc()
            x = _ntt_core(
                x, self._live_perm(), self._live_wpows(), self.logn,
                inverse=True,
            )
        x = F.mul(x, self._size_inv)
        off = self._live_off(True)
        if off is not None:
            x = F.mul(x, off)
        return x

    def get_coset(self, offset: int) -> "JaxDomain":
        return domain(self.size, offset * self.offset % R)


def _limb_ntt_ok(n: int) -> bool:
    """Route big transforms to the limb-major Pallas path (ops/ntt_limb.py)
    on TPU backends, or anywhere under DG16_FORCE_LIMB_NTT=1 (differential
    tests exercise the identical XLA bodies on CPU). Small transforms keep
    the row-major fori core: the limb path's layout transposes only pay
    off when the butterfly work dominates."""
    from ..utils import config as _config

    if _config.env_flag("DG16_FORCE_LIMB_NTT"):
        return True
    from .limb_kernels import use_pallas

    return use_pallas() and n >= 2048


@functools.partial(jax.jit, static_argnums=(1, 2))
def _limb_ntt_route(x, n: int, inverse: bool):
    """(..., n, 16) row-major <-> limb-major shim around ntt_limb (no 1/n
    scaling — the caller's ifft applies size_inv, as with _ntt_core).

    The limb pipeline works in the redundant [0, 2p) Montgomery class;
    the row-major world requires CANONICAL limbs (returning redundant
    representatives silently corrupted downstream F.mul results — caught
    by the prove_single integration test), so canon() at the boundary."""
    from .ntt_limb import lfr, ntt_limb

    batch = x.shape[:-2]
    flat = x.reshape((-1, n, N_LIMBS))

    def one(v):  # (n, 16) -> (n, 16)
        return jnp.transpose(lfr().canon(ntt_limb(jnp.transpose(v), n,
                                                  inverse)))

    out = jax.vmap(one)(flat)
    return out.reshape(batch + (n, N_LIMBS))


def _zpad(x, n):
    k = x.shape[-2]
    assert k <= n, f"input length {k} exceeds domain size {n}"
    if k == n:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, n - k), (0, 0)]
    return jnp.pad(x, pad)


def _bitrev_traced(n: int):
    """(n,) int32 bit-reversal permutation as traced device ops (the numpy
    table would bake a 4·n-byte literal into any enclosing jit)."""
    return _bitrev(n, jnp)


def _powers(base: int, n: int) -> list[int]:
    out, acc = [], 1
    for _ in range(n):
        out.append(acc)
        acc = acc * base % R
    return out


def _powers_device(base: int, n: int) -> jnp.ndarray:
    """(n, 16) table of base^0..base^{n-1}, built with O(log n) device muls.

    Host work is O(1) (encode the base once); the table doubles on device:
    [b^0..b^{k-1}] -> [b^0..b^{2k-1}] via one batched multiply by b^k.
    """
    F = fr()
    logn = max(1, (n - 1).bit_length())
    # base^(2^b) for each bit, via repeated squaring on a single element —
    # all muls here share the (1, 16) shape so only one executable compiles.
    bit_pows = [F.encode([base % R])]
    for _ in range(logn - 1):
        bit_pows.append(F.mul(bit_pows[-1], bit_pows[-1]))
    # tbl[k] = prod_{b: bit b of k set} base^(2^b); logn muls of shape (n, 16).
    k = jnp.arange(n, dtype=jnp.uint32)
    tbl = jnp.broadcast_to(jnp.asarray(F.one), (n, N_LIMBS))
    for b in range(logn):
        hit = ((k >> b) & 1) == 1
        tbl = jnp.where(hit[:, None], F.mul(tbl, bit_pows[b]), tbl)
    return tbl


@functools.cache
def domain(size: int, offset: int = 1) -> JaxDomain:
    return JaxDomain(size, offset)
