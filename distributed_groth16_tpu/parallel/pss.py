"""Packed secret sharing (PSS) over BN254 Fr for JAX/TPU.

The sharding format of the whole framework: `l` secrets are packed into one
degree-(t+l) polynomial and dealt as `n = 4l` shares (threshold `t = l-1`),
exactly the zkSaaS scheme of the reference's secret-sharing crate
(secret-sharing/src/pss.rs:13-148):

  * shares    = evaluations on the size-n `share` domain,
  * secrets   = evaluations on a coset (offset = Fr generator) of the
                size-(l+t+1) `secret` domain,
  * products  = evaluations on the size-2(l+t+1) `secret2` coset.

pack   : IFFT on `secret` (zero-padded), FFT on `share`        (pss.rs:86-92)
unpack : IFFT on `share`, truncate to 2l coeffs, FFT on `secret`, keep l
                                                                (pss.rs:110-127)
unpack2: IFFT on `share`, FFT on `secret2`, keep even indices of the first
         2l entries                                             (pss.rs:131-148)

All field-vector transforms run batched on device via ops/ntt.py (one tiny
NTT per m/l chunk, vectorized over the chunk axis — the TPU-friendly shape).
Group-element ("in the exponent") packing for the CRS exposes the same maps
as precomputed l x n / n x l Fr matrices applied with one batched
double-and-add ladder (dist-primitives/src/dmsm/mod.rs:50-68 semantics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import refmath as rm
from ..ops.constants import FR_GENERATOR, R, to_limbs
from ..ops.curve import CurvePoints, fixed_scalar_ladder_tensors
from ..ops.field import fr
from ..ops.ntt import domain


class PackedSharingParams:
    """PSS parameters and transforms for packing factor l (n = 4l parties).

    Defaults to BN254 Fr. Passing another (modulus, generator) — e.g.
    BLS12-377's — builds the HOST domains (and hence the pack/unpack
    matrices and every in-the-exponent map) over that field; the DEVICE
    field-share transforms stay BN254-only (their NTT/encode stack is
    built over ops/constants.R) and raise loudly if called.
    """

    def __init__(self, l: int, modulus: int = R,
                 generator: int = FR_GENERATOR):
        assert l >= 1 and (l & (l - 1)) == 0, "packing factor must be a power of 2"
        self.l = l
        self.t = l - 1
        self.n = 4 * l
        self.modulus = modulus
        assert self.n == 2 * (self.t + self.l + 1)
        if modulus == R:
            self.share = domain(self.n)
            self.secret = domain(self.l + self.t + 1, offset=FR_GENERATOR)
            self.secret2 = domain(
                2 * (self.l + self.t + 1), offset=FR_GENERATOR
            )
        else:
            self.share = self.secret = self.secret2 = None
        # host-side mirrors for matrix construction / ground truth
        self.share_h = rm.Domain(self.n, modulus=modulus,
                                 generator=generator)
        self.secret_h = rm.Domain(self.l + self.t + 1, offset=generator,
                                  modulus=modulus, generator=generator)
        self.secret2_h = rm.Domain(2 * (self.l + self.t + 1),
                                   offset=generator, modulus=modulus,
                                   generator=generator)

    def _device_domains(self):
        if self.share is None:
            raise NotImplementedError(
                "device field-share transforms are BN254-Fr-only; this "
                "PackedSharingParams was built over a different scalar "
                "field (pack scalars host-side, e.g. "
                "bls12_377.pack_scalars_377)"
            )
        return self.share, self.secret, self.secret2

    # -- field-vector transforms (batched over leading axes) ------------------

    def pack_from_public(self, secrets):
        """(..., l, 16) secrets -> (..., n, 16) shares."""
        assert secrets.shape[-2] == self.l
        share, secret, _ = self._device_domains()
        return share.fft(secret.ifft(secrets))

    def pack_from_public_rand(self, secrets, rng: np.random.Generator):
        """Packing with t+1 uniform-in-Fr random filler points — the hiding
        randomness of the PSS scheme (pss.rs:72-82; unlike the reference's
        test rng, fillers here are drawn uniformly from the full field)."""
        assert secrets.shape[-2] == self.l
        batch = secrets.shape[:-2]
        count = int(np.prod(batch, dtype=np.int64)) * (self.t + 1)
        # one bulk draw of 320-bit values (>=64 bits of slack over the 254-bit
        # modulus keeps the mod-R bias negligible), vectorized via frombuffer
        raw = np.frombuffer(rng.bytes(count * 40), dtype=np.uint8)
        raw = raw.reshape(count, 40)
        vals = np.empty(count, dtype=object)
        weights = [1 << (8 * i) for i in range(40)]
        cols = [raw[:, i] for i in range(40)]
        acc = np.zeros(count, dtype=object)
        for w, col in zip(weights, cols):
            acc += col.astype(object) * w
        vals = acc % R
        rand = fr().encode(vals.reshape(batch + (self.t + 1,)))
        full = jnp.concatenate([secrets, rand], axis=-2)
        share, secret, _ = self._device_domains()
        return share.fft(secret.ifft(full))

    def unpack(self, shares):
        """(..., n, 16) degree-(t+l) shares -> (..., l, 16) secrets."""
        assert shares.shape[-2] == self.n
        share, secret, _ = self._device_domains()
        coeffs = share.ifft(shares)[..., : secret.size, :]
        return secret.fft(coeffs)[..., : self.l, :]

    def unpack2(self, shares):
        """(..., n, 16) degree-2(t+l) shares -> (..., l, 16) secrets."""
        assert shares.shape[-2] == self.n
        share, _, secret2 = self._device_domains()
        coeffs = share.ifft(shares)
        evals = secret2.fft(coeffs)
        return evals[..., : 2 * self.l : 2, :]

    # -- linear maps as explicit Fr matrices (for group elements) ------------

    @functools.cached_property
    def pack_matrix(self) -> list[list[int]]:
        """(n, l) ints: shares = M @ secrets."""
        cols = []
        for i in range(self.l):
            e = [0] * self.l
            e[i] = 1
            coeffs = self.secret_h.ifft(e)
            cols.append(self.share_h.fft(coeffs))
        return [[cols[i][p] for i in range(self.l)] for p in range(self.n)]

    @functools.cached_property
    def unpack_matrix(self) -> list[list[int]]:
        """(l, n) ints: secrets = M @ shares (degree t+l shares)."""
        cols = []
        for j in range(self.n):
            e = [0] * self.n
            e[j] = 1
            coeffs = self.share_h.ifft(e)[: self.secret_h.size]
            cols.append(self.secret_h.fft(coeffs)[: self.l])
        return [[cols[j][i] for j in range(self.n)] for i in range(self.l)]

    @functools.cached_property
    def unpack2_matrix(self) -> list[list[int]]:
        """(l, n) ints: secrets = M @ shares (degree 2(t+l) shares)."""
        cols = []
        for j in range(self.n):
            e = [0] * self.n
            e[j] = 1
            coeffs = self.share_h.ifft(e)
            evals = self.secret2_h.fft(coeffs)
            cols.append(evals[: 2 * self.l : 2])
        return [[cols[j][i] for j in range(self.n)] for i in range(self.l)]

    @functools.cached_property
    def unpack2_weights(self) -> list[int]:
        """(n,) ints: w_j = sum_o unpack2_matrix[o][j] mod modulus, so that
        the SUM of the l secrets a degree-2(t+l) sharing packs is
        sum_j w_j * share_j. d_msm only ever sums what it unpacks, so each
        party weighs its own shares by w_j before its MSM and the king adds
        the n points."""
        mat = self.unpack2_matrix
        return [sum(row[j] for row in mat) % self.modulus
                for j in range(self.n)]

    def unpack2_weight_limbs(self, F, party: int) -> np.ndarray:
        """Party `party`'s w_j as STANDARD-form limbs of F, the scalar field
        this PackedSharingParams is built over: F.mul(mont_shares, this)
        is w_j * shares in standard form, one Montgomery product."""
        assert F.p == self.modulus, "weights live in pp's own scalar field"
        return np.array(to_limbs(self.unpack2_weights[party], F.nl),
                        dtype=np.uint32)

    # -- group-element ("in the exponent") transforms -------------------------
    #
    # d_msm's king does not unpack in the exponent: it sums the points its
    # parties weighed by `unpack2_weights` (parallel/dmsm.py). These maps
    # serve the CRS packing, the mesh path and point-NTT tests.
    #
    # Two implementations of the same linear maps on curve points:
    #
    #  * dense ladder (default): the (o, k) transform matrix applied in ONE
    #    fixed-scalar multi-exponentiation ladder. With the BN254 G1 GLV
    #    endomorphism (ops/glv.py) every matrix entry splits into two
    #    ~129-bit halves over the doubled base set {P, phi(P)}, so the
    #    sequential depth is 129 point-add rounds — half of plain
    #    double-and-add, and ~2x fewer than the reference's O(n log n)
    #    point-domain NTT at the deployed party counts (n <= 32), where
    #    each of the log n butterfly levels is itself a full-width ladder.
    #
    #  * point-domain NTT (parallel/pointntt.py): the reference's algorithm
    #    (dist-primitives/src/dmsm/mod.rs:7-68) — IFFT on the share domain,
    #    FFT on the secret/secret2 coset, directly on point tensors. Op
    #    count O(n log n) beats the dense O(l n) matrix only from n ~ 64
    #    parties up (each NTT level costs a full ladder of depth nbits), so
    #    `method="auto"` switches there.

    _NTT_THRESHOLD = 64

    def _ladder_tensors(self, curve: CurvePoints, which: str):
        """Device tensors (bits, signs, nbits) for the dense ladder of the
        named matrix. bits: (o, K, nbits) uint32; signs: (o, K) bool (GLV
        halves can be negative) or None; K = 2k with GLV (bases then endo
        images), k without. Cached ON the curve object keyed by matrix
        content (l, which) — id()-keyed caching would go stale if a curve
        instance were collected and its id reused."""
        cache = curve.__dict__.setdefault("_pss_ladder_cache", {})
        key = (self.l, which)
        if key in cache:
            return cache[key]
        mat = {
            "pack": self.pack_matrix,
            "unpack": self.unpack_matrix,
            "unpack2": self.unpack2_matrix,
        }[which]
        o, k = len(mat), len(mat[0])
        flat = [mat[a][b] for a in range(o) for b in range(k)]
        # ensure_compile_time_eval: this precomputation is pure-constant, but
        # first use may happen inside a jit/shard_map trace — without the
        # eval fence the cached tensors would be tracers of that trace and
        # poison every later caller (UnexpectedTracerError)
        with jax.ensure_compile_time_eval():
            bits, signs, nbits = fixed_scalar_ladder_tensors(curve, flat)
            # (P, o*k, nbits) -> per output row [part0 | part1 entries]
            P = bits.shape[0]
            bits = (
                bits.reshape(P, o, k, nbits)
                .transpose(1, 0, 2, 3)
                .reshape(o, P * k, nbits)
            )
            if signs is not None:
                signs = (
                    signs.reshape(P, o, k).transpose(1, 0, 2).reshape(o, P * k)
                )
        cache[key] = (jax.device_get(bits),
                      None if signs is None else jax.device_get(signs), nbits)
        return cache[key]

    def _apply_point_matrix(self, curve: CurvePoints, which: str, pts):
        """out[..., o, :] = sum_i mat[o][i] * pts[..., i, :].

        pts: (..., k) + point shape. One nbits-step ladder: the doubling
        chain runs on the (..., K) base set only (row-independent); the
        conditional (sign-adjusted) adds run batched over (..., o, K). Then
        a log-K tree sum over the K axis.

        Both ladder paths run under jit: eagerly-dispatched scan/fori
        executables are an XLA:CPU crash class in this environment
        (segfault in backend_compile_and_load once enough executables are
        live in the process — the class prove._maybe_mul dodged by going
        host-side; reproduced at test_pss.py:108 via eager
        sum_sequential).
        """
        bits, signs, nbits = self._ladder_tensors(curve, which)
        bits = jnp.asarray(bits)  # cache holds host arrays (tracer hygiene)
        signs = None if signs is None else jnp.asarray(signs)
        o = bits.shape[0]
        ax = pts.ndim - 2 - curve.coord_axes  # index of the k axis
        batch = pts.shape[:ax]
        base = pts
        if curve.glv is not None:
            base = jnp.concatenate([pts, curve.endo(pts)], axis=ax)
        K = base.shape[ax]

        # TPU fast path: run the ladder limb-major so every add/double in
        # the nbits-step sweep rides the Pallas kernels — CRS packing was
        # 74% of the million-2^12 wall-clock on the row-major path.
        from ..ops.msm import _tree_group

        B = int(np.prod(batch, dtype=np.int64)) if batch else 1
        g = _tree_group(curve, B * o * K)
        if g is not None:
            from ..ops.limb_kernels import ladder_apply_jit

            rm_flat = base.reshape((B * K,) + (3,) + curve.elem_shape)
            lm = g.from_rowmajor(rm_flat).reshape(g.ROWS, B, K)
            out_lm = ladder_apply_jit(g, lm, bits, signs, nbits)
            out_rm = g.to_rowmajor(out_lm.reshape(g.ROWS, B * o))
            return out_rm.reshape(batch + (o, 3) + curve.elem_shape)
        return _dense_ladder_jit(curve, ax, nbits, base, bits, signs)

    def packexp_from_public(self, curve: CurvePoints, pts, method="auto"):
        """(..., l) + point -> (..., n) + point (dmsm/mod.rs:61-68)."""
        if self._pick_exp_method(method) == "ntt":
            from .pointntt import packexp_ntt

            return packexp_ntt(self, curve, pts)
        return self._apply_point_matrix(curve, "pack", pts)

    def unpackexp(
        self, curve: CurvePoints, shares, degree2: bool = False, method="auto"
    ):
        """(..., n) + point -> (..., l) + point (dmsm/mod.rs:7-48)."""
        if self._pick_exp_method(method) == "ntt":
            from .pointntt import unpackexp_ntt

            return unpackexp_ntt(self, curve, shares, degree2)
        which = "unpack2" if degree2 else "unpack"
        return self._apply_point_matrix(curve, which, shares)

    def _pick_exp_method(self, method: str) -> str:
        if self.modulus != R:
            # pointntt's domains/twiddles are built over BN254 Fr; the
            # dense matrix ladder is the only in-exponent path for other
            # scalar fields
            if method == "ntt":
                raise NotImplementedError(
                    "in-exponent point-NTT is BN254-Fr-only; use the "
                    "dense ladder for this scalar field"
                )
            return "dense"
        if method == "auto":
            return "ntt" if self.n >= self._NTT_THRESHOLD else "dense"
        assert method in ("dense", "ntt")
        return method


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _dense_ladder_jit(curve: CurvePoints, ax: int, nbits: int,
                      base, bits, signs):
    """Row-major fixed-scalar ladder + sequential K-reduction as ONE jitted
    program (see _apply_point_matrix's crash-class note)."""
    o = bits.shape[0]
    batch = base.shape[:ax]
    K = base.shape[ax]
    acc = jnp.broadcast_to(
        curve.infinity(),
        batch + (o, K, 3) + curve.elem_shape,
    )

    def body(i, state):
        acc, b = state
        bit = bits[..., i]  # (o, K)
        addend = jnp.expand_dims(b, ax)
        if signs is not None:
            addend = curve.select(signs, curve.neg(addend), addend)
        cand = curve.add(acc, addend)
        acc = curve.select(bit == 1, cand, acc)
        return acc, curve.double(b)

    acc, _ = jax.lax.fori_loop(0, nbits, body, (acc, base))
    # K is small (<= 2n): sequential accumulation is one add instance,
    # the compile-light reduction
    return curve.sum_sequential(acc, axis=len(batch) + 1)


@functools.cache
def pss(l: int) -> PackedSharingParams:
    return PackedSharingParams(l)


# ---------------------------------------------------------------------------
# Host-side ground truth (pure ints) for differential tests
# ---------------------------------------------------------------------------


def pack_host(pp: PackedSharingParams, secrets: list[int]) -> list[int]:
    assert len(secrets) == pp.l
    return pp.share_h.fft(pp.secret_h.ifft(secrets))


def unpack_host(pp: PackedSharingParams, shares: list[int]) -> list[int]:
    coeffs = pp.share_h.ifft(shares)[: pp.secret_h.size]
    return pp.secret_h.fft(coeffs)[: pp.l]


def unpack2_host(pp: PackedSharingParams, shares: list[int]) -> list[int]:
    coeffs = pp.share_h.ifft(shares)
    return pp.secret2_h.fft(coeffs)[: 2 * pp.l : 2]
