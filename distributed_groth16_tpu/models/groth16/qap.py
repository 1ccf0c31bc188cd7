"""QAP witness reduction on device — the model's forward-input stage.

Mirrors the reference's groth16/src/qap.rs:44-187 semantics:

  * `qap(r1cs, assignment)`: per-constraint inner products
    a_j = <A_j, z>, b_j = <B_j, z> on the size-m domain
    (m = next pow2 of num_constraints + num_instance), the input-consistency
    rows a[nc..nc+ni] = z[..ni] appended (qap.rs:69-73), c = a ⊙ b.
  * `QAP.pss(pp)`: bit-reverse + stride-chunk + pack each vector, transpose
    to per-party shares (qap.rs:143-187) — pack_strided does exactly this.

TPU-first sparse matvec: the R1CS matrices are lowered once to sorted-COO
device tensors; evaluation is one batched Montgomery multiply over the nnz
entries followed by a log-depth `lax.associative_scan` prefix sum under
field addition and a per-row boundary gather — no scatter, no host loop
(same trick as the MSM bucketing in ops/msm.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property

import jax
import jax.numpy as jnp
import numpy as np

from ...frontend.r1cs import R1CS
from ...ops.field import fr
from ...ops.ntt import JaxDomain, domain
from ...parallel.packing import pack_strided
from ...parallel.pss import PackedSharingParams
from ...telemetry import metrics as _tm

_CHECKS = _tm.registry().counter(
    "witness_device_checks_total",
    "Witnesses whose satisfiability was decided on the device, from the "
    "QAP evaluations their own proof uses, by the verdict that was read",
    ("verdict",),
)
# bound at import, so that an unraised verdict still prints 0
_CHECK_OK = _CHECKS.labels(verdict="ok")
_CHECK_REJECTED = _CHECKS.labels(verdict="rejected")
# what a refused witness fails with, whichever check refused it (the form's
# on the host, `service/worker.py`, or the rows' here)
UNSATISFIED = "witness does not satisfy the circuit"


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


@dataclass
class SparseMatrixDevice:
    """Sorted-COO device form of one R1CS matrix (rows sorted, host-static
    row boundaries)."""

    coeffs: jnp.ndarray  # (nnz, 16) Montgomery
    cols: jnp.ndarray  # (nnz,) int32
    ends_idx: jnp.ndarray  # (num_rows,) device: clamp(end-1, 0)
    starts_idx: jnp.ndarray  # (num_rows,) device: clamp(start-1, 0)
    nonempty: jnp.ndarray  # (num_rows,) device bool
    at_origin: jnp.ndarray  # (num_rows,) device bool: row starts at entry 0
    num_rows: int

    @staticmethod
    def build(rows: list[list[tuple[int, int]]]) -> "SparseMatrixDevice":
        F = fr()
        coeffs, cols, row_ids = [], [], []
        for j, row in enumerate(rows):
            for coeff, wire in row:
                coeffs.append(coeff)
                cols.append(wire)
                row_ids.append(j)
        if not coeffs:  # fully empty matrix: keep one dummy zero entry
            coeffs, cols, row_ids = [0], [0], [0]
        row_ids = np.asarray(row_ids, dtype=np.int64)
        starts = np.searchsorted(row_ids, np.arange(len(rows)), side="left")
        ends = np.searchsorted(row_ids, np.arange(len(rows)), side="right")
        return SparseMatrixDevice(
            coeffs=F.encode(coeffs),
            cols=jnp.asarray(np.asarray(cols, dtype=np.int32)),
            ends_idx=jnp.asarray(np.maximum(ends - 1, 0)),
            starts_idx=jnp.asarray(np.maximum(starts - 1, 0)),
            nonempty=jnp.asarray(ends > starts),
            at_origin=jnp.asarray(starts == 0),
            num_rows=len(rows),
        )

    def matvec(self, z: jnp.ndarray) -> jnp.ndarray:
        """(nw, 16) Montgomery assignment -> (num_rows, 16) row inner
        products, all on device."""
        return _matvec_jit(
            self.coeffs, self.cols, self.ends_idx, self.starts_idx,
            self.nonempty, self.at_origin, z,
        )


@jax.jit  # eager associative_scan dispatch is an XLA:CPU crash class
def _matvec_jit(coeffs, cols, ends_idx, starts_idx, nonempty, at_origin, z):
    F = fr()
    prod = F.mul(coeffs, jnp.take(z, cols, axis=0))
    prefix = jax.lax.associative_scan(F.add, prod, axis=0)
    hi = jnp.take(prefix, ends_idx, axis=0)
    lo = jnp.take(prefix, starts_idx, axis=0)
    val = jnp.where(at_origin[:, None], hi, F.sub(hi, lo))
    return jnp.where(nonempty[:, None], val, jnp.zeros_like(val))


@jax.jit
def _rows_equal(cz, c):
    return jnp.all(cz == c[: cz.shape[0]])


def require_satisfied(flag) -> None:
    """Read `CompiledR1CS.satisfied`'s verdict from the device (the host
    waits here for the QAP's products, and for nothing queued behind
    them) and refuse a witness that failed it, before it can become a
    proof that does not verify. The one place the verdict is read."""
    if bool(flag):
        _CHECK_OK.inc()
        return
    _CHECK_REJECTED.inc()
    raise ValueError(UNSATISFIED)


@dataclass
class QAP:
    """Evaluated QAP vectors on device (groth16/src/qap.rs:17-29)."""

    num_inputs: int
    num_constraints: int
    a: jnp.ndarray  # (m, 16)
    b: jnp.ndarray  # (m, 16)
    c: jnp.ndarray  # (m, 16)
    domain: JaxDomain

    def pss(self, pp: PackedSharingParams) -> list["PackedQAPShare"]:
        """Per-party packed shares in the bitrev+strided d_fft layout
        (qap.rs:143-187)."""
        sa = pack_strided(pp, self.a)
        sb = pack_strided(pp, self.b)
        sc = pack_strided(pp, self.c)
        return [
            PackedQAPShare(
                num_inputs=self.num_inputs,
                num_constraints=self.num_constraints,
                a=sa[i],
                b=sb[i],
                c=sc[i],
                domain=self.domain,
            )
            for i in range(pp.n)
        ]


@dataclass
class PackedQAPShare:
    num_inputs: int
    num_constraints: int
    a: jnp.ndarray  # (m/l, 16)
    b: jnp.ndarray
    c: jnp.ndarray
    domain: JaxDomain


class CompiledR1CS:
    """R1CS lowered to device tensors once, reusable across witnesses."""

    def __init__(self, r1cs: R1CS):
        self.r1cs = r1cs
        self.num_inputs = r1cs.num_instance
        self.num_constraints = r1cs.num_constraints
        self.domain_size = _next_pow2(self.num_constraints + self.num_inputs)
        self.A = SparseMatrixDevice.build(r1cs.a)
        self.B = SparseMatrixDevice.build(r1cs.b)
        self.C = SparseMatrixDevice.build(r1cs.c)

    @cached_property
    def dom(self) -> JaxDomain:
        return domain(self.domain_size)

    def qap(self, z_mont: jnp.ndarray) -> QAP:
        """z_mont: (num_wires, 16) Montgomery full assignment."""
        F = fr()
        m = self.domain_size
        nc, ni = self.num_constraints, self.num_inputs
        pad = [(0, m - nc - ni), (0, 0)]
        a = jnp.concatenate([self.A.matvec(z_mont), z_mont[:ni]], axis=0)
        a = jnp.pad(a, pad)
        b = jnp.pad(self.B.matvec(z_mont), [(0, m - nc), (0, 0)])
        c = F.mul(a, b)  # b is zero past nc, so c too (qap.rs:75-81)
        return QAP(
            num_inputs=ni,
            num_constraints=nc,
            a=a,
            b=b,
            c=c,
            domain=self.dom,
        )

    def satisfied(self, z_mont: jnp.ndarray, qap: QAP) -> jnp.ndarray:
        """Whether <A_j, z> * <B_j, z> == <C_j, z> in every row j, as one
        device boolean: `R1CS.is_satisfied`'s verdict on the values of
        `z_mont`, from the products `qap = self.qap(z_mont)` already holds
        (`qap.c[:nc]` is `a * b` row for row) and one more matrix-vector
        product, `C z`. Nothing is read back here; `require_satisfied`
        reads it.

        The comparison is limb-wise equality of the Montgomery forms.
        Every `PrimeField` op returns the canonical representative (< r)
        of its class for canonical inputs, `F.encode` reduces mod r as
        `eval_lc` does, and x -> x * 2^256 mod r is a bijection of
        [0, r): equal limbs are equal field elements and no others are."""
        return _rows_equal(self.C.matvec(z_mont), qap.c)


def qap_from_r1cs(r1cs: R1CS, assignment: list[int]) -> QAP:
    """One-shot helper: host assignment ints -> device QAP."""
    return CompiledR1CS(r1cs).qap(fr().encode(assignment))
