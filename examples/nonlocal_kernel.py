"""Distributed kernels over REAL sockets, one OS process per party — the
reference's per-kernel launcher matrix (scripts/dfft_test.zsh,
dmsm_bench.zsh, dpp_test.zsh run dist-primitives/examples/{dfft_test,
dmsm_bench,dpp_test}.rs the same way: build, spawn n ranks, wait).

Every rank deterministically builds the full input from --seed (the
trusted-dealer convention of nonlocal_sha256.py), keeps its own share
row, runs the selected kernel over a ProdNet star, and rank 0 checks the
revealed result against the pure-bigint refmath ground truth.

Run one process per rank (see scripts/dfft_test.sh et al.):
  python examples/nonlocal_kernel.py --kernel dfft|dmsm|dpp --id <rank> \
      --input <addressfile> --certs <certdir> --n 8 [--m 256] [--plain]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import random
import sys

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _ROOT)


async def _run_dfft(opt, pp, net):
    """d_fft with king_clear: king receives the clear evaluations and
    compares against the host NTT (dfft_test.rs semantics)."""
    import jax.numpy as jnp  # noqa: F401

    from distributed_groth16_tpu.ops import refmath as rm
    from distributed_groth16_tpu.ops.constants import R
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.ops.ntt import domain
    from distributed_groth16_tpu.parallel.dfft import d_fft
    from distributed_groth16_tpu.parallel.packing import pack_strided

    F = fr()
    rng = random.Random(opt.seed)
    x = [rng.randrange(R) for _ in range(opt.m)]
    share = pack_strided(pp, F.encode(x))[opt.id]
    clear = await d_fft(
        share, False, 1, False, domain(opt.m), pp, net, king_clear=True
    )
    if opt.id != 0:
        return 0
    got = [int(v) for v in F.decode(clear)]
    want = rm.Domain(opt.m).fft(x)
    ok = got == want
    print(f"rank 0: d_fft vs host NTT ground truth: {'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


async def _run_dmsm(opt, pp, net):
    """d_msm over generator multiples: every rank derives its CRS-style
    base shares via the scalar route, its witness shares by consecutive
    packing; the clear result must equal (sum b_i x_i) * G."""
    from distributed_groth16_tpu.models.groth16.proving_key import (
        _pack_query_scalars,
    )
    from distributed_groth16_tpu.ops import refmath as rm
    from distributed_groth16_tpu.ops.constants import G1_GENERATOR, R
    from distributed_groth16_tpu.ops.curve import g1
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.parallel.dmsm import d_msm
    import jax.numpy as jnp

    F = fr()
    C1 = g1()
    rng = random.Random(opt.seed)
    base_s = [rng.randrange(R) for _ in range(opt.m)]  # discrete logs
    wit = [rng.randrange(R) for _ in range(opt.m)]
    bases = _pack_query_scalars("g1", pp, F.encode(base_s))[opt.id]
    c = opt.m // pp.l
    chunks = F.encode(wit).reshape(c, pp.l, 16)
    scal_shares = jnp.swapaxes(pp.pack_from_public(chunks), 0, 1)[opt.id]
    out = await d_msm(C1, bases, scal_shares, pp, net)
    if opt.id != 0:
        return 0
    got = C1.decode(out[None])[0]
    s = sum(b * w for b, w in zip(base_s, wit)) % R
    want = rm.G1.scalar_mul(G1_GENERATOR, s)
    ok = got == want
    print(f"rank 0: d_msm vs host ground truth: {'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


async def _run_dpp(opt, pp, net):
    """d_pp (partial products): reveal the output shares on the king via
    a second round and compare against host prefix products
    (dpp_test.rs semantics)."""
    import jax.numpy as jnp

    from distributed_groth16_tpu.ops.constants import R
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.ops.refmath import finv
    from distributed_groth16_tpu.parallel.dpp import d_pp

    F = fr()
    rng = random.Random(opt.seed)
    num = [rng.randrange(1, R) for _ in range(opt.m)]
    den = [rng.randrange(1, R) for _ in range(opt.m)]
    c = opt.m // pp.l

    def consec(vals):
        chunks = F.encode(vals).reshape(c, pp.l, 16)
        return jnp.swapaxes(pp.pack_from_public(chunks), 0, 1)

    out_share = await d_pp(
        consec(num)[opt.id], consec(den)[opt.id], pp, net
    )

    def king_reveal(shares):
        stacked = jnp.swapaxes(jnp.stack(shares, axis=0), 0, 1)  # (c, n, 16)
        clear = pp.unpack(stacked).reshape(-1, 16)  # chunk-major
        return [clear] * pp.n

    clear = await net.king_compute(out_share, king_reveal, 1)
    if opt.id != 0:
        return 0
    got = [int(v) for v in F.decode(clear)]
    want, acc = [], 1
    for nu, de in zip(num, den):
        acc = acc * nu % R * finv(de, R) % R
        want.append(acc)
    ok = got == want
    print(f"rank 0: d_pp vs host prefix products: {'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


KERNELS = {"dfft": _run_dfft, "dmsm": _run_dmsm, "dpp": _run_dpp}


async def run(opt) -> int:
    from distributed_groth16_tpu.parallel.prodnet import ProdNet
    from distributed_groth16_tpu.parallel.pss import PackedSharingParams
    from distributed_groth16_tpu.utils.certs import (
        king_ssl_context,
        peer_ssl_context,
    )
    from distributed_groth16_tpu.utils.config import read_address_file

    addrs = read_address_file(opt.input)
    n = opt.n or len(addrs)
    assert n % 4 == 0, "party count must be 4l"
    pp = PackedSharingParams(n // 4)
    assert opt.m % pp.l == 0, "--m must be a multiple of l"

    king_addr = addrs[0]
    cert = lambda i: os.path.join(opt.certs, f"{i}.cert.pem")  # noqa: E731
    key = lambda i: os.path.join(opt.certs, f"{i}.key.pem")  # noqa: E731
    if opt.id == 0:
        ctx = None if opt.plain else king_ssl_context(
            cert(0), key(0), [cert(i) for i in range(1, n)]
        )
        net = await ProdNet.new_king(king_addr, n, ctx)
    else:
        ctx = None if opt.plain else peer_ssl_context(
            cert(opt.id), key(opt.id), cert(0)
        )
        net = await ProdNet.new_peer(opt.id, king_addr, n, ctx)
    try:
        return await KERNELS[opt.kernel](opt, pp, net)
    finally:
        await net.close()


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--kernel", choices=sorted(KERNELS), required=True)
    p.add_argument("--id", type=int, required=True)
    p.add_argument("--input", required=True, help="address file")
    p.add_argument("--certs", default="certs")
    p.add_argument("--n", type=int, default=None)
    p.add_argument("--m", type=int, default=256)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--plain", action="store_true")
    return asyncio.run(run(p.parse_args()))


if __name__ == "__main__":
    sys.exit(main())
