"""Full distributed Groth16 prover over REAL sockets, one OS process per
party — the reference's headline deployment mode
(groth16/examples/nonlocal_sha256.rs:126, launched by scripts/sha256.zsh).

Every rank builds the circuit + witness deterministically, loads (or rank 0
computes) the dev-seed proving key, packs the identical PSS dealing, keeps
its own row, then runs the full proving round over a ProdNet star (mTLS via
utils/certs.py unless --plain). Rank 0 reassembles and pairing-verifies.

Run one process per rank (see scripts/nonlocal_sha256.sh):
  python examples/nonlocal_sha256.py --id <rank> --input <addressfile> \
      --certs <certdir> --n 8 [--circuit sha256|chain] [--plain]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _ROOT)

# the 8 rank processes share the package's persistent compile cache
# (utils/cache.py) instead of each cold-compiling the full prover

def _build_circuit(opt):
    if opt.circuit == "sha256":
        from distributed_groth16_tpu.frontend.sha256 import sha256_circuit

        cs, pubs = sha256_circuit(opt.msg.encode())
        r1cs, z = cs.finish()
        return r1cs, z, pubs
    from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit

    nc = (1 << opt.log2_constraints) - 2
    cs = mult_chain_circuit(opt.x0, nc)
    r1cs, z = cs.finish()
    return r1cs, z, z[1:r1cs.num_instance]


def _load_or_make_pk(r1cs, opt):
    """Rank 0 computes the (deterministic, dev-seed) key and publishes it
    via an atomic rename; other ranks wait for the artifact — the same
    trusted-dealer role the reference's examples play in-process."""
    import hashlib

    from distributed_groth16_tpu.models.groth16 import setup
    from distributed_groth16_tpu.models.groth16.keys import ProvingKey

    key = hashlib.sha256(
        f"{opt.circuit}-{r1cs.num_constraints}-{r1cs.num_wires}".encode()
    ).hexdigest()[:16]
    cache = os.path.join(os.path.dirname(__file__), "..", ".bench_cache")
    os.makedirs(cache, exist_ok=True)
    path = os.path.join(cache, f"pk_{key}.npz")
    if os.path.exists(path):
        return ProvingKey.load(path)
    if opt.id == 0:
        pk = setup(r1cs)
        tmp = f"{path[:-4]}.{os.getpid()}.tmp.npz"
        pk.save(tmp)  # savez keeps the name verbatim (.npz suffix present)
        os.replace(tmp, path)
        return pk
    deadline = time.time() + opt.setup_timeout
    while not os.path.exists(path):
        if time.time() > deadline:
            raise TimeoutError("rank 0 never published the proving key")
        time.sleep(0.5)
    time.sleep(0.5)  # let the rename settle on networked filesystems
    return ProvingKey.load(path)


async def run(opt) -> int:
    from distributed_groth16_tpu.models.groth16 import (
        CompiledR1CS,
        distributed_prove_party,
        pack_from_witness,
        pack_proving_key,
        reassemble_proof,
        verify,
    )
    from distributed_groth16_tpu.ops.field import fr
    from distributed_groth16_tpu.parallel.prodnet import ProdNet
    from distributed_groth16_tpu.parallel.pss import PackedSharingParams
    from distributed_groth16_tpu.utils.certs import (
        king_ssl_context,
        peer_ssl_context,
    )
    from distributed_groth16_tpu.utils.config import read_address_file
    from distributed_groth16_tpu.utils.timers import PhaseTimings, phase

    timings = PhaseTimings()
    addrs = read_address_file(opt.input)
    n = opt.n or len(addrs)
    assert n % 4 == 0, "party count must be 4l"
    pp = PackedSharingParams(n // 4)

    with phase("build circuit", timings):
        r1cs, z, pubs = _build_circuit(opt)
    with phase("setup/load pk", timings):
        pk = _load_or_make_pk(r1cs, opt)

    with phase("packing", timings):
        F = fr()
        z_mont = F.encode(z)
        comp = CompiledR1CS(r1cs)
        qap_share = comp.qap(z_mont).pss(pp)[opt.id]
        crs_share = pack_proving_key(pk, pp, strip=True)[opt.id]
        a_share = pack_from_witness(pp, z_mont[1:])[opt.id]
        ax_share = pack_from_witness(pp, z_mont[r1cs.num_instance:])[opt.id]

    with phase("connect", timings):
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
        with phase("MPC prove (over sockets)", timings):
            share = await distributed_prove_party(
                pp, crs_share, qap_share, a_share, ax_share, net
            )
        if opt.id == 0:
            proof = reassemble_proof(share, pk)
            ok = verify(pk.vk, proof, pubs)
            print(f"rank 0: pairing verification {'OK' if ok else 'FAILED'}")
            if not ok:
                return 1
    finally:
        await net.close()

    print(f"rank {opt.id} phase timings (ms):")
    for k, v in timings.as_millis().items():
        print(f"  {k:30s} {v:10.1f}")
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--id", type=int, required=True)
    p.add_argument("--input", required=True, help="address file")
    p.add_argument("--certs", default="certs")
    p.add_argument("--n", type=int, default=None)
    p.add_argument("--circuit", choices=("sha256", "chain"), default="sha256")
    p.add_argument("--msg", default="hello world")
    p.add_argument("--log2-constraints", type=int, default=10)
    p.add_argument("--x0", type=int, default=999992)
    p.add_argument("--plain", action="store_true")
    p.add_argument("--setup-timeout", type=float, default=1800.0)
    return asyncio.run(run(p.parse_args()))


if __name__ == "__main__":
    sys.exit(main())
