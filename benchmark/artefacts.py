"""What a cell needs on disk before its first request, kept in the checkout
so that only a cell's first run there builds it.

    <checkout>/.bench_cache/<generator>-<key>/
        store/                  the service's own CircuitStore: the saved
                                circuit(s) with their proving key, written
                                by POST /save_circuit (which runs `setup`)
        manifest.json           circuit ids in the order they were saved,
                                the verifying key, the circuit's sizes
        pool/w<i>.wtns          the i-th witness of the pool
        pool/w<i>.json          its public inputs
        pool/p<i>.bin           its proof, once the oracle has passed it,
        pool/p<i>.json          and the job kind that produced it
        pool/x<i>.bin           the same proof corrupted (C + G)

`<key>` is a hash of the generator's parameters and the pool seed, so the
two sha256 configurations share one directory: they prove the same
circuit with the same witnesses, and with r = s = 0 their proofs are the
same bytes, which is how the MPC cell is compared with the single-node
one. The service's `setup` is deterministic (fixed dev seed, as
upstream's), and loading a saved circuit from the store is its normal
path. `--seed` is not part of the key: it draws the order and the
arrivals, not the pool (see the configuration's `assumed`).

Every cell builds what it finds missing by itself; none needs another to
have run.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os

from .reference import groth16 as oracle

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
CACHE_ROOT = os.path.join(CHECKOUT, ".bench_cache")


def _write(path: str, data: bytes) -> None:
    tmp = path + ".part"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)


def _read(path: str) -> bytes | None:
    try:
        with open(path, "rb") as f:
            return f.read()
    except FileNotFoundError:
        return None


class Artefacts:
    def __init__(self, circuit: dict, cache_root: str = CACHE_ROOT):
        self.circuit = circuit
        self.generator = importlib.import_module(
            f"benchmark.circuits.{circuit['generator']}"
        )
        key = hashlib.sha256(json.dumps(
            [circuit["generator"], circuit["params"], circuit["pool_seed"]],
            sort_keys=True,
        ).encode()).hexdigest()[:12]
        self.root = os.path.join(cache_root, f"{circuit['generator']}-{key}")
        self.store_dir = os.path.join(self.root, "store")
        self.pool_dir = os.path.join(self.root, "pool")
        os.makedirs(self.store_dir, exist_ok=True)
        os.makedirs(self.pool_dir, exist_ok=True)
        self._manifest_path = os.path.join(self.root, "manifest.json")
        raw = _read(self._manifest_path)
        self.manifest = json.loads(raw) if raw else {"circuit_ids": []}
        self.built: list[str] = []  # what this run had to make

    def _pool(self, name: str) -> str:
        return os.path.join(self.pool_dir, name)

    # -- witnesses ------------------------------------------------------------

    def ensure_witnesses(self, n: int) -> None:
        for i in range(n):
            if os.path.exists(self._pool(f"w{i}.wtns")) and os.path.exists(
                self._pool(f"w{i}.json")
            ):
                continue
            wtns, publics = self.generator.witness(
                self.circuit["params"], self.circuit["pool_seed"], i
            )
            _write(self._pool(f"w{i}.wtns"), wtns)
            _write(self._pool(f"w{i}.json"),
                   json.dumps([str(x) for x in publics]).encode())
            self.built.append(f"witness {i}")

    def publics(self, i: int) -> list[str]:
        return json.loads(_read(self._pool(f"w{i}.json")))

    # -- circuits -------------------------------------------------------------

    def ensure_circuits(self, n: int, save, load_key) -> list[str]:
        """`save(r1cs bytes) -> circuit id` is POST /save_circuit;
        `load_key(circuit id) -> (vk, domain_size, constraints, wires)`
        reads the stored key once, for the manifest."""
        ids = [
            cid for cid in self.manifest["circuit_ids"]
            if os.path.exists(
                os.path.join(self.store_dir, cid, "proving_key.npz")
            )
        ]
        r1cs = None
        while len(ids) < n:
            r1cs = r1cs or self.generator.r1cs(self.circuit["params"])
            ids.append(save(r1cs))
            self.built.append(f"circuit {len(ids) - 1} (setup)")
        if ids != self.manifest["circuit_ids"] or "vk" not in self.manifest:
            vk, m, constraints, wires = load_key(ids[0])
            self.manifest.update(
                circuit_ids=ids, vk=vk.to_json(), domain_size=m,
                constraints=constraints, wires=wires,
            )
            _write(self._manifest_path,
                   json.dumps(self.manifest, indent=1).encode())
        return ids[:n]

    @property
    def vk(self) -> oracle.VerifyingKey:
        return oracle.VerifyingKey.from_json(self.manifest["vk"])

    # -- proofs ---------------------------------------------------------------

    def proof(self, i: int) -> bytes | None:
        return _read(self._pool(f"p{i}.bin"))

    def proof_kind(self, i: int) -> str | None:
        raw = _read(self._pool(f"p{i}.json"))
        return json.loads(raw)["kind"] if raw else None

    def keep_proof(self, i: int, proof: bytes, kind: str) -> None:
        """Only ever called with a proof the oracle has passed."""
        _write(self._pool(f"p{i}.bin"), proof)
        _write(self._pool(f"x{i}.bin"), oracle.corrupt(proof))
        _write(self._pool(f"p{i}.json"), json.dumps({"kind": kind}).encode())
        self.built.append(f"proof {i}")

    def pool_entry(self, i: int) -> dict:
        """Paths and public inputs of one pool entry, for the load
        generator's plan."""
        return {
            "wtns": self._pool(f"w{i}.wtns"),
            "publics": self.publics(i),
            "proof": self._pool(f"p{i}.bin"),
            "bad_proof": self._pool(f"x{i}.bin"),
        }
