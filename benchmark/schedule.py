"""The one general traffic generator: what request goes out when, as a pure
function of a traffic file's parameters and `--seed`.

A traffic mix is a data file `benchmark/traffic/<mix>.json`:

  loop          "closed" (each client sends its next request when the last
                one has answered: callers that wait for their proof) or
                "open" (requests go out on a schedule whatever the server
                does: independent users)
  clients       closed loop: how many callers
  rate, burst,  open loop: mean requests per second; requests arrive in
  arrivals      groups of `burst`; "poisson" (exponential gaps between
                groups) or "uniform" (a group every burst/rate seconds)
  mix           [{"kind": "prove" | "verify", "weight": w}, ...]; "prove"
                is the configuration's own proving job (its `prove` block
                says `prove` or `mpc_prove` and `l`), "verify" is
                POST /verify_proof. An open loop draws each request's kind
                by weight; in a closed loop every caller sends one kind,
                and callers are dealt out to the kinds by whole-number
                weights (1 : 1 over four callers is two of each)
  witness_pool  how many of the configuration's pool of witnesses are in
                play; requests take them in turn in an order drawn from
                the seed
  corrupt_share share of the pool whose proof a "verify" request sends
                corrupted (the truth is then `isValid: false`)
  circuits,     how many saved circuits the requests spread over, and the
  zipf_s        exponent of their popularity (0 = uniform)

Nothing here imports jax, the program or the clock: the load generator
(`loadgen.py`, a child process) and the tests call the same functions.
"""

from __future__ import annotations

import random
from dataclasses import dataclass

KINDS = ("prove", "verify")


@dataclass(frozen=True)
class Traffic:
    loop: str
    mix: tuple  # ((kind, weight), ...)
    witness_pool: int
    clients: int = 1
    rate: float = 0.0
    burst: int = 1
    arrivals: str = "poisson"
    corrupt_share: float = 0.0
    circuits: int = 1
    zipf_s: float = 0.0

    @staticmethod
    def from_dict(doc: dict) -> "Traffic":
        mix = tuple((m["kind"], float(m["weight"])) for m in doc["mix"])
        t = Traffic(
            loop=doc["loop"],
            mix=mix,
            witness_pool=int(doc["witness_pool"]),
            clients=int(doc.get("clients", 1)),
            rate=float(doc.get("rate", 0.0)),
            burst=int(doc.get("burst", 1)),
            arrivals=doc.get("arrivals", "poisson"),
            corrupt_share=float(doc.get("corrupt_share", 0.0)),
            circuits=int(doc.get("circuits", 1)),
            zipf_s=float(doc.get("zipf_s", 0.0)),
        )
        t.validate()
        return t

    def validate(self) -> None:
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, not {self.loop!r}")
        if not self.mix or any(
            k not in KINDS or w <= 0 for k, w in self.mix
        ):
            raise ValueError(f"mix needs kinds of {KINDS} with weights > 0")
        if self.witness_pool < 1 or self.circuits < 1:
            raise ValueError("witness_pool and circuits must be at least 1")
        if self.loop == "closed" and self.clients < 1:
            raise ValueError("a closed loop needs clients >= 1")
        if self.loop == "open" and (self.rate <= 0 or self.burst < 1):
            raise ValueError("an open loop needs rate > 0 and burst >= 1")
        if self.arrivals not in ("poisson", "uniform"):
            raise ValueError("arrivals must be poisson or uniform")
        if not 0.0 <= self.corrupt_share <= 1.0:
            raise ValueError("corrupt_share must lie in [0, 1]")

    @property
    def kinds(self) -> tuple:
        """The distinct kinds of the mix, in the file's order: what the
        warm-up sends one request of."""
        return tuple(dict.fromkeys(k for k, _ in self.mix))


@dataclass(frozen=True)
class Request:
    """One planned request. `due_s` is seconds after the window's start in
    an open loop and None in a closed one."""

    kind: str
    circuit: int
    witness: int
    corrupt: bool
    due_s: float | None = None


class Plan:
    """What a seed makes of a traffic mix. `closed(client, j)` is the j-th
    request of one closed-loop client; `open_schedule(seconds)` is every
    arrival of an open loop inside the window."""

    def __init__(self, traffic: Traffic, seed: int):
        self.traffic = traffic
        self.seed = int(seed)
        rng = random.Random(f"dg16-bench/{self.seed}/pool")
        self.order = list(range(traffic.witness_pool))
        rng.shuffle(self.order)
        n_bad = round(traffic.corrupt_share * traffic.witness_pool)
        self.corrupted = frozenset(
            rng.sample(range(traffic.witness_pool), n_bad)
        )
        weights = [
            1.0 / (i + 1) ** traffic.zipf_s for i in range(traffic.circuits)
        ]
        self._circuit_weights = weights
        self._kind_names = [k for k, _ in traffic.mix]
        self._kind_weights = [w for _, w in traffic.mix]

    def _draw(self, turn: int, stream: str, due_s=None, kind=None) -> Request:
        """`turn` picks the witness (the pool in its seeded order, in
        turn); kind and circuit are drawn from a stream of their own so
        that one request's draw never shifts another's."""
        t = self.traffic
        rng = random.Random(f"dg16-bench/{self.seed}/{stream}")
        drawn = rng.choices(self._kind_names, self._kind_weights)[0]
        kind = kind or drawn
        circuit = rng.choices(range(t.circuits), self._circuit_weights)[0]
        witness = self.order[turn % t.witness_pool]
        return Request(
            kind=kind,
            circuit=circuit,
            witness=witness,
            corrupt=kind == "verify" and witness in self.corrupted,
            due_s=due_s,
        )

    def closed(self, client: int, j: int) -> Request:
        # clients start evenly spread over the pool and each walks all of
        # it; each sends one kind, dealt out by the mix's whole weights
        t = self.traffic
        start = (client * t.witness_pool) // t.clients
        dealt = [k for k, w in t.mix for _ in range(max(1, round(w)))]
        return self._draw(start + j, f"c{client}/{j}",
                          kind=dealt[client % len(dealt)])

    def open_schedule(self, seconds: float) -> list[Request]:
        t = self.traffic
        rng = random.Random(f"dg16-bench/{self.seed}/arrivals")
        gap = t.burst / t.rate
        out: list[Request] = []
        # the first group is due a gap after the start, like every other
        due = rng.expovariate(1.0 / gap) if t.arrivals == "poisson" else gap
        while due < seconds:
            for _ in range(t.burst):
                out.append(self._draw(len(out), f"o{len(out)}", due_s=due))
            due += rng.expovariate(1.0 / gap) if t.arrivals == "poisson" \
                else gap
        return out

    def warmup(self) -> list[Request]:
        """One request of each kind the mix holds, on the first circuit,
        with the last witness of the seeded order, never corrupted."""
        w = self.order[-1]
        return [
            Request(kind=k, circuit=0, witness=w, corrupt=False)
            for k in self.traffic.kinds
        ]
