"""The comparison that decides `correct`, outside the window.

A returned proof is right if it equals, byte for byte, the proof of its
witness that the oracle (`reference/`, the benchmark's own copy of the
pure-Python pairing check) has passed before and the artefact cache
keeps, or else passes the oracle itself. With r = s = 0 proofs are a
function of key and witness, so the first is the common case; at least
one proof of every run goes through the oracle whatever the cache says.
A verdict of POST /verify_proof is right if it is the truth the request
was built with. Beside the answers, the program's exact counters have to
show that the work took the device path the cell is there to measure.
"""

from __future__ import annotations

import re

from .reference import groth16 as oracle

_SERIES = re.compile(r'^(\w+)\{([^}]*)\}\s+([-+0-9.eE]+|NaN)$')
_LABEL = re.compile(r'(\w+)="([^"]*)"')

def counter(text: str, family: str) -> dict[tuple, float]:
    """{label values, in the series' own order: value} of one family of a
    Prometheus text exposition."""
    out = {}
    for line in text.splitlines():
        m = _SERIES.match(line)
        if m and m.group(1) == family:
            labels = tuple(v for _, v in _LABEL.findall(m.group(2)))
            out[labels] = float(m.group(3))
    return out


def routes_moved(before: str, after: str) -> dict[tuple, float]:
    a = counter(after, "kernel_route_total")
    b = counter(before, "kernel_route_total")
    return {k: v - b.get(k, 0.0) for k, v in a.items() if v != b.get(k, 0.0)}


def judge_requests(requests: list[dict], artefacts, prove_kind: str) -> dict:
    """Mark every record `valid` and say how each proof was judged."""
    vk = artefacts.vk
    tally = {"matched_cache": 0, "oracle_passed": 0, "oracle_failed": 0,
             "matched_single_node": 0, "differs_from_cache": 0}

    def by_oracle(rec: dict, proof: bytes) -> bool:
        ok = oracle.verify(vk, proof, artefacts.publics(rec["witness"]))
        tally["oracle_passed" if ok else "oracle_failed"] += 1
        return ok

    for rec in requests:
        rec["valid"] = False
        if not rec["ok"]:
            continue
        if rec["kind"] == "verify":
            rec["valid"] = rec["verdict"] == (not rec["corrupt"])
            continue
        proof = bytes.fromhex(rec["proof"])
        cached = artefacts.proof(rec["witness"])
        if cached is not None and proof == cached:
            rec["valid"] = True
            tally["matched_cache"] += 1
            if artefacts.proof_kind(rec["witness"]) == "prove":
                tally["matched_single_node"] += 1
        elif by_oracle(rec, proof):
            rec["valid"] = True
            if cached is None:
                artefacts.keep_proof(rec["witness"], proof, prove_kind)
            else:
                tally["differs_from_cache"] += 1
    proofs = [r for r in requests if r["kind"] == "prove" and r["ok"]]
    if proofs and not (tally["oracle_passed"] or tally["oracle_failed"]):
        rec = proofs[0]
        rec["valid"] = by_oracle(rec, bytes.fromhex(rec["proof"]))
    return tally


def device_path_faults(run: dict) -> list[str]:
    """Why the run is not `correct` although its answers may be: empty when
    everything took the path the cell measures."""
    faults = []
    rec = run["records"]
    kinds = {r["kind"] for r in run["requests"]}
    if run["compiles_in_window"]:
        faults.append(
            f"{run['compiles_in_window']} compilation(s) inside the window"
        )
    if "prove" in kinds and run["on_chip"]:
        # the configuration says which routes its proofs must take (the MPC
        # round's transforms are rows under 2048 and never ride ntt/limb)
        moved = routes_moved(rec["metrics_before"], rec["metrics_after"])
        for route in run["config"]["device_routes"]:
            if moved.get(tuple(route.split("/")), 0) <= 0:
                faults.append(f"route {route} did not advance")
        for route, n in moved.items():
            if "pippenger" in route[-1] and n > 0:
                faults.append(f"generic route {'/'.join(route)} advanced")
    parties = run["config"].get("parties")
    if "prove" in kinds and parties:
        crs0, crs1 = (rec[k]["crsCache"] for k in ("stats_before", "stats_after"))
        if crs1["misses"] != crs0["misses"]:
            faults.append("the packed CRS missed its cache inside the window")
        short = [
            j for j, d in run["dtos"].items()
            if d["kind"] == "mpc_prove" and d["partySpans"] != parties["n"]
        ]
        if short:
            faults.append(
                f"{len(short)} job(s) did not show {parties['n']} parties"
            )
    lost = [j for j, d in run["dtos"].items() if d["state"] != "DONE"]
    if lost:
        faults.append(f"{len(lost)} accepted job(s) did not end DONE")
    return faults
