#!/usr/bin/env python3
"""The load generator: a child process of `run.py` that drives the served
path over real HTTP with the routes `api/cli.py` uses.

    python benchmark/loadgen.py <plan.json> <records.json>

It imports neither jax nor the program, so it does not share the server's
interpreter lock and never touches the chip; everything it knows comes
from the plan file, and everything it learnt goes back in the records
file. What it does, in order:

  warm-up   one request of each kind the traffic mix holds (set-up time)
  before    GET /metrics and /stats
  window    the traffic, for `seconds` seconds on its own monotonic clock:
            every request sent before the end is waited for and recorded,
            none is sent after. A proving request ends when
            GET /jobs/{id}/result returns 200 (polled every `poll_s`; the
            status DTO is not polled: building its span tree costs the
            prover's interpreter lock)
  trace     with `trace` set, POST /profile once the first request of the
            window has answered, for `factor` times that request's time,
            clamped to [min_s, max_s] and to what is left of the window
  after     GET /metrics and /stats, then GET /jobs/{id} once per job: its
            clock has stopped by then
  late      how late the generator itself ran: by how much its sleeps
            overslept, and in an open loop how long after it was due each
            request went out
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import requests  # noqa: E402

from benchmark.schedule import Plan, Request, Traffic  # noqa: E402

# a sleep that overslept by more than its own length is worth knowing of;
# keeping every overshoot of a long open loop is not
_MAX_SLEEP_SAMPLES = 20000


class Lateness:
    """Oversleep of the generator's own sleeps, in seconds."""

    def __init__(self):
        self._lock = threading.Lock()
        self.samples: list[float] = []

    def sleep(self, seconds: float) -> None:
        t = time.monotonic()
        time.sleep(seconds)
        over = time.monotonic() - t - seconds
        with self._lock:
            if len(self.samples) < _MAX_SLEEP_SAMPLES:
                self.samples.append(over)


class Driver:
    def __init__(self, plan: dict):
        self.plan = plan
        self.url = plan["url"]
        self.poll_s = float(plan["poll_s"])
        self.job_timeout_s = float(plan["job_timeout_s"])
        self.prove = plan["prove"]
        self.circuit_ids = plan["circuit_ids"]
        self.late = Lateness()
        self._payloads: dict = {}
        self._local = threading.local()

    # -- plumbing -------------------------------------------------------------

    @property
    def http(self) -> requests.Session:
        s = getattr(self._local, "session", None)
        if s is None:
            s = self._local.session = requests.Session()
            s.trust_env = False  # loopback: never through a proxy
        return s

    def get_json(self, path: str):
        r = self.http.get(self.url + path, timeout=60)
        r.raise_for_status()
        return r.json()

    def get_text(self, path: str) -> str:
        r = self.http.get(self.url + path, timeout=60)
        r.raise_for_status()
        return r.text

    def _file(self, witness: int, key: str) -> bytes:
        """A pool entry's file, read once (before the window: `preload`)."""
        k = (witness, key)
        if k not in self._payloads:
            with open(self.plan["pool"][witness][key], "rb") as f:
                self._payloads[k] = f.read()
        return self._payloads[k]

    def preload(self, reqs) -> None:
        for req in reqs:
            if req.kind == "prove":
                self._file(req.witness, "wtns")
            else:
                self._file(req.witness, "bad_proof" if req.corrupt else "proof")

    # -- one request ----------------------------------------------------------

    def send(self, req: Request, client: int, seq: int) -> dict:
        rec = {
            "client": client, "seq": seq, "kind": req.kind,
            "circuit": req.circuit, "witness": req.witness,
            "corrupt": req.corrupt, "due": req.due_s, "ok": False,
        }
        try:
            if req.kind == "prove":
                self._prove(req, rec)
            else:
                self._verify(req, rec)
        except (requests.RequestException, ValueError, KeyError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
            rec.setdefault("t_done", time.monotonic())
        return rec

    def _prove(self, req: Request, rec: dict) -> None:
        fields = {
            "circuit_id": self.circuit_ids[req.circuit].encode(),
            "witness_file": self._file(req.witness, "wtns"),
        }
        if self.prove["kind"] == "mpc_prove":
            fields.update(mpc=b"1", l=str(self.prove["l"]).encode())
        rec["t_send"] = time.monotonic()
        r = self.http.post(
            self.url + "/jobs/prove",
            files={k: (k, v) for k, v in fields.items()},
            timeout=self.job_timeout_s,
        )
        rec["t_accepted"] = time.monotonic()
        rec["http"] = r.status_code
        if r.status_code != 202:
            rec["error"] = f"POST /jobs/prove: HTTP {r.status_code}"
            rec["t_done"] = rec["t_accepted"]
            return
        job_id = rec["job_id"] = r.json()["jobId"]
        deadline = rec["t_send"] + self.job_timeout_s
        while True:
            self.late.sleep(self.poll_s)
            r = self.http.get(f"{self.url}/jobs/{job_id}/result", timeout=60)
            now = time.monotonic()
            if r.status_code == 200:
                rec["t_done"] = now
                rec["proof"] = bytes(r.json()["proof"]).hex()
                rec["ok"] = True
                return
            if r.status_code != 409 or now > deadline:
                rec["t_done"] = now
                rec["http"] = r.status_code
                rec["error"] = (
                    f"GET result: HTTP {r.status_code} {r.text[:200]}"
                    if r.status_code != 409 else "still running at the limit"
                )
                return

    def _verify(self, req: Request, rec: dict) -> None:
        proof = self._file(req.witness, "bad_proof" if req.corrupt else "proof")
        body = {
            "circuitId": self.circuit_ids[req.circuit],
            "proof": list(proof),
            "publicInputs": self.plan["pool"][req.witness]["publics"],
        }
        rec["t_send"] = time.monotonic()
        r = self.http.post(
            self.url + "/verify_proof", json=body, timeout=self.job_timeout_s
        )
        rec["t_done"] = time.monotonic()
        rec["http"] = r.status_code
        if r.status_code != 200:
            rec["error"] = f"POST /verify_proof: HTTP {r.status_code}"
            return
        doc = r.json()
        rec["verdict"] = bool(doc["isValid"])
        rec["server_ms"] = doc["timeTaken"]
        rec["ok"] = True

    # -- the window -----------------------------------------------------------

    def closed_loop(self, plan: Plan, seconds: float, on_first) -> tuple:
        t = plan.traffic
        self.preload(
            plan.closed(c, j)
            for c in range(t.clients) for j in range(t.witness_pool)
        )
        records: list[dict] = []
        lock = threading.Lock()
        gate = threading.Barrier(t.clients + 1)
        start = [0.0]

        def client(c: int) -> None:
            gate.wait()
            j = 0
            while time.monotonic() - start[0] < seconds:
                rec = self.send(plan.closed(c, j), c, j)
                with lock:
                    records.append(rec)
                    first = len(records) == 1
                if first:
                    on_first(rec, start[0])
                j += 1

        threads = [
            threading.Thread(target=client, args=(c,), name=f"client-{c}")
            for c in range(t.clients)
        ]
        for th in threads:
            th.start()
        start[0] = time.monotonic()
        gate.wait()
        for th in threads:
            th.join()
        return start[0], records

    def open_loop(self, plan: Plan, seconds: float, on_first) -> tuple:
        schedule = plan.open_schedule(seconds)
        self.preload(schedule)
        records: list[dict] = []
        lock = threading.Lock()
        start = time.monotonic()

        def one(i: int, req: Request) -> None:
            rec = self.send(req, 0, i)
            with lock:
                records.append(rec)
                first = len(records) == 1
            if first:
                on_first(rec, start)

        # as many requests in flight as the server's queue bound can hold
        with ThreadPoolExecutor(max_workers=96) as pool:
            futures = []
            for i, req in enumerate(schedule):
                wait = start + req.due_s - time.monotonic()
                if wait > 0:
                    self.late.sleep(wait)
                futures.append(pool.submit(one, i, req))
            for f in futures:
                f.result()
        for rec in records:
            # an open loop's clock starts when the request was due
            rec["t_due"] = start + rec["due"]
        return start, records


def _party_spans(spans: list) -> int:
    n = 0
    stack = list(spans)
    while stack:
        node = stack.pop()
        n += node["name"] == "prove.party"
        stack.extend(node.get("children", ()))
    return n


def slim_dto(doc: dict) -> dict:
    """What the benchmark reads of GET /jobs/{id}."""
    metrics = doc.get("metrics") or {}
    return {
        "state": doc["state"],
        "kind": doc["kind"],
        "createdAt": doc["createdAt"],
        "startedAt": doc["startedAt"],
        "finishedAt": doc["finishedAt"],
        "phases": doc.get("phases") or {},
        "partySpans": _party_spans(metrics.get("spans") or []),
    }


def run(plan: dict) -> dict:
    d = Driver(plan)
    traffic = Traffic.from_dict(plan["traffic"])
    p = Plan(traffic, plan["seed"])
    seconds = float(plan["seconds"])
    out: dict = {"anchor": {"epoch": time.time(), "mono": time.monotonic()}}

    d.get_json("/readyz")
    out["warmup"] = []
    for i, req in enumerate(p.warmup()):
        rec = d.send(req, -1, i)
        out["warmup"].append(rec)
        if not rec["ok"]:
            # nothing after a failed warm-up means anything
            out["fatal"] = f"warm-up {req.kind} failed: {rec.get('error')}"
            return out

    out["metrics_before"] = d.get_text("/metrics")
    out["stats_before"] = d.get_json("/stats")
    d.late = Lateness()  # the window's own: the warm-up's sleeps are set-up

    profile: dict = {}
    prof = plan.get("profile")

    def on_first(first: dict, window_start: float) -> None:
        """Called once, from the client thread whose request answered
        first: with `trace` set, the slice starts here, before that
        client's next request."""
        if not prof:
            return
        took = first["t_done"] - first.get("t_due", first["t_send"])
        left = seconds - (time.monotonic() - window_start) - 0.5
        want = min(max(prof["factor"] * took, prof["min_s"]), prof["max_s"])
        duration = max(1.0, min(want, left))
        r = d.http.post(
            d.url + "/profile", json={"durationS": duration}, timeout=60
        )
        profile.update(
            http=r.status_code, wanted_s=want, duration_s=duration,
            started_epoch=time.time(),
        )
        if r.status_code == 202:
            profile["id"] = r.json()["id"]

    loop = d.closed_loop if traffic.loop == "closed" else d.open_loop
    start, records = loop(p, seconds, on_first)
    records.sort(key=lambda r: r.get("t_send", r["t_done"]))
    out["window"] = {
        "start": start,
        "start_epoch": out["anchor"]["epoch"] + start - out["anchor"]["mono"],
        "seconds": seconds,
        "last_done": max((r["t_done"] for r in records), default=start),
    }
    out["requests"] = records

    if profile.get("id"):
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            caps = {c["id"]: c for c in d.get_json("/profile")["captures"]}
            cap = caps.get(profile["id"], {})
            if cap.get("state") != "running":
                profile["state"] = cap.get("state")
                profile["error"] = cap.get("error")
                break
            time.sleep(0.25)
    out["profile"] = profile or None

    out["metrics_after"] = d.get_text("/metrics")
    out["stats_after"] = d.get_json("/stats")
    out["dtos"] = {
        r["job_id"]: slim_dto(d.get_json(f"/jobs/{r['job_id']}"))
        for r in out["warmup"] + records if r.get("job_id")
    }
    over = sorted(d.late.samples)
    out["late"] = {
        "sleeps": len(over),
        "oversleep_ms_p50": 1e3 * over[len(over) // 2] if over else 0.0,
        "oversleep_ms_max": 1e3 * over[-1] if over else 0.0,
        "send_late_ms_max": 1e3 * max(
            (r["t_send"] - r["t_due"] for r in records
             if "t_due" in r and "t_send" in r),
            default=0.0,
        ),
    }
    return out


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    out = run(plan)
    tmp = out_path + ".part"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, out_path)
    return 1 if "fatal" in out else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
