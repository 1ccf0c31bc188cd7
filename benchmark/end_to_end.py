"""The end-to-end metrics: arithmetic on the load generator's records, on
the client's clock. Every function takes the run (see `run.py`) and
returns a number, or None where the cell sent no such request.

  proof_p50_s     median over every proving request sent inside the window
                  of: POST /jobs/prove sent (in an open loop: due) -> proof
                  bytes received
  proofs_per_s    valid proofs completed / (last completion - window start)
  verifies_per_s  verdicts that agree with the known truth, by the same rule
  mix_proofs_per_s  `proofs_per_s` under its own name and bound, for a cell
                  whose callers also verify: such a cell's rates swing more,
                  and one bound a metric would loosen the proving cells'.
                  No cell of BENCHMARK.json reports it or `verifies_per_s`
                  today (PERF.md section 6): they are here for the cell
                  that will, which may add entries and edit no file
  setup_s         process start -> first request of the window sent

The rates use the drain rule: clients stop submitting at `--seconds`,
requests in flight are waited for and counted, and the divisor runs to the
last completion. So no request is cut, and the rate does not step with
where the window's edge falls in a request.
"""

from __future__ import annotations

import statistics


def _of_kind(run: dict, kind: str) -> list[dict]:
    return [r for r in run["requests"] if r["kind"] == kind]


def latency_s(rec: dict) -> float:
    return rec["t_done"] - rec.get("t_due", rec["t_send"])


def proof_p50_s(run: dict):
    done = [latency_s(r) for r in _of_kind(run, "prove") if r.get("valid")]
    return statistics.median(done) if done else None


def _rate(run: dict, kind: str):
    recs = _of_kind(run, kind)
    if not recs:
        return None
    span = max(r["t_done"] for r in recs) - run["window"]["start"]
    return sum(bool(r.get("valid")) for r in recs) / span


def proofs_per_s(run: dict):
    return _rate(run, "prove")


def verifies_per_s(run: dict):
    return _rate(run, "verify")


def setup_s(run: dict):
    return run["window"]["start_epoch"] - run["t0_epoch"]


METRICS = {
    "proof_p50_s": proof_p50_s,
    "proofs_per_s": proofs_per_s,
    "mix_proofs_per_s": proofs_per_s,
    "verifies_per_s": verifies_per_s,
    "setup_s": setup_s,
}
