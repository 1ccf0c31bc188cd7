"""What several readers share: picking the window's jobs out of the run."""

from __future__ import annotations

import statistics


def window_dtos(run: dict, kind: str | None = None) -> list[dict]:
    """Status DTOs of the jobs the window's valid requests ran as."""
    ids = [r["job_id"] for r in run["requests"]
           if r.get("job_id") and r.get("valid")]
    out = [run["dtos"][j] for j in ids if j in run["dtos"]]
    return [d for d in out if kind is None or d["kind"] == kind]


def median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def per_job(run: dict):
    """The reduced trace's per-job block, or None without a whole job."""
    trace = run.get("trace")
    return trace["per_job"] if trace else None
