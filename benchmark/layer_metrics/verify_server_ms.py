"""`timeTaken` of the POST /verify_proof response, median: the handler's own
wall clock round its kind=verify job (the response carries no job id)."""

from ._common import median

LAYER, UNIT, MOVES = "verifier", "ms", "verifies_per_s"


def read(run):
    return median(
        r["server_ms"] for r in run["requests"]
        if r["kind"] == "verify" and r.get("valid")
    )
