"""POST /jobs/prove sent -> 202 received, on the client's clock: the front
door's share of a request (multipart upload of the witness, admission)."""

from ._common import median

LAYER, UNIT, MOVES = "front door", "ms", "proof_p50_s"


def read(run):
    return median(
        1e3 * (r["t_accepted"] - r["t_send"])
        for r in run["requests"] if r.get("valid") and "t_accepted" in r
    )
