"""Status DTO startedAt - createdAt, median: how long a job waited for a
worker."""

from ._common import median, window_dtos

LAYER, UNIT, MOVES = "queue and workers", "ms", "proofs_per_s"


def read(run):
    return median(
        1e3 * (d["startedAt"] - d["createdAt"]) for d in window_dtos(run)
    )
