"""Status DTO finishedAt - startedAt, median: a job's time on its worker,
host phases, device work and readback together."""

from ._common import median, window_dtos

LAYER, UNIT, MOVES = "queue and workers", "s", "proof_p50_s"


def read(run):
    return median(d["finishedAt"] - d["startedAt"] for d in window_dtos(run))
