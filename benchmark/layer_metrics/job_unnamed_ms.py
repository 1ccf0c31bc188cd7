"""What of a job lies in no phase: 1e3 x (finishedAt - startedAt) less the
sum of the DTO's top-level phases (keys without a dot), median. Since PR 23
those phases partition the `job` span, so this is the hand-over from the
event loop to the worker thread after `startedAt` and the bookkeeping
before `finishedAt`. Read only where the program has the new phases."""

from ._common import median, window_dtos

LAYER, UNIT, MOVES = "queue and workers", "ms", "proof_p50_s"


def read(run):
    return median(
        1e3 * (d["finishedAt"] - d["startedAt"])
        - sum(ms for key, ms in d["phases"].items() if "." not in key)
        for d in window_dtos(run)
        if "encode" in d["phases"] and "serialize" in d["phases"]
    )
