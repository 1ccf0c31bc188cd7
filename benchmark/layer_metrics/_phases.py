"""What the readers of the job's own time account (PR 23) share: one key of
the status DTO's `phases`, in ms, median over the window's jobs. Top-level
phases (keys without a dot) partition the `job` span; a dotted key is a
child of the phase it names. A program without the key (the parent of the
PR that brought it) gives None, and the metric is left out."""

from ._common import median, window_dtos


def phase_ms(run, key):
    return median(d["phases"].get(key) for d in window_dtos(run))
