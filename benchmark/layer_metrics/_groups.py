"""What the readers of PR 23's kernel groups share: the per-job seconds of
one group of `kernel_groups/`, in ms. A group that matched no launch (the
program before the PR that named its programs so) sums to 0, and the
metric is left out like one with no whole job in the slice."""

from ._common import per_job


def group_ms(run, group):
    pj = per_job(run)
    seconds = pj["group_s"].get(group) if pj else None
    return 1e3 * seconds if seconds else None
