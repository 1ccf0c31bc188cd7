"""The MSMs' share of their MEMORY roofline: the least time the chip's HBM
could take to move what one proof's MSMs must read (`shapes.py`: every
point and scalar once) over the time their programs took on the device.
There is no published 32-bit-integer peak for the vector unit, so the
compute bound is not measured and this is not yet a true roofline share."""

from .. import peaks, shapes
from ._common import per_job

LAYER, UNIT, MOVES = "kernels", "%", "proof_p50_s"


def read(run):
    pj = per_job(run)
    if not pj or not pj["group_s"].get("msm"):
        return None
    least_s = shapes.msm_min_bytes(
        shapes.proof_msms(run["config"], run["sizes"])
    ) / peaks.load(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / pj["group_s"]["msm"]
