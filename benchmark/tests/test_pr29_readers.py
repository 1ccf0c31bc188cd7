"""The per-layer reader PR 29 added, on hand-made runs: the movement of the
program's counter of batch-affine up-sweep levels over the window's
completed proofs, and None (the metric is left out of the line) where the
program has no such counter, as the parent of that PR has not, or the
window completed no proof."""

import pytest

from benchmark.layer_metrics import msm_affine_levels_per_req

PARENT_TEXT = '''# TYPE msm_wide_scalars_total counter
msm_wide_scalars_total 16
# TYPE kernel_route_total counter
kernel_route_total{kernel="msm",path="tree"} 4
'''


def _text(levels):
    return PARENT_TEXT + f'''# TYPE msm_affine_levels_total counter
msm_affine_levels_total {levels}
'''


def _run(before, after, proofs=3, kind="prove"):
    ids = [f"j{i}" for i in range(proofs)]
    return {
        "records": {"metrics_before": before, "metrics_after": after},
        # one request that failed: it is not among the completed proofs
        "requests": [{"job_id": j, "valid": True} for j in ids]
        + [{"job_id": "bad", "valid": False}],
        "dtos": {j: {"kind": kind} for j in ids + ["bad"]},
    }


@pytest.mark.parametrize("run,want", [
    # the warm-up's proofs moved it by 8; three proofs of the window by 12
    (_run(_text(8), _text(20)), 4.0),
    # the MPC round's d_msms: many full-width launches a proof
    (_run(_text(0), _text(192), proofs=2, kind="mpc_prove"), 96.0),
    # the counter is there and no launch had a level over the rule
    (_run(_text(0), _text(0)), 0.0),
    # the parent's /metrics text: no such counter
    (_run(PARENT_TEXT, PARENT_TEXT), None),
    # no proof completed in the window
    (_run(_text(8), _text(8), proofs=0), None),
    # jobs of a kind that runs no MSM
    (_run(_text(8), _text(8), kind="verify"), None),
    # no records at all
    (_run(None, None), None),
])
def test_levels_per_req_is_the_counters_movement_over_the_proofs(run, want):
    got = msm_affine_levels_per_req.read(run)
    assert got == (pytest.approx(want) if want is not None else None)


def test_a_run_without_records_reads_nothing():
    assert msm_affine_levels_per_req.read({}) is None
