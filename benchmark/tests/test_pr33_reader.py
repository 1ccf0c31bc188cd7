"""What PR 33 added to the benchmark: the reader of
`witness_device_checks_per_req` on hand-made /metrics texts, and its
`BENCHMARK.json` entry against the issue's fields. Nothing the benchmark
had is edited; `prove.check` is matched by `host_spans/pr22.txt`'s
`prove.*`, `packing.check` by `pr32.txt`'s `packing.*`."""

import fnmatch
import json
import os

import pytest

from benchmark.layer_metrics import witness_device_checks_per_req

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "witness_device_checks_per_req"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

# the parent's /metrics text: no such family
PARENT_TEXT = '''# TYPE circuit_cache_hits_total counter
circuit_cache_hits_total 12
# TYPE msm_limb0_declined_total counter
msm_limb0_declined_total{reason="over_capacity"} 6
'''


def _text(ok, rejected=0):
    return PARENT_TEXT + f'''# TYPE witness_device_checks_total counter
witness_device_checks_total{{verdict="ok"}} {ok}
witness_device_checks_total{{verdict="rejected"}} {rejected}
'''


def _run(before, after, proofs=4, kind="prove"):
    ids = [f"j{i}" for i in range(proofs)]
    return {
        "records": {"metrics_before": before, "metrics_after": after},
        "requests": [{"job_id": j, "valid": True} for j in ids]
        + [{"job_id": "bad", "valid": False}],
        "dtos": {j: {"kind": kind} for j in ids + ["bad"]},
    }


@pytest.mark.parametrize("run,want", [
    # one verdict read a proof, the warm-up's two before the window
    (_run(_text(2), _text(6)), 1.0),
    (_run(_text(2), _text(6), kind="mpc_prove"), 1.0),
    # both verdicts are checks: summed
    (_run(_text(2, 1), _text(5, 2)), 1.0),
    # bound at import and never raised: the loop still ran on the host
    (_run(_text(0), _text(0)), 0.0),
    # no first text: the movement is the whole of the second
    (_run(None, _text(4)), 1.0),
    # the parent's /metrics text: no such counter
    (_run(PARENT_TEXT, PARENT_TEXT), None),
    # no proof completed in the window; no records at all
    (_run(_text(2), _text(2), proofs=0), None),
    (_run(None, None), None),
    ({}, None),
])
def test_checks_per_req_is_both_verdicts_movement_over_the_proofs(run, want):
    got = witness_device_checks_per_req.read(run)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_entry_is_the_issues():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "host preparation",
        "moves": "proof_p50_s",
        "workloads": ["sha256_single_c1", "million_chain_c1",
                      "sha256_mpc_c1"],
    }
    mod = witness_device_checks_per_req
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    # each of its cells reports the end-to-end metric it moves
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    # and it reads the counter that the program binds under that name
    from distributed_groth16_tpu.models.groth16 import qap  # noqa: F401
    from distributed_groth16_tpu.telemetry import metrics
    text = metrics.registry().render_prometheus()
    for verdict in ("ok", "rejected"):
        assert f'{mod.FAMILY}{{verdict="{verdict}"}} ' in text


@pytest.mark.parametrize("span", ["prove.check", "packing.check"])
def test_the_spans_the_read_brought_are_names_the_trace_reducer_knows(span):
    patterns = []
    for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", "host_spans"))):
        with open(os.path.join(ROOT, "benchmark", "host_spans", name)) as f:
            patterns += [ln.strip() for ln in f
                         if ln.strip() and not ln.startswith("#")]
    assert any(fnmatch.fnmatchcase(span, p) for p in patterns)
