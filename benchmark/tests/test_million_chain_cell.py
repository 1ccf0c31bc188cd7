"""The cell PR 30 added, `million_chain_c1`, and what it brought: the
configuration `million-chain-bn254-single` against the circuit its
generator builds, its `BENCHMARK.json` entries against the contract, the
reader of `msm_limb0_declined_per_req` on hand-made /metrics texts, and the
cell through `rehearse.py` on the CPU at a tiny length."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.layer_metrics import msm_limb0_declined_per_req

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "million_chain_c1", "million-chain-bn254-single"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
    DOC = json.load(f)


def test_expect_is_what_the_generator_builds_at_the_timed_length():
    """Sizes only, no key: the constraint system of `circuits/mult_chain.py`
    at `length` 65000, and the domain `setup` would give it."""
    from distributed_groth16_tpu.frontend.r1cs import mult_chain_circuit

    params = DOC["circuit"]["params"]
    assert DOC["circuit"]["generator"] == "mult_chain"
    assert params == {"length": 65000} and DOC["reduced"] == ["length"]
    assert DOC["published"]["length"] == 1 << 20
    r1cs, z = mult_chain_circuit(
        3 + 1000 * DOC["circuit"]["pool_seed"], params["length"]
    ).finish()
    rows = r1cs.num_constraints + r1cs.num_instance
    assert {
        "constraints": r1cs.num_constraints,
        "wires": r1cs.num_wires,
        "domain_size": 1 << (rows - 1).bit_length(),
    } == DOC["expect"]
    # the witness fills the field: far more wide wires than the limb-0
    # form has slots for (15 a wire in the padding of 65002 to 65536)
    wide = sum(v >> 16 != 0 for v in z)
    assert wide > 64990 and (65536 - r1cs.num_wires) // 15 == 35
    assert sum(v >> 240 != 0 for v in z) > 64900


def test_the_configuration_keeps_the_served_deployments_guarantees():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sha256-bn254-single.json")) as f:
        sha = json.load(f)
    for key in ("guarantees", "prove", "parties", "service", "device_routes",
                "curve", "randomness"):
        assert DOC[key] == sha[key], key
    assert len(DOC["source"]) <= 200 and "\n" not in DOC["source"]
    assert len(DOC["why_reduced"]) == 4  # the cut, and its three reasons


# what the issue asked to report in the cell, beside `proof_p50_s`
REPORTS = (
    "submit_ms", "job_run_s", "host_prep_ms", "load_r1cs_ms", "load_key_ms",
    "witness_check_ms", "encode_ms", "job_unnamed_ms", "dev_busy_ms_per_req",
    "launches_per_req", "msm_dev_ms_per_req", "msm_g1_dev_ms_per_req",
    "msm_g2_dev_ms_per_req", "ntt_dev_ms_per_req", "msm_hbm_roof_pct",
    "circuit_cache_hit_share", "msm_affine_levels_per_req",
)


def test_benchmark_json_holds_the_configuration_the_cell_and_the_metric():
    """Only what this cell owns, found by name: where an entry sits, and
    what other cells and metrics the file holds, is a later PR's to say."""
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == DOC["source"]
    assert entry["reduced"] == ["length"]
    (cell,) = [w for w in BENCH["workloads"] if w["config"] == CONFIG]
    assert {k: cell[k] for k in ("name", "traffic", "chips")} == {
        "name": CELL, "traffic": "prove_c1", "chips": 1}
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in ("proof_p50_s",) + REPORTS:
        assert CELL in by_name[name]["workloads"], name
    assert by_name["msm_limb0_declined_per_req"] == {
        "name": "msm_limb0_declined_per_req", "unit": "count",
        "better": "lower", "source": "program_counter", "layer": "kernels",
        "moves": "proof_p50_s", "workloads": ["sha256_single_c1", CELL],
    }


PARENT_TEXT = '''# TYPE msm_affine_levels_total counter
msm_affine_levels_total 40
# TYPE kernel_route_total counter
kernel_route_total{kernel="msm",path="tree"} 8
'''


def _text(declined):
    return PARENT_TEXT + f'''# TYPE msm_limb0_declined_total counter
msm_limb0_declined_total{{reason="over_capacity"}} {declined}
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
    # a witness that fills the field: A, B and L declined in every proof
    (_run(_text(6), _text(18)), 3.0),
    # a witness of bits: the series is bound at import and stays at 0
    (_run(_text(0), _text(0)), 0.0),
    # the parent's /metrics text: no such counter
    (_run(PARENT_TEXT, PARENT_TEXT), None),
    # no proof completed in the window; no records at all
    (_run(_text(6), _text(6), proofs=0), None),
    (_run(None, None), None),
    ({}, None),
])
def test_declined_per_req_is_the_counters_movement_over_the_proofs(run, want):
    got = msm_limb0_declined_per_req.read(run)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_cell_rehearses_on_the_cpu_in_both_trace_modes():
    """`rehearse.py --workload million_chain_c1` at 16 constraints: the
    cell's own configuration file, traffic file and readers through
    `run.py`'s pieces, a process of its own as on the chip."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELL, "--length", "16", "--seconds", "2",
         "--seed", "3000000111"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = {
        line.split()[2]: json.loads(line.split(" ", 3)[3])
        for line in out.stdout.splitlines()
        if line.startswith(f"rehearsed {CELL} ")
    }
    assert set(lines) == {"trace=0", "trace=1"}
    for shape in lines.values():
        assert shape["correct"] is True and shape["failed"] == 0
        assert shape["attempted"] >= 1
    assert lines["trace=0"]["metrics"] == ["proof_p50_s", "setup_s"]
    traced = lines["trace=1"]["metrics"]
    assert "msm_limb0_declined_per_req" in traced
    assert "msm_affine_levels_per_req" in traced
