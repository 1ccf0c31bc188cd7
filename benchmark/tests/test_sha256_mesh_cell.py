"""The four-chip cell `sha256_mesh_c1`: the configuration
`sha256-bn254-mesh-n8l2` (upstream's eight-party round over the four chips
of one host, two parties a chip, as one mesh program) against the MPC
configuration it shares its circuit with, its `BENCHMARK.json` entries
against the contract, each new reader on a hand-made run (a program with
the mesh round and one without), and the new program group on the
program names of both rounds."""

import importlib
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "sha256_mesh_c1", "sha256-bn254-mesh-n8l2"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


DOC, STAR = _config(CONFIG), _config("sha256-bn254-mpc-n8l2")


def test_the_deployment_is_the_star_cells_on_four_chips():
    """The same circuit, pool, sizes, job and parties as the one-chip MPC
    configuration, and its guarantees word for word; what differs is the
    source (upstream's multi-process layout), the layout and the route the
    proofs must take."""
    for key in ("curve", "circuit", "expect", "prove", "parties",
                "service", "randomness", "guarantees", "reduced"):
        assert DOC[key] == STAR[key], key
    # upstream's eight ranks on one host, not its one-process HTTP route
    assert DOC["source"] != STAR["source"]
    assert "network-address/8" in DOC["source"]
    assert DOC["expect"] == {"constraints": 27810, "wires": 27627,
                             "domain_size": 32768}
    assert DOC["parties"] == {"n": 8, "l": 2, "t": 1}
    assert DOC["device_routes"] == ["mpc_round/mesh"]
    assert any("two a chip" in a for a in DOC["assumed"])
    assert DOC["name"] == CONFIG


def test_benchmark_json_holds_the_configuration_and_the_four_chip_cell():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == DOC["source"] and entry["reduced"] == []
    (cell,) = [w for w in BENCH["workloads"] if w["config"] == CONFIG]
    assert {k: cell[k] for k in ("name", "traffic", "chips")} == {
        "name": CELL, "traffic": "prove_c1", "chips": 4}
    for why in (entry["why"], cell["why"]):
        assert len(why) <= 200 and "\n" not in why and "\t" not in why
    # the cell is the benchmark's one four-chip cell, and the last entries
    assert BENCH["configs"][-1] is entry and BENCH["workloads"][-1] is cell
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [CELL]


# the accepted metrics whose readers can read the mesh round, and whose
# lists no earlier test holds to their cells
JOINS = (
    "proof_p50_s", "submit_ms", "job_run_s", "host_prep_ms",
    "dev_busy_ms_per_req", "launches_per_req", "load_r1cs_ms",
    "load_key_ms", "witness_check_ms", "encode_ms", "job_unnamed_ms",
    "circuit_cache_hit_share",
)
# inside the one program no kernel is a launch of its own, and these are
# raised at trace time or by the star's king functions
NOT_READ = (
    "msm_dev_ms_per_req", "msm_g1_dev_ms_per_req", "msm_g2_dev_ms_per_req",
    "ntt_dev_ms_per_req", "msm_hbm_roof_pct", "dfft_dev_ms_per_req",
    "pss_ladder_dev_ms_per_req", "msm_affine_levels_per_req",
    "mpc_king_wall_ms", "msm_limb0_declined_per_req",
)
NEW = {
    "mesh_place_ms": ("collectives", "ms", "program_span"),
    "mesh_dev_ms_per_req": ("prover", "ms", "device_trace"),
    "mesh_gather_mb_per_req": ("collectives", "MB", "program_counter"),
}


def test_the_cell_joins_the_lists_it_can_be_read_in():
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in JOINS:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in NOT_READ:
        assert CELL not in by_name[name]["workloads"], name
    assert CELL not in by_name["proofs_per_s"]["workloads"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_the_cells_alone_and_states_what_its_reader_does(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    layer, unit, source = NEW[name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "proof_p50_s", "workloads": [CELL],
    }
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        layer, unit, "proof_p50_s")


# -- the readers, on hand-made runs ------------------------------------------

STAR_PHASES = {
    "load": 1.4, "witness": 1.1, "encode": 16.0, "packing": 133.3,
    "packing.qap": 101.0, "packing.crs": 0.05, "packing.witness": 20.0,
    "packing.check": 12.0, "MPC Proof": 3600.0, "MPC Proof.round": 2900.0,
    "MPC Proof.reassemble": 700.0, "serialize": 0.03,
}
MESH_PHASES = dict(STAR_PHASES, **{"packing.place": 9.5, "packing": 145.0})

OLD_TEXT = '''# TYPE kernel_route_total counter
kernel_route_total{kernel="msm",path="tree"} 64
'''


def _text(gathered):
    return OLD_TEXT + f'''# TYPE mesh_gather_bytes_total counter
mesh_gather_bytes_total {gathered}
'''


def _run(phases, group_s=None, before=None, after=None, proofs=2):
    ids = [f"j{i}" for i in range(proofs)]
    dto = {"state": "DONE", "kind": "mpc_prove", "createdAt": 10.0,
           "startedAt": 10.0, "finishedAt": 11.5, "phases": phases,
           "partySpans": 8}
    trace = None if group_s is None else {
        "per_job": {"jobs": proofs, "busy_s": 1.1, "launches": 4.0,
                    "group_s": group_s}}
    cell = {c["name"]: c for c in BENCH["workloads"]}[CELL]
    return {
        "cell": cell,
        "records": {"metrics_before": before, "metrics_after": after},
        "requests": [{"kind": "prove", "job_id": j, "valid": True, "ok": True}
                     for j in ids]
        + [{"kind": "prove", "job_id": "bad", "valid": False, "ok": False}],
        "dtos": {j: dict(dto) for j in ids + ["bad"]},
        "trace": trace,
    }


def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


def test_place_reads_the_phase_or_nothing():
    assert _read("mesh_place_ms", _run(MESH_PHASES)) == 9.5
    # the star's round places nothing; an older program has no such phase
    assert _read("mesh_place_ms", _run(STAR_PHASES)) is None
    assert _read("mesh_place_ms", {"requests": [], "dtos": {}}) is None


def test_mesh_device_time_is_per_chip():
    """`trace_reduce` adds up the four chips' launches; the reader divides
    by the cell's chips."""
    groups = {"msm": 0.0, "dfft": 0.0, "mesh_prover": 4.4}
    assert _read("mesh_dev_ms_per_req", _run(MESH_PHASES, groups)) == (
        pytest.approx(1100.0))
    # a round on one chip: no mesh program ran
    star = {"msm": 3.09, "dfft": 0.37, "mesh_prover": 0.0}
    assert _read("mesh_dev_ms_per_req", _run(STAR_PHASES, star)) is None
    assert _read("mesh_dev_ms_per_req", _run(MESH_PHASES)) is None


@pytest.mark.parametrize("run,want", [
    # 2 proofs moved the counter by 113.26 MB: 56.63 MB a proof
    (_run(MESH_PHASES, before=_text(1000), after=_text(113_258_728 + 1000)),
     56.629364),
    # the counter is bound at import: the star's rounds leave it at 0
    (_run(STAR_PHASES, before=_text(0), after=_text(0)), 0.0),
    # an older program's /metrics text: no such counter
    (_run(STAR_PHASES, before=OLD_TEXT, after=OLD_TEXT), None),
    # no proof completed in the window; no records at all
    (_run(MESH_PHASES, before=_text(5), after=_text(5), proofs=0), None),
    ({}, None),
])
def test_gathered_bytes_are_the_counters_movement_over_the_proofs(run, want):
    got = _read("mesh_gather_mb_per_req", run)
    assert got == (pytest.approx(want) if want is not None else None)


def _traced_line(run):
    """The run through `run.py`'s `result_line`, as the cell's traced line."""
    cell, config, traffic = bench_run.load_cell(BENCH, CELL)
    run.update(cell=cell, config=config, traffic=traffic, on_chip=False,
               window={"start": 100.0, "start_epoch": 1100.0},
               t0_epoch=1000.0, device_kind="TPU v5 lite",
               memory_peak_bytes=3.2e9, compiles_in_window=0,
               sizes={"wires": 27627, "instance": 3, "domain_size": 32768},
               setup={"trace_s": 150.0, "compile_s": 100.0,
                      "artefacts_s": 0.1, "warmup_s": 300.0})
    for r in run["requests"]:
        r.update(t_send=100.0, t_accepted=100.01, t_done=101.3)
    return bench_run.result_line(run, BENCH, trace=True, device={}, faults=[])


def test_the_line_of_a_star_run_leaves_the_new_metrics_out():
    """A program without the mesh round runs the cell through the star round
    on one chip of the four, with these benchmark files: none of the
    new readers finds anything there."""
    got = set(_traced_line(_run(
        STAR_PHASES, {"msm": 3.09, "mesh_prover": 0.0},
        before=OLD_TEXT, after=OLD_TEXT,
    ))["metrics"])
    assert not set(NEW) & got
    assert {"job_run_s", "dev_busy_ms_per_req", "launches_per_req"} <= got


def test_the_line_of_the_change_holds_every_new_metric():
    line = _traced_line(_run(
        MESH_PHASES, {"msm": 0.0, "mesh_prover": 4.4},
        before=_text(0), after=_text(2 * 56_628_864),
    ))
    assert set(NEW) <= set(line["metrics"])
    assert line["metrics"]["mesh_gather_mb_per_req"] == {
        "value": pytest.approx(56.628864), "unit": "MB"}
    assert not set(NOT_READ) & set(line["metrics"])


# -- the group file -----------------------------------------------------------


def test_the_mesh_group_names_the_one_program_and_no_star_program():
    groups = tr.load_groups()
    assert groups["mesh_prover"] == ["mesh_prover"]
    star = ("_msm_tree_jit_g1", "_msm_tree_jit_g2", "_fft1_local",
            "_fft2_king", "_ntt_core", "_sum_points", "mul", "add",
            "_matvec_jit", "from_mont")
    for program in star:
        assert not any(x in program for x in groups["mesh_prover"]), program
    # the mesh program is no other group's
    for g, needles in groups.items():
        hit = any(x in "mesh_prover" for x in needles)
        assert hit == (g == "mesh_prover"), g
