"""The cell PR 32 added, `sha256_mpc_c1`, and what it brought: the
configuration `sha256-bn254-mpc-n8l2` (in the tree since PR 22) against the
circuit its generator builds, its `BENCHMARK.json` entries against the
contract, each new reader on a hand-made run (a program with the round's
time account and one without), the two new program groups and the new
span names on a hand-made trace of the round's program names, and the cell
through `rehearse.py` on the CPU at a tiny length."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "sha256_mpc_c1", "sha256-bn254-mpc-n8l2"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
    DOC = json.load(f)


def test_expect_is_what_the_generator_builds():
    """Sizes only, no key: the one-block constraint system of
    `circuits/sha256.py`, the domain `setup` would give it, and the shares
    a party's four MSMs run over (`shapes.proof_msms`' MPC branch)."""
    from benchmark import shapes
    from distributed_groth16_tpu.frontend.sha256 import sha256_circuit

    assert DOC["circuit"]["generator"] == "sha256"
    assert DOC["circuit"]["params"] == {"blocks": 1} and DOC["reduced"] == []
    cs, publics = sha256_circuit(b"dg16 bench pool 22/0")
    r1cs, z = cs.finish()
    rows = r1cs.num_constraints + r1cs.num_instance
    assert {
        "constraints": r1cs.num_constraints,
        "wires": r1cs.num_wires,
        "domain_size": 1 << (rows - 1).bit_length(),
    } == DOC["expect"]
    assert r1cs.num_instance == len(publics) + 1 == 3
    sizes = dict(wires=r1cs.num_wires, instance=3, domain_size=32768)
    msms = shapes.proof_msms(DOC, sizes)
    # eight parties x (A, B, L, H) over 1/l of the length
    assert msms == [("g1", 13813), ("g2", 13813), ("g1", 13812),
                    ("g1", 16384)] * 8
    # the same circuit, pool and seed as the single-node configuration:
    # one `.bench_cache` key, so a proof is compared across the two
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sha256-bn254-single.json")) as f:
        single = json.load(f)
    assert DOC["circuit"] == single["circuit"]
    assert DOC["expect"] == single["expect"]


def test_the_configuration_states_the_rounds_guarantees():
    assert DOC["prove"] == {"kind": "mpc_prove", "l": 2}
    assert DOC["parties"] == {"n": 8, "l": 2, "t": 1}
    assert DOC["device_routes"] == ["msm/tree"]
    assert len(DOC["guarantees"]) == 3
    assert any("eight prove.party spans" in g for g in DOC["guarantees"])
    assert len(DOC["source"]) <= 200 and "\n" not in DOC["source"]


# the accepted `_c1` metrics whose readers can read an `mpc_prove` job
JOINS = (
    "submit_ms", "job_run_s", "host_prep_ms", "load_r1cs_ms", "load_key_ms",
    "witness_check_ms", "encode_ms", "job_unnamed_ms", "dev_busy_ms_per_req",
    "launches_per_req", "msm_dev_ms_per_req", "msm_g1_dev_ms_per_req",
    "msm_g2_dev_ms_per_req", "ntt_dev_ms_per_req", "msm_hbm_roof_pct",
    "circuit_cache_hit_share", "msm_affine_levels_per_req",
)
# what this PR added: name -> (layer, source)
NEW = {
    "mpc_packing_ms": ("prover", "program_span"),
    "mpc_packing_qap_ms": ("prover", "program_span"),
    "mpc_round_ms": ("prover", "program_span"),
    "mpc_round_enqueue_ms": ("prover", "program_span"),
    "mpc_round_drain_ms": ("prover", "program_span"),
    "mpc_king_wall_ms": ("collectives", "program_counter"),
    "pss_ladder_dev_ms_per_req": ("kernels", "device_trace"),
    "dfft_dev_ms_per_req": ("kernels", "device_trace"),
}


def test_benchmark_json_holds_the_configuration_and_the_cell():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == DOC["source"] and entry["reduced"] == []
    (cell,) = [w for w in BENCH["workloads"] if w["config"] == CONFIG]
    assert {k: cell[k] for k in ("name", "traffic", "chips")} == {
        "name": CELL, "traffic": "prove_c1", "chips": 1}
    for why in (entry["why"], cell["why"]):
        assert len(why) <= 200 and "\n" not in why and "\t" not in why


def test_the_cell_joins_every_list_it_can_be_read_in_and_not_limb0s():
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in ("proof_p50_s",) + JOINS:
        assert CELL in by_name[name]["workloads"], name
    # the round hands `msm` no view, so that counter cannot move here (and
    # test_million_chain_cell.py holds its list to the two cells it has)
    assert CELL not in by_name["msm_limb0_declined_per_req"]["workloads"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_the_cells_alone_and_states_what_its_reader_does(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    layer, source = NEW[name]
    assert entry == {
        "name": name, "unit": "ms", "better": "lower", "source": source,
        "layer": layer, "moves": "proof_p50_s", "workloads": [CELL],
    }
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        layer, "ms", "proof_p50_s")


# -- the readers, on hand-made runs ------------------------------------------

# a DTO's phases as the parent gives them for an `mpc_prove` job (PR 30's
# chip run), and with the round's time account
OLD_PHASES = {
    "load.r1cs": 1.082, "load.key": 0.24, "load": 1.456,
    "witness.parse": 13.34, "witness.check": 59.593, "witness": 73.038,
    "encode": 15.382, "packing": 146.86, "MPC Proof": 4568.411,
    "serialize": 0.025,
}
NEW_PHASES = dict(
    OLD_PHASES, **{
        "packing.qap": 120.5, "packing.crs": 0.06, "packing.witness": 26.1,
        "MPC Proof.round": 2900.25, "MPC Proof.reassemble": 1668.0,
    }
)

PARENT_TEXT = '''# TYPE msm_affine_levels_total counter
msm_affine_levels_total 96
# TYPE collective_seconds histogram
collective_seconds_count{op="king_compute"} 32
'''


def _text(dmsm, dfft):
    return PARENT_TEXT + f'''# TYPE mpc_king_seconds_total counter
mpc_king_seconds_total{{stage="dfft"}} {dfft}
mpc_king_seconds_total{{stage="dmsm"}} {dmsm}
'''


def _run(phases, group_s=None, before=None, after=None, proofs=2,
         kind="mpc_prove"):
    ids = [f"j{i}" for i in range(proofs)]
    dto = {"state": "DONE", "kind": kind, "createdAt": 10.0,
           "startedAt": 10.0, "finishedAt": 14.81, "phases": phases,
           "partySpans": 8}
    trace = None if group_s is None else {
        "per_job": {"jobs": proofs, "busy_s": 3.611, "launches": 789.0,
                    "group_s": group_s}}
    return {
        "records": {"metrics_before": before, "metrics_after": after},
        "requests": [{"kind": "prove", "job_id": j, "valid": True, "ok": True}
                     for j in ids]
        + [{"kind": "prove", "job_id": "bad", "valid": False, "ok": False}],
        "dtos": {j: dict(dto) for j in ids + ["bad"]},
        "trace": trace,
    }


def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


@pytest.mark.parametrize("name,key,at_parent", [
    ("mpc_packing_ms", "packing", True),
    ("mpc_round_ms", "MPC Proof", True),
    ("mpc_packing_qap_ms", "packing.qap", False),
    ("mpc_round_enqueue_ms", "MPC Proof.round", False),
    ("mpc_round_drain_ms", "MPC Proof.reassemble", False),
])
def test_a_phase_reader_reads_its_key_or_nothing(name, key, at_parent):
    assert _read(name, _run(NEW_PHASES)) == NEW_PHASES[key]
    # the parent's DTO has the two phases and none of their children
    want = OLD_PHASES[key] if at_parent else None
    assert _read(name, _run(OLD_PHASES)) == want
    # a `prove` job has neither
    single = {"load": 1.0, "witness": 75.0, "encode": 15.0, "prove": 217.0}
    assert _read(name, _run(single, kind="prove")) is None
    assert _read(name, {"requests": [], "dtos": {}}) is None


def test_enqueue_and_drain_partition_the_round():
    run = _run(NEW_PHASES)
    parts = (_read("mpc_round_enqueue_ms", run)
             + _read("mpc_round_drain_ms", run))
    assert parts == pytest.approx(_read("mpc_round_ms", run), rel=0.01)


@pytest.mark.parametrize("run,want", [
    # both stages moved: (0.9 - 0.3) + (2.5 - 1.1) s over two proofs
    (_run(NEW_PHASES, before=_text(0.3, 1.1), after=_text(0.9, 2.5)), 1000.0),
    # the series are bound at import: labelled, and unmoved they read 0
    (_run(NEW_PHASES, before=_text(0, 0), after=_text(0, 0)), 0.0),
    # the warm-up's text is missing: the movement is from 0
    (_run(NEW_PHASES, before=None, after=_text(0.5, 0.5)), 500.0),
    # the parent's /metrics text: no such counter
    (_run(OLD_PHASES, before=PARENT_TEXT, after=PARENT_TEXT), None),
    # no proof completed in the window; no records at all
    (_run(NEW_PHASES, before=_text(1, 1), after=_text(1, 1), proofs=0), None),
    (_run(NEW_PHASES), None),
    ({}, None),
])
def test_king_wall_is_the_counters_movement_over_the_proofs(run, want):
    got = _read("mpc_king_wall_ms", run)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("group", ["pss_ladder", "dfft"])
def test_a_group_reader_reads_its_group_or_nothing(group):
    name = f"{group}_dev_ms_per_req"
    mpc = {"msm": 2.6566, "msm_g1": 0.8756, "msm_g2": 1.7810, "ntt": 0.1171,
           "pss_ladder": 0.4181, "dfft": 0.3719}
    assert _read(name, _run(NEW_PHASES, mpc)) == pytest.approx(1e3 * mpc[group])
    # a `prove` job runs no such program: the group sums to 0
    single = dict(mpc, pss_ladder=0.0, dfft=0.0)
    assert _read(name, _run(NEW_PHASES, single)) is None
    assert _read(name, _run(NEW_PHASES)) is None  # untraced: no per-job block


GROUP_S = {"msm": 2.6566, "msm_g1": 0.8756, "msm_g2": 1.7810, "ntt": 0.1171,
           "pss_ladder": 0.4181, "dfft": 0.3719}


def _traced_line(run):
    """The run through `run.py`'s `result_line`, as the cell's traced line."""
    cell, config, traffic = bench_run.load_cell(BENCH, CELL)
    run.update(cell=cell, config=config, traffic=traffic, on_chip=False,
               window={"start": 100.0, "start_epoch": 1100.0},
               t0_epoch=1000.0, device_kind="TPU v5 lite",
               memory_peak_bytes=937024512, compiles_in_window=0,
               sizes={"wires": 27627, "instance": 3, "domain_size": 32768},
               setup={"trace_s": 103.0, "compile_s": 76.0,
                      "artefacts_s": 0.1, "warmup_s": 233.0})
    for r in run["requests"]:
        r.update(t_send=100.0, t_accepted=100.01, t_done=104.6)
    return bench_run.result_line(run, BENCH, trace=True, device={}, faults=[])


def test_the_line_of_a_parent_run_leaves_the_new_keys_metrics_out():
    """The parent runs the cell (the driver lays this PR's benchmark files
    over it): its traced line holds the accepted metrics and the two phases
    it has, and none of what reads this PR's keys and counter."""
    got = set(_traced_line(_run(
        OLD_PHASES, GROUP_S, before=PARENT_TEXT, after=PARENT_TEXT
    ))["metrics"])
    assert not {"mpc_packing_qap_ms", "mpc_round_enqueue_ms",
                "mpc_round_drain_ms", "mpc_king_wall_ms"} & got
    assert {"mpc_packing_ms", "mpc_round_ms", "pss_ladder_dev_ms_per_req",
            "dfft_dev_ms_per_req", "job_run_s", "msm_hbm_roof_pct"} <= got
    assert "msm_limb0_declined_per_req" not in got


def test_the_line_of_the_change_holds_every_new_metric():
    line = _traced_line(_run(
        NEW_PHASES, GROUP_S, before=_text(0, 0), after=_text(0.2, 0.4)
    ))
    assert set(NEW) <= set(line["metrics"])
    assert line["metrics"]["mpc_king_wall_ms"] == {
        "value": pytest.approx(300.0), "unit": "ms"}


# -- the group files and the span names, on the round's program names --------

# program names as a traced run of the cell on the chip lists them
# (`breakdown.device_ops`, PR 30's scratch cell and this PR's runs), and
# those of the accepted cells (PERF.md section 3)
MPC_PROGRAMS = (
    "_msm_tree_jit_g2", "_msm_tree_jit_g1", "_dense_ladder_jit",
    "_fft1_local", "_ntt_core", "mul", "add", "_fft2_king", "_matvec_jit",
    "from_mont",
)
ACCEPTED_PROGRAMS = (
    "_msm_tree_jit_g1", "_msm_tree_jit_g2", "_msm_tree_jit_g1_limb0",
    "_msm_tree_jit_g1_limb0_fill", "_msm_tree_jit_g2_limb0",
    "_msm_tree_jit_g2_limb0_fill", "_limb_ntt_route", "_ntt_core",
    "_matvec_jit", "mul", "add", "sub", "from_mont", "_msm_ladder_jit",
    "convert_element_type",
)
WANT = {"_dense_ladder_jit": "pss_ladder", "_fft1_local": "dfft",
        "_fft2_king": "dfft", "_ntt_core": "ntt",
        "_msm_tree_jit_g1": "msm", "_msm_tree_jit_g2": "msm"}


def _groups_of(program, groups):
    return {g for g, needles in groups.items()
            if any(x in program for x in needles)}


def test_the_new_groups_share_no_program_with_the_accepted_ones():
    groups = tr.load_groups()
    assert {"pss_ladder", "dfft"} <= set(groups)
    disjoint = {g: groups[g] for g in ("msm", "ntt", "pss_ladder", "dfft")}
    for program in MPC_PROGRAMS:
        got = _groups_of(program, disjoint)
        assert got == ({WANT[program]} if program in WANT else set()), program
    # `ladder_apply`, the limb-major ladder of the key's packing (set-up)
    assert _groups_of("ladder_apply", disjoint) == {"pss_ladder"}
    for program in ACCEPTED_PROGRAMS:
        assert not _groups_of(
            program, {g: groups[g] for g in ("pss_ladder", "dfft")}), program


def _events(rows):
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * 1e6)} "
        f"duration_ps: {int(d * 1e6)} }}" for m, s, d in rows
    )


def _meta(names):
    return "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items()
    )


# One chip, microseconds, one job [100, 1100). A d_fft: `_fft1_local` then a
# gap while the king's tail is dispatched, then `_fft2_king`; a d_msm: a G1
# tree launch, a gap in which the parties wait for the king, whose unpack
# is dispatched in the second half of it, then the ladder.
MPC_MODULES = [(1, 150, 100), (2, 300, 20), (3, 400, 200), (4, 800, 100),
               (5, 900, 50)]
MPC_HOST = [
    (1, 100, 1000),                 # job
    (2, 110, 980),                  # MPC Proof
    (3, 120, 700),                  # MPC Proof.round
    (4, 140, 200),                  # dfft.fft
    (5, 255, 40),                   # dfft.king: covers the gap 250..300
    (6, 390, 420),                  # dmsm
    (7, 595, 210),                  # net.king_compute: the gap 600..800
    (8, 720, 75),                   # dmsm.king: 75 of the gap's 200 us
    (9, 960, 120),                  # MPC Proof.reassemble: the gap 950..1100
]
MPC_TEXT = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {_events(MPC_MODULES)} }}
  {_meta({1: "jit__fft1_local(11)", 2: "jit__fft2_king(12)",
          3: "jit__msm_tree_jit_g1(13)", 4: "jit__dense_ladder_jit(14)",
          5: "jit__ntt_core(15)"})}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 7 name: "worker" timestamp_ns: 0 {_events(MPC_HOST)} }}
  {_meta({1: "job", 2: "MPC Proof", 3: "MPC Proof.round", 4: "dfft.fft",
          5: "dfft.king", 6: "dmsm", 7: "net.king_compute", 8: "dmsm.king",
          9: "MPC Proof.reassemble"})}
}}
"""


def test_the_data_files_reduce_a_round_to_its_groups_and_name_its_gaps(
    tmp_path
):
    """With the group and span files as they are on disk: the four groups
    cover the round's launches, and an idle gap reads the king's own
    function where that is what the host was in, the collective's wait
    where the parties waited, the wait for the chip at the round's end."""
    from jax.profiler import ProfileData

    path = tmp_path / "mpc.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(MPC_TEXT))
    out = tr.reduce_trace(str(path), 1)
    g = out["per_job"]["group_s"]
    assert g["dfft"] == pytest.approx(120e-6)
    assert g["pss_ladder"] == pytest.approx(100e-6)
    assert g["msm"] == g["msm_g1"] == pytest.approx(200e-6)
    assert g["msm_g2"] == 0 and g["ntt"] == pytest.approx(50e-6)
    covered = g["msm"] + g["ntt"] + g["pss_ladder"] + g["dfft"]
    assert covered == pytest.approx(out["per_job"]["busy_s"])
    gaps = {n: t for n, t in out["idle_gaps"][5:]}
    assert gaps["all:dfft.king"] == pytest.approx(50e-6)       # 250..300
    assert gaps["all:net.king_compute"] == pytest.approx(200e-6)  # 600..800
    assert gaps["all:MPC Proof.reassemble"] == pytest.approx(150e-6)
    # a gap the king's unpack fills names it, not the collective
    named = tr.name_gap((725e-6, 790e-6), tr.read_xplane(
        str(path), tr.load_span_patterns()).spans)
    assert named == "dmsm.king"


def test_the_cell_rehearses_on_the_cpu_in_both_trace_modes():
    """`rehearse.py --workload sha256_mpc_c1` at 16 constraints: the cell's
    own configuration file (job kind, parties, guarantees), traffic file
    and readers through `run.py`'s pieces, a process of its own as on the
    chip; `checks.py` holds each job to eight `prove.party` spans and the
    window to no packed-CRS miss."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELL, "--length", "16", "--seconds", "3",
         "--seed", "3200000111"],
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
    traced = set(lines["trace=1"]["metrics"])
    # the device-trace readers find no chip here; the rest have values
    assert {n for n, (_, source) in NEW.items()
            if source != "device_trace"} <= traced
    assert "msm_limb0_declined_per_req" not in traced
