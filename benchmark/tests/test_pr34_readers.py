"""What PR 34 added to the benchmark: the readers of `gc_ms_per_req`,
`background_ms_per_req` and `submit_server_ms` on hand-made /metrics
texts, their `BENCHMARK.json` entries against the issue's fields, and
`host_spans/pr34.txt`'s names. Nothing the benchmark had is edited."""

import fnmatch
import json
import os

import pytest

from benchmark.layer_metrics import (
    background_ms_per_req,
    gc_ms_per_req,
    submit_server_ms,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["sha256_single_c1", "million_chain_c1", "sha256_mpc_c1"]

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

# the parent's /metrics text: none of the three families
PARENT_TEXT = '''# TYPE circuit_cache_hits_total counter
circuit_cache_hits_total 12
# TYPE witness_device_checks_total counter
witness_device_checks_total{verdict="ok"} 6
'''


def _text(gc=(0.0, 0.0, 0.0), bg=(0.0, 0.0), prove=(0.0, 0), result=(0.0, 0)):
    """A /metrics text of the change: gc seconds per generation, background
    seconds per task, and (seconds, requests) of two routes."""
    lines = [PARENT_TEXT.rstrip("\n")]
    lines += [f'python_gc_seconds_total{{generation="{g}"}} {v}'
              for g, v in enumerate(gc)]
    lines += [f'python_gc_collections_total{{generation="{g}"}} 1000'
              for g in range(3)]
    lines += [f'background_seconds_total{{task="{t}"}} {v}'
              for t, v in zip(("devmem", "slo"), bg)]
    for route, (s, n) in (("/jobs/prove", prove),
                          ("/jobs/{job_id}/result", result)):
        lines.append(f'http_server_seconds_total{{route="{route}"}} {s}')
        lines.append(f'http_server_requests_total{{route="{route}"}} {n}')
    return "\n".join(lines) + "\n"


def _run(before, after, proofs=4, kind="prove"):
    ids = [f"j{i}" for i in range(proofs)]
    return {
        "records": {"metrics_before": before, "metrics_after": after},
        "requests": [{"job_id": j, "valid": True} for j in ids]
        + [{"job_id": "bad", "valid": False}],
        "dtos": {j: {"kind": kind} for j in ids + ["bad"]},
    }


@pytest.mark.parametrize("run,want", [
    # every generation's seconds: (0.001 + 0.003 + 0.4) over four proofs
    (_run(_text(), _text(gc=(0.001, 0.003, 0.4))), 101.0),
    (_run(_text(gc=(1, 1, 1)), _text(gc=(1.004, 1, 1.4)),
          kind="mpc_prove"), 101.0),
    # bound at import and never raised
    (_run(_text(), _text()), 0.0),
    # no first text: the movement is the whole of the second
    (_run(None, _text(gc=(0.0, 0.0, 0.8))), 200.0),
    # the parent's text; no proof in the window; no records
    (_run(PARENT_TEXT, PARENT_TEXT), None),
    (_run(_text(), _text(gc=(1, 1, 1)), proofs=0), None),
    ({}, None),
])
def test_gc_ms_per_req_is_every_generations_movement_over_the_proofs(run, want):
    got = gc_ms_per_req.read(run)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("run,want", [
    # both tasks: (0.002 + 0.006) over four proofs
    (_run(_text(), _text(bg=(0.002, 0.006))), 2.0),
    (_run(_text(bg=(5, 0)), _text(bg=(5.004, 0))), 1.0),
    (_run(_text(), _text()), 0.0),
    (_run(PARENT_TEXT, PARENT_TEXT), None),
    (_run(_text(), _text(bg=(1, 1)), proofs=0), None),
    ({}, None),
])
def test_background_ms_per_req_is_every_tasks_movement_over_the_proofs(
        run, want):
    got = background_ms_per_req.read(run)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("run,want", [
    # 0.1 s over 40 POSTs; the result route's 9 s over 3,000 polls is not read
    (_run(_text(prove=(0.5, 10), result=(1.0, 100)),
          _text(prove=(0.6, 50), result=(10.0, 3100))), 2.5),
    (_run(None, _text(prove=(0.12, 40), result=(3.0, 900))), 3.0),
    # nothing moved there: no mean to take
    (_run(_text(prove=(0.5, 10)), _text(prove=(0.5, 10))), None),
    # the parent's text, and no records
    (_run(PARENT_TEXT, PARENT_TEXT), None),
    ({}, None),
])
def test_submit_server_ms_reads_the_prove_route_alone(run, want):
    got = submit_server_ms.read(run)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("mod,name,layer", [
    (gc_ms_per_req, "gc_ms_per_req", "host runtime"),
    (background_ms_per_req, "background_ms_per_req", "host runtime"),
    (submit_server_ms, "submit_server_ms", "front door"),
])
def test_the_entries_are_the_issues(mod, name, layer):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": layer,
        "moves": "proof_p50_s", "workloads": CELLS,
    }
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])


def test_the_readers_read_families_the_program_binds():
    from distributed_groth16_tpu.api import server  # noqa: F401 — binds http_*
    from distributed_groth16_tpu.telemetry import metrics

    text = metrics.registry().render_prometheus()
    for family in (gc_ms_per_req.FAMILY, background_ms_per_req.FAMILY,
                   "http_server_seconds_total", "http_server_requests_total"):
        assert f"# TYPE {family} counter" in text


@pytest.mark.parametrize("span", [
    "bg.devmem", "bg.slo", "gc", "jax.trace", "jax.lower", "jax.compile",
    "http", "dmsm.king.stack", "dmsm.king.unpack", "dmsm.king.sum",
])
def test_the_new_spans_are_names_the_trace_reducer_knows(span):
    from benchmark import trace_reduce

    assert any(fnmatch.fnmatchcase(span, p)
               for p in trace_reduce.load_span_patterns())
