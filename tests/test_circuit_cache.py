"""Resident circuits (ISSUE 27): what depends on the circuit alone (the
parsed circuit, its compiled matrices, the device-resident proving key) is
built once per circuit and shared by the workers.

The cache is an instance of `service/crs_cache.py`'s class; the first
tests here hold what that class gained for it (a version an entry is held
to, a weight beside the count), the rest hold the executor to the issue:
one build for concurrent misses, byte-identical proofs from a hit, a
changed file is a miss, eviction, the time account and the counters.

CPU, tiny circuits: these check behaviour, never a speed.
"""

import asyncio
import os
import subprocess
import sys
import threading
import time

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

import distributed_groth16_tpu
from distributed_groth16_tpu.api.server import ApiServer
from distributed_groth16_tpu.api.store import CircuitStore
from distributed_groth16_tpu.frontend.ark_serde import proof_to_bytes
from distributed_groth16_tpu.frontend.r1cs import R1CS, mult_chain_circuit
from distributed_groth16_tpu.frontend.readers import write_r1cs, write_wtns
from distributed_groth16_tpu.models.groth16 import (
    CompiledR1CS,
    pack_proving_key,
)
from distributed_groth16_tpu.models.groth16.prove import prove_single
from distributed_groth16_tpu.models.groth16.reference import prove_host
from distributed_groth16_tpu.models.groth16.setup import setup
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.parallel.pss import PackedSharingParams
from distributed_groth16_tpu.service import CrsCache, ProofJob
from distributed_groth16_tpu.service import worker as worker_mod
from distributed_groth16_tpu.service.worker import ProofExecutor
from distributed_groth16_tpu.telemetry import devmem
from distributed_groth16_tpu.telemetry import metrics as tm
from distributed_groth16_tpu.utils.config import ServiceConfig

JOIN_S = 120.0

# -- the class: versions and weights -----------------------------------------


def _sized(capacity=8, budget=10):
    return CrsCache(
        capacity, counters=worker_mod._CIRCUIT_COUNTERS,
        weigh=len, budget=lambda: budget,
    )


def test_another_version_is_a_miss_that_replaces_the_entry():
    cache, built = CrsCache(4), []

    def mk(tag):
        return lambda: built.append(tag) or tag

    assert cache.get_or_pack("c", mk("old"), version=1) == "old"
    assert cache.get_or_pack("c", mk("never"), version=1) == "old"
    assert cache.get_or_pack("c", mk("new"), version=2) == "new"
    assert cache.get_or_pack("c", mk("never"), version=2) == "new"
    assert built == ["old", "new"] and len(cache) == 1
    s = cache.stats()
    # the replaced entry is a miss, not an eviction
    assert (s["hits"], s["misses"], s["evictions"]) == (2, 2, 0)


def test_entries_are_turned_out_by_weight_oldest_first():
    cache = _sized(budget=10)
    for key in ("a", "b"):
        cache.get_or_pack(key, lambda: "xxxx")
    cache.get_or_pack("a", lambda: "never")  # refreshes `a`
    cache.get_or_pack("c", lambda: "xxxx")  # 12 > 10: `b` goes
    assert "a" in cache and "c" in cache and "b" not in cache
    s = cache.stats()
    assert s["bytes"] == 8 and s["evictions"] == 1 and s["entries"] == 2
    # what was turned out is built again
    built = []
    cache.get_or_pack("b", lambda: built.append(1) or "xxxx")
    assert built == [1] and "b" in cache


def test_an_entry_over_the_whole_budget_is_served_and_not_kept():
    cache = _sized(budget=10)
    cache.get_or_pack("small", lambda: "xxxx")
    assert cache.get_or_pack("huge", lambda: "x" * 11) == "x" * 11
    assert "huge" not in cache and "small" in cache
    s = cache.stats()
    assert s["evictions"] == 0 and s["bytes"] == 4
    # and again: it is a miss every time, never an error
    assert cache.get_or_pack("huge", lambda: "x" * 11) == "x" * 11
    assert cache.stats()["misses"] == 3


def test_without_a_limit_the_count_alone_bounds_the_entries():
    cache = _sized(capacity=2, budget=None)
    for key in ("a", "b", "c"):
        cache.get_or_pack(key, lambda: "x" * 1000)
    assert len(cache) == 2 and cache.stats()["bytes"] == 2000


def test_the_packed_crs_instance_reports_no_bytes():
    cache = CrsCache(2)
    cache.get_or_pack("k", lambda: "v")
    assert "bytes" not in cache.stats()


def test_each_family_counts_under_its_own_names():
    reg = tm.registry()

    def val(name):
        return reg.snapshot().get(name, 0.0)

    names = [
        f"{fam}_{what}_total"
        for fam in ("crs_cache", "circuit_cache")
        for what in ("hits", "misses", "evictions")
    ]
    before = {n: val(n) for n in names}
    cache = _sized(capacity=1, budget=None)
    cache.get_or_pack("a", lambda: "v")
    cache.get_or_pack("a", lambda: "v")
    cache.get_or_pack("b", lambda: "v")
    moved = {n: val(n) - before[n] for n in names}
    assert moved == {
        "crs_cache_hits_total": 0, "crs_cache_misses_total": 0,
        "crs_cache_evictions_total": 0, "circuit_cache_hits_total": 1,
        "circuit_cache_misses_total": 2, "circuit_cache_evictions_total": 1,
    }


# -- the chip's limit --------------------------------------------------------


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats,want", [
    ([{"bytes_limit": 16 << 30}, {"bytes_limit": 15 << 30}], 15 << 30),
    ([None, {"bytes_limit": 8}], 8),
    ([None, {}], None),
    ([], None),
])
def test_limit_bytes_is_the_least_limit_or_none(stats, want):
    assert devmem.limit_bytes([_Dev(s) for s in stats]) == want


def test_on_the_cpu_no_limit_so_no_budget():
    assert devmem.limit_bytes() is None
    assert worker_mod._resident_budget() is None


def test_the_budget_is_a_share_of_one_chips_limit(monkeypatch):
    monkeypatch.setattr(devmem, "limit_bytes", lambda: 16 << 30)
    assert worker_mod._resident_budget() == 4 << 30


# -- the executor ------------------------------------------------------------


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """Two saved circuits of different lengths in one store."""
    root = str(tmp_path_factory.mktemp("resident_store"))
    store = CircuitStore(root)
    out = {}
    for name, length in (("nine", 9), ("five", 5)):
        r1cs, z = mult_chain_circuit(length, 7).finish()
        cid = store.save_circuit(name, write_r1cs(r1cs), b"")
        out[name] = (cid, r1cs, z)
    return root, out


def _executor(root, **cfg):
    return ProofExecutor(CircuitStore(root), cfg=ServiceConfig(**cfg))


def _job(cid, z, kind="prove"):
    return ProofJob(
        kind=kind, circuit_id=cid, fields={"witness_file": write_wtns(z)}
    )


def _counting_load(ex, monkeypatch, delay=0.0, fail_first=False):
    calls, real = [], ex.store.load

    def load(circuit_id, timings=None):
        calls.append(circuit_id)
        time.sleep(delay)
        if fail_first and len(calls) == 1:
            raise OSError("disk hiccup")
        return real(circuit_id, timings)

    monkeypatch.setattr(ex.store, "load", load)
    return calls


def test_two_threads_missing_one_cold_circuit_build_it_once(
    saved, monkeypatch
):
    root, circuits = saved
    cid = circuits["nine"][0]
    ex = _executor(root)
    calls = _counting_load(ex, monkeypatch, delay=0.2)
    got = []
    threads = [
        threading.Thread(target=lambda: got.append(ex.circuit(cid)))
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(JOIN_S)
    assert not any(t.is_alive() for t in threads)
    assert calls == [cid]
    assert len(got) == 4 and all(g is got[0] for g in got)
    s = ex.circuit_cache.stats()
    assert s["misses"] == 1 and s["hits"] == 3


def test_a_failed_build_leaves_the_circuit_usable(saved, monkeypatch):
    root, circuits = saved
    cid = circuits["nine"][0]
    ex = _executor(root)
    calls = _counting_load(ex, monkeypatch, fail_first=True)
    with pytest.raises(OSError, match="disk hiccup"):
        ex.circuit(cid)
    assert cid not in ex.circuit_cache
    circ = ex.circuit(cid)
    assert ex.circuit(cid) is circ and calls == [cid, cid]


def test_an_entry_weighs_its_keys_and_its_matrices_arrays(saved):
    root, circuits = saved
    circ = _executor(root).circuit(circuits["nine"][0])
    arrays = [
        circ.pk.beta_g1, circ.pk.delta_g1, circ.pk.a_query,
        circ.pk.b_g1_query, circ.pk.b_g2_query, circ.pk.h_query,
        circ.pk.l_query,
    ]
    # C too (ISSUE 33): the device's witness check reads it every job
    for m in (circ.comp.A, circ.comp.B, circ.comp.C):
        arrays += [m.coeffs, m.cols, m.ends_idx, m.starts_idx, m.nonempty,
                   m.at_origin]
    assert all(isinstance(a, jax.Array) for a in arrays)
    assert circ.device_bytes() == sum(a.nbytes for a in arrays) > 0


def test_a_second_job_is_a_hit_and_its_proof_is_the_same_bytes(saved):
    root, circuits = saved
    cid, _, z = circuits["nine"]
    ex = _executor(root)
    first = ex.run(_job(cid, z))
    entry = ex.circuit(cid)
    second = ex.run(_job(cid, z))
    assert ex.circuit(cid) is entry
    s = ex.circuit_cache.stats()
    assert s["misses"] == 1 and s["hits"] == 3
    assert first["proof"] == second["proof"]
    # and the same as one made from a fresh read of the disk
    r1cs, pk = CircuitStore(root).load(cid)
    fresh = prove_single(pk, CompiledR1CS(r1cs), fr().encode(z))
    assert bytes(first["proof"]) == proof_to_bytes(fresh)


MPC_TOP_LEVEL = ("load", "witness", "encode", "packing", "MPC Proof",
                 "serialize")
MPC_CHILDREN = {
    "packing": ("packing.qap", "packing.crs", "packing.witness",
                "packing.check"),
    "MPC Proof": ("MPC Proof.round", "MPC Proof.reassemble"),
}


def _king_seconds():
    return {
        k[0]: c.value
        for k, c in tm.registry().family("mpc_king_seconds_total").items()
    }


def _weighted_rounds():
    return tm.registry().family("dmsm_weighted_rounds_total").value


@pytest.fixture(scope="module")
def mpc_jobs(saved):
    """Two served `mpc_prove` jobs on one resident entry, then the `prove`
    job of the same witness: the one compiled round the cases below share.
    Of the second MPC job (a packed-CRS hit, as every job of a benchmark
    window is) it keeps the result, the wall round `ProofExecutor.run`,
    what its trace buffer holds and how the king's counter moved."""
    root, circuits = saved
    cid, r1cs, z = circuits["nine"]
    ex = _executor(root)
    entry = ex.circuit(cid)
    held = dict(vars(entry.pk))
    first = ex.run(_job(cid, z, "mpc_prove"))
    job = _job(cid, z, "mpc_prove")
    before = _king_seconds()
    weighted = _weighted_rounds()
    t0 = time.perf_counter()
    second = ex.run(job)
    wall_ms = 1e3 * (time.perf_counter() - t0)
    moved = {k: v - before[k] for k, v in _king_seconds().items()}
    weighted = _weighted_rounds() - weighted
    return {
        "ex": ex, "cid": cid, "r1cs": r1cs, "z": z, "entry": entry,
        "held": held, "results": (first, second), "wall_ms": wall_ms,
        "events": job.trace.events(), "king_moved": moved,
        "weighted_moved": weighted,
        "single": ex.run(_job(cid, z)),
    }


def test_two_mpc_proofs_on_one_entry_leave_it_as_it_was(mpc_jobs):
    """`packed_crs` packs with `strip=True`: that may clear a dealer's
    scalars and nothing of a key read from disk."""
    ex, entry, held = mpc_jobs["ex"], mpc_jobs["entry"], mpc_jobs["held"]
    assert held["query_scalars"] is None
    proofs = [r["proof"] for r in mpc_jobs["results"]]
    assert ex.circuit(mpc_jobs["cid"]) is entry
    assert all(vars(entry.pk)[k] is v for k, v in held.items())
    assert proofs[0] == proofs[1]
    # r = s = 0: the single-node proof of the same witness, byte for byte
    assert proofs[0] == mpc_jobs["single"]["proof"]
    assert ex.crs_cache.stats()["misses"] == 1


def test_a_served_mpc_proof_is_the_host_provers_bytes(mpc_jobs):
    """The served path (`ProofExecutor.run`, kind `mpc_prove`) against the
    reference prover, as `test_groth16.py` holds the bare round to it."""
    r1cs, z = mpc_jobs["r1cs"], mpc_jobs["z"]
    want = proof_to_bytes(prove_host(setup(r1cs), r1cs, z))
    assert bytes(mpc_jobs["results"][1]["proof"]) == want


def test_an_mpc_jobs_phases_hold_the_rounds_account(mpc_jobs):
    """ISSUE 32: `packing` and `MPC Proof` have children under dotted keys,
    so the top-level keys still partition the job."""
    phases = mpc_jobs["results"][1]["phases"]
    dotted = {c for cs in MPC_CHILDREN.values() for c in cs}
    assert set(MPC_TOP_LEVEL) | dotted <= set(phases)
    assert {k for k in phases if "." not in k} == set(MPC_TOP_LEVEL)
    # the phases lie inside `ProofExecutor.run`'s wall (as_millis rounds
    # each); how much of it they name depends on the machine's load, so
    # only a gross loss is held against
    named = sum(phases[k] for k in MPC_TOP_LEVEL)
    wall_ms = mpc_jobs["wall_ms"]
    assert 0.75 * wall_ms - 50.0 <= named <= wall_ms + 1.0, (named, wall_ms)


@pytest.mark.parametrize("parent", sorted(MPC_CHILDREN))
def test_an_mpc_phase_is_its_children_and_little_else(mpc_jobs, parent):
    phases = mpc_jobs["results"][1]["phases"]
    children = sum(phases[c] for c in MPC_CHILDREN[parent])
    # structural: the children lie inside their parent (as_millis rounds
    # each phase to a microsecond). Between them lie an assignment or two,
    # which a loaded machine can stretch: loose on that side
    assert children <= phases[parent] + 0.01
    assert children >= 0.75 * phases[parent] - 50.0


@pytest.mark.parametrize("name,count", [("dmsm.king", 4), ("dfft.king", 6)])
def test_the_kings_own_function_is_a_span_on_party_0_only(
    mpc_jobs, name, count
):
    """With r = s = 0 a round runs four d_msms (A, B and C's two) and
    `ext_wit.h` three d_iffts and three d_ffts: the king's part of each is
    one span, on the wall clock (on the chip its eager ops wait for the
    device: PERF.md, PR 32), inside the kernel's own span."""
    events = mpc_jobs["events"]
    found = [e for e in events if e["name"] == name]
    assert len(found) == count
    assert {e["pid"] for e in found} == {0}
    assert all("clock" not in e["args"] for e in found)
    kernel = name.split(".")[0]
    outer = [
        (e["ts"], e["ts"] + e["dur"]) for e in events
        if e["pid"] == 0 and e["name"] in (kernel, f"{kernel}.fft",
                                           f"{kernel}.ifft")
    ]
    assert len(outer) == count
    for e in found:
        assert any(lo <= e["ts"] and e["ts"] + e["dur"] <= hi
                   for lo, hi in outer)


@pytest.mark.parametrize("statement", ["stack", "sum"])
def test_each_statement_of_the_dmsm_king_is_a_child_span(mpc_jobs, statement):
    """ISSUE 34 (e): an idle chip inside the king's d_msm function names
    the statement the host sat in. One child of each `dmsm.king`, in
    order, on party 0, wall clock."""
    events = mpc_jobs["events"]
    kings = {e["args"]["id"]: e for e in events if e["name"] == "dmsm.king"}
    found = [e for e in events if e["name"] == f"dmsm.king.{statement}"]
    assert len(found) == len(kings) == 4
    assert {e["args"]["parent"] for e in found} == set(kings)
    assert {e["pid"] for e in found} == {0}
    assert all("clock" not in e["args"] for e in found)
    for e in found:
        king = kings[e["args"]["parent"]]
        assert king["ts"] <= e["ts"] and e["ts"] + e["dur"] <= king["ts"] + king["dur"]


def test_the_kings_counter_moves_by_the_spans_wall(mpc_jobs):
    moved = mpc_jobs["king_moved"]
    assert set(moved) == {"dmsm", "dfft"}
    for stage, seconds in moved.items():
        spans = sum(e["dur"] for e in mpc_jobs["events"]
                    if e["name"] == f"{stage}.king") / 1e6
        # structural: the counter's clock starts before the span's and
        # ends after it. What lies between the two clocks is a span's
        # opening and closing, ten times a round: loose on that side
        assert spans <= seconds + 1e-4
        assert seconds <= 1.25 * spans + 0.05


def test_the_kings_counter_prints_zero_before_any_round():
    """Both series are bound when `parallel/net.py` is imported, so the
    benchmark's first /metrics text holds them unraised."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from distributed_groth16_tpu.parallel import net\n"
         "from distributed_groth16_tpu.telemetry import metrics\n"
         "print(metrics.registry().render_prometheus())"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=JOIN_S, check=True,
    ).stdout.splitlines()
    assert 'mpc_king_seconds_total{stage="dfft"} 0' in out
    assert 'mpc_king_seconds_total{stage="dmsm"} 0' in out


def test_each_dmsm_king_sums_weighted_points(mpc_jobs):
    """The king of each of a proof's four d_msms adds the parties'
    weighted points: the counter moves by four, and no statement of the
    king unpacks in the exponent."""
    assert mpc_jobs["weighted_moved"] == 4
    names = {e["name"] for e in mpc_jobs["events"]}
    assert "dmsm.king.unpack" not in names


def test_the_weighted_rounds_counter_prints_zero_before_any_round():
    """Bound when `parallel/dmsm.py` is imported, as the server does."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from distributed_groth16_tpu.parallel import dmsm\n"
         "from distributed_groth16_tpu.telemetry import metrics\n"
         "print(metrics.registry().render_prometheus())"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=JOIN_S, check=True,
    ).stdout.splitlines()
    assert "dmsm_weighted_rounds_total 0" in out


# -- the device's verdict on a served witness (ISSUE 33) ----------------------
# here, beside the compiled prover and round that `mpc_jobs` holds; the
# verdict itself is held to `is_satisfied` in test_witness_device_check.py


def _device_checks():
    fam = tm.registry().family("witness_device_checks_total")
    return {k[0]: c.value for k, c in fam.items()}


def _off_the_served_path(monkeypatch):
    def refuse(self, z):
        raise AssertionError("R1CS.is_satisfied ran on the served path")

    monkeypatch.setattr(R1CS, "is_satisfied", refuse)


@pytest.mark.parametrize("kind,never_opened", [
    ("prove", ("prove.A", "prove.B", "prove.C", "prove.decode")),
    ("mpc_prove", ("prove.party", "MPC Proof", "MPC Proof.round")),
])
def test_a_bad_witness_is_refused_by_the_devices_verdict_alone(
    mpc_jobs, monkeypatch, kind, never_opened
):
    """FAILED with the message it always had, before an MSM is enqueued
    (`prove`) or a round is entered (`mpc_prove`); the executor and the
    resident entry serve the next job."""
    _off_the_served_path(monkeypatch)
    ex, cid, z = mpc_jobs["ex"], mpc_jobs["cid"], mpc_jobs["z"]
    bad = list(z)
    bad[-1] = (bad[-1] + 1) % fr().p
    job = _job(cid, bad, kind)
    before = _device_checks()
    with pytest.raises(
        ValueError, match="^witness does not satisfy the circuit$"
    ):
        ex.run(job)
    moved = {k: v - before[k] for k, v in _device_checks().items()}
    assert moved == {"ok": 0, "rejected": 1}
    names = {e["name"] for e in job.trace.events()}
    assert not names & set(never_opened)
    # where the verdict was read, after the form's own check passed
    read_in = "prove.check" if kind == "prove" else "packing.check"
    assert {"witness.check", read_in} <= names
    assert ex.circuit(cid) is mpc_jobs["entry"]
    good = ex.run(_job(cid, z, kind))
    assert good["proof"] == mpc_jobs["single"]["proof"]
    moved = {k: v - before[k] for k, v in _device_checks().items()}
    assert moved == {"ok": 1, "rejected": 1}


def test_a_witness_of_the_wrong_form_is_refused_before_the_upload(mpc_jobs):
    ex, cid, z = mpc_jobs["ex"], mpc_jobs["cid"], mpc_jobs["z"]
    before = _device_checks()
    for bad in (z[:-1], z + [0], [2] + z[1:]):
        job = _job(cid, bad)
        with pytest.raises(ValueError, match="does not satisfy"):
            ex.run(job)
        names = {e["name"] for e in job.trace.events()}
        assert "witness.check" in names and "encode" not in names
    assert _device_checks() == before


def test_a_bad_witness_fails_alone_in_a_batch(mpc_jobs, monkeypatch):
    """`BatchProver.run_batch` reads each job's verdict before the job
    joins the batch. The mesh program is stood in for by the sequential
    prover (tests/test_scheduler.py holds the two to the same bytes)."""
    from types import SimpleNamespace

    import numpy as np

    from distributed_groth16_tpu.scheduler import batch_prover as bp
    from distributed_groth16_tpu.scheduler.bucketer import BucketKey

    _off_the_served_path(monkeypatch)
    ex, cid, z = mpc_jobs["ex"], mpc_jobs["cid"], mpc_jobs["z"]
    proved = []

    def sequential(pk, comp, pp, mesh, crs_shares, z_monts, prover=None):
        proved.append(len(z_monts))
        return [prove_single(pk, comp, zm) for zm in z_monts]

    monkeypatch.setattr(bp, "build_batch_mesh_prover", lambda *a: None)
    monkeypatch.setattr(bp, "prove_batch", sequential)
    bad = list(z)
    bad[3] = (bad[3] + 1) % fr().p
    jobs = [_job(cid, w) for w in (z, bad, z)]
    entry = mpc_jobs["entry"]
    key = BucketKey("prove", cid, "bn254", entry.pk.domain_size,
                    entry.r1cs.num_instance, 2)
    before = _device_checks()
    outcomes = dict(
        (job.id, out) for job, out in bp.BatchProver(ex).run_batch(
            jobs, key, SimpleNamespace(devices=np.array([object()]))
        )
    )
    assert proved == [2]
    failed = outcomes[jobs[1].id]
    assert isinstance(failed, ValueError)
    assert str(failed) == "witness does not satisfy the circuit"
    for job in (jobs[0], jobs[2]):
        assert outcomes[job.id]["proof"] == mpc_jobs["single"]["proof"]
        assert outcomes[job.id]["batchSize"] == 2
    moved = {k: v - before[k] for k, v in _device_checks().items()}
    # the stand-in's two `prove_single` calls read a verdict each as well
    assert moved == {"ok": 4, "rejected": 1}


def test_the_verdicts_counter_prints_zero_before_any_job():
    """Both series are bound when `models/groth16/qap.py` is imported, so
    the benchmark's first /metrics text holds them unraised."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from distributed_groth16_tpu.service import worker\n"
         "from distributed_groth16_tpu.telemetry import metrics\n"
         "print(metrics.registry().render_prometheus())"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=JOIN_S, check=True,
    ).stdout.splitlines()
    assert 'witness_device_checks_total{verdict="ok"} 0' in out
    assert 'witness_device_checks_total{verdict="rejected"} 0' in out


def test_strip_clears_the_dealers_scalars_and_nothing_else(saved):
    _, circuits = saved
    pk = setup(circuits["five"][1], seed=42)
    assert pk.query_scalars is not None
    held = {k: v for k, v in vars(pk).items() if k != "query_scalars"}
    pack_proving_key(pk, PackedSharingParams(2), strip=True)
    assert pk.query_scalars is None
    assert all(vars(pk)[k] is v for k, v in held.items())


def test_nothing_in_the_package_donates_a_buffer():
    """Entries are shared by the workers' threads: a donated argument
    would hand one job's key to XLA to overwrite under another."""
    pkg = os.path.dirname(distributed_groth16_tpu.__file__)
    found = []
    for base, _, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path, encoding="utf-8") as fh:
                    text = fh.read()
                if "donate_arg" in text or "donate=" in text:
                    found.append(os.path.relpath(path, pkg))
    assert found == []


def _bump(path):
    """Give `path` a later mtime than anything beside it has."""
    st = os.stat(path)
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 2_000_000_000))


def test_a_newer_r1cs_in_the_directory_is_a_miss_that_serves_it(saved):
    root, circuits = saved
    r1cs9, z9 = mult_chain_circuit(9, 7).finish()
    store = CircuitStore(root)
    cid = store.save_circuit("swap", write_r1cs(r1cs9), b"")
    ex = _executor(root)
    old = ex.circuit(cid)
    assert old.r1cs.num_constraints == r1cs9.num_constraints
    # put by hand: another circuit's file, newer than the saved one
    newer = os.path.join(store._dir(cid), "by_hand.r1cs")
    with open(newer, "wb") as f:
        f.write(write_r1cs(circuits["five"][1]))
    _bump(newer)
    new = ex.circuit(cid)
    assert new is not old
    assert new.r1cs.num_constraints == circuits["five"][1].num_constraints
    assert new.comp.num_constraints == new.r1cs.num_constraints
    assert store.identity(cid)[0] == store._latest(cid, ".r1cs") == newer
    assert ex.circuit(cid) is new and len(ex.circuit_cache) == 1
    s = ex.circuit_cache.stats()
    assert (s["hits"], s["misses"], s["evictions"]) == (1, 2, 0)


def test_a_rewritten_proving_key_is_a_miss_that_serves_it(saved):
    root, _ = saved
    r1cs, z = mult_chain_circuit(9, 7).finish()
    store = CircuitStore(root)
    cid = store.save_circuit("rekey", write_r1cs(r1cs), b"")
    ex = _executor(root)
    old = ex.circuit(cid)
    before = ex.run(_job(cid, z))["proof"]
    key_path = store._key_path(cid)
    setup(r1cs, seed=7).save(key_path)
    _bump(key_path)
    new = ex.circuit(cid)
    assert new is not old and ex.circuit(cid) is new
    after = ex.run(_job(cid, z))["proof"]
    assert after != before
    fresh_r1cs, fresh_pk = store.load(cid)
    fresh = prove_single(fresh_pk, CompiledR1CS(fresh_r1cs), fr().encode(z))
    assert bytes(after) == proof_to_bytes(fresh)


@pytest.mark.parametrize("touch", ["size", "mtime"])
def test_identity_moves_with_either_files_size_or_mtime(saved, touch):
    root, _ = saved
    r1cs, _ = mult_chain_circuit(5, 7).finish()
    store = CircuitStore(root)
    cid = store.save_circuit(f"ident{touch}", write_r1cs(r1cs), b"")
    seen = {store.identity(cid)}
    assert store.identity(cid) in seen
    for path in (store._latest(cid, ".r1cs"), store._key_path(cid)):
        if touch == "mtime":
            _bump(path)
        else:
            st = os.stat(path)
            with open(path, "ab") as f:
                f.write(b"\0")
            os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns))
        ident = store.identity(cid)
        assert ident not in seen
        seen.add(ident)


@pytest.mark.parametrize("call", ["identity", "load", "circuit"])
def test_an_unknown_circuit_id_still_raises_file_not_found(saved, call):
    root, _ = saved
    ex = _executor(root)
    fn = ex.circuit if call == "circuit" else getattr(ex.store, call)
    with pytest.raises(FileNotFoundError):
        fn("circuit_nobody_saved")
    assert len(ex.circuit_cache) == 0


def test_a_circuit_without_its_key_raises_file_not_found(saved):
    root, _ = saved
    r1cs, _ = mult_chain_circuit(5, 7).finish()
    store = CircuitStore(root)
    cid = store.save_circuit("nokey", write_r1cs(r1cs), b"")
    os.remove(store._key_path(cid))
    with pytest.raises(FileNotFoundError):
        _executor(root).circuit(cid)


def test_circuits_are_turned_out_by_count_and_built_again(saved):
    root, circuits = saved
    ex = _executor(root, crs_cache_size=1)
    nine, five = circuits["nine"][0], circuits["five"][0]
    a = ex.circuit(nine)
    ex.circuit(five)
    assert nine not in ex.circuit_cache and five in ex.circuit_cache
    again = ex.circuit(nine)
    assert again is not a
    assert again.r1cs.num_constraints == a.r1cs.num_constraints
    s = ex.circuit_cache.stats()
    assert (s["misses"], s["evictions"], s["entries"]) == (3, 2, 1)


def test_circuits_are_turned_out_by_device_bytes(saved):
    root, circuits = saved
    ex = _executor(root)
    nine, five = circuits["nine"][0], circuits["five"][0]
    w9 = ex.circuit(nine).device_bytes()
    w5 = ex.circuit(five).device_bytes()
    assert ex.circuit_cache.stats()["bytes"] == w9 + w5
    # room for either and not for both
    ex.circuit_cache.clear()
    ex.circuit_cache.budget = lambda: max(w9, w5) + 1
    ex.circuit(nine)
    ex.circuit(five)
    assert nine not in ex.circuit_cache and five in ex.circuit_cache
    assert ex.circuit_cache.stats()["bytes"] == w5
    # room for neither: served, not kept
    ex.circuit_cache.clear()
    ex.circuit_cache.budget = lambda: min(w9, w5) - 1
    assert ex.circuit(nine).device_bytes() == w9
    assert len(ex.circuit_cache) == 0


def test_a_witness_that_fails_the_check_leaves_the_entry_in_place(saved):
    root, circuits = saved
    cid, _, z = circuits["five"]
    ex = _executor(root)
    entry = ex.circuit(cid)
    bad = list(z)
    bad[-1] = (bad[-1] + 1) % fr().p
    with pytest.raises(ValueError, match="does not satisfy"):
        ex.run(_job(cid, bad))
    assert ex.circuit(cid) is entry
    assert ex.run(_job(cid, z))["proof"]
    assert ex.circuit_cache.stats()["misses"] == 1


def test_capacity_zero_reads_the_disk_for_every_job(saved, monkeypatch):
    root, circuits = saved
    cid = circuits["five"][0]
    ex = _executor(root, crs_cache_size=0)
    calls = _counting_load(ex, monkeypatch)
    assert ex.circuit(cid) is not ex.circuit(cid)
    assert calls == [cid, cid] and len(ex.circuit_cache) == 0


# -- the time account and the counters, through the API ----------------------

TOP_LEVEL = ("load", "witness", "encode", "prove", "serialize")
DOTTED = ("load.r1cs", "load.key", "prove.r1cs")


@pytest.fixture(scope="module")
def miss_then_hit(saved):
    """The status DTOs of two `prove` jobs on one circuit, the first on a
    cold server, with /stats and the counters' movement round both."""
    root, circuits = saved
    cid, _, z = circuits["nine"]
    names = [f"circuit_cache_{w}_total" for w in ("hits", "misses", "evictions")]

    async def run():
        server = ApiServer(
            CircuitStore(root), ServiceConfig(workers=1, queue_bound=4)
        )
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            before = tm.registry().snapshot()
            dtos = []
            for _ in range(2):
                resp = await client.post(
                    "/jobs/prove",
                    data={"circuit_id": cid, "witness_file": write_wtns(z)},
                )
                body = await resp.json()
                assert resp.status == 202, body
                while True:
                    resp = await client.get(f"/jobs/{body['jobId']}")
                    st = await resp.json()
                    if st["state"] in ("DONE", "FAILED", "CANCELLED"):
                        break
                    await asyncio.sleep(0.02)
                assert st["state"] == "DONE", st
                dtos.append(st)
            after = tm.registry().snapshot()
            stats = await (await client.get("/stats")).json()
            text = await (await client.get("/metrics")).text()
            entry = server.executor.circuit(cid)
        finally:
            await client.close()
        moved = {n: after.get(n, 0.0) - before.get(n, 0.0) for n in names}
        return dtos, stats, moved, text, entry.device_bytes()

    return asyncio.run(run())


@pytest.mark.parametrize("which", [0, 1], ids=["miss", "hit"])
def test_a_miss_and_a_hit_both_carry_the_whole_account(miss_then_hit, which):
    dto = miss_then_hit[0][which]
    phases = dto["phases"]
    assert set(TOP_LEVEL) | set(DOTTED) <= set(phases)
    assert {k for k in phases if "." not in k} == set(TOP_LEVEL)
    # the top-level phases partition the job (tests/test_time_account.py)
    job_ms = 1e3 * (dto["finishedAt"] - dto["startedAt"])
    named = sum(phases[k] for k in TOP_LEVEL)
    assert 0.9 * job_ms <= named <= job_ms + 1.0, (named, job_ms)
    assert phases["load"] >= phases["load.r1cs"] + phases["load.key"] - 0.01
    assert phases["prove"] >= phases["prove.r1cs"] - 0.01


def test_a_hits_load_is_a_lookup_and_a_misses_is_the_read(miss_then_hit):
    miss, hit = (d["phases"] for d in miss_then_hit[0])
    # a hit stats two files; a miss parses one and uploads the other
    assert hit["load"] < miss["load"]
    assert hit["load.key"] < miss["load.key"]


def test_the_span_tree_keeps_its_shape_on_a_hit(miss_then_hit):
    for dto in miss_then_hit[0]:
        (job,) = [s for s in dto["metrics"]["spans"] if s["name"] == "job"]
        assert [c["name"] for c in job["children"]] == list(TOP_LEVEL)
        load = job["children"][0]
        assert [c["name"] for c in load["children"]][:2] == [
            "load.r1cs", "load.key",
        ]


def test_stats_and_counters_move_as_the_catalog_says(miss_then_hit):
    _, stats, moved, text, entry_bytes = miss_then_hit
    block = stats["circuitCache"]
    assert (block["hits"], block["misses"], block["evictions"]) == (1, 1, 0)
    assert block["entries"] == 1 and block["capacity"] == 8
    assert block["hitRate"] == 0.5
    assert block["bytes"] == entry_bytes > 0
    assert "bytes" not in stats["crsCache"]
    assert moved == {
        "circuit_cache_hits_total": 1, "circuit_cache_misses_total": 1,
        "circuit_cache_evictions_total": 0,
    }
    for name in moved:
        assert f"# TYPE {name} counter" in text
