"""The generators' schedules are a function of the traffic file and the
seed, and of nothing else."""

import glob
import json
import os
import statistics

import pytest

from benchmark.schedule import Plan, Traffic

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")

CLOSED = {"loop": "closed", "clients": 4, "witness_pool": 8,
          "mix": [{"kind": "prove", "weight": 1}]}
VERIFY = {"loop": "closed", "clients": 1, "witness_pool": 8,
          "corrupt_share": 0.125, "mix": [{"kind": "verify", "weight": 1}]}
OPEN = {"loop": "open", "rate": 5.0, "burst": 1, "witness_pool": 8,
        "mix": [{"kind": "prove", "weight": 3}, {"kind": "verify", "weight": 1}],
        "corrupt_share": 0.25, "circuits": 3, "zipf_s": 1.0}


def test_every_traffic_file_parses():
    files = glob.glob(os.path.join(TRAFFIC, "*.json"))
    assert files
    for path in files:
        with open(path) as f:
            Traffic.from_dict(json.load(f))


def test_closed_loop_same_seed_same_requests():
    a, b = (Plan(Traffic.from_dict(CLOSED), 7) for _ in range(2))
    for c in range(4):
        assert [a.closed(c, j) for j in range(20)] == \
               [b.closed(c, j) for j in range(20)]


def test_closed_loop_walks_the_whole_pool_in_turn():
    plan = Plan(Traffic.from_dict(CLOSED), 3)
    for c in range(4):
        taken = [plan.closed(c, j).witness for j in range(8)]
        assert sorted(taken) == list(range(8))
        # and round again in the same order
        assert [plan.closed(c, j).witness for j in range(8, 16)] == taken
    # four clients start two entries apart in the seeded order
    firsts = [plan.closed(c, 0).witness for c in range(4)]
    assert firsts == [plan.order[2 * c] for c in range(4)]


def test_seed_changes_the_order():
    orders = {tuple(Plan(Traffic.from_dict(CLOSED), s).order) for s in range(8)}
    assert len(orders) > 1


def test_verify_mix_corrupts_one_in_eight():
    plan = Plan(Traffic.from_dict(VERIFY), 11)
    cycle = [plan.closed(0, j) for j in range(8)]
    assert sum(r.corrupt for r in cycle) == 1
    assert all(r.kind == "verify" for r in cycle)
    assert not any(r.corrupt for r in plan.warmup())


def test_closed_loop_callers_each_send_one_kind():
    doc = dict(VERIFY, clients=4, mix=[{"kind": "prove", "weight": 1},
                                       {"kind": "verify", "weight": 1}])
    plan = Plan(Traffic.from_dict(doc), 2)
    kinds = [{plan.closed(c, j).kind for j in range(12)} for c in range(4)]
    assert kinds == [{"prove"}, {"verify"}, {"prove"}, {"verify"}]
    assert not any(plan.closed(0, j).corrupt for j in range(8))
    assert sum(plan.closed(1, j).corrupt for j in range(8)) == 1


def test_open_loop_schedule():
    traffic = Traffic.from_dict(OPEN)
    sched = Plan(traffic, 5).open_schedule(200.0)
    assert sched == Plan(traffic, 5).open_schedule(200.0)
    assert sched != Plan(traffic, 6).open_schedule(200.0)
    due = [r.due_s for r in sched]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 200.0
    # Poisson at 5/s over 200 s: 1000 expected, sd about 32
    assert 850 < len(sched) < 1150
    gaps = [b - a for a, b in zip(due, due[1:])]
    assert 0.17 < statistics.mean(gaps) < 0.23
    kinds = [r.kind for r in sched]
    assert 0.65 < kinds.count("prove") / len(kinds) < 0.85
    # Zipf s = 1 over three circuits: 6/11, 3/11, 2/11
    by_circuit = [sum(r.circuit == c for r in sched) for c in range(3)]
    assert by_circuit[0] > by_circuit[1] > by_circuit[2] > 0
    assert not any(r.corrupt for r in sched if r.kind == "prove")


def test_open_loop_bursts_and_uniform_arrivals():
    doc = dict(OPEN, burst=8, rate=4.0, arrivals="uniform")
    sched = Plan(Traffic.from_dict(doc), 1).open_schedule(21.0)
    # a group of 8 every 2 s, the first at 2 s
    assert len(sched) == 80
    assert {round(r.due_s, 9) for r in sched} == {2.0 * k for k in range(1, 11)}


@pytest.mark.parametrize("bad", [
    {"loop": "sideways"}, {"mix": []}, {"witness_pool": 0},
    {"mix": [{"kind": "mine", "weight": 1}]}, {"corrupt_share": 2},
    {"loop": "open", "rate": 0},
])
def test_bad_traffic_is_refused(bad):
    with pytest.raises(ValueError):
        Traffic.from_dict(dict(CLOSED, **bad))
