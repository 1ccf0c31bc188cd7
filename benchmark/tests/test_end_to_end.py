"""The end-to-end arithmetic, the drain rule first."""

import pytest

from benchmark import end_to_end


def _run(requests, start=100.0):
    return {"requests": requests, "window": {"start": start,
            "start_epoch": 1000.0 + start}, "t0_epoch": 1000.0}


def _prove(t_send, t_done, valid=True, **kw):
    return dict(kind="prove", t_send=t_send, t_done=t_done, valid=valid, **kw)


def test_drain_rule_counts_what_was_in_flight_and_divides_to_the_last():
    # a 10 s window from t = 100; the last request was sent at 109.5 and
    # answered at 112: it counts, and the divisor runs to 112
    reqs = [_prove(100 + 2 * i, 102 + 2 * i) for i in range(5)]
    reqs.append(_prove(109.5, 112.0))
    assert end_to_end.proofs_per_s(_run(reqs)) == pytest.approx(6 / 12.0)


def test_invalid_answers_do_not_count_but_their_time_does():
    reqs = [_prove(100, 101), _prove(101, 103, valid=False)]
    assert end_to_end.proofs_per_s(_run(reqs)) == pytest.approx(1 / 3.0)
    assert end_to_end.proof_p50_s(_run(reqs)) == pytest.approx(1.0)


def test_median_and_open_loop_clock():
    reqs = [_prove(100, 101), _prove(101, 103), _prove(103, 106)]
    assert end_to_end.proof_p50_s(_run(reqs)) == pytest.approx(2.0)
    # an open loop's request is timed from when it was due
    late = [_prove(100.5, 101.0, t_due=100.0)]
    assert end_to_end.proof_p50_s(_run(late)) == pytest.approx(1.0)


def test_kinds_are_kept_apart_and_absent_kinds_report_nothing():
    reqs = [_prove(100, 101),
            dict(kind="verify", t_send=100, t_done=100.5, valid=True)]
    run = _run(reqs)
    assert end_to_end.verifies_per_s(run) == pytest.approx(2.0)
    assert end_to_end.proofs_per_s(run) == pytest.approx(1.0)
    assert end_to_end.verifies_per_s(_run(reqs[:1])) is None


def test_setup_runs_from_process_start_to_the_first_request():
    assert end_to_end.setup_s(_run([], start=93.25)) == pytest.approx(93.25)
