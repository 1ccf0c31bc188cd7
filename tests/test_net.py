"""Net-layer tests: the sum-of-ids smoke test (mpc-net/examples/add_ids.rs)
plus collective semantics and channel independence."""

import asyncio

import pytest

from distributed_groth16_tpu.parallel.net import (
    CHANNELS,
    MpcNetError,
    make_local_nets,
    simulate_network_round,
)


def test_sum_of_ids():
    """Every party contributes its id; king sums and broadcasts — the
    reference's prod smoke test (add_ids.rs)."""

    async def party(net, _):
        def f(vals):
            return [sum(vals)] * net.n_parties

        return await net.king_compute(net.party_id, f)

    out = simulate_network_round(8, party)
    assert out == [sum(range(8))] * 8


def test_gather_ordering_and_king_inclusion():
    async def party(net, data):
        got = await net.gather_to_king(data)
        if net.is_king:
            assert got == [f"p{i}" for i in range(net.n_parties)]
            return "king-saw-all"
        assert got is None
        return "client"

    out = simulate_network_round(
        4, party, [f"p{i}" for i in range(4)]
    )
    assert out[0] == "king-saw-all"


def test_scatter_from_king():
    async def party(net, _):
        vals = [i * 10 for i in range(net.n_parties)] if net.is_king else None
        return await net.scatter_from_king(vals)

    assert simulate_network_round(4, party) == [0, 10, 20, 30]


def test_scatter_validates_length():
    async def party(net, _):
        if net.is_king:
            with pytest.raises(MpcNetError):
                await net.scatter_from_king([1, 2])  # wrong length
            # then run a correct scatter so clients unblock
            return await net.scatter_from_king(list(range(net.n_parties)))
        return await net.scatter_from_king(None)

    assert simulate_network_round(3, party) == [0, 1, 2]


def test_channels_are_independent():
    """Two concurrent collectives on different sids don't interleave."""

    async def party(net, _):
        async def round_on(sid, tag):
            def f(vals):
                assert all(v[0] == tag for v in vals)
                return [(tag, sum(v[1] for v in vals))] * net.n_parties

            return await net.king_compute((tag, net.party_id), f, sid=sid)

        a, b = await asyncio.gather(
            round_on(0, "a"), round_on(2, "b")
        )
        return a, b

    out = simulate_network_round(4, party)
    assert all(o == (("a", 6), ("b", 6)) for o in out)


def test_fabric_shape():
    nets = make_local_nets(3)
    assert [n.party_id for n in nets] == [0, 1, 2]
    assert nets[0].is_king and not nets[1].is_king
    assert len(nets[0]._fabric) == 3 * 2 * CHANNELS


def test_rendezvous_runs_the_work_once_and_hands_each_party_its_row():
    """The in-process star's rendezvous: one call of `f` for the n
    parties' values in party order, party j gets row j; on one sid the
    k-th call of every party is one meeting, and two sids meet apart."""
    calls = []

    def f(vals):
        calls.append(list(vals))
        return [v * 10 for v in vals]

    async def party(net, _):
        assert net.rendezvous is not None
        a, b = await asyncio.gather(
            net.batch_local(net.party_id, f, sid=0),
            net.batch_local(100 + net.party_id, f, sid=1),
        )
        c = await net.batch_local(200 + net.party_id, f, sid=0)
        return a, b, c

    out = simulate_network_round(4, party)
    assert out == [(10 * j, 10 * (100 + j), 10 * (200 + j)) for j in range(4)]
    assert sorted(calls) == [
        [0, 1, 2, 3], [100, 101, 102, 103], [200, 201, 202, 203]
    ]


def test_rendezvous_failure_reaches_every_party():
    """An exception of the work is every party's: none waits on."""

    def f(vals):
        raise ValueError("the batched work failed")

    async def party(net, _):
        with pytest.raises(ValueError):
            await net.batch_local(net.party_id, f)
        return "raised"

    assert simulate_network_round(3, party) == ["raised"] * 3


def test_a_net_made_alone_offers_no_rendezvous():
    from distributed_groth16_tpu.parallel.net import LocalSimNet

    nets = make_local_nets(2)
    assert nets[0].rendezvous is nets[1].rendezvous
    assert LocalSimNet(0, 2, nets[0]._fabric).rendezvous is None
