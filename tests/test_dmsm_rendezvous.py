"""The star round's `d_msm`s through the in-process rendezvous: every
party's local MSM of one `d_msm` is a row of ONE launch of the batched
tree program (forced on XLA:CPU by DG16_FORCE_TREE_MSM, as the chip takes
it by itself), and the proof is the single-node prover's byte for byte.

One round is proved once for the file (`proved`): its G1 and G2 batched
tree programs cost minutes of XLA:CPU compile, and the fault test's
rounds reuse them, so that no deadline there runs while a program
compiles."""

import asyncio
import time

import pytest

from distributed_groth16_tpu.frontend.ark_serde import proof_to_bytes
from distributed_groth16_tpu.frontend.r1cs import ConstraintSystem
from distributed_groth16_tpu.models.groth16 import (
    CompiledR1CS,
    distributed_prove_party,
    pack_from_witness,
    pack_proving_key,
    reassemble_proof,
    setup,
)
from distributed_groth16_tpu.models.groth16.prove import prove_single
from distributed_groth16_tpu.ops.field import fr
from distributed_groth16_tpu.parallel.net import (
    MpcTimeoutError,
    run_round_with_retries,
)
from distributed_groth16_tpu.parallel.pss import PackedSharingParams
from distributed_groth16_tpu.telemetry import metrics
from distributed_groth16_tpu.utils.config import NetConfig

# l = 1: four parties, so a d_msm's launch carries four rows
L = 1
# the fault test's deadline: every program is compiled by then, so the
# round's ops take well under a second on XLA:CPU
DEADLINE = NetConfig(op_timeout_s=10.0, heartbeat_interval_s=0.0)


def _counts() -> dict:
    reg = metrics.registry()
    routes = {k: c.value for k, c in reg.family("kernel_route_total").items()}
    local = {k: c.value for k, c in reg.family("dmsm_local_msm_total").items()}
    return {
        "msm/tree": routes.get(("msm", "tree"), 0),
        "msm_batched/tree": routes.get(("msm_batched", "tree"), 0),
        "batched": local.get(("batched",), 0),
        "alone": local.get(("alone",), 0),
    }


def _squares(length: int = 7):
    """x -> x^2 from a witness x0, no public input but the constant: 8
    witness wires over a domain of 8, so that the A, W and U shares of
    l = 1 are all 8 long and share one G1 program."""
    cs = ConstraintSystem()
    x = 7
    prev = cs.new_witness(x)
    for _ in range(length):
        x = x * x % fr().p
        nxt = cs.new_witness(x)
        cs.enforce([(1, prev)], [(1, prev)], [(1, nxt)])
        prev = nxt
    return cs.finish()


@pytest.fixture(scope="module")
def world():
    r1cs, z = _squares()
    pp = PackedSharingParams(L)
    pk = setup(r1cs)
    z_mont = fr().encode(z)
    qap = CompiledR1CS(r1cs).qap(z_mont)
    ni = r1cs.num_instance
    # packed here, off the tree route: only the round is forced onto it
    crs = pack_proving_key(pk, pp)
    a_sh = pack_from_witness(pp, z_mont[1:])
    ax_sh = pack_from_witness(pp, z_mont[ni:])
    qap_sh = qap.pss(pp)
    data = [(crs[i], qap_sh[i], a_sh[i], ax_sh[i]) for i in range(pp.n)]
    assert {x.shape[0] for x in (crs[0].s, crs[0].w, crs[0].u)} == {8}
    single = prove_single(pk, CompiledR1CS(r1cs), z_mont)
    return dict(pp=pp, pk=pk, data=data, single=single)


def _round(world, party=None, **kw):
    pp = world["pp"]

    async def honest(net, data):
        crs, qs, a_s, ax_s = data
        return await distributed_prove_party(pp, crs, qs, a_s, ax_s, net)

    res = run_round_with_retries(pp.n, party or honest, world["data"], **kw)
    return reassemble_proof(res[0], world["pk"])


@pytest.fixture(scope="module")
def proved(world):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DG16_FORCE_TREE_MSM", "1")
        before = _counts()
        proof = _round(world)
        after = _counts()
    return proof, {k: after[k] - before[k] for k in before}


def test_the_batched_round_proves_the_single_node_proof_byte_for_byte(
    world, proved
):
    proof, _ = proved
    assert proof_to_bytes(proof) == proof_to_bytes(world["single"])


def test_every_local_msm_is_a_row_of_one_launch_a_d_msm(world, proved):
    """r = s = 0: four d_msms (A, B, and C's W and U), each one launch of
    n rows; every row still counts as a tree MSM."""
    n = world["pp"].n
    _, moved = proved
    assert moved == {
        "msm/tree": 4 * n, "msm_batched/tree": 4, "batched": 4 * n,
        "alone": 0,
    }


def test_a_party_killed_before_the_rendezvous_costs_one_round(
    world, proved, monkeypatch
):
    """The last party dies before its first rendezvous: the others wait
    out the deadline, no longer, the round ends in MpcTimeoutError, and
    `run_round_with_retries` proves on a fresh fabric."""
    monkeypatch.setenv("DG16_FORCE_TREE_MSM", "1")
    pp = world["pp"]
    state = {"round": 0, "died": None, "ended": None, "error": None}

    async def party(net, data):
        if net.party_id == 0:
            state["round"] += 1
        if net.party_id == pp.n - 1 and state["round"] == 1:

            async def dead(*_a, **_kw):
                state["died"] = state["died"] or time.monotonic()
                await asyncio.sleep(3600)

            net.batch_local = dead
        crs, qs, a_s, ax_s = data
        return await distributed_prove_party(pp, crs, qs, a_s, ax_s, net)

    def on_retry(attempt, e):
        state["ended"], state["error"] = time.monotonic(), e

    proof = _round(
        world, party, retries=1, net_cfg=DEADLINE, on_retry=on_retry
    )
    assert state["round"] == 2
    assert isinstance(state["error"], MpcTimeoutError)
    assert state["error"].op == "batch_local"
    waited = state["ended"] - state["died"]
    assert DEADLINE.op_timeout_s - 0.05 <= waited < DEADLINE.op_timeout_s + 2
    assert proof_to_bytes(proof) == proof_to_bytes(proved[0])
