"""Commit verification through the TILED device path, verdict by
verdict, with a golden kernel at the wire (tests/wire_golden.py) in
place of the compiled ones: walk -> BatchVerifier seam -> verify_batch
-> _verify_pipelined (prep_arrays, tiles, pre_bad, mask assembly) ->
the index a refusal names.

verify_commit_light is held to the plain reference of configuration
valset-10k (benchmark/reference/commit_light.py), verify_commit to its
strict twin below, on sets of 150 and 200 validators at a 64-lane
tile (two to four tiles a commit).  One more test pins the plan at
the real size, 10,000 validators, without a kernel.
"""
import functools
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import wire_golden

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import commit_light, fixtures  # noqa: E402
from cometbft_tpu.crypto import _ed25519_ref as ref  # noqa: E402
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.crypto.pipeline import tile_plan  # noqa: E402
from cometbft_tpu.ops import ed25519_jax as ej  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402
from cometbft_tpu.types.validator_set import (  # noqa: E402
    Validator, ValidatorSet,
)

CHAIN_ID = "tiled-commit"
TILE = 64
KERNELS = ("_jit_verify_packed", "_pallas_verify_packed")
_ref_verify = functools.lru_cache(maxsize=None)(ref.verify)


@pytest.fixture
def device_path(monkeypatch):
    """The seam's device path on this CPU: backend ``tpu``, 64-lane
    tiles, one device, the golden kernel.  Yields the lane counts of
    the dispatches made."""
    dispatched = []

    def golden(wire, **static):
        dispatched.append(wire.shape[0])
        return jnp.asarray(wire_golden.verify_wire(wire))

    monkeypatch.setenv("COMETBFT_TPU_VERIFY_TILE", str(TILE))
    monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
    # conftest's eight virtual devices would send a tile to the mesh
    # partitioner: one chip is what the cell runs on
    monkeypatch.setenv("COMETBFT_TPU_SHARD_MIN", "1000000")
    for name in KERNELS:
        monkeypatch.setattr(ej, name, golden)
    monkeypatch.setattr(ej, "_SEEN_SHAPES", set(ej._SEEN_SHAPES))
    monkeypatch.setattr(ref, "verify", _ref_verify)
    monkeypatch.setattr(crypto_batch, "_backend", "tpu")
    crypto_batch.reset_tpu_breaker()
    yield dispatched
    assert crypto_batch.tpu_breaker().state == "closed"
    crypto_batch.reset_tpu_breaker()


@functools.lru_cache(maxsize=None)
def signed_set(n: int, powers: str):
    """(vset, block_id, height, an honest commit's signatures, the
    commit: the test spoils and restores its signatures)."""
    privs = fixtures.seeded_privs(n, 27, tag=f"tiled-{powers}")
    power = {"equal": lambda i: 10,
             "unequal": lambda i: 1 + i * 7 % 13}[powers]
    vset = ValidatorSet([Validator.new(p.pub_key(), power(i))
                         for i, p in enumerate(privs)])
    by_addr = {p.pub_key().address(): p for p in privs}
    height = 5
    bid = fixtures.seeded_block_id(27, height)
    commit = fixtures.signed_commit(
        CHAIN_ID, vset, [by_addr[v.address] for v in vset.validators],
        height, bid)
    return vset, bid, height, tuple(
        cs.signature for cs in commit.signatures), commit


def forged(sig: bytes) -> bytes:
    return bytes([sig[0] ^ 1]) + sig[1:]


def s_plus_l(sig: bytes) -> bytes:
    """The same signature with a non-canonical S: host prep refuses it
    (pre_bad) and sends a padding lane in its place."""
    s = int.from_bytes(sig[32:], "little") + ref.L
    return sig[:32] + s.to_bytes(32, "little")


def light_stop(vset) -> int:
    """How many signatures a light verification takes."""
    needed, tallied = vset.total_voting_power() * 2 // 3, 0
    for i, v in enumerate(vset.validators):
        tallied += v.voting_power
        if tallied > needed:
            return i + 1
    raise AssertionError("a full commit has the power")


def verify_strict(lanes, total_power: int) -> tuple:
    """commit_light.verify_light's strict twin (upstream's
    VerifyCommit): every signature is verified."""
    tallied = sum(power for _, _, _, power in lanes)
    if tallied <= total_power * 2 // 3:
        return commit_light.NOT_ENOUGH_POWER, tallied
    for i, (pub, msg, sig, _) in enumerate(lanes):
        if not ref.verify(pub, msg, sig):
            return commit_light.WRONG_SIGNATURE, i
    return commit_light.ACCEPTED, None


# case -> (lane indices to spoil, how), given the tile plan of the
# lanes a verification takes and the light verification's stop
CASES = {
    "honest": lambda plan, taken, stop: ([], forged),
    "forged_lane_0": lambda plan, taken, stop: ([0], forged),
    "forged_last_lane_of_tile_0":
        lambda plan, taken, stop: ([plan[0][1] - 1], forged),
    "forged_first_lane_of_tile_1":
        lambda plan, taken, stop: ([plan[1][0]], forged),
    "forged_last_verified_lane":
        lambda plan, taken, stop: ([taken - 1], forged),
    "forged_one_past_the_light_stop":
        lambda plan, taken, stop: ([stop], forged),
    "two_forged_in_different_tiles":
        lambda plan, taken, stop: ([plan[-1][0] + 3, plan[0][0] + 7],
                                   forged),
    "s_not_below_l_last_lane_of_tile_0":
        lambda plan, taken, stop: ([plan[0][1] - 1], s_plus_l),
    "s_not_below_l_first_lane_of_tile_1":
        lambda plan, taken, stop: ([plan[1][0]], s_plus_l),
}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("function", ["verify_commit_light",
                                      "verify_commit"])
@pytest.mark.parametrize("powers", ["equal", "unequal"])
@pytest.mark.parametrize("n", [150, 200])
def test_verdict_is_the_references(device_path, n, powers, function,
                                   case):
    vset, bid, height, sigs, commit = signed_set(n, powers)
    light = function == "verify_commit_light"
    stop = light_stop(vset)
    taken = stop if light else n
    plan = tile_plan(taken, TILE)
    assert len(plan) >= 2 and stop < n
    spoiled, how = CASES[case](plan, taken, stop)
    for cs, sig in zip(commit.signatures, sigs):
        cs.signature = sig
    for i in spoiled:
        commit.signatures[i].signature = how(sigs[i])
    commit.__dict__.pop("_vsb_tmpls", None)

    lanes = [(v.pub_key.bytes(), commit.vote_sign_bytes(CHAIN_ID, i),
              commit.signatures[i].signature, v.voting_power)
             for i, v in enumerate(vset.validators)]
    total = vset.total_voting_power()
    want = commit_light.verify_light(lanes, total) if light \
        else verify_strict(lanes, total)
    # the reference's answer is the one the case was built to have
    seen = [i for i in spoiled if i < taken]
    assert want == ((commit_light.WRONG_SIGNATURE, min(seen)) if seen
                    else (commit_light.ACCEPTED, None))

    try:
        getattr(validation, function)(CHAIN_ID, vset, bid, height,
                                      commit)
        got = (commit_light.ACCEPTED, None)
    except validation.NotEnoughVotingPowerError as e:
        got = (commit_light.NOT_ENOUGH_POWER, e.got)
    except validation.VerificationError as e:
        head = str(e).split(":")[0]
        assert head.startswith("wrong signature (#"), str(e)
        got = (commit_light.WRONG_SIGNATURE,
               int(head[len("wrong signature (#"):-1]))
    assert got == want
    # through the tiled device path, and no lane past the stop sent
    assert device_path == [TILE] * len(plan)


def test_the_reference_judges_power_before_signatures():
    vset, _, _, sigs, commit = signed_set(150, "equal")
    lanes = [(v.pub_key.bytes(), commit.vote_sign_bytes(CHAIN_ID, i),
              forged(sigs[i]), v.voting_power)
             for i, v in enumerate(vset.validators)][:100]
    assert commit_light.verify_light(lanes, 1500) == (
        commit_light.NOT_ENOUGH_POWER, 1000)
    assert commit_light.verify_light(lanes, 1499) == (
        commit_light.WRONG_SIGNATURE, 0)
    assert commit_light.verify_light([], 0) == (
        commit_light.NOT_ENOUGH_POWER, 0)


def test_golden_kernel_agrees_with_the_golden_model_on_edge_lanes():
    """The wire kernel against _ed25519_ref.verify where prep sends
    the lane, on forged and ZIP-215 edge lanes (small-order A and R,
    non-canonical y, S = 0), and true on padding and refused lanes."""
    import random
    from benchmark.reference import golden
    vset, _, _, sigs, commit = signed_set(150, "equal")
    honest = [(v.pub_key.bytes(), commit.vote_sign_bytes(CHAIN_ID, i),
               sigs[i]) for i, v in enumerate(vset.validators)][:8]
    lanes = honest + golden.edge_lanes(random.Random(27), honest)
    wire, pre_bad = ej.prep_arrays(lanes, 64)
    mask = wire_golden.verify_wire(wire)
    want = [_ref_verify(*lane) for lane in lanes]
    assert True in want[8:] and False in want[8:]
    for i, lane in enumerate(lanes):
        assert (mask[i] and not pre_bad[i]) == want[i], i
    assert pre_bad[:len(lanes)].any()       # S + L was among them
    assert mask[pre_bad].all() and mask[len(lanes):].all()
    with pytest.raises(ValueError):
        wire_golden.verify_wire(np.zeros((4, 191), np.uint8))


def test_the_plan_at_10000_validators(monkeypatch):
    """valset-10k: a light verification takes 6,667 signatures, planned
    as two balanced tiles that both pad to the 4,096 bucket, and
    warm-up warms that one shape."""
    monkeypatch.delenv("COMETBFT_TPU_VERIFY_TILE", raising=False)
    monkeypatch.setenv("COMETBFT_TPU_SHARD_MIN", "1000000")
    assert 10000 * 10 * 2 // 3 // 10 + 1 == 6667
    plan = tile_plan(6667, 4096)
    assert plan == [(0, 3334), (3334, 6667)]
    for kernel in ("pallas", "xla"):
        assert [ej._padded(hi - lo, kernel) for lo, hi in plan] == \
            [4096, 4096]
    warmed = []
    for name in KERNELS:
        monkeypatch.setattr(
            ej, name, lambda wire, **static: warmed.append(wire.shape)
            or jnp.ones(wire.shape[0], dtype=bool))
    monkeypatch.setenv("COMETBFT_TPU_KERNEL", "pallas")
    ej._warmup_bucket.cache_clear()
    try:
        ej.warmup(6667)
    finally:
        ej._warmup_bucket.cache_clear()
    assert warmed == [(4096, ej.WIRE_LANE_BYTES)]
