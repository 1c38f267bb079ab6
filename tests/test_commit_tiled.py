"""Commit verification through the TILED device path, verdict by
verdict, with a golden kernel at the wire (tests/wire_golden.py) in
place of the compiled ones: walk (the native walk since PR 38, a
tile at a time into extend_raw()) -> BatchVerifier seam, whose add()
and extend_raw() hand a full tile to ops TilePipeline the moment it
has one (the
tile's host prep begins on the native prep thread; the tile handed
over before it is launched) -> tiles, pre_bad, mask assembly -> the
index a refusal names.

verify_commit_light is held to the plain reference of configuration
valset-10k (benchmark/reference/commit_light.py), verify_commit to its
strict twin below, on sets of 150 and 200 validators at a 64-lane
tile (two to four tiles a commit), and at the edges of the streamed
path: a batch of exactly one tile, one more, exactly two, seven with
a short last one; masks in feed order; a verifier dropped with a prep
in flight and with a tile in flight; a kernel that fails under add()
and under verify(); an open breaker; verify_async().  One more test
pins the plan at the real size, 10,000 validators, without a kernel.
"""
import asyncio
import dataclasses
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
from cometbft_tpu.crypto import pipeline as crypto_pipeline  # noqa: E402
from cometbft_tpu.crypto.pipeline import tile_plan  # noqa: E402
from cometbft_tpu.libs import tracing  # noqa: E402
from cometbft_tpu.ops import ed25519_jax as ej  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402
from cometbft_tpu.types.commit import Commit, CommitSig  # noqa: E402
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

    monkeypatch.setattr(crypto_pipeline, "TILE", TILE)
    monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
    # conftest's eight virtual devices would send a tile to the mesh
    # partitioner: one chip is what the cell runs on
    monkeypatch.setattr(ej, "SHARD_MIN", 1000000)
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


def verdict(function, vset, bid, height, commit):
    """(verdict, index or tally) as commit_light's references give."""
    try:
        getattr(validation, function)(CHAIN_ID, vset, bid, height,
                                      commit)
        return commit_light.ACCEPTED, None
    except validation.NotEnoughVotingPowerError as e:
        return commit_light.NOT_ENOUGH_POWER, e.got
    except validation.VerificationError as e:
        head = str(e).split(":")[0]
        assert head.startswith("wrong signature (#"), str(e)
        return (commit_light.WRONG_SIGNATURE,
                int(head[len("wrong signature (#"):-1]))


def variant(n: int, changed=None):
    """(vset, block id, height, a fresh copy of signed_set(n)'s honest
    commit with the CommitSigs of ``changed`` {index: f(CommitSig)}
    replaced)."""
    vset, bid, height, sigs, commit = signed_set(n, "equal")
    css = [dataclasses.replace(cs, signature=sig)
           for cs, sig in zip(commit.signatures, sigs)]
    for i, change in (changed or {}).items():
        css[i] = change(css[i])
    return vset, bid, height, Commit(
        height=commit.height, round=commit.round,
        block_id=commit.block_id, signatures=css)


def resigned(how):
    return lambda cs: dataclasses.replace(cs,
                                          signature=how(cs.signature))


@pytest.fixture
def recorder(tmp_path):
    old = tracing.set_recorder(
        tracing.Recorder(buffer_size=65536, dump_dir=str(tmp_path)))
    yield tracing.recorder()
    tracing.set_recorder(old)


@pytest.fixture
def adds(monkeypatch):
    """Counts the items handed to the seam's verifier, by add() or by
    the native walk's extend_raw(), each counted before it is taken:
    [n]."""
    count = [0]
    verifier = crypto_batch.GuardedTpuBatchVerifier
    add, extend_raw = verifier.add, verifier.extend_raw

    def counted(self, *item):
        count[0] += 1
        return add(self, *item)

    def counted_raw(self, triples):
        count[0] += len(triples)
        return extend_raw(self, triples)

    monkeypatch.setattr(verifier, "add", counted)
    monkeypatch.setattr(verifier, "extend_raw", counted_raw)
    return count


# where a forged signature sits, given the batch and its tile count;
# the middle tile of one or two tiles is the first
SPOILED = {
    "honest": lambda n, tiles: None,
    "first_lane": lambda n, tiles: 0,
    "last_lane": lambda n, tiles: n - 1,
    "first_lane_of_a_middle_tile":
        lambda n, tiles: (tiles // 2) * TILE,
    "last_lane_of_a_middle_tile":
        lambda n, tiles: min(n, (tiles // 2 + 1) * TILE) - 1,
}


@pytest.mark.parametrize("spoiled", SPOILED)
@pytest.mark.parametrize("n,tiles", [(TILE, 1), (TILE + 1, 2),
                                     (2 * TILE, 2),
                                     (6 * TILE + 33, 7)])
def test_a_batch_at_the_edge_of_a_tile(device_path, n, tiles, spoiled):
    """Exactly one tile (handed over by the last add(), launched and
    settled by verify()), one lane more (a remainder of one, at the
    tile's shape), exactly two tiles (both handed over from add(),
    empty remainder), seven with a short last one (valset-10k's
    shape: six from add(), 33 lanes from verify())."""
    at = SPOILED[spoiled](n, tiles)
    vset, bid, height, commit = variant(
        n, {} if at is None else {at: resigned(forged)})
    assert verdict("verify_commit", vset, bid, height, commit) == (
        (commit_light.ACCEPTED, None) if at is None
        else (commit_light.WRONG_SIGNATURE, at))
    assert device_path == [TILE] * tiles


def seam_verifier(vset, commit, upto=None):
    """A verifier of the seam with the commit's first ``upto``
    signatures added, as the walk adds them."""
    bv = crypto_batch.create_batch_verifier(vset.validators[0].pub_key)
    for i, v in enumerate(vset.validators[:upto]):
        bv.add(v.pub_key, commit.vote_sign_bytes(CHAIN_ID, i),
               commit.signatures[i].signature)
    return bv


def test_masks_come_back_in_feed_order(device_path):
    """Seven tiles, forged lanes at both ends of the first, a middle
    and the last tile, one refused by host prep: the mask names
    exactly them, whichever tile settled when."""
    n = 6 * TILE + 33
    bad = {0, TILE - 1, TILE, 3 * TILE + 5, 6 * TILE - 1, 6 * TILE,
           n - 1}
    changed = {i: resigned(forged) for i in bad}
    changed[2 * TILE + 9] = resigned(s_plus_l)
    vset, _, _, commit = variant(n, changed)
    bv = seam_verifier(vset, commit)
    # six tiles handed over, five of them launched, inside the adds
    assert device_path == [TILE] * 5
    ok, mask = bv.verify()
    assert not ok and mask == [i not in changed for i in range(n)]
    assert device_path == [TILE] * 7


def test_tiles_are_on_their_way_before_the_walk_ends(
        device_path, adds, monkeypatch):
    """200 validators, strict: the 64th signature handed over begins
    tile 0's prep, the 128th launches tile 0, the 192nd tile 1;
    verify() hands over the 8 left, which launches tile 2, and
    launches them.  Never another shape."""
    seen = []
    golden = ej._jit_verify_packed
    for name in KERNELS:
        monkeypatch.setattr(
            ej, name, lambda wire, **static:
            seen.append(adds[0]) or golden(wire, **static))
    vset, bid, height, commit = variant(200)
    assert verdict("verify_commit", vset, bid, height,
                   commit) == (commit_light.ACCEPTED, None)
    assert device_path == [TILE] * 4
    assert seen == [2 * TILE, 3 * TILE, 200, 200] and adds[0] == 200


# (validators, signatures the walk adds before it stops, tiles
# launched by then): a prep alone in flight, a prep and a tile
IN_FLIGHT = {"a_prep": (150, 90, 0), "a_prep_and_a_tile": (200, 130, 1)}


def dropped_cleanly(launched: int) -> None:
    """What a dropped verifier leaves: no batch_verify and no
    mask_handback; of a tile that was launched (the golden kernel
    has finished when feed() looks, so it is settled there) its
    spans, under a parent that never records; the breaker unheard."""
    names = [e["name"] for e in tracing.snapshot()]
    assert "commit_walk" in names
    assert not {"batch_verify", "mask_handback"} & set(names)
    assert names.count("kernel_execute") == launched
    assert names.count("host_prep") == launched
    br = crypto_batch.tpu_breaker()
    assert br.state == "closed" and br._failures == 0


@pytest.mark.parametrize("in_flight", IN_FLIGHT)
def test_a_short_tally_drops_what_is_in_flight(device_path, recorder,
                                               in_flight):
    """Too many absent: the walk adds its signatures (a tile's prep
    begins at every 64th, the tile before it is launched), then the
    tally is short.  NotEnoughVotingPowerError comes before any mask
    is read; the prep in flight is taken back or left to finish, the
    breaker hears nothing; the next verification, on a verifier of
    its own, is right."""
    n, present, launched = IN_FLIGHT[in_flight]
    absent = {i: lambda cs: CommitSig.absent()
              for i in range(present, n)}
    vset, bid, height, commit = variant(n, absent)
    assert verdict("verify_commit", vset, bid, height, commit) == (
        commit_light.NOT_ENOUGH_POWER, present * 10)
    assert device_path == [TILE] * launched
    dropped_cleanly(launched)

    tracing.clear()
    vset, bid, height, commit = variant(150, {70: resigned(forged)})
    assert verdict("verify_commit", vset, bid, height, commit) == (
        commit_light.WRONG_SIGNATURE, 70)
    assert device_path == [TILE] * (launched + 3)
    (seam,) = [e for e in tracing.snapshot()
               if e["name"] == "batch_verify"]
    assert seam["attrs"] == {"backend": "tpu", "batch": 150}


@pytest.mark.parametrize("in_flight", IN_FLIGHT)
def test_a_walk_that_raises_drops_what_is_in_flight(
        device_path, recorder, in_flight):
    """A signature of 63 bytes: the walk refuses it and raises as it
    always did, what was handed over by then is dropped."""
    n, at, launched = IN_FLIGHT[in_flight]
    vset, bid, height, commit = variant(
        n, {at: resigned(lambda sig: sig[:63])})
    assert verdict("verify_commit", vset, bid, height, commit) == (
        commit_light.WRONG_SIGNATURE, at)
    assert device_path == [TILE] * launched
    dropped_cleanly(launched)
    vset, bid, height, commit = variant(150)
    assert verdict("verify_commit", vset, bid, height, commit) == (
        commit_light.ACCEPTED, None)


@pytest.mark.parametrize("explodes_at", [0, 1, 2, 3])
def test_a_kernel_that_raises_sends_the_whole_batch_to_the_cpu(
        device_path, recorder, monkeypatch, crypto_log, explodes_at):
    """200 signatures, four tiles: the kernel fails under tile 0
    (launched by the 128th add()), under tile 1 (by the 192nd), under
    tile 2 (by verify()'s hand-over of the remainder) or under the
    remainder itself: each time the failure is recorded once against
    the breaker (latched: no transient shape), logged, and the WHOLE
    batch of 200 is judged by the CPU verifier, from the bytes the
    seam kept, with fallback=True: same verdict, same index."""
    golden = ej._jit_verify_packed
    calls = []

    def exploding(wire, **static):
        calls.append(wire.shape[0])
        if len(calls) == explodes_at + 1:
            raise RuntimeError("Mosaic lowering failed on this tile")
        return golden(wire, **static)

    for name in KERNELS:
        monkeypatch.setattr(ej, name, exploding)
    vset, bid, height, commit = variant(200, {140: resigned(forged)})
    try:
        assert verdict("verify_commit", vset, bid, height, commit) \
            == (commit_light.WRONG_SIGNATURE, 140)
        assert len(calls) == explodes_at + 1
        assert crypto_batch.tpu_breaker().state == "latched_open"
        seams = [e["attrs"] for e in tracing.snapshot()
                 if e["name"] == "batch_verify"]
        # the device's span ends where it failed, with what had
        # been added by then
        assert seams == [
            {"backend": "tpu", "batch": (2 * TILE, 3 * TILE, 200, 200)[
                explodes_at], "error": "RuntimeError"},
            {"backend": "cpu", "batch": 200, "fallback": True}]
        errors = [r for r in crypto_log if r.levelname == "ERROR"]
        assert len(errors) == 1
        assert "Mosaic lowering failed" in errors[0].getMessage()
        # the breaker is open: the next batch dispatches nothing
        vset, bid, height, commit = variant(150)
        assert verdict("verify_commit", vset, bid, height, commit) \
            == (commit_light.ACCEPTED, None)
        assert len(calls) == explodes_at + 1
    finally:
        crypto_batch.reset_tpu_breaker()


def test_an_open_breaker_means_no_dispatch_from_add(device_path,
                                                    recorder):
    crypto_batch.tpu_breaker().record_failure(latch=True)
    try:
        vset, bid, height, commit = variant(150, {5: resigned(forged)})
        assert verdict("verify_commit", vset, bid, height, commit) == (
            commit_light.WRONG_SIGNATURE, 5)
        assert device_path == []
        (seam,) = [e["attrs"] for e in tracing.snapshot()
                   if e["name"] == "batch_verify"]
        assert seam == {"backend": "cpu", "batch": 150,
                        "fallback": False}
    finally:
        crypto_batch.reset_tpu_breaker()


def test_verify_async_settles_what_add_dispatched(device_path):
    """add() on the event loop's thread hands over two tiles and
    launches the first; the staging worker that runs verify()
    launches the second and the remainder and settles all."""
    from cometbft_tpu.crypto import pipeline
    vset, _, _, commit = variant(150, {130: resigned(forged)})

    async def go():
        bv = seam_verifier(vset, commit)
        assert device_path == [TILE]
        return await bv.verify_async()

    try:
        ok, mask = asyncio.run(go())
    finally:
        pipeline.reset_workers()
    assert not ok and mask == [i != 130 for i in range(150)]
    assert device_path == [TILE] * 3


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
    """valset-10k: a light verification takes 6,667 signatures.  The
    seam's verifier streams them as six tiles of 1,024 (handed over
    from add()) + 523 (by verify()); verify_batch, handed the whole
    list, plans seven balanced tiles.  Either way every chunk
    dispatches at the 1,024 bucket, the pipeline's one shape: 7,168
    lanes for 6,667, and warm-up warms that one shape.  The CPU
    verifier's MSM keeps its 4,096."""
    monkeypatch.setattr(ej, "SHARD_MIN", 1000000)
    assert 10000 * 10 * 2 // 3 // 10 + 1 == 6667
    assert (crypto_pipeline.TILE, crypto_pipeline.MSM_TILE) == \
        (1024, 4096)
    plan = tile_plan(6667, 1024)
    assert plan == [(lo, min(lo + 953, 6667))
                    for lo in range(0, 6667, 953)] and len(plan) == 7
    bv = crypto_batch.GuardedTpuBatchVerifier()
    assert (bv._tile, bv._feed_at) == (1024, 1024)
    assert divmod(6667, bv._tile) == (6, 523)
    for kernel in ("pallas", "xla"):
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", kernel)
        assert ej.TilePipeline(bv._tile)._m == 1024
        assert [ej._padded(hi - lo, kernel) for lo, hi in plan] == \
            [1024] * 7
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
    assert warmed == [(1024, ej.WIRE_LANE_BYTES)]
