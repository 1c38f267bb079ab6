"""The catch-up cell's chain (PR 33): how fast a sync it outlasts, by
arithmetic over the cell's own files, and that the failure names that
rate; that the prefix set-up makes in process for pre-warm is the
child's chain, height for height, and that set-up fails where it is
not; and the order of set-up's laps, which is what lets the child hide
under both cold shapes."""
import asyncio
import types

import pytest

from _bench_util import ROOT, manifest
from benchmark.lib import loader
from benchmark.reference import chain as chainlib
from test_benchmark_harness import compiles, run_ctx, tiny_ctx  # noqa: F401

BENCH = loader.Bench(ROOT)
CELL = "qa-175.catchup"
catchup = BENCH.traffic("catchup")
# the heights warm-up consumes before the window opens (66-70 in every
# run of PR 32)
WARMUP_HEIGHTS = 70


def test_the_chain_outlasts_a_sync_of_33_heights_a_second():
    cell, seconds = BENCH.cell(CELL), manifest()["run_seconds"]
    heights, margin = cell.param("chain_heights"), \
        cell.param("chain_margin")
    rate = catchup.ceiling(heights, margin, WARMUP_HEIGHTS, seconds)
    assert rate == (heights - margin - WARMUP_HEIGHTS) / seconds >= 33
    # the cut is still a cut
    assert "chain_heights" in BENCH.config("qa-175")["reduced"]
    # pre-warm can go quiet inside its prefix, and the forged block
    # lies inside warm-up
    assert catchup.PREFIX_HEIGHTS > cell.param("prewarm_ops") \
        + cell.param("quiet_ops", 32)
    assert cell.param("forged_height") < cell.param("warmup_ops")


def state_at(height, window_from, caught_up=False):
    return types.SimpleNamespace(
        chain=types.SimpleNamespace(height=2000),
        dst_store=types.SimpleNamespace(height=height), margin=40,
        seconds=50.0, window_from=window_from,
        done=types.SimpleNamespace(is_set=lambda: caught_up))


@pytest.mark.parametrize("height,window_from,caught_up,says", [
    (1960, 70, False, None),        # the margin itself is left
    (1961, 70, False, "a 50 s window opened at height 70 holds at most "
                      "37.8 heights/s"),
    (1700, 66, True, "holds at most 37.9 heights/s"),
    (1990, None, False, "the window had not opened"),
], ids=["margin_left", "inside_margin", "caught_up", "in_warm_up"])
def test_the_failure_names_the_rate_the_chain_held(
        height, window_from, caught_up, says):
    state = state_at(height, window_from, caught_up)
    if says is None:
        catchup._must_have_chain_left(state)
        return
    with pytest.raises(RuntimeError) as e:
        catchup._must_have_chain_left(state)
    assert says in str(e.value)
    assert f"node at height {height} of 2000 (margin 40)" in str(e.value)
    assert str(e.value).endswith("lengthen chain_heights")
    if window_from is not None:
        assert f"{catchup.ceiling(2000, 40, window_from, 50.0):.1f} " \
            in str(e.value)


@pytest.fixture(scope="module")
def chains(tmp_path_factory):
    """(the child's chain, a prefix of it made in this process), at the
    rehearsal's size."""
    r = BENCH.cell(CELL).params["rehearsal"]
    kw = dict(chain_id=catchup.CHAIN_ID, seed=11,
              n_validators=r["validators"], power=10,
              heights=r["chain_heights"],
              txs_per_block=r["txs_per_block"], tx_bytes=1024)
    out = str(tmp_path_factory.mktemp("chain") / "chain.pickle")
    child = chainlib.start_child(out, **kw)
    try:
        prefix = asyncio.run(chainlib.fabricate(**dict(kw, heights=24)))
        chain = chainlib.load_child(child, out, **kw)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
    return chain, prefix


def test_the_prefix_is_the_childs_first_heights(chains):
    chain, prefix = chains
    assert (chain.height, prefix.height) == (80, 24)
    catchup.must_extend(prefix, chain)
    for h in range(1, prefix.height + 1):
        assert prefix.block_hash[h] == chain.block_hash[h]
        assert prefix.app_hash[h] == chain.app_hash[h]
        ours, theirs = prefix.block_store.load_block(h), \
            chain.block_store.load_block(h)
        assert ours.last_commit.to_proto() == theirs.last_commit.to_proto()
        assert ours.hash() == theirs.hash() == chain.block_hash[h]
    assert [v.address for v in prefix.vset.validators] == \
        [v.address for v in chain.vset.validators]


@pytest.mark.parametrize("field", ["block_hash", "app_hash"])
def test_a_chain_that_leaves_the_prefix_is_refused(chains, field):
    chain, prefix = chains
    other = types.SimpleNamespace(
        block_hash=dict(chain.block_hash), app_hash=dict(chain.app_hash))
    getattr(other, field)[17] = b"\x00" * 32
    with pytest.raises(RuntimeError, match="at height 17"):
        catchup.must_extend(prefix, other)
    del getattr(other, field)[17]
    with pytest.raises(RuntimeError, match="at height 17"):
        catchup.must_extend(prefix, other)


def test_set_up_fails_where_the_child_made_another_chain(
        compiles, tmp_path, monkeypatch):  # noqa: F811
    ctx, driver = tiny_ctx(CELL, False, 0.3, compiles, tmp_path)
    load = chainlib.load_child

    def another(*a, **kw):
        chain = load(*a, **kw)
        chain.app_hash[9] = chain.app_hash[8]
        return chain

    monkeypatch.setattr(driver.chainlib, "load_child", another)
    with pytest.raises(RuntimeError, match="differs from the prefix "
                                           "pre-warm verified at height 9"):
        asyncio.run(driver.set_up(ctx))
    # pre-warm had run, over the prefix, before the child was waited for
    assert list(ctx.laps)[-2:] == ["warm_device_path", "prewarm"]


def test_set_up_waits_for_the_child_last(compiles, tmp_path):  # noqa: F811
    """warm_device_path (with the prefix), pre-warm over the prefix,
    and only then the child's chain: the ``chain`` lap, which
    chain_build_s reads, is what fabrication adds to setup_s."""
    ctx, driver = tiny_ctx(CELL, True, 0.3, compiles, tmp_path)
    result, _ = run_ctx(ctx, driver)
    laps = list(ctx.laps)
    assert laps[laps.index("warm_device_path"):][:3] == [
        "warm_device_path", "prewarm", "chain"]
    assert result["metrics"]["chain_build_s"]["value"] == \
        ctx.laps["chain"]
    assert result["failed"] == 0 and result["attempted"] > 0
