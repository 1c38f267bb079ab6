"""The tail of a batched commit verification and the interpreter's own
time on its spans (types/validation.py, ISSUE 37).

A 1,000-validator commit through ``verify_commit`` /
``verify_commit_light`` on the CPU verifier: the verdict and the error
message of each case are what they were before the release had a span
(honest, forged, short of power, everything cached); ``commit_release``
is the last child of a batched ``commit_verify``, frees the verifier
inside itself and leaves ``commit_verify`` nothing to do after it;
``commit_walk`` and ``commit_verify`` note the collections that struck
them.
"""
import functools
import gc
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import fixtures  # noqa: E402
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.libs import tracing  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402
from cometbft_tpu.types.signature_cache import SignatureCache  # noqa: E402
from cometbft_tpu.types.validator_set import (  # noqa: E402
    Validator, ValidatorSet,
)

CHAIN_ID = "release-commit"
N = 1000
HEIGHT = 9


@functools.lru_cache(maxsize=None)
def signed_set():
    privs = fixtures.seeded_privs(N, 37, tag="release")
    vset = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = fixtures.seeded_block_id(37, HEIGHT)
    commit = fixtures.signed_commit(
        CHAIN_ID, vset, [by_addr[v.address] for v in vset.validators],
        HEIGHT, bid)
    return vset, bid, commit, tuple(
        cs.signature for cs in commit.signatures)


@pytest.fixture
def commit_of_1000():
    """(vset, block id, the commit with honest signatures); whatever a
    test spoils is restored."""
    vset, bid, commit, honest = signed_set()
    yield vset, bid, commit
    for cs, sig in zip(commit.signatures, honest):
        cs.signature = sig
    for cs in commit.signatures:
        cs.block_id_flag = validation.BLOCK_ID_FLAG_COMMIT


@pytest.fixture
def recorder(tmp_path):
    old = tracing.set_recorder(tracing.Recorder(
        buffer_size=65536, dump_dir=str(tmp_path)))
    was = gc.isenabled()
    gc.disable()        # a collection is not this file's subject
    yield tracing.recorder()
    if was:
        gc.enable()
    tracing.set_recorder(old)


def forged(sig: bytes) -> bytes:
    return bytes([sig[0] ^ 1]) + sig[1:]


def _children(events, parent):
    return [e for e in events if e["parent"] == parent["id"]]


def _end(e):
    return e["ts_ns"] + e["dur_ns"]


def _one(events, name):
    found = [e for e in events if e["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


class TestVerdictsUnchanged:
    @pytest.mark.parametrize("case", [
        "honest", "forged", "short_of_power", "everything_cached"])
    @pytest.mark.parametrize("light", [False, True])
    def test_verdict_message_and_release(self, commit_of_1000,
                                         recorder, case, light):
        vset, bid, commit = commit_of_1000
        cache = None
        expected = None
        if case == "forged":
            idx = 333
            commit.signatures[idx].signature = forged(
                commit.signatures[idx].signature)
            expected = (
                validation.VerificationError,
                f"wrong signature (#{idx}): "
                f"{commit.signatures[idx].signature.hex().upper()}")
        elif case == "short_of_power":
            # 400 of 1,000 left: 4,000 of a needed 6,666
            for cs in commit.signatures[400:]:
                cs.block_id_flag = validation.BLOCK_ID_FLAG_ABSENT
                cs.signature = b""
            expected = (
                validation.NotEnoughVotingPowerError,
                "invalid commit -- insufficient voting power: got "
                "4000, needed more than 6666")
        elif case == "everything_cached":
            cache = SignatureCache()
            self._verify(light, vset, bid, commit, cache)
            tracing.clear()

        if expected is None:
            assert self._verify(light, vset, bid, commit, cache) is None
        else:
            with pytest.raises(expected[0]) as err:
                self._verify(light, vset, bid, commit, cache)
            assert str(err.value) == expected[1]
            assert type(err.value) is expected[0]

        events = tracing.snapshot()
        root = _one(events, "commit_verify")
        kids = _children(events, root)
        if case in ("honest", "forged"):
            # the last child, and the request's own end right behind
            assert [k["name"] for k in kids] == [
                "commit_walk", "batch_verify", "commit_release"]
            release = kids[-1]
            assert release["category"] == tracing.CONSENSUS
            assert release["ts_ns"] >= _end(kids[1])
            assert 0 <= _end(root) - _end(release) < 300_000
            assert root["attrs"].get("error") == (
                "VerificationError" if case == "forged" else None)
        else:
            # no batch ran: nothing to release, nothing raised by it
            assert [k["name"] for k in kids] == ["commit_walk"]
        walked = 667 if light and case != "short_of_power" else N
        assert kids[0]["attrs"]["walked"] == walked

    @staticmethod
    def _verify(light, vset, bid, commit, cache):
        fn = (validation.verify_commit_light if light
              else validation.verify_commit)
        return fn(CHAIN_ID, vset, bid, HEIGHT, commit, cache=cache)

    def test_a_forged_commit_still_caches_the_valid_ones_before_it(
            self, commit_of_1000, recorder):
        vset, bid, commit = commit_of_1000
        commit.signatures[5].signature = forged(
            commit.signatures[5].signature)
        cache = SignatureCache()
        with pytest.raises(validation.VerificationError):
            validation.verify_commit(CHAIN_ID, vset, bid, HEIGHT,
                                     commit, cache=cache)
        assert len(cache) == 5
        for cs in commit.signatures[:5]:
            assert cache.get(cs.signature) is not None


class TestRelease:
    def test_the_verifier_dies_inside_commit_release(
            self, commit_of_1000, recorder, monkeypatch):
        """What the walk gathered is freed under the span that is named
        for it, not while commit_verify's frame unwinds."""
        vset, bid, commit = commit_of_1000
        died_under = []
        make = crypto_batch.create_batch_verifier

        class Probe:
            """The verifier, with a last word."""

            def __init__(self, inner):
                self._inner = inner

            def add(self, *a):
                return self._inner.add(*a)

            def verify(self):
                return self._inner.verify()

            def __del__(self):
                sp = tracing.current()
                died_under.append(sp.name if sp is not None else None)

        monkeypatch.setattr(crypto_batch, "create_batch_verifier",
                            lambda pub: Probe(make(pub)))
        validation.verify_commit(CHAIN_ID, vset, bid, HEIGHT, commit)
        assert died_under == ["commit_release"]
        # and on the refusal path
        commit.signatures[7].signature = forged(
            commit.signatures[7].signature)
        with pytest.raises(validation.VerificationError):
            validation.verify_commit(CHAIN_ID, vset, bid, HEIGHT, commit)
        assert died_under == ["commit_release"] * 2

    def test_release_is_off_with_the_recorder(self, commit_of_1000,
                                              tmp_path):
        vset, bid, commit = commit_of_1000
        old = tracing.set_recorder(tracing.Recorder(
            enabled=False, dump_dir=str(tmp_path)))
        try:
            validation.verify_commit(CHAIN_ID, vset, bid, HEIGHT, commit)
            assert tracing.snapshot() == []
        finally:
            tracing.set_recorder(old)


class TestInterpreterTimeOnTheSpans:
    def test_walk_and_verify_note_gc_us_and_nothing_else_does(
            self, commit_of_1000, recorder):
        vset, bid, commit = commit_of_1000
        validation.verify_commit(CHAIN_ID, vset, bid, HEIGHT, commit)
        events = tracing.snapshot()
        assert _one(events, "commit_walk")["attrs"] == {
            "lookup": "index", "walked": N, "cache_hits": 0, "gc_us": 0}
        assert _one(events, "commit_verify")["attrs"] == {"gc_us": 0}
        for name in ("batch_verify", "commit_release"):
            assert "gc_us" not in (_one(events, name).get("attrs") or {})

    def test_a_collection_in_the_walk_lands_on_both_spans(
            self, commit_of_1000, recorder):
        vset, bid, commit = commit_of_1000
        struck = []

        def ignore(commit_sig):
            if not struck:
                struck.append(True)
                gc.collect()
            return False

        with validation._observe_kind("batch", HEIGHT):
            validation._verify_commit_batch(
                CHAIN_ID, vset, commit,
                vset.total_voting_power() * 2 // 3,
                ignore, lambda c: True, True, True, None)
        events = tracing.snapshot()
        pause = _one(events, "gc_pause")
        walk = _one(events, "commit_walk")
        root = _one(events, "commit_verify")
        assert pause["parent"] == walk["id"]
        assert pause["height"] == HEIGHT
        assert pause["attrs"]["generation"] == 2
        assert walk["attrs"]["gc_us"] == pause["dur_ns"] // 1000
        assert root["attrs"]["gc_us"] == pause["dur_ns"] // 1000
