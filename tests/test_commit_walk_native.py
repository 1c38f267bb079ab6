"""The native commit walk (native/commit_walk.hpp, driven by
types/validation._walk_native) against the walk it stands in for,
types/validation._walk_commit with the batch path's handle.

One parametrised test verifies the same 1,200-validator commits twice,
through the native walk and with the native module absent (so
``_walk_commit`` runs), behind a verifier that records what each walk
hands it.  Cases: absent and nil flags mixed in, a
flag no template exists for, a forged signature at indices 0, 1,023,
1,024, 1,025 and the last, signatures of 63 and 65 bytes, a nil key,
timestamps with seconds 0, negative and 2**63-1 and nanos 0 and
999,999,999, and the tally exactly at and one past the 2/3 mark; each
under verify_commit, verify_commit_light and its all-signatures
variant.  Verdicts, messages, tallied power, the triples byte for byte,
the lanes, the ``walked`` note and the chunks handed to the seam are
compared.  Beside it: a 10,000-validator commit end to end on the CPU verifier,
the ValidatorSet's key-table memo, and the walk counter.
"""
import functools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import fixtures  # noqa: E402
from cometbft_tpu.crypto import (  # noqa: E402
    _native_loader, ed25519, secp256k1,
)
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.crypto import pipeline as crypto_pipeline  # noqa: E402
from cometbft_tpu.libs import metrics as libmetrics  # noqa: E402
from cometbft_tpu.libs import tracing  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402
from cometbft_tpu.types.commit import Commit, CommitSig  # noqa: E402
from cometbft_tpu.types.signature_cache import SignatureCache  # noqa: E402
from cometbft_tpu.types.timestamp import Timestamp  # noqa: E402
from cometbft_tpu.types.validator_set import (  # noqa: E402
    Validator, ValidatorSet,
)
from cometbft_tpu.types.vote import (  # noqa: E402
    BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL,
)

CHAIN_ID = "walk-native"
N = 1200            # 12,000 of power: 2/3 is exactly 8,000
POWER = 10
HEIGHT = 38
TILE = 1024


@pytest.fixture(autouse=True)
def _native_present():
    if _native_loader.load() is None:
        pytest.skip("no native module: the Python walk is all there is")
    assert crypto_pipeline.tile_bucket() == TILE


def _without_the_module(patch) -> None:
    """The process as one without the native module runs it."""
    patch.setenv("COMETBFT_TPU_NATIVE", "0")
    patch.setattr(_native_loader, "_mod", None)
    assert _native_loader.load(allow_build=False) is None


@functools.lru_cache(maxsize=None)
def _keys():
    privs = fixtures.seeded_privs(N, 38, tag="walk-native")
    vset, privs = fixtures.make_valset(privs, POWER)
    return vset, privs, fixtures.seeded_block_id(38, HEIGHT)


@functools.lru_cache(maxsize=None)
def _signed(i: int, flag: int, ts: Timestamp) -> bytes:
    """Validator i's signature over its vote's plain sign bytes."""
    vset, privs, bid = _keys()
    commit = Commit(height=HEIGHT, round=1, block_id=bid, signatures=[
        CommitSig() for _ in range(N)])
    commit.signatures[i] = CommitSig(block_id_flag=flag, timestamp=ts)
    return privs[i].sign(commit.get_vote(i).sign_bytes(CHAIN_ID))


def _ts(i: int) -> Timestamp:
    return Timestamp(fixtures.BASE_TIME_S + i % 3, (i * 7919) % 10**9)


def _lane(i: int, flag: int = BLOCK_ID_FLAG_COMMIT, ts=None,
          sig=None) -> CommitSig:
    vset = _keys()[0]
    ts = _ts(i) if ts is None else ts
    if flag == BLOCK_ID_FLAG_ABSENT:
        return CommitSig.absent()
    return CommitSig(block_id_flag=flag,
                     validator_address=vset.validators[i].address,
                     timestamp=ts,
                     signature=_signed(i, flag, ts) if sig is None
                     else sig)


def _forged(i: int) -> CommitSig:
    cs = _lane(i)
    cs.signature = bytes([cs.signature[0] ^ 1]) + cs.signature[1:]
    return cs


def _resized(i: int, n: int) -> CommitSig:
    cs = _lane(i)
    cs.signature = (cs.signature * 2)[:n]
    return cs


# lanes a case changes: index -> CommitSig maker
CASES = {
    "honest": {},
    "absent_and_nil_mixed_in": {
        **{i: functools.partial(_lane, i, BLOCK_ID_FLAG_NIL)
           for i in range(1, N, 11)},
        **{i: functools.partial(_lane, i, BLOCK_ID_FLAG_ABSENT)
           for i in range(3, N, 7)}},
    "a_flag_without_a_template": {
        600: lambda: CommitSig(block_id_flag=7,
                               validator_address=b"\x01" * 20,
                               timestamp=_ts(600),
                               signature=b"\x00" * 64)},
    **{f"forged_at_{i}": {i: functools.partial(_forged, i)}
       for i in (0, 1023, 1024, 1025, N - 1)},
    "forged_at_the_light_exit": {800: functools.partial(_forged, 800)},
    "forged_past_the_light_exit": {801: functools.partial(_forged, 801)},
    "sig_of_63_bytes": {500: functools.partial(_resized, 500, 63)},
    "sig_of_65_bytes": {1100: functools.partial(_resized, 1100, 65)},
    "seconds_0_nanos_0": {i: functools.partial(_lane, i, ts=Timestamp(0, 0))
                          for i in (2, 1024, 1101)},
    "seconds_negative": {
        2: functools.partial(_lane, 2, ts=Timestamp(-1, 5)),
        1024: functools.partial(_lane, 1024, ts=Timestamp.zero()),
        1101: functools.partial(
            _lane, 1101, ts=Timestamp(-2**63, 999_999_999))},
    "seconds_max": {i: functools.partial(
        _lane, i, ts=Timestamp(2**63 - 1, 999_999_999))
        for i in (2, 1024, 1101)},
    "nanos_0_and_max": {
        **{i: functools.partial(_lane, i, ts=Timestamp(1_700_000_000, 0))
           for i in range(0, N, 5)},
        **{i: functools.partial(
            _lane, i, ts=Timestamp(1_700_000_000, 999_999_999))
           for i in range(1, N, 5)}},
    "two_thirds_exactly": {i: functools.partial(
        _lane, i, BLOCK_ID_FLAG_ABSENT) for i in range(800, N)},
    "all_absent": {i: functools.partial(_lane, i, BLOCK_ID_FLAG_ABSENT)
                   for i in range(N)},
    "a_nil_key": {},        # validator 5's key taken away, below
}

# verify function, its keyword arguments
MODES = {
    "verify_commit": (validation.verify_commit, {}),
    "verify_commit_light": (validation.verify_commit_light, {}),
    "verify_commit_light_all": (validation.verify_commit_light,
                                {"count_all_signatures": True}),
}


def _variant(case: str):
    vset, _, bid = _keys()
    commit = Commit(height=HEIGHT, round=1, block_id=bid, signatures=[
        CASES[case][i]() if i in CASES[case] else _lane(i)
        for i in range(N)])
    if case == "a_nil_key":
        vset = vset.copy()
        vset.validators[5].pub_key = None
    return vset, bid, commit


class _Recording:
    """The seam's CPU verifier, keeping what the walk hands it: by
    add() (the Python walk) or a tile at a time by extend_raw() (the
    native walk)."""

    def __init__(self, inner):
        self.inner = inner
        self.triples = []
        self.chunks = []

    def add(self, pub_key, msg, sig):
        self.inner.add(pub_key, msg, sig)
        self.triples.append((pub_key.bytes(), bytes(msg), bytes(sig)))

    def extend_raw(self, triples):
        self.inner.extend_raw(triples)
        self.triples.extend(triples)
        self.chunks.append(len(triples))

    def verify(self):
        return self.inner.verify()


def _walk(case, mode, absent: bool = False):
    """What one verification gave: outcome (None, or the error's type
    and message), the walk's tally, the verifier, the lanes of a
    native walk and the ``commit_walk`` span's attributes (less the
    collector's ``gc_us``); ``absent``: with no native module."""
    vset, bid, commit = _variant(case)
    fn, kwargs = MODES[mode]
    made, seen = [], {}
    create = crypto_batch.create_batch_verifier

    def making(pub_key):
        made.append(_Recording(create(pub_key)))
        return made[-1]

    def keeping(m, name):
        walk = getattr(validation, name)

        def kept(*a, **kw):
            seen[name] = walk(*a, **kw)
            return seen[name]
        m.setattr(validation, name, kept)

    tracing.clear()
    with pytest.MonkeyPatch.context() as m:
        if absent:
            _without_the_module(m)
        keeping(m, "_walk_commit")
        keeping(m, "_walk_native")
        m.setattr(crypto_batch, "create_batch_verifier", making)
        try:
            fn(CHAIN_ID, vset, bid, HEIGHT, commit, **kwargs)
            outcome = None
        except Exception as e:  # noqa: BLE001 — the outcome is compared
            outcome = (type(e).__name__, str(e))
    walks = [{k: v for k, v in e["attrs"].items() if k != "gc_us"}
             for e in tracing.snapshot() if e["name"] == "commit_walk"]
    native = seen.get("_walk_native")
    tally = native[0] if native else seen.get("_walk_commit")
    return {"outcome": outcome, "tally": tally,
            "bv": made[0] if made else None,
            "lanes": list(native[1]) if native else None,
            "walk": walks[0] if walks else None,
            "vset": vset, "commit": commit}


@pytest.fixture
def recorder(tmp_path):
    old = tracing.set_recorder(tracing.Recorder(
        buffer_size=65536, dump_dir=str(tmp_path)))
    yield tracing.recorder()
    tracing.set_recorder(old)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", CASES)
def test_the_native_walk_is_the_python_walk(recorder, case, mode):
    before = validation.walk_stats()
    got = _walk(case, mode)
    mid = validation.walk_stats()
    ref = _walk(case, mode, absent=True)
    after = validation.walk_stats()
    walked = got["bv"] is not None     # not refused before the walk
    assert mid["native"] == before["native"] + walked
    assert after["python"] == mid["python"] + walked
    assert ref["bv"] is None or ref["bv"].chunks == []

    assert got["outcome"] == ref["outcome"]
    assert got["tally"] == ref["tally"]
    assert got["walk"] == ref["walk"]
    if not walked:
        return
    want, triples, chunks = (ref["bv"].triples, got["bv"].triples,
                             got["bv"].chunks)
    if got["tally"] is None:    # the walk raised: a tile at a time
        assert chunks == [TILE] * len(chunks)
        assert triples == want[:len(triples)]
        return
    # byte for byte, and the lanes the verdict names by
    assert triples == want
    assert chunks == [TILE] * (len(want) // TILE) + (
        [len(want) % TILE] if len(want) % TILE else [])
    vset, commit = got["vset"], got["commit"]
    index = {v.pub_key.bytes(): i for i, v in enumerate(vset.validators)}
    assert got["lanes"] == [index[t[0]] for t in triples]
    assert [t[1] for t in triples] == [
        commit.get_vote(i).sign_bytes(CHAIN_ID) for i in got["lanes"]]


def test_where_the_cases_land(recorder):
    """The cases reach what they are named for (native walk)."""
    def outcome(case, mode):
        return _walk(case, mode)["outcome"]

    assert outcome("honest", "verify_commit") is None
    wrong = "VerificationError", "wrong signature (#%d): "
    for i in (0, 1023, 1024, 1025, N - 1):
        got = outcome(f"forged_at_{i}", "verify_commit")
        assert got[0] == wrong[0] and got[1].startswith(wrong[1] % i)
    assert outcome("forged_at_the_light_exit", "verify_commit_light")[1] \
        .startswith(wrong[1] % 800)
    assert outcome("forged_past_the_light_exit",
                   "verify_commit_light") is None
    assert outcome("forged_past_the_light_exit",
                   "verify_commit_light_all")[1].startswith(wrong[1] % 801)
    assert outcome("sig_of_63_bytes", "verify_commit_light")[1] \
        .startswith(wrong[1] % 500)
    assert outcome("sig_of_65_bytes", "verify_commit_light") is None
    assert outcome("sig_of_65_bytes", "verify_commit")[1] \
        .startswith(wrong[1] % 1100)
    short = ("NotEnoughVotingPowerError",
             "invalid commit -- insufficient voting power: got 8000, "
             "needed more than 8000")
    assert outcome("two_thirds_exactly", "verify_commit_light") == short
    assert outcome("a_flag_without_a_template", "verify_commit") == (
        "CommitError", "unknown BlockIDFlag 7")
    assert "nil PubKey at index 5" in outcome("a_nil_key",
                                              "verify_commit")[1]
    walk = _walk("honest", "verify_commit_light")
    assert walk["walk"]["walked"] == 801 and walk["tally"] == 8010


def test_a_declined_walk_is_walked_again(monkeypatch, recorder):
    """An input the native walk was not written for (a timestamp that
    is a list) hands the commit back: a fresh verifier, the Python
    walk from the start, the same verdict."""
    vset, bid, commit = _variant("forged_at_1025")
    cs = commit.signatures[1030]
    cs.timestamp = list(cs.timestamp)
    before = validation.walk_stats()
    made = []
    create = crypto_batch.create_batch_verifier
    monkeypatch.setattr(crypto_batch, "create_batch_verifier",
                        lambda pk: made.append(_Recording(create(pk))) or
                        made[-1])
    with pytest.raises(validation.VerificationError,
                       match=r"wrong signature \(#1025\)"):
        validation.verify_commit(CHAIN_ID, vset, bid, HEIGHT, commit)
    after = validation.walk_stats()
    assert after["declined"] == before["declined"] + 1
    assert after["native"] == before["native"]
    assert [len(bv.chunks) for bv in made] == [1, 0]
    assert len(made[1].triples) == N


def test_ten_thousand_validators_end_to_end(monkeypatch):
    """valset-10k's shape through verify_commit_light on the CPU
    verifier: honest accepted, a forgery below the 2/3 mark named, the
    same as the Python walk gives."""
    privs = fixtures.seeded_privs(10_000, 38, tag="walk-native-10k")
    vset, privs = fixtures.make_valset(privs, POWER)
    bid = fixtures.seeded_block_id(38, 7)
    commit = fixtures.signed_commit(CHAIN_ID, vset, privs, 7, bid)
    before = validation.walk_stats()
    validation.verify_commit_light(CHAIN_ID, vset, bid, 7, commit)
    cs = commit.signatures[4321]
    cs.signature = bytes([cs.signature[0] ^ 4]) + cs.signature[1:]
    outcomes = []
    for native in (True, False):
        with monkeypatch.context() as m:
            if not native:
                _without_the_module(m)
            with pytest.raises(validation.VerificationError) as ei:
                validation.verify_commit_light(CHAIN_ID, vset, bid, 7,
                                               commit)
            outcomes.append(str(ei.value))
    assert outcomes[0] == outcomes[1]
    assert outcomes[0].startswith("wrong signature (#4321): ")
    after = validation.walk_stats()
    assert after["native"] == before["native"] + 2
    assert after["python"] == before["python"] + 1


def _ed25519_set(n: int, tag: bytes) -> ValidatorSet:
    return ValidatorSet([Validator.new(
        ed25519.gen_priv_key_from_secret(tag + b"%d" % i).pub_key(),
        POWER + i) for i in range(n)])


def _new_key(tag: bytes):
    return ed25519.gen_priv_key_from_secret(tag).pub_key()


# how the set changes; each must leave a table built afresh
CHANGES = {
    "an_added_validator": lambda vs: vs.update_with_change_set(
        [Validator.new(_new_key(b"joiner"), 7)]),
    "a_removed_validator": lambda vs: vs.update_with_change_set(
        [Validator.new(vs.validators[2].pub_key, 0)]),
    "a_power_change": lambda vs: vs.update_with_change_set(
        [Validator.new(vs.validators[0].pub_key, 99)]),
    "a_copy": lambda vs: vs.copy(),
    "a_copy_with_its_priorities_moved": lambda vs:
        vs.copy_increment_proposer_priority(3),
}


@pytest.mark.parametrize("change", CHANGES)
def test_the_key_table_follows_the_set(change):
    vs = _ed25519_set(6, b"table-")
    table = vs.ed25519_key_table()
    assert vs.ed25519_key_table() is table     # memoised
    out = CHANGES[change](vs)
    now = out if isinstance(out, ValidatorSet) else vs
    if now is vs:
        assert vs._key_table_memo is None      # dropped by the update
    else:
        assert now._key_table_memo is None     # a copy builds its own
        assert vs.ed25519_key_table() is table
    keys, powers = now.ed25519_key_table()
    assert keys == tuple(v.pub_key.bytes() for v in now.validators)
    assert powers == b"".join(v.voting_power.to_bytes(8, sys.byteorder)
                              for v in now.validators)


def test_a_key_of_another_type_makes_no_table():
    vs = ValidatorSet([Validator.new(_new_key(b"ed"), 10),
                       Validator.new(secp256k1.gen_priv_key().pub_key(),
                                     10)])
    assert vs.ed25519_key_table() is None


def _walk_counter(executor: str) -> float:
    for line in libmetrics.DEFAULT.render().splitlines():
        if line.startswith("cometbft_consensus_commit_walk_total{") and \
                f'executor="{executor}"' in line:
            return float(line.split()[-1])
    raise AssertionError(f"no commit_walk_total for {executor}")


def _counted(monkeypatch, verify) -> str:
    """The executor whose count one verification moved (the family
    read as /metrics reads it)."""
    before = {k: _walk_counter(k) for k in validation.walk_stats()}
    verify()
    moved = [k for k in before if _walk_counter(k) != before[k]]
    assert len(moved) == 1 and _walk_counter(moved[0]) == before[
        moved[0]] + 1
    return moved[0]


def test_the_counter_says_who_walked(monkeypatch):
    vset, bid, commit = _variant("honest")
    light = functools.partial(validation.verify_commit_light, CHAIN_ID,
                              vset, bid, HEIGHT, commit)
    assert _counted(monkeypatch, light) == "native"
    assert _counted(monkeypatch, functools.partial(
        light, cache=SignatureCache())) == "python"
    assert _counted(monkeypatch, functools.partial(
        validation.verify_commit_light_trusting, CHAIN_ID, vset, commit,
        validation.Fraction(1, 3))) == "python"
    with monkeypatch.context() as m:
        _without_the_module(m)
        assert _counted(m, light) == "python"
    assert _counted(monkeypatch, functools.partial(
        validation.verify_commit, CHAIN_ID, vset, bid, HEIGHT,
        commit)) == "native"
