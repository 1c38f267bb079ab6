"""The native executor of wire/proto.py's descriptors against the
Python walk it stands in for (native/wire_codec.hpp).

The walk (`proto._py_encode` / `proto._py_decode`) is the reference:
every descriptor the package defines gives the same bytes and the same
dict through both, hostile bytes give the same value or the same
exception type, and a 1,000-validator light block stored under one
executor reads under the other.
"""
import importlib
import random

import pytest

import test_wire
from cometbft_tpu.crypto import _native_loader, ed25519
from cometbft_tpu.db.db import MemDB
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.light.store import TrustedStore
from cometbft_tpu.types.block import Header, LightBlock, SignedHeader
from cometbft_tpu.types.block_id import BlockID
from cometbft_tpu.types.commit import Commit, CommitSig
from cometbft_tpu.types.part_set import PartSetHeader
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.types.vote import BLOCK_ID_FLAG_COMMIT
from cometbft_tpu.wire import pb, proto
from cometbft_tpu.wire.proto import F, Msg

_MODULES = (
    "wire.pb", "wire.abci_pb", "wire.consensus_pb", "wire.state_pb",
    "wire.privval_pb", "rpc.grpc.pb", "blocksync.reactor",
    "evidence.reactor", "mempool.messages", "p2p.pex",
    "statesync.reactor",
)


def _all_descriptors() -> dict:
    """name -> every Msg a module above defines or reaches."""
    found: dict = {}

    def walk(desc):
        if desc.name in found:
            assert found[desc.name] is desc, desc.name
            return
        found[desc.name] = desc
        for f in desc.fields:
            if f.msg is not None:
                walk(f.msg)

    for mod in _MODULES:
        m = importlib.import_module("cometbft_tpu." + mod)
        for _, v in sorted(vars(m).items()):
            if isinstance(v, Msg):
                walk(v)
    return found


DESCRIPTORS = _all_descriptors()


@pytest.fixture
def native():
    mod = _native_loader.load()
    if mod is None:
        pytest.skip("no native module (no compiler, or "
                    "COMETBFT_TPU_NATIVE=0): the walk is all there is")
    return mod


def _without_the_module(patch) -> None:
    """encode()/decode() as a process without the module runs them:
    the loader's own switch, as tests/test_native.py throws it."""
    patch.setenv("COMETBFT_TPU_NATIVE", "0")
    patch.setattr(_native_loader, "_mod", None)
    assert _native_loader.load(allow_build=False) is None


@pytest.fixture
def python_walk(monkeypatch):
    _without_the_module(monkeypatch)


# ---- seeded messages --------------------------------------------------

_INTS = (0, 1, -1, 127, 128, 300, 2**31 - 1, -2**31, 2**32, 2**63 - 1,
         -2**63, -62135596800, 2**64 + 5, -2**64 - 5)    # & MASK64
_UINTS = (0, 1, 127, 128, 2**32 - 1, 2**32, 2**63, 2**64 - 1)


def _scalar(kind: str, rng: random.Random):
    if kind in ("int32", "int64", "enum"):
        return rng.choice(_INTS + (rng.getrandbits(62), True))
    if kind in ("uint32", "uint64"):
        return rng.choice(_UINTS + (rng.getrandbits(64),))
    if kind == "bool":
        return rng.choice((True, False, 0, 1, 7))
    if kind == "sfixed64":
        return rng.choice((0, -1, 2**63 - 1, -2**63, rng.getrandbits(60)))
    if kind == "fixed64":
        return rng.choice((0, 1, 2**64 - 1, rng.getrandbits(64)))
    if kind == "sfixed32":
        return rng.choice((0, -1, 2**31 - 1, -2**31, rng.getrandbits(30)))
    if kind == "fixed32":
        return rng.choice((0, 1, 2**32 - 1, rng.getrandbits(32)))
    if kind == "bytes":
        b = rng.randbytes(rng.choice((0, 1, 20, 32, 64, 127, 128, 300)))
        return rng.choice((bytes, bytes, bytearray, memoryview))(b)
    assert kind == "string"
    return rng.choice(("", "a", "chain-id", "ünï©ödé ☃", "x" * 200))


def _message(desc: Msg, rng: random.Random, depth: int = 0) -> dict:
    d = {}
    for f in desc.fields:
        roll = rng.random()
        if roll < 0.2:
            continue                        # absent
        if roll < 0.3:
            d[f.name] = None                # present and None
            continue
        if f.kind == "msg":
            def sub():
                if depth > 6 or rng.random() < 0.15:
                    return {}               # empty, not absent
                return _message(f.msg, rng, depth + 1)
            make = sub
        else:
            make = lambda: _scalar(f.kind, rng)   # noqa: E731
        if f.repeated:
            items = [make() for _ in range(rng.choice((0, 1, 2, 5)))]
            d[f.name] = tuple(items) if rng.random() < 0.3 else items
        else:
            d[f.name] = make()
    return d


def _unknown_field(desc: Msg, rng: random.Random) -> bytes:
    """One well-formed field whose number the descriptor lacks."""
    known = {f.num for f in desc.fields}
    num = rng.choice([n for n in (1, 2, 15, 16, 99, 2047, 2048, 70000)
                      if n not in known])
    wt = rng.choice((0, 1, 2, 5))
    body = {0: proto.encode_uvarint(rng.getrandbits(rng.choice((7, 63)))),
            1: rng.randbytes(8), 5: rng.randbytes(4),
            2: b"\x03abc"}[wt]
    return proto.encode_uvarint(num << 3 | wt) + body


def _same(a, b) -> bool:
    """Equal AND of the same types and key order: True is not 1."""
    return repr(a) == repr(b)


@pytest.mark.parametrize("name", sorted(DESCRIPTORS))
def test_parity(name, native):
    """Seeded messages of one descriptor: zeros, negatives, empty and
    absent sub-messages, `always` fields, repeated scalars and
    messages, unknown fields spliced in."""
    desc = DESCRIPTORS[name]
    rng = random.Random("parity/" + name)
    before = native.wire_stats()
    for _ in range(25):
        d = _message(desc, rng)
        want = proto._py_encode(desc, d)
        got = native.wire_encode(desc, d)
        assert type(got) is bytes and got == want, d
        assert proto.encode(desc, d) == want
        spliced = (_unknown_field(desc, rng) + want
                   + _unknown_field(desc, rng))
        for raw in (want, spliced, bytearray(spliced)):
            back = proto._py_decode(desc, raw)
            assert _same(native.wire_decode(desc, raw), back), raw
            assert _same(proto.decode(desc, raw), back)
    assert _same(native.wire_decode(desc, b""), proto._py_decode(desc, b""))
    assert native.wire_encode(desc, {}) == proto._py_encode(desc, {})
    after = native.wire_stats()
    assert after[1] == before[1], "a plain message was declined"
    assert after[0] > before[0]


# ---- a sub-message's length, written behind its body --------------------

_IN_PLACE = Msg(
    "test.InPlace",
    F(1, "pad", "bytes"),
    F(2, "m", "msg", msg=Msg(
        "test.InPlace.Body",
        F(1, "t", "bool"), F(2, "g", "fixed32"), F(3, "k", "fixed64"),
        F(4, "rt", "bool", repeated=True))),
    F(3, "rm", "msg", repeated=True, msg=Msg(
        "test.InPlace.Item", F(1, "t", "bool"), F(2, "s", "sfixed32"))),
    F(4, "e", "msg", always=True, msg=Msg("test.InPlace.Empty")),
)
_IN_PLACE_OUTER = Msg("test.InPlaceOuter", F(1, "pad", "bytes"),
                      F(2, "in", "msg", msg=_IN_PLACE))

# bodies that reach the buffer through fixed-size writes alone, so
# nothing but the length's own write can move it: bools, one fixed32,
# one fixed64, and runs of bools on both sides of the 128-byte length
_IN_PLACE_BODIES = {
    "bool": {"m": {"t": True}},
    "fixed32": {"m": {"g": 0xDEADBEEF}},
    "fixed64": {"m": {"k": 2**64 - 1}},
    "bools-to-127": {"m": {"rt": [True] * 63, "t": True}},
    "bools-from-128": {"m": {"rt": [True] * 64}},
    "bools-past-128": {"m": {"rt": [True, False] * 40, "g": 1}},
    "items": {"rm": [{"t": True}, {"s": -1}, {}] * 8},
    "empty": {},
}


@pytest.mark.parametrize("body", sorted(_IN_PLACE_BODIES))
def test_length_written_in_place_at_every_offset(body, native):
    """A pad sweeps the sub-message over every offset around the end of
    the encoder's first (in-line) buffer and around each of its later
    sizes: the length goes in behind the body without moving it."""
    d = dict(_IN_PLACE_BODIES[body])
    pads = [*range(0, 16), *range(100, 140), *range(360, 540),
            *range(1000, 1040), *range(2020, 2060)]
    for n in pads:
        d["pad"] = bytes(n)
        want = proto._py_encode(_IN_PLACE, d)
        assert native.wire_encode(_IN_PLACE, d) == want, n
        outer = {"pad": bytes(n % 7), "in": d}
        want = proto._py_encode(_IN_PLACE_OUTER, outer)
        assert native.wire_encode(_IN_PLACE_OUTER, outer) == want, n
        assert _same(native.wire_decode(_IN_PLACE_OUTER, want),
                     proto._py_decode(_IN_PLACE_OUTER, want))


# ---- what the executor declines ----------------------------------------

_ODD = Msg(
    "test.Odd",
    F(1, "i", "int64"), F(2, "u", "uint64"), F(3, "b", "bytes"),
    F(4, "s", "string"), F(5, "f", "sfixed32"), F(6, "g", "fixed32"),
    F(7, "t", "bool"), F(8, "m", "msg", msg=pb.TIMESTAMP),
    F(9, "ri", "int64", repeated=True),
    F(10, "rb", "bytes", repeated=True),
    F(11, "rm", "msg", msg=pb.TIMESTAMP, repeated=True),
    F(12, "h", "sfixed64"), F(13, "k", "fixed64"),
)


class _Dict(dict):
    pass


_ODD_VALUES = [
    {"i": "7"}, {"i": 7.9}, {"i": 2**64}, {"i": -2**70},
    {"u": -1}, {"u": 2**64}, {"u": "3"}, {"b": "text"}, {"b": 5},
    {"b": [1, 2]}, {"b": []}, {"s": b"raw"}, {"s": "\ud800"}, {"s": 0},
    {"f": 2**31}, {"f": -2**31 - 1}, {"g": 2**32}, {"g": -1},
    {"h": 2**63}, {"k": 2**64}, {"k": -1}, {"t": "yes"}, {"t": []},
    {"m": []}, {"m": 5}, {"m": _Dict(seconds=3)}, {"m": {"seconds": "x"}},
    {"ri": 5}, {"ri": [None]}, {"ri": [1, "2"]}, {"ri": range(1, 3)},
    {"ri": b"\x01\x02"}, {"rb": [5]}, {"rb": b"ab"}, {"rb": ["s"]},
    {"rm": [None]}, {"rm": [{}, 5]}, {"rm": {"seconds": 1}},
    {"b": memoryview(b"abcdefgh").cast("H")},
    {"rb": [memoryview(b"abcdefgh").cast("H")]},
    _Dict(i=5), [("i", 5)], None, 5,
]


def _outcome(fn, *args):
    try:
        return fn(*args)
    except Exception as e:         # noqa: BLE001 - the type IS the result
        return type(e)


def _decode_outcome(fn, *args):
    """As _outcome, and a rejection's text with it: the executor says
    what the walk says."""
    try:
        return fn(*args)
    except ValueError as e:
        return type(e), str(e)


@pytest.mark.parametrize("i", range(len(_ODD_VALUES)))
def test_odd_values_are_the_walks(i, native):
    """A value the executor was not written for is declined, never
    guessed at: encode() then answers as the walk does, error or not."""
    d = _ODD_VALUES[i]
    want = _outcome(proto._py_encode, _ODD, d)
    declined = native.wire_stats()[1]
    assert native.wire_encode(_ODD, d) is None
    assert native.wire_stats()[1] == declined + 1
    assert _same(_outcome(proto.encode, _ODD, d), want)


def test_decode_declines_what_is_not_bytes(native):
    raw = proto._py_encode(pb.TIMESTAMP, {"seconds": 5})
    for data in (memoryview(raw), list(raw), "text", None, 5):
        assert native.wire_decode(pb.TIMESTAMP, data) is None
        assert _same(_outcome(proto.decode, pb.TIMESTAMP, data),
                     _outcome(proto._py_decode, pb.TIMESTAMP, data))


def test_descriptor_that_does_not_compile_is_declined(native):
    """A descriptor nested deeper than 32 levels, or one that reaches
    itself, is the walk's: the executor's stack follows descriptors."""
    deep = Msg("test.Deep0", F(1, "v", "int64"))
    for i in range(1, 40):
        deep = Msg(f"test.Deep{i}", F(1, "sub", "msg", msg=deep))
    d = {}
    leaf = d
    for _ in range(39):
        leaf["sub"] = {}
        leaf = leaf["sub"]
    leaf["v"] = 7
    raw = proto._py_encode(deep, d)
    assert native.wire_encode(deep, d) is None
    assert native.wire_decode(deep, raw) is None
    assert proto.encode(deep, d) == raw and proto.decode(deep, raw) == d
    for _ in range(8):
        deep, d = deep.fields[0].msg, d["sub"]
    raw = proto._py_encode(deep, d)         # 32 levels: compiles
    assert native.wire_encode(deep, d) == raw
    assert native.wire_decode(deep, raw) == d
    loop = Msg("test.Loop", F(1, "again", "msg", msg=pb.TIMESTAMP),
               F(2, "v", "int64"))
    object.__setattr__(loop.fields[0], "msg", loop)
    assert native.wire_encode(loop, {"again": {"v": 1}}) is None
    assert proto.encode(loop, {"again": {"v": 1}}) == b"\x0a\x02\x10\x01"
    assert native.wire_encode("not a descriptor", {}) is None


# ---- hostile bytes ----------------------------------------------------

def _decode_both(desc, raw, native):
    """The walk's outcome, the wrapper's, and the executor's own (which
    may also be None: declined)."""
    want = _decode_outcome(proto._py_decode, desc, raw)
    assert _same(_decode_outcome(proto.decode, desc, raw), want), raw.hex()
    got = _decode_outcome(native.wire_decode, desc, raw)
    assert got is None or _same(got, want), raw.hex()
    return want[0] if isinstance(want, tuple) else want


def _small_vote() -> bytes:
    return proto._py_encode(pb.VOTE, {
        "type": 2, "height": 12345, "round": -1,
        "block_id": {"hash": b"\xab" * 32,
                     "part_set_header": {"total": 3, "hash": b"\xcd" * 32}},
        "timestamp": {"seconds": 1700000000, "nanos": 5},
        "validator_address": b"\x11" * 20, "validator_index": 7,
        "signature": b"\x22" * 64})


def test_truncation_at_every_offset(native):
    raws = [_small_vote(),
            proto._py_encode(_ODD, {
                "i": -5, "u": 9, "b": b"xyz", "s": "héllo", "f": -2,
                "g": 3, "t": True, "m": {"seconds": 1}, "ri": [1, -1],
                "rb": [b"", b"q"], "rm": [{}, {"nanos": 4}], "h": -9,
                "k": 2**63})]
    failed = 0
    for desc, raw in zip((pb.VOTE, _ODD), raws):
        for cut in range(len(raw) + 1):
            want = _decode_both(desc, raw[:cut], native)
            failed += want is ValueError
    assert failed > 100             # most cuts land inside a field


def test_varints_and_lengths_out_of_range(native):
    V = proto.encode_uvarint
    cases = [
        b"\x08" + b"\xff" * 9 + b"\x01",            # 2^64 - 1
        b"\x08" + b"\xff" * 9 + b"\x7f",            # the walk: > 2^64
        b"\x08" + b"\x80" * 9 + b"\x02",            # bit 64 alone
        b"\x08" + b"\xff" * 10 + b"\x01",           # eleven bytes
        b"\x08" + b"\x80" * 10,                     # never ends
        b"\x08" + b"\x80" * 9 + b"\x00",            # ten bytes of zero
        b"\xff" * 9 + b"\x7f" + b"\x01",            # a key above 2^64
        b"\x80" * 9 + b"\x01" + b"\x01",            # key 2^63: field 2^60
        b"\x08",                                    # tag, no value
        b"\x1a" + V(5) + b"abc",                    # length past the end
        b"\x1a" + V(2**63 - 1) + b"abc",
        b"\x1a" + V(2**63) + b"abc",
        b"\x1a" + V(2**64 - 1) + b"abc",
        b"\x42" + V(2**63 + 7) + b"\x08\x01",       # a sub-message's
        b"\x42" + V(3) + b"\x08\x01",
        b"\x42\x04\x0a\x7f\x08\x01",                # unknown LEN inside
        b"\xfa\x7f" + V(2**64 - 1) + b"zz",         # unknown, skipped
        b"\xf9\x7f\x01\x02",                        # unknown fixed64, cut
        b"\xfd\x7f\x01",                            # unknown fixed32, cut
        b"\xfb\x7f", b"\xfc\x7f", b"\xfe\x7f", b"\xff\x7f",  # wt 3 4 6 7
        b"\x0b", b"\x0c", b"\x0e", b"\x0f",         # the same, field 1
        b"\x22\x02\xc3\x28",                        # bad utf-8
        b"\x22\x01\xff",
    ]
    seen = set()
    for raw in cases:
        want = _decode_both(_ODD, raw, native)
        seen.add(want if isinstance(want, type) else dict)
    assert seen == {dict, ValueError, UnicodeDecodeError}
    # the one input class the executor hands back: more than 64 bits
    declined = native.wire_stats()[1]
    assert native.wire_decode(_ODD, cases[1]) is None
    assert native.wire_stats()[1] == declined + 1
    assert proto.decode(_ODD, cases[1]) == {"i": (2**70 - 1) - 2**64}


def test_every_wire_type_for_every_kind(native):
    """decode shapes a value by the wire type it FINDS."""
    V = proto.encode_uvarint
    bodies = {0: V(2**64 - 1), 1: b"\xff" * 8, 2: b"\x02\xc3\xa9",
              3: b"", 4: b"", 5: b"\xfe\xff\xff\xff", 6: b"", 7: b""}
    for f in _ODD.fields:
        for wt, body in bodies.items():
            raw = V(f.num << 3 | wt) + body
            _decode_both(_ODD, raw, native)
            _decode_both(_ODD, raw + raw, native)


def test_ten_thousand_mutations(native):
    rng = random.Random("hostile")
    descs = [pb.VOTE, pb.COMMIT, pb.HEADER, pb.LIGHT_BLOCK, pb.BLOCK,
             DESCRIPTORS["cometbft.consensus.v2.Message"]
             if "cometbft.consensus.v2.Message" in DESCRIPTORS
             else pb.PROPOSAL, _ODD]
    seeds = []
    for desc in descs:
        for _ in range(6):
            d = _message(desc, rng)
            seeds.append((desc, proto._py_encode(desc, d)))
    seeds = [(desc, raw) for desc, raw in seeds if 0 < len(raw) < 4096]
    outcomes: dict = {}
    for _ in range(10_000):
        desc, raw = rng.choice(seeds)
        b = bytearray(raw)
        for _ in range(rng.choice((1, 1, 2, 4))):
            at = rng.randrange(len(b)) if b else 0
            op = rng.randrange(5)
            if op == 0 and b:
                b[at] ^= 1 << rng.randrange(8)
            elif op == 1 and b:
                b[at] = rng.choice((0, 0x7f, 0x80, 0xff, rng.randrange(256)))
            elif op == 2:
                b[at:at] = rng.randbytes(rng.choice((1, 2, 9)))
            elif op == 3 and b:
                del b[at:at + rng.choice((1, 2, 9))]
            elif b:
                del b[at:]
        want = _decode_both(desc, bytes(b), native)
        key = want if isinstance(want, type) else dict
        outcomes[key] = outcomes.get(key, 0) + 1
    assert outcomes.get(dict, 0) > 1000 and outcomes.get(ValueError, 0) > 1000
    assert set(outcomes) <= {dict, ValueError, UnicodeDecodeError}


# ---- the sign-bytes vectors, under both executors ------------------------

class TestSignBytesVectorsNative(test_wire.TestVoteSignBytesGoldenVectors):
    @pytest.fixture(autouse=True)
    def _executor(self, native):
        before = native.wire_stats()
        yield
        after = native.wire_stats()
        assert after[0] > before[0] and after[1] == before[1]


class TestSignBytesVectorsPythonWalk(
        test_wire.TestVoteSignBytesGoldenVectors):
    @pytest.fixture(autouse=True)
    def _executor(self, python_walk):
        before = proto.codec_stats()
        yield
        after = proto.codec_stats()
        assert after["python"] > before["python"]
        assert after["native"] == before["native"]


class TestSplicedVectorsPythonWalk(
        test_wire.TestVoteSignBytesVectorsSpliced):
    @pytest.fixture(autouse=True)
    def _executor(self, python_walk):
        yield


class TestRoundTripPythonWalk(test_wire.TestRoundTrip):
    @pytest.fixture(autouse=True)
    def _executor(self, python_walk):
        yield


# ---- the deployment's size, once ---------------------------------------

def _light_block(n: int, height: int) -> LightBlock:
    """A light block of the shape light-1k stores: n validators, n
    commit signatures (made-up keys and signatures: the store keeps
    bytes, it verifies nothing)."""
    rng = random.Random(f"light-{n}-{height}")
    vals = ValidatorSet([
        Validator(address=pub.address(), pub_key=pub, voting_power=10)
        for pub in (ed25519.Ed25519PubKey(rng.randbytes(32)) for _ in range(n))])
    header = Header(
        chain_id="light-1k", height=height,
        time=Timestamp(1_700_000_000 + height, 0),
        last_block_id=BlockID(
            hash=rng.randbytes(32),
            part_set_header=PartSetHeader(1, rng.randbytes(32))),
        validators_hash=vals.hash(), next_validators_hash=vals.hash(),
        proposer_address=vals.validators[0].address)
    bid = BlockID(hash=header.hash(),
                  part_set_header=PartSetHeader(1, b"\xAA" * 32))
    sigs = [CommitSig(block_id_flag=BLOCK_ID_FLAG_COMMIT,
                      validator_address=v.address,
                      timestamp=Timestamp(1_700_000_000 + height, i + 1),
                      signature=rng.randbytes(64))
            for i, v in enumerate(vals.validators)]
    return LightBlock(
        signed_header=SignedHeader(
            header=header,
            commit=Commit(height=height, round=0, block_id=bid,
                          signatures=sigs)),
        validator_set=vals)


def test_light_block_of_1000_validators_through_the_store(
        native, monkeypatch):
    lbs = [_light_block(1000, h) for h in (1, 65)]

    def run(db):
        """Save both, read back by height and as latest; what the
        light client's guarantee `stored` reads."""
        store = TrustedStore(db)
        for lb in lbs:
            store.save_light_block(lb)
        got = [store.light_block(1), store.light_block(65),
               store.latest()]
        assert store.light_block(2) is None
        return [(g.height, g.signed_header.header.hash(),
                 g.validator_set.hash(),
                 g.signed_header.header.validators_hash) for g in got]

    want = [(lb.height, lb.signed_header.header.hash(),
             lb.validator_set.hash(),
             lb.signed_header.header.validators_hash)
            for lb in (lbs[0], lbs[1], lbs[1])]
    db_native, db_python = MemDB(), MemDB()
    before = proto.codec_stats()
    assert run(db_native) == want
    mid = proto.codec_stats()
    assert mid["native"] > before["native"]
    assert mid["declined"] == before["declined"]
    assert mid["python"] == before["python"]
    with monkeypatch.context() as m:
        _without_the_module(m)
        assert run(db_python) == want
        assert proto.codec_stats()["python"] > mid["python"]
        # written by the executor, read by the walk
        assert [TrustedStore(db_native).light_block(h).signed_header
                .header.hash() for h in (1, 65)] == [w[1] for w in want[:2]]
    stored = {k: v for k, v in db_native.iterator(b"", b"\xff")}
    assert stored == {k: v for k, v in db_python.iterator(b"", b"\xff")}
    assert len(stored) == 2 and all(len(v) > 170_000 for v in
                                    stored.values())
    # written by the walk, read by the executor
    store = TrustedStore(db_python)
    assert store.latest().validator_set.hash() == want[2][2]
    assert [store.light_block(h).signed_header.header.hash()
            for h in (1, 65)] == [w[1] for w in want[:2]]
    assert proto.codec_stats()["declined"] == before["declined"]


def test_codec_counter_on_the_metrics_page(native):
    proto.encode(pb.TIMESTAMP, {"seconds": 1})
    stats = proto.codec_stats()
    assert set(stats) == {"native", "python", "declined"}
    lines = [ln for ln in libmetrics.DEFAULT.render().splitlines()
             if ln.startswith("cometbft_wire_codec_total{")]
    assert lines == [
        f'cometbft_wire_codec_total{{executor="{k}"}} {stats[k]}'
        for k in ("declined", "native", "python")]
