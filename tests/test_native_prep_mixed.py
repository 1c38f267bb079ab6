"""The native host prep on batches of mixed message lengths: vote sign
bytes (~110 bytes), canonical vote-extension sign bytes (~1.9 kB) and
the empty non-replay-protected extension, in any order, at 1 to 300
lanes, with the ZIP-215 edge lanes spliced in.  The prep's multi-buffer
SHA-512 groups lanes by block count; a batch of a chain with vote
extensions is the first to hand it three lengths at once.  The verdict
a lane gets from what the prep wrote (A, R, S and k = SHA-512(R||A||M)
mod L, from the wire buffer, by the golden model's cofactored equation)
must be the per-signature CPU verifier's, lane for lane."""
import random

import pytest

from benchmark.reference import golden
from cometbft_tpu.crypto import _ed25519_ref as ref
from cometbft_tpu.crypto import _native_loader, ed25519, pipeline
from cometbft_tpu.ops import ed25519_jax as ej

SIZES = [1, 2, 7, 8, 9, 63, 64, 65, 129, 240, 300]


@pytest.fixture(scope="module")
def native():
    if _native_loader.load(allow_build=True) is None or \
            ej._native_prep() is None:
        pytest.skip("no native module here (no compiler)")


def _mixed(rng: random.Random, n: int) -> list:
    privs = [ed25519.gen_priv_key_from_secret(b"mixed-%d" % i)
             for i in range(8)]
    items = []
    for _ in range(n):
        priv = rng.choice(privs)
        msg = rng.choice((rng.randbytes(110), rng.randbytes(1_900),
                          rng.randbytes(rng.randrange(1_850, 1_950)),
                          b""))
        items.append((priv.pub_key().bytes(), msg, priv.sign(msg)))
    return items


def _scalar(windows) -> int:
    """The scalar behind [64] 4-bit little-endian windows."""
    return sum(int(w) << (4 * i) for i, w in enumerate(windows))


def _prep_verdicts(items: list) -> list[bool]:
    """What the native prep hands the kernel, judged lane by lane."""
    wire, pre_bad = ej.prep_arrays(items, pipeline.bucket(len(items)))
    a_b, r_b, s_w8, k_w8 = ej.wire_views(wire)
    out = []
    for i in range(len(items)):
        A, R = ref.decompress(bytes(a_b[i])), ref.decompress(bytes(r_b[i]))
        if pre_bad[i] or A is None or R is None:
            out.append(False)
            continue
        s, k = _scalar(s_w8[i]), _scalar(k_w8[i])
        neg = lambda p: ((ref.P - p[0]) % ref.P, p[1])  # noqa: E731
        chk = ref.point_add(ref.scalar_mult(s, ref.B), ref.point_add(
            neg(R), neg(ref.scalar_mult(k, A))))
        out.append(ref.is_identity_cofactored(chk))
    return out


@pytest.mark.parametrize("n", SIZES)
def test_the_prep_judges_every_lane_as_the_cpu_verifier(native, n):
    rng = random.Random(4000 + n)
    items = _mixed(rng, n)
    # the edge lanes (forged R and S, a wrong message, S = 0, S + L,
    # small-order and non-canonical points) in seeded slots
    special = golden.edge_lanes(rng, items)[:max(1, n // 3)]
    for slot, lane in zip(rng.sample(range(n), len(special)), special):
        items[slot] = lane
    got = _prep_verdicts(items)
    want = [ed25519.Ed25519PubKey(pub).verify_signature(msg, sig)
            for pub, msg, sig in items]
    assert got == want
    lengths = {len(msg) for _, msg, _ in items}
    if n >= 64:
        assert 0 in lengths and max(lengths) >= 1_850 and 110 in lengths
        assert not all(got) and any(got)


def test_uniform_batches_are_the_mixed_ones_sorted(native):
    """Grouping by block count does not depend on the order the lanes
    come in: the prep of a mixed batch, row for row, is the prep of
    each lane where it lies in a batch sorted by length."""
    rng = random.Random(40)
    items = _mixed(rng, 200)
    order = sorted(range(200), key=lambda i: len(items[i][1]))
    mixed, mixed_bad = ej.prep_arrays(items, 256)
    ranked, ranked_bad = ej.prep_arrays([items[i] for i in order], 256)
    for j, i in enumerate(order):
        assert bytes(mixed[i]) == bytes(ranked[j])
        assert mixed_bad[i] == ranked_bad[j]
