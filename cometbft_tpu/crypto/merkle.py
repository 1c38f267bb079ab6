"""RFC-6962-style binary merkle tree with domain-separated hashing, proofs,
and chained proof operators.

Reference: crypto/merkle/tree.go (HashFromByteSlices, leaf/inner prefixes,
getSplitPoint), crypto/merkle/proof.go (Proof.Verify, aunts),
crypto/merkle/proof_op.go (ProofOperators for IAVL-style chained proofs).

A JAX-vectorized tree hash for large leaf counts lives in ops/merkle_jax.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

from .tmhash import sum as _sha256

LEAF_PREFIX = b"\x00"
INNER_PREFIX = b"\x01"


def leaf_hash(leaf: bytes) -> bytes:
    return _sha256(LEAF_PREFIX + leaf)


def inner_hash(left: bytes, right: bytes) -> bytes:
    return _sha256(INNER_PREFIX + left + right)


def empty_hash() -> bytes:
    return _sha256(b"")


def _split_point(n: int) -> int:
    """Largest power of two strictly less than n (reference: tree.go:89)."""
    if n < 1:
        raise ValueError("n must be >= 1")
    b = 1 << (n.bit_length() - 1)
    return b // 2 if b == n else b


def hash_from_byte_slices(items: Sequence[bytes]) -> bytes:
    """Merkle root of items (reference: crypto/merkle/tree.go:11).
    Large trees route through the C++ fast path when available."""
    n = len(items)
    if n >= 8:
        from ._native_loader import load
        # never compile on this path — it runs inside the consensus
        # loop; the node pre-builds at startup (prebuild_async)
        native = load(allow_build=False)
        if native is not None:
            try:
                return native.merkle_root(list(items))
            except TypeError:
                pass        # non-bytes items: python path raises too
    if n == 0:
        return empty_hash()
    if n == 1:
        return leaf_hash(items[0])
    k = _split_point(n)
    return inner_hash(hash_from_byte_slices(items[:k]),
                      hash_from_byte_slices(items[k:]))


@dataclass
class Proof:
    """Merkle inclusion proof (reference: crypto/merkle/proof.go)."""
    total: int
    index: int
    leaf_hash: bytes
    aunts: list[bytes] = field(default_factory=list)

    def verify(self, root: bytes, leaf: bytes) -> None:
        if self.total < 0:
            raise ValueError("proof total must be >= 0")
        if self.index < 0:
            raise ValueError("proof index must be >= 0")
        lh = leaf_hash(leaf)
        if lh != self.leaf_hash:
            raise ValueError("invalid leaf hash")
        computed = self.compute_root_hash()
        if computed != root:
            raise ValueError("invalid proof: root mismatch")

    def compute_root_hash(self) -> bytes:
        return _compute_from_aunts(self.index, self.total, self.leaf_hash,
                                   self.aunts)

    def to_dict(self) -> dict:
        return {"total": self.total, "index": self.index,
                "leaf_hash": self.leaf_hash.hex(),
                "aunts": [a.hex() for a in self.aunts]}

    @classmethod
    def from_dict(cls, d: dict) -> "Proof":
        return cls(total=d["total"], index=d["index"],
                   leaf_hash=bytes.fromhex(d["leaf_hash"]),
                   aunts=[bytes.fromhex(a) for a in d["aunts"]])


def _compute_from_aunts(index: int, total: int, lh: bytes,
                        aunts: Sequence[bytes]) -> bytes:
    if index >= total or index < 0 or total <= 0:
        raise ValueError("invalid index/total")
    if total == 1:
        if aunts:
            raise ValueError("unexpected aunts for single leaf")
        return lh
    if not aunts:
        raise ValueError("missing aunts")
    k = _split_point(total)
    if index < k:
        left = _compute_from_aunts(index, k, lh, aunts[:-1])
        return inner_hash(left, aunts[-1])
    right = _compute_from_aunts(index - k, total - k, lh, aunts[:-1])
    return inner_hash(aunts[-1], right)


def proofs_from_byte_slices(items: Sequence[bytes]) -> tuple[bytes, list[Proof]]:
    """Root + one inclusion proof per item (reference: proof.go:40).
    Leaf hashing is batched through the C++ fast path when available
    (part-set splitting runs this on every proposal block)."""
    from ._native_loader import batched_hashes
    hashes = batched_hashes("leaf_hashes", items)
    if hashes is None:
        hashes = [leaf_hash(it) for it in items]
    trails, root_node = _trails_from_leaf_hashes(hashes)
    root = root_node.hash if root_node else empty_hash()
    proofs = []
    for i, trail in enumerate(trails):
        proofs.append(Proof(total=len(items), index=i,
                            leaf_hash=trail.hash,
                            aunts=trail.flatten_aunts()))
    return root, proofs


class _Node:
    __slots__ = ("hash", "parent", "left", "right")

    def __init__(self, h: bytes):
        self.hash = h
        self.parent = None
        self.left = None   # sibling trail nodes, reference naming
        self.right = None

    def flatten_aunts(self) -> list[bytes]:
        aunts = []
        node = self
        while node is not None:
            if node.left is not None:
                aunts.append(node.left.hash)
            elif node.right is not None:
                aunts.append(node.right.hash)
            node = node.parent
        return aunts


def _trails_from_leaf_hashes(hashes: Sequence[bytes]):
    n = len(hashes)
    if n == 0:
        return [], None
    if n == 1:
        node = _Node(hashes[0])
        return [node], node
    k = _split_point(n)
    lefts, left_root = _trails_from_leaf_hashes(hashes[:k])
    rights, right_root = _trails_from_leaf_hashes(hashes[k:])
    root = _Node(inner_hash(left_root.hash, right_root.hash))
    left_root.parent = root
    left_root.right = right_root
    right_root.parent = root
    right_root.left = left_root
    return lefts + rights, root


# -- compact multiproofs ----------------------------------------------------
# One proof object covering many leaves of one tree, sharing the
# interior hashes every per-leaf Proof would repeat ("Compact Merkle
# Multiproofs", PAPERS.md).  Layout: the proven leaf positions
# (`indices`, canonical sorted-unique) plus the roots of every maximal
# subtree containing NO proven leaf (`aunts`), emitted in the
# deterministic left-to-right order a pre-order walk of the RFC-6962
# split-point tree visits them.  Verification replays the same walk,
# consuming leaf hashes at proven positions and aunts everywhere else,
# so builder and verifier agree on the order by construction and the
# proof needs no per-aunt position tags.


@dataclass
class Multiproof:
    """Compact inclusion proof for several leaves of one merkle tree.

    Wire parity with Proof: ints for total/indices, hex hashes in
    to_dict/from_dict.  ``verify`` takes the raw leaf values (what the
    caller fetched and wants proven) in ``indices`` order and raises
    ValueError on any mismatch, like Proof.verify."""
    total: int
    indices: list[int] = field(default_factory=list)
    aunts: list[bytes] = field(default_factory=list)

    def validate_basic(self) -> None:
        if self.total < 0:
            raise ValueError("multiproof total must be >= 0")
        prev = -1
        for i in self.indices:
            if i <= prev:
                raise ValueError(
                    "multiproof indices must be sorted and unique")
            prev = i
        if self.indices and self.indices[-1] >= self.total:
            raise ValueError("multiproof index out of range")

    def verify(self, root: bytes, leaves: Sequence[bytes]) -> None:
        """Verify ``leaves`` (raw tree ITEMS, aligned with
        ``indices``) against ``root``; each gets the RFC-6962 leaf
        prefix hash on the way in.  NOTE: for the tx tree the items
        are the per-tx sha256 digests (types/tx.py txs_hash) — pass
        the digests HERE, they are not yet leaf hashes.  Use
        verify_hashes only with true leaf-prefix hashes
        (``leaf_hash(item)``)."""
        from ._native_loader import batched_hashes
        hashes = batched_hashes("leaf_hashes", list(leaves))
        if hashes is None:
            hashes = [leaf_hash(leaf) for leaf in leaves]
        self.verify_hashes(root, hashes)

    def verify_hashes(self, root: bytes,
                      leaf_hashes: Sequence[bytes]) -> None:
        computed = self.compute_root_hash(leaf_hashes)
        if computed != root:
            raise ValueError("invalid multiproof: root mismatch")

    def compute_root_hash(self, leaf_hashes: Sequence[bytes]) -> bytes:
        self.validate_basic()
        if len(leaf_hashes) != len(self.indices):
            raise ValueError(
                f"multiproof expects {len(self.indices)} leaves, "
                f"got {len(leaf_hashes)}")
        aunts = iter(self.aunts)
        hashes = iter(leaf_hashes)
        pos = 0                       # next unconsumed index pointer

        def walk(lo: int, hi: int) -> bytes:
            nonlocal pos
            if pos >= len(self.indices) or self.indices[pos] >= hi:
                # no proven leaf in [lo, hi): one pre-supplied subtree
                # root covers the whole range
                try:
                    return next(aunts)
                except StopIteration:
                    raise ValueError(
                        "invalid multiproof: missing aunts") from None
            if hi - lo == 1:
                pos += 1
                return next(hashes)
            k = lo + _split_point(hi - lo)
            left = walk(lo, k)
            right = walk(k, hi)
            return inner_hash(left, right)

        if self.total == 0:
            if self.aunts or self.indices:
                raise ValueError(
                    "unexpected aunts/indices for empty tree")
            return empty_hash()
        out = walk(0, self.total)
        if pos != len(self.indices):
            raise ValueError("invalid multiproof: unconsumed indices")
        try:
            next(aunts)
        except StopIteration:
            return out
        raise ValueError("invalid multiproof: unconsumed aunts")

    def to_dict(self) -> dict:
        return {"total": self.total, "indices": list(self.indices),
                "aunts": [a.hex() for a in self.aunts]}

    @classmethod
    def from_dict(cls, d: dict) -> "Multiproof":
        return cls(total=d["total"], indices=list(d["indices"]),
                   aunts=[bytes.fromhex(a) for a in d["aunts"]])


def _root_from_leaf_hashes(hashes: Sequence[bytes]) -> bytes:
    if len(hashes) == 1:
        return hashes[0]
    k = _split_point(len(hashes))
    return inner_hash(_root_from_leaf_hashes(hashes[:k]),
                      _root_from_leaf_hashes(hashes[k:]))


def root_from_leaf_hashes(hashes: Sequence[bytes]) -> bytes:
    """Merkle root over pre-hashed leaves (``leaf_hash(item)`` each).
    The statetree caches kv leaf hashes across commits and recomputes
    only the changed ones, so the root builder must accept hashes
    directly rather than re-hash every item per block."""
    return root_and_cost_from_leaf_hashes(hashes)[0]


def root_and_cost_from_leaf_hashes(
        hashes: Sequence[bytes]) -> tuple[bytes, int]:
    """root_from_leaf_hashes and the number of inner hashes it
    computed: this builder rebuilds every inner node, one for each
    leaf but the first.  Whoever changes how the root is built changes
    the count here, beside it (tests/test_statetree.py holds the count
    to the digests really taken)."""
    if not hashes:
        return empty_hash(), 0
    return _root_from_leaf_hashes(hashes), len(hashes) - 1


def multiproof_from_byte_slices(
        items: Sequence[bytes],
        indices: Sequence[int]) -> tuple[bytes, Multiproof]:
    """Root + one compact proof for the leaves at ``indices``.

    Input indices may arrive unsorted/duplicated (a batch of client
    keys); the proof carries the canonical sorted-unique form and
    callers supply leaves in that order.  Every untargeted subtree is
    hashed exactly once, so building is O(n) regardless of how many
    leaves are proven."""
    from ._native_loader import batched_hashes
    hashes = batched_hashes("leaf_hashes", items)
    if hashes is None:
        hashes = [leaf_hash(it) for it in items]
    return multiproof_from_leaf_hashes(hashes, indices)


def multiproof_from_leaf_hashes(
        hashes: Sequence[bytes],
        indices: Sequence[int]) -> tuple[bytes, Multiproof]:
    """Multiproof over pre-hashed leaves (tx digests, kv bindings)."""
    total = len(hashes)
    idx = sorted(set(indices))
    if idx and (idx[0] < 0 or idx[-1] >= total):
        raise ValueError(
            f"multiproof index out of range [0, {total})")
    if total == 0:
        return empty_hash(), Multiproof(total=0)
    aunts: list[bytes] = []
    pos = 0

    def build(lo: int, hi: int) -> bytes:
        nonlocal pos
        if pos >= len(idx) or idx[pos] >= hi:
            h = _root_from_leaf_hashes(hashes[lo:hi])
            aunts.append(h)
            return h
        if hi - lo == 1:
            pos += 1
            return hashes[lo]
        k = lo + _split_point(hi - lo)
        left = build(lo, k)
        right = build(k, hi)
        return inner_hash(left, right)

    root = build(0, total)
    return root, Multiproof(total=total, indices=idx, aunts=aunts)


# -- chained proof operators (reference: crypto/merkle/proof_op.go) ---------

def _uvarint(n: int) -> bytes:
    """Uvarint length prefix (reference: crypto/merkle/types.go:30
    encodeByteSlice)."""
    from ..wire.proto import encode_uvarint
    return encode_uvarint(n)

def value_op_leaf(key: bytes, value: bytes) -> bytes:
    """The <key, value-hash> leaf binding shared by ValueOp proofs and
    the kvstore state multiproof (reference: proof_value.go:89-102 —
    encodeByteSlice(key) + encodeByteSlice(sha256(value)))."""
    vhash = _sha256(value)
    return _uvarint(len(key)) + key + _uvarint(len(vhash)) + vhash


class ProofOperator:
    def run(self, values: list[bytes]) -> list[bytes]:
        raise NotImplementedError

    def get_key(self) -> bytes:
        raise NotImplementedError


@dataclass
class ValueOp(ProofOperator):
    """Proves leaf value inclusion under a root (reference: proof_value.go)."""
    key: bytes
    proof: Proof

    def run(self, values: list[bytes]) -> list[bytes]:
        if len(values) != 1:
            raise ValueError("ValueOp expects one value")
        lh = leaf_hash(value_op_leaf(self.key, values[0]))
        if lh != self.proof.leaf_hash:
            raise ValueError("leaf hash mismatch")
        return [self.proof.compute_root_hash()]

    def get_key(self) -> bytes:
        return self.key


class ProofOperators(list):
    def verify(self, root: bytes, keypath: Sequence[bytes],
               args: list[bytes]) -> None:
        keys = list(keypath)
        for op in self:
            key = op.get_key()
            if key:
                if not keys or keys[-1] != key:
                    raise ValueError(f"key mismatch on {key!r}")
                keys.pop()
            args = op.run(args)
        if args[0] != root:
            raise ValueError("root mismatch after proof chain")
        if keys:
            raise ValueError("unconsumed keypath")

    def verify_value(self, root: bytes, keypath: Sequence[bytes],
                     value: bytes) -> None:
        self.verify(root, keypath, [value])
