"""oracle: a kvstore whose validators put oracle prices in their vote
extensions (the shape of dYdX Chain's Slinky oracle over ABCI++).

- ExtendVote: the prices of ``PRICE_PAIRS`` currency pairs, made
  deterministically from the app's seed, the height and the
  validator, encoded as Slinky's ``OracleVoteExtension`` protobuf
  (``map<uint64, bytes> prices = 1``: pair id -> 8-byte big-endian
  price), uncompressed; the non-replay-protected extension is empty
  (CometBFT v1 signs it all the same).
- VerifyVoteExtension: the payload decodes, every id is a known pair,
  every price is eight bytes and not zero, and the whole is under
  ``MAX_EXTENSION_BYTES``; otherwise REJECT.
- PrepareProposal: the proposer's ``local_last_commit`` (an
  ``ExtendedCommitInfo``, encoded once) is the block's first tx.
- ProcessProposal: that tx decodes; its votes are the block's last
  commit's, validator for validator and flag for flag; every payload
  is well formed; the extension signatures of the committing votes
  verify in ONE batch of the crypto seam (``crypto.batch``); and they
  carry more than 2/3 of the last commit's power (what Cosmos SDK's
  ``ValidateVoteExtensions`` holds an injected commit to).
- FinalizeBlock: each pair's median price over the committing votes is
  written under ``oracle/<pair id>``, so the app hash covers the oracle;
  every other tx is the kvstore's.

An app checks the injected signatures itself: the node's verified-vote
memo is the consensus engine's and an ABCI app does not share it.  The
chain id and the height extensions start at come with InitChain and are
kept in the app's database, so a node restarted over its stores checks
the same way.
"""
from __future__ import annotations

import functools
import hashlib
import json
import struct
from dataclasses import replace
from typing import Optional

from ..db import DB
from ..libs import tracing
from ..wire import abci_pb, decode, encode
from ..wire.proto import decode_uvarint, encode_uvarint
from . import types as abci
from .kvstore import KVStoreApplication
from .pb import _ext_commit_info_from, _ext_commit_info_to

PRICE_PAIRS = 128
MAX_EXTENSION_BYTES = 4096
ORACLE_PREFIX = b"oracle/"
_GENESIS_KEY = b"oracle_genesis"     # in the app's database, not the tree
_BLOCK_ID_FLAG_COMMIT = 2
_PRICE = struct.Struct(">Q")


class PayloadError(ValueError):
    """An extension that is not a well-formed price payload."""


def seeded_prices(seed: int, height: int, address: bytes
                  ) -> dict[int, int]:
    """The prices ``address`` reports at ``height``: pair p around
    (p + 1) x 10^8, spread by the p-th 4 bytes of a SHAKE-256 stream of
    (seed, height, address)."""
    stream = hashlib.shake_256(b"oracle|%d|%d|%s" % (
        seed, height, address.hex().encode())).digest(4 * PRICE_PAIRS)
    return {p: (p + 1) * 100_000_000 +
            int.from_bytes(stream[4 * p:4 * p + 4], "big") % 1_000_000
            for p in range(PRICE_PAIRS)}


def encode_prices(prices: dict[int, int]) -> bytes:
    """``OracleVoteExtension{prices}``, pair ids ascending."""
    out = bytearray()
    for pair in sorted(prices):
        entry = b"\x08" + encode_uvarint(pair) + b"\x12\x08" + \
            _PRICE.pack(prices[pair])
        out += b"\x0a" + encode_uvarint(len(entry)) + entry
    return bytes(out)


# an entry of a pair id below 128 as encode_prices (and Go's encoder)
# writes it: 0a 0c 08 <id> 12 08 <price>
_ENTRY = 14
_TAGS = ((0, 0x0A), (1, 0x0C), (2, 0x08), (4, 0x12), (5, 0x08))


@functools.lru_cache(maxsize=8)
def _entries(k: int) -> struct.Struct:
    return struct.Struct(">" + "3xB2xQ" * k)


def decode_prices(raw: bytes) -> dict[int, int]:
    """The prices of an ``OracleVoteExtension``; PayloadError unless
    every field is a map entry of a known pair id and an 8-byte price
    that is not zero, each pair once."""
    if len(raw) > MAX_EXTENSION_BYTES:
        raise PayloadError(f"extension of {len(raw)} bytes is over "
                           f"the cap of {MAX_EXTENSION_BYTES}")
    k, rest = divmod(len(raw), _ENTRY)
    if k and not rest and all(
            raw[at::_ENTRY] == bytes((tag,)) * k for at, tag in _TAGS) \
            and max(raw[3::_ENTRY]) < 0x80:
        # every entry in the one-byte-id form: one unpack
        flat = _entries(k).unpack(raw)
        prices = dict(zip(flat[::2], flat[1::2]))
        if len(prices) != k or max(prices) >= PRICE_PAIRS or \
                not all(flat[1::2]):
            raise PayloadError("unknown or repeated pair id, or no price")
        return prices
    prices: dict[int, int] = {}
    pos, end = 0, len(raw)
    try:
        while pos < end:
            if raw[pos] != 0x0A:
                raise PayloadError(f"unexpected field tag {raw[pos]}")
            size, pos = decode_uvarint(raw, pos + 1)
            stop = pos + size
            if stop > end:
                raise PayloadError("truncated map entry")
            pair = price = None
            while pos < stop:
                tag = raw[pos]
                if tag == 0x08:
                    pair, pos = decode_uvarint(raw, pos + 1)
                elif tag == 0x12 and raw[pos + 1] == 8 and \
                        pos + 10 <= stop:
                    (price,) = _PRICE.unpack_from(raw, pos + 2)
                    pos += 10
                else:
                    raise PayloadError("malformed map entry")
            if pos != stop or pair is None or not price:
                raise PayloadError("map entry without a pair or a price")
            if pair >= PRICE_PAIRS or pair in prices:
                raise PayloadError(f"unknown or repeated pair id {pair}")
            prices[pair] = price
    except (IndexError, ValueError) as e:
        if isinstance(e, PayloadError):
            raise
        raise PayloadError(str(e)) from e
    return prices


def median(values: list[int]) -> int:
    """The middle price; of an even count, the floor of the mean of
    the two middle ones."""
    s = sorted(values)
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) // 2


def oracle_key(pair: int) -> bytes:
    return ORACLE_PREFIX + b"%d" % pair


class OracleApplication(KVStoreApplication):
    def __init__(self, db: Optional[DB] = None, seed: int = 0,
                 validator_address: bytes = b"", **kw):
        super().__init__(db=db, **kw)
        self.seed = seed
        # whose prices ExtendVote reports (the node's own validator)
        self.validator_address = validator_address
        genesis = json.loads(self.db.get(_GENESIS_KEY) or b"{}")
        self.chain_id = genesis.get("chain_id", "")
        self.vote_extensions_enable_height = genesis.get("ve_height", 0)
        self._pub_keys: dict[bytes, object] = {}
        # block hash -> medians, from ProcessProposal to FinalizeBlock
        self._validated: dict[bytes, dict[int, int]] = {}

    def _injected(self, height: int) -> bool:
        """Whether block ``height`` starts with the last height's
        extended commit info: extensions were on at height - 1."""
        h = self.vote_extensions_enable_height
        return 0 < h <= height - 1

    async def init_chain(self, req: abci.InitChainRequest
                         ) -> abci.InitChainResponse:
        self.chain_id = req.chain_id
        params = req.consensus_params
        if params is not None:
            self.vote_extensions_enable_height = \
                params.feature.vote_extensions_enable_height
        self.db.set(_GENESIS_KEY, json.dumps({
            "chain_id": self.chain_id,
            "ve_height": self.vote_extensions_enable_height}).encode())
        return await super().init_chain(req)

    # -- vote extensions ----------------------------------------------------
    async def extend_vote(self, req: abci.ExtendVoteRequest
                          ) -> abci.ExtendVoteResponse:
        return abci.ExtendVoteResponse(vote_extension=encode_prices(
            seeded_prices(self.seed, req.height, self.validator_address)))

    async def verify_vote_extension(
            self, req: abci.VerifyVoteExtensionRequest
    ) -> abci.VerifyVoteExtensionResponse:
        try:
            decode_prices(req.vote_extension)
        except PayloadError:
            return abci.VerifyVoteExtensionResponse(
                status=abci.VERIFY_VOTE_EXTENSION_STATUS_REJECT)
        return abci.VerifyVoteExtensionResponse(
            status=abci.VERIFY_VOTE_EXTENSION_STATUS_ACCEPT)

    # -- the injected commit info -------------------------------------------
    async def prepare_proposal(self, req: abci.PrepareProposalRequest
                               ) -> abci.PrepareProposalResponse:
        resp = await super().prepare_proposal(req)
        if self._injected(req.height):
            resp.txs = [encode(abci_pb.EXTENDED_COMMIT_INFO,
                               _ext_commit_info_to(req.local_last_commit))
                        ] + resp.txs
        return resp

    async def process_proposal(self, req: abci.ProcessProposalRequest
                               ) -> abci.ProcessProposalResponse:
        txs = req.txs
        if self._injected(req.height):
            medians = self._validate(req.height, txs[0],
                                     req.proposed_last_commit) \
                if txs else None
            if medians is None:
                return abci.ProcessProposalResponse(
                    status=abci.PROCESS_PROPOSAL_STATUS_REJECT)
            self._validated = {req.hash: medians}
            txs = txs[1:]
        return await super().process_proposal(
            abci.ProcessProposalRequest(txs=txs, height=req.height))

    def _pub_key(self, address: bytes):
        pk = self._pub_keys.get(address)
        if pk is None:
            from ..crypto import encoding as crypto_encoding
            entry = self._val_addr_to_pubkey.get(address)
            if entry is None:
                return None
            pk = crypto_encoding.pub_key_from_type_and_bytes(*entry)
            self._pub_keys[address] = pk
        return pk

    def _validate(self, height: int, tx: bytes,
                  last_commit: abci.CommitInfo) -> Optional[dict]:
        """The medians of the injected commit info of ``height - 1``,
        or None where it does not hold (the module's docstring)."""
        from ..crypto import batch as crypto_batch
        from ..types import canonical

        with tracing.span(tracing.ABCI, "oracle_validate") as sp:
            info = _decode_info(tx)
            if info is None:
                return None
            sp.note(votes=len(info.votes))
            if [(v.validator.address, v.block_id_flag)
                    for v in info.votes] != \
                    [(v.validator.address, v.block_id_flag)
                     for v in last_commit.votes]:
                return None
            total = sum(v.validator.power for v in last_commit.votes)
            signed, verifier, prices = 0, None, []
            for vote, given in zip(info.votes, last_commit.votes):
                if vote.block_id_flag != _BLOCK_ID_FLAG_COMMIT:
                    continue
                pk = self._pub_key(vote.validator.address)
                if pk is None or not vote.extension_signature:
                    return None
                try:
                    prices.append(decode_prices(vote.vote_extension))
                except PayloadError:
                    return None
                if verifier is None:
                    verifier = crypto_batch.create_batch_verifier(pk)
                verifier.add(pk, canonical.vote_extension_sign_bytes(
                    self.chain_id, height - 1, info.round,
                    vote.vote_extension), vote.extension_signature)
                signed += given.validator.power
            if verifier is None or signed * 3 <= total * 2:
                return None
            ok, _ = verifier.verify()
            if not ok:
                return None
        return self._medians(prices)

    def _medians(self, prices: list[dict]) -> dict[int, int]:
        return {p: median([got[p] for got in prices if p in got])
                for p in range(PRICE_PAIRS)
                if any(p in got for got in prices)}

    def _decided_medians(self, tx: bytes) -> dict[int, int]:
        """The medians of a block this app did not see proposed
        (blocksync, the handshake's replay): the network decided it,
        so its commit info is taken as given."""
        info = _decode_info(tx)
        prices = []
        for vote in info.votes if info is not None else ():
            if vote.block_id_flag == _BLOCK_ID_FLAG_COMMIT:
                try:
                    prices.append(decode_prices(vote.vote_extension))
                except PayloadError:
                    continue
        return self._medians(prices)

    def _finalize_txs(self, req: abci.FinalizeBlockRequest
                      ) -> list[abci.ExecTxResult]:
        if not self._injected(req.height) or not req.txs:
            return super()._finalize_txs(req)
        medians = self._validated.pop(req.hash, None)
        if medians is None:
            medians = self._decided_medians(req.txs[0])
        self._validated.clear()
        for pair, price in sorted(medians.items()):
            self.tree.set(oracle_key(pair), b"%d" % price)
        return [abci.ExecTxResult(code=0)] + super()._finalize_txs(
            replace(req, txs=req.txs[1:]))


def _decode_info(tx: bytes) -> Optional[abci.ExtendedCommitInfo]:
    try:
        return _ext_commit_info_from(decode(abci_pb.EXTENDED_COMMIT_INFO,
                                            tx))
    except (ValueError, TypeError):     # not an ExtendedCommitInfo
        return None

