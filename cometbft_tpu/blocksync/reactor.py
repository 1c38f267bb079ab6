"""Blocksync reactor: serve and fetch committed blocks.

Reference: internal/blocksync/reactor.go (:611) — BlocksyncChannel 0x40;
verifies the first block's commit with VerifyCommitLight using the
SECOND block's LastCommit, then ApplyBlock; switches to consensus when
caught up.
"""
from __future__ import annotations

import asyncio
from typing import Callable, Optional

from ..libs import tracing
from ..libs.log import Logger, new_logger
from ..p2p.conn import ChannelDescriptor
from ..p2p.switch import Peer, Reactor
from ..state.state import State as SMState
from ..types.block import Block
from ..types.block_id import BlockID
from ..types.commit import ExtendedCommit
from ..state.validation import BlockValidationError
from ..types.validation import VerificationError, verify_commit_light
from ..wire import pb, encode, decode
from ..wire.proto import F, Msg
from .pool import BlockPool

BLOCKSYNC_CHANNEL = 0x40
_STATUS_UPDATE_INTERVAL_S = 2.0
_SWITCH_TO_CONSENSUS_INTERVAL_S = 0.2

BLOCK_REQUEST = Msg("cometbft.blocksync.v2.BlockRequest",
                    F(1, "height", "int64"))
NO_BLOCK_RESPONSE = Msg("cometbft.blocksync.v2.NoBlockResponse",
                        F(1, "height", "int64"))
STATUS_REQUEST = Msg("cometbft.blocksync.v2.StatusRequest")
STATUS_RESPONSE = Msg("cometbft.blocksync.v2.StatusResponse",
                      F(1, "height", "int64"), F(2, "base", "int64"))
BLOCK_RESPONSE = Msg(
    "cometbft.blocksync.v2.BlockResponse",
    F(1, "block", "msg", msg=pb.BLOCK),
    F(2, "ext_commit", "msg", msg=pb.EXTENDED_COMMIT),
)
MESSAGE = Msg(
    "cometbft.blocksync.v2.Message",
    F(1, "block_request", "msg", msg=BLOCK_REQUEST),
    F(2, "no_block_response", "msg", msg=NO_BLOCK_RESPONSE),
    F(3, "block_response", "msg", msg=BLOCK_RESPONSE),
    F(4, "status_request", "msg", msg=STATUS_REQUEST),
    F(5, "status_response", "msg", msg=STATUS_RESPONSE),
)


class BlocksyncReactor(Reactor):
    def __init__(self, state: SMState, block_exec, block_store,
                 active: bool,
                 on_caught_up: Optional[Callable] = None,
                 logger: Optional[Logger] = None,
                 metrics=None):
        """on_caught_up(state, height) fires once when sync completes
        (the node switches to consensus there — reference:
        SwitchToConsensus)."""
        super().__init__("BLOCKSYNC")
        if logger is not None:
            self.logger = logger
        from .metrics import Metrics
        self.metrics = metrics if metrics is not None else Metrics()
        self.state = state
        self.block_exec = block_exec
        self.block_store = block_store
        self.active = active
        self.on_caught_up = on_caught_up
        self.pool: Optional[BlockPool] = None
        self._tasks: list = []   # SupervisedTask handles

    def get_channels(self) -> list[ChannelDescriptor]:
        return [ChannelDescriptor(id=BLOCKSYNC_CHANNEL, priority=5,
                                  send_queue_capacity=1000)]

    # ------------------------------------------------------------------
    async def start_sync(self) -> None:
        """Begin syncing (reference: OnStart when blocksync enabled).
        Both routines (and the pool's requester loop) are
        supervisor-owned: a crash restarts the loop instead of
        silently wedging the sync."""
        self.pool = BlockPool(
            self.block_store.height + 1
            if self.block_store.height else
            max(self.state.initial_height, 1),
            send_request=self._send_block_request,
            ban_peer=self._ban_peer,
            supervisor=self.supervisor)
        self.pool.start()
        self.metrics.syncing.set(1)
        self._tasks = [
            self.supervisor.spawn(lambda: self._sync_routine(),
                                  name="blocksync_sync",
                                  kind="blocksync_sync"),
            self.supervisor.spawn(lambda: self._status_routine(),
                                  name="blocksync_status",
                                  kind="blocksync_status"),
        ]

    async def stop_sync(self) -> None:
        if self.pool is not None:
            self.pool.stop()
        for t in self._tasks:
            t.cancel()
        self._tasks = []

    # ------------------------------------------------------------------
    async def add_peer(self, peer: Peer) -> None:
        # announce our range; ask for theirs
        peer.send(BLOCKSYNC_CHANNEL, encode(MESSAGE, {
            "status_response": {
                "height": self.block_store.height,
                "base": self.block_store.base}}))
        if self.active:
            peer.send(BLOCKSYNC_CHANNEL,
                      encode(MESSAGE, {"status_request": {}}))

    async def remove_peer(self, peer: Peer, reason: str) -> None:
        if self.pool is not None:
            self.pool.remove_peer(peer.id)

    async def receive(self, chan_id: int, peer: Peer,
                      msg_bytes: bytes) -> None:
        t0 = tracing.now_ns()
        d = decode(MESSAGE, msg_bytes)
        if "block_request" in d:
            await self._respond_to_block_request(
                peer, d["block_request"].get("height", 0))
        elif "status_request" in d:
            peer.send(BLOCKSYNC_CHANNEL, encode(MESSAGE, {
                "status_response": {
                    "height": self.block_store.height,
                    "base": self.block_store.base}}))
        elif "status_response" in d and self.pool is not None:
            sr = d["status_response"]
            self.pool.set_peer_range(peer.id, sr.get("base", 0),
                                     sr.get("height", 0))
        elif "block_response" in d and self.pool is not None:
            br = d["block_response"]
            if br.get("block") is None:
                return
            block = Block.from_proto(br["block"])
            ec = ExtendedCommit.from_proto(br["ext_commit"]) \
                if br.get("ext_commit") is not None else None
            self.pool.add_block(peer.id, block, ec, len(msg_bytes))
            tracing.record_span(tracing.BLOCKSYNC, "block_decode", t0,
                                height=block.header.height)
        elif "no_block_response" in d:
            pass   # peer doesn't have it; timeouts handle reassignment

    async def _respond_to_block_request(self, peer: Peer,
                                        height: int) -> None:
        with tracing.span(tracing.BLOCKSYNC, "block_serve",
                          height=height):
            block = self.block_store.load_block(height)
            if block is None:
                peer.send(BLOCKSYNC_CHANNEL, encode(MESSAGE, {
                    "no_block_response": {"height": height}}))
                return
            resp: dict = {"block": block.to_proto()}
            ec = self.block_store.load_block_ext_commit(height)
            if ec is not None:
                resp["ext_commit"] = ec.to_proto()
            peer.send(BLOCKSYNC_CHANNEL,
                      encode(MESSAGE, {"block_response": resp}))

    # ------------------------------------------------------------------
    def _send_block_request(self, peer_id: str, height: int) -> bool:
        peer = self.switch.peers.get(peer_id) if self.switch else None
        if peer is None:
            return False
        return peer.send(BLOCKSYNC_CHANNEL, encode(MESSAGE, {
            "block_request": {"height": height}}))

    def _ban_peer(self, peer_id: str, reason: str) -> None:
        if self.switch is None:
            return
        peer = self.switch.peers.get(peer_id)
        if peer is not None:
            # supervised one-shot teardown (AST-checked invariant)
            self.supervisor.spawn(
                lambda: self.switch.stop_peer(peer, reason),
                name=f"stop_peer:{peer_id[:12]}", kind="stop_peer")

    # ------------------------------------------------------------------
    async def _status_routine(self) -> None:
        try:
            while True:
                if self.switch is not None:
                    self.switch.broadcast(
                        BLOCKSYNC_CHANNEL,
                        encode(MESSAGE, {"status_request": {}}))
                await asyncio.sleep(_STATUS_UPDATE_INTERVAL_S)
        except asyncio.CancelledError:
            raise

    async def _sync_routine(self) -> None:
        """Verify-then-apply loop (reference: poolRoutine /
        processBlock)."""
        caught_up_since: float = 0.0
        try:
            while True:
                pool = self.pool
                if pool is None:
                    return
                # park until a block arrives / the head advances; the
                # 250ms fallback drives the caught-up grace check
                t_wait = tracing.now_ns()
                parked = await pool.wait_apply()
                t_woke = tracing.now_ns()
                # caught up?  Require it to HOLD across more than one
                # status-broadcast round so a single early low-height
                # StatusResponse can't end the sync prematurely
                # (reference: switchToConsensusTicker + grace period).
                now = asyncio.get_running_loop().time()
                if pool.peers and pool.is_caught_up():
                    if caught_up_since == 0.0:
                        caught_up_since = now
                    elif now - caught_up_since > \
                            2 * _STATUS_UPDATE_INTERVAL_S:
                        self.logger.info(
                            "blocksync complete; switching to "
                            "consensus", height=pool.height - 1)
                        await self._finish_sync(pool)
                        return
                else:
                    caught_up_since = 0.0

                first, second, first_ext = pool.peek_two_blocks()
                ready = first is not None and second is not None
                if parked or not ready:
                    # the loop had nothing to apply
                    tracing.record_span(
                        tracing.BLOCKSYNC, "sync_wait", t_wait, t_woke)
                if not ready:
                    continue
                with tracing.span(tracing.BLOCKSYNC, "sync_height",
                                  height=first.header.height,
                                  runtime=True) as sp:
                    applied = await self._sync_height(
                        pool, first, second, first_ext)
                    sp.note(outcome="applied" if applied
                            else "refused")
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self.logger.error("sync routine failed", err=str(e))
            raise

    async def _sync_height(self, pool, first, second,
                           first_ext) -> bool:
        """Verify, store and apply ``first`` (certified by
        ``second.last_commit``).  False when it was refused and asked
        for again."""
        height = first.header.height
        with tracing.span(tracing.BLOCKSYNC, "part_set"):
            first_parts = first.make_part_set()
            first_id = BlockID(hash=first.hash(),
                               part_set_header=first_parts.header())
        try:
            # the second block's LastCommit certifies the first
            if second.last_commit is None:
                raise VerificationError("missing last commit")
            verify_commit_light(
                self.state.chain_id, self.state.validators,
                first_id, height, second.last_commit)
            # the commit only certifies the header hash; validate
            # the full block (data/evidence hashes, header wiring)
            # before persisting/executing it — reference:
            # internal/blocksync/reactor.go:552 ValidateBlock
            self.block_exec.validate_block(self.state, first)
        except (VerificationError, BlockValidationError) as e:
            self.logger.error("invalid block in sync", height=height,
                              err=str(e))
            pool.redo_request(height, str(e))
            pool.redo_request(height + 1, str(e))
            return False

        seen_commit = second.last_commit
        ext_enabled = self.state.consensus_params.feature \
            .vote_extensions_enabled(height)
        if ext_enabled:
            if first_ext is None:
                self.logger.error(
                    "peer sent block without extended commit "
                    "while extensions are enabled", height=height)
                pool.redo_request(height, "missing extended commit")
                return False
            try:
                # reference reactor.go:565 — never persist an
                # extended commit missing extension signatures
                first_ext.ensure_extensions(True)
            except Exception as e:
                self.logger.error(
                    "peer sent extended commit with missing "
                    "extension signatures", height=height, err=str(e))
                pool.redo_request(height, str(e))
                return False
            self.block_store.save_block_with_extended_commit(
                first, first_parts, first_ext)
        else:
            self.block_store.save_block(first, first_parts,
                                        seen_commit)
        self.state = await self.block_exec.apply_verified_block(
            self.state, first_id, first, pool.max_peer_height())
        self.metrics.latest_block_height.set(height)
        self.metrics.num_txs.set(len(first.data.txs))
        self.metrics.total_txs.add(len(first.data.txs))
        self.metrics.block_size_bytes.set(first_parts.byte_size)
        pool.pop_request()
        return True

    async def _finish_sync(self, pool) -> None:
        """Hand off to consensus WITHOUT cancelling the task running
        this method — a pending self-cancellation would abort the
        switch at its first real suspension point."""
        height = pool.height - 1
        pool.stop()
        self.metrics.syncing.set(0)
        self.pool = None
        current = asyncio.current_task()
        for t in self._tasks:
            if getattr(t, "runner", t) is not current:
                t.cancel()
        self._tasks = []
        if self.on_caught_up is not None:
            await self.on_caught_up(self.state, height)
