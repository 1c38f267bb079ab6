"""Node: assembles DBs, state, ABCI, mempool, consensus, p2p, RPC.

Reference: node/node.go:53 (Node struct), node/setup.go:102-750 (the
constructors), boot order in OnStart (:~370): RPC listeners → transport
listen → switch start (dials peers) → consensus.
"""
from __future__ import annotations

import asyncio
import os
from typing import Optional

from ..abci.client import AppConns, ClientCreator
from ..abci.kvstore import KVStoreApplication
from ..config import Config
from ..consensus.reactor import ConsensusReactor
from ..consensus.replay import Handshaker, ReplayError, catchup_replay
from ..consensus.state import ConsensusState
from ..consensus.wal import WAL, CorruptWALError
from ..db import new_db
from ..libs.log import Logger, new_logger, set_level
from ..mempool import CListMempool
from ..mempool.reactor import MempoolReactor
from ..p2p.key import NodeKey
from ..p2p.switch import Switch
from ..privval import FilePV
from ..state import make_genesis_state
from ..state.execution import BlockExecutor
from ..state.store import Store
from ..store import BlockStore
from ..types.events import EventBus
from ..types.genesis import GenesisDoc, pub_key_to_json
from ..abci import types as abci


class NodeError(Exception):
    pass


def init_files(config: Config, chain_id: str = "") -> GenesisDoc:
    """`cometbft init`: write node key, priv validator, genesis
    (reference: cmd/cometbft/commands/init.go)."""
    import secrets as _secrets
    from ..types.genesis import GenesisValidator
    from ..types.timestamp import Timestamp

    home = config.base.home
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)

    pv = FilePV.load_or_generate(
        config.base.path(config.base.priv_validator_key_file),
        config.base.path(config.base.priv_validator_state_file))
    NodeKey.load_or_gen(config.base.path(config.base.node_key_file))

    genesis_path = config.base.path(config.base.genesis_file)
    if os.path.exists(genesis_path):
        return GenesisDoc.from_file(genesis_path)
    doc = GenesisDoc(
        chain_id=chain_id or f"test-chain-{_secrets.token_hex(3)}",
        genesis_time=Timestamp.now(),
        validators=[GenesisValidator(
            address=b"", pub_key=pv.get_pub_key(), power=10)],
    )
    doc.validate_and_complete()
    doc.save_as(genesis_path)
    return doc


class Node:
    def __init__(self, config: Config,
                 app=None,
                 genesis_doc: Optional[GenesisDoc] = None,
                 logger: Optional[Logger] = None):
        self.config = config
        from ..config import validate_basic
        validate_basic(config)
        self.logger = logger if logger is not None else \
            new_logger("node")
        set_level(config.base.log_level)
        home = config.base.home
        db_dir = config.base.path(config.base.db_dir)

        # flight recorder (libs/tracing.py): the always-on span rings
        # every subsystem appends to; crash dumps land in the data dir
        # unless instrumentation.dump_dir points elsewhere
        from ..libs import tracing
        dump_dir = config.instrumentation.dump_dir
        tracing.configure(
            enabled=config.instrumentation.trace_enabled,
            buffer_size=config.instrumentation.trace_buffer_size,
            categories=config.instrumentation.trace_categories or None,
            dump_dir=config.base.path(dump_dir) if dump_dir
            else db_dir,
            anchor_interval_s=config.instrumentation
            .trace_anchor_interval_s)
        from ..types import signature_cache
        signature_cache.set_default_capacity(
            config.base.signature_cache_size)

        # --- genesis & identity -----------------------------------------
        self.genesis_doc = genesis_doc if genesis_doc is not None else \
            GenesisDoc.from_file(config.base.path(
                config.base.genesis_file))
        self.node_key = NodeKey.load_or_gen(
            config.base.path(config.base.node_key_file))
        # stamp the recorder with our identity (the key loads after
        # configure) so every dump/scrape names the node it came from
        tracing.recorder().node_id = self.node_key.id[:12]
        if config.base.priv_validator_laddr:
            # remote signer: key lives in an external process
            # (reference: createAndStartPrivValidatorSocketClient,
            # setup.go:715); connection established in start()
            self.priv_validator = None
        else:
            self.priv_validator = FilePV.load_or_generate(
                config.base.path(config.base.priv_validator_key_file),
                config.base.path(config.base.priv_validator_state_file))

        # --- storage ----------------------------------------------------
        backend = config.base.db_backend
        self.block_store = BlockStore(new_db("blockstore", backend,
                                             db_dir))
        self.state_store = Store(new_db("state", backend, db_dir))

        # --- application ------------------------------------------------
        if app is None and config.base.abci in ("builtin",
                                                "builtin_unsync"):
            if config.base.proxy_app in ("kvstore", "persistent_kvstore"):
                # snapshots on by default so any builtin-kvstore node
                # can serve statesync joiners (reference: e2e kvstore
                # manifests set SnapshotInterval; snapshots are cheap)
                app = KVStoreApplication(
                    db=new_db("app", backend, db_dir),
                    snapshot_interval=10)
            else:
                raise NodeError(
                    f"unknown proxy_app {config.base.proxy_app!r} "
                    f"(pass an Application instance for custom apps)")
        self.app = app
        self.app_conns = ClientCreator(
            app=app, addr=config.base.proxy_app,
            transport=config.base.abci).new_app_conns()

        # --- state ------------------------------------------------------
        state = self.state_store.load()
        if state is None:
            state = make_genesis_state(self.genesis_doc)
            self.state_store.save(state)
        self.initial_state = state

        # --- event bus --------------------------------------------------
        self.event_bus = EventBus()

        # --- metrics: one shared registry, per-subsystem families fed
        # at the point of action (reference: per-package metrics.go,
        # served at /metrics) -------------------------------------------
        from ..abci.metrics import Metrics as ProxyMetrics
        from ..blocksync.metrics import Metrics as BlocksyncMetrics
        from ..consensus.metrics import Metrics as ConsensusMetrics
        from ..libs.metrics import Registry
        from ..libs.supervisor import Metrics as SupervisorMetrics
        from ..libs.supervisor import Supervisor
        from ..mempool.metrics import Metrics as MempoolMetrics
        from ..p2p.metrics import Metrics as P2PMetrics
        from ..state.metrics import Metrics as StateMetrics
        from ..statesync.metrics import Metrics as StatesyncMetrics
        self.metrics_registry = Registry()
        self.consensus_metrics = ConsensusMetrics(self.metrics_registry)
        self.mempool_metrics = MempoolMetrics(self.metrics_registry)
        self.p2p_metrics = P2PMetrics(self.metrics_registry)
        self.blocksync_metrics = BlocksyncMetrics(self.metrics_registry)
        self.statesync_metrics = StatesyncMetrics(self.metrics_registry)
        self.state_metrics = StateMetrics(self.metrics_registry)
        self.proxy_metrics = ProxyMetrics(self.metrics_registry)
        # failure-domain supervision: node-level loops (consensus
        # receive) run under this supervisor; the switch owns a
        # sibling sharing the same metric family, so every restart is
        # visible at /metrics
        self.supervisor_metrics = SupervisorMetrics(
            self.metrics_registry)
        self.supervisor = Supervisor("node", logger=self.logger,
                                     metrics=self.supervisor_metrics)
        # liveness plane (libs/health.py): event-loop lag histogram
        # sampled by a supervised task started in start(), served by
        # /health and /metrics
        from ..libs.health import Metrics as HealthMetrics
        self.health_metrics = HealthMetrics(self.metrics_registry)

        # --- lightserve: height-keyed RPC response cache ----------------
        # immutable responses (blocks/commits/light blocks/multiproofs
        # below the tip) served from RAM so light-client read traffic
        # never reaches the stores (docs/light_proofs.md)
        from ..lightserve.cache import Metrics as LightserveMetrics
        from ..lightserve.cache import ResponseCache
        self.lightserve_cache = None
        if config.rpc.cache_max_bytes > 0:
            self.lightserve_cache = ResponseCache(
                config.rpc.cache_max_bytes,
                metrics=LightserveMetrics(self.metrics_registry))
            # statetree pruning must not drop a version the cache
            # still serves responses for — a client that just read a
            # cached height could no longer get it proven
            if hasattr(self.app, "version_pin"):
                cache = self.lightserve_cache
                self.app.version_pin = cache.heights

        # --- mempool ----------------------------------------------------
        self.mempool: Optional[CListMempool] = None
        self.mempool_reactor: Optional[MempoolReactor] = None

        # --- consensus (created in start after handshake) ---------------
        self.consensus_state: Optional[ConsensusState] = None
        self.consensus_reactor: Optional[ConsensusReactor] = None

        # --- p2p --------------------------------------------------------
        self.switch = Switch(
            self.node_key, self.genesis_doc.chain_id,
            listen_addr=config.p2p.laddr.replace("tcp://", ""),
            moniker=config.base.moniker,
            send_rate=config.p2p.send_rate,
            recv_rate=config.p2p.recv_rate,
            metrics=self.p2p_metrics,
            supervisor_metrics=self.supervisor_metrics)
        self.switch.private_ids = {
            s.strip() for s in
            config.p2p.private_peer_ids.split(",") if s.strip()}

        self._rpc_server = None
        self._started = False

    # ------------------------------------------------------------------
    async def start(self) -> None:
        """Boot order mirrors node.OnStart."""
        cfg = self.config

        # resolve the crypto backend here, once, off the event loop:
        # under `auto` the first ask starts the JAX backend, which
        # blocks for seconds on a chip host — and fails the start,
        # loudly, if the backend cannot come up
        from ..crypto import batch as crypto_batch
        backend = await asyncio.to_thread(crypto_batch.get_backend)
        if backend == "tpu":
            # a device node needs the native host prep before its
            # first batch and the kernel compiled before consensus
            # starts — else the first commit stalls for a Mosaic
            # compile inside the consensus timeouts
            await asyncio.to_thread(
                warm_device_path,
                self.initial_state.validators.size())
        else:
            # compile the C++ fast paths off-thread so the first big
            # merkle hash in the consensus loop never waits on g++
            from ..crypto._native_loader import prebuild_async
            prebuild_async()

        if cfg.base.priv_validator_laddr:
            from ..privval.signer import (
                RetrySignerClient, SignerClient, SignerListenerEndpoint,
            )
            self._signer_endpoint = SignerListenerEndpoint(
                cfg.base.priv_validator_laddr)
            await self._signer_endpoint.start()
            await self._signer_endpoint.wait_for_signer()
            client = RetrySignerClient(SignerClient(
                self._signer_endpoint, self.genesis_doc.chain_id))
            await client.fetch_pub_key()
            self.priv_validator = client

        # out-of-process app: open the four socket AppConns first
        # (reference: createAndStartProxyAppConns, setup.go:179)
        await self.app_conns.start()

        # deadline propagation on the remote ABCI boundary: a wedged
        # app process cannot hang consensus forever (builtin apps
        # share our event loop, so no deadline there)
        if cfg.base.abci not in ("local", "builtin",
                                 "builtin_unsync") and \
                cfg.base.abci_call_timeout_ns > 0:
            from ..abci.client import apply_deadlines
            apply_deadlines(
                self.app_conns,
                default_timeout_s=cfg.base.abci_call_timeout_ns / 1e9,
                retries=cfg.base.abci_call_retries)

        # per-method ABCI timing (reference: proxy metrics)
        from ..abci.metrics import instrument_app_conns
        instrument_app_conns(self.app_conns, self.proxy_metrics)

        # optional ABCI call-trace recording for the grammar checker
        # (reference: the e2e app records requests for
        # test/e2e/pkg/grammar/checker.go)
        if cfg.base.abci_grammar_trace:
            from ..abci.grammar import RecordingClient
            self.abci_trace: list = []
            for _conn in ("consensus", "mempool", "query", "snapshot"):
                setattr(self.app_conns, _conn,
                        RecordingClient(getattr(self.app_conns, _conn),
                                        self.abci_trace))

        # ABCI handshake reconciles app and store
        handshaker = Handshaker(self.state_store, self.initial_state,
                                self.block_store, self.genesis_doc,
                                logger=new_logger("handshaker"))
        await handshaker.handshake(self.app_conns)
        state = self.state_store.load() or self.initial_state

        # mempool (lanes from the app's Info)
        info = await self.app_conns.query.info(abci.InfoRequest())
        self.mempool = CListMempool(
            cfg.mempool, self.app_conns.mempool,
            lanes=info.lane_priorities or None,
            default_lane=info.default_lane,
            height=state.last_block_height,
            metrics=self.mempool_metrics)

        # pruner service (reference: state/pruner.go via setup.go)
        from ..state.pruner import Pruner
        self.pruner = Pruner(
            self.state_store, self.block_store,
            new_db("pruner", cfg.base.db_backend,
                   cfg.base.path(cfg.base.db_dir)),
            # must be known BEFORE the first prune pass: with a
            # companion configured, blocks it hasn't released must
            # survive restarts
            companion_enabled=bool(cfg.grpc.privileged_laddr and
                                   cfg.grpc.pruning_service_enabled),
            metrics=self.state_metrics)
        # started below, once the indexers are attached — a pass that
        # ran before attachment would skip indexer pruning

        # evidence pool
        from ..evidence import EvidencePool
        from ..evidence.reactor import EvidenceReactor
        self.evidence_pool = EvidencePool(
            new_db("evidence", cfg.base.db_backend,
                   cfg.base.path(cfg.base.db_dir)),
            self.state_store, self.block_store)

        # indexers + service (reference: setup.go createAndStartIndexerService)
        from ..indexer import BlockIndexer, IndexerService, TxIndexer
        if cfg.tx_index.indexer == "kv":
            idx_db = new_db("tx_index", cfg.base.db_backend,
                            cfg.base.path(cfg.base.db_dir))
            self.tx_indexer = TxIndexer(idx_db)
            self.block_indexer = BlockIndexer(idx_db)
            self.indexer_service = IndexerService(
                self.tx_indexer, self.block_indexer, self.event_bus)
            await self.indexer_service.start()
        elif cfg.tx_index.indexer == "psql":
            # relational event sink (reference: state/indexer/sink/psql
            # wired via setup.go; embedded SQL engine in this build)
            import os as _os
            from ..indexer import SQLEventSink
            conn = cfg.tx_index.psql_conn or cfg.base.path(
                _os.path.join(cfg.base.db_dir, "events.sqlite"))
            self._event_sink = SQLEventSink(
                conn, self.genesis_doc.chain_id)
            self.tx_indexer = self._event_sink.tx_indexer
            self.block_indexer = self._event_sink.block_indexer
            self.indexer_service = IndexerService(
                self.tx_indexer, self.block_indexer, self.event_bus)
            await self.indexer_service.start()
        else:
            self.tx_indexer = None
            self.block_indexer = None
            self.indexer_service = None
        # companion pruning covers the indexers too (pruner.go)
        self.pruner.tx_indexer = self.tx_indexer
        self.pruner.block_indexer = self.block_indexer
        await self.pruner.start()

        block_exec = BlockExecutor(
            self.state_store, self.app_conns.consensus,
            mempool=self.mempool, evpool=self.evidence_pool,
            event_bus=self.event_bus,
            block_store=self.block_store,
            metrics=self.state_metrics)
        block_exec.pruner = self.pruner

        wal_path = cfg.base.path(cfg.consensus.wal_file)
        self.consensus_state = ConsensusState(
            cfg.consensus, state, block_exec, self.block_store,
            priv_validator=self.priv_validator,
            event_bus=self.event_bus, wal=WAL(wal_path),
            metrics=self.consensus_metrics,
            supervisor=self.supervisor)
        try:
            try:
                await catchup_replay(self.consensus_state, wal_path)
            except CorruptWALError as e:
                # reference state.go OnStart: one repair attempt — keep
                # the valid prefix, stash the corrupt tail, replay again
                from ..consensus.wal import repair_wal_file
                # justified synchronous durability point: one-shot WAL
                # repair during startup replay — consensus is not
                # running yet and the truncate must complete before
                # anything else touches the WAL
                # bftlint: disable=blocking-in-async
                dropped = repair_wal_file(wal_path)
                # repair may have renamed the head file out from under
                # the already-open append handle
                self.consensus_state.wal.reopen()
                self.logger.error(
                    "WAL corrupted; repaired by truncating",
                    err=str(e), dropped_bytes=dropped)
                await catchup_replay(self.consensus_state, wal_path)
        except (ReplayError, CorruptWALError) as e:
            # reference state.go OnStart: a non-corruption catchup error
            # (e.g. the end-height barrier was never written because we
            # crashed between block save and WAL fsync — the handshake
            # already replayed the block) is logged and the node starts
            # anyway; only height-in-flight votes are lost
            self.logger.error(
                "Error on catchup replay; proceeding to start node "
                "anyway", err=str(e))
        # WAL catchup can itself finalize a block — use the freshest
        # state for the blocksync decision and reactor
        state = self.state_store.load() or state

        # statesync runs only on a fresh node; it always hands off to
        # blocksync, so blocksync is forced on behind it (reference:
        # setup.go:569 startStateSync -> blocksync reactor)
        run_statesync = (cfg.statesync.enable and
                         state.last_block_height == 0)

        # blocksync decision (reference: setup.go — sync unless we are
        # the only validator)
        run_blocksync = run_statesync or (
            cfg.blocksync.enable and
            not _only_validator_is_us(
                state, self.priv_validator.get_pub_key()))

        self.consensus_reactor = ConsensusReactor(
            self.consensus_state, wait_sync=run_blocksync)
        self.switch.add_reactor(self.consensus_reactor)
        self.mempool_reactor = MempoolReactor(self.mempool, cfg.mempool)
        self.switch.add_reactor(self.mempool_reactor)
        self.switch.add_reactor(EvidenceReactor(self.evidence_pool))

        from ..blocksync import BlocksyncReactor

        async def _switch_to_consensus(new_state, height):
            """Reference: consensus.Reactor.SwitchToConsensus —
            reconstruct LastCommit from the stored seen commit before
            updating to the synced state."""
            if new_state.last_block_height > 0:
                self.consensus_state.rs.last_commit = None
                # off the event loop: the seen commit's batch verify
                # is O(validators) kernel work and the p2p loop is
                # live during the switch (crypto/pipeline.py seam)
                await self.consensus_state \
                    .reconstruct_last_commit_off_loop(new_state)
            self.consensus_state.update_to_state(new_state)
            # flip wait_sync only once RoundState reflects the synced
            # height: the off-loop reconstruction above yields the
            # loop, and a peer connecting mid-window must not be sent
            # a NewRoundStep built from the stale pre-sync state
            self.consensus_reactor.wait_sync = False
            await self.consensus_state.start()
            self.logger.info("Switched from blocksync to consensus",
                             height=height)

        self.blocksync_reactor = BlocksyncReactor(
            state, block_exec, self.block_store,
            active=run_blocksync,
            on_caught_up=_switch_to_consensus,
            metrics=self.blocksync_metrics)
        self.switch.add_reactor(self.blocksync_reactor)
        self._run_blocksync = run_blocksync

        # statesync (reference: setup.go:569 startStateSync): a fresh
        # node with statesync enabled bootstraps from a peer snapshot,
        # with trusted state/commit fetched via the light client over
        # the configured RPC servers; every node serves snapshots
        from ..statesync.reactor import StatesyncReactor
        from ..statesync.syncer import Syncer
        self._statesync_syncer = None
        if run_statesync:
            sp = await self._make_state_provider(state)
            syncer = Syncer(
                self.app_conns, sp,
                request_chunk=lambda snap, i:
                    self.statesync_reactor.request_chunk(snap, i),
                chunk_timeout_s=(cfg.statesync
                                 .chunk_request_timeout_ns / 1e9),
                chunk_dir=cfg.statesync.temp_dir or None)
            self._statesync_syncer = syncer
            self.statesync_reactor = StatesyncReactor(
                self.app_conns, syncer,
                metrics=self.statesync_metrics)
        else:
            self.statesync_reactor = StatesyncReactor(
                self.app_conns, metrics=self.statesync_metrics)
        self.switch.add_reactor(self.statesync_reactor)

        # event-loop lag sampler: always-on liveness signal behind
        # /health and cometbft_node_event_loop_lag_seconds; dies with
        # the supervisor in stop()
        if cfg.instrumentation.loop_lag_interval_s > 0:
            from ..libs.health import LoopLagSampler
            sampler = LoopLagSampler(
                self.health_metrics,
                interval_s=cfg.instrumentation.loop_lag_interval_s)
            self.supervisor.spawn(sampler.run, name="loop_lag",
                                  kind="loop_lag")

        # RPC before p2p (reference: OnStart order)
        if cfg.rpc.laddr:
            from ..rpc.server import RPCServer
            self._rpc_server = RPCServer(self, cfg.rpc)
            await self._rpc_server.start()

        # live profiling endpoint (reference: node.go pprofSrv, gated
        # by instrumentation.pprof_laddr)
        if cfg.instrumentation.pprof_listen_addr:
            from ..libs.pprof import PprofServer
            self._pprof_server = PprofServer(
                cfg.instrumentation.pprof_listen_addr)
            await self._pprof_server.start()

        # gRPC data-companion services (reference: node.go grpcSrv +
        # grpcPrivSrv, config.go GRPCConfig)
        if cfg.grpc.laddr:
            from ..rpc.grpc import GRPCServer
            self._grpc_server = GRPCServer(
                block_store=self.block_store,
                state_store=self.state_store,
                event_bus=self.event_bus,
                version_service=cfg.grpc.version_service_enabled,
                block_service=cfg.grpc.block_service_enabled,
                block_results_service=(
                    cfg.grpc.block_results_service_enabled))
            await self._grpc_server.start(cfg.grpc.laddr)
        if cfg.grpc.privileged_laddr and \
                cfg.grpc.pruning_service_enabled:
            from ..rpc.grpc import GRPCServer
            self._grpc_priv_server = GRPCServer(
                pruner=self.pruner, pruning_service=True)
            await self._grpc_priv_server.start(
                cfg.grpc.privileged_laddr)

        await self.switch.start()
        if cfg.p2p.persistent_peers:
            addrs = [a.strip() for a in
                     cfg.p2p.persistent_peers.split(",") if a.strip()]
            self.switch.dial_peers_async(
                [a.split("@")[-1] for a in addrs])

        if self._statesync_syncer is not None:
            try:
                new_state, commit = \
                    await self._statesync_syncer.sync_any(
                        cfg.statesync.discovery_time_ns / 1e9)
            except Exception:
                # boot failed mid-way: tear down what already started
                # (switch, RPC, pruner, indexer) instead of leaking it
                await self.stop()
                raise
            # bootstrap stores at the snapshot height (reference:
            # statesync.Reactor -> state.Store.Bootstrap + the seen
            # commit the blocksync verify path needs); consensus state
            # is updated (with LastCommit reconstruction) by the
            # blocksync->consensus handoff
            self.state_store.bootstrap(new_state)
            self.block_store.save_seen_commit_standalone(commit)
            self.blocksync_reactor.state = new_state
            self.statesync_reactor.metrics.syncing.set(0)
            self.logger.info("State sync complete",
                             height=new_state.last_block_height)
            await self.blocksync_reactor.start_sync()
        elif self._run_blocksync:
            await self.blocksync_reactor.start_sync()
        else:
            await self.consensus_state.start()
        self._started = True
        self.logger.info("Node started",
                         node_id=self.node_key.id[:12],
                         chain=self.genesis_doc.chain_id)

    async def stop(self) -> None:
        if getattr(self, "pruner", None) is not None:
            await self.pruner.stop()
        if getattr(self, "indexer_service", None) is not None:
            await self.indexer_service.stop()
        if getattr(self, "_event_sink", None) is not None:
            self._event_sink.close()
        if self.consensus_state is not None:
            await self.consensus_state.stop()
        await self.supervisor.stop()
        await self.switch.stop()
        if self._rpc_server is not None:
            await self._rpc_server.stop()
        if getattr(self, "_pprof_server", None) is not None:
            await self._pprof_server.stop()
        if getattr(self, "_grpc_server", None) is not None:
            await self._grpc_server.stop()
        if getattr(self, "_grpc_priv_server", None) is not None:
            await self._grpc_priv_server.stop()
        await self.app_conns.stop()
        if getattr(self, "_signer_endpoint", None) is not None:
            await self._signer_endpoint.stop()
        self._started = False
        self.logger.info("Node stopped")

    async def _make_state_provider(self, state):
        """Light-client state provider over the configured RPC servers
        (reference: stateprovider.go:29)."""
        from ..statesync.syncer import new_rpc_state_provider
        cfg = self.config.statesync
        if not cfg.rpc_servers or not cfg.trust_hash or \
                not cfg.trust_height:
            raise NodeError(
                "statesync.enable requires rpc_servers and "
                "trust_height/trust_hash (reference config)")
        return await new_rpc_state_provider(
            self.genesis_doc.chain_id, self.genesis_doc,
            list(cfg.rpc_servers), cfg.trust_height,
            bytes.fromhex(cfg.trust_hash), cfg.trust_period_ns)

    # ------------------------------------------------------------------
    @property
    def height(self) -> int:
        return self.block_store.height

    def status(self) -> dict:
        state = self.state_store.load()
        latest_meta = self.block_store.load_block_meta(
            self.block_store.height)
        pub = self.priv_validator.get_pub_key()
        return {
            "node_info": {
                "id": self.node_key.id,
                "listen_addr": self.switch.listen_addr,
                "network": self.genesis_doc.chain_id,
                "moniker": self.config.base.moniker,
            },
            "sync_info": {
                "latest_block_hash":
                    latest_meta.block_id.hash.hex().upper()
                    if latest_meta else "",
                "latest_app_hash":
                    (state.app_hash.hex().upper() if state else ""),
                "latest_block_height": str(self.block_store.height),
                "latest_block_time":
                    latest_meta.header.time.rfc3339()
                    if latest_meta else "",
                "earliest_block_height": str(self.block_store.base),
                "catching_up": False,
            },
            "validator_info": {
                "address": pub.address().hex().upper(),
                "pub_key": pub_key_to_json(pub),
                "voting_power": str(_voting_power(state, pub)),
            },
        }


def warm_device_path(n_validators: int) -> None:
    """Build the native host prep and compile every kernel shape an
    n_validators set dispatches at: a full commit (verify_commit), the
    +2/3 prefix verify_commit_light stops at, and a small vote batch.
    Blocking — g++ and Mosaic compiles; call it off the event loop."""
    from ..crypto._native_loader import load
    from ..ops import ed25519_jax
    load(allow_build=True)
    for n in sorted({1, n_validators * 2 // 3 + 1, n_validators}):
        ed25519_jax.warmup(n)


def _voting_power(state, pub) -> int:
    if state is None or state.validators is None:
        return 0
    _, val = state.validators.get_by_address(pub.address())
    return val.voting_power if val else 0


def _only_validator_is_us(state, pub) -> bool:
    """Reference: node/setup.go onlyValidatorIsUs."""
    if state.validators is None or state.validators.size() != 1:
        return False
    return state.validators.validators[0].address == pub.address()
