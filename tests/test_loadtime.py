"""Load generation + latency report (reference: test/loadtime,
test/e2e/runner/benchmark.go)."""
import asyncio
import os
import tempfile


def _mk_node_cfg(d):
    from cometbft_tpu.config import Config
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.timestamp import Timestamp

    home = os.path.join(d, "node")
    cfg = Config()
    cfg.base.home = home
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.timeout_commit_ns = 50_000_000
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    pv = FilePV.generate(
        cfg.base.path(cfg.base.priv_validator_key_file),
        cfg.base.path(cfg.base.priv_validator_state_file))
    NodeKey.load_or_gen(cfg.base.path(cfg.base.node_key_file))
    gen = GenesisDoc(
        chain_id="load-chain", genesis_time=Timestamp.now(),
        validators=[GenesisValidator(
            address=b"", pub_key=pv.get_pub_key(), power=10)],
    )
    # PBTS: block time is the proposer's clock at proposal, so tx
    # latency (block time - send time) is non-negative; without it
    # BFT time lags by up to one commit interval (the reference QA
    # baseline, CometBFT-QA-v1, also runs with PBTS)
    gen.consensus_params.feature.pbts_enable_height = 1
    gen.save_as(cfg.base.path(cfg.base.genesis_file))
    return cfg


class TestPayload:
    def test_roundtrip_and_padding(self):
        from cometbft_tpu.tools.loadtime import (
            payload_bytes, payload_from_tx,
        )

        tx = payload_bytes("exp1", size=300, rate=50, connections=2)
        assert len(tx) >= 300
        assert tx.startswith(b"a=")        # kvstore single-key form
        p = payload_from_tx(tx)
        assert p["id"] == "exp1" and p["rate"] == 50
        assert p["time_ns"] > 0
        assert payload_from_tx(b"other=tx") is None
        assert payload_from_tx(b"a=nothex!") is None

    def test_stats(self):
        from cometbft_tpu.tools.loadtime import Stats

        s = Stats.from_samples([1.0, 2.0, 3.0, 4.0])
        assert s.count == 4 and s.min_s == 1.0 and s.max_s == 4.0
        assert abs(s.avg_s - 2.5) < 1e-9
        assert s.p50_s in (2.0, 3.0)
        assert Stats.from_samples([]).count == 0


class TestLoadAgainstLiveNode:
    def test_generate_and_report(self):
        from cometbft_tpu.node.node import Node
        from cometbft_tpu.tools import loadtime

        async def run():
            with tempfile.TemporaryDirectory() as d:
                node = Node(_mk_node_cfg(d))
                await node.start()
                try:
                    ep = f"http://{node._rpc_server.listen_addr}"
                    # block 1 carries the genesis time (reference:
                    # state.go MakeBlock at initial height), so load
                    # must start after it or its txs get negative
                    # latencies
                    for _ in range(200):
                        if node.height >= 1:
                            break
                        await asyncio.sleep(0.02)
                    else:
                        raise AssertionError(
                            "node never reached height 1")
                    res = await loadtime.generate(
                        [ep], rate=40, connections=2,
                        duration_s=2.0, size=200)
                    assert res.accepted > 10, \
                        f"only {res.accepted}/{res.sent} accepted"
                    assert res.errors == 0
                    # let the tail commit
                    h = node.height
                    for _ in range(200):
                        if node.height > h + 1:
                            break
                        await asyncio.sleep(0.02)
                    rep = await loadtime.report(
                        ep, experiment_id=res.experiment_id)
                    assert rep.latency.count > 10
                    assert rep.negative_latencies == 0
                    assert 0 < rep.latency.p50_s < 10
                    assert rep.block_interval.count > 1
                    assert rep.block_interval.avg_s > 0
                finally:
                    await node.stop()
        asyncio.run(run())


class TestBaselineBenchmarks:
    def test_configs_run_at_tiny_sizes(self):
        """The BASELINE benchmark configs (#2, #4, #5; #3 is the
        benchmark's cell light-1k.skip) execute and emit sane timings
        (tools/benchmarks.py)."""
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.tools import benchmarks as b

        crypto_batch.set_backend("cpu")
        try:
            r2 = b.config2_batch_verify(sizes=(16,))
            assert r2["results_ms"]["16"] > 0
            r4 = b.config4_replay_tally(n_vals=8, heights=2)
            assert r4["tally_ms_p50"] > 0
            r5 = b.config5_mixed_stress(n_vals=12, n_bls=4)
            assert r5["mixed_commit_verify_ms"] > 0
        finally:
            crypto_batch.set_backend("auto")
