"""Metrics v2 contract tests.

* A Prometheus-text-format parser validates the FULL /metrics output
  of a live multi-validator net: HELP/TYPE lines for every family,
  histogram bucket monotonicity, ``le="+Inf"`` == ``_count``, label
  escaping — so metrics v2 can never emit scrape-breaking text.
* The tier-1 cardinality/help guard: every registered family carries
  non-empty help, label names come from a bounded allowlist (no
  per-tx / unbounded label sets), and the per-family child cap
  collapses excess label values into one overflow series.
* Histogram exemplars link bucket observations to the flight-recorder
  height (``/metrics?exemplars=1``).
"""
import asyncio
import os
import tempfile

import pytest

from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.metrics import (
    DEFAULT, Registry, _CHILDREN_MAX, render_merged,
)


# ---------------------------------------------------------------------
# Prometheus text-format parser (exposition format 0.0.4)

def _unescape(s: str, quotes: bool) -> str:
    out = []
    i = 0
    while i < len(s):
        c = s[i]
        if c == "\\" and i + 1 < len(s):
            nxt = s[i + 1]
            if nxt == "\\":
                out.append("\\")
                i += 2
                continue
            if nxt == "n":
                out.append("\n")
                i += 2
                continue
            if quotes and nxt == '"':
                out.append('"')
                i += 2
                continue
        out.append(c)
        i += 1
    return "".join(out)


def _parse_labels(s: str, line: str) -> tuple[dict, str]:
    """Parse '{k="v",...}rest' -> (labels, rest); raises AssertionError
    on malformed input (that IS the contract being tested)."""
    assert s[0] == "{", line
    labels = {}
    i = 1
    while True:
        if s[i] == "}":
            return labels, s[i + 1:]
        j = s.index("=", i)
        key = s[i:j]
        assert s[j + 1] == '"', f"unquoted label value: {line}"
        k = j + 2
        raw = []
        while True:
            c = s[k]
            if c == "\\":
                raw.append(s[k:k + 2])
                k += 2
                continue
            if c == '"':
                break
            assert c != "\n", f"raw newline inside label: {line}"
            raw.append(c)
            k += 1
        labels[key] = _unescape("".join(raw), quotes=True)
        i = k + 1
        if s[i] == ",":
            i += 1


def parse_exposition(text: str) -> dict:
    """-> {family: {"help": str, "type": str,
                    "samples": [(sample_name, labels, value)]}}"""
    families: dict[str, dict] = {}
    last_family = None
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            rest = line[len("# HELP "):]
            name, _, help_ = rest.partition(" ")
            fam = families.setdefault(
                name, {"help": "", "type": "", "samples": []})
            fam["help"] = _unescape(help_, quotes=False)
            last_family = name
            continue
        if line.startswith("# TYPE "):
            rest = line[len("# TYPE "):]
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram",
                            "summary", "untyped"), line
            assert name in families, \
                f"TYPE before HELP for {name}: {line}"
            families[name]["type"] = kind
            last_family = name
            continue
        assert not line.startswith("#"), f"stray comment: {line}"
        # sample line: name[{labels}] value[ # exemplar]
        brace = line.find("{")
        space = line.find(" ")
        if brace != -1 and brace < space:
            sample_name = line[:brace]
            labels, rest = _parse_labels(line[brace:], line)
        else:
            sample_name = line[:space]
            labels, rest = {}, line[space:]
        rest = rest.strip()
        value_str = rest.split(" ", 1)[0]
        value = float(value_str)
        # attribute the sample to its family (histogram suffixes)
        fam_name = sample_name
        for suffix in ("_bucket", "_sum", "_count"):
            if sample_name.endswith(suffix) and \
                    sample_name[: -len(suffix)] in families and \
                    families[sample_name[: -len(suffix)]]["type"] \
                    == "histogram":
                fam_name = sample_name[: -len(suffix)]
                break
        assert fam_name in families, \
            f"sample with no HELP/TYPE: {line}"
        families[fam_name]["samples"].append(
            (sample_name, labels, value))
        last_family = fam_name
    return families


def assert_exposition_contract(text: str) -> dict:
    """The full scrape contract over an exposition page."""
    families = parse_exposition(text)
    assert families
    for name, fam in families.items():
        assert fam["type"], f"{name}: missing TYPE"
        assert fam["help"].strip(), f"{name}: empty HELP"
        if fam["type"] != "histogram":
            continue
        # group histogram samples per label set (minus le)
        series: dict[tuple, dict] = {}
        for sample_name, labels, value in fam["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            s = series.setdefault(
                key, {"buckets": [], "sum": None, "count": None})
            if sample_name == name + "_bucket":
                s["buckets"].append((labels["le"], value))
            elif sample_name == name + "_sum":
                s["sum"] = value
            elif sample_name == name + "_count":
                s["count"] = value
        for key, s in series.items():
            assert s["buckets"], f"{name}{key}: no buckets"
            assert s["sum"] is not None, f"{name}{key}: no _sum"
            assert s["count"] is not None, f"{name}{key}: no _count"
            inf = [v for le, v in s["buckets"] if le == "+Inf"]
            assert len(inf) == 1, f"{name}{key}: +Inf bucket count"
            assert inf[0] == s["count"], \
                f"{name}{key}: le=+Inf {inf[0]} != _count {s['count']}"
            finite = sorted(
                ((float(le), v) for le, v in s["buckets"]
                 if le != "+Inf"))
            counts = [v for _, v in finite] + inf
            assert counts == sorted(counts), \
                f"{name}{key}: buckets not monotonic: {counts}"
    return families


# ---------------------------------------------------------------------
# renderer unit contracts

class TestExpositionFormat:
    def test_label_escaping_roundtrip(self):
        reg = Registry()
        c = reg.counter("t", "esc", "escaping test", labels=("who",))
        hostile = 'mon"iker\\with\nnewline'
        c.with_labels(hostile).add(3)
        fams = assert_exposition_contract(reg.render())
        (_, labels, value), = fams["cometbft_t_esc"]["samples"]
        assert labels["who"] == hostile
        assert value == 3

    def test_help_escaping(self):
        reg = Registry()
        reg.gauge("t", "h", "line one\nline two")
        fams = parse_exposition(reg.render())
        assert fams["cometbft_t_h"]["help"] == "line one\nline two"

    def test_histogram_contract_and_exemplars(self):
        reg = Registry()
        h = reg.histogram("t", "lat", "latency", labels=("be",),
                          buckets=(0.1, 1.0))
        old = tracing.set_recorder(tracing.Recorder())
        try:
            tracing.set_height(42)
            h.with_labels("cpu").observe(0.05)
            h.with_labels("cpu").observe(3.0)
        finally:
            tracing.set_recorder(old)
        assert_exposition_contract(reg.render())
        # default render carries no exemplar syntax
        assert " # {" not in reg.render()
        out = reg.render(exemplars=True)
        assert 'trace_height="42"' in out
        # the exemplar rides the bucket the observation fell into
        line = [ln for ln in out.splitlines()
                if 'le="0.1"' in ln][0]
        assert "# {" in line and " 0.05 " in line

    def test_openmetrics_counter_total_suffix(self):
        """The exemplar page is OpenMetrics: counter samples carry
        the mandatory _total suffix and already-suffixed names don't
        double it."""
        reg = Registry()
        reg.counter("t", "ops", "plain counter").add(3)
        reg.counter("t", "bytes_total", "pre-suffixed").add(7)
        om = reg.render(exemplars=True)
        assert "cometbft_t_ops_total 3" in om
        assert "# TYPE cometbft_t_ops counter" in om
        assert "cometbft_t_bytes_total 7" in om
        assert "# TYPE cometbft_t_bytes counter" in om
        assert "bytes_total_total" not in om
        # default text-format render is unchanged
        plain = reg.render()
        assert "cometbft_t_ops 3" in plain
        assert "cometbft_t_ops_total" not in plain

    def test_render_merged_dedups_families(self):
        a, b = Registry(), Registry()
        a.counter("t", "x", "from a").add(1)
        b.counter("t", "x", "from b").add(5)
        b.counter("t", "y", "only b").add(2)
        out = render_merged(a, b)
        assert out.count("# TYPE cometbft_t_x counter") == 1
        assert "cometbft_t_x 1" in out       # first registry wins
        assert "cometbft_t_y 2" in out
        assert_exposition_contract(out)


# ---------------------------------------------------------------------
# cardinality / help guards (tier-1 CI satellite)

def _assemble_full_registry() -> Registry:
    """Every subsystem family a node registers, on one registry."""
    from cometbft_tpu.abci.metrics import Metrics as ProxyMetrics
    from cometbft_tpu.blocksync.metrics import (
        Metrics as BlocksyncMetrics,
    )
    from cometbft_tpu.consensus.metrics import (
        Metrics as ConsensusMetrics,
    )
    from cometbft_tpu.libs.supervisor import (
        Metrics as SupervisorMetrics,
    )
    from cometbft_tpu.lightserve.cache import (
        Metrics as LightserveMetrics,
    )
    from cometbft_tpu.mempool.metrics import Metrics as MempoolMetrics
    from cometbft_tpu.p2p.metrics import Metrics as P2PMetrics
    from cometbft_tpu.state.metrics import Metrics as StateMetrics
    from cometbft_tpu.statesync.metrics import (
        Metrics as StatesyncMetrics,
    )
    reg = Registry()
    for cls in (ConsensusMetrics, MempoolMetrics, P2PMetrics,
                BlocksyncMetrics, StatesyncMetrics, StateMetrics,
                ProxyMetrics, SupervisorMetrics, LightserveMetrics):
        cls(reg)
    return reg


# label names whose value sets are bounded by construction: protocol
# enums, claimed channel ids, config-capped peer slots, app-declared
# lanes (all further capped by the per-family child ceiling).
# Unbounded identifiers — tx hashes, heights, addresses-as-labels on
# histograms — must never appear here.
_ALLOWED_LABELS = {
    "step", "peer_id", "chID", "lane", "matches_current",
    "proposer_address", "status", "vote_type", "is_timely", "method",
    "conn", "type", "supervisor", "kind", "task", "backend",
    "pad_bucket", "phase", "kernel", "warm", "name", "le",
    "breaker",      # code-defined breaker names (crypto_tpu_kernel)
    "state",        # breaker state enum (closed/half-open/open/latched)
    "worker",       # verification workers: hard-coded names at the
                    # few SupervisedWorker construction sites
                    # (verify_stage / verify_kernel)
    "outcome",      # how a light-client hop ended: three literals
                    # (verified / cant_trust / invalid), bound once in
                    # light/client.hop_counters
    "executor",     # who ran a wire descriptor or a commit walk:
                    # three literals (native / python / declined), the
                    # keys of wire/proto.codec_stats and of
                    # types/validation.walk_stats
    "path",         # how a vote's signature was judged on the serial
                    # path: two literals (memo / serial), the keys of
                    # types/vote._VERIFY_COUNTS
    "verdict",      # what a burst pre-verification left in the memo:
                    # three literals (valid / invalid / unjudged), the
                    # keys of types/vote._PREVERIFIED
    "prep",         # whether a streamed tile's launch found its host
                    # prep done: two literals (ready / waited), bound
                    # in ops/ed25519_jax.TilePipeline._launch_tile
    "generation",   # the collector's three generations (0 / 1 / 2),
                    # the indices of libs/tracing's totals
    "mode",         # CPU time in user or system mode: two literals,
                    # the keys of libs/metrics._cpu_seconds
    "codec",        # the payload a WAL record was read from: two
                    # literals (binary / json), the keys of
                    # consensus/wal._RECORDS
}


class TestCardinalityGuard:
    def test_every_family_has_help_and_bounded_labels(self):
        # also pull in the lazily-registered process-global families
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.types import signature_cache
        crypto_batch.verify_seconds_histogram()
        crypto_batch.tpu_breaker()
        signature_cache._metrics()
        reg = _assemble_full_registry()
        for fam in reg.collect() + DEFAULT.collect():
            assert fam["help"].strip(), \
                f"{fam['name']}: empty help text"
            for label in fam["labels"]:
                assert label in _ALLOWED_LABELS, (
                    f"{fam['name']}: label {label!r} not in the "
                    f"bounded-label allowlist — unbounded label sets "
                    f"blow up scrape size under churn")

    def test_child_cap_collapses_into_overflow_series(self):
        reg = Registry()
        c = reg.counter("t", "churn", "per-peer churn",
                        labels=("peer_id",))
        c.max_children = 8
        for i in range(100):
            c.with_labels(f"peer-{i}").add()
        fams = parse_exposition(reg.render())
        samples = fams["cometbft_t_churn"]["samples"]
        assert len(samples) == 9        # 8 distinct + 1 overflow
        overflow = [v for _, labels, v in samples
                    if labels["peer_id"] == "overflow"]
        assert overflow == [100 - 8]
        # total observations survive the collapse
        assert sum(v for _, _, v in samples) == 100

    def test_default_cap_is_sane(self):
        assert 512 <= _CHILDREN_MAX <= 16384

    def test_one_ladder(self):
        """crypto/batch.pad_bucket and ops/ed25519_jax._bucket are one
        function over one list (crypto/pipeline), so CPU and TPU
        observations share label values whatever writes the ladder —
        in place: the benchmark's rehearsal, the tuner, the reset."""
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.crypto import pipeline
        from cometbft_tpu.ops import ed25519_jax as ej
        sizes = (1, 16, 17, 63, 64, 65, 100, 1024, 4096, 5000, 10**6)

        def agreed():
            assert ej._BUCKETS is pipeline.BUCKETS
            seam = [crypto_batch.pad_bucket(n) for n in sizes]
            assert seam == [ej._bucket(n) for n in sizes]
            tile = crypto_batch.GuardedTpuBatchVerifier(object())._tile
            assert tile == ej.tile_bucket()
            return seam, tile

        ej.reset_bucket_tuning()
        try:
            base = agreed()
            assert base == ([64, 64, 64, 64, 64, 1024, 1024, 1024,
                             4096, 10240, 16384], 1024)
            ej._BUCKETS[:] = [16]       # benchmark/lib/rehearsal.py
            assert agreed() == ([16] * len(sizes), 16)
            ej.reset_bucket_tuning()
            assert agreed() == base
            for _ in range(ej._TUNE_MIN_SAMPLES):
                ej._tune_record(100, 1024, 0.001, 0.010)
            seam, tile = agreed()
            assert seam[sizes.index(100)] == 128 and tile == 1024
            ej.reset_bucket_tuning()
            assert agreed() == base
            assert ej._BUCKETS == list(ej._BASE_BUCKETS)
        finally:
            ej.reset_bucket_tuning()


# ---------------------------------------------------------------------
# acceptance: the full exposition of a live multi-validator run

async def _fetch(addr: str, path: str) -> str:
    host, port = addr.rsplit(":", 1)
    reader, writer = await asyncio.open_connection(host, int(port))
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n"
                 f"Connection: close\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read(-1)
    writer.close()
    return raw.split(b"\r\n\r\n", 1)[1].decode()


def _mk_cfg(d, name):
    from cometbft_tpu.config import Config
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.privval import FilePV
    home = os.path.join(d, name)
    cfg = Config()
    cfg.base.home = home
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.allow_duplicate_ip = True
    cfg.consensus.timeout_commit_ns = 30_000_000
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    pv = FilePV.generate(
        cfg.base.path(cfg.base.priv_validator_key_file),
        cfg.base.path(cfg.base.priv_validator_state_file))
    NodeKey.load_or_gen(cfg.base.path(cfg.base.node_key_file))
    return cfg, pv


RUNTIME_FAMILIES = {
    "cometbft_runtime_gc_pause_seconds_total":
        ("generation", {"0", "1", "2"}),
    "cometbft_runtime_gc_collections_total":
        ("generation", {"0", "1", "2"}),
    "cometbft_process_cpu_seconds_total": ("mode", {"user", "system"}),
    "cometbft_process_context_switches_total":
        ("kind", {"voluntary", "involuntary"}),
}


def _family_values(fams: dict, name: str) -> dict:
    label, _ = RUNTIME_FAMILIES[name]
    return {labels[label]: v for _, labels, v in fams[name]["samples"]}


class TestRuntimeFamilies:
    """The interpreter's own time on the process-global registry:
    read at scrape time, nothing observed on a hot path."""

    @pytest.mark.parametrize("name", sorted(RUNTIME_FAMILIES))
    def test_family_is_a_counter_with_its_literals(self, name):
        fams = assert_exposition_contract(DEFAULT.render())
        label, values = RUNTIME_FAMILIES[name]
        assert fams[name]["type"] == "counter"
        assert set(_family_values(fams, name)) == values
        assert all(v >= 0 for v in _family_values(fams, name).values())

    def test_pause_totals_are_the_sum_of_the_hooks_pauses(self):
        """Per generation, what /metrics gained over a stretch is what
        the gc_pause spans of that stretch add up to (with the span
        threshold at zero every collection is a span), and what the
        benchmark's own snapshot of the registry reads."""
        import gc
        import sys
        root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        if root not in sys.path:
            sys.path.insert(0, root)
        from benchmark.lib import probes

        pauses = "cometbft_runtime_gc_pause_seconds_total"
        counts = "cometbft_runtime_gc_collections_total"
        old = tracing.set_recorder(tracing.Recorder(buffer_size=1024))
        threshold = tracing.GC_SPAN_MIN_NS
        tracing.GC_SPAN_MIN_NS = 0
        was = gc.isenabled()
        gc.disable()
        try:
            before = parse_exposition(DEFAULT.render())
            snap0 = probes.metrics_snapshot(DEFAULT)
            for generation in (0, 1, 2, 2, 0):
                gc.collect(generation)
            after = parse_exposition(DEFAULT.render())
            delta = probes.metrics_delta(
                snap0, probes.metrics_snapshot(DEFAULT))
            spans = [e for e in tracing.snapshot()
                     if e["name"] == "gc_pause"]
        finally:
            if was:
                gc.enable()
            tracing.GC_SPAN_MIN_NS = threshold
            tracing.set_recorder(old)
        assert len(spans) == 5
        for gen, n in (("0", 2), ("1", 1), ("2", 2)):
            span_s = sum(e["dur_ns"] for e in spans
                         if str(e["attrs"]["generation"]) == gen) / 1e9
            got = (_family_values(after, pauses)[gen]
                   - _family_values(before, pauses)[gen])
            assert got == pytest.approx(span_s, rel=1e-6, abs=1e-9)
            assert (_family_values(after, counts)[gen]
                    - _family_values(before, counts)[gen]) == n
            assert probes.total(delta, pauses, generation=gen) == \
                pytest.approx(span_s, rel=1e-6, abs=1e-9)
            assert probes.total(delta, counts, generation=gen) == n

    def test_cpu_seconds_follow_the_work(self):
        def cpu():
            return sum(_family_values(
                parse_exposition(DEFAULT.render()),
                "cometbft_process_cpu_seconds_total").values())
        import time
        c0 = cpu()
        t_end = time.perf_counter() + 0.05
        while time.perf_counter() < t_end:
            pass
        assert cpu() - c0 > 0.02


class TestLiveExpositionContract:
    def test_live_multi_validator_metrics_contract(self):
        """GET /metrics on a live 3-validator net passes the full
        exposition contract AND serves the metrics-v2 histogram
        families the perf analyses hang off: consensus step duration,
        quorum-prevote delay, batch-verify latency (by backend + pad
        bucket), ABCI call latency, p2p queue-stall duration."""
        from cometbft_tpu.node.node import Node
        from cometbft_tpu.rpc.client import HTTPClient
        from cometbft_tpu.types.genesis import (
            GenesisDoc, GenesisValidator,
        )
        from cometbft_tpu.types.timestamp import Timestamp

        async def run():
            with tempfile.TemporaryDirectory() as d:
                cfgs = [_mk_cfg(d, f"n{i}") for i in range(3)]
                gen = GenesisDoc(
                    chain_id="contract-chain",
                    genesis_time=Timestamp.now(),
                    validators=[GenesisValidator(
                        address=b"", pub_key=pv.get_pub_key(),
                        power=10) for _, pv in cfgs])
                for cfg, _ in cfgs:
                    gen.save_as(cfg.base.path(cfg.base.genesis_file))
                nodes = [Node(cfg) for cfg, _ in cfgs]
                for n in nodes:
                    await n.start()
                try:
                    for i, a in enumerate(nodes):
                        for b in nodes[i + 1:]:
                            await a.switch.dial_peer(
                                b.switch.listen_addr)
                    cli = HTTPClient(
                        f"http://{nodes[0]._rpc_server.listen_addr}",
                        timeout=30.0)
                    for i in range(4):
                        await cli.broadcast_tx_sync(
                            b"contract%d=v" % i)
                    for _ in range(600):
                        if all(n.height >= 4 for n in nodes):
                            break
                        await asyncio.sleep(0.02)
                    assert all(n.height >= 4 for n in nodes), \
                        "net did not progress"
                    addr = nodes[0]._rpc_server.listen_addr
                    body = await _fetch(addr, "/metrics")
                    fams = assert_exposition_contract(body)

                    def hist_observed(name, **want_labels):
                        fam = fams.get(name)
                        assert fam is not None, f"missing {name}"
                        assert fam["type"] == "histogram", name
                        for s_name, labels, v in fam["samples"]:
                            if not s_name.endswith("_count"):
                                continue
                            if all(labels.get(k) == v2 for k, v2
                                   in want_labels.items()) and v > 0:
                                return True
                        return False

                    assert hist_observed(
                        "cometbft_consensus_step_duration_seconds")
                    assert hist_observed(
                        "cometbft_consensus_"
                        "quorum_prevote_delay_seconds")
                    assert hist_observed(
                        "cometbft_consensus_block_interval_seconds")
                    assert hist_observed(
                        "cometbft_consensus_rounds_per_height")
                    assert hist_observed(
                        "cometbft_proxy_method_timing_seconds",
                        conn="consensus")
                    assert hist_observed(
                        "cometbft_mempool_checktx_duration_seconds")
                    assert hist_observed(
                        "cometbft_p2p_message_send_size_bytes")
                    # batch-verify rode the live commit-verification
                    # path, labeled by backend and pad bucket
                    assert hist_observed(
                        "cometbft_crypto_batch_verify_seconds",
                        backend="cpu", pad_bucket="64")
                    # the interpreter's own time, merged in from the
                    # process-global registry
                    for name, (label, values) in \
                            RUNTIME_FAMILIES.items():
                        assert fams[name]["type"] == "counter", name
                        assert {lb[label] for _, lb, _ in
                                fams[name]["samples"]} == values, name
                    # the stall family serves its full bucket ladder
                    # even before any stall happened
                    stall = fams[
                        "cometbft_p2p_queue_stall_seconds"]
                    assert any(
                        s.endswith("_bucket")
                        for s, _, _ in stall["samples"])
                    # exemplar mode: OpenMetrics output, bucket
                    # observations link to a trace height
                    om = await _fetch(addr, "/metrics?exemplars=1")
                    assert 'trace_height="' in om
                finally:
                    for n in nodes:
                        await n.stop()
        asyncio.run(run())
