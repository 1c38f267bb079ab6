"""Fleet observatory: clock-anchor fits, cross-node merge math, and
the /trace anchor contract (tools/fleet_report.py; ISSUE 19).

The synthetic-fleet tests construct 3 nodes whose monotonic clocks
have known offsets and drift, inject known propagation latencies on
the shared wall timeline, and require the report to reconstruct them
within tolerance — the merge math is only trustworthy if injected
ground truth survives the round trip through anchors + fit.
"""
import asyncio
import importlib.util
import json
import os

import pytest

from cometbft_tpu.libs import tracing

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MS = 1_000_000  # ns


def _fr():
    spec = importlib.util.spec_from_file_location(
        "fleet_report", os.path.join(_ROOT, "tools",
                                     "fleet_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


class TestClockFit:
    def test_single_anchor_pins_offset_only(self):
        fr = _fr()
        off, drift = fr.fit_clock([(1_000, 5_000)])
        assert off == 4_000 and drift == 0.0
        assert fr.to_wall(1_000, (off, drift)) == 5_000

    def test_offset_and_drift_recovered_exactly(self):
        fr = _fr()
        true_off, true_drift = 7_000_000_000.0, 2e-6
        anchors = [(m, int(m * (1 + true_drift) + true_off))
                   for m in (0, 10**9, 3 * 10**9, 10 * 10**9)]
        off, drift = fr.fit_clock(anchors)
        assert drift == pytest.approx(true_drift, abs=1e-9)
        for m in (0, 5 * 10**9, 10 * 10**9):
            want = m * (1 + true_drift) + true_off
            assert abs(fr.to_wall(m, (off, drift)) - want) < 0.1 * MS

    def test_no_anchors_is_identity(self):
        fr = _fr()
        assert fr.fit_clock([]) == (0.0, 0.0)
        assert fr.to_wall(123, (0.0, 0.0)) == 123


# ---------------------------------------------------------------------
# synthetic 3-node fleet with known clock errors + latencies

T0 = 100 * 10**9  # the proposer's first-sent instant, wall ns


def _node(name, off_ns, events, drift=0.0):
    """Build a flight-dump record for a node whose monotonic clock
    satisfies wall = mono*(1+drift) + off_ns.  ``events`` is
    [(wall_ts_ns, name, attrs)] — converted to the node's monotonic
    domain, which is what the recorder would have written."""
    def mono(w):
        return int(round((w - off_ns) / (1 + drift)))
    evs = [{"ts_ns": mono(w), "dur_ns": 0, "category": "consensus",
            "name": n, "height": 5, "attrs": a}
           for w, n, a in events]
    anchors = [[m, int(m * (1 + drift) + off_ns)]
               for m in (0, 20 * 10**9, 200 * 10**9)]
    return {"node": name, "anchors": anchors, "events": evs}


def _fleet():
    pv, pc = 1, 2  # canonical PREVOTE_TYPE / PRECOMMIT_TYPE
    # proposer a (validator 0): first-sent at T0, commits at +90ms
    a = _node("a", off_ns=0, events=[
        (T0, "proposal_broadcast", {"round": 0, "parts": 2}),
        (T0 + 40 * MS, "vote_recv", {"type": pv, "index": 1}),
        (T0 + 50 * MS, "vote_recv", {"type": pv, "index": 2}),
        (T0 + 62 * MS, "vote_recv", {"type": pv, "index": 3}),
        (T0 + 75 * MS, "vote_recv", {"type": pc, "index": 1}),
        (T0 + 80 * MS, "vote_recv", {"type": pc, "index": 2}),
        (T0 + 85 * MS, "vote_recv", {"type": pc, "index": 3}),
        (T0 + 90 * MS, "commit", {}),
    ])
    # b: clock 5 s ahead + 1e-6 drift; sees the proposal 30 ms after
    # first-sent, reaches 2/3 prevote power (3rd distinct foreign
    # vote of 4 equal validators) at +70 ms, commits at +95 ms
    b = _node("b", off_ns=5 * 10**9, drift=1e-6, events=[
        (T0 + 30 * MS, "proposal_recv", {"peer": "a"}),
        (T0 + 40 * MS, "vote_recv", {"type": pv, "index": 0}),
        (T0 + 55 * MS, "vote_recv", {"type": pv, "index": 2}),
        (T0 + 70 * MS, "vote_recv", {"type": pv, "index": 3}),
        (T0 + 70 * MS, "vote_recv", {"type": pv, "index": 3}),
        (T0 + 95 * MS, "commit", {}),
    ])
    # c: clock 12 s behind; the straggler — sees the proposal at
    # +45 ms, commits last at +110 ms
    c = _node("c", off_ns=-12 * 10**9, events=[
        (T0 + 45 * MS, "proposal_recv", {"peer": "b"}),
        (T0 + 50 * MS, "vote_recv", {"type": pv, "index": 0}),
        (T0 + 60 * MS, "vote_recv", {"type": pv, "index": 1}),
        (T0 + 110 * MS, "commit", {}),
    ])
    return [a, b, c]


class TestFleetMerge:
    def test_injected_latencies_reconstructed(self):
        fr = _fr()
        report = fr.analyze([fr.node_record(r, r["node"])
                             for r in _fleet()])
        assert report["nodes"] == ["a", "b", "c"]
        h = report["heights"][5]
        assert h["proposer"] == "a"
        rows = h["nodes"]
        tol = 1.0  # ms: fit error must stay far below the latencies
        assert rows["b"]["proposal_seen_ms"] == \
            pytest.approx(30.0, abs=tol)
        assert rows["c"]["proposal_seen_ms"] == \
            pytest.approx(45.0, abs=tol)
        # 4 equal validators: 1/3 crossed at the 2nd distinct foreign
        # vote, 2/3 at the 3rd; duplicate deliveries carry no power
        assert rows["b"]["prevote_t13_ms"] == \
            pytest.approx(55.0, abs=tol)
        assert rows["b"]["prevote_t23_ms"] == \
            pytest.approx(70.0, abs=tol)
        assert rows["a"]["precommit_t23_ms"] == \
            pytest.approx(85.0, abs=tol)
        # c never collected 2/3 prevote power in these events
        assert rows["c"]["prevote_t23_ms"] is None
        assert h["commit_skew_ms"] == pytest.approx(20.0, abs=tol)
        # straggler table: c trails on both proposal and commit
        st = report["stragglers"]
        assert st["c"]["mean_proposal_delay_ms"] == \
            pytest.approx(45.0, abs=tol)
        assert st["c"]["mean_commit_delay_ms"] > \
            st["a"]["mean_commit_delay_ms"]
        # proposal hop latencies are the injected 30/45 ms deltas
        hops = report["hop_latency_ms"]["proposal"]
        assert hops["n"] == 2
        assert hops["max"] == pytest.approx(45.0, abs=tol)

    def test_clock_fits_reported(self):
        fr = _fr()
        report = fr.analyze([fr.node_record(r, r["node"])
                             for r in _fleet()])
        fits = report["clock_fits"]
        assert fits["b"]["offset_ns"] == \
            pytest.approx(5e9, rel=1e-3)
        assert fits["c"]["offset_ns"] == \
            pytest.approx(-12e9, rel=1e-3)

    def test_fleet_collection_file_and_text_render(self, tmp_path):
        fr = _fr()
        path = os.path.join(str(tmp_path), "fleet_test.json")
        with open(path, "w") as f:
            json.dump({"nodes": {r["node"]: r for r in _fleet()}}, f)
        nodes = fr.load_inputs([path])
        assert sorted(n["node"] for n in nodes) == ["a", "b", "c"]
        text = fr.render_report(fr.analyze(nodes))
        assert "proposer=a" in text
        assert "stragglers" in text
        # stringified-int64 events (a /trace body) parse identically
        stringified = []
        for r in _fleet():
            r2 = dict(r)
            r2["anchors"] = [[str(m), str(w)]
                             for m, w in r["anchors"]]
            r2["events"] = [{**e, "ts_ns": str(e["ts_ns"]),
                             "dur_ns": str(e["dur_ns"]),
                             "height": str(e["height"])}
                            for e in r["events"]]
            stringified.append(fr.node_record(r2, r2["node"]))
        rep2 = fr.analyze(stringified)
        assert rep2["heights"][5]["nodes"]["b"]["proposal_seen_ms"] \
            == pytest.approx(30.0, abs=1.0)


class TestTraceAnchorContract:
    def test_trace_serves_anchors_per_spec(self):
        """docs/rpc-spec.json requires the anchor field; the route
        must serve (monotonic_ns, wall_ns) string pairs."""
        with open(os.path.join(_ROOT, "docs", "rpc-spec.json")) as f:
            spec = json.load(f)
        required = spec["methods"]["trace"]["result_required"]
        assert "anchors" in required and "node" in required
        event_required = spec["methods"]["trace"]["event_required"]
        assert {"id", "parent", "tid"} <= set(event_required)
        from cometbft_tpu.rpc import core
        old = tracing.set_recorder(
            tracing.Recorder(node_id="contract-probe"))
        try:
            with tracing.span(tracing.CONSENSUS, "step:Commit",
                              height=1):
                tracing.instant(tracing.CONSENSUS, "commit")
            resp = run(core.routes(None)["trace"]())
        finally:
            tracing.set_recorder(old)
        for field in required:
            assert field in resp, field
        # every event names itself, its parent and its thread, as
        # int64 strings; the instant's parent is the span around it
        outer, inner = resp["events"]
        for ev in (outer, inner):
            for field in event_required:
                assert field in ev, field
            assert int(ev["id"]) > 0 and int(ev["tid"]) > 0
        assert outer["parent"] == "0"
        assert inner["parent"] == outer["id"]
        assert inner["height"] == "1"
        assert resp["node"] == "contract-probe"
        assert resp["anchors"], "at least the construction anchor"
        for pair in resp["anchors"]:
            assert len(pair) == 2
            mono, wall = int(pair[0]), int(pair[1])
            assert mono > 0 and wall > 0

    def test_dump_carries_anchors_and_node(self, tmp_path):
        r = tracing.Recorder(node_id="dump-probe",
                             dump_dir=str(tmp_path))
        r.record_instant("consensus", "commit", 3, None)
        path = r.dump("probe")
        with open(path) as f:
            rec = json.load(f)
        assert rec["node"] == "dump-probe"
        assert rec["anchors"]
        mono, wall = rec["anchors"][0]
        assert isinstance(mono, int) and isinstance(wall, int)

    def test_anchor_refresh_passive_and_bounded(self):
        r = tracing.Recorder(anchor_interval_s=1e-9)
        for _ in range(200):
            r.record_instant("p2p", "recv", 0, None)
        assert 2 <= len(r.anchors) <= r.ANCHORS_MAX
        first = r.anchors[0]
        r2 = tracing.Recorder(anchor_interval_s=3600.0)
        for _ in range(200):
            r2.record_instant("p2p", "recv", 0, None)
        assert len(r2.anchors) == 1  # interval not reached
        # the first anchor survives eviction (drift baseline)
        r3 = tracing.Recorder(anchor_interval_s=1e-9)
        f0 = r3.anchors[0]
        for _ in range(r3.ANCHORS_MAX * 3):
            r3.record_instant("p2p", "recv", 0, None)
        assert len(r3.anchors) <= r3.ANCHORS_MAX
        assert r3.anchors[0] == f0
        assert first  # silence unused warning
