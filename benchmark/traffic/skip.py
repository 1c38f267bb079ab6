"""Traffic kind ``skip``: one light-client sync every ``interval_ms``,
open loop, timed from when the request was due.

One request is what a relayer, a light proxy or a state-syncing node
does to establish trust in a header: a NEW ``light.Client`` (skipping
mode, the configuration's trust level, an empty ``TrustedStore`` on a
memory DB, the in-process provider as primary and as the one witness)
that was initialised at height 1 before its clock starts, then
``await client.verify_to_height(chain_heights)``: bisection over a
seeded chain whose validator set changes at every height
(reference/skipping.build_chain), every hop's two commit checks
through the BatchVerifier seam behind one SignatureCache, every
verified block saved, the target compared with the witness's.

The provider hands every light block over as a decoder would: a fresh
LightBlock / ValidatorSet / Commit built by ``LightBlock.from_proto``
from the block's wire form as a dict, carrying no memo (hash, address
index, sign-bytes template).  That decode is the benchmark's own work
inside the request, made visible as ``light_fetch_ms``.  A seeded one
request in ``forged_one_in`` is served a target block with one forged
signature at a seeded commit index below the 2/3 mark; it must end in
InvalidHeaderError naming that index, with no bisection after it.

``check`` holds every request to reference/skipping.verify_skipping:
hop sequence, outcomes, named index and (from the ``batch_verify``
spans' ``batch`` and the ``commit_walk`` spans' ``walked`` /
``cache_hits``) the size of every batch and walk.  They are integers
and bits, so the comparison is exact: no tolerance.

Parameters (cell file, then configuration): validators, power,
churn_per_height, chain_heights, trust_level, trusting_period_h,
max_clock_drift_s, now_after_tip_s, interval_ms, forged_one_in,
warmup_ops, quiet_ops, warmup_max_ops, cpu_check_requests, overrun_s,
spin_ms.
"""
from __future__ import annotations

import asyncio
import gc
import sys
import time
from dataclasses import dataclass, field

from benchmark.lib import probes, schedule, spantree, stats
from benchmark.lib.session import Outcome
from benchmark.reference import fixtures, golden, skipping

CHAIN_ID = "bench-light"
EXIT_NO_PROGRAM = 3     # run.py's: the program is not in this checkout


class Provider:
    """The chain, in process, as upstream's mock provider: primary and
    witness of one request.  ``forged`` replaces single heights."""

    def __init__(self, wire: dict, forged: dict):
        self._wire = wire
        self._forged = forged

    async def light_block(self, height: int):
        from cometbft_tpu.types.block import LightBlock
        return LightBlock.from_proto(
            self._forged.get(height) or self._wire[height])

    async def report_evidence(self, ev) -> None:
        raise RuntimeError("the one witness serves the primary's chain")

    def id(self) -> str:
        return "bench-provider"


@dataclass
class Request:
    client: object              # light.Client, initialised at height 1
    forged: int = -1            # commit index forged in the target, or -1


@dataclass
class State:
    chain: object
    target: int
    warm: list
    window: list
    expected: dict              # forged index (or -1) -> [skipping.Hop]
    plain: dict                 # height -> PlainBlock (the honest chain)
    forged_plain: dict          # forged index -> PlainBlock of the target
    setup_spans: list = field(default_factory=list)


async def _serve(state: State, req: Request):
    """(what the client returned or None, the refusal or None)."""
    from cometbft_tpu.light.verifier import LightClientError
    try:
        return await req.client.verify_to_height(
            state.target, now=state.chain.now), None
    except LightClientError as e:
        return None, e


def _wrong(state: State, req: Request, lb, err) -> str:
    """'' when the request ended as known by construction."""
    from cometbft_tpu.light.verifier import InvalidHeaderError
    if req.forged < 0:
        if err is not None:
            return f"honest chain refused: {str(err)[:80]}"
        if lb.height != state.target or \
                lb.hash() != state.chain.header_hash(state.target):
            return "the client returned another block than the target"
        return ""
    if err is None:
        return f"target forged at #{req.forged} accepted"
    if not isinstance(err, InvalidHeaderError) or \
            f"(#{req.forged})" not in str(err):
        return (f"forged #{req.forged} refused under another name: "
                f"{type(err).__name__}: {str(err)[:80]}")
    return ""


async def set_up(ctx) -> State:
    from cometbft_tpu.crypto import _native_loader
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.light.client import SKIPPING, Client, TrustOptions
    from cometbft_tpu.light.store import TrustedStore
    from cometbft_tpu.types.block import LightBlock
    from cometbft_tpu.types.validation import Fraction

    if not hasattr(tracing, "LIGHT"):
        # every comparison below reads the light client's spans
        print("benchmark: this checkout's light client records no "
              "spans (libs/tracing has no LIGHT category): "
              f"{ctx.cell.name} cannot be held to its reference here",
              file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)

    n = int(ctx.param("validators"))
    target = int(ctx.param("chain_heights"))
    interval_s = float(ctx.param("interval_ms")) / 1e3
    one_in = int(ctx.param("forged_one_in"))
    level = tuple(ctx.param("trust_level"))
    n_window = schedule.count_due(interval_s, ctx.seconds)
    n_warm = int(ctx.param("warmup_max_ops"))

    if _native_loader.load(allow_build=True) is None:
        raise RuntimeError("native host prep did not build")
    ctx.lap("native")

    chain = skipping.build_chain(
        CHAIN_ID, ctx.seed, n, int(ctx.param("power")),
        int(ctx.param("churn_per_height")), target,
        int(ctx.param("now_after_tip_s")))
    fixtures.check_sign_bytes(
        CHAIN_ID, chain.blocks[target].validator_set,
        chain.blocks[target].signed_header.commit, ctx.rng("sign-bytes"))
    ctx.lap("sign")

    wire = {h: lb.to_proto() for h, lb in chain.blocks.items()}
    plain: dict = {}

    def fetch(height: int):
        if height not in plain:
            plain[height] = skipping.plain(CHAIN_ID, chain.blocks[height])
        return plain[height]

    state = State(chain=chain, target=target, warm=[], window=[],
                  expected={-1: skipping.verify_skipping(
                      fetch, 1, target, level)},
                  plain=plain, forged_plain={})
    rng = ctx.rng("forge")
    # a light check stops at the 2/3 mark: forge below it
    vset = chain.blocks[target].validator_set
    upto = vset.total_voting_power() * 2 // 3 \
        // vset.validators[0].voting_power + 1
    options = TrustOptions(
        int(ctx.param("trusting_period_h")) * 3600 * 10 ** 9, 1,
        chain.header_hash(1))

    async def request() -> Request:
        forged, served = -1, {}
        if rng.randrange(one_in) == 0:
            forged = rng.randrange(upto)
            lb = LightBlock.from_proto(wire[target])
            cs = lb.signed_header.commit.signatures[forged]
            cs.signature = fixtures.flip_bit(rng, cs.signature, 0, 32)
            served[target] = lb.to_proto()
            if forged not in state.expected:
                state.forged_plain[forged] = skipping.plain(CHAIN_ID, lb)
                state.expected[forged] = skipping.verify_skipping(
                    lambda h: state.forged_plain[forged]
                    if h == target else fetch(h), 1, target, level)
        provider = Provider(wire, served)
        client = Client(
            CHAIN_ID, options, provider, [provider],
            TrustedStore(MemDB()), verification_mode=SKIPPING,
            trust_level=Fraction(*level),
            max_clock_drift_ns=int(ctx.param("max_clock_drift_s"))
            * 10 ** 9)
        await client.initialize(now=chain.now)
        return Request(client, forged)

    reqs = [await request() for _ in range(n_warm + n_window)]
    state.warm, state.window = reqs[:n_warm], reqs[n_warm:]
    checked = skipping.golden_sample(plain.values(), ctx.rng("golden"))
    print(f"[skip] reference: {_summary(state.expected[-1])}; "
          f"{len(state.expected) - 1} forged targets; {checked} lanes "
          f"of the CPU verifier held to the golden model", flush=True)
    ctx.lap("requests")

    # the chain and the requests are the benchmark's, not the
    # program's: keep them out of every later garbage collection
    gc.freeze()

    # warm-up: whole requests, back to back, in a thread of its own
    # (the program sets a kernel shape up inside the call that first
    # needs it; from a deep stack that costs several times as much)
    gate = ctx.warmup_gate()

    closed_loop_ms = []

    def warm() -> None:
        for req in state.warm:
            t0 = time.monotonic()
            bad = _wrong(state, req, *asyncio.run(_serve(state, req)))
            closed_loop_ms.append((time.monotonic() - t0) * 1e3)
            if bad:
                raise RuntimeError(f"warm-up: {bad}")
            gate.op_done()
            if gate.done():
                return
        raise RuntimeError(
            f"warm-up still changing after its {n_warm} requests "
            f"(last change at operation {gate.last_change_op})")

    await asyncio.to_thread(warm)
    state.setup_spans = gate.setup_spans
    ctx.lap("warmup")
    quiet = closed_loop_ms[gate.last_change_op:]
    print(f"[skip] warm after {gate.ops} requests; buckets seen "
          f"{sorted(gate.buckets)}; the {len(quiet)} quiet ones, back "
          f"to back: median {stats.median(quiet)} ms a request",
          flush=True)

    # the seam's mask against the golden model, at the cell's own
    # shape: the lanes of the last hop's trusting check
    last = state.expected[-1][-1]
    report = golden.check_mask(
        skipping.dispatched(plain[last.candidate], plain[last.trusted],
                            last)[0], ctx.rng("mask"))
    print(f"[skip] mask check: {report}", flush=True)
    ctx.lap("mask_check")
    # and what warm-up left behind (two traced and lowered kernel
    # shapes are a few million objects): a full collection over them
    # is 140-230 ms inside one request in seven (PERF.md, PR 31)
    gc.collect()
    gc.freeze()
    return state


def _summary(hops: list) -> str:
    return " ".join(
        f"{h.trusted}>{h.candidate}:{h.outcome}"
        + (f"[{len(h.trusting.verified)}+{len(h.light.verified)}]"
           if h.outcome == skipping.VERIFIED and h.trusting else "")
        for h in hops)


async def run(ctx, state: State, window) -> dict:
    interval_s = float(ctx.param("interval_ms")) / 1e3
    lat_ms, late_ms, results, host_spans = [], [], [], []
    idle_from = time.monotonic_ns()
    async for i, due, late in schedule.paced(
            window.start, interval_s, window.seconds,
            spin_s=float(ctx.param("spin_ms", 2.0)) / 1e3,
            overrun_s=float(ctx.param("overrun_s", schedule.OVERRUN_S))):
        t0 = time.monotonic_ns()
        host_spans.append({"name": "await_next_request",
                           "ts_ns": idle_from, "dur_ns": t0 - idle_from})
        lb, err = await _serve(state, state.window[i])
        idle_from = time.monotonic_ns()
        lat_ms.append((idle_from / 1e9 - due) * 1e3)
        late_ms.append(late * 1e3)
        # judged now, so that no light block outlives its request
        results.append((t0, idle_from,
                        _wrong(state, state.window[i], lb, err)))
        host_spans.append({"name": "light_request", "ts_ns": t0,
                           "dur_ns": idle_from - t0})
    return {"lat_ms": lat_ms, "late_ms": late_ms, "results": results,
            "host_spans": host_spans}


def end_to_end(ctx, state: State, samples: dict) -> dict:
    return {"verify_p50_ms": stats.median(samples["lat_ms"])}


def observed_hops(spans: list) -> list:
    """What the program's spans say the light client did: per
    ``light_hop`` span, in order, ``(its start, (trusted, candidate,
    outcome), checks)`` with, per commit check the attempt came to
    (``trusting``: signers looked up by address, ``light``: by index),
    ``(signatures walked, signatures batched, cache hits)``."""
    kids = spantree.children(spans)

    def below(ev, name):
        return [k for k in kids.get(ev["id"], ()) if k["name"] == name]

    hops = []
    for hop in spans:
        if hop["name"] != "light_hop":
            continue
        checks = {}
        for check in below(hop, "commit_verify"):
            for walk in below(check, "commit_walk"):
                # the batch's span opens under the walk where the walk
                # fills a tile, else under the check
                batched = sum(probes.attr(b, "batch", 0) for b in
                              below(check, "batch_verify")
                              + below(walk, "batch_verify"))
                checks["trusting" if probes.attr(walk, "lookup")
                       == "address" else "light"] = (
                    probes.attr(walk, "walked"), batched,
                    probes.attr(walk, "cache_hits"))
        hops.append((hop["ts_ns"], (probes.attr(hop, "trusted"),
                                    probes.attr(hop, "candidate"),
                                    probes.attr(hop, "outcome")), checks))
    return hops


def expected_hop(hop) -> tuple:
    """A reference hop in ``observed_hops``' form."""
    checks = {}
    for name in ("trusting", "light"):
        check = getattr(hop, name)
        if check is not None:
            checks[name] = (check.walked, len(check.verified),
                            len(check.taken) - len(check.verified))
    if hop.outcome == skipping.CANT_TRUST:
        # refused on the tally alone: a whole walk, nothing batched
        checks["trusting"] = (hop.trusting.walked, 0, 0)
    return (hop.trusted, hop.candidate, hop.outcome), checks


async def check(ctx, state: State, samples: dict) -> Outcome:
    from cometbft_tpu.libs import tracing

    problems, failed = [], 0
    results = samples["results"]
    spans = tracing.snapshot()
    hops = observed_hops(spans)
    for i, (t0, t1, wrong) in enumerate(results):
        req = state.window[i]
        want = state.expected[req.forged]
        bad = [wrong]
        got = [h[1:] for h in hops if t0 <= h[0] < t1]
        if len(got) != len(want):
            bad.append(f"{len(got)} hops where the reference has "
                       f"{len(want)}")
        bad += [f"hop {g} where the reference has {w}"
                for g, w in zip(got, map(expected_hop, want)) if g != w]
        # guarantee (d): every verified hop reads back from the store
        for hop in want:
            if hop.outcome != skipping.VERIFIED:
                continue
            stored = req.client.store.light_block(hop.candidate)
            if stored is None or stored.hash() != \
                    state.chain.header_hash(hop.candidate):
                bad.append(f"height {hop.candidate} does not read "
                           f"back from the store")
        bad = [b for b in bad if b]
        failed += bool(bad)
        if bad and len(problems) < 6:
            problems.append(f"request {i}: " + "; ".join(bad[:3]))

    # a seeded sample of requests: every lane their verdicts rest on,
    # through the seam again at the same shapes, against the
    # per-signature CPU verifier
    rng = ctx.rng("cpu-check")
    k = min(int(ctx.param("cpu_check_requests")), len(results))
    lanes = 0
    for i in rng.sample(range(len(results)), k):
        forged = state.window[i].forged
        for hop in state.expected[forged]:
            target = state.forged_plain[forged] if forged >= 0 and \
                hop.candidate == state.target \
                else state.plain[hop.candidate]
            for items in skipping.dispatched(
                    target, state.plain[hop.trusted], hop):
                lanes += len(items)
                try:
                    golden.compare_cpu(items, golden.seam_mask(items)[1])
                except RuntimeError as e:
                    problems.append(
                        f"request {i}, hop to {hop.candidate}: {e}")
    print(f"[skip] {len(results)} requests, "
          f"{sum(1 for r in state.window[:len(results)] if r.forged >= 0)}"
          f" forged, {failed} not as the reference has them; {k} "
          f"requests' {lanes} lanes compared one by one; p95 "
          f"{stats.percentile(samples['lat_ms'], 95)} ms, max "
          f"{max(samples['lat_ms'], default=None)} ms, generator late "
          f"p95 {stats.percentile(samples['late_ms'], 95)} ms",
          flush=True)
    # a request the generator never reached before the window closed
    never = len(state.window) - len(results)
    return Outcome(attempted=len(state.window), failed=failed + never,
                   problems=problems)


async def tear_down(ctx, state: State) -> None:
    return None
