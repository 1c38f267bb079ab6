"""Traffic kind ``verify``: one fresh commit every ``interval_ms``
through the program's commit verification, open loop, timed from when
the request was due.

What a validator pays per block, alone on an idle device: the
configuration's validator set signs a fresh height each time (no memo
or signature cache has seen it; cache=None), a seeded one in
``forged_one_in`` commits carries one forged signature that must be
refused by index.  Every commit of warm-up and window is pre-signed in
set-up.

Parameters (cell file, then configuration): validators, power,
interval_ms, forged_one_in, function (verify_commit |
verify_commit_light), warmup_ops, warmup_max_ops, cpu_check_commits,
overrun_s (how long after the window the generator may still serve
what was due in it; schedule.paced).
"""
from __future__ import annotations

import asyncio
import gc
import time
from dataclasses import dataclass, field

from benchmark.lib import schedule, stats
from benchmark.lib.session import Outcome
from benchmark.reference import fixtures, golden

CHAIN_ID = "bench-verify"


@dataclass
class Request:
    args: tuple                 # what the verification function takes
    forged: int = -1            # index of the forged signature, or -1


@dataclass
class State:
    fn: object
    vset: object
    warm: list
    window: list
    setup_spans: list = field(default_factory=list)
    registries: tuple = ()


def _verdict(fn, req: Request):
    """None when accepted, else the refusal's message."""
    from cometbft_tpu.types.validation import VerificationError
    try:
        fn(*req.args)
    except VerificationError as e:
        return str(e)
    return None


def _wrong(req: Request, verdict) -> str:
    """'' when the verdict is the one known by construction."""
    if req.forged < 0:
        return "" if verdict is None else \
            f"honest commit refused: {verdict[:80]}"
    if verdict is None:
        return f"commit forged at #{req.forged} accepted"
    if f"(#{req.forged})" not in verdict:
        return (f"forged #{req.forged} refused under another name: "
                f"{verdict[:80]}")
    return ""


async def set_up(ctx) -> State:
    from cometbft_tpu.crypto import _native_loader
    from cometbft_tpu.types import validation

    n = int(ctx.param("validators"))
    fn = getattr(validation, ctx.param("function"))
    interval_s = float(ctx.param("interval_ms")) / 1e3
    one_in = int(ctx.param("forged_one_in"))
    n_window = schedule.count_due(interval_s, ctx.seconds)
    n_warm = int(ctx.param("warmup_max_ops"))

    if _native_loader.load(allow_build=True) is None:
        raise RuntimeError("native host prep did not build")
    ctx.lap("native")

    vset, privs = fixtures.make_valset(
        fixtures.seeded_privs(n, ctx.seed), int(ctx.param("power")))
    rng = ctx.rng("forge")
    # a light verification stops at the 2/3 mark: forge below it
    upto = n if fn is validation.verify_commit else \
        vset.total_voting_power() * 2 // 3 \
        // vset.validators[0].voting_power + 1

    def request(height: int, may_forge: bool = True) -> Request:
        bid = fixtures.seeded_block_id(ctx.seed, height)
        commit = fixtures.signed_commit(CHAIN_ID, vset, privs, height,
                                        bid)
        forged = -1
        if may_forge and rng.randrange(one_in) == 0:
            forged = rng.randrange(upto)
            cs = commit.signatures[forged]
            cs.signature = fixtures.flip_bit(rng, cs.signature, 0, 32)
        return Request((CHAIN_ID, vset, bid, height, commit), forged)

    reqs = [request(h) for h in range(1, n_warm + n_window + 1)]
    honest = request(n_warm + n_window + 1, may_forge=False)
    fixtures.check_sign_bytes(CHAIN_ID, vset, reqs[0].args[4],
                              ctx.rng("sign-bytes"))
    state = State(fn=fn, vset=vset, warm=reqs[:n_warm],
                  window=reqs[n_warm:n_warm + n_window])
    ctx.lap("sign")

    # the pre-signed commits are the benchmark's, not the program's:
    # keep them out of every later garbage collection
    gc.freeze()

    # warm-up: the cell's own traffic, back to back, in a thread of
    # its own — the program sets a kernel shape up inside the call
    # that first needs it, and JAX's trace + lowering of the kernel
    # costs three to four times as much from a deep Python stack as
    # from a shallow one (PERF.md, PR 22)
    gate = ctx.warmup_gate()

    def warm() -> None:
        for req in state.warm:
            bad = _wrong(req, _verdict(fn, req))
            if bad:
                raise RuntimeError(f"warm-up: {bad}")
            gate.op_done()
            if gate.done():
                return
        raise RuntimeError(
            f"warm-up still changing after its {n_warm} pre-signed "
            f"commits (last change at operation {gate.last_change_op})")

    await asyncio.to_thread(warm)
    state.setup_spans = gate.setup_spans
    ctx.lap("warmup")
    print(f"[verify] warm after {gate.ops} commits; buckets seen "
          f"{sorted(gate.buckets)}", flush=True)

    # the seam's mask against the golden model, at the cell's own shape
    report = golden.check_mask(
        golden.commit_items(CHAIN_ID, vset, honest.args[4]),
        ctx.rng("mask"))
    print(f"[verify] mask check: {report}", flush=True)
    ctx.lap("mask_check")
    return state


async def run(ctx, state: State, window) -> dict:
    interval_s = float(ctx.param("interval_ms")) / 1e3
    lat_ms, late_ms, verdicts, host_spans = [], [], [], []
    fn = state.fn
    idle_from = time.monotonic_ns()
    async for i, due, late in schedule.paced(
            window.start, interval_s, window.seconds,
            spin_s=float(ctx.param("spin_ms", 2.0)) / 1e3,
            overrun_s=float(ctx.param("overrun_s", schedule.OVERRUN_S))):
        t0 = time.monotonic_ns()
        host_spans.append({"name": "await_next_request",
                           "ts_ns": idle_from, "dur_ns": t0 - idle_from})
        req = state.window[i]
        verdict = _verdict(fn, req)
        idle_from = time.monotonic_ns()
        lat_ms.append((idle_from / 1e9 - due) * 1e3)
        late_ms.append(late * 1e3)
        verdicts.append(verdict)
        host_spans.append({"name": "verify_commit_call", "ts_ns": t0,
                           "dur_ns": idle_from - t0})
    return {"lat_ms": lat_ms, "late_ms": late_ms, "verdicts": verdicts,
            "host_spans": host_spans}


def end_to_end(ctx, state: State, samples: dict) -> dict:
    return {"verify_p50_ms": stats.median(samples["lat_ms"])}


async def check(ctx, state: State, samples: dict) -> Outcome:
    problems = []
    verdicts = samples["verdicts"]
    wrong = [(i, w) for i, w in (
        (i, _wrong(state.window[i], v)) for i, v in enumerate(verdicts))
        if w]
    for i, w in wrong[:4]:
        problems.append(f"commit {i}: {w}")
    # a seeded sample of the window's commits, lane by lane, against
    # the per-signature CPU verifier
    rng = ctx.rng("cpu-check")
    k = min(int(ctx.param("cpu_check_commits")), len(verdicts))
    for i in rng.sample(range(len(verdicts)), k):
        chain_id, vset, _, _, commit = state.window[i].args
        items = golden.commit_items(chain_id, vset, commit)
        _, mask = golden.seam_mask(items)
        try:
            golden.compare_cpu(items, mask)
        except RuntimeError as e:
            problems.append(f"commit {i}: {e}")
    print(f"[verify] {len(verdicts)} commits, "
          f"{sum(1 for r in state.window[:len(verdicts)] if r.forged >= 0)}"
          f" forged, {len(wrong)} wrong verdicts; {k} commits compared "
          f"lane by lane; p95 {stats.percentile(samples['lat_ms'], 95)}"
          f" ms, max {max(samples['lat_ms'], default=None)} ms, "
          f"generator late p95 "
          f"{stats.percentile(samples['late_ms'], 95)} ms", flush=True)
    # a commit the generator never reached before the window closed
    never = len(state.window) - len(verdicts)
    return Outcome(attempted=len(state.window),
                   failed=len(wrong) + never, problems=problems)


async def tear_down(ctx, state: State) -> None:
    return None
