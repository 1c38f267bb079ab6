#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process (a chip belongs to one process).  The device gate comes
first: without a TPU, or with fewer chips than the cell asks for, the
command exits non-zero and prints no result.  Then the cell's traffic
driver sets up (data from --seed, the program's own warm-up, the
cell's own traffic until nothing compiles any more), the window is
measured for --seconds, the outputs are checked, and the LAST stdout
line is one JSON object with exactly the keys ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
--trace 1, ``breakdown``).  --trace 0 reports the cell's end-to-end
metrics, --trace 1 its per-layer metrics.  Everything printed before
the last line is set-up fact, not result.

``--rehearsal`` debugs the benchmark itself on a CPU (tiny sizes, the
Pallas kernel interpreted): it says so, never prints ``correct: true``
and exits 4, never 0.
"""
from __future__ import annotations

import time

T_START = time.monotonic()      # process start, as near as Python gets

import argparse     # noqa: E402
import asyncio      # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_DEVICE = 2
EXIT_NO_PROGRAM = 3
EXIT_BAD_CELL = 5
TRACE_SECONDS = 4.0     # the profiled sub-window: the window's last seconds


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def device_summary(busy=None) -> dict:
    """The device as JAX reports it; the peak is the fullest chip's."""
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if busy is not None:
        out["busy_s"] = busy["busy_s"]
        out["window_s"] = busy["window_s"]
    return out


def per_layer_metrics(bench, cell: str, obs) -> dict[str, dict]:
    """A traced run's metrics: every per_layer entry of the cell through
    its reader; one that finds nothing to read is left out."""
    metrics = {}
    for m in bench.metrics("per_layer", cell):
        value = bench.reader(m["name"]).read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


async def run_cell(ctx, driver) -> tuple[dict, list[str]]:
    """(the last line's object, what made the run incorrect)."""
    from benchmark.lib import probes, profile
    from benchmark.lib.session import Obs, Window
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.libs.breaker import CLOSED

    ctx.configure_tracing()
    state = await driver.set_up(ctx)
    ctx.lap("set_up")
    registries = [libmetrics.DEFAULT] + list(
        getattr(state, "registries", ()))

    # -- the measured window ----------------------------------------------
    tracing.clear()
    before = probes.metrics_snapshot(*registries)
    compiles_before = len(ctx.compiles)
    window = Window(start=time.monotonic(), seconds=ctx.seconds)
    t0_ns = time.monotonic_ns()
    setup_s = window.start - ctx.t_start
    log(f"window opens after {setup_s:.3f} s of set-up: "
        + " ".join(f"{k}={v:.2f}" for k, v in ctx.laps.items()))

    capture = None
    capture_task = None
    if ctx.trace:
        trace_dir = os.path.join(ctx.work_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        capture = profile.Capture(trace_dir)

        async def start_capture():
            await asyncio.sleep(max(
                0.0, ctx.seconds - min(TRACE_SECONDS, ctx.seconds)))
            await asyncio.to_thread(capture.start)

        capture_task = asyncio.get_running_loop().create_task(
            start_capture())

    samples = await driver.run(ctx, state, window)

    t1_ns = time.monotonic_ns()
    compiles_in_window = len(ctx.compiles) - compiles_before
    if capture is not None:
        await capture_task
        await asyncio.to_thread(capture.stop)
    after = probes.metrics_snapshot(*registries)
    events = tracing.snapshot()
    spans = probes.spans_between(events, t0_ns, t1_ns)

    # -- everything below is outside the timed window -----------------------
    outcome = await driver.check(ctx, state, samples)
    await driver.tear_down(ctx, state)

    problems = list(outcome.problems)
    if compiles_in_window:
        funs = sorted({f for f, _, _ in
                       ctx.compiles.events[compiles_before:]})
        problems.append(f"{compiles_in_window} backend compile(s) "
                        f"inside the window: {funs}")
    batches = [ev for ev in spans if ev["name"] == "batch_verify"
               and probes.attr(ev, "backend") in ("tpu", "cpu")]
    off_device = [ev for ev in batches
                  if probes.attr(ev, "backend") != "tpu"
                  or probes.attr(ev, "fallback")]
    if not batches:
        problems.append("no batch_verify span in the window: the "
                        "device path was never driven")
    if off_device:
        problems.append(f"{len(off_device)} of {len(batches)} batches "
                        f"ran off the device")
    whole = probes.metrics_snapshot(libmetrics.DEFAULT)
    cpu_batches = probes.total(
        whole, "cometbft_crypto_batch_verify_seconds_count",
        backend="cpu")
    if cpu_batches:
        problems.append(f"{cpu_batches:.0f} ed25519 batches were "
                        f"observed on the CPU verifier")
    if crypto_batch.tpu_breaker().state != CLOSED:
        problems.append(
            f"TPU breaker is {crypto_batch.tpu_breaker().state}")
    want_platform = "cpu" if ctx.rehearsal else "tpu"
    platforms = {probes.attr(ev, "platform") for ev in spans
                 if ev["name"] == "kernel_execute"}
    if platforms - {want_platform}:
        problems.append(f"masks came off {sorted(map(str, platforms))}")

    metrics: dict[str, dict] = {}
    trace = busy = breakdown = None
    if not ctx.trace:
        values = dict(driver.end_to_end(ctx, state, samples))
        values["setup_s"] = setup_s
        for m in ctx.bench.metrics("end_to_end", ctx.cell.name):
            if values.get(m["name"]) is None:
                problems.append(f"end-to-end metric {m['name']} has "
                                f"no value")
                continue
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        xplane = profile.find_xplane(capture.out_dir)
        if xplane is not None:
            trace = profile.read_xplane(xplane, capture.anchors_mono_ns)
            busy = profile.busy(trace)
        if not ctx.keep_trace:
            shutil.rmtree(capture.out_dir, ignore_errors=True)
        trace_spans = []
        if len(capture.anchors_mono_ns) >= 2:
            # the program's spans, and the driver's own (what the
            # benchmark's side of the host was doing: waiting for the
            # next request is not the program's time)
            trace_spans = probes.spans_between(
                events + list(samples.get("host_spans", ())),
                capture.anchors_mono_ns[0],
                capture.anchors_mono_ns[-1])
        if busy is None and not ctx.rehearsal:
            problems.append("the trace holds no device operation")
        obs = Obs(cell=ctx.cell, spans=spans,
                  setup_spans=list(getattr(state, "setup_spans", ())),
                  metrics=probes.metrics_delta(before, after),
                  samples=samples,
                  compiles_in_window=compiles_in_window,
                  laps=dict(ctx.laps),
                  device_kind=device_summary()["kind"],
                  trace=trace, trace_spans=trace_spans)
        metrics = per_layer_metrics(ctx.bench, ctx.cell.name, obs)
        if trace is not None:
            breakdown = {
                "device_ops": profile.top_ops(trace),
                "idle_gaps": profile.attribute_gaps(trace, trace_spans)}
            if ctx.keep_trace:
                with open(os.path.join(ctx.work_dir, "trace.json"),
                          "w") as f:
                    json.dump({"trace": trace, "spans": trace_spans}, f)

    for p in problems:
        log(f"PROBLEM: {p}")
    result = {"correct": not problems and not ctx.rehearsal,
              "attempted": outcome.attempted, "failed": outcome.failed,
              "metrics": metrics, "device": device_summary(busy)}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--param", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="override one traffic parameter (a JSON "
                         "value) for a sweep; the driver never does")
    ap.add_argument("--keep-trace", action="store_true",
                    help="with --trace 1: leave the reduced trace and "
                         "the spans beside it in .bench_work/<cell>/")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    from benchmark.lib import loader
    try:
        bench = loader.Bench(ROOT)
        cell = bench.cell(args.workload)
        driver = bench.traffic(cell.driver)
    except (loader.BenchError, OSError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_BAD_CELL
    try:
        import cometbft_tpu  # noqa: F401 — the system under test
        from cometbft_tpu.ops import device
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}",
              file=sys.stderr)
        return EXIT_NO_PROGRAM

    from benchmark.lib import rehearsal
    from benchmark.lib.compiles import CompileLog
    from benchmark.lib.session import Ctx
    if args.rehearsal:
        print(rehearsal.BANNER, flush=True)
        dev = device.probe()
        rehearsal.rehearse_on_cpu()
    else:
        try:
            dev = device.require_tpu()
        except device.NoTpuError as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return EXIT_NO_DEVICE
        if dev.count < cell.chips:
            print(f"benchmark: cell {cell.name} needs {cell.chips} "
                  f"chip(s), JAX sees {dev.count}", file=sys.stderr)
            return EXIT_NO_DEVICE
    log(f"{cell.name} seed={args.seed} on {dev.count} x {dev.kind} "
        f"({dev.platform}); compile cache {dev.cache_dir}")

    seconds = args.seconds if args.seconds is not None \
        else float(bench.manifest["run_seconds"])
    ctx = Ctx(bench, cell, args.seed, seconds, bool(args.trace),
              args.rehearsal, CompileLog(), T_START)
    ctx.keep_trace = args.keep_trace
    for item in args.param:
        key, _, value = item.partition("=")
        ctx.overrides[key] = json.loads(value)
        log(f"parameter override {key}={ctx.overrides[key]!r}: not "
            f"the cell as BENCHMARK.json defines it")
    ctx.lap("device")
    result, problems = asyncio.run(run_cell(ctx, driver))
    print(json.dumps(result), flush=True)
    if args.rehearsal:
        return rehearsal.EXIT if not problems else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
