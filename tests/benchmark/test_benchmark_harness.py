"""The harness end to end on the CPU: run.py's run_cell drives a
``verify`` and a ``catchup`` cell at tiny sizes in this process (the
CPU verifier stands in for the device, so ``correct`` must come out
false), and the last-line object has exactly the contract's keys.  The
command itself must exit non-zero, with no result, without a chip and
without the program."""
import asyncio
import json
import os
import subprocess
import sys

import pytest

from _bench_util import ROOT, copy_benchmark
from benchmark import run as bench_run
from benchmark.lib import loader
from benchmark.lib.compiles import CompileLog
from benchmark.lib.session import Ctx

TINY = {
    "qa-175.verify": {"validators": 12, "interval_ms": 10,
                      "warmup_ops": 4, "quiet_ops": 2,
                      "warmup_max_ops": 8, "cpu_check_commits": 2,
                      "spin_ms": 0},
    "qa-175.catchup": {"validators": 8, "chain_heights": 300,
                       "chain_margin": 4, "forged_height": 4,
                       "prewarm_ops": 4, "warmup_ops": 8, "quiet_ops": 4,
                       "txs_per_block": 1, "tx_bytes": 64,
                       "kv_check_keys": 4},
}


@pytest.fixture(scope="module")
def compiles():
    return CompileLog()


def tiny_ctx(cell_name, trace, seconds, compiles, tmp_path):
    """(a Ctx of the cell at TINY's sizes, the cell's traffic driver)"""
    bench = loader.Bench(ROOT)
    cell = bench.cell(cell_name)
    ctx = Ctx(bench, cell, seed=7, seconds=seconds, trace=trace,
              rehearsal=False, compiles=compiles,
              t_start=bench_run.time.monotonic())
    ctx.work_dir = str(tmp_path)
    ctx.overrides.update(TINY[cell_name])
    return ctx, bench.traffic(cell.driver)


def run_ctx(ctx, driver):
    """run_cell's (result, problems), the recorder put back after."""
    from cometbft_tpu.libs import tracing
    old = tracing.recorder()
    try:
        return asyncio.run(bench_run.run_cell(ctx, driver))
    finally:
        tracing.set_recorder(old)


def run_tiny(cell_name, trace, seconds, compiles, tmp_path):
    return run_ctx(*tiny_ctx(cell_name, trace, seconds, compiles,
                             tmp_path))


@pytest.mark.parametrize("trace", [False, True])
def test_verify_cell_runs_and_prints_the_contracts_keys(
        trace, compiles, tmp_path):
    result, problems = run_tiny("qa-175.verify", trace, 0.3, compiles,
                                tmp_path)
    assert set(result) - {"breakdown"} == {
        "correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["attempted"] == 30 and result["failed"] == 0
    # the CPU verifier did the work: never correct under any name
    assert result["correct"] is False
    assert any("off the device" in p for p in problems)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))
    if trace:
        assert "commit_verify_ms" in result["metrics"]
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        assert "verify_p50_ms" not in result["metrics"]
    else:
        assert set(result["metrics"]) == {"verify_p50_ms", "setup_s"}
    json.dumps(result)


def test_catchup_cell_syncs_refuses_the_forgery_and_matches_the_chain(
        compiles, tmp_path):
    result, problems = run_tiny("qa-175.catchup", False, 0.3, compiles,
                                tmp_path)
    assert set(result["metrics"]) == {"sync_heights_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["sync_heights_per_s"]["value"] > 0
    # every complaint is about the device, none about the chain
    assert problems and all(
        "device" in p or "CPU verifier" in p for p in problems)


def test_no_chip_means_no_result_and_a_non_zero_exit():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "qa-175.verify", "--seed", "0", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == bench_run.EXIT_NO_DEVICE
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    root = copy_benchmark(str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "qa-175.verify", "--seed", "0", "--seconds", "1",
         "--trace", "0"], cwd=root, capture_output=True, text=True,
        timeout=120, env={k: v for k, v in os.environ.items()
                          if k != "PYTHONPATH"})
    assert proc.returncode == bench_run.EXIT_NO_PROGRAM
    assert proc.stdout.strip() == ""


def test_an_unknown_cell_is_refused():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "no.such", "--seed", "0", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120)
    assert proc.returncode == bench_run.EXIT_BAD_CELL
    assert proc.stdout.strip() == ""
