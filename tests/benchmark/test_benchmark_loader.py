"""The harness is driven by data: a configuration, a cell, a traffic
kind and a per-layer reader added as NEW files (and new entries) are
found by name, with no edit to any file that was there.  The rehearsal
adds a ``commit-10k``-shaped configuration at a tiny validator count —
the cell PERF.md's Open questions promises as data."""
import json
import os

import pytest

from _bench_util import ROOT, copy_benchmark, file_hashes
from benchmark.lib import loader

NEW_CONFIG = {
    "name": "commit-10k",
    "source": "BASELINE.json config 5: 10,000 validators",
    "fixed": {"validators": 12, "key_type": "ed25519", "power": 10},
    "reduced": [], "assumed": {}, "chips": 1,
}
NEW_CELL = {
    "function": "verify_commit_light", "interval_ms": 500,
    "forged_one_in": 16, "warmup_ops": 4, "warmup_max_ops": 8,
    "cpu_check_commits": 1,
}
NEW_READER = '''"""Requests in the window (a new per-layer metric)."""


def read(obs):
    return len(obs.samples.get("lat_ms", ()))
'''
NEW_TRAFFIC = '''"""A new traffic kind, as a file of its own."""
from benchmark.lib.session import Outcome

MARK = "echo-driver"


async def set_up(ctx):
    return None


async def run(ctx, state, window):
    return {"lat_ms": [1.0]}


def end_to_end(ctx, state, samples):
    return {"verify_p50_ms": 1.0}


async def check(ctx, state, samples):
    return Outcome(attempted=1, failed=0)


async def tear_down(ctx, state):
    return None
'''


@pytest.fixture()
def grown(tmp_path):
    """A copy of the benchmark with one of each kind of thing added."""
    root = copy_benchmark(str(tmp_path))
    before = file_hashes(os.path.join(root, "benchmark"))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", "commit-10k.json"),
              "w") as f:
        json.dump(NEW_CONFIG, f)
    with open(os.path.join(bdir, "workloads", "commit-10k.verify.json"),
              "w") as f:
        json.dump(NEW_CELL, f)
    with open(os.path.join(bdir, "workloads", "commit-10k.echo.json"),
              "w") as f:
        json.dump({}, f)
    # a new traffic mix for a driver that is there: a data file alone
    with open(os.path.join(bdir, "workloads", "commit-10k.sparse.json"),
              "w") as f:
        json.dump(dict(NEW_CELL, driver="verify", interval_ms=2000), f)
    with open(os.path.join(bdir, "layers", "requests_seen.py"),
              "w") as f:
        f.write(NEW_READER)
    with open(os.path.join(bdir, "traffic", "echo.py"), "w") as f:
        f.write(NEW_TRAFFIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["configs"].append({
        "name": "commit-10k", "source": NEW_CONFIG["source"],
        "file": "benchmark/configs/commit-10k.json", "reduced": [],
        "why": "the north-star validator count"})
    for traffic in ("verify", "echo", "sparse"):
        m["workloads"].append({
            "name": f"commit-10k.{traffic}", "config": "commit-10k",
            "traffic": traffic, "chips": 1, "why": "added as data"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in ("verify_p50_ms", "commit_verify_ms"):
            metric["workloads"].append("commit-10k.verify")
    m["per_layer"].append({
        "name": "requests_seen", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "verify_p50_ms", "workloads": ["commit-10k.verify"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return root, before


def test_nothing_that_was_there_was_edited(grown):
    root, before = grown
    after = file_hashes(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 6


def test_new_configuration_and_cell_are_found_by_name(grown):
    root, _ = grown
    bench = loader.Bench(root)
    cell = bench.cell("commit-10k.verify")
    assert cell.config_name == "commit-10k" and cell.chips == 1
    assert cell.param("validators") == 12           # from the config
    assert cell.param("function") == "verify_commit_light"
    assert cell.param("no_such", "fallback") == "fallback"
    with pytest.raises(loader.BenchError):
        cell.param("no_such")
    assert "commit-10k.verify" in bench.cell_names()


def test_old_driver_serves_the_new_cell_and_new_driver_loads(grown):
    root, _ = grown
    bench = loader.Bench(root)
    verify = bench.traffic(bench.cell("commit-10k.verify").driver)
    for fn in ("set_up", "run", "end_to_end", "check", "tear_down"):
        assert callable(getattr(verify, fn))
    echo = bench.traffic(bench.cell("commit-10k.echo").driver)
    assert echo.MARK == "echo-driver"
    # loaded from the copy, not from this checkout
    assert echo.__file__.startswith(root)


def test_a_new_mix_for_an_old_driver_is_a_data_file_alone(grown):
    root, _ = grown
    bench = loader.Bench(root)
    sparse = bench.cell("commit-10k.sparse")
    assert sparse.traffic == "sparse" and sparse.driver == "verify"
    assert sparse.param("interval_ms") == 2000
    assert bench.traffic(sparse.driver).__file__ == bench.traffic(
        bench.cell("commit-10k.verify").driver).__file__
    assert not os.path.exists(
        os.path.join(root, "benchmark", "traffic", "sparse.py"))


def test_new_reader_is_found_and_listed_for_its_cell_only(grown):
    root, _ = grown
    bench = loader.Bench(root)

    class Obs:
        samples = {"lat_ms": [1, 2, 3]}
    assert bench.reader("requests_seen").read(Obs()) == 3
    names = [m["name"] for m in bench.metrics(
        "per_layer", "commit-10k.verify")]
    assert "requests_seen" in names and "commit_verify_ms" in names
    assert "requests_seen" not in [
        m["name"] for m in bench.metrics("per_layer", "qa-175.verify")]
    assert [m["name"] for m in bench.metrics(
        "end_to_end", "commit-10k.verify")] == ["verify_p50_ms",
                                                "setup_s"]


@pytest.mark.parametrize("what,call", [
    ("cell", lambda b: b.cell("no.such")),
    ("config", lambda b: b.config("no-such")),
    ("traffic", lambda b: b.traffic("no_such")),
    ("reader", lambda b: b.reader("no_such"))])
def test_a_missing_name_is_an_error_not_a_default(what, call):
    with pytest.raises(loader.BenchError):
        call(loader.Bench(ROOT))


@pytest.mark.parametrize("cell", loader.Bench(ROOT).cell_names())
def test_every_cell_of_this_checkout_loads(cell):
    bench = loader.Bench(ROOT)
    c = bench.cell(cell)
    driver = bench.traffic(c.driver)
    assert callable(driver.set_up) and driver.__doc__
    for m in bench.metrics("per_layer", cell):
        assert callable(bench.reader(m["name"]).read)
    assert c.config["reduced"] == next(
        e["reduced"] for e in bench.manifest["configs"]
        if e["name"] == c.config_name)
    assert c.config["guarantees"]


def test_the_unregistered_load_pieces_still_load():
    """net-4.load was measured and left out of BENCHMARK.json (its runs
    spread too widely, PERF.md section 7); its driver, configuration and
    cell file stay, ready to be registered as data."""
    bench = loader.Bench(ROOT)
    driver = bench.traffic("load")
    for fn in ("set_up", "run", "end_to_end", "check", "tear_down"):
        assert callable(getattr(driver, fn))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "net-4.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "net-4.load.json")) as f:
        params = json.load(f)
    assert config["fixed"]["validators"] == 4 and config["guarantees"]
    assert params["rate_tx_per_s"] > 0
    for metric in ("block_interval_ms", "txs_per_block",
                   "loop_lag_p95_ms", "net_seam_ms"):
        assert callable(bench.reader(metric).read)
