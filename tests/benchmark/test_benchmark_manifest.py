"""BENCHMARK.json against the contract: keys, names, units, files, and
that every name in it leads to a file of its own."""
import os
import re

import pytest

from _bench_util import ROOT, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
M = manifest()
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_are_exactly_the_contracts():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"][:2] == ["python3", "benchmark/run.py"]
    assert 1 <= len(M["paths"]) <= 16
    assert isinstance(M["run_seconds"], int)
    assert 1 <= M["run_seconds"] <= 51
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) \
        <= 64 * 1024


def test_a_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert any(entry["file"].startswith(p + "/") for p in M["paths"])
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    assert len(entry["reduced"]) <= 16
    assert all(NAME.match(k) for k in entry["reduced"])
    for text in (entry["source"], entry["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text
    assert any(w["config"] == entry["name"] for w in M["workloads"])


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in M["configs"]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "workloads", cell["name"] + ".json"))
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".py"))


def test_cells_are_unique_pairs_and_few_take_four_chips():
    assert 2 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in M["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", ()):
        assert cell in CELLS
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert 1 <= len(metric["layer"]) <= 200
        moved = {m["name"]: m for m in M["end_to_end"]}[metric["moves"]]
        # reported only where the metric it moves is
        for cell in CELLS:
            if reports(metric, cell):
                assert reports(moved, cell), (metric["name"], cell)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", metric["name"] + ".py"))


def test_metric_names_are_unique_and_setup_s_is_everywhere():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    setup = {m["name"]: m for m in M["end_to_end"]}["setup_s"]
    assert "workloads" not in setup and setup["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    e2e = [m["name"] for m in M["end_to_end"] if reports(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(m, cell) for m in M["per_layer"])


def test_files_under_paths_use_only_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in M["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(d, name), ROOT)
                assert ok.match(rel), rel


# the readers of net-4.load, a cell whose files are in place and which
# is not registered (PERF.md, Open questions)
NET_4 = ("net_commit_verify_ms", "net_device_idle_share",
         "net_fallback_share", "net_gen_late_p95_ms", "net_seam_ms",
         "loop_lag_p95_ms", "txs_per_block", "block_interval_ms")
READERS = sorted(
    name[:-3] for name in os.listdir(os.path.join(ROOT, "benchmark",
                                                  "layers"))
    if name.endswith(".py") and name != "__init__.py")


@pytest.mark.parametrize("reader", READERS)
def test_every_reader_is_an_entry_or_a_named_net_4_reader(reader):
    """A reader without an entry is read by no run and is no ledger
    column: it cannot land silently (PR 31's twelve did)."""
    listed = [m["name"] for m in M["per_layer"]]
    assert (reader in listed) != (reader in NET_4), reader
