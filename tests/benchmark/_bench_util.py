"""Shared by the benchmark's CPU tests: where the repo is, and a
temporary copy of the benchmark that a test may add files to."""
import hashlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def copy_benchmark(dst: str) -> str:
    """BENCHMARK.json and benchmark/ (no caches) copied under dst."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(dst, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    return dst


def file_hashes(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out
