"""Each driver's rehearsal (JAX on the CPU, the Pallas kernel
interpreted, tiny sizes): what the builder runs before spending chip
time.  Slow — the interpreted kernel takes most of a minute to trace —
so opt-in: pytest tests/benchmark -m slow."""
import json
import os
import subprocess
import sys

import pytest

from _bench_util import ROOT, manifest
from benchmark.lib import rehearsal

pytestmark = pytest.mark.slow


@pytest.mark.timeout_s(900)
@pytest.mark.parametrize("cell",
                         [w["name"] for w in manifest()["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_never_passes_for_a_chip_run(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "4",
         "--trace", str(trace), "--rehearsal"],
        env=env, capture_output=True, text=True, timeout=880)
    lines = proc.stdout.strip().splitlines()
    assert lines[0] == rehearsal.BANNER
    assert proc.returncode == rehearsal.EXIT, proc.stdout[-2000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"
    # an interpreted kernel under the profiler can run so late that the
    # window closes on a request or two: they count as failed
    assert last["attempted"] > 0
    assert last["failed"] <= last["attempted"] // 4
