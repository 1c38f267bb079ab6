#!/usr/bin/env python3
"""benchmark/measure.py — repeat one cell as the driver does and print
the spread behind a bound.

    python3 benchmark/measure.py --workload <cell> --runs 6 [--seed0 100]
        [--trace 0|1] [--seconds S] [--out chiprun_out/<file>.jsonl]

Runs ``benchmark/run.py`` ``--runs`` times, one process after another
(a chip belongs to one process; this parent never imports JAX), each
with another seed, keeps every run's last line, and prints per metric
the median and the spread (distance between the quartiles over the
median).  The first run of a cell in a checkout compiles: its setup_s
is listed apart.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default="")
    ap.add_argument("extra", nargs="*",
                    help="further arguments for run.py, after --")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds if args.seconds is not None \
        else manifest["run_seconds"]
    lines = []
    for i in range(args.runs):
        cmd = list(manifest["command"]) + [
            "--workload", args.workload, "--seed", str(args.seed0 + i),
            "--seconds", str(seconds), "--trace", str(args.trace)
        ] + args.extra
        t0 = time.monotonic()
        proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                              text=True)
        wall = time.monotonic() - t0
        out = proc.stdout.strip().splitlines()
        for ln in out[:-1]:
            print(f"  | {ln}", flush=True)
        try:
            line = json.loads(out[-1]) if proc.returncode == 0 else None
        except (IndexError, ValueError):
            line = None
        print(f"run {i} seed {args.seed0 + i}: rc={proc.returncode} "
              f"wall={wall:.1f}s {json.dumps(line)}", flush=True)
        if line is not None:
            line["_seed"] = args.seed0 + i
            line["_wall_s"] = wall
            lines.append(line)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".",
                            exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(
                        {"workload": args.workload, **line}) + "\n")
    names = sorted({k for ln in lines for k in ln["metrics"]})
    print(f"== {args.workload}: {len(lines)}/{args.runs} runs, "
          f"correct {sum(bool(ln['correct']) for ln in lines)}, "
          f"failed ops {sum(ln['failed'] for ln in lines)}")
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln["metrics"]]
        later = vals[1:] if name == "setup_s" and len(vals) > 1 \
            else vals
        sp = stats.spread(later)
        print(f"   {name}: median {stats.median(later):.6g} spread "
              f"{'n/a' if sp is None else f'{100 * sp:.2f}%'} "
              f"values {[round(v, 4) for v in vals]}")
    return 0 if len(lines) == args.runs else 1


if __name__ == "__main__":
    sys.exit(main())
