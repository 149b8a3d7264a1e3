"""Sets of runs of one cell, and their spreads by the contract's rule.

    python3 chipbench/measure.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 --out chiprun_out/sets --label set1 [--trace 0]

Runs ``run.py`` once per seed, one after the other (this process stays off
JAX: a chip belongs to one process at a time), appends each result line to
``<out>/<label>.jsonl`` with the seed and the wall time, keeps each run's
stderr beside it, and prints per metric the median and the spread: the
distance between the first and third quartile (``statistics.quantiles(n=4)``)
as a share of the median.  A bound is set from about five times the widest
spread over a cell's two sets.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list) -> float:
    """(Q3 - Q1) / median; 0 for fewer than two values."""
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def summarise(lines: list) -> dict:
    """metric -> (median, spread, n) over result lines."""
    out = {}
    names = sorted({n for ln in lines for n in ln.get("metrics", {})})
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln.get("metrics", {})]
        if vals:
            out[name] = (statistics.median(vals), spread(vals), len(vals))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", required=True)
    ap.add_argument("--label", required=True)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.label}.jsonl")
    lines = []
    for seed in args.seeds.split(","):
        t = time.monotonic()
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", seed, "--seconds", args.seconds,
             "--trace", args.trace], capture_output=True, text=True)
        wall = time.monotonic() - t
        with open(os.path.join(args.out, f"{args.label}.{seed}.err"), "w") as f:
            f.write(proc.stderr)
        last = (proc.stdout.strip().splitlines() or [""])[-1]
        try:
            line = json.loads(last)
        except json.JSONDecodeError:
            line = {"error": last[-500:]}
        line.update(seed=int(seed), rc=proc.returncode, wall_s=wall)
        lines.append(line)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(f"{args.label} seed {seed}: rc {proc.returncode} in {wall:.0f}s "
              f"correct={line.get('correct')} attempted={line.get('attempted')} "
              f"failed={line.get('failed')} " + " ".join(
                  f"{k}={v['value']:.4g}" for k, v in
                  line.get("metrics", {}).items()), flush=True)
    for name, (med, spr, n) in summarise(lines).items():
        print(f"{args.label} {name}: median {med:.6g} spread {100 * spr:.2f}% "
              f"over {n}", flush=True)
    return 0 if all(ln["rc"] == 0 and ln.get("correct") for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
