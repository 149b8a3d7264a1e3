"""Sets of runs of one cell, and their spreads by the two rules in use.

    python3 chipbench/measure.py --workload <cell> --seeds 11,12,13 \
        --seconds <run_seconds> --out chiprun_out/sets --label set1 [--trace 0]

Runs ``run.py`` once per seed, one after the other (this process stays off
JAX: a chip belongs to one process at a time), appends each result line to
``<out>/<label>.jsonl`` with the seed, the wall time and the run's ``diag``
line (``harness.window_diagnostics``), keeps each run's stderr beside it,
and prints per metric the median and two spreads, each a share of the median:

``spread`` (the quartile rule)
    the distance between the first and third quartile of
    ``statistics.quantiles(n=4)``.  A ``benchmark`` PR's bound is refused as
    too loose where it is over eight times the wider ``spread`` of the
    check's two sets.

``check_spread`` (the quartile rule, the run farthest from the median out)
    A ``benchmark`` PR's bound is refused as too tight where the mean of the
    two sets' ``check_spread`` is over half of it (PR 29 was: 6.1% and 4.6%
    against 6%).

``driver_spread`` (the rule that decides every later PR's check)
    the range of one side's runs, leaving out the run farthest from their
    median.  Where it exceeds the metric's bound on either side the driver
    cannot tell whether the metric changed and the PR is ``unresolved``,
    whatever it did; past half the bound the driver warns.

Beside both it prints ``driver_spread / bound`` against the bound
``BENCHMARK.json`` gives the metric.  The target for a set is **at or under
0.5** (half the bound) in every bounded metric but ``setup_s``, which the
driver judges by its median alone; a set over 1 would have come out
``unresolved``.  Take such sets without ``CHIPBENCH_KEEP``: its sampler
widens the spreads (PERF.md section 2).
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
BENCHMARK = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")
#: the harness's stderr line that carries a run's window diagnostics
DIAG_MARK = "diag: "


def spread(values: list) -> float:
    """(Q3 - Q1) / median; 0 for fewer than two values."""
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def without_farthest(values: list) -> list:
    """``values`` sorted, less the one farthest from their median (of a tie,
    the one whose absence narrows the range more) while at least two stay."""
    kept = sorted(values)
    if len(kept) > 2:
        med = statistics.median(kept)
        # the farthest value is one of the two ends
        low, high = med - kept[0], kept[-1] - med
        drop_low = low > high or (
            low == high and kept[-1] - kept[1] <= kept[-2] - kept[0])
        kept = kept[1:] if drop_low else kept[:-1]
    return kept


def check_spread(values: list) -> float:
    """``spread`` of the values less the one farthest from their median, as
    a share of the median of all of them."""
    if len(values) < 2:
        return 0.0
    kept = without_farthest(values)
    if len(kept) < 2:
        return 0.0
    q = statistics.quantiles(kept, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def driver_spread(values: list) -> float:
    """The range of ``values`` over their median, leaving out the value
    farthest from the median (of a tie, the one whose absence narrows the
    range more) while at least two stay; 0 for fewer than two values."""
    if len(values) < 2:
        return 0.0
    kept = without_farthest(values)
    return (kept[-1] - kept[0]) / statistics.median(values)


def bounds() -> dict:
    """End-to-end metric -> its bound in ``BENCHMARK.json``."""
    with open(BENCHMARK) as f:
        return {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}


def summarise(lines: list) -> dict:
    """metric -> (median, spread, driver_spread, n, check_spread) over
    result lines."""
    out = {}
    names = sorted({n for ln in lines for n in ln.get("metrics", {})})
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln.get("metrics", {})]
        if vals:
            out[name] = (statistics.median(vals), spread(vals),
                         driver_spread(vals), len(vals), check_spread(vals))
    return out


def verdict(ratio: float) -> str:
    """What a side whose ``driver_spread / bound`` is ``ratio`` does to a
    later PR's check."""
    if ratio > 1:
        return "UNRESOLVED: the spread is wider than the bound"
    return "warned: over half the bound" if ratio > 0.5 else "ok"


def diag_of(stderr: str):
    """The last ``diag:`` line of a run's stderr, parsed; None without."""
    for ln in reversed(stderr.splitlines()):
        at = ln.find(DIAG_MARK)
        if at >= 0:
            try:
                return json.loads(ln[at + len(DIAG_MARK):])
            except json.JSONDecodeError:
                return None
    return None


def report(label: str, lines: list, bound_of: dict) -> None:
    for name, (med, spr, drv, n, chk) in summarise(lines).items():
        text = (f"{label} {name}: median {med:.6g} spread {100 * spr:.2f}% "
                f"check_spread {100 * chk:.2f}% "
                f"driver_spread {100 * drv:.2f}% over {n}")
        if name in bound_of:
            ratio = drv / bound_of[name]
            text += (f"; bound {100 * bound_of[name]:g}%, check_spread/bound "
                     f"{chk / bound_of[name]:.2f}, driver_spread/bound"
                     f" {ratio:.2f} ({verdict(ratio)})")
        print(text, flush=True)


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
        line.update(seed=int(seed), rc=proc.returncode, wall_s=wall,
                    diag=diag_of(proc.stderr))
        lines.append(line)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(f"{args.label} seed {seed}: rc {proc.returncode} in {wall:.0f}s "
              f"correct={line.get('correct')} attempted={line.get('attempted')} "
              f"failed={line.get('failed')} " + " ".join(
                  f"{k}={v['value']:.4g}" for k, v in
                  line.get("metrics", {}).items()), flush=True)
        if line["diag"]:
            print(f"{args.label} seed {seed}: diag {json.dumps(line['diag'])}",
                  flush=True)
    report(args.label, lines, bounds())
    return 0 if all(ln["rc"] == 0 and ln.get("correct") for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
