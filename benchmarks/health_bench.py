"""Group-health plane overhead gate: fold on vs off (ISSUE 18).

The health fold claims the needle-in-a-million detector is (near) free:
per-group stall/churn/heat columns update inside the already-fused tick,
the reductions (log2 histograms, scalar gauges, ``lax.top_k``) are O(G)
device work on arrays the tick already touched, and the host adopts a
``6 + 64 + 6K`` float column per tick.  This bench prices exactly that
delta through the REAL stack (``stack_bench.py``: admission -> device
tick -> WAL fsync -> compacted outbox -> execution -> completion).

Three interleaved arms per leg, fresh subprocess each (the metrics
registry switch is read at import):

* **off**  — ``group_health=false`` (the baseline every prior PR priced);
* **on**   — the full fold + top-K + gauge adoption;
* **on_nometrics** — fold on with ``GPTPU_METRICS=0``: isolates the
  device fold from the host-side gauge plumbing.

Legs: decisions/s at the capacity knee with the WAL on, and wall ms/tick
at ``--groups-big`` (default 1M — the paper's headline scale, where a
per-tick device cost is most visible).  Gate: on-vs-off overhead < 2 %.

Writes ``benchmarks/results_health_pr18.json`` and prints one JSON line
(``run_artifacts.py`` consumes the line).

Usage: python benchmarks/health_bench.py [--groups-knee 131072]
       [--groups-big 1048576] [--repeat 2] [--platform cpu] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

ARMS = ("off", "on", "on_nometrics")


def run_stack(groups: int, ticks: int, warmup: int, wal: bool, arm: str,
              platform: str) -> dict:
    env = dict(os.environ)
    env["GPTPU_METRICS"] = "0" if arm == "on_nometrics" else "1"
    cmd = [sys.executable, os.path.join(HERE, "stack_bench.py"),
           "--groups", str(groups), "--ticks", str(ticks),
           "--warmup", str(warmup), "--platform", platform,
           "--lat-samples", "0"]
    if arm != "off":
        cmd.append("--health")
    if wal:
        cmd.append("--wal")
    out = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                         env=env, timeout=3600)
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise RuntimeError(
        f"stack_bench produced no JSON (arm={arm}); "
        f"stderr tail: {out.stderr.strip()[-400:]!r}")


def ab_leg(groups: int, ticks: int, warmup: int, wal: bool, repeat: int,
           platform: str) -> dict:
    """Interleaved three-arm runs; best-of-N per arm (shared-box
    interference only ever slows a run down, so max estimates the
    uncontended number for every arm identically)."""
    runs = {arm: [] for arm in ARMS}
    for _ in range(repeat):
        for arm in ARMS:
            r = run_stack(groups, ticks, warmup, wal, arm, platform)
            runs[arm].append({
                "decisions_per_s": r["value"],
                "tick_ms": round(1000.0 / r["detail"]["ticks_per_s"], 2),
            })
    best = {arm: max(rs, key=lambda x: x["decisions_per_s"])
            for arm, rs in runs.items()}
    off = best["off"]["decisions_per_s"]

    def pct(arm: str) -> float:
        on = best[arm]["decisions_per_s"]
        return (off - on) / off * 100.0 if off else 0.0

    raw = pct("on")
    return {
        "groups": groups,
        "wal": wal,
        "ticks": ticks,
        **best,
        # negative raw delta = health arm measured FASTER (pure noise);
        # the gate compares the clamped value, raw recorded for honesty
        "overhead_pct_raw": round(raw, 3),
        "overhead_pct": round(max(raw, 0.0), 3),
        "overhead_pct_nometrics_raw": round(pct("on_nometrics"), 3),
        "all_runs": runs,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups-knee", type=int, default=1 << 17)
    ap.add_argument("--groups-big", type=int, default=1 << 20)
    ap.add_argument("--ticks", type=int, default=12)
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--big-ticks", type=int, default=5)
    ap.add_argument("--big-warmup", type=int, default=2)
    ap.add_argument("--repeat", type=int, default=4)
    ap.add_argument("--big-repeat", type=int, default=2,
                    help="best-of-N for the large-G leg (single-run legs "
                         "are hostage to co-tenant noise at 20s/tick)")
    ap.add_argument("--gate-pct", type=float, default=2.0)
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--skip-big", action="store_true",
                    help="knee leg only (quick refresh)")
    ap.add_argument("--out", default=os.path.join(
        HERE, "results_health_pr18.json"))
    args = ap.parse_args()

    legs = {}
    legs["capacity_knee_wal"] = ab_leg(
        args.groups_knee, args.ticks, args.warmup, wal=True,
        repeat=args.repeat, platform=args.platform)
    if not args.skip_big:
        legs["large_g_tick"] = ab_leg(
            args.groups_big, args.big_ticks, args.big_warmup, wal=False,
            repeat=args.big_repeat, platform=args.platform)

    ok = all(l["overhead_pct"] < args.gate_pct for l in legs.values())
    doc = {
        "generated_unix": int(time.time()),
        "gate_pct": args.gate_pct,
        "pass": ok,
        "method": "interleaved group_health off/on/on+GPTPU_METRICS=0 "
                  "stack_bench subprocesses, best-of-N per arm",
        "environment": {"cpu_count": os.cpu_count(),
                        "python": sys.version.split()[0],
                        "platform": args.platform},
        "legs": legs,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    knee = legs["capacity_knee_wal"]
    print(json.dumps({
        "metric": "group_health_overhead_pct_at_capacity_knee",
        "value": knee["overhead_pct"],
        "unit": "% decisions/s lost vs group_health=false (clamped at 0)",
        "pass_lt_pct": args.gate_pct,
        "pass": ok,
        "knee_decisions_per_s": {a: knee[a]["decisions_per_s"]
                                 for a in ARMS},
        "large_g_tick_ms": ({a: legs["large_g_tick"][a]["tick_ms"]
                             for a in ARMS}
                            if "large_g_tick" in legs else None),
        "written": args.out,
    }))


if __name__ == "__main__":
    main()
