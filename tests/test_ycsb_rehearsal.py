"""``chipbench/run.py`` on the YCSB A deployment at the rehearsal's size
(ISSUE 36: ``rehearsal-ycsb-a-3r-4k`` x ``open1k-ycsb-a``), off the chip:
whole, the run is ``correct`` and prints the per-layer metrics the PR adds;
with the served path broken underneath (one replica skips a ``SETRANGE``, or
answers a read from before it) it is not.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YCSB = ["--config-file", "chipbench/configs/rehearsal-ycsb-a-3r-4k.json",
        "--traffic", "open1k-ycsb-a"]


def _env(tmp_path) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("GPTPU_", "CHIPBENCH_"))}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path), PYTHONHASHSEED="0",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               CHIPBENCH_REHEARSAL="1", GPTPU_PALLAS="1",
               GPTPU_PALLAS_INTERPRET="1")
    return env


def _notes(stderr: str) -> list:
    return [ln.split("] ", 1)[1] for ln in stderr.splitlines()
            if ln.startswith("[") and "] " in ln]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_ycsb_deployment_rehearses_correct(tmp_path, trace):
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", *YCSB, "--seed", "3000003602",
         "--seconds", "3", "--trace", str(trace)],
        cwd=ROOT, env=_env(tmp_path), text=True, capture_output=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["attempted"] == 3000 and line["failed"] == 0
    assert line["compared"]["wrong_answers"] == {"value": 0, "limit": 0}
    notes = _notes(out.stderr)
    order = [n.split(":")[0] for n in notes if n.split(":")[0] in (
        "populated and adopted 4,032 groups", "preload", "warm-up", "window")]
    assert order == ["populated and adopted 4,032 groups", "preload",
                     "warm-up", "window"], notes
    assert any(n.startswith("preload: 4,032 records") for n in notes)
    kinds = [n for n in notes if n.startswith("acknowledged by kind: ")]
    by_kind = json.loads(kinds[0].split(": ", 1)[1])
    assert set(by_kind) == {"read", "update"}
    assert by_kind["read"] >= 1500 and by_kind["update"] >= 1500
    assert any("256 read back by GET" in n for n in notes)
    if trace == 0:
        assert line["metrics"]["goodput_ops"]["value"] == 1000.0
        assert set(line["metrics"]) == {"commit_p50_ms", "commit_p95_ms",
                                        "goodput_ops", "setup_s"}
        return
    # the three per-layer metrics the PR adds, beside the others
    metrics = line["metrics"]
    assert {"tick_period_ms", "req_queue_ms", "wal_fsync_ms",
            "execute_ms"} <= set(metrics)
    # half the replies are records of 1,000 bytes, half are OK
    assert metrics["reply_bytes_mean"]["unit"] == "B"
    assert 480 < metrics["reply_bytes_mean"]["value"] < 520
    # a tick journals the updates it placed: 115 bytes each and framing
    assert metrics["wal_bytes_per_tick"]["unit"] == "B"
    assert 100 < metrics["wal_bytes_per_tick"]["value"] < 100000
    # folded onto 4,032 names the hottest draws 4% of 1,000 req/s: some
    # ticks leave its requests behind, most leave nothing
    assert metrics["inbox_clear_pct"]["unit"] == "%"
    assert 20 < metrics["inbox_clear_pct"]["value"] <= 100
    assert "histogram_zero_share: inbox_deferred_requests" in out.stderr


#: the run with the served path broken underneath: ``KVApp.execute`` is what
#: produces every answer and every stored record of the timed path
BROKEN = """
import sys, time
T = time.monotonic()
sys.path.insert(0, ".")
from gigapaxos_tpu.models import replicable
plain = replicable.KVApp.execute
first = []
before = {}   # (replica, name) -> the record its latest SETRANGE changed
def execute(self, name, request, request_id):
    if not first and name.startswith("bg"):   # not the generator's probe app
        first.append(self)
    if request.startswith(b"SETRANGE") and name.startswith("bg"):
        before[id(self), name] = self.db[name]["r"]
        if FAULT == "skipped" and self is first[0] and request_id % 7 == 0:
            return b"OK"    # one replica skips the write and says it did it
    out = plain(self, name, request, request_id)
    if (FAULT == "stale_read" and request.startswith(b"GET")
            and (id(self), name) in before and request_id % 3 == 0):
        return before[id(self), name].encode()   # the record before it
    return out
replicable.KVApp.execute = execute
from chipbench import harness
sys.exit(harness.main(sys.argv[1:], T))
"""


@pytest.mark.parametrize("fault,says", [
    ("skipped", "replicas differ in fields"),
    ("stale_read", "stale: overwritten before the read began")])
def test_a_ycsb_run_whose_served_path_is_broken_is_not_correct(
        tmp_path, fault, says):
    out = subprocess.run(
        [sys.executable, "-c", BROKEN.replace("FAULT", repr(fault)), *YCSB,
         "--seed", "3000003603", "--seconds", "2", "--trace", "0"],
        cwd=ROOT, env=_env(tmp_path), text=True, capture_output=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["attempted"] == 2000
    wrong = line["compared"]["wrong_answers"]
    assert wrong["limit"] == 0 and wrong["value"] >= 10
    assert "WRONG: " in out.stderr and says in out.stderr
