"""``run.py`` end to end off the chip: the rehearsal prints a last line with
exactly the contract's keys, and without the switch a CPU prints no result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSE = ["--config-file", "chipbench/configs/rehearsal-3r-4k.json",
            "--traffic", "open1k-put-uniform"]
#: the fixture of the harness's read path: reads beside updates, names drawn
#: as YCSB draws them, records loaded before the warm-up
MIX = REHEARSE[:3] + ["open1k-rw-zipf"]


def _run(args, tmp_path, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("GPTPU_", "CHIPBENCH_"))}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args, "--seed", "3000000011"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(tmp_path, trace):
    out = _run(REHEARSE + ["--seconds", "3", "--trace", str(trace)], tmp_path,
               CHIPBENCH_REHEARSAL="1", GPTPU_PALLAS="1",
               GPTPU_PALLAS_INTERPRET="1")
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True
    assert line["compared"] == {
        "wrong_answers": {"value": 0, "limit": 0},
        "requests_due": {"value": 3000, "limit": ">0"}}
    assert out.stderr.strip().splitlines()[-2:] == [
        "compared: wrong_answers 0 (limit 0)",
        "compared: requests_due 3000 (limit >0)"]
    from chipbench import measure

    diag = measure.diag_of(out.stderr)   # the line measure.py copies to a set
    assert diag["ar"]["tick1"] > diag["ar"]["tick0"] > 0
    assert diag["rc"]["period_ms"] > 0 and len(diag["gc_collections"]) == 3
    # nothing compiles inside the window (the sweep's row buckets were warmed
    # in set-up), and the host's counters over it are there
    assert diag["compiles"] == {"n": 0, "s": 0.0}
    assert diag["host"]["user_s"] > 0 and diag["host"]["minor_faults"] >= 0
    assert "sweep: 10 row buckets up to 8192 compiled" in out.stderr
    assert line["attempted"] == 3000 and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if trace == 0:
        assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        assert line["metrics"]["goodput_ops"] == {"value": 1000.0,
                                                  "unit": "ops/s"}
    else:
        # off the chip no metric read from the device trace is printed
        printed = set(line["metrics"])
        from_trace = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
        assert printed == {m["name"] for m in bench["per_layer"]} - from_trace
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert not [p for p in os.listdir(tmp_path) if p.startswith("chipbench_")]


def test_the_mix_of_reads_and_updates_rehearses_correct(tmp_path):
    out = _run(MIX + ["--seconds", "3", "--trace", "0"], tmp_path,
               CHIPBENCH_REHEARSAL="1", GPTPU_PALLAS="1",
               GPTPU_PALLAS_INTERPRET="1")
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["attempted"] == 3000 and line["failed"] == 0
    assert line["compared"] == {
        "wrong_answers": {"value": 0, "limit": 0},
        "requests_due": {"value": 3000, "limit": ">0"}}
    assert line["metrics"]["goodput_ops"]["value"] == 1000.0
    notes = [ln.split("] ", 1)[1] for ln in out.stderr.splitlines()
             if ln.startswith("[") and "] " in ln]
    # the records were loaded after the populate and before the warm-up, so
    # inside setup_s, through the manager's bulk path
    order = [n.split(":")[0] for n in notes if n.split(":")[0] in (
        "populated and adopted 4,032 groups", "preload", "warm-up", "window")]
    assert order == ["populated and adopted 4,032 groups", "preload",
                     "warm-up", "window"], notes
    assert any(n.startswith("preload: 4,032 records") for n in notes)
    # reads and updates are both among the acknowledged requests
    kinds = [n for n in notes if n.startswith("acknowledged by kind: ")]
    assert len(kinds) == 1
    by_kind = json.loads(kinds[0].split(": ", 1)[1])
    assert set(by_kind) == {"read", "update"}
    assert by_kind["read"] >= 1500 and by_kind["update"] >= 1500
    assert sum(by_kind.values()) >= 3000   # the warm-up's are checked too


#: the run with the preload cut into waves of 1,000, as a load of 1M records
#: is cut into waves of 262,144 on the chip
WAVES = """
import functools, sys, time
T = time.monotonic()
sys.path.insert(0, ".")
from chipbench import deployment, harness
plain = deployment._bulk_wave
def counted(cluster, rows, payloads, timeout_s):
    print(f"wave of {len(payloads)}", file=sys.stderr)
    return plain(cluster, rows, payloads, timeout_s)
deployment._bulk_wave = counted
deployment.preload = functools.partial(deployment.preload, wave=1000)
sys.exit(harness.main(sys.argv[1:], T))
"""


def test_a_preload_of_several_waves_rehearses_correct(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("GPTPU_", "CHIPBENCH_"))}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               CHIPBENCH_REHEARSAL="1", GPTPU_PALLAS="1",
               GPTPU_PALLAS_INTERPRET="1")
    out = subprocess.run(
        [sys.executable, "-c", WAVES, *MIX, "--seed", "3000000013",
         "--seconds", "2", "--trace", "0"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["attempted"] == 2000 and line["failed"] == 0
    assert [ln for ln in out.stderr.splitlines() if ln.startswith("wave of ")
            ] == ["wave of 1000"] * 4 + ["wave of 32"]
    assert "preload: 4,032 records" in out.stderr


def test_a_cpu_without_the_switch_prints_no_result(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    out = _run(["--workload", cell, "--seconds", "1"], tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "not a TPU" in out.stderr
    out = _run(REHEARSE + ["--seconds", "1"], tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""


#: the run with the served path broken underneath: ``KVApp.execute`` is what
#: produces every answer and every stored value of the timed path
BROKEN = """
import sys, time
T = time.monotonic()
sys.path.insert(0, ".")
from gigapaxos_tpu.models import replicable
plain = replicable.KVApp.execute
first = []
before = {}   # (replica, name) -> the value its latest PUT overwrote
def execute(self, name, request, request_id):
    if request.startswith(b"PUT") and "k" in self.db.get(name, {}):
        before[id(self), name] = self.db[name]["k"]
    out = plain(self, name, request, request_id)
    first.append(self) if not first else None
    if (FAULT == "stale_read" and request.startswith(b"GET")
            and (id(self), name) in before and request_id % 3 == 0):
        return before[id(self), name].encode()   # an overwritten value
    if request.startswith(b"PUT") and hash(request) % 50 == 0:
        if FAULT == "reply":
            return b"KO"                   # the answer, altered where produced
        if FAULT == "stored" and self is first[0]:
            self.db[name]["k"] = "altered"  # one replica holds another value
        if FAULT == "lost":
            del self.db[name]["k"]          # acknowledged, held by nobody
    return out
replicable.KVApp.execute = execute
from chipbench import harness
sys.exit(harness.main(sys.argv[1:], T))
"""


@pytest.mark.parametrize("traffic,fault", [
    (REHEARSE, "reply"), (REHEARSE, "stored"), (REHEARSE, "lost"),
    (MIX, "stored"), (MIX, "stale_read")])
def test_a_run_whose_served_path_is_broken_is_not_correct(tmp_path, traffic,
                                                          fault):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("GPTPU_", "CHIPBENCH_"))}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path), PYTHONHASHSEED="0",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               CHIPBENCH_REHEARSAL="1", GPTPU_PALLAS="1",
               GPTPU_PALLAS_INTERPRET="1")
    out = subprocess.run(
        [sys.executable, "-c", BROKEN.replace("FAULT", repr(fault)), *traffic,
         "--seed", "3000000012", "--seconds", "2", "--trace", "0"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["attempted"] == 2000
    wrong = line["compared"]["wrong_answers"]
    assert wrong["limit"] == 0 and wrong["value"] >= 10
    assert f"compared: wrong_answers {wrong['value']} (limit 0)" in out.stderr
    assert "WRONG: " in out.stderr
    if fault == "stale_read":
        assert "stale: overwritten before the read began" in out.stderr
