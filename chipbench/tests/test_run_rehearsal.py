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
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True
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


def test_a_cpu_without_the_switch_prints_no_result(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    out = _run(["--workload", cell, "--seconds", "1"], tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "not a TPU" in out.stderr
    out = _run(REHEARSE + ["--seconds", "1"], tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
