"""``run.py`` on the four-chip cell's rehearsal configuration, end to end on
four virtual CPU devices: the sharded path builds through the same
``deployment.make_config`` as the one-chip cell, nothing compiles inside the
window (``deployment.warm_sweep_buckets`` reaches the sweep programs of
sharded state), and the line carries the host metric the mesh adds."""

import json
import os
import subprocess
import sys

from chipbench import measure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSE = ["--config-file", "chipbench/configs/rehearsal-3r-4k-mesh4.json",
            "--traffic", "open1k-put-uniform"]


def _run(args, tmp_path, devices: int):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("GPTPU_", "CHIPBENCH_"))}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               CHIPBENCH_REHEARSAL="1", GPTPU_PALLAS="1",
               GPTPU_PALLAS_INTERPRET="1")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args, "--seed", "3000003011"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=600)


def test_rehearsal_on_four_virtual_devices_prints_the_contracts_line(tmp_path):
    out = _run(REHEARSE + ["--seconds", "3", "--trace", "1"], tmp_path, 4)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["attempted"] == 3000 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    diag = measure.diag_of(out.stderr)
    assert diag["compiles"] == {"n": 0, "s": 0.0}
    assert diag["ar"]["tick1"] > diag["ar"]["tick0"] > 0
    assert "sweep: 10 row buckets up to 8192 compiled" in out.stderr
    # the mesh program is not the plain compact tick: said, not raised
    assert line["metrics"]["mesh_dispatch_ms"]["value"] > 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    from_trace = {m["name"] for m in bench["per_layer"]
                  if m["source"] == "device_trace"}
    assert set(line["metrics"]) == {
        m["name"] for m in bench["per_layer"]} - from_trace


def test_fewer_devices_than_the_configuration_names_is_refused(tmp_path):
    out = _run(REHEARSE + ["--seconds", "1", "--trace", "0"], tmp_path, 2)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "needs 4 chip(s); JAX shows 2" in out.stderr
