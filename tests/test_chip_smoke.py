"""chip_smoke.py off the chip: the rehearsal, and the refusals.

The smoke itself has no CPU mode.  Its stages are importable functions, and
this test runs all of them in a fresh process at 4,096 groups with the Pallas
kernels interpreted — the same code the chip runs at 1M groups, so a stage
that stops composing fails here, before it costs chip time.  The process is
separate so that no jit cache traced without the kernels can stand in for
the programs under test, and so the environment switches stay out of this
one.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("GPTPU_PALLAS") and k != "GPTPU_NO_PALLAS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def test_rehearsal_runs_every_stage_with_interpreted_kernels(tmp_path):
    cache = tmp_path / "placed_cache"
    default = os.path.join(ROOT, ".jax_cache")
    before = sorted(os.listdir(default)) if os.path.isdir(default) else None
    code = (
        "import chip_smoke\n"
        "dev = chip_smoke.run(groups=4096, wave=1024, on_chip=False,\n"
        "                     log_path=None, ready_timeout_s=600,\n"
        "                     rpc_timeout_s=120)\n"
        "print('REHEARSAL-OK', dev['platform'])\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=str(tmp_path), text=True,
        capture_output=True, timeout=900,
        env=_env(GPTPU_PALLAS="1", GPTPU_PALLAS_INTERPRET="1",
                 JAX_COMPILATION_CACHE_DIR=str(cache)),
    )
    tail = out.stdout[-3000:] + out.stderr[-3000:]
    assert out.returncode == 0, tail
    assert "REHEARSAL-OK cpu" in out.stdout, tail
    # every gated stage ran, on the kernel path
    for needle in ("journal backend NativeJournal",
                   "read back 3 acknowledged values",
                   "wide wave: 1,024 groups executed on 3 replicas",
                   "served tick: {'pallas_calls': 26, 'interpreted': 26",
                   # the tile the rule took at 4,096 lanes: one block
                   "kernel=gather_planes_pallas,lanes=4096",
                   "kernel=match_planes_pallas,lanes=4096",
                   "program mixed: compiled and ran",
                   "program lease: compiled and ran",
                   "program health: compiled and ran",
                   "sparse, 0 overflowed",
                   "after restart: 1,024 wave groups equal"):
        assert needle in out.stdout, (needle, tail)
    # a cache placed from outside is the one written, and the only one
    assert any(cache.iterdir())
    assert f"compile cache: {cache} (JAX_COMPILATION_CACHE_DIR" in out.stdout
    after = sorted(os.listdir(default)) if os.path.isdir(default) else None
    assert after == before
    assert not (tmp_path / ".jax_cache").exists()


def _refusal(script):
    out = subprocess.run([sys.executable, os.path.join(ROOT, script)],
                         cwd=ROOT, text=True, capture_output=True,
                         timeout=300, env=_env())
    assert out.returncode != 0, out.stdout + out.stderr
    assert out.stdout.strip() == "", out.stdout  # no result, no number
    assert "not a TPU" in out.stderr, out.stderr


def test_chip_smoke_exits_nonzero_without_a_chip():
    _refusal("chip_smoke.py")


def test_bench_exits_nonzero_without_a_chip():
    _refusal("bench.py")


def test_chip_smoke_refuses_interpreted_kernels():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], cwd=ROOT,
        text=True, capture_output=True, timeout=300,
        env=_env(GPTPU_PALLAS_INTERPRET="1"))
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "GPTPU_PALLAS_INTERPRET is set" in out.stderr
