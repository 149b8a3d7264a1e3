"""Nothing on the served path may hide the device, or a plane that is down.

Unit tests for the pieces the chip bring-up rests on: a tick that raises
fails its plane's start-up instead of looking like client timeouts; the
compile cache goes where it is placed; the native journal is built from
source where the library is absent, and running the Python journal instead
is never silent; the cells supervisor counts chips without touching JAX and
keeps what its workers say on stderr.
"""

import logging
import os
import shutil
import sys

import pytest

from gigapaxos_tpu.config import CellsConfig, GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.paxos.driver import PlaneDown, TickDriver
from gigapaxos_tpu.paxos.manager import PaxosManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- a plane is down
class Refusal(RuntimeError):
    """Stands in for what a first tick can raise on a chip: a program the
    device compiler refuses, HBM exhaustion."""


def _small_cfg():
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    return cfg


def test_a_tick_that_raises_is_recorded_and_releases_waiters(monkeypatch):
    m = PaxosManager(_small_cfg(), 3, [KVApp() for _ in range(3)])
    monkeypatch.setattr(m, "tick", lambda: (_ for _ in ()).throw(
        Refusal("Mosaic failed to compile TPU kernel")))
    d = TickDriver(m).start()
    # released at once, not after the 120 s start-up timeout
    assert d.wait_ready(60) is False
    assert isinstance(d.fatal, Refusal)
    with pytest.raises(PlaneDown, match="Mosaic failed to compile") as ei:
        d.require_ready(60)
    assert ei.value.__cause__ is d.fatal
    d.stop()
    assert not d._thread.is_alive()


def test_a_plane_that_never_ticks_times_out_as_down():
    m = PaxosManager(_small_cfg(), 3, [KVApp() for _ in range(3)])
    d = TickDriver(m)  # never started: its first tick never completes
    with pytest.raises(PlaneDown, match="did not complete"):
        d.require_ready(0.05)


def test_cluster_construction_raises_on_a_dead_plane(monkeypatch):
    from gigapaxos_tpu.node import InProcessCluster

    def refuse(self):
        raise Refusal("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setattr(PaxosManager, "tick", refuse)
    cfg = _small_cfg()
    for i in range(3):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", 0)
        cfg.nodes.reconfigurators[f"RC{i}"] = ("127.0.0.1", 0)
    with pytest.raises(PlaneDown, match="out of HBM"):
        InProcessCluster(cfg, KVApp, ready_timeout_s=60)


def test_a_ready_cluster_freezes_what_it_holds_and_thaws_it_on_close():
    """ISSUE 36: once both planes are up the collector's oldest generation
    no longer walks what the process holds for life (``gc.freeze``); a closed
    cluster hands all of it back, so that its cycles are collected."""
    import gc

    from gigapaxos_tpu.node import InProcessCluster

    cfg = _small_cfg()
    for i in range(3):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", 0)
        cfg.nodes.reconfigurators[f"RC{i}"] = ("127.0.0.1", 0)
    gc.unfreeze()
    young, middle, oldest = gc.get_threshold()
    cluster = InProcessCluster(cfg, KVApp, ready_timeout_s=120)
    try:
        # ... and collects that generation a hundredth as often while it
        # serves (a deployment's records are made after this point, and a
        # collection walks them with every thread stopped); the young
        # generations as before
        assert gc.get_threshold() == (young, middle, 100 * oldest)
        frozen = gc.get_freeze_count()
        # the modules alone are tens of thousands of tracked objects
        assert frozen > 10_000
        assert gc.isenabled()
        # what is made from here on is the collector's as before
        ring = []
        ring.append(ring)
        del ring
        assert gc.collect() >= 1
        # (a frozen object still goes when its last reference does)
        assert 10_000 < gc.get_freeze_count() <= frozen
    finally:
        cluster.close()
    assert gc.get_freeze_count() == 0
    assert gc.get_threshold() == (young, middle, oldest)


# ------------------------------------------------------------- compile cache
def test_compile_cache_goes_where_it_is_placed(monkeypatch, tmp_path):
    import jax

    from gigapaxos_tpu import compile_cache

    saved = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: JAX reads the variable itself, no code sets
        # another directory
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved
        # not placed: one fixed path under the checkout, whatever the
        # working directory
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.chdir(tmp_path)
        first = compile_cache.configure()
        monkeypatch.chdir(ROOT)
        assert compile_cache.configure() == first
        assert first == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


# ------------------------------------------------------------ native journal
@pytest.fixture
def fresh_native_loader(monkeypatch, tmp_path):
    """The loader pointed at a copy of native/ that holds the source and the
    Makefile but no built library — what a fresh clone looks like."""
    from gigapaxos_tpu.wal import native_journal as nj

    native = tmp_path / "native"
    native.mkdir()
    for f in ("journal.cc", "Makefile"):
        shutil.copy(os.path.join(ROOT, "native", f), native / f)
    monkeypatch.setattr(nj, "_NATIVE_DIR", str(native))
    monkeypatch.setattr(nj, "_LIB", None)
    monkeypatch.setattr(nj, "_LOAD_ERROR", None)
    return nj, native


def test_native_library_is_built_from_source_when_absent(
        fresh_native_loader, tmp_path):
    nj, native = fresh_native_loader
    assert not (native / "libgpjournal.so").exists()
    j = nj.NativeJournal(str(tmp_path / "journal.00000000.log"))
    j.append(b"record")
    j.sync()
    j.close()
    assert (native / "libgpjournal.so").exists()
    assert not list(native.glob("*.tmp"))  # renamed into place, not left


def test_python_journal_stand_in_is_never_silent(
        fresh_native_loader, tmp_path, caplog):
    from gigapaxos_tpu.wal import logger as wal_logger
    from gigapaxos_tpu.wal.journal import PyJournal

    nj, native = fresh_native_loader
    (native / "journal.cc").write_text("this is not C++\n")
    wal_logger._NATIVE_FALLBACK_LOGGED.clear()
    with caplog.at_level(logging.WARNING, logger="gptpu.wal"):
        js = [wal_logger._new_journal(str(tmp_path / f"j{i}.log"), True)
              for i in range(3)]
    for j in js:
        assert isinstance(j, PyJournal)
        j.close()
    said = [r for r in caplog.records if "native journal" in r.getMessage()]
    assert len(said) == 1  # the reason, once — not once per journal roll
    assert "error" in said[0].getMessage()  # the compiler's own words


# ------------------------------------------------------------ cells and chips
def test_supervisor_counts_chips_without_jax(monkeypatch):
    from gigapaxos_tpu.cells import supervisor as sup

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
    assert sup.visible_tpu_chips() == 0  # the platform is pinned elsewhere
    monkeypatch.delenv("JAX_PLATFORMS")
    assert sup.visible_tpu_chips() == 4
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2")
    assert sup.visible_tpu_chips() == 1
    env = sup.chip_env(3, 9999)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["TPU_PROCESS_PORT"] == "9999"


def test_supervisor_refuses_more_cells_than_chips(monkeypatch, tmp_path):
    from gigapaxos_tpu.cells import supervisor as sup

    monkeypatch.setattr(sup, "visible_tpu_chips", lambda: 1)
    with pytest.raises(ValueError, match="2 cells on a host with 1 TPU"):
        sup.CellSupervisor(str(tmp_path), cells=CellsConfig(
            enabled=True, n_cells=2))
    # several chips: each worker is confined to its own through its
    # environment
    monkeypatch.setattr(sup, "visible_tpu_chips", lambda: 4)
    s = sup.CellSupervisor(str(tmp_path), cells=CellsConfig(
        enabled=True, n_cells=2))
    try:
        chips = [s.specs[k].env["TPU_VISIBLE_CHIPS"] for k in range(2)]
        ports = {s.specs[k].env["TPU_PROCESS_PORT"] for k in range(2)}
        assert chips == ["0", "1"] and len(ports) == 2
        assert "TPU_VISIBLE_CHIPS" not in s.specs[0].to_json()
    finally:
        s.m.close()


def test_worker_stderr_is_kept(tmp_path):
    from gigapaxos_tpu.cells.supervisor import CellHandle, CellSpec

    spec = CellSpec(cell=0, n_cells=1, actives={}, reconfigurators={},
                    peers={}, wal_dir=str(tmp_path / "c0" / "ar"),
                    rc_wal_dir=str(tmp_path / "c0" / "rc"))
    # a worker that dies at start-up (here: an empty topology) used to say
    # nothing at all
    h = CellHandle(spec, python=sys.executable)
    with pytest.raises(RuntimeError, match="startup_failed|worker exited"):
        h.expect("ready", timeout=120)
    h.proc.wait(timeout=60)
    assert h.stderr_path == str(tmp_path / "c0" / "worker.stderr")
    assert os.path.exists(h.stderr_path)
