"""Pallas ring-gather / match-select kernels vs the XLA one-hot reference.

Runs the pallas kernels in interpreter mode (CPU suite) over randomized
shapes — including every shape class the fused ticks use them with — and
checks exact equality against the portable select-chain implementations.
Cases with a ``budget`` lower the kernels' VMEM budget, so that a few
thousand lanes tile into a grid of several steps, as 1M lanes do on the
chip; the tile rule itself is checked as a pure function at the
production shapes.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from gigapaxos_tpu.ops import pallas_gather as pg
from gigapaxos_tpu.ops.pallas_gather import (gather_planes_pallas,
                                             match_planes_pallas)

#: a budget under which 4,096 lanes tile into 4-16 grid steps
SMALL = 150_000


def _lower_budget(monkeypatch, budget, steps):
    """Lower the kernels' VMEM budget for one case and check that the case
    then runs a grid of several steps."""
    if budget is None:
        return
    monkeypatch.setattr(pg, "VMEM_BUDGET", budget)
    assert steps() >= 4


@pytest.mark.parametrize(
    "lead,wp,j,g,budget",
    [((3,), 8, 8, 256, None), ((3,), 12, 8, 128, None),
     ((), 8, 4, 128, None), ((2, 3), 8, 8, 256, None),
     ((3,), 4, 4, 512, None),
     # register plane (W=1), the shipped window on the odd lane block
     # max_groups=4224 leaves (128 lanes a tile), window-order W=4
     ((3,), 1, 1, 256, None), ((3,), 4, 4, 4224, None),
     ((), 4, 4, 128, None),
     # lead 1, 3 and 5 (five replicas) folded into tiles of a several-step
     # grid
     ((1,), 4, 4, 4096, SMALL), ((3,), 4, 4, 4096, SMALL),
     ((5,), 4, 4, 4096, SMALL), ((), 4, 4, 4096, SMALL)],
)
def test_gather_planes_matches_take_along_axis(lead, wp, j, g, budget,
                                               monkeypatch):
    _lower_budget(monkeypatch, budget, lambda: g // pg.gather_lanes(
        int(np.prod(lead)), wp, j, g, 4, False, budget))
    rng = np.random.default_rng(42)
    arr = rng.integers(-999, 999, size=lead + (wp, g)).astype(np.int32)
    idx = rng.integers(0, wp, size=(j, g)).astype(np.int32)
    got = np.asarray(
        gather_planes_pallas(jnp.asarray(arr), jnp.asarray(idx),
                             interpret=True)
    )
    want = np.take_along_axis(arr, np.broadcast_to(idx, lead + (j, g)),
                              axis=-2)
    assert (got == want).all()
    # bool payloads ride an i32 cast inside the kernel
    ab = arr % 2 == 0
    gotb = np.asarray(
        gather_planes_pallas(jnp.asarray(ab), jnp.asarray(idx),
                             interpret=True)
    )
    assert (gotb == np.take_along_axis(
        ab, np.broadcast_to(idx, lead + (j, g)), axis=-2)).all()


@pytest.mark.parametrize("e,j,g,budget", [
    (3, 8, 256, None), (12, 8, 128, None), (3, 4, 512, None),
    # the intake's shape ([R·P, G] entries, W rows), several grid steps
    (12, 4, 4096, SMALL), (3, 8, 2048, SMALL // 4)])
def test_match_planes_matches_reference(e, j, g, budget, monkeypatch):
    _lower_budget(monkeypatch, budget,
                  lambda: g // pg.match_lanes(e, j, g, 4, budget))
    rng = np.random.default_rng(7)
    vals = rng.integers(1, 999, size=(e, g)).astype(np.int32)
    # unique keys per lane among matchable entries, some -1 (masked out)
    keys = np.argsort(rng.random((e, g)), axis=0).astype(np.int32)
    keys[rng.random((e, g)) < 0.3] = -1
    idx = rng.integers(0, e, size=(j, g)).astype(np.int32)
    got = np.asarray(
        match_planes_pallas(jnp.asarray(vals), jnp.asarray(keys),
                            jnp.asarray(idx), interpret=True)
    )
    want = np.zeros((j, g), np.int32)
    for jj in range(j):
        for ee in range(e):
            hit = keys[ee] == idx[jj]
            want[jj][hit] = vals[ee][hit]
    assert (got == want).all()
    # bool payloads (the stop flags) ride an i32 cast inside the kernel
    vb = vals % 2 == 0
    gotb = np.asarray(
        match_planes_pallas(jnp.asarray(vb), jnp.asarray(keys),
                            jnp.asarray(idx), interpret=True)
    )
    assert gotb.dtype == np.bool_ and (gotb == (want % 2 == 0)
                                       & (want != 0)).all()


@pytest.mark.parametrize("lead,wp,g,budget", [
    (3, 4, 256, None), (3, 1, 128, None), (3, 8, 4224, None),
    (1, 4, 4096, SMALL), (3, 4, 4096, SMALL), (5, 4, 4096, SMALL)])
def test_gather_planes_per_lead_indices(lead, wp, g, budget, monkeypatch):
    """Phase 4's own-window gather: every replica row carries its own
    ``[J, G]`` index block (the kernel's ``perlead`` path)."""
    _lower_budget(monkeypatch, budget, lambda: g // pg.gather_lanes(
        lead, wp, wp, g, 4, True, budget))
    rng = np.random.default_rng(3)
    arr = rng.integers(-999, 999, size=(lead, wp, g)).astype(np.int32)
    idx = rng.integers(0, wp, size=(lead, wp, g)).astype(np.int32)
    got = np.asarray(gather_planes_pallas(
        jnp.asarray(arr), jnp.asarray(idx), interpret=True))
    assert (got == np.take_along_axis(arr, idx, axis=-2)).all()
    ab = arr % 2 == 0
    gotb = np.asarray(gather_planes_pallas(
        jnp.asarray(ab), jnp.asarray(idx), interpret=True))
    assert (gotb == np.take_along_axis(ab, idx, axis=-2)).all()


M = 1 << 20


@pytest.mark.parametrize("kind,dims,lanes", [
    # the tick's [R, W, G] gathers at 1M: a shared [J, G] index (prepare,
    # tally) and a per-replica one (execute); to_ring's [W, G]; five
    # replicas; a four-chip shard; the 128k configuration; the intake's
    # key match ([R·P, G] entries, W rows); max_groups = 4224
    ("gather", dict(lead=3, wp=4, j=4, g=M, perlead=False), 32768),
    ("gather", dict(lead=3, wp=4, j=4, g=M, perlead=True), 32768),
    ("gather", dict(lead=1, wp=4, j=4, g=M, perlead=False), 65536),
    ("gather", dict(lead=5, wp=4, j=4, g=M, perlead=False), 16384),
    ("gather", dict(lead=3, wp=4, j=4, g=M // 4, perlead=False), 32768),
    ("gather", dict(lead=3, wp=4, j=4, g=M // 8, perlead=False), 32768),
    ("gather", dict(lead=3, wp=4, j=4, g=4224, perlead=False), 128),
    ("match", dict(e=12, j=4, g=M), 32768),
    ("match", dict(e=12, j=4, g=4224), 128),
])
def test_tile_rule_at_the_production_shapes(kind, dims, lanes):
    """The tile is read off the call's shape: the widest ``128 · 2^k``
    lanes that divide G and whose double-buffered tiles fit the budget."""
    g, budget = dims["g"], pg.VMEM_BUDGET
    if kind == "gather":
        lead, wp, j = dims["lead"], dims["wp"], dims["j"]
        gb = pg.gather_lanes(lead, wp, j, g, 4, dims["perlead"])
        idx_rows = (lead if dims["perlead"] else 1) * j
        rows = lead * wp + idx_rows + lead * j
    else:
        gb = pg.match_lanes(dims["e"], dims["j"], g, 4)
        rows = 2 * dims["e"] + 2 * dims["j"]
    working_set = lambda lanes: 2 * 4 * rows * lanes  # noqa: E731
    assert gb == lanes
    assert gb % pg.LANES == 0 and g % gb == 0
    assert working_set(gb) <= budget
    # widest: twice the lanes would not divide G or not fit
    assert g % (2 * gb) or working_set(2 * gb) > budget
    if g == M and dims.get("lead") == 3:
        assert g // gb <= 64


def test_each_build_counts_its_lanes(monkeypatch):
    """A kernel build (once per distinct shape, at trace time) counts the
    lanes its tile took, by kernel."""
    from gigapaxos_tpu.obs.metrics import registry

    monkeypatch.setattr(pg, "VMEM_BUDGET", 200_000)
    c = registry().counter("pallas_kernel_builds_total",
                           kernel=pg.GATHER_KERNEL, lanes="1024")
    m = registry().counter("pallas_kernel_builds_total",
                           kernel=pg.MATCH_KERNEL, lanes="1024")
    before = (c.value, m.value)
    arr = jnp.zeros((2, 4, 2048), jnp.int32)
    idx = jnp.zeros((4, 2048), jnp.int32)
    gather_planes_pallas(arr, idx, interpret=True)
    gather_planes_pallas(arr + 1, idx, interpret=True)  # built already
    match_planes_pallas(jnp.zeros((4, 2048), jnp.int32),
                        jnp.zeros((4, 2048), jnp.int32), idx,
                        interpret=True)
    assert (c.value, m.value) == (before[0] + 1, before[1] + 1)


def test_kernels_refuse_shapes_they_cannot_tile():
    """No silent drop to the select chain: a lane count that is not a
    multiple of 128, or an index rank the kernel does not know, raises at
    trace time and names the shape."""
    a = jnp.zeros((3, 4, 100), jnp.int32)
    with pytest.raises(ValueError, match=r"arr\(3, 4, 100\)"):
        gather_planes_pallas(a, jnp.zeros((4, 100), jnp.int32),
                             interpret=True)
    with pytest.raises(ValueError, match="leading dims"):
        gather_planes_pallas(jnp.zeros((3, 4, 128), jnp.int32),
                             jnp.zeros((2, 4, 128), jnp.int32),
                             interpret=True)
    with pytest.raises(ValueError, match=r"vals\(12, 100\)"):
        match_planes_pallas(jnp.zeros((12, 100), jnp.int32),
                            jnp.zeros((12, 100), jnp.int32),
                            jnp.zeros((4, 100), jnp.int32), interpret=True)


def test_window_ops_have_no_fallback_where_the_kernels_run(monkeypatch):
    """ops/window routes through the kernels whenever the policy says so —
    and then an untileable shape is an error, not the one-hot path."""
    from gigapaxos_tpu.ops import pallas_gather as pg
    from gigapaxos_tpu.ops import window

    monkeypatch.delenv("GPTPU_PALLAS", raising=False)
    monkeypatch.delenv("GPTPU_NO_PALLAS", raising=False)
    arr = jnp.arange(3 * 4 * 100, dtype=jnp.int32).reshape(3, 4, 100)
    idx = jnp.zeros((4, 100), jnp.int32)
    window.gather_planes(arr, idx)  # CPU backend: the select chain
    monkeypatch.setattr(pg, "_on_tpu", lambda: True)
    monkeypatch.setenv("GPTPU_PALLAS_INTERPRET", "1")
    with pytest.raises(ValueError, match="multiple of 128"):
        window.gather_planes(arr, idx)
    with pytest.raises(ValueError, match="multiple of 128"):
        window.match_planes(arr[0], arr[1], idx)


def test_policy_follows_the_program_not_the_device_count(monkeypatch):
    from gigapaxos_tpu.ops import pallas_gather as pg

    for k in ("GPTPU_PALLAS", "GPTPU_NO_PALLAS"):
        monkeypatch.delenv(k, raising=False)
    assert not pg.use_pallas_gather()  # this suite runs on the CPU backend
    monkeypatch.setattr(pg, "_on_tpu", lambda: True)
    # jax.devices() is never consulted: 8 virtual devices are visible here
    assert pg.use_pallas_gather()
    with pg.global_view_trace():
        assert not pg.use_pallas_gather()
    assert pg.use_pallas_gather()
    with pytest.raises(ValueError, match="max_groups=1000"):
        pg.check_lanes(1000, "paxos.max_groups")
    pg.check_lanes(4224, "paxos.max_groups")

    # a backend that cannot initialise is an error, never "no Pallas"
    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(pg, "_on_tpu", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pg.use_pallas_gather()
