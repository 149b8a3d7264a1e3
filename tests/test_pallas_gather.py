"""Pallas ring-gather / match-select kernels vs the XLA one-hot reference.

Runs the pallas kernels in interpreter mode (CPU suite) over randomized
shapes — including every shape class the fused ticks use them with — and
checks exact equality against the portable select-chain implementations.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from gigapaxos_tpu.ops.pallas_gather import (gather_planes_pallas,
                                             match_planes_pallas)


@pytest.mark.parametrize(
    "lead,wp,j,g",
    [((3,), 8, 8, 256), ((3,), 12, 8, 128), ((), 8, 4, 128),
     ((2, 3), 8, 8, 256), ((3,), 4, 4, 512),
     # register plane (W=1), the shipped window on the odd lane block
     # max_groups=4224 leaves (gcd(4224, 4096) = 128), window-order W=4
     ((3,), 1, 1, 256), ((3,), 4, 4, 4224), ((), 4, 4, 128)],
)
def test_gather_planes_matches_take_along_axis(lead, wp, j, g):
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


@pytest.mark.parametrize("e,j,g", [(3, 8, 256), (12, 8, 128), (3, 4, 512)])
def test_match_planes_matches_reference(e, j, g):
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


@pytest.mark.parametrize("wp,g", [(4, 256), (1, 128), (8, 4224)])
def test_gather_planes_per_lead_indices(wp, g):
    """Phase 4's own-window gather: every replica row carries its own
    ``[J, G]`` index block (the kernel's ``perlead`` path)."""
    rng = np.random.default_rng(3)
    arr = rng.integers(-999, 999, size=(3, wp, g)).astype(np.int32)
    idx = rng.integers(0, wp, size=(3, wp, g)).astype(np.int32)
    got = np.asarray(gather_planes_pallas(
        jnp.asarray(arr), jnp.asarray(idx), interpret=True))
    assert (got == np.take_along_axis(arr, idx, axis=-2)).all()


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
