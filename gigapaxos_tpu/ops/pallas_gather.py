"""Pallas TPU kernel for the ring-window plane gather.

``ops/window.gather_planes`` (the in-order delivery / tally alignment
primitive — ``PaxosAcceptor.putAndRemoveNextExecutable``'s ring read) is the
tick's hottest op: the XLA one-hot formulation materializes
``[..., J, Wp, G]`` broadcast temporaries in HBM, which at the BASELINE
configuration (W=8, G=1M) is ~768 MB per gather and ~10 gathers per tick
(byte counts from shapes), scaling with W².

This kernel performs the same per-lane permutation entirely in VMEM: each
grid step loads one ``[lead, Wp, Gb]`` tile of ``arr`` and its ``[J, Gb]``
(or per-lead ``[lead, J, Gb]``) index tile, emits
``out[l, j, g] = arr[l, idx[j, g], g]`` via an unrolled Wp-way select on
registers, lead row by lead row, and writes ``[lead, J, Gb]`` back — HBM
traffic is one read of ``arr`` + ``idx`` and one write of ``out`` (the W²
work stays on the VPU).  ``match_planes_pallas`` tiles the same way.

**The tile.**  The grid runs over lane blocks only, and ``Gb`` is the
widest ``128 · 2^k`` that divides G and keeps the double-buffered tiles
(arr + idx + out, two buffers each) within :data:`VMEM_BUDGET`
(:func:`gather_lanes`, :func:`match_lanes`): 32,768 lanes at the tick's
``[3, 4, 1M]`` int32 gathers (32 steps), 65,536 for ``[4, 1M]``, 16,384 at
five replicas, 128 where G = 4,224.  A fixed 4,096-lane tile with the lead
axis on the grid made a 1M-lane ``[3, 4, G]`` call 768 steps of 192 KB,
about 0.23 µs of HBM time each at 819 GB/s, and the steps took about
0.48 µs (a v5e trace: 370 µs a call): half of every call was the fixed
cost of a step.  That layout also fetched a shared ``[J, G]`` index once
per lead row (3 × 16.8 MB at 1M where 16.8 MB is needed); with the lead
axis in the block it is fetched once per lane block.  On v5e the folded
lead axis is most of the gain (768 steps -> 256 at 4,096 lanes); past
8,192 lanes the width moves a call by a few percent.

Used by the fused ticks whenever they run on a TPU backend
(``use_pallas_gather()``), and there it is the ONLY path: a shape the
kernels cannot serve is a trace-time error, never a silent drop to the
select chain.  The one-hot XLA path is what other backends run (the CPU
suite) and the semantic reference (``tests/test_pallas_gather.py`` checks
the two against each other).  Each distinct build counts the lanes it took
in ``pallas_kernel_builds_total{kernel, lanes}``.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np


#: TPU lane width: the group (minor) axis tiles in blocks of this many
LANES = 128

#: stable kernel names: what a lowered program's custom calls and a profiler
#: trace's events are found by
GATHER_KERNEL = "gather_planes_pallas"
MATCH_KERNEL = "match_planes_pallas"


#: VMEM one call's tiles may take, double-buffered (arr + idx + out blocks,
#: two buffers each): under the 16 MiB of scoped VMEM a v5e kernel gets by
#: default, so no compiler parameter is needed
VMEM_BUDGET = 10 << 20


def _lane_block(g: int, lane_bytes: int, budget: int) -> int:
    """Lanes of one tile: the largest ``128 · 2^k`` that divides g and keeps
    the double-buffered working set (``2 · lane_bytes`` a lane) within
    ``budget``; 128 at the least (callers only guarantee g % 128 == 0, e.g.
    max_groups = 4224)."""
    gb = LANES
    while g % (2 * gb) == 0 and 2 * lane_bytes * (2 * gb) <= budget:
        gb *= 2
    return gb


def gather_lanes(lead: int, wp: int, j_out: int, g: int, itemsize: int,
                 perlead: bool, budget: int = VMEM_BUDGET) -> int:
    """Tile lanes of a gather: ``arr [lead, Wp, gb]``, ``idx [J, gb]`` (or
    ``[lead, J, gb]``) and ``out [lead, J, gb]`` a grid step."""
    rows = lead * wp + (lead if perlead else 1) * j_out + lead * j_out
    return _lane_block(g, rows * itemsize, budget)


def match_lanes(e_planes: int, j_out: int, g: int, itemsize: int,
                budget: int = VMEM_BUDGET) -> int:
    """Tile lanes of a key match: ``vals`` / ``keys [E, gb]`` and ``idx`` /
    ``out [J, gb]`` a grid step (keys and idx are int32)."""
    return _lane_block(g, (e_planes + j_out) * (itemsize + 4), budget)


def _count_build(kernel: str, lanes: int) -> None:
    from ..obs.metrics import registry

    registry().counter(
        "pallas_kernel_builds_total",
        help="Pallas plane kernels built, by the tile lanes the rule chose",
        kernel=kernel, lanes=str(lanes)).inc()


def _gather_kernel(arr_ref, idx_ref, out_ref, *, perlead: bool):
    # arr [lead, Wp, Gb]; idx [J, Gb] (shared) or [lead, J, Gb] (per-lead);
    # out [lead, J, Gb]
    lead, wp, _ = arr_ref.shape
    for l in range(lead):
        for j in range(out_ref.shape[1]):
            sel = idx_ref[l, j, :] if perlead else idx_ref[j, :]
            acc = jnp.zeros_like(out_ref[l, j, :])
            for i in range(wp):
                acc = jnp.where(sel == i, arr_ref[l, i, :], acc)
            out_ref[l, j, :] = acc


@functools.lru_cache(maxsize=None)
def _build(lead: int, wp: int, j_out: int, g: int, dtype_name: str,
           interpret: bool, perlead: bool, budget: int):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)
    gb = gather_lanes(lead, wp, j_out, g, dtype.itemsize, perlead, budget)
    _count_build(GATHER_KERNEL, gb)
    idx_spec = (
        pl.BlockSpec((lead, j_out, gb), lambda b: (0, 0, b)) if perlead
        else pl.BlockSpec((j_out, gb), lambda b: (0, b))
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, perlead=perlead),
        out_shape=jax.ShapeDtypeStruct((lead, j_out, g), dtype),
        grid=(g // gb,),
        in_specs=[
            pl.BlockSpec((lead, wp, gb), lambda b: (0, 0, b)),
            idx_spec,
        ],
        out_specs=pl.BlockSpec((lead, j_out, gb), lambda b: (0, 0, b)),
        interpret=interpret,
        name=GATHER_KERNEL,
    )


def _refuse(op: str, why: str, **shapes) -> ValueError:
    got = ", ".join(f"{k}{tuple(v)}" for k, v in shapes.items())
    return ValueError(
        f"{op}: no Pallas formulation for {got}: {why}.  On a TPU backend "
        f"there is no fallback path (size paxos.max_groups / "
        f"register_groups, per mesh shard, to a multiple of {LANES})")


def gather_planes_pallas(arr, idx, interpret: bool | None = None):
    """Drop-in for ``window.gather_planes`` on TPU.

    ``arr``: ``[..., Wp, G]``; ``idx``: ``[J, G]`` (shared across leading
    dims) or ``[..., J, G]``.  Lanes G must be a multiple of 128; any other
    shape raises at trace time.
    """
    if interpret is None:
        interpret = default_interpret()
    if arr.ndim < 2 or arr.shape[-1] % LANES:
        raise _refuse("gather_planes", f"the lane (last) axis must be a "
                      f"multiple of {LANES}", arr=arr.shape, idx=idx.shape)
    if not (idx.ndim == 2 or idx.shape == arr.shape[:-2] + idx.shape[-2:]):
        raise _refuse("gather_planes", "idx must be [J, G] or carry arr's "
                      "leading dims", arr=arr.shape, idx=idx.shape)
    wp, g = arr.shape[-2], arr.shape[-1]
    j_out = idx.shape[-2]
    lead_shape = arr.shape[:-2]
    lead = int(np.prod(lead_shape)) if lead_shape else 1
    # bool/i8 tiles hit Mosaic's narrow-dtype tiling constraints; gather in
    # i32 and cast back (the arrays this feeds are i32-dominated anyway)
    squeeze_bool = arr.dtype == jnp.bool_
    a = arr.astype(jnp.int32) if squeeze_bool else arr
    a = a.reshape(lead, wp, g)
    if idx.ndim > 2:
        # per-lead indices: flatten into the lead axis pairing
        ix = idx.reshape(lead, j_out, g).astype(jnp.int32)
        out = _build(lead, wp, j_out, g, str(a.dtype), interpret, True,
                     VMEM_BUDGET)(a, ix)
    else:
        ix = idx.astype(jnp.int32)
        out = _build(lead, wp, j_out, g, str(a.dtype), interpret, False,
                     VMEM_BUDGET)(a, ix)
    out = out.reshape(*lead_shape, j_out, g)
    return out.astype(jnp.bool_) if squeeze_bool else out


def _kernel_match(vals_ref, keys_ref, idx_ref, out_ref, *, e_planes: int,
                  j_out: int):
    # vals/keys [E, Gb]; idx [J, Gb]; out [J, Gb]
    for j in range(j_out):
        want = idx_ref[j, :]
        acc = jnp.zeros_like(out_ref[j, :])
        for e in range(e_planes):
            acc = jnp.where(keys_ref[e, :] == want, vals_ref[e, :], acc)
        out_ref[j, :] = acc


@functools.lru_cache(maxsize=None)
def _build_match(e_planes: int, j_out: int, g: int, dtype_name: str,
                 interpret: bool, budget: int):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)
    gb = match_lanes(e_planes, j_out, g, dtype.itemsize, budget)
    _count_build(MATCH_KERNEL, gb)
    kern = functools.partial(_kernel_match, e_planes=e_planes, j_out=j_out)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((j_out, g), dtype),
        grid=(g // gb,),
        in_specs=[
            pl.BlockSpec((e_planes, gb), lambda b: (0, b)),
            pl.BlockSpec((e_planes, gb), lambda b: (0, b)),
            pl.BlockSpec((j_out, gb), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((j_out, gb), lambda b: (0, b)),
        interpret=interpret,
        name=MATCH_KERNEL,
    )


def match_planes_pallas(vals, keys, idx, interpret: bool | None = None):
    """Per-lane key-match select (see window.match_planes): ``vals``/``keys``
    ``[E, G]``, ``idx`` ``[J, G]`` -> ``[J, G]``."""
    if interpret is None:
        interpret = default_interpret()
    if vals.ndim != 2 or keys.shape != vals.shape or idx.ndim != 2:
        raise _refuse("match_planes", "vals/keys must be [E, G] and idx "
                      "[J, G]", vals=vals.shape, keys=keys.shape,
                      idx=idx.shape)
    if vals.shape[-1] % LANES:
        raise _refuse("match_planes", f"the lane (last) axis must be a "
                      f"multiple of {LANES}", vals=vals.shape, idx=idx.shape)
    e_planes, g = vals.shape
    j_out = idx.shape[0]
    squeeze_bool = vals.dtype == jnp.bool_
    v = vals.astype(jnp.int32) if squeeze_bool else vals
    out = _build_match(e_planes, j_out, g, str(v.dtype), interpret,
                       VMEM_BUDGET)(
        v, keys.astype(jnp.int32), idx.astype(jnp.int32)
    )
    return out.astype(jnp.bool_) if squeeze_bool else out


_tls = threading.local()


@contextlib.contextmanager
def global_view_trace():
    """Mark the enclosed trace as a global-view program that GSPMD will
    partition over a multi-device mesh (``parallel/mesh.sharded_tick``).

    A pallas custom call has no sharding rule, so under GSPMD its
    ``[R, W, G]`` operands would be replicated across the mesh; that one
    formulation keeps the XLA select chain.  Everything else — a
    single-device jit, or a shard_map body, where each shard's block is
    concrete — runs the kernels.  The decision follows where the program
    runs, never how many devices the host happens to show.  The flag is
    thread-local because jit tracing of independent programs can race across
    threads (driver thread vs. test thread)."""
    prev = getattr(_tls, "global_view", False)
    _tls.global_view = True
    try:
        yield
    finally:
        _tls.global_view = prev


def in_global_view_trace() -> bool:
    return getattr(_tls, "global_view", False)


def _on_tpu() -> bool:
    # a backend that cannot initialise raises here: on a TPU host that is an
    # error to surface, not a reason to trace the select chain instead
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas interpret mode default (env GPTPU_PALLAS_INTERPRET=1): lets the
    CPU suite execute the real kernel path end-to-end inside shard_map."""
    return bool(os.environ.get("GPTPU_PALLAS_INTERPRET"))


def use_pallas_gather() -> bool:
    """True when the fused ticks route plane gathers through the pallas
    kernel: on a TPU backend, everywhere except inside a
    :func:`global_view_trace`.  Overrides: GPTPU_NO_PALLAS=1 forces off,
    GPTPU_PALLAS=1 forces on (pair with GPTPU_PALLAS_INTERPRET=1 off-TPU)."""
    if os.environ.get("GPTPU_NO_PALLAS"):
        return False
    if os.environ.get("GPTPU_PALLAS"):
        return True
    return not in_global_view_trace() and _on_tpu()


def check_lanes(g: int, what: str) -> None:
    """Refuse, at construction, a group count the kernels cannot tile —
    the same condition the trace would refuse, named before the first tick
    instead of inside it.  A no-op wherever the select chain runs."""
    if g % LANES and use_pallas_gather():
        raise ValueError(
            f"{what}={g} is not a multiple of {LANES}: the TPU ring-gather "
            f"kernels tile the group axis in {LANES}-lane blocks and there "
            f"is no fallback path on this backend")
