"""Pallas TPU kernel for the ring-window plane gather.

``ops/window.gather_planes`` (the in-order delivery / tally alignment
primitive — ``PaxosAcceptor.putAndRemoveNextExecutable``'s ring read) is the
tick's hottest op: the XLA one-hot formulation materializes
``[..., J, Wp, G]`` broadcast temporaries in HBM, which at the BASELINE
configuration (W=8, G=1M) is ~768 MB per gather and ~10 gathers per tick
(byte counts from shapes), scaling with W².

This kernel performs the same per-lane permutation entirely in VMEM: each
grid step loads one ``[Wp, Gb]`` tile and its ``[J, Gb]`` index tile, emits
``out[j, g] = arr[idx[j, g], g]`` via an unrolled Wp-way select on
registers, and writes ``[J, Gb]`` back — HBM traffic is exactly one read of
``arr`` + ``idx`` and one write of ``out`` (the W² work stays on the VPU).

Used by the fused ticks whenever they run on a TPU backend
(``use_pallas_gather()``), and there it is the ONLY path: a shape the
kernels cannot serve is a trace-time error, never a silent drop to the
select chain.  The one-hot XLA path is what other backends run (the CPU
suite) and the semantic reference (``tests/test_pallas_gather.py`` checks
the two against each other).
"""

from __future__ import annotations

import contextlib
import functools
import math
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


def _lane_block(g: int) -> int:
    """Largest power-of-two-times-128 divisor of g, capped at 4096 lanes
    (callers only guarantee g % 128 == 0 — e.g. max_groups = 4224)."""
    return math.gcd(g, 4096)


def _gather_kernel(arr_ref, idx_ref, out_ref, *, wp: int, j_out: int,
                   perlead: bool):
    # arr [1, Wp, Gb]; idx [J, Gb] (shared) or [1, J, Gb] (per-lead);
    # out [1, J, Gb]
    for j in range(j_out):
        sel = idx_ref[0, j, :] if perlead else idx_ref[j, :]
        acc = jnp.zeros_like(out_ref[0, j, :])
        for i in range(wp):
            acc = jnp.where(sel == i, arr_ref[0, i, :], acc)
        out_ref[0, j, :] = acc


@functools.lru_cache(maxsize=None)
def _build(lead: int, wp: int, j_out: int, g: int, dtype_name: str,
           interpret: bool, perlead: bool = False):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)
    gb = _lane_block(g)
    kern = functools.partial(_gather_kernel, wp=wp, j_out=j_out,
                             perlead=perlead)
    idx_spec = (
        pl.BlockSpec((1, j_out, gb), lambda l, b: (l, 0, b)) if perlead
        else pl.BlockSpec((j_out, gb), lambda l, b: (0, b))
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((lead, j_out, g), dtype),
        grid=(lead, g // gb),
        in_specs=[
            pl.BlockSpec((1, wp, gb), lambda l, b: (l, 0, b)),
            idx_spec,
        ],
        out_specs=pl.BlockSpec((1, j_out, gb), lambda l, b: (l, 0, b)),
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
        out = _build(lead, wp, j_out, g, str(a.dtype), interpret,
                     perlead=True)(a, ix)
    else:
        ix = idx.astype(jnp.int32)
        out = _build(lead, wp, j_out, g, str(a.dtype), interpret)(a, ix)
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
                 interpret: bool):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)
    gb = _lane_block(g)
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
    out = _build_match(e_planes, j_out, g, str(v.dtype), interpret)(
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
