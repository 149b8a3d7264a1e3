"""Ring-buffer window primitives for ``[..., W, G]`` arrays (G = lane axis).

The reference keeps per-group sparse maps ``acceptedProposals`` and
``committedRequests`` keyed by slot (``PaxosAcceptor.java:108-115``) whose
size is bounded in practice by the out-of-order arrival window.  Here each
group owns a fixed ring of W slots: slot ``s`` lives at ring plane
``s & (W-1)`` (the second-to-last axis) and an entry is valid only for slots
in ``[exec_slot, exec_slot + W)``.  In-order extraction
(``PaxosAcceptor.putAndRemoveNextExecutable``, PaxosAcceptor.java:325-366)
becomes a leading-run count over the reordered window — branch-free and
lane-parallel.  W stays off the lane axis on purpose: a minor dimension of 8
pads to 128 on TPU (16x HBM blowup); see state.py's layout note.
"""

from __future__ import annotations

import jax.numpy as jnp


def ring_index(slots, window: int):
    """Ring index for (possibly wrapped) int32 slot numbers. W power of two."""
    return jnp.bitwise_and(slots.astype(jnp.int32), jnp.int32(window - 1))


def window_slots(exec_slot, window: int):
    """Absolute slots covered by each group's window, in window order.

    ``exec_slot``: ``[..., G]`` -> ``[..., W, G]`` with plane j holding
    exec_slot + j (plane axis = second-to-last, per the module layout)."""
    ar = jnp.arange(window, dtype=jnp.int32)
    return exec_slot[..., None, :] + ar[:, None]


def in_window(slots, exec_slot, window: int):
    """True where ``slots`` (``[..., W, G]``) fall inside
    [exec_slot, exec_slot+W) for their group (wraparound-aware);
    ``exec_slot``: ``[..., G]``."""
    d = (slots - exec_slot[..., None, :]).astype(jnp.int32)
    return (d >= 0) & (d < window)


def leading_run(valid):
    """Number of leading True along the plane (second-to-last) axis per
    group: how many consecutive in-order entries are ready.
    ``valid``: bool ``[..., W, G]`` -> int32 ``[..., G]``."""
    return jnp.sum(jnp.cumprod(valid.astype(jnp.int32), axis=-2), axis=-2)


def gather_planes(arr, idx):
    """Gather along the plane (second-to-last) axis via one-hot selects.

    ``arr``: ``[..., Wp, G]``; ``idx``: ``[..., J, G]`` int32 in [0, Wp).
    Returns ``out[..., j, g] = arr[..., idx[..., j, g], g]``.

    PRECONDITION: every idx value must be in [0, Wp) — callers pass mod-W /
    clamped ring indices.  Out-of-range indices are UNDEFINED and the two
    implementations genuinely diverge there (the pallas kernel yields 0,
    this one-hot fallback yields plane 0's value); never rely on either.

    This is the TPU-friendly form of ``take_along_axis`` for ring windows:
    the G (lane) axis stays minor and fully parallel, and the Wp-way select
    unrolls into Wp fused ``where`` ops instead of a hardware gather along a
    non-lane axis.  Wp is the ring depth (small, e.g. 8).

    On TPU backends the select chain is executed by a pallas kernel that
    keeps the Wp-way work in VMEM (ops/pallas_gather.py) — the XLA
    formulation materializes the broadcast temporaries in HBM.  There the
    kernel is the only path: a shape it cannot serve (lanes not a multiple
    of 128, an idx rank it does not know) raises at trace time instead of
    dropping to the select chain.  This one-hot path is what other
    backends run, and the semantic reference.
    """
    from .pallas_gather import gather_planes_pallas, use_pallas_gather

    if use_pallas_gather():
        return gather_planes_pallas(arr, idx)
    wp = arr.shape[-2]
    res = None
    for w in range(wp):
        plane = arr[..., w : w + 1, :]  # [..., 1, G]
        # every idx value lies in [0, wp), so each position is overwritten
        # by its matching plane exactly once
        res = plane if res is None else jnp.where(idx == w, plane, res)
    target = jnp.broadcast_shapes(res.shape, idx.shape)
    return jnp.broadcast_to(res, target) if res.shape != target else res


def match_planes(vals, keys, idx):
    """Per-lane key-match select: ``out[..., j, g] = vals[..., e, g]`` for
    the entry ``e`` with ``keys[..., e, g] == idx[..., j, g]`` (0 when no
    entry matches; keys must be unique per lane among entries that can
    match).

    The generalization of :func:`gather_planes` from plane-number indices to
    arbitrary per-lane keys — used by the intake stage to place the
    rank-q taken request onto its ring plane without a sort (argsort over
    the request axis was measured at ~2/3 of the whole fused tick on TPU;
    sort lowers catastrophically there, and this E-way select keeps the
    lane axis fully parallel).
    """
    from .pallas_gather import match_planes_pallas, use_pallas_gather

    if use_pallas_gather():
        return match_planes_pallas(vals, keys, idx)
    e_planes = vals.shape[-2]
    res = jnp.zeros(vals.shape[:-2] + idx.shape[-2:], vals.dtype)
    for e in range(e_planes):
        res = jnp.where(
            keys[..., e : e + 1, :] == idx, vals[..., e : e + 1, :], res
        )
    return res


def clear_below(arr, slot_of_entry, watermark, fill):
    """Invalidate ring entries whose slot is below ``watermark``.

    ``arr``: payload ``[..., W, G]``; ``slot_of_entry``: the absolute slot each
    ring entry claims to hold ``[..., W, G]``; ``watermark``: ``[..., G]``.
    Entries with slot < watermark are replaced by ``fill``.
    """
    stale = (slot_of_entry - watermark[..., None, :]).astype(jnp.int32) < 0
    return jnp.where(stale, fill, arr)
