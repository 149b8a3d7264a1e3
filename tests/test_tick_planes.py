"""The one served tick (``ops.tick.paxos_tick_planes``) against the plain
reference: ``paxos_tick_impl`` per plane followed by ``_compact_outbox_impl``
or ``pack_outbox_impl``, composed by hand here.  Over every combination of
planes a manager can hold, seeded random traffic, a few ticks: the new
planes and the packs are equal bit for bit, tick by tick.  And the one
replay scan is K steps of the same entry."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.ops import tick as tk
from gigapaxos_tpu.paxos import state as st

R, W, P, G_LOG, G_REG = 3, 4, 2, 8, 4
TICKS = 10

_ref_tick = jax.jit(tk.paxos_tick_impl, static_argnames=(
    "own_row", "exec_budget", "lease_horizon", "wedge_ticks",
    "health_decay_shift", "health_topk"))
_ref_compact = jax.jit(tk._compact_outbox_impl, static_argnums=(1, 2))
_ref_pack = jax.jit(tk.pack_outbox_impl)
_ref_demand = jax.jit(
    lambda d, taken, decay: decay * d + jnp.sum(taken.astype(d.dtype),
                                                axis=(0, 1)),
    static_argnums=(2,))


def _plane(g: int, w: int):
    return st.create_groups(st.init_state(R, g, w),
                            np.arange(g, dtype=np.int32),
                            np.ones((g, R), bool))


def _planes(lease: bool, reg: bool, health: bool, demand: bool):
    return tk.TickPlanes(
        state=_plane(G_LOG, W),
        rstate=_plane(G_REG, 1) if reg else None,
        lease=tk.init_lease(G_LOG, 2) if lease else None,
        rlease=tk.init_lease(G_REG, 2) if lease and reg else None,
        health=tk.init_health(G_LOG) if health else None,
        rhealth=tk.init_health(G_REG) if health and reg else None,
        demand=jnp.zeros(G_LOG, jnp.float32) if demand else None)


def _traffic(seed: int, g_total: int, ticks: int = TICKS):
    """Seeded inboxes: about a third of the positions carry a fresh request
    id, and replica 0 is down for three ticks in the middle (an election,
    a lease fence and a stall for the folds to see)."""
    rng = np.random.default_rng(seed)
    rid = itertools.count(1)
    for t in range(ticks):
        hit = rng.random((R, P, g_total)) < 0.3
        req = np.zeros((R, P, g_total), np.int32)
        req[hit] = [next(rid) for _ in range(int(hit.sum()))]
        alive = np.ones(R, bool)
        alive[0] = not 3 <= t < 6
        yield tk.TickInbox(jnp.asarray(req),
                           jnp.zeros((R, P, g_total), jnp.bool_),
                           jnp.asarray(alive))


def _reference(planes: tk.TickPlanes, inbox: tk.TickInbox,
               p: tk.TickParams):
    """The composition the one entry replaces, plane by plane."""
    inboxes = [inbox]
    if planes.rstate is not None:
        inboxes = [tk.TickInbox(inbox.req[:, :, :G_LOG],
                                inbox.stop[:, :, :G_LOG], inbox.alive),
                   tk.TickInbox(inbox.req[:, :, G_LOG:],
                                inbox.stop[:, :, G_LOG:], inbox.alive)]
    new, packs, demand = [], [], planes.demand
    for ib, s, le, he in zip(inboxes,
                             (planes.state, planes.rstate),
                             (planes.lease, planes.rlease),
                             (planes.health, planes.rhealth)):
        res = _ref_tick(
            s, ib, own_row=p.own_row, exec_budget=p.exec_budget, lease=le,
            lease_horizon=p.lease_horizon, health=he,
            wedge_ticks=p.wedge_ticks,
            health_decay_shift=p.health_decay_shift,
            health_topk=min(p.health_topk, s.exec_slot.shape[1]))
        s, out, rest = res[0], res[1], list(res[2:])
        lp = hp = None
        if le is not None:
            le, lp = rest.pop(0), rest.pop(0)
        if he is not None:
            he, hp = rest
        if demand is not None and not new:  # the log plane's intake
            demand = _ref_demand(demand, out.intake_taken, p.demand_decay)
        new.append((s, le, he))
        packs.append((_ref_compact(out, p.exec_budget, p.lag_budget)
                      if p.compact else _ref_pack(out), lp, hp))
    new += [(None, None, None)] * (2 - len(new))
    packs += [(None, None, None)] * (2 - len(packs))
    (s_l, le_l, he_l), (s_r, le_r, he_r) = new
    (pk_l, lp_l, hp_l), (pk_r, lp_r, hp_r) = packs
    return (tk.TickPlanes(s_l, s_r, le_l, le_r, he_l, he_r, demand),
            tk.TickPacks(pk_l, pk_r, lp_l, lp_r, hp_l, hp_r))


def _assert_same(got, want, what: str) -> None:
    assert jax.tree.structure(got) == jax.tree.structure(want), what
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (what, path)
        assert np.array_equal(a, b), (what, jax.tree_util.keystr(path))


CASES = [(c, le, reg, he, False)
         for c in (True, False) for le in (False, True)
         for reg in (False, True) for he in (False, True)]
CASES.append((True, False, False, False, True))


def _case_id(case) -> str:
    compact, lease, reg, health, demand = case
    return "-".join(
        ["compact" if compact else "packed"]
        + [n for n, on in (("lease", lease), ("register", reg),
                           ("health", health), ("demand", demand)) if on])


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_the_one_entry_equals_the_per_plane_composition(case):
    compact, lease, reg, health, demand = case
    params = tk.TickParams(
        exec_budget=48 if compact else 0, lag_budget=16, compact=compact,
        lease_horizon=6, wedge_ticks=2, health_decay_shift=3, health_topk=6,
        demand_decay=0.75 if demand else 0.0)
    planes = _planes(lease, reg, health, demand)
    ref = _planes(lease, reg, health, demand)
    executed = 0
    for t, inbox in enumerate(_traffic(7, G_LOG + (G_REG if reg else 0))):
        ref, ref_packs = _reference(ref, inbox, params)
        planes, packs = tk.paxos_tick_planes(planes, inbox, params)
        _assert_same(packs, ref_packs, f"packs of tick {t}")
        _assert_same(planes, ref, f"planes after tick {t}")
        if compact:
            # the flat buffer and its head: one header
            executed += int(np.asarray(packs.out.flat)[0])
            assert int(np.asarray(packs.out.head)[0]) == int(
                np.asarray(packs.out.flat)[0])
    # absent planes stay absent, present ones come back
    assert [x is None for x in planes] == [
        False, not reg, not lease, not (lease and reg), not health,
        not (health and reg), not demand]
    assert [x is None for x in packs] == [
        False, not reg, not lease, not (lease and reg), not health,
        not (health and reg)]
    if compact:
        assert executed > 0, "the traffic decided nothing"
    if demand:
        assert float(np.asarray(planes.demand).sum()) > 0


def test_the_log_plane_only_entry_is_the_kept_names_program(monkeypatch):
    """``paxos_tick_compact`` (kept for the harness's cross-check) and the
    served entry over the log plane alone trace the same program: the same
    Pallas calls, the same equations, the same operands."""
    monkeypatch.setenv("GPTPU_PALLAS", "1")
    monkeypatch.setenv("GPTPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("GPTPU_NO_PALLAS", raising=False)
    g, E, Lb = 384, 200, 24  # shapes no other test traces: no cached jaxpr
    state = jax.eval_shape(lambda: st.init_state(R, g, W))
    inbox = jax.eval_shape(lambda: tk.make_inbox(R, g, P))
    kept = tk.paxos_tick_compact.trace(state, inbox, -1, E, Lb).jaxpr
    one = tk.paxos_tick_planes.trace(
        tk.TickPlanes(state), inbox,
        tk.TickParams(exec_budget=E, lag_budget=Lb, compact=True)).jaxpr
    n_kept, n_one = (str(j).count("pallas_call[") for j in (kept, one))
    assert n_kept == n_one > 0, (n_kept, n_one)
    assert len(kept.jaxpr.invars) == len(one.jaxpr.invars)
    assert len(kept.jaxpr.outvars) == len(one.jaxpr.outvars)

    def prims(closed):
        return [str(e.primitive) for e in closed.jaxpr.eqns]

    assert prims(kept) == prims(one)


@pytest.mark.parametrize("lease,reg", [(False, False), (True, True)],
                         ids=["log-plane", "lease-register"])
def test_the_replay_scan_is_k_steps_of_the_one_entry(lease, reg):
    """``replay_scan_ticks`` over a window of COO inboxes == the served
    entry stepped once per tick over the same inboxes made dense (its
    compact columns at ``scat_budget``; here that is ``exec_budget``)."""
    K, M, scat = 4, 64, 48
    g_total = G_LOG + (G_REG if reg else 0)
    params = tk.TickParams(exec_budget=scat, lag_budget=16, compact=True,
                           lease_horizon=6)
    inboxes = list(_traffic(11, g_total, K))
    cols = {k: np.zeros((K, M), np.int32) for k in ("e", "p", "rid")}
    cols["g"] = np.full((K, M), g_total, np.int32)  # padding: dropped
    cols["stop"] = np.zeros((K, M), bool)
    for k, ib in enumerate(inboxes):
        e, p, g = np.nonzero(np.asarray(ib.req))
        assert len(e) <= M
        cols["e"][k, :len(e)], cols["p"][k, :len(e)] = e, p
        cols["g"][k, :len(e)] = g
        cols["rid"][k, :len(e)] = np.asarray(ib.req)[e, p, g]
    xs = {k: jnp.asarray(v) for k, v in cols.items()}
    xs["alive"] = jnp.stack([ib.alive for ib in inboxes])

    start = _planes(lease, reg, False, False)
    planes, packs, lp_last, waits = tk.replay_scan_ticks(
        start, xs, P, params, scat)
    # not donated: the window can be run again from the pre-window planes
    assert not any(a.is_deleted() for a in jax.tree.leaves(start))
    step = _planes(lease, reg, False, False)
    for k, ib in enumerate(inboxes):
        step, pk = tk.paxos_tick_planes(step, ib, params)
        row = pk.out.flat if pk.rout is None else jnp.concatenate(
            [pk.out.flat, pk.rout.flat])
        assert np.array_equal(np.asarray(packs[k]), np.asarray(row)), k
        if lease:
            lps = [lp for lp in (pk.lease_pack, pk.rlease_pack)
                   if lp is not None]
            assert int(waits[k]) == sum(
                int(np.asarray(lp)[tk.LP_WAIT].sum()) for lp in lps)
    _assert_same(planes, step, "planes after the window")
    if lease:
        _assert_same(lp_last, (pk.lease_pack, pk.rlease_pack),
                     "the final tick's lease packs")
    else:
        assert lp_last == (None, None) and waits is None
