"""Benchmark: sustained decisions/sec/chip on the dense consensus engine.

Reproduces the reference's capacity-probe methodology
(``TESTPaxosConfig.java:190-229``: drive load, measure sustained decision
throughput) at the BASELINE.json north-star configuration: 1M concurrent
3-replica Paxos groups on one chip, one request per group per tick.

Load generation runs on-device (the analog of the in-JVM TESTPaxosClient) so
the measurement is the consensus engine, not host Python.  Prints ONE JSON
line: {"metric", "value", "unit", "vs_baseline"}.

One process, on the chip: ``python bench.py`` is :func:`run_bench` here and
exits non-zero, printing no number, when JAX's default backend is not a TPU.
There is no CPU fallback.  ``GPTPU_BENCH_PLATFORM`` names another platform
outright (e.g. ``cpu`` for a rehearsal); the metric name then carries it.

Env knobs: GPTPU_BENCH_GROUPS (default 1<<20), GPTPU_BENCH_TICKS (default 30),
GPTPU_BENCH_REPLICAS (3), GPTPU_BENCH_WINDOW (8), GPTPU_BENCH_PLATFORM,
GPTPU_BENCH_APP=device_kv (fuse the device-resident KV app behind the tick —
decisions execute on-device, models/device_kv.py), GPTPU_BENCH_LAT_TICKS
(default 15; 0 disables the closed-loop commit-latency phase),
GPTPU_BENCH_PHASES (default 1; 0 disables the per-phase tick profile).
"""

import json
import os
import time

import numpy as np

BASELINE_DECISIONS_PER_SEC = 100_000.0  # north star: >=100k dec/s/chip


def _profile_phases(R, G, W, P, reps=8, exec_budget=4096, lag_budget=1024):
    """Per-phase wall-time buckets for the LOADED tick (VERDICT r5 item 10).

    XLA exposes no intra-program phase timers, so each bucket is measured
    as a separately-jitted CUMULATIVE PREFIX of the tick body: returning
    only ``intake_taken`` dead-code-eliminates everything past the intake
    scatter (phases 0-2a), adding ``decided_now`` extends through accept +
    tally (2b-2c), and the full (state, outbox) program is the whole tick.
    A bucket is the delta between consecutive prefixes; ``outbox_pack`` is
    the compact scatter as its own dispatch on a materialized outbox, and
    ``control_summary_readback`` is the host's entire per-tick device
    contact (compact buffer transfer + unpack, sweep-frontier dispatch +
    O(rows) gather).  Fusion overlaps phase boundaries, so buckets need
    not sum exactly to the fused ms/tick — they bound where the time
    goes, not a cycle-exact attribution.  Profiles the plain consensus
    tick regardless of GPTPU_BENCH_APP."""
    import jax
    import jax.numpy as jnp

    from gigapaxos_tpu.ops.tick import (TickInbox, _compact_outbox_impl,
                                        frontier_rows, paxos_tick_impl,
                                        sweep_frontier, unpack_compact)
    from gigapaxos_tpu.paxos import state as st

    state = st.init_state(R, G, W)
    state = st.create_groups(
        state, np.arange(G, dtype=np.int32), np.ones((G, R), bool)
    )
    g = jnp.arange(G, dtype=jnp.int32)
    req = jnp.zeros((R, P, G), jnp.int32).at[:, 0, :].set(
        jnp.where(g[None, :] % R == jnp.arange(R)[:, None], 1 + g[None, :], 0)
    )
    inbox = TickInbox(req, jnp.zeros((R, P, G), jnp.bool_),
                      jnp.ones((R,), jnp.bool_))

    def timed(fn, *args):
        out = fn(*args)  # compile + warm
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / reps, out

    p_intake = jax.jit(lambda s, ib: paxos_tick_impl(s, ib)[1].intake_taken)

    def _thru_tally(s, ib):
        o = paxos_tick_impl(s, ib)[1]
        return o.intake_taken, o.decided_now

    p_tally = jax.jit(_thru_tally)
    p_full = jax.jit(paxos_tick_impl)
    t_intake, _ = timed(p_intake, state, inbox)
    t_tally, _ = timed(p_tally, state, inbox)
    t_full, (post, out) = timed(p_full, state, inbox)

    p_pack = jax.jit(
        lambda o: _compact_outbox_impl(o, exec_budget, lag_budget).flat
    )
    t_pack, packed = timed(p_pack, out)

    rows = jnp.arange(16, dtype=jnp.int32)  # typical live outstanding rows
    fr = sweep_frontier(post.exec_slot, post.member, inbox.alive)
    jax.block_until_ready(frontier_rows(*fr, rows))  # warm both programs
    t0 = time.perf_counter()
    for _ in range(reps):
        unpack_compact(packed, R, G, exec_budget, lag_budget)
        fr = sweep_frontier(post.exec_slot, post.member, inbox.alive)
        for a in frontier_rows(*fr, rows):
            np.asarray(a)
    t_read = 1e3 * (time.perf_counter() - t0) / reps

    return {
        "intake_scatter": round(t_intake, 3),
        "tally": round(max(t_tally - t_intake, 0.0), 3),
        "exec_extract": round(max(t_full - t_tally, 0.0), 3),
        "outbox_pack": round(t_pack, 3),
        "control_summary_readback": round(t_read, 3),
        "full_tick": round(t_full, 3),
        "reps": reps,
        "method": ("cumulative-prefix jits (DCE) + separate pack/readback "
                   "dispatches; fusion overlap means buckets need not sum "
                   "to ms_per_tick"),
    }


def run_bench() -> dict:
    import jax

    from gigapaxos_tpu import compile_cache

    compile_cache.configure()
    platform = os.environ.get("GPTPU_BENCH_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
    elif jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py: JAX's default backend is {jax.default_backend()!r}, "
            f"not a TPU, and there is no fallback; name a platform with "
            f"GPTPU_BENCH_PLATFORM to run elsewhere on purpose")

    import jax.numpy as jnp

    from gigapaxos_tpu.ops.tick import TickInbox, paxos_tick_impl
    from gigapaxos_tpu.paxos import state as st

    R = int(os.environ.get("GPTPU_BENCH_REPLICAS", 3))
    G = int(os.environ.get("GPTPU_BENCH_GROUPS", 1 << 20))
    W = int(os.environ.get("GPTPU_BENCH_WINDOW", 8))
    # production inbox shape (paxos.proposals_per_tick default); the load
    # generator still issues one request per group per tick
    P = int(os.environ.get("GPTPU_BENCH_P", 4))
    n_ticks = int(os.environ.get("GPTPU_BENCH_TICKS", 30))

    state = st.init_state(R, G, W)
    state = st.create_groups(
        state, np.arange(G, dtype=np.int32), np.ones((G, R), bool)
    )

    device_app = os.environ.get("GPTPU_BENCH_APP") == "device_kv"

    def make_inbox(rid_base):
        # on-device load generator: every group gets one fresh request id per
        # tick at entry replica (g % R)
        g = jnp.arange(G, dtype=jnp.int32)
        rids = rid_base + g
        req = jnp.zeros((R, P, G), jnp.int32)
        req = req.at[:, 0, :].set(
            jnp.where(g[None, :] % R == jnp.arange(R)[:, None], rids[None, :], 0)
        )
        return TickInbox(
            req, jnp.zeros((R, P, G), jnp.bool_), jnp.ones((R,), jnp.bool_)
        ), rids

    # Measurement loop: dispatch all n_ticks back-to-back and block once at
    # the end — jax's async dispatch queues them so the device crunches
    # steady-state (the in-JVM TESTPaxosClient open-loop analog).  A fully
    # on-device lax.scan variant exists behind GPTPU_BENCH_SCAN=1.
    from jax import lax

    use_scan = bool(os.environ.get("GPTPU_BENCH_SCAN"))

    # ONE per-tick body shared by both drivers (eager dispatch queue and
    # on-device lax.scan) so the two paths cannot measure different
    # workloads.  carry is a tuple: (state, acc) or (state, kv, acc).
    if device_app:
        from gigapaxos_tpu.models.device_kv import (OP_PUT, fused_step,
                                                    init_kv,
                                                    register_requests)

        slots = 8
        table = 1 << max(16, (4 * G - 1).bit_length())
        kv0 = init_kv(R, G, slots=slots, table=table)
        carry0 = (state, kv0, jnp.int32(0))

        def tick_once(carry, rid_base):
            state, kv, acc = carry
            inbox, rids = make_inbox(rid_base)
            g = jnp.arange(G, dtype=jnp.int32)
            # synthetic KV workload (the TESTPaxosApp state-update analog):
            # PUT key (g & slots-1) = rid, descriptors registered on-device
            kv = register_requests(
                kv, rids, jnp.full(G, OP_PUT, jnp.int32),
                jnp.bitwise_and(g, slots - 1) + 1, rids,
            )
            state, kv, out, _resp, _miss = fused_step(state, kv, inbox)
            return (state, kv, acc + jnp.sum(out.decided_now))
    else:
        carry0 = (state, jnp.int32(0))

        def tick_once(carry, rid_base):
            state, acc = carry
            inbox, _rids = make_inbox(rid_base)
            new_state, out = paxos_tick_impl(state, inbox)
            return (new_state, acc + jnp.sum(out.decided_now))

    if use_scan:
        def run_n(carry, base):
            def body(carry, i):
                return tick_once(carry, base + i * G), None

            carry, _ = lax.scan(
                body, carry, jnp.arange(n_ticks, dtype=jnp.int32)
            )
            return carry

        run_j = jax.jit(run_n, donate_argnums=(0,))
        carry = run_j(carry0, jnp.int32(1))  # compile + warm
        jax.block_until_ready(carry[-1])
        carry = carry[:-1] + (jnp.int32(0),)  # reset acc: count timed only
        t0 = time.perf_counter()
        carry = run_j(carry, jnp.int32(1 + n_ticks * G))
        total_decisions = int(carry[-1])  # blocks until the scan completes
        dt = time.perf_counter() - t0
    else:
        step_j = jax.jit(tick_once, donate_argnums=(0,))
        carry = step_j(carry0, jnp.int32(1))  # compile + warm
        jax.block_until_ready(carry[-1])
        carry = carry[:-1] + (jnp.int32(0),)
        t0 = time.perf_counter()
        for i in range(n_ticks):
            carry = step_j(carry, jnp.int32(1 + (i + 1) * G))
        total_decisions = int(carry[-1])  # blocks on the queued ticks
        dt = time.perf_counter() - t0

    dps = total_decisions / dt

    # Closed-loop commit-latency phase: the throughput loop above queues
    # ticks open-loop, so its wall time says nothing about how long ONE
    # wave takes from request entry to decision visible on the host.  Here
    # each tick blocks before the next is dispatched — entry-to-commit
    # latency of a full wave, the per-request commit latency at 1 req/group
    # (the TESTPaxosClient RTT column's kernel-path analog).
    lat_ticks = int(os.environ.get("GPTPU_BENCH_LAT_TICKS", 15))
    lat_p50 = lat_p99 = None
    if lat_ticks > 0:
        if use_scan:  # the scan path never built the single-tick program
            step_j = jax.jit(tick_once, donate_argnums=(0,))
        base0 = 1 + 2 * (n_ticks + 1) * G  # past every rid the loops used
        carry = step_j(carry, jnp.int32(base0))  # (re)compile + warm
        jax.block_until_ready(carry[-1])
        lats = []
        for i in range(lat_ticks):
            t0 = time.perf_counter()
            carry = step_j(carry, jnp.int32(base0 + (i + 1) * G))
            jax.block_until_ready(carry[-1])
            lats.append(time.perf_counter() - t0)
        lat_p50 = float(np.percentile(lats, 50)) * 1e3
        lat_p99 = float(np.percentile(lats, 99)) * 1e3

    backend = jax.devices()[0].platform
    suffix = f"_{backend}" if backend != "tpu" else ""
    app_tag = "_device_kv" if device_app else ""
    result = {
        "metric": (f"decisions_per_sec_per_chip_{G}_groups_{R}_replicas"
                   f"{app_tag}{suffix}"),
        "value": round(dps, 1),
        "unit": "decisions/s",
        "vs_baseline": round(dps / BASELINE_DECISIONS_PER_SEC, 2),
        # dec/s = decisions_per_tick / ms_per_tick: published rounds have
        # quoted all three inconsistently (PARITY.md reconciliation column),
        # so every run now emits the factors next to the headline rate.
        "decisions_per_tick": round(total_decisions / max(n_ticks, 1), 2),
        "ms_per_tick": round(1e3 * dt / max(n_ticks, 1), 3),
        # self-describing run shape (ISSUE 16): slot-ring depth and the
        # log/register group split this probe ran with
        "detail": {"window": W, "mode_mix": {"log": G, "register": 0}},
    }
    if lat_p50 is not None:
        result["commit_latency_ms"] = {
            "p50": round(lat_p50, 3), "p99": round(lat_p99, 3),
            "closed_loop_ticks": lat_ticks,
        }
    if os.environ.get("GPTPU_BENCH_PHASES", "1") != "0":
        result["phase_ms"] = _profile_phases(R, G, W, P)
    return result


if __name__ == "__main__":
    print(json.dumps(run_bench()))
