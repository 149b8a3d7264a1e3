"""The block-sparse outbox compaction against a plain numpy reference of
``CompactLayout`` (ISSUE 27): both branches of ``_compact_columns``, the K
boundary, the budgets biting, on one device and GSPMD-partitioned; and the
donor-status select against ``take_along_axis``.

The plane (R=3, W=4, G=8,192) is wide enough for both branches once the
module's K is lowered to 64 for the test; the served path's K makes every
test-sized plane dense from its shape alone.  Each case also holds the head
of the buffer (ISSUE 34) to it: the same host outbox while ``n_exec <= K``,
refused by its own header above.
"""

import contextlib
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.ops import tick as tk

R, W, G, P = 3, 4, 8192, 4
E, LB = 4096, 32
K = 64
N = R * W * G


def reference_buffer(out: dict) -> np.ndarray:
    """``CompactLayout`` by hand: header | taken_bits | four exec columns |
    six laggard columns, hits in flat (r, j, g) order, zero-filled."""
    j = np.arange(W)[None, :, None]
    mask = (j < out["exec_count"][:, None, :]).reshape(-1)
    hits = np.flatnonzero(mask)
    r, jj, g = np.unravel_index(hits[:E], (R, W, G))
    cols = np.zeros((4, E), np.int32)
    cols[0, :len(r)] = out["exec_req"][r, jj, g]
    cols[1, :len(r)] = r | (out["exec_stop"][r, jj, g].astype(np.int32) << 8)
    cols[2, :len(r)] = out["exec_base"][r, g] + jj
    cols[3, :len(r)] = g
    lhits = np.flatnonzero((out["lag"] >= W).reshape(-1))
    lr, lg = np.unravel_index(lhits[:LB], (R, G))
    lcols = np.zeros((6, LB), np.int32)
    for i, v in enumerate((lr, lg, out["donor"][lr, lg],
                           out["donor_exec"][lr, lg],
                           out["donor_status"][lr, lg],
                           (out["exec_base"] + out["exec_count"])[lr, lg])):
        lcols[i, :len(lr)] = v
    taken = np.zeros((R, G), np.int32)
    for p in range(P):
        taken |= out["intake_taken"][:, p, :].astype(np.int32) << p
    header = np.array([len(hits), out["decided_now"].sum(), len(lhits)],
                      np.int32)
    return np.concatenate([header, taken.reshape(-1), cols.reshape(-1),
                           lcols.reshape(-1)])


def exec_counts(rng, kind) -> np.ndarray:
    """[R, G] exec_count whose mask has the case's hits."""
    cnt = np.zeros((R, G), np.int32)
    flat = cnt.reshape(-1)
    if kind == "every":
        cnt[:] = W
    elif kind == "budget":  # exactly E lanes: the budget may have bitten
        flat[rng.choice(R * G, E // W, replace=False)] = W
    elif kind == "one_block":  # 40 hits inside one 128-lane block
        cnt[1, 256:296] = 1
    elif kind == "one_per_block":  # j = 0 of every 128th group: K blocks
        cnt[0, :K * 128:128] = 1
    elif isinstance(kind, tuple):  # (layout, n): n hits in one block, or
        layout, n = kind           # one a block (flat[i] is lane j = 0)
        if layout == "one_block":
            cnt[1, 256:256 + n] = 1
        else:
            flat[:n * 128:128] = 1
    else:  # that many single hits (count 1 -> lane j = 0)
        flat[rng.choice(R * G, kind, replace=False)] = 1
    return cnt


def random_outbox(seed: int, hits, laggards) -> dict:
    """``laggards``: that many at random, or ``("one_per_block", n)``."""
    rng = np.random.default_rng(seed)
    lag = rng.integers(0, W, (R, G)).astype(np.int32)  # none reaches W
    lag.reshape(-1)[np.arange(laggards[1]) * 128
                    if isinstance(laggards, tuple)
                    else rng.choice(R * G, laggards, replace=False)] = W + 3
    i32 = lambda hi, shape: rng.integers(-1, hi, shape).astype(np.int32)
    return dict(
        exec_req=i32(1 << 30, (R, W, G)),
        exec_stop=rng.random((R, W, G)) < 0.1,
        exec_base=i32(1 << 20, (R, G)),
        exec_count=exec_counts(rng, hits),
        intake_taken=rng.random((R, P, G)) < 0.01,
        coord_id=i32(R, (G,)),
        decided_now=i32(3, (G,)) + 1,
        lag=lag,
        donor=i32(R, (R, G)),
        donor_exec=i32(1 << 20, (R, G)),
        donor_status=i32(5, (R, G)),
    )


def small_k():
    """The module's K lowered to 64 while a program is traced, so that this
    plane has both branches (exec: K = 64; laggards: K = min(64, LB))."""
    return mock.patch.object(tk, "_SPARSE_BLOCKS", K)


def device_outbox(out: dict, put=lambda field, v: jnp.asarray(v)):
    return tk.TickOutbox(**{k: put(k, v) for k, v in out.items()})


@pytest.fixture(scope="module")
def compact():
    fn = jax.jit(functools.partial(tk._compact_outbox_impl, exec_budget=E,
                                   lag_budget=LB))

    def run(outbox: tk.TickOutbox) -> tuple:
        """(flat buffer, head) of one compaction, as numpy."""
        with small_k():
            assert tk.compact_blocks(N, E) == K
            assert tk.compact_blocks(R * G, LB) == LB
            return tuple(np.asarray(a) for a in fn(outbox))

    return run


CASES = [
    # (exec hits, laggards); the branch each list takes at K = 64 / 32
    (0, 0), (1, 0), (K - 1, 0), (K, 0), (K + 1, 0),  # sparse ... dense
    ("every", 0), ("budget", 0),                     # dense, past E / at E
    ("one_block", 0), ("one_per_block", 0),          # sparse: 1 and K blocks
    (5, 1), (5, LB), (5, LB + 5),                    # lag sparse, sparse, dense
    (K + 9, LB - 1), ("every", R * G),               # dense beside sparse; all
]


@pytest.mark.parametrize("case", range(len(CASES)),
                         ids=[f"exec-{h}-lag-{l}" for h, l in CASES])
def test_packed_buffer_equals_the_reference_word_for_word(compact, case):
    hits, laggards = CASES[case]
    out = random_outbox(case, hits, laggards)
    got, head = compact(device_outbox(out))
    with small_k():
        assert_reference_buffer(out, got, head)


def expected_path(ladder: tuple, count: int) -> str:
    """The tier the rule names for ``count``: the narrowest that holds it."""
    return next((f"sparse{k}" for k in ladder if count <= k), "dense")


def assert_reference_buffer(out: dict, got, head) -> None:
    """``got`` and ``head`` (one compaction of ``out``, traced under the
    module constants in force) hold ``reference_buffer(out)`` word for word;
    the host mirrors the branch from the header alone."""
    want = reference_buffer(out)
    assert got.shape == want.shape == (
        tk.CompactLayout(R, G, E, LB).total_plain,)
    bad = np.flatnonzero(got != want)
    assert bad.size == 0, (bad[:8], got[bad[:8]], want[bad[:8]])
    n_exec, lag_n = int(got[0]), int(got[2])
    for n, cap, count in ((N, E, n_exec), (R * G, LB, lag_n)):
        assert tk.compact_path(n, cap, count) == expected_path(
            tk.compact_tiers(n, cap), count)
    Kh = tk.CompactLayout(R, G, E, LB, P).head_exec
    assert head.shape == (tk.CompactLayout(R, G, E, LB, P).total_head,)
    # the head holds what the sparse code can fill: the same host outbox
    # from a seventh of the words, or None and the flat buffer is pulled
    co = tk.unpack_compact(got, R, G, E, LB)
    co_h = tk.unpack_head(head, R, G, P, E, LB)
    assert (co_h is None) == (n_exec > Kh)
    if co_h is not None:
        for f, a, b in zip(co._fields, co_h, co):
            if f == "taken_bits":
                a, b = tk.taken_dense(co_h, G), tk.taken_dense(co, G)
            assert np.array_equal(a, b) if f != "taken_shift" else (
                (a, b) == (P, 0)), f


#: the ladder lowered for this plane: exec tiers (8, 64), laggard (8, 32)
LADDER = (8,)


@contextlib.contextmanager
def small_ladder():
    """``small_k()`` with a narrower tier below it, while a program is
    traced: every list of this plane has two sparse widths and the dense
    code."""
    with mock.patch.object(tk, "_SPARSE_TIERS", LADDER), small_k():
        assert tk.compact_tiers(N, E) == (8, K)
        assert tk.compact_tiers(R * G, LB) == (8, LB)
        yield


@pytest.fixture(scope="module")
def ladder_compact():
    """(flat, head) of one compaction under the lowered ladder, on one
    device or GSPMD-partitioned over four virtual devices."""
    from jax.sharding import NamedSharding

    from gigapaxos_tpu.parallel import mesh as pmesh
    from gigapaxos_tpu.parallel.shard_tick import _OUTBOX_SPECS

    fns = {}

    def run(out: dict, where: str) -> tuple:
        put = lambda k, v: jnp.asarray(v)
        if where == "mesh":
            if len(jax.devices()) < 4:
                pytest.skip("needs 4 virtual devices")
            mesh = pmesh.make_mesh(jax.devices()[:4], replica_shards=1)
            put = lambda k, v: jax.device_put(
                v, NamedSharding(mesh, _OUTBOX_SPECS[k]))
        fn = fns.setdefault(where, jax.jit(functools.partial(
            tk._compact_outbox_impl, exec_budget=E, lag_budget=LB)))
        with small_ladder():
            return tuple(np.asarray(a) for a in fn(device_outbox(out, put)))

    return run


#: (list, tier, count - tier, where the hits lie) at every tier boundary
BOUNDARIES = [(lst, k, d, lay)
              for lst, tiers in (("exec", (8, K)), ("lag", (8, LB)))
              for k in tiers for d in (-1, 0, 1)
              for lay in (("one_per_block", "one_block") if lst == "exec"
                          else ("one_per_block",))]


@pytest.mark.parametrize("where", ["one_device", "mesh"])
@pytest.mark.parametrize("lst,tier,delta,layout", BOUNDARIES, ids=[
    f"{lst}-{k}{d:+d}-{lay}" for lst, k, d, lay in BOUNDARIES])
def test_every_tier_boundary_equals_the_reference_word_for_word(
        ladder_compact, lst, tier, delta, layout, where):
    """A count one under, at and one over each width of the ladder: the
    flat buffer and the head are the reference's whichever width ran.  A
    width too narrow for the count would drop its last hit (one hit a block
    fills one row of the tile a tier has no row for; hits in one block rank
    past the tier's slots), so equality at ``tier + 1`` is the device
    having gone wider."""
    count = tier + delta
    exec_hits, laggards = ((layout, count), 0) if lst == "exec" else (
        3, (layout, count))
    out = random_outbox(3 * tier + delta, exec_hits, laggards)
    got, head = ladder_compact(out, where)
    assert int(got[0 if lst == "exec" else 2]) == count
    n, cap = (N, E) if lst == "exec" else (R * G, LB)
    with small_ladder():
        assert_reference_buffer(out, got, head)
        ladder = tk.compact_tiers(n, cap)
        up = ladder.index(tier) + (delta > 0)
        assert tk.compact_path(n, cap, count) == (
            f"sparse{ladder[up]}" if up < len(ladder) else "dense")


def test_served_path_k_leaves_test_sized_planes_dense():
    # every plane the tier-1 tests build: decided from the shape, no cond
    assert tk.compact_blocks(3 * 4 * 4096, 8192) == 0
    assert tk.compact_blocks(3 * 4096, 1024) == 0
    assert tk.compact_tiers(3 * 4 * 4096, 8192) == ()
    assert tk.compact_tiers(3 * 4096, 1024) == ()
    assert tk.compact_path(3 * 4 * 4096, 8192, 0) == "dense"
    # the benchmark's planes: both lists have the sparse ladder, and a
    # served tick's few hundred executions take its narrow end
    for g in (1 << 17, 1 << 20):
        assert tk.compact_blocks(3 * 4 * g, 2 * g) == tk._SPARSE_BLOCKS
        assert tk.compact_blocks(3 * g, 1024) == 1024
        assert tk.compact_tiers(3 * 4 * g, 2 * g) == (
            *tk._SPARSE_TIERS, tk._SPARSE_BLOCKS)
        assert tk.compact_tiers(3 * g, 1024) == tuple(
            t for t in tk._SPARSE_TIERS if t < 1024) + (1024,)
        for count in (0, 128, 129, 400, 1024, 1025, 2310, 8192):
            assert tk.compact_path(3 * 4 * g, 2 * g, count) == (
                expected_path(tk.compact_tiers(3 * 4 * g, 2 * g), count))
        assert tk.compact_path(3 * 4 * g, 2 * g, 2310) == "sparse8192"
        assert tk.compact_path(3 * 4 * g, 2 * g, 3 * 65536) == "dense"
        assert tk.compact_path(3 * g, 1024, 0) == (
            f"sparse{tk.compact_tiers(3 * g, 1024)[0]}")


@pytest.mark.parametrize("tail", [0, 1, 127])
def test_a_width_that_is_no_multiple_of_the_block_pads_its_tail(tail):
    n = 40 * 128 + tail
    rng = np.random.default_rng(tail)
    mask = np.zeros(n, bool)
    mask[rng.choice(n, 7, replace=False)] = True
    mask[-1] = True  # a hit in the padded block
    col = rng.integers(1, 1 << 30, n).astype(np.int32)
    with mock.patch.object(tk, "_SPARSE_BLOCKS", 8):
        assert tk.compact_blocks(n, 16) == 8
        count, got = jax.jit(lambda m, c: tk._compact_columns(m, [c], 16))(
            jnp.asarray(mask), jnp.asarray(col))
    want = np.zeros(16, np.int32)
    want[:8] = col[mask]
    assert int(count) == 8 and np.array_equal(np.asarray(got)[0], want)


@pytest.mark.parametrize("replica_shards", [1, 2])
def test_partitioned_compaction_equals_the_one_device_buffer(replica_shards):
    """The mesh runs the same helper as a GSPMD-partitioned dispatch over
    the sharded outbox (``parallel/shard_tick.py``)."""
    from jax.sharding import NamedSharding

    from gigapaxos_tpu.parallel import mesh as pmesh
    from gigapaxos_tpu.parallel.shard_tick import _OUTBOX_SPECS

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = pmesh.make_mesh(jax.devices()[:4], replica_shards=replica_shards)
    fn = jax.jit(functools.partial(tk._compact_outbox_impl, exec_budget=E,
                                   lag_budget=LB))
    for hits, laggards in ((K - 1, 3), (K + 1, LB + 5)):
        out = random_outbox(11, hits, laggards)
        if replica_shards == 2:  # R = 3 does not divide: pad a replica
            out = {k: (np.concatenate([v, np.zeros_like(v[:1])])
                       if v.ndim > 1 else v) for k, v in out.items()}
        with small_k():
            want = fn(device_outbox(out))
            got = fn(device_outbox(out, lambda k, v: jax.device_put(
                v, NamedSharding(mesh, _OUTBOX_SPECS[k]))))
        assert int(want.flat[0]) == hits and int(want.flat[2]) == laggards
        # the second output did not move the partitioner (shard_tick.py:
        # an operand added to this jit once multiplied the header's counts)
        assert np.array_equal(got.flat, want.flat)
        assert np.array_equal(got.head, want.head)
        assert list(want.head[:3]) == list(want.flat[:3])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_donor_status_select_equals_take_along_axis(seed):
    rng = np.random.default_rng(seed)
    r = 3 + seed  # R = 3, 4, 5
    status = rng.integers(0, 6, (r, 512)).astype(np.int32)
    d_id = rng.integers(-1, r, (r, 512)).astype(np.int32)  # -1: no donor
    want = np.take_along_axis(status, np.clip(d_id, 0, r - 1), axis=0)
    got = jax.jit(tk._select_rows)(jnp.asarray(status), jnp.asarray(d_id))
    assert got.dtype == jnp.int32 and np.array_equal(np.asarray(got), want)
