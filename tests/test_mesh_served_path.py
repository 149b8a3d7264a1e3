"""The served path with the group axis sharded over four devices, against the
plain reference (ISSUE 30: the ``modea-3r-1m-mesh4`` configuration).

One seeded schedule of PUT / GET / DEL goes through ``InProcessCluster`` and
the client twice: on the four-device mapping the benchmark's four-chip cell
runs (``chipbench/configs/rehearsal-3r-4k-mesh4.json``: the cell's
configuration at 4,096 groups) and on the one-device mapping
(``rehearsal-3r-4k.json``), both built the way ``chipbench/run.py`` builds a
cell (``deployment.make_config`` / ``build_cluster`` / ``populate``), with
the Pallas kernels interpreted.  Every reply and every replica's table is
held to ``chipbench/references/kv_register.py`` (``RefKV``, ``check_run``:
what decides a run's ``correct`` on the chip); the mapping may change no answer; a write
dropped on one replica fails the check; the journal written under the mesh
replays to the same tables after a restart; and the mesh's ticks count their
dispatches.  Both configurations set ``pipeline_ticks``: every tick whose
inbox left nothing behind completes its outbox in the call that dispatched
it (ISSUE 31), on both mappings.
"""

import copy
import dataclasses
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import deployment, load, spec  # noqa: E402
from chipbench.references import Op, kv_register  # noqa: E402
from gigapaxos_tpu.obs.metrics import registry  # noqa: E402

CONFIGS = {"mesh4": "chipbench/configs/rehearsal-3r-4k-mesh4.json",
           "one": "chipbench/configs/rehearsal-3r-4k.json"}
KEY = "k"
SEED = 3000003007
#: concurrent PUTs over this many names (drawn with replacement: some names
#: are written twice while the first write is in flight)
N_PUTS, PUT_NAMES = 400, 256
#: rounds of one PUT / GET / DEL per name, a round at a time, over these names
ROUNDS, MIXED_NAMES = 6, 48
DEADLINE_S = 60.0


def schedule(seed: int, names: list) -> tuple:
    """(concurrent PUTs [(name, payload, entry)], rounds of mixed ops
    [[(name, payload, entry)]]): the same for every mapping."""
    rng = np.random.default_rng([seed, 30])
    puts = [(names[int(rng.integers(PUT_NAMES))], f"PUT {KEY} a{i}".encode(),
             int(rng.integers(3))) for i in range(N_PUTS)]
    mixed = names[1000:1000 + MIXED_NAMES]
    rounds = []
    for r in range(ROUNDS):
        ops = []
        for j, name in enumerate(mixed):
            op = ("PUT", "GET", "DEL")[int(rng.integers(3))] if r else "PUT"
            body = f"PUT {KEY} b{r}.{j}" if op == "PUT" else f"{op} {KEY}"
            ops.append((name, body.encode(), int(rng.integers(3))))
        rounds.append(ops)
    return puts, rounds


def offer(client, actives: list, ops: list) -> list:
    """Send ``ops`` all at once; [(sent, done, packet)] in their order."""
    got = [None] * len(ops)
    done = threading.Semaphore(0)

    def on_reply(i, sent, p):
        got[i] = (sent, time.monotonic(), p)
        done.release()

    for i, (name, payload, entry) in enumerate(ops):
        client.send_request(
            name, payload,
            lambda p, i=i, sent=time.monotonic(): on_reply(i, sent, p),
            active=actives[entry])
    for _ in ops:
        assert done.acquire(timeout=DEADLINE_S), "a request got no reply"
    return got


def _by_label(snap: dict, family: str, plane: str, label: str) -> dict:
    """{value of ``label``: count} over a counter family's series of a plane."""
    out = {}
    for key, val in snap.items():
        if key.startswith(family + "{") and f"plane={plane}" in key:
            out[key.partition(label + "=")[2].rstrip("}").split(",")[0]] = val
    return out


@dataclasses.dataclass
class Served:
    """What one mapping's run left behind, as plain data."""

    devices: int                 # devices the data plane's state lies on
    rc_devices: int              # ... and the control plane's
    replies: list                # (name, request, reply) in the order offered
    writes: dict                 # name -> [Op] of the PUT phase, replies in
    tables: dict                 # name -> [one dict per replica], at the end
    readback: dict               # name -> value a GET through the client gave
    problems: list               # check_run on the live cluster
    problems_dropped_write: list  # ... with one write dropped on one replica
    ticks: dict                  # plane -> ticks dispatched while counted
    dispatches: dict             # plane -> {program: count} over those ticks
    completions: dict            # plane -> {mode: count} over those ticks
    restarted_tables: dict       # name -> [one dict per replica], replayed
    restarted_get: dict          # name -> value a GET gave after the restart
    replayed_tick: int           # the data plane's tick number after replay


def _plane_counts(cluster) -> tuple:
    ticks, counts, modes = {}, {}, {}
    for plane, m in (("ar", cluster.manager), ("rc", cluster.rc_manager)):
        with m.lock:  # a tick counts its dispatches under this lock
            ticks[plane] = m.tick_num
            snap = registry().snapshot()
            counts[plane] = _by_label(snap, "mesh_dispatches_total", plane,
                                      "program")
            modes[plane] = _by_label(snap, "tick_completions_total", plane,
                                     "mode")
    return ticks, counts, modes


def _rose(after: dict, before: dict) -> dict:
    return {p: {k: n - before[p].get(k, 0) for k, n in after[p].items()}
            for p in after}


def serve(mapping: str, run_dir: str) -> Served:
    from gigapaxos_tpu.client import ReconfigurableAppClient
    from gigapaxos_tpu.reconfiguration import packets as pkt

    config = spec.load_config(CONFIGS[mapping])
    cfg = deployment.make_config(config)
    cluster = deployment.build_cluster(config, cfg, run_dir, 600.0)
    client = None
    try:
        m = cluster.manager
        ticks0, counts0, modes0 = _plane_counts(cluster)
        names = deployment.populate(cluster, int(config["populate_groups"]))
        actives = list(cfg.nodes.active_ids())
        client = ReconfigurableAppClient(cfg.nodes)
        puts, rounds = schedule(SEED, names)

        replies, writes = [], {}
        for (name, payload, _), (sent, done, p) in zip(
                puts, offer(client, actives, puts)):
            assert p.get("ok"), p
            replies.append((name, payload, pkt.b64d(p["response"]) or b""))
            writes.setdefault(name, []).append(Op(
                "update", KEY, payload.decode().split(" ", 2)[2], sent, done,
                "ok", replies[-1][2]))
        for ops in rounds:
            for (name, payload, _), (_, _, p) in zip(
                    ops, offer(client, actives, ops)):
                assert p.get("ok"), p
                replies.append((name, payload,
                                pkt.b64d(p["response"]) or b""))

        touched = sorted({name for name, _, _ in replies})
        acked = sorted(writes)
        readback = load.read_back(client, acked[:64], actives, KEY, DEADLINE_S)

        def tables_of(name):
            return deployment.replica_tables(cluster, name)

        # check_run holds a name's table to its PUTs alone: the names of the
        # mixed rounds (DELs among them) are held to RefKV by the test
        read = {name: {KEY: got} for name, got in readback.items()}
        problems = kv_register.check_run(writes, tables_of, read, {})
        # a write dropped on one replica: acknowledged, held by two of three
        victim = m.apps[1].db[f"{acked[0]}#0"]
        dropped = victim.pop(KEY)
        faulty = kv_register.check_run(writes, tables_of, read, {})
        victim[KEY] = dropped
        tables = {name: copy.deepcopy(tables_of(name)) for name in touched}
        # the control plane's ticks here are idle probes, a quarter of its
        # wall time: beside five other test workers the schedule can end
        # before it has made the eleven that the count below asks for
        # (PERF.md 7(e)); the probes go on, so wait for them
        waited = time.monotonic() + 60.0
        while (cluster.rc_manager.tick_num - ticks0["rc"] <= 10
               and time.monotonic() < waited):
            time.sleep(0.05)
        ticks1, counts1, modes1 = _plane_counts(cluster)
        served = Served(
            devices=len(m.state.exec_slot.sharding.device_set),
            rc_devices=len(
                cluster.rc_manager.state.exec_slot.sharding.device_set),
            replies=replies, writes=writes, tables=tables, readback=readback,
            problems=problems, problems_dropped_write=faulty,
            ticks={p: ticks1[p] - ticks0[p] for p in ticks1},
            dispatches=_rose(counts1, counts0),
            completions=_rose(modes1, modes0),
            restarted_tables={}, restarted_get={}, replayed_tick=0)
    finally:
        if client is not None:
            client.close()
        cluster.close()

    # ---- a second cluster on the same WAL directories: the replay
    cfg = deployment.make_config(config)
    cluster = deployment.build_cluster(config, cfg, run_dir, 600.0)
    client = ReconfigurableAppClient(cfg.nodes)
    try:
        served.replayed_tick = cluster.manager.tick_num
        served.restarted_tables = {
            name: copy.deepcopy(deployment.replica_tables(cluster, name))
            for name in touched}
        served.restarted_get = load.read_back(
            client, acked[:16], list(cfg.nodes.active_ids()), KEY, DEADLINE_S)
    finally:
        client.close()
        cluster.close()
    return served


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The schedule through both mappings, kernels interpreted."""
    with pytest.MonkeyPatch.context() as env:
        env.setenv("GPTPU_PALLAS", "1")
        env.setenv("GPTPU_PALLAS_INTERPRET", "1")
        return {mapping: serve(mapping, str(tmp_path_factory.mktemp(mapping)))
                for mapping in CONFIGS}


@pytest.mark.parametrize("mapping", list(CONFIGS))
def test_the_state_lies_where_the_configuration_says(runs, mapping):
    want = 4 if mapping == "mesh4" else 1
    assert runs[mapping].devices == runs[mapping].rc_devices == want


@pytest.mark.parametrize("mapping", list(CONFIGS))
def test_every_reply_and_every_replicas_table_equal_the_references(runs,
                                                                   mapping):
    served = runs[mapping]
    assert len(served.replies) == N_PUTS + ROUNDS * MIXED_NAMES
    ref = kv_register.RefKV()
    kinds = set()
    for name, request, reply in served.replies:
        assert reply == ref.apply(name, request), (name, request, reply)
        kinds.add((request[:3], reply[:2]))
    # the schedule met every answer the app can give
    assert kinds >= {(b"PUT", b"OK"), (b"GET", b"NF"), (b"DEL", b"OK"),
                     (b"DEL", b"NF")}
    assert any(op == b"GET" and reply != b"NF" for op, reply in kinds)
    mixed = {name for name, _, _ in served.replies[N_PUTS:]}
    once = {name for name, ws in served.writes.items() if len(ws) == 1}
    assert len(mixed) == MIXED_NAMES and len(once) > 50
    for name in mixed | once:  # one writer at a time: the table is exact
        assert served.tables[name] == [ref.tables.get(name, {})] * 3, name


@pytest.mark.parametrize("mapping", list(CONFIGS))
def test_check_run_passes_and_a_dropped_write_fails_it(runs, mapping):
    served = runs[mapping]
    assert len(served.writes) > 150 and len(served.readback) == 64
    assert any(len(ws) > 1 for ws in served.writes.values())
    assert served.problems == []
    assert len(served.problems_dropped_write) >= 1
    assert any("replicas differ" in p for p in served.problems_dropped_write)


def test_the_mapping_changes_no_answer(runs):
    mesh, one = runs["mesh4"], runs["one"]
    assert mesh.replies == one.replies
    settled = {name for name, ws in mesh.writes.items() if len(ws) == 1}
    settled |= {name for name, _, _ in mesh.replies[N_PUTS:]}
    for name in settled:
        assert mesh.tables[name] == one.tables[name], name
    assert set(mesh.tables) == set(one.tables)


@pytest.mark.parametrize("mapping", list(CONFIGS))
def test_the_journal_replays_to_the_same_tables_after_a_restart(runs,
                                                                mapping):
    served = runs[mapping]
    assert served.replayed_tick > 0
    assert served.restarted_tables == served.tables
    assert len(served.restarted_get) == 16
    for name, value in served.restarted_get.items():
        assert value == served.tables[name][0].get(KEY), name


def test_a_mesh_tick_counts_its_two_dispatches(runs):
    """``mesh_dispatches_total{plane,program}``: every tick of a sharded
    plane enqueues the shard_map tick and the compaction (no fold: the
    placement plane is off); a one-device plane counts nothing."""
    mesh, one = runs["mesh4"], runs["one"]
    for plane in ("ar", "rc"):
        # enough ticks for the equality below to say something.  (It was
        # "> 20" while every reply took two calls; answered by the call
        # that dispatched it, the same schedule takes 19 data-plane ticks
        # and 15 control-plane ones, and an idle probe waits for its own
        # program.)
        assert mesh.ticks[plane] > 10, mesh.ticks
        assert mesh.dispatches[plane] == {"tick": mesh.ticks[plane],
                                          "compact": mesh.ticks[plane],
                                          "fold": 0}
        assert not any(one.dispatches[plane].values())


@pytest.mark.parametrize("mapping", list(CONFIGS))
def test_the_served_ticks_complete_in_the_call_that_dispatched_them(runs,
                                                                    mapping):
    """``tick_completions_total{plane,mode}`` counts every dispatched tick
    once, and on this schedule (nobody waiting for the device; of the 400
    concurrent PUTs a handful of names draw more than P, and a tick holds
    only where those left behind are as many as those it placed: at most a
    tick or two here) the same-call side took the rest:
    the answers above are the same-call path's, under ``pipeline_ticks``."""
    served = runs[mapping]
    for plane in ("ar", "rc"):
        modes = served.completions[plane]
        assert sum(modes.values()) == served.ticks[plane]
        assert modes["held"] <= 3, modes
        assert modes["same_call"] >= 0.8 * served.ticks[plane], modes


def test_the_four_chip_configuration_is_the_one_chip_one_but_for_its_mapping():
    """``modea-3r-1m-mesh4.json`` against ``modea-3r-1m.json``: the same
    deployment, guarantees word for word, and only the mapping's settings
    differ; both build through ``deployment.make_config``; each rehearsal
    file is its cell's configuration at 4,096 groups."""
    one = spec.load_config("chipbench/configs/modea-3r-1m.json")
    four = spec.load_config("chipbench/configs/modea-3r-1m-mesh4.json")
    assert four["guarantees"] == one["guarantees"]
    assert four["reduced"] == one["reduced"] == ["max_groups",
                                                 "populate_groups"]
    same = ("nodes", "app", "native_journal", "populate_groups",
            "replicas_per_group")
    assert [four[k] for k in same] == [one[k] for k in same]
    assert (four["chips"], one["chips"]) == (4, 1)
    moved = {k for k in set(one["paxos"]) | set(four["paxos"])
             if one["paxos"].get(k) != four["paxos"].get(k)}
    assert moved == {"mesh_devices", "mesh_replica_shards"}
    assert four["paxos"]["mesh_devices"] == 4
    assert four["paxos"]["mesh_replica_shards"] == 1
    assert len(four["source"]) <= 200 and "v5e-4" in four["source"]
    for key in ("mapping", "reference", "reduced_why", "assumed",
                "deployment"):
        assert four[key], key
    cfg = deployment.make_config(four)
    assert (cfg.paxos.max_groups, cfg.paxos.mesh_devices) == (1 << 20, 4)
    assert cfg.paxos.compact_outbox and cfg.paxos.pipeline_ticks
    for big, small in (("modea-3r-1m-mesh4", "rehearsal-3r-4k-mesh4"),
                       ("modea-3r-1m", "rehearsal-3r-4k")):
        a = spec.load_config(f"chipbench/configs/{big}.json")
        b = spec.load_config(f"chipbench/configs/{small}.json")
        assert {k: v for k, v in a["paxos"].items() if k != "max_groups"} \
            == {k: v for k, v in b["paxos"].items() if k != "max_groups"}
        assert a["chips"] == b["chips"] and a["guarantees"] == b["guarantees"]


def test_a_setting_the_program_lacks_is_an_error_not_a_silent_default():
    """The four-chip file names only settings ``cfg.paxos`` has (so the
    parent commit builds it too); one it lacks stops ``make_config``."""
    four = spec.load_config("chipbench/configs/modea-3r-1m-mesh4.json")
    broken = copy.deepcopy(four)
    broken["paxos"]["warm_sweep_programs"] = True
    with pytest.raises(deployment.DeploymentError):
        deployment.make_config(broken)
