"""A tick's journal record never outgrows what a journal scan believes
(ISSUE 36: a load of 1 KB records in waves of 262,144 made one record of 270
MB, over ``wal.journal.MAX_FRAME``: written and acknowledged, and read as a
scribble at the next start).  ``_place_bulk`` places a tick's bulk work up
to half of ``MAX_RECORD`` in journal bytes, in arrival order, and the logger
refuses a record a scan would refuse.
"""

import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.obs.metrics import registry
from gigapaxos_tpu.paxos import manager as manager_mod
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.wal import journal, logger
from gigapaxos_tpu.wal.logger import PaxosLogger, WalError, recover


def _manager(tmp_path, pipeline: bool, plane: str = "cap"):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 64
    cfg.paxos.compact_outbox = True
    cfg.paxos.pipeline_ticks = pipeline
    apps = [KVApp() for _ in range(3)]
    wal = PaxosLogger(str(tmp_path), native=False)
    m = PaxosManager(cfg, 3, apps, wal=wal, spill_ns=plane)
    rows = []
    for i in range(48):
        assert m.create_paxos_instance(f"g{i}", [0, 1, 2])
        rows.append(m.rows.row(f"g{i}"))
    return cfg, apps, m, rows


def _wal_bytes(plane: str) -> tuple:
    h = registry().snapshot()["wal_append_bytes{plane=%s}" % plane]
    return h["count"], h["sum"], max(map(int, h["buckets"]), default=0)


@pytest.mark.parametrize("pipeline", [False, True])
def test_a_wave_over_the_cap_is_journaled_in_several_records(
        tmp_path, monkeypatch, pipeline):
    # 48 records of 1,006 + 32 bytes against a cap of 8,192 / 2: four a tick
    monkeypatch.setattr(manager_mod, "_WAL_MAX_RECORD", 8192)
    plane = f"cap{int(pipeline)}"
    cfg, apps, m, rows = _manager(tmp_path, pipeline, plane)
    payloads = [f"PUT r {i:04d}".encode() + b"." * 996 for i in range(48)]
    # two more to the first name, behind its record: a key's order holds
    extra = [b"SETRANGE r 0 AAAA", b"SETRANGE r 2 BBBB"]
    answers = {}
    rids = m.propose_bulk(
        rows + rows[:1] * 2, payloads + extra,
        batch_sink=lambda offs, resps: answers.update(zip(offs.tolist(),
                                                          resps)))
    assert (rids > 0).all()
    count0, sum0, _ = _wal_bytes(plane)
    for _ in range(40):
        m.tick()
    m.drain_pipeline()
    assert answers == {i: b"OK" for i in range(50)}
    count1, sum1, top = _wal_bytes(plane)
    # every record under the cap's half plus one body; the bodies once each
    assert top <= (8192 // 2 + 1038).bit_length()
    assert 48 * 1006 < sum1 - sum0 < 48 * 1100 + 40 * 64
    placed_ticks = -(-48 * 1038 // (8192 // 2))   # 13: the wave took that many
    assert m.tick_num >= placed_ticks
    want = {f"g{i}": {"r": f"{i:04d}" + "." * 996} for i in range(48)}
    want["g0"]["r"] = "AABBBB" + "." * 994
    for app in apps:
        assert app.db == want
    # ... and it replays: no record is one a scan refuses
    m.wal.close()
    assert max(len(r) for r in journal.read_journal(
        m.wal._journal_path(m.wal.seq))) <= 8192 // 2 + 1038 + 64
    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, str(tmp_path), native=False)
    assert [a.db for a in apps2] == [want] * 3
    m2.wal.close()


def test_one_body_over_the_cap_is_still_placed(tmp_path, monkeypatch):
    monkeypatch.setattr(manager_mod, "_WAL_MAX_RECORD", 1024)
    cfg, apps, m, rows = _manager(tmp_path, False)
    rids = m.propose_bulk(rows[:3], [b"PUT r " + b"x" * 2000] * 3)
    assert (rids > 0).all()
    for _ in range(12):
        m.tick()
    assert all(app.db[f"g{i}"] == {"r": "x" * 2000}
               for app in apps for i in range(3))
    m.wal.close()


def test_the_logger_refuses_a_record_a_scan_would_refuse(tmp_path,
                                                         monkeypatch):
    assert journal.MAX_RECORD == journal.MAX_FRAME - 9
    monkeypatch.setattr(logger, "MAX_RECORD", 4096)
    cfg, apps, m, rows = _manager(tmp_path, False)
    m.propose("g0", b"PUT r " + b"y" * 5000)
    with pytest.raises(WalError, match="over the 4,096 a journal scan"):
        m.tick()
    # nothing of it reached the file, and nothing was acknowledged
    m.wal.close()
    assert all(len(r) <= 4096 for r in journal.read_journal(
        m.wal._journal_path(m.wal.seq)))
    assert all("g0" not in app.db or "r" not in app.db["g0"] for app in apps)


def test_the_scan_refuses_what_the_cap_keeps_out(tmp_path, monkeypatch):
    """What the cap is for: a frame over MAX_FRAME with records behind it is
    a scribble to the scanner, not a record."""
    path = str(tmp_path / "j.log")
    j = journal.PyJournal(path)
    j.append(b"first")
    j.append(b"z" * 3000)
    j.append(b"behind it")
    j.close()
    assert journal.scan_journal(path).kind == "clean"
    monkeypatch.setattr(journal, "MAX_FRAME", 2048)
    scan = journal.scan_journal(path)
    assert scan.kind == "scribble" and scan.records == [b"first"]
