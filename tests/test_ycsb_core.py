"""``chipbench/generators/ycsb_core`` (ISSUE 36): YCSB core workload A's
transaction phase as a schedule: the same work under every seed, one field
an update, the names as YCSB's scrambled zipfian draws them, and payloads the
record reference rebuilds from what the schedule says of them.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import deployment, spec  # noqa: E402
from chipbench.generators import open_poisson_mix, ycsb_core  # noqa: E402
from chipbench.references import Op  # noqa: E402
from chipbench.references.kv_record import request_of  # noqa: E402

N_NAMES = 1048512
TRAFFIC = "open1k-ycsb-a"


def _params(**changed) -> dict:
    return dict(spec.load_traffic(TRAFFIC)["params"], **changed)


def test_the_traffic_file_states_workload_a_at_its_published_widths():
    traffic = spec.load_traffic(TRAFFIC)
    assert traffic["generator"] == "ycsb_core"
    assert traffic["params"] == {
        "rate_per_s": 1000, "readproportion": 0.5, "updateproportion": 0.5,
        "names": "scrambled_zipfian", "zipfian_constant": 0.99,
        "item_count": 10 ** 10, "zetan": 26.46902820178302,
        "entry": "uniform", "key": "r", "fieldcount": 10, "fieldlength": 100,
        "readallfields": True, "writeallfields": False}
    assert traffic["preload"] == {"key": "r", "value_bytes": 1000}
    mix = spec.load_traffic("open1k-rw-zipf")["params"]
    for constant in ("zipfian_constant", "item_count", "zetan"):
        assert traffic["params"][constant] == mix[constant]


@pytest.mark.parametrize("seed", [1, 2, 3000003601, 2 ** 31 + 5])
def test_every_seed_offers_the_same_work(seed):
    s = ycsb_core.schedule(_params(), seed, 20.0, N_NAMES, 3)
    assert len(s.due) == 20000 and (np.diff(s.due) >= 0).all()
    assert 0.0 <= s.due[0] and s.due[-1] < 20.0
    assert s.kind.count("read") == 10000 and s.kind.count("update") == 10000
    assert set(s.key) == {"r"}
    assert set(np.unique(s.entry)) == {0, 1, 2}
    assert 0 <= s.name.min() and s.name.max() < N_NAMES
    again = ycsb_core.schedule(_params(), seed, 20.0, N_NAMES, 3)
    assert again.payload == s.payload and (again.name == s.name).all()
    warm = ycsb_core.schedule(_params(), seed, 20.0, N_NAMES, 3, stream=1,
                              seq0=10 ** 9)
    assert warm.payload != s.payload
    assert not set(filter(None, warm.value)) & set(filter(None, s.value))


def test_payloads_are_what_the_schedule_says_of_them():
    s = ycsb_core.schedule(_params(), 5, 4.0, 4032, 3)
    written = []
    for i in range(len(s.due)):
        assert s.payload[i] == request_of(Op(s.kind[i], s.key[i], s.value[i],
                                             0.0, 0.0, "ok"))
        if s.kind[i] == "read":
            assert s.payload[i] == b"GET r" and s.value[i] is None
            continue
        verb, key, offset, data = s.payload[i].decode().split(" ")
        assert (verb, key) == ("SETRANGE", "r")
        assert int(offset) % 100 == 0 and 0 <= int(offset) < 1000
        assert len(data) == 100 and data[:12].isdecimal()
        assert set(data[12:]) <= set("0123456789abcdef")
        assert len(s.payload[i]) == len(f"SETRANGE r {offset} ") + 100
        written.append(data)
    assert len(set(written)) == len(written) == 2000
    # ... and against the loaded records' slices, which carry their own
    # sequence numbers (from 2 * 10**9) in the first field alone
    loaded = deployment.record_values(5, 64, 1000)
    slices = {v.decode()[i:i + 100] for v in loaded.tolist()
              for i in range(0, 1000, 100)}
    assert len(slices) == 640 and not slices & set(written)


def test_an_updates_field_is_uniform_over_the_ten():
    s = ycsb_core.schedule(_params(), 11, 100.0, N_NAMES, 3)
    offsets = np.array([int(v.split(" ", 1)[0]) for v in s.value
                        if v is not None])
    counts = np.bincount(offsets // 100, minlength=10)
    assert len(offsets) == 50000 and set(offsets % 100) == {0}
    # 5,000 expected a field, sigma 67
    assert counts.min() > 4700 and counts.max() < 5300
    # the field does not follow the name or the kind's position
    assert abs(np.corrcoef(offsets, np.arange(len(offsets)))[0, 1]) < 0.02


def test_the_hottest_name_draws_one_in_zetan():
    p = _params()
    s = ycsb_core.schedule(p, 36, 400.0, N_NAMES, 3)
    share = np.bincount(s.name, minlength=N_NAMES) / len(s.name)
    hottest = int(share.argmax())
    assert hottest == int(open_poisson_mix.fnvhash64(
        np.zeros(1, np.int64))[0] % N_NAMES)   # rank 0's name, every seed
    # 400,000 draws: sigma of the share is 0.0003
    assert abs(share[hottest] - 1 / p["zetan"]) < 0.0015
    assert 0.10 < np.sort(share)[-10:].sum() < 0.12
    # reads and updates draw the name alike
    hot_kinds = [k for k, n in zip(s.kind, s.name) if n == hottest]
    assert 0.45 < hot_kinds.count("read") / len(hot_kinds) < 0.55


def test_what_the_generator_refuses():
    for changed in (dict(names="uniform"), dict(readproportion=0.95),
                    dict(writeallfields=True), dict(readallfields=False),
                    dict(entry="first")):
        with pytest.raises(ValueError):
            ycsb_core.schedule(_params(**changed), 4, 1.0, 4096, 3)
    # workload B's shares are the same generator's
    b = ycsb_core.schedule(_params(readproportion=0.95,
                                   updateproportion=0.05), 4, 20.0, 4096, 3)
    assert b.kind.count("read") == 19000


def test_the_configuration_is_modea_at_ycsbs_widths():
    """``ycsb-a-3r-1m.json`` against ``modea-3r-1m.json``: the same
    deployment and program; the second and third guarantee word for word,
    the first widened to reads; its rehearsal file the same at 4,096."""
    base = spec.load_config("chipbench/configs/modea-3r-1m.json")
    ycsb = spec.load_config("chipbench/configs/ycsb-a-3r-1m.json")
    small = spec.load_config("chipbench/configs/rehearsal-ycsb-a-3r-4k.json")
    same = ("deployment", "chips", "nodes", "app", "native_journal", "paxos",
            "populate_groups", "replicas_per_group")
    assert [ycsb[k] for k in same] == [base[k] for k in same]
    assert ycsb["guarantees"][1:] == base["guarantees"][1:]
    assert len(ycsb["guarantees"]) == 3
    assert "reads included" in ycsb["guarantees"][0]
    assert "read_leases off" in ycsb["guarantees"][0]
    assert ycsb["paxos"].get("read_leases", False) is False
    assert ycsb["reference"] == small["reference"] == "kv_record"
    assert ycsb["reduced"] == ["recordcount", "operationcount"]
    assert "workloads/workloada" in ycsb["source"] and len(ycsb["source"]) <= 200
    assert ycsb["record"]["fieldcount"] * ycsb["record"]["fieldlength"] == 1000
    assert small["guarantees"] == ycsb["guarantees"]
    assert small["paxos"]["max_groups"] == 4096 and small["reduced"] == []
    assert {k: v for k, v in small["paxos"].items() if k != "max_groups"} == {
        k: v for k, v in ycsb["paxos"].items() if k != "max_groups"}
