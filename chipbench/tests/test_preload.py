"""The records a traffic file's ``preload`` asks for: seeded, unique against
every value a generator writes, and handed to the reference without a dict
of a million dicts."""

import types

import numpy as np
import pytest

from chipbench import deployment, spec
from chipbench.generators import open_poisson_mix


def test_record_values_are_seeded_and_unique_against_the_traffic():
    a = deployment.record_values(3000000035, 4032, 32)
    assert a.dtype == np.dtype("S32") and a.shape == (4032,)
    assert (a == deployment.record_values(3000000035, 4032, 32)).all()
    assert (a != deployment.record_values(3000000036, 4032, 32)).any()
    values = [v.decode() for v in a.tolist()]
    assert [int(v[:12]) for v in values] == list(
        range(deployment.PRELOAD_SEQ0, deployment.PRELOAD_SEQ0 + 4032))
    assert all(set(v[12:]) <= set("0123456789abcdef") for v in values)
    params = spec.load_traffic("open1k-rw-zipf")["params"]
    for stream, seq0, seconds in ((0, 0, 20.0), (1, 10 ** 9, 150.0)):
        s = open_poisson_mix.schedule(params, 3000000035, seconds, 4032, 3,
                                      stream=stream, seq0=seq0)
        assert not set(values) & set(filter(None, s.value))
    with pytest.raises(ValueError):
        deployment.record_values(1, 8, 12)


def test_loaded_maps_a_name_to_its_record():
    values = deployment.record_values(7, 100, 16)
    loaded = deployment.Loaded("k", values)
    assert len(loaded) == 100 and list(loaded)[:2] == ["bg0", "bg1"]
    assert loaded["bg42"] == {"k": values[42].decode()}
    assert loaded.get("bg100", {}) == {} and loaded.get("other7", {}) == {}
    assert "bg99" in loaded and "bg-1" not in loaded and "bg" not in loaded


class _FakeCluster:
    """What ``deployment.preload`` touches of a cluster: the row of a name,
    ``propose_bulk`` (answered at once, through the sink, in two parts and
    out of order) and the driver's ``kick``."""

    def __init__(self, answer=lambda payload: b"OK", admit=lambda i: True):
        self.calls, self.kicks, self._answer, self._admit = [], 0, answer, admit
        self.manager = types.SimpleNamespace(
            rows=types.SimpleNamespace(row=lambda name: int(name[2:-2]) + 7),
            propose_bulk=self._propose_bulk)
        self.driver = types.SimpleNamespace(kick=self._kick)

    def _kick(self):
        self.kicks += 1

    def _propose_bulk(self, rows, payloads, batch_sink=None):
        self.calls.append((np.array(rows), list(payloads)))
        rids = np.array([i + 1 if self._admit(i) else -2
                         for i in range(len(rows))], np.int64)
        answers = [self._answer(p) for p in reversed(payloads)]
        half = len(answers) // 2
        batch_sink(None, answers[:half])
        batch_sink(None, answers[half:])
        return rids


def test_preload_writes_every_name_once_in_waves():
    names = [f"bg{i}" for i in range(4032)]
    cluster = _FakeCluster()
    loaded = deployment.preload(cluster, names, {"key": "k", "value_bytes": 32},
                                3000000035, wave=1000)
    # five waves (the last of 32), each kicked, the names in order, one
    # ``PUT k <its record>`` a name
    assert [len(rows) for rows, _ in cluster.calls] == [1000] * 4 + [32]
    assert cluster.kicks == 5
    assert (np.concatenate([rows for rows, _ in cluster.calls])
            == np.arange(4032) + 7).all()
    sent = [p for _, payloads in cluster.calls for p in payloads]
    assert sent == [f"PUT k {loaded[n]['k']}".encode() for n in names]
    assert len(set(sent)) == 4032 and len(loaded) == 4032
    # the default is one wave at a rehearsal's size
    cluster = _FakeCluster()
    deployment.preload(cluster, names, {"key": "k", "value_bytes": 32}, 1)
    assert [len(rows) for rows, _ in cluster.calls] == [4032]
    assert deployment.PRELOAD_WAVE == 262144


@pytest.mark.parametrize("fault,says", [
    (dict(answer=lambda p: b"NF" if p.endswith(b"0") else b"OK"),
     "records not answered OK"),
    (dict(admit=lambda i: i != 3), "1 of 1000 records not admitted")])
def test_preload_refuses_a_record_that_was_not_written(fault, says):
    names = [f"bg{i}" for i in range(2500)]
    with pytest.raises(deployment.DeploymentError, match=says):
        deployment.preload(_FakeCluster(**fault), names,
                           {"key": "k", "value_bytes": 32}, 5, wave=1000)
