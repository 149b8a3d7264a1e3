"""The generators: ``open_poisson``'s schedule is what it was before a
``Schedule`` said what each request is; ``open_poisson_mix`` offers the same
work under every seed and draws names as YCSB does."""

import hashlib

import numpy as np
import pytest

from chipbench import spec
from chipbench.generators import open_poisson, open_poisson_mix
from chipbench.references import Op
from chipbench.references.kv_register import request_of

#: (seed, seconds, stream, seq0) -> what the parent commit's
#: ``open_poisson.schedule`` gave over 1,048,512 names and 3 entries
#: (recorded from commit a8db808 before this file's PR touched the generator)
RECORDED = {
    (3000000011, 20.0, 0, 0): (
        20000, 0.0007872556553278898, 942045, 0,
        b"PUT k 0000000000005679ca85d0a73f583f79",
        "d0f3553a49ec99bc877dceefcc6fdea1d47f0202741a1cf527da53933158114d"),
    (7, 3.0, 1, 10 ** 9): (
        3000, 0.0008417842921442098, 324179, 2,
        b"PUT k 001000000000d4e879ce3c81c8bcd6bb",
        "60fa8a5fb46407679bd58602e107256e0a1ba7a820b56416c7fe4ca322ef1f14"),
}
N_NAMES = 1048512


def _mix_params() -> dict:
    return spec.load_traffic("open1k-rw-zipf")["params"]


@pytest.mark.parametrize("case", list(RECORDED))
def test_open_poisson_draws_what_the_parent_drew(case):
    seed, seconds, stream, seq0 = case
    n, due0, name0, entry0, payload0, sha = RECORDED[case]
    params = spec.load_traffic("open1k-put-uniform")["params"]
    s = open_poisson.schedule(params, seed, seconds, N_NAMES, 3,
                              stream=stream, seq0=seq0)
    assert (len(s.due), float(s.due[0]), int(s.name[0]), int(s.entry[0]),
            s.payload[0]) == (n, due0, name0, entry0, payload0)
    h = hashlib.sha256()
    for part in (s.due.tobytes(), s.name.tobytes(), s.entry.tobytes(),
                 b"".join(s.payload), "".join(s.value).encode(), b"k"):
        h.update(part)
    assert h.hexdigest() == sha
    assert s.kind == ["update"] * n and s.key == ["k"] * n


@pytest.mark.parametrize("generator,traffic", [
    (open_poisson, "open1k-put-uniform"), (open_poisson_mix, "open1k-rw-zipf")])
def test_a_schedule_says_what_each_request_is(generator, traffic):
    s = generator.schedule(spec.load_traffic(traffic)["params"], 5, 2.0,
                           4032, 3)
    assert len(s.payload) == len(s.kind) == len(s.key) == len(s.value) == 2000
    for i in range(2000):
        assert s.payload[i] == request_of(Op(s.kind[i], s.key[i], s.value[i],
                                             0.0, 0.0, "ok"))
        assert (s.value[i] is None) == (s.kind[i] == "read")
    written = [v for v in s.value if v is not None]
    assert len(set(written)) == len(written)   # the reference needs them unique
    assert all(len(v) == 32 and v[:12].isdecimal() for v in written)


@pytest.mark.parametrize("seed", [1, 2, 3000000035])
def test_the_mix_offers_the_same_work_under_every_seed(seed):
    s = open_poisson_mix.schedule(_mix_params(), seed, 20.0, N_NAMES, 3)
    assert len(s.due) == 20000 and (np.diff(s.due) >= 0).all()
    assert 0.0 <= s.due[0] and s.due[-1] < 20.0
    reads = s.kind.count("read")
    assert abs(reads / 20000 - 0.5) < 0.01 and reads == 10000
    assert set(s.kind) == {"read", "update"}
    assert set(np.unique(s.entry)) == {0, 1, 2}
    assert 0 <= s.name.min() and s.name.max() < N_NAMES
    again = open_poisson_mix.schedule(_mix_params(), seed, 20.0, N_NAMES, 3)
    assert again.payload == s.payload and (again.name == s.name).all()
    other = open_poisson_mix.schedule(_mix_params(), seed, 20.0, N_NAMES, 3,
                                      stream=1, seq0=10 ** 9)
    assert other.payload != s.payload
    assert not set(filter(None, other.value)) & set(filter(None, s.value))


def _fnv1a_64(octets: bytes) -> int:
    h = 0xCBF29CE484222325
    for b in octets:
        h = ((h ^ b) * 1099511628211) % 2 ** 64
    return h


def test_the_hash_is_fnv_1a_over_eight_octets_low_first():
    assert _fnv1a_64(b"a") == 0xAF63DC4C8601EC8C   # the published test vector
    vals = np.array([0, 1, 255, 256, 10 ** 10 - 1, 2 ** 40 + 12345], np.int64)
    want = []
    for v in vals.tolist():
        h = _fnv1a_64(int(v).to_bytes(8, "little"))
        want.append(abs(h - 2 ** 64 if h >= 2 ** 63 else h))   # Math.abs
    assert open_poisson_mix.fnvhash64(vals).tolist() == want


def test_the_zipfian_is_ycsbs_closed_form_with_ycsbs_constants():
    p = _mix_params()
    items, theta, zetan = p["item_count"], p["zipfian_constant"], p["zetan"]
    assert (items, theta) == (10 ** 10, 0.99)
    # zetan is the sum of i**-theta over the items: Euler-Maclaurin from 10**6
    head = float((np.arange(1, 10 ** 6, dtype=np.float64) ** -theta).sum())
    a, b = 10.0 ** 6, float(items)
    tail = ((b ** (1 - theta) - a ** (1 - theta)) / (1 - theta)
            + (a ** -theta + b ** -theta) / 2)
    assert abs(head + tail - zetan) < 1e-6
    u = np.array([0.0, 0.999 / zetan, 1.001 / zetan,
                  (1 + 0.5 ** theta) * 0.999 / zetan, 0.5, 1 - 1e-12])
    rank = open_poisson_mix.zipfian_ranks(u, items, theta, zetan)
    assert rank[:4].tolist() == [0, 0, 1, 1]
    assert 1 < rank[4] < rank[5] < items


def _shares(n_names: int, seconds: float = 200.0) -> np.ndarray:
    s = open_poisson_mix.schedule(_mix_params(), 35, seconds, n_names, 3)
    return np.bincount(s.name, minlength=n_names) / len(s.name)


def test_the_hottest_name_draws_what_the_constants_give():
    p = _mix_params()
    zetan, theta = p["zetan"], p["zipfian_constant"]
    ranks = np.arange(10 ** 6, dtype=np.int64)
    mass = (ranks + 1.0) ** -theta / zetan   # exact for ranks 0 and 1
    for n_names, lo, hi in ((N_NAMES, 0.036, 0.040), (4096, None, None)):
        share = _shares(n_names)
        hottest = int(share.argmax())
        assert hottest == int(open_poisson_mix.fnvhash64(ranks[:1])[0]
                              % n_names)   # rank 0's name, under every seed
        # what the constants give that name: the first million ranks that
        # hash onto it, and its share of the ranks beyond, spread evenly
        onto = open_poisson_mix.fnvhash64(ranks) % n_names == hottest
        want = mass[onto].sum() + (1.0 - mass.sum()) / n_names
        assert abs(share[hottest] - want) < 0.002, (n_names, share[hottest])
        if lo is not None:
            assert lo < share[hottest] < hi and abs(want - 1 / zetan) < 1e-4
        else:
            assert want > 1 / zetan + 1e-4   # folded onto 4,096: a little more
    # the ten hottest of 1M draw 11%
    ten = np.sort(_shares(N_NAMES))[-10:].sum()
    assert 0.10 < ten < 0.12


def test_uniform_names_are_uniform():
    p = dict(_mix_params(), names="uniform")
    s = open_poisson_mix.schedule(p, 4, 20.0, 4096, 3)
    assert np.bincount(s.name, minlength=4096).max() < 30
    with pytest.raises(ValueError):
        open_poisson_mix.schedule(dict(p, names="latest"), 4, 1.0, 4096, 3)
