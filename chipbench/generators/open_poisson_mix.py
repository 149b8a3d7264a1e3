"""Open-loop Poisson arrivals of single-key reads and updates, the names
uniform or as YCSB draws them.

Parameters (a traffic file's ``params``), beside ``open_poisson``'s
``rate_per_s``, ``entry``, ``key`` and ``value_bytes``:

``read_share``   the share of requests that are ``GET <key>``; the others
                 are ``PUT <key> <value>``.  A window holds exactly
                 ``round(n * read_share)`` reads at seeded positions, so
                 every seed offers the same work in another order
``names``        ``uniform``, or ``scrambled_zipfian``: YCSB's
                 ``requestdistribution=zipfian``
                 (``ScrambledZipfianGenerator``): a zipfian rank of constant
                 ``zipfian_constant`` over ``item_count`` items, whose zeta
                 is ``zetan``, hashed by FNV-1a (64 bit) modulo the populated
                 names.  The three constants are YCSB's own (0.99, 10**10,
                 26.469...), whatever the record count: the hottest name
                 draws 1 / zetan = 3.8% and the ranks beyond the record count
                 (42% of the draws at 1M) fall near-uniformly
"""

from __future__ import annotations

import numpy as np

from .open_poisson import Schedule, _rng, unique_values

_FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
_FNV_PRIME_64 = np.uint64(1099511628211)


def zipfian_ranks(u: np.ndarray, items: int, theta: float,
                  zetan: float) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` over ``u`` uniform in [0, 1): the
    rank drawn, 0 the most popular (Gray et al., "Quickly generating
    billion-record synthetic databases", SIGMOD 1994)."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    rank = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    uz = u * zetan
    rank[uz < zeta2] = 1
    rank[uz < 1.0] = 0
    return rank


def fnvhash64(val: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` of non-negative int64 values: FNV-1a over
    the eight octets, low first, in wrapping 64-bit arithmetic, then
    ``Math.abs`` of the signed result."""
    val = val.astype(np.uint64)
    h = np.full(val.shape, _FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h = (h ^ (val & np.uint64(0xFF))) * _FNV_PRIME_64
        val = val >> np.uint64(8)
    return np.abs(h.view(np.int64))


def schedule(params: dict, seed: int, seconds: float, n_names: int,
             n_entries: int, stream: int = 0, seq0: int = 0) -> Schedule:
    """The schedule of one phase, as ``open_poisson.schedule``."""
    if params["entry"] != "uniform":
        raise ValueError("open_poisson_mix draws entries uniformly")
    n = int(round(float(params["rate_per_s"]) * seconds))
    rng = _rng(seed, stream)
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    if params["names"] == "uniform":
        name = rng.integers(0, n_names, size=n)
    elif params["names"] == "scrambled_zipfian":
        rank = zipfian_ranks(rng.uniform(size=n), int(params["item_count"]),
                             float(params["zipfian_constant"]),
                             float(params["zetan"]))
        name = fnvhash64(rank) % n_names
    else:
        raise ValueError(f"open_poisson_mix does not know names = "
                         f"{params['names']!r}")
    entry = rng.integers(0, n_entries, size=n)
    read = np.zeros(n, bool)
    read[rng.permutation(n)[:int(round(n * float(params["read_share"])))]] = True
    key = params["key"]
    value = unique_values(rng, n, int(params["value_bytes"]), seq0)
    get = f"GET {key}".encode()
    payload, kind = [], []
    for i in range(n):
        if read[i]:
            value[i] = None
            payload.append(get)
            kind.append("read")
        else:
            payload.append(f"PUT {key} {value[i]}".encode())
            kind.append("update")
    return Schedule(due, name, entry, payload, kind, [key] * n, value)
