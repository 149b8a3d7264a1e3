"""Open-loop Poisson arrivals of single-key writes.

Parameters (a traffic file's ``params``):

``rate_per_s``   offered rate; the window holds exactly ``rate * seconds``
                 requests, at instants uniform over it (a Poisson process
                 given its count), so every seed offers the same amount of
                 work in another order and ``goodput_ops`` reads the rate
                 while the system keeps up
``names``        ``uniform``: the name of each request is uniform over the
                 populated groups
``entry``        ``uniform``: the entry replica is uniform over the actives
``key``          the key every request writes
``value_bytes``  length of the value; the first 12 characters are the
                 request's sequence number, so every value is unique
"""

from __future__ import annotations

import dataclasses

import numpy as np

_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)


@dataclasses.dataclass
class Schedule:
    """``n`` requests: ``due`` seconds from the start of the schedule,
    ascending; ``name``/``entry`` indices into the populated names and the
    actives; ``payload[i]`` the request bytes; ``value[i]`` what it writes."""

    due: np.ndarray
    name: np.ndarray
    entry: np.ndarray
    payload: list
    value: list
    key: str


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def schedule(params: dict, seed: int, seconds: float, n_names: int,
             n_entries: int, stream: int = 0, seq0: int = 0) -> Schedule:
    """The schedule of one phase.  ``stream`` separates the phases of one run
    (warm-up, window) under one seed; ``seq0`` keeps their values apart."""
    if params["names"] != "uniform" or params["entry"] != "uniform":
        raise ValueError("open_poisson draws names and entries uniformly")
    n = int(round(float(params["rate_per_s"]) * seconds))
    rng = _rng(seed, stream)
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    name = rng.integers(0, n_names, size=n)
    entry = rng.integers(0, n_entries, size=n)
    key = params["key"]
    width = int(params["value_bytes"])
    if width < 16:
        raise ValueError("value_bytes under 16 leaves no room for the "
                         "sequence number and the seeded tail")
    tail = _HEX[rng.integers(0, 16, size=(n, width - 12))]
    value = [f"{seq0 + i:012d}" + tail[i].tobytes().decode()
             for i in range(n)]
    payload = [f"PUT {key} {v}".encode() for v in value]
    return Schedule(due, name, entry, payload, value, key)
