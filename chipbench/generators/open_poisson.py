"""Open-loop Poisson arrivals of single-key writes; and ``Schedule``, what
every generator returns.

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
    actives; ``payload[i]`` the request bytes, and what the check needs to
    know of them: ``kind[i]`` (``update``, ``read`` or ``delete``),
    ``key[i]`` the key it touches, ``value[i]`` what an update writes
    (``None`` otherwise)."""

    due: np.ndarray
    name: np.ndarray
    entry: np.ndarray
    payload: list
    kind: list
    key: list
    value: list


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def value_array(rng: np.random.Generator, n: int, width: int,
                seq0: int) -> np.ndarray:
    """``n`` values of ``width`` characters as an ``S<width>`` array: the
    sequence number from ``seq0`` in the first 12, so that no two values of
    a run are equal (the reference names a read's write by its value), then
    a seeded hexadecimal tail."""
    if width < 16:
        raise ValueError("value_bytes under 16 leaves no room for the "
                         "sequence number and the seeded tail")
    tail = _HEX[rng.integers(0, 16, size=(n, width - 12))]
    seq = seq0 + np.arange(n, dtype=np.int64)
    digits = seq[:, None] // 10 ** np.arange(11, -1, -1, dtype=np.int64) % 10
    both = np.concatenate([(digits + ord("0")).astype(np.uint8), tail], axis=1)
    return np.ascontiguousarray(both).view(f"S{width}").ravel()


def unique_values(rng: np.random.Generator, n: int, width: int,
                  seq0: int) -> list:
    """``value_array`` as a list of ``str``."""
    return [v.decode() for v in value_array(rng, n, width, seq0).tolist()]


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
    value = unique_values(rng, n, int(params["value_bytes"]), seq0)
    payload = [f"PUT {key} {v}".encode() for v in value]
    return Schedule(due, name, entry, payload, ["update"] * n, [key] * n,
                    value)
