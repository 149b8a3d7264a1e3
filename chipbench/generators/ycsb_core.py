"""YCSB ``CoreWorkload``'s transaction phase, open loop: reads of a whole
record beside updates of one field, the names as YCSB draws its keys.

A name holds one record under ``key``: ``fieldcount`` fields of
``fieldlength`` bytes, loaded before the run (the traffic file's
``preload``: YCSB's load phase, an insert of all fields).  Parameters (a
traffic file's ``params``), named as YCSB's properties where it has one:

``rate_per_s``        YCSB's ``-target``; arrivals as ``open_poisson``: the
                      window holds exactly ``rate * seconds`` requests at
                      instants uniform over it
``readproportion``    the share that are ``GET <key>``: with
                      ``readallfields`` true a read returns the record
                      whole.  Exactly ``round(n * readproportion)`` reads at
                      seeded positions, so every seed offers the same work
``updateproportion``  the others: ``SETRANGE <key> <offset> <bytes>`` of one
                      field drawn uniformly (``writeallfields`` false),
                      ``offset = field * fieldlength``, the bytes unique to
                      the request (``open_poisson.unique_values``).  The two
                      shares must add to 1: inserts, scans and
                      read-modify-writes are not known here
``names``             ``scrambled_zipfian`` (``requestdistribution=zipfian``)
                      with ``zipfian_constant``, ``item_count`` and ``zetan``
                      as ``open_poisson_mix`` documents them
``entry``             ``uniform``: the entry replica, over the actives

An update's ``Schedule.value`` is the request's own tail, ``"<offset>
<bytes>"``: unique because the bytes are, and what the record reference
rebuilds the request from.

**A program without ``SETRANGE`` fails here, at import.**  A new cell is
tried first on the parent of the PR that brings it, with that PR's benchmark
files laid over the parent's tree: this file is then there and the
program's ``SETRANGE`` is not.  The harness imports a cell's generator
before it builds anything, so a tree whose ``KVApp`` answers ``ERR`` to this
mix exits non-zero in the seconds the imports take ("cannot run this
configuration") where it would otherwise build a 1M-group cluster, load a
million records for minutes and print ``correct: false`` with exit code 0.
The harness has no such question to ask of a configuration's ``app``; when
it has one (a ``benchmark`` PR's), this goes.
"""

from __future__ import annotations

import numpy as np

from .open_poisson import Schedule, _rng, unique_values
from .open_poisson_mix import fnvhash64, zipfian_ranks


def _program_executes_setrange() -> bool:
    from gigapaxos_tpu.models.replicable import KVApp

    app = KVApp()
    app.execute("probe", b"PUT r ab", 0)
    return (app.execute("probe", b"SETRANGE r 1 c", 1) == b"OK"
            and app.execute("probe", b"GET r", 2) == b"ac")


if not _program_executes_setrange():
    raise ImportError("ycsb_core: this tree's KVApp does not execute "
                      "SETRANGE <key> <offset> <bytes>; the mix cannot run "
                      "on it")


def schedule(params: dict, seed: int, seconds: float, n_names: int,
             n_entries: int, stream: int = 0, seq0: int = 0) -> Schedule:
    """The schedule of one phase, as ``open_poisson.schedule``."""
    reads, updates = (float(params["readproportion"]),
                      float(params["updateproportion"]))
    if abs(reads + updates - 1.0) > 1e-9:
        raise ValueError("ycsb_core knows reads and updates only: "
                         "readproportion + updateproportion must be 1")
    if params["readallfields"] is not True or params["writeallfields"]:
        raise ValueError("ycsb_core reads all fields and writes one")
    if params["names"] != "scrambled_zipfian" or params["entry"] != "uniform":
        raise ValueError("ycsb_core draws names by the scrambled zipfian "
                         "and entries uniformly")
    n = int(round(float(params["rate_per_s"]) * seconds))
    rng = _rng(seed, stream)
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    rank = zipfian_ranks(rng.uniform(size=n), int(params["item_count"]),
                         float(params["zipfian_constant"]),
                         float(params["zetan"]))
    name = fnvhash64(rank) % n_names
    entry = rng.integers(0, n_entries, size=n)
    read = np.zeros(n, bool)
    read[rng.permutation(n)[:int(round(n * reads))]] = True
    width = int(params["fieldlength"])
    offset = rng.integers(0, int(params["fieldcount"]), size=n) * width
    fresh = unique_values(rng, n, width, seq0)
    key = params["key"]
    get = f"GET {key}".encode()
    payload, kind, value = [], [], []
    for i in range(n):
        if read[i]:
            payload.append(get)
            kind.append("read")
            value.append(None)
        else:
            value.append(f"{offset[i]} {fresh[i]}")
            payload.append(f"SETRANGE {key} {value[i]}".encode())
            kind.append("update")
    return Schedule(due, name, entry, payload, kind, [key] * n, value)
