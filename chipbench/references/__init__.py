"""References, one file each, found by the name a configuration gives under
``"reference"`` (``spec.reference``).  A reference is a plain implementation
of the deployment's semantics and the comparison that decides ``correct``;
it imports nothing of ``gigapaxos_tpu`` and takes nothing the program made
but the answers, the replicas' tables and the instants the client saw.

The interface of a reference module (``chipbench/README.md`` has the row):

``check_run(ops_by_name, tables_of, readback, initial) -> [problem strings]``

``ops_by_name``  service name -> [``Op``], every request the run sent to it,
                 warm-up and window, in the order sent
``tables_of``    ``tables_of(name)`` -> one dict per replica: what each holds
``readback``     name -> {key: what a ``GET`` through the client returned
                 after the drain} (``None`` = not found; a name that got no
                 acknowledged answer carries the exception text)
``initial``      mapping name -> {key: value} of the records loaded before
                 the warm-up (``deployment.preload``); empty without one
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Op:
    """One request as the client saw it.  ``kind`` is ``update``, ``read``
    or ``delete``; ``value`` what an update writes (``None`` otherwise);
    ``sent`` / ``done`` the instants it was sent and its reply arrived;
    ``status`` is ``ok``, ``refused`` (answered busy, expired or an error:
    the system says it did not execute it) or ``unknown`` (no reply: it may
    or may not have executed); ``reply`` the reply's bytes of an
    acknowledged request (``None``: not compared)."""

    kind: str
    key: str
    value: str | None
    sent: float
    done: float
    status: str
    reply: bytes | None = None
