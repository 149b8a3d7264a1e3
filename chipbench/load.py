"""The load generator: one thread offers a schedule through the client's
asynchronous path and the client's receive thread stamps the replies.

Open loop: a request is sent when it is due whatever came back so far, and
its latency counts from the due instant.  How late each send was is kept
(``late``), so a starved generator is not read as a fast server.
"""

from __future__ import annotations

import time

import numpy as np

from . import stats


class Load:
    """One offered schedule and what came back.  All instants are
    ``time.monotonic()`` seconds."""

    def __init__(self, sched, names: list, actives: list):
        n = len(sched.due)
        self.sched = sched
        self.names = names
        self.actives = actives
        self.t0 = None                      # instant of schedule second 0
        self.due = np.full(n, np.nan)       # absolute due instants
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.status = np.zeros(n, np.int8)  # stats.PENDING ...
        self.reply = [None] * n             # the response field, as received
        self.n_sent = 0

    def _on_reply(self, i: int, p: dict) -> None:
        self.done[i] = time.monotonic()
        self.reply[i] = p.get("response")
        if p.get("ok"):
            self.status[i] = stats.OK
        else:
            self.status[i] = {"busy": stats.BUSY, "expired": stats.EXPIRED
                              }.get(p.get("error"), stats.ERROR)

    def offer(self, client, t0: float, stop=None) -> None:
        """Send every request at ``t0 + due``; ``stop()`` true ends the
        offering early (the warm-up offers until enough ticks have run)."""
        s = self.sched
        self.t0 = t0
        self.due[:] = t0 + s.due
        send = client.send_request
        for i in range(len(s.due)):
            wait = self.due[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            if stop is not None and stop():
                break
            self.sent[i] = time.monotonic()
            send(self.names[s.name[i]], s.payload[i],
                 lambda p, i=i: self._on_reply(i, p),
                 active=self.actives[s.entry[i]])
            self.n_sent = i + 1

    def answered(self) -> int:
        return int((self.status[:self.n_sent] != stats.PENDING).sum())

    def wait_replies(self, deadline_s: float) -> bool:
        """Until every sent request is answered, or ``deadline_s`` after the
        last send (the client's own deadline: past it a reply is a failure
        anyway).  True when all were answered."""
        if self.n_sent == 0:
            return True
        until = self.sent[self.n_sent - 1] + deadline_s
        while self.answered() < self.n_sent:
            if time.monotonic() > until:
                return False
            time.sleep(0.02)
        return True

    def late_ms(self) -> np.ndarray:
        """Send instant minus due instant of every request sent, ms."""
        n = self.n_sent
        return (self.sent[:n] - self.due[:n]) * 1e3


def read_back(client, names: list, actives: list, key: str,
              deadline_s: float) -> dict:
    """``GET key`` of each name through the client, all in flight at once
    (one at a time would take a commit latency each).  Returns name -> value
    (None for not found); a name that got no acknowledged answer maps to the
    exception text, which no reference allows."""
    from gigapaxos_tpu.reconfiguration import packets as pkt

    got: dict = {}
    for j, name in enumerate(names):
        client.send_request(
            name, f"GET {key}".encode(),
            lambda p, name=name: got.__setitem__(name, p),
            active=actives[j % len(actives)])
    until = time.monotonic() + deadline_s
    while len(got) < len(names) and time.monotonic() < until:
        time.sleep(0.02)
    out = {}
    for name in names:
        p = got.get(name)
        if p is None or not p.get("ok"):
            out[name] = f"<no acknowledged GET: {p}>"
            continue
        body = pkt.b64d(p["response"]) or b""
        out[name] = None if body == b"NF" else body.decode()
    return out
