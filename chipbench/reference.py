"""The write-only form of the reference, as it stood until ISSUE 35: a thin
import of ``references/kv_register.py``, kept because
``tests/test_mesh_served_path.py`` holds the served path to ``RefKV``,
``Write`` and this ``check_run``, and a ``benchmark`` PR may not edit
``tests/``.  The harness does not import it: a configuration names its
reference (``spec.reference``).  So there are two ``RefKV``: this one (in
``kv_register``) and ``chip_smoke.py``'s, the original.
"""

from __future__ import annotations

import dataclasses

from .references import Op
from .references import kv_register
from .references.kv_register import RefKV  # noqa: F401  (tests/ import it)


@dataclasses.dataclass
class Write:
    """One ``PUT`` as the client saw it (``references.Op`` without its kind,
    key and reply)."""

    value: str
    sent: float
    done: float
    status: str


def check_run(writes_by_name: dict, tables_of, replies: list, readback: dict,
              key: str) -> list:
    """``kv_register.check_run`` over writes to one ``key``.  ``replies``:
    (name, request bytes, reply bytes) of acknowledged ``PUT``s, matched to
    their writes by the request; ``readback``: name -> the value a ``GET``
    returned.  A reply whose request is no listed write's ``PUT <key>
    <value>`` is an error, not a reply left unchecked."""
    answered = {(name, request): reply for name, request, reply in replies}
    ops = {name: [Op("update", key, w.value, w.sent, w.done, w.status,
                     answered.pop((name, f"PUT {key} {w.value}".encode()),
                                  None))
                  for w in writes]
           for name, writes in writes_by_name.items()}
    if answered:
        raise ValueError(f"{len(answered)} replies match no listed write, "
                         f"the first {next(iter(answered))!r}")
    return kv_register.check_run(
        ops, tables_of, {name: {key: got} for name, got in readback.items()},
        {})
