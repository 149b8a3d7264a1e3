"""One malloc setting for a process whose hot path is a thread.

glibc gives every thread but the first its own arena, made of 64 MiB heaps
that are reserved unreadable and made writable as the arena grows: one
``mprotect`` for about every page of net growth.  The tick thread is such a
thread, and everything a tick allocates comes from its arena: inboxes,
outbox copies, every request's decoded body, every value the app keeps.
Where a system call is cheap nobody sees it.  On the sealed hosts this
repository is measured on, one costs about a tenth of a millisecond:
400,000 values of 1 KB kept by a thread took 10.3-13.1 s there against 1.2-1.5
s on the first thread, and the 3.1 M ``PUT`` executions of a load of 1 KB
records took 101 s of its 135 (PERF.md section 6, PR 36).

``M_TOP_PAD`` is the slack malloc asks for beyond each growth.  At 64 MiB
or more a new heap is made writable whole, once, and then grows no more; the
first thread's ``brk`` heap grows in steps of it.  The pad is address space:
only touched pages are resident.  Setting it also fixes the ``mmap``
threshold at its initial 128 KiB (glibc stops adapting it once any of these
parameters is set), which is what a process starts with anyway.
"""

from __future__ import annotations

import ctypes

#: ``M_TOP_PAD`` of ``<malloc.h>``
_M_TOP_PAD = -2
HEAP_BYTES = 64 << 20


def grow_arenas_by_whole_heaps() -> bool:
    """``mallopt(M_TOP_PAD, HEAP_BYTES)``; idempotent.  False where the C
    library has no ``mallopt`` (not glibc): nothing to cure there."""
    try:
        return bool(ctypes.CDLL(None).mallopt(_M_TOP_PAD, HEAP_BYTES))
    except (OSError, AttributeError):
        return False
