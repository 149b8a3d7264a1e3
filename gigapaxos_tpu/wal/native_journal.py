"""ctypes binding for the C++ journal backend (``native/journal.cc``).

The shared library is a build product, not a tracked file: it is built from
``journal.cc`` on first use wherever it is absent (a fresh clone, the chip
tool's copy) if the toolchain is available (no pybind11 in the target image
— plain C ABI + ctypes).  After editing ``journal.cc``, ``make -C native``.
On-disk format is byte-identical to :mod:`gigapaxos_tpu.wal.journal`, so
readers are shared.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_LIB = None
_LOAD_ERROR: NativeUnavailable | None = None
_LOCK = threading.Lock()
_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")


class NativeUnavailable(RuntimeError):
    pass


def _load():
    global _LIB, _LOAD_ERROR
    with _LOCK:
        if _LIB is not None:
            return _LIB
        if _LOAD_ERROR is not None:
            # cache the failure: re-running the build subprocess on every
            # journal roll would put a fork+compile on the durability path
            raise _LOAD_ERROR
        so = os.path.abspath(os.path.join(_NATIVE_DIR, "libgpjournal.so"))
        src = os.path.abspath(os.path.join(_NATIVE_DIR, "journal.cc"))
        try:
            if not os.path.exists(so):
                if not os.path.exists(src):
                    raise NativeUnavailable("journal.cc not found")
                # the Makefile renames the finished library into place, so
                # cell workers that all find it absent can build at once
                subprocess.run(
                    ["make", "-C", os.path.dirname(src), "libgpjournal.so"],
                    check=True,
                    capture_output=True,
                )
            lib = ctypes.CDLL(so)
        except Exception as e:
            # a failed build's reason is the compiler's stderr, not make's
            # exit status
            err = getattr(e, "stderr", None)
            tail = f" | {err.decode(errors='replace')[-400:]}" if err else ""
            _LOAD_ERROR = NativeUnavailable(
                f"native journal unavailable: {e}{tail}")
            raise _LOAD_ERROR from e
        lib.gpj_open.restype = ctypes.c_void_p
        lib.gpj_open.argtypes = [ctypes.c_char_p]
        lib.gpj_append.restype = ctypes.c_int
        lib.gpj_append.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
        ]
        lib.gpj_sync.restype = ctypes.c_int
        lib.gpj_sync.argtypes = [ctypes.c_void_p]
        lib.gpj_close.restype = None
        lib.gpj_close.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return lib


class NativeJournal:
    def __init__(self, path: str):
        lib = _load()
        self._lib = lib
        if os.path.exists(path) and os.path.getsize(path) > 0:
            # scribble classification is the Python scanner's job: gpj_open
            # truncates at the first bad frame, which on a mid-log scribble
            # would silently destroy the intact (possibly acked) suffix
            from .journal import JournalCorruptError, scan_journal

            scan = scan_journal(path)
            if scan.kind == "scribble":
                raise JournalCorruptError(path, scan)
        self._h = lib.gpj_open(path.encode())
        if not self._h:
            raise OSError(f"gpj_open failed for {path}")
        self.path = path
        self.failed = False

    def append(self, record: bytes) -> None:
        if self.failed:
            raise OSError("journal has failed; refusing further appends")
        if self._lib.gpj_append(self._h, record, len(record)) != 0:
            self.failed = True
            raise OSError("journal append failed")

    def sync(self) -> None:
        if self.failed:
            raise OSError("journal has failed; refusing further syncs")
        if self._lib.gpj_sync(self._h) != 0:
            self.failed = True
            raise OSError("journal sync failed")

    def close(self) -> None:
        if self._h:
            self._lib.gpj_close(self._h)
            self._h = None
