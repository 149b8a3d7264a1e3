"""``utils/heap.grow_arenas_by_whole_heaps`` (ISSUE 36): the one malloc
setting a process with a tick thread takes, made where the thread is."""

import ctypes
import threading

from gigapaxos_tpu.utils import heap


def test_the_setting_is_taken_and_can_be_taken_again():
    has_mallopt = hasattr(ctypes.CDLL(None), "mallopt")
    assert heap.grow_arenas_by_whole_heaps() is has_mallopt
    assert heap.grow_arenas_by_whole_heaps() is has_mallopt
    assert heap.HEAP_BYTES == 64 << 20   # glibc's HEAP_MAX_SIZE on 64 bit


def test_a_tick_driver_takes_it(monkeypatch):
    from gigapaxos_tpu.paxos import driver

    taken = []
    monkeypatch.setattr(driver, "grow_arenas_by_whole_heaps",
                        lambda: taken.append(1))
    driver.TickDriver(manager=None)
    assert taken == [1]


def test_a_thread_still_keeps_what_it_allocates_after_it():
    heap.grow_arenas_by_whole_heaps()
    kept = []

    def work():
        kept.extend(("%06d" % i) * 170 for i in range(20000))  # 1 KB each

    t = threading.Thread(target=work)
    t.start()
    t.join()
    assert len(kept) == 20000 and kept[12345][:6] == "012345"
