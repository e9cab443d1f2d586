"""HotLock: a mutex for the few-bytecode critical sections that every
request thread enters many times a request (a metric's counter, the
planner's memo).

``threading.Lock`` blocks a contended ``acquire`` in the kernel, and the
kernel hands a released lock straight to a blocked waiter — a thread
that does not hold the interpreter's lock (the GIL) and now owns the
mutex while it waits for it. Every other request thread that reaches
the mutex meanwhile blocks behind it, so the next release is again a
hand-off to a thread without the GIL: a convoy, and it sustains itself.
Eight connection threads taking the planner's lock 16-32 times a
request served the ``plan`` stage in 0.2 ms or in 8-20 ms, for tens of
seconds at a time, with nothing else changed (PERF.md, PR 27: the
host's "two speeds"; a host-only harness of eight planning threads
reproduces both levels and the flip between them).

A ``HotLock`` is only ever taken by a thread that is running, i.e.
holds the GIL: a contended ``acquire`` does not block, it yields the
GIL (``time.sleep(0)``) and tries again, so a release never hands the
mutex to a thread that cannot use it at once. Use it where the critical
section is a few bytecodes and never blocks; a section that can wait
(I/O, another lock, a device) would make the waiters spin and keeps
``threading.Lock``. Context manager only, on purpose.

When NOT to use it: on a lock that is merely taken often. The convoy
needs waiters, i.e. a critical section long or frequent enough that
threads arrive while it is held (the planner's, 16-32 times a request).
A ``threading.Lock`` that a read takes once or twice around a few
bytecodes is not one: under eight clients on the chip's host
``FairDispatchQueue.acquire`` / ``release`` read 3.4 / 3.9 us a read and
``CostModel.record``, which sorts a 64-deque under its lock, 12.8 us
(segment timing, PERF.md PR 30), where the same read lost 1.6-2.0 ms at
each of three places that let go of the interpreter WITHOUT any lock (a
``getrandom``, a buffer's destructor, a ``poll``). Look there first: a
stage that swells under load has a GIL release in it, and a mutex is
only one way to have one. And a ``HotLock``'s waiters spin through the
interpreter (``sleep(0)`` is a release and a re-acquire each turn): with
many threads already queued for the GIL every turn is another trip to
the back of that queue. Seven read-path locks swapped at once read
worse in a CPU rehearsal (PR 30's issue); the chip was not asked, since
its timing had cleared the locks.
"""

from __future__ import annotations

import time
from _thread import allocate_lock


class HotLock:
    __slots__ = ("_mu",)

    def __init__(self):
        self._mu = allocate_lock()

    def __enter__(self):
        acquire = self._mu.acquire
        while not acquire(False):
            time.sleep(0)   # give the interpreter to the holder

    def __exit__(self, exc_type, exc, tb):
        self._mu.release()
