"""``utils.hotlock.HotLock``: the mutex of the critical sections every
request thread enters (metric counters, the planner's memo). It must
exclude like a lock; what it adds — a contended acquire yields the
interpreter and tries again, it never blocks in the kernel, so a release
is never handed to a thread that cannot run (PERF.md, PR 27) — shows
here as: a waiter gets in once the holder leaves, and the holder is
never starved by its waiters."""

import threading
import time

import pytest

from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.plan.planner import Planner, SubresultCache
from pilosa_tpu.utils.hotlock import HotLock


def test_excludes_eight_threads_doing_read_modify_write():
    lock, box, n = HotLock(), [0], 4000

    def bump():
        for _ in range(n):
            with lock:
                v = box[0]
                if v % 64 == 0:
                    time.sleep(0)       # lose the interpreter inside
                box[0] = v + 1
    threads = [threading.Thread(target=bump) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert box[0] == 8 * n


def test_a_waiter_enters_when_the_holder_leaves_and_not_before():
    lock, order = HotLock(), []
    inside = threading.Event()

    def holder():
        with lock:
            inside.set()
            time.sleep(0.05)
            order.append("holder leaves")

    def waiter():
        inside.wait()
        with lock:
            order.append("waiter enters")
    threads = [threading.Thread(target=f) for f in (holder, waiter)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert order == ["holder leaves", "waiter enters"]


def test_released_on_an_exception():
    lock = HotLock()
    with pytest.raises(ValueError):
        with lock:
            raise ValueError("inside")
    with lock:      # would spin for ever if it were still held
        pass


def test_the_hot_sections_use_it():
    assert isinstance(obs_metrics.QUERIES_TOTAL._mu, HotLock)
    child = obs_metrics.PLANNER_DECISIONS.labels("planned")
    assert isinstance(child._mu, HotLock)
    assert isinstance(obs_metrics.PLANNER_PLAN_SECONDS._default()._mu,
                      HotLock)
    assert isinstance(Planner(None)._mu, HotLock)
    assert isinstance(SubresultCache()._mu, HotLock)
