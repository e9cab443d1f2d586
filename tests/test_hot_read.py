"""A hot read leaves the interpreter only where it waits for I/O
(ISSUE 30): its connection thread lets go of the interpreter lock at
``recv``, the program launch, the result fetch (and the result's
release, which is jaxlib's) and ``sendall``, once each.

Two places did besides (PERF.md, PR 30, segment timing on the chip):
the query id (``uuid4`` is a ``getrandom`` made with the lock released)
and the socket's Python-level timeout (a poll before every recv and
every send). What took their place is pinned here; what a release costs
a contended thread is the chip's to show, not a CPU test's. The two
sites the issue suspected and the timing cleared (``FairDispatchQueue``,
``CostModel.record``: 3-13 us a read) keep their code; their contracts
are pinned in ``test_tier.py::TestFairDispatch`` and
``test_costmodel.py::TestFeedbackLoop``."""

import http.client
import os
import socket
import struct
import sys
import threading
import time

import pytest

from pilosa_tpu.sched import QueryContext
from pilosa_tpu.sched import context as sched_context
from pilosa_tpu.server.server import Server

HEX = set("0123456789abcdef")
COUNT = (b'Count(Intersect(Bitmap(frame="f", rowID=1),'
         b' Bitmap(frame="f", rowID=2)))')


# -- query ids -----------------------------------------------------------------


@pytest.fixture
def fresh_ids():
    """Whatever a test did to the generator, the next test draws from
    one seeded from the kernel's pool again."""
    yield
    sched_context._seed_ids()


def test_ids_are_16_hex_and_distinct_over_1e5_draws_and_two_generators(
        fresh_ids):
    ids = [sched_context.new_query_id() for _ in range(50_000)]
    sched_context._seed_ids()       # what another process would hold
    ids += [sched_context.new_query_id() for _ in range(50_000)]
    assert all(len(i) == 16 and set(i) <= HEX for i in ids)
    assert len(set(ids)) == 100_000


def test_the_generator_is_seeded_from_the_kernels_pool_once(
        monkeypatch, fresh_ids):
    """Two processes differ because their seeds do: the same 16 bytes
    give the same ids, other bytes give others, and drawing asks the
    pool for nothing."""
    calls = []

    def pool(n, fill=b"\x01"):
        calls.append(n)
        return fill * n

    monkeypatch.setattr(os, "urandom", pool)
    sched_context._seed_ids()
    first = [sched_context.new_query_id() for _ in range(8)]
    sched_context._seed_ids()
    assert [sched_context.new_query_id() for _ in range(8)] == first
    monkeypatch.setattr(os, "urandom", lambda n: pool(n, b"\x02"))
    sched_context._seed_ids()
    assert not set(first) & {sched_context.new_query_id()
                             for _ in range(8)}
    assert calls == [16, 16, 16]


@pytest.mark.parametrize("given", [None, "", "c0ffee"],
                         ids=["coordinator", "empty-header", "forwarded"])
def test_a_context_draws_its_id_without_a_system_call(monkeypatch, given):
    """A coordinator read draws; a forwarded leg keeps its
    coordinator's id."""
    def no_pool(n):
        raise AssertionError("os.urandom called for a query id")

    monkeypatch.setattr(os, "urandom", no_pool)
    ctx = QueryContext(pql="Count()", id=given)
    if given:
        assert ctx.id == given
    else:
        assert len(ctx.id) == 16 and set(ctx.id) <= HEX
        assert QueryContext().id != ctx.id


def test_concurrent_draws_lose_and_repeat_nothing():
    """``getrandbits`` is one C call under the interpreter lock: eight
    threads drawing at once, switching every few bytecodes, get
    distinct ids with no lock of the generator's own."""
    got: list[list[str]] = [[] for _ in range(8)]

    def draw(out):
        for _ in range(5_000):
            out.append(sched_context.new_query_id())

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=draw, args=(g,)) for g in got]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    ids = [i for g in got for i in g]
    assert len(ids) == 40_000 and len(set(ids)) == 40_000


# -- the served read -----------------------------------------------------------


@pytest.fixture
def served(tmp_path):
    s = Server(str(tmp_path / "s"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    conn = http.client.HTTPConnection(s.host, timeout=30)

    def post(path, body, headers=None):
        conn.request("POST", path, body, headers or {})
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.getheaders())

    assert post("/index/i", b"{}")[0] == 200
    assert post("/index/i/frame/f", b"{}")[0] == 200
    for row, cols in ((1, (3, 5, 9)), (2, (5, 9, 11))):
        for col in cols:
            assert post("/index/i/query",
                        f'SetBit(frame="f", rowID={row},'
                        f' columnID={col})'.encode())[0] == 200
    try:
        yield s, post
    finally:
        conn.close()
        s.close()


def test_a_served_count_answers_with_the_kernels_pool_out_of_reach(
        served, monkeypatch):
    """``os.urandom`` raises on every connection thread once the server
    is up: a Count(Intersect(...)) through Handler still answers, under
    a fresh 16-hex id each time, and a forwarded id is kept."""
    _, post = served
    real = os.urandom

    def pool(n):
        if threading.current_thread().name == "httpd-conn":
            raise OSError("getrandom on a connection thread")
        return real(n)

    monkeypatch.setattr(os, "urandom", pool)
    ids = []
    for _ in range(20):
        status, body, headers = post("/index/i/query", COUNT)
        assert status == 200 and b'"results":[2]' in body.replace(
            b" ", b"")
        ids.append(headers["X-Pilosa-Query-Id"])
    assert all(len(i) == 16 and set(i) <= HEX for i in ids)
    assert len(set(ids)) == 20
    _, _, headers = post("/index/i/query", COUNT,
                         {"X-Pilosa-Query-Id": "fromcoordinator1"})
    assert headers["X-Pilosa-Query-Id"] == "fromcoordinator1"


def test_a_connection_socket_blocks_under_the_kernels_timeouts(served):
    """No Python-level timeout (under one every recv and every send is
    a poll AND the call, two trips out of the interpreter for one):
    the idle limit is SO_RCVTIMEO / SO_SNDTIMEO on a blocking socket."""
    s, post = served
    assert post("/index/i/query", COUNT)[0] == 200
    httpd = s._httpd
    with httpd._conns_mu:
        conns = list(httpd._conns)
    assert conns
    tv = struct.pack("ll", int(httpd.IDLE_TIMEOUT_S), 0)
    for c in conns:
        assert c.gettimeout() is None
        for opt in (socket.SO_RCVTIMEO, socket.SO_SNDTIMEO):
            assert c.getsockopt(socket.SOL_SOCKET, opt, len(tv)) == tv


@pytest.mark.parametrize("sent", [b"", b"POST /index/i/query HTTP/1.1\r\n"
                                  b"Content-Length: 100\r\n\r\nCount("],
                         ids=["idle", "mid-request"])
def test_the_kernels_timeout_still_closes_a_silent_connection(
        served, monkeypatch, sent):
    """A keep-alive client that goes quiet, between requests or in the
    middle of one, gives its thread and socket back after the limit,
    and one that speaks within it is served."""
    s, post = served
    monkeypatch.setattr(type(s._httpd), "IDLE_TIMEOUT_S", 0.3)
    host, port = s.host.rsplit(":", 1)
    with socket.create_connection((host, int(port)), timeout=10) as c:
        c.sendall(b"POST /index/i/query HTTP/1.1\r\nContent-Length: "
                  + str(len(COUNT)).encode() + b"\r\n\r\n" + COUNT)
        assert b"200 OK" in c.recv(65536)
        time.sleep(0.1)             # inside the limit: still open
        if sent:
            c.sendall(sent)
        t0 = time.monotonic()
        assert c.recv(65536) == b""         # closed by the server
        assert 0.1 < time.monotonic() - t0 < 5.0
    assert post("/index/i/query", COUNT)[0] == 200
