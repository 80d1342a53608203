"""Event-loop serving core (serve/asyncore.py): concurrency far past the
worker pool, pipelined framing, the connection cap, drain semantics, and
the threaded fallback (ISSUE-7 tentpole)."""

import json
import socket
import threading
import time

import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.serve import Client, Server, ServerError


@pytest.fixture(autouse=True, scope="module")
def _lock_witness():
    # runtime lock-order witness (lint/witness.py): the event-loop
    # front end + tenancy scheduler run under declared-order checking
    from cloudberry_tpu.lint import witness

    with witness.watching():
        yield


def _session(**over):
    s = cb.Session(Config().with_overrides(**over) if over else Config())
    s.sql("create table t (a bigint, b bigint) distributed by (a)")
    s.sql("insert into t values " +
          ",".join(f"({i}, {i * 2})" for i in range(500)))
    return s


def test_async_is_the_default_transport():
    from cloudberry_tpu.serve.asyncore import AsyncFrontEnd

    with Server(session=_session()) as srv:
        assert isinstance(srv._transport, AsyncFrontEnd)
        with Client(srv.host, srv.port) as c:
            assert c.sql("select count(*) as n from t")["rows"] == [[500]]


def test_threaded_fallback_still_works():
    from cloudberry_tpu.serve.server import _ThreadedTransport

    s = _session(**{"serve.threaded": True})
    with Server(session=s) as srv:
        assert isinstance(srv._transport, _ThreadedTransport)
        with Client(srv.host, srv.port) as c:
            assert c.sql("select count(*) as n from t")["rows"] == [[500]]


def test_many_connections_few_threads():
    """64 concurrent connections — an order of magnitude past the worker
    pool — all served, with correct per-connection results."""
    s = _session(**{"serve.workers": 4, "serve.io_threads": 2})
    errors = []
    with Server(session=s) as srv:
        before = threading.active_count()

        def one(i):
            try:
                with Client(srv.host, srv.port) as c:
                    out = c.sql(f"select b from t where a = {i}")
                    if out["rows"] != [[i * 2]]:
                        errors.append(f"wrong row for {i}: {out['rows']}")
            except Exception as e:  # pragma: no cover
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(64)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        # the server side added no per-connection threads (the client
        # side owns the 64; server threads stay a small constant)
        assert threading.active_count() - before <= 70
    assert not errors, errors[:3]


def test_pipelined_requests_answered_in_order():
    """A client that writes N requests before reading any gets N
    responses in request order — the per-connection serialization
    guarantee of the event loop."""
    with Server(session=_session()) as srv:
        sock = socket.create_connection((srv.host, srv.port), timeout=30)
        try:
            payload = b"".join(
                json.dumps({"sql": f"select b from t where a = {i}"})
                .encode() + b"\n" for i in range(10))
            sock.sendall(payload)
            f = sock.makefile("rb")
            for i in range(10):
                resp = json.loads(f.readline())
                assert resp["ok"] and resp["rows"] == [[i * 2]], (i, resp)
        finally:
            sock.close()


def test_connection_cap_returns_retryable_server_busy():
    s = _session(**{"serve.max_connections": 2})
    with Server(session=s) as srv:
        held = [Client(srv.host, srv.port) for _ in range(2)]
        try:
            c3 = Client(srv.host, srv.port)
            with pytest.raises(ServerError) as ei:
                c3.sql("select count(*) as n from t")
            assert ei.value.etype == "ServerBusy"
            assert ei.value.retryable
        finally:
            for c in held:
                c.close()
        # slots free again after the held connections close
        deadline = time.monotonic() + 10
        while True:
            try:
                with Client(srv.host, srv.port) as c:
                    assert c.sql("select count(*) as n from t")[
                        "rows"] == [[500]]
                break
            except ServerError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.05)


def test_server_busy_client_reconnect_retry():
    """ISSUE-7 satellite: the retry policy honors SERVER_BUSY by name
    and reconnects (the refusal closes the socket), so a client riding
    a briefly-full server eventually succeeds."""
    s = _session(**{"serve.max_connections": 1})
    with Server(session=s) as srv:
        blocker = Client(srv.host, srv.port)

        def free_slot():
            time.sleep(0.15)
            blocker.close()

        threading.Thread(target=free_slot).start()
        with Client(srv.host, srv.port, retry_reads=True, max_retries=6,
                    backoff_s=0.05) as c:
            out = c.sql("select count(*) as n from t")
            assert out["rows"] == [[500]]


def test_async_drain_never_drops_accepted_requests():
    """Server.stop(drain_s) on the event-loop core: every accepted
    request gets its answer (result or the retryable drain refusal)."""
    s = _session()
    srv = Server(session=s).start()
    results = []
    errors = []
    stop_client = threading.Event()

    def pound(i):
        try:
            with Client(srv.host, srv.port) as c:
                while not stop_client.is_set():
                    try:
                        out = c.sql(f"select b from t where a = {i}")
                        results.append(out["rows"][0][0])
                    except ServerError as e:
                        if e.etype in ("ServerDraining",) or \
                                str(e).startswith(
                                    "server closed the connection"):
                            return  # visible refusal/shutdown: fine
                        raise
                    except OSError:
                        # a reset mid-send during shutdown is a VISIBLE
                        # connection failure (the request was never
                        # accepted), not a silent drop
                        return
        except Exception as e:  # pragma: no cover
            errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=pound, args=(i,))
               for i in range(6)]
    for th in threads:
        th.start()
    time.sleep(0.4)
    srv.stop(drain_s=10.0)
    stop_client.set()
    for th in threads:
        th.join(timeout=30)
    assert not errors, errors[:3]
    assert results  # real work flowed before the drain


def test_async_per_connection_txn_rolls_back_on_disconnect(tmp_path):
    """Per-connection backends over a durable store: a dropped
    connection aborts its open wire transaction (the backend-exit
    rollback), same as the threaded transport."""
    cfg = Config().with_overrides(
        **{"storage.root": str(tmp_path / "store")})
    with Server(config=cfg) as srv:
        with Client(srv.host, srv.port) as c:
            c.sql("create table d (x bigint) distributed by (x)")
            c.sql("insert into d values (1)")
        c2 = Client(srv.host, srv.port)
        c2.sql("begin")
        c2.sql("insert into d values (2)")
        c2.close()  # connection dies with the transaction open
        deadline = time.monotonic() + 10
        while True:
            with Client(srv.host, srv.port) as c3:
                n = c3.sql("select count(*) as n from d")["rows"][0][0]
            if n == 1 or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert n == 1  # the in-txn insert rolled back


def test_async_auth_and_lockout():
    with Server(session=_session(), auth_token="hunter2",
                max_login_failures=2, lockout_s=30.0) as srv:
        for _ in range(2):
            with pytest.raises(ServerError, match="authentication"):
                Client(srv.host, srv.port, token="nope")
        with pytest.raises(ServerError, match="locked"):
            Client(srv.host, srv.port, token="hunter2")


@pytest.mark.parametrize("keepalive_s, answered", [(0.2, True),
                                                   (30.0, False)])
def test_a_long_request_keeps_its_connection_alive(monkeypatch, keepalive_s,
                                                   answered):
    """A statement served for longer than the client's socket time limit
    (a first send that compiles for minutes): a space every
    ``serve.keepalive_s`` holds the connection, the answer's line parses as ever, and the
    connection goes on; with the silence longer than the limit the
    client gives up, as it always did."""
    sound = Server._execute

    def slow(self, req, sess, async_cb=None):
        if "count(*)" in req.get("sql", ""):
            time.sleep(2.5)
        return sound(self, req, sess, async_cb=async_cb)

    monkeypatch.setattr(Server, "_execute", slow)
    with Server(session=_session(
            **{"serve.keepalive_s": keepalive_s})) as srv:
        c = Client(srv.host, srv.port, timeout=1.0)
        try:
            if answered:
                for _ in range(2):
                    assert c.sql("select count(*) as n from t")["rows"] \
                        == [[500]]
                assert c.sql("select a from t where a = 7")["rows"] == [[7]]
            else:
                with pytest.raises(OSError):
                    c.sql("select count(*) as n from t")
        finally:
            c._sock.close()
