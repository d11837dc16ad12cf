"""Pooled keep-alive transport (parallel/transport.py): connection
reuse, idle eviction, stale-connection replay, gzip bodies, deadline
clamps, HTTP-error-as-status semantics — plus the CI wiring for
``tools/check_transport_usage.py`` (no unpooled urlopen on the worker
data plane)."""

import gzip
import json
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

from sbeacon_tpu.parallel.transport import (
    PooledTransport,
    urllib_get,
    urllib_post,
)
from sbeacon_tpu.resilience import Deadline, deadline_scope

REPO = Path(__file__).resolve().parent.parent


# -- a tiny keep-alive echo server (no engine needed) -------------------------


def _make_echo_handler():
    class EchoHandler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _send(self, status, doc):
            body = json.dumps(doc).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            # read BEFORE answering: the client clears the flag as soon
            # as it has the response, and may win that race
            sneaky = getattr(self.server, "sneaky_close", False)
            if self.path == "/missing":
                self._send(404, {"error": "not found"})
            else:
                self._send(200, {"ok": True, "path": self.path})
            if sneaky:
                # close WITHOUT a Connection: close header — the silent
                # idle-close a pooled client only discovers on its next
                # send (the replay-once scenario)
                self.close_connection = True

        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(n)
            was_gzip = (
                self.headers.get("Content-Encoding", "").lower() == "gzip"
            )
            if was_gzip:
                raw = gzip.decompress(raw)
            self._send(
                200,
                {"len": len(raw), "gzip": was_gzip, "echo": json.loads(raw)},
            )

    return EchoHandler


class _EchoServer:
    def __init__(self, port: int = 0):
        self.server = ThreadingHTTPServer(
            ("127.0.0.1", port), _make_echo_handler()
        )
        self.accepts = 0
        orig = self.server.get_request

        def counting_get_request():
            self.accepts += 1
            return orig()

        self.server.get_request = counting_get_request
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self.thread.start()

    @property
    def port(self) -> int:
        return self.server.server_address[1]

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def shutdown(self):
        self.server.shutdown()
        self.server.server_close()


@pytest.fixture()
def echo():
    s = _EchoServer()
    try:
        yield s
    finally:
        s.shutdown()


# -- pooling ------------------------------------------------------------------


def test_sequential_calls_reuse_one_connection(echo):
    t = PooledTransport(pool_size=2)
    try:
        for k in range(8):
            status, doc = t.get_json(f"{echo.url}/hello", 5)
            assert status == 200 and doc["ok"]
        status, doc = t.post_json(f"{echo.url}/echo", {"k": 1}, 5)
        assert status == 200 and doc["echo"] == {"k": 1}
        m = t.metrics()
        assert m["opened"] == 1, m
        assert m["reused"] == 8, m
        assert echo.accepts == 1
    finally:
        t.close()


def test_pool_bounds_kept_connections(echo):
    """A concurrency burst beyond pool_size opens extra connections but
    only pool_size survive checkin — the rest are closed, not hoarded."""
    t = PooledTransport(pool_size=2)
    try:
        barrier = threading.Barrier(5)

        def one():
            barrier.wait()
            status, _ = t.get_json(f"{echo.url}/x", 5)
            assert status == 200

        threads = [threading.Thread(target=one) for _ in range(5)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        m = t.metrics()
        assert m["pooled"] <= 2, m
        assert m["opened"] >= 2, m  # a real burst happened
    finally:
        t.close()


def test_idle_ttl_evicts_pooled_connections(echo):
    clock = [0.0]
    t = PooledTransport(pool_size=2, idle_ttl_s=10.0, clock=lambda: clock[0])
    try:
        t.get_json(f"{echo.url}/a", 5)
        clock[0] = 5.0
        t.get_json(f"{echo.url}/b", 5)  # fresh enough: reused
        assert t.metrics()["reused"] == 1
        clock[0] = 20.0  # idle past the TTL: evicted, new conn opened
        t.get_json(f"{echo.url}/c", 5)
        m = t.metrics()
        assert m["evicted"] == 1, m
        assert m["opened"] == 2, m
    finally:
        t.close()


def test_stale_pooled_connection_replayed_once(echo):
    """The server closing a pooled connection between requests must be
    invisible: the call replays once on a fresh connection."""
    t = PooledTransport(pool_size=2)
    try:
        echo.server.sneaky_close = True
        assert t.get_json(f"{echo.url}/a", 5)[0] == 200
        # the pooled connection is now half-closed server-side; the
        # next call discovers that mid-send and replays transparently
        echo.server.sneaky_close = False
        status, doc = t.get_json(f"{echo.url}/b", 5)
        assert status == 200 and doc["ok"]
        assert t.metrics()["retried"] == 1
        assert t.metrics()["opened"] == 2
    finally:
        t.close()


def test_gzip_bodies_over_threshold(echo):
    t = PooledTransport(gzip_min_bytes=64)
    try:
        small = {"k": "v"}
        status, doc = t.post_json(f"{echo.url}/echo", small, 5)
        assert status == 200 and doc["gzip"] is False
        big = {"pad": "x" * 500}
        status, doc = t.post_json(f"{echo.url}/echo", big, 5)
        assert status == 200
        assert doc["gzip"] is True and doc["echo"] == big
        assert t.metrics()["gzip_bodies"] == 1
    finally:
        t.close()


def test_http_error_statuses_are_returned_not_raised(echo):
    t = PooledTransport()
    try:
        status, doc = t.get_json(f"{echo.url}/missing", 5)
        assert status == 404 and "error" in doc
    finally:
        t.close()


def test_deadline_clamps_before_send(echo):
    t = PooledTransport()
    try:
        with deadline_scope(Deadline.after(1e-9)):
            with pytest.raises(TimeoutError):
                t.get_json(f"{echo.url}/a", 5)
    finally:
        t.close()


def test_bytes_body_passthrough(echo):
    """post_json ships pre-serialized bytes verbatim (the dispatcher's
    no-double-encode hot path)."""
    t = PooledTransport()
    try:
        body = json.dumps({"pre": "serialized"}).encode()
        status, doc = t.post_json(f"{echo.url}/echo", body, 5)
        assert status == 200 and doc["echo"] == {"pre": "serialized"}
        assert PooledTransport.post_json.accepts_bytes
        assert PooledTransport.post_bytes.accepts_bytes
    finally:
        t.close()


# -- unpooled fallbacks -------------------------------------------------------


def test_urllib_get_returns_status_on_http_error(echo):
    """ISSUE 5 satellite regression: urllib_get must carry the same
    HTTPError -> (code, body) handling urllib_post always had — a 404
    on a discovery GET is a countable answer, not an exception."""
    status, doc = urllib_get(f"{echo.url}/missing", 5)
    assert status == 404 and "error" in doc
    status, doc = urllib_post(f"{echo.url}/echo", {"a": 1}, 5)
    assert status == 200 and doc["echo"] == {"a": 1}


# -- CI wiring for the transport-usage lint -----------------------------------


def test_transport_usage_lint():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_transport_usage.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_transport_usage_lint_catches_violations(tmp_path):
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_transport_usage import scan
    finally:
        sys.path.pop(0)

    pkg = tmp_path / "sbeacon_tpu"
    (pkg / "parallel").mkdir(parents=True)
    (pkg / "parallel" / "transport.py").write_text(
        "import urllib.request\n"
        "def ok(u):\n"
        "    return urllib.request.urlopen(u)\n"
    )
    (pkg / "rogue.py").write_text(
        "import urllib.request\n"
        "def bad(u):\n"
        "    return urllib.request.urlopen(u)\n"
    )
    hits = scan(pkg)
    assert len(hits) == 1 and "rogue.py" in hits[0]
