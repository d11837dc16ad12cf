"""Threaded stdlib HTTP transport for BeaconApp.

The reference's API Gateway + AWS_PROXY integration layer (reference:
api.tf REST resources, stage 'prod') reduced to one ThreadingHTTPServer:
URL + query string + JSON body in, JSON out, CORS header kept
(reference apiutils/api_response.py HEADERS).
"""

from __future__ import annotations

import json
import math
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from ..utils.trace import stage, thread_clock
from .app import BeaconApp


def _make_handler(app: BeaconApp):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args):  # quiet by default
            pass

        def setup(self):
            # a connection's thread, by name the ``request`` role of
            # runtime.thread_cpu_ms (utils/trace.THREAD_ROLES)
            threading.current_thread().name = "request"
            super().setup()

        def finish(self):
            # the connection is over and its thread ends: the thread's
            # last reading, for the role's sums
            try:
                super().finish()
            finally:
                thread_clock.leave()

        def parse_request(self):
            # the request line has just been read: ``http.read`` runs
            # from here over the headers, and again in _respond over
            # the URL and the body
            with stage("http.read"):
                return super().parse_request()

        def _respond(self):
            with stage("http.read"):
                parsed = urlparse(self.path)
                # flatten single-valued query params (API-GW style)
                query = {
                    k: (v[0] if len(v) == 1 else ",".join(v))
                    for k, v in parse_qs(parsed.query).items()
                }
                body = None
                bad_body = False
                length = int(self.headers.get("Content-Length") or 0)
                if length:
                    raw = self.rfile.read(length)
                    try:
                        body = json.loads(raw)
                    except json.JSONDecodeError:
                        bad_body = True
                headers = dict(self.headers.items())
            if bad_body:
                self._send(400, {"error": "invalid JSON body"})
                return
            status, payload = app.handle(
                self.command, parsed.path, query, body, headers=headers
            )
            self._send(status, payload)

        def _send(self, status: int, payload):
            with stage("http.write"):
                self._write(status, payload)

        def _write(self, status: int, payload):
            if isinstance(payload, str):
                # text payloads (Prometheus exposition from /metrics)
                # go out verbatim as text/plain
                data = payload.encode()
                content_type = "text/plain; version=0.0.4"
            else:
                data = json.dumps(payload).encode()
                content_type = "application/json"
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Access-Control-Allow-Origin", "*")
            retry_after = (
                payload.get("retryAfterSeconds")
                if isinstance(payload, dict) and status in (429, 503)
                else None
            )
            if retry_after is not None:
                # standard client-backoff hint: the SAME value as the
                # envelope's retryAfterSeconds — the app layer already
                # normalized it to RFC 9110 integral seconds (rounded
                # up), so the ceil here is a no-op guard for payloads
                # minted outside BeaconApp.handle
                self.send_header(
                    "Retry-After", str(max(1, math.ceil(retry_after)))
                )
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_OPTIONS(self):  # CORS preflight
            self.send_response(204)
            self.send_header("Access-Control-Allow-Origin", "*")
            self.send_header(
                "Access-Control-Allow-Methods", "GET, POST, PATCH, OPTIONS"
            )
            self.send_header(
                "Access-Control-Allow-Headers",
                # the client-settable request headers DEPLOYMENT.md
                # documents: auth, per-request deadline, trace id
                "Content-Type, Authorization, X-Beacon-Deadline, "
                "X-Beacon-Trace",
            )
            self.send_header("Content-Length", "0")
            self.end_headers()

        do_GET = _respond
        do_POST = _respond
        do_PATCH = _respond

    return Handler


class _BeaconServer(ThreadingHTTPServer):
    # socketserver's default listen backlog is 5: a 16-client connect
    # burst overflows it, the kernel drops the SYN, and the client's
    # SYN retransmit fires after exactly 1 s — measured as ~1050 ms
    # p99 outliers with the entire serving path warm (r5 soak tail
    # decomposition: in-process p99 was 1.4x p50, HTTP p99 was 17x).
    request_queue_size = 128


def make_server(app: BeaconApp, host: str = "127.0.0.1", port: int = 0):
    """ThreadingHTTPServer bound to (host, port); port 0 picks a free one."""
    return _BeaconServer((host, port), _make_handler(app))


def serve(app: BeaconApp, host: str = "0.0.0.0", port: int = 5000):
    """Blocking serve-forever (the deployment entry)."""
    server = make_server(app, host, port)
    try:
        server.serve_forever()
    finally:
        server.server_close()
        # app-owned pools/tables die with the deployment entry (the
        # runner's worker threads are non-daemon; leaving them alive
        # stalls interpreter exit on the atexit join)
        app.close()


def start_background(app: BeaconApp, host: str = "127.0.0.1", port: int = 0):
    """(server, thread) with the server running on a daemon thread —
    used by tests and the benchmark harness."""
    server = make_server(app, host, port)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server, t


def build_app(config, worker_urls=()) -> tuple[BeaconApp, int]:
    """Everything the deployment entry does before it warms and binds:
    compile cache, armed fault plan, engine (fronting ``worker_urls``
    when given), app, and the persisted shards re-pinned. Returns
    ``(app, shards loaded)``. ``main`` and ``chip_smoke.py`` both start
    the server through here and :func:`warm_app`."""
    import logging

    from ..config import enable_persistent_compile_cache
    from ..harness.faults import install_from_env

    try:
        enable_persistent_compile_cache()
    except OSError:
        # an optimisation, never a dependency: the server starts cold
        logging.getLogger(__name__).exception(
            "persistent compilation cache unavailable"
        )
    # chaos runs against a real server: BEACON_FAULT_PLAN arms seeded
    # fault injection (harness/faults.py); unset = no-op
    install_from_env()
    engine = None
    if worker_urls:
        from ..engine import VariantEngine
        from ..parallel.dispatch import DistributedEngine

        # the local VariantEngine hosts this machine's shards; BeaconApp
        # wires ingestion to it (engine.local) while queries fan out
        # through the coordinator
        engine = DistributedEngine(
            list(worker_urls), local=VariantEngine(config), config=config
        )
    app = BeaconApp(config, engine=engine)
    return app, app.ingest.load_all()


def warm_app(app: BeaconApp) -> int:
    """Pre-compile every dispatchable kernel program so no request pays
    a first-compile (the soak-tail cause, VERDICT r4 #10/next #7);
    returns the number of programs touched."""
    warm = getattr(app.engine, "warmup", None)
    return warm() if warm else 0


def main(argv: list[str] | None = None) -> None:
    """``python -m sbeacon_tpu.api.server`` — the deployment entry the
    reference expresses as terraform apply (api.tf + lambda env blocks):
    one process serving the full Beacon v2 surface over a disk-backed
    store, optionally fronting remote worker hosts (--worker)."""
    import argparse

    from ..config import BeaconConfig

    p = argparse.ArgumentParser(description="TPU-native Beacon v2 server")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=5000)
    p.add_argument(
        "--data-root",
        default=None,
        help="storage root (default: BeaconConfig/./beacon_data)",
    )
    p.add_argument(
        "--worker",
        action="append",
        default=[],
        metavar="URL",
        help="remote worker base URL (repeatable); queries fan out across "
        "workers + local shards",
    )
    args = p.parse_args(argv)

    config = BeaconConfig.from_env(args.data_root)
    app, n = build_app(config, args.worker)
    n_warm = warm_app(app)
    print(
        f"beacon serving on {args.host}:{args.port} "
        f"({n} index shards loaded, {len(args.worker)} workers, "
        f"{n_warm} kernel programs warmed)"
    )
    serve(app, host=args.host, port=args.port)


if __name__ == "__main__":  # pragma: no cover
    main()
