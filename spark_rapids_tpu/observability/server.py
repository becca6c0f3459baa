"""Embedded telemetry plane: a kill-switched stdlib HTTP server.

One daemon-threaded :class:`ThreadingHTTPServer` bound to 127.0.0.1
(``spark.rapids.tpu.telemetry.{enabled,port}``) exposes the process's
existing observability surfaces to scrapers and load balancers without
adding any dependency:

==============  ===========================================================
``/metrics``    Prometheus exposition text — the metrics registry's
                ``prometheus_text()`` (``text/plain; version=0.0.4``)
``/healthz``    JSON liveness/readiness: engine degraded + quarantine
                state (serving/engine.py), admission queue depth,
                device-semaphore saturation.  **HTTP 503** while the
                engine is degraded, 200 otherwise — a load balancer can
                drain a degraded engine from rotation on status alone.
``/queries``    the flight-recorder ring (observability/history.py) as a
                JSON array, newest last
``/doctor``     last ranked doctor verdicts (per-query and per-tenant),
                including the ``slo-burn`` verdict when a tenant burns
``/slo``        per-tenant multi-window SLO burn rates
                (observability/slo.py)
==============  ===========================================================

Ownership and lifecycle: the ServingEngine starts one server in
``__init__`` and closes it in ``close()``; a classic (non-serving)
TpuSession does the same when the conf enables it.  ``close()`` is
leak-free by contract — it shuts the serve loop down, closes the
listening socket and joins the serve thread, which tools/leak_sentinel.py
asserts (no lingering thread, the port rebinds).

The server holds no state of its own: every route is a callable injected
by the owner, evaluated per request under a broad exception guard (a
failing source yields HTTP 500 with the error, never a dead serve
thread).  With the kill switch off (default) nothing binds, nothing
starts, and no behavior changes anywhere — asserted bit-identical by
tests/test_telemetry.py.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, Optional, Tuple


class TelemetryServer:
    """Serve the injected observability sources over HTTP until closed.

    ``healthz`` returns ``(healthy: bool, payload: dict)`` — unhealthy
    maps to HTTP 503.  ``metrics_text`` returns exposition text; the
    remaining sources return JSON-serializable objects.
    """

    def __init__(self,
                 metrics_text: Callable[[], str],
                 healthz: Callable[[], Tuple[bool, Dict[str, Any]]],
                 queries: Callable[[], Any],
                 doctor: Callable[[], Any],
                 slo: Callable[[], Any],
                 host: str = "127.0.0.1", port: int = 0):
        self._routes: Dict[str, Callable[[], Any]] = {
            "/queries": queries, "/doctor": doctor, "/slo": slo}
        self._metrics_text = metrics_text
        self._healthz = healthz
        self._httpd: Optional[ThreadingHTTPServer] = ThreadingHTTPServer(
            (host, int(port)), self._make_handler())
        self._httpd.daemon_threads = True
        self.host, self.port = self._httpd.server_address[:2]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"srt-telemetry-{self.port}", daemon=True)
        self._thread.start()

    @property
    def endpoint(self) -> str:
        return f"http://{self.host}:{self.port}"

    def close(self) -> None:
        """Stop serving, release the port and join the serve thread
        (idempotent)."""
        httpd, self._httpd = self._httpd, None
        if httpd is None:
            return
        httpd.shutdown()
        httpd.server_close()
        self._thread.join(timeout=5.0)

    # --- request handling -------------------------------------------------
    def _make_handler(self):
        server = self

        class _Handler(BaseHTTPRequestHandler):
            # request threads are short-lived daemons; never let a slow
            # or dead client pin one forever
            timeout = 10.0

            def do_GET(self):  # noqa: N802 (BaseHTTPRequestHandler API)
                path = self.path.split("?", 1)[0].rstrip("/") or "/"
                try:
                    if path == "/metrics":
                        body = server._metrics_text().encode()
                        ctype = "text/plain; version=0.0.4; charset=utf-8"
                        status = 200
                    elif path == "/healthz":
                        healthy, payload = server._healthz()
                        body = _to_json(payload)
                        ctype = "application/json"
                        status = 200 if healthy else 503
                    elif path in server._routes:
                        body = _to_json(server._routes[path]())
                        ctype = "application/json"
                        status = 200
                    else:
                        body = _to_json(
                            {"error": f"no route {path!r}",
                             "routes": ["/metrics", "/healthz",
                                        "/queries", "/doctor", "/slo"]})
                        ctype = "application/json"
                        status = 404
                except Exception as e:  # noqa: BLE001 — route isolation
                    body = _to_json(
                        {"error": f"{type(e).__name__}: {e}"})
                    ctype = "application/json"
                    status = 500
                try:
                    self.send_response(status)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                except (BrokenPipeError, ConnectionResetError):
                    pass  # client went away mid-reply

            def log_message(self, fmt, *log_args):
                pass  # no per-request stderr chatter

        return _Handler


def _to_json(obj: Any) -> bytes:
    return json.dumps(obj, default=str).encode()
