"""Telemetry exporters: training-side HTTP listener and JSONL stream.

Two ways out of the process for the registry's numbers, both stdlib:

* ``TelemetryHTTPServer`` — a daemon-thread HTTP listener (enabled by
  ``MXNET_TELEMETRY_PORT``) serving ``/metrics`` (Prometheus text
  exposition), ``/metrics.json`` (raw registry snapshot), and
  ``/healthz``. This is the *training-side* scrape point; serving
  replicas already have an HTTP front end, so ``serve/http.py`` grows
  the same exposition on its existing ``/metrics`` route instead.
* ``JsonlWriter`` — appends one registry snapshot per 16-step window to
  a JSONL file next to the chrome trace (``MXNET_TELEMETRY_JSONL``, or
  ``$MXNET_TELEMETRY_DIR/telemetry.jsonl``), giving post-hoc tooling a
  step-time/MFU/engine-depth time series without a scraper running.

Both are opt-in via flags and fail soft: a dead port or full disk must
never take down the training loop.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


class _Handler(BaseHTTPRequestHandler):
    server_version = "mxtpu-telemetry/1"
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):         # quiet by default
        pass

    def _reply(self, code, body, content_type):
        data = body.encode("utf-8") if isinstance(body, str) else body
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        from mxnet_tpu.telemetry import prom, registry
        path = self.path.split("?", 1)[0]
        if path == "/metrics":
            self._reply(200, prom.exposition(registry.default_registry()),
                        prom.CONTENT_TYPE)
        elif path == "/metrics.json":
            self._reply(200, json.dumps(registry.snapshot()),
                        "application/json")
        elif path == "/healthz":
            self._reply(200, json.dumps({"status": "ok",
                                         "time": time.time()}),
                        "application/json")
        else:
            self._reply(404, json.dumps({"error": "not found"}),
                        "application/json")


class TelemetryHTTPServer:
    def __init__(self, host="0.0.0.0", port=0):
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._thread = None

    @property
    def address(self):
        host, port = self._httpd.server_address[:2]
        return "http://%s:%d" % (host, port)

    @property
    def port(self):
        return self._httpd.server_address[1]

    def start(self):
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.2},
            name="mxtpu-telemetry-http", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(5)


_http_lock = threading.Lock()
_http_server = None
_http_failed = False


def start_http(port, host="0.0.0.0"):
    return TelemetryHTTPServer(host=host, port=port).start()


def maybe_start_http():
    """Start the flag-gated listener once per process; returns it (or
    None when MXNET_TELEMETRY_PORT is 0/unset or the bind failed)."""
    global _http_server, _http_failed
    with _http_lock:
        if _http_server is not None or _http_failed:
            return _http_server
        try:
            from mxnet_tpu.config import flags
            port = int(flags.telemetry_port)
        except Exception:
            port = 0
        if port <= 0:
            return None
        try:
            _http_server = start_http(port)
        except OSError as e:
            _http_failed = True
            print("telemetry: could not bind metrics listener on port "
                  "%d: %s" % (port, e), file=sys.stderr)
            return None
        return _http_server


def jsonl_path():
    """Resolved JSONL stream path, or None when disabled."""
    try:
        from mxnet_tpu.config import flags
        if flags.telemetry_jsonl:
            return flags.telemetry_jsonl
        if flags.telemetry_dir:
            return os.path.join(flags.telemetry_dir, "telemetry.jsonl")
    except Exception:
        pass
    return None


class JsonlWriter:
    """Append-per-window snapshot stream. Opens/closes per write so the
    stream survives forks and supervised restarts without stale handles;
    at one write per 16 steps the syscall cost is noise."""

    def __init__(self, path):
        self.path = path
        self._lock = threading.Lock()
        self._warned = False

    def write(self, record):
        line = json.dumps(record, default=str)
        try:
            with self._lock:
                d = os.path.dirname(self.path)
                if d:
                    os.makedirs(d, exist_ok=True)
                with open(self.path, "a") as f:
                    f.write(line + "\n")
            return True
        except OSError as e:
            if not self._warned:
                self._warned = True
                print("telemetry: jsonl stream %s unwritable: %s"
                      % (self.path, e), file=sys.stderr)
            return False
