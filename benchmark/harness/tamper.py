"""The control: a server whose answers are altered where the clients
receive them.

The configuration states one guarantee a run can show — every answer
equals the plain recomputation — and no precision to step down from,
so the control breaks that guarantee: a proxy between the load
generator and the server alters every 5th answer (a count off by one,
a column dropped). `TamperedServer` stands in for `harness.server.Server`
(`cell.Server = TamperedServer`): set-up, warm-up, window and comparison
then run as in any run, and `correct` has to come out false.
`benchmark/control.py` runs it at a cell's own size on the chip;
`tests/test_cells_cpu.py` at a size a test can hold.
"""

from __future__ import annotations

import http.client
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from harness.server import Server

EVERY = 5


def alter(res):
    """One answer, made wrong the way a faulty kernel or merge would."""
    if isinstance(res, int):
        return res + 1
    if isinstance(res, list) and res:
        res[0]["count"] += 1
        return res
    if isinstance(res, dict) and "columns" in res:
        res["columns"] = res["columns"][1:] or [0]
        return res
    if isinstance(res, dict):
        res["count"] += 1
        return res
    return [{"id": 0, "count": 1}]      # an empty TopN or GroupBy


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        body = self.rfile.read(int(self.headers["Content-Length"]))
        conn = http.client.HTTPConnection("127.0.0.1", self.server.upstream)
        conn.request("POST", self.path, body,
                     {"Content-Type": self.headers["Content-Type"]})
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        with self.server.lock:
            self.server.seen += 1
            hit = self.server.seen % EVERY == 0
        if hit and resp.status == 200:
            (res,) = json.loads(data)["results"]
            data = json.dumps({"results": [alter(res)]}).encode()
            self.server.altered += 1
        self.send_response(resp.status)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *a):
        pass


class TamperedServer(Server):
    """The server as the harness starts it; the port the load generator
    is given leads through the altering proxy. The harness's own
    requests (`/info`, the load, the family queries) go direct."""

    altered = 0     # of the last one stopped, for the caller to read

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.proxy = ThreadingHTTPServer(("127.0.0.1", 0), _Handler)
        self.proxy.daemon_threads = True
        self.proxy.upstream = self.port
        self.proxy.lock = threading.Lock()
        self.proxy.seen = self.proxy.altered = 0
        self._thread = threading.Thread(target=self.proxy.serve_forever,
                                        daemon=True, name="bench-tamper")
        self._thread.start()
        self.port = self.proxy.server_address[1]

    def _close_proxy(self) -> None:
        if self._thread.is_alive():
            self.proxy.shutdown()
            self.proxy.server_close()
            self._thread.join(timeout=10)
            TamperedServer.altered = self.proxy.altered

    def stop(self, timeout_s: float = 120.0) -> int:
        self._close_proxy()
        return super().stop(timeout_s)

    def kill(self) -> None:
        self._close_proxy()
        super().kill()
