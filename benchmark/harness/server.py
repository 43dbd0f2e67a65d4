"""One server child, started through the program's normal entry point.

The pattern is `chip_smoke.Server`'s (PR 21): this parent never imports
jax, the child is the only process that touches the device, and a child
that exits before it answers (no chip under `--platform tpu`, or no
program in the checkout) fails the run.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

HTTP_TIMEOUT_S = 900  # a cold query waits for its compile


class BenchFailure(Exception):
    """The run cannot give a result; exit non-zero, print no result."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Client:
    """One keep-alive connection, as a client library holds."""

    def __init__(self, port: int, timeout: float = HTTP_TIMEOUT_S):
        self.port = port
        self.timeout = timeout
        self.conn = None

    def request(self, method: str, path: str, body: bytes = None,
                ctype: str = "application/json") -> tuple:
        """(status, body bytes); reconnects once on a dropped
        keep-alive connection."""
        headers = {"Content-Type": ctype} if body is not None else {}
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
            try:
                self.conn.request(method, path, body, headers)
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except (http.client.HTTPException, ConnectionError,
                    BrokenPipeError, socket.timeout):
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Server:
    """`python -m pilosa_tpu.cli server` (or, for a traced run, the
    benchmark's launcher around the same `cmd_server`)."""

    def __init__(self, checkout: str, data_dir: str, platform: str,
                 config_toml: str, log_path: str, env: dict,
                 launcher: list = None):
        self.port = free_port()
        self.log_path = log_path
        entry = launcher or ["-m", "pilosa_tpu.cli"]
        self._log = open(log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, *entry, "server", "-d", data_dir,
             "-b", f"127.0.0.1:{self.port}", "-c", config_toml,
             "--platform", platform],
            cwd=checkout, env=env, stdout=self._log, stderr=self._log)
        self.client = Client(self.port)

    def wait_ready(self, timeout_s: float = 600.0) -> dict:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited {self.proc.returncode} before "
                    f"answering; log tail:\n{self.log_tail()}")
            try:
                return self.get("/info")
            except (OSError, http.client.HTTPException):
                time.sleep(0.1)
        raise BenchFailure(f"server not ready after {timeout_s:.0f}s")

    def request(self, method: str, path: str, body: bytes = None,
                ctype: str = "application/json") -> dict:
        status, data = self.client.request(method, path, body, ctype)
        if status != 200:
            raise BenchFailure(f"{method} {path} -> {status}: "
                               f"{data[:500]!r}")
        return json.loads(data)

    def get(self, path: str) -> dict:
        return self.request("GET", path)

    def post_json(self, path: str, obj: dict) -> dict:
        return self.request("POST", path, json.dumps(obj).encode())

    def query(self, index: str, pql: str):
        (res,) = self.request("POST", f"/index/{index}/query",
                              pql.encode(), "text/plain")["results"]
        return res

    def snapshot(self) -> dict:
        """The program's counters at one instant, as the readers take
        them: /internal/health, /debug/vars and /info."""
        return {"health": self.get("/internal/health"),
                "vars": self.get("/debug/vars"),
                "info": self.get("/info"),
                "time": time.time()}

    def log_tail(self, n: int = 30) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(
                "utf-8", "replace")

    def stop(self, timeout_s: float = 120.0) -> int:
        """SIGTERM, wait for the graceful drain (the holder flushes, a
        traced launcher writes its trace), return the exit code."""
        self.client.close()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.kill()
                raise BenchFailure(
                    f"server ignored SIGTERM for {timeout_s:.0f}s")
        self._log.close()
        return self.proc.returncode

    def kill(self) -> None:
        self.client.close()
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if not self._log.closed:
            self._log.close()


def server_env(cache_dir: str) -> dict:
    """The child's environment: the caller's, with the compile cache
    placed inside the checkout (the program takes
    JAX_COMPILATION_CACHE_DIR and names no other directory), so that
    two checkouts on one machine share nothing and the harness prunes
    only what is its own. No PILOSA_TPU_* switch is set."""
    return dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir)
