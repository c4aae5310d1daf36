"""apptest harness (reference apptest/: spawns real binaries on localhost,
drives them over HTTP with typed helpers). Provides an in-process vmsingle
fixture for speed plus a subprocess spawner for process-level tests."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.parse
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class VmSingleProc:
    """vmsingle in a subprocess (apptest/app.go analog) — thin wrapper over
    AppProc that self-allocates the HTTP port."""

    def __init__(self, data_path: str, port: int = 0, extra_flags=()):
        if port == 0:
            port = free_ports(1)[0]
        self.port = port
        self._app = AppProc(
            "vmsingle",
            [f"-storageDataPath={data_path}",
             f"-httpListenAddr=127.0.0.1:{port}", *extra_flags],
            port, "vmsingle")
        self.proc = self._app.proc

    def stop(self):
        self._app.stop()


class Client:
    """HTTP driver (apptest/client.go analog)."""

    def __init__(self, port: int, host="127.0.0.1"):
        self.base = f"http://{host}:{port}"

    def get(self, path: str, **params) -> tuple[int, bytes]:
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params, doseq=True)
        try:
            with urllib.request.urlopen(url, timeout=30) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def post(self, path: str, body: bytes = b"", headers=None, **params
             ) -> tuple[int, bytes]:
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params, doseq=True)
        req = urllib.request.Request(url, data=body, method="POST",
                                     headers=headers or {})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    # typed helpers (apptest/model.go analog)

    def query_range(self, query: str, start, end, step) -> dict:
        code, body = self.get("/api/v1/query_range", query=query,
                              start=start, end=end, step=step)
        assert code == 200, body
        return json.loads(body)

    def query(self, query: str, time_s=None) -> dict:
        params = {"query": query}
        if time_s is not None:
            params["time"] = time_s
        code, body = self.get("/api/v1/query", **params)
        assert code == 200, body
        return json.loads(body)

    def force_flush(self):
        code, _ = self.get("/internal/force_flush")
        assert code == 200


class AppProc:
    """Any apps/* module in a subprocess (cluster apptest processes).
    `env` adds/overrides environment variables for the child (chaos
    tests use it for VM_FAULTS / VM_TENANT_QUOTAS / RPC knobs)."""

    def __init__(self, module: str, flags: list, health_port: int,
                 name: str = "", env: dict | None = None):
        env_overrides = env
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        env.setdefault("JAX_PLATFORMS", "cpu")
        if env_overrides:
            env.update(env_overrides)
        self.name = name or module
        self.port = health_port
        self.proc = subprocess.Popen(
            [sys.executable, "-m", f"victoriametrics_tpu.apps.{module}",
             *flags],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        self._wait_ready()

    def _wait_ready(self, timeout=30):
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{self.port}/health", timeout=1):
                    return
            except OSError:
                if self.proc.poll() is not None:
                    out = self.proc.stdout.read().decode()
                    raise RuntimeError(f"{self.name} died:\n{out}")
                time.sleep(0.1)
        raise TimeoutError(f"{self.name} did not become ready")

    def stop(self, kill=False):
        if kill:
            self.proc.kill()
        else:
            self.proc.terminate()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()


def free_ports(n: int) -> list:
    import socket
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def tree_matrix_body(grid_s, series, head: dict, trace=None) -> bytes:
    """A ``query_range`` body the way it was made before the native
    matrix writer, kept as the oracle of its tests: a Python list a
    point, a dict a row, one ``json.dumps`` over the whole tree."""
    import math

    from victoriametrics_tpu.query.format_value import fmt_value
    result = []
    for r in series:
        vals = [[float(t), fmt_value(v)]
                for t, v in zip(grid_s, r.values)
                if not math.isnan(v)]
        if vals:
            result.append({"metric": r.metric_name.to_dict(),
                           "values": vals})
    body = dict(head, data={"resultType": "matrix", "result": result})
    if trace is not None:
        body["trace"] = trace
    return json.dumps(body).encode()
