"""store + worker + frontend as separate processes over TCP (copied from
``chip_smoke.py``'s serve phase, which ran on the chip in PR 21).

The caller never imports JAX: the worker is the only process on the chip, and
what it runs on is read from its own ``/health`` probe.
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

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class DeployFailed(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body=None, timeout=30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = None if body is None else json.dumps(body)
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        raw = r.read()
        return r.status, (json.loads(raw) if raw else None)
    finally:
        conn.close()


class Proc:
    """A started process with its log; always reaped by ``stop``."""

    def __init__(self, name: str, cmd: list, env: dict, logdir: str):
        self.name = name
        self.log_path = os.path.join(logdir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.t_start = time.monotonic()
        self.p = subprocess.Popen(cmd, env=env, stdout=self._log,
                                  stderr=subprocess.STDOUT, cwd=ROOT)

    def log_text(self) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def check_alive(self) -> None:
        rc = self.p.poll()
        if rc is not None:
            raise DeployFailed(
                f"{self.name} exited early (rc={rc}); log tail:\n"
                + self.log_text()[-4000:])

    def stop(self, grace: float = 15.0) -> None:
        if self.p.poll() is None:
            self.p.send_signal(signal.SIGTERM)
            try:
                self.p.wait(grace)
            except subprocess.TimeoutExpired:
                self.p.kill()
                self.p.wait()
        self._log.close()


def wait_until(what: str, fn, procs, timeout: float, every: float = 0.25):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        for p in procs:
            p.check_alive()
        try:
            got = fn()
            if got:
                return got
        except (OSError, ValueError, http.client.HTTPException) as e:
            last = e
        time.sleep(every)
    raise DeployFailed(f"timed out after {timeout}s waiting for {what} "
                       f"(last error: {last})")


class Deployment:
    """One store, one worker (through ``worker_launch``), one frontend."""

    def __init__(self, rundir: str, env: dict):
        self.rundir = rundir
        self.procs: list = []
        self.store_port = free_port()
        self.http_port = free_port()
        self.sys_port = free_port()
        self.env = dict(env)
        self.env["PYTHONPATH"] = ROOT + os.pathsep + self.env.get(
            "PYTHONPATH", "")
        self.env["PYTHONUNBUFFERED"] = "1"
        self.env["PYTHONHASHSEED"] = "0"
        # A deployment setting, not a program change: twice on the chip
        # (PR 23) the store expired the worker's and the frontend's leases in
        # the same instant (no keepalive seen for the 10 s TTL, during a
        # long first prefill) and the model vanished from the frontend.
        # Nothing here depends on fast failure detection.
        self.env["DYNTPU_LEASE_TTL_S"] = "600"
        self.stamps: dict = {}

    def _start(self, name: str, module: str, argv: list, extra_env=None):
        env = dict(self.env)
        env.update(extra_env or {})
        p = Proc(name, [sys.executable, "-m", module] + argv, env,
                 self.rundir)
        self.procs.append(p)
        return p

    def start_all(self, config_path: str, served: str, tok_path: str,
                  worker_env: dict, rehearse: bool) -> None:
        addr = f"127.0.0.1:{self.store_port}"
        self.store = self._start(
            "store", "dynamo_tpu.runtime.store",
            ["--host", "127.0.0.1", "--port", str(self.store_port)])

        def store_up():
            with socket.create_connection(("127.0.0.1", self.store_port), 1):
                return True
        wait_until("store", store_up, [self.store], 30, 0.05)
        wargs = ["--config", config_path, "--model-name", served,
                 "--tokenizer", tok_path, "--store-addr", addr]
        if rehearse:
            wargs.append("--rehearse")
        env = {"DYNTPU_SYSTEM_ENABLED": "1",
               "DYNTPU_SYSTEM_PORT": str(self.sys_port)}
        env.update(worker_env)
        self.worker = self._start(
            "worker", "benchmarks.chip.worker_launch", wargs, env)

    def wait_ready(self, served: str, timeout: float) -> dict:
        def ready():
            _, body = http_json(self.sys_port, "GET", "/health")
            return (body or {}).get("probes", {}).get("engine")
        rep = wait_until("worker ready", ready, self.procs, timeout, 0.5)
        self.stamps["worker_ready_s"] = time.monotonic() - self.worker.t_start
        # The frontend starts only now (as chip_smoke.py does).  Started
        # beside the worker it missed the model in 4 of 41 runs on the chip
        # (PR 23): ModelWatcher._handle_put gives up for good when the model
        # key's event arrives before the model card is readable, and a
        # frontend that finds both keys already there cannot hit that race.
        self.frontend = self._start(
            "frontend", "dynamo_tpu.frontend",
            ["--host", "127.0.0.1", "--port", str(self.http_port),
             "--store-addr", f"127.0.0.1:{self.store_port}",
             "--router-mode", "round_robin"])

        def listed():
            _, body = http_json(self.http_port, "GET", "/v1/models")
            return any(m.get("id") == served
                       for m in (body or {}).get("data", []))
        wait_until("model listed by frontend", listed, self.procs, 60)
        return rep

    def engine_probe(self) -> dict:
        _, body = http_json(self.sys_port, "GET", "/health")
        return body["probes"]["engine"]

    def check_alive(self) -> None:
        for p in self.procs:
            p.check_alive()

    def stop_all(self) -> None:
        for p in reversed(self.procs):
            p.stop()
