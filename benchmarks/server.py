"""Start, watch and stop the system under test, and read what it serves
about itself.  The benchmark's parent process never imports JAX: the child
holds the chip.  Launch, READY wait and the ``/health`` +
``/debug/compiles`` checks follow ``chip_smoke.py``."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROGRAM = "llama_fastapi_k8s_gpu_tpu"      # the system under test


class ServerFailed(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def log_tail(path: str, n: int = 2000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


class Server:
    """One server child on one GGUF file."""

    def __init__(self, model_path: str, env: dict, work: str):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.device_path = os.path.join(work, "device.json")
        self.log_path = os.path.join(work, "server.log")
        for p in (self.device_path, self.log_path):
            if os.path.exists(p):
                os.remove(p)
        full = dict(os.environ)
        full["PYTHONPATH"] = ROOT + os.pathsep + full.get("PYTHONPATH", "")
        full.setdefault("TPU_LOG_DIR", "disabled")
        full.update({
            "LFKT_MODEL_DIR": os.path.dirname(model_path),
            "LFKT_MODEL_NAME": os.path.basename(model_path),
            "LFKT_HOST": "127.0.0.1", "LFKT_PORT": str(self.port),
            # the compile cache: a fixed path inside the checkout, whatever
            # the machine's own environment says
            "JAX_COMPILATION_CACHE_DIR": os.path.join(ROOT, ".lfkt_xla_cache"),
            **env})
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "server_child.py"),
             self.device_path],
            cwd=ROOT, env=full, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    # -- http ---------------------------------------------------------------
    def get(self, path: str, timeout: float = 30.0):
        try:
            with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
                return r.status, r.read().decode("utf-8", "replace")
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode("utf-8", "replace")

    def get_json(self, path: str, timeout: float = 30.0) -> dict:
        status, text = self.get(path, timeout)
        if status != 200:
            raise ServerFailed(f"GET {path} answered {status}: {text[:200]}")
        return json.loads(text)

    # -- life cycle -----------------------------------------------------------
    def device(self) -> dict | None:
        try:
            with open(self.device_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def wait_ready(self, deadline: float, want_platform: str) -> dict:
        """Poll until ``/health`` is READY; returns the health document.
        Ends early when the child exits or reports another platform."""
        checked = False
        while True:
            if self.proc.poll() is not None:
                raise ServerFailed(
                    f"server exited {self.proc.returncode} before it was "
                    f"ready: {log_tail(self.log_path)}")
            if time.time() > deadline:
                raise ServerFailed("server not ready before the deadline: "
                                   + log_tail(self.log_path))
            if not checked:
                dev = self.device()
                if dev is not None:
                    checked = True
                    if dev["platform"] != want_platform:
                        raise ServerFailed(
                            f"JAX found platform {dev['platform']!r}, this "
                            f"cell runs on {want_platform!r} only")
            try:
                status, text = self.get("/health", timeout=5)
            except OSError:
                status = None
            if status == 200 and checked:
                doc = json.loads(text)
                if doc.get("state") == "READY":
                    return doc
            time.sleep(0.25)

    def stop(self, grace: float = 60.0) -> int | None:
        """SIGTERM, wait for the drain, SIGKILL the group if it outlasts
        ``grace``.  Returns the exit code."""
        if self.proc.poll() is None:
            for sig, wait in ((signal.SIGTERM, grace), (signal.SIGKILL, 10.0)):
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    self.proc.wait(timeout=wait)
                    break
                except subprocess.TimeoutExpired:
                    continue
        rc = self.proc.wait()
        try:                       # nothing of the group may outlive the run
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self._log.close()
        return rc


def parse_gauge(metrics_text: str, name: str) -> float | None:
    for line in metrics_text.splitlines():
        if line.startswith(name + " "):
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                return None
    return None


def total_compiles(compiles_doc: dict) -> int:
    return sum(int(p.get("compiles", 0))
               for p in compiles_doc.get("programs", []))
