"""Process plumbing of a cell: engine and router as two OS processes, their
health, their documents (`/metrics`, `/debug/*`) and their orderly stop.

Copied from `chip_smoke.py` (Child, health wait, SIGTERM, the `/metrics`
parser), which stays where it is; the flags come from the configuration
file, not from the smoke. Nothing here imports JAX: the engine holds the
chip, and this process must never touch it.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(method: str, url: str, body: dict | None = None,
              timeout: float = 120.0) -> dict:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise RuntimeError(
            f"{method} {url} -> HTTP {e.code}: {e.read()[:400]!r}"
        ) from e


def log_tail(path: str, n: int = 2000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError as e:
        return f"<no log: {e}>"


class Child:
    """One OS process of the stack, its log, and its orderly stop."""

    def __init__(self, name: str, argv: list[str], out_dir: str,
                 env: dict | None = None):
        self.name = name
        self.log_path = os.path.join(out_dir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, *argv], cwd=REPO, env=env,
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def wait_healthy(self, url: str, timeout_s: float) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited rc={self.proc.returncode} before "
                    f"healthy: {log_tail(self.log_path)}"
                )
            try:
                with urllib.request.urlopen(url + "/health", timeout=2) as r:
                    if r.status == 200:
                        return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass  # not listening yet; the loop's deadline decides
            time.sleep(0.5)
        raise TimeoutError(
            f"{self.name} not healthy after {timeout_s:.0f}s: "
            f"{log_tail(self.log_path)}"
        )

    def stop(self, grace_s: float = 60.0) -> int | None:
        """SIGTERM, wait; SIGKILL a process that ignores it. Returns the
        exit code; nothing outlives the run either way."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()
        return self.proc.returncode


_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(?:\{(?:[^"{}]|"(?:[^"\\]|\\.)*")*\})?)'
    r' (\S+)(?: -?[0-9]+)?$'
)


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text exposition -> {series-with-labels: value}; a line
    that is neither a comment nor a sample raises."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if m is None:
            raise ValueError(f"unparseable metrics line: {line[:200]!r}")
        out[m.group(1)] = float(m.group(2))
    return out


def metric_sum(samples: dict[str, float], name: str) -> float:
    """Sum of every series of one metric family (all label sets)."""
    return sum(v for k, v in samples.items()
               if k == name or k.startswith(name + "{"))


def engine_argv(config: dict, seed: int, port: int) -> list[str]:
    """The engine's command line: the configuration file's `engine_flags`,
    verbatim, plus the seed and the port. The compile cache directory comes
    through the environment run.py gives the child (the program's own rule,
    utils/compile_cache.py, takes JAX_COMPILATION_CACHE_DIR first)."""
    return [
        "-m", "vllm_production_stack_tpu.engine.server",
        "--host", "127.0.0.1", "--port", str(port),
        "--seed", str(seed % (2 ** 31)),
        *[str(f) for f in config["engine_flags"]],
    ]


def router_argv(config: dict, engine_url: str, port: int) -> list[str]:
    return [
        "-m", "vllm_production_stack_tpu.router.app",
        "--host", "127.0.0.1", "--port", str(port),
        "--service-discovery", "static",
        "--static-backends", engine_url,
        "--static-models", config["model"],
    ]
