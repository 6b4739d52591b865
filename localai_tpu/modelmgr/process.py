"""Backend process management: spawn, log-tail, terminate.

Parity with the reference's process manager (reference: pkg/model/
process.go:73-137 — chmod+exec with --addr, stdout/stderr tailed into the
core logs, SIGTERM cleanup), re-based on subprocess + threads.
"""

from __future__ import annotations

import collections
import logging
import os
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Optional

log = logging.getLogger("localai_tpu.modelmgr.process")

# the directory that holds the localai_tpu package: put on every spawned
# backend's PYTHONPATH so `python -m localai_tpu.backend.*` imports
# whatever directory the server was started from
PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class BackendProcess:
    """A spawned backend speaking the contract on 127.0.0.1:port."""

    def __init__(self, command: list, addr: str, env: Optional[dict] = None,
                 name: str = ""):
        self.command = command
        self.addr = addr
        self.name = name or os.path.basename(command[0])
        self.proc: Optional[subprocess.Popen] = None
        self._env = env
        self._tail_threads: list = []
        # readiness/failure markers observed in the log tail: the spawn
        # retry uses bind_failed to detect losing the free_port() -> bind
        # race ("address already in use", raised by make_server)
        self.started = threading.Event()
        self.bind_failed = threading.Event()
        # the child's last stderr lines: its output is logged at DEBUG,
        # so when it dies this is what the error must carry
        self._stderr_tail: collections.deque = collections.deque(maxlen=30)

    def stderr_tail(self) -> str:
        """The last lines the backend wrote to stderr (drains the tail
        readers first when the process is already dead)."""
        if not self.alive():
            for t in self._tail_threads:
                t.join(timeout=1.0)
        return "\n".join(self._stderr_tail)

    def start(self):
        env = dict(os.environ)
        if self._env:
            env.update(self._env)
        env["PYTHONPATH"] = os.pathsep.join(
            [PACKAGE_ROOT] + [p for p in env.get("PYTHONPATH", "").split(
                os.pathsep) if p and p != PACKAGE_ROOT])
        log.info("starting backend %s: %s (addr %s)", self.name,
                 shlex.join(self.command), self.addr)
        self.proc = subprocess.Popen(
            self.command,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            env=env,
            start_new_session=True,  # own process group for clean kill
        )
        for stream in (self.proc.stdout, self.proc.stderr):
            t = threading.Thread(target=self._tail, args=(stream,), daemon=True)
            t.start()
            self._tail_threads.append(t)

    def _tail(self, stream):
        keep = stream is self.proc.stderr
        try:
            for line in iter(stream.readline, b""):
                text = line.decode(errors="replace").rstrip()
                if "gRPC Server listening at" in text:
                    self.started.set()
                elif "address already in use" in text.lower():
                    self.bind_failed.set()
                if keep:
                    self._stderr_tail.append(text)
                log.debug("[%s] %s", self.name, text)
        except ValueError:
            pass  # stream closed

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def stop(self, grace_s: float = 10.0):
        if not self.proc:
            return
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
            deadline = time.monotonic() + grace_s
            while time.monotonic() < deadline and self.proc.poll() is None:
                time.sleep(0.1)
            if self.proc.poll() is None:
                try:
                    os.killpg(self.proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        # drain the tails before closing the pipes (ISSUE 7 satellite):
        # the readers see EOF once the process is dead, so this is
        # bounded — closing first silently dropped the final log lines
        for t in self._tail_threads:
            t.join(timeout=5.0)
        self._tail_threads = []
        for s in (self.proc.stdout, self.proc.stderr):
            try:
                s.close()
            except Exception:
                pass


def spawn_python_backend(module: str, extra_args: Optional[list] = None,
                         env: Optional[dict] = None, name: str = "",
                         bind_race_wait_s: float = 2.0) -> BackendProcess:
    """Spawn `python -m <module> --addr 127.0.0.1:<freeport>`.

    free_port() closes its probe socket before the backend binds, so
    another process can steal the port in between (ISSUE 7 satellite):
    if the child dies with "address already in use" in its tail, retry
    ONCE with a fresh port. Deliberately one retry — a second loss in a
    row means something is systematically wrong with the port space.
    """
    for attempt in (0, 1):
        port = free_port()
        addr = f"127.0.0.1:{port}"
        cmd = [sys.executable, "-m", module, "--addr", addr] + (extra_args or [])
        bp = BackendProcess(cmd, addr, env=env, name=name or module)
        bp.start()
        if attempt == 1:
            return bp
        # watch briefly for the bind race losing; a slow import simply
        # exhausts the window and proceeds to the caller's health poll
        deadline = time.monotonic() + bind_race_wait_s
        while time.monotonic() < deadline:
            if bp.started.is_set() or bp.bind_failed.is_set() \
                    or not bp.alive():
                break
            time.sleep(0.02)
        if not bp.alive():
            # the tail may stamp bind_failed slightly after poll() flips:
            # give the reader threads a moment to drain the death message
            for t in bp._tail_threads:
                t.join(timeout=1.0)
        if not bp.bind_failed.is_set():
            return bp
        log.warning("backend %s lost the %s bind race; retrying on a "
                    "fresh port", bp.name, addr)
        bp.stop(grace_s=0.0)
    return bp  # unreachable; satisfies the type checker
