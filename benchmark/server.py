"""The system under test, started as a user starts it:

    python -m localai_tpu run --models-path <dir> --address 127.0.0.1:<port>

with a model YAML that points at the checkpoint the benchmark made. The
process tree is the program's own (HTTP server -> model manager -> spawned
runner -> Engine); this module only writes the YAML, waits, reads the debug
endpoints, and stops everything again. It never imports jax.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

MODEL = "bench"


def write_model_yaml(models_dir: str, ckpt_dir: str, serving: dict) -> None:
    """``serving`` is the configuration file's block of model-YAML fields;
    whatever it leaves out stays at the program's default."""
    lines = [f"name: {MODEL}", "backend: tpu-llm", "parameters:",
             f"  model: {ckpt_dir}"]
    for key, val in serving.items():
        lines.append(f"{key}: {json.dumps(val)}")
    lines += ["template:", '  completion: "{{ Input }}"',
              '  chat_message: "{{ Content }}"', '  chat: "{{ Input }}"', ""]
    with open(os.path.join(models_dir, MODEL + ".yaml"), "w") as f:
        f.write("\n".join(lines))


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


class Server:
    def __init__(self, root: str, models_dir: str, env: dict, log_path: str):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "localai_tpu", "run", "--models-path",
             models_dir, "--address", f"127.0.0.1:{self.port}"],
            cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    def get(self, path: str, timeout: float = 60.0):
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return json.load(r)

    def wait_ready(self, deadline: float) -> None:
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(f"server exited {self.proc.returncode}")
            try:
                with urllib.request.urlopen(self.base + "/readyz", timeout=2):
                    return
            except OSError:
                if time.monotonic() > deadline:
                    raise TimeoutError("server never became ready") from None
                time.sleep(0.2)

    def model_state(self) -> dict:
        """The runner's own report; /debug/state leaves a model out when
        its runner did not answer within the route's 5 s, so ask again."""
        for _ in range(6):
            st = self.get("/debug/state")["models"].get(MODEL)
            if st is not None:
                return st
            time.sleep(1.0)
        raise RuntimeError("the runner does not answer /debug/state")

    def log_tail(self, n: int = 60) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def stop(self) -> None:
        """Stop the server and everything below it, and wait until it is
        gone: an orphaned runner keeps the chip."""
        family = descendants(self.proc.pid)
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        t_kill = time.monotonic() + 5
        while True:
            alive = [p for p in family
                     if (_proc_stat(p) or ("Z",))[0] != "Z"]
            if not alive:
                return
            if time.monotonic() > t_kill + 15:
                raise RuntimeError(f"could not stop processes {alive}")
            for p in alive:
                try:
                    os.kill(p, signal.SIGKILL if time.monotonic() > t_kill
                            else signal.SIGTERM)
                except ProcessLookupError:
                    pass
            time.sleep(0.2)


def _proc_stat(pid):
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return fields[0], int(fields[1])
    except (OSError, ValueError, IndexError):
        return None


def descendants(pid: int) -> list:
    """Every process below ``pid`` (backends run in their own sessions, so
    a process group does not reach them)."""
    kids = {}
    for d in filter(str.isdigit, os.listdir("/proc")):
        st = _proc_stat(d)
        if st:
            kids.setdefault(st[1], []).append(int(d))
    out, todo = [], [pid]
    while todo:
        for k in kids.get(todo.pop(), []):
            out.append(k)
            todo.append(k)
    return out
