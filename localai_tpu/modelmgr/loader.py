"""Model lifecycle: load/route/shutdown backends per model.

Parity with the reference's ModelLoader (reference: pkg/model/loader.go:22-28
model map keyed by modelID; initializers.go:457 BackendLoader, :502
GreedyLoader ordered autodetect, :402-423 health-check poll loop,
loader.go:143-168 busy-aware shutdown, loader.go:170-206 CheckIsLoaded
zombie cleanup; external backends initializers.go:336-360).

TPU re-design: backends are Python modules spawned as gRPC subprocesses
(or in-process servers for tests/embedded use). Capability probing is not
CPU-flag selection (AVX/CUDA variants) but device platform: one engine
binary serves any TPU/CPU host because XLA owns code generation.
"""

from __future__ import annotations

import logging
import random
import threading
import time
from typing import Callable, Optional

from localai_tpu.backend import contract_pb2 as pb
from localai_tpu.backend.service import BackendClient, BackendServicer, make_server
from localai_tpu.modelmgr.process import BackendProcess, free_port, spawn_python_backend
from localai_tpu.services.errors import CircuitOpenError
from localai_tpu.services.eventlog import EVENTS

log = logging.getLogger("localai_tpu.modelmgr.loader")


class CircuitBreaker:
    """Per-model load circuit breaker (ISSUE 7 crash recovery): after
    ``threshold`` CONSECUTIVE spawn/LoadModel failures the breaker opens
    and load attempts fail fast with CircuitOpenError (HTTP 503 with the
    breaker state in the body) for ``cooldown_s`` — a crash-looping
    checkpoint must not burn a spawn + multi-second weight load per
    request. After the cooldown one probe attempt is let through
    (half-open); its outcome closes or re-opens the breaker."""

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 name: str = ""):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self.name = name            # model id, for event-log records
        self.failures = 0
        self.state = "closed"       # closed | open | half-open
        self.opened_t = 0.0
        self._lock = threading.Lock()

    def check(self, model_id: str):
        """Raise CircuitOpenError if open; transition to half-open when
        the cooldown has elapsed (that caller becomes the probe)."""
        with self._lock:
            if self.state != "open":
                return
            remaining = self.cooldown_s - (time.monotonic() - self.opened_t)
            if remaining <= 0:
                self.state = "half-open"
                EVENTS.emit("circuit_half_open", model=self.name or model_id)
                return
            # breaker-state dict built inline: snapshot() takes this same
            # non-reentrant lock
            raise CircuitOpenError(
                f"circuit open for model {model_id}: {self.failures} "
                f"consecutive load failures; retry in {remaining:.1f}s",
                retry_after_s=max(1.0, remaining),
                detail={"breaker": {
                    "state": "open", "failures": self.failures,
                    "cooldown_s": self.cooldown_s,
                    "retry_after_s": round(remaining, 1)}})

    def record_failure(self):
        opened = False
        with self._lock:
            self.failures += 1
            if self.state == "half-open" or self.failures >= self.threshold:
                opened = self.state != "open"
                self.state = "open"
                self.opened_t = time.monotonic()
            n = self.failures
        if opened:
            EVENTS.emit("circuit_open", model=self.name, failures=n,
                        cooldown_s=self.cooldown_s)

    def record_success(self):
        with self._lock:
            closed = self.state != "closed"
            self.failures = 0
            self.state = "closed"
        if closed:
            EVENTS.emit("circuit_close", model=self.name)

    def snapshot(self) -> dict:
        with self._lock:
            remaining = 0.0
            if self.state == "open":
                remaining = max(0.0, self.cooldown_s
                                - (time.monotonic() - self.opened_t))
            return {"state": self.state, "failures": self.failures,
                    "cooldown_s": self.cooldown_s,
                    "retry_after_s": round(remaining, 1)}

# ordered by priority, mirroring the reference's autoload order
# (initializers.go:33-57): the main engine first, specialized after.
KNOWN_BACKENDS: dict = {
    "tpu-llm": "localai_tpu.backend.runner",
    "tpu-embeddings": "localai_tpu.backend.embed_runner",
    "tpu-rerank": "localai_tpu.backend.rerank_runner",
    "tpu-diffusion": "localai_tpu.backend.diffusion_runner",
    "tpu-whisper": "localai_tpu.backend.whisper_runner",
    "tpu-tts": "localai_tpu.backend.tts_runner",
    "local-store": "localai_tpu.backend.store_backend",
    "fake": "localai_tpu.backend.fake",
    # remote HF Inference API passthrough (reference:
    # backend/go/llm/langchain — lowest greedy priority)
    "langchain-huggingface": "localai_tpu.backend.remote_runner",
}
GREEDY_ORDER = ["tpu-llm", "langchain-huggingface"]


class LoadedModel:
    def __init__(self, model_id: str, backend_name: str, client: BackendClient,
                 process: Optional[BackendProcess] = None, server=None):
        self.model_id = model_id
        self.backend_name = backend_name
        self.client = client
        self.process = process
        self.server = server  # in-process grpc server (embedded backends)
        self.last_used = time.monotonic()
        self.busy = 0
        self.health_fails = 0     # consecutive failed idle health probes
        self.first_fail_t = 0.0   # when the current failure streak began
        self.watchdog = None  # set by ModelLoader when a watchdog is attached
        # set before close() so the supervisor thread can tell an
        # operator-requested shutdown from a crash it must respawn
        self.intentional_stop = False
        self.supervisor: Optional[threading.Thread] = None
        # cross-process clock (ISSUE 12): the offset that shifts backend
        # trace timestamps onto the frontend timeline. A backend this
        # manager started on this machine shares its wall clock: offset
        # 0. A remote one is measured over Health round trips after the
        # load (measure_clock). {} when the backend sent no handshake
        # (e.g. FakeServicer's plain "loaded") — merge then falls back
        # to raw epochs. Re-measured automatically on respawn because
        # every spawn goes through _spawn_and_load.
        self.clock: dict = {}
        self._lock = threading.Lock()

    def mark_busy(self):
        with self._lock:
            self.busy += 1
            self.last_used = time.monotonic()
        if self.watchdog is not None:
            self.watchdog.mark(self.model_id, True)

    def mark_idle(self):
        with self._lock:
            self.busy = max(0, self.busy - 1)
            idle = self.busy == 0
            self.last_used = time.monotonic()
            # a completed request is the strongest health signal there is
            self.health_fails = 0
        if idle and self.watchdog is not None:
            self.watchdog.mark(self.model_id, False)

    def close(self):
        self.intentional_stop = True
        try:
            self.client.close()
        except Exception:
            pass
        if self.server is not None:
            self.server.stop(grace=1)
        if self.process is not None:
            self.process.stop()


class ModelLoader:
    def __init__(self, health_attempts: int = 600, health_interval_s: float = 0.5,
                 single_active: bool = False,
                 breaker_threshold: int = 3, breaker_cooldown_s: float = 30.0,
                 respawn_backoff_base_s: float = 0.5,
                 respawn_backoff_cap_s: float = 15.0,
                 respawn_max_attempts: int = 5):
        self.models: dict[str, LoadedModel] = {}
        self._lock = threading.Lock()           # guards the dicts only
        self._load_locks: dict[str, threading.Lock] = {}  # serialize per-model loads
        self.health_attempts = health_attempts
        self.health_interval_s = health_interval_s
        self.single_active = single_active
        self.external_backends: dict[str, str] = {}   # name -> module or host:port
        self.embedded: dict[str, Callable[[], BackendServicer]] = {}
        self.watchdog = None
        # crash recovery (ISSUE 7): per-model circuit breakers, supervisor
        # respawn backoff, and respawn telemetry for /metrics
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_s = breaker_cooldown_s
        self.respawn_backoff_base_s = respawn_backoff_base_s
        self.respawn_backoff_cap_s = respawn_backoff_cap_s
        self.respawn_max_attempts = respawn_max_attempts
        self._breakers: dict[str, CircuitBreaker] = {}
        self.respawns: dict[str, int] = {}
        self._closed = False

    # ---- registration ----

    def register_external(self, name: str, target: str):
        """target: python module path or 'host:port' (reference:
        EXTERNAL_GRPC_BACKENDS semantics, initializers.go:336-360)."""
        self.external_backends[name] = target

    def register_embedded(self, name: str, factory: Callable[[], BackendServicer]):
        """In-process backend (reference: pkg/grpc/embed.go Provide)."""
        self.embedded[name] = factory

    # ---- loading ----

    def backend_loader(self, backend_name: str, model_id: str,
                       model_opts: pb.ModelOptions) -> LoadedModel:
        # per-model serialization; the global lock is only held for dict ops
        # so a multi-minute weight load never blocks other models' lookups
        with self._lock:
            load_lock = self._load_locks.setdefault(model_id, threading.Lock())
        with load_lock:
            with self._lock:
                lm = self.models.get(model_id)
            if lm is not None:
                # a BUSY backend is alive by definition (requests are
                # streaming through it) — probing it with a short-timeout
                # health RPC under load is how r4's bench watched the
                # loader KILL a healthy, saturated backend mid-serving
                # (the gRPC thread can answer slowly when the host core
                # is contended). Idle backends are probed, but a single
                # failed/timed-out probe must NOT kill a live process
                # either (same failure mode, observed in a busy==0 gap):
                # respawn only when the process is actually dead or three
                # consecutive probes failed. A truly wedged-but-alive
                # backend is the watchdog's job (busy-too-long kills).
                dead = lm.process is not None and not lm.process.alive()
                now = time.monotonic()
                if not dead and lm.busy > 0:
                    lm.last_used = now
                    return lm
                if not dead and self._healthy(lm):
                    lm.health_fails = 0
                    lm.last_used = now
                    return lm
                if lm.health_fails == 0:
                    lm.first_fail_t = now
                lm.health_fails += 1
                # back-to-back probes inside one transient stall must not
                # exhaust the strikes: require >= 3 failures SPREAD over
                # >= 30s before replacing a live process
                if not dead and (lm.health_fails < 3
                                 or now - lm.first_fail_t < 30.0):
                    log.warning("model %s health probe failed (%d); "
                                "keeping the live backend", model_id,
                                lm.health_fails)
                    lm.last_used = now
                    return lm
                log.warning("model %s backend %s; respawning", model_id,
                            "process died" if dead else
                            "unhealthy repeatedly")
                self._drop(model_id)
            if self.single_active:
                # pop victims under the lock, close OUTSIDE it: close()
                # can block up to 10 s in the process-stop grace, and
                # holding the global lock through it stalls every other
                # loader operation (ISSUE 7 satellite)
                with self._lock:
                    victims = [self._pop_locked(m)
                               for m, o in list(self.models.items())
                               if m != model_id and o.busy == 0]
                for v in victims:
                    self._close_lm(v)
            # circuit breaker: a crash-looping model fails fast here with
            # the breaker state instead of burning another spawn + load
            breaker = self._breaker(model_id)
            breaker.check(model_id)
            try:
                lm = self._spawn_and_load(backend_name, model_id, model_opts)
            except Exception:
                breaker.record_failure()
                raise
            breaker.record_success()
            with self._lock:
                self.models[model_id] = lm
            self._start_supervisor(lm, backend_name, model_opts)
            return lm

    def _breaker(self, model_id: str) -> CircuitBreaker:
        with self._lock:
            b = self._breakers.get(model_id)
            if b is None:
                b = self._breakers[model_id] = CircuitBreaker(
                    self.breaker_threshold, self.breaker_cooldown_s,
                    name=model_id)
            return b

    # ---- crash recovery (ISSUE 7) ----

    def _start_supervisor(self, lm: LoadedModel, backend_name: str,
                          model_opts: pb.ModelOptions):
        """Waiter thread on the backend process: detects death the moment
        the kernel reaps it (no polling interval) and respawns with
        exponential backoff + jitter. In-flight streams fail immediately
        at the gRPC layer (UNAVAILABLE -> structured retryable error via
        services/errors.py); this thread restores capacity for the NEXT
        request."""
        if lm.process is None:
            return
        t = threading.Thread(
            target=self._supervise, args=(lm, backend_name, model_opts),
            name=f"supervise-{lm.model_id}", daemon=True)
        lm.supervisor = t
        t.start()

    def _supervise(self, lm: LoadedModel, backend_name: str,
                   model_opts: pb.ModelOptions):
        rc = lm.process.proc.wait()
        if lm.intentional_stop or self._closed:
            return
        with self._lock:
            if self.models.get(lm.model_id) is not lm:
                return  # already replaced/dropped by another path
            self.respawns[lm.model_id] = self.respawns.get(lm.model_id, 0) + 1
            n_respawns = self.respawns[lm.model_id]
        log.warning(
            "backend for model %s died unexpectedly (exit %s); "
            "respawning with backoff. Its last stderr:\n%s",
            lm.model_id, rc, lm.process.stderr_tail())
        EVENTS.emit("respawn", model=lm.model_id, exit_code=rc,
                    respawns=n_respawns)
        base = self.respawn_backoff_base_s
        for attempt in range(self.respawn_max_attempts):
            # full jitter: crash-looping fleets must not thunder in sync
            delay = min(self.respawn_backoff_cap_s,
                        base * (2 ** attempt)) * (0.5 + random.random())
            time.sleep(delay)
            if self._closed or lm.intentional_stop:
                return
            try:
                # backend_loader sees the dead process and replaces it;
                # the breaker counts consecutive failures for us
                self.backend_loader(backend_name, lm.model_id, model_opts)
                return
            except CircuitOpenError:
                return  # breaker open: stop burning spawns; loads re-probe
            except Exception as e:
                log.warning("respawn attempt %d/%d for model %s failed: %s",
                            attempt + 1, self.respawn_max_attempts,
                            lm.model_id, e)
        log.error("model %s: giving up after %d respawn attempts",
                  lm.model_id, self.respawn_max_attempts)

    def stats(self) -> dict:
        """Per-model recovery telemetry for /readyz and /metrics:
        {model: {respawns, breaker, circuit_state}} with circuit_state
        encoded 0=closed 1=open 2=half-open (Prometheus gauge)."""
        with self._lock:
            names = set(self.models) | set(self._breakers) | set(self.respawns)
            breakers = dict(self._breakers)
            respawns = dict(self.respawns)
        out = {}
        code = {"closed": 0, "open": 1, "half-open": 2}
        for name in names:
            b = breakers.get(name)
            snap = b.snapshot() if b is not None else {
                "state": "closed", "failures": 0,
                "cooldown_s": self.breaker_cooldown_s, "retry_after_s": 0.0}
            out[name] = {"respawns": respawns.get(name, 0),
                         "breaker": snap,
                         "circuit_state": code.get(snap["state"], 0)}
        return out

    def greedy_loader(self, model_id: str, model_opts: pb.ModelOptions,
                      order: Optional[list] = None) -> LoadedModel:
        """Try backends in priority order (reference: GreedyLoader
        initializers.go:502)."""
        errors = []
        for name in order or GREEDY_ORDER:
            try:
                return self.backend_loader(name, model_id, model_opts)
            except CircuitOpenError:
                # breaker open is per-MODEL, not per-backend: trying the
                # next backend would re-raise from the same breaker; the
                # whole point is a fast 503 with the breaker state
                raise
            except Exception as e:
                errors.append(f"{name}: {e}")
        raise RuntimeError("could not load model with any backend: " + "; ".join(errors))

    def _spawn_and_load(self, backend_name: str, model_id: str,
                        model_opts: pb.ModelOptions) -> LoadedModel:
        client, process, server = self._connect_backend(backend_name)
        try:
            self._wait_healthy(client, process)
            res = client.load_model(model_opts)
            if not res.success:
                raise RuntimeError(f"LoadModel failed: {res.message}")
        except Exception as e:
            client.close()
            if server is not None:
                server.stop(grace=0)
            if process is None:
                raise
            died = not process.alive()
            process.stop()
            if died:
                # the child is gone (the chip was taken, a kernel failed
                # to compile, an import broke): its output is logged at
                # DEBUG, so the error itself must say why
                raise RuntimeError(
                    f"{e} (exit {process.proc.returncode}); the backend's "
                    f"last stderr:\n{process.stderr_tail()}") from e
            raise
        lm = LoadedModel(model_id, backend_name, client, process, server)
        lm.clock = _parse_handshake(res.message)
        if lm.clock:
            local = process is not None or server is not None
            lm.clock.update(measure_clock(
                None if local else client.health_clock))
        lm.watchdog = self.watchdog
        if self.watchdog is not None:
            self.watchdog.add(model_id, lm)
        return lm

    def _connect_backend(self, backend_name: str):
        """Returns (client, process|None, inproc_server|None)."""
        if backend_name in self.embedded:
            addr = f"127.0.0.1:{free_port()}"
            server = make_server(self.embedded[backend_name](), addr)
            server.start()
            return BackendClient(addr), None, server
        target = self.external_backends.get(backend_name)
        if target and _looks_like_addr(target):
            return BackendClient(target), None, None
        module = target or KNOWN_BACKENDS.get(backend_name)
        if module is None:
            raise ValueError(f"unknown backend: {backend_name}")
        process = spawn_python_backend(module, name=backend_name)
        return BackendClient(process.addr), process, None

    def _wait_healthy(self, client: BackendClient, process: Optional[BackendProcess]):
        for _ in range(self.health_attempts):
            if process is not None and not process.alive():
                raise RuntimeError("backend process died during startup")
            if client.health(timeout=1.0):
                return
            time.sleep(self.health_interval_s)
        raise TimeoutError("backend did not become healthy")

    def _healthy(self, lm: LoadedModel) -> bool:
        if lm.process is not None and not lm.process.alive():
            return False
        return lm.client.health(timeout=5.0)

    # ---- queries ----

    def get(self, model_id: str) -> Optional[LoadedModel]:
        with self._lock:
            return self.models.get(model_id)

    def list_loaded(self) -> list:
        with self._lock:
            return list(self.models.keys())

    # ---- shutdown ----

    def shutdown_model(self, model_id: str, force: bool = False,
                       max_wait_s: float = 120.0):
        """Busy-aware shutdown (reference: loader.go:143-168)."""
        deadline = time.monotonic() + max_wait_s
        wait = 2.0
        while True:
            with self._lock:
                lm = self.models.get(model_id)
                if lm is None:
                    return
                if lm.busy == 0 or force or time.monotonic() > deadline:
                    lm = self._pop_locked(model_id)
                else:
                    lm = None
            if lm is not None:
                # close OUTSIDE the lock: process.stop can block up to
                # its 10 s grace, and holding the global lock through it
                # stalls every other loader operation (ISSUE 7 satellite)
                self._close_lm(lm)
                return
            time.sleep(min(wait, 5.0))
            wait *= 1.5

    def _pop_locked(self, model_id: str) -> Optional[LoadedModel]:
        """Unregister a model; caller holds self._lock. The (possibly
        slow) close is the caller's job, outside the lock."""
        lm = self.models.pop(model_id, None)
        if lm is not None and self.watchdog is not None:
            self.watchdog.remove(model_id)
        return lm

    @staticmethod
    def _close_lm(lm: Optional[LoadedModel]):
        if lm is None:
            return
        lm.intentional_stop = True   # before close: park the supervisor
        try:
            lm.close()
        except Exception:
            log.exception("backend close failed for model %s", lm.model_id)

    def _drop(self, model_id: str):
        with self._lock:
            lm = self._pop_locked(model_id)
        self._close_lm(lm)

    def stop_all(self):
        self._closed = True
        with self._lock:
            victims = [self._pop_locked(m) for m in list(self.models)]
        for lm in victims:
            self._close_lm(lm)


def _parse_handshake(message: str) -> dict:
    """The backend's identity from a LoadModel reply (ISSUE 12): its pid
    and trace epoch. Backends that reply with a plain string
    (FakeServicer's "loaded", older runners) yield {} — merged traces
    then fall back to raw epoch alignment. The clock offset is NOT taken
    from this reply: a load lasts minutes, and half of its round trip
    was the estimate's error (measure_clock)."""
    try:
        doc = __import__("json").loads(message)
        hs = doc.get("handshake") or {}
        bw = float(hs["wall"])
    except (ValueError, TypeError, KeyError, AttributeError):
        return {}
    return {
        "backend_wall": bw,
        "backend_pid": int(hs.get("pid", 0) or 0),
        "trace_epoch": float(hs.get("trace_epoch", 0.0) or 0.0),
    }


def measure_clock(probe, trips: int = 3) -> dict:
    """Backend-minus-frontend wall-clock offset.

    ``probe`` None: the backend runs on this machine (the manager spawned
    or embedded it) and shares its wall clock: offset 0. Otherwise
    ``probe()`` makes one Health round trip and returns the backend's
    wall clock as stamped in the reply; the midpoint of a round trip is
    the best estimate of when the stamp was taken, so

        offset_s = backend_wall - (t_send + t_recv) / 2

    with the true offset within +-rtt/2 of it. Of ``trips`` round trips
    the shortest wins. A backend that stamps nothing (0.0) or fails the
    probe yields offset 0."""
    out = {"offset_s": 0.0, "rtt_s": 0.0, "local": probe is None}
    best = None
    for _ in range(trips if probe is not None else 0):
        t_send = time.time()
        try:
            wall = float(probe())
        except Exception:
            continue
        t_recv = time.time()
        if wall > 0 and (best is None or t_recv - t_send < best[0]):
            best = (t_recv - t_send, wall - (t_send + t_recv) / 2.0)
    if best is not None:
        out["rtt_s"], out["offset_s"] = max(0.0, best[0]), best[1]
    out["measured_at"] = time.time()
    return out


def _looks_like_addr(target: str) -> bool:
    host, _, port = target.rpartition(":")
    return bool(host) and port.isdigit()
