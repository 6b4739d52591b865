"""System observability (ISSUE 8): XLA compile tracking, memory
watermarks, goodput/MFU accounting.

Complementary to services/tracing.py (per-request spans): this module
watches the SYSTEM — what the compiler and the memory pools are doing
underneath the request stream.

**Compile tracking.** jax emits a
``/jax/core/compile/backend_compile_duration`` monitoring event once
per program built, synchronously on the compiling thread — whether the
compiler ran or the persistent compilation cache served it; the latter
also emits ``/jax/compilation_cache/cache_hits``, counted separately
(``compiles_from_cache``) so a warm restart can be told from a cold one.
Executions of an already-built program emit neither. We register ONE module-level
listener. It gives every event to the process's record (``PROCESS``:
whatever thread compiled), and to the engine whose thread is compiling
via a thread-local registration: the engine loop thread registers its
CompileTracker at startup, and ``precompile()`` (which runs on the
loader/caller thread) wraps itself in :func:`activated`. An event on a
thread that bound no tracker (the sync worker, the emitter, a gRPC
handler, ``LoadModel`` outside ``precompile()``) is *unowned*: no engine
counter hears it, so ``PROCESS`` keeps it apart with the thread's name
and the line that compiled. Program
attribution rides the same thread-local — ``Engine._program`` brackets
every call of a jitted program with ``note_program(kind, key)`` /
``note_program(None)``, so whatever compiles inside the call (the first
call, or a re-specialisation to a new shape) is named after it, and a
helper's compile between two programs never takes a program's name.
Seconds and counts per program kind are kept for the process's life
(``by_kind``); ``last_compiles`` is a short ring of the most recent.

The warm boundary is marked at the END of ``precompile()``: everything
before it (including incidental helper fills like ``jnp.ones``) is
warmup; any compile after it is a "compile storm" — a structured
WARNING + ``compile_storm`` event, because a post-warmup recompile is a
latency cliff the bucket tables were supposed to prevent.

**Watermarks.** High-water marks over gauge samples (peak active /
retained / offloaded pages, host bytes, …) — cheap max() folds sampled
from the engine loop so peaks between /metrics scrapes are not lost.

**Host memory.** ``host_memory()`` reads the process's resident sizes
from ``/proc/self/status``; ``HOST`` keeps them as every ``load`` span
left them and as they stood when ``LoadModel`` returned. ``GC_FULL``
times the collector's full passes from the collector's own callback: a
pass stops every Python thread, the loop that would notice included.

**Goodput / MFU.** Analytic FLOPs-per-token from the model config
(matmul params ×2 + attention term) and achieved tokens/s over a
rolling window → model FLOPs utilization against the device's peak
(the ``PEAK_FLOPS`` table keyed by device kind; a CPU has no entry and
MFU reads 0.0 there, "not measured"). Goodput counts ONLY
completed-request tokens — sheds, timeouts, stalls and errors produce
no goodput even though they burned FLOPs.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import logging
import os
import resource
import sys
import threading
import time
from collections import deque

log = logging.getLogger("localai_tpu.sysobs")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fired (before the duration event, same thread) when the program came
# out of the persistent compilation cache instead of the compiler
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_LAST_COMPILES = 256    # ring of recent compiles kept per tracker: a
# precompile of some fifty programs must not push its first ones out

_tl = threading.local()
_listener_lock = threading.Lock()
_listener_installed = False


def _on_event_duration(name: str, secs: float, **kw):
    if name != _COMPILE_EVENT:
        return
    tracker = getattr(_tl, "tracker", None)
    if tracker is not None:
        tracker.on_compile(secs)
    PROCESS.on_compile(secs, owned=tracker is not None)


def _on_event(name: str, **kw):
    if name != _CACHE_HIT_EVENT:
        return
    tracker = getattr(_tl, "tracker", None)
    if tracker is not None:
        tracker.on_cache_hit()
    else:
        PROCESS.on_unowned_cache_hit()


def install_listener():
    """Register the module-level jax.monitoring listener (idempotent).
    Gated on import success so non-jax processes can still import the
    watermark/goodput halves of this module."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        if GC_FULL.on_gc not in gc.callbacks:
            gc.callbacks.append(GC_FULL.on_gc)
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_event_duration)
            monitoring.register_event_listener(_on_event)
            _listener_installed = True
        except Exception as e:  # pragma: no cover - jax always present in CI
            log.warning("compile-event listener unavailable: %s", e)


def register_thread(tracker: "CompileTracker"):
    """Bind `tracker` to THIS thread for compile attribution (engine
    loop threads call this once at startup)."""
    _tl.tracker = tracker


class activated:
    """Context manager binding a tracker to the current thread for the
    duration of a block — used by precompile(), which runs on the
    loader/caller thread, not the engine loop."""

    def __init__(self, tracker: "CompileTracker"):
        self.tracker = tracker

    def __enter__(self):
        self.prev = getattr(_tl, "tracker", None)
        _tl.tracker = self.tracker
        return self.tracker

    def __exit__(self, *exc):
        _tl.tracker = self.prev
        return False


class CompileTracker:
    """Per-engine XLA compilation counters + compile-storm detection."""

    def __init__(self, model: str = "", on_storm=None):
        self.model = model
        self.on_storm = on_storm    # callable(rec) — eventlog write-through
        self.compiles = 0
        self.compiles_from_cache = 0   # of those, persistent-cache loads
        self.compile_seconds = 0.0
        self.compiles_after_warmup = 0
        self.warm = False
        self._by_kind: dict = {}   # kind -> [seconds, compiles]
        self._last: deque = deque(maxlen=_LAST_COMPILES)
        self._lock = threading.Lock()
        install_listener()

    def note_program(self, kind, key=None):
        """Name the program whose call is about to be made on THIS thread
        (a compile inside the call is its); ``None`` ends the call."""
        _tl.program = (f"{kind}:{key}" if key is not None else kind)

    def mark_warm(self):
        """precompile() finished: every compile from now on is a storm."""
        with self._lock:
            self.warm = True

    def on_cache_hit(self):
        with self._lock:
            self.compiles_from_cache += 1

    def on_compile(self, secs: float):
        # the note stays until the call it names returns: one call may
        # compile more than one executable
        program = getattr(_tl, "program", None) or "?"
        kind = program.split(":", 1)[0]
        with self._lock:
            self.compiles += 1
            self.compile_seconds += secs
            k = self._by_kind.setdefault(kind, [0.0, 0])
            k[0] += secs
            k[1] += 1
            storm = self.warm
            rec = {"t": round(time.time(), 3), "seconds": round(secs, 4),
                   "program": program, "after_warmup": storm}
            self._last.append(rec)
            if storm:
                self.compiles_after_warmup += 1
        if storm:
            # a recompile after warmup is a latency cliff: make it loud
            # (structured WARNING) and durable (eventlog write-through)
            log.warning(json.dumps({
                "event": "compile_after_warmup", "model": self.model,
                "program": program, "seconds": round(secs, 4),
                "compiles_after_warmup": self.compiles_after_warmup}))
            if self.on_storm is not None:
                try:
                    self.on_storm(rec)
                except Exception:
                    pass

    def last_compiles(self) -> list:
        with self._lock:
            return list(self._last)

    def by_kind(self) -> dict:
        """{program kind: {"seconds", "compiles"}} since process start."""
        with self._lock:
            return {k: {"seconds": round(v[0], 4), "compiles": v[1]}
                    for k, v in sorted(self._by_kind.items())}

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles_total": self.compiles,
                    "compiles_from_cache": self.compiles_from_cache,
                    "compile_seconds_total": round(self.compile_seconds, 4),
                    "compiles_after_warmup": self.compiles_after_warmup,
                    "warm": self.warm}


_UNOWNED_RING = 64      # last unowned compile events kept
_NOT_THE_CALLER = (os.sep + "jax" + os.sep, os.sep + "jaxlib" + os.sep)


def _compiling_frame() -> str:
    """``file:line function`` of the innermost frame of THIS thread that
    is neither jax's nor this module's: the listener runs synchronously
    on the compiling thread, so that frame is the call that compiled."""
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        if fn != __file__ and not any(p in fn for p in _NOT_THE_CALLER):
            return f"{fn}:{f.f_lineno} {f.f_code.co_name}"
        f = f.f_back
    return "?"


class ProcessCompiles:
    """Every compile event of the process, whatever thread it fired on,
    and apart those no thread-bound CompileTracker took ("unowned").
    One instance (``PROCESS``): a pool of engines has one record and one
    warm mark, which the runner sets when ``LoadModel`` returns."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_seconds = 0.0
        self.unowned = 0
        self.unowned_seconds = 0.0
        self.unowned_from_cache = 0
        self.unowned_after_warmup = 0
        self.warm = False
        self._by_thread: dict = {}   # thread name -> [seconds, compiles]
        self._ring: deque = deque(maxlen=_UNOWNED_RING)
        self._last = None            # monotonic of the last compile event

    def mark_warm(self):
        with self._lock:
            self.warm = True

    def on_unowned_cache_hit(self):
        with self._lock:
            self.unowned_from_cache += 1

    def on_compile(self, secs: float, owned: bool):
        rec = None if owned else {
            "t": round(time.time(), 3), "seconds": round(secs, 4),
            "thread": threading.current_thread().name,
            "where": _compiling_frame()}
        with self._lock:
            self.compiles += 1
            self.compile_seconds += secs
            self._last = time.monotonic()
            if owned:
                return
            self.unowned += 1
            self.unowned_seconds += secs
            t = self._by_thread.setdefault(rec["thread"], [0.0, 0])
            t[0] += secs
            t[1] += 1
            late = rec["after_warmup"] = self.warm
            self._ring.append(rec)
            if late:
                self.unowned_after_warmup += 1
        if late:
            # the latency cliff a storm is, on a thread whose compiles
            # the engine's compiles_after_warmup cannot count
            log.warning(json.dumps({
                "event": "compile_after_warmup_unowned", **rec,
                "unowned_after_warmup": self.unowned_after_warmup}))

    def since_last(self, at: float):
        """Seconds from the last compile event heard to ``at`` (a
        time.monotonic(); negative: it compiled after ``at``), None
        before the first."""
        last = self._last
        return None if last is None else round(at - last, 3)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "compiles_total": self.compiles,
                "compile_seconds_total": round(self.compile_seconds, 4),
                "unowned_compiles": self.unowned,
                "unowned_seconds": round(self.unowned_seconds, 4),
                "unowned_from_cache": self.unowned_from_cache,
                "unowned_after_warmup": self.unowned_after_warmup,
                "warm": self.warm,
                "unowned_by_thread": {
                    k: [round(v[0], 4), v[1]]
                    for k, v in sorted(self._by_thread.items())},
                "unowned_last": list(self._ring)}


PROCESS = ProcessCompiles()


class FullCollections:
    """The collector's full passes (generation 2), timed. A full pass
    walks every tracked object with the GIL held: every Python thread of
    the process waits for it, the loop that would notice included, so it
    is recorded from the collector's own callback. One instance
    (``GC_FULL``), hooked in by :func:`install_listener`."""

    def __init__(self):
        self.passes = 0
        self.seconds = 0.0
        self._t0 = 0.0
        self._last: deque = deque(maxlen=16)   # (t0, t1, collected)

    def on_gc(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.monotonic()
            return
        t1 = time.monotonic()
        self.passes += 1
        self.seconds += t1 - self._t0
        self._last.append((self._t0, t1, info.get("collected", 0)))

    def seconds_within(self, a: float, b: float) -> float:
        """Seconds of full passes inside [a, b] (time.monotonic())."""
        return sum(max(0.0, min(b, t1) - max(a, t0))
                   for t0, t1, _n in list(self._last))

    def snapshot(self) -> dict:
        now = time.monotonic()
        return {"passes": self.passes, "seconds": round(self.seconds, 4),
                "last": [{"ago_s": round(now - t1, 3),
                          "seconds": round(t1 - t0, 4), "collected": n}
                         for t0, t1, n in list(self._last)[-4:]]}


GC_FULL = FullCollections()


_STATUS_KEYS = {"VmRSS": "rss_bytes", "VmHWM": "rss_peak_bytes",
                "RssAnon": "rss_anon_bytes", "RssFile": "rss_file_bytes",
                "RssShmem": "rss_shmem_bytes",
                "VmSize": "vm_size_bytes", "VmData": "vm_data_bytes"}


def parse_proc_status(text: str) -> dict:
    """The memory sizes of a ``/proc/<pid>/status`` text, in bytes
    (_STATUS_KEYS): resident now, its high-water mark, resident split
    into anonymous, file-backed and shared memory, and the mapped sizes
    (all of it, and the private writable part: what a sandbox kernel that
    gives no split - gVisor gives VmSize, VmRSS and VmData only - still
    tells of WHICH memory went: an unmapped arena takes both down with
    the resident size, a purge neither, an unmapped file VmSize alone).
    A line that is absent or does not parse is left out."""
    out = {}
    for ln in text.splitlines():
        key, _, rest = ln.partition(":")
        name = _STATUS_KEYS.get(key)
        if name is not None:
            try:
                out[name] = int(rest.split()[0]) * 1024     # "<n> kB"
            except (ValueError, IndexError):
                pass
    return out


def host_memory(path: str = "/proc/self/status") -> dict:
    """What the process holds now (parse_proc_status): one read, 20-50
    us; {} where the file cannot be read. Where the kernel keeps no
    VmHWM the peak is getrusage's ``ru_maxrss``, the same counter."""
    try:
        with open(path) as f:
            out = parse_proc_status(f.read())
    except OSError:
        return {}
    if "rss_bytes" in out and "rss_peak_bytes" not in out:
        out["rss_peak_bytes"] = max(out["rss_bytes"], resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024)
    return out


def _mb(n_bytes) -> float:
    return round(n_bytes / 1e6, 1)


class HostMemory:
    """The runner's resident memory through a load and after it. Every
    span of the track ``load`` reads it at its exit (tracing.py), so the
    ring says at which phase and leaf the peak stood; the runner marks
    what is left when ``LoadModel`` returns. One instance (``HOST``)."""

    def __init__(self):
        self.at_warm: dict = {}
        self.peak_in_load: dict = {}

    def on_load_span(self, name: str, args: dict):
        """A ``load`` span ends: its ``rss_mb`` / ``rss_peak_mb``, and the
        last span at whose exit the high-water mark had risen."""
        hm = host_memory()
        if "rss_bytes" not in hm:
            return
        args["rss_mb"] = _mb(hm["rss_bytes"])
        args["rss_peak_mb"] = _mb(hm["rss_peak_bytes"])
        if hm["rss_peak_bytes"] > self.peak_in_load.get("bytes", 0):
            self.peak_in_load = {"bytes": hm["rss_peak_bytes"], "span": name,
                                 "leaf": args.get("leaf", "")}

    def mark_warm(self):
        self.at_warm = host_memory()

    def snapshot(self) -> dict:
        return {**host_memory(), "at_warm": self.at_warm,
                "peak_in_load": self.peak_in_load}


HOST = HostMemory()


def mark_warm():
    """``LoadModel`` returned: a compile from here on that no engine's
    tracker hears is ``unowned_after_warmup`` (one mark for a pool of
    engines), and what the process holds now is ``at_warm``."""
    PROCESS.mark_warm()
    HOST.mark_warm()


class Watermarks:
    """High-water (and a few low-water) marks over sampled gauges."""

    def __init__(self):
        self._peak: dict = {}
        self._lock = threading.Lock()

    def sample(self, **gauges):
        with self._lock:
            for name, val in gauges.items():
                if val is None:
                    continue
                cur = self._peak.get(name)
                if cur is None or val > cur:
                    self._peak[name] = val

    def peak(self, name: str, default=0):
        with self._lock:
            return self._peak.get(name, default)

    def snapshot(self) -> dict:
        with self._lock:
            return {f"peak_{k}": v for k, v in sorted(self._peak.items())}


def flops_per_token(cfg, ctx: int = 0) -> float:
    """Analytic forward-pass FLOPs per generated token for a llama-family
    config: 2 FLOPs per matmul weight parameter, plus the attention
    score/value term (~4*h FLOPs per layer per context row) at context
    depth `ctx`. Embedding lookup is free; the LM head counts (it is a
    matmul), tied or not."""
    h = cfg.hidden_size
    kv = cfg.num_kv_heads * cfg.head_dim_
    q = cfg.num_heads * cfg.head_dim_
    per_layer = (h * q          # q proj
                 + 2 * h * kv   # k,v proj
                 + q * h        # o proj
                 + 3 * h * cfg.intermediate_size)  # gate/up/down
    matmul_params = cfg.num_layers * per_layer + h * cfg.vocab_size
    attn = 4.0 * cfg.num_layers * ctx * h if ctx > 0 else 0.0
    return 2.0 * matmul_params + attn


# Peak dense bf16 FLOP/s of one chip, keyed by the device_kind string
# jax reports for it (checked against libtpu 0.0.34's topology client).
# Source for every row: Google Cloud TPU documentation, the system
# architecture page of that generation ("TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM, 16 GB). One jax device per chip in each.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,    # v5e
    "TPU v6 lite": 918e12,    # v6e
    "TPU v5": 459e12,         # v5p
    "TPU v4": 275e12,
}


def peak_device_flops(device) -> float:
    """Peak FLOP/s of ``device`` from PEAK_FLOPS. A CPU has no entry and
    reads 0.0 (MFU "not measured"); an accelerator that is not in the
    table is an error — a made-up or zero peak would put a wrong MFU on
    the very hardware the number is for."""
    if device.platform == "cpu":
        return 0.0
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device kind {device.device_kind!r}; "
            f"add it to services/sysobs.py PEAK_FLOPS with its source "
            f"(known: {sorted(PEAK_FLOPS)})") from None


class GoodputMeter:
    """Completed-request token accounting → goodput tok/s and MFU.

    `add(n)` is called ONLY from the clean-finish branch of the engine's
    emit path — sheds/timeouts/stalls never reach it, so `tokens_total`
    is useful-work throughput by construction."""

    def __init__(self, flops_per_tok: float = 0.0, peak_flops: float = 0.0,
                 window_s: float = 60.0):
        self.flops_per_tok = float(flops_per_tok)
        self.peak_flops = float(peak_flops)
        self.window_s = float(window_s)
        self.tokens_total = 0
        self.requests_total = 0
        self._window: deque = deque()   # (t_monotonic, n_tokens)
        self._lock = threading.Lock()

    def add(self, n_tokens: int):
        now = time.monotonic()
        with self._lock:
            self.tokens_total += int(n_tokens)
            self.requests_total += 1
            self._window.append((now, int(n_tokens)))
            self._trim(now)

    def _trim(self, now: float):
        horizon = now - self.window_s
        w = self._window
        while w and w[0][0] < horizon:
            w.popleft()

    def tok_s(self) -> float:
        now = time.monotonic()
        with self._lock:
            self._trim(now)
            if not self._window:
                return 0.0
            toks = sum(n for _, n in self._window)
            span = max(now - self._window[0][0], 1e-3)
        return toks / span

    def mfu(self, tok_s: float = None) -> float:
        if self.peak_flops <= 0 or self.flops_per_tok <= 0:
            return 0.0
        rate = self.tok_s() if tok_s is None else tok_s
        return rate * self.flops_per_tok / self.peak_flops

    def snapshot(self) -> dict:
        rate = self.tok_s()
        return {"goodput_tokens_total": self.tokens_total,
                "goodput_requests_total": self.requests_total,
                "goodput_tok_s": round(rate, 3),
                "mfu": round(self.mfu(rate), 6),
                "flops_per_token": self.flops_per_tok,
                "peak_flops": self.peak_flops}


# ---------------------------------------------------------------------------
# SLO engine (ISSUE 12): per-priority-class latency objectives with
# multi-window burn-rate evaluation, plus the violation flight recorder.
# ---------------------------------------------------------------------------

# the priority classes the scheduler knows, in the same order the
# colon-separated option values use (matches priority_weights)
SLO_CLASSES = ("high", "normal", "low")

# metric -> EngineConfig/options knob suffix; all thresholds in ms
SLO_METRICS = ("ttft_ms", "itl_ms", "queue_wait_ms")

# burn-rate windows (name -> seconds). Multi-window per SRE practice:
# the short window catches fast burns, the long one sustained ones.
SLO_WINDOWS = (("5m", 300.0), ("1h", 3600.0))


def parse_slo_classes(spec: str) -> dict:
    """Parse a colon-separated per-class threshold spec into
    {class: threshold_ms}. Accepted shapes (option values ride a
    comma-joined wire, so colon is the list separator, as in
    priority_weights):

    * ``""``            -> {} (no objective declared)
    * ``"500"``         -> the one threshold applies to every class
    * ``"250:1000:5000"`` -> high:normal:low
    * ``"high=250:low=5000"`` -> named subset; unnamed classes have no
      objective

    Raises ValueError on anything else so config validation can reject
    typos at scan time instead of silently serving without SLOs."""
    spec = (spec or "").strip()
    if not spec:
        return {}
    parts = [p.strip() for p in spec.split(":") if p.strip()]
    if not parts:
        return {}

    def _ms(v: str) -> float:
        ms = float(v)
        if not ms > 0:
            raise ValueError(f"SLO threshold must be > 0 ms, got {v!r}")
        return ms

    if any("=" in p for p in parts):
        out = {}
        for p in parts:
            if "=" not in p:
                raise ValueError(
                    f"mixed named and positional SLO classes in {spec!r}")
            k, v = (x.strip() for x in p.split("=", 1))
            if k not in SLO_CLASSES:
                raise ValueError(
                    f"unknown SLO class {k!r} (want one of {SLO_CLASSES})")
            out[k] = _ms(v)
        return out
    if len(parts) == 1:
        ms = _ms(parts[0])
        return {c: ms for c in SLO_CLASSES}
    if len(parts) == len(SLO_CLASSES):
        return {c: _ms(p) for c, p in zip(SLO_CLASSES, parts)}
    raise ValueError(
        f"SLO spec {spec!r} must have 1 or {len(SLO_CLASSES)} "
        f"(high:normal:low) colon-separated values, got {len(parts)}")


@dataclasses.dataclass
class AutoscaleSignals:
    """One policy-input snapshot (ISSUE 19) — everything the autoscaler
    is allowed to see, gathered by the pool on the housekeeping cadence
    and handed to ``AutoscalePolicy.sample()``. Kept a plain dataclass
    so every scaling decision can flight-record ``asdict(signals)`` as
    the evidence that justified it."""
    replicas: int = 1            # routable (alive, non-draining) replicas
    queued: int = 0              # queued requests summed over replicas
    queue_frac: float = 0.0      # queued / (max_queued_requests * replicas)
    busy_frac: float = 0.0       # active slots / total slots
    burn_5m: float = 0.0         # worst short-window SLO burn, any class
    free_page_frac: float = 1.0  # min over replicas (shared pool pressure)
    preempt_rate_per_min: float = 0.0  # summed preemption EWMA

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


class SLOEngine:
    """Per-(metric, class) objective tracking with windowed burn rates.

    Samples are (timestamp, violated?) pairs in bounded deques; the burn
    rate of a window is ``(violations / samples) / error_budget`` — the
    standard "how many times faster than allowed are we spending the
    error budget" number: 1.0 means exactly on budget, >1 means the SLO
    will be missed if the rate holds. `clock` is injectable so the
    window arithmetic is unit-testable with hand-picked timestamps.

    Thread-safety: observe() is called from the engine loop (single
    writer); snapshot()/burn_events() from metrics pulls — a lock keeps
    the deques consistent."""

    def __init__(self, objectives: dict, error_budget: float = 0.01,
                 clock=time.monotonic, max_samples: int = 4096,
                 burn_event_interval_s: float = 30.0):
        # objectives: {metric: {class: threshold_ms}}
        self.objectives = {m: dict(c) for m, c in (objectives or {}).items()
                           if c}
        self.error_budget = max(1e-6, float(error_budget))
        self.clock = clock
        self._samples: dict = {}     # (metric, cls) -> deque[(t, bad)]
        self._violations: dict = {}  # (metric, cls) -> int
        self._last_burn_event: dict = {}  # (metric, cls) -> t
        self._burn_event_interval = float(burn_event_interval_s)
        self._max_samples = int(max_samples)
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return bool(self.objectives)

    def observe(self, metric: str, cls: str, value_ms: float,
                rid: str = ""):
        """Record one sample; returns the violation record (dict) when
        the sample broke its objective, else None. No objective declared
        for (metric, class) -> cheap no-op."""
        threshold = self.objectives.get(metric, {}).get(cls)
        if threshold is None:
            return None
        bad = value_ms > threshold
        now = self.clock()
        with self._lock:
            dq = self._samples.get((metric, cls))
            if dq is None:
                dq = self._samples[(metric, cls)] = deque(
                    maxlen=self._max_samples)
            dq.append((now, bad))
            if bad:
                self._violations[(metric, cls)] = \
                    self._violations.get((metric, cls), 0) + 1
        if not bad:
            return None
        return {"metric": metric, "class": cls,
                "value_ms": round(float(value_ms), 3),
                "objective_ms": threshold, "rid": rid}

    def _burn(self, dq, now: float, window_s: float):
        total = bad = 0
        horizon = now - window_s
        for t, b in dq:
            if t >= horizon:
                total += 1
                bad += b
        if total == 0:
            return 0.0, 0
        return (bad / total) / self.error_budget, total

    def snapshot(self) -> dict:
        """{class: {metric: {objective_ms, burn_5m, burn_1h, n_5m,
        violations}}, violations_total, error_budget}."""
        now = self.clock()
        out = {"error_budget": self.error_budget, "classes": {}}
        total_viol = 0
        with self._lock:
            for metric, classes in self.objectives.items():
                for cls, threshold in classes.items():
                    dq = self._samples.get((metric, cls), ())
                    viol = self._violations.get((metric, cls), 0)
                    total_viol += viol
                    rec = {"objective_ms": threshold, "violations": viol}
                    for wname, wsec in SLO_WINDOWS:
                        burn, n = self._burn(dq, now, wsec)
                        rec[f"burn_{wname}"] = round(burn, 4)
                        rec[f"n_{wname}"] = n
                    out["classes"].setdefault(cls, {})[metric] = rec
        out["violations_total"] = total_viol
        return out

    def max_burn(self, window_s: Optional[float] = None) -> float:
        """Policy-input scalar (ISSUE 19): the WORST burn across every
        observed (metric, class) pair over the short window (default:
        the 5m window). This is the autoscaler's primary scale-out
        signal — any one class burning its budget is reason to add a
        replica, whichever metric is suffering. Pairs with no samples
        in the window contribute nothing (an idle class is not 'fine',
        it is silent)."""
        wsec = float(window_s) if window_s else SLO_WINDOWS[0][1]
        now = self.clock()
        worst = 0.0
        with self._lock:
            for dq in self._samples.values():
                burn, n = self._burn(dq, now, wsec)
                if n and burn > worst:
                    worst = burn
        return worst

    def burn_events(self) -> list:
        """(metric, class) pairs whose SHORT-window burn is > 1 right
        now, rate-limited to one record per pair per
        `burn_event_interval_s` — the caller turns these into `slo_burn`
        structured events."""
        now = self.clock()
        out = []
        wname, wsec = SLO_WINDOWS[0]
        with self._lock:
            for (metric, cls), dq in self._samples.items():
                burn, n = self._burn(dq, now, wsec)
                if burn <= 1.0 or n == 0:
                    continue
                last = self._last_burn_event.get((metric, cls), -1e18)
                if now - last < self._burn_event_interval:
                    continue
                self._last_burn_event[(metric, cls)] = now
                out.append({"metric": metric, "class": cls,
                            "window": wname, "burn": round(burn, 4),
                            "samples": n,
                            "objective_ms":
                                self.objectives[metric][cls]})
        return out


class FlightRecorder:
    """Atomic on-violation dumps: merged chrome trace + state snapshot +
    last-N events written as ONE json file to `out_dir` (tmp file +
    os.replace so a reader never sees a half-written dump).

    Rate-limited (`min_interval_s` between dumps) and disk-bounded
    (`max_dumps` newest kept; older flight dumps are pruned) so a
    sustained violation storm cannot fill the disk. `clock` injectable
    for deterministic tests. dump() never raises — the recorder is
    telemetry, not a serving dependency."""

    PREFIX = "localai-flight-"

    def __init__(self, out_dir: str = "", min_interval_s: float = 30.0,
                 max_dumps: int = 8, clock=time.monotonic):
        import tempfile

        self.out_dir = out_dir or tempfile.gettempdir()
        self.min_interval_s = float(min_interval_s)
        self.max_dumps = max(1, int(max_dumps))
        self.clock = clock
        self.dumps = 0          # written
        self.suppressed = 0     # rate-limited away
        self._last_t = None
        self._lock = threading.Lock()

    def dump(self, reason: str, payload: dict, tag: str = "slo") -> str:
        """Write one flight dump; returns its path, or "" when
        rate-limited or on write failure."""
        now = self.clock()
        with self._lock:
            if self._last_t is not None \
                    and now - self._last_t < self.min_interval_s:
                self.suppressed += 1
                return ""
            self._last_t = now
            self.dumps += 1
            seq = self.dumps
        rec = {"reason": reason, "tag": tag, "ts": round(time.time(), 6)}
        rec.update(payload or {})
        name = (f"{self.PREFIX}{tag}-{os.getpid()}-"
                f"{int(time.time() * 1000)}-{seq}.json")
        path = os.path.join(self.out_dir, name)
        tmp = path + ".tmp"
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(rec, f, default=str)
            os.replace(tmp, path)
        except Exception as e:
            log.warning("flight-recorder dump failed: %s", e)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return ""
        self._prune()
        return path

    def _prune(self):
        """Keep only the newest `max_dumps` flight dumps in out_dir."""
        try:
            mine = sorted(
                f for f in os.listdir(self.out_dir)
                if f.startswith(self.PREFIX) and f.endswith(".json"))
            for f in mine[:-self.max_dumps]:
                try:
                    os.unlink(os.path.join(self.out_dir, f))
                except OSError:
                    pass
        except OSError:
            pass

    def snapshot(self) -> dict:
        with self._lock:
            return {"dumps": self.dumps, "suppressed": self.suppressed,
                    "dir": self.out_dir, "max_dumps": self.max_dumps,
                    "min_interval_s": self.min_interval_s}


def device_memory_stats() -> list:
    """Allocator counters of EVERY local device, in device order:
    [{id, device_kind, bytes_in_use, peak_bytes_in_use, bytes_limit}].
    TPU runtimes report them; the CPU client returns None, and the
    entry then carries id and kind alone ("no device counters here —
    the analytic weight/KV accounting is what there is")."""
    import jax

    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        out.append({"id": dev.id, "device_kind": dev.device_kind,
                    **{k: int(stats[k]) for k in
                       ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                       if k in stats}})
    return out
