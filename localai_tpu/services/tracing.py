"""Request-lifecycle tracing: a low-overhead ring-buffer span tracer.

The engine records timestamped spans (queue_wait, admission, prefill
dispatches, decode bursts, detok, stream flush) keyed by the request's
correlation id into a fixed-size ring — bounded memory, no allocation
churn beyond one tuple per span, one lock. Aggregate totals per span
name survive ring wraparound, so the host-walltime vs device-time
decomposition (``summary()["decomp_ms"]``) reflects the whole engine
lifetime even when individual spans have been overwritten.

``span()`` is the one instrumentation call (engine, runner, loader): a
context manager that records the ring span on exit and, only while a
profiler capture is running (``capturing``, set and cleared by the
runner's ``Profile`` RPC), also enters a ``jax.profiler.TraceAnnotation``
of the same name, so the capture's host plane shows the same spans as the
ring on the profiler's own clock. ``record()`` stays for spans whose ends
are observed on different threads (dispatch -> sync-worker ready). The
sync worker's own span is ``sync_wait`` (track ``sync``): one per
dispatched item, the ``np.asarray`` that blocks on the device and the copy
back, with the ``kind`` and ``steps`` it waited for; an item that overran
its kind's pace also leaves ``late_dispatch`` there (engine.py,
``LATE_FACTOR``).

``chrome_trace()`` renders the ring as Chrome trace-event JSON
(https://ui.perfetto.dev loads it directly): one track per slot plus
one for the scheduler tick loop and one for engine-level dispatches.

The reference exposes per-slot timings as plain struct fields
(grpc-server.cpp:2465-2488 slot timing block); this module is that
layer rebuilt around the dispatch-first engine, where "where did the
wall-clock go" must distinguish host dispatch cost from device compute
observed at sync-worker completion.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time

from localai_tpu.services import sysobs

# Span names counted as HOST loop work in the decomposition: time the
# engine thread spends admitting, dispatching and moving KV pages,
# measured as plain walltime deltas on the engine thread (detok and the
# stream queue puts are the emitter thread's: EMITTER_SPANS). The ``tick_*`` phase
# spans are NOT here: they contain these and would count them twice.
HOST_SPANS = frozenset({
    "admission",
    "prefill_chunk",
    "prefill_dispatch",
    "decode_burst",
    "kv_offload_gather",
    "kv_restore_scatter",
})

# Span names counted as DEVICE time: dispatch call → sync-worker
# ready-set (the only trustworthy device-completion observation point
# on this platform — block_until_ready/is_ready lie here, see
# engine._sync_worker).
DEVICE_SPANS = frozenset({
    "prefill_device",
    "decode_burst_device",
})

# Span names recorded by the EMITTER worker thread (ISSUE 9): detok,
# stop-scan and stream queue puts that used to run on the engine loop.
# They get their own decomposition bucket — this walltime overlaps both
# device compute and the host loop, so folding it into host_loop would
# double-count time the engine thread never spent.
EMITTER_SPANS = frozenset({
    "emit_bg",
    "stream_flush_bg",
})

# Sync-worker ready-set → engine loop picking the result up: the
# finish-detection latency called out in the r5 verdict.
FINISH_DETECT_SPAN = "finish_detect"

# 60 s of a 16-slot engine at its highest tick rate with a factor of
# two to spare, from the span rate measured on the chip: 1,200 spans a
# second at 90 speculative ticks a second (PERF.md section 6, PR 34; the
# 32768 of PR 25 held 27 s of that); a slot costs one tuple, a few short
# strings and a small dict: about 0.3 KB.
DEFAULT_RING_SIZE = 131072

# a ``load`` span's memory reading and its place in the ring, as one step
_LOAD_SPAN_LOCK = threading.Lock()


class _NullSpan:
    """What ``span()`` returns with tracing off: one shared object, so the
    call costs a branch. Writes to its ``args`` go nowhere."""

    class _Sink(dict):
        def __setitem__(self, k, v):
            pass

        def update(self, *a, **kw):
            pass

    args = _Sink()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One ``RingTracer.span()`` in flight. ``args`` may be filled in
    while the span is open (counts known only at its end); the profiler
    annotation carries the scalar args known at entry."""

    __slots__ = ("_tr", "name", "track", "rid", "args", "t0", "_ann")

    def __init__(self, tr, name, track, rid, args):
        self._tr, self.name, self.track = tr, name, track
        self.rid, self.args = rid, args
        self._ann = None

    def __enter__(self):
        ann = self._tr._annotation
        if ann is not None:
            try:
                self._ann = ann(self.name, **{
                    k: v for k, v in self.args.items()
                    if isinstance(v, (int, float, str))})
                self._ann.__enter__()
            except Exception:  # pragma: no cover - profiler went away
                self._ann = None
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.track == "load":
            # the runner's resident memory as every phase and leaf of a
            # load left it (rss_mb, rss_peak_mb); read and entered under
            # one lock, because load spans end on two threads (the
            # tokenizer's beside the weights') and the ring's order is
            # the order of their readings
            with _LOAD_SPAN_LOCK:
                sysobs.HOST.on_load_span(self.name, self.args)
                self._tr.record(self.name, self.track, self.t0, t1,
                                self.rid, self.args or None)
            return False
        self._tr.record(self.name, self.track, self.t0, t1, self.rid,
                        self.args or None)
        return False


class RingTracer:
    """Fixed-size span ring with always-on per-name aggregates.

    ``span()`` and ``record()`` are the hot-path entry points; when
    ``enabled`` is False both return on their first line without taking
    the lock (trace=0 is a true no-op). Spans are (name, track, t0, t1,
    rid, args) tuples with t0/t1 from time.monotonic(), entered in the
    order they END.
    """

    def __init__(self, size: int = DEFAULT_RING_SIZE, enabled: bool = True):
        self.size = max(1, int(size))
        self.enabled = bool(enabled) and int(size) > 0
        self._buf: list = [None] * self.size
        self._n = 0  # total spans ever recorded (monotonic)
        self._w = 0  # next write position
        self._held = 0  # spans in the ring (<= size)
        self._agg: dict = {}  # name -> [total_s, count]
        self._lock = threading.Lock()
        # jax.profiler.TraceAnnotation while a capture runs, else None
        # (set_capturing): this module never imports jax by itself, the
        # HTTP process uses it too
        self._annotation = None
        # Trace epoch: chrome_trace timestamps are relative to this so
        # perfetto's timeline starts near zero.
        self.t0 = time.monotonic()
        self.t0_epoch = time.time()

    def configure(self, size: int, enabled: bool = True):
        """Apply a model's ``trace`` / ``trace_ring_size`` options to the
        process's ring (the runner builds it before it knows them). The
        newest retained spans are kept."""
        size = max(1, int(size))
        with self._lock:
            if size != self.size:
                kept = self._retained()[-size:]
                self._buf = kept + [None] * (size - len(kept))
                self.size, self._held = size, len(kept)
                self._w = len(kept) % size
            self.enabled = bool(enabled)
        if not self.enabled:
            self._annotation = None

    @property
    def capturing(self) -> bool:
        return self._annotation is not None

    def set_capturing(self, on: bool):
        """The Profile RPC brackets a capture with this: while it is on,
        every ``span()`` is also a TraceAnnotation in the capture."""
        if on and self.enabled:
            import jax

            self._annotation = jax.profiler.TraceAnnotation
        else:
            self._annotation = None

    def span(self, name, track, rid="", **args):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, track, rid, args)

    def record(self, name, track, t0, t1, rid="", args=None):
        if not self.enabled:
            return
        with self._lock:
            self._buf[self._w] = (name, track, t0, t1, rid, args)
            self._w = (self._w + 1) % self.size
            self._n += 1
            if self._held < self.size:
                self._held += 1
            a = self._agg.get(name)
            if a is None:
                a = self._agg[name] = [0.0, 0]
            a[0] += t1 - t0
            a[1] += 1

    def _retained(self) -> list:
        if self._held < self.size:
            return self._buf[:self._held]
        return self._buf[self._w:] + self._buf[:self._w]

    def spans(self) -> list:
        """Retained spans, oldest first, as dicts."""
        with self._lock:
            raw = self._retained()
        return [
            {"name": s[0], "track": s[1], "t0": s[2], "t1": s[3],
             "rid": s[4], "args": s[5]}
            for s in raw
        ]

    def reset(self):
        with self._lock:
            self._buf = [None] * self.size
            self._n = self._w = self._held = 0
            self._agg = {}
            self.t0 = time.monotonic()
            self.t0_epoch = time.time()

    def summary(self) -> dict:
        """Aggregate totals + the host-vs-device decomposition."""
        if not self.enabled:
            return {"enabled": False}
        with self._lock:
            n, held = self._n, self._held
            agg = {k: (v[0], v[1]) for k, v in self._agg.items()}
            oldest = self._buf[self._w] if held == self.size else None
        by_span = {
            name: {"total_ms": round(tot * 1e3, 3), "count": cnt,
                   "avg_ms": round(tot * 1e3 / cnt, 4) if cnt else 0.0}
            for name, (tot, cnt) in sorted(agg.items())
        }
        host = sum(t for name, (t, _) in agg.items() if name in HOST_SPANS)
        device = sum(t for name, (t, _) in agg.items() if name in DEVICE_SPANS)
        emitter = sum(t for name, (t, _) in agg.items()
                      if name in EMITTER_SPANS)
        fin = agg.get(FINISH_DETECT_SPAN, (0.0, 0))[0]
        # the wall time from which the ring still holds EVERY span: its
        # own epoch until the first span is overwritten, then the moment
        # the oldest retained span was recorded (spans enter when they
        # end, so whatever ended later is still here)
        dropped = max(0, n - held)
        since = oldest[3] if dropped and oldest is not None else self.t0
        return {
            "enabled": True,
            "ring_size": self.size,
            "spans_recorded": n,
            "spans_dropped": dropped,
            "oldest_retained_epoch": self.t0_epoch + (since - self.t0),
            "by_span_ms": by_span,
            "decomp_ms": {
                "host_loop": round(host * 1e3, 3),
                "device": round(device * 1e3, 3),
                "emitter": round(emitter * 1e3, 3),
                "finish_detect": round(fin * 1e3, 3),
            },
        }


# for callers handed no tracer (a bare load_llama_params): every call a no-op
NO_TRACER = RingTracer(1, enabled=False)


def _track_order_key(track: str):
    # scheduler first, engine dispatches second, slots in numeric order.
    if track == "sched":
        return (0, 0)
    if track == "engine":
        return (1, 0)
    if track.startswith("slot"):
        try:
            return (2, int(track[4:]))
        except ValueError:
            pass
    return (3, track)


def chrome_trace(tracer: RingTracer, pid: int = 1,
                 process_name: str = "localai-engine") -> dict:
    """Render the ring as a Chrome trace-event JSON object.

    One thread (track) per slot plus "sched" (the engine tick loop) and
    "engine" (dispatch/device spans). Load the serialized dict at
    https://ui.perfetto.dev or chrome://tracing.

    The top-level ``localai`` block carries this process's trace epoch
    (wall-clock t0 of the relative-µs timeline) and pid — the anchor the
    HTTP process uses to re-base backend timelines onto ONE merged
    cross-process trace (ISSUE 12); a backend on another machine is
    corrected by the clock offset measured over Health round trips.
    """
    spans = []
    for s in tracer.spans():
        spans.append(s)
        a = s["args"] or {}
        if s["name"] == "decode_burst_device" and a.get("slot_ids"):
            # the burst span names the slots that rode it; their tracks
            # are drawn from it (no span per slot per burst is recorded)
            for i, rid in zip(a["slot_ids"], a.get("rids") or
                              [""] * len(a["slot_ids"])):
                spans.append({"name": "decode", "track": f"slot{i}",
                              "t0": s["t0"], "t1": s["t1"], "rid": rid,
                              "args": {"steps": a.get("steps")}})
    tracks = sorted({s["track"] for s in spans}, key=_track_order_key)
    tid = {t: i for i, t in enumerate(tracks)}
    events: list = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": process_name},
    }]
    for t in tracks:
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid[t],
            "args": {"name": t},
        })
        events.append({
            "name": "thread_sort_index", "ph": "M", "pid": pid,
            "tid": tid[t], "args": {"sort_index": tid[t]},
        })
    base = tracer.t0
    for s in spans:
        args = dict(s["args"]) if s["args"] else {}
        if s["rid"]:
            args["request_id"] = s["rid"]
        events.append({
            "name": s["name"],
            "cat": "engine",
            "ph": "X",
            "pid": pid,
            "tid": tid[s["track"]],
            "ts": round((s["t0"] - base) * 1e6, 1),
            "dur": round(max(0.0, s["t1"] - s["t0"]) * 1e6, 1),
            "args": args,
        })
    return {"displayTimeUnit": "ms", "traceEvents": events,
            "localai": {"t0_epoch": tracer.t0_epoch,
                        "pid": os.getpid()}}


# --- frontend (HTTP/API process) tracer (ISSUE 12) -------------------------
# The core process gets its own RingTracer so the request timeline no
# longer fractures at the gRPC boundary: HTTP parse/route spans and the
# gRPC-hop span are recorded here under the same correlation id the
# backend keys its spans by, and /debug/trace merges both rings onto one
# clock-aligned timeline. LOCALAI_TRACE=0 disables it (record() is then
# the same first-line no-op the engine's trace=0 knob gives the backend).

_frontend_tracer = None
_frontend_lock = threading.Lock()


def frontend_tracer() -> RingTracer:
    """Per-process singleton tracer for the HTTP/API process."""
    global _frontend_tracer
    with _frontend_lock:
        if _frontend_tracer is None:
            enabled = os.environ.get("LOCALAI_TRACE", "1").strip().lower() \
                not in ("0", "false", "off", "no")
            size = int(os.environ.get("LOCALAI_TRACE_RING_SIZE", "2048")
                       or 2048)
            _frontend_tracer = RingTracer(size, enabled=enabled)
        return _frontend_tracer


def dump_ring(tracer: RingTracer, out_dir: str = "", tag: str = "stall") -> str:
    """Write the span ring to disk as perfetto-loadable JSON; return the path.

    The post-mortem half of the stall watchdog (ROADMAP PR-6 follow-up
    "stream the ring to disk for post-mortem of wedged runs"): when the
    engine aborts a wedged dispatch it calls this so the trace of the
    run-up to the stall survives the process.
    """
    out_dir = out_dir or tempfile.gettempdir()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir,
        f"localai-{tag}-{os.getpid()}-{int(time.time() * 1e3)}.trace.json")
    with open(path, "w") as f:
        json.dump(chrome_trace(tracer), f)
    return path
