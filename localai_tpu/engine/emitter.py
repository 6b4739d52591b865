"""Dedicated stream-emission worker (ISSUE 9): detok, stop-sequence
scanning, logprob/event assembly and ``req.out`` queue puts OFF the
engine scheduler loop.

The engine thread keeps all id-level control — EOS, grammar advance and
rollback, length limits, context-shift triggers, KV bookkeeping and slot
release for engine-detected finishes — and hands this worker one
immutable token batch per processed burst / prefill pass
(``push_batch``). The worker owns all text-level state for a request:
the slot snapshot's ``IncrementalDetokenizer`` and ``held_text`` are
single-writer (this thread), and the worker is
the ONLY writer of ``req.out`` for slotted requests, so per-slot FIFO
order is simply the queue's FIFO order.

Stop sequences are text-level, so they are DETECTED here — possibly
after the engine has already dispatched further decode steps for the
slot. The worker truncates the text before the stop, closes
the stream, and feeds the finish back via ``note_finish``; the engine
applies the note on its next tick (release the slot, pull a racing
context-shift re-prefill back out of the queue, account goodput).
Tokens decoded past the stop are discarded exactly like tokens decoded
past any other in-flight invalidation (rollback / shift / release:
slots ride out bursts).

Failure paths (cancel, timeout, stall-abort, engine error, shutdown)
route their final events through ``push_final`` on the same queue, so
they land AFTER any still-queued tokens for the stream. A worker wedged
longer than the dispatch stall budget is detected by the engine's
watchdog via the ``t_item_start`` heartbeat and replaced wholesale
(``abandon``); the ``emitter_wedge_ms`` fault drives that path in
chaos tests.
"""

from __future__ import annotations

import logging
import queue
import threading
import time

from localai_tpu.services.faults import FAULTS

log = logging.getLogger(__name__)


def check_stops(snap, delta):
    """If a stop sequence completes in emitted+delta text, return the
    delta truncated before the stop; else None."""
    total = snap.detok.text  # includes delta already
    for stop in snap.req.stop_sequences:
        idx = total.find(stop, max(0, len(total) - len(delta) - len(stop)))
        if idx != -1:
            emitted_before = len(total) - len(delta)
            return delta[: max(0, idx - emitted_before)]
    return None


def holdback(snap, delta):
    """Withhold a suffix of delta that is a prefix of any stop sequence."""
    total = snap.detok.text
    hold = 0
    for stop in snap.req.stop_sequences:
        for k in range(min(len(stop) - 1, len(total)), 0, -1):
            if total.endswith(stop[:k]):
                hold = max(hold, min(k, len(delta)))
                break
    if hold:
        return delta[:-hold], delta[-hold:]
    return delta, ""


class EmitterWorker:
    """One background thread draining immutable token batches.

    Constructor takes the engine's collaborators instead of importing
    them (engine imports this module; the reverse would be a cycle):
    ``stream_event`` is the StreamEvent dataclass, ``merge_events`` the
    per-burst coalescer, ``note_finish(slot, snap, ndec, timings)`` the
    engine callback for emitter-detected stop-sequence finishes, and
    ``note_abort(slot, snap)`` the callback for streams this worker had
    to FAIL (an item raised — e.g. a detokenizer exception): the stream
    is already closed with a structured error here; the engine just
    releases the slot.
    """

    def __init__(self, tracer, stream_event, merge_events, note_finish,
                 note_abort=None, name: str = "engine-emitter"):
        self._tracer = tracer
        self._StreamEvent = stream_event
        self._merge = merge_events
        self._note_finish = note_finish
        self._note_abort = note_abort
        self._q: "queue.Queue" = queue.Queue()
        self._dead = False
        # per-slot text-level state: slot -> [snap, finished]. Bounded by
        # the slot count: a new snap for a slot resets the entry, and a
        # finished flag makes late items for the old snap no-ops (no
        # double-None on cancel-after-stop races).
        self._st: dict = {}
        # watchdog heartbeat: monotonic stamp of the item being processed
        # RIGHT NOW, 0.0 when idle — the engine's stall watchdog reads it.
        self.t_item_start = 0.0
        self.emitted = 0          # tokens emitted (telemetry / tests)
        self._unfinished = 0
        self._lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._loop, name=name, daemon=True)
        self._thread.start()

    # ---- engine-side API (single producer: the engine thread, plus the
    # ---- shutdown caller after that thread is joined) ----

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def qsize(self) -> int:
        return self._q.qsize()

    def idle(self) -> bool:
        with self._lock:
            return self._unfinished == 0

    def push_batch(self, entries) -> None:
        """Hand over one immutable token batch (one burst/prefill pass).

        Each entry: ``{slot, snap, tokens: [(id, logprob, n_decoded)],
        finish: None|"stop"|"length", timings: dict|None}`` — ``finish``
        set only for engine-detected finishes (EOS / length), in which
        case ``timings`` carries the engine-computed final timings."""
        with self._lock:
            self._unfinished += 1
        self._q.put(("batch", entries))

    def push_final(self, slot, snap, evs) -> None:
        """Route a failure/shutdown final through the stream's FIFO so it
        lands after any still-queued tokens. An ``evs`` list ending in
        None closes the stream (later items for the snap are dropped)."""
        with self._lock:
            self._unfinished += 1
        self._q.put(("final", slot, snap, evs))

    def abandon(self) -> None:
        """Watchdog kill: the (possibly wedged) thread discards whatever
        remains when it wakes; the engine builds a fresh worker. Never
        joins — the thread may stay stuck for a while."""
        self._dead = True
        self._q.put(None)

    def takeover(self) -> list:
        """Watchdog kill + queue seizure: mark the worker dead, hand back
        every still-queued item so the engine can fail those streams
        directly. Never joins — the thread may stay stuck on its current
        item for a while; anything it puts after the engine's direct
        error+None close lands past the sentinel and consumers ignore
        it."""
        self._dead = True
        items = []
        while True:
            try:
                it = self._q.get_nowait()
            except queue.Empty:
                break
            if it is not None:
                items.append(it)
        self._q.put(None)   # unstick the thread so it can exit
        return items

    def drain(self, timeout: float = 5.0) -> bool:
        """Wait until everything queued so far has been processed."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.idle():
                return True
            if not self.alive:
                return self.idle()
            time.sleep(0.002)
        return False

    def stop(self, timeout: float = 5.0) -> bool:
        """Drain then terminate the worker thread (engine shutdown)."""
        ok = self.drain(timeout)
        self._dead = True
        self._q.put(None)
        self._thread.join(timeout=2.0)
        return ok

    # ---- worker thread ----

    def _loop(self):
        while True:
            item = self._q.get()
            if item is None or self._dead:
                break
            self.t_item_start = time.monotonic()
            try:
                if FAULTS.active:
                    ms = FAULTS.take("emitter_wedge_ms")
                    if ms is not None:
                        time.sleep(float(ms) / 1e3)
                if item[0] == "batch":
                    self._process_batch(item[1])
                else:
                    _kind, slot, snap, evs = item
                    self._final(slot, snap, evs)
            except Exception as e:
                log.exception("emitter item failed")
                self._fail_item(item, e)
            finally:
                self.t_item_start = 0.0
                with self._lock:
                    self._unfinished -= 1

    def _state(self, slot, snap):
        st = self._st.get(slot)
        if st is None or st[0] is not snap:
            st = self._st[slot] = [snap, False]
        return st

    def _final(self, slot, snap, evs):
        st = self._state(slot, snap)
        if st[1]:
            return   # stream already closed (e.g. emitter-detected stop)
        out = snap.req.out
        for ev in evs:
            out.put(ev)
        if evs and evs[-1] is None:
            st[1] = True

    def _fail_item(self, item, exc):
        """An item raised mid-processing: fail every affected stream with
        a structured error so no consumer hangs on a stream whose tokens
        died with the exception (as the engine loop's generic handler
        does), and tell the engine to release the slots. Must never
        raise — it runs inside the worker's exception handler."""
        try:
            if item[0] == "batch":
                affected = [(e["slot"], e["snap"]) for e in item[1]]
            else:
                affected = [(item[1], item[2])]
            for slot, snap in affected:
                st = self._state(slot, snap)
                if st[1]:
                    continue
                st[1] = True
                snap.req.out.put(self._StreamEvent(
                    token_id=-1, text="", logprob=0.0, finish_reason="stop",
                    error=f"{type(exc).__name__}: {exc}"))
                snap.req.out.put(None)
                if self._note_abort is not None:
                    self._note_abort(slot, snap)
        except Exception:
            log.exception("emitter failure cleanup failed")

    def _process_batch(self, entries):
        t0 = time.monotonic()
        # compute pass first: detok + stop-scan + event assembly, no
        # queue traffic. Finished flags flip HERE, so later entries for
        # an already-finished snap in the same batch still short-circuit
        # exactly as the interleaved per-entry path did.
        writes = []
        notes = []
        for e in entries:
            out, evs, note = self._build_entry(e)
            if evs:
                writes.append((out, evs))
            if note is not None:
                notes.append(note)
        # then ONE writer pass per drained batch (ISSUE 10, closes the
        # PR-9 follow-up): with preemption making multi-slot finals in
        # one tick common, the puts go out back-to-back instead of
        # interleaving with per-slot detok work
        tput = time.monotonic()
        for out, evs in writes:
            for ev in evs:
                out.put(ev)
        # engine feedback after the streams are closed (same order the
        # per-entry path produced: put, put None, then note_finish)
        for slot, snap, ndec, timings in notes:
            self._note_finish(slot, snap, ndec, timings)
        t1 = time.monotonic()
        tr = self._tracer
        if tr.enabled:
            # detok + stop-scan, then the queue puts: the decomposition
            # keeps this thread's walltime out of host_loop (it overlaps
            # the engine loop)
            tr.record("emit_bg", "emitter", t0, tput,
                      args={"entries": len(entries)})
            tr.record("stream_flush_bg", "emitter", tput, t1)

    def _timings(self, snap, ndec):
        """Final-event timings for an emitter-detected stop (the engine
        computes these itself for finishes it detects)."""
        t_done = time.monotonic()
        req = snap.req
        dt = t_done - snap.t_first_token
        queue_wait_ms = max(0.0, (snap.t_start - req.t_submit) * 1e3) \
            if req.t_submit else 0.0
        admit_to_first_ms = max(0.0, (snap.t_first_token - snap.t_start) * 1e3) \
            if snap.t_first_token else 0.0
        return {
            "prefill_ms": snap.t_prefill_ms,
            "queue_wait_ms": queue_wait_ms,
            "admit_to_first_ms": admit_to_first_ms,
            "reused_prompt_tokens": snap.reused,
            "decode_tokens_per_s":
                (ndec - 1) / dt if dt > 0 and ndec > 1 else 0.0,
        }

    def _build_entry(self, e):
        """Detok + stop-scan + event assembly for one entry, NO queue
        traffic: returns ``(out_queue, events, note)``. ``events`` may
        end in the None stream-close sentinel; ``note`` is the
        ``(slot, snap, ndec, timings)`` engine feedback for an
        emitter-DETECTED stop (the engine does not know yet — it must
        release the slot and drop tokens decoded past the stop)."""
        snap = e["snap"]
        slot = e["slot"]
        st = self._state(slot, snap)
        if st[1]:
            return None, (), None
        toks = e["tokens"]
        finish = e["finish"]
        evs = []
        last_j = len(toks) - 1
        for j, (tok, lp, ndec) in enumerate(toks):
            fin = finish if j == last_j else None
            timings = None   # set only for emitter-DETECTED stops
            if fin == "stop":
                # engine-detected EOS: the token itself is never
                # detokenized
                delta = snap.held_text + snap.detok.flush()
                snap.held_text = ""
            elif fin == "length":
                delta = snap.held_text + snap.detok.push(tok) \
                    + snap.detok.flush()
                snap.held_text = ""
            else:
                delta = snap.held_text + snap.detok.push(tok)
                snap.held_text = ""
                if snap.req.stop_sequences:
                    cut = check_stops(snap, delta)
                    if cut is not None:
                        delta, fin = cut, "stop"
                        timings = self._timings(snap, ndec)
                    elif delta:
                        delta, snap.held_text = holdback(snap, delta)
            ev = self._StreamEvent(
                token_id=tok, text=delta, logprob=lp, finish_reason=fin,
                prompt_tokens=snap.prompt_len, completion_tokens=ndec)
            self.emitted += 1
            if fin is not None:
                st[1] = True
                ev.timings = e["timings"] if timings is None else timings
                final = []
                if evs:
                    final.append(evs[0] if len(evs) == 1
                                 else self._merge(evs))
                final.append(ev)
                final.append(None)
                note = (slot, snap, ndec, timings) \
                    if timings is not None else None
                return snap.req.out, final, note
            evs.append(ev)
        if evs:
            return snap.req.out, \
                [evs[0] if len(evs) == 1 else self._merge(evs)], None
        return None, (), None
