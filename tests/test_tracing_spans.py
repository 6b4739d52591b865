"""The one tracer (PR 25): ``RingTracer.span()``, the engine loop's tick
phases, named programs and scopes, LoadModel's spans, the profile anchor in
/debug/state, the clock offset of local and remote backends."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.models import llama
from localai_tpu.services import sysobs, tracing
from localai_tpu.services.tracing import RingTracer

TICK_PHASES = ("tick_admit", "tick_prefetch", "tick_prefill_pack",
               "tick_dispatch_decode", "tick_drain", "tick_housekeeping",
               "tick_idle_wait")
SCOPES = ("embed", "layer/attn_proj", "layer/attn", "layer/mlp",
          "final_norm", "lm_head", "sample")


class _FakeAnnotation:
    seen = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _FakeAnnotation.seen.append(("enter", self.name, self.kw))

    def __exit__(self, *exc):
        _FakeAnnotation.seen.append(("exit", self.name))


# ------------------------------------------------------------- the span call

def test_span_records_ring_and_annotation_only_while_capturing():
    tr = RingTracer(size=16)
    _FakeAnnotation.seen = []
    with tr.span("tick_admit", "sched", queued=3) as sp:
        sp.args["admitted"] = 2          # a count known only at the end
    assert _FakeAnnotation.seen == []    # no capture: the ring only
    s = tr.spans()[-1]
    assert (s["name"], s["track"]) == ("tick_admit", "sched")
    assert s["args"] == {"queued": 3, "admitted": 2} and s["t1"] >= s["t0"]
    tr._annotation = _FakeAnnotation     # what set_capturing(True) installs
    assert tr.capturing
    with tr.span("decode_burst", "engine", rid="r1", steps=4, slots=[1, 2]):
        pass
    # the annotation carries the name and the scalar args known at entry
    assert _FakeAnnotation.seen == [
        ("enter", "decode_burst", {"steps": 4}), ("exit", "decode_burst")]
    assert tr.spans()[-1]["rid"] == "r1"
    tr.set_capturing(False)
    assert not tr.capturing


def test_span_is_a_noop_with_tracing_off():
    tr = RingTracer(size=16, enabled=False)
    a, b = tr.span("x", "sched"), tr.span("y", "engine", k=1)
    assert a is b                        # the shared null span
    with a as sp:
        sp.args["n"] = 1                 # goes nowhere
        sp.args.update(m=2)
    assert dict(sp.args) == {} and tr.spans() == []
    tr.set_capturing(True)               # nothing to annotate either
    assert not tr.capturing


def test_span_is_a_trace_annotation_in_a_real_capture(tmp_path):
    from jax.profiler import ProfileData

    tr = RingTracer(size=16)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tr.set_capturing(True)
        with tr.span("tick_idle_wait", "sched", queued=0):
            time.sleep(0.002)
    finally:
        tr.set_capturing(False)
        jax.profiler.stop_trace()
    xp = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs
          if f.endswith(".xplane.pb")]
    events = [e for p in ProfileData.from_file(xp[0]).planes
              if p.name.startswith("/host:") for ln in p.lines
              for e in ln.events if e.name == "tick_idle_wait"]
    assert len(events) == 1
    assert dict(events[0].stats)["queued"] == 0
    assert events[0].duration_ns >= 2_000_000
    assert tr.summary()["by_span_ms"]["tick_idle_wait"]["count"] == 1


def test_ring_holds_a_window_and_says_from_when():
    tr = RingTracer(size=8)
    assert tracing.DEFAULT_RING_SIZE >= 131072
    base = tr.t0
    for i in range(8):
        tr.record("s", "sched", base + i, base + i + 0.5)
    s = tr.summary()
    # nothing overwritten yet: complete since the tracer's own epoch
    assert s["spans_dropped"] == 0
    assert s["oldest_retained_epoch"] == pytest.approx(tr.t0_epoch)
    for i in range(8, 11):
        tr.record("s", "sched", base + i, base + i + 0.5)
    s2 = tr.summary()
    assert s2["spans_dropped"] == 3
    # the oldest retained span is #3, recorded at base + 3.5: whatever
    # ended after that is still in the ring
    assert s2["oldest_retained_epoch"] == pytest.approx(tr.t0_epoch + 3.5)
    assert s2["by_span_ms"]["s"]["count"] == 11      # totals survive


def test_configure_resizes_and_keeps_the_newest():
    tr = RingTracer(size=4)
    for i in range(6):
        tr.record(f"s{i}", "load", float(i), float(i) + 1)
    tr.configure(8)
    assert [s["name"] for s in tr.spans()] == ["s2", "s3", "s4", "s5"]
    tr.record("s6", "load", 6.0, 7.0)
    assert [s["name"] for s in tr.spans()][-2:] == ["s5", "s6"]
    tr.configure(2, enabled=True)
    assert [s["name"] for s in tr.spans()] == ["s5", "s6"]
    tr.configure(2, enabled=False)
    tr.record("s7", "load", 7.0, 8.0)
    assert tr.summary() == {"enabled": False}


# ------------------------------------------------------- the engine's loop

def _tiny(byte_tokenizer, tracer=None, **kw):
    cfg = llama.LlamaConfig(
        vocab_size=258, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = eng.EngineConfig(**{**dict(
        num_slots=2, max_context=64, prefill_buckets=(16,), prefill_chunk=16,
        decode_burst=2, kv_layout="paged", kv_page_size=16), **kw})
    return eng.Engine(cfg, params, byte_tokenizer, ecfg, tracer=tracer)


def _gen(engine, tok, prompt="hello tracer", n=6):
    return engine.generate_text(eng.GenRequest(
        prompt_ids=tok.encode(prompt),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, ignore_eos=True))


@pytest.fixture(scope="module")
def loop_engine(byte_tokenizer):
    e = _tiny(byte_tokenizer)
    e.start(precompile=False)
    for i in range(3):
        _gen(e, byte_tokenizer, prompt=f"request number {i}")
    time.sleep(0.05)                     # let the loop park once more
    yield e
    e.shutdown()


@pytest.mark.parametrize("name", TICK_PHASES + ("sync_wait",))
def test_engine_loop_records_every_phase(loop_engine, name):
    by = loop_engine.tracer.summary()["by_span_ms"]
    assert by[name]["count"] >= 1, sorted(by)


def test_tick_phases_nest_under_their_tick(loop_engine):
    spans = loop_engine.tracer.spans()
    ticks = [s for s in spans if s["name"] == "tick"]
    phases = [s for s in spans if s["name"] in TICK_PHASES
              and s["name"] != "tick_idle_wait"]
    assert ticks and all(s["track"] == "sched" for s in ticks + phases)
    for t in ticks:
        inside = [p for p in phases
                  if p["t0"] >= t["t0"] - 1e-6 and p["t1"] <= t["t1"] + 1e-6]
        assert {p["name"] for p in inside} >= {
            "tick_admit", "tick_prefill_pack", "tick_dispatch_decode",
            "tick_drain"}
        # a tick is covered by its phases: what is left is loop overhead
        assert sum(p["t1"] - p["t0"] for p in inside) <= \
            (t["t1"] - t["t0"]) + 1e-4
    # the dispatch annotations keep their names, inside the phases
    burst = next(s for s in spans if s["name"] == "decode_burst")
    assert any(p["name"] in ("tick_dispatch_decode", "tick_prefill_pack")
               and p["t0"] <= burst["t0"] and burst["t1"] <= p["t1"] + 1e-6
               for p in phases)
    # parked with nothing to do: the idle wait says so
    idle = [s for s in spans if s["name"] == "tick_idle_wait"]
    assert idle and all("queued" in s["args"] for s in idle)


def test_tick_spans_carry_the_counts(loop_engine):
    ticks = [s for s in loop_engine.tracer.spans() if s["name"] == "tick"]
    for t in ticks:
        assert {"slots_active", "prefill_tokens", "decode_tokens",
                "queued"} <= set(t["args"])
    assert sum(t["args"]["prefill_tokens"] for t in ticks) >= 3 * 10
    dec = [t for t in ticks if t["args"]["decode_tokens"] > 0]
    assert dec and all(t["args"]["slots_active"] >= 1 for t in dec)
    # no span per slot per burst, no analytic spans: the burst span names
    # its slots and requests instead
    names = {s["name"] for s in loop_engine.tracer.spans()}
    assert not names & {"decode", "spec_draft", "spec_verify",
                        "decode_dispatch"}
    b = next(s for s in loop_engine.tracer.spans()
             if s["name"] == "decode_burst_device")
    assert len(b["args"]["slot_ids"]) == len(b["args"]["rids"]) >= 1
    doc = tracing.chrome_trace(loop_engine.tracer)
    assert any(e["name"] == "decode" and e.get("args", {}).get("request_id")
               for e in doc["traceEvents"] if e["ph"] == "X")


def test_default_ring_drops_nothing_over_200_requests_of_bursts(
        byte_tokenizer):
    """200 requests' worth of spans of a loaded engine at the default ring:
    nothing dropped; with a ring smaller than the run,
    oldest_retained_epoch moves."""
    e = _tiny(byte_tokenizer, num_slots=4, decode_burst=8)
    e.start(precompile=False)
    try:
        _gen(e, byte_tokenizer, n=4)             # compiles out of the way
        n0 = e.tracer.summary()["spans_recorded"]
        outs = [e.submit(eng.GenRequest(
            prompt_ids=byte_tokenizer.encode(f"loaded engine, request {i}"),
            params=sampling.SamplingParamsHost(temperature=0.0),
            max_new_tokens=32, ignore_eos=True)) for i in range(8)]
        for out in outs:
            while out.get() is not None:
                pass
        s = e.tracer.summary()
        per_request = (s["spans_recorded"] - n0) / 8
    finally:
        e.shutdown()
    assert s["spans_dropped"] == 0
    # (the toy's loop passes many times a burst on the CPU, where a jit
    # call blocks; on the chip a request costs about 36 spans, PERF.md)
    assert per_request * 200 < tracing.DEFAULT_RING_SIZE, per_request
    small = _tiny(byte_tokenizer, trace_ring_size=64)
    small.start(precompile=False)
    try:
        e0 = small.tracer.summary()["oldest_retained_epoch"]
        for _ in range(4):
            _gen(small, byte_tokenizer, n=8)
        s = small.tracer.summary()
        assert s["spans_dropped"] > 0
        assert s["oldest_retained_epoch"] > e0
        assert s["oldest_retained_epoch"] <= time.time()
    finally:
        small.shutdown()


# --------------------------------- a dispatch that overruns its pace (PR 43)

from localai_tpu.services.faults import FAULTS        # noqa: E402


def _late(e):
    return [s for s in e.tracer.spans() if s["name"] == "late_dispatch"]


def _gen_within(e, tok, prompt, n=8, limit_s=120):
    """_gen on a thread of its own: a wedged engine fails the test at
    ``limit_s`` and does not hang the run. -> (events, wall ms)"""
    import threading

    out = []
    t0 = time.monotonic()
    t = threading.Thread(target=lambda: out.append(_gen(e, tok, prompt, n)))
    t.start()
    t.join(timeout=limit_s)
    assert not t.is_alive(), f"no answer in {limit_s} s"
    return out[0][1], (time.monotonic() - t0) * 1e3


@pytest.fixture()
def paced_engine(byte_tokenizer):
    """A toy engine whose every program kind has a pace: a handful of
    requests after the first ones' compiles."""
    e = _tiny(byte_tokenizer)
    e.start(precompile=False)
    for i in range(6):
        _gen_within(e, byte_tokenizer, f"pace {i}")
    yield e
    FAULTS.reset()
    e.shutdown()


def test_sync_wait_says_what_it_waited_for(paced_engine):
    waits = [s for s in paced_engine.tracer.spans()
             if s["name"] == "sync_wait"]
    assert waits and all(s["track"] == "sync" for s in waits)
    kinds = {s["args"]["kind"].split(":")[0] for s in waits}
    assert kinds >= {"prefill_pack_head"} and kinds & {"decode_burst",
                                                      "spec_tick"}
    assert all(s["args"]["steps"] >= 1 for s in waits)
    # each kind's pace is kept, at most PACE_ITEMS items of it
    assert set(paced_engine._pace) == {s["args"]["kind"] for s in waits}
    assert all(0 < len(p) <= eng.PACE_ITEMS
               for p in paced_engine._pace.values())


def test_a_held_dispatch_leaves_exactly_one_late_dispatch(
        paced_engine, byte_tokenizer, tmp_path):
    import gc
    import threading

    e = paced_engine
    e.ecfg.stall_dump_dir = str(tmp_path)
    delay_ms = 600
    assert delay_ms / 1e3 > eng.LATE_FACTOR * max(
        np.median(p) * 2 for p in e._pace.values())
    assert delay_ms / 1e3 > 2 * eng.LATE_MIN_S
    lc0 = e.metrics()["lifecycle"]
    flights0 = e._flight.snapshot()["dumps"]
    # full passes of the collector all through the request: the record
    # says how much of the wait was theirs
    done = threading.Event()

    def collect():
        while not done.wait(0.05):
            gc.collect()

    collector = threading.Thread(target=collect)
    collector.start()
    FAULTS.arm("sync_delay_ms", str(delay_ms), count=1)
    try:
        events, wall_ms = _gen_within(e, byte_tokenizer, "held dispatch")
    finally:
        done.set()
        collector.join(timeout=30)
    # completed, no abort
    assert all(ev.error is None for ev in events)
    assert events[-1].completion_tokens == 8
    # (a loaded machine may add a true hiccup of its own: the held item's
    # record is the one as long as the injected delay, and no wait of the
    # request can be longer than the request)
    late = [s for s in _late(e) if s["args"]["overdue_ms"] >= 0.5 * delay_ms]
    assert len(late) == 1
    sp, a = late[0], late[0]["args"]
    assert sp["track"] == "sync" and sp["rid"] != ""
    assert 0.5 * delay_ms <= a["overdue_ms"] <= wall_ms
    assert a["expected_ms"] < delay_ms / eng.LATE_FACTOR
    assert (sp["t1"] - sp["t0"]) * 1e3 == pytest.approx(
        a["overdue_ms"] + a["expected_ms"], abs=1.0)
    assert a["kind"] in e._pace and a["steps"] >= 1 and a["slots"] >= 1
    # the sync worker's faults and switches over its wait, the process's
    # CPU time over the same wait, what its resident memory did since the
    # last half-second sample before the wait, the collector's share
    assert {"majflt", "minflt", "nvcsw", "nivcsw"} <= set(a)
    assert a["proc_user_ms"] >= 0 and a["proc_sys_ms"] >= 0
    assert all(abs(a[k]) < 4096 for k in (
        "rss_mb", "rss_anon_mb", "rss_file_mb", "vm_size_mb", "vm_data_mb"))
    assert 0 < a["gc_ms"] < a["overdue_ms"] + a["expected_ms"]
    assert a["since_compile_s"] >= 0
    lc = e.metrics()["lifecycle"]
    assert lc["late_dispatches"] == lc0["late_dispatches"] + len(_late(e))
    assert lc["late_dispatch_s"] - lc0["late_dispatch_s"] == pytest.approx(
        sum(s["args"]["overdue_ms"] for s in _late(e)) / 1e3, abs=1e-3)
    assert lc["stalls"] == lc0["stalls"]
    # no ring dump, no flight dump, no event while it was overdue
    assert e._flight.snapshot()["dumps"] == flights0
    assert list(tmp_path.iterdir()) == []
    assert e.state_snapshot()["lifecycle"]["late_dispatches"] \
        == lc["late_dispatches"]


def test_fifty_healthy_bursts_record_no_late_dispatch(
        paced_engine, byte_tokenizer, monkeypatch):
    # (the toy's device is this machine's CPU, which the other tests
    # share: a quarter second's hiccup there would be a true record, so
    # the floor is raised and the factor alone is what is tested)
    monkeypatch.setattr(eng, "LATE_MIN_S", 2.0)
    e = paced_engine
    n0 = e.tracer.summary()["by_span_ms"]["sync_wait"]["count"]
    for i in range(5):
        _gen_within(e, byte_tokenizer, f"healthy {i}", n=24)
    assert e.tracer.summary()["by_span_ms"]["sync_wait"]["count"] - n0 >= 50
    assert _late(e) == []
    assert e.metrics()["lifecycle"]["late_dispatches"] == 0


def test_with_the_ring_off_a_late_dispatch_is_counted_and_not_sampled(
        byte_tokenizer, monkeypatch):
    """The counters need no ring; the rusage and /proc samples exist for
    the span's arguments alone and are not taken."""
    sampled = []
    real = eng._wait_rusage
    monkeypatch.setattr(eng, "_wait_rusage",
                        lambda: sampled.append(1) or real())
    e = _tiny(byte_tokenizer, tracer=RingTracer(size=64, enabled=False))
    e.start(precompile=False)
    try:
        for i in range(4):
            _gen_within(e, byte_tokenizer, f"pace {i}")
        FAULTS.arm("sync_delay_ms", "600", count=1)
        _gen_within(e, byte_tokenizer, "held, ring off")
        lc = e.metrics()["lifecycle"]
        assert lc["late_dispatches"] >= 1 and lc["late_dispatch_s"] > 0.3
        assert sampled == [] and e.tracer.spans() == []
    finally:
        FAULTS.reset()
        e.shutdown()


def test_the_stall_abort_behaves_as_before(paced_engine, byte_tokenizer,
                                           tmp_path):
    e = paced_engine
    e.ecfg.dispatch_stall_ms = 700
    e.ecfg.stall_dump_dir = str(tmp_path)
    FAULTS.arm("sync_delay_ms", "1500", count=1)
    t0 = time.monotonic()
    events = list(e.generate(eng.GenRequest(
        prompt_ids=byte_tokenizer.encode("wedged dispatch"),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=8, ignore_eos=True)))
    # aborted at the budget, not when the delayed item came home
    assert events[-1].error_kind == "stall"
    assert 0.7 <= time.monotonic() - t0 < 1.5
    assert e.metrics()["lifecycle"]["stalls"] == 1
    time.sleep(1.0)                      # let the delayed item drain
    e.ecfg.dispatch_stall_ms = 30000


def _homecoming(e, item, kind, t_dispatch, t_ready):
    """What the sync worker does to an item that comes home at
    ``t_ready``, then the loop's note of it."""
    item.kind = kind
    item.t_ref = e._overdue_ref(item)
    item.t_ready = e._t_last_ready = t_ready
    e._note_ready(item)


def test_an_item_behind_an_unready_one_has_not_begun_its_wait(
        byte_tokenizer):
    """The sync worker syncs in dispatch order: a prefill head queued
    behind a burst that is still computing is not late by its own few
    milliseconds of pace (the chip's first cold granite run of PR 42
    read 35 such records of 36 before this rule), and the parked loop's
    abort counts from the same point."""
    import collections

    e = _tiny(byte_tokenizer)            # never started: the items are ours
    now = time.monotonic()
    e._pace = {"decode_burst": collections.deque([0.2]),       # s a step
               "prefill_pack_head:16": collections.deque([0.01])}
    burst = eng._Burst(2, [], None, t_dispatch=now - 1.0)
    head = eng._PendingPrefill([], None, None, None, now - 1.0, split=True)
    # the burst took 0.5 s of an expected 0.4; the head, dispatched with
    # it, came home 10 ms after it: 1 s after its dispatch, 10 ms of wait
    _homecoming(e, burst, "decode_burst", now - 1.0, now - 0.5)
    _homecoming(e, head, "prefill_pack_head:16", now - 1.0, now - 0.49)
    assert head.t_ref == now - 0.5
    assert _late(e) == [] and e._lc["late_dispatches"] == 0
    assert len(e._pace["prefill_pack_head:16"]) == 2
    # the stall abort's reference is the same: 0.49 s of quiet, not 1 s
    e.ecfg.dispatch_stall_ms = 700
    parked = eng._Burst(2, [], None, t_dispatch=now - 1.0)
    e._fifo.append(parked)
    e._check_parked_stall()              # 0.49 s since the last ready stamp
    e._t_last_ready = now - 0.8
    with pytest.raises(eng._DispatchStall):
        e._check_parked_stall()
    e._fifo.clear()
    # ... and a head that itself waits a second, a hundred times its pace,
    # is late: one span from its own reference point
    e._t_last_ready = now - 0.49
    slow = eng._PendingPrefill([], None, None, None, now - 1.0, split=True)
    _homecoming(e, slow, "prefill_pack_head:16", now - 1.0, now + 0.51)
    (sp,) = _late(e)
    assert sp["args"]["kind"] == "prefill_pack_head:16"
    assert sp["args"]["overdue_ms"] == pytest.approx(1000 - 10, abs=1.0)
    assert (sp["t0"], sp["t1"]) == (now - 0.49, now + 0.51)
    assert e._lc["late_dispatches"] == 1


def test_a_kind_with_no_history_leaves_no_record(byte_tokenizer):
    e = _tiny(byte_tokenizer)
    now = time.monotonic()
    first = eng._PendingPrefill([], None, None, None, now - 5.0)
    _homecoming(e, first, "prefill_final:16", now - 5.0, now)
    assert _late(e) == [] and e._lc["late_dispatches"] == 0
    assert list(e._pace["prefill_final:16"]) == [pytest.approx(5.0)]
    # an item that errored before the device (no ready stamp) is no sample
    e._note_ready(eng._PendingPrefill([], None, None, None, now))
    assert set(e._pace) == {"prefill_final:16"}


def test_late_counters_and_runner_memory_reach_the_metrics_route():
    from localai_tpu.api import localai_routes as routes

    names = dict(routes._LIFECYCLE_COUNTERS)
    assert names["late_dispatches"] == "late_dispatches_total"
    assert names["stalls"] == "engine_stalls_total"
    src = open(routes.__file__).read()
    assert src.count('"late_dispatch_seconds_total"') == 2   # cleared, set
    assert dict(routes._HOST_MEM_GAUGES) == {
        "rss_bytes": "runner_rss_bytes",
        "rss_peak_bytes": "runner_rss_peak_bytes"}
    assert "peak_host_rss_bytes" in routes._SYSOBS_WATERMARKS


# ------------------------------------------------- names on what the device runs

@pytest.fixture(scope="module")
def lowered_programs(byte_tokenizer):
    """Every program a precompiled toy engine (n-gram speculation on)
    builds, lowered: {program: (kind, module text with locations)}."""
    seen = {}
    orig = eng.Engine._program

    def spy(self, kind, key, attention, fn, **kw):
        d = orig(self, kind, key, attention, fn, **kw)
        name = kind if key is None else f"{kind}:{key}"

        def call(*args):
            if name not in seen:
                shapes = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                                   jnp.result_type(a)), args)
                seen[name] = (kind, d.jit_fn.lower(*shapes).as_text(
                    debug_info=True))
            return d(*args)

        call.jit_fn = d.jit_fn
        return call

    eng.Engine._program = spy
    try:
        e = _tiny(byte_tokenizer, draft="ngram")
        e.start(precompile=True)
        try:
            _gen(e, byte_tokenizer)
            report = e._attention_report()["programs"]
        finally:
            e.shutdown()
    finally:
        eng.Engine._program = orig
    return report, seen


def test_every_program_is_named_after_its_kind(lowered_programs):
    report, seen = lowered_programs
    assert set(seen) <= set(report) and len(seen) >= 8
    kinds = {k for k, _ in seen.values()}
    assert {"decode_burst", "spec_tick", "prefill_pack"} <= kinds
    for name, (kind, text) in seen.items():
        assert f"module @jit_{kind} " in text or \
            f"module @jit_{kind}\n" in text, (name, text[:200])
        assert "_lambda_" not in text.split("\n", 1)[0]
    # the shape key stays out of the name: one name per kind
    assert all(":" not in k for k in kinds)


@pytest.mark.parametrize("kind,scopes", [
    ("decode_burst", SCOPES),
    ("spec_tick", SCOPES + ("spec_draft", "spec_verify")),
    ("prefill_pack", ("embed", "layer/attn_proj", "layer/attn", "layer/mlp",
                      "final_norm", "lm_head", "sample")),
])
def test_hlo_carries_the_scope_names(lowered_programs, kind, scopes):
    _, seen = lowered_programs
    text = next(t for k, t in seen.values() if k == kind)
    for s in scopes:
        # a location is the scope path of its operation, relative to the
        # enclosing scan body: "layer/mlp/dot_general", "jit(f)/embed/..."
        assert f'"{s}/' in text or f"/{s}/" in text, (kind, s)


def test_compile_seconds_per_kind_outlive_the_ring():
    tr = sysobs.CompileTracker(model="t")
    x = jnp.ones((3,), jnp.float32)
    with sysobs.activated(tr):
        tr.note_program("decode_burst", (2, True))
        jax.jit(lambda y: y * 3 + 1)(x)
        tr.note_program(None)
        jax.jit(lambda y: y * 5 - 1)(x)      # a helper: takes no one's name
        for i in range(sysobs._LAST_COMPILES + 4):
            tr.note_program("prefill_pack", i)
            tr.on_compile(0.001)
            tr.note_program(None)
    by = tr.by_kind()
    assert by["decode_burst"]["compiles"] == 1
    assert by["decode_burst"]["seconds"] > 0
    assert by["?"]["compiles"] >= 1
    assert by["prefill_pack"]["compiles"] == sysobs._LAST_COMPILES + 4
    # the ring of recent compiles forgot the first; the totals did not
    assert not any(c["program"].startswith("decode_burst")
                   for c in tr.last_compiles())


# -------------------------------------------------- LoadModel from the inside

@pytest.fixture(scope="module")
def loaded_servicer(tmp_path_factory):
    from localai_tpu.backend import contract_pb2 as pb
    from localai_tpu.backend import runner
    from tests.tinymodel import write_tiny_checkpoint, write_tiny_tokenizer

    d = str(tmp_path_factory.mktemp("toy-ckpt"))
    write_tiny_checkpoint(d)
    write_tiny_tokenizer(d)
    os.environ["LOCALAI_PRECOMPILE"] = "0"
    # a process record of this load's own: an earlier LoadModel in the
    # worker's process (another test file's) has marked the shared one warm
    process = pytest.MonkeyPatch()
    process.setattr(sysobs, "PROCESS", sysobs.ProcessCompiles())
    sv = runner.EngineServicer()
    ring = sv.tracer
    res = sv.LoadModel(pb.ModelOptions(
        model=d, dtype="float32", context_size=64, num_slots=2,
        prefill_buckets=[16], quantization="int8",
        options="trace_ring_size=4096"), None)
    assert res.success, res.message
    yield sv, ring
    sv.engine.shutdown()
    process.undo()
    os.environ.pop("LOCALAI_PRECOMPILE", None)


def test_load_spans_account_for_load_model(loaded_servicer):
    sv, ring = loaded_servicer
    # one ring per process: the runner's, handed to the engine
    assert sv.engine.tracer is ring and ring.size == 4096
    by = ring.summary()["by_span_ms"]
    assert by["load_model"]["count"] == 1
    for name in ("load_source", "load_quantize", "load_cast",
                 "load_device_wait", "load_engine_init", "load_precompile"):
        assert by[name]["count"] >= 1, name
    # (load_tokenizer runs on its own thread beside the others: what of
    # it the load waited for is load_tokenizer_join)
    parts = sum(v["total_ms"] for k, v in by.items()
                if k.startswith("load_")
                and k not in ("load_model", "load_tokenizer"))
    assert parts == pytest.approx(by["load_model"]["total_ms"], rel=0.05)
    spans = {s["name"]: s for s in ring.spans()}
    lm = spans["load_model"]
    assert all(lm["t0"] <= s["t0"] and s["t1"] <= lm["t1"]
               for n, s in spans.items()
               if n.startswith("load_") and n != "load_model")
    assert {"programs", "from_cache", "compile_seconds"} <= \
        set(spans["load_precompile"]["args"])


def test_debug_state_carries_profile_and_trace(loaded_servicer, tmp_path):
    from localai_tpu.backend import contract_pb2 as pb

    sv, ring = loaded_servicer
    st = sv.engine.state_snapshot()
    assert st["profile"] is None and st["trace"]["enabled"]
    assert "load_model" in st["trace"]["by_span_ms"]
    assert "oldest_retained_epoch" in st["trace"]
    assert isinstance(st["compiles_by_kind"], dict)
    res = sv.Profile(pb.PredictOptions(prompt=json.dumps(
        {"seconds": 0.1, "dir": str(tmp_path)})), None)
    assert res.success, res.message
    prof = json.loads(json.dumps(sv.engine.state_snapshot()))["profile"]
    assert prof["capture_dir"] == str(tmp_path) == res.message
    assert prof["seconds"] == 0.1 and prof["stop_trace_s"] >= 0
    assert abs(prof["epoch_ns"] / 1e9 - time.time()) < 60
    assert prof["monotonic_ns"] <= time.monotonic_ns()
    assert not ring.capturing            # cleared with the capture
    # the capture's first host event is the anchor, with the same clocks
    from jax.profiler import ProfileData

    xp = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs
          if f.endswith(".xplane.pb")]
    anchors = [e for p in ProfileData.from_file(xp[0]).planes
               if p.name.startswith("/host:") for ln in p.lines
               for e in ln.events if e.name == "clock_anchor"]
    assert len(anchors) == 1
    assert dict(anchors[0].stats)["monotonic_ns"] == prof["monotonic_ns"]


def test_every_load_span_carries_the_runners_resident_memory(loaded_servicer):
    sv, ring = loaded_servicer
    loads = [s for s in ring.spans() if s["track"] == "load"]
    assert {s["name"] for s in loads} >= {
        "load_model", "load_imports", "load_tokenizer", "load_source",
        "load_quantize", "load_cast", "load_device_wait",
        "load_tokenizer_join", "load_engine_init", "load_precompile"}
    for s in loads:
        assert 0 < s["args"]["rss_mb"] <= s["args"]["rss_peak_mb"], s
    # spans enter the ring as they end: the high-water mark never falls
    # (but by the kernel's per-thread counting, a fraction of a MB)
    peaks = [s["args"]["rss_peak_mb"] for s in loads]
    assert all(b >= a - 1.0 for a, b in zip(peaks, peaks[1:]))
    hm = sv.engine.state_snapshot()["host_memory"]
    assert {"rss_bytes", "rss_peak_bytes", "rss_anon_bytes", "rss_file_bytes",
            "rss_shmem_bytes", "vm_size_bytes", "vm_data_bytes", "at_warm",
            "peak_in_load"} == set(hm)
    # as LoadModel returned: after its last span, before anything served
    assert hm["at_warm"]["rss_peak_bytes"] / 1e6 >= peaks[-1] - 1.0
    assert hm["at_warm"]["rss_bytes"] > 0
    pk = hm["peak_in_load"]
    assert pk["bytes"] / 1e6 == pytest.approx(max(peaks), abs=0.1)
    assert any(s["args"]["rss_peak_mb"] == pytest.approx(max(peaks), abs=.1)
               for s in loads if s["name"] == pk["span"]
               and s["args"].get("leaf", "") == pk["leaf"])
    # the process record heard the load's own compiles (the listener is
    # installed when LoadModel starts) and is warm now
    cp = sv.engine.state_snapshot()["compiles_process"]
    assert cp["warm"] is True and cp["unowned_after_warmup"] == 0
    assert cp["compiles_total"] >= cp["unowned_compiles"] >= 0


def test_load_spans_of_two_threads_enter_the_ring_in_reading_order(
        monkeypatch):
    """A load span that ends on another thread (load_tokenizer) reads the
    memory and enters the ring in one step: the ring's high-water marks
    never fall, though one thread's reading is slow and the other's span
    ends inside it."""
    import threading

    reading = threading.Event()
    real = sysobs.host_memory
    plan = {"slow-span": (100, 0.3),
            threading.current_thread().name: (200, 0.0)}

    def host_memory():
        # (an engine of this module's fixture samples it too, on its own)
        if threading.current_thread().name not in plan:
            return real()
        peak, wait = plan[threading.current_thread().name]
        reading.set()
        time.sleep(wait)
        return {"rss_bytes": peak * 10**6, "rss_peak_bytes": peak * 10**6}

    monkeypatch.setattr(sysobs, "host_memory", host_memory)
    monkeypatch.setattr(sysobs, "HOST", sysobs.HostMemory())
    ring = RingTracer(16)

    def slow():
        with ring.span("load_tokenizer", "load"):
            pass

    t = threading.Thread(target=slow, name="slow-span")
    t.start()
    assert reading.wait(10)
    with ring.span("load_source", "load", leaf="w_down"):
        pass
    t.join(10)
    assert not t.is_alive()
    got = [(s["name"], s["args"]["rss_peak_mb"]) for s in ring.spans()]
    assert got == [("load_tokenizer", 100.0), ("load_source", 200.0)]
    assert sysobs.HOST.peak_in_load == {
        "bytes": 200 * 10**6, "span": "load_source", "leaf": "w_down"}


def test_status_reports_what_the_runner_holds_now(loaded_servicer):
    sv, _ring = loaded_servicer
    st = sv.Status(None, None)
    b = dict(st.memory.breakdown)
    now = sysobs.host_memory()
    assert set(b) == {"rss", "rss_peak"} and st.memory.total == b["rss"]
    assert b["rss"] <= b["rss_peak"] == now["rss_peak_bytes"]
    assert b["rss"] == pytest.approx(now["rss_bytes"], rel=0.2)


# ----------------------------------------------------------- one clock

def test_local_backend_clock_shift_is_zero():
    from localai_tpu.modelmgr.loader import measure_clock

    c = measure_clock(None)
    assert c["offset_s"] == 0.0 and c["rtt_s"] == 0.0 and c["local"]


def test_remote_clock_takes_the_least_of_three_round_trips(monkeypatch):
    import localai_tpu.modelmgr.loader as ld

    # three round trips of 0.30, 0.02 and 0.10 s against a backend whose
    # clock is 5 s ahead; the stamp is taken at each trip's midpoint
    trips = [(100.0, 100.30), (200.0, 200.02), (300.0, 300.10)]
    clock = iter(t for pair in trips for t in pair)
    stamps = iter((a + b) / 2 + 5.0 + err
                  for (a, b), err in zip(trips, (0.1, 0.0, -0.03)))
    monkeypatch.setattr(ld.time, "time", lambda: next(clock, 400.0))
    calls = []

    def probe():
        calls.append(1)
        return next(stamps)

    c = ld.measure_clock(probe)
    assert len(calls) == 3 and not c["local"]
    assert c["rtt_s"] == pytest.approx(0.02)
    assert c["offset_s"] == pytest.approx(5.0)
    # a backend that stamps nothing, or fails, gives no offset
    monkeypatch.setattr(ld.time, "time", time.time)
    assert ld.measure_clock(lambda: 0.0)["offset_s"] == 0.0

    def boom():
        raise RuntimeError("unreachable")

    assert ld.measure_clock(boom)["offset_s"] == 0.0


def test_health_reply_carries_the_wall_clock():
    from localai_tpu.backend.service import BackendServicer

    r = BackendServicer().Health(None, None)
    assert r.message == b"OK"
    assert abs(r.timing_prompt_processing / 1e3 - time.time()) < 5
