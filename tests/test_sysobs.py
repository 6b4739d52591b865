"""System observability (ISSUE 8): XLA compile tracking (zero after
warmup, storm detection on a cold program), memory watermarks vs pool
accounting, MFU/goodput arithmetic, event-log ring semantics, the
/debug/state + /debug/events + /readyz surfaces, and exemplar
exposition."""

import asyncio
import json
import threading

import jax
import jax.numpy as jnp
import httpx
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.engine.paging import PagePool
from localai_tpu.models import llama
from localai_tpu.services import sysobs
from localai_tpu.services.eventlog import EVENTS, EventLog
from localai_tpu.services.metrics import (Metrics, escape_label_value,
                                          label_str)


# -------------------------------------------------------- compile tracking

@pytest.fixture(scope="module")
def warm_engine(byte_tokenizer):
    """Tiny PRECOMPILED paged engine: the warm boundary is marked, so
    any further compile is a storm by contract."""
    cfg = llama.LlamaConfig(
        vocab_size=258, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=256,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = eng.EngineConfig(num_slots=2, max_context=64,
                            prefill_buckets=(16,), prefill_chunk=16,
                            decode_burst=2, kv_layout="paged",
                            kv_page_size=16)
    e = eng.Engine(cfg, params, byte_tokenizer, ecfg)
    e.start(precompile=True)
    yield e
    e.shutdown()


def _gen(engine, tok, prompt="hello sysobs", n=6):
    req = eng.GenRequest(
        prompt_ids=tok.encode(prompt),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, ignore_eos=True,
    )
    return engine.generate_text(req)


def test_precompile_marks_warm_and_counts_compiles(warm_engine):
    snap = warm_engine._cobs.snapshot()
    assert snap["warm"] is True
    # precompile() compiled the serving variants with the tracker bound
    assert snap["compiles_total"] > 0
    assert snap["compile_seconds_total"] > 0
    # attribution: the fn-getter notes name the compiled programs
    programs = {c["program"] for c in warm_engine._cobs.last_compiles()}
    assert any(p.startswith("decode_burst") for p in programs)
    assert any(p.startswith("prefill") for p in programs)


def test_repeated_waves_compile_nothing_after_warmup(warm_engine,
                                                     byte_tokenizer):
    """The acceptance contract: a repeated wave of identical-shape
    traffic on a precompiled engine causes ZERO recompiles."""
    before = warm_engine._cobs.snapshot()
    for _ in range(2):
        _gen(warm_engine, byte_tokenizer)
    after = warm_engine._cobs.snapshot()
    assert after["compiles_after_warmup"] == before["compiles_after_warmup"]
    assert after["compiles_after_warmup"] == 0


def test_cold_program_after_warmup_is_a_storm(warm_engine):
    """A compile on a warm engine increments the storm counter and
    emits a structured compile_storm event through the engine's
    eventlog write-through."""
    # built OUTSIDE the activated block: the ones-fill is itself a tiny
    # compile and must not consume the program note
    x = jnp.ones((4,), jnp.float32)
    before = warm_engine._cobs.snapshot()
    with sysobs.activated(warm_engine._cobs):
        warm_engine._cobs.note_program("test_cold_bucket", 99)
        # a fresh lambda is a fresh jit cache entry -> one real compile
        jax.jit(lambda y: y * 2 + 1)(x)
    after = warm_engine._cobs.snapshot()
    assert (after["compiles_after_warmup"]
            == before["compiles_after_warmup"] + 1)
    storms = [ev for ev in EVENTS.events()
              if ev.get("event") == "compile_storm"
              and ev.get("program") == "test_cold_bucket:99"]
    assert storms, "compile_storm event missing from the process ring"
    assert storms[-1]["after_warmup"] is True


def test_tracker_thread_isolation():
    """Two engines compiling on different threads must not cross-count:
    dispatch is by thread-local registration."""
    x = jnp.ones((2,), jnp.float32)
    a, b = sysobs.CompileTracker(model="a"), sysobs.CompileTracker(model="b")
    with sysobs.activated(a):
        jax.jit(lambda y: y - 3)(x)
    assert a.snapshot()["compiles_total"] >= 1
    assert b.snapshot()["compiles_total"] == 0


# --------------------------------------- compiles on every thread (PR 43)

_X3 = None


def _compile_on_bare_thread(name):
    """One real compile on a thread that binds no tracker; -> the line
    of this file that called the jitted function."""
    x = _X3
    line = []

    def run():
        f = jax.jit(lambda y: y * 5 - 2)        # fresh lambda: a compile
        line.append(run.__code__.co_firstlineno + 3)
        f(x)

    t = threading.Thread(target=run, name=name)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()
    return line[0]


def test_process_record_hears_a_bare_thread_and_says_where():
    global _X3
    import time

    # (the ones-fill is itself a tiny compile, on this thread: first)
    _X3 = jnp.ones((3,), jnp.float32)
    sysobs.install_listener()
    before = sysobs.PROCESS.snapshot()
    line = _compile_on_bare_thread("bare-compiler")
    after = sysobs.PROCESS.snapshot()
    assert after["compiles_total"] == before["compiles_total"] + 1
    assert after["unowned_compiles"] == before["unowned_compiles"] + 1
    assert after["unowned_seconds"] > before["unowned_seconds"]
    assert after["compile_seconds_total"] > before["compile_seconds_total"]
    secs, n = after["unowned_by_thread"]["bare-compiler"]
    assert n == 1 and secs > 0
    rec = after["unowned_last"][-1]
    assert rec["thread"] == "bare-compiler" and rec["after_warmup"] is False
    # the innermost frame that is not jax's: the call that compiled, as a
    # string (a frame object would keep its locals alive)
    assert rec["where"] == f"{__file__}:{line} run", rec["where"]
    assert 0 <= sysobs.PROCESS.since_last(time.monotonic()) < 60
    json.dumps(after)


def test_owned_compile_is_the_trackers_and_counted_once():
    x = jnp.ones((5,), jnp.float32)
    tr = sysobs.CompileTracker(model="owner")
    before = sysobs.PROCESS.snapshot()
    with sysobs.activated(tr):
        tr.note_program("owned_kind", 7)
        jax.jit(lambda y: y * 7 + 3)(x)
        tr.note_program(None)
    after = sysobs.PROCESS.snapshot()
    snap = tr.snapshot()
    assert snap["compiles_total"] == 1
    assert tr.by_kind()["owned_kind"]["compiles"] == 1
    # the process record counts it once, and not as unowned
    assert after["compiles_total"] == before["compiles_total"] + 1
    assert after["unowned_compiles"] == before["unowned_compiles"]
    assert after["unowned_last"] == before["unowned_last"]
    # seconds agree: one event, two records
    assert after["compile_seconds_total"] - before["compile_seconds_total"] \
        == pytest.approx(snap["compile_seconds_total"], abs=2e-4)


def test_unowned_compile_after_the_warm_mark_leaves_the_gate_alone(
        warm_engine, caplog, monkeypatch):
    """What `correct` reads (the engine tracker's compiles_after_warmup)
    does not move; the process record says what it could not hear."""
    gate = warm_engine._cobs.snapshot()
    before = sysobs.PROCESS.snapshot()
    monkeypatch.setattr(sysobs.HOST, "at_warm", {})
    monkeypatch.setattr(sysobs.PROCESS, "warm", before["warm"])
    sysobs.mark_warm()                   # what LoadModel does as it returns
    assert sysobs.HOST.at_warm["rss_bytes"] > 0
    with caplog.at_level("WARNING", logger="localai_tpu.sysobs"):
        _compile_on_bare_thread("late-compiler")
    after = sysobs.PROCESS.snapshot()
    assert after["warm"] is True
    assert after["unowned_after_warmup"] == before["unowned_after_warmup"] + 1
    assert after["unowned_last"][-1]["after_warmup"] is True
    assert warm_engine._cobs.snapshot() == gate
    warned = [json.loads(r.getMessage()) for r in caplog.records
              if "compile_after_warmup_unowned" in r.getMessage()]
    assert len(warned) == 1 and warned[0]["thread"] == "late-compiler"
    assert warned[0]["where"].startswith(__file__)
    # /debug/state carries the same record, the gate's counters beside it
    st = warm_engine.state_snapshot()
    assert st["compiles_process"]["unowned_after_warmup"] \
        == after["unowned_after_warmup"]
    assert st["compiles"]["compiles_after_warmup"] \
        == gate["compiles_after_warmup"]
    assert st["host_memory"]["at_warm"] == sysobs.HOST.at_warm


def test_full_collections_are_timed_from_the_collectors_own_callback():
    import gc
    import time

    sysobs.install_listener()
    assert sysobs.GC_FULL.on_gc in gc.callbacks
    sysobs.install_listener()                    # idempotent: hooked once
    assert gc.callbacks.count(sysobs.GC_FULL.on_gc) == 1
    gc.disable()                 # only the passes this test asks for
    try:
        before = sysobs.GC_FULL.snapshot()
        gc.collect(0)                            # a young pass: not counted
        assert sysobs.GC_FULL.snapshot()["passes"] == before["passes"]
        junk = [[i] for i in range(200000)]      # something to walk
        t0 = time.monotonic()
        gc.collect()
        t1 = time.monotonic()
        after = sysobs.GC_FULL.snapshot()
    finally:
        gc.enable()
    assert after["passes"] == before["passes"] + 1
    took = after["seconds"] - before["seconds"]
    assert 0 < took <= t1 - t0 + 1e-3
    assert after["last"][-1]["seconds"] == pytest.approx(took, abs=2e-4)
    assert 0 <= after["last"][-1]["ago_s"] < 5 and len(after["last"]) <= 4
    # the seconds of it that fall inside a wait: all, none, the first half
    within = sysobs.GC_FULL.seconds_within
    assert within(t0, t1) == pytest.approx(took, abs=2e-4)
    assert within(t1 + 1, t1 + 2) == 0.0
    p0, p1, _n = sysobs.GC_FULL._last[-1]
    assert within(t0, (p0 + p1) / 2) == pytest.approx(took / 2, abs=2e-4)
    del junk


# -------------------------------------------- the runner's resident memory

_STATUS_TEXT = """Name:\tpython3
Umask:\t0022
State:\tS (sleeping)
VmPeak:\t 40123456 kB
VmSize:\t 39000000 kB
VmHWM:\t 24023436 kB
VmRSS:\t  9876544 kB
RssAnon:\t  8000000 kB
RssFile:\t  1876000 kB
RssShmem:\t      544 kB
VmData:\t 30000000 kB
Threads:\t97
"""


def test_host_memory_parses_a_proc_status_text():
    assert sysobs.parse_proc_status(_STATUS_TEXT) == {
        "rss_bytes": 9876544 * 1024, "rss_peak_bytes": 24023436 * 1024,
        "rss_anon_bytes": 8000000 * 1024, "rss_file_bytes": 1876000 * 1024,
        "rss_shmem_bytes": 544 * 1024, "vm_size_bytes": 39000000 * 1024,
        "vm_data_bytes": 30000000 * 1024}
    # an older kernel's text has no split; a torn line is left out
    assert sysobs.parse_proc_status("VmRSS:\t 12 kB\nVmHWM:\nRssAnon") \
        == {"rss_bytes": 12 * 1024}
    assert sysobs.parse_proc_status("") == {}


# what the benchmark machine's kernel (gVisor) gives: no high-water mark,
# no split (the first chip run of PR 43 died of a KeyError on it)
_SANDBOX_STATUS_TEXT = """Name:\tpython3
VmSize:\t  779144 kB
VmRSS:\t  300908 kB
VmData:\t  514472 kB
Threads:\t13
"""


def test_host_memory_on_a_kernel_without_a_high_water_mark(tmp_path):
    import resource

    path = tmp_path / "status"
    path.write_text(_SANDBOX_STATUS_TEXT)
    hm = sysobs.host_memory(str(path))
    assert set(hm) == {"rss_bytes", "rss_peak_bytes", "vm_size_bytes",
                       "vm_data_bytes"}
    # getrusage's ru_maxrss, the same counter, and never under the reading
    assert hm["rss_peak_bytes"] == max(300908 * 1024, resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024)
    args = {}
    rec = sysobs.HostMemory()
    real = sysobs.host_memory
    sysobs.host_memory = lambda: real(str(path))
    try:
        rec.on_load_span("load_cast", args)
    finally:
        sysobs.host_memory = real
    assert args == {"rss_mb": 308.1, "rss_peak_mb": round(
        hm["rss_peak_bytes"] / 1e6, 1)}
    assert rec.peak_in_load["span"] == "load_cast"


def test_host_memory_reads_this_process_and_gives_nothing_where_it_cannot(
        tmp_path, monkeypatch):
    hm = sysobs.host_memory()
    assert set(hm) == {"rss_bytes", "rss_peak_bytes", "rss_anon_bytes",
                       "rss_file_bytes", "rss_shmem_bytes", "vm_size_bytes",
                       "vm_data_bytes"}
    assert 0 < hm["rss_bytes"] <= hm["rss_peak_bytes"]
    assert hm["rss_anon_bytes"] + hm["rss_file_bytes"] \
        + hm["rss_shmem_bytes"] == pytest.approx(hm["rss_bytes"], rel=0.05)
    assert sysobs.host_memory(str(tmp_path / "no-such-file")) == {}
    # a load span on a system without /proc records no number, no error
    args = {}
    rec = sysobs.HostMemory()
    monkeypatch.setattr(sysobs, "host_memory", lambda: {})
    rec.on_load_span("load_cast", args)
    rec.mark_warm()
    assert args == {}
    assert rec.snapshot() == {"at_warm": {}, "peak_in_load": {}}


def test_load_spans_say_where_the_peak_rose(monkeypatch):
    readings = iter([(5, 5), (9, 9), (4, 9), (6, 9)])

    def fake():
        now, peak = next(readings)
        return {"rss_bytes": now * 10**6, "rss_peak_bytes": peak * 10**6}

    monkeypatch.setattr(sysobs, "host_memory", fake)
    rec = sysobs.HostMemory()
    spans = []
    for name, leaf in (("load_source", "wq"), ("load_cast", "w_down"),
                       ("load_source", "embed"), ("load_precompile", None)):
        args = {"leaf": leaf} if leaf else {}
        rec.on_load_span(name, args)
        spans.append(args)
    assert [a["rss_mb"] for a in spans] == [5.0, 9.0, 4.0, 6.0]
    assert [a["rss_peak_mb"] for a in spans] == [5.0, 9.0, 9.0, 9.0]
    # the span at whose exit the high-water mark LAST rose
    assert rec.peak_in_load == {"bytes": 9 * 10**6, "span": "load_cast",
                                "leaf": "w_down"}


# ------------------------------------------------------------- watermarks

def test_watermarks_max_fold():
    wm = sysobs.Watermarks()
    wm.sample(pool=3, host=0)
    wm.sample(pool=7, host=None)   # None samples are skipped
    wm.sample(pool=2, host=5)
    assert wm.peak("pool") == 7
    assert wm.snapshot() == {"peak_host": 5, "peak_pool": 7}


def test_engine_watermarks_match_pool_accounting(warm_engine,
                                                 byte_tokenizer,
                                                 monkeypatch):
    _gen(warm_engine, byte_tokenizer)
    m = warm_engine.metrics()
    so = m["sysobs"]
    wm = so["watermarks"]
    pool = warm_engine._pool
    # a served request must have left a high-water mark, and no peak can
    # exceed the physical pool
    assert wm["peak_pool_pages_in_use"] >= 1
    assert wm["peak_pool_pages_in_use"] <= pool.num_pages
    # the one host gauge, folded by the loop every half second and NOT by
    # the fold a /metrics pull or an admission runs
    import time

    t_end = time.monotonic() + 30
    while "peak_host_rss_bytes" not in warm_engine._wm.snapshot() \
            and time.monotonic() < t_end:
        time.sleep(0.05)
    rss = sysobs.host_memory()["rss_bytes"]
    assert 0.5 * rss < warm_engine._wm.peak("host_rss_bytes") < 2 * rss
    calls = []
    monkeypatch.setattr(sysobs, "host_memory", lambda: calls.append(
        threading.current_thread().name) or {})
    warm_engine._sample_watermarks()
    assert threading.current_thread().name not in calls   # the loop's only
    assert so["host_memory"]["rss_bytes"] > 0       # what /metrics exports
    assert wm["peak_slots_active"] >= 1
    assert wm["peak_tokens_total"] >= 1
    # weight bytes: computed from the actual param tree, so > 0
    assert so["weight_bytes"] > 0
    frag = so["fragmentation"]
    assert frag["free_pages"] == pool.free_pages
    assert frag["hole_pages"] + frag["tail_pages"] == frag["free_pages"]


def test_pagepool_fragmentation_holes_vs_tail():
    pool = PagePool(num_slots=2, max_context=64, page_size=16)  # 8 pages
    assert pool.fragmentation() == {"free_pages": 8, "tail_pages": 8,
                                    "hole_pages": 0, "ratio": 0.0}
    # pages pop from the free-list head (0,1,2): freeing page 1 leaves a
    # HOLE below the in-use region while 3..7 remain the contiguous tail
    pages = [pool.alloc_detached() for _ in range(3)]
    assert pages == [0, 1, 2]
    pool.unref_detached(1)
    frag = pool.fragmentation()
    assert frag["free_pages"] == 6
    assert frag["tail_pages"] == 5   # 3..7
    assert frag["hole_pages"] == 1   # page 1
    assert frag["ratio"] == pytest.approx(1 / 6, abs=1e-4)


# ------------------------------------------------------------ goodput/MFU

def test_flops_per_token_hand_computed():
    cfg = llama.LlamaConfig(
        vocab_size=100, hidden_size=8, intermediate_size=16,
        num_layers=2, num_heads=2, num_kv_heads=1,
        max_position_embeddings=64,
    )
    # head_dim = 8/2 = 4; q = 2*4 = 8 cols; kv = 1*4 = 4 cols
    per_layer = (8 * 8          # q proj
                 + 2 * 8 * 4    # k,v proj
                 + 8 * 8        # o proj
                 + 3 * 8 * 16)  # gate/up/down
    expect = 2.0 * (2 * per_layer + 8 * 100)
    assert sysobs.flops_per_token(cfg) == expect
    # attention term: 4 * layers * ctx * hidden
    assert (sysobs.flops_per_token(cfg, ctx=10)
            == expect + 4.0 * 2 * 10 * 8)


def test_goodput_meter_and_mfu():
    m = sysobs.GoodputMeter(flops_per_tok=1e9, peak_flops=1e12)
    m.add(100)
    m.add(50)
    snap = m.snapshot()
    assert snap["goodput_tokens_total"] == 150
    assert snap["goodput_requests_total"] == 2
    # at an explicit 100 tok/s: 100 * 1e9 / 1e12 = 0.1 MFU
    assert m.mfu(tok_s=100.0) == pytest.approx(0.1)


def test_mfu_honest_zero_without_peak():
    m = sysobs.GoodputMeter(flops_per_tok=1e9, peak_flops=0.0)
    m.add(1000)
    assert m.mfu(tok_s=1e6) == 0.0


def test_peak_device_flops_is_a_table_keyed_by_device_kind(monkeypatch):
    """The chip reports "TPU v5 lite" (not "v5e"); a CPU is "not
    measured" (0.0); an accelerator outside the table is an error, and
    no environment variable can assume a rate."""
    import types

    import jax

    dev = lambda plat, kind: types.SimpleNamespace(  # noqa: E731
        platform=plat, device_kind=kind)
    assert sysobs.peak_device_flops(dev("tpu", "TPU v5 lite")) == 197e12
    assert sysobs.peak_device_flops(jax.devices()[0]) == 0.0
    monkeypatch.setenv("LOCALAI_PEAK_TFLOPS", "2.5")
    assert sysobs.peak_device_flops(dev("cpu", "cpu")) == 0.0
    with pytest.raises(ValueError, match="TPU v9"):
        sysobs.peak_device_flops(dev("tpu", "TPU v9"))


def test_engine_goodput_counts_only_completions(warm_engine,
                                                byte_tokenizer):
    before = warm_engine.metrics()["sysobs"]["goodput"]
    _gen(warm_engine, byte_tokenizer, n=5)
    after = warm_engine.metrics()["sysobs"]["goodput"]
    assert (after["goodput_tokens_total"]
            == before["goodput_tokens_total"] + 5)
    assert (after["goodput_requests_total"]
            == before["goodput_requests_total"] + 1)


# --------------------------------------------------------------- eventlog

def test_eventlog_ring_bounded_and_ordered():
    ev = EventLog(sink="off", ring_size=16)
    for i in range(100):
        ev.emit("tick", n=i)
    evs = ev.events()
    assert len(evs) == 16
    assert [e["n"] for e in evs] == list(range(84, 100))
    assert evs[-1]["seq"] == 100
    assert ev.events(last=3) == evs[-3:]
    assert ev.snapshot()["ring_size"] == 16


def test_eventlog_file_sink_write_through(tmp_path):
    path = tmp_path / "events.jsonl"
    ev = EventLog(sink=str(path), ring_size=8)
    ev.emit("admit", rid="r1", queued=2)
    ev.emit("shed", rid="r2", reason="queue_full")
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [ln["event"] for ln in lines] == ["admit", "shed"]
    assert lines[0]["rid"] == "r1"
    assert lines[1]["reason"] == "queue_full"


def test_eventlog_bad_sink_never_raises():
    ev = EventLog(sink="/nonexistent-dir-xyz/events.jsonl", ring_size=4)
    ev.emit("still_works")   # ring-only fallback
    assert ev.sink == "off"
    assert ev.events()[-1]["event"] == "still_works"


def test_engine_lifecycle_events_have_correlation_ids(warm_engine,
                                                      byte_tokenizer):
    _gen(warm_engine, byte_tokenizer)
    evs = EVENTS.events()
    admits = [e for e in evs if e["event"] == "admit"]
    completes = [e for e in evs if e["event"] == "complete"]
    assert admits and completes
    # the completion's rid pivots back to its admission
    assert completes[-1]["rid"] in {e["rid"] for e in admits}
    assert completes[-1]["completion_tokens"] >= 1


# ------------------------------------------------- state snapshot (engine)

def test_engine_state_snapshot_shape(warm_engine, byte_tokenizer):
    _gen(warm_engine, byte_tokenizer)
    s = warm_engine.state_snapshot()
    assert s["warm"] is True
    assert len(s["slots"]) == 2
    assert s["queued"] == 0
    assert s["compiles"]["compiles_total"] > 0
    assert s["weight_bytes"] > 0
    pool = s["pool"]
    assert pool["pages_total"] == warm_engine._pool.num_pages
    assert len(pool["pages_per_slot"]) == 2
    assert "fragmentation" in pool
    json.dumps(s)   # the snapshot must be JSON-serializable as-is


# ------------------------------------------------------- HTTP debug surface

@pytest.fixture(scope="module")
def server():
    from localai_tpu.api.app import build_app, run_app
    from localai_tpu.backend.fake import FakeServicer
    from localai_tpu.capabilities import Capabilities
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.modelmgr.loader import ModelLoader
    from localai_tpu.modelmgr.process import free_port

    port = free_port()
    app_config = AppConfig(models_path="/tmp/localai-test-models",
                           address=f"127.0.0.1:{port}")
    loader = ModelLoader(health_attempts=100, health_interval_s=0.1)
    loader.register_embedded("fake", FakeServicer)
    configs = {"tiny": ModelConfig(name="tiny", backend="fake",
                                   model="tiny"),
               # /debug/kv shape variants (ISSUE 15): audited-off and
               # merged multi-replica views, loaded on demand by the
               # kv endpoint tests
               "tinyoff": ModelConfig(name="tinyoff", backend="fake",
                                      model="tiny",
                                      options=["kv_audit=off"]),
               "tinypool": ModelConfig(name="tinypool", backend="fake",
                                       model="tiny",
                                       options=["engines=2"])}
    caps = Capabilities(app_config, loader, configs)
    app = build_app(caps, app_config)

    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            await run_app(app, app_config.address)
            started.set()

        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(10)

    class H:
        base = f"http://127.0.0.1:{port}"

    # load "tiny" so the debug surfaces have a backend to pull from
    r = httpx.post(f"{H.base}/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "hello world"}],
    }, timeout=60)
    assert r.status_code == 200, r.text
    yield H
    loop.call_soon_threadsafe(loop.stop)
    loader.stop_all()


def test_metrics_content_type_and_escaping(server):
    r = httpx.get(f"{server.base}/metrics")
    assert r.status_code == 200
    assert r.headers["content-type"].startswith(
        "text/plain; version=0.0.4")
    assert "localai_api_call_bucket" in r.text


def test_readyz_body_has_breakers_and_load(server):
    r = httpx.get(f"{server.base}/readyz")
    assert r.status_code == 200
    body = r.json()
    assert body["status"] == "ready"
    assert body["breakers"]["tiny"]["state"] == "closed"
    load = body["load"]["tiny"]
    assert load["queue_depth"] == 0
    assert load["slots_total"] == 1


def test_debug_state_endpoint(server):
    r = httpx.get(f"{server.base}/debug/state")
    assert r.status_code == 200
    body = r.json()
    assert body["uptime_s"] >= 0
    assert "tiny" in body["loader"]
    st = body["models"]["tiny"]
    assert st["warm"] is True
    assert st["compiles"]["compiles_total"] == 0
    assert "eventlog" in body


def test_debug_events_endpoint_merges_and_tags(server):
    EVENTS.emit("core_marker", detail="from-core")
    r = httpx.get(f"{server.base}/debug/events")
    assert r.status_code == 200
    evs = r.json()["events"]
    procs = {e["proc"] for e in evs}
    assert "core" in procs
    assert "backend:tiny" in procs   # the fake's ring rode GetState
    assert any(e["event"] == "core_marker" for e in evs)
    # time-ordered merge
    ts = [e.get("ts", 0.0) for e in evs]
    assert ts == sorted(ts)
    # ?last trims to the most recent N
    r2 = httpx.get(f"{server.base}/debug/events", params={"last": 1})
    assert len(r2.json()["events"]) == 1


def test_debug_kv_endpoint(server):
    r = httpx.get(f"{server.base}/debug/kv")
    assert r.status_code == 200
    kv = r.json()["models"]["tiny"]
    assert kv["mode"] == "on"
    assert kv["pool"]["pages_total"] == 8
    assert kv["pool"]["free"] + kv["pool"]["active"] + kv["pool"][
        "retained"] == kv["pool"]["pages_total"]
    aud = kv["audit"]
    assert aud["violations"] == 0 and aud["last_violations"] == []
    assert aud["ledger"]["counts"]["alloc"] >= 1
    assert kv["ledger_tail"][0]["op"] == "alloc"
    assert kv["chains"][0]["depth"] == 0
    assert "host" in kv


def test_debug_kv_endpoint_off_and_multi_replica_shapes(server):
    for name in ("tinyoff", "tinypool"):
        r = httpx.post(f"{server.base}/v1/chat/completions", json={
            "model": name,
            "messages": [{"role": "user", "content": "hello"}],
        }, timeout=60)
        assert r.status_code == 200, r.text
    models = httpx.get(f"{server.base}/debug/kv").json()["models"]
    # kv_audit=off: no auditor, no ledger — just the mode marker
    off = models["tinyoff"]
    assert off["mode"] == "off" and "ledger_tail" not in off
    # engines=2: the pool's merged view, one entry per replica
    tp = models["tinypool"]
    assert tp["engine_replicas"] == 2
    assert [r["replica"] for r in tp["replicas"]] == [0, 1]
    assert all(r["audit"]["violations"] == 0 for r in tp["replicas"])
    assert "shared_host" in tp and "pool_index_keys" in tp


# -------------------------------------------------------------- exemplars

def _parse_prom(text):
    out = {}
    for ln in text.splitlines():
        if ln.startswith("#"):
            continue
        out.setdefault(ln.split("{")[0].split(" ")[0], []).append(ln)
    return out


def test_exemplar_rides_matching_bucket():
    m = Metrics()
    m.set_histogram("ttft_seconds", label_str(model="m1"),
                    [0.1, 1.0, 10.0], [2, 3, 1, 0], 4.2, 6)
    m.set_exemplar("ttft_seconds", label_str(model="m1"),
                   0.5, "req-worst", ts=1234.5)
    lines = _parse_prom(m.render())["localai_ttft_seconds_bucket"]
    tagged = [ln for ln in lines if "# {" in ln]
    assert len(tagged) == 1
    # 0.5 falls in the le="1.0" bucket
    assert 'le="1.0"' in tagged[0]
    assert 'trace_id="req-worst"' in tagged[0]
    assert tagged[0].rstrip().endswith("0.5 1234.500")


def test_exemplar_over_top_bucket_lands_on_inf():
    m = Metrics()
    m.set_histogram("itl_seconds", label_str(model="m1"),
                    [0.1, 1.0], [1, 1, 1], 20.0, 3)
    m.set_exemplar("itl_seconds", label_str(model="m1"), 15.0, "slowest")
    lines = _parse_prom(m.render())["localai_itl_seconds_bucket"]
    tagged = [ln for ln in lines if "# {" in ln]
    assert len(tagged) == 1
    assert 'le="+Inf"' in tagged[0]


def test_label_value_escaping():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert label_str(model='we"ird') == 'model="we\\"ird"'
    # sorted for stable exposition
    assert label_str(b="2", a="1") == 'a="1",b="2"'


def test_clear_instrument_drops_exemplars():
    m = Metrics()
    m.set_histogram("h", label_str(model="x"), [1.0], [1, 0], 0.5, 1)
    m.set_exemplar("h", label_str(model="x"), 0.5, "t")
    m.clear_instrument("h")
    assert "# {" not in m.render()
