"""End-to-end request observability (ISSUE 12): cross-process trace
propagation (HTTP -> gRPC metadata -> backend ring -> ONE merged
timeline), the LoadModel clock handshake, the per-class SLO engine with
hand-checked burn-rate arithmetic, the violation flight recorder, and
the slo_* config-knob validation."""

import asyncio
import json
import os
import threading

import httpx
import jax
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.models import llama
from localai_tpu.modelmgr.loader import _parse_handshake, measure_clock
from localai_tpu.services import sysobs
from localai_tpu.services.eventlog import EVENTS


# ----------------------------------------------------- slo spec parsing

def test_parse_slo_classes_shapes():
    assert sysobs.parse_slo_classes("") == {}
    assert sysobs.parse_slo_classes("  ") == {}
    assert sysobs.parse_slo_classes("500") == {
        "high": 500.0, "normal": 500.0, "low": 500.0}
    assert sysobs.parse_slo_classes("250:1000:5000") == {
        "high": 250.0, "normal": 1000.0, "low": 5000.0}
    assert sysobs.parse_slo_classes("high=250:low=5000") == {
        "high": 250.0, "low": 5000.0}


@pytest.mark.parametrize("bad", [
    "250:1000",            # wrong positional count
    "hgih=250",            # typo'd class name
    "high=250:1000",       # mixed named and positional
    "high=0",              # threshold must be > 0
    "-5",                  # negative
    "high=abc",            # not a number
])
def test_parse_slo_classes_rejects(bad):
    with pytest.raises(ValueError):
        sysobs.parse_slo_classes(bad)


# ------------------------------------------------- burn-rate arithmetic

class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_burn_rate_hand_checked():
    """90 good + 10 bad samples at a 1% error budget: the violation
    fraction is 0.10, so burn = 0.10 / 0.01 = exactly 10x."""
    clk = _FakeClock()
    slo = sysobs.SLOEngine({"ttft_ms": {"normal": 100.0}},
                           error_budget=0.01, clock=clk)
    for _ in range(90):
        assert slo.observe("ttft_ms", "normal", 50.0) is None
    for _ in range(10):
        v = slo.observe("ttft_ms", "normal", 150.0, rid="r-slow")
        assert v == {"metric": "ttft_ms", "class": "normal",
                     "value_ms": 150.0, "objective_ms": 100.0,
                     "rid": "r-slow"}
    snap = slo.snapshot()
    s = snap["classes"]["normal"]["ttft_ms"]
    assert s["burn_5m"] == pytest.approx(10.0)
    assert s["burn_1h"] == pytest.approx(10.0)
    assert s["n_5m"] == 100
    assert s["violations"] == 10
    assert snap["violations_total"] == 10


def test_burn_rate_window_expiry():
    """Samples age out of the 5m window but stay in the 1h one."""
    clk = _FakeClock()
    slo = sysobs.SLOEngine({"ttft_ms": {"low": 10.0}},
                           error_budget=0.01, clock=clk)
    for _ in range(4):
        slo.observe("ttft_ms", "low", 99.0)   # all violations
    s = slo.snapshot()["classes"]["low"]["ttft_ms"]
    assert s["burn_5m"] == pytest.approx(100.0)   # 100% / 1%
    clk.t += 301.0                                # past 5m, inside 1h
    s = slo.snapshot()["classes"]["low"]["ttft_ms"]
    assert s["n_5m"] == 0
    assert s["burn_5m"] == 0.0
    assert s["burn_1h"] == pytest.approx(100.0)
    clk.t += 3600.0                               # past 1h too
    s = slo.snapshot()["classes"]["low"]["ttft_ms"]
    assert s["burn_1h"] == 0.0


def test_no_objective_is_cheap_noop():
    slo = sysobs.SLOEngine({"ttft_ms": {"high": 100.0}})
    # class without an objective, and metric without one: both no-ops
    assert slo.observe("ttft_ms", "low", 1e9) is None
    assert slo.observe("itl_ms", "high", 1e9) is None
    assert slo.snapshot()["violations_total"] == 0
    assert not sysobs.SLOEngine({}).enabled
    assert slo.enabled


def test_burn_events_fire_and_rate_limit():
    clk = _FakeClock()
    slo = sysobs.SLOEngine({"ttft_ms": {"low": 10.0}}, error_budget=0.01,
                           clock=clk, burn_event_interval_s=30.0)
    slo.observe("ttft_ms", "low", 99.0)
    evs = slo.burn_events()
    assert len(evs) == 1
    assert evs[0]["metric"] == "ttft_ms"
    assert evs[0]["class"] == "low"
    assert evs[0]["window"] == "5m"
    assert evs[0]["burn"] > 1
    # within the interval: suppressed; after it: fires again
    assert slo.burn_events() == []
    clk.t += 31.0
    slo.observe("ttft_ms", "low", 99.0)
    assert len(slo.burn_events()) == 1
    # a healthy pair never emits
    ok = sysobs.SLOEngine({"ttft_ms": {"high": 1e6}}, clock=clk)
    ok.observe("ttft_ms", "high", 1.0)
    assert ok.burn_events() == []


# ------------------------------------------------------ flight recorder

def test_flight_recorder_dump_and_rate_limit(tmp_path):
    clk = _FakeClock()
    fr = sysobs.FlightRecorder(str(tmp_path), min_interval_s=30.0,
                               clock=clk)
    p1 = fr.dump("slo:ttft_ms:low", {"state": {"x": 1}}, tag="slo")
    assert p1 and os.path.exists(p1)
    doc = json.loads(open(p1).read())
    assert doc["reason"] == "slo:ttft_ms:low"
    assert doc["state"] == {"x": 1}
    # inside the interval: suppressed, counted
    assert fr.dump("slo:again", {}) == ""
    assert fr.snapshot()["dumps"] == 1
    assert fr.snapshot()["suppressed"] == 1
    clk.t += 31.0
    assert fr.dump("slo:later", {}) != ""
    assert fr.snapshot()["dumps"] == 2


def test_flight_recorder_bounded_disk(tmp_path):
    clk = _FakeClock()
    fr = sysobs.FlightRecorder(str(tmp_path), min_interval_s=0.0,
                               max_dumps=3, clock=clk)
    paths = []
    for i in range(6):
        clk.t += 1.0
        paths.append(fr.dump(f"r{i}", {"i": i}))
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("localai-flight-")]
    assert len(files) == 3                      # pruned to max_dumps
    assert os.path.exists(paths[-1])            # newest kept
    assert not os.path.exists(paths[0])         # oldest pruned


def test_flight_recorder_falls_back_to_tempdir():
    import tempfile

    # no configured stall_dump_dir: dumps still land somewhere (the
    # system tempdir), still rate-limited and disk-bounded
    fr = sysobs.FlightRecorder("")
    assert fr.out_dir == tempfile.gettempdir()
    assert fr.snapshot()["dir"] == tempfile.gettempdir()


# ------------------------------------------------------- clock handshake

def test_parse_handshake_midpoint_math():
    hs = _parse_handshake(json.dumps({
        "status": "loaded",
        "handshake": {"wall": 2000.0, "mono": 5.0,
                      "trace_epoch": 1999.5, "pid": 424242},
    }))
    # the LoadModel reply names the backend; the offset is never taken
    # from its round trip (a load lasts minutes)
    assert "offset_s" not in hs and "rtt_s" not in hs
    assert hs["backend_wall"] == 2000.0
    assert hs["backend_pid"] == 424242
    assert hs["trace_epoch"] == 1999.5
    # the midpoint math lives in measure_clock, over Health round trips
    times = iter([1000.0, 1000.2])
    import localai_tpu.modelmgr.loader as ld

    real = ld.time.time
    ld.time.time = lambda: next(times, 1000.2)
    try:
        c = measure_clock(lambda: 2000.0, trips=1)
    finally:
        ld.time.time = real
    assert c["offset_s"] == pytest.approx(2000.0 - 1000.1)
    assert c["rtt_s"] == pytest.approx(0.2)


@pytest.mark.parametrize("message", [
    "loaded",                      # legacy plain-string reply
    "",                            # empty
    "{}",                          # JSON without a handshake
    '{"handshake": {}}',           # handshake without a wall stamp
    '{"handshake": {"wall": "x"}}',  # non-numeric stamp
])
def test_parse_handshake_tolerates_legacy(message):
    assert _parse_handshake(message) == {}


# ----------------------------------------------------- config validation

def test_model_config_validates_slo_knobs():
    from localai_tpu.config.model_config import ModelConfig

    good = ModelConfig(name="m", backend="llama", model="m", options=[
        "slo_ttft_ms=high=250:low=5000", "slo_itl_ms=100",
        "slo_queue_wait_ms=50:100:200", "slo_error_budget=0.05"])
    assert good.validate() == []

    bad = ModelConfig(name="m", backend="llama", model="m",
                      options=["slo_ttft_ms=hgih=250"])
    assert any("SLO" in p or "slo" in p for p in bad.validate())

    bad_budget = ModelConfig(name="m", backend="llama", model="m",
                             options=["slo_error_budget=1.5"])
    assert bad_budget.validate()
    bad_budget0 = ModelConfig(name="m", backend="llama", model="m",
                              options=["slo_error_budget=0"])
    assert bad_budget0.validate()


# --------------------------------------------- engine-level integration

@pytest.fixture(scope="module")
def slo_engine(byte_tokenizer, tmp_path_factory):
    """Tiny engine with an impossible low-class TTFT objective and an
    unlimited-rate flight recorder: every low request must violate.
    Module-scoped — engine bring-up dominates tier-1 cost, and the two
    consumers touch disjoint state (per-rid events / consumed-on-pull
    exemplars)."""
    tmp_path = tmp_path_factory.mktemp("slo-flight")
    cfg = llama.LlamaConfig(
        vocab_size=258, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=256,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = eng.EngineConfig(num_slots=2, max_context=64,
                            prefill_buckets=(16,),
                            slo_ttft_ms="high=60000:low=0.001",
                            stall_dump_dir=str(tmp_path))
    e = eng.Engine(cfg, params, byte_tokenizer, ecfg)
    e._flight = sysobs.FlightRecorder(str(tmp_path), min_interval_s=0.0)
    e.start(precompile=True)
    yield e, str(tmp_path)
    e.shutdown()


def _gen(engine, tok, priority, n=4):
    req = eng.GenRequest(
        prompt_ids=tok.encode("slo probe"), priority=priority,
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, ignore_eos=True,
    )
    engine.generate_text(req)
    return req.request_id


def test_engine_slo_violation_dumps_and_events(slo_engine, byte_tokenizer):
    engine, dump_dir = slo_engine
    rid = _gen(engine, byte_tokenizer, "low")
    _gen(engine, byte_tokenizer, "high")

    m = engine.metrics()
    slo = m["slo"]
    low = slo["classes"]["low"]["ttft_ms"]
    high = slo["classes"]["high"]["ttft_ms"]
    assert low["violations"] >= 1
    assert low["burn_5m"] > 1
    assert high["violations"] == 0
    assert high["burn_5m"] == 0.0
    assert high["n_5m"] >= 1          # the sample recorded, cleanly

    evs = EVENTS.events()
    viol = [e for e in evs if e["event"] == "slo_violation"
            and e["rid"] == rid]
    assert viol and viol[-1]["cls"] == "low"
    assert viol[-1]["metric"] == "ttft_ms"
    dumps = [e for e in evs if e["event"] == "flight_dump"]
    assert dumps

    files = [f for f in os.listdir(dump_dir)
             if f.startswith("localai-flight-") and f.endswith(".json")]
    assert files
    doc = json.loads(open(os.path.join(dump_dir, sorted(files)[0])).read())
    # the dump is the full forensic bundle: merged-trace + state + events
    assert any(v["class"] == "low" for v in doc["violations"])
    assert "traceEvents" in doc["trace"]
    assert "slots" in doc["state"]
    assert isinstance(doc["events"], list)

    # the recorder's own counters ride metrics() and the state snapshot
    assert m["flight_recorder"]["dumps"] >= 1
    assert engine.state_snapshot()["flight_recorder"]["dumps"] >= 1
    assert "slo" in engine.state_snapshot()


def test_exemplar_carries_propagated_trace_id(slo_engine, byte_tokenizer):
    """Cross-process exemplar closure (PR-8 follow-up): the request_id a
    backend engine sees IS the frontend correlation id (runner copies
    localai-trace-id into GenRequest.request_id), so the worst-span
    exemplar the /metrics scrape re-exports points at the same id the
    HTTP process minted — one id from client header to histogram tag."""
    engine, _ = slo_engine
    req = eng.GenRequest(
        prompt_ids=byte_tokenizer.encode("exemplar probe"),
        request_id="corr-id-from-http", priority="high",
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=4, ignore_eos=True,
    )
    engine.generate_text(req)
    ex = engine.metrics().get("hist_exemplars") or {}
    assert ex.get("ttft_seconds", {}).get("trace_id") == "corr-id-from-http"
    # consumed on pull: the next scrape sees only newer worst spans
    assert "ttft_seconds" not in (engine.metrics().get("hist_exemplars")
                                  or {})


def test_engine_without_objectives_has_no_slo_layer(byte_tokenizer):
    cfg = llama.LlamaConfig(
        vocab_size=258, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=256,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    e = eng.Engine(cfg, params, byte_tokenizer,
                   eng.EngineConfig(num_slots=2, max_context=64,
                                    prefill_buckets=(16,)))
    # not started: the knob wiring is an init-time property
    assert e._slo is None
    assert "slo" not in e.metrics()
    assert "slo" not in e.state_snapshot()


# ------------------------------------ HTTP -> gRPC -> backend, end to end

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
    servicers = []
    loader.register_embedded(
        "fake", lambda: servicers.append(FakeServicer()) or servicers[-1])
    configs = {"tiny": ModelConfig(name="tiny", backend="fake",
                                   model="tiny")}
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

    H.loader = loader
    H.servicers = servicers
    r = httpx.post(f"{H.base}/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "hello world"}],
    }, timeout=60)
    assert r.status_code == 200, r.text
    yield H
    loop.call_soon_threadsafe(loop.stop)
    loader.stop_all()


def test_clock_handshake_measured_on_load(server):
    lm = server.loader.get("tiny")
    clock = lm.clock
    # the fake replies with a handshake; same machine, so the offset is
    # bounded by the rpc round-trip (the honest uncertainty bound)
    assert clock, "LoadModel handshake missing"
    assert abs(clock["offset_s"]) <= clock["rtt_s"] + 0.05
    assert clock["backend_pid"] == os.getpid()    # embedded: same process
    assert clock["trace_epoch"] > 0


def test_trace_id_propagates_over_grpc_metadata(server):
    rid = "trace-prop-e2e-1"
    r = httpx.post(f"{server.base}/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "trace me"}],
    }, headers={"X-Correlation-ID": rid}, timeout=60)
    assert r.status_code == 200
    seen = [md for s in server.servicers for md in s.seen_metadata]
    assert any(md.get("localai-trace-id") == rid for md in seen), seen
    # the priority class rides the same metadata hop (mirrored knob)
    assert all("localai-trace-id" in md for md in seen if md)


def test_debug_trace_merges_one_timeline(server):
    rid = "trace-merge-e2e-1"
    r = httpx.post(f"{server.base}/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "merge me"}],
    }, headers={"X-Correlation-ID": rid}, timeout=60)
    assert r.status_code == 200
    doc = httpx.get(f"{server.base}/debug/trace", timeout=30).json()
    doc = json.loads(json.dumps(doc))      # perfetto-loadable round-trip
    assert doc["displayTimeUnit"] == "ms"
    procs = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert "localai-http" in procs
    assert any(p != "localai-http" for p in procs)
    # ONE merged timeline: the SAME request id under BOTH pids
    pids = {e["pid"] for e in doc["traceEvents"]
            if (e.get("args") or {}).get("request_id") == rid}
    assert len(pids) >= 2, doc["traceEvents"]
    # clock block: per-backend offset/rtt/shift from the handshake
    clocks = doc["localai"]["clocks"]
    assert "tiny" in clocks
    for k in ("offset_s", "rtt_s", "shift_us"):
        assert k in clocks["tiny"]
    # all X-event timestamps are finite numbers after the shift
    for e in doc["traceEvents"]:
        if e.get("ph") == "X":
            assert isinstance(e["ts"], (int, float))


def test_metrics_render_with_new_instruments(server):
    # the clear-list now includes slo_*/mem_device_*/flight_* names; a
    # fake-backed scrape must render cleanly without those series
    r = httpx.get(f"{server.base}/metrics", timeout=30)
    assert r.status_code == 200
    assert "localai_api_call" in r.text
