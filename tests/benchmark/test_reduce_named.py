"""reduce_named's arithmetic on a hand-made capture, its protobuf reader on
a hand-encoded file, the recorded chip capture
(tests/benchmark/data/named_v5e.json.gz), and every new reader on a run
that has nothing for it to read."""

import gzip
import json
import os
import types

import pytest

from benchmark import reduce_named as rn
from benchmark import spec

DATA = os.path.join(os.path.dirname(__file__), "data", "named_v5e.json.gz")
US = 1000
NEW_METRICS = (
    "host_bound_idle_pct", "no_work_idle_pct", "decode_program_ms_per_step",
    "prefill_device_share_pct", "mlp_proj_share_pct",
    "decode_tail_share_pct", "load_quantize_s", "load_cast_put_s",
    "load_source_s", "load_precompile_s", "decode_batch_mean",
    "span_window_lost_s")


def _capture():
    """Device (its clock 10 us ahead of the host's): a prefill program
    0-100 us, a decode program 300-500 us, a second 700-900 us.
    Host: the loop packs a prompt 80-250, dispatches 250-310, parks with
    nothing queued 320-680, dispatches 680-720."""
    ops = [["fusion.1_fusion", 0, 100 * US],                    # prefill
           # decode program 1: mlp 80, attention kernel 60, sampling 40,
           # a compiler copy 20 (no scope)
           ["while.2_while", 300 * US, 200 * US],
           ["fusion.3_fusion", 300 * US, 80 * US],
           ["paged_decode_attention.4_custom-call", 380 * US, 60 * US],
           ["fusion.5_fusion", 440 * US, 40 * US],
           ["copy.6_copy", 480 * US, 20 * US],
           # decode program 2, the same operations
           ["fusion.3_fusion", 700 * US, 80 * US],
           ["paged_decode_attention.4_custom-call", 780 * US, 60 * US],
           ["fusion.5_fusion", 840 * US, 40 * US],
           ["copy.6_copy", 880 * US, 20 * US]]
    modules = [["jit_prefill_pack_head(11)", 0, 100 * US, 1],
               ["jit_decode_burst(22)", 300 * US, 200 * US, 2],
               ["jit_decode_burst(22)", 700 * US, 200 * US, 3]]
    host = [["clock_anchor", 0, 1 * US,
             {"monotonic_ns": 5_000_000_000, "epoch_ns": 1_000_000_000_000}],
            ["tick_prefill_pack", 90 * US, 170 * US, {}],
            ["tick_dispatch_decode", 260 * US, 60 * US, {}],
            ["tick_idle_wait", 330 * US, 360 * US, {"queued": 0}],
            ["tick_dispatch_decode", 690 * US, 40 * US, {}],
            # enqueue <= start and end <= completion bound the skew: 10 us
            ["DoEnqueueProgram", 305 * US, 1 * US, {"run_id": 2}],
            ["CompleteCallbacks", 515 * US, 1 * US, {"run_id": 2}]]
    scopes = {"22": {
        "fusion.3_fusion": "jit(decode_burst)/while/body/layer/mlp/dot_general",
        "paged_decode_attention.4_custom-call":
            "jit(decode_burst)/while/body/layer/attn/jit(x)/pallas_call",
        "fusion.5_fusion": "jit(decode_burst)/while/body/sample/sort"},
        "11": {"fusion.1_fusion": "jit(prefill_pack_head)/layer/mlp/dot"}}
    return {"device": [{"name": "/device:TPU:0", "modules": modules,
                        "ops": ops}], "host": host, "scopes": scopes}


def test_gaps_are_attributed_to_what_the_host_was_doing():
    out = rn.reduce(_capture())
    assert out["clock_skew_ns"] == {"shift": 10 * US, "low": 5 * US,
                                    "high": 15 * US}
    assert out["window_s"] == pytest.approx(900e-6)
    assert out["busy_s"] == pytest.approx(500e-6)
    # after the 10 us shift the gaps are 110-310 and 510-710 us (host clock)
    # gap 1: all of it under tick_prefill_pack / tick_dispatch_decode
    # gap 2: 510-690 parked with nothing queued (180), 690-710 dispatching
    assert out["idle_host_bound_s"] == pytest.approx(220e-6)
    assert out["idle_no_work_s"] == pytest.approx(180e-6)
    assert out["idle_other_s"] == pytest.approx(0.0, abs=1e-12)
    assert out["idle_by_phase"]["tick_idle_wait"] == pytest.approx(180e-6)
    assert out["idle_by_phase"]["tick_prefill_pack"] == pytest.approx(150e-6)
    assert out["idle_by_phase"]["tick_dispatch_decode"] == \
        pytest.approx(70e-6)
    total_idle = out["window_s"] - out["busy_s"]
    assert out["idle_host_bound_s"] + out["idle_no_work_s"] == \
        pytest.approx(total_idle)


def test_clock_skew_leans_on_the_completion_bound():
    mods = [["jit_decode_burst(1)", 10_000_000, 5_000_000, 7],
            ["jit_decode_burst(1)", 15_000_000, 5_000_000, 8]]
    # run 8 was enqueued a whole program before it started (pipelined): a
    # loose lower bound; its completion came 1.6 ms (host clock) after
    host = [["DoEnqueueProgram", 10_100_000, 1000, {"run_id": 8}],
            ["CompleteCallbacks", 21_600_000, 1000, {"run_id": 8}]]
    shift, lo, hi = rn._skew_ns(mods, host)
    assert (lo, hi) == (-4_900_000, 1_600_000) and shift == 1_400_000
    # a program that started on an idle device tightens it: the middle
    host.append(["DoEnqueueProgram", 11_300_000, 1000, {"run_id": 7}])
    assert rn._skew_ns(mods, host) == (1_450_000, 1_300_000, 1_600_000)
    assert rn._skew_ns(mods, []) == (0, None, None)


def test_a_parked_loop_with_requests_queued_is_not_no_work():
    cap = _capture()
    cap["host"][3][3]["queued"] = 2
    out = rn.reduce(cap)
    assert out["idle_no_work_s"] == 0.0
    assert out["idle_other_s"] == pytest.approx(180e-6)


def test_device_time_by_program_and_scope():
    out = rn.reduce(_capture())
    assert out["modules"]["jit_decode_burst"] == {
        "s": pytest.approx(400e-6), "n": 2.0}
    assert out["decode_module_s"] == pytest.approx(400e-6)
    assert out["prefill_module_s"] == pytest.approx(100e-6)
    # the while spans its body and is left out; the rest covers the program
    assert out["decode_ops_s"] == pytest.approx(400e-6)
    assert out["decode_scope_s"] == {
        "layer/mlp": pytest.approx(160e-6),
        "layer/attn": pytest.approx(120e-6),
        "sample": pytest.approx(80e-6), "unscoped": pytest.approx(40e-6)}
    assert out["scope_ops"]["unscoped"] == [
        ["copy.6_copy", pytest.approx(40e-6)]]
    assert out["scope_ops"]["layer/attn"][0][0] == \
        "paged_decode_attention.4_custom-call"
    assert rn.scope_share_pct(out, ("layer/mlp", "layer/attn_proj")) == \
        pytest.approx(40.0)
    assert rn.scope_share_pct(out, ("lm_head", "sample", "spec_draft",
                                    "spec_verify")) == pytest.approx(20.0)
    # a program that names no scope gives None, not 0
    cap = _capture()
    cap["scopes"] = {}
    assert rn.scope_share_pct(rn.reduce(cap), ("layer/mlp",)) is None
    assert rn.scope_share_pct(None, ("layer/mlp",)) is None


def test_steps_join_through_the_clock_anchor():
    # the anchor: capture time 0 is wall 1000.0 s. Two bursts: dispatched
    # 250 us, ready 520 us (4 steps); dispatched 680, ready 930 (2 steps);
    # a prefill's span in between must not be taken
    def span(t0_us, t1_us, steps, name="decode_burst_device"):
        return {"name": name, "t": 1000.0 + t0_us / 1e6,
                "dur_ms": (t1_us - t0_us) / 1e3, "args": {"steps": steps}}

    spans = [span(250, 520, 4), span(0, 120, 0, "prefill_device"),
             span(680, 930, 2)]
    prof = {"epoch_ns": 1_000_000_000_000, "monotonic_ns": 5_000_000_000}
    out = rn.reduce(_capture(), spans, prof)
    assert out["decode_matched"] == {
        "executions": 2, "module_s": pytest.approx(400e-6), "steps": 6}
    # without spans or anchor: no step count
    assert rn.reduce(_capture())["decode_matched"] is None
    cap = _capture()
    cap["host"] = [h for h in cap["host"] if h[0] != "clock_anchor"]
    assert rn.reduce(cap, spans, prof)["decode_matched"] is None
    # a burst that became ready before the execution ended is not its span
    ms = 1_000_000
    assert rn._match_steps([(10 * ms, 20 * ms)], [(0, 15 * ms, 4)]) == []
    assert rn._match_steps([(10 * ms, 20 * ms)],
                           [(0, 15 * ms, 4), (9 * ms, 26 * ms, 2)]) == \
        [(10 * ms, 2)]


def test_scope_of_takes_the_outermost_named_scope():
    f = rn.scope_of
    assert f("jit(decode_burst)/while/body/closed_call/layer/mlp/dot") == \
        "layer/mlp"
    assert f("jit(x)/layer/attn_proj/mul") == "layer/attn_proj"
    assert f("jit(x)/layer/attn/jit(p)/paged_decode_attention/pallas_call") \
        == "layer/attn"
    assert f("jit(spec_tick)/while/body/spec_verify/while/body/layer/mlp/d") \
        == "spec_verify"
    assert f("jit(x)/while/body/sample/sort") == "sample"
    assert f("jit(x)/while/body/transpose") == "unscoped"


def _enc_varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _ld(field, payload):
    return _enc_varint(field << 3 | 2) + _enc_varint(len(payload)) + payload


def _vi(field, n):
    return _enc_varint(field << 3) + _enc_varint(n)


def test_the_wire_reader_finds_scope_paths_in_event_metadata(tmp_path):
    def stat_meta(i, name):
        return _ld(5, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, name.encode())))

    def event_meta(i, name, tf_op, program):
        stats = (_ld(5, _vi(1, 1) + _ld(5, tf_op.encode())) if tf_op else b"") \
            + _ld(5, _vi(1, 2) + _vi(3, program))
        return _ld(4, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, name.encode())
                                      + stats))

    plane = _ld(2, b"/device:TPU:0") + stat_meta(1, "tf_op") \
        + stat_meta(2, "program_id") \
        + event_meta(1, "%fusion.3 = bf16[16]{0} fusion(bf16[16]{0} %p), "
                     "kind=kLoop", "jit(decode_burst)/layer/mlp/dot", 22) \
        + event_meta(2, "%copy.6 = bf16[16]{0} copy(bf16[16]{0} %q)", "", 22) \
        + event_meta(3, "jit_decode_burst(22)", "", 22)
    host = _ld(2, b"/host:CPU") + stat_meta(1, "tf_op") + event_meta(
        1, "%fusion.9 = f32[] fusion(f32[] %z), kind=kLoop", "jit(x)/y", 5)
    p = tmp_path / "t.xplane.pb"
    p.write_bytes(_ld(1, plane) + _ld(1, host))
    assert rn._device_metadata(str(p)) == {
        "22": {"fusion.3_fusion": "jit(decode_burst)/layer/mlp/dot"}}


def _recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded capture")
def test_reduce_on_the_recorded_capture():
    rec = _recorded()
    out = rn.reduce(rec["capture"], rec["spans"], rec["profile"])
    want = rec["expect"]
    for k in ("window_s", "busy_s", "idle_host_bound_s", "idle_no_work_s",
              "decode_module_s", "prefill_module_s", "decode_ops_s"):
        assert out[k] == pytest.approx(want[k], rel=1e-6), k
    assert out["decode_matched"] == want["decode_matched"]
    # read off the capture by hand: three spec ticks of 17.1, 53.7 and 53.7
    # ms and one prefill head of 18.8 ms, back to back (143.2 ms, no gap
    # over 20 us); the two whole ticks ran 2 rounds each
    assert out["window_s"] == pytest.approx(0.14323, abs=1e-5)
    assert out["decode_module_s"] == pytest.approx(0.12441, abs=1e-5)
    assert out["prefill_module_s"] == pytest.approx(0.01879, abs=1e-5)
    assert out["decode_matched"] == {
        "executions": 2, "module_s": pytest.approx(0.10731, abs=1e-5),
        "steps": 4}
    # no module of the named program is a lambda, the scopes cover the
    # decode programs to a tenth of a percent, and the verify pass of a
    # speculation that accepts nothing is the largest of them
    assert all(k.startswith("jit_") and "lambda" not in k
               for k in out["modules"])
    assert out["decode_ops_s"] == pytest.approx(out["decode_module_s"],
                                                rel=0.002)
    pct = {k: 100 * v / out["decode_module_s"]
           for k, v in out["decode_scope_s"].items()}
    assert pct == {
        "spec_verify": pytest.approx(46.72, abs=0.01),
        "layer/attn": pytest.approx(22.91, abs=0.01),
        "layer/mlp": pytest.approx(19.80, abs=0.01),
        "unscoped": pytest.approx(5.41, abs=0.01),
        "layer/attn_proj": pytest.approx(3.22, abs=0.01),
        "sample": pytest.approx(1.66, abs=0.01),
        "spec_draft": pytest.approx(0.17, abs=0.01),
        "embed": pytest.approx(0.0, abs=0.01),
        "final_norm": pytest.approx(0.0, abs=0.01)}
    assert out["scope_ops"]["layer/attn"][0][0].startswith(
        "paged_decode_attention")
    assert rn.scope_share_pct(out, ("layer/mlp", "layer/attn_proj")) == \
        pytest.approx(23.02, abs=0.01)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded capture")
@pytest.mark.parametrize("phase,stats,want", [
    ("tick_idle_wait", {"queued": 0}, "idle_no_work_s"),
    ("tick_prefill_pack", {}, "idle_host_bound_s"),
    ("tick_idle_wait", {"queued": 3}, "idle_other_s")])
def test_a_hand_made_gap_in_the_recorded_capture(phase, stats, want):
    """The prefill head and its operations moved 5 ms later: the gap goes
    to what the (hand-made) host annotation over it says."""
    cap = _recorded()["capture"]
    dev = cap["device"][0]
    head = next(m for m in dev["modules"]
                if m[0].startswith("jit_prefill_pack_head"))
    cut, gap = head[1], 5_000_000
    for row in dev["modules"] + dev["ops"]:
        if row[1] >= cut:
            row[1] += gap
    shift = rn._skew_ns(dev["modules"], cap["host"])[0]
    cap["host"] = [h for h in cap["host"]
                   if h[0] not in rn.HOST_WORK + (rn.IDLE_PHASE,)]
    cap["host"].append([phase, cut + shift - 1_000_000, gap + 2_000_000,
                        stats])
    out = rn.reduce(cap)
    for k in ("idle_no_work_s", "idle_host_bound_s", "idle_other_s"):
        assert out[k] == pytest.approx(gap / 1e9 if k == want else 0.0,
                                       abs=2e-5), (k, out[k])


def _bare_ctx():
    """A traced run of a program from before PR 25: /debug/state has no
    ``profile`` and no ``trace``, its tick spans carry no counts."""
    return types.SimpleNamespace(
        state_end={"compiles": {}}, trace={"busy_s": 1.0},
        spans=[{"name": "tick", "t": 1.0, "dur_ms": 1.0,
                "args": {"dispatched": 1}},
               {"name": "decode_burst_device", "t": 1.0, "dur_ms": 20.0,
                "args": {"steps": 4}}],
        run=types.SimpleNamespace(w0_wall=1000.0), timings={}, cell=None)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_reader_with_nothing_to_read_returns_none(metric):
    read = spec.layer_reader(metric)
    assert read(_bare_ctx()) is None
    ctx = _bare_ctx()
    ctx.state_end = None
    ctx.spans = []
    assert read(ctx) is None


def test_readers_on_what_the_program_reports():
    ctx = _bare_ctx()
    ctx.state_end = {"trace": {
        "oldest_retained_epoch": 1002.5,
        "by_span_ms": {"load_model": {"total_ms": 9000.0},
                       "load_quantize": {"total_ms": 4000.0},
                       "load_cast": {"total_ms": 1000.0},
                       "load_device_wait": {"total_ms": 500.0},
                       "load_source": {"total_ms": 2000.0},
                       "load_precompile": {"total_ms": 1200.0}}}}
    ctx.spans = [{"name": "tick", "t": 1.0, "dur_ms": 1.0,
                  "args": {"slots_active": a, "decode_tokens": d}}
                 for a, d in ((4, 64), (8, 128), (16, 0))]
    r = {m: spec.layer_reader(m)(ctx) for m in NEW_METRICS}
    assert r["load_quantize_s"] == 4.0 and r["load_source_s"] == 2.0
    assert r["load_cast_put_s"] == 1.5 and r["load_precompile_s"] == 1.2
    assert r["decode_batch_mean"] == 6.0     # the tick without decode: out
    assert r["span_window_lost_s"] == 2.5
    ctx.state_end["trace"]["oldest_retained_epoch"] = 990.0
    assert spec.layer_reader("span_window_lost_s")(ctx) == 0.0
    # the capture's readers share one reduction, kept on the run's context
    ctx._named = rn.reduce(_capture())
    assert spec.layer_reader("host_bound_idle_pct")(ctx) == \
        pytest.approx(100 * 220 / 900)
    assert spec.layer_reader("no_work_idle_pct")(ctx) == \
        pytest.approx(100 * 180 / 900)
    assert spec.layer_reader("prefill_device_share_pct")(ctx) == \
        pytest.approx(20.0)
    assert spec.layer_reader("mlp_proj_share_pct")(ctx) == pytest.approx(40.0)
    assert spec.layer_reader("decode_tail_share_pct")(ctx) == \
        pytest.approx(20.0)
    assert spec.layer_reader("decode_program_ms_per_step")(ctx) is None
