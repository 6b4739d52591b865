"""reduce_trace's arithmetic on a small hand-made capture and on a trace
recorded on the chip (tests/benchmark/data/planes_v5e.json.gz: the planes of
one profiler capture of mistral7b.chat_rate, cut to a few thousand events)."""

import gzip
import json
import os

import pytest

from benchmark import reduce_trace as rt

DATA = os.path.join(os.path.dirname(__file__), "data", "planes_v5e.json.gz")


def _planes():
    us = 1000
    ops = [["fusion.1_fusion", 0, 10 * us], ["paged_decode_attention.1_custom-call", 10 * us, 5 * us],
           ["while.2_while", 0, 25 * us], ["fusion.2_fusion", 15 * us, 5 * us], ["paged_decode_attention.1_custom-call", 20 * us, 5 * us],
           # 100 us idle, covered by a host "decode_burst" span
           ["fusion.1_fusion", 125 * us, 10 * us], ["ragged.3_custom-call", 130 * us, 10 * us],
           # 60 us idle with nothing in flight on the host
           ["fusion.9_fusion", 200 * us, 50 * us]]
    modules = [["jit__lambda_", 0, 25 * us], ["jit__lambda_", 125 * us, 15 * us],
               ["jit__lambda_", 200 * us, 50 * us]]
    host = [["decode_burst", 20 * us, 100 * us], ["unrelated", 0, 500 * us]]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops", "events": ops},
                       {"name": "XLA Modules", "events": modules}]},
            {"name": "/host:CPU", "lines": [{"name": "engine", "events": host}]}]


def test_reduce_on_a_hand_made_capture():
    out = rt.reduce(_planes(), n_layers=2)
    assert out["window_s"] == pytest.approx(250e-6)
    # busy: 25 + (125..140 = 15) + 50 us; the two overlapping ops count once
    assert out["busy_s"] == pytest.approx(90e-6)
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"decode_burst": 100e-6, "no_dispatch_in_flight": 60e-6})
    assert out["device_ops"][0] == ["fusion.9_fusion", pytest.approx(50e-6)]
    assert dict(out["device_ops"])["fusion.1_fusion"] == pytest.approx(20e-6)
    assert out["decode_kernel_s"] == pytest.approx(10e-6)
    # one module ran paged-decode attention: 2 calls over 2 layers = 1 step
    assert out["decode_module_s"] == pytest.approx(25e-6)
    assert out["decode_steps"] == pytest.approx(1.0)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        rt.reduce([{"name": "/host:CPU", "lines": []}], 1)
    with pytest.raises(ValueError):
        rt.reduce([{"name": "/device:TPU:0",
                    "lines": [{"name": "XLA Ops", "events": []}]}], 1)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_reduce_on_the_recorded_trace():
    with gzip.open(DATA, "rt") as f:
        rec = json.load(f)
    out = rt.reduce(rec["planes"], rec["layers"])
    for key, want in rec["expect"].items():
        assert out[key] == pytest.approx(want, rel=1e-6), key
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["decode_steps"] > 0
    assert out["decode_kernel_s"] < out["decode_module_s"] <= out["busy_s"] * 1.001


def test_op_name_cuts_the_hlo_text_down_to_the_operation_itself():
    raw = ("%fusion.310 = (f32[16]{0:T(128)S(1)}, bf16[16,1,4096]{2,0,1}) "
           "fusion(%paged_decode_attention_append.9, %p.1), kind=kLoop")
    assert rt.op_name(raw) == "fusion.310_fusion"
    assert rt.DECODE_KERNEL not in rt.op_name(raw)
    kernel = ("%paged_decode_attention_append.9 = bf16[16,32,128]{2,1,0} "
              "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"")
    assert rt.op_name(kernel) == "paged_decode_attention_append.9_custom-call"
    assert rt.op_name("jit__lambda(123)") == "jit__lambda(123)"


def test_control_flow_is_busy_time_but_not_an_operation():
    out = rt.reduce(_planes(), n_layers=2)
    assert "while.2_while" not in dict(out["device_ops"])
