"""The olmo_hybrid family with the real files: maker -> check -> verdict ->
counts on the CPU at the rehearsal's toy width (the published widths are the
hand file's, read by test_spec.py), what its decode programs look like to
reduce_trace, and the readers of the three metrics this family brought."""

import json
import math
import os
import types

import numpy as np
import pytest

from benchmark import make_checkpoint, reduce_trace as rt, roofline, spec
from benchmark.layer_metrics import (_linear_attn,
                                     gated_delta_decode_roofline,
                                     linear_attn_share_pct,
                                     recurrent_state_mb)
from benchmark.run import compared_lines, decide_correct

CELL = "olmo-hybrid.longgen_rate"


def _toy():
    with open(os.path.join(spec.ROOT, "benchmark", "rehearsal",
                           "olmo_hybrid.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    from benchmark.reference import check

    conf = _toy()
    conf["serving"]["context_size"] = 1024
    return conf, check.check(conf, 2147483659, [[137, 4], [70, 12]],
                             ["sound", "weights_int8", "state_bf16"],
                             str(tmp_path_factory.mktemp("fam")))


def test_cell_resolves_to_the_family_and_its_files():
    cell = spec.resolve(CELL)
    fam = spec.family_of(cell.config)
    assert fam.__name__ == "benchmark.families.olmo_hybrid"
    assert cell.traffic["generator"] == "open_loop_stratified"
    assert cell.config["reduced"].keys() == {"num_hidden_layers",
                                             "layer_types"}
    assert cell.config["layer_types"] == (["linear_attention"] * 3
                                          + ["full_attention"]) * 3
    assert [m["name"] for m in cell.per_layer][-3:] == [
        "linear_attn_share_pct", "gated_delta_decode_roofline",
        "recurrent_state_mb"]


def test_maker_writes_the_table_and_a_cut_is_of_the_same_model(tmp_path):
    from safetensors import safe_open

    conf = _toy()
    fam = spec.family_of(conf)

    def tensors(layers, rows, sub):
        d = str(tmp_path / sub)
        make_checkpoint.make(conf, 5, d, layers=layers, vocab_rows=rows)
        with safe_open(os.path.join(d, "model.safetensors"), "np") as h:
            t = {k: h.get_tensor(k) for k in h.keys()}
        with open(os.path.join(d, "config.json")) as f:
            return t, json.load(f)

    (full, cfg), (cut, _) = tensors(0, 0, "whole"), tensors(4, 100, "cut")
    assert all(np.array_equal(v, full[k][:len(v)]) for k, v in cut.items())
    assert cfg["model_type"] == "olmo_hybrid" and "family" not in cfg
    assert cfg["rope_parameters"] == {"rope_theta": None}
    table = {r[0]: r for r in fam.tensor_table(conf, 4)}
    assert set(full) == set(table)
    assert full["model.layers.0.linear_attn.q_conv1d.weight"].shape == \
        (4 * 16, 1, 4)
    assert full["model.layers.1.linear_attn.A_log"].shape == (4,)
    assert "model.layers.3.self_attn.q_norm.weight" in full
    assert "model.layers.3.linear_attn.A_log" not in full
    # the decay's own (scale, shift): alpha where a trained model's is
    assert table["model.layers.0.linear_attn.A_log"][3] == (0.3, -3.5)
    a_log = np.concatenate([full[f"model.layers.{i}.linear_attn.A_log"]
                            for i in range(3)]).astype(np.float32)
    assert -4.5 < a_log.mean() < -2.5


def test_check_yields_one_number_a_group_and_the_controls_fail(checked):
    conf, out = checked
    for variant in ("sound", "weights_int8", "state_bf16"):
        assert set(out[variant]) == {"logits_err", "kv_err", "state_err",
                                     "conv_err", "seconds"}
    sound = out["sound"]
    limits = {k: 1.5 * v for k, v in sound.items() if k != "seconds"}
    ok, compared = decide_correct({"sound": sound}, limits,
                                  {"platform": ("tpu", "tpu")})
    assert ok and len(compared_lines(compared)) == 5
    low, _ = decide_correct({"sound": out["weights_int8"]}, limits, {})
    assert not low
    assert out["weights_int8"]["logits_err"] > 2 * sound["logits_err"]


def test_counts_are_the_issues_arithmetic():
    cell = spec.resolve(CELL)
    hf = cell.config
    fam = spec.family_of(hf)
    p = roofline.param_counts(hf)
    assert round(p["linear_layers"] / 9 / 1e6, 1) == 215.6
    assert round(p["full_layers"] / 3 / 1e6, 1) == 185.8
    assert round((p["embed"] + p["head"]) / 1e6, 1) == 770.7
    # three periods of 832.5 M and the rest: the issue's 3.27 B (hand file)
    assert round((p["linear_layers"] + p["full_layers"]) / 3 / 1e6, 1) == 832.5
    assert round(sum(p.values()) / 1e9, 2) == 3.27
    assert fam.recurrent_state_bytes(hf) == 30 * 96 * 192 * 4
    assert roofline.state_bytes_per_token(hf) == 3 * 15360
    # one more live slot costs its state read and written in 9 layers and
    # an embedding row; one more live token 46 KB
    one = roofline.decode_step_least_bytes(hf, 2, 10000, 1)
    assert roofline.decode_step_least_bytes(hf, 2, 10000, 2) - one == \
        9 * 2 * 2211840 + 3840 * 2
    assert roofline.decode_step_least_bytes(hf, 2, 10001, 1) - one == 46080
    assert fam.gated_delta_decode_least_bytes(hf, 26) == 26 * 9 * 4423680


def test_reduce_trace_counts_the_hybrids_steps():
    """A decode program of this family makes one paged-decode call a FULL
    layer a step: a burst of 16 steps shows 16, which the family's
    ``decode_kernel_calls_per_step`` turns back into the 16 its
    ``decode_burst_device`` span says."""
    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    steps, us = 16, 1000
    calls = fam.decode_kernel_calls_per_step(hf)
    assert calls == 3
    ops, t = [], 0
    for _ in range(steps):
        for layer in range(12):
            name = "paged_decode_attention.1_custom-call" if layer % 4 == 3 \
                else "gated_delta_decode.2_custom-call"
            ops.append([name, t, 5 * us])
            t += 6 * us
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_decode_burst(7)", 0, t]]}]}]
    out = rt.reduce(planes, calls, fam.DECODE_KERNELS)
    assert out["decode_steps"] == pytest.approx(steps)
    assert out["decode_kernel_s"] == pytest.approx(steps * 3 * 5e-6)


def _capture():
    us = 1000
    scopes = {"7": {"fusion.1_fusion": "jit(decode_burst)/while/body/layer/"
                                       "linear_attn/mul",
                    "gated_delta_decode.3_custom-call":
                        "jit(decode_burst)/while/body/layer/linear_attn/"
                        "gated_delta_decode",
                    "fusion.2_fusion": "jit(decode_burst)/while/body/layer/"
                                       "attn_proj/linear/dot_general"},
              "9": {"fusion.5_fusion": "jit(prefill_pack_head)/while/body/"
                                       "layer/linear_attn/gated_delta_chunk/"
                                       "while/body/dot_general",
                    "fusion.6_fusion": "jit(prefill_pack_head)/while/body/"
                                       "layer/linear_attn/mul"}}
    ops = [["fusion.1_fusion", 0, 10 * us],
           ["gated_delta_decode.3_custom-call", 10 * us, 30 * us],
           ["fusion.2_fusion", 40 * us, 60 * us],
           ["fusion.5_fusion", 200 * us, 40 * us],
           ["fusion.6_fusion", 240 * us, 10 * us],
           ["fusion.5_fusion", 300 * us, 40 * us]]
    mods = [["jit_decode_burst(7)", 0, 100 * us, 1],
            ["jit_prefill_pack_head(9)", 200 * us, 50 * us, 2],
            ["jit_prefill_pack_head(9)", 300 * us, 50 * us, 3]]
    return {"device": [{"name": "/device:TPU:0", "modules": mods,
                        "ops": ops}], "host": [], "scopes": scopes}


def test_linear_attention_time_is_read_off_a_capture_by_scope():
    out = _linear_attn.reduce(_capture())
    assert out["decode_module_s"] == pytest.approx(100e-6)
    assert out["decode_linear_attn_s"] == pytest.approx(40e-6)
    assert out["decode_kernel_s"] == pytest.approx(30e-6)
    assert out["prefill_module_s"] == pytest.approx(100e-6)
    assert out["prefill_linear_attn_s"] == pytest.approx(90e-6)
    assert out["prefill_chunk_s"] == pytest.approx(80e-6)
    assert out["prefill_executions"] == 2
    assert out["decode_kernel_calls"] == 1


def _ctx(summary, trace):
    cell = spec.resolve(CELL)
    return types.SimpleNamespace(
        cell=cell, device={"kind": "TPU v5 lite"}, _linear_attn=summary,
        trace=trace, state_end={"recurrent_state_bytes": 5e8},
        state_samples=[{"slots_active": 24}, {"slots_active": 28},
                       {"slots_active": 0}], spans=[])


def test_the_three_readers_on_hand_made_numbers():
    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    summary = {"decode_module_s": 1.0, "decode_linear_attn_s": 0.3,
               "decode_kernel_s": 0.2, "prefill_module_s": 0.2,
               "prefill_linear_attn_s": 0.1, "prefill_chunk_s": 0.05,
               "prefill_executions": 4, "decode_kernel_calls": 288}
    ctx = _ctx(summary, {"decode_steps": 32})
    assert linear_attn_share_pct.read(ctx) == pytest.approx(30.0)
    assert recurrent_state_mb.read(ctx) == pytest.approx(500.0)
    # 288 kernel calls are 32 steps of 9 linear layers, 26 live slots each:
    # 9 x 4.42 MB a slot a step over 819 GB/s, against the kernel's time
    least = 32 * 26 * 9 * 4423680 / 819e9
    assert gated_delta_decode_roofline.read(ctx) == \
        pytest.approx(100 * least / 0.2)
    assert fam.gated_delta_least_flops(hf, 2000) == \
        7 * 2000 * 9 * 30 * 96 * 192
    # the jax.numpy form has no kernel call: the scope stands in for it and
    # the steps are reduce_trace's
    ctx = _ctx({**summary, "decode_kernel_s": 0.0, "decode_kernel_calls": 0},
               {"decode_steps": 32})
    assert gated_delta_decode_roofline.read(ctx) == \
        pytest.approx(100 * least / 0.3)


def test_readers_return_none_where_the_program_has_nothing_to_read():
    """The parent's program: no capture directory, no counter, no scope."""
    empty = types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"},
        state_end={"profile": None}, state_samples=[], spans=[], trace=None)
    for reader in (linear_attn_share_pct, gated_delta_decode_roofline,
                   recurrent_state_mb):
        assert reader.read(empty) is None
    zeros = dict.fromkeys(("decode_module_s", "decode_linear_attn_s",
                           "decode_kernel_s", "prefill_module_s",
                           "prefill_linear_attn_s", "prefill_chunk_s",
                           "prefill_executions", "decode_kernel_calls"), 0.0)
    ctx = _ctx(zeros, None)
    ctx.state_end = {}
    for reader in (linear_attn_share_pct, gated_delta_decode_roofline,
                   recurrent_state_mb):
        assert reader.read(ctx) is None
    assert math.isclose(_linear_attn.live_slots(_ctx(zeros, {})), 26.0)



def test_only_the_maker_process_changes_its_allocator(tmp_path):
    """``tensor_table`` tunes malloc where it runs as ``python -m
    benchmark.make_checkpoint`` and nowhere else; the file it writes is the
    same bytes either way."""
    import hashlib
    import subprocess
    import sys

    fam = spec.family_of(_toy())
    calls = []
    real = fam._maker_keeps_freed_blocks
    try:
        import ctypes
        cdll = ctypes.CDLL
        ctypes.CDLL = lambda *a: calls.append(a) or cdll(*a)
        real()                      # a test process: returns before libc
        assert calls == []
    finally:
        ctypes.CDLL = cdll
    conf = os.path.join(spec.ROOT, "benchmark", "rehearsal",
                        "olmo_hybrid.json")
    subprocess.run([sys.executable, "-m", "benchmark.make_checkpoint",
                    "--config", conf, "--seed", "5", "--out",
                    str(tmp_path / "child")], cwd=spec.ROOT, check=True,
                   stdout=subprocess.DEVNULL)
    make_checkpoint.make(_toy(), 5, str(tmp_path / "here"))
    digest = [hashlib.sha256(open(os.path.join(
        str(tmp_path / d), "model.safetensors"), "rb").read()).hexdigest()
        for d in ("child", "here")]
    assert digest[0] == digest[1]
