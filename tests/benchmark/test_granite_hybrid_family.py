"""The granite_hybrid family with the real files: maker -> check -> verdict ->
counts on the CPU at the rehearsal's toy width (the published widths are the
hand file's, read by test_spec.py), what its decode programs look like to
reduce_trace, the readers of the two metrics this family brought, and the
two cells' traffic."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import make_checkpoint, reduce_trace as rt, roofline, spec
from benchmark.layer_metrics import (_ssm, mamba2_decode_roofline,
                                     recurrent_state_mb, ssm_share_pct)
from benchmark.run import compared_lines, decide_correct

CELL = "granite-h-micro.longgen_many"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def _toy():
    with open(os.path.join(spec.ROOT, "benchmark", "rehearsal",
                           "granite_hybrid.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    from benchmark.reference import check

    conf = _toy()
    conf["serving"]["context_size"] = 1024
    return conf, check.check(conf, 2147483659, [[137, 4], [70, 12]],
                             ["sound", "weights_int8", "state_bf16"],
                             str(tmp_path_factory.mktemp("fam")))


def test_cells_resolve_to_their_families_and_files():
    cell = spec.resolve(CELL)
    fam = spec.family_of(cell.config)
    assert fam.__name__ == "benchmark.families.granite_hybrid"
    assert cell.traffic["generator"] == "open_loop_stratified"
    assert cell.config["reduced"] == {}             # nothing is cut
    assert cell.config["num_hidden_layers"] == 40
    assert cell.config["layer_types"] == PERIOD * 4
    names = [m["name"] for m in cell.per_layer]
    assert names[-3:] == ["recurrent_state_mb", "ssm_share_pct",
                          "mamba2_decode_roofline"]
    assert "linear_attn_share_pct" not in names
    assert "gated_delta_decode_roofline" not in names
    dense = spec.resolve("mistral7b.longgen_rate")
    assert spec.family_of(dense.config).__name__ == "benchmark.families.llama"
    assert dense.traffic == spec.resolve("olmo-hybrid.longgen_rate").traffic
    # every list the chat cell is on but one: an accepted test pins
    # spec_verify_round_pct's list to the three cells it was brought with
    assert [m["name"] for m in dense.per_layer] == [
        m["name"] for m in spec.resolve("mistral7b.chat_rate").per_layer
        if m["name"] != "spec_verify_round_pct"]


def test_config_holds_every_number_of_the_catalogs_entry():
    """The published config as the catalog has it, key for key."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352, "layer_types": PERIOD * 4}
    conf = spec.resolve(CELL).config
    assert {k: conf[k] for k in published} == published
    assert conf["serving"] == {"dtype": "bfloat16", "context_size": 2048,
                               "num_slots": 48, "prefill_buckets": [512]}
    assert conf["precision"]["recurrent_state"] == "float32"
    assert set(published) - {"layer_types"} <= set(
        spec.family_of(conf).HF_KEYS)


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

    (full, cfg), (cut, _) = tensors(0, 0, "whole"), tensors(6, 100, "cut")
    assert all(np.array_equal(v, full[k][:len(v)]) for k, v in cut.items())
    assert "model.layers.5.self_attn.q_proj.weight" in cut
    assert not any(k.startswith("model.layers.6.") for k in cut)
    assert cfg["model_type"] == "granitemoehybrid" and "family" not in cfg
    assert cfg["tie_word_embeddings"] is True and cfg["num_local_experts"] == 0
    table = {r[0]: r for r in fam.tensor_table(conf, 10)}
    assert set(full) == set(table) and "lm_head.weight" not in full
    Di, Ns, Hs = 8 * 32, 128, 8
    assert full["model.layers.0.mamba.in_proj.weight"].shape == \
        (2 * Di + 2 * Ns + Hs, 128)
    assert full["model.layers.0.mamba.conv1d.weight"].shape == \
        (Di + 2 * Ns, 1, 4)
    assert full["model.layers.0.mamba.conv1d.bias"].shape == (Di + 2 * Ns,)
    assert full["model.layers.1.mamba.A_log"].shape == (Hs,)
    assert full["model.layers.0.shared_mlp.input_linear.weight"].shape == \
        (2 * 256, 128)
    assert "model.layers.5.mamba.A_log" not in full
    # the step's and the decay's own (scale, shift): dt and A where the
    # family initialises them, not the 0.5 a step that zeros give
    assert table["model.layers.0.mamba.A_log"][3] == (0.7, 1.4)
    assert table["model.layers.0.mamba.dt_bias"][3] == (0.7, -4.6)
    mamba = [i for i, k in enumerate(PERIOD) if k == "mamba"]
    a = np.exp(np.concatenate([full[f"model.layers.{i}.mamba.A_log"]
                               for i in mamba]).astype(np.float64))
    dt = np.log1p(np.exp(np.concatenate(
        [full[f"model.layers.{i}.mamba.dt_bias"] for i in mamba]
    ).astype(np.float64)))
    assert 1.0 < np.median(a) < 16.0 and 0.001 < np.median(dt) < 0.1
    decay = np.exp(-np.median(a) * np.median(dt))
    assert 0.9 < decay < 0.999


def test_check_yields_one_number_a_group_and_the_controls_fail(checked):
    conf, out = checked
    for variant in ("sound", "weights_int8", "state_bf16"):
        assert set(out[variant]) == {"logits_err", "kv_err", "state_err",
                                     "conv_err", "state_slow_err", "seconds"}
    sound = out["sound"]
    limits = {k: 1.5 * v for k, v in sound.items() if k != "seconds"}
    ok, compared = decide_correct({"sound": sound}, limits,
                                  {"platform": ("tpu", "tpu")})
    assert ok and len(compared_lines(compared)) == 6
    low, _ = decide_correct({"sound": out["weights_int8"]}, limits, {})
    assert not low
    # (at this toy width bfloat16's own rounding is most of the reading;
    # the factor at the published widths is the study's, PERF.md section 2)
    assert out["weights_int8"]["logits_err"] > 1.5 * sound["logits_err"]


def test_state_slow_is_the_deepest_sequences_slowest_heads(tmp_path):
    """Both sides cut the same heads out of the same sequences: those that
    decode longest, and a layer the SLOW_HEADS heads whose decay at rest
    (exp(A_log) * softplus(dt_bias), from the checkpoint) is nearest 1."""
    from safetensors.numpy import load_file

    conf = _toy()
    fam = spec.family_of(conf)
    hf = {k: conf[k] for k in fam.HF_KEYS if k in conf}
    hf["num_hidden_layers"] = 6
    ckpt = str(tmp_path / "c")
    make_checkpoint.make(conf, 7, ckpt, layers=6, vocab_rows=64)
    full = load_file(ckpt + "/model.safetensors")
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    seqs = [([1] * 5, [1] * 3), ([1] * 4, [1] * 9), ([1] * 2, [1] * 9)]
    # a state whose every element names its sequence, layer and head
    last = [np.broadcast_to((100 * s + 10 * np.arange(5)[:, None]
                             + np.arange(H)[None] / 100)[..., None, None],
                            (5, H, P, N)) for s in range(3)]
    got = fam._slow(ckpt, hf, seqs, last)
    assert [g.shape for g in got] == [(5, fam.SLOW_HEADS, P, N)] * 2
    for s, g in zip((1, 2), got):
        for li in range(5):
            rest = np.exp(full[f"model.layers.{li}.mamba.A_log"].astype(
                np.float64)) * np.log1p(np.exp(
                    full[f"model.layers.{li}.mamba.dt_bias"].astype(
                        np.float64)))
            want = np.sort(rest)[:fam.SLOW_HEADS]
            heads = np.rint((g[li, :, 0, 0] - 100 * s - 10 * li) * 100
                            ).astype(int)
            np.testing.assert_allclose(rest[heads], want)


def test_counts_are_the_issues_arithmetic():
    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    p = roofline.param_counts(hf)
    assert round(p["mamba_layers"] / 36 / 1e6, 2) == 76.18
    assert round(p["attention_layers"] / 4 / 1e6, 2) == 60.82
    assert round(p["embed"] / 1e6, 1) == 205.5 and p["head"] == 0
    assert round(sum(p.values()) / 1e9, 3) == 3.191
    assert round(2 * sum(p.values()) / 1e9, 2) == 6.38
    assert fam.recurrent_state_bytes(hf) == 64 * 64 * 128 * 4 == 2097152
    assert roofline.state_bytes_per_token(hf) == 8192
    # one more live slot costs its state read and written in 36 layers and
    # an embedding row; one more live token 8 KB
    one = roofline.decode_step_least_bytes(hf, 2, 10000, 1)
    assert roofline.decode_step_least_bytes(hf, 2, 10000, 2) - one == \
        36 * 2 * 2097152 + 2048 * 2
    assert roofline.decode_step_least_bytes(hf, 2, 10001, 1) - one == 8192
    assert fam.mamba2_decode_least_bytes(hf, 30) == 30 * 36 * 4194304
    # the issue's step at 30 live slots: 11.0 GB, the mixers' state 41%
    step = roofline.decode_step_least_bytes(hf, 2, 13500, 30)
    assert round(step / 1e9, 1) == 11.0
    assert round(100 * fam.mamba2_decode_least_bytes(hf, 30) / step) == 41
    assert fam.mamba2_least_flops(hf, 7) == 5 * 7 * 36 * 64 * 64 * 128


def test_reduce_trace_counts_the_steps_by_the_attention_layers():
    """A decode program of this family makes one paged-decode call an
    ATTENTION layer a step: a burst of 8 steps shows 32."""
    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    steps, us = 8, 1000
    calls = fam.decode_kernel_calls_per_step(hf)
    assert calls == 4
    ops, t = [], 0
    for _ in range(steps):
        for kind in hf["layer_types"]:
            name = "paged_decode_attention.1_custom-call" \
                if kind == "attention" else "mamba2_decode.2_custom-call"
            ops.append([name, t, 5 * us])
            t += 6 * us
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_decode_burst(7)", 0, t]]}]}]
    out = rt.reduce(planes, calls, fam.DECODE_KERNELS)
    assert out["decode_steps"] == pytest.approx(steps)
    assert out["decode_kernel_s"] == pytest.approx(steps * 4 * 5e-6)


def _capture():
    us = 1000
    d = "jit(decode_burst)/while/body/closed_call/layer/"
    p = "jit(prefill_pack_head)/while/body/closed_call/layer/"
    scopes = {"7": {"fusion.1_fusion": d + "ssm/mul",
                    "mamba2_decode.3_custom-call": d + "ssm/mamba2_decode",
                    "fusion.2_fusion": d + "attn_proj/ssm/dot_general"},
              "9": {"fusion.5_fusion": p + "ssm/ssd_chunk/while/body/"
                                           "dot_general",
                    "fusion.6_fusion": p + "ssm/mul"}}
    ops = [["fusion.1_fusion", 0, 10 * us],
           ["mamba2_decode.3_custom-call", 10 * us, 30 * us],
           ["fusion.2_fusion", 40 * us, 60 * us],
           ["fusion.5_fusion", 200 * us, 40 * us],
           ["fusion.6_fusion", 240 * us, 10 * us],
           ["mamba2_decode.3_custom-call", 310 * us, 20 * us],
           ["mamba2_decode.3_custom-call", 500 * us, 20 * us]]
    mods = [["jit_decode_burst(7)", 0, 100 * us, 1],
            ["jit_prefill_pack_head(9)", 200 * us, 50 * us, 2],
            ["jit_decode_burst(7)", 300 * us, 50 * us, 3],
            ["jit_decode_burst(7)", 490 * us, 50 * us, 4]]
    # the anchor puts the ring's clock 1 s ahead of the capture's
    host = [["clock_anchor", 0, 0, {}]]
    return {"device": [{"name": "/device:TPU:0", "modules": mods,
                        "ops": ops}], "host": host, "scopes": scopes}


def _burst(t0_us, t1_us, slots):
    return {"name": "decode_burst_device", "t": 1.0 + t0_us / 1e6,
            "dur_ms": (t1_us - t0_us) / 1e3,
            "args": {"steps": 1, "slot_ids": list(range(slots))}}


def test_ssm_time_and_live_slots_are_read_off_a_capture():
    """Two bursts in flight: the second was dispatched before the first's
    kernel call ran, and the call still goes to the first (it became ready
    sooner). A call no span covers is counted but not matched."""
    spans = [_burst(-5, 105, 30), _burst(-2, 360, 8)]
    out = _ssm.reduce(_capture(), spans, {"epoch_ns": int(1e9)})
    assert out["decode_module_s"] == pytest.approx(200e-6)
    assert out["decode_ssm_s"] == pytest.approx(80e-6)
    assert out["decode_kernel_s"] == pytest.approx(70e-6)
    assert out["prefill_module_s"] == pytest.approx(50e-6)
    assert out["prefill_ssm_s"] == pytest.approx(50e-6)
    assert out["prefill_chunk_s"] == pytest.approx(40e-6)
    assert out["decode_kernel_calls"] == 3
    assert out["matched_kernel_calls"] == 2
    assert out["matched_kernel_s"] == pytest.approx(50e-6)
    assert out["live_slot_calls"] == 30 + 8
    # without the spans the kernel is timed and no call finds a burst
    bare = _ssm.reduce(_capture())
    assert bare["decode_kernel_calls"] == 3 and bare["live_slot_calls"] == 0


def _ctx(summary):
    return types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"}, _ssm=summary,
        trace=None, state_end={"recurrent_state_bytes": 3.669e9},
        state_samples=[], spans=[])


def test_the_readers_on_hand_made_numbers():
    summary = {"decode_module_s": 1.0, "decode_ssm_s": 0.4,
               "decode_kernel_s": 0.31, "matched_kernel_s": 0.3,
               "decode_kernel_calls": 2900, "matched_kernel_calls": 2880,
               "live_slot_calls": 2880 * 30}
    ctx = _ctx(summary)
    assert ssm_share_pct.read(ctx) == pytest.approx(40.0)
    assert recurrent_state_mb.read(ctx) == pytest.approx(3669.0)
    # 2880 calls are 80 steps of 36 mamba layers with 30 live slots each:
    # 4.19 MB a slot a call over 819 GB/s, against the kernel's time there
    least = 2880 * 30 * 2 * 2097152 / 819e9
    assert mamba2_decode_roofline.read(ctx) == \
        pytest.approx(100 * least / 0.3)
    # all 48 slots live in every call at 5.12 us a slot is the peak
    # itself: nothing a run can read passes 100
    full = {**summary, "live_slot_calls": 2880 * 48,
            "matched_kernel_s": 2880 * 48 * 2 * 2097152 / 819e9}
    assert mamba2_decode_roofline.read(_ctx(full)) == pytest.approx(100.0)


def test_readers_return_none_where_the_program_has_nothing_to_read():
    """The parent's program: no capture directory, no counter, no scope;
    and the jax.numpy form, which has no kernel call."""
    empty = types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"},
        state_end={"profile": None}, state_samples=[], spans=[], trace=None)
    for reader in (ssm_share_pct, mamba2_decode_roofline):
        assert reader.read(empty) is None
    zeros = dict.fromkeys(("decode_module_s", "decode_ssm_s",
                           "decode_kernel_s", "matched_kernel_s",
                           "decode_kernel_calls", "matched_kernel_calls",
                           "live_slot_calls"), 0.0)
    for reader in (ssm_share_pct, mamba2_decode_roofline):
        assert reader.read(_ctx(zeros)) is None
    jnp_form = {**zeros, "decode_module_s": 1.0, "decode_ssm_s": 0.5}
    assert ssm_share_pct.read(_ctx(jnp_form)) == pytest.approx(50.0)
    assert mamba2_decode_roofline.read(_ctx(jnp_form)) is None


def test_the_parent_fails_the_new_cell_at_once(tmp_path, monkeypatch):
    """A checkout without ``benchmark/families/granite_hybrid.py`` (the
    parent, with this PR's benchmark files laid over it, still lacks the
    program; a benchmark without the family lacks even this): resolving the
    cell's family is a SpecError, before any process is started."""
    conf = dict(spec.resolve(CELL).config, family="granite_hybrid_absent")
    with pytest.raises(spec.SpecError, match="has no module"):
        spec.family_of(conf)


# ---- traffic ----

def _schedule(mix, seed, seconds=51):
    cell = spec.resolve(mix)
    gen = spec.generator(cell.traffic["generator"])
    return gen(cell.traffic, seconds, seed, cell.config["vocab_size"],
               int(cell.config["serving"]["context_size"]))


def _lengths(sched):
    return sorted((r.prompt_tokens, r.max_tokens) for r in sched.window)


@pytest.mark.parametrize("seed", [1, 12345, 2147483659])
def test_longgen_many_sends_the_same_multiset_for_every_seed(seed):
    base, other = _lengths(_schedule(CELL, 7)), _lengths(_schedule(CELL, seed))
    assert base == other and len(base) >= 120
    assert all(128 <= p <= 768 and 256 <= o <= 640 for p, o in base)


def test_longgen_many_is_longgen_rate_but_for_its_rate_and_check_depth():
    many = spec.resolve(CELL).traffic
    rate = spec.resolve("olmo-hybrid.longgen_rate").traffic
    differ = {k for k in many if many[k] != rate.get(k)}
    assert differ == {"rate_per_s", "check_lengths"}
    assert many["check_lengths"][:4] == rate["check_lengths"][:4]
    assert many["check_lengths"][4] == [256, 384]     # the decode depth
    assert many["rate_per_s"] >= 2 * rate["rate_per_s"]


def test_the_dense_longgen_cell_cuts_each_request_to_its_context():
    sched = _schedule("mistral7b.longgen_rate", 2147483659)
    pairs = _lengths(sched)
    assert len(pairs) >= 40
    assert all(p + o <= 1024 for p, o in pairs)
