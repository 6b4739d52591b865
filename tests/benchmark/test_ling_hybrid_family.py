"""The ling_hybrid family with the real files: maker -> check -> verdict ->
counts on the CPU at the rehearsal's toy width (the published widths are the
hand file's, read by test_spec.py), what its decode programs look like to
reduce_trace, the readers of the three metrics this family brought, the
cell's traffic, the family added to a copy of the benchmark as files and
entries only, and the whole command under ``--rehearsal``."""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import make_checkpoint, reduce_trace as rt, roofline, spec
from benchmark.layer_metrics import (_ling, expert_pairs_held_pct,
                                     kda_decode_roofline,
                                     mla_decode_roofline)
from benchmark.run import compared_lines, decide_correct

CELL, CONFIG = "ling-flash-vl.reason_wide", "ling-3.0-flash-vl-l6"
CONTROLS = ("weights_int8", "state_bf16", "latent_fp8", "topk_group_8",
            "no_expert_bias")
OURS = ["kda_decode_roofline", "mla_decode_roofline",
        "expert_pairs_held_pct"]
FILES = ["benchmark/families/ling_hybrid.py",
         "benchmark/reference/ling_hybrid_f32.py",
         "benchmark/rehearsal/ling_hybrid.json",
         "benchmark/configs/ling-3.0-flash-vl-l6.json",
         "benchmark/traffic/reason_wide.json",
         "benchmark/layer_metrics/_ling.py",
         "benchmark/layer_metrics/kda_decode_roofline.py",
         "benchmark/layer_metrics/mla_decode_roofline.py",
         "benchmark/layer_metrics/expert_pairs_held_pct.py",
         "tests/benchmark/data/hand/ling-3.0-flash-vl-l6.json"]


def _toy():
    with open(os.path.join(spec.ROOT, "benchmark", "rehearsal",
                           "ling_hybrid.json")) as f:
        return json.load(f)


def _hand():
    with open(os.path.join(spec.ROOT, "tests", "benchmark", "data", "hand",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    from benchmark.reference import check

    conf = _toy()
    conf["serving"]["context_size"] = 1024
    conf["serving"]["dtype"] = conf["precision"]["weights"] = "float32"
    return conf, check.check(conf, 2147483659, [[137, 4], [70, 12]],
                             ["sound", *CONTROLS],
                             str(tmp_path_factory.mktemp("fam")))


def test_cell_resolves_to_its_family_and_files():
    cell = spec.resolve(CELL)
    fam = spec.family_of(cell.config)
    assert fam.__name__ == "benchmark.families.ling_hybrid"
    assert cell.traffic["generator"] == "open_loop_stratified"
    assert set(cell.config["reduced"]) == {"num_hidden_layers", "num_experts",
                                           "vocab_size"}
    names = [m["name"] for m in cell.per_layer]
    assert names[-3:] == OURS
    for n in ("moe_share_pct", "moe_experts_roofline", "experts_touched_pct",
              "expert_load_max_over_mean", "recurrent_state_mb",
              "decode_batch_mean", "decode_step_roofline",
              "linear_attn_share_pct"):
        assert n in names
    for absent in ("ssm_share_pct", "mamba2_decode_roofline",
                   "gated_delta_decode_roofline", "spec_verify_round_pct",
                   "decode_program_ms_per_step", "runner_rss_peak_gb",
                   "late_dispatch_s"):
        assert absent not in names
    # every list lfm2's cell is on, linear_attn_share_pct and its own three
    lfm2 = [m["name"] for m in
            spec.resolve("lfm2-24b-a2b.longgen_wide").per_layer]
    assert len(lfm2) == 29
    assert [n for n in names if n not in OURS + ["linear_attn_share_pct"]] \
        == lfm2
    # the new entries stand after every entry that was there
    all_names = [m["name"] for m in spec.load_benchmark()["per_layer"]]
    assert all_names[-3:] == OURS


def test_config_holds_every_number_of_the_catalogs_entry():
    """The published config as the catalog has it, key for key, but for
    the three keys of the cut."""
    published = {
        "image_patch_token": 157157, "video_patch_token": 156909,
        "image_start_token": 157158, "video_start_token": 157160,
        "hidden_size": 2560, "intermediate_size": 6144,
        "first_k_dense_replace": 2, "max_position_embeddings": 131072,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_key_value_heads": 32, "rope_theta": 6000000,
        "rms_norm_eps": 1e-06, "head_dim": 128, "partial_rotary_factor": 0.5,
        "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
        "n_group": 8, "topk_group": 4, "use_qk_norm": True,
        "score_function": "sigmoid",
        "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
        "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
        "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
        "short_conv_kernel_size": 4, "use_nGPT": False,
        "scale_router_input": False, "value_norm": False,
        "up_proj_norm": False,
        "gated_attention_proj_granularity_type": "head_wise",
        "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
        "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
        "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
        "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
    conf = spec.resolve(CELL).config
    assert {k: conf[k] for k in published} == published
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (6, 128, 39296)
    assert conf["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                                 "vocab_size": 157184}
    assert conf["expert_parallel"] == {"num_experts_total": 512, "size": 4,
                                       "rank": 0, "placement": "strided"}
    assert conf["serving"] == {"dtype": "bfloat16", "context_size": 4096,
                               "num_slots": 96, "prefill_buckets": [512]}
    assert conf["check"]["layers"] == 6 and conf["check"]["vocab_rows"] == 8192
    assert conf["check"]["limits"]["route_err"] == 0
    assert set(conf["check"]["controls"]) >= set(CONTROLS)
    assert "28 chips" in conf["deployment"] and "strided" in conf["deployment"]
    fam = spec.family_of(conf)
    held = fam.held_experts(conf)
    assert len(held) == 128 and held[:3] == [0, 4, 8]
    # 16 of each of the 8 groups of 64
    assert [sum(1 for e in held if e // 64 == g) for g in range(8)] == [16] * 8
    with open(os.path.join(spec.ROOT, "benchmark", "traffic",
                           "reason_wide.json")) as f:
        mix = json.load(f)
    assert mix["check_lengths"] == [[137, 8], [256, 8], [431, 8], [768, 8],
                                    [256, 384]]


def test_maker_writes_the_held_experts_and_a_cut_is_of_the_same_model(
        tmp_path):
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

    (full, cfg), (cut, _) = tensors(0, 0, "whole"), tensors(3, 100, "cut")
    assert all(np.array_equal(v, full[k][:len(v)]) for k, v in cut.items())
    assert "model.layers.1.mlp.down_proj.weight" in cut
    assert "model.layers.2.mlp.experts.28.up_proj.weight" in cut
    assert "model.layers.2.mlp.shared_experts.up_proj.weight" in cut
    assert not any(k.startswith("model.layers.3.") for k in cut)
    assert "model.layers.5.self_attn.kv_a_proj_with_mqa.weight" in full
    assert "model.layers.5.linear_attn.q_proj.weight" not in full
    assert "model.layers.4.self_attn.q_proj.weight" not in full
    assert cfg["model_type"] == "ling_hybrid" and "family" not in cfg
    assert cfg["expert_parallel"]["num_experts_total"] == 32
    table = {r[0]: r for r in fam.tensor_table(conf, 6)}
    assert set(full) == set(table) and "lm_head.weight" in full
    experts = {int(k.split(".experts.")[1].split(".")[0]) for k in full
               if ".experts." in k}
    assert experts == set(range(0, 32, 4))          # global ids, held only
    assert full["model.layers.2.mlp.gate.weight"].shape == (32, 128)
    assert full["model.layers.0.linear_attn.q_conv1d.weight"].shape == \
        (64, 1, 4)
    assert full["model.layers.5.self_attn.kv_b_proj.weight"].shape == \
        (4 * 32, 32)
    assert table["model.layers.3.mlp.gate.expert_bias"][3] == \
        (fam.BIAS_SCALE, 0.0)
    assert table["model.layers.0.linear_attn.dt_bias"][3] == fam.DT_BIAS
    dt = full["model.layers.0.linear_attn.dt_bias"].astype(np.float64)
    assert -4.6 < dt.mean() < -3.4


def test_check_yields_one_number_a_group_and_every_control_fails(checked):
    conf, out = checked
    for variant in ("sound", *CONTROLS):
        assert set(out[variant]) == {"logits_err", "latent_err", "state_err",
                                     "conv_err", "route_err", "seconds"}, \
            out[variant]
    sound = out["sound"]
    assert sound["route_err"] == 0.0        # exactly: nothing out of slack
    limits = {k: max(2 * v, 1e-4) for k, v in sound.items() if k != "seconds"}
    limits["route_err"] = 0
    ok, compared = decide_correct({"sound": sound}, limits,
                                  {"platform": ("tpu", "tpu")})
    assert ok and len(compared_lines(compared)) == 6
    for control in CONTROLS:
        low, _ = decide_correct({"sound": out[control]}, limits, {})
        assert not low, (control, out[control])
    # what each control is caught by
    assert out["state_bf16"]["state_err"] > 10 * sound["state_err"]
    assert out["latent_fp8"]["latent_err"] > 10 * sound["latent_err"]
    assert out["topk_group_8"]["route_err"] > 0
    assert out["no_expert_bias"]["route_err"] > 0
    assert out["weights_int8"]["logits_err"] > 10 * sound["logits_err"]


def test_the_sound_variant_is_the_run_the_reference_followed(tmp_path):
    from benchmark.reference import check

    conf = _toy()
    fam = spec.family_of(conf)
    hf = {k: conf[k] for k in fam.HF_KEYS if k in conf}
    ckpt = str(tmp_path / "ckpt")
    make_checkpoint.make(conf, 3, ckpt)
    seqs = check.sequences([[20, 3]], 3, conf["vocab_size"])
    ref = fam.reference(ckpt, hf, 6, "bfloat16", seqs)
    assert set(ref[1]) == set(fam.CHECK_GROUPS)
    assert all((r == 1).all() for r in ref[1]["route"])
    assert ref[1]["latent"][0].shape == (1, 23, 32 + 8)
    assert ref[1]["state"][0].shape == (5, 4, 16, 16)
    calls = []
    real = fam._run_program
    fam._run_program = lambda *a: calls.append(a[3]) or real(*a)
    try:
        sound = fam.program(ckpt, hf, conf["serving"], {}, seqs, 1024)
        control = fam.program(ckpt, hf, conf["serving"],
                              {"config": {"use_expert_bias": False}}, seqs,
                              1024)
    finally:
        fam._run_program = real
    assert calls == [{"config": {"use_expert_bias": False}}]
    assert sound[0][0].shape == control[0][0].shape == (4, 512)


def test_route_group_counts_groups_and_choices_out_of_slack():
    from benchmark.families import ling_hybrid as fam

    # 2 groups of 3, the better one kept, k = 2
    biased = np.asarray([[[0.9, 0.8, 0.5, 0.7, 0.6, 0.1]]])
    groups = np.asarray([[[1.7, 1.3]]])
    ref = {"biased": [biased], "groups": [groups], "k": 2, "topk_group": 1}

    def count(chosen):
        return fam._route_group([np.asarray([[chosen]])], ref)[0].item() - 1

    assert count([0, 1]) == 0 and count([1, 0]) == 0
    assert count([0, 2]) == 1       # 0.5 is 0.3 under the 2nd best
    # from the group that was not kept: 0.4 below the kept group's score
    # (twice, a chosen expert each); inside that group the two are its best
    assert count([3, 4]) == 2
    near = {**ref, "groups": [np.asarray([[[1.7, 1.69]]])]}
    assert fam._route_group([np.asarray([[[3, 4]]])], near)[0].item() == 1
    # three groups of 2, the best 2 kept: where the second and the third
    # tie within the slack a sound program may have kept either, and its
    # choice is read under the filling that suits it; with no tie it is not
    biased = np.asarray([[[0.8, 0.7, 0.3, 0.2, 0.9, 0.1]]])
    for third, want in ((1.2, 1), (1.3, 3)):
        tie = {"biased": [biased], "k": 2, "topk_group": 2,
               "groups": [np.asarray([[[1.5, 1.195, third]]])]}
        assert fam._route_group([np.asarray([[[0, 1]]])],
                                tie)[0].item() == want


def test_counts_are_the_issues_arithmetic():
    from tests.benchmark.test_spec import worked

    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    p = roofline.param_counts(hf)
    a = {k: worked(v) for k, v in _hand()["issue_arithmetic"].items()
         if k != "note"}
    assert round(a["kda_mixer"] / 1e6, 1) == 63.0        # ISSUE: 62.9 M
    assert round(a["mla_mixer"] / 1e6, 1) == 32.0        # ISSUE: 31.9 M
    assert round(a["dense_ff_a_layer"] / 1e6, 1) == 47.2
    assert a["one_expert"] == fam.expert_params(hf) == 5898240
    assert round(a["expert_layer_here"] / 1e6, 1) == 762.2
    assert round(a["embedding_and_head"] / 1e6, 1) == 201.2
    assert round(a["weights_GB"], 2) == round(2 * sum(p.values()) / 1e9, 2) \
        == 7.38
    assert round(a["a_second_period_GB"], 1) == 9.8        # does not fit
    assert p["experts"] == 4 * 128 * 5898240
    assert roofline.state_bytes_per_token(hf) == a["latent_row_bytes"] == 1152
    assert a["latent_row_bytes_as_pooled"] == 1280
    assert fam.recurrent_state_bytes(hf) == 2097152
    assert round(a["state_GB_at_96_slots"], 2) == 1.01
    assert round(a["tails_MB_at_96_slots"]) == 35
    # one more sequence costs an embedding row and five states moved twice,
    # one more live token a latent row
    one = roofline.decode_step_least_bytes(hf, 2, 10000, 1)
    assert roofline.decode_step_least_bytes(hf, 2, 10000, 2) - one == \
        2560 * 2 + 2 * 5 * 2097152
    assert roofline.decode_step_least_bytes(hf, 2, 10001, 1) - one == 1152
    # the kernels' least work
    assert fam.kda_decode_least_bytes(hf, 60 * 5) == 60 * 5 * 2 * 2097152
    nbytes, flops = fam.mla_decode_least(hf, 60 * 1200, 60)
    assert nbytes == 60 * 1200 * 1152
    assert flops == 2 * (60 * 1200 + 60) * 32 * (512 + 64 + 512)
    assert fam.moe_experts_least_bytes(hf, 4 * 128) == 2 * p["experts"]


def test_reduce_trace_counts_the_steps_by_both_kernels():
    """A decode program of this family makes one ``kda_decode`` call a KDA
    layer and one ``mla_paged_decode`` call an MLA layer a step: a burst of
    8 steps shows 48."""
    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    steps, us = 8, 1000
    calls = fam.decode_kernel_calls_per_step(hf)
    assert calls == 6
    ops, t = [], 0
    for _ in range(steps):
        for i in range(6):
            ops.append(["mla_paged_decode.1_custom-call" if i == 5
                        else "kda_decode.3_custom-call", t, 5 * us])
            ops.append(["fusion.9_fusion", t + 5 * us, us])
            t += 6 * us
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_decode_burst(7)", 0, t]]}]}]
    out = rt.reduce(planes, calls, fam.DECODE_KERNELS)
    assert out["decode_steps"] == pytest.approx(steps)
    assert out["decode_kernel_s"] == pytest.approx(steps * 6 * 5e-6)


def _capture():
    """Three runs of the decode program, each two steps of 5 KDA calls and
    one MLA call, and a prefill pack whose kernels do not count."""
    us = 1000
    ops, mods = [], []
    for r in range(3):
        t = r * 200 * us
        mods.append(["jit_decode_burst(7)", t, 100 * us, r])
        for step in range(2):
            for i in range(5):
                ops.append(["kda_decode.3_custom-call",
                            t + (step * 6 + i) * 8 * us, 6 * us])
            ops.append(["mla_paged_decode.1_custom-call",
                        t + (step * 6 + 5) * 8 * us, 4 * us])
    mods.append(["jit_prefill_pack_head(9)", 150 * us, 40 * us, 9])
    ops.append(["kda_decode.5_custom-call", 150 * us, 30 * us])
    host = [["clock_anchor", 0, 0, {}]]
    return {"device": [{"name": "/device:TPU:0", "modules": mods,
                        "ops": ops}], "host": host, "scopes": {}}


def _burst(t0_us, t1_us, slots, rows):
    return {"name": "decode_burst_device", "t": 1.0 + t0_us / 1e6,
            "dur_ms": (t1_us - t0_us) / 1e3,
            "args": {"steps": 2, "slot_ids": list(range(slots)),
                     "ctx_rows": rows}}


def test_kernel_time_live_slots_and_rows_are_read_off_a_capture():
    spans = [_burst(-5, 105, 3, 3000), _burst(195, 305, 4, 4400)]
    out = _ling.reduce(_capture(), spans, {"epoch_ns": int(1e9)})
    assert out["kda_kernel_s"] == pytest.approx(30 * 6e-6)  # not the pack's
    assert out["kda_calls"] == 30 and out["mla_calls"] == 6
    assert out["kda_matched_calls"] == 20 and out["mla_matched_calls"] == 4
    assert out["kda_matched_s"] == pytest.approx(20 * 6e-6)
    assert out["mla_matched_s"] == pytest.approx(4 * 4e-6)
    assert out["kda_live_slot_calls"] == 10 * 3 + 10 * 4
    assert out["mla_live_slot_calls"] == 2 * 3 + 2 * 4
    assert out["mla_ctx_rows"] == 2 * 3000 + 2 * 4400
    bare = _ling.reduce(_capture())
    assert bare["kda_kernel_s"] == pytest.approx(30 * 6e-6)
    assert bare["kda_matched_calls"] == 0 and bare["mla_ctx_rows"] == 0


def _ctx(summary, moe=None):
    return types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"},
        _ling=summary, trace=None, state_samples=[], spans=[],
        state_end={"moe": moe})


def test_the_readers_on_hand_made_numbers():
    summary = {"kda_matched_s": 0.5, "kda_live_slot_calls": 60 * 5 * 100,
               "mla_matched_s": 0.02, "mla_live_slot_calls": 60 * 100,
               "mla_ctx_rows": 60 * 1200 * 100}
    ctx = _ctx(summary, {"decode": {"pairs": [[3, 1], [2, 2]],
                                    "pairs_routed": [16, 16]}})
    least = 60 * 5 * 100 * 2 * 2097152 / 819e9
    assert kda_decode_roofline.read(ctx) == pytest.approx(100 * least / 0.5)
    by_bytes = 60 * 1200 * 100 * 1152 / 819e9
    by_flops = 2 * (60 * 1200 + 60) * 100 * 32 * 1088 / 197e12
    assert by_bytes > by_flops
    assert mla_decode_roofline.read(ctx) == pytest.approx(
        100 * by_bytes / 0.02)
    assert expert_pairs_held_pct.read(ctx) == pytest.approx(25.0)
    # every live slot's state moved at the peak is 100: no run passes it
    full = {**summary, "kda_matched_s": least}
    assert kda_decode_roofline.read(_ctx(full)) == pytest.approx(100.0)


def test_readers_return_none_where_the_program_has_nothing_to_read():
    """The parent's program: no capture directory, no kernel, no counter of
    routed pairs; and another family's cell."""
    empty = types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"},
        state_end={"profile": None, "moe": {"decode": {"pairs": [[1]]}}},
        state_samples=[], trace=None, spans=[])
    for reader in (kda_decode_roofline, mla_decode_roofline,
                   expert_pairs_held_pct):
        assert reader.read(empty) is None
    zeros = dict.fromkeys(("kda_matched_s", "kda_live_slot_calls",
                           "mla_matched_s", "mla_live_slot_calls",
                           "mla_ctx_rows"), 0.0)
    assert kda_decode_roofline.read(_ctx(zeros)) is None
    assert mla_decode_roofline.read(_ctx(zeros)) is None
    other = _ctx({**zeros, "kda_matched_s": 1.0, "kda_live_slot_calls": 5.0,
                  "mla_matched_s": 1.0, "mla_live_slot_calls": 5.0})
    other.cell = spec.resolve("lfm2-24b-a2b.longgen_wide")
    assert kda_decode_roofline.read(other) is None
    assert mla_decode_roofline.read(other) is None


def test_the_parent_fails_the_new_cell_at_once():
    """The parent, with this PR's benchmark files laid over it, lacks
    ``models/ling_hybrid.py``: the check's first import of the program
    fails, before a server is started; a checkout without the family module
    fails at ``spec.family_of``."""
    conf = dict(spec.resolve(CELL).config, family="ling_hybrid_absent")
    with pytest.raises(spec.SpecError, match="has no module"):
        spec.family_of(conf)
    with open(os.path.join(spec.ROOT, "benchmark", "families",
                           "ling_hybrid.py")) as f:
        src = f.read()
    # the program is imported inside the functions that run it, first of all
    # by the sound run the check starts with
    assert "from localai_tpu.models import ling_hybrid as model" in src
    assert "\nimport jax" not in src and "\nfrom localai_tpu" not in src


# ---- the family as files and entries only ----

def test_the_family_came_as_new_files_and_entries_only(tmp_path):
    """As test_second_family.py does with its toy: the benchmark as it was
    (this family's files and entries taken out of a copy), then the files
    and the entries put in; nothing that was there is edited but
    ``BENCHMARK.json``, and of its entries only the ``workloads`` lists."""
    root = str(tmp_path / "root")
    skip = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"), ignore=skip)
    for rel in FILES:
        if rel.startswith("benchmark/"):
            assert os.path.isfile(os.path.join(root, rel)), rel
            os.remove(os.path.join(root, rel))
    new = spec.load_benchmark()
    old = json.loads(json.dumps(new))
    old["configs"] = [c for c in old["configs"] if c["name"] != CONFIG]
    old["workloads"] = [w for w in old["workloads"] if w["name"] != CELL]
    old["per_layer"] = [m for m in old["per_layer"] if m["name"] not in OURS]
    for m in old["end_to_end"] + old["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].remove(CELL)
    assert new["configs"][:-1] == old["configs"]
    assert new["workloads"][:-1] == old["workloads"]
    assert new["per_layer"][:-3] != old["per_layer"]     # the lists grew
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert new[key] == old[key]
    touched = 0
    for was, now in zip(old["per_layer"], new["per_layer"][:-3], strict=True):
        was, now = dict(was), dict(now)
        if now.get("workloads") != was.get("workloads"):
            assert now.pop("workloads") == was.pop("workloads") + [CELL]
            touched += 1
        assert now == was
    assert touched == 30
    # every file the family brought is a new one beside those that were
    # there: none of the copy's remaining files names it
    for d, _dirs, names in os.walk(os.path.join(root, "benchmark")):
        for n in names:
            if n.endswith((".py", ".json")):
                with open(os.path.join(d, n)) as f:
                    assert "ling_hybrid" not in f.read(), os.path.join(d, n)


# ---- traffic ----

def _schedule(mix, seed, seconds=51):
    cell = spec.resolve(mix)
    gen = spec.generator(cell.traffic["generator"])
    return gen(cell.traffic, seconds, seed, cell.config["vocab_size"],
               int(cell.config["serving"]["context_size"]))


def _lengths(sched):
    return sorted((r.prompt_tokens, r.max_tokens) for r in sched.window)


@pytest.mark.parametrize("seed", [1, 12345, 2147483659])
def test_reason_wide_sends_the_same_multiset_for_every_seed(seed):
    base, other = _lengths(_schedule(CELL, 7)), _lengths(_schedule(CELL, seed))
    assert base == other and len(base) >= 150
    assert all(128 <= p <= 768 and 768 <= o <= 1536 for p, o in base)
    assert max(p + o for p, o in base) <= 768 + 1536 < 4096


def test_reason_wide_is_longgen_wide_but_for_its_answers():
    mine = spec.resolve(CELL).traffic
    wide = spec.resolve("lfm2-24b-a2b.longgen_wide").traffic
    differ = {k for k in mine if mine[k] != wide.get(k)}
    assert differ <= {"rate_per_s", "output_tokens", "warmup_s",
                      "check_lengths"}
    assert mine["prompt_tokens"] == wide["prompt_tokens"]
    assert mine["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 0.25, "min": 768, "max": 1536}
    assert mine["warmup_s"] == 30 and mine["block"] == 16
    why = next(w["why"] for w in spec.load_benchmark()["workloads"]
               if w["name"] == CELL)
    assert f"{mine['rate_per_s']:g} req/s" in why and "knee" in why
    assert len(why) <= 200


# ---- the whole command ----

def test_the_whole_command_runs_under_rehearsal(tmp_path):
    """Checkpoint and check -> server -> window -> last line, at the toy
    width on the CPU: the line is stamped cpu (never correct), no request
    fails, nothing compiles after the warm-up."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "6", "--trace", "0", "--rehearsal"],
        cwd=spec.ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert set(line["metrics"]) <= {"tpot_p85_ms", "setup_s"}
    got = line["compared"]
    assert set(got) >= {"logits_err", "latent_err", "state_err", "conv_err",
                        "route_err", "platform"}
    assert got["compiles_after_warmup"]["ok"] and got["failed_requests"]["ok"]
    assert not got["platform"]["ok"]
