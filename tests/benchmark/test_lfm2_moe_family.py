"""The lfm2_moe family with the real files: maker -> check -> verdict -> counts
on the CPU at the rehearsal's toy width (the published widths are the hand
file's, read by test_spec.py), what its decode programs look like to
reduce_trace, the readers of the four metrics this family brought, the cell's
traffic, and the whole command under ``--rehearsal``."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import make_checkpoint, reduce_trace as rt, roofline, spec
from benchmark.layer_metrics import (_moe, expert_load_max_over_mean,
                                     experts_touched_pct,
                                     moe_experts_roofline, moe_share_pct,
                                     recurrent_state_mb)
from benchmark.run import compared_lines, decide_correct

CELL = "lfm2-24b-a2b.longgen_wide"
TEN = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
CONTROLS = ("weights_int8", "top_k_3", "no_expert_bias")


def _toy():
    with open(os.path.join(spec.ROOT, "benchmark", "rehearsal",
                           "lfm2_moe.json")) as f:
        return json.load(f)


def _hand():
    with open(os.path.join(spec.ROOT, "tests", "benchmark", "data", "hand",
                           "lfm2-24b-a2b-l10.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    from benchmark.reference import check

    conf = _toy()
    conf["serving"]["context_size"] = 1024
    return conf, check.check(conf, 2147483659, [[137, 4], [70, 12]],
                             ["sound", *CONTROLS],
                             str(tmp_path_factory.mktemp("fam")))


def test_cell_resolves_to_its_family_and_files():
    cell = spec.resolve(CELL)
    fam = spec.family_of(cell.config)
    assert fam.__name__ == "benchmark.families.lfm2_moe"
    assert cell.traffic["generator"] == "open_loop_stratified"
    assert set(cell.config["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert cell.config["num_hidden_layers"] == 10
    assert cell.config["layer_types"] == TEN
    names = [m["name"] for m in cell.per_layer]
    assert names[-4:] == ["moe_share_pct", "moe_experts_roofline",
                          "experts_touched_pct", "expert_load_max_over_mean"]
    assert "recurrent_state_mb" in names and "decode_step_roofline" in names
    for absent in ("ssm_share_pct", "mamba2_decode_roofline",
                   "linear_attn_share_pct", "spec_verify_round_pct",
                   "decode_program_ms_per_step"):
        assert absent not in names
    # every list the granite cell is on but its own two
    granite = [m["name"] for m in
               spec.resolve("granite-h-micro.longgen_many").per_layer]
    assert names[:-4] == [n for n in granite if n not in (
        "ssm_share_pct", "mamba2_decode_roofline")]


def test_config_holds_every_number_of_the_catalogs_entry():
    """The published config as the catalog has it, key for key, but for
    the two keys of the cut."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    conf = spec.resolve(CELL).config
    assert {k: conf[k] for k in published} == published
    assert conf["serving"] == {"dtype": "bfloat16", "context_size": 2048,
                               "num_slots": 64, "prefill_buckets": [512]}
    assert conf["precision"] == {"weights": "bfloat16", "kv": "bfloat16",
                                 "router_scores": "float32"}
    assert set(published) <= set(spec.family_of(conf).HF_KEYS)
    assert conf["check"]["layers"] == 4          # every kind of layer
    assert conf["check"]["limits"]["route_err"] == 0
    assert set(conf["check"]["controls"]) >= set(CONTROLS)
    assert "stage one of a four-stage pipeline" in conf["deployment"]
    assert "64 of 64 experts" in conf["deployment"]


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
    # the first four layers, whatever their kinds: conv + dense twice,
    # attention + experts, conv + experts
    assert "model.layers.1.feed_forward.w2.weight" in cut
    assert "model.layers.2.self_attn.q_layernorm.weight" in cut
    assert "model.layers.2.feed_forward.experts.7.w3.weight" in cut
    assert "model.layers.3.conv.in_proj.weight" in cut
    assert "model.layers.3.feed_forward.expert_bias" in cut
    assert not any(k.startswith("model.layers.4.") for k in cut)
    assert "model.layers.2.feed_forward.w1.weight" not in full
    assert "model.layers.1.feed_forward.gate.weight" not in full
    assert cfg["model_type"] == "lfm2_moe" and "family" not in cfg
    assert cfg["rope_parameters"]["rope_theta"] == 10000
    table = {r[0]: r for r in fam.tensor_table(conf, 10)}
    assert set(full) == set(table) and "lm_head.weight" not in full
    assert full["model.layers.0.conv.in_proj.weight"].shape == (3 * 128, 128)
    assert full["model.layers.0.conv.conv.weight"].shape == (128, 1, 3)
    assert full["model.layers.2.self_attn.k_layernorm.weight"].shape == (32,)
    assert full["model.layers.5.feed_forward.gate.weight"].shape == (8, 128)
    assert full["model.layers.5.feed_forward.experts.0.w2.weight"].shape == \
        (128, 64)
    # the bias states its own scale: a tenth of the scores' spread, near 0
    assert table["model.layers.5.feed_forward.expert_bias"][3] == \
        (fam.BIAS_SCALE, 0.0)
    bias = np.concatenate([full[f"model.layers.{i}.feed_forward.expert_bias"]
                           for i in range(2, 10)]).astype(np.float64)
    assert abs(bias.mean()) < 0.01 and 0.01 < bias.std() < 0.03


def test_check_yields_one_number_a_group_and_every_control_fails(checked):
    conf, out = checked
    for variant in ("sound", *CONTROLS):
        assert set(out[variant]) == {"logits_err", "kv_err", "conv_err",
                                     "route_err", "seconds"}
    sound = out["sound"]
    assert sound["route_err"] == 0.0        # exactly: no choice out of slack
    limits = {k: 1.5 * v for k, v in sound.items() if k != "seconds"}
    assert limits["route_err"] == 0
    ok, compared = decide_correct({"sound": sound}, limits,
                                  {"platform": ("tpu", "tpu")})
    assert ok and len(compared_lines(compared)) == 5
    for control in CONTROLS:
        low, _ = decide_correct({"sound": out[control]}, limits, {})
        assert not low, control
    # a bias left out is caught by the choices, and by them alone where the
    # layers before the first expert layer are all it shares with the sound
    # program (the K/V rows of the first expert layer's attention)
    assert out["no_expert_bias"]["route_err"] > 0
    assert out["no_expert_bias"]["kv_err"] == sound["kv_err"]
    # an expert a token left out is part of the mathematics left out
    assert out["top_k_3"]["logits_err"] > 3 * sound["logits_err"]
    assert out["weights_int8"]["logits_err"] > 1.5 * sound["logits_err"]


def test_the_sound_variant_is_the_run_the_reference_followed(tmp_path):
    """``reference`` runs the sound program and follows its choices;
    ``program`` hands the same run back, and runs a control afresh."""
    from benchmark.reference import check

    conf = _toy()
    fam = spec.family_of(conf)
    hf = {k: conf[k] for k in fam.HF_KEYS if k in conf}
    hf["num_hidden_layers"] = 4
    ckpt = str(tmp_path / "ckpt")
    make_checkpoint.make(conf, 3, ckpt, layers=4)
    seqs = check.sequences([[20, 3]], 3, conf["vocab_size"])
    ref = fam.reference(ckpt, hf, 4, "bfloat16", seqs)
    assert set(ref[1]) == set(fam.CHECK_GROUPS)
    assert all((r == 1).all() for r in ref[1]["route"])
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
    assert all((r == 1).all() for r in sound[1]["route"])


def test_counts_are_the_issues_arithmetic():
    from tests.benchmark.test_spec import worked

    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    p = roofline.param_counts(hf)
    a = {k: worked(v) for k, v in _hand()["issue_arithmetic"].items()
         if k != "note"}
    assert a["one_expert"] == fam.expert_params(hf) == 9437184
    assert round(a["experts_a_layer_GB"], 3) == 1.208
    assert a["conv_operator"] == 16783360
    assert a["attention_operator"] == 10485888
    assert a["dense_ff_a_layer"] == 72351744
    assert round(a["whole_model_expert_layers_GB"], 1) == 45.9
    assert p["experts"] == 8 * 64 * 9437184 and p["head"] == 0
    assert round(a["weights_GB"], 2) == round(2 * sum(p.values()) / 1e9, 2) \
        == 10.53
    assert round(a["experts_GB"], 2) == 9.66
    assert round(a["a_third_period_GB"], 1) == 15.5      # does not fit
    assert roofline.state_bytes_per_token(hf) == a["kv_bytes_a_token"] == 4096
    assert a["kv_bytes_a_token_as_pooled"] == 8192
    assert round(a["tails_MB_at_64_slots"], 1) == 4.2
    assert fam.recurrent_state_bytes(hf) * 8 * 64 == 8 * 64 * 2 * 2048 * 2
    # the step's bound counts 4 experts a layer, whatever the batch: one
    # more sequence costs an embedding row, one more live token 4 KB
    one = roofline.decode_step_least_bytes(hf, 2, 10000, 1)
    assert roofline.decode_step_least_bytes(hf, 2, 10000, 2) - one == 4096
    assert roofline.decode_step_least_bytes(hf, 2, 10001, 1) - one == 4096
    bound = roofline.decode_step_least_bytes(hf, 2, 48 * 580, 48)
    assert round(bound / 1e9, 2) == 1.59
    # what a step at 48 rows reads is six times that: the share reads low
    touched = 64 * (1 - (60 / 64) ** 48)
    assert round(touched, 1) == 61.1
    read = bound + fam.moe_experts_least_bytes(hf, 8 * (touched - 4))
    assert round(read / 1e9, 1) == 10.2
    assert fam.moe_experts_least_bytes(hf, 64 * 8) == 2 * p["experts"]


def test_reduce_trace_counts_the_steps_by_the_attention_layers():
    """A decode program of this family makes one paged-decode call an
    ATTENTION layer a step: a burst of 8 steps shows 16."""
    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    steps, us = 8, 1000
    calls = fam.decode_kernel_calls_per_step(hf)
    assert calls == 2
    ops, t = [], 0
    for _ in range(steps):
        for kind in hf["layer_types"]:
            name = "paged_decode_attention.1_custom-call" \
                if kind == "full_attention" else "fusion.9_fusion"
            ops.append([name, t, 5 * us])
            t += 6 * us
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_decode_burst(7)", 0, t]]}]}]
    out = rt.reduce(planes, calls, fam.DECODE_KERNELS)
    assert out["decode_steps"] == pytest.approx(steps)
    assert out["decode_kernel_s"] == pytest.approx(steps * 2 * 5e-6)


def _capture():
    """Five runs of the decode program (the first and the last may be cut
    by the capture's edges) and one prefill pack."""
    us = 1000
    d = "jit(decode_burst)/while/body/closed_call/layer/"
    p = "jit(prefill_pack_head)/while/body/closed_call/layer/"
    scopes = {"7": {"fusion.1_fusion": d + "mlp/router/dot_general",
                    "gmm.3_custom-call": d + "mlp/experts/gmm",
                    "fusion.2_fusion": d + "mlp/dot_general",
                    "fusion.4_fusion": d + "attn_proj/conv/dot_general"},
              "9": {"gmm.5_custom-call": p + "mlp/experts/gmm"}}
    ops, mods = [], []
    for r in range(5):
        t = r * 200 * us
        mods.append(["jit_decode_burst(7)", t, 100 * us, r])
        ops += [["fusion.4_fusion", t, 10 * us],
                ["fusion.1_fusion", t + 10 * us, 5 * us],
                ["gmm.3_custom-call", t + 15 * us, 60 * us],
                ["fusion.2_fusion", t + 75 * us, 20 * us]]
    mods.append(["jit_prefill_pack_head(9)", 150 * us, 40 * us, 9])
    ops.append(["gmm.5_custom-call", 150 * us, 30 * us])
    # the anchor puts the ring's clock 1 s ahead of the capture's
    host = [["clock_anchor", 0, 0, {}]]
    return {"device": [{"name": "/device:TPU:0", "modules": mods,
                        "ops": ops}], "host": host, "scopes": scopes}


def _burst(t0_us, t1_us, touched, steps=2):
    return {"name": "decode_burst_device", "t": 1.0 + t0_us / 1e6,
            "dur_ms": (t1_us - t0_us) / 1e3,
            "args": {"steps": steps, "slot_ids": [0, 1],
                     "experts_touched": touched}}


def test_expert_time_and_touched_experts_are_read_off_a_capture():
    """Runs 1, 2 and 3 can be matched (0 and 4 are the capture's edges);
    run 2's burst reports nothing, and a span is given to one run only."""
    spans = [_burst(-5, 105, 900), _burst(195, 305, 980),
             {**_burst(395, 505, 0), "args": {"steps": 2}},
             _burst(595, 705, 1000), _burst(795, 905, 990)]
    out = _moe.reduce(_capture(), spans, {"epoch_ns": int(1e9)})
    assert out["decode_module_s"] == pytest.approx(500e-6)
    assert out["decode_router_s"] == pytest.approx(25e-6)
    assert out["decode_experts_s"] == pytest.approx(300e-6)   # not the pack's
    assert out["matched_runs"] == 2
    assert out["matched_experts_s"] == pytest.approx(120e-6)
    assert out["matched_experts_touched"] == 980 + 1000
    assert out["matched_steps"] == 4
    # without the spans the scopes are timed and no run finds a burst
    bare = _moe.reduce(_capture())
    assert bare["decode_experts_s"] == pytest.approx(300e-6)
    assert bare["matched_runs"] == 0 and bare["matched_experts_s"] == 0


def _ctx(summary, spans=(), moe=None):
    return types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"}, _moe=summary,
        trace=None, state_samples=[], spans=list(spans),
        state_end={"recurrent_state_bytes": 4194304, "moe": moe})


def test_the_readers_on_hand_made_numbers():
    summary = {"decode_module_s": 1.0, "decode_router_s": 0.02,
               "decode_experts_s": 0.78, "matched_experts_s": 0.7,
               "matched_runs": 10, "matched_steps": 80,
               "matched_experts_touched": 80 * 8 * 61}
    pairs = [[10] * 63 + [30]] * 4 + [[15] * 64] * 4
    moe = {"experts": 64, "experts_per_token": 4,
           "decode": {"steps": 100, "experts_touched": [6000] * 8,
                      "pairs": pairs}}
    spans = [_burst(0, 100, 8 * 8 * 60, steps=8),
             _burst(100, 200, 8 * 8 * 62, steps=8),
             {"name": "tick", "args": {}}]
    ctx = _ctx(summary, spans, moe)
    assert moe_share_pct.read(ctx) == pytest.approx(80.0)
    assert recurrent_state_mb.read(ctx) == pytest.approx(4.194304)
    # 80 steps of 8 layers touching 61 experts of 18.9 MB each over 819 GB/s
    least = 80 * 8 * 61 * 3 * 2048 * 1536 * 2 / 819e9
    assert moe_experts_roofline.read(ctx) == pytest.approx(100 * least / 0.7)
    assert experts_touched_pct.read(ctx) == pytest.approx(100 * 61 / 64)
    assert expert_load_max_over_mean.read(ctx) == pytest.approx(
        (30 * 64 / 660 + 1.0) / 2)
    # every expert of every layer touched in every step, read at the peak,
    # is 100: nothing a run can read passes it
    full = {**summary, "matched_experts_touched": 80 * 8 * 64,
            "matched_experts_s": 80 * 8 * 64 * 3 * 2048 * 1536 * 2 / 819e9}
    assert moe_experts_roofline.read(_ctx(full)) == pytest.approx(100.0)


def test_readers_return_none_where_the_program_has_nothing_to_read():
    """The parent's program: no capture directory, no counter, no scope, no
    argument on its spans."""
    empty = types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"},
        state_end={"profile": None}, state_samples=[], trace=None,
        spans=[{"name": "decode_burst_device",
                "args": {"steps": 8, "slot_ids": [1]}}])
    for reader in (moe_share_pct, moe_experts_roofline, experts_touched_pct,
                   expert_load_max_over_mean):
        assert reader.read(empty) is None
    zeros = dict.fromkeys(("decode_module_s", "decode_router_s",
                           "decode_experts_s", "matched_experts_s",
                           "matched_runs", "matched_steps",
                           "matched_experts_touched"), 0.0)
    for reader in (moe_share_pct, moe_experts_roofline):
        assert reader.read(_ctx(zeros)) is None
    dense = {**zeros, "decode_module_s": 1.0}       # a family with no experts
    assert moe_share_pct.read(_ctx(dense)) is None


def test_the_parent_fails_the_new_cell_at_once():
    """A checkout without ``benchmark/families/lfm2_moe.py`` fails at
    ``spec.family_of``; the parent, with this PR's benchmark files laid over
    it, lacks ``models/lfm2_moe.py``: the check's first import of the
    program fails, before a server is started."""
    conf = dict(spec.resolve(CELL).config, family="lfm2_moe_absent")
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
def test_longgen_wide_sends_the_same_multiset_for_every_seed(seed):
    base, other = _lengths(_schedule(CELL, 7)), _lengths(_schedule(CELL, seed))
    assert base == other and len(base) >= 250
    assert all(128 <= p <= 768 and 256 <= o <= 640 for p, o in base)


def test_longgen_wide_is_longgen_many_but_for_its_rate_and_check_depth():
    wide = spec.resolve(CELL).traffic
    many = spec.resolve("granite-h-micro.longgen_many").traffic
    differ = {k for k in wide if wide[k] != many.get(k)}
    assert differ == {"rate_per_s", "check_lengths"}
    assert wide["check_lengths"][:4] == many["check_lengths"][:4]
    assert wide["check_lengths"][4] == [256, 64]
    assert wide["rate_per_s"] > many["rate_per_s"]
    # the rate and the knee it is a share of are in the cell's why
    why = next(w["why"] for w in spec.load_benchmark()["workloads"]
               if w["name"] == CELL)
    assert f"{wide['rate_per_s']:g} req/s" in why and "knee" in why


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
        stderr=subprocess.PIPE, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    # (a request of 256-640 tokens rarely ends inside six seconds of a CPU's
    # window: tpot_p85_ms is reported where one did)
    assert "setup_s" in line["metrics"]
    assert set(line["metrics"]) <= {"tpot_p85_ms", "setup_s"}
    got = line["compared"]
    assert got["route_err"] == {"value": 0.0, "limit": 0, "ok": True}
    assert got["compiles_after_warmup"]["ok"] and got["failed_requests"]["ok"]
    assert not got["platform"]["ok"]
