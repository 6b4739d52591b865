"""The xing4 family with the real files: maker -> check -> verdict -> counts on
the CPU at the rehearsal's toy width (the published widths are the hand
file's, read by test_spec.py), what its decode programs look like to
reduce_trace, the readers of the two metrics this family brought, the cell's
traffic, the family added to a copy of the benchmark as files and entries
only, and the whole command under ``--rehearsal``."""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import make_checkpoint, reduce_trace as rt, roofline, spec
from benchmark.layer_metrics import (_ling, hc_share_pct, mla_ctx_rows_mean,
                                     mla_prefill_walk_share_pct,
                                     mla_decode_roofline)
from benchmark.run import compared_lines, decide_correct

CELL, CONFIG = "xing4-29b-a4b.docqa_long", "xing4.0-29b-a4b-l6"
CONTROLS = ("weights_int8", "latent_fp8", "hc_bf16", "sinkhorn_1",
            "no_yarn_mscale", "no_expert_bias")
OURS = ["hc_share_pct", "mla_ctx_rows_mean", "mla_prefill_walk_share_pct"]
FILES = ["benchmark/families/xing4.py",
         "benchmark/reference/xing4_f32.py",
         "benchmark/rehearsal/xing4.json",
         "benchmark/configs/xing4.0-29b-a4b-l6.json",
         "benchmark/traffic/docqa_long.json",
         "benchmark/layer_metrics/hc_share_pct.py",
         "benchmark/layer_metrics/mla_ctx_rows_mean.py",
         "benchmark/layer_metrics/mla_prefill_walk_share_pct.py",
         "tests/benchmark/data/hand/xing4.0-29b-a4b-l6.json"]


def _toy():
    with open(os.path.join(spec.ROOT, "benchmark", "rehearsal",
                           "xing4.json")) as f:
        return json.load(f)


def _hand():
    with open(os.path.join(spec.ROOT, "tests", "benchmark", "data", "hand",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return next(e for e in map(json.loads, f)
                    if e["name"] == "Xing4.0-29B-A4B")


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
    assert fam.__name__ == "benchmark.families.xing4"
    assert cell.traffic["generator"] == "doc_sessions" and cell.chips == 1
    assert set(cell.config["reduced"]) == {"num_hidden_layers"}
    names = [m["name"] for m in cell.per_layer]
    assert names[-3:] == OURS
    for n in ("prefix_hit_token_pct", "moe_share_pct",
              "moe_experts_roofline", "experts_touched_pct",
              "expert_load_max_over_mean", "mla_decode_roofline",
              "decode_batch_mean", "decode_step_roofline"):
        assert n in names
    for absent in ("recurrent_state_mb", "linear_attn_share_pct",
                   "kda_decode_roofline", "expert_pairs_held_pct",
                   "spec_verify_round_pct", "decode_program_ms_per_step",
                   "runner_rss_peak_gb", "late_dispatch_s",
                   "burst_step_max_over_p50"):
        assert absent not in names
    # every list all the accepted cells are on, the experts' four, the
    # latent walk's roofline and its own three
    ling = [m["name"] for m in
            spec.resolve("ling-flash-vl.reason_wide").per_layer]
    assert [n for n in names if n not in OURS] == [
        n for n in ling if n not in (
            "linear_attn_share_pct", "recurrent_state_mb",
            "kda_decode_roofline", "expert_pairs_held_pct")]
    assert len(names) == 32
    # the new entries stand after every entry that was there, and list
    # this cell alone
    per_layer = spec.load_benchmark()["per_layer"]
    assert [m["name"] for m in per_layer][-3:] == OURS
    assert all(m["workloads"] == [CELL] and m["moves"] == "tpot_p85_ms"
               for m in per_layer[-3:])
    assert [(m["source"], m["layer"]) for m in per_layer[-3:]] == [
        ("device_trace", "kernels"), ("program_span", "KV manager"),
        ("device_trace", "kernels")]
    bench = spec.load_benchmark()
    assert len(bench["workloads"]) == 9
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_config_holds_every_number_of_the_catalogs_entry():
    """The published config as the catalog has it, key for key, but for
    the one key of the cut."""
    conf = spec.resolve(CELL).config
    entry = _catalog()
    if entry is not None:
        published = dict(entry["config"])
        assert conf["source"] == entry["source_url"]
    else:
        published = {k: v for k, v in _toy().items()
                     if k in ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                              "rope_theta", "routed_scaling_factor")}
    assert published.pop("num_hidden_layers", 40) == 40
    assert {k: conf[k] for k in published} == published
    assert conf["num_hidden_layers"] == 6
    assert conf["published"] == {"num_hidden_layers": 40}
    assert (conf["hidden_size"], conf["n_routed_experts"],
            conf["vocab_size"], conf["q_lora_rank"], conf["hc_mult"]) == \
        (3584, 64, 131072, 768, 4)
    assert conf["rope_scaling"]["factor"] == 64
    assert conf["serving"] == {"dtype": "bfloat16", "context_size": 16384,
                               "num_slots": 32, "prefill_buckets": [512]}
    assert conf["precision"] == {"weights": "bfloat16", "latent": "bfloat16",
                                 "hc": "float32", "router_scores": "float32"}
    assert conf["check"]["layers"] == 6 and conf["check"]["vocab_rows"] == 8192
    assert conf["check"]["limits"]["route_err"] == 0
    assert set(conf["check"]["limits"]) == {"logits_err", "latent_err",
                                            "hc_err", "route_err"}
    assert set(conf["check"]["controls"]) == set(CONTROLS)
    assert "ten pipeline stages" in conf["deployment"]
    said = " ".join(conf["assumed"])
    for item in ("hyper-connection's form", "read-out", "tensor name",
                 "rms_norm_eps", "half-split", "checkpoint maker draws",
                 "multi-token prediction"):
        assert item in said, item
    with open(os.path.join(spec.ROOT, "benchmark", "traffic",
                           "docqa_long.json")) as f:
        mix = json.load(f)
    assert mix["check_lengths"] == [[12384, 8], [6200, 192]]
    # the toy keeps every key of the published config too
    assert set(published) <= set(_toy())


def test_maker_writes_the_hyper_connections_and_a_cut_is_of_the_same_model(
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
    assert "model.layers.2.mlp.experts.7.up_proj.weight" in cut
    assert "model.layers.2.mlp.shared_experts.up_proj.weight" in cut
    assert not any(k.startswith("model.layers.3.") for k in cut)
    assert cfg["model_type"] == "xing4_0" and "family" not in cfg
    assert cfg["rope_scaling"]["type"] == "yarn" and cfg["hc_mult"] == 4
    table = {r[0]: r for r in fam.tensor_table(conf, 4)}
    assert set(full) == set(table) and "lm_head.weight" in full
    # nothing of the multi-token prediction module
    assert not any(k.startswith("model.layers.4.") for k in full)
    assert full["model.layers.0.attn_hc.weight"].shape == (24, 4 * 128)
    assert full["model.layers.3.mlp_hc.scale"].shape == (3,)
    assert full["model.hc_head.weight"].shape == (4, 4 * 128)
    assert full["model.hc_head.bias"].shape == (4,)
    assert full["model.layers.0.self_attn.q_a_proj.weight"].shape == (48, 128)
    assert full["model.layers.0.self_attn.q_b_proj.weight"].shape == \
        (4 * 24, 48)
    assert full["model.layers.2.mlp.gate.weight"].shape == (8, 128)
    assert table["model.layers.3.mlp.gate.e_score_correction_bias"][3] == \
        (fam.BIAS_SCALE, 0.0)
    assert table["model.layers.1.attn_hc.bias"][3] == fam.HC_BIAS
    # the draw leaves the mixes with work to do: scales near 1, biases of
    # half a unit, m of unit size
    s = np.concatenate([full[f"model.layers.{i}.{sub}_hc.scale"]
                        for i in range(4) for sub in ("attn", "mlp")])
    b = np.concatenate([full[f"model.layers.{i}.{sub}_hc.bias"]
                        for i in range(4) for sub in ("attn", "mlp")])
    assert 0.85 < s.astype(np.float64).min() and s.max() < 1.15
    assert 0.35 < b.astype(np.float64).std() < 0.65
    w = full["model.layers.0.attn_hc.weight"].astype(np.float64)
    assert 0.8 < w.std() * np.sqrt(4 * 128) < 1.2


def test_check_yields_one_number_a_group_and_every_control_fails(checked):
    conf, out = checked
    for variant in ("sound", *CONTROLS):
        assert set(out[variant]) == {"logits_err", "latent_err", "route_err",
                                     "hc_err", "seconds"}, out[variant]
    sound = out["sound"]
    assert sound["route_err"] == 0.0        # exactly: nothing out of slack
    limits = {k: max(2 * v, 1e-4) for k, v in sound.items() if k != "seconds"}
    limits["route_err"] = 0
    ok, compared = decide_correct({"sound": sound}, limits,
                                  {"platform": ("tpu", "tpu")})
    assert ok and len(compared_lines(compared)) == 5
    for control in CONTROLS:
        low, _ = decide_correct({"sound": out[control]}, limits, {})
        assert not low, (control, out[control])
    # what each control is caught by
    assert out["hc_bf16"]["hc_err"] > 1000 * sound["hc_err"]
    assert out["sinkhorn_1"]["hc_err"] > 10 * out["hc_bf16"]["hc_err"]
    assert out["latent_fp8"]["latent_err"] > 100 * sound["latent_err"]
    assert out["latent_fp8"]["hc_err"] == sound["hc_err"]
    assert out["no_yarn_mscale"]["logits_err"] > 0.1
    assert out["no_yarn_mscale"]["hc_err"] == sound["hc_err"]
    assert out["no_expert_bias"]["route_err"] > 0
    assert out["weights_int8"]["logits_err"] > 100 * sound["logits_err"]


def test_the_sound_variant_is_the_run_the_reference_followed(tmp_path):
    from benchmark.reference import check

    conf = _toy()
    fam = spec.family_of(conf)
    hf = {k: conf[k] for k in fam.HF_KEYS if k in conf}
    ckpt = str(tmp_path / "ckpt")
    make_checkpoint.make(conf, 3, ckpt)
    seqs = check.sequences([[20, 3]], 3, conf["vocab_size"])
    ref = fam.reference(ckpt, hf, 4, "bfloat16", seqs)
    assert set(ref[1]) == set(fam.CHECK_GROUPS)
    assert all((r == 1).all() for r in ref[1]["route"])
    assert ref[1]["latent"][0].shape == (4, 23, 32 + 8)
    assert ref[1]["hc"][0].shape == (23, 4 + 4 + 16)
    # rows and columns of the first sublayer's mix sum to 1
    M = ref[1]["hc"][0][:, 8:].reshape(23, 4, 4)
    np.testing.assert_allclose(M.sum(1), 1.0, atol=1e-5)
    np.testing.assert_allclose(M.sum(2), 1.0, atol=1e-5)
    calls = []
    real = fam._run_program
    fam._run_program = lambda *a: calls.append(a[3]) or real(*a)
    try:
        sound = fam.program(ckpt, hf, conf["serving"], {}, seqs, 1024)
        control = fam.program(ckpt, hf, conf["serving"],
                              {"config": {"hc_sinkhorn_iters": 1}}, seqs,
                              1024)
    finally:
        fam._run_program = real
    assert calls == [{"config": {"hc_sinkhorn_iters": 1}}]
    assert sound[0][0].shape == control[0][0].shape == (4, 512)
    M1 = control[1]["hc"][0][:, 8:].reshape(23, 4, 4)
    assert np.abs(M1.sum(2) - 1.0).max() > 0.01         # rows not yet


def test_counts_are_the_issues_arithmetic():
    from tests.benchmark.test_spec import worked

    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    p = roofline.param_counts(hf)
    a = {k: worked(v) for k, v in _hand()["issue_arithmetic"].items()
         if k != "note"}
    assert round(a["mla_mixer"] / 1e6, 2) == 28.41
    assert round(a["hyper_connection_a_sublayer"] / 1e6, 3) == 0.344
    assert round(a["dense_ff_a_layer"] / 1e6, 2) == 99.09
    assert a["one_expert"] == fam.expert_params(hf) == 11010048
    assert round(a["dense_layer"] / 1e6, 1) == 128.2
    assert round(a["expert_layer"] / 1e6, 1) == 745.0
    assert round(a["embedding_and_head"] / 1e6, 1) == 939.5
    assert round(a["weights_GB"], 2) == round(2 * sum(p.values()) / 1e9, 2) \
        == 8.35
    assert round(sum(p.values()) / 1e9, 3) == 4.176
    assert round(a["a_fifth_expert_layer_GB"], 2) == 1.49
    assert p["experts"] == 4 * 64 * 11010048
    assert p["hyper"] == 6 * 2 * (24 * 14336 + 27) + 4 * 14336 + 5
    assert roofline.state_bytes_per_token(hf) == a["latent_bytes_a_token"] \
        == 6 * 1152
    assert a["latent_bytes_a_token_as_pooled"] == 7680
    assert round(a["latent_pool_GB"], 2) == 3.02
    assert a["pool_tokens"] == 393216
    # one more sequence costs an embedding row, one more live token a
    # latent row in each of the six layers
    one = roofline.decode_step_least_bytes(hf, 2, 10000, 1)
    assert roofline.decode_step_least_bytes(hf, 2, 10000, 2) - one == 3584 * 2
    assert roofline.decode_step_least_bytes(hf, 2, 10001, 1) - one == 6912
    # the kernel's least work
    nbytes, flops = fam.mla_decode_least(hf, 16 * 9000, 16)
    assert nbytes == 16 * 9000 * 1152
    assert flops == 2 * (16 * 9000 + 16) * 32 * (512 + 64 + 512)
    assert fam.moe_experts_least_bytes(hf, 4 * 64) == 2 * p["experts"]


def test_reduce_trace_counts_the_steps_by_the_latent_walks():
    """A decode program of this family makes one ``mla_paged_decode`` call
    a layer a step: a burst of 8 steps shows 48."""
    hf = spec.resolve(CELL).config
    fam = spec.family_of(hf)
    steps, us = 8, 1000
    calls = fam.decode_kernel_calls_per_step(hf)
    assert calls == 6 and fam.DECODE_KERNELS == ("mla_paged_decode",)
    ops, t = [], 0
    for _ in range(steps):
        for i in range(6):
            ops.append(["mla_paged_decode.1_custom-call", t, 5 * us])
            ops.append(["fusion.9_fusion", t + 5 * us, us])
            t += 6 * us
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_decode_burst(7)", 0, t]]}]}]
    out = rt.reduce(planes, calls, fam.DECODE_KERNELS)
    assert out["decode_steps"] == pytest.approx(steps)
    assert out["decode_kernel_s"] == pytest.approx(steps * 6 * 5e-6)


def _capture():
    """Three runs of the decode program, each two steps of six latent walks
    with the hyper-connections' fusions between, and a prefill pack whose
    operations do not count."""
    us = 1000
    ops, mods = [], []
    scopes = {"7": {}, "9": {
        "fusion.70_fusion": "jit(p)/layer/hc/add",
        "fusion.71_fusion": "jit(p)/while/body/layer/attn/mla_walk/while/"
                            "body/while/body/dot_general"}}
    for r in range(3):
        t = r * 200 * us
        mods.append(["jit_decode_burst(7)", t, 100 * us, r])
        for step in range(2):
            for i in range(6):
                at = t + (step * 6 + i) * 8 * us
                ops.append(["mla_paged_decode.1_custom-call", at, 4 * us])
                ops.append([f"fusion.{i}_fusion", at + 4 * us, us])
                scopes["7"][f"fusion.{i}_fusion"] = \
                    "jit(decode_burst)/while/body/layer/hc/div"
                ops.append(["fusion.99_fusion", at + 5 * us, 2 * us])
                scopes["7"]["fusion.99_fusion"] = \
                    "jit(decode_burst)/while/body/layer/mlp/experts/dot"
    mods.append(["jit_prefill_pack_head(9)", 150 * us, 40 * us, 9])
    ops.append(["fusion.70_fusion", 150 * us, 30 * us])
    ops.append(["fusion.71_fusion", 180 * us, 8 * us])
    host = [["clock_anchor", 0, 0, {}]]
    return {"device": [{"name": "/device:TPU:0", "modules": mods,
                        "ops": ops}], "host": host, "scopes": scopes}


def _burst(t0_us, t1_us, slots, rows):
    return {"name": "decode_burst_device", "t": 1.0 + t0_us / 1e6,
            "dur_ms": (t1_us - t0_us) / 1e3,
            "args": {"steps": 2, "slot_ids": list(range(slots)),
                     "ctx_rows": rows}}


def test_hyper_connection_time_and_latent_rows_are_read_off_a_capture():
    out = hc_share_pct.reduce(_capture())
    assert out["decode_module_s"] == pytest.approx(3 * 100e-6)
    assert out["decode_hc_s"] == pytest.approx(36 * 1e-6)   # not the pack's
    walk = mla_prefill_walk_share_pct.reduce(_capture())
    assert walk == {"prefill_module_s": pytest.approx(40e-6),
                    "prefill_walk_s": pytest.approx(8e-6)}
    spans = [_burst(-5, 105, 3, 27000), _burst(195, 305, 4, 44000)]
    got = _ling.reduce(_capture(), spans, {"epoch_ns": int(1e9)})
    assert got["mla_calls"] == 36 and got["mla_matched_calls"] == 24
    assert got["mla_live_slot_calls"] == 12 * 3 + 12 * 4
    assert got["mla_ctx_rows"] == 12 * 27000 + 12 * 44000
    assert got["kda_calls"] == 0
    ctx = types.SimpleNamespace(spans=spans + [
        {"name": "prefill_device", "t": 1.0, "dur_ms": 1.0, "args": {}}])
    assert mla_ctx_rows_mean.read(ctx) == pytest.approx(71000 / 7)


def _ctx(summary):
    return types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"},
        _ling=summary, trace=None, state_samples=[], spans=[],
        state_end={})


def test_the_latent_walks_roofline_on_hand_made_numbers():
    summary = {"mla_matched_s": 0.2, "mla_live_slot_calls": 16 * 6 * 100,
               "mla_ctx_rows": 16 * 9000 * 6 * 100}
    by_bytes = 16 * 9000 * 6 * 100 * 1152 / 819e9
    by_flops = 2 * (16 * 9000 + 16) * 6 * 100 * 32 * 1088 / 197e12
    assert by_bytes > by_flops
    assert mla_decode_roofline.read(_ctx(summary)) == pytest.approx(
        100 * by_bytes / 0.2)
    full = {**summary, "mla_matched_s": by_bytes}
    assert mla_decode_roofline.read(_ctx(full)) == pytest.approx(100.0)


def test_readers_return_none_where_the_program_has_nothing_to_read():
    """The parent's program: no capture directory, no scope, no
    ``ctx_rows`` on a burst; and a capture that names no such scope."""
    empty = types.SimpleNamespace(
        cell=spec.resolve(CELL), device={"kind": "TPU v5 lite"},
        state_end={"profile": None}, state_samples=[], trace=None,
        spans=[{"name": "decode_burst_device", "t": 1.0, "dur_ms": 1.0,
                "args": {"steps": 2, "slot_ids": [0, 1]}}])
    for reader in (hc_share_pct, mla_ctx_rows_mean, mla_decode_roofline,
                   mla_prefill_walk_share_pct):
        assert reader.read(empty) is None
    assert mla_ctx_rows_mean.read(types.SimpleNamespace(spans=[])) is None
    bare = _capture()
    bare["scopes"] = {}
    assert hc_share_pct.reduce(bare)["decode_hc_s"] == 0.0
    assert mla_prefill_walk_share_pct.reduce(bare)["prefill_walk_s"] == 0.0
    zeros = dict.fromkeys(("mla_matched_s", "mla_live_slot_calls",
                           "mla_ctx_rows"), 0.0)
    assert mla_decode_roofline.read(_ctx(zeros)) is None


def test_the_parent_fails_the_new_cell_at_once():
    """The parent, with this PR's benchmark files laid over it, lacks
    ``models/xing4.py``: the check's first import of the program fails,
    before a server is started; a checkout without the family module fails
    at ``spec.family_of``."""
    conf = dict(spec.resolve(CELL).config, family="xing4_absent")
    with pytest.raises(spec.SpecError, match="has no module"):
        spec.family_of(conf)
    with open(os.path.join(spec.ROOT, "benchmark", "families",
                           "xing4.py")) as f:
        src = f.read()
    # the program is imported inside the functions that run it, first of all
    # by the sound run the check starts with
    assert "from localai_tpu.models import xing4 as model" in src
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
    at = {c["name"]: i for i, c in enumerate(new["configs"])}[CONFIG]
    assert new["configs"][:at] == old["configs"][:at]       # put at the end
    assert new["configs"][at + 1:] == old["configs"][at:]
    at = {w["name"]: i for i, w in enumerate(new["workloads"])}[CELL]
    assert new["workloads"][:at] + new["workloads"][at + 1:] == \
        old["workloads"]
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert new[key] == old[key]
    touched = 0
    kept = [m for m in new["per_layer"] if m["name"] not in OURS]
    for was, now in zip(old["per_layer"], kept, strict=True):
        was, now = dict(was), dict(now)
        if now.get("workloads") != was.get("workloads"):
            # appended to the list as it stood when this cell came
            mine = now["workloads"].index(CELL)
            assert now["workloads"][:mine] == was["workloads"][:mine]
            touched += 1
            now.pop("workloads"), was.pop("workloads")
        assert now == was
    assert touched == 29
    # every file the family brought is a new one beside those that were
    # there: none of the copy's remaining files names it
    for d, _dirs, names in os.walk(os.path.join(root, "benchmark")):
        for n in names:
            if n.endswith((".py", ".json")):
                with open(os.path.join(d, n)) as f:
                    assert "xing4" not in f.read(), os.path.join(d, n)


# ---- traffic ----

def _schedule(seed, seconds=51):
    cell = spec.resolve(CELL)
    gen = spec.generator(cell.traffic["generator"])
    return gen(cell.traffic, seconds, seed, cell.config["vocab_size"],
               int(cell.config["serving"]["context_size"]))


@pytest.mark.parametrize("seed", [1, 12345, 2147483659])
def test_docqa_long_sends_the_same_multiset_for_every_seed(seed):
    def lengths(s):
        return sorted((r.prompt_tokens, r.max_tokens) for r in s.window)

    base, other = lengths(_schedule(7)), lengths(_schedule(seed))
    assert base == other and len(base) >= 60
    assert all(6144 + 32 <= p <= 12288 + 96 and 96 <= o <= 192
               for p, o in base)
    assert max(p + o for p, o in base) <= 12288 + 96 + 192 < 16384


def test_docqa_long_asks_each_document_four_times_five_requests_apart():
    mine = spec.resolve(CELL).traffic
    short = spec.resolve("nemo12b.docqa_rate").traffic
    differ = {k for k in mine if mine[k] != short.get(k)}
    assert differ == {"rate_per_s", "warmup_s", "doc_tokens",
                      "output_tokens", "check_lengths"}
    assert (mine["asks_per_doc"], mine["ask_stride"], mine["warmup_s"]) == \
        (4, 5, 20)
    assert mine["doc_tokens"] == {"dist": "uniform", "min": 6144,
                                  "max": 12288}
    assert mine["question_tokens"] == {"dist": "uniform", "min": 32,
                                       "max": 96}
    assert mine["output_tokens"] == {"dist": "uniform", "min": 96,
                                     "max": 192}
    sched = _schedule(2147483659)
    docs = {}
    for k, r in enumerate(sched.window):
        docs.setdefault(r.tag.split(".")[0], []).append(k)
    assert all(len(at) == 4 for at in docs.values())
    assert min(b - a for at in docs.values()
               for a, b in zip(at, at[1:])) >= 4
    # three asks in four can be admitted from shared pages: about 70% of
    # the window's prompt tokens
    total = sum(r.prompt_tokens for r in sched.window)
    first = sum(sched.window[at[0]].prompt_tokens for at in docs.values())
    assert 0.70 < 1 - first / total < 0.76
    why = next(w["why"] for w in spec.load_benchmark()["workloads"]
               if w["name"] == CELL)
    assert f"{mine['rate_per_s']:g} req/s" in why and "knee" in why
    assert len(why) <= 200


# ---- the whole command ----

@pytest.mark.slow
def test_the_whole_command_runs_under_rehearsal(tmp_path):
    """Checkpoint and check -> server -> window -> last line, at the toy
    width on the CPU: the line is stamped cpu (never correct), no request
    fails, nothing compiles after the warm-up. Marked slow: the cell's own
    lengths (a reference pass over 12384 positions, documents of 6-12 k)
    take eight minutes on the CPU at any width (477 s by hand, PR 50)."""
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
    assert set(got) >= {"logits_err", "latent_err", "hc_err", "route_err",
                        "platform"}
    assert got["compiles_after_warmup"]["ok"] and got["failed_requests"]["ok"]
    assert not got["platform"]["ok"]
