"""The ling_hybrid family (models/ling_hybrid.py: Kimi delta attention layers
beside MLA layers over a latent page pool, a group-limited routed expert
feed-forward of which this chip holds a strided share, a shared expert) on
the served path, at toy width on seeded random weights: against the plain
float32 reference (benchmark/reference/ling_hybrid_f32.py, which imports
nothing of the program), the grouped router's equations, the shares of the
experts adding up to the uncut layer, what is refused by name, the loader's
held experts, through the engine and through the runner."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.models import ling_hybrid as lh
from localai_tpu.ops import kvcache, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_config(layers=6, **change):
    """benchmark/rehearsal/ling_hybrid.json: 32 experts in 4 groups (2
    kept), 4 a token, 8 held here (every fourth), the published pattern of
    layers at a narrow width."""
    with open(os.path.join(ROOT, "benchmark", "rehearsal",
                           "ling_hybrid.json")) as f:
        conf = json.load(f)
    conf.update(num_hidden_layers=layers, **change)
    conf["check"]["layers"] = layers
    return conf


# ---- against the reference ----

@pytest.fixture(scope="module")
def both(tmp_path_factory):
    """One whole period (KDA + dense twice, KDA + experts three times, MLA +
    experts) in float32: the program's prefill (a 600-token prompt in two
    chunks packed beside shorter ones: fresh and ``continued`` segments, a
    chunk boundary inside a segment), then up to 9 decode steps through the
    latent pool and the states, a slot past its last step idle; and the
    reference's full forward, one sequence at a time, following the
    program's choices."""
    from safetensors import safe_open

    from benchmark import make_checkpoint, spec
    from benchmark.reference import check
    from benchmark.reference import ling_hybrid_f32 as ref_model

    conf = _toy_config()
    fam = spec.family_of(conf)
    hf = {k: conf[k] for k in fam.HF_KEYS if k in conf}
    ckpt = str(tmp_path_factory.mktemp("ling") / "ckpt")
    make_checkpoint.make(conf, 11, ckpt)
    seqs = check.sequences([[70, 4], [130, 6], [5, 3], [600, 9]], 11,
                           conf["vocab_size"])
    prog = fam._run_program(ckpt, hf, "float32", {}, seqs, 1024)
    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        read = ref_model.weight_reader(h.get_tensor, "bfloat16")
        spec_ = [(p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
                 for p, d in seqs]
        ref = ref_model.forward(read, hf, 6, spec_, choices=prog[2])
    return fam, hf, prog, ref


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "ling_hybrid_f32.py")) as f:
        assert "localai_tpu" not in f.read()


@pytest.mark.parametrize("what", ["logits", "latent", "state", "conv"])
def test_program_agrees_with_the_float32_reference(both, what):
    from benchmark.reference.check import rel_err

    _, _, (logits, groups, _), ref = both
    got, want = {
        "logits": (logits, [r["logits"] for r in ref]),
        "latent": (groups["latent"], [r["latent"] for r in ref]),
        "state": (groups["state"],
                  [r["state"][i] for i in (0, 1) for r in ref]),
        "conv": (groups["conv"], [r["conv"][i] for i in (0, 1) for r in ref]),
    }[what]
    flat = [np.concatenate([np.asarray(x).ravel() for x in side])
            for side in (got, want)]
    assert rel_err(*flat) < 5e-5


def test_in_float32_the_programs_choices_are_the_references_own(both):
    fam, hf, (_, _, chosen), ref = both
    for c, r in zip(chosen, ref):
        assert c.shape == r["chosen"].shape == (c.shape[0], 4, 4)
        gb, below, above = fam.route_shortfall(c, r["biased"], r["groups"],
                                               4, 2)
        assert gb.max() < 1e-5 and below.max() < 1e-5 and above.max() < 1e-5
        same = (np.sort(c, -1) == np.sort(r["chosen"], -1)).all(-1)
        assert same.mean() > 0.995


# ---- the router ----

def _router(T=12, D=16, E=32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (T, D)),
            jax.random.normal(k2, (D, E)) / np.sqrt(D),
            jax.random.normal(k3, (E,)) * 0.05)


def test_grouped_route_is_the_references_rule_written_out():
    """Per token in numpy: a group's score the sum of its two largest biased
    scores, the best 2 of 4 groups kept, the 4 largest inside them chosen,
    weights the plain scores over their sum times the scale."""
    h, wg, bias = _router()
    experts, w = moe.route(h, wg, bias, 4, scale=2.5, n_group=4,
                           topk_group=2, eps=1e-20)
    s = 1 / (1 + np.exp(-np.asarray(h, np.float64) @ np.asarray(wg)))
    c = s + np.asarray(bias)
    for t in range(h.shape[0]):
        gs = np.sort(c[t].reshape(4, 8), -1)[:, -2:].sum(-1)
        kept = np.argsort(-gs)[:2]
        inside = np.full(32, -np.inf)
        for g in kept:
            inside[g * 8:(g + 1) * 8] = c[t, g * 8:(g + 1) * 8]
        want = np.argsort(-inside)[:4]
        assert set(np.asarray(experts[t])) == set(want)
        assert set(np.asarray(experts[t]) // 8) <= set(kept)
        ws = s[t, np.asarray(experts[t])]
        np.testing.assert_allclose(np.asarray(w[t]), ws / ws.sum() * 2.5,
                                   rtol=1e-5)


def test_one_group_is_todays_rule_bit_for_bit():
    """n_group = 1, topk_group = 1 (the defaults): the rule lfm2_moe's
    cell is measured with, written out as it was before groups."""
    h, wg, bias = _router(T=40, E=8)
    live = jnp.arange(40) % 5 != 0
    for b in (bias, None):
        got = moe.route(h, wg, b, 2, scale=1.0, active=live)
        f32 = jnp.float32
        s = jax.nn.sigmoid(jnp.dot(h.astype(f32), wg.astype(f32),
                                   precision=jax.lax.Precision.HIGHEST))
        choice = s if b is None else s + b.astype(f32)[None]
        experts = jax.lax.top_k(choice, 2)[1].astype(jnp.int32)
        w = jnp.take_along_axis(s, experts, axis=1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        np.testing.assert_array_equal(
            np.asarray(got[0]), np.asarray(jnp.where(live[:, None], experts,
                                                     8)))
        np.testing.assert_array_equal(
            np.asarray(got[1]), np.asarray(jnp.where(live[:, None], w, 0.0)))
    same = moe.route(h, wg, bias, 2, n_group=1, topk_group=1)
    np.testing.assert_array_equal(np.asarray(same[0]),
                                  np.asarray(moe.route(h, wg, bias, 2)[0]))


def test_no_group_limit_is_another_choice():
    h, wg, bias = _router(T=64)
    a = moe.route(h, wg, bias, 4, n_group=4, topk_group=2)[0]
    b = moe.route(h, wg, bias, 4, n_group=4, topk_group=4)[0]
    assert (np.sort(np.asarray(a), -1) != np.sort(np.asarray(b), -1)).any()


# ---- the share test ----

def test_the_four_strided_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four chips of the deployment compute, each
    over its own strided quarter of the experts, and the shared expert
    counted ONCE add up to what the layer gives with every expert held; a
    row that is not live adds nothing anywhere; the held pairs of the four
    shares add up to the routed pairs."""
    E, D, F, T, k = 32, 16, 8, 24, 4
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    h = jax.random.normal(ks[0], (T, D))
    wg = jax.random.normal(ks[1], (D, E)) / np.sqrt(D)
    bias = jax.random.normal(ks[2], (E,)) * 0.05
    w1 = jax.random.normal(ks[3], (2, E, D, F)) / np.sqrt(D)
    w3 = jax.random.normal(ks[4], (2, E, D, F)) / np.sqrt(D)
    w2 = jax.random.normal(ks[5], (2, E, F, D)) / np.sqrt(F)
    sh = [jax.random.normal(ks[6], (D, F)), jax.random.normal(ks[7], (D, F)),
          jax.random.normal(ks[6], (F, D))]
    live = jnp.arange(T) % 6 != 5
    experts, w = moe.route(h, wg, bias, k, scale=2.5, active=live, n_group=4,
                           topk_group=2, eps=1e-20)
    whole = moe.experts_ffn(h, experts, w, w1, w3, w2, jnp.int32(1)) \
        + moe.shared_ffn(h, *sh)
    parts, held_pairs = [], 0
    for rank in range(4):
        held = tuple(range(rank, E, 4))
        ids = jnp.asarray(held)
        parts.append(moe.experts_ffn(
            h, experts, w, w1[:, ids], w3[:, ids], w2[:, ids], jnp.int32(1),
            held=held, n_experts=E))
        st = np.asarray(moe.route_stats(experts, E, held))
        assert st.shape == (8 + 2,) and st[-1] == k * int(live.sum())
        held_pairs += st[:8].sum()
    total = sum(parts) + moe.shared_ffn(h, *sh)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)
    assert held_pairs == k * int(live.sum())
    for p in parts:
        assert not np.asarray(p)[~np.asarray(live)].any()
    # strided: whichever groups a row keeps, every share holds a quarter of
    # its candidates, so the shares' loads are near even
    assert np.abs(np.asarray([np.abs(np.asarray(p)).sum() for p in parts])
                  ).min() > 0


def test_a_share_of_the_model_is_the_references_share(both):
    """The fixture's program held 8 of 32 experts (every fourth): the
    reference, given the same share, agreed; with another share it does
    not."""
    fam, hf, (logits, _, chosen), ref = both
    assert fam.held_experts(hf) == list(range(0, 32, 4))
    cfg = lh.LingHybridConfig.from_hf_config(hf)
    assert cfg.num_experts == 32 and cfg.held == tuple(range(0, 32, 4))
    assert cfg.layer_kinds == ("kda_dense", "kda_dense", "kda_moe",
                               "kda_moe", "kda_moe", "mla_moe")
    held = np.isin(np.concatenate(chosen), fam.held_experts(hf))
    assert 0.15 < held.mean() < 0.35             # a quarter, near enough


# ---- what is refused ----

@pytest.mark.parametrize("change, what", [
    ({"expert_swiglu_limit_list": [0, 0, 0, 4, 0, 0]}, "clamped SwiGLU"),
    ({"share_expert_swiglu_limit_list": [0, 0, 5, 0, 0, 0]},
     "clamped SwiGLU"),
    ({"num_nextn_predict_layers": 1}, "multi-token prediction"),
    ({"q_lora_rank": 64}, "low-rank query"),
    ({"use_kda_lora": True}, "low-rank KDA gate"),
    ({"use_nGPT": True}, "use_nGPT"),
    ({"score_function": "softmax"}, "score_function"),
    ({"kda_safe_gate": False}, "lower bound"),
    ({"gated_attention_proj_granularity_type": "elementwise"}, "head_wise"),
    ({"first_k_dense_replace": 6}, "no expert layer"),
    ({"num_experts": 9}, "strided share"),
], ids=["swiglu", "shared-swiglu", "mtp", "q-lora", "kda-lora", "ngpt",
        "softmax", "unsafe-gate", "gate-kind", "all-dense", "share"])
def test_what_is_not_built_is_refused_by_name(change, what):
    with pytest.raises(ValueError, match=what):
        lh.LingHybridConfig.from_hf_config({**_toy_config(), **change})


def test_a_contiguous_cache_or_an_int8_one_is_refused():
    cfg = lh.LingHybridConfig.from_hf_config(_toy_config())
    with pytest.raises(ValueError, match="paged"):
        lh.init_cache(cfg, 2, 64)
    with pytest.raises(ValueError, match="int8 latent"):
        lh.init_cache(cfg, 2, 64, dtype=jnp.int8, page_size=16)
    ck, cv = lh.init_cache(cfg, 2, 64, page_size=16)
    # one plane: the latent pool; no second one
    assert ck["pages"].shape == (1, 8, 16, 1, 128)
    assert cv["pages"].shape[0] == 0 and cv["pages"].size == 0
    assert ck["kda"].shape == (5, 2, 4, 16, 16)
    assert ck["kda"].dtype == jnp.float32
    assert lh.latent_cache_bytes(ck) == 8 * 16 * 128 * 2


# ---- through the engine ----

CFG = dataclasses.replace(
    lh.LingHybridConfig.from_hf_config(_toy_config(), dtype=jnp.float32),
    vocab_size=256)


def _engine(tok, **kw):
    params = lh.init_params(CFG, jax.random.PRNGKey(0))
    ecfg = eng.EngineConfig(**{**dict(
        num_slots=1, max_context=128, prefill_buckets=(16, 64),
        decode_burst=4, cache_dtype=jnp.float32), **kw})
    e = eng.Engine(CFG, params, tok, ecfg, family=lh)
    e.start()
    return e


def _greedy(tok, prompt, n):
    return eng.GenRequest(
        prompt_ids=tok.encode(prompt),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, ignore_eos=True)


def _collect(out):
    events = []
    while (ev := out.get(timeout=120.0)) is not None:
        events.append(ev)
    return events


def test_engine_counts_held_pairs_and_routed_pairs_apart(byte_tokenizer):
    assert lh.CAPABILITIES == {"paged", "packed_prefill", "route_stats"}
    e = _engine(byte_tokenizer, num_slots=3)
    try:
        assert e._paged and e._packed and e._pcache is None
        prompt = "one live slot of three"
        ids = eng.event_ids(list(e.generate(_greedy(byte_tokenizer, prompt,
                                                    9))))
        st = e.state_snapshot()
        spans = [s for s in e.tracer.spans()
                 if s["name"] == "decode_burst_device"]
    finally:
        e.shutdown()
    assert len(ids) == 9 and st["family"] == "ling_hybrid"
    # 5 KDA layers x 3 slots: a state of 4 x 16 x 16 and 3 rows of 192
    assert st["recurrent_state_bytes"] == 5 * 3 * (4 * 16 * 16 + 3 * 192) * 4
    # 1 MLA layer: the default pool of 3 x 128 rows of 128 float32
    assert st["latent_cache_bytes"] == 3 * 128 * 128 * 4
    assert st["attention"]["decode"]["impl"] == "jnp:mla_gather_append" \
        if "decode" in st["attention"] else True
    m = st["moe"]
    assert m["experts"] == 8                         # held here, of 32
    n_prompt = len(byte_tokenizer.encode(prompt))
    for kind in ("prefill", "decode"):
        c = m[kind]
        pairs, routed = np.asarray(c["pairs"]), np.asarray(c["pairs_routed"])
        assert pairs.shape == (4, 8) and routed.shape == (4,)
        assert (routed == routed[0]).all() and routed[0] % 4 == 0
        if kind == "prefill":
            assert routed[0] == 4 * n_prompt        # k pairs a prompt token
        else:
            assert 4 * 8 <= routed[0] <= 4 * c["steps"]
        assert (pairs.sum(1) <= routed).all() and pairs.sum() > 0
        assert pairs.sum() < routed.sum()           # three quarters elsewhere
    assert all("ctx_rows" in s["args"] for s in spans)
    with open(eng.__file__) as f:
        assert "ling_hybrid" not in f.read()    # no test of the family


def test_two_concurrent_requests_stream_what_each_streams_alone(
        byte_tokenizer):
    prompts = ["the first tenant asks a short question",
               "and a second, longer one, arrives while the first decodes"]
    alone = []
    for p in prompts:
        e = _engine(byte_tokenizer)
        try:
            alone.append(eng.event_ids(list(e.generate(
                _greedy(byte_tokenizer, p, 12)))))
        finally:
            e.shutdown()
    e = _engine(byte_tokenizer, num_slots=2)
    try:
        outs = [e.submit(_greedy(byte_tokenizer, p, 12)) for p in prompts]
        together = [eng.event_ids(_collect(o)) for o in outs]
    finally:
        e.shutdown()
    assert together == alone and all(len(t) == 12 for t in together)


def test_a_long_prompt_in_chunks_streams_what_one_pack_does(byte_tokenizer):
    """States, tails and latent rows carried from pack to pack: the
    continued MLA form over the pool and the continued KDA chunks."""
    prompt = "tails carried from pack to pack " * 3
    outs = []
    for chunk in (16, 128):
        e = _engine(byte_tokenizer, prefill_chunk=chunk,
                    prefill_buckets=(chunk,))
        try:
            outs.append(eng.event_ids(list(e.generate(
                _greedy(byte_tokenizer, prompt, 10)))))
        finally:
            e.shutdown()
    assert len(outs[0]) == 10 and outs[0] == outs[1]


# ---- through the loader and the runner ----

def _write_checkpoint(tmp_path, **change):
    from benchmark import make_checkpoint

    d = str(tmp_path / "ckpt")
    make_checkpoint.make(_toy_config(), 3, d)
    if change:
        with open(os.path.join(d, "config.json")) as f:
            c = json.load(f)
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump({**c, **change}, f)
    return d


def _load(d, num_slots=6, **kw):
    from localai_tpu.backend import contract_pb2 as pb
    from localai_tpu.backend.runner import EngineServicer

    sv = EngineServicer()
    res = sv.LoadModel(pb.ModelOptions(
        model=d, context_size=128, num_slots=num_slots, dtype="float32",
        prefill_buckets=[32], **kw), None)
    return sv, res


def test_the_loader_streams_the_held_experts_and_reads_no_other(tmp_path):
    from safetensors import safe_open

    d = _write_checkpoint(tmp_path)
    with open(os.path.join(d, "config.json")) as f:
        hf = json.load(f)
    assert hf["model_type"] == "ling_hybrid" and hf["num_experts"] == 8
    cfg = lh.LingHybridConfig.from_hf_config(hf, dtype=jnp.float32)
    params = lh.load_hf_params(d, cfg, dtype=jnp.float32)
    lay = params["layers"]
    assert lay["w1"].shape == (4, 8, 128, 32) == lay["w3"].shape
    assert lay["w2"].shape == (4, 8, 32, 128)
    assert lay["router"].shape == (4, 128, 32)       # the model's width
    assert lay["router"].dtype == lay["kda_A_log"].dtype == jnp.float32
    with safe_open(os.path.join(d, "model.safetensors"), "np") as h:
        names = set(h.keys())
        # the checkpoint holds the held experts alone, under global ids: a
        # loader that read another would have failed
        assert "model.layers.2.mlp.experts.4.up_proj.weight" in names
        assert not any(f".experts.{e}." in n for n in names
                       for e in (1, 2, 3, 5))
        ff = "model.layers.{}.mlp."
        for mi, j in ((0, 0), (3, 5), (2, 7)):
            for leaf, name in (("w1", "gate_proj"), ("w3", "up_proj"),
                               ("w2", "down_proj")):
                np.testing.assert_array_equal(
                    lay[leaf][mi, j], h.get_tensor(
                        ff.format(2 + mi) + f"experts.{4 * j}.{name}.weight").T)
        np.testing.assert_array_equal(
            lay["router"][2], h.get_tensor(ff.format(4) + "gate.weight").T)
        np.testing.assert_array_equal(
            lay["sh_w2"][1],
            h.get_tensor(ff.format(3) + "shared_experts.down_proj.weight").T)
        a = "model.layers.3.linear_attn."
        np.testing.assert_array_equal(
            lay["kda_qkv"][3], np.concatenate(
                [h.get_tensor(a + f"{n}_proj.weight").T for n in "qkv"], -1))
        np.testing.assert_array_equal(
            lay["kda_conv"][3], np.concatenate(
                [h.get_tensor(a + f"{n}_conv1d.weight")[:, 0, :].T
                 for n in "qkv"], -1))
        np.testing.assert_array_equal(
            lay["mla_kvb"][0],
            h.get_tensor("model.layers.5.self_attn.kv_b_proj.weight").T)
    with pytest.raises(ValueError, match="int4"):
        lh.load_hf_params(d, cfg, quantize="int4")
    q = lh.load_hf_params(d, cfg, dtype=jnp.float32, quantize="int8")["layers"]
    assert set(q["kda_qkv"]) == {"q", "s"} and q["w1"].dtype == jnp.float32


def test_runner_serves_a_ling_hybrid_checkpoint(tmp_path, monkeypatch):
    from localai_tpu.backend import contract_pb2 as pb

    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    sv, res = _load(_write_checkpoint(tmp_path), mesh_tp=1)
    try:
        assert res.success, res.message
        assert sv.engine.family is lh and sv.engine._paged

        class _Ctx:
            def is_active(self):
                return True

            def abort(self, code, msg):
                raise AssertionError(f"abort: {code} {msg}")

        chunks = list(sv.PredictStream(pb.PredictOptions(
            prompt="t5 t9 t40 t7", max_tokens=6, temperature=0.0,
            ignore_eos=True), _Ctx()))
        assert "".join(c.message.decode("utf-8", "replace") for c in chunks)
        st = sv.engine.state_snapshot()
        assert st["moe"]["decode"]["steps"] >= 5
        assert st["latent_cache_bytes"] > 0
    finally:
        if getattr(sv, "engine", None) is not None:
            sv.engine.shutdown()


def test_runner_refuses_a_mesh_a_projector_and_a_clamped_layer(
        tmp_path, monkeypatch):
    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    d = _write_checkpoint(tmp_path)
    _, res = _load(d, mesh_tp=4)
    assert not res.success and "one device" in res.message
    _, res = _load(d, mesh_tp=1, mmproj="tower")
    assert not res.success and "vision tower" in res.message
    d2 = _write_checkpoint(tmp_path / "x",
                           expert_swiglu_limit_list=[0, 0, 0, 0, 4, 4])
    _, res = _load(d2, mesh_tp=1)
    assert not res.success and "clamped SwiGLU" in res.message
    d3 = _write_checkpoint(tmp_path / "y", model_type="ling_v9")
    _, res = _load(d3, mesh_tp=1)
    assert not res.success and "ling_hybrid" in res.message
