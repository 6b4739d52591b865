"""The lfm2_moe family (models/lfm2_moe.py: gated short-convolution layers
beside GQA layers, a routed expert feed-forward after all but the leading
dense layers) on the served path, at toy width on seeded random weights:
against the plain float32 reference (benchmark/reference/lfm2_moe_f32.py,
which imports nothing of the program), the router's equations one by one,
the grouped expert form against a per-token loop, a slot that does not
decode, the loader's stacked leaves, through the engine (counters,
concurrent requests) and through the runner."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.models import lfm2_moe as lm
from localai_tpu.ops import kvcache, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_config(layers=10, **change):
    """benchmark/rehearsal/lfm2_moe.json: 8 experts, 2 a token, the
    published pattern of layers at a narrow width."""
    with open(os.path.join(ROOT, "benchmark", "rehearsal",
                           "lfm2_moe.json")) as f:
        conf = json.load(f)
    conf.update(num_hidden_layers=layers, **change)
    conf["check"]["layers"] = layers
    return conf


# ---- against the reference ----

@pytest.fixture(scope="module")
def both(tmp_path_factory):
    """One dense layer and one period (conv + dense, attention + experts,
    conv + experts x3) in float32: the program's prefill (a 600-token
    prompt in two chunks packed beside shorter ones: fresh and
    ``continued`` segments, a chunk boundary inside a segment), then up to
    9 decode steps through the paged cache and the tails, a slot past its
    last step idle; and the reference's full forward, one sequence at a
    time, following the program's choices."""
    from safetensors import safe_open

    from benchmark import make_checkpoint, spec
    from benchmark.reference import check
    from benchmark.reference import lfm2_moe_f32 as ref_model

    conf = _toy_config(layers=5, num_dense_layers=1, layer_types=[
        "conv", "full_attention", "conv", "conv", "conv"])
    fam = spec.family_of(conf)
    hf = {k: conf[k] for k in fam.HF_KEYS if k in conf}
    ckpt = str(tmp_path_factory.mktemp("lfm2") / "ckpt")
    make_checkpoint.make(conf, 11, ckpt)
    seqs = check.sequences([[70, 4], [130, 6], [5, 3], [600, 9]], 11,
                           conf["vocab_size"])
    prog = fam._run_program(ckpt, hf, "float32", {}, seqs, 1024)
    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        read = ref_model.weight_reader(h.get_tensor, "bfloat16")
        spec_ = [(p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
                 for p, d in seqs]
        ref = ref_model.forward(read, hf, 5, spec_, choices=prog[2])
    return fam, hf, prog, ref


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "lfm2_moe_f32.py")) as f:
        assert "localai_tpu" not in f.read()


@pytest.mark.parametrize("what", ["logits", "kv", "conv"])
def test_program_agrees_with_the_float32_reference(both, what):
    from benchmark.reference.check import rel_err

    _, _, (logits, groups, _), ref = both
    got, want = {
        "logits": (logits, [r["logits"] for r in ref]),
        "kv": (groups["kv"], [r[x] for x in ("k", "v") for r in ref]),
        "conv": (groups["conv"], [r["conv"][i] for i in (0, 1) for r in ref]),
    }[what]
    flat = [np.concatenate([np.asarray(x).ravel() for x in side])
            for side in (got, want)]
    assert rel_err(*flat) < 2e-5


def test_in_float32_the_programs_choices_are_the_references_own(both):
    fam, hf, (_, _, chosen), ref = both
    for c, r in zip(chosen, ref):
        assert c.shape == r["chosen"].shape == (c.shape[0], 4, 2)
        below, above = fam.route_shortfall(c, r["biased"], 2)
        assert below.max() < 1e-5 and above.max() < 1e-5
        # the same sets but for a tie within float32 rounding
        same = (np.sort(c, -1) == np.sort(r["chosen"], -1)).all(-1)
        assert same.mean() > 0.999


def test_route_group_counts_the_choices_out_of_slack():
    from benchmark.families import lfm2_moe as fam

    biased = np.asarray([[[0.9, 0.8, 0.5, 0.48, 0.1]]])      # k = 2: kth 0.8
    ref = {"biased": [biased], "k": 2}

    def count(chosen):
        return fam._route_group([np.asarray([[chosen]])], ref)[0].item() - 1

    assert count([0, 1]) == 0
    assert count([1, 0]) == 0                   # order is not a choice
    assert count([0, 2]) == 1                   # 0.5 is 0.3 under the 2nd
    assert count([2, 3]) == 3                   # both low, and 0.9 left out
    near = np.asarray([[[0.9, 0.8, 0.79, 0.1, 0.1]]])
    assert fam._route_group([np.asarray([[[0, 2]]])],
                            {"biased": [near], "k": 2})[0].item() == 1


# ---- the router, one equation at a time ----

def _router(T=64, D=32, E=8, seed=0):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((D, E)) / np.sqrt(D), jnp.float32)
    return h, wg, jax.nn.sigmoid(h @ wg)


def test_the_choice_is_the_k_largest_scores_and_the_weights_sum_to_one():
    h, wg, s = _router()
    experts, w = moe.route(h, wg, None, 3)
    np.testing.assert_array_equal(np.sort(experts, -1),
                                  np.sort(np.argsort(-s, -1)[:, :3], -1))
    got = np.take_along_axis(np.asarray(s), np.asarray(experts), 1)
    np.testing.assert_allclose(w, got / (got.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    _, raw = moe.route(h, wg, None, 3, norm_topk=False)
    np.testing.assert_allclose(raw, got, rtol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weight():
    h, wg, s = _router()
    bias = jnp.zeros((8,)).at[5].set(10.0)      # expert 5 always wins
    experts, w = moe.route(h, wg, bias, 2, norm_topk=False)
    assert (np.asarray(experts) == 5).any(-1).all()
    np.testing.assert_allclose(
        w, np.take_along_axis(np.asarray(s), np.asarray(experts), 1),
        rtol=1e-6)                              # the score, not score + 10
    plain, _ = moe.route(h, wg, None, 2)
    assert not (np.asarray(plain) == 5).any(-1).all()


def test_the_scale_multiplies_the_normalised_weights():
    h, wg, _ = _router()
    _, w1 = moe.route(h, wg, None, 2)
    _, w2 = moe.route(h, wg, None, 2, scale=2.5)
    np.testing.assert_allclose(w2, 2.5 * np.asarray(w1), rtol=1e-6)


def test_a_row_that_is_not_live_routes_nowhere():
    h, wg, _ = _router(T=6)
    live = jnp.asarray([True, False, True, True, False, False])
    experts, w = moe.route(h, wg, None, 2, active=live)
    assert (np.asarray(experts)[~np.asarray(live)] == 8).all()
    assert (np.asarray(w)[~np.asarray(live)] == 0).all()
    stats = np.asarray(moe.route_stats(experts, 8))
    assert stats[:8].sum() == 2 * 3             # k pairs a live row
    assert stats[8] == len(set(np.asarray(experts)[np.asarray(live)].ravel()))
    assert stats[9] == 2 * 3                    # every pair, held or not


# ---- the expert products ----

def _loop(h, experts, weights, w1, w3, w2):
    """One token, one choice at a time."""
    out = np.zeros(h.shape, np.float64)
    for t in range(h.shape[0]):
        for e, w in zip(experts[t], weights[t]):
            if e < w1.shape[0]:
                x = h[t].astype(np.float64)
                g, u = x @ w1[e], x @ w3[e]
                out[t] += w * ((g / (1 + np.exp(-g)) * u) @ w2[e])
    return out


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "gmm-interpreted"])
@pytest.mark.parametrize("routing", ["even", "one-expert", "some-unchosen",
                                     "idle-rows"])
def test_the_grouped_form_is_the_per_token_loop(kernel, routing):
    """Both grouped products: ``jax.lax.ragged_dot`` (what runs off the
    chip) and the Pallas kernel in interpret mode."""
    form = functools.partial(moe.experts_ffn, pallas=kernel, interpret=kernel)
    T, D, F, E, k = 24, 16, 8, 6, 2
    rng = np.random.default_rng(3)
    h = rng.standard_normal((T, D)).astype(np.float32)
    w1, w3 = (rng.standard_normal((E, D, F)).astype(np.float32) / 4
              for _ in range(2))
    w2 = rng.standard_normal((E, F, D)).astype(np.float32) / 3
    weights = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    experts = {
        "even": np.stack([np.arange(T) % E, (np.arange(T) + 3) % E], 1),
        "one-expert": np.stack([np.full(T, 4), np.full(T, 4)], 1),
        "some-unchosen": np.stack([np.arange(T) % 2, 2 + np.arange(T) % 2],
                                  1),                 # experts 4, 5 empty
        "idle-rows": np.where((np.arange(T) % 3 == 0)[:, None], E, np.stack(
            [np.arange(T) % E, (np.arange(T) + 1) % E], 1)),
    }[routing].astype(np.int32)
    if routing == "idle-rows":
        weights = np.where(experts == E, 0, weights).astype(np.float32)
    # the forms take the stacks whole and a layer: the second of three
    stack = [jnp.stack([jnp.ones_like(w) * 9, w, -jnp.asarray(w)])
             for w in map(jnp.asarray, (w1, w3, w2))]
    got = jax.jit(form)(*map(jnp.asarray, (h, experts, weights)), *stack,
                        jnp.int32(1))
    np.testing.assert_allclose(got, _loop(h, experts, weights, w1, w3, w2),
                               rtol=2e-4, atol=2e-5)


# ---- the model's own functions ----

CFG = lm.Lfm2MoeConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_layers=5,
    kinds=("conv", "attention", "conv", "conv", "conv"), num_dense_layers=1,
    num_heads=4, num_kv_heads=2, num_experts=8, num_experts_per_tok=2,
    rope_theta=10000.0, max_position_embeddings=256, dtype=jnp.float32)


def test_a_slot_that_does_not_decode_changes_no_state_and_counts_nowhere():
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    ck, cv = (kvcache.with_page_table(
        c, jnp.arange(16, dtype=jnp.int32).reshape(4, 4))
        for c in lm.init_cache(CFG, 4, 64, page_size=16))
    ck = dict(ck, conv=jax.random.normal(jax.random.PRNGKey(1),
                                         ck["conv"].shape))
    active = jnp.asarray([True, False, True, False])
    tokens = jnp.asarray([5, 6, 7, 8], jnp.int32)
    lengths = jnp.asarray([3, 9, 1, 0], jnp.int32)
    _, ck2, cv2, stats = jax.jit(
        lambda *a: lm.engine_decode(params, CFG, *a, route_stats=True))(
        tokens, lengths, active, ck, cv)
    idle = ~np.asarray(active)
    np.testing.assert_array_equal(np.asarray(ck2["conv"])[:, idle],
                                  np.asarray(ck["conv"])[:, idle])
    assert (np.asarray(ck2["conv"])[:, ~idle]
            != np.asarray(ck["conv"])[:, ~idle]).any()
    for a, b in ((ck, ck2), (cv, cv2)):     # two rows written a layer
        assert (np.asarray(a["pages"]) != np.asarray(b["pages"])
                ).any(axis=(-1, -2)).sum() == 2 * CFG.attn_layers
    st = np.asarray(stats).reshape(CFG.moe_layers, -1)
    assert (st[:, :-2].sum(1) == 2 * 2).all()       # k x live rows a layer
    assert ((st[:, -2] >= 2) & (st[:, -2] <= 4)).all()
    assert (st[:, -1] == 2 * 2).all()       # every expert held: all routed
    # and the idle slots' tokens do not matter
    _, _, _, stats2 = jax.jit(
        lambda *a: lm.engine_decode(params, CFG, *a, route_stats=True))(
        tokens.at[1].set(99).at[3].set(77), lengths, active, ck, cv)
    np.testing.assert_array_equal(stats, stats2)


def test_layer_runs_scan_the_dense_layers_then_whole_periods():
    from localai_tpu.models.hybrid_common import scan_layer_runs

    cfg = lm.Lfm2MoeConfig.from_hf_config(_toy_config(layers=40, layer_types=(
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
        + ["full_attention", "conv"])))
    seen = []

    def fn(kind):
        def one(carry, ki, i):
            jax.debug.callback(
                lambda k, j: seen.append((kind, int(k), int(j))), ki, i,
                ordered=True)
            return carry + 1
        return one

    kinds = cfg.layer_kinds
    n = jax.jit(lambda: scan_layer_runs(
        kinds, jnp.int32(0), {k: fn(k) for k in set(kinds)}, lead=2))()
    jax.effects_barrier()
    assert int(n) == 40
    want, count = [], {}
    for i, k in enumerate(kinds):
        want.append((k, count.get(k, 0), i))
        count[k] = count.get(k, 0) + 1
    assert seen == want
    # ten layers trace as three bodies: the dense run, the attention layer
    # and the run of three conv layers of one period
    traced = []

    def body(kind):
        def one(carry, ki, i):
            traced.append(kind)
            return carry + 1
        return one

    jax.make_jaxpr(lambda: scan_layer_runs(
        kinds[:10], jnp.int32(0), {k: body(k) for k in set(kinds)}, lead=2))()
    assert traced == ["conv_dense", "attention_moe", "conv_moe"]


@pytest.mark.parametrize("change, what", [
    ({"conv_bias": True}, "conv_bias"),
    ({"layer_types": ["conv", "conv", "sliding_attention"] + ["conv"] * 7},
     "'conv' or 'full_attention'"),
    ({"num_dense_layers": 3}, "past the first attention layer"),
    ({"num_dense_layers": 10, "layer_types": ["conv"] * 10},
     "no expert layer"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_type"),
], ids=["bias", "unknown-kind", "dense-past-attention", "all-dense", "rope"])
def test_what_is_not_built_is_refused_by_name(change, what):
    with pytest.raises(ValueError, match=what):
        lm.Lfm2MoeConfig.from_hf_config({**_toy_config(), **change})


def test_a_contiguous_cache_or_an_int8_one_is_refused():
    with pytest.raises(ValueError, match="paged"):
        lm.init_cache(CFG, 2, 64)
    with pytest.raises(ValueError, match="int8 KV"):
        lm.init_cache(CFG, 2, 64, dtype=jnp.int8, page_size=16)


# ---- through the engine ----

def _engine(tok, **kw):
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    ecfg = eng.EngineConfig(**{**dict(
        num_slots=1, max_context=128, prefill_buckets=(16, 64),
        decode_burst=4, cache_dtype=jnp.float32), **kw})
    e = eng.Engine(CFG, params, tok, ecfg, family=lm)
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


def test_engine_counters_add_up_to_k_pairs_a_routed_row(byte_tokenizer):
    assert lm.CAPABILITIES == {"paged", "packed_prefill", "route_stats"}
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
    assert len(ids) == 9 and st["family"] == "lfm2_moe"
    # 4 conv layers x 3 slots x 2 rows of 64 float32
    assert st["recurrent_state_bytes"] == 4 * 3 * 2 * 64 * 4
    m = st["moe"]
    assert m["experts"] == 8
    n_prompt = len(byte_tokenizer.encode(prompt))
    for kind, rows in (("prefill", n_prompt), ("decode", None)):
        c = m[kind]
        pairs = np.asarray(c["pairs"])
        assert pairs.shape == (4, 8) and c["steps"] >= 1
        per_layer = pairs.sum(1)
        assert (per_layer == per_layer[0]).all() and per_layer[0] % 2 == 0
        if rows is not None:
            assert per_layer[0] == 2 * rows         # k pairs a prompt token
        else:
            # every decode step that ran routed the one live row: the 8
            # tokens past the first, and what a burst ran past the last
            assert 2 * 8 <= per_layer[0] <= 2 * c["steps"]
        touched = np.asarray(c["experts_touched"])
        assert (touched <= np.minimum(per_layer, 8 * c["steps"])).all()
        assert (touched >= c["steps"]).all() or kind == "decode"
    # the spans' experts_touched are the counter, burst by burst
    assert sum(s["args"]["experts_touched"] for s in spans) == \
        sum(m["decode"]["experts_touched"])
    with open(eng.__file__) as f:
        assert "lfm2" not in f.read()        # no test of the family's name


def test_two_concurrent_requests_stream_what_each_streams_alone(
        byte_tokenizer):
    """Rows that share a decode step and a prefill pack share the grouped
    products: neither moves the other's bytes."""
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
    prompt = "tails carried from pack to pack " * 3
    outs = []
    for chunk in (16, 128):
        e = _engine(byte_tokenizer, prefill_chunk=chunk,
                    prefill_buckets=(chunk,))
        try:
            outs.append(eng.event_ids(list(e.generate(
                _greedy(byte_tokenizer, prompt, 10)))))
            packs = e.state_snapshot()["moe"]["prefill"]["steps"]
        finally:
            e.shutdown()
        assert packs == (-(-len(byte_tokenizer.encode(prompt)) // chunk))
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


def test_stacked_leaves_are_the_checkpoints_per_expert_tensors(tmp_path):
    from safetensors import safe_open

    d = _write_checkpoint(tmp_path)
    with open(os.path.join(d, "config.json")) as f:
        hf = json.load(f)
    assert hf["model_type"] == "lfm2_moe"
    cfg = lm.Lfm2MoeConfig.from_hf_config(hf, dtype=jnp.float32)
    params = lm.load_hf_params(d, cfg, dtype=jnp.float32)
    lay = params["layers"]
    assert "lm_head" not in params
    assert lay["w1"].shape == (8, 8, 128, 64) == lay["w3"].shape
    assert lay["w2"].shape == (8, 8, 64, 128)
    assert lay["router"].dtype == lay["expert_bias"].dtype == jnp.float32
    with safe_open(os.path.join(d, "model.safetensors"), "np") as h:
        assert "lm_head.weight" not in set(h.keys())
        ff = "model.layers.{}.feed_forward."
        for mi, e in ((0, 0), (3, 5), (7, 7)):
            for name in ("w1", "w3", "w2"):
                np.testing.assert_array_equal(
                    lay[name][mi, e], h.get_tensor(
                        ff.format(2 + mi) + f"experts.{e}.{name}.weight").T)
        np.testing.assert_array_equal(
            lay["router"][2], h.get_tensor(ff.format(4) + "gate.weight").T)
        np.testing.assert_array_equal(
            lay["expert_bias"][2], h.get_tensor(ff.format(4) + "expert_bias"))
        np.testing.assert_array_equal(
            lay["w_gate"][1], h.get_tensor(ff.format(1) + "w1.weight").T)
        in_proj = h.get_tensor("model.layers.3.conv.in_proj.weight")
        np.testing.assert_array_equal(lay["conv_in"][2], in_proj.T)
        np.testing.assert_array_equal(
            lay["conv_w"][2],
            h.get_tensor("model.layers.3.conv.conv.weight")[:, 0, :].T)
        np.testing.assert_array_equal(
            lay["q_norm"][1],
            h.get_tensor("model.layers.6.self_attn.q_layernorm.weight"))
    assert lay["conv_in"].shape == (8, 128, 384) and lay["wq"].shape[0] == 2
    with pytest.raises(ValueError, match="int4"):
        lm.load_hf_params(d, cfg, quantize="int4")
    q = lm.load_hf_params(d, cfg, dtype=jnp.float32, quantize="int8")["layers"]
    assert set(q["conv_in"]) == {"q", "s"} and q["w1"].dtype == jnp.float32


def test_runner_serves_an_lfm2_moe_checkpoint(tmp_path, monkeypatch):
    from localai_tpu.backend import contract_pb2 as pb

    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    sv, res = _load(_write_checkpoint(tmp_path), mesh_tp=1)
    try:
        assert res.success, res.message
        assert sv.engine.family is lm and sv.engine._paged

        class _Ctx:
            def is_active(self):
                return True

            def abort(self, code, msg):
                raise AssertionError(f"abort: {code} {msg}")

        chunks = list(sv.PredictStream(pb.PredictOptions(
            prompt="t5 t9 t40 t7", max_tokens=6, temperature=0.0,
            ignore_eos=True), _Ctx()))
        assert "".join(c.message.decode("utf-8", "replace") for c in chunks)
        assert sv.engine.state_snapshot()["moe"]["decode"]["steps"] >= 5
    finally:
        if getattr(sv, "engine", None) is not None:
            sv.engine.shutdown()


def test_runner_refuses_a_mesh_and_names_the_family_among_the_known(
        tmp_path, monkeypatch):
    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    d = _write_checkpoint(tmp_path)
    _, res = _load(d, mesh_tp=4)
    assert not res.success and "one device" in res.message
    d2 = _write_checkpoint(tmp_path / "x", model_type="lfm3")
    _, res = _load(d2, mesh_tp=1)
    assert not res.success and "lfm2_moe" in res.message


# ---- over HTTP: model manager -> spawned runner -> the same Engine ----

LFM2_YAML = """\
name: lfm2
backend: tpu-llm
parameters:
  model: lfm2-ckpt
  max_tokens: 8
context_size: 128
num_slots: 64
dtype: float32
prefill_buckets: [32]
mesh:
  tp: 1
  dp: 1
template:
  completion: "{{ Input }}"
  chat_message: "{{ Content }}"
  chat: "{{ Input }}"
"""


def test_the_http_executor_holds_as_many_streams_as_the_runners_pool():
    from localai_tpu.api.app import EXECUTOR_WORKERS
    from localai_tpu.backend.runner import RPC_WORKERS_MANY_SLOTS

    assert EXECUTOR_WORKERS == RPC_WORKERS_MANY_SLOTS == 256


@pytest.mark.e2e
def test_eighty_streams_of_a_64_slot_model_all_reach_the_engines_queue(
        tmp_path, monkeypatch):
    """A streamed request holds one worker of the HTTP server's executor
    for its life. With 64 workers the 65th request waited in the EXECUTOR's
    queue, where no scheduler, priority or ``queue_wait`` span could see it,
    and entered the engine only as a slot came free; with the runner's 256
    all 80 are the engine's at once: 64 decode, 16 stand in its queue."""
    import asyncio
    import threading
    import time

    import httpx

    from benchmark import make_checkpoint
    from localai_tpu.api.app import build_app, run_app
    from localai_tpu.capabilities import Capabilities
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import scan_models_dir
    from localai_tpu.modelmgr.loader import ModelLoader
    from localai_tpu.modelmgr.process import free_port

    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    make_checkpoint.make(_toy_config(), 3, str(tmp_path / "lfm2-ckpt"))
    (tmp_path / "lfm2.yaml").write_text(LFM2_YAML)
    port = free_port()
    app_config = AppConfig(models_path=str(tmp_path),
                           address=f"127.0.0.1:{port}")
    loader = ModelLoader(health_attempts=600, health_interval_s=0.2)
    caps = Capabilities(app_config, loader, scan_models_dir(str(tmp_path)))
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

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)
    base = f"http://127.0.0.1:{port}"
    body = {"model": "lfm2", "max_tokens": 40, "ignore_eos": True,
            "temperature": 0.0, "stream": True,
            "messages": [{"role": "user", "content": "t5 t9 t40 t7"}]}
    done, errors, seen = [], [], []

    def one(i):
        try:
            with httpx.stream("POST", f"{base}/v1/chat/completions",
                              json={**body, "messages": [{
                                  "role": "user", "content": f"t{5 + i} t9"}]},
                              timeout=600.0) as r:
                assert r.status_code == 200, r.read()
                done.append(sum(1 for ln in r.iter_lines()
                                if ln.startswith("data: {")))
        except Exception as e:          # shown by the assertion below
            errors.append(repr(e))

    try:
        # the first request loads the model and compiles its programs
        r = httpx.post(f"{base}/v1/chat/completions",
                       json={**body, "stream": False, "max_tokens": 4},
                       timeout=600.0)
        assert r.status_code == 200, r.text
        threads = [threading.Thread(target=one, args=(i,)) for i in range(80)]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            st = httpx.get(f"{base}/debug/state", timeout=60.0).json()
            m = st["models"].get("lfm2")
            if m:
                seen.append((m["slots_active"], m["queued"]))
            time.sleep(0.05)
        for t in threads:
            t.join()
        model = httpx.get(f"{base}/debug/state",
                          timeout=60.0).json()["models"]["lfm2"]
        waits = model["trace"]["by_span_ms"]
    finally:
        loop.call_soon_threadsafe(loop.stop)
        loader.stop_all()
    assert not errors and len(done) == 80, errors[:3]
    assert all(n >= 1 for n in done)        # (tokens coalesce into events)
    assert model["family"] == "lfm2_moe"
    # every slot busy AND requests standing in the ENGINE's queue behind them
    assert max(a for a, _ in seen) == 64
    assert max(q for a, q in seen if a == 64) >= 8, sorted(set(seen))[-5:]
    assert "queue_wait" in waits
    pairs = np.asarray(model["moe"]["decode"]["pairs"])
    assert pairs.shape == (8, 8) and (pairs.sum(1) == pairs.sum(1)[0]).all()
