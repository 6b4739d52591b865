"""The xing4 family (models/xing4.py: MLA on every layer under
manifold-constrained hyper-connections, a routed expert feed-forward beside a
shared expert, a latent page pool and NOTHING ELSE in a slot) on the served
path, at toy width on seeded random weights: against the plain float32
reference (benchmark/reference/xing4_f32.py, which imports nothing of the
program), YaRN by hand, what is refused by name, the loader with a
multi-token-prediction module's tensors in the file, and prefix reuse over a
pool of ONE plane: at the model's level through a shared page table, through
the engine's tiers, through a prompt-cache file, through the runner."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.models import xing4
from localai_tpu.ops import kvcache, mla
from localai_tpu.ops.rope import yarn_inv_freq, yarn_mscale

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_config(**change):
    """benchmark/rehearsal/xing4.json: 2 dense + 2 expert layers, 8 experts
    (2 a token), 4 residual streams, YaRN over 64 original positions."""
    with open(os.path.join(ROOT, "benchmark", "rehearsal",
                           "xing4.json")) as f:
        conf = json.load(f)
    conf.update(change)
    return conf


# ---- against the reference ----

@pytest.fixture(scope="module")
def both(tmp_path_factory):
    """The program's prefill (a 600-token prompt in two chunks packed beside
    shorter ones: fresh and ``continued`` segments over ten pages of 64, a
    chunk boundary inside a segment), then up to 9 decode steps through the
    latent pool, a slot past its last step idle; and the reference's full
    forward pass, one sequence at a time, following the program's choices."""
    from safetensors import safe_open

    from benchmark import make_checkpoint, spec
    from benchmark.reference import check
    from benchmark.reference import xing4_f32 as ref_model

    conf = _toy_config()
    fam = spec.family_of(conf)
    hf = {k: conf[k] for k in fam.HF_KEYS if k in conf}
    ckpt = str(tmp_path_factory.mktemp("xing4") / "ckpt")
    make_checkpoint.make(conf, 11, ckpt)
    seqs = check.sequences([[70, 4], [130, 6], [5, 3], [600, 9]], 11,
                           conf["vocab_size"])
    prog = fam._run_program(ckpt, hf, "float32", {}, seqs, 1024)
    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        read = ref_model.weight_reader(h.get_tensor, "bfloat16")
        spec_ = [(p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
                 for p, d in seqs]
        ref = ref_model.forward(read, hf, 4, spec_, choices=prog[2])
    return fam, hf, prog, ref


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "xing4_f32.py")) as f:
        assert "localai_tpu" not in f.read()


@pytest.mark.parametrize("what", ["logits", "latent", "hc"])
def test_program_agrees_with_the_float32_reference(both, what):
    """Logits, not tokens. Both sides are float32 on the same weights: what
    is left is the order of the sums (the absorbed decode against
    materialised heads, the online softmax over blocks of committed rows,
    the grouped expert products): under 5e-5 of the norm."""
    from benchmark.reference.check import rel_err

    _, _, (logits, groups, _), ref = both
    got, want = {
        "logits": (logits, [r["logits"] for r in ref]),
        "latent": (groups["latent"], [r["latent"] for r in ref]),
        "hc": (groups["hc"], [r["hc"][0] for r in ref]),
    }[what]
    assert [np.shape(a) for a in got] == [np.shape(a) for a in want]
    flat = [np.concatenate([np.asarray(x).ravel() for x in side])
            for side in (got, want)]
    assert rel_err(*flat) < 5e-5


def test_in_float32_the_programs_choices_are_the_references_own(both):
    fam, hf, (_, _, chosen), ref = both
    for c, r in zip(chosen, ref):
        assert c.shape == r["chosen"].shape == (c.shape[0], 2, 2)
        below, above = fam.route_shortfall(c, r["biased"], 2)
        assert below.max() < 1e-5 and above.max() < 1e-5
        same = (np.sort(c, -1) == np.sort(r["chosen"], -1)).all(-1)
        assert same.mean() > 0.995


def test_one_stream_is_the_plain_residual_in_program_and_reference(tmp_path):
    """``hc_mult`` 1: no hyper-connection tensors, ``x += F(norm(x))``."""
    from safetensors import safe_open

    from benchmark import make_checkpoint, spec
    from benchmark.reference import check
    from benchmark.reference import xing4_f32 as ref_model
    from benchmark.reference.check import rel_err

    conf = _toy_config(hc_mult=1, num_hidden_layers=3)
    fam = spec.family_of(conf)
    hf = {k: conf[k] for k in fam.HF_KEYS if k in conf}
    ckpt = str(tmp_path / "ckpt")
    make_checkpoint.make(conf, 5, ckpt)
    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        assert not any("hc" in k for k in h.keys())
        seqs = check.sequences([[40, 3]], 5, conf["vocab_size"])
        logits, groups, chosen = fam._run_program(ckpt, hf, "float32", {},
                                                  seqs, 64)
        ref = ref_model.forward(
            ref_model.weight_reader(h.get_tensor, "bfloat16"), hf, 3,
            [(p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
             for p, d in seqs], choices=chosen)
    assert groups["hc"] == [] and ref[0]["hc"] == []
    assert rel_err(logits[0], ref[0]["logits"]) < 5e-5


# ---- YaRN, by hand ----

def test_yarn_frequencies_and_score_scale_are_the_hand_worked_values():
    """64 rotary columns, theta 1e4, factor 64 over 4096 original positions:
    the ramp runs from dimension floor(64 ln(4096 / (32 2 pi)) / (2 ln 1e4))
    = 10 to ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23."""
    inv = yarn_inv_freq(64, 1e4, 64.0, 4096)
    base = 1e4 ** -(np.arange(32) / 32)
    assert inv.shape == (32,)
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e4))) == 23
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-12)   # kept
    np.testing.assert_allclose(inv[23:], base[23:] / 64, rtol=1e-12)
    # halfway up the ramp, dimension 16 + 0.5 -> (16 - 10) / 13 of the way
    r = (16 - 10) / 13
    assert inv[16] == pytest.approx(base[16] / 64 * r + base[16] * (1 - r))
    assert yarn_mscale(64.0, 1.0) == pytest.approx(0.1 * math.log(64) + 1)
    assert yarn_mscale(64.0, 1.0) == pytest.approx(1.4158883)
    assert yarn_mscale(1.0, 1.0) == 1.0
    cfg = xing4.Xing4Config(rope_scaling_factor=64.0, rope_mscale=1.0,
                            rope_mscale_all_dim=1.0)
    assert cfg.score_scale == pytest.approx(192 ** -0.5 * 1.4158883 ** 2, rel=1e-6)
    assert cfg.rope_magnitude == 1.0
    np.testing.assert_array_equal(cfg.rope_inv_freq, inv)
    # the control's config: the temperature off, the frequencies as they were
    off = dataclasses.replace(cfg, rope_mscale_all_dim=0.0)
    assert off.score_scale == pytest.approx(192 ** -0.5)
    np.testing.assert_array_equal(off.rope_inv_freq, inv)
    plain = xing4.Xing4Config()
    np.testing.assert_allclose(plain.rope_inv_freq, base)
    assert plain.score_scale == pytest.approx(192 ** -0.5)
    # ops/mla.py's terms: scaled frequencies in, the default as it was
    pos = jnp.asarray([0, 5, 4097])
    sin, cos = mla.rope_terms(pos, 64, 1e4, inv_freq=inv)
    np.testing.assert_allclose(sin[:, 31], np.sin(np.asarray(pos) * inv[31]),
                               atol=1e-6)
    np.testing.assert_allclose(cos[:, 32:], cos[:, :32])
    s0, c0 = mla.rope_terms(pos, 64, 1e4)
    np.testing.assert_allclose(s0[:, 31], np.sin(np.asarray(pos) * base[31]),
                               atol=1e-5)
    # the reference's own arithmetic agrees
    from benchmark.reference import xing4_f32 as ref_model

    hf = _toy_config(qk_rope_head_dim=64, qk_nope_head_dim=128)
    hf["rope_scaling"]["original_max_position_embeddings"] = 4096
    np.testing.assert_allclose(ref_model.rope_inv_freq(hf), inv, rtol=1e-12)
    assert ref_model.score_scale(hf) == pytest.approx(cfg.score_scale)


# ---- what is refused ----

@pytest.mark.parametrize("change, what", [
    ({"rope_scaling": {"type": "llama3", "factor": 8}}, "rope_scaling.type"),
    ({"n_group": 8, "topk_group": 4}, "group-limited routing"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"n_shared_experts": 2}, "shared experts"),
    ({"q_lora_rank": None}, "full-rank query"),
    ({"attention_bias": True}, "attention_bias"),
    ({"ep_size": 4}, "a share of the experts"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
])
def test_what_is_not_built_is_refused_by_name(change, what):
    with pytest.raises(ValueError, match=what):
        xing4.Xing4Config.from_hf_config(_toy_config(**change))


def test_what_is_built_is_read_off_the_published_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4.0-29b-a4b-l6.json")) as f:
        cfg = xing4.Xing4Config.from_hf_config(json.load(f))
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.moe_layers) == (6, 2, 4)
    assert cfg.layer_kinds == ("dense",) * 2 + ("moe",) * 4
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_clamp) == \
        (4, 20, (-30.0, 30.0))
    assert cfg.latent_width == 640 and cfg.q_head_dim == 192
    assert cfg.rope_scaling_factor == 64.0 and cfg.num_experts == 64
    assert cfg.score_scale == pytest.approx(192 ** -0.5 * 1.4158883 ** 2, rel=1e-6)
    assert xing4.hc_width(cfg) == 24
    # a model with one stream and no YaRN is allowed: the plain residual
    one = xing4.Xing4Config.from_hf_config(
        _toy_config(hc_mult=1, rope_scaling=None))
    assert one.hc_mult == 1 and one.rope_scaling_factor == 1.0
    assert "hc_w" not in xing4.init_params(
        dataclasses.replace(one, vocab_size=64),
        jax.random.PRNGKey(0))["layers"]


def test_a_contiguous_cache_or_an_int8_one_is_refused():
    cfg = xing4.Xing4Config.from_hf_config(_toy_config(), dtype=jnp.float32)
    with pytest.raises(ValueError, match="paged KV layout only"):
        xing4.init_cache(cfg, 2, 64)
    with pytest.raises(ValueError, match="int8 latent cache"):
        xing4.init_cache(cfg, 2, 64, jnp.int8, page_size=16)
    ck, cv = xing4.init_cache(cfg, 2, 64, page_size=16)
    assert set(ck) == set(cv) == {"pages", "ptab"}     # no state leaf
    assert ck["pages"].shape == (4, 8, 16, 1, 128)
    assert cv["pages"].shape == (0, 8, 16, 1, 128)
    assert xing4.latent_cache_bytes(ck) == 4 * 8 * 16 * 128 * 4
    assert kvcache.state_bytes(ck) == 0


# ---- a shared page table, at the model's level ----

CFG = dataclasses.replace(
    xing4.Xing4Config.from_hf_config(_toy_config(), dtype=jnp.float32),
    vocab_size=256)
PG = 16


def _pack(cfg, tokens, slot, start, C, N=64, S=3):
    """One segment of ``tokens`` for ``slot`` from position ``start``."""
    n = len(tokens)
    tok = np.zeros((N,), np.int32)
    pos = np.full((N,), C, np.int32)
    seg = np.full((N,), S, np.int32)
    tok[:n], pos[:n], seg[:n] = tokens, np.arange(start, start + n), 0
    slots = np.full((S,), S, np.int32)
    slots[0] = slot
    z = np.zeros((S,), np.int32)
    st, ln = z.copy(), z.copy()
    st[0], ln[0] = start, n
    return tuple(map(jnp.asarray, (tok, pos, seg, slots, st, z, ln)))


def test_a_slot_resumes_from_anothers_latent_pages_and_copies_what_it_writes():
    """Slot 0 holds a document of 40 rows (2.5 pages of 16). Slot 1 is given
    slot 0's two full pages by reference and a CLONE of the third (the first
    divergent page: both planes go through ops/kvcache.py::clone_page, the
    empty one as it comes), then prefills its own question from row 40:
    the logits of a fresh prefill of document + question in slot 2, and
    slot 0's rows as they were."""
    C, S = 64, 3
    params = xing4.init_params(CFG, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    doc = rng.integers(3, 256, 40).tolist()
    ask = rng.integers(3, 256, 9).tolist()
    ck, cv = xing4.init_cache(CFG, S, C, page_size=PG)
    n_pages = ck["pages"].shape[1]
    tab = np.full((S, C // PG), n_pages, np.int32)
    tab[0], tab[2] = [0, 1, 2, 3], [8, 9, 10, 11]
    ck, cv = (kvcache.with_page_table(c, jnp.asarray(tab)) for c in (ck, cv))
    run = jax.jit(lambda *a, c: xing4.ragged_prefill_routed(
        params, CFG, *a, continued=c), static_argnames="c")
    _, ck, cv, _ = run(*_pack(CFG, doc, 0, 0, C), ck, cv, c=False)
    donor = np.asarray(ck["pages"][:, :3])
    # the share: two pages by reference, the divergent third cloned to 5
    ck, cv = (kvcache.clone_page(c, 2, 5) for c in (ck, cv))
    assert cv["pages"].shape[0] == 0
    tab[1] = [0, 1, 5, 6]
    ck, cv = (kvcache.with_page_table(c, jnp.asarray(tab)) for c in (ck, cv))
    shared, ck, cv, _ = run(*_pack(CFG, ask, 1, 40, C), ck, cv, c=True)
    fresh, ck, cv, _ = run(*_pack(CFG, doc + ask, 2, 0, C), ck, cv, c=False)
    np.testing.assert_allclose(shared[0], fresh[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(ck["pages"][:, :3]), donor)
    # the clone took the question's rows, the donor's third page did not
    assert np.abs(np.asarray(ck["pages"][:, 5, 8:])).sum() > 0
    assert np.abs(np.asarray(ck["pages"][:, 2, 8:])).sum() == 0
    # and a decode step through the shared pages is the fresh slot's
    tok = jnp.asarray([0, 7, 7], jnp.int32)
    lens = jnp.asarray([C, 49, 49], jnp.int32)
    act = jnp.asarray([False, True, True])
    lg, ck, cv, _ = jax.jit(lambda *a: xing4.decode_step(params, CFG, *a))(
        tok, lens, act, ck, cv)
    np.testing.assert_allclose(lg[1], lg[2], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(ck["pages"][:, :3]), donor)


# ---- through the engine ----

def _engine(tok, **kw):
    params = xing4.init_params(CFG, jax.random.PRNGKey(0))
    ecfg = eng.EngineConfig(**{**dict(
        num_slots=2, max_context=128, prefill_buckets=(16, 64),
        decode_burst=4, cache_dtype=jnp.float32, kv_page_size=PG), **kw})
    e = eng.Engine(CFG, params, tok, ecfg, family=xing4)
    e.start()
    return e


def _greedy(tok, prompt, n, **kw):
    return eng.GenRequest(
        prompt_ids=tok.encode(prompt),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, ignore_eos=True, **kw)


def _collect(out):
    events = []
    while (ev := out.get(timeout=120.0)) is not None:
        events.append(ev)
    return events


DOC = "a contract of several clauses, asked about by more than one reader; "
ASKS = ["who signs?", "when does it end, and on what notice?"]


def _alone(tok, prompt, n):
    e = _engine(tok, kv_prefix_cache=False)
    try:
        return eng.event_ids(list(e.generate(_greedy(tok, prompt, n))))
    finally:
        e.shutdown()


def test_engine_declares_prefix_reuse_and_no_host_tier(byte_tokenizer):
    assert xing4.CAPABILITIES == {"paged", "packed_prefill", "prefix_reuse",
                                  "route_stats"}
    e = _engine(byte_tokenizer)
    try:
        assert e._paged and e._packed and e._pcache is not None
        # kv_offload is the engine's default, and this family declares no
        # host tier: none is built, no per-slot prefill program is warmed
        assert e.ecfg.kv_offload and e._hstore is None
        assert not e._per_slot_prefill
        assert kvcache.shape(e.cv)[0] == 0 and kvcache.shape(e.ck)[0] == 4
        ids = eng.event_ids(list(e.generate(
            _greedy(byte_tokenizer, DOC + ASKS[0], 9))))
        st = e.state_snapshot()
        spans = [s for s in e.tracer.spans()
                 if s["name"] == "decode_burst_device"]
    finally:
        e.shutdown()
    assert len(ids) == 9 and st["family"] == "xing4"
    assert st["recurrent_state_bytes"] == 0
    assert st["latent_cache_bytes"] == 4 * 2 * 128 * 128 * 4
    assert st["moe"]["experts"] == 8
    assert np.asarray(st["moe"]["decode"]["pairs"]).shape == (2, 8)
    assert all("ctx_rows" in s["args"] and s["args"]["slot_ids"] == [0]
               for s in spans)
    assert spans[-1]["args"]["ctx_rows"] >= len(DOC)
    with pytest.raises(ValueError, match="snap-back window"):
        eng.Engine(CFG, e.params, byte_tokenizer, eng.EngineConfig(
            num_slots=2, max_context=128, kv_page_size=PG,
            kv_window_pages=2, kv_window_policy="drop"), family=xing4)


def test_an_ask_admitted_from_a_live_slots_pages_streams_what_it_streams_alone(
        byte_tokenizer):
    """Tier 2 of ``_paged_admission``: the second ask arrives while the
    first still decodes, takes the document's full pages by reference and a
    clone of the first divergent page (``_share_prefix``), and prefills
    its question alone. Both stream what each streams with no other
    request about: the donor's rows were left as they were."""
    tok = byte_tokenizer
    want = [_alone(tok, DOC + a, 12) for a in ASKS]
    e = _engine(tok)
    try:
        first = e.submit(_greedy(tok, DOC + ASKS[0], 12))
        head = first.get(timeout=120.0)             # it decodes now
        second = e.submit(_greedy(tok, DOC + ASKS[1], 12))
        got = [eng.event_ids([head] + _collect(first)),
               eng.event_ids(_collect(second))]
        reused = e._reused_total
        adm = [s["args"]["reused_rows"] for s in e.tracer.spans()
               if s["name"] == "admission"]
    finally:
        e.shutdown()
    assert got == want
    # the whole document but what the engine's floor leaves: mid-page, so
    # the share cloned a page
    assert adm[0] == 0 and adm[1] >= len(DOC) - 1 and adm[1] % PG
    assert reused == adm[1]


def test_an_ask_after_the_release_is_spliced_from_the_prefix_cache(
        byte_tokenizer):
    """Tier 3: one slot; the first ask ends, another tenant takes the slot,
    and the document's full pages are still found by their hashes
    (engine/prefix_cache.py over a pool with room to retain them); the
    boundary page is copy-on-write guarded."""
    tok = byte_tokenizer
    want = _alone(tok, DOC + ASKS[1], 10)
    e = _engine(tok, num_slots=1, kv_pool_pages=24)
    try:
        for p in (DOC + ASKS[0], "an unrelated tenant in between, long "
                  "enough to take the slot's own pages over"):
            list(e.generate(_greedy(tok, p, 6)))
        got = eng.event_ids(list(e.generate(_greedy(tok, DOC + ASKS[1], 10))))
        hits = e._pcache.stats()
        adm = [s["args"]["reused_rows"] for s in e.tracer.spans()
               if s["name"] == "admission"]
    finally:
        e.shutdown()
    assert got == want
    assert adm[-1] == len(DOC) // PG * PG and hits["hits"] >= 1


def test_a_prompt_cache_file_holds_one_plane_and_restores_it(
        byte_tokenizer, tmp_path):
    """The file's ``v`` has no layers, as the cache it came from; a second
    engine restores the rows and streams what the first did."""
    tok = byte_tokenizer
    path = str(tmp_path / "doc.npz")
    e = _engine(tok)
    try:
        first = eng.event_ids(list(e.generate(_greedy(
            tok, DOC + ASKS[0], 8, prompt_cache_path=path))))
        for _ in range(200):
            if os.path.exists(path):
                break
            __import__("time").sleep(0.05)
    finally:
        e.shutdown()
    data = np.load(path)
    assert data["k"].shape[0] == 4 and data["v"].shape[0] == 0
    assert data["k"].shape[1] == len(data["tokens"]) == len(DOC + ASKS[0])
    e = _engine(tok)
    try:
        again = eng.event_ids(list(e.generate(_greedy(
            tok, DOC + ASKS[0], 8, prompt_cache_path=path,
            prompt_cache_ro=True))))
        adm = [s["args"]["reused_rows"] for s in e.tracer.spans()
               if s["name"] == "admission"]
    finally:
        e.shutdown()
    assert adm == [len(DOC + ASKS[0]) - 1]
    # the file holds float16 rows: the restored latent differs in its last
    # bits, the greedy stream on these weights does not
    assert again == first


def test_a_long_prompt_in_chunks_streams_what_one_pack_does(byte_tokenizer):
    """Latent rows carried from pack to pack: the continued form over the
    pool, several pages, a chunk boundary inside a page."""
    prompt = DOC + "and its annex, " * 2
    outs = []
    for chunk in (24, 128):
        e = _engine(byte_tokenizer, prefill_chunk=chunk,
                    prefill_buckets=(chunk,))
        try:
            outs.append(eng.event_ids(list(e.generate(
                _greedy(byte_tokenizer, prompt, 10)))))
        finally:
            e.shutdown()
    assert len(outs[0]) == 10 and outs[0] == outs[1]


# ---- through the loader and the runner ----

MTP = ("enorm.weight", "hnorm.weight", "eh_proj.weight",
       "self_attn.o_proj.weight")


def _write_checkpoint(tmp_path, mtp=True, **change):
    """The maker's checkpoint and, as a published one has, tensors of the
    multi-token prediction module after the last layer."""
    from safetensors import safe_open
    from safetensors.numpy import save_file

    from benchmark import make_checkpoint

    d = str(tmp_path / "ckpt")
    conf = _toy_config()
    make_checkpoint.make(conf, 3, d)
    if mtp:
        f = os.path.join(d, "model.safetensors")
        with safe_open(f, "np") as h:
            t = {k: h.get_tensor(k) for k in h.keys()}
        L = conf["num_hidden_layers"]
        for name in MTP:
            t[f"model.layers.{L}.{name}"] = np.ones((8, 8), np.float16)
        save_file(t, f)
    if change:
        with open(os.path.join(d, "config.json")) as f:
            c = json.load(f)
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump({**c, **change}, f)
    return d


def _load(d, num_slots=4, **kw):
    from localai_tpu.backend import contract_pb2 as pb
    from localai_tpu.backend.runner import EngineServicer

    sv = EngineServicer()
    res = sv.LoadModel(pb.ModelOptions(
        model=d, context_size=128, num_slots=num_slots, dtype="float32",
        prefill_buckets=[32], **kw), None)
    return sv, res


def test_the_loader_reads_the_maker_checkpoint_and_skips_the_mtp_module(
        tmp_path, caplog):
    from safetensors import safe_open

    from localai_tpu.engine import weights

    d = _write_checkpoint(tmp_path)
    with open(os.path.join(d, "config.json")) as f:
        hf = json.load(f)
    assert hf["model_type"] == "xing4_0" and "family" not in hf
    assert hf["num_nextn_predict_layers"] == 1
    cfg = xing4.Xing4Config.from_hf_config(hf, dtype=jnp.float32)
    with caplog.at_level("INFO"):
        params = xing4.load_hf_params(d, cfg, dtype=jnp.float32)
    assert "skipped 4 tensors of the multi-token prediction" in caplog.text
    lay = params["layers"]
    want = jax.tree.map(lambda a: (a.shape, a.dtype), xing4.init_params(
        cfg, jax.random.PRNGKey(0), jnp.float32))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == want
    with safe_open(os.path.join(d, "model.safetensors"), "np") as h:
        names = list(h.keys())
        assert sorted(weights.xing4_mtp_tensors(names, cfg)) == sorted(
            f"model.layers.4.{n}" for n in MTP)
        p = "model.layers.{}."
        for leaf, i, name in (
                ("mla_qa", 1, "self_attn.q_a_proj.weight"),
                ("mla_qb", 3, "self_attn.q_b_proj.weight"),
                ("mla_kvb", 0, "self_attn.kv_b_proj.weight"),
                ("mla_o", 2, "self_attn.o_proj.weight")):
            np.testing.assert_array_equal(
                lay[leaf][i], h.get_tensor(p.format(i) + name).T)
        for j, sub in enumerate(("attn_hc", "mlp_hc")):
            np.testing.assert_array_equal(
                lay["hc_w"][2, j], h.get_tensor(p.format(2) + sub + ".weight").T)
            np.testing.assert_array_equal(
                lay["hc_b"][2, j], h.get_tensor(p.format(2) + sub + ".bias"))
        np.testing.assert_array_equal(
            lay["w2"][1, 5],
            h.get_tensor(p.format(3) + "mlp.experts.5.down_proj.weight").T)
        np.testing.assert_array_equal(
            lay["expert_bias"][0],
            h.get_tensor(p.format(2) + "mlp.gate.e_score_correction_bias"))
        np.testing.assert_array_equal(
            params["hc_head_w"], h.get_tensor("model.hc_head.weight").T)
    assert lay["hc_s"].dtype == lay["router"].dtype == jnp.float32
    assert lay["hc_w"].shape == (4, 2, 4 * 128, 24)
    with pytest.raises(ValueError, match="int4"):
        xing4.load_hf_params(d, cfg, quantize="int4")
    q = xing4.load_hf_params(d, cfg, dtype=jnp.float32,
                             quantize="int8")["layers"]
    assert set(q["mla_qb"]) == {"q", "s"} and q["w1"].dtype == jnp.float32
    assert q["hc_w"].dtype == jnp.float32


class _Ctx:
    def is_active(self):
        return True

    def abort(self, code, msg):
        raise AssertionError(f"abort: {code} {msg}")


def test_runner_serves_a_xing4_checkpoint_and_reuses_a_prefix(
        tmp_path, monkeypatch):
    from localai_tpu.backend import contract_pb2 as pb

    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    sv, res = _load(_write_checkpoint(tmp_path), mesh_tp=1,
                    options="kv_page_size=16")
    try:
        assert res.success, res.message
        assert sv.engine.family is xing4 and sv.engine._paged
        assert sv.engine._pcache is not None and sv.engine._hstore is None
        doc = " ".join(f"t{3 + i % 400}" for i in range(50))
        for ask in ("t5 t9 t40 t7", "t8 t8 t11"):
            chunks = list(sv.PredictStream(pb.PredictOptions(
                prompt=doc + " " + ask, max_tokens=6, temperature=0.0,
                ignore_eos=True), _Ctx()))
            assert "".join(c.message.decode("utf-8", "replace")
                           for c in chunks)
        st = sv.engine.state_snapshot()
        assert st["moe"]["decode"]["steps"] >= 10
        assert st["latent_cache_bytes"] > 0
        assert "prefix_reuse" in st["capabilities"]
        assert sv.engine._reused_total >= 48
    finally:
        if getattr(sv, "engine", None) is not None:
            sv.engine.shutdown()


@pytest.mark.parametrize("kw, what", [
    ({"options": "kv_offload=1"}, "kv_offload: a host tier"),
    ({"options": "kv_host_pool_mb=64"}, "kv_host_pool_mb: a host tier"),
    ({"options": "kv_window_pages=4"}, "kv_window_pages: a host tier"),
    ({"mesh_tp": 4}, "one device"),
    ({"mmproj": "tower"}, "vision tower"),
    ({"draft_model": "small"}, "draft models are llama-family only"),
])
def test_runner_refuses_by_name_what_the_family_does_not_declare(
        tmp_path, monkeypatch, kw, what):
    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    d = _write_checkpoint(tmp_path, mtp=False)
    _, res = _load(d, **{"mesh_tp": 1, **kw})
    assert not res.success and what in res.message, res.message


def test_runner_takes_kv_offload_off_and_refuses_a_config_it_cannot_compute(
        tmp_path, monkeypatch):
    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    d = _write_checkpoint(tmp_path, mtp=False)
    sv, res = _load(d, mesh_tp=1, options="kv_offload=0")
    assert res.success, res.message
    sv.engine.shutdown()
    d2 = _write_checkpoint(tmp_path / "x", mtp=False, n_group=4)
    _, res = _load(d2, mesh_tp=1)
    assert not res.success and "group-limited routing" in res.message
    d3 = _write_checkpoint(tmp_path / "y", mtp=False, model_type="xing5_0")
    _, res = _load(d3, mesh_tp=1)
    assert not res.success and "xing4_0" in res.message
