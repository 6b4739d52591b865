"""The olmo_hybrid family (models/olmo_hybrid.py) on the served path, at toy
width on seeded random weights: against the plain float32 reference
(benchmark/reference/olmo_hybrid_f32.py, which imports nothing of the
program), through the engine (slot re-use, preemption and resume), through the
runner, and the engine's family capabilities that replaced its llama switch."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.models import llama, mamba, olmo_hybrid as oh, rwkv
from localai_tpu.ops import kvcache
from localai_tpu.services.eventlog import EVENTS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_config(dtype="float32"):
    with open(os.path.join(ROOT, "benchmark", "rehearsal",
                           "olmo_hybrid.json")) as f:
        conf = json.load(f)
    conf["serving"].update(dtype=dtype, context_size=1024)
    return conf


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """The program in float32 (prefill in 2 chunks of a 600-token prompt
    packed beside shorter ones, then up to 16 decode steps through the paged
    cache and the state) against the reference, and the control that holds
    the recurrent state in bfloat16."""
    from benchmark.reference import check

    return check.check(
        _toy_config(), 11, [[70, 4], [130, 6], [5, 3], [600, 16]],
        ["sound", "state_bf16"], str(tmp_path_factory.mktemp("hybrid")))


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "olmo_hybrid_f32.py")) as f:
        assert "localai_tpu" not in f.read()


@pytest.mark.parametrize("what", ["logits_err", "kv_err", "state_err",
                                  "conv_err"])
def test_program_agrees_with_the_float32_reference(checked, what):
    assert checked["sound"][what] < 2e-5, checked["sound"]


def test_state_in_bfloat16_does_not(checked):
    low = checked["state_bf16"]
    assert low["state_err"] > 1e-3 and low["logits_err"] > 1e-3, low
    assert low["state_err"] > 100 * checked["sound"]["state_err"]


@pytest.mark.parametrize("layers, kinds", [
    (6, None), (4, ["full_attention"] * 4),
    (8, ["linear_attention", "full_attention"] * 4)])
def test_a_depth_that_is_no_whole_period_is_an_error(layers, kinds):
    conf = {**_toy_config(), "num_hidden_layers": layers}
    if kinds:
        conf["layer_types"] = kinds
    with pytest.raises(ValueError, match="whole periods"):
        oh.OlmoHybridConfig.from_hf_config(conf)


def test_a_rotary_base_a_contiguous_cache_or_a_mesh_is_refused(byte_tokenizer):
    conf = _toy_config()
    with pytest.raises(ValueError, match="rotary"):
        oh.OlmoHybridConfig.from_hf_config(
            {**conf, "rope_parameters": {"rope_theta": 1e4}})
    cfg = oh.OlmoHybridConfig.from_hf_config(conf)
    with pytest.raises(ValueError, match="paged"):
        oh.init_cache(cfg, 2, 64)
    from localai_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(meshlib.MeshPlan(dp=1, tp=2),
                             devices=jax.devices()[:2])
    with pytest.raises(AssertionError, match="mesh is not declared"):
        eng.Engine(CFG, None, byte_tokenizer, eng.EngineConfig(),
                   family=oh, mesh=mesh)


def test_per_slot_prefill_is_the_packed_one():
    """``prefill`` ([B, T] batches) and ``ragged_prefill`` leave the same
    logits, rows and state."""
    cfg = oh.OlmoHybridConfig.from_hf_config(_toy_config(), jnp.float32)
    params = oh.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    toks = rng.integers(3, cfg.vocab_size, (2, 40)).astype(np.int32)
    lens = np.array([40, 23], np.int32)
    ptab = jnp.arange(2 * 4, dtype=jnp.int32).reshape(2, 4)

    def fresh():
        return tuple(kvcache.with_page_table(c, ptab)
                     for c in oh.init_cache(cfg, 2, 64, page_size=16))

    lg, ck, _ = oh.prefill(params, cfg, toks, lens, *fresh(),
                           np.array([1, 0], np.int32), np.zeros(2, np.int32))
    flat = np.concatenate([toks[0, :40], toks[1, :23], np.zeros(1, np.int32)])
    pos = np.concatenate([np.arange(40), np.arange(23), [64]]).astype(np.int32)
    seg = np.concatenate([np.zeros(40), np.ones(23), [2]]).astype(np.int32)
    lg2, ck2, _ = oh.ragged_prefill(
        params, cfg, flat, pos, seg, np.array([1, 0], np.int32),
        np.zeros(2, np.int32), np.array([0, 40], np.int32), lens, *fresh())
    np.testing.assert_allclose(lg, lg2, atol=1e-4, rtol=1e-4)
    for leaf in ("delta", "conv", "pages"):
        np.testing.assert_allclose(ck[leaf], ck2[leaf], atol=1e-5, rtol=1e-4)


# ---- through the engine ----

CFG = oh.OlmoHybridConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=4,
    num_heads=4, num_kv_heads=4, linear_heads=4, linear_key_dim=8,
    linear_value_dim=16, max_position_embeddings=256, dtype=jnp.float32)


def _engine(tok, **kw):
    params = oh.init_params(CFG, jax.random.PRNGKey(0))
    ecfg = eng.EngineConfig(**{**dict(
        num_slots=1, max_context=128, prefill_buckets=(16, 64),
        decode_burst=4, cache_dtype=jnp.float32), **kw})
    e = eng.Engine(CFG, params, tok, ecfg, family=oh)
    e.start()
    return e


def _greedy(tok, prompt, n, priority=""):
    return eng.GenRequest(
        prompt_ids=tok.encode(prompt),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, ignore_eos=True, priority=priority)


def _collect(out):
    events = []
    while (ev := out.get(timeout=120.0)) is not None:
        events.append(ev)
    return events


def test_engine_gates_follow_what_the_family_declares(byte_tokenizer):
    assert llama.CAPABILITIES == {"paged", "packed_prefill", "prefix_reuse",
                                  "kv_offload", "speculation", "self_extend",
                                  "multimodal", "mesh"}
    assert mamba.CAPABILITIES == rwkv.CAPABILITIES == {"mesh"}
    assert oh.CAPABILITIES == {"paged", "packed_prefill"}
    e = _engine(byte_tokenizer, num_slots=2)
    try:
        assert e._paged and e._packed and e._pcache is None
        assert e._spec_mode == "off" and not e._per_slot_prefill
        st = e.state_snapshot()
        assert st["family"] == "olmo_hybrid"
        assert st["capabilities"] == ["packed_prefill", "paged"]
        # 3 linear layers x 2 slots x (4 x 8 x 16 f32 + 3 x 128 f32)
        assert st["recurrent_state_bytes"] == 3 * 2 * (512 + 384) * 4
        assert kvcache.state_bytes(e.cv) == 0
    finally:
        e.shutdown()
    with open(eng.__file__) as f:
        assert "_fam_llama" not in f.read()


def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(byte_tokenizer):
    """One slot: the second request runs where the first left its state and
    its rows. Its stream is what an engine that never saw the first gives."""
    e = _engine(byte_tokenizer)
    try:
        first = eng.event_ids(list(e.generate(
            _greedy(byte_tokenizer, "the first tenant of the slot", 12))))
        second = eng.event_ids(list(e.generate(
            _greedy(byte_tokenizer, "another prompt", 12))))
        again = eng.event_ids(list(e.generate(
            _greedy(byte_tokenizer, "the first tenant of the slot", 12))))
    finally:
        e.shutdown()
    fresh = _engine(byte_tokenizer)
    try:
        want = eng.event_ids(list(fresh.generate(
            _greedy(byte_tokenizer, "another prompt", 12))))
    finally:
        fresh.shutdown()
    assert len(second) == 12 and second == want and second != first
    assert again == first


def test_a_long_prompt_in_chunks_streams_what_one_pack_does(byte_tokenizer):
    """A 100-token prompt through packs of 16 (continued segments, each
    from the slot's state) against one pack of 128."""
    prompt = "state carried from pack to pack " * 3
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


def test_a_preempted_and_resumed_request_streams_the_undisturbed_tokens(
        byte_tokenizer):
    e = _engine(byte_tokenizer)
    try:
        base = eng.event_ids(list(e.generate(
            _greedy(byte_tokenizer, "background work", 40, "low"))))
        EVENTS.clear()
        low = _greedy(byte_tokenizer, "background work", 40, "low")
        out_low = e.submit(low)
        first = out_low.get(timeout=120.0)
        out_high = e.submit(_greedy(byte_tokenizer, "urgent", 6, "high"))
        high = _collect(out_high)
        events = [first] + _collect(out_low)
        assert all(ev.error is None for ev in high + events)
        assert [ev for ev in EVENTS.events() if ev["event"] == "preempt"
                and ev["rid"] == low.request_id]
        stats = e.metrics()["scheduler"]
        assert stats["resumes"] >= 1 and stats["resume_reprefills"] >= 1
        assert stats["resume_restore_rows"] == 0      # nothing to resume from
        spans = [s for s in e.tracer.spans() if s["name"] == "resume"]
        assert spans and spans[-1]["args"]["reprefill_rows"] > \
            len(low.prompt_ids) - 40
        adm = [s for s in e.tracer.spans() if s["name"] == "admission"]
        assert adm and all(s["args"]["state_reset"] for s in adm)
    finally:
        e.shutdown()
    assert eng.event_ids(events) == base and len(base) == 40


# ---- through the runner ----

def _write_checkpoint(tmp_path, model_type="olmo_hybrid"):
    from benchmark import make_checkpoint

    conf = _toy_config()
    d = str(tmp_path / "ckpt")
    make_checkpoint.make(conf, 3, d)
    if model_type != "olmo_hybrid":
        with open(os.path.join(d, "config.json")) as f:
            c = json.load(f)
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump({**c, "model_type": model_type}, f)
    return d


def _load(d, **kw):
    from localai_tpu.backend import contract_pb2 as pb
    from localai_tpu.backend.runner import EngineServicer

    sv = EngineServicer()
    res = sv.LoadModel(pb.ModelOptions(
        model=d, context_size=128, num_slots=2, dtype="float32",
        prefill_buckets=[32], **kw), None)
    return sv, res


def test_runner_serves_an_olmo_hybrid_checkpoint(tmp_path, monkeypatch):
    from localai_tpu.backend import contract_pb2 as pb

    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    sv, res = _load(_write_checkpoint(tmp_path), mesh_tp=1)
    try:
        assert res.success, res.message
        assert sv.engine.family is oh and sv.engine._paged
        class _Ctx:
            def is_active(self):
                return True

            def abort(self, code, msg):
                raise AssertionError(f"abort: {code} {msg}")

        chunks = list(sv.PredictStream(pb.PredictOptions(
            prompt="t5 t9 t40 t7", max_tokens=6, temperature=0.0,
            ignore_eos=True), _Ctx()))
        assert "".join(c.message.decode("utf-8", "replace") for c in chunks)
        assert sum(c.tokens for c in chunks if c.tokens) >= 6 or chunks
    finally:
        if getattr(sv, "engine", None) is not None:
            sv.engine.shutdown()


def test_runner_refuses_a_mesh_and_names_the_types_it_knows(tmp_path,
                                                            monkeypatch):
    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    d = _write_checkpoint(tmp_path)
    _, res = _load(d, mesh_tp=4)
    assert not res.success and "one device" in res.message
    d2 = _write_checkpoint(tmp_path / "x", model_type="olmo_hybird")
    _, res = _load(d2, mesh_tp=1)
    assert not res.success
    for known in ("olmo_hybrid", "mamba", "rwkv", "llama", "mistral"):
        assert known in res.message
