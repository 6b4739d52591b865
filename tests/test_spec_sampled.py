"""Stochastic speculative sampling (ISSUE 18): rejection-sampling
acceptance so temperature>0 slots ride the fused spec tick.

Coverage layers:

* `accept_sampled` units — hand-checked acceptance probabilities
  (explicit draft distribution AND the deterministic one-hot
  degeneration), residual renormalization with the draft token zeroed,
  all-reject => exactly one fresh sample, full-accept => drafts + bonus,
  inactive-slot key/emission neutrality;
* `verify_dist` — per-position distribution identity with the plain
  sampler's filter_window (the distribution-preservation mechanism);
* engine-level distribution preservation — chi-square goodness-of-fit
  of spec-sampled vs plain-sampled token frequencies over a fixed seed
  ladder (two deterministic runs; the acceptance contract is
  distribution-identity, not byte-identity);
* the PR-10 re-admission contract for a preempted SAMPLED spec slot —
  the resumed continuation is bit-for-bit a fresh re-admission of
  (prompt + emitted) with the same seed on an identical spec-on engine;
* eligibility exclusions that must hold by TEST, not comment: grammar-
  constrained slots and lockstep engines never enter spec rounds.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling, speculative
from localai_tpu.models import llama
from localai_tpu.services.eventlog import EVENTS

from .conftest import ByteTokenizer


# ---------- accept_sampled units ----------


def _keys(S, base=0):
    return jnp.stack([
        jax.random.key_data(jax.random.PRNGKey(base + i)) for i in range(S)])


def _dist(rows, V):
    """[len(rows)] probability rows -> [W, V] array."""
    out = np.zeros((len(rows), V), np.float32)
    for j, row in enumerate(rows):
        for tok, p in row.items():
            out[j, tok] = p
    return out


def test_accept_sampled_full_accept_emits_drafts_plus_bonus():
    V, D = 8, 3
    drafts = jnp.asarray([[3, 5, 2]], jnp.int32)
    tp = jnp.asarray(_dist([{3: 1.0}, {5: 1.0}, {2: 1.0}, {7: 1.0}], V))[None]
    out, n_out, k, new_keys = speculative.accept_sampled(
        drafts, tp, None, _keys(1), jnp.asarray([True]))
    assert int(k[0]) == 3 and int(n_out[0]) == 4
    assert np.asarray(out[0]).tolist() == [3, 5, 2, 7]
    assert not np.array_equal(np.asarray(new_keys), np.asarray(_keys(1)))


def test_accept_sampled_all_reject_exactly_one_fresh_sample():
    # p(draft) == 0 at position 0: u < 0 never accepts, and the residual
    # (p with the draft token zeroed) IS p — the single emitted token
    # comes from the target's position-0 law
    V, D = 8, 3
    drafts = jnp.asarray([[3, 3, 3]], jnp.int32)
    tp = jnp.asarray(_dist(
        [{6: 1.0}, {1: 1.0}, {1: 1.0}, {1: 1.0}], V))[None]
    out, n_out, k, _ = speculative.accept_sampled(
        drafts, tp, None, _keys(1), jnp.asarray([True]))
    assert int(k[0]) == 0 and int(n_out[0]) == 1
    assert int(out[0, 0]) == 6


def test_accept_sampled_acceptance_probability_and_residual():
    # p0 = {a:.5, b:.3, c:.2}, draft = a (one-hot q): acceptance is
    # exactly u < 0.5; rejected slots resample from the residual
    # norm(p0 with a zeroed) = {b:.6, c:.4} — never a
    V, S = 8, 4000
    a, b, c = 3, 4, 5
    drafts = jnp.full((S, 1), a, jnp.int32)
    tp = jnp.broadcast_to(jnp.asarray(
        _dist([{a: 0.5, b: 0.3, c: 0.2}, {1: 1.0}], V))[None], (S, 2, V))
    out, n_out, k, _ = speculative.accept_sampled(
        drafts, tp, None, _keys(S), jnp.ones((S,), bool))
    k = np.asarray(k)
    first = np.asarray(out[:, 0])
    acc_rate = float((k == 1).mean())
    assert abs(acc_rate - 0.5) < 0.04          # +-5 sigma at S=4000
    rej = first[k == 0]
    assert rej.size > 0 and not np.any(rej == a)
    frac_b = float((rej == b).mean())
    assert abs(frac_b - 0.6) < 0.06
    assert np.array_equal(np.asarray(n_out), k + 1)


def test_accept_sampled_explicit_draft_probs_ratio():
    # non-one-hot q: p = {x:.2, y:.5, z:.3}, q = {x:.4, y:.6}, draft = x
    # => accept with min(1, .2/.4) = 0.5; the residual clip(p - q, 0)
    # has mass ONLY on z — rejection always emits z (hand-checked)
    V, S = 8, 4000
    x, y, z = 2, 3, 4
    drafts = jnp.full((S, 1), x, jnp.int32)
    tp = jnp.broadcast_to(jnp.asarray(
        _dist([{x: 0.2, y: 0.5, z: 0.3}, {1: 1.0}], V))[None], (S, 2, V))
    qp = jnp.broadcast_to(jnp.asarray(
        _dist([{x: 0.4, y: 0.6}], V))[None], (S, 1, V))
    out, _n, k, _ = speculative.accept_sampled(
        drafts, tp, qp, _keys(S, base=100), jnp.ones((S,), bool))
    k = np.asarray(k)
    first = np.asarray(out[:, 0])
    assert abs(float((k == 1).mean()) - 0.5) < 0.04
    assert np.all(first[k == 0] == z)


def test_accept_sampled_one_hot_degeneration_bit_exact():
    # draft_probs=None must equal an explicit one-hot q bit-for-bit:
    # same keys => same uniforms => same acceptances and resamples
    V, S, D = 16, 64, 3
    rng = np.random.default_rng(0)
    drafts = jnp.asarray(rng.integers(0, V, size=(S, D)), jnp.int32)
    raw = rng.random((S, D + 1, V)).astype(np.float32)
    tp = jnp.asarray(raw / raw.sum(-1, keepdims=True))
    onehot = jnp.asarray(
        np.eye(V, dtype=np.float32)[np.asarray(drafts)])        # [S, D, V]
    act = jnp.ones((S,), bool)
    o1, n1, k1, nk1 = speculative.accept_sampled(
        drafts, tp, None, _keys(S), act)
    o2, n2, k2, nk2 = speculative.accept_sampled(
        drafts, tp, onehot, _keys(S), act)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert np.array_equal(np.asarray(n1), np.asarray(n2))
    assert np.array_equal(np.asarray(k1), np.asarray(k2))
    assert np.array_equal(np.asarray(nk1), np.asarray(nk2))


def test_accept_sampled_inactive_slot_untouched():
    V = 8
    drafts = jnp.asarray([[3], [3]], jnp.int32)
    tp = jnp.broadcast_to(jnp.asarray(
        _dist([{3: 1.0}, {5: 1.0}], V))[None], (2, 2, V))
    keys = _keys(2)
    out, n_out, _k, new_keys = speculative.accept_sampled(
        drafts, tp, None, keys, jnp.asarray([True, False]))
    assert int(n_out[0]) == 2 and int(n_out[1]) == 0
    assert np.array_equal(np.asarray(new_keys[1]), np.asarray(keys[1]))
    assert not np.array_equal(np.asarray(new_keys[0]), np.asarray(keys[0]))


# ---------- verify_dist: the distribution-identity mechanism ----------


def test_verify_dist_matches_plain_filter_window():
    """Each verify position's (idx, probs) must equal what filter_window
    produces for that position's logits under the slot's params — the
    same code path plain `sample` draws its categorical from."""
    S, W, V = 2, 3, 64
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(S, W, V)).astype(np.float32))
    sp = sampling.make_slot_params(S)
    sp["temperature"][:] = [0.7, 1.3]
    sp["top_k"][:] = [8, 0]
    sp["top_p"][:] = [0.9, 0.95]
    sp["greedy"][:] = False
    spj = {k: jnp.asarray(v) for k, v in sp.items()}
    vidx, vprobs = sampling.verify_dist(logits, spj, use_typical=False)
    zb = jnp.zeros((1, 1), jnp.float32)
    for s in range(S):
        row = {k: jnp.asarray(v[s:s + 1]) for k, v in sp.items()}
        for w in range(W):
            idx, masked, _ = sampling.filter_window(
                logits[s, w][None], row, None, None, zb, mu=None,
                use_penalties=False, use_typical=False, use_mirostat=False)
            probs = jax.nn.softmax(masked, axis=-1)
            assert np.array_equal(np.asarray(vidx[s, w]), np.asarray(idx[0]))
            np.testing.assert_allclose(np.asarray(vprobs[s, w]),
                                       np.asarray(probs[0]), rtol=1e-6)
    # rank-0 of the window is the greedy argmax (byte-stability anchor)
    assert np.array_equal(np.asarray(vidx[:, :, 0]),
                          np.asarray(jnp.argmax(logits, axis=-1)))


def test_two_sample_chi2_helper():
    rng = np.random.default_rng(3)
    p = np.asarray([0.5, 0.3, 0.15, 0.05])
    a = np.bincount(rng.choice(4, size=2000, p=p), minlength=4)
    b = np.bincount(rng.choice(4, size=2000, p=p), minlength=4)
    _stat, dof, pv = speculative.two_sample_chi2(a, b)
    assert dof >= 1 and pv > 0.01             # same law: not rejected
    c = np.bincount(rng.choice(4, size=2000, p=p[::-1]), minlength=4)
    _stat, _dof, pv_bad = speculative.two_sample_chi2(a, c)
    assert pv_bad < 1e-6                      # different law: rejected


# ---------- engine-level: sampled slots ride the spec tick ----------


def _cfg():
    return llama.LlamaConfig(
        vocab_size=258, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_position_embeddings=256,
        dtype=jnp.float32)


def _engine(params, draft_mode="ngram", **kw):
    e = eng.Engine(
        _cfg(), params, ByteTokenizer(),
        eng.EngineConfig(num_slots=2, max_context=128,
                         prefill_buckets=(16, 32, 64), prefill_chunk=64,
                         cache_dtype=jnp.float32, draft=draft_mode, **kw))
    e.start()
    return e


def _sampled_req(prompt: str, seed: int, n: int = 40, **pkw):
    return eng.GenRequest(
        prompt_ids=ByteTokenizer().encode(prompt),
        params=sampling.SamplingParamsHost(temperature=0.8, seed=seed, **pkw),
        max_new_tokens=n, ignore_eos=True)


PROMPT = "the cat sat on the mat. the cat sat on the mat. the cat sat"


def test_sampled_slot_joins_spec_and_splits_mode_counters():
    params = llama.init_params(_cfg(), jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    e = _engine(params, decode_burst=8)
    try:
        assert e._spec_mode == "ngram"
        _, evs = e.generate_text(_sampled_req(PROMPT, seed=5))
        assert len(eng.event_ids(evs)) == 40
        st = e._spec_stats
        bm = st["by_mode"]["sampled"]
        assert st["dispatches"] > 0
        assert bm["rounds"] > 0                  # it actually speculated
        assert bm["tokens"] >= bm["rounds"]      # >= 1 token per round
        # ISSUE 34: only a round with a draft proposes; the others took
        # the plain sampler and count as rounds alone
        assert 0 <= bm["rows_drafted"] <= bm["rounds"]
        assert bm["proposed"] == bm["rows_drafted"] * e.ecfg.n_draft
        assert bm == {k: st[k] for k in bm}      # the one mode is the total
        assert st["by_mode"]["greedy"]["rounds"] == 0
        sp = e.metrics()["spec"]
        assert sp["by_mode"]["sampled"]["rounds"] == bm["rounds"]
        assert sp["by_mode"]["sampled"]["accept_per_dispatch"] >= 1.0
        assert 0.0 <= sp["by_mode"]["sampled"]["acceptance_rate"] <= 1.0
        snap = e.state_snapshot()
        assert snap["spec"]["by_mode"]["sampled"]["rounds"] == bm["rounds"]
    finally:
        e.shutdown()


def test_sampled_row_without_a_draft_draws_from_the_plain_sampler():
    """ISSUE 34: a sampled spec row whose history offers no continuation
    takes the plain decode step and the plain sampler, one categorical a
    token on the slot's own key: while no round drafts, the stream is
    the `draft: 0` engine's byte for byte, not only in distribution."""
    params = llama.init_params(_cfg(), jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    prompt = "".join(chr(33 + (7 * i) % 90) for i in range(24))

    def run(draft_mode):
        e = _engine(params, draft_mode=draft_mode, decode_burst=4)
        try:
            _, evs = e.generate_text(_sampled_req(prompt, seed=3, n=24))
            return eng.event_ids(evs), e.metrics()["spec"]
        finally:
            e.shutdown()

    on, sp = run("ngram")
    off, _ = run("0")
    bm = sp["by_mode"]["sampled"]
    assert bm["rounds"] > 0 and sp["dispatches"] > 0
    assert bm["rows_drafted"] == 0 and bm["proposed"] == 0, \
        "pick a seed whose sampled stream repeats no 3-gram"
    assert sp["rounds_verified"] == 0
    assert bm["tokens"] == bm["rounds"]
    assert on == off


@pytest.mark.parametrize("prompt,top_k,drafted_share", [
    (PROMPT, 16, 0.0),          # a stream that rarely repeats: plain rounds
    ("abab abab ab", 2, 0.1),   # a stream that loops: drafts, acceptances
], ids=["rarely_drafted", "often_drafted"])
def test_spec_sampled_chi_square_distribution_parity(prompt, top_k,
                                                     drafted_share):
    """THE distribution-preservation contract: over a fixed seed ladder,
    spec-on sampled token frequencies are chi-square-indistinguishable
    from plain (spec-off) sampling. Both runs are fully deterministic
    (fixed seeds), so this does not flake — it fails only if the
    acceptance/residual math biases the law. Since ISSUE 34 a round
    verifies only where a draft exists, so the second case is the one
    that exercises rejection sampling: its stream loops, a tenth of the
    rounds or more draft, and drafts are accepted."""
    params = llama.init_params(_cfg(), jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    seeds = range(10)
    V = _cfg().vocab_size

    def run(draft_mode):
        e = _engine(params, draft_mode=draft_mode, decode_burst=8)
        counts = np.zeros((V,), np.int64)
        try:
            for s in seeds:
                _, evs = e.generate_text(
                    _sampled_req(prompt, seed=s, top_k=top_k))
                ids = eng.event_ids(evs)
                assert len(ids) == 40
                counts += np.bincount(ids, minlength=V)[:V]
            return counts, dict(e._spec_stats["by_mode"]["sampled"])
        finally:
            e.shutdown()

    on, bm = run("ngram")
    off, _ = run("0")
    assert bm["rounds"] > 0                      # spec path actually ran
    assert bm["rows_drafted"] >= drafted_share * bm["rounds"]
    assert bm["proposed"] == bm["rows_drafted"] * 4
    if drafted_share:
        assert bm["accepted"] > 0
    assert int(on.sum()) == int(off.sum()) == 10 * 40
    stat, dof, p = speculative.two_sample_chi2(on, off)
    assert dof >= 1
    assert p > 0.01, f"distribution drift: chi2={stat:.2f} dof={dof} p={p:.4f}"


def test_sampled_spec_preempt_resume_readmission_contract(
        tiny_llama, byte_tokenizer):
    """PR-10 resume contract for a SAMPLED spec slot: the resumed
    continuation is bit-for-bit what a fresh re-admission of
    (prompt + emitted-before-pause) computes on an identical spec-on
    engine with the same seed — the RNG key re-seeds from params.seed at
    (re-)admission and the per-round spec RNG schedule is deterministic,
    so resume-as-readmission stays exact even though sampled spec is
    only distribution-identical to spec-OFF decoding."""
    cfg, params = tiny_llama
    kw = dict(num_slots=1, max_context=96, prefill_buckets=(16, 64),
              decode_burst=4, kv_prefix_cache=False, kv_offload=False,
              cache_dtype=jnp.float32)

    def req(prompt_ids, n, priority="", seed=11):
        return eng.GenRequest(
            prompt_ids=list(prompt_ids),
            params=sampling.SamplingParamsHost(temperature=0.8, seed=seed),
            max_new_tokens=n, ignore_eos=True, priority=priority)

    prompt = byte_tokenizer.encode("resume me resume me resume me")
    e = eng.Engine(cfg, params, byte_tokenizer,
                   eng.EngineConfig(draft="ngram", **kw))
    e.start()
    try:
        assert e._spec_mode == "ngram"
        EVENTS.clear()
        req_low = req(prompt, 48, priority="low")
        out_low = e.submit(req_low)
        first = out_low.get(timeout=60.0)
        assert first.error is None
        out_high = e.submit(eng.GenRequest(
            prompt_ids=byte_tokenizer.encode("urgent"),
            params=sampling.SamplingParamsHost(temperature=0.0),
            max_new_tokens=8, ignore_eos=True, priority="high"))
        high_evs = []
        while True:
            ev = out_high.get(timeout=60.0)
            if ev is None:
                break
            high_evs.append(ev)
        low_evs = [first]
        while True:
            ev = out_low.get(timeout=60.0)
            if ev is None:
                break
            low_evs.append(ev)
        assert all(ev.error is None for ev in high_evs + low_evs)
        pre = [ev for ev in EVENTS.events()
               if ev["event"] == "preempt" and ev["rid"] == req_low.request_id]
        assert pre, "the high arrival should preempt the sampled spec slot"
        k = pre[0]["n_decoded"]
        low_ids = eng.event_ids(low_evs)
        assert len(low_ids) == 48 and 0 < k < 48
        assert e._spec_stats["by_mode"]["sampled"]["rounds"] > 0
        stats = e.metrics()["scheduler"]
        assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
    finally:
        e.shutdown()

    # fresh spec-ON engine, re-admission of the identical token history
    ref_engine = eng.Engine(cfg, params, byte_tokenizer,
                            eng.EngineConfig(draft="ngram", **kw))
    ref_engine.start()
    try:
        ref = eng.event_ids(list(ref_engine.generate(
            req(prompt + low_ids[:k], 48 - k, priority="low"))))
    finally:
        ref_engine.shutdown()
    assert low_ids[k:] == ref


# ---------- exclusions that must hold by test ----------


def test_grammar_constrained_slot_never_enters_spec_rounds():
    from localai_tpu.functions.grammars import json_schema

    params = llama.init_params(_cfg(), jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    grammar = json_schema.schema_to_grammar(
        {"type": "object", "properties": {"city": {"enum": ["sf", "nyc"]}},
         "required": ["city"]})
    e = _engine(params, decode_burst=8)
    try:
        assert e._spec_mode == "ngram"
        req = eng.GenRequest(
            prompt_ids=ByteTokenizer().encode("call: call: call:"),
            params=sampling.SamplingParamsHost(temperature=0.8, seed=5),
            max_new_tokens=32, grammar=grammar)
        _, evs = e.generate_text(req)
        assert eng.event_ids(evs)
        # the grammared slot was the ONLY traffic: no spec tick may run
        assert e._spec_stats["dispatches"] == 0
        assert e._spec_stats["rounds"] == 0
    finally:
        e.shutdown()


def test_lockstep_engine_resolves_spec_off():
    import types

    params = llama.init_params(_cfg(), jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    bus = types.SimpleNamespace(send=lambda *a, **k: None,
                                close=lambda: None)
    e = eng.Engine(_cfg(), params, ByteTokenizer(),
                   eng.EngineConfig(num_slots=2, max_context=128,
                                    prefill_buckets=(16, 32, 64),
                                    cache_dtype=jnp.float32, draft="ngram"),
                   bus=bus)
    e.start()
    try:
        # lockstep dispatches are not in the follower descriptor set:
        # the mode resolver forces spec OFF even with draft requested
        assert e._spec_mode == "off"
        _, evs = e.generate_text(_sampled_req(PROMPT, seed=5, n=16))
        assert len(eng.event_ids(evs)) == 16
        assert e._spec_stats["dispatches"] == 0
    finally:
        e.shutdown()
