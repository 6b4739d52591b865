"""Fused speculative tick (ISSUE 13): per-slot spec masking, n-gram
prompt-lookup self-drafting, and the paged draft KV riding the existing
page lifecycle.

Five layers of coverage:

* `ngram_propose` units — match / most-recent-match / no-match /
  short-history / history-end clipping / ring-rotation invariance,
  each with the `has` it returns beside the proposals;
* the routing of a round (ISSUE 34) — a row without a draft takes the
  plain step, the verify pass runs only where a draft exists, mixed
  rows run both, and the two `cond`s stay in the round loop;
* fused mixed tick — a greedy (speculating) and a sampled (plain) slot
  decode through ONE chained dispatch per tick, byte-identical to the
  spec-off engine, with the dispatch-count assertion
  (`mixed_dispatches > 0`) pinning that there is no whole-engine
  spec/burst alternation left to starve greedy neighbors;
* spec x preemption — a speculating low slot is paused by a high
  arrival and its resumed continuation is bit-for-bit what a fresh
  SPEC-OFF engine computes for the identical token history (the resume
  contract AND greedy losslessness in one byte gate);
* paged draft cache x host tier — offloaded pages carry the draft
  planes, a corrupt draft plane decays losslessly to a target-only
  entry, and a restored conversation stays byte-identical while it
  keeps speculating.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.engine.kv_offload import HostPageStore
from localai_tpu.engine.speculative import ngram_propose
from localai_tpu.models import llama
from localai_tpu.ops import kvcache
from localai_tpu.services.eventlog import EVENTS

from .conftest import ByteTokenizer


# ---------- n-gram drafter units ----------


def _props(rows, tokens, ring_pos=None, n_draft=4, ngram=3):
    """(proposals [S, D], has [S]) as lists."""
    ring = jnp.asarray(np.asarray(rows, np.int32))
    S = ring.shape[0]
    rp = (jnp.zeros((S,), jnp.int32) if ring_pos is None
          else jnp.asarray(np.asarray(ring_pos, np.int32)))
    props, has = ngram_propose(jnp.asarray(np.asarray(tokens, np.int32)),
                               ring, rp, n_draft, ngram)
    assert has.shape == (S,) and has.dtype == jnp.bool_
    return np.asarray(props).tolist(), np.asarray(has).tolist()


def test_ngram_match_proposes_continuation():
    # period-4 repetition: trailing gram [6,7,8] recurs, and the
    # continuation after the most recent match is the next period
    hist = [5, 6, 7, 8] * 4
    assert _props([hist], [8]) == ([[5, 6, 7, 8]], [True])


def test_ngram_most_recent_match_wins():
    # [1,2,3] occurs at chronological starts 0 and 8 with DIFFERENT
    # continuations; prompt-lookup proposes the most recent one's
    hist = [1, 2, 3, 9, 0, 0, 0, 0, 1, 2, 3, 7, 0, 1, 2, 3]
    assert _props([hist], [3]) == ([[7, 0, 1, 2]], [True])


def test_ngram_no_match_has_no_draft():
    # strictly increasing history: the trailing gram never recurs, so
    # the drafter says it has nothing (the proposal row is a filler,
    # the current token repeated) and the engine's round gives the row
    # the plain step
    hist = list(range(16))
    assert _props([hist], [15]) == ([[15, 15, 15, 15]], [False])


def test_ngram_short_history_has_no_draft():
    # -1 ring seeds still inside the trailing gram: no valid match
    hist = [-1] * 14 + [7, 9]
    assert _props([hist], [9]) == ([[9, 9, 9, 9]], [False])


def test_ngram_continuation_clips_at_history_end():
    # match near the end of history: the proposal is clipped at the
    # newest entry instead of reading past it
    hist = [0] * 10 + [1, 2, 3, 1, 2, 3]
    assert _props([hist], [3]) == ([[1, 2, 3, 3]], [True])


def test_ngram_ring_rotation_invariant():
    # the device ring is circular (write at pos % N, then advance);
    # proposals must depend only on the chronological view
    hist = np.asarray([5, 6, 7, 8] * 4, np.int32)
    for p in (3, 7, 15):
        assert _props([np.roll(hist, p)], [8], ring_pos=[p]) == \
            ([[5, 6, 7, 8]], [True])


def test_ngram_batch_rows_independent():
    # one batched call, three regimes — per-slot masking means one
    # row's miss never perturbs its neighbors
    rows = [[5, 6, 7, 8] * 4, list(range(16)), [-1] * 14 + [7, 9]]
    assert _props(rows, [8, 15, 9]) == (
        [[5, 6, 7, 8], [15] * 4, [9] * 4], [True, False, False])


# ---------- fused mixed tick ----------


def _cfg():
    return llama.LlamaConfig(
        vocab_size=258, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_position_embeddings=256,
        dtype=jnp.float32)


def _engine(params, draft_mode="auto", draft=None, **kw):
    e = eng.Engine(
        _cfg(), params, ByteTokenizer(),
        eng.EngineConfig(num_slots=2, max_context=128,
                         prefill_buckets=(16, 32), prefill_chunk=32,
                         cache_dtype=jnp.float32, draft=draft_mode, **kw),
        draft=draft)
    e.start()
    return e


def _collect(out, timeout: float = 60.0) -> list:
    events = []
    while True:
        ev = out.get(timeout=timeout)
        if ev is None:
            return events
        events.append(ev)


def test_fused_mixed_tick_byte_parity_and_single_dispatch():
    """The tentpole acceptance gate: a greedy slot speculating via
    n-gram self-drafting and a spec-INELIGIBLE slot (repeat penalty —
    per-token ring evolution keeps it out of the verify round, ISSUE 18
    widened eligibility to sampled-but-pure requests) decoding plainly
    ride ONE fused dispatch per tick (no `_spec_turn` whole-engine
    alternation — `mixed_dispatches` is the dispatch-count evidence),
    and the greedy stream stays byte-identical to the speculation-off
    engine.  This is also the mixed-traffic starvation regression: the
    greedy neighbor keeps speculating while the plain slot is live."""
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = "the rain in spain falls mainly on the plain on the plain"

    e = _engine(params, draft_mode="0", decode_burst=4)
    try:
        assert e._spec_mode == "off"
        req = eng.GenRequest(prompt_ids=ByteTokenizer().encode(prompt),
                             params=sampling.SamplingParamsHost(temperature=0.0),
                             max_new_tokens=32, ignore_eos=True)
        _, evs = e.generate_text(req)
        ref = eng.event_ids(evs)
        assert e._spec_stats["dispatches"] == 0   # spec tick never ran
    finally:
        e.shutdown()

    # small bursts so the two streams genuinely interleave tick-by-tick
    # (a large decode_burst lets either slot drain in one solo burst)
    e = _engine(params, draft_mode="ngram", decode_burst=4)
    try:
        assert e._spec_mode == "ngram"
        tok = ByteTokenizer()
        out_g = e.submit(eng.GenRequest(
            prompt_ids=tok.encode(prompt),
            params=sampling.SamplingParamsHost(temperature=0.0),
            max_new_tokens=32, ignore_eos=True))
        out_s = e.submit(eng.GenRequest(
            prompt_ids=tok.encode("something else entirely"),
            params=sampling.SamplingParamsHost(temperature=1.0, seed=7,
                                               repeat_penalty=1.1),
            max_new_tokens=32, ignore_eos=True))
        evs_g, evs_s = _collect(out_g), _collect(out_s)
        assert eng.event_ids(evs_g) == ref        # lossless beside plain
        assert len(eng.event_ids(evs_s)) == 32
        st = e._spec_stats
        assert st["dispatches"] > 0 and st["rounds"] > 0
        # THE dispatch-count assertion: at least one fused tick carried
        # a speculating row AND a plain row through the same dispatch
        assert st["mixed_dispatches"] > 0
        # mode attribution: only the greedy slot speculated here
        assert st["by_mode"]["greedy"]["rounds"] == st["rounds"]
        assert st["by_mode"]["sampled"]["rounds"] == 0
        sp = e.metrics()["spec"]
        assert sp["mode"] == "ngram"
        assert sp["rounds"] == st["rounds"]
        # each spec round emits at least its bonus token
        assert sp["accept_per_dispatch"] >= 1.0
        assert 0.0 <= sp["acceptance_rate"] <= 1.0
    finally:
        e.shutdown()


def test_ngram_self_speculation_needs_no_draft_model():
    """draft=auto with NO second model resolves to n-gram mode: every
    llama-family greedy request speculates by default, no draft KV."""
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    e = _engine(params, draft_mode="0")
    try:
        req = eng.GenRequest(prompt_ids=ByteTokenizer().encode("abab abab ab"),
                             params=sampling.SamplingParamsHost(temperature=0.0),
                             max_new_tokens=24, ignore_eos=True)
        _, evs = e.generate_text(req)
        ref = eng.event_ids(evs)
    finally:
        e.shutdown()

    e = _engine(params)          # draft="auto", no draft model
    try:
        assert e._spec_mode == "ngram"
        req = eng.GenRequest(prompt_ids=ByteTokenizer().encode("abab abab ab"),
                             params=sampling.SamplingParamsHost(temperature=0.0),
                             max_new_tokens=24, ignore_eos=True)
        _, evs = e.generate_text(req)
        assert eng.event_ids(evs) == ref
        assert e.dck is None                     # self-drafting: no draft KV
        assert e._spec_stats["rounds"] > 0
    finally:
        e.shutdown()


# ---------- a round does only the work its rows need (ISSUE 34) ----------


def _no_repeated_gram(history, n=3) -> bool:
    grams = [tuple(history[i:i + n]) for i in range(len(history) - n + 1)]
    return len(set(grams)) == len(grams)


def _random_prompt(seed: int, n: int = 20) -> list:
    return [int(x) for x in np.random.default_rng(seed).integers(1, 250, n)]


def _greedy_ids(e, prompt_ids, n, **req_kw):
    _, evs = e.generate_text(eng.GenRequest(
        prompt_ids=list(prompt_ids),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, **{"ignore_eos": True, **req_kw}))
    return eng.event_ids(evs)


@pytest.fixture(scope="module")
def f32_params():
    return llama.init_params(_cfg(), jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def spec_off_ids(f32_params):
    """Greedy ids of the `draft: 0` engine, one request at a time."""
    e = _engine(f32_params, draft_mode="0", decode_burst=4)
    memo = {}

    def ids(prompt_ids, n, **req_kw):
        key = (tuple(prompt_ids), n, tuple(sorted(req_kw)))
        if key not in memo:
            memo[key] = _greedy_ids(e, prompt_ids, n, **req_kw)
        return memo[key]

    yield ids
    e.shutdown()


REPETITIVE = ByteTokenizer().encode("abab abab ab")


def test_round_without_a_draft_is_a_plain_step(f32_params, spec_off_ids):
    """(a) Unshared random prompts whose history never repeats a 3-gram:
    the drafter has nothing in any round, so no verify pass runs, nothing
    is proposed, every round emits one token a row from the decode step a
    plain burst runs, and the ids are the `draft: 0` engine's."""
    prompts = [_random_prompt(1), _random_prompt(2)]
    refs = [spec_off_ids(p, 24) for p in prompts]
    for p, r in zip(prompts, refs):
        assert _no_repeated_gram(p + r), "pick a prompt that does not loop"
    e = _engine(f32_params, draft_mode="ngram", decode_burst=4)
    try:
        outs = [e.submit(eng.GenRequest(
            prompt_ids=p, params=sampling.SamplingParamsHost(temperature=0.0),
            max_new_tokens=24, ignore_eos=True)) for p in prompts]
        got = [eng.event_ids(_collect(o)) for o in outs]
        assert got == refs
        sp = e.state_snapshot()["spec"]
        assert sp["dispatches"] > 0 and sp["rounds"] > 0   # spec ticks ran
        assert sp["rounds_verified"] == 0 and sp["rows_drafted"] == 0
        assert sp["proposed"] == 0 and sp["accepted"] == 0
        assert sp["tokens"] == sp["rounds"]        # one token a row a round
        m = e.metrics()["spec"]
        assert m["rounds_verified"] == 0 and m["acceptance_rate"] == 0.0
        assert m["accept_per_dispatch"] == 1.0
    finally:
        e.shutdown()


def test_speculation_is_alive_where_a_draft_exists(f32_params, spec_off_ids):
    """(b) A prompt that repeats a block: drafts exist, the verify pass
    runs and accepts, a round yields more than one token, and the ids
    are still the `draft: 0` engine's."""
    ref = spec_off_ids(REPETITIVE, 32)
    e = _engine(f32_params, draft_mode="ngram", decode_burst=4)
    try:
        assert _greedy_ids(e, REPETITIVE, 32) == ref
        sp = e.metrics()["spec"]
        assert sp["rows_drafted"] > 0 and sp["rounds_verified"] > 0
        assert sp["accepted"] > 0
        assert sp["proposed"] == sp["rows_drafted"] * e.ecfg.n_draft
        assert sp["tokens"] / sp["rounds"] > 1.0
        assert sp["acceptance_rate"] == sp["accepted"] / sp["proposed"]
    finally:
        e.shutdown()


def test_round_runs_both_passes_for_mixed_rows(f32_params, spec_off_ids):
    """(c) One tick carries a drafted greedy row, an undrafted greedy row
    and a grammared row (never a spec row): the verify pass and the plain
    step both run in one round, and each row's ids are its solo run's."""
    from localai_tpu.functions.grammars import json_schema

    grammar = json_schema.schema_to_grammar(
        {"type": "object", "properties": {"city": {"enum": ["sf", "nyc"]}},
         "required": ["city"]})
    call = ByteTokenizer().encode("call: call: call:")
    unshared = _random_prompt(3)
    refs = [spec_off_ids(REPETITIVE, 32), spec_off_ids(unshared, 32),
            spec_off_ids(call, 32, grammar=grammar, ignore_eos=False)]
    assert _no_repeated_gram(unshared + refs[1])
    e = eng.Engine(
        _cfg(), f32_params, ByteTokenizer(),
        eng.EngineConfig(num_slots=3, max_context=128,
                         prefill_buckets=(16, 32), prefill_chunk=32,
                         cache_dtype=jnp.float32, draft="ngram",
                         decode_burst=4, trace=True))    # ticks of one round
    e.start()
    try:
        greedy = sampling.SamplingParamsHost(temperature=0.0)
        outs = [e.submit(eng.GenRequest(prompt_ids=list(p), params=greedy,
                                        max_new_tokens=32, **kw))
                for p, kw in ((REPETITIVE, {"ignore_eos": True}),
                              (unshared, {"ignore_eos": True}),
                              (call, {"grammar": grammar}))]
        got = [eng.event_ids(_collect(o)) for o in outs]
        assert got == refs
        sp = e.state_snapshot()["spec"]
        assert sp["mixed_dispatches"] > 0
        assert 0 < sp["rows_drafted"] < sp["rounds"]
        spans = [s["args"] for s in e.tracer.spans()
                 if s["name"] == "spec_round"]
        assert sum(a["rounds_verified"] for a in spans) == \
            sp["rounds_verified"]
        # one round in which a spec row verified and another spec row
        # took the plain step beside the grammared one
        assert any(a["rounds"] == 1
                   and 0 < a["rows_drafted"] < a["spec_slots"]
                   for a in spans)
    finally:
        e.shutdown()


def test_spec_tick_keeps_its_two_conditionals_in_the_round_loop(f32_params):
    """(d) The traced `jit_spec_tick`: the round scan's body holds two
    `cond`s, the plain step and the verify pass. Folding either back
    into `where`s makes every round pay both forward passes again."""
    e = _engine(f32_params, draft_mode="ngram", decode_burst=4)
    try:
        S = e.ecfg.num_slots
        c_tok, c_len, c_ring, c_rpos, c_mu = e._host_chain()
        jaxpr = jax.make_jaxpr(
            lambda *a: e._spec_tick_body(*a, n_rounds=2))(
            e.params, c_tok, e.ck, e.cv, c_len, c_ring, c_rpos, e.bias,
            e.rng_keys, sampling.pack_slot_params(e.slot_params),
            e.active_dev, c_mu, e._pack_ov(np.zeros((S,), np.bool_)),
            np.zeros((S,), np.bool_))
    finally:
        e.shutdown()
    scans = [q for q in jaxpr.jaxpr.eqns if q.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 2
    body = scans[0].params["jaxpr"].jaxpr
    conds = [q for q in body.eqns if q.primitive.name == "cond"]
    assert len(conds) == 2

    for c in conds:
        skip, run = sorted(c.params["branches"],
                           key=lambda br: len(br.jaxpr.eqns))
        # a skipped pass makes a few zeros and hands the cache back; a
        # pass that runs is a forward over every layer
        assert len(skip.jaxpr.eqns) < 8
        assert any(q.primitive.name == "scan" for q in run.jaxpr.eqns)
        assert not any(q.primitive.name == "scan" for q in skip.jaxpr.eqns)


# ---------- spec x preemption ----------


def _greedy_req(tok, prompt: str, n: int, priority: str = ""):
    return eng.GenRequest(
        prompt_ids=tok.encode(prompt),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, ignore_eos=True, priority=priority)


def test_spec_slot_preempt_resume_byte_gate(tiny_llama, byte_tokenizer):
    """Spec slots are preemptible since ISSUE 13 (the `_preempt_eligible`
    spec exclusion is gone).  The byte gate: the pre-pause prefix matches
    the unpreempted spec-off run, and the resumed continuation is
    bit-for-bit what a fresh SPEC-OFF engine computes for a prompt of
    (original prompt + tokens emitted before the pause) — so both the
    resume contract and greedy losslessness hold across the pause."""
    cfg, params = tiny_llama
    kw = dict(num_slots=1, max_context=96, prefill_buckets=(16, 64),
              decode_burst=4, kv_prefix_cache=False, kv_offload=False)

    e0 = eng.Engine(cfg, params, byte_tokenizer,
                    eng.EngineConfig(draft="0", **kw))
    e0.start()
    try:
        base = eng.event_ids(list(e0.generate(
            _greedy_req(byte_tokenizer, "spec resume", 64, priority="low"))))
    finally:
        e0.shutdown()

    e = eng.Engine(cfg, params, byte_tokenizer,
                   eng.EngineConfig(draft="ngram", **kw))
    e.start()
    try:
        assert e._spec_mode == "ngram"
        # unpreempted run: lossless vs the spec-off engine
        un = eng.event_ids(list(e.generate(
            _greedy_req(byte_tokenizer, "spec resume", 64, priority="low"))))
        assert un == base
        assert e._spec_stats["rounds"] > 0       # it actually speculated
        # preempt round: low decodes alone, high displaces it
        EVENTS.clear()
        req_low = _greedy_req(byte_tokenizer, "spec resume", 64,
                              priority="low")
        out_low = e.submit(req_low)
        first = out_low.get(timeout=60.0)
        assert first.error is None
        out_high = e.submit(_greedy_req(byte_tokenizer, "urgent", 8,
                                        priority="high"))
        high_evs = _collect(out_high)
        low_evs = [first] + _collect(out_low)
        assert all(ev.error is None for ev in high_evs + low_evs)
        pre = [ev for ev in EVENTS.events()
               if ev["event"] == "preempt" and ev["rid"] == req_low.request_id]
        assert pre, "the high arrival should preempt the speculating slot"
        k = pre[0]["n_decoded"]
        low_ids = eng.event_ids(low_evs)
        assert len(low_ids) == 64 and 0 < k < 64
        assert low_ids[:k] == base[:k]
        stats = e.metrics()["scheduler"]
        assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
    finally:
        e.shutdown()

    # the resumed continuation == fresh SPEC-OFF re-admission of the
    # identical token history
    ref_engine = eng.Engine(cfg, params, byte_tokenizer,
                            eng.EngineConfig(draft="0", **kw))
    ref_engine.start()
    try:
        req = eng.GenRequest(
            prompt_ids=byte_tokenizer.encode("spec resume") + low_ids[:k],
            params=sampling.SamplingParamsHost(temperature=0.0),
            max_new_tokens=64 - k, ignore_eos=True, priority="low")
        ref = eng.event_ids(list(ref_engine.generate(req)))
    finally:
        ref_engine.shutdown()
    assert low_ids[k:] == ref


# ---------- paged draft cache x host tier ----------


def _page(v, shape=(2, 4, 2, 8)):
    return np.full(shape, v, np.float32)


def test_host_store_draft_planes_decay_losslessly():
    """Draft planes are an acceleration, not correctness: a corrupt
    draft payload decays the entry to target-only (speculation re-warms)
    instead of dropping the subtree, and a later duplicate-key put can
    re-attach the missing planes."""
    s = HostPageStore(kvcache.page_scope(4, "unit"), 4, budget_mb=64)
    key = kvcache.page_chain_hash(kvcache.PAGE_HASH_ROOT, [1] * 4, s.scope)
    s.put(key, kvcache.PAGE_HASH_ROOT, 0, _page(1), _page(2),
          dk=_page(3), dv=_page(4))
    e = s.get(key)
    assert e is not None and np.array_equal(e.dk, _page(3))
    b0 = s.bytes_used
    e.dk[...] = 77.0                       # flip bits in the draft plane
    e2 = s.get(key)
    assert e2 is not None                  # entry SURVIVES the draft CRC
    assert e2.dk is None and e2.dv is None
    assert np.array_equal(e2.k, _page(1))  # target rows untouched
    assert s.bytes_used < b0               # accounting followed the decay
    s.put(key, kvcache.PAGE_HASH_ROOT, 0, _page(1), _page(2),
          dk=_page(5), dv=_page(6))
    e3 = s.get(key)
    assert e3 is not None and np.array_equal(e3.dk, _page(5))
    assert s.pages == 1                    # touched, never duplicated


class _Tok:
    eos_token_id = 0

    def decode(self, ids, **kw):
        return "".join(chr(97 + (i % 26)) for i in ids)

    def convert_ids_to_tokens(self, ids):
        return [chr(97 + (i % 26)) for i in ids]


@pytest.fixture(scope="module")
def offload_cfg_params():
    cfg = llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _paged_spec_engine(cfg, params):
    e = eng.Engine(
        cfg, params, _Tok(),
        eng.EngineConfig(num_slots=2, max_context=128,
                         prefill_buckets=(16, 64), prefill_chunk=64,
                         cache_dtype=jnp.float32,
                         kv_layout="paged", kv_page_size=16,
                         kv_pool_pages=8, kv_offload=True,
                         kv_host_pool_mb=64),
        draft=(cfg, params))
    e.start()
    return e


def _run(e, ids, n=8):
    _, evs = e.generate_text(eng.GenRequest(
        prompt_ids=list(ids), max_new_tokens=n, ignore_eos=True,
        params=sampling.SamplingParamsHost(temperature=0.0)))
    return eng.event_ids(evs), evs


def _wait_offloaded(e, n=1, timeout=5.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if e._hstore is not None and e._hstore.pages >= n:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"host store never reached {n} pages: {e._hstore.stats()}")


def test_paged_draft_cache_offload_restore_parity(offload_cfg_params):
    """The paged draft KV rides the main page lifecycle: offloaded
    pages carry the draft planes to the host tier, the restored
    conversation splices them back with the target chain, the greedy
    stream stays byte-identical to the cold run, and the restored slot
    KEEPS speculating (no cold spec_ok=False fallback left)."""
    cfg, params = offload_cfg_params
    rng = np.random.default_rng(10)
    a = [int(x) for x in rng.integers(1, 120, size=48)]
    e = _paged_spec_engine(cfg, params)
    try:
        assert e._spec_mode == "model"
        ref, _ = _run(e, a)
        # greedy admission lazily allocated the PAGED draft cache
        assert e.dck is not None
        rounds0 = e._spec_stats["rounds"]
        assert rounds0 > 0
        # churn: one slot's worth of pool means every admission evicts
        for _ in range(3):
            _run(e, [int(x) for x in rng.integers(1, 120, size=48)])
        _wait_offloaded(e, 3)
        assert not any(t[:48] == a for t in e._cache_tokens), \
            "churn failed to overwrite the conversation's slot"
        st0 = e._hstore.stats()
        assert st0["offloaded_pages"] >= 3
        # the host entries carry the draft planes of the same pages
        with e._hstore._lock:
            assert all(en.dk is not None
                       for en in e._hstore._entries.values())
        rounds1 = e._spec_stats["rounds"]
        got, evs = _run(e, a)
        assert got == ref                        # byte-identical restore
        st = e._hstore.stats()
        assert st["restores"] == st0["restores"] + 1
        assert st["restored_pages"] >= st0["restored_pages"] + 1
        assert evs[-1].timings["reused_prompt_tokens"] >= 16
        # the restored slot resumed SPECULATING on the spliced prefix
        assert e._spec_stats["rounds"] > rounds1
    finally:
        e.shutdown()
