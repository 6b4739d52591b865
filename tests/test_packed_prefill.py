"""Ragged packed prefill (ISSUE 4): token-budget cross-slot prompt
batching in one dispatch.

Pins:
  * the jnp packed attention (ops/ragged_prefill.py) == the per-segment
    references (causal_attention for fresh, mixed_prefill_attention for
    continued), including int8 cache rows;
  * the Pallas kernel (ops/pallas/ragged_prefill.py, interpret mode) ==
    the jnp fallback over a paged pool;
  * the int8 {q, scales} paged DECODE kernel variant (ROADMAP PR-1
    follow-up) == the jnp gather fallback, interpret mode;
  * exact greedy byte-parity through the REAL engine between
    prefill_packed=1 and prefill_packed=0 for a concurrent mixed wave
    (fresh finals, longer-than-chunk prompts, COW prefix share and
    prefix-cache splice landing mid-pack, context-shift re-prefill) —
    f32 weights (bf16 rounding ties flip argmax between equal-value
    candidates across differently shaped programs; see BENCH notes);
  * prefill_packed=0 never touches the ragged path;
  * the token budget bounds every pack; packing telemetry in metrics();
  * the same parity on the 8-device dryrun mesh (slow);
  * knob validation + /metrics exposition for the TTFT decomposition.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.models import llama
from localai_tpu.ops import kvcache


@pytest.fixture(scope="module")
def tiny_cfg_params():
    cfg = llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=256, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------- op-level parity ----------

def _paged_layer(shape, dtype, pgs, rng):
    """A fully-allocated single-layer paged cache with random rows."""
    S, C = shape[1], shape[2]
    pc = kvcache.init_paged(shape, dtype, pgs)
    ptab = np.asarray(pc["ptab"]).copy()
    for s in range(S):
        ptab[s] = np.arange(s * (C // pgs), (s + 1) * (C // pgs))
    pc = kvcache.with_page_table(pc, jnp.asarray(ptab))
    rows = jnp.asarray(rng.normal(size=shape[1:]).astype(np.float32))
    slot = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None], (S, C))
    col = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None, :], (S, C))
    return kvcache.layer(kvcache.scatter_prefill(pc, 0, slot, col, rows), 0)


def _pack_meta(C, N, B, segs):
    """segs: [(seg_id, slot, start, off, length)] -> packed index arrays
    with the pad-sentinel conventions of the engine packer."""
    seg_of = np.full((N,), B, np.int32)
    seg_slots = np.full((B,), B, np.int32)
    seg_start = np.zeros((B,), np.int32)
    seg_off = np.zeros((B,), np.int32)
    seg_len = np.zeros((B,), np.int32)
    for b, slot, start, off, length in segs:
        seg_of[off:off + length] = b
        seg_slots[b], seg_start[b] = slot, start
        seg_off[b], seg_len[b] = off, length
    return (jnp.asarray(seg_of), jnp.asarray(seg_slots),
            jnp.asarray(seg_start), jnp.asarray(seg_off),
            jnp.asarray(seg_len))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_ragged_attention_matches_per_segment_reference(dtype):
    """Packed = per-segment: a continued segment matches
    mixed_prefill_attention, a fresh one matches causal_attention —
    plain and int8 cache rows."""
    from localai_tpu.ops.attention import (causal_attention,
                                           mixed_prefill_attention)
    from localai_tpu.ops.ragged_prefill import ragged_prefill_attention

    rng = np.random.default_rng(0)
    S, C, KV, G, hd, pgs, N = 4, 32, 2, 2, 16, 8, 16
    lc = _paged_layer((1, S, C, KV, hd), dtype, pgs, rng)
    seg_of, seg_slots, seg_start, seg_off, seg_len = _pack_meta(
        C, N, S, [(0, 0, 10, 0, 5), (1, 2, 0, 5, 7)])
    q = jnp.asarray(rng.normal(size=(N, KV * G, hd)).astype(np.float32))
    ck = jnp.asarray(rng.normal(size=(N, KV, hd)).astype(np.float32))
    cv = jnp.asarray(rng.normal(size=(N, KV, hd)).astype(np.float32))
    out = ragged_prefill_attention(q, ck, cv, seg_of, seg_slots, seg_start,
                                   lc, lc, G, continued=True)
    k_rows = kvcache.gather_layer_rows(lc, jnp.asarray([0]))
    ref0 = mixed_prefill_attention(q[0:5][None], ck[0:5][None], cv[0:5][None],
                                   k_rows, k_rows, jnp.asarray([10]),
                                   jnp.asarray([5]), G)[0]
    ref1 = causal_attention(q[5:12][None], ck[5:12][None], cv[5:12][None],
                            jnp.ones((1, 7), bool), G)[0]
    np.testing.assert_allclose(np.asarray(out[0:5]), np.asarray(ref0),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(out[5:12]), np.asarray(ref1),
                               atol=3e-5)
    # fresh variant: identical for start == 0 segments
    out_f = ragged_prefill_attention(q, ck, cv, seg_of, seg_slots,
                                     jnp.zeros((S,), jnp.int32), lc, lc, G,
                                     continued=False)
    np.testing.assert_allclose(np.asarray(out_f[5:12]), np.asarray(ref1),
                               atol=3e-5)


def test_ragged_prefill_pallas_matches_jnp():
    """The packed-prefill TPU kernel (interpret mode) == the jnp
    fallback, including empty pad segments and mid-page prefixes."""
    from localai_tpu.ops.pallas.ragged_prefill import (
        ragged_prefill_attention_pallas)
    from localai_tpu.ops.ragged_prefill import ragged_prefill_attention

    rng = np.random.default_rng(1)
    S, C, KV, G, hd, pgs, N = 4, 32, 2, 3, 16, 8, 24
    lc = _paged_layer((1, S, C, KV, hd), jnp.float32, pgs, rng)
    segs = [(0, 1, 20, 0, 6), (1, 3, 0, 6, 10), (2, 0, 7, 16, 4)]
    seg_of, seg_slots, seg_start, seg_off, seg_len = _pack_meta(
        C, N, S, segs)
    q = jnp.asarray(rng.normal(size=(N, KV * G, hd)).astype(np.float32))
    ck = jnp.asarray(rng.normal(size=(N, KV, hd)).astype(np.float32))
    cv = jnp.asarray(rng.normal(size=(N, KV, hd)).astype(np.float32))
    ref = ragged_prefill_attention(q, ck, cv, seg_of, seg_slots, seg_start,
                                   lc, lc, G, continued=True)
    out = ragged_prefill_attention_pallas(
        q, ck, cv, lc["pages"], lc["pages"], lc["ptab"], seg_slots,
        seg_start, seg_off, seg_len, q_per_kv=G, pkb=8, interpret=True)
    real = np.asarray(seg_of) < S
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(ref)[real],
                               atol=2e-4)


def test_paged_pallas_int8_decode_matches_jnp():
    """The {q, scales} paged decode kernel variant (interpret mode) ==
    decode_attention_append over the dense-gathered int8 rows."""
    from localai_tpu.ops.attention import decode_attention_append
    from localai_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_append_quant)

    rng = np.random.default_rng(2)
    S, C, KV, G, hd, pgs = 4, 32, 2, 2, 16, 8
    lq = _paged_layer((1, S, C, KV, hd), jnp.int8, pgs, rng)
    q = jnp.asarray(rng.normal(size=(S, KV * G, hd)).astype(np.float32))
    nk = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    nv = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    lengths = jnp.asarray([20, 5, 32, 0], jnp.int32)
    out = paged_decode_attention_append_quant(
        q, nk, nv, lq["pages"], lq["scales"], lq["pages"], lq["scales"],
        lq["ptab"], lengths, q_per_kv=G, interpret=True)
    ref = decode_attention_append(q, nk, nv, kvcache.gather_all_rows(lq),
                                  kvcache.gather_all_rows(lq), lengths, G)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-4, rtol=3e-4)


# ---------- engine e2e ----------

class _Tok:
    eos_token_id = 0

    def decode(self, ids, **kw):
        return "".join(chr(97 + (i % 26)) for i in ids)

    def convert_ids_to_tokens(self, ids):
        return [chr(97 + (i % 26)) for i in ids]


def _engine(cfg, params, packed, mesh=None, slots=4, ctx=128, draft=None,
            **kw):
    e = eng.Engine(
        cfg, params, _Tok(),
        eng.EngineConfig(num_slots=slots, max_context=ctx,
                         prefill_buckets=(16, 64), prefill_chunk=32,
                         cache_dtype=jnp.float32, kv_layout="paged",
                         kv_page_size=16, prefill_packed=packed, **kw),
        mesh=mesh, draft=draft)
    e.start()
    return e


def _run_wave(e, prompts, n=8):
    """Submit concurrently (the packed path needs co-pending prompts),
    drain in submit order — greedy, so outputs are order-independent."""
    outs = [e.submit(eng.GenRequest(
        prompt_ids=list(p), max_new_tokens=n, ignore_eos=True,
        params=sampling.SamplingParamsHost(temperature=0.0)))
        for p in prompts]
    res = []
    for o in outs:
        ids = []
        while True:
            ev = o.get()
            if ev is None:
                break
            assert not ev.error, ev.error
            ids.extend(ev.token_ids or
                       ([ev.token_id] if ev.token_id >= 0 else []))
        res.append(ids)
    return res


def _mixed_prompts(rng):
    """Fresh shorts, a longer-than-chunk prompt (multi-tick chunked
    ingestion) and a shared-prefix pair (COW share lands mid-pack)."""
    prompts = [rng.integers(1, 120, size=n).tolist()
               for n in (40, 12, 70, 9, 25, 33)]
    prompts.append(prompts[0][:30] + rng.integers(1, 120, size=6).tolist())
    return prompts


@pytest.fixture(scope="module")
def engine_pair(tiny_cfg_params):
    """ONE (sequential, packed) engine pair shared by the parity tests —
    engine construction + lazy jit compiles dominate this file's
    runtime, and parity only needs both engines to see IDENTICAL
    traffic histories (greedy outputs are invariant to which reuse
    tier admission lands on: reused rows are byte-equal)."""
    cfg, params = tiny_cfg_params
    e_seq = _engine(cfg, params, packed=False)
    e_pack = _engine(cfg, params, packed=True)
    yield e_seq, e_pack
    e_seq.shutdown()
    e_pack.shutdown()


def test_packed_vs_sequential_greedy_parity(engine_pair):
    """Byte-exact greedy parity through the REAL engine for a concurrent
    mixed wave, prefill_packed=1 vs 0 — and the packed path actually
    ran (telemetry)."""
    e0, e1 = engine_pair
    prompts = _mixed_prompts(np.random.default_rng(3))
    ref = _run_wave(e0, prompts)
    assert e0.metrics()["packed_prefill"]["dispatches"] == 0
    assert e0.metrics()["prefill_packed"] is False
    got = _run_wave(e1, prompts)
    m = e1.metrics()
    assert m["prefill_packed"] is True
    assert m["packed_prefill"]["dispatches"] > 0
    assert m["packed_prefill"]["segments"] > len(prompts) - 1
    assert m["packed_prefill"]["tokens"] >= sum(
        len(p) for p in prompts) - 30  # minus the COW-shared prefix
    assert got == ref


def test_packed_prefix_cache_splice_mid_pack(engine_pair):
    """Cross-release prefix-cache splice landing mid-pack: turn 2 of a
    conversation (its history's slot long since churned away) packs
    together with fresh prompts; parity vs the sequential path, and
    the splice actually fired."""
    e0, e1 = engine_pair
    rng = np.random.default_rng(4)
    hist = rng.integers(1, 120, size=40).tolist()
    turn2 = hist + rng.integers(1, 120, size=10).tolist()
    churn = [rng.integers(1, 120, size=20).tolist() for _ in range(4)]
    fresh = [rng.integers(1, 120, size=n).tolist() for n in (14, 22)]
    results = []
    for e in (e0, e1):
        first = _run_wave(e, [hist])          # occupy + release a slot
        _run_wave(e, churn)                   # churn every slot
        hits0 = e.metrics()["prefix_cache"]["hits"]
        wave = _run_wave(e, [turn2] + fresh)  # splice rides the pack
        results.append((first, wave))
        if e is e1:
            assert e.metrics()["prefix_cache"]["hits"] > hits0
    assert results[0] == results[1]


def test_packed_context_shift_reprefill(engine_pair):
    """Context-shift re-prefill (tail-half recompute) goes through the
    packed path byte-identically."""
    e0, e1 = engine_pair
    prompt = np.random.default_rng(5).integers(1, 120, size=20).tolist()
    ref = _run_wave(e0, [prompt], n=120)
    got = _run_wave(e1, [prompt], n=120)
    assert got == ref and len(ref[0]) == 120


def test_prefill_packed_off_restores_legacy(engine_pair, monkeypatch):
    """prefill_packed=0 must never reach the ragged forward."""
    e0, _ = engine_pair

    def boom(*a, **kw):  # pragma: no cover - the assertion is "not called"
        raise AssertionError("ragged_prefill called with prefill_packed=0")

    monkeypatch.setattr(llama, "ragged_prefill", boom)
    out = _run_wave(e0, _mixed_prompts(np.random.default_rng(9)))
    assert all(len(x) == 8 for x in out)


def test_packed_token_budget_bounds_every_pack(tiny_cfg_params,
                                               engine_pair):
    """prefill_token_budget caps each pack's bucket (observed at the
    compiled-variant boundary) and parity holds at a tiny budget."""
    cfg, params = tiny_cfg_params
    e0, _ = engine_pair
    prompts = _mixed_prompts(np.random.default_rng(3))
    ref = _run_wave(e0, prompts)
    buckets = []
    orig = eng.Engine._get_packed_fn

    def spy(self, bucket, continued):
        buckets.append(bucket)
        return orig(self, bucket, continued)

    eng.Engine._get_packed_fn = spy
    try:
        e1 = _engine(cfg, params, packed=True, prefill_token_budget=16)
        try:
            got = _run_wave(e1, prompts)
            m = e1.metrics()
        finally:
            e1.shutdown()
    finally:
        eng.Engine._get_packed_fn = orig
    assert got == ref
    assert m["prefill_token_budget"] == 16
    assert buckets and max(buckets) <= 16
    assert m["packed_prefill"]["dispatches"] >= \
        m["packed_prefill"]["tokens"] // 16


@pytest.mark.slow
def test_packed_mesh_parity(tiny_cfg_params):
    """Packed-vs-sequential parity on the 8-device dryrun mesh
    (dp=2, tp=4): the ragged batch replicates (ragged_pack_spec) while
    heads shard on tp."""
    from localai_tpu.parallel import mesh as meshlib
    from localai_tpu.parallel.sharding import shard_params

    cfg, params = tiny_cfg_params
    mesh = meshlib.make_mesh(meshlib.MeshPlan(dp=2, tp=4),
                             devices=jax.devices()[:8])
    prompts = [p[:24] for p in _mixed_prompts(np.random.default_rng(6))][:4]
    sharded = shard_params(mesh, params, cfg.tie_word_embeddings)
    e0 = _engine(cfg, sharded, packed=False, mesh=mesh, slots=4)
    try:
        ref = _run_wave(e0, prompts, n=6)
    finally:
        e0.shutdown()
    sharded = shard_params(mesh, params, cfg.tie_word_embeddings)
    e1 = _engine(cfg, sharded, packed=True, mesh=mesh, slots=4)
    try:
        got = _run_wave(e1, prompts, n=6)
        assert e1.metrics()["packed_prefill"]["dispatches"] > 0
    finally:
        e1.shutdown()
    assert got == ref


# ---------- ISSUE 11: segment-blocked kernel + early-emit + overlap ----------

def test_ragged_kernel_plan_long_packs():
    """Long packs STAY on the kernel path at 8B head shapes (KV=8, G=4,
    hd=128): the plan's scratch is per-q-block, so pack length never
    disqualifies — only pathological per-block widths do."""
    from localai_tpu.ops.pallas.ragged_prefill import ragged_kernel_plan

    for N in (1024, 1152, 2048, 4096):
        plan = ragged_kernel_plan(N, 8, 4, 128)
        assert plan is not None, N
        qb, pkb = plan
        assert N % qb == 0 and N % pkb == 0 and qb <= 128
    assert ragged_kernel_plan(2048, 8, 4, 128) == (128, 128)
    assert ragged_kernel_plan(0, 8, 4, 128) is None
    # only PER-BLOCK scratch can disqualify (pathological head widths)
    assert ragged_kernel_plan(1024, 64, 8, 1024) is None


def test_ragged_kernel_shape_fallback_predicate():
    """The engine's fallback counter predicate: SHAPE-driven only —
    static layout/dtype choices (contiguous, int8) route to jnp by
    design and must NOT count, or the CI zero-fallback gate is noise."""
    big = llama.LlamaConfig(
        vocab_size=32, hidden_size=512 * 1024, intermediate_size=64,
        num_layers=1, num_heads=512, num_kv_heads=64,
        max_position_embeddings=64)
    small = llama.LlamaConfig(
        vocab_size=32, hidden_size=64, intermediate_size=64,
        num_layers=1, num_heads=4, num_kv_heads=2,
        max_position_embeddings=64)
    pc = kvcache.init_paged((1, 2, 32, 2, 16), jnp.float32, 8)
    qc = kvcache.init_paged((1, 2, 32, 2, 16), jnp.int8, 8)
    cc = kvcache.init((1, 2, 32, 2, 16), jnp.float32)
    assert llama.ragged_kernel_shape_fallback(pc, 64, small) is False
    assert llama.ragged_kernel_shape_fallback(pc, 1024, big) is True
    assert llama.ragged_kernel_shape_fallback(qc, 1024, big) is False
    assert llama.ragged_kernel_shape_fallback(cc, 1024, big) is False


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_long_pack_parity_vs_per_slot(dtype):
    """>1k packed tokens (the old whole-pack layout's VMEM cliff): the
    segment-blocked kernel (interpret mode) == the jnp packed reference
    == the per-slot references. int8 pages run the jnp path only (the
    kernel is plain-float by design)."""
    from localai_tpu.ops.attention import mixed_prefill_attention
    from localai_tpu.ops.pallas.ragged_prefill import (
        ragged_kernel_plan, ragged_prefill_attention_pallas)
    from localai_tpu.ops.ragged_prefill import ragged_prefill_attention

    rng = np.random.default_rng(11)
    S, C, KV, G, hd, pgs = 4, 64, 2, 2, 16, 16
    N = 1152  # > 1k, not a power of two: qb == gcd(N, 128) == 128
    lc = _paged_layer((1, S, C, KV, hd), dtype, pgs, rng)
    segs = [(0, 1, 40, 0, 500), (1, 3, 0, 500, 380), (2, 0, 17, 880, 260)]
    seg_of, seg_slots, seg_start, seg_off, seg_len = _pack_meta(
        C, N, S, segs)
    q = jnp.asarray(rng.normal(size=(N, KV * G, hd)).astype(np.float32))
    ck = jnp.asarray(rng.normal(size=(N, KV, hd)).astype(np.float32))
    cv = jnp.asarray(rng.normal(size=(N, KV, hd)).astype(np.float32))
    ref = ragged_prefill_attention(q, ck, cv, seg_of, seg_slots, seg_start,
                                   lc, lc, G, continued=True)
    for b, slot, start, off, ln in segs:
        k_rows = kvcache.gather_layer_rows(lc, jnp.asarray([slot]))
        sref = mixed_prefill_attention(
            q[off:off + ln][None], ck[off:off + ln][None],
            cv[off:off + ln][None], k_rows, k_rows,
            jnp.asarray([start]), jnp.asarray([ln]), G)[0]
        np.testing.assert_allclose(np.asarray(ref[off:off + ln]),
                                   np.asarray(sref), atol=3e-4)
    if dtype == jnp.int8:
        return  # kernel path is plain-float; jnp vs per-slot was the pin
    plan = ragged_kernel_plan(N, KV, G, hd)
    assert plan == (128, 128)
    out = ragged_prefill_attention_pallas(
        q, ck, cv, lc["pages"], lc["pages"], lc["ptab"], seg_slots,
        seg_start, seg_off, seg_len, q_per_kv=G, pkb=plan[1], qb=plan[0],
        interpret=True)
    real = np.asarray(seg_of) < S
    np.testing.assert_allclose(np.asarray(out)[real],
                               np.asarray(ref)[real], atol=3e-4)


def test_split_early_emit_default_and_parity(tiny_cfg_params, engine_pair):
    """Packed admission dispatches both of its forms, the early-emit
    head (finals present, room in the pipeline, a full burst runnable)
    and the plain pack otherwise (a lone prompt longer than one chunk
    starts with a pack that holds no final segment): a fresh engine
    that ran both stays byte-identical to the per-slot path, and the
    shape-fallback counter stays 0 (every CPU test pack has a kernel
    plan)."""
    cfg, params = tiny_cfg_params
    e0, e1 = engine_pair
    assert e1.metrics()["packed_prefill"]["kernel_fallback"] == 0
    waves = [_mixed_prompts(np.random.default_rng(seed))
             for seed in (8, 21)]
    waves.append(
        [np.random.default_rng(2).integers(1, 120, size=80).tolist()])
    ref = [_run_wave(e0, prompts, n=24) for prompts in waves]
    e2 = _engine(cfg, params, packed=True)
    try:
        got = [_run_wave(e2, prompts, n=24) for prompts in waves]
        assert {"packed_head", "packed"} <= {
            k[0] for k in e2._final_fns if isinstance(k, tuple)}
        assert e2.metrics()["packed_prefill"]["kernel_fallback"] == 0
    finally:
        e2.shutdown()
    assert got == ref


def test_kernel_fallback_counter_plumbing(tiny_cfg_params, monkeypatch):
    """A continued pack whose shape has no kernel plan increments
    metrics()["packed_prefill"]["kernel_fallback"] (the predicate is
    consulted once per continued packed dispatch)."""
    cfg, params = tiny_cfg_params
    e = _engine(cfg, params, packed=True)
    try:
        _run_wave(e, _mixed_prompts(np.random.default_rng(22)))
        assert e.metrics()["packed_prefill"]["kernel_fallback"] == 0
        calls = []
        monkeypatch.setattr(llama, "ragged_kernel_shape_fallback",
                            lambda *a: calls.append(a) or True)
        _run_wave(e, _mixed_prompts(np.random.default_rng(23)))
        assert calls, "no continued pack consulted the predicate"
        assert e.metrics()["packed_prefill"]["kernel_fallback"] >= len(calls)
    finally:
        e.shutdown()


def test_packed_spec_slots_parity(tiny_cfg_params):
    """Spec-eligible slots now pack (ISSUE 11 lifted the exclusion): a
    draft-equipped packed engine stays byte-identical to the unpacked
    draft engine, and the packed draft-cache mirror actually compiled."""
    cfg, params = tiny_cfg_params
    draft_params = llama.init_params(cfg, jax.random.PRNGKey(5))
    prompts = _mixed_prompts(np.random.default_rng(24))
    e0 = _engine(cfg, params, packed=False, draft=(cfg, draft_params),
                 n_draft=3)
    try:
        ref = _run_wave(e0, prompts, n=16)
    finally:
        e0.shutdown()
    e1 = _engine(cfg, params, packed=True, draft=(cfg, draft_params),
                 n_draft=3)
    try:
        got = _run_wave(e1, prompts, n=16)
        assert any(isinstance(k, tuple) and k[0] == "draft_packed"
                   for k in e1._chunk_fns), "draft mirror never compiled"
        assert e1.metrics()["packed_prefill"]["dispatches"] > 0
    finally:
        e1.shutdown()
    assert got == ref


def test_overlap_halves_unit():
    """overlap_halves is bit-exact for any row-wise fn: slicing the
    token axis changes no operand and no reduction order."""
    from localai_tpu.parallel.sharding import overlap_halves

    rng = np.random.default_rng(30)
    w = jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32))

    def fn(t):
        return jnp.einsum("bnd,df->bnf", t, w)

    for n in (1, 2, 7, 64):  # n < 2 falls through to one call
        x = jnp.asarray(rng.normal(size=(2, n, 16)).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(overlap_halves(fn, x, axis=1)), np.asarray(fn(x)))


def test_comm_overlap_forced_greedy_parity(tiny_cfg_params, engine_pair):
    """comm_overlap=1 (forced on, no mesh) keeps greedy output
    byte-identical — the halved-pack layer body is exact, not an
    approximation; auto stays OFF without a mesh."""
    cfg, params = tiny_cfg_params
    e0, e1 = engine_pair
    assert e1._comm_overlap is False  # auto + no mesh
    prompts = _mixed_prompts(np.random.default_rng(31))
    ref = _run_wave(e0, prompts)
    e2 = _engine(cfg, params, packed=True, comm_overlap="1")
    try:
        assert e2._comm_overlap is True
        got = _run_wave(e2, prompts)
    finally:
        e2.shutdown()
    assert got == ref


@pytest.mark.slow
def test_comm_overlap_mesh_parity(tiny_cfg_params):
    """comm_overlap auto (meshed -> ON) vs 0 on the 8-device dryrun
    mesh (dp=2, tp=4): greedy byte parity with the overlap engaged."""
    from localai_tpu.parallel import mesh as meshlib
    from localai_tpu.parallel.sharding import shard_params

    cfg, params = tiny_cfg_params
    mesh = meshlib.make_mesh(meshlib.MeshPlan(dp=2, tp=4),
                             devices=jax.devices()[:8])
    prompts = [p[:24] for p in _mixed_prompts(np.random.default_rng(32))][:4]
    outs = {}
    for co in ("0", "auto"):
        sharded = shard_params(mesh, params, cfg.tie_word_embeddings)
        e = _engine(cfg, sharded, packed=True, mesh=mesh, slots=4,
                    comm_overlap=co)
        try:
            assert e._comm_overlap is (co == "auto")
            outs[co] = _run_wave(e, prompts, n=6)
        finally:
            e.shutdown()
    assert outs["auto"] == outs["0"]


def test_burst_share_weighted():
    """Decode-burst DRR shaping (PR-10 follow-up): neutral whenever no
    STRICTLY higher class has prefill work pending, weighted shrink
    when one does."""
    from localai_tpu.engine.scheduler import Scheduler

    s = Scheduler()  # weights 4:2:1
    assert s.burst_share(None, [0, 0, 0], 8) == 8  # nothing decoding
    assert s.burst_share(1, [0, 0, 0], 8) == 8     # nothing pending
    assert s.burst_share(0, [0, 4, 2], 8) == 8     # only lower pending
    assert s.burst_share(1, [0, 3, 0], 8) == 8     # same class pending
    assert s.burst_share(2, [1, 0, 0], 1) == 1     # cap floor
    assert s.burst_share(2, [1, 0, 0], 8) == 1     # 8*1 // (1+4)
    assert s.burst_share(1, [2, 0, 0], 8) == 2     # 8*2 // (2+4)
    assert s.burst_share(2, [0, 1, 0], 8) == 2     # 8*1 // (1+2)


# ---------- knobs + telemetry ----------

def test_packed_knobs_validate():
    from localai_tpu.config.model_config import ModelConfig

    ok = ModelConfig(name="m", options=["prefill_packed=0",
                                        "prefill_token_budget=1024"])
    assert ok.validate() == []
    bad = ModelConfig(name="m", options=["prefill_packed=maybe"])
    assert any("prefill_packed" in p for p in bad.validate())
    bad2 = ModelConfig(name="m", options=["prefill_token_budget=-1"])
    assert any("prefill_token_budget" in p for p in bad2.validate())
    ok2 = ModelConfig(name="m", options=["comm_overlap=auto"])
    assert ok2.validate() == []
    bad4 = ModelConfig(name="m", options=["comm_overlap=yes"])
    assert any("comm_overlap" in p for p in bad4.validate())


def test_ttft_metrics_exposition():
    """The localai_ttft_* gauges + packed-prefill counters render in
    Prometheus exposition format (the names localai_routes.py exports
    from the engine's GetMetrics JSON side-channel)."""
    from localai_tpu.services.metrics import Metrics

    m = Metrics()
    m.set_gauge("ttft_queue_wait_p50_ms", 12.5, 'model="x"')
    m.set_gauge("ttft_admit_to_first_p50_ms", 80.0, 'model="x"')
    m.set_gauge("ttft_prefill_dispatch_p50_ms", 30.5, 'model="x"')
    m.set_gauge("ttft_samples", 42, 'model="x"')
    m.set_counter("prefill_packed_dispatches_total", 7, 'model="x"')
    m.set_counter("prefill_packed_tokens_total", 1234, 'model="x"')
    m.set_counter("prefill_kernel_fallback_total", 3, 'model="x"')
    text = m.render()
    assert 'localai_prefill_kernel_fallback_total{model="x"} 3' in text
    assert 'localai_ttft_queue_wait_p50_ms{model="x"} 12.5' in text
    assert 'localai_ttft_admit_to_first_p50_ms{model="x"} 80' in text
    assert 'localai_ttft_prefill_dispatch_p50_ms{model="x"} 30.5' in text
    assert 'localai_prefill_packed_dispatches_total{model="x"} 7' in text
    assert 'localai_prefill_packed_tokens_total{model="x"} 1234' in text
    m.clear_instrument("ttft_queue_wait_p50_ms")
    assert "ttft_queue_wait_p50_ms" not in m.render()


def test_engine_metrics_report_ttft_decomp_and_packing(engine_pair):
    """metrics() carries both halves the /metrics export reads: the
    rolling TTFT decomposition and the packed-prefill totals."""
    _, e1 = engine_pair
    _run_wave(e1, [np.random.default_rng(7).integers(
        1, 120, size=20).tolist()])
    m = e1.metrics()
    assert m["packed_prefill"]["dispatches"] >= 1
    d = m["ttft_decomp_p50_ms"]
    assert set(d) == {"queue_wait", "admit_to_first",
                      "prefill_dispatch", "n"}
    assert d["n"] >= 1
