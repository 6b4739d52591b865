"""Cross-release prefix cache (engine/prefix_cache.py): hash-chain
identity, retained-page lifecycle, LRU eviction under pool pressure, and
engine-level cross-release reuse with greedy parity vs cold prefill.

The page lifecycle under test:  free -> active -> retained -> (reused |
evicted).  "Retained" pages are alive only through PrefixPageCache holds
(engine/paging.py hold/drop) after every slot table let go; admission
splices a matching hash chain back into a table with zero KV row copies
and the existing COW guard protects the boundary write.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.engine.paging import (KVLifecycleError, PagePool,
                                       PoolExhausted)
from localai_tpu.engine.prefix_cache import PrefixPageCache, build_scope
from localai_tpu.models import llama
from localai_tpu.ops import kvcache


# ---------- hash chain ----------

def test_page_chain_hash_identity_and_scoping():
    scope_a = kvcache.page_scope(16, "llama", 2, 2, 16)
    scope_b = kvcache.page_scope(32, "llama", 2, 2, 16)   # page size differs
    toks = list(range(16))
    h1 = kvcache.page_chain_hash(kvcache.PAGE_HASH_ROOT, toks, scope_a)
    h2 = kvcache.page_chain_hash(kvcache.PAGE_HASH_ROOT, toks, scope_a)
    assert h1 == h2 and len(h1) == kvcache.PAGE_HASH_BYTES
    # scope, parent, and content each fold into the digest
    assert h1 != kvcache.page_chain_hash(kvcache.PAGE_HASH_ROOT, toks, scope_b)
    assert h1 != kvcache.page_chain_hash(h1, toks, scope_a)
    assert h1 != kvcache.page_chain_hash(
        kvcache.PAGE_HASH_ROOT, toks[:-1] + [99], scope_a)
    # container-independent: list == np.int32 array
    assert h1 == kvcache.page_chain_hash(
        kvcache.PAGE_HASH_ROOT, np.asarray(toks, np.int32), scope_a)


def test_chain_keys_diverge_and_hide_the_tail():
    """Same length, different tokens mid-chain: every key past the
    divergent page differs — a stale suffix can never be matched."""
    c = PrefixPageCache(kvcache.page_scope(4, "t"), 4)
    a = list(range(16))
    b = list(range(8)) + [99] + list(range(9, 16))   # differs in page 2
    ka, kb = list(c.chain_keys(a)), list(c.chain_keys(b))
    assert len(ka) == len(kb) == 4
    assert ka[:2] == kb[:2]
    assert ka[2] != kb[2] and ka[3] != kb[3]


# ---------- store lifecycle on a bare pool ----------

def _pool_with_chain(toks, pgs=4, num_pages=0):
    pool = PagePool(num_slots=2, max_context=16, page_size=pgs,
                    num_pages=num_pages)
    pool.ensure(0, len(toks))
    cache = PrefixPageCache(kvcache.page_scope(pgs, "unit"), pgs)
    return pool, cache


def test_insert_retain_match_and_release():
    toks = list(range(14))                      # 3 full pages + partial
    pool, cache = _pool_with_chain(toks)
    added = cache.insert(pool, 0, toks)
    assert added == 3 and cache.pages_held == 3
    # while the table still references them the pages are ACTIVE
    assert pool.retained_pages == 0 and pool.active_pages == 4
    pool.release(0, 0)
    # now only the cache holds the 3 full pages; the partial page freed
    assert pool.retained_pages == 3 and pool.active_pages == 0
    assert pool.free_pages == pool.num_pages - 3

    # chain match: full prefix, divergent tail, full miss
    assert len(cache.match(toks, 8)) == 3
    assert len(cache.match(toks[:9], 8)) == 2      # only 2 full pages given
    div = list(toks)
    div[5] = 99                                    # page 1 diverges
    assert len(cache.match(div, 8)) == 1
    assert cache.match([7] * 14, 8) == []

    # splice back into a table: refs bump, retained -> active
    rows = pool.splice(1, cache.match(toks, 8))
    assert rows == 12
    assert pool.active_pages == 3 and pool.retained_pages == 0
    assert all(pool.page_refs(1, i) == 2 for i in range(3))


def test_insert_dedups_identical_chains():
    toks = list(range(12))
    pool, cache = _pool_with_chain(toks)
    pool.ensure(1, len(toks))
    assert cache.insert(pool, 0, toks) == 3
    # slot 1 independently prefilled the same tokens: same keys, no new
    # holds — its pages simply free with its table
    assert cache.insert(pool, 1, toks) == 0
    pool.release(0, 0)
    pool.release(1, 0)
    assert pool.retained_pages == 3


def test_evict_lru_first_with_cascade():
    pgs = 4
    pool = PagePool(num_slots=2, max_context=16, page_size=pgs)  # 8 pages
    cache = PrefixPageCache(kvcache.page_scope(pgs, "unit"), pgs)
    a, b = list(range(12)), list(range(100, 112))
    pool.ensure(0, 12)
    cache.insert(pool, 0, a)
    pool.release(0, 0)
    pool.ensure(0, 12)
    cache.insert(pool, 0, b)
    pool.release(0, 0)
    assert pool.retained_pages == 6 and pool.free_pages == 2
    cache.match(a, 8)            # touch chain A: B is now LRU
    dropped = cache.evict(pool, need_free=4)
    assert dropped >= 2 and pool.free_pages >= 4
    assert len(cache.match(a, 8)) == 3       # A survived untouched
    # B lost its tail first (deepest-first within the LRU tick, so the
    # most-reusable chain roots die last); eviction stops the moment
    # enough pages are free
    assert len(cache.match(b, 8)) <= 1
    # evicting everything empties the store and frees every page
    cache.evict(pool, need_free=pool.num_pages)
    assert cache.pages_held == 0 and pool.free_pages == pool.num_pages
    assert (pool.refs == 0).all() and (pool.held == 0).all()


def test_hold_on_free_page_is_rejected():
    pool = PagePool(num_slots=1, max_context=16, page_size=4)
    with pytest.raises(KVLifecycleError):
        pool.hold(0)


def test_pool_telemetry_prometheus_exposition():
    """The /metrics surface for the new gauges/counters (the API process
    refreshes these from each backend's GetMetrics JSON side-channel)."""
    from localai_tpu.services.metrics import Metrics

    m = Metrics()
    m.set_gauge("kv_pool_pages", 12, 'model="x",state="free"')
    m.set_gauge("kv_pool_pages", 3, 'model="x",state="retained"')
    m.set_counter("prefix_cache_hits_total", 5, 'model="x"')
    text = m.render()
    assert "# TYPE localai_kv_pool_pages gauge" in text
    assert 'localai_kv_pool_pages{model="x",state="free"} 12' in text
    assert 'localai_kv_pool_pages{model="x",state="retained"} 3' in text
    assert "# TYPE localai_prefix_cache_hits_total counter" in text
    assert 'localai_prefix_cache_hits_total{model="x"} 5' in text
    m.clear_instrument("kv_pool_pages")
    assert "kv_pool_pages" not in m.render()
    assert "prefix_cache_hits_total" in m.render()  # others untouched


# ---------- engine e2e ----------

class _Tok:
    eos_token_id = 0

    def decode(self, ids, **kw):
        return "".join(chr(97 + (i % 26)) for i in ids)

    def convert_ids_to_tokens(self, ids):
        return [chr(97 + (i % 26)) for i in ids]


@pytest.fixture(scope="module")
def tiny_cfg_params():
    cfg = llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, page_size=16, mesh=None, slots=2, pool_pages=0,
            prefix_cache=True, min_rows=16):
    e = eng.Engine(
        cfg, params, _Tok(),
        eng.EngineConfig(num_slots=slots, max_context=128,
                         prefill_buckets=(16, 64), prefill_chunk=64,
                         cache_dtype=jnp.float32, kv_layout="paged",
                         kv_page_size=page_size, kv_pool_pages=pool_pages,
                         kv_prefix_cache=prefix_cache,
                         kv_prefix_cache_min_rows=min_rows),
        mesh=mesh)
    e.start()
    return e


def _greedy(e, ids, n=6):
    _, evs = e.generate_text(eng.GenRequest(
        prompt_ids=list(ids), max_new_tokens=n, ignore_eos=True,
        params=sampling.SamplingParamsHost(temperature=0.0)))
    return eng.event_ids(evs), evs


def _prompt(rng, n):
    return [int(x) for x in rng.integers(1, 120, size=n)]


def test_cross_release_reuse_greedy_parity(tiny_cfg_params):
    """The headline lifecycle: a conversation's slot is overwritten by
    unrelated traffic, yet its second turn splices the retained pages
    from the store — byte-identical greedy output, hit counted, rows
    reused, zero KV copies (the COW clone fires at most per boundary)."""
    cfg, params = tiny_cfg_params
    rng = np.random.default_rng(10)
    pgs = 16
    a = _prompt(rng, 48)                       # 3 full pages
    e = _engine(cfg, params, page_size=pgs, slots=2)
    try:
        ref, _ = _greedy(e, a)                 # cold prefill
        # churn BOTH slots with unrelated prompts so a's pages survive
        # only in the cross-release store
        for i in range(3):
            _greedy(e, _prompt(rng, 48))
        assert not any(t[:len(a)] == a for t in e._cache_tokens), \
            "churn failed to overwrite the conversation's slot"
        hits0 = e._pcache.hits
        got, evs = _greedy(e, a)               # second turn after churn
        assert got == ref                      # byte-identical to cold
        assert e._pcache.hits == hits0 + 1
        # full pages of the prompt reused; 48 rows cap to 47 (one token
        # must remain to produce last-position logits)
        assert evs[-1].timings["reused_prompt_tokens"] == 47
        m = e.metrics()
        assert m["prefix_cache"]["hits"] >= 1
        assert m["prefix_cache"]["hit_rows"] >= 47
        assert m["kv_pages_retained"] > 0
        assert (m["kv_pages_free"] + m["kv_pages_retained"]
                + m["kv_pages_active"] == m["kv_pages_total"])
    finally:
        e.shutdown()


def test_no_false_reuse_on_hash_chain_divergence(tiny_cfg_params):
    """Same length, different tokens: only the identical leading pages
    may be reused; the divergent tail never matches, and the output
    equals a cold prefill of the divergent prompt."""
    cfg, params = tiny_cfg_params
    # (seed 11's prompt ends, in the cold engine alone, on two equal
    # bfloat16 maxima, tokens 50 and 51: which of two equal maxima a
    # greedy row takes is the sampler's tie rule, not this test's subject)
    rng = np.random.default_rng(12)
    pgs = 16
    a = _prompt(rng, 48)
    div = list(a)
    div[20] = (div[20] % 119) + 1 if div[20] != 119 else 1  # page 1 differs
    assert div != a and len(div) == len(a)
    e_cold = _engine(cfg, params, page_size=pgs, slots=2)
    try:
        ref_div, _ = _greedy(e_cold, div)
    finally:
        e_cold.shutdown()
    e = _engine(cfg, params, page_size=pgs, slots=2)
    try:
        _greedy(e, a)
        for _ in range(3):
            _greedy(e, _prompt(rng, 48))
        got, evs = _greedy(e, div)
        assert got == ref_div
        # page 0 is genuinely identical -> legitimately reusable; pages
        # 1-2 diverge and must NOT be spliced
        assert evs[-1].timings["reused_prompt_tokens"] <= pgs
        # an all-different prompt of the same length reuses nothing
        other = _prompt(np.random.default_rng(99), 48)
        _, evs2 = _greedy(e, other)
        assert evs2[-1].timings["reused_prompt_tokens"] == 0
    finally:
        e.shutdown()


def test_eviction_under_pool_pressure_no_deadlock(tiny_cfg_params):
    """Oversubscribed pool: retained pages are evicted LRU-first and the
    admissions succeed instead of deadlocking or failing PoolExhausted."""
    cfg, params = tiny_cfg_params
    rng = np.random.default_rng(12)
    pgs = 16
    # 8 pages = exactly ONE slot's worth of context (128 rows): serving
    # 48-token prompts back to back forces reclaim + eviction
    e = _engine(cfg, params, page_size=pgs, slots=2, pool_pages=8)
    try:
        _greedy(e, _prompt(rng, 48))
        for _ in range(3):
            _greedy(e, _prompt(rng, 48))       # each admission pressures
        m = e.metrics()
        assert m["prefix_cache"]["evicted_pages"] > 0
        assert m["kv_pool_oversubscription"] == 2.0
        assert (m["kv_pages_free"] + m["kv_pages_retained"]
                + m["kv_pages_active"] == m["kv_pages_total"])
    finally:
        e.shutdown()


def test_min_rows_guard_on_store_hits(tiny_cfg_params):
    """ISSUE satellite: the min-prefix-reuse threshold must gate cache-
    store hits exactly like live-slot matches — a 1-page BOS match never
    wins over a clean prefill, while a long chain still splices."""
    cfg, params = tiny_cfg_params
    rng = np.random.default_rng(15)
    pgs = 16
    # pool sized ABOVE the contiguous reservation (a legal choice: more
    # retention headroom for more HBM) so no eviction muddies the guard
    e = _engine(cfg, params, page_size=pgs, slots=2, pool_pages=32,
                min_rows=32)
    try:
        x = _prompt(rng, 48)
        _greedy(e, x)
        for _ in range(3):
            _greedy(e, _prompt(rng, 48))
        assert not any(t[:len(x)] == x for t in e._cache_tokens)
        # one shared page (16 rows) < min_rows: rejected, full prefill
        y = list(x[:pgs]) + _prompt(rng, 32)
        misses0 = e._pcache.misses
        _, evs = _greedy(e, y)
        assert evs[-1].timings["reused_prompt_tokens"] == 0
        assert e._pcache.misses == misses0 + 1
        # ... but a full 47-row chain match still clears the bar.
        # (y's release retained its own longer chain whose first page is
        # x's page 0 — resubmitting x must NOT splice y's divergent
        # tail: the chain walk stops at x's own pages.)
        got, evs = _greedy(e, x)
        assert evs[-1].timings["reused_prompt_tokens"] == 47
        assert e._pcache.hits >= 1
    finally:
        e.shutdown()


def test_prefix_cache_off_restores_pr1_lifecycle(tiny_cfg_params):
    """kv_prefix_cache=0: no store is built, releases free pages exactly
    as in PR 1, and cross-release admission pays a full prefill."""
    cfg, params = tiny_cfg_params
    rng = np.random.default_rng(13)
    a = _prompt(rng, 48)
    e = _engine(cfg, params, slots=2, prefix_cache=False)
    try:
        assert e._pcache is None
        ref, _ = _greedy(e, a)
        for _ in range(3):
            _greedy(e, _prompt(rng, 48))
        got, evs = _greedy(e, a)
        assert got == ref
        assert evs[-1].timings["reused_prompt_tokens"] == 0
        assert (e._pool.held == 0).all()
        m = e.metrics()
        assert "prefix_cache" not in m and m["kv_pages_retained"] == 0
    finally:
        e.shutdown()


def test_retention_excluded_from_contiguous_fallbacks(tiny_cfg_params):
    """The store must never exist for layouts without pages: contiguous
    opt-out, multi-host lockstep fallback, and self-extend fallback all
    construct without a PrefixPageCache."""
    import types

    cfg, params = tiny_cfg_params
    ecfg = eng.EngineConfig(num_slots=2, max_context=128,
                            cache_dtype=jnp.float32,
                            kv_layout="contiguous")
    e = eng.Engine(cfg, params, _Tok(), ecfg)
    assert e._pool is None and e._pcache is None
    e.shutdown()

    bus = types.SimpleNamespace(send=lambda *a, **k: None,
                                close=lambda: None)
    e = eng.Engine(cfg, params, _Tok(),
                   eng.EngineConfig(num_slots=2, max_context=128,
                                    cache_dtype=jnp.float32,
                                    kv_layout="auto"), bus=bus)
    assert not e._paged and e._pcache is None
    e.shutdown()

    e = eng.Engine(cfg, params, _Tok(),
                   eng.EngineConfig(num_slots=2, max_context=128,
                                    cache_dtype=jnp.float32,
                                    kv_layout="auto", ga_n=2, ga_w=32))
    assert not e._paged and e._pcache is None
    e.shutdown()


@pytest.mark.slow
def test_cross_release_parity_on_mesh(tiny_cfg_params):
    """Cross-release reuse parity under the 8-device dryrun mesh (dp=2,
    tp=4): the spliced chain gathers through the replicated page table
    on every shard."""
    from localai_tpu.parallel import mesh as meshlib
    from localai_tpu.parallel.sharding import shard_params

    cfg, params = tiny_cfg_params
    mesh = meshlib.make_mesh(meshlib.MeshPlan(dp=2, tp=4),
                             devices=jax.devices()[:8])
    sharded = shard_params(mesh, params, cfg.tie_word_embeddings)
    rng = np.random.default_rng(14)
    a = _prompt(rng, 32)
    e = _engine(cfg, sharded, mesh=mesh, slots=4)
    try:
        ref, _ = _greedy(e, a, n=4)
        for _ in range(5):
            _greedy(e, _prompt(rng, 32), n=4)
        assert not any(t[: len(a)] == a for t in e._cache_tokens)
        got, evs = _greedy(e, a, n=4)
        assert got == ref
        assert evs[-1].timings["reused_prompt_tokens"] >= 16
        assert e._pcache.hits >= 1
    finally:
        e.shutdown()
