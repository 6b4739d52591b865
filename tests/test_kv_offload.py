"""Two-tier KV page store (engine/kv_offload.py): host-RAM offload of
evicted chains, prefetch-ahead restore at admission, LRU cascade
device -> host -> gone, disk persistence, and PR-2 parity when off.

The lifecycle under test extends PR 2's:
    free -> active -> retained -> (reused | OFFLOADED | free)
where an offloaded page's rows live in the HostPageStore (numpy, device
representation preserved) and a later prefix-cache hit restores them
into freshly allocated device pages spliced onto the admitting slot's
table — dispatch-only, never a serving-loop sync.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.engine.kv_offload import HostPageStore
from localai_tpu.engine.paging import PagePool
from localai_tpu.engine.prefix_cache import PrefixPageCache
from localai_tpu.models import llama
from localai_tpu.ops import kvcache


# ---------- host store units ----------

def _scope(pgs=4):
    return kvcache.page_scope(pgs, "unit")


def _page(v, shape=(2, 4, 2, 8)):
    return np.full(shape, v, np.float32)


def _chain(store, n, start=0, parent=None, val=0.0):
    """Insert an n-entry chain; returns the keys."""
    keys = []
    parent = parent if parent is not None else kvcache.PAGE_HASH_ROOT
    for i in range(n):
        key = kvcache.page_chain_hash(parent, [start + i] * 4, store.scope)
        store.put(key, parent, i, _page(val + i), _page(val + i + 100))
        keys.append(key)
        parent = key
    return keys


def test_host_store_put_get_and_dedup():
    s = HostPageStore(_scope(), 4, budget_mb=64)
    keys = _chain(s, 3)
    assert s.pages == 3
    e = s.get(keys[1])
    assert e is not None and e.depth == 1
    assert np.array_equal(e.k, _page(1))
    # duplicate keys touch, never duplicate
    s.put(keys[0], kvcache.PAGE_HASH_ROOT, 0, _page(9), _page(9))
    assert s.pages == 3 and np.array_equal(s.get(keys[0]).k, _page(0))
    assert s.get(b"\x01" * 16) is None


def test_host_store_budget_lru_eviction_with_cascade():
    """The host->gone edge: LRU-first past the byte budget, descendants
    cascading away with their ancestor (orphans are unreachable)."""
    page_bytes = 2 * _page(0).nbytes
    budget_mb = 1
    cap = (budget_mb << 20) // page_bytes
    s = HostPageStore(_scope(), 4, budget_mb=budget_mb)
    a = _chain(s, 3, start=0)
    s.get(a[0]); s.get(a[1]); s.get(a[2])     # touch A: B will be LRU...
    b = _chain(s, 3, start=50, val=50)        # ...except B is newer; touch A
    for k in a:
        assert s.get(k) is not None
    # fill to the brim with fresh chains: B (oldest untouched) dies first
    n_fill = cap - s.pages + 1
    _chain(s, n_fill, start=100, val=200)
    assert s.bytes_used <= s.budget_bytes
    assert s.get(b[0]) is None or s.get(b[2]) is None
    assert s.evicted_pages > 0
    # cascade: removing a root removed every descendant
    present = [k for k in b if s.contains(k)]
    depths = [s.get(k).depth for k in present]
    assert depths == sorted(depths)   # never a child without its ancestors


# ---------- shared mode (ISSUE 14: one host tier, N replicas) ----------


def test_shared_store_mapped_keys_never_evicted():
    """A key some replica's device tier still maps — plus its whole
    ancestor chain (a child without its ancestors is unreachable) — must
    survive budget eviction; the budget degrades to best-effort and the
    skip is counted. Unmapping releases the protection."""
    page_bytes = 2 * _page(0).nbytes
    budget_mb = 1
    cap = (budget_mb << 20) // page_bytes
    s = HostPageStore(_scope(), 4, budget_mb=budget_mb)
    a = _chain(s, 3, start=0)
    s.map_key(a[2], owner=0)        # tail mapped -> whole chain protected
    assert s.mapped_count(a[2]) == 1
    # fill way past the budget with INDEPENDENT single-page chains (a
    # single long chain would cascade away in one eviction): A is the
    # LRU victim every pass, but it is protected
    for i in range(cap):
        _chain(s, 1, start=100 + i, val=200)
    for k in a:
        assert s.contains(k), "mapped chain (or an ancestor) was evicted"
    assert s.evict_blocked >= 1
    assert s.stats()["mapped_keys"] == 1
    # a second owner keeps the pin alive when the first lets go
    s.map_key(a[2], owner=1)
    s.unmap_key(a[2], owner=0)
    for i in range(8):
        _chain(s, 1, start=5000 + i, val=90)
    assert all(s.contains(k) for k in a)
    # last owner unmaps -> A is ordinary LRU prey again (its ticks are
    # the oldest in the store, so the next budget pass takes it)
    s.unmap_key(a[2], owner=1)
    assert s.stats()["mapped_keys"] == 0
    for i in range(8):
        _chain(s, 1, start=6000 + i, val=91)
    assert not any(s.contains(k) for k in a)


def test_shared_store_unmap_owner_drops_all():
    s = HostPageStore(_scope(), 4, budget_mb=64)
    a = _chain(s, 2, start=0)
    b = _chain(s, 2, start=50, val=50)
    s.map_key(a[1], owner=7)
    s.map_key(b[0], owner=7)
    s.map_key(b[0], owner=8)
    assert s.unmap_owner(7) == 2
    assert s.stats()["mapped_keys"] == 1     # owner 8 still pins b[0]
    assert s.unmap_owner(8) == 1
    assert s.stats()["mapped_keys"] == 0
    assert s.unmap_owner(7) == 0             # idempotent


def test_shared_store_concurrent_put_get_evict_race():
    """Two 'replica' threads hammer one store with puts/gets under a
    budget small enough to keep eviction storming, while a third churns
    map/unmap on a pinned chain. The shared-mode invariants must hold
    throughout: no exceptions, the pinned chain survives every eviction
    pass, and the byte budget stays best-effort-bounded."""
    page_bytes = 2 * _page(0).nbytes
    s = HostPageStore(_scope(), 4, budget_mb=1)
    cap = (1 << 20) // page_bytes
    pinned = _chain(s, 3, start=0)
    s.map_key(pinned[2], owner="pin")
    errors = []

    def hammer(tid):
        try:
            for round_ in range(6):
                keys = _chain(s, cap // 3, start=1000 * (tid + 1),
                              val=10.0 * tid)
                for k in keys[::7]:
                    e = s.get(k)        # CRC-verified read or clean miss
                    if e is not None:
                        assert e.k is not None
        except Exception as ex:   # pragma: no cover - failure reporting
            errors.append(ex)

    def churn():
        try:
            for _ in range(200):
                s.map_key(pinned[1], owner="churn")
                s.mapped_count(pinned[1])
                s.unmap_key(pinned[1], owner="churn")
        except Exception as ex:   # pragma: no cover - failure reporting
            errors.append(ex)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in (0, 1)]
    threads.append(threading.Thread(target=churn))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert all(s.contains(k) for k in pinned), "pinned chain was evicted"
    st = s.stats()
    assert st["mapped_keys"] == 1            # only the durable pin remains
    # budget is best-effort: exceeded only while everything left is
    # protected, which a 3-page pin can never cause at a 1 MB budget
    assert s.bytes_used <= s.budget_bytes


def test_shared_kv_pool_store_loads_and_saves_once(tmp_path, monkeypatch):
    """N replicas share ONE persisted store: the pool loads the file
    once (not once per replica) and persists it once at shutdown."""
    from localai_tpu.engine import kv_offload as kvo
    from localai_tpu.engine.pool import SharedKV

    path = str(tmp_path / "pool_store.npz")
    seed = HostPageStore(_scope(), 4, budget_mb=16)
    keys = _chain(seed, 3)
    assert seed.save(path)
    calls = {"load": 0, "save": 0}
    real_load, real_save = kvo.HostPageStore.load, kvo.HostPageStore.save

    def counting_load(self, p):
        calls["load"] += 1
        return real_load(self, p)

    def counting_save(self, p):
        calls["save"] += 1
        return real_save(self, p)

    monkeypatch.setattr(kvo.HostPageStore, "load", counting_load)
    monkeypatch.setattr(kvo.HostPageStore, "save", counting_save)
    shared = SharedKV()
    s0 = shared.host_store(_scope(), 4, 16, path)     # replica 0 asks
    s1 = shared.host_store(_scope(), 4, 16, path)     # replica 1 asks
    assert s0 is s1 and calls["load"] == 1
    assert all(s0.contains(k) for k in keys)
    extra = _chain(s0, 1, start=77, val=7)
    assert shared.save() and calls["save"] == 1       # pool shutdown
    fresh = HostPageStore(_scope(), 4, budget_mb=16)
    assert fresh.load(path) == 4                      # one file, 4 pages
    assert fresh.contains(extra[0])


def test_device_to_host_handoff_on_evict():
    """PrefixPageCache.evict(on_evict=...) fires for every dropped entry
    BEFORE the pool reference dies — the engine's offload handoff point;
    the full cascade lands in the host store, then pool pages are free."""
    pgs = 4
    pool = PagePool(num_slots=2, max_context=16, page_size=pgs)
    cache = PrefixPageCache(kvcache.page_scope(pgs, "unit"), pgs)
    toks = list(range(12))
    pool.ensure(0, 12)
    cache.insert(pool, 0, toks)
    pool.release(0, 0)
    assert pool.retained_pages == 3
    seen = []

    def on_evict(e):
        assert pool.refs[e.page] > 0, "handoff after the page died"
        seen.append((e.key, e.depth))

    dropped = cache.evict(pool, need_free=pool.num_pages, on_evict=on_evict)
    assert dropped == 3 and len(seen) == 3
    assert pool.free_pages == pool.num_pages
    assert {d for _k, d in seen} == {0, 1, 2}


def test_host_store_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "store.npz")
    s = HostPageStore(_scope(), 4, budget_mb=64)
    keys = _chain(s, 3)
    assert s.save(path) and os.path.exists(path)
    s2 = HostPageStore(_scope(), 4, budget_mb=64)
    assert s2.load(path) == 3
    for i, k in enumerate(keys):
        e = s2.get(k)
        assert e is not None and e.depth == i
        assert np.array_equal(e.k, _page(i))
        assert np.array_equal(e.v, _page(i + 100))
    # reloaded pages are not re-counted as this process's offloads
    assert s2.offloaded_pages == 0


def test_host_store_persistence_rejects_mismatch_and_corruption(tmp_path):
    path = str(tmp_path / "store.npz")
    s = HostPageStore(_scope(), 4, budget_mb=64)
    _chain(s, 2)
    assert s.save(path)
    # different scope (model/geometry/dtype) -> ignored, never crashed on
    other = HostPageStore(kvcache.page_scope(4, "other-model"), 4, 64)
    assert other.load(path) == 0 and other.pages == 0
    # different page size -> ignored
    other_pg = HostPageStore(_scope(), 8, 64)
    assert other_pg.load(path) == 0
    # truncated file -> ignored
    raw = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(raw[: len(raw) // 3])
    s3 = HostPageStore(_scope(), 4, budget_mb=64)
    assert s3.load(path) == 0 and s3.pages == 0
    # non-npz garbage -> ignored
    with open(path, "wb") as f:
        f.write(b"not an npz" * 7)
    assert s3.load(path) == 0
    # missing file -> 0, quietly
    assert s3.load(str(tmp_path / "absent.npz")) == 0


def test_gather_scatter_pages_dtype_preserving():
    """ops/kvcache offload primitives: gather reads whole physical pages
    in the device representation, scatter restores them byte-exactly;
    sentinel page ids drop (restore batches pad with them)."""
    shape = (2, 3, 8, 2, 4)   # [L, S, C, KV, hd], pg=4 -> 6 pages
    for dtype in (jnp.bfloat16, jnp.int8):
        cache = kvcache.init_paged(shape, dtype, page_size=4, num_pages=6)
        key = jax.random.PRNGKey(0)
        if dtype == jnp.int8:
            cache["pages"] = jax.random.randint(
                key, cache["pages"].shape, -100, 100, jnp.int8)
            cache["scales"] = jax.random.uniform(key, cache["scales"].shape)
        else:
            cache["pages"] = jax.random.normal(
                key, cache["pages"].shape).astype(dtype)
        idx = jnp.asarray([1, 4], jnp.int32)
        rows = kvcache.gather_pages(cache, idx)
        blank = kvcache.init_paged(shape, dtype, page_size=4, num_pages=6)
        # sentinel-padded restore: ids [1, 4, 6, 6] with zero-pad rows
        pad2 = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros(a.shape[:1] + (2,) + a.shape[2:], a.dtype)],
                axis=1), rows)
        out = kvcache.scatter_pages(blank, jnp.asarray([1, 4, 6, 6],
                                                       jnp.int32), pad2)
        for p in (1, 4):
            np.testing.assert_array_equal(np.asarray(out["pages"][:, p]),
                                          np.asarray(cache["pages"][:, p]))
            if dtype == jnp.int8:
                np.testing.assert_array_equal(
                    np.asarray(out["scales"][:, p]),
                    np.asarray(cache["scales"][:, p]))
        untouched = [p for p in range(6) if p not in (1, 4)]
        for p in untouched:
            assert not np.asarray(out["pages"][:, p]).any()


def test_offload_prometheus_exposition():
    """The /metrics surface for the host tier: state="offloaded" pool
    gauge + localai_kv_offload_*_total counters."""
    from localai_tpu.services.metrics import Metrics

    m = Metrics()
    m.set_gauge("kv_pool_pages", 5, 'model="x",state="offloaded"')
    m.set_gauge("kv_offload_host_bytes", 81920, 'model="x"')
    for name, v in (("pages", 7), ("bytes", 114688), ("restores", 2),
                    ("hits", 2), ("misses", 1)):
        m.set_counter(f"kv_offload_{name}_total", v, 'model="x"')
    text = m.render()
    assert 'localai_kv_pool_pages{model="x",state="offloaded"} 5' in text
    assert "# TYPE localai_kv_offload_pages_total counter" in text
    assert 'localai_kv_offload_pages_total{model="x"} 7' in text
    assert 'localai_kv_offload_bytes_total{model="x"} 114688' in text
    assert 'localai_kv_offload_restores_total{model="x"} 2' in text
    assert 'localai_kv_offload_hits_total{model="x"} 2' in text
    assert 'localai_kv_offload_misses_total{model="x"} 1' in text
    m.clear_instrument("kv_offload_pages_total")
    assert "kv_offload_pages_total" not in m.render()


def test_kv_offload_knobs_validate():
    from localai_tpu.config.model_config import ModelConfig

    ok = ModelConfig(name="m", options=["kv_offload=0",
                                       "kv_host_pool_mb=128",
                                       "kv_host_store=store.npz"])
    assert ok.validate() == []
    bad = ModelConfig(name="m", options=["kv_offload=maybe"])
    assert any("kv_offload" in p for p in bad.validate())
    bad2 = ModelConfig(name="m", options=["kv_host_pool_mb=big"])
    assert any("kv_host_pool_mb" in p for p in bad2.validate())


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
            offload=True, host_mb=64, store_path="", cache_dtype=None):
    e = eng.Engine(
        cfg, params, _Tok(),
        eng.EngineConfig(num_slots=slots, max_context=128,
                         prefill_buckets=(16, 64), prefill_chunk=64,
                         cache_dtype=cache_dtype or jnp.float32,
                         kv_layout="paged", kv_page_size=page_size,
                         kv_pool_pages=pool_pages, kv_offload=offload,
                         kv_host_pool_mb=host_mb,
                         kv_host_store_path=store_path),
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


def _wait_offloaded(e, n=1, timeout=5.0):
    """Offload transfers complete on the sync worker — wait for them."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if e._hstore is not None and e._hstore.pages >= n:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"host store never reached {n} pages: {e._hstore.stats()}")


def test_offload_restore_greedy_parity(tiny_cfg_params):
    """The headline: a chain evicted from the device pool under pressure
    is offloaded to host RAM, and the conversation's next turn restores
    it — byte-identical greedy output vs the cold prefill, restored
    device rows byte-identical to the cold rows, restore counted, and
    the restore dispatch visible in the engine's timing marks (the
    non-blocking assertion: only dispatch-time marks exist; there is no
    sync/wait mark in the restore path at all)."""
    cfg, params = tiny_cfg_params
    rng = np.random.default_rng(10)
    a = _prompt(rng, 48)
    # pool = ONE slot's worth of context: every admission pressures.
    # The engine's own FIRST run of ``a`` is the cold reference —
    # the pool is empty at that point, so it IS the cold prefill.
    e = _engine(cfg, params, pool_pages=8)
    try:
        ref, _ = _greedy(e, a)
        slot0 = next(i for i, t in enumerate(e._cache_tokens)
                     if t[:48] == a)
        e._commit_ptab()
        ref_rows = np.asarray(kvcache.slot_rows(e.ck, slot0))[:, :47]
        for _ in range(3):
            _greedy(e, _prompt(rng, 48))
        _wait_offloaded(e, 3)
        assert not any(t[:48] == a for t in e._cache_tokens), \
            "churn failed to overwrite the conversation's slot"
        st0 = e._hstore.stats()
        assert st0["offloaded_pages"] >= 3
        got2, evs = _greedy(e, a)
        assert got2 == ref                       # byte-identical
        st = e._hstore.stats()
        assert st["restores"] == st0["restores"] + 1
        assert st["restored_pages"] >= st0["restored_pages"] + 1
        assert evs[-1].timings["reused_prompt_tokens"] >= 16
        # restored device rows == the cold prefill's rows, byte-wise
        # (minus the COW boundary row the tail prefill rewrites)
        slot1 = next(i for i, t in enumerate(e._cache_tokens)
                     if t[:48] == a)
        e._commit_ptab()
        got_rows = np.asarray(kvcache.slot_rows(e.ck, slot1))[:, :47]
        reused = evs[-1].timings["reused_prompt_tokens"]
        np.testing.assert_array_equal(got_rows[:, :reused],
                                      ref_rows[:, :reused])
        # timing marks: restore + offload were DISPATCHED on the
        # serving loop (no blocking marks exist for either path)
        by = e.tracer.summary()["by_span_ms"]
        assert by["kv_restore_scatter"]["count"] >= 1
        assert by["kv_offload_gather"]["count"] >= 1
        assert not any("wait" in k for k in by
                       if "restore" in k or "offload" in k)
        m = e.metrics()
        assert m["kv_pages_offloaded"] == e._hstore.pages
        assert m["kv_offload"]["restores"] >= 1
        assert (m["kv_pages_free"] + m["kv_pages_retained"]
                + m["kv_pages_active"] == m["kv_pages_total"])
    finally:
        e.shutdown()


def test_restore_miss_falls_back_to_prefill(tiny_cfg_params):
    """Host tier consulted and empty (budget squeezed it out): admission
    pays a plain prefill, byte-identical to the cold output — the PR-2
    behavior, with the miss counted."""
    cfg, params = tiny_cfg_params
    rng = np.random.default_rng(11)
    a = _prompt(rng, 48)
    # the pressured engine's own first (empty-pool) run is the cold ref
    e = _engine(cfg, params, pool_pages=8, host_mb=1)
    try:
        ref, _ = _greedy(e, a)
        for _ in range(3):
            _greedy(e, _prompt(rng, 48))
        _wait_offloaded(e, 1)
        # force a's chain out of the host tier regardless of budget luck
        with e._hstore._lock:
            keys = list(e._hstore._entries)
        for k in keys:
            with e._hstore._lock:
                e._hstore._remove_tree_locked(k)
        misses0 = e._hstore.stats()["misses"]
        got, evs = _greedy(e, a)
        assert got == ref
        assert evs[-1].timings["reused_prompt_tokens"] == 0
        assert e._hstore.stats()["misses"] == misses0 + 1
    finally:
        e.shutdown()


def test_kv_offload_off_restores_pr2_lifecycle(tiny_cfg_params):
    """kv_offload=0: no host store is built, eviction frees pages
    exactly as in PR 2 (no gather dispatches), outputs match the offload
    engine's, and the metrics surface carries no offload keys."""
    cfg, params = tiny_cfg_params
    rng = np.random.default_rng(13)
    prompts = [_prompt(rng, 48) for _ in range(4)]

    def run(offload):
        e = _engine(cfg, params, pool_pages=8, offload=offload)
        try:
            outs = []
            outs.append(_greedy(e, prompts[0])[0])
            for p in prompts[1:]:
                outs.append(_greedy(e, p)[0])
            out2, evs = _greedy(e, prompts[0])
            outs.append(out2)
            return e, outs, evs
        finally:
            e.shutdown()

    e_off, outs_off, evs_off = run(False)
    assert e_off._hstore is None
    m = e_off.metrics()
    assert "kv_offload" not in m and "kv_pages_offloaded" not in m
    assert ("offload_gather", 1) not in e_off._fork_fns
    assert ("offload_gather", 2) not in e_off._fork_fns
    e_on, outs_on, _ = run(True)
    assert outs_off == outs_on       # token-identical either way
    # PR-2 lifecycle: the evicted chain re-prefills (no reuse)...
    assert evs_off[-1].timings["reused_prompt_tokens"] == 0
    # ...and the off engine's pool saw the same eviction pressure
    assert e_off._pcache.evicted_pages > 0


def test_offload_persistence_across_engine_restart(tiny_cfg_params,
                                                   tmp_path):
    """ROADMAP follow-up "persist the store across restarts": offloaded
    chains serialized on graceful shutdown restore into a NEW engine of
    the same model, and the next turn splices them without re-prefill;
    an engine with a different scope ignores the file."""
    cfg, params = tiny_cfg_params
    rng = np.random.default_rng(14)
    a = _prompt(rng, 48)
    path = str(tmp_path / "kv_host_store.npz")
    e = _engine(cfg, params, pool_pages=8, store_path=path)
    try:
        ref, _ = _greedy(e, a)   # empty-pool first run = cold reference
        for _ in range(3):
            _greedy(e, _prompt(rng, 48))
        _wait_offloaded(e, 3)
    finally:
        e.shutdown()
    assert os.path.exists(path)

    e2 = _engine(cfg, params, pool_pages=8, store_path=path)
    try:
        assert e2._hstore.pages >= 3
        got, evs = _greedy(e2, a)
        assert got == ref
        assert evs[-1].timings["reused_prompt_tokens"] >= 16
        assert e2._hstore.stats()["restores"] >= 1
    finally:
        e2.shutdown()
    # scope-mismatch and corrupt-file rejection are covered at the
    # HostPageStore level (test_host_store_persistence_rejects_*);
    # engine init routes through the same load()


def test_default_pool_shrinks_only_with_host_tier(tiny_cfg_params):
    """ROADMAP follow-up: the auto default pool drops to 3/4 of the
    contiguous reservation once the host tier absorbs evictions — and
    only for serving-sized pools; tiny rigs and kv_offload=0 keep the
    full reservation (bit-for-bit PR-2 sizing)."""
    cfg, params = tiny_cfg_params
    # serving-sized: 8 slots * 8 pages = 64 full -> shrunk to 48
    e = _engine(cfg, params, slots=8)
    assert e._pool.num_pages == 48
    assert e.ck["pages"].shape[1] == 48   # the device pool shrank too
    assert e._pool.oversubscription > 1.0
    e.shutdown()
    e = _engine(cfg, params, slots=8, offload=False)
    assert e._pool.num_pages == 64
    e.shutdown()
    # tiny pool: full reservation either way
    e = _engine(cfg, params, slots=2)
    assert e._pool.num_pages == 16
    e.shutdown()


@pytest.mark.slow
def test_offload_restore_parity_on_mesh(tiny_cfg_params):
    """Offload -> restore parity under the 8-device dryrun mesh (dp=2,
    tp=4): the page gather/scatter run on sharded pools (pages sharded
    over kv heads on tp), and the restored rows still match. float32
    params: the parity compares restore-then-continue against a full
    prefill, whose forwards run at different shapes — mesh partitioning
    plus bf16 rounding flips greedy near-ties on noise unrelated to the
    mechanism under test."""
    import dataclasses as _dc

    from localai_tpu.parallel import mesh as meshlib
    from localai_tpu.parallel.sharding import shard_params

    cfg, _ = tiny_cfg_params
    cfg = _dc.replace(cfg, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = meshlib.make_mesh(meshlib.MeshPlan(dp=2, tp=4),
                             devices=jax.devices()[:8])
    sharded = shard_params(mesh, params, cfg.tie_word_embeddings)
    rng = np.random.default_rng(15)
    a = _prompt(rng, 48)
    e0 = _engine(cfg, sharded, mesh=mesh, slots=4, pool_pages=0,
                 offload=False)
    try:
        ref, _ = _greedy(e0, a, n=4)
    finally:
        e0.shutdown()
    # 4 slots, 12 pages: every 48-token admission (4 pages incl. the
    # decode tail) pressures past free-slot reclaim into cache eviction
    e = _engine(cfg, sharded, mesh=mesh, slots=4, pool_pages=12)
    try:
        assert _greedy(e, a, n=4)[0] == ref
        for _ in range(6):
            _greedy(e, _prompt(rng, 48), n=4)
        _wait_offloaded(e, 1)
        got, evs = _greedy(e, a, n=4)
        assert got == ref
        assert e._hstore.stats()["restores"] >= 1
    finally:
        e.shutdown()
