"""Paged KV cache: pool invariants, copy-on-write, attention parity, and
the engine's zero-copy shared-prefix admission.

The paged layout (ops/kvcache.py, engine/paging.py, the ragged paged
kernel in ops/pallas/paged_attention.py) replaces the contiguous
per-slot [L, S, C, KV, hd] reservation; these tests pin:
  * allocator invariants (refcounts, free list, lazy growth);
  * copy-on-write divergence after a shared prefix;
  * paged decode attention == contiguous reference (bf16 atol, int8,
    and the Pallas kernel in interpret mode);
  * exact greedy token parity through the real engine, single device
    and on the 8-device dryrun mesh;
  * shared-prefix admission reuses pages with ZERO row copies (page
    refcounts), and the default pool never exceeds the old reservation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.engine.paging import PagePool, PoolExhausted
from localai_tpu.models import llama
from localai_tpu.ops import kvcache


# ---------- allocator invariants ----------

def test_pool_alloc_free_refcount_invariants():
    pool = PagePool(num_slots=3, max_context=64, page_size=16)  # 12 pages
    assert pool.num_pages == 12 and pool.free_pages == 12
    pool.ensure(0, 40)          # 3 pages
    assert int(pool.owned[0]) == 3 and pool.free_pages == 9
    assert all(pool.page_refs(0, i) == 1 for i in range(3))
    pool.ensure(0, 40)          # idempotent
    assert pool.free_pages == 9

    shared = pool.share(0, 1, 40)       # full pages only: 2 * 16 rows
    assert shared == 32
    assert pool.page_refs(0, 0) == 2 and pool.page_refs(0, 1) == 2
    assert pool.page_refs(0, 2) == 1
    assert pool.free_pages == 9         # sharing allocates nothing

    pool.release(0, 0)                  # slot 0 lets go of all three
    assert pool.free_pages == 10        # only the unshared page returns
    assert pool.page_refs(1, 0) == 1    # slot 1 now sole owner
    pool.release(1, 0)
    assert pool.free_pages == 12
    assert (pool.refs == 0).all()

    # exhaustion raises (engine reclaims + retries above this layer)
    for s in range(3):
        pool.ensure(s, 64)
    with pytest.raises(PoolExhausted):
        pool._alloc()


def test_pool_cow_boundary_and_adopt():
    pool = PagePool(num_slots=2, max_context=64, page_size=16)
    pool.ensure(0, 50)
    pool.share(0, 1, 50)                # 48 rows = 3 full pages
    # writing row 48 in slot 1 would hit... slot 1 owns only 3 pages
    assert pool.cow_page(1, 40) == 2    # row 40 sits in a shared page
    new = pool.alloc_detached()
    pool.replace(1, 2, new)
    assert pool.page_refs(1, 2) == 1 and pool.page_refs(0, 2) == 1
    extra = pool.alloc_detached()
    pool.adopt(1, extra)
    assert int(pool.owned[1]) == 4


# ---------- representation / attention parity ----------

@pytest.fixture(scope="module")
def tiny_cfg_params():
    cfg = llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _paged_pair(shape, dtype, pgs, perm):
    """Paged k-cache with a scrambled page table covering two slots."""
    pc = kvcache.init_paged(shape, dtype, pgs)
    ptab = np.asarray(pc["ptab"]).copy()
    mp = ptab.shape[1]
    ptab[0] = perm[:mp]
    ptab[1] = perm[mp:2 * mp]
    return kvcache.with_page_table(pc, jnp.asarray(ptab))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_paged_decode_attention_matches_contiguous(dtype):
    """The jnp fallback path: dense-gathered paged rows == contiguous
    rows through decode_attention_append, bf16 and int8."""
    from localai_tpu.ops.attention import decode_attention_append

    rng = np.random.default_rng(0)
    S, C, KV, G, hd, pgs = 2, 32, 2, 2, 16, 8
    shape = (1, S, C, KV, hd)
    perm = rng.permutation(S * C // pgs)
    rows = jnp.asarray(rng.normal(size=(S, C, KV, hd)).astype(np.float32))
    lengths = jnp.asarray([20, 7], jnp.int32)
    slot = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None], (S, C))
    col = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None, :], (S, C))
    pk = kvcache.layer(kvcache.scatter_prefill(
        _paged_pair(shape, dtype, pgs, perm), 0, slot, col, rows), 0)
    ck = kvcache.layer(kvcache.scatter_prefill(
        kvcache.init(shape, dtype), 0, slot, col, rows), 0)
    q = jnp.asarray(rng.normal(size=(S, KV * G, hd)).astype(np.float32))
    nk = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    nv = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    out_p = decode_attention_append(q, nk, nv, kvcache.gather_all_rows(pk),
                                    kvcache.gather_all_rows(pk), lengths, G)
    out_c = decode_attention_append(q, nk, nv, ck, ck, lengths, G)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                               atol=1e-2, rtol=1e-2)


def test_ragged_paged_pallas_kernel_matches_jnp_reference():
    """The TPU kernel (interpret mode on CPU) == decode_attention_append
    over dense-gathered pages, including ragged lengths and empty slots."""
    from localai_tpu.ops.attention import decode_attention_append
    from localai_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_append)

    rng = np.random.default_rng(1)
    S, KV, G, hd, pgs, mp, n_pages = 4, 2, 3, 16, 8, 4, 10
    q = jnp.asarray(rng.normal(size=(S, KV * G, hd)).astype(np.float32))
    nk = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    nv = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    pk = jnp.asarray(rng.normal(size=(n_pages, pgs, KV, hd)).astype(np.float32))
    pv = jnp.asarray(rng.normal(size=(n_pages, pgs, KV, hd)).astype(np.float32))
    ptab = np.full((S, mp), n_pages, np.int32)
    ptab[0, :3] = [5, 1, 7]
    ptab[1, :1] = [2]
    ptab[2] = [0, 3, 4, 6]
    ptab = jnp.asarray(ptab)
    lengths = jnp.asarray([20, 5, 32, 0], jnp.int32)
    out = paged_decode_attention_append(q, nk, nv, pk, pv, ptab, lengths,
                                        q_per_kv=G, interpret=True)
    lk = {"pages": pk, "ptab": ptab}
    lv = {"pages": pv, "ptab": ptab}
    ref = decode_attention_append(q, nk, nv, kvcache.gather_all_rows(lk),
                                  kvcache.gather_all_rows(lv), lengths, G)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _stacked_pool(rng, quant, L, n_pages, pgs, KV, hd, ptab):
    """A stacked paged cache whose every page (allocated or not) holds
    random rows, so a read or a write that strays shows."""
    shape = (L, n_pages, pgs, KV, hd)
    if quant:
        return {"pages": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                "scales": jnp.asarray(rng.uniform(0.01, 0.03, shape[:-1]),
                                      jnp.float32),
                "ptab": ptab}
    return {"pages": jnp.asarray(rng.normal(size=shape).astype(np.float32)),
            "ptab": ptab}


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_paged_kernels_index_the_stacked_pool_by_layer(quant, layer):
    """Both kernel variants (interpret mode) read layer ``li`` of the
    STACKED pool, ``li`` a traced scalar as inside the scan over layers,
    through a shuffled page table with sentinel entries and an empty
    slot == decode_attention_append over that layer's gathered rows."""
    from localai_tpu.ops.attention import decode_attention_append
    from localai_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_append, paged_decode_attention_append_quant)

    rng = np.random.default_rng(3)
    L, S, KV, G, hd, pgs, mp, n_pages = 3, 4, 2, 3, 16, 8, 4, 10
    q = jnp.asarray(rng.normal(size=(S, KV * G, hd)).astype(np.float32))
    nk = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    nv = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    ptab = np.full((S, mp), n_pages, np.int32)
    ptab[0, :3] = [5, 1, 7]
    ptab[1, :1] = [2]
    ptab[2] = [9, 3, 4, 6]
    ptab = jnp.asarray(ptab)
    lengths = jnp.asarray([20, 5, 32, 0], jnp.int32)
    ck = _stacked_pool(rng, quant, L, n_pages, pgs, KV, hd, ptab)
    cv = _stacked_pool(rng, quant, L, n_pages, pgs, KV, hd, ptab)

    @jax.jit
    def run(li):
        if quant:
            return paged_decode_attention_append_quant(
                q, nk, nv, ck["pages"], ck["scales"], cv["pages"],
                cv["scales"], ptab, lengths, li, q_per_kv=G, interpret=True)
        return paged_decode_attention_append(
            q, nk, nv, ck["pages"], cv["pages"], ptab, lengths, li,
            q_per_kv=G, interpret=True)

    out = run(jnp.int32(layer))
    ref = decode_attention_append(
        q, nk, nv, kvcache.gather_all_rows(kvcache.layer(ck, layer)),
        kvcache.gather_all_rows(kvcache.layer(cv, layer)), lengths, G)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-4, rtol=3e-4)


def _same(a, b):
    """Elementwise a == b with NaN equal to NaN."""
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype.kind != "f":
        return a == b
    return (a == b) | (np.isnan(a) & np.isnan(b))


@pytest.mark.parametrize("quant,hd,rule", [
    (False, 128, False), (False, 64, False), (False, 128, True),
    (False, 64, True), (True, 128, False), (True, 128, True)],
    ids=["float-ring", "float-walk", "float-ring-rule", "float-walk-rule",
         "int8-walk", "int8-rule"])
def test_paged_kernels_touch_only_pages_a_live_slot_holds(quant, hd, rule,
                                                          monkeypatch):
    """Both kernel variants (interpret mode) over a table of 6 pages (no
    power of two), on the driver the shapes select: the ring for float
    pages of 128 lanes, the walk for heads of 64 and for int8; called
    directly or, for "rule", through
    models/llama.py::_decode_attend_write. Every page no live slot holds
    is NaN (for int8: its scales), page-table entries past a slot's
    length are unallocated or stale, and two slots are inactive (write
    position C). Live slots — empty, one row, exactly one turn of the
    ring (the walk: one page), one row past it, C - 1 — equal
    decode_attention_append over their gathered rows; an inactive slot's
    output is finite and its K/V write drops."""
    import dataclasses
    from functools import partial

    from localai_tpu.ops.attention import decode_attention_append
    from localai_tpu.ops.pallas import paged_attention as pa

    rng = np.random.default_rng(7)
    L, KV, G, pgs, mp, li = 2, 2, 2, 8, 6, 1
    C = mp * pgs
    ring = pa.ring_takes(KV, hd, jnp.int8 if quant else jnp.float32)
    assert ring == (hd == 128 and not quant)
    turn = pgs * (2 if ring else 1)
    write_at = np.asarray([0, 1, turn, turn + 1, C - 1, C, 21, C], np.int32)
    S = len(write_at)
    n_pages = S * mp + 3
    read = np.where(write_at >= C, 0, write_at)
    # held entries: a shuffled table; past them the sentinel or a stale id
    ptab = rng.permutation(n_pages)[:S * mp].astype(np.int32).reshape(S, mp)
    held = np.zeros((n_pages,), bool)
    for s in range(S):
        n = -(-int(read[s]) // pgs)
        held[ptab[s, :n]] = True
        ptab[s, n:] = np.where(rng.random(mp - n) < 0.5, n_pages,
                               rng.integers(0, n_pages, mp - n))
    ptab = jnp.asarray(ptab)
    clean_k = _stacked_pool(rng, quant, L, n_pages, pgs, KV, hd, ptab)
    clean_v = _stacked_pool(rng, quant, L, n_pages, pgs, KV, hd, ptab)

    def unheld_nan(c):
        leaf = "scales" if quant else "pages"
        keep = held.reshape((1, -1) + (1,) * (c[leaf].ndim - 2))
        return {**c, leaf: jnp.where(keep, c[leaf], jnp.nan)}

    ck, cv = unheld_nan(clean_k), unheld_nan(clean_v)
    q = jnp.asarray(rng.normal(size=(S, KV * G, hd)).astype(np.float32))
    nk = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    nv = jnp.asarray(rng.normal(size=(S, KV, hd)).astype(np.float32))
    ref = decode_attention_append(
        q, nk, nv, kvcache.gather_all_rows(kvcache.layer(clean_k, li)),
        kvcache.gather_all_rows(kvcache.layer(clean_v, li)),
        jnp.asarray(read), G)

    if rule:
        cfg = dataclasses.replace(
            llama.LlamaConfig(
                vocab_size=64, hidden_size=KV * G * hd, intermediate_size=64,
                num_layers=L, num_heads=KV * G, num_kv_heads=KV,
                max_position_embeddings=C),
            attn=llama.AttnTarget(pallas=True))
        for name in ("paged_decode_attention_append",
                     "paged_decode_attention_append_quant"):
            monkeypatch.setattr(pa, name,
                                partial(getattr(pa, name), interpret=True))
        out, wk, wv = jax.jit(
            lambda *a: llama._decode_attend_write(*a, cfg))(
            q, nk, nv, ck, cv, jnp.int32(li), jnp.asarray(write_at))
        # the row write: one row a slot in range whose page the table
        # names, in layer li alone
        rows = {(li, int(ptab[s, w // pgs]), w % pgs)
                for s, w in enumerate(write_at)
                if w < C and ptab[s, w // pgs] < n_pages}
        for before, after in ((ck, wk), (cv, wv)):
            for leaf in [k for k in before if k != "ptab"]:
                same = _same(before[leaf], after[leaf])
                changed = set(zip(*np.nonzero(
                    ~same.reshape(same.shape[:3] + (-1,)).all(-1))))
                assert changed == rows, (leaf, changed ^ rows)
    else:
        tail = (ptab, jnp.asarray(pa.read_lengths(jnp.asarray(write_at), C)),
                jnp.int32(li))
        if quant:
            out = jax.jit(partial(pa.paged_decode_attention_append_quant,
                                  q_per_kv=G, interpret=True))(
                q, nk, nv, ck["pages"], ck["scales"], cv["pages"],
                cv["scales"], *tail)
        else:
            out = jax.jit(partial(pa.paged_decode_attention_append,
                                  q_per_kv=G, interpret=True))(
                q, nk, nv, ck["pages"], cv["pages"], *tail)
    out = np.asarray(out)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, np.asarray(ref), atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("kv_heads,head_dim,dtype,ring", [
    # the cells: mistral7b / the Nemo cells, olmo-hybrid (30 heads
    # stored as 32), and a tp=4 shard's share of each
    (8, 128, jnp.bfloat16, True), (32, 128, jnp.bfloat16, True),
    (2, 128, jnp.bfloat16, True), (4, 128, jnp.bfloat16, True),
    (8, 128, jnp.float32, True), (16, 256, jnp.bfloat16, True),
    # int8 pages, whole or a shard: their scales fill no tile
    (8, 128, jnp.int8, False), (32, 128, jnp.int8, False),
    (2, 128, jnp.int8, False),
    (8, 64, jnp.bfloat16, False),       # TinyLlama's heads: half the lanes
    (30, 128, jnp.bfloat16, False),     # the hybrid's heads unpadded
    (1, 128, jnp.bfloat16, False),      # one KV head a shard
    (8, 128, jnp.float16, False),
])
def test_ring_takes_what_mosaic_can_copy(kv_heads, head_dim, dtype, ring):
    """The driver follows from the operands' shapes (no option, no
    model's name): tests/test_tpu_compile.py compiles both sides of
    this table for a v5e."""
    from localai_tpu.ops.pallas import paged_attention as pa

    assert pa.ring_takes(kv_heads, head_dim, dtype) == ring


@pytest.mark.parametrize("tp", [2, 4])
def test_ring_on_a_mesh_copies_its_shard_of_the_pool(tp):
    """The ring's own DMA under shard_map (models/llama.py::_on_mesh),
    through _decode_attend_write on a tp mesh of CPU devices, kernels in
    the strict TPU interpreter: 8 KV heads, 4 or 2 a shard, the pool
    split over tp and left in place; parity.py's data (shuffled table,
    inactive slots, every page no live slot holds NaN)."""
    import dataclasses

    from jax.experimental.pallas import tpu as pltpu

    from localai_tpu.ops.attention import decode_attention_append
    from localai_tpu.ops.pallas import paged_attention as pa, parity
    from localai_tpu.parallel import mesh as meshlib

    slots, mp, pgs, (KV, G, hd) = 4, 6, 8, (8, 2, 128)
    rng = np.random.default_rng(31)
    (lc, lv), (lc32, lv32) = parity._paged_kv(rng, jnp.float32, KV, hd, pgs,
                                              slots, mp)
    q, nk, nv = (jnp.asarray(parity._bf16_exact(rng, (slots, n, hd)))
                 for n in (KV * G, KV, KV))
    write_at = parity._lengths(pgs, slots, mp)
    read = pa.read_lengths(write_at, mp * pgs)
    assert pa.ring_takes(KV // tp, hd, jnp.float32)
    mesh = meshlib.make_mesh(meshlib.MeshPlan(tp=tp), jax.devices()[:tp])
    cfg = dataclasses.replace(
        llama.LlamaConfig(vocab_size=64, hidden_size=KV * G * hd,
                          intermediate_size=64, num_layers=2,
                          num_heads=KV * G, num_kv_heads=KV,
                          max_position_embeddings=mp * pgs),
        attn=llama.AttnTarget(pallas=True, mesh=mesh))
    ck, cv = (kvcache.device_put(
        {"pages": parity._stacked(parity._unheld_nan(c["pages"], c["ptab"],
                                                     read, pgs)),
         "ptab": c["ptab"]}, mesh, (None, None, None, "tp", None))
        for c in (lc, lv))
    with pltpu.force_tpu_interpret_mode():
        out = jax.jit(lambda *a: llama._decode_attend_write(*a, cfg)[0])(
            q, nk, nv, ck, cv, jnp.int32(1), write_at)
    ref = decode_attention_append(
        q, nk, nv, kvcache.gather_all_rows(lc32),
        kvcache.gather_all_rows(lv32), read, G)
    assert parity._max_err(out, ref) <= 3e-4


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_decode_step_writes_only_its_rows(dtype):
    """One decode step on a paged cache changes, in each layer, the one
    row of each active slot and NOTHING else: the other layers' pages,
    unallocated pages, inactive slots' rows (column C: page n_pages must
    drop, never wrap into layer li + 1) and a row whose page the table
    does not name stay bit-identical."""
    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
        num_heads=4, num_kv_heads=2, max_position_embeddings=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(4)
    S, pgs, mp, n_pages = 4, 8, 4, 12
    ptab = np.full((S, mp), n_pages, np.int32)
    ptab[0, :3] = [5, 1, 7]
    ptab[1, :1] = [2]
    ptab[2, :2] = [9, 3]        # row 16 sits in a page it does not own
    ptab[3] = [0, 4, 6, 8]
    ptab = jnp.asarray(ptab)
    lengths = jnp.asarray([20, 5, 16, 31], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    hd = cfg.head_dim_
    ck = _stacked_pool(rng, dtype == jnp.int8, cfg.num_layers, n_pages, pgs,
                       cfg.num_kv_heads, hd, ptab)
    cv = _stacked_pool(rng, dtype == jnp.int8, cfg.num_layers, n_pages, pgs,
                       cfg.num_kv_heads, hd, ptab)
    if dtype != jnp.int8:
        ck["pages"], cv["pages"] = (c["pages"].astype(dtype)
                                    for c in (ck, cv))
    tokens = jnp.asarray([3, 9, 27, 11], jnp.int32)
    _, nk, nv = jax.jit(lambda *a: llama.engine_decode(params, cfg, *a))(
        tokens, lengths, active, ck, cv)
    written = {(7, 20 % pgs), (2, 5 % pgs)}     # slots 0 and 1 only
    for before, after in ((ck, nk), (cv, nv)):
        for leaf in [k for k in before if k != "ptab"]:
            b, a = np.asarray(before[leaf]), np.asarray(after[leaf])
            changed = {(li, pg, off)
                       for li, pg, off in zip(*np.nonzero(
                           (b != a).reshape(b.shape[:3] + (-1,)).any(-1)))}
            assert changed == {(li, pg, off)
                               for li in range(cfg.num_layers)
                               for pg, off in written}, (leaf, changed)


def test_cow_divergence_preserves_source_rows(tiny_cfg_params):
    """After sharing a prefix and cloning the boundary page, writes into
    the clone must not leak into the source slot's view."""
    cfg, _ = tiny_cfg_params
    S, C, pgs = 2, 32, 8
    shape = (cfg.num_layers, S, C, cfg.num_kv_heads, cfg.head_dim_)
    pool = PagePool(S, C, pgs)
    pc = kvcache.init_paged(shape, jnp.float32, pgs)
    rng = np.random.default_rng(2)
    rows = jnp.asarray(rng.normal(size=(cfg.num_layers, C, cfg.num_kv_heads,
                                        cfg.head_dim_)).astype(np.float32))
    pool.ensure(0, 20)
    pc = kvcache.with_page_table(pc, jnp.asarray(pool.ptab))
    pc = kvcache.tree_slot_update(pc, 0, rows)      # slot 0: rows [0, 20)+
    # share 20 rows into slot 1: 2 full pages + boundary clone of page 2
    shared = pool.share(0, 1, 20)
    assert shared == 16
    src_page = int(pool.ptab[0, 2])
    new = pool.alloc_detached()
    pc = kvcache.with_page_table(pc, jnp.asarray(pool.ptab))
    pc = kvcache.clone_page(pc, src_page, new)
    pool.adopt(1, new)
    pc = kvcache.with_page_table(pc, jnp.asarray(pool.ptab))
    # slot 1 diverges at row 17
    div = jnp.asarray(rng.normal(size=(cfg.num_layers, cfg.num_kv_heads,
                                       cfg.head_dim_)).astype(np.float32))
    pc = kvcache.scatter_prefill(pc, 0, jnp.asarray([[1]], jnp.int32),
                                 jnp.asarray([[17]], jnp.int32),
                                 div[0][None, None])
    s0 = np.asarray(kvcache.slot_rows(pc, 0))
    s1 = np.asarray(kvcache.slot_rows(pc, 1))
    np.testing.assert_array_equal(s0[:, :20], np.asarray(rows)[:, :20])
    np.testing.assert_array_equal(s1[:, :17], np.asarray(rows)[:, :17])
    np.testing.assert_array_equal(s1[0, 17], np.asarray(div)[0])
    assert not np.array_equal(s1[0, 17], s0[0, 17])


# ---------- engine e2e ----------

class _Tok:
    eos_token_id = 0

    def decode(self, ids, **kw):
        return "".join(chr(97 + (i % 26)) for i in ids)

    def convert_ids_to_tokens(self, ids):
        return [chr(97 + (i % 26)) for i in ids]


def _engine(cfg, params, layout, page_size=16, mesh=None, slots=2):
    e = eng.Engine(
        cfg, params, _Tok(),
        eng.EngineConfig(num_slots=slots, max_context=128,
                         prefill_buckets=(16, 64), prefill_chunk=64,
                         cache_dtype=jnp.float32, kv_layout=layout,
                         kv_page_size=page_size),
        mesh=mesh)
    e.start()
    return e


def _greedy(e, ids, n=8):
    _, evs = e.generate_text(eng.GenRequest(
        prompt_ids=list(ids), max_new_tokens=n, ignore_eos=True,
        params=sampling.SamplingParamsHost(temperature=0.0)))
    return eng.event_ids(evs)


def test_engine_paged_matches_contiguous_greedy(tiny_cfg_params):
    """Exact greedy token parity through the REAL engine (chunked
    prefill + burst decode + sampling), paged vs contiguous."""
    cfg, params = tiny_cfg_params
    prompt = [int(x) for x in
              np.random.default_rng(3).integers(1, 120, size=40)]
    e1 = _engine(cfg, params, "contiguous")
    try:
        ref = _greedy(e1, prompt)
    finally:
        e1.shutdown()
    e2 = _engine(cfg, params, "paged")
    try:
        assert e2.metrics()["kv_layout"] == "paged"
        got = _greedy(e2, prompt)
    finally:
        e2.shutdown()
    assert got == ref


@pytest.mark.parametrize("draft", ["0", "ngram"])
def test_kv_walk_counts_the_pages_live_rows_hold(tiny_cfg_params, draft):
    """/debug/state's kv_walk: pages_grid is num_slots x max_pages for
    every decode step that ran, pages_live the pages its rows' lengths
    span. One request of 40 + 8 tokens holds 3 pages of 16 rows on every
    step; under n-gram speculation it is a spec row on every tick, and
    the rounds counted are those in which it had no draft and took the
    decode step (counted when the tick is folded: ISSUE 34)."""
    cfg, params = tiny_cfg_params
    e = eng.Engine(
        cfg, params, _Tok(),
        eng.EngineConfig(num_slots=2, max_context=128,
                         prefill_buckets=(16, 64), prefill_chunk=64,
                         cache_dtype=jnp.float32, kv_layout="paged",
                         kv_page_size=16, draft=draft))
    e.start()
    try:
        assert e.state_snapshot()["kv_walk"] == {"pages_live": 0,
                                                 "pages_grid": 0}
        _greedy(e, [int(x) for x in
                    np.random.default_rng(3).integers(1, 120, size=40)])
        walk = e.state_snapshot()["kv_walk"]
        spec = e.state_snapshot()["spec"]["dispatches"]
    finally:
        e.shutdown()
    steps, rest = divmod(walk["pages_grid"], 2 * (128 // 16))
    assert steps > 0 and rest == 0
    assert walk["pages_live"] == 3 * steps
    assert (spec == 0) == (draft == "0")


@pytest.mark.parametrize("dtype, prompt_seed, near_tie", [
    (jnp.bfloat16, 5, False),
    (jnp.float32, 4, False),
    (jnp.bfloat16, 4, True),
])
def test_engine_paged_matches_contiguous_on_mesh(tiny_cfg_params, dtype,
                                                 prompt_seed, near_tie):
    """Same parity under the 8-device dryrun mesh (dp=2, tp=4). Since
    ISSUE 34 an undrafted row's token comes from the decode step, which
    the two layouts run through different code (write-then-attend
    against gather-and-append); before it the verify pass, one code for
    both layouts, emitted every token. The first bfloat16 prompt is one
    on which the layouts agree with speculation on and off, before
    ISSUE 34 and after; float32 holds the prompt the test had, byte for
    byte. On that prompt at bfloat16 the two decode steps break a tie of
    these tiny weights differently (second token 95 against 50, with
    draft: 0 on the tree before ISSUE 34 too): there the streams must
    agree up to the first difference and the two tokens chosen at it
    must lie within one bfloat16 step of each other in log-probability
    (they read 0.0014 apart; a step is 2**-6 between 2 and 4)."""
    import dataclasses

    from localai_tpu.parallel import mesh as meshlib
    from localai_tpu.parallel.sharding import shard_params

    cfg = dataclasses.replace(tiny_cfg_params[0], dtype=dtype)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    mesh = meshlib.make_mesh(meshlib.MeshPlan(dp=2, tp=4),
                             devices=jax.devices()[:8])
    prompt = [int(x) for x in
              np.random.default_rng(prompt_seed).integers(1, 120, size=24)]

    def run(layout):
        sharded = shard_params(mesh, params, cfg.tie_word_embeddings)
        e = _engine(cfg, sharded, layout, mesh=mesh, slots=4)
        try:
            _, evs = e.generate_text(eng.GenRequest(
                prompt_ids=list(prompt), max_new_tokens=6, ignore_eos=True,
                params=sampling.SamplingParamsHost(temperature=0.0)))
        finally:
            e.shutdown()
        lps = [lp for ev in evs if ev.token_ids or ev.token_id >= 0
               for lp in (ev.logprobs or [ev.logprob])]
        return eng.event_ids(evs), lps

    ref, ref_lps = run("contiguous")
    got, got_lps = run("paged")
    if not near_tie:
        assert got == ref
        return
    assert len(got) == len(ref) == len(got_lps) == len(ref_lps) == 6
    k = next((j for j in range(6) if got[j] != ref[j]), None)
    if k is not None:
        assert abs(got_lps[k] - ref_lps[k]) < 2 ** -6, (k, got, ref)


def test_shared_prefix_zero_copy_refcounts(tiny_cfg_params):
    """Two CONCURRENT requests sharing a page-aligned system prefix: the
    second admission points its table at the first one's pages (refcount
    2) with ZERO KV row copies — no fork body, no page clone."""
    cfg, params = tiny_cfg_params
    pgs = 16
    sys_prefix = [int(x) for x in
                  np.random.default_rng(5).integers(1, 120, size=2 * pgs)]
    e = _engine(cfg, params, "paged", page_size=pgs)
    try:
        ra = eng.GenRequest(prompt_ids=sys_prefix + [121, 122],
                            max_new_tokens=48, ignore_eos=True,
                            params=sampling.SamplingParamsHost(temperature=0.0))
        out_a = e.submit(ra)
        first = out_a.get()            # A's prefill committed, decoding
        assert first is not None and first.error is None
        rb = eng.GenRequest(prompt_ids=sys_prefix + [123, 124],
                            max_new_tokens=4, ignore_eos=True,
                            params=sampling.SamplingParamsHost(temperature=0.0))
        evs_b = []
        for ev in e.generate(rb):
            evs_b.append(ev)
        # B reused A's prefix via page sharing
        assert evs_b[-1].timings["reused_prompt_tokens"] >= 2 * pgs
        # zero row copies: both shared pages are ref-count shared (A's
        # table + B's, plus — since PR 2 — a prefix-cache retention hold
        # once B released), and neither the fork body nor the COW clone
        # ever compiled/ran
        pool = e._pool
        slot_b = next(i for i, t in enumerate(e._cache_tokens)
                      if t[:len(sys_prefix)] == sys_prefix
                      and t[len(sys_prefix):len(sys_prefix) + 2] == [123, 124])
        assert pool.page_refs(slot_b, 0) >= 2
        assert pool.page_refs(slot_b, 1) >= 2
        assert e._pcache is not None and e._pcache.pages_held >= 2
        assert "page_clone" not in e._fork_fns
        assert "main" not in e._fork_fns
        m = e.metrics()
        assert m["kv_pages_shared"] >= 2
        # drain A
        while out_a.get() is not None:
            pass
    finally:
        e.shutdown()


def test_paged_pool_never_exceeds_contiguous_reservation(tiny_cfg_params):
    """Default pool sizing: paged HBM <= the old S * max_context rows."""
    cfg, params = tiny_cfg_params
    e = _engine(cfg, params, "paged")
    try:
        S, C = e.ecfg.num_slots, e.ecfg.max_context
        rows_paged = e.ck["pages"].shape[1] * e.ck["pages"].shape[2]
        assert rows_paged <= S * C
        assert kvcache.shape(e.ck) == (cfg.num_layers, S, C,
                                       cfg.num_kv_heads, cfg.head_dim_)
    finally:
        e.shutdown()


# ---- recurrent-state leaves beside the pages (module doc, point 3) ----

def _state_caches(family):
    """A family's own ``init_cache`` at toy width: (cache_k, its state
    leaves' names)."""
    if family == "olmo_hybrid":
        from localai_tpu.models import olmo_hybrid as fam

        cfg = fam.OlmoHybridConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=4,
            num_heads=2, num_kv_heads=2, linear_heads=2, linear_key_dim=8,
            linear_value_dim=8, dtype=jnp.float32)
        names = {"delta", "conv"}
    else:
        from localai_tpu.models import granite_hybrid as fam

        cfg = fam.GraniteHybridConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
            period=("mamba", "mamba", "attention"), num_heads=2,
            num_kv_heads=2, ssm_heads=2, ssm_head_dim=32, ssm_state=16,
            dtype=jnp.float32)
        names = {"ssm", "conv"}
    ck, _cv = fam.init_cache(cfg, 3, 32, jnp.float32, page_size=8)
    return ck, names


def _rebuilt(helper, ck):
    pages = ck["pages"]
    L, n_pages, pg, kv, hd = pages.shape
    if helper == "with_page_table":
        return kvcache.with_page_table(
            ck, jnp.arange(12, dtype=jnp.int32).reshape(3, 4))
    if helper == "scatter_prefill":
        return kvcache.scatter_prefill(
            ck, 0, jnp.zeros((1, 2), jnp.int32),
            jnp.arange(2, dtype=jnp.int32)[None], jnp.ones((1, 2, kv, hd)))
    if helper == "scatter_ragged":
        return kvcache.scatter_ragged(
            ck, 0, jnp.zeros((2,), jnp.int32), jnp.arange(2, dtype=jnp.int32),
            jnp.ones((2, kv, hd)))
    if helper == "scatter_pages":
        return kvcache.scatter_pages(
            ck, jnp.asarray([1], jnp.int32), jnp.ones((L, 1, pg, kv, hd)))
    if helper == "tree_slot_update":
        return kvcache.tree_slot_update(ck, 1, jnp.ones((L, 32, kv, hd)))
    assert helper == "clone_page"
    return kvcache.clone_page(ck, 0, 1)


@pytest.mark.parametrize("helper", ["with_page_table", "scatter_prefill",
                                    "scatter_ragged", "scatter_pages",
                                    "tree_slot_update", "clone_page"])
@pytest.mark.parametrize("family", ["olmo_hybrid", "granite_hybrid"])
def test_page_helpers_carry_whatever_state_leaves_a_family_names(family,
                                                                 helper):
    """The state leaves are what the family's ``init_cache`` returned
    beside pages / ptab; every helper that rebuilds the paged dict hands
    the very same arrays on, and ``state_bytes`` sums them."""
    ck, names = _state_caches(family)
    ck = {k: (v + 1 if k in names else v) for k, v in ck.items()}
    leaves = kvcache.state_leaves(ck)
    assert set(leaves) == names
    assert kvcache.state_bytes(ck) == sum(
        a.size * a.dtype.itemsize for a in leaves.values()) > 0
    assert kvcache.state_layers(ck) == leaves["conv"].shape[0]
    out = _rebuilt(helper, ck)
    assert set(kvcache.state_leaves(out)) == names
    for k in names:
        assert out[k] is ck[k]
    assert kvcache.state_bytes(out) == kvcache.state_bytes(ck)
    # K/V views come without them
    assert set(kvcache.layer(out, 0)) == {"pages", "ptab"}


def test_a_cache_of_rows_alone_has_no_state():
    ck = kvcache.init_paged((2, 3, 32, 2, 8), jnp.float32, 8)
    assert kvcache.state_leaves(ck) == {} and kvcache.state_bytes(ck) == 0
    assert kvcache.state_layers(ck) == 0
    assert kvcache.state_bytes(jnp.zeros((2, 3, 32, 2, 8))) == 0
