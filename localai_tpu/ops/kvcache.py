"""KV-cache representations: quantized (int8) and PAGED layouts.

Two orthogonal axes of representation, both expressed as pytrees so the
engine's jitted bodies stay shape-stable and donation-friendly:

1. QUANTIZED (int8, per-row-per-head scales) — see below.
2. PAGED (Ragged Paged Attention, PAPERS.md arxiv 2604.15464): instead
   of one contiguous [L, S, C, KV, hd] reservation, KV rows live in a
   shared PAGE POOL

       {"pages": [L, n_pages, page_size, KV, hd],
        "ptab":  int32 [S, max_pages]}            (+ "scales" when int8)

   with a per-slot page table mapping logical row c of slot s to
   physical row ``ptab[s, c // page_size] * page_size + c % page_size``.
   Unallocated table entries hold the sentinel ``n_pages`` so gathers
   fill zeros and scatters drop (mode="drop") — the same OOB discipline
   the contiguous layout uses for inactive slots. The page table rides
   INSIDE the cache pytree: every jitted engine body (bursts, prefill,
   fused admission, restore) is layout-agnostic — the host allocator
   (engine/paging.py) mutates its numpy mirror and commits it as a new
   ``ptab`` leaf before dispatch. Logical shape() stays
   [L, S, max_pages*page_size, KV, hd], so capacity math is unchanged.

   Why: HBM is reserved for actual rows (lazily, page granularity)
   instead of worst-case per slot, and a shared prompt prefix is
   REF-COUNTED page sharing instead of a row copy (copy-on-write: the
   first divergent page is cloned, see clone_page / engine admission).

3. RECURRENT STATE beside the pages (models/olmo_hybrid.py,
   models/granite_hybrid.py): a family in which only some layers have K/V
   rows keeps, for the others, a fixed-size state per slot as further
   leaves of the paged ``cache_k`` dict, with "pages" then holding the
   attention layers alone. WHICH leaves is the family's to say: whatever
   its ``init_cache`` returns in ``cache_k`` beside "pages", "ptab" and
   "scales" is a state leaf (``state_leaves``), shaped ``[layers of that
   kind, S, ...]`` - olmo_hybrid's "delta" [L_lin, S, H, K, V] float32 and
   "conv" [L_lin, S, 3, Ch], granite_hybrid's "ssm" [L_ssm, S, H, P, N]
   float32 and "conv". Every function here that rebuilds a paged dict
   from an old one (with_page_table, scatter_*, clone_page) carries any
   such leaf through untouched; ``layer`` and the gathers return K/V views
   without them; ``state_bytes`` sums them. They are written only by the
   family's own programs: zeroed by a prefill segment that starts at
   position 0, untouched for an inactive slot. No page helper reads them,
   which is why such a family declares no prefix reuse (engine.py): a page
   of K/V without the state at its boundary cannot be resumed from. There
   is no sharding rule for them: such a family does not declare "mesh",
   and the runner and the engine refuse it one.

Quantized representation (int8, per-row-per-head scales).

`kv_cache_dtype: int8` in the model YAML (reference analogue: llama.cpp's
`cache-type-k q8_0`, plumbed via backend.proto ModelOptions and vLLM's
kv_cache_dtype knob, /root/reference/backend/python/vllm/backend.py:92-111)
switches the engine cache from a plain bf16 array to this pytree:

    {"q": int8 [L, S, C, KV, hd], "s": float32 [L, S, C, KV]}

i.e. symmetric int8 with one scale per (layer, slot, position, kv-head),
quantized over head_dim. At hd=128 the scale overhead is 4/128 = 3%, so
the cache shrinks ~1.94x vs bf16 — which is the whole point: decode on
one chip is HBM-bandwidth-bound and slot count is capped by KV size, so
halving the KV doubles the concurrent slots the weight read amortizes
over (VERDICT r4 headline math).

TPU-first numerics: the scales NEVER produce a dequantized cache tensor.
Attention folds them outside the contraction —
    scores[s,kv,g,c] = (q . k_q[c]) * s_k[s,c,kv]         (per-key logit scale)
    out = einsum(probs * s_v[s,c,kv], v_q)                 (scale into probs)
— so the MXU consumes the int8 rows cast in-register (the same fusion the
int8 weight path relies on, models/llama.py:_mat) and HBM reads stay 1
byte/element. See ops/attention.py for the score-side folding.
"""

from __future__ import annotations

import hashlib
from typing import Any, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

Cache = Union[jax.Array, dict]

_EPS = 1e-8

# ---------- page identity hashing (cross-release prefix cache) ----------
#
# The engine's PrefixPageCache (engine/prefix_cache.py) indexes committed
# FULL pages by a chained block hash so a released slot's prompt-prefix
# pages stay findable after the slot is gone. The hash lives here, next
# to the layout it names, because it IS part of the page representation
# contract: a page's identity is (scope, parent chain, its token ids) —
# never its float content, which is not bit-stable across dtypes/meshes.

PAGE_HASH_BYTES = 16
PAGE_HASH_ROOT = b"\x00" * PAGE_HASH_BYTES


def page_scope(page_size: int, *parts) -> bytes:
    """Scope token for a page-hash chain: page size + any model-identity
    parts (family, layer/head geometry, cache dtype, tokenizer id...).
    Two engines whose scopes differ can NEVER alias each other's chains —
    the scope is folded into every link, so a different tokenization or
    page layout diverges at the first hash."""
    text = "|".join([f"pg={int(page_size)}"] + [str(p) for p in parts])
    return hashlib.blake2b(text.encode("utf-8"),
                           digest_size=PAGE_HASH_BYTES).digest()


def page_chain_hash(parent: bytes, token_ids, scope: bytes) -> bytes:
    """hash(scope, parent, page_token_ids) — one link of the chained
    block hash. parent is PAGE_HASH_ROOT for the first page. Token ids
    are hashed as int64 so the digest is independent of the caller's
    container (list / np array) and of numpy's default int width."""
    h = hashlib.blake2b(digest_size=PAGE_HASH_BYTES)
    h.update(scope)
    h.update(parent)
    h.update(np.asarray(token_ids, np.int64).tobytes())
    return h.digest()


_PAGE_LEAVES = ("pages", "ptab", "scales")


def state_leaves(cache: Any) -> dict:
    """The per-slot recurrent-state leaves of a paged cache dict: what
    the family's ``init_cache`` put there beside the page pool (module
    doc, point 3). Empty for a cache of K/V rows alone."""
    if not is_paged(cache):
        return {}
    return {k: v for k, v in cache.items() if k not in _PAGE_LEAVES}


def state_bytes(cache: Any) -> int:
    """Device bytes a cache holds as per-slot recurrent state (0 for a
    cache of K/V rows alone)."""
    return int(sum(a.size * a.dtype.itemsize
                   for a in state_leaves(cache).values()))


def state_layers(cache: Any) -> int:
    """Layers that keep a recurrent state a slot (their leaves' leading
    axis; 0 for a cache of K/V rows alone)."""
    return max((a.shape[0] for a in state_leaves(cache).values()), default=0)


def wants_quant(dtype) -> bool:
    """True when the configured cache dtype selects the int8 representation."""
    return dtype == jnp.int8


def is_paged(cache: Any) -> bool:
    """True for the page-pool layout (full cache or single-layer view)."""
    return isinstance(cache, dict) and "ptab" in cache


def is_quant(cache: Any) -> bool:
    """True when rows are stored int8 with folded scales — for BOTH the
    contiguous {"q","s"} pytree and the paged {"pages","scales","ptab"}."""
    return isinstance(cache, dict) and ("q" in cache or "scales" in cache)


def init(shape: Tuple[int, ...], dtype) -> Cache:
    """Zeros cache of the given logical shape; int8 -> quantized pytree."""
    if wants_quant(dtype):
        return {"q": jnp.zeros(shape, jnp.int8),
                "s": jnp.zeros(shape[:-1], jnp.float32)}
    return jnp.zeros(shape, dtype)


def init_paged(shape: Tuple[int, ...], dtype, page_size: int,
               num_pages: int = 0) -> Cache:
    """Page-pool cache for logical shape [L, S, C, KV, hd].

    C must be a page_size multiple; max_pages = C // page_size. num_pages
    defaults to S * max_pages — exactly the old contiguous reservation,
    never more (callers shrink it to realize HBM savings). The page table
    starts all-sentinel (nothing allocated)."""
    L, S, C, KV, hd = shape
    if C % page_size:
        raise ValueError(f"max_context {C} not a multiple of page_size "
                         f"{page_size}")
    mp = C // page_size
    np_ = num_pages or S * mp
    ptab = jnp.full((S, mp), np_, jnp.int32)
    if wants_quant(dtype):
        return {"pages": jnp.zeros((L, np_, page_size, KV, hd), jnp.int8),
                "scales": jnp.zeros((L, np_, page_size, KV), jnp.float32),
                "ptab": ptab}
    return {"pages": jnp.zeros((L, np_, page_size, KV, hd), dtype),
            "ptab": ptab}


def page_size(cache: Cache) -> int:
    return cache["pages"].shape[-3]


def num_phys_pages(cache: Cache) -> int:
    return cache["pages"].shape[-4]


def with_page_table(cache: Cache, ptab) -> Cache:
    """New cache dict with the (host-updated) page table committed."""
    out = dict(cache)
    out["ptab"] = ptab
    return out


def shape(cache: Cache) -> Tuple[int, ...]:
    """LOGICAL shape [L, S, C, KV, hd] — paged caches report
    C = max_pages * page_size so capacity math is layout-agnostic."""
    if is_paged(cache):
        pg = cache["pages"]
        s, mp = cache["ptab"].shape
        return (pg.shape[0], s, mp * pg.shape[-3]) + pg.shape[-2:]
    if is_quant(cache):
        return cache["q"].shape
    return cache.shape


def store_dtype(cache: Cache):
    """The dtype new rows must be cast to before a raw scatter (plain
    caches only; quantized caches go through quantize())."""
    if is_paged(cache):
        return cache["pages"].dtype
    if is_quant(cache):
        return jnp.int8
    return cache.dtype


def _row_index(ptab_rows: jax.Array, pg: int) -> jax.Array:
    """Expand page-table rows [..., MP] to physical row ids [..., MP*pg].
    Sentinel entries expand past the pool — gathers must use mode="fill"."""
    base = ptab_rows[..., :, None] * pg + jnp.arange(pg, dtype=jnp.int32)
    return base.reshape(*ptab_rows.shape[:-1], ptab_rows.shape[-1] * pg)


def _page_of(ptab_rows: jax.Array, cols: jax.Array, pg: int,
             n_pages: int) -> Tuple[jax.Array, jax.Array]:
    """(physical page, in-page offset) for logical columns, vectorized.

    ptab_rows [..., MP] are the owning slots' table rows aligned with
    cols [...]. Out-of-range columns (>= MP*pg, e.g. the drop sentinel
    used for inactive slots) map to page n_pages so scatters drop."""
    mp = ptab_rows.shape[-1]
    pidx = cols // pg
    page = jnp.take_along_axis(
        ptab_rows, jnp.minimum(pidx, mp - 1)[..., None], axis=-1)[..., 0]
    return jnp.where(pidx < mp, page, n_pages), cols % pg


def quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 over the trailing (head_dim) axis.

    x: [..., hd] -> (q int8 [..., hd], s float32 [...]).
    """
    x32 = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1) / 127.0, _EPS)
    q = jnp.clip(jnp.round(x32 / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def dequantize(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    """Materialize float rows (slot-local ops only: prompt-cache export,
    self-extend re-rotation — never the attention hot path)."""
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


def gather_slots(cache: Cache, slot_ids: jax.Array) -> Cache:
    """cache[:, slot_ids] per leaf (continued-prefill row read)."""
    if is_quant(cache):
        return {"q": cache["q"][:, slot_ids], "s": cache["s"][:, slot_ids]}
    return cache[:, slot_ids]


def layer(cache: Cache, li) -> Cache:
    """Select one layer (inside the lax.scan over layers) for a jnp
    reader. There is no inverse: writers (scatter_prefill and its
    callers) update the whole cache at ``[li, ...]`` in place, and the
    Pallas kernels index the stacked pool by layer themselves — a layer
    sliced out of the scan carry for a custom call, or set back into it,
    is a copy of that layer's pool."""
    if is_paged(cache):
        out = {"pages": cache["pages"][li], "ptab": cache["ptab"]}
        if "scales" in cache:
            out["scales"] = cache["scales"][li]
        return out
    if is_quant(cache):
        return {"q": cache["q"][li], "s": cache["s"][li]}
    return cache[li]


def gather_layer_rows(lcache: Cache, slot_ids: jax.Array) -> Cache:
    """lcache[slot_ids] for a single-layer cache [S, C, KV, hd].

    Paged caches materialize the selected slots' logical rows densely
    (page gather with zero fill for unallocated pages) — prefill-path
    only; the decode hot path uses the paged kernel / gather_all_rows."""
    if is_paged(lcache):
        pg = lcache["pages"].shape[-3]
        idx = _row_index(lcache["ptab"][slot_ids], pg)          # [B, C]
        flat = lcache["pages"].reshape((-1,) + lcache["pages"].shape[-2:])
        rows = jnp.take(flat, idx, axis=0, mode="fill", fill_value=0)
        if "scales" in lcache:
            sflat = lcache["scales"].reshape(-1, lcache["scales"].shape[-1])
            return {"q": rows,
                    "s": jnp.take(sflat, idx, axis=0, mode="fill",
                                  fill_value=0)}
        return rows
    if is_quant(lcache):
        return {"q": lcache["q"][slot_ids], "s": lcache["s"][slot_ids]}
    return lcache[slot_ids]


def gather_all_rows(lcache: Cache) -> Cache:
    """Single-layer paged cache -> dense [S, C, KV, hd] rows for every
    slot (the pure-jnp decode fallback used where the Pallas ragged
    kernel is unavailable, e.g. JAX_PLATFORMS=cpu)."""
    if not is_paged(lcache):
        return lcache
    s = lcache["ptab"].shape[0]
    return gather_layer_rows(lcache, jnp.arange(s, dtype=jnp.int32))


def scatter_prefill(cache: Cache, li, rows: jax.Array, cols: jax.Array,
                    new_kv: jax.Array) -> Cache:
    """Batched row scatter: cache[li, rows[b,t], cols[b,t]] = new_kv[b,t]
    — every KV write of the model step (prompt chunks, ragged packs, and
    the decode step's one row per slot as rows/cols [S, 1]).

    cache: full [L, S, C, KV, hd]; rows/cols: [B, T]; new_kv: [B, T, KV, hd].
    A column past the slot's capacity maps to page n_pages, out of range
    on the PAGE axis, so the write drops — it cannot land in layer li+1.
    """
    if is_paged(cache):
        n_pages = cache["pages"].shape[1]
        pg = cache["pages"].shape[-3]
        page, off = _page_of(cache["ptab"][rows], cols, pg, n_pages)
        out = dict(cache)
        if "scales" in cache:
            q, s = quantize(new_kv)
            out["pages"] = cache["pages"].at[li, page, off].set(
                q, mode="drop")
            out["scales"] = cache["scales"].at[li, page, off].set(
                s, mode="drop")
        else:
            out["pages"] = cache["pages"].at[li, page, off].set(
                new_kv.astype(cache["pages"].dtype), mode="drop")
        return out
    if is_quant(cache):
        q, s = quantize(new_kv)
        return {"q": cache["q"].at[li, rows, cols].set(q, mode="drop"),
                "s": cache["s"].at[li, rows, cols].set(s, mode="drop")}
    return cache.at[li, rows, cols].set(
        new_kv.astype(cache.dtype), mode="drop")


def scatter_ragged(cache: Cache, li, slot_of: jax.Array, cols: jax.Array,
                   new_kv: jax.Array) -> Cache:
    """RAGGED packed-prefill scatter: cache[li, slot_of[n], cols[n]] =
    new_kv[n] for a [N]-token pack whose tokens belong to many slots.

    slot_of/cols: [N] int32; new_kv: [N, KV, hd] float. Pad tokens use
    the column sentinel C (paged: any col >= MP*page_size) so the write
    DROPS — the same OOB discipline every other scatter here uses. For
    the paged layout the write goes through each token's own slot's page
    table, i.e. this is the "ragged scatter into the page pool" of the
    packed prefill step (engine.py)."""
    return scatter_prefill(cache, li, slot_of[None], cols[None],
                           new_kv[None])


def tree_slot_update(cache: Cache, dst, new_rows: Cache) -> Cache:
    """cache[:, dst] = new_rows per leaf (fork / restore bodies).

    Paged caches scatter the dense row set into dst's OWN pages via the
    table; rows over unallocated pages are dropped. (Page SHARING is a
    host-side table edit, not a device op — see engine/paging.py.)"""
    if is_paged(cache):
        pg = cache["pages"].shape[-3]
        c = cache["ptab"].shape[1] * pg
        cols = jnp.arange(c, dtype=jnp.int32)
        # cols always < C = MP*pg, so the table lookup is in range; the
        # sentinel entries of unallocated pages drop the writes themselves
        page = jnp.take(cache["ptab"][dst], cols // pg)
        off = cols % pg
        out = dict(cache)
        if "scales" in cache:
            out["pages"] = cache["pages"].at[:, page, off].set(
                new_rows["q"], mode="drop")
            out["scales"] = cache["scales"].at[:, page, off].set(
                new_rows["s"], mode="drop")
        else:
            out["pages"] = cache["pages"].at[:, page, off].set(
                new_rows.astype(cache["pages"].dtype), mode="drop")
        return out
    if is_quant(cache):
        return {"q": cache["q"].at[:, dst].set(new_rows["q"]),
                "s": cache["s"].at[:, dst].set(new_rows["s"])}
    return cache.at[:, dst].set(new_rows)


def clone_page(cache: Cache, src_page, dst_page) -> Cache:
    """Copy one physical page (all layers) — the copy-on-write primitive:
    admission clones the FIRST DIVERGENT page of a shared prefix before
    the new request's prefill writes into it."""
    out = dict(cache)
    out["pages"] = cache["pages"].at[:, dst_page].set(cache["pages"][:, src_page])
    if "scales" in cache:
        out["scales"] = cache["scales"].at[:, dst_page].set(
            cache["scales"][:, src_page])
    return out


def gather_pages(cache: Cache, page_ids: jax.Array) -> Cache:
    """Read whole physical pages [L, n, page_size, KV, hd] (+ scales
    [L, n, page_size, KV] when int8) — the device->host OFFLOAD read.
    Dtype-preserving: int8 pages stay quantized, bf16 stays bf16, so the
    host tier stores the exact device representation. page_ids out of
    range clip (callers pad with repeats and slice host-side)."""
    rows = jnp.take(cache["pages"], page_ids, axis=1, mode="clip")
    if "scales" in cache:
        return {"q": rows,
                "s": jnp.take(cache["scales"], page_ids, axis=1,
                              mode="clip")}
    return rows


def scatter_pages(cache: Cache, page_ids: jax.Array, rows: Cache) -> Cache:
    """Write whole pages back into the pool — the host->device RESTORE
    upload, gather_pages' inverse. rows carries the representation
    gather_pages produced; sentinel page_ids (>= n_pages) DROP, so
    callers pad restore batches to a compiled bucket size."""
    out = dict(cache)
    if "scales" in cache:
        out["pages"] = cache["pages"].at[:, page_ids].set(
            rows["q"], mode="drop")
        out["scales"] = cache["scales"].at[:, page_ids].set(
            rows["s"], mode="drop")
    else:
        out["pages"] = cache["pages"].at[:, page_ids].set(
            rows.astype(cache["pages"].dtype), mode="drop")
    return out


def slot_rows(cache: Cache, slot) -> Cache:
    """cache[:, slot] per leaf -> [L, C, KV, hd] (+ scales)."""
    if is_paged(cache):
        pg = cache["pages"].shape[-3]
        idx = _row_index(cache["ptab"][slot], pg)               # [C]
        # the pool's rows counted out, not inferred: a plane of no layers
        # (ops/mla.py's cache_v) has no size to infer them from
        L, n_rows = cache["pages"].shape[0], cache["pages"].shape[1] * pg
        flat = cache["pages"].reshape(
            (L, n_rows) + cache["pages"].shape[-2:])
        rows = jnp.take(flat, idx, axis=1, mode="fill", fill_value=0)
        if "scales" in cache:
            sflat = cache["scales"].reshape(
                L, n_rows, cache["scales"].shape[-1])
            return {"q": rows,
                    "s": jnp.take(sflat, idx, axis=1, mode="fill",
                                  fill_value=0)}
        return rows
    if is_quant(cache):
        return {"q": cache["q"][:, slot], "s": cache["s"][:, slot]}
    return cache[:, slot]


def where_rows(mask_c: jax.Array, a: Cache, b: Cache) -> Cache:
    """Select rows along the C axis between two row sets [L, C, KV, hd].

    mask_c: [C] bool (True -> a). Scales select with the same row mask.
    """
    if is_quant(a):
        return {"q": jnp.where(mask_c[None, :, None, None], a["q"], b["q"]),
                "s": jnp.where(mask_c[None, :, None], a["s"], b["s"])}
    return jnp.where(mask_c[None, :, None, None], a, b)


def rows_to_float(rows: Cache, dtype) -> jax.Array:
    """[L, C, KV, hd] row set -> dense float (prompt-cache save path)."""
    if is_quant(rows):
        return dequantize(rows["q"], rows["s"], dtype)
    return rows.astype(dtype)


def rows_from_float(rows: jax.Array, like: Cache) -> Cache:
    """Dense float [L, C, KV, hd] -> the cache's ROW representation
    (what tree_slot_update accepts as new_rows)."""
    if is_quant(like):
        q, s = quantize(rows)
        return {"q": q, "s": s}
    return rows.astype(store_dtype(like))


def cache_sharding(mesh, spec5):
    """NamedShardings for the cache under a 5-dim PartitionSpec; the scale
    leaf ([L, S, C, KV]) drops the trailing head_dim entry."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    full = NamedSharding(mesh, P(*spec5))
    scales = NamedSharding(mesh, P(*spec5[:-1]))
    return full, scales


def paged_sharding(mesh, spec5):
    """Paged layout under the same LOGICAL 5-dim spec: pages
    [L, n_pages, page_size, KV, hd] keep the layer and kv-head entries
    (kv heads on tp); the slot/context entries have no physical analogue
    — any slot's rows may live in any page, so the page axis is
    replicated. The page table is replicated (parallel/sharding.py
    page_table_spec): it is tiny and every shard needs all of it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    pspec = (spec5[0], None, None, spec5[3], spec5[4])
    return (NamedSharding(mesh, P(*pspec)),
            NamedSharding(mesh, P(*pspec[:-1])),
            NamedSharding(mesh, P(None, None)))


def device_put(cache: Cache, mesh, spec5) -> Cache:
    from jax.sharding import NamedSharding, PartitionSpec as P

    if is_paged(cache):
        pages_sh, scales_sh, ptab_sh = paged_sharding(mesh, spec5)
        out = {"pages": jax.device_put(cache["pages"], pages_sh),
               "ptab": jax.device_put(cache["ptab"], ptab_sh)}
        if "scales" in cache:
            out["scales"] = jax.device_put(cache["scales"], scales_sh)
        return out
    if is_quant(cache):
        full, scales = cache_sharding(mesh, spec5)
        return {"q": jax.device_put(cache["q"], full),
                "s": jax.device_put(cache["s"], scales)}
    return jax.device_put(cache, NamedSharding(mesh, P(*spec5)))
