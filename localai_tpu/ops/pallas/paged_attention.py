"""Ragged paged decode attention (Pallas/TPU).

Decode KV lives in a shared physical page pool
[L, n_pages, page_size, KV, hd] addressed through a per-slot
page table [S, max_pages]; a mixed-length batch is "ragged" — each slot
touches only the pages its table names (Ragged Paged Attention,
PAPERS.md arxiv 2604.15464). A naive XLA gather materializes a dense
[S, C, KV, hd] copy of the pool every layer of every step; this kernel
reads pages IN PLACE, out of the STACKED pool, and its work follows the
pages that live slots hold. A slot of length 0 (an empty slot, or one
the caller marks inactive: models/llama.py::_decode_attend_write)
fetches no page and computes nothing but its own token; a live slot
computes its own ceil(length / page_size) pages and no other: the
per-head loop run masked over every entry of every slot's page table
was 83-95% of the kernel's time in the benchmark's cells (PERF.md
section 6, PR 31).

Two drivers share the page arithmetic (``_attend_page``, ``_finish``);
``ring_takes`` chooses from the operands' shapes:

  * The ring (``_ring_kernel``): grid (S,), one program a slot. The pool
    stays in HBM (``pl.ANY``); the program loops over the slot's own
    pages and fetches each with ``make_async_copy`` into a ring of two
    VMEM page buffers, one page ahead of the one it computes on (the
    prefetch-ahead-of-decode idea of PRESERVE, arxiv 2501.08192; a
    page's copy is a quarter of its arithmetic, and rings of 4 and 8
    read the same on the chip: PERF.md section 6, PR 31). A slot costs
    its pages and one grid step. Mosaic copies out of an HBM
    array only along whole tiles of its layout, so this driver takes
    float pages whose [KV, hd] face is whole tiles (hd a multiple of
    128; KV 2, 4 or a multiple of 8: compiled for a v5e,
    tests/test_tpu_compile.py).
  * The walk (``_walk_kernel``): grid (S, max_pages), the K/V blocks
    chosen by BlockSpec index maps from the scalar-prefetched page
    table, so the grid pipeline double-buffers them. Entries past a
    slot's last page are remapped to that page (the same block again:
    the pipeline skips the DMA; a slot of length 0 names physical page
    0 whatever its table says) and their programs skip the per-head
    loop, but each still costs a grid step: about 0.2 us, 0.2 ms a
    layer at 16 slots x 64 pages with no slot live (PERF.md section 6,
    PR 31). It takes every shape, and the int8 cache: the int8 scales
    [Pg, KV] f32 have 8 of a tile's 128 lanes and cannot ride the ring.

The page table, the lengths and the layer index are SCALAR-PREFETCH
arguments. The layer is the leading coordinate of every copy and block,
so the kernel's operand is the pool itself, with no slice in front of
it: handed ``pool[layer]`` XLA copied that layer's pool out of the
carry and wrote it back after the row scatter, every layer of every
step (PERF.md section 6, PR 27).

Softmax is accumulated online across pages (m/l/acc VMEM scratch), one
update a page, in f32; the current token's own k/v is appended from
registers after the last page, matching
ops/attention.py::decode_attention_append — the jnp fallback used on
CPU (kvcache.gather_all_rows) and the parity reference in tests.

With int8 pages the per-(row, kv-head) scales are folded OUTSIDE the
contraction — scores for K, probs for V — exactly the fold
ops/attention.py::_split_cache does on the jnp path, so HBM reads stay
1 byte/element on the decode hot path instead of falling back to the
dense gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def ring_takes(kv_heads: int, head_dim: int, dtype) -> bool:
    """Whether the kernel can copy the pool's pages itself (the ring) or
    the grid pipeline has to (the walk); the module docstring says why.
    From the operands' shapes alone — under shard_map ``kv_heads`` is
    the shard's share."""
    return (jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and head_dim % 128 == 0
            and (kv_heads in (2, 4) or kv_heads % 8 == 0))


def read_lengths(write_lengths, context: int):
    """The lengths the kernels read by, from the engine's write
    positions: a slot whose position is out of range (``context``: the
    engine's mark of an inactive slot, whose row write the scatter then
    drops) reads nothing."""
    return jnp.where(write_lengths >= context, 0, write_lengths)


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _attend_page(q_ref, page, first, length, m_ref, l_ref, acc_ref):
    """One page of a slot into its online-softmax state. ``page(h)`` is
    KV head h of the page: (k [Pg, hd], v [Pg, hd], key scales [Pg] or
    None, value scales); ``first`` the page's first logical row.

    The scale fold of int8 pages matches ops/attention.py (scores * s_k
    per key column; probs * s_v before the value contraction) so no
    dequantized page ever materializes."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h]                               # [G, hd]
        k, v, sk, sv = page(h)
        scale = jax.lax.rsqrt(jnp.float32(q.shape[-1]))
        qf = q.astype(jnp.float32) * scale
        scores = jax.lax.dot_general(                 # [G, Pg] NT matmul
            qf, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if sk is not None:
            scores = scores * sk[None, :]
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + first
        scores = jnp.where(col < length, scores, _NEG_INF)

        m_prev = m_ref[h]                             # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)               # [G, Pg]
        l_ref[h] = l_ref[h] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        if sv is not None:
            probs = probs * sv[None, :]
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            probs, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _finish(q_ref, nk_ref, nv_ref, out_ref, m_ref, l_ref, acc_ref):
    """The current token's own key/value (register append; visible; it
    stays float with an int8 cache — the engine holds it in registers,
    only cache rows are quantized), then the output."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h]
        nk = nk_ref[0, h]                             # [1, hd]
        nv = nv_ref[0, h]
        scale = jax.lax.rsqrt(jnp.float32(q.shape[-1]))
        qf = q.astype(jnp.float32) * scale
        s_self = jnp.sum(qf * nk.astype(jnp.float32), axis=-1,
                         keepdims=True)               # [G, 1]
        m_fin = jnp.maximum(m_ref[h], s_self)
        alpha = jnp.exp(m_ref[h] - m_fin)
        p_self = jnp.exp(s_self - m_fin)
        denom = l_ref[h] * alpha + p_self
        out = (acc_ref[h] * alpha + p_self * nv.astype(jnp.float32))
        out_ref[0, h] = (out / denom).astype(out_ref.dtype)


def _ring_kernel(ptab_ref, len_ref, layer_ref, q_ref, nk_ref, nv_ref,
                 kp_ref, vp_ref, out_ref, kring, vring, sem, *state):
    """One slot's program: q [1, KV, G, hd]; kp/vp the stacked pools in
    HBM; kring/vring [2, Pg, KV, hd], the page computed on and the one
    on its way; sem the DMA semaphores [K or V, place]; ``state`` the
    online-softmax scratch."""
    s = pl.program_id(0)
    length = len_ref[s]
    n_pages = kp_ref.shape[1]
    pg = kring.shape[1]
    held = (length + pg - 1) // pg        # the pages the slot holds

    def copies(i):
        """Page i of the slot -> place i % 2 of the ring."""
        pid = jnp.clip(ptab_ref[s, i], 0, n_pages - 1)
        return [pltpu.make_async_copy(pool.at[layer_ref[0], pid],
                                      ring.at[i % 2], sem.at[j, i % 2])
                for j, (pool, ring) in enumerate(((kp_ref, kring),
                                                  (vp_ref, vring)))]

    _init(*state)

    @pl.when(held > 0)
    def _prime():
        for c in copies(0):
            c.start()

    def page(i, carry):
        # the place page i - 1 has just left takes page i + 1
        @pl.when(i + 1 < held)
        def _ahead():
            for c in copies(i + 1):
                c.start()

        for c in copies(i):
            c.wait()
        _attend_page(q_ref, lambda h: (kring[i % 2, :, h, :],
                                       vring[i % 2, :, h, :], None, None),
                     i * pg, length, *state)
        return carry

    jax.lax.fori_loop(0, held, page, 0)
    _finish(q_ref, nk_ref, nv_ref, out_ref, *state)


def _walk_kernel(ptab_ref, len_ref, layer_ref, q_ref, nk_ref, nv_ref, *refs):
    """One (slot, page-table entry) program: q [1, KV, G, hd]; ``refs``
    are the K page block [1, Pg, KV, hd], for int8 its scales
    [1, Pg, KV], the V page and its scales, the output and the
    online-softmax scratch, persistent across the page walk (the output
    block index is invariant in the page dimension). ``ptab_ref`` and
    ``layer_ref`` are read by the index maps alone."""
    pages, out_ref, state = refs[:-4], refs[-4], refs[-3:]
    s = pl.program_id(0)
    p = pl.program_id(1)
    length = len_ref[s]
    pg = pages[0].shape[1]

    @pl.when(p == 0)
    def _first():
        _init(*state)

    # a page the slot does not hold would add alpha = 1, probs = 0
    @pl.when(p * pg < length)
    def _page():
        def page(h):
            if len(pages) == 4:
                kp, sk, vp, sv = pages
                return (kp[0, :, h, :], vp[0, :, h, :], sk[0, :, h],
                        sv[0, :, h])
            kp, vp = pages
            return kp[0, :, h, :], vp[0, :, h, :], None, None

        _attend_page(q_ref, page, p * pg, length, *state)

    @pl.when(p == pl.num_programs(1) - 1)
    def _last():
        _finish(q_ref, nk_ref, nv_ref, out_ref, *state)


def stacked_pool(pools, layer):
    """(stacked pools, layer as the [1] int32 prefetch operand), for
    this kernel and ops/pallas/ragged_prefill.py. Pools with no layer
    axis (kernel tests; pages come first and are then 4-D) are the
    stacked pool with L = 1."""
    if pools[0].ndim == 4:
        pools = [p[None] for p in pools]
    return pools, jnp.asarray(layer, jnp.int32).reshape(1)


def _walk_specs(pools, pg: int, kv_heads: int, hd: int):
    """Block specs of the page walk over the stacked pools (K/V pages
    and, for int8, their scales)."""
    n_pages = pools[0].shape[1]

    def page_map(s, p, ptab_ref, len_ref, layer_ref):
        # entries past the slot's last page revisit that page (no DMA);
        # a slot that holds none names physical page 0, whatever its
        # table says. The body computes none of these.
        last = (len_ref[s] + pg - 1) // pg - 1        # -1: no page held
        pid = ptab_ref[s, jnp.minimum(p, jnp.maximum(last, 0))]
        return (layer_ref[0],
                jnp.where(last < 0, 0, jnp.clip(pid, 0, n_pages - 1)),
                0, 0, 0)

    return [pl.BlockSpec((None, 1, pg, kv_heads, hd), page_map)
            if pool.ndim == 5 else
            pl.BlockSpec((None, 1, pg, kv_heads),
                         lambda *a: page_map(*a)[:4])
            for pool in pools]


def _paged_decode(name, q, new_k, new_v, pools, ptab, lengths, layer, *,
                  q_per_kv: int, interpret: bool):
    """Both variants' call: ``pools`` is (pages_k, pages_v), or for int8
    (pages_k, scales_k, pages_v, scales_v)."""
    S, H, hd = q.shape
    mp = ptab.shape[1]
    pools, layer = stacked_pool(pools, layer)
    _, _, pg, kv_heads, _ = pools[0].shape
    G = q_per_kv
    qg = q.reshape(S, kv_heads, G, hd)
    nk = new_k.reshape(S, kv_heads, 1, hd)
    nv = new_v.reshape(S, kv_heads, 1, hd)

    def slot_block(width):
        return pl.BlockSpec((1, kv_heads, width, hd),
                            lambda s, *_: (s, 0, 0, 0))

    state = [pltpu.VMEM((kv_heads, G, 1), jnp.float32),    # running max
             pltpu.VMEM((kv_heads, G, 1), jnp.float32),    # running denom
             pltpu.VMEM((kv_heads, G, hd), jnp.float32)]   # running out
    if ring_takes(kv_heads, hd, pools[0].dtype):
        kernel, grid = _ring_kernel, (S,)
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY) for _ in pools]
        scratch = [*(pltpu.VMEM((2, pg, kv_heads, hd), pool.dtype)
                     for pool in pools),
                   pltpu.SemaphoreType.DMA((2, 2)), *state]
    else:
        kernel, grid = _walk_kernel, (S, mp)
        pool_specs = _walk_specs(pools, pg, kv_heads, hd)
        scratch = state
    out = pl.pallas_call(
        kernel,
        # the custom call's name in a profiler capture; the benchmark's
        # reduce_trace finds the kernel by the substring "paged_decode"
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # ptab, lengths, layer
            grid=grid,
            in_specs=[slot_block(G), slot_block(1), slot_block(1),
                      *pool_specs],
            out_specs=slot_block(G),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((S, kv_heads, G, hd), q.dtype),
        interpret=interpret,
    )(ptab, lengths, layer, qg, nk, nv, *pools)
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("q_per_kv", "interpret"))
def paged_decode_attention_append(q, new_k, new_v, pages_k, pages_v, ptab,
                                  lengths, layer=0, *, q_per_kv: int,
                                  interpret: bool = False):
    """q: [S, H, hd]; new_k/new_v: [S, KV, hd]; pages_k/v:
    [L, n_pages, page_size, KV, hd] (the stacked page pool; without the
    layer axis: L = 1); ptab: [S, max_pages] int32 (sentinel n_pages =
    unallocated); lengths: [S]; layer: int32 scalar, traced inside the
    scan over layers. Returns [S, H, hd] (q.dtype). Semantics match
    ops/attention.py::decode_attention_append over the slot's logical
    rows [0, lengths[s]) of that layer plus the register-appended
    current token."""
    return _paged_decode("paged_decode_attention", q, new_k, new_v,
                         (pages_k, pages_v), ptab, lengths, layer,
                         q_per_kv=q_per_kv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("q_per_kv", "interpret"))
def paged_decode_attention_append_quant(q, new_k, new_v, pages_k, scales_k,
                                        pages_v, scales_v, ptab, lengths,
                                        layer=0, *, q_per_kv: int,
                                        interpret: bool = False):
    """Int8-KV variant of paged_decode_attention_append: pages_k/v are
    int8 [L, n_pages, page_size, KV, hd] and scales_k/v are their f32
    [L, n_pages, page_size, KV] companions (the {"pages","scales"} leaves
    of ops/kvcache.py's quantized paged cache), all stacked and read at
    ``layer``. new_k/new_v stay float. Semantics match
    decode_attention_append over the dense-gathered {"q","s"} rows (the
    jnp fallback / parity reference). Always the walk."""
    return _paged_decode("paged_decode_attention_quant", q, new_k, new_v,
                         (pages_k, scales_k, pages_v, scales_v), ptab,
                         lengths, layer, q_per_kv=q_per_kv,
                         interpret=interpret)
