"""Pallas TPU kernel: RAGGED PAGED decode (append-)attention.

The paged KV layout (ops/kvcache.py) stores rows in a shared page pool
[L, n_pages, page_size, KV, hd], all layers stacked, with a per-slot
page table [S, max_pages]; a mixed-length batch is "ragged" — each slot
touches only the pages its table names (Ragged Paged Attention,
PAPERS.md arxiv 2604.15464). A naive XLA gather materializes a dense
[S, C, KV, hd] copy of the pool every layer of every step; this kernel
reads pages IN PLACE, out of the STACKED pool:

  * Grid (S, max_pages): one program per (slot, page-table entry).
  * The page table, the lengths and the layer index are SCALAR-PREFETCH
    arguments, consumed by the K/V BlockSpec index maps — the grid
    pipeline therefore knows page p+1's physical address while page p
    computes, and its automatic double-buffering overlaps the next
    page's HBM read with the current page's FLOPs (the
    prefetch-ahead-of-decode idea of PRESERVE, arxiv 2501.08192,
    expressed through the Pallas pipeline).
  * The layer is the leading block coordinate (a squeezed dimension), so
    the caller inside the scan over layers hands over the whole scan
    carry. A Mosaic custom call needs a materialized operand: given
    ``pool[layer]`` XLA copied that layer's pool out of the carry and
    wrote it back after the row scatter, every layer of every step
    (PERF.md section 6, PR 27).
  * Table entries past a slot's last valid page are remapped to the last
    valid page in the index map: consecutive grid steps then name the
    SAME block, and the pipeline skips the redundant DMA entirely —
    short slots cost ~their own length in HBM reads, not max_pages.
  * Softmax is accumulated online across pages (m/l/acc VMEM scratch);
    the current token's own k/v is appended from registers at the final
    page, matching ops/attention.py::decode_attention_append — the jnp
    fallback used on CPU (kvcache.gather_all_rows) and the parity
    reference in tests.

The int8 paged cache has its own kernel variant below
(paged_decode_attention_append_quant): pages stay int8 in HBM and the
per-(row, kv-head) scales are folded OUTSIDE the contraction — scores
for K, probs for V — exactly the fold ops/attention.py::_split_cache
does on the jnp path, so HBM reads stay 1 byte/element on the decode
hot path instead of falling back to the dense gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(ptab_ref, len_ref, layer_ref, q_ref, nk_ref, nv_ref, kp_ref,
            vp_ref, out_ref, m_ref, l_ref, acc_ref):
    """One (slot, page) program: q [1, KV, G, hd]; k/v page [1, Pg, KV, hd];
    online-softmax state in VMEM scratch, persistent across the page walk
    (the output block index is invariant in the page dimension).
    ``layer_ref`` is read by the index maps alone."""
    s = pl.program_id(0)
    p = pl.program_id(1)
    mp = pl.num_programs(1)
    length = len_ref[s]
    pg = kp_ref.shape[1]
    kv_heads = kp_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(kv_heads):
        q = q_ref[0, h]                               # [G, hd]
        k = kp_ref[0, :, h, :]                        # [Pg, hd]
        v = vp_ref[0, :, h, :]
        scale = jax.lax.rsqrt(jnp.float32(q.shape[-1]))
        qf = q.astype(jnp.float32) * scale
        scores = jax.lax.dot_general(                 # [G, Pg] NT matmul
            qf, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + p * pg
        scores = jnp.where(col < length, scores, _NEG_INF)

        m_prev = m_ref[h]                             # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)               # [G, Pg]
        l_ref[h] = l_ref[h] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            probs, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(p == mp - 1)
    def _finish():
        for h in range(kv_heads):
            q = q_ref[0, h]
            nk = nk_ref[0, h]                         # [1, hd]
            nv = nv_ref[0, h]
            scale = jax.lax.rsqrt(jnp.float32(q.shape[-1]))
            qf = q.astype(jnp.float32) * scale
            # current token's own key/value (register append; visible)
            s_self = jnp.sum(qf * nk.astype(jnp.float32), axis=-1,
                             keepdims=True)           # [G, 1]
            m_fin = jnp.maximum(m_ref[h], s_self)
            alpha = jnp.exp(m_ref[h] - m_fin)
            p_self = jnp.exp(s_self - m_fin)
            denom = l_ref[h] * alpha + p_self
            out = (acc_ref[h] * alpha + p_self * nv.astype(jnp.float32))
            out_ref[0, h] = (out / denom).astype(out_ref.dtype)


def stacked_pool(pools, layer):
    """(stacked pools, layer as the [1] int32 prefetch operand), for
    this kernel and ops/pallas/ragged_prefill.py. Pools with no layer
    axis (kernel tests; pages come first and are then 4-D) are the
    stacked pool with L = 1."""
    if pools[0].ndim == 4:
        pools = [p[None] for p in pools]
    return pools, jnp.asarray(layer, jnp.int32).reshape(1)


def _page_maps(pg: int, n_pages: int):
    """Index maps of the page walk over the stacked pool (K/V pages and,
    for int8, their scales)."""
    def page_map(s, p, ptab_ref, len_ref, layer_ref):
        # pages past the slot's last valid one revisit the last valid
        # block (no DMA); fully-empty slots clamp to physical page 0 —
        # their scores are all masked (col < 0 never holds)
        n_valid = (len_ref[s] + pg - 1) // pg
        last = jnp.maximum(n_valid - 1, 0)
        pid = ptab_ref[s, jnp.minimum(p, last)]
        return (layer_ref[0], jnp.clip(pid, 0, n_pages - 1), 0, 0, 0)

    def scale_map(s, p, ptab_ref, len_ref, layer_ref):
        return page_map(s, p, ptab_ref, len_ref, layer_ref)[:4]

    return page_map, scale_map


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
    S, H, hd = q.shape
    (pages_k, pages_v), layer = stacked_pool((pages_k, pages_v), layer)
    _, n_pages, pg, kv_heads, _ = pages_k.shape
    mp = ptab.shape[1]
    G = q_per_kv
    qg = q.reshape(S, kv_heads, G, hd)
    nk = new_k.reshape(S, kv_heads, 1, hd)
    nv = new_v.reshape(S, kv_heads, 1, hd)
    page_map, _ = _page_maps(pg, n_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # ptab, lengths, layer
        grid=(S, mp),
        in_specs=[
            pl.BlockSpec((1, kv_heads, G, hd),
                         lambda s, p, pt, ln, li: (s, 0, 0, 0)),
            pl.BlockSpec((1, kv_heads, 1, hd),
                         lambda s, p, pt, ln, li: (s, 0, 0, 0)),
            pl.BlockSpec((1, kv_heads, 1, hd),
                         lambda s, p, pt, ln, li: (s, 0, 0, 0)),
            pl.BlockSpec((None, 1, pg, kv_heads, hd), page_map),
            pl.BlockSpec((None, 1, pg, kv_heads, hd), page_map),
        ],
        out_specs=pl.BlockSpec((1, kv_heads, G, hd),
                               lambda s, p, pt, ln, li: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, G, 1), jnp.float32),    # running max
            pltpu.VMEM((kv_heads, G, 1), jnp.float32),    # running denom
            pltpu.VMEM((kv_heads, G, hd), jnp.float32),   # running out
        ],
    )
    out = pl.pallas_call(
        _kernel,
        # the custom call's name in a profiler capture; the benchmark's
        # reduce_trace finds the kernel by the substring "paged_decode"
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, kv_heads, G, hd), q.dtype),
        interpret=interpret,
    )(ptab, lengths, layer, qg, nk, nv, pages_k, pages_v)
    return out.reshape(S, H, hd)


def _kernel_quant(ptab_ref, len_ref, layer_ref, q_ref, nk_ref, nv_ref, kp_ref,
                  sk_ref, vp_ref, sv_ref, out_ref, m_ref, l_ref, acc_ref):
    """_kernel with the int8 {q, scales} page representation: k/v pages
    arrive int8 and their per-(row, kv-head) scales ride as separate
    [1, Pg, KV] blocks of the same page walk. The scale fold matches
    ops/attention.py (scores * s_k per key column; probs * s_v before
    the value contraction) so no dequantized page ever materializes.
    The current token's own k/v (nk/nv) stays float — the engine holds
    it in registers; only cache rows are quantized."""
    s = pl.program_id(0)
    p = pl.program_id(1)
    mp = pl.num_programs(1)
    length = len_ref[s]
    pg = kp_ref.shape[1]
    kv_heads = kp_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(kv_heads):
        q = q_ref[0, h]                               # [G, hd]
        k = kp_ref[0, :, h, :]                        # [Pg, hd] int8
        v = vp_ref[0, :, h, :]
        sk = sk_ref[0, :, h]                          # [Pg] f32
        sv = sv_ref[0, :, h]
        scale = jax.lax.rsqrt(jnp.float32(q.shape[-1]))
        qf = q.astype(jnp.float32) * scale
        scores = jax.lax.dot_general(                 # [G, Pg] NT matmul
            qf, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        scores = scores * sk[None, :]                 # key scale fold
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + p * pg
        scores = jnp.where(col < length, scores, _NEG_INF)

        m_prev = m_ref[h]                             # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)               # [G, Pg]
        l_ref[h] = l_ref[h] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            probs * sv[None, :], v.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(p == mp - 1)
    def _finish():
        for h in range(kv_heads):
            q = q_ref[0, h]
            nk = nk_ref[0, h]                         # [1, hd] float
            nv = nv_ref[0, h]
            scale = jax.lax.rsqrt(jnp.float32(q.shape[-1]))
            qf = q.astype(jnp.float32) * scale
            s_self = jnp.sum(qf * nk.astype(jnp.float32), axis=-1,
                             keepdims=True)           # [G, 1]
            m_fin = jnp.maximum(m_ref[h], s_self)
            alpha = jnp.exp(m_ref[h] - m_fin)
            p_self = jnp.exp(s_self - m_fin)
            denom = l_ref[h] * alpha + p_self
            out = (acc_ref[h] * alpha + p_self * nv.astype(jnp.float32))
            out_ref[0, h] = (out / denom).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_per_kv", "interpret"))
def paged_decode_attention_append_quant(q, new_k, new_v, pages_k, scales_k,
                                        pages_v, scales_v, ptab, lengths,
                                        layer=0, *, q_per_kv: int,
                                        interpret: bool = False):
    """Int8-KV variant of paged_decode_attention_append: pages_k/v are
    int8 [L, n_pages, page_size, KV, hd] and scales_k/v are their f32
    [L, n_pages, page_size, KV] companions (the {"pages","scales"} leaves
    of the quantized paged cache, ops/kvcache.py), indexed by the same
    ``layer``. new_k/new_v stay float. Semantics match
    decode_attention_append over the dense-gathered {"q","s"} rows (the
    jnp fallback / parity reference)."""
    S, H, hd = q.shape
    (pages_k, scales_k, pages_v, scales_v), layer = stacked_pool(
        (pages_k, scales_k, pages_v, scales_v), layer)
    _, n_pages, pg, kv_heads, _ = pages_k.shape
    mp = ptab.shape[1]
    G = q_per_kv
    qg = q.reshape(S, kv_heads, G, hd)
    nk = new_k.reshape(S, kv_heads, 1, hd)
    nv = new_v.reshape(S, kv_heads, 1, hd)
    page_map, scale_map = _page_maps(pg, n_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # ptab, lengths, layer
        grid=(S, mp),
        in_specs=[
            pl.BlockSpec((1, kv_heads, G, hd),
                         lambda s, p, pt, ln, li: (s, 0, 0, 0)),
            pl.BlockSpec((1, kv_heads, 1, hd),
                         lambda s, p, pt, ln, li: (s, 0, 0, 0)),
            pl.BlockSpec((1, kv_heads, 1, hd),
                         lambda s, p, pt, ln, li: (s, 0, 0, 0)),
            pl.BlockSpec((None, 1, pg, kv_heads, hd), page_map),
            pl.BlockSpec((None, 1, pg, kv_heads), scale_map),
            pl.BlockSpec((None, 1, pg, kv_heads, hd), page_map),
            pl.BlockSpec((None, 1, pg, kv_heads), scale_map),
        ],
        out_specs=pl.BlockSpec((1, kv_heads, G, hd),
                               lambda s, p, pt, ln, li: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, G, 1), jnp.float32),    # running max
            pltpu.VMEM((kv_heads, G, 1), jnp.float32),    # running denom
            pltpu.VMEM((kv_heads, G, hd), jnp.float32),   # running out
        ],
    )
    out = pl.pallas_call(
        _kernel_quant,
        name="paged_decode_attention_quant",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, kv_heads, G, hd), q.dtype),
        interpret=interpret,
    )(ptab, lengths, layer, qg, nk, nv, pages_k, scales_k, pages_v, scales_v)
    return out.reshape(S, H, hd)
