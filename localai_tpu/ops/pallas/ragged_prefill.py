"""Pallas TPU kernel: RAGGED PACKED PREFILL attention, segment-blocked.

The packed prefill step (ops/ragged_prefill.py has the semantics and the
jnp fallback) feeds one [N]-token batch holding the prompt tails of up
to B slots; each token attends its slot's committed cache PAGES plus the
pack's own keys causally within its segment. A naive XLA lowering
gathers every segment's dense [C] row window per layer; this kernel
walks the pages IN PLACE, the same way ops/pallas/paged_attention.py
does for decode — and like it out of the STACKED pool
[L, n_pages, page_size, KV, hd], the layer index being one more
scalar-prefetch argument and the leading coordinate of the page blocks,
so no layer of the pool is copied out of the scan carry for the call.

The grid blocks QUERIES PER SEGMENT (Ragged Paged Attention style)
instead of keeping the whole pack's query rows resident:

  * Grid (NQB, B, MP + NKB): for each QB-row query block, sweep every
    segment's MP committed page-table entries, then the NKB blocks of
    the pack's own keys.
  * The page table, the per-segment metadata (slot, start, offset,
    length) and the layer are SCALAR-PREFETCH arguments consumed by the
    K/V BlockSpec index maps — the pipeline knows page j+1's address while
    page j computes. Entries past a segment's last committed page, and
    every (q-block, segment) pair that does not overlap, clamp to a
    constant block so consecutive skipped steps revisit (no DMA), and
    their compute is predicated off entirely (``pl.when``).
  * Online softmax per q-block (m/l/acc VMEM scratch over QB*G query
    rows). Each query row belongs to exactly one segment and every
    other segment's scores are fully masked for it, so the accumulator
    runs across the whole (segment, kv-step) sweep without per-segment
    resets; the q-block's output is written once at the final step.

Scratch is therefore INDEPENDENT of the pack size N. What VMEM does
bound is the per-block width; ``ragged_kernel_plan`` below counts it
(padding and double-buffering included) against the same limit the
kernel hands the compiler, and is the single source of truth for the
blocking and for "does this pack stay on the kernel path", shared by
models/llama.py and the engine's fallback counter.

Operands enter the kernel HEAD-MAJOR — q [KV, N*G, hd], pack keys
[KV, N, hd] — so one head's rows are a dense [rows, hd] tile; the
wrapper transposes in XLA on the way in and out.

Plain float paged caches only (the int8 paged prefill folds scales
through the jnp fallback).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.pallas.paged_attention import stacked_pool

_NEG_INF = -1e30

# The kernel's VMEM ceiling: ragged_kernel_plan sizes the blocking
# against it and pallas_call hands the same number to Mosaic as
# vmem_limit_bytes, so the plan and the compiler cannot disagree about
# the budget. 32 MiB is a quarter of a v5e core's 128 MiB VMEM (the
# compiler's own default scoped limit there is 16 MiB).
_VMEM_LIMIT = 32 * 1024 * 1024


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(qb: int, pkb: int, kv_heads: int, q_per_kv: int,
                head_dim: int, page_size: int, itemsize: int) -> int:
    """VMEM the kernel needs at this blocking, counted the way Mosaic
    lays it out: the last dim pads to 128 lanes, the second-to-last to
    the dtype's sublane tile (8 rows of 32 bits), every pipelined block
    is double-buffered, and the body's f32 temporaries (one head's
    scaled q, one score/prob/mask block, one k and v operand) live
    beside the scratch, plus 1 MiB for the compiler's own use. Within
    0.5 MiB of the smallest limit Mosaic accepts at the shapes
    tests/test_tpu_compile.py compiles."""
    sub = 32 // itemsize
    lanes = _pad(head_dim, 128)
    rows = _pad(qb * q_per_kv, 8)
    q_blk = kv_heads * _pad(qb * q_per_kv, sub) * lanes * itemsize
    pack_blk = kv_heads * _pad(pkb, sub) * lanes * itemsize
    page_blk = page_size * _pad(kv_heads, sub) * lanes * itemsize
    pipelined = 2 * (2 * q_blk + 2 * pack_blk + 2 * page_blk)
    scratch = kv_heads * rows * (128 + 128 + lanes) * 4    # m, l, acc
    blk = _pad(max(page_size, pkb), 128)
    temps = (rows * (3 * blk + 2 * lanes) + 2 * blk * lanes) * 4
    return pipelined + scratch + temps + (1 << 20)


def ragged_kernel_plan(N: int, kv_heads: int, q_per_kv: int, head_dim: int,
                       page_size: int = 64, itemsize: int = 2
                       ) -> Optional[Tuple[int, int]]:
    """Blocking plan ``(qb, pkb)`` for an N-token pack, or None when no
    blocking fits VMEM.

    ``qb`` (query block) and ``pkb`` (pack-key block) are equal: the
    largest power of two <= 128 dividing N whose footprint
    (``_vmem_bytes``) fits ``_VMEM_LIMIT``. A plan this returns compiles
    — tests/test_tpu_compile.py holds it to that for v5e at 8B and 1B
    head shapes. Scratch is per-q-block, so pack LENGTH never
    disqualifies a pack; only per-block width can."""
    if N <= 0:
        return None
    qb = math.gcd(N, 128)
    while qb >= 8:
        if _vmem_bytes(qb, qb, kv_heads, q_per_kv, head_dim, page_size,
                       itemsize) <= _VMEM_LIMIT:
            return qb, qb
        qb //= 2
    return None


def _kernel(ptab_ref, slots_ref, start_ref, off_ref, len_ref, layer_ref,
            q_ref, ck_ref, cv_ref, kp_ref, vp_ref,
            out_ref, m_ref, l_ref, acc_ref, *, mp: int, pkb: int, qb: int,
            G: int):
    """One (q-block, segment, kv-step) program. q [KV, QB*G, hd] (row r
    is query q_lo + r // G, group r % G); ck/cv pack keys [KV, PKB, hd];
    kp/vp one page [1, Pg, KV, hd]. A step is EITHER a page step
    (j < mp) or a pack-key step; each runs its own predicated body.
    ``layer_ref`` is read by the index maps alone."""
    i = pl.program_id(0)
    b = pl.program_id(1)
    j = pl.program_id(2)
    nb = pl.num_programs(1)
    nj = pl.num_programs(2)
    kv_heads, rows, hd = q_ref.shape
    pg = kp_ref.shape[1]
    start = start_ref[b]
    off = off_ref[b]
    length = len_ref[b]
    q_lo = i * qb
    scale = jax.lax.rsqrt(jnp.float32(hd))

    @pl.when((b == 0) & (j == 0))
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def row_mask(width):
        """(query index n per score row, is the row in this segment?) at
        the full [rows, width] score shape — built from 2-D iotas so no
        boolean vector is ever broadcast or concatenated (Mosaic cannot
        relayout i1 vregs)."""
        n = q_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) // G
        return n, (n >= off) & (n < off + length)

    def accumulate(h, scores, mask, v):
        """Online-softmax update of head h with one masked score block."""
        scores = jnp.where(mask, scores, _NEG_INF)
        m_prev = m_ref[h]                                     # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zero where masked: an all-masked row has
        # m == _NEG_INF and exp(score - m) would be exp(0) == 1
        probs = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    def scores_of(h, k):
        qf = q_ref[h].astype(jnp.float32) * scale             # [rows, hd]
        return jax.lax.dot_general(
            qf, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [rows, BLK]

    # does this (q-block, segment) pair overlap at all? A skipped step
    # is exact: all its scores would mask to -inf, so m/l/acc are
    # unchanged (alpha == 1, probs == 0).
    seg_hit = (length > 0) & (off < q_lo + qb) & (off + length > q_lo)
    pk_lo = (j - mp) * pkb

    @pl.when(seg_hit & (j < mp) & (j * pg < start))
    def _page():
        _, in_seg = row_mask(pg)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, pg), 1) + j * pg
        mask = in_seg & (col < start)
        for h in range(kv_heads):
            k = kp_ref[0, :, h, :].astype(jnp.float32)        # [Pg, hd]
            v = vp_ref[0, :, h, :].astype(jnp.float32)
            accumulate(h, scores_of(h, k), mask, v)

    @pl.when(seg_hit & (j >= mp) & (pk_lo < off + length)
             & (pk_lo + pkb > off) & (pk_lo < q_lo + qb))
    def _pack():
        n, in_seg = row_mask(pkb)
        midx = jax.lax.broadcasted_iota(jnp.int32, (rows, pkb), 1) + pk_lo
        # causal within the segment; midx <= n < off + length bounds it above
        mask = in_seg & (midx >= off) & (midx <= n)
        for h in range(kv_heads):
            k = ck_ref[h].astype(jnp.float32)                 # [PKB, hd]
            v = cv_ref[h].astype(jnp.float32)
            accumulate(h, scores_of(h, k), mask, v)

    @pl.when((b == nb - 1) & (j == nj - 1))
    def _finish():
        # every row accumulated only from its own segment (other
        # segments masked it); rows in no segment have l == 0 -> 0
        for h in range(kv_heads):
            denom = l_ref[h] + (l_ref[h] == 0.0)                  # pad: 0/1
            out_ref[h] = (acc_ref[h] / denom).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("q_per_kv", "pkb", "qb", "interpret"))
def ragged_prefill_attention_pallas(q, chunk_k, chunk_v, pages_k, pages_v,
                                    ptab, seg_slots, seg_start, seg_off,
                                    seg_len, layer=0, *, q_per_kv: int,
                                    pkb: int = 128,
                                    qb: Optional[int] = None,
                                    interpret: bool = False):
    """q: [N, H, hd]; chunk_k/chunk_v: [N, KV, hd] (this pack's keys, not
    yet scattered); pages_k/v: [L, n_pages, page_size, KV, hd] stacked
    page pool (without the layer axis: L = 1); ptab: [S, MP] int32
    (sentinel n_pages = unallocated);
    seg_slots/seg_start/seg_off/seg_len: [B] int32 segment tables (pad
    segments: seg_len == 0); layer: int32 scalar, traced inside the scan
    over layers. ``pkb`` (pack-key block) and ``qb`` (query
    block, default ``gcd(N, 128)``) must divide N; use
    ``ragged_kernel_plan`` to pick both. Returns [N, H, hd] (q.dtype);
    semantics match ops/ragged_prefill.py::ragged_prefill_attention over
    a paged cache."""
    N, H, hd = q.shape
    (pages_k, pages_v), layer = stacked_pool((pages_k, pages_v), layer)
    _, n_pages, pg, kv_heads, _ = pages_k.shape
    mp = ptab.shape[1]
    B = seg_slots.shape[0]
    G = q_per_kv
    if qb is None:
        qb = math.gcd(N, 128)
    assert N % pkb == 0 and N % qb == 0, (N, pkb, qb)
    nkb = N // pkb
    nqb = N // qb
    # head-major operands: each head's [rows, hd] tile is then dense in
    # VMEM (a [.., G, hd] minor pair would pad G up to the sublane tile
    # and the body would relayout it on every step)
    qh = q.reshape(N, kv_heads, G, hd).transpose(1, 0, 2, 3) \
        .reshape(kv_heads, N * G, hd)
    ckh = chunk_k.transpose(1, 0, 2)
    cvh = chunk_v.transpose(1, 0, 2)

    def _seg_hit(i, b, off_ref, len_ref):
        q_lo = i * qb
        return (len_ref[b] > 0) & (off_ref[b] < q_lo + qb) \
            & (off_ref[b] + len_ref[b] > q_lo)

    def q_map(i, b, j, *refs):
        return (0, i, 0)

    def page_map(i, b, j, ptab_ref, slots_ref, start_ref, off_ref, len_ref,
                 layer_ref):
        # pages past the segment's last committed one — and every page
        # of a (q-block, segment) pair with no overlap — clamp to a
        # constant so consecutive skipped steps revisit (no DMA);
        # their compute is predicated off in the kernel
        n_valid = (start_ref[b] + pg - 1) // pg
        last = jnp.maximum(n_valid - 1, 0)
        # pad segments carry a sentinel slot id one past the table
        slot = jnp.minimum(slots_ref[b], ptab_ref.shape[0] - 1)
        pid = ptab_ref[slot, jnp.minimum(jnp.minimum(j, mp - 1), last)]
        hit = _seg_hit(i, b, off_ref, len_ref) & (j * pg < start_ref[b])
        return (layer_ref[0],
                jnp.where(hit, jnp.clip(pid, 0, n_pages - 1), 0), 0, 0, 0)

    def pack_map(i, b, j, ptab_ref, slots_ref, start_ref, off_ref, len_ref,
                 layer_ref):
        blk = jnp.clip(j - mp, 0, nkb - 1)
        q_lo = i * qb
        lo, hi = off_ref[b], off_ref[b] + len_ref[b]
        pk_lo = blk * pkb
        hit = _seg_hit(i, b, off_ref, len_ref) & (j >= mp) \
            & (pk_lo < hi) & (pk_lo + pkb > lo) & (pk_lo < q_lo + qb)
        return (0, jnp.where(hit, blk, 0), 0)

    rows = qb * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,      # ptab, seg_slots, seg_start/off/len, layer
        grid=(nqb, B, mp + nkb),
        in_specs=[
            pl.BlockSpec((kv_heads, rows, hd), q_map),
            pl.BlockSpec((kv_heads, pkb, hd), pack_map),
            pl.BlockSpec((kv_heads, pkb, hd), pack_map),
            pl.BlockSpec((None, 1, pg, kv_heads, hd), page_map),
            pl.BlockSpec((None, 1, pg, kv_heads, hd), page_map),
        ],
        out_specs=pl.BlockSpec((kv_heads, rows, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, rows, 1), jnp.float32),    # running max
            pltpu.VMEM((kv_heads, rows, 1), jnp.float32),    # running denom
            pltpu.VMEM((kv_heads, rows, hd), jnp.float32),   # running out
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, mp=mp, pkb=pkb, qb=qb, G=G),
        name="ragged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kv_heads, N * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(ptab, seg_slots, seg_start, seg_off, seg_len, layer,
      qh, ckh, cvh, pages_k, pages_v)
    return out.reshape(kv_heads, N, G, hd).transpose(1, 0, 2, 3) \
        .reshape(N, H, hd)
