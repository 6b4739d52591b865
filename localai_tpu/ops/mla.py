"""Multi-head latent attention (MLA, DeepSeek-V2's) over a LATENT page pool.

A token leaves ONE row in the cache, shared by all heads: the normed latent
``c`` (``kv_lora_rank`` wide) and the rotated shared key part ``r``
(``qk_rope_head_dim``), where a grouped-query layer leaves a K and a V row
a KV head. Per head ``[k_nope_h | v_h] = W_kvb,h c``, the key is
``[k_nope_h | r]``, and

    scores = (q_nope_h . k_nope_h + q_rope_h . r) * (nope + rope)^-0.5

The pool is ops/kvcache.py's paged layout with one "KV head" whose width
is the row's, padded with zeros to a multiple of 128 (``pool_width``: 576
-> 640; the device's own layout for a minor axis of 576 puts the PAGE axis
on the lanes instead, and every kernel call would then get a transposed copy
of the pool): ``{"pages": [L_mla, n_pages, page, 1, Wd], "ptab": [S, MP]}``,
so the page table, the allocator and every helper of ops/kvcache.py are
those of the K/V pools. There is no second plane: the family's ``cache_v``
holds a pool of no layers.

TWO FORMS of the same attention over that one pool:

  ``prefill_attention``  MATERIALISED: the pack's own rows, and for a
      continued segment its slot's committed rows block by block, are
      expanded through ``W_kvb`` into per-head keys and values, and the
      pack attends as ops/ragged_prefill.py's does (the same masks and pad
      conventions). The committed rows are walked a segment at a time and
      only as far as the segment's own start, with an online softmax.
  ``decode_attention``   ABSORBED: ``W_kvb``'s key part goes into the query
      (``absorb_query``: ``q'_h = [W_kvb,K,h^T q_nope_h | q_rope_h]``), the
      heads then attend as H query heads over ONE shared row a token, the
      values are the latents themselves, and ``W_kvb``'s value part comes
      after (``expand_values``). On the TPU the walk over a slot's pages is
      ops/pallas/mla_decode.py's kernel; elsewhere a page gather.

Both read the cache BEFORE the caller scatters the new rows (the module rule
of ops/attention.py) and take the current token's own row from registers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from localai_tpu.ops import kvcache
from localai_tpu.ops.norms import rms_norm

_NEG_INF = -1e30
_BLOCK = 512            # committed rows expanded at a time (prefill)
WALK_SCOPE = "mla_walk"  # prefill_attention's walk over committed rows


def pool_width(kv_lora_rank: int, rope_dim: int) -> int:
    """A latent row as the pool holds it: a multiple of 128 (module doc)."""
    return -(-(kv_lora_rank + rope_dim) // 128) * 128


def rope_terms(positions, dim: int, theta: float, inv_freq=None,
               mscale: float = 1.0):
    """positions [...] -> (sin, cos) [..., dim], the half-split convention
    of ops/rope.py. ``inv_freq`` [dim / 2]: scaled frequencies in place of
    ``theta``'s own (ops/rope.py::yarn_inv_freq), ``mscale`` their
    magnitude (YaRN's ``mscale / mscale_all_dim``)."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    if mscale != 1.0:
        return jnp.sin(ang) * mscale, jnp.cos(ang) * mscale
    return jnp.sin(ang), jnp.cos(ang)


def lora_query(h, w_qa, q_norm, w_qb, eps: float):
    """The query through its own low-rank pair: ``W_qb RMSNorm(W_qa h)``.
    h [N, D]; w_qa [D, r], q_norm [r], w_qb [r, H dq] -> [N, H dq]."""
    return rms_norm(h @ w_qa, q_norm, eps) @ w_qb


def split_kvb(kvb, rank: int, heads: int, nope: int):
    """``W_kvb`` [R, H (nope + v)] -> its key part [R, H, nope] and its
    value part [R, H, v]."""
    kvb = kvb.reshape(rank, heads, -1)
    return kvb[..., :nope], kvb[..., nope:]


def expand_rows(lat, w_k, w_v, rope_dim: int):
    """Latent rows [n, Wd] (``[c | r | 0]``) -> the per-head keys
    ``[k_nope | r]`` [n, H, nope + rope] and values [n, H, v] they stand
    for: the materialised form's ``expand``."""
    R, H = w_k.shape[:2]
    lat = lat.astype(w_k.dtype)      # (a pool held lower: a control)
    c, r = lat[:, :R], lat[:, R:R + rope_dim]
    k_nope = jnp.einsum("nr,rhd->nhd", c, w_k)
    r = jnp.broadcast_to(r[:, None], (lat.shape[0], H, rope_dim))
    return (jnp.concatenate([k_nope, r.astype(k_nope.dtype)], -1),
            jnp.einsum("nr,rhd->nhd", c, w_v))


def latent_rows(c, r, width: int):
    """c [N, R] (normed), r [N, rope] (rotated) -> the pool's rows
    [N, 1, width]: ``[c | r | 0]``."""
    row = jnp.concatenate([c, r.astype(c.dtype)], axis=-1)
    return jnp.pad(row, ((0, 0), (0, width - row.shape[-1])))[:, None]


def absorb_query(q_nope, q_rope, w_k, width: int, scale: float):
    """q_nope [S, H, nope], q_rope [S, H, rope] (rotated), w_k [R, H, nope]
    (``W_kvb``'s key part) -> q' [S, H, width] float32, scaled: what scores
    a latent row ``[c | r | 0]`` as the materialised key would."""
    f32 = jnp.float32
    qa = jnp.einsum("shd,rhd->shr", q_nope.astype(f32), w_k.astype(f32),
                    precision=jax.lax.Precision.HIGHEST)
    q = jnp.concatenate([qa, q_rope.astype(f32)], axis=-1) * scale
    return jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[-1])))


def expand_values(o_latent, w_v):
    """o_latent [S, H, R] (attention's output in the latent), w_v
    [R, H, v] (``W_kvb``'s value part) -> [S, H, v] float32."""
    return jnp.einsum("shr,rhd->shd", o_latent.astype(jnp.float32),
                      w_v.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def decode_attention(q_abs, new_row, ck, li, lengths, rank: int,
                     pallas: bool = False, interpret: bool = False):
    """One token a slot, absorbed. q_abs [S, H, Wd] float32 (``absorb_query``);
    new_row [S, 1, Wd] the token's own row (not yet in the pool); ``ck`` the
    whole latent cache, read at layer ``li``; ``lengths`` [S] the rows each
    slot holds (0: an idle slot attends its own token alone)
    -> [S, H, rank] float32: the attention output in the latent."""
    if pallas:
        from localai_tpu.ops.pallas.mla_decode import mla_paged_decode

        return mla_paged_decode(q_abs, new_row, ck["pages"], ck["ptab"],
                                lengths, li, rank=rank, interpret=interpret)
    f32 = jnp.float32
    rows = kvcache.gather_all_rows(kvcache.layer(ck, li))[:, :, 0]  # [S,C,Wd]
    rows = jnp.concatenate([rows, new_row.astype(rows.dtype)], axis=1)
    C = rows.shape[1] - 1
    sc = jnp.einsum("shw,scw->shc", q_abs, rows.astype(f32))
    col = jnp.arange(C + 1, dtype=jnp.int32)[None]
    seen = (col < lengths[:, None]) | (col == C)
    p = jax.nn.softmax(jnp.where(seen[:, None], sc, _NEG_INF), axis=-1)
    return jnp.einsum("shc,scr->shr", p, rows[..., :rank].astype(f32))


def _slot_block(ck, li, slot, first, n: int):
    """Rows [first, first + n) of ``slot`` in layer ``li`` of the whole
    latent cache -> [n, Wd] (zeros where the slot holds no page). The
    stacked pool is indexed flat: a layer sliced out of it first would be a
    copy of that layer's pool."""
    pages = ck["pages"]
    n_pages, pg = pages.shape[1], pages.shape[2]
    cols = first + jnp.arange(n, dtype=jnp.int32)
    mp = ck["ptab"].shape[1]
    page = jnp.where(cols < mp * pg, jnp.take(
        ck["ptab"][slot], jnp.minimum(cols // pg, mp - 1)), n_pages)
    off = cols % pg
    flat = pages.reshape(-1, pages.shape[-1])
    row = (li * n_pages + page) * pg + off
    return jnp.take(flat, jnp.where(page < n_pages, row, flat.shape[0]),
                    axis=0, mode="fill", fill_value=0)


def prefill_attention(q, k, v, seg_of, seg_slots, seg_start, ck, li, expand,
                      scale: float, continued: bool = False):
    """Packed ragged prefill, materialised (module doc). q, k [N, H, dq]
    (this pack's queries and per-head keys ``[k_nope | r]``), v [N, H, dv];
    seg_of [N], seg_slots, seg_start [B] as ops/ragged_prefill.py has them;
    ``ck`` the whole latent cache, read at layer ``li`` and only when
    ``continued`` (static); ``expand(rows [n, Wd]) -> (k [n, H, dq], v [n, H, dv])``.
    -> [N, H, dv] in q's dtype."""
    dtype, f32 = q.dtype, jnp.float32
    N, H, _ = q.shape
    dv = v.shape[-1]
    sc_pack = jnp.einsum("nhd,mhd->hnm", q, k).astype(f32) * scale
    idx = jnp.arange(N, dtype=jnp.int32)
    mask_pack = (seg_of[:, None] == seg_of[None, :]) \
        & (idx[None, :] <= idx[:, None])                       # [N(q), N(k)]
    sc_pack = jnp.where(mask_pack[None], sc_pack, _NEG_INF)
    if not continued:
        probs = jax.nn.softmax(sc_pack, axis=-1).astype(dtype)
        return jnp.einsum("hnm,mhd->nhd", probs, v)

    S = ck["ptab"].shape[0]

    def segment(carry, seg):
        b, slot, start = seg
        mine = seg_of == b                                       # [N]

        def block(i, c):
            m, l, acc = c
            kb, vb = expand(_slot_block(ck, li, jnp.minimum(slot, S - 1),
                                        i * _BLOCK, _BLOCK))
            sc = jnp.einsum("nhd,chd->hnc", q, kb.astype(dtype)) \
                .astype(f32) * scale
            cols = i * _BLOCK + jnp.arange(_BLOCK, dtype=jnp.int32)
            seen = mine[:, None] & (cols[None, :] < start)       # [N, blk]
            sc = jnp.where(seen[None], sc, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            # explicit zero for unseen columns: an all-unseen row has
            # m_new == _NEG_INF and exp(sc - m_new) would be 1 there
            p = jnp.where(seen[None], jnp.exp(sc - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "hnc,chd->hnd", p, vb.astype(f32))
            return m_new, l * alpha + jnp.sum(p, axis=-1), acc

        return jax.lax.fori_loop(0, (start + _BLOCK - 1) // _BLOCK, block,
                                 carry), None

    B = seg_slots.shape[0]
    init = (jnp.full((H, N), _NEG_INF, f32), jnp.zeros((H, N), f32),
            jnp.zeros((H, N, dv), f32))
    # the walk over committed rows under a name of its own: a device trace
    # can tell it from the pack's own products
    with jax.named_scope(WALK_SCOPE):
        (m_c, l_c, a_c), _ = jax.lax.scan(
            segment, init,
            (jnp.arange(B, dtype=jnp.int32), seg_slots, seg_start))
    # the joint softmax over [committed rows, pack]: every token sees at
    # least itself in the pack, so the total is finite
    m_tot = jnp.maximum(m_c, jnp.max(sc_pack, axis=-1))
    p_pack = jnp.where(mask_pack[None], jnp.exp(sc_pack - m_tot[..., None]),
                       0.0)
    alpha = jnp.exp(m_c - m_tot)
    denom = l_c * alpha + jnp.sum(p_pack, axis=-1)
    out = (a_c * alpha[..., None] + jnp.einsum(
        "hnm,mhd->hnd", p_pack, v.astype(f32))) / denom[..., None]
    return out.transpose(1, 0, 2).astype(dtype)
