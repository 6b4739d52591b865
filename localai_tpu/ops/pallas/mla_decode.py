"""Pallas TPU kernel: absorbed MLA decode attention over the latent page pool
(ops/mla.py has the two forms and the pool).

H query heads attend over ONE shared row a token, and the row is key and
value at once (the values are its first ``rank`` columns), so a page is
fetched ONCE where ops/pallas/paged_attention.py fetches a K and a V page.
Otherwise this is that module's ring driver: grid ``(S,)``, one program a
slot; the stacked pool stays in HBM (``pl.ANY``) and comes in whole with the
layer index, the page table and the lengths as scalar-prefetch arguments; the
program loops over the slot's OWN pages, fetching each with
``make_async_copy`` into a ring of two VMEM page buffers, one page ahead of
the one it computes on. A slot of length 0 (idle) fetches no page and
computes nothing but its own token. A page's face ``[page, Wd]`` is whole
tiles of the pool's layout (``Wd`` a multiple of 128, ``page`` of 16), which
is what Mosaic copies out of an HBM array.

The softmax is accumulated online across pages in float32; the current
token's own row is appended from registers after the last page, as
ops/mla.py::decode_attention's jnp form does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(ptab_ref, len_ref, layer_ref, q_ref, new_ref, pool_ref, out_ref,
            ring, sem, m_ref, l_ref, acc_ref):
    """One slot's program: q [1, H, Wd] float32 (absorbed, scaled); new
    [1, 1, Wd] the token's own row; pool [L, n_pages, Pg, Wd] in HBM; ring
    [2, Pg, Wd]; out [1, H, R]; m, l [H, 1] and acc [H, R] the online
    softmax."""
    s = pl.program_id(0)
    length = len_ref[s]
    n_pages = pool_ref.shape[1]
    pg = ring.shape[1]
    R = acc_ref.shape[-1]
    held = (length + pg - 1) // pg        # the pages the slot holds
    f32 = jnp.float32

    def copy(i):
        """Page i of the slot -> place i % 2 of the ring."""
        pid = jnp.clip(ptab_ref[s, i], 0, n_pages - 1)
        return pltpu.make_async_copy(pool_ref.at[layer_ref[0], pid],
                                     ring.at[i % 2], sem.at[i % 2])

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(held > 0)
    def _prime():
        copy(0).start()

    q = q_ref[0]                                             # [H, Wd]

    def page(i, carry):
        # the place page i - 1 has just left takes page i + 1
        @pl.when(i + 1 < held)
        def _ahead():
            copy(i + 1).start()

        copy(i).wait()
        rows = ring[i % 2].astype(f32)                       # [Pg, Wd]
        sc = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)     # [H, Pg]
        col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) + i * pg
        sc = jnp.where(col < length, sc, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, rows[:, :R], (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, held, page, 0)
    # the token's own row, from registers
    new = new_ref[0].astype(f32)                             # [1, Wd]
    s_self = jnp.sum(q * new, axis=-1, keepdims=True)        # [H, 1]
    m_fin = jnp.maximum(m_ref[...], s_self)
    alpha = jnp.exp(m_ref[...] - m_fin)
    p_self = jnp.exp(s_self - m_fin)
    denom = l_ref[...] * alpha + p_self
    out_ref[0] = ((acc_ref[...] * alpha + p_self * new[:, :R])
                  / denom).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "interpret"))
def mla_paged_decode(q_abs, new_row, pages, ptab, lengths, layer=0, *,
                     rank: int, interpret: bool = False):
    """q_abs [S, H, Wd] float32; new_row [S, 1, Wd]; pages [L, n_pages,
    page, 1, Wd] (the latent pool of ops/mla.py: one "KV head"); ptab
    [S, max_pages] int32; lengths [S]; layer: int32 scalar, traced inside
    the scan over layers -> [S, H, rank] float32. Semantics match
    ops/mla.py::decode_attention's jnp form."""
    S, H, Wd = q_abs.shape
    L, n_pages, pg = pages.shape[:3]
    pool = pages.reshape(L, n_pages, pg, Wd)   # the unit axis: no data moves
    f32 = jnp.float32
    return pl.pallas_call(
        _kernel,
        # the custom call's name in a profiler capture: the benchmark's
        # mla_decode_roofline finds the kernel by it
        name="mla_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # ptab, lengths, layer
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, Wd), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((1, 1, Wd), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, rank), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, pg, Wd), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((H, 1), f32), pltpu.VMEM((H, 1), f32),
                            pltpu.VMEM((H, rank), f32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), f32),
        interpret=interpret,
    )(ptab, lengths, jnp.asarray(layer, jnp.int32).reshape(1),
      q_abs.astype(f32), new_row, pool)
