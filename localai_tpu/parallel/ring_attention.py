"""Ring attention: causal attention with the sequence dim sharded over the
"sp" mesh axis, K/V blocks rotating around the ring via ppermute.

The reference has NO sequence/context parallelism (SURVEY §5.7 — long
context is handled inside one llama.cpp process via self-extend and
context-shift). On TPU, long-context parity is a mesh axis: each sp rank
holds one sequence block of Q/K/V; K/V blocks hop neighbor-to-neighbor
over ICI (jax.lax.ppermute) while each rank folds every visiting block
into a numerically-stable online softmax (flash-attention style m/l/o
accumulators). Compute and memory per chip stay O(T/sp * T) and O(T/sp),
and the collectives are nearest-neighbor — the layout the ICI torus is
built for.

Causality across blocks uses absolute positions derived from the visiting
block's ring index, so the result is bit-for-bit the same math as
ops.attention.causal_attention on a single device (up to fp reduction
order).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -1e30


def _block_attn(q, k, v, q_pos, k_pos, q_per_kv: int):
    """Unnormalized block attention with running-softmax stats.

    q [B, Tq, H, hd]; k/v [B, Tk, KV, hd]; q_pos [Tq], k_pos [Tk] absolute.
    Returns (scores_exp_sum l [B,KV,G,Tq], row max m [B,KV,G,Tq],
             weighted values o [B,Tq,KV,G,hd]).
    """
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Tq, KV, q_per_kv, hd)
    scale = jnp.float32(1.0) / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * scale
    mask = k_pos[None, :] <= q_pos[:, None]                      # [Tq, Tk]
    s = jnp.where(mask[None, None, None, :, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1)                                       # [B,KV,G,Tq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: m == -inf -> p would be exp(0)=1 garbage; zero them
    live = m > _NEG_INF / 2
    p = jnp.where(live[..., None], p, 0.0)
    m = jnp.where(live, m, _NEG_INF)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v).astype(jnp.float32)
    return l, m, o


def _ring_body(q, k, v, *, axis: str, n: int, q_per_kv: int):
    """shard_map body: one sequence block per sp rank."""
    idx = jax.lax.axis_index(axis)
    B, Tb, H, hd = q.shape
    KV = k.shape[2]
    G = q_per_kv
    q_pos = idx * Tb + jnp.arange(Tb, dtype=jnp.int32)

    o = jnp.zeros((B, Tb, KV, G, hd), jnp.float32)
    m = jnp.full((B, KV, G, Tb), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, KV, G, Tb), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = k, v
    for step in range(n):  # static ring walk, unrolled at trace time
        k_idx = (idx - step) % n
        k_pos = k_idx * Tb + jnp.arange(Tb, dtype=jnp.int32)
        bl, bm, bo = _block_attn(q, k_cur, v_cur, q_pos, k_pos, G)
        new_m = jnp.maximum(m, bm)
        live = new_m > _NEG_INF / 2
        alpha = jnp.where(live, jnp.exp(m - new_m), 0.0)
        beta = jnp.where(live, jnp.exp(bm - new_m), 0.0)
        l = l * alpha + bl * beta
        o = (o * alpha.transpose(0, 3, 1, 2)[..., None]
             + bo * beta.transpose(0, 3, 1, 2)[..., None])
        m = new_m
        if step + 1 < n:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)

    denom = jnp.maximum(l.transpose(0, 3, 1, 2)[..., None], 1e-20)
    out = (o / denom).reshape(B, Tb, H, hd)
    return out.astype(q.dtype)


def ring_causal_attention(q, k, v, mesh: Mesh, q_per_kv: int = 1,
                          axis: str = "sp"):
    """Causal attention with sequence sharded on ``axis``.

    q [B, T, H, hd]; k/v [B, T, KV, hd] — T must divide by mesh.shape[axis].
    Returns [B, T, H, hd] with the same sharding.
    """
    n = mesh.shape[axis]
    if n == 1:
        from localai_tpu.ops.attention import causal_attention

        valid = jnp.ones(q.shape[:2], bool)
        return causal_attention(q, k, v, valid, q_per_kv)
    spec = P(None, axis, None, None)
    body = functools.partial(_ring_body, axis=axis, n=n, q_per_kv=q_per_kv)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)


def sp_sharding(mesh: Mesh, axis: str = "sp") -> NamedSharding:
    """Sharding for [B, T, heads, hd] activations split on sequence."""
    return NamedSharding(mesh, P(None, axis, None, None))
