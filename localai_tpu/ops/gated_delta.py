"""The gated delta rule (gated DeltaNet linear attention) in jax.numpy.

Per head, with a state ``S`` in R^{K x V}, a token's normalised query and
key ``q, k`` in R^K, value ``v`` in R^V, write strength ``beta`` and log
decay ``g <= 0`` (``alpha = exp(g)``):

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

Three forms of the same rule:

  ``gated_delta_recurrent``  token by token: the definition, the tests'
                             yardstick. Never on the served path.
  ``gated_delta_chunk``      prefill: chunks of 64 tokens over a pack of
                             several segments (below).
  ``gated_delta_decode``     one token a slot, on the STACKED state
                             ``[L_lin, S, H, K, V]`` at a layer index
                             (ops/pallas/gated_delta.py is the TPU kernel).

The chunked form. Inside a chunk, with ``G_i = sum_{j<=i} g_j`` and the
pseudo-values ``u_i = beta_i (v_i - alpha_i S_{i-1}^T k_i)``:

    S_t = exp(G_t) S_0 + sum_{i<=t} exp(G_t - G_i) k_i u_i^T
    (I + A) U = diag(beta) (V - diag(exp G) K S_0),
        A_ti = beta_t exp(G_t - G_i) (k_t . k_i) for i < t, else 0

so ``U = W - Y S_0`` with ``W = (I+A)^-1 diag(beta) V`` and
``Y = (I+A)^-1 diag(beta exp G) K``: the unit-lower-triangular system is
solved once a chunk for every chunk of the pack at once (no state in it),
and what is sequential is three small matmuls a chunk:

    U  = W - Y S
    O  = (exp(G) * Q) S + (Q K^T * D) U        D_ti = exp(G_t - G_i), i <= t
    S' = exp(G_C) S + (exp(G_C - G) * K)^T U

Every exponent is <= 0 (no division by a decay), so nothing overflows
however strong the decay. The state path runs in float32 at ``highest``
matmul precision: on a TPU a float32 dot is otherwise one bfloat16 pass,
and the state is what the configuration states as float32.

Segments. A pack holds several requests' prompt pieces back to back. Each
segment is cut into chunks of its own (``chunk_plan``: at most
``N/64 + B`` chunks for N tokens in B segments), so a chunk never spans
two requests; a chunk that opens a segment starts from that segment's
state (zero when fresh, the slot's when continued), one that closes it
leaves the state in ``finals``. The loop over chunks runs only as far as
the pack has chunks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def gated_delta_recurrent(q, k, v, g, beta, s0):
    """The definition. q, k [T, H, K]; v [T, H, V]; g, beta [T, H];
    s0 [H, K, V] -> (o [T, H, V], s [H, K, V]), float32."""
    f32 = jnp.float32

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, None, None]
        ks = jnp.einsum("hkv,hk->hv", s, kt, precision=_HI)
        u = bt[:, None] * (vt - ks)
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=_HI)

    s, o = jax.lax.scan(step, s0.astype(f32), tuple(
        a.astype(f32) for a in (q, k, v, g, beta)))
    return o, s


def chunk_plan(seg_off, seg_len, n_tokens: int, chunk: int = CHUNK):
    """How a pack of ``n_tokens`` in B segments falls into chunks, none of
    which spans two segments. -> dict of int32 arrays over NC = n_tokens /
    chunk + B chunks: ``tok`` [NC, chunk] pack index of each position
    (clipped for pads), ``valid`` [NC, chunk], ``seg`` [NC], ``first`` /
    ``last`` [NC] (the chunk opens / closes its segment), and ``n`` the
    number of chunks in use (chunks past it are pads)."""
    B = seg_len.shape[0]
    NC = n_tokens // chunk + B
    per = (seg_len + chunk - 1) // chunk                    # [B]
    ends = jnp.cumsum(per)
    c = jnp.arange(NC, dtype=jnp.int32)
    seg = jnp.minimum(jnp.searchsorted(ends, c, side="right"), B - 1)
    seg = seg.astype(jnp.int32)
    j = c - (jnp.take(ends, seg) - jnp.take(per, seg))      # chunk in segment
    used = c < ends[-1]
    start = jnp.take(seg_off, seg) + j * chunk
    left = jnp.where(used, jnp.take(seg_len, seg) - j * chunk, 0)
    pos = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    valid = pos < left[:, None]
    tok = jnp.clip(start[:, None] + pos, 0, n_tokens - 1)
    return {"tok": tok, "valid": valid, "seg": seg,
            "first": used & (j == 0),
            "last": used & (j == jnp.take(per, seg) - 1),
            "n": ends[-1].astype(jnp.int32)}


def gated_delta_chunk(q, k, v, g, beta, s0, plan):
    """Chunked prefill over a pack. q, k [N, H, K] (normalised, q scaled);
    v [N, H, V]; g, beta [N, H]; s0 [B, H, K, V] each segment's starting
    state; ``plan`` from ``chunk_plan``.
    -> (o [N, H, V] float32, finals [B, H, K, V] float32: the state after
    each segment's last token; a segment of no tokens keeps ``s0``)."""
    f32 = jnp.float32
    N, H, K = q.shape
    s0 = jnp.asarray(s0)
    tok, valid = plan["tok"], plan["valid"]
    NC, C = tok.shape

    def chunks(x):                       # [N, H, ...] -> [NC, H, C, ...]
        return jnp.moveaxis(jnp.take(x.astype(f32), tok, axis=0), 2, 1)

    vm = jnp.moveaxis(jnp.broadcast_to(valid[:, :, None], (NC, C, H)), 2, 1)
    qc, kc, vc = chunks(q), chunks(k), chunks(v)             # [NC, H, C, *]
    # a pad position is inert: no write (beta 0), no decay (g 0)
    gc = jnp.where(vm, chunks(g), 0.0)
    bc = jnp.where(vm, chunks(beta), 0.0)                    # [NC, H, C]
    G = jnp.cumsum(gc, axis=-1)
    tri = jnp.tril(jnp.ones((C, C), bool))
    # the exponent is masked before exp: above the diagonal it is >= 0
    D = jnp.exp(jnp.where(tri, G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = jnp.einsum("nhck,nhdk->nhcd", kc, kc, precision=_HI)
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                  bc[..., :, None] * D * kk, 0.0)
    rhs = jnp.concatenate([bc[..., None] * vc,
                           (bc * jnp.exp(G))[..., None] * kc], -1)
    wy = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    W, Y = wy[..., :vc.shape[-1]], wy[..., vc.shape[-1]:]
    qk = jnp.einsum("nhck,nhdk->nhcd", qc, kc, precision=_HI) * D
    q_in = jnp.exp(G)[..., None] * qc                # reads the carried state
    k_out = jnp.exp(G[..., -1:] - G)[..., None] * kc  # what reaches the end
    g_end = jnp.exp(G[..., -1])                              # [NC, H]

    def body(c, carry):
        s, finals, o = carry
        b = plan["seg"][c]
        s = jnp.where(plan["first"][c], s0[b].astype(f32), s)
        u = W[c] - jnp.einsum("hck,hkv->hcv", Y[c], s, precision=_HI)
        oc = jnp.einsum("hck,hkv->hcv", q_in[c], s, precision=_HI) \
            + jnp.einsum("hcd,hdv->hcv", qk[c], u, precision=_HI)
        s = g_end[c][:, None, None] * s \
            + jnp.einsum("hck,hcv->hkv", k_out[c], u, precision=_HI)
        finals = finals.at[b].set(jnp.where(plan["last"][c], s, finals[b]))
        return s, finals, o.at[c].set(oc)

    init = (jnp.zeros(s0.shape[1:], f32), s0.astype(f32),
            jnp.zeros((NC, H, C, vc.shape[-1]), f32))
    _, finals, o = jax.lax.fori_loop(0, plan["n"], body, init)
    # back to pack order: every real token is in exactly one chunk
    flat = jnp.where(valid, tok, N).reshape(-1)
    o = jnp.zeros((N + 1, H, o.shape[-1]), f32).at[flat].set(
        jnp.moveaxis(o, 1, 2).reshape(NC * C, H, -1), mode="drop")
    return o[:N], finals


def gated_delta_decode(delta, li, q, k, v, g, beta, active):
    """One token a slot on the stacked state. delta [L_lin, S, H, K, V];
    ``li`` the linear layer; q, k [S, H, K]; v [S, H, V]; g, beta [S, H];
    active [S] bool -> (o [S, H, V] float32, delta).

    Written as multiplies and reductions rather than dots so that XLA
    fuses the read of ``delta[li]`` into them and updates the carry in
    place: a dot wants its operand materialised, which would copy a
    layer of state out of the scan carry and back (PERF.md section 6,
    PR 27, found the same for the page pool)."""
    f32 = jnp.float32
    s = jax.lax.dynamic_index_in_dim(delta, li, 0, keepdims=False)
    sd = s.astype(f32) * jnp.exp(g.astype(f32))[..., None, None]
    ks = jnp.sum(sd * k.astype(f32)[..., None], axis=-2)         # [S, H, V]
    u = beta.astype(f32)[..., None] * (v.astype(f32) - ks)
    new = sd + k.astype(f32)[..., None] * u[..., None, :]
    o = jnp.sum(new * q.astype(f32)[..., None], axis=-2)
    new = jnp.where(active[:, None, None, None], new.astype(delta.dtype), s)
    return o, jax.lax.dynamic_update_index_in_dim(delta, new, li, 0)
