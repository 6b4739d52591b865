"""Kimi delta attention (KDA): the delta rule with a decay PER KEY CHANNEL,
in jax.numpy.

Per head, with a state ``S`` in R^{K x V}, a token's normalised query and
key ``q, k`` in R^K, value ``v`` in R^V, write strength ``beta`` and log
decay ``g`` in R^K, ``g <= 0`` (``alpha = exp(g)``, a vector over the key
channels where ops/gated_delta.py's is one number a head):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Three forms of the same rule, as ops/gated_delta.py has them for the scalar
decay; the pack's chunk plan (``chunk_plan``), ``l2norm`` and the layout of
the state leaf ``[L, S, H, K, V]`` float32 are that module's:

  ``kda_recurrent``  token by token: the definition, the tests' yardstick.
  ``kda_chunk``      prefill: chunks of 16 tokens over a pack of segments.
  ``kda_decode``     one token a slot on the stacked state at a layer index
                     (ops/pallas/kda_decode.py is the TPU kernel).

The chunked form. Inside a chunk, with ``G_i = sum_{j<=i} g_j`` (a vector)
and the pseudo-values ``u_i = beta_i (v_i - (Diag(alpha_i) S_{i-1})^T k_i)``:

    S_t = Diag(exp G_t) S_0 + sum_{i<=t} Diag(exp(G_t - G_i)) k_i u_i^T
    (I + A) U = diag(beta) (V - (exp(G) * K) S_0),
        A_ti = beta_t sum_c k_tc k_ic exp(G_tc - G_ic)   for i < t, else 0

With one decay a head ``exp(G_t - G_i)`` comes out of ``k_t . k_i`` as a
factor; with one a channel it does not, and the product over channels has
to be a matrix product of ``k_t * exp(G_t)`` with ``k_i * exp(-G_i)``: a
DIVISION BY A DECAY. ``exp(-G_i)`` is bounded only because ``G`` is counted
inside the chunk and a chunk is ``CHUNK`` = 16 tokens: the model's gate
holds ``g >= kda_lower_bound = -5`` a token, so ``|G_t - G_i| <= 80 < 88``,
float32's range (what the lower bound exists for). Both factors are counted
from the chunk's middle row (``|.| <= 40`` each): counted from its first,
``exp(G_t)`` times a small channel is a denormal at the chunk's end, which
the hardware flushes to zero. Every other exponent is <= 0. The chunk plan
cuts at segment borders, so ``G`` never runs over two requests. What is
sequential is, as for the scalar rule, three small products a chunk:

    U  = W - Y S          W = (I+A)^-1 diag(beta) V,  Y = (I+A)^-1 diag(beta) (exp(G) * K)
    O  = (exp(G) * Q) S + (sum_c q_tc k_ic exp(G_tc - G_ic), i <= t) U
    S' = Diag(exp G_C) S + (exp(G_C - G) * K)^T U

The state path runs in float32 at ``highest`` matmul precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 16
# the most a token's log decay may be below 0 for CHUNK tokens of it to stay
# inside float32's range (module doc): CHUNK * 5 = 80 < 88
MIN_LOG_DECAY = -5.0
_HI = jax.lax.Precision.HIGHEST


def kda_recurrent(q, k, v, g, beta, s0):
    """The definition. q, k, g [T, H, K]; v [T, H, V]; beta [T, H];
    s0 [H, K, V] -> (o [T, H, V], s [H, K, V]), float32."""
    f32 = jnp.float32

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, :, None]
        ks = jnp.einsum("hkv,hk->hv", s, kt, precision=_HI)
        u = bt[:, None] * (vt - ks)
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=_HI)

    s, o = jax.lax.scan(step, s0.astype(f32), tuple(
        a.astype(f32) for a in (q, k, v, g, beta)))
    return o, s


def kda_chunk(q, k, v, g, beta, s0, plan):
    """Chunked prefill over a pack. q, k [N, H, K] (normalised, q scaled);
    v [N, H, V]; g [N, H, K] (each >= ``MIN_LOG_DECAY``); beta [N, H];
    s0 [B, H, K, V] each segment's starting state; ``plan`` from
    ``gated_delta.chunk_plan(..., chunk=CHUNK)``.
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
    gc = jnp.where(vm[..., None], chunks(g), 0.0)            # [NC, H, C, K]
    bc = jnp.where(vm, chunks(beta), 0.0)                    # [NC, H, C]
    G = jnp.cumsum(gc, axis=-2)
    k_dec = jnp.exp(G) * kc              # what reads the carried state
    q_dec = jnp.exp(G) * qc
    k_out = jnp.exp(G[..., -1:, :] - G) * kc     # what reaches the end
    g_end = jnp.exp(G[..., -1, :])                           # [NC, H, K]
    # the pair terms exp(G_t - G_i): the division (module doc), both
    # factors counted from the chunk's MIDDLE row, so that neither leaves
    # float32's range at either end (exp(-80) times a small channel is a
    # denormal, which the hardware flushes to zero)
    Gm = G - G[..., C // 2:C // 2 + 1, :]
    k_mid, q_mid, k_inv = jnp.exp(Gm) * kc, jnp.exp(Gm) * qc, \
        jnp.exp(-Gm) * kc
    below = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(below, bc[..., :, None] * jnp.einsum(
        "nhck,nhdk->nhcd", k_mid, k_inv, precision=_HI), 0.0)
    rhs = jnp.concatenate([bc[..., None] * vc, bc[..., None] * k_dec], -1)
    wy = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    W, Y = wy[..., :vc.shape[-1]], wy[..., vc.shape[-1]:]
    qk = jnp.where(jnp.tril(jnp.ones((C, C), bool)), jnp.einsum(
        "nhck,nhdk->nhcd", q_mid, k_inv, precision=_HI), 0.0)

    def body(c, carry):
        s, finals, o = carry
        b = plan["seg"][c]
        s = jnp.where(plan["first"][c], s0[b].astype(f32), s)
        u = W[c] - jnp.einsum("hck,hkv->hcv", Y[c], s, precision=_HI)
        oc = jnp.einsum("hck,hkv->hcv", q_dec[c], s, precision=_HI) \
            + jnp.einsum("hcd,hdv->hcv", qk[c], u, precision=_HI)
        s = g_end[c][:, :, None] * s \
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


def kda_decode(state, li, q, k, v, g, beta, active):
    """One token a slot on the stacked state. state [L, S, H, K, V]; ``li``
    the KDA layer; q, k, g [S, H, K]; v [S, H, V]; beta [S, H]; active [S]
    bool -> (o [S, H, V] float32, state). Multiplies and reductions, not
    dots, for ops/gated_delta.py::gated_delta_decode's reason: XLA fuses
    the read of ``state[li]`` into them and updates the carry in place."""
    f32 = jnp.float32
    s = jax.lax.dynamic_index_in_dim(state, li, 0, keepdims=False)
    sd = s.astype(f32) * jnp.exp(g.astype(f32))[..., None]
    ks = jnp.sum(sd * k.astype(f32)[..., None], axis=-2)         # [S, H, V]
    u = beta.astype(f32)[..., None] * (v.astype(f32) - ks)
    new = sd + k.astype(f32)[..., None] * u[..., None, :]
    o = jnp.sum(new * q.astype(f32)[..., None], axis=-2)
    new = jnp.where(active[:, None, None, None], new.astype(state.dtype), s)
    return o, jax.lax.dynamic_update_index_in_dim(state, new, li, 0)
