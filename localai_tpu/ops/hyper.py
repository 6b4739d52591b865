"""Manifold-constrained hyper-connections ("mHC", DeepSeek-AI, arXiv
2512.24880): ``n`` residual streams in place of one, and around every
sublayer ``F`` a learned, input-dependent mix in place of ``x += F(norm(x))``:

    x    = vec(X)                                   X [n, C], stream-major
    m    = (W x) * rsqrt(mean(x^2) + eps)           [n n + 2 n]
    pre  = sigmoid(s0 m[:n] + b[:n]) + hc_eps       what F reads of each stream
    post = 2 sigmoid(s1 m[n:2n] + b[n:2n])          what each stream takes of F
    M    = exp(clip(s2 m[2n:] + b[2n:], lo, hi)) as [n, n], then ``iters``
           Sinkhorn rounds (rows /= their sum + hc_eps, columns likewise):
           doubly stochastic, the manifold of the name
    u    = sum_i pre[i] X[i]                        F's input, before its norm
    X'[i] = post[i] F(norm(u)) + sum_j M[i, j] X[j]

and before the head a read-out ``h = sum_i (sigmoid(s m_h + b) + hc_eps)[i]
X[i]`` from an ``[n, n C]`` matrix. ``mix`` gives a sublayer its input and
its weights (``weights`` those alone), ``merge`` writes the streams back,
``readout`` is the head's.

The streams ``X [T, n, C]`` are held in the model's activation dtype; what
is computed from them here is float32: the product's accumulation, the norm,
the three nonlinearities, the Sinkhorn rounds, and both weighted sums, which
are cast back once. The weights come back feature-major, ``post [n, T]`` and
``M [n, n, T]``, and the rounds run over the n n entries as SEPARATE ``[T]``
vectors (``sinkhorn``), so that what a round does is elementwise over tokens
and XLA fuses across rounds. Over one array with ``M.sum(axis)``, or with
sums of an array's slices, a round is four operations that do not fuse into
the next (an entry needs its whole row from the round before): 1237 of the
2394 operations a decode step of models/xing4.py executes, against 685 of
1870 in this form, counted in the program compiled for a v5e
(tests/test_tpu_compile.py holds the count; what it is in time is
``hc_share_pct``'s to say). All of it runs under
``jax.named_scope("layer/hc")``: the benchmark's ``hc_share_pct`` finds its
device time by that name.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_scope = jax.named_scope
SCOPE = "layer/hc"


class HyperConfig(NamedTuple):
    """What of a model's config the mixes read."""
    eps: float = 1e-6               # rms_norm_eps, in the streams' norm
    hc_eps: float = 1e-6
    iters: int = 20                 # hc_sinkhorn_iters
    clamp: tuple = (-30.0, 30.0)    # mhc_h_res_clamp_min / max


def _moments(X, w, hp: HyperConfig):
    """X [T, n, C], w [n C, k] -> m [k, T] (module doc), float32."""
    x = X.reshape(X.shape[0], -1)
    # operands as the streams hold them: bfloat16 products are exact in the
    # float32 accumulator; float32 streams (tests) take the full product
    m = jnp.einsum("tc,ck->kt", x, w.astype(x.dtype), precision=_HIGHEST,
                   preferred_element_type=_F32)
    xf = x.astype(_F32)
    return m * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + hp.eps)[None]


def sinkhorn(M, iters: int, eps: float):
    """M [n, n, T] positive -> ``iters`` rounds of rows then columns, each
    divided by its sum + eps: [n, n, T], doubly stochastic in the limit.
    The entries are taken apart once, as n n vectors [T], and a sum's
    reciprocal is taken once for the n entries it divides (module doc)."""
    n = M.shape[0]
    m = [[M[i, j] for j in range(n)] for i in range(n)]
    for _ in range(iters):
        for i in range(n):
            inv = 1.0 / (sum(m[i][1:], m[i][0]) + eps)
            m[i] = [a * inv for a in m[i]]
        for j in range(n):
            inv = 1.0 / (sum((m[i][j] for i in range(1, n)), m[0][j]) + eps)
            for i in range(n):
                m[i][j] = m[i][j] * inv
    return jnp.stack([jnp.stack(row) for row in m])


def _weighted(weights, X):
    """weights [n, T], X [T, n, C] -> sum_i weights[i] X[:, i] [T, C],
    float32."""
    terms = [weights[i][:, None] * X[:, i].astype(_F32)
             for i in range(X.shape[1])]
    return sum(terms[1:], terms[0])


def weights(X, hc, hp: HyperConfig):
    """A sublayer's three weights (module doc): X [T, n, C]; hc = (w [n C,
    n n + 2 n], s [3], b [n n + 2 n]) -> (pre [n, T], post [n, T], M
    [n, n, T]), float32."""
    w, s, b = hc
    n = X.shape[1]
    with _scope(SCOPE):
        m = _moments(X, w, hp)
        s, b = s.astype(_F32), b.astype(_F32)[:, None]
        pre = jax.nn.sigmoid(s[0] * m[:n] + b[:n]) + hp.hc_eps
        post = 2.0 * jax.nn.sigmoid(s[1] * m[n:2 * n] + b[n:2 * n])
        A = jnp.clip(s[2] * m[2 * n:] + b[2 * n:], *hp.clamp)
        return pre, post, sinkhorn(jnp.exp(A).reshape(n, n, -1), hp.iters,
                                   hp.hc_eps)


def mix(X, hc, hp: HyperConfig):
    """X [T, n, C] and a sublayer's hyper-connection -> (u [T, C] in X's
    dtype: the sublayer's input before its norm, post [n, T], M [n, n, T])."""
    pre, post, M = weights(X, hc, hp)
    with _scope(SCOPE):
        return _weighted(pre, X).astype(X.dtype), post, M


def merge(X, y, post, M):
    """X'[i] = post[i] y + sum_j M[i, j] X[j]; y [T, C] -> [T, n, C] in X's
    dtype."""
    with _scope(SCOPE):
        yf = y.astype(_F32)
        return jnp.stack([post[i][:, None] * yf + _weighted(M[i], X)
                          for i in range(X.shape[1])], axis=1).astype(X.dtype)


def readout(X, hc_head, hp: HyperConfig):
    """X [T, n, C]; hc_head = (w [n C, n], s [1], b [n]) -> h [T, C]."""
    w, s, b = hc_head
    with _scope(SCOPE):
        m = _moments(X, w, hp)
        pre = jax.nn.sigmoid(s.astype(_F32)[0] * m
                             + b.astype(_F32)[:, None]) + hp.hc_eps
        return _weighted(pre, X).astype(X.dtype)
