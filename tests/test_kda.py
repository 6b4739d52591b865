"""Kimi delta attention (ops/kda.py, ops/pallas/kda_decode.py): the chunked
prefill form and the one-token decode forms against the token-by-token
definition, with the decay a vector over the key channels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops import gated_delta as gd
from localai_tpu.ops import kda
from localai_tpu.ops.pallas.kda_decode import kda_decode_pallas

H, K, V = 3, 16, 16


def _inputs(key, T, g_fixed=None):
    ks = jax.random.split(key, 5)
    q = gd.l2norm(jax.random.normal(ks[0], (T, H, K))) * K ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (T, H, K)))
    v = jax.random.normal(ks[2], (T, H, V))
    g = kda.MIN_LOG_DECAY * jax.nn.sigmoid(
        2.0 * jax.random.normal(ks[3], (T, H, K)) - 1.0)
    if g_fixed is not None:
        g = jnp.full((T, H, K), g_fixed, jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta


def test_the_definition_is_the_papers_recurrence():
    """S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T, spelled out
    with matrices for one head."""
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(0), 5)
    o, s = kda.kda_recurrent(q, k, v, g, beta, jnp.zeros((H, K, V)))
    S = np.zeros((K, V))
    for t in range(5):
        kt = np.asarray(k[t, 0], np.float64)
        S = (np.eye(K) - float(beta[t, 0]) * np.outer(kt, kt)) \
            @ np.diag(np.exp(np.asarray(g[t, 0], np.float64))) @ S \
            + float(beta[t, 0]) * np.outer(kt, np.asarray(v[t, 0]))
        np.testing.assert_allclose(np.asarray(o[t, 0]),
                                   S.T @ np.asarray(q[t, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s[0]), S, atol=1e-5)


@pytest.mark.parametrize("lens,starts", [
    ([40], [0]), ([16, 1, 35], [0, 0, 0]), ([7, 50], [0, 9]),
    ([33, 0, 20], [5, 0, 0])], ids=["one", "three", "continued", "empty"])
def test_chunked_pack_agrees_with_the_definition(lens, starts):
    B, N = len(lens), 64
    off = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    parts = [_inputs(jax.random.PRNGKey(10 + b), lens[b]) for b in range(B)]
    s0 = jnp.where(jnp.asarray(starts)[:, None, None, None] > 0,
                   jax.random.normal(jax.random.PRNGKey(3), (B, H, K, V)), 0)
    want = [kda.kda_recurrent(*parts[b], s0[b]) for b in range(B)]
    pack = [jnp.zeros((N,) + a.shape[1:]).at[:sum(lens)].set(
        jnp.concatenate([p[i] for p in parts]))
        for i, a in enumerate(parts[0])]
    plan = gd.chunk_plan(jnp.asarray(off), jnp.asarray(lens, jnp.int32), N,
                         chunk=kda.CHUNK)
    o, finals = jax.jit(kda.kda_chunk)(*pack, s0, plan)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(o[off[b]:off[b] + lens[b]]), np.asarray(want[b][0]),
            atol=2e-5)
        np.testing.assert_allclose(np.asarray(finals[b]),
                                   np.asarray(want[b][1]), atol=2e-5)


def test_256_tokens_at_the_lower_bound_throughout_stay_finite_and_right():
    """g = -5 on every channel of every token: a chunk's inverse decay
    reaches exp(80), inside float32 only because a chunk is 16 tokens."""
    T = 256
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(5), T,
                               g_fixed=kda.MIN_LOG_DECAY)
    s0 = jax.random.normal(jax.random.PRNGKey(6), (1, H, K, V))
    plan = gd.chunk_plan(jnp.zeros((1,), jnp.int32),
                         jnp.asarray([T], jnp.int32), T, chunk=kda.CHUNK)
    o, finals = jax.jit(kda.kda_chunk)(q, k, v, g, beta, s0, plan)
    ro, rs = kda.kda_recurrent(q, k, v, g, beta, s0[0])
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro), atol=2e-5)
    np.testing.assert_allclose(np.asarray(finals[0]), np.asarray(rs),
                               atol=2e-5)
    assert kda.CHUNK * -kda.MIN_LOG_DECAY < 88       # float32's exp range


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_decode_agrees_with_the_definition_and_leaves_idle_slots(form):
    L, S = 2, 5
    state = jax.random.normal(jax.random.PRNGKey(1), (L, S, H, K, V))
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(2), S)
    active = jnp.asarray([True, False, True, True, False])
    if form == "jnp":
        fn = jax.jit(kda.kda_decode)
    else:
        fn = jax.jit(lambda *a: kda_decode_pallas(*a, interpret=True))
    o, new = fn(state, jnp.int32(1), q, k, v, g, beta, active)
    for s in range(S):
        ro, rs = kda.kda_recurrent(q[s:s + 1], k[s:s + 1], v[s:s + 1],
                                   g[s:s + 1], beta[s:s + 1], state[1, s])
        if active[s]:
            np.testing.assert_allclose(np.asarray(o[s]), np.asarray(ro[0]),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(new[1, s]), np.asarray(rs),
                                       atol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(new[1, s]),
                                          np.asarray(state[1, s]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))


def test_pallas_decode_with_no_slot_live_changes_nothing():
    state = jax.random.normal(jax.random.PRNGKey(1), (1, 3, H, K, V))
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(2), 3)
    o, new = kda_decode_pallas(state, jnp.int32(0), q, k, v, g, beta,
                               jnp.zeros((3,), bool), interpret=True)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(state))
    assert not np.asarray(o).any()


def test_a_scalar_decay_gives_the_gated_delta_rule():
    """With every channel of a head at one decay KDA is ops/gated_delta.py's
    rule."""
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(7), 20)
    g1 = g[..., 0]
    s0 = jnp.zeros((H, K, V))
    a = kda.kda_recurrent(q, k, v, jnp.broadcast_to(g1[..., None], g.shape),
                          beta, s0)
    b = gd.gated_delta_recurrent(q, k, v, g1, beta, s0)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]), atol=1e-6)
