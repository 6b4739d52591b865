"""ops/hyper.py (manifold-constrained hyper-connections) on the CPU at a
small size, seeded random weights, against the plain float32 reference
(benchmark/reference/xing4_f32.py: the same equations written token-major
with ``jnp.sum`` over the axes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops import hyper
from localai_tpu.ops.norms import rms_norm

T, C = 9, 32
HP = hyper.HyperConfig()


def _draw(n, seed=0, heads=None):
    """X [T, n, C], a sublayer's (w [n C, outs], s, b) as the benchmark's
    maker draws them, and a sublayer's own weights."""
    rng = np.random.default_rng(seed)
    outs = heads or n * (n + 2)
    X = rng.standard_normal((T, n, C)).astype(np.float32)
    w = (rng.standard_normal((n * C, outs)) / np.sqrt(n * C)).astype(
        np.float32)
    s = (1 + 0.05 * rng.standard_normal((1 if heads else 3,))).astype(
        np.float32)
    b = (0.5 * rng.standard_normal((outs,))).astype(np.float32)
    return jnp.asarray(X), tuple(map(jnp.asarray, (w, s, b)))


def _reference(X, hc, hp, iters=None):
    """The issue's equations, a token at a time in numpy float64."""
    w, s, b = (np.asarray(a, np.float64) for a in hc)
    X = np.asarray(X, np.float64)
    n = X.shape[1]
    out = []
    for t in range(X.shape[0]):
        x = X[t].reshape(-1)
        m = (x @ w) / np.sqrt(np.mean(x * x) + hp.eps)
        sig = lambda z: 1 / (1 + np.exp(-z))           # noqa: E731
        pre = sig(s[0] * m[:n] + b[:n]) + hp.hc_eps
        post = 2 * sig(s[1] * m[n:2 * n] + b[n:2 * n])
        M = np.exp(np.clip(s[2] * m[2 * n:] + b[2 * n:], *hp.clamp)
                   ).reshape(n, n)
        for _ in range(hp.iters if iters is None else iters):
            M = M / (M.sum(1, keepdims=True) + hp.hc_eps)
            M = M / (M.sum(0, keepdims=True) + hp.hc_eps)
        out.append((pre, post, M))
    return [np.stack(a) for a in zip(*out)]


@pytest.mark.parametrize("n", [2, 4])
def test_one_sublayer_is_the_equations_written_out(n):
    X, hc = _draw(n, seed=n)
    norm_w = jnp.linspace(0.5, 1.5, C)
    f_w = jnp.asarray(np.random.default_rng(9).standard_normal((C, C)),
                      jnp.float32) / np.sqrt(C)
    u, post, M = hyper.mix(X, hc, HP)
    y = jnp.tanh(rms_norm(u, norm_w, 1e-6) @ f_w)
    got = hyper.merge(X, y, post, M)
    pre_r, post_r, M_r = _reference(X, hc, HP)
    np.testing.assert_allclose(post.T, post_r, rtol=2e-5)
    np.testing.assert_allclose(M.transpose(2, 0, 1), M_r, rtol=2e-5,
                               atol=1e-7)
    Xr = np.asarray(X, np.float64)
    u_r = np.einsum("ti,tic->tc", pre_r, Xr)
    np.testing.assert_allclose(u, u_r, rtol=2e-5, atol=1e-6)
    want = post_r[:, :, None] * np.asarray(y, np.float64)[:, None] \
        + np.einsum("tij,tjc->tic", M_r, Xr)
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-6)
    assert got.dtype == X.dtype and got.shape == X.shape


def test_the_mix_is_doubly_stochastic_after_twenty_rounds_and_not_after_one():
    X, hc = _draw(4, seed=1)
    _, _, M = hyper.weights(X, hc, HP)
    assert M.shape == (4, 4, T)
    np.testing.assert_allclose(np.asarray(M).sum(0), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(M).sum(1), 1.0, atol=1e-5)
    assert float(M.min()) > 0.001 and float(M.max()) < 0.95   # not degenerate
    _, _, M1 = hyper.weights(X, hc, HP._replace(iters=1))
    # one round: the columns sum to 1, the rows do not yet
    np.testing.assert_allclose(np.asarray(M1).sum(0), 1.0, atol=1e-5)
    assert np.abs(np.asarray(M1).sum(1) - 1.0).max() > 0.02
    np.testing.assert_allclose(
        M1.transpose(2, 0, 1), _reference(X, hc, HP, iters=1)[2], rtol=2e-5)


def test_the_clamp_comes_before_the_exponential():
    X, (w, s, b) = _draw(4, seed=2)
    big = b.at[8:].add(jnp.asarray([200.0] + [0.0] * 15))
    _, _, M = hyper.weights(X, (w, s, big), HP)
    assert np.isfinite(np.asarray(M)).all()
    np.testing.assert_allclose(
        M.transpose(2, 0, 1), _reference(X, (w, s, big), HP)[2], rtol=1e-4,
        atol=1e-7)


def test_readout_is_the_pre_weights_of_its_own_matrix():
    X, hc = _draw(4, seed=3, heads=4)
    w, s, b = (np.asarray(a, np.float64) for a in hc)
    Xr = np.asarray(X, np.float64)
    x = Xr.reshape(T, -1)
    m = (x @ w) / np.sqrt(np.mean(x * x, -1, keepdims=True) + HP.eps)
    pre = 1 / (1 + np.exp(-(s[0] * m + b))) + HP.hc_eps
    np.testing.assert_allclose(hyper.readout(X, hc, HP),
                               np.einsum("ti,tic->tc", pre, Xr), rtol=2e-5,
                               atol=1e-6)


def test_one_stream_with_neutral_weights_is_the_plain_residual():
    """``hc_mult`` 1, scales 0, the pre-bias far up, the others 0: pre = 1,
    post = 1, M = 1, so ``X' = X + F(norm(X))``."""
    X, (w, _, _) = _draw(1, seed=4)
    hc = (w, jnp.zeros((3,)), jnp.asarray([30.0, 0.0, 0.0]))
    u, post, M = hyper.mix(X, hc, HP)
    np.testing.assert_allclose(u, X[:, 0], rtol=1e-5)
    y = jnp.tanh(u)
    np.testing.assert_allclose(hyper.merge(X, y, post, M)[:, 0],
                               X[:, 0] + y, rtol=1e-4, atol=1e-5)


def test_bfloat16_streams_keep_the_mixes_in_float32():
    X, hc = _draw(4, seed=5)
    Xb = X.astype(jnp.bfloat16)
    pre, post, M = hyper.weights(Xb, hc, HP)
    assert pre.dtype == post.dtype == M.dtype == jnp.float32
    want = _reference(Xb.astype(jnp.float32), (
        hc[0].astype(jnp.bfloat16).astype(jnp.float32), *hc[1:]), HP)
    np.testing.assert_allclose(M.transpose(2, 0, 1), want[2], rtol=1e-4,
                               atol=1e-6)
    u, post, M = hyper.mix(Xb, hc, HP)
    assert u.dtype == jnp.bfloat16
    assert hyper.merge(Xb, u, post, M).dtype == jnp.bfloat16
    # float32 is no option of the program's: the comparison's control
    # computes the REFERENCE's mixes in bfloat16 (benchmark/families/xing4.py)
    assert "dtype" not in hyper.HyperConfig._fields


def test_the_ops_run_under_the_scope_the_trace_finds():
    X, hc = _draw(4)
    text = jax.jit(lambda X, hc: hyper.mix(X, hc, HP)).lower(
        X, hc).as_text(debug_info=True)
    assert "layer/hc" in text and hyper.SCOPE == "layer/hc"
