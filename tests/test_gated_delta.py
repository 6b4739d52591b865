"""The gated delta rule (ops/gated_delta.py, ops/pallas/gated_delta.py):
the chunked prefill form and both one-token forms against the token-by-token
recurrence, at toy width on seeded random inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops import gated_delta as gd
from localai_tpu.ops.pallas.gated_delta import gated_delta_decode_pallas

H, K, V = 4, 16, 32


def _tokens(rng, T):
    q = gd.l2norm(rng.standard_normal((T, H, K))) * K ** -0.5
    k = gd.l2norm(rng.standard_normal((T, H, K)))
    v = rng.standard_normal((T, H, V)).astype(np.float32)
    g = -0.2 * np.abs(rng.standard_normal((T, H))).astype(np.float32)
    beta = 2 * jax.nn.sigmoid(rng.standard_normal((T, H)).astype(np.float32))
    return [np.asarray(a, np.float32) for a in (q, k, v, g, beta)]


def _pack(lens, continued, n_tokens, seed):
    """A pack of segments of ``lens`` tokens (0: a pad segment), and what the
    recurrence gives for each on its own."""
    rng = np.random.default_rng(seed)
    B = len(lens)
    offs = np.cumsum([0] + list(lens[:-1])).astype(np.int32)
    s0 = rng.standard_normal((B, H, K, V)).astype(np.float32) \
        if continued else np.zeros((B, H, K, V), np.float32)
    parts = [_tokens(rng, T) for T in lens]
    pack = [np.zeros((n_tokens,) + parts[0][i].shape[1:], np.float32)
            for i in range(5)]
    for b, (o, T) in enumerate(zip(offs, lens)):
        for i in range(5):
            pack[i][o:o + T] = parts[b][i]
    want = [gd.gated_delta_recurrent(*parts[b], s0[b]) for b in range(B)]
    return pack, s0, offs, want


@pytest.mark.parametrize("lens, continued", [
    ([1], False), ([63], False), ([64], False), ([65], True),
    ([128], True), ([200], False),
    ([70, 1, 64, 130, 0], False), ([70, 1, 64, 130, 0], True),
    ([64, 64, 3], True), ([5, 0, 0, 250], True),
], ids=lambda x: str(x).replace(" ", ""))
def test_chunked_rule_equals_the_recurrence(lens, continued):
    N = 512
    pack, s0, offs, want = _pack(lens, continued, N, seed=len(lens) + sum(lens))
    plan = gd.chunk_plan(jnp.asarray(offs), jnp.asarray(lens, jnp.int32), N)
    assert int(plan["n"]) == sum(-(-T // gd.CHUNK) for T in lens)
    o, finals = jax.jit(gd.gated_delta_chunk)(*pack, s0, plan)
    for b, (of, T) in enumerate(zip(offs, lens)):
        ro, rs = want[b]
        np.testing.assert_allclose(o[of:of + T], ro, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(finals[b], rs, atol=2e-5, rtol=2e-5)
    # a segment of no tokens keeps the state it came with
    for b, T in enumerate(lens):
        if T == 0:
            np.testing.assert_array_equal(finals[b], s0[b])


def test_strong_decay_neither_overflows_nor_leaks():
    """g = -40 a token: every exponent in the chunk form is <= 0."""
    rng = np.random.default_rng(3)
    q, k, v, g, beta = _tokens(rng, 130)
    g = np.full_like(g, -40.0)
    s0 = rng.standard_normal((1, H, K, V)).astype(np.float32)
    plan = gd.chunk_plan(jnp.zeros((1,), jnp.int32),
                         jnp.asarray([130], jnp.int32), 256)
    pad = [np.pad(a, [(0, 126)] + [(0, 0)] * (a.ndim - 1))
           for a in (q, k, v, g, beta)]
    o, finals = gd.gated_delta_chunk(*pad, s0, plan)
    ro, rs = gd.gated_delta_recurrent(q, k, v, g, beta, s0[0])
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o[:130], ro, atol=2e-5)
    np.testing.assert_allclose(finals[0], rs, atol=2e-5)


def _decode_case(seed=0, L=3, S=5):
    rng = np.random.default_rng(seed)
    delta = rng.standard_normal((L, S, H, K, V)).astype(np.float32)
    q, k, v, g, beta = _tokens(rng, S)
    active = np.array([True, False, True, True, False])
    return delta, q, k, v, g, beta, active


@pytest.mark.parametrize("form", ["jnp", "pallas"])
@pytest.mark.parametrize("li", [0, 2])
def test_decode_update_is_one_step_in_place(form, li):
    delta, q, k, v, g, beta, active = _decode_case()
    if form == "jnp":
        fn = jax.jit(gd.gated_delta_decode)
    else:
        fn = jax.jit(lambda *a: gated_delta_decode_pallas(*a, interpret=True))
    o, new = fn(delta, jnp.int32(li), q, k, v, g, beta, active)
    new = np.asarray(new)
    for s in range(delta.shape[1]):
        ro, rs = gd.gated_delta_recurrent(
            *(a[s:s + 1] for a in (q, k, v, g, beta)), delta[li, s])
        if active[s]:
            np.testing.assert_allclose(new[li, s], rs, atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(o[s], ro[0], atol=1e-5, rtol=1e-5)
        else:       # an inactive slot's state is bit-identical
            np.testing.assert_array_equal(new[li, s], delta[li, s])
    for other in range(delta.shape[0]):     # and so is every other layer's
        if other != li:
            np.testing.assert_array_equal(new[other], delta[other])


def test_decode_forms_agree_and_bf16_state_does_not():
    delta, q, k, v, g, beta, active = _decode_case(seed=5)
    args = (jnp.int32(1), q, k, v, g, beta, active)
    o1, n1 = gd.gated_delta_decode(delta, *args)
    o2, n2 = gated_delta_decode_pallas(delta, *args, interpret=True)
    np.testing.assert_allclose(o1[active], o2[active], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(n1, n2, atol=1e-5, rtol=1e-5)
    _, low = gd.gated_delta_decode(jnp.asarray(delta, jnp.bfloat16), *args)
    assert low.dtype == jnp.bfloat16
    err = np.abs(np.asarray(low, np.float32) - n1)[1, active].max()
    assert 1e-3 < err < 5e-2
