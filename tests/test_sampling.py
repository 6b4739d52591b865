"""Sampling suite unit tests (hermetic, CPU)."""

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.engine import sampling


def _mk(S=2, V=64):
    sp = sampling.make_slot_params(S)
    ring, pos = sampling.make_ring(S)
    bias = jnp.zeros((S, V), jnp.float32)
    keys = jax.vmap(jax.random.key_data)(
        jax.vmap(jax.random.PRNGKey)(jnp.arange(S, dtype=jnp.uint32))
    )
    return sp, ring, pos, bias, keys


def test_greedy_picks_argmax():
    sp, ring, pos, bias, keys = _mk()
    logits = jnp.zeros((2, 64), jnp.float32).at[0, 7].set(5.0).at[1, 13].set(5.0)
    ids, logprobs, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert list(np.asarray(ids)) == [7, 13]
    assert np.all(np.asarray(logprobs) <= 0)


def test_top_k_restricts_support():
    sp, ring, pos, bias, keys = _mk()
    sp = sampling.set_slot(sp, 0, sampling.SamplingParamsHost(temperature=1.0, top_k=2, top_p=1.0))
    sp = sampling.set_slot(sp, 1, sampling.SamplingParamsHost(temperature=1.0, top_k=2, top_p=1.0))
    logits = jnp.zeros((2, 64), jnp.float32).at[:, 3].set(10.0).at[:, 9].set(9.0)
    seen = set()
    for trial in range(20):
        keys2 = jax.vmap(jax.random.key_data)(
            jax.vmap(jax.random.PRNGKey)(jnp.arange(2, dtype=jnp.uint32) + trial * 100)
        )
        ids, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys2)
        seen.update(np.asarray(ids).tolist())
    assert seen <= {3, 9}


def test_top_p_keeps_head():
    sp, ring, pos, bias, keys = _mk()
    sp = sampling.set_slot(sp, 0, sampling.SamplingParamsHost(temperature=1.0, top_k=0, top_p=0.5))
    logits = jnp.zeros((2, 64), jnp.float32).at[0, 5].set(20.0)  # ~all mass on 5
    for trial in range(10):
        keys2 = jax.vmap(jax.random.key_data)(
            jax.vmap(jax.random.PRNGKey)(jnp.arange(2, dtype=jnp.uint32) + trial)
        )
        ids, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys2)
        assert int(np.asarray(ids)[0]) == 5


def test_repeat_penalty_suppresses_seen_tokens():
    sp, ring, pos, bias, keys = _mk()
    sp = sampling.set_slot(sp, 0, sampling.SamplingParamsHost(temperature=0.0, repeat_penalty=100.0))
    ring, pos = sampling.set_slot_ring(ring, pos, 0, [7, 7, 7])
    logits = jnp.zeros((2, 64), jnp.float32).at[0, 7].set(5.0).at[0, 8].set(4.0)
    ids, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert int(np.asarray(ids)[0]) == 8  # 7 heavily penalized


def test_frequency_penalty():
    sp, ring, pos, bias, keys = _mk()
    sp = sampling.set_slot(sp, 0, sampling.SamplingParamsHost(temperature=0.0, frequency_penalty=2.0))
    ring, pos = sampling.set_slot_ring(ring, pos, 0, [7, 7, 7])  # 5.0 - 6.0 < 4.0
    logits = jnp.zeros((2, 64), jnp.float32).at[0, 7].set(5.0).at[0, 8].set(4.0)
    ids, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert int(np.asarray(ids)[0]) == 8


def test_penalty_window_expires():
    """Tokens older than repeat_last_n are NOT penalized (llama.cpp last-n)."""
    sp, ring, pos, bias, keys = _mk()
    sp = sampling.set_slot(sp, 0, sampling.SamplingParamsHost(
        temperature=0.0, repeat_penalty=100.0, repeat_last_n=2))
    # token 7 seen long ago, then two other tokens push it out of the window
    ring, pos = sampling.set_slot_ring(ring, pos, 0, [7, 1, 2])
    logits = jnp.zeros((2, 64), jnp.float32).at[0, 7].set(5.0).at[0, 8].set(4.0)
    ids, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert int(np.asarray(ids)[0]) == 7  # 7 outside window: unpenalized


def test_ring_wraps_and_updates():
    ring, pos = sampling.make_ring(2)
    active = jnp.array([True, False])
    for t in range(sampling.RING_N + 3):
        ids = jnp.array([t % 100, 55], jnp.int32)
        ring, pos = sampling.update_ring(ring, pos, ids, active)
    assert int(pos[0]) == sampling.RING_N + 3
    assert int(pos[1]) == 0
    assert np.all(np.asarray(ring[1]) == -1)  # inactive slot untouched
    # most recent write landed at (RING_N + 2) % RING_N
    assert int(ring[0, (sampling.RING_N + 2) % sampling.RING_N]) == (sampling.RING_N + 2) % 100


def test_logit_bias():
    sp, ring, pos, bias, keys = _mk()
    bias = bias.at[0, 42].set(100.0)
    logits = jnp.zeros((2, 64), jnp.float32).at[0, 7].set(5.0)
    ids, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert int(np.asarray(ids)[0]) == 42


def test_deterministic_seed():
    sp, ring, pos, bias, keys = _mk()
    sp = sampling.set_slot(sp, 0, sampling.SamplingParamsHost(temperature=1.5, top_k=0, top_p=1.0))
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 64)) * 3
    a, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    b, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mirostat_v2_adapts_mu():
    sp, ring, pos, bias, keys = _mk()
    sp = sampling.set_slot(sp, 0, sampling.SamplingParamsHost(
        temperature=1.0, mirostat=2, mirostat_tau=3.0, mirostat_eta=0.2))
    mu = sampling.make_mu(2)
    mu[0] = 6.0
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, 64)) * 2
    ids, _, _, new_mu = sampling.sample(logits, sp, ring, pos, bias, keys, mu)
    new_mu = np.asarray(new_mu)
    assert 0 <= int(ids[0]) < 64
    assert new_mu[0] != 6.0          # mu moved toward tau for the miro slot
    assert new_mu[1] == mu[1]        # non-mirostat slot untouched
    # a tiny mu forces the argmax candidate (only rank-0 survives the cut)
    mu[0] = 1e-6
    ids2, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys, mu)
    assert int(ids2[0]) == int(np.argmax(np.asarray(logits)[0]))


# ---------- the greedy branch (ISSUE 48) ----------

def _model_like_logits(S, V, seed=0):
    """Rows with a model's head shape: mass falling off as rank**-2 over a
    shuffled vocabulary, rounded through bfloat16 as the models' heads are."""
    rng = np.random.default_rng(seed)
    base = -2.0 * np.log(np.arange(1, V + 1, dtype=np.float32))
    rows = np.stack([base[rng.permutation(V)] for _ in range(S)])
    rows += rng.normal(0, 0.05, rows.shape).astype(np.float32)
    return jnp.asarray(rows, jnp.bfloat16).astype(jnp.float32)


def _window(logits, sp, ring, pos, bias, keys, mu=None):
    """The window branch called by itself: the parent's `sample`."""
    return sampling._sample_window(
        logits, sp, ring, pos, bias, keys, mu, use_penalties=True,
        use_typical=True, use_mirostat=True)


def _same_bits(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def test_greedy_rows_pick_the_same_alone_and_beside_a_sampling_row():
    S, V = 4, 1024
    sp, ring, pos, bias, keys = _mk(S, V)
    logits = _model_like_logits(S, V)
    alone = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert _same_bits(alone[2:3], [keys])        # nobody drew: keys as sent
    sampling.set_slot(sp, 3, sampling.SamplingParamsHost(temperature=0.8))
    mixed = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert np.array_equal(np.asarray(mixed[0])[:3], np.asarray(alone[0])[:3])
    # the batch with a sampling row IS the window branch, bit for bit:
    # ids, logprobs and every row's new key (fixed seed)
    assert _same_bits(mixed[:3], _window(logits, sp, ring, pos, bias, keys)[:3])
    assert not np.array_equal(np.asarray(mixed[2])[3], np.asarray(keys)[3])


def test_window_branch_keeps_mu_bit_for_bit():
    sp, ring, pos, bias, keys = _mk()
    sampling.set_slot(sp, 0, sampling.SamplingParamsHost(
        temperature=1.0, mirostat=2, mirostat_tau=3.0, mirostat_eta=0.2))
    mu = sampling.make_mu(2)
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, 64)) * 2
    assert _same_bits(sampling.sample(logits, sp, ring, pos, bias, keys, mu),
                      _window(logits, sp, ring, pos, bias, keys, mu))


def test_free_rows_do_not_vote_and_a_live_penalised_row_does():
    sp, ring, pos, bias, keys = _mk(3, 64)
    # row 2 is a free slot that last served a sampling request
    sampling.set_slot(sp, 2, sampling.SamplingParamsHost(temperature=0.8))
    live = np.array([True, True, False])
    on_device = jax.jit(lambda spp, a: sampling.all_plain_greedy(
        sampling.unpack_slot_params(spp), a))
    for active, want in ((live, True), (None, False),
                         (np.array([True, True, True]), False)):
        assert bool(sampling.all_plain_greedy(sp, active)) is want
        if active is not None:
            assert bool(on_device(sampling.pack_slot_params(sp), active)) is want
    logits = jnp.zeros((3, 64), jnp.float32).at[:, 7].set(5.0)
    out = sampling.sample(logits, sp, ring, pos, bias, keys, active=live)
    assert _same_bits(out[2:3], [keys])          # the greedy branch ran
    # one live greedy row with a penalty: the window runs for the batch
    sampling.set_slot(sp, 1, sampling.SamplingParamsHost(
        temperature=0.0, presence_penalty=0.5))
    assert not sampling.all_plain_greedy(sp, live)
    assert not on_device(sampling.pack_slot_params(sp), live)
    out = sampling.sample(logits, sp, ring, pos, bias, keys, active=live)
    assert _same_bits(out[:3], _window(logits, sp, ring, pos, bias, keys)[:3])
    for p in (sampling.SamplingParamsHost(temperature=0.0, repeat_penalty=1.1),
              sampling.SamplingParamsHost(temperature=0.0,
                                          frequency_penalty=0.1)):
        sampling.set_slot(sp, 1, p)
        assert not sampling.all_plain_greedy(sp, live)


def test_greedy_branch_takes_the_lower_of_two_equal_maxima():
    sp, ring, pos, bias, keys = _mk()
    logits = jnp.zeros((2, 64), jnp.float32).at[0, 40].set(5.0) \
        .at[0, 9].set(5.0).at[1, 63].set(2.0).at[1, 62].set(2.0)
    ids, _, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert list(np.asarray(ids)) == [9, 62]


def test_greedy_branch_logprob_is_over_the_whole_row():
    S, V = 4, 39296
    sp, ring, pos, bias, keys = _mk(S, V)
    logits = _model_like_logits(S, V, seed=1)
    ids, lps, _, _ = sampling.sample(logits, sp, ring, pos, bias, keys)
    full = jax.nn.log_softmax(logits, axis=-1)
    want = np.asarray(full)[np.arange(S), np.asarray(ids)]
    np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=1e-5)
    w_ids, w_lps, _, _ = _window(logits, sp, ring, pos, bias, keys)
    assert np.array_equal(np.asarray(w_ids), np.asarray(ids))
    gap = np.asarray(w_lps) - np.asarray(lps)    # the window drops the tail
    assert np.all(gap >= -1e-6) and np.all(gap < 0.05), gap


def test_logit_bias_moves_the_greedy_branch_pick():
    sp, ring, pos, bias, keys = _mk()
    logits = jnp.zeros((2, 64), jnp.float32).at[:, 7].set(5.0)
    bias = bias.at[0, 42].set(100.0)
    ids, lps = sampling._greedy_rows(logits, bias)
    assert list(np.asarray(ids)) == [42, 7]
    out = sampling.sample(logits, sp, ring, pos, bias, keys)
    assert _same_bits(out, (ids, lps, keys))


def test_greedy_branch_lowers_without_a_sort_or_a_window():
    x = jax.ShapeDtypeStruct((4, 1024), jnp.float32)
    text = jax.jit(sampling._greedy_rows).lower(x, x).as_text().lower()
    for word in ("sort", "approx", "top_k", "threefry", "rng"):
        assert word not in text, word
    # the words are the right ones: the window's front half has them
    window = jax.jit(lambda l, b: jax.lax.approx_max_k(l + b, sampling.SORT_K)
                     ).lower(x, x).as_text().lower()
    assert "approx" in window and "top_k" in window
