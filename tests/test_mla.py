"""Multi-head latent attention over the latent page pool (ops/mla.py,
ops/pallas/mla_decode.py): the absorbed decode form against the
materialised one, the packed prefill (fresh and continued) against plain
causal attention, through a shuffled page table."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops import kvcache, mla

H, NOPE, ROPE, R, VD = 4, 16, 8, 32, 16
PAGE, S, C = 16, 3, 64
WD = mla.pool_width(R, ROPE)
SCALE = (NOPE + ROPE) ** -0.5


def _weights(key):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, (R, H, NOPE)) / np.sqrt(R),
            jax.random.normal(k2, (R, H, VD)) / np.sqrt(R))


def _expand(w_k, w_v):
    def expand(lat):
        c, r = lat[:, :R], lat[:, R:R + ROPE]
        k = jnp.concatenate([jnp.einsum("nr,rhd->nhd", c, w_k),
                             jnp.broadcast_to(r[:, None], (len(lat), H, ROPE))],
                            -1)
        return k, jnp.einsum("nr,rhd->nhd", c, w_v)
    return expand


def _pool(rows_by_slot):
    """A one-layer latent cache holding each slot's rows, pages shuffled."""
    ck = kvcache.init_paged((2, S, C, 1, WD), jnp.float32, PAGE)
    ptab = np.random.default_rng(0).permutation(S * C // PAGE).astype(
        np.int32).reshape(S, -1)
    ck = kvcache.with_page_table(ck, jnp.asarray(ptab))
    for s, rows in enumerate(rows_by_slot):
        n = len(rows)
        ck = kvcache.scatter_prefill(
            ck, 1, jnp.full((1, n), s, jnp.int32),
            jnp.arange(n, dtype=jnp.int32)[None], rows[None, :, None])
    return ck


def _plain(q, lat, w_k, w_v):
    """Causal attention of q [T, H, dq] over the latent rows lat [T, WD],
    materialised."""
    k, v = _expand(w_k, w_v)(lat)
    s = jnp.einsum("thd,shd->hts", q, k) * SCALE
    T = q.shape[0]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
    return jnp.einsum("hts,shd->thd", p, v)


def test_pool_width_is_lane_aligned():
    assert mla.pool_width(512, 64) == 640 and WD == 128


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_absorbed_decode_agrees_with_materialised_attention(form):
    w_k, w_v = _weights(jax.random.PRNGKey(0))
    lens = [37, 0, 20]
    key = jax.random.PRNGKey(1)
    lat = [mla.latent_rows(jax.random.normal(jax.random.fold_in(key, s),
                                             (n + 1, R)),
                           jax.random.normal(jax.random.fold_in(key, 9 + s),
                                             (n + 1, ROPE)), WD)[:, 0]
           for s, n in enumerate(lens)]
    ck = _pool([rows[:-1] for rows in lat])
    q = jax.random.normal(jax.random.PRNGKey(2), (S, H, NOPE + ROPE))
    q_abs = mla.absorb_query(q[..., :NOPE], q[..., NOPE:], w_k, WD, SCALE)
    new = jnp.stack([rows[-1] for rows in lat])[:, None]
    o_lat = mla.decode_attention(
        q_abs, new, ck, jnp.int32(1), jnp.asarray(lens, jnp.int32), R,
        pallas=form == "pallas", interpret=True)
    got = mla.expand_values(o_lat, w_v)
    for s in range(S):
        qs = jnp.zeros((lens[s] + 1, H, NOPE + ROPE)).at[-1].set(q[s])
        want = _plain(qs, lat[s], w_k, w_v)[-1]
        np.testing.assert_allclose(np.asarray(got[s]), np.asarray(want),
                                   atol=2e-5)


@pytest.mark.parametrize("continued", [False, True])
def test_packed_prefill_agrees_with_plain_causal_attention(continued):
    w_k, w_v = _weights(jax.random.PRNGKey(3))
    expand = _expand(w_k, w_v)
    starts = [20, 0, 0] if continued else [0, 0, 0]
    lens = [13, 9, 0]
    key = jax.random.PRNGKey(4)
    lat = [mla.latent_rows(
        jax.random.normal(jax.random.fold_in(key, s), (starts[s] + n, R)),
        jax.random.normal(jax.random.fold_in(key, 7 + s),
                          (starts[s] + n, ROPE)), WD)[:, 0]
        for s, n in enumerate(lens)]
    qs = [jax.random.normal(jax.random.fold_in(key, 20 + s),
                            (starts[s] + n, H, NOPE + ROPE))
          for s, n in enumerate(lens)]
    ck = _pool([rows[:starts[s]] for s, rows in enumerate(lat)])
    N = 32
    off = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    seg_of = np.full((N,), S, np.int32)
    rows = jnp.zeros((N, WD))
    q = jnp.zeros((N, H, NOPE + ROPE))
    for s, n in enumerate(lens):
        seg_of[off[s]:off[s] + n] = s
        rows = rows.at[off[s]:off[s] + n].set(lat[s][starts[s]:])
        q = q.at[off[s]:off[s] + n].set(qs[s][starts[s]:])
    k, v = expand(rows)
    out = jax.jit(lambda *a: mla.prefill_attention(
        *a, expand, SCALE, continued=continued))(
        q, k, v, jnp.asarray(seg_of), jnp.arange(S, dtype=jnp.int32),
        jnp.asarray(starts, jnp.int32), ck, jnp.int32(1))
    for s, n in enumerate(lens):
        want = _plain(qs[s], lat[s], w_k, w_v)[starts[s]:]
        np.testing.assert_allclose(np.asarray(out[off[s]:off[s] + n]),
                                   np.asarray(want), atol=2e-5)
