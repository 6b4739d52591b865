"""What the hybrid families share (``olmo_hybrid``: gated delta rule,
``granite_hybrid``: Mamba-2, ``lfm2_moe``: gated short convolution): the
scan over periods and over runs of same-kind layers, the head, and the
causal depthwise convolution over a PACK of segments with each slot's tail
carried in and out. A family imports these; none imports another family."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from localai_tpu.models.llama import _mat
from localai_tpu.ops import kvcache


def unembed(x, params, cfg):
    w = _mat(params["embed"], x.dtype).T if cfg.tie_word_embeddings \
        else _mat(params["lm_head"], x.dtype)
    return (x @ w).astype(jnp.float32)


def scan_periods(cfg, period_fn, carry):
    """``period_fn(carry, p)`` over the periods; it takes its weights from
    the stacked leaves by layer index (each family's ``_layer``)."""
    return jax.lax.scan(lambda c, p: (period_fn(c, p), None), carry,
                        jnp.arange(cfg.periods, dtype=jnp.int32))[0]


def _runs(kinds):
    """[(kind, its index among the layers of its kind in ``kinds``, its
    position in ``kinds``, count)] a run of consecutive layers of one
    kind."""
    runs, seen = [], {}
    for j, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][3] += 1
        else:
            runs.append([kind, seen.get(kind, 0), j, 1])
        seen[kind] = seen.get(kind, 0) + 1
    return runs


def scan_layer_runs(kinds, carry, layer_fns, lead: int = 0):
    """The layers ``kinds`` (one kind name a layer) over ``carry``. Past
    the first ``lead`` layers the list is taken as PERIODS (the shortest
    prefix whose repetition gives it, the last one possibly cut short): a
    scan over the whole periods, and inside a period - as among the leading
    layers and in a cut last period - one scan over each RUN of layers of
    one kind, so that a program holds a layer's body once a run and not
    once a layer. Traced ten layers to the period, one cell's 25 programs
    took 240 s of tracing and lowering at every start-up, whatever the
    compile cache held (PERF.md section 6, PR 36). ``layer_fns[kind](carry,
    ki, i)`` runs layer ``i`` (traced), the ``ki``-th of its kind."""
    kinds = tuple(kinds)
    body = kinds[lead:]
    n = next((p for p in range(1, len(body) + 1)
              if all(body[j] == body[j % p] for j in range(len(body)))), 1)
    periods = len(body) // n
    if periods < 2:
        n, periods = len(body), 1
    period, rest = body[:n], body[n * periods:]

    def run_all(carry, some, k_base, j_base):
        for kind, k0, j0, count in _runs(some):
            def one(c, r, fn=layer_fns[kind], k=k_base(kind) + k0,
                    j=j_base + j0):
                return fn(c, k + r, j + r)

            if count == 1:
                carry = one(carry, 0)
            else:
                carry = jax.lax.scan(
                    lambda c, r, one=one: (one(c, r), None), carry,
                    jnp.arange(count, dtype=jnp.int32))[0]
        return carry

    carry = run_all(carry, kinds[:lead], lambda kind: 0, 0)
    before = {k: kinds[:lead].count(k) for k in set(kinds)}
    if periods > 1:
        carry = jax.lax.scan(
            lambda c, p: (run_all(
                c, period, lambda kind: before[kind] + period.count(kind) * p,
                lead + n * p), None),
            carry, jnp.arange(periods, dtype=jnp.int32))[0]
    else:
        carry = run_all(carry, period, lambda kind: before[kind], lead)
    done = lead + n * periods
    return run_all(carry, rest,
                   lambda kind: before[kind] + period.count(kind) * periods,
                   done)


def _prev_inputs(pre, init, seg, j, d: int):
    """The convolution's input ``d`` tokens before each packed token:
    the pack's own row where the segment reaches back that far, else the
    segment's starting tail ``init`` [B, 3, Ch] (oldest first)."""
    own = jnp.roll(pre, d, axis=0)
    W1 = init.shape[1]
    flat = init.reshape(-1, init.shape[-1])                  # [B*3, Ch]
    idx = jnp.clip(seg * W1 + (W1 + j - d), 0, flat.shape[0] - 1)
    return jnp.where((j >= d)[:, None], own,
                     jnp.take(flat, idx, axis=0).astype(pre.dtype))


def packed_conv(pre, conv0, cw, seg, j):
    """The causal depthwise convolution over a pack, before its bias and
    activation: pre [N, Ch] the pack's inputs, conv0 [B, W-1, Ch] each
    segment's starting tail, cw [W, Ch] float32 -> [N, Ch] float32."""
    f32 = jnp.float32
    W1 = conv0.shape[1]
    acc = pre.astype(f32) * cw[W1][None]
    for d in range(1, W1 + 1):
        acc = acc + _prev_inputs(pre, conv0, seg, j, d).astype(f32) \
            * cw[W1 - d][None]
    return acc


def new_tails(pre, conv0, seg_off, seg_len):
    """Each segment's convolution tail after the pack [B, W-1, Ch]: its
    last W-1 inputs, reaching into the old tail ``conv0`` where the
    segment is shorter than that."""
    N, W1 = pre.shape[0], conv0.shape[1]
    # rows of the pack that become each segment's new tail
    tail_j = seg_len[:, None] - W1 + jnp.arange(W1, dtype=jnp.int32)[None]
    own = jnp.take(pre, jnp.clip(seg_off[:, None] + tail_j, 0, N - 1),
                   axis=0)
    old = jnp.take_along_axis(
        conv0, jnp.clip(tail_j + W1, 0, W1 - 1)[..., None], axis=1)
    return jnp.where((tail_j >= 0)[..., None], own, old.astype(pre.dtype))


def prefill_as_pack(ragged, params, cfg, tokens, seq_lens, cache_k, cache_v,
                    slot_ids, start_pos, continued=False):
    """A [B, T] batch through a family's ``ragged`` prefill as one pack of
    B segments of T tokens each."""
    B, T = tokens.shape
    C = kvcache.shape(cache_k)[2]
    t = jnp.arange(T, dtype=jnp.int32)[None]
    seq_lens, start_pos = jnp.asarray(seq_lens), jnp.asarray(start_pos)
    valid = t < seq_lens[:, None]
    pos = jnp.where(valid, start_pos[:, None] + t, C).reshape(-1)
    seg_of = jnp.where(valid, jnp.arange(B, dtype=jnp.int32)[:, None],
                       B).reshape(-1)
    return ragged(
        params, cfg, jnp.asarray(tokens).reshape(-1), pos, seg_of,
        jnp.asarray(slot_ids), start_pos,
        jnp.arange(B, dtype=jnp.int32) * T, seq_lens, cache_k, cache_v,
        continued=continued)
