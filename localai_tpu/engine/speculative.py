"""Speculative decoding: propose D tokens cheaply, verify with the target.

Capability parity: the reference plumbs draft-model fields end-to-end
(reference: backend.proto DraftModel, backend_config.go DraftModel) into
llama.cpp's speculative sampling. TPU re-design: one ROUND is a single
compiled program — a DRAFTER proposes D tokens, then the target model
scores all D+1 positions in ONE batched forward (prefill with
return_all_logits) and greedy acceptance keeps the matched prefix plus
the target's correction/bonus token. Greedy speculation is LOSSLESS: the
emitted stream is bit-identical to plain greedy decoding of the target
model, whatever the drafter proposes — rejected drafts only waste the
round's spare compute.

Two drafters (engine knob ``draft``):

  * ``model``: a second, smaller llama-family model autoregressively
    proposes via a lax.scan of decode steps over its own KV cache
    (draft_propose).
  * ``ngram``: prompt-lookup / n-gram SELF-speculation (ngram_propose) —
    the slot's trailing n-gram is matched against its own prompt+emitted
    history (the device-side penalty ring), and the continuation after
    the most recent match is proposed. No second model, no draft KV, so
    every llama-family greedy request can speculate by default. A miss
    says so (``has`` false): the row has no draft, and the engine's
    round gives it the plain decode step instead of a verify pass.

Cache invariant (target and draft models alike): rows [0, length) hold
the accepted context, and the CURRENT token (last emitted) is not yet
ingested; a round ingests it as its first input. Rows written for
rejected proposals sit above the new length and are masked/overwritten.

Since ISSUE 13 speculation is a packed citizen of the engine's fused
decode tick (engine.py _spec_tick_body): spec-eligible slots whose
drafter has a draft take a propose+verify round while every other row
(non-spec neighbors, and spec rows in a round without a draft) takes a
plain decode step — one chained dispatch, no whole-engine spec/burst
alternation, and each of the round's two forward passes runs only if
it has a row.

Since ISSUE 18 sampled (temperature>0) slots speculate too, via
rejection-sampling acceptance (accept_sampled, leviathan-style): draft
token x_j is accepted with probability min(1, p(x_j)/q(x_j)) against
the FILTERED target distribution p (sampling.verify_dist — the exact
law plain `sample` draws from), and the first rejection resamples from
the residual norm(max(0, p - q)). Our drafters are deterministic (n-gram
lookup / greedy draft model), so q is a one-hot: acceptance degenerates
to u < p(x_j) and the residual is p with the draft token zeroed. Sampled
speculation is lossless IN DISTRIBUTION (chi-square-tested), not
byte-identical — the spec tick consumes the slot's RNG key on a
different schedule (one acceptance+resample draw per round vs one
categorical per token), so a given seed yields a different, equally
distributed stream than spec-off — and since every executed round
advances the key, the bytes also depend on how rounds partition into
dispatches under load. Greedy slots keep accept_greedy and remain
bit-identical to plain greedy decoding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from localai_tpu.models import llama


def ngram_propose(tokens, ring, ring_pos, n_draft: int, ngram: int):
    """Prompt-lookup proposals from the slot's own token history.

    tokens [S]: current (not yet ingested) token per slot; ring
    [S, RING_N] / ring_pos [S]: the penalty ring (engine/sampling.py) —
    prompt-seeded at admission and updated with every emitted token, so
    it IS the trailing prompt+generation history, already device-side.
    Returns (proposals [S, D] int32, has [S] bool): ``has`` is whether
    the slot's history offered a continuation at all.

    The trailing ``ngram``-gram (current token last) is compared against
    every aligned window of the chronological history; the continuation
    after the MOST RECENT match is proposed, clipped at the history end
    (self-overlap is deliberate — repetitive continuations are exactly
    what prompt-lookup exploits). No valid match (including short
    histories still holding -1 seed entries) is ``has`` false; the
    proposal row is then a repeat of the current token, a filler no
    verify pass should be spent on.
    """
    S, N = ring.shape
    D, G = n_draft, ngram
    ar = jnp.arange(N, dtype=jnp.int32)
    # chronological view, oldest -> newest: the ring writes at
    # pos % N then advances, so entry (pos + j) % N ages left-to-right
    # and (pos - 1) % N — chronological index N-1 — is the current token
    idx = (ring_pos[:, None] + ar[None, :]) % N
    hist = jnp.take_along_axis(jnp.asarray(ring), idx, axis=1)   # [S, N]
    trail = hist[:, N - G:]                                      # [S, G]
    starts = jnp.arange(N - G, dtype=jnp.int32)                  # [P]
    win = starts[:, None] + jnp.arange(G, dtype=jnp.int32)[None, :]
    wins = hist[:, win]                                          # [S, P, G]
    ok = jnp.all(wins == trail[:, None, :], axis=-1)
    ok &= jnp.all(wins >= 0, axis=-1)              # unwritten seed entries
    ok &= jnp.all(trail >= 0, axis=-1)[:, None]    # short history: no match
    p_best = jnp.max(jnp.where(ok, starts[None, :], -1), axis=1)  # [S]
    has = p_best >= 0
    cont = jnp.minimum(
        p_best[:, None] + G + jnp.arange(D, dtype=jnp.int32)[None, :], N - 1)
    props = jnp.take_along_axis(hist, cont, axis=1)              # [S, D]
    return jnp.where(has[:, None], props,
                     jnp.asarray(tokens)[:, None]).astype(jnp.int32), has


def draft_propose(dparams, dcfg: llama.LlamaConfig, tokens, lengths,
                  dck, dcv, active, n_draft: int):
    """Draft-model proposals: D+1 autoregressive greedy decode steps.

    The draft cache ingests current + ALL proposals (D+1 steps, so the
    last proposal's KV row exists when fully accepted — otherwise the
    draft cache carries a permanent hole inside the accepted context and
    acceptance quality decays). Inactive slots write at the OOB row so
    the scatter drops (contiguous and paged layouts alike).
    Returns (drafts [S, D], has [S], dck, dcv); a draft model always
    proposes, so ``has`` is all true.
    """
    from localai_tpu.ops import kvcache

    dC = kvcache.shape(dck)[2]

    def dstep(carry, _):
        tok, dl, dck, dcv = carry
        wl = jnp.where(active, dl, dC)
        logits, dck, dcv = llama.decode_step(dparams, dcfg, tok, wl, dck, dcv)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, dl + active.astype(jnp.int32), dck, dcv), nxt

    (_, _, dck, dcv), proposals = jax.lax.scan(
        dstep, (tokens, lengths, dck, dcv), None, length=n_draft + 1)
    return proposals[:n_draft].T, jnp.ones_like(active), dck, dcv


def accept_greedy(drafts, greedy, active):
    """Greedy acceptance: longest matched prefix + the target's bonus.

    drafts [S, D] proposals; greedy [S, D+1] the target's greedy picks at
    every position; active [S] bool. Returns (out [S, D+1] emitted
    tokens, n_out [S] valid counts = matched prefix + 1 bonus, k [S]
    accepted-draft counts).
    """
    S, D = drafts.shape
    match = (drafts == greedy[:, :D]).astype(jnp.int32)
    acc_prefix = jnp.cumprod(match, axis=1)
    k = jnp.sum(acc_prefix, axis=1)                             # [S]
    bonus = jnp.take_along_axis(greedy, k[:, None], axis=1)[:, 0]
    pos = jnp.arange(D + 1, dtype=jnp.int32)[None, :]
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((S, 1), jnp.int32)], axis=1)
    out = jnp.where(pos < k[:, None], drafts_pad,
                    jnp.where(pos == k[:, None], bonus[:, None], 0))
    n_out = (k + 1) * active.astype(jnp.int32)
    return out, n_out, k


def accept_sampled(drafts, target_probs, draft_probs, rng_keys, active):
    """Stochastic (rejection-sampling) acceptance for sampled slots.

    drafts [S, D] proposals; target_probs [S, D+1, V] the FILTERED target
    distribution at every verify position (each row sums to 1 over the
    candidate support — sampling.verify_dist scattered to vocab);
    draft_probs [S, D, V] the drafter's proposal distribution, or None
    for deterministic drafters (n-gram / greedy draft model: q is a
    one-hot at the draft token, so acceptance is u < p(x_j) and the
    residual is p with the draft token zeroed); rng_keys [S, 2] uint32;
    active [S] bool.

    Accept draft x_j with probability min(1, p(x_j)/q(x_j)); the first
    rejection at position j emits one token resampled from
    norm(max(0, p_j - q_j)); full acceptance draws the bonus from
    p_D. Exactly ONE categorical draw and D uniforms are consumed per
    slot per round, unconditionally — the RNG schedule is data-
    independent, so a fixed seed ladder replays bit-identically.
    Inactive slots keep their keys untouched.

    Returns (out [S, D+1] emitted tokens, n_out [S] valid counts =
    accepted prefix + 1, k [S] accepted-draft counts, new_keys [S, 2]).
    """
    S, D = drafts.shape
    pos = jnp.arange(D + 1, dtype=jnp.int32)[None, :]

    def one(key_data, dr, tp, qp):
        key = jax.random.wrap_key_data(key_data)
        key, sub_u, sub_c = jax.random.split(key, 3)
        u = jax.random.uniform(sub_u, (D,))
        p_dr = jnp.take_along_axis(tp[:D], dr[:, None], axis=1)[:, 0]  # [D]
        if qp is None:
            ratio = p_dr
            resid = tp[:D].at[jnp.arange(D, dtype=jnp.int32), dr].set(0.0)
        else:
            q_dr = jnp.take_along_axis(qp, dr[:, None], axis=1)[:, 0]
            ratio = jnp.minimum(1.0, p_dr / jnp.clip(q_dr, 1e-20))
            resid = jnp.clip(tp[:D] - qp, 0.0)
        accept = (u < ratio).astype(jnp.int32)
        k = jnp.sum(jnp.cumprod(accept))
        # final token: residual row k on rejection, bonus row D otherwise
        fin = jnp.where(k < D, resid[jnp.minimum(k, D - 1)], tp[D])
        # numerically-empty residual (p==q up to rounding): fall back to
        # the target row so the categorical stays well-defined
        fin = jnp.where(jnp.any(fin > 0), fin, tp[jnp.minimum(k, D)])
        fin_logits = jnp.where(fin > 0, jnp.log(fin), -jnp.inf)
        choice = jax.random.categorical(sub_c, fin_logits).astype(jnp.int32)
        return jax.random.key_data(key), choice, k

    if draft_probs is None:
        new_keys, final_tok, k = jax.vmap(
            lambda kd, dr, tp: one(kd, dr, tp, None))(
                rng_keys, drafts, target_probs)
    else:
        new_keys, final_tok, k = jax.vmap(one)(
            rng_keys, drafts, target_probs, draft_probs)

    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((S, 1), jnp.int32)], axis=1)
    out = jnp.where(pos < k[:, None], drafts_pad,
                    jnp.where(pos == k[:, None], final_tok[:, None], 0))
    n_out = (k + 1) * active.astype(jnp.int32)
    new_keys = jnp.where(active[:, None], new_keys, rng_keys)
    return out.astype(jnp.int32), n_out, k, new_keys


def two_sample_chi2(counts_a, counts_b, min_expected: float = 5.0):
    """Two-sample chi-square homogeneity test (host-side numpy).

    counts_a/counts_b: per-category observation counts of the two
    samples (e.g. token-id frequencies of a spec-sampled vs a
    plain-sampled run). Categories whose combined count is below
    ``min_expected`` are pooled into one bin so the asymptotic
    approximation holds. Returns (stat, dof, p_value); p ~ U[0,1] when
    both samples draw from the same law — the distribution-preservation
    gate asserts p above a small alpha. Uses the unequal-N form
    chi2 = sum (K1*a_i - K2*b_i)^2 / (a_i + b_i) with K1 = sqrt(NB/NA),
    K2 = sqrt(NA/NB).
    """
    import numpy as np

    a = np.asarray(counts_a, np.float64).ravel()
    b = np.asarray(counts_b, np.float64).ravel()
    tot = a + b
    big = tot >= min_expected
    a = np.concatenate([a[big], [a[~big].sum()]])
    b = np.concatenate([b[big], [b[~big].sum()]])
    keep = (a + b) > 0
    a, b = a[keep], b[keep]
    if len(a) < 2 or a.sum() == 0 or b.sum() == 0:
        return 0.0, 0, 1.0
    k1 = np.sqrt(b.sum() / a.sum())
    k2 = np.sqrt(a.sum() / b.sum())
    stat = float(np.sum((k1 * a - k2 * b) ** 2 / (a + b)))
    dof = int(len(a) - 1)
    try:
        from scipy.stats import chi2 as _chi2
        p = float(_chi2.sf(stat, dof))
    except Exception:   # pragma: no cover — scipy ships with jax
        from jax.scipy.special import gammaincc
        p = float(gammaincc(dof / 2.0, stat / 2.0))
    return stat, dof, p


def spec_round(params, dparams, cfg: llama.LlamaConfig, dcfg: llama.LlamaConfig,
               tokens, lengths, ck, cv, dck, dcv, active, n_draft: int):
    """One standalone draft-model speculative round for all slots.

    tokens [S]: current (not yet ingested) token per slot; lengths [S];
    ck/cv target cache; dck/dcv draft cache; active [S] bool.
    Returns (out [S, D+1] emitted tokens, out_lp, n_out [S] valid counts,
    ck, cv, dck, dcv, lengths_new). Kept as the minimal reference round
    (unit-tested directly); the engine's serving path runs the fused
    multi-round tick instead (engine.py _spec_tick_body), which composes
    these same propose/verify/accept pieces per round.
    """
    from localai_tpu.ops import kvcache

    S = tokens.shape[0]
    D = n_draft
    C = kvcache.shape(ck)[2]

    # 1. drafter proposes D tokens
    drafts, _has, dck, dcv = draft_propose(dparams, dcfg, tokens, lengths,
                                           dck, dcv, active, D)

    # 2. target scores current + proposals in one forward
    tin = jnp.concatenate([tokens[:, None], drafts], axis=1)   # [S, D+1]
    seq = jnp.full((S,), D + 1, jnp.int32)
    start = jnp.where(active, lengths, C)  # inactive rows -> OOB, dropped
    all_logits, ck, cv = llama.prefill(
        params, cfg, tin, seq, ck, cv, jnp.arange(S, dtype=jnp.int32), start,
        continued=True, return_all_logits=True)
    greedy = jnp.argmax(all_logits, axis=-1).astype(jnp.int32)  # [S, D+1]

    # 3. greedy acceptance: longest prefix where draft matches target
    out, n_out, _k = accept_greedy(drafts, greedy, active)
    # matching logprobs for the emitted tokens (target distribution)
    logp_all = jax.nn.log_softmax(all_logits, axis=-1)
    out_lp = jnp.take_along_axis(logp_all, out[:, :, None], axis=2)[:, :, 0]

    lengths_new = lengths + n_out
    return out, out_lp, n_out, ck, cv, dck, dcv, lengths_new
