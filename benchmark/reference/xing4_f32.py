"""A plain float32 forward pass of Xing4.0's decoder (``model_type: xing4_0``):
multi-head latent attention (MLA) on every layer, the query through its own
low-rank pair, YaRN on the rotary columns; the first ``first_k_dense_replace``
layers followed by a dense SwiGLU feed-forward and every later one by a routed
expert feed-forward (sigmoid scores, the k largest of score + bias over ONE
group) beside a shared expert; and in place of the residual ``x += f(norm(x))``
a MANIFOLD-CONSTRAINED HYPER-CONNECTION a sublayer over ``hc_mult`` residual
streams ("mHC", DeepSeek-AI, arXiv 2512.24880). Straight ``jax.numpy`` in
float32 at ``highest`` matmul precision; no cache, no kernel, no absorbed
projections (every head's keys and values are materialised), no grouping of
tokens by expert; attention in blocks of queries, so that a 12 k document's
scores fit. One sequence at a time, one layer's weights at a time, read from
the checkpoint file in HF layout (``[out, in]``). It takes nothing the program
has made.

With ``n = hc_mult`` streams of width C, X [T, n, C]:           # ASSUMED: all

    X[i] = E[token]                                     every stream alike
    one sublayer F (mixer or feed-forward), its own (W [n n + 2 n, n C],
    s [3], b [n n + 2 n]):
        x    = vec(X)                               stream-major, [n C]
        m    = (W x) * rsqrt(mean(x^2) + rms_norm_eps)
        pre  = sigmoid(s0 m[:n] + b[:n]) + hc_eps
        post = 2 sigmoid(s1 m[n:2n] + b[n:2n])
        A    = clip(s2 m[2n:] + b[2n:], clamp_min, clamp_max) as [n, n]
        M    = exp(A); hc_sinkhorn_iters times: rows /= (their sum + hc_eps),
               columns /= (their sum + hc_eps)
        u    = sum_i pre[i] X[i]
        X'[i] = post[i] F(RMSNorm_w(u)) + sum_j M[i, j] X[j]
    read-out (W_h [n, n C], s_h [1], b_h [n]):
        h = sum_i (sigmoid(s_h m_h + b_h) + hc_eps)[i] X[i]
        logits = W_lm RMSNorm_w(h)                             (untied)
    ``hc_mult`` 1: no such tensors, the plain ``x += F(norm(x))``.

    mixer: q = W_qb RMSNorm(W_qa u) in [H, nope | rope]; [c | k_r] = W_kva u,
        c = RMSNorm(c); the rope parts rotated (half-split) with YaRN's
        frequencies over ``qk_rope_head_dim``; cos and sin times
        yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim);
        [k_nope_h | v_h] = W_kvb c;
        scores = (q_nope . k_nope + q_rope . k_r) (nope + rope)^-0.5
                 yarn_mscale(factor, mscale_all_dim)^2, causal; W_o of the heads
    ff, dense:   W_2 (silu(W_1 h) * W_3 h)
    ff, experts: s = sigmoid(W_g h); choice = the k largest of
                 s + e_score_correction_bias; weight = s[choice] /
                 (sum s[choice] + 1e-20) * routed_scaling_factor;
                 ff = sum_j weight_j E_j(h) + E_shared(h)

A choice of experts is not continuous, so a caller may hand in ``choices``
(the experts another computation chose for each token of each expert layer):
the weights and the sum are then taken at THOSE experts, still from this
file's own scores, which come back either way. What the published
``config.json`` does not say (the configuration file's ``assumed``) is marked
ASSUMED where it is made. Tensors of the multi-token prediction module
(``model.layers.<num_hidden_layers>.*``) are never read.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.reference.llama_f32 import weight_reader  # noqa: F401

Q_BLOCK = 512            # queries a block of the attention


def yarn_mscale(factor: float, a: float) -> float:
    """YaRN's attention temperature: 0.1 a ln(factor) + 1 (1 at factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def rope_inv_freq(hf: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` inverse frequencies: ``rope_theta``'s,
    and under ``rope_scaling.type: yarn`` divided by ``factor`` below the
    ramp between ``beta_slow`` and ``beta_fast`` rotations over the original
    positions, untouched above it."""
    dim, theta = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = hf.get("rope_scaling")
    if not rs:
        return inv
    assert rs.get("type", rs.get("rope_type")) == "yarn", rs
    orig = rs["original_max_position_embeddings"]

    def corr(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(rs.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0, 1)
    return inv / rs["factor"] * ramp + inv * (1 - ramp)


def score_scale(hf: dict) -> float:
    """(nope + rope)^-0.5 times YaRN's ``mscale_all_dim`` temperature,
    squared (DeepSeek-V3's MLA)."""
    rs = hf.get("rope_scaling") or {}
    m = yarn_mscale(rs.get("factor", 1.0), rs.get("mscale_all_dim", 0.0)) \
        if rs.get("mscale_all_dim") else 1.0
    return (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5 * m * m


def rotary_mscale(hf: dict) -> float:
    rs = hf.get("rope_scaling") or {}
    f = rs.get("factor", 1.0)
    return yarn_mscale(f, rs.get("mscale", 1.0)) \
        / yarn_mscale(f, rs.get("mscale_all_dim", 0.0))


def forward(read, hf: dict, n_layers: int, seqs, choices=None,
            hc_sublayers=((0, 0),), hc_dtype: str = "float32"):
    """seqs: [(token_ids, n_prompt, logit_positions)]; choices: None or, a
    sequence, int [T, L_moe, k] -> one dict a sequence, numpy float32:
      logits [len(logit_positions), V]
      latent [L, T, R + rope]        every layer's rows ``[c | k_r]``
      biased [T, L_moe, E]           s + e_score_correction_bias
      chosen [T, L_moe, k]           its own choice
      hc     [len(hc_sublayers)][T, 2 n + n n]  ``[pre | post | vec(M)]`` of
             the sublayers (layer, 0: mixer / 1: feed-forward) asked for
    ``hc_dtype``: what the hyper-connections are computed in (their
    product, the norm, the three mixes, the two weighted sums): a CONTROL of
    the comparison asks what bfloat16 would do; all else stays float32.
    One layer's weights are on the device at a time, for every sequence."""
    import jax
    import jax.numpy as jnp

    H = hf["num_attention_heads"]
    nope, rdim = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    R, vdim = hf["kv_lora_rank"], hf["v_head_dim"]
    eps = float(hf["rms_norm_eps"])
    nd = hf["first_k_dense_replace"]
    E, k_tok = hf["n_routed_experts"], hf["num_experts_per_tok"]
    scaling = float(hf.get("routed_scaling_factor", 1.0))
    n = int(hf.get("hc_mult", 1))
    iters = int(hf.get("hc_sinkhorn_iters", 20))
    hc_eps = float(hf.get("hc_eps", 1e-6))
    lo = float(hf.get("mhc_h_res_clamp_min", -30))
    hi = float(hf.get("mhc_h_res_clamp_max", 30))
    assert hf.get("n_group", 1) == 1 and hf.get("scoring_func") == "sigmoid"
    assert hf.get("n_shared_experts", 1) == 1
    inv_freq = jnp.asarray(rope_inv_freq(hf), jnp.float32)
    rot_scale, sm_scale = rotary_mscale(hf), score_scale(hf)
    hd = jnp.dtype(hc_dtype)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x):             # x [T, ..., rdim]: the last axis is rotated
        # ASSUMED: the half-split convention (rotate_half)
        T = x.shape[0]
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
        ang = jnp.concatenate([ang, ang], -1).reshape(
            (T,) + (1,) * (x.ndim - 2) + (rdim,))
        rot = jnp.concatenate([-x[..., rdim // 2:], x[..., :rdim // 2]], -1)
        return (x * jnp.cos(ang) + rot * jnp.sin(ang)) * rot_scale

    def hc_moments(X, w):
        """X [T, n, C], w [outs, n C] -> m [T, outs] (module doc)."""
        x = X.reshape(X.shape[0], -1).astype(hd)
        # ASSUMED: rms_norm_eps in the streams' norm
        return (x @ w.T.astype(hd)) * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + eps)

    def hc_mix(X, w, s, b):
        """X [T, n, C] -> pre [T, n], post [T, n], M [T, n, n]."""
        m = hc_moments(X, w)
        s, b = s.astype(hd), b.astype(hd)
        # ASSUMED: hc_eps in the pre-weights and in the Sinkhorn denominators
        pre = jax.nn.sigmoid(s[0] * m[:, :n] + b[:n]) + hc_eps
        post = 2.0 * jax.nn.sigmoid(s[1] * m[:, n:2 * n] + b[n:2 * n])
        # ASSUMED: the clamp before exp; M[i, j] mixes stream j into stream i
        M = jnp.exp(jnp.clip(s[2] * m[:, 2 * n:] + b[2 * n:], lo, hi)
                    ).reshape(-1, n, n)
        for _ in range(iters):
            M = M / (jnp.sum(M, -1, keepdims=True) + hc_eps)
            M = M / (jnp.sum(M, -2, keepdims=True) + hc_eps)
        return pre, post, M

    def weighted(pre, X):
        """sum_i pre[:, i] X[:, i], in ``hc_dtype``, back in X's."""
        return jnp.einsum("ti,tic->tc", pre, X.astype(hd)).astype(X.dtype)

    def sublayer(X, hc, norm_w, fn):
        """One sublayer under its hyper-connection (module doc) -> (X', the
        sublayer's own result, [pre | post | vec(M)] or None)."""
        if n == 1:
            y, aux = fn(rms(X[:, 0], norm_w))
            return X + y[:, None], aux, None
        pre, post, M = hc_mix(X, *hc)
        f32 = X.dtype
        y, aux = fn(rms(weighted(pre, X), norm_w))
        X = (post[:, :, None] * y[:, None, :].astype(hd)
             + jnp.einsum("tij,tjc->tic", M, X.astype(hd))).astype(f32)
        return X, aux, jnp.concatenate(
            [pre, post, M.reshape(M.shape[0], -1)], -1).astype(f32)

    def mla_op(h, w):
        T = h.shape[0]
        q = (rms(h @ w["qa"].T, w["q_norm"]) @ w["qb"].T).reshape(
            T, H, nope + rdim)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:])
        kva = h @ w["kva"].T
        c = rms(kva[:, :R], w["kv_norm"])
        r = rope(kva[:, R:])                     # shared by the heads
        kv = (c @ w["kvb"].T).reshape(T, H, nope + vdim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        blocks = -(-T // Q_BLOCK)
        pad = blocks * Q_BLOCK - T

        def block(_, i):
            t0 = i * Q_BLOCK
            qn = jax.lax.dynamic_slice_in_dim(q_nope_p, t0, Q_BLOCK)
            qr = jax.lax.dynamic_slice_in_dim(q_rope_p, t0, Q_BLOCK)
            s = (jnp.einsum("thd,shd->hts", qn, k_nope)
                 + jnp.einsum("thd,sd->hts", qr, r)) * sm_scale
            causal = (t0 + jnp.arange(Q_BLOCK))[:, None] \
                >= jnp.arange(T)[None, :]
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return None, jnp.einsum("hts,shd->thd", p, v)

        q_nope_p = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0)))
        q_rope_p = jnp.pad(q_rope, ((0, pad), (0, 0), (0, 0)))
        o = jax.lax.scan(block, None, jnp.arange(blocks))[1]
        o = o.reshape(blocks * Q_BLOCK, H * vdim)[:T]
        return o @ w["o"].T, jnp.concatenate([c, r], -1)

    def dense_ff(h, w):
        return (jax.nn.silu(h @ w["w1"].T) * (h @ w["w3"].T)) @ w["w2"].T, 0

    def scores(h, w):
        s = jax.nn.sigmoid(h @ w["gate"].T)
        c = s + w["bias"][None]     # the bias takes part in the choice only
        return s, c, jax.lax.top_k(c, k_tok)[1]

    def experts_ff(h, s, chosen, w, w1, w3, w2):
        """chosen [T, k]: the weighted sum over the chosen experts, one
        expert at a time over every token (a token that did not choose it
        weighs it 0), and the shared expert."""
        wt = jnp.take_along_axis(s, chosen, axis=1)
        if hf.get("norm_topk_prob", True):
            # ASSUMED: the sum of the chosen scores is guarded by 1e-20
            wt = wt / (jnp.sum(wt, -1, keepdims=True) + 1e-20)
        wt = wt * scaling
        comb = jnp.sum(jax.nn.one_hot(chosen, E) * wt[..., None], axis=1)

        def one(acc, e):
            a1, a3, a2, c = e
            y = (jax.nn.silu(h @ a1.T) * (h @ a3.T)) @ a2.T
            return acc + c[:, None] * y, None

        routed = jax.lax.scan(one, jnp.zeros_like(h), (w1, w3, w2, comb.T))[0]
        shared = (jax.nn.silu(h @ w["sh1"].T) * (h @ w["sh3"].T)) @ w["sh2"].T
        return routed + shared

    @jax.jit
    def run_mla(X, w, hc):
        return sublayer(X, hc, w["in_norm"], lambda h: mla_op(h, w))

    @jax.jit
    def run_dense(X, w, hc):
        return sublayer(X, hc, w["post_norm"], lambda h: dense_ff(h, w))

    @jax.jit
    def run_scores(X, w, hc):
        """The feed-forward's input and its scores (the choice may be
        another's: the experts run in ``run_experts``)."""
        if n == 1:
            h = rms(X[:, 0], w["post_norm"])
        else:
            h = rms(weighted(hc_mix(X, *hc)[0], X),
                    w["post_norm"])
        return scores(h, w)

    @jax.jit
    def run_experts(X, s, chosen, w, hc, w1, w3, w2):
        return sublayer(X, hc, w["post_norm"], lambda h: (
            experts_ff(h, s, chosen, w, w1, w3, w2), 0))

    @jax.jit
    def head(X, hc, norm, w_head):
        if n == 1:
            return rms(X[:, 0], norm) @ w_head.T
        w, s, b = hc
        pre = jax.nn.sigmoid(s[0].astype(hd) * hc_moments(X, w)
                             + b.astype(hd)) + hc_eps
        return rms(weighted(pre, X), norm) @ w_head.T

    # ASSUMED: model_type and tensor names (HF's for this model cannot be
    # read here): DeepSeek-V3's, and the hyper-connections' beside them
    a = "self_attn."
    names = {"in_norm": "input_layernorm.weight",
             "post_norm": "post_attention_layernorm.weight",
             "qa": a + "q_a_proj.weight", "q_norm": a + "q_a_layernorm.weight",
             "qb": a + "q_b_proj.weight",
             "kva": a + "kv_a_proj_with_mqa.weight",
             "kv_norm": a + "kv_a_layernorm.weight",
             "kvb": a + "kv_b_proj.weight", "o": a + "o_proj.weight"}
    dense_names = {"w1": "mlp.gate_proj.weight", "w3": "mlp.up_proj.weight",
                   "w2": "mlp.down_proj.weight"}
    moe_names = {"gate": "mlp.gate.weight",
                 "bias": "mlp.gate.e_score_correction_bias",
                 "sh1": "mlp.shared_experts.gate_proj.weight",
                 "sh3": "mlp.shared_experts.up_proj.weight",
                 "sh2": "mlp.shared_experts.down_proj.weight"}

    def hc_of(prefix):
        if n == 1:
            return None
        return tuple(jnp.asarray(read(f"{prefix}.{p}"))
                     for p in ("weight", "scale", "bias"))

    with jax.default_matmul_precision("highest"):
        embed = read("model.embed_tokens.weight")
        longest = max(len(ids) for ids, _, _ in seqs)

        def padded(a):       # to the longest sequence, at the end
            a = np.asarray(a)
            return np.concatenate(
                [a, np.zeros((longest - len(a),) + a.shape[1:], a.dtype)])

        xs = [jnp.repeat(jnp.asarray(embed[padded(ids)], jnp.float32)[
            :, None], n, axis=1) for ids, _, _ in seqs]
        out = [{"latent": [], "biased": [], "chosen": [], "hc": {}}
               for _ in seqs]
        for i in range(n_layers):
            p = f"model.layers.{i}."
            ff = dense_names if i < nd else moe_names
            w = {k: jnp.asarray(read(p + nm))
                 for k, nm in {**names, **ff}.items()}
            hcs = [hc_of(p + "attn_hc"), hc_of(p + "mlp_hc")]
            if i >= nd:
                stacks = [jnp.asarray(np.stack([read(
                    f"{p}mlp.experts.{e}.{pr}.weight") for e in range(E)]))
                    for pr in ("gate_proj", "up_proj", "down_proj")]
            for j, (ids, _, _) in enumerate(seqs):
                T = len(ids)
                xs[j], rows, mix = run_mla(xs[j], w, hcs[0])
                out[j]["latent"].append(np.asarray(rows)[:T])
                if (i, 0) in hc_sublayers and mix is not None:
                    out[j]["hc"][(i, 0)] = np.asarray(mix)[:T]
                if i < nd:
                    xs[j], _, mix = run_dense(xs[j], w, hcs[1])
                else:
                    s, biased, chosen = run_scores(xs[j], w, hcs[1])
                    out[j]["biased"].append(np.asarray(biased)[:T])
                    out[j]["chosen"].append(np.asarray(chosen)[:T])
                    if choices is not None:
                        chosen = jnp.asarray(padded(choices[j][:, i - nd]),
                                             jnp.int32)
                    xs[j], _, mix = run_experts(xs[j], s, chosen, w, hcs[1],
                                                *stacks)
                if (i, 1) in hc_sublayers and mix is not None:
                    out[j]["hc"][(i, 1)] = np.asarray(mix)[:T]
            del w
        norm = jnp.asarray(read("model.norm.weight"))
        w_head = jnp.asarray(read("lm_head.weight"))     # ASSUMED: untied
        hc_head = hc_of("model.hc_head")
        res = []
        for j, (_, _, at) in enumerate(seqs):
            o = out[j]
            T = len(seqs[j][0])
            res.append({
                "logits": np.asarray(head(xs[j][np.asarray(at)], hc_head,
                                          norm, w_head)),
                "latent": np.stack(o["latent"]),
                "biased": np.stack(o["biased"], 1) if o["biased"]
                else np.zeros((T, 0, E), np.float32),
                "chosen": np.stack(o["chosen"], 1) if o["chosen"]
                else np.zeros((T, 0, k_tok), np.int32),
                "hc": [o["hc"][key] for key in hc_sublayers
                       if key in o["hc"]]})
        return res
