"""A plain float32 forward pass of the LFM2-MoE decoder (``model_type:
lfm2_moe``): gated short-convolution layers beside grouped-query attention
layers, as ``layer_types`` says, the first ``num_dense_layers`` followed by a
dense SwiGLU feed-forward and every later one by a routed expert
feed-forward. Straight ``jax.numpy`` in float32 at ``highest`` matmul
precision; no cache, no kernel, no batching, no grouping of tokens by expert
(every expert is applied to the tokens that chose it, one expert at a time).
One sequence at a time, one layer's weights at a time, read from the
checkpoint file in HF layout (``[out, in]``).

With x the residual stream and norm = RMSNorm (learned weight, ``norm_eps``):

    x = E[token]
    x = x + op(norm_op(x));  x = x + ff(norm_ff(x))          every layer
    logits = norm_out(x) @ E^T                                 (tied head)

    op, conv layer:  [B | C | u] = W_in h;  v = B * u
                     y_t = sum_{j=0..W-1} w[:, j] * v_{t-W+1+j}   (v = 0 before
                     the first token; depthwise, causal, no bias)
                     op = W_out (C * y)
    op, attention:   q, k, v = W_q h, W_k h, W_v h in heads of hd; q and k
                     RMS-normed per head, then rotary; causal softmax
                     attention at scale hd^-0.5, grouped; W_o
    ff, dense:       W_2 (silu(W_1 h) * W_3 h)
    ff, experts:     s = sigmoid(W_g h) over the E experts
                     choice = the k largest of s + expert_bias
                     weight = s[choice] / (sum s[choice] + 1e-6)
                              * routed_scaling_factor
                     ff = sum_j weight_j * W_2^e (silu(W_1^e h) * W_3^e h)

A choice of experts is not continuous, so a caller may hand in ``choices``
(the experts another computation chose for each token of each expert layer):
the weights and the sum are then taken at THOSE experts, still from this
file's own scores, and the arithmetic past the router is compared like for
like. Its own biased scores ``s + expert_bias`` come back either way, for
the caller to hold those choices against.

Sequences are padded at their END to the longest one's length, so that each
layer's function compiles once and not once a length (thirty compilations
were most of a check's three minutes): every operator is causal and every
feed-forward token-wise, so a padded position changes nothing before it, and
what is returned is cut to the real length.

It takes nothing the program has made. What the published ``config.json``
does not say (the configuration file's ``assumed``) is marked ASSUMED at
the line that makes it.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.llama_f32 import weight_reader  # noqa: F401


def forward(read, hf: dict, n_layers: int, seqs, choices=None):
    """seqs: [(token_ids, n_prompt, logit_positions)]; choices: None or, a
    sequence, int [T, L_moe, k'] -> one dict a sequence, numpy float32:
      logits [len(logit_positions), V]
      k, v   [L_attn, T, KV, hd]        the attention layers' rows (k after
                                        the head norm and the rotation)
      conv   [2, L_conv, W-1, D]        the conv layers' last W-1 values of
                                        v = B * u, oldest first, after token
                                        n_prompt-1 and after the last token
      biased [T, L_moe, E]              s + expert_bias (s where the config
                                        uses no bias)
      chosen [T, L_moe, k]              its own k largest of ``biased``
    One layer's weights are on the device at a time, for every sequence."""
    import jax
    import jax.numpy as jnp

    D = hf["hidden_size"]
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    # ASSUMED: head_dim = hidden_size / num_attention_heads (no head_dim key)
    hd = hf.get("head_dim") or D // H
    W = hf["conv_L_cache"]
    E, K = hf["num_experts"], hf["num_experts_per_tok"]
    nd = hf["num_dense_layers"]
    eps = float(hf["norm_eps"])
    theta = float(hf["rope_parameters"]["rope_theta"])
    scaling = float(hf.get("routed_scaling_factor", 1.0))
    kinds = list(hf["layer_types"])[:n_layers]
    assert not hf.get("conv_bias")

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x):             # x [T, heads, hd]
        # ASSUMED: the half-split convention (rotate_half), the whole head
        T = x.shape[0]
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
        rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
        return x * cos + rot * sin

    def tail(vp, n):         # rows n-W+1 .. n-1 of v, zeros before row 0
        return jax.lax.dynamic_slice_in_dim(vp, n, W - 1)

    def conv_op(h, w, n_prompt, n_total):
        T = h.shape[0]
        bcu = h @ w["in_proj"].T
        # ASSUMED: in_proj's three parts are B | C | u in this order
        B, C, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
        v = B * u
        vp = jnp.concatenate([jnp.zeros((W - 1, D)), v])
        y = sum(vp[j:j + T] * w["conv"][:, 0, j][None] for j in range(W))
        return (C * y) @ w["out_proj"].T, \
            jnp.stack([tail(vp, n_prompt), tail(vp, n_total)])

    def attn_op(h, w):
        T = h.shape[0]
        q = rope(rms((h @ w["q"].T).reshape(T, H, hd), w["q_norm"]))
        k = rope(rms((h @ w["k"].T).reshape(T, KV, hd), w["k_norm"]))
        v = (h @ w["v"].T).reshape(T, KV, hd)
        kk = jnp.repeat(k, H // KV, axis=1)
        vv = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("thd,shd->hts", q, kk) * hd ** -0.5
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shd->thd", p, vv).reshape(T, H * hd)
        return a @ w["o"].T, (k, v)

    def dense_ff(h, w):
        return (jax.nn.silu(h @ w["w1"].T) * (h @ w["w3"].T)) @ w["w2"].T

    def scores(h, w):
        # ASSUMED: scores in float32 (here everything is)
        s = jax.nn.sigmoid(h @ w["gate"].T)
        # the bias takes part in the choice only
        return s, (s + w["bias"][None] if hf.get("use_expert_bias") else s)

    def experts_ff(h, s, chosen, w1, w3, w2):
        """chosen [T, k']: the weighted sum over the chosen experts, one
        expert at a time over every token (a token that did not choose it
        weighs it 0)."""
        wt = jnp.take_along_axis(s, chosen, axis=1)
        if hf.get("norm_topk_prob", True):
            # ASSUMED: the 1e-6 sits in the denominator, beside the sum
            wt = wt / (jnp.sum(wt, -1, keepdims=True) + 1e-6)
        wt = wt * scaling
        comb = jnp.sum(jax.nn.one_hot(chosen, E) * wt[..., None], axis=1)

        def one(acc, e):
            a1, a3, a2, c = e
            y = (jax.nn.silu(h @ a1.T) * (h @ a3.T)) @ a2.T
            return acc + c[:, None] * y, None

        return jax.lax.scan(one, jnp.zeros_like(h), (w1, w3, w2, comb.T))[0]

    @jax.jit
    def run_attn(x, w):
        y, kv = attn_op(rms(x, w["op_norm"]), w)
        return x + y, kv

    @jax.jit
    def run_conv(x, w, n_prompt, n_total):
        y, tails = conv_op(rms(x, w["op_norm"]), w, n_prompt, n_total)
        return x + y, tails

    @jax.jit
    def run_dense(x, w):
        return x + dense_ff(rms(x, w["ff_norm"]), w)

    @jax.jit
    def run_scores(x, w):
        s, biased = scores(rms(x, w["ff_norm"]), w)
        return s, biased, jax.lax.top_k(biased, K)[1]

    @jax.jit
    def run_experts(x, s, chosen, w, w1, w3, w2):
        return x + experts_ff(rms(x, w["ff_norm"]), s, chosen, w1, w3, w2)

    @jax.jit
    def head(x, norm, emb):
        return rms(x, norm) @ emb.T

    # ASSUMED: tensor names (HF's for this model_type cannot be read here)
    names = {"op_norm": "operator_norm.weight", "ff_norm": "ffn_norm.weight"}
    op_names = {
        "conv": {"in_proj": "conv.in_proj.weight", "conv": "conv.conv.weight",
                 "out_proj": "conv.out_proj.weight"},
        "full_attention": {
            "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
            "v": "self_attn.v_proj.weight", "o": "self_attn.out_proj.weight",
            "q_norm": "self_attn.q_layernorm.weight",
            "k_norm": "self_attn.k_layernorm.weight"}}
    dense_names = {k: f"feed_forward.{k}.weight" for k in ("w1", "w2", "w3")}
    moe_names = {"gate": "feed_forward.gate.weight",
                 "bias": "feed_forward.expert_bias"}
    with jax.default_matmul_precision("highest"):
        embed = read("model.embed_tokens.weight")
        longest = max(len(ids) for ids, _, _ in seqs)

        def padded(a):       # to the longest sequence, at the end
            a = np.asarray(a)
            return np.concatenate(
                [a, np.zeros((longest - len(a),) + a.shape[1:], a.dtype)])

        xs = [jnp.asarray(embed[padded(ids)], jnp.float32)
              for ids, _, _ in seqs]
        out = [{"k": [], "v": [], "conv": [], "biased": [], "chosen": []}
               for _ in seqs]
        for i, kind in enumerate(kinds):
            ff = dense_names if i < nd else moe_names
            w = {k: jnp.asarray(read(f"model.layers.{i}.{n}"))
                 for k, n in {**names, **op_names[kind], **ff}.items()}
            if i >= nd:
                stacks = [jnp.asarray(np.stack([read(
                    f"model.layers.{i}.feed_forward.experts.{e}.{p}.weight")
                    for e in range(E)])) for p in ("w1", "w3", "w2")]
            for j, (ids, n_prompt, _) in enumerate(seqs):
                T = len(ids)
                if kind == "full_attention":
                    xs[j], (k, v) = run_attn(xs[j], w)
                    out[j]["k"].append(np.asarray(k)[:T])
                    out[j]["v"].append(np.asarray(v)[:T])
                else:
                    xs[j], c = run_conv(xs[j], w, n_prompt, T)
                    out[j]["conv"].append(np.asarray(c))
                if i < nd:
                    xs[j] = run_dense(xs[j], w)
                    continue
                s, biased, chosen = run_scores(xs[j], w)
                out[j]["biased"].append(np.asarray(biased)[:T])
                out[j]["chosen"].append(np.asarray(chosen)[:T])
                if choices is not None:
                    chosen = jnp.asarray(padded(choices[j][:, i - nd]),
                                         jnp.int32)
                xs[j] = run_experts(xs[j], s, chosen, w, *stacks)
            del w
        norm = jnp.asarray(read("model.embedding_norm.weight"))
        emb = jnp.asarray(embed)        # ASSUMED: the head is tied
        res = []
        for j, (_, _, at) in enumerate(seqs):
            o = out[j]
            res.append({
                "logits": np.asarray(head(xs[j][np.asarray(at)], norm, emb)),
                "k": np.stack(o["k"]), "v": np.stack(o["v"]),
                "conv": np.stack(o["conv"], 1),
                "biased": np.stack(o["biased"], 1),
                "chosen": np.stack(o["chosen"], 1)})
        return res
