"""A plain float32 forward pass of the Olmo-Hybrid decoder
(``model_type: olmo_hybrid``): gated-DeltaNet linear-attention layers beside
full-attention layers, as ``layer_types`` says, each followed by a SwiGLU
MLP. Straight ``jax.numpy`` in float32 at ``highest`` matmul precision; the
linear layers' recurrence runs token by token (no chunking), there is no
cache, no kernel, no batching. One sequence at a time, one layer's weights
at a time, read from the checkpoint file in HF layout (``[out, in]``).

For a token ``x_t`` of a linear layer, per head:

    q~ = W_q x, k~ = W_k x, v~ = W_v x          each channel then through a
    causal depthwise convolution of width 4 over time, then SiLU
    q = l2norm(q) * K^-1/2, k = l2norm(k)
    beta = 2 sigmoid(W_b x)                      (linear_allow_neg_eigval)
    g = -exp(A_log) softplus(W_a x + dt_bias),   alpha = exp(g)
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t
    out = W_o [ RMSNorm_head(o_t) * SiLU(W_g x) ]

It takes nothing the program has made. What the published ``config.json``
does not say (the configuration file's ``assumed``) is marked ASSUMED at
the line that makes it.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.llama_f32 import weight_reader  # noqa: F401

L2_EPS = 1e-6


def forward(read, hf: dict, n_layers: int, seqs):
    """seqs: [(token_ids, n_prompt, logit_positions)] -> one dict a
    sequence, numpy float32:
      logits [len(logit_positions), V]
      k, v   [L_full, T, KV, hd]            the full layers' rows
      delta  [2, L_lin, H, K, V]            the linear layers' state after
      conv   [2, L_lin, 3, 2HK + HV]        token n_prompt-1 and after the
                                            last token; conv = the last 3
                                            inputs of the convolution,
                                            oldest first, channels q|k|v
    One layer's weights are on the device at a time, for every sequence."""
    import jax
    import jax.numpy as jnp

    D = hf["hidden_size"]
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or D // H
    Hl = hf["linear_num_value_heads"]
    assert hf["linear_num_key_heads"] == Hl     # as published: 30 and 30
    K, Vd = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    W = hf["linear_conv_kernel_dim"]
    eps = float(hf["rms_norm_eps"])
    kinds = list(hf["layer_types"])[:n_layers]
    beta_max = 2.0 if hf.get("linear_allow_neg_eigval") else 1.0

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def mlp(x, w):
        return (jax.nn.silu(x @ w["gate"].T) * (x @ w["up"].T)) @ w["down"].T

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    def conv(x, w):          # x [T, C]; w [C, 1, W]: causal, depthwise
        T = x.shape[0]
        xp = jnp.concatenate([jnp.zeros((W - 1, x.shape[1])), x])
        return sum(xp[j:j + T] * w[:, 0, j][None] for j in range(W))

    def tail(x, n):          # the last W-1 rows of x[:n], zeros before row 0
        xp = jnp.concatenate([jnp.zeros((W - 1, x.shape[1])), x[:n]])
        return xp[-(W - 1):]

    def recur(s, q, k, v, g, b):     # token by token
        def step(s, x):
            qt, kt, vt, gt, bt = x
            s = s * jnp.exp(gt)[:, None, None]
            u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
            s = s + kt[:, :, None] * u[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, qt)
        return jax.lax.scan(step, s, (q, k, v, g, b))

    def linear_layer(x, w, n_prompt):
        T = x.shape[0]
        pre = jnp.concatenate([x @ w["q"].T, x @ w["k"].T, x @ w["v"].T], -1)
        cw = jnp.concatenate([w["qc"], w["kc"], w["vc"]], 0)
        act = jax.nn.silu(conv(pre, cw))
        q = l2(act[:, :Hl * K].reshape(T, Hl, K)) * K ** -0.5
        k = l2(act[:, Hl * K:2 * Hl * K].reshape(T, Hl, K))
        v = act[:, 2 * Hl * K:].reshape(T, Hl, Vd)
        b = beta_max * jax.nn.sigmoid(x @ w["b"].T)
        g = -jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["a"].T + w["dt_bias"])
        s_p, o_p = recur(jnp.zeros((Hl, K, Vd)), *(
            a[:n_prompt] for a in (q, k, v, g, b)))
        s_e, o_e = recur(s_p, *(a[n_prompt:] for a in (q, k, v, g, b)))
        o = jnp.concatenate([o_p, o_e])
        gate = jax.nn.silu(x @ w["g"].T).reshape(T, Hl, Vd)
        # the head norm's weight is one [V] vector for every head
        y = (rms(o, w["o_norm"]) * gate).reshape(T, Hl * Vd) @ w["o"].T
        return y, (jnp.stack([s_p, s_e]),
                   jnp.stack([tail(pre, n_prompt), tail(pre, T)]))

    def full_layer(x, w):
        T = x.shape[0]
        # ASSUMED (OLMo 2 / OLMo 3): an RMSNorm over the WHOLE q and k
        # projections, before the heads are split
        q = rms(x @ w["q"].T, w["q_norm"]).reshape(T, H, hd)
        k = rms(x @ w["k"].T, w["k_norm"]).reshape(T, KV, hd)
        v = (x @ w["v"].T).reshape(T, KV, hd)
        # ASSUMED: no rotary embedding (rope_parameters.rope_theta is null;
        # position reaches these layers through the recurrent ones below)
        kk = jnp.repeat(k, H // KV, axis=1)
        vv = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("thd,shd->hts", q, kk) / np.sqrt(hd)
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shd->thd", p, vv).reshape(T, H * hd)
        return a @ w["o"].T, (k, v)         # ASSUMED: no bias anywhere

    def block(x, w, mixed):
        # ASSUMED (OLMo 2 / OLMo 3): post-norm, x + norm(f(x)), both halves
        x = x + rms(mixed, w["post_attn"])
        return x + rms(mlp(x, w), w["post_ff"])

    @jax.jit
    def run_full(x, w):
        y, kv = full_layer(x, w)
        return block(x, w, y), kv

    def run_linear(x, w, n_prompt):
        y, st = linear_layer(x, w, n_prompt)
        return block(x, w, y), st
    run_linear = jax.jit(run_linear, static_argnums=2)

    @jax.jit
    def head(x, norm, lm):
        return rms(x, norm) @ lm.T

    common = {"post_attn": "post_attention_layernorm.weight",
              "post_ff": "post_feedforward_layernorm.weight",
              "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
              "down": "mlp.down_proj.weight"}
    # ASSUMED: tensor names (HF's for this model_type cannot be read here)
    names = {
        "full_attention": {
            **common, **{k: f"self_attn.{k}_proj.weight" for k in "qkvo"},
            "q_norm": "self_attn.q_norm.weight",
            "k_norm": "self_attn.k_norm.weight"},
        "linear_attention": {
            **common, **{k: f"linear_attn.{k}_proj.weight" for k in "qkvgoab"},
            "qc": "linear_attn.q_conv1d.weight",
            "kc": "linear_attn.k_conv1d.weight",
            "vc": "linear_attn.v_conv1d.weight",
            "A_log": "linear_attn.A_log", "dt_bias": "linear_attn.dt_bias",
            "o_norm": "linear_attn.o_norm.weight"}}
    with jax.default_matmul_precision("highest"):
        embed = read("model.embed_tokens.weight")
        xs = [jnp.asarray(embed[np.asarray(ids)], jnp.float32)
              for ids, _, _ in seqs]
        del embed
        out = [{"k": [], "v": [], "delta": [], "conv": []} for _ in seqs]
        for i, kind in enumerate(kinds):
            w = {k: jnp.asarray(read(f"model.layers.{i}.{n}"))
                 for k, n in names[kind].items()}
            for j, (_, n_prompt, _) in enumerate(seqs):
                if kind == "full_attention":
                    xs[j], (k, v) = run_full(xs[j], w)
                    out[j]["k"].append(np.asarray(k))
                    out[j]["v"].append(np.asarray(v))
                else:
                    xs[j], (s, c) = run_linear(xs[j], w, n_prompt)
                    out[j]["delta"].append(np.asarray(s))
                    out[j]["conv"].append(np.asarray(c))
            del w
        norm = jnp.asarray(read("model.norm.weight"))
        lm = jnp.asarray(read("lm_head.weight"))
        res = []
        for j, (_, _, at) in enumerate(seqs):
            o = out[j]
            res.append({
                "logits": np.asarray(head(xs[j][np.asarray(at)], norm, lm)),
                "k": np.stack(o["k"]), "v": np.stack(o["v"]),
                "delta": np.stack(o["delta"], 1),
                "conv": np.stack(o["conv"], 1)})
        return res
