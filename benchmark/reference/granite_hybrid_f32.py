"""A plain float32 forward pass of the Granite-4.0-H decoder
(``model_type: granitemoehybrid``, dense: ``num_local_experts`` 0): Mamba-2
state-space layers beside grouped-query attention layers, as ``layer_types``
says, each followed by a SwiGLU MLP. Straight ``jax.numpy`` in float32 at
``highest`` matmul precision; the state-space recurrence runs token by token
(the definition, not the chunked form), there is no cache, no kernel, no
batching. One sequence at a time, one layer's weights at a time, read from
the checkpoint file in HF layout (``[out, in]``).

With x the residual stream:

    x = E[token] * embedding_multiplier
    x = x + residual_multiplier * mixer(rmsnorm(x))        every layer
    x = x + residual_multiplier * mlp(rmsnorm(x))
    logits = (rmsnorm(x) @ E^T) / logits_scaling            (tied head)

    mlp(h) = W_out (silu(g) * u),  [g, u] = W_in h in halves

    attention: softmax(attention_multiplier * q k^T) v, causal, grouped
    (H query heads over KV key/value heads), no bias, no positional encoding

    mamba (Mamba-2, one group; per head a state H in R^{P x N}):
      [z, xBC, dt] = W_in h                       (d_inner | d_inner + 2N | heads)
      xBC = silu(conv1d_causal_depthwise(xBC) + b_conv),  [x, B, C] = xBC
      dt = softplus(dt + dt_bias),  a = exp(-exp(A_log) * dt)
      H_t = a_t H_{t-1} + (dt_t x_t) (x) B_t,   y_t = H_t C_t + D x_t
      out = W_out rmsnorm(y * silu(z))            (gate before norm, over d_inner)

It takes nothing the program has made. What the published ``config.json``
does not say (the configuration file's ``assumed``) is marked ASSUMED at
the line that makes it.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.llama_f32 import weight_reader  # noqa: F401


def forward(read, hf: dict, n_layers: int, seqs):
    """seqs: [(token_ids, n_prompt, logit_positions)] -> one dict a
    sequence, numpy float32:
      logits [len(logit_positions), V]
      k, v   [L_attn, T, KV, hd]          the attention layers' rows
      ssm    [2, L_ssm, H, P, N]          the mamba layers' state after
      conv   [2, L_ssm, 3, d_inner + 2N]  token n_prompt-1 and after the
                                          last token; conv = the last 3
                                          inputs of the convolution, oldest
                                          first, channels x|B|C
    One layer's weights are on the device at a time, for every sequence."""
    import jax
    import jax.numpy as jnp

    D = hf["hidden_size"]
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    # ASSUMED: head_dim = hidden_size / num_attention_heads (the source has
    # no head_dim key)
    hd = hf.get("head_dim") or D // H
    Hs, P = hf["mamba_n_heads"], hf["mamba_d_head"]
    Ns, W = hf["mamba_d_state"], hf["mamba_d_conv"]
    assert hf.get("mamba_n_groups", 1) == 1 and not hf.get("num_local_experts")
    Di = Hs * P
    F = hf["shared_intermediate_size"]
    eps = float(hf["rms_norm_eps"])
    emb_mult = float(hf["embedding_multiplier"])
    res_mult = float(hf["residual_multiplier"])
    attn_mult = float(hf["attention_multiplier"])
    logit_div = float(hf["logits_scaling"])
    kinds = list(hf["layer_types"])[:n_layers]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def mlp(h, w):
        gu = h @ w["mlp_in"].T
        # ASSUMED: input_linear's first half is the gate, its second the up
        return (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ w["mlp_out"].T

    def conv(x, w, b):       # x [T, C]; w [C, 1, W]; b [C]: causal, depthwise
        T = x.shape[0]
        xp = jnp.concatenate([jnp.zeros((W - 1, x.shape[1])), x])
        return sum(xp[j:j + T] * w[:, 0, j][None] for j in range(W)) + b[None]

    def tail(x, n):          # the last W-1 rows of x[:n], zeros before row 0
        xp = jnp.concatenate([jnp.zeros((W - 1, x.shape[1])), x[:n]])
        return xp[-(W - 1):]

    def recur(s, x, b, c, dt, a):    # token by token
        def step(s, t):
            xt, bt, ct, dtt, at = t
            s = at[:, None, None] * s \
                + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
            return s, jnp.einsum("hpn,n->hp", s, ct)
        return jax.lax.scan(step, s, (x, b, c, dt, a))

    def mamba_layer(h, w, n_prompt):
        T = h.shape[0]
        zxd = h @ w["in_proj"].T
        # ASSUMED: in_proj's columns are z | xBC | dt in this order
        z, pre, dt = zxd[:, :Di], zxd[:, Di:2 * Di + 2 * Ns], \
            zxd[:, 2 * Di + 2 * Ns:]
        act = jax.nn.silu(conv(pre, w["conv_w"], w["conv_b"]))
        x = act[:, :Di].reshape(T, Hs, P)
        b, c = act[:, Di:Di + Ns], act[:, Di + Ns:]
        # ASSUMED: dt is not clamped (time_step_limit (0, inf))
        dt = jax.nn.softplus(dt + w["dt_bias"])
        a = jnp.exp(-jnp.exp(w["A_log"]) * dt)
        args = (x, b, c, dt, a)
        s_p, y_p = recur(jnp.zeros((Hs, P, Ns)), *(t[:n_prompt] for t in args))
        s_e, y_e = recur(s_p, *(t[n_prompt:] for t in args))
        y = jnp.concatenate([y_p, y_e]) + w["D"][None, :, None] * x
        # the gate is applied before the norm, which runs over all of d_inner
        y = rms(y.reshape(T, Di) * jax.nn.silu(z), w["norm"])
        return y @ w["out_proj"].T, (
            jnp.stack([s_p, s_e]),
            jnp.stack([tail(pre, n_prompt), tail(pre, T)]))

    def attn_layer(h, w):
        T = h.shape[0]
        q = (h @ w["q"].T).reshape(T, H, hd)
        k = (h @ w["k"].T).reshape(T, KV, hd)
        v = (h @ w["v"].T).reshape(T, KV, hd)
        # no positional encoding: position_embedding_type "nope"
        kk = jnp.repeat(k, H // KV, axis=1)
        vv = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("thd,shd->hts", q, kk) * attn_mult
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shd->thd", p, vv).reshape(T, H * hd)
        return a @ w["o"].T, (k, v)         # attention_bias false

    def block(x, w, mixed):
        x = x + res_mult * mixed
        return x + res_mult * mlp(rms(x, w["post_norm"]), w)

    @jax.jit
    def run_attn(x, w):
        y, kv = attn_layer(rms(x, w["in_norm"]), w)
        return block(x, w, y), kv

    def run_mamba(x, w, n_prompt):
        y, st = mamba_layer(rms(x, w["in_norm"]), w, n_prompt)
        return block(x, w, y), st
    run_mamba = jax.jit(run_mamba, static_argnums=2)

    @jax.jit
    def head(x, norm, emb):
        return (rms(x, norm) @ emb.T) / logit_div

    common = {"in_norm": "input_layernorm.weight",
              "post_norm": "post_attention_layernorm.weight",
              "mlp_in": "shared_mlp.input_linear.weight",
              "mlp_out": "shared_mlp.output_linear.weight"}
    # ASSUMED: tensor names (HF's for this model_type cannot be read here)
    names = {
        "attention": {
            **common, **{k: f"self_attn.{k}_proj.weight" for k in "qkvo"}},
        "mamba": {
            **common, "in_proj": "mamba.in_proj.weight",
            "conv_w": "mamba.conv1d.weight", "conv_b": "mamba.conv1d.bias",
            "A_log": "mamba.A_log", "D": "mamba.D",
            "dt_bias": "mamba.dt_bias", "norm": "mamba.norm.weight",
            "out_proj": "mamba.out_proj.weight"}}
    with jax.default_matmul_precision("highest"):
        embed = read("model.embed_tokens.weight")
        xs = [jnp.asarray(embed[np.asarray(ids)], jnp.float32) * emb_mult
              for ids, _, _ in seqs]
        out = [{"k": [], "v": [], "ssm": [], "conv": []} for _ in seqs]
        for i, kind in enumerate(kinds):
            w = {k: jnp.asarray(read(f"model.layers.{i}.{n}"))
                 for k, n in names[kind].items()}
            for j, (_, n_prompt, _) in enumerate(seqs):
                if kind == "attention":
                    xs[j], (k, v) = run_attn(xs[j], w)
                    out[j]["k"].append(np.asarray(k))
                    out[j]["v"].append(np.asarray(v))
                else:
                    xs[j], (s, c) = run_mamba(xs[j], w, n_prompt)
                    out[j]["ssm"].append(np.asarray(s))
                    out[j]["conv"].append(np.asarray(c))
            del w
        norm = jnp.asarray(read("model.norm.weight"))
        emb = jnp.asarray(embed)        # tie_word_embeddings: no lm_head
        res = []
        for j, (_, _, at) in enumerate(seqs):
            o = out[j]
            res.append({
                "logits": np.asarray(head(xs[j][np.asarray(at)], norm, emb)),
                "k": np.stack(o["k"]), "v": np.stack(o["v"]),
                "ssm": np.stack(o["ssm"], 1),
                "conv": np.stack(o["conv"], 1)})
        return res
