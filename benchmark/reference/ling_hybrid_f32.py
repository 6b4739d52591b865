"""A plain float32 forward pass of Ling-3.0-flash's language decoder
(``model_type: ling_hybrid``): Kimi delta attention (KDA) layers beside
multi-head latent attention (MLA) layers, the first ``first_k_dense_replace``
layers followed by a dense SwiGLU feed-forward and every later one by a
routed expert feed-forward (group-limited choice) beside a shared expert.
Straight ``jax.numpy`` in float32 at ``highest`` matmul precision; no cache,
no kernel, no batching, no chunking of the recurrence (KDA runs token by
token), no absorbed projections (MLA materialises every head's keys and
values), no grouping of tokens by expert. One sequence at a time, one
layer's weights at a time, read from the checkpoint file in HF layout
(``[out, in]``).

With x the residual stream and norm = RMSNorm (learned weight,
``rms_norm_eps``), blocks pre-norm:                               # ASSUMED

    x = E[token]
    x = x + mix(norm_in(x));  x = x + ff(norm_post(x))       every layer
    logits = norm_out(x) @ W_head^T                          (untied) ASSUMED

    mix, layer i with (i + 1) % layer_group_size != 0: KDA, H heads of K = V:
        q, k, v = SiLU(conv4(W_q h)), SiLU(conv4(W_k h)), SiLU(conv4(W_v h))
                  (depthwise, causal, 4 taps, zeros before the first token)
        q = l2norm(q) * K^-0.5;  k = l2norm(k)               per head
        g = lower * sigmoid(exp(A_log_h) * (W_f h + dt_bias))    [H, K], <= 0
        beta = sigmoid(W_b h)                                    [H]
        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
        mix = W_o (RMSNorm_head(o) * sigmoid(W_g h))
    mix, every layer_group_size-th layer: MLA, H heads:
        q = W_q h in [H, nope | rope], RMS-normed per head, the rope part
            rotated; [c | r] = W_kva h; c = RMSNorm(c); r rotated (one for
            all heads); [k_nope_h | v_h] = W_kvb c
        scores = (q_nope . k_nope + q_rope . r) * (nope + rope)^-0.5, causal
        mix = W_o (o_h * sigmoid((W_gate h)_h))
    ff, dense:   W_2 (silu(W_1 h) * W_3 h)
    ff, experts: s = sigmoid(W_g h) over the E experts of the MODEL
                 c = s + expert_bias; a group (E / n_group consecutive
                 experts) scores the sum of its two largest c; the
                 topk_group best groups are kept; choice = the k largest c
                 inside them
                 weight = s[choice] / (sum s[choice] + 1e-20) * scaling
                 ff = sum_{j: choice_j HELD HERE} weight_j E_j(h) + E_shared(h)

A chip's SHARE. ``expert_parallel`` in the configuration says which of the
model's experts this chip holds (``rank, rank + size, ...``: strided). The
router scores all of them and the weights are normalised over all of a
token's k choices; the sum is over the held ones. What the absent experts
would have added is left out, and that partial result goes on to the next
layer, as it does in the program.

A choice of experts is not continuous, so a caller may hand in ``choices``
(the experts another computation chose for each token of each expert layer):
the weights and the sum are then taken at THOSE experts, still from this
file's own scores. Its own scores ``c`` and group scores come back either
way, for the caller to hold those choices against.

Sequences are padded at their END to the longest one's length (every mixer
is causal and every feed-forward token-wise); what is returned is cut to the
real length. It takes nothing the program has made. What the published
``config.json`` does not say (the configuration file's ``assumed``) is marked
ASSUMED at the line that makes it.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.llama_f32 import weight_reader  # noqa: F401


def held_experts(hf: dict):
    """The global ids of the experts this chip holds, sorted: the model's
    all, or the strided share ``expert_parallel`` states."""
    ep = hf.get("expert_parallel")
    if not ep:
        return list(range(hf["num_experts"]))
    return list(range(ep["rank"], ep["num_experts_total"], ep["size"]))


def forward(read, hf: dict, n_layers: int, seqs, choices=None):
    """seqs: [(token_ids, n_prompt, logit_positions)]; choices: None or, a
    sequence, int [T, L_moe, k] (global expert ids) -> one dict a sequence,
    numpy float32:
      logits [len(logit_positions), V]
      latent [L_mla, T, R + rope]       the MLA layers' rows ``[c | r]``
      state  [2, L_kda, H, K, V]        the KDA states after token
                                        n_prompt-1 and after the last token
      conv   [2, L_kda, W-1, 3HK]       the KDA layers' last W-1 inputs of
                                        the convolutions (q | k | v), then
      biased [T, L_moe, E]              s + expert_bias (s without a bias)
      groups [T, L_moe, n_group]        the groups' scores
      chosen [T, L_moe, k]              its own choice
    One layer's weights are on the device at a time, for every sequence."""
    import jax
    import jax.numpy as jnp

    H = hf["num_attention_heads"]
    K = hf.get("head_dim", 128)
    W = hf.get("short_conv_kernel_size", 4)
    lower = float(hf.get("kda_lower_bound", -5))
    nope, rdim = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    R, vdim = hf["kv_lora_rank"], hf["v_head_dim"]
    theta = float(hf["rope_theta"])
    eps = float(hf["rms_norm_eps"])
    nd = hf["first_k_dense_replace"]
    held = held_experts(hf)
    E = (hf.get("expert_parallel") or {}).get("num_experts_total",
                                              hf["num_experts"])
    k_tok = hf["num_experts_per_tok"]
    n_group, topk_group = hf.get("n_group", 1), hf.get("topk_group", 1)
    scaling = float(hf.get("routed_scaling_factor", 1.0))
    group = hf["layer_group_size"]
    # the flags that are false switch nothing on                    ASSUMED
    for flag in ("use_nGPT", "value_norm", "up_proj_norm",
                 "scale_router_input", "use_kda_lora", "use_mla_nope"):
        assert not hf.get(flag), flag
    assert hf.get("q_lora_rank") is None
    # the clamped SwiGLU is zero for every layer held
    assert not any(hf.get("expert_swiglu_limit_list", ())[:n_layers])
    assert not any(hf.get("share_expert_swiglu_limit_list", ())[:n_layers])

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def l2norm(x):
        # ASSUMED: flash-linear-attention's l2norm, eps 1e-6 under the root
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def rope(x):             # x [T, ..., rdim]: the last axis is rotated
        # ASSUMED: the half-split convention (rotate_half); use_mla_nope
        # false = the MLA layers rotate
        T = x.shape[0]
        inv = 1.0 / theta ** (jnp.arange(0, rdim, 2, dtype=jnp.float32)
                              / rdim)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
        ang = jnp.concatenate([ang, ang], -1).reshape(
            (T,) + (1,) * (x.ndim - 2) + (rdim,))
        rot = jnp.concatenate([-x[..., rdim // 2:], x[..., :rdim // 2]], -1)
        return x * jnp.cos(ang) + rot * jnp.sin(ang)

    def conv4(u, w):         # u [T, Ch], w [Ch, 1, W]: causal, depthwise
        T = u.shape[0]
        up = jnp.concatenate([jnp.zeros((W - 1, u.shape[1])), u])
        return sum(up[j:j + T] * w[:, 0, j][None] for j in range(W))

    def kda_op(h, w, n_prompt, n_total):
        T = h.shape[0]
        pre = [h @ w[n].T for n in ("q", "k", "v")]
        q, k, v = (jax.nn.silu(conv4(u, w[n + "_conv"])).reshape(T, H, K)
                   for u, n in zip(pre, ("q", "k", "v")))  # linear_silu
        q = l2norm(q) * K ** -0.5
        k = l2norm(k)
        # ASSUMED: kda_safe_gate = fla's lower-bound gate; W_f is a single
        # matrix (no_kda_lora)
        g = lower * jax.nn.sigmoid(
            jnp.exp(w["A_log"])[None, :, None]
            * (h @ w["f"].T + w["dt_bias"][None]).reshape(T, H, K))
        beta = jax.nn.sigmoid(h @ w["b"].T)      # ASSUMED: beta in (0, 1)

        def step(carry, x):
            s, at_prompt, at_end = carry
            qt, kt, vt, gt, bt, t = x
            s = s * jnp.exp(gt)[:, :, None]      # Diag(alpha) S: rows scale
            u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
            s = s + kt[:, :, None] * u[:, None, :]
            return (s, jnp.where(t == n_prompt - 1, s, at_prompt),
                    jnp.where(t == n_total - 1, s, at_end)), \
                jnp.einsum("hkv,hk->hv", s, qt)

        zero = jnp.zeros((H, K, K))
        (_, at_prompt, at_end), o = jax.lax.scan(
            step, (zero, zero, zero),
            (q, k, v, g, beta, jnp.arange(T)))
        # ASSUMED: group_norm_size 1 = the output norm is a head's own; the
        # KDA gate is per channel
        y = rms(o, w["o_norm"]) * jax.nn.sigmoid(h @ w["g"].T).reshape(T, H, K)
        prep = jnp.concatenate(
            [jnp.zeros((W - 1, 3 * H * K)), jnp.concatenate(pre, -1)])
        tails = jnp.stack([
            jax.lax.dynamic_slice_in_dim(prep, n_prompt, W - 1),
            jax.lax.dynamic_slice_in_dim(prep, n_total, W - 1)])
        return y.reshape(T, H * K) @ w["o"].T, \
            jnp.stack([at_prompt, at_end]), tails

    def mla_op(h, w):
        T = h.shape[0]
        q = (h @ w["q"].T).reshape(T, H, nope + rdim)
        if hf.get("use_qk_norm"):
            # ASSUMED: one RMSNorm of nope + rope a head on q, before the
            # rotary; on the key side the latent's own norm (a per-head key
            # norm after W_kvb would forbid the absorbed decode)
            q = rms(q, w["q_norm"])
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:])
        kva = h @ w["kva"].T
        c = rms(kva[:, :R], w["kv_norm"])
        r = rope(kva[:, R:])                     # shared by the heads
        kv = (c @ w["kvb"].T).reshape(T, H, nope + vdim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
             + jnp.einsum("thd,sd->hts", q_rope, r)) * (nope + rdim) ** -0.5
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v)
        # ASSUMED: the head-wise gate belongs to the MLA layer
        o = o * jax.nn.sigmoid(h @ w["head_gate"].T)[..., None]
        return o.reshape(T, H * vdim) @ w["o"].T, jnp.concatenate([c, r], -1)

    def dense_ff(h, w):
        return (jax.nn.silu(h @ w["w1"].T) * (h @ w["w3"].T)) @ w["w2"].T

    def scores(h, w):
        s = jax.nn.sigmoid(h @ w["gate"].T)
        # the bias takes part in the choice only
        c = s + w["bias"][None] if hf.get("moe_router_enable_expert_bias") \
            else s
        T = h.shape[0]
        grouped = c.reshape(T, n_group, E // n_group)
        # ASSUMED: a group's score is the sum of its two largest (DeepSeek-
        # V3's rule)
        gs = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
        kept = jax.lax.top_k(gs, topk_group)[1]
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None], 1)
        inside = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)
        return s, c, gs, jax.lax.top_k(inside, k_tok)[1]

    def experts_ff(h, s, chosen, w, w1, w3, w2):
        """chosen [T, k] global ids: the weighted sum over the chosen
        experts THAT ARE HELD, one expert at a time over every token (a
        token that did not choose it weighs it 0), and the shared expert."""
        wt = jnp.take_along_axis(s, chosen, axis=1)
        if hf.get("norm_topk_prob", True):
            # ASSUMED: the sum of the chosen scores is guarded by 1e-20
            wt = wt / (jnp.sum(wt, -1, keepdims=True) + 1e-20)
        wt = wt * scaling
        comb = jnp.sum(jax.nn.one_hot(chosen, E) * wt[..., None], axis=1)
        comb = comb[:, jnp.asarray(held)]                    # [T, E_held]

        def one(acc, e):
            a1, a3, a2, c = e
            y = (jax.nn.silu(h @ a1.T) * (h @ a3.T)) @ a2.T
            return acc + c[:, None] * y, None

        routed = jax.lax.scan(one, jnp.zeros_like(h), (w1, w3, w2, comb.T))[0]
        shared = (jax.nn.silu(h @ w["sh1"].T) * (h @ w["sh3"].T)) @ w["sh2"].T
        return routed + shared

    @jax.jit
    def run_mla(x, w):
        y, rows = mla_op(rms(x, w["in_norm"]), w)
        return x + y, rows

    @jax.jit
    def run_kda(x, w, n_prompt, n_total):
        y, states, tails = kda_op(rms(x, w["in_norm"]), w, n_prompt, n_total)
        return x + y, states, tails

    @jax.jit
    def run_dense(x, w):
        return x + dense_ff(rms(x, w["post_norm"]), w)

    @jax.jit
    def run_scores(x, w):
        return scores(rms(x, w["post_norm"]), w)

    @jax.jit
    def run_experts(x, s, chosen, w, w1, w3, w2):
        return x + experts_ff(rms(x, w["post_norm"]), s, chosen, w, w1, w3,
                              w2)

    @jax.jit
    def head(x, norm, w_head):
        return rms(x, norm) @ w_head.T

    # ASSUMED: model_type and tensor names (HF's for this model cannot be
    # read here)
    names = {"in_norm": "input_layernorm.weight",
             "post_norm": "post_attention_layernorm.weight"}
    a = "linear_attn."
    kda_names = {
        **{n: f"{a}{n}_proj.weight" for n in "qkvfbgo"},
        **{n + "_conv": f"{a}{n}_conv1d.weight" for n in "qkv"},
        "A_log": a + "A_log", "dt_bias": a + "dt_bias",
        "o_norm": a + "o_norm.weight"}
    a = "self_attn."
    mla_names = {"q": a + "q_proj.weight", "q_norm": a + "q_norm.weight",
                 "kva": a + "kv_a_proj_with_mqa.weight",
                 "kv_norm": a + "kv_a_layernorm.weight",
                 "kvb": a + "kv_b_proj.weight",
                 "head_gate": a + "g_proj.weight",
                 "o": a + "o_proj.weight"}
    dense_names = {"w1": "mlp.gate_proj.weight", "w3": "mlp.up_proj.weight",
                   "w2": "mlp.down_proj.weight"}
    moe_names = {"gate": "mlp.gate.weight", "bias": "mlp.gate.expert_bias",
                 "sh1": "mlp.shared_experts.gate_proj.weight",
                 "sh3": "mlp.shared_experts.up_proj.weight",
                 "sh2": "mlp.shared_experts.down_proj.weight"}
    with jax.default_matmul_precision("highest"):
        embed = read("model.embed_tokens.weight")
        longest = max(len(ids) for ids, _, _ in seqs)

        def padded(a):       # to the longest sequence, at the end
            a = np.asarray(a)
            return np.concatenate(
                [a, np.zeros((longest - len(a),) + a.shape[1:], a.dtype)])

        xs = [jnp.asarray(embed[padded(ids)], jnp.float32)
              for ids, _, _ in seqs]
        out = [{"latent": [], "state": [], "conv": [], "biased": [],
                "groups": [], "chosen": []} for _ in seqs]
        for i in range(n_layers):
            is_mla = (i + 1) % group == 0
            ff = dense_names if i < nd else moe_names
            w = {k: jnp.asarray(read(f"model.layers.{i}.{n}"))
                 for k, n in {**names, **(mla_names if is_mla else kda_names),
                              **ff}.items()}
            if i >= nd:
                stacks = [jnp.asarray(np.stack([read(
                    f"model.layers.{i}.mlp.experts.{e}.{p}.weight")
                    for e in held]))
                    for p in ("gate_proj", "up_proj", "down_proj")]
            for j, (ids, n_prompt, _) in enumerate(seqs):
                T = len(ids)
                if is_mla:
                    xs[j], rows = run_mla(xs[j], w)
                    out[j]["latent"].append(np.asarray(rows)[:T])
                else:
                    xs[j], st, tails = run_kda(xs[j], w, n_prompt, T)
                    out[j]["state"].append(np.asarray(st))
                    out[j]["conv"].append(np.asarray(tails))
                if i < nd:
                    xs[j] = run_dense(xs[j], w)
                    continue
                s, biased, gs, chosen = run_scores(xs[j], w)
                out[j]["biased"].append(np.asarray(biased)[:T])
                out[j]["groups"].append(np.asarray(gs)[:T])
                out[j]["chosen"].append(np.asarray(chosen)[:T])
                if choices is not None:
                    chosen = jnp.asarray(padded(choices[j][:, i - nd]),
                                         jnp.int32)
                xs[j] = run_experts(xs[j], s, chosen, w, *stacks)
            del w
        norm = jnp.asarray(read("model.norm.weight"))
        w_head = jnp.asarray(read("lm_head.weight"))     # ASSUMED: untied
        res = []
        for j, (_, _, at) in enumerate(seqs):
            o = out[j]
            res.append({
                "logits": np.asarray(head(xs[j][np.asarray(at)], norm,
                                          w_head)),
                "latent": np.stack(o["latent"]) if o["latent"]
                else np.zeros((0,)),
                "state": np.stack(o["state"], 1),
                "conv": np.stack(o["conv"], 1),
                "biased": np.stack(o["biased"], 1),
                "groups": np.stack(o["groups"], 1),
                "chosen": np.stack(o["chosen"], 1)})
        return res
