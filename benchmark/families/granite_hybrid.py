"""The Granite-4.0-H decoder (``granitemoehybrid``, dense): periods of
Mamba-2 state-space layers around one grouped-query attention layer with no
positional encoding, pre-norm blocks with muP multipliers, a shared SwiGLU
MLP after every mixer, a head tied to the embedding. The program side is
``models/granite_hybrid.py`` as the engine calls it (paged K/V for the
attention layers, a recurrent state a slot for the mamba ones); the
reference is ``benchmark/reference/granite_hybrid_f32.py``. Tensor names are
this repo's reading (the configuration's ``assumed``)."""

from __future__ import annotations

import os

import numpy as np

from benchmark.families.olmo_hybrid import _maker_keeps_freed_blocks

HF_KEYS = ("architectures", "model_type", "vocab_size", "hidden_size",
           "intermediate_size", "shared_intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "hidden_act", "max_position_embeddings", "attention_bias",
           "rms_norm_eps", "tie_word_embeddings", "layer_types",
           "attention_multiplier", "embedding_multiplier", "logits_scaling",
           "residual_multiplier", "mamba_chunk_size", "mamba_conv_bias",
           "mamba_d_conv", "mamba_d_head", "mamba_d_state", "mamba_expand",
           "mamba_n_groups", "mamba_n_heads", "mamba_proj_bias",
           "normalization_function", "num_experts_per_tok",
           "num_local_experts", "position_embedding_type", "rope_scaling",
           "rope_theta")
# every K and V row of the attention layers; every mamba layer's recurrent
# state and convolution tail, after prefill and after the last decode step;
# and, of the sequences that decode longest, the last state of each mamba
# layer's slowest heads alone (``_slow``): a state held in a lower precision
# drifts by a rounding a step for as long as a head remembers, which is a
# factor where a head remembers the whole decode and a fifth over the
# inputs' own rounding in the whole state's norm (PERF.md section 2)
CHECK_GROUPS = ("kv", "state", "conv", "state_slow")
SLOW_HEADS = 4             # a mamba layer
DECODE_KERNELS = ("paged_decode",)

CHUNK = 512                # the engine's prefill_chunk
PACK_BUCKETS = (512, 1024)  # the engine's pack buckets at that chunk
PAGE = 64                  # the engine's kv_page_size
STATE_ITEMSIZE = 4         # the recurrent state is float32


def _dims(hf: dict) -> dict:
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    kinds = list(hf["layer_types"])[:hf["num_hidden_layers"]]
    Hs, P, Ns = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    return {"D": D, "F": hf["shared_intermediate_size"], "H": H,
            "hd": hf.get("head_dim") or D // H,
            "KV": hf["num_key_value_heads"], "Hs": Hs, "P": P, "Ns": Ns,
            "Di": Hs * P, "Ch": Hs * P + 2 * Ns, "W": hf["mamba_d_conv"],
            "n_ssm": kinds.count("mamba"), "n_attn": kinds.count("attention"),
            "kinds": kinds}


def tensor_table(cfg: dict, layers: int, vocab_rows: int = 0):
    """[(HF name, shape, kind[, (scale, shift)])] in file order. The step
    and the decay state their own values so that they lie where the family
    initialises them: ``dt = softplus(W_dt h + dt_bias)`` about 0.001 to 0.1
    (W_dt h is N(0, 1); dt_bias N(-4.6, 0.7)), ``A = exp(A_log)`` about 1 to
    16 (A_log N(1.4, 0.7)): a decay of 0.2 to 0.999 a step, heads that
    forget in a few tokens beside heads that keep a thousand. Zeros there
    give 0.5 a step for every head, a state three tokens deep that would
    hide a state error. No ``lm_head.weight``: the head is tied."""
    d = _dims({**cfg, "num_hidden_layers": layers})
    D, F, Vr = d["D"], d["F"], vocab_rows or cfg["vocab_size"]
    t = [("model.embed_tokens.weight", (Vr, D), "embed")]
    for i, kind in enumerate(d["kinds"]):
        p = f"model.layers.{i}."
        t.append((p + "input_layernorm.weight", (D,), "norm"))
        if kind == "mamba":
            a = p + "mamba."
            t += [(a + "in_proj.weight", (d["Di"] + d["Ch"] + d["Hs"], D),
                   "linear"),
                  (a + "conv1d.weight", (d["Ch"], 1, d["W"]), "linear"),
                  (a + "conv1d.bias", (d["Ch"],), "norm", (0.2, 0.0)),
                  (a + "A_log", (d["Hs"],), "norm", (0.7, 1.4)),
                  (a + "D", (d["Hs"],), "norm", (0.3, 1.0)),
                  (a + "dt_bias", (d["Hs"],), "norm", (0.7, -4.6)),
                  (a + "norm.weight", (d["Di"],), "norm"),
                  (a + "out_proj.weight", (D, d["Di"]), "linear")]
        else:
            a = p + "self_attn."
            H, KV = d["H"] * d["hd"], d["KV"] * d["hd"]
            t += [(a + "q_proj.weight", (H, D), "linear"),
                  (a + "k_proj.weight", (KV, D), "linear"),
                  (a + "v_proj.weight", (KV, D), "linear"),
                  (a + "o_proj.weight", (D, H), "linear")]
        t += [(p + "post_attention_layernorm.weight", (D,), "norm"),
              (p + "shared_mlp.input_linear.weight", (2 * F, D), "linear"),
              (p + "shared_mlp.output_linear.weight", (D, F), "linear")]
    t.append(("model.norm.weight", (D,), "norm"))
    _maker_keeps_freed_blocks()
    return t


def _slow(ckpt, hf, seqs, last_states):
    """The ``state_slow`` group of either side: ``last_states`` [n_seq][L_ssm,
    H, P, N] -> [n_deep][L_ssm, SLOW_HEADS, P, N], the sequences with the
    most decode steps and, a layer, the heads whose decay at rest is nearest
    1 (``exp(A_log) * softplus(dt_bias)`` least), read from the checkpoint."""
    from safetensors import safe_open

    mamba = [i for i, k in enumerate(_dims(hf)["kinds"]) if k == "mamba"]
    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        rest = np.stack([
            np.exp(h.get_tensor(f"model.layers.{i}.mamba.A_log").astype(
                np.float64)) * np.logaddexp(0.0, h.get_tensor(
                    f"model.layers.{i}.mamba.dt_bias").astype(np.float64))
            for i in mamba])                                 # [L_ssm, H]
    heads = np.argsort(rest, axis=1, kind="stable")[:, :SLOW_HEADS]
    steps = max(len(d) for _, d in seqs)
    return [np.take_along_axis(np.asarray(last_states[s]),
                               heads[:, :, None, None], axis=1)
            for s, (_, d) in enumerate(seqs) if len(d) == steps]


def program(ckpt, hf, serving, variant, seqs, context):
    """The family's ``load_hf_params`` (``engine/weights.py``: its cast,
    its quantization), ``ragged_prefill`` over packs of up to 1024 tokens in
    chunks of 512 (fresh and ``continued``, one and several segments: the
    chunked state-space dual and, on the TPU, the Pallas ragged-prefill
    kernel), then ``engine_decode`` through the paged cache with a shuffled
    page table and the slots' recurrent state (on the TPU the
    ``mamba2_decode`` kernel; a slot past its last step is inactive). A
    control may hold the state in a lower precision (``state_dtype``).
    -> (logits [n_seq][d+1, V], {"kv": K then V [n_seq][L_attn, T, KV, hd],
    "state": the state after prefill then after the last step [n_seq][L_ssm,
    H, P, N], "conv": the convolution tails likewise [n_seq][L_ssm, 3, Ch],
    "state_slow": ``_slow`` of the states after the last step})."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.models import granite_hybrid as model
    from localai_tpu.ops import kvcache

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        serving.get("dtype", "bfloat16")]
    cfg = model.GraniteHybridConfig.from_hf_config(hf, dtype=dtype)
    quant = variant.get("quantization", serving.get("quantization", ""))
    state_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        variant.get("state_dtype", "float32")]
    params = model.load_hf_params(ckpt, cfg, dtype=dtype, quantize=quant)
    S = len(seqs)
    ck, cv = model.init_cache(cfg, S, context, dtype=dtype,
                              page_size=PAGE, state_dtype=state_dtype)
    mp = context // PAGE
    ptab = np.random.default_rng(1).permutation(S * mp).astype(np.int32)
    ptab = jnp.asarray(ptab.reshape(S, mp))
    ck, cv = (kvcache.with_page_table(c, ptab) for c in (ck, cv))

    prefill = {c: jax.jit(lambda p, *a, c=c: model.ragged_prefill(
        p, cfg, *a, continued=c)) for c in (False, True)}
    decode = jax.jit(lambda p, t, ln, act, k, v: model.engine_decode(
        p, cfg, t, ln, act, k, v))

    def state_of(s):
        return (np.asarray(ck["ssm"][:, s], np.float32),
                np.asarray(ck["conv"][:, s], np.float32))

    done = [0] * S
    logits = [[] for _ in range(S)]
    after_prefill = [None] * S
    while any(done[s] < len(seqs[s][0]) for s in range(S)):
        segs, used = [], 0          # one pack: segments up to 1024 tokens
        for s in range(S):
            n = min(CHUNK, len(seqs[s][0]) - done[s])
            if n > 0 and used + n <= PACK_BUCKETS[-1]:
                segs.append((s, done[s], used, n))
                used += n
        N = next(b for b in PACK_BUCKETS if b >= used)
        tok = np.zeros((N,), np.int32)
        pos = np.full((N,), context, np.int32)
        seg_of = np.full((N,), S, np.int32)
        slots = np.full((S,), S, np.int32)
        start, off, ln = (np.zeros((S,), np.int32) for _ in range(3))
        for b, (s, st, o, n) in enumerate(segs):
            tok[o:o + n] = seqs[s][0][st:st + n]
            pos[o:o + n] = np.arange(st, st + n)
            seg_of[o:o + n] = b
            slots[b], start[b], off[b], ln[b] = s, st, o, n
        cont = any(st > 0 for _, st, _, _ in segs)
        lg, ck, cv = prefill[cont](params, *map(jnp.asarray, (
            tok, pos, seg_of, slots, start, off, ln)), ck, cv)
        lg = np.asarray(lg, np.float32)
        for b, (s, st, o, n) in enumerate(segs):
            done[s] = st + n
            if done[s] == len(seqs[s][0]):
                logits[s].append(lg[b])
                after_prefill[s] = state_of(s)
    steps = max(len(d) for _, d in seqs)
    for j in range(steps):
        live = np.asarray([j < len(d) for _, d in seqs])
        tok = np.asarray([d[j] if live[s] else 0
                          for s, (_, d) in enumerate(seqs)], np.int32)
        # a slot past its last step is inactive: no row, no state update
        ln = np.asarray([len(p) + j for p, _ in seqs], np.int32)
        lg, ck, cv = decode(params, jnp.asarray(tok), jnp.asarray(ln),
                            jnp.asarray(live), ck, cv)
        lg = np.asarray(lg, np.float32)
        for s in range(S):
            if live[s]:
                logits[s].append(lg[s])
    rows = []
    for cache in (ck, cv):
        per_layer = [np.asarray(kvcache.rows_to_float(kvcache.gather_all_rows(
            kvcache.layer(cache, li)), jnp.float32))
            for li in range(cfg.attn_layers)]
        rows.append(np.stack(per_layer))            # [L_attn, S, C, KV, hd]
    hd = cfg.head_dim_          # the pool pads a head to a multiple of 128
    ks = [rows[0][:, s, :len(p) + len(d), :, :hd]
          for s, (p, d) in enumerate(seqs)]
    vs = [rows[1][:, s, :len(p) + len(d), :, :hd]
          for s, (p, d) in enumerate(seqs)]
    at_end = [state_of(s) for s in range(S)]
    return [np.stack(x) for x in logits], {
        "kv": ks + vs,
        "state": [x[0] for x in after_prefill] + [x[0] for x in at_end],
        "conv": [x[1] for x in after_prefill] + [x[1] for x in at_end],
        "state_slow": _slow(ckpt, hf, seqs, [x[0] for x in at_end])}


def reference(ckpt, hf, layers, weights_precision, seqs):
    from safetensors import safe_open

    from benchmark.reference import granite_hybrid_f32 as ref_model

    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        read = ref_model.weight_reader(h.get_tensor, weights_precision)
        ref = ref_model.forward(read, hf, layers, [
            (p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
            for p, d in seqs])
    return [r["logits"] for r in ref], {
        "kv": [r[x] for x in ("k", "v") for r in ref],
        "state": [r["ssm"][i] for i in (0, 1) for r in ref],
        "conv": [r["conv"][i] for i in (0, 1) for r in ref],
        "state_slow": _slow(ckpt, hf, seqs, [r["ssm"][1] for r in ref])}


def param_counts(hf: dict) -> dict:
    """Parameters by group: whole layers of each kind (mixer, MLP and the
    two norms), the final norm, the embedding; the head is the embedding
    (tied) and counts 0."""
    d = _dims(hf)
    D, F, V = d["D"], d["F"], hf["vocab_size"]
    mlp = 3 * D * F + 2 * D
    mamba = D * (d["Di"] + d["Ch"] + d["Hs"]) + d["Ch"] * d["W"] + d["Ch"] \
        + 3 * d["Hs"] + d["Di"] + d["Di"] * D
    H, KV = d["H"] * d["hd"], d["KV"] * d["hd"]
    attn = 2 * D * H + 2 * D * KV
    return {"mamba_layers": d["n_ssm"] * (mamba + mlp),
            "attention_layers": d["n_attn"] * (attn + mlp),
            "final_norm": D, "embed": V * D,
            "head": 0 if hf.get("tie_word_embeddings", True) else V * D}


def state_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """K and V rows one token leaves in every ATTENTION layer's cache (a
    mamba layer's state does not grow with context:
    ``recurrent_state_bytes``)."""
    d = _dims(hf)
    return 2 * d["n_attn"] * d["KV"] * d["hd"] * itemsize


def recurrent_state_bytes(hf: dict) -> int:
    """Bytes of state-space state one slot holds in ONE mamba layer."""
    d = _dims(hf)
    return d["Hs"] * d["P"] * d["Ns"] * STATE_ITEMSIZE


def mamba2_decode_least_bytes(hf: dict, live_slot_steps: float) -> float:
    """Least HBM bytes of the state updates of ``live_slot_steps`` (live
    slots x decode steps): each one's state read once and written once in
    every mamba layer. A slot that is not live moves nothing."""
    return 2 * live_slot_steps * _dims(hf)["n_ssm"] * recurrent_state_bytes(hf)


def mamba2_least_flops(hf: dict, tokens: float) -> float:
    """Least operations of the recurrence for ``tokens`` tokens through
    every mamba layer: per head the decay (PN), the rank-one write (2PN)
    and H C (2PN) - whatever form computes them."""
    d = _dims(hf)
    return 5 * tokens * d["n_ssm"] * d["Hs"] * d["P"] * d["Ns"]


def decode_step_least_bytes(hf: dict, weight_itemsize: int,
                            live_tokens: float, batch: float,
                            state_itemsize: int = 2) -> float:
    """Least HBM bytes one decode step of ``batch`` sequences must move:
    every layer's weights once and the embedding table once as the head (at
    the width they are stored in), the final norm, one embedding row a
    sequence, every live K/V row of the attention layers once, and each of
    the ``batch`` live slots' recurrent state read and written once in
    every mamba layer (a step rewrites it: the write is not optional).
    Activations, scales and the convolution tails are left out: the least."""
    p = param_counts(hf)
    head = p["head"] or p["embed"]
    weights = (p["mamba_layers"] + p["attention_layers"] + head) \
        * weight_itemsize + p["final_norm"] * 2
    embed_rows = batch * hf["hidden_size"] * weight_itemsize
    return weights + embed_rows \
        + live_tokens * state_bytes_per_token(hf, state_itemsize) \
        + mamba2_decode_least_bytes(hf, batch)


def decode_step_least_flops(hf: dict, live_tokens: float,
                            batch: float) -> float:
    """2 per weight per sequence in the layers and the head, 4 * hd per
    query head per live K/V row in the attention layers, the recurrence a
    sequence."""
    p = param_counts(hf)
    d = _dims(hf)
    head = p["head"] or p["embed"]
    attn = 4 * d["n_attn"] * d["H"] * d["hd"] * live_tokens
    return 2 * batch * (p["mamba_layers"] + p["attention_layers"] + head) \
        + attn + mamba2_least_flops(hf, batch)


def decode_kernel_calls_per_step(hf: dict) -> int:
    """One paged-decode attention call an ATTENTION layer a step."""
    return _dims(hf)["n_attn"]
