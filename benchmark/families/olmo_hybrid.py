"""The Olmo-Hybrid decoder: periods of three gated-DeltaNet linear-attention
layers and one full-attention layer (QK-norm, no rotary), post-norm blocks,
SwiGLU MLPs. The program side is ``models/olmo_hybrid.py`` as the engine
calls it (paged K/V for the full layers, a recurrent state a slot for the
linear ones); the reference is ``benchmark/reference/olmo_hybrid_f32.py``.
Tensor names are this repo's reading (the configuration's ``assumed``)."""

from __future__ import annotations

import os

import numpy as np

HF_KEYS = ("architectures", "model_type", "vocab_size", "hidden_size",
           "intermediate_size", "num_hidden_layers", "num_attention_heads",
           "num_key_value_heads", "head_dim", "hidden_act",
           "max_position_embeddings", "attention_bias", "rms_norm_eps",
           "tie_word_embeddings", "layer_types", "linear_num_key_heads",
           "linear_num_value_heads", "linear_key_head_dim",
           "linear_value_head_dim", "linear_conv_kernel_dim",
           "linear_allow_neg_eigval", "rope_parameters")
# every K and V row of the full layers; every linear layer's recurrent state
# and convolution tail, after prefill and after the last decode step
CHECK_GROUPS = ("kv", "state", "conv")
DECODE_KERNELS = ("paged_decode",)

CHUNK = 512                # the engine's prefill_chunk
PACK_BUCKETS = (512, 1024)  # the engine's pack buckets at that chunk
PAGE = 64                  # the engine's kv_page_size
STATE_ITEMSIZE = 4         # the recurrent state is float32


def _dims(hf: dict) -> dict:
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    hd = hf.get("head_dim") or D // H
    Hl = hf["linear_num_value_heads"]
    K, V = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    kinds = list(hf["layer_types"])[:hf["num_hidden_layers"]]
    return {"D": D, "F": hf["intermediate_size"], "H": H, "hd": hd,
            "KV": hf["num_key_value_heads"], "Hl": Hl, "K": K, "V": V,
            "W": hf["linear_conv_kernel_dim"],
            "n_lin": kinds.count("linear_attention"),
            "n_full": kinds.count("full_attention"), "kinds": kinds}


def _maker_keeps_freed_blocks():
    """In the checkpoint maker's own process (``python -m
    benchmark.make_checkpoint``; nowhere else), have the allocator keep what
    numpy frees and hand it out again, in place of unmapping every block.
    The maker's threads draw each 64 MB of a checkpoint through about
    0.6 GB of temporaries (``make_checkpoint.block_values``), glibc maps and
    unmaps each one, and the benchmark machine takes unmapped pages back
    only after the maker has ended: its free memory fell by 1.2 to 1.5 GB
    for every second the maker ran, whatever else did, and a fault there is
    dear. The Mistral checkpoints end within the machine's 40 GiB; at this
    family's three periods the maker, alone, did not (PERF.md section 6,
    PR 30). With one arena on the heap, no mmap for large blocks and no
    trimming the same 6.5 GB are written in 15 s where 3.2 GB took 28, and
    the machine keeps 30 GB free. Set before the maker's threads start: a
    process that has threads already, as the check has, keeps their arenas,
    and a test process should not change its allocator."""
    import ctypes
    import sys

    spec_ = getattr(sys.modules.get("__main__"), "__spec__", None)
    if getattr(spec_, "name", "") != "benchmark.make_checkpoint":
        return

    M_TRIM_THRESHOLD, M_TOP_PAD, M_MMAP_MAX, M_ARENA_MAX = -1, -2, -4, -8
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):       # no glibc: nothing to tune
        return
    # the threshold is a size_t inside: -1 is its largest value, never trim
    for param, value in ((M_ARENA_MAX, 1), (M_MMAP_MAX, 0),
                         (M_TRIM_THRESHOLD, -1), (M_TOP_PAD, 1 << 26)):
        mallopt(param, value)


def tensor_table(cfg: dict, layers: int, vocab_rows: int = 0):
    """[(HF name, shape, kind[, (scale, shift)])] in file order. The decay's
    tensors state their own values so that alpha = exp(-exp(A_log) *
    softplus(W_a x + dt_bias)) lies where a trained model's does, about 0.9
    to 0.999: the state neither dies nor blows up over 1400 tokens."""
    d = _dims({**cfg, "num_hidden_layers": layers})
    D, F, Vr = d["D"], d["F"], vocab_rows or cfg["vocab_size"]
    qk, vv = d["Hl"] * d["K"], d["Hl"] * d["V"]
    t = [("model.embed_tokens.weight", (Vr, D), "embed")]
    for i, kind in enumerate(d["kinds"]):
        p = f"model.layers.{i}."
        if kind == "linear_attention":
            a = p + "linear_attn."
            t += [(a + "q_proj.weight", (qk, D), "linear"),
                  (a + "k_proj.weight", (qk, D), "linear"),
                  (a + "v_proj.weight", (vv, D), "linear"),
                  (a + "g_proj.weight", (vv, D), "linear"),
                  (a + "a_proj.weight", (d["Hl"], D), "linear",
                   (0.5 / np.sqrt(D), 0.0)),
                  (a + "b_proj.weight", (d["Hl"], D), "linear"),
                  (a + "q_conv1d.weight", (qk, 1, d["W"]), "linear",
                   (0.5, 0.0)),
                  (a + "k_conv1d.weight", (qk, 1, d["W"]), "linear",
                   (0.5, 0.0)),
                  (a + "v_conv1d.weight", (vv, 1, d["W"]), "linear",
                   (0.5, 0.0)),
                  (a + "A_log", (d["Hl"],), "norm", (0.3, -3.5)),
                  (a + "dt_bias", (d["Hl"],), "norm", (0.3, -1.0)),
                  (a + "o_norm.weight", (d["V"],), "norm"),
                  (a + "o_proj.weight", (D, vv), "linear")]
        else:
            a = p + "self_attn."
            H, KV = d["H"] * d["hd"], d["KV"] * d["hd"]
            t += [(a + "q_proj.weight", (H, D), "linear"),
                  (a + "k_proj.weight", (KV, D), "linear"),
                  (a + "v_proj.weight", (KV, D), "linear"),
                  (a + "q_norm.weight", (H,), "norm"),
                  (a + "k_norm.weight", (KV,), "norm"),
                  (a + "o_proj.weight", (D, H), "linear")]
        t += [(p + "post_attention_layernorm.weight", (D,), "norm"),
              (p + "mlp.gate_proj.weight", (F, D), "linear"),
              (p + "mlp.up_proj.weight", (F, D), "linear"),
              (p + "mlp.down_proj.weight", (D, F), "linear"),
              (p + "post_feedforward_layernorm.weight", (D,), "norm")]
    t.append(("model.norm.weight", (D,), "norm"))
    if not cfg.get("tie_word_embeddings", False):
        t.append(("lm_head.weight", (Vr, D), "linear"))
    _maker_keeps_freed_blocks()
    return t


def program(ckpt, hf, serving, variant, seqs, context):
    """The family's ``load_hf_params`` (``engine/weights.py``: its cast,
    its quantization), ``ragged_prefill`` over packs of up to 1024 tokens in
    chunks of 512 (fresh and ``continued``, one and several segments:
    the chunked delta rule and, on the TPU, the Pallas ragged-prefill
    kernel), then ``engine_decode`` through the paged cache with a
    shuffled page table and the slots' recurrent state. A control may hold
    the state in a lower precision (``state_dtype``).
    -> (logits [n_seq][d+1, V], {"kv": K then V [n_seq][L_full, T, KV, hd],
    "state": delta after prefill then after the last step [n_seq][L_lin, H,
    K, V], "conv": the convolution tails likewise [n_seq][L_lin, 3, Ch]})."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.models import olmo_hybrid as model
    from localai_tpu.ops import kvcache

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        serving.get("dtype", "bfloat16")]
    cfg = model.OlmoHybridConfig.from_hf_config(hf, dtype=dtype)
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
        return (np.asarray(ck["delta"][:, s], np.float32),
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
            for li in range(cfg.periods)]
        rows.append(np.stack(per_layer))            # [L_full, S, C, KV, hd]
    KV = cfg.num_kv_heads       # the pool pads its heads to a multiple of 8
    ks = [rows[0][:, s, :len(p) + len(d), :KV] for s, (p, d) in enumerate(seqs)]
    vs = [rows[1][:, s, :len(p) + len(d), :KV] for s, (p, d) in enumerate(seqs)]
    at_end = [state_of(s) for s in range(S)]
    return [np.stack(x) for x in logits], {
        "kv": ks + vs,
        "state": [x[0] for x in after_prefill] + [x[0] for x in at_end],
        "conv": [x[1] for x in after_prefill] + [x[1] for x in at_end]}


def reference(ckpt, hf, layers, weights_precision, seqs):
    from safetensors import safe_open

    from benchmark.reference import olmo_hybrid_f32 as ref_model

    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        read = ref_model.weight_reader(h.get_tensor, weights_precision)
        ref = ref_model.forward(read, hf, layers, [
            (p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
            for p, d in seqs])
    return [r["logits"] for r in ref], {
        "kv": [r[x] for x in ("k", "v") for r in ref],
        "state": [r["delta"][i] for i in (0, 1) for r in ref],
        "conv": [r["conv"][i] for i in (0, 1) for r in ref]}


def param_counts(hf: dict) -> dict:
    """Parameters by group: whole layers of each kind (mixer, MLP and the
    two post-norms), the final norm, the embedding, the head."""
    d = _dims(hf)
    D, F, V = d["D"], d["F"], hf["vocab_size"]
    qk, vv = d["Hl"] * d["K"], d["Hl"] * d["V"]
    mlp = 3 * D * F + 2 * D
    lin = D * (2 * qk + 3 * vv) + 2 * d["Hl"] * D \
        + (2 * qk + vv) * d["W"] + 2 * d["Hl"] + d["V"]
    H, KV = d["H"] * d["hd"], d["KV"] * d["hd"]
    full = 2 * D * H + 2 * D * KV + H + KV
    return {"linear_layers": d["n_lin"] * (lin + mlp),
            "full_layers": d["n_full"] * (full + mlp),
            "final_norm": D, "embed": V * D,
            "head": 0 if hf.get("tie_word_embeddings") else V * D}


def state_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """K and V rows one token leaves in every FULL layer's cache (a linear
    layer's state does not grow with context: ``recurrent_state_bytes``)."""
    d = _dims(hf)
    return 2 * d["n_full"] * d["KV"] * d["hd"] * itemsize


def recurrent_state_bytes(hf: dict) -> int:
    """Bytes of delta-rule state one slot holds in ONE linear layer."""
    d = _dims(hf)
    return d["Hl"] * d["K"] * d["V"] * STATE_ITEMSIZE


def gated_delta_decode_least_bytes(hf: dict, batch: float) -> float:
    """Least HBM bytes of one decode step's state updates: every live
    slot's state read once and written once in every linear layer."""
    return 2 * batch * _dims(hf)["n_lin"] * recurrent_state_bytes(hf)


def gated_delta_least_flops(hf: dict, tokens: float) -> float:
    """Least operations of the delta rule for ``tokens`` tokens through
    every linear layer: per head the decay (KV), S^T k (2KV), the rank-one
    write (2KV) and S^T q (2KV) - whatever form computes them."""
    d = _dims(hf)
    return 7 * tokens * d["n_lin"] * d["Hl"] * d["K"] * d["V"]


def decode_step_least_bytes(hf: dict, weight_itemsize: int,
                            live_tokens: float, batch: float,
                            state_itemsize: int = 2) -> float:
    """Least HBM bytes one decode step of ``batch`` sequences must move:
    every layer's weights and the head once (at the width they are stored
    in), the final norm, one embedding row a sequence, every live K/V row
    of the full layers once, and each live slot's recurrent state read and
    written once in every linear layer (a step rewrites it: the write is
    not optional, as a K/V row's is not counted). Activations, scales and
    the convolution tails are left out: the least."""
    p = param_counts(hf)
    weights = (p["linear_layers"] + p["full_layers"] + p["head"]) \
        * weight_itemsize + p["final_norm"] * 2
    embed_rows = batch * hf["hidden_size"] * weight_itemsize
    return weights + embed_rows \
        + live_tokens * state_bytes_per_token(hf, state_itemsize) \
        + gated_delta_decode_least_bytes(hf, batch)


def decode_step_least_flops(hf: dict, live_tokens: float,
                            batch: float) -> float:
    """2 per weight per sequence in the layers and the head, 4 * hd per
    head per live K/V row in the full layers, the delta rule a sequence."""
    p = param_counts(hf)
    d = _dims(hf)
    attn = 4 * d["n_full"] * d["H"] * d["hd"] * live_tokens
    return 2 * batch * (p["linear_layers"] + p["full_layers"] + p["head"]) \
        + attn + gated_delta_least_flops(hf, batch)


def decode_kernel_calls_per_step(hf: dict) -> int:
    """One paged-decode attention call a FULL layer a step."""
    return _dims(hf)["n_full"]
