"""The LFM2-MoE decoder (``lfm2_moe``): gated short-convolution layers beside
grouped-query attention layers (q and k normed per head, rotary), the first
``num_dense_layers`` followed by a dense SwiGLU feed-forward, every later one
by a routed expert feed-forward (sigmoid scores, the k largest of score +
bias chosen), a head tied to the embedding. The program side is
``models/lfm2_moe.py`` as the engine calls it (paged K/V for the attention
layers, a convolution tail a slot for the conv ones); the reference is
``benchmark/reference/lfm2_moe_f32.py``. Tensor names are this repo's reading
(the configuration's ``assumed``).

A CHOICE OF EXPERTS IS NOT CONTINUOUS. With bfloat16 activations the
program's scores differ from the float32 reference's by a few thousandths,
so now and then one of a token's experts flips, which moves that token's
expert output by a third: a plain ``logits_err`` would read several percent
for a sound program. The comparison therefore splits in two:

  * the group ``route`` holds the program's choices against the reference's
    OWN scores: an expert the program chose has to lie within ``ROUTE_SLACK``
    of the reference's k-th best biased score, and one it left out may not
    lie more than that above it. The group's arrays count, a (token, expert
    layer), the choices that do not: the reference's side is all ones, the
    program's is one plus its count, so ``||program - reference|| /
    ||reference||`` is exactly 0 when no choice is out of slack, and the
    configuration's limit for ``route_err`` is 0;
  * past the router the reference FOLLOWS the sound program's choices (its
    ``choices`` argument: the weights still come from its own scores), so
    that ``logits_err``, ``kv_err`` and ``conv_err`` measure arithmetic and
    are as tight as the other families'.

``reference/check.py`` calls ``reference`` before ``program``, so
``reference`` runs the sound program itself (the serving dtype is
the configuration's ``precision.weights``, the context the sequences' own
length rounded up to pages) and ``program`` hands the same result back for
the sound variant; a control runs afresh and is held against the same
reference, whose scores ``reference`` keeps (``_LAST``) for its ``route``
group.
"""

from __future__ import annotations

import os
import sys

import numpy as np

from benchmark.families.olmo_hybrid import _maker_keeps_freed_blocks

HF_KEYS = ("architectures", "model_type", "vocab_size", "hidden_size",
           "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads", "layer_types",
           "num_dense_layers", "num_experts", "num_experts_per_tok",
           "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
           "conv_L_cache", "conv_bias", "norm_eps", "rope_parameters",
           "max_position_embeddings", "tie_word_embeddings")
# every K and V row of the attention layers; every conv layer's tail after
# prefill and after the last decode step; the choices (module doc)
CHECK_GROUPS = ("kv", "conv", "route")
DECODE_KERNELS = ("paged_decode",)

CHUNK = 512                # the engine's prefill_chunk
PACK_BUCKETS = (512, 1024)  # the engine's pack buckets at that chunk
PAGE = 64                  # the engine's kv_page_size
# How far below the reference's k-th best biased score a chosen expert may
# lie (and an unchosen one above it). The program's scores are float32
# products of bfloat16 activations: the residual stream it norms differs from
# the reference's by under 1% in a random direction, a score's logit is of
# unit size and a sigmoid's slope at most a quarter. Measured on the chip over
# 10 seeds (PERF.md section 2, PR 40): the worst choice of a seed lies
# 0.0040-0.0070 from that score (scores computed in bfloat16 read the same);
# the slack is three times that and the spread of the bias (0.02), which is
# what the ``no_expert_bias`` control is caught by (its worst choice: 0.056 to
# 0.108).
ROUTE_SLACK = 0.02
BIAS_SCALE = 0.02          # expert_bias ~ N(0, BIAS_SCALE): a tenth of the
#                            scores' spread (sigmoid of N(0, 1): 0.21)


def _dims(hf: dict) -> dict:
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    kinds = list(hf["layer_types"])[:hf["num_hidden_layers"]]
    nd = hf["num_dense_layers"]
    return {"D": D, "F": hf["intermediate_size"],
            "Fe": hf["moe_intermediate_size"], "H": H,
            "hd": hf.get("head_dim") or D // H,
            "KV": hf["num_key_value_heads"], "W": hf["conv_L_cache"],
            "E": hf["num_experts"], "K": hf["num_experts_per_tok"],
            "nd": min(nd, len(kinds)), "kinds": kinds,
            "n_conv": kinds.count("conv"),
            "n_attn": kinds.count("full_attention"),
            "n_moe": max(len(kinds) - nd, 0)}


def tensor_table(cfg: dict, layers: int, vocab_rows: int = 0):
    """[(HF name, shape, kind[, (scale, shift)])] in file order. The router
    is a plain linear (its logits are N(0, 1) on a normed input, its
    scores sigmoid of that); ``expert_bias`` states its own scale, at which
    it changes the choice for a measurable share of tokens and leaves the
    load near even. No ``lm_head.weight``: the head is tied."""
    d = _dims({**cfg, "num_hidden_layers": layers})
    D, Vr = d["D"], vocab_rows or cfg["vocab_size"]
    t = [("model.embed_tokens.weight", (Vr, D), "embed")]
    for i, kind in enumerate(d["kinds"]):
        p = f"model.layers.{i}."
        t.append((p + "operator_norm.weight", (D,), "norm"))
        if kind == "conv":
            t += [(p + "conv.in_proj.weight", (3 * D, D), "linear"),
                  (p + "conv.conv.weight", (D, 1, d["W"]), "linear"),
                  (p + "conv.out_proj.weight", (D, D), "linear")]
        else:
            a = p + "self_attn."
            H, KV = d["H"] * d["hd"], d["KV"] * d["hd"]
            t += [(a + "q_proj.weight", (H, D), "linear"),
                  (a + "k_proj.weight", (KV, D), "linear"),
                  (a + "v_proj.weight", (KV, D), "linear"),
                  (a + "out_proj.weight", (D, H), "linear"),
                  (a + "q_layernorm.weight", (d["hd"],), "norm"),
                  (a + "k_layernorm.weight", (d["hd"],), "norm")]
        t.append((p + "ffn_norm.weight", (D,), "norm"))
        f = p + "feed_forward."
        if i < d["nd"]:
            t += [(f + "w1.weight", (d["F"], D), "linear"),
                  (f + "w3.weight", (d["F"], D), "linear"),
                  (f + "w2.weight", (D, d["F"]), "linear")]
            continue
        t += [(f + "gate.weight", (d["E"], D), "linear"),
              (f + "expert_bias", (d["E"],), "norm", (BIAS_SCALE, 0.0))]
        for e in range(d["E"]):
            t += [(f + f"experts.{e}.w1.weight", (d["Fe"], D), "linear"),
                  (f + f"experts.{e}.w3.weight", (d["Fe"], D), "linear"),
                  (f + f"experts.{e}.w2.weight", (D, d["Fe"]), "linear")]
    t.append(("model.embedding_norm.weight", (D,), "norm"))
    _maker_keeps_freed_blocks()
    return t


def _run_program(ckpt, hf, dtype_name, variant, seqs, context):
    """The family's ``load_hf_params`` (``engine/weights.py``: its cast, its
    expert stacks a layer at a time), ``ragged_prefill_routed`` over packs
    of up to 1024 tokens in chunks of 512 (fresh and ``continued``, one and
    several segments: the grouped expert form and, on the TPU, the Pallas
    ragged-prefill kernel), then ``decode_step`` as ``engine_decode`` calls
    it, through the paged cache with a shuffled page table and the slots'
    convolution tails (a slot past its last step is inactive and routes
    nowhere). A control changes the program's config (``config``: fields of
    ``Lfm2MoeConfig``) or asks for int8 weights (``quantization``).
    -> (logits [n_seq][d+1, V], {"kv": K then V [n_seq][L_attn, T, KV, hd],
    "conv": the tails after prefill then after the last step
    [n_seq][L_conv, W-1, D]}, choices [n_seq][T, L_moe, k])."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from localai_tpu.models import lfm2_moe as model
    from localai_tpu.ops import kvcache

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype_name]
    cfg = model.Lfm2MoeConfig.from_hf_config(hf, dtype=dtype)
    params = model.load_hf_params(ckpt, cfg, dtype=dtype,
                                  quantize=variant.get("quantization", ""))
    cfg = dataclasses.replace(cfg, **variant.get("config", {}))
    S = len(seqs)
    ck, cv = model.init_cache(cfg, S, context, dtype=dtype, page_size=PAGE)
    mp = context // PAGE
    ptab = np.random.default_rng(1).permutation(S * mp).astype(np.int32)
    ptab = jnp.asarray(ptab.reshape(S, mp))
    ck, cv = (kvcache.with_page_table(c, ptab) for c in (ck, cv))

    prefill = {c: jax.jit(lambda p, *a, c=c: model.ragged_prefill_routed(
        p, cfg, *a, continued=c)) for c in (False, True)}
    decode = jax.jit(lambda p, t, ln, act, k, v: model.decode_step(
        p, cfg, t, jnp.where(act, ln, context), act, k, v))

    def tails_of(s):
        return np.asarray(ck["conv"][:, s], np.float32)

    done = [0] * S
    logits = [[] for _ in range(S)]
    chosen = [[] for _ in range(S)]          # a sequence: [tokens, L_moe, k]
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
        lg, ck, cv, ch = prefill[cont](params, *map(jnp.asarray, (
            tok, pos, seg_of, slots, start, off, ln)), ck, cv)
        lg, ch = np.asarray(lg, np.float32), np.asarray(ch)
        assert (ch[:, used:] == cfg.num_experts).all(), \
            "a pad token of the pack was routed"
        for b, (s, st, o, n) in enumerate(segs):
            chosen[s].append(ch[:, o:o + n].swapaxes(0, 1))
            done[s] = st + n
            if done[s] == len(seqs[s][0]):
                logits[s].append(lg[b])
                after_prefill[s] = tails_of(s)
    steps = max(len(d) for _, d in seqs)
    for j in range(steps):
        live = np.asarray([j < len(d) for _, d in seqs])
        tok = np.asarray([d[j] if live[s] else 0
                          for s, (_, d) in enumerate(seqs)], np.int32)
        # a slot past its last step is inactive: no row, no tail, no expert
        ln = np.asarray([len(p) + j for p, _ in seqs], np.int32)
        lg, ck, cv, ch = decode(params, jnp.asarray(tok), jnp.asarray(ln),
                                jnp.asarray(live), ck, cv)
        lg, ch = np.asarray(lg, np.float32), np.asarray(ch)
        assert (ch[:, ~live] == cfg.num_experts).all(), \
            "a slot that does not decode was routed"
        for s in range(S):
            if live[s]:
                logits[s].append(lg[s])
                chosen[s].append(ch[:, s][None])
    rows = []
    for cache in (ck, cv):
        per_layer = [np.asarray(kvcache.rows_to_float(kvcache.gather_all_rows(
            kvcache.layer(cache, li)), jnp.float32))
            for li in range(cfg.attn_layers)]
        rows.append(np.stack(per_layer))            # [L_attn, S, C, KV, hdp]
    hd = cfg.head_dim_          # the pool pads a head to a multiple of 128
    ks = [rows[0][:, s, :len(p) + len(d), :, :hd]
          for s, (p, d) in enumerate(seqs)]
    vs = [rows[1][:, s, :len(p) + len(d), :, :hd]
          for s, (p, d) in enumerate(seqs)]
    # the program scales q, never k: K rows are the reference's own
    at_end = [tails_of(s) for s in range(S)]
    return ([np.stack(x) for x in logits],
            {"kv": ks + vs, "conv": after_prefill + at_end},
            [np.concatenate(c) for c in chosen])


def route_shortfall(chosen, biased, k: int):
    """chosen [T, L, k'] (the program's), biased [T, L, E] (the
    reference's) -> (below [T, L, k'], above [T, L, E]): how far each
    chosen expert's biased score lies below the reference's k-th best, and
    how far each expert left out lies above it (0 for a chosen one)."""
    kth = np.sort(biased, axis=-1)[..., -k][..., None]
    below = kth - np.take_along_axis(biased, chosen, axis=-1)
    out = np.ones(biased.shape, bool)
    np.put_along_axis(out, chosen, False, axis=-1)
    return below, np.where(out, biased - kth, 0.0)


def _route_group(chosen, ref):
    """The program's side of ``route`` (module doc): a (token, expert
    layer), one plus the choices out of slack."""
    k = ref["k"]
    out, worst = [], 0.0
    for c, b in zip(chosen, ref["biased"]):
        below, above = route_shortfall(c, b, k)
        worst = max(worst, float(below.max()), float(above.max()))
        out.append(1.0 + (below > ROUTE_SLACK).sum(-1)
                   + (above > ROUTE_SLACK).sum(-1))
    # how much of the slack the worst choice used: what ROUTE_SLACK is set by
    print(f"[lfm2_moe] route: the choice farthest from the reference's "
          f"k-th best score is {worst:.5f} off (slack {ROUTE_SLACK})",
          file=sys.stderr, flush=True)
    return out


# what the last ``reference`` call left for ``program``: "key" (checkpoint,
# sequences), "sound" (the sound program's result), "biased" and "k" (the
# reference's scores, for the ``route`` group)
_LAST: dict = {}


def _key(ckpt, seqs):
    return ckpt, hash(str(seqs))


def _context(seqs) -> int:
    return -(-max(len(p) + len(d) for p, d in seqs) // PAGE) * PAGE


def program(ckpt, hf, serving, variant, seqs, context):
    """The sound variant is the run ``reference`` made (module doc); a
    control runs here. -> (logits, {"kv", "conv", "route"})."""
    sound = _LAST["sound"] \
        if not variant and _LAST.get("key") == _key(ckpt, seqs) else None
    if sound is None:
        sound = _run_program(ckpt, hf, serving.get("dtype", "bfloat16"),
                             variant, seqs, context)
    logits, groups, chosen = sound
    return logits, {**groups, "route": _route_group(chosen, _LAST)}


def reference(ckpt, hf, layers, weights_precision, seqs):
    from safetensors import safe_open

    from benchmark.reference import lfm2_moe_f32 as ref_model

    sound = _run_program(ckpt, hf, weights_precision, {}, seqs,
                         _context(seqs))
    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        read = ref_model.weight_reader(h.get_tensor, weights_precision)
        ref = ref_model.forward(read, hf, layers, [
            (p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
            for p, d in seqs], choices=sound[2])
    _LAST.update(key=_key(ckpt, seqs), sound=sound,
                 biased=[r["biased"] for r in ref],
                 k=hf["num_experts_per_tok"])
    return [r["logits"] for r in ref], {
        "kv": [r[x] for x in ("k", "v") for r in ref],
        "conv": [r["conv"][i] for i in (0, 1) for r in ref],
        "route": [np.ones(r["biased"].shape[:2]) for r in ref]}


def param_counts(hf: dict) -> dict:
    """Parameters by group: the layers' operators and norms, the dense
    feed-forwards, the routers (weight and bias), the experts, the final
    norm, the embedding; the head is the embedding (tied) and counts 0."""
    d = _dims(hf)
    D, V = d["D"], hf["vocab_size"]
    conv = 3 * D * D + D * d["W"] + D * D
    H, KV = d["H"] * d["hd"], d["KV"] * d["hd"]
    attn = 2 * D * H + 2 * D * KV + 2 * d["hd"]
    return {"operators": d["n_conv"] * conv + d["n_attn"] * attn
            + 2 * D * len(d["kinds"]),
            "dense_ff": d["nd"] * 3 * D * d["F"],
            "routers": d["n_moe"] * (D * d["E"] + d["E"]),
            "experts": d["n_moe"] * d["E"] * expert_params(hf),
            "final_norm": D, "embed": V * D,
            "head": 0 if hf.get("tie_word_embeddings", True) else V * D}


def expert_params(hf: dict) -> int:
    """One expert of one layer: three projections."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def state_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """K and V rows one token leaves in every ATTENTION layer's cache (a
    conv layer's tail does not grow with context)."""
    d = _dims(hf)
    return 2 * d["n_attn"] * d["KV"] * d["hd"] * itemsize


def recurrent_state_bytes(hf: dict, itemsize: int = 2) -> int:
    """Bytes of convolution tail one slot holds in ONE conv layer."""
    d = _dims(hf)
    return (d["W"] - 1) * d["D"] * itemsize


def moe_experts_least_bytes(hf: dict, experts_touched: float,
                            weight_itemsize: int = 2) -> float:
    """Least HBM bytes of the expert products in which ``experts_touched``
    (distinct experts a layer a step, summed over layers and steps) were
    touched: each one's three projections read once. Activations and the
    router are left out: the least."""
    return experts_touched * expert_params(hf) * weight_itemsize


def decode_step_least_bytes(hf: dict, weight_itemsize: int,
                            live_tokens: float, batch: float,
                            state_itemsize: int = 2) -> float:
    """Least HBM bytes one decode step of ``batch`` sequences must move:
    every weight outside the experts once and the embedding table once as
    the head, one embedding row a sequence, every live K/V row of the
    attention layers once, and in every expert layer the ``k`` experts that
    ONE token chooses: what a batch whose rows all agree would touch. A
    step whose rows disagree reads more (nearly every expert at 48 rows),
    so this is a BOUND and the step's share of it reads low here;
    ``moe_experts_roofline`` counts the experts a capture's steps did
    touch. Activations and the convolution tails are left out."""
    p = param_counts(hf)
    d = _dims(hf)
    head = p["head"] or p["embed"]
    weights = (p["operators"] + p["dense_ff"] + p["routers"] + head
               + d["n_moe"] * d["K"] * expert_params(hf)) * weight_itemsize \
        + p["final_norm"] * 2
    embed_rows = batch * hf["hidden_size"] * weight_itemsize
    return weights + embed_rows \
        + live_tokens * state_bytes_per_token(hf, state_itemsize)


def decode_step_least_flops(hf: dict, live_tokens: float,
                            batch: float) -> float:
    """2 per weight a sequence uses (its k experts a layer, not all E) in
    the layers and the head, 4 * hd per query head per live K/V row in the
    attention layers."""
    p = param_counts(hf)
    d = _dims(hf)
    head = p["head"] or p["embed"]
    used = p["operators"] + p["dense_ff"] + p["routers"] + head \
        + d["n_moe"] * d["K"] * expert_params(hf)
    return 2 * batch * used + 4 * d["n_attn"] * d["H"] * d["hd"] * live_tokens


def decode_kernel_calls_per_step(hf: dict) -> int:
    """One paged-decode attention call an ATTENTION layer a step."""
    return _dims(hf)["n_attn"]
