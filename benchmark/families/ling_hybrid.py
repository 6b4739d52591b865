"""Ling-3.0-flash's language decoder (``ling_hybrid``): Kimi delta attention
(KDA, a decay a key channel) layers beside multi-head latent attention (MLA)
layers, one MLA layer closing every ``layer_group_size`` layers; the first
``first_k_dense_replace`` followed by a dense SwiGLU feed-forward, every
later one by a routed expert feed-forward (sigmoid scores + bias, the choice
limited to the best ``topk_group`` of ``n_group`` groups) beside a shared
expert; an untied head. The program side is ``models/ling_hybrid.py`` as the
engine calls it (a latent page pool for the MLA layers, a state and a
convolution tail a slot for the KDA ones); the reference is
``benchmark/reference/ling_hybrid_f32.py``. Tensor names are this repo's
reading (the configuration's ``assumed``).

A CHIP'S SHARE OF THE EXPERTS. ``expert_parallel`` in the configuration
names the deployment's expert placement: ``num_experts_total`` over ``size``
chips, strided, this chip ``rank``; ``num_experts`` counts the experts held
here. The checkpoint holds those experts' tensors under their GLOBAL ids and
no other's; the router keeps the model's width. Program and reference
compute the held experts' part of a layer's result and the shared expert.

A CHOICE OF EXPERTS IS NOT CONTINUOUS, as ``families/lfm2_moe.py``'s module
doc says; the comparison splits the same way, with one more step for the
groups:

  * the group ``route`` holds the program's choices against the reference's
    OWN scores. A group the program chose from has to score within
    ``GROUP_SLACK`` of the reference's ``topk_group``-th best group; and,
    among the experts of the groups the program kept (those it chose from,
    filled up with the reference's best; where two groups tie within the
    slack, whichever filling reads best), a chosen expert has to lie within
    ``ROUTE_SLACK`` of the reference's k-th best biased score and one left
    out may not lie more than that above it. The group's arrays count, a
    (token, expert layer), what is out of slack: the reference's side is
    all ones, the program's one plus its count, so ``route_err`` is exactly
    0 when nothing is, and the configuration's limit is 0;
  * past the router the reference FOLLOWS the sound program's choices, so
    that ``logits_err``, ``latent_err``, ``state_err`` and ``conv_err``
    measure arithmetic.

``reference/check.py`` calls ``reference`` before ``program``, so
``reference`` runs the sound program itself and ``program`` hands the same
result back for the sound variant (``_LAST``); a control runs afresh and is
held against the same reference.

This module also has the counts the three readers of this family's own
per-layer metrics take (``kda_decode_least_bytes``, ``mla_decode_least``).
"""

from __future__ import annotations

import os
import sys
from itertools import combinations

import numpy as np

from benchmark.families.olmo_hybrid import _maker_keeps_freed_blocks
from benchmark.reference.ling_hybrid_f32 import held_experts  # noqa: F401

HF_KEYS = (
    "architectures", "model_type", "vocab_size", "hidden_size",
    "intermediate_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "first_k_dense_replace", "layer_group_size", "short_conv_kernel_size",
    "kda_lower_bound", "kda_safe_gate", "linear_silu", "no_kda_lora",
    "use_kda_lora", "mtp_use_kda", "num_kv_heads_for_linear_attn",
    "group_norm_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "use_qk_norm", "use_mla_nope",
    "gated_attention_proj_granularity_type", "rope_theta", "rotary_dim",
    "partial_rotary_factor", "rms_norm_eps", "num_experts",
    "expert_parallel", "num_experts_per_tok", "n_group", "topk_group",
    "norm_topk_prob", "moe_router_enable_expert_bias",
    "routed_scaling_factor", "score_function", "use_nGPT", "value_norm",
    "up_proj_norm", "scale_router_input", "expert_swiglu_limit_list",
    "share_expert_swiglu_limit_list", "max_position_embeddings",
    "tie_word_embeddings")
# every [c | r] row left in the latent pool; every KDA state and every
# convolution tail after prefill and after the last decode step; the choices
CHECK_GROUPS = ("latent", "state", "conv", "route")
DECODE_KERNELS = ("kda_decode", "mla_paged_decode")

CHUNK = 512                # the engine's prefill_chunk
PACK_BUCKETS = (512, 1024)  # the engine's pack buckets at that chunk
PAGE = 64                  # the engine's kv_page_size
# How far below the reference's k-th best biased score a chosen expert may
# lie (and an unchosen one above it), and how far below the reference's
# ``topk_group``-th best group a group the program chose from may score:
# families/lfm2_moe.py's reasoning (scores are float32 products of bfloat16
# activations, a sigmoid's slope is at most a quarter), and this family's
# own two readings on the chip (PERF.md section 2, PR 47): a run compares
# 2.4 million scores where lfm2_moe's compares a tenth of that, and its
# worst reads higher.
ROUTE_SLACK = 0.05
GROUP_SLACK = 0.02
BIAS_SCALE = 0.02          # expert_bias ~ N(0, BIAS_SCALE), as lfm2_moe's
# KDA's gate parameters as the maker draws them: exp(A_log) near 1, and
# dt_bias so that a channel's decay a token exp(-5 sigmoid(.)) runs from
# nearly none to strong over the channels (median 0.91, a tenth below 0.5)
A_LOG = (0.3, 0.0)
DT_BIAS = (1.5, -4.0)


def _dims(hf: dict) -> dict:
    L, g = hf["num_hidden_layers"], hf["layer_group_size"]
    mixers = ["mla" if (i + 1) % g == 0 else "kda" for i in range(L)]
    nd = min(hf["first_k_dense_replace"], L)
    ep = hf.get("expert_parallel") or {}
    return {"D": hf["hidden_size"], "F": hf["intermediate_size"],
            "Fe": hf["moe_intermediate_size"],
            "Fs": hf.get("moe_shared_expert_intermediate_size",
                         hf["moe_intermediate_size"]),
            "H": hf["num_attention_heads"], "K": hf.get("head_dim", 128),
            "W": hf.get("short_conv_kernel_size", 4),
            "R": hf["kv_lora_rank"], "nope": hf["qk_nope_head_dim"],
            "rope": hf["qk_rope_head_dim"], "vd": hf["v_head_dim"],
            "E": ep.get("num_experts_total", hf["num_experts"]),
            "Eh": hf["num_experts"], "k": hf["num_experts_per_tok"],
            "nd": nd, "mixers": mixers, "n_kda": mixers.count("kda"),
            "n_mla": mixers.count("mla"), "n_moe": L - nd}


def tensor_table(cfg: dict, layers: int, vocab_rows: int = 0):
    """[(HF name, shape, kind[, (scale, shift)])] in file order. Of the
    experts only those held here, under their global ids."""
    d = _dims({**cfg, "num_hidden_layers": layers})
    D, H, K = d["D"], d["H"], d["K"]
    Vr = vocab_rows or cfg["vocab_size"]
    t = [("model.embed_tokens.weight", (Vr, D), "embed")]
    for i, mixer in enumerate(d["mixers"]):
        p = f"model.layers.{i}."
        t.append((p + "input_layernorm.weight", (D,), "norm"))
        if mixer == "kda":
            a = p + "linear_attn."
            for n in "qkv":
                t += [(a + n + "_proj.weight", (H * K, D), "linear"),
                      (a + n + "_conv1d.weight", (H * K, 1, d["W"]),
                       "linear")]
            t += [(a + "f_proj.weight", (H * K, D), "linear"),
                  (a + "dt_bias", (H * K,), "norm", DT_BIAS),
                  (a + "A_log", (H,), "norm", A_LOG),
                  (a + "b_proj.weight", (H, D), "linear"),
                  (a + "g_proj.weight", (H * K, D), "linear"),
                  (a + "o_norm.weight", (K,), "norm"),
                  (a + "o_proj.weight", (D, H * K), "linear")]
        else:
            a = p + "self_attn."
            t += [(a + "q_proj.weight", (H * (d["nope"] + d["rope"]), D),
                   "linear"),
                  (a + "q_norm.weight", (d["nope"] + d["rope"],), "norm"),
                  (a + "kv_a_proj_with_mqa.weight", (d["R"] + d["rope"], D),
                   "linear"),
                  (a + "kv_a_layernorm.weight", (d["R"],), "norm"),
                  (a + "kv_b_proj.weight", (H * (d["nope"] + d["vd"]),
                                            d["R"]), "linear"),
                  (a + "g_proj.weight", (H, D), "linear"),
                  (a + "o_proj.weight", (D, H * d["vd"]), "linear")]
        t.append((p + "post_attention_layernorm.weight", (D,), "norm"))
        f = p + "mlp."
        if i < d["nd"]:
            t += [(f + "gate_proj.weight", (d["F"], D), "linear"),
                  (f + "up_proj.weight", (d["F"], D), "linear"),
                  (f + "down_proj.weight", (D, d["F"]), "linear")]
            continue
        t += [(f + "gate.weight", (d["E"], D), "linear"),
              (f + "gate.expert_bias", (d["E"],), "norm", (BIAS_SCALE, 0.0))]
        for e in held_experts(cfg):
            t += [(f + f"experts.{e}.gate_proj.weight", (d["Fe"], D),
                   "linear"),
                  (f + f"experts.{e}.up_proj.weight", (d["Fe"], D), "linear"),
                  (f + f"experts.{e}.down_proj.weight", (D, d["Fe"]),
                   "linear")]
        t += [(f + "shared_experts.gate_proj.weight", (d["Fs"], D), "linear"),
              (f + "shared_experts.up_proj.weight", (d["Fs"], D), "linear"),
              (f + "shared_experts.down_proj.weight", (D, d["Fs"]),
               "linear")]
    t.append(("model.norm.weight", (D,), "norm"))
    t.append(("lm_head.weight", (Vr, D), "linear"))
    _maker_keeps_freed_blocks()
    return t


def _run_program(ckpt, hf, dtype_name, variant, seqs, context):
    """The family's ``load_hf_params`` (``engine/weights.py``: its cast, the
    held experts' stacks a layer at a time), ``ragged_prefill_routed`` over
    packs of up to 1024 tokens in chunks of 512 (fresh and ``continued``,
    one and several segments: the chunked KDA rule, the materialised MLA
    form over the latent pool, the grouped expert form), then
    ``decode_step`` as ``engine_decode`` calls it, through the latent pool
    with a shuffled page table and the slots' states (the absorbed MLA form;
    on the TPU the two Pallas kernels; a slot past its last step is inactive
    and routes nowhere). A control changes the program's config
    (``config``), asks for int8 weights (``quantization``), holds the state
    lower (``state_dtype``) or the latent rows (``latent_dtype``).
    -> (logits [n_seq][d+1, V], {"latent": [n_seq][L_mla, T, R + rope],
    "state": after prefill then after the last step [n_seq][L_kda, H, K, V],
    "conv": likewise [n_seq][L_kda, W-1, 3HK]}, choices [n_seq][T, L_moe,
    k])."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from localai_tpu.models import ling_hybrid as model
    from localai_tpu.ops import kvcache

    names = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
             "float8_e4m3fn": jnp.float8_e4m3fn}
    dtype = names[dtype_name]
    cfg = model.LingHybridConfig.from_hf_config(hf, dtype=dtype)
    params = model.load_hf_params(ckpt, cfg, dtype=dtype,
                                  quantize=variant.get("quantization", ""))
    cfg = dataclasses.replace(cfg, **variant.get("config", {}))
    S = len(seqs)
    ck, cv = model.init_cache(
        cfg, S, context, dtype=names[variant.get("latent_dtype", dtype_name)],
        page_size=PAGE,
        state_dtype=names[variant.get("state_dtype", "float32")])
    mp = context // PAGE
    ptab = np.random.default_rng(1).permutation(S * mp).astype(np.int32)
    ptab = jnp.asarray(ptab.reshape(S, mp))
    ck, cv = (kvcache.with_page_table(c, ptab) for c in (ck, cv))

    prefill = {c: jax.jit(lambda p, *a, c=c: model.ragged_prefill_routed(
        p, cfg, *a, continued=c)) for c in (False, True)}
    decode = jax.jit(lambda p, t, ln, act, k, v: model.decode_step(
        p, cfg, t, jnp.where(act, ln, context), act, k, v))

    def leaf_of(name, s):
        return np.asarray(ck[name][:, s], np.float32)

    done = [0] * S
    logits = [[] for _ in range(S)]
    chosen = [[] for _ in range(S)]          # a sequence: [tokens, L_moe, k]
    after_prefill = {"kda": [None] * S, "conv": [None] * S}
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
                for name in after_prefill:
                    after_prefill[name][s] = leaf_of(name, s)
    steps = max(len(d) for _, d in seqs)
    for j in range(steps):
        live = np.asarray([j < len(d) for _, d in seqs])
        tok = np.asarray([d[j] if live[s] else 0
                          for s, (_, d) in enumerate(seqs)], np.int32)
        # a slot past its last step is inactive: no row, no state, no expert
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
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    rows = np.stack([np.asarray(kvcache.rows_to_float(
        kvcache.gather_all_rows(kvcache.layer(ck, li)), jnp.float32))
        for li in range(cfg.mla_layers)]) if cfg.mla_layers \
        else np.zeros((0, S, context, 1, width), np.float32)
    latent = [rows[:, s, :len(p) + len(d), 0, :width]
              for s, (p, d) in enumerate(seqs)]
    return ([np.stack(x) for x in logits],
            {"latent": latent,
             "state": after_prefill["kda"] + [leaf_of("kda", s)
                                              for s in range(S)],
             "conv": after_prefill["conv"] + [leaf_of("conv", s)
                                              for s in range(S)]},
            [np.concatenate(c) for c in chosen])


def _within(chosen, biased, kept, k: int):
    """One token: among the experts of the groups ``kept`` [n_group] bool,
    how far each chosen expert lies below the k-th best biased score, and
    how far each one left out lies above it."""
    per = biased.shape[-1] // kept.shape[-1]
    inside = np.where(np.repeat(kept, per, -1), biased, -np.inf)
    kth = np.sort(inside, -1)[..., -k][..., None]
    below = kth - np.take_along_axis(biased, chosen, -1)
    out = np.isfinite(inside)
    np.put_along_axis(out, chosen, False, axis=-1)
    return below, np.where(out, biased - kth, 0.0)


def route_shortfall(chosen, biased, groups, k: int, topk_group: int):
    """chosen [T, L, k] (the program's, global ids), biased [T, L, E] and
    groups [T, L, n_group] (the reference's scores) -> (group_below
    [T, L, k]: how far the group of each chosen expert scores below the
    reference's ``topk_group``-th best group; below [T, L, k], above
    [T, L, E]: among the experts of the groups the program KEPT, how far
    each chosen expert lies below the k-th best biased score, and how far
    each one left out lies above it).

    The groups the program kept are not all seen in its choice: those it
    chose from were kept, and the rest are the reference's best - unless
    two groups score within ``GROUP_SLACK`` of the ``topk_group``-th place,
    where a sound program may have kept either. A (token, layer) that reads
    out of slack under the reference's own filling is therefore read again
    under every filling the slack allows (each group clearly above the
    place kept, none clearly below it), and the reading with the fewest
    experts out of slack stands."""
    n_group = groups.shape[-1]
    per = biased.shape[-1] // n_group
    g_of = chosen // per                                        # [T, L, k]
    kth_g = np.sort(groups, -1)[..., -topk_group][..., None]
    group_below = kth_g - np.take_along_axis(groups, g_of, -1)
    used = np.zeros(groups.shape, bool)
    np.put_along_axis(used, g_of, True, axis=-1)
    # the program's own groups first, then the reference's best
    order = np.argsort(-(groups + np.where(used, 1e3, 0.0)), -1)
    kept = np.zeros(groups.shape, bool)
    np.put_along_axis(kept, order[..., :topk_group], True, axis=-1)
    below, above = _within(chosen, biased, kept, k)

    def out_of_slack(b, a):
        return int((b > ROUTE_SLACK).sum() + (a > ROUTE_SLACK).sum())

    for t, l in np.argwhere((below > ROUTE_SLACK).any(-1)
                            | (above > ROUTE_SLACK).any(-1)):
        gs, u = groups[t, l], used[t, l]
        sure = u | (gs > kth_g[t, l] + GROUP_SLACK)
        may = np.flatnonzero(~sure & (gs >= kth_g[t, l] - GROUP_SLACK))
        free = topk_group - int(sure.sum())
        best = out_of_slack(below[t, l], above[t, l])
        for extra in combinations(may, max(free, 0)) if free > 0 else ():
            alt = sure.copy()
            alt[list(extra)] = True
            b, a = _within(chosen[t, l], biased[t, l], alt, k)
            if out_of_slack(b, a) < best:
                best = out_of_slack(b, a)
                below[t, l], above[t, l] = b, a
    return group_below, below, above


def _route_group(chosen, ref):
    """The program's side of ``route`` (module doc): a (token, expert
    layer), one plus what is out of slack."""
    out, worst, worst_g = [], 0.0, 0.0
    for c, b, g in zip(chosen, ref["biased"], ref["groups"]):
        gb, below, above = route_shortfall(c, b, g, ref["k"],
                                           ref["topk_group"])
        worst = max(worst, float(below.max()), float(above.max()))
        worst_g = max(worst_g, float(gb.max()))
        out.append(1.0 + (gb > GROUP_SLACK).sum(-1)
                   + (below > ROUTE_SLACK).sum(-1)
                   + (above > ROUTE_SLACK).sum(-1))
    # how much of the slack the worst choice used: what the slacks are set by
    print(f"[ling_hybrid] route: the choice farthest from the reference's "
          f"k-th best score is {worst:.5f} off (slack {ROUTE_SLACK}), the "
          f"group farthest from its k-th best group {worst_g:.5f} (slack "
          f"{GROUP_SLACK})", file=sys.stderr, flush=True)
    return out


# what the last ``reference`` call left for ``program``: "key" (checkpoint,
# sequences), "sound" (the sound program's result), and the reference's
# scores for the ``route`` group
_LAST: dict = {}


def _key(ckpt, seqs):
    return ckpt, hash(str(seqs))


def _context(seqs) -> int:
    return -(-max(len(p) + len(d) for p, d in seqs) // PAGE) * PAGE


def program(ckpt, hf, serving, variant, seqs, context):
    """The sound variant is the run ``reference`` made (module doc); a
    control runs here. -> (logits, {"latent", "state", "conv", "route"})."""
    sound = _LAST["sound"] \
        if not variant and _LAST.get("key") == _key(ckpt, seqs) else None
    if sound is None:
        sound = _run_program(ckpt, hf, serving.get("dtype", "bfloat16"),
                             variant, seqs, _context(seqs))
    logits, groups, chosen = sound
    return logits, {**groups, "route": _route_group(chosen, _LAST)}


def reference(ckpt, hf, layers, weights_precision, seqs):
    from safetensors import safe_open

    from benchmark.reference import ling_hybrid_f32 as ref_model

    sound = _run_program(ckpt, hf, weights_precision, {}, seqs,
                         _context(seqs))
    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        # the checkpoint's values are bfloat16's exactly: a float32 program
        # (the CPU tests' toy width) reads the same weights
        read = ref_model.weight_reader(
            h.get_tensor, "bfloat16" if weights_precision == "float32"
            else weights_precision)
        ref = ref_model.forward(read, hf, layers, [
            (p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
            for p, d in seqs], choices=sound[2])
    _LAST.update(key=_key(ckpt, seqs), sound=sound,
                 biased=[r["biased"] for r in ref],
                 groups=[r["groups"] for r in ref],
                 k=hf["num_experts_per_tok"],
                 topk_group=hf.get("topk_group", 1))
    return [r["logits"] for r in ref], {
        "latent": [r["latent"] for r in ref],
        "state": [r["state"][i] for i in (0, 1) for r in ref],
        "conv": [r["conv"][i] for i in (0, 1) for r in ref],
        "route": [np.ones(r["biased"].shape[:2]) for r in ref]}


# ---- counts ----

def expert_params(hf: dict) -> int:
    """One routed expert of one layer: three projections."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def param_counts(hf: dict) -> dict:
    """Parameters HELD HERE by group: the mixers and the layers' norms, the
    dense feed-forwards, the routers (the model's width, and the bias), the
    held routed experts, the shared experts, the final norm, the embedding,
    the head."""
    d = _dims(hf)
    D, V, H, K = d["D"], hf["vocab_size"], d["H"], d["K"]
    kda = 6 * D * H * K + 3 * H * K * d["W"] + H * K + H + D * H + K
    mla = D * H * (d["nope"] + d["rope"]) + (d["nope"] + d["rope"]) \
        + D * (d["R"] + d["rope"]) + d["R"] \
        + d["R"] * H * (d["nope"] + d["vd"]) + D * H + H * d["vd"] * D
    return {"mixers": d["n_kda"] * kda + d["n_mla"] * mla
            + 2 * D * len(d["mixers"]),
            "dense_ff": d["nd"] * 3 * D * d["F"],
            "routers": d["n_moe"] * (D * d["E"] + d["E"]),
            "experts": d["n_moe"] * d["Eh"] * expert_params(hf),
            "shared": d["n_moe"] * 3 * D * d["Fs"],
            "final_norm": D, "embed": V * D,
            "head": 0 if hf.get("tie_word_embeddings", False) else V * D}


def state_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """The latent row one token leaves in every MLA layer's pool, as
    published (``[c | r]``; the pool pads it to a multiple of 128 columns).
    A KDA layer's state does not grow with context."""
    d = _dims(hf)
    return d["n_mla"] * (d["R"] + d["rope"]) * itemsize


def recurrent_state_bytes(hf: dict, itemsize: int = 4) -> int:
    """Bytes of KDA state one slot holds in ONE KDA layer (float32)."""
    d = _dims(hf)
    return d["H"] * d["K"] * d["K"] * itemsize


def moe_experts_least_bytes(hf: dict, experts_touched: float,
                            weight_itemsize: int = 2) -> float:
    """Least HBM bytes of the routed products in which ``experts_touched``
    (distinct HELD experts a layer a step, summed over layers and steps)
    were touched: each one's three projections read once."""
    return experts_touched * expert_params(hf) * weight_itemsize


def kda_decode_least_bytes(hf: dict, live_slot_calls: float) -> float:
    """Least HBM bytes of ``kda_decode`` calls in which ``live_slot_calls``
    slots were live, summed over the calls (a call is one KDA layer of one
    step): each one's float32 state read once and written once."""
    return 2.0 * live_slot_calls * recurrent_state_bytes(hf, 4)


def mla_decode_least(hf: dict, ctx_rows: float, live_slot_calls: float,
                     itemsize: int = 2):
    """(least HBM bytes, least operations) of ``mla_paged_decode`` calls
    whose live slots held ``ctx_rows`` latent rows in all (summed over the
    calls: a call is one MLA layer of one step) and numbered
    ``live_slot_calls``: each row read once as published (R + rope wide);
    the absorbed products, 2 per multiply-add: every head's score against a
    row over R + rope columns and its value sum over R, the slot's own token
    included."""
    d = _dims(hf)
    rows = ctx_rows + live_slot_calls
    return (ctx_rows * (d["R"] + d["rope"]) * itemsize,
            2.0 * rows * d["H"] * (2 * d["R"] + d["rope"]))


def decode_step_least_bytes(hf: dict, weight_itemsize: int,
                            live_tokens: float, batch: float,
                            state_itemsize: int = 2) -> float:
    """Least HBM bytes one decode step of ``batch`` sequences must move:
    every weight outside the routed experts once (the shared experts and the
    head among them), one embedding row a sequence, every live latent row
    once, every live sequence's KDA states read and written once (float32),
    and in every expert layer the held experts that ONE token's ``k``
    choices touch at the least: none need be held here, so none is counted.
    A BOUND, as families/lfm2_moe.py's: ``moe_experts_roofline`` counts the
    experts a capture's steps did touch."""
    p = param_counts(hf)
    d = _dims(hf)
    weights = (p["mixers"] + p["dense_ff"] + p["routers"] + p["shared"]
               + (p["head"] or p["embed"])) * weight_itemsize \
        + p["final_norm"] * 2
    return weights + batch * hf["hidden_size"] * weight_itemsize \
        + live_tokens * state_bytes_per_token(hf, state_itemsize) \
        + 2.0 * batch * d["n_kda"] * recurrent_state_bytes(hf, 4)


def decode_step_least_flops(hf: dict, live_tokens: float,
                            batch: float) -> float:
    """2 per weight a sequence uses outside the routed experts (of which it
    need use none here), the absorbed attention over the live latent rows,
    and 6 a state element for the KDA update (decay, write, read)."""
    p = param_counts(hf)
    d = _dims(hf)
    used = p["mixers"] + p["dense_ff"] + p["routers"] + p["shared"] \
        + (p["head"] or p["embed"])
    return 2 * batch * used \
        + 2.0 * d["n_mla"] * d["H"] * (2 * d["R"] + d["rope"]) * live_tokens \
        + 6.0 * batch * d["n_kda"] * d["H"] * d["K"] * d["K"]


def decode_kernel_calls_per_step(hf: dict) -> int:
    """One ``kda_decode`` call a KDA layer and one ``mla_paged_decode`` call
    an MLA layer a step."""
    d = _dims(hf)
    return d["n_kda"] + d["n_mla"]
