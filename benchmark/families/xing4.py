"""Xing4.0's decoder (``xing4_0``): multi-head latent attention (MLA) on every
layer, the query through a low-rank pair, YaRN on the rotary columns; every
sublayer under a manifold-constrained hyper-connection (``hc_mult`` residual
streams, a Sinkhorn-normalised mix); the first ``first_k_dense_replace``
layers followed by a dense SwiGLU feed-forward, every later one by a routed
expert feed-forward (sigmoid scores + ``e_score_correction_bias``, one group)
beside a shared expert; an untied head after the streams' read-out. The
program side is ``models/xing4.py`` as the engine calls it (a latent page
pool and nothing else in a slot); the reference is
``benchmark/reference/xing4_f32.py``. Tensor names are this repo's reading
(the configuration's ``assumed``).

A CHOICE OF EXPERTS IS NOT CONTINUOUS, as ``families/lfm2_moe.py``'s module
doc says, and the comparison splits the same way: the group ``route`` holds
the program's choices against the reference's OWN scores (a chosen expert
within ``ROUTE_SLACK`` of the reference's k-th best biased score, none left
out more than that above it; the arrays count what is out of slack, so the
limit is 0), and past the router the reference FOLLOWS the sound program's
choices, so that ``logits_err`` and ``latent_err`` measure arithmetic.

THE HYPER-CONNECTION'S OWN GROUP. ``hc`` holds the three weights ``[pre |
post | vec(M)]`` of the FIRST sublayer (layer 0's mixer), every token: its
input is the embedding, which both sides hold exactly, so the group reads
what the mixes are COMPUTED in (float32: about 1e-6) and nothing upstream of
them; a later sublayer's weights carry the bfloat16 streams' own rounding
(a few thousandths), which would hide a mix computed in bfloat16. The
program's side is ``ops/hyper.py::weights`` on the loaded leaves under the
variant's config. What the later sublayers do is in ``logits_err`` and
``latent_err``.

THE CONTROLS change what a checkpoint or a deployment could state (a
``config`` key ``from_hf_config`` reads, int8 weights, the pool's dtype),
zero a loaded leaf (``zero_leaves``: the selection bias), or, where the
program has no such switch and should have none, put the REFERENCE computed
lower in the program's place (``reference``: the mixes in bfloat16, which
ops/hyper.py holds in float32 whatever it is told).

``reference/check.py`` calls ``reference`` before ``program``, so
``reference`` runs the sound program itself and ``program`` hands the same
result back for the sound variant (``_LAST``); a control runs afresh and is
held against the same reference.

This module also has the counts ``mla_decode_roofline``'s reader takes
(``mla_decode_least``).
"""

from __future__ import annotations

import os
import sys

import numpy as np

from benchmark.families.lfm2_moe import route_shortfall
from benchmark.families.olmo_hybrid import _maker_keeps_freed_blocks

HF_KEYS = (
    "architectures", "model_type", "attention_bias", "ep_size",
    "first_k_dense_replace", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "max_position_embeddings",
    "moe_intermediate_size", "moe_layer_freq", "n_group", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_nextn_predict_layers", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
    "mhc_h_res_clamp_min", "mhc_h_res_clamp_max", "q_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_theta",
    "rope_scaling", "routed_scaling_factor", "scoring_func",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
    "vocab_size")
# every [c | r] row left in the latent pool; the choices; the first
# sublayer's three mixes (module doc)
CHECK_GROUPS = ("latent", "route", "hc")
DECODE_KERNELS = ("mla_paged_decode",)
HC_SUBLAYERS = ((0, 0),)

CHUNK = 512                # the engine's prefill_chunk
PACK_BUCKETS = (512, 1024)  # the engine's pack buckets at that chunk
PAGE = 64                  # the engine's kv_page_size
# How far below the reference's k-th best biased score a chosen expert may
# lie (and an unchosen one above it): families/lfm2_moe.py's reasoning; a
# check here compares 4.8 million scores (18.8 k tokens x 4 layers x 64) to
# lfm2_moe's quarter of a million. On the chip the worst choice of a seed
# lies 0.0057-0.0075 from that score (PERF.md section 2, PR 50): the slack
# is four times that, and a twelfth of what the ``no_expert_bias`` control's
# worst choice is out by (0.36-0.63).
ROUTE_SLACK = 0.03
BIAS_SCALE = 0.02          # e_score_correction_bias ~ N(0, BIAS_SCALE)
# The hyper-connections as the maker draws them: the matrix N(0, 1 / (n C))
# as any linear, so that m = W x / rms(x) is N(0, 1) a token; the three
# scales 1 + N(0, 0.05); the biases N(0, 0.5). The logits of pre, post and
# the residual mix then spread over about +-1.1: pre in 0.25-0.75, post in
# 0.5-1.5, and exp(A) varies about ninefold between its entries, so that
# the Sinkhorn rounds have work to do (one round leaves rows 10% off) and
# M is neither uniform nor a permutation.
HC_BIAS = (0.5, 0.0)


def _dims(hf: dict) -> dict:
    L = hf["num_hidden_layers"]
    nd = min(hf["first_k_dense_replace"], L)
    n = hf.get("hc_mult", 1)
    return {"D": hf["hidden_size"], "F": hf["intermediate_size"],
            "Fe": hf["moe_intermediate_size"],
            "H": hf["num_attention_heads"], "Rq": hf["q_lora_rank"],
            "R": hf["kv_lora_rank"], "nope": hf["qk_nope_head_dim"],
            "rope": hf["qk_rope_head_dim"], "vd": hf["v_head_dim"],
            "E": hf["n_routed_experts"], "k": hf["num_experts_per_tok"],
            "n": n, "hc": n * (n + 2) if n > 1 else 0, "L": L, "nd": nd,
            "n_moe": L - nd}


def _hc_rows(prefix: str, outs: int, d: dict, n_scales: int):
    return [(prefix + ".weight", (outs, d["n"] * d["D"]), "linear"),
            (prefix + ".scale", (n_scales,), "norm"),
            (prefix + ".bias", (outs,), "norm", HC_BIAS)]


def tensor_table(cfg: dict, layers: int, vocab_rows: int = 0):
    """[(HF name, shape, kind[, (scale, shift)])] in file order: the
    next-token model. Nothing of the multi-token prediction module
    (``num_nextn_predict_layers``; DeepSeek-V3's layout has it after the
    last layer): nothing reads it, how its block joins the residual streams
    is not published, and the loader skips such tensors by name
    (tests/test_xing4.py puts some there)."""
    d = _dims({**cfg, "num_hidden_layers": layers})
    D, H = d["D"], d["H"]
    Vr = vocab_rows or cfg["vocab_size"]
    t = [("model.embed_tokens.weight", (Vr, D), "embed")]
    for i in range(layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        t += [(p + "input_layernorm.weight", (D,), "norm"),
              (a + "q_a_proj.weight", (d["Rq"], D), "linear"),
              (a + "q_a_layernorm.weight", (d["Rq"],), "norm"),
              (a + "q_b_proj.weight", (H * (d["nope"] + d["rope"]), d["Rq"]),
               "linear"),
              (a + "kv_a_proj_with_mqa.weight", (d["R"] + d["rope"], D),
               "linear"),
              (a + "kv_a_layernorm.weight", (d["R"],), "norm"),
              (a + "kv_b_proj.weight", (H * (d["nope"] + d["vd"]), d["R"]),
               "linear"),
              (a + "o_proj.weight", (D, H * d["vd"]), "linear")]
        if d["hc"]:
            t += _hc_rows(p + "attn_hc", d["hc"], d, 3)
        t.append((p + "post_attention_layernorm.weight", (D,), "norm"))
        if d["hc"]:
            t += _hc_rows(p + "mlp_hc", d["hc"], d, 3)
        f = p + "mlp."
        if i < d["nd"]:
            t += [(f + "gate_proj.weight", (d["F"], D), "linear"),
                  (f + "up_proj.weight", (d["F"], D), "linear"),
                  (f + "down_proj.weight", (D, d["F"]), "linear")]
            continue
        t += [(f + "gate.weight", (d["E"], D), "linear"),
              (f + "gate.e_score_correction_bias", (d["E"],), "norm",
               (BIAS_SCALE, 0.0))]
        for e in range(d["E"]):
            t += [(f + f"experts.{e}.gate_proj.weight", (d["Fe"], D),
                   "linear"),
                  (f + f"experts.{e}.up_proj.weight", (d["Fe"], D), "linear"),
                  (f + f"experts.{e}.down_proj.weight", (D, d["Fe"]),
                   "linear")]
        t += [(f + "shared_experts.gate_proj.weight", (d["Fe"], D), "linear"),
              (f + "shared_experts.up_proj.weight", (d["Fe"], D), "linear"),
              (f + "shared_experts.down_proj.weight", (D, d["Fe"]),
               "linear")]
    if d["hc"]:
        t += _hc_rows("model.hc_head", d["n"], d, 1)
    t.append(("model.norm.weight", (D,), "norm"))
    t.append(("lm_head.weight", (Vr, D), "linear"))
    _maker_keeps_freed_blocks()
    return t


def _overlaid(hf: dict, over: dict) -> dict:
    """``hf`` with a control's keys laid over it, a nested group key by
    key."""
    out = dict(hf)
    for k, v in over.items():
        out[k] = {**hf.get(k, {}), **v} if isinstance(v, dict) else v
    return out


def _run_program(ckpt, hf, dtype_name, variant, seqs, context):
    """The family's ``load_hf_params`` (``engine/weights.py``: its cast, the
    experts' stacks a layer at a time), ``ragged_prefill_routed`` over packs of up to 1024 tokens in
    chunks of 512 (fresh and ``continued``, one and several segments: the
    materialised MLA form over the latent pool, 24 packs for a 12 k
    document; the grouped expert form), then ``decode_step`` as
    ``engine_decode`` calls it, through the latent pool with a shuffled page
    table (the absorbed MLA form; on the TPU the Pallas kernel; a slot past
    its last step is inactive and routes nowhere). A control changes what
    the checkpoint's config says (``config``), asks for int8 weights
    (``quantization``), holds the latent rows lower (``latent_dtype``) or
    zeroes loaded leaves of the layer stack (``zero_leaves``).
    -> (logits [n_seq][d+1, V], {"latent": [n_seq][L, T, R + rope], "hc":
    [n_seq][T, 2 n + n n]}, choices [n_seq][T, L_moe, k])."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.models import xing4 as model
    from localai_tpu.ops import hyper, kvcache

    names = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
             "float8_e4m3fn": jnp.float8_e4m3fn}
    dtype = names[dtype_name]
    cfg = model.Xing4Config.from_hf_config(
        _overlaid(hf, variant.get("config", {})), dtype=dtype)
    params = model.load_hf_params(ckpt, cfg, dtype=dtype,
                                  quantize=variant.get("quantization", ""))
    for leaf in variant.get("zero_leaves", ()):
        params["layers"][leaf] = jnp.zeros_like(params["layers"][leaf])
    S = len(seqs)
    ck, cv = model.init_cache(
        cfg, S, context, dtype=names[variant.get("latent_dtype", dtype_name)],
        page_size=PAGE)
    mp = context // PAGE
    ptab = np.random.default_rng(1).permutation(S * mp).astype(np.int32)
    # a table each: the pool is donated to every call, the empty plane not
    ck, cv = (kvcache.with_page_table(c, jnp.array(ptab.reshape(S, mp)))
              for c in (ck, cv))

    prefill = {c: jax.jit(lambda p, *a, c=c: model.ragged_prefill_routed(
        p, cfg, *a, continued=c), donate_argnums=(8,))
        for c in (False, True)}
    decode = jax.jit(lambda p, t, ln, act, k, v: model.decode_step(
        p, cfg, t, jnp.where(act, ln, context), act, k, v),
        donate_argnums=(4,))

    done = [0] * S
    logits = [[] for _ in range(S)]
    chosen = [[] for _ in range(S)]          # a sequence: [tokens, L_moe, k]
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
    steps = max(len(d) for _, d in seqs)
    for j in range(steps):
        live = np.asarray([j < len(d) for _, d in seqs])
        tok = np.asarray([d[j] if live[s] else 0
                          for s, (_, d) in enumerate(seqs)], np.int32)
        # a slot past its last step is inactive: no row, no expert
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
    latent = [[] for _ in range(S)]
    for li in range(cfg.num_layers):      # a layer at a time: 12 k rows each
        rows = np.asarray(kvcache.rows_to_float(kvcache.gather_all_rows(
            kvcache.layer(ck, li)), jnp.float32))
        for s, (p, d) in enumerate(seqs):
            latent[s].append(rows[s, :len(p) + len(d), 0, :width])
    groups = {"latent": [np.stack(x) for x in latent], "hc": []}
    if cfg.hc_mult > 1:
        @jax.jit
        def first_mix(params, tokens):
            X = model._embed(params, tokens, cfg)
            hc = tuple(params["layers"][k][0, 0]
                       for k in ("hc_w", "hc_s", "hc_b"))
            pre, post, M = hyper.weights(X, hc, cfg.hc_params)
            return jnp.concatenate(
                [pre, post, M.reshape(-1, M.shape[-1])]).T

        groups["hc"] = [np.asarray(first_mix(
            params, jnp.asarray(p + d, jnp.int32)), np.float32)
            for p, d in seqs]
    return ([np.stack(x) for x in logits], groups,
            [np.concatenate(c) for c in chosen])


def _route_group(chosen, ref):
    """The program's side of ``route`` (module doc): a (token, expert
    layer), one plus the choices out of slack."""
    out, worst = [], 0.0
    for c, b in zip(chosen, ref["biased"]):
        if not c.size:
            out.append(np.ones(c.shape[:2]))
            continue
        below, above = route_shortfall(c, b, ref["k"])
        worst = max(worst, float(below.max()), float(above.max()))
        out.append(1.0 + (below > ROUTE_SLACK).sum(-1)
                   + (above > ROUTE_SLACK).sum(-1))
    # how much of the slack the worst choice used: what ROUTE_SLACK is set by
    print(f"[xing4] route: the choice farthest from the reference's k-th "
          f"best score is {worst:.5f} off (slack {ROUTE_SLACK})",
          file=sys.stderr, flush=True)
    return out


# what the last ``reference`` call left for ``program``: "key" (checkpoint,
# sequences), "sound" (the sound program's result), "layers" and
# "weights_precision" (what it was asked for), "biased" and "k" (the
# reference's scores, for the ``route`` group)
_LAST: dict = {}


def _key(ckpt, seqs):
    return ckpt, hash(str(seqs))


def _context(seqs) -> int:
    return -(-max(len(p) + len(d) for p, d in seqs) // PAGE) * PAGE


def _reference_pass(ckpt, hf, layers, weights_precision, seqs, **how):
    """``benchmark/reference/xing4_f32.py::forward`` on the checkpoint."""
    from safetensors import safe_open

    from benchmark.reference import xing4_f32 as ref_model

    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        # the checkpoint's values are bfloat16's exactly: a float32 program
        # (the CPU tests' toy width) reads the same weights
        read = ref_model.weight_reader(
            h.get_tensor, "bfloat16" if weights_precision == "float32"
            else weights_precision)
        return ref_model.forward(read, hf, layers, [
            (p + d, len(p), list(range(len(p) - 1, len(p) + len(d))))
            for p, d in seqs], hc_sublayers=HC_SUBLAYERS, **how)


def program(ckpt, hf, serving, variant, seqs, context):
    """The sound variant is the run ``reference`` made (module doc); a
    control runs here: the program, or with ``reference`` the reference
    computed lower in its place, on its own choices.
    -> (logits, {"latent", "route", "hc"})."""
    same = _LAST.get("key") == _key(ckpt, seqs)
    if "reference" in variant:
        assert same, "a control follows the reference of its own sequences"
        low = _reference_pass(ckpt, hf, _LAST["layers"],
                              _LAST["weights_precision"], seqs,
                              **variant["reference"])
        return [r["logits"] for r in low], {
            "latent": [r["latent"] for r in low],
            "hc": [a for r in low for a in r["hc"]],
            "route": _route_group([r["chosen"] for r in low], _LAST)}
    sound = _LAST["sound"] if not variant and same else None
    if sound is None:
        sound = _run_program(ckpt, hf, serving.get("dtype", "bfloat16"),
                             variant, seqs, _context(seqs))
    logits, groups, chosen = sound
    return logits, {**groups, "route": _route_group(chosen, _LAST)}


def reference(ckpt, hf, layers, weights_precision, seqs):
    sound = _run_program(ckpt, hf, weights_precision, {}, seqs,
                         _context(seqs))
    ref = _reference_pass(ckpt, hf, layers, weights_precision, seqs,
                          choices=sound[2])
    _LAST.update(key=_key(ckpt, seqs), sound=sound, layers=layers,
                 weights_precision=weights_precision,
                 biased=[r["biased"] for r in ref],
                 k=hf["num_experts_per_tok"])
    return [r["logits"] for r in ref], {
        "latent": [r["latent"] for r in ref],
        "hc": [a for r in ref for a in r["hc"]],
        "route": [np.ones(r["biased"].shape[:2]) for r in ref]}


# ---- counts ----

def expert_params(hf: dict) -> int:
    """One routed expert of one layer: three projections."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def param_counts(hf: dict) -> dict:
    """Parameters of the next-token model by group: the mixers and the
    layers' norms, the hyper-connections (a layer's two and the read-out),
    the dense feed-forwards, the routers (and their bias), the routed
    experts, the shared experts, the final norm, the embedding, the head.
    The multi-token prediction module is not counted."""
    d = _dims(hf)
    D, V, H = d["D"], hf["vocab_size"], d["H"]
    mla = D * d["Rq"] + d["Rq"] + d["Rq"] * H * (d["nope"] + d["rope"]) \
        + D * (d["R"] + d["rope"]) + d["R"] \
        + d["R"] * H * (d["nope"] + d["vd"]) + H * d["vd"] * D
    hc = d["hc"] * d["n"] * D + 3 + d["hc"] if d["hc"] else 0
    head_hc = d["n"] * d["n"] * D + 1 + d["n"] if d["hc"] else 0
    return {"mixers": d["L"] * (mla + 2 * D),
            "hyper": d["L"] * 2 * hc + head_hc,
            "dense_ff": d["nd"] * 3 * D * d["F"],
            "routers": d["n_moe"] * (D * d["E"] + d["E"]),
            "experts": d["n_moe"] * d["E"] * expert_params(hf),
            "shared": d["n_moe"] * 3 * D * d["Fe"],
            "final_norm": D, "embed": V * D,
            "head": 0 if hf.get("tie_word_embeddings", False) else V * D}


def state_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """The latent row one token leaves in every layer's pool, as published
    (``[c | r]``; the pool pads it to a multiple of 128 columns)."""
    d = _dims(hf)
    return d["L"] * (d["R"] + d["rope"]) * itemsize


def moe_experts_least_bytes(hf: dict, experts_touched: float,
                            weight_itemsize: int = 2) -> float:
    """Least HBM bytes of the routed products in which ``experts_touched``
    (distinct experts a layer a step, summed over layers and steps) were
    touched: each one's three projections read once."""
    return experts_touched * expert_params(hf) * weight_itemsize


def mla_decode_least(hf: dict, ctx_rows: float, live_slot_calls: float,
                     itemsize: int = 2):
    """(least HBM bytes, least operations) of ``mla_paged_decode`` calls
    whose live slots held ``ctx_rows`` latent rows in all (summed over the
    calls: a call is one layer of one step) and numbered
    ``live_slot_calls``: each row read once as published (R + rope wide);
    the absorbed products, 2 per multiply-add: every head's score against a
    row over R + rope columns and its value sum over R, the slot's own token
    included."""
    d = _dims(hf)
    rows = ctx_rows + live_slot_calls
    return (ctx_rows * (d["R"] + d["rope"]) * itemsize,
            2.0 * rows * d["H"] * (2 * d["R"] + d["rope"]))


def _always_read(hf: dict) -> int:
    """The weights every step reads whatever it routes: all but the routed
    experts and the embedding table (the head among them)."""
    p = param_counts(hf)
    return p["mixers"] + p["hyper"] + p["dense_ff"] + p["routers"] \
        + p["shared"] + (p["head"] or p["embed"])


def decode_step_least_bytes(hf: dict, weight_itemsize: int,
                            live_tokens: float, batch: float,
                            state_itemsize: int = 2) -> float:
    """Least HBM bytes one decode step of ``batch`` sequences must move:
    every weight outside the routed experts once, one embedding row a
    sequence, every live latent row once, and in every expert layer the
    experts that ONE token's ``k`` choices touch (the least a step of any
    batch reads). A BOUND, as families/lfm2_moe.py's:
    ``moe_experts_roofline`` counts the experts a capture's steps did
    touch."""
    d = _dims(hf)
    return (_always_read(hf) + d["n_moe"] * d["k"] * expert_params(hf)) \
        * weight_itemsize + param_counts(hf)["final_norm"] * 2 \
        + batch * hf["hidden_size"] * weight_itemsize \
        + live_tokens * state_bytes_per_token(hf, state_itemsize)


def decode_step_least_flops(hf: dict, live_tokens: float,
                            batch: float) -> float:
    """2 per weight a sequence uses (its ``k`` experts a layer among them)
    and the absorbed attention over the live latent rows."""
    d = _dims(hf)
    used = _always_read(hf) + d["n_moe"] * d["k"] * expert_params(hf)
    return 2 * batch * used \
        + 2.0 * d["L"] * d["H"] * (2 * d["R"] + d["rope"]) * live_tokens


def decode_kernel_calls_per_step(hf: dict) -> int:
    """One ``mla_paged_decode`` call a layer a step."""
    return hf["num_hidden_layers"]
