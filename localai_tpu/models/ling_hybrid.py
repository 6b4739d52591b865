"""Ling-3.0-flash's language decoder (``model_type: ling_hybrid``): Kimi delta
attention (KDA) layers beside multi-head latent attention (MLA) layers, one
MLA layer closing every ``layer_group_size`` layers; the first
``first_k_dense_replace`` layers followed by a dense SwiGLU feed-forward,
every later one by a routed expert feed-forward with GROUP-LIMITED choice
(ops/moe.py) and a SHARED expert beside it. Of the router's ``num_experts``
this chip may hold a share (``expert_parallel`` in the config: strided over
``size`` chips, this one ``rank``); it then computes its own experts' part
of a layer's result and nothing stands in for the rest.

Up to four kinds of (mixer, feed-forward) layer in one stack - ``kda_dense``,
``kda_moe``, ``mla_moe`` (``mla_dense`` where a test's period is short) - and
two kinds of state in one slot:

  * a KDA layer (ops/kda.py): ``q, k, v = SiLU(conv4(W x))`` (causal
    depthwise, one tap set a channel), ``q = l2norm(q) K^-0.5``, ``k =
    l2norm(k)``; a log decay A KEY CHANNEL ``g = lower * sigmoid(exp(A_log_h)
    (W_f x + dt_bias))`` in ``[lower, 0]`` (``kda_lower_bound``), ``beta =
    sigmoid(W_b x)`` a head; ``y = W_o (RMSNorm_head(o) * sigmoid(W_g x))``.
    Per slot it keeps the state ``kda`` [H, K, V] float32 and the last three
    inputs of the convolution ``conv`` [3, 3HK] (time-major, as
    models/olmo_hybrid.py's);
  * an MLA layer (ops/mla.py): ``q = W_q x`` in heads of ``nope + rope``,
    RMS-normed per head (one weight of ``nope + rope``) and its rope part
    rotated; ``[c | r] = W_kva x``, ``c`` RMS-normed, ``r`` rotated; the
    cache row is ``[c | r]``; each head's output times ``sigmoid((W_gate
    x)_h)``. Prefill attends with per-head keys and values materialised
    through ``W_kvb``, decode with ``W_kvb`` absorbed into the query and the
    output: two forms over one pool of latent rows.

The cache is the paged pytree with the latent pool as its pages and two
state leaves on ``cache_k``; ``cache_v`` holds a pool of NO layers (an MLA
row is key and value at once):

    cache_k = {"pages": [L_mla, n_pages, page, 1, Wd], "ptab": [S, MP],
               "kda": [L_kda, S, H, K, V] f32, "conv": [L_kda, S, 3, 3HK]}
    cache_v = {"pages": [0, n_pages, page, 1, Wd], "ptab": [S, MP]}

with the hybrids' rules: a prefill segment that starts at position 0 starts
from zero state whatever the slot held, a continued one from the slot's; an
inactive slot's state is untouched by a decode step, and such a slot ROUTES
NOWHERE. engine/paging.py counts tokens and pages and is as it was.

Blocks are pre-norm: ``x += mix(norm(x))``, ``x += ff(norm(x))``; the head is
not tied. The layer scan runs over runs of same-kind layers
(hybrid_common.scan_layer_runs): the dense layers, then the periods.

What the engine may do with this family is ``CAPABILITIES``: paged rows and
packed prefill, no prefix reuse (a page of latent rows without the KDA state
at its boundary cannot be resumed from), and ``route_stats``. What the
published model has and this module does not build is refused by name in
``from_hf_config``: a vision tower's injection (the runner refuses the
projector), multi-token prediction heads, the clamped SwiGLU of
``expert_swiglu_limit_list`` on a held layer, a low-rank query or KDA gate.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.models import llama
from localai_tpu.models.hybrid_common import (new_tails, packed_conv,
                                              prefill_as_pack,
                                              scan_layer_runs, unembed)
from localai_tpu.models.llama import AttnTarget, _embed_rows, _mat, _mlp
from localai_tpu.ops import gated_delta, kda, kvcache, mla, moe
from localai_tpu.ops.norms import rms_norm
from localai_tpu.ops.rope import rotate_by_delta

CAPABILITIES = frozenset({"paged", "packed_prefill", "route_stats"})

_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144           # the dense layers' feed-forward
    moe_intermediate_size: int = 768        # one expert's
    shared_intermediate_size: int = 768     # the shared expert's
    num_layers: int = 42
    layer_group_size: int = 6               # the last of each group is MLA
    num_dense_layers: int = 2               # first_k_dense_replace
    num_heads: int = 32
    head_dim: int = 128                     # KDA's key and value width
    conv_kernel: int = 4                    # short_conv_kernel_size
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    use_qk_norm: bool = True
    rope_theta: float = 6e6
    num_experts: int = 512                  # the router's width
    held: Optional[Tuple[int, ...]] = None  # global ids held here; None: all
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    attn: Optional[AttnTarget] = None

    @property
    def mixers(self) -> Tuple[str, ...]:
        g = self.layer_group_size
        return tuple("mla" if (i + 1) % g == 0 else "kda"
                     for i in range(self.num_layers))

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """(mixer, feed-forward) a layer, as the layer scan names it."""
        return tuple(m + ("_dense" if i < self.num_dense_layers else "_moe")
                     for i, m in enumerate(self.mixers))

    @property
    def kda_layers(self) -> int:
        return self.mixers.count("kda")

    @property
    def mla_layers(self) -> int:
        return self.mixers.count("mla")

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def experts_held(self) -> int:
        return self.num_experts if self.held is None else len(self.held)

    @property
    def kda_channels(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A latent row as the pool holds it (ops/mla.py::pool_width)."""
        return mla.pool_width(self.kv_lora_rank, self.qk_rope_head_dim)

    # what models/llama.py's ``attn_target`` and the engine's reports read
    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def q_per_kv(self) -> int:
        return self.num_heads

    @property
    def head_dim_(self) -> int:
        return self.latent_width

    @staticmethod
    def from_hf_config(cfg: dict, dtype=jnp.bfloat16) -> "LingHybridConfig":
        L = cfg["num_hidden_layers"]
        nd = int(cfg.get("first_k_dense_replace", 0))

        def refuse(what):
            raise ValueError(f"ling_hybrid: {what} is not built")

        if cfg.get("q_lora_rank") is not None:
            refuse("a low-rank query projection (q_lora_rank)")
        if cfg.get("use_kda_lora") or not cfg.get("no_kda_lora", True):
            refuse("a low-rank KDA gate (use_kda_lora)")
        for flag in ("use_nGPT", "value_norm", "up_proj_norm",
                     "scale_router_input", "use_mla_nope"):
            if cfg.get(flag):
                refuse(f"{flag} true")
        if cfg.get("score_function", "sigmoid") != "sigmoid":
            refuse(f"score_function {cfg['score_function']!r} (only sigmoid)")
        if not cfg.get("kda_safe_gate", True):
            refuse("a KDA gate without its lower bound (kda_safe_gate false)")
        if not cfg.get("linear_silu", True):
            refuse("linear_silu false")
        if cfg.get("num_kv_heads_for_linear_attn", 0) not in (
                0, cfg["num_attention_heads"]):
            refuse("fewer key heads than heads in the KDA layers "
                   "(num_kv_heads_for_linear_attn)")
        if cfg.get("group_norm_size", 1) != 1:
            refuse("a KDA output norm over several heads (group_norm_size)")
        if cfg.get("gated_attention_proj_granularity_type",
                   "head_wise") != "head_wise":
            refuse("an MLA output gate that is not head_wise")
        if cfg.get("num_nextn_predict_layers") or cfg.get("mtp_num_layers"):
            refuse("multi-token prediction heads")
        if cfg.get("head_dim", 128) != cfg.get("v_head_dim", 128):
            refuse("KDA heads whose width differs from v_head_dim")
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            if any(cfg.get(key, ())[:L]):
                refuse(f"the clamped SwiGLU of {key} (nonzero for a layer "
                       f"among the first {L})")
        if nd >= L:
            refuse(f"first_k_dense_replace = {nd} with {L} layers (no "
                   "expert layer)")
        E = cfg["num_experts"]
        ep = cfg.get("expert_parallel")
        held = None
        if ep:
            # the file counts the experts held here; the router's width is
            # the deployment's
            if ep.get("placement", "strided") != "strided":
                refuse(f"expert placement {ep['placement']!r} (only strided)")
            total, size, rank = ep["num_experts_total"], ep["size"], ep["rank"]
            held = tuple(range(rank, total, size))
            if len(held) != E:
                raise ValueError(
                    f"ling_hybrid: num_experts = {E} is not rank {rank}'s "
                    f"strided share of {total} experts over {size} chips "
                    f"({len(held)})")
            E = total
        if E % cfg.get("n_group", 1):
            refuse(f"{E} experts in {cfg['n_group']} unequal groups")
        return LingHybridConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            shared_intermediate_size=cfg.get(
                "moe_shared_expert_intermediate_size",
                cfg["moe_intermediate_size"]),
            num_layers=L, layer_group_size=cfg["layer_group_size"],
            num_dense_layers=nd, num_heads=cfg["num_attention_heads"],
            head_dim=cfg.get("head_dim", 128),
            conv_kernel=cfg.get("short_conv_kernel_size", 4),
            kda_lower_bound=float(cfg.get("kda_lower_bound", -5)),
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            use_qk_norm=bool(cfg.get("use_qk_norm", False)),
            rope_theta=float(cfg.get("rope_theta", 1e4)),
            num_experts=E, held=held,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            n_group=cfg.get("n_group", 1), topk_group=cfg.get("topk_group", 1),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            use_expert_bias=bool(cfg.get("moe_router_enable_expert_bias",
                                         False)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            dtype=dtype)


def route_stats_shape(cfg: LingHybridConfig) -> Tuple[int, int]:
    """(expert layers L, experts HELD here E): ops/moe.py::route_stats a
    layer, ``[L, E + 2]`` laid flat (models/lfm2_moe.py has the same)."""
    return cfg.moe_layers, cfg.experts_held


def latent_cache_bytes(cache_k) -> int:
    """Device bytes of the latent page pool (the engine reports it beside
    ``recurrent_state_bytes``)."""
    p = cache_k["pages"]
    return int(p.size * p.dtype.itemsize)


def decode_attn_impl(cfg, ck) -> str:
    """What the engine reports a decode program's attention as (in place of
    models/llama.py's names: the MLA layers run none of its kernels)."""
    del ck
    return "pallas:mla_paged_decode" if llama._target(cfg).pallas \
        else "jnp:mla_gather_append"


def ragged_attn_impl(cfg, ck, N: int, continued: bool) -> str:
    del cfg, ck, N
    return "jnp:mla_ragged" if continued else "jnp:mla_ragged_fresh"


def _stats(choices, cfg):
    """choices [L_moe, rows, k] -> ``route_stats`` of each layer, laid end
    to end [L_moe * (E_held + 2)] float32."""
    return jax.vmap(lambda c: moe.route_stats(c, cfg.num_experts, cfg.held))(
        choices).reshape(-1)


def load_hf_params(model_dir: str, cfg: LingHybridConfig, dtype=jnp.bfloat16,
                   quantize: str = "", tracer=None) -> dict:
    """The adapter contract's loader (backend/runner.py); the leaves and
    the cast are engine/weights.py's."""
    from localai_tpu.engine import weights

    return weights.load_ling_hybrid_params(
        model_dir, cfg, dtype=dtype, quantize=quantize, tracer=tracer)


def init_cache(cfg: LingHybridConfig, num_slots: int, max_len: int,
               dtype=None, page_size: int = 0, num_pages: int = 0,
               state_dtype=jnp.float32):
    """(cache_k, cache_v) as in the module doc. ``state_dtype`` is float32
    unless a test or the benchmark's control asks what a lower precision
    would do."""
    if not page_size:
        raise ValueError("ling_hybrid serves on the paged KV layout only "
                         "(kv_layout=contiguous and lockstep are refused)")
    if kvcache.wants_quant(dtype or cfg.dtype):
        raise ValueError("ling_hybrid: an int8 latent cache is not built")
    Wd = cfg.latent_width
    ck = kvcache.init_paged((cfg.mla_layers, num_slots, max_len, 1, Wd),
                            dtype or cfg.dtype, page_size, num_pages)
    cv = kvcache.init_paged((0, num_slots, max_len, 1, Wd),
                            dtype or cfg.dtype, page_size, num_pages)
    ck["kda"] = jnp.zeros((cfg.kda_layers, num_slots, cfg.num_heads,
                           cfg.head_dim, cfg.head_dim), state_dtype)
    ck["conv"] = jnp.zeros((cfg.kda_layers, num_slots, cfg.conv_kernel - 1,
                            3 * cfg.kda_channels), cfg.dtype)
    return ck, cv


def init_params(cfg: LingHybridConfig, key: jax.Array, dtype=None) -> dict:
    """Random parameters in the stacked layout (tests)."""
    dtype = dtype or cfg.dtype
    D, F, Fe = cfg.hidden_size, cfg.intermediate_size, \
        cfg.moe_intermediate_size
    Fs = cfg.shared_intermediate_size
    L, Lk, La = cfg.num_layers, cfg.kda_layers, cfg.mla_layers
    Ld, Lm = cfg.num_dense_layers, cfg.moe_layers
    E, Eh, H = cfg.num_experts, cfg.experts_held, cfg.num_heads
    Ch, R = cfg.kda_channels, cfg.kv_lora_rank
    dq, nv = cfg.q_head_dim, cfg.qk_nope_head_dim + cfg.v_head_dim
    ks = iter(jax.random.split(key, 40))

    def init(shape, fan_in, dt=dtype, shift=0.0):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / np.sqrt(fan_in) + shift).astype(dt)

    f32 = jnp.float32
    params = {
        "embed": init((cfg.vocab_size, D), 1.0),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": init((D, cfg.vocab_size), D),
        "layers": {
            "mix_norm": jnp.ones((L, D), dtype),
            "ff_norm": jnp.ones((L, D), dtype),
            "kda_qkv": init((Lk, D, 3 * Ch), D),
            "kda_conv": init((Lk, cfg.conv_kernel, 3 * Ch), cfg.conv_kernel),
            "kda_f": init((Lk, D, Ch), D),
            "kda_dt_bias": init((Lk, Ch), 1.0, f32, -2.0),
            "kda_A_log": init((Lk, H), 10.0, f32),
            "kda_b": init((Lk, D, H), D), "kda_g": init((Lk, D, Ch), D),
            "kda_o_norm": jnp.ones((Lk, cfg.head_dim), dtype),
            "kda_o": init((Lk, Ch, D), Ch),
            "mla_q": init((La, D, H * dq), D),
            "mla_q_norm": jnp.ones((La, dq), dtype),
            "mla_kva": init((La, D, R + cfg.qk_rope_head_dim), D),
            "mla_kv_norm": jnp.ones((La, R), dtype),
            "mla_kvb": init((La, R, H * nv), R),
            "mla_gate": init((La, D, H), D),
            "mla_o": init((La, H * cfg.v_head_dim, D), H * cfg.v_head_dim),
            "w_gate": init((Ld, D, F), D), "w_up": init((Ld, D, F), D),
            "w_down": init((Ld, F, D), F),
            "router": init((Lm, D, E), D, f32),
            "expert_bias": init((Lm, E), 2500.0, f32),
            "w1": init((Lm, Eh, D, Fe), D), "w3": init((Lm, Eh, D, Fe), D),
            "w2": init((Lm, Eh, Fe, D), Fe),
            "sh_w1": init((Lm, D, Fs), D), "sh_w3": init((Lm, D, Fs), D),
            "sh_w2": init((Lm, Fs, D), Fs),
        },
    }
    if cfg.tie_word_embeddings:
        del params["lm_head"]
    return params


_NORMS = ("mix_norm", "ff_norm")
_KDA = ("kda_qkv", "kda_conv", "kda_f", "kda_dt_bias", "kda_A_log", "kda_b",
        "kda_g", "kda_o_norm", "kda_o")
_MLA = ("mla_q", "mla_q_norm", "mla_kva", "mla_kv_norm", "mla_kvb",
        "mla_gate", "mla_o")
_DENSE = ("w_gate", "w_up", "w_down")
_ROUTED = ("router", "expert_bias", "sh_w1", "sh_w3", "sh_w2")


def _layer(layers: dict, names, i) -> dict:
    """Layer ``i`` (traced) of the leaves ``names``, each stacked over the
    layers that hold one: ONE dynamic index a leaf (a {q, s} int8 leaf is
    indexed leaf by leaf)."""
    def one(a):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    return {k: jax.tree.map(one, layers[k]) for k in names}


# ---- the KDA mixer ----

def _kda_proj(h, w, cfg):
    """The projections of a KDA layer from the normed h [N, D]: the
    convolution's input (q | k | v channels), the output gate, the log decay
    a key channel and the write strength a head.
    -> pre [N, 3HK], gate [N, H, V], g [N, H, K] f32, beta [N, H] f32."""
    dt, f32 = h.dtype, jnp.float32
    H, K = cfg.num_heads, cfg.head_dim
    pre = h @ _mat(w["kda_qkv"], dt)
    gate = (h @ _mat(w["kda_g"], dt)).reshape(-1, H, K)
    f = (h @ _mat(w["kda_f"], dt)).astype(f32) + w["kda_dt_bias"].astype(f32)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(w["kda_A_log"].astype(f32))[None, :, None]
        * f.reshape(-1, H, K))
    beta = jax.nn.sigmoid((h @ _mat(w["kda_b"], dt)).astype(f32))
    return pre, gate, g, beta


def _kda_heads(act, cfg):
    """Convolved, SiLU'd channels [N, 3HK] -> q [N, H, K] (normalised,
    scaled), k (normalised), v, float32."""
    H, K = cfg.num_heads, cfg.head_dim
    n, ch = act.shape[0], cfg.kda_channels
    q = gated_delta.l2norm(act[:, :ch].reshape(n, H, K)) * K ** -0.5
    k = gated_delta.l2norm(act[:, ch:2 * ch].reshape(n, H, K))
    return q, k, act[:, 2 * ch:].reshape(n, H, K).astype(jnp.float32)


def _kda_out(o, gate, w, cfg):
    """W_o [RMSNorm_head(o) * sigmoid(gate)], in the model's dtype."""
    y = rms_norm(o, w["kda_o_norm"], cfg.rms_norm_eps) \
        * jax.nn.sigmoid(gate.astype(jnp.float32))
    return y.reshape(o.shape[0], -1).astype(cfg.dtype) \
        @ _mat(w["kda_o"], cfg.dtype)


def kda_decode(cfg, state, li, q, k, v, g, beta, active):
    """The one-token update on the stacked state: the Pallas kernel where
    ``cfg.attn`` says kernels run (a TPU, no mesh), jax.numpy elsewhere."""
    if llama._target(cfg).pallas and state.dtype == jnp.float32:
        from localai_tpu.ops.pallas.kda_decode import kda_decode_pallas

        return kda_decode_pallas(state, li, q, k, v, g, beta, active)
    return kda.kda_decode(state, li, q, k, v, g, beta, active)


# ---- the MLA mixer ----

def _mla_proj(h, w, cfg, sin, cos):
    """From the normed h [N, D] and the rotary terms [N, rope]: q_nope
    [N, H, nope], q_rope [N, H, rope] (normed per head, rotated), c [N, R]
    (normed), r [N, rope] (rotated), the heads' output gate [N, H] f32."""
    dt, n = h.dtype, h.shape[0]
    H, nope, R = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = (h @ _mat(w["mla_q"], dt)).reshape(n, H, cfg.q_head_dim)
    if cfg.use_qk_norm:
        q = rms_norm(q, w["mla_q_norm"], cfg.rms_norm_eps)
    q_rope = rotate_by_delta(q[..., nope:], sin[:, None], cos[:, None])
    kva = h @ _mat(w["mla_kva"], dt)
    c = rms_norm(kva[:, :R], w["mla_kv_norm"], cfg.rms_norm_eps)
    r = rotate_by_delta(kva[:, R:], sin, cos)
    gate = jax.nn.sigmoid((h @ _mat(w["mla_gate"], dt)).astype(jnp.float32))
    return q[..., :nope], q_rope, c, r, gate


def _kvb(w, cfg):
    """``W_kvb`` [R, H (nope + v)] -> its key part [R, H, nope] and its
    value part [R, H, v]."""
    kvb = _mat(w["mla_kvb"], cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.num_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    return kvb[..., :cfg.qk_nope_head_dim], kvb[..., cfg.qk_nope_head_dim:]


def _mla_out(o, gate, w, cfg):
    """o [N, H, v] float32, gate [N, H] -> W_o of the gated heads."""
    y = (o.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
    return y.reshape(o.shape[0], -1) @ _mat(w["mla_o"], cfg.dtype)


def _rope_terms(cfg, positions):
    return mla.rope_terms(positions, cfg.qk_rope_head_dim, cfg.rope_theta)


# ---- the feed-forwards ----

def _dense_ff(x, e, w, cfg):
    with _scope("layer/mlp"):
        h = rms_norm(x, e["ff_norm"], cfg.rms_norm_eps)
        return x + _mlp(h[None], w)[0]


def _moe_ff(x, e, w, layers, mi, cfg, live):
    """x + this chip's part of the routed expert feed-forward of norm(x) +
    the shared expert, expert layer ``mi``. x [N, D]; ``live`` [N]: the rows
    that route. -> (x, experts [N, k]: global ids)."""
    h = rms_norm(x, e["ff_norm"], cfg.rms_norm_eps)
    with _scope("layer/mlp/router"):
        experts, weights = moe.route(
            h, w["router"],
            w["expert_bias"] if cfg.use_expert_bias else None,
            cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, active=live,
            n_group=cfg.n_group, topk_group=cfg.topk_group, eps=1e-20)
    with _scope("layer/mlp/experts"):
        y = moe.experts_ffn(h, experts, weights, layers["w1"], layers["w3"],
                            layers["w2"], mi,
                            pallas=llama._target(cfg).pallas,
                            held=cfg.held, n_experts=cfg.num_experts)
    with _scope("layer/mlp/shared"):
        y = y + moe.shared_ffn(h, _mat(w["sh_w1"], h.dtype),
                               _mat(w["sh_w3"], h.dtype),
                               _mat(w["sh_w2"], h.dtype))
    return x + y, experts


def _head(x, params, cfg):
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with _scope("lm_head"):
        return unembed(x, params, cfg)


def _embed(params, tokens, cfg):
    with _scope("embed"):
        return _embed_rows(params["embed"], tokens, cfg.dtype)


def _run_layers(cfg, params, carry, kda_op, mla_op, live):
    """The layer stack over ``carry`` = (x, ck, choices): ``kda_op(x, w, e,
    ck, ki)`` and ``mla_op(x, w, e, ck, ai)`` are the two mixers (decode or
    packed), each -> its residual update and the cache; the feed-forward
    follows by the layer's kind."""
    layers = params["layers"]
    nd = cfg.num_dense_layers

    def ff(x, e, i, choices, dense):
        if dense:
            return _dense_ff(x, e, _layer(layers, _DENSE, i), cfg), choices
        x, experts = _moe_ff(x, e, _layer(layers, _ROUTED, i - nd), layers,
                             i - nd, cfg, live)
        return x, jax.lax.dynamic_update_index_in_dim(choices, experts,
                                                      i - nd, 0)

    def layer_fn(mixer, dense):
        names, op = (_KDA, kda_op) if mixer == "kda" else (_MLA, mla_op)

        def fn(carry, ki, i):
            x, ck, choices = carry
            e = _layer(layers, _NORMS, i)
            x, ck = op(x, _layer(layers, names, ki), e, ck, ki)
            x, choices = ff(x, e, i, choices, dense)
            return x, ck, choices
        return fn

    # ``ki`` counts a layer among those of its (mixer, feed-forward) kind;
    # the leaves are stacked by mixer, so a kind's count starts where the
    # mixer's earlier layers of the other feed-forward end
    kinds = cfg.layer_kinds
    fns = {}
    for kind in set(kinds):
        mixer, dense = kind.split("_")[0], kind.endswith("_dense")
        before = sum(1 for k in kinds[:nd] if k.startswith(mixer)) \
            if not dense else 0

        def fn(carry, ki, i, f=layer_fn(mixer, dense), b=before):
            return f(carry, ki + b, i)
        fns[kind] = fn
    return scan_layer_runs(kinds, carry, fns, lead=nd)


def _no_choices(cfg, rows):
    return jnp.full((cfg.moe_layers, rows, cfg.num_experts_per_tok),
                    cfg.num_experts, jnp.int32)


def decode_step(params, cfg: LingHybridConfig, tokens, lengths, active,
                cache_k, cache_v):
    """One decode step for all slots. tokens [S]; ``lengths`` the position
    each slot's new latent row is written at (C for an inactive slot: the
    write drops) and its rotary position; ``active`` [S] gates the states
    and the routing.
    -> (logits [S, V], cache_k, cache_v, choices [L_moe, S, k])."""
    f32 = jnp.float32
    S = tokens.shape[0]
    x = _embed(params, tokens, cfg)                              # [S, D]
    C = kvcache.shape(cache_k)[2]
    sin, cos = _rope_terms(cfg, lengths)
    read = jnp.where(lengths >= C, 0, lengths)   # an idle slot reads nothing
    slot = jnp.arange(S, dtype=jnp.int32)[:, None]
    pallas = llama._target(cfg).pallas

    def kda_op(x, w, e, ck, ki):
        h = rms_norm(x, e["mix_norm"], cfg.rms_norm_eps)
        with _scope("layer/attn_proj/linear"):
            pre, gate, g, beta = _kda_proj(h, w, cfg)
        with _scope("layer/linear_attn"):
            old = jax.lax.dynamic_index_in_dim(ck["conv"], ki, 0, False)
            win = jnp.concatenate([old, pre[:, None]], axis=1)
            act = jax.nn.silu(jnp.sum(
                win.astype(f32) * w["kda_conv"].astype(f32)[None], axis=1))
            new = jnp.where(active[:, None, None], win[:, 1:], old)
            ck = dict(ck, conv=jax.lax.dynamic_update_index_in_dim(
                ck["conv"], new, ki, 0))
            q, k, v = _kda_heads(act, cfg)
            o, state = kda_decode(cfg, ck["kda"], ki, q, k, v, g, beta,
                                  active)
            ck = dict(ck, kda=state)
        with _scope("layer/attn_proj/linear"):
            return x + _kda_out(o, gate, w, cfg), ck

    def mla_op(x, w, e, ck, ai):
        h = rms_norm(x, e["mix_norm"], cfg.rms_norm_eps)
        with _scope("layer/attn_proj"):
            q_nope, q_rope, c, r, gate = _mla_proj(h, w, cfg, sin, cos)
            w_k, w_v = _kvb(w, cfg)
            row = mla.latent_rows(c, r, cfg.latent_width)    # [S, 1, Wd]
            q_abs = mla.absorb_query(q_nope, q_rope, w_k, cfg.latent_width,
                                     cfg.q_head_dim ** -0.5)
        with _scope("layer/attn"):
            o_lat = mla.decode_attention(
                q_abs, row, ck, ai, read, cfg.kv_lora_rank,
                # the kernel copies float pages of 2 or 4 bytes
                pallas=pallas and ck["pages"].dtype.itemsize >= 2)
            ck = kvcache.scatter_prefill(ck, ai, slot, lengths[:, None],
                                         row[:, None])
        with _scope("layer/attn_proj"):
            return x + _mla_out(mla.expand_values(o_lat, w_v), gate, w,
                                cfg), ck

    x, cache_k, choices = _run_layers(
        cfg, params, (x, cache_k, _no_choices(cfg, S)), kda_op, mla_op,
        active)
    return _head(x, params, cfg), cache_k, cache_v, choices


def engine_decode(params, cfg, tokens, lengths, active, cache_k, cache_v,
                  pos_offset=None, route_stats: bool = False):
    """Engine adapter (the contract of models/llama.py and the hybrids): an
    inactive slot writes no row (its position is forced to C, which the
    scatter drops), keeps its states and routes nowhere. ``pos_offset``
    belongs to self-extend, which this family does not declare. With
    ``route_stats`` a fourth result: the step's route stats."""
    del pos_offset
    C = kvcache.shape(cache_k)[2]
    logits, ck, cv, choices = decode_step(
        params, cfg, tokens, jnp.where(active, lengths, C), active, cache_k,
        cache_v)
    if route_stats:
        return logits, ck, cv, _stats(choices, cfg)
    return logits, ck, cv


def ragged_prefill_routed(params, cfg: LingHybridConfig, tokens, positions,
                          seg_of, seg_slots, seg_start, seg_off, seg_len,
                          cache_k, cache_v, continued: bool = False):
    """Packed prefill on models/llama.py::ragged_prefill's contract (its
    docstring has the arguments). An MLA layer attends with materialised
    heads over the pack and, continued, the slot's committed latent rows,
    and writes the pack's rows; a KDA layer runs the chunked rule over the
    pack's segments, each from zero state when it starts at position 0 and
    from its slot's otherwise, and leaves its final state and convolution
    tail in the slot; the expert layers run the grouped form over the
    pack's real tokens (a pad token routes nowhere). Pad segments (slot
    sentinel) write nothing.
    -> (logits [B, V], cache_k, cache_v, choices [L_moe, N, k])."""
    f32 = jnp.float32
    N = tokens.shape[0]
    B = seg_slots.shape[0]
    S = cache_k["kda"].shape[1]
    W1 = cfg.conv_kernel - 1
    x = _embed(params, tokens, cfg)                              # [N, D]
    seg = jnp.minimum(seg_of, B - 1)
    slot_of = jnp.take(seg_slots, seg)
    real = seg_of < B
    j = jnp.where(real, jnp.arange(N, dtype=jnp.int32)
                  - jnp.take(seg_off, seg), 0)               # index in segment
    plan = gated_delta.chunk_plan(seg_off, seg_len, N, chunk=kda.CHUNK)
    slots_c = jnp.minimum(seg_slots, S - 1)
    fresh = seg_start == 0
    C = kvcache.shape(cache_k)[2]
    sin, cos = _rope_terms(cfg, jnp.where(positions < C, positions, 0))

    def kda_op(x, w, e, ck, ki):
        h = rms_norm(x, e["mix_norm"], cfg.rms_norm_eps)
        with _scope("layer/attn_proj/linear"):
            pre, gate, g, beta = _kda_proj(h, w, cfg)
        with _scope("layer/linear_attn"):
            if continued:
                conv0 = jnp.where(fresh[:, None, None], 0, jnp.take(
                    jax.lax.dynamic_index_in_dim(ck["conv"], ki, 0, False),
                    slots_c, axis=0))
                s0 = jnp.where(fresh[:, None, None, None], 0, jnp.take(
                    jax.lax.dynamic_index_in_dim(ck["kda"], ki, 0, False),
                    slots_c, axis=0))
            else:
                conv0 = jnp.zeros((B, W1, 3 * cfg.kda_channels), pre.dtype)
                s0 = jnp.zeros((B,) + ck["kda"].shape[2:], f32)
            acc = packed_conv(pre, conv0, w["kda_conv"].astype(f32), seg, j)
            q, k, v = _kda_heads(jax.nn.silu(acc), cfg)
            with _scope("kda_chunk"):
                o, finals = kda.kda_chunk(q, k, v, g, beta, s0, plan)
            tail = new_tails(pre, conv0, seg_off, seg_len)   # [B, 3, 3HK]
            ck = dict(ck,
                      conv=ck["conv"].at[ki, seg_slots].set(
                          tail.astype(ck["conv"].dtype), mode="drop"),
                      kda=ck["kda"].at[ki, seg_slots].set(
                          finals.astype(ck["kda"].dtype), mode="drop"))
        with _scope("layer/attn_proj/linear"):
            return x + _kda_out(o, gate, w, cfg), ck

    def mla_op(x, w, e, ck, ai):
        h = rms_norm(x, e["mix_norm"], cfg.rms_norm_eps)
        H, R = cfg.num_heads, cfg.kv_lora_rank
        with _scope("layer/attn_proj"):
            q_nope, q_rope, c, r, gate = _mla_proj(h, w, cfg, sin, cos)
            w_k, w_v = _kvb(w, cfg)
            rows = mla.latent_rows(c, r, cfg.latent_width)   # [N, 1, Wd]

            def expand(lat):         # [n, Wd] -> k [n, H, dq], v [n, H, v]
                lat = lat.astype(w_k.dtype)  # (a pool held lower: a control)
                c_, r_ = lat[:, :R], lat[:, R:R + cfg.qk_rope_head_dim]
                k_nope = jnp.einsum("nr,rhd->nhd", c_, w_k)
                r_ = jnp.broadcast_to(r_[:, None], (lat.shape[0], H,
                                                    r_.shape[-1]))
                return (jnp.concatenate([k_nope, r_.astype(k_nope.dtype)],
                                        -1),
                        jnp.einsum("nr,rhd->nhd", c_, w_v))

            # the pack's own keys and values from the rows as the pool
            # will hold them
            k, v = expand(rows[:, 0].astype(ck["pages"].dtype))
            q = jnp.concatenate([q_nope, q_rope], -1)
        with _scope("layer/attn"):
            o = mla.prefill_attention(
                q, k.astype(q.dtype), v.astype(q.dtype), seg_of, seg_slots,
                seg_start, ck, ai, expand, cfg.q_head_dim ** -0.5,
                continued=continued)
            ck = kvcache.scatter_ragged(ck, ai, slot_of, positions, rows)
        with _scope("layer/attn_proj"):
            return x + _mla_out(o, gate, w, cfg), ck

    x, cache_k, choices = _run_layers(
        cfg, params, (x, cache_k, _no_choices(cfg, N)), kda_op, mla_op, real)
    last = jnp.maximum(seg_off + seg_len - 1, 0)
    return (_head(jnp.take(x, last, axis=0), params, cfg), cache_k, cache_v,
            choices)


def ragged_prefill(params, cfg, tokens, positions, seg_of, seg_slots,
                   seg_start, seg_off, seg_len, cache_k, cache_v,
                   continued: bool = False, rope_positions=None,
                   comm_overlap: bool = False, route_stats: bool = False):
    """Engine adapter of ``ragged_prefill_routed``. ``comm_overlap`` is for
    a mesh, which this family refuses; with ``route_stats`` a fourth
    result: the pack's route stats."""
    assert rope_positions is None, "self-extend is not declared"
    del comm_overlap
    logits, ck, cv, choices = ragged_prefill_routed(
        params, cfg, tokens, positions, seg_of, seg_slots, seg_start,
        seg_off, seg_len, cache_k, cache_v, continued=continued)
    if route_stats:
        return logits, ck, cv, _stats(choices, cfg)
    return logits, ck, cv


def prefill(params, cfg, tokens, seq_lens, cache_k, cache_v, slot_ids,
            start_pos, continued=False, mm_pos=None, mm_vec=None,
            return_all_logits=False, positions=None):
    """The per-slot prefill of the adapter contract, as one pack (the
    engine's packed path is what serves; this is for callers that hold a
    [B, T] batch)."""
    assert mm_pos is None and positions is None and not return_all_logits, \
        "multimodal, explicit positions and all-logits are not declared"
    return prefill_as_pack(ragged_prefill, params, cfg, tokens, seq_lens,
                           cache_k, cache_v, slot_ids, start_pos, continued)
