"""Xing4.0's decoder (``model_type: xing4_0``): multi-head latent attention
(MLA) on EVERY layer, under manifold-constrained hyper-connections
(ops/hyper.py: ``hc_mult`` residual streams, a learned Sinkhorn-normalised mix
around every sublayer); the first ``first_k_dense_replace`` layers followed
by a dense SwiGLU feed-forward, every later one by a routed expert
feed-forward (ops/moe.py: sigmoid scores + a selection bias, the k largest
over ONE group, all experts held) beside a SHARED expert; an untied head
after the streams' read-out.

  * the MLA mixer (ops/mla.py) is DeepSeek-V3's: the query through its own
    low-rank pair, ``q = W_qb RMSNorm(W_qa u)`` in heads of ``nope + rope``;
    ``[c | r] = W_kva u``, ``c`` RMS-normed; the rope parts rotated with
    YaRN's frequencies over the ``qk_rope_head_dim`` columns
    (ops/rope.py::yarn_inv_freq) and the scores scaled by ``(nope +
    rope)^-0.5 yarn_mscale(factor, mscale_all_dim)^2``; no per-head norm, no
    output gate. The cache row is ``[c | r]``. Prefill attends with per-head
    keys and values materialised through ``W_kvb``, decode with ``W_kvb``
    absorbed into the query and the output: two forms over one pool.
  * a sublayer ``F`` reads ``u = sum_i pre[i] X[i]`` through its own input
    norm and writes ``X'[i] = post[i] F(norm(u)) + sum_j M[i, j] X[j]``
    (ops/hyper.py has the equations). With ``hc_mult`` 1 there are no such
    weights and the block is the plain pre-norm residual.

The cache is the paged pytree with the latent pool as its pages and NOTHING
ELSE: ``cache_v`` holds a pool of no layers (an MLA row is key and value at
once), and no leaf holds a per-slot state:

    cache_k = {"pages": [L, n_pages, page, 1, Wd], "ptab": [S, MP]}
    cache_v = {"pages": [0, n_pages, page, 1, Wd], "ptab": [S, MP]}

so a slot IS its pages, and a slot can resume from another's: this family
declares ``prefix_reuse`` (the prefix cache, the copy-on-write share, fork
dedup and prompt-cache files all go through ops/kvcache.py's page helpers,
which take a plane of no layers as it comes). It does not declare
``kv_offload``: engine/kv_offload.py, services/kv_wire.py and
services/kv_audit.py size and check a page as a K and a V plane, and half a
row offloaded is worse than none; the runner refuses the option by name.

What the published model has and this module does not build is refused by
name in ``from_hf_config``: a rope scaling other than YaRN, group-limited
routing, a softmax router, more than one shared expert's width, attention
biases. The multi-token prediction module (``num_nextn_predict_layers``) is
NOT a refusal: it sits after the last layer, drafts and serves no token of
the next-token model; the loader skips its tensors by name and counts them,
and the family declares no speculation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.models import llama
from localai_tpu.models.hybrid_common import (prefill_as_pack,
                                              scan_layer_runs, unembed)
from localai_tpu.models.llama import AttnTarget, _embed_rows, _mat, _mlp
from localai_tpu.ops import hyper, kvcache, mla, moe
from localai_tpu.ops.norms import rms_norm
from localai_tpu.ops.rope import rotate_by_delta, yarn_inv_freq, yarn_mscale

CAPABILITIES = frozenset({"paged", "packed_prefill", "prefix_reuse",
                          "route_stats"})

_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216           # the dense layers' feed-forward
    moe_intermediate_size: int = 1024       # one expert's, and the shared one's
    num_layers: int = 40
    num_dense_layers: int = 2               # first_k_dense_replace
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    rope_scaling_factor: float = 1.0        # 1: no YaRN
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    attn: Optional[AttnTarget] = None

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        nd = self.num_dense_layers
        return ("dense",) * nd + ("moe",) * (self.num_layers - nd)

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A latent row as the pool holds it (ops/mla.py::pool_width)."""
        return mla.pool_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def score_scale(self) -> float:
        """``(nope + rope)^-0.5`` times YaRN's ``mscale_all_dim``
        temperature squared (1 where that is 0 or nothing is scaled)."""
        m = yarn_mscale(self.rope_scaling_factor, self.rope_mscale_all_dim) \
            if self.rope_mscale_all_dim else 1.0
        return self.q_head_dim ** -0.5 * m * m

    @property
    def rope_inv_freq(self) -> np.ndarray:
        """YaRN's frequencies (at a factor of 1, ``rope_theta``'s own)."""
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                             self.rope_scaling_factor,
                             self.rope_original_max_position,
                             self.rope_beta_fast, self.rope_beta_slow)

    @property
    def rope_magnitude(self) -> float:
        """What cos and sin are scaled by: ``mscale / mscale_all_dim``'s
        temperatures."""
        f = self.rope_scaling_factor
        return yarn_mscale(f, self.rope_mscale) \
            / yarn_mscale(f, self.rope_mscale_all_dim)

    @property
    def hc_params(self) -> hyper.HyperConfig:
        return hyper.HyperConfig(self.rms_norm_eps, self.hc_eps,
                                 self.hc_sinkhorn_iters, tuple(self.hc_clamp))

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
    def from_hf_config(cfg: dict, dtype=jnp.bfloat16) -> "Xing4Config":
        L = cfg["num_hidden_layers"]
        nd = int(cfg.get("first_k_dense_replace", 0))

        def refuse(what):
            raise ValueError(f"xing4_0: {what} is not built")

        rs = cfg.get("rope_scaling") or {}
        kind = rs.get("type", rs.get("rope_type", "yarn" if rs else None))
        if rs and kind != "yarn":
            refuse(f"rope_scaling.type {kind!r} (only yarn)")
        if cfg.get("n_group", 1) > 1 or cfg.get("topk_group", 1) > 1:
            refuse("group-limited routing (n_group > 1)")
        if cfg.get("scoring_func", "sigmoid") != "sigmoid":
            refuse(f"scoring_func {cfg['scoring_func']!r} (only sigmoid)")
        if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
            refuse(f"topk_method {cfg['topk_method']!r} (only noaux_tc)")
        if cfg.get("n_shared_experts", 1) != 1:
            refuse(f"{cfg['n_shared_experts']} shared experts (only one)")
        if cfg.get("moe_layer_freq", 1) != 1:
            refuse("an expert layer every moe_layer_freq > 1 layers")
        if cfg.get("attention_bias"):
            refuse("attention_bias true")
        if cfg.get("hidden_act", "silu") != "silu":
            refuse(f"hidden_act {cfg['hidden_act']!r} (only silu)")
        if cfg.get("q_lora_rank") is None:
            refuse("a full-rank query projection (q_lora_rank null)")
        if cfg.get("ep_size", 1) != 1:
            refuse("a share of the experts (ep_size > 1)")
        if cfg.get("hc_mult", 1) < 1:
            refuse(f"hc_mult {cfg['hc_mult']}")
        if nd > L:
            refuse(f"first_k_dense_replace = {nd} with {L} layers")
        return Xing4Config(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_layers=L, num_dense_layers=nd,
            num_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            rope_theta=float(cfg.get("rope_theta", 1e4)),
            rope_scaling_factor=float(rs.get("factor", 1.0)),
            rope_original_max_position=int(rs.get(
                "original_max_position_embeddings", 4096)),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1.0)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
            num_experts=cfg["n_routed_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            hc_mult=int(cfg.get("hc_mult", 1)),
            hc_sinkhorn_iters=int(cfg.get("hc_sinkhorn_iters", 20)),
            hc_eps=float(cfg.get("hc_eps", 1e-6)),
            hc_clamp=(float(cfg.get("mhc_h_res_clamp_min", -30)),
                      float(cfg.get("mhc_h_res_clamp_max", 30))),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            dtype=dtype)


def route_stats_shape(cfg: Xing4Config) -> Tuple[int, int]:
    """(expert layers L, experts E): ops/moe.py::route_stats a layer,
    ``[L, E + 2]`` laid flat (models/lfm2_moe.py has the same)."""
    return cfg.moe_layers, cfg.num_experts


def latent_cache_bytes(cache_k) -> int:
    """Device bytes of the latent page pool (the engine reports it, and
    gives its decode bursts' spans the latent rows their slots held)."""
    p = cache_k["pages"]
    return int(p.size * p.dtype.itemsize)


def decode_attn_impl(cfg, ck) -> str:
    """What the engine reports a decode program's attention as."""
    del ck
    return "pallas:mla_paged_decode" if llama._target(cfg).pallas \
        else "jnp:mla_gather_append"


def ragged_attn_impl(cfg, ck, N: int, continued: bool) -> str:
    del cfg, ck, N
    return "jnp:mla_ragged" if continued else "jnp:mla_ragged_fresh"


def _stats(choices, cfg):
    """choices [L_moe, rows, k] -> ``route_stats`` of each layer, laid end
    to end [L_moe * (E + 2)] float32."""
    if not cfg.moe_layers:
        return jnp.zeros((0,), jnp.float32)
    return jax.vmap(lambda c: moe.route_stats(c, cfg.num_experts))(
        choices).reshape(-1)


def load_hf_params(model_dir: str, cfg: Xing4Config, dtype=jnp.bfloat16,
                   quantize: str = "", tracer=None) -> dict:
    """The adapter contract's loader (backend/runner.py); the leaves and
    the cast are engine/weights.py's."""
    from localai_tpu.engine import weights

    return weights.load_xing4_params(
        model_dir, cfg, dtype=dtype, quantize=quantize, tracer=tracer)


def init_cache(cfg: Xing4Config, num_slots: int, max_len: int, dtype=None,
               page_size: int = 0, num_pages: int = 0):
    """(cache_k, cache_v) as in the module doc."""
    if not page_size:
        raise ValueError("xing4_0 serves on the paged KV layout only "
                         "(kv_layout=contiguous and lockstep are refused)")
    if kvcache.wants_quant(dtype or cfg.dtype):
        raise ValueError("xing4_0: an int8 latent cache is not built")
    Wd = cfg.latent_width
    return tuple(kvcache.init_paged((layers, num_slots, max_len, 1, Wd),
                                    dtype or cfg.dtype, page_size, num_pages)
                 for layers in (cfg.num_layers, 0))


def hc_width(cfg: Xing4Config) -> int:
    """Outputs of a sublayer's hyper-connection: n n + 2 n."""
    return cfg.hc_mult * (cfg.hc_mult + 2)


def init_params(cfg: Xing4Config, key: jax.Array, dtype=None) -> dict:
    """Random parameters in the stacked layout (tests). The hyper-
    connections' scales and biases are drawn as the benchmark's maker draws
    them (benchmark/families/xing4.py): scales near 1, biases N(0, 0.5)."""
    dtype = dtype or cfg.dtype
    D, F, Fe = cfg.hidden_size, cfg.intermediate_size, \
        cfg.moe_intermediate_size
    L, Ld, Lm = cfg.num_layers, cfg.num_dense_layers, cfg.moe_layers
    E, H, R, n = cfg.num_experts, cfg.num_heads, cfg.kv_lora_rank, cfg.hc_mult
    dq, nv = cfg.q_head_dim, cfg.qk_nope_head_dim + cfg.v_head_dim
    ks = iter(jax.random.split(key, 40))

    def init(shape, fan_in, dt=dtype, shift=0.0):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / np.sqrt(fan_in) + shift).astype(dt)

    f32 = jnp.float32
    layers = {
        "mix_norm": jnp.ones((L, D), dtype),
        "ff_norm": jnp.ones((L, D), dtype),
        "mla_qa": init((L, D, cfg.q_lora_rank), D),
        "mla_q_norm": jnp.ones((L, cfg.q_lora_rank), dtype),
        "mla_qb": init((L, cfg.q_lora_rank, H * dq), cfg.q_lora_rank),
        "mla_kva": init((L, D, R + cfg.qk_rope_head_dim), D),
        "mla_kv_norm": jnp.ones((L, R), dtype),
        "mla_kvb": init((L, R, H * nv), R),
        "mla_o": init((L, H * cfg.v_head_dim, D), H * cfg.v_head_dim),
        "w_gate": init((Ld, D, F), D), "w_up": init((Ld, D, F), D),
        "w_down": init((Ld, F, D), F),
        "router": init((Lm, D, E), D, f32),
        "expert_bias": init((Lm, E), 2500.0, f32),
        "w1": init((Lm, E, D, Fe), D), "w3": init((Lm, E, D, Fe), D),
        "w2": init((Lm, E, Fe, D), Fe),
        "sh_w1": init((Lm, D, Fe), D), "sh_w3": init((Lm, D, Fe), D),
        "sh_w2": init((Lm, Fe, D), Fe),
    }
    params = {"embed": init((cfg.vocab_size, D), 1.0),
              "final_norm": jnp.ones((D,), dtype),
              "lm_head": init((D, cfg.vocab_size), D), "layers": layers}
    if n > 1:
        layers["hc_w"] = init((L, 2, n * D, hc_width(cfg)), n * D)
        layers["hc_s"] = init((L, 2, 3), 400.0, f32, 1.0)
        layers["hc_b"] = init((L, 2, hc_width(cfg)), 4.0, f32)
        params["hc_head_w"] = init((n * D, n), n * D)
        params["hc_head_s"] = init((1,), 400.0, f32, 1.0)
        params["hc_head_b"] = init((n,), 4.0, f32)
    if cfg.tie_word_embeddings:
        del params["lm_head"]
    return params


_NORMS = ("mix_norm", "ff_norm")
_HC = ("hc_w", "hc_s", "hc_b")
_MLA = ("mla_qa", "mla_q_norm", "mla_qb", "mla_kva", "mla_kv_norm",
        "mla_kvb", "mla_o")
_DENSE = ("w_gate", "w_up", "w_down")
_ROUTED = ("router", "expert_bias", "sh_w1", "sh_w3", "sh_w2")


def _layer(layers: dict, names, i) -> dict:
    """Layer ``i`` (traced) of the leaves ``names``, each stacked over the
    layers that hold one: ONE dynamic index a leaf (a {q, s} int8 leaf is
    indexed leaf by leaf)."""
    def one(a):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    return {k: jax.tree.map(one, layers[k]) for k in names}


def _rope_terms(cfg, positions):
    return mla.rope_terms(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                          inv_freq=cfg.rope_inv_freq,
                          mscale=cfg.rope_magnitude)


def _mla_proj(h, w, cfg, sin, cos):
    """From the normed h [N, D] and the rotary terms [N, rope]: q_nope
    [N, H, nope], q_rope [N, H, rope] (rotated), c [N, R] (normed), r
    [N, rope] (rotated)."""
    dt, n = h.dtype, h.shape[0]
    nope, R = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = mla.lora_query(h, _mat(w["mla_qa"], dt), w["mla_q_norm"],
                       _mat(w["mla_qb"], dt), cfg.rms_norm_eps).reshape(
                           n, cfg.num_heads, cfg.q_head_dim)
    q_rope = rotate_by_delta(q[..., nope:], sin[:, None], cos[:, None])
    kva = h @ _mat(w["mla_kva"], dt)
    c = rms_norm(kva[:, :R], w["mla_kv_norm"], cfg.rms_norm_eps)
    return q[..., :nope], q_rope, c, rotate_by_delta(kva[:, R:], sin, cos)


def _kvb(w, cfg):
    return mla.split_kvb(_mat(w["mla_kvb"], cfg.dtype), cfg.kv_lora_rank,
                         cfg.num_heads, cfg.qk_nope_head_dim)


def _mla_out(o, w, cfg):
    """o [N, H, v] -> W_o of the heads."""
    return o.astype(cfg.dtype).reshape(o.shape[0], -1) \
        @ _mat(w["mla_o"], cfg.dtype)


def _dense_ff(h, w):
    with _scope("layer/mlp"):
        return _mlp(h[None], w)[0]


def _moe_ff(h, w, layers, mi, cfg, live):
    """The routed expert feed-forward of the normed h [N, D] + the shared
    expert, expert layer ``mi``; ``live`` [N]: the rows that route.
    -> (y, experts [N, k])."""
    with _scope("layer/mlp/router"):
        experts, weights = moe.route(
            h, w["router"], w["expert_bias"], cfg.num_experts_per_tok,
            norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
            active=live, eps=1e-20)
    with _scope("layer/mlp/experts"):
        y = moe.experts_ffn(h, experts, weights, layers["w1"], layers["w3"],
                            layers["w2"], mi,
                            pallas=llama._target(cfg).pallas,
                            n_experts=cfg.num_experts)
    with _scope("layer/mlp/shared"):
        y = y + moe.shared_ffn(h, _mat(w["sh_w1"], h.dtype),
                               _mat(w["sh_w3"], h.dtype),
                               _mat(w["sh_w2"], h.dtype))
    return y, experts


def _sublayer(X, hc, norm_w, fn, cfg):
    """One sublayer under its hyper-connection: ``fn(normed input) -> (y,
    aux)``; X [N, n, C] -> (X', aux). ``hc`` None (``hc_mult`` 1): the
    plain pre-norm residual."""
    if hc is None:
        y, aux = fn(rms_norm(X[:, 0], norm_w, cfg.rms_norm_eps))
        return X + y[:, None].astype(X.dtype), aux
    u, post, M = hyper.mix(X, hc, cfg.hc_params)
    y, aux = fn(rms_norm(u, norm_w, cfg.rms_norm_eps))
    return hyper.merge(X, y, post, M), aux


def _head(X, params, cfg):
    if cfg.hc_mult > 1:
        x = hyper.readout(X, (params["hc_head_w"], params["hc_head_s"],
                              params["hc_head_b"]), cfg.hc_params)
    else:
        x = X[:, 0]
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with _scope("lm_head"):
        return unembed(x, params, cfg)


def _embed(params, tokens, cfg):
    """The streams ``X [N, n, C]``: every stream starts as the embedding."""
    with _scope("embed"):
        x = _embed_rows(params["embed"], tokens, cfg.dtype)
        return jnp.broadcast_to(x[:, None], (x.shape[0], cfg.hc_mult,
                                             x.shape[1]))


def _run_layers(cfg, params, carry, mla_op, live):
    """The layer stack over ``carry`` = (X, ck, choices): ``mla_op(h, w, ck,
    i) -> (y, ck)`` is the mixer on its normed input (decode or packed);
    the feed-forward follows by the layer's kind."""
    layers = params["layers"]
    nd = cfg.num_dense_layers

    def hcs(i):
        if cfg.hc_mult == 1:
            return None, None
        w = _layer(layers, _HC, i)
        return tuple((w["hc_w"][j], w["hc_s"][j], w["hc_b"][j])
                     for j in (0, 1))

    def layer_fn(dense):
        def fn(carry, ki, i):
            X, ck, choices = carry
            e = _layer(layers, _NORMS, i)
            hc_mix, hc_ff = hcs(i)
            w = _layer(layers, _MLA, i)
            X, ck = _sublayer(X, hc_mix, e["mix_norm"],
                              lambda h: mla_op(h, w, ck, i), cfg)
            if dense:
                wd = _layer(layers, _DENSE, ki)
                X, _ = _sublayer(X, hc_ff, e["ff_norm"],
                                 lambda h: (_dense_ff(h, wd), None), cfg)
                return X, ck, choices
            wr = _layer(layers, _ROUTED, ki)
            X, experts = _sublayer(
                X, hc_ff, e["ff_norm"],
                lambda h: _moe_ff(h, wr, layers, ki, cfg, live), cfg)
            return X, ck, jax.lax.dynamic_update_index_in_dim(
                choices, experts, ki, 0)
        return fn

    return scan_layer_runs(cfg.layer_kinds, carry,
                           {"dense": layer_fn(True), "moe": layer_fn(False)},
                           lead=nd)


def _no_choices(cfg, rows):
    return jnp.full((cfg.moe_layers, rows, cfg.num_experts_per_tok),
                    cfg.num_experts, jnp.int32)


def decode_step(params, cfg: Xing4Config, tokens, lengths, active, cache_k,
                cache_v):
    """One decode step for all slots. tokens [S]; ``lengths`` the position
    each slot's new latent row is written at (C for an inactive slot: the
    write drops) and its rotary position; ``active`` [S] gates the routing.
    -> (logits [S, V], cache_k, cache_v, choices [L_moe, S, k])."""
    S = tokens.shape[0]
    X = _embed(params, tokens, cfg)                              # [S, n, D]
    C = kvcache.shape(cache_k)[2]
    sin, cos = _rope_terms(cfg, lengths)
    read = jnp.where(lengths >= C, 0, lengths)   # an idle slot reads nothing
    slot = jnp.arange(S, dtype=jnp.int32)[:, None]
    # the kernel copies float pages of 2 or 4 bytes
    pallas = llama._target(cfg).pallas \
        and cache_k["pages"].dtype.itemsize >= 2

    def mla_op(h, w, ck, li):
        with _scope("layer/attn_proj"):
            q_nope, q_rope, c, r = _mla_proj(h, w, cfg, sin, cos)
            w_k, w_v = _kvb(w, cfg)
            row = mla.latent_rows(c, r, cfg.latent_width)    # [S, 1, Wd]
            q_abs = mla.absorb_query(q_nope, q_rope, w_k, cfg.latent_width,
                                     cfg.score_scale)
        with _scope("layer/attn"):
            o_lat = mla.decode_attention(q_abs, row, ck, li, read,
                                         cfg.kv_lora_rank, pallas=pallas)
            ck = kvcache.scatter_prefill(ck, li, slot, lengths[:, None],
                                         row[:, None])
        with _scope("layer/attn_proj"):
            return _mla_out(mla.expand_values(o_lat, w_v), w, cfg), ck

    X, cache_k, choices = _run_layers(
        cfg, params, (X, cache_k, _no_choices(cfg, S)), mla_op, active)
    return _head(X, params, cfg), cache_k, cache_v, choices


def engine_decode(params, cfg, tokens, lengths, active, cache_k, cache_v,
                  pos_offset=None, route_stats: bool = False):
    """Engine adapter (the contract of models/llama.py and the hybrids): an
    inactive slot writes no row (its position is forced to C, which the
    scatter drops) and routes nowhere. ``pos_offset`` (self-extend, the
    snap-back window) is not declared. With ``route_stats`` a fourth
    result: the step's route stats."""
    del pos_offset
    C = kvcache.shape(cache_k)[2]
    logits, ck, cv, choices = decode_step(
        params, cfg, tokens, jnp.where(active, lengths, C), active, cache_k,
        cache_v)
    if route_stats:
        return logits, ck, cv, _stats(choices, cfg)
    return logits, ck, cv


def ragged_prefill_routed(params, cfg: Xing4Config, tokens, positions,
                          seg_of, seg_slots, seg_start, seg_off, seg_len,
                          cache_k, cache_v, continued: bool = False):
    """Packed prefill on models/llama.py::ragged_prefill's contract (its
    docstring has the arguments). A layer attends with materialised heads
    over the pack and, continued, the slot's committed latent rows (its
    own or the pages it shares), and writes the pack's rows; the expert
    layers run the grouped form over the pack's real tokens (a pad token
    routes nowhere). Pad segments (slot sentinel) write nothing.
    -> (logits [B, V], cache_k, cache_v, choices [L_moe, N, k])."""
    N = tokens.shape[0]
    B = seg_slots.shape[0]
    X = _embed(params, tokens, cfg)                              # [N, n, D]
    seg = jnp.minimum(seg_of, B - 1)
    slot_of = jnp.take(seg_slots, seg)
    real = seg_of < B
    C = kvcache.shape(cache_k)[2]
    sin, cos = _rope_terms(cfg, jnp.where(positions < C, positions, 0))

    def mla_op(h, w, ck, li):
        with _scope("layer/attn_proj"):
            q_nope, q_rope, c, r = _mla_proj(h, w, cfg, sin, cos)
            w_k, w_v = _kvb(w, cfg)
            rows = mla.latent_rows(c, r, cfg.latent_width)   # [N, 1, Wd]

            def expand(lat):
                return mla.expand_rows(lat, w_k, w_v, cfg.qk_rope_head_dim)

            # the pack's own keys and values from the rows as the pool
            # will hold them
            k, v = expand(rows[:, 0].astype(ck["pages"].dtype))
            q = jnp.concatenate([q_nope, q_rope], -1)
        with _scope("layer/attn"):
            o = mla.prefill_attention(
                q, k.astype(q.dtype), v.astype(q.dtype), seg_of, seg_slots,
                seg_start, ck, li, expand, cfg.score_scale,
                continued=continued)
            ck = kvcache.scatter_ragged(ck, li, slot_of, positions, rows)
        with _scope("layer/attn_proj"):
            return _mla_out(o, w, cfg), ck

    X, cache_k, choices = _run_layers(
        cfg, params, (X, cache_k, _no_choices(cfg, N)), mla_op, real)
    last = jnp.maximum(seg_off + seg_len - 1, 0)
    return (_head(jnp.take(X, last, axis=0), params, cfg), cache_k, cache_v,
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
    engine's packed path is what serves; a continued single segment after a
    shared prefix may come this way)."""
    assert mm_pos is None and positions is None and not return_all_logits, \
        "multimodal, explicit positions and all-logits are not declared"
    return prefill_as_pack(ragged_prefill, params, cfg, tokens, seq_lens,
                           cache_k, cache_v, slot_ids, start_pos, continued)
