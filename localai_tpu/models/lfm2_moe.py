"""LFM2-MoE decoder (``model_type: lfm2_moe``): gated short-convolution
layers beside grouped-query attention layers (``layer_types``), the first
``num_dense_layers`` followed by a dense SwiGLU feed-forward and every later
one by a ROUTED EXPERT feed-forward (ops/moe.py: sigmoid scores over
``num_experts``, the ``num_experts_per_tok`` largest of score + bias chosen,
the scores of the chosen normalised).

Three kinds of (operator, feed-forward) layer in one stack - ``conv_dense``,
``attention_moe``, ``conv_moe`` - and two kinds of state in one slot:

  * an ATTENTION layer is causal softmax attention over H query heads and
    KV key/value heads, no bias; q and k are RMS-normed per head (one weight
    of ``head_dim``, shared by the heads) and then rotated (``rope_theta``,
    the whole head, half-split convention). Its K/V rows live in the paged
    pool of ops/kvcache.py and go through models/llama.py's kernels, the
    pool holding each head padded with zeros to a multiple of 128
    (``pool_head_dim``; models/granite_hybrid.py's module doc says why);
    the kernels scale by ``pool_head_dim ** -0.5``, so q is multiplied by
    ``q_scale`` on its way in;
  * a CONV layer: ``[B | C | u] = in_proj(h)``, ``v = B * u``, ``y_t = sum_j
    w[:, j] * v_{t-W+1+j}`` (depthwise, causal, ``conv_L_cache`` = W taps, no
    bias, no activation), ``out_proj(C * y)``. Per slot it keeps the last
    W - 1 rows of ``v`` (``conv`` [W - 1, D], oldest first) and nothing else.

The cache is the paged pytree with ONE more leaf on ``cache_k``:

    cache_k = {"pages": [L_attn, n_pages, page, KV, hdp], "ptab": [S, MP],
               "conv": [L_conv, S, W - 1, D]}
    cache_v = {"pages": ..., "ptab": ...}

with the hybrids' rules: a prefill segment that starts at position 0 starts
from a zero tail whatever the slot held, a continued one from the slot's; an
inactive slot's tail is untouched by a decode step, and such a slot ROUTES
NOWHERE: it touches no expert and counts in no counter.

Blocks are pre-norm: ``x += op(operator_norm(x))``, ``x += ff(ffn_norm(x))``;
``logits = embedding_norm(x) @ E^T`` (the head tied to the embedding).

Weights are stacked by what holds them: ``conv_*`` over the conv layers,
``wq`` .. ``k_norm`` over the attention layers, the dense feed-forward over
the dense layers, the router, its bias and the experts ``[L_moe, E, ...]``
over the expert layers, the two norms over every layer. The layer scan runs
over runs of same-kind layers (hybrid_common.scan_layer_runs): the dense
layers, then periods of the expert layers.

Every function that runs the layers also yields the experts each row chose,
``[L_moe, rows, k]``; the engine's adapters turn them into
route stats (``route_stats_shape``: per expert layer the (row, expert) pairs
each expert got, then the distinct experts touched) when asked to.

What the engine may do with this family is ``CAPABILITIES``: paged KV and
packed prefill, for models/olmo_hybrid.py's reasons (no prefix reuse: a page
of K/V without the tails at its boundary cannot be resumed from); and
``route_stats``: its steps report their routing.
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
from localai_tpu.ops import kvcache, moe
from localai_tpu.ops.norms import rms_norm
from localai_tpu.ops.rope import rope_delta_terms, rotate_by_delta

CAPABILITIES = frozenset({"paged", "packed_prefill", "route_stats"})

_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776          # the dense layers' feed-forward
    moe_intermediate_size: int = 1536       # one expert's
    num_layers: int = 40
    kinds: Tuple[str, ...] = ("conv", "conv") \
        + ("attention", "conv", "conv", "conv") * 9 + ("attention", "conv")
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_kernel: int = 3                    # conv_L_cache
    rms_norm_eps: float = 1e-5              # norm_eps
    rope_theta: float = 1e6
    rope_scaling_type: str = "default"
    rope_scaling_factor: float = 1.0
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    attn: Optional[AttnTarget] = None

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def conv_layers(self) -> int:
        return self.kinds.count("conv")

    @property
    def attn_layers(self) -> int:
        return self.kinds.count("attention")

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """(operator, feed-forward) a layer, as the layer scan names it."""
        return tuple(k + ("_dense" if i < self.num_dense_layers else "_moe")
                     for i, k in enumerate(self.kinds))

    @property
    def pool_head_dim(self) -> int:
        """A head's width as the page pool holds it: a multiple of 128; the
        extra columns of q, k and v are zero."""
        return -(-self.head_dim_ // 128) * 128

    @property
    def attn_cfg(self) -> "Lfm2MoeConfig":
        """This config as models/llama.py's attention helpers read it:
        heads of ``pool_head_dim``."""
        return dataclasses.replace(self, head_dim=self.pool_head_dim)

    @property
    def q_scale(self) -> float:
        """What q is multiplied by so that the attention kernels' own
        ``pool_head_dim ** -0.5`` comes out as ``head_dim ** -0.5``."""
        return (self.pool_head_dim / self.head_dim_) ** 0.5

    @staticmethod
    def from_hf_config(cfg: dict, dtype=jnp.bfloat16) -> "Lfm2MoeConfig":
        L = cfg["num_hidden_layers"]
        names = {"conv": "conv", "full_attention": "attention"}
        kinds = tuple(cfg["layer_types"])[:L]
        if len(kinds) != L or set(kinds) - set(names):
            raise ValueError("lfm2_moe: layer_types must name 'conv' or "
                             f"'full_attention' for each of {L} layers; "
                             f"got {kinds}")
        if cfg.get("conv_bias"):
            raise ValueError("lfm2_moe: conv_bias true is not built (the "
                             "short convolution and its projections carry "
                             "no bias here)")
        nd = int(cfg.get("num_dense_layers", 0))
        if "full_attention" in kinds[:nd]:
            raise ValueError(
                f"lfm2_moe: num_dense_layers = {nd} reaches past the first "
                f"attention layer (layer {kinds.index('full_attention')}): "
                "a dense feed-forward after an attention operator is not "
                "built")
        if nd >= L:
            raise ValueError(f"lfm2_moe: num_dense_layers = {nd} leaves no "
                             f"expert layer among {L}")
        rope = cfg.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError("lfm2_moe: rope_type "
                             f"{rope.get('rope_type')!r} is not built "
                             "(only 'default')")
        return Lfm2MoeConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_layers=L, kinds=tuple(names[k] for k in kinds),
            num_dense_layers=nd,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads",
                                 cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            use_expert_bias=bool(cfg.get("use_expert_bias", False)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            conv_kernel=cfg.get("conv_L_cache", 3),
            rms_norm_eps=cfg.get("norm_eps", 1e-5),
            rope_theta=float(rope.get("rope_theta",
                                      cfg.get("rope_theta", 1e6))),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            dtype=dtype)


def route_stats_shape(cfg: Lfm2MoeConfig) -> Tuple[int, int]:
    """(expert layers L, experts E): a step's route stats are, per expert
    layer, the pairs each of the E experts got, the experts touched and the
    pairs routed in all (ops/moe.py::route_stats), ``[L, E + 2]`` laid flat
    (the engine asks by this name where a family declares
    ``route_stats``)."""
    return cfg.moe_layers, cfg.num_experts


def _stats(choices, cfg):
    """choices [L_moe, rows, k] -> ``route_stats`` of each layer, laid end
    to end [L_moe * (E + 2)] float32."""
    return jax.vmap(lambda c: moe.route_stats(c, cfg.num_experts))(
        choices).reshape(-1)


def load_hf_params(model_dir: str, cfg: Lfm2MoeConfig, dtype=jnp.bfloat16,
                   quantize: str = "", tracer=None) -> dict:
    """The adapter contract's loader (backend/runner.py); the leaves and
    the cast are engine/weights.py's."""
    from localai_tpu.engine import weights

    return weights.load_lfm2_moe_params(
        model_dir, cfg, dtype=dtype, quantize=quantize, tracer=tracer)


def init_cache(cfg: Lfm2MoeConfig, num_slots: int, max_len: int, dtype=None,
               page_size: int = 0, num_pages: int = 0):
    """(cache_k, cache_v) as in the module doc. Only the attention layers
    have rows in the page pool."""
    if not page_size:
        raise ValueError("lfm2_moe serves on the paged KV layout only "
                         "(kv_layout=contiguous and lockstep are refused)")
    if kvcache.wants_quant(dtype or cfg.dtype):
        raise ValueError("lfm2_moe: an int8 KV cache is not built")
    shape = (cfg.attn_layers, num_slots, max_len, cfg.num_kv_heads,
             cfg.pool_head_dim)
    ck = kvcache.init_paged(shape, dtype or cfg.dtype, page_size, num_pages)
    cv = kvcache.init_paged(shape, dtype or cfg.dtype, page_size, num_pages)
    ck["conv"] = jnp.zeros(
        (cfg.conv_layers, num_slots, cfg.conv_kernel - 1, cfg.hidden_size),
        cfg.dtype)
    return ck, cv


def init_params(cfg: Lfm2MoeConfig, key: jax.Array, dtype=None) -> dict:
    """Random parameters in the stacked layout (tests)."""
    dtype = dtype or cfg.dtype
    D, F, Fe = cfg.hidden_size, cfg.intermediate_size, \
        cfg.moe_intermediate_size
    L, Lc, La = cfg.num_layers, cfg.conv_layers, cfg.attn_layers
    Ld, Lm, E = cfg.num_dense_layers, cfg.moe_layers, cfg.num_experts
    hd = cfg.head_dim_
    H, KVd = cfg.num_heads * hd, cfg.num_kv_heads * hd
    ks = iter(jax.random.split(key, 24))

    def init(shape, fan_in, dt=dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    return {
        "embed": init((cfg.vocab_size, D), 1.0),
        "final_norm": jnp.ones((D,), dtype),
        "layers": {
            "op_norm": jnp.ones((L, D), dtype),
            "ff_norm": jnp.ones((L, D), dtype),
            "conv_in": init((Lc, D, 3 * D), D),
            "conv_w": init((Lc, cfg.conv_kernel, D), cfg.conv_kernel),
            "conv_out": init((Lc, D, D), D),
            "wq": init((La, D, H), D), "wk": init((La, D, KVd), D),
            "wv": init((La, D, KVd), D), "wo": init((La, H, D), H),
            "q_norm": jnp.ones((La, hd), dtype),
            "k_norm": jnp.ones((La, hd), dtype),
            "w_gate": init((Ld, D, F), D), "w_up": init((Ld, D, F), D),
            "w_down": init((Ld, F, D), F),
            "router": init((Lm, D, E), D, jnp.float32),
            "expert_bias": init((Lm, E), 100.0, jnp.float32),
            "w1": init((Lm, E, D, Fe), D), "w3": init((Lm, E, D, Fe), D),
            "w2": init((Lm, E, Fe, D), Fe),
        },
    }


_CONV = ("conv_in", "conv_w", "conv_out")
_ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_NORMS = ("op_norm", "ff_norm")
_DENSE = ("w_gate", "w_up", "w_down")
_ROUTER = ("router", "expert_bias")


def _layer(layers: dict, names, i) -> dict:
    """Layer ``i`` (traced) of the leaves ``names``, each stacked over the
    layers that hold one: ONE dynamic index a leaf (a {q, s} int8 leaf is
    indexed leaf by leaf)."""
    def one(a):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    return {k: jax.tree.map(one, layers[k]) for k in names}


def _conv_proj(x, w, e, cfg):
    """x [N, D] -> the operator norm, then ``in_proj`` in its three parts:
    the input gate B, the output gate C, the convolution's input u."""
    h = rms_norm(x, e["op_norm"], cfg.rms_norm_eps)
    bcu = h @ _mat(w["conv_in"], h.dtype)
    D = cfg.hidden_size
    return bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]


def _attn_qkv(x, layer, e, cfg, sin, cos):
    """x [N, D] -> q [N, H, hdp] (scaled: ``q_scale``), k, v [N, KV, hdp]
    with hdp = ``pool_head_dim`` (zero columns past ``head_dim``): the
    operator norm, the projections, q and k normed per head and rotated
    (sin, cos [N, hd])."""
    dt, hd, n = x.dtype, cfg.head_dim_, x.shape[0]
    h = rms_norm(x, e["op_norm"], cfg.rms_norm_eps)

    def heads(a, n_heads):
        return a.reshape(n, n_heads, hd)

    def pad(a):
        return jnp.pad(a, ((0, 0), (0, 0), (0, cfg.pool_head_dim - hd)))

    def normed(a, w, scale=1.0):
        a = rotate_by_delta(rms_norm(a, w, cfg.rms_norm_eps),
                            sin[:, None], cos[:, None])
        if scale != 1.0:
            a = (a.astype(jnp.float32) * scale).astype(dt)
        return a

    q = normed(heads(h @ _mat(layer["wq"], dt), cfg.num_heads),
               layer["q_norm"], cfg.q_scale)
    k = normed(heads(h @ _mat(layer["wk"], dt), cfg.num_kv_heads),
               layer["k_norm"])
    v = heads(h @ _mat(layer["wv"], dt), cfg.num_kv_heads)
    return pad(q), pad(k), pad(v)


def _attn_out(attn, layer, cfg):
    """attn [N, H, hdp] -> W_o of its first ``head_dim`` columns a head."""
    a = attn[:, :, :cfg.head_dim_].reshape(attn.shape[0], -1)
    return a @ _mat(layer["wo"], a.dtype)


def _dense_ff(x, e, w, cfg):
    with _scope("layer/mlp"):
        h = rms_norm(x, e["ff_norm"], cfg.rms_norm_eps)
        return x + _mlp(h[None], w)[0]


def _moe_ff(x, e, w, layers, mi, cfg, live):
    """x + the routed expert feed-forward of norm(x), expert layer ``mi``
    (ops/moe.py, which takes the expert stacks whole). x [N, D]; ``live``
    [N]: the rows that route. -> (x, experts [N, k])."""
    h = rms_norm(x, e["ff_norm"], cfg.rms_norm_eps)
    with _scope("layer/mlp/router"):
        experts, weights = moe.route(
            h, w["router"],
            w["expert_bias"] if cfg.use_expert_bias else None,
            cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, active=live)
    with _scope("layer/mlp/experts"):
        y = moe.experts_ffn(h, experts, weights, layers["w1"], layers["w3"],
                            layers["w2"], mi,
                            pallas=llama._target(cfg).pallas)
    return x + y, experts


def _head(x, params, cfg):
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with _scope("lm_head"):
        return unembed(x, params, cfg)


def _embed(params, tokens, cfg):
    with _scope("embed"):
        return _embed_rows(params["embed"], tokens, cfg.dtype)


def _run_layers(cfg, params, carry, conv_op, attn_op, live):
    """The layer stack over ``carry`` = (x, ck, cv, choices): ``conv_op(x,
    w, e, ck, ci)`` and ``attn_op(x, layer, e, ck, cv, ai)`` are the two
    operators (decode or packed), each -> its residual update and caches;
    the feed-forward follows by the layer's kind."""
    layers = params["layers"]
    nd = cfg.num_dense_layers

    def ff(x, e, i, choices, dense):
        if dense:
            return _dense_ff(x, e, _layer(layers, _DENSE, i), cfg), choices
        x, experts = _moe_ff(x, e, _layer(layers, _ROUTER, i - nd), layers,
                             i - nd, cfg, live)
        return x, jax.lax.dynamic_update_index_in_dim(choices, experts,
                                                      i - nd, 0)

    def conv_layer(dense, n_before):
        def fn(carry, ki, i):
            x, ck, cv, choices = carry
            ci = ki + n_before
            e = _layer(layers, _NORMS, i)
            x, ck = conv_op(x, _layer(layers, _CONV, ci), e, ck, ci)
            x, choices = ff(x, e, i, choices, dense)
            return x, ck, cv, choices
        return fn

    def attn_layer(carry, ki, i):
        x, ck, cv, choices = carry
        e = _layer(layers, _NORMS, i)
        x, ck, cv = attn_op(x, _layer(layers, _ATTN, ki), e, ck, cv, ki)
        x, choices = ff(x, e, i, choices, False)
        return x, ck, cv, choices

    return scan_layer_runs(
        cfg.layer_kinds, carry,
        {"conv_dense": conv_layer(True, 0),
         "conv_moe": conv_layer(False, nd),      # the dense layers are conv
         "attention_moe": attn_layer}, lead=nd)


def _no_choices(cfg, rows):
    return jnp.full((cfg.moe_layers, rows, cfg.num_experts_per_tok),
                    cfg.num_experts, jnp.int32)


def decode_step(params, cfg: Lfm2MoeConfig, tokens, lengths, active,
                cache_k, cache_v):
    """One decode step for all slots. tokens [S]; ``lengths`` the position
    each slot's new K/V row is written at (C for an inactive slot: the
    write drops) and its rotary position; ``active`` [S] gates the
    convolution tails and the routing.
    -> (logits [S, V], cache_k, cache_v, choices [L_moe, S, k])."""
    f32 = jnp.float32
    x = _embed(params, tokens, cfg)                              # [S, D]
    sin, cos = rope_delta_terms(cfg, lengths)          # [S, head_dim]

    def conv_op(x, w, e, ck, ci):
        with _scope("layer/attn_proj/conv"):
            B, C, u = _conv_proj(x, w, e, cfg)
        with _scope("layer/conv"):
            v = B * u
            old = jax.lax.dynamic_index_in_dim(ck["conv"], ci, 0, False)
            win = jnp.concatenate([old, v[:, None]], axis=1)
            y = jnp.sum(win.astype(f32) * w["conv_w"].astype(f32)[None],
                        axis=1)
            new = jnp.where(active[:, None, None], win[:, 1:], old)
            ck = dict(ck, conv=jax.lax.dynamic_update_index_in_dim(
                ck["conv"], new, ci, 0))
            gated = (C.astype(f32) * y).astype(x.dtype)
        with _scope("layer/attn_proj/conv"):
            return x + gated @ _mat(w["conv_out"], x.dtype), ck

    def attn_op(x, layer, e, ck, cv, ai):
        with _scope("layer/attn_proj"):
            q, k, v = _attn_qkv(x, layer, e, cfg, sin, cos)
        with _scope("layer/attn"):
            attn, ck, cv = llama._decode_attend_write(
                q, k, v, ck, cv, ai, lengths, cfg.attn_cfg)
        with _scope("layer/attn_proj"):
            return x + _attn_out(attn, layer, cfg), ck, cv

    x, cache_k, cache_v, choices = _run_layers(
        cfg, params, (x, cache_k, cache_v, _no_choices(cfg, x.shape[0])),
        conv_op, attn_op, active)
    return _head(x, params, cfg), cache_k, cache_v, choices


def engine_decode(params, cfg, tokens, lengths, active, cache_k, cache_v,
                  pos_offset=None, route_stats: bool = False):
    """Engine adapter (the contract of models/llama.py and the hybrids): an
    inactive slot writes no K/V row (its position is forced to C, which the
    scatter drops), keeps its tail and routes nowhere. ``pos_offset``
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


def ragged_prefill_routed(params, cfg: Lfm2MoeConfig, tokens, positions,
                          seg_of, seg_slots, seg_start, seg_off, seg_len,
                          cache_k, cache_v, continued: bool = False):
    """Packed prefill on models/llama.py::ragged_prefill's contract (its
    docstring has the arguments). The attention layers attend and write K/V
    rows exactly as there; a conv layer runs the causal convolution over
    the pack's segments, each from a zero tail when it starts at position 0
    and from its slot's otherwise, and leaves its new tail in the slot; the
    expert layers run the grouped form over the pack's real tokens (a pad
    token routes nowhere). Pad segments (slot sentinel) write nothing.
    -> (logits [B, V], cache_k, cache_v, choices [L_moe, N, k])."""
    f32 = jnp.float32
    N = tokens.shape[0]
    B = seg_slots.shape[0]
    S = cache_k["conv"].shape[1]
    W1 = cfg.conv_kernel - 1
    x = _embed(params, tokens, cfg)                              # [N, D]
    seg = jnp.minimum(seg_of, B - 1)
    slot_of = jnp.take(seg_slots, seg)
    real = seg_of < B
    j = jnp.where(real, jnp.arange(N, dtype=jnp.int32)
                  - jnp.take(seg_off, seg), 0)               # index in segment
    slots_c = jnp.minimum(seg_slots, S - 1)
    fresh = seg_start == 0
    C = kvcache.shape(cache_k)[2]
    sin, cos = rope_delta_terms(
        cfg, jnp.where(positions < C, positions, 0))            # (pads: 0)

    def conv_op(x, w, e, ck, ci):
        with _scope("layer/attn_proj/conv"):
            Bg, Cg, u = _conv_proj(x, w, e, cfg)
        with _scope("layer/conv"):
            v = Bg * u
            if continued:
                conv0 = jnp.where(fresh[:, None, None], 0, jnp.take(
                    jax.lax.dynamic_index_in_dim(ck["conv"], ci, 0, False),
                    slots_c, axis=0))
            else:
                conv0 = jnp.zeros((B, W1, cfg.hidden_size), v.dtype)
            y = packed_conv(v, conv0, w["conv_w"].astype(f32), seg, j)
            tail = new_tails(v, conv0, seg_off, seg_len)     # [B, W-1, D]
            ck = dict(ck, conv=ck["conv"].at[ci, seg_slots].set(
                tail.astype(ck["conv"].dtype), mode="drop"))
            gated = (Cg.astype(f32) * y).astype(x.dtype)
        with _scope("layer/attn_proj/conv"):
            return x + gated @ _mat(w["conv_out"], x.dtype), ck

    def attn_op(x, layer, e, ck, cv, ai):
        with _scope("layer/attn_proj"):
            q, k, v = _attn_qkv(x, layer, e, cfg, sin, cos)
        with _scope("layer/attn"):
            attn, ck, cv = llama.ragged_attend_write(
                cfg.attn_cfg, q, k, v, ck, cv, ai, seg_of, seg_slots,
                seg_start, seg_off, seg_len, slot_of, positions, continued)
        with _scope("layer/attn_proj"):
            return x + _attn_out(attn, layer, cfg), ck, cv

    x, cache_k, cache_v, choices = _run_layers(
        cfg, params, (x, cache_k, cache_v, _no_choices(cfg, N)),
        conv_op, attn_op, real)
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
