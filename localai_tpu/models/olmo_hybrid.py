"""Olmo-Hybrid decoder (``model_type: olmo_hybrid``): gated-DeltaNet
linear-attention layers beside full-attention layers, three of the first to
one of the second (``layer_types``), each followed by a SwiGLU MLP.

Two kinds of layer, two kinds of state in one slot:

  * a FULL layer is causal softmax attention over H heads with an RMSNorm
    on the whole q and k projections and no rotary embedding; its K/V rows
    live in the paged pool of ops/kvcache.py and go through the same
    kernels as models/llama.py's (``ragged_attend_write``,
    ``_decode_attend_write``);
  * a LINEAR layer keeps, per slot, a recurrent state ``delta``
    [H, K, V] float32 and the last three inputs of its width-4 causal
    convolution ``conv`` [3, 2HK + HV] (ops/gated_delta.py has the rule).

The cache is the paged pytree with two more leaves on ``cache_k``:

    cache_k = {"pages": [L_full, n_pages, page, KVp, hd], "ptab": [S, MP],
               "delta": [L_lin, S, H, K, V] f32, "conv": [L_lin, S, 3, Ch]}
    cache_v = {"pages": ..., "ptab": ...}

Pages are allocated by engine/paging.py as for any paged family; the state
is per slot. A prefill segment that starts at position 0 starts from a zero
state whatever the slot held (admission, resume and context shift all
re-prefill from 0), a continued one from the slot's; an inactive slot's
state is untouched by a decode step. ``conv`` is time-major ([3, Ch], not
the [Ch, 3] of the recurrence's textbook form): a trailing axis of 3 would
be padded to 128 lanes on the TPU.

The layer scan runs over PERIODS of four layers; weights are stacked
``[periods, 3, ...]`` (linear), ``[periods, ...]`` (full) and
``[periods, 4, ...]`` (MLP and the two post-norms of every layer). Blocks
are post-norm: ``x + norm(mixer(x))``, ``x + norm(mlp(x))``.

What the engine may do with this family is ``CAPABILITIES``: paged KV and
packed prefill. Prefix reuse, speculation, self-extend and multimodal
injection are off: a page of K/V without the recurrent state at its
boundary cannot be resumed from, and a rejected draft would have to roll
the state back (ROADMAP.md M2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.models import llama
from localai_tpu.models.hybrid_common import (new_tails, packed_conv,
                                              prefill_as_pack, scan_periods,
                                              unembed)
from localai_tpu.models.llama import AttnTarget, _embed_rows, _mat, _mlp
from localai_tpu.ops import gated_delta, kvcache
from localai_tpu.ops.norms import rms_norm

CAPABILITIES = frozenset({"paged", "packed_prefill"})
PERIOD = ("linear_attention",) * 3 + ("full_attention",)

_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 30
    num_kv_heads: int = 30
    head_dim: Optional[int] = None
    linear_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    attn: Optional[AttnTarget] = None

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def kv_heads_padded(self) -> int:
        """KV heads as the page pool holds them: a multiple of 8. The
        TPU's own layout for a pool whose second-minor axis is 30 puts the
        page axis there instead, and the attention kernels, which want
        [page, KV, hd] row-major, then get a transposed copy of the whole
        pool every layer of every step. The tiles of (30, 128) are padded
        to 32 rows in memory either way."""
        return -(-self.num_kv_heads // 8) * 8

    @property
    def attn_cfg(self) -> "OlmoHybridConfig":
        """This config as models/llama.py's attention helpers read it:
        the padded head counts (the extra heads' q, k and v are zero)."""
        kv = self.kv_heads_padded
        return dataclasses.replace(self, num_kv_heads=kv,
                                   num_heads=kv * self.q_per_kv,
                                   head_dim=self.head_dim_)

    @property
    def periods(self) -> int:
        return self.num_layers // len(PERIOD)

    @property
    def lin_layers(self) -> int:
        return 3 * self.periods

    @property
    def qk_channels(self) -> int:
        return self.linear_heads * self.linear_key_dim

    @property
    def v_channels(self) -> int:
        return self.linear_heads * self.linear_value_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.qk_channels + self.v_channels

    @staticmethod
    def from_hf_config(cfg: dict, dtype=jnp.bfloat16) -> "OlmoHybridConfig":
        L = cfg["num_hidden_layers"]
        kinds = tuple(cfg.get("layer_types") or PERIOD * (L // 4))[:L]
        if L % len(PERIOD) or kinds != PERIOD * (L // len(PERIOD)):
            raise ValueError(
                "olmo_hybrid: layer_types must be whole periods of 3 x "
                f"linear_attention then 1 x full_attention; got {L} layers "
                f"{kinds}")
        if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
            raise ValueError("olmo_hybrid: linear key and value head "
                             "counts differ; only equal counts are built")
        if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
            raise ValueError("olmo_hybrid: a rotary base is set; the full "
                             "layers here apply no rotary embedding")
        return OlmoHybridConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"], num_layers=L,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads",
                                 cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            linear_heads=cfg["linear_num_value_heads"],
            linear_key_dim=cfg["linear_key_head_dim"],
            linear_value_dim=cfg["linear_value_head_dim"],
            conv_kernel=cfg["linear_conv_kernel_dim"],
            allow_neg_eigval=bool(cfg.get("linear_allow_neg_eigval", False)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            dtype=dtype)


def load_hf_params(model_dir: str, cfg: OlmoHybridConfig, dtype=jnp.bfloat16,
                   quantize: str = "", tracer=None) -> dict:
    """The adapter contract's loader (backend/runner.py); the leaves, the
    cast and the int8 path are engine/weights.py's, shared with llama."""
    from localai_tpu.engine import weights

    return weights.load_olmo_hybrid_params(model_dir, cfg, dtype=dtype,
                                           quantize=quantize, tracer=tracer)


def init_cache(cfg: OlmoHybridConfig, num_slots: int, max_len: int,
               dtype=None, page_size: int = 0, num_pages: int = 0,
               state_dtype=jnp.float32):
    """(cache_k, cache_v) as in the module doc. Only the full layers have
    rows in the page pool; ``state_dtype`` is float32 unless a test or the
    benchmark's control asks what a lower precision would do."""
    if not page_size:
        raise ValueError("olmo_hybrid serves on the paged KV layout only "
                         "(kv_layout=contiguous and lockstep are refused)")
    if kvcache.wants_quant(dtype or cfg.dtype):
        raise ValueError("olmo_hybrid: an int8 KV cache is not built")
    shape = (cfg.periods, num_slots, max_len, cfg.kv_heads_padded,
             cfg.head_dim_)
    ck = kvcache.init_paged(shape, dtype or cfg.dtype, page_size, num_pages)
    cv = kvcache.init_paged(shape, dtype or cfg.dtype, page_size, num_pages)
    ck["delta"] = jnp.zeros(
        (cfg.lin_layers, num_slots, cfg.linear_heads, cfg.linear_key_dim,
         cfg.linear_value_dim), state_dtype)
    ck["conv"] = jnp.zeros(
        (cfg.lin_layers, num_slots, cfg.conv_kernel - 1, cfg.conv_channels),
        cfg.dtype)
    return ck, cv


def init_params(cfg: OlmoHybridConfig, key: jax.Array, dtype=None) -> dict:
    """Random parameters in the stacked layout (tests)."""
    dtype = dtype or cfg.dtype
    P_, D, F = cfg.periods, cfg.hidden_size, cfg.intermediate_size
    H, hd, Hl = cfg.num_heads * cfg.head_dim_, cfg.head_dim_, cfg.linear_heads
    KVd = cfg.num_kv_heads * hd
    ks = iter(jax.random.split(key, 16))

    def init(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    params = {
        "embed": init((cfg.vocab_size, D), 1.0),
        "final_norm": jnp.ones((D,), dtype),
        "layers": {
            "lin_qkv": init((P_, 3, D, cfg.conv_channels), D),
            "lin_g": init((P_, 3, D, cfg.v_channels), D),
            "lin_ab": init((P_, 3, D, 2 * Hl), D),
            "lin_o": init((P_, 3, cfg.v_channels, D), cfg.v_channels),
            "lin_conv": init((P_, 3, cfg.conv_kernel, cfg.conv_channels),
                             cfg.conv_kernel),
            "lin_A_log": jnp.full((P_, 3, Hl), -3.0, jnp.float32),
            "lin_dt_bias": jnp.zeros((P_, 3, Hl), jnp.float32),
            "lin_o_norm": jnp.ones((P_, 3, cfg.linear_value_dim), dtype),
            "wq": init((P_, D, H), D), "wk": init((P_, D, KVd), D),
            "wv": init((P_, D, KVd), D), "wo": init((P_, H, D), H),
            "q_norm": jnp.ones((P_, H), dtype),
            "k_norm": jnp.ones((P_, KVd), dtype),
            "mixer_norm": jnp.ones((P_, 4, D), dtype),
            "mlp_norm": jnp.ones((P_, 4, D), dtype),
            "w_gate": init((P_, 4, D, F), D), "w_up": init((P_, 4, D, F), D),
            "w_down": init((P_, 4, F, D), F),
        },
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init((D, cfg.vocab_size), D)
    return params


_FULL = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_EVERY = ("mixer_norm", "mlp_norm", "w_gate", "w_up", "w_down")


def _layer(layers: dict, names, i, per_period: int) -> dict:
    """Layer ``i`` (traced) of the leaves ``names``, which are stacked
    ``[periods, per_period, ...]`` (``[periods, ...]`` for 1). ONE dynamic
    index a leaf into the flattened leading axes, which XLA fuses into the
    consuming matmul as it does for models/llama.py's scan: a period's
    slice taken first and a layer's from that materialised the period's
    weights, 1.46 GB of temporaries a decode step at the benchmark's size.
    A {q, s} int8 leaf is indexed leaf by leaf."""
    def one(a):
        if per_period > 1:
            a = a.reshape((-1,) + a.shape[2:])
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    return {k: jax.tree.map(one, layers[k]) for k in names}


def _lin_names(layers: dict):
    return [k for k in layers if k.startswith("lin_")]


def _post(x, y, w, eps):
    return x + rms_norm(y, w, eps)


def _mlp_block(x, w, cfg):
    """x + norm(mlp(x)) with one layer's leaves ``w``. x [B, T, D]."""
    with _scope("layer/mlp"):
        return _post(x, _mlp(x, w), w["mlp_norm"], cfg.rms_norm_eps)


def _linear_proj(x, w, cfg):
    """The projections of a linear layer from x [N, D]: the convolution's
    input (q|k|v channels), the output gate, the log decay and the write
    strength. -> pre [N, Ch], gate [N, H, V], g [N, H] f32, beta [N, H]."""
    dt = x.dtype
    Hl = cfg.linear_heads
    pre = x @ _mat(w["lin_qkv"], dt)
    gate = (x @ _mat(w["lin_g"], dt)).reshape(-1, Hl, cfg.linear_value_dim)
    ab = (x @ _mat(w["lin_ab"], dt)).astype(jnp.float32)
    g = -jnp.exp(w["lin_A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ab[:, :Hl] + w["lin_dt_bias"].astype(jnp.float32))
    beta = (2.0 if cfg.allow_neg_eigval else 1.0) * jax.nn.sigmoid(ab[:, Hl:])
    return pre, gate, g, beta


def _split_heads(act, cfg):
    """Convolved, SiLU'd channels [N, Ch] -> q [N, H, K] (normalised,
    scaled), k [N, H, K] (normalised), v [N, H, V], float32."""
    Hl, K = cfg.linear_heads, cfg.linear_key_dim
    n, qk = act.shape[0], cfg.qk_channels
    q = gated_delta.l2norm(act[:, :qk].reshape(n, Hl, K)) * K ** -0.5
    k = gated_delta.l2norm(act[:, qk:2 * qk].reshape(n, Hl, K))
    v = act[:, 2 * qk:].reshape(n, Hl, cfg.linear_value_dim)
    return q, k, v.astype(jnp.float32)


def _linear_out(o, gate, w, cfg):
    """W_o [RMSNorm_head(o) * SiLU(gate)], in the model's dtype."""
    y = rms_norm(o, w["lin_o_norm"], cfg.rms_norm_eps) \
        * jax.nn.silu(gate.astype(jnp.float32))
    return y.reshape(o.shape[0], -1).astype(cfg.dtype) \
        @ _mat(w["lin_o"], cfg.dtype)


def _full_qkv(x, layer, cfg):
    """x [N, D] -> q [N, Hp, hd], k, v [N, KVp, hd] (``kv_heads_padded``):
    an RMSNorm over the whole q and k projections before the heads are
    split; no rotary."""
    dt, hd, eps = x.dtype, cfg.head_dim_, cfg.rms_norm_eps
    n = x.shape[0]
    q = rms_norm(x @ _mat(layer["wq"], dt), layer["q_norm"], eps)
    k = rms_norm(x @ _mat(layer["wk"], dt), layer["k_norm"], eps)
    v = x @ _mat(layer["wv"], dt)
    pad = cfg.kv_heads_padded - cfg.num_kv_heads

    def heads(a, n_heads, extra):       # zero heads up to the pool's count
        return jnp.pad(a.reshape(n, n_heads, hd), ((0, 0), (0, extra), (0, 0)))

    return (heads(q, cfg.num_heads, pad * cfg.q_per_kv),
            heads(k, cfg.num_kv_heads, pad), heads(v, cfg.num_kv_heads, pad))


def decode_step(params, cfg: OlmoHybridConfig, tokens, lengths, active,
                cache_k, cache_v):
    """One decode step for all slots. tokens [S]; ``lengths`` the position
    each slot's new K/V row is written at (C for an inactive slot: the
    write drops); ``active`` [S] gates the recurrent state.
    -> (logits [S, V], cache_k, cache_v)."""
    S = tokens.shape[0]
    with _scope("embed"):
        x = _embed_rows(params["embed"], tokens, cfg.dtype)      # [S, D]

    layers = params["layers"]

    def period_fn(carry, p):
        x, ck, cv = carry
        for j in range(3):
            li = 3 * p + j
            w = _layer(layers, _lin_names(layers), li, 3)
            e = _layer(layers, _EVERY, 4 * p + j, 4)
            with _scope("layer/attn_proj/linear"):
                pre, gate, g, beta = _linear_proj(x, w, cfg)
            with _scope("layer/linear_attn"):
                old = jax.lax.dynamic_index_in_dim(ck["conv"], li, 0, False)
                win = jnp.concatenate([old, pre[:, None]], axis=1)
                act = jax.nn.silu(jnp.sum(
                    win.astype(jnp.float32)
                    * w["lin_conv"].astype(jnp.float32)[None], axis=1))
                new = jnp.where(active[:, None, None], win[:, 1:], old)
                ck = dict(ck, conv=jax.lax.dynamic_update_index_in_dim(
                    ck["conv"], new, li, 0))
                q, k, v = _split_heads(act, cfg)
                o, delta = gated_delta_decode(cfg, ck["delta"], li, q, k, v,
                                              g, beta, active)
                ck = dict(ck, delta=delta)
            with _scope("layer/attn_proj/linear"):
                x = _post(x, _linear_out(o, gate, w, cfg),
                          e["mixer_norm"], cfg.rms_norm_eps)
            x = _mlp_block(x[:, None], e, cfg)[:, 0]
        layer = _layer(layers, _FULL, p, 1)
        e = _layer(layers, _EVERY, 4 * p + 3, 4)
        with _scope("layer/attn_proj"):
            q, k, v = _full_qkv(x, layer, cfg)
        with _scope("layer/attn"):
            attn, ck, cv = llama._decode_attend_write(
                q, k, v, ck, cv, p, lengths, cfg.attn_cfg)
        with _scope("layer/attn_proj"):
            attn = attn[:, :cfg.num_heads].reshape(S, -1)
            x = _post(x, attn @ _mat(layer["wo"], x.dtype),
                      e["mixer_norm"], cfg.rms_norm_eps)
        x = _mlp_block(x[:, None], e, cfg)[:, 0]
        return x, ck, cv

    x, cache_k, cache_v = scan_periods(cfg, period_fn,
                                        (x, cache_k, cache_v))
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with _scope("lm_head"):
        logits = unembed(x, params, cfg)
    return logits, cache_k, cache_v


def gated_delta_decode(cfg, delta, li, q, k, v, g, beta, active):
    """The one-token update on the stacked state: the Pallas kernel where
    ``cfg.attn`` says kernels run (a TPU, no mesh), jax.numpy elsewhere."""
    if llama._target(cfg).pallas and delta.dtype == jnp.float32:
        from localai_tpu.ops.pallas.gated_delta import (
            gated_delta_decode_pallas)

        return gated_delta_decode_pallas(delta, li, q, k, v, g, beta, active)
    return gated_delta.gated_delta_decode(delta, li, q, k, v, g, beta, active)


def engine_decode(params, cfg, tokens, lengths, active, cache_k, cache_v,
                  pos_offset=None):
    """Engine adapter (the contract of models/llama.py and mamba.py): an
    inactive slot writes no K/V row (its position is forced to C, which
    the scatter drops) and keeps its recurrent state. ``pos_offset``
    belongs to self-extend, which this family does not declare."""
    del pos_offset
    C = kvcache.shape(cache_k)[2]
    return decode_step(params, cfg, tokens, jnp.where(active, lengths, C),
                       active, cache_k, cache_v)


def ragged_prefill(params, cfg: OlmoHybridConfig, tokens, positions, seg_of,
                   seg_slots, seg_start, seg_off, seg_len, cache_k, cache_v,
                   continued: bool = False, rope_positions=None,
                   comm_overlap: bool = False):
    """Packed prefill on models/llama.py::ragged_prefill's contract (its
    docstring has the arguments). The full layers attend and write K/V
    rows exactly as there; a linear layer runs the chunked delta rule
    over the pack's segments, each from zero state when it starts at
    position 0 and from its slot's otherwise, and leaves its final state
    and convolution tail in the slot. Pad segments (slot sentinel) write
    nothing. ``comm_overlap`` is for a mesh, which this family refuses."""
    assert rope_positions is None, "self-extend is not declared"
    del comm_overlap
    N = tokens.shape[0]
    B = seg_slots.shape[0]
    S = cache_k["delta"].shape[1]
    W1 = cfg.conv_kernel - 1
    with _scope("embed"):
        x = _embed_rows(params["embed"], tokens, cfg.dtype)      # [N, D]
    seg = jnp.minimum(seg_of, B - 1)
    slot_of = jnp.take(seg_slots, seg)
    real = seg_of < B
    j = jnp.where(real, jnp.arange(N, dtype=jnp.int32)
                  - jnp.take(seg_off, seg), 0)               # index in segment
    plan = gated_delta.chunk_plan(seg_off, seg_len, N)
    slots_c = jnp.minimum(seg_slots, S - 1)
    fresh = seg_start == 0

    def linear_attn(pre, g, beta, w, ck, li):
        f32 = jnp.float32
        if continued:
            conv0 = jnp.where(fresh[:, None, None], 0, jnp.take(
                jax.lax.dynamic_index_in_dim(ck["conv"], li, 0, False),
                slots_c, axis=0))
            s0 = jnp.where(fresh[:, None, None, None], 0, jnp.take(
                jax.lax.dynamic_index_in_dim(ck["delta"], li, 0, False),
                slots_c, axis=0))
        else:
            conv0 = jnp.zeros((B, W1, cfg.conv_channels), pre.dtype)
            s0 = jnp.zeros((B,) + ck["delta"].shape[2:], f32)
        acc = packed_conv(pre, conv0, w["lin_conv"].astype(f32), seg, j)
        q, k, v = _split_heads(jax.nn.silu(acc), cfg)
        with _scope("gated_delta_chunk"):
            o, finals = gated_delta.gated_delta_chunk(q, k, v, g, beta, s0,
                                                      plan)
        tail = new_tails(pre, conv0, seg_off, seg_len)      # [B, 3, Ch]
        ck = dict(ck,
                  conv=ck["conv"].at[li, seg_slots].set(
                      tail.astype(ck["conv"].dtype), mode="drop"),
                  delta=ck["delta"].at[li, seg_slots].set(
                      finals.astype(ck["delta"].dtype), mode="drop"))
        return o, ck

    layers = params["layers"]

    def period_fn(carry, p):
        x, ck, cv = carry
        for jj in range(3):
            w = _layer(layers, _lin_names(layers), 3 * p + jj, 3)
            e = _layer(layers, _EVERY, 4 * p + jj, 4)
            with _scope("layer/attn_proj/linear"):
                pre, gate, g, beta = _linear_proj(x, w, cfg)
            with _scope("layer/linear_attn"):
                o, ck = linear_attn(pre, g, beta, w, ck, 3 * p + jj)
            with _scope("layer/attn_proj/linear"):
                x = _post(x, _linear_out(o, gate, w, cfg),
                          e["mixer_norm"], cfg.rms_norm_eps)
            x = _mlp_block(x[None], e, cfg)[0]
        layer = _layer(layers, _FULL, p, 1)
        e = _layer(layers, _EVERY, 4 * p + 3, 4)
        with _scope("layer/attn_proj"):
            q, k, v = _full_qkv(x, layer, cfg)
        with _scope("layer/attn"):
            attn, ck, cv = llama.ragged_attend_write(
                cfg.attn_cfg, q, k, v, ck, cv, p, seg_of, seg_slots,
                seg_start, seg_off, seg_len, slot_of, positions, continued)
        with _scope("layer/attn_proj"):
            attn = attn[:, :cfg.num_heads].reshape(N, -1)
            x = _post(x, attn @ _mat(layer["wo"], x.dtype),
                      e["mixer_norm"], cfg.rms_norm_eps)
        x = _mlp_block(x[None], e, cfg)[0]
        return x, ck, cv

    x, cache_k, cache_v = scan_periods(cfg, period_fn,
                                        (x, cache_k, cache_v))
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with _scope("lm_head"):
        last = jnp.maximum(seg_off + seg_len - 1, 0)
        logits = unembed(jnp.take(x, last, axis=0), params, cfg)
    return logits, cache_k, cache_v


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
