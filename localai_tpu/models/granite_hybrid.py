"""Granite-4.0-H decoder (``model_type: granitemoehybrid``, dense siblings):
Mamba-2 state-space layers beside grouped-query attention layers with no
positional encoding, nine of the first to one of the second
(``layer_types``), every one followed by a SwiGLU MLP.

Two kinds of layer, two kinds of state in one slot:

  * an ATTENTION layer is causal softmax attention over H query heads and
    KV key/value heads, no bias, no rotary, with the scale
    ``attention_multiplier`` in place of ``head_dim ** -0.5``; its K/V rows
    live in the paged pool of ops/kvcache.py and go through the same
    kernels as models/llama.py's (``ragged_attend_write``,
    ``_decode_attend_write``). The pool holds each head padded with zeros
    to a multiple of 128 (``pool_head_dim``; 64 -> 128 as published): the
    TPU's own layout for a pool whose minor axis is 64 puts the PAGE axis
    minor instead, and the attention kernels, which want ``[page, KV, hd]``
    row-major, then get a transposed copy of the whole pool into and out of
    every program (two 288 MB copies a pool at the benchmark's size; in
    memory the row-major tiles pad 64 lanes to 128 either way). The kernels
    compute ``hd ** -0.5`` of the width they see, so q is scaled by
    ``attention_multiplier * pool_head_dim ** 0.5`` on its way in;
  * a MAMBA layer (Mamba-2, one group) keeps, per slot, a recurrent state
    ``ssm`` [Hs, P, N] float32 and the last three inputs of its width-4
    causal convolution ``conv`` [3, Hs P + 2 N] (ops/ssd.py has the rule).

The cache is the paged pytree with two more leaves on ``cache_k``:

    cache_k = {"pages": [L_attn, n_pages, page, KV, hd], "ptab": [S, MP],
               "ssm": [L_ssm, S, Hs, P, N] f32, "conv": [L_ssm, S, 3, Ch]}
    cache_v = {"pages": ..., "ptab": ...}

with the rules of models/olmo_hybrid.py: a prefill segment that starts at
position 0 starts from a zero state whatever the slot held, a continued one
from the slot's; an inactive slot's state is untouched by a decode step
(the decode kernel does not read it either: ops/pallas/mamba2_decode.py).

Blocks are PRE-norm with muP multipliers: ``x = E[token] *
embedding_multiplier``; ``x += residual_multiplier * mixer(norm(x))``;
``x += residual_multiplier * mlp(norm(x))``; ``logits = head(norm(x)) /
logits_scaling``, the head tied to the embedding.

The layer scan runs over PERIODS of ``layer_types`` (the shortest prefix
whose repetition gives the list: ten layers as published, ``mamba`` x5,
``attention``, ``mamba`` x4) and, inside a period, over each run of
layers of one kind (``_scan_layers``); weights are stacked ``[periods, n, ...]``
with n the layers of that kind in a period (``ssm_*``: mamba layers; ``wq``
.. ``wo``: attention layers; the two norms and the MLP: every layer).

What the engine may do with this family is ``CAPABILITIES``: paged KV and
packed prefill, for models/olmo_hybrid.py's reasons (ROADMAP.md M2). A
config with routed experts (``num_local_experts > 0``: the H-Tiny and
H-Small siblings) is refused: no routed expert layer is built here.
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
from localai_tpu.ops import kvcache, ssd
from localai_tpu.ops.norms import rms_norm

CAPABILITIES = frozenset({"paged", "packed_prefill"})

_scope = jax.named_scope


def _period(kinds: Tuple[str, ...]) -> Tuple[str, ...]:
    """The shortest prefix of ``kinds`` whose repetition gives ``kinds``."""
    L = len(kinds)
    for p in range(1, L + 1):
        if L % p == 0 and kinds[:p] * (L // p) == kinds:
            return kinds[:p]
    return kinds


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192          # shared_intermediate_size
    num_layers: int = 40
    period: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    conv_kernel: int = 4
    ssm_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
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
    def periods(self) -> int:
        return self.num_layers // len(self.period)

    @property
    def ssm_per_period(self) -> int:
        return self.period.count("mamba")

    @property
    def attn_per_period(self) -> int:
        return self.period.count("attention")

    @property
    def ssm_layers(self) -> int:
        return self.periods * self.ssm_per_period

    @property
    def attn_layers(self) -> int:
        return self.periods * self.attn_per_period

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_state

    @property
    def pool_head_dim(self) -> int:
        """A head's width as the page pool holds it: a multiple of 128
        (the module doc says why); the extra columns of q, k and v are
        zero."""
        return -(-self.head_dim_ // 128) * 128

    @property
    def attn_cfg(self) -> "GraniteHybridConfig":
        """This config as models/llama.py's attention helpers read it:
        heads of ``pool_head_dim``."""
        return dataclasses.replace(self, head_dim=self.pool_head_dim)

    @property
    def q_scale(self) -> float:
        """What q is multiplied by so that the attention kernels' own
        ``pool_head_dim ** -0.5`` comes out as ``attention_multiplier``."""
        return self.attention_multiplier * self.pool_head_dim ** 0.5

    @staticmethod
    def from_hf_config(cfg: dict, dtype=jnp.bfloat16) -> "GraniteHybridConfig":
        L = cfg["num_hidden_layers"]
        if cfg.get("num_local_experts", 0) or cfg.get("num_experts_per_tok", 0):
            raise ValueError(
                "granitemoehybrid: num_local_experts = "
                f"{cfg.get('num_local_experts')} asks for a routed expert "
                "(mixture-of-experts) feed-forward, which is not built for "
                "this family: only the dense siblings (num_local_experts 0, "
                "the shared MLP alone) are served (the layer such a sibling "
                "would call is ops/moe.py)")
        kinds = tuple(cfg["layer_types"])[:L]
        if len(kinds) != L or set(kinds) - {"mamba", "attention"}:
            raise ValueError("granitemoehybrid: layer_types must name "
                             f"'mamba' or 'attention' for each of {L} "
                             f"layers; got {kinds}")
        if len(set(kinds)) != 2:
            raise ValueError("granitemoehybrid: layer_types must hold both "
                             f"kinds of layer; got {kinds}")
        if cfg.get("mamba_n_groups", 1) != 1:
            raise ValueError("granitemoehybrid: mamba_n_groups != 1 is not "
                             "built (B and C are shared by every head)")
        if cfg.get("position_embedding_type", "nope") != "nope":
            raise ValueError("granitemoehybrid: position_embedding_type "
                             f"{cfg['position_embedding_type']!r}; the "
                             "attention layers here apply none ('nope')")
        if cfg.get("mamba_proj_bias") or cfg.get("attention_bias"):
            raise ValueError("granitemoehybrid: projection biases are not "
                             "built (mamba_proj_bias, attention_bias)")
        if not cfg.get("mamba_conv_bias", True):
            raise ValueError("granitemoehybrid: a convolution without bias "
                             "is not built (mamba_conv_bias false)")
        Hs, P_ = cfg["mamba_n_heads"], cfg["mamba_d_head"]
        if Hs * P_ != cfg["mamba_expand"] * cfg["hidden_size"]:
            raise ValueError("granitemoehybrid: mamba_n_heads * mamba_d_head "
                             "!= mamba_expand * hidden_size")
        return GraniteHybridConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg.get("shared_intermediate_size",
                                      cfg.get("intermediate_size")),
            num_layers=L, period=_period(kinds),
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads",
                                 cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            ssm_heads=Hs, ssm_head_dim=P_, ssm_state=cfg["mamba_d_state"],
            conv_kernel=cfg["mamba_d_conv"],
            ssm_chunk=cfg.get("mamba_chunk_size", 256),
            embedding_multiplier=float(cfg.get("embedding_multiplier", 1.0)),
            residual_multiplier=float(cfg.get("residual_multiplier", 1.0)),
            attention_multiplier=float(cfg["attention_multiplier"]),
            logits_scaling=float(cfg.get("logits_scaling", 1.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            dtype=dtype)


def load_hf_params(model_dir: str, cfg: GraniteHybridConfig,
                   dtype=jnp.bfloat16, quantize: str = "",
                   tracer=None) -> dict:
    """The adapter contract's loader (backend/runner.py); the leaves, the
    cast and the int8 path are engine/weights.py's, shared with llama."""
    from localai_tpu.engine import weights

    return weights.load_granite_hybrid_params(
        model_dir, cfg, dtype=dtype, quantize=quantize, tracer=tracer)


def init_cache(cfg: GraniteHybridConfig, num_slots: int, max_len: int,
               dtype=None, page_size: int = 0, num_pages: int = 0,
               state_dtype=jnp.float32):
    """(cache_k, cache_v) as in the module doc. Only the attention layers
    have rows in the page pool; ``state_dtype`` is float32 unless a test or
    the benchmark's control asks what a lower precision would do."""
    if not page_size:
        raise ValueError("granite_hybrid serves on the paged KV layout only "
                         "(kv_layout=contiguous and lockstep are refused)")
    if kvcache.wants_quant(dtype or cfg.dtype):
        raise ValueError("granite_hybrid: an int8 KV cache is not built")
    shape = (cfg.attn_layers, num_slots, max_len, cfg.num_kv_heads,
             cfg.pool_head_dim)
    ck = kvcache.init_paged(shape, dtype or cfg.dtype, page_size, num_pages)
    cv = kvcache.init_paged(shape, dtype or cfg.dtype, page_size, num_pages)
    ck["ssm"] = jnp.zeros(
        (cfg.ssm_layers, num_slots, cfg.ssm_heads, cfg.ssm_head_dim,
         cfg.ssm_state), state_dtype)
    ck["conv"] = jnp.zeros(
        (cfg.ssm_layers, num_slots, cfg.conv_kernel - 1, cfg.conv_channels),
        cfg.dtype)
    return ck, cv


def init_params(cfg: GraniteHybridConfig, key: jax.Array, dtype=None) -> dict:
    """Random parameters in the stacked layout (tests)."""
    dtype = dtype or cfg.dtype
    P_, D, F = cfg.periods, cfg.hidden_size, cfg.intermediate_size
    nm, na, n = cfg.ssm_per_period, cfg.attn_per_period, len(cfg.period)
    H, KVd = cfg.num_heads * cfg.head_dim_, cfg.num_kv_heads * cfg.head_dim_
    Hs, Di, Ch = cfg.ssm_heads, cfg.d_inner, cfg.conv_channels
    ks = iter(jax.random.split(key, 24))

    def init(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    params = {
        "embed": init((cfg.vocab_size, D), 1.0),
        "final_norm": jnp.ones((D,), dtype),
        "layers": {
            "ssm_in_z": init((P_, nm, D, Di), D),
            "ssm_in_xbc": init((P_, nm, D, Ch), D),
            "ssm_in_dt": init((P_, nm, D, Hs), D),
            "ssm_out": init((P_, nm, Di, D), Di),
            "ssm_conv": init((P_, nm, cfg.conv_kernel, Ch), cfg.conv_kernel),
            "ssm_conv_b": init((P_, nm, Ch), 4.0),
            "ssm_A_log": jnp.log(uniform((P_, nm, Hs), 1.0, 16.0)),
            "ssm_D": jnp.ones((P_, nm, Hs), jnp.float32),
            "ssm_dt_bias": uniform((P_, nm, Hs), -4.0, -2.0),
            "ssm_norm": jnp.ones((P_, nm, Di), dtype),
            "wq": init((P_, na, D, H), D), "wk": init((P_, na, D, KVd), D),
            "wv": init((P_, na, D, KVd), D), "wo": init((P_, na, H, D), H),
            "attn_norm": jnp.ones((P_, n, D), dtype),
            "mlp_norm": jnp.ones((P_, n, D), dtype),
            "w_gate": init((P_, n, D, F), D), "w_up": init((P_, n, D, F), D),
            "w_down": init((P_, n, F, D), F),
        },
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init((D, cfg.vocab_size), D)
    return params


_ATTN = ("wq", "wk", "wv", "wo")
_EVERY = ("attn_norm", "mlp_norm", "w_gate", "w_up", "w_down")


def _layer(layers: dict, names, i) -> dict:
    """Layer ``i`` (traced) of the leaves ``names``, stacked ``[periods,
    n, ...]``: ONE dynamic index a leaf into the flattened leading axes
    (models/olmo_hybrid.py::_layer says why). A {q, s} int8 leaf is
    indexed leaf by leaf."""
    def one(a):
        return jax.lax.dynamic_index_in_dim(
            a.reshape((-1,) + a.shape[2:]), i, 0, keepdims=False)
    return {k: jax.tree.map(one, layers[k]) for k in names}


def _ssm_names(layers: dict):
    return [k for k in layers if k.startswith("ssm_")]


def _mlp_block(x, e, cfg):
    """x + residual_multiplier * mlp(norm(x)). x [B, T, D]."""
    with _scope("layer/mlp"):
        h = rms_norm(x, e["mlp_norm"], cfg.rms_norm_eps)
        return _residual(x, _mlp(h, e), cfg)


def _residual(x, y, cfg):
    """x + residual_multiplier * y, the product in float32: 0.22 is not a
    bfloat16 number, and rounding it first would scale every branch of
    every layer by the same 0.1%."""
    return x + (y.astype(jnp.float32)
                * cfg.residual_multiplier).astype(x.dtype)


def _ssm_proj(x, w, e, cfg):
    """The projections of a mamba layer from x [N, D]: the input norm, then
    the gate z [N, Di], the convolution's input [N, Ch] (x | B | C
    channels) and the raw step [N, Hs] float32."""
    dt = x.dtype
    h = rms_norm(x, e["attn_norm"], cfg.rms_norm_eps)
    return (h @ _mat(w["ssm_in_z"], dt), h @ _mat(w["ssm_in_xbc"], dt),
            (h @ _mat(w["ssm_in_dt"], dt)).astype(jnp.float32))


def _ssm_inputs(act, dtr, w, cfg):
    """Convolved, SiLU'd channels [N, Ch] float32 and the raw step ->
    x [N, Hs, P], B, C [N, Ns], dt, la [N, Hs] (la the log decay)."""
    f32 = jnp.float32
    Di, Ns = cfg.d_inner, cfg.ssm_state
    xs = act[:, :Di].reshape(-1, cfg.ssm_heads, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dtr + w["ssm_dt_bias"].astype(f32))
    la = -jnp.exp(w["ssm_A_log"].astype(f32)) * dt
    return xs, act[:, Di:Di + Ns], act[:, Di + Ns:], dt, la


def _ssm_out(y, xs, z, w, cfg):
    """W_out [RMSNorm(y * SiLU(z))] with the skip term ``D x`` added to y
    first: the gate is applied BEFORE the norm, which runs over all of
    ``d_inner``. -> [N, D] in the model's dtype."""
    f32 = jnp.float32
    y = y + w["ssm_D"].astype(f32)[None, :, None] * xs
    y = y.reshape(y.shape[0], -1) * jax.nn.silu(z.astype(f32))
    y = rms_norm(y, w["ssm_norm"], cfg.rms_norm_eps)
    return y.astype(cfg.dtype) @ _mat(w["ssm_out"], cfg.dtype)


def _attn_qkv(x, layer, e, cfg):
    """x [N, D] -> q [N, H, hdp] (scaled: ``q_scale``), k, v [N, KV, hdp]
    with hdp = ``pool_head_dim`` (zero columns past ``head_dim``); the
    input norm first, no bias, no rotary."""
    dt, hd, n = x.dtype, cfg.head_dim_, x.shape[0]
    h = rms_norm(x, e["attn_norm"], cfg.rms_norm_eps)
    q = ((h @ _mat(layer["wq"], dt)).astype(jnp.float32)
         * cfg.q_scale).astype(dt)
    k = h @ _mat(layer["wk"], dt)
    v = h @ _mat(layer["wv"], dt)

    def heads(a, n_heads):
        return jnp.pad(a.reshape(n, n_heads, hd),
                       ((0, 0), (0, 0), (0, cfg.pool_head_dim - hd)))

    return (heads(q, cfg.num_heads), heads(k, cfg.num_kv_heads),
            heads(v, cfg.num_kv_heads))


def _attn_out(attn, layer, cfg):
    """attn [N, H, hdp] -> W_o of its first ``head_dim`` columns a head."""
    a = attn[:, :, :cfg.head_dim_].reshape(attn.shape[0], -1)
    return a @ _mat(layer["wo"], a.dtype)


def _head(x, params, cfg):
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with _scope("lm_head"):
        return unembed(x, params, cfg) / cfg.logits_scaling


def _embed(params, tokens, cfg):
    with _scope("embed"):
        rows = _embed_rows(params["embed"], tokens, cfg.dtype)
        return (rows.astype(jnp.float32)
                * cfg.embedding_multiplier).astype(cfg.dtype)


def _scan_layers(cfg, carry, layer_fns):
    """The layers over ``carry``: a scan over the periods and, inside one,
    over each run of layers of one kind (as published: five mamba layers,
    the attention layer inline, four mamba layers).
    ``layer_fns[kind](carry, ki, i)`` runs layer ``i`` (traced), the
    ``ki``-th of its kind."""
    return scan_layer_runs(cfg.period * cfg.periods, carry, layer_fns)


def mamba2_decode(cfg, state, li, xs, dt, la, B, C, active):
    """The one-token update on the stacked state: the Pallas kernel where
    ``cfg.attn`` says kernels run (a TPU, no mesh), jax.numpy elsewhere."""
    if llama._target(cfg).pallas and state.dtype == jnp.float32:
        from localai_tpu.ops.pallas.mamba2_decode import mamba2_decode_pallas

        return mamba2_decode_pallas(state, li, xs, dt, la, B, C, active)
    return ssd.ssd_decode(state, li, xs, dt, la, B, C, active)


def decode_step(params, cfg: GraniteHybridConfig, tokens, lengths, active,
                cache_k, cache_v):
    """One decode step for all slots. tokens [S]; ``lengths`` the position
    each slot's new K/V row is written at (C for an inactive slot: the
    write drops); ``active`` [S] gates the recurrent state.
    -> (logits [S, V], cache_k, cache_v)."""
    f32 = jnp.float32
    x = _embed(params, tokens, cfg)                              # [S, D]
    layers = params["layers"]

    def mamba_layer(carry, ki, i):
        x, ck, cv = carry
        e = _layer(layers, _EVERY, i)
        w = _layer(layers, _ssm_names(layers), ki)
        with _scope("layer/attn_proj/ssm"):
            z, pre, dtr = _ssm_proj(x, w, e, cfg)
        with _scope("layer/ssm"):
            old = jax.lax.dynamic_index_in_dim(ck["conv"], ki, 0, False)
            win = jnp.concatenate([old, pre[:, None]], axis=1)
            act = jax.nn.silu(
                jnp.sum(win.astype(f32) * w["ssm_conv"].astype(f32)[None],
                        axis=1) + w["ssm_conv_b"].astype(f32)[None])
            new = jnp.where(active[:, None, None], win[:, 1:], old)
            ck = dict(ck, conv=jax.lax.dynamic_update_index_in_dim(
                ck["conv"], new, ki, 0))
            xs, B, C, dt, la = _ssm_inputs(act, dtr, w, cfg)
            y, state = mamba2_decode(cfg, ck["ssm"], ki, xs, dt, la, B, C,
                                     active)
            ck = dict(ck, ssm=state)
        with _scope("layer/attn_proj/ssm"):
            x = _residual(x, _ssm_out(y, xs, z, w, cfg), cfg)
        return _mlp_block(x[:, None], e, cfg)[:, 0], ck, cv

    def attn_layer(carry, ki, i):
        x, ck, cv = carry
        e = _layer(layers, _EVERY, i)
        layer = _layer(layers, _ATTN, ki)
        with _scope("layer/attn_proj"):
            q, k, v = _attn_qkv(x, layer, e, cfg)
        with _scope("layer/attn"):
            attn, ck, cv = llama._decode_attend_write(
                q, k, v, ck, cv, ki, lengths, cfg.attn_cfg)
        with _scope("layer/attn_proj"):
            x = _residual(x, _attn_out(attn, layer, cfg), cfg)
        return _mlp_block(x[:, None], e, cfg)[:, 0], ck, cv

    x, cache_k, cache_v = _scan_layers(
        cfg, (x, cache_k, cache_v),
        {"mamba": mamba_layer, "attention": attn_layer})
    return _head(x, params, cfg), cache_k, cache_v


def engine_decode(params, cfg, tokens, lengths, active, cache_k, cache_v,
                  pos_offset=None):
    """Engine adapter (the contract of models/llama.py and olmo_hybrid.py):
    an inactive slot writes no K/V row (its position is forced to C, which
    the scatter drops) and keeps its recurrent state. ``pos_offset``
    belongs to self-extend, which this family does not declare."""
    del pos_offset
    C = kvcache.shape(cache_k)[2]
    return decode_step(params, cfg, tokens, jnp.where(active, lengths, C),
                       active, cache_k, cache_v)


def ragged_prefill(params, cfg: GraniteHybridConfig, tokens, positions,
                   seg_of, seg_slots, seg_start, seg_off, seg_len, cache_k,
                   cache_v, continued: bool = False, rope_positions=None,
                   comm_overlap: bool = False):
    """Packed prefill on models/llama.py::ragged_prefill's contract (its
    docstring has the arguments). The attention layers attend and write
    K/V rows exactly as there; a mamba layer runs the chunked state-space
    dual over the pack's segments (ops/ssd.py), each from zero state when
    it starts at position 0 and from its slot's otherwise, and leaves its
    final state and convolution tail in the slot. Pad segments (slot
    sentinel) write nothing. ``comm_overlap`` is for a mesh, which this
    family refuses."""
    assert rope_positions is None, "self-extend is not declared"
    del comm_overlap
    f32 = jnp.float32
    N = tokens.shape[0]
    B = seg_slots.shape[0]
    S = cache_k["ssm"].shape[1]
    W1 = cfg.conv_kernel - 1
    x = _embed(params, tokens, cfg)                              # [N, D]
    seg = jnp.minimum(seg_of, B - 1)
    slot_of = jnp.take(seg_slots, seg)
    real = seg_of < B
    j = jnp.where(real, jnp.arange(N, dtype=jnp.int32)
                  - jnp.take(seg_off, seg), 0)               # index in segment
    slots_c = jnp.minimum(seg_slots, S - 1)
    fresh = seg_start == 0

    def ssm(pre, dtr, w, ck, ki):
        if continued:
            conv0 = jnp.where(fresh[:, None, None], 0, jnp.take(
                jax.lax.dynamic_index_in_dim(ck["conv"], ki, 0, False),
                slots_c, axis=0))
            s0 = jnp.where(fresh[:, None, None, None], 0, jnp.take(
                jax.lax.dynamic_index_in_dim(ck["ssm"], ki, 0, False),
                slots_c, axis=0))
        else:
            conv0 = jnp.zeros((B, W1, cfg.conv_channels), pre.dtype)
            s0 = jnp.zeros((B,) + ck["ssm"].shape[2:], f32)
        acc = packed_conv(pre, conv0, w["ssm_conv"].astype(f32), seg, j) \
            + w["ssm_conv_b"].astype(f32)[None]
        xs, Bm, Cm, dt, la = _ssm_inputs(jax.nn.silu(acc), dtr, w, cfg)
        with _scope("ssd_chunk"):
            y, finals = ssd.ssd_chunk(xs, dt, la, Bm, Cm, s0, seg_off,
                                      seg_len, chunk=cfg.ssm_chunk)
        tail = new_tails(pre, conv0, seg_off, seg_len)      # [B, 3, Ch]
        ck = dict(ck,
                  conv=ck["conv"].at[ki, seg_slots].set(
                      tail.astype(ck["conv"].dtype), mode="drop"),
                  ssm=ck["ssm"].at[ki, seg_slots].set(
                      finals.astype(ck["ssm"].dtype), mode="drop"))
        return y, xs, ck

    layers = params["layers"]

    def mamba_layer(carry, ki, i):
        x, ck, cv = carry
        e = _layer(layers, _EVERY, i)
        w = _layer(layers, _ssm_names(layers), ki)
        with _scope("layer/attn_proj/ssm"):
            z, pre, dtr = _ssm_proj(x, w, e, cfg)
        with _scope("layer/ssm"):
            y, xs, ck = ssm(pre, dtr, w, ck, ki)
        with _scope("layer/attn_proj/ssm"):
            x = _residual(x, _ssm_out(y, xs, z, w, cfg), cfg)
        return _mlp_block(x[None], e, cfg)[0], ck, cv

    def attn_layer(carry, ki, i):
        x, ck, cv = carry
        e = _layer(layers, _EVERY, i)
        layer = _layer(layers, _ATTN, ki)
        with _scope("layer/attn_proj"):
            q, k, v = _attn_qkv(x, layer, e, cfg)
        with _scope("layer/attn"):
            attn, ck, cv = llama.ragged_attend_write(
                cfg.attn_cfg, q, k, v, ck, cv, ki, seg_of, seg_slots,
                seg_start, seg_off, seg_len, slot_of, positions, continued)
        with _scope("layer/attn_proj"):
            x = _residual(x, _attn_out(attn, layer, cfg), cfg)
        return _mlp_block(x[None], e, cfg)[0], ck, cv

    x, cache_k, cache_v = _scan_layers(
        cfg, (x, cache_k, cache_v),
        {"mamba": mamba_layer, "attention": attn_layer})
    last = jnp.maximum(seg_off + seg_len - 1, 0)
    return _head(jnp.take(x, last, axis=0), params, cfg), cache_k, cache_v


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
