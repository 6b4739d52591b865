"""RWKV v4 LM — the third LLM family through the unchanged serving engine.

Replaces the reference's RWKV backend
(/root/reference/backend/go/llm/rwkv/rwkv.go:1-95 — a cgo wrapper over
rwkv.cpp) with a TPU-native port of the HF ``RwkvForCausalLM`` layout.
Like Mamba, RWKV is TPU-flattering: generation state is FIXED-SIZE per
sequence (per layer: a token-shift vector for each of the two mixers plus
the wkv numerator/denominator/max accumulators — no KV cache growing
with context), so it rides the engine's (cache_k, cache_v) lanes via the
same family-adapter contract as models/mamba.py:

  init_cache(cfg, S, C, dtype)  -> (att_state [L,S,4,D], ffn_state [L,S,1,D])
  engine_decode(params, cfg, tokens, lengths, active, ck, cv, pos_offset)
  prefill(params, cfg, tokens, seq_lens, ck, cv, slot_ids, start_pos, ...)

att_state lanes: [prev_x, wkv_num, wkv_den, wkv_max]; a FRESH sequence
starts from zeros except wkv_max = -1e38 (the HF init), handled by the
fresh-row masking in prefill. The wkv recurrence uses the max-state
stabilized form (exactly HF modeling_rwkv.rwkv_linear_attention_cpu) so
torch parity is bit-for-bit testable.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# what the engine may do with this family (engine.py, Engine.__init__): a
# fixed-size state in the cache lanes on the contiguous layout, no more
CAPABILITIES = frozenset({"mesh"})

_MAX_INIT = -1e38


@dataclasses.dataclass(frozen=True)
class RwkvConfig:
    vocab_size: int = 50277
    hidden_size: int = 768
    num_layers: int = 12
    attention_hidden_size: int = 768   # == hidden_size for v4
    intermediate_size: int = 3072      # 4 * hidden_size default
    layer_norm_epsilon: float = 1e-5
    rescale_every: int = 6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.float32

    @property
    def max_position_embeddings(self) -> int:
        # no positional encoding; context bounded by engine accounting
        return 1 << 20

    @property
    def d_inner(self) -> int:
        # sharding-axis analogue used by generic family plumbing
        return self.attention_hidden_size

    @staticmethod
    def from_hf_config(c: dict, dtype=jnp.float32) -> "RwkvConfig":
        hs = c.get("hidden_size", 768)
        return RwkvConfig(
            vocab_size=c.get("vocab_size", 50277),
            hidden_size=hs,
            num_layers=c.get("num_hidden_layers", 12),
            attention_hidden_size=c.get("attention_hidden_size", hs) or hs,
            intermediate_size=c.get("intermediate_size", 4 * hs) or 4 * hs,
            layer_norm_epsilon=c.get("layer_norm_epsilon", 1e-5),
            rescale_every=c.get("rescale_every", 6),
            tie_word_embeddings=c.get("tie_word_embeddings", False),
            dtype=dtype,
        )

    @staticmethod
    def from_json(path: str, dtype=jnp.float32) -> "RwkvConfig":
        with open(path) as f:
            return RwkvConfig.from_hf_config(json.load(f), dtype=dtype)


def _ln(x, w, b, eps):
    mu = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
    var = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
    return (((x.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps))
            .astype(x.dtype) * w + b)


# the {q, s} int8 contract is shared by every family — see ops/quant.py
from localai_tpu.ops.quant import mat as _mat  # noqa: E402

QUANT_NAMES = ("att_key", "att_value", "att_receptance", "att_output",
               "ffn_key", "ffn_receptance", "ffn_value")


def quantize_params(params: dict) -> dict:
    """Weight-only per-out-channel int8 for the mixer Linears."""
    from localai_tpu.ops.quant import quantize_weight as q

    out = dict(params)
    out["layers"] = {k: (q(v) if k in QUANT_NAMES else v)
                     for k, v in params["layers"].items()}
    return out


def init_params(cfg: RwkvConfig, key: jax.Array, dtype=None) -> dict:
    dtype = dtype or cfg.dtype
    L, D, A, F = (cfg.num_layers, cfg.hidden_size,
                  cfg.attention_hidden_size, cfg.intermediate_size)
    ks = jax.random.split(key, 12)

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    params = {
        "embed": init(ks[0], (cfg.vocab_size, D), D),
        "pre_ln_w": jnp.ones((D,), dtype), "pre_ln_b": jnp.zeros((D,), dtype),
        "out_ln_w": jnp.ones((D,), dtype), "out_ln_b": jnp.zeros((D,), dtype),
        "head": init(ks[1], (D, cfg.vocab_size), D),
        "layers": {
            "ln1_w": jnp.ones((L, D), dtype), "ln1_b": jnp.zeros((L, D), dtype),
            "ln2_w": jnp.ones((L, D), dtype), "ln2_b": jnp.zeros((L, D), dtype),
            "time_decay": jnp.zeros((L, A), jnp.float32) - 1.0,
            "time_first": jnp.zeros((L, A), jnp.float32),
            "mix_k": jnp.full((L, D), 0.5, dtype),
            "mix_v": jnp.full((L, D), 0.5, dtype),
            "mix_r": jnp.full((L, D), 0.5, dtype),
            "att_key": init(ks[2], (L, D, A), D),
            "att_value": init(ks[3], (L, D, A), D),
            "att_receptance": init(ks[4], (L, D, A), D),
            "att_output": init(ks[5], (L, A, D), A),
            "ffn_mix_k": jnp.full((L, D), 0.5, dtype),
            "ffn_mix_r": jnp.full((L, D), 0.5, dtype),
            "ffn_key": init(ks[6], (L, D, F), D),
            "ffn_receptance": init(ks[7], (L, D, D), D),
            "ffn_value": init(ks[8], (L, F, D), F),
        },
    }
    return params


def load_hf_params(model_dir: str, cfg: RwkvConfig, dtype=jnp.float32,
                   quantize: str = "", tracer=None) -> dict:
    """HF ``RwkvForCausalLM`` safetensors layout.

    HF's ``rescale_every`` machinery (output projections divided by
    2^(i//rescale) AND hidden states halved periodically) is a balanced
    fp16-overflow trick whose net function is identity — this port runs
    the plain arithmetic in fp32/bf16, which is exactly equivalent."""
    from localai_tpu.engine.weights import _open_shards

    shards = _open_shards(model_dir)

    def get(name):
        for pref in ("", "rwkv."):
            if pref + name in shards:
                return np.asarray(shards[pref + name].get_tensor(pref + name))
        raise KeyError(name)

    L = cfg.num_layers
    bl = "blocks.{i}."
    at = bl + "attention."
    ff = bl + "feed_forward."

    def stack(fmt, transpose=False, squeeze=False):
        mats = []
        for i in range(L):
            m = get(fmt.format(i=i))
            if squeeze:
                m = m.reshape(-1)
            if transpose:
                m = m.T
            mats.append(m)
        return jnp.asarray(np.stack(mats), dtype)

    params = {
        "embed": jnp.asarray(get("embeddings.weight"), dtype),
        "pre_ln_w": jnp.asarray(get("blocks.0.pre_ln.weight"), dtype),
        "pre_ln_b": jnp.asarray(get("blocks.0.pre_ln.bias"), dtype),
        "out_ln_w": jnp.asarray(get("ln_out.weight"), dtype),
        "out_ln_b": jnp.asarray(get("ln_out.bias"), dtype),
        "head": jnp.asarray(get("head.weight").T, dtype),
        "layers": {
            "ln1_w": stack(bl + "ln1.weight"),
            "ln1_b": stack(bl + "ln1.bias"),
            "ln2_w": stack(bl + "ln2.weight"),
            "ln2_b": stack(bl + "ln2.bias"),
            "time_decay": jnp.asarray(np.stack(
                [get((at + "time_decay").format(i=i)).reshape(-1)
                 for i in range(L)]), jnp.float32),
            "time_first": jnp.asarray(np.stack(
                [get((at + "time_first").format(i=i)).reshape(-1)
                 for i in range(L)]), jnp.float32),
            "mix_k": stack(at + "time_mix_key", squeeze=True),
            "mix_v": stack(at + "time_mix_value", squeeze=True),
            "mix_r": stack(at + "time_mix_receptance", squeeze=True),
            "att_key": stack(at + "key.weight", transpose=True),
            "att_value": stack(at + "value.weight", transpose=True),
            "att_receptance": stack(at + "receptance.weight", transpose=True),
            "att_output": stack(at + "output.weight", transpose=True),
            "ffn_mix_k": stack(ff + "time_mix_key", squeeze=True),
            "ffn_mix_r": stack(ff + "time_mix_receptance", squeeze=True),
            "ffn_key": stack(ff + "key.weight", transpose=True),
            "ffn_receptance": stack(ff + "receptance.weight", transpose=True),
            "ffn_value": stack(ff + "value.weight", transpose=True),
        },
    }
    if quantize == "int8":
        params = quantize_params(params)
    return params


def init_cache(cfg: RwkvConfig, num_slots: int, max_len: int, dtype=None):
    """Fixed-size per-slot state (fp32 — the wkv accumulators are
    precision-sensitive): att lanes [L, S, 4, D] = [prev_x, num, den, max]
    (max initialized to -1e38, the HF fresh-state value); ffn lane
    [L, S, 1, D] = [prev_x]."""
    L, D = cfg.num_layers, cfg.hidden_size
    att = jnp.zeros((L, num_slots, 4, D), jnp.float32)
    att = att.at[:, :, 3].set(_MAX_INIT)
    ffn = jnp.zeros((L, num_slots, 1, D), jnp.float32)
    return att, ffn


def _fresh_att_state(shape_like):
    fresh = jnp.zeros_like(shape_like)
    return fresh.at[..., 3, :].set(_MAX_INIT)


def _time_mixing(x, st, ly, cfg):
    """x [B, D]; st [B, 4, D] = [prev_x, num, den, max]. Returns
    (out [B, D], st). Exactly HF rwkv_linear_attention_cpu."""
    dt = x.dtype
    prev_x, num, den, mx = (st[:, 0].astype(dt),
                            st[:, 1].astype(jnp.float32),
                            st[:, 2].astype(jnp.float32),
                            st[:, 3].astype(jnp.float32))
    xk = x * ly["mix_k"] + prev_x * (1 - ly["mix_k"])
    xv = x * ly["mix_v"] + prev_x * (1 - ly["mix_v"])
    xr = x * ly["mix_r"] + prev_x * (1 - ly["mix_r"])
    r = jax.nn.sigmoid(xr @ _mat(ly["att_receptance"], dt))
    k = (xk @ _mat(ly["att_key"], dt)).astype(jnp.float32)
    v = (xv @ _mat(ly["att_value"], dt)).astype(jnp.float32)
    u = ly["time_first"].astype(jnp.float32)
    w = -jnp.exp(ly["time_decay"].astype(jnp.float32))
    # output: stabilized (num + e^{u+k} v) / (den + e^{u+k})
    max_out = jnp.maximum(mx, u + k)
    e1 = jnp.exp(mx - max_out)
    e2 = jnp.exp(u + k - max_out)
    wkv = (e1 * num + e2 * v) / (e1 * den + e2)
    # state advance: decay by e^w, absorb current k/v
    max_st = jnp.maximum(mx + w, k)
    e1s = jnp.exp(mx + w - max_st)
    e2s = jnp.exp(k - max_st)
    num = e1s * num + e2s * v
    den = e1s * den + e2s
    out = (r * wkv.astype(dt)) @ _mat(ly["att_output"], dt)
    st = jnp.stack([x.astype(jnp.float32), num, den, max_st], axis=1)
    return out, st


def _channel_mixing(x, st, ly, cfg):
    """x [B, D]; st [B, 1, D] = [prev_x]."""
    dt = x.dtype
    prev_x = st[:, 0].astype(dt)
    xk = x * ly["ffn_mix_k"] + prev_x * (1 - ly["ffn_mix_k"])
    xr = x * ly["ffn_mix_r"] + prev_x * (1 - ly["ffn_mix_r"])
    r = jax.nn.sigmoid(xr @ _mat(ly["ffn_receptance"], dt))
    k = jnp.square(jax.nn.relu(xk @ _mat(ly["ffn_key"], dt)))
    out = r * (k @ _mat(ly["ffn_value"], dt))
    return out, x.astype(jnp.float32)[:, None, :]


def _layer_scan(params, cfg, h, att, ffn, active=None):
    """h [B, D] through all layers; state updates masked where inactive."""

    def layer_fn(carry, inp):
        hc = carry
        ly, att_l, ffn_l = inp
        xa = _ln(hc, ly["ln1_w"], ly["ln1_b"], cfg.layer_norm_epsilon)
        out_a, natt = _time_mixing(xa, att_l, ly, cfg)
        hc = hc + out_a
        xf = _ln(hc, ly["ln2_w"], ly["ln2_b"], cfg.layer_norm_epsilon)
        out_f, nffn = _channel_mixing(xf, ffn_l, ly, cfg)
        hc = hc + out_f
        if active is not None:
            natt = jnp.where(active[:, None, None], natt, att_l)
            nffn = jnp.where(active[:, None, None], nffn, ffn_l)
        return hc, (natt, nffn)

    return jax.lax.scan(layer_fn, h, (dict(params["layers"]), att, ffn))


def _forward_token(params, cfg, tokens, att, ffn, active=None):
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    h = _ln(h, params["pre_ln_w"], params["pre_ln_b"],
            cfg.layer_norm_epsilon)
    h, (att, ffn) = _layer_scan(params, cfg, h, att, ffn, active)
    h = _ln(h, params["out_ln_w"], params["out_ln_b"],
            cfg.layer_norm_epsilon)
    logits = (h.astype(jnp.float32)
              @ _mat(params["head"], jnp.float32).astype(jnp.float32))
    return logits, att, ffn


def engine_decode(params, cfg, tokens, lengths, active, att, ffn,
                  pos_offset=None):
    """Engine adapter: one decode step for all slots (state frozen where
    inactive). lengths/pos_offset unused — no positional encoding."""
    del lengths, pos_offset
    return _forward_token(params, cfg, tokens, att, ffn, active=active)


def prefill(params, cfg, tokens, seq_lens, att, ffn, slot_ids, start_pos,
            continued=False, mm_pos=None, mm_vec=None,
            return_all_logits=False, positions=None):
    """Engine adapter: ingest B prompts. Fresh rows (start_pos == 0)
    reset to the INIT state (zeros + wkv_max = -1e38); continued rows
    resume. Mirrors models/mamba.py:prefill."""
    assert mm_pos is None and positions is None, \
        "multimodal/positions are llama-family features"
    B, T = tokens.shape
    att_rows = jnp.take(att, slot_ids, axis=1)   # [L, B, 4, D]
    ffn_rows = jnp.take(ffn, slot_ids, axis=1)   # [L, B, 1, D]
    fresh = (jnp.asarray(start_pos) == 0)[None, :, None, None]
    att_rows = jnp.where(fresh, _fresh_att_state(att_rows), att_rows)
    ffn_rows = jnp.where(fresh, 0.0, ffn_rows)

    def step(carry, xs_t):
        att_r, ffn_r, last_h = carry
        tok, t = xs_t
        act = t < jnp.asarray(seq_lens)
        h = jnp.take(params["embed"], tok, axis=0).astype(cfg.dtype)
        h = _ln(h, params["pre_ln_w"], params["pre_ln_b"],
                cfg.layer_norm_epsilon)
        h, (att_r, ffn_r) = _layer_scan(params, cfg, h, att_r, ffn_r, act)
        is_last = (t == jnp.asarray(seq_lens) - 1)[:, None]
        last_h = jnp.where(is_last, h, last_h)
        return (att_r, ffn_r, last_h), (h if return_all_logits else None)

    last0 = jnp.zeros((B, cfg.hidden_size), cfg.dtype)
    (att_rows, ffn_rows, last_h), hs = jax.lax.scan(
        step, (att_rows, ffn_rows, last0),
        (jnp.asarray(tokens).T, jnp.arange(T, dtype=jnp.int32)))
    att = att.at[:, slot_ids].set(att_rows)
    ffn = ffn.at[:, slot_ids].set(ffn_rows)

    def head(h):
        h = _ln(h, params["out_ln_w"], params["out_ln_b"],
                cfg.layer_norm_epsilon)
        return (h.astype(jnp.float32)
                @ _mat(params["head"], jnp.float32).astype(jnp.float32))

    if return_all_logits:
        return head(hs.transpose(1, 0, 2)), att, ffn
    return head(last_h), att, ffn
