"""Mamba selective-state-space LM — the second LLM family (VERDICT r3 #9).

Replaces the reference's Mamba backend
(backend/python/mamba/backend.py:1-179, mamba_ssm via torch) with a
TPU-native port of the HF `MambaForCausalLM` layout. Mamba is the
TPU-flattering architecture: generation state is FIXED-SIZE per sequence
(a depthwise-conv window plus a [d_inner, d_state] SSM state — no KV
cache growing with context), and the recurrence is scan-native, so the
serving engine's slot model maps onto it directly: the (conv_state,
ssm_state) pair rides the engine's (cache_k, cache_v) lanes.

Implements the engine adapter contract shared with models/llama.py:
  init_cache(cfg, S, C, dtype)  -> (conv_state, ssm_state)
  engine_decode(params, cfg, tokens, lengths, active, ck, cv, pos_offset)
  prefill(params, cfg, tokens, seq_lens, ck, cv, slot_ids, start_pos, ...)
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


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    vocab_size: int = 50280
    hidden_size: int = 768
    state_size: int = 16
    num_layers: int = 24
    conv_kernel: int = 4
    expand: int = 2
    time_step_rank: int = 48
    layer_norm_epsilon: float = 1e-5
    use_conv_bias: bool = True
    use_bias: bool = False
    tie_word_embeddings: bool = True
    dtype: Any = jnp.float32

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def max_position_embeddings(self) -> int:
        # no positional encoding: context is bounded only by the engine's
        # token accounting (the runner clamps its default to 4096)
        return 1 << 20

    @staticmethod
    def from_hf_config(c: dict, dtype=jnp.float32) -> "MambaConfig":
        hs = c.get("hidden_size", 768)
        tsr = c.get("time_step_rank", "auto")
        if tsr == "auto" or tsr is None:
            tsr = -(-hs // 16)
        return MambaConfig(
            vocab_size=c.get("vocab_size", 50280),
            hidden_size=hs,
            state_size=c.get("state_size", 16),
            num_layers=c.get("num_hidden_layers", c.get("n_layer", 24)),
            conv_kernel=c.get("conv_kernel", 4),
            expand=c.get("expand", 2),
            time_step_rank=int(tsr),
            layer_norm_epsilon=c.get("layer_norm_epsilon", 1e-5),
            use_conv_bias=c.get("use_conv_bias", True),
            use_bias=c.get("use_bias", False),
            tie_word_embeddings=c.get("tie_word_embeddings", True),
            dtype=dtype,
        )

    @staticmethod
    def from_json(path: str, dtype=jnp.float32) -> "MambaConfig":
        with open(path) as f:
            return MambaConfig.from_hf_config(json.load(f), dtype=dtype)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


# the {q, s} int8 contract is shared by every family — see ops/quant.py
from localai_tpu.ops.quant import mat as _mat  # noqa: E402

QUANT_NAMES = ("in_proj_x", "in_proj_z", "x_proj", "dt_proj_w", "out_proj")


def quantize_params(params: dict) -> dict:
    """Weight-only per-out-channel int8 for the mixer projections (the
    bulk of mamba's weights; conv/norm/A/D stay dense — tiny, and the SSM
    recurrence itself is precision-sensitive)."""
    from localai_tpu.ops.quant import quantize_weight as q

    out = dict(params)
    out["layers"] = {k: (q(v) if k in QUANT_NAMES else v)
                     for k, v in params["layers"].items()}
    return out


def init_params(cfg: MambaConfig, key: jax.Array, dtype=None) -> dict:
    dtype = dtype or cfg.dtype
    L, D, Di = cfg.num_layers, cfg.hidden_size, cfg.d_inner
    N, R, K = cfg.state_size, cfg.time_step_rank, cfg.conv_kernel
    ks = jax.random.split(key, 8)

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :],
                         (Di, N))
    params = {
        "embed": init(ks[0], (cfg.vocab_size, D), D),
        "final_norm": jnp.ones((D,), dtype),
        "layers": {
            "norm": jnp.ones((L, D), dtype),
            # HF stores in_proj as one [D, 2*Di] matrix ([x; z] halves);
            # kept SPLIT here so tensor parallelism shards each half's
            # d_inner axis evenly (a contiguous split of the concatenated
            # axis would put all x on some devices and all z on others)
            "in_proj_x": init(ks[1], (L, D, Di), D),
            "in_proj_z": init(ks[7], (L, D, Di), D),
            "conv_w": init(ks[2], (L, Di, K), K),
            "conv_b": jnp.zeros((L, Di), dtype),
            "x_proj": init(ks[3], (L, Di, R + 2 * N), Di),
            "dt_proj_w": init(ks[4], (L, R, Di), R),
            "dt_proj_b": jnp.zeros((L, Di), dtype),
            "A_log": jnp.log(jnp.broadcast_to(A, (L, Di, N))).astype(dtype),
            "D": jnp.ones((L, Di), dtype),
            "out_proj": init(ks[5], (L, Di, D), Di),
        },
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init(ks[6], (D, cfg.vocab_size), D)
    return params


def load_hf_params(model_dir: str, cfg: MambaConfig, dtype=jnp.float32,
                   quantize: str = "", tracer=None) -> dict:
    from localai_tpu.engine.weights import _open_shards

    shards = _open_shards(model_dir)

    def get(name):
        for pref in ("", "backbone."):
            if pref + name in shards:
                return np.asarray(shards[pref + name].get_tensor(pref + name))
        raise KeyError(name)

    L = cfg.num_layers

    def stack(fmt, transpose=False):
        mats = [get(fmt.format(i=i)) for i in range(L)]
        if transpose:
            mats = [m.T for m in mats]
        return jnp.asarray(np.stack(mats), dtype)

    ly = "layers.{i}.mixer."
    in_proj = np.stack([get((ly + "in_proj.weight").format(i=i)).T
                        for i in range(L)])          # [L, D, 2*Di]
    Di = cfg.d_inner
    params = {
        "embed": jnp.asarray(get("embeddings.weight"), dtype),
        "final_norm": jnp.asarray(get("norm_f.weight"), dtype),
        "layers": {
            "norm": stack("layers.{i}.norm.weight"),
            "in_proj_x": jnp.asarray(in_proj[:, :, :Di], dtype),
            "in_proj_z": jnp.asarray(in_proj[:, :, Di:], dtype),
            # conv1d weight [Di, 1, K] -> [Di, K] (depthwise)
            "conv_w": jnp.asarray(np.stack(
                [get((ly + "conv1d.weight").format(i=i))[:, 0, :]
                 for i in range(L)]), dtype),
            "conv_b": stack(ly + "conv1d.bias"),
            "x_proj": stack(ly + "x_proj.weight", True),
            "dt_proj_w": stack(ly + "dt_proj.weight", True),
            "dt_proj_b": stack(ly + "dt_proj.bias"),
            "A_log": stack(ly + "A_log"),
            "D": stack(ly + "D"),
            "out_proj": stack(ly + "out_proj.weight", True),
        },
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype)
    if quantize == "int8":
        params = quantize_params(params)
    return params


def init_cache(cfg: MambaConfig, num_slots: int, max_len: int, dtype=None):
    """Fixed-size per-slot generation state (max_len only bounds the
    engine's token accounting — the state itself is O(1) in context):
    (conv_state [L, S, Di, K-1], ssm_state [L, S, Di, N]) float32 —
    SSM recurrences are precision-sensitive, states stay fp32."""
    L, Di = cfg.num_layers, cfg.d_inner
    return (jnp.zeros((L, num_slots, Di, cfg.conv_kernel - 1), jnp.float32),
            jnp.zeros((L, num_slots, Di, cfg.state_size), jnp.float32))


def _unembed(x, params, cfg):
    if cfg.tie_word_embeddings:
        return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                          params["embed"].astype(jnp.float32))
    return jnp.einsum("...d,dv->...v", x.astype(jnp.float32),
                      params["lm_head"].astype(jnp.float32))


def _mixer_step(h, conv_st, ssm_st, ly, cfg):
    """One token through one mixer. h [B, D]; conv_st [B, Di, K-1];
    ssm_st [B, Di, N]. Returns (out [B, D], conv_st, ssm_st)."""
    R, N = cfg.time_step_rank, cfg.state_size
    dt_ = h.dtype
    x = h @ _mat(ly["in_proj_x"], dt_)           # [B, Di]
    z = h @ _mat(ly["in_proj_z"], dt_)
    window = jnp.concatenate([conv_st, x[:, :, None]], axis=-1)  # [B,Di,K]
    conv_st = window[:, :, 1:]
    x = jnp.sum(window * ly["conv_w"][None], axis=-1) + ly["conv_b"][None]
    x = jax.nn.silu(x)                           # [B, Di]
    proj = x @ _mat(ly["x_proj"], x.dtype)       # [B, R+2N]
    dt = proj[:, :R] @ _mat(ly["dt_proj_w"], proj.dtype) + ly["dt_proj_b"][None]
    dt = jax.nn.softplus(dt)                     # [B, Di]
    Bm = proj[:, R:R + N]                        # [B, N]
    Cm = proj[:, R + N:]
    A = -jnp.exp(ly["A_log"].astype(jnp.float32))          # [Di, N]
    dA = jnp.exp(dt[:, :, None] * A[None])                 # [B, Di, N]
    dB = dt[:, :, None] * Bm[:, None, :]
    ssm_st = ssm_st * dA + dB * x[:, :, None]
    y = jnp.einsum("bdn,bn->bd", ssm_st, Cm) + ly["D"][None] * x
    y = y * jax.nn.silu(z)
    # conv/ssm state stays fp32 (recurrences are precision-sensitive) but
    # the residual path must return to the model dtype — otherwise the
    # fp32 state promotes every later layer's matmuls to f32
    return ((y @ _mat(ly["out_proj"], y.dtype)).astype(cfg.dtype),
            conv_st, ssm_st)


def _layer_scan(params, cfg, h, conv, ssm, active=None):
    """Scan h through all layers; state updates masked where not active.
    Shared by decode and prefill (they must never diverge)."""

    def layer_fn(carry, inp):
        hc = carry
        ly, conv_l, ssm_l = inp
        res = hc
        hn = _rms(hc, ly["norm"], cfg.layer_norm_epsilon)
        out, nconv, nssm = _mixer_step(hn, conv_l, ssm_l, ly, cfg)
        if active is not None:
            nconv = jnp.where(active[:, None, None], nconv, conv_l)
            nssm = jnp.where(active[:, None, None], nssm, ssm_l)
        return res + out, (nconv, nssm)

    return jax.lax.scan(layer_fn, h, (dict(params["layers"]), conv, ssm))


def _forward_token(params, cfg, tokens, conv, ssm, active=None):
    """One step for all rows. tokens [B]; conv/ssm [L, B, ...]."""
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    h, (conv, ssm) = _layer_scan(params, cfg, h, conv, ssm, active)
    h = _rms(h, params["final_norm"], cfg.layer_norm_epsilon)
    return _unembed(h, params, cfg), conv, ssm


def engine_decode(params, cfg, tokens, lengths, active, conv, ssm,
                  pos_offset=None):
    """Engine adapter: one decode step for all slots. Inactive slots'
    states must not advance (the engine computes every slot every step).
    lengths/pos_offset are unused — Mamba has no positional encoding."""
    del lengths, pos_offset
    return _forward_token(params, cfg, tokens, conv, ssm, active=active)


def prefill(params, cfg, tokens, seq_lens, conv, ssm, slot_ids, start_pos,
            continued=False, mm_pos=None, mm_vec=None,
            return_all_logits=False, positions=None):
    """Engine adapter: ingest B prompts into their slots' states.

    Scan-native: the recurrence IS the architecture, so ingestion is a
    lax.scan over positions carrying (conv, ssm) for the B rows. Rows
    with start_pos == 0 start from zero state (a fresh prompt must not
    inherit the slot's previous occupant); continued rows resume the
    slot's existing state. Padding rows (t >= seq_len) don't advance.
    Duplicate slot_ids (batch padding) scatter identical values."""
    assert mm_pos is None and positions is None, \
        "multimodal/positions are llama-family features"
    B, T = tokens.shape
    conv_rows = jnp.take(conv, slot_ids, axis=1)     # [L, B, Di, K-1]
    ssm_rows = jnp.take(ssm, slot_ids, axis=1)
    fresh = (jnp.asarray(start_pos) == 0)[None, :, None, None]
    conv_rows = jnp.where(fresh, 0.0, conv_rows)
    ssm_rows = jnp.where(fresh, 0.0, ssm_rows)

    def step(carry, xs_t):
        conv_r, ssm_r, last_h = carry
        tok, t = xs_t
        act = t < jnp.asarray(seq_lens)
        h = jnp.take(params["embed"], tok, axis=0).astype(cfg.dtype)
        h, (conv_r, ssm_r) = _layer_scan(params, cfg, h, conv_r, ssm_r, act)
        is_last = (t == jnp.asarray(seq_lens) - 1)[:, None]
        last_h = jnp.where(is_last, h, last_h)
        return (conv_r, ssm_r, last_h), (h if return_all_logits else None)

    last0 = jnp.zeros((B, cfg.hidden_size), cfg.dtype)
    (conv_rows, ssm_rows, last_h), hs = jax.lax.scan(
        step, (conv_rows, ssm_rows, last0),
        (jnp.asarray(tokens).T, jnp.arange(T, dtype=jnp.int32)))
    conv = conv.at[:, slot_ids].set(conv_rows)
    ssm = ssm.at[:, slot_ids].set(ssm_rows)
    last_h = _rms(last_h, params["final_norm"], cfg.layer_norm_epsilon)
    if return_all_logits:
        hs = _rms(hs.transpose(1, 0, 2), params["final_norm"],
                  cfg.layer_norm_epsilon)
        return _unembed(hs, params, cfg), conv, ssm
    return _unembed(last_h, params, cfg), conv, ssm
