"""Rotary position embeddings with the scaling families users of the
reference expect (none/linear/yarn/llama3 — reference plumbs these knobs
end-to-end: backend.proto:226-231, grpc-server.cpp:2310-2330).

Uses the HF "rotate_half" convention (split head_dim in halves) so weights
converted from HF checkpoints work unmodified.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _base_inv_freq(cfg) -> np.ndarray:
    hd = cfg.head_dim_
    return 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))


def yarn_inv_freq(dim: int, theta: float, factor: float, orig: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0
                  ) -> np.ndarray:
    """YaRN's ``dim / 2`` inverse frequencies: interpolate the low-frequency
    dimensions (divide by ``factor``), keep the high-frequency ones, a linear
    ramp between the dimensions that make ``beta_fast`` and ``beta_slow``
    rotations over the ``orig`` positions."""
    inv_freq = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def correction_dim(num_rot):
        return dim * np.log(orig / (num_rot * 2 * np.pi)) / (2 * np.log(theta))

    low = np.floor(correction_dim(beta_fast))
    high = np.ceil(correction_dim(beta_slow))
    low, high = max(low, 0), min(high, dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1 - ramp
    return inv_freq / factor * (1 - mask) + inv_freq * mask


def yarn_mscale(factor: float, a: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 a ln(factor) + 1`` (1 where
    nothing is scaled)."""
    return 1.0 if factor <= 1.0 else 0.1 * a * float(np.log(factor)) + 1.0


def _scaled_inv_freq(cfg) -> np.ndarray:
    """Static (trace-time) inverse frequencies with scaling applied."""
    inv_freq = _base_inv_freq(cfg)
    t = cfg.rope_scaling_type
    if t in ("none", "default") or cfg.rope_scaling_factor == 1.0 and t != "llama3":
        return inv_freq
    if t == "linear":
        return inv_freq / cfg.rope_scaling_factor
    if t == "llama3":
        # Llama-3.1 frequency-dependent NTK scaling.
        low_wl = cfg.rope_original_max_position / cfg.rope_low_freq_factor
        high_wl = cfg.rope_original_max_position / cfg.rope_high_freq_factor
        wavelen = 2 * np.pi / inv_freq
        scaled = inv_freq / cfg.rope_scaling_factor
        smooth = (cfg.rope_original_max_position / wavelen - cfg.rope_low_freq_factor) / (
            cfg.rope_high_freq_factor - cfg.rope_low_freq_factor
        )
        smooth = np.clip(smooth, 0.0, 1.0)
        mid = (1 - smooth) * scaled + smooth * inv_freq
        out = np.where(wavelen < high_wl, inv_freq, np.where(wavelen > low_wl, scaled, mid))
        return out
    if t == "yarn":
        return yarn_inv_freq(cfg.head_dim_, cfg.rope_theta,
                             cfg.rope_scaling_factor,
                             cfg.rope_original_max_position)
    raise ValueError(f"unknown rope scaling type: {t}")


def rope_frequencies(cfg, positions: jax.Array):
    """positions [B, T] -> (sin, cos) each [B, T, head_dim] (half-duplicated)."""
    inv_freq = jnp.asarray(_scaled_inv_freq(cfg), jnp.float32)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq[None, None, :]  # [B,T,hd/2]
    # yarn attention temperature scaling
    mscale = 1.0
    if cfg.rope_scaling_type == "yarn" and cfg.rope_scaling_factor > 1.0:
        mscale = 0.1 * np.log(cfg.rope_scaling_factor) + 1.0
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.sin(emb) * mscale, jnp.cos(emb) * mscale


def rope_delta_terms(cfg, delta: jax.Array):
    """delta positions [...] -> (sin, cos) each [..., head_dim] for a PURE
    rotation by ``delta * inv_freq`` — no yarn attention-temperature
    mscale. RoPE rotations compose (angle is linear in position for every
    scaling family, which only modifies inv_freq), so cached keys written
    at position a become keys at position b when rotated by (b - a); the
    mscale magnitude factor is already baked into the cached keys and must
    not be applied twice. Used by the self-extend KV re-rotation
    (reference: grpc-server.cpp:1916-1927 llama_kv_cache_seq_div/add)."""
    inv_freq = jnp.asarray(_scaled_inv_freq(cfg), jnp.float32)
    freqs = delta.astype(jnp.float32)[..., None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.sin(emb), jnp.cos(emb)


def rotate_by_delta(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x [..., hd]; sin/cos broadcastable [..., hd]. rotate_half rotation."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + rotated * sin).astype(dtype)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x [B, T, H, hd]; sin/cos [B, T, hd]. HF rotate_half convention."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    out = x * cos[:, :, None, :] + rotated * sin[:, :, None, :]
    return out.astype(dtype)
