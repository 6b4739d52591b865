"""A plain float32 forward pass of the Llama/Mistral decoder, as published:
RMSNorm, grouped-query attention with rotate-half RoPE, SwiGLU MLP, untied
head. Straight ``jax.numpy`` in float32 at ``highest`` matmul precision; no
kernels, no cache, no batching. One sequence at a time, one layer's weights
at a time, read from the checkpoint file in HF layout (``[out, in]``).

It takes nothing the program has made: the weights come from the file the
benchmark wrote from the seed, and where the configuration states int8
weights the rounding below is this file's own.
"""

from __future__ import annotations

import numpy as np


def int8_round(w: np.ndarray, channel_axis: int) -> np.ndarray:
    """Symmetric int8 weight-only rounding as the configuration states it:
    one scale per output channel, max|w| / 127 over the contraction axis,
    round to nearest, values in [-127, 127]. Returns the dequantized
    float32 weight."""
    w = np.asarray(w, np.float32)
    red = tuple(a for a in range(w.ndim) if a != channel_axis % w.ndim)
    s = np.maximum(np.max(np.abs(w), axis=red, keepdims=True) / 127.0, 1e-12)
    return np.clip(np.rint(w / s), -127, 127) * s


def weight_reader(tensors, weights_precision: str, vocab_rows: int = 0):
    """-> read(name) giving a float32 array in HF layout. ``tensors`` maps
    HF names to arrays (a safetensors handle's get_tensor). For int8 the
    output channel is axis 0 of a linear ``[out, in]``; the embedding table
    is rounded per hidden column, as a ``[vocab(in), hidden(out)]`` matrix."""
    def read(name):
        w = np.asarray(tensors(name))
        if vocab_rows and (name.startswith("lm_head")
                           or "embed_tokens" in name):
            w = w[:vocab_rows]
        w = w.astype(np.float32)
        if weights_precision == "int8" and w.ndim == 2:
            w = int8_round(w, 1 if "embed_tokens" in name else 0)
        elif weights_precision not in ("int8", "bfloat16"):
            raise ValueError(f"unknown weights precision {weights_precision}")
        return w
    return read


def forward(read, hf: dict, n_layers: int, seqs):
    """seqs: [(token_ids, logit_positions)] ->
    [(logits [len(logit_positions), V] float32,
      K [L, T, KV, hd] after RoPE, V [L, T, KV, hd])] as numpy.
    One layer's weights are on the device at a time, for every sequence."""
    import jax
    import jax.numpy as jnp

    D = hf["hidden_size"]
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or D // H
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):                       # x [T, heads, hd]
        inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = pos[:, None] * inv[None, :]
        ang = jnp.concatenate([ang, ang], -1)[:, None, :]
        half = hd // 2
        rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
        return x * jnp.cos(ang) + rot * jnp.sin(ang)

    @jax.jit
    def layer(x, w):
        T = x.shape[0]
        pos = jnp.arange(T, dtype=jnp.float32)
        h = rms(x, w["ln1"])
        q = rope((h @ w["q"].T).reshape(T, H, hd), pos)
        k = rope((h @ w["k"].T).reshape(T, KV, hd), pos)
        v = (h @ w["v"].T).reshape(T, KV, hd)
        kk = jnp.repeat(k, H // KV, axis=1)
        vv = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("thd,shd->hts", q, kk) / np.sqrt(hd)
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shd->thd", p, vv).reshape(T, H * hd)
        x = x + a @ w["o"].T
        h = rms(x, w["ln2"])
        x = x + (jax.nn.silu(h @ w["gate"].T) * (h @ w["up"].T)) @ w["down"].T
        return x, k, v

    @jax.jit
    def head(x, norm, lm):
        return rms(x, norm) @ lm.T

    names = {"ln1": "input_layernorm", "q": "self_attn.q_proj",
             "k": "self_attn.k_proj", "v": "self_attn.v_proj",
             "o": "self_attn.o_proj", "ln2": "post_attention_layernorm",
             "gate": "mlp.gate_proj", "up": "mlp.up_proj",
             "down": "mlp.down_proj"}
    with jax.default_matmul_precision("highest"):
        embed = read("model.embed_tokens.weight")
        xs = [jnp.asarray(embed[np.asarray(ids)], jnp.float32)
              for ids, _ in seqs]
        del embed
        ks = [[] for _ in seqs]
        vs = [[] for _ in seqs]
        for i in range(n_layers):
            w = {k: jnp.asarray(read(f"model.layers.{i}.{n}.weight"))
                 for k, n in names.items()}
            for j in range(len(seqs)):
                xs[j], k, v = layer(xs[j], w)
                ks[j].append(np.asarray(k))
                vs[j].append(np.asarray(v))
            del w
        norm = jnp.asarray(read("model.norm.weight"))
        lm = jnp.asarray(read("lm_head.weight"))
        return [(np.asarray(head(xs[j][np.asarray(at)], norm, lm)),
                 np.stack(ks[j]), np.stack(vs[j]))
                for j, (_, at) in enumerate(seqs)]
