"""Llama-family decoder (Llama 2/3/3.1, Mistral, Qwen2-style) in functional JAX.

TPU-first design notes:
  * Parameters are a plain pytree with all transformer layers STACKED on a
    leading axis so the forward pass is a single ``lax.scan`` — one trace,
    one compile, O(1) HLO size in depth.
  * All shapes are static; prefill uses bucketed sequence lengths and decode
    is a fixed [num_slots] batch so XLA compiles each bucket exactly once.
  * Sharding is expressed with ``jax.sharding.PartitionSpec`` per leaf (see
    localai_tpu/parallel/sharding.py); attention heads and MLP intermediate
    are split on the "tp" mesh axis, batch/slots on "dp".
  * GQA (num_kv_heads < num_heads) native; KV cache layout is
    [layers, slots, max_len, kv_heads, head_dim] which keeps the decode
    attention contraction MXU-friendly and the per-slot cache rows
    contiguous in HBM.

Capability parity target: the reference's main LLM engine is llama.cpp
behind a gRPC server (reference: backend/cpp/llama/grpc-server.cpp); this
module plays the role of llama.cpp's forward pass (llama_decode) for the
TPU engine in localai_tpu/engine/.
"""

from __future__ import annotations

import dataclasses
import json
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from localai_tpu.ops.rope import apply_rope, rope_frequencies
from localai_tpu.ops.norms import rms_norm
from localai_tpu.ops import kvcache
from localai_tpu.ops.attention import (
    causal_attention,
    decode_attention,
    decode_attention_append,
    mixed_prefill_attention,
)


# what the engine may do with this family (engine.py, Engine.__init__)
CAPABILITIES = frozenset({"paged", "packed_prefill", "prefix_reuse",
                          "kv_offload", "speculation", "self_extend",
                          "multimodal", "mesh"})


def _decode_attn_mode() -> str:
    """LOCALAI_DECODE_ATTN: scatter (default) | append | pallas — the
    CONTIGUOUS layout's decode attention (the paged layout has one)."""
    import os

    return os.environ.get("LOCALAI_DECODE_ATTN", "scatter")


@dataclasses.dataclass(frozen=True)
class AttnTarget:
    """Where a model's attention runs: the Pallas kernels of ops/pallas
    (TPU only) or the jnp reference. Decided once from the platform and
    the mesh (``attn_target``) and carried on ``LlamaConfig.attn``, so
    every trace is built from an explicit choice and ``*_attn_impl``
    below can name it per program."""
    pallas: bool = False
    mesh: Any = None    # kernels shard_map over its "tp" axis (None: one device)


def attn_target(cfg: "LlamaConfig", mesh=None) -> AttnTarget:
    """Pallas kernels on a TPU backend, jnp anywhere else. Mosaic
    kernels cannot be partitioned by GSPMD, so on a mesh they run under
    shard_map over "tp" — they are independent per KV head, the axis
    parallel/sharding.py splits the cache on. KV heads that do not
    divide tp leave the cache replicated, and the meshed program then
    takes the jnp path."""
    pallas = jax.default_backend() == "tpu"
    if mesh is not None and cfg.num_kv_heads % mesh.shape.get("tp", 1):
        pallas = False
    return AttnTarget(pallas=pallas, mesh=mesh)


def _target(cfg: "LlamaConfig") -> AttnTarget:
    """cfg.attn, or for direct callers that never set it (tests,
    scripts): decide from the backend, one device."""
    return cfg.attn if cfg.attn is not None else attn_target(cfg)


def decode_attn_impl(cfg: "LlamaConfig", lck) -> str:
    """Name of the decode-attention implementation a program over the
    cache ``lck`` (whole or one layer) is built with — _decode_attend_write
    dispatches on this string and the engine reports it per program."""
    pallas = _target(cfg).pallas
    if kvcache.is_paged(lck):
        if not pallas:
            return "jnp:paged_gather_append"
        return ("pallas:paged_decode_int8" if kvcache.is_quant(lck)
                else "pallas:paged_decode")
    mode = _decode_attn_mode()
    if mode == "pallas" and pallas and not kvcache.is_quant(lck):
        return "pallas:decode_append"
    if mode == "append" or (mode == "pallas" and kvcache.is_quant(lck)):
        return "jnp:append"
    return "jnp:scatter"


def ragged_attn_impl(cfg: "LlamaConfig", lck, N: int, continued: bool) -> str:
    """Name of the attention implementation an [N]-token packed-prefill
    program is built with. Fresh packs read no cache rows, and int8
    pages fold their scales through the jnp path by design; a float
    paged cache runs the Pallas kernel when ragged_kernel_plan finds a
    blocking that fits VMEM."""
    if not continued:
        return "jnp:ragged_fresh"
    if not (_target(cfg).pallas and kvcache.is_paged(lck)
            and not kvcache.is_quant(lck)):
        return "jnp:ragged"
    if _ragged_plan(cfg, lck, N) is None:
        return "jnp:ragged(no kernel plan)"
    return "pallas:ragged_prefill"


def _ragged_plan(cfg: "LlamaConfig", lck, N: int):
    from localai_tpu.ops.pallas.ragged_prefill import ragged_kernel_plan

    pages = lck["pages"]
    mesh = _target(cfg).mesh
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1   # heads per shard
    return ragged_kernel_plan(N, cfg.num_kv_heads // tp, cfg.q_per_kv,
                              cfg.head_dim_, page_size=pages.shape[-3],
                              itemsize=pages.dtype.itemsize)


def _on_mesh(cfg: "LlamaConfig", kernel, in_specs, out_spec):
    """``kernel`` as is on one device; on a mesh, under shard_map with
    the head axes split over "tp" and everything else replicated."""
    mesh = _target(cfg).mesh
    if mesh is None:
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)


# the stacked page pool [L, n_pages, page_size, KV, hd] under shard_map:
# KV heads over "tp", everything else (the layer axis too) replicated
_POOL = P(None, None, None, "tp", None)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 10000.0
    rope_scaling_type: str = "none"  # none | linear | yarn | llama3
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    dtype: Any = jnp.bfloat16
    attn: Optional[AttnTarget] = None   # None: decided per call (_target)

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @staticmethod
    def from_hf_config(cfg: dict, dtype=jnp.bfloat16) -> "LlamaConfig":
        """Build from a HuggingFace ``config.json`` dict (llama/mistral/qwen2)."""
        rope_scaling = cfg.get("rope_scaling") or {}
        rs_type = rope_scaling.get("rope_type", rope_scaling.get("type", "none")) or "none"
        return LlamaConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling_type=rs_type,
            rope_scaling_factor=rope_scaling.get("factor", 1.0),
            rope_low_freq_factor=rope_scaling.get("low_freq_factor", 1.0),
            rope_high_freq_factor=rope_scaling.get("high_freq_factor", 4.0),
            rope_original_max_position=rope_scaling.get(
                "original_max_position_embeddings", cfg.get("max_position_embeddings", 8192)
            ),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            attention_bias=cfg.get("attention_bias", False),
            dtype=dtype,
        )

    @staticmethod
    def from_json(path: str, dtype=jnp.bfloat16) -> "LlamaConfig":
        with open(path) as f:
            return LlamaConfig.from_hf_config(json.load(f), dtype=dtype)


def init_params(cfg: LlamaConfig, key: jax.Array, dtype=None) -> dict:
    """Random-init parameter pytree (layers stacked on axis 0)."""
    dtype = dtype or cfg.dtype
    hd = cfg.head_dim_
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    H, KV = cfg.num_heads, cfg.num_kv_heads
    keys = jax.random.split(key, 10)

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)

    params = {
        "embed": init(keys[0], (cfg.vocab_size, D), D),
        "layers": {
            "attn_norm": jnp.ones((L, D), dtype),
            "wq": init(keys[1], (L, D, H * hd), D),
            "wk": init(keys[2], (L, D, KV * hd), D),
            "wv": init(keys[3], (L, D, KV * hd), D),
            "wo": init(keys[4], (L, H * hd, D), H * hd),
            "mlp_norm": jnp.ones((L, D), dtype),
            "w_gate": init(keys[5], (L, D, F), D),
            "w_up": init(keys[6], (L, D, F), D),
            "w_down": init(keys[7], (L, F, D), F),
        },
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init(keys[8], (D, cfg.vocab_size), D)
    return params


# the {q, s} int8 contract is shared by every family — see ops/quant.py
from localai_tpu.ops.quant import mat as _mat  # noqa: E402


def _embed_rows(embed, tokens, dtype):
    """Token-embedding lookup; int8 tables dequantize AFTER the gather."""
    if isinstance(embed, dict):
        rows = jnp.take(embed["q"], tokens, axis=0).astype(jnp.float32)
        return (rows * embed["s"]).astype(dtype)
    return jnp.take(embed, tokens, axis=0).astype(dtype)


def quantize_params(params: dict, bits: int = 8, group: int = 128) -> dict:
    """Weight-only quantization for every matmul weight (norms stay
    as-is). Capability parity: the reference serves quantized GGUF
    (Q4/Q8) by default; these are the TPU-native analogues — the MXU
    consumes dequantized tiles while HBM traffic halves (int8) or
    quarters (int4) vs bf16.

    bits=8: per-out-channel symmetric int8 everywhere.
    bits=4: group-128 symmetric int4 for the LAYER matmuls (~85% of an
    8B's weight bytes) while embed/lm_head stay int8 — the embedding
    gather dequantizes row-wise (grouped scales don't compose with it)
    and the unembed is the quality-critical matmul."""
    import functools

    from localai_tpu.ops.quant import quantize_weight, quantize_weight_int4

    quant_names = {"embed", "lm_head", "wq", "wk", "wv", "wo",
                   "w_gate", "w_up", "w_down"}
    q = (functools.partial(quantize_weight_int4, group=group)
         if bits == 4 else quantize_weight)

    out = {}
    for name, leaf in params.items():
        if name == "layers":
            out[name] = {k: (q(v) if k in quant_names else v)
                         for k, v in leaf.items()}
        elif name in quant_names:
            out[name] = quantize_weight(leaf) if bits == 4 else q(leaf)
        else:
            out[name] = leaf
    return out


def dequantize_params(params: dict, dtype=jnp.bfloat16) -> dict:
    """Inverse of quantize_params: int8 {q, s} leaves back to dense float
    (used by the train step — gradients need float leaves)."""
    def dq(leaf):
        if isinstance(leaf, dict) and set(leaf) == {"q", "s"}:
            return _mat(leaf, dtype)
        return leaf

    out = {}
    for name, leaf in params.items():
        if name == "layers":
            out[name] = {k: dq(v) for k, v in leaf.items()}
        else:
            out[name] = dq(leaf)
    return out


# Scope names on everything the device runs (PERF.md section 3): a
# profiler capture's operations carry them in their op_name path, and
# benchmark/reduce_named.py sorts device time by them. Metadata only.
_scope = jax.named_scope


def _project_qkv(x, layer, cfg: LlamaConfig):
    """x: [B, T, D] -> q [B,T,H,hd], k/v [B,T,KV,hd]."""
    B, T, _ = x.shape
    hd = cfg.head_dim_
    dt = x.dtype
    q = jnp.einsum("btd,dh->bth", x, _mat(layer["wq"], dt)).reshape(B, T, cfg.num_heads, hd)
    k = jnp.einsum("btd,dh->bth", x, _mat(layer["wk"], dt)).reshape(B, T, cfg.num_kv_heads, hd)
    v = jnp.einsum("btd,dh->bth", x, _mat(layer["wv"], dt)).reshape(B, T, cfg.num_kv_heads, hd)
    return q, k, v


def _mlp(x, layer):
    dt = x.dtype
    gate = jnp.einsum("btd,df->btf", x, _mat(layer["w_gate"], dt))
    up = jnp.einsum("btd,df->btf", x, _mat(layer["w_up"], dt))
    return jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up,
                      _mat(layer["w_down"], dt))


def _unembed(x, params, cfg: LlamaConfig):
    if cfg.tie_word_embeddings:
        w = _mat(params["embed"], x.dtype).T
    else:
        w = _mat(params["lm_head"], x.dtype)
    return jnp.einsum("btd,dv->btv", x, w).astype(jnp.float32)


def prefill(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,      # [B, T] int32, right-padded
    seq_lens: jax.Array,    # [B] int32 true lengths
    cache_k: jax.Array,     # [L, S, C, KV, hd]
    cache_v: jax.Array,
    slot_ids: jax.Array,    # [B] int32 cache slots to fill
    start_pos: jax.Array,   # [B] int32 position offset (nonzero = continued prefix)
    continued: bool = False,  # STATIC: True when any start_pos may be nonzero
    mm_pos: Optional[jax.Array] = None,   # [B, P] chunk-relative positions
    mm_vec: Optional[jax.Array] = None,   # [B, P, D] injected embeddings
    return_all_logits: bool = False,      # STATIC: logits for every position
    positions: Optional[jax.Array] = None,  # [B, T] RoPE position override
):
    """Process full prompts, write KV into the cache slots, return last-token logits.

    ``continued`` selects the attention path at trace time: fresh prompts
    attend chunk-locally (cheap); continued chunks attend through the cache
    rows with absolute-position masking. Returns (logits [B, V] at position
    seq_lens-1, cache_k, cache_v).

    mm_pos/mm_vec implement LLaVA-style multimodal injection (reference:
    grpc-server.cpp:1157-1180,1425-1440): projected image-patch embeddings
    replace the token embeddings at the given chunk-relative positions.
    Inactive entries must use a LARGE positive sentinel (>= T) so the
    scatter's mode="drop" discards them — negative indices would WRAP.

    INVARIANT (enforced by the engine scheduler, not checkable in-jit):
    start_pos + T <= cache capacity C. Out-of-range rows are dropped by
    the KV scatter (mode="drop"), i.e. silently lost, not clamped.
    """
    B, T = tokens.shape
    if positions is None:
        positions = start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    sin, cos = rope_frequencies(cfg, positions)
    with _scope("embed"):
        x = _embed_rows(params["embed"], tokens, cfg.dtype)
    if mm_pos is not None:
        bidx = jnp.arange(B, dtype=jnp.int32)[:, None] * jnp.ones_like(mm_pos)
        x = x.at[bidx, mm_pos].set(mm_vec.astype(cfg.dtype), mode="drop")
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < seq_lens[:, None]  # [B, T]

    def layer_fn(carry, layer):
        x, ck, cv = carry
        li = layer.pop("_idx")
        with _scope("layer/attn_proj"):
            h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _project_qkv(h, layer, cfg)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        with _scope("layer/attn"):
            attn, ck, cv = attend_write(q, k, v, ck, cv, li)
        with _scope("layer/attn_proj"):
            x = x + jnp.einsum("bth,hd->btd", attn.reshape(B, T, -1),
                               _mat(layer["wo"], x.dtype))
        with _scope("layer/mlp"):
            h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
            x = x + _mlp(h, layer)
        return (x, ck, cv), None

    def attend_write(q, k, v, ck, cv, li):
        if continued:
            # continued prefix: committed keys live in the cache. Rows are
            # read BEFORE this chunk's scatter (attention combines them
            # with the in-register chunk keys). int8 caches pass the
            # {"q","s"} rows straight through — the attention op folds
            # scales without a dequantized copy. What this read costs on
            # the chip (it is the speculative verify pass): kvcache.layer
            # slices the layer's pool out of the scan carry and
            # gather_layer_rows gathers a dense [B, C, KV, hd] copy of
            # it, per layer — 404 MB of temporaries at 16 x 4096
            # (compiled for a v5e, PERF.md section 7). The decode step
            # and the packed prefill no longer slice a layer (PR 27:
            # that copy, out and back, was 17-24% of decode device
            # time); this path still does.
            k_rows = kvcache.gather_layer_rows(kvcache.layer(ck, li), slot_ids)
            v_rows = kvcache.gather_layer_rows(kvcache.layer(cv, li), slot_ids)
            if not kvcache.is_quant(k_rows):
                k_rows = k_rows.astype(cfg.dtype)
                v_rows = v_rows.astype(cfg.dtype)
            attn = mixed_prefill_attention(q, k, v, k_rows, v_rows,
                                           start_pos, seq_lens, cfg.q_per_kv)
        else:
            attn = causal_attention(q, k, v, valid, cfg.q_per_kv)
        # write this layer's K/V for all B prompts into their slots with ONE
        # batched scatter (ck[li, slot_ids[b], start_pos[b]+t] = k[b, t]) —
        # a python loop of per-prompt dynamic_update_slices serializes B*2
        # updates per layer and dominated batched-prefill time. Duplicate
        # slot entries (engine batch padding) write identical rows.
        rows = slot_ids[:, None] * jnp.ones((1, T), jnp.int32)              # [B, T]
        cols = start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B, T]
        ck = kvcache.scatter_prefill(ck, li, rows, cols, k)
        cv = kvcache.scatter_prefill(cv, li, rows, cols, v)
        return attn, ck, cv

    layers = dict(params["layers"])
    layers["_idx"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, cache_k, cache_v), _ = jax.lax.scan(layer_fn, (x, cache_k, cache_v), layers)
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    # gather hidden state at the last valid position of each prompt
    if return_all_logits:
        # [B, T, V] — used by speculative verification (every draft
        # position needs the target's next-token distribution)
        with _scope("lm_head"):
            return _unembed(x, params, cfg), cache_k, cache_v
    with _scope("lm_head"):
        last = jnp.take_along_axis(
            x, (seq_lens - 1)[:, None, None].astype(jnp.int32), axis=1)
        logits = _unembed(last, params, cfg)[:, 0, :]
    return logits, cache_k, cache_v


def ragged_kernel_shape_fallback(cache_k, N: int, cfg: LlamaConfig) -> bool:
    """Would a continued [N]-token pack leave the Pallas kernel path for
    SHAPE reasons? The engine counts these per packed dispatch
    (metrics()["packed_prefill"]["kernel_fallback"]) so a regression of
    the long-pack cliff is observable. Deliberately platform- and
    dtype-independent: int8 scales and contiguous layouts are static
    config choices routed to the jnp path by design, not a
    length-dependent cliff — counting them would bury the signal (and
    make the CPU-CI zero-fallback gate meaningless)."""
    # layout, dtype and page shape are read off the whole cache: slicing
    # out a layer here would dispatch a device copy on every pack
    if not kvcache.is_paged(cache_k) or kvcache.is_quant(cache_k):
        return False
    return _ragged_plan(cfg, cache_k, N) is None


def ragged_attend_write(cfg, q, k, v, ck, cv, li, seg_of, seg_slots,
                        seg_start, seg_off, seg_len, slot_of, positions,
                        continued: bool):
    """One layer of a packed prefill: attention for the [N] packed tokens
    over their own pack and (``continued``) their slots' committed rows,
    then the ragged scatter of the new K/V rows into layer ``li`` of the
    whole cache. q [N, H, hd]; k, v [N, KV, hd] -> (attn [N, H, hd], ck,
    cv). ``cfg`` gives ``attn``, ``num_kv_heads``, ``q_per_kv`` and
    ``head_dim_`` (models/olmo_hybrid.py's full layers come here too)."""
    from localai_tpu.ops.ragged_prefill import ragged_prefill_attention

    N = q.shape[0]
    # committed rows are read BEFORE this pack's scatter (the same
    # no-read-after-write rule as every other attention path here)
    if ragged_attn_impl(cfg, ck, N, continued).startswith("pallas"):
        from localai_tpu.ops.pallas.ragged_prefill import (
            ragged_prefill_attention_pallas)

        # the kernel indexes the stacked pool by layer: slicing the
        # layer out here would copy it out of the scan carry
        qb, pkb = _ragged_plan(cfg, ck, N)
        heads, rep = P(None, "tp", None), P(None)
        attn = _on_mesh(
            cfg, partial(ragged_prefill_attention_pallas,
                         q_per_kv=cfg.q_per_kv, pkb=pkb, qb=qb),
            (heads, heads, heads, _POOL, _POOL, P(None, None),
             rep, rep, rep, rep, P()), heads)(
            q, k, v, ck["pages"], cv["pages"], ck["ptab"],
            seg_slots, seg_start, seg_off, seg_len, li)
    else:
        attn = ragged_prefill_attention(
            q, k, v, seg_of, seg_slots, seg_start,
            kvcache.layer(ck, li), kvcache.layer(cv, li),
            cfg.q_per_kv, continued=continued)
    ck = kvcache.scatter_ragged(ck, li, slot_of, positions, k)
    cv = kvcache.scatter_ragged(cv, li, slot_of, positions, v)
    return attn, ck, cv


def ragged_prefill(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,      # [N] int32 packed prompt tokens (pads 0)
    positions: jax.Array,   # [N] int32 absolute cache position (pads: C)
    seg_of: jax.Array,      # [N] int32 segment per token (pads: sentinel)
    seg_slots: jax.Array,   # [B] int32 slot per segment (pads: sentinel)
    seg_start: jax.Array,   # [B] int32 committed rows per segment
    seg_off: jax.Array,     # [B] int32 pack offset of each segment
    seg_len: jax.Array,     # [B] int32 tokens in each segment (pads: 0)
    cache_k: jax.Array,
    cache_v: jax.Array,
    continued: bool = False,  # STATIC: True when any seg_start may be > 0
    rope_positions: Optional[jax.Array] = None,  # [N] RoPE override
    comm_overlap: bool = False,  # STATIC: TokenWeave halved-pack overlap
):
    """RAGGED PACKED PREFILL: process the prompt tails of up to B slots
    as ONE [N]-token batch — per-segment causal self-attention plus
    (``continued`` only) attention over each slot's committed cache
    rows, with the new KV rows written through every token's own slot's
    page table in one ragged scatter (ops/kvcache.py::scatter_ragged).

    ``rope_positions`` decouples rotation from placement for
    self-extend segments: the cache position (``positions``) drives the
    KV scatter while compressed group-attention positions drive RoPE —
    committed rows were already re-rotated in place by the engine, so
    attention itself stays position-table-free. ``comm_overlap``
    (STATIC) splits the pack in two around each layer's out-projection
    and MLP so their contraction-sharded matmuls become independent
    matmul + all-reduce chains XLA can interleave on a tp mesh
    (parallel/sharding.py::overlap_halves; bit-exact, so greedy output
    is byte-identical either way).

    This is the reference's llama_batch packing (engine.py module doc:
    grpc-server.cpp:1671+ packs prompt chunks of all slots into one
    batch) expressed TPU-natively: the pack pads only to a small set of
    TOTAL-token buckets, so a tick's worth of ragged prompt tails costs
    one dispatch and near-zero pad compute instead of one padded
    per-slot bucket each (see engine.py packed-prefill scheduling).

    Returns (logits [B, V] at each segment's last packed token,
    cache_k, cache_v). Pad segments (seg_len == 0) produce garbage
    logits rows the caller must gate on; their tokens write nothing
    (position sentinel C drops the scatter) and their state is never
    sampled (slot sentinel drops the engine's key/mu writes).
    """
    from localai_tpu.parallel.sharding import overlap_halves

    N = tokens.shape[0]
    B = seg_slots.shape[0]
    rp = positions if rope_positions is None else rope_positions
    sin, cos = rope_frequencies(cfg, rp[None, :])
    with _scope("embed"):
        x = _embed_rows(params["embed"], tokens, cfg.dtype)[None]   # [1, N, D]
    # per-token target slot for the ragged KV scatter (pads ride the
    # clipped lookup; their position sentinel drops the write)
    slot_of = jnp.take(seg_slots, jnp.minimum(seg_of, B - 1))

    def layer_fn(carry, layer):
        x, ck, cv = carry
        li = layer.pop("_idx")
        with _scope("layer/attn_proj"):
            h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _project_qkv(h, layer, cfg)     # [1, N, {H|KV}, hd]
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        with _scope("layer/attn"):
            attn, ck, cv = ragged_attend_write(
                cfg, q[0], k[0], v[0], ck, cv, li, seg_of, seg_slots,
                seg_start, seg_off, seg_len, slot_of, positions, continued)
        attn_r = attn[None].reshape(1, N, -1)

        def out_proj(t):
            with _scope("layer/attn_proj"):
                return jnp.einsum("bth,hd->btd", t,
                                  _mat(layer["wo"], x.dtype))

        def mlp_half(t):
            with _scope("layer/mlp"):
                return _mlp(rms_norm(t, layer["mlp_norm"],
                                     cfg.rms_norm_eps), layer)

        if comm_overlap:
            x = x + overlap_halves(out_proj, attn_r, axis=1)
            x = x + overlap_halves(mlp_half, x, axis=1)
        else:
            x = x + out_proj(attn_r)
            x = x + mlp_half(x)
        return (x, ck, cv), None

    layers = dict(params["layers"])
    layers["_idx"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, cache_k, cache_v), _ = jax.lax.scan(layer_fn, (x, cache_k, cache_v),
                                            layers)
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with _scope("lm_head"):
        # hidden state at each segment's LAST packed token (pads clamp to 0)
        last = jnp.maximum(seg_off + seg_len - 1, 0)
        hs = jnp.take(x[0], last, axis=0)                       # [B, D]
        logits = _unembed(hs[None], params, cfg)[0]
    return logits, cache_k, cache_v


def _decode_attend_write(q1, k1, v1, ck, cv, li, lengths, cfg: LlamaConfig):
    """One decode token per slot in layer ``li``: attend + write the new
    K/V row, both on the WHOLE cache (the scan carry).

    q1 [S, H, hd]; k1/v1 [S, KV, hd]; returns (attn [S, H, hd], ck, cv).

    The implementation is decode_attn_impl's choice. The paged layout
    has one form per backend (ragged paged kernel on TPU, page gather +
    append-attention elsewhere). The contiguous layout keeps three,
    selected by LOCALAI_DECODE_ATTN: post-scatter einsum (default),
    append-attention (pre-scatter read) in jnp, or the same through
    ops/pallas/decode_attention.py. Semantically identical; which is
    fastest on the chip is not measured (ROADMAP S5/D2).

    The paged kernels index the stacked pool by ``li`` and the row write
    is one scatter into the carry, which XLA performs in place. Slicing
    the layer out for the kernel and setting it back after the write
    made XLA copy a layer of the pool out of the carry and back, per
    layer per step: 17 and 24% of decode device time (PERF.md section 6,
    PR 27).

    ``lengths`` is the WRITE position: the engine hands an inactive slot
    ``lengths == C`` so that its row write drops (engine_decode;
    decode_step's invariant keeps active slots below C). The paged
    kernels read by ``read_lengths`` of it, where such a slot has length
    0: it fetches no page and computes nothing but its own token, one
    grid step of about a microsecond, for an output the caller
    discards. Given C it walked every entry of its page table, stale
    ones too, and was the dearest slot of the step: a fifth of a
    millisecond a layer at 4096 context (PERF.md section 6, PR 31). The
    jnp forms mask instead of walking and keep ``lengths``."""
    S = q1.shape[0]
    slot = jnp.arange(S, dtype=jnp.int32)[:, None]

    def write(cache, new):
        # cache[li, s, lengths[s]] = new[s]; out-of-range positions
        # (lengths == C: inactive slots) are dropped, preserving the
        # capacity invariant
        return kvcache.scatter_prefill(cache, li, slot, lengths[:, None],
                                       new[:, None])

    impl = decode_attn_impl(cfg, ck)
    heads = P(None, "tp", None)
    if impl == "jnp:scatter":
        # write first, then attend over the updated rows ([0, lengths])
        ck, cv = write(ck, k1), write(cv, v1)
        attn = decode_attention(q1, kvcache.layer(ck, li),
                                kvcache.layer(cv, li), lengths + 1,
                                cfg.q_per_kv)
        return attn, ck, cv
    # every other form reads the cache BEFORE the write and appends the
    # current token's k/v from registers
    if impl.startswith("pallas:paged_decode"):
        from localai_tpu.ops.pallas.paged_attention import read_lengths

        read = read_lengths(lengths, kvcache.shape(ck)[2])
    if impl == "pallas:paged_decode_int8":
        # int8 pages stay quantized in HBM: the {q, scales} kernel
        # variant folds the scales in VMEM
        from localai_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_append_quant)

        scales = P(*_POOL[:-1])
        attn = _on_mesh(
            cfg, partial(paged_decode_attention_append_quant,
                         q_per_kv=cfg.q_per_kv),
            (heads, heads, heads, _POOL, scales, _POOL, scales,
             P(None, None), P(None), P()), heads)(
            q1, k1, v1, ck["pages"], ck["scales"], cv["pages"],
            cv["scales"], ck["ptab"], read, li)
    elif impl == "pallas:paged_decode":
        from localai_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_append)

        attn = _on_mesh(
            cfg, partial(paged_decode_attention_append,
                         q_per_kv=cfg.q_per_kv),
            (heads, heads, heads, _POOL, _POOL, P(None, None), P(None),
             P()), heads)(
            q1, k1, v1, ck["pages"], cv["pages"], ck["ptab"], read, li)
    elif impl == "pallas:decode_append":
        from localai_tpu.ops.pallas.decode_attention import (
            decode_attention_append_pallas)

        rows = P(None, None, "tp", None)
        attn = _on_mesh(
            cfg, partial(decode_attention_append_pallas,
                         q_per_kv=cfg.q_per_kv),
            (heads, heads, heads, rows, rows, P(None)), heads)(
            q1, k1, v1, kvcache.layer(ck, li), kvcache.layer(cv, li),
            lengths)
    elif impl == "jnp:paged_gather_append":
        # pure-jnp page gather + append-attention (the gathered
        # {"q","s"} rows fold scales exactly like the contiguous path)
        attn = decode_attention_append(
            q1, k1, v1, kvcache.gather_all_rows(kvcache.layer(ck, li)),
            kvcache.gather_all_rows(kvcache.layer(cv, li)), lengths,
            cfg.q_per_kv)
    else:
        assert impl == "jnp:append", impl
        attn = decode_attention_append(
            q1, k1, v1, kvcache.layer(ck, li), kvcache.layer(cv, li),
            lengths, cfg.q_per_kv)
    return attn, write(ck, k1), write(cv, v1)


def decode_step(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,     # [S] int32 — one token per slot
    lengths: jax.Array,    # [S] int32 — current context length per slot (position of new token)
    cache_k: jax.Array,    # [L, S, C, KV, hd]
    cache_v: jax.Array,
    pos_offset: jax.Array = None,  # [S] int32 — self-extend position offset
):
    """One decode step for ALL slots (inactive slots are masked by caller).

    Returns (logits [S, V], cache_k, cache_v). The new token for slot s is
    written at cache position lengths[s]; attention spans [0, lengths[s]].
    With self-extend (group attention) active, its RoPE position is
    lengths[s] - pos_offset[s]: cache ROWS keep raw token order (attention
    masking is row-based) while positions are compressed.

    INVARIANT (enforced by the engine scheduler): lengths[s] < C for active
    slots. At lengths[s] == C the one_hot write row is all-zero and the new
    token's K/V would be silently dropped — the scheduler must context-shift
    or finish the request before the cache fills.
    """
    S = tokens.shape[0]
    positions = lengths[:, None]  # [S, 1]
    if pos_offset is not None:
        positions = positions - pos_offset[:, None]
    sin, cos = rope_frequencies(cfg, positions)
    with _scope("embed"):
        x = _embed_rows(params["embed"], tokens, cfg.dtype)[:, None, :]  # [S,1,D]
    C = kvcache.shape(cache_k)[2]

    def layer_fn(carry, layer):
        x, ck, cv = carry
        li = layer.pop("_idx")
        with _scope("layer/attn_proj"):
            h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _project_qkv(h, layer, cfg)  # q [S,1,H,hd], k/v [S,1,KV,hd]
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        with _scope("layer/attn"):
            attn, ck, cv = _decode_attend_write(q[:, 0], k[:, 0], v[:, 0],
                                                ck, cv, li, lengths, cfg)
        with _scope("layer/attn_proj"):
            x = x + jnp.einsum("sh,hd->sd", attn.reshape(S, -1),
                               _mat(layer["wo"], x.dtype))[:, None, :]
        with _scope("layer/mlp"):
            h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
            x = x + _mlp(h, layer)
        return (x, ck, cv), None

    layers = dict(params["layers"])
    layers["_idx"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, cache_k, cache_v), _ = jax.lax.scan(layer_fn, (x, cache_k, cache_v), layers)
    with _scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with _scope("lm_head"):
        logits = _unembed(x, params, cfg)[:, 0, :]
    return logits, cache_k, cache_v


def engine_decode(params, cfg, tokens, lengths, active, cache_k, cache_v,
                  pos_offset=None):
    """Engine adapter (shared contract with models/mamba.py): one decode
    step for all slots; inactive slots must not write KV — their write
    position is forced to C so the scatter's mode=\"drop\" discards it."""
    C = kvcache.shape(cache_k)[2]
    write_lengths = jnp.where(active, lengths, C)
    return decode_step(params, cfg, tokens, write_lengths, cache_k, cache_v,
                       pos_offset=pos_offset)


def shift_cache_positions(cache_k: jax.Array, cfg: LlamaConfig,
                          slot: jax.Array, deltas: jax.Array) -> jax.Array:
    """Re-rotate ONE slot's cached keys by per-row position deltas [C].

    The recomputeless self-extend primitive: grouped attention compresses
    the positions of past blocks (reference KV surgery:
    grpc-server.cpp:1904-1927); since RoPE rotations compose, rotating the
    cached (already-rotated) keys by (new_pos - old_pos) is EXACT. Values
    carry no positional encoding and stay untouched. Rows with delta 0
    are rotated by the identity."""
    from localai_tpu.ops.rope import rope_delta_terms, rotate_by_delta

    sin, cos = rope_delta_terms(cfg, deltas)            # [C, hd]
    rows = kvcache.slot_rows(cache_k, slot)             # [L, C, KV, hd]
    if kvcache.is_quant(rows):
        # dequant -> rotate -> requant for the ONE slot being compressed
        # (slot-local, off the hot path; one extra quantization rounding)
        dense = kvcache.dequantize(rows["q"], rows["s"], cfg.dtype)
        out = rotate_by_delta(dense, sin[None, :, None, :],
                              cos[None, :, None, :])
        return kvcache.tree_slot_update(cache_k, slot,
                                        kvcache.rows_from_float(out, cache_k))
    out = rotate_by_delta(rows, sin[None, :, None, :], cos[None, :, None, :])
    if kvcache.is_paged(cache_k):
        # scatter the rotated rows back through the page table (the slot
        # owns its pages exclusively here: cross-slot page sharing is
        # disabled under self-extend — see engine admission gates)
        return kvcache.tree_slot_update(cache_k, slot, out)
    return cache_k.at[:, slot].set(out)


def init_cache(cfg: LlamaConfig, num_slots: int, max_len: int, dtype=None,
               page_size: int = 0, num_pages: int = 0):
    """KV cache: ([L, S, C, KV, hd], [L, S, C, KV, hd]); ``dtype=int8``
    selects the quantized {"q","s"} pytree (ops/kvcache.py).

    ``page_size > 0`` selects the PAGED layout instead: a shared page
    pool (num_pages physical pages, default the full S * C/page_size —
    i.e. never more HBM than the contiguous reservation) plus a per-slot
    page table, same logical shape."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, num_slots, max_len, cfg.num_kv_heads, cfg.head_dim_)
    if page_size:
        return (kvcache.init_paged(shape, dtype, page_size, num_pages),
                kvcache.init_paged(shape, dtype, page_size, num_pages))
    return kvcache.init(shape, dtype), kvcache.init(shape, dtype)
