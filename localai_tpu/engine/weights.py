"""HF safetensors checkpoint -> stacked JAX param pytree.

Replaces the reference's GGUF weight pipeline (llama.cpp model loading +
core/config/guesser.go GGUF header parsing) with the TPU-native flow:
HF safetensors shards are memory-mapped, per-layer tensors are stacked on
a leading layer axis (for the scan-over-layers forward), cast to bf16, and
placed shard-by-shard onto the device mesh so peak host memory stays at
one tensor, not one model.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import threading
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.engine.gguf import find_gguf
from localai_tpu.services.tracing import NO_TRACER

log = logging.getLogger("localai_tpu.weights")

try:
    from safetensors import safe_open
except ImportError:  # pragma: no cover
    safe_open = None


def _open_shards(model_dir: str):
    """Yield (name -> shard accessor) across all safetensors files."""
    index_path = os.path.join(model_dir, "model.safetensors.index.json")
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {model_dir}")
    handles = {f: safe_open(f, framework="np") for f in files}
    name_to_file = {}
    if os.path.exists(index_path):
        with open(index_path) as f:
            index = json.load(f)
        for name, fname in index["weight_map"].items():
            name_to_file[name] = handles[os.path.join(model_dir, fname)]
    else:
        for f, h in handles.items():
            for name in h.keys():
                name_to_file[name] = h
    return name_to_file


_QUANT_NAMES = {"embed", "lm_head", "wq", "wk", "wv", "wo",
                "w_gate", "w_up", "w_down",
                # models/olmo_hybrid.py's linear-attention projections
                "lin_qkv", "lin_g", "lin_o",
                # models/granite_hybrid.py's mamba projections
                "ssm_in_z", "ssm_in_xbc", "ssm_out",
                # models/lfm2_moe.py's convolution projections
                "conv_in", "conv_out",
                # models/ling_hybrid.py's KDA and MLA projections and its
                # shared expert
                "kda_qkv", "kda_f", "kda_g", "kda_o", "mla_q", "mla_kva",
                "mla_kvb", "mla_o", "sh_w1", "sh_w3", "sh_w2",
                # models/xing4.py's query pair
                "mla_qa", "mla_qb"}


def _make_put(cfg, mesh, dtype, quantize, adapter=None, pace=None,
              tracer=None):
    """Leaf placer: host array + pytree path -> (LoRA-merged) cast /
    int8/int4-quantized / mesh-sharded device leaf. ``pace`` (streaming
    loads, ISSUE 19) is called with each host leaf before placement —
    the accounting/chaos/yield seam of ``stream_llama_params``.

    ``tracer`` (the runner's RingTracer) gets, per leaf, ``load_quantize``
    (ops/quant.py::quantize_weight*, which also hands the int8 array to
    the device), ``load_cast`` (ops/hostblocks.py::cast_leaf, then
    ``jnp.asarray`` of the finished array: off a mesh, the hand-over to
    the device) and ``load_put`` (the mesh placement). The first two carry
    ``threads`` and ``blocks``: both passes run on the host, by blocks on
    a pool of threads that lives for the leaf (1 and 1: a small leaf,
    worked inline), and give the bits the one-thread whole-array form
    gives. Each leaf's output is a fresh host array: the hand-over
    may still be reading it when ``put`` returns, and nothing here waits
    for the device (the runner waits once, ``load_device_wait``)."""
    span = (tracer or NO_TRACER).span

    def leaf_spec(spec_path: tuple):
        from localai_tpu.parallel import sharding as shardlib

        node = shardlib.llama_param_specs(cfg.tie_word_embeddings)
        for k in spec_path:
            node = node[k]
        return node

    def put(arr: np.ndarray, spec_path: tuple):
        if pace is not None:
            pace(arr)
        leaf_name = spec_path[-1]
        if adapter is not None and spec_path[0] == "layers" \
                and adapter.targets_leaf(leaf_name, cfg.num_layers):
            # merge W += scale*(B@A) BEFORE cast/quantization (reference:
            # LoraAdapter applied at load, grpc-server.cpp:2295-2309);
            # in-place per layer — no full-leaf delta buffer
            arr = np.array(arr, np.float32)  # always a fresh writable copy
            adapter.apply_to_leaf(leaf_name, cfg.num_layers, arr)
        if quantize in ("int8", "int4") and leaf_name in _QUANT_NAMES:
            from localai_tpu.ops.quant import (quantize_weight,
                                               quantize_weight_int4)

            # int4 applies to the layer matmuls only; embed/lm_head stay
            # int8 (see models/llama.py quantize_params for why)
            if quantize == "int4" and spec_path[0] == "layers":
                # the group count must divide the tp degree on the
                # contraction axis or the scale can't shard with its
                # weight (e.g. llama-2's 11008 FFN: 86 groups vs tp=8)
                divisor = 1
                if mesh is not None:
                    from localai_tpu.parallel.sharding import fit_spec

                    axis = fit_spec(mesh, arr.shape,
                                    leaf_spec(spec_path))[-2]
                    if axis is not None:
                        divisor = mesh.shape[axis]
                with span("load_quantize", "load", leaf=leaf_name,
                          bits=4) as sp:
                    leaf = quantize_weight_int4(arr, shard_divisor=divisor,
                                                ran=sp.args)
            else:
                with span("load_quantize", "load", leaf=leaf_name,
                          bits=8) as sp:
                    leaf = quantize_weight(arr, ran=sp.args)
        else:
            from localai_tpu.ops.hostblocks import cast_leaf

            with span("load_cast", "load", leaf=leaf_name) as sp:
                leaf = jnp.asarray(cast_leaf(arr, dtype, ran=sp.args), dtype)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from localai_tpu.ops.quant import scale_spec
            from localai_tpu.parallel.sharding import fit_spec

            node = fit_spec(
                mesh, (leaf["q"] if isinstance(leaf, dict) else leaf).shape,
                leaf_spec(spec_path))
            with span("load_put", "load", leaf=leaf_name):
                if isinstance(leaf, dict):
                    q = jax.device_put(leaf["q"], NamedSharding(mesh, node))
                    s = jax.device_put(leaf["s"], NamedSharding(
                        mesh, scale_spec(leaf, node)))
                    return {"q": q, "s": s}
                return jax.device_put(leaf, NamedSharding(mesh, node))
        return leaf

    return put


def _assemble(source, put, tracer=None) -> dict:
    """Fold a (spec_path, host array) stream into the stacked pytree,
    placing each leaf as it arrives and freeing the host copy — peak
    host memory is one stacked leaf, not the dense model. Each pull from
    ``source`` (read, stack, transpose: _host_leaf_source is lazy) is one
    ``load_source`` span."""
    span = (tracer or NO_TRACER).span
    params: dict = {"layers": {}}
    it = iter(source)
    while True:
        with span("load_source", "load") as sp:
            item = next(it, None)
            if item is not None:
                sp.args["leaf"] = item[0][-1]
        if item is None:
            break
        spec_path, arr = item
        del item
        node = params
        for k in spec_path[:-1]:
            node = node[k]
        node[spec_path[-1]] = put(arr, spec_path)
        del arr
    return params


def _host_leaf_source(model_dir: str, cfg, quantize: str = ""):
    """-> (iterator of (spec_path, host np array), effective_quantize).

    The single checkpoint-format front door: GGUF (dequantized host-side
    by engine/gguf.py), HF safetensors (+GPTQ/AWQ detection, which may
    upgrade ``quantize`` — hence it is returned), or the RANDOM-weights
    bench gate. Iteration is leaf-at-a-time in every case; both
    load_llama_params and the ISSUE-19 streaming loader/prefetcher
    consume this."""
    gguf_path = find_gguf(model_dir)
    if gguf_path is not None:
        from localai_tpu.engine import gguf as gguflib

        g = gguflib.open_gguf(gguf_path)
        return gguflib.iter_llama_tensors(g, cfg), quantize
    try:
        tensors = _open_shards(model_dir)
    except FileNotFoundError:
        if os.environ.get("LOCALAI_ALLOW_RANDOM_WEIGHTS") == "1":
            # BENCH/TEST ONLY: a config.json-only dir serves random weights
            # through the same cast/quantize/shard path — lets the full
            # serving stack run benchmark-shaped models (e.g. 8B int8 on
            # one chip) without writing a multi-GB checkpoint to disk.
            # Gated: silently serving garbage from an incomplete real
            # checkpoint would be far worse than this convenience.
            return _iter_random_leaves(cfg), quantize
        raise

    def get(name: str) -> np.ndarray:
        h = tensors[name]
        return h.get_tensor(name)

    from localai_tpu.engine import gptq as gptqlib

    qmeta = gptqlib.detect(model_dir)
    if qmeta is not None and not quantize:
        # a GPTQ/AWQ checkpoint carries a memory intent; default to the
        # TPU-native weight-only int8 so loading it doesn't silently
        # inflate to dense bf16 (set quantization explicitly to override)
        quantize = "int8"

    L = cfg.num_layers

    def linear_T(name: str) -> np.ndarray:
        """Linear weight as [in, out]; GPTQ/AWQ-packed modules are
        dequantized host-side (engine/gptq.py) in that orientation."""
        base = name[: -len(".weight")]
        if qmeta is not None and base + ".qweight" in tensors:
            return gptqlib.dequant_linear(get, base, qmeta)
        return get(name).T

    def stack(fmt: str, transpose: bool = False) -> np.ndarray:
        mats = []
        for i in range(L):
            name = fmt.format(i=i)
            mats.append(linear_T(name) if transpose else get(name))
        return np.stack(mats)

    def gen():
        p = "model.layers.{i}."
        yield ("embed",), get("model.embed_tokens.weight")
        yield ("layers", "attn_norm"), stack(p + "input_layernorm.weight")
        yield ("layers", "wq"), stack(p + "self_attn.q_proj.weight", transpose=True)
        yield ("layers", "wk"), stack(p + "self_attn.k_proj.weight", transpose=True)
        yield ("layers", "wv"), stack(p + "self_attn.v_proj.weight", transpose=True)
        yield ("layers", "wo"), stack(p + "self_attn.o_proj.weight", transpose=True)
        yield ("layers", "mlp_norm"), stack(p + "post_attention_layernorm.weight")
        yield ("layers", "w_gate"), stack(p + "mlp.gate_proj.weight", transpose=True)
        yield ("layers", "w_up"), stack(p + "mlp.up_proj.weight", transpose=True)
        yield ("layers", "w_down"), stack(p + "mlp.down_proj.weight", transpose=True)
        yield ("final_norm",), get("model.norm.weight")
        if not cfg.tie_word_embeddings:
            yield ("lm_head",), linear_T("lm_head.weight")

    return gen(), quantize


def load_llama_params(
    model_dir: str,
    cfg,
    mesh=None,
    dtype=jnp.bfloat16,
    quantize: str = "",
    lora_adapter: str = "",
    lora_scale: float = 1.0,
    tracer=None,
) -> dict:
    """Load HF llama/mistral/qwen2-style weights into the stacked pytree.

    When ``mesh`` is given, each leaf is placed with the tensor-parallel
    sharding from parallel/sharding.py as it is assembled. quantize="int8"
    converts matmul weights to weight-only per-channel int8 at load time
    (reference parity: quantized GGUF serving). ``lora_adapter`` (a PEFT
    adapter dir) is merged into the weights as they stream (engine/lora.py).

    GGUF checkpoints (a .gguf path, or a dir holding one — what the
    ``ollama://``/``oci://`` puller produces) are dequantized host-side by
    engine/gguf.py and flow through the same cast/quantize/place path.
    """
    from localai_tpu.engine.lora import maybe_adapter

    adapter = maybe_adapter(lora_adapter, lora_scale)
    source, quantize = _host_leaf_source(model_dir, cfg, quantize)
    return _assemble(source, _make_put(cfg, mesh, dtype, quantize, adapter,
                                       tracer=tracer), tracer)


def _olmo_hybrid_leaf_source(model_dir: str, cfg):
    """(spec_path, host array) for models/olmo_hybrid.py's stacked layout:
    linear-attention leaves ``[periods, 3, ...]``, full-attention leaves
    ``[periods, ...]``, the MLP and the two post-norms of every layer
    ``[periods, 4, ...]``. Linear weights become ``[in, out]``; q|k|v (and
    a|b) projections are laid side by side for one matmul; the three
    depthwise convolutions ``[ch, 1, W]`` become one ``[W, ch]`` leaf."""
    tensors = _open_shards(model_dir)
    P_ = cfg.periods

    def top(name: str) -> np.ndarray:
        return tensors[name].get_tensor(name)

    def get(i: int, name: str) -> np.ndarray:
        return top(f"model.layers.{i}.{name}")

    def per_period(js, one) -> np.ndarray:
        """one(layer index) stacked [periods, len(js), ...]."""
        return np.stack([np.stack([one(4 * p + j) for j in js])
                         for p in range(P_)])

    def lin(one):
        return per_period((0, 1, 2), one)

    def full(name: str, transpose=False):
        a = per_period((3,), lambda i: get(i, name))[:, 0]
        return a.swapaxes(-1, -2) if transpose else a

    def every(name: str, transpose=False):
        a = per_period((0, 1, 2, 3), lambda i: get(i, name))
        return a.swapaxes(-1, -2) if transpose else a

    def side_by_side(i: int, names, conv=False):
        if conv:                        # [ch, 1, W] -> [W, ch]
            return np.concatenate(
                [get(i, f"linear_attn.{n}_conv1d.weight")[:, 0, :].T
                 for n in names], axis=-1)
        return np.concatenate(
            [get(i, f"linear_attn.{n}_proj.weight").T for n in names],
            axis=-1)

    la, sa = "linear_attn.", "self_attn."
    yield ("embed",), top("model.embed_tokens.weight")
    yield ("layers", "lin_qkv"), lin(lambda i: side_by_side(i, "qkv"))
    yield ("layers", "lin_g"), lin(lambda i: get(i, la + "g_proj.weight").T)
    yield ("layers", "lin_ab"), lin(lambda i: side_by_side(i, "ab"))
    yield ("layers", "lin_o"), lin(lambda i: get(i, la + "o_proj.weight").T)
    yield ("layers", "lin_conv"), lin(
        lambda i: side_by_side(i, "qkv", conv=True))
    yield ("layers", "lin_A_log"), lin(lambda i: get(i, la + "A_log"))
    yield ("layers", "lin_dt_bias"), lin(lambda i: get(i, la + "dt_bias"))
    yield ("layers", "lin_o_norm"), lin(
        lambda i: get(i, la + "o_norm.weight"))
    for leaf, name in (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o")):
        yield ("layers", leaf), full(sa + name + "_proj.weight", True)
    yield ("layers", "q_norm"), full(sa + "q_norm.weight")
    yield ("layers", "k_norm"), full(sa + "k_norm.weight")
    yield ("layers", "mixer_norm"), every("post_attention_layernorm.weight")
    yield ("layers", "mlp_norm"), every("post_feedforward_layernorm.weight")
    yield ("layers", "w_gate"), every("mlp.gate_proj.weight", True)
    yield ("layers", "w_up"), every("mlp.up_proj.weight", True)
    yield ("layers", "w_down"), every("mlp.down_proj.weight", True)
    yield ("final_norm",), top("model.norm.weight")
    if not cfg.tie_word_embeddings:
        yield ("lm_head",), top("lm_head.weight").T


def load_olmo_hybrid_params(model_dir: str, cfg, dtype=jnp.bfloat16,
                            quantize: str = "", tracer=None) -> dict:
    """Load an ``olmo_hybrid`` checkpoint (HF safetensors) through the
    same cast / int8 / placement path as ``load_llama_params``. No mesh:
    the family refuses one (models/olmo_hybrid.py)."""
    if quantize not in ("", "int8"):
        raise ValueError(f"quantization={quantize!r} is not supported for "
                         "olmo_hybrid (only weight-only int8)")
    return _assemble(_olmo_hybrid_leaf_source(model_dir, cfg),
                     _make_put(cfg, None, dtype, quantize, tracer=tracer),
                     tracer)


def _granite_hybrid_leaf_source(model_dir: str, cfg):
    """(spec_path, host array) for models/granite_hybrid.py's stacked
    layout: every leaf ``[periods, n, ...]`` with n the layers of its kind
    in a period. Linear weights become ``[in, out]``; ``mamba.in_proj`` is
    split into its z | xBC | dt columns and ``shared_mlp.input_linear``
    into gate | up, so that each is a matmul of its own width; the
    depthwise convolution ``[ch, 1, W]`` becomes ``[W, ch]``. The head is
    tied: the checkpoint holds no ``lm_head.weight``."""
    tensors = _open_shards(model_dir)
    n = len(cfg.period)
    Di, Ch, F = cfg.d_inner, cfg.conv_channels, cfg.intermediate_size

    def top(name: str) -> np.ndarray:
        return tensors[name].get_tensor(name)

    def stacked(kind, one) -> np.ndarray:
        """one(layer index) over the layers of ``kind`` (None: every
        layer), stacked [periods, layers of that kind a period, ...]."""
        js = [j for j, k in enumerate(cfg.period) if kind in (None, k)]
        return np.stack([np.stack([one(n * p + j) for j in js])
                         for p in range(cfg.periods)])

    def get(i: int, name: str) -> np.ndarray:
        return top(f"model.layers.{i}.{name}")

    def cols(name: str, lo: int, hi: int):
        """Columns [lo, hi) of a linear weight as [in, out]."""
        return lambda i: get(i, name)[lo:hi].T

    in_proj, mlp_in = "mamba.in_proj.weight", "shared_mlp.input_linear.weight"
    yield ("embed",), top("model.embed_tokens.weight")
    for leaf, lo, hi in (("ssm_in_z", 0, Di), ("ssm_in_xbc", Di, Di + Ch),
                         ("ssm_in_dt", Di + Ch, Di + Ch + cfg.ssm_heads)):
        yield ("layers", leaf), stacked("mamba", cols(in_proj, lo, hi))
    yield ("layers", "ssm_out"), stacked(
        "mamba", lambda i: get(i, "mamba.out_proj.weight").T)
    yield ("layers", "ssm_conv"), stacked(
        "mamba", lambda i: get(i, "mamba.conv1d.weight")[:, 0, :].T)
    for leaf, name in (("ssm_conv_b", "conv1d.bias"), ("ssm_A_log", "A_log"),
                       ("ssm_D", "D"), ("ssm_dt_bias", "dt_bias"),
                       ("ssm_norm", "norm.weight")):
        yield ("layers", leaf), stacked(
            "mamba", lambda i, name=name: get(i, "mamba." + name))
    for leaf, name in (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o")):
        yield ("layers", leaf), stacked(
            "attention",
            lambda i, name=name: get(i, f"self_attn.{name}_proj.weight").T)
    yield ("layers", "attn_norm"), stacked(
        None, lambda i: get(i, "input_layernorm.weight"))
    yield ("layers", "mlp_norm"), stacked(
        None, lambda i: get(i, "post_attention_layernorm.weight"))
    yield ("layers", "w_gate"), stacked(None, cols(mlp_in, 0, F))
    yield ("layers", "w_up"), stacked(None, cols(mlp_in, F, 2 * F))
    yield ("layers", "w_down"), stacked(
        None, lambda i: get(i, "shared_mlp.output_linear.weight").T)
    yield ("final_norm",), top("model.norm.weight")
    if not cfg.tie_word_embeddings:
        yield ("lm_head",), top("lm_head.weight").T


def load_granite_hybrid_params(model_dir: str, cfg, dtype=jnp.bfloat16,
                               quantize: str = "", tracer=None) -> dict:
    """Load a ``granitemoehybrid`` checkpoint (HF safetensors) through the
    same cast / int8 / placement path as ``load_llama_params``. No mesh:
    the family refuses one (models/granite_hybrid.py)."""
    if quantize not in ("", "int8"):
        raise ValueError(f"quantization={quantize!r} is not supported for "
                         "granite_hybrid (only weight-only int8)")
    return _assemble(_granite_hybrid_leaf_source(model_dir, cfg),
                     _make_put(cfg, None, dtype, quantize, tracer=tracer),
                     tracer)


def _lfm2_moe_leaf_source(model_dir: str, cfg):
    """(spec_path, host array) for models/lfm2_moe.py's stacked layout, all
    but the expert stacks: every leaf stacked over the layers that hold
    one (conv layers, attention layers, dense layers, expert layers, every
    layer). Linear weights become ``[in, out]``; the depthwise convolution
    ``[D, 1, W]`` becomes ``[W, D]``; the router and its bias stay
    float32. The head is tied: no ``lm_head.weight``."""
    tensors = _open_shards(model_dir)
    nd = cfg.num_dense_layers

    def top(name: str) -> np.ndarray:
        return tensors[name].get_tensor(name)

    def stacked(which, one) -> np.ndarray:
        return np.stack([one(i) for i in which])

    def get(name: str, transpose=False, dtype=None):
        def one(i):
            a = top(f"model.layers.{i}.{name}")
            a = a.T if transpose else a
            return a.astype(dtype) if dtype else a
        return one

    every = range(cfg.num_layers)
    conv = [i for i, k in enumerate(cfg.kinds) if k == "conv"]
    attn = [i for i, k in enumerate(cfg.kinds) if k == "attention"]
    dense, routed = range(nd), range(nd, cfg.num_layers)
    yield ("embed",), top("model.embed_tokens.weight")
    yield ("layers", "op_norm"), stacked(every, get("operator_norm.weight"))
    yield ("layers", "ff_norm"), stacked(every, get("ffn_norm.weight"))
    yield ("layers", "conv_in"), stacked(
        conv, get("conv.in_proj.weight", True))
    yield ("layers", "conv_w"), stacked(
        conv, lambda i: top(f"model.layers.{i}.conv.conv.weight")[:, 0, :].T)
    yield ("layers", "conv_out"), stacked(
        conv, get("conv.out_proj.weight", True))
    for leaf, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                       ("wo", "out_proj")):
        yield ("layers", leaf), stacked(
            attn, get(f"self_attn.{name}.weight", True))
    yield ("layers", "q_norm"), stacked(
        attn, get("self_attn.q_layernorm.weight"))
    yield ("layers", "k_norm"), stacked(
        attn, get("self_attn.k_layernorm.weight"))
    for leaf, name in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
        yield ("layers", leaf), stacked(
            dense, get(f"feed_forward.{name}.weight", True))
    yield ("layers", "router"), stacked(
        routed, get("feed_forward.gate.weight", True, np.float32))
    yield ("layers", "expert_bias"), stacked(
        routed, get("feed_forward.expert_bias", False, np.float32))
    yield ("final_norm",), top("model.embedding_norm.weight")


def _expert_stack(tensors, tensor_name, n_layers: int, ids, leaf_name: str,
                  dtype, span):
    """One projection of the experts ``ids`` of every expert layer as ONE
    device leaf ``[n_layers, len(ids), in, out]``, streamed a layer at a
    time: ``tensor_name(mi, e)`` is expert ``e``'s tensor (HF ``[out,
    in]``) in expert layer ``mi``. The host holds one layer's tensors, the
    device casts and transposes them into the leaf in place (the leaf is
    donated to its own update), so neither side ever holds a second copy of
    the stack. ``ids`` are the experts THIS CHIP holds (all of them, or a
    share's sorted global ids): no other expert's tensor is read, and a
    checkpoint need not have it."""
    ids = list(ids)

    @partial(jax.jit, donate_argnums=0)
    def set_layer(leaf, rows, mi):
        return jax.lax.dynamic_update_index_in_dim(
            leaf, rows.astype(dtype).swapaxes(-1, -2), mi, 0)

    leaf = None
    for mi in range(n_layers):
        with span("load_source", "load", leaf=leaf_name):
            rows = np.stack([tensors[n].get_tensor(n) for n in (
                tensor_name(mi, e) for e in ids)])
        with span("load_cast", "load", leaf=leaf_name):
            rows = jnp.asarray(rows)
            if leaf is None:
                leaf = jnp.zeros((n_layers, len(ids)) + rows.shape[:0:-1],
                                 dtype)
        with span("load_put", "load", leaf=leaf_name):
            leaf = set_layer(leaf, rows, mi)
        del rows
    return leaf


def load_lfm2_moe_params(model_dir: str, cfg, dtype=jnp.bfloat16,
                         quantize: str = "", tracer=None) -> dict:
    """Load an ``lfm2_moe`` checkpoint (HF safetensors): every leaf but
    the expert stacks through the cast / placement path of
    ``load_llama_params``, the three expert stacks a layer at a time
    (``_expert_stack``). No mesh: the family refuses one.
    ``quantize="int8"`` takes the leaves ops/quant.py's quantizer takes (a
    whole host leaf: the operators' projections, the dense feed-forwards,
    the embedding); the expert stacks, nine tenths of the bytes, stay in
    ``dtype``: quantized expert leaves are not built."""
    if quantize not in ("", "int8"):
        raise ValueError(f"quantization={quantize!r} is not supported for "
                         "lfm2_moe (only weight-only int8)")
    if quantize:
        log.warning("lfm2_moe: quantization=int8 leaves the expert stacks "
                    "in %s (quantized expert leaves are not built)",
                    jnp.dtype(dtype).name)
    span = (tracer or NO_TRACER).span
    cast = _make_put(cfg, None, dtype, quantize, tracer=tracer)

    def put(arr, spec_path):
        # the router computes its scores in float32: its leaves are not cast
        if spec_path[-1] in ("router", "expert_bias"):
            return jnp.asarray(arr, jnp.float32)
        return cast(arr, spec_path)

    params = _assemble(_lfm2_moe_leaf_source(model_dir, cfg), put, tracer)
    tensors = _open_shards(model_dir)
    nd = cfg.num_dense_layers
    for name in ("w1", "w3", "w2"):
        params["layers"][name] = _expert_stack(
            tensors, lambda mi, e, name=name: (
                f"model.layers.{nd + mi}.feed_forward.experts.{e}."
                f"{name}.weight"),
            cfg.moe_layers, range(cfg.num_experts), name, dtype, span)
    return params


def _ling_hybrid_leaf_source(model_dir: str, cfg):
    """(spec_path, host array) for models/ling_hybrid.py's stacked layout,
    all but the expert stacks: every leaf stacked over the layers that hold
    one (KDA layers, MLA layers, dense layers, expert layers, every layer).
    Linear weights become ``[in, out]``; KDA's three depthwise convolutions
    ``[HK, 1, W]`` become one ``[W, 3HK]`` (q | k | v channels, as the
    fused projection has them); the router, its bias, ``A_log`` and
    ``dt_bias`` stay float32."""
    tensors = _open_shards(model_dir)
    nd = cfg.num_dense_layers

    def top(name: str) -> np.ndarray:
        return tensors[name].get_tensor(name)

    def stacked(which, one) -> np.ndarray:
        return np.stack([one(i) for i in which])

    def get(name: str, transpose=False, dtype=None):
        def one(i):
            a = top(f"model.layers.{i}.{name}")
            a = a.T if transpose else a
            return a.astype(dtype) if dtype else a
        return one

    def fused(names, one_of):
        return lambda i: np.concatenate([one_of(n)(i) for n in names], -1)

    every = range(cfg.num_layers)
    kda = [i for i, m in enumerate(cfg.mixers) if m == "kda"]
    mla = [i for i, m in enumerate(cfg.mixers) if m == "mla"]
    dense, routed = range(nd), range(nd, cfg.num_layers)
    f32 = np.float32
    yield ("embed",), top("model.embed_tokens.weight")
    yield ("layers", "mix_norm"), stacked(every, get("input_layernorm.weight"))
    yield ("layers", "ff_norm"), stacked(
        every, get("post_attention_layernorm.weight"))
    a = "linear_attn."
    yield ("layers", "kda_qkv"), stacked(kda, fused(
        ("q", "k", "v"), lambda n: get(f"{a}{n}_proj.weight", True)))
    yield ("layers", "kda_conv"), stacked(kda, fused(
        ("q", "k", "v"),
        lambda n: lambda i: top(
            f"model.layers.{i}.{a}{n}_conv1d.weight")[:, 0, :].T))
    yield ("layers", "kda_f"), stacked(kda, get(a + "f_proj.weight", True))
    yield ("layers", "kda_dt_bias"), stacked(kda, get(a + "dt_bias", False,
                                                      f32))
    yield ("layers", "kda_A_log"), stacked(kda, get(a + "A_log", False, f32))
    yield ("layers", "kda_b"), stacked(kda, get(a + "b_proj.weight", True))
    yield ("layers", "kda_g"), stacked(kda, get(a + "g_proj.weight", True))
    yield ("layers", "kda_o_norm"), stacked(kda, get(a + "o_norm.weight"))
    yield ("layers", "kda_o"), stacked(kda, get(a + "o_proj.weight", True))
    a = "self_attn."
    for leaf, name, t in (("mla_q", "q_proj.weight", True),
                          ("mla_q_norm", "q_norm.weight", False),
                          ("mla_kva", "kv_a_proj_with_mqa.weight", True),
                          ("mla_kv_norm", "kv_a_layernorm.weight", False),
                          ("mla_kvb", "kv_b_proj.weight", True),
                          ("mla_gate", "g_proj.weight", True),
                          ("mla_o", "o_proj.weight", True)):
        yield ("layers", leaf), stacked(mla, get(a + name, t))
    for leaf, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                       ("w_down", "down_proj")):
        yield ("layers", leaf), stacked(dense, get(f"mlp.{name}.weight", True))
    yield ("layers", "router"), stacked(
        routed, get("mlp.gate.weight", True, f32))
    yield ("layers", "expert_bias"), stacked(
        routed, get("mlp.gate.expert_bias", False, f32))
    for leaf, name in (("sh_w1", "gate_proj"), ("sh_w3", "up_proj"),
                       ("sh_w2", "down_proj")):
        yield ("layers", leaf), stacked(
            routed, get(f"mlp.shared_experts.{name}.weight", True))
    yield ("final_norm",), top("model.norm.weight")
    if not cfg.tie_word_embeddings:
        yield ("lm_head",), top("lm_head.weight").T


def load_ling_hybrid_params(model_dir: str, cfg, dtype=jnp.bfloat16,
                            quantize: str = "", tracer=None) -> dict:
    """Load a ``ling_hybrid`` checkpoint (HF safetensors): every leaf but
    the expert stacks through the cast / placement path of
    ``load_llama_params``, the three expert stacks a layer at a time and
    only the experts this chip holds (``_expert_stack`` over ``cfg.held``).
    No mesh: the family refuses one. ``quantize="int8"`` takes the leaves
    ops/quant.py's quantizer takes (the mixers' projections, the dense and
    shared feed-forwards, the embedding and the head); the expert stacks
    stay in ``dtype``: quantized expert leaves are not built."""
    if quantize not in ("", "int8"):
        raise ValueError(f"quantization={quantize!r} is not supported for "
                         "ling_hybrid (only weight-only int8)")
    if quantize:
        log.warning("ling_hybrid: quantization=int8 leaves the expert "
                    "stacks in %s (quantized expert leaves are not built)",
                    jnp.dtype(dtype).name)
    span = (tracer or NO_TRACER).span
    cast = _make_put(cfg, None, dtype, quantize, tracer=tracer)
    keep_f32 = ("router", "expert_bias", "kda_A_log", "kda_dt_bias")

    def put(arr, spec_path):
        if spec_path[-1] in keep_f32:
            return jnp.asarray(arr, jnp.float32)
        return cast(arr, spec_path)

    params = _assemble(_ling_hybrid_leaf_source(model_dir, cfg), put, tracer)
    tensors = _open_shards(model_dir)
    nd = cfg.num_dense_layers
    held = range(cfg.num_experts) if cfg.held is None else cfg.held
    for leaf, name in (("w1", "gate_proj"), ("w3", "up_proj"),
                       ("w2", "down_proj")):
        params["layers"][leaf] = _expert_stack(
            tensors, lambda mi, e, name=name: (
                f"model.layers.{nd + mi}.mlp.experts.{e}.{name}.weight"),
            cfg.moe_layers, held, leaf, dtype, span)
    return params


def stream_llama_params(
    model_dir: str,
    cfg,
    mesh=None,
    dtype=jnp.bfloat16,
    quantize: str = "",
    lora_adapter: str = "",
    lora_scale: float = 1.0,
    prefetcher: "Optional[WeightPrefetcher]" = None,
    tracer=None,
) -> tuple:
    """Streaming variant of :func:`load_llama_params` -> (params, stats).

    Same leaves, same cast/quantize/place path, two differences (ISSUE
    19, the warm scale-out / gallery-swap spin-up path):

    * a per-leaf pace hook — an explicit GIL yield so serving sibling
      threads keep their cadence while a multi-GB load streams, plus the
      ``weight_stream_slow_ms`` chaos seam (a slow disk/NFS source must
      degrade the LOAD, never the siblings);
    * when ``prefetcher`` holds this model's parsed leaves (predicted
      ahead of time from the gallery request log), the file-read /
      GPTQ-dequant / per-layer stack work is already paid — the warm
      path only casts and places, which is the measured SWAP_WARM_MS
      win.

    ``stats``: {leaves, bytes, prefetch_hit, ms}.
    """
    from localai_tpu.engine.lora import maybe_adapter
    from localai_tpu.services.faults import FAULTS

    t0 = time.monotonic()
    stats = {"leaves": 0, "bytes": 0, "prefetch_hit": False, "ms": 0.0}

    def pace(arr):
        stats["leaves"] += 1
        stats["bytes"] += int(arr.nbytes)
        if FAULTS.active:
            ms = FAULTS.take("weight_stream_slow_ms")
            if ms:
                time.sleep(min(30.0, float(ms) / 1000.0))
        time.sleep(0)   # explicit GIL yield between leaves

    adapter = maybe_adapter(lora_adapter, lora_scale)
    entry = prefetcher.consume(model_dir) if prefetcher is not None else None
    if entry is not None:
        stats["prefetch_hit"] = True
        source = iter(entry.leaves)
        if not quantize:
            quantize = entry.quantize
    else:
        source, quantize = _host_leaf_source(model_dir, cfg, quantize)
    params = _assemble(
        source, _make_put(cfg, mesh, dtype, quantize, adapter, pace=pace,
                          tracer=tracer), tracer)
    stats["ms"] = (time.monotonic() - t0) * 1000.0
    return params, stats


class _PrefetchEntry:
    __slots__ = ("leaves", "quantize", "nbytes")

    def __init__(self, leaves, quantize, nbytes):
        self.leaves = leaves        # [(spec_path, host np array), ...]
        self.quantize = quantize    # effective (GPTQ detection applied)
        self.nbytes = nbytes


class WeightPrefetcher:
    """Host-side parsed-leaf cache for predicted-next models (ISSUE 19,
    PRESERVE-style).

    ``prefetch()`` parses a checkpoint into its final host leaves (file
    reads, GPTQ dequant, per-layer stacking, cast to the serving dtype —
    the expensive host half of a load) on a background thread, bounded
    by ``budget_mb``; a later ``stream_llama_params(..., prefetcher=...)``
    for that model consumes the entry and only pays device placement of
    already-device-dtype bytes (half the volume for a bf16 load of an
    f32 checkpoint). Entries are popped on consume (the leaves feed
    placement directly; keeping them would double host RAM) and
    abandoned — not trimmed — when a model exceeds the budget: a partial
    cache can't make a load warm.
    """

    def __init__(self, budget_mb: int = 8192):
        self.budget_bytes = max(1, int(budget_mb)) * 1024 * 1024
        self._cache: dict = {}      # model_dir -> _PrefetchEntry
        self._inflight: dict = {}   # model_dir -> Thread
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.bytes_total = 0        # bytes warmed into cache, lifetime
        self.prefetches = 0         # completed warms
        self.aborted = 0            # over-budget / failed warms

    def prefetch(self, model_dir: str, cfg, quantize: str = "",
                 dtype=jnp.bfloat16, wait: bool = False):
        """Warm ``model_dir`` in the background (idempotent while cached
        or in flight). ``dtype`` is the serving dtype the eventual load
        will request — unquantized leaves are pre-cast to it host-side
        so the consume path places the exact device bytes. ``wait=True``
        blocks until the warm finishes — bench/test use; production
        callers fire and forget."""
        with self._lock:
            t = self._inflight.get(model_dir)
            if t is None and model_dir not in self._cache:
                t = threading.Thread(
                    target=self._warm,
                    args=(model_dir, cfg, quantize, dtype),
                    name="weight-prefetch", daemon=True)
                self._inflight[model_dir] = t
                t.start()
        if wait and t is not None:
            t.join()

    def _warm(self, model_dir: str, cfg, quantize: str, dtype=None):
        try:
            source, q = _host_leaf_source(model_dir, cfg, quantize)
            leaves, total = [], 0
            for spec_path, arr in source:
                arr = np.asarray(arr)
                if dtype is not None and not q:
                    # pre-cast to the serving dtype: quantized loads keep
                    # f32 (quantize_weight wants full precision); a later
                    # load at a different dtype just re-casts — correct,
                    # merely not warm
                    arr = np.ascontiguousarray(arr.astype(dtype))
                total += int(arr.nbytes)
                if total > self.budget_bytes:
                    # abandon, don't trim: a partial cache still pays
                    # the cold path and would pin host RAM for nothing
                    self.aborted += 1
                    log.warning("weight prefetch of %s abandoned: %d B "
                                "exceeds budget %d B", model_dir, total,
                                self.budget_bytes)
                    return
                leaves.append((spec_path, arr))
                time.sleep(0)   # same politeness as the streaming load
            with self._lock:
                self._cache[model_dir] = _PrefetchEntry(leaves, q, total)
                self.bytes_total += total
                self.prefetches += 1
        except Exception:
            self.aborted += 1
            log.warning("weight prefetch of %s failed", model_dir,
                        exc_info=True)
        finally:
            with self._lock:
                self._inflight.pop(model_dir, None)

    def consume(self, model_dir: str) -> Optional[_PrefetchEntry]:
        """Pop the cached entry for a model about to load (hit), or None
        (miss — counted either way, exported as the hit/miss metrics)."""
        with self._lock:
            e = self._cache.pop(model_dir, None)
        if e is None:
            self.misses += 1
            return None
        self.hits += 1
        return e

    def cached(self, model_dir: str) -> bool:
        with self._lock:
            return model_dir in self._cache

    def snapshot(self) -> dict:
        with self._lock:
            cached = {d: e.nbytes for d, e in self._cache.items()}
        return {"hits": self.hits, "misses": self.misses,
                "bytes_total": self.bytes_total,
                "prefetches": self.prefetches, "aborted": self.aborted,
                "cached": cached,
                "budget_bytes": self.budget_bytes}


def random_params(cfg, dtype=jnp.bfloat16, quantize: str = "") -> dict:
    """Public entry for benchmark-shaped random weights: leaf-at-a-time
    host init streamed through the standard cast/quantize/place path, so
    an 8B never exists densely in f32 (32 GB) on host or device."""
    return _random_llama_params(cfg, _make_put(cfg, None, dtype, quantize))


def _iter_random_leaves(cfg):
    """Leaf-at-a-time random weights (see the gate in _host_leaf_source)."""
    rng = np.random.default_rng(0)
    hd = cfg.head_dim_
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    H, KV, V = cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size

    def mk(shape, fan_in):
        a = rng.standard_normal(shape, dtype=np.float32)
        a /= np.float32(np.sqrt(fan_in))
        return a

    leaves = [
        (("embed",), lambda: mk((V, D), D)),
        (("layers", "attn_norm"), lambda: np.ones((L, D), np.float32)),
        (("layers", "wq"), lambda: mk((L, D, H * hd), D)),
        (("layers", "wk"), lambda: mk((L, D, KV * hd), D)),
        (("layers", "wv"), lambda: mk((L, D, KV * hd), D)),
        (("layers", "wo"), lambda: mk((L, H * hd, D), H * hd)),
        (("layers", "mlp_norm"), lambda: np.ones((L, D), np.float32)),
        (("layers", "w_gate"), lambda: mk((L, D, F), D)),
        (("layers", "w_up"), lambda: mk((L, D, F), D)),
        (("layers", "w_down"), lambda: mk((L, F, D), F)),
        (("final_norm",), lambda: np.ones((D,), np.float32)),
    ]
    if not cfg.tie_word_embeddings:
        leaves.append((("lm_head",), lambda: mk((D, V), D)))
    for spec_path, gen in leaves:
        yield spec_path, gen()


def _random_llama_params(cfg, put) -> dict:
    return _assemble(_iter_random_leaves(cfg), put)


def save_llama_params(params: dict, cfg, model_dir: str):
    """Write params back to HF layout (single shard). Test/export helper."""
    from safetensors.numpy import save_file

    os.makedirs(model_dir, exist_ok=True)
    out = {}
    ly = params["layers"]
    np32 = lambda a: np.asarray(jax.device_get(a), np.float32)
    out["model.embed_tokens.weight"] = np32(params["embed"])
    out["model.norm.weight"] = np32(params["final_norm"])
    if "lm_head" in params:
        out["lm_head.weight"] = np32(params["lm_head"]).T
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = np32(ly["attn_norm"][i])
        out[p + "self_attn.q_proj.weight"] = np32(ly["wq"][i]).T
        out[p + "self_attn.k_proj.weight"] = np32(ly["wk"][i]).T
        out[p + "self_attn.v_proj.weight"] = np32(ly["wv"][i]).T
        out[p + "self_attn.o_proj.weight"] = np32(ly["wo"][i]).T
        out[p + "post_attention_layernorm.weight"] = np32(ly["mlp_norm"][i])
        out[p + "mlp.gate_proj.weight"] = np32(ly["w_gate"][i]).T
        out[p + "mlp.up_proj.weight"] = np32(ly["w_up"][i]).T
        out[p + "mlp.down_proj.weight"] = np32(ly["w_down"][i]).T
    save_file(out, os.path.join(model_dir, "model.safetensors"))


def xing4_mtp_tensors(names, cfg) -> list:
    """The checkpoint's tensors that belong to the multi-token prediction
    module: DeepSeek-V3's layout puts it after the last layer, as
    ``model.layers.<num_hidden_layers>.*`` and beyond. The next-token model
    reads none of them."""
    out = []
    for name in names:
        parts = name.split(".")
        if parts[:2] == ["model", "layers"] and parts[2].isdigit() \
                and int(parts[2]) >= cfg.num_layers:
            out.append(name)
    return out


def _xing4_leaf_source(model_dir: str, cfg):
    """(spec_path, host array) for models/xing4.py's stacked layout, all
    but the expert stacks: every leaf stacked over the layers that hold one
    (every layer, dense layers, expert layers). Linear weights become
    ``[in, out]``; a layer's two hyper-connections (mixer, feed-forward)
    stack as ``[2, ...]``; the router, its bias and the hyper-connections'
    scales and biases stay float32."""
    tensors = _open_shards(model_dir)
    nd = cfg.num_dense_layers

    def top(name: str) -> np.ndarray:
        return tensors[name].get_tensor(name)

    def stacked(which, one) -> np.ndarray:
        return np.stack([one(i) for i in which])

    def get(name: str, transpose=False, dtype=None):
        def one(i):
            a = top(f"model.layers.{i}.{name}")
            a = a.T if transpose else a
            return a.astype(dtype) if dtype else a
        return one

    def pair(name, transpose=False, dtype=None):
        """The mixer's and the feed-forward's hyper-connection of a layer."""
        return lambda i: np.stack([get(f"{sub}_hc.{name}", transpose,
                                       dtype)(i) for sub in ("attn", "mlp")])

    every = range(cfg.num_layers)
    dense, routed = range(nd), range(nd, cfg.num_layers)
    f32 = np.float32
    yield ("embed",), top("model.embed_tokens.weight")
    yield ("layers", "mix_norm"), stacked(every, get("input_layernorm.weight"))
    yield ("layers", "ff_norm"), stacked(
        every, get("post_attention_layernorm.weight"))
    a = "self_attn."
    for leaf, name, t in (("mla_qa", "q_a_proj.weight", True),
                          ("mla_q_norm", "q_a_layernorm.weight", False),
                          ("mla_qb", "q_b_proj.weight", True),
                          ("mla_kva", "kv_a_proj_with_mqa.weight", True),
                          ("mla_kv_norm", "kv_a_layernorm.weight", False),
                          ("mla_kvb", "kv_b_proj.weight", True),
                          ("mla_o", "o_proj.weight", True)):
        yield ("layers", leaf), stacked(every, get(a + name, t))
    if cfg.hc_mult > 1:
        yield ("layers", "hc_w"), stacked(every, pair("weight", True))
        yield ("layers", "hc_s"), stacked(every, pair("scale", False, f32))
        yield ("layers", "hc_b"), stacked(every, pair("bias", False, f32))
        yield ("hc_head_w",), top("model.hc_head.weight").T
        yield ("hc_head_s",), top("model.hc_head.scale").astype(f32)
        yield ("hc_head_b",), top("model.hc_head.bias").astype(f32)
    for leaf, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                       ("w_down", "down_proj")) if nd else ():
        yield ("layers", leaf), stacked(dense, get(f"mlp.{name}.weight", True))
    if routed:
        yield ("layers", "router"), stacked(
            routed, get("mlp.gate.weight", True, f32))
        yield ("layers", "expert_bias"), stacked(
            routed, get("mlp.gate.e_score_correction_bias", False, f32))
    for leaf, name in (("sh_w1", "gate_proj"), ("sh_w3", "up_proj"),
                       ("sh_w2", "down_proj")) if routed else ():
        yield ("layers", leaf), stacked(
            routed, get(f"mlp.shared_experts.{name}.weight", True))
    yield ("final_norm",), top("model.norm.weight")
    if not cfg.tie_word_embeddings:
        yield ("lm_head",), top("lm_head.weight").T


def load_xing4_params(model_dir: str, cfg, dtype=jnp.bfloat16,
                      quantize: str = "", tracer=None) -> dict:
    """Load a ``xing4_0`` checkpoint (HF safetensors): every leaf but the
    expert stacks through the cast / placement path of
    ``load_llama_params``, the three expert stacks a layer at a time, all
    ``cfg.num_experts`` held (``_expert_stack``, as lfm2_moe's). No mesh:
    the family refuses one. The multi-token prediction module's tensors
    are skipped by name and counted (``xing4_mtp_tensors``).
    ``quantize="int8"`` takes the leaves ops/quant.py's quantizer takes (the
    mixers' projections, the dense and shared feed-forwards, the embedding
    and the head); the expert stacks and the hyper-connections stay in
    ``dtype``."""
    if quantize not in ("", "int8"):
        raise ValueError(f"quantization={quantize!r} is not supported for "
                         "xing4_0 (only weight-only int8)")
    if quantize:
        log.warning("xing4_0: quantization=int8 leaves the expert stacks "
                    "in %s (quantized expert leaves are not built)",
                    jnp.dtype(dtype).name)
    span = (tracer or NO_TRACER).span
    cast = _make_put(cfg, None, dtype, quantize, tracer=tracer)
    keep_f32 = ("router", "expert_bias", "hc_s", "hc_b", "hc_head_s",
                "hc_head_b")

    def put(arr, spec_path):
        if spec_path[-1] in keep_f32:
            return jnp.asarray(arr, jnp.float32)
        return cast(arr, spec_path)

    tensors = _open_shards(model_dir)
    skipped = xing4_mtp_tensors(tensors, cfg)
    if skipped:
        log.info("xing4_0: skipped %d tensors of the multi-token prediction "
                 "module (model.layers.%d.* and beyond): the next-token "
                 "model reads none", len(skipped), cfg.num_layers)
    params = _assemble(_xing4_leaf_source(model_dir, cfg), put, tracer)
    nd = cfg.num_dense_layers
    if cfg.moe_layers:
        for leaf, name in (("w1", "gate_proj"), ("w3", "up_proj"),
                           ("w2", "down_proj")):
            params["layers"][leaf] = _expert_stack(
                tensors, lambda mi, e, name=name: (
                    f"model.layers.{nd + mi}.mlp.experts.{e}.{name}.weight"),
                cfg.moe_layers, range(cfg.num_experts), leaf, dtype, span)
    return params
