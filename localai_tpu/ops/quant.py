"""Weight-only quantization — the ONE {q, s} contract every LLM family
shares (llama, mamba, rwkv).

Capability parity: the reference serves quantized GGUF (Q4/Q8) by
default; the TPU-native analogues are
  * per-out-channel symmetric int8 ({q: int8 [..., in, out],
    s: f32 [..., 1, out]}) — XLA fuses the cast + scale into the
    consuming matmul, so the MXU consumes dequantized tiles while HBM
    reads stay int8 (measured ~2.2x faster than bf16 matmuls on the
    serving chip);
  * group-wise symmetric int4 ({q: int4 [..., in, out],
    s: f32 [..., in/g, 1, out]}) — jnp.int4 packs two values/byte in
    HBM, halving weight traffic again where decode is bandwidth-bound;
    group scales along the contraction axis (GPTQ's layout) keep the
    4-bit rounding loss per-group instead of per-column.
The two forms are discriminated by scale rank (grouped scales carry one
extra axis), so ``mat`` is the single dequant point for every family.
shard_params' scale-spec handling and the XLA fusion pattern both depend
on these exact layouts, so they live in one place.

Where it runs: both quantizers run on the HOST, in numpy float32 (a TPU's
float32 divide need not round as numpy's does, and a model that fills the
chip in int8 has no room there for a stacked leaf in float16 and float32),
by independent column blocks on a pool of host threads
(ops/hostblocks.py), and hand the finished int8 array to the device. The
blocks see quantize_weight*'s formula numpy call for numpy call, so q and s
are bit for bit what the one-thread whole-array form of it gives
(tests/test_quant_blocked.py has that form written out), whatever the
thread count and for every input layout.
"""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp
import numpy as np

from localai_tpu.ops import hostblocks


def _quantize_block(scratch, w, q, s, lo: int, hi: int):
    """One block of a leaf, grouped: ``w``, ``q`` [..., G, g, c] and ``s``
    [..., G, 1, c] -> q = clip(rint(w / s), lo, hi) with s = max|w| / hi
    over the g rows of a group, floored at 1e-12: quantize_weight*'s
    formula, numpy call for numpy call in float32, over ``CHUNK_ELEMS``
    values at a time in the thread's scratch (a max taken in parts is the
    same max)."""
    g = w.shape[-2]
    rows = max(1, hostblocks.CHUNK_ELEMS // max(1, w.size // g))
    shape = w.shape[:-2] + (min(rows, g), w.shape[-1])
    x, t = scratch.get("x", shape), scratch.get("t", shape)
    part = scratch.get("max", s.shape)
    for r in range(0, g, rows):
        n = min(rows, g - r)
        xs, ts = x[..., :n, :], t[..., :n, :]
        np.copyto(xs, w[..., r:r + n, :], casting="unsafe")
        np.abs(xs, out=ts)
        np.max(ts, axis=-2, keepdims=True, out=part if r else s)
        if r:
            np.maximum(s, part, out=s)
    np.divide(s, float(hi), out=s)
    np.maximum(s, 1e-12, out=s)
    for r in range(0, g, rows):
        n = min(rows, g - r)
        xs = x[..., :n, :]
        if rows < g:                # else the block's float32 is still there
            np.copyto(xs, w[..., r:r + n, :], casting="unsafe")
        np.divide(xs, s, out=xs)
        np.rint(xs, out=xs)
        np.clip(xs, lo, hi, out=xs)
        np.copyto(q[..., r:r + n, :], xs, casting="unsafe")


def _quantize_grouped(w, g: int, lo: int, hi: int, threads, ran):
    """Host half of both quantizers: ``w`` [..., in, out] in groups of ``g``
    along the contraction axis -> (q int8 [..., in/g, g, out], s float32
    [..., in/g, 1, out]), fresh arrays, by blocks (ops/hostblocks.py)."""
    lead, (cin, out) = w.shape[:-2], w.shape[-2:]
    w = w.reshape(*lead, cin // g, g, out)      # splits an axis: a view
    q = np.empty(w.shape, np.int8)
    s = np.empty((*lead, cin // g, 1, out), np.float32)
    how = hostblocks.run_blocks(
        partial(_quantize_block, lo=lo, hi=hi), (w, q, s), len(lead),
        w.ndim - 1, hostblocks.BLOCK_COLS, threads)
    if ran is not None:
        ran.update(how)
    return q, s


def quantize_weight(w, threads=None, ran=None) -> dict:
    """[..., in, out] float weight -> {"q": int8, "s": f32 per-out-channel
    scale}. The scale reduces ONLY the contraction (second-to-last) axis,
    so stacked [L, in, out] weights keep per-layer scales.

    ``threads`` forces the width of the host pool (tests; by default the
    process's cores, and one for a small leaf); ``ran``, a dict, receives
    how the pass ran ({"threads", "blocks"}: the loader's span arguments)."""
    w = np.asarray(w)
    q, s = _quantize_grouped(w, w.shape[-2], -127, 127, threads, ran)
    return {"q": jnp.asarray(q.reshape(w.shape)),
            "s": jnp.asarray(s.reshape(*w.shape[:-2], 1, w.shape[-1]),
                             jnp.float32)}


def pick_int4_group(cin: int, group: int = 128, shard_divisor: int = 1):
    """Largest group size <= ``group`` whose count divides evenly into
    both the contraction axis and ``shard_divisor`` tp shards (so the
    grouped scale's group axis stays shardable alongside a row-parallel
    weight). None when no group >= 16 qualifies (caller falls back to
    int8). E.g. llama-2's 11008 FFN with tp=8: 128 gives 86 groups (not
    divisible by 8) -> picks 86 (128 groups)."""
    for g in range(min(group, cin), 15, -1):
        if cin % g == 0 and (cin // g) % shard_divisor == 0:
            return g
    return None


def quantize_weight_int4(w, group: int = 128, shard_divisor: int = 1,
                         threads=None, ran=None) -> dict:
    """[..., in, out] float weight -> {"q": int4, "s": f32 group scale
    [..., in/g, 1, out]}. Symmetric round-to-nearest over [-8, 7] with
    max-abs group scales — the data layout (not the Hessian search) of
    GPTQ, so real GPTQ checkpoints can map onto it losslessly.

    The effective group size is pick_int4_group(...): at most ``group``,
    adjusted so the group count divides ``shard_divisor`` (the tp degree
    on the contraction axis, when known at load time). Falls back to
    per-channel int8 when no viable group exists (tiny test models)."""
    w = np.asarray(w)
    g = pick_int4_group(w.shape[-2], group, shard_divisor)
    if g is None:
        return quantize_weight(w, threads, ran)
    q, s = _quantize_grouped(w, g, -8, 7, threads, ran)
    return {"q": jnp.asarray(q.reshape(w.shape), jnp.int4),
            "s": jnp.asarray(s, jnp.float32)}


def is_grouped(w) -> bool:
    """True for a group-scaled (int4) {q, s} leaf."""
    return isinstance(w, dict) and w["s"].ndim == w["q"].ndim + 1


def scale_spec(leaf: dict, weight_spec):
    """PartitionSpec for a {q, s} leaf's scale given its weight's spec.

    Flat (int8) scales [..., 1, out] follow only the output-channel
    partitioning. Grouped (int4) scales [..., in/g, 1, out] additionally
    follow the contraction-axis partitioning on their group axis, so
    row-parallel weights (wo, w_down) keep their scales device-local."""
    from jax.sharding import PartitionSpec as P

    if is_grouped(leaf):
        return P(*weight_spec[:-1], None, weight_spec[-1])
    return P(*([None] * (leaf["s"].ndim - 1) + [weight_spec[-1]]))


def mat(w, dtype):
    """Dequantize a weight leaf if needed (pass-through for dense)."""
    if isinstance(w, dict):
        q, s = w["q"], w["s"]
        if s.ndim == q.ndim + 1:            # grouped (int4) scales
            shape = q.shape
            G = s.shape[-3]
            wd = q.reshape(*shape[:-2], G, shape[-2] // G, shape[-1])
            wd = wd.astype(jnp.float32) * s
            return wd.reshape(shape).astype(dtype)
        return (q.astype(jnp.float32) * s).astype(dtype)
    return w
