"""The loader's host passes by blocks (ops/hostblocks.py) give the bits of
the one-thread whole-array form: ``quantize_weight``, ``quantize_weight_int4``
and the cast of an unquantized leaf, over the shapes the loaders call them
with, inline and on a pool of 1, 2 and 5 threads. The references below are
the functions as they stood before the blocks (PR 41), written out."""

import sys
import threading

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from localai_tpu.ops import hostblocks
from localai_tpu.ops.hostblocks import cast_leaf
from localai_tpu.ops.quant import (pick_int4_group, quantize_weight,
                                   quantize_weight_int4)

BF16 = np.dtype(ml_dtypes.bfloat16)


def ref_int8(w):
    w32 = np.asarray(w, np.float32)
    s = np.max(np.abs(w32), axis=w32.ndim - 2, keepdims=True) / 127.0
    s = np.maximum(s, 1e-12)
    qv = np.clip(np.rint(w32 / s), -127, 127).astype(np.int8)
    return qv, s


def ref_int4(w, group=128, shard_divisor=1):
    w32 = np.asarray(w, np.float32)
    cin = w32.shape[-2]
    g = pick_int4_group(cin, group, shard_divisor)
    if g is None:
        return ref_int8(w32)
    lead, out = w32.shape[:-2], w32.shape[-1]
    wg = w32.reshape(*lead, cin // g, g, out)
    s = np.max(np.abs(wg), axis=-2, keepdims=True) / 7.0
    s = np.maximum(s, 1e-12)
    qv = np.clip(np.rint(wg / s), -8, 7)
    return np.asarray(qv.reshape(w32.shape), ml_dtypes.int4), s


def _normal(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal(shape, dtype=np.float32) * 0.02
    w.flat[::97] *= 40.0            # outliers: columns clip at +-127 / +-8
    return w.astype(dtype)


def _zero_column(shape, dtype):
    w = _normal(shape, dtype)
    w[..., :, 5] = 0                # scale floors at 1e-12, q is 0
    w[..., :, -1] = 0
    return w


# name -> (maker, takes the pool). Widths are no multiple of BLOCK_COLS; a
# pool leaf holds INLINE_ELEMS values or more.
LEAVES = {
    "embed_2d_inline": (lambda: _normal((300, 72), np.float16), False),
    "embed_2d": (lambda: _normal((2100, 520), np.float16), True),
    "stack_inline": (lambda: _normal((3, 64, 130), np.float16), False),
    "stack": (lambda: _normal((3, 256, 1400), np.float16), True),
    "hybrid_stack_inline": (lambda: _normal((2, 3, 32, 48), np.float16),
                            False),
    "hybrid_stack": (lambda: _normal((2, 3, 128, 1400), np.float16), True),
    "bfloat16": (lambda: _normal((2, 384, 1400), BF16), True),
    "float32": (lambda: _normal((2, 384, 1400), np.float32), True),
    "float32_inline": (lambda: _normal((2, 48, 100), np.float32), False),
    "transposed_2d": (lambda: _normal((1100, 1024), np.float16).T, True),
    "transposed_inline": (lambda: _normal((96, 64), np.float16).T, False),
    "swapped_stack": (
        lambda: _normal((2, 1100, 512), np.float16).swapaxes(-1, -2), True),
    "strided_rows": (lambda: _normal((2, 768, 1400), np.float16)[:, ::2],
                     True),
    "zero_column": (lambda: _zero_column((2, 384, 1400), np.float16), True),
    "zero_column_inline": (lambda: _zero_column((2, 48, 100), np.float16),
                           False),
    # taller than one scratch: the block's max is taken in parts
    "tall_block": (lambda: _normal(
        (hostblocks.CHUNK_ELEMS // hostblocks.BLOCK_COLS + 300, 600),
        np.float16), True),
}
# None: the pool as the loader runs it (the process's cores)
WIDTHS = [None, 1, 2, 5]
CASES = [pytest.param(name, t, id=f"{name}-t{t}")
         for name, (_, pooled) in LEAVES.items()
         for t in (WIDTHS if pooled else [None])]


def _check_ran(ran, name, threads):
    pooled = LEAVES[name][1]
    if not pooled:
        assert ran == {"threads": 1, "blocks": 1}
        return
    assert ran["blocks"] > 1
    want = threads or hostblocks.host_threads()
    assert ran["threads"] == min(want, ran["blocks"])


@pytest.mark.parametrize("name,threads", CASES)
def test_quantize_weight_bits(name, threads):
    w = LEAVES[name][0]()
    ran = {}
    got = quantize_weight(w, threads=threads, ran=ran)
    q, s = ref_int8(w)
    assert got["q"].dtype == jnp.int8 and got["s"].dtype == jnp.float32
    assert got["s"].shape == s.shape
    assert np.array_equal(np.asarray(got["q"]), q)
    assert np.array_equal(np.asarray(got["s"]), s)
    _check_ran(ran, name, threads)


@pytest.mark.parametrize("name,threads", CASES)
def test_quantize_weight_int4_bits(name, threads):
    w = LEAVES[name][0]()
    ran = {}
    got = quantize_weight_int4(w, threads=threads, ran=ran)
    q, s = ref_int4(w)
    assert got["q"].dtype == q.dtype and got["s"].shape == s.shape
    assert np.array_equal(np.asarray(got["q"]), q)
    assert np.array_equal(np.asarray(got["s"]), s)
    _check_ran(ran, name, threads)


@pytest.mark.parametrize("cin,group,divisor", [
    (1056, 128, 1),     # 96 rows a group
    (1024, 64, 8),      # the group count divides a tp degree
    (1061, 128, 1),     # a prime: no group, the int8 fallback
])
def test_quantize_weight_int4_groups(cin, group, divisor):
    w = _normal((cin, 1030), np.float16)
    got = quantize_weight_int4(w, group=group, shard_divisor=divisor,
                               threads=2)
    q, s = ref_int4(w, group, divisor)
    assert got["q"].dtype == q.dtype and got["s"].shape == s.shape
    assert np.array_equal(np.asarray(got["q"]), q)
    assert np.array_equal(np.asarray(got["s"]), s)


def _f16_patterns():
    """Every float16 there is (NaNs of both signs, infinities, subnormals),
    tiled to a pool leaf."""
    every = np.arange(1 << 16, dtype=np.uint16).view(np.float16)
    return np.tile(every, 68).reshape(4352, 1024)


def _f32_patterns():
    """Random float32 bit patterns and the cases of the rounding: ties to
    even both ways, the carry into the exponent and into infinity, NaNs
    whose top fraction bits are clear."""
    rng = np.random.default_rng(3)
    u = rng.integers(0, 1 << 32, size=(4300, 1030), dtype=np.uint64)
    u = u.astype(np.uint32)
    edges = np.array([0x3F808000, 0x3F818000, 0x3F807FFF, 0x3F808001,
                      0x7F7FFFFF, 0xFF7FFFFF, 0x7F800000, 0xFF800000,
                      0x7F800001, 0xFF800001, 0x7FC00000, 0xFFFFFFFF,
                      0x00000001, 0x80000001, 0x00008000, 0x0000FFFF,
                      0x00000000, 0x80000000], np.uint32)
    u[0, :edges.size] = edges
    u[:, 700] = edges[8]            # a NaN in every block of that column
    return u.view(np.float32)


CASTS = {
    "f16_every_value_to_bf16": (_f16_patterns, jnp.bfloat16),
    "f32_patterns_to_bf16": (_f32_patterns, jnp.bfloat16),
    "f16_stack_to_bf16": (lambda: _normal((3, 256, 1400), np.float16),
                          jnp.bfloat16),
    "f16_transposed_to_bf16": (
        lambda: _normal((4400, 1024), np.float16).T, jnp.bfloat16),
    "f16_hybrid_stack_to_bf16": (
        lambda: _normal((2, 3, 128, 1400), np.float16), jnp.bfloat16),
    "f16_to_f32": (lambda: _normal((2, 384, 1400), np.float16),
                   jnp.float32),
    "f32_to_f16": (lambda: _normal((2, 384, 1400), np.float32),
                   jnp.float16),
    "bf16_to_f32": (lambda: _normal((2, 384, 1400), BF16), jnp.float32),
    "bf16_to_bf16": (lambda: _normal((2, 384, 1400), BF16), jnp.bfloat16),
    "norm_1d_to_bf16": (lambda: _normal((5120,), np.float16), jnp.bfloat16),
    "stack_inline_to_bf16": (lambda: _normal((3, 64, 130), np.float16),
                             jnp.bfloat16),
    "nan_inline_to_bf16": (lambda: _f16_patterns()[:64], jnp.bfloat16),
}


@pytest.mark.parametrize("threads", WIDTHS)
@pytest.mark.parametrize("name", CASTS)
def test_cast_leaf_bytes(name, threads):
    make, dtype = CASTS[name]
    arr = make()
    want = np.asarray(jnp.asarray(arr, dtype))
    ran = {}
    got = cast_leaf(arr, dtype, threads=threads, ran=ran)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    if arr.dtype == got.dtype:
        assert got is arr and ran == {"threads": 1, "blocks": 1}
    else:
        assert got is not arr and not np.shares_memory(got, arr)
        # a run of rows under one leading index, CHUNK_ELEMS values or fewer
        if arr.size < hostblocks.INLINE_ELEMS:
            assert ran == {"threads": 1, "blocks": 1}
        else:
            row = int(np.prod(arr.shape[-1:]))
            rows = max(1, hostblocks.CHUNK_ELEMS // row)
            assert ran["blocks"] == int(np.prod(arr.shape[:-2])) * \
                -(-arr.shape[-2] // rows) > 1


def test_more_threads_than_cores_same_bits():
    """Workers preempted mid-block, four to a core: blocks share nothing, so
    the bits hold."""
    w = _normal((4, 256, 2100), np.float16)
    q, s = ref_int8(w)
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        got = quantize_weight(w, threads=4 * hostblocks.host_threads())
        cast = cast_leaf(w, jnp.bfloat16, threads=4 * hostblocks.host_threads())
    finally:
        sys.setswitchinterval(before)
    assert np.array_equal(np.asarray(got["q"]), q)
    assert np.array_equal(np.asarray(got["s"]), s)
    assert cast.tobytes() == w.astype(BF16).tobytes()


def test_a_blocks_exception_reaches_the_caller():
    def work(scratch, a):
        raise RuntimeError("block failed")

    with pytest.raises(RuntimeError, match="block failed"):
        hostblocks.run_blocks(work, (np.zeros((2, 1024, 1024)),), 1, 2,
                              hostblocks.BLOCK_COLS, threads=3)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("hostblocks")]


@pytest.fixture
def toy_checkpoint(tmp_path):
    from localai_tpu.models import llama
    from tests.tinymodel import write_tiny_checkpoint

    d = str(tmp_path / "toy")
    hf = write_tiny_checkpoint(d)
    return d, llama.LlamaConfig.from_hf_config(hf, dtype=jnp.float32)


@pytest.mark.parametrize("quantize", ["", "int8", "int4"])
def test_no_thread_outlives_the_load(toy_checkpoint, monkeypatch, quantize):
    """Every leaf of the toy model takes the pool here (the inline bound is
    lifted), and the process has the threads it had before the load."""
    from localai_tpu.engine import weights
    from localai_tpu.services.tracing import RingTracer

    d, cfg = toy_checkpoint
    inline = weights.load_llama_params(d, cfg, dtype=jnp.bfloat16,
                                       quantize=quantize)
    monkeypatch.setattr(hostblocks, "INLINE_ELEMS", 0)
    monkeypatch.setattr(hostblocks, "BLOCK_COLS", 16)
    ring = RingTracer(4096)
    before = threading.active_count()
    pooled = weights.load_llama_params(d, cfg, dtype=jnp.bfloat16,
                                       quantize=quantize, tracer=ring)
    assert threading.active_count() == before
    assert not [t for t in threading.enumerate()
                if t.name.startswith("hostblocks")]
    spans = [s for s in ring.spans()
             if s["name"] in ("load_quantize", "load_cast")]
    assert max(s["args"]["threads"] for s in spans) > 1
    # and the pool's leaves are the inline path's
    import jax

    same = jax.tree_util.tree_map(
        lambda a, b: a.dtype == b.dtype and
        np.asarray(a).tobytes() == np.asarray(b).tobytes(), inline, pooled)
    assert all(jax.tree_util.tree_leaves(same))


def test_stream_loader_places_through_the_blocks(toy_checkpoint, monkeypatch):
    """``stream_llama_params`` calls the same placer (from the prefetch
    thread in production): same leaves, no thread left."""
    import jax

    from localai_tpu.engine import weights

    d, cfg = toy_checkpoint
    want = weights.load_llama_params(d, cfg, dtype=jnp.bfloat16,
                                     quantize="int8")
    monkeypatch.setattr(hostblocks, "INLINE_ELEMS", 0)
    monkeypatch.setattr(hostblocks, "BLOCK_COLS", 16)
    before = threading.active_count()
    out = {}
    t = threading.Thread(target=lambda: out.update(zip(
        ("params", "stats"), weights.stream_llama_params(
            d, cfg, dtype=jnp.bfloat16, quantize="int8"))))
    t.start()
    t.join(120)
    assert not t.is_alive() and threading.active_count() == before
    same = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a).tobytes() == np.asarray(b).tobytes(),
        want, out["params"])
    assert all(jax.tree_util.tree_leaves(same))


def test_load_spans_carry_threads_and_blocks(toy_checkpoint):
    """``load_quantize`` and ``load_cast`` say how each leaf's pass ran; a
    toy leaf runs inline: one block, one thread."""
    from localai_tpu.engine import weights
    from localai_tpu.services.tracing import RingTracer

    d, cfg = toy_checkpoint
    ring = RingTracer(4096)
    weights.load_llama_params(d, cfg, dtype=jnp.bfloat16, quantize="int8",
                              tracer=ring)
    spans = ring.spans()
    quant = [s for s in spans if s["name"] == "load_quantize"]
    cast = [s for s in spans if s["name"] == "load_cast"]
    assert {s["args"]["leaf"] for s in quant} >= {"embed", "wq", "w_down"}
    assert {s["args"]["leaf"] for s in cast} >= {"attn_norm", "final_norm"}
    for s in quant:
        assert s["args"]["bits"] == 8
    for s in quant + cast:
        assert s["args"]["threads"] == 1 and s["args"]["blocks"] == 1
