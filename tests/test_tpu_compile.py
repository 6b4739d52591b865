"""Compile the serving path's TPU programs with no TPU attached.

libtpu ships a compile-only client: ``topologies.get_topology_desc``
describes a v5e 2x2 host, and lowering against its devices runs the real
XLA:TPU and Mosaic compilers. Nothing executes, so this proves only that
the programs BUILD for the chip the system is for — which is what the
CPU suite could never see (interpret-mode Pallas skips Mosaic; GSPMD on
CPU devices never meets a Mosaic custom call). Numerical parity on the
chip is chip_smoke.py's job.

Shapes are Llama-3.1-8B's (the chip_smoke.py model): 8 KV heads x 4
query groups of 128, page 64, int8 weights.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from localai_tpu.models import llama
from localai_tpu.ops.quant import scale_spec
from localai_tpu.parallel import sharding as shardlib
from localai_tpu.parallel.mesh import AXES

KV, G, HD, PAGE = 8, 4, 128, 64
S, C = 16, 1024
MP = C // PAGE
NP = S * MP
CFG_8B = llama.LlamaConfig(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
    rope_theta=500000.0, max_position_embeddings=2048)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:   # no libtpu in this install
        pytest.skip(f"compile-only TPU client unavailable: {e!r}")
    assert t.devices[0].device_kind == "TPU v5 lite"
    return t


def _on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _seg_tables(A, B=S):
    i32 = jnp.int32
    return [A((B,), i32) for _ in range(4)]


@pytest.mark.parametrize("quant", [False, True])
def test_paged_decode_kernels_compile(topo, quant):
    from localai_tpu.ops.pallas import paged_attention as pa

    A = _on(SingleDeviceSharding(topo.devices[0]))
    bf, i32 = jnp.bfloat16, jnp.int32
    q, nk = A((S, KV * G, HD), bf), A((S, KV, HD), bf)
    tail = (A((S, MP), i32), A((S,), i32))
    if quant:
        pages, scales = A((NP, PAGE, KV, HD), jnp.int8), \
            A((NP, PAGE, KV), jnp.float32)
        pa.paged_decode_attention_append_quant.lower(
            q, nk, nk, pages, scales, pages, scales, *tail,
            q_per_kv=G).compile()
    else:
        pages = A((NP, PAGE, KV, HD), bf)
        pa.paged_decode_attention_append.lower(
            q, nk, nk, pages, pages, *tail, q_per_kv=G).compile()


def test_contiguous_decode_kernel_compiles(topo):
    from localai_tpu.ops.pallas.decode_attention import (
        decode_attention_append_pallas)

    A = _on(SingleDeviceSharding(topo.devices[0]))
    bf = jnp.bfloat16
    rows = A((S, C, KV, HD), bf)
    decode_attention_append_pallas.lower(
        A((S, KV * G, HD), bf), A((S, KV, HD), bf), A((S, KV, HD), bf),
        rows, rows, A((S,), jnp.int32), q_per_kv=G).compile()


@pytest.mark.parametrize("heads,dtype,packs", [
    ((8, 4, 128), jnp.bfloat16, (128, 512, 1024)),   # Llama-3.1-8B
    ((4, 8, 64), jnp.bfloat16, (1024,)),             # TinyLlama-1.1B
    ((8, 4, 128), jnp.float32, (1024,)),     # f32 KV: twice the block bytes
    ((2, 4, 128), jnp.bfloat16, (1024,)),    # the 8B's per-device share, tp=4
])
def test_ragged_prefill_compiles_wherever_the_plan_says(topo, heads, dtype,
                                                        packs):
    """A plan ragged_kernel_plan returns compiles. The 8B row covers
    every pack bucket chip_smoke.py's engine builds; the blocking (and
    so the VMEM footprint) does not depend on the pack length."""
    from localai_tpu.ops.pallas import ragged_prefill as rp

    kv, g, hd = heads
    A = _on(SingleDeviceSharding(topo.devices[0]))
    pages = A((NP, PAGE, kv, hd), dtype)
    for N in packs:
        qb, pkb = rp.ragged_kernel_plan(
            N, kv, g, hd, page_size=PAGE, itemsize=jnp.dtype(dtype).itemsize)
        rp.ragged_prefill_attention_pallas.lower(
            A((N, kv * g, hd), dtype), A((N, kv, hd), dtype),
            A((N, kv, hd), dtype), pages, pages, A((S, MP), jnp.int32),
            *_seg_tables(A), q_per_kv=g, pkb=pkb, qb=qb).compile()


def test_ragged_plan_shrinks_then_refuses(topo):
    """The plan answers for VMEM: wide heads get a smaller block, and
    what fits at no block size gets None (the engine's counted jnp
    fallback) — never a plan the compiler then rejects."""
    from localai_tpu.ops.pallas import ragged_prefill as rp

    assert rp.ragged_kernel_plan(1024, 8, 4, 128) == (128, 128)
    wide = rp.ragged_kernel_plan(1024, 8, 16, 128)
    assert wide is not None and wide[0] < 128
    A = _on(SingleDeviceSharding(topo.devices[0]))
    bf = jnp.bfloat16
    pages = A((NP, PAGE, 8, 128), bf)
    rp.ragged_prefill_attention_pallas.lower(
        A((1024, 8 * 16, 128), bf), A((1024, 8, 128), bf),
        A((1024, 8, 128), bf), pages, pages, A((S, MP), jnp.int32),
        *_seg_tables(A), q_per_kv=16, pkb=wide[1], qb=wide[0]).compile()
    assert rp.ragged_kernel_plan(1024, 64, 8, 1024) is None


def _abstract_8b(cfg, A_of, kv_dtype):
    """(params, ck, cv) as ShapeDtypeStructs: int8 {q, s} weights, paged
    KV. ``A_of(spec)`` -> constructor placing a leaf under that spec."""
    L, D, F, V = (cfg.num_layers, cfg.hidden_size, cfg.intermediate_size,
                  cfg.vocab_size)
    Hd, KVd = cfg.num_heads * cfg.head_dim_, cfg.num_kv_heads * cfg.head_dim_
    shapes = {
        "embed": (V, D), "lm_head": (D, V), "final_norm": (D,),
        "layers": {"attn_norm": (L, D), "mlp_norm": (L, D),
                   "wq": (L, D, Hd), "wk": (L, D, KVd), "wv": (L, D, KVd),
                   "wo": (L, Hd, D), "w_gate": (L, D, F), "w_up": (L, D, F),
                   "w_down": (L, F, D)}}

    def leaf(name, shape, spec):
        if "norm" in name:
            return A_of(spec)(shape, cfg.dtype)
        s_shape = shape[:-2] + (1, shape[-1])
        sds = jax.ShapeDtypeStruct
        s_spec = scale_spec({"q": sds(shape, jnp.int8),
                             "s": sds(s_shape, jnp.float32)}, spec)
        return {"q": A_of(spec)(shape, jnp.int8),
                "s": A_of(s_spec)(s_shape, jnp.float32)}

    specs = shardlib.llama_param_specs()
    params = {k: ({n: leaf(n, v[n], specs[k][n]) for n in v}
                  if isinstance(v, dict) else leaf(k, v, specs[k]))
              for k, v in shapes.items()}
    cache = jax.eval_shape(lambda: llama.init_cache(
        cfg, S, C, kv_dtype, page_size=PAGE))
    pspec = shardlib.paged_cache_spec()

    def place(c):
        out = {"pages": A_of(pspec)(c["pages"].shape, c["pages"].dtype),
               "ptab": A_of(P(None, None))(c["ptab"].shape, jnp.int32)}
        if "scales" in c:
            out["scales"] = A_of(P(*pspec[:-1]))(c["scales"].shape,
                                                 jnp.float32)
        return out

    return params, place(cache[0]), place(cache[1])


@pytest.mark.parametrize("tp", [1, 4])
def test_8b_serving_programs_compile(topo, tp):
    """Whole-model decode_step and ragged_prefill(continued=True) with
    abstract int8 weights, on one device and on a tp=4 mesh over the
    host's four — the meshed form needs the kernels under shard_map
    (Mosaic kernels cannot be partitioned automatically)."""
    if tp == 1:
        sh = SingleDeviceSharding(topo.devices[0])
        mesh, A_of = None, (lambda spec: _on(sh))
    else:
        mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 1, tp, 1), AXES)
        A_of = lambda spec: _on(NamedSharding(mesh, spec))  # noqa: E731
    cfg = dataclasses.replace(
        CFG_8B, attn=llama.AttnTarget(pallas=True, mesh=mesh))
    rep = A_of(P(None))
    i32 = jnp.int32
    N = 1024

    def decode(p, t, ln, ck, cv):
        return llama.decode_step(p, cfg, t, ln, ck, cv)

    def pack(p, t, pos, so, ss, st, off, ln, ck, cv):
        return llama.ragged_prefill(p, cfg, t, pos, so, ss, st, off, ln,
                                    ck, cv, continued=True)

    for kv_dtype in (jnp.bfloat16, jnp.int8):
        params, ck, cv = _abstract_8b(cfg, A_of, kv_dtype)
        want = "pallas:paged_decode" + ("_int8" if kv_dtype == jnp.int8
                                        else "")
        assert llama.decode_attn_impl(cfg, ck) == want
        jax.jit(decode).lower(params, rep((S,), i32), rep((S,), i32),
                              ck, cv).compile()
        # int8 pages route packed prefill to the jnp path by design
        want = "jnp:ragged" if kv_dtype == jnp.int8 \
            else "pallas:ragged_prefill"
        assert llama.ragged_attn_impl(cfg, ck, N, True) == want
        jax.jit(pack).lower(
            params, rep((N,), i32), rep((N,), i32), rep((N,), i32),
            *[rep((S,), i32) for _ in range(4)], ck, cv).compile()
