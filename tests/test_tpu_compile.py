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
@pytest.mark.parametrize("slots,mp,kv,g", [
    (S, MP, KV, G),       # chip_smoke.py's 8B; mistral7b.chat_rate
    (16, 64, 8, 4),       # the Nemo cells: 16 x 4096
    (32, 32, 32, 1),      # olmo-hybrid: 32 x 2048, one query head a KV head
    (16, 64, 2, 4),       # a tp=4 shard of the Nemo geometry
], ids=["8x16", "8x64", "32x32", "2x64"])
def test_paged_decode_kernels_compile(topo, quant, slots, mp, kv, g):
    """At the benchmark cells' decode geometries, with the driver the
    kernel's own rule chooses: the page buffers and the body's
    temporaries fit Mosaic's scoped VMEM."""
    from localai_tpu.ops.pallas import paged_attention as pa

    A = _on(SingleDeviceSharding(topo.devices[0]))
    bf, i32 = jnp.bfloat16, jnp.int32
    n_pages = slots * mp
    q, nk = A((slots, kv * g, HD), bf), A((slots, kv, HD), bf)
    # the stacked pool and a traced layer, as the layer scan hands them over
    tail = (A((slots, mp), i32), A((slots,), i32), A((), i32))
    if quant:
        pages, scales = A((3, n_pages, PAGE, kv, HD), jnp.int8), \
            A((3, n_pages, PAGE, kv), jnp.float32)
        pa.paged_decode_attention_append_quant.lower(
            q, nk, nk, pages, scales, pages, scales, *tail,
            q_per_kv=g).compile()
    else:
        pages = A((3, n_pages, PAGE, kv, HD), bf)
        pa.paged_decode_attention_append.lower(
            q, nk, nk, pages, pages, *tail, q_per_kv=g).compile()


@pytest.mark.parametrize("dtype,kv,hd,ring", [
    (jnp.bfloat16, 2, 128, True), (jnp.bfloat16, 4, 128, True),
    (jnp.bfloat16, 16, 256, True), (jnp.bfloat16, 24, 128, True),
    (jnp.float32, 8, 128, True),
    (jnp.bfloat16, 8, 64, False),       # TinyLlama's heads
    (jnp.bfloat16, 30, 128, False),     # the hybrid's heads unpadded
    (jnp.bfloat16, 1, 128, False),
])
def test_paged_decode_driver_compiles_wherever_the_rule_says(topo, dtype, kv,
                                                             hd, ring):
    """ring_takes answers for Mosaic: where it says so, the kernel's own
    page copies out of the HBM pool compile (a page is whole tiles of
    the pool's layout); everywhere else the walk runs, and compiles."""
    from localai_tpu.ops.pallas import paged_attention as pa

    assert pa.ring_takes(kv, hd, dtype) == ring
    A = _on(SingleDeviceSharding(topo.devices[0]))
    q, nk = A((S, kv * G, hd), dtype), A((S, kv, hd), dtype)
    pages = A((3, NP, PAGE, kv, hd), dtype)
    pa.paged_decode_attention_append.lower(
        q, nk, nk, pages, pages, A((S, MP), jnp.int32), A((S,), jnp.int32),
        A((), jnp.int32), q_per_kv=G).compile()


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
    pages = A((3, NP, PAGE, kv, hd), dtype)     # stacked, layer traced
    for N in packs:
        qb, pkb = rp.ragged_kernel_plan(
            N, kv, g, hd, page_size=PAGE, itemsize=jnp.dtype(dtype).itemsize)
        rp.ragged_prefill_attention_pallas.lower(
            A((N, kv * g, hd), dtype), A((N, kv, hd), dtype),
            A((N, kv, hd), dtype), pages, pages, A((S, MP), jnp.int32),
            *_seg_tables(A), A((), jnp.int32), q_per_kv=g, pkb=pkb,
            qb=qb).compile()


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


# ---------- the layer scan copies no layer of the page pool ----------

CFG_7B_L12 = llama.LlamaConfig(     # the chat cell: Mistral-7B widths, 12 layers
    vocab_size=32768, hidden_size=4096, intermediate_size=14336,
    num_layers=12, num_heads=32, num_kv_heads=8, head_dim=128,
    rope_theta=1e6, max_position_embeddings=32768,
    attn=llama.AttnTarget(pallas=True))


def _abstract_7b(A, context, kv_dtype):
    """(params, ck, cv): bf16 weights and a paged cache of 16 slots x
    ``context``, as ShapeDtypeStructs on one described device."""
    def place(tree):
        return jax.tree.map(lambda x: A(x.shape, x.dtype), tree)

    params = jax.eval_shape(
        lambda: llama.init_params(CFG_7B_L12, jax.random.PRNGKey(0)))
    ck, cv = jax.eval_shape(lambda: llama.init_cache(
        CFG_7B_L12, S, context, kv_dtype, page_size=PAGE))
    return place(params), place(ck), place(cv)


_HLO_DTYPE = {"bf16": jnp.bfloat16, "s8": jnp.int8, "f32": jnp.float32}


def _computations(hlo: str) -> dict:
    """name -> instruction lines of every computation of the module."""
    import re

    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _reachable(comps: dict, roots) -> list:
    """The instruction lines of the computations ``roots`` name and of
    every fusion, call, loop body and condition under them."""
    import re

    callee = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += [c for ln in comps[name] for c in callee.findall(ln)]
    return [ln for name in sorted(seen) for ln in comps[name]]


def _loop_instructions(hlo: str):
    """The instruction lines of every computation a ``while`` of the
    optimised module runs: loop bodies and conditions, and the fusions
    and calls under them."""
    import re

    comps = _computations(hlo)
    loops = [c for lines in comps.values() for ln in lines if " while(" in ln
             for c in re.findall(r"(?:body|condition)=%?([\w.\-]+)", ln)]
    assert loops, "no while loop in the module: the layer scan is gone"
    return _reachable(comps, loops)


def _assert_no_layer_of_the_pool(compiled, ck):
    """No instruction inside the layer loop produces one layer of the
    pool — an array of the pages' (or the scales') dtype and trailing
    dims with one layer's element count, however the leading dims are
    folded — and the program's temporaries are smaller than one layer's
    K pool. A Mosaic call handed ``pool[li]``, or a layer set back with
    ``.at[li].set``, shows as both: the parent read 269,485,568 bytes of
    temporaries at 16 x 4096 (two layers of a 134 MB pool).

    int8 pages: the kernel's tiling pads the scales' 8 KV heads to 128
    lanes, and XLA relays the stacked scales out to that at the
    program's edge (PERF.md section 7); that one relayout is taken off
    the temporaries before the comparison."""
    import math
    import re

    leaves = [v for k, v in ck.items() if k != "ptab"]
    found = []
    for line in _loop_instructions(compiled.as_text()):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]", line)
        if not m or m.group(1) not in _HLO_DTYPE:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        for leaf in leaves:
            tail = leaf.shape[3:]       # (KV, hd) of pages, (KV,) of scales
            if (_HLO_DTYPE[m.group(1)] == leaf.dtype
                    and dims[-len(tail):] == tail
                    and math.prod(dims) == math.prod(leaf.shape[1:])):
                found.append(line.strip()[:200])
    assert not found, "\n".join(found)
    temp = compiled.memory_analysis().temp_size_in_bytes
    if "scales" in ck:
        temp -= 2 * math.prod(ck["scales"].shape[:-1]) * 128 * 4    # K, V
    assert temp < math.prod(ck["pages"].shape[1:]) * ck["pages"].dtype.itemsize


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("context", [1024, 4096])
def test_decode_step_copies_no_layer_of_the_pool(topo, context, kv_dtype):
    """decode_step with donated caches at the benchmark cells' cache
    geometry: the paged kernel reads the stacked pool by layer index and
    the row write is an in-place scatter on the scan carry."""
    A = _on(SingleDeviceSharding(topo.devices[0]))
    params, ck, cv = _abstract_7b(A, context, kv_dtype)

    def decode(p, t, ln, ck, cv):
        return llama.decode_step(p, CFG_7B_L12, t, ln, ck, cv)

    compiled = jax.jit(decode, donate_argnums=(3, 4)).lower(
        params, A((S,), jnp.int32), A((S,), jnp.int32), ck, cv).compile()
    _assert_no_layer_of_the_pool(compiled, ck)


@pytest.mark.parametrize("context", [1024, 4096])
def test_ragged_prefill_copies_no_layer_of_the_pool(topo, context):
    """The same for a continued 1024-token pack through the ragged
    prefill kernel (bf16 pages: int8 pages take the jnp path)."""
    A = _on(SingleDeviceSharding(topo.devices[0]))
    params, ck, cv = _abstract_7b(A, context, jnp.bfloat16)
    i32, N = jnp.int32, 1024
    assert llama.ragged_attn_impl(CFG_7B_L12, ck, N, True) == \
        "pallas:ragged_prefill"

    def pack(p, t, pos, so, ss, st, off, ln, ck, cv):
        return llama.ragged_prefill(p, CFG_7B_L12, t, pos, so, ss, st, off,
                                    ln, ck, cv, continued=True)

    compiled = jax.jit(pack, donate_argnums=(8, 9)).lower(
        params, A((N,), i32), A((N,), i32), A((N,), i32),
        *_seg_tables(A), ck, cv).compile()
    _assert_no_layer_of_the_pool(compiled, ck)


# ---------- the spec tick's conditionals alias the pool ----------


@pytest.mark.parametrize("context", [1024, 4096])
def test_spec_tick_passes_the_pool_through_its_conditionals(topo, context):
    """`jit_spec_tick` (ISSUE 34) at the chat and the document cells'
    cache geometry: the pool rides the round scan's carry through two
    conditionals (the plain step, the verify pass), and a skipped branch
    hands it back. XLA must alias it there: no instruction produces a
    copy of a whole pool, the donated caches alias the outputs, and both
    conditionals sit in the compiled round loop."""
    import math
    import re
    import types

    from localai_tpu.engine import engine as eng, sampling

    A = _on(SingleDeviceSharding(topo.devices[0]))
    params, ck, cv = _abstract_7b(A, context, jnp.bfloat16)
    e = object.__new__(eng.Engine)      # the body reads these and no more
    e.ecfg = types.SimpleNamespace(n_draft=4, num_slots=S, spec_ngram=3)
    e.cfg, e.family, e.draft_cfg = CFG_7B_L12, llama, None
    e._state_shardings = None
    spp = sampling.pack_slot_params(sampling.make_slot_params(S))
    i32, f32 = jnp.int32, jnp.float32
    compiled = jax.jit(
        lambda *a: e._spec_tick_body(*a, n_rounds=2,
                                     flags=(False, False, False)),
        donate_argnums=(2, 3, 8)).lower(
        params, A((S,), i32), ck, cv, A((S,), i32),
        A((S, sampling.RING_N), i32), A((S,), i32),
        A((S, CFG_7B_L12.vocab_size), f32), A((S, 2), jnp.uint32),
        A(spp.shape, spp.dtype), A((S,), jnp.bool_), A((S,), f32),
        A((7 + sampling.RING_N, S), f32), A((S,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    pool = ck["pages"].shape
    pool_bytes = math.prod(pool) * 2
    dims = ",".join(str(d) for d in pool)
    copies = [ln.strip()[:160] for ln in hlo.splitlines()
              if re.search(rf"= bf16\[{dims}\]\S* copy(-start|-done)?\(", ln)]
    assert not copies, "\n".join(copies)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    if context == 4096:
        # the verify pass's dense per-layer gather and the weights XLA
        # relays for it are under one pool (PERF.md section 7); a pool
        # copied out of a conditional would not be
        assert mem.temp_size_in_bytes < pool_bytes
    in_loop = "\n".join(_loop_instructions(hlo))
    assert len(re.findall(r" conditional\(", in_loop)) == 2


def test_sampler_greedy_branch_compiles_without_a_sort(topo):
    """`sampling.sample` at the Ling cell's geometry ([96, 39296] float32
    logits): ONE conditional, whose true branch - every row that counts
    plain greedy - holds no sort and no ApproxTopK, and whose false
    branch is the window with both (ISSUE 48)."""
    import re

    from localai_tpu.engine import sampling

    A = _on(SingleDeviceSharding(topo.devices[0]))
    S_L, V_L = 96, 39296
    spp = sampling.pack_slot_params(sampling.make_slot_params(S_L))
    i32, f32 = jnp.int32, jnp.float32

    def sample(logits, spp, ring, pos, bias, keys, mu, active):
        return sampling.sample(
            logits, sampling.unpack_slot_params(spp), ring, pos, bias, keys,
            mu, use_penalties=False, use_typical=False, use_mirostat=False,
            active=active)

    hlo = jax.jit(sample).lower(
        A((S_L, V_L), f32), A(spp.shape, spp.dtype),
        A((S_L, sampling.RING_N), i32), A((S_L,), i32), A((S_L, V_L), f32),
        A((S_L, 2), jnp.uint32), A((S_L,), f32),
        A((S_L,), jnp.bool_)).compile().as_text()
    comps = _computations(hlo)
    conds = [ln for lines in comps.values() for ln in lines
             if " conditional(" in ln]
    assert len(conds) == 1, conds
    # lax.cond lowers to a case on the predicate as an index: branch 0 is
    # the false function, branch 1 the true one
    m = re.search(r"branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}",
                  conds[0])
    assert m, conds[0]
    window, greedy = ("\n".join(_reachable(comps, [b])) for b in m.groups())
    wanted = re.compile(r" sort\(|ApproxTopK|approx_top_k", re.I)
    assert not wanted.findall(greedy)
    assert re.search(r" sort\(", window)
    assert re.search(r"ApproxTopK|approx_top_k", window, re.I)


# ---------- the hybrid family: paged K/V beside a recurrent state ----------

S_H, C_H, POOL_H = 32, 2048, 768     # the olmo-hybrid cell's cache geometry


def _abstract_hybrid(A):
    """(cfg, params, ck, cv): Olmo-Hybrid-7B widths, 12 layers, bf16
    weights, the cell's slots and page pool, as ShapeDtypeStructs."""
    from localai_tpu.models import olmo_hybrid as oh

    cfg = oh.OlmoHybridConfig(num_layers=12,
                              attn=llama.AttnTarget(pallas=True))

    def place(tree):
        return jax.tree.map(lambda x: A(x.shape, x.dtype), tree)

    params = jax.eval_shape(
        lambda: oh.init_params(cfg, jax.random.PRNGKey(0)))
    ck, cv = jax.eval_shape(lambda: oh.init_cache(
        cfg, S_H, C_H, jnp.bfloat16, page_size=PAGE, num_pages=POOL_H))
    return cfg, place(params), place(ck), place(cv)


def test_gated_delta_decode_kernel_compiles(topo):
    """The in-place state update at the cell's shape: 9 x 32 slots x 30
    heads x 96 x 192 float32, the layer traced."""
    from localai_tpu.ops.pallas.gated_delta import gated_delta_decode_pallas

    A = _on(SingleDeviceSharding(topo.devices[0]))
    f32 = jnp.float32
    H, K, V = 30, 96, 192
    compiled = jax.jit(gated_delta_decode_pallas, donate_argnums=(0,)).lower(
        A((9, S_H, H, K, V), f32), A((), jnp.int32), A((S_H, H, K), f32),
        A((S_H, H, K), f32), A((S_H, H, V), f32), A((S_H, H), f32),
        A((S_H, H), f32), A((S_H,), jnp.bool_)).compile()
    # aliased onto its input: nothing the size of a layer of state is made
    assert compiled.memory_analysis().temp_size_in_bytes < S_H * H * K * V * 4


def _assert_state_and_pool_stay_in_place(compiled, ck):
    """The program's temporaries are smaller than ONE layer of the
    recurrent state as the chip lays it out (192 lanes pad to 256) plus one
    layer of the K pool: neither is copied out of the layer loop's carry."""
    import math

    L, S, H, K, V = ck["delta"].shape
    one_state = S * H * K * 256 * 4
    one_pool = math.prod(ck["pages"].shape[1:]) * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    # W_g is relaid once at the program's edge (398 MB: XLA's choice for
    # the matmul, outside the loop)
    temp -= math.prod((L, 3840, 5760)) * 2
    assert temp < one_state + one_pool, temp


def test_hybrid_decode_step_compiles_in_place(topo):
    """engine_decode at the cell's size with donated caches: the paged
    kernel on a pool of 32 (30 padded) KV heads, the delta kernel on the
    stacked state, every weight read through ONE fused dynamic slice."""
    from localai_tpu.models import olmo_hybrid as oh

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_hybrid(A)
    assert ck["pages"].shape[-2:] == (32, 128)
    assert llama.decode_attn_impl(cfg.attn_cfg, ck) == "pallas:paged_decode"

    def decode(p, t, ln, act, ck, cv):
        return oh.engine_decode(p, cfg, t, ln, act, ck, cv)

    compiled = jax.jit(decode, donate_argnums=(4, 5)).lower(
        params, A((S_H,), jnp.int32), A((S_H,), jnp.int32),
        A((S_H,), jnp.bool_), ck, cv).compile()
    assert "gated_delta_decode" in compiled.as_text()
    _assert_state_and_pool_stay_in_place(compiled, ck)


@pytest.mark.parametrize("N", [512, 1024])
def test_hybrid_packed_prefill_compiles(topo, N):
    """A continued pack through the chunked delta rule and the ragged
    prefill kernel (30 heads of 128 over a padded pool)."""
    from localai_tpu.models import olmo_hybrid as oh

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_hybrid(A)
    i32 = jnp.int32
    assert llama.ragged_attn_impl(cfg.attn_cfg, ck, N, True) == \
        "pallas:ragged_prefill"

    def pack(p, t, pos, so, ss, st, off, ln, ck, cv):
        return oh.ragged_prefill(p, cfg, t, pos, so, ss, st, off, ln, ck, cv,
                                 continued=True)

    compiled = jax.jit(pack, donate_argnums=(8, 9)).lower(
        params, A((N,), i32), A((N,), i32), A((N,), i32),
        *_seg_tables(A, S_H), ck, cv).compile()
    # fits beside 9.8 GB of weights and caches on a 16 GB chip
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 << 30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9


# ---------- granite_hybrid: Mamba-2 state beside a pool of 64-wide heads ----------

S_G, C_G, POOL_G = 48, 2048, 1152    # granite-h-micro.longgen_many's geometry


def _abstract_granite(A):
    """(cfg, params, ck, cv): Granite-4.0-H-Micro as published, all 40
    layers, bf16 weights, the cell's slots and page pool, as
    ShapeDtypeStructs."""
    from localai_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig(attn=llama.AttnTarget(pallas=True))

    def place(tree):
        return jax.tree.map(lambda x: A(x.shape, x.dtype), tree)

    params = jax.eval_shape(
        lambda: gh.init_params(cfg, jax.random.PRNGKey(0)))
    ck, cv = jax.eval_shape(lambda: gh.init_cache(
        cfg, S_G, C_G, jnp.bfloat16, page_size=PAGE, num_pages=POOL_G))
    return cfg, place(params), place(ck), place(cv)


def _named(hlo: str, part: str):
    """The instruction lines of an optimised module whose instruction's
    name holds ``part``: the names a profiler capture shows, which is how
    benchmark/layer_metrics/_ssm.py finds the state kernel's calls."""
    import re

    return [ln for ln in hlo.splitlines()
            if re.match(rf"\s*(?:ROOT )?%[\w.\-]*{part}[\w.\-]* = ", ln)]


@pytest.fixture(scope="module")
def granite_decode(topo):
    """(cfg, ck, compiled): engine_decode at the cell's size with donated
    caches, compiled once for the tests that read it."""
    from localai_tpu.models import granite_hybrid as gh

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_granite(A)

    def decode(p, t, ln, act, ck, cv):
        return gh.engine_decode(p, cfg, t, ln, act, ck, cv)

    return cfg, ck, jax.jit(decode, donate_argnums=(4, 5)).lower(
        params, A((S_G,), jnp.int32), A((S_G,), jnp.int32),
        A((S_G,), jnp.bool_), ck, cv).compile()


def test_mamba2_decode_kernel_compiles(topo, monkeypatch, granite_decode):
    """The in-place state update at the cell's shape: 36 x 48 slots x 64
    heads x 64 x 128 float32, the layer traced, a slot's whole state a
    program (8.4 MB of VMEM double-buffered). And what the benchmark's
    mamba2_decode_roofline stands on: the state goes through ONE custom
    call whose name holds ``mamba2_decode``, aliased onto itself, and the
    decode program has one such call a run of mamba layers and no other
    instruction of that name (a second one, or state traffic moved out of
    the kernel, is how a sound change reads over 100%)."""
    import re

    from localai_tpu.ops.pallas import mamba2_decode as md

    A = _on(SingleDeviceSharding(topo.devices[0]))
    f32 = jnp.float32
    H, P_, N = 64, 64, 128

    def compile_kernel():
        # a function of its own each time: a patched limit is traced anew
        return jax.jit(lambda *a: md.mamba2_decode_pallas(*a),
                       donate_argnums=(0,)).lower(
            A((36, S_G, H, P_, N), f32), A((), jnp.int32),
            A((S_G, H, P_), f32), A((S_G, H), f32), A((S_G, H), f32),
            A((S_G, N), f32), A((S_G, N), f32),
            A((S_G,), jnp.bool_)).compile()

    compiled = compile_kernel()
    # aliased onto its input: nothing the size of a slot's state is made
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < H * P_ * N * 4
    assert mem.alias_size_in_bytes == 36 * S_G * H * P_ * N * 4
    hlo = compiled.as_text()
    calls = _named(hlo, "mamba2_decode")
    assert len(calls) == 1 and " custom-call(" in calls[0], calls
    # operand 3 is the state (0-2 the scalar-prefetch arguments)
    assert "output_to_operand_aliasing={{1}: (3, {})}" in calls[0]

    # the decode program: one call a run of mamba layers (the layer scan
    # runs each run's body once a layer), nothing else of that name
    cfg, _ck, decode = granite_decode
    runs = sum(k == "mamba" and (i == 0 or cfg.period[i - 1] != "mamba")
               for i, k in enumerate(cfg.period))
    calls = _named(decode.as_text(), "mamba2_decode")
    assert runs and len(calls) == runs, calls
    assert all(" custom-call(" in c and "output_to_operand_aliasing" in c
               for c in calls), calls

    # VMEM: under a limit nothing fits, the compiler says what it needs
    limit = md._VMEM_LIMIT
    monkeypatch.setattr(md, "_VMEM_LIMIT", 1 << 20)
    with pytest.raises(Exception, match="vmem") as refused:
        compile_kernel()
    need = re.search(r"Scoped allocation with size ([\d.]+)M",
                     str(refused.value))
    # two blocks in, two out, and under a megabyte of operands
    assert need and 8 << 20 <= float(need.group(1)) * 2 ** 20 < 10 << 20
    assert 10 << 20 < limit


def test_granite_decode_step_compiles_in_place(granite_decode):
    """engine_decode at the cell's size with donated caches: the state
    kernel on the stacked state, the paged kernel on a pool whose heads are
    padded from 64 to 128 columns. The program's temporaries are a few
    megabytes: no layer of the state and no copy of the pool (at 64
    columns the chip's own layout put the PAGE axis minor and the program
    transposed both pools on its way in and out, 1.2 GB of temporaries),
    and no instruction of the layer loop makes a layer of state."""
    import math
    import re

    cfg, ck, compiled = granite_decode
    assert ck["pages"].shape == (4, POOL_G, PAGE, 8, 128)
    assert ck["ssm"].shape == (36, S_G, 64, 64, 128)
    assert llama.decode_attn_impl(cfg.attn_cfg, ck) == "pallas:paged_decode"
    hlo = compiled.as_text()
    assert "mamba2_decode" in hlo and "paged_decode" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    one_layer = math.prod(ck["ssm"].shape[1:])
    made = []
    for line in _loop_instructions(hlo):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = f32\[([\d,]*)\]", line)
        if m and math.prod(int(d) for d in m.group(1).split(",") if d) \
                == one_layer:
            made.append(line.strip()[:200])
    assert not made, "\n".join(made)


@pytest.mark.parametrize("N", [512, 1024])
def test_granite_packed_prefill_compiles(topo, N):
    """A continued pack through the chunked state-space dual (chunks of
    256, one dynamic slice each) and the ragged prefill kernel."""
    from localai_tpu.models import granite_hybrid as gh

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_granite(A)
    i32 = jnp.int32
    assert llama.ragged_attn_impl(cfg.attn_cfg, ck, N, True) == \
        "pallas:ragged_prefill"

    def pack(p, t, pos, so, ss, st, off, ln, ck, cv):
        return gh.ragged_prefill(p, cfg, t, pos, so, ss, st, off, ln, ck, cv,
                                 continued=True)

    compiled = jax.jit(pack, donate_argnums=(8, 9)).lower(
        params, A((N,), i32), A((N,), i32), A((N,), i32),
        *_seg_tables(A, S_G), ck, cv).compile()
    # fits beside 11.3 GB of weights and caches on a 16 GB chip
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 512 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9


# ---------- lfm2_moe: expert stacks of 1.6 GB a projection, never copied ----------

S_L, C_L, POOL_L = 64, 2048, 1536    # lfm2-24b-a2b.longgen_wide's geometry


def _abstract_lfm2(A):
    """(cfg, params, ck, cv): the first ten layers of LFM2-24B-A2B at its
    published widths, all 64 experts, bf16 weights, the cell's slots and
    page pool, as ShapeDtypeStructs."""
    from localai_tpu.models import lfm2_moe as lm

    cfg = lm.Lfm2MoeConfig(
        num_layers=10, kinds=("conv", "conv")
        + ("attention", "conv", "conv", "conv") * 2,
        attn=llama.AttnTarget(pallas=True))

    def place(tree):
        return jax.tree.map(lambda x: A(x.shape, x.dtype), tree)

    params = jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    ck, cv = jax.eval_shape(lambda: lm.init_cache(
        cfg, S_L, C_L, jnp.bfloat16, page_size=PAGE, num_pages=POOL_L))
    return cfg, place(params), place(ck), place(cv)


def test_lfm2_decode_step_compiles_without_a_copy_of_an_expert_layer(topo):
    """engine_decode at the cell's size with donated caches, routing
    counters and all: 10.5 GB of arguments, and temporaries far under the
    0.4 GB that ONE projection of one layer's 64 experts is (a layer
    sliced out of the stack for the grouped product was such a copy)."""
    from localai_tpu.models import lfm2_moe as lm

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_lfm2(A)
    assert params["layers"]["w1"].shape == (8, 64, 2048, 1536)
    assert ck["pages"].shape == (2, POOL_L, PAGE, 8, 128)
    assert ck["conv"].shape == (8, S_L, 2, 2048)
    assert llama.decode_attn_impl(cfg.attn_cfg, ck) == "pallas:paged_decode"

    def decode(p, t, ln, act, ck, cv):
        return lm.engine_decode(p, cfg, t, ln, act, ck, cv, route_stats=True)

    compiled = jax.jit(decode, donate_argnums=(4, 5)).lower(
        params, A((S_L,), jnp.int32), A((S_L,), jnp.int32),
        A((S_L,), jnp.bool_), ck, cv).compile()
    mem = compiled.memory_analysis()
    assert 10.5e9 < mem.argument_size_in_bytes < 12.5e9
    assert mem.temp_size_in_bytes < 64 << 20
    assert "paged_decode" in compiled.as_text()


@pytest.mark.parametrize("N", [512, 1024])
def test_lfm2_packed_prefill_compiles(topo, N):
    """A continued pack through the grouped expert form (4 N pairs sorted
    by expert, three ragged products a layer over the whole stacks) and
    the ragged prefill kernel."""
    from localai_tpu.models import lfm2_moe as lm

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_lfm2(A)
    i32 = jnp.int32
    assert llama.ragged_attn_impl(cfg.attn_cfg, ck, N, True) == \
        "pallas:ragged_prefill"

    def pack(p, t, pos, so, ss, st, off, ln, ck, cv):
        return lm.ragged_prefill(p, cfg, t, pos, so, ss, st, off, ln, ck, cv,
                                 continued=True, route_stats=True)

    compiled = jax.jit(pack, donate_argnums=(8, 9)).lower(
        params, A((N,), i32), A((N,), i32), A((N,), i32),
        *_seg_tables(A, S_L), ck, cv).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 256 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9


# ---------- ling_hybrid: KDA states, a latent pool, a share of the experts ----------

S_K, C_K, POOL_K = 96, 4096, 4608    # ling-flash-vl.reason_wide's geometry


def _abstract_ling(A):
    """(cfg, params, ck, cv): the first six layers of Ling-3.0-flash at its
    published widths, a strided 128 of 512 experts and a quarter of the
    vocabulary, bf16 weights, the cell's slots and page pool, as
    ShapeDtypeStructs."""
    from localai_tpu.models import ling_hybrid as lh

    cfg = lh.LingHybridConfig(
        vocab_size=39296, num_layers=6, held=tuple(range(0, 512, 4)),
        attn=llama.AttnTarget(pallas=True))

    def place(tree):
        return jax.tree.map(lambda x: A(x.shape, x.dtype), tree)

    params = jax.eval_shape(
        lambda: lh.init_params(cfg, jax.random.PRNGKey(0)))
    ck, cv = jax.eval_shape(lambda: lh.init_cache(
        cfg, S_K, C_K, jnp.bfloat16, page_size=PAGE, num_pages=POOL_K))
    return cfg, place(params), place(ck), place(cv)


def test_ling_kernels_compile_at_the_cells_geometry(topo):
    """``kda_decode`` on the stacked state (a slot's 2.1 MB block in and
    out, double-buffered, under the kernel's own VMEM limit) and
    ``mla_paged_decode`` over the latent pool (pages of 64 x 640 bfloat16
    copied whole out of HBM)."""
    from localai_tpu.ops.pallas.kda_decode import kda_decode_pallas
    from localai_tpu.ops.pallas.mla_decode import mla_paged_decode

    A = _on(SingleDeviceSharding(topo.devices[0]))
    f32, bf, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    hk = A((S_K, 32, 128), f32)
    kda = jax.jit(kda_decode_pallas, donate_argnums=0).lower(
        A((5, S_K, 32, 128, 128), f32), A((), i32), hk, hk, hk, hk,
        A((S_K, 32), f32), A((S_K,), jnp.bool_)).compile()
    assert "kda_decode" in kda.as_text()
    assert kda.memory_analysis().temp_size_in_bytes < 64 << 20
    mla = jax.jit(lambda *a: mla_paged_decode(*a, rank=512)).lower(
        A((S_K, 32, 640), f32), A((S_K, 1, 640), bf),
        A((1, POOL_K, PAGE, 1, 640), bf), A((S_K, C_K // PAGE), i32),
        A((S_K,), i32), A((), i32)).compile()
    assert "mla_paged_decode" in mla.as_text()
    # the pool goes to the kernel as it lies: no copy of it
    assert mla.memory_analysis().temp_size_in_bytes < 16 << 20


def test_ling_decode_step_compiles_in_place(topo):
    """engine_decode at the cell's size with donated caches, routing
    counters and all: 8.8 GB of arguments, and temporaries far under a
    layer of state (1 GB over 5 layers), a layer's held experts (0.25 GB a
    projection) or the latent pool (0.38 GB)."""
    from localai_tpu.models import ling_hybrid as lh

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_ling(A)
    assert params["layers"]["w1"].shape == (4, 128, 2560, 768)
    assert params["layers"]["router"].shape == (4, 2560, 512)
    assert ck["pages"].shape == (1, POOL_K, PAGE, 1, 640)
    assert cv["pages"].shape == (0, POOL_K, PAGE, 1, 640)
    assert ck["kda"].shape == (5, S_K, 32, 128, 128)
    assert lh.decode_attn_impl(cfg, ck) == "pallas:mla_paged_decode"

    def decode(p, t, ln, act, ck, cv):
        return lh.engine_decode(p, cfg, t, ln, act, ck, cv, route_stats=True)

    compiled = jax.jit(decode, donate_argnums=(4, 5)).lower(
        params, A((S_K,), jnp.int32), A((S_K,), jnp.int32),
        A((S_K,), jnp.bool_), ck, cv).compile()
    mem = compiled.memory_analysis()
    assert 8.5e9 < mem.argument_size_in_bytes < 9.5e9
    assert mem.temp_size_in_bytes < 128 << 20
    hlo = compiled.as_text()
    assert "kda_decode" in hlo and "mla_paged_decode" in hlo


@pytest.mark.parametrize("N", [512, 1024])
def test_ling_packed_prefill_compiles(topo, N):
    """A continued pack: the chunked KDA rule in chunks of 16, the
    materialised MLA form walking the slots' committed latent rows, the
    grouped expert products over the held stacks."""
    from localai_tpu.models import ling_hybrid as lh

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_ling(A)
    i32 = jnp.int32

    def pack(p, t, pos, so, ss, st, off, ln, ck, cv):
        return lh.ragged_prefill(p, cfg, t, pos, so, ss, st, off, ln, ck, cv,
                                 continued=True, route_stats=True)

    compiled = jax.jit(pack, donate_argnums=(8, 9)).lower(
        params, A((N,), i32), A((N,), i32), A((N,), i32),
        *_seg_tables(A, S_K), ck, cv).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9


# ---------- xing4: MLA on every layer, hyper-connections, a latent pool alone ----------

S_X, C_X, POOL_X = 32, 16384, 6144    # xing4-29b-a4b.docqa_long's geometry


def _abstract_xing4(A):
    """(cfg, params, ck, cv): the first six layers of Xing4.0-29B-A4B at its
    published widths, all 64 experts and the whole vocabulary, bf16 weights,
    the cell's slots and its default pool of three quarters, as
    ShapeDtypeStructs."""
    from localai_tpu.models import xing4

    cfg = xing4.Xing4Config(
        num_layers=6, rope_scaling_factor=64.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, attn=llama.AttnTarget(pallas=True))

    def place(tree):
        return jax.tree.map(lambda x: A(x.shape, x.dtype), tree)

    params = jax.eval_shape(
        lambda: xing4.init_params(cfg, jax.random.PRNGKey(0)))
    ck, cv = jax.eval_shape(lambda: xing4.init_cache(
        cfg, S_X, C_X, jnp.bfloat16, page_size=PAGE, num_pages=POOL_X))
    return cfg, place(params), place(ck), place(cv)


_NO_OP = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast",
          "after-all"}


def _executed_ops(text: str, scope: str):
    """(operations one run of a compiled program executes, those whose
    ``op_name`` lies under ``scope``): the entry computation's instructions,
    a fusion as one, a loop's body times its trip count."""
    import re

    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            comps[cur].append(line)

    def trips(line):
        m = re.search(r'known_trip_count":\{"n":"(\d+)"', line)
        if m:
            return int(m.group(1))
        cond = re.search(r"condition=%?([\w.\-]+)", line).group(1)
        for c in comps[cond]:
            m = re.search(r"constant\((\d+)\)", c)
            if m:
                return int(m.group(1))
        return 1

    def count(name):
        n = under = 0
        for line in comps[name]:
            op = re.search(r"= .*? ([\w\-]+)\(", line)
            if not op or op.group(1) in _NO_OP:
                continue
            if op.group(1) == "while":
                a, b = count(re.search(r"body=%?([\w.\-]+)",
                                       line).group(1))
                n, under = n + trips(line) * a, under + trips(line) * b
            else:
                n, under = n + 1, under + (scope in line)
        return n, under

    return count(entry)


def test_mla_decode_kernel_compiles_at_256_pages_a_slot(topo):
    """``mla_paged_decode`` with a page table of 256 entries a slot (32 KB
    of scalar prefetch) over the six-layer pool, which goes to the kernel as
    it lies."""
    from localai_tpu.ops.pallas.mla_decode import mla_paged_decode

    A = _on(SingleDeviceSharding(topo.devices[0]))
    f32, bf, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    mla = jax.jit(lambda *a: mla_paged_decode(*a, rank=512)).lower(
        A((S_X, 32, 640), f32), A((S_X, 1, 640), bf),
        A((6, POOL_X, PAGE, 1, 640), bf), A((S_X, C_X // PAGE), i32),
        A((S_X,), i32), A((), i32)).compile()
    assert "mla_paged_decode" in mla.as_text()
    assert mla.memory_analysis().temp_size_in_bytes < 16 << 20


def test_xing4_decode_step_compiles_in_place(topo):
    """engine_decode at the cell's size with donated caches, routing
    counters and all: 8.35 GB of weights and 3.02 GB of pool as arguments,
    and temporaries far under a layer's experts (0.47 GB a projection) or a
    layer of the pool (0.5 GB)."""
    from localai_tpu.models import xing4

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_xing4(A)
    assert params["layers"]["w1"].shape == (4, 64, 3584, 1024)
    assert params["layers"]["hc_w"].shape == (6, 2, 4 * 3584, 24)
    assert params["layers"]["mla_qb"].shape == (6, 768, 32 * 192)
    assert ck["pages"].shape == (6, POOL_X, PAGE, 1, 640)
    assert cv["pages"].shape == (0, POOL_X, PAGE, 1, 640)
    assert set(ck) == {"pages", "ptab"}
    assert xing4.decode_attn_impl(cfg, ck) == "pallas:mla_paged_decode"

    def decode(p, t, ln, act, ck, cv):
        return xing4.engine_decode(p, cfg, t, ln, act, ck, cv,
                                   route_stats=True)

    compiled = jax.jit(decode, donate_argnums=(4, 5)).lower(
        params, A((S_X,), jnp.int32), A((S_X,), jnp.int32),
        A((S_X,), jnp.bool_), ck, cv).compile()
    mem = compiled.memory_analysis()
    assert 11.2e9 < mem.argument_size_in_bytes < 11.6e9
    assert mem.temp_size_in_bytes < 256 << 20
    assert "mla_paged_decode" in compiled.as_text()
    # the hyper-connections' operations a step (ops/hyper.py's module doc:
    # 1237 of 2394 with the Sinkhorn rounds over one array, 685 of 1870 with
    # the entries as separate vectors)
    total, hc = _executed_ops(compiled.as_text(), "layer/hc")
    assert 200 < hc <= 700 and total <= 1900, (total, hc)


@pytest.mark.parametrize("N", [512, 1024])
def test_xing4_packed_prefill_compiles(topo, N):
    """A continued pack: the materialised MLA form walking up to 256 pages
    of a slot's committed latent rows in blocks of 512, the four streams
    through twelve hyper-connections, the grouped expert products over all
    64 experts: arguments and temporaries inside the chip's 16 GB."""
    from localai_tpu.models import xing4

    A = _on(SingleDeviceSharding(topo.devices[0]))
    cfg, params, ck, cv = _abstract_xing4(A)
    i32 = jnp.int32

    def pack(p, t, pos, so, ss, st, off, ln, ck, cv):
        return xing4.ragged_prefill(p, cfg, t, pos, so, ss, st, off, ln, ck,
                                    cv, continued=True, route_stats=True)

    compiled = jax.jit(pack, donate_argnums=(8, 9)).lower(
        params, A((N,), i32), A((N,), i32), A((N,), i32),
        *_seg_tables(A, S_X), ck, cv).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
