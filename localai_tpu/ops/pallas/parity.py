"""On-device parity of every Pallas kernel against its jnp reference.

    python -m localai_tpu.ops.pallas.parity
    python -m localai_tpu.ops.pallas.parity sweep

The first runs each kernel of this package COMPILED, on whatever device jax finds
(chip_smoke.py runs it on the chip before it boots the server; the
process exits and releases the chip), at Llama-3.1-8B head shapes: 8 KV
heads x 4 query groups of 128, page 64. Interpret-mode tests prove the
kernels' arithmetic; tests/test_tpu_compile.py proves they build for the
chip; only this proves the built kernel computes the right thing there —
the in-kernel strided head slices, the scalar-prefetched page table and
the layer coordinate of the stacked pool (_stacked) are where a layout
surprise would show.

Slot lengths are mixed on purpose: an empty slot, one row, a slot ending
exactly on a page boundary, one just past it, a full slot, and a slot
the engine marks inactive (write position C, which the kernels' caller
maps to a read length of 0) over stale page-table entries (_lengths).
Every page no live slot holds is NaN: the paged kernels must neither
fetch a row from it into their arithmetic nor compute on it. Besides
the 8B shape the paged decode kernel runs at the benchmark cells' decode
geometries (CELLS), which proves the Mosaic lowering and the VMEM fit
of its page ring there (the int8 variant: of its page walk).
Inputs are seeded and bf16-exact, so the kernel (bf16 in, f32 inside)
and the reference (the same values in f32, matmuls at highest precision)
see identical numbers, and what remains is the kernel's own error plus
one bf16 rounding of the output.

Prints one JSON line: the device and the largest error per kernel (see
TOLERANCE for the measure). Exits 1 if an error exceeds the tolerance.

The Mamba-2 state kernel (ops/pallas/mamba2_decode.py) runs at the
granite cell's geometry (SSM_CELL: 48 slots x 64 heads x 64 x 128 float32,
two stacked layers) with 0, 8, 30 and 48 slots live: the state of every
slot that is not live, and the whole of the other layer, is NaN going in
and must come out the very bits it was, while the live slots' update
(``mamba2_decode[liveN]``: 0.0, or the check raises) and output
(``mamba2_decode_y[liveN]``) match ops/ssd.py::ssd_decode.

``sweep`` is a measurement and decides nothing: the compiled paged
decode kernel's microseconds a call at CELLS against live slots and
context reserved (paged_decode_sweep: PERF.md section 6's table, PR 31;
about 4 chip-minutes), and the state kernel's at SSM_CELL against live
slots (mamba2_decode_sweep, with ``live8_over_live48``: a kernel that
moves only live slots reads well under 0.3 there), one JSON line.
``sweep mamba2`` runs the second alone.

The two kernels of ``ling_hybrid`` run at its cell's geometry (KDA_CELL: 96
slots x 32 heads x 128 x 128 float32; MLA_CELL: 96 slots x 64 pages of 64
rows x 640 bfloat16, 32 query heads): ``kda_decode`` as the Mamba-2 kernel
does (NaN wherever no live slot holds, against ops/kda.py::kda_decode), and
``mla_paged_decode`` with the mixed lengths over NaN pages, against
ops/mla.py::decode_attention's page gather, and again at MLA_LONG (256
pages a slot: ``xing4``'s context of 16384). ``ling`` runs those alone;
``sweep kda`` times the state kernel against live slots
(kda_decode_sweep).
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.ops import kda, kvcache, mla, ssd
from localai_tpu.ops.attention import decode_attention_append
from localai_tpu.ops.pallas.decode_attention import (
    decode_attention_append_pallas)
from localai_tpu.ops.pallas.kda_decode import kda_decode_pallas
from localai_tpu.ops.pallas.mamba2_decode import mamba2_decode_pallas
from localai_tpu.ops.pallas.paged_attention import (
    paged_decode_attention_append, paged_decode_attention_append_quant,
    read_lengths)
from localai_tpu.ops.pallas.ragged_prefill import (
    ragged_kernel_plan, ragged_prefill_attention_pallas)
from localai_tpu.ops.ragged_prefill import ragged_prefill_attention

# The error of a kernel is max |out - ref| / (1 + |ref|): absolute for
# small outputs, relative for large ones (an empty slot's output is the
# new value row itself, up to ~4 in magnitude). Two things spend it. The
# kernel rounds its f32 result to bf16 once: up to 2^-9 ~ 0.002 of the
# value, and all there is in interpret mode. On the chip the MXU's
# default precision also rounds the f32 operands of both matmuls (scaled
# q . k, then probs . v) to bf16 — the same precision the jnp serving
# path runs at — which measured 0.003-0.0075 on a v5e (PERF.md, PR 21).
# The tolerance leaves those a factor of 2.5; a layout bug (wrong head,
# wrong page) is an error of order 1.
TOLERANCE = 2e-2

KV, G, HD, PAGE = 8, 4, 128, 64     # Llama-3.1-8B heads, engine page size
S, MP = 8, 8                        # slots, pages per slot (context 512)

# The benchmark cells' decode geometries (PERF.md section 4): slots,
# pages a slot, (KV heads, query heads a KV head, head size), and the
# rows a live slot holds on a mean step there.
CELLS = {
    "8x16": (16, 16, (8, 4, HD), 300),      # mistral7b.chat_rate: 16 x 1024
    "8x64": (16, 64, (8, 4, HD), 2500),     # the Nemo cells: 16 x 4096
    "32x32": (32, 32, (32, 1, HD), 450),    # olmo-hybrid: 32 x 2048, padded
}


# granite-h-micro.longgen_many's state geometry: slots, heads, head size,
# state size; the live counts the kernel is checked and timed at
SSM_CELL = (48, 64, 64, 128)
SSM_LIVE = (0, 8, 30, 48)


# ling-flash-vl.reason_wide's geometries: slots, heads, key and value size;
# slots, pages a slot, query heads, the pool's row width, the latent's rank
KDA_CELL = (96, 32, 128, 128)
KDA_LIVE = (0, 8, 64, 96)
MLA_CELL = (96, 64, 32, 640, 512)
# xing4-29b-a4b.docqa_long's: a context of 16384 is 256 pages a slot (half
# the cell's 32 slots: the walk is a slot's own, and the pool is laid out on
# the host first)
MLA_LONG = (16, 256, 32, 640, 512)


def _lengths(page: int, slots: int = S, mp: int = MP):
    """Write positions of ``slots`` slots of ``mp`` pages: the mix of the
    module docstring, repeated; ``mp * page`` (= C) marks a slot
    inactive."""
    mix = (0, 1, page, page + 1, 3 * page, mp * page, mp * page - 1,
           page // 2 + 5, 3 * page + 8)
    return jnp.asarray([mix[i % len(mix)] for i in range(slots)], jnp.int32)


def _bf16_exact(rng, shape):
    x = rng.standard_normal(shape, dtype=np.float32)
    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


def _paged_kv(rng, dtype, heads, hd, page, slots: int = S, mp: int = MP):
    """One layer's paged K and V caches over ONE shuffled page table (a
    kernel that ignored the table would read the wrong rows). Returns
    ((k, v) in ``dtype``, (k, v) as the reference reads them: float32
    twins, or the same int8 + scales, which it folds itself)."""
    n_pages = slots * mp
    ptab = jnp.asarray(rng.permutation(n_pages).astype(np.int32)
                       .reshape(slots, mp))

    def layer():
        rows = jnp.asarray(_bf16_exact(rng, (n_pages, page, heads, hd)))
        if dtype == jnp.int8:
            q, s = kvcache.quantize(rows)
            lc = {"pages": q, "scales": s, "ptab": ptab}
            return lc, lc
        return ({"pages": rows.astype(dtype), "ptab": ptab},
                {"pages": rows, "ptab": ptab})

    (k, k_ref), (v, v_ref) = layer(), layer()
    return (k, v), (k_ref, v_ref)


def _stacked(pool):
    """The pool as layer 1 of a stacked [2, ...] pool whose layer 0
    holds the same rows on the wrong pages: the program hands the
    kernels the stacked pool and a layer index, and a kernel that
    ignored the index would read layer 0."""
    return jnp.stack([jnp.roll(pool, 1, axis=0), pool])


def _max_err(out, ref, keep=None):
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    err = np.abs(out - ref) / (1.0 + np.abs(ref))
    if keep is not None:
        err = err[keep]
    assert np.isfinite(err).all(), "non-finite kernel output"
    return float(err.max())


def _unheld_nan(leaf, ptab, lengths, page: int):
    """``leaf`` ([n_pages, ...] float) with every page no slot holds
    (by the table and the read lengths) turned to NaN."""
    held = np.zeros(leaf.shape[0], bool)
    for row, n in zip(np.asarray(ptab), -(-np.asarray(lengths) // page)):
        held[row[:n]] = True
    return jnp.where(held.reshape((-1,) + (1,) * (leaf.ndim - 1)), leaf,
                     jnp.nan)


def check_paged_decode(quant: bool, interpret: bool = False,
                       heads=(KV, G, HD), page: int = PAGE,
                       slots: int = S, mp: int = MP) -> float:
    kv, g, hd = heads
    rng = np.random.default_rng(1 + quant)
    (lc, lv), (lc32, lv32) = _paged_kv(
        rng, jnp.int8 if quant else jnp.bfloat16, kv, hd, page, slots, mp)
    q = _bf16_exact(rng, (slots, kv * g, hd))
    nk, nv = (_bf16_exact(rng, (slots, kv, hd)),
              _bf16_exact(rng, (slots, kv, hd)))
    # the engine's write positions, read as the kernels' caller reads them
    lengths = read_lengths(_lengths(page, slots, mp), mp * page)
    nan = lambda leaf: _stacked(_unheld_nan(   # noqa: E731
        leaf, lc["ptab"], lengths, page))
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)   # noqa: E731
    if quant:
        out = paged_decode_attention_append_quant(
            bf(q), bf(nk), bf(nv), _stacked(lc["pages"]),
            nan(lc["scales"]), _stacked(lv["pages"]), nan(lv["scales"]),
            lc["ptab"], lengths, 1, q_per_kv=g, interpret=interpret)
    else:
        out = paged_decode_attention_append(
            bf(q), bf(nk), bf(nv), nan(lc["pages"]), nan(lv["pages"]),
            lc["ptab"], lengths, 1, q_per_kv=g, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = decode_attention_append(
            jnp.asarray(q), jnp.asarray(nk), jnp.asarray(nv),
            kvcache.gather_all_rows(lc32), kvcache.gather_all_rows(lv32),
            lengths, g)
    return _max_err(out, ref)


def time_paged_decode(slots: int, mp: int, heads, rows: int, live: int,
                      calls: int = 256, page: int = PAGE) -> float:
    """Microseconds a call of the compiled paged decode kernel at a
    cell's geometry with ``live`` of its slots holding ``rows`` rows and
    the others inactive (write position C, alternating with the live
    ones): ``calls`` calls chained in one program, each one's output the
    next one's queries, the layer alternating; the best of three."""
    kv, g, hd = heads
    n_pages = slots * mp
    rng = np.random.default_rng(5)
    ptab = jnp.asarray(rng.permutation(n_pages).astype(np.int32)
                       .reshape(slots, mp))
    write = np.full((slots,), mp * page, np.int32)
    if live:
        write[np.linspace(0, slots - 1, live).round().astype(int)] = rows
    lengths = read_lengths(jnp.asarray(write), mp * page)
    pool = jnp.zeros((2, n_pages, page, kv, hd), jnp.bfloat16)
    nk = jnp.asarray(_bf16_exact(rng, (slots, kv, hd)), jnp.bfloat16)
    q = jnp.asarray(_bf16_exact(rng, (slots, kv * g, hd)), jnp.bfloat16)

    @jax.jit
    def chain(q, pool_k, pool_v):
        return jax.lax.fori_loop(
            0, calls, lambda i, q: paged_decode_attention_append(
                q, nk, nk, pool_k, pool_v, ptab, lengths, i % 2,
                q_per_kv=g), q)

    chain(q, pool, pool).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        chain(q, pool, pool).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return round(best / calls * 1e6, 2)


def paged_decode_sweep() -> dict:
    """time_paged_decode at each of CELLS: no slot live, half and all at
    the cell's rows, and all at full context (a live page must cost no
    more there than before the kernel skipped the others)."""
    out = {}
    for name, (slots, mp, heads, rows) in CELLS.items():
        out[name] = {
            f"live{n}_rows{r}": time_paged_decode(slots, mp, heads, r, n)
            for n, r in ((0, rows), (slots // 2, rows), (slots, rows),
                         (slots, mp * PAGE - 1))}
    return out


def check_contiguous_decode(interpret: bool = False,
                            heads=(KV, G, HD), page: int = PAGE) -> float:
    kv, g, hd = heads
    rng = np.random.default_rng(3)
    C = MP * page
    ck, cv = _bf16_exact(rng, (S, C, kv, hd)), _bf16_exact(rng, (S, C, kv, hd))
    q = _bf16_exact(rng, (S, kv * g, hd))
    nk, nv = _bf16_exact(rng, (S, kv, hd)), _bf16_exact(rng, (S, kv, hd))
    lengths = _lengths(page)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)   # noqa: E731
    out = decode_attention_append_pallas(
        bf(q), bf(nk), bf(nv), bf(ck), bf(cv), lengths, q_per_kv=g,
        interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = decode_attention_append(
            jnp.asarray(q), jnp.asarray(nk), jnp.asarray(nv),
            jnp.asarray(ck), jnp.asarray(cv), lengths, g)
    return _max_err(out, ref)


def check_ragged_prefill(N: int, interpret: bool = False,
                         heads=(KV, G, HD), page: int = PAGE) -> float:
    """An [N]-token pack of five segments: a fresh prompt, a
    continuation from mid-page, one from exactly a page boundary, a
    one-token tail, and the rest of the pack continuing a long prefix —
    then pad segments (length 0) up to S."""
    kv, g, hd = heads
    rng = np.random.default_rng(4)
    (lc, lv), (lc32, lv32) = _paged_kv(rng, jnp.bfloat16, kv, hd, page)
    a = N // 8
    assert a > 5, "pack too small for the segment plan"
    lens = [3 * a, a + 5, a - 5, 1]
    lens.append(N - sum(lens) - 7)             # 7 trailing pad tokens
    starts = [0, page + 9, 2 * page, page + 13, 5 * page + 3]
    assert all(st < MP * page for st in starts), "prefixes exceed the context"
    slots = [2, 0, 5, 7, 3]
    B = S
    seg_slots = np.full((B,), S, np.int32)     # pad segments: sentinel slot
    seg_start = np.zeros((B,), np.int32)
    seg_off = np.zeros((B,), np.int32)
    seg_len = np.zeros((B,), np.int32)
    seg_of = np.full((N,), B, np.int32)        # pad tokens: sentinel segment
    off = 0
    for b, (ln, st, sl) in enumerate(zip(lens, starts, slots)):
        seg_slots[b], seg_start[b], seg_off[b], seg_len[b] = sl, st, off, ln
        seg_of[off:off + ln] = b
        off += ln
    q = _bf16_exact(rng, (N, kv * g, hd))
    k, v = _bf16_exact(rng, (N, kv, hd)), _bf16_exact(rng, (N, kv, hd))
    qb, pkb = ragged_kernel_plan(N, kv, g, hd, page_size=page, itemsize=2)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)   # noqa: E731
    out = ragged_prefill_attention_pallas(
        bf(q), bf(k), bf(v), _stacked(lc["pages"]), _stacked(lv["pages"]),
        lc["ptab"], jnp.asarray(seg_slots), jnp.asarray(seg_start),
        jnp.asarray(seg_off), jnp.asarray(seg_len), 1, q_per_kv=g, pkb=pkb,
        qb=qb, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = ragged_prefill_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(seg_of), jnp.asarray(seg_slots),
            jnp.asarray(seg_start), lc32, lv32, g, continued=True)
    return _max_err(out, ref, keep=seg_of < B)    # pad rows are garbage


def _live_mask(slots: int, live: int):
    """``live`` of ``slots`` slots, spread evenly (the first and the last
    among them when there are two or more)."""
    mask = np.zeros((slots,), bool)
    if live:
        mask[np.linspace(0, slots - 1, live).round().astype(int)] = True
    return mask


def _ssm_inputs(rng, slots, heads, p, n):
    f32 = np.float32
    x = rng.standard_normal((slots, heads, p)).astype(f32)
    dt = (0.01 + 0.1 * rng.random((slots, heads))).astype(f32)
    la = (-dt * np.exp(rng.uniform(0, 2.7, (heads,)))).astype(f32)
    B = rng.standard_normal((slots, n)).astype(f32)
    C = rng.standard_normal((slots, n)).astype(f32)
    return tuple(jnp.asarray(a) for a in (x, dt, la, B, C))


def check_mamba2_decode(live: int, interpret: bool = False,
                        cell=SSM_CELL) -> tuple:
    """The state kernel with ``live`` slots live on layer 1 of a stacked
    [2, ...] state -> (the error of the live slots' state, that of their
    output) against the jax.numpy form, apart: the state is three
    elementwise operations and has to come out the reference's very bits
    where the kernel is compiled (the interpreter on the CPU may fuse a
    multiply and an add that the reference does not), the output is a sum
    of 128 products and may differ in rounding order. Everything no live
    slot holds is NaN going in and has to come out bit for bit, and the
    output of a slot that is not live has to be zero."""
    slots, heads, p, n = cell
    rng = np.random.default_rng(7 + live)
    mask = _live_mask(slots, live)
    state = rng.standard_normal((2, slots, heads, p, n)).astype(np.float32)
    state[0] = np.nan
    state[1, ~mask] = np.nan
    args = _ssm_inputs(rng, slots, heads, p, n)
    active = jnp.asarray(mask)
    y, new = jax.jit(lambda s, *a: mamba2_decode_pallas(
        s, jnp.int32(1), *a, active, interpret=interpret),
        donate_argnums=0)(jnp.asarray(state), *args)
    y_ref, new_ref = ssd.ssd_decode(jnp.asarray(state), 1, *args, active)
    y, new, new_ref = np.asarray(y), np.asarray(new), np.asarray(new_ref)
    untouched = np.isnan(new[0]).all() and np.isnan(new[1, ~mask]).all()
    assert untouched, "a state no live slot holds was rewritten"
    assert not y[~mask].any(), "output of a slot that is not live"
    if not live:
        return 0.0, 0.0
    state_err = _max_err(new[1, mask], new_ref[1, mask])
    assert interpret or state_err == 0.0, \
        f"a live slot's state is not ssd_decode's: {state_err}"
    return state_err, _max_err(y[mask], np.asarray(y_ref)[mask])


def time_mamba2_decode(live: int, calls: int = 256, cell=SSM_CELL) -> float:
    """Microseconds a call of the compiled state kernel at the cell's
    geometry with ``live`` slots live: ``calls`` calls chained in one
    program on one state, the layer alternating; the best of three."""
    slots, heads, p, n = cell
    rng = np.random.default_rng(9)
    args = _ssm_inputs(rng, slots, heads, p, n)
    active = jnp.asarray(_live_mask(slots, live))

    @jax.jit
    def chain(state):
        def body(i, carry):
            state, acc = carry
            y, state = mamba2_decode_pallas(state, i % 2, *args, active)
            return state, acc + y
        return jax.lax.fori_loop(
            0, calls, body, (state, jnp.zeros((slots, heads, p))))

    state = jnp.zeros((2, slots, heads, p, n), jnp.float32)
    jax.block_until_ready(chain(state))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(state))
        best = min(best, time.perf_counter() - t0)
    return round(best / calls * 1e6, 2)


def mamba2_decode_sweep() -> dict:
    out = {f"live{n}": time_mamba2_decode(n) for n in SSM_LIVE}
    out["live8_over_live48"] = round(out["live8"] / out["live48"], 4)
    return out


def _kda_inputs(rng, slots, heads, k, v):
    f32 = np.float32

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.standard_normal((slots, heads, k))) * k ** -0.5
    kk = unit(rng.standard_normal((slots, heads, k)))
    vv = rng.standard_normal((slots, heads, v))
    g = -5.0 / (1.0 + np.exp(-rng.normal(-3.0, 2.0, (slots, heads, k))))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((slots, heads))))
    return tuple(jnp.asarray(a.astype(f32)) for a in (q, kk, vv, g, beta))


def check_kda_decode(live: int, interpret: bool = False,
                     cell=KDA_CELL) -> tuple:
    """``kda_decode`` with ``live`` slots live on layer 1 of a stacked
    [2, ...] state -> (the error of the live slots' state, that of their
    output) against ops/kda.py::kda_decode. Everything no live slot holds is
    NaN going in and has to come out bit for bit, and the output of a slot
    that is not live has to be zero."""
    slots, heads, k, v = cell
    rng = np.random.default_rng(17 + live)
    mask = _live_mask(slots, live)
    state = rng.standard_normal((2, slots, heads, k, v)).astype(np.float32)
    state[0] = np.nan
    state[1, ~mask] = np.nan
    args = _kda_inputs(rng, slots, heads, k, v)
    active = jnp.asarray(mask)
    o, new = jax.jit(lambda s, *a: kda_decode_pallas(
        s, jnp.int32(1), *a, active, interpret=interpret),
        donate_argnums=0)(jnp.asarray(state), *args)
    o_ref, new_ref = kda.kda_decode(jnp.asarray(state), 1, *args, active)
    o, new, new_ref = np.asarray(o), np.asarray(new), np.asarray(new_ref)
    untouched = np.isnan(new[0]).all() and np.isnan(new[1, ~mask]).all()
    assert untouched, "a state no live slot holds was rewritten"
    assert not o[~mask].any(), "output of a slot that is not live"
    if not live:
        return 0.0, 0.0
    return (_max_err(new[1, mask], new_ref[1, mask]),
            _max_err(o[mask], np.asarray(o_ref)[mask]))


def time_kda_decode(live: int, calls: int = 256, cell=KDA_CELL) -> float:
    """Microseconds a call of the compiled KDA state kernel at the cell's
    geometry with ``live`` slots live (as ``time_mamba2_decode``)."""
    slots, heads, k, v = cell
    args = _kda_inputs(np.random.default_rng(9), slots, heads, k, v)
    active = jnp.asarray(_live_mask(slots, live))

    @jax.jit
    def chain(state):
        def body(i, carry):
            state, acc = carry
            o, state = kda_decode_pallas(state, i % 2, *args, active)
            return state, acc + o
        return jax.lax.fori_loop(
            0, calls, body, (state, jnp.zeros((slots, heads, v))))

    state = jnp.zeros((2, slots, heads, k, v), jnp.float32)
    jax.block_until_ready(chain(state))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(state))
        best = min(best, time.perf_counter() - t0)
    return round(best / calls * 1e6, 2)


def kda_decode_sweep() -> dict:
    out = {f"live{n}": time_kda_decode(n) for n in KDA_LIVE}
    out["live8_over_live96"] = round(out["live8"] / out["live96"], 4)
    return out


def check_mla_decode(interpret: bool = False, cell=MLA_CELL) -> float:
    """``mla_paged_decode`` over layer 1 of a stacked latent pool, mixed
    lengths through a shuffled page table, every page no live slot holds
    NaN, against the page gather of ops/mla.py::decode_attention."""
    slots, mp, heads, wd, rank = cell
    rng = np.random.default_rng(23)
    n_pages = slots * mp
    pool = np.full((2, n_pages, PAGE, 1, wd), np.nan, np.float32)
    ptab = rng.permutation(n_pages).astype(np.int32).reshape(slots, mp)
    write = np.asarray(_lengths(PAGE, slots, mp))
    read = np.asarray(read_lengths(jnp.asarray(write), mp * PAGE))
    for s in range(slots):
        for pg in range(-(-int(read[s]) // PAGE)):
            pool[1, ptab[s, pg]] = _bf16_exact(rng, (PAGE, 1, wd))
    q = jnp.asarray(_bf16_exact(rng, (slots, heads, wd)) * wd ** -0.5)
    new = jnp.asarray(_bf16_exact(rng, (slots, 1, wd)), jnp.bfloat16)
    ck = {"pages": jnp.asarray(pool, jnp.bfloat16), "ptab": jnp.asarray(ptab)}
    out = mla.decode_attention(q, new, ck, jnp.int32(1), jnp.asarray(read),
                               rank, pallas=True, interpret=interpret)
    # the reference reads zeros where the kernel may read nothing
    ck32 = {"pages": jnp.asarray(np.nan_to_num(pool)), "ptab": ck["ptab"]}
    with jax.default_matmul_precision("highest"):
        ref = mla.decode_attention(q, new, ck32, jnp.int32(1),
                                   jnp.asarray(read), rank)
    return _max_err(out, ref)


def ling_errors(interpret: bool) -> dict:
    return {
        **{"kda_decode" + part + name: err
           for name, args in (
               [("", (3, True, (6, 4, 16, 128)))] if interpret else
               [(f"[live{n}]", (n,)) for n in KDA_LIVE])
           for part, err in zip(("", "_o"), check_kda_decode(*args))},
        "mla_paged_decode": check_mla_decode(
            interpret, (6, 4, 4, 128, 64) if interpret else MLA_CELL),
        **({} if interpret else {
            "mla_paged_decode[256 pages]": check_mla_decode(
                cell=MLA_LONG)})}


def main(argv=None) -> int:
    # --interpret: the CPU rehearsal of chip_smoke.py (Pallas interpreter,
    # one small pack); without it the kernels run compiled, which needs
    # the chip
    argv = sys.argv[1:] if argv is None else argv
    interpret = "--interpret" in argv
    dev = jax.devices()[0]
    if not interpret and dev.platform != "tpu":
        print(f"no TPU: jax found {jax.devices()}", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if "sweep" in argv and "kda" in argv:
        print(json.dumps({"kda_decode_us": kda_decode_sweep(),
                          "device": device}))
        return 0
    if "sweep" in argv:
        out = {"mamba2_decode_us": mamba2_decode_sweep()}
        if "mamba2" not in argv:
            out["paged_decode_us"] = paged_decode_sweep()
        print(json.dumps({**out, "device": device}))
        return 0
    errors = ling_errors(interpret) if "ling" in argv else {
        **ling_errors(interpret),
        "paged_decode": check_paged_decode(False, interpret),
        "paged_decode_int8": check_paged_decode(True, interpret),
        # the cells' geometries: compiled only (the interpreter walks a
        # 1024-page pool for minutes)
        **({} if interpret else {
            f"paged_decode{'_int8' if quant else ''}[{name}]":
            check_paged_decode(quant, heads=heads, slots=slots, mp=mp)
            for name, (slots, mp, heads, _) in CELLS.items()
            for quant in (False, True)}),
        "decode_append": check_contiguous_decode(interpret),
        # the granite cell's geometry compiled; a small one interpreted
        **{"mamba2_decode" + part + name: err
           for name, args in (
               [("", (3, True, (6, 4, 8, 128)))] if interpret else
               [(f"[live{n}]", (n,)) for n in SSM_LIVE])
           for part, err in zip(("", "_y"), check_mamba2_decode(*args))},
        # the pack buckets chip_smoke.py's engine builds
        **{f"ragged_prefill[{n}]": check_ragged_prefill(n, interpret)
           for n in ((128,) if interpret else (128, 512, 1024))},
    }
    ok = all(e <= TOLERANCE for e in errors.values())
    print(json.dumps({
        "ok": ok, "interpret": interpret, "tolerance": TOLERANCE,
        "max_error": errors, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
