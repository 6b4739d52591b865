"""Pallas TPU kernel: Mamba-2's one-token state update, IN PLACE on the
stacked state, for LIVE slots only.

A decode step rewrites every live slot's state in every state-space layer:
``H = a H + (dt x) (x) B``, ``y = H C`` (ops/ssd.py has the rule). The state
is what the step moves - H x P x N float32 a slot a layer, 2.1 MB at
64 x 64 x 128 - so the kernel's whole job is to read a live slot's state
once and write it once, and to touch no other:

  * the STACKED state ``[L_ssm, S, H, P, N]`` (N = 128 on the lanes: nothing
    pads) comes in whole with the layer index as a scalar-prefetch argument
    read by the index maps, and goes out aliased onto itself
    (``input_output_aliases``): no layer of it is sliced out of the scan
    carry or set back (PERF.md section 6, PR 27);
  * LIVE SLOTS ONLY, by a compacted list: the grid has one program a slot,
    and two more scalar-prefetch arguments say which slot program ``i``
    works on - ``ids`` holds the live slots first, in order, then its last
    live entry repeated - and how many are live. Program ``i < n_live``
    moves slot ``ids[i]``'s state; a program past the live ones maps to the
    block the last live program held, which Pallas neither fetches again
    nor writes back before the grid ends, and its body does nothing: about
    a third of a microsecond where a live slot costs six. (The other
    choice, an index map that revisits the block last visited with the
    slots in place, has no block to revisit before the first live slot.)
    With no slot live every program maps to slot 0, whose state the first
    one copies to the output block so that what is written back is what was
    read;
  * one program holds a slot's whole state (2.1 MB; in and out blocks
    double-buffered are 8.4 MB of VMEM, hence ``vmem_limit_bytes``) and
    walks its heads, unrolled: per head a ``[P, N]`` tile, the decay and
    ``B``, ``C`` as ``[1, N]`` rows that broadcast down it as they are.
    ``dt x`` has to run DOWN the tile and ``y`` comes off it as a column,
    so both cross the kernel's edge TRANSPOSED, ``[P, H]`` a slot (head
    size on the sublanes, heads on the lanes; the wrapper transposes 16 KB
    a slot in XLA): a head's ``dt x`` is then lane ``h`` of that block
    broadcast along the lanes, and its ``y`` is laid into lane ``h`` of the
    output block by a select. (Handing columns over as ``[.., P, 1]`` would
    pad each to 128 lanes in HBM, as much as the state itself.)
  * TWO REGIONS a live slot, and this is where the kernel's time was. The
    lane broadcast of ``dt x`` and the lane reduction that gives ``y`` are
    both work of the cross-lane unit, 512 of each a slot. Alone either is
    cheap; what is dear is the unit going from one kind to the other, which
    a body that takes a head at a time (broadcast, update, reduce, next
    head) makes it do a thousand times a slot. So the body is two
    ``pl.when`` regions, which the scheduler does not move operations
    across: the first writes every head's new state (all the broadcasts),
    the second reads the new state back from the output block in VMEM and
    reduces it (all the reductions). The arithmetic is the one-region
    form's to the bit, ``y`` included.

What a live slot costs alone, in microseconds (TPU v5e; the slope of
``parity sweep mamba2`` between 8 and 48 live; "compute" is the same body
with every program on one resident block, so that nothing is copied;
PERF.md section 6, PR 38):

    form                                            kernel   compute
    a head at a time, both kinds interleaved         7.55     7.48
      (the kernel as PR 36 left it)
    two regions (THIS FILE)                          6.39     2.10
      regions of 32, 16, 8, 4 heads, not of 64                2.17, 2.31,
                                                              2.66, 3.25
    broadcasts only (wrong numbers)                  6.37     1.16
    reductions only (wrong numbers)                  6.41     0.98
    neither (wrong numbers)                          6.38     0.35
    row sums on the MXU against a ones matrix,       6.40     2.24
      ``new * C`` in three bfloat16 parts (exact)
      the parts by masking float32 / 4 heads a dot   6.40     2.23
      one pass at Mosaic's default precision         6.39     1.24
        (rounds to bfloat16: y off by 1.7e-3, refused)
    head pairs as [128, 128] tiles, transposed       6.38     3.23
    ``dt x`` from SMEM scalars, MXU row sums         6.36
    the state copied by hand (pl.ANY, a ring of      6.44-6.50
      2 / 3 / 4 states in VMEM, 1 / 2 / 4 copies each way)
    ... and read and write never at the same time    6.55-6.60

Every form that does not interleave the two kinds lands on 6.4 us, whatever
its compute costs and however the copies are issued: that is what moving
4.19 MB costs on this chip when half of it is written (657 GB/s of the 819
the data sheet gives, whose figure would be 5.1 us; XLA's own in-place
update of the same array moves 663-669 GB/s). The kernel is copy-bound;
the form kept is the one that leaves the arithmetic and the operand
layout as they were.

``y`` of a slot that is not live is not written by the kernel; the wrapper
zeroes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 32 << 20


def _kernel(layer_ref, ids_ref, n_ref, s_ref, dxt_ref, a_ref, b_ref, c_ref,
            yt_ref, out_ref):
    """s, out [H, P, N]; dxt, yt [P, Hp] (heads on the lanes, padded to
    128); a [H, N] (a head's decay along its row); b, c [1, N].
    ``layer_ref`` and ``ids_ref`` are read by the index maps alone."""
    H = s_ref.shape[0]
    i = pl.program_id(0)
    n_live = n_ref[0]

    live = i < n_live

    # two regions, not one: see the module's docstring
    @pl.when(live)
    def _():
        b = b_ref[...]                                       # [1, N]
        dxt = dxt_ref[...]                                   # [P, Hp]
        for h in range(H):
            out_ref[h] = s_ref[h] * a_ref[h:h + 1, :] + dxt[:, h:h + 1] * b

    @pl.when(live)
    def _():
        c = c_ref[...]                                       # [1, N]
        lane = jax.lax.broadcasted_iota(jnp.int32, yt_ref.shape, 1)
        yt = jnp.zeros(yt_ref.shape, jnp.float32)
        for h in range(H):
            yt = jnp.where(lane == h,
                           jnp.sum(out_ref[h] * c, axis=1, keepdims=True), yt)
        yt_ref[...] = yt

    @pl.when((n_live == 0) & (i == 0))
    def _():
        out_ref[...] = s_ref[...]


def live_order(active):
    """-> (ids [S] int32: the live slots first, in order, then the last
    live one repeated (slot 0 when none is), n_live [1] int32)."""
    S = active.shape[0]
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32)
    last = jnp.take(order, jnp.maximum(n - 1, 0))
    return (jnp.where(jnp.arange(S, dtype=jnp.int32) < n, order, last),
            n.reshape(1))


def mamba2_decode_pallas(state, li, x, dt, la, B, C, active,
                         interpret: bool = False):
    """state [L_ssm, S, H, P, N] float32; ``li`` the state-space layer;
    x [S, H, P]; dt, la [S, H]; B, C [S, N]; active [S] bool
    -> (y [S, H, P] float32, zero for a slot that is not live; state
    updated at layer ``li`` for the live slots, no other block of it
    read or written)."""
    L, S, H, P, N = state.shape
    f32 = jnp.float32
    ids, n_live = live_order(active)
    Hp = -(-H // 128) * 128
    dxt = jnp.swapaxes(dt.astype(f32)[..., None] * x.astype(f32), 1, 2)
    dxt = jnp.pad(dxt, ((0, 0), (0, 0), (0, Hp - H)))        # [S, P, Hp]
    a = jnp.broadcast_to(jnp.exp(la.astype(f32))[..., None], (S, H, N))

    def slot(i, li_ref, ids_ref, n_ref):
        return (ids_ref[i], 0, 0)

    def slot_state(i, li_ref, ids_ref, n_ref):
        return (li_ref[0], ids_ref[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((None, None, H, P, N), slot_state),
            pl.BlockSpec((None, P, Hp), slot),
            pl.BlockSpec((None, H, N), slot),
            pl.BlockSpec((None, 1, N), slot),
            pl.BlockSpec((None, 1, N), slot),
        ],
        out_specs=[
            pl.BlockSpec((None, P, Hp), slot),
            pl.BlockSpec((None, None, H, P, N), slot_state),
        ],
    )
    yt, state = pl.pallas_call(
        _kernel,
        # the custom call's name in a profiler capture: the benchmark's
        # mamba2_decode_roofline finds the kernel by it
        name="mamba2_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, P, Hp), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands 0-2 are the scalar-prefetch arguments, 3 the state
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(li, (1,)).astype(jnp.int32), ids, n_live, state, dxt, a,
      B.astype(f32)[:, None, :], C.astype(f32)[:, None, :])
    y = jnp.swapaxes(yt[:, :, :H], 1, 2)                     # [S, H, P]
    return jnp.where(active[:, None, None], y, 0.0), state
