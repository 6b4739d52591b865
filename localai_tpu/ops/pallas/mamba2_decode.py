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
    a third of a microsecond where a live slot costs five. (The other
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
    output block by a select. Handing columns over as ``[.., P, 1]`` would
    pad each to 128 lanes in HBM, as much as the state itself; standing a
    row up as a column inside the kernel (a masked lane reduction, and a
    masked sublane reduction back: the first form of this kernel, as
    ops/pallas/gated_delta.py does) cost 12.5 us a live slot where this
    costs 7.9 and the copies alone 5.1 (PERF.md section 6, PR 36).

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

    @pl.when(i < n_live)
    def _():
        b, c = b_ref[...], c_ref[...]                        # [1, N]
        dxt = dxt_ref[...]                                   # [P, Hp]
        lane = jax.lax.broadcasted_iota(jnp.int32, dxt.shape, 1)
        yt = jnp.zeros(dxt.shape, jnp.float32)
        for h in range(H):
            new = s_ref[h] * a_ref[h:h + 1, :] + dxt[:, h:h + 1] * b
            out_ref[h] = new                                 # [P, N]
            yt = jnp.where(lane == h,
                           jnp.sum(new * c, axis=1, keepdims=True), yt)
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
