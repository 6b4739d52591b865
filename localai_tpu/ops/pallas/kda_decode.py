"""Pallas TPU kernel: Kimi delta attention's one-token update, IN PLACE on
the stacked recurrent state, for LIVE slots only.

A decode step rewrites every live slot's state in every KDA layer: scale
ROW ``c`` of ``S`` by ``alpha_c`` (the decay is a vector over the key
channels: ops/kda.py has the rule), write ``k (beta (v - S^T k))^T`` into it,
read ``S^T q`` off the result. The state is what the step moves - H x K x V
float32 a slot a layer, 2.1 MB at 32 x 128 x 128 - so the kernel's job is to
read a live slot's state once and write it once, and to touch no other:

  * the STACKED state ``[L, S, H, K, V]`` comes in whole with the layer index
    as a scalar-prefetch argument read by the index maps, and goes out
    aliased onto itself: no layer of it is sliced out of the scan carry or
    set back (PERF.md section 6, PR 27);
  * LIVE SLOTS ONLY, by ops/pallas/mamba2_decode.py's compacted list
    (``live_order``): program ``i < n_live`` moves slot ``ids[i]``'s state,
    a program past the live ones maps to the block the last live program
    held, which Pallas neither fetches again nor writes back, and its body
    does nothing. (ops/pallas/gated_delta.py, the scalar-decay kernel, folds
    an idle slot into its arguments and still reads and writes its tile.)
    With no slot live the first program copies slot 0's state to the output
    block, so that what is written back is what was read;
  * one program holds a slot's whole state (in and out blocks double-buffered
    are 8.4 MB of VMEM, hence ``vmem_limit_bytes``) and walks its heads,
    unrolled: per head a ``[K, V]`` tile. ``v`` and the output are rows
    ``[1, V]`` that broadcast down the tile as they are; the decay, ``k``
    and ``q`` run DOWN the tile, so they cross the kernel's edge
    TRANSPOSED, ``[K, H]`` a slot (key channels on the sublanes, heads on
    the lanes, padded to 128): a head's column is lane ``h`` of that block
    broadcast along the lanes. (Handed over as ``[.., K, 1]`` each would pad
    to 128 lanes in HBM, as much as the state itself.) The two reductions
    run over the sublanes.

``o`` of a slot that is not live is not written by the kernel; the wrapper
zeroes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.pallas.mamba2_decode import live_order

_VMEM_LIMIT = 32 << 20


def _kernel(layer_ref, ids_ref, n_ref, s_ref, at_ref, kt_ref, qt_ref, v_ref,
            b_ref, o_ref, out_ref):
    """s, out [H, K, V]; at, kt, qt [K, Hp] (heads on the lanes); v, o
    [H, V]; b [H, V] (a head's write strength along its row).
    ``layer_ref`` and ``ids_ref`` are read by the index maps alone."""
    H = s_ref.shape[0]
    i = pl.program_id(0)
    n_live = n_ref[0]

    @pl.when(i < n_live)
    def _():
        at, kt, qt = at_ref[...], kt_ref[...], qt_ref[...]   # [K, Hp]
        for h in range(H):
            k = kt[:, h:h + 1]                               # [K, 1]
            sd = s_ref[h] * at[:, h:h + 1]                   # [K, V]
            ks = jnp.sum(sd * k, axis=0, keepdims=True)      # [1, V]
            u = b_ref[h:h + 1, :] * (v_ref[h:h + 1, :] - ks)
            new = sd + k * u
            out_ref[h] = new
            o_ref[h:h + 1, :] = jnp.sum(new * qt[:, h:h + 1], axis=0,
                                        keepdims=True)

    @pl.when((n_live == 0) & (i == 0))
    def _():
        out_ref[...] = s_ref[...]


def kda_decode_pallas(state, li, q, k, v, g, beta, active,
                      interpret: bool = False):
    """state [L, S, H, K, V] float32; ``li`` the KDA layer; q, k, g
    [S, H, K]; v [S, H, V]; beta [S, H]; active [S] bool
    -> (o [S, H, V] float32, zero for a slot that is not live; state updated
    at layer ``li`` for the live slots, no other block of it read or
    written)."""
    L, S, H, K, V = state.shape
    f32 = jnp.float32
    ids, n_live = live_order(active)
    Hp = -(-H // 128) * 128

    def down(a):                 # [S, H, K] -> [S, K, Hp]
        return jnp.pad(jnp.swapaxes(a.astype(f32), 1, 2),
                       ((0, 0), (0, 0), (0, Hp - H)))

    b = jnp.broadcast_to(beta.astype(f32)[..., None], (S, H, V))

    def slot(i, li_ref, ids_ref, n_ref):
        return (ids_ref[i], 0, 0)

    def slot_state(i, li_ref, ids_ref, n_ref):
        return (li_ref[0], ids_ref[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((None, None, H, K, V), slot_state),
            pl.BlockSpec((None, K, Hp), slot),
            pl.BlockSpec((None, K, Hp), slot),
            pl.BlockSpec((None, K, Hp), slot),
            pl.BlockSpec((None, H, V), slot),
            pl.BlockSpec((None, H, V), slot),
        ],
        out_specs=[
            pl.BlockSpec((None, H, V), slot),
            pl.BlockSpec((None, None, H, K, V), slot_state),
        ],
    )
    o, state = pl.pallas_call(
        _kernel,
        # the custom call's name in a profiler capture: the benchmark's
        # kda_decode_roofline finds the kernel by it
        name="kda_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, V), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands 0-2 are the scalar-prefetch arguments, 3 the state
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(li, (1,)).astype(jnp.int32), ids, n_live, state,
      down(jnp.exp(g.astype(f32))), down(k), down(q), v.astype(f32), b)
    return jnp.where(active[:, None, None], o, 0.0), state
