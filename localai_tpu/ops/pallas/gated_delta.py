"""Pallas TPU kernel: the gated delta rule's one-token update, IN PLACE on
the stacked recurrent state.

A decode step rewrites every live slot's state in every linear-attention
layer: read ``S``, decay it, write ``k (beta (v - S^T k))^T`` into it, read
``S^T q`` off the result (ops/gated_delta.py has the rule). The state is
what the step moves - H x K x V float32 a slot a layer, 2.2 MB at 30 x 96 x
192 - so the kernel's whole job is to read it once and write it once:

  * the STACKED state ``[L_lin, S, H, K, V]`` comes in whole with the layer
    index as a scalar-prefetch argument read by the index maps, and goes
    out aliased onto itself (``input_output_aliases``): no layer of it is
    sliced out of the scan carry or set back (PERF.md section 6, PR 27: a
    layer handed to a custom call as ``state[li]`` is copied out and back);
  * grid ``(S, H / hb)``: one program a slot and a block of ``hb`` heads
    (73.7 KB a head at 96 x 192 float32; ``hb`` heads amortise a grid step's
    fixed cost, in and out blocks double-buffered stay under 4 MB of VMEM);
  * per program the update is elementwise multiplies and two reductions
    over K on the VPU. q, k and v arrive as rows (``[.., 1, K]``,
    ``[.., 1, V]``); v broadcasts down a ``[K, V]`` tile as it is, and q and
    k are stood up as columns inside the kernel by a masked lane reduction
    (K x K multiplies a head, half the tile's own): handing them over as
    ``[.., K, 1]`` would pad each to 128 lanes in HBM, 47 MB apiece a layer
    a step at 32 x 30 x 96, a third of what the state itself moves.

An inactive slot's state must not change: the caller folds that into the
arguments (``g = 0``, ``beta = 0``: decay by 1, write nothing), which leaves
the tile's values as they were; its tile is still read and written back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, s_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
            o_ref, out_ref):
    """s [1, hb, K, V]; q, k [1, hb, 1, K]; v [1, hb, 1, V]; g, b
    [1, hb, 1, 1]. ``layer_ref`` is read by the index maps alone."""
    hb, K, _ = s_ref.shape[1:]
    eye = jax.lax.broadcasted_iota(jnp.int32, (hb, K, K), 1) == \
        jax.lax.broadcasted_iota(jnp.int32, (hb, K, K), 2)

    def column(row):            # [hb, 1, K] -> [hb, K, 1], no relayout
        return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)

    s = s_ref[0] * jnp.exp(g_ref[0])                         # [hb, K, V]
    k = column(k_ref[0])
    ks = jnp.sum(s * k, axis=1, keepdims=True)               # [hb, 1, V]
    u = b_ref[0] * (v_ref[0] - ks)
    new = s + k * u
    o_ref[0] = jnp.sum(new * column(q_ref[0]), axis=1, keepdims=True)
    out_ref[0] = new


def _head_block(H: int, K: int, V: int, budget: int = 1 << 20) -> int:
    """Heads a program: the largest divisor of H whose tile is <= budget."""
    return max(d for d in range(1, H + 1)
               if H % d == 0 and (d == 1 or d * K * V * 4 <= budget))


def gated_delta_decode_pallas(delta, li, q, k, v, g, beta, active,
                              interpret: bool = False):
    """delta [L_lin, S, H, K, V] float32; ``li`` the linear layer; q, k
    [S, H, K]; v [S, H, V]; g, beta [S, H]; active [S] bool
    -> (o [S, H, V] float32, delta updated at layer ``li``)."""
    L, S, H, K, V = delta.shape
    f32 = jnp.float32
    hb = _head_block(H, K, V)
    live = active[:, None]
    g = jnp.where(live, g.astype(f32), 0.0)[..., None, None]
    beta = jnp.where(live, beta.astype(f32), 0.0)[..., None, None]

    def at(s, j, li_ref):
        return (s, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, H // hb),
        in_specs=[
            pl.BlockSpec((None, 1, hb, K, V),
                         lambda s, j, li_ref: (li_ref[0], s, j, 0, 0)),
            pl.BlockSpec((1, hb, 1, K), at),
            pl.BlockSpec((1, hb, 1, K), at),
            pl.BlockSpec((1, hb, 1, V), at),
            pl.BlockSpec((1, hb, 1, 1), at),
            pl.BlockSpec((1, hb, 1, 1), at),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, 1, V), at),
            pl.BlockSpec((None, 1, hb, K, V),
                         lambda s, j, li_ref: (li_ref[0], s, j, 0, 0)),
        ],
    )
    o, delta = pl.pallas_call(
        _kernel,
        # the custom call's name in a profiler capture: the benchmark's
        # gated_delta_decode_roofline finds the kernel by it
        name="gated_delta_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, 1, V), f32),
                   jax.ShapeDtypeStruct(delta.shape, f32)],
        # operand 0 is the scalar-prefetch layer index, 1 the state
        input_output_aliases={1: 1},
        interpret=interpret,
    )(jnp.reshape(li, (1,)).astype(jnp.int32), delta,
      q.astype(f32)[:, :, None, :], k.astype(f32)[:, :, None, :],
      v.astype(f32)[:, :, None, :], g, beta)
    return o[:, :, 0, :], delta
