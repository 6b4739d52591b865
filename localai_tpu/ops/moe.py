"""A routed expert feed-forward: scores, selection, the matrix products over
stacked experts, the weighted sum back.

    s       = sigmoid(W_g h)                     float32, over all E experts
    choice  = the k largest of s + bias          the bias takes part HERE only
    weight  = s[choice] / (sum(s[choice]) + 1e-6)   (``norm_topk``), * scale
    out     = sum_j weight_j * w2_e (silu(w1_e h) * w3_e h),  e = choice_j

Experts are stacked leaves ``w1, w3 [L, E, D, F]`` and ``w2 [L, E, F, D]``
over the expert layers. A row that is not live (a slot that does not decode,
a pack's padding) ROUTES NOWHERE: its choices are the sentinel ``E``, its
weights 0; it touches no expert and ``route_stats`` counts it nowhere.

ONE form of the products, for the decode step and for a prefill pack alike
(``experts_ffn``): the (row, choice) pairs sorted by expert, one grouped
matrix product a projection over exactly the pairs routed, the weighted sum
back by row. It reads the experts a batch TOUCHES and no other. The grouped
product is jax's own Pallas kernel (``megablox.gmm``) where kernels run and
``jax.lax.ragged_dot`` elsewhere (PERF.md section 6, PR 40, has the
measurements: XLA's own lowering of ``ragged_dot`` on the TPU ran at a third
of the roofline, and the form that runs every row through every expert and
masks was no faster at 48 live rows and twice as slow at 8).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def route(h, w_gate, bias, k: int, *, norm_topk: bool = True,
          scale: float = 1.0, active=None):
    """h [T, D]; w_gate [D, E]; bias [E] or None -> (experts [T, k] int32,
    weights [T, k] float32). Scores are computed in float32. A row where
    ``active`` [T] is false routes nowhere (module doc)."""
    f32 = jnp.float32
    E = w_gate.shape[-1]
    s = jax.nn.sigmoid(jnp.dot(h.astype(f32), w_gate.astype(f32),
                               precision=_HIGHEST))
    choice = s if bias is None else s + bias.astype(f32)[None]
    experts = jax.lax.top_k(choice, k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(s, experts, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * scale
    if active is not None:
        experts = jnp.where(active[:, None], experts, E)
        w = jnp.where(active[:, None], w, 0.0)
    return experts, w


def route_stats(experts, E: int):
    """experts [T, k] (sentinel ``E``: routed nowhere) -> [E + 1] float32:
    the (row, expert) pairs each expert got, then how many distinct experts
    were touched."""
    pairs = jnp.sum(jax.nn.one_hot(experts.reshape(-1), E, dtype=jnp.float32),
                    axis=0)
    return jnp.concatenate(
        [pairs, jnp.sum(pairs > 0, dtype=jnp.float32)[None]])


_TM = 128               # rows of (row, choice) pairs a tile of the kernel


def _tile(n: int, most: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``most``; ``n`` itself where there is none."""
    return next((t for t in range(most - most % 128, 0, -128) if n % t == 0),
                n)


def _gmm(x, w, sizes, interpret: bool = False):
    """x [M, K] (M a multiple of ``_TM``) @ w [G, K, N] by groups of rows
    ``sizes`` [G] -> [M, N]. Tiles as measured at K, N = 2048, 1536 and back
    (PERF.md section 6, PR 40): the contraction whole up to 2048."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    K, N = w.shape[1:]
    wide = K >= N
    return gmm(x, w, sizes, preferred_element_type=x.dtype,
               tiling=(_TM, _tile(K, 2048 if wide else 768),
                       _tile(N, 768 if wide else 2048)),
               interpret=interpret)


def experts_ffn(h, experts, weights, w1, w3, w2, layer, pallas: bool = False,
                interpret: bool = False):
    """h [T, D]; experts, weights [T, k]; w1, w3 [L, E, D, F], w2 [L, E, F,
    D]: the WHOLE stacks, of which ``layer`` (traced) is used -> [T, D] in
    h's dtype, the sum over a row's choices in float32. ``pallas``: the
    grouped products through the Pallas kernel (module doc).

    The stacks go to the products as ``L * E`` groups of which all but the
    layer's ``E`` are empty: a reshape, where a layer sliced out of the
    stack for the product is a copy of it (1.2 GB a layer at the
    benchmark's widths; PERF.md section 6, PR 40)."""
    T, k = experts.shape
    L, E = w1.shape[:2]
    flat = experts.reshape(-1)                                   # [T k]
    order = jnp.argsort(flat, stable=True)        # the sentinel E sorts last
    sizes = jnp.sum(flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None],
                    axis=0, dtype=jnp.int32)
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,))

    def product(x, w):
        w = w.reshape((L * E,) + w.shape[2:])
        if pallas:
            return _gmm(x, w, sizes, interpret)
        return jax.lax.ragged_dot(x, w, sizes)

    x = jnp.take(h, order // k, axis=0)                          # [T k, D]
    if pallas:          # whole tiles of rows; the padding is in no group
        x = jnp.pad(x, ((0, -(T * k) % _TM), (0, 0)))
    g, u = product(x, w1), product(x, w3)
    y = product((jax.nn.silu(g) * u).astype(h.dtype), w2)[:T * k]
    # rows past the last group belong to no expert, whatever the product
    # left there
    y = jnp.where((jnp.take(flat, order) < E)[:, None], y, 0)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(T, k, -1).astype(jnp.float32)
    return jnp.sum(y * weights[..., None], axis=1).astype(h.dtype)
