"""A routed expert feed-forward: scores, selection (plain or limited to the
best groups of experts), the matrix products over the stacked experts THIS
CHIP HOLDS, the weighted sum back; a shared expert beside it.

    s       = sigmoid(W_g h)                     float32, over all E experts
    c       = s + bias                           the bias takes part HERE only
    groups  = with ``n_group`` > 1: the experts in n_group equal runs; a
              group scores the sum of its two largest c, the ``topk_group``
              best groups are kept and c counts nowhere else
    choice  = the k largest of c
    weight  = s[choice] / (sum(s[choice]) + eps)    (``norm_topk``), * scale
    out     = sum_j weight_j * w2_e (silu(w1_e h) * w3_e h),  e = choice_j
              (+ ``shared_ffn``: one expert every row takes, unweighted)

Experts are stacked leaves ``w1, w3 [L, E_held, D, F]`` and ``w2 [L, E_held,
F, D]`` over the expert layers. The router always scores all ``E`` experts
of the model. A chip that holds a share of them says which (``held``: their
sorted global ids, ``E_held`` of them); a choice that is not held is
computed on another chip: here it goes to the sentinel as an idle row's
does - no product, no weight - so what ``experts_ffn`` returns is THIS
chip's part of the sum, and the parts of all the shares add up to the whole
layer's (tests/test_ling_hybrid.py holds that). A row that is not live (a
slot that does not decode, a pack's padding) ROUTES NOWHERE: its choices
are the sentinel ``E``, its weights 0; it touches no expert and
``route_stats`` counts it nowhere.

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
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def keep_groups(choice, n_group: int, topk_group: int):
    """choice [T, E] -> the same with every expert outside the row's
    ``topk_group`` best of ``n_group`` groups at -inf; a group's score is
    the sum of its two largest entries (DeepSeek-V3's rule)."""
    T, E = choice.shape
    grouped = choice.reshape(T, n_group, E // n_group)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)     # [T, n_group]
    kept = jax.lax.top_k(score, topk_group)[1]
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None],
                   axis=1)                                     # [T, n_group]
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)


def route(h, w_gate, bias, k: int, *, norm_topk: bool = True,
          scale: float = 1.0, active=None, n_group: int = 1,
          topk_group: int = 1, eps: float = 1e-6):
    """h [T, D]; w_gate [D, E]; bias [E] or None -> (experts [T, k] int32,
    weights [T, k] float32). Scores are computed in float32. With
    ``n_group`` > 1 the choice is limited to the ``topk_group`` best groups
    (module doc); 1 and 1 is the plain rule. A row where ``active`` [T] is
    false routes nowhere (module doc)."""
    f32 = jnp.float32
    E = w_gate.shape[-1]
    s = jax.nn.sigmoid(jnp.dot(h.astype(f32), w_gate.astype(f32),
                               precision=_HIGHEST))
    choice = s if bias is None else s + bias.astype(f32)[None]
    if n_group > 1:
        choice = keep_groups(choice, n_group, topk_group)
    experts = jax.lax.top_k(choice, k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(s, experts, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    w = w * scale
    if active is not None:
        experts = jnp.where(active[:, None], experts, E)
        w = jnp.where(active[:, None], w, 0.0)
    return experts, w


def local_ids(experts, E: int, held=None):
    """experts [..] global ids (sentinel ``E``) -> their index among the
    experts ``held`` here (sorted global ids; None: all ``E``), with the
    sentinel ``len(held)`` for a choice that is not held and for ``E``."""
    if held is None:
        return experts
    held = np.asarray(held)
    table = np.full((E + 1,), len(held), np.int32)
    table[held] = np.arange(len(held), dtype=np.int32)
    return jnp.take(jnp.asarray(table), experts, axis=0)


def route_stats(experts, E: int, held=None):
    """experts [T, k] (global ids; sentinel ``E``: routed nowhere) ->
    [E_held + 2] float32: the (row, expert) pairs each HELD expert got, how
    many distinct held experts were touched, and how many pairs the rows
    routed in all, held here or not (with every expert held: the first
    entries' sum)."""
    n = E if held is None else len(held)
    pairs = jnp.sum(jax.nn.one_hot(local_ids(experts, E, held).reshape(-1),
                                   n, dtype=jnp.float32), axis=0)
    return jnp.concatenate(
        [pairs, jnp.sum(pairs > 0, dtype=jnp.float32)[None],
         jnp.sum(experts < E, dtype=jnp.float32)[None]])


def shared_ffn(h, w1, w3, w2):
    """The shared expert: h [T, D]; w1, w3 [D, F], w2 [F, D] (one layer's)
    -> [T, D] in h's dtype."""
    dt = h.dtype
    return (jax.nn.silu(h @ w1.astype(dt)) * (h @ w3.astype(dt))) \
        @ w2.astype(dt)


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
                interpret: bool = False, held=None, n_experts: int = 0):
    """h [T, D]; experts, weights [T, k]; w1, w3 [L, E, D, F], w2 [L, E, F,
    D]: the WHOLE stacks, of which ``layer`` (traced) is used -> [T, D] in
    h's dtype, the sum over a row's choices in float32. ``pallas``: the
    grouped products through the Pallas kernel (module doc). ``held``: the
    sorted global ids of the stacks' ``E`` experts among the router's
    ``n_experts`` (None: the stacks hold them all); the result is then this
    share's part of the sum (module doc).

    The stacks go to the products as ``L * E`` groups of which all but the
    layer's ``E`` are empty: a reshape, where a layer sliced out of the
    stack for the product is a copy of it (1.2 GB a layer at the
    benchmark's widths; PERF.md section 6, PR 40)."""
    T, k = experts.shape
    L, E = w1.shape[:2]
    flat = local_ids(experts, n_experts, held).reshape(-1)       # [T k]
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
