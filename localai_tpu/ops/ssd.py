"""Mamba-2's state-space recurrence (the state-space dual, SSD) in jax.numpy.

Per head, with a state ``H`` in R^{P x N} (P the head size, N the state
size), a token's input ``x`` in R^P, step ``dt > 0``, log decay
``la = -exp(A_log) dt <= 0`` (one scalar a head), and ``B, C`` in R^N shared
by every head (one group):

    H_t = exp(la_t) H_{t-1} + (dt_t x_t) (x) B_t
    y_t = H_t C_t                  (the caller adds the skip term D x_t)

Three forms of the same rule:

  ``ssd_recurrent``  token by token: the definition, the tests' yardstick.
                     Never on the served path.
  ``ssd_chunk``      prefill: chunks of ``chunk`` tokens (256:
                     ``mamba_chunk_size``) over a pack of several segments.
  ``ssd_decode``     one token a slot, on the STACKED state
                     ``[L_ssm, S, H, P, N]`` at a layer index
                     (ops/pallas/mamba2_decode.py is the TPU kernel).

The chunked form. Inside a chunk, with ``G_i = sum_{k<=i} la_k``:

    Y  = (L o (C B^T)) (dt x) + exp(G) (C H_0^T)   L_ij = exp(G_i - G_j), j <= i
    H' = exp(G_Q) H_0 + sum_j exp(G_Q - G_j) (dt_j x_j) (x) B_j

matrix products only: the state enters a chunk once and leaves it once, and
nothing is solved for (the delta rule's chunked form needs a triangular
solve a chunk; this one does not). Every exponent is <= 0. The state path
runs in float32 at ``highest`` matmul precision, as ops/gated_delta.py's
does and for the same reason.

Segments follow ops/gated_delta.py::chunk_plan: a chunk never spans two
requests, one that opens a segment starts from that segment's state (zero
when fresh, the slot's when continued), one that closes it leaves the state
in ``finals``. A segment's tokens are contiguous in the pack, so a chunk is
one dynamic slice of it and the loop holds one chunk's temporaries, not the
pack's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from localai_tpu.ops.gated_delta import chunk_plan

CHUNK = 256
_HI = jax.lax.Precision.HIGHEST


def ssd_recurrent(x, dt, la, B, C, h0):
    """The definition. x [T, H, P]; dt, la [T, H]; B, C [T, N];
    h0 [H, P, N] -> (y [T, H, P], h [H, P, N]), float32."""
    f32 = jnp.float32

    def step(h, a):
        xt, dtt, lat, bt, ct = a
        h = h * jnp.exp(lat)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, ct, precision=_HI)

    h, y = jax.lax.scan(step, h0.astype(f32), tuple(
        a.astype(f32) for a in (x, dt, la, B, C)))
    return y, h


def ssd_chunk(x, dt, la, B, C, h0, seg_off, seg_len, chunk: int = CHUNK):
    """Chunked prefill over a pack. x [N, H, P]; dt, la [N, H]; B, C
    [N, Ns]; h0 [Bs, H, P, Ns] each segment's starting state; ``seg_off`` /
    ``seg_len`` [Bs] as in the packed-prefill contract.
    -> (y [N, H, P] float32 (pad rows zero), finals [Bs, H, P, Ns] float32:
    the state after each segment's last token; a segment of no tokens keeps
    ``h0``)."""
    f32 = jnp.float32
    N, H, P = x.shape
    plan = chunk_plan(seg_off, seg_len, N, chunk)
    start = plan["tok"][:, 0]                                # [NC]
    left = jnp.sum(plan["valid"], axis=-1).astype(jnp.int32)
    pos = jnp.arange(chunk, dtype=jnp.int32)
    tri = pos[:, None] >= pos[None, :]

    def padded(a):      # a chunk's slice may reach past the pack's end
        return jnp.pad(a.astype(f32), ((0, chunk),) + ((0, 0),) * (a.ndim - 1))

    xp, dtp, lap, Bp, Cp = (padded(a) for a in (x, dt, la, B, C))
    h0 = jnp.asarray(h0).astype(f32)

    def body(c, carry):
        h, finals, y = carry
        b = plan["seg"][c]
        at = start[c]
        ok = pos < left[c]

        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, at, chunk, axis=0)

        # a position past the segment's end is inert: no input, no decay
        dtc = jnp.where(ok[:, None], cut(dtp), 0.0)          # [Q, H]
        G = jnp.cumsum(jnp.where(ok[:, None], cut(lap), 0.0), axis=0)
        dx = dtc[:, :, None] * jnp.where(ok[:, None, None], cut(xp), 0.0)
        Bc = jnp.where(ok[:, None], cut(Bp), 0.0)            # [Q, Ns]
        Cc = jnp.where(ok[:, None], cut(Cp), 0.0)
        h = jnp.where(plan["first"][c], h0[b], h)
        cb = jnp.einsum("in,jn->ij", Cc, Bc, precision=_HI)  # [Q, Q]
        Gh = G.T                                             # [H, Q]
        # the exponent is masked before exp: above the diagonal it is >= 0
        L = jnp.exp(jnp.where(tri[None], Gh[:, :, None] - Gh[:, None, :],
                              -jnp.inf))                     # [H, Q, Q]
        yc = jnp.einsum("hij,jhp->ihp", L * cb[None], dx, precision=_HI) \
            + jnp.exp(G)[:, :, None] * jnp.einsum(
                "in,hpn->ihp", Cc, h, precision=_HI)
        h = jnp.exp(G[-1])[:, None, None] * h + jnp.einsum(
            "jhp,jn->hpn", jnp.exp(G[-1][None] - G)[:, :, None] * dx, Bc,
            precision=_HI)
        finals = finals.at[b].set(jnp.where(plan["last"][c], h, finals[b]))
        old = jax.lax.dynamic_slice_in_dim(y, at, chunk, axis=0)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(ok[:, None, None], yc, old), at, axis=0)
        return h, finals, y

    init = (jnp.zeros(h0.shape[1:], f32), h0,
            jnp.zeros((N + chunk, H, P), f32))
    _, finals, y = jax.lax.fori_loop(0, plan["n"], body, init)
    return y[:N], finals


def ssd_decode(state, li, x, dt, la, B, C, active):
    """One token a slot on the stacked state. state [L_ssm, S, H, P, N];
    ``li`` the state-space layer; x [S, H, P]; dt, la [S, H]; B, C [S, N];
    active [S] bool -> (y [S, H, P] float32, state). An inactive slot's
    state is untouched.

    Multiplies and a reduction rather than dots, so that XLA fuses the
    read of ``state[li]`` into them and updates the carry in place
    (ops/gated_delta.py::gated_delta_decode has the finding)."""
    f32 = jnp.float32
    s = jax.lax.dynamic_index_in_dim(state, li, 0, keepdims=False)
    new = s.astype(f32) * jnp.exp(la.astype(f32))[..., None, None] \
        + (dt.astype(f32)[..., None] * x.astype(f32))[..., None] \
        * B.astype(f32)[:, None, None, :]
    y = jnp.sum(new * C.astype(f32)[:, None, None, :], axis=-1)
    new = jnp.where(active[:, None, None, None], new.astype(state.dtype), s)
    return y, jax.lax.dynamic_update_index_in_dim(state, new, li, 0)
