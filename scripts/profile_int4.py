"""Measure int4 vs int8 weight-only matmul streaming on the serving chip.

Question being answered: decode is HBM-bandwidth-bound, so if XLA
streams jnp.int4 weights at 2 values/byte, weight traffic halves and the
ceiling ~doubles. If the int4 path instead materializes an unpacked copy
(or the runtime lacks a packed int4 layout), it will measure AT OR BELOW
int8 and the whole int4 campaign is dead on arrival — measure before
building. Never run on the chip (ROADMAP S6).

Run on the chip, nothing else using it:
    chiprun -- python scripts/profile_int4.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

S = 32          # decode batch (slots)
D, F = 4096, 14336   # 8B-class hidden/ffn
STEPS = 30


def bench(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / STEPS


def main():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((S, D)), jnp.bfloat16)
    w = rng.standard_normal((D, F)).astype(np.float32)

    # per-out-channel int8 (the shipping scheme)
    s8 = np.abs(w).max(axis=0, keepdims=True) / 127.0
    q8 = jnp.asarray(np.clip(np.rint(w / s8), -127, 127), jnp.int8)
    s8 = jnp.asarray(s8, jnp.float32)

    # group-128 int4
    G = 128
    wg = w.reshape(D // G, G, F)
    s4 = np.abs(wg).max(axis=1, keepdims=True) / 7.0
    q4 = np.clip(np.rint(wg / s4), -8, 7).astype(np.int8)
    q4 = jnp.asarray(q4.reshape(D, F), jnp.int4)
    s4 = jnp.asarray(s4, jnp.float32)          # [D/G, 1, F]

    wbf = jnp.asarray(w, jnp.bfloat16)

    @jax.jit
    def m_bf16(x, w):
        return x @ w

    @jax.jit
    def m_i8(x, q, s):
        return x @ (q.astype(jnp.float32) * s).astype(jnp.bfloat16)

    @jax.jit
    def m_i4(x, q, s):
        wd = (q.reshape(D // G, G, F).astype(jnp.float32) * s)
        return x @ wd.reshape(D, F).astype(jnp.bfloat16)

    @jax.jit
    def m_i4_flat(x, q, s):
        # per-out-channel int4 (no groups) — isolates group-scale cost
        return x @ (q.astype(jnp.float32) * s).astype(jnp.bfloat16)

    print(f"device: {jax.devices()[0]}, shapes x[{S},{D}] w[{D},{F}]")
    nbytes = {"bf16": D * F * 2, "int8": D * F, "int4": D * F // 2}
    for name, t in [
        ("bf16", bench(m_bf16, x, wbf)),
        ("int8", bench(m_i8, x, q8, s8)),
        ("int4-g128", bench(m_i4, x, q4, s4)),
        ("int4-flat", bench(m_i4_flat, x, q4, s8 / 16.0)),
    ]:
        nb = nbytes.get(name.split("-")[0], D * F // 2)
        print(f"{name:10s} {t * 1e3:8.3f} ms/matmul   "
              f"{nb / t / 1e9:7.1f} GB/s effective")
    print("int4 HBM bytes on device:",
          q4.nbytes if hasattr(q4, "nbytes") else "?")


if __name__ == "__main__":
    main()
