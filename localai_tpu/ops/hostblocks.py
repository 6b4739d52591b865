"""Whole-leaf host passes of the loader, run as independent blocks on a pool
of host threads.

A checkpoint leaf ``[..., in, out]`` that the loader quantizes or casts on
the host (ops/quant.py::quantize_weight*, ``cast_leaf`` below) is billions of
values; as one chain of whole-array numpy calls on one thread each call
allocates (and page-faults) a float32 temporary of the whole leaf. Here the
pass runs over blocks of one leading index, each worked in a float32 scratch
that a thread keeps across its blocks, on a pool of as many threads as the
process has cores, writing into one preallocated output of the leaf. A
quantizer's block is ``BLOCK_COLS`` output channels (its scale reduces only
the contraction axis, so column blocks share nothing); the cast's is a run
of rows (it reduces nothing, and rows are contiguous: the output's pages are
first touched in order). Every element sees the same numpy operations in
the same precision as the whole-array form, so the result is that form's,
bit for bit, whatever the thread count. numpy's inner loops release the GIL:
plain threads suffice. The pool lives for one leaf; no thread outlives the
call.
"""

from __future__ import annotations

import os
import queue
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

# output channels a block: a run of 1 KiB (float16) in every row of the leaf
BLOCK_COLS = 512
# float32 values of one scratch array: a quantizer's block is worked this
# many values at a time, and a block of the cast is this many. Not smaller:
# every numpy call takes the GIL back when its loop ends, and with a dozen
# threads calls of under a million values spend their time waiting for it
# (PERF.md section 6, PR 41).
CHUNK_ELEMS = 1 << 21
# a leaf below this is one block on the calling thread: starting a pool
# costs more than the pass
INLINE_ELEMS = 1 << 20

_BF16 = np.dtype(ml_dtypes.bfloat16)


def host_threads() -> int:
    """Cores this process may run on: the pool's width."""
    return len(os.sched_getaffinity(0))


class Scratch:
    """One thread's work space: named arrays, grown to the largest request
    and reused by every block the thread works."""

    def __init__(self):
        self._bufs: dict = {}

    def get(self, name: str, shape: tuple) -> np.ndarray:
        """float32 ``shape`` (its contents are whatever was left there)."""
        n = int(np.prod(shape))
        buf = self._bufs.get(name)
        if buf is None or buf.size < n:
            buf = self._bufs[name] = np.empty(n, np.float32)
        return buf[:n].reshape(shape)


def run_blocks(work, arrays, n_lead: int, axis: int, step: int,
               threads=None) -> dict:
    """``work(scratch, *views)`` over every block of ``arrays`` (host arrays
    of one rank that share their first ``n_lead`` axes): ``step`` indices of
    ``axis`` under one leading index. The last axis is a run of output
    channels, which a quantizer's scale leaves independent; axis ``n_lead``
    is a run of rows, contiguous in a contiguous leaf. A small leaf is one
    block (the arrays themselves) on the calling thread. ``threads`` forces
    the pool's width (tests). -> {"threads", "blocks"}: how it ran."""
    first = arrays[0]
    if first.size < INLINE_ELEMS:
        work(Scratch(), *arrays)
        return {"threads": 1, "blocks": 1}
    todo: queue.SimpleQueue = queue.SimpleQueue()
    whole = (slice(None),) * (axis - n_lead)
    for idx in np.ndindex(*first.shape[:n_lead]):
        for k in range(0, first.shape[axis], step):
            todo.put(idx + whole + (slice(k, k + step),))
    blocks = todo.qsize()
    threads = max(1, min(threads or host_threads(), blocks))

    def drain():
        scratch = Scratch()
        while True:
            try:
                block = todo.get_nowait()
            except queue.Empty:
                return
            work(scratch, *(a[block] for a in arrays))

    if threads == 1:
        drain()
    else:
        with ThreadPoolExecutor(threads, "hostblocks") as pool:
            for done in [pool.submit(drain) for _ in range(threads)]:
                done.result()       # a block's exception is raised here
    return {"threads": threads, "blocks": blocks}


def _cast_block(scratch: Scratch, src: np.ndarray, dst: np.ndarray):
    """One block of ``cast_leaf``. float16 / float32 -> bfloat16 is worked
    here in numpy's own loops, which release the GIL (ml_dtypes' cast does
    not, so it cannot be spread over threads): through float32, rounded to
    nearest even by the integer steps of Eigen's ``float_to_bfloat16_rtne``
    (which is ml_dtypes' cast), ``(bits + 0x7FFF + (bits >> 16 & 1)) >> 16``,
    a NaN to the quiet NaN of its sign. Any other pair is numpy's cast."""
    if dst.dtype != _BF16 or src.dtype not in (np.float16, np.float32):
        np.copyto(dst, src, casting="unsafe")
        return
    dst = dst.view(np.uint16)
    x = scratch.get("x", src.shape)
    bias = scratch.get("bias", src.shape).view(np.uint32)
    np.copyto(x, src)
    has_nan = np.isnan(np.max(x)) if x.size else False
    bits = x.view(np.uint32)
    np.right_shift(bits, 16, out=bias)
    np.bitwise_and(bias, 1, out=bias)
    np.add(bias, 0x7FFF, out=bias)
    np.add(bits, bias, out=bits)
    np.right_shift(bits, 16, out=bits)
    np.copyto(dst, bits, casting="unsafe")
    if has_nan:
        nan = np.isnan(src)
        dst[nan] = np.where(np.signbit(src[nan]), 0xFFC0, 0x7FC0)


def cast_leaf(arr, dtype, threads=None, ran=None) -> np.ndarray:
    """A host leaf in ``dtype``, bit for bit ``np.asarray(arr, dtype)``
    (which is the host half of ``jnp.asarray(arr, dtype)``), converted by
    blocks into a fresh array; ``arr`` itself where it already has the
    dtype. ``ran``, a dict, receives how it ran ({"threads", "blocks"})."""
    arr = np.asarray(arr)
    dtype = np.dtype(dtype)
    how = {"threads": 1, "blocks": 1}
    out = arr
    if arr.dtype != dtype:
        out = np.empty(arr.shape, dtype)
        n_lead = max(0, arr.ndim - 2)
        row = int(np.prod(arr.shape[n_lead + 1:]))
        how = run_blocks(_cast_block, (arr, out), n_lead, n_lead,
                         max(1, CHUNK_ELEMS // max(1, row)), threads)
    if ran is not None:
        ran.update(how)
    return out
