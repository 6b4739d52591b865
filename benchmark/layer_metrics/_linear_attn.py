"""Device time of what the linear-attention layers run, from the profiler
capture: the operations under the scope ``layer/linear_attn`` (the
convolution, the delta rule, the state write) inside the decode programs and
inside the prefill programs, the ``gated_delta_decode`` kernel's own calls,
and the chunked rule's scope ``gated_delta_chunk``.

    python -m benchmark.layer_metrics._linear_attn <capture dir>

prints them as one JSON line. ``benchmark/reduce_named.py`` sorts decode time
by a fixed list of scopes that has no linear-attention scope (there its
operations are ``unscoped``), so the readers of this PR's metrics reduce the
capture a second time, with that module's loader: ``summary(ctx)`` runs this
module once per traced run as a child (benchmark/run.py never imports jax)
and keeps the result on ``ctx``. A program without those scopes gives
zeros, and every reader then returns None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

SCOPE, CHUNK_SCOPE, KERNEL = ("layer/linear_attn", "gated_delta_chunk",
                              "gated_delta_decode")


def reduce(cap: dict) -> dict:
    from benchmark.reduce_named import (CONTROL_FLOW, DECODE_MODULES,
                                        PREFILL_MODULES, _kind, _program_id)

    tot = dict.fromkeys(("decode_module", "decode_linear_attn",
                         "decode_kernel", "prefill_module",
                         "prefill_linear_attn", "prefill_chunk"), 0)
    execs = kernel_calls = 0
    devs = [d for d in cap["device"] if d["ops"]]
    for d in devs:
        mods = sorted((s, s + dur, name) for name, s, dur, _r in d["modules"])
        for s, e, name in mods:
            if _kind(name).startswith(DECODE_MODULES):
                tot["decode_module"] += e - s
            elif _kind(name).startswith(PREFILL_MODULES):
                tot["prefill_module"] += e - s
                execs += 1
        k = 0
        for name, s, dur in sorted(d["ops"], key=lambda o: o[1]):
            while k < len(mods) and mods[k][1] <= s:
                k += 1
            if k == len(mods) or mods[k][0] > s or \
                    name.rsplit("_", 1)[-1] in CONTROL_FLOW:
                continue
            kind = _kind(mods[k][2])
            side = "decode" if kind.startswith(DECODE_MODULES) else \
                "prefill" if kind.startswith(PREFILL_MODULES) else None
            if side is None:
                continue
            path = "/" + (cap["scopes"].get(_program_id(mods[k][2]), {})
                          .get(name) or "") + "/"
            if "/" + SCOPE + "/" in path:
                tot[side + "_linear_attn"] += dur
            if side == "decode" and KERNEL in name:
                tot["decode_kernel"] += dur
                kernel_calls += 1
            if side == "prefill" and "/" + CHUNK_SCOPE + "/" in path:
                tot["prefill_chunk"] += dur
    n = max(1, len(devs))
    out = {k + "_s": v / n / 1e9 for k, v in tot.items()}
    out["prefill_executions"] = execs / n
    out["decode_kernel_calls"] = kernel_calls / n
    return out


def summary(ctx):
    """This run's numbers, computed once and kept on ``ctx``; None where
    the program reports no capture."""
    if hasattr(ctx, "_linear_attn"):
        return ctx._linear_attn
    ctx._linear_attn = None
    cap_dir = ((ctx.state_end or {}).get("profile") or {}).get("capture_dir")
    if not cap_dir or not os.path.isdir(cap_dir):
        return None
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.layer_metrics._linear_attn",
         cap_dir], cwd=os.path.dirname(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__)))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=300)
    if p.returncode != 0:
        print(f"[_linear_attn] exited {p.returncode}: {p.stderr[-2000:]}",
              flush=True)
        return None
    ctx._linear_attn = json.loads(p.stdout.strip().splitlines()[-1])
    print(f"[_linear_attn] {json.dumps(ctx._linear_attn)}", flush=True)
    return ctx._linear_attn


def live_slots(ctx):
    """Mean slots decoding over the window's /debug/state samples."""
    from benchmark import stats

    live = [s["slots_active"] for s in ctx.state_samples if s["slots_active"]]
    return stats.mean(live) if live else None


if __name__ == "__main__":
    from benchmark.reduce_named import load_capture

    try:
        print(json.dumps(reduce(load_capture(sys.argv[1]))))
    except (FileNotFoundError, ValueError) as e:
        sys.exit(f"no capture to read: {e}")
