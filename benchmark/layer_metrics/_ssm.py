"""Device time of what the state-space (Mamba-2) layers run, from the
profiler capture: the operations under the scope ``layer/ssm`` (the
convolution, the state update, the state write) inside the decode programs
and inside the prefill programs, the ``mamba2_decode`` kernel's own calls with
the live slots each ran for, and the chunked form's scope ``ssd_chunk``.

    python -m benchmark.layer_metrics._ssm <capture dir> [--spans FILE]

prints them as one JSON line. ``benchmark/reduce_named.py`` sorts decode time
by a fixed list of scopes that has no state-space scope (there these
operations are ``unscoped``), so the readers of these metrics reduce the
capture a second time, with that module's loader, as
``layer_metrics/_linear_attn.py`` does: ``summary(ctx)`` runs this module
once per traced run as a child (benchmark/run.py never imports jax) and keeps
the result on ``ctx``. A program without those scopes gives zeros, and every
reader then returns None.

Live slots. The kernel moves the state of the slots that are live and of no
other, so its least bytes are counted per live slot. Each call is placed on
the ring's clock (the capture's ``clock_anchor`` and /debug/state's
``profile.epoch_ns``, as reduce_named does) and given to the
``decode_burst_device`` span that was dispatched before it and became ready
soonest after it: executions are first in, first out, so that is the burst
that was running. The span's ``slot_ids`` are the slots still live when the
host read the burst's tokens: at most those the device worked on, so the
share can only read low. ``--spans`` is a JSON file {"profile": ..., "spans":
[...]}; without it no call finds a burst and ``live_slot_calls`` is 0.
"""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys
import tempfile

SCOPE, CHUNK_SCOPE, KERNEL = "layer/ssm", "ssd_chunk", "mamba2_decode"
BURST = "decode_burst_device"


def reduce(cap: dict, spans=None, profile=None) -> dict:
    from benchmark.reduce_named import (ANCHOR, CONTROL_FLOW, DECODE_MODULES,
                                        PREFILL_MODULES, _kind, _program_id,
                                        _skew_ns)

    tot = dict.fromkeys(("decode_module", "decode_ssm", "decode_kernel",
                         "matched_kernel", "prefill_module", "prefill_ssm",
                         "prefill_chunk"), 0)
    kernel_calls = matched_calls = live_slot_calls = 0
    anchor = next((h for h in cap["host"] if h[0] == ANCHOR), None)
    bursts = []
    if spans and profile and anchor is not None:
        base = anchor[1] - int(profile["epoch_ns"])
        bursts = sorted(
            (int((sp["t"] + sp["dur_ms"] / 1e3) * 1e9) + base,
             int(sp["t"] * 1e9) + base,
             len((sp.get("args") or {}).get("slot_ids") or ()))
            for sp in spans if sp["name"] == BURST)
    ready = [b[0] for b in bursts]
    devs = [d for d in cap["device"] if d["ops"]]
    for d in devs:
        shift = _skew_ns(d["modules"], cap["host"])[0]
        mods = sorted((s, s + dur, name) for name, s, dur, _r in d["modules"])
        for s, e, name in mods:
            if _kind(name).startswith(DECODE_MODULES):
                tot["decode_module"] += e - s
            elif _kind(name).startswith(PREFILL_MODULES):
                tot["prefill_module"] += e - s
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
                tot[side + "_ssm"] += dur
            if side == "prefill" and "/" + CHUNK_SCOPE + "/" in path:
                tot["prefill_chunk"] += dur
            if side == "decode" and KERNEL in name:
                tot["decode_kernel"] += dur
                kernel_calls += 1
                j = bisect.bisect_left(ready, s + shift)
                if j < len(bursts) and bursts[j][1] <= s + shift:
                    tot["matched_kernel"] += dur
                    matched_calls += 1
                    live_slot_calls += bursts[j][2]
    n = max(1, len(devs))
    out = {k + "_s": v / n / 1e9 for k, v in tot.items()}
    out["decode_kernel_calls"] = kernel_calls / n
    out["matched_kernel_calls"] = matched_calls / n
    # the sum over the matched calls of the slots live in each
    out["live_slot_calls"] = live_slot_calls / n
    return out


def summary(ctx):
    """This run's numbers, computed once and kept on ``ctx``; None where
    the program reports no capture."""
    if hasattr(ctx, "_ssm"):
        return ctx._ssm
    ctx._ssm = None
    prof = (ctx.state_end or {}).get("profile") or {}
    cap_dir = prof.get("capture_dir")
    if not cap_dir or not os.path.isdir(cap_dir):
        return None
    fd, spans_file = tempfile.mkstemp(suffix=".json", dir=cap_dir)
    with os.fdopen(fd, "w") as f:
        json.dump({"profile": prof, "spans": [
            s for s in ctx.spans if s["name"] == BURST]}, f)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.layer_metrics._ssm", cap_dir,
         "--spans", spans_file], cwd=os.path.dirname(os.path.dirname(
             os.path.dirname(os.path.abspath(__file__)))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=300)
    os.unlink(spans_file)
    if p.returncode != 0:
        print(f"[_ssm] exited {p.returncode}: {p.stderr[-2000:]}", flush=True)
        return None
    ctx._ssm = json.loads(p.stdout.strip().splitlines()[-1])
    print(f"[_ssm] {json.dumps(ctx._ssm)}", flush=True)
    return ctx._ssm


if __name__ == "__main__":
    from benchmark.reduce_named import load_capture

    spans = profile = None
    if "--spans" in sys.argv:
        with open(sys.argv[sys.argv.index("--spans") + 1]) as f:
            given = json.load(f)
        spans, profile = given["spans"], given["profile"]
    try:
        print(json.dumps(reduce(load_capture(sys.argv[1]), spans, profile)))
    except (FileNotFoundError, ValueError) as e:
        sys.exit(f"no capture to read: {e}")
