"""Device time of the two decode kernels ``ling_hybrid`` brought, from the
profiler capture: the ``kda_decode`` and ``mla_paged_decode`` calls inside the
decode programs, each placed on the ring's clock and given to the
``decode_burst_device`` span that was running (as ``layer_metrics/_ssm.py``
does for ``mamba2_decode``: executions are first in, first out), with that
span's live slots (``slot_ids``) and the latent rows those slots held when
the burst began (``ctx_rows``, at the least).

    python -m benchmark.layer_metrics._ling <capture dir> [--spans FILE]

prints them as one JSON line; ``summary(ctx)`` runs this module once per
traced run as a child (benchmark/run.py never imports jax) and keeps the
result on ``ctx``. A program without those kernels gives zeros, a span
without those arguments counts nothing, and every reader then returns None.
The slots of a span are those still live when the host read the burst's
tokens, and its rows those they held before the burst's first step: at most
what the device worked on, so a share can only read low.
"""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys
import tempfile

KERNELS = {"kda": "kda_decode", "mla": "mla_paged_decode"}
BURST = "decode_burst_device"


def reduce(cap: dict, spans=None, profile=None) -> dict:
    from benchmark.reduce_named import (ANCHOR, CONTROL_FLOW, DECODE_MODULES,
                                        _kind, _skew_ns)

    tot = {f"{k}_{what}": 0 for k in KERNELS
           for what in ("kernel", "matched", "calls", "matched_calls",
                        "live_slot_calls", "ctx_rows")}
    anchor = next((h for h in cap["host"] if h[0] == ANCHOR), None)
    bursts = []
    if spans and profile and anchor is not None:
        base = anchor[1] - int(profile["epoch_ns"])
        bursts = sorted(
            (int((sp["t"] + sp["dur_ms"] / 1e3) * 1e9) + base,
             int(sp["t"] * 1e9) + base,
             len((sp.get("args") or {}).get("slot_ids") or ()),
             (sp.get("args") or {}).get("ctx_rows") or 0)
            for sp in spans if sp["name"] == BURST)
    ready = [b[0] for b in bursts]
    devs = [d for d in cap["device"] if d["ops"]]
    for d in devs:
        shift = _skew_ns(d["modules"], cap["host"])[0]
        mods = sorted((s, s + dur, name) for name, s, dur, _r in d["modules"])
        k = 0
        for name, s, dur in sorted(d["ops"], key=lambda o: o[1]):
            while k < len(mods) and mods[k][1] <= s:
                k += 1
            if k == len(mods) or mods[k][0] > s or \
                    name.rsplit("_", 1)[-1] in CONTROL_FLOW or \
                    not _kind(mods[k][2]).startswith(DECODE_MODULES):
                continue
            which = next((w for w, n in KERNELS.items() if n in name), None)
            if which is None:
                continue
            tot[which + "_kernel"] += dur
            tot[which + "_calls"] += 1
            j = bisect.bisect_left(ready, s + shift)
            if j < len(bursts) and bursts[j][1] <= s + shift:
                tot[which + "_matched"] += dur
                tot[which + "_matched_calls"] += 1
                tot[which + "_live_slot_calls"] += bursts[j][2]
                tot[which + "_ctx_rows"] += bursts[j][3]
    n = max(1, len(devs))
    return {(k + "_s" if k.endswith(("_kernel", "_matched")) else k):
            (v / n / 1e9 if k.endswith(("_kernel", "_matched")) else v / n)
            for k, v in tot.items()}


def summary(ctx):
    """This run's numbers, computed once and kept on ``ctx``; None where
    the program reports no capture."""
    if hasattr(ctx, "_ling"):
        return ctx._ling
    ctx._ling = None
    prof = (ctx.state_end or {}).get("profile") or {}
    cap_dir = prof.get("capture_dir")
    if not cap_dir or not os.path.isdir(cap_dir):
        return None
    fd, spans_file = tempfile.mkstemp(suffix=".json", dir=cap_dir)
    with os.fdopen(fd, "w") as f:
        json.dump({"profile": prof, "spans": [
            s for s in ctx.spans if s["name"] == BURST]}, f)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.layer_metrics._ling", cap_dir,
         "--spans", spans_file], cwd=os.path.dirname(os.path.dirname(
             os.path.dirname(os.path.abspath(__file__)))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=300)
    os.unlink(spans_file)
    if p.returncode != 0:
        print(f"[_ling] exited {p.returncode}: {p.stderr[-2000:]}", flush=True)
        return None
    ctx._ling = json.loads(p.stdout.strip().splitlines()[-1])
    print(f"[_ling] {json.dumps(ctx._ling)}", flush=True)
    return ctx._ling


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
