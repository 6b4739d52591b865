"""What the routed expert layers cost and touch, for the four readers of the
expert metrics: from the profiler capture the device time of the operations
under the scopes ``layer/mlp/router`` and ``layer/mlp/experts`` inside the
decode programs, and the experts' time in those decode programs that a
``decode_burst_device`` span could be matched to, with that span's
``experts_touched`` (the burst's distinct experts a layer a step, summed over
layers and steps: the engine folds it from the burst's own result); and the
same spans' sums over the whole window.

    python -m benchmark.layer_metrics._moe <capture dir> [--spans FILE]

prints the capture's part as one JSON line. ``benchmark/reduce_named.py``
sorts decode time by a fixed list of scopes in which both of these count
under ``layer/mlp``, so the readers reduce the capture a second time, with
that module's loader, as ``layer_metrics/_ssm.py`` does: ``summary(ctx)`` runs
this module once per traced run as a child (benchmark/run.py never imports
jax) and keeps the result on ``ctx``. A program without those scopes or that
counter gives zeros or nothing, and every reader then returns None.

Matching. Each run of a decode program is placed on the ring's clock (the
capture's ``clock_anchor`` and /debug/state's ``profile.epoch_ns``) and given
to the burst span that was dispatched before it started and became ready
soonest after: executions are first in, first out, so that is the burst the
program ran. A span is given to one run only, and the first and the last
decode run of the capture, which it may hold in part, are left out: the
experts' time is then the whole time of exactly the steps whose touched
experts are counted. ``--spans`` is a JSON file {"profile": ..., "spans":
[...]}; without it no run finds a burst.
"""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys
import tempfile

ROUTER, EXPERTS = "layer/mlp/router", "layer/mlp/experts"
BURST = "decode_burst_device"


def reduce(cap: dict, spans=None, profile=None) -> dict:
    from benchmark.reduce_named import (ANCHOR, CONTROL_FLOW, DECODE_MODULES,
                                        _kind, _program_id, _skew_ns)

    tot = dict.fromkeys(("decode_module", "decode_router", "decode_experts",
                         "matched_experts"), 0)
    matched_runs = matched_steps = touched = 0
    anchor = next((h for h in cap["host"] if h[0] == ANCHOR), None)
    bursts = []
    if spans and profile and anchor is not None:
        base = anchor[1] - int(profile["epoch_ns"])
        bursts = sorted(
            (int((sp["t"] + sp["dur_ms"] / 1e3) * 1e9) + base,
             int(sp["t"] * 1e9) + base,
             (sp.get("args") or {}).get("experts_touched"),
             (sp.get("args") or {}).get("steps", 0))
            for sp in spans if sp["name"] == BURST
            and (sp.get("args") or {}).get("experts_touched") is not None)
    ready = [b[0] for b in bursts]
    devs = [d for d in cap["device"] if d["ops"]]
    for d in devs:
        shift = _skew_ns(d["modules"], cap["host"])[0]
        mods = sorted((s, s + dur, name) for name, s, dur, _r in d["modules"])
        decode = [m for m in mods if _kind(m[2]).startswith(DECODE_MODULES)]
        given, used = {}, set()
        for s, e, name in decode[1:-1]:
            j = bisect.bisect_left(ready, s + shift)
            if j < len(bursts) and bursts[j][1] <= s + shift \
                    and j not in used:
                used.add(j)
                given[s] = bursts[j]
        for s, e, name in decode:
            tot["decode_module"] += e - s
        for b in given.values():
            matched_runs += 1
            touched += b[2]
            matched_steps += b[3]
        k = 0
        for name, s, dur in sorted(d["ops"], key=lambda o: o[1]):
            while k < len(mods) and mods[k][1] <= s:
                k += 1
            if k == len(mods) or mods[k][0] > s or \
                    name.rsplit("_", 1)[-1] in CONTROL_FLOW or \
                    not _kind(mods[k][2]).startswith(DECODE_MODULES):
                continue
            path = "/" + (cap["scopes"].get(_program_id(mods[k][2]), {})
                          .get(name) or "") + "/"
            if "/" + ROUTER + "/" in path:
                tot["decode_router"] += dur
            elif "/" + EXPERTS + "/" in path:
                tot["decode_experts"] += dur
                if mods[k][0] in given:
                    tot["matched_experts"] += dur
    n = max(1, len(devs))
    out = {k + "_s": v / n / 1e9 for k, v in tot.items()}
    out["matched_runs"] = matched_runs / n
    out["matched_steps"] = matched_steps / n
    # the sum over the matched runs of their spans' experts_touched
    out["matched_experts_touched"] = touched / n
    return out


def summary(ctx):
    """This run's capture numbers, computed once and kept on ``ctx``; None
    where the program reports no capture."""
    if hasattr(ctx, "_moe"):
        return ctx._moe
    ctx._moe = None
    prof = (ctx.state_end or {}).get("profile") or {}
    cap_dir = prof.get("capture_dir")
    if not cap_dir or not os.path.isdir(cap_dir):
        return None
    fd, spans_file = tempfile.mkstemp(suffix=".json", dir=cap_dir)
    with os.fdopen(fd, "w") as f:
        json.dump({"profile": prof, "spans": [
            s for s in ctx.spans if s["name"] == BURST]}, f)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.layer_metrics._moe", cap_dir,
         "--spans", spans_file], cwd=os.path.dirname(os.path.dirname(
             os.path.dirname(os.path.abspath(__file__)))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=300)
    os.unlink(spans_file)
    if p.returncode != 0:
        print(f"[_moe] exited {p.returncode}: {p.stderr[-2000:]}", flush=True)
        return None
    ctx._moe = json.loads(p.stdout.strip().splitlines()[-1])
    print(f"[_moe] {json.dumps(ctx._moe)}", flush=True)
    return ctx._moe


def window_bursts(ctx):
    """(decode steps, experts touched) over the window's
    ``decode_burst_device`` spans that report ``experts_touched`` (the
    burst's distinct experts a layer a step, summed over layers and steps),
    or None where none does."""
    args = [s["args"] for s in ctx.spans if s["name"] == BURST
            and (s.get("args") or {}).get("experts_touched") is not None]
    if not args:
        return None
    return (sum(a["steps"] for a in args),
            sum(a["experts_touched"] for a in args))


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
