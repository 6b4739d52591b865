"""From a jax.profiler capture of a program that NAMES what it runs (PR 25)
to the numbers the named per-layer metrics read: the device's idle time by
what the host was doing (tick phases), device time by program (module
name) and, inside the decode programs, by named scope.

Like reduce_trace, two stages, so that the arithmetic is checked on a small
recorded capture without a profiler (tests/benchmark/test_reduce_named.py):
``load_capture`` turns the ``*.xplane.pb`` into plain dicts, ``reduce``
turns those into the summary.

    python -m benchmark.reduce_named <capture dir> [--spans FILE] [--dump N]

prints the summary as one JSON line (``--dump N``: the plain capture, at
most N device operations: how the recorded test capture was made). Runs
with JAX_PLATFORMS=cpu: reading a capture needs no chip. The readers
(benchmark/layer_metrics/*) reach it through ``named(ctx)``, which runs this
module once per traced run as a child (benchmark/run.py never imports jax)
and keeps the result on ``ctx``. The capture is found through
``ctx.state_end["profile"]["capture_dir"]`` (the runner's /debug/state): a
program that does not report it (any commit before PR 25) gives None, and
every reader of this module then returns None.

What a capture of the program looks like since PR 25 (TPU v5e, jax 0.9):

- ``/device:TPU:<n>`` / ``XLA Modules``: one event per program execution,
  named ``jit_<kind>(<program id>)`` after ``Engine._program``'s kind:
  ``jit_decode_burst`` and ``jit_spec_tick`` are the decode programs,
  ``jit_prefill_*`` the prefill programs. Each carries a ``run_id``.
- ``XLA Ops``: one event per operation executed, named by its HLO text.
  The scope path (``jit(decode_burst)/while/body/.../layer/mlp/dot_general``,
  from ``jax.named_scope`` in models/llama.py and the engine's step bodies)
  is NOT in the event's stats nor in the text: it is the ``tf_op`` stat of
  the event's *metadata*, which ``jax.profiler.ProfileData`` does not
  surface. ``_device_metadata`` reads it from the file with a protobuf wire
  reader (XSpace.planes=1; XPlane: name=2, lines=3, event_metadata=4,
  stat_metadata=5; XEventMetadata: name=2, stats=5; XStat: metadata_id=1,
  uint64=3, int64=4, str=5). A fusion carries its root instruction's path.
  Operations the compiler inserted (copies, slices, async copies) have no
  path: they are counted as ``unscoped``.
- ``/host:CPU``: threads. Every ``RingTracer.span()`` of the runner is an
  event there while the capture runs (``tick_*`` on the engine loop's
  thread, ``sync_wait`` on the sync worker's), with its scalar args as
  stats (``tick_idle_wait`` has ``queued``). ``clock_anchor`` is the first:
  its stats are the runner's ``time.monotonic_ns()`` / ``time.time_ns()``
  at its own timestamp, which places ring spans (wall clock) on the
  capture's timeline. ``DoEnqueueProgram`` / ``CompleteCallbacks`` carry a
  program execution's ``run_id``.
- The device plane's clock runs ahead of the host plane's by a millisecond
  or two (first capture looked at: a module "started" 1.05 ms before the
  host enqueued it). ``_skew_ns`` bounds the offset from below by enqueue
  <= start and from above by end <= completion, per ``run_id``. The upper
  bound is the tight one (the completion callback follows the end at
  once; a pipelined enqueue precedes its start by a whole program), so the
  shift is the middle of the bounds where they lie within 0.4 ms of each
  other and 0.2 ms under the upper bound otherwise; gaps are attributed
  after the shift.

A decode step: one step of a ``decode_burst`` scan, one round of a
``spec_tick``; each ``decode_burst_device`` ring span says how many its
dispatch ran (``steps``). A module execution is matched to the span that
became ready just after it ended.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile

from benchmark.reduce_trace import (CONTROL_FLOW, DEVICE_PLANE, GAP_FLOOR_NS,
                                    MODULES_LINE, OPS_LINE, _gaps, _union_ns,
                                    find_xplane, op_name)

IDLE_PHASE = "tick_idle_wait"
HOST_WORK = ("tick_admit", "tick_prefetch", "tick_prefill_pack",
             "tick_dispatch_decode", "tick_drain", "tick_housekeeping",
             "sync_wait")
ANCHOR = "clock_anchor"
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
HOST_KEEP = HOST_WORK + (IDLE_PHASE, ANCHOR, ENQUEUE, COMPLETE)
DECODE_MODULES = ("jit_decode_burst", "jit_spec_tick")
PREFILL_MODULES = ("jit_prefill_",)
# the first of these found in an operation's scope path names its scope
# (outermost wins: the layers of the speculative verify pass count as
# spec_verify, not as layer/mlp)
SCOPES = ("spec_draft", "spec_verify", "embed", "layer/attn_proj",
          "layer/attn", "layer/mlp", "final_norm", "lm_head", "sample")
MATCH_TOL_NS = 500_000
SKEW_SLACK_NS = 400_000


# ---------------------------------------------------------------- the file

def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b, i, end):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is its (start, end) in ``b``."""
    while i < end:
        k, i = _varint(b, i)
        f, w = k >> 3, k & 7
        if w == 0:
            v, i = _varint(b, i)
        elif w == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif w == 1:
            v, i = None, i + 8
        elif w == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {w} in an xplane file")
        yield f, w, v


def _map_value(b, span):
    for f, w, v in _fields(b, *span):
        if f == 2 and w == 2:
            return v
    return None


def _device_metadata(path: str) -> dict:
    """{program id: {operation (reduce_trace.op_name): scope path}} from the
    device planes' event metadata (their ``tf_op`` and ``program_id``)."""
    with open(path, "rb") as fh:
        b = fh.read()
    out: dict = {}
    for f, w, plane in _fields(b, 0, len(b)):
        if f != 1 or w != 2:
            continue
        name, stat_names, metas = "", {}, []
        for f2, w2, v2 in _fields(b, *plane):
            if f2 == 2 and w2 == 2:
                name = b[v2[0]:v2[1]].decode("utf-8", "replace")
            elif f2 == 5 and w2 == 2:
                sm = _map_value(b, v2)
                sid, sname = 0, ""
                for f3, w3, v3 in _fields(b, *(sm or (0, 0))):
                    if f3 == 1 and w3 == 0:
                        sid = v3
                    elif f3 == 2 and w3 == 2:
                        sname = b[v3[0]:v3[1]].decode("utf-8", "replace")
                stat_names[sid] = sname
            elif f2 == 4 and w2 == 2:
                metas.append(v2)
        if not name.startswith(DEVICE_PLANE):
            continue
        for entry in metas:
            em = _map_value(b, entry)
            raw, tf_op, program = "", "", 0
            for f3, w3, v3 in _fields(b, *(em or (0, 0))):
                if f3 == 2 and w3 == 2:
                    raw = b[v3[0]:v3[1]].decode("utf-8", "replace")
                elif f3 == 5 and w3 == 2:
                    mid, val = 0, None
                    for f4, w4, v4 in _fields(b, *v3):
                        if f4 == 1 and w4 == 0:
                            mid = v4
                        elif f4 == 5 and w4 == 2:
                            val = b[v4[0]:v4[1]].decode("utf-8", "replace")
                        elif f4 in (3, 4) and w4 == 0:
                            val = v4
                    if stat_names.get(mid) == "tf_op":
                        tf_op = val or ""
                    elif stat_names.get(mid) == "program_id":
                        program = val or 0
            if raw.startswith("%") and tf_op:
                out.setdefault(str(program), {})[op_name(raw)] = tf_op
    return out


def load_capture(path: str, max_ops: int = 0) -> dict:
    """{"device": [{"name", "modules": [[name, start_ns, dur_ns, run_id]],
    "ops": [[operation, start_ns, dur_ns]]}], "host": [[name, start_ns,
    dur_ns, {stat: value}]], "scopes": {program id: {operation: path}}}"""
    from jax.profiler import ProfileData

    xplane = find_xplane(path)
    data = ProfileData.from_file(xplane)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        st = dict(e.stats)
                        mods.append([e.name, int(e.start_ns),
                                     int(e.duration_ns),
                                     int(st.get("run_id", -1))])
                elif line.name == OPS_LINE:
                    for e in line.events:
                        ops.append([op_name(e.name), int(e.start_ns),
                                    int(e.duration_ns)])
            if max_ops and len(ops) > max_ops:
                # a cut for the recorded test capture: whole program
                # executions only, and the operations inside them
                end = ops[max_ops - 1][1] + ops[max_ops - 1][2]
                mods = [m for m in mods if m[1] + m[2] <= end]
                end = max((m[1] + m[2] for m in mods), default=0)
                ops = [o for o in ops if o[1] < end]
            device.append({"name": plane.name, "modules": mods, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_KEEP:
                        st = {k: v for k, v in dict(e.stats).items()
                              if not k.startswith("_")
                              and isinstance(v, (int, float, str))}
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns), st])
    host.sort(key=lambda h: h[1])
    if max_ops:
        ends = [o[1] + o[2] for d in device for o in d["ops"]] or [0]
        host = [h for h in host if h[0] == ANCHOR
                or h[1] < max(ends) + 20_000_000]
    return {"device": device, "host": host,
            "scopes": _device_metadata(xplane)}


# ------------------------------------------------------------ the numbers

def scope_of(path: str) -> str:
    best, at = "unscoped", len(path) + 1
    p = "/" + path + "/"
    for s in SCOPES:
        i = p.find("/" + s + "/")
        if 0 <= i < at:
            best, at = s, i
    return best


def _program_id(module_name: str) -> str:
    m = re.search(r"\((\d+)\)\s*$", module_name)
    return m.group(1) if m else ""


def _kind(module_name: str) -> str:
    return module_name.split("(", 1)[0]


def _skew_ns(modules, host):
    """-> (shift to add to device times, lower bound, upper bound): for
    each program execution the host enqueued it before it started and saw
    it complete after it ended."""
    enq = {h[3].get("run_id"): h[1] for h in host if h[0] == ENQUEUE}
    done = {h[3].get("run_id"): h[1] for h in host if h[0] == COMPLETE}
    lo = hi = None
    for _n, s, d, rid in modules:
        if rid in enq:
            lo = enq[rid] - s if lo is None else max(lo, enq[rid] - s)
        if rid in done:
            v = done[rid] - (s + d)
            hi = v if hi is None else min(hi, v)
    if hi is None:
        return (lo or 0), lo, hi
    tight_lo = max(hi - SKEW_SLACK_NS, lo if lo is not None else hi)
    return (min(tight_lo, hi) + hi) // 2, lo, hi


def _overlap_ns(gap, intervals):
    """Length of ``gap`` covered by the union of ``intervals``."""
    a, b = gap
    return _union_ns([(max(a, s), min(b, e)) for s, e in intervals
                      if s < b and e > a])


def _match_steps(decode_mods, bursts):
    """[(module duration ns, steps)] for the decode-program executions that
    found their ``decode_burst_device`` span: the one that became ready
    soonest after the execution ended and was dispatched before it began.
    ``bursts``: [(t0_ns, t_ready_ns, steps)] on the capture's clock."""
    out, j = [], 0
    bursts = sorted(bursts, key=lambda x: x[1])
    for s, e in sorted(decode_mods):
        while j < len(bursts) and bursts[j][1] < e - MATCH_TOL_NS:
            j += 1
        if j < len(bursts) and bursts[j][0] <= s + MATCH_TOL_NS:
            out.append((e - s, bursts[j][2]))
            j += 1
    return out


def reduce(cap: dict, spans=None, profile=None) -> dict:
    """``spans``: the engine's ``decode_burst_device`` ring spans of the
    window ({"t": wall s, "dur_ms", "args"}); ``profile``: /debug/state's
    ``profile`` (the anchor's clocks). Without them the steps are None."""
    devs = [d for d in cap["device"] if d["ops"]]
    if not devs:
        raise ValueError("no operation ran on the device in the capture")
    host = cap["host"]
    anchor = next((h for h in host if h[0] == ANCHOR), None)
    work = [(h[1], h[1] + h[2]) for h in host if h[0] in HOST_WORK]
    parked = [(h[1], h[1] + h[2]) for h in host if h[0] == IDLE_PHASE
              and int(h[3].get("queued", 0)) == 0]
    phases = {}
    for h in host:
        if h[0] in HOST_WORK or h[0] == IDLE_PHASE:
            phases.setdefault(h[0], []).append((h[1], h[1] + h[2]))

    n = len(devs)
    tot = {k: 0 for k in ("window", "busy", "host_bound", "no_work",
                          "other", "small", "decode", "prefill",
                          "decode_ops")}
    by_phase, by_module, by_scope, scope_ops = {}, {}, {}, {}
    decode_mods, skew = [], (0, None, None)
    for d in devs:
        skew = _skew_ns(d["modules"], host)
        shift = skew[0]
        iv = [(s + shift, s + shift + dur) for _n, s, dur in d["ops"]]
        t0, t1 = min(s for s, _ in iv), max(e for _, e in iv)
        tot["window"] += t1 - t0
        tot["busy"] += _union_ns(iv)
        for g in _gaps(iv, t0, t1):
            if g[1] - g[0] < GAP_FLOOR_NS:
                tot["small"] += g[1] - g[0]
                continue
            hb = _overlap_ns(g, work)
            nw = _overlap_ns(g, work + parked) - hb
            tot["host_bound"] += hb
            tot["no_work"] += nw
            tot["other"] += g[1] - g[0] - hb - nw
            for name, ivs in phases.items():
                ov = _overlap_ns(g, ivs)
                if ov:
                    by_phase[name] = by_phase.get(name, 0) + ov
        mods = sorted((s, s + dur, name) for name, s, dur, _r in d["modules"])
        for s, e, name in mods:
            k = _kind(name)
            m = by_module.setdefault(k, [0, 0])
            m[0] += e - s
            m[1] += 1
            if k.startswith(DECODE_MODULES):
                tot["decode"] += e - s
                # whole executions only: a step count needs the whole one
                if s + shift >= t0 and e + shift <= t1:
                    decode_mods.append((s + shift, e + shift))
            elif k.startswith(PREFILL_MODULES):
                tot["prefill"] += e - s
        # operations inside decode-program executions, by scope
        k = 0
        for name, s, dur in sorted(d["ops"], key=lambda o: o[1]):
            while k < len(mods) and mods[k][1] <= s:
                k += 1
            if k == len(mods) or mods[k][0] > s or \
                    not _kind(mods[k][2]).startswith(DECODE_MODULES) or \
                    name.rsplit("_", 1)[-1] in CONTROL_FLOW:
                continue      # (the opcode itself: custom-call is no call)
            path = cap["scopes"].get(_program_id(mods[k][2]), {}).get(name)
            sc = scope_of(path) if path else "unscoped"
            by_scope[sc] = by_scope.get(sc, 0) + dur
            tot["decode_ops"] += dur
            o = scope_ops.setdefault(sc, {})
            o[name] = o.get(name, 0) + dur

    steps = None
    if spans is not None and profile and anchor is not None:
        base = anchor[1] - int(profile["epoch_ns"])
        bursts = [(int(sp["t"] * 1e9) + base,
                   int((sp["t"] + sp["dur_ms"] / 1e3) * 1e9) + base,
                   int((sp.get("args") or {}).get("steps", 0)))
                  for sp in spans if sp["name"] == "decode_burst_device"]
        steps = _match_steps(decode_mods, [b for b in bursts if b[2] > 0])

    def sec(v):
        return v / n / 1e9

    return {
        "window_s": sec(tot["window"]), "busy_s": sec(tot["busy"]),
        "idle_host_bound_s": sec(tot["host_bound"]),
        "idle_no_work_s": sec(tot["no_work"]),
        "idle_other_s": sec(tot["other"]),
        "idle_small_gaps_s": sec(tot["small"]),
        "idle_by_phase": {k: sec(v) for k, v in sorted(
            by_phase.items(), key=lambda kv: -kv[1])},
        "clock_skew_ns": {"shift": skew[0], "low": skew[1], "high": skew[2]},
        "anchored": anchor is not None,
        "modules": {k: {"s": sec(v[0]), "n": v[1] / n}
                    for k, v in sorted(by_module.items(),
                                       key=lambda kv: -kv[1][0])},
        "decode_module_s": sec(tot["decode"]),
        "prefill_module_s": sec(tot["prefill"]),
        "decode_ops_s": sec(tot["decode_ops"]),
        "decode_scope_s": {k: sec(v) for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        # the longest operations of each scope (what a share is made of)
        "scope_ops": {sc: [[k, sec(v)] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:4]]
            for sc, ops in scope_ops.items()},
        "decode_matched": None if steps is None else {
            "executions": len(steps),
            "module_s": sum(d for d, _ in steps) / 1e9,
            "steps": sum(k for _, k in steps)},
    }


def scope_share_pct(summary: dict, scopes) -> float | None:
    """Device time of the operations under ``scopes`` over the device time
    of the decode programs, %."""
    if not summary or not summary.get("decode_module_s"):
        return None
    if not any(k != "unscoped" for k in summary["decode_scope_s"]):
        return None          # the program names no scope
    return 100.0 * sum(summary["decode_scope_s"].get(s, 0.0)
                       for s in scopes) / summary["decode_module_s"]


# ---------------------------------------------------- the readers' way in

def named(ctx):
    """The summary of this run's capture, computed once (a CPU child) and
    kept on ``ctx``; None where the program reports no capture."""
    if hasattr(ctx, "_named"):
        return ctx._named
    ctx._named = None
    prof = (ctx.state_end or {}).get("profile") or {}
    cap_dir = prof.get("capture_dir")
    try:
        find_xplane(cap_dir or "/nonexistent")
    except FileNotFoundError:
        return None          # the capture failed: nothing to read
    import time

    t0 = time.monotonic()
    fd, spans_file = tempfile.mkstemp(suffix=".json", dir=cap_dir)
    with os.fdopen(fd, "w") as f:
        json.dump({"profile": prof, "spans": [
            s for s in ctx.spans if s["name"] == "decode_burst_device"]}, f)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.reduce_named", cap_dir, "--spans",
         spans_file], cwd=os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=300)
    os.unlink(spans_file)
    if p.returncode != 0:
        raise RuntimeError(f"reduce_named exited {p.returncode}:\n"
                           f"{p.stderr[-3000:]}")
    out = json.loads(p.stdout.strip().splitlines()[-1])
    out["reduce_s"] = time.monotonic() - t0
    ctx._named = out
    # the run's log: what the shares are made of, and what they leave out
    dm = out["decode_module_s"] or 1.0
    print(f"[reduce_named] {out['reduce_s']:.1f}s; device busy "
          f"{out['busy_s']:.3f}s of {out['window_s']:.3f}s; idle: host-bound "
          f"{out['idle_host_bound_s']:.4f}s, no work "
          f"{out['idle_no_work_s']:.4f}s, other {out['idle_other_s']:.4f}s, "
          f"gaps under 20us {out['idle_small_gaps_s']:.4f}s; by phase "
          f"{json.dumps(out['idle_by_phase'])}; clock skew "
          f"{json.dumps(out['clock_skew_ns'])}", flush=True)
    print(f"[reduce_named] modules {json.dumps(out['modules'])}", flush=True)
    print(f"[reduce_named] decode programs {out['decode_module_s']:.4f}s; "
          f"operations in them {out['decode_ops_s']:.4f}s "
          f"({100 * out['decode_ops_s'] / dm:.1f}%); by scope % " +
          json.dumps({k: round(100 * v / dm, 2)
                      for k, v in out["decode_scope_s"].items()}) +
          f"; matched {json.dumps(out['decode_matched'])}", flush=True)
    print("[reduce_named] longest operations by scope, % of the decode "
          "programs: " + json.dumps({
              sc: [[k, round(100 * v / dm, 2)] for k, v in ops]
              for sc, ops in out["scope_ops"].items()}), flush=True)
    return out


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--spans", default="")
    ap.add_argument("--dump", type=int, default=0)
    a = ap.parse_args(argv)
    if a.dump:
        print(json.dumps(load_capture(a.path, a.dump)))
        return 0
    extra = {}
    if a.spans:
        with open(a.spans) as f:
            extra = json.load(f)
    print(json.dumps(reduce(load_capture(a.path), extra.get("spans"),
                            extra.get("profile"))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
