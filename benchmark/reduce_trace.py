"""From a jax.profiler capture (``*.xplane.pb``) to the numbers the
per-layer metrics read: device busy and idle time, the table of device
operations, the idle gaps by what the host was doing, and the device time
of the decode programs per step.

Two stages, so that the arithmetic is checked on a small recorded trace
without a profiler (tests/benchmark): ``load_planes`` turns the capture
into plain dicts, ``reduce`` turns those into the summary.

    python -m benchmark.reduce_trace <capture dir or .xplane.pb> [--dump N]

prints the summary as one JSON line; ``--dump N`` instead prints the planes
themselves, at most N events a line (how the recorded test trace was made).
Runs with JAX_PLATFORMS=cpu: reading a capture needs no chip.

What the trace of this program looks like (TPU v5e, jax 0.9): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
program execution) and ``XLA Ops`` (one event per operation executed; a
kernel inside the layer scan appears once per layer per step), and a plane
``/host:CPU`` whose lines are threads; ``Engine._annot`` shows there as
events named ``decode_burst``, ``prefill_pack`` and so on. A device
operation's event name is its whole HLO text (``op_name`` cuts it down), and a
``while`` spans the operations of its body, so control flow is left out of
the operation table (it would count its body twice) but not out of busy time,
which is a union of intervals. Every jitted
program of the engine is a lambda, so modules cannot be told apart by name:
a module execution is a *decode program* if paged-decode attention calls ran
inside it, and it ran (calls / layers) steps.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DECODE_KERNEL = "paged_decode"          # substring of the custom call's name
CONTROL_FLOW = ("while", "conditional", "call")   # they span their bodies' ops
GAP_FLOOR_NS = 20_000                   # shorter gaps are launch spacing
HOST_NAMES = ("decode_burst", "prefill_pack", "prefill_pack_head",
              "prefill_pack_fused", "prefill_chunk", "prefill_final",
              "prefill_fused", "kv_offload_gather", "kv_restore_scatter")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def op_name(raw: str) -> str:
    """The profiler names a device operation by its whole HLO text,
    ``%fusion.3 = bf16[...] fusion(%a, %b), kind=...``: operands and all.
    -> ``fusion.3_fusion`` (its own name and its opcode), so that an
    operation is never mistaken for one of its operands."""
    m = re.match(r"%(\S+) = .*? ([\w\-]+)\(", raw)
    return f"{m.group(1)}_{m.group(2)}" if m else raw[:120]


def load_planes(path: str, max_events: int = 0) -> list:
    """[{name, lines: [{name, events: [[name, start_ns, dur_ns], ...]}]}]
    Device planes whole, with operations named by ``op_name``; of the host
    planes only the engine's annotations (a capture's python-tracer events
    are most of its size and nothing reads them)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device:
                ops = line.name == OPS_LINE
                ev = [[op_name(e.name) if ops else e.name, int(e.start_ns),
                       int(e.duration_ns)] for e in line.events]
            else:
                ev = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events if e.name in HOST_NAMES]
            if max_events:
                ev = ev[:max_events]
            if ev:
                lines.append({"name": line.name, "events": ev})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals, t0, t1):
    """Idle intervals of [t0, t1] not covered by ``intervals``."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(a, b) for a, b in out if b > a]


def _host_spans(planes):
    spans = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                spans += [(s, s + d, n) for n, s, d in line["events"]
                          if n in HOST_NAMES]
    return sorted(spans)


def _attribute(gap, host_spans):
    """What the host was doing for most of an idle gap."""
    a, b = gap
    best, best_ns = "no_dispatch_in_flight", 0
    for s, e, n in host_spans:
        if s >= b:
            break
        ov = min(b, e) - max(a, s)
        if ov > best_ns:
            best, best_ns = n, ov
    return best if best_ns * 2 >= (b - a) else "no_dispatch_in_flight"


def reduce(planes: list, n_layers: int) -> dict:
    devs = [p for p in planes if p["name"].startswith(DEVICE_PLANE)]
    if not devs:
        raise ValueError("the capture has no " + DEVICE_PLANE + " plane: "
                         + ", ".join(p["name"] for p in planes))
    host = _host_spans(planes)
    busy, windows, op_time, gap_time = [], [], {}, {}
    decode_ns = decode_calls = kernel_ns = 0
    for p in devs:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        iv = [(s, s + d) for _, s, d in ops]
        t0, t1 = min(s for s, _ in iv), max(e for _, e in iv)
        busy.append(_union_ns(iv))
        windows.append(t1 - t0)
        for n, _, d in ops:
            if not n.endswith(CONTROL_FLOW):
                op_time[n] = op_time.get(n, 0) + d
        for g in _gaps(iv, t0, t1):
            if g[1] - g[0] >= GAP_FLOOR_NS:
                k = _attribute(g, host)
                gap_time[k] = gap_time.get(k, 0) + g[1] - g[0]
        calls = sorted((s, d) for n, s, d in ops if DECODE_KERNEL in n)
        kernel_ns += sum(d for _, d in calls)
        starts = [s for s, _ in calls]
        for _, s, d in lines.get(MODULES_LINE, []):
            k = bisect.bisect_left(starts, s + d) - bisect.bisect_left(starts, s)
            if k:
                decode_ns += d
                decode_calls += k
    if not busy:
        raise ValueError("no operation ran on the device in the capture")
    n = len(busy)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])
    out = {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": sum(windows) / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in top[:10]],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(gap_time.items(), key=lambda kv: -kv[1])[:10]],
        "decode_kernel_s": kernel_ns / n / 1e9,
        "decode_module_s": decode_ns / n / 1e9,
        "decode_steps": decode_calls / max(1, n_layers) / n,
    }
    return out


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--dump", type=int, default=0)
    a = ap.parse_args(argv)
    if a.dump:
        print(json.dumps(load_planes(a.path, a.dump)))
    else:
        print(json.dumps(reduce(load_planes(a.path), a.layers)))


if __name__ == "__main__":
    sys.exit(main())
