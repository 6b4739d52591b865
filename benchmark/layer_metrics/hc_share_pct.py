"""Device time of the operations under the scope ``layer/hc`` (the
hyper-connections of ``xing4``: a sublayer's three mixes with their Sinkhorn
rounds, the weighted sums in and out of the streams, the read-out before the
head) over the device time of the decode programs, from the profiler
capture. benchmark/reduce_named.py counts the same operations as
``unscoped``, so this reader reduces the capture once more with that
module's loader, as ``layer_metrics/_linear_attn.py`` does.

    python -m benchmark.layer_metrics.hc_share_pct <capture dir>

prints ``{"decode_module_s", "decode_hc_s"}`` as one JSON line; ``read(ctx)``
runs this module once per traced run as a child (benchmark/run.py never
imports jax). A program that names no such scope, as every other family's
and this PR's parent, gives nothing to read, and the metric is left out.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

SCOPE = "layer/hc"


def scope_time(cap: dict, scope: str, decode: bool = True):
    """(device seconds of the decode programs' executions - of the prefill
    programs' with ``decode`` false -, device seconds of their operations
    under ``scope``), a device."""
    from benchmark.reduce_named import (CONTROL_FLOW, DECODE_MODULES,
                                        PREFILL_MODULES, _kind, _program_id)

    kinds = DECODE_MODULES if decode else PREFILL_MODULES
    module = under = 0
    devs = [d for d in cap["device"] if d["ops"]]
    for d in devs:
        mods = sorted((s, s + dur, name) for name, s, dur, _r in d["modules"])
        module += sum(e - s for s, e, name in mods
                      if _kind(name).startswith(kinds))
        k = 0
        for name, s, dur in sorted(d["ops"], key=lambda o: o[1]):
            while k < len(mods) and mods[k][1] <= s:
                k += 1
            if k == len(mods) or mods[k][0] > s or \
                    name.rsplit("_", 1)[-1] in CONTROL_FLOW or \
                    not _kind(mods[k][2]).startswith(kinds):
                continue
            path = "/" + (cap["scopes"].get(_program_id(mods[k][2]), {})
                          .get(name) or "") + "/"
            if "/" + scope + "/" in path:
                under += dur
    n = max(1, len(devs))
    return module / n / 1e9, under / n / 1e9


def reduce(cap: dict) -> dict:
    module, hc = scope_time(cap, SCOPE)
    return {"decode_module_s": module, "decode_hc_s": hc}


def read_share(ctx, module: str, whole: str, part: str):
    """``module`` (one of this directory's) run as a child on the run's
    capture -> 100 x its ``part`` over its ``whole``, None where either is
    0 or there is no capture."""
    cap_dir = ((ctx.state_end or {}).get("profile") or {}).get("capture_dir")
    if not cap_dir or not os.path.isdir(cap_dir):
        return None
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.layer_metrics." + module, cap_dir],
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=300)
    if p.returncode != 0:
        print(f"[{module}] exited {p.returncode}: {p.stderr[-2000:]}",
              flush=True)
        return None
    t = json.loads(p.stdout.strip().splitlines()[-1])
    print(f"[{module}] {json.dumps(t)}", flush=True)
    if not t[whole] or not t[part]:
        return None
    return 100.0 * t[part] / t[whole]


def read(ctx):
    return read_share(ctx, "hc_share_pct", "decode_module_s", "decode_hc_s")


def main(reduce_fn, argv):
    from benchmark.reduce_named import load_capture

    try:
        print(json.dumps(reduce_fn(load_capture(argv[1]))))
    except (FileNotFoundError, ValueError) as e:
        sys.exit(f"no capture to read: {e}")


if __name__ == "__main__":
    main(reduce, sys.argv)
