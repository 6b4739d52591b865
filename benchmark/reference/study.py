"""Read the two numbers a limit is set from, in one process on the chip:
the largest error the sound program gives over a dozen seeds, and the
smallest each control gives over a few.

    python -m benchmark.reference.study --config FILE --traffic FILE
        [--seeds 12] [--control-seeds 3] [--first-seed N]

Each seed gets the part of its own checkpoint that the check holds (the
maker is a function of seed and tensor name), removed before the next. Prints one
JSON line per seed and a summary line; PERF.md records the result.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile

from benchmark.reference import check


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147483000)
    a = ap.parse_args(argv)
    with open(a.config) as f:
        config = json.load(f)
    with open(a.traffic) as f:
        lengths = json.load(f)["check_lengths"]

    from localai_tpu.backend.runner import require_accelerator
    from localai_tpu.utils.jaxtools import enable_compilation_cache

    enable_compilation_cache()
    require_accelerator()
    controls = list(config["check"]["controls"])
    sound, ctl = {}, {c: {} for c in controls}
    for i in range(a.seeds):
        seed = a.first_seed + 7919 * i
        tmp = tempfile.mkdtemp(prefix="localai_study_")
        try:
            variants = ["sound"] + (controls if i < a.control_seeds else [])
            out = check.check(config, seed, lengths, variants, tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        print(json.dumps({"seed": seed, **{v: out[v] for v in variants}}),
              flush=True)
        for k, v in out["sound"].items():
            if k != "seconds":
                sound.setdefault(k, []).append(v)
        for c in variants[1:]:
            for k, v in out[c].items():
                if k not in ("seconds", "crashed"):
                    ctl[c].setdefault(k, []).append(v)
    print(json.dumps({
        "device": check.device_info(), "seeds": a.seeds,
        "sound_max": {k: max(v) for k, v in sound.items()},
        "sound_min": {k: min(v) for k, v in sound.items()},
        "control_min": {c: {k: min(v) for k, v in d.items()}
                        for c, d in ctl.items()}}))


if __name__ == "__main__":
    sys.exit(main())
