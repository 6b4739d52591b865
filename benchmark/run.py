"""One run of one cell of the benchmark.

    python3 -m benchmark.run --workload NAME --seed N --seconds S --trace 0|1

A run is a new process: it makes the configuration's checkpoint from the
seed (a child, host cores only), starts the server as a user does
(``python -m localai_tpu run``; the model manager spawns the runner, which
holds the chip), loads the model through the first request, sends the
traffic mix's warm-up and then its window, and stops the server. Beside the
maker, before the server takes the chip, a second child compares the
program's model path with the float32 reference on the part of the same
checkpoint it holds. The last line of stdout is the result object. With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from spans, counters and a
profiler capture of the window's last two seconds.

This process never imports jax: the chip belongs to the check, then to the
runner. Flags the driver never passes: ``--rehearsal`` (toy width on the
CPU, stamped cpu and so never correct), ``--rates a,b,c`` (the rate sweep:
one boot, one window per rate, no result line), ``--variants`` (controls
for the check), ``--keep DIR`` (copy logs there).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse      # noqa: E402
import asyncio       # noqa: E402
import glob          # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import re            # noqa: E402
import shutil        # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402
import types         # noqa: E402

from benchmark import e2e_metrics, loadgen, server, spec, stats   # noqa: E402

ROOT = spec.ROOT
PROFILE_S = 2.0          # the window's last two seconds (see Side._profile)
CAPTURE_WAIT_S = 150.0
STATE_PERIOD_S = 0.5


def log(msg):
    print(f"[{time.monotonic() - T_PROCESS_START:7.1f}s] {msg}", flush=True)


def child(args, env, timeout, what):
    """Run a child of the benchmark to its end; its last stdout line is JSON."""
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, "-m", *args], cwd=ROOT, env=env,
                       stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                       text=True, timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"{what} exited {p.returncode}:\n{p.stderr[-3000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1]), \
        time.monotonic() - t0


def parse_counters(text: str) -> dict:
    """Prometheus text -> {name without the localai_ prefix: sum over labels}."""
    out = {}
    for m in re.finditer(r"^(\w+?)(?:\{[^}]*\})? ([0-9.eE+-]+)$", text, re.M):
        name = re.sub(r"^localai_", "", m.group(1))
        out[name] = out.get(name, 0.0) + float(m.group(2))
    return out


def window_spans(trace: dict, w0_wall: float, w1_wall: float) -> list:
    """The engine's spans that began inside the window. /debug/trace
    re-bases a backend's spans onto the HTTP process's timeline by a clock
    offset estimated from the LoadModel round trip (half of a load that
    takes minutes: tens of seconds of error), so that shift is undone here:
    both processes are on this machine's clock."""
    clock = ((trace.get("localai") or {}).get("clocks") or {}).get(
        server.MODEL)
    if not clock:
        return []
    out = []
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X" or ev.get("cat") != "engine":
            continue
        t = clock["t0_epoch"] + (ev["ts"] - clock["shift_us"]) / 1e6
        if w0_wall <= t < w1_wall:
            out.append({"name": ev["name"], "t": t,
                        "dur_ms": ev.get("dur", 0.0) / 1e3,
                        "args": ev.get("args") or {}})
    return out


class Side:
    """What a run collects beside the traffic: counters at the window's
    edges always; in a traced run also state samples and one profiler
    capture of the window's last seconds."""

    def __init__(self, srv, traced, tmp, strict=True):
        self.srv, self.traced, self.strict = srv, traced, strict
        self.tmp = tmp                       # where the runner's captures land
        self.counters0 = self.counters1 = {}
        self.state_samples, self.capture_dir = [], ""

    async def _get(self, session, path, as_json=True):
        async with session.get(self.srv.base + path) as r:
            return await (r.json() if as_json else r.text())

    async def _profile(self, session, at):
        """One capture of the window's last PROFILE_S seconds. Stopping the
        profiler serialises every python-tracer event of a busy runner, which
        at 4.5 req/s took longer than the route's RPC deadline (30 s) and
        holds the runner up meanwhile: so the capture is short, ends with the
        window (the hold-up falls into the drain, not into what is measured),
        and where the RPC gives up the capture is still written: wait for it."""
        await asyncio.sleep(max(0.0, at - time.monotonic()))
        doc = await self._get(
            session, f"/debug/profile?seconds={PROFILE_S}&model={server.MODEL}")
        if doc.get("success"):
            self.capture_dir = doc["capture_dir"]
            return
        log(f"the profile RPC gave up ({str(doc)[:200]}); waiting for the "
            "capture the runner is still writing")
        root = self.tmp
        deadline = time.monotonic() + CAPTURE_WAIT_S
        while time.monotonic() < deadline:
            found = glob.glob(os.path.join(root, "localai-prof-*", "**",
                                           "*.xplane.pb"), recursive=True)
            if found:
                self.capture_dir = found[0].split(os.sep + "plugins")[0]
                return
            await asyncio.sleep(1.0)
        if self.strict:     # a starved CPU rehearsal may never finish one
            raise RuntimeError(f"profiler capture failed: {doc}")

    async def _sample(self, session, w0, w1):
        t = w0
        while t < w1:
            await asyncio.sleep(max(0.0, t - time.monotonic()))
            st = (await self._get(session, "/debug/state"))["models"].get(
                server.MODEL)
            t += STATE_PERIOD_S
            if st is None:        # the runner did not answer in time: no sample
                continue
            self.state_samples.append({
                "t": time.monotonic(), "slots_active": st["slots_active"],
                "queued": st["queued"],
                "kv_rows": sum(s["committed"] for s in st["slots"] if s)})

    async def __call__(self, w0, w1):
        import aiohttp

        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=300)) as session:
            await asyncio.sleep(max(0.0, w0 - time.monotonic()))
            self.counters0 = parse_counters(
                await self._get(session, "/metrics", as_json=False))
            extra = []
            if self.traced:
                late = max(w0, w1 - PROFILE_S - 0.3)
                extra = [asyncio.ensure_future(self._profile(session, late)),
                         asyncio.ensure_future(self._sample(session, w0, w1))]
            await asyncio.sleep(max(0.0, w1 - time.monotonic()))
            self.counters1 = parse_counters(
                await self._get(session, "/metrics", as_json=False))
            for f in extra:
                await f


def make_schedule(cell, traffic, seconds, seed):
    gen = spec.generator(traffic["generator"])
    return gen(traffic, seconds, seed, cell.config["vocab_size"],
               int(cell.config["serving"]["context_size"]))


def sweep(srv, cell, rates, seconds, seed):
    """The rate sweep: one window per rate against one boot."""
    for i, rate in enumerate(rates):
        traffic = dict(cell.traffic, rate_per_s=rate)
        sched = make_schedule(cell, traffic, seconds, seed + i)
        run = asyncio.run(loadgen.drive(srv.base, sched, seconds))
        win = [r for r in run.records if r.in_window]
        ttft = e2e_metrics.ttft_sample(run)
        half = [[(r.first - r.due) * 1e3 for r in win
                 if r.ok and (r.due < run.w0 + seconds / 2) == first]
                for first in (True, False)]
        st = srv.model_state()
        print(json.dumps({
            "rate": rate, "sent": len(win),
            "failed": e2e_metrics.attempted_failed(run)[1],
            "ttft_p50": stats.percentile(ttft, 50),
            "ttft_p85": stats.percentile(ttft, 85),
            "ttft_p95": stats.percentile(ttft, 95),
            "ttft_p50_halves": [stats.percentile(h, 50) for h in half],
            "tpot_p85": e2e_metrics.compute("tpot_p85_ms", run),
            "out_tok_s": e2e_metrics.out_tok_s(run),
            "unfinished_at_close": sum(1 for r in win if r.done > run.w1),
            "drain_s": max(r.done for r in win) - run.w1,
            "queued_after": st["queued"]}), flush=True)


def decide_correct(check, limits, facts):
    """Every number compared, beside its limit; -> (correct, lines)."""
    lines, ok = [], True
    for key, limit in limits.items():
        val = check["sound"].get(key)
        good = val is not None and val <= limit
        lines.append(f"check {key}: {val} (limit {limit}) "
                     f"{'ok' if good else 'NOT CORRECT'}")
        ok &= good
    for name, (val, want) in facts.items():
        good = val == want
        lines.append(f"check {name}: {val} (must be {want}) "
                     f"{'ok' if good else 'NOT CORRECT'}")
        ok &= good
    return ok, lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--rates", default="")
    ap.add_argument("--variants", default="sound")
    ap.add_argument("--keep", default="")
    a = ap.parse_args(argv)

    if not os.path.isfile(os.path.join(ROOT, "localai_tpu", "__main__.py")):
        sys.exit("this checkout holds the benchmark but not the program "
                 "(localai_tpu/): nothing to measure")
    cell = spec.resolve(a.workload)
    readers = {m["name"]: spec.layer_reader(m["name"])
               for m in cell.per_layer}          # unknown names fail here
    want_platform = "tpu"
    env = dict(os.environ)
    env.pop("LOCALAI_ALLOW_RANDOM_WEIGHTS", None)
    config_file = cell.config_file
    if a.rehearsal:
        want_platform = "cpu"
        env.update(JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="1")
        config_file = os.path.join(ROOT, "benchmark", "rehearsal",
                                   "toy-width.json")
        with open(config_file) as f:
            cell.config = json.load(f)

    tmp = tempfile.mkdtemp(prefix="localai_bench_")
    env["TMPDIR"] = tmp                  # the runner's profiler captures land here
    models = os.path.join(tmp, "models")
    ckpt = os.path.join(models, server.MODEL)
    os.makedirs(models)
    srv = check_proc = None
    timings, result = {}, None
    try:
        # the reference check holds the chip while the maker holds the host
        # cores; the server starts when both are done
        t_check = time.monotonic()
        check_proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.reference.check", "--config",
             config_file, "--traffic", cell.traffic_file, "--seed",
             str(a.seed), "--variants", a.variants, "--scratch", tmp], cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        made, timings["ckpt_make_s"] = child(
            ["benchmark.make_checkpoint", "--config", config_file, "--seed",
             str(a.seed), "--out", ckpt], env, 600, "checkpoint maker")
        log(f"checkpoint: {made}")
        out, err = check_proc.communicate(timeout=900)
        if check_proc.returncode != 0:
            raise RuntimeError(f"reference check exited "
                               f"{check_proc.returncode}:\n{err[-3000:]}")
        check = json.loads(out.strip().splitlines()[-1])
        timings["check_s"] = time.monotonic() - t_check
        log(f"reference check took {timings['check_s']:.1f}s: "
            f"{json.dumps(check)}")
        server.write_model_yaml(models, server.MODEL, cell.config["serving"])
        srv = server.Server(ROOT, models, env, os.path.join(tmp, "server.log"))
        srv.wait_ready(time.monotonic() + 120)
        log("server is up; the first request loads the model")
        timings["load_model_s"] = asyncio.run(
            loadgen.first_request(srv.base))
        st = srv.model_state()
        device = {"platform": st["platform"], "kind": st["device_kind"],
                  "count": st["device_count"]}
        log(f"model loaded in {timings['load_model_s']:.1f}s on {device}; "
            f"compiles {st['compiles']}")
        if device["platform"] != want_platform or \
                device["count"] != cell.chips:
            raise RuntimeError(f"the cell asks for {cell.chips} "
                               f"{want_platform} chip(s); the runner holds "
                               f"{device}")
        if a.rates:
            sweep(srv, cell, [float(r) for r in a.rates.split(",")],
                  a.seconds, a.seed)
            return 0

        sched = make_schedule(cell, cell.traffic, a.seconds, a.seed)
        side = Side(srv, bool(a.trace), tmp, strict=not a.rehearsal)
        run = asyncio.run(loadgen.drive(srv.base, sched, a.seconds, side))
        setup_s = run.w0 - T_PROCESS_START
        log(f"window done: set-up {setup_s:.1f}s, "
            f"{len(run.records)} requests sent")
        for kind, sample in (("ttft", e2e_metrics.ttft_sample(run)),
                             ("tpot", e2e_metrics.tpot_sample(run))):
            log(f"{kind}_ms over {len(sample)} requests: mean "
                f"{stats.mean(sample)}, " + ", ".join(
                    f"p{q} {stats.percentile(sample, q)}"
                    for q in (25, 50, 70, 85, 95)))
        state_end = srv.model_state()
        spans = []
        if a.trace:
            spans = window_spans(srv.get("/debug/trace", timeout=120),
                                 run.w0_wall, run.w0_wall + a.seconds)
            log(f"{len(spans)} engine spans began inside the window")
        peak = max((d.get("peak_bytes_in_use", 0)
                    for d in state_end["device_mem"]), default=0)
        srv.stop()
        srv = None
        log("server stopped")

        trace = None
        if a.trace:
            cpu_env = dict(env, JAX_PLATFORMS="cpu")
            if a.keep:      # the planes themselves, to look at by hand
                p = subprocess.run(
                    [sys.executable, "-m", "benchmark.reduce_trace",
                     side.capture_dir, "--dump", "3000"], cwd=ROOT,
                    env=cpu_env, stdout=subprocess.PIPE, text=True)
                os.makedirs(a.keep, exist_ok=True)
                with open(os.path.join(a.keep, "planes.json"), "w") as f:
                    f.write(p.stdout)
            try:
                trace, _ = child(
                    ["benchmark.reduce_trace", side.capture_dir, "--layers",
                     str(cell.config["num_hidden_layers"])], cpu_env, 300,
                    "trace reduction")
            except (RuntimeError, OSError) as e:
                if not a.rehearsal:      # a CPU capture has no device plane
                    raise
                log(f"rehearsal: {str(e).splitlines()[-1]}")
                trace = {"busy_s": 0.0, "window_s": PROFILE_S,
                         "device_ops": [], "idle_gaps": [],
                         "decode_steps": 0, "decode_module_s": 0.0}
            log(f"trace: busy {trace['busy_s']:.3f}s of "
                f"{trace['window_s']:.3f}s; decode steps "
                f"{trace['decode_steps']:.1f} in {trace['decode_module_s']:.3f}s")

        attempted, failed = e2e_metrics.attempted_failed(run)
        win = [r for r in run.records if r.in_window]
        facts = {
            "platform": (device["platform"], "tpu"),
            "check_platform": (check["device"]["platform"], "tpu"),
            "failed_requests": (failed, 0),
            "prompt_length_mismatches": (sum(
                1 for r in win if r.prompt_tokens >= 0
                and r.prompt_tokens != r.req.prompt_tokens), 0),
            "compiles_after_warmup": (
                state_end["compiles"]["compiles_after_warmup"], 0),
        }
        correct, lines = decide_correct(
            check, cell.config["check"].get("limits") or {}, facts)
        if not cell.config["check"].get("limits"):
            correct = False
            lines.append("check: the configuration states no limits yet")
        for ln in lines:
            log(ln)
        for r in [r for r in win if not r.ok][:5]:
            log(f"failed request: {r.error or 'wrong token counts'} "
                f"(prompt {r.prompt_tokens}/{r.req.prompt_tokens}, "
                f"out {r.completion_tokens}/{r.req.max_tokens})")

        units = {m["name"]: m["unit"]
                 for m in cell.end_to_end + cell.per_layer}
        values = {}
        if a.trace:
            ctx = types.SimpleNamespace(
                run=run, schedule=sched, spans=spans, cell=cell,
                state_samples=side.state_samples, counters0=side.counters0,
                counters1=side.counters1, state_end=state_end,
                timings=timings, trace=trace, device=device)
            for name, read in readers.items():
                values[name] = stats.finite(read(ctx))
        else:
            for m in cell.end_to_end:
                values[m["name"]] = setup_s if m["name"] == "setup_s" else \
                    stats.finite(e2e_metrics.compute(m["name"], run))
        log(f"timings: {timings}")
        result = {
            "correct": bool(correct), "attempted": attempted,
            "failed": failed,
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items() if v is not None},
            "device": {**device, "memory_peak_bytes": peak},
        }
        if a.trace:
            result["device"].update(busy_s=trace["busy_s"],
                                    window_s=trace["window_s"])
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
    except BaseException:
        if srv is not None:
            sys.stderr.write("---- server log (tail) ----\n" + srv.log_tail())
        raise
    finally:
        if srv is not None:
            srv.stop()
        if check_proc is not None and check_proc.poll() is None:
            check_proc.kill()
            check_proc.wait()
        if a.keep:
            os.makedirs(a.keep, exist_ok=True)
            if os.path.exists(os.path.join(tmp, "server.log")):
                shutil.copy(os.path.join(tmp, "server.log"), a.keep)
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
