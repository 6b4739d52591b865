"""Benchmark: serving throughput of the TPU engine on the real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Default mode measures the REAL serving path — the continuous-batching
Engine (chunked prefill, burst decode, full sampling suite, streaming
token queues). BASELINE.json's metric is "tokens/sec/chip + p50 TTFT on
/v1/chat/completions"; this is that path minus HTTP framing (the HTTP
layer is exercised end-to-end by tests/test_e2e_http.py). ``--kernel``
runs the bare jitted decode-burst loop instead (model + sampler only).

Baseline: the driver north-star is >2000 tok/s aggregate for Llama-3.1-8B
on a v5e-8 (BASELINE.json). Until multi-chip hardware is available this
bench runs a TinyLlama-1.1B-shaped model (the largest llama-family config
that fits one v5e chip in bf16 with a serving-sized KV cache) and reports
aggregate decode tokens/sec/chip; vs_baseline is value / 2000.

Weights are random-init (no network egress in this environment); the
compute path is identical to serving a real checkpoint.
"""

import json
import os
import sys
import time

import numpy as np

# Global wall-clock deadline (monotonic), set by main() when the budget
# watchdog arms — measurement loops shrink adaptively as it nears so the
# bench degrades to fewer passes instead of wedging.
_GLOBAL_DEADLINE = float("inf")


class _ByteTokenizer:
    """Minimal byte-level tokenizer (ids 0-255; 256=EOS) for the bench."""
    vocab_size = 257
    eos_token_id = 256

    def encode(self, text):
        return list(text.encode("utf-8", errors="replace"))

    def decode(self, ids, **kw):
        return bytes(i for i in ids if 0 <= i < 256).decode("utf-8", errors="replace")

    def convert_ids_to_tokens(self, ids):
        return [chr(i) if i < 256 else "</s>" for i in ids]

    def get_vocab_size(self):
        return self.vocab_size


PRESETS = {
    # TinyLlama-1.1B shape
    "1b": dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
               num_layers=22, num_heads=32, num_kv_heads=4, head_dim=64),
    # Llama-3.1-8B shape (the BASELINE.json metric model; int8 weights
    # ~8.5 GB fit a single v5e chip)
    "8b": dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
               num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128),
    # small smoke config (CPU-safe)
    "smoke": dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                  num_layers=2, num_heads=8, num_kv_heads=8, head_dim=16),
}

# serving shape per preset: (slots, context, quantization, kv dtype).
# 8b runs int8 weights AND int8 KV: r4 pinned decode at this rig's HBM
# roofline at 16 slots with the KV the capacity limiter — int8 KV halves
# it, so 32 slots amortize the same weight read over 2x the tokens.
HTTP_PRESETS = {
    "1b": dict(slots=32, ctx=1024, quant="", kv=""),
    # burst 8 (not the engine-default 16): r5 sweep at 32 slots measured
    # 505 vs 463 tok/s AND p50 TTFT 1157 vs 1957 ms — smaller bursts
    # release/admit slots sooner, which outweighs dispatch overhead here
    "8b": dict(slots=32, ctx=1024, quant="int8", kv="int8", burst=8),
    "smoke": dict(slots=2, ctx=128, quant="", kv=""),  # CPU-safe harness check
}


def _write_bench_model(models_dir: str, preset: str, slots: int, ctx: int,
                       quant: str, kv: str = "", burst: int = 0) -> None:
    """config.json-only checkpoint (random weights via the gated loader
    fallback) + a size-matched word-level tokenizer + model YAML."""
    import json as _json

    shape = PRESETS[preset]
    ckpt = os.path.join(models_dir, f"bench-{preset}")
    os.makedirs(ckpt, exist_ok=True)
    with open(os.path.join(ckpt, "config.json"), "w") as f:
        _json.dump({
            "architectures": ["LlamaForCausalLM"],
            "vocab_size": shape["vocab_size"],
            "hidden_size": shape["hidden_size"],
            "intermediate_size": shape["intermediate_size"],
            "num_hidden_layers": shape["num_layers"],
            "num_attention_heads": shape["num_heads"],
            "num_key_value_heads": shape["num_kv_heads"],
            "head_dim": shape["head_dim"],
            "max_position_embeddings": 2048,
            "rms_norm_eps": 1e-5, "rope_theta": 500000.0,
            "bos_token_id": 1, "eos_token_id": 2,
            "tie_word_embeddings": False,
        }, f)
    from tokenizers import Tokenizer, models as tokmodels
    from tokenizers.pre_tokenizers import WhitespaceSplit

    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    for i in range(3, shape["vocab_size"]):
        vocab[f"t{i}"] = i
    tok = Tokenizer(tokmodels.WordLevel(vocab=vocab, unk_token="<unk>"))
    tok.pre_tokenizer = WhitespaceSplit()
    tok.save(os.path.join(ckpt, "tokenizer.json"))
    with open(os.path.join(ckpt, "tokenizer_config.json"), "w") as f:
        _json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                    "bos_token": "<s>", "eos_token": "</s>",
                    "model_max_length": 2048}, f)
    with open(os.path.join(models_dir, f"bench-{preset}.yaml"), "w") as f:
        f.write(f"""\
name: bench-{preset}
backend: tpu-llm
parameters:
  model: bench-{preset}
  temperature: 0.8
  top_k: 40
  top_p: 0.95
context_size: {ctx}
num_slots: {slots}
dtype: bfloat16
quantization: "{quant}"
kv_cache_dtype: "{kv or 'bfloat16'}"
{f"decode_burst: {burst}" if burst else "# decode_burst: engine default"}
prefill_buckets: [128, 512]
template:
  completion: "{{{{ Input }}}}"
  chat_message: "{{{{ Content }}}}"
  chat: "{{{{ Input }}}}"
""")


def bench_http(preset: str, prompt_len: int, max_new: int,
               target_tokens: int) -> dict:
    """THE BASELINE.json metric: tokens/sec/chip + TTFT measured on
    /v1/chat/completions over real HTTP with SSE streaming — full stack
    (aiohttp app -> capabilities -> gRPC -> subprocess engine on the TPU),
    closed-loop with de-phased concurrent streams.

    The parent process stays on the CPU platform; the spawned backend owns
    the chip (reference measures at the endpoint too:
    core/services/metrics.go:36-44)."""
    import asyncio
    import tempfile
    import threading

    import httpx

    hp = HTTP_PRESETS[preset]
    S = int(os.environ.get("LOCALAI_BENCH_SLOTS", hp["slots"]))
    kv = os.environ.get("LOCALAI_BENCH_KV", hp.get("kv", ""))
    models = tempfile.mkdtemp(prefix=f"bench-{preset}-")
    burst = int(os.environ.get("LOCALAI_BENCH_BURST")
                or hp.get("burst", 0) or 0)
    _write_bench_model(models, preset, S, hp["ctx"], hp["quant"], kv, burst)

    os.environ["LOCALAI_ALLOW_RANDOM_WEIGHTS"] = "1"

    from localai_tpu.api.app import build_app, run_app
    from localai_tpu.capabilities import Capabilities
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import scan_models_dir
    from localai_tpu.modelmgr.loader import ModelLoader
    from localai_tpu.modelmgr.process import free_port

    port = free_port()
    app_config = AppConfig(models_path=models, address=f"127.0.0.1:{port}")
    # model load = spawn + weight gen + precompile: can take many minutes
    # for fresh 8B int8 executables (persistent cache makes reruns fast) —
    # but never longer than the bench's remaining budget (BENCH_r05 wedge
    # fix: the loader health loop used to out-wait the parent watchdog)
    attempts = 1200
    remaining = _GLOBAL_DEADLINE - time.monotonic()
    if remaining != float("inf"):
        attempts = max(20, min(1200, int(remaining / 0.5) - 20))
    loader = ModelLoader(health_attempts=attempts, health_interval_s=0.5)
    configs = scan_models_dir(models)
    caps = Capabilities(app_config, loader, configs)
    app = build_app(caps, app_config)

    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            await run_app(app, app_config.address)
            started.set()

        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(30)
    base = f"http://127.0.0.1:{port}"
    model = f"bench-{preset}"
    rng = np.random.default_rng(0)
    V = PRESETS[preset]["vocab_size"]

    def prompt_text(n):
        ids = rng.integers(3, V, size=n)
        return " ".join(f"t{i}" for i in ids)

    n_runs = int(os.environ.get("LOCALAI_BENCH_RUNS", "3"))
    # closed-loop concurrency: 1:1 with slots. Oversubscription was
    # tried (r5: 1.25x at 32 slots) and LOWERED throughput 505->459 on
    # this rig — the extra client threads steal the single host core from
    # the engine loop; the knob stays for multi-core hosts
    n_streams = int(os.environ.get("LOCALAI_BENCH_STREAMS", S))

    async def drive():
        """Boot-once, measure n_runs times (median-of-n with min/max —
        one run's number is unattributable above the run-to-run
        noise), then take the unloaded TTFT floor."""
        errors = []  # shared across warmup / passes / unloaded probes

        async def one_stream(client, n_new):
            body = {"model": model, "stream": True, "ignore_eos": True,
                    "max_tokens": n_new,
                    "messages": [{"role": "user",
                                  "content": prompt_text(prompt_len)}]}
            t0 = time.monotonic()
            ttft = None
            usage_ct = 0
            async with client.stream("POST", f"{base}/v1/chat/completions",
                                     json=body) as r:
                if r.status_code != 200:
                    errors.append(await r.aread())
                    return 0, None
                async for line in r.aiter_lines():
                    if not line.startswith("data: "):
                        continue
                    data = line[len("data: "):]
                    if data == "[DONE]":
                        break
                    chunk = json.loads(data)
                    ch = chunk.get("choices") or [{}]
                    delta = ch[0].get("delta") or {}
                    if ttft is None and delta.get("content"):
                        ttft = time.monotonic() - t0
                    if chunk.get("usage"):
                        usage_ct = chunk["usage"].get("completion_tokens",
                                                      usage_ct)
            return usage_ct, ttft

        async def one_pass(client):
            results = {"completed": 0, "ttfts": []}
            stop = asyncio.Event()

            async def consumer(tid):
                first = True
                while not stop.is_set():
                    n_new = (max(8, max_new - (tid * max_new) // n_streams)
                             if first else max_new)
                    first = False
                    ct, ttft = await one_stream(client, n_new)
                    results["completed"] += ct
                    if ttft is not None:
                        results["ttfts"].append(ttft)
                    if results["completed"] >= target_tokens or errors:
                        stop.set()

            t0 = time.monotonic()
            tasks = [asyncio.create_task(consumer(i))
                     for i in range(n_streams)]
            await asyncio.gather(*tasks)
            return results, time.monotonic() - t0

        timeout = httpx.Timeout(connect=60, read=3600, write=60, pool=3600)
        # pool sized to the STREAM count, not the slot count: with
        # LOCALAI_BENCH_STREAMS oversubscription (> S) a cap of S+4 made
        # the extra streams block on the client pool, so the measurement
        # reflected pool starvation rather than engine behavior
        limits = httpx.Limits(max_connections=max(S, n_streams) + 4)
        async with httpx.AsyncClient(timeout=timeout, limits=limits) as client:
            # warmup: trigger model load + jit warm, one full round
            warm = [one_stream(client, max_new) for _ in range(S)]
            await asyncio.gather(*warm)
            passes = []
            for _ in range(n_runs):
                # adaptive n_runs shrink: once warm, stop measuring when
                # the global deadline nears — fewer passes beat a wedge
                if passes and time.monotonic() > _GLOBAL_DEADLINE - 45:
                    break
                passes.append(await one_pass(client))
                if errors:
                    break
            # unloaded TTFT floor: single stream against the idle server
            unloaded = []
            for _ in range(3):
                _, ttft = await one_stream(client, 4)
                if ttft is not None:
                    unloaded.append(ttft)
        return passes, unloaded, errors

    try:
        passes, unloaded, errors = asyncio.run(drive())
    finally:
        loader.stop_all()
        loop.call_soon_threadsafe(loop.stop)
        # hard sweep of THIS bench's children: an orphaned backend that
        # survives stop_all keeps the chip and wedges every later bench
        # phase (observed r5). -P scopes the kill to our own spawns;
        # the settle sleep is paid only when an orphan was actually found
        import subprocess as _sp

        try:
            if _sp.run(["pkill", "-9", "-P", str(os.getpid()), "-f",
                        "localai_tpu.backend.runner"],
                       check=False).returncode == 0:
                time.sleep(3)
        except OSError:
            pass  # no pkill binary — nothing to sweep with
    if errors:
        raise RuntimeError(str(errors[0])[:500])
    rates = [res["completed"] / wall for res, wall in passes]
    ttfts = [t for res, _ in passes for t in res["ttfts"]]
    return {
        "tok_s": float(np.median(rates)),
        "tok_s_min": float(np.min(rates)),
        "tok_s_max": float(np.max(rates)),
        "n_runs": len(rates),
        "p50_ttft_ms": float(np.percentile(ttfts, 50) * 1e3),
        "p95_ttft_ms": float(np.percentile(ttfts, 95) * 1e3),
        "unloaded_ttft_ms": float(np.median(unloaded) * 1e3) if unloaded else 0.0,
        "completion_tokens": int(sum(res["completed"] for res, _ in passes)),
        "wall_s": float(sum(w for _, w in passes)),
    }




def _kv_sweep(engine, out=None) -> dict:
    """End-of-phase KV audit sweep (ISSUE 15): one full auditor pass on
    the quiesced engine (or EnginePool), folded into the phase dict as
    flat kv_audit_violations / kv_leaked_pages totals so ci.sh can gate
    KV_AUDIT_VIOLATIONS=0 and KV_LEAKED_PAGES=0. Accumulates (+=) when
    a phase runs several engines. Sweep failures are reported, not
    raised — a broken auditor must not sink the bench numbers."""
    kv = {"kv_audit_violations": 0, "kv_leaked_pages": 0}
    try:
        snap = engine.kv_audit_sweep()
        kv["kv_audit_violations"] = int(snap.get("violations", 0) or 0)
        kv["kv_leaked_pages"] = int(snap.get("leaked_pages", 0) or 0)
    except Exception as e:
        print(f"kv audit sweep failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    if out is not None:
        for k, v in kv.items():
            out[k] = int(out.get(k, 0) or 0) + v
    return kv


def _cold_bucket_probe(engine, ecfg) -> dict:
    """Force one compile AFTER warmup and verify the sysobs pipeline
    catches it: a packed-prefill program at a pack size precompile()'s
    ladder never contains (budget + 7), invoked with the all-pads
    warmup arguments so it writes nothing. Expected: exactly one
    compiles_after_warmup increment + one compile_storm event in the
    process event ring."""
    from localai_tpu.engine import sampling
    from localai_tpu.services import sysobs
    from localai_tpu.services.eventlog import EVENTS

    out = {"detected": False, "compiles_after_warmup_delta": 0,
           "storm_event": False}
    if not getattr(engine, "_packed", False):
        out["error"] = "packed prefill off"
        return out
    before = engine._cobs.snapshot()
    try:
        S_, C_ = ecfg.num_slots, ecfg.max_context
        bucket = engine._pack_budget + 7
        sent = np.full((S_,), S_, np.int32)
        zs = np.zeros((S_,), np.int32)
        pack_args = (np.zeros((bucket,), np.int32),
                     np.full((bucket,), C_, np.int32),
                     np.full((bucket,), S_, np.int32),
                     sent, zs, zs, zs, np.zeros((S_,), np.bool_))
        spp = sampling.pack_slot_params(engine.slot_params)
        with sysobs.activated(engine._cobs):
            fn = engine._get_packed_fn(bucket, False)
            _, _, engine.ck, engine.cv, engine.rng_keys, _ = fn(
                engine.params, *pack_args, engine.ck, engine.cv,
                engine.ring, engine.ring_pos, engine.bias,
                engine.rng_keys, spp, engine.mu)
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:200]
        return out
    after = engine._cobs.snapshot()
    delta = (after["compiles_after_warmup"]
             - before["compiles_after_warmup"])
    out["compiles_after_warmup_delta"] = delta
    out["storm_event"] = any(
        ev.get("event") == "compile_storm"
        and "prefill_pack" in str(ev.get("program", ""))
        for ev in EVENTS.events())
    out["detected"] = delta >= 1 and out["storm_event"]
    return out


def bench_serving(cfg, S, C, prompt_len, max_new, target_tokens, burst):
    """Closed-loop serving measurement: keep the engine saturated with S
    in-flight requests (fresh one submitted as each completes), run until
    ~target_tokens completion tokens, report aggregate tok/s + TTFT. This
    is the steady-state shape of a loaded OpenAI endpoint — wave-style
    benches understate throughput via end-of-wave burst shrinkage."""
    import threading

    import jax
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.models import llama

    import jax.numpy as jnp

    from localai_tpu.engine.weights import random_params

    params = random_params(
        cfg, quantize=os.environ.get("LOCALAI_BENCH_QUANT", ""))
    cache_dtype = (jnp.int8 if os.environ.get("LOCALAI_BENCH_KV", "") == "int8"
                   else jnp.bfloat16)
    layout = os.environ.get("LOCALAI_BENCH_KV_LAYOUT", "")
    ecfg = eng.EngineConfig(num_slots=S, max_context=C,
                            prefill_buckets=(prompt_len, 512),
                            prefill_chunk=512, cache_dtype=cache_dtype,
                            # burst<=0 = keep the EngineConfig default
                            **({"decode_burst": burst} if burst > 0 else {}),
                            # paged vs contiguous KV comparison knob
                            **({"kv_layout": layout} if layout else {}),
                            # ragged packed prefill on/off + token budget
                            # (LOCALAI_BENCH_PACKED=0 restores per-slot)
                            **({"prefill_packed": False} if os.environ.get(
                                "LOCALAI_BENCH_PACKED", "") == "0" else {}),
                            **({"prefill_token_budget": pb} if (pb := int(
                                os.environ.get("LOCALAI_BENCH_PREFILL_BUDGET",
                                               "0") or 0)) > 0 else {}),
                            # dedicated emission worker on/off (ISSUE 9;
                            # LOCALAI_BENCH_EMITTER=0 restores in-loop)
                            **({"emitter": False} if os.environ.get(
                                "LOCALAI_BENCH_EMITTER", "") == "0" else {}))
    engine = eng.Engine(cfg, params, _ByteTokenizer(), ecfg,
                        eos_token_ids={cfg.vocab_size - 1})
    engine.start(precompile=True)
    rng = np.random.default_rng(0)

    lock = threading.Lock()
    state = {"completed": 0, "ttfts": [], "errors": [], "stop": False,
             "launched": 0, "decomp": []}
    done = threading.Event()
    # see bench_http: 1:1 with slots; oversubscription loses on a 1-core host
    n_streams = int(os.environ.get("LOCALAI_BENCH_STREAMS", S))

    # constrained-decode mode (LOCALAI_BENCH_GRAMMAR=1): every request
    # carries a JSON-ish GBNF grammar — measures the speculative
    # verify+rollback design's cost vs unconstrained serving
    grammar = ""
    if os.environ.get("LOCALAI_BENCH_GRAMMAR", "") == "1":
        # not accepting until 200 digits: EOS stays masked, so requests
        # run to max_new and the measurement is pure constrained decode
        grammar = 'root ::= "[" [0-9]{200,400} "]"'

    def make_req(n_new=None):
        return eng.GenRequest(
            prompt_ids=rng.integers(0, 255, size=prompt_len).tolist(),
            params=sampling.SamplingParamsHost(
                temperature=0.8, top_k=40, top_p=0.95),
            max_new_tokens=n_new or max_new,
            ignore_eos=True,
            grammar=grammar,
        )

    def consume(tid):
        first = True
        while True:
            with lock:
                if state["stop"]:
                    return
                state["launched"] += 1
            # STAGGER each consumer's first request: the closed loop
            # launches all S consumers at t0, which phase-locks completions
            # into waves of S (half the fleet idles while the other half
            # prefilled) — an artifact of the harness, not of serving.
            # Spreading first-request lengths desyncs the fleet so the
            # measurement reflects steady-state load.
            n_new = max(8, max_new - (tid * max_new) // n_streams) \
                if first else None
            first = False
            r = make_req(n_new)
            t_submit = time.monotonic()
            out = engine.submit(r)
            ttft = None
            completion = 0
            decomp = None
            while True:
                ev = out.get()
                if ev is None:
                    break
                if ttft is None:
                    ttft = time.monotonic() - t_submit
                if ev.error:
                    with lock:
                        state["errors"].append(ev.error)
                if ev.finish_reason:
                    completion = ev.completion_tokens
                    if ev.timings:
                        decomp = (ev.timings.get("queue_wait_ms", 0.0),
                                  ev.timings.get("admit_to_first_ms", 0.0),
                                  ev.timings.get("prefill_ms", 0.0))
            with lock:
                state["completed"] += completion
                if ttft is not None:
                    state["ttfts"].append(ttft)
                if decomp is not None:
                    state["decomp"].append(decomp)
                if state["completed"] >= target_tokens or state["errors"]:
                    state["stop"] = True
                    done.set()

    # warmup: short closed-loop passes until every jit variant is hot AND
    # the burst/prefill alternation pattern has stabilized
    for _ in range(3):
        warm = [eng.GenRequest(
            prompt_ids=rng.integers(0, 255, size=prompt_len).tolist(),
            params=sampling.SamplingParamsHost(temperature=0.8, top_k=40),
            max_new_tokens=2 * ecfg.decode_burst, ignore_eos=True)
            for _ in range(S)]
        outs = [engine.submit(r) for r in warm]
        for o in outs:
            while o.get() is not None:
                pass

    # measure steady state only: warmup's in-serving compiles otherwise
    # dominate the finish-detect / host-loop decomposition totals
    engine.tracer.reset()

    t0 = time.monotonic()
    threads = [threading.Thread(target=consume, args=(i,), daemon=True)
               for i in range(n_streams)]
    for t in threads:
        t.start()
    done.wait()
    wall = time.monotonic() - t0
    with lock:
        completed, ttfts, errors = (state["completed"], list(state["ttfts"]),
                                    list(state["errors"]))
        decomp = list(state["decomp"])
    for t in threads:
        t.join(timeout=10)

    # unloaded TTFT: single request against the now-idle engine (VERDICT r2:
    # the closed-loop TTFT folds queue wait in; record the floor too)
    unloaded = []
    for _ in range(4):
        r = make_req()
        t_submit = time.monotonic()
        out = engine.submit(r)
        first = out.get()
        unloaded.append(time.monotonic() - t_submit)
        engine.cancel(r.request_id)
        while first is not None:
            first = out.get()
    final_metrics = engine.metrics()
    kv_layout = final_metrics.get("kv_layout", "")
    kv_sweep = _kv_sweep(engine)
    engine.shutdown()
    # cold-bucket probe (ISSUE 8 acceptance): a novel pack size — one
    # precompile() never visits — must be DETECTED as a compile storm:
    # counted in compiles_after_warmup and emitted as a structured
    # compile_storm event. Driven through the real fn-getter seam with
    # the all-pads warmup idiom (writes no KV rows); runs after
    # shutdown so the donated-buffer reassignment can't race the loop.
    cold_bucket = _cold_bucket_probe(engine, ecfg)
    if errors:
        raise RuntimeError(errors[0])
    p50 = float(np.percentile(ttfts, 50) * 1e3)
    unl = float(np.median(unloaded) * 1e3)
    out = {
        "kv_layout": kv_layout,
        "tok_s": completed / wall,
        "p50_ttft_ms": p50,
        "p95_ttft_ms": float(np.percentile(ttfts, 95) * 1e3),
        "unloaded_ttft_ms": unl,
        # the packed-prefill tracked number: how much slower TTFT gets
        # under full load vs the idle floor (1.0 = prompt ingestion
        # keeps up with admission; the r04 bucketed path sat at ~2.8)
        "ttft_loaded_unloaded_ratio": round(p50 / unl, 3) if unl else 0.0,
        "completion_tokens": completed,
        "wall_s": wall,
    }
    # system observability (ISSUE 8): compile hygiene of the measured
    # run (must be 0 — precompile covers every serving-path variant),
    # pool high-water mark, goodput/MFU (MFU reads 0 on CPU: no peak in
    # sysobs.PEAK_FLOPS, "not measured"), plus the
    # intentionally-cold-bucket detection probe
    so = final_metrics.get("sysobs") or {}
    out["compiles_after_warmup"] = (so.get("compiles")
                                    or {}).get("compiles_after_warmup")
    out["peak_pool_pages"] = (so.get("watermarks")
                              or {}).get("peak_pool_pages_in_use")
    gp = so.get("goodput") or {}
    out["mfu"] = gp.get("mfu")
    out["goodput_tokens"] = gp.get("goodput_tokens_total")
    out["cold_bucket"] = cold_bucket
    out.update(kv_sweep)
    if decomp:
        d = np.asarray(decomp)
        out["ttft_decomp_p50_ms"] = {
            "queue_wait": round(float(np.percentile(d[:, 0], 50)), 1),
            "admit_to_first": round(float(np.percentile(d[:, 1], 50)), 1),
            "prefill_dispatch": round(float(np.percentile(d[:, 2], 50)), 1),
        }
    # MEASURED host-loop vs device-time decomposition from the span
    # tracer (services/tracing.py): where the serving-vs-kernel tok/s
    # gap actually goes — host dispatch/detok/flush walltime, device
    # compute (dispatch -> sync-worker ready), and finish-detection lag
    # (ready -> engine pickup)
    trace = final_metrics.get("trace") or {}
    if trace.get("enabled"):
        out["host_device_decomp_ms"] = trace["decomp_ms"]
        out["span_breakdown_ms"] = {
            k: v["total_ms"] for k, v in trace["by_span_ms"].items()}
    return out


def bench_packed_prefill(cfg, S, C, max_new=24, rounds=4):
    """Packed-prefill acceptance scenario (ISSUE 4): CLOSED-LOOP mixed
    greedy traffic — S streams (one per slot, the bench_http shape, so
    TTFT measures prompt-ingestion latency from each request's own
    submit rather than queue wait for a slot) over ``rounds`` waves of
    short fresh, longer-than-chunk (multi-tick chunked ingestion) and
    shared-prefix prompts (COW share / prefix-cache splice landing
    mid-pack), with prefill_packed on vs off on otherwise identical
    engines. Streams finish together wave-style, so every admission
    wave leaves multiple slots pending prefill — the packing case.
    Reports per-mode loaded p50 TTFT, tok/s, the loaded/unloaded TTFT
    ratio, and byte-compares the greedy outputs (f32 weights: bf16
    rounding ties flip argmax between differently-shaped-but-equal
    programs — see bench_multiturn's parity note)."""
    import threading

    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.weights import random_params

    params = random_params(cfg)
    rng = np.random.default_rng(7)
    chunk = max(16, C // 4)
    shared = rng.integers(0, 255, size=max(16, C // 6)).tolist()

    def make_prompt(i):
        kind = i % 3
        if kind == 0:      # short fresh
            return rng.integers(0, 255, size=C // 8).tolist()
        if kind == 1:      # longer than a chunk -> multi-tick ingestion
            return rng.integers(0, 255, size=chunk + C // 8).tolist()
        # shared prefix -> COW share / prefix-cache splice mid-pack
        return shared + rng.integers(0, 255, size=C // 16).tolist()

    # [stream][round] prompt schedule, identical for both modes
    schedule = [[make_prompt(t * S + s) for t in range(rounds)]
                for s in range(S)]

    out = {}
    outputs = {}
    # "packed" rides the default fuse mode (the early-emit split);
    # "packed_nofuse" pins fuse off so the split's first-token-delay
    # recovery is measurable (the ci.sh fused-vs-unfused TTFT line)
    for mode in ("packed", "packed_nofuse", "sequential"):
        ecfg = eng.EngineConfig(
            num_slots=S, max_context=C, prefill_buckets=(32, 128),
            prefill_chunk=chunk, cache_dtype=jnp.float32,
            # budget = one full admission wave (the packing win; the
            # knob's decode-ITL bound is irrelevant at smoke scale)
            prefill_token_budget=C,
            prefill_packed=(mode != "sequential"),
            **({"prefill_packed_fuse": "0"}
               if mode == "packed_nofuse" else {}))
        engine = eng.Engine(cfg, params, _ByteTokenizer(), ecfg,
                            eos_token_ids={cfg.vocab_size - 1})
        engine.start(precompile=True)

        def make_req(p):
            return eng.GenRequest(
                prompt_ids=list(p), max_new_tokens=max_new, ignore_eos=True,
                params=sampling.SamplingParamsHost(temperature=0.0))

        # warmup round (outside the measurement; slots retain nothing
        # the schedule reuses — fresh random prompts)
        warm = [engine.submit(make_req(
            rng.integers(0, 255, size=C // 8).tolist())) for _ in range(S)]
        for o in warm:
            while o.get() is not None:
                pass

        ttfts = []
        lock = threading.Lock()
        outs = [[] for _ in range(S)]

        def stream(sid):
            for p in schedule[sid]:
                t1 = time.monotonic()
                o = engine.submit(make_req(p))
                ttft = None
                ids = []
                while True:
                    ev = o.get()
                    if ev is None:
                        break
                    if ttft is None:
                        ttft = time.monotonic() - t1
                    if ev.token_ids:
                        ids.extend(ev.token_ids)
                    elif ev.token_id >= 0:
                        ids.append(ev.token_id)
                with lock:
                    if ttft is not None:
                        ttfts.append(ttft)
                outs[sid].append(ids)

        t0 = time.monotonic()
        threads = [threading.Thread(target=stream, args=(s,), daemon=True)
                   for s in range(S)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.monotonic() - t0
        outputs[mode] = outs
        # unloaded floor against the now-idle engine
        unloaded = []
        for _ in range(3):
            t1 = time.monotonic()
            o = engine.submit(make_req(schedule[0][0]))
            first = o.get()
            unloaded.append(time.monotonic() - t1)
            while first is not None:
                first = o.get()
        m = engine.metrics()
        _kv_sweep(engine, out)
        engine.shutdown()
        p50 = float(np.percentile(ttfts, 50) * 1e3) if ttfts else 0.0
        unl = float(np.median(unloaded) * 1e3) if unloaded else 0.0
        out[mode] = {
            "p50_ttft_ms": round(p50, 1),
            "unloaded_ttft_ms": round(unl, 1),
            "ttft_loaded_unloaded_ratio": round(p50 / unl, 3) if unl else 0.0,
            "tok_s": round(sum(len(x) for o_ in outs for x in o_) / wall, 1),
            "packed_prefill": m.get("packed_prefill"),
        }
    out["greedy_match"] = (outputs["packed"] == outputs["sequential"]
                           and outputs["packed"] == outputs["packed_nofuse"])
    seq, pk = out["sequential"]["p50_ttft_ms"], out["packed"]["p50_ttft_ms"]
    out["ttft_speedup"] = round(seq / pk, 3) if pk else 0.0
    out["ttft_loaded_unloaded_ratio"] = \
        out["packed"]["ttft_loaded_unloaded_ratio"]
    # early-emit acceptance: fused loaded TTFT no worse than unfused
    nf = out["packed_nofuse"]["p50_ttft_ms"]
    out["fused_ttft_ms"] = pk
    out["unfused_ttft_ms"] = nf
    out["fused_ttft_ratio"] = round(pk / nf, 3) if nf else 0.0
    return out


def bench_packed_longpack(cfg, S=4, max_new=8):
    """Long-prompt packed-prefill phase (ISSUE 11): every admission wave
    packs S * chunk > 1k prompt tokens, the shape the old whole-pack
    kernel spilled out of VMEM on. Gates: the >1k pack program actually
    compiled (bucket evidence), ZERO shape fallbacks off the kernel
    plan (metrics counter, paged f32 cache), and greedy byte parity vs
    the per-slot path."""
    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.weights import random_params

    chunk, C = 384, 1536
    params = random_params(cfg)
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, 255, size=2 * chunk).tolist()
               for _ in range(S)]

    outs = {}
    stats = {}
    ka = {}
    for mode in ("packed", "sequential"):
        ecfg = eng.EngineConfig(
            num_slots=S, max_context=C, prefill_buckets=(128, chunk),
            prefill_chunk=chunk, cache_dtype=jnp.float32,
            kv_layout="paged", kv_page_size=64,
            prefill_token_budget=S * chunk,
            prefill_packed=(mode == "packed"))
        e = eng.Engine(cfg, params, _ByteTokenizer(), ecfg,
                       eos_token_ids={cfg.vocab_size - 1})
        e.start()  # lazy compiles: the fn-cache keys prove pack sizes
        t0 = time.monotonic()
        streams = [e.submit(eng.GenRequest(
            prompt_ids=list(p), max_new_tokens=max_new, ignore_eos=True,
            params=sampling.SamplingParamsHost(temperature=0.0)))
            for p in prompts]
        res = []
        for o in streams:
            ids = []
            while True:
                ev = o.get()
                if ev is None:
                    break
                ids.extend(ev.token_ids or
                           ([ev.token_id] if ev.token_id >= 0 else []))
            res.append(ids)
        wall = time.monotonic() - t0
        outs[mode] = res
        if mode == "packed":
            m = e.metrics()["packed_prefill"]
            buckets = [k[1] for k in e._final_fns
                       if isinstance(k, tuple)
                       and k[0] in ("packed", "packed_head")]
            stats = {"max_pack_bucket": max(buckets, default=0),
                     "kernel_fallbacks": m["kernel_fallback"],
                     "packed_tokens": m["tokens"],
                     "wall_s": round(wall, 2)}
        _kv_sweep(e, ka)
        e.shutdown()
    stats["greedy_match"] = outs["packed"] == outs["sequential"]
    stats.update(ka)
    return stats


def bench_chaos(cfg, S, C, max_new=16, flood=12):
    """Fault-lifecycle SLO scenario (ISSUE 7), on ONE engine:

    1. saturation shed — queue bound dropped to 1, then ``flood``
       concurrent submits; every refused request must carry a
       structured "shed" event (not a hang, not a raw traceback) and
       carry it within 50 ms of submit;
    2. stall recovery — a one-shot injected sync-worker delay wedges a
       prefill; the watchdog must abort ONLY that request, dump the
       span ring to disk, and the next request must reproduce the
       pre-fault greedy baseline byte-for-byte (f32 weights, same
       parity reasoning as bench_packed_prefill)."""
    import tempfile
    import threading

    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.weights import random_params
    from localai_tpu.services.faults import FAULTS

    params = random_params(cfg)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 255, size=max(8, C // 8)).tolist()
    flood_prompts = [rng.integers(0, 255, size=max(8, C // 8)).tolist()
                     for _ in range(flood)]

    ecfg = eng.EngineConfig(num_slots=S, max_context=C,
                            prefill_buckets=(32, 128),
                            cache_dtype=jnp.float32)
    engine = eng.Engine(cfg, params, _ByteTokenizer(), ecfg,
                        eos_token_ids={cfg.vocab_size - 1})
    engine.start(precompile=True)

    def make_req(p):
        return eng.GenRequest(
            prompt_ids=list(p), max_new_tokens=max_new, ignore_eos=True,
            params=sampling.SamplingParamsHost(temperature=0.0))

    def run_one(p):
        o = engine.submit(make_req(p))
        ids, last = [], None
        while True:
            ev = o.get()
            if ev is None:
                break
            last = ev
            if ev.token_ids:
                ids.extend(ev.token_ids)
            elif ev.token_id >= 0:
                ids.append(ev.token_id)
        return ids, last

    out = {}
    saved_maxq = engine.ecfg.max_queued_requests
    saved_stall = engine.ecfg.dispatch_stall_ms
    try:
        baseline, _ = run_one(prompt)
        out["baseline_tokens"] = len(baseline)

        # ---- saturation shed ----
        engine.ecfg.max_queued_requests = 1
        lock = threading.Lock()
        shed_lat, counts = [], {"shed": 0, "served": 0, "other": 0}

        def flood_one(i):
            t1 = time.monotonic()
            o = engine.submit(make_req(flood_prompts[i]))
            first_dt = None
            ids, last = [], None
            while True:
                ev = o.get()
                if ev is None:
                    break
                if first_dt is None:
                    first_dt = time.monotonic() - t1
                last = ev
                if ev.token_ids:
                    ids.extend(ev.token_ids)
                elif ev.token_id >= 0:
                    ids.append(ev.token_id)
            with lock:
                if last is not None and getattr(
                        last, "error_kind", None) == "shed":
                    counts["shed"] += 1
                    shed_lat.append(first_dt or 0.0)
                elif ids:
                    counts["served"] += 1
                else:
                    counts["other"] += 1

        threads = [threading.Thread(target=flood_one, args=(i,),
                                    daemon=True) for i in range(flood)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        engine.ecfg.max_queued_requests = saved_maxq
        out["shed"] = counts["shed"]
        out["served"] = counts["served"]
        out["unstructured"] = counts["other"]
        out["shed_p95_ms"] = (round(float(
            np.percentile(shed_lat, 95) * 1e3), 2) if shed_lat else None)
        out["shed_under_50ms"] = bool(shed_lat) and max(shed_lat) < 0.05

        # ---- stall abort + ring dump + byte-exact recovery ----
        dump_dir = tempfile.mkdtemp(prefix="localai-chaos-")
        engine.ecfg.dispatch_stall_ms = 300
        engine.ecfg.stall_dump_dir = dump_dir
        FAULTS.arm("sync_delay_ms", "2000", count=1)
        _ids, last = run_one(prompt)
        out["stall_aborted"] = bool(
            last is not None and getattr(last, "error_kind", None) == "stall")
        out["stall_dump"] = len([f for f in os.listdir(dump_dir)
                                 if f.endswith(".trace.json")])
        time.sleep(2.2)  # let the delayed sync worker drain its item
        engine.ecfg.dispatch_stall_ms = saved_stall
        recovered, _ = run_one(prompt)
        out["survivors_identical"] = recovered == baseline
        out["recovered"] = int(out["stall_aborted"] and out["stall_dump"] > 0
                               and out["survivors_identical"])
        m = engine.metrics()
        out["lifecycle"] = m.get("lifecycle")
    finally:
        FAULTS.reset()
        _kv_sweep(engine, out)
        engine.shutdown()
    return out


def bench_priority(cfg, S, C, low_new=64, high_new=8, n_high=4):
    """Preemptive priority scheduler scenario (ISSUE 10), three phases:

    1. preempt ON: a saturating ``low`` background (2*S long greedy
       decodes) holds every slot, then a wave of ``high`` arrivals lands;
       each high's TTFT is measured while the scheduler pauses low slots
       to make room;
    2. preempt OFF: the identical workload on a FIFO engine — the high
       wave must wait for slots to drain, so the p50 TTFT ratio (off/on)
       is the headline number (ISSUE 10 acceptance: >= 2x);
    3. resume byte match: one controlled preempt/resume round with the
       prefix cache off (resume = full re-prefill): the paused request's
       pre-preemption prefix must match its solo greedy baseline and its
       continuation must be bit-for-bit what a FRESH submission of
       (prompt + emitted tokens) computes — the honest resume contract
       (prefill-vs-decode kernel numerics make parity against an
       uninterrupted run unguaranteeable; see engine._start_resume)."""
    import threading

    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.weights import random_params
    from localai_tpu.services.eventlog import EVENTS

    params = random_params(cfg)
    rng = np.random.default_rng(13)
    plen = max(8, C // 8)
    n_low = 2 * S
    low_prompts = [rng.integers(0, 255, size=plen).tolist()
                   for _ in range(n_low)]
    high_prompts = [rng.integers(0, 255, size=plen).tolist()
                    for _ in range(n_high)]

    def make_req(ids, priority, max_new):
        return eng.GenRequest(
            prompt_ids=list(ids), max_new_tokens=max_new, ignore_eos=True,
            priority=priority,
            params=sampling.SamplingParamsHost(temperature=0.0))

    def drain(o, first_ev=None):
        ids, last = [], None
        ev = first_ev
        while True:
            if ev is None:
                ev = o.get()
                if ev is None:
                    break
            last = ev
            if ev.token_ids:
                ids.extend(ev.token_ids)
            elif ev.token_id >= 0:
                ids.append(ev.token_id)
            ev = None
        return ids, last

    def run_one(engine, ids, priority, max_new):
        return drain(engine.submit(make_req(ids, priority, max_new)))

    def wave(engine):
        """Saturate with lows, then fire the high wave; returns the highs'
        TTFTs and the lows' (ids, last-event) pairs."""
        outs_low = [engine.submit(make_req(p, "low", low_new))
                    for p in low_prompts]
        t0 = time.monotonic()
        while engine.num_active < S and time.monotonic() - t0 < 30:
            time.sleep(0.005)
        ttfts, lock = [], threading.Lock()

        def one_high(i):
            t1 = time.monotonic()
            o = engine.submit(make_req(high_prompts[i], "high", high_new))
            first = None
            while True:
                ev = o.get()
                if ev is None:
                    break
                if first is None and (ev.token_ids or ev.token_id >= 0):
                    first = time.monotonic() - t1
            with lock:
                ttfts.append(first if first is not None else float("inf"))

        threads = [threading.Thread(target=one_high, args=(i,), daemon=True)
                   for i in range(n_high)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        lows = [drain(o) for o in outs_low]
        return ttfts, lows

    out = {"n_low": n_low, "n_high": n_high,
           "low_new": low_new, "high_new": high_new}
    base_ecfg = dict(num_slots=S, max_context=C, prefill_buckets=(32, 128),
                     cache_dtype=jnp.float32, max_queued_requests=64)

    # ---- phase 1: preempt ON ----
    engine = eng.Engine(cfg, params, _ByteTokenizer(),
                        eng.EngineConfig(**base_ecfg),
                        eos_token_ids={cfg.vocab_size - 1})
    engine.start(precompile=True)
    try:
        ttft_on, lows_on = wave(engine)
        sched = engine.metrics().get("scheduler") or {}
    finally:
        _kv_sweep(engine, out)
        engine.shutdown()
    out["p50_ttft_on_ms"] = round(float(np.percentile(ttft_on, 50)) * 1e3, 2)
    out["preemptions"] = sched.get("preemptions", 0)
    out["resumes"] = sched.get("resumes", 0)
    out["low_complete"] = all(
        len(ids) == low_new and (last is None or last.error is None)
        for ids, last in lows_on)

    # ---- phase 2: preempt OFF (FIFO) ----
    engine = eng.Engine(cfg, params, _ByteTokenizer(),
                        eng.EngineConfig(preempt=False, **base_ecfg),
                        eos_token_ids={cfg.vocab_size - 1})
    engine.start(precompile=True)
    try:
        ttft_off, _ = wave(engine)
    finally:
        _kv_sweep(engine, out)
        engine.shutdown()
    out["p50_ttft_off_ms"] = round(float(np.percentile(ttft_off, 50)) * 1e3, 2)
    out["ttft_ratio"] = round(
        out["p50_ttft_off_ms"] / max(1e-6, out["p50_ttft_on_ms"]), 2)

    # ---- phase 3: resume ≡ fresh re-admission, bit for bit ----
    ecfg_m = eng.EngineConfig(kv_prefix_cache=False, kv_offload=False,
                              **{**base_ecfg, "num_slots": 1})
    engine = eng.Engine(cfg, params, _ByteTokenizer(), ecfg_m,
                        eos_token_ids={cfg.vocab_size - 1})
    engine.start(precompile=True)
    try:
        mp = low_prompts[0]
        base, _ = run_one(engine, mp, "low", low_new)
        EVENTS.clear()
        req_low = make_req(mp, "low", low_new)
        o_low = engine.submit(req_low)
        first = o_low.get()          # decode is under way
        high_ids, high_last = run_one(engine, high_prompts[0], "high",
                                      high_new)
        low_ids, low_last = drain(o_low, first_ev=first)
        pre = [ev for ev in EVENTS.events() if ev["event"] == "preempt"
               and ev["rid"] == req_low.request_id]
        out["match_preempted"] = bool(pre)
        match = False
        if pre and low_last is not None and low_last.error is None \
                and high_last is not None and high_last.error is None:
            k = int(pre[0]["n_decoded"])
            ref, _ = run_one(engine, list(mp) + low_ids[:k], "low",
                             low_new - k)
            match = (0 < k < low_new and len(low_ids) == low_new
                     and len(high_ids) == high_new
                     and low_ids[:k] == base[:k] and low_ids[k:] == ref)
        out["resume_byte_match"] = match
    finally:
        _kv_sweep(engine, out)
        engine.shutdown()
    return out


def bench_spec(cfg, S, C, n_req=None, max_new=64):
    """Speculative decoding scenario (ISSUE 13): a mixed greedy wave with
    model-free n-gram self-speculation (``draft=ngram``) vs speculation
    off (``draft=0``), byte-identical by construction (greedy speculation
    is lossless) and faster per emitted token when acceptance lands.

    Prompts tile a short repeated pattern so the greedy continuation has
    self-similar structure the prompt-lookup drafter can exploit (small
    random-weight models also fall into greedy cycles, which n-gram
    drafting predicts near-perfectly once entered). Headline numbers:
    accepted-tokens-per-dispatch (emitted spec tokens per verify round —
    1.0 means speculation bought nothing) and the emitted-token ITL on
    vs off. The byte gate doubles as the ``spec=0`` untouched check: the
    off engine runs the plain burst path bit-for-bit.

    A second SAMPLED wave (ISSUE 18: temperature 0.8, fixed seed ladder,
    top-k sharpened so prompt-lookup proposals land inside the filtered
    window) reruns the same prompts through rejection-sampling
    acceptance: headline ``sampled_accept_per_dispatch`` (from the
    per-mode counter split) and a two-sample chi-square p-value of
    spec-on vs spec-off token frequencies — sampled speculation is
    lossless in DISTRIBUTION, not bytes, so the gate is statistical."""
    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling, speculative
    from localai_tpu.engine.weights import random_params

    params = random_params(cfg)
    rng = np.random.default_rng(17)
    n_req = n_req or 2 * S
    plen = max(16, C // 8)
    pat = rng.integers(0, 255, size=8)
    prompts = []
    for i in range(n_req):
        p = np.tile(np.roll(pat, i), plen // 8 + 1)[:plen]
        prompts.append(p.tolist())
    ka = {}

    def run_waves(draft):
        # ONE engine (one precompile of the spec-tick ladder) serves the
        # greedy wave then the sampled wave — the sampled wave riding the
        # already-compiled tick is itself evidence that rejection
        # acceptance shares the combined compiled body (ISSUE 18); the
        # per-mode counter split keeps the headlines separable
        ecfg = eng.EngineConfig(
            num_slots=S, max_context=C, prefill_buckets=(32, 128),
            cache_dtype=jnp.float32, draft=draft)
        engine = eng.Engine(cfg, params, _ByteTokenizer(), ecfg,
                            eos_token_ids={cfg.vocab_size - 1})
        engine.start(precompile=True)

        def wave(sampled):
            def _params(i):
                if sampled:
                    return sampling.SamplingParamsHost(
                        temperature=0.8, seed=1000 + i, top_k=4)
                return sampling.SamplingParamsHost(temperature=0.0)

            outs = [engine.submit(eng.GenRequest(
                prompt_ids=list(p), max_new_tokens=max_new, ignore_eos=True,
                params=_params(i)))
                for i, p in enumerate(prompts)]
            ids, itls = [], []
            for o in outs:
                toks, times = [], []
                while True:
                    ev = o.get()
                    if ev is None:
                        break
                    got = list(ev.token_ids) if ev.token_ids else (
                        [ev.token_id] if ev.token_id >= 0 else [])
                    toks.extend(got)
                    times.extend([time.monotonic()] * len(got))
                ids.append(toks)
                if len(times) > 1:
                    itls.append((times[-1] - times[0]) / (len(times) - 1))
            return ids, itls

        try:
            ids_g, itls_g = wave(sampled=False)
            ids_s, itls_s = wave(sampled=True)
            spec = (engine.metrics().get("spec") or {})
            return ids_g, itls_g, ids_s, itls_s, spec
        finally:
            _kv_sweep(engine, ka)
            engine.shutdown()

    ids_off, itls_off, ids_soff, itls_soff, _ = run_waves("0")
    ids_on, itls_on, ids_son, itls_son, spec = run_waves("ngram")
    bg = (spec.get("by_mode") or {}).get("greedy") or {}
    out = {"n_req": n_req, "max_new": max_new,
           "byte_match": ids_on == ids_off,
           "itl_on_ms": round(float(np.median(itls_on)) * 1e3, 3)
           if itls_on else None,
           "itl_off_ms": round(float(np.median(itls_off)) * 1e3, 3)
           if itls_off else None,
           "accept_per_dispatch": round(
               bg.get("accept_per_dispatch", 0.0), 3),
           "acceptance_rate": round(bg.get("acceptance_rate", 0.0), 3),
           "rounds": bg.get("rounds", 0),
           "dispatches": spec.get("dispatches", 0),
           "mixed_dispatches": spec.get("mixed_dispatches", 0)}
    if out["itl_on_ms"] and out["itl_off_ms"]:
        out["itl_speedup"] = round(out["itl_off_ms"] / out["itl_on_ms"], 2)

    # sampled-wave gates (ISSUE 18): same prompts, temperature 0.8 +
    # seed ladder; both runs are deterministic, so the chi-square
    # p-value is a fixed number — the distribution-preservation gate
    bm = (spec.get("by_mode") or {}).get("sampled") or {}
    V = cfg.vocab_size

    def _counts(ids):
        flat = [t for toks in ids for t in toks]
        return np.bincount(np.asarray(flat, np.int64), minlength=V)[:V]

    _stat, dof, pval = speculative.two_sample_chi2(
        _counts(ids_son), _counts(ids_soff))
    out.update({
        "sampled_accept_per_dispatch": round(
            bm.get("accept_per_dispatch", 0.0), 3),
        "sampled_acceptance_rate": round(
            bm.get("acceptance_rate", 0.0), 3),
        "sampled_rounds": bm.get("rounds", 0),
        "sampled_itl_on_ms": round(float(np.median(itls_son)) * 1e3, 3)
        if itls_son else None,
        "sampled_itl_off_ms": round(float(np.median(itls_soff)) * 1e3, 3)
        if itls_soff else None,
        "sampled_chi2_p": round(pval, 4),
        "sampled_chi2_dof": dof,
        "sampled_dist_ok": bool(pval > 0.01),
    })
    out.update(ka)
    return out


def bench_replicas(cfg, S, C, max_new=48):
    """Engine replica pool scenario (ISSUE 14): ONE pool of two replicas
    sharing a host KV tier and a cross-replica prefix index, three
    phases in sequence:

    1. prefix affinity + cross-replica warm restore: cold prompts land
       on one replica and their retained chains offload to the shared
       tier under pool pressure; resubmitting a device-warm prompt must
       route to the SAME replica via the shared index (affinity hit,
       byte-identical); then the SIBLING — which never saw these
       prompts — alternates fresh cold prefills with restores of the
       chains its sibling computed, pulled from the SHARED store, and
       the warm TTFT must beat the cold full re-prefill (median cold vs
       best warm after a one-off warm-up run; alternation keeps every
       warm sample a true host restore, never a device splice);
    2. live migration: a mid-decode request is migrated to the sibling
       (pause -> offload to the shared tier -> resume-as-readmission);
       the client stream never closes and the continuation must equal a
       fresh pool re-admission of (prompt + tokens emitted before the
       pause) — the MIGRATE_BYTE_MATCH gate;
    3. crash recovery: the victim's home replica dies mid-stream (its
       device KV is lost); the pool harvests the request and a sibling
       adopts it, restoring the warm prefix from the SHARED host tier;
       the stream finishes error-free and byte-matches the same fresh
       re-admission contract — the REPLICA_RECOVERED gate.

    Byte-gate references go through the POOL, not a cold engine, so
    affinity splices the same retained conditioning rows the migrated /
    recovered continuation saw (prefill-vs-decode kernel numerics can
    differ in the last ulps; see bench_priority phase 3 and
    engine._start_resume)."""
    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.pool import EnginePool
    from localai_tpu.engine.weights import random_params
    from localai_tpu.services.eventlog import EVENTS
    from localai_tpu.services.faults import FAULTS

    params = random_params(cfg)
    rng = np.random.default_rng(23)
    C = max(96, C)
    pg = 8
    # 1 slot/replica and a device pool exactly one slot deep: retained
    # chains always evict — and thus offload to the shared host tier —
    # when the next admission needs the pages
    ecfg = eng.EngineConfig(num_slots=1, max_context=C,
                            prefill_buckets=(32, 128), decode_burst=4,
                            kv_page_size=pg, kv_pool_pages=C // pg,
                            cache_dtype=jnp.float32, kv_audit="on")

    def make_req(ids, n):
        return eng.GenRequest(
            prompt_ids=list(ids), max_new_tokens=n, ignore_eos=True,
            params=sampling.SamplingParamsHost(temperature=0.0))

    def drain(o, first_ev=None):
        ids, err = [], None
        ev = first_ev
        while True:
            if ev is None:
                ev = o.get()
                if ev is None:
                    break
            if ev.error is not None:
                err = ev.error
            if ev.token_ids:
                ids.extend(ev.token_ids)
            elif ev.token_id >= 0:
                ids.append(ev.token_id)
            ev = None
        return ids, err

    # phases 2/3 decode max_new tokens, so their prompt leaves headroom
    plen = min(max(48, C // 2 - 8), C - max_new - 8)
    plen -= plen % pg                      # page-aligned: whole-chain reuse
    # phase 1 only decodes 8 tokens, so its prompts run near-context:
    # the skipped prefill has to dominate the per-page restore overhead
    # for the warm-beats-cold compare to measure what it claims
    plen1 = (C - 24) - (C - 24) % pg
    out = {"max_new": max_new, "plen": plen, "plen1": plen1}
    pool = EnginePool.build(cfg, params, _ByteTokenizer(), ecfg,
                            engines=2, eos_token_ids={cfg.vocab_size - 1})
    pool.start(precompile=True)
    try:
        # ---- phase 1: affinity routing + cross-replica warm restore ----
        # three cold prompts, submitted back to back: each admission
        # evicts the previous retained chain (the pool is one slot
        # deep), which IS the device -> host offload into the shared
        # store; the last chain stays device-resident
        def timed_submit(ids, n):
            r = make_req(ids, n)
            t0 = time.monotonic()
            o = pool.submit(r)
            first = o.get()
            ttft = time.monotonic() - t0
            toks, err = drain(o, first_ev=first)
            return r, ttft, toks, err
        colds = [rng.integers(0, 255, size=plen1).tolist()
                 for _ in range(3)]
        cold_ttfts, cold_ids, home = [], [], None
        for p in colds:
            r, ttft, toks, err = timed_submit(p, 8)
            cold_ttfts.append(ttft)
            cold_ids.append(toks)
            home = pool.where(r.request_id)
        cold_ttft = float(np.median(cold_ttfts))
        # wait for the evicted chains to land in the shared host tier
        # and the last chain's release-path insert to hit the index
        store = pool._shared.store
        keys = list(pool._engines[home]._pcache.chain_keys(colds[2]))
        n_chain = len(keys)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if (store.pages >= n_chain and
                    pool._shared.index.match_depths(keys).get(home, 0) > 0):
                break
            time.sleep(0.02)
        out["host_store_pages"] = store.pages
        # device-warm resubmission routes BACK to the retaining replica
        # (twice: the first pays the one-off splice-path compiles)
        hits0 = pool.affinity_hits
        ids_warm, err_w = None, None
        for _ in range(2):
            r, warm_ttft, ids_warm, err_w = timed_submit(colds[2], 8)
        out["affinity_hits"] = pool.affinity_hits - hits0
        out["affinity_same_replica"] = pool.where(r.request_id) == home
        out["affinity_byte_match"] = (err_w is None
                                      and ids_warm == cold_ids[2])
        # cross-replica warm restore, engine-direct on the SIBLING so
        # both sides of the compare run on an idle pool: the sibling
        # has never seen these prompts — cold is a full re-prefill of
        # fresh same-length prompts, warm restores the chains replica
        # `home` computed from the SHARED host store. (Routing TO the
        # warm tier is what the affinity/load phases above prove;
        # pinning `home` busy to force routing here would let the
        # pin's own decode compete for compute and poison the timing.)
        def timed_direct(engine, ids, n):
            r = make_req(ids, n)
            t0 = time.monotonic()
            o = engine.submit(r)
            first = o.get()
            ttft = time.monotonic() - t0
            toks, err = drain(o, first_ev=first)
            return ttft, toks, err
        sib = pool._engines[1 - home]
        restored0 = store.stats()["restored_pages"]
        timed_direct(sib, colds[0], 8)      # warm-up: one-off overheads
        cold_sib, host_warm = [], []
        # alternate cold/warm: near-context chains mean the sibling's
        # pool holds at most one resident chain, so every cold
        # full-prefill evicts the chain the next warm run restores —
        # each warm sample is a TRUE host-tier restore, not a device
        # splice of a still-resident chain
        for i in range(3):
            cold_sib.append(timed_direct(sib, rng.integers(
                0, 255, size=plen1).tolist(), 8)[0])
            host_warm.append(timed_direct(sib, colds[(i + 1) % 2], 8)[0])
        host_warm_ttft = min(host_warm)
        cold_sib_ttft = float(np.median(cold_sib))
        out["host_restored_pages"] = \
            store.stats()["restored_pages"] - restored0
        out["cold_ttft_ms"] = round(cold_ttft * 1e3, 2)
        out["warm_ttft_ms"] = round(warm_ttft * 1e3, 2)
        out["cold_sib_ttft_ms"] = round(cold_sib_ttft * 1e3, 2)
        out["host_warm_ttft_ms"] = round(host_warm_ttft * 1e3, 2)
        out["warm_beats_cold"] = bool(
            out["host_restored_pages"] > 0
            and host_warm_ttft < cold_sib_ttft)
        out["warm_ttft_speedup"] = round(
            cold_sib_ttft / max(1e-6, host_warm_ttft), 2)

        # ---- phase 2: live migration mid-decode ----
        EVENTS.clear()
        p2 = rng.integers(0, 255, size=plen).tolist()
        req = make_req(p2, max_new)
        o = pool.submit(req)
        first = o.get()
        src = pool.where(req.request_id)
        migrated = pool.migrate(req.request_id, reason="rebalance",
                                timeout_s=30.0)
        ids, err = drain(o, first_ev=first)
        migs = [ev for ev in EVENTS.events() if ev["event"] == "migrate"
                and ev["rid"] == req.request_id]
        k = migs[0]["n_decoded"] if migs else 0
        out["migrated"] = bool(migrated and migs)
        out["migrate_dst"] = pool.where(req.request_id)
        out["migrate_n_decoded"] = k
        match = False
        if (migrated and err is None and len(ids) == max_new
                and 0 < k < max_new
                and pool.where(req.request_id) == 1 - src):
            ref, rerr = drain(pool.submit(make_req(
                list(p2) + ids[:k], max_new - k)))
            match = rerr is None and ids[k:] == ref
        out["migrate_byte_match"] = match
        out["migrations_rebalance"] = pool._migrations["rebalance"]

        # ---- phase 3: kill the victim's home replica mid-stream ----
        # warm the shared host tier first: a short run retains the
        # victim chain on its home, then an unrelated squeeze evicts it
        # through the normal reclaim path (device -> host offload)
        p3 = rng.integers(0, 255, size=plen).tolist()
        r0 = make_req(p3, 4)
        drain(pool.submit(r0))
        home = pool.where(r0.request_id)
        n_chain = len(list(pool._engines[home]._pcache.chain_keys(p3)))
        drain(pool.submit(make_req(
            rng.integers(0, 255, size=plen).tolist(),
            min(60, C - plen - 8))))
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and store.pages < n_chain:
            time.sleep(0.02)
        EVENTS.clear()
        victim = make_req(p3, max_new)
        o = pool.submit(victim)
        first = o.get()
        home = pool.where(victim.request_id)
        FAULTS.arm(f"replica{home}_die", count=1)
        ids, err = drain(o, first_ev=first)
        migs = [ev for ev in EVENTS.events() if ev["event"] == "migrate"
                and ev["rid"] == victim.request_id]
        k = migs[0]["n_decoded"] if migs else 0
        m = pool.metrics()
        out["crash_stream_ok"] = err is None and len(ids) == max_new
        out["crash_migrations"] = pool._migrations["crash"]
        out["replicas_alive_after"] = m["pool"]["replicas_alive"]
        out["crash_n_decoded"] = k
        cmatch = False
        if out["crash_stream_ok"] and 0 < k < max_new \
                and pool.where(victim.request_id) != home:
            ref, rerr = drain(pool.submit(make_req(
                list(p3) + ids[:k], max_new - k)))
            cmatch = rerr is None and ids[k:] == ref
        out["crash_byte_match"] = cmatch
        out["recovered"] = bool(out["crash_stream_ok"] and cmatch
                                and pool._migrations["crash"] >= 1
                                and m["pool"]["replicas_alive"] == 1)
    finally:
        FAULTS.reset()
        _kv_sweep(pool, out)
        pool.shutdown()
    return out


def bench_autoscale(cfg, S, C, max_new=32):
    """SLO-driven replica autoscaling + predictive weight prefetch
    (ISSUE 19), five phases on the CPU-safe smoke shape:

    0. control: the SAME admission burst against a static one-replica
       pool MUST shed — proves the load is real, not theater;
    1. scale-out pre-shed: a burst fires the queue-fill leading
       indicator and the pool must add a replica BEFORE any admission
       shed (AUTOSCALE_PRE_SHED); the follow-up burst is absorbed
       shed-free by the wider pool;
    2. slow weight stream alongside serving: a whole-checkpoint
       stream_llama_params load with the weight_stream_slow_ms chaos
       fault armed runs WHILE the burst serves — the load must finish
       degraded without stalling the serving replicas or flapping the
       scaler;
    3. idle scale-in with an in-flight survivor: after the burst
       drains, the policy scales back in; the still-decoding request is
       live-migrated off each retiring replica and its continuation
       must byte-match a fresh pool re-admission of (prompt + emitted)
       — SCALE_IN_BYTE_MATCH;
    4. warm-vs-cold spin-up (the gallery model-swap path): streaming
       the saved checkpoint with the WeightPrefetcher's parsed leaves
       already cached must beat the cold stream by >= 2x
       (SWAP_COLD_MS / SWAP_WARM_MS / SWAP_RATIO).

    The executed decision sequence must never reverse inside the
    cool-down window: AUTOSCALE_FLAPS stays 0 across every phase."""
    import shutil
    import tempfile
    import threading

    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.pool import EnginePool
    from localai_tpu.engine.weights import (WeightPrefetcher, random_params,
                                            save_llama_params,
                                            stream_llama_params)
    from localai_tpu.services.eventlog import EVENTS
    from localai_tpu.services.faults import FAULTS

    params = random_params(cfg)
    rng = np.random.default_rng(31)
    pg = 8
    plen = 16
    base = dict(num_slots=2, max_context=C, prefill_buckets=(plen, 64),
                decode_burst=2, kv_page_size=pg,
                kv_pool_pages=max(32, 2 * C // pg),
                cache_dtype=jnp.float32, max_queued_requests=6)

    def make_req(ids, n):
        return eng.GenRequest(
            prompt_ids=list(ids), max_new_tokens=n, ignore_eos=True,
            params=sampling.SamplingParamsHost(temperature=0.0))

    def drain(o):
        ids, err = [], None
        while True:
            ev = o.get()
            if ev is None:
                break
            if ev.error is not None:
                err = ev.error
            if ev.token_ids:
                ids.extend(ev.token_ids)
            elif ev.token_id >= 0:
                ids.append(ev.token_id)
        return ids, err

    def burst(pool, n, new):
        return [pool.submit(make_req(
            rng.integers(0, 255, size=plen).tolist(), new))
            for _ in range(n)]

    out = {"max_new": max_new}

    # ---- phase 0: control — the same burst on a STATIC pool sheds ----
    ctl = EnginePool.build(cfg, params, _ByteTokenizer(),
                           eng.EngineConfig(**base), engines=1,
                           eos_token_ids={cfg.vocab_size - 1})
    ctl.start(precompile=False)
    try:
        errs = [drain(o)[1] for o in burst(ctl, 15, max_new)]
        out["sheds_without_autoscale"] = sum(1 for e in errs
                                             if e is not None)
    finally:
        _kv_sweep(ctl, out)
        ctl.shutdown()

    # checkpoint for the stream-load phases: bigger than the serving
    # shape so the read/parse/stack work the prefetcher pays ahead of
    # time dominates fixed overheads (still CPU-safe, ~50 MB f32)
    swap_dir = tempfile.mkdtemp(prefix="localai-swap-")
    from localai_tpu.models import llama
    swap_cfg = llama.LlamaConfig(
        max_position_embeddings=256, vocab_size=2048, hidden_size=512,
        intermediate_size=1536, num_layers=4, num_heads=8,
        num_kv_heads=8, head_dim=64)
    save_llama_params(random_params(swap_cfg), swap_cfg, swap_dir)

    # ---- main pool: autoscaling on, one replica, burst-friendly ----
    ecfg = eng.EngineConfig(autoscale=True, autoscale_min=1,
                            autoscale_max=3, autoscale_dwell_ms=400,
                            autoscale_cooldown_ms=700, **base)
    pool = EnginePool.build(cfg, params, _ByteTokenizer(), ecfg,
                            engines=1, eos_token_ids={cfg.vocab_size - 1})
    EVENTS.clear()
    pool.start(precompile=False)
    try:
        # ---- phase 1: the ramp must scale out BEFORE any shed ----
        outs = burst(pool, 5, max_new)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if any(ev["event"] == "scale_out" for ev in EVENTS.events()):
                break
            time.sleep(0.02)
        evs = EVENTS.events()
        first_out = next((ev for ev in evs
                          if ev["event"] == "scale_out"), None)
        out["scale_out_events"] = sum(1 for ev in evs
                                      if ev["event"] == "scale_out")
        out["sheds_before_scaleout"] = sum(
            1 for ev in evs if ev["event"] == "shed"
            and (first_out is None or ev["ts"] < first_out["ts"]))
        out["pre_shed"] = bool(first_out is not None
                               and out["sheds_before_scaleout"] == 0)
        out["spinup_ms"] = first_out["spinup_ms"] if first_out else None

        # ---- phase 2: slow weight stream must not stall serving ----
        FAULTS.configure("weight_stream_slow_ms=25*")
        slow = {}

        def slow_load():
            _, slow_st = stream_llama_params(swap_dir, swap_cfg)
            slow.update(slow_st)

        # let the widened pool absorb most of the phase-1 ramp first:
        # the follow-up burst proves steady throughput under the slow
        # stream, not a second intentional queue overrun
        drain_by = time.monotonic() + 10.0
        while time.monotonic() < drain_by:
            m = pool.metrics()
            if sum(r["queued"] for r in m["replicas"]) <= 2:
                break
            time.sleep(0.05)
        t = threading.Thread(target=slow_load, daemon=True)
        t.start()
        for _ in range(10):
            outs += burst(pool, 1, max_new)
            time.sleep(0.03)
        errs = [drain(o)[1] for o in outs]
        t.join(timeout=120)
        FAULTS.disarm("weight_stream_slow_ms")
        out["burst_errors"] = sum(1 for e in errs if e is not None)
        out["slow_stream_ms"] = round(slow.get("ms", 0.0), 1)
        # the fault sleeps 25 ms per leaf: the load must have been
        # degraded (seam fired) yet the serving burst stayed shed-free
        out["slow_stream_degraded"] = bool(
            slow.get("leaves", 0) > 0
            and slow["ms"] >= 25.0 * slow["leaves"])
        out["slow_stream_stall_free"] = out["burst_errors"] == 0

        # ---- phase 3: idle scale-in, in-flight rider byte-gated ----
        # keep one long decode alive on a NON-zero replica so the
        # idle-decay scale-in exercises the live-migrate drain path; a
        # background drainer detects the rider finishing early (smoke
        # decodes are fast) so a fresh rider can take its place
        long_new = min(480, C - plen - pg)
        results: dict = {}

        def ride(r, o):
            results[r.request_id] = drain(o)

        riders: list = []

        def ensure_rider():
            for _ in range(3):
                # least-loaded routing: a short decoy parks on replica 0
                # first so the long rider lands on a retirable replica
                burst(pool, 1, 4)
                # keep a pristine prompt copy: _start_resume rewrites
                # req.prompt_ids to the full processed history, so the
                # byte-gate reference must not read it back off the req
                p = rng.integers(0, 255, size=plen).tolist()
                r = make_req(p, long_new)
                o = pool.submit(r)
                if pool.where(r.request_id) != 0:
                    th = threading.Thread(target=ride, args=(r, o),
                                          daemon=True)
                    th.start()
                    riders.append((r, th, p))
                    return
                results[r.request_id] = drain(o)  # mis-routed: flush it

        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            if len(pool._routable_idx()) == 1:
                break
            if not any(th.is_alive() for _, th, _p in riders):
                ensure_rider()
            time.sleep(0.05)
        for _, th, _p in riders:
            th.join(timeout=60)
        evs = EVENTS.events()
        out["scale_in_events"] = sum(1 for ev in evs
                                     if ev["event"] == "scale_in")
        out["replicas_final"] = len(pool._routable_idx())
        byte_gate = None
        for r, _th, p in reversed(riders):
            migs = [ev for ev in evs if ev["event"] == "migrate"
                    and ev.get("rid") == r.request_id
                    and ev.get("reason") == "scale_in"]
            # the reference must splice the rider's retained chain, so
            # the rider has to have LANDED on the surviving replica —
            # a chain whose final home later retired is gone with it
            if not migs or migs[-1].get("dst") != 0:
                continue
            ids, err = results.get(r.request_id, (None, "undrained"))
            out["scale_in_migrations"] = len(migs)
            if err is None and ids is not None and len(ids) == long_new:
                k = migs[-1]["n_decoded"]
                out["scale_in_n_decoded"] = k
                ref, rerr = drain(pool.submit(make_req(
                    list(p) + ids[:k], long_new - k)))
                byte_gate = rerr is None and ids[k:] == ref
            break
        out["byte_gate_ok"] = byte_gate

        # ---- flap accounting across every phase above ----
        snap = pool._policy.snapshot()
        out["flaps"] = snap["flaps"]
        out["autoscale_decisions"] = snap["decisions"]
        out["flaps_suppressed"] = snap["flaps_suppressed"]

        # ---- phase 4: warm-vs-cold streamed spin-up ----
        colds, warms = [], []
        warm_hit = False
        pf = WeightPrefetcher(budget_mb=2048)
        for _ in range(3):
            _, st = stream_llama_params(swap_dir, swap_cfg)
            colds.append(st["ms"])
            pf.prefetch(swap_dir, swap_cfg, wait=True)
            _, st = stream_llama_params(swap_dir, swap_cfg,
                                        prefetcher=pf)
            warms.append(st["ms"])
            warm_hit = warm_hit or st["prefetch_hit"]
        out["swap_cold_ms"] = round(float(np.median(colds)), 1)
        out["swap_warm_ms"] = round(float(np.median(warms)), 1)
        out["swap_ratio"] = round(out["swap_cold_ms"]
                                  / max(1e-3, out["swap_warm_ms"]), 2)
        out["swap_prefetch_hit"] = warm_hit
        out["weight_prefetch"] = pf.snapshot()
    finally:
        FAULTS.reset()
        _kv_sweep(pool, out)
        pool.shutdown()
        shutil.rmtree(swap_dir, ignore_errors=True)
    return out


def bench_cluster(cfg, S, C, max_new=32):
    """Cross-host KV federation scenario (ISSUE 17): TWO ClusterHosts —
    each its own EnginePool + host KV tier, joined only by the KV
    streaming transport — behind one ClusterRouter, in three phases:

    1. cross-host warm serve: a prompt admitted on host 0 is re-served
       on host 1; the chain must STREAM over the wire into host 1's
       local tier (kv_stream_hits >= 1) and the greedy output must be
       byte-identical — the KV_STREAM_HITS gate;
    2. host crash mid-stream: host 0's engine loop dies under a live
       decode (its host tier + wire server survive); the router
       re-adopts on host 1, which pulls the checkpointed chain out of
       the carcass over the wire; the stream finishes error-free and
       byte-matches a fresh re-admission on the adopting host — the
       CLUSTER_HOST_RECOVERED gate;
    3. prefill/decode disaggregation (fresh prefill+decode cluster):
       the prefill host pays TTFT then retires the chain to the
       transport, the decode host splices it and carries the stream
       byte-identically (DISAGG_BYTE_MATCH gate), and the victim's
       decode ITL is measured against a concurrent prefill wave
       hammering the prefill host (itl_wave_ratio — Splitwise's
       isolation claim, reported not gated on CPU).

    Byte-gate references go through the ROUTER pinned to the adopting
    host, so they splice the same conditioning tier (the PR-10 numerics
    caveat, now spanning hosts)."""
    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.cluster import ClusterHost, ClusterRouter
    from localai_tpu.engine.weights import random_params
    from localai_tpu.services.eventlog import EVENTS
    from localai_tpu.services.faults import FAULTS

    params = random_params(cfg)
    rng = np.random.default_rng(29)
    C = max(128, C)
    pg = 8
    ecfg = eng.EngineConfig(num_slots=2, max_context=C,
                            prefill_buckets=(32, 128), decode_burst=4,
                            kv_page_size=pg, cache_dtype=jnp.float32,
                            kv_audit="on")
    plen = min(64, C - max_new - 8)
    plen -= plen % pg                      # page-aligned: whole-chain reuse
    out = {"max_new": max_new, "plen": plen}

    def make_req(ids, n):
        return eng.GenRequest(
            prompt_ids=list(ids), max_new_tokens=n, ignore_eos=True,
            params=sampling.SamplingParamsHost(temperature=0.0))

    def drain(o, first_ev=None):
        """-> (ids, per-token arrival stamps, err)."""
        ids, ts, err = [], [], None
        ev = first_ev
        while True:
            if ev is None:
                ev = o.get()
                if ev is None:
                    break
            if ev.error is not None:
                err = ev.error
            now = time.monotonic()
            if ev.token_ids:
                ids.extend(ev.token_ids)
                ts.extend([now] * len(ev.token_ids))
            elif ev.token_id >= 0:
                ids.append(ev.token_id)
                ts.append(now)
            ev = None
        return ids, ts, err

    def itl_ms(ts):
        if len(ts) < 2:
            return None
        return round((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3, 2)

    def wait_for(pred, timeout=15.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline and not pred():
            time.sleep(0.02)
        return pred()

    def build_cluster(roles):
        hosts = [ClusterHost.build(cfg, params, _ByteTokenizer(), ecfg,
                                   host_id=i, engines=1, role=role)
                 for i, role in enumerate(roles)]
        router = ClusterRouter(hosts)
        router.start(precompile=True)
        return router

    # ---- phases 1+2: a two-host both/both cluster ----
    router = build_cluster(["both", "both"])
    h0, h1 = router.hosts
    try:
        # phase 1: warm cross-host serve over the wire
        p1 = rng.integers(0, 255, size=plen).tolist()
        r1 = make_req(p1, 8)
        t0 = time.monotonic()
        o = router.submit(r1, host=0)
        first = o.get()
        out["cold_ttft_ms"] = round((time.monotonic() - t0) * 1e3, 2)
        ids_cold, _, err = drain(o, first_ev=first)
        keys = list(h0.pool._engines[0]._pcache.chain_keys(p1))
        store0 = h0.pool._shared.store
        wait_for(lambda: all(store0.contains(k) for k in keys))
        hits0 = h1.fed.stats()["hits"]
        ids_warm, warm_ttft = None, None
        for _ in range(2):      # first warm run pays splice compiles
            rw = make_req(p1, 8)
            t0 = time.monotonic()
            o = router.submit(rw, host=1)
            first = o.get()
            warm_ttft = time.monotonic() - t0
            ids_warm, _, werr = drain(o, first_ev=first)
        st = h1.fed.stats()
        out["warm_ttft_ms"] = round(warm_ttft * 1e3, 2)
        out["kv_stream_hits"] = st["hits"] - hits0
        out["kv_stream_pages"] = st["pages"]
        out["kv_stream_bytes"] = st["bytes"]
        out["kv_stream_served_pages"] = h0.server.stats()["pages_out"]
        out["stream_byte_match"] = (err is None and werr is None
                                    and ids_warm == ids_cold)

        # phase 2: kill host 0 under a live decode
        p2 = rng.integers(0, 255, size=plen).tolist()
        drain(router.submit(make_req(p2, 4), host=0))   # warm the chain
        keys2 = list(h0.pool._engines[0]._pcache.chain_keys(p2))
        wait_for(lambda: all(store0.contains(k) for k in keys2))
        EVENTS.clear()
        victim = make_req(p2, max_new)
        o = router.submit(victim, host=0)
        first = o.get()
        h0.kill()
        ids, _, err = drain(o, first_ev=first)
        migs = [ev for ev in EVENTS.events() if ev["event"] == "migrate"
                and ev["rid"] == victim.request_id]
        k = migs[0]["n_decoded"] if migs else 0
        m = router.metrics()
        out["crash_stream_ok"] = err is None and len(ids) == max_new
        out["crash_n_decoded"] = k
        out["hosts_alive_after"] = m["cluster"]["hosts_alive"]
        out["host_recovered"] = m["cluster"]["hosts_recovered"]
        cmatch = False
        if out["crash_stream_ok"] and 0 < k < max_new \
                and router.where(victim.request_id) == 1:
            ref, _, rerr = drain(router.submit(
                make_req(list(p2) + ids[:k], max_new - k), host=1))
            cmatch = rerr is None and ids[k:] == ref
        out["crash_byte_match"] = cmatch
    finally:
        FAULTS.reset()
        _kv_sweep(router, out)
        router.shutdown()

    # ---- phase 3: prefill/decode disaggregation ----
    router = build_cluster(["prefill", "decode"])
    try:
        EVENTS.clear()
        p3 = rng.integers(0, 255, size=plen).tolist()
        req = make_req(p3, max_new)
        o = router.submit(req)
        ids, ts, err = drain(o)
        hand = [ev for ev in EVENTS.events()
                if ev["event"] == "disagg_handoff"
                and ev["rid"] == req.request_id]
        k = hand[0]["n_decoded"] if hand else 0
        out["disagg_handoffs"] = \
            router.metrics()["cluster"]["disagg_handoffs"]
        out["disagg_n_decoded"] = k
        out["disagg_stream_ok"] = err is None and len(ids) == max_new
        out["disagg_itl_ms"] = itl_ms(ts[max(1, k):])
        dmatch = False
        if out["disagg_stream_ok"] and 0 < k < max_new \
                and router.where(req.request_id) == 1:
            ref, _, rerr = drain(router.submit(
                make_req(list(p3) + ids[:k], max_new - k), host=1))
            dmatch = rerr is None and ids[k:] == ref
        out["disagg_byte_match"] = dmatch
        # decode ITL under a concurrent prefill wave on the other host
        victim = make_req(rng.integers(0, 255, size=plen).tolist(),
                          max_new)
        o = router.submit(victim)
        wave = [router.submit(make_req(
            rng.integers(0, 255, size=plen).tolist(), 2))
            for _ in range(6)]
        ids_w, ts_w, werr = drain(o)
        for w in wave:
            drain(w)
        kw = next((ev["n_decoded"] for ev in EVENTS.events()
                   if ev["event"] == "disagg_handoff"
                   and ev["rid"] == victim.request_id), 1)
        out["disagg_itl_wave_ms"] = itl_ms(ts_w[max(1, kw):])
        if out["disagg_itl_ms"] and out["disagg_itl_wave_ms"]:
            out["itl_wave_ratio"] = round(
                out["disagg_itl_wave_ms"] / out["disagg_itl_ms"], 2)
        out["disagg_wave_ok"] = werr is None and len(ids_w) == max_new
    finally:
        FAULTS.reset()
        _kv_sweep(router, out)
        router.shutdown()
    # ---- phase 4: real-process remote hosts (ISSUE 20) ----
    # The control plane's three contracts, each against a SPAWNED OS
    # process (not a thread): a slow host is depreferred, never killed
    # (CLUSTER_SLOW_NOT_KILLED); graceful drain hands live streams to a
    # sibling byte-identically and the child exits 0
    # (CLUSTER_DRAIN_BYTE_MATCH); kill -9 mid-stream recovers
    # byte-identically on the sibling (CLUSTER_PROC_RECOVERED).
    from localai_tpu.services.cluster_rpc import RemoteHostHandle

    mcfg = {k: int(getattr(cfg, k)) for k in
            ("vocab_size", "hidden_size", "intermediate_size",
             "num_layers", "num_heads", "num_kv_heads",
             "max_position_embeddings")}
    if getattr(cfg, "head_dim", None):
        mcfg["head_dim"] = int(cfg.head_dim)
    spec = {
        "host_id": 1, "role": "both", "engines": 1,
        # param_dtype bf16 = random_params' default, so the child's
        # weights are bit-identical to this process's `params`
        "model": {"kind": "llama-random", "dtype": "float32",
                  "param_dtype": "bfloat16", "config": mcfg},
        "tokenizer": "byte256",
        "engine": {"num_slots": 2, "max_context": C,
                   "prefill_buckets": [32, 128], "decode_burst": 4,
                   "kv_page_size": pg, "cache_dtype": "float32",
                   "kv_audit": "on"},
        "precompile": False, "drain_grace_s": 8.0, "drain_linger_s": 0.5,
    }
    env = dict(os.environ)
    if "JAX_PLATFORMS" not in env:
        import jax
        env["JAX_PLATFORMS"] = jax.default_backend()

    def spawn(dead_ms):
        return RemoteHostHandle.spawn(spec, env=env, heartbeat_ms=100,
                                      suspect_ms=400, dead_ms=dead_ms)

    # spawn A: slow phase, then graceful drain. dead_ms is generous so
    # GIL pauses in THIS process can't walk the detector to sticky DEAD
    # — a slow child must end the phase alive.
    t0 = time.monotonic()
    hA = spawn(dead_ms=6000)
    out["proc_spawn_s"] = round(time.monotonic() - t0, 1)
    router = ClusterRouter([
        ClusterHost.build(cfg, params, _ByteTokenizer(), ecfg,
                          host_id=0, engines=1, role="both"), hA])
    router.start(precompile=True)
    try:
        p4 = rng.integers(0, 255, size=plen).tolist()
        _, _, werr = drain(router.submit(make_req(p4, 4), host=1))
        out["proc_warm_ok"] = werr is None

        # slow != dead: 600 ms RPC delay on every frame (> suspect_ms
        # 400) holds the rtt-EWMA SUSPECT rung once it converges
        hA.fault("cluster_rpc_delay_ms=600*")
        sus = wait_for(
            lambda: hA.heartbeat_telemetry()["rtt_ewma_ms"] > 500, 25)
        states = set()
        tend = time.monotonic() + 1.5
        while time.monotonic() < tend:
            states.add(hA.state)
            time.sleep(0.05)
        routed_away = []
        for _ in range(3):
            r = make_req(rng.integers(0, 255, size=plen).tolist(), 2)
            drain(router.submit(r))
            routed_away.append(router.where(r.request_id) == 0)
        hA.fault("reset")
        rec = wait_for(lambda: hA.state == "alive", 15)
        out["slow_states"] = sorted(states)
        out["slow_routed_away"] = sum(routed_away)
        out["slow_not_killed"] = bool(sus and states == {"suspect"}
                                      and all(routed_away) and rec)

        # graceful drain mid-stream: handoff -> sibling re-adopts the
        # continuation byte-identically, child exits 0
        EVENTS.clear()
        p5 = rng.integers(0, 255, size=plen).tolist()
        victim = make_req(p5, max_new)
        o = router.submit(victim, host=1)
        first = o.get()
        router.drain_host(1)
        ids, _, derr = drain(o, first_ev=first)
        migs = [ev for ev in EVENTS.events() if ev["event"] == "migrate"
                and ev["rid"] == victim.request_id]
        k = migs[0]["n_decoded"] if migs else 0
        out["drain_reason"] = migs[0]["reason"] if migs else None
        out["drain_n_decoded"] = k
        dmatch = False
        if derr is None and len(ids) == max_new and 0 < k < max_new \
                and router.where(victim.request_id) == 0:
            ref, _, rerr = drain(router.submit(
                make_req(list(p5) + ids[:k], max_new - k), host=0))
            dmatch = rerr is None and ids[k:] == ref
        exited = wait_for(lambda: hA.proc.poll() is not None, 30)
        out["drain_child_exit"] = hA.proc.poll() if exited else None
        out["drain_byte_match"] = bool(dmatch
                                       and out["drain_child_exit"] == 0)
    finally:
        FAULTS.reset()
        _kv_sweep(router, out)
        router.shutdown()

    # spawn B: kill -9 mid-stream. Tight dead_ms — detection speed is
    # the point here, and no compile runs between kill and failover.
    hB = spawn(dead_ms=1500)
    router = ClusterRouter([
        ClusterHost.build(cfg, params, _ByteTokenizer(), ecfg,
                          host_id=0, engines=1, role="both"), hB])
    router.start(precompile=True)
    try:
        drain(router.submit(make_req(  # child pays its compile now
            rng.integers(0, 255, size=plen).tolist(), 4), host=1))
        EVENTS.clear()
        p6 = rng.integers(0, 255, size=plen).tolist()
        victim = make_req(p6, max_new)
        o = router.submit(victim, host=1)
        first = o.get()
        hB.kill()
        ids, _, cerr = drain(o, first_ev=first)
        migs = [ev for ev in EVENTS.events() if ev["event"] == "migrate"
                and ev["rid"] == victim.request_id]
        k = migs[0]["n_decoded"] if migs else 0
        out["proc_crash_reason"] = migs[0]["reason"] if migs else None
        out["proc_crash_n_decoded"] = k
        pmatch = False
        if cerr is None and len(ids) == max_new and 0 < k < max_new \
                and router.where(victim.request_id) == 0:
            ref, _, rerr = drain(router.submit(
                make_req(list(p6) + ids[:k], max_new - k), host=0))
            pmatch = rerr is None and ids[k:] == ref
        m = router.metrics()["cluster"]
        out["proc_remote_recovered"] = m.get("remote_recovered", 0)
        out["proc_host_states"] = m.get("host_states")
        out["proc_recovered"] = bool(
            pmatch and m.get("remote_recovered", 0) >= 1
            and m.get("host_states", {}).get("1") == "dead")
    finally:
        FAULTS.reset()
        _kv_sweep(router, out)
        router.shutdown()

    out["recovered"] = bool(out.get("crash_stream_ok")
                            and out.get("crash_byte_match")
                            and out.get("host_recovered") == 1
                            and out.get("hosts_alive_after") == 1)
    return out


def bench_slo(cfg, S, C, n_low=6, n_high=4, max_new=8):
    """Per-class SLO burn-rate + violation flight-recorder scenario
    (ISSUE 12), on ONE engine with a deliberately split objective:

    * ``low`` gets an impossible 0.01 ms TTFT objective — every low
      request MUST violate, so the 5m burn rate must exceed 1, a
      rate-limited ``slo_burn`` event must fire, and the flight
      recorder must land at least one dump (tagged with the low class)
      on disk;
    * ``high`` gets a loose 60 s objective — its samples must record
      but with ZERO violations and a 0.0 burn (the alerting side must
      not cry wolf on a healthy class).

    Also stitches a synthetic frontend http span to the engine's span
    ring with the same epoch-anchored shift /debug/trace uses (offset
    is exactly 0 in-process), and checks one request id shows up under
    BOTH pids of one valid merged JSON trace (``trace_merged``)."""
    import tempfile

    import jax.numpy as jnp
    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.weights import random_params
    from localai_tpu.services import tracing
    from localai_tpu.services.eventlog import EVENTS

    params = random_params(cfg)
    rng = np.random.default_rng(17)
    plen = max(8, C // 8)
    prompts = [rng.integers(0, 255, size=plen).tolist()
               for _ in range(n_low + n_high)]

    dump_dir = tempfile.mkdtemp(prefix="localai-slo-")
    ecfg = eng.EngineConfig(num_slots=S, max_context=C,
                            prefill_buckets=(32, 128),
                            cache_dtype=jnp.float32,
                            slo_ttft_ms="high=60000:low=0.01",
                            stall_dump_dir=dump_dir)
    engine = eng.Engine(cfg, params, _ByteTokenizer(), ecfg,
                        eos_token_ids={cfg.vocab_size - 1})
    engine.start(precompile=True)

    def run_one(ids, priority):
        req = eng.GenRequest(
            prompt_ids=list(ids), max_new_tokens=max_new, ignore_eos=True,
            priority=priority,
            params=sampling.SamplingParamsHost(temperature=0.0))
        o = engine.submit(req)
        while True:
            if o.get() is None:
                break
        return req.request_id

    out = {"n_low": n_low, "n_high": n_high}
    try:
        EVENTS.clear()
        rid0 = ""
        for i in range(n_low):
            rid = run_one(prompts[i], "low")
            rid0 = rid0 or rid
        for i in range(n_high):
            run_one(prompts[n_low + i], "high")
        # one metrics pull = the /metrics scrape: snapshots burn rates
        # and emits the rate-limited slo_burn events
        slo = engine.metrics().get("slo") or {}
        low = ((slo.get("classes") or {}).get("low") or {}).get(
            "ttft_ms") or {}
        high = ((slo.get("classes") or {}).get("high") or {}).get(
            "ttft_ms") or {}
        out["burn_5m_low"] = low.get("burn_5m")
        out["burn_5m_high"] = high.get("burn_5m")
        out["violations_low"] = low.get("violations")
        out["violations_high"] = high.get("violations")
        evs = EVENTS.events()
        out["violation_events"] = sum(
            1 for e in evs if e["event"] == "slo_violation")
        out["burn_events"] = sum(
            1 for e in evs if e["event"] == "slo_burn")
        dumps = sorted(f for f in os.listdir(dump_dir)
                       if f.startswith("localai-flight-")
                       and f.endswith(".json"))
        out["flight_dumps"] = len(dumps)
        out["flight_dump_low"] = False
        if dumps:
            with open(os.path.join(dump_dir, dumps[0])) as f:
                doc = json.load(f)
            out["flight_dump_low"] = any(
                v.get("class") == "low"
                for v in doc.get("violations") or [])

        # ---- merged cross-process trace (the /debug/trace shift; the
        # handshake offset is identically 0 for a same-process pair) ----
        ft = tracing.RingTracer(size=64)
        t1 = time.monotonic()
        ft.record("http", "http", t1 - 0.005, t1, rid=rid0)
        fdoc = tracing.chrome_trace(ft, pid=0, process_name="localai-http")
        bdoc = engine.trace_events()
        shift_us = (bdoc["localai"]["t0_epoch"]
                    - fdoc["localai"]["t0_epoch"]) * 1e6
        merged = list(fdoc["traceEvents"])
        for evd in bdoc["traceEvents"]:
            evd = dict(evd)
            if evd.get("ph") != "M":
                evd["ts"] = evd.get("ts", 0.0) + shift_us
            merged.append(evd)
        blob = json.dumps({"displayTimeUnit": "ms",
                           "traceEvents": merged})
        pids = {evd.get("pid")
                for evd in json.loads(blob)["traceEvents"]
                if (evd.get("args") or {}).get("request_id") == rid0}
        out["trace_merged"] = int(len(pids) >= 2)
    finally:
        _kv_sweep(engine, out)
        engine.shutdown()
    return out


def bench_multiturn(cfg, S, C, n_conv, n_turns, sys_len, user_len, max_new,
                    pressure=False):
    """Multi-turn shared-prefix scenario (PR 2 acceptance): N greedy
    conversations of K turns each, submitted round-robin through S << N
    slots so every conversation's slot is overwritten between its own
    turns — the shape where PR 1's live-slot reuse never fires and the
    cross-release prefix cache (engine/prefix_cache.py) is the only
    thing standing between turn 2 and a full re-prefill. Runs the same
    token schedule with the cache on and off and reports per-phase TTFT,
    the store hit-rate, and whether greedy outputs stayed byte-identical
    (they must: reused pages hold the same rows a cold prefill writes).

    ``pressure=True`` is the PR 3 acceptance variant: the DEVICE pool is
    sized to ~half the conversations' working set so retained chains get
    evicted between turns, and the on/off axis becomes kv_offload (the
    host-RAM tier) instead of the prefix cache — off, every warm turn
    behind an eviction re-prefills; on, it restores from host RAM. The
    pressure comparison runs the cache in float32: the byte-identical
    check compares restore-then-continue against full re-prefill, whose
    forwards run at different bucket shapes — under bf16 the shape-
    dependent rounding (~2^-8 relative) is the same magnitude as a
    512-vocab random model's top-logit gaps, so greedy flips on numeric
    noise unrelated to the mechanism under test; f32 puts the noise
    floor ~2^-23 where the comparison is deterministic."""
    import jax.numpy as jnp

    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.weights import random_params

    params = random_params(
        cfg, quantize=os.environ.get("LOCALAI_BENCH_QUANT", ""))
    pgs = 16
    final_rows = sys_len + n_turns * (user_len + max_new)
    working_pages = n_conv * (-(-final_rows // pgs))
    # pressured pool: ~half the working set, floored at live demand (S
    # slots of the final history + COW/boundary headroom) so admission
    # always succeeds and the squeeze lands on RETAINED chains only
    pressured = max(S * (-(-final_rows // pgs)) + 2, working_pages // 2)
    out = {"pressure": bool(pressure),
           **({"kv_pool_pages": pressured,
               "working_set_pages": working_pages} if pressure else {})}
    gen_by_mode = {}
    for mode in ("on", "off"):
        ecfg = eng.EngineConfig(
            num_slots=S, max_context=C, prefill_buckets=(32, 128, 512),
            prefill_chunk=min(512, C),
            cache_dtype=jnp.float32 if pressure else jnp.bfloat16,
            kv_layout="paged", kv_page_size=pgs,
            # default scenario: headroom ABOVE the contiguous reservation
            # so retention is bounded by the scenario, not by eviction —
            # the win measured is reuse, not replacement policy
            kv_pool_pages=(pressured if pressure
                           else (n_conv + S) * (C // pgs)),
            kv_prefix_cache=(True if pressure else mode == "on"),
            kv_offload=(mode == "on") if pressure else False)
        engine = eng.Engine(cfg, params, _ByteTokenizer(), ecfg,
                            eos_token_ids={cfg.vocab_size - 1})
        engine.start(precompile=False)
        rng = np.random.default_rng(7)
        histories = [rng.integers(0, 255, size=sys_len).tolist()
                     for _ in range(n_conv)]
        ttfts = {"cold": [], "warm": []}
        gens = []
        try:
            for turn in range(n_turns):
                for c in range(n_conv):
                    ids = histories[c] + rng.integers(
                        0, 255, size=user_len).tolist()
                    req = eng.GenRequest(
                        prompt_ids=ids, max_new_tokens=max_new,
                        ignore_eos=True,
                        params=sampling.SamplingParamsHost(temperature=0.0))
                    t0 = time.monotonic()
                    q = engine.submit(req)
                    ttft = None
                    toks = []
                    while True:
                        ev = q.get()
                        if ev is None:
                            break
                        if ttft is None:
                            ttft = time.monotonic() - t0
                        if ev.error:
                            raise RuntimeError(ev.error)
                        toks.extend(ev.token_ids or
                                    ([ev.token_id] if ev.token_id >= 0
                                     else []))
                    # the first turn of the first pass over the fleet is
                    # also paying jit warmup — drop conv 0 turn 0 from
                    # the timing (it stays in the token parity check)
                    if not (turn == 0 and c == 0):
                        ttfts["cold" if turn == 0 else "warm"].append(ttft)
                    gens.append(toks)
                    histories[c] = ids + toks
            m = engine.metrics()
        finally:
            _kv_sweep(engine, out)
            engine.shutdown()
        gen_by_mode[mode] = gens
        r = {
            "p50_ttft_cold_ms": float(np.percentile(ttfts["cold"], 50) * 1e3),
            "p50_ttft_warm_ms": float(np.percentile(ttfts["warm"], 50) * 1e3),
        }
        pc = m.get("prefix_cache")
        if pc:
            consulted = pc["hits"] + pc["misses"]
            r["hit_rate"] = round(pc["hits"] / consulted, 3) if consulted else 0.0
            r["reused_rows"] = pc["hit_rows"]
            r["evicted_pages"] = pc["evicted_pages"]
        off = m.get("kv_offload")
        if off:
            r["offloaded_pages"] = off["offloaded_pages"]
            r["restored_pages"] = off["restored_pages"]
            r["restores"] = off["restores"]
        out[("offload_" if pressure else "cache_") + mode] = r
    tag = "offload_" if pressure else "cache_"
    out["greedy_match"] = gen_by_mode["on"] == gen_by_mode["off"]
    warm_on = out[tag + "on"]["p50_ttft_warm_ms"]
    warm_off = out[tag + "off"]["p50_ttft_warm_ms"]
    out["warm_ttft_speedup"] = round(warm_off / warm_on, 3) if warm_on else 0.0
    return out


def bench_longcontext(cfg, S, C, max_new=32):
    """Long-context serving tier (ISSUE 16 acceptance): TTFT + ITL vs
    context length on the snap-back window engine, whose on-device KV is
    a bounded working set (kv_window_pages) with the cold middle demoted
    to the host tier, plus the decode-time prefetch-ahead pipeline.

    Three phases, one engine each where needed:

      1. cold sweep — one greedy request per context length (CI scale:
         fractions of C; set LOCALAI_BENCH_LC_LENS=4096,...,131072 on a
         real chip) through the WINDOWED engine, recording TTFT and the
         inter-token-latency distribution. The acceptance claim is the
         ITL p99 staying flat as context grows — the window caps the
         attention working set, so decode cost stops scaling with
         context.
      2. unwindowed reference — the same sweep through a plain paged
         engine sized to fit everything (possible at CI scale; the whole
         point is that it is NOT possible at 128k), for the TTFT/ITL
         comparison, plus the byte gate: a prompt short enough to fit
         INSIDE the window must produce byte-identical greedy output on
         both engines (the window machinery must be invisible until the
         policy actually engages).
      3. prefetch warm turn — both slots are pinned by decode blockers,
         then the longest conversation's follow-up turn is queued behind
         them: the prefetch tick must restore its sink + tail-window
         links from the host tier DURING the blockers' bursts, so the
         admission finds them resident (PREFETCH_HIT > 0) and never
         pays a synchronous restore it predicted (PREFETCH_LATE == 0).

    Ends with the ISSUE-15 audit sweep over the deep chains the sweep
    left behind: demote / compress / prefetch are first-class ledger
    ops, so KV_AUDIT_VIOLATIONS / KV_LEAKED_PAGES must both be 0."""
    import jax.numpy as jnp

    from localai_tpu.engine import engine as eng
    from localai_tpu.engine import sampling
    from localai_tpu.engine.weights import random_params

    pgs = 16
    W = int(os.environ.get("LOCALAI_BENCH_LC_WINDOW", "4"))
    sink = int(os.environ.get("LOCALAI_BENCH_LC_SINK", "1"))
    ahead = int(os.environ.get("LOCALAI_BENCH_LC_AHEAD", "2"))
    lens_env = os.environ.get("LOCALAI_BENCH_LC_LENS", "")
    if lens_env:
        lens = [int(x) for x in lens_env.split(",") if x.strip()]
    else:
        lens = [C // 8, C // 4, C // 2, (3 * C) // 4]
    lens = sorted({min(n, C - max_new - 8) for n in lens if n >= pgs})
    budget_rows = (sink + W) * pgs
    out = {"window_pages": W, "sink_pages": sink, "prefetch_ahead": ahead,
           "page_size": pgs, "window_rows": budget_rows, "ctx_lens": lens,
           "kv_audit_violations": 0, "kv_leaked_pages": 0}

    def _run(engine, ids, mn):
        req = eng.GenRequest(
            prompt_ids=list(ids), max_new_tokens=mn, ignore_eos=True,
            params=sampling.SamplingParamsHost(temperature=0.0))
        t0 = time.monotonic()
        q = engine.submit(req)
        ttft, last, toks, itls = None, None, [], []
        while True:
            ev = q.get()
            if ev is None:
                break
            now = time.monotonic()
            if ev.error:
                raise RuntimeError(ev.error)
            new = ev.token_ids or ([ev.token_id] if ev.token_id >= 0
                                   else [])
            if new:
                if ttft is None:
                    ttft = now - t0
                elif last is not None:
                    # events carry whole bursts: spread the gap over the
                    # burst so the samples approximate per-token ITL
                    itls.extend([(now - last) / len(new)] * len(new))
                last = now
                toks.extend(new)
        return ttft, toks, itls

    def _sweep_engine(windowed):
        ecfg = eng.EngineConfig(
            num_slots=S, max_context=C, prefill_buckets=(32, 64),
            prefill_chunk=64, decode_burst=4,
            cache_dtype=jnp.float32,
            kv_layout="paged", kv_page_size=pgs,
            # windowed: a pool a fraction of the sweep's full working
            # set — the window is what makes the long prompts fit.
            # unwindowed reference: sized to hold everything (only
            # possible because CI scale is small)
            kv_pool_pages=(S * (sink + W + 8) + 24 if windowed
                           else S * (C // pgs) + 8),
            kv_audit="on",
            **(dict(kv_window_pages=W, kv_sink_pages=sink,
                    kv_window_policy="demote", kv_prefetch_ahead=ahead,
                    kv_offload=True)
               if windowed else dict(kv_offload=False)))
        engine = eng.Engine(cfg, params, _ByteTokenizer(), ecfg,
                            eos_token_ids={cfg.vocab_size - 1})
        engine.start(precompile=False)
        return engine

    params = random_params(
        cfg, quantize=os.environ.get("LOCALAI_BENCH_QUANT", ""))
    rng = np.random.default_rng(11)
    prompts = {n: rng.integers(0, 255, size=n).tolist() for n in lens}
    # short-prompt byte gate: must fit the working set INCLUDING the
    # generated tokens and the window-advance look-ahead margin
    # (decode_burst * (n_draft + 1) + 2), so the window never engages
    mn_short = 12
    short_len = max(pgs, budget_rows - mn_short - 32)
    short_ids = rng.integers(0, 255, size=short_len).tolist()
    warm_len = budget_rows + 2 * pgs   # jit warmup that DOES window
    blk_ids = [rng.integers(0, 255, size=24).tolist() for _ in range(S)]

    gen_by_mode = {}
    for mode in ("windowed", "unwindowed"):
        engine = _sweep_engine(windowed=(mode == "windowed"))
        per_len = {}
        try:
            # jit warmup: one short prompt for the plain paths plus one
            # past the window budget so the win-piece prefill / windowed
            # decode programs compile OUTSIDE the timed sweep
            _run(engine, rng.integers(0, 255, size=pgs).tolist(), 4)
            _run(engine, rng.integers(0, 255, size=warm_len).tolist(), 12)
            for n in lens:
                ttft, toks, itls = _run(engine, prompts[n], max_new)
                itls = itls or [0.0]
                per_len[str(n)] = {
                    "ttft_ms": round((ttft or 0.0) * 1e3, 1),
                    "itl_p50_ms": round(
                        float(np.percentile(itls, 50)) * 1e3, 2),
                    "itl_p99_ms": round(
                        float(np.percentile(itls, 99)) * 1e3, 2),
                    "windowed": bool(n + max_new > budget_rows
                                     and mode == "windowed"),
                }
            _, gen_by_mode[mode], _ = _run(engine, short_ids, mn_short)
            if mode == "windowed":
                # phase 3: warm follow-up turn behind decode blockers —
                # its host-tier links must be prefetched DURING the
                # blockers' bursts, ahead of its admission
                longest = lens[-1]
                warm_ids = (prompts[longest]
                            + rng.integers(0, 255, size=8).tolist())
                bqs = [engine.submit(eng.GenRequest(
                    prompt_ids=ids, max_new_tokens=48, ignore_eos=True,
                    params=sampling.SamplingParamsHost(temperature=0.0)))
                    for ids in blk_ids]
                t0 = time.monotonic()
                wq = engine.submit(eng.GenRequest(
                    prompt_ids=warm_ids, max_new_tokens=8,
                    ignore_eos=True,
                    params=sampling.SamplingParamsHost(temperature=0.0)))
                warm_ttft = None
                # drain the warm stream FIRST (blocked on wq.get its
                # first-token timestamp is arrival time); the blocker
                # queues just buffer meanwhile
                for q in [wq] + bqs:
                    while True:
                        ev = q.get()
                        if ev is None:
                            break
                        if ev.error:
                            raise RuntimeError(ev.error)
                        if q is wq and warm_ttft is None and (
                                ev.token_ids or ev.token_id >= 0):
                            warm_ttft = time.monotonic() - t0
                out["warm_turn_ttft_ms"] = round(
                    (warm_ttft or 0.0) * 1e3, 1)
                m = engine.metrics()
                off = m.get("kv_offload") or {}
                for k in ("prefetch_issued", "prefetch_hits",
                          "prefetch_late", "prefetch_wasted",
                          "offloaded_pages", "restored_pages"):
                    out[k] = off.get(k)
                dbg = engine.kv_debug()
                out["prefetch_staged_after"] = (
                    dbg.get("prefetch") or {}).get("staged_pages")
        finally:
            _kv_sweep(engine, out)
            engine.shutdown()
        out[f"{mode}_by_len"] = per_len
    wl = out["windowed_by_len"]
    p99s = [wl[str(n)]["itl_p99_ms"] for n in lens]
    out["itl_p99_ratio"] = (round(p99s[-1] / p99s[0], 3)
                            if p99s and p99s[0] else None)
    out["short_byte_match"] = (
        gen_by_mode["windowed"] == gen_by_mode["unwindowed"])
    return out


def bench_kernel(cfg, S, C, steps, inner):
    """Bare decode-burst loop: model + sampler, no engine thread."""
    import jax
    import jax.numpy as jnp
    from localai_tpu.engine import sampling
    from localai_tpu.models import llama

    from localai_tpu.engine.weights import random_params

    params = random_params(
        cfg, quantize=os.environ.get("LOCALAI_BENCH_QUANT", ""))
    kv_dtype = (jnp.int8 if os.environ.get("LOCALAI_BENCH_KV", "") == "int8"
                else None)
    ck, cv = llama.init_cache(cfg, S, C, kv_dtype)
    slot_params = sampling.make_slot_params(S)
    ring, rpos = sampling.make_ring(S)
    bias = jnp.zeros((S, cfg.vocab_size), jnp.float32)
    keys = jax.vmap(jax.random.key_data)(
        jax.vmap(jax.random.PRNGKey)(jnp.arange(S, dtype=jnp.uint32)))
    active = jnp.ones((S,), jnp.bool_)

    @jax.jit
    def burst(params, slot_params, bias, active, tokens, lengths, ck, cv, ring, rpos, keys):
        def body(carry, _):
            tokens, lengths, ck, cv, ring, rpos, keys = carry
            logits, ck, cv = llama.decode_step(params, cfg, tokens, lengths, ck, cv)
            ids, _, keys, _ = sampling.sample(logits, slot_params, ring, rpos, bias, keys)
            ring, rpos = sampling.update_ring(ring, rpos, ids, active)
            return (ids, lengths + 1, ck, cv, ring, rpos, keys), ids

        carry, ids_seq = jax.lax.scan(
            body, (tokens, lengths, ck, cv, ring, rpos, keys), None, length=inner)
        return carry, ids_seq

    tokens = jnp.zeros((S,), jnp.int32)
    lengths = jnp.full((S,), C // 2, jnp.int32)  # mid-context, realistic load

    carry, ids_seq = burst(params, slot_params, bias, active, tokens, lengths,
                           ck, cv, ring, rpos, keys)
    np.asarray(ids_seq)  # sync
    (tokens, lengths, ck, cv, ring, rpos, keys) = carry
    lengths = jnp.full((S,), C // 2, jnp.int32)

    n_bursts = max(min(steps, C // 2 - 2) // inner, 1)
    t0 = time.perf_counter()
    for _ in range(n_bursts):
        carry, ids_seq = burst(params, slot_params, bias, active, tokens, lengths,
                               ck, cv, ring, rpos, keys)
        (tokens, lengths, ck, cv, ring, rpos, keys) = carry
        # tokens MUST reach the host each burst in real serving, so the
        # timing ends on the device_get
        np.asarray(ids_seq)
    dt = time.perf_counter() - t0
    return {"tok_s": S * n_bursts * inner / dt}


def _arm_budget_watchdog(partial_line: dict) -> float:
    """Global wall-clock deadline (un-wedgeable bench, verdict r05 #1):
    LOCALAI_BENCH_DEADLINE_S takes precedence over the legacy
    LOCALAI_BENCH_BUDGET_S name (default 480 s — the harness kills at
    ~600, and r05 showed a watchdog AT the harness limit loses the race
    and dies rc=124 with empty output; 0 disables): a daemon thread
    prints whatever the finished phases measured so far as ONE JSON line
    (with an ``error`` field naming the overrun) and exits rc=0 at the
    deadline, so ``parsed`` is never null no matter what wedges.
    Returns the deadline (monotonic) or +inf."""
    import threading

    budget = float(os.environ.get(
        "LOCALAI_BENCH_DEADLINE_S",
        os.environ.get("LOCALAI_BENCH_BUDGET_S", "480")))
    if budget <= 0:
        return float("inf")
    deadline = time.monotonic() + budget

    def watchdog():
        # small sleep slices: one long sleep can overshoot under load,
        # and the whole point is beating the harness's hard kill
        while time.monotonic() < deadline:
            time.sleep(min(2.0, max(0.1, deadline - time.monotonic())))
        partial_line.setdefault("metric", "bench_budget_exceeded")
        partial_line["budget_exceeded_s"] = budget
        partial_line["error"] = (
            f"wall-clock deadline ({budget:g}s) exceeded; "
            "emitting partial results")
        print(json.dumps(partial_line), flush=True)
        os._exit(0)

    threading.Thread(target=watchdog, daemon=True,
                     name="bench-budget").start()
    return deadline


def _emit_phase(name: str, payload) -> None:
    """Incremental per-phase progress on STDERR (stdout stays reserved
    for the single final JSON summary line the harness parses)."""
    try:
        print(json.dumps({"phase": name, "result": payload}),
              file=sys.stderr, flush=True)
    except (TypeError, ValueError):
        print(json.dumps({"phase": name, "result": str(payload)[:500]}),
              file=sys.stderr, flush=True)


def _kv_pick(out: dict, *srcs) -> dict:
    """Fold a subprocess phase's flat KV audit totals (ISSUE 15) into
    the parent's whitelisted phase dict, accumulating across sources so
    ci.sh can gate the summed KV_AUDIT_VIOLATIONS / KV_LEAKED_PAGES."""
    for r in srcs:
        for k in ("kv_audit_violations", "kv_leaked_pages"):
            if (r or {}).get(k) is not None:
                out[k] = int(out.get(k, 0) or 0) + int(r[k] or 0)
    return out


def _subprocess_jax_platform(deadline: float) -> str:
    """JAX_PLATFORMS value for spawned bench subprocesses: the parent's
    explicit setting if any, else "" (= let jax pick the chip) when a
    fresh interpreter can initialize a backend quickly, else "cpu".
    On chipless containers unpinned TPU discovery HANGS rather than
    failing, which used to eat the whole compare budget as subprocess
    timeouts — so the probe itself is time-boxed."""
    import subprocess

    if os.environ.get("JAX_PLATFORMS"):
        return os.environ["JAX_PLATFORMS"]
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    try:
        res = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            env=env, capture_output=True, text=True,
            timeout=max(10, min(45, deadline - time.monotonic() - 60)))
        if res.returncode == 0 and res.stdout.strip():
            return ""
    except Exception:
        pass
    return "cpu"


def _engine_direct_layout_compare(deadline: float, partial: dict) -> dict:
    """Decode tok/s for the PAGED vs CONTIGUOUS KV layouts: two
    engine-direct subprocesses on a small preset
    (LOCALAI_BENCH_COMPARE_PRESET, default the CPU-safe smoke shape; set
    1b/8b on a real chip) with identical everything but kv_layout."""
    import subprocess

    cmp_preset = os.environ.get("LOCALAI_BENCH_COMPARE_PRESET", "smoke")
    hp = HTTP_PRESETS.get(cmp_preset, HTTP_PRESETS["smoke"])
    platform = _subprocess_jax_platform(deadline)
    out = {}
    for layout in ("paged", "contiguous"):
        remaining = deadline - time.monotonic()
        if remaining < 30:
            out[f"{layout}_error"] = "budget exhausted"
            break
        env = dict(os.environ)
        env.update({
            "LOCALAI_BENCH_PRESET": cmp_preset,
            "LOCALAI_BENCH_SLOTS": str(hp["slots"]),
            "LOCALAI_BENCH_CTX": str(hp["ctx"]),
            "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
            "LOCALAI_BENCH_KV": hp.get("kv", ""),
            "LOCALAI_BENCH_KV_LAYOUT": layout,
            "LOCALAI_BENCH_PROMPT": os.environ.get(
                "LOCALAI_BENCH_COMPARE_PROMPT", "48"),
            "LOCALAI_BENCH_NEW": os.environ.get(
                "LOCALAI_BENCH_COMPARE_NEW", "32"),
            "LOCALAI_BENCH_TOKENS": os.environ.get(
                "LOCALAI_BENCH_COMPARE_TOKENS", "256"),
            "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
            "LOCALAI_BENCH_DEADLINE_S": "0",
        })
        if platform:
            env["JAX_PLATFORMS"] = platform
        else:
            env.pop("JAX_PLATFORMS", None)
        try:
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--engine"],
                env=env, capture_output=True, text=True,
                timeout=max(30, min(remaining - 10, 1800)))
            for ln in res.stdout.splitlines():
                ln = ln.strip()
                if ln.startswith("{"):
                    r = json.loads(ln)
                    out[f"{layout}_tok_s"] = r.get("value")
                    _kv_pick(out, r)
            if f"{layout}_tok_s" not in out:
                out[f"{layout}_error"] = (f"rc={res.returncode} "
                                          f"stderr={res.stderr[-200:]}")
        except Exception as e:
            out[f"{layout}_error"] = f"{type(e).__name__}: {e}"[:200]
        partial.update({f"kv_layout_compare_{k}": v for k, v in out.items()})
    _emit_phase("kv_layout_compare", out)
    return out


def _engine_direct_packed(deadline: float, partial: dict) -> dict:
    """The packed-prefill acceptance scenario as a bench phase: a
    concurrent mixed-prompt wave, prefill_packed on vs off, engine-direct
    in a subprocess (LOCALAI_BENCH_MT_PRESET, default the CPU-safe smoke
    shape). Reports the packed-vs-sequential loaded-TTFT speedup, the
    loaded/unloaded TTFT ratio (the tracked line in scripts/ci.sh), and
    greedy byte-parity between the two scheduling modes."""
    import subprocess

    mt_preset = os.environ.get("LOCALAI_BENCH_MT_PRESET", "smoke")
    hp = HTTP_PRESETS.get(mt_preset, HTTP_PRESETS["smoke"])
    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": mt_preset,
        # the scenario's own canonical context (C=256 via the CLI
        # default), NOT the harness preset's ctx: at ctx=128 every
        # prompt fits one admission wave and the loaded p50 TTFT the
        # FUSED_TTFT_MS= line tracks becomes tick-phase noise
        "LOCALAI_BENCH_CTX": os.environ.get("LOCALAI_BENCH_CTX", "0"),
        "LOCALAI_BENCH_SLOTS": os.environ.get("LOCALAI_BENCH_SLOTS", "4"),
        "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
    })
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--packed-prefill"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                lp = r.get("longpack") or {}
                out = {"ttft_speedup": r.get("ttft_speedup"),
                       "greedy_match": r.get("greedy_match"),
                       "ttft_loaded_unloaded_ratio": r.get(
                           "ttft_loaded_unloaded_ratio"),
                       "packed_ms": r.get("packed", {}).get("p50_ttft_ms"),
                       "sequential_ms": r.get("sequential", {}).get(
                           "p50_ttft_ms"),
                       "packed_tok_s": r.get("packed", {}).get("tok_s"),
                       "sequential_tok_s": r.get("sequential", {}).get(
                           "tok_s"),
                       "fused_ttft_ms": r.get("fused_ttft_ms"),
                       "unfused_ttft_ms": r.get("unfused_ttft_ms"),
                       "longpack_fallbacks": lp.get("kernel_fallbacks"),
                       "longpack_max_bucket": lp.get("max_pack_bucket"),
                       "longpack_match": lp.get("greedy_match")}
                _kv_pick(out, r, lp)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    partial.update({f"packed_prefill_{k}": v for k, v in out.items()})
    _emit_phase("packed_prefill", out)
    return out


def _engine_direct_chaos(deadline: float, partial: dict) -> dict:
    """The fault-lifecycle SLO scenario (ISSUE 7) as a bench phase:
    saturation-shed latency plus stall-abort/ring-dump recovery with
    greedy byte parity, engine-direct in a subprocess on the CPU-safe
    smoke shape (LOCALAI_BENCH_CHAOS_PRESET to override)."""
    import subprocess

    ch_preset = os.environ.get("LOCALAI_BENCH_CHAOS_PRESET", "smoke")
    hp = HTTP_PRESETS.get(ch_preset, HTTP_PRESETS["smoke"])
    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": ch_preset,
        "LOCALAI_BENCH_SLOTS": str(hp["slots"]),
        "LOCALAI_BENCH_CTX": str(hp["ctx"]),
        "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
    })
    env.pop("LOCALAI_FAULTS", None)  # the scenario arms its own faults
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--chaos"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                out = {"ok": r.get("value"),
                       "shed": r.get("shed"),
                       "served": r.get("served"),
                       "unstructured": r.get("unstructured"),
                       "shed_p95_ms": r.get("shed_p95_ms"),
                       "shed_under_50ms": r.get("shed_under_50ms"),
                       "stall_aborted": r.get("stall_aborted"),
                       "stall_dump": r.get("stall_dump"),
                       "recovered": r.get("recovered"),
                       "survivors_identical": r.get("survivors_identical")}
                _kv_pick(out, r)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    partial.update({f"chaos_{k}": v for k, v in out.items()})
    _emit_phase("chaos", out)
    return out


def _engine_direct_priority(deadline: float, partial: dict) -> dict:
    """The preemptive priority scheduler scenario (ISSUE 10) as a bench
    phase: high-priority TTFT under a saturating low background, preempt
    on vs off, plus the resume byte-match gate — engine-direct in a
    subprocess on the CPU-safe smoke shape (LOCALAI_BENCH_PRIO_PRESET
    to override)."""
    import subprocess

    pr_preset = os.environ.get("LOCALAI_BENCH_PRIO_PRESET", "smoke")
    hp = HTTP_PRESETS.get(pr_preset, HTTP_PRESETS["smoke"])
    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": pr_preset,
        "LOCALAI_BENCH_SLOTS": str(hp["slots"]),
        "LOCALAI_BENCH_CTX": str(hp["ctx"]),
        "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
    })
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--priority"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                out = {"ttft_ratio": r.get("ttft_ratio"),
                       "p50_ttft_on_ms": r.get("p50_ttft_on_ms"),
                       "p50_ttft_off_ms": r.get("p50_ttft_off_ms"),
                       "preemptions": r.get("preemptions"),
                       "resumes": r.get("resumes"),
                       "low_complete": r.get("low_complete"),
                       "resume_byte_match": r.get("resume_byte_match")}
                _kv_pick(out, r)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    partial.update({f"priority_{k}": v for k, v in out.items()})
    _emit_phase("priority", out)
    return out


def _engine_direct_slo(deadline: float, partial: dict) -> dict:
    """The per-class SLO burn-rate + flight-recorder scenario (ISSUE 12)
    as a bench phase: tight low-class objective must burn and dump,
    loose high-class must stay clean, one merged two-pid trace —
    engine-direct in a subprocess on the CPU-safe smoke shape
    (LOCALAI_BENCH_SLO_PRESET to override)."""
    import subprocess

    sl_preset = os.environ.get("LOCALAI_BENCH_SLO_PRESET", "smoke")
    hp = HTTP_PRESETS.get(sl_preset, HTTP_PRESETS["smoke"])
    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": sl_preset,
        "LOCALAI_BENCH_SLOTS": str(hp["slots"]),
        "LOCALAI_BENCH_CTX": str(hp["ctx"]),
        "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
    })
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--slo"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                out = {"ok": r.get("value"),
                       "burn_5m_low": r.get("burn_5m_low"),
                       "burn_5m_high": r.get("burn_5m_high"),
                       "violations_low": r.get("violations_low"),
                       "violations_high": r.get("violations_high"),
                       "violation_events": r.get("violation_events"),
                       "burn_events": r.get("burn_events"),
                       "flight_dumps": r.get("flight_dumps"),
                       "flight_dump_low": r.get("flight_dump_low"),
                       "trace_merged": r.get("trace_merged")}
                _kv_pick(out, r)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    partial.update({f"slo_{k}": v for k, v in out.items()})
    _emit_phase("slo", out)
    return out


def _engine_direct_spec(deadline: float, partial: dict) -> dict:
    """The speculative-decoding scenario (ISSUE 13) as a bench phase:
    n-gram self-speculation on vs off over the same greedy wave —
    accepted-tokens-per-dispatch, ITL both ways, byte-identical output —
    engine-direct in a subprocess on the CPU-safe smoke shape
    (LOCALAI_BENCH_SPEC_PRESET to override)."""
    import subprocess

    sp_preset = os.environ.get("LOCALAI_BENCH_SPEC_PRESET", "smoke")
    hp = HTTP_PRESETS.get(sp_preset, HTTP_PRESETS["smoke"])
    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": sp_preset,
        "LOCALAI_BENCH_SLOTS": str(hp["slots"]),
        "LOCALAI_BENCH_CTX": str(hp["ctx"]),
        "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
    })
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--spec"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                out = {"ok": r.get("ok"),
                       "accept_per_dispatch": r.get("accept_per_dispatch"),
                       "acceptance_rate": r.get("acceptance_rate"),
                       "byte_match": r.get("byte_match"),
                       "itl_on_ms": r.get("itl_on_ms"),
                       "itl_off_ms": r.get("itl_off_ms"),
                       "itl_speedup": r.get("itl_speedup"),
                       "rounds": r.get("rounds"),
                       "dispatches": r.get("dispatches"),
                       "mixed_dispatches": r.get("mixed_dispatches"),
                       # ISSUE 18: stochastic speculative sampling wave
                       "sampled_accept_per_dispatch": r.get(
                           "sampled_accept_per_dispatch"),
                       "sampled_acceptance_rate": r.get(
                           "sampled_acceptance_rate"),
                       "sampled_rounds": r.get("sampled_rounds"),
                       "sampled_itl_on_ms": r.get("sampled_itl_on_ms"),
                       "sampled_itl_off_ms": r.get("sampled_itl_off_ms"),
                       "sampled_chi2_p": r.get("sampled_chi2_p"),
                       "sampled_dist_ok": r.get("sampled_dist_ok")}
                _kv_pick(out, r)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    partial.update({f"spec_{k}": v for k, v in out.items()})
    _emit_phase("spec", out)
    return out


def _engine_direct_replicas(deadline: float, partial: dict) -> dict:
    """The engine replica pool scenario (ISSUE 14) as a bench phase:
    prefix-affinity routing across two replicas, forced live migration
    with the byte gate, and kill-one-replica crash recovery through the
    shared host tier — engine-direct in a subprocess on the CPU-safe
    smoke shape (LOCALAI_BENCH_REPLICAS_PRESET to override)."""
    import subprocess

    rp_preset = os.environ.get("LOCALAI_BENCH_REPLICAS_PRESET", "smoke")
    hp = HTTP_PRESETS.get(rp_preset, HTTP_PRESETS["smoke"])
    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": rp_preset,
        "LOCALAI_BENCH_SLOTS": str(hp["slots"]),
        "LOCALAI_BENCH_CTX": str(hp["ctx"]),
        "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
    })
    env.pop("LOCALAI_FAULTS", None)  # the scenario arms its own faults
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--replicas"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                out = {"ok": r.get("ok"),
                       "affinity_hits": r.get("affinity_hits"),
                       "affinity_byte_match": r.get("affinity_byte_match"),
                       "cold_ttft_ms": r.get("cold_ttft_ms"),
                       "warm_ttft_ms": r.get("warm_ttft_ms"),
                       "host_warm_ttft_ms": r.get("host_warm_ttft_ms"),
                       "warm_beats_cold": r.get("warm_beats_cold"),
                       "warm_ttft_speedup": r.get("warm_ttft_speedup"),
                       "migrate_byte_match": r.get("migrate_byte_match"),
                       "migrations_rebalance": r.get("migrations_rebalance"),
                       "crash_migrations": r.get("crash_migrations"),
                       "crash_byte_match": r.get("crash_byte_match"),
                       "replicas_alive_after": r.get("replicas_alive_after"),
                       "recovered": r.get("recovered")}
                _kv_pick(out, r)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    partial.update({f"replicas_{k}": v for k, v in out.items()})
    _emit_phase("replicas", out)
    return out


def _engine_direct_multiturn(deadline: float, partial: dict) -> dict:
    """The PR-2 acceptance scenario as a default-bench phase: multi-turn
    conversations under slot churn, prefix cache on vs off, in one
    engine-direct subprocess (LOCALAI_BENCH_MT_PRESET, default the
    CPU-safe smoke shape; set 1b/8b on a real chip)."""
    import subprocess

    mt_preset = os.environ.get("LOCALAI_BENCH_MT_PRESET", "smoke")
    hp = HTTP_PRESETS.get(mt_preset, HTTP_PRESETS["smoke"])
    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": mt_preset,
        "LOCALAI_BENCH_CTX": str(hp["ctx"]),
        "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
    })
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--multiturn"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                out = {"warm_ttft_speedup": r.get("warm_ttft_speedup"),
                       "hit_rate": r.get("cache_on", {}).get("hit_rate"),
                       "greedy_match": r.get("greedy_match"),
                       "warm_ms_on": round(r.get("cache_on", {}).get(
                           "p50_ttft_warm_ms", 0.0), 1),
                       "warm_ms_off": round(r.get("cache_off", {}).get(
                           "p50_ttft_warm_ms", 0.0), 1)}
                _kv_pick(out, r)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    partial.update({f"multiturn_{k}": v for k, v in out.items()})
    _emit_phase("multiturn_prefix_cache", out)
    return out


def _engine_direct_offload(deadline: float, partial: dict) -> dict:
    """The PR-3 acceptance scenario as a default-bench phase: multi-turn
    under FORCED POOL PRESSURE (device pool ~half the working set),
    kv_offload on vs off, engine-direct in a subprocess — warm turns
    behind an eviction restore from host RAM instead of re-prefilling."""
    import subprocess

    mt_preset = os.environ.get("LOCALAI_BENCH_MT_PRESET", "smoke")
    hp = HTTP_PRESETS.get(mt_preset, HTTP_PRESETS["smoke"])
    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": mt_preset,
        "LOCALAI_BENCH_CTX": str(hp["ctx"]),
        "LOCALAI_BENCH_QUANT": hp.get("quant", ""),
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
    })
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--multiturn",
             "--pressure"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                out = {"warm_ttft_speedup": r.get("warm_ttft_speedup"),
                       "greedy_match": r.get("greedy_match"),
                       "restores": r.get("offload_on", {}).get("restores"),
                       "warm_ms_on": round(r.get("offload_on", {}).get(
                           "p50_ttft_warm_ms", 0.0), 1),
                       "warm_ms_off": round(r.get("offload_off", {}).get(
                           "p50_ttft_warm_ms", 0.0), 1)}
                _kv_pick(out, r)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    partial.update({f"kv_offload_pressure_{k}": v for k, v in out.items()})
    _emit_phase("kv_offload_pressure", out)
    return out


def _engine_direct_decomp(deadline: float, partial: dict,
                          emitter: bool = True) -> dict:
    """Host-vs-device walltime decomposition as a bench phase: a short
    engine-direct serving run (subprocess, trace ring on) whose output
    carries the span tracer's measured split — host loop (dispatch +
    detok + flush), device compute, emitter-thread time, finish-
    detection lag — plus the per-request TTFT span breakdown. This is
    the measured answer to the r5 serving-vs-kernel gap question
    (scripts/ci.sh prints it as the HOST_LOOP_MS/DEVICE_MS/
    FINISH_DETECT_MS tracked line, for BOTH emitter settings).
    ``emitter=False`` reruns with the in-loop emission path (ISSUE 9
    before/after comparison)."""
    import subprocess

    remaining = deadline - time.monotonic()
    if remaining < 30:
        return {"error": "budget exhausted"}
    env = dict(os.environ)
    env.update({
        "LOCALAI_BENCH_PRESET": "smoke",
        "LOCALAI_BENCH_CTX": str(HTTP_PRESETS["smoke"]["ctx"]),
        "LOCALAI_BENCH_SLOTS": os.environ.get("LOCALAI_BENCH_SLOTS", "2"),
        "LOCALAI_BENCH_PROMPT": "32",
        "LOCALAI_BENCH_NEW": "24",
        "LOCALAI_BENCH_TOKENS": "192",
        "LOCALAI_BENCH_QUANT": "",
        "LOCALAI_BENCH_BUDGET_S": "0",   # parent watchdog governs
        "LOCALAI_BENCH_DEADLINE_S": "0",
        "LOCALAI_BENCH_EMITTER": "" if emitter else "0",
    })
    platform = _subprocess_jax_platform(deadline)
    if platform:
        env["JAX_PLATFORMS"] = platform
    else:
        env.pop("JAX_PLATFORMS", None)
    out = {}
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--engine"],
            env=env, capture_output=True, text=True,
            timeout=max(30, min(remaining - 10, 1800)))
        for ln in res.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                r = json.loads(ln)
                if "host_device_decomp_ms" in r:
                    out = {
                        "host_device_decomp_ms": r["host_device_decomp_ms"],
                        "span_breakdown_ms": r.get("span_breakdown_ms"),
                        "ttft_decomp_p50_ms": r.get("ttft_decomp_p50_ms"),
                        "tok_s": r.get("value"),
                        "compiles_after_warmup": r.get(
                            "compiles_after_warmup"),
                        "peak_pool_pages": r.get("peak_pool_pages"),
                        "mfu": r.get("mfu"),
                        "cold_bucket": r.get("cold_bucket"),
                    }
                    _kv_pick(out, r)
        if not out:
            out = {"error": (f"rc={res.returncode} "
                             f"stderr={res.stderr[-200:]}")}
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    tag = "" if emitter else "_off"
    partial.update({f"decomp{tag}_{k}": v for k, v in out.items()})
    _emit_phase(f"host_device_decomp{tag}", out)
    return out


def main():
    prompt_len = int(os.environ.get("LOCALAI_BENCH_PROMPT", "128"))
    max_new = int(os.environ.get("LOCALAI_BENCH_NEW", "128"))
    # default sized so the 8B HTTP measurement finishes in ~8 min
    target = int(os.environ.get("LOCALAI_BENCH_TOKENS", "4096"))

    partial = {}
    deadline = _arm_budget_watchdog(partial)
    global _GLOBAL_DEADLINE
    _GLOBAL_DEADLINE = deadline

    if ("--engine" in sys.argv or "--kernel" in sys.argv
            or "--multiturn" in sys.argv or "--packed-prefill" in sys.argv
            or "--chaos" in sys.argv or "--priority" in sys.argv
            or "--slo" in sys.argv or "--spec" in sys.argv
            or "--replicas" in sys.argv or "--longcontext" in sys.argv
            or "--cluster" in sys.argv or "--autoscale" in sys.argv):
        # engine-direct / kernel modes own the chip in-process
        from localai_tpu.utils.jaxtools import enable_compilation_cache

        enable_compilation_cache()
        preset = os.environ.get("LOCALAI_BENCH_PRESET", "1b")
        from localai_tpu.models import llama
        cfg = llama.LlamaConfig(max_position_embeddings=2048, **PRESETS[preset])

        S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "32"))
        C = int(os.environ.get("LOCALAI_BENCH_CTX", "1024"))

        if "--multiturn" in sys.argv:
            # multi-turn shared-prefix scenario with forced slot churn:
            # few slots, more conversations. Defaults scale with the
            # context so the K-turn histories always fit without a shift.
            # --pressure additionally squeezes the device pool to ~half
            # the working set and flips the on/off axis to kv_offload
            # (PR 3 acceptance: restore-from-host vs re-prefill); its
            # longer system prompt makes the re-prefill cost visible.
            pressure = "--pressure" in sys.argv
            import jax.numpy as jnp

            # float32 weights for BOTH multiturn scenarios: the greedy
            # byte-parity gate compares fresh-vs-continued prefill
            # programs, and bf16 rounding flips argmax between
            # equal-value candidates across differently shaped programs
            # (the packed-prefill continued path made one such tie land
            # in the default schedule; see bench_multiturn parity note)
            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            if pressure:
                # context >= 256 so the re-prefill being avoided is big
                # enough to dominate fixed per-request overhead
                C = max(C, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                        or 256, 256)
            mt = {k: int(os.environ["LOCALAI_BENCH_MT_" + k.upper()])
                  if "LOCALAI_BENCH_MT_" + k.upper() in os.environ else v
                  for k, v in dict(
                      slots=2, convs=6, turns=3,
                      sys=max(32, C // 2 if pressure else C // 4),
                      user=max(8, C // 24), new=max(8, C // 24)).items()}
            # keep the final history inside the context window
            assert mt["sys"] + mt["turns"] * (mt["user"] + mt["new"]) < C - 1
            r = bench_multiturn(cfg, mt["slots"], C, mt["convs"],
                                mt["turns"], mt["sys"], mt["user"],
                                mt["new"], pressure=pressure)
            print(json.dumps({
                "metric": (f"multiturn_kv_offload_{preset}" if pressure
                           else f"multiturn_prefix_cache_{preset}"),
                "value": r["warm_ttft_speedup"], "unit": "x warm-turn TTFT",
                **r,
            }))
            return

        if "--packed-prefill" in sys.argv:
            # packed-vs-sequential prompt ingestion (ISSUE 4 acceptance):
            # f32 weights for byte-exact greedy across the two program
            # shapes (see bench_packed_prefill)
            import jax.numpy as jnp

            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "4"))
            C = max(128, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 256)
            r = bench_packed_prefill(cfg, S, C)
            # long-prompt phase (ISSUE 11): >1k-token packs stay on the
            # kernel plan with zero shape fallbacks, byte-identical
            r["longpack"] = bench_packed_longpack(cfg, S)
            print(json.dumps({
                "metric": f"packed_prefill_{preset}",
                "value": r["ttft_speedup"], "unit": "x loaded TTFT",
                **r,
            }))
            return

        if "--chaos" in sys.argv:
            # fault-lifecycle SLO (ISSUE 7): f32 weights so the
            # post-stall recovery request can be byte-compared against
            # the pre-fault greedy baseline
            import jax.numpy as jnp

            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "2"))
            C = max(96, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 128)
            r = bench_chaos(cfg, S, C)
            ok = (r.get("recovered") == 1 and r.get("shed", 0) >= 1
                  and r.get("unstructured", 0) == 0
                  and r.get("shed_under_50ms") is True)
            print(json.dumps({
                "metric": f"chaos_{preset}", "value": 1 if ok else 0,
                "unit": "ok", **r,
            }))
            return

        if "--priority" in sys.argv:
            # preemptive priority scheduler (ISSUE 10): f32 weights so
            # the resume byte-match gate can compare the paused
            # request's continuation against a fresh re-admission
            import jax.numpy as jnp

            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "2"))
            C = max(96, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 128)
            r = bench_priority(cfg, S, C)
            ok = (r.get("ttft_ratio") is not None
                  and r.get("ttft_ratio") >= 2.0
                  and r.get("preemptions", 0) >= 1
                  and r.get("low_complete") is True
                  and r.get("resume_byte_match") is True)
            print(json.dumps({
                "metric": f"priority_{preset}",
                "value": r.get("ttft_ratio"), "unit": "x high-prio TTFT",
                "ok": 1 if ok else 0, **r,
            }))
            return

        if "--spec" in sys.argv:
            # speculative decoding (ISSUE 13): f32 weights so the greedy
            # byte gate compares the spec tick against the plain burst
            # across two differently shaped programs
            import jax.numpy as jnp

            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "2"))
            C = max(96, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 128)
            r = bench_spec(cfg, S, C)
            ok = (r.get("accept_per_dispatch") is not None
                  and r.get("accept_per_dispatch") > 1.0
                  and r.get("byte_match") is True
                  and (r.get("sampled_accept_per_dispatch") or 0) > 1.0
                  and r.get("sampled_dist_ok") is True)
            print(json.dumps({
                "metric": f"spec_{preset}",
                "value": r.get("accept_per_dispatch"),
                "unit": "tok/dispatch", "ok": 1 if ok else 0, **r,
            }))
            return

        if "--replicas" in sys.argv:
            # engine replica pool (ISSUE 14): f32 weights so the
            # migration / crash-recovery byte gates can compare the
            # continued stream against a fresh pool re-admission
            import jax.numpy as jnp

            rp = dict(PRESETS[preset])
            if preset == "smoke":
                # the smoke model is small enough that a padded-bucket
                # prefill costs LESS than restoring the same pages from
                # the host tier, so the warm-vs-cold compare would
                # measure path overhead, not the skipped prefill. Scale
                # compute up for this scenario only: prefill FLOPs grow
                # ~quadratically with hidden size, restore bytes only
                # linearly, putting the rig in the regime the shared
                # tier exists for (still CPU-safe).
                rp.update(hidden_size=384, intermediate_size=1024,
                          num_layers=4, num_heads=8, num_kv_heads=8,
                          head_dim=48)
            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **rp)
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "1"))
            C = max(96, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 128)
            r = bench_replicas(cfg, S, C)
            ok = (r.get("affinity_hits", 0) >= 1
                  and r.get("affinity_byte_match") is True
                  and r.get("warm_beats_cold") is True
                  and r.get("migrate_byte_match") is True
                  and r.get("recovered") is True)
            print(json.dumps({
                "metric": f"replicas_{preset}", "value": 1 if ok else 0,
                "unit": "ok", "ok": 1 if ok else 0, **r,
            }))
            return

        if "--autoscale" in sys.argv:
            # SLO-driven replica autoscaling + predictive weight
            # prefetch (ISSUE 19): f32 weights so the scale-in
            # live-migration byte gate compares the continued stream
            # against a fresh pool re-admission deterministically
            import jax.numpy as jnp

            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "2"))
            # 512 so the phase-3 rider decodes long enough to stay
            # in flight across BOTH idle scale-ins (3 -> 2 -> 1): its
            # final migration must land on the surviving replica for
            # the byte gate's reference splice
            C = max(512, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 512)
            r = bench_autoscale(cfg, S, C)
            ok = (r.get("sheds_without_autoscale", 0) >= 1
                  and r.get("pre_shed") is True
                  and r.get("scale_out_events", 0) >= 1
                  and r.get("scale_in_events", 0) >= 1
                  and r.get("flaps") == 0
                  and r.get("slow_stream_degraded") is True
                  and r.get("slow_stream_stall_free") is True
                  and r.get("byte_gate_ok") is True
                  and (r.get("swap_ratio") or 0) >= 2.0)
            print(json.dumps({
                "metric": f"autoscale_{preset}", "value": 1 if ok else 0,
                "unit": "ok", "ok": 1 if ok else 0, **r,
            }))
            return

        if "--cluster" in sys.argv:
            # cross-host KV federation (ISSUE 17): f32 weights so the
            # cross-host stream / crash-recovery / disagg byte gates
            # compare the continued stream against a fresh re-admission
            # on the adopting host deterministically
            import jax.numpy as jnp

            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "2"))
            C = max(128, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 128)
            r = bench_cluster(cfg, S, C)
            ok = (r.get("kv_stream_hits", 0) >= 1
                  and r.get("stream_byte_match") is True
                  and r.get("disagg_byte_match") is True
                  and r.get("recovered") is True
                  and r.get("proc_recovered") is True
                  and r.get("drain_byte_match") is True
                  and r.get("slow_not_killed") is True
                  and r.get("kv_audit_violations") == 0)
            print(json.dumps({
                "metric": f"cluster_{preset}", "value": 1 if ok else 0,
                "unit": "ok", "ok": 1 if ok else 0, **r,
            }))
            return

        if "--slo" in sys.argv:
            # per-class SLO burn + flight recorder (ISSUE 12): a tight
            # low-class TTFT objective must burn and dump, a loose
            # high-class one must stay clean, and the request id must
            # survive into one merged two-pid trace
            import jax.numpy as jnp

            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "2"))
            C = max(96, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 128)
            r = bench_slo(cfg, S, C)
            ok = (r.get("burn_5m_low") is not None
                  and r.get("burn_5m_low") > 1.0
                  and r.get("burn_5m_high") == 0.0
                  and r.get("violations_low", 0) >= 1
                  and r.get("violations_high") == 0
                  and r.get("violation_events", 0) >= 1
                  and r.get("flight_dumps", 0) >= 1
                  and r.get("flight_dump_low") is True
                  and r.get("trace_merged") == 1)
            print(json.dumps({
                "metric": f"slo_{preset}", "value": 1 if ok else 0,
                "unit": "ok", **r,
            }))
            return

        if "--longcontext" in sys.argv:
            # long-context serving tier (ISSUE 16): f32 weights so the
            # short-prompt byte gate (window machinery invisible until
            # the policy engages) compares deterministically across the
            # windowed / unwindowed engines
            import jax.numpy as jnp

            cfg = llama.LlamaConfig(max_position_embeddings=2048,
                                    dtype=jnp.float32, **PRESETS[preset])
            S = int(os.environ.get("LOCALAI_BENCH_SLOTS", "2"))
            C = max(256, int(os.environ.get("LOCALAI_BENCH_CTX", "0"))
                    or 512)
            r = bench_longcontext(cfg, S, C)
            ok = (r.get("prefetch_late") == 0
                  and (r.get("prefetch_hits") or 0) >= 1
                  and r.get("short_byte_match") is True
                  and (r.get("offloaded_pages") or 0) >= 1)
            print(json.dumps({
                "metric": f"longcontext_{preset}", "value": 1 if ok else 0,
                "unit": "ok", "ok": 1 if ok else 0, **r,
            }))
            return

        if "--kernel" in sys.argv:
            steps = int(os.environ.get("LOCALAI_BENCH_STEPS", "128"))
            inner = int(os.environ.get("LOCALAI_BENCH_INNER", "16"))
            r = bench_kernel(cfg, S, C, steps, inner)
            qtag = "int8" if os.environ.get("LOCALAI_BENCH_QUANT", "") == "int8" else "bf16"
            print(json.dumps({
                "metric": f"kernel_decode_tok_s_per_chip_llama_{preset}_{qtag}_slots{S}",
                "value": round(r["tok_s"], 1), "unit": "tok/s",
                "vs_baseline": round(r["tok_s"] / 2000.0, 3),
            }))
            return

        # 0/unset = engine default (EngineConfig.decode_burst)
        burst = int(os.environ.get("LOCALAI_BENCH_BURST") or 0)
        r = bench_serving(cfg, S, C, prompt_len, max_new, target, burst)
        gtag = "_grammar" if os.environ.get("LOCALAI_BENCH_GRAMMAR", "") == "1" else ""
        ltag = (f"_{r['kv_layout']}" if r.get("kv_layout") else "")
        print(json.dumps({
            "metric": (f"engine_tok_s_per_chip_llama_{preset}_"
                       f"{'int8' if os.environ.get('LOCALAI_BENCH_QUANT', '') == 'int8' else 'bf16'}"
                       f"_slots{S}{gtag}{ltag}"),
            "value": round(r["tok_s"], 1), "unit": "tok/s",
            "vs_baseline": round(r["tok_s"] / 2000.0, 3),
            "p50_ttft_ms": round(r["p50_ttft_ms"], 1),
            "p95_ttft_ms": round(r["p95_ttft_ms"], 1),
            "unloaded_ttft_ms": round(r["unloaded_ttft_ms"], 1),
            **({"ttft_decomp_p50_ms": r["ttft_decomp_p50_ms"]}
               if "ttft_decomp_p50_ms" in r else {}),
            **({"host_device_decomp_ms": r["host_device_decomp_ms"]}
               if "host_device_decomp_ms" in r else {}),
            **({"span_breakdown_ms": r["span_breakdown_ms"]}
               if "span_breakdown_ms" in r else {}),
            # sysobs (ISSUE 8): compile hygiene + pool peak + MFU +
            # the cold-bucket detection probe
            "compiles_after_warmup": r.get("compiles_after_warmup"),
            "peak_pool_pages": r.get("peak_pool_pages"),
            "mfu": r.get("mfu"),
            "cold_bucket": r.get("cold_bucket"),
            # end-of-phase KV audit sweep (ISSUE 15): both must be 0
            "kv_audit_violations": r.get("kv_audit_violations"),
            "kv_leaked_pages": r.get("kv_leaked_pages"),
        }))
        return

    if "--smoke" in sys.argv:
        # CI harness check (scripts/ci.sh): the cheap engine-direct
        # phases only — layout compare, packed-prefill TTFT compare,
        # prefix-cache multiturn, offload-under-pressure multiturn — no
        # HTTP stack, no big presets.
        # rc=0 iff every phase produced a result and greedy stayed
        # byte-identical; always ends in one JSON line.
        import jax

        jax.config.update("jax_platforms", "cpu")
        layout_cmp = _engine_direct_layout_compare(deadline, partial)
        packed = _engine_direct_packed(deadline, partial)
        multiturn = _engine_direct_multiturn(deadline, partial)
        offload = _engine_direct_offload(deadline, partial)
        decomp = _engine_direct_decomp(deadline, partial)
        # in-loop emission rerun (ISSUE 9): the before/after pair
        # scripts/ci.sh gates on — finish_detect(emitter on) must beat
        # the polled in-loop path
        decomp_off = _engine_direct_decomp(deadline, partial, emitter=False)
        # per-class SLO burn + flight recorder + merged trace (ISSUE 12,
        # scripts/ci.sh SLO_BURN_5M/SLO_VIOLATIONS/TRACE_MERGED line)
        slo = _engine_direct_slo(deadline, partial)
        # speculative decoding (ISSUE 13, scripts/ci.sh
        # SPEC_ACCEPT_PER_DISPATCH/SPEC_BYTE_MATCH line): n-gram
        # self-speculation must beat 1.0 accepted-tokens-per-dispatch
        # and stay byte-identical to speculation-off greedy
        spec = _engine_direct_spec(deadline, partial)
        # engine replica pool (ISSUE 14, scripts/ci.sh
        # REPLICA_AFFINITY_HITS/MIGRATE_BYTE_MATCH/REPLICA_RECOVERED
        # line): cross-replica affinity routing, live-migration byte
        # gate, kill-one-replica recovery via the shared host tier
        replicas = _engine_direct_replicas(deadline, partial)
        ok = ("paged_tok_s" in layout_cmp
              and packed.get("greedy_match") is True
              and multiturn.get("greedy_match") is True
              and offload.get("greedy_match") is True
              and "host_device_decomp_ms" in decomp
              and "host_device_decomp_ms" in decomp_off
              and slo.get("ok") == 1
              and spec.get("ok") == 1
              and replicas.get("ok") == 1)
        print(json.dumps({
            "metric": "bench_smoke", "value": 1 if ok else 0, "unit": "ok",
            "kv_layout_compare": layout_cmp,
            "packed_prefill": packed,
            # the tracked TTFT line (scripts/ci.sh greps this): loaded
            # p50 / unloaded floor under the packed scheduler
            "ttft_loaded_unloaded_ratio": packed.get(
                "ttft_loaded_unloaded_ratio"),
            "multiturn_prefix_cache": multiturn,
            "kv_offload_pressure": offload,
            # measured host-loop vs device-time split from the span
            # tracer (scripts/ci.sh HOST_LOOP_MS/... tracked line),
            # with the emitter on (default) and off (in-loop emission)
            "host_device_decomp": decomp,
            "host_device_decomp_off": decomp_off,
            # sysobs tracked numbers (ISSUE 8, scripts/ci.sh
            # COMPILES_AFTER_WARMUP/PEAK_POOL_PAGES/MFU line): compile
            # hygiene of the repeated-wave serving phase must be 0, and
            # the intentionally cold bucket must be detected
            "compiles_after_warmup": decomp.get("compiles_after_warmup"),
            "peak_pool_pages": decomp.get("peak_pool_pages"),
            "mfu": decomp.get("mfu"),
            "cold_bucket_detected": (decomp.get("cold_bucket")
                                     or {}).get("detected"),
            # SLO burn + flight recorder (ISSUE 12): the tight low class
            # must burn (>1) and dump; the loose high class must stay
            # clean; one request id under both pids of the merged trace
            "slo": slo,
            "slo_burn_5m": slo.get("burn_5m_low"),
            "slo_violations": slo.get("violations_low"),
            "trace_merged": slo.get("trace_merged"),
            # speculative decoding (ISSUE 13): accepted tokens per verify
            # dispatch with draft=ngram, byte parity vs speculation off;
            # ISSUE 18 adds the sampled wave (rejection acceptance) —
            # accept-per-dispatch must exceed 1.0 AND the chi-square test
            # must not distinguish spec-on from plain sampling
            "spec": spec,
            "spec_accept_per_dispatch": spec.get("accept_per_dispatch"),
            "spec_byte_match": spec.get("byte_match"),
            "spec_sampled_accept_per_dispatch": spec.get(
                "sampled_accept_per_dispatch"),
            "spec_sampled_dist_ok": spec.get("sampled_dist_ok"),
            # engine replica pool (ISSUE 14): affinity must hit on the
            # warm resubmission, migration and crash recovery must stay
            # byte-identical to a fresh pool re-admission
            "replicas": replicas,
            "replica_affinity_hits": replicas.get("affinity_hits"),
            "migrate_byte_match": replicas.get("migrate_byte_match"),
            "replica_recovered": replicas.get("recovered"),
            # KV lifecycle auditor (ISSUE 15, scripts/ci.sh
            # KV_AUDIT_VIOLATIONS/KV_LEAKED_PAGES line): every phase
            # above ends with a full audit sweep; the summed totals
            # across all of them must be 0/0
            **_kv_pick({"kv_audit_violations": 0, "kv_leaked_pages": 0},
                       layout_cmp, packed, multiturn, offload, decomp,
                       decomp_off, slo, spec, replicas),
        }))
        sys.exit(0 if ok else 1)

    # DEFAULT: the BASELINE.json metric — /v1/chat/completions over real
    # HTTP with SSE, on the 8B (north-star model) preset. The parent
    # process pins itself to the CPU platform (config, not env — the
    # spawned backend must still see the chip). Add presets via
    # LOCALAI_BENCH_PRESETS=8b,1b.
    import jax

    jax.config.update("jax_platforms", "cpu")
    # CHEAPEST phases first, so the budget watchdog can never starve
    # them (each phase reports incrementally on stderr and folds into
    # the watchdog's partial line): decode tok/s for the paged vs
    # contiguous KV layouts, the packed-prefill TTFT compare, the
    # multi-turn prefix-cache scenario, and the offload-under-pressure
    # scenario, engine-direct on small presets (identical either side)
    layout_cmp = _engine_direct_layout_compare(deadline, partial)
    packed_cmp = _engine_direct_packed(deadline, partial)
    multiturn = _engine_direct_multiturn(deadline, partial)
    offload_cmp = _engine_direct_offload(deadline, partial)
    chaos_cmp = _engine_direct_chaos(deadline, partial)
    priority_cmp = _engine_direct_priority(deadline, partial)
    slo_cmp = _engine_direct_slo(deadline, partial)
    spec_cmp = _engine_direct_spec(deadline, partial)
    replicas_cmp = _engine_direct_replicas(deadline, partial)
    presets = os.environ.get("LOCALAI_BENCH_PRESETS", "8b").split(",")
    presets = [p.strip() for p in presets if p.strip()]
    results = {}
    errors = {}
    for p in presets:
        if deadline - time.monotonic() < 60:
            errors[p] = "skipped: bench budget exhausted"
            continue
        try:
            results[p] = bench_http(p, prompt_len, max_new, target)
            partial[f"{p}_tok_s"] = round(results[p]["tok_s"], 1)
            _emit_phase(f"http_{p}",
                        {"tok_s": round(results[p]["tok_s"], 1),
                         "p50_ttft_ms": round(results[p]["p50_ttft_ms"], 1)})
        except Exception as e:  # report what ran; a preset OOM shouldn't
            errors[p] = f"{type(e).__name__}: {e}"  # zero the whole bench
            _emit_phase(f"http_{p}", {"error": errors[p][:200]})
    if not results:
        line = {"metric": "http_chat_tok_s_per_chip", "value": None,
                "unit": "tok/s",
                "kv_layout_compare": layout_cmp,
                "packed_prefill": packed_cmp,
                "multiturn_prefix_cache": multiturn,
                "kv_offload_pressure": offload_cmp,
                "chaos": chaos_cmp,
                "priority": priority_cmp,
                "slo": slo_cmp,
                "spec": spec_cmp,
                "replicas": replicas_cmp,
                "errors": {p: e[:200] for p, e in errors.items()}}
        print(json.dumps(line))
        return
    primary = "8b" if "8b" in results else sorted(results)[0]
    r = results[primary]
    # effective config = preset value unless env-overridden (bench_http
    # honors the same overrides; labels and the engine-direct subprocess
    # must describe what actually ran)
    eff_kv = os.environ.get("LOCALAI_BENCH_KV",
                            HTTP_PRESETS[primary].get("kv", ""))
    qtag = "int8" if HTTP_PRESETS.get(primary, {}).get("quant") == "int8" else "bf16"
    kvtag = "kvint8" if eff_kv == "int8" else ""

    # engine-direct same-preset measurement in a FRESH subprocess (the
    # HTTP backend subprocess released the chip when the loader stopped):
    # makes the HTTP-path overhead computable on the 8B (VERDICT r4 #2 —
    # r4 published engine-direct numbers for the 1b only)
    engine_direct = None
    engine_direct_err = None
    if os.environ.get("LOCALAI_BENCH_DIRECT", "1") != "0":
        import subprocess

        env = dict(os.environ)
        env.update({
            "LOCALAI_BENCH_PRESET": primary,
            "LOCALAI_BENCH_SLOTS": str(int(os.environ.get(
                "LOCALAI_BENCH_SLOTS", HTTP_PRESETS[primary]["slots"]))),
            "LOCALAI_BENCH_CTX": str(HTTP_PRESETS[primary]["ctx"]),
            "LOCALAI_BENCH_QUANT": HTTP_PRESETS[primary]["quant"],
            "LOCALAI_BENCH_KV": eff_kv,
            # the PARENT watchdog + subprocess timeout govern the child
            # (BENCH_r05 wedge fix: a child re-arming the full budget
            # outlived the parent's deadline and timed the bench out)
            "LOCALAI_BENCH_BUDGET_S": "0",
            "LOCALAI_BENCH_DEADLINE_S": "0",
        })
        # forward the burst only when one is actually specified, so an
        # unset knob means "engine default" in BOTH phases (no third
        # hardcoded copy of the default)
        eff_burst = int(os.environ.get("LOCALAI_BENCH_BURST")
                        or HTTP_PRESETS[primary].get("burst", 0) or 0)
        if eff_burst > 0:
            env["LOCALAI_BENCH_BURST"] = str(eff_burst)
        else:
            env.pop("LOCALAI_BENCH_BURST", None)
        env.pop("JAX_PLATFORMS", None)
        # the HTTP backend subprocess can take a few seconds to exit and
        # release the chip; "UNAVAILABLE: TPU backend setup" here means
        # we raced it — wait and retry
        for attempt in range(3):
            engine_direct_err = None
            # deadline-aware timeout (BENCH_r05 wedge fix): the default
            # flow must respect the shrinking remaining budget end to
            # end, not park up to an hour past the parent's deadline
            remaining = deadline - time.monotonic()
            if remaining < 60:
                engine_direct_err = "skipped: bench budget exhausted"
                break
            try:
                if attempt:
                    time.sleep(15)
                out = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), "--engine"],
                    env=env, capture_output=True, text=True,
                    timeout=max(60, min(remaining - 10, 3600)))
                for ln in out.stdout.splitlines():
                    ln = ln.strip()
                    if ln.startswith("{"):
                        engine_direct = json.loads(ln)
                if engine_direct is None:
                    engine_direct_err = (f"rc={out.returncode} "
                                         f"stderr={out.stderr[-300:]}")
            except Exception as e:
                engine_direct_err = f"{type(e).__name__}: {e}"
            if engine_direct is not None or (
                    engine_direct_err and "UNAVAILABLE" not in engine_direct_err):
                break
        if engine_direct_err:
            print(f"engine-direct subprocess failed: {engine_direct_err}",
                  file=sys.stderr)
    # BASELINE.json's north star is >2000 tok/s AGGREGATE on a v5e-8 for
    # Llama-3.1-8B on /v1/chat/completions = 250 tok/s/chip; this bench
    # measures tokens/sec/chip on one chip, so vs_baseline compares
    # per-chip rates (request-level dp across 8 chips scales linearly)
    per_chip_target = 250.0 if primary == "8b" else 2000.0
    line = {
        "metric": (f"http_chat_tok_s_per_chip_llama_{primary}_{qtag}{kvtag}_slots"
                   f"{int(os.environ.get('LOCALAI_BENCH_SLOTS', HTTP_PRESETS[primary]['slots']))}"),
        "value": round(r["tok_s"], 1), "unit": "tok/s",
        "vs_baseline": round(r["tok_s"] / per_chip_target, 3),
        "baseline_note": ("north_star 2000 tok/s aggregate on v5e-8 = "
                          "250 tok/s/chip" if primary == "8b" else
                          "vs 2000 tok/s"),
        "n_runs": r.get("n_runs", 1),
        "tok_s_min": round(r.get("tok_s_min", r["tok_s"]), 1),
        "tok_s_max": round(r.get("tok_s_max", r["tok_s"]), 1),
        "p50_ttft_ms": round(r["p50_ttft_ms"], 1),
        "p95_ttft_ms": round(r["p95_ttft_ms"], 1),
        "unloaded_ttft_ms": round(r["unloaded_ttft_ms"], 1),
        # loaded-vs-idle TTFT — the packed-prefill tracked ratio on the
        # full HTTP path (r04 bucketed path: 1130 / 402 = 2.8x)
        "ttft_loaded_unloaded_ratio": round(
            r["p50_ttft_ms"] / r["unloaded_ttft_ms"], 3)
        if r.get("unloaded_ttft_ms") else None,
        "weights_note": ("random weights via gated loader fallback "
                         "(no-egress rig); compute path identical to a "
                         "real checkpoint"),
        "packed_prefill": packed_cmp,
        "multiturn_prefix_cache": multiturn,
        "kv_offload_pressure": offload_cmp,
        "chaos": chaos_cmp,
        "priority": priority_cmp,
        "slo": slo_cmp,
        "spec": spec_cmp,
        "replicas": replicas_cmp,
    }
    if engine_direct is not None:
        line["engine_direct_tok_s"] = engine_direct.get("value")
        if engine_direct.get("value"):
            line["http_vs_engine_direct_pct"] = round(
                100.0 * r["tok_s"] / engine_direct["value"], 1)
    elif engine_direct_err:
        line["engine_direct_error"] = engine_direct_err[:200]
    for p, rr in results.items():
        if p != primary:
            line[f"{p}_tok_s"] = round(rr["tok_s"], 1)
            line[f"{p}_p50_ttft_ms"] = round(rr["p50_ttft_ms"], 1)
            line[f"{p}_p95_ttft_ms"] = round(rr["p95_ttft_ms"], 1)
    for p, err in errors.items():
        line[f"{p}_error"] = err[:200]
    print(json.dumps(line))


def _main_unwedgeable():
    """main() with the ANY-failure contract: whatever dies (bad preset,
    boot hang turned exception, OOM, a wedged device raising), stdout
    still ends with ONE parseable JSON line carrying an ``error`` field
    — ``parsed`` must never be null (verdict r05 #1). SystemExit passes
    through (the modes use exit codes deliberately)."""
    try:
        main()
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001 - the contract IS catch-all
        print(json.dumps({
            "metric": "bench_failed",
            "error": f"{type(e).__name__}: {e}"[:500],
        }), flush=True)
        sys.exit(0)


if __name__ == "__main__":
    _main_unwedgeable()
