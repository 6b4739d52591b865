"""The TPU engine served over the backend contract.

This is the process spawned per model by the model manager — the analogue
of the reference's llama.cpp gRPC server binary (reference:
backend/cpp/llama/grpc-server.cpp:2503-2541 main, --addr flag), with the
slot machinery replaced by localai_tpu.engine.

Run: python -m localai_tpu.backend.runner --addr 127.0.0.1:PORT
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import grpc
import numpy as np

from localai_tpu.backend import contract_pb2 as pb
from localai_tpu.backend.service import (BackendServicer, RpcPool,
                                         make_server, parse_options)
from localai_tpu.engine.gguf import find_gguf
from localai_tpu.services import sysobs

log = logging.getLogger("localai_tpu.backend.runner")

# config.json model_type -> how tpu-llm serves it: the dense Llama block
# (models/llama.py), or a family module localai_tpu/models/<module>.py and
# its config class
_LLAMA_TYPES = frozenset({"llama", "mistral", "qwen2", "qwen", "gemma",
                          "phi3"})
_FAMILIES = {"mamba": ("mamba", "MambaConfig"),
             "rwkv": ("rwkv", "RwkvConfig"),
             "olmo_hybrid": ("olmo_hybrid", "OlmoHybridConfig"),
             "granitemoehybrid": ("granite_hybrid", "GraniteHybridConfig"),
             "lfm2_moe": ("lfm2_moe", "Lfm2MoeConfig"),
             "ling_hybrid": ("ling_hybrid", "LingHybridConfig"),
             "xing4_0": ("xing4", "Xing4Config")}

# options that ask for the host tier under the page pool or for what stands
# on it (engine/kv_offload.py, the snap-back window, services/kv_wire.py)
_HOST_TIER_OPTIONS = ("kv_offload", "kv_host_store", "kv_host_pool_mb",
                      "kv_window_pages", "kv_serve", "kv_peers")

# Threads of the runner's gRPC server. A streaming request holds one for
# its whole life (waiting on the engine's queue, then on its tokens), so
# this caps the requests the ENGINE can see at once: its slots and its own
# queue, whose scheduler, priorities and queue_wait spans cannot act on a
# request still parked in gRPC's. The pool is built before LoadModel says
# how many slots there are, at the contract's default; a model with more
# slots than that raises it there: at 16 a model of 48 slots decoded 15 at
# a time, its other slots empty behind a 35 s time to first token (PERF.md
# section 6, PR 36). A model of up to 16 slots keeps the 16 it was measured
# with: what its engine sees of the queue is part of its cells' traffic.
RPC_WORKERS = 16
RPC_WORKERS_MANY_SLOTS = 256

# engine lifecycle failure kinds -> gRPC status codes, so the core can
# distinguish shed (retry later) from timeout from stall without parsing
# message strings (services/errors.py maps them back to HTTP 429/504/503)
_EVENT_STATUS = {
    "shed": grpc.StatusCode.RESOURCE_EXHAUSTED,
    "timeout": grpc.StatusCode.DEADLINE_EXCEEDED,
    "stall": grpc.StatusCode.ABORTED,
}


def _abort_event(context, ev):
    """Abort the RPC for an engine error event with the kind-mapped
    status code; the engine's Retry-After hint rides trailing metadata
    (the hand-rolled stubs cannot grow proto fields)."""
    if ev.retry_after_s:
        context.set_trailing_metadata(
            (("localai-retry-after", f"{ev.retry_after_s:g}"),))
    context.abort(_EVENT_STATUS.get(ev.error_kind, grpc.StatusCode.INTERNAL),
                  ev.error)


def _sampling_from_predict(opts: pb.PredictOptions):
    from localai_tpu.engine.sampling import SamplingParamsHost

    return SamplingParamsHost(
        temperature=opts.temperature,
        top_k=opts.top_k,
        top_p=opts.top_p if opts.top_p > 0 else 1.0,
        min_p=opts.min_p,
        typical_p=opts.typical_p if opts.typical_p > 0 else 1.0,
        repeat_penalty=opts.repeat_penalty if opts.repeat_penalty > 0 else 1.0,
        # llama.cpp semantics: -1 = whole context (capped at the ring size
        # here), 0/unset = default 64 (proto3 cannot distinguish explicit 0)
        repeat_last_n=(opts.repeat_last_n if opts.repeat_last_n > 0
                       else -1 if opts.repeat_last_n < 0 else 64),
        presence_penalty=opts.presence_penalty,
        frequency_penalty=opts.frequency_penalty,
        mirostat=opts.mirostat,
        mirostat_tau=opts.mirostat_tau or 5.0,
        mirostat_eta=opts.mirostat_eta or 0.1,
        seed=opts.seed if opts.seed != 0 else -1,
        logit_bias={int(k): float(v) for k, v in opts.logit_bias.items()},
    )


def _read_tokenizer(tracer, gguf_path: Optional[str], tok_dir: str):
    """The tokenizer stage of a load (span ``load_tokenizer``): a GGUF
    file's own vocabulary, or the tokenizer files of ``tok_dir``. It needs
    nothing the weights produce and they need nothing of it, so ``_load``
    runs it on a thread of its own beside them and takes the future's
    result where the engine is first handed the tokenizer: the stage sets
    nothing on the servicer (a load that fails before the join leaves no
    half-set tokenizer), and its own failure is raised at the join as the
    exception it was."""
    with tracer.span("load_tokenizer", "load"):
        if gguf_path is not None:
            from localai_tpu.engine import gguf_tokenizer

            return gguf_tokenizer.from_gguf(gguf_path)
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(tok_dir)


class EngineServicer(BackendServicer):
    """LLM serving: LoadModel/Predict/PredictStream/Embedding/Tokenize/
    Status/GetMetrics on top of the continuous-batching engine."""

    def __init__(self, rpc_pool=None):
        from localai_tpu.services.tracing import RingTracer

        self.rpc_pool = rpc_pool   # service.RpcPool of the server, if any

        # the process's one span ring, from process start: LoadModel's
        # spans go in first, then the Engine is handed the same ring
        self.tracer = RingTracer()
        self.engine = None
        self.tokenizer = None
        self.model_cfg = None
        self.vision = None
        self.vision_cfg = None
        self.model_path = ""       # base dir for relative prompt-cache paths
        self._state = pb.StatusResponse.UNINITIALIZED
        self._load_lock = threading.Lock()
        self._embed = False
        self.kv_server = None      # ISSUE 17: KVWireServer when kv_serve=
        self.kv_fed = None         # ISSUE 17: FederatedKV when kv_peers=

    @staticmethod
    def _host_store_path(extra: dict, request) -> str:
        """kv_host_store=path option -> absolute persistence path for the
        offloaded-page store (engine/kv_offload.py); relative paths land
        next to the prompt caches under model_path."""
        p = str(extra.get("kv_host_store", "") or "")
        if not p:
            return ""
        if not os.path.isabs(p) and request.model_path:
            base = os.path.join(request.model_path, "prompt_cache")
            os.makedirs(base, exist_ok=True)
            p = os.path.join(base, p)
        return p

    @staticmethod
    def _sane_ga_w(extra: dict) -> int:
        n = max(1, int(extra.get("ga_n", 1) or 1))
        w = int(extra.get("ga_w", 512) or 512)
        w = max(w, n)
        return w - (w % n)   # divisible window: no shared block boundaries

    # ---- lifecycle ----

    def LoadModel(self, request: pb.ModelOptions, context) -> pb.Result:
        with self._load_lock:
            try:
                with self.tracer.span("load_model", "load",
                                      model=request.model):
                    self._load(request)
                sysobs.mark_warm()
                self._state = pb.StatusResponse.READY
                # clock handshake (ISSUE 12): Result.message carries this
                # process's wall/monotonic clocks and the tracer epoch so
                # the loader can measure the cross-process clock offset
                # that aligns merged /debug/trace timelines. The loader
                # tolerates a plain "loaded" from backends that don't
                # participate (fakes, external bridges).
                hs = {"status": "loaded",
                      "handshake": {
                          "wall": time.time(),
                          "mono": time.monotonic(),
                          "trace_epoch": self.tracer.t0_epoch,
                          "pid": os.getpid()}}
                return pb.Result(success=True, message=json.dumps(hs))
            except Exception as e:  # surface the error to the core
                self._state = pb.StatusResponse.ERROR
                log.exception("LoadModel failed")
                return pb.Result(success=False, message=f"{type(e).__name__}: {e}")

    def _load(self, request: pb.ModelOptions):
        model_dir = request.model
        if request.model_path and not os.path.isabs(model_dir):
            model_dir = os.path.join(request.model_path, model_dir)
        gguf_path = find_gguf(model_dir)
        # the tokenizer loads beside everything up to the engine's
        # construction (a GGUF file's own vocabulary unless the request
        # names a tokenizer directory)
        pool = ThreadPoolExecutor(1, "load-tokenizer")
        tokenizer = pool.submit(
            _read_tokenizer, self.tracer,
            None if request.tokenizer else gguf_path,
            request.tokenizer or model_dir)
        pool.shutdown(wait=False)   # the thread ends with its one task
        with self.tracer.span("load_imports", "load"):
            # a process's first load pays for importing jax and the
            # engine, and for jax reaching the chip
            import jax
            import jax.numpy as jnp

            from localai_tpu.engine import engine as eng
            from localai_tpu.engine import weights
            from localai_tpu.models import llama
            from localai_tpu.parallel import mesh as meshlib
            from localai_tpu.parallel import sharding as shardlib

            require_accelerator()
            # hear compiles from here on, whatever thread runs them: the
            # loader's own (before any Engine binds a tracker) are the
            # process record's unowned ones
            sysobs.install_listener()
        # the model's trace / trace_ring_size options, applied to the
        # process's ring before the load records into it
        extra = parse_options(request.options)
        self.tracer.configure(
            int(extra.get("trace_ring_size", 0) or 0) or self.tracer.size,
            enabled=str(extra.get("trace", "")).strip().lower()
            not in ("0", "false", "off", "no"))
        span = self.tracer.span
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}.get(
            request.dtype or "bfloat16", jnp.bfloat16
        )
        family = None
        if gguf_path is not None:
            # GGUF checkpoint (ollama://, oci:// or gallery pull): config
            # and tokenizer come from the file's own metadata
            from localai_tpu.engine import gguf as gguflib

            cfg = dataclasses.replace(
                gguflib.config_from_gguf(gguflib.open_gguf(gguf_path)),
                dtype=dtype)
        else:
            cfg_path = os.path.join(model_dir, "config.json")
            with open(cfg_path) as f:
                cfg_dict = json.load(f)
            mtype = cfg_dict.get("model_type", "")
            if mtype in _FAMILIES:
                # families beside the dense Llama block ride the same
                # engine slots through the family adapter: mamba / rwkv
                # with a fixed-size recurrent state in the cache lanes
                # (reference: backend/python/mamba, backend/go/llm/rwkv),
                # olmo_hybrid, granite_hybrid, lfm2_moe and ling_hybrid with
                # paged K/V (or latent) rows and a recurrent state,
                # xing4_0 with latent rows alone
                import importlib

                module, cfg_class = _FAMILIES[mtype]
                family = importlib.import_module(
                    "localai_tpu.models." + module)
                cfg = getattr(family, cfg_class).from_hf_config(
                    cfg_dict, dtype=dtype)
                if request.lora_adapter:
                    raise ValueError("LoRA adapters are llama-family only")
                if request.mmproj and "multimodal" not in family.CAPABILITIES:
                    raise ValueError(
                        f"mmproj: a vision tower's injection is not built "
                        f"for {mtype} (image parts are llama-family only)")
                if request.draft_model:
                    raise ValueError(
                        "speculative draft models are llama-family only")
                asked = [k for k in _HOST_TIER_OPTIONS if str(
                    extra.get(k, "")).strip().lower() not in (
                        "", "0", "false", "off", "no")]
                if asked and "prefix_reuse" in family.CAPABILITIES \
                        and "kv_offload" not in family.CAPABILITIES:
                    raise ValueError(
                        f"{asked[0]}: a host tier under {mtype}'s pool is "
                        "not built (kv_offload, the snap-back window and "
                        "the page wire hold a K and a V plane; this "
                        "family's pages are one latent plane)")
                if "ga_n" in (request.options or ""):
                    raise ValueError(
                        "self-extend (group_attn_n) is llama-family only")
                if request.quantization not in ("", "int8"):
                    # unknown schemes must fail loudly (and fast, before
                    # the weight load): silently serving full-precision
                    # weights would fake the memory savings
                    raise ValueError(
                        f"quantization={request.quantization!r} is not "
                        f"supported for {mtype} (only weight-only int8)")
            elif mtype and mtype not in _LLAMA_TYPES:
                raise ValueError(
                    f"model_type {mtype!r} is not served by tpu-llm: it "
                    f"knows {sorted(_LLAMA_TYPES)} (the dense Llama block) "
                    f"and {sorted(_FAMILIES)}")
            else:
                cfg = llama.LlamaConfig.from_hf_config(cfg_dict, dtype=dtype)

        # kv_cache_dtype (YAML -> capabilities.py:31 -> here): the memory
        # knob that buys batch — int8 KV halves the cache so slot count
        # can double on a bandwidth-bound chip (reference analogue:
        # llama.cpp cache-type-k q8_0 / vLLM kv_cache_dtype,
        # /root/reference/backend/python/vllm/backend.py:92-111).
        # Validated BEFORE the weight load so a bad knob fails fast.
        from localai_tpu.config.model_config import KV_CACHE_DTYPES

        kv_dt_name = (request.kv_cache_dtype or "bfloat16").lower()
        kv_dt_map = {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
                     "float16": jnp.float16, "f16": jnp.float16,
                     "float32": jnp.float32, "f32": jnp.float32,
                     "int8": jnp.int8, "q8_0": jnp.int8}
        assert set(kv_dt_map) == set(KV_CACHE_DTYPES)  # schema <-> runner sync
        if kv_dt_name not in kv_dt_map:
            raise ValueError(
                f"unknown kv_cache_dtype {kv_dt_name!r} "
                f"(one of {sorted(kv_dt_map)})")
        cache_dtype = kv_dt_map[kv_dt_name]
        if family is not None and cache_dtype == jnp.int8:
            # mamba/rwkv cache lanes hold recurrent STATE, not KV rows;
            # quantizing recurrent state accumulates error every step
            # (olmo_hybrid's full layers have rows, but no int8 form)
            raise ValueError(
                f"kv_cache_dtype {kv_dt_name!r} is llama-family only "
                f"(mamba/rwkv cache lanes carry recurrent state, kept "
                f"fp32); float dtypes are accepted as no-ops for these "
                f"families")
        if family is not None and "paged" not in family.CAPABILITIES:
            # float kv_cache_dtype values are NO-OPS for recurrent-state
            # families (their init_cache pins fp32 — SSM/wkv recurrences
            # are precision-sensitive) and the YAML validator accepts
            # them for any family: accept rather than fail a valid
            # config at load time (ADVICE r5, runner.py:201)
            cache_dtype = jnp.bfloat16

        n_dev = len(jax.devices())
        tp = request.mesh_tp or n_dev
        dp = request.mesh_dp or 1
        mesh = None
        if tp * dp > 1:
            mesh = meshlib.make_mesh(meshlib.MeshPlan(dp=dp, tp=tp),
                                     devices=jax.devices()[: tp * dp])
        if mesh is not None and "mesh" not in (family or llama).CAPABILITIES:
            raise ValueError(
                f"{family.__name__.rsplit('.', 1)[-1]} serves on one device: "
                "the family declares no sharding rule for its cache (set "
                f"mesh_tp: 1, not {tp})")
        lora_dir = request.lora_adapter
        if lora_dir and request.model_path and not os.path.isabs(lora_dir):
            lora_dir = os.path.join(request.model_path, lora_dir)
        # weight_prefetch=1 swaps the loader itself (ISSUE 19)
        stream_load = str(extra.get("weight_prefetch", "")
                          ).strip().lower() in ("1", "true", "on", "yes")
        stream_auto = str(extra.get("autoscale", "")
                          ).strip().lower() in ("1", "true", "on", "yes")
        self.weight_stream_stats = None
        if family is not None:
            # r5 (VERDICT r4 #7): mamba is no longer a single-chip
            # second-class citizen — weight-only int8 of the mixer
            # projections and Megatron-style tp over d_inner
            params = family.load_hf_params(
                model_dir, cfg, dtype=dtype,
                quantize=request.quantization or
                ("int8" if request.dtype == "int8" else ""),
                tracer=self.tracer)
            if mesh is not None and mtype == "mamba":
                from jax.sharding import PartitionSpec as P

                from localai_tpu.parallel import sharding as shardlib

                tp_size = mesh.shape.get("tp", 1)
                if tp_size > 1 and cfg.d_inner % tp_size == 0:
                    specs = shardlib.mamba_param_specs(
                        cfg.tie_word_embeddings)
                    if cfg.vocab_size % tp_size:
                        specs["embed"] = P(None, None)
                        if "lm_head" in specs:
                            specs["lm_head"] = P(None, None)
                    params = shardlib.shard_params(mesh, params, specs=specs)
        elif stream_load:
            # leaf-at-a-time streaming load (ISSUE 19): bounded host-RAM
            # chunks + per-leaf yields, so siblings serving in this
            # process keep their cadence while a swap/scale-out loads
            params, self.weight_stream_stats = weights.stream_llama_params(
                model_dir, cfg, mesh=mesh, dtype=dtype,
                quantize=request.quantization or
                ("int8" if request.dtype == "int8" else ""),
                lora_adapter=lora_dir, lora_scale=request.lora_scale or 1.0,
                tracer=self.tracer)
            log.info("streamed weight load: %d leaves, %.1f MB, %.0f ms",
                     self.weight_stream_stats["leaves"],
                     self.weight_stream_stats["bytes"] / 1e6,
                     self.weight_stream_stats["ms"])
        else:
            params = weights.load_llama_params(
                model_dir, cfg, mesh=mesh, dtype=dtype,
                quantize=request.quantization or
                ("int8" if request.dtype == "int8" else ""),
                lora_adapter=lora_dir, lora_scale=request.lora_scale or 1.0,
                tracer=self.tracer)
        with span("load_device_wait", "load"):
            # the one wait of the load: what the per-leaf host calls left
            # in flight (copies to the device, the cast where it runs there)
            jax.block_until_ready(params)

        ecfg = eng.EngineConfig(
            num_slots=request.num_slots or 8,
            max_context=request.context_size or min(cfg.max_position_embeddings, 4096),
            prefill_buckets=tuple(request.prefill_buckets) or (32, 128, 512, 2048),
            cache_dtype=cache_dtype,
            # self-extend (model YAML group_attn_n/group_attn_w via the
            # options k=v escape hatch, reference backend.proto Options).
            # Sanitized here too: external gRPC clients bypass the YAML
            # validator, and ga_w=0 or non-divisible windows would crash
            # or degrade the engine loop.
            ga_n=max(1, int(extra.get("ga_n", 1) or 1)),
            ga_w=self._sane_ga_w(extra),
            # 0 (or absent) = engine default, matching the YAML contract
            **({"decode_burst": db} if (db := int(
                extra.get("decode_burst", 0) or 0)) > 0 else {}),
            # paged-KV knobs via the options escape hatch: the engine's
            # "auto" default picks the paged layout for llama-family
            # serving; kv_layout=contiguous opts out, kv_page_size /
            # kv_pool_pages tune the pool (EngineConfig docs)
            **({"kv_layout": kl} if (kl := str(
                extra.get("kv_layout", "") or "")) in
               ("paged", "contiguous") else {}),
            **({"kv_page_size": kp} if (kp := int(
                extra.get("kv_page_size", 0) or 0)) > 0 else {}),
            **({"kv_pool_pages": kpp} if (kpp := int(
                extra.get("kv_pool_pages", 0) or 0)) > 0 else {}),
            # cross-release prefix cache (PR 2): kv_prefix_cache=0 opts
            # out (restores PR-1 lifecycle exactly);
            # kv_prefix_cache_min_rows guards short accidental matches
            **({"kv_prefix_cache": False} if str(
                extra.get("kv_prefix_cache", "")).strip().lower() in
               ("0", "false", "off", "no") else {}),
            **({"kv_prefix_cache_min_rows": mr} if (mr := int(
                extra.get("kv_prefix_cache_min_rows", 0) or 0)) > 0
               else {}),
            # two-tier host offload (PR 3): kv_offload=0 opts out
            # (restores the PR-2 lifecycle exactly); kv_host_pool_mb
            # bounds the host tier; kv_host_store=path persists it
            # across restarts (relative paths resolve under model_path)
            **({"kv_offload": False} if str(
                extra.get("kv_offload", "")).strip().lower() in
               ("0", "false", "off", "no") else {}),
            **({"kv_host_pool_mb": hmb} if (hmb := int(
                extra.get("kv_host_pool_mb", 0) or 0)) > 0 else {}),
            **({"kv_host_store_path": hsp} if (hsp := self._host_store_path(
                extra, request)) else {}),
            # KV lifecycle auditor (ISSUE 15): off = zero-cost no-op,
            # on = report-only scans (default), strict = raise
            **({"kv_audit": ka} if (ka := str(
                extra.get("kv_audit", "") or "")) in
               ("off", "on", "strict") else {}),
            # long-context serving tier (ISSUE 16): kv_window_pages
            # bounds the on-device working set (0 = off, the default);
            # kv_sink_pages pins attention-sink head pages on device;
            # kv_window_policy picks what happens to cold middle pages
            # (demote to host / drop); kv_prefetch_ahead sets the
            # decode-time restore pipeline depth (explicit 0 disables
            # prefetch, so isdigit passes it through)
            **({"kv_window_pages": wp} if (wp := int(
                extra.get("kv_window_pages", 0) or 0)) > 0 else {}),
            **({"kv_sink_pages": int(v)} if (v := str(
                extra.get("kv_sink_pages", "")).strip()).isdigit()
               else {}),
            **({"kv_window_policy": wpol} if (wpol := str(
                extra.get("kv_window_policy", "") or "")) in
               ("demote", "drop") else {}),
            **({"kv_prefetch_ahead": int(v)} if (v := str(
                extra.get("kv_prefetch_ahead", "")).strip()).isdigit()
               else {}),
            # ragged packed prefill (this PR): prefill_packed=0 opts
            # back into the per-slot bucketed path bit-for-bit;
            # prefill_token_budget caps packed prompt tokens per
            # scheduler tick (0 = engine auto, 2x prefill_chunk)
            **({"prefill_packed": False} if str(
                extra.get("prefill_packed", "")).strip().lower() in
               ("0", "false", "off", "no") else {}),
            **({"prefill_token_budget": ptb} if (ptb := int(
                extra.get("prefill_token_budget", 0) or 0)) > 0 else {}),
            # comm_overlap=auto|0|1 (ISSUE 11): TokenWeave-style halved-
            # pack overlap of per-layer collectives with compute
            # (auto = meshed backends only; bit-exact either way)
            **({"comm_overlap": cov} if (cov := str(
                extra.get("comm_overlap", "") or "")) in
               ("auto", "0", "1") else {}),
            # observability (this PR): trace=0 turns the span tracer into
            # a hot-path no-op; trace_ring_size bounds retained spans;
            # slow_request_ms logs a span decomposition when TTFT or e2e
            # exceeds the threshold
            **({"trace": False} if str(
                extra.get("trace", "")).strip().lower() in
               ("0", "false", "off", "no") else {}),
            **({"trace_ring_size": trs} if (trs := int(
                extra.get("trace_ring_size", 0) or 0)) > 0 else {}),
            **({"slow_request_ms": srm} if (srm := int(
                extra.get("slow_request_ms", 0) or 0)) > 0 else {}),
            # fault-tolerant lifecycle (ISSUE 7): admission control,
            # per-request deadlines, stall watchdog. Explicit 0 must pass
            # through (it DISABLES the bound), so these use isdigit
            # instead of the >0 idiom above.
            **({"max_queued_requests": int(v)} if (v := str(
                extra.get("max_queued_requests", "")).strip()).isdigit()
               else {}),
            **({"max_queue_wait_ms": int(v)} if (v := str(
                extra.get("max_queue_wait_ms", "")).strip()).isdigit()
               else {}),
            **({"request_timeout_ms": int(v)} if (v := str(
                extra.get("request_timeout_ms", "")).strip()).isdigit()
               else {}),
            **({"dispatch_stall_ms": int(v)} if (v := str(
                extra.get("dispatch_stall_ms", "")).strip()).isdigit()
               else {}),
            **({"stall_dump_dir": sdd} if (sdd := str(
                extra.get("stall_dump_dir", "") or "")) else {}),
            # system observability (ISSUE 8): structured event-log sink
            # (path|stderr|off)
            **({"event_log": evl} if (evl := str(
                extra.get("event_log", "") or "")) else {}),
            # event_log_max_mb bounds the file sink (0 disables
            # rotation, so isdigit passes the explicit 0 through)
            **({"event_log_max_mb": int(v)} if (v := str(
                extra.get("event_log_max_mb", "")).strip()).isdigit()
               else {}),
            # preemptive priority scheduler (ISSUE 10): preempt=0 restores
            # strict-FIFO admission bit-for-bit; priority_weights is
            # colon-separated (the options wire splits on commas);
            # priority sets the model-wide default class
            **({"preempt": False} if str(
                extra.get("preempt", "")).strip().lower() in
               ("0", "false", "off", "no") else {}),
            **({"priority_weights": pw} if (pw := str(
                extra.get("priority_weights", "") or "")) else {}),
            **({"priority": pc} if (pc := str(
                extra.get("priority", "") or "").strip().lower()) in
               ("high", "normal", "low") else {}),
            **({"max_preemptions": int(v)} if (v := str(
                extra.get("max_preemptions", "")).strip()).isdigit()
               else {}),
            **({"resume_reserve_pages": int(v)} if (v := str(
                extra.get("resume_reserve_pages", "")).strip()).isdigit()
               else {}),
            **({"priority_aging_ms": int(v)} if (v := str(
                extra.get("priority_aging_ms", "")).strip()).isdigit()
               else {}),
            # per-class SLO objectives (ISSUE 12): colon-separated
            # high:normal:low thresholds in ms (one value = all classes),
            # like priority_weights — the options wire splits on commas.
            # slo_error_budget tunes the burn-rate denominator.
            **({"slo_ttft_ms": st} if (st := str(
                extra.get("slo_ttft_ms", "") or "")) else {}),
            **({"slo_itl_ms": si} if (si := str(
                extra.get("slo_itl_ms", "") or "")) else {}),
            **({"slo_queue_wait_ms": sq} if (sq := str(
                extra.get("slo_queue_wait_ms", "") or "")) else {}),
            **({"slo_error_budget": seb} if (seb := float(
                extra.get("slo_error_budget", 0) or 0)) > 0 else {}),
            # speculative decoding (ISSUE 13): draft picks the drafter
            # (auto = model when a draft model is loaded, else n-gram
            # self-speculation; 0/off disables), n_draft sets the
            # proposal depth (explicit 0 disables, so isdigit passes it
            # through), spec_ngram the lookup n-gram length
            **({"draft": dr} if (dr := str(
                extra.get("draft", "") or "").strip().lower()) in
               ("auto", "model", "ngram", "0", "off", "none", "false")
               else {}),
            **({"n_draft": int(v)} if (v := str(
                extra.get("n_draft", "")).strip()).isdigit() else {}),
            **({"spec_ngram": sn} if (sn := int(
                extra.get("spec_ngram", 0) or 0)) > 0 else {}),
            # prefill/decode disaggregation role (ISSUE 17): "both"
            # (the default) is bit-for-bit the single-host path;
            # "prefill" retires finished prefills to the cluster
            # transport, "decode" is a routing hint
            **({"disagg": dg} if (dg := str(
                extra.get("disagg", "") or "").strip().lower()) in
               ("prefill", "decode", "both") else {}),
            # SLO-driven replica autoscaling (ISSUE 19): autoscale=0 (the
            # default) builds no policy object and no policy thread —
            # bit-for-bit the static pool path. autoscale_max=0 means
            # "twice the configured engines"; explicit 0 must pass, so
            # isdigit. Burn thresholds are floats (>0).
            **({"autoscale": True} if stream_auto else {}),
            **({"autoscale_min": amn} if (amn := int(
                extra.get("autoscale_min", 0) or 0)) > 0 else {}),
            **({"autoscale_max": int(v)} if (v := str(
                extra.get("autoscale_max", "")).strip()).isdigit()
               else {}),
            **({"autoscale_burn_out": abo} if (abo := float(
                extra.get("autoscale_burn_out", 0) or 0)) > 0 else {}),
            **({"autoscale_burn_in": abi} if (abi := float(
                extra.get("autoscale_burn_in", 0) or 0)) > 0 else {}),
            **({"autoscale_dwell_ms": adw} if (adw := int(
                extra.get("autoscale_dwell_ms", 0) or 0)) > 0 else {}),
            **({"autoscale_cooldown_ms": acd} if (acd := int(
                extra.get("autoscale_cooldown_ms", 0) or 0)) > 0 else {}),
            # predictive weight prefetch / streaming load (ISSUE 19)
            **({"weight_prefetch": True} if stream_load else {}),
            # federated KV stream timing (ISSUE 20, formerly hardcoded):
            # peer cooldown / negative-cache TTL / connect timeout.
            # Explicit 0 is meaningful (no cooldown / no negative
            # cache), so isdigit passes it through.
            **({"kv_stream_cooldown_ms": int(v)} if (v := str(
                extra.get("kv_stream_cooldown_ms", "")).strip()).isdigit()
               else {}),
            **({"kv_stream_negcache_ms": int(v)} if (v := str(
                extra.get("kv_stream_negcache_ms", "")).strip()).isdigit()
               else {}),
            **({"kv_stream_connect_timeout_ms": cto} if (cto := int(
                extra.get("kv_stream_connect_timeout_ms", 0) or 0)) > 0
               else {}),
            # cluster control plane (ISSUE 20): host placement + the
            # failure-detector / retry schedule knobs
            **({"cluster_mode": cm} if (cm := str(
                extra.get("cluster_mode", "") or "").strip().lower()) in
               ("inproc", "process") else {}),
            **({"cluster_heartbeat_ms": chb} if (chb := int(
                extra.get("cluster_heartbeat_ms", 0) or 0)) > 0 else {}),
            **({"cluster_suspect_ms": csu} if (csu := int(
                extra.get("cluster_suspect_ms", 0) or 0)) > 0 else {}),
            **({"cluster_dead_ms": cde} if (cde := int(
                extra.get("cluster_dead_ms", 0) or 0)) > 0 else {}),
            **({"cluster_rpc_timeout_ms": crt} if (crt := int(
                extra.get("cluster_rpc_timeout_ms", 0) or 0)) > 0 else {}),
            **({"cluster_rpc_retries": int(v)} if (v := str(
                extra.get("cluster_rpc_retries", "")).strip()).isdigit()
               else {}),
            **({"cluster_rpc_backoff_ms": crb} if (crb := int(
                extra.get("cluster_rpc_backoff_ms", 0) or 0)) > 0 else {}),
        )
        # chaos harness: a faults=... model option arms the in-process
        # fault table (same spec format as the LOCALAI_FAULTS env var,
        # ';'-separated because the options wire splits on commas)
        if extra.get("faults"):
            from localai_tpu.services.faults import FAULTS

            FAULTS.configure(str(extra["faults"]))
        draft = None
        if request.draft_model:
            ddir = request.draft_model
            if request.model_path and not os.path.isabs(ddir):
                ddir = os.path.join(request.model_path, ddir)
            dgguf = find_gguf(ddir)
            if dgguf is not None:
                from localai_tpu.engine import gguf as gguflib

                dcfg = dataclasses.replace(gguflib.config_from_gguf(
                    gguflib.open_gguf(dgguf)), dtype=dtype)
            else:
                dcfg = llama.LlamaConfig.from_json(
                    os.path.join(ddir, "config.json"), dtype=dtype)
            dparams = weights.load_llama_params(
                ddir, dcfg, mesh=mesh, dtype=dtype,
                quantize=request.quantization or
                ("int8" if request.dtype == "int8" else ""))
            draft = (dcfg, dparams)

        self.model_cfg = cfg
        self.model_path = request.model_path or os.path.dirname(model_dir)
        # engine replica pool (ISSUE 14): engines=N>1 builds an EnginePool
        # (shared host KV tier + cross-replica prefix index, prefix-affinity
        # routing, live migration). engines=1 (the default) constructs a
        # plain Engine — no pool object anywhere on the path, so single-
        # engine behavior stays bit-for-bit.
        n_engines = max(1, int(extra.get("engines", 1) or 1))
        if self.rpc_pool is not None \
                and ecfg.num_slots * n_engines > RPC_WORKERS:
            self.rpc_pool.grow(RPC_WORKERS_MANY_SLOTS)
        with span("load_tokenizer_join", "load"):
            # what the load still waits for the tokenizer's thread: near
            # 0 when the stage hid behind the weights whole
            self.tokenizer = tokenizer.result()
        if n_engines > 1 or ecfg.autoscale:
            # autoscale=1 needs the pool even at engines=1: the pool IS
            # the actuator (resize), and its build-arg stash is what lets
            # the policy add replicas later (ISSUE 19)
            from localai_tpu.engine.pool import EnginePool

            with span("load_engine_init", "load", engines=n_engines):
                # pool replicas keep a ring each (their slot tracks would
                # collide in one); Profile switches them all
                self.engine = EnginePool.build(
                    cfg, params, self.tokenizer, ecfg, engines=n_engines,
                    mesh=mesh, draft=draft, family=family)
        else:
            with span("load_engine_init", "load", engines=1):
                self.engine = eng.Engine(
                    cfg, params, self.tokenizer, ecfg, mesh=mesh,
                    draft=draft, family=family, tracer=self.tracer)
        # compile the whole serving surface before accepting traffic (a cold
        # compile mid-request stalls every active slot for 20-40s); skippable
        # for tests that only care about wiring
        with span("load_precompile", "load") as sp:
            self.engine.start(
                precompile=os.environ.get("LOCALAI_PRECOMPILE", "1") != "0")
            # (a pool's snapshot has no process-wide compile count)
            comp = self.engine.state_snapshot().get("compiles") or {}
            sp.args.update(
                programs=comp.get("compiles_total", 0),
                from_cache=comp.get("compiles_from_cache", 0),
                compile_seconds=comp.get("compile_seconds_total", 0.0))
        # cross-host KV federation (ISSUE 17): kv_serve=1|host:port makes
        # this host's KV tier network-addressable (peers stream chain
        # entries out of it); kv_peers=host:port|host:port attaches the
        # federated tier so a local host-store miss consults peers before
        # falling back to re-prefill. Both absent (the default) leaves
        # the single-host path untouched.
        self.kv_server = None
        self.kv_fed = None
        kv_serve = str(extra.get("kv_serve", "") or "").strip()
        serve_on = kv_serve.lower() not in ("", "0", "false", "off", "no")
        kv_peers = [a.strip() for a in
                    str(extra.get("kv_peers", "") or "").split("|")
                    if a.strip()]
        if serve_on or kv_peers:
            if n_engines > 1:
                store, index = (self.engine._shared.store,
                                self.engine._shared.index)
            else:
                store, index = self.engine._hstore, None
            if store is None:
                log.warning("kv_serve/kv_peers ignored: no host KV "
                               "tier (kv_offload=0 or a non-paged layout)")
            else:
                if serve_on:
                    from localai_tpu.services.kv_wire import KVWireServer

                    bind, port = "127.0.0.1", 0
                    if ":" in kv_serve:
                        b, _, p = kv_serve.rpartition(":")
                        bind, port = b, int(p)
                    self.kv_server = KVWireServer(
                        store, index=index,
                        host_id=int(extra.get("kv_host_id", 0) or 0),
                        bind=bind, port=port)
                    log.info("kv wire serving at %s",
                                self.kv_server.start())
                if kv_peers:
                    from localai_tpu.engine.kv_stream import (FederatedKV,
                                                              KVStreamClient)

                    self.kv_fed = FederatedKV(store, [
                        KVStreamClient(
                            a, store.scope, store.page_size,
                            timeout_s=ecfg.kv_stream_connect_timeout_ms
                            / 1e3,
                            cooldown_s=ecfg.kv_stream_cooldown_ms / 1e3)
                        for a in kv_peers],
                        neg_ttl_s=ecfg.kv_stream_negcache_ms / 1e3,
                    ).attach()
                    log.info("kv federated tier attached: %d peer(s)",
                                len(kv_peers))
        self._embed = request.embeddings

        # multimodal projector (LLaVA-style vision tower; reference injects
        # CLIP embeddings at [img-N] placeholders, grpc-server.cpp:1157-1180)
        self.vision = None
        self.vision_cfg = None
        if request.mmproj:
            from localai_tpu.models import vision

            vdir = request.mmproj
            if request.model_path and not os.path.isabs(vdir):
                vdir = os.path.join(request.model_path, vdir)
            self.vision_cfg = vision.VisionConfig.from_json(
                os.path.join(vdir, "config.json"), proj_dim=cfg.hidden_size)
            self.vision = vision.load_params(vdir, self.vision_cfg)

    # ---- inference ----

    def _expand_media(self, opts: pb.PredictOptions):
        """Tokenize the prompt around [img-N]/[vid-N] placeholders and
        compute injection positions + projected embeddings: images one
        CLIP pass each; videos as uniformly sampled frames through the
        same tower (reference vLLM video semantics,
        backend/python/vllm/backend.py:208-236)."""
        import base64
        import re

        from localai_tpu.models import vision

        n_frames = int(os.environ.get("LOCALAI_VIDEO_FRAMES", "4"))
        pieces = re.split(r"(\[img-\d+\]|\[vid-\d+\])", opts.prompt)
        ids: list = []
        positions: list = []
        vectors: list = []
        pad = getattr(self.tokenizer, "pad_token_id", None) or 0

        def inject(img_bytes: bytes):
            emb = vision.embed_image(self.vision, self.vision_cfg, img_bytes)
            for v in emb:
                positions.append(len(ids))
                vectors.append(v)
                ids.append(pad)

        for piece in pieces:
            mi = re.fullmatch(r"\[img-(\d+)\]", piece)
            mv = re.fullmatch(r"\[vid-(\d+)\]", piece)
            if mi and int(mi.group(1)) < len(opts.images):
                inject(base64.b64decode(opts.images[int(mi.group(1))]))
            elif mv and int(mv.group(1)) < len(opts.videos):
                vid = base64.b64decode(opts.videos[int(mv.group(1))])
                for frame in vision.sample_video_frames(vid, n_frames):
                    inject(frame)
            elif piece:
                ids.extend(self.tokenizer.encode(
                    piece, add_special_tokens=not ids))
        import numpy as np

        return ids, positions, (np.stack(vectors) if vectors else None)

    def _build_request(self, opts: pb.PredictOptions, context=None):
        from localai_tpu.engine.engine import GenRequest

        # per-request hints ride invocation metadata (the compiled
        # descriptor cannot grow PredictOptions fields — same constraint
        # as the localai-retry-after trailing metadata): the priority
        # class (ISSUE 10) and the cross-process trace id (ISSUE 12).
        # Guarded with getattr: in-process callers pass bare context
        # fakes. An empty priority -> the engine applies the model
        # default; an empty trace id falls back to the correlation_id
        # proto field, keeping older cores traceable.
        priority = ""
        trace_id = ""
        meta_fn = getattr(context, "invocation_metadata", None)
        if meta_fn is not None:
            for key, value in meta_fn() or ():
                if key == "localai-priority":
                    priority = str(value)
                elif key == "localai-trace-id":
                    trace_id = str(value)

        # media parts the backend cannot consume are a loud error, never a
        # silent drop (VERDICT r4 #6): the HTTP layer 400s these first;
        # this is the backstop for direct gRPC clients
        if opts.audios:
            raise ValueError(
                "audio content parts are not consumable by the LLM "
                "backend; use the transcription endpoint for speech input")
        if (opts.images or opts.videos) and self.vision is None:
            raise ValueError(
                "image/video content parts require a vision-capable model "
                "(set mmproj in the model config)")
        mm_positions: list = []
        mm_vectors = None
        if (opts.images or opts.videos) and self.vision is not None \
                and not opts.prompt_ids:
            ids, mm_positions, mm_vectors = self._expand_media(opts)
        elif opts.prompt_ids:
            ids = list(opts.prompt_ids)
        else:
            ids = self.tokenizer.encode(opts.prompt)
        cache_path = opts.prompt_cache_path
        if cache_path and not os.path.isabs(cache_path):
            base = os.path.join(self.model_path or ".", "prompt_cache")
            os.makedirs(base, exist_ok=True)
            cache_path = os.path.join(base, cache_path)
        return GenRequest(
            prompt_ids=ids,
            params=_sampling_from_predict(opts),
            max_new_tokens=opts.max_tokens or 256,
            stop_sequences=list(opts.stop_sequences),
            ignore_eos=opts.ignore_eos,
            grammar=opts.grammar,
            mm_positions=mm_positions,
            mm_vectors=mm_vectors,
            request_id=trace_id or opts.correlation_id or "",
            prompt_cache_path=cache_path,
            prompt_cache_ro=opts.prompt_cache_ro,
            prompt_cache_all=opts.prompt_cache_all,
            priority=priority,
        )

    def Predict(self, request: pb.PredictOptions, context) -> pb.Reply:
        self._require_ready(context)
        req = self._build_request(request, context)
        text, events = self.engine.generate_text(req)
        last = events[-1] if events else None
        if last is not None and last.error:
            _abort_event(context, last)
        if request.echo:
            text = request.prompt + text
        return pb.Reply(
            message=text.encode("utf-8"),
            tokens=last.completion_tokens if last else 0,
            prompt_tokens=last.prompt_tokens if last else 0,
            finish_reason=(last.finish_reason or "") if last else "",
            timing_prompt_processing=(last.timings or {}).get("prefill_ms", 0.0) if last else 0.0,
            timing_token_generation=(last.timings or {}).get("decode_tokens_per_s", 0.0) if last else 0.0,
        )

    def PredictStream(self, request: pb.PredictOptions, context):
        self._require_ready(context)
        req = self._build_request(request, context)
        out = self.engine.submit(req)
        while True:
            ev = out.get()
            if ev is None:
                return
            if not context.is_active():
                # client cancelled: reference parity is TASK_TYPE_CANCEL
                # (utils.hpp:53-56); here -> cancel the slot
                self.engine.cancel(req.request_id)
                return
            if ev.error:
                _abort_event(context, ev)
            yield pb.Reply(
                message=ev.text.encode("utf-8"),
                token_id=ev.token_id,
                logprob=ev.logprob,
                # burst-coalesced chunks: every member token (engine emits
                # one event per slot per decode burst)
                token_ids=ev.token_ids or ([ev.token_id] if ev.token_id >= 0 else []),
                logprobs=ev.logprobs or ([ev.logprob] if ev.token_id >= 0 else []),
                tokens=ev.completion_tokens,
                prompt_tokens=ev.prompt_tokens,
                finish_reason=ev.finish_reason or "",
            )

    def Embedding(self, request: pb.PredictOptions, context) -> pb.EmbeddingResult:
        self._require_ready(context)
        if not hasattr(self.engine, "embed"):
            context.abort(grpc.StatusCode.UNIMPLEMENTED, "model not loaded for embeddings")
        vec = self.engine.embed(request.prompt)
        return pb.EmbeddingResult(embeddings=[float(x) for x in vec])

    def TokenizeString(self, request: pb.PredictOptions, context) -> pb.TokenizationResponse:
        if self.tokenizer is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, "no model loaded")
        ids = self.tokenizer.encode(request.prompt)
        return pb.TokenizationResponse(length=len(ids), tokens=ids)

    # ---- observability ----

    def Status(self, request, context) -> pb.StatusResponse:
        # what the process holds NOW is what /backend/monitor and the
        # watchdog's memory reading compare; the peak rides beside it
        hm = sysobs.host_memory()
        breakdown = {k: hm[k + "_bytes"] for k in ("rss", "rss_peak")
                     if k + "_bytes" in hm}
        total = breakdown.get("rss", 0)
        state = self._state
        if state == pb.StatusResponse.READY and self.engine and self.engine.num_active > 0:
            state = pb.StatusResponse.BUSY
        return pb.StatusResponse(
            state=state, memory=pb.MemoryUsageData(total=total, breakdown=breakdown)
        )

    def GetMetrics(self, request, context) -> pb.MetricsResponse:
        if not self.engine:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, "no model loaded")
        m = self.engine.metrics()
        if getattr(self, "weight_stream_stats", None):
            m["weight_stream"] = self.weight_stream_stats
        # the engine's FULL stats dict (kv pool occupancy, prefix-cache
        # counters, TTFT decomposition, ...) rides the proto's free
        # string field as JSON: the stubs are hand-rolled (no protoc in
        # the image), so the wire cannot grow typed fields per release —
        # the core's /metrics exporter and tokenMetrics endpoint parse
        # this instead (api/localai_routes.py)
        try:
            stats_json = json.dumps(m)
        except (TypeError, ValueError):
            stats_json = ""
        return pb.MetricsResponse(
            tokens_per_second=m["tokens_per_second_active"],
            tokens_generated=m["total_tokens_generated"],
            slots_active=m["slots_active"],
            slots_total=m["slots_total"],
            queued=m["queued"],
            uptime_s=m["uptime_s"],
            prompt_json_for_slot=stats_json,
        )

    # ---- observability side-channel (service.py METHODS additions) ----

    def GetTrace(self, request, context) -> pb.Reply:
        """Chrome trace-event JSON of the engine's span ring. The span
        data itself is process-local (the engine lives in this backend
        subprocess); the core's /debug/trace endpoint merges one of
        these per loaded model."""
        self._require_ready(context)
        try:
            payload = json.dumps(self.engine.trace_events())
        except Exception as e:
            context.abort(grpc.StatusCode.INTERNAL,
                          f"trace export failed: {type(e).__name__}: {e}")
        return pb.Reply(message=payload.encode("utf-8"))

    def GetState(self, request, context) -> pb.Reply:
        """Live engine-state snapshot + this backend process's event-log
        ring as JSON (ISSUE 8). The core's /debug/state and /debug/events
        endpoints merge one of these per loaded model."""
        self._require_ready(context)
        from localai_tpu.services.eventlog import EVENTS

        try:
            payload = json.dumps({
                "state": self.engine.state_snapshot(),
                "events": EVENTS.events(),
                # KV lifecycle view (ISSUE 15): tier map + genealogy +
                # ledger tail for the core's /debug/kv endpoint
                "kv": self.engine.kv_debug(),
            }, default=str)
        except Exception as e:
            context.abort(grpc.StatusCode.INTERNAL,
                          f"state export failed: {type(e).__name__}: {e}")
        return pb.Reply(message=payload.encode("utf-8"))

    def _engines(self) -> list:
        """The Engine, or a pool's replicas."""
        return getattr(self.engine, "_engines", None) or [self.engine]

    def _tracers(self) -> list:
        """The process's ring, and a pool's per-replica rings."""
        out = [self.tracer]
        for e in self._engines():
            if all(e.tracer is not t for t in out):
                out.append(e.tracer)
        return out

    def Profile(self, request, context) -> pb.Result:
        """Capture a jax.profiler trace (TensorBoard/perfetto format) for
        the requested number of seconds while the engine keeps serving.
        Request rides PredictOptions.prompt as JSON {"seconds": N}.

        While the capture runs every ``RingTracer.span()`` of this process
        is also an annotation in it. The first host event is
        ``clock_anchor``, carrying this process's monotonic and wall
        clocks: with the event's own timestamp it places every ring span
        and every client-clock instant on the capture's timeline. The
        python tracer is off (host TraceMe events are kept; nothing reads
        python frames). stop_trace still takes 7-8 s per captured second
        of a busy device, whatever the host options (PERF.md section 6,
        PR 25): the route's deadline allows for it. /debug/state ->
        profile describes the last capture."""
        self._require_ready(context)
        import tempfile
        import time as _time

        try:
            req = json.loads(request.prompt or "{}")
        except ValueError:
            req = {}
        seconds = min(60.0, max(0.1, float(req.get("seconds", 3) or 3)))
        out_dir = req.get("dir") or tempfile.mkdtemp(prefix="localai-prof-")
        prof = {"capture_dir": out_dir, "seconds": seconds}
        try:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(out_dir, profiler_options=opts)
            prof["monotonic_ns"] = _time.monotonic_ns()
            prof["epoch_ns"] = _time.time_ns()
            with jax.profiler.TraceAnnotation(
                    "clock_anchor", monotonic_ns=prof["monotonic_ns"],
                    epoch_ns=prof["epoch_ns"]):
                pass
            for tr in self._tracers():
                tr.set_capturing(True)
            try:
                _time.sleep(seconds)
            finally:
                for tr in self._tracers():
                    tr.set_capturing(False)
                t_stop = _time.monotonic()
                jax.profiler.stop_trace()
                prof["stop_trace_s"] = round(_time.monotonic() - t_stop, 3)
                prof["capture_bytes"] = sum(
                    os.path.getsize(os.path.join(d, f))
                    for d, _, fs in os.walk(out_dir) for f in fs)
        except Exception as e:
            return pb.Result(
                success=False,
                message=f"profiler capture failed: {type(e).__name__}: {e}")
        finally:
            for e in self._engines():
                e.profile_state = prof
        log.info("profiler capture: %s", json.dumps(prof))
        return pb.Result(success=True, message=out_dir)

    def _require_ready(self, context):
        if self.engine is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, "no model loaded")


def require_accelerator() -> str:
    """The platform this backend will serve from — "tpu", or "cpu" only
    where the environment asks for it by name (JAX_PLATFORMS=cpu: the
    test suite, CPU rehearsals). Anything else is refused: with
    JAX_PLATFORMS unset jax falls back to the CPU with only a warning
    when it cannot open the chip (another process holds it, or there is
    none), and a model sized for HBM then "serves" from host memory."""
    import jax

    platform = jax.default_backend()
    # jax's default backend is the FIRST platform the variable names
    # ("tpu,cpu", as TPU hosts set it, asks for the TPU)
    asked = os.environ.get("JAX_PLATFORMS", "").lower().split(",")[0]
    if platform != "tpu" and platform != asked:
        raise RuntimeError(
            f"no TPU: jax initialised the {platform!r} backend "
            f"(devices {jax.devices()}). Is another process holding the "
            "chip? One process owns a chip at a time. To run on the CPU "
            "on purpose, set JAX_PLATFORMS=cpu.")
    return platform


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--addr", required=True)
    parser.add_argument("--log-level", default="info")
    args = parser.parse_args(argv)
    logging.basicConfig(level=args.log_level.upper())
    # this process tokenizes and never calls torch: transformers' own
    # switch keeps its import from loading torch with it, which beside
    # the weights outlasts them (the load then waits 13 s at
    # load_tokenizer_join) and holds 1 GB; an operator's setting stands
    os.environ.setdefault("USE_TORCH", "0")
    from localai_tpu.utils.jaxtools import enable_compilation_cache

    enable_compilation_cache()
    pool = RpcPool(max_workers=RPC_WORKERS)
    servicer = EngineServicer(rpc_pool=pool)
    server = make_server(servicer, args.addr, pool=pool)
    server.start()
    log.info("backend listening on %s", args.addr)
    print(f"gRPC Server listening at {args.addr}", flush=True)  # readiness marker
    server.wait_for_termination()


if __name__ == "__main__":
    main()
