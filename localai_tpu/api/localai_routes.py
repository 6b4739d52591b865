"""LocalAI-specific + 3rd-party-compat endpoints.

Parity with the reference route tables (reference: core/http/routes/
localai.go:14-71 — gallery ops, TTS, sound generation, tokenize, stores,
/metrics, backend monitor/shutdown, /system, /version, p2p, tokenMetrics;
routes/health.go — /healthz /readyz; routes/elevenlabs.go; routes/jina.go).
"""

from __future__ import annotations

import os
import secrets
import tempfile
import time

from aiohttp import web

from localai_tpu import __version__
from localai_tpu.api.app import api_error, get_state
from localai_tpu.backend import contract_pb2 as pb
from localai_tpu.services.eventlog import EVENTS
from localai_tpu.services.metrics import CONTENT_TYPE, METRICS, label_str


def register(app: web.Application):
    r = app.router
    # health (reference: routes/health.go). /healthz is pure liveness;
    # /readyz is distinct (ISSUE 7): it consults the loader's circuit
    # breakers so an orchestrator stops routing to a crash-looping node
    r.add_get("/healthz", healthz)
    r.add_get("/readyz", readyz)
    # tts + sound generation
    r.add_post("/tts", tts)
    r.add_post("/sound-generation", sound_generation)
    # elevenlabs compat (reference: routes/elevenlabs.go)
    r.add_post("/v1/text-to-speech/{voice_id}", elevenlabs_tts)
    r.add_post("/v1/sound-generation", sound_generation)
    # jina compat (reference: routes/jina.go)
    r.add_post("/v1/rerank", rerank)
    # tokenize
    r.add_post("/v1/tokenize", tokenize)
    # stores (reference: routes/localai.go:49-53)
    r.add_post("/stores/set", stores_set)
    r.add_post("/stores/delete", stores_delete)
    r.add_post("/stores/get", stores_get)
    r.add_post("/stores/find", stores_find)
    # observability
    r.add_get("/metrics", metrics)
    r.add_get("/backend/monitor", backend_monitor)
    r.add_post("/backend/monitor", backend_monitor)
    r.add_post("/backend/shutdown", backend_shutdown)
    r.add_get("/system", system_info)
    r.add_get("/version", version)
    r.add_get("/v1/tokenMetrics", token_metrics)
    r.add_get("/debug/trace", debug_trace)
    r.add_get("/debug/profile", debug_profile)
    # system observability (ISSUE 8): live engine-state snapshot +
    # merged structured event log
    r.add_get("/debug/state", debug_state)
    r.add_get("/debug/events", debug_events)
    r.add_get("/debug/kv", debug_kv)
    # gallery (reference: routes/localai.go:14-44)
    r.add_post("/models/apply", models_apply)
    r.add_post("/models/delete/{name}", models_delete)
    r.add_get("/models/available", models_available)
    r.add_get("/models/jobs/{uuid}", models_job_status)
    r.add_get("/models/jobs", models_all_jobs)
    r.add_post("/models/galleries", add_gallery)
    r.add_delete("/models/galleries", remove_gallery)
    # p2p parity surface (topology is static on TPU; report the mesh)
    r.add_get("/api/p2p", p2p_nodes)
    r.add_get("/api/p2p/token", p2p_token)


async def healthz(request):
    return web.Response(text="OK")


def _readyz_load(state) -> dict:
    """Per-model queue depth + slots-in-flight off the (cheap, native)
    GetMetrics fields, short-timeout and failure-tolerant: readiness
    must answer even when a backend is wedged."""
    import json

    out = {}
    for name in state.caps.loader.list_loaded():
        lm = state.caps.loader.get(name)
        if lm is None:
            continue
        try:
            m = lm.client.get_metrics(timeout=1.0)
            out[name] = {"queue_depth": int(m.queued),
                         "slots_in_flight": int(m.slots_active),
                         "slots_total": int(m.slots_total)}
            # target-vs-actual replicas + last scaling decision (ISSUE
            # 19): parsed tolerantly from the stats JSON — absent on
            # unpooled models and non-JSON backends
            try:
                stats = json.loads(m.prompt_json_for_slot or "{}")
            except (ValueError, TypeError):
                stats = {}
            if "engine_replicas" in stats:
                pool = stats.get("pool") or {}
                out[name]["replicas_alive"] = pool.get(
                    "replicas_alive", stats["engine_replicas"])
                out[name]["replicas_target"] = stats.get(
                    "engine_replicas_target",
                    pool.get("replicas_target"))
                auto = pool.get("autoscale")
                if auto:
                    out[name]["last_scale_decision"] = auto.get(
                        "last_decision")
        except Exception:
            out[name] = {"queue_depth": None, "slots_in_flight": None}
    return out


async def readyz(request):
    """Readiness distinct from liveness: 503 (with Retry-After) while any
    model's load circuit breaker is open — the process is alive, but a
    load balancer should prefer other replicas until the breaker cools.
    The body carries the full breaker map plus per-model queue depth and
    slots-in-flight (ISSUE 8 satellite, closes the PR-7 follow-up) so an
    external LB can weight replicas, not just drop them."""
    state = get_state(request)
    try:
        stats = state.caps.loader.stats()
    except Exception:
        stats = {}
    breakers = {name: s["breaker"] for name, s in stats.items()}
    open_breakers = {name: b for name, b in breakers.items()
                     if b["state"] == "open"}
    load = await state.run_blocking(_readyz_load, state)
    if open_breakers:
        retry_after = max(1, int(max(
            b.get("retry_after_s", 0.0) for b in open_breakers.values())))
        return web.json_response(
            {"status": "unready", "circuit_open": open_breakers,
             "breakers": breakers, "load": load},
            status=503, headers={"Retry-After": str(retry_after)})
    return web.json_response(
        {"status": "ready",
         "models_loaded": len(state.caps.loader.list_loaded()),
         "breakers": breakers, "load": load})


async def run_audio_capability(request, call) -> web.Response:
    """Run a sync capability ``call(dst)`` that writes a wav to dst; return
    the audio as the response body. The temp file is always cleaned up."""
    state = get_state(request)
    dst = os.path.join(tempfile.gettempdir(), f"localai-audio-{secrets.token_hex(8)}.wav")
    try:
        await state.run_blocking(call, dst)
        with open(dst, "rb") as f:
            return web.Response(body=f.read(), content_type="audio/wav")
    finally:
        if os.path.exists(dst):
            os.unlink(dst)


async def version(request):
    return web.json_response({"version": __version__})


_POOL_GAUGES = ("kv_pages_total", "kv_pages_free", "kv_pages_retained",
                "kv_pages_active", "kv_pages_offloaded")
_PCACHE_COUNTERS = ("hits", "misses", "evicted_pages", "inserted_pages",
                    "hit_rows")
# host-tier transfer totals (engine/kv_offload.py stats key -> metric):
# localai_kv_offload_{pages,bytes,restores,hits,misses}_total
_OFFLOAD_COUNTERS = (("offloaded_pages", "pages"),
                     ("offloaded_bytes", "bytes"),
                     ("restores", "restores"),
                     ("hits", "hits"),
                     ("misses", "misses"),
                     ("evicted_pages", "evicted_pages"),
                     ("restored_pages", "restored_pages"))
# prefetch-ahead pipeline totals (ISSUE 16; engine/kv_offload.py stats
# key -> localai_kv_prefetch_<metric>_total): pages restored ahead of
# need, pages the admission claimed (hits), sync restores the pipeline
# predicted but lost (late), and expired/raided speculation (wasted)
_PREFETCH_COUNTERS = (("prefetch_issued", "issued"),
                      ("prefetch_hits", "hits"),
                      ("prefetch_late", "late"),
                      ("prefetch_wasted", "wasted"))
# per-request TTFT decomposition (engine.py _ttft_decomp rolling window,
# p50 over the last 512 finished requests) — loaded-TTFT regressions
# show up here without running bench: queue_wait (admission backlog),
# admit_to_first (prefill scheduling + other slots' work), and the pure
# prefill dispatch time. stats key -> localai_ttft_<metric>_p50_ms
_TTFT_GAUGES = (("queue_wait", "queue_wait"),
                ("admit_to_first", "admit_to_first"),
                ("prefill_dispatch", "prefill_dispatch"))
# packed-prefill scheduling totals (engine.py metrics()["packed_prefill"])
_PACKED_COUNTERS = ("dispatches", "tokens", "segments", "pad_tokens",
                    "kernel_fallback")
# engine-owned latency histograms (engine.py metrics()["histograms"]):
# re-exposed verbatim with proper _bucket/_sum/_count exposition
_LATENCY_HISTOGRAMS = ("ttft_seconds", "itl_seconds",
                       "decode_burst_seconds", "prefill_dispatch_seconds")
# fault-tolerant lifecycle counters (engine.py metrics()["lifecycle"],
# ISSUE 7): stats key -> localai_<metric> per model
_LIFECYCLE_COUNTERS = (("requests_shed", "requests_shed_total"),
                       ("requests_timed_out", "requests_timed_out_total"),
                       ("stalls", "engine_stalls_total"),
                       ("stall_dumps", "stall_dumps_total"),
                       # dispatches that overran their own pace without
                       # reaching the stall abort (engine.py LATE_FACTOR);
                       # the seconds they were overdue are a gauge below
                       # (set_counter truncates a float)
                       ("late_dispatches", "late_dispatches_total"))
# preemptive priority scheduler (ISSUE 10): preempt/resume totals +
# per-class depth gauges, from engine metrics()["scheduler"]
_SCHED_COUNTERS = (("preemptions", "preemptions_total"),
                   ("resumes", "resume_restore_total"),
                   ("resume_reprefills", "resume_restore_reprefills_total"),
                   ("resume_restore_rows", "resume_restore_rows_total"),
                   ("aged_promotions", "priority_aged_promotions_total"))
# system observability (ISSUE 8): XLA compile tracking + memory
# watermarks + goodput/MFU, from engine metrics()["sysobs"]
_SYSOBS_COUNTERS = ("xla_compiles_total", "xla_compiles_after_warmup_total",
                    "goodput_tokens_total")
_SYSOBS_GAUGES = ("xla_compile_seconds", "mfu", "goodput_tok_s",
                  "mem_weight_bytes", "mem_pool_frag_holes",
                  "mem_pool_frag_ratio")
# watermark keys are prefixed mem_ on export; the known set is cleared
# explicitly so unloads don't leave stale per-model peaks behind
_SYSOBS_WATERMARKS = ("peak_queued", "peak_slots_active",
                      "peak_tokens_total", "peak_pool_active_pages",
                      "peak_pool_retained_pages", "peak_pool_pages_in_use",
                      "peak_host_offloaded_pages", "peak_host_bytes",
                      "peak_device_bytes_in_use", "peak_host_rss_bytes")
# the runner process's resident memory now and at its high-water mark:
# engine sysobs.host_memory (services/sysobs.py::host_memory)
_HOST_MEM_GAUGES = (("rss_bytes", "runner_rss_bytes"),
                    ("rss_peak_bytes", "runner_rss_peak_bytes"))
# device allocator stats: engine sysobs.device_mem, one entry per local
# device -> localai_mem_device_<key>{device=}; no counters on CPU
_DEVICE_MEM_GAUGES = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
# per-class SLO engine (ISSUE 12): burn-rate gauges per
# (model, priority, metric, window) + violation totals, from engine
# metrics()["slo"]; flight-recorder dump counters ride along
_SLO_WINDOWS = (("burn_5m", "5m"), ("burn_1h", "1h"))
# speculative decoding (ISSUE 13): per-round totals + the acceptance
# rate, from engine metrics()["spec"]; since ISSUE 18 each series is
# additionally split by acceptance mode — mode="greedy" (accept_greedy)
# vs mode="sampled" (rejection-sampling acceptance) — from
# metrics()["spec"]["by_mode"], alongside the unlabeled aggregate
_SPEC_COUNTERS = (("rounds", "spec_rounds_total"),
                  ("proposed", "spec_proposed_total"),
                  ("accepted", "spec_accepted_total"))
# KV lifecycle auditor (ISSUE 15): scan/violation/leak/ledger totals,
# from engine metrics()["kv_audit"] (pool-aggregated for engines>1)
_KV_AUDIT_COUNTERS = ("checks", "violations", "leaked_pages",
                      "ledger_events")
# cross-host KV streaming transport (ISSUE 17): the federated tier's
# fetch totals, from engine metrics()["kv_stream"] (stats key ->
# localai_kv_stream_<metric>_total)
_KV_STREAM_COUNTERS = (("fetches", "fetches"), ("hits", "hits"),
                       ("misses", "misses"), ("pages", "pages"),
                       ("bytes", "bytes"), ("pushes", "pushes"),
                       ("pushed_pages", "pushed_pages"),
                       ("corrupt_rejected", "corrupt_rejected"))


def _refresh_engine_metrics(state):
    """Pull each loaded LLM backend's engine stats (the JSON side-channel
    on GetMetrics — see backend/runner.py) into the Prometheus registry:
    kv pool occupancy gauges + prefix-cache counters, labeled by model.
    Runs synchronously right before every /metrics render, Prometheus
    pull style; backends without GetMetrics (tts, diffusion, ...) are
    skipped."""
    import json as _json

    for g in ("kv_pool_pages", "kv_pool_oversubscription",
              "prefix_cache_entries", "kv_offload_host_bytes",
              "ttft_samples", "queue_depth", "slots_in_flight",
              *_LATENCY_HISTOGRAMS,
              *(f"ttft_{m}_p50_ms" for _k, m in _TTFT_GAUGES),
              *(f"prefill_packed_{k}_total" for k in _PACKED_COUNTERS),
              "prefill_kernel_fallback_total", "decode_bursts_total",
              *(f"prefix_cache_{k}_total" for k in _PCACHE_COUNTERS),
              *(f"kv_offload_{m}_total" for _k, m in _OFFLOAD_COUNTERS),
              *(f"kv_prefetch_{m}_total" for _k, m in _PREFETCH_COUNTERS),
              "kv_prefetch_inflight",
              *(m for _k, m in _LIFECYCLE_COUNTERS),
              "late_dispatch_seconds_total",
              *(m for _k, m in _SCHED_COUNTERS),
              "queue_depth_class", "resume_queue_depth",
              *_SYSOBS_COUNTERS, *_SYSOBS_GAUGES,
              *(f"mem_{k}" for k in _SYSOBS_WATERMARKS),
              *(f"mem_device_{k}" for k in _DEVICE_MEM_GAUGES),
              *(m for _k, m in _HOST_MEM_GAUGES),
              "slo_burn_rate", "slo_objective_ms", "slo_violations_total",
              "slo_error_budget", "flight_dumps_total",
              "flight_dumps_suppressed_total",
              *(m for _k, m in _SPEC_COUNTERS),
              "spec_acceptance_rate",
              *(f"kv_audit_{k}_total" for k in _KV_AUDIT_COUNTERS),
              *(f"kv_stream_{m}_total" for _k, m in _KV_STREAM_COUNTERS),
              "kv_stream_inflight", "kv_stream_peers_online",
              "cluster_hosts", "disagg_handoffs_total",
              "engine_queue_limit", "cluster_host_state",
              "cluster_heartbeat_rtt_ms", "cluster_rpc_retries_total",
              "cluster_rpc_timeouts_total",
              "engine_replicas", "replica_queue_depth",
              "replica_slots_in_flight", "replica_migrations_total",
              "pool_affinity_hits_total", "pool_affinity_misses_total",
              "resume_reserve_pages",
              "engine_replicas_target", "autoscale_decisions_total",
              "autoscale_flaps_suppressed_total",
              "weight_prefetch_hits_total", "weight_prefetch_bytes_total",
              "backend_respawns_total", "circuit_state"):
        METRICS.clear_instrument(g)
    # loader-owned recovery telemetry (ISSUE 7): respawn counts + breaker
    # state come from the core's loader, not the backend — a model whose
    # backend is DEAD right now is exactly the one that must still export
    try:
        for name, s in state.caps.loader.stats().items():
            METRICS.set_counter("backend_respawns_total", s["respawns"],
                                label_str(model=name))
            METRICS.set_gauge("circuit_state", s["circuit_state"],
                              label_str(model=name))
    except Exception:
        pass
    for name in state.caps.loader.list_loaded():
        lm = state.caps.loader.get(name)
        if lm is None:
            continue
        try:
            m = lm.client.get_metrics(timeout=2.0)
            stats = _json.loads(m.prompt_json_for_slot or "{}")
        except Exception:
            continue
        # TTFT decomposition + packed-prefill scheduling: any engine
        # layout (the gauges exist for contiguous caches too)
        td = stats.get("ttft_decomp_p50_ms")
        if td:
            for skey, mkey in _TTFT_GAUGES:
                METRICS.set_gauge(f"ttft_{mkey}_p50_ms",
                                  td.get(skey, 0.0), label_str(model=name))
            METRICS.set_gauge("ttft_samples", td.get("n", 0),
                              label_str(model=name))
        # scheduler load gauges + latency histograms (any layout)
        METRICS.set_gauge("queue_depth", stats.get("queued", 0),
                          label_str(model=name))
        METRICS.set_gauge("slots_in_flight", stats.get("slots_active", 0),
                          label_str(model=name))
        for hname, h in (stats.get("histograms") or {}).items():
            if hname in _LATENCY_HISTOGRAMS:
                METRICS.set_histogram(hname, label_str(model=name),
                                      h.get("le", ()), h.get("counts", ()),
                                      h.get("sum", 0.0), h.get("count", 0))
        pp = stats.get("packed_prefill")
        if pp and stats.get("prefill_packed"):
            for key in _PACKED_COUNTERS:
                METRICS.set_counter(f"prefill_packed_{key}_total",
                                    pp.get(key, 0), label_str(model=name))
            # headline alias (ISSUE 11): a pack that left the Pallas
            # kernel path for the jnp reference is a silent throughput
            # cliff — exported under its own name so dashboards can
            # alert on it without knowing the packed_prefill family
            METRICS.set_counter("prefill_kernel_fallback_total",
                                pp.get("kernel_fallback", 0),
                                label_str(model=name))
        # decode bursts by the sampler's branch: all live rows plain
        # greedy (argmax, no candidate window) or the window
        for branch, n in (stats.get("sampler_bursts") or {}).items():
            METRICS.set_counter("decode_bursts_total", n,
                                label_str(model=name, sampler=branch))
        lc = stats.get("lifecycle")
        if lc:
            for skey, mkey in _LIFECYCLE_COUNTERS:
                METRICS.set_counter(mkey, lc.get(skey, 0),
                                    label_str(model=name))
            METRICS.set_gauge("late_dispatch_seconds_total",
                              lc.get("late_dispatch_s", 0.0),
                              label_str(model=name))
        # preemptive priority scheduler (ISSUE 10): preempt/resume
        # totals + per-class queue depth (queued + parked-for-resume)
        sch = stats.get("scheduler")
        if sch and sch.get("preempt"):
            for skey, mkey in _SCHED_COUNTERS:
                METRICS.set_counter(mkey, sch.get(skey, 0),
                                    label_str(model=name))
            METRICS.set_gauge("resume_queue_depth",
                              sch.get("resume_depth", 0),
                              label_str(model=name))
            for cls, n in (sch.get("queued_by_class") or {}).items():
                METRICS.set_gauge("queue_depth_class", n,
                                  label_str(model=name, priority=cls))
            # resume-reserve autosize (ISSUE 14 satellite): the
            # EFFECTIVE reserve — explicit knob, or the preemption-rate
            # EWMA-derived value when the knob is 0
            METRICS.set_gauge("resume_reserve_pages",
                              sch.get("resume_reserve_pages", 0),
                              label_str(model=name))
        # engine replica pool (ISSUE 14): pool width, per-replica load,
        # migration totals by reason. engines=1 exports width 1 and no
        # per-replica/pool series (plain Engine stats carry no "pool")
        METRICS.set_gauge("engine_replicas",
                          stats.get("engine_replicas", 1),
                          label_str(model=name))
        for r in (stats.get("replicas") or []):
            rl = label_str(model=name, replica=str(r.get("replica", 0)))
            METRICS.set_gauge("replica_queue_depth", r.get("queued", 0), rl)
            METRICS.set_gauge("replica_slots_in_flight",
                              r.get("slots_in_flight", 0), rl)
        pool = stats.get("pool")
        if pool:
            for reason, n in (pool.get("migrations") or {}).items():
                METRICS.set_counter("replica_migrations_total", n,
                                    label_str(model=name, reason=reason))
            METRICS.set_counter("pool_affinity_hits_total",
                                pool.get("affinity_hits", 0),
                                label_str(model=name))
            METRICS.set_counter("pool_affinity_misses_total",
                                pool.get("affinity_misses", 0),
                                label_str(model=name))
            # SLO-driven autoscaling (ISSUE 19): target width + decision/
            # suppressed-flap counters by direction. Absent unless
            # autoscale=1 built a policy.
            METRICS.set_gauge("engine_replicas_target",
                              pool.get("replicas_target",
                                       stats.get("engine_replicas", 1)),
                              label_str(model=name))
            auto = pool.get("autoscale")
            if auto:
                for d, n in (auto.get("decisions") or {}).items():
                    METRICS.set_counter("autoscale_decisions_total", n,
                                        label_str(model=name, direction=d))
                for d, n in (auto.get("flaps_suppressed") or {}).items():
                    METRICS.set_counter(
                        "autoscale_flaps_suppressed_total", n,
                        label_str(model=name, direction=d))
        # streamed weight-load + in-backend prefetch stats (ISSUE 19)
        ws = stats.get("weight_stream")
        if ws:
            METRICS.set_counter("weight_prefetch_hits_total",
                                1 if ws.get("prefetch_hit") else 0,
                                label_str(model=name, source="backend"))
            METRICS.set_counter("weight_prefetch_bytes_total",
                                ws.get("bytes", 0),
                                label_str(model=name, source="backend"))
        # speculative decoding (ISSUE 13): per-round proposal/acceptance
        # totals + the derived acceptance rate, skipped when the engine
        # resolved speculation off (non-llama, lockstep, draft=0)
        spec = stats.get("spec")
        if spec and spec.get("mode") not in (None, "off"):
            for skey, mkey in _SPEC_COUNTERS:
                METRICS.set_counter(mkey, spec.get(skey, 0),
                                    label_str(model=name))
            METRICS.set_gauge("spec_acceptance_rate",
                              spec.get("acceptance_rate", 0.0),
                              label_str(model=name))
            # ISSUE 18: per-acceptance-mode split (greedy vs sampled)
            for mode, c in (spec.get("by_mode") or {}).items():
                for skey, mkey in _SPEC_COUNTERS:
                    METRICS.set_counter(
                        mkey, c.get(skey, 0),
                        label_str(model=name, mode=mode))
                METRICS.set_gauge("spec_acceptance_rate",
                                  c.get("acceptance_rate", 0.0),
                                  label_str(model=name, mode=mode))
        # system observability (ISSUE 8): compile counters, memory
        # watermarks, goodput/MFU
        so = stats.get("sysobs")
        if so:
            comp = so.get("compiles") or {}
            METRICS.set_counter("xla_compiles_total",
                                comp.get("compiles_total", 0),
                                label_str(model=name))
            METRICS.set_counter("xla_compiles_after_warmup_total",
                                comp.get("compiles_after_warmup", 0),
                                label_str(model=name))
            # float seconds: exposed as a gauge (set_counter truncates)
            METRICS.set_gauge("xla_compile_seconds",
                              comp.get("compile_seconds_total", 0.0),
                              label_str(model=name))
            gp = so.get("goodput") or {}
            METRICS.set_counter("goodput_tokens_total",
                                gp.get("goodput_tokens_total", 0),
                                label_str(model=name))
            METRICS.set_gauge("goodput_tok_s", gp.get("goodput_tok_s", 0.0),
                              label_str(model=name))
            METRICS.set_gauge("mfu", gp.get("mfu", 0.0),
                              label_str(model=name))
            for k, v in (so.get("watermarks") or {}).items():
                METRICS.set_gauge(f"mem_{k}", v, label_str(model=name))
            METRICS.set_gauge("mem_weight_bytes",
                              so.get("weight_bytes", 0),
                              label_str(model=name))
            frag = so.get("fragmentation")
            if frag:
                METRICS.set_gauge("mem_pool_frag_holes",
                                  frag.get("hole_pages", 0),
                                  label_str(model=name))
                METRICS.set_gauge("mem_pool_frag_ratio",
                                  frag.get("ratio", 0.0),
                                  label_str(model=name))
            # device allocator stats (ISSUE 12 satellite): real HBM
            # numbers when the backend platform exposes memory_stats()
            for dm in so.get("device_mem") or []:
                for key in _DEVICE_MEM_GAUGES:
                    if key in dm:
                        METRICS.set_gauge(
                            f"mem_device_{key}", dm[key],
                            label_str(model=name, device=str(dm["id"])))
            hm = so.get("host_memory") or {}
            for key, metric in _HOST_MEM_GAUGES:
                if key in hm:
                    METRICS.set_gauge(metric, hm[key], label_str(model=name))
        # per-class SLO engine (ISSUE 12): burn-rate gauges + violation
        # counters per (priority class, metric); the flight recorder's
        # dump/suppression totals ride the same pull
        slo = stats.get("slo")
        if slo:
            METRICS.set_gauge("slo_error_budget",
                              slo.get("error_budget", 0.0),
                              label_str(model=name))
            for cls, metrics_d in (slo.get("classes") or {}).items():
                for metric, s in (metrics_d or {}).items():
                    labels = label_str(model=name, priority=cls,
                                       slo_metric=metric)
                    METRICS.set_gauge("slo_objective_ms",
                                      s.get("objective_ms", 0.0), labels)
                    METRICS.set_counter("slo_violations_total",
                                        s.get("violations", 0), labels)
                    for skey, window in _SLO_WINDOWS:
                        METRICS.set_gauge(
                            "slo_burn_rate", s.get(skey, 0.0),
                            label_str(model=name, priority=cls,
                                      slo_metric=metric, window=window))
        fr = stats.get("flight_recorder")
        if fr:
            METRICS.set_counter("flight_dumps_total", fr.get("dumps", 0),
                                label_str(model=name))
            METRICS.set_counter("flight_dumps_suppressed_total",
                                fr.get("suppressed", 0),
                                label_str(model=name))
        # per-span exemplars (ISSUE 8 satellite, closes the PR-6
        # follow-up): worst-since-last-pull observation per histogram,
        # tagged with its request correlation id
        for hname, ex in (stats.get("hist_exemplars") or {}).items():
            if hname in _LATENCY_HISTOGRAMS:
                METRICS.set_exemplar(hname, label_str(model=name),
                                     ex.get("value", 0.0),
                                     ex.get("trace_id", ""),
                                     ex.get("ts", 0.0))
        if stats.get("kv_layout") != "paged":
            continue
        for key in _POOL_GAUGES:
            if key in stats:
                state_name = key[len("kv_pages_"):]
                METRICS.set_gauge(
                    "kv_pool_pages",
                    stats[key],
                    label_str(model=name, state=state_name))
        if "kv_pool_oversubscription" in stats:
            METRICS.set_gauge("kv_pool_oversubscription",
                              stats["kv_pool_oversubscription"],
                              label_str(model=name))
        pc = stats.get("prefix_cache")
        if pc:
            METRICS.set_gauge("prefix_cache_entries", pc.get("entries", 0),
                              label_str(model=name))
            for key in _PCACHE_COUNTERS:
                METRICS.set_counter(f"prefix_cache_{key}_total",
                                    pc.get(key, 0), label_str(model=name))
        off = stats.get("kv_offload")
        if off:
            METRICS.set_gauge("kv_offload_host_bytes", off.get("bytes", 0),
                              label_str(model=name))
            for skey, mkey in _OFFLOAD_COUNTERS:
                METRICS.set_counter(f"kv_offload_{mkey}_total",
                                    off.get(skey, 0), label_str(model=name))
            for skey, mkey in _PREFETCH_COUNTERS:
                METRICS.set_counter(f"kv_prefetch_{mkey}_total",
                                    off.get(skey, 0), label_str(model=name))
            METRICS.set_gauge("kv_prefetch_inflight",
                              off.get("prefetch_inflight", 0),
                              label_str(model=name))
        ka = stats.get("kv_audit")
        if ka:
            for key in _KV_AUDIT_COUNTERS:
                METRICS.set_counter(f"kv_audit_{key}_total",
                                    ka.get(key, 0), label_str(model=name))
        # cross-host KV federation (ISSUE 17): the peer tier's transfer
        # totals; absent unless kv_peers= armed a federated tier
        ks = stats.get("kv_stream")
        if ks:
            for skey, mkey in _KV_STREAM_COUNTERS:
                METRICS.set_counter(f"kv_stream_{mkey}_total",
                                    ks.get(skey, 0), label_str(model=name))
            METRICS.set_gauge("kv_stream_inflight", ks.get("inflight", 0),
                              label_str(model=name))
            METRICS.set_gauge("kv_stream_peers_online",
                              ks.get("peers_online", 0),
                              label_str(model=name))
        # admission capacity after autoscale co-scaling (ISSUE 20): the
        # effective queue limit tracks live width, so shed behavior is
        # observable next to queue_depth
        if "queue_limit" in stats:
            METRICS.set_gauge("engine_queue_limit",
                              stats.get("queue_limit", 0),
                              label_str(model=name))
        # cluster width + prefill/decode disaggregation handoffs
        cl = stats.get("cluster")
        if cl:
            METRICS.set_gauge("cluster_hosts", cl.get("hosts_alive", 0),
                              label_str(model=name))
            # process-mode control plane (ISSUE 20): failure-detector
            # states, heartbeat RTT, and the RPC retry/timeout ledger
            for st in ("alive", "suspect", "dead"):
                METRICS.set_gauge(
                    "cluster_host_state",
                    sum(1 for v in (cl.get("host_states") or {}).values()
                        if v == st),
                    label_str(model=name, state=st))
            for hid, hb in (cl.get("heartbeat") or {}).items():
                METRICS.set_gauge("cluster_heartbeat_rtt_ms",
                                  hb.get("rtt_ms", 0.0),
                                  label_str(model=name, host=hid))
            rpc = cl.get("rpc") or {}
            for op, n in (rpc.get("retries") or {}).items():
                METRICS.set_counter("cluster_rpc_retries_total", n,
                                    label_str(model=name, op=op))
            for op, n in (rpc.get("timeouts") or {}).items():
                METRICS.set_counter("cluster_rpc_timeouts_total", n,
                                    label_str(model=name, op=op))
        dg = stats.get("disagg")
        if dg:
            METRICS.set_counter("disagg_handoffs_total",
                                dg.get("handoffs", 0),
                                label_str(model=name,
                                          role=dg.get("role", "both")))
    # frontend weight byte-warmer (ISSUE 19): OS-page-cache warm totals
    # for predicted-next gallery models. Process-level (the warmer spans
    # models), so labeled by source rather than model — the backend's
    # in-process stream stats export the source="backend" twin above
    wp = getattr(state.caps, "weight_prefetcher", None)
    if wp is not None:
        ws = wp.snapshot()
        METRICS.set_counter("weight_prefetch_hits_total",
                            ws.get("hits", 0),
                            label_str(source="frontend"))
        METRICS.set_counter("weight_prefetch_bytes_total",
                            ws.get("bytes_total", 0),
                            label_str(source="frontend"))


async def metrics(request):
    state = get_state(request)
    if state.config.disable_metrics_endpoint:
        return api_error("metrics disabled", 404)
    await state.run_blocking(_refresh_engine_metrics, state)
    # full Content-Type set via headers: aiohttp's content_type= kwarg
    # rejects parameters (";"), and the exposition version IS part of
    # the Prometheus scrape contract (ISSUE 8 satellite)
    return web.Response(text=METRICS.render(),
                        headers={"Content-Type": CONTENT_TYPE})


def _collect_traces(state) -> dict:
    """Merge the HTTP process's span ring AND every loaded model's ring
    into ONE clock-aligned Chrome trace JSON (ISSUE 12 tentpole): the
    frontend is pid 0 ("localai-http"), each backend its own pid with
    its slot/scheduler tracks under it. Backend timestamps are relative
    to THAT process's trace epoch, so each event is shifted by

        (backend_t0_epoch - offset_s - frontend_t0_epoch) µs

    where offset_s is the backend-vs-frontend wall-clock skew
    (loader.LoadedModel.clock): 0 for a backend the model manager
    started on this machine, else the estimate of the shortest of three
    Health round trips after the load, its error within rtt_s / 2
    (loader.measure_clock; never the LoadModel round trip). Backends
    without GetTrace or without the epoch block (old fakes) and RPC
    failures are skipped/unshifted — a debug surface must never 500
    because one backend is old."""
    import json as _json

    from localai_tpu.services.tracing import chrome_trace, frontend_tracer

    front = chrome_trace(frontend_tracer(), pid=0,
                         process_name="localai-http")
    f_epoch = front["localai"]["t0_epoch"]
    events: list = list(front["traceEvents"])
    clocks: dict = {}
    pid = 0
    for name in state.caps.loader.list_loaded():
        lm = state.caps.loader.get(name)
        if lm is None:
            continue
        try:
            r = lm.client.get_trace(timeout=5.0)
            trace = _json.loads(bytes(r.message).decode("utf-8"))
        except Exception:
            continue
        pid += 1
        clock = getattr(lm, "clock", None) or {}
        b_epoch = float((trace.get("localai") or {}).get("t0_epoch", 0.0)
                        or 0.0)
        shift_us = ((b_epoch - clock.get("offset_s", 0.0) - f_epoch) * 1e6
                    if b_epoch else 0.0)
        clocks[name] = {"offset_s": clock.get("offset_s", 0.0),
                        "rtt_s": clock.get("rtt_s", 0.0),
                        "t0_epoch": b_epoch, "shift_us": round(shift_us, 1)}
        for ev in trace.get("traceEvents", []):
            ev["pid"] = pid
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                ev["args"] = {"name": f"localai-engine:{name}"}
            elif shift_us and "ts" in ev:
                ev["ts"] = round(ev["ts"] + shift_us, 1)
            events.append(ev)
    return {"displayTimeUnit": "ms", "traceEvents": events,
            "localai": {"t0_epoch": f_epoch, "clocks": clocks}}


async def debug_trace(request):
    """Chrome trace-event JSON of every loaded engine's span ring —
    load the response body at https://ui.perfetto.dev."""
    state = get_state(request)
    trace = await state.run_blocking(_collect_traces, state)
    return web.json_response(trace)


def _backend_state_payloads(state) -> dict:
    """Pull each loaded backend's GetState JSON (engine snapshot + event
    ring). Backends without GetState (tts, diffusion, old fakes) answer
    UNIMPLEMENTED and are skipped — debug surfaces never 500 because one
    backend can't answer."""
    import json as _json

    out = {}
    for name in state.caps.loader.list_loaded():
        lm = state.caps.loader.get(name)
        if lm is None:
            continue
        try:
            r = lm.client.get_state(timeout=5.0)
            out[name] = _json.loads(bytes(r.message).decode("utf-8"))
        except Exception:
            continue
    return out


def _collect_state(state) -> dict:
    """One live-JSON snapshot of the whole serving system (ISSUE 8):
    core uptime + loader recovery stats + per-engine slots/queues/pool
    map/compile history, plus the core process's own event-log ring."""
    try:
        loader_stats = state.caps.loader.stats()
    except Exception:
        loader_stats = {}
    payloads = _backend_state_payloads(state)
    out = {
        "uptime_s": round(time.time() - state.started_at, 1),
        "version": __version__,
        "loader": loader_stats,
        "models": {name: p.get("state") for name, p in payloads.items()},
        "eventlog": EVENTS.snapshot(),
    }
    # predictive weight prefetch (ISSUE 19): the frontend byte-warmer's
    # counters + the request-log scores it predicts from. Absent unless
    # some model armed weight_prefetch=1 (the warmer is built lazily)
    wp = getattr(state.caps, "weight_prefetcher", None)
    if wp is not None:
        out["weight_prefetch"] = {
            "warmer": wp.snapshot(),
            "requests": state.caps.model_requests.snapshot(),
        }
    return out


async def debug_state(request):
    """Live JSON of engine internals: slots in flight, queue depths, kv
    pool map, breaker state, last N compiles (ISSUE 8 tentpole)."""
    state = get_state(request)
    snap = await state.run_blocking(_collect_state, state)
    return web.json_response(snap)


def _collect_events(state, last: int = 0) -> list:
    """Merge the core process's event ring with every backend's (pulled
    over GetState), tag each record's origin, and return them in time
    order — one correlation-id'd stream across process boundaries."""
    merged = [dict(ev, proc="core") for ev in EVENTS.events()]
    for name, p in _backend_state_payloads(state).items():
        lm = state.caps.loader.get(name)
        # clock-handshake correction (ISSUE 12): backend events carry
        # the BACKEND's wall clock; subtracting the measured offset puts
        # them on the frontend timeline so the sort below is honest
        off = (getattr(lm, "clock", None) or {}).get("offset_s", 0.0) \
            if lm is not None else 0.0
        for ev in p.get("events") or []:
            ev = dict(ev, proc=f"backend:{name}", model=name)
            if off and "ts" in ev:
                ev["ts"] = ev["ts"] - off
            merged.append(ev)
    merged.sort(key=lambda ev: ev.get("ts", 0.0))
    if last > 0:
        merged = merged[-last:]
    return merged


async def debug_events(request):
    """Merged structured event log (admissions, sheds, timeouts,
    respawns, circuit transitions, compile storms, pool pressure) from
    the core and every backend: GET /debug/events[?last=N]."""
    state = get_state(request)
    try:
        last = int(request.query.get("last", 0))
    except ValueError:
        return api_error("last must be an integer", 400)
    events = await state.run_blocking(_collect_events, state, last)
    return web.json_response({"events": events, "count": len(events)})


async def debug_kv(request):
    """KV lifecycle view per loaded model (ISSUE 15): tier map,
    per-chain genealogy, fragmentation layout, audit counters + last
    violations and the ledger tail. Rides the "kv" key of each
    backend's GetState; models with kv_audit=off (or no pages) answer
    the {"mode": "off"} shape, and an EnginePool answers the merged
    multi-replica view."""
    state = get_state(request)
    payloads = await state.run_blocking(_backend_state_payloads, state)
    return web.json_response(
        {"models": {name: p.get("kv") or {"mode": "off"}
                    for name, p in payloads.items()}})


async def debug_profile(request):
    """Capture a jax.profiler device trace on a loaded backend:
    GET /debug/profile?seconds=N[&model=name]. Returns the backend-local
    directory holding the TensorBoard/perfetto capture."""
    state = get_state(request)
    try:
        seconds = float(request.query.get("seconds", 3))
    except ValueError:
        return api_error("seconds must be a number", 400)
    model = request.query.get("model", "")
    loaded = state.caps.loader.list_loaded()
    if model and model not in loaded:
        return api_error(f"model {model} is not loaded", 404)
    names = [model] if model else list(loaded)
    for name in names:
        lm = state.caps.loader.get(name)
        if lm is None:
            continue
        try:
            # the capture itself, then stop_trace: 7-8 s per captured
            # second of a busy device whose steps are a few hundred
            # operations (PERF.md section 6, PR 25), and 25 s where a
            # step is 1500 small ones (models/xing4.py: section 6, PR 50,
            # where 30 + 12 x seconds gave up on a capture of 2 s)
            r = await state.run_blocking(
                lm.client.profile, seconds, 60.0 + 60.0 * seconds)
        except Exception as e:
            return api_error(f"profile RPC failed: {e}", 502)
        return web.json_response({
            "model": name,
            "success": bool(r.success),
            "capture_dir": r.message,
            "seconds": seconds,
        }, status=200 if r.success else 500)
    return api_error("no profilable model loaded", 404)


# --------------- tts / sound ---------------

async def tts(request):
    state = get_state(request)
    body = await request.json()
    model = body.get("model") or body.get("backend") or ""
    if not model:
        return api_error("model is required", 400, "invalid_request_error")
    mc = state.caps.resolve(model)
    return await run_audio_capability(
        request, lambda dst: state.caps.tts(
            mc, body.get("input", ""), body.get("voice", ""),
            body.get("language", ""), dst))


async def elevenlabs_tts(request):
    state = get_state(request)
    body = await request.json()
    voice_id = request.match_info["voice_id"]
    model = body.get("model_id") or ""
    if not model:
        return api_error("model_id is required", 400, "invalid_request_error")
    mc = state.caps.resolve(model)
    return await run_audio_capability(
        request, lambda dst: state.caps.tts(
            mc, body.get("text", ""), voice_id, body.get("language_code", ""), dst))


async def sound_generation(request):
    state = get_state(request)
    body = await request.json()
    model = body.get("model_id") or body.get("model") or ""
    if not model:
        return api_error("model is required", 400, "invalid_request_error")
    mc = state.caps.resolve(model)
    return await run_audio_capability(
        request, lambda dst: state.caps.sound_generation(
            mc, body.get("text", ""), dst,
            body.get("duration_seconds"), body.get("temperature")))


# --------------- rerank ---------------

async def rerank(request):
    state = get_state(request)
    body = await request.json()
    model = body.get("model") or ""
    if not model:
        return api_error("model is required", 400, "invalid_request_error")
    mc = state.caps.resolve(model)
    res = await state.run_blocking(
        state.caps.rerank, mc, body.get("query", ""),
        list(body.get("documents", [])), int(body.get("top_n") or 0))
    return web.json_response({
        "model": model,
        "usage": {"total_tokens": res.usage.total_tokens,
                  "prompt_tokens": res.usage.prompt_tokens},
        "results": [
            {"index": r.index, "relevance_score": r.relevance_score,
             "document": {"text": r.text}}
            for r in res.results
        ],
    })


# --------------- tokenize ---------------

async def tokenize(request):
    state = get_state(request)
    body = await request.json()
    model = body.get("model") or ""
    if not model:
        return api_error("model is required", 400, "invalid_request_error")
    mc = state.caps.resolve(model)
    tokens = await state.run_blocking(state.caps.tokenize, mc, body.get("content", ""))
    return web.json_response({"tokens": tokens})


# --------------- stores ---------------

def _store_client(request):
    return get_state(request).caps.store_client()


async def stores_set(request):
    state = get_state(request)
    body = await request.json()
    keys = body.get("keys", [])
    values = body.get("values", [])
    if len(keys) != len(values):
        return api_error("keys and values must have equal length", 400)
    client = await state.run_blocking(_store_client, request)
    await state.run_blocking(client.stores_set, pb.StoresSetOptions(
        keys=[pb.StoresKey(floats=k) for k in keys],
        values=[pb.StoresValue(bytes=str(v).encode()) for v in values],
    ))
    return web.json_response({})


async def stores_delete(request):
    state = get_state(request)
    body = await request.json()
    client = await state.run_blocking(_store_client, request)
    await state.run_blocking(client.stores_delete, pb.StoresDeleteOptions(
        keys=[pb.StoresKey(floats=k) for k in body.get("keys", [])]))
    return web.json_response({})


async def stores_get(request):
    state = get_state(request)
    body = await request.json()
    client = await state.run_blocking(_store_client, request)
    res = await state.run_blocking(client.stores_get, pb.StoresGetOptions(
        keys=[pb.StoresKey(floats=k) for k in body.get("keys", [])]))
    return web.json_response({
        "keys": [list(k.floats) for k in res.keys],
        "values": [v.bytes.decode() for v in res.values],
    })


async def stores_find(request):
    state = get_state(request)
    body = await request.json()
    client = await state.run_blocking(_store_client, request)
    res = await state.run_blocking(client.stores_find, pb.StoresFindOptions(
        key=pb.StoresKey(floats=body.get("key", [])),
        top_k=int(body.get("topk") or body.get("top_k") or 10)))
    return web.json_response({
        "keys": [list(k.floats) for k in res.keys],
        "values": [v.bytes.decode() for v in res.values],
        "similarities": list(res.similarities),
    })


# --------------- backend monitor / system ---------------

async def backend_monitor(request):
    """(reference: core/services/backend_monitor.go + endpoint)"""
    state = get_state(request)
    if request.method == "POST":
        body = await request.json()
        model = body.get("model", "")
    else:
        model = request.query.get("model", "")
    if not model:
        return api_error("model is required", 400, "invalid_request_error")
    lm = state.caps.loader.get(model)
    if lm is None:
        return api_error(f"model {model} is not loaded", 404)
    status = await state.run_blocking(lm.client.status)
    return web.json_response({
        "memory_info": {"total": status.memory.total,
                        "breakdown": dict(status.memory.breakdown)},
        "state": pb.StatusResponse.State.Name(status.state),
    })


async def backend_shutdown(request):
    state = get_state(request)
    body = await request.json()
    model = body.get("model", "")
    if not model:
        return api_error("model is required", 400, "invalid_request_error")
    await state.run_blocking(state.caps.loader.shutdown_model, model)
    return web.json_response({})


def _backend_devices(state) -> list:
    """Devices as the loaded backends report them (GetState -> engine
    state_snapshot). The HTTP process never asks jax itself: touching a
    backend here would take the chip from the runner that needs it —
    one process owns a chip. Nothing loaded -> nothing known -> []."""
    devices = {}
    for model, payload in _backend_state_payloads(state).items():
        st = payload.get("state") or {}
        for d in st.get("device_mem") or []:
            devices.setdefault(d["id"], {
                "id": d["id"], "platform": st.get("platform", ""),
                "kind": d.get("device_kind", ""), "models": []})
            devices[d["id"]]["models"].append(model)
    return [devices[i] for i in sorted(devices)]


async def system_info(request):
    """(reference: routes/localai.go:60-66 /system)"""
    state = get_state(request)
    devices = await state.run_blocking(_backend_devices, state)
    return web.json_response({
        "backends": sorted(state.caps.loader.list_loaded()),
        "devices": devices,
        "loaded_models": sorted(state.caps.loader.list_loaded()),
        "version": __version__,
    })


async def token_metrics(request):
    """(reference: core/http/endpoints/localai/get_token_metrics.go)"""
    state = get_state(request)
    body = {}
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:
            body = {}
    model = body.get("model") or request.query.get("model", "")
    if not model:
        return api_error("model is required", 400, "invalid_request_error")
    lm = state.caps.loader.get(model)
    if lm is None:
        return api_error(f"model {model} is not loaded", 404)
    m = await state.run_blocking(lm.client.get_metrics)
    try:
        import json as _json

        engine_stats = _json.loads(m.prompt_json_for_slot or "{}")
    except Exception:
        engine_stats = {}
    return web.json_response({
        "model": model,
        "tokens_per_second": m.tokens_per_second,
        "tokens_generated": m.tokens_generated,
        "slots_active": m.slots_active,
        "slots_total": m.slots_total,
        "queued": m.queued,
        "uptime_s": m.uptime_s,
        # full engine stats dict (kv pool occupancy, prefix-cache
        # hit/miss/evict, TTFT decomposition) — see Engine.metrics()
        "engine": engine_stats,
    })


# --------------- gallery ---------------

async def models_apply(request):
    state = get_state(request)
    if state.gallery_service is None:
        return api_error("gallery service not available", 503)
    body = await request.json()
    job_id = state.gallery_service.submit_apply(body)
    return web.json_response({
        "uuid": job_id,
        "status": str(request.url.with_path(f"/models/jobs/{job_id}")),
    })


async def models_delete(request):
    state = get_state(request)
    if state.gallery_service is None:
        return api_error("gallery service not available", 503)
    name = request.match_info["name"]
    job_id = state.gallery_service.submit_delete(name)
    return web.json_response({
        "uuid": job_id,
        "status": str(request.url.with_path(f"/models/jobs/{job_id}")),
    })


async def models_available(request):
    state = get_state(request)
    if state.gallery_service is None:
        return api_error("gallery service not available", 503)
    models = await state.run_blocking(state.gallery_service.list_available)
    return web.json_response(models)


async def models_job_status(request):
    state = get_state(request)
    if state.gallery_service is None:
        return api_error("gallery service not available", 503)
    status = state.gallery_service.job_status(request.match_info["uuid"])
    if status is None:
        return api_error("job not found", 404)
    return web.json_response(status)


async def models_all_jobs(request):
    state = get_state(request)
    if state.gallery_service is None:
        return api_error("gallery service not available", 503)
    return web.json_response(state.gallery_service.all_jobs())


async def add_gallery(request):
    state = get_state(request)
    body = await request.json()
    state.config.galleries.append({"name": body.get("name"), "url": body.get("url")})
    return web.json_response({"name": body.get("name")})


async def remove_gallery(request):
    state = get_state(request)
    body = await request.json()
    state.config.galleries = [
        g for g in state.config.galleries if g.get("name") != body.get("name")
    ]
    return web.json_response({})


# --------------- p2p parity ---------------

async def p2p_nodes(request):
    """On TPU the 'swarm' is the static device mesh — report it in the
    same shape the reference reports federated nodes (reference:
    core/http/endpoints/localai/p2p.go), as the loaded backends see it
    (_backend_devices)."""
    state = get_state(request)
    devices = await state.run_blocking(_backend_devices, state)
    nodes = [{"name": f"device-{d['id']}", "id": str(d["id"]),
              "online": True, "platform": d["platform"]} for d in devices]
    return web.json_response({"nodes": nodes, "federated_nodes": []})


async def p2p_token(request):
    return web.json_response({"token": ""})
