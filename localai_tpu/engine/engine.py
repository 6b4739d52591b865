"""The TPU serving engine: continuous batching over a compiled decode step.

Re-design of the reference's slot-based continuous-batching server
(reference: backend/cpp/llama/grpc-server.cpp — llama_client_slot :162-301,
task queue utils.hpp:192,336, update_slots hot loop :1578-2013) for XLA's
compilation model:

  * The decode step is ONE jitted function over ALL slots, compiled once —
    inactive slots ride along masked (static shapes, no recompiles).
  * Prompts are ingested by a RAGGED PACKED PREFILL step between decode
    steps (reference packs prompt chunks and decode tokens into one
    llama_batch, :1671+): each tick packs the pending prompt tails of
    ALL queued slots — fresh finals, continued prefix-reuse tails, long
    prompts' chunks, context-shift re-prefills — into ONE
    [total_tokens] batch padded only to a small set of total-token
    buckets, and runs one compiled program that writes every segment's
    KV rows through its own slot's page table and samples first tokens
    for the final segments (models/llama.py ragged_prefill;
    ops/ragged_prefill.py + ops/pallas/ragged_prefill.py). A
    prefill_token_budget caps packed tokens per tick so decode ITL
    stays bounded, and admitting a long prompt never stalls decode for
    active slots by more than one budget's compute.
    ``prefill_packed=0`` restores the per-slot bucketed path (chunks +
    batched same-bucket finals + fused admission) bit-for-bit.
  * KV PREFIX REUSE: per-slot cache contents are tracked host-side; a new
    request is admitted into the free slot sharing the longest common
    token prefix and only the suffix is prefilled (reference:
    grpc-server.cpp:1721-1835 cache_tokens common-prefix reuse).
  * CONTEXT SHIFT: when a slot's cache fills mid-generation, the engine
    re-prefills the tail half of the context into the slot (chunked, so
    other slots keep decoding) and generation continues — the recompute
    equivalent of the reference's KV surgery (llama_kv_cache_seq_rm/add,
    grpc-server.cpp:1832,1916-1927), which XLA's immutable buffers and
    RoPE'd keys make the honest TPU design.
  * Sampling (full per-slot parameter suite) and the penalty-ring update
    are fused INTO the compiled steps — no per-token host round-trip for
    anything but the sampled ids themselves.
  * Admission/stop logic runs host-side on a dedicated engine thread,
    mirroring the reference's queue thread (grpc-server.cpp:2083-2096).
"""

from __future__ import annotations

import collections
import dataclasses
import os
import queue
import resource
import statistics
import threading
import time
import uuid
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.engine import sampling
from localai_tpu.engine.detok import IncrementalDetokenizer
from localai_tpu.engine.scheduler import (
    PRIORITY_CLASSES, PRIORITY_RANK, ResumeEntry, Scheduler,
    normalize_priority, parse_priority_weights)
from localai_tpu.services import sysobs
from localai_tpu.services.eventlog import EVENTS
from localai_tpu.services.faults import FAULTS
from localai_tpu.models import llama
from localai_tpu.ops import kvcache

# Engine-owned latency histograms, re-exposed over /metrics as real
# Prometheus histograms (services/metrics.py set_histogram). Buckets in
# seconds, sized for serving latencies: sub-ms dispatch costs up to
# multi-second TTFTs.
_HIST_BUCKETS = {
    "ttft_seconds": (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                     2.5, 5.0, 10.0, 30.0),
    "itl_seconds": (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                    0.25, 0.5, 1.0),
    "decode_burst_seconds": (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                             0.1, 0.25, 0.5, 1.0, 2.5),
    "prefill_dispatch_seconds": (0.0005, 0.001, 0.0025, 0.005, 0.01,
                                 0.025, 0.05, 0.1, 0.25, 0.5, 1.0),
}


@dataclasses.dataclass
class EngineConfig:
    num_slots: int = 8
    max_context: int = 2048
    prefill_buckets: tuple = (32, 128, 512, 2048)
    prefill_chunk: int = 512   # max prompt tokens per slot per prefill tick
    # RAGGED PACKED PREFILL (module doc): pack every queued slot's
    # pending prompt tail into ONE ragged dispatch per tick instead of
    # per-slot bucket-padded chunks/finals. llama-family, non-lockstep,
    # ga_n == 1 only — ineligible slots (multimodal, self-extend,
    # draft-mirrored) transparently take the per-slot path. 0 restores
    # the per-slot scheduling bit-for-bit.
    prefill_packed: bool = True
    # max packed prompt tokens per tick — the decode-ITL bound of the
    # packed path (a tick's pack stalls decode for one pack's compute).
    # 0 = auto: 2 * prefill_chunk, clamped to max_context.
    prefill_token_budget: int = 0
    # TokenWeave-style compute/communication overlap (models/llama.py +
    # parallel/sharding.py): packed-prefill layers split the token axis
    # in two so the out-proj / down-proj all-reduce of half N overlaps
    # the matmul of half N+1 on the tp mesh. Bit-exact (greedy output
    # byte-identical on or off). "auto" = only when the engine runs on
    # a mesh (single-chip programs have no collectives to hide);
    # "1"/"0" force.
    comm_overlap: str = "auto"
    context_shift: bool = True  # re-prefill tail window when a slot's cache fills
    cache_dtype: Any = jnp.bfloat16
    # KV layout (llama family): "auto" -> the PAGED page-pool layout
    # (ops/kvcache.py; ragged paged decode kernel on TPU) except in
    # multi-host lockstep mode, where the page table is leader-local
    # host state the followers can't replay -> contiguous. "paged" /
    # "contiguous" force it. Paged admission allocates pages lazily per
    # prefill chunk, shares prompt-prefix pages copy-on-write between
    # slots (ref-counted; the first divergent page is cloned) and
    # returns pages to a free list on finish.
    kv_layout: str = "auto"
    kv_page_size: int = 64
    # physical pages in the pool; 0 = num_slots * max_context/page_size
    # (exactly the contiguous reservation — never more HBM). Shrink to
    # oversubscribe against actual usage; retained prefixes of free
    # slots are reclaimed under pressure.
    kv_pool_pages: int = 0
    # cross-release prefix cache (engine/prefix_cache.py): on slot
    # release/context-shift, committed full pages are RETAINED in a
    # token-hash-keyed store instead of freed, and admission splices
    # matching chains into the new slot's table (zero KV row copies,
    # works after the source slot is long gone). Retained pages are
    # evicted LRU-first under pool pressure, so the knob costs no
    # correctness — only free-list headroom. Paged layout only; off
    # restores PR-1 behavior exactly.
    kv_prefix_cache: bool = True
    # minimum reusable rows for a prefix-cache hit (and the live-slot
    # share scan) to beat a clean prefill — a 1-page BOS match must
    # never force the slow continued-prefill path
    kv_prefix_cache_min_rows: int = 16
    # two-tier KV page store (engine/kv_offload.py): when _reclaim_pages
    # would evict a retained chain, its page rows are OFFLOADED to a
    # host-RAM store (same chained block hash keys, int8 pages kept
    # quantized) via a non-blocking device gather, and a prefix-cache
    # hit against an offloaded chain RESTORES the pages into freshly
    # allocated device rows with the upload overlapped against in-flight
    # decode work — the LRU cascades device -> host -> gone. Requires
    # the prefix cache; off restores the PR-2 lifecycle exactly.
    kv_offload: bool = True
    # host-tier byte budget (the host->gone edge of the LRU cascade)
    kv_host_pool_mb: int = 256
    # persist the host store here on graceful shutdown and reload it at
    # init (version/scope-checked; a mismatched or corrupt file is
    # ignored). "" = no persistence.
    kv_host_store_path: str = ""
    # --- long-context serving tier (ISSUE 16) ---
    # snap-back sliding window (SnapStream, arXiv:2511.03092): bound the
    # on-device KV working set to kv_sink_pages attention-sink head
    # pages + this many tail pages; the cold middle demotes to the host
    # tier page by page as decode advances (or drops, see
    # kv_window_policy), so context length is limited by host RAM, not
    # HBM. Paged layout + prefix cache only; 0 = off (bit-for-bit the
    # unwindowed path). Positions stay ABSOLUTE via pos_offset — the
    # window compacts cache rows, never RoPE positions.
    kv_window_pages: int = 0
    # attention-sink head pages pinned on device while a window is
    # active (StreamingLLM-style: the first tokens anchor attention)
    kv_sink_pages: int = 1
    # what happens to the demoted cold middle: "demote" offloads it to
    # the host tier (restorable — the default), "drop" discards it
    # under an explicit compression policy, recorded as a first-class
    # "compress" ledger op so kv_audit=strict stays clean
    kv_window_policy: str = "demote"
    # decode-time prefetch-ahead pipeline (PRESERVE, arXiv:2501.08192):
    # the scheduler scans queued requests each tick and issues
    # double-buffered host->device restores for the chain links their
    # admission will need, AHEAD of the admission — at most this many
    # restore batches in flight. 0 disables (restores happen
    # synchronously at admission, the pre-PR behavior).
    kv_prefetch_ahead: int = 2
    # speculative decoding: draft proposals per round (0 disables even
    # when a draft model is loaded); greedy slots only
    n_draft: int = 4
    # drafting mode (ISSUE 13): "auto" uses the loaded draft model when
    # one exists and falls back to model-free n-gram self-speculation
    # (prompt-lookup over the slot's own token ring) for llama-family
    # greedy slots; "model" / "ngram" force a drafter; "0" disables
    # speculation entirely. Greedy speculation is LOSSLESS whatever the
    # drafter proposes (see engine/speculative.py).
    draft: str = "auto"
    # n-gram length the prompt-lookup drafter matches against the token
    # ring (draft=ngram); longer grams propose less often but more
    # accurately on repetitive continuations
    spec_ngram: int = 3
    # decode BURST: run up to this many decode steps per device dispatch
    # (lax.scan), amortizing per-dispatch overhead (its size on the chip is
    # not measured; ROADMAP S5 re-tunes this value). Grammar-constrained
    # slots ride bursts speculatively (verify + free rollback at processing
    # time); bursts clamp to cache-capacity conditions, see _pick_burst.
    decode_burst: int = 16
    # decode bursts kept in flight on the device (r4): with depth 2 the
    # host's sync of burst N overlaps burst N+1's compute, so host-side
    # processing never idles the device. Deeper than 2 buys nothing (the
    # host work fits easily inside one burst) and worsens admission lag.
    pipeline_depth: int = 2
    # self-extend / group attention (reference: ga_n/ga_w slot state,
    # grpc-server.cpp:209-213, KV surgery :1904-1927): with ga_n > 1,
    # every completed window of ga_w raw tokens has its RoPE positions
    # divided by ga_n (cached keys re-rotated in place — rotations
    # compose, so this is exact and recomputeless), letting a model
    # trained to max_position_embeddings attend usefully over ga_n x
    # longer raw contexts. Cache ROWS are unaffected (context shift still
    # governs capacity).
    ga_n: int = 1
    ga_w: int = 512
    # request-lifecycle tracing (services/tracing.py): per-request spans
    # (queue_wait / admission / prefill dispatch / decode burst / detok /
    # stream flush) in a fixed ring, host-vs-device decomposition in
    # metrics()["trace"], Chrome trace export via trace_events().
    # trace=0 makes every record() call a no-op on the hot path.
    trace: bool = True
    trace_ring_size: int = 131072   # tracing.DEFAULT_RING_SIZE
    # slow-request structured log: when a finished request's TTFT or
    # end-to-end wall exceeds this many ms, log one WARNING with the
    # span decomposition. 0 disables.
    slow_request_ms: int = 0
    # --- fault-tolerant request lifecycle (ISSUE 7) ---
    # admission control: submit() sheds (structured 429-mapped error,
    # never an unbounded queue) once this many requests are already
    # waiting for a slot. 0 = unbounded (pre-PR-7 behavior).
    max_queued_requests: int = 256
    # queued requests that waited longer than this are shed at the next
    # admission tick — bounds worst-case queue sojourn under sustained
    # overload. 0 disables.
    max_queue_wait_ms: int = 0
    # per-request deadline from submit(): expired requests get a
    # structured timeout error and are cancelled through the normal
    # engine.cancel path (slot + pages released). 0 disables.
    request_timeout_ms: int = 0
    # stall watchdog: if a dispatched prefill/decode item sees no
    # sync-worker ready-set transition for this long, the engine dumps
    # the span ring to disk, aborts ONLY the stalled requests with
    # structured errors, and keeps serving. 0 disables (pre-PR-7
    # behavior: wait forever).
    dispatch_stall_ms: int = 30000
    # where stall ring dumps land; "" = the system temp dir.
    stall_dump_dir: str = ""
    # --- system observability (ISSUE 8) ---
    # structured event-log sink: a file path, "stderr", or "off"/"" for
    # ring-only (events are ALWAYS retained in the bounded in-memory
    # ring surfaced at /debug/events; this knob adds write-through).
    event_log: str = ""
    # event-log file-sink rotation bound (MB): at this size the file
    # rotates to <path>.1, one generation kept. 0 disables rotation.
    event_log_max_mb: int = 64
    # --- preemptive priority scheduler (ISSUE 10, engine/scheduler.py) ---
    # pause/offload/resume: a higher-priority request that cannot be
    # admitted PREEMPTS the lowest-class active slot — the victim pauses
    # at a burst boundary, its committed pages stay retained (offloading
    # host-side under pool pressure through the normal reclaim path),
    # and resume is plain re-admission through the prefix-splice /
    # host-restore tiers (a killed host entry degrades to a
    # byte-identical re-prefill). Also enables priority-ordered
    # admission, DRR prefill shares and shed fairness. 0 restores
    # strict-FIFO admission bit-for-bit.
    preempt: bool = True
    # deficit-round-robin weights for the high:normal:low classes'
    # shares of the packed-prefill token budget (colon-separated —
    # option values ride a comma-joined wire, so no commas)
    priority_weights: str = "4:2:1"
    # starvation guard: one request is never preempted more than this
    # many times; after that it is immune and runs to completion
    max_preemptions: int = 2
    # free pages held back from FRESH admissions while preempted
    # requests wait to resume (resumes themselves ignore the reserve,
    # so a resume can always make progress). 0 disables.
    resume_reserve_pages: int = 0
    # model-default priority class for requests that don't carry one
    # ("high" | "normal" | "low")
    priority: str = "normal"
    # starvation aging: queued/parked work older than this is treated
    # one class higher when ordering admissions. 0 disables.
    priority_aging_ms: int = 4000
    # --- per-class SLO engine (ISSUE 12, services/sysobs.py) ---
    # latency objectives per priority class, colon-separated
    # high:normal:low thresholds in ms (one value applies to every
    # class; named subsets like "high=250:low=5000" work too — option
    # values ride a comma-joined wire, so colon separates, as in
    # priority_weights). "" = no objective for that metric; all three
    # empty leaves the SLO engine unbuilt (zero per-request cost).
    slo_ttft_ms: str = ""
    slo_itl_ms: str = ""
    slo_queue_wait_ms: str = ""
    # error budget the burn rate is measured against: burn = (violation
    # fraction in window) / budget, so burn > 1 means the class misses
    # its SLO if the rate holds. 0.01 = a 99% objective.
    slo_error_budget: float = 0.01
    # --- KV lifecycle ledger + invariant auditor (ISSUE 15) ---
    # "off" = zero-cost no-op (no auditor object, no ledger, every hook
    # dissolves into one `is not None` check — like trace=0); "on" =
    # continuous report-only scans on the housekeeping cadence (default:
    # counters + kv_audit_violation events + flight dumps); "strict" =
    # violations raise KVAuditError, for tests and chaos rigs.
    kv_audit: str = "on"
    # --- prefill/decode disaggregation (ISSUE 17) ---
    # cluster role: "both" (the default — a normal engine, bit-for-bit
    # the single-host path), "prefill" (admission + packed prefill
    # only: once a slot's prefill completes and its first token is out,
    # the request is ejected via the PR-10 pause primitive, its chain
    # force-offloaded to the host tier, and the ResumeEntry handed to
    # the registered disagg_handoff — the cluster router streams the
    # chain to a decode host and re-admits it there), or "decode" (a
    # routing hint: the cluster router sends it no fresh prefill work;
    # the engine itself needs no restriction — a resumed admission's
    # splice prefill is part of decoding the handoff). With no handoff
    # registered a "prefill" engine serves requests to completion like
    # "both" — a request is never stranded on a role knob.
    disagg: str = "both"
    # --- SLO-driven replica autoscaling (ISSUE 19) ---
    # 0 (default) = bit-for-bit the static pool path: no policy object,
    # no policy thread, no prefetcher constructed. 1 = the pool's
    # housekeeping tick feeds live signals (SLO burn, queue fill, page
    # pressure, preemption EWMA) to engine/autoscale.AutoscalePolicy
    # and executes the returned EnginePool.resize(n) targets.
    autoscale: bool = False
    autoscale_min: int = 1
    autoscale_max: int = 0          # 0 = twice the configured engines=N
    # scale-out fires when the worst short-window SLO burn crosses
    # burn_out; scale-in needs sustained idle with burn under burn_in.
    autoscale_burn_out: float = 1.0
    autoscale_burn_in: float = 0.05
    # hysteresis brakes: same-direction dwell and opposite-direction
    # cool-down, both in ms (the bench rig shrinks them to seconds).
    autoscale_dwell_ms: int = 2000
    autoscale_cooldown_ms: int = 4000
    # --- predictive weight prefetch (ISSUE 19, PRESERVE-style) ---
    # 1 = model loads go through weights.stream_llama_params (leaf-at-
    # a-time, bounded host RAM) and the frontend warms the predicted-
    # next gallery model's parsed leaves into a host cache ahead of its
    # first request.
    weight_prefetch: bool = False
    # --- cluster control plane (ISSUE 20) ---
    # "inproc" (default) = every cluster host is an in-process handle:
    # no RPC server, no heartbeats — bit-for-bit the PR-17 path.
    # "process" = hosts run as separate OS processes behind
    # services/cluster_rpc.py, driven through RemoteHostHandle.
    cluster_mode: str = "inproc"
    # heartbeat probe cadence, and the failure-detector windows: a host
    # with no successful beat (or only slow beats) for suspect_ms is
    # SUSPECT (de-preferred in routing, no new KV-streaming work, its
    # streams stay alive); silent past dead_ms it is DEAD (byte-gated
    # stream recovery on siblings). suspect < dead, always.
    cluster_heartbeat_ms: int = 250
    cluster_suspect_ms: int = 1000
    cluster_dead_ms: int = 3000
    # control-plane per-op deadline + full-jitter retry schedule
    # (idempotent ops only: DIGEST/METRICS/HEARTBEAT/AUDIT; SUBMIT is
    # never auto-retried — recovery re-admits instead)
    cluster_rpc_timeout_ms: int = 2000
    cluster_rpc_retries: int = 3
    cluster_rpc_backoff_ms: int = 50
    # --- federated KV stream timing (ISSUE 20, was hardcoded) ---
    # a failed peer sits out cooldown_ms before being re-tried; negative
    # membership probes cache for negcache_ms; connect/IO timeout for
    # peer stream sockets. Tune together with the detector windows so
    # the KV tier and the control plane agree on peer health.
    kv_stream_cooldown_ms: int = 5000
    kv_stream_negcache_ms: int = 500
    kv_stream_connect_timeout_ms: int = 5000


@dataclasses.dataclass
class GenRequest:
    prompt_ids: list
    params: sampling.SamplingParamsHost = dataclasses.field(
        default_factory=sampling.SamplingParamsHost
    )
    max_new_tokens: int = 256
    stop_sequences: list = dataclasses.field(default_factory=list)
    ignore_eos: bool = False
    grammar: str = ""               # GBNF constrained decoding
    # prompt-cache persistence (reference: backend.proto:132-138,
    # options.go:182-191): committed KV rows + tokens saved to this path
    # on finish, restored on prefix match at admission
    prompt_cache_path: str = ""
    prompt_cache_ro: bool = False   # restore only, never write
    prompt_cache_all: bool = False  # persist generated rows too
    # multimodal (LLaVA-style): projected image embeddings to inject at
    # absolute prompt positions (prompt_ids holds pad tokens there)
    mm_positions: list = dataclasses.field(default_factory=list)  # [P] ints
    mm_vectors: Any = None          # np [P, hidden] float32
    request_id: str = ""
    # priority class ("high" | "normal" | "low"); "" = the model default
    # (EngineConfig.priority). Normalized by Engine.submit — unknown
    # values degrade to the default, never an error (ISSUE 10).
    priority: str = ""
    # filled by engine:
    out: "queue.Queue" = None  # receives StreamEvent, then None sentinel
    t_submit: float = 0.0      # stamped by Engine.submit (TTFT decomposition)
    deadline: float = 0.0      # monotonic; stamped by submit from request_timeout_ms

    def __post_init__(self):
        if not self.request_id:
            self.request_id = uuid.uuid4().hex[:16]
        if self.out is None:
            self.out = queue.Queue()


@dataclasses.dataclass
class StreamEvent:
    token_id: int
    text: str               # finalized delta (may be "")
    logprob: float
    finish_reason: Optional[str] = None  # "stop" | "length" | None
    prompt_tokens: int = 0
    completion_tokens: int = 0
    timings: Optional[dict] = None
    error: Optional[str] = None
    # burst-coalesced events carry every member token (r3: emitting one
    # queue event per token cost ~0.35 ms/token of host time on the 1-core
    # serving host — GIL/wakeup churn — and serialized against the next
    # dispatch; the engine now emits ONE event per slot per processed
    # burst). token_id/logprob above are the LAST member's.
    token_ids: Optional[list] = None
    logprobs: Optional[list] = None
    # lifecycle failure taxonomy (ISSUE 7): set alongside `error` so the
    # gRPC runner can map the failure to the right status code instead
    # of a blanket INTERNAL. "shed" | "timeout" | "stall" | None.
    error_kind: Optional[str] = None
    # crude client back-off hint derived from live queue depth / slot
    # occupancy; surfaced as Retry-After at the HTTP layer.
    retry_after_s: float = 0.0


def event_ids(events) -> list:
    """Flatten a stream of (possibly coalesced) events to token ids."""
    out = []
    for e in events:
        if e.token_ids:
            out.extend(e.token_ids)
        elif e.token_id >= 0:
            out.append(e.token_id)
    return out


def _merge_events(evs: list) -> StreamEvent:
    last = evs[-1]
    return dataclasses.replace(
        last,
        text="".join(e.text for e in evs),
        token_ids=[e.token_id for e in evs],
        logprobs=[e.logprob for e in evs],
    )


class _DispatchStall(Exception):
    """Raised by _wait_ready when a dispatched item saw no sync-worker
    ready-set transition within dispatch_stall_ms. Carries the wedged
    item so _handle_stall can abort exactly its requests."""

    def __init__(self, item):
        super().__init__("device dispatch stalled")
        self.item = item


# A dispatched item was LATE when it waited, from _overdue_ref to ready,
# more than LATE_MIN_S and LATE_FACTOR times the median pace (seconds a
# step) of its program kind's last PACE_ITEMS items. 3x: the slowest
# healthy burst of any cell of the ledger is under 1.5x its median, the
# stall of PERF.md section 6 (ROADMAP S13) 5-11x. 0.25 s: under it a
# stream's reader sees no hiccup.
LATE_FACTOR = 3.0
LATE_MIN_S = 0.25
PACE_ITEMS = 64

_RU_NAMES = ("majflt", "minflt", "nvcsw", "nivcsw",
             "proc_user_ms", "proc_sys_ms")


def _wait_rusage():
    """What a wait is charged with (_RU_NAMES): the calling thread's
    faults and context switches, and the whole process's CPU time, user
    and system - a wait in which the process burns system time is the
    kernel's (memory handed back, page tables), not the device's."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    cpu = resource.getrusage(resource.RUSAGE_SELF)
    return (ru.ru_majflt, ru.ru_minflt, ru.ru_nvcsw, ru.ru_nivcsw,
            cpu.ru_utime * 1e3, cpu.ru_stime * 1e3)


def _riders(item) -> list:
    """[(slot index, _Slot snapshot)] of a dispatched item."""
    return item.slots if isinstance(item, _Burst) else item.group


class _ReplicaDead(BaseException):
    """Chaos-only (ISSUE 14): the ``replica<N>_die`` fault kills this
    replica's loop thread the way a lost host would — BaseException so
    _run's ``except Exception`` recovery can NOT save it. Raised at the
    tick top, where the host mirrors (slots, token histories, emitted
    counts) are consistent with everything already flushed to the
    emitter, so the pool's crash recovery rebuilds resume state from an
    honest snapshot."""


class _Burst:
    """A dispatched decode burst awaiting host processing. Its packed
    results are synced by the engine's SYNC WORKER thread (one thread,
    device dispatch order — concurrent np.asarray calls from two threads
    convoy on the client's transfer path and can invert completion
    order, which metastably collapsed serving throughput ~7x)."""
    __slots__ = ("n_steps", "slots", "pack", "group", "t_dispatch",
                 "t_ready", "pack_np", "ids_np", "lps_np", "first_ids",
                 "first_lps", "folded", "skip_slots", "ready", "err",
                 "head", "spec_mask", "spec_width", "n_out_np",
                 "drafted_np", "spec_greedy", "experts_touched",
                 "kind", "t_ref", "ru")

    def __init__(self, n_steps, slots, pack, group=(), t_dispatch=0.0,
                 head=None):
        self.n_steps = n_steps
        self.slots = slots          # [(index, _Slot snapshot), ...]
        self.pack = pack            # device [2K+1(+2), S] f32
        # fused spec tick (ISSUE 13): per-slot spec mask and tokens per
        # round (n_draft + 1); spec_width 0 marks a plain burst
        self.spec_mask = None
        self.spec_width = 0
        self.n_out_np = None        # [R, S] per-round emit counts
        self.drafted_np = None      # [R, S] bool: the row had a draft
        self.spec_greedy = None     # [S] dispatch-time greedy snapshot
        # distinct experts touched, summed over steps and expert layers
        # (a family that reports its routing; _fold_burst)
        self.experts_touched = None
        self.group = list(group)    # fused-admission slots (subset of slots)
        # early-emit split: the _PendingPrefill head this burst is
        # chained off on-device. The sync worker readies the head FIRST
        # (dispatch order), so its first tokens emit before this burst
        # syncs; _fold_burst then reads first_ids from the head.
        self.head = head
        self.t_dispatch = t_dispatch
        self.t_ready = 0.0          # sync-worker completion stamp
        # pace record (_enqueue, _sync_worker, _note_ready): the program
        # kind, the reference point the wait is counted from, the sync
        # worker's rusage over the wait
        self.kind, self.t_ref, self.ru = "", 0.0, None
        self.pack_np = None
        self.ids_np = None
        self.lps_np = None
        self.first_ids = None       # [S] np (fused groups only)
        self.first_lps = None
        self.folded = False
        self.ready = threading.Event()
        self.err = None
        # slots whose host state was rolled back AFTER this burst was
        # dispatched (grammar rollback): the burst's tokens for them are
        # conditioned on a discarded token and must be dropped wholesale
        self.skip_slots: set = set()


class _PendingPrefill:
    """A dispatched final-prefill group awaiting its device results.

    The sampled-first-token sync runs on the engine's SYNC WORKER thread
    (np.asarray releases the GIL during the device wait), so the serving
    loop never blocks on a prefill that is still queued behind in-flight
    decode bursts."""
    __slots__ = ("group", "out_ids", "logprobs", "mu_out", "t0",
                 "t_ready", "ids_np", "lps_np", "mu_np", "ready", "err",
                 "split", "processed", "routed",
                 "kind", "t_ref", "ru")
    n_steps = 1     # a prefill is paced as a whole item
    t_dispatch = property(lambda self: self.t0)

    def __init__(self, group, out_ids, logprobs, mu_out, t0, split=False,
                 routed=False):
        self.group = group
        self.out_ids = out_ids
        self.logprobs = logprobs
        self.mu_out = mu_out
        self.t0 = t0
        self.t_ready = 0.0          # sync-worker completion stamp
        self.kind, self.t_ref, self.ru = "", 0.0, None
        self.ids_np = self.lps_np = self.mu_np = None
        self.ready = threading.Event()
        self.err = None
        # early-emit split head: device chain state was already updated
        # in-program, so processing only EMITS first tokens + stamps
        # timing — the chained burst carries the slots' mirror updates.
        # ``processed`` guards against double emission when _drain_fifo
        # block-syncs the burst past a not-yet-processed head.
        self.split = split
        self.processed = False
        # a packed prefill of a family that reports its routing: the
        # pack's route stats follow the segments' logprobs
        self.routed = routed


class _PendingOffload:
    """A dispatched device->host page gather awaiting its transfer.

    The gather itself is issued between decode dispatches (one jit call,
    no sync); the blocking np.asarray runs on the SYNC WORKER thread in
    dispatch order, so offloading never stalls the serving loop. Once
    materialized, the worker inserts the pages straight into the host
    store (HostPageStore locks internally)."""
    __slots__ = ("metas", "k_rows", "v_rows", "store", "d_rows", "err")

    def __init__(self, metas, k_rows, v_rows, store, d_rows=None):
        self.metas = metas        # [(key, parent, depth), ...] per page
        self.k_rows = k_rows      # device [L, B, pg, KV, hd] (+ scales)
        self.v_rows = v_rows
        self.store = store
        self.d_rows = d_rows      # (dk, dv) draft-cache rows or None
        self.err = None

    def run(self):
        """Sync the gather and hand each page to the host store."""
        import jax as _jax

        k_np = _jax.tree.map(np.asarray, self.k_rows)
        v_np = _jax.tree.map(np.asarray, self.v_rows)
        dk_np = dv_np = None
        if self.d_rows is not None:
            dk_np = _jax.tree.map(np.asarray, self.d_rows[0])
            dv_np = _jax.tree.map(np.asarray, self.d_rows[1])

        def page(rows, i):
            if isinstance(rows, dict):
                return {"q": np.ascontiguousarray(rows["q"][:, i]),
                        "s": np.ascontiguousarray(rows["s"][:, i])}
            return np.ascontiguousarray(rows[:, i])

        for i, (key, parent, depth) in enumerate(self.metas):
            self.store.put(
                key, parent, depth, page(k_np, i), page(v_np, i),
                dk=page(dk_np, i) if dk_np is not None else None,
                dv=page(dv_np, i) if dv_np is not None else None)


class _PendingPrefetch(_PendingOffload):
    """A prefetch-ahead restore batch in the sync worker (ISSUE 16).

    The scatter itself was already dispatched by the engine loop (device
    order protects the upload against later work); this item exists so
    the sync worker observes the upload's completion in dispatch order
    and retires the store's inflight gauge. It reuses the offload
    branch's terminal handling (run + continue, exempt from fault
    injection) — ``metas``/``store`` keep their slots, ``k_rows`` holds
    a tiny device handle dependent on the scatter to sync against."""

    def run(self):
        np.asarray(self.k_rows)      # blocks until the scatter executed
        self.store.note_prefetch_done()


class _Slot:
    __slots__ = (
        "req", "detok", "generated", "held_text", "prompt_len",
        "t_start", "t_first_token", "n_decoded", "t_prefill_ms",
        "grammar", "gstate", "bias_base", "cur_penalty",
        "phase", "pending", "written", "reused", "cache_len", "committed",
        "mm_pos", "mm_vec", "spec_ok", "ga_blocks", "prio", "preempts",
        "win_off", "chain_keys",
    )

    def __init__(self, req: GenRequest, detok, prompt_len: int):
        self.req = req
        self.detok = detok
        self.generated: list[int] = []
        self.held_text = ""   # text withheld due to partial stop-seq match
        self.prompt_len = prompt_len
        self.t_start = time.monotonic()
        self.t_first_token = 0.0
        self.n_decoded = 0
        self.t_prefill_ms = 0.0
        self.grammar = None     # functions.grammars.automaton.Grammar
        self.gstate = None      # current frozenset state
        self.bias_base = None   # np [V] logit_bias row under the grammar mask
        self.cur_penalty = None  # last uploaded penalty row (identity-compared)
        self.phase = "prefill"  # "prefill" -> "decode"
        self.mm_pos = None      # np [P] absolute prompt positions (P-bucketed)
        self.mm_vec = None      # np [P, hidden] injected embeddings
        self.spec_ok = False    # greedy+ungrammared: may join spec rounds
        self.pending: list[int] = []   # prompt tokens not yet prefilled
        self.written = 0        # cache rows already valid for this request
        self.reused = 0         # prefix tokens reused from a previous request
        self.cache_len = 0      # rows occupied in the slot's KV cache
        self.committed = 0      # rows whose KV write has actually executed
        self.ga_blocks = 0      # self-extend: position blocks compressed
        # snap-back window (ISSUE 16): absolute rows already demoted off
        # the device (a page multiple). All row coordinates above
        # (written/committed/cache_len + engine lengths) are COMPACT —
        # absolute position = compact + win_off, carried to the device
        # through pos_offset. 0 = unwindowed, every path bit-for-bit.
        self.win_off = 0
        # chain keys of the slot's absolute FULL pages, extended lazily
        # from _cache_tokens as pages fill — (key, parent, depth) per
        # page, so demotion can offload without rehashing from the root
        self.chain_keys: list = []
        # priority scheduling (ISSUE 10): class rank (0 = high) and how
        # many times this REQUEST has been preempted (survives resume)
        self.prio = PRIORITY_RANK.get(req.priority, 1)
        self.preempts = 0


class Engine:
    """Owns the model state and a background step-loop thread."""

    def __init__(
        self,
        model_cfg: llama.LlamaConfig,
        params,
        tokenizer,
        engine_cfg: EngineConfig = None,
        eos_token_ids: Optional[set] = None,
        mesh=None,
        param_shardings=None,
        draft: Optional[tuple] = None,   # (LlamaConfig, params) draft model
        bus=None,                        # parallel/lockstep.LeaderBus
        family=None,                     # model-family module (default llama)
        replica_id: int = 0,             # position in an EnginePool (ISSUE 14)
        shared_kv=None,                  # pool.SharedKV: one host tier + index
        tracer=None,                     # the process's RingTracer (runner)
    ):
        self.cfg = model_cfg
        self.ecfg = engine_cfg or EngineConfig()
        # effective admission limit (ISSUE 20): identical to the
        # configured knob for a standalone engine; EnginePool.resize()
        # rescales it proportionally with live replica width
        self.maxq_effective = self.ecfg.max_queued_requests
        # replica-pool membership (ISSUE 14): standalone engines are
        # replica 0 of a pool of one and OWN their host tier (shutdown
        # persists it); pool members share ONE HostPageStore the pool
        # owns, and report device-tier membership to the pool index.
        self.replica_id = int(replica_id)
        self._shared_kv = shared_kv
        self._hstore_owned = shared_kv is None
        # model-family adapter (init_cache / engine_decode / prefill /
        # ragged_prefill). What the engine may do with a family is what
        # the family DECLARES (its CAPABILITIES): "paged" (K/V rows in the
        # page pool, through llama's attention kernels), "packed_prefill"
        # (a ragged_prefill forward), "prefix_reuse" (a slot can resume
        # from cached pages alone: prefix cache, COW sharing, fork
        # dedup, prompt-cache files), "kv_offload" (its pages are a K and
        # a V plane, which is what the host tier, the snap-back window
        # over it and the page wire hold: models/xing4.py's one latent
        # plane reuses prefixes and declares no host tier), "speculation",
        # "self_extend", "multimodal", and "mesh" (the family's params
        # and cache have sharding rules). models/llama.py declares all;
        # mamba / rwkv only "mesh" (a fixed-size state in the cache
        # lanes, on the contiguous fallback); models/olmo_hybrid.py the
        # first two: its slot holds paged K/V AND a recurrent state, a
        # page without the state at its boundary cannot be resumed from,
        # and the state has no sharding rule.
        self.family = family if family is not None else llama
        self._caps = caps = frozenset(
            getattr(self.family, "CAPABILITIES", ()))
        if "paged" in caps:
            # where attention runs (Pallas kernels or jnp) is decided
            # HERE, from the platform and the mesh, and rides the config
            # into every trace; state_snapshot()["attention"] reports it
            self.cfg = dataclasses.replace(
                model_cfg, attn=llama.attn_target(model_cfg, mesh))
        self._fam_name = getattr(self.family, "__name__",
                                 "llama").rsplit(".", 1)[-1]
        # "route_stats": the family's steps report their routing (a routed
        # expert feed-forward: ops/moe.py). The numbers ride the
        # results a dispatch brings back anyway - a burst's pack, a prefill
        # pack's logprobs - and are folded into /debug/state's "moe"
        # (its stats: per expert layer the pairs each of the E experts held
        # here got, the experts touched, and the pairs routed in all, held
        # here or not: [L, E + 2], flat)
        L_r, E_r = self.family.route_stats_shape(model_cfg) \
            if "route_stats" in caps else (0, 0)
        self._n_route = L_r * (E_r + 2)
        self._route_rows_n = -(-self._n_route // self.ecfg.num_slots)
        self._moe = {k: {"steps": 0,
                         "experts_touched": np.zeros((L_r,), np.int64),
                         "pairs_routed": np.zeros((L_r,), np.int64),
                         "pairs": np.zeros((L_r, E_r), np.int64)}
                     for k in ("decode", "prefill")} if self._n_route else None
        assert mesh is None or "mesh" in caps, \
            f"a mesh is not declared by {self._fam_name}"
        assert draft is None or "speculation" in caps, \
            f"draft speculation is not declared by {self._fam_name}"
        assert self.ecfg.ga_n <= 1 or "self_extend" in caps, \
            f"self-extend is not declared by {self._fam_name}"
        # multi-host lockstep mode: every device dispatch is mirrored to
        # follower processes (see parallel/lockstep.py); features whose
        # dispatches are not in the descriptor set are rejected/disabled
        self._bus = bus
        if bus is not None:
            assert draft is None, "speculative draft unsupported in lockstep"
            assert self.ecfg.ga_n <= 1, "self-extend unsupported in lockstep"
        self.tokenizer = tokenizer
        self.mesh = mesh
        S = self.ecfg.num_slots
        C = self.ecfg.max_context
        V = model_cfg.vocab_size

        self.params = params
        # speculative decoding (greedy-lossless; see engine/speculative.py)
        self.draft_cfg, self.draft_params = draft if draft else (None, None)
        if self.draft_cfg is not None:
            self.draft_cfg = dataclasses.replace(
                self.draft_cfg, attn=llama.attn_target(self.draft_cfg, mesh))
        # drafting-mode resolution (ISSUE 13): llama-family only (the
        # spec tick composes llama.prefill), never in lockstep (spec
        # dispatches are not in the descriptor set) and never with
        # self-extend (rounds advance row=position). Everything outside
        # those engine modes keeps its pre-spec dispatch stream
        # bit-for-bit (the fused tick is only ever compiled or
        # dispatched when _spec_mode != "off").
        d = str(self.ecfg.draft or "auto").lower()
        if d in ("0", "off", "none", "false"):
            mode = "off"
        elif d == "model":
            mode = "model" if self.draft_params is not None else "off"
        elif d == "ngram":
            mode = "ngram"
        else:   # auto
            mode = "model" if self.draft_params is not None else "ngram"
        if ("speculation" not in caps or bus is not None
                or self.ecfg.ga_n > 1 or self.ecfg.n_draft <= 0):
            mode = "off"
        self._spec_mode = mode
        self._state_shardings = self._make_state_shardings()
        # paged KV layout resolution (EngineConfig.kv_layout doc):
        # llama-family only; lockstep followers can't replay the leader's
        # host-side page-table mutations, so "auto" degrades there
        if self.ecfg.kv_layout == "paged" and bus is not None:
            raise ValueError("kv_layout=paged is unsupported in multi-host "
                             "lockstep mode (host-local page tables)")
        # self-extend composes with the paged layout since ISSUE 16: the
        # in-place key re-rotation is confined to rows past the
        # compressed region (never the shared/retained pages, whose
        # delta-0 rewrite is value-identical), cross-slot sharing is
        # gated off under ga, and the prefix/host scopes fold ga_n/ga_w
        # in so compressed rows only ever match under the same mapping.
        # "auto" still degrades to contiguous under ga (the historical
        # default); opt in with an explicit kv_layout=paged.
        self._paged = "paged" in caps and (
            self.ecfg.kv_layout == "paged"
            or (self.ecfg.kv_layout == "auto" and bus is None
                and self.ecfg.ga_n <= 1))
        self._pool = None
        self._pcache = None
        self._hstore = None
        self._rstager = None
        self._pool_pages = 0     # resolved physical pool size (0 = full)
        pg = 0
        if self._paged:
            from localai_tpu.engine.paging import PagePool

            pg = max(1, min(self.ecfg.kv_page_size, C))
            while C % pg:     # page size must divide the context
                pg -= 1
            offload_on = self.ecfg.kv_prefix_cache and self.ecfg.kv_offload
            self._pool_pages = self.ecfg.kv_pool_pages
            full = S * (C // pg)
            if self._pool_pages == 0 and offload_on and full >= 64:
                # ROADMAP follow-up: with oversubscription telemetry AND
                # a host tier absorbing evictions, the default pool no
                # longer needs the worst-case contiguous reservation —
                # serving-sized pools shrink 25% (evicted chains offload
                # instead of re-prefilling). Tiny test/bench pools (< 64
                # pages) keep the full reservation: at that scale one
                # slot's context is a large pool fraction and shrinkage
                # would manufacture admission failures, not save HBM.
                self._pool_pages = max(full * 3 // 4, S + C // pg)
            self._pool = PagePool(S, C, pg, self._pool_pages)
            if self.ecfg.kv_prefix_cache and "prefix_reuse" in caps:
                # cross-release page retention; NEVER built for the
                # contiguous fallbacks (lockstep / mamba / rwkv) — those
                # layouts have no pages to retain — nor for a family
                # whose slot cannot resume from pages alone
                from localai_tpu.engine import prefix_cache

                scope = prefix_cache.build_scope(
                    self._fam_name, model_cfg, pg, self.ecfg.cache_dtype)
                if self.ecfg.ga_n > 1:
                    # self-extend rows are position-COMPRESSED: fold the
                    # grouping geometry into the scope so they can only
                    # ever match (device tier, host tier, persisted
                    # store) under the identical ga_n/ga_w mapping
                    scope = scope + b"|ga:%d:%d" % (self.ecfg.ga_n,
                                                    self.ecfg.ga_w)
                # pool mode: device-tier membership feeds the shared
                # cross-replica index (prefix-affinity routing) and the
                # shared store's mapping refcounts
                hooks = (shared_kv.prefix_hooks(self.replica_id)
                         if shared_kv is not None else {})
                self._pcache = prefix_cache.PrefixPageCache(
                    scope, pg, **hooks)
                if self.ecfg.kv_offload and "kv_offload" in caps:
                    # the host-RAM tier under the pool (the scope doubles
                    # as the persisted file's model/geometry check)
                    from localai_tpu.engine.kv_offload import (
                        HostPageStore, RestoreStager)

                    if shared_kv is not None:
                        # ONE host tier for the whole pool; the pool owns
                        # persistence (saved once, not per replica)
                        self._hstore = shared_kv.host_store(
                            scope, pg, self.ecfg.kv_host_pool_mb,
                            self.ecfg.kv_host_store_path)
                    else:
                        self._hstore = HostPageStore(
                            scope, pg, self.ecfg.kv_host_pool_mb)
                    # double-buffered restore staging (ISSUE 9 satellite):
                    # consecutive restore uploads alternate buffer sets so
                    # an in-flight scatter never aliases a refill
                    self._rstager = RestoreStager()
                    if self._hstore_owned and self.ecfg.kv_host_store_path:
                        n = self._hstore.load(self.ecfg.kv_host_store_path)
                        if n:
                            import logging as _logging

                            _logging.getLogger(__name__).info(
                                "kv host store: reloaded %d offloaded "
                                "pages from %s", n,
                                self.ecfg.kv_host_store_path)
        # --- long-context tier (ISSUE 16): snap-back window + prefetch ---
        self._win_pages = 0
        self._win_sink = max(0, int(self.ecfg.kv_sink_pages))
        self._prefetch = None
        if self.ecfg.kv_window_pages > 0:
            W = int(self.ecfg.kv_window_pages)
            if "kv_offload" not in caps:
                raise ValueError(
                    "kv_window_pages: the snap-back window is not declared "
                    f"by {self._fam_name}")
            if not self._paged or self._pcache is None:
                raise ValueError(
                    "kv_window_pages requires the paged KV layout with the "
                    "prefix cache enabled (kv_prefix_cache=1)")
            if self.ecfg.kv_window_policy not in ("demote", "drop"):
                raise ValueError(
                    "kv_window_policy must be demote|drop, got "
                    f"{self.ecfg.kv_window_policy!r}")
            if (self.ecfg.kv_window_policy == "demote"
                    and self._hstore is None):
                raise ValueError(
                    "kv_window_policy=demote requires the host tier "
                    "(kv_offload=1); use kv_window_policy=drop to run a "
                    "window without host RAM")
            if (self._win_sink + W + 2) * pg > C:
                raise ValueError(
                    f"kv window does not fit: (sink {self._win_sink} + "
                    f"window {W} + 2) pages of {pg} rows exceeds "
                    f"max_context {C}")
            if self.ecfg.ga_n > 1:
                raise ValueError(
                    "kv_window_pages does not compose with self-extend "
                    "(ga_n > 1): both mechanisms own the slot's RoPE "
                    "position offset")
            self._win_pages = W
        if (self._paged and self._hstore is not None
                and self.ecfg.kv_prefetch_ahead > 0):
            from localai_tpu.engine.kv_offload import PrefetchPipeline

            self._prefetch = PrefetchPipeline()
        # device-resident state: big (KV cache), rarely-mutated (bias), or
        # not host-mirrorable (PRNG keys). Everything per-slot and small
        # lives as HOST numpy — admissions/releases are then free in-place
        # writes instead of ~3ms `.at[].set` dispatches, and the arrays ride
        # to the device as ordinary jit args each step.
        self.ck, self.cv = self.family.init_cache(
            model_cfg, S, C, self.ecfg.cache_dtype,
            **({"page_size": pg, "num_pages": self._pool_pages}
               if self._paged else {}))
        # per-slot recurrent state beside the K/V rows (ops/kvcache.py)
        self._state_bytes = kvcache.state_bytes(self.ck)
        # a family whose rows are latent (one plane: ops/mla.py) says how
        # many bytes its pool holds; None for K/V planes
        latent = getattr(self.family, "latent_cache_bytes", None)
        self._latent_bytes = latent(self.ck) if latent else None
        self._kv_walk = {"pages_live": 0, "pages_grid": 0}   # _count_kv_walk
        self._state_layers = kvcache.state_layers(self.ck)
        self._state_walk = {"slot_steps_live": 0, "slot_steps_grid": 0}
        # bursts by the branch of the sampler their steps take
        # (_sampler_branch): metrics()["sampler_bursts"]
        self._sampler_bursts = {"plain_greedy": 0, "window": 0}
        # draft cache is allocated LAZILY at the first spec-eligible
        # admission (r2 allocated it up front, doubling per-slot KV HBM
        # even when no request could ever speculate)
        self.dck = self.dcv = None
        self.bias = jnp.zeros((S, V), jnp.float32)
        self.rng_keys = jax.vmap(jax.random.key_data)(
            jax.vmap(jax.random.PRNGKey)(jnp.arange(S, dtype=jnp.uint32))
        )
        self.slot_params = sampling.make_slot_params(S)
        self.ring, self.ring_pos = sampling.make_ring(S)
        self.mu = sampling.make_mu(S)
        self.lengths = np.zeros((S,), np.int32)
        self.cur_tokens = np.zeros((S,), np.int32)
        self.active_dev = np.zeros((S,), np.bool_)
        self.pos_offset = np.zeros((S,), np.int32)  # self-extend offsets
        # snap-back window (ISSUE 16): compact rows each slot demoted
        # since the last dispatch — subtracted from the device chain's
        # lengths via override-pack row 6, zeroed after every pack
        self._win_delta = np.zeros((S,), np.int32)
        self._adm_win_off = 0   # window offset chosen by _paged_admission
        self._bias_dirty = np.zeros((S,), np.bool_)
        self._shard_state()

        if eos_token_ids:
            self.eos_ids = set(eos_token_ids)
        else:
            self.eos_ids = set()
            eid = getattr(tokenizer, "eos_token_id", None)
            if eid is not None:
                self.eos_ids.add(int(eid))

        # host mirrors
        self.slots: list[Optional[_Slot]] = [None] * S
        self._cache_tokens: list[list[int]] = [[] for _ in range(S)]
        self._prefill_queue: list[int] = []   # slot ids awaiting prefill chunks
        self._cancelled: set = set()
        self._queue: "queue.Queue[GenRequest]" = queue.Queue()
        self._wake = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._load_time = time.monotonic()
        self._total_tokens = 0
        self._reused_total = 0
        # (queue_wait_ms, admit_to_first_ms, prefill_ms) per finished
        # request — rolling window for the TTFT decomposition in metrics()
        from collections import deque
        self._ttft_decomp: "deque" = deque(maxlen=512)
        # at maxlen every append mutates the deque, and metrics() reads it
        # from gRPC handler threads — unsynchronized iteration raises
        self._decomp_lock = threading.Lock()
        self._rollbacks = 0     # grammar rollbacks (test observability)

        self._burst_fns: dict[int, Callable] = {}
        self._chunk_fns: dict[int, Callable] = {}
        self._final_fns: dict[tuple, Callable] = {}
        # fused spec-tick counters (ISSUE 13): dispatches = spec ticks
        # issued, mixed_dispatches = ticks that carried BOTH spec-masked
        # and plain-decode slots, rounds = per-slot round totals,
        # rows_drafted = those of them in which the drafter had a draft
        # (ISSUE 34: the others took the plain step), proposed /
        # accepted = draft tokens of the drafted rows, tokens = tokens
        # the spec slots emitted (one a plain round; accepted + bonus a
        # drafted one), rounds_verified = rounds of a tick in which the
        # verify pass ran at all (any row drafted; per tick, not per
        # slot). by_mode (ISSUE 18) splits the per-slot totals between
        # greedy (accept_greedy) and sampled (accept_sampled) slots; the
        # flat keys stay the cross-mode aggregates.
        self._spec_stats = {"dispatches": 0, "mixed_dispatches": 0,
                            "rounds": 0, "rows_drafted": 0,
                            "proposed": 0, "accepted": 0, "tokens": 0,
                            "rounds_verified": 0,
                            "by_mode": {
                                m: {"rounds": 0, "rows_drafted": 0,
                                    "proposed": 0, "accepted": 0,
                                    "tokens": 0}
                                for m in ("greedy", "sampled")}}

        # pipelined decode state (r4 redesign): bursts chain device-side
        # through (tokens, lengths, ring, ring_pos, mu) output handles, and
        # host events (admission, release, context shift, rollback) no
        # longer invalidate the whole chain — each dispatch composes the
        # chain with per-slot OVERRIDE rows taken from the host mirrors
        # (see _decode_burst_body), so dispatch NEVER waits on a device
        # sync. Dispatched work (decode bursts + final-prefill groups)
        # lives in one FIFO mirroring the device's execution order; the
        # loop keeps up to pipeline_depth bursts in flight and only
        # block-syncs the FIFO head, which by then is (nearly) computed.
        self._chain = None                    # device handles or None
        self._override: set = set()           # slots whose chain rows are stale
        self._fifo = collections.deque()      # _Burst | _PendingPrefill
        self._burst_ms_ema = 0.0   # plain-burst dispatch->processed latency
        self._sync_q: "queue.Queue" = queue.Queue()
        self._sync_thread = threading.Thread(
            target=self._sync_worker, name="engine-sync", daemon=True)
        self._sync_thread.start()

        # effective prefill buckets always include the chunk size; both are
        # clamped to the cache capacity (a bucket larger than max_context
        # could never be written and would crash the prefill KV update)
        self._chunk = min(self.ecfg.prefill_chunk, C)
        self._buckets = tuple(sorted(set(
            [b for b in self.ecfg.prefill_buckets if b <= min(self._chunk, C)]
            + [self._chunk])))
        # fresh final prefills batch up to this many prompts per dispatch
        # (padded by repeating the last entry, so only two compiled batch
        # sizes exist per bucket: 1 and _final_pad). Sized for the wave-
        # turnover case (r3 trace: all slots finishing together serialized
        # 4 groups of 8 through one pending slot, stalling the device ~1s
        # per wave): one group should swallow half the fleet.
        self._final_pad = max(8, min(16, self.ecfg.num_slots))
        # ragged packed prefill (module doc): one dispatch per tick for
        # ALL queued slots' prompt tails. Families without the ragged
        # forward and lockstep (the pack op is not in the descriptor
        # set) keep the per-slot path; ineligible SLOTS (multimodal,
        # position-compressed self-extend) fall back per-slot inside
        # _prefill_step. Spec slots pack too — their draft mirror rides
        # a packed ragged program (_get_draft_packed_fn); a ga engine's
        # UNcompressed slots pack normally (compressed ones need
        # explicit grouped positions and go singly, _prefill_ga_piece).
        self._packed = (self.ecfg.prefill_packed
                        and "packed_prefill" in caps and bus is None)
        # the per-slot prefill programs serve what cannot ride a pack
        # (multimodal shapes, compressed self-extend positions, the
        # snap-back window over the host tier): a family that declares
        # none of those and packs never dispatches them, so they are not
        # warmed either
        self._per_slot_prefill = (not self._packed or bool(
            caps & {"multimodal", "self_extend", "kv_offload"}))
        co = str(self.ecfg.comm_overlap)
        # TokenWeave halved-pack overlap (models/llama.py): only ever a
        # win when per-layer collectives exist, so auto arms it on a
        # mesh and keeps single-device serving on the one-chain path.
        # Bit-exact either way (parallel/sharding.py::overlap_halves).
        self._comm_overlap = co == "1" or (co == "auto"
                                           and self.mesh is not None)
        budget = self.ecfg.prefill_token_budget or 2 * self._chunk
        self._pack_budget = max(1, min(budget, C))
        # total-token pad buckets for the pack: the per-slot ladder
        # capped at the budget, plus the budget itself (the loaded
        # steady state) — a handful of compiled variants, warmed by
        # precompile()
        self._pack_buckets = tuple(sorted(
            {min(b, self._pack_budget) for b in self._buckets}
            | {self._pack_budget}))
        # packed-prefill telemetry (metrics(); exercised by tests):
        # dispatches, packed real tokens, segments, pad waste, and
        # dispatches whose shape left the Pallas kernel path
        # (models/llama.py::ragged_kernel_shape_fallback — the ~1k-token
        # cliff this counter keeps observable)
        self._pack_stats = {"dispatches": 0, "tokens": 0, "segments": 0,
                            "pad_tokens": 0, "kernel_fallback": 0}

        # grammar-constrained decoding (lazy: built on first grammar request)
        self._grammar_cache: dict[str, Any] = {}
        self._mask_builder = None
        self._token_strs: Optional[list] = None

        # the span tracer (services/tracing.py): the runner hands in the
        # process's ring (its LoadModel spans are already in it); a bare
        # Engine builds its own. trace=0 makes span()/record() no-ops on
        # the hot path
        from localai_tpu.services.tracing import RingTracer

        self.tracer = tracer if tracer is not None else RingTracer(
            self.ecfg.trace_ring_size, enabled=bool(self.ecfg.trace))
        self.profile_state: Optional[dict] = None   # set by runner.Profile
        # what the current tick dispatched, for the tick span's counts
        self._tick_prefill_tokens = 0
        self._tick_decode_tokens = 0
        self._slow_ms = float(self.ecfg.slow_request_ms)
        # per-request latency histograms (re-exposed by /metrics as real
        # Prometheus histograms): name -> [bucket counts + +Inf, sum, n].
        # Single writer (engine thread); metrics() reads are snapshots.
        self._hists = {name: [[0] * (len(b) + 1), 0.0, 0]
                       for name, b in _HIST_BUCKETS.items()}
        self._t_last_burst = 0.0
        # lifecycle telemetry + watchdog state (ISSUE 7). _t_last_ready is
        # the last sync-worker ready-set stamp (_overdue_ref reads it).
        self._t_last_ready = 0.0
        self._lc = {"requests_shed": 0, "requests_timed_out": 0,
                    "stalls": 0, "stall_dumps": 0,
                    "late_dispatches": 0, "late_dispatch_s": 0.0}
        # program kind -> seconds a step of its last PACE_ITEMS items,
        # each counted from its own _overdue_ref (engine thread only)
        self._pace: dict = {}
        # (monotonic, sysobs.host_memory()) at the loop's half-second
        # folds, a minute of them: what a late wait began with
        self._rss = collections.deque(maxlen=128)
        self._lc_lock = threading.Lock()
        # in-flight prefill dedup: leader slot -> [(sib_slot, snap, leader
        # snap, ids)]; KV rows fork when the leader's prefill commits
        self._fork_waiters: dict = {}
        self._fork_fns: dict = {}
        # grammar slots whose mask row changed since the last device flush
        self._gbias_flush: set = set()
        # --- system observability (ISSUE 8, services/sysobs.py) ---
        # structured event-log sink (per-process singleton; the engine's
        # knob arms it for this backend process)
        if self.ecfg.event_log:
            EVENTS.configure(self.ecfg.event_log,
                             max_mb=self.ecfg.event_log_max_mb)
        # XLA compile tracking: the jax.monitoring listener dispatches to
        # this tracker from whichever thread registered it (the engine
        # loop registers at startup; precompile() wraps itself)
        self._cobs = sysobs.CompileTracker(
            model=self._fam_name,
            on_storm=lambda rec: EVENTS.emit("compile_storm", **rec))
        # model-forward programs by name -> {attention, dispatches}
        self._programs: dict = {}
        # memory watermarks: peaks folded from engine-loop tick samples
        self._wm = sysobs.Watermarks()
        try:
            self._weight_bytes = int(sum(
                a.size * a.dtype.itemsize for a in jax.tree.leaves(params)
                if hasattr(a, "size") and hasattr(a, "dtype")))
        except Exception:
            self._weight_bytes = 0
        # goodput/MFU: completed-request tokens only (sheds and timeouts
        # burn FLOPs but never reach the clean-finish accounting)
        # the device as jax reports it, from the process that holds it
        # (the HTTP parent never initialises a backend — it reads this)
        dev0 = jax.devices()[0]
        self._device = {"platform": dev0.platform,
                        "device_kind": dev0.device_kind,
                        "device_count": len(jax.devices())}
        peak = sysobs.peak_device_flops(dev0)   # unknown accelerator: raises
        # (the formula is the dense Llama block's)
        fpt = (sysobs.flops_per_token(self.cfg, ctx=C // 2)
               if self.family is llama else 0.0)
        self._goodput = sysobs.GoodputMeter(flops_per_tok=fpt,
                                            peak_flops=peak)
        # exemplar tracking: worst observation per histogram since the
        # last metrics() pull, with its request correlation id
        self._hist_worst: dict = {}
        self._pool_pressure = False   # hysteresis for pool_pressure events
        # --- event-driven hot path (ISSUE 9) ---
        # idle arm: with the sync worker waking the loop on every ready-set
        # transition (_wake), the fixed 50 ms poll tick is dead weight —
        # park until woken, bounded only by the watchdog cadence.
        stall_s = self.ecfg.dispatch_stall_ms / 1e3
        self._idle_wait_s = min(1.0, stall_s / 4) if stall_s > 0 else 1.0
        # emitter handoff: per-tick token batch (slot -> entry, insertion
        # ordered) flushed as ONE queue item per processed burst/prefill,
        # plus the note channel for emitter-detected stop finishes.
        self._em_batch: dict = {}
        self._em_notes: list = []
        self._em_lock = threading.Lock()
        # the emitter worker owns detok, stop-sequence scanning and every
        # stream queue put; the loop keeps id-level control (_emit_token)
        self._emitter = self._make_emitter()
        # reusable host-side staging for per-dispatch overrides and packed
        # segment tables: round-robin pools deep enough that no buffer is
        # rewritten while its async device transfer may still be reading
        self._ov_pool = [np.empty((7 + sampling.RING_N, S), np.float32)
                         for _ in range(max(6, self.ecfg.pipeline_depth + 4))]
        self._ov_pool_idx = 0
        self._seg_pools: dict = {}   # bucket -> round-robin list of arrays
        self._seg_pool_idx: dict = {}
        # --- preemptive priority scheduler (ISSUE 10) ---
        # the scheduler owns the per-tick run decision: aged-rank
        # admission ordering, DRR prefill-budget shares, preemption
        # victim selection and the resume queue. preempt=0 leaves it
        # unbuilt and every path below falls back to strict FIFO.
        self._default_prio = normalize_priority(self.ecfg.priority)
        self._sched = None
        if self.ecfg.preempt:
            self._sched = Scheduler(
                parse_priority_weights(self.ecfg.priority_weights),
                max_preemptions=self.ecfg.max_preemptions,
                aging_ms=float(self.ecfg.priority_aging_ms))
        # --- live migration out of this replica (ISSUE 14) ---
        # request_id -> handoff callable, drained by the engine loop at
        # the next tick: the slot preempts (PR-10 pause), its retained
        # chain force-offloads to the (shared) host tier, and the
        # ResumeEntry is handed to the pool instead of parked here.
        self._migrate_req: dict = {}
        self._migrate_lock = threading.Lock()
        # replica_die fault name (chaos: pool crash recovery) — checked
        # at the tick top only while fault injection is armed
        self._die_fault = f"replica{self.replica_id}_die"
        # --- cluster serving (ISSUE 17) ---
        # prefill/decode disaggregation: the cluster router registers a
        # handoff here on "prefill"-role engines; _process_disagg ejects
        # finished-prefill slots into it at the tick top. None = no
        # cluster — the tick-top check is one attribute read.
        self.disagg_handoff = None
        self._disagg_prefill = (self.ecfg.disagg == "prefill")
        self.disagg_handoffs = 0
        # warm-chain checkpointing (DejaVu-style KV streaming for crash
        # recovery): when armed by a ClusterHost, active slots' committed
        # chains are retained + force-offloaded to the host tier on the
        # watermark cadence — so a host that dies mid-decode leaves its
        # warm chains fetchable by the sibling that re-adopts its work.
        self.kv_checkpoint = False
        # --- resume_reserve_pages autosize (ISSUE 14 satellite; the
        # open PR-10 follow-up): EWMA of preemptions/min x average pages
        # retained per preemption -> effective reserve when the explicit
        # knob is 0. Starts at 0, so engines that never preempt keep
        # bit-for-bit admission behavior.
        self._preempt_marks: "deque" = deque(maxlen=256)   # monotonic stamps
        self._preempt_rate_ewma = 0.0    # preemptions per minute
        self._preempt_pages_ewma = 0.0   # pages retained per preemption
        self._reserve_auto = 0
        self._t_reserve_sample = time.monotonic()
        # --- per-class SLO engine + violation flight recorder (ISSUE 12)
        # Built only when an objective is declared — the finish-path
        # observe() calls are then dict lookups; with no objectives the
        # whole layer is None-checked away.
        objectives = {}
        for metric, spec in (("ttft_ms", self.ecfg.slo_ttft_ms),
                             ("itl_ms", self.ecfg.slo_itl_ms),
                             ("queue_wait_ms", self.ecfg.slo_queue_wait_ms)):
            classes = sysobs.parse_slo_classes(spec)   # raises on typos
            if classes:
                objectives[metric] = classes
        self._slo = (sysobs.SLOEngine(
            objectives, error_budget=self.ecfg.slo_error_budget)
            if objectives else None)
        # the flight recorder dumps merged trace + state + events on SLO
        # violations AND watchdog/stall events, into the same directory
        # the stall ring dumps use
        self._flight = sysobs.FlightRecorder(self.ecfg.stall_dump_dir)
        # last allocator sample, one entry per local device
        # (sysobs.device_memory_stats) — see _sample_watermarks
        self._device_mem: list = []
        # --- KV lifecycle ledger + online invariant auditor (ISSUE 15)
        # kv_audit=off (or a non-paged layout) constructs NOTHING: every
        # hook in paging/prefix_cache/kv_offload gates on a single
        # `audit is not None`, so the off path is the pre-PR hot path.
        self._kv_audit = None
        if self._paged and self.ecfg.kv_audit != "off":
            from localai_tpu.services.kv_audit import KVAuditor

            aud = KVAuditor(mode=self.ecfg.kv_audit,
                            replica=self.replica_id,
                            seed=self.replica_id)
            aud.on_violation = self._on_kv_violation
            self._pool.audit = aud
            if self._pcache is not None:
                self._pcache.audit = aud
            if self._hstore is not None and (self._hstore_owned
                                             or self._hstore.audit is None):
                # owned store: this replica's ledger records its tier
                # transitions and its housekeeping scans it. Shared store
                # (pool mode): the first replica's ledger takes the
                # store-level records; the POOL housekeeping scans it so
                # shared violations are counted once, not per replica.
                self._hstore.audit = aud
            self._kv_audit = aud

    def _sync_worker(self):
        """ALL device->host syncs run here, one at a time, in dispatch
        (= device execution) order: each np.asarray then blocks only
        until its own item finishes computing. The serving loop never
        issues a transfer itself — it dispatches, and consumes results
        whose ``ready`` event has fired."""
        while True:
            item = self._sync_q.get()
            if item is None:
                return
            if FAULTS.active and not isinstance(item, _PendingOffload):
                d = FAULTS.take("sync_delay_ms")
                if d is not None:
                    # stall injection: the ready-set transition is late, so
                    # the dispatch watchdog should fire on the waiting item
                    time.sleep(int(d) / 1e3)
                if FAULTS.take("sync_fail") is not None:
                    item.err = RuntimeError("injected fault: sync_fail")
                    item.t_ref = self._overdue_ref(item)
                    item.t_ready = self._t_last_ready = time.monotonic()
                    item.ready.set()
                    self._wake.set()
                    continue
            # sync_wait: this thread blocked on the device (and the copy
            # back) for one dispatched item; with an idle device under it,
            # the host is what the device waits for
            paced = not isinstance(item, _PendingOffload)
            with self.tracer.span(
                    "sync_wait", "sync",
                    **({"kind": item.kind, "steps": item.n_steps}
                       if paced else {"kind": "kv_offload"})):
                try:
                    # what this thread's wait cost it (a copy back that
                    # faults or is descheduled shows here only), for the
                    # late_dispatch span: sampled only while spans are kept
                    ru0 = _wait_rusage() \
                        if paced and self.tracer.enabled else None
                    if isinstance(item, _Burst):
                        item.pack_np = np.asarray(item.pack)
                    elif isinstance(item, _PendingOffload):
                        # terminal here: offloads produce no tokens, so they
                        # never enter the dispatch FIFO — sync + store insert
                        # both live on this thread, off the serving loop
                        item.run()
                        continue
                    else:
                        item.ids_np = np.asarray(item.out_ids)
                        item.lps_np = np.asarray(item.logprobs)
                        item.mu_np = np.asarray(item.mu_out)
                except Exception as e:  # surfaced when the item is processed
                    if isinstance(item, _PendingOffload):
                        # a failed offload only loses a reusable copy — log
                        # and keep serving (the chain just re-prefills later)
                        __import__("logging").getLogger(__name__).exception(
                            "kv page offload failed")
                        continue
                    item.err = e
            if ru0 is not None:
                item.ru = [round(b - a, 1)
                           for a, b in zip(ru0, _wait_rusage())]
            # the ready-set stamp IS the device-completion observation
            # point (the np.asarray above returned): span
            # t_dispatch->t_ready is device time, t_ready->process
            # pickup is finish-detection latency
            item.t_ref = self._overdue_ref(item)
            item.t_ready = self._t_last_ready = time.monotonic()
            item.ready.set()
            self._wake.set()

    def _hobserve(self, name: str, seconds: float, rid: str = ""):
        h = self._hists[name]
        for i, b in enumerate(_HIST_BUCKETS[name]):
            if seconds <= b:
                h[0][i] += 1
                break
        else:
            h[0][-1] += 1
        h[1] += seconds
        h[2] += 1
        # per-span exemplar (ISSUE 8 satellite): remember the WORST
        # observation since the last metrics() pull with its correlation
        # id, so /metrics can attach an OpenMetrics exemplar pointing at
        # the span a latency investigation should start from
        if rid:
            worst = self._hist_worst.get(name)
            if worst is None or seconds > worst[0]:
                self._hist_worst[name] = (seconds, rid, time.time())

    def _flight_dump(self, reason: str, tag: str = "slo", **extra):
        """Violation flight recorder (ISSUE 12): atomically persist the
        merged evidence for ONE bad moment — chrome trace, /debug/state
        snapshot and the last events — so a stall or SLO burn seen on a
        dashboard at 3am still has its context on disk at 9am. Rate
        limiting and disk bounds live in sysobs.FlightRecorder; this
        wrapper only assembles the payload and must never raise into the
        engine loop."""
        try:
            payload = {
                "trace": self.trace_events(),
                "state": self.state_snapshot(),
                "events": EVENTS.events(last=256),
            }
            payload.update(extra)
            path = self._flight.dump(reason, payload, tag=tag)
            if path:
                EVENTS.emit("flight_dump", reason=reason, tag=tag, path=path)
            return path
        except Exception:  # pragma: no cover - defensive
            __import__("logging").getLogger(__name__).exception(
                "flight dump failed")
            return ""

    def _on_kv_violation(self, v: dict):
        """KVAuditor callback (ISSUE 15): one structured event per
        violation + a flight dump with the ledger tail attached, so the
        last ~64 page transitions that led to the broken invariant are
        on disk next to the trace/state evidence. Rate limiting lives in
        the recorder; this must never raise into the audit pass."""
        try:
            EVENTS.emit("kv_audit_violation",
                        **{k: (x if isinstance(x, (str, int, float))
                               else str(x)) for k, x in v.items()})
            self._flight_dump("kv_audit:" + str(v.get("check", "?")),
                              tag="kv_audit", kv_violation=v,
                              kv_ledger_tail=(
                                  self._kv_audit.ledger.tail(64)
                                  if self._kv_audit is not None else []))
        except Exception:  # pragma: no cover - defensive
            pass

    def _kv_audit_tick(self, drained: bool = False) -> list:
        """One online audit pass (ISSUE 15), riding the engine-loop
        housekeeping cadence so the pool's host mirrors are never
        mid-mutation. The only detached pages that survive a tick
        boundary are the prefetch pipeline's (ISSUE 16) — declared as
        extras so the leak scan can tell them from orphans; every other
        alloc_detached/unref_detached pairs within single calls on this
        thread. Strict mode lets the KVAuditError propagate — in the
        live loop that lands in the generic step-failure recovery, in
        tests it fails the test."""
        aud = self._kv_audit
        if aud is None:
            return []
        extras = ([rec[0] for rec in self._prefetch.pages.values()]
                  if self._prefetch is not None else None)
        return aud.run(
            self._pool, pcache=self._pcache,
            hstore=self._hstore if self._hstore_owned else None,
            extra_pages=extras, drained=drained)

    def kv_audit_sweep(self, drained: bool = False) -> dict:
        """On-demand full audit pass + snapshot (bench phase ends, CI
        gates, tests). The caller must be quiesced — nothing in flight —
        since the scan reads the host mirrors without the engine loop's
        serialization."""
        if self._kv_audit is None:
            return {"mode": "off", "checks": 0, "violations": 0,
                    "leaked_pages": 0, "ledger_events": 0}
        self._kv_audit_tick(drained=drained)
        return self._kv_audit.snapshot()

    def kv_debug(self) -> dict:
        """/debug/kv payload (ISSUE 15): tier map, per-chain genealogy,
        fragmentation layout, audit counters + last violations, and the
        ledger tail. ``{"mode": "off"}`` shape when auditing is off or
        the layout has no pages."""
        if self._kv_audit is None:
            return {"mode": "off", "replica": self.replica_id}
        pool = self._pool
        out = {
            "mode": self._kv_audit.mode,
            "replica": self.replica_id,
            "pool": {
                "pages_total": pool.num_pages,
                "page_size": pool.page_size,
                "free": pool.free_pages,
                "active": pool.active_pages,
                "retained": pool.retained_pages,
                "shared": int((pool.refs > 1).sum()),
                "oversubscription": round(pool.oversubscription, 4),
                "fragmentation": pool.fragmentation(),
                "pages_per_slot": [int(n) for n in pool.owned],
            },
            "chains": (self._pcache.genealogy(64)
                       if self._pcache is not None else []),
            "audit": self._kv_audit.snapshot(),
            "ledger_tail": self._kv_audit.ledger.tail(64),
        }
        if self._hstore is not None:
            out["host"] = self._hstore.stats()
            if self._hstore.federated is not None:
                # peer tier (ISSUE 17): wire fetch/push totals
                out["kv_stream"] = self._hstore.federated.stats()
        if self._win_pages:
            out["window"] = {
                "pages": self._win_pages,
                "sink_pages": self._win_sink,
                "policy": self.ecfg.kv_window_policy,
                "win_off_rows": [
                    (s.win_off if s is not None else 0) for s in self.slots],
            }
        if self._prefetch is not None:
            out["prefetch"] = {
                "staged_pages": len(self._prefetch),
                "seen_rids": len(self._prefetch.seen_rids),
            }
        return out

    def _slo_finish(self, s, ndec: int, t_done: float, ttft_ms: float,
                    queue_wait_ms: float):
        """Feed one finished request into the SLO engine (ISSUE 12).

        Called from _finish_accounting with the same timings the
        histograms see, so burn rates and latency buckets can never
        disagree about what happened. ITL is the per-request mean
        inter-token gap — (t_done - t_first)/(ndec-1) — which matches how
        a client experiences stream smoothness without keeping per-token
        stamps around."""
        if self._slo is None or not self._slo.enabled:
            return
        cls = s.req.priority or "normal"
        violations = []
        v = self._slo.observe("ttft_ms", cls, ttft_ms, rid=s.req.request_id)
        if v:
            violations.append(v)
        v = self._slo.observe("queue_wait_ms", cls, queue_wait_ms,
                              rid=s.req.request_id)
        if v:
            violations.append(v)
        if ndec > 1 and s.t_first_token:
            itl_ms = (t_done - s.t_first_token) * 1e3 / (ndec - 1)
            v = self._slo.observe("itl_ms", cls, itl_ms,
                                  rid=s.req.request_id)
            if v:
                violations.append(v)
        for v in violations:
            EVENTS.emit("slo_violation", rid=v["rid"], metric=v["metric"],
                        cls=v["class"], value_ms=round(v["value_ms"], 1),
                        objective_ms=v["objective_ms"])
        if violations:
            self._flight_dump(
                f"slo:{violations[0]['metric']}:{violations[0]['class']}",
                tag="slo", violations=violations)

    def _annot(self, name: str, **args):
        """The span around a dispatch call: a ring span on track
        ``engine`` and, while a profiler capture runs, an annotation of
        the same name in it (benchmark/reduce_trace.py::HOST_NAMES reads
        these names)."""
        return self.tracer.span(name, "engine", **args)

    def _program(self, kind: str, key, attention: str, fn, **jit_kw):
        """Every jitted program of the engine is built here. The function
        it jits is named after ``kind``, so a profiler capture's module
        line and the HLO read ``jit_<kind>`` (the shape key stays out of
        the name: one name per kind). Each call names the program for
        compile attribution around the call that may compile it
        (sysobs.CompileTracker), and counts the dispatch; the
        implementation of attention it was built with is recorded for
        state_snapshot()["attention"]["programs"]."""
        def named(*args):
            return fn(*args)

        named.__name__ = named.__qualname__ = kind
        jit_fn = jax.jit(named, **jit_kw)
        name = kind if key is None else f"{kind}:{key}"
        rec = self._programs[name] = {"attention": attention,
                                      "dispatches": 0}
        cobs = self._cobs

        def dispatch(*args):
            rec["dispatches"] += 1
            cobs.note_program(kind, key)
            try:
                return jit_fn(*args)
            finally:
                cobs.note_program(None)

        dispatch.jit_fn = jit_fn
        return dispatch

    def _decode_attn(self) -> str:
        if "paged" not in self._caps:
            return f"{self._fam_name}:recurrent"
        # a family whose attention is not llama's kernels names its own
        return getattr(self.family, "decode_attn_impl",
                       llama.decode_attn_impl)(self.cfg, self.ck)

    def _ragged_attn(self, bucket: int, continued: bool) -> str:
        return getattr(self.family, "ragged_attn_impl",
                       llama.ragged_attn_impl)(self.cfg, self.ck, bucket,
                                               continued)

    def _attention_report(self) -> dict:
        """Where this engine's attention runs, and per compiled program
        the implementation it was built with plus its dispatch count
        since warm-up."""
        target = self.cfg.attn if "paged" in self._caps else None
        pallas = bool(target and target.pallas)
        return {
            "pallas": pallas,
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            # Mosaic kernels are not GSPMD-partitionable: on a mesh they
            # run under shard_map over tp (models/llama.py::_on_mesh)
            "kernels_under_shard_map": pallas and self.mesh is not None,
            "programs": {n: dict(r) for n, r in self._programs.items()},
        }

    def _make_state_shardings(self) -> Optional[dict]:
        """NamedShardings for the engine's device state when serving on a
        mesh (parallel/sharding.py cache_spec: slots on dp, kv heads on tp).
        Falls back to replication per axis when sizes don't divide — a
        wrong-but-silent replicated cache is exactly the HBM waste this
        exists to avoid, so only shard what divides evenly."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        dp = self.mesh.shape.get("dp", 1)
        tp = self.mesh.shape.get("tp", 1)
        slot_ax = "dp" if dp > 1 and self.ecfg.num_slots % dp == 0 else None
        if "paged" in self._caps:
            # [L, S, C, KV, hd]: kv heads on tp
            kv_ax = "tp" if tp > 1 and self.cfg.num_kv_heads % tp == 0 \
                else None
            cache_spec = (None, slot_ax, None, kv_ax, None)
        elif self._fam_name == "mamba":
            # mamba conv/ssm state [L, S, Di, {K-1|N}]: d_inner on tp,
            # matching mamba_param_specs so the recurrence is shard-local
            di_ax = "tp" if tp > 1 and self.cfg.d_inner % tp == 0 else None
            cache_spec = (None, slot_ax, di_ax, None)
        else:
            # rwkv state [L, S, {4|1}, D]: D is the trailing axis; params
            # are replicated for this family, so keep D unsharded too
            cache_spec = (None, slot_ax, None, None)

        def ns(*spec):
            return NamedSharding(self.mesh, P(*spec))

        return {
            # raw spec tuple: the int8 llama cache is a pytree whose scale
            # leaf drops the hd axis (kvcache.device_put builds both)
            "cache_spec": cache_spec,
            "slot_vec": ns(slot_ax),                        # [S]
            "slot_mat": ns(slot_ax, None),                  # [S, V] / [S, 2]
        }

    def _shard_state(self):
        """Commit device-resident state to the mesh (ADVICE r1: without this
        the dp/tp cache sharding was never applied in the real serving path —
        every device held a full replica of the KV cache). Host-numpy slot
        state needs no commitment — it enters jitted steps as arguments and
        GSPMD places it."""
        sh = self._state_shardings
        if sh is None:
            return
        self.ck = kvcache.device_put(self.ck, self.mesh, sh["cache_spec"])
        self.cv = kvcache.device_put(self.cv, self.mesh, sh["cache_spec"])
        self.bias = jax.device_put(self.bias, sh["slot_mat"])
        self.rng_keys = jax.device_put(self.rng_keys, sh["slot_mat"])

    def _host_chain(self) -> tuple:
        """(tokens, lengths, ring, ring_pos, mu) from the host mirrors,
        for a dispatch with no device chain to continue. On a mesh the
        copies are placed with the shardings the jitted bodies pin
        their chain OUTPUTS to (_pin_chain), so a program sees ONE
        input type whether it is fed from the host or chained — a
        second type is a second compile, and it would land after the
        warm mark (chip_smoke.py --tp 4 caught ten)."""
        chain = (self.cur_tokens.copy(), self.lengths.copy(),
                 self.ring.copy(), self.ring_pos.copy(), self.mu.copy())
        if self._state_shardings is None:
            return chain
        return tuple(jax.device_put(a, self._slot_sharding(a))
                     for a in chain)

    def _slot_sharding(self, a):
        """Mesh sharding of a per-slot [S] vector or [S, n] matrix."""
        return self._state_shardings["slot_mat" if a.ndim == 2
                                     else "slot_vec"]

    def _place_pack(self, args: list, meta: list) -> tuple:
        """A ragged pack's [N] token arrays and [S] segment tables as
        the packed programs take them: host numpy on one device; on a
        mesh, explicitly replicated (parallel/sharding.py ragged specs —
        the pack has no slot/dp axis for GSPMD to infer). Serving and
        precompile both come through here, so both feed ONE input type."""
        if self.mesh is None:
            return args, meta
        from jax.sharding import NamedSharding

        from localai_tpu.parallel import sharding as shardlib

        psh = NamedSharding(self.mesh, shardlib.ragged_pack_spec())
        ssh = NamedSharding(self.mesh, shardlib.ragged_seg_spec())
        return ([jax.device_put(a, psh) for a in args],
                [jax.device_put(a, ssh) for a in meta])

    def _pin_chain(self, *chain) -> tuple:
        """Inside a jitted body: the chain outputs, constrained on a
        mesh to the shardings _host_chain places host copies with."""
        if self._state_shardings is None:
            return chain
        return tuple(jax.lax.with_sharding_constraint(
            a, self._slot_sharding(a)) for a in chain)

    # ---------- paged KV plumbing ----------

    def _commit_ptab(self):
        """Commit the host page-table mirror into the cache pytrees (the
        table rides INSIDE ck/cv so every jitted body stays
        layout-agnostic). Called before any dispatch that touches the
        cache; a no-op unless the allocator dirtied the table."""
        if not self._paged or not self._pool.dirty:
            return
        # ck and cv are donated separately, so they need DISTINCT table
        # buffers — but one stacked host->device transfer plus two
        # device-side slices beats two independent uploads (ISSUE 9:
        # half the transfer dispatches on every allocator change). The
        # paged draft cache (ISSUE 13) rides the SAME table: draft rows
        # live at the same page ids as the target's, so spec slots share
        # the prefix cache and offload/restore machinery for free.
        n = 4 if self.dck is not None else 2
        stacked = np.stack((self._pool.ptab,) * n)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(self.mesh, P(None, None, None))
            both = jax.device_put(stacked, sh)
        else:
            both = jnp.asarray(stacked)
        self.ck = kvcache.with_page_table(self.ck, both[0])
        self.cv = kvcache.with_page_table(self.cv, both[1])
        if self.dck is not None:
            self.dck = kvcache.with_page_table(self.dck, both[2])
            self.dcv = kvcache.with_page_table(self.dcv, both[3])
        self._pool.dirty = False

    def _reclaim_pages(self, slot, need_free: int):
        """Two-tier reclaim under pool pressure, cheapest truth first:
          1. free slots' retained TABLES are released (their
             _cache_tokens cleared so _pick_slot stops advertising the
             prefix) — with the prefix cache on, pages it holds survive
             this with refs dropping to the cache's hold alone;
          2. prefix-cache entries are EVICTED LRU-first until enough
             pages are free (engine/prefix_cache.py).
        Purely host-side and non-blocking — admission either gets its
        pages or sees PoolExhausted from the retried alloc, never a
        deadlock against work the scheduler still has to run."""
        # ``slot`` (int or tuple) names tables reclaim must NOT release:
        # mid-admission the destination slot is still unoccupied, and a
        # share/restore source may be a free slot — freeing either would
        # invalidate pages the caller is actively splicing
        protect = slot if isinstance(slot, tuple) else (slot,)
        for i, s in enumerate(self.slots):
            if self._pool.free_pages >= need_free:
                return
            if s is None and i not in protect and self._pool.owned[i]:
                self._pool.release(i, 0)
                self._cache_tokens[i] = []
        if (self._prefetch is not None and len(self._prefetch)
                and self._pool.free_pages < need_free):
            # pool pressure outranks speculation: raid the prefetch
            # pipeline's staged pages BEFORE evicting retained chains —
            # staged pages are merely predicted-useful (their content
            # still lives in the host tier), retained chains are
            # known-useful. Counted WASTED: the prediction lost to load.
            drained = self._prefetch.drain()
            for _key, rec in drained:
                self._pool.unref_detached(rec[0])
            if drained and self._hstore is not None:
                self._hstore.note_prefetch_wasted(len(drained))
        if self._pcache is not None:
            victims = []
            on_evict = None
            if self._hstore is not None:
                # device->host handoff: collect each evicted entry while
                # its page id still names valid rows; one batched gather
                # goes out below, BEFORE any dispatch that could reuse
                # the freed pages (device program order makes the copy
                # read the pre-eviction content)
                def on_evict(e, _v=victims):
                    if not self._hstore.contains(e.key):
                        _v.append((e.key, e.parent, e.depth, e.page))
            self._pcache.evict(self._pool, need_free, on_evict)
            if victims:
                self._dispatch_offload(victims)

    def _ensure_pages(self, slot: int, rows: int):
        """Lazy page allocation with reclaim-and-retry on pool pressure."""
        if not self._paged:
            return
        from localai_tpu.engine.paging import PoolExhausted

        if FAULTS.active and FAULTS.take("page_alloc_fail") is not None:
            raise PoolExhausted("injected fault: page_alloc_fail")
        try:
            self._pool.ensure(slot, rows)
            return
        except PoolExhausted:
            pass
        self._reclaim_pages(slot, self._pool.pages_for(rows))
        if self._sched is not None:
            try:
                self._pool.ensure(slot, rows)
                return
            except PoolExhausted:
                pass
            # pool-pressure preemption (closes the PR-3 "offload ACTIVE
            # slots under extreme pressure" follow-up): pause a
            # strictly-lower-priority DECODE slot — decode-only because
            # this runs mid-prefill-pack, where a prefill-phase victim
            # could be a seg of the pack being built — then reclaim
            # again so its now-retained pages evict/offload
            me = self.slots[slot]
            my_rank = me.prio if me is not None else PRIORITY_RANK["high"]
            victim = self._pick_victim(my_rank, decode_only=True)
            if victim is not None and victim != slot:
                self._preempt_slot(victim, why="pool_pressure")
                self._reclaim_pages(slot, self._pool.pages_for(rows))
        self._pool.ensure(slot, rows)   # raises PoolExhausted if truly full

    def _alloc_detached(self, slot=-1) -> int:
        """alloc_detached with the same reclaim-and-retry discipline as
        _ensure_pages: a COW boundary clone must not fail while retained
        pages are still evictable. ``slot`` is the table being built —
        reclaim must not release it (mid-admission the slot is still
        unoccupied, so without the exclusion reclaim would free the
        pages just spliced into it)."""
        from localai_tpu.engine.paging import PoolExhausted

        try:
            return self._pool.alloc_detached()
        except PoolExhausted:
            self._reclaim_pages(slot, 1)
            return self._pool.alloc_detached()

    def _get_page_clone_fn(self):
        fn = self._fork_fns.get("page_clone")
        if fn is None:
            fn = self._program(
                "kv_page_clone", None, "none",
                lambda ck, cv, src, dst: (kvcache.clone_page(ck, src, dst),
                                          kvcache.clone_page(cv, src, dst)),
                donate_argnums=(0, 1))
            self._fork_fns["page_clone"] = fn
        return fn

    def _get_draft_clone_fn(self):
        fn = self._fork_fns.get("page_clone_draft")
        if fn is None:
            fn = self._program(
                "draft_kv_page_clone", None, "none",
                lambda ck, cv, src, dst: (kvcache.clone_page(ck, src, dst),
                                          kvcache.clone_page(cv, src, dst)),
                donate_argnums=(0, 1))
            self._fork_fns["page_clone_draft"] = fn
        return fn

    def _cow_guard(self, slot: int, row: int):
        """Copy-on-write: if the page containing ``row`` (the slot's first
        write position) is shared, clone it into a fresh page before any
        scatter can touch it. Pages before it stay shared — zero copies
        for the common prefix; this one page is the 'first divergent
        page' clone. The paged draft cache clones the same page id: its
        rows diverge exactly when the target's do."""
        if not self._paged:
            return
        pi = self._pool.cow_page(slot, row)
        if pi < 0:
            return
        new = self._alloc_detached(slot)
        old = int(self._pool.ptab[slot, pi])
        self._commit_ptab()
        self.ck, self.cv = self._get_page_clone_fn()(
            self.ck, self.cv, np.int32(old), np.int32(new))
        if self.dck is not None:
            self.dck, self.dcv = self._get_draft_clone_fn()(
                self.dck, self.dcv, np.int32(old), np.int32(new))
        self._pool.replace(slot, pi, new)

    def _get_offload_gather_fn(self, batch: int):
        key = ("offload_gather", batch)
        fn = self._fork_fns.get(key)
        if fn is None:
            fn = self._program(
                "kv_offload_gather", batch, "none",
                lambda ck, cv, idx: (kvcache.gather_pages(ck, idx),
                                     kvcache.gather_pages(cv, idx)))
            self._fork_fns[key] = fn
        return fn

    def _get_restore_scatter_fn(self, batch: int):
        key = ("restore_scatter", batch)
        fn = self._fork_fns.get(key)
        if fn is None:
            fn = self._program(
                "kv_restore_scatter", batch, "none",
                lambda ck, cv, idx, kr, vr: (
                    kvcache.scatter_pages(ck, idx, kr),
                    kvcache.scatter_pages(cv, idx, vr)),
                donate_argnums=(0, 1))
            self._fork_fns[key] = fn
        return fn

    def _dispatch_offload(self, victims: list):
        """Issue ONE non-blocking device gather for a batch of evicted
        pages and queue the host transfer on the sync worker. The batch
        pads to a power of two (repeat-last — duplicate reads are free)
        so only log2 gather programs ever compile."""
        n = len(victims)
        B = 1
        while B < n:
            B *= 2
        idx = np.full((B,), victims[-1][3], np.int32)
        for i, (_k, _p, _d, page) in enumerate(victims):
            idx[i] = page
        with self._annot("kv_offload_gather", pages=n):
            k_rows, v_rows = self._get_offload_gather_fn(B)(self.ck,
                                                            self.cv, idx)
        d_rows = None
        if self.dck is not None:
            # paged draft cache (ISSUE 13): offload the draft rows of the
            # same pages so a restored spec slot resumes drafting without
            # a cold draft cache (the gather fn re-specializes per cache
            # shape under jit, so the same callable serves both)
            with self._annot("kv_offload_gather_draft"):
                d_rows = self._get_offload_gather_fn(B)(self.dck,
                                                        self.dcv, idx)
        item = _PendingOffload([(k, p, d) for k, p, d, _pg in victims],
                               k_rows, v_rows, self._hstore, d_rows)
        self._sync_q.put(item)

    def _upload_pages(self, pages: list, host_hits: list):
        """Dispatch the async host->device scatter copying ``host_hits``
        (host-tier entries) into ``pages`` (allocated device page ids,
        same order/length), draft planes included — the shared upload
        half of _restore_offloaded, the windowed admission, and the
        prefetch tick. Pure dispatch: no table edits, no host syncs; by
        device program order the copy completes before any later
        dispatch reads the rows."""
        pool = self._pool
        n = len(host_hits)
        B = 1
        while B < n:
            B *= 2
        # sentinel-pad the scatter batch: out-of-pool page ids DROP
        idx = np.full((B,), pool.num_pages, np.int32)
        idx[:n] = pages[:n]

        # double-buffered staging (PR-3 follow-up): the async scatter
        # dispatched below may still be READING the previous parity's
        # buffers while this batch fills the other set — reuse without
        # aliasing, and no per-restore stack/concatenate allocations
        par = self._rstager.begin()
        ks = self._rstager.fill(par, "k", host_hits, lambda e: e.k, B)
        vs = self._rstager.fill(par, "v", host_hits, lambda e: e.v, B)

        with self._annot("kv_restore_scatter", pages=n):
            self.ck, self.cv = self._get_restore_scatter_fn(B)(
                self.ck, self.cv, idx, ks, vs)
        # paged draft cache (ISSUE 13): restore the draft rows of any hit
        # that carried them (entries offloaded pre-draft, loaded from an
        # old disk snapshot, or whose draft payload failed its CRC have
        # dk None — their draft rows stay cold, which is merely an
        # acceptance-rate hit, never a correctness one)
        dhits = [(j, e) for j, e in enumerate(host_hits)
                 if e.dk is not None] if self.dck is not None else []
        if dhits:
            B2 = 1
            while B2 < len(dhits):
                B2 *= 2
            didx = np.full((B2,), pool.num_pages, np.int32)
            for c, (j, _e) in enumerate(dhits):
                didx[c] = pages[j]
            dents = [e for _j, e in dhits]
            dks = self._rstager.fill(par, "dk", dents, lambda e: e.dk, B2)
            dvs = self._rstager.fill(par, "dv", dents, lambda e: e.dv, B2)
            with self._annot("kv_restore_scatter_draft"):
                self.dck, self.dcv = self._get_restore_scatter_fn(B2)(
                    self.dck, self.dcv, didx, dks, dvs)

    def _restore_offloaded(self, slot: int, host_hits: list) -> int:
        """Upload offloaded pages into freshly allocated device rows and
        splice them onto the slot's table — DISPATCH-THEN-SPLICE: the
        host->device copy is issued as one async jit call (it overlaps
        whatever decode bursts are already in flight; by device program
        order it completes before the slot's prefill reads the rows),
        the table edit is pure host work, and the serving loop never
        syncs. Partial allocation under pool pressure degrades to a
        shorter restored chain (still contiguous from the root).
        Returns the number of pages actually restored."""
        pool = self._pool
        pages = pool.alloc_many(len(host_hits))
        if len(pages) < len(host_hits):
            self._reclaim_pages(slot, len(host_hits) - len(pages))
            pages.extend(pool.alloc_many(len(host_hits) - len(pages)))
        host_hits = host_hits[:len(pages)]
        if not host_hits:
            for p in pages:
                pool.unref_detached(p)
            return 0
        n = len(host_hits)
        self._upload_pages(pages, host_hits)
        for e, p in zip(host_hits, pages[:n]):
            pool.adopt(slot, p)
            # restored pages re-enter the device tier immediately: the
            # attach hold makes refs >= 2, so the admitting prefill's
            # boundary write COW-clones instead of corrupting the copy
            self._pcache.attach(pool, e.key, e.parent, p, e.depth)
        self._hstore.note_restore(n)
        return n

    def _share_prefix(self, src: int, dst: int, rows: int) -> int:
        """Zero-copy prefix transfer: full pages covering rows[0:rows] are
        ref-count-shared into dst's table; when the prefix ends mid-page,
        that FIRST DIVERGENT page is cloned (one page copy, never a row
        loop) so dst reuses exactly ``rows`` rows."""
        shared = self._pool.share(src, dst, rows)
        if shared < rows:
            pi = shared // self._pool.page_size
            new = self._alloc_detached((src, dst))
            src_page = int(self._pool.ptab[src, pi])
            self._commit_ptab()
            self.ck, self.cv = self._get_page_clone_fn()(
                self.ck, self.cv, np.int32(src_page), np.int32(new))
            if self.dck is not None:
                self.dck, self.dcv = self._get_draft_clone_fn()(
                    self.dck, self.dcv, np.int32(src_page), np.int32(new))
            self._pool.adopt(dst, new)
            shared = rows
        return shared

    def _prefetch_tick(self):
        """Decode-time prefetch-ahead (ISSUE 16, tentpole): scan the
        admission queue's head, predict which HOST-TIER chain links each
        request's admission will restore, and upload them into detached
        device pages NOW — overlapped with the decode bursts already in
        flight — so the admission finds the rows resident and the
        synchronous restore cost drops off TTFT (PRESERVE,
        arXiv:2501.08192). Window-aware: with the snap-back window armed
        only the sink + tail-window links are fetched, so speculation
        never pulls the cold middle a windowed admission would skip.
        Never evicts truth for speculation: fetches stop at the pool's
        free headroom and a failed alloc simply ends the pass."""
        pf = self._prefetch
        pf.tick += 1
        expired = pf.expire()
        if expired:
            for _key, rec in expired:
                self._pool.unref_detached(rec[0])
            self._hstore.note_prefetch_wasted(len(expired))
        ahead = max(1, int(self.ecfg.kv_prefetch_ahead))
        with self._queue.mutex:
            reqs = list(self._queue.queue)[:ahead]
        if not reqs:
            return
        pool = self._pool
        pg = pool.page_size
        C = self.ecfg.max_context
        budget = 4 * ahead * max(1, self._win_pages or 8)  # pages/tick
        for req in reqs:
            if budget <= 0:
                break
            rid = req.request_id
            if rid in pf.seen_rids or req.mm_vectors is not None:
                continue
            pf.seen_rids.add(rid)
            ids = list(req.prompt_ids)
            # mirror _start_request's head truncation — keys past it
            # would be fetched for a prompt that will never admit them
            max_prompt = C - 1 - min(req.max_new_tokens, C // 4)
            if len(ids) > max_prompt:
                ids = ids[-max_prompt:]
            n_links = (len(ids) - 1) // pg
            if n_links <= 0:
                continue
            keys = []
            for i, key in enumerate(self._pcache.chain_keys(ids)):
                if i >= n_links:
                    break
                keys.append(key)
            d = 0                      # device-resident chain depth
            while d < len(keys) and self._pcache.contains(keys[d]):
                d += 1
            n_avail = d
            while n_avail < len(keys) and (
                    keys[n_avail] in pf.pages
                    # contains_any (ISSUE 17): a chain link held only by
                    # a PEER host still counts as available — the get()
                    # below streams it through the federated tier, so
                    # prefetch-ahead rides the transport (PRESERVE
                    # across hosts)
                    or self._hstore.contains_any(keys[n_avail])):
                n_avail += 1
            if n_avail <= d:
                continue
            sink, W = self._win_sink, self._win_pages
            if W and n_avail > sink + W:
                wanted = list(range(sink)) + list(range(n_avail - W,
                                                        n_avail))
            else:
                wanted = list(range(n_avail))
            fetch = [i for i in wanted
                     if i >= d and keys[i] not in pf.pages][:budget]
            if not fetch:
                continue
            if pool.free_pages < len(fetch) + 4:
                break                  # headroom guard: truth first
            ents = []
            for i in fetch:
                e = self._hstore.get(keys[i])
                if e is None:
                    break              # hole opened since the probe
                ents.append(e)
            if not ents:
                continue
            pages = pool.alloc_many(len(ents))
            if len(pages) < len(ents):
                # speculation never reclaims: give back and stop
                for p in pages:
                    pool.unref_detached(p)
                break
            self._upload_pages(pages, ents)
            for e, p in zip(ents, pages):
                pf.register(e.key, e.parent, p, e.depth)
            self._hstore.note_prefetch_issued(len(ents))
            budget -= len(ents)
            # completion probe rides the sync worker in dispatch order:
            # a scalar slice of the post-scatter cache blocks exactly
            # until this batch's upload executed, then retires the
            # store's inflight gauge — the /debug/kv restore depth
            leaf = jax.tree.leaves(self.ck)[0]
            self._sync_q.put(_PendingPrefetch(
                [], leaf[(0,) * leaf.ndim], None, self._hstore))

    def _abs_chain_keys(self, slot: int, s, upto_page: int) -> list:
        """Absolute chain keys for the slot's first ``upto_page`` full
        pages, extended incrementally from its absolute token history
        and cached on the slot (ISSUE 16). A windowed slot's compact
        table no longer maps 1:1 onto its token stream, so window
        advance / release derive offload keys from the ABSOLUTE stream
        — O(new pages) per call, not O(context) per advance."""
        keys = s.chain_keys
        toks = self._cache_tokens[slot]
        pg = self._pool.page_size
        upto_page = min(upto_page, len(toks) // pg)
        if len(keys) < upto_page:
            parent = keys[-1] if keys else kvcache.PAGE_HASH_ROOT
            scope = self._pcache.scope
            for i in range(len(keys), upto_page):
                parent = kvcache.page_chain_hash(
                    parent, toks[i * pg:(i + 1) * pg], scope)
                keys.append(parent)
        return keys

    def _advance_window(self, i: int, upcoming: int):
        """Snap-back window advance (ISSUE 16): before dispatching work
        that would push slot i's compact rows past the bounded working
        set ((sink + window) pages), demote the oldest non-sink FULL
        committed pages out of the table. Policy "demote" first
        offloads their content to the host tier (the async gather is
        dispatched BEFORE pool.demote can recycle the pages — device
        program order protects the copy, same as _reclaim_pages);
        policy "drop" records an explicit ledger "compress" op instead,
        so the auditor sees the rows leave by policy, not by leak.
        Compact coordinates then re-base: lengths/committed/written
        shrink by the demoted rows while pos_offset/win_off grow by the
        same amount — RoPE positions stay ABSOLUTE — and _win_delta
        carries the length rebase into an in-flight decode chain
        without forcing an override."""
        s = self.slots[i]
        if (not self._win_pages or not self._paged or s is None
                or s.mm_pos is not None or self.ecfg.ga_n > 1):
            # ga rotation owns pos_offset; the window never composes
            # with it (windowed admission is already ga-gated too)
            return
        pool = self._pool
        pg = pool.page_size
        sink = self._win_sink
        rows = max(int(self.lengths[i]), s.written) + max(0, upcoming)
        budget = (sink + self._win_pages) * pg
        if rows <= budget:
            return
        k = pool.pages_for(rows - budget)
        # only fully COMMITTED pages may leave (uncommitted speculative
        # rows must stay rollback-able), and never the sinks
        k = min(k, s.committed // pg - sink)
        if k <= 0:
            return
        start_abs = s.win_off // pg + sink
        if self.ecfg.kv_window_policy == "demote":
            victims = []
            keys = self._abs_chain_keys(i, s, start_abs + k)
            for t in range(min(k, len(keys) - start_abs)):
                ap = start_abs + t
                if self._hstore.contains(keys[ap]):
                    continue
                parent = keys[ap - 1] if ap > 0 else kvcache.PAGE_HASH_ROOT
                victims.append((keys[ap], parent, ap,
                                int(pool.ptab[i, sink + t])))
            if victims:
                self._dispatch_offload(victims)
        elif pool.audit is not None:
            # drop policy: the middle rows are compressed away — a
            # first-class lifecycle op, not a leak
            pool.audit.ledger.record("compress", slot=i)
        pool.demote(i, sink, k)
        delta = k * pg
        self.lengths[i] -= delta
        self.pos_offset[i] += delta
        s.win_off += delta
        s.committed -= delta
        s.written -= delta
        s.cache_len = max(0, s.cache_len - delta)
        if self._chain is not None:
            self._win_delta[i] += delta

    def _windowed_admission(self, slot: int, ids: list, cap: int,
                            cached_pages: list, rid: str = ""):
        """Snap-back window at (re-)admission (ISSUE 16): when the
        two-tier chain covers more of the prompt than the bounded
        on-device working set (sink + window pages), splice/restore ONLY
        the attention-sink head and the tail window. The cold middle
        never touches the device — it stays retained device-side or in
        the host tier — and the slot's compact row coordinates re-base
        by ``win_off`` = the skipped middle rows (positions stay
        absolute via pos_offset). Returns the compact reused row count
        (stashing self._adm_win_off for _start_request), or None to fall
        through to the unwindowed admission path."""
        pool = self._pool
        pg = pool.page_size
        sink, W = self._win_sink, self._win_pages
        d = len(cached_pages)
        # phase 1: availability over the whole chain with cheap
        # membership probes only — no LRU touch, no CRC on the middle
        # links the selection will skip (a 128k chain must not pay a
        # full-store CRC walk per admission)
        keys = []
        for i, key in enumerate(self._pcache.chain_keys(ids)):
            if i >= cap // pg:
                break           # always leave >= 1 token to prefill
            keys.append(key)
        n_avail = d
        while n_avail < len(keys):
            key = keys[n_avail]
            if ((self._prefetch is not None
                 and key in self._prefetch.pages)
                    # contains_any (ISSUE 17): peer-held links count as
                    # available — the selected links' get() streams them
                    # in through the federated tier; a probe/get race
                    # (peer died in between) is the same handled hole as
                    # a local CRC drop
                    or self._hstore.contains_any(key)):
                n_avail += 1
            else:
                break
        n_avail = min(n_avail, len(keys))
        if n_avail <= sink + W:
            return None         # fits the working set: no window needed
        while True:
            sel = list(range(sink)) + list(range(n_avail - W, n_avail))
            # device-resident selected links are always a PREFIX of the
            # compact order (the device tier is prefix-closed, so the
            # links it holds are exactly [0, d))
            splice_pages = [cached_pages[i] for i in sel if i < d]
            rest = [i for i in sel if i >= d]
            fetched = []        # (abs link, key, prefetch rec | entry)
            failed_at = -1
            for i in rest:
                key = keys[i]
                rec = (self._prefetch.claim(key)
                       if self._prefetch is not None else None)
                if rec is not None:
                    fetched.append((i, key, rec))
                    continue
                e = self._hstore.get(key)
                if e is None:
                    failed_at = i
                    break
                fetched.append((i, key, e))
            if failed_at < 0:
                break
            # a link vanished between probe and get (budget eviction,
            # CRC drop): shrink availability to the hole and reselect;
            # claimed prefetch pages go back on the shelf first
            for i, key, rec in fetched:
                if isinstance(rec, list):
                    self._prefetch.register(key, rec[1], rec[0], rec[2])
            n_avail = failed_at
            if n_avail <= sink + W:
                return None
        ents = [r for _i, _k, r in fetched if not isinstance(r, list)]
        pages = pool.alloc_many(len(ents))
        if len(pages) < len(ents):
            self._reclaim_pages(slot, len(ents) - len(pages))
            pages.extend(pool.alloc_many(len(ents) - len(pages)))
        if len(pages) < len(ents):
            # a partial window would leave holes mid-table — give the
            # pages back and let the unwindowed path degrade gracefully
            for p in pages:
                pool.unref_detached(p)
            for i, key, rec in fetched:
                if isinstance(rec, list):
                    self._prefetch.register(key, rec[1], rec[0], rec[2])
            return None
        pool.release(slot, 0)
        pool.splice(slot, splice_pages)
        if ents:
            self._upload_pages(pages, ents)
        pi = 0
        n_pre = 0
        for i, key, rec in fetched:
            if isinstance(rec, list):
                page = rec[0]       # prefetched: rows already on device
                n_pre += 1
            else:
                page = pages[pi]
                pi += 1
            pool.adopt(slot, page)
            if i < sink:
                # device-tier re-entry only for links that keep the tier
                # prefix-closed (the contiguous sink continuation of the
                # device chain); tail-window pages ride the table alone
                # and free with it
                self._pcache.attach(
                    pool, key,
                    rec[1] if isinstance(rec, list) else rec.parent,
                    page, i)
        if n_pre:
            self._hstore.note_prefetch_hit(n_pre)
        if ents:
            self._hstore.note_restore(len(ents))
            if (self._prefetch is not None
                    and rid in self._prefetch.seen_rids):
                # the pipeline scanned this request but the admission
                # still restored synchronously: the prefetch was LATE
                self._hstore.note_prefetch_late(len(ents))
        middle = n_avail - sink - W
        self._adm_win_off = middle * pg
        if pool.audit is not None:
            # first-class ledger op: the middle of the chain was
            # window-compressed out of the on-device working set
            pool.audit.ledger.record("compress", slot=slot)
        compact = (sink + W) * pg
        self._cow_guard(slot, compact)
        self._pcache.note_hit(compact)
        return compact

    def _paged_admission(self, slot: int, ids: list, common: int,
                         rid: str = "") -> int:
        """Paged prefix reuse at admission. Returns the reusable row
        count. Four tiers, best (longest usable prefix) wins:
          1. the slot's OWN retained rows (common — free, pages already
             owned);
          2. another slot's prefix, shared COPY-ON-WRITE (_share_prefix):
             zero KV row copies for the full pages, at most one page
             clone at the divergence boundary; only rows that are
             read-only for the source (committed prompt rows of an
             active slot / retained rows of a free one) are eligible;
          3. the CROSS-RELEASE prefix cache (engine/prefix_cache.py):
             the prompt's chained page hashes are matched against
             retained pages and the chain is spliced into the slot's
             table — zero copies, works after the source slot is gone;
          4. none — pages released for reuse by the pool.
        Tiers 2 and 3 share the min-rows guard (kv_prefix_cache_min_rows)
        so a 1-page BOS match never forces the slow continued-prefill
        path, and either way the first page this request will write is
        COW-guarded. With the snap-back window armed (ISSUE 16) a chain
        longer than the working set takes _windowed_admission instead,
        which sets self._adm_win_off; this method always resets it."""
        pool = self._pool
        self._adm_win_off = 0
        if "prefix_reuse" not in self._caps:
            pool.release(slot, 0)       # tier 4, always
            return 0
        min_rows = max(1, self.ecfg.kv_prefix_cache_min_rows)
        cap = len(ids) - 1              # always leave >= 1 token to prefill
        best_src, best_rows = -1, 0
        if self.ecfg.ga_n <= 1:
            # cross-slot scan (self-extend rewrites cached keys in place,
            # so sharing is gated off under ga — rotation would corrupt
            # the other referents' view)
            for j, sj in enumerate(self.slots):
                if j == slot:
                    continue
                toks = self._cache_tokens[j]
                limit = len(toks) if sj is None else min(sj.committed,
                                                         sj.prompt_len)
                if sj is not None and sj.win_off > 0:
                    # a windowed live source only retains its sink pages
                    # as a contiguous absolute prefix — everything past
                    # them sits at compact (shifted) rows share() must
                    # never alias
                    limit = min(limit, self._win_sink * pool.page_size)
                limit = min(limit, cap, pool.slot_rows_capacity(j))
                n = 0
                for a, b in zip(toks[:limit], ids):
                    if a != b:
                        break
                    n += 1
                if n > best_rows:
                    best_src, best_rows = j, n
        if self._pcache is not None:
            cached_pages = self._pcache.match(ids, pool.max_pages)
            if (self._win_pages and self._hstore is not None
                    and self.ecfg.ga_n <= 1):
                win = self._windowed_admission(slot, ids, cap,
                                               cached_pages, rid=rid)
                if win is not None:
                    return win
            if self.ecfg.ga_n > 1:
                # self-extend composition (ISSUE 16 satellite): only rows
                # inside the COMPRESSED region of the new request are
                # byte-reusable — a compressed row's grouped position
                # depends solely on its absolute index, never on the
                # block count, so rows both sides have compressed agree
                # exactly while the raw tail does not. The scope already
                # pins ga_n/ga_w; the release path inserts only
                # fully-compressed pages under the same rule.
                cap = min(cap, self._ga_c(len(ids)) * self.ecfg.ga_w)
            host_hits = []
            pre_keys = []
            if self._hstore is not None:
                # TWO-TIER chain walk: the device tier is prefix-closed
                # (eviction cascades subtrees), so the host tier can only
                # CONTINUE the chain past the device pages — same key
                # sequence, links [d, h) served from offloaded copies.
                # Prefetched links (ISSUE 16) are claimed first while
                # they are the CONTIGUOUS continuation — their rows are
                # already on device, so they cost an adopt, not a
                # restore.
                want = min(pool.max_pages, cap // pool.page_size + 1)
                for i, key in enumerate(self._pcache.chain_keys(ids)):
                    if i < len(cached_pages):
                        continue
                    if (len(cached_pages) + len(pre_keys)
                            + len(host_hits) >= want):
                        break
                    if (self._prefetch is not None and not host_hits
                            and key in self._prefetch.pages):
                        pre_keys.append(key)
                        continue
                    e = self._hstore.get(key)
                    if e is None:
                        break
                    host_hits.append(e)
            cached_rows = min(
                (len(cached_pages) + len(pre_keys) + len(host_hits))
                * pool.page_size, cap)
            if cached_rows >= min_rows and cached_rows > max(common,
                                                            best_rows):
                pool.release(slot, 0)
                pool.splice(slot, cached_pages)
                n_pre = 0
                for key in pre_keys:
                    rec = self._prefetch.claim(key)
                    if rec is None:     # claimed away mid-admission
                        break
                    # the pipeline's detached reference transfers to the
                    # table; attach re-enters the device tier (the chain
                    # stays prefix-closed — these links continue it)
                    pool.adopt(slot, rec[0])
                    self._pcache.attach(pool, key, rec[1], rec[0], rec[2])
                    n_pre += 1
                if n_pre:
                    self._hstore.note_prefetch_hit(n_pre)
                if n_pre < len(pre_keys):
                    host_hits = []      # chain has a hole past the claim
                restored = 0
                if host_hits:
                    # dispatch-then-splice (see _restore_offloaded): the
                    # upload overlaps in-flight decode work; a partial
                    # restore under pool pressure shortens the reuse,
                    # never fails the admission
                    restored = self._restore_offloaded(slot, host_hits)
                    if (self._prefetch is not None
                            and rid in self._prefetch.seen_rids):
                        # scanned by the pipeline, restored sync anyway:
                        # the prefetch lost the race — LATE
                        self._hstore.note_prefetch_late(restored)
                cached_rows = min(
                    (len(cached_pages) + n_pre + restored)
                    * pool.page_size, cap)
                if cached_rows == 0:
                    # pathological: nothing spliced and nothing restored
                    self._pcache.note_miss()
                    return 0
                # a retained page re-entering a table carries refs >= 2
                # (table + cache hold), so the existing COW guard clones
                # the boundary page before the first prefill write —
                # cached rows are immutable by construction
                self._cow_guard(slot, cached_rows)
                self._pcache.note_hit(cached_rows)
                return cached_rows
            if self._hstore is not None and not host_hits and not pre_keys \
                    and len(ids) // pool.page_size > len(cached_pages):
                # the host tier was consulted past the device chain and
                # had nothing usable — the restore-miss path: plain
                # prefill, byte-identical to PR-2 behavior
                self._hstore.note_miss()
            self._pcache.note_miss()
        if best_rows > common and best_rows >= min_rows:
            pool.release(slot, 0)
            return self._share_prefix(best_src, slot, best_rows)
        pool.release(slot, common)
        if common:
            self._cow_guard(slot, common)
        return common

    # ---------- jitted step bodies ----------

    def _family_step(self, fn, *a, **kw):
        """``fn`` (the family's engine_decode or ragged_prefill) -> (logits,
        ck, cv, route stats [n] float32, or None for a family that reports
        none)."""
        if self._n_route:
            return fn(*a, **kw, route_stats=True)
        return (*fn(*a, **kw), None)

    def _route_rows(self, stats):
        """Route stats [n] as whole rows of a burst's [*, S] pack."""
        S, rows = self.ecfg.num_slots, self._route_rows_n
        return jnp.pad(stats, (0, rows * S - self._n_route)).reshape(rows, S)

    def _fold_route(self, kind: str, flat, steps: int) -> int:
        """Fold the route stats a dispatch brought back (``flat``: the sum
        over its ``steps`` decode steps, or one prefill pack's) into
        /debug/state's "moe"; -> the experts touched, summed over steps and
        layers."""
        c = self._moe[kind]
        st = np.rint(flat[:self._n_route]).astype(np.int64).reshape(
            len(c["pairs"]), -1)
        c["steps"] += steps
        c["pairs"] += st[:, :-2]
        c["experts_touched"] += st[:, -2]
        c["pairs_routed"] += st[:, -1]
        return int(st[:, -2].sum())

    def _moe_snapshot(self) -> dict:
        """/debug/state's "moe", since start: for the decode steps and for
        the prefill packs apart, how many there were (``steps``), an expert
        layer the distinct experts touched summed over them
        (``experts_touched`` [L_moe]), the (row, expert) pairs each expert
        held here got (``pairs`` [L_moe][E]; ``experts`` is that E) and the
        pairs the rows routed in all, to experts held here or on other
        chips (``pairs_routed`` [L_moe]: the sum of ``pairs`` where every
        expert is held)."""
        return {"experts": self._moe["decode"]["pairs"].shape[1],
                **{k: {"steps": c["steps"],
                       "experts_touched": c["experts_touched"].tolist(),
                       "pairs_routed": c["pairs_routed"].tolist(),
                       "pairs": c["pairs"].tolist()}
                   for k, c in self._moe.items()}}

    def _compose_overrides(self, tokens, lengths, ring, ring_pos, mu, ov_pack):
        """Merge host override rows (ONE packed [7+RING_N, S] f32 upload:
        mask, tokens, lengths, ring_pos, mu, pos_offset, win_delta,
        ring.T) into the chain state. pos_offset (self-extend / snap-back
        window) is NOT override-gated — it is current host truth every
        dispatch. win_delta (ISSUE 16) is an unconditional SUBTRACT from
        the chained device lengths: a window advance re-bases a slot's
        compact rows mid-chain without forcing an override (and therefore
        without a host sync); overridden slots carry already-rebased host
        lengths, so _pack_ov zeroes their delta to avoid double-counting."""
        ov_mask = ov_pack[0] > 0
        tokens = jnp.where(ov_mask, ov_pack[1].astype(jnp.int32), tokens)
        lengths = jnp.where(ov_mask, ov_pack[2].astype(jnp.int32), lengths) \
            - ov_pack[6].astype(jnp.int32)
        ring_pos = jnp.where(ov_mask, ov_pack[3].astype(jnp.int32),
                             jnp.asarray(ring_pos))
        mu = jnp.where(ov_mask, ov_pack[4], jnp.asarray(mu))
        pos_offset = ov_pack[5].astype(jnp.int32)
        ring = jnp.where(ov_mask[:, None], ov_pack[7:].T.astype(jnp.int32),
                         jnp.asarray(ring))
        return tokens, lengths, ring, ring_pos, mu, pos_offset

    def _decode_burst_body(self, params, tokens, ck, cv, lengths, ring, ring_pos,
                           bias, keys, slot_params, active, mu,
                           ov_pack, n_steps: int,
                           flags: tuple = (True, True, True)):
        """n_steps decode+sample steps in ONE dispatch (lax.scan).

        One dispatch and one host sync then pay for n_steps tokens per
        slot. bias/slot_params/active are constant across the burst.

        tokens/lengths/ring/ring_pos/mu arrive as the previous burst's
        DEVICE output handles (the chain); ov_pack carries host rows
        composed in for newly activated / rolled-back / re-admitted slots —
        so host events never force a chain rebuild (and therefore never
        force the host to wait on an in-flight burst before it can
        dispatch the next one)."""
        slot_params = sampling.unpack_slot_params(slot_params)
        tokens, lengths, ring, ring_pos, mu, pos_offset = \
            self._compose_overrides(tokens, lengths, ring, ring_pos, mu,
                                    ov_pack)

        step = self._make_scan_step(params, slot_params, bias, active, flags,
                                    pos_offset)
        carry = (tokens, ck, cv, lengths, ring, ring_pos, keys, mu)
        carry, (ids_all, lps_all, route) = jax.lax.scan(
            step, carry, None, length=n_steps)
        tokens, ck, cv, lengths, ring, ring_pos, keys, mu = carry
        # tokens/lengths/ring/mu are returned as DEVICE handles so the next
        # burst can chain off them without a host round-trip (pipelined
        # decode). Everything the host needs (ids, logprobs, post-burst mu)
        # is PACKED into one [2K+1, S] float32 array: one device->host
        # transfer per burst instead of three tiny ones.
        # float32 holds token ids exactly (vocab << 2^24). A family that
        # reports its routing adds the burst's sum as further rows.
        pack = jnp.concatenate(
            [ids_all.astype(jnp.float32), lps_all, mu[None, :]]
            + ([] if route is None
               else [self._route_rows(route.sum(axis=0))]), axis=0)
        return pack, ck, cv, keys, self._pin_chain(
            tokens, lengths, ring, ring_pos, mu)

    def _make_scan_step(self, params, slot_params, bias, active, flags,
                        pos_offset=None):
        """The shared decode+sample scan step for plain and fused bursts.

        Inactive slots (free / mid-prefill) must NOT advance their cache
        state (the family adapter masks KV writes / state updates), and
        only active slots consume RNG/mirostat/ring state: a prefilling
        slot's seeded state must not advance with others' decode steps.
        Which branch of the sampler a step takes is constant over the
        burst, so the predicate is computed here, outside the scan."""
        all_plain = sampling.all_plain_greedy(slot_params, active)

        def step(carry, _):
            tokens, ck, cv, lengths, ring, ring_pos, keys, mu = carry
            logits, ck, cv, route = self._family_step(
                self.family.engine_decode,
                params, self.cfg, tokens, lengths, active, ck, cv,
                pos_offset=pos_offset)
            ids, logprobs, new_keys, new_mu = sampling.sample(
                logits, slot_params, ring, ring_pos, bias, keys, mu,
                use_penalties=flags[0], use_typical=flags[1],
                use_mirostat=flags[2], all_plain=all_plain)
            keys = jnp.where(active[:, None], new_keys, keys)
            mu = jnp.where(active, new_mu, mu)
            ring, ring_pos = sampling.update_ring(ring, ring_pos, ids, active)
            lengths = lengths + active.astype(jnp.int32)
            tokens = jnp.where(active, ids, tokens)
            return ((tokens, ck, cv, lengths, ring, ring_pos, keys, mu),
                    (ids, logprobs, route))

        return step

    def _prefill_chunk_body(self, params, tokens, seq_len, ck, cv, slot, start_pos,
                            mm_pos=None, mm_vec=None):
        """Non-final chunk: write KV only, no sampling. (The penalty ring is
        seeded host-side at admission from the full prompt tail.)"""
        _, ck, cv = self.family.prefill(params, self.cfg, tokens, seq_len, ck,
                                        cv, slot, start_pos, continued=True,
                                        mm_pos=mm_pos, mm_vec=mm_vec)
        return ck, cv

    def _fused_body(self, params, tokens, ck, cv, lengths, ring, ring_pos,
                    bias, keys, slot_params, active, mu,
                    ov_pack, p_tokens, p_seq, p_slots, p_start,
                    n_steps: int):
        """FUSED admission: final-prefill a batch of B fresh prompts,
        sample their first tokens, and run the decode burst with those
        slots already active — all in ONE dispatch.

        Separate dispatches pay the per-dispatch overhead twice, and the
        prefill->host->activate round-trip idles the admitted slots
        between them. Fusing
        collapses both, and makes singleton admissions as cheap as batched
        ones, so admission never holds requests back to form groups.
        (The reference packs prompt chunks and decode tokens into one
        llama_batch for the same reason — grpc-server.cpp:1671+.)

        Duplicate p_slots entries (pow2 batch padding repeats the last
        prompt) stay idempotent: every per-slot update is a .set() of
        identical values (same inputs -> same sampled id)."""
        slot_params = sampling.unpack_slot_params(slot_params)
        tokens, lengths, ring, ring_pos, mu, pos_offset = \
            self._compose_overrides(tokens, lengths, ring, ring_pos, mu,
                                    ov_pack)

        logits, ck, cv = self.family.prefill(params, self.cfg, p_tokens,
                                             p_seq, ck, cv, p_slots, p_start,
                                             continued=False)
        sp_rows = jax.tree.map(lambda a: jnp.take(jnp.asarray(a), p_slots,
                                                  axis=0), slot_params)
        rpos_rows = jnp.take(ring_pos, p_slots, axis=0)
        ids_f, lps_f, new_keys, new_mu = sampling.sample(
            logits, sp_rows,
            jnp.take(ring, p_slots, axis=0), rpos_rows,
            jnp.take(bias, p_slots, axis=0),
            jnp.take(keys, p_slots, axis=0),
            jnp.take(mu, p_slots, axis=0))
        keys = keys.at[p_slots].set(new_keys)
        mu = mu.at[p_slots].set(new_mu)
        lengths = lengths.at[p_slots].set(p_start + p_seq)
        tokens = tokens.at[p_slots].set(ids_f)
        # the sampled first token enters the penalty ring (idempotent form)
        ring = ring.at[p_slots, rpos_rows % sampling.RING_N].set(ids_f)
        ring_pos = ring_pos.at[p_slots].set(rpos_rows + 1)
        active = jnp.asarray(active).at[p_slots].set(True)

        # fused bursts always run the full sampler (one compiled variant
        # per (bucket, B); a flags dimension would double the precompile
        # set for a small sampler saving)
        step = self._make_scan_step(params, slot_params, bias, active,
                                    (True, True, True), pos_offset)
        carry = (tokens, ck, cv, lengths, ring, ring_pos, keys, mu)
        carry, (ids_all, lps_all, _route) = jax.lax.scan(step, carry, None,
                                                         length=n_steps)
        tokens, ck, cv, lengths, ring, ring_pos, keys, mu = carry
        S = self.ecfg.num_slots
        first_ids = jnp.zeros((S,), jnp.float32).at[p_slots].set(
            ids_f.astype(jnp.float32))
        first_lps = jnp.zeros((S,), jnp.float32).at[p_slots].set(lps_f)
        pack = jnp.concatenate(
            [ids_all.astype(jnp.float32), lps_all, mu[None, :],
             first_ids[None, :], first_lps[None, :]], axis=0)
        return pack, ck, cv, keys, self._pin_chain(
            tokens, lengths, ring, ring_pos, mu)

    def _get_fused_fn(self, bucket: int, batch: int):
        key = ("fused", bucket, batch)
        fn = self._burst_fns.get(key)
        if fn is None:
            fn = self._program(
                "prefill_fused", (bucket, batch),
                f"jnp:causal + {self._decode_attn()}",
                lambda *a: self._fused_body(
                    *a, n_steps=self.ecfg.decode_burst),
                donate_argnums=(2, 3, 8))
            self._burst_fns[key] = fn
        return fn

    def _prefill_final_body(self, params, tokens, seq_len, ck, cv, slot, start_pos,
                            ring, ring_pos, bias, keys, slot_params, mu,
                            continued: bool, mm_pos=None, mm_vec=None,
                            positions=None):
        """Final chunk for a BATCH of B prompts: write KV, sample each one's
        first output token. slot may contain duplicate entries (batch
        padding repeats the last prompt; duplicate KV writes and key
        scatters are idempotent — same inputs, last write wins)."""
        logits, ck, cv = self.family.prefill(
            params, self.cfg, tokens, seq_len, ck, cv, slot, start_pos,
            continued=continued, mm_pos=mm_pos, mm_vec=mm_vec,
            positions=positions)
        slot_params = sampling.unpack_slot_params(slot_params)
        sp_rows = jax.tree.map(lambda a: jnp.take(jnp.asarray(a), slot, axis=0),
                               slot_params)
        bias_rows = jnp.take(bias, slot, axis=0)
        key_rows = jnp.take(keys, slot, axis=0)
        ring_rows = jnp.take(jnp.asarray(ring), slot, axis=0)
        rpos_rows = jnp.take(jnp.asarray(ring_pos), slot, axis=0)
        mu_rows = jnp.take(jnp.asarray(mu), slot, axis=0)
        ids, logprobs, new_keys, new_mu = sampling.sample(
            logits, sp_rows, ring_rows, rpos_rows, bias_rows, key_rows, mu_rows)
        keys = keys.at[slot].set(new_keys)
        mu = jnp.asarray(mu).at[slot].set(new_mu)
        return ids, logprobs, ck, cv, keys, mu

    def _packed_prefill_body(self, params, tokens, positions, seg_of,
                             seg_slots, seg_start, seg_off, seg_len,
                             final_mask, ck, cv, ring, ring_pos, bias, keys,
                             slot_params, mu, continued: bool):
        """RAGGED PACKED PREFILL step (one compiled program per
        (total-token bucket, continued?)): every segment's KV rows are
        written through its own slot's page table, FINAL segments (their
        slot's whole remaining prompt fits this pack) sample their first
        output token, non-final segments only write KV — the
        generalization of the fused final-prefill groups to arbitrary
        fresh/continued mixes. Pad segments carry the slot sentinel S,
        so their state writes DROP and their RNG is never consumed; a
        real non-final segment's gated write puts its OWN old value
        back (slots are unique per pack, so the scatter stays
        well-defined)."""
        logits, ck, cv, route = self._family_step(
            self.family.ragged_prefill,
            params, self.cfg, tokens, positions, seg_of, seg_slots,
            seg_start, seg_off, seg_len, ck, cv, continued=continued,
            comm_overlap=self._comm_overlap)
        slot_params = sampling.unpack_slot_params(slot_params)
        sp_rows = jax.tree.map(
            lambda a: jnp.take(jnp.asarray(a), seg_slots, axis=0),
            slot_params)
        ring_rows = jnp.take(jnp.asarray(ring), seg_slots, axis=0)
        rpos_rows = jnp.take(jnp.asarray(ring_pos), seg_slots, axis=0)
        bias_rows = jnp.take(bias, seg_slots, axis=0)
        key_rows = jnp.take(keys, seg_slots, axis=0)
        mu_rows = jnp.take(jnp.asarray(mu), seg_slots, axis=0)
        ids, logprobs, new_keys, new_mu = sampling.sample(
            logits, sp_rows, ring_rows, rpos_rows, bias_rows, key_rows,
            mu_rows, active=final_mask)
        keys = keys.at[seg_slots].set(
            jnp.where(final_mask[:, None], new_keys, key_rows),
            mode="drop")
        mu = jnp.asarray(mu).at[seg_slots].set(
            jnp.where(final_mask, new_mu, mu_rows), mode="drop")
        if route is not None:
            # the pack's routing rides home behind the segments' logprobs
            logprobs = jnp.concatenate([logprobs, route])
        return ids, logprobs, ck, cv, keys, mu

    def _get_packed_fn(self, bucket: int, continued: bool):
        key = ("packed", bucket, continued)
        fn = self._final_fns.get(key)
        if fn is None:
            fn = self._program(
                "prefill_pack", (bucket, continued),
                self._ragged_attn(bucket, continued),
                lambda *a: self._packed_prefill_body(*a,
                                                     continued=continued),
                donate_argnums=(9, 10, 14))
            self._final_fns[key] = fn
        return fn

    def _split_head_body(self, params, tokens, ck, cv, lengths, ring,
                         ring_pos, bias, keys, slot_params, active, mu,
                         ov_pack, p_tokens, p_positions, seg_of, seg_slots,
                         seg_start, seg_off, seg_len, final_mask,
                         continued: bool):
        """EARLY-EMIT admission, prefill head: compose overrides,
        ragged-prefill every segment (fresh or continued), sample the
        FINAL segments' first tokens, fold them into the chain state,
        and return the per-segment first tokens as their own device
        outputs. Pad / non-final segments are gated exactly as in
        _packed_prefill_body (sentinel slots drop, finals-only state
        writes). The engine dispatches a plain decode burst chained off
        the returned handles (_dispatch_packed_split), so under load one
        tick ingests prompts AND decodes; the sync worker materializes
        THIS half first, so first tokens reach the stream without
        waiting for the burst's compute."""
        sp = sampling.unpack_slot_params(slot_params)
        tokens, lengths, ring, ring_pos, mu, _pos_offset = \
            self._compose_overrides(tokens, lengths, ring, ring_pos, mu,
                                    ov_pack)

        logits, ck, cv, route = self._family_step(
            self.family.ragged_prefill,
            params, self.cfg, p_tokens, p_positions, seg_of, seg_slots,
            seg_start, seg_off, seg_len, ck, cv, continued=continued,
            comm_overlap=self._comm_overlap)
        ring_rows = jnp.take(ring, seg_slots, axis=0)
        rpos_rows = jnp.take(ring_pos, seg_slots, axis=0)
        ids_f, lps_f, new_keys, new_mu = sampling.sample(
            logits,
            jax.tree.map(lambda a: jnp.take(jnp.asarray(a), seg_slots,
                                            axis=0), sp),
            ring_rows, rpos_rows,
            jnp.take(bias, seg_slots, axis=0),
            jnp.take(keys, seg_slots, axis=0),
            jnp.take(mu, seg_slots, axis=0), active=final_mask)
        gate = final_mask
        keys = keys.at[seg_slots].set(
            jnp.where(gate[:, None], new_keys,
                      jnp.take(keys, seg_slots, axis=0)), mode="drop")
        mu = mu.at[seg_slots].set(
            jnp.where(gate, new_mu, jnp.take(mu, seg_slots, axis=0)),
            mode="drop")
        lengths = lengths.at[seg_slots].set(
            jnp.where(gate, seg_start + seg_len,
                      jnp.take(lengths, seg_slots, axis=0)), mode="drop")
        tokens = tokens.at[seg_slots].set(
            jnp.where(gate, ids_f, jnp.take(tokens, seg_slots, axis=0)),
            mode="drop")
        rcol = rpos_rows % sampling.RING_N
        ring = ring.at[seg_slots, rcol].set(
            jnp.where(gate, ids_f, ring[seg_slots, rcol]), mode="drop")
        ring_pos = ring_pos.at[seg_slots].set(
            jnp.where(gate, rpos_rows + 1, rpos_rows), mode="drop")
        if route is not None:
            lps_f = jnp.concatenate([lps_f, route])
        return (ids_f, lps_f, ck, cv, keys,
                self._pin_chain(tokens, lengths, ring, ring_pos, mu))

    def _get_split_head_fn(self, bucket: int, continued: bool):
        key = ("packed_head", bucket, continued)
        fn = self._final_fns.get(key)
        if fn is None:
            fn = self._program(
                "prefill_pack_head", (bucket, continued),
                self._ragged_attn(bucket, continued),
                lambda *a: self._split_head_body(*a, continued=continued),
                donate_argnums=(2, 3, 8))
            self._final_fns[key] = fn
        return fn

    def _get_draft_packed_fn(self, bucket: int):
        """Draft-model ragged prompt ingestion (open PR-4 follow-up:
        spec slots are packed citizens now). Same ragged program as the
        target's, minus sampling — the draft cache embeds its own layout
        (paged since ISSUE 13, riding the main page table; contiguous on
        the fallbacks), so scatter_ragged branches to the right path by
        itself."""
        key = ("draft_packed", bucket)
        fn = self._chunk_fns.get(key)
        if fn is None:
            fn = self._program(
                "draft_prefill_pack", bucket,
                llama.ragged_attn_impl(self.draft_cfg, self.dck, bucket,
                                       True),
                lambda p, t, pos, so, ss, st, off, ln, ck, cv:
                    llama.ragged_prefill(
                        p, self.draft_cfg, t, pos, so, ss, st, off, ln,
                        ck, cv, continued=True)[1:],
                donate_argnums=(8, 9))
            self._chunk_fns[key] = fn
        return fn

    def _get_burst_fn(self, n_steps: int, flags: tuple = (True, True, True)):
        key = (n_steps, flags)
        fn = self._burst_fns.get(key)
        if fn is None:
            # donate the cache + keys; chain inputs stay undonated (they are
            # tiny, and mirror-fed dispatches pass host numpy for them)
            fn = self._program(
                "decode_burst", key, self._decode_attn(),
                lambda *a: self._decode_burst_body(*a, n_steps=n_steps,
                                                   flags=flags),
                donate_argnums=(2, 3, 8))
            self._burst_fns[key] = fn
        return fn

    def _get_chunk_fn(self, bucket: int):
        fn = self._chunk_fns.get(bucket)
        if fn is None:
            fn = self._program(
                "prefill_chunk", bucket, "jnp:gather_mixed",
                self._prefill_chunk_body, donate_argnums=(3, 4))
            self._chunk_fns[bucket] = fn
        return fn

    def _get_draft_chunk_fn(self, bucket: int):
        """Draft-model prompt ingestion (the draft has its OWN config —
        the target-cfg chunk body would mis-shape or mis-parameterize
        it). The draft cache embeds its layout, so the same body serves
        the paged draft cache (ISSUE 13) and the contiguous fallbacks."""
        key = ("draft", bucket)
        fn = self._chunk_fns.get(key)
        if fn is None:
            fn = self._program(
                "draft_prefill_chunk", bucket, "jnp:gather_mixed",
                lambda p, t, s, ck, cv, sl, st: llama.prefill(
                    p, self.draft_cfg, t, s, ck, cv, sl, st,
                    continued=True)[1:],
                donate_argnums=(3, 4))
            self._chunk_fns[key] = fn
        return fn

    def _get_final_fn(self, bucket: int, batch: int, continued: bool):
        key = (bucket, batch, continued)
        fn = self._final_fns.get(key)
        if fn is None:
            fn = self._program(
                "prefill_final", key,
                "jnp:gather_mixed" if continued else "jnp:causal",
                lambda *a: self._prefill_final_body(
                    *a, continued=continued),
                donate_argnums=(3, 4, 10))
            self._final_fns[key] = fn
        return fn

    # self-extend prefill variants (B=1, explicit grouped positions;
    # lazily compiled — ga is off by default)

    def _get_ga_chunk_fn(self, bucket: int):
        key = ("ga", bucket)
        fn = self._chunk_fns.get(key)
        if fn is None:
            fn = self._program(
                "prefill_chunk_ga", bucket, "jnp:gather_mixed",
                lambda p, t, sl, ck, cv, slo, st, pos: llama.prefill(
                    p, self.cfg, t, sl, ck, cv, slo, st, continued=True,
                    positions=pos)[1:],
                donate_argnums=(3, 4))
            self._chunk_fns[key] = fn
        return fn

    def _get_ga_final_fn(self, bucket: int, continued: bool):
        key = ("ga_final", bucket, continued)
        fn = self._final_fns.get(key)
        if fn is None:
            fn = self._program(
                "prefill_final_ga", (bucket, continued), "jnp:gather_mixed",
                lambda *a: self._prefill_final_body(
                    *a[:13], continued=continued, positions=a[13]),
                donate_argnums=(3, 4, 10))
            self._final_fns[key] = fn
        return fn

    def _get_ga_rotate_fn(self):
        fn = self._fork_fns.get("ga_rotate")
        if fn is None:
            fn = self._program(
                "kv_ga_rotate", None, "none",
                lambda ck, slot, deltas: llama.shift_cache_positions(
                    ck, self.cfg, slot, deltas),
                donate_argnums=(0,))
            self._fork_fns["ga_rotate"] = fn
        return fn

    # multimodal prefill variants (B=1, lazily compiled on first vision
    # request; keyed additionally on the image-embedding bucket P)

    def _get_mm_chunk_fn(self, bucket: int, pbucket: int):
        key = ("mm", bucket, pbucket)
        fn = self._chunk_fns.get(key)
        if fn is None:
            fn = self._program(
                "prefill_chunk_mm", (bucket, pbucket), "jnp:gather_mixed",
                self._prefill_chunk_body, donate_argnums=(3, 4))
            self._chunk_fns[key] = fn
        return fn

    def _get_mm_final_fn(self, bucket: int, pbucket: int, continued: bool):
        key = ("mm", bucket, pbucket, continued)
        fn = self._final_fns.get(key)
        if fn is None:
            fn = self._program(
                "prefill_final_mm", (bucket, pbucket, continued),
                "jnp:gather_mixed" if continued else "jnp:causal",
                lambda *a: self._prefill_final_body(*a[:13], continued=continued,
                                                    mm_pos=a[13], mm_vec=a[14]),
                donate_argnums=(3, 4, 10))
            self._final_fns[key] = fn
        return fn

    # ---------- public API ----------

    def precompile(self):
        """Compile + execute every jitted variant the serving loop can hit
        (burst sizes, prefill buckets x fresh/continued) BEFORE taking
        traffic. A cold compile mid-wave stalls every active request
        for as long as it takes (seconds per program at 8B width;
        PERF.md has the measured cold ladder).

        Bursts run with all slots inactive — a state-preserving no-op.
        Prefill warmups write one garbage row into (free) slot 0's cache;
        admission reseeds all per-slot state, so this is invisible to
        traffic. Mirrors the reference's LoadToMemory warmup
        (core/startup/startup.go:148-176); pairs with the persistent
        compilation cache (utils/jaxtools.py) so restarts compile fast.

        ISSUE 8: the body runs with this engine's CompileTracker bound
        to the calling thread (precompile runs on the loader/caller
        thread, not the engine loop), and the END of precompile marks
        the warm boundary — incidental warmup compiles (helper fills,
        first-touch jnp ops) land before the mark, and any compile
        observed after it is a compile storm."""
        with sysobs.activated(self._cobs):
            self._precompile_impl()
        self._cobs.mark_warm()
        for rec in self._programs.values():
            rec["dispatches"] = 0    # count serving, not warm-up

    def _precompile_impl(self):
        k = 1
        ks = []
        while k <= self.ecfg.decode_burst:
            ks.append(k)
            k *= 2
        S = self.ecfg.num_slots
        no_ov = self._pack_ov(np.zeros((S,), np.bool_))
        spp = sampling.pack_slot_params(self.slot_params)
        # chain-fed programs warm with the chain as serving feeds it
        c_tok, c_len, c_ring, c_rpos, c_mu = self._host_chain()
        for k in ks:
            for flags in ((False, False, False), (True, True, True)):
                fn = self._get_burst_fn(k, flags)
                _, self.ck, self.cv, self.rng_keys, _ = fn(
                    self.params, c_tok, self.ck, self.cv, c_len,
                    c_ring, c_rpos, self.bias, self.rng_keys,
                    spp, self.active_dev, c_mu, no_ov)
        if self._spec_mode != "off" and self.ecfg.ga_n <= 1:
            # fused spec-tick ladder (ISSUE 13): same pow2 discipline as
            # the burst ladder, capped exactly like _plan_spec so no spec
            # round-count ever compiles mid-serving. The warmup mask is
            # all-inactive: every KV write drops.
            if self._spec_mode == "model":
                self._ensure_draft_cache()
                self._commit_ptab()
            no_spec = np.zeros((S,), np.bool_)
            r = 1
            rs = []
            while r <= max(1, self.ecfg.decode_burst
                           // (self.ecfg.n_draft + 1)):
                rs.append(r)
                r *= 2
            for r in rs:
                for flags in ((False, False, False), (True, True, True)):
                    fn = self._get_spec_tick_fn(r, flags)
                    if self._spec_mode == "model":
                        (_, self.ck, self.cv, self.rng_keys, _,
                         self.dck, self.dcv) = fn(
                            self.params, c_tok, self.ck,
                            self.cv, c_len, c_ring,
                            c_rpos, self.bias, self.rng_keys,
                            spp, self.active_dev, c_mu, no_ov,
                            no_spec, self.draft_params, self.dck,
                            self.dcv)
                    else:
                        _, self.ck, self.cv, self.rng_keys, _ = fn(
                            self.params, c_tok, self.ck,
                            self.cv, c_len, c_ring,
                            c_rpos, self.bias, self.rng_keys,
                            spp, self.active_dev, c_mu, no_ov,
                            no_spec)
        for bucket in (self._buckets if self._per_slot_prefill else ()):
            one = np.ones((1,), np.int32)
            zero = np.zeros((1,), np.int32)
            tokens = np.zeros((1, bucket), np.int32)
            if bucket == self._chunk:
                # non-final chunks always use the full chunk bucket
                self.ck, self.cv = self._get_chunk_fn(bucket)(
                    self.params, tokens, one, self.ck, self.cv, zero, zero)
            finals = [(1, False), (1, True)]
            fb = 2
            while fb <= self._final_pad:
                finals.append((fb, False))
                fb *= 2
            for batch, continued in finals:
                if batch == 1:
                    tb, sb = tokens, one
                    slotb = startb = zero
                else:
                    tb = np.zeros((batch, bucket), np.int32)
                    sb = np.ones((batch,), np.int32)
                    slotb = startb = np.zeros((batch,), np.int32)
                fn = self._get_final_fn(bucket, batch, continued)
                _, _, self.ck, self.cv, self.rng_keys, _ = fn(
                    self.params, tb, sb, self.ck, self.cv, slotb, startb,
                    self.ring, self.ring_pos, self.bias, self.rng_keys,
                    spp, self.mu)
            # fused admission variants (prefill+first-token+burst)
            Bs = [1]
            fb = 2
            while fb <= self._final_pad:
                Bs.append(fb)
                fb *= 2
            for B in Bs:
                fn = self._get_fused_fn(bucket, B)
                _, self.ck, self.cv, self.rng_keys, _ = fn(
                    self.params, c_tok, self.ck, self.cv,
                    c_len, c_ring, c_rpos, self.bias,
                    self.rng_keys, spp, self.active_dev,
                    c_mu, no_ov,
                    np.zeros((B, bucket), np.int32), np.ones((B,), np.int32),
                    np.zeros((B,), np.int32), np.zeros((B,), np.int32))
        if self._packed:
            # ragged packed prefill variants: one program per
            # (total-token bucket, continued?). The warmup pack is ALL
            # PADS (sentinel segments/positions/slots), so it writes no
            # KV rows and consumes no slot state — invisible to traffic.
            S_ = self.ecfg.num_slots
            C_ = self.ecfg.max_context
            sent = np.full((S_,), S_, np.int32)
            zs = np.zeros((S_,), np.int32)
            nofinal = np.zeros((S_,), np.bool_)
            for bucket in self._pack_buckets:
                for continued in (False, True):
                    p_args, p_meta = self._place_pack(
                        [np.zeros((bucket,), np.int32),
                         np.full((bucket,), C_, np.int32),
                         np.full((bucket,), S_, np.int32)],
                        [sent, zs, zs, zs, nofinal])
                    pack_args = (*p_args, *p_meta)
                    fn = self._get_packed_fn(bucket, continued)
                    _, _, self.ck, self.cv, self.rng_keys, _ = fn(
                        self.params, *pack_args,
                        self.ck, self.cv, self.ring, self.ring_pos,
                        self.bias, self.rng_keys, spp, self.mu)
                    # chain outputs are DISCARDED: the head donates
                    # only ck/cv/keys, and the engine's host-side
                    # tokens/lengths/ring/mu arrays must stay numpy
                    hfn = self._get_split_head_fn(bucket, continued)
                    _, _, self.ck, self.cv, self.rng_keys, _ = hfn(
                        self.params, c_tok, self.ck, self.cv,
                        c_len, c_ring, c_rpos, self.bias,
                        self.rng_keys, spp, self.active_dev, c_mu,
                        no_ov, *pack_args)
        if self._paged:
            # page-table commit (two op-by-op slices of the stacked
            # upload) and the copy-on-write page clone: both first run
            # at an admission otherwise. Page 0 cloned onto itself is a
            # no-op.
            self._pool.dirty = True
            self._commit_ptab()
            zero = np.int32(0)
            self.ck, self.cv = self._get_page_clone_fn()(
                self.ck, self.cv, zero, zero)
            if self.dck is not None:
                self.dck, self.dcv = self._get_draft_clone_fn()(
                    self.dck, self.dcv, zero, zero)
        if self._hstore is not None:
            # host-tier transfer programs: the first eviction/restore
            # must not pay a cold compile mid-serving. Gather reads page
            # 0 (harmless); the scatter warm-up writes nothing (all
            # sentinel ids drop).
            B = 1
            while B <= 16:
                idx_g = np.zeros((B,), np.int32)
                idx_s = np.full((B,), self._pool.num_pages, np.int32)
                rows = self._get_offload_gather_fn(B)(self.ck, self.cv,
                                                      idx_g)
                zeros = jax.tree.map(
                    lambda a: np.zeros(a.shape, a.dtype),
                    jax.tree.map(np.asarray, rows[0]))
                self.ck, self.cv = self._get_restore_scatter_fn(B)(
                    self.ck, self.cv, idx_s, zeros, zeros)
                if self.dck is not None and self._paged:
                    # draft-cache shapes re-specialize the same jitted
                    # gather/scatter callables (ISSUE 13): warm them too
                    drows = self._get_offload_gather_fn(B)(
                        self.dck, self.dcv, idx_g)
                    dzeros = jax.tree.map(
                        lambda a: np.zeros(a.shape, a.dtype),
                        jax.tree.map(np.asarray, drows[0]))
                    self.dck, self.dcv = self._get_restore_scatter_fn(B)(
                        self.dck, self.dcv, idx_s, dzeros, dzeros)
                B *= 2
        # admission-path op-level helpers: seed_slot_key builds a PRNGKey
        # (broadcast + squeeze) and scatters it into the key matrix —
        # three tiny implicit jits that would otherwise land on the FIRST
        # real admission and read as false compile storms (ISSUE 8)
        self.rng_keys = sampling.seed_slot_key(
            self.rng_keys, 0, sampling.SamplingParamsHost(),
            fallback_seed=0)
        jax.block_until_ready(self.ck)

    def start(self, precompile: bool = False):
        if self._paged and self._pool.oversubscription > 1.5:
            # sizing hint (ROADMAP follow-up): an operator who shrank
            # kv_pool_pages past 1.5x logical demand should know what
            # admission now leans on — one line, at start, not per event
            import logging as _logging

            _logging.getLogger(__name__).info(
                "kv pool oversubscription %.2fx (%d pages for %d logical):"
                " admission relies on %s under full load; watch "
                "localai_kv_pool_pages{state=\"free\"} and grow "
                "kv_pool_pages if admissions fail",
                self._pool.oversubscription, self._pool.num_pages,
                self.ecfg.num_slots * self._pool.max_pages,
                "prefix-cache eviction + host-RAM offload"
                if self._hstore is not None else "prefix-cache eviction")
        if precompile:
            self.precompile()
        self._thread = threading.Thread(target=self._run, name="engine-loop", daemon=True)
        self._thread.start()

    def shutdown(self):
        self._stop = True
        self._wake.set()
        if self._thread:
            self._thread.join(timeout=10)
        self._sync_q.put(None)
        if (self._hstore is not None and self.ecfg.kv_host_store_path
                and self._hstore_owned):
            # graceful-shutdown persistence: let the worker drain any
            # in-flight offload gathers into the store first, then
            # serialize it for the next engine of this model. Pool
            # replicas never save — the POOL persists the shared store
            # once (ISSUE 14), not once per replica.
            self._sync_thread.join(timeout=30)
            self._hstore.save(self.ecfg.kv_host_store_path)
        if (self._kv_audit is not None and self.num_active == 0
                and self._queue.qsize() == 0):
            # post-drain leak freedom (ISSUE 15): a drained engine must
            # balance to zero — evict the retention tier (dropping its
            # holds), then prove all pages free, all holds gone, and the
            # ledger agreeing. Only meaningful when nothing was cut off
            # mid-flight; strict mode raises out of shutdown by design.
            from localai_tpu.services.kv_audit import KVAuditError

            try:
                for i, s in enumerate(self.slots):
                    if s is None and self._pool.owned[i]:
                        # freed-slot prefix retention is legal live state;
                        # drop it so the drained pool balances to zero
                        self._pool.release(i, 0)
                        self._cache_tokens[i] = []
                if self._pcache is not None:
                    self._pcache.evict(self._pool, self._pool.num_pages)
                self._kv_audit_tick(drained=True)
            except KVAuditError:
                raise
            except Exception:
                __import__("logging").getLogger(__name__).exception(
                    "post-drain kv audit failed")
        if self._bus is not None:
            self._bus.close()
        # close every consumer: queued requests and still-active slots
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            req.out.put(StreamEvent(token_id=-1, text="", logprob=0.0,
                                    finish_reason="stop", error="engine shut down"))
            req.out.put(None)
        for i, s in enumerate(self.slots):
            if s is not None:
                self.slots[i] = None
                ev = StreamEvent(token_id=-1, text="", logprob=0.0,
                                 finish_reason="stop", error="engine shut down")
                # lands after any still-queued tokens for the stream
                self._emitter.push_final(i, s, [ev, None])
        self._emitter.stop(timeout=5.0)

    def _reset_device_state(self):
        if self._bus is not None:
            self._bus.send("reset")
        S = self.ecfg.num_slots
        V = self.cfg.vocab_size
        if self._paged:
            from localai_tpu.engine.paging import PagePool

            if self._prefetch is not None:
                # staged prefetch pages die with the pool below — drop
                # the bookkeeping (no unref: the fresh pool has no
                # record of them) and count the batch WASTED
                n = len(self._prefetch.drain())
                if n and self._hstore is not None:
                    self._hstore.note_prefetch_wasted(n)
            self._pool = PagePool(S, self.ecfg.max_context,
                                  self._pool.page_size,
                                  self._pool_pages)
            if self._pcache is not None:
                # the pool (and its holds) died with the device state;
                # forget the index, keep the telemetry counters. The
                # HOST tier survives — its numpy copies don't reference
                # the dead pool, so offloaded chains stay restorable.
                self._pcache.clear()
            if self._kv_audit is not None:
                # rebind the fresh pool and zero the ledger's running
                # balances — the reset is itself a ledger event (ISSUE
                # 15); totals and the ring survive for post-mortems
                self._pool.audit = self._kv_audit
                self._kv_audit.ledger.rebase()
        self.ck, self.cv = self.family.init_cache(
            self.cfg, S, self.ecfg.max_context, self.ecfg.cache_dtype,
            **({"page_size": self._pool.page_size,
                "num_pages": self._pool_pages}
               if self._paged else {}))
        self.dck = self.dcv = None   # re-ensured at the next spec admission
        self.ring, self.ring_pos = sampling.make_ring(S)
        self.bias = jnp.zeros((S, V), jnp.float32)
        self.rng_keys = jax.vmap(jax.random.key_data)(
            jax.vmap(jax.random.PRNGKey)(jnp.arange(S, dtype=jnp.uint32))
        )
        self.lengths = np.zeros((S,), np.int32)
        self.cur_tokens = np.zeros((S,), np.int32)
        self.active_dev = np.zeros((S,), np.bool_)
        self.pos_offset = np.zeros((S,), np.int32)
        self._bias_dirty = np.zeros((S,), np.bool_)
        self.slot_params = sampling.make_slot_params(S)
        self.mu = sampling.make_mu(S)
        self._shard_state()
        self._cache_tokens = [[] for _ in range(S)]
        self._prefill_queue = []
        self._chain = None
        self._win_delta.fill(0)   # no chain left to rebase (ISSUE 16)
        self._override = set()
        self._fifo.clear()
        self._fork_waiters = {}
        self._gbias_flush = set()

    def submit(self, req: GenRequest) -> "queue.Queue":
        req.t_submit = time.monotonic()
        req.priority = normalize_priority(req.priority, self._default_prio)
        # admission control (ISSUE 7): shed at the door instead of queuing
        # unboundedly — the caller gets a structured "shed" event on the
        # normal output queue within microseconds, not a growing sojourn.
        # maxq_effective tracks the configured limit until the pool
        # rescales it with replica width (ISSUE 20): a scaled-in pool
        # sheds at the narrower width's limit instead of promising the
        # full fleet's queue depth.
        maxq = self.maxq_effective
        if maxq > 0 and self._queue.qsize() >= maxq:
            # queue-wait-aware shed fairness (ISSUE 10, closes the PR-7
            # follow-up): a full queue sheds the longest-queued request
            # of the lowest class STRICTLY below the newcomer's — a
            # flood of equals still refuses the arrival (the PR-7
            # contract), but background traffic can no longer crowd
            # interactive work out of the queue. The victim gets the
            # same structured shed event / 429 shape it always did.
            victim = None
            if self._sched is not None:
                with self._queue.mutex:
                    queued = [(r.priority, r.t_submit, r)
                              for r in self._queue.queue]
                victim = self._sched.pick_shed_victim(
                    PRIORITY_RANK[req.priority], queued)
                if victim is not None:
                    with self._queue.mutex:
                        try:
                            self._queue.queue.remove(victim)
                        except ValueError:
                            victim = None   # raced with admission
            if victim is None:
                self._shed(req, f"server overloaded: {maxq} requests "
                                f"already queued (max_queued_requests)")
                return req.out
            self._shed(victim,
                       f"displaced by a {req.priority}-priority arrival "
                       f"(queue full at {maxq}; longest-queued "
                       f"{victim.priority} request shed)")
        if self.ecfg.request_timeout_ms > 0:
            req.deadline = req.t_submit + self.ecfg.request_timeout_ms / 1e3
        self._queue.put(req)
        self._wake.set()
        return req.out

    def _retry_after_hint(self) -> float:
        """Crude client back-off from the live queue_depth / slot gauges:
        roughly 'queue drains one request per slot per second', floored
        at 1 s. Precision is not the point — a monotone signal is."""
        return max(1.0, round(
            self._queue.qsize() / max(1, self.ecfg.num_slots), 1))

    def _shed(self, req: GenRequest, reason: str, kind: str = "shed"):
        with self._lc_lock:
            self._lc["requests_shed"] += 1
        EVENTS.emit("shed", rid=req.request_id, reason=reason,
                    queued=self._queue.qsize())
        req.out.put(StreamEvent(
            token_id=-1, text="", logprob=0.0, finish_reason="stop",
            error=reason, error_kind=kind,
            retry_after_s=self._retry_after_hint()))
        req.out.put(None)

    def _timeout_event(self, req: GenRequest) -> StreamEvent:
        with self._lc_lock:
            self._lc["requests_timed_out"] += 1
        EVENTS.emit("timeout", rid=req.request_id,
                    timeout_ms=self.ecfg.request_timeout_ms)
        return StreamEvent(
            token_id=-1, text="", logprob=0.0, finish_reason="stop",
            error=(f"request deadline exceeded "
                   f"({self.ecfg.request_timeout_ms} ms)"),
            error_kind="timeout")

    def cancel(self, request_id: str):
        """Cancel a queued or running request (reference parity:
        TASK_TYPE_CANCEL, utils.hpp:53-56). The slot is released at the
        next step boundary; a None sentinel closes the output queue."""
        self._cancelled.add(request_id)
        self._wake.set()

    def generate(self, req: GenRequest) -> Iterator[StreamEvent]:
        """Synchronous streaming helper."""
        out = self.submit(req)
        while True:
            ev = out.get()
            if ev is None:
                return
            yield ev

    def generate_text(self, req: GenRequest) -> tuple[str, list[StreamEvent]]:
        events = list(self.generate(req))
        return "".join(e.text for e in events), events

    @property
    def num_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def metrics(self) -> dict:
        """Parity with the reference's GetMetrics RPC (grpc-server.cpp:2465)."""
        active = [s for s in self.slots if s is not None]
        tok_s = 0.0
        for s in active:
            dt = time.monotonic() - (s.t_first_token or s.t_start)
            if s.n_decoded and dt > 0:
                tok_s += s.n_decoded / dt
        out = {
            "slots_total": self.ecfg.num_slots,
            "slots_active": len(active),
            "queued": self._queue.qsize(),
            "total_tokens_generated": self._total_tokens,
            "tokens_per_second_active": tok_s,
            "prompt_tokens_reused": self._reused_total,
            "uptime_s": time.monotonic() - self._load_time,
            "replica_id": self.replica_id,
            "engine_replicas": 1,    # EnginePool.metrics() overrides
            # ragged packed prefill (module doc): scheduling mode +
            # per-dispatch packing efficiency (pad_tokens / tokens is
            # the bucket-pad waste the packing removed per-slot)
            "prefill_packed": self._packed,
            "prefill_token_budget": self._pack_budget,
            "packed_prefill": dict(self._pack_stats),
            # decode bursts by the sampler's branch (_sampler_branch)
            "sampler_bursts": dict(self._sampler_bursts),
        }
        # speculative decoding (ISSUE 13): per-round counters + the two
        # derived rates the bench/CI gate on — acceptance (accepted /
        # proposed) and accepted-tokens-per-dispatch (emitted spec
        # tokens, bonus included, per slot-round — the per-dispatch
        # verify unit; 1.0 means speculation is buying nothing, >1.0 is
        # the whole point)
        st = self._spec_stats
        out["spec"] = {
            "mode": self._spec_mode,
            "n_draft": self.ecfg.n_draft,
            **{k: v for k, v in st.items() if k != "by_mode"},
            "acceptance_rate": (st["accepted"] / st["proposed"]
                                if st["proposed"] else 0.0),
            "accept_per_dispatch": (st["tokens"] / st["rounds"]
                                    if st["rounds"] else 0.0),
            # ISSUE 18: the same counters + derived rates split by
            # acceptance mode (greedy accept_greedy vs sampled
            # rejection-sampling) — /metrics labels and /debug/state
            # carry this through verbatim
            "by_mode": {
                m: {**c,
                    "acceptance_rate": (c["accepted"] / c["proposed"]
                                        if c["proposed"] else 0.0),
                    "accept_per_dispatch": (c["tokens"] / c["rounds"]
                                            if c["rounds"] else 0.0)}
                for m, c in st["by_mode"].items()},
        }
        if self._paged:
            out["kv_layout"] = "paged"
            out["kv_page_size"] = self._pool.page_size
            out["kv_pages_total"] = self._pool.num_pages
            out["kv_pages_in_use"] = self._pool.pages_in_use
            out["kv_pages_shared"] = int((self._pool.refs > 1).sum())
            # pool occupancy gauges (ROADMAP: "shrink default
            # kv_pool_pages once oversubscription telemetry exists"):
            # free + retained + active == total; retained is reclaimable
            out["kv_pages_free"] = self._pool.free_pages
            out["kv_pages_retained"] = self._pool.retained_pages
            out["kv_pages_active"] = self._pool.active_pages
            out["kv_pool_oversubscription"] = round(
                self._pool.oversubscription, 4)
            if self._pcache is not None:
                out["prefix_cache"] = self._pcache.stats()
            if self._hstore is not None:
                # host tier: state=offloaded pool gauge + transfer totals
                out["kv_pages_offloaded"] = self._hstore.pages
                out["kv_offload"] = self._hstore.stats()
                fed = self._hstore.federated
                if fed is not None:
                    # peer tier (ISSUE 17) ->
                    # localai_kv_stream_{pages,bytes,fetches,hits,
                    # misses}_total
                    out["kv_stream"] = fed.stats()
            if self.ecfg.disagg != "both":
                out["disagg"] = {"role": self.ecfg.disagg,
                                 "handoffs": self.disagg_handoffs}
            if self._kv_audit is not None:
                # lifecycle auditor (ISSUE 15): checks/violations/leaked
                # pages/ledger events -> localai_kv_audit_*_total
                out["kv_audit"] = self._kv_audit.snapshot()
        else:
            out["kv_layout"] = "contiguous"
        with self._decomp_lock:
            d = list(self._ttft_decomp)
        if d:
            qw, af, pf = (sorted(x[i] for x in d) for i in range(3))
            mid = len(d) // 2
            out["ttft_decomp_p50_ms"] = {
                "queue_wait": round(qw[mid], 1),
                "admit_to_first": round(af[mid], 1),
                "prefill_dispatch": round(pf[mid], 1),
                "n": len(d),
            }
        # latency histograms (re-exposed by /metrics as Prometheus
        # histograms) + span-tracer aggregates incl. the host-vs-device
        # walltime decomposition
        out["histograms"] = {
            name: {"le": list(_HIST_BUCKETS[name]),
                   "counts": list(h[0]),
                   "sum": round(h[1], 6), "count": h[2]}
            for name, h in self._hists.items()}
        out["trace"] = self.tracer.summary()
        # fault-tolerant lifecycle telemetry (ISSUE 7): shed/timeout/stall
        # counters + the effective knobs, re-exposed per model on /metrics
        with self._lc_lock:
            lc = dict(self._lc)
        lc["max_queued_requests"] = self.ecfg.max_queued_requests
        lc["queue_limit_effective"] = self.maxq_effective
        lc["max_queue_wait_ms"] = self.ecfg.max_queue_wait_ms
        lc["request_timeout_ms"] = self.ecfg.request_timeout_ms
        lc["dispatch_stall_ms"] = self.ecfg.dispatch_stall_ms
        out["lifecycle"] = lc
        # effective admission limit -> localai_engine_queue_limit (the
        # pool overrides this with the co-scaled routable sum)
        out["queue_limit"] = self.maxq_effective
        # event-driven emission (ISSUE 9)
        out["emitter"] = {"alive": self._emitter.alive,
                          "queued": self._emitter.qsize(),
                          "emitted": self._emitter.emitted}
        # system observability (ISSUE 8): compile tracking + memory
        # watermarks + goodput/MFU, re-exposed per model on /metrics
        self._sample_watermarks()
        sys_obs = {"compiles": self._cobs.snapshot(),
                   "watermarks": self._wm.snapshot(),
                   "goodput": self._goodput.snapshot(),
                   "weight_bytes": self._weight_bytes}
        if self._paged:
            sys_obs["fragmentation"] = self._pool.fragmentation()
        sys_obs["device_mem"] = self._device_mem
        sys_obs["host_memory"] = sysobs.host_memory()
        out["sysobs"] = sys_obs
        # SLO engine (ISSUE 12): per-class burn rates + violation totals,
        # re-exposed as localai_slo_* gauges; short-window burns > 1 also
        # become rate-limited slo_burn events so the log tells the same
        # story the dashboard does
        if self._slo is not None and self._slo.enabled:
            out["slo"] = self._slo.snapshot()
            for rec in self._slo.burn_events():
                EVENTS.emit("slo_burn", **rec)
        out["flight_recorder"] = self._flight.snapshot()
        # preemptive priority scheduler (ISSUE 10): DRR counters, resume
        # queue depth, per-class queue/active gauges + effective knobs
        if self._sched is not None:
            sch = self._sched.stats()
            sch["preempt"] = True
            sch["max_preemptions"] = self.ecfg.max_preemptions
            # the reserve actually applied (explicit knob, or the
            # preemption-rate autosized value — ISSUE 14 satellite)
            sch["resume_reserve_pages"] = self.resume_reserve_effective
            sch["resume_reserve_auto"] = self._reserve_auto
            sch["preempt_rate_per_min"] = round(self._preempt_rate_ewma, 3)
            queued_by = {c: 0 for c in PRIORITY_CLASSES}
            with self._queue.mutex:
                for req in self._queue.queue:
                    queued_by[normalize_priority(
                        req.priority, self._default_prio)] += 1
            active_by = {c: 0 for c in PRIORITY_CLASSES}
            for s in active:
                active_by[PRIORITY_CLASSES[s.prio]] += 1
            resume_by = {c: 0 for c in PRIORITY_CLASSES}
            for c in self._sched.resume_priorities():
                resume_by[c] += 1
            sch["queued_by_class"] = queued_by
            sch["active_by_class"] = active_by
            sch["resume_by_class"] = resume_by
            out["scheduler"] = sch
        else:
            out["scheduler"] = {"preempt": False}
        # per-histogram exemplars: worst observation since the last pull
        # (consumed — each scrape sees that interval's worst span)
        worst, self._hist_worst = self._hist_worst, {}
        if worst:
            out["hist_exemplars"] = {
                name: {"value": round(v, 6), "trace_id": rid, "ts": ts}
                for name, (v, rid, ts) in worst.items()}
        return out

    def _sample_watermarks(self):
        """Fold current gauges into the high-water marks (engine-loop
        tick + every metrics() pull) and fire a pool_pressure event on
        the free-fraction threshold crossing (hysteresis: one event per
        excursion, cleared when the pool recovers past 2x)."""
        wm = {"queued": self._queue.qsize(), "slots_active": self.num_active,
              "tokens_total": self._total_tokens}
        # device memory: the allocator's own counters for every local
        # device, folded into the high-water marks as the fullest
        # device's. The CPU client has no counters — the analytic
        # weight/KV accounting above is what there is
        self._device_mem = sysobs.device_memory_stats()
        in_use = [d["bytes_in_use"] for d in self._device_mem
                  if "bytes_in_use" in d]
        if in_use:
            wm["device_bytes_in_use"] = max(in_use)
        if self._paged:
            wm["pool_active_pages"] = self._pool.active_pages
            wm["pool_retained_pages"] = self._pool.retained_pages
            wm["pool_pages_in_use"] = self._pool.pages_in_use
            if self._hstore is not None:
                wm["host_offloaded_pages"] = self._hstore.pages
                wm["host_bytes"] = self._hstore.bytes_used
            free_frac = self._pool.free_pages / max(1, self._pool.num_pages)
            if not self._pool_pressure and free_frac < 0.05:
                self._pool_pressure = True
                EVENTS.emit("pool_pressure",
                            free_pages=self._pool.free_pages,
                            total_pages=self._pool.num_pages,
                            retained=self._pool.retained_pages,
                            active=self._pool.active_pages)
            elif self._pool_pressure and free_frac > 0.10:
                self._pool_pressure = False
        self._wm.sample(**wm)
        self._autosize_reserve()

    def _autosize_reserve(self):
        """resume_reserve_pages autosize (ISSUE 14 satellite, the open
        PR-10 follow-up): when the explicit knob is 0, derive an
        effective reserve from observed preemption pressure —
        EWMA(preemptions/min) x EWMA(pages retained per preemption),
        clamped to a quarter of the pool. Rides the 0.5 s watermark
        cadence; engines that never preempt stay at 0 (bit-for-bit
        pre-PR admission)."""
        if not self._paged or self._sched is None:
            return
        now = time.monotonic()
        dt = now - self._t_reserve_sample
        if dt < 0.5:
            return
        self._t_reserve_sample = now
        # instantaneous rate over a sliding 60 s window of marks
        horizon = now - 60.0
        # marks inside a sliding 60 s window = preemptions per minute
        inst = float(sum(1 for t in self._preempt_marks if t >= horizon))
        # EWMA with a ~15 s time constant at the 0.5 s cadence
        a = min(1.0, dt / 15.0)
        self._preempt_rate_ewma = ((1 - a) * self._preempt_rate_ewma
                                   + a * inst)
        if self.ecfg.resume_reserve_pages > 0:
            return    # explicit knob wins; EWMA still tracked for metrics
        cap = max(1, self._pool.num_pages // 4)
        want = self._preempt_rate_ewma * max(1.0, self._preempt_pages_ewma)
        self._reserve_auto = min(cap, int(round(want)))

    @property
    def resume_reserve_effective(self) -> int:
        """The reserve _admit_sched actually applies: the explicit knob
        when set, else the preemption-rate autosized value."""
        if self.ecfg.resume_reserve_pages > 0:
            return self.ecfg.resume_reserve_pages
        return self._reserve_auto

    def note_pool_resize(self, n_old: int, n_new: int):
        """Re-anchor the preemption-EWMA reserve when the pool's replica
        count changes (ISSUE 19 satellite). The EWMA was learned under
        the OLD replica count: a scale-out spreads the same offered load
        over more replicas, roughly halving per-replica preemption
        pressure, but the ~15 s EWMA time constant would keep the stale
        reserve pinned for many seconds — pages held back from admission
        for preemptions that will no longer happen here. Rescale the
        rate by old/new and recompute the auto reserve immediately
        instead of waiting for the EWMA to drift there."""
        if n_old <= 0 or n_new <= 0 or n_old == n_new:
            return
        ratio = float(n_old) / float(n_new)
        self._preempt_rate_ewma *= ratio
        if not self._paged or self._sched is None:
            return
        if self.ecfg.resume_reserve_pages > 0:
            return    # explicit knob wins, nothing derived to fix
        cap = max(1, self._pool.num_pages // 4)
        want = self._preempt_rate_ewma * max(1.0, self._preempt_pages_ewma)
        self._reserve_auto = min(cap, int(round(want)))

    def state_snapshot(self) -> dict:
        """Live engine-state JSON for /debug/state (ISSUE 8): slots,
        queues, pool map summary, warmth, last N compiles — the
        at-a-glance answer to "what is this engine doing right now"."""
        slots = []
        for i, s in enumerate(self.slots):
            if s is None:
                slots.append(None)
                continue
            slots.append({
                "rid": s.req.request_id,
                "prompt_tokens": len(s.req.prompt_ids),
                "committed": int(s.committed),
                "n_decoded": int(s.n_decoded),
                "age_s": round(time.monotonic() - s.t_start, 3)})
        out = {
            "slots": slots,
            "slots_active": self.num_active,
            "queued": self._queue.qsize(),
            "warm": self._cobs.snapshot()["warm"],
            "compiles": self._cobs.snapshot(),
            "last_compiles": self._cobs.last_compiles(),
            "compiles_by_kind": self._cobs.by_kind(),
            # every compile of the PROCESS, whatever thread it ran on
            # (one record for all engines of a pool), and apart those no
            # engine's tracker above could hear
            "compiles_process": sysobs.PROCESS.snapshot(),
            "gc_full": sysobs.GC_FULL.snapshot(),
            # what the runner holds resident: now, when LoadModel
            # returned, and the load span that left the peak
            "host_memory": sysobs.HOST.snapshot(),
            # per-span totals since process start (they survive the
            # ring's wrap) and from when the ring is complete
            "trace": self.tracer.summary(),
            # the last profiler capture (runner.Profile): where it is and
            # the clock anchor that places ring spans on its timeline
            "profile": self.profile_state,
            "watermarks": self._wm.snapshot(),
            "goodput": self._goodput.snapshot(),
            "weight_bytes": self._weight_bytes,
            "family": self._fam_name,
            "capabilities": sorted(self._caps),
            "recurrent_state_bytes": self._state_bytes,
            **({} if self._latent_bytes is None else
               {"latent_cache_bytes": self._latent_bytes}),
            "kv_walk": dict(self._kv_walk),
            "state_walk": dict(self._state_walk),
            **({} if self._moe is None else {"moe": self._moe_snapshot()}),
            **self._device,
            "device_mem": sysobs.device_memory_stats(),
            "attention": self._attention_report(),
        }
        # speculative counters with the ISSUE-18 per-mode split (greedy
        # vs sampled rejection acceptance), mirroring metrics()["spec"]
        st = self._spec_stats
        out["spec"] = {
            "mode": self._spec_mode,
            **{k: v for k, v in st.items() if k != "by_mode"},
            "by_mode": {m: dict(c) for m, c in st["by_mode"].items()},
        }
        if self._slo is not None and self._slo.enabled:
            out["slo"] = self._slo.snapshot()
        out["flight_recorder"] = self._flight.snapshot()
        with self._lc_lock:
            out["lifecycle"] = dict(self._lc)
        if self._paged:
            out["pool"] = {
                "pages_total": self._pool.num_pages,
                "page_size": self._pool.page_size,
                "free": self._pool.free_pages,
                "active": self._pool.active_pages,
                "retained": self._pool.retained_pages,
                "shared": int((self._pool.refs > 1).sum()),
                "oversubscription": round(self._pool.oversubscription, 4),
                "fragmentation": self._pool.fragmentation(),
                "pages_per_slot": [int(n) for n in self._pool.owned],
            }
            if self._hstore is not None:
                out["host_store"] = self._hstore.stats()
        return out

    def trace_events(self) -> dict:
        """The span ring as Chrome trace-event JSON (perfetto-loadable):
        one track per slot + scheduler + engine dispatch tracks."""
        from localai_tpu.services import tracing

        return tracing.chrome_trace(self.tracer)

    # ---------- grammar-constrained decoding ----------

    def _grammar_for(self, text: str):
        """Compile (cached) + lazily build the vocab mask builder.

        Prefers the native C++ runtime (runtime/grammar.cc via
        functions/grammars/native.py) — a cold mask walk over a 32k vocab
        costs hundreds of ms in the python automaton vs ~ms native; the
        python path remains the fallback (and the semantic reference)."""
        from localai_tpu.functions.grammars import native
        from localai_tpu.functions.grammars.automaton import (
            Grammar, TokenMaskBuilder, token_strings)

        use_native = native.get_lib() is not None
        if self._mask_builder is None:
            self._token_strs = token_strings(self.tokenizer)
            builder_cls = (native.NativeMaskBuilder if use_native
                           else TokenMaskBuilder)
            self._mask_builder = builder_cls(
                self._token_strs, self.eos_ids, self.cfg.vocab_size)
        g = self._grammar_cache.get(text)
        if g is None:
            if len(self._grammar_cache) > 64:
                self._grammar_cache.clear()
            cls = native.NativeGrammar if use_native else Grammar
            g = cls.from_text(text)
            self._grammar_cache[text] = g
        return g

    def _advance_grammar(self, slot: int, s: _Slot, token_id: int) -> bool:
        """Advance the slot's grammar by the emitted token. Returns False if
        the token is outside the grammar (the caller rolls the slot back).
        The device bias row is NOT written here — burst processing advances
        several states per slot and only the LAST one's mask matters for
        the next dispatch, so rows are flushed once per processed burst
        (_flush_grammar_bias)."""
        piece = (self._token_strs[token_id]
                 if 0 <= token_id < len(self._token_strs) else None)
        if piece is None:
            return False
        nxt = s.grammar.advance_string(s.gstate, piece)
        if nxt is None:
            return False
        s.gstate = nxt
        penalty = self._mask_builder.penalty_row(s.grammar, nxt)
        if penalty is not s.cur_penalty:  # memoized per state: identity == equality
            s.cur_penalty = penalty
            self._gbias_flush.add(slot)
        return True

    def _flush_grammar_bias(self):
        """Write the pending grammar-mask rows to the device bias — ONE
        batched scatter per processed burst, not one dispatch per slot
        (32 grammared slots × ~1-2 ms per .at[].set halved constrained
        throughput when flushed individually)."""
        slots = [i for i in self._gbias_flush
                 if self.slots[i] is not None
                 and self.slots[i].grammar is not None]
        self._gbias_flush.clear()
        if not slots:
            return
        # pad the batch to a power of two by REPEATING the first slot
        # (duplicate scatter writes are idempotent): each distinct batch
        # size is its own XLA executable, and 20-40s compiles for 30
        # different sizes would stall serving for minutes
        k = 1
        while k < len(slots):
            k *= 2
        padded = slots + [slots[0]] * (k - len(slots))
        rows = np.stack([self.slots[i].bias_base + self.slots[i].cur_penalty
                         for i in padded])
        self.bias = self.bias.at[np.asarray(padded, np.int32)].set(
            jnp.asarray(rows))
        if self._bus is not None:
            from localai_tpu.parallel.lockstep import encode_bias_row

            self._bus.send("bias_rows", slots=list(padded),
                           rows=[encode_bias_row(r) for r in rows])
        for i in slots:
            self._bias_dirty[i] = True

    def _rollback_grammar(self, slot: int, s: _Slot) -> bool:
        """Discard an invalid speculative token: grammar slots ride full
        bursts masked by their LAST-FLUSHED state (one burst stale under
        pipelining), so a mid-burst token can fall outside the grammar.
        Recompute semantics make the rollback free — reset the slot's
        device length to the last valid row; stale rows are rewritten.
        Returns False (the _process_burst signal to skip the slot's
        remaining burst tokens)."""
        s.generated.pop()
        s.n_decoded -= 1
        self._total_tokens -= 1
        self._rollbacks += 1
        # quiescent invariant (r4, verified against a fresh-prefill KV
        # oracle): lengths == cache_len - 1 — the pending token toks[-1]
        # has row cache_len-1, to be (re)written by the next step. r3 set
        # lengths = cache_len here, which re-wrote the pending token's KV
        # one row too far and silently position-shifted every row after a
        # rollback.
        s.committed = min(s.committed, max(s.cache_len - 1, 0))
        self.lengths[slot] = max(s.cache_len - 1, 0)
        toks = self._cache_tokens[slot]
        self.cur_tokens[slot] = toks[-1] if toks else 0
        self.ring, self.ring_pos = sampling.set_slot_ring(
            self.ring, self.ring_pos, slot, toks)
        # ensure the next dispatch carries this state's mask + the
        # corrected mirrors (chain override)
        self._gbias_flush.add(slot)
        self._override.add(slot)
        # every PIPELINED in-flight burst (dispatched before this rollback
        # was known) sampled its tokens conditioned on the discarded one —
        # drop this slot from them wholesale: neither their folds nor
        # their emissions may touch the corrected mirrors
        for b in self._fifo:
            if isinstance(b, _Burst):
                b.skip_slots.add(slot)
        return False

    # ---------- engine loop ----------

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def _pick_slot(self, ids: list) -> tuple:
        """Free slot with the longest cached common prefix (reference:
        grpc-server.cpp:1721-1835). Returns (slot, reusable_len) or (None, 0)."""
        best, best_key = None, None
        for i, s in enumerate(self.slots):
            if s is not None:
                continue
            common = 0
            for a, b in zip(self._cache_tokens[i], ids):
                if a != b:
                    break
                common += 1
            # prefer the longest common prefix; on ties (esp. common == 0)
            # evict the slot with the LEAST cached content so unrelated
            # requests don't destroy another conversation's reusable prefix
            key = (common, -len(self._cache_tokens[i]))
            if best_key is None or key > best_key:
                best, best_key = i, key
        if best is None:
            return None, 0
        # always leave >= 1 token to prefill so we have last-position logits
        return best, min(best_key[0], len(ids) - 1)

    def _run(self):
        """The engine loop (r4): every iteration dispatches first (prefill
        chunks/finals, then up to pipeline_depth decode bursts — all
        async), and only then block-syncs the OLDEST dispatched item,
        which by FIFO execution order is already (nearly) computed. The
        device therefore always has at least one dispatch queued behind
        the one it is executing; host-side syncs, detok, stop-scans and
        queue puts all overlap device compute."""
        import logging

        log = logging.getLogger(__name__)
        # bind this engine's compile tracker to the loop thread: every
        # jit dispatch (and therefore every XLA compile) the serving
        # path triggers happens right here (ISSUE 8)
        sysobs.register_thread(self._cobs)
        t_wm = 0.0
        try:
            self._run_ticks(t_wm)
        except _ReplicaDead:
            # chaos: die like a lost host — the thread just ends, with
            # _stop still False (that asymmetry IS the pool's death
            # signal) and the host mirrors intact for recovery harvest
            log.warning("replica %d: loop killed by replica_die fault",
                        self.replica_id)

    def _run_ticks(self, t_wm: float):
        # Every stretch of the loop is inside one tick_* phase span on
        # track "sched" (PERF.md section 3 lists them), so that an idle
        # gap of the device in a profiler capture names what the host was
        # doing: tick_idle_wait is "nothing to do", every other phase is
        # host work the device may be waiting for. The tick span, their
        # parent, is recorded when the tick did something.
        span = self.tracer.span
        while not self._stop:
            try:
                t_tick = time.monotonic()
                self._tick_prefill_tokens = self._tick_decode_tokens = 0
                if FAULTS.active and FAULTS.take(self._die_fault) is not None:
                    raise _ReplicaDead()
                with span("tick_housekeeping", "sched"):
                    # live migration out (ISSUE 14): eject requested
                    # streams at the tick top — previous tick fully
                    # processed, so the pause point is a burst boundary
                    # like any preempt
                    if self._migrate_req:
                        self._process_migrations()
                    # prefill/decode disaggregation (ISSUE 17): on a
                    # prefill-role engine, slots whose prefill completed
                    # (first token out) retire to the cluster transport
                    # at the same burst boundary migration uses
                    if self._disagg_prefill and \
                            self.disagg_handoff is not None:
                        self._process_disagg()
                    if t_tick - t_wm > 0.5:
                        # watermark fold (ISSUE 8): cheap max() samples
                        # so pool peaks between /metrics scrapes are not
                        # lost
                        t_wm = t_tick
                        self._sample_watermarks()
                        # the one host gauge: here only, not at every
                        # admission's and /metrics pull's fold
                        hm = sysobs.host_memory()
                        self._rss.append((t_tick, hm))
                        self._wm.sample(host_rss_bytes=hm.get("rss_bytes"))
                        if self.kv_checkpoint:
                            # cluster mode (ISSUE 17): stream active
                            # slots' warm chains to the host tier so a
                            # host crash leaves them fetchable by
                            # re-adopting siblings
                            self._checkpoint_active_chains()
                        if self._kv_audit is not None:
                            # online KV invariant audit (ISSUE 15): same
                            # cadence, same thread — the mirrors are
                            # between ticks, so the O(num_pages) scans
                            # see a consistent pool
                            self._kv_audit_tick()
                    # emitter-detected stop finishes land as notes
                    # (ISSUE 9); apply before admission so the freed
                    # slots are admittable this very tick
                    self._apply_emitter_notes()
                # pick up whatever completed while the previous tick was
                # packing/dispatching BEFORE spending this tick's host
                # time — ready bursts otherwise pay a full tick of
                # finish-detect each (ISSUE 9); never blocks
                with span("tick_drain", "sched"):
                    drained0 = self._drain_fifo(block=False)
                with span("tick_admit", "sched"):
                    admitted = self._admit()
                if self._prefetch is not None:
                    # prefetch-ahead for the requests STILL queued after
                    # this tick's admissions (ISSUE 16): their host-tier
                    # restores overlap the decode work dispatched below
                    with span("tick_prefetch", "sched"):
                        self._prefetch_tick()
                with span("tick_prefill_pack", "sched"):
                    prefilled = self._prefill_step()
                # prompt packing is the longest host stretch of the tick;
                # collect anything that completed under it (no-op when
                # nothing is ready)
                with span("tick_drain", "sched"):
                    drained0 |= self._drain_fifo(block=False)
                with span("tick_dispatch_decode", "sched"):
                    dispatched = self._dispatch_decode()
                # the batch this tick's decode steps run with (a slot that
                # finishes in the drain below was still part of it)
                slots_active = self.num_active
                with span("tick_drain", "sched"):
                    drained = self._drain_fifo(
                        can_feed=dispatched or prefilled) or drained0
                if self.tracer.enabled and (admitted or prefilled
                                            or dispatched or drained):
                    self.tracer.record(
                        "tick", "sched", t_tick, time.monotonic(),
                        args={"admitted": int(admitted),
                              "prefilled": int(prefilled),
                              "dispatched": int(dispatched),
                              "drained": int(drained),
                              "slots_active": slots_active,
                              "prefill_tokens": self._tick_prefill_tokens,
                              "decode_tokens": self._tick_decode_tokens,
                              "queued": self._queue.qsize()})
                if not (admitted or prefilled or dispatched or drained):
                    # a dispatched item the loop is NOT blocked on (e.g. a
                    # prefill whose worker-side sync wedged) parks in the
                    # FIFO while the loop idles here — the watchdog must
                    # cover that wedge too, not just _wait_ready callers
                    with span("tick_housekeeping", "sched"):
                        self._check_parked_stall()
                        self._check_emitter_wedge()
                    # event-driven idle (ISSUE 9): the sync worker and the
                    # emitter note channel both set _wake, so the fixed
                    # 50 ms poll tick is gone — park until woken, waking
                    # on a watchdog-scaled timeout only to re-run the
                    # stall/wedge checks above
                    with span("tick_idle_wait", "sched",
                              queued=self._queue.qsize(),
                              in_flight=len(self._fifo)):
                        self._wake.wait(timeout=self._idle_wait_s)
                        self._wake.clear()
            except _DispatchStall as st:
                # stall watchdog (ISSUE 7): a narrower failure than the
                # generic handler below — abort ONLY the stalled item's
                # requests, dump the span ring for post-mortem, keep the
                # device state (survivors keep serving).
                self._handle_stall(st.item)
            except Exception as e:  # never let the loop die: fail active requests
                self._recover_step_failure(e)

    def _recover_step_failure(self, e: Exception):
        """Generic step-failure recovery: fail every active request with a
        structured error and reinitialize device state so the engine
        survives instead of erroring forever. Factored out of _run so the
        chaos suite can drive the exact production recovery path against
        a manually-ticked engine."""
        import logging

        log = logging.getLogger(__name__)
        log.exception("engine step failed")
        for i, s in enumerate(self.slots):
            if s is not None:
                ev = StreamEvent(
                    token_id=-1, text="", logprob=0.0,
                    finish_reason="stop", error=f"{type(e).__name__}: {e}",
                )
                # FIFO with any still-queued tokens (ISSUE 9)
                self._emitter.push_final(i, s, [ev, None])
                self._release_slot(i)
        # a failure inside a donated jitted call leaves ck/cv/ring/
        # keys pointing at deleted buffers — reinitialize device state
        # so the engine survives instead of erroring forever
        try:
            self._reset_device_state()
        except Exception:
            log.exception("device state reset failed; engine unusable")
            self._stop = True

    def _admission_ready(self) -> bool:
        """Admit the moment a slot is free: fused admission (prefill +
        first token + burst in one dispatch) makes singleton admissions as
        cheap as batched ones, so holding requests back to form groups
        (r2/r3 did, up to 0.35 s) only idles freed slots. The prefill
        queue itself still batches whatever has accumulated per dispatch."""
        return not self._queue.empty() and self._free_count() > 0

    def _admit(self) -> bool:
        self._reap_expired()
        self._reap_cancelled()
        if self._sched is not None:
            return self._admit_sched()
        if not self._admission_ready():
            return False
        admitted = False
        batch: list[GenRequest] = []
        while not self._queue.empty() and self._free_count() > len(batch):
            try:
                batch.append(self._queue.get_nowait())
            except queue.Empty:
                break
        # identical prompts admitted together prefill ONCE: the first
        # becomes the leader; the rest fork its KV rows on commit
        # (VERDICT r2 #5 — true shared-prefix for n>1)
        leaders: dict = {}
        for req in batch:
            if self._admit_one(req, leaders):
                admitted = True
        return admitted

    def _admit_one(self, req: GenRequest, leaders: dict) -> bool:
        """Admit one popped request (shared by the FIFO and scheduler
        paths): cancellation check, fork-dedup leader/sibling logic, and
        failure containment. Returns True when a slot was started."""
        if req.request_id in self._cancelled:
            self._cancelled.discard(req.request_id)
            req.out.put(None)
            return False
        key = None
        # fork-dedup shares KV rows verbatim; under self-extend those
        # rows are position-compressed state the sibling's own ga
        # bookkeeping would re-compress, and in lockstep mode the fork
        # op is not in the descriptor set — mutually exclusive
        if not req.grammar and req.mm_vectors is None \
                and self.ecfg.ga_n <= 1 and self._bus is None \
                and "prefix_reuse" in self._caps:
            # truncation depends on max_new_tokens; bucket it into the key
            key = (tuple(req.prompt_ids),
                   min(req.max_new_tokens, self.ecfg.max_context // 4))
        try:
            if key is not None and key in leaders:
                lslot, lsnap, lids = leaders[key]
                self._start_fork_sibling(req, lslot, lsnap, lids)
            else:
                slot, ids, snap = self._start_request(req)
                if key is not None and snap.mm_pos is None:
                    leaders[key] = (slot, snap, ids)
            return True
        except Exception as e:
            import logging

            logging.getLogger(__name__).exception("admission failed")
            req.out.put(StreamEvent(
                token_id=-1, text="", logprob=0.0, finish_reason="stop",
                error=f"{type(e).__name__}: {e}",
            ))
            req.out.put(None)
            return False

    def _pop_queued(self, req: GenRequest) -> bool:
        """Remove a specific request from the admission queue (scheduler
        path: ordered pops instead of FIFO gets). False when a reaper or
        shed-displacement raced us to it."""
        with self._queue.mutex:
            try:
                self._queue.queue.remove(req)
                return True
            except ValueError:
                return False

    def _admit_sched(self) -> bool:
        """Priority admission (ISSUE 10): pop queued work in aged-rank
        order (stable FIFO within a class, so single-class traffic
        admits exactly like the FIFO path), merge the resume queue in by
        effective class, hold ``resume_reserve_pages`` back from fresh
        admissions while preempted work waits, and — when the best
        waiting request strictly outranks an active slot and no slot is
        free — preempt the victim and admit into its slot."""
        sched = self._sched
        if self._queue.empty() and sched.resume_depth == 0:
            return False
        admitted = False
        leaders: dict = {}
        reserve = self.resume_reserve_effective
        # hard bound on the work loop: every iteration either admits,
        # preempts (at most num_slots times), or breaks
        guard = 2 * self.ecfg.num_slots + 8
        while guard > 0:
            guard -= 1
            now = time.monotonic()
            with self._queue.mutex:
                entries = [(r.priority, r.t_submit, r)
                           for r in self._queue.queue]
            cand = sched.order_queued(entries) if entries else []
            head = None
            while cand:
                r = cand[0]
                if r.request_id not in self._cancelled:
                    head = r
                    break
                # cancelled while queued: close the stream and move on
                cand.pop(0)
                if self._pop_queued(r):
                    self._cancelled.discard(r.request_id)
                    r.out.put(None)
            res = sched.peek_resume()
            if head is None and res is None:
                break
            head_rank = sched.effective_rank(
                head.priority, now - head.t_submit) if head is not None \
                else len(PRIORITY_CLASSES)
            res_rank = sched.effective_rank(
                res.priority, now - res.t_parked) if res is not None \
                else len(PRIORITY_CLASSES)
            # parked work already paid its queue wait once — on rank
            # ties it resumes before a fresh admission
            use_resume = res is not None and res_rank <= head_rank
            rank = res_rank if use_resume else head_rank
            if self._free_count() == 0:
                victim = self._pick_victim(rank)
                if victim is None:
                    break
                self._preempt_slot(victim, why="priority")
                continue   # the freed slot admits on the next pass
            if use_resume:
                entry = sched.pop_resume()
                try:
                    self._start_resume(entry)
                    admitted = True
                except Exception:
                    import logging

                    logging.getLogger(__name__).exception(
                        "resume admission failed; request re-parked")
                    sched.requeue_front(entry)
                    break
            else:
                if reserve > 0 and self._paged and sched.resume_depth > 0 \
                        and self._pool.free_pages <= reserve:
                    # fresh work would eat the pages a parked resume
                    # needs — only resumes may pass until pressure lifts
                    break
                if not self._pop_queued(head):
                    continue   # raced with a reaper / shed displacement
                if self._admit_one(head, leaders):
                    admitted = True
        return admitted

    def _preempt_eligible(self, slot: int, s: "_Slot") -> bool:
        """Pausable slots only: pause/resume round-trips through token
        re-admission, so anything whose slot state is NOT reconstructible
        from tokens is excluded — grammar automata (mid-generation state),
        multimodal rows (image embeddings, not tokens), prompt-cache
        requests (their save path assumes one continuous tenancy), and
        fork leaders with waiters still attached. Spec slots are
        pausable since ISSUE 13: the paged draft cache offloads/restores
        with the main pages, the n-gram drafter has no slot state, and a
        contiguous-draft slot simply resumes without speculation."""
        return (s.grammar is None and s.mm_pos is None
                and not s.req.prompt_cache_path
                and s.phase in ("prefill", "decode")
                and slot not in self._fork_waiters
                and s.req.request_id not in self._cancelled)

    def _pick_victim(self, incoming_rank: int,
                     decode_only: bool = False) -> Optional[int]:
        """Engine-side victim scan feeding Scheduler.pick_victim: only
        paged layouts can pause (committed pages retain/offload; the
        contiguous fallbacks would forfeit all progress), and only
        eligible slots are offered. ``decode_only`` restricts to
        decode-phase slots — required when called mid-prefill-pack, where
        a prefill-phase victim could be part of the pack being built."""
        if not self._paged or self._sched is None:
            return None
        cands = []
        for i, s in enumerate(self.slots):
            if s is None or not self._preempt_eligible(i, s):
                continue
            if decode_only and s.phase != "decode":
                continue
            cands.append((i, PRIORITY_CLASSES[s.prio], s.t_start,
                          s.preempts))
        return self._sched.pick_victim(incoming_rank, cands)

    def _preempt_slot(self, slot: int, why: str = "priority",
                      park: bool = True):
        """Pause an active slot at a burst boundary and park its request
        for resume (ISSUE 10). With ``park=False`` (live migration,
        ISSUE 14) the ResumeEntry is RETURNED instead of parked — the
        caller hands it to a sibling replica, and this engine's
        preemption counters stay untouched (migration is placement, not
        capacity pressure). Committed pages are RETAINED through the
        prefix cache exactly like a release/context-shift — under
        continued pool pressure they offload host-side through the
        normal reclaim path — so resume is plain re-admission: the
        chained-hash splice (device or host tier) restores the KV, and a
        killed host entry degrades to a full re-prefill of the identical
        token history.
        Invalidation mirrors _context_shift: tokens already emitted are
        kept; tokens still in flight for the slot are dropped from their
        bursts (the resume re-computes from the last kept token)."""
        s = self.slots[slot]
        if s is None:
            return False
        t0 = time.monotonic()
        hist = list(self._cache_tokens[slot])   # prompt + emitted tokens
        committed = min(s.committed, len(hist))
        if self._paged:
            # retention FIRST (slot references still pin the pages), then
            # the whole table returns to the pool for the displacing
            # request — the retained chain survives as cache holds
            if self._pcache is not None:
                self._pcache.insert(self._pool, slot, hist[:committed])
            self._pool.release(slot, 0)
        entry = ResumeEntry(
            req=s.req, ids=hist, priority=s.req.priority,
            generated=list(s.generated), n_decoded=s.n_decoded,
            prompt_len=s.prompt_len, detok=s.detok,
            held_text=s.held_text, t_start=s.t_start,
            t_first_token=s.t_first_token or None,
            t_prefill_ms=s.t_prefill_ms, mu=float(self.mu[slot]),
            preempt_count=s.preempts + (1 if park else 0))
        if park:
            self._sched.park(entry)
            # resume-reserve autosize input (ISSUE 14 satellite): stamp
            # the preemption and fold retained-pages into its EWMA —
            # migrations don't count, they are not capacity pressure
            pg = self._pool.page_size if self._paged else 1
            pages = committed // max(1, pg)
            self._preempt_marks.append(time.monotonic())
            if len(self._preempt_marks) == 1:
                self._preempt_pages_ewma = float(pages)
            else:
                self._preempt_pages_ewma = (
                    0.7 * self._preempt_pages_ewma + 0.3 * pages)
        self.slots[slot] = None
        self.active_dev[slot] = False
        self.lengths[slot] = 0
        # the table is empty now — advertising the old prefix to
        # _pick_slot would promise rows the pool no longer maps
        self._cache_tokens[slot] = []
        try:
            self._prefill_queue.remove(slot)
        except ValueError:
            pass
        # burst boundary: in-flight tokens for this slot are conditioned
        # on state the next tenant overwrites — drop them (same rule as
        # _context_shift / emitter-detected stops)
        for b in self._fifo:
            if isinstance(b, _Burst):
                b.skip_slots.add(slot)
        if park:
            with self._lc_lock:
                self._lc["preemptions"] = self._lc.get("preemptions", 0) + 1
        EVENTS.emit("preempt", rid=s.req.request_id, slot=slot, why=why,
                    priority=s.req.priority, n_decoded=s.n_decoded,
                    retained_rows=committed)
        if self.tracer.enabled:
            self.tracer.record("preempt", f"slot{slot}", t0,
                               time.monotonic(), rid=s.req.request_id,
                               args={"why": why,
                                     "retained_rows": committed})
        return True if park else entry

    def _start_resume(self, entry: "ResumeEntry"):
        """Re-admit a preempted request (ISSUE 10). Admission IS the
        resume path: the full token history (prompt + emitted tokens)
        goes back through _start_request, whose reuse tiers splice the
        retained device chain, restore offloaded pages, or — when the
        host entry was evicted or failed its CRC — fall back to a full
        re-prefill. Either way the continuation is conditioned on the
        identical token history, byte-for-byte what a fresh submission
        of (prompt + emitted tokens) would compute; streaming state
        (detokenizer, held text, counts, timings) carries over so the
        client sees one uninterrupted stream."""
        sched = self._sched
        req = entry.req
        req.prompt_ids = list(entry.ids)
        t0 = time.monotonic()
        slot, ids, s = self._start_request(req, resume=entry)
        sched.resumes += 1
        sched.resume_restore_rows += s.reused
        if s.reused == 0:
            sched.resume_reprefills += 1
        EVENTS.emit("resume", rid=req.request_id, slot=slot,
                    priority=req.priority, reused_rows=s.reused,
                    reprefill_rows=len(ids) - s.reused,
                    parked_ms=round((t0 - entry.t_parked) * 1e3, 1))
        if self.tracer.enabled:
            self.tracer.record("resume", f"slot{slot}", t0,
                               time.monotonic(), rid=req.request_id,
                               args={"reused_rows": s.reused,
                                     # prompt AND produced tokens: all of
                                     # them where a dropped recurrent
                                     # state cannot be restored
                                     "reprefill_rows": len(ids) - s.reused})
        return slot

    # ---- replica-pool surface (ISSUE 14) -------------------------------

    @property
    def loop_alive(self) -> bool:
        """True while the engine loop thread is serving. False after
        shutdown() — or, with _stop still False, after a crash the
        generic recovery could not catch (the replica_die chaos fault):
        that asymmetry is the pool health check's death signal."""
        return self._thread is not None and self._thread.is_alive()

    def request_migration(self, request_id: str, handoff) -> None:
        """Ask the engine loop to eject ``request_id`` at the next tick
        top (a burst boundary, like any preemption). ``handoff(payload)``
        fires on the ENGINE LOOP thread with:
          ("resume", ResumeEntry, mapped_keys)  — was active or parked;
            retained pages force-offloaded to the (shared) host tier and
            mapped under ("migrate", rid) so budget eviction can't race
            the sibling's restore (the pool unmaps after adoption)
          ("fresh", GenRequest, [])             — still queued, nothing
            computed: plain re-submit on the target
          None                                   — unknown/finished, or
            the slot is migration-ineligible (grammar/multimodal/fork
            state does not ride a ResumeEntry)"""
        with self._migrate_lock:
            self._migrate_req[request_id] = handoff
        self._wake.set()

    def adopt_resume(self, entry: "ResumeEntry") -> bool:
        """Adopt a sibling replica's preempted request (migration-in).
        The entry parks in THIS engine's resume queue — without bumping
        its preemption counters — and the normal _admit_sched path
        re-admits it: the chain lookup splices the same pages back from
        the shared host tier, or re-prefills the identical history.
        Thread-safe (list append under the GIL); callable from the pool
        thread. False when this engine has no scheduler (preempt=0)."""
        if self._sched is None:
            return False
        self._sched.adopt(entry)
        self._wake.set()
        return True

    def _process_migrations(self):
        """Engine-loop half of request_migration (tick top)."""
        with self._migrate_lock:
            items = list(self._migrate_req.items())
            self._migrate_req.clear()
        import logging
        log = logging.getLogger(__name__)
        for rid, handoff in items:
            try:
                payload = self._eject_request(rid)
            except Exception:
                log.exception("migration eject failed for %s", rid)
                payload = None
            try:
                handoff(payload)
            except Exception:
                log.exception("migration handoff failed for %s", rid)

    def _eject_request(self, rid: str):
        """Remove ``rid`` from this replica wherever it lives (active
        slot -> pause; queued -> unqueue; parked -> unpark) and return
        the request_migration payload."""
        owner = ("migrate", rid)
        # active slot: PR-10 pause, but hand the entry out instead of
        # parking it (park=False keeps preemption counters honest)
        for i, s in enumerate(self.slots):
            if s is None or s.req.request_id != rid:
                continue
            if self._sched is None or not self._preempt_eligible(i, s):
                return None
            entry = self._preempt_slot(i, why="migrate", park=False)
            if entry is True or not entry:
                return None
            return ("resume", entry, self._offload_chain(entry.ids, owner))
        # still queued: nothing computed yet, plain re-route
        with self._queue.mutex:
            for r in self._queue.queue:
                if r.request_id == rid:
                    self._queue.queue.remove(r)
                    return ("fresh", r, [])
        # parked on this replica's resume queue
        if self._sched is not None:
            entry = self._sched.remove_parked(rid)
            if entry is not None:
                return ("resume", entry,
                        self._offload_chain(entry.ids, owner))
        return None

    def _offload_chain(self, ids, owner=None) -> list:
        """Force-copy the retained device chain for ``ids`` into the
        host tier WITHOUT dropping the device entries (unlike eviction:
        the local copy stays warm; the host copy is what a sibling
        replica restores from). Maps every covered key under ``owner``
        first, so the async put can never lose a budget-eviction race.
        Returns the mapped keys (engine-loop thread only: dispatches a
        device gather)."""
        if self._pcache is None or self._hstore is None:
            return []
        mapped: list = []
        victims: list = []
        for key in self._pcache.chain_keys(ids):
            e = self._pcache._entries.get(key)
            if e is None:
                break
            if owner is not None:
                self._hstore.map_key(key, owner)
                mapped.append(key)
            if not self._hstore.contains(key):
                victims.append((e.key, e.parent, e.depth, e.page))
        if victims:
            self._dispatch_offload(victims)
        return mapped

    # ---- prefill/decode disaggregation (ISSUE 17) ----------------------

    def _process_disagg(self):
        """Engine-loop tick-top on a "prefill"-role engine: retire every
        slot whose prefill has completed (>= 1 decoded token — the
        packed prefill and its first-token emit are done, so TTFT was
        paid HERE) to the cluster transport. The ejection IS the PR-10
        pause primitive with park=False, exactly like live migration:
        the chain force-offloads to the host tier mapped under
        ("disagg", rid) so budget eviction can't race the decode host's
        streamed restore, and the ResumeEntry goes to the registered
        handoff. A handoff that fails re-parks the entry locally — the
        request is never stranded, this engine just decodes it like
        role "both" would."""
        for i, s in enumerate(self.slots):
            if s is None or s.n_decoded < 1 or s.phase != "decode":
                continue
            if getattr(s.req, "_no_disagg", False):
                continue    # router had no decode host: serve locally
            if self._sched is None or not self._preempt_eligible(i, s):
                continue
            rid = s.req.request_id
            entry = self._preempt_slot(i, why="disagg", park=False)
            if entry is True or not entry:
                continue
            keys = self._offload_chain(entry.ids, ("disagg", rid))
            self.disagg_handoffs += 1
            if self._kv_audit is not None:
                self._kv_audit.ledger.record("disagg", rid=rid)
            try:
                self.disagg_handoff(entry, keys)
            except Exception:
                log.exception("disagg handoff failed for %s; decoding "
                              "locally", rid)
                self._sched.adopt(entry)

    def _checkpoint_active_chains(self):
        """Watermark-cadence warm-chain streaming (cluster mode,
        ISSUE 17): retain + force-offload every active slot's committed
        chain so the host tier — and through the wire server, every
        peer — always holds a near-current copy (DejaVu streams KV off
        the accelerator continuously; a crashed host's in-flight work
        then resumes on a sibling from streamed state instead of a full
        re-prefill). Steady-state cost is one pcache.insert dedup and
        one contains() walk per slot — pages already offloaded are
        skipped inside _offload_chain."""
        if self._pcache is None or self._hstore is None or not self._paged:
            return
        for i, s in enumerate(self.slots):
            if s is None or s.win_off > 0:
                continue        # windowed slots checkpoint via demote
            hist = self._cache_tokens[i]
            committed = min(s.committed, len(hist))
            pg = self._pool.page_size
            if committed < pg:
                continue
            self._pcache.insert(self._pool, i, hist[:committed])
            self._offload_chain(hist[:committed])

    def _free_count(self) -> int:
        return sum(1 for s in self.slots if s is None)

    def _reap_cancelled(self):
        if not self._cancelled:
            return
        for i, s in enumerate(self.slots):
            if s is not None and s.req.request_id in self._cancelled:
                self._cancelled.discard(s.req.request_id)
                self._release_slot(i)
                # close the stream AFTER queued tokens drain (ISSUE 9)
                self._emitter.push_final(i, s, [None])
                # a cancelled LEADER must not strand fork-waiting siblings
                self._process_fork_waiters(i)

    def _reap_expired(self):
        """Per-request deadlines + queue-wait shedding (ISSUE 7), on the
        engine thread at admission ticks. Queued casualties are failed
        directly; active ones go through the normal cancel path so the
        slot, its pages, and any fork waiters are released exactly like a
        client disconnect."""
        timeout_on = self.ecfg.request_timeout_ms > 0
        qwait_s = self.ecfg.max_queue_wait_ms / 1e3
        if not timeout_on and qwait_s <= 0:
            return
        now = time.monotonic()
        # queued requests: scan the underlying deque under the queue's own
        # mutex (queue.Queue exposes it precisely for bulk maintenance)
        with self._queue.mutex:
            victims = [r for r in self._queue.queue
                       if (timeout_on and r.deadline and now > r.deadline)
                       or (qwait_s > 0 and now - r.t_submit > qwait_s)]
            for r in victims:
                self._queue.queue.remove(r)
        for r in victims:
            if timeout_on and r.deadline and now > r.deadline:
                r.out.put(self._timeout_event(r))
                r.out.put(None)
            else:
                self._shed(r, f"queued longer than max_queue_wait_ms "
                              f"({self.ecfg.max_queue_wait_ms} ms)")
        if not timeout_on:
            return
        for i, s in enumerate(self.slots):
            if s is not None and s.req.deadline and now > s.req.deadline \
                    and s.req.request_id not in self._cancelled:
                # decoding for a dead client: error event now, then the
                # cancel path releases the slot and closes the stream
                # no trailing None here — the cancel path routes the
                # stream close through the emitter queue itself
                self._emitter.push_final(i, s, [self._timeout_event(s.req)])
                self.cancel(s.req.request_id)

    def _enqueue(self, item, kind: str):
        """Hand a dispatched item to the FIFO (where the loop and the
        stall handler find it) and to the sync worker, named by the
        program kind its pace is kept under."""
        item.kind = kind
        self._fifo.append(item)
        self._sync_q.put(item)

    def _overdue_ref(self, item) -> float:
        """The point a dispatched item's wait is counted from, for the
        late record and the stall abort alike: its own dispatch, or the
        LAST ready transition of any item where that came later — a deep
        pipeline whose head is slow while the worker visibly progresses
        is load, not a stall. jax compiles inside the dispatch call on
        the loop's thread, so compile time eats neither budget."""
        return max(item.t_dispatch, self._t_last_ready)

    def _check_parked_stall(self):
        """Stall detection for the idle branch of the loop: the oldest
        dispatched-but-unready FIFO item is the one the sync worker
        should be finishing right now; if nothing has gone ready within
        the stall budget of its dispatch, it is wedged."""
        stall_s = self.ecfg.dispatch_stall_ms / 1e3
        if stall_s <= 0 or not self._fifo:
            return
        head = self._fifo[0]
        if not head.ready.is_set() and \
                time.monotonic() - self._overdue_ref(head) > stall_s:
            raise _DispatchStall(head)

    def _wait_ready(self, item):
        """Block until the sync worker marks ``item`` ready — with the
        stall watchdog armed (dispatch_stall_ms > 0), never forever."""
        stall_s = self.ecfg.dispatch_stall_ms / 1e3
        if stall_s <= 0:
            item.ready.wait()
            return
        step = min(stall_s / 2, 0.5)
        while not item.ready.wait(timeout=step):
            if time.monotonic() - self._overdue_ref(item) > stall_s:
                raise _DispatchStall(item)

    def _note_ready(self, item):
        """A dispatched item came home (engine thread, once an item): its
        pace sample and, where it overran its kind's pace (LATE_FACTOR,
        LATE_MIN_S), ONE ``late_dispatch`` span from the final numbers.
        Nothing polls while an item is overdue: in the stall this records
        no Python thread of the process runs."""
        if not item.t_ready:
            return
        waited = item.t_ready - item.t_ref
        pace = self._pace.get(item.kind)
        if pace is None:
            pace = self._pace[item.kind] = collections.deque(
                maxlen=PACE_ITEMS)
        elif waited > LATE_MIN_S:
            exp = statistics.median(pace) * item.n_steps
            if waited > LATE_FACTOR * exp:
                self._record_late(item, waited, exp)
        pace.append(waited / item.n_steps)

    def _record_late(self, item, waited: float, exp: float):
        overdue = waited - exp
        with self._lc_lock:
            self._lc["late_dispatches"] += 1
            self._lc["late_dispatch_s"] += overdue
        if not self.tracer.enabled:
            return
        # memory now less the last half-second sample before the wait
        # began: which kind of memory the process gave back inside it
        # (as far as the kernel splits it: sysobs.parse_proc_status)
        now = sysobs.host_memory()
        before = next((hm for t, hm in reversed(self._rss)
                       if t <= item.t_ref), {})
        gave = {k + "_mb": round((now[k + "_bytes"] - before[k + "_bytes"])
                                 / 1e6, 1)
                for k in ("rss", "rss_anon", "rss_file", "vm_size", "vm_data")
                if k + "_bytes" in now and k + "_bytes" in before}
        riders = _riders(item)
        self.tracer.record(
            "late_dispatch", "sync", item.t_ref, item.t_ready,
            rid=riders[0][1].req.request_id if riders else "",
            args={"kind": item.kind, "steps": item.n_steps,
                  "slots": len(riders),
                  "expected_ms": round(exp * 1e3, 3),
                  "overdue_ms": round(overdue * 1e3, 3),
                  **dict(zip(_RU_NAMES, item.ru or ())), **gave,
                  # the collector's full passes inside the wait: they
                  # stop every Python thread too
                  "gc_ms": round(1e3 * sysobs.GC_FULL.seconds_within(
                      item.t_ref, item.t_ready), 3),
                  # from the last compile heard to the wait's start:
                  # negative where something compiled DURING the wait
                  "since_compile_s": sysobs.PROCESS.since_last(item.t_ref)})

    def _handle_stall(self, item):
        """Abort ONLY the stalled item's requests: structured error events,
        span-ring dump to disk (the PR-6 post-mortem follow-up), slots and
        FIFO entry released. Device state is kept — slots outside the
        wedged item keep serving; if the device is truly dead, their own
        dispatches will stall and be reaped the same way."""
        import json as _json
        import logging

        log = logging.getLogger(__name__)
        stalled = [(i, snap) for i, snap in _riders(item)
                   if self.slots[i] is snap]
        with self._lc_lock:
            self._lc["stalls"] += 1
        dump_path = ""
        try:
            from localai_tpu.services.tracing import dump_ring

            dump_path = dump_ring(self.tracer, self.ecfg.stall_dump_dir)
            with self._lc_lock:
                self._lc["stall_dumps"] += 1
        except Exception:
            log.exception("stall ring dump failed")
        log.warning(_json.dumps({
            "event": "dispatch_stall",
            "dispatch_stall_ms": self.ecfg.dispatch_stall_ms,
            "item": type(item).__name__,
            "requests": [snap.req.request_id for _, snap in stalled],
            "slots": [i for i, _ in stalled],
            "ring_dump": dump_path,
        }))
        EVENTS.emit("stall_dump",
                    dispatch_stall_ms=self.ecfg.dispatch_stall_ms,
                    requests=[snap.req.request_id for _, snap in stalled],
                    ring_dump=dump_path)
        # flight recorder (ISSUE 12): the ring dump above is spans only;
        # this bundle adds state + recent events for the same moment
        self._flight_dump("stall", tag="stall",
                          requests=[snap.req.request_id
                                    for _, snap in stalled])
        try:
            self._fifo.remove(item)
        except ValueError:
            pass
        for i, snap in stalled:
            ev = StreamEvent(
                token_id=-1, text="", logprob=0.0, finish_reason="stop",
                error=(f"device dispatch stalled > "
                       f"{self.ecfg.dispatch_stall_ms} ms; request aborted"),
                error_kind="stall")
            # FIFO-ordered behind any tokens already handed over, so
            # the abort reaches queued-but-unemitted tokens too
            self._emitter.push_final(i, snap, [ev, None])
            self._release_slot(i)
            self._process_fork_waiters(i)

    def _start_request(self, req: GenRequest, resume=None):
        """Admit a request: install sampling state and queue its prompt for
        chunked prefill. No model compute happens here.

        With ``resume`` (a ResumeEntry) this doubles as the preemption
        restore path: ``req.prompt_ids`` already holds the full processed
        history (original prompt + emitted tokens), so head truncation is
        skipped — the history was truncated at first admission and stays
        < C-1 by the context-shift invariant — and the streaming state
        (detokenizer, counts, timings) is grafted onto the fresh slot so
        the client sees one uninterrupted stream."""
        if self._bus is not None and req.mm_vectors is not None:
            raise ValueError(
                "multimodal injection is not supported in multi-host "
                "lockstep mode")
        t_adm = time.monotonic()
        if resume is None:
            EVENTS.emit("admit", rid=req.request_id,
                        prompt_tokens=len(req.prompt_ids),
                        queued=self._queue.qsize())
        C = self.ecfg.max_context
        ids = list(req.prompt_ids)
        shift = 0
        if resume is not None:
            # safety clamp only: keep the tail if the history somehow
            # reached the context edge (the shift path should prevent it)
            if len(ids) > C - 1:
                shift = len(ids) - (C - 1)
                ids = ids[-(C - 1):]
        else:
            # truncate the prompt head, keeping the tail (reference
            # semantics: grpc-server.cpp truncation keeps the prompt tail)
            max_prompt = C - 1 - min(req.max_new_tokens, C // 4)
            if len(ids) > max_prompt:
                shift = len(ids) - max_prompt
                ids = ids[-max_prompt:]
        if not ids:
            ids = [getattr(self.tokenizer, "eos_token_id", 0) or 0]

        mm_pos = mm_vec = None
        if req.mm_vectors is not None and "multimodal" not in self._caps:
            raise ValueError("multimodal injection is not declared by "
                             f"the {self._fam_name} family")
        if req.mm_vectors is not None and len(req.mm_positions):
            pos = np.asarray(req.mm_positions, np.int64) - shift
            keep = (pos >= 0) & (pos < len(ids))
            pos = pos[keep]
            vec = np.asarray(req.mm_vectors, np.float32)[keep]
            pb = 16
            while pb < len(pos):
                pb *= 2
            # sentinel >= any bucket so the injection scatter DROPS pads
            # (negative sentinels would wrap to the last column)
            mm_pos = np.full((pb,), 1 << 30, np.int64)
            mm_pos[: len(pos)] = pos
            mm_vec = np.zeros((pb, self.cfg.hidden_size), np.float32)
            mm_vec[: len(pos)] = vec

        slot, common = self._pick_slot(ids)
        assert slot is not None, "_start_request called with no free slot"
        # a short accidental prefix match (e.g. two prompts sharing a BOS or
        # first word) is not worth the slow path it forces: continued
        # prefills run singly while fresh finals batch 8 per dispatch.
        # Reuse only prefixes long enough to beat that cost (real multi-turn
        # chats share hundreds of system/history tokens). Multimodal prompts
        # never reuse (their cache rows hold image embeddings, not tokens).
        if common < 16 or mm_pos is not None:
            common = 0
        if self.ecfg.ga_n > 1 or "prefix_reuse" not in self._caps:
            # self-extend re-maps positions as the context grows, and a
            # family that does not declare prefix reuse has no rows a
            # slot could resume from (none at all, or none without the
            # recurrent state at their boundary)
            common = 0
        win_off = 0
        if self._paged:
            if mm_pos is not None:
                # no reuse or sharing for image rows: recycle the slot's
                # retained pages into the pool
                self._pool.release(slot, 0)
            else:
                # paged reuse: own retained pages, or copy-on-write page
                # sharing from ANY slot's prefix (zero KV row copies).
                # Under self-extend only the tier-3 compressed-region
                # reuse applies (gated inside, ISSUE 16 satellite).
                common = self._paged_admission(slot, ids, common,
                                               rid=req.request_id)
                # snap-back admission (ISSUE 16): ``common`` is COMPACT
                # (sink + window rows); win_off is the skipped middle
                win_off = self._adm_win_off
        if "prefix_reuse" in self._caps and self.ecfg.ga_n <= 1 \
                and mm_pos is None and win_off == 0:
            # (the disk prompt cache stores contiguous rows — a windowed
            # table has no contiguous image to overlay, skip it)
            common = self._restore_prompt_cache(slot, req, ids, common)

        # install sampling state for the slot
        self.slot_params = sampling.set_slot(self.slot_params, slot, req.params)
        # mirostat v2 initializes mu at 2*tau (llama.cpp semantics)
        tau = req.params.mirostat_tau if req.params.mirostat_tau > 0 else 5.0
        self.mu[slot] = 2.0 * tau
        if resume is not None and resume.mu is not None:
            self.mu[slot] = resume.mu   # mirostat state survives the pause
        fallback = hash(req.request_id) & 0x7FFFFFFF
        self.rng_keys = sampling.seed_slot_key(
            self.rng_keys, slot, req.params, fallback_seed=fallback
        )
        if self._bus is not None:
            sv = req.params.seed
            self._bus.send("seed", slot=slot,
                           seed=int(sv) if sv is not None and sv >= 0
                           else fallback)
        grammar = gstate = bias_base = penalty0 = None
        if req.grammar:
            grammar = self._grammar_for(req.grammar)
            gstate = grammar.initial_state()
            bias_base = np.zeros((self.cfg.vocab_size,), np.float32)
            for tok, b in (req.params.logit_bias or {}).items():
                t = int(tok)
                if 0 <= t < bias_base.shape[0]:
                    bias_base[t] = float(b)
            penalty0 = self._mask_builder.penalty_row(grammar, gstate)
            self.bias = self.bias.at[slot].set(jnp.asarray(bias_base + penalty0))
            if self._bus is not None:
                from localai_tpu.parallel.lockstep import encode_bias_row

                self._bus.send("bias_rows", slots=[slot],
                               rows=[encode_bias_row(bias_base + penalty0)])
            self._bias_dirty[slot] = True
        elif req.params.logit_bias:
            self.bias = sampling.set_slot_logit_bias(self.bias, slot, req.params)
            if self._bus is not None:
                self._bus.send("bias_sparse", slot=slot,
                               pairs={int(t): float(b) for t, b in
                                      req.params.logit_bias.items()})
            self._bias_dirty[slot] = True
        elif self._bias_dirty[slot]:
            # clear a previous request's grammar mask / bias row; skipping
            # the device write for never-biased slots keeps admission free of
            # dispatches in the common case
            self.bias = self.bias.at[slot].set(0.0)
            if self._bus is not None:
                self._bus.send("bias_clear", slot=slot)
            self._bias_dirty[slot] = False

        # penalty ring covers the prompt tail (llama.cpp last-n semantics
        # include prompt tokens); reused prefixes are part of the prompt
        self.ring, self.ring_pos = sampling.set_slot_ring(
            self.ring, self.ring_pos, slot, ids)
        if common:
            self._reused_total += common

        s = _Slot(req, IncrementalDetokenizer(self.tokenizer), len(ids))
        s.grammar, s.gstate, s.bias_base = grammar, gstate, bias_base
        s.cur_penalty = penalty0
        s.mm_pos, s.mm_vec = mm_pos, mm_vec
        self._init_ga(slot, s, len(ids))
        # per-SLOT speculation eligibility (ISSUE 13 greedy, ISSUE 18
        # sampled: per-request, any drafting mode — with draft=auto every
        # ungrammared llama-family request speculates via n-gram
        # self-drafting; greedy slots accept via accept_greedy
        # (byte-identical), sampled slots via rejection sampling against
        # the filtered verify distribution (distribution-identical)).
        # Gates: ungrammared, no logit_bias, no penalties, no mirostat —
        # the spec verify scores W positions against ONE frozen sampler
        # state, so per-token-evolving logit shaping (penalty ring,
        # mirostat mu) would silently diverge from the burst sampler.
        # The n-gram drafter has no draft state, so reused prefixes and
        # preemption resumes stay eligible; the model drafter on the
        # CONTIGUOUS fallback still requires a draft-mirrored prompt (no
        # reused prefix, no resume) — only the PAGED draft cache shares
        # and restores prefix rows (stale draft planes there cost
        # acceptance quality, never correctness).
        sp = req.params
        s.spec_ok = (self._spec_mode != "off"
                     and not req.grammar
                     and mm_pos is None
                     and not sp.logit_bias
                     and sp.repeat_penalty in (0.0, 1.0)
                     and sp.presence_penalty == 0.0
                     and sp.frequency_penalty == 0.0
                     and (sp.mirostat or 0) == 0)
        if self._spec_mode == "model" and not self._paged \
                and (common != 0 or resume is not None):
            s.spec_ok = False
        if s.spec_ok and self._spec_mode == "model":
            self._ensure_draft_cache()
        s.win_off = win_off
        if win_off:
            # compact coordinates: the reused prefix covers the absolute
            # rows [0, sink) ++ [win_off + sink_rows, win_off + common);
            # pending resumes past the absolute end of the window. RoPE
            # stays absolute via pos_offset (set after _init_ga below).
            s.pending = ids[win_off + common:]
        else:
            s.pending = ids[common:]
        s.written = common
        s.reused = common
        if win_off:
            self.pos_offset[slot] = win_off
        # multimodal rows are image embeddings, not token embeddings — a
        # later text request must never "reuse" them as a token prefix
        self._cache_tokens[slot] = [] if mm_pos is not None else list(ids)
        if resume is not None:
            # graft the paused stream back on: the emitter keys its state
            # on the (slot, snap) it is handed, and its FIFO queue makes
            # handing it the same detokenizer safe across the pause
            s.detok = resume.detok
            s.held_text = resume.held_text
            s.generated = list(resume.generated)
            s.n_decoded = resume.n_decoded
            s.prompt_len = resume.prompt_len
            s.t_start = resume.t_start
            s.t_first_token = resume.t_first_token or 0.0
            s.t_prefill_ms = resume.t_prefill_ms
            s.preempts = resume.preempt_count
        self.slots[slot] = s
        self._prefill_queue.append(slot)
        # fold a watermark sample at admission: a request shorter than the
        # loop's sampling throttle must still leave a high-water mark
        self._sample_watermarks()
        tr = self.tracer
        if tr.enabled and resume is None:
            t1 = time.monotonic()
            if req.t_submit:
                tr.record("queue_wait", f"slot{slot}", req.t_submit,
                          s.t_start, rid=req.request_id)
            # admission covers prefix-cache splice + host-tier restore
            # (_paged_admission / _restore_prompt_cache above)
            tr.record("admission", f"slot{slot}", t_adm, t1,
                      rid=req.request_id,
                      args={"prompt_tokens": len(ids), "reused_rows": common,
                            # the prefill that starts at row 0 zeroes
                            # the slot's recurrent state
                            "state_reset": bool(self._state_bytes
                                                and common == 0)})
        return slot, ids, s

    def _start_fork_sibling(self, req: GenRequest, leader_slot: int,
                            leader_snap: "_Slot", ids: list):
        """Admit a request whose prompt is IDENTICAL to an in-flight
        leader's: install sampling state but prefill nothing — when the
        leader's prefill commits, its KV rows are forked to this slot and
        only the last prompt token is re-prefilled (for this slot's own
        first-token sampling). True shared-prefix for n>1 / simultaneous
        identical prompts (VERDICT r2 #5)."""
        slot, _ = self._pick_slot(ids)
        assert slot is not None
        self.slot_params = sampling.set_slot(self.slot_params, slot, req.params)
        tau = req.params.mirostat_tau if req.params.mirostat_tau > 0 else 5.0
        self.mu[slot] = 2.0 * tau
        self.rng_keys = sampling.seed_slot_key(
            self.rng_keys, slot, req.params,
            fallback_seed=hash(req.request_id) & 0x7FFFFFFF)
        if req.params.logit_bias:
            self.bias = sampling.set_slot_logit_bias(self.bias, slot, req.params)
            self._bias_dirty[slot] = True
        elif self._bias_dirty[slot]:
            self.bias = self.bias.at[slot].set(0.0)
            self._bias_dirty[slot] = False
        self.ring, self.ring_pos = sampling.set_slot_ring(
            self.ring, self.ring_pos, slot, ids)
        s = _Slot(req, IncrementalDetokenizer(self.tokenizer), len(ids))
        s.phase = "fork_wait"
        s.pending = []
        if self._paged:
            # drop the previous tenant's retained pages now: the fork
            # resolution either shares the leader's pages into an empty
            # table or downgrades to a fresh full prefill — and the old
            # pages may be shared with other slots (never overwrite)
            self._pool.release(slot, 0)
        self._cache_tokens[slot] = []
        self.slots[slot] = s
        self._fork_waiters.setdefault(leader_slot, []).append(
            (slot, s, leader_snap, ids))

    def _get_fork_fn(self, shape_key):
        fn = self._fork_fns.get(shape_key)
        if fn is None:
            def body(ck, cv, src, dst, n):
                C = kvcache.shape(ck)[2]
                mask = jnp.arange(C, dtype=jnp.int32) < n
                nk = kvcache.where_rows(mask, kvcache.slot_rows(ck, src),
                                        kvcache.slot_rows(ck, dst))
                nv = kvcache.where_rows(mask, kvcache.slot_rows(cv, src),
                                        kvcache.slot_rows(cv, dst))
                return (kvcache.tree_slot_update(ck, dst, nk),
                        kvcache.tree_slot_update(cv, dst, nv))

            fn = self._program("kv_fork", shape_key, "none", body,
                               donate_argnums=(0, 1))
            self._fork_fns[shape_key] = fn
        return fn

    def _process_fork_waiters(self, leader_slot: int):
        """Called when a leader's final prefill resolves: fork its committed
        rows to each waiting sibling and queue their 1-token finals. A
        vanished/failed leader downgrades siblings to full prefills."""
        waiters = self._fork_waiters.pop(leader_slot, None)
        if not waiters:
            return
        for sib, s, lsnap, ids in waiters:
            if self.slots[sib] is not s:
                continue  # sibling cancelled while waiting
            leader_ok = (self.slots[leader_slot] is lsnap
                         and lsnap.phase == "decode"
                         and self._cache_tokens[leader_slot][:len(ids)] == ids)
            if leader_ok and len(ids) > 1 and self._paged:
                # PAGED fork-dedup: the sibling's table points at the
                # leader's full prompt pages (ref-counted, zero row
                # copies; one boundary-page clone when the prompt ends
                # mid-page). The leader only ever appends past its
                # prompt, so shared pages stay read-only for it.
                n = len(ids) - 1
                self._pool.release(sib, 0)
                shared = self._share_prefix(leader_slot, sib, n)
                s.pending = ids[shared:]
                s.written = shared
                s.committed = shared
                s.reused = shared
                self._reused_total += shared
                self._cache_tokens[sib] = list(ids)
                # paged siblings share the draft planes of the same pages
                # (ISSUE 13), so spec eligibility follows the same
                # admission purity gates as _start_request
                fsp = s.req.params
                s.spec_ok = (self._spec_mode != "off"
                             and not s.req.grammar
                             and s.mm_pos is None
                             and not fsp.logit_bias
                             and fsp.repeat_penalty in (0.0, 1.0)
                             and fsp.presence_penalty == 0.0
                             and fsp.frequency_penalty == 0.0
                             and (fsp.mirostat or 0) == 0)
                if s.spec_ok and self._spec_mode == "model":
                    self._ensure_draft_cache()
            elif leader_ok and len(ids) > 1:
                n = len(ids) - 1
                self.ck, self.cv = self._get_fork_fn("main")(
                    self.ck, self.cv, leader_slot, sib, n)
                # a sibling qualifies under the same purity gates as
                # admission; with the model drafter it additionally needs
                # the leader's draft rows to exist so they can be forked
                sp = s.req.params
                pure = (not s.req.grammar
                        and not sp.logit_bias
                        and sp.repeat_penalty in (0.0, 1.0)
                        and sp.presence_penalty == 0.0
                        and sp.frequency_penalty == 0.0
                        and (sp.mirostat or 0) == 0)
                if self._spec_mode == "model":
                    s.spec_ok = (pure and lsnap.spec_ok
                                 and self.dck is not None)
                else:
                    s.spec_ok = self._spec_mode != "off" and pure
                if self.dck is not None and lsnap.spec_ok:
                    self.dck, self.dcv = self._get_fork_fn("draft")(
                        self.dck, self.dcv, leader_slot, sib, n)
                s.pending = [ids[-1]]
                s.written = n
                s.committed = n
                s.reused = n
                self._reused_total += n
                self._cache_tokens[sib] = list(ids[:-1])
            else:
                # leader gone or 1-token prompt: plain full prefill
                s.pending = list(ids)
                s.written = 0
                self._cache_tokens[sib] = list(ids)
            s.phase = "prefill"
            self._prefill_queue.append(sib)

    # ---------- prompt-cache persistence ----------

    def _get_restore_fn(self):
        fn = self._fork_fns.get("restore")
        if fn is None:
            def body(ck, cv, kfull, vfull, slot, n):
                C = kvcache.shape(ck)[2]
                mask = jnp.arange(C, dtype=jnp.int32) < n
                nk = kvcache.where_rows(mask, kvcache.rows_from_float(kfull, ck),
                                        kvcache.slot_rows(ck, slot))
                nv = kvcache.where_rows(mask, kvcache.rows_from_float(vfull, cv),
                                        kvcache.slot_rows(cv, slot))
                return (kvcache.tree_slot_update(ck, slot, nk),
                        kvcache.tree_slot_update(cv, slot, nv))

            fn = self._program("prompt_cache_restore", None, "none", body,
                               donate_argnums=(0, 1))
            self._fork_fns["restore"] = fn
        return fn

    def _load_prompt_cache_rows(self, path: str, m: int):
        """Read a prompt-cache file into float16 staging arrays sized to
        the full cache row shape with rows [:m] filled. Returns
        (kfull, vfull, tokens) or (None, None, None) if unreadable.
        Shared by the leader's restore path and the lockstep follower's
        cache_restore replay (both must build IDENTICAL inputs)."""
        L, _, C, KV, hd = kvcache.shape(self.ck)
        Lv = kvcache.shape(self.cv)[0]   # 0: one latent plane, no V rows
        try:
            data = np.load(path)
            ctoks = data["tokens"].tolist()
            # float16 staging (matches the file; halves the host alloc +
            # host->device transfer vs float32 — runs on the engine loop).
            # The row copies stay INSIDE the try: a concurrent re-save
            # (shorter prefix) or a different-config file surfaces as a
            # shape-mismatch ValueError here, and must degrade to
            # no-reuse, not fail the engine loop / kill a follower
            kfull = np.zeros((L, C, KV, hd), np.float16)
            vfull = np.zeros((Lv, C, KV, hd), np.float16)
            kfull[:, :m] = data["k"][:, :m]
            vfull[:, :m] = data["v"][:, :m]
        except Exception:
            __import__("logging").getLogger(__name__).exception(
                "unreadable prompt cache %s", path)
            return None, None, None
        return kfull, vfull, ctoks

    def _restore_prompt_cache(self, slot: int, req: GenRequest, ids: list,
                              common: int) -> int:
        """If the request names a prompt-cache file whose saved prefix beats
        the slot's own cached prefix, upload those KV rows and return the
        new reusable length (reference: prompt_cache_path restore,
        options.go:182-191)."""
        path = req.prompt_cache_path
        if not path or not os.path.exists(path):
            return common
        try:
            ctoks = np.load(path)["tokens"].tolist()
        except Exception:
            log_ = __import__("logging").getLogger(__name__)
            log_.exception("unreadable prompt cache %s", path)
            return common
        m = 0
        for a, b in zip(ctoks, ids):
            if a != b:
                break
            m += 1
        m = min(m, len(ids) - 1, self.ecfg.max_context - 1)
        if m <= common or m < 16:
            return common
        # re-compare the second read's tokens: a concurrent atomic re-save
        # between the two np.load calls would otherwise install KV rows
        # from a different file version than the prefix validated above
        kfull, vfull, ctoks2 = self._load_prompt_cache_rows(path, m)
        if kfull is None or ctoks2[:m] != ids[:m]:
            return common
        if self._paged:
            # the restore scatter writes rows [0, m) through the slot's
            # table — never into pages other slots still reference: drop
            # any shared pages first (restore beats sharing: m > common)
            npg = min(self._pool.pages_for(m), int(self._pool.owned[slot]))
            if any(self._pool.page_refs(slot, i) > 1 for i in range(npg)):
                self._pool.release(slot, 0)
            self._ensure_pages(slot, m)
            self._commit_ptab()
        if self._bus is not None:
            # followers replay the same restore body from the same file
            # (shared filesystem); the token prefix rides along so a
            # follower seeing a DIFFERENT file version fails loudly
            # instead of silently diverging the mesh
            self._bus.send("cache_restore", slot=slot, m=m, path=path,
                           tokens=ctoks[:m])
        self.ck, self.cv = self._get_restore_fn()(
            self.ck, self.cv, kfull, vfull, slot, m)
        return m

    def _get_cache_export_fn(self, n2: int):
        """Jitted (ck, cv, slot) -> dense float16 rows [L, n2, KV, hd],
        REPLICATED on the mesh: in multi-process serving the slot's rows
        live sharded across processes, so exporting them is a collective
        every process must issue (lockstep op "cache_save")."""
        key = ("export", n2)
        fn = self._fork_fns.get(key)
        if fn is None:
            out_sh = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                out_sh = NamedSharding(self.mesh, P())

            def body(ck, cv, slot):
                kr = kvcache.slot_rows(ck, slot)
                vr = kvcache.slot_rows(cv, slot)
                if kvcache.is_quant(kr):
                    kr = {"q": kr["q"][:, :n2], "s": kr["s"][:, :n2]}
                    vr = {"q": vr["q"][:, :n2], "s": vr["s"][:, :n2]}
                else:
                    kr, vr = kr[:, :n2], vr[:, :n2]
                return (kvcache.rows_to_float(kr, jnp.float16),
                        kvcache.rows_to_float(vr, jnp.float16))

            fn = self._program(
                "prompt_cache_export", n2, "none", body,
                out_shardings=(out_sh, out_sh) if out_sh else None)
            self._fork_fns[key] = fn
        return fn

    def _save_prompt_cache(self, slot: int, s: "_Slot"):
        """Persist the slot's committed rows + tokens on finish."""
        req = s.req
        if not req.prompt_cache_path or req.prompt_cache_ro \
                or "prefix_reuse" not in self._caps:
            return
        if self.ecfg.ga_n > 1:
            # rows may hold position-compressed (self-extend) keys; a
            # later raw-position engine restoring them would corrupt the
            # reused prefix — and restore is disabled while ga is on
            return
        n = s.committed if req.prompt_cache_all else min(s.prompt_len,
                                                         s.committed)
        tokens = self._cache_tokens[slot][:n]
        n = min(n, len(tokens))
        if n < 16:
            return  # below the reuse threshold; not worth the file
        try:
            # slice on DEVICE now (the backing ck/cv buffers get donated to
            # the next dispatch; an independent slice survives that), at a
            # power-of-two length so only log2(C) slice programs compile.
            # The expensive device->host sync + disk write runs on a
            # background thread, off the serving loop (r3 review finding).
            n2 = 1
            while n2 < n:
                n2 *= 2
            n2 = min(n2, self.ecfg.max_context)
            # dense-f16 export on device (dequantizes int8 rows in-jit, so
            # the file format is cache-dtype independent); in lockstep
            # mode the export is a replicated all-gather COLLECTIVE, so
            # the descriptor goes out first and every process issues it
            if self._bus is not None:
                self._bus.send("cache_save", slot=slot, n2=n2)
            self._commit_ptab()   # export gathers through the page table
            k_dev, v_dev = self._get_cache_export_fn(n2)(
                self.ck, self.cv, np.int32(slot))
            path = req.prompt_cache_path
            toks = np.asarray(tokens[:n], np.int32)

            def write():
                try:
                    k = np.asarray(k_dev)[:, :n]
                    v = np.asarray(v_dev)[:, :n]
                    tmp = path + ".tmp"
                    with open(tmp, "wb") as f:
                        np.savez(f, tokens=toks, k=k, v=v)
                    os.replace(tmp, path)
                except Exception:
                    __import__("logging").getLogger(__name__).exception(
                        "prompt cache save failed: %s", path)

            threading.Thread(target=write, daemon=True,
                             name="prompt-cache-save").start()
        except Exception:
            __import__("logging").getLogger(__name__).exception(
                "prompt cache save failed: %s", req.prompt_cache_path)

    # ---------- self-extend (group attention) ----------

    def _ga_c(self, P: int) -> int:
        """Position blocks fully compressed after ingesting P tokens."""
        return max(0, (P - 1) // self.ecfg.ga_w)

    def _ga_positions(self, lo: int, hi: int, c: int) -> "np.ndarray":
        """Grouped RoPE positions for raw rows [lo, hi) under c compressed
        blocks: each full block of ga_w raw tokens occupies ga_w/ga_n
        positions (integer-divided, so positions repeat within a group —
        that IS grouped attention); rows past the compressed region keep
        unit spacing."""
        n, w = self.ecfg.ga_n, self.ecfg.ga_w
        i = np.arange(lo, hi, dtype=np.int64)
        pos = np.where(i < c * w,
                       (i // w) * (w // n) + (i % w) // n,
                       c * (w // n) + (i - c * w))
        return pos.astype(np.int32)

    def _prefill_ga_piece(self, slot: int, s: "_Slot") -> bool:
        """One prefill piece for a slot whose prompt spans compressed
        position blocks: explicit grouped positions, one prompt per
        dispatch. (The reference ingests long prompts chunked and then
        divides their cached positions, grpc-server.cpp:1904-1927;
        ingesting directly at the final grouped positions is the same
        mapping without the intermediate surgery.)"""
        chunk = self._chunk
        remaining = len(s.pending)
        final = remaining <= chunk
        take = remaining if final else chunk
        bucket = self._bucket_for(take) if final else chunk
        positions = np.zeros((1, bucket), np.int32)
        positions[0, :take] = self._ga_positions(s.written, s.written + take,
                                                 s.ga_blocks)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :take] = s.pending[:take]
        self._ensure_pages(slot, s.written + take)
        self._commit_ptab()
        t0 = time.monotonic()
        if not final:
            self.ck, self.cv = self._get_ga_chunk_fn(bucket)(
                self.params, tokens, np.array([take], np.int32), self.ck,
                self.cv, np.array([slot], np.int32),
                np.array([s.written], np.int32), positions)
            s.pending = s.pending[take:]
            s.written += take
            s.committed = s.written
            s.t_prefill_ms += (time.monotonic() - t0) * 1e3
            return True
        out_ids, logprobs, self.ck, self.cv, self.rng_keys, mu_out = \
            self._get_ga_final_fn(bucket, s.written > 0)(
                self.params, tokens, np.array([take], np.int32), self.ck,
                self.cv, np.array([slot], np.int32),
                np.array([s.written], np.int32),
                self.ring.copy(), self.ring_pos.copy(), self.bias,
                self.rng_keys, sampling.pack_slot_params(self.slot_params),
                self.mu.copy(), positions)
        s.pending = []
        s.written += take
        if slot in self._prefill_queue:
            self._prefill_queue.remove(slot)
        item = _PendingPrefill([(slot, s)], out_ids, logprobs, mu_out, t0)
        self._enqueue(item, f"prefill_final:{bucket}")
        return True

    def _prefill_win_piece(self, slot: int, s: "_Slot") -> bool:
        """One prefill piece for a snap-back-windowed slot (ISSUE 16):
        cache rows are COMPACT (s.written) but RoPE positions are
        ABSOLUTE (win_off + written + t), so the piece rides the
        explicit-positions programs self-extend already compiled — same
        shapes, different position map, zero new program variants.
        Singly, like ga pieces: the packed/ragged programs derive
        positions from the cache row."""
        chunk = self._chunk
        remaining = len(s.pending)
        final = remaining <= chunk
        take = remaining if final else chunk
        bucket = self._bucket_for(take) if final else chunk
        positions = np.zeros((1, bucket), np.int32)
        positions[0, :take] = s.win_off + s.written + np.arange(
            take, dtype=np.int32)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :take] = s.pending[:take]
        self._ensure_pages(slot, s.written + take)
        self._commit_ptab()
        t0 = time.monotonic()
        if not final:
            self.ck, self.cv = self._get_ga_chunk_fn(bucket)(
                self.params, tokens, np.array([take], np.int32), self.ck,
                self.cv, np.array([slot], np.int32),
                np.array([s.written], np.int32), positions)
            s.pending = s.pending[take:]
            s.written += take
            s.committed = s.written
            s.t_prefill_ms += (time.monotonic() - t0) * 1e3
            return True
        out_ids, logprobs, self.ck, self.cv, self.rng_keys, mu_out = \
            self._get_ga_final_fn(bucket, s.written > 0)(
                self.params, tokens, np.array([take], np.int32), self.ck,
                self.cv, np.array([slot], np.int32),
                np.array([s.written], np.int32),
                self.ring.copy(), self.ring_pos.copy(), self.bias,
                self.rng_keys, sampling.pack_slot_params(self.slot_params),
                self.mu.copy(), positions)
        s.pending = []
        s.written += take
        if slot in self._prefill_queue:
            self._prefill_queue.remove(slot)
        item = _PendingPrefill([(slot, s)], out_ids, logprobs, mu_out, t0)
        self._enqueue(item, f"prefill_final:{bucket}")
        return True

    def _init_ga(self, slot: int, s: "_Slot", P: int):
        """Set the slot's self-extend state for a fresh P-token ingestion."""
        if self.ecfg.ga_n <= 1 or s.mm_pos is not None:
            s.ga_blocks = 0
            self.pos_offset[slot] = 0
            return
        n, w = self.ecfg.ga_n, self.ecfg.ga_w
        s.ga_blocks = self._ga_c(P)
        self.pos_offset[slot] = s.ga_blocks * (w - w // n)

    def _maybe_self_extend(self, slot: int, s: "_Slot") -> bool:
        """Compress newly completed position blocks (reference KV surgery:
        grpc-server.cpp:1904-1927, recomputeless here — cached keys are
        re-rotated in place since RoPE rotations compose). Returns True if
        a compression ran: the slot's not-yet-processed in-flight tokens
        carry stale positions and are dropped (recompute semantics, the
        same trade grammar rollback makes)."""
        n, w = self.ecfg.ga_n, self.ecfg.ga_w
        did = False
        while s.committed >= (s.ga_blocks + 1) * w:
            c = s.ga_blocks
            bd = w - w // n
            deltas = np.zeros((self.ecfg.max_context,), np.int32)
            i = np.arange(c * w, (c + 1) * w, dtype=np.int64)
            old = i - self.pos_offset[slot]
            new = c * (w // n) + (i - c * w) // n
            deltas[c * w:(c + 1) * w] = (new - old).astype(np.int32)
            deltas[(c + 1) * w:s.committed] = -bd
            self._commit_ptab()   # rotation reads/writes via the table
            self.ck = self._get_ga_rotate_fn()(self.ck, np.int32(slot), deltas)
            self.pos_offset[slot] += bd
            s.ga_blocks = c + 1
            did = True
        if did:
            # reset the slot's decode state to host truth: the pending
            # token toks[-1] occupies row cache_len-1 (same corrected
            # recipe as grammar rollback; see the invariant note there)
            self.lengths[slot] = max(s.cache_len - 1, 0)
            toks = self._cache_tokens[slot]
            self.cur_tokens[slot] = toks[-1] if toks else 0
            self.ring, self.ring_pos = sampling.set_slot_ring(
                self.ring, self.ring_pos, slot, toks)
            self._override.add(slot)
            for b in self._fifo:
                if isinstance(b, _Burst):
                    b.skip_slots.add(slot)
        return did

    def _prefill_plan(self, slot: int):
        """(final, take, bucket, continued) for a slot's next chunk."""
        s = self.slots[slot]
        chunk = self._chunk
        remaining = len(s.pending)
        final = remaining <= chunk
        take = remaining if final else chunk
        bucket = self._bucket_for(take) if final else chunk
        return final, take, bucket, s.written > 0

    def _prefill_step(self) -> bool:
        """Process the next prompt chunk(s).

        Fresh FINAL chunks sharing a bucket are batched into ONE dispatch of
        up to _final_pad prompts (padded by repeating the last entry) — the
        reference packs all prompt chunks into one llama_batch
        (grpc-server.cpp:1671+); one dispatch per prompt pays the
        dispatch overhead B times. Long-prompt (chunked) and
        continued (prefix-reuse) prefills go singly. Up to TWO final
        groups are in flight at a time (see _process_prefill).
        """
        if sum(1 for x in self._fifo if not isinstance(x, _Burst)) >= 2:
            return False
        while self._prefill_queue:
            slot = self._prefill_queue[0]
            s = self.slots[slot]
            if s is None or s.phase != "prefill":
                self._prefill_queue.pop(0)  # cancelled/stale entry
                continue
            break
        else:
            return False

        if self.ecfg.ga_n > 1 and s.ga_blocks > 0:
            # prompt spans compressed position blocks: explicit grouped
            # positions, singly (never grouped or fused)
            return self._prefill_ga_piece(slot, s)

        if self._win_pages:
            # snap-back during INGESTION too: a fresh long prompt must
            # never grow the device working set past the window — demote
            # committed middle pages before the next chunk lands, then
            # prefill at explicit absolute positions
            self._advance_window(slot, min(len(s.pending), self._chunk))
            if s.win_off > 0:
                return self._prefill_win_piece(slot, s)

        # RAGGED PACKED PREFILL (module doc): when the head slot is
        # eligible, one dispatch packs EVERY eligible queued slot's
        # pending tail under the token budget — replacing per-slot
        # chunks and the same-bucket final groups. Ineligible slots
        # (multimodal shapes, draft-mirrored spec slots) keep their
        # place in the queue and take this per-slot path when they
        # reach the head.
        if self._packed and self._pack_eligible(s):
            return self._prefill_step_packed()

        final, take, bucket, continued = self._prefill_plan(slot)

        def mm_rel(mm_pos, start, take, bucket):
            """Chunk-relative injection positions (pads -> OOB sentinel)."""
            rel = np.where((mm_pos >= start) & (mm_pos < start + take),
                           mm_pos - start, 1 << 30)
            return rel.astype(np.int32)[None]

        t0 = time.monotonic()
        if not final:
            self._ensure_pages(slot, s.written + take)
            self._commit_ptab()
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :take] = s.pending[:take]
            args = (self.params, tokens, np.array([take], np.int32), self.ck,
                    self.cv, np.array([slot], np.int32),
                    np.array([s.written], np.int32))
            if s.mm_pos is not None:
                fn = self._get_mm_chunk_fn(bucket, len(s.mm_pos))
                args = args + (mm_rel(s.mm_pos, s.written, take, bucket),
                               s.mm_vec[None])
            else:
                fn = self._get_chunk_fn(bucket)
                if self._bus is not None:
                    self._bus.send("chunk", bucket=bucket, tokens=tokens,
                                   seq_len=args[2], slot=args[5],
                                   start=args[6])
            self._tick_prefill_tokens += take
            with self._annot("prefill_chunk"):
                self.ck, self.cv = fn(*args)
            if self.dck is not None and s.spec_ok:
                # mirror the prompt into the draft cache (speculative
                # rounds need the same context; see engine/speculative.py)
                self.dck, self.dcv = self._get_draft_chunk_fn(bucket)(
                    self.draft_params, tokens, np.array([take], np.int32),
                    self.dck, self.dcv, np.array([slot], np.int32),
                    np.array([s.written], np.int32))
            s.pending = s.pending[take:]
            s.written += take
            s.committed = s.written
            t1 = time.monotonic()
            s.t_prefill_ms += (t1 - t0) * 1e3
            self._hobserve("prefill_dispatch_seconds", t1 - t0)
            if self.tracer.enabled:
                self.tracer.record("prefill_chunk", f"slot{slot}", t0, t1,
                                   rid=s.req.request_id,
                                   args={"tokens": take, "bucket": bucket})
            return True

        # collect a batch of fresh finals with the same bucket (queue order);
        # multimodal finals go singly (their injection shapes are per-request)
        group = [(slot, take)]
        if not continued and s.mm_pos is None:
            for other in self._prefill_queue[1:]:
                if len(group) >= self._final_pad:
                    break
                so = self.slots[other]
                if so is None or so.phase != "prefill" \
                        or so.mm_pos is not None or so.ga_blocks > 0 \
                        or so.win_off > 0:
                    continue
                of, ot, ob, oc = self._prefill_plan(other)
                if of and not oc and ob == bucket:
                    group.append((other, ot))
        # FUSED admission (r4): when the pipeline has room and a full-size
        # burst is runnable, prefill+first-token+decode-burst go out as ONE
        # dispatch (see _fused_body) — no separate prefill dispatch, no
        # activation round-trip, and no reason to hold admissions back
        if (not continued and s.mm_pos is None
                and self._n_inflight_bursts() < self.ecfg.pipeline_depth
                and self._pick_burst(
                    extra=[(t, self.slots[g].req.max_new_tokens)
                           for g, t in group]) == self.ecfg.decode_burst):
            return self._dispatch_fused(group, bucket)
        # pad to the next power of two (each size is precompiled): r3
        # padded every group straight to _final_pad, so a typical group of
        # ~7 prompts burned 2x its prefill compute on repeated padding rows
        if len(group) == 1:
            B = 1
        else:
            B = 2
            while B < len(group):
                B *= 2

        for gslot, gtake in group:
            self._ensure_pages(gslot, self.slots[gslot].written + gtake)
        self._commit_ptab()
        tokens = np.zeros((B, bucket), np.int32)
        seq_len = np.ones((B,), np.int32)
        slots_v = np.zeros((B,), np.int32)
        start_v = np.zeros((B,), np.int32)
        for b in range(B):
            gslot, gtake = group[min(b, len(group) - 1)]  # pad = repeat last
            gs = self.slots[gslot]
            tokens[b, :gtake] = gs.pending[:gtake]
            seq_len[b] = gtake
            slots_v[b] = gslot
            start_v[b] = gs.written

        # ring/ring_pos/slot_params copied: see the aliasing note in
        # _dispatch_decode (in-flight dispatches must not see host mutations)
        args = (self.params, tokens, seq_len, self.ck, self.cv, slots_v, start_v,
                self.ring.copy(), self.ring_pos.copy(), self.bias, self.rng_keys,
                sampling.pack_slot_params(self.slot_params), self.mu.copy())
        if s.mm_pos is not None:
            fn = self._get_mm_final_fn(bucket, len(s.mm_pos), continued)
            args = args + (mm_rel(s.mm_pos, start_v[0], take, bucket),
                           s.mm_vec[None])
        else:
            fn = self._get_final_fn(bucket, B, continued)
            if self._bus is not None:
                self._bus.send("final", bucket=bucket, B=B,
                               continued=continued, tokens=tokens,
                               seq_len=seq_len, slots_v=slots_v,
                               start_v=start_v, ring=args[7],
                               ring_pos=args[8], spp=args[11], mu=args[12])
        self._tick_prefill_tokens += sum(t for _g, t in group)
        with self._annot("prefill_final"):
            out_ids, logprobs, self.ck, self.cv, self.rng_keys, mu_out = \
                fn(*args)
        if self.dck is not None and any(
                self.slots[g].spec_ok for g, _ in group):
            # draft ingests the same prompt rows (no sampling needed);
            # padded/ineligible rows are harmless duplicates
            self.dck, self.dcv = self._get_draft_chunk_fn(bucket)(
                self.draft_params, tokens, seq_len, self.dck, self.dcv,
                slots_v, start_v)
        # ASYNC: don't sync here — the result would be serialized behind any
        # in-flight decode burst, idling the device. The group rides the
        # dispatch FIFO; _drain_fifo block-syncs it when it reaches the
        # head (all device work dispatched before it has then been synced,
        # so the wait is just this prefill's own remaining compute).
        # Bookkeeping (pending/written) is advanced NOW so a second
        # dispatch can't double-prefill the same slots.
        for gslot, gtake in group:
            gs = self.slots[gslot]
            gs.pending = []
            gs.written += gtake
            if gslot in self._prefill_queue:
                self._prefill_queue.remove(gslot)
        item = _PendingPrefill(
            [(gslot, self.slots[gslot]) for gslot, _ in group],
            out_ids, logprobs, mu_out, t0)
        self._enqueue(item, f"prefill_final:{bucket}")
        t1 = time.monotonic()
        self._hobserve("prefill_dispatch_seconds", t1 - t0)
        if self.tracer.enabled:
            self.tracer.record("prefill_dispatch", "engine", t0, t1,
                               args={"slots": len(group), "bucket": bucket})
        return True

    def _pack_eligible(self, s: "_Slot") -> bool:
        """May this slot's prompt tail ride a ragged pack? Multimodal
        prompts keep their per-request injection shapes (own compiled
        variants) and position-COMPRESSED self-extend slots need
        explicit grouped positions — both go singly. Spec slots pack:
        their draft-cache mirror rides a packed ragged program of its
        own (_get_draft_packed_fn), dispatched right behind the
        target's."""
        return s.mm_pos is None and s.ga_blocks == 0 and s.win_off == 0

    def _prefill_step_packed(self) -> bool:
        """ONE ragged dispatch for this tick's prompt ingestion: walk the
        prefill queue in order, take each eligible slot's pending tail
        (up to prefill_chunk per slot) until the token budget fills,
        and run the packed program. Final segments (ordered FIRST so
        the _PendingPrefill group indexes the output rows 0..F-1)
        sample their first token and ride the dispatch FIFO exactly
        like a legacy final group; non-final segments only advance
        their written/committed bookkeeping — their next chunk packs
        on a later tick, decode bursts interleaving in between."""
        S = self.ecfg.num_slots
        C = self.ecfg.max_context
        budget = self._pack_budget
        # weighted-fair packing (ISSUE 10): when slots of MORE THAN ONE
        # priority class have pending prompt tokens, the scheduler's
        # deficit round-robin caps each class's share of the budget.
        # Single-class traffic never enters the DRR path, so the packing
        # below stays bit-for-bit identical to the FIFO engine's.
        infl_vec = None
        drr = None
        sched = self._sched
        if sched is not None:
            infl_vec, pend, _act = self._plan_vec()
            if sum(1 for n in pend if n > 0) > 1:
                sched.begin_tick(budget, pend)
                drr = pend
        segs = []                   # (slot, s, take, final)
        total = 0
        for slot in self._prefill_queue:
            if len(segs) >= S or total >= budget:
                break
            s = self.slots[slot]
            if s is None or s.phase != "prefill" \
                    or not self._pack_eligible(s) or not s.pending:
                continue
            take = min(len(s.pending), self._chunk, budget - total)
            if take <= 0:
                continue
            if drr is not None:
                # slack = budget no other class can absorb this tick
                # (their pending work or deficit is exhausted) — granted
                # beyond the deficit so the walk stays work-conserving
                r = s.prio
                others = sum(min(sched.deficit(j), drr[j])
                             for j in range(len(drr)) if j != r)
                slack = max(0, (budget - total) - others)
                take = sched.take(r, take, slack)
                if take <= 0:
                    continue
                drr[r] = max(0, drr[r] - take)
            segs.append((slot, s, take, take == len(s.pending)))
            total += take
        if not segs:
            return False
        # finals first: _process_prefill reads ids_np[b] for group row b
        segs.sort(key=lambda t: not t[3])

        t0 = time.monotonic()
        for slot, s, take, _f in segs:
            self._ensure_pages(slot, s.written + take)
        self._commit_ptab()

        bucket = next(b for b in self._pack_buckets if total <= b)
        (tokens, positions, seg_of, seg_slots, seg_start, seg_off,
         seg_len, final_mask) = self._pack_arrays(bucket, C, S)
        off = 0
        for b, (slot, s, take, final) in enumerate(segs):
            tokens[off:off + take] = s.pending[:take]
            positions[off:off + take] = np.arange(s.written,
                                                  s.written + take)
            seg_of[off:off + take] = b
            seg_slots[b] = slot
            seg_start[b] = s.written
            seg_off[b] = off
            seg_len[b] = take
            final_mask[b] = final
            off += take
        continued = any(s.written > 0 for _sl, s, _t, _f in segs)

        args, meta = self._place_pack(
            [tokens, positions, seg_of],
            [seg_slots, seg_start, seg_off, seg_len, final_mask])

        self._pack_stats["dispatches"] += 1
        self._pack_stats["tokens"] += total
        self._tick_prefill_tokens += total
        self._pack_stats["segments"] += len(segs)
        self._pack_stats["pad_tokens"] += bucket - total
        if continued and llama.ragged_kernel_shape_fallback(
                self.ck, bucket, self.cfg):
            # this pack's SHAPE pushed the attention off the Pallas
            # kernel (the pre-segment-blocked grid fell back above ~1k
            # tokens at 8B head shapes) — counted per dispatch so the
            # cliff is observable in metrics() and gated in CI. Fresh
            # packs (continued=False) read no cache rows and take the
            # jnp path by design, so they never count.
            self._pack_stats["kernel_fallback"] += 1

        if self.dck is not None and any(
                s.spec_ok for _sl, s, _t, _f in segs):
            # draft mirrors the SAME ragged pack (no sampling): spec
            # slots used to force the whole pack onto the per-slot path;
            # padded / spec-ineligible segments are harmless duplicate
            # KV writes into draft rows nobody reads
            self.dck, self.dcv = self._get_draft_packed_fn(bucket)(
                self.draft_params, *args, *meta[:4], self.dck, self.dcv)

        # early-emit admission: when finals are present, the pipeline
        # has room and a full-size burst is runnable, the pack goes out
        # as the prefill head with the decode burst chained off its
        # device outputs (_dispatch_packed_split); otherwise the plain
        # packed program alone
        finals = [(slot, s, take) for slot, s, take, f in segs if f]
        if (finals
                and self._n_inflight_bursts() < self.ecfg.pipeline_depth
                and self._pick_burst(
                    extra=[(s.written + t, s.req.max_new_tokens)
                           for _sl, s, t in finals],
                    infl_vec=infl_vec)
                == self.ecfg.decode_burst):
            return self._dispatch_packed_split(segs, args, meta,
                                               bucket, continued, t0)

        fn = self._get_packed_fn(bucket, continued)
        # ring/ring_pos/mu copied: in-flight dispatches must not see
        # host mutations (same aliasing rule as the legacy finals)
        with self._annot("prefill_pack"):
            out_ids, logprobs, self.ck, self.cv, self.rng_keys, mu_out = fn(
                self.params, *args, *meta, self.ck, self.cv,
                self.ring.copy(), self.ring_pos.copy(), self.bias,
                self.rng_keys, sampling.pack_slot_params(self.slot_params),
                self.mu.copy())

        group = []
        t1 = time.monotonic()
        for slot, s, take, final in segs:
            s.pending = s.pending[take:]
            s.written += take
            if final:
                if slot in self._prefill_queue:
                    self._prefill_queue.remove(slot)
                group.append((slot, s))
            else:
                # non-final: KV rows are committed in device dispatch
                # order (same contract as the legacy chunk path)
                s.committed = s.written
                s.t_prefill_ms += (t1 - t0) * 1e3
        self._hobserve("prefill_dispatch_seconds", t1 - t0)
        if self.tracer.enabled:
            self.tracer.record("prefill_dispatch", "engine", t0, t1,
                               args={"tokens": total, "segments": len(segs),
                                     "bucket": bucket, "packed": True})
        if group or self._n_route:
            # (a pack with no final segment brings nothing back but its
            # routing, where the family reports one)
            item = _PendingPrefill(group, out_ids, logprobs, mu_out, t0,
                                   routed=bool(self._n_route))
            self._enqueue(item, f"prefill_pack:{bucket}")
        return True

    def _dispatch_packed_split(self, segs, args, meta, bucket: int,
                               continued: bool, t0: float) -> bool:
        """EARLY-EMIT admission tick, issued as TWO dispatches: the
        prefill head (_split_head_body: ragged prefill + first-token
        sampling + chain-state fold) and a plain decode burst chained
        off its device outputs. Between them the head's first tokens are
        synced and EMITTED (the only host round-trip; the device is
        computing the head for its whole duration, so the pipeline
        bubble is just the emit + dispatch latency) — finals' TTFT does
        not pay for the decode half. Final segments' slots flip to
        decode NOW and the burst rides the FIFO with ``head`` linked for
        its first-token rows; non-final segments only advance their
        prefill bookkeeping."""
        S = self.ecfg.num_slots
        C = self.ecfg.max_context
        K = self.ecfg.decode_burst
        group_snaps = []
        t1 = time.monotonic()
        for slot, s, take, final in segs:
            s.pending = s.pending[take:]
            s.written += take
            if not final:
                s.committed = s.written
                s.t_prefill_ms += (t1 - t0) * 1e3
                continue
            s.phase = "decode"
            s.cache_len = s.written
            self.lengths[slot] = s.written
            self.active_dev[slot] = True
            self._override.add(slot)
            if slot in self._prefill_queue:
                self._prefill_queue.remove(slot)
            group_snaps.append((slot, s))
        infl = self._inflight_vec()
        active = self.active_dev.copy()
        included = list(group_snaps)
        for i, s in enumerate(self.slots):
            if s is None or s.phase != "decode" \
                    or any(g == i for g, _ in group_snaps):
                continue
            if s.req.max_new_tokens - s.n_decoded - infl[i] <= 0:
                active[i] = False
                continue
            included.append((i, s))
        for gslot, gs in group_snaps:
            self._ensure_pages(gslot, min(C, gs.written + K + 2))
        for i, s in included:
            if any(g == i for g, _ in group_snaps):
                continue
            self._ensure_pages(i, min(C, int(self.lengths[i])
                                      + infl[i] + K + 2))
        self._commit_ptab()
        ov_mask = np.zeros((S,), np.bool_)
        if self._chain is None:
            chain = self._host_chain()
        else:
            chain = self._chain
            for i in self._override:
                ov_mask[i] = True
        self._override.clear()
        spp = sampling.pack_slot_params(self.slot_params)
        head_fn = self._get_split_head_fn(bucket, continued)
        with self._annot("prefill_pack_head"):
            ids_f, lps_f, self.ck, self.cv, self.rng_keys, chain = head_fn(
                self.params, chain[0], self.ck, self.cv, chain[1],
                chain[2], chain[3], self.bias, self.rng_keys, spp,
                active, chain[4], self._pack_ov(ov_mask), *args, *meta)
        # EARLY EMIT before the decode half goes out: the head's tiny
        # outputs (first ids/logprobs/mu) sync on the worker while the
        # device is still computing them, the engine processes the group
        # — first tokens reach the streams HERE — and only then issues
        # the chained burst. On async backends the pipeline bubble is
        # just this host round-trip (the device is busy with the head
        # for the whole wait); on synchronous-dispatch backends (the CPU
        # smoke rig, where a jit call blocks for its own compute) the
        # wait is free — so TTFT stops paying for the decode half.
        head = _PendingPrefill(group_snaps, ids_f, lps_f, chain[4], t0,
                               split=True, routed=bool(self._n_route))
        self._enqueue(head, f"prefill_pack_head:{bucket}")
        self._wait_ready(head)
        self._fifo.remove(head)
        self._process_prefill(head)
        # a grammar rollback / context shift inside the head's emission
        # corrects host mirrors and poisons in-flight bursts by walking
        # the FIFO — the chained burst isn't dispatched yet, so it missed
        # that walk: anything newly in _override sampled conditioned on
        # state the rollback discarded and must be skipped the same way
        poisoned = set(self._override)
        # the burst chains off the head's DEVICE outputs: overrides were
        # consumed by the head, so its ov mask is all-False (pos_offset
        # still rides — it is current-host-truth every dispatch)
        burst_fn = self._get_burst_fn(K)
        self._tick_decode_tokens += K * len(included)
        self._count_kv_walk(K, infl, [i for i, _ in included])
        with self._annot("decode_burst", steps=K, slots=len(included),
                         plain_greedy=self._sampler_branch(active)):
            pack, self.ck, self.cv, self.rng_keys, self._chain = burst_fn(
                self.params, chain[0], self.ck, self.cv, chain[1],
                chain[2], chain[3], self.bias, self.rng_keys, spp,
                active, chain[4], self._pack_ov(np.zeros((S,), np.bool_)))
        self._hobserve("prefill_dispatch_seconds", time.monotonic() - t0)
        if self.tracer.enabled:
            self.tracer.record("prefill_dispatch", "engine", t0,
                               time.monotonic(),
                               args={"segments": len(segs), "bucket": bucket,
                                     "packed": True, "fused": "split"})
        b = _Burst(K, included, pack, group=group_snaps, t_dispatch=t0,
                   head=head)
        b.skip_slots |= poisoned
        self._enqueue(b, "decode_burst")
        return True

    def _dispatch_fused(self, group, bucket: int) -> bool:
        """Dispatch final-prefill + first-token sampling + a full decode
        burst for ``group`` (fresh, non-multimodal prompts) in ONE device
        call. The group's slots flip to decode phase NOW; their first
        tokens come back in the burst's packed results."""
        t_d = time.monotonic()
        S = self.ecfg.num_slots
        K = self.ecfg.decode_burst
        if len(group) == 1:
            B = 1
        else:
            B = 2
            while B < len(group):
                B *= 2
        p_tokens = np.zeros((B, bucket), np.int32)
        p_seq = np.ones((B,), np.int32)
        p_slots = np.zeros((B,), np.int32)
        p_start = np.zeros((B,), np.int32)
        for b in range(B):
            gslot, gtake = group[min(b, len(group) - 1)]  # pad = repeat last
            gs = self.slots[gslot]
            p_tokens[b, :gtake] = gs.pending[:gtake]
            p_seq[b] = gtake
            p_slots[b] = gslot
            p_start[b] = gs.written
        group_snaps = []
        for gslot, gtake in group:
            gs = self.slots[gslot]
            gs.pending = []
            gs.written += gtake
            gs.phase = "decode"
            # cache_len must reflect the prompt rows NOW: _pick_burst and
            # _plan_spec cost capacity as cache_len + inflight decode
            # steps, and the fused burst is in flight from this moment
            gs.cache_len = gs.written
            self.lengths[gslot] = gs.written
            self.active_dev[gslot] = True
            self._override.add(gslot)
            if gslot in self._prefill_queue:
                self._prefill_queue.remove(gslot)
            group_snaps.append((gslot, gs))
        # budget-mask other decoding slots exactly like _dispatch_decode
        # (one FIFO pass for all slots' in-flight counts — ISSUE 9)
        infl = self._inflight_vec()
        active = self.active_dev.copy()
        included = list(group_snaps)
        for i, s in enumerate(self.slots):
            if s is None or s.phase != "decode" or any(g == i for g, _ in group_snaps):
                continue
            if s.req.max_new_tokens - s.n_decoded - infl[i] <= 0:
                active[i] = False
                continue
            included.append((i, s))
        C = self.ecfg.max_context
        for gslot, gs in group_snaps:
            # pages for the prompt rows AND the K fused burst steps
            self._ensure_pages(gslot, min(C, gs.written + K + 2))
        for i, s in included:
            if any(g == i for g, _ in group_snaps):
                continue
            self._ensure_pages(i, min(C, int(self.lengths[i])
                                      + infl[i] + K + 2))
        self._commit_ptab()
        ov_mask = np.zeros((S,), np.bool_)
        if self._chain is None:
            chain = self._host_chain()
        else:
            chain = self._chain
            for i in self._override:
                ov_mask[i] = True
        cold = self._chain is None
        self._override.clear()
        fn = self._get_fused_fn(bucket, B)
        spp = sampling.pack_slot_params(self.slot_params)
        ovp = self._pack_ov(ov_mask)
        if self._bus is not None:
            self._bus.send("fused", bucket=bucket, B=B,
                           chain=chain if cold else None,
                           spp=spp, active=active, ovp=ovp,
                           p_tokens=p_tokens, p_seq=p_seq, p_slots=p_slots,
                           p_start=p_start)
        self._tick_prefill_tokens += sum(t for _g, t in group)
        self._tick_decode_tokens += K * len(included)
        self._count_kv_walk(K, infl, [i for i, _ in included])
        with self._annot("prefill_fused", steps=K, slots=len(included),
                         plain_greedy=self._sampler_branch(active)):
            pack, self.ck, self.cv, self.rng_keys, self._chain = fn(
                self.params, chain[0], self.ck, self.cv, chain[1],
                chain[2], chain[3], self.bias, self.rng_keys,
                spp, active, chain[4], ovp,
                p_tokens, p_seq, p_slots, p_start,
            )
        if self.dck is not None and any(s.spec_ok for _, s in group_snaps):
            self.dck, self.dcv = self._get_draft_chunk_fn(bucket)(
                self.draft_params, p_tokens, p_seq, self.dck, self.dcv,
                p_slots, p_start)
        self._hobserve("prefill_dispatch_seconds", time.monotonic() - t_d)
        if self.tracer.enabled:
            self.tracer.record("prefill_dispatch", "engine", t_d,
                               time.monotonic(),
                               args={"slots": len(group_snaps),
                                     "bucket": bucket, "fused": True})
        b = _Burst(K, included, pack, group=group_snaps, t_dispatch=t_d)
        self._enqueue(b, f"prefill_fused:{bucket}")
        return True

    def _process_prefill(self, item: "_PendingPrefill"):
        """Activate a dispatched final-prefill group (its results already
        synced by the worker): flip the slots to decode phase and mark
        them as chain OVERRIDES so the next burst dispatch picks their
        state from the host mirrors without a chain rebuild."""
        if not item.ready.is_set():
            self._wait_ready(item)
        if item.err is not None:
            raise item.err
        if item.split:
            return self._process_split_head(item)
        self._note_ready(item)
        if item.routed:
            self._fold_route("prefill", item.lps_np[-self._n_route:], 1)
        group = item.group
        ids_np, lps_np, mu_np, t0 = item.ids_np, item.lps_np, item.mu_np, item.t0
        # scatter ONLY the group's mu entries — and only where the slot
        # still belongs to the dispatched request: a cancel + re-admit while
        # the prefill was in flight must not inherit the stale mu
        for gslot, snap in group:
            if self.slots[gslot] is snap:
                self.mu[gslot] = mu_np[gslot]
        t1 = time.monotonic()
        trc = self.tracer
        if trc.enabled and item.t_ready:
            # dispatch start -> sync-worker ready: device compute (plus
            # queueing behind earlier dispatches); ready -> now: the
            # engine loop's pickup lag
            trc.record("prefill_device", "engine", t0, item.t_ready,
                       args={"slots": len(group)})
            trc.record("finish_detect", "engine", item.t_ready, t1)

        for b, (gslot, snap) in enumerate(group):
            gs = self.slots[gslot]
            if gs is not snap:
                continue  # cancelled while the prefill was in flight
            first_id = int(ids_np[b])
            gs.cache_len = gs.written
            gs.committed = gs.written
            gs.phase = "decode"

            self.lengths[gslot] = gs.written
            self.cur_tokens[gslot] = first_id
            self.active_dev[gslot] = True
            self._override.add(gslot)
            # mirror the sampled token into the penalty ring
            self.ring[gslot, self.ring_pos[gslot] % sampling.RING_N] = first_id
            self.ring_pos[gslot] += 1

            gs.t_prefill_ms += (t1 - t0) * 1e3
            if gs.t_first_token == 0.0:
                gs.t_first_token = t1
                if gs.req.t_submit:
                    self._hobserve("ttft_seconds", t1 - gs.req.t_submit,
                                   rid=gs.req.request_id)
                if trc.enabled:
                    trc.record("prefill", f"slot{gslot}", t0, t1,
                               rid=gs.req.request_id,
                               args={"prompt_tokens": gs.prompt_len})
            self._emit_token(gslot, first_id, float(lps_np[b]))
        # leaders just committed: fork their rows to any waiting siblings
        # (vanished leaders downgrade the siblings to full prefills)
        for gslot, _snap in group:
            self._process_fork_waiters(gslot)
        self._flush_grammar_bias()
        self._flush_em_batch()

    def _process_split_head(self, item: "_PendingPrefill"):
        """EARLY-EMIT head processing (results already synced): emit the
        final segments' first tokens and stamp TTFT — NOTHING else. The
        slots flipped to decode at dispatch, the device chain state was
        advanced in-program, and the chained burst's fold carries the
        host-mirror updates; writing mirrors here would race the
        in-flight burst (a later dispatch composing them as overrides
        would REWIND device state). A grammar rollback / context shift /
        self-extend inside _emit poisons pipelined bursts via the usual
        FIFO walk; the burst CHAINED to this head dispatches after this
        runs, so _dispatch_packed_split carries anything newly overridden
        here into its skip_slots instead."""
        if item.processed:
            return
        item.processed = True
        self._note_ready(item)
        if item.routed:
            self._fold_route("prefill", item.lps_np[-self._n_route:], 1)
        group = item.group
        ids_np, lps_np, t0 = item.ids_np, item.lps_np, item.t0
        t1 = time.monotonic()
        trc = self.tracer
        if trc.enabled and item.t_ready:
            trc.record("prefill_device", "engine", t0, item.t_ready,
                       args={"slots": len(group), "split": True})
            trc.record("finish_detect", "engine", item.t_ready, t1)
        for b, (gslot, snap) in enumerate(group):
            gs = self.slots[gslot]
            if gs is not snap:
                continue  # cancelled while the head was in flight
            gs.committed = gs.written
            gs.t_prefill_ms += (t1 - t0) * 1e3
            if gs.t_first_token == 0.0:
                gs.t_first_token = t1
                if gs.req.t_submit:
                    self._hobserve("ttft_seconds", t1 - gs.req.t_submit,
                                   rid=gs.req.request_id)
                if trc.enabled:
                    trc.record("prefill", f"slot{gslot}", t0, t1,
                               rid=gs.req.request_id,
                               args={"prompt_tokens": gs.prompt_len,
                                     "fused": "split"})
            self._emit_token(gslot, int(ids_np[b]), float(lps_np[b]))
        for gslot, _snap in group:
            self._process_fork_waiters(gslot)
        self._flush_grammar_bias()
        self._flush_em_batch()

    def _pack_ov(self, ov_mask) -> "np.ndarray":
        """Build the packed override upload. Round-robin buffer reuse
        (ISSUE 9): a dispatch's async host->device copy must never read
        a buffer a LATER dispatch is refilling, so the pool is deeper
        than the pipeline can hold in flight — no per-dispatch
        allocation, no aliasing of live host mirrors."""
        p = self._ov_pool[self._ov_pool_idx]
        self._ov_pool_idx = (self._ov_pool_idx + 1) % len(self._ov_pool)
        p[0] = ov_mask
        p[1] = self.cur_tokens
        p[2] = self.lengths
        p[3] = self.ring_pos
        p[4] = self.mu
        p[5] = self.pos_offset
        # window-advance length rebase (ISSUE 16): subtracted from the
        # chained device lengths unconditionally; overridden slots take
        # their (already rebased) host lengths instead, so their delta
        # must not apply on top — and a COLD dispatch feeds rebased host
        # lengths for EVERY slot, so the whole delta row drops
        if self._chain is None:
            self._win_delta.fill(0)
        p[6] = self._win_delta
        p[6][np.asarray(ov_mask, bool)] = 0.0
        self._win_delta.fill(0)
        p[7:] = self.ring.T
        return p

    def _pack_arrays(self, bucket: int, C: int, S: int) -> tuple:
        """Reusable (round-robin) host arrays for one packed-prefill
        dispatch, reset to their pad values (ISSUE 9: eight fresh
        allocations per packed dispatch, gone). Pool depth mirrors
        _pack_ov: deeper than the pipeline can hold in flight, so an
        async upload never reads a buffer being refilled."""
        pool = self._seg_pools.get(bucket)
        if pool is None:
            depth = max(6, self.ecfg.pipeline_depth + 4)
            pool = self._seg_pools[bucket] = [
                (np.empty((bucket,), np.int32),    # tokens
                 np.empty((bucket,), np.int32),    # positions
                 np.empty((bucket,), np.int32),    # seg_of
                 np.empty((S,), np.int32),         # seg_slots
                 np.empty((S,), np.int32),         # seg_start
                 np.empty((S,), np.int32),         # seg_off
                 np.empty((S,), np.int32),         # seg_len
                 np.empty((S,), np.bool_))         # final_mask
                for _ in range(depth)]
            self._seg_pool_idx[bucket] = 0
        i = self._seg_pool_idx[bucket]
        self._seg_pool_idx[bucket] = (i + 1) % len(pool)
        (tokens, positions, seg_of, seg_slots, seg_start, seg_off,
         seg_len, final_mask) = pool[i]
        tokens.fill(0)
        positions.fill(C)      # pad: scatter drops
        seg_of.fill(S)         # pad: own segment id
        seg_slots.fill(S)      # pad: state writes drop
        seg_start.fill(0)
        seg_off.fill(0)
        seg_len.fill(0)
        final_mask.fill(False)
        return pool[i]

    def _n_inflight_bursts(self) -> int:
        return sum(1 for x in self._fifo if isinstance(x, _Burst))

    def _inflight_vec(self) -> list:
        """Decode tokens already dispatched (unprocessed) per slot, in
        ONE pass over the FIFO (ISSUE 9): dispatch planners that used to
        call the per-slot scan once per candidate slot — rescanning the
        FIFO S times per dispatch — take this vector once instead."""
        n = [0] * self.ecfg.num_slots
        for b in self._fifo:
            if not isinstance(b, _Burst):
                continue
            gset = {i for i, _ in b.group}
            for i, _ in b.slots:
                if i not in b.skip_slots:
                    # spec-masked slots may emit up to W tokens per round
                    # (conservative upper bound — acceptance is unknown
                    # until the tick syncs)
                    w = (b.spec_width if b.spec_width
                         and b.spec_mask[i] else 1)
                    n[i] += b.n_steps * w + (1 if i in gset else 0)
        return n

    def _inflight_steps(self, slot: int) -> int:
        """Decode tokens already dispatched (unprocessed) for a slot."""
        return self._inflight_vec()[slot]

    def _plan_vec(self):
        """One-pass planner state for a tick (ISSUE 10, extending the
        ISSUE-9 one-pass FIFO walk to the admission/budget walk): the
        in-flight vector plus per-class accounting — pending prompt
        tokens a class could pack this tick (chunk-capped, like the
        packed walk's own ``take``) and active slot counts.  Returns
        ``(infl_vec, pending_by_class, active_by_class)``."""
        infl_vec = self._inflight_vec()
        ncls = len(PRIORITY_CLASSES)
        pend = [0] * ncls
        act = [0] * ncls
        for s in self.slots:
            if s is None:
                continue
            act[s.prio] += 1
            if s.phase == "prefill" and s.pending:
                pend[s.prio] += min(len(s.pending), self._chunk)
        return infl_vec, pend, act

    def _drain_fifo(self, can_feed: bool = False,
                    block: bool = True) -> bool:
        """Process dispatched work. Prefill groups activate as soon as the
        sync worker flags them ready (any position in the FIFO — safe:
        a prefill group's slots are disjoint from every in-flight burst's
        participants, since they were mid-prefill at those dispatches).
        The oldest burst is block-synced only when the pipeline is already
        full or nothing more can be dispatched (``can_feed`` False) — at
        most one BLOCKING sync per call, so the loop refills the pipeline
        between syncs and the device always has work queued. Bursts that
        are ALREADY ready are all processed (ISSUE 9: their device work
        is done, so holding them to one per tick only inflated
        finish-detect by a full tick per queued burst); ``block`` False
        skips the blocking sync entirely (top-of-tick drain: pick up
        whatever completed while the previous tick packed prompts)."""
        progressed = False
        for item in [x for x in self._fifo
                     if not isinstance(x, _Burst) and x.ready.is_set()]:
            self._fifo.remove(item)
            self._process_prefill(item)
            progressed = True
        synced = False
        while True:
            acted = False
            for idx, item in enumerate(self._fifo):
                if not isinstance(item, _Burst):
                    continue   # a not-yet-ready prefill ahead; bursts may
                    # pass it
                if not item.ready.is_set():
                    if not block or synced or (
                            can_feed and self._n_inflight_bursts()
                            < self.ecfg.pipeline_depth):
                        break
                    synced = True
                del self._fifo[idx]
                self._process_burst(item)
                progressed = True
                acted = True
                break
            if not acted:
                break
        return progressed

    def _pick_burst(self, extra=None, infl_vec=None) -> int:
        """Burst length for this dispatch: a power of two <= decode_burst,
        clamped so no slot crosses its context-shift threshold mid-burst
        (tokens past the threshold would be silently position-less).
        Grammar-constrained slots ride FULL bursts speculatively: tokens
        are verified against the automaton at processing time and the slot
        rolls back (free — recompute semantics) on the first invalid one
        (r3; replaces the r2 design that forced burst=1 fleet-wide).
        Slots that finish mid-burst (EOS/stop/budget) simply ride out the
        burst; their tail tokens are discarded host-side — cheaper than
        clamping every slot to the smallest remaining budget. Host mirrors
        lag by every in-flight (pipelined) burst, so those steps count
        against the capacity clamp too."""
        cap = self.ecfg.decode_burst
        budget = 1
        if infl_vec is None:
            infl_vec = self._inflight_vec()
        for i, s in enumerate(self.slots):
            if s is None or s.phase != "decode":
                continue
            infl = infl_vec[i]
            used = s.cache_len + infl
            cap = min(cap, max(1, self.ecfg.max_context - 2 - used))
            budget = max(budget, s.req.max_new_tokens - s.n_decoded - infl)
        for take, max_new in (extra or ()):
            cap = min(cap, max(1, self.ecfg.max_context - 2 - take))
            budget = max(budget, max_new - 1)  # first token sampled in-fn
        cap = min(cap, budget)
        if self._sched is not None:
            # priority-weighted burst sizing (ISSUE 11, S2): when prompt
            # work of a strictly higher class waits behind this burst,
            # the scheduler's weights shrink it so admission comes back
            # around sooner. preempt=0 -> _sched is None -> bit-for-bit
            # today's sizing; so is any single-class workload.
            pend = [0] * len(PRIORITY_CLASSES)
            dec_rank = None
            for s in self.slots:
                if s is None:
                    continue
                if s.phase == "prefill" and s.pending:
                    pend[s.prio] += 1
                elif s.phase == "decode":
                    dec_rank = (s.prio if dec_rank is None
                                else min(dec_rank, s.prio))
            cap = self._sched.burst_share(dec_rank, pend, cap)
        k = 1
        while k * 2 <= cap:
            k *= 2
        return k

    def _ensure_draft_cache(self):
        """Lazily materialize the draft-model KV cache (model drafter
        only — the n-gram drafter has no draft state). On paged engines
        it lives in the PAGED pool riding the MAIN page table (ISSUE
        13): draft rows sit at the same page ids as the target's, so
        prefix sharing, COW cloning and offload/restore extend to spec
        slots with no second allocator."""
        if self.dck is not None or self.draft_cfg is None:
            return
        self.dck, self.dcv = llama.init_cache(
            self.draft_cfg, self.ecfg.num_slots, self.ecfg.max_context,
            self.ecfg.cache_dtype,
            **({"page_size": self._pool.page_size,
                "num_pages": self._pool_pages} if self._paged else {}))
        if self._paged:
            # the fresh draft cache carries an empty page table; dirty
            # the allocator so the next commit stamps live state into it
            self._pool.dirty = True

    def _spec_tick_body(self, params, tokens, ck, cv, lengths, ring,
                        ring_pos, bias, keys, slot_params, active, mu,
                        ov_pack, spec_mask, dparams=None, dck=None,
                        dcv=None, *, n_rounds: int,
                        flags: tuple = (True, True, True)):
        """The FUSED spec tick (ISSUE 13): n_rounds speculative rounds in
        ONE dispatch. A round routes its rows by what the drafter found
        (ISSUE 34): a spec-masked slot WITH a draft takes a D-token
        target-verify pass; every other active row — a non-spec slot, or
        a spec slot whose history offered no continuation this round —
        takes a plain decode+sample step. Each pass sits under a
        lax.cond on "has a row", so a round without a draft costs a
        plain step and a round of drafted rows alone costs the verify
        pass; a skipped branch hands ck, cv, keys and mu back untouched.
        The plain rows run the exact _make_scan_step ops (engine_decode
        + sampling.sample, the other rows masked out of the KV write
        and the state folds) so their stream is bit-identical to a
        plain burst; drafted rows verify through the same
        continued-prefill forward spec_round uses, with the other rows
        parked at the OOB row so the scatter drops them.

        Drafted rows accept greedily (accept_greedy, byte-identical to
        plain greedy) when the slot is greedy, and via rejection
        sampling against the filtered verify distribution
        (accept_sampled + sampling.verify_dist, ISSUE 18 —
        distribution-identical to plain sampling) when temperature > 0;
        an undrafted sampled row draws from the plain sampler, which IS
        that law. Both modes share ONE compiled body so the precompile
        ladder and the COMPILES_AFTER_WARMUP=0 gate are untouched.

        Pack layout [2*R*W + 2*R + 1, S] f32: ids (R*W rows,
        round-major), logprobs (R*W), per-round emit counts (R),
        per-round "this row was drafted" (R), mu — where W = n_draft + 1
        tokens per verified round (accepted prefix + bonus) and a plain
        row emits exactly 1 at position 0 of its round."""
        from localai_tpu.engine import speculative

        sp = sampling.unpack_slot_params(slot_params)
        tokens, lengths, ring, ring_pos, mu, pos_offset = \
            self._compose_overrides(tokens, lengths, ring, ring_pos, mu,
                                    ov_pack)
        D = self.ecfg.n_draft
        W = D + 1
        S = self.ecfg.num_slots
        C = kvcache.shape(ck)[2]
        model_mode = dck is not None
        spec_active = active & spec_mask
        plain_active = active & ~spec_mask
        slot_ids = jnp.arange(S, dtype=jnp.int32)

        def round_step(carry, _):
            (tokens, ck, cv, dck, dcv, lengths, ring, ring_pos, keys,
             mu) = carry
            with jax.named_scope("spec_draft"):
                if model_mode:
                    drafts, has, dck, dcv = speculative.draft_propose(
                        dparams, self.draft_cfg, tokens, lengths, dck, dcv,
                        spec_active, D)
                else:
                    drafts, has = speculative.ngram_propose(
                        tokens, ring, ring_pos, D, self.ecfg.spec_ngram)
            drafted = spec_active & has
            plain_rows = plain_active | (spec_active & ~has)

            def plain_step(ck, cv, keys, mu):
                # bit-identical ops to _make_scan_step; drafted rows are
                # masked out of the KV write and the state folds
                logits, ck, cv = self.family.engine_decode(
                    params, self.cfg, tokens, lengths, plain_rows, ck, cv,
                    pos_offset=pos_offset)
                ids0, lps0, new_keys, new_mu = sampling.sample(
                    logits, sp, ring, ring_pos, bias, keys, mu,
                    use_penalties=flags[0], use_typical=flags[1],
                    use_mirostat=flags[2], active=plain_rows)
                keys = jnp.where(plain_rows[:, None], new_keys, keys)
                mu = jnp.where(plain_rows, new_mu, mu)
                return ids0, lps0, ck, cv, keys, mu

            def no_plain_row(ck, cv, keys, mu):
                return (jnp.zeros((S,), jnp.int32),
                        jnp.zeros((S,), jnp.float32), ck, cv, keys, mu)

            ids0, lps0, ck, cv, keys, mu = jax.lax.cond(
                plain_rows.any(), plain_step, no_plain_row, ck, cv, keys, mu)

            def verify(ck, cv, keys):
                # current token + D proposals scored in one continued
                # prefill; undrafted rows park at the OOB start so their
                # writes drop (their single KV write is the decode
                # step's above)
                with jax.named_scope("spec_verify"):
                    tin = jnp.concatenate([tokens[:, None], drafts], axis=1)
                    seq = jnp.full((S,), W, jnp.int32)
                    start = jnp.where(drafted, lengths, C)
                    all_logits, ck, cv = self.family.prefill(
                        params, self.cfg, tin, seq, ck, cv, slot_ids, start,
                        continued=True, return_all_logits=True)
                    # filtered verify distribution via the sampler's own
                    # code path (sampling.filter_window under
                    # verify_dist): idx[:,:,0] is approx_max_k's retained
                    # global argmax, so the greedy spec stream is plain
                    # greedy's wherever a row's maximum is single (among
                    # equal maxima the window's rank 0 is its sort's pick
                    # and sampling's greedy branch takes the lowest
                    # index: both are a maximum) — and the window probs
                    # ARE the law plain sampling draws from, so rejection
                    # acceptance against them is distribution-lossless
                    vidx, vprobs = sampling.verify_dist(
                        all_logits, sp, use_typical=flags[1])
                    greedy = vidx[:, :, 0]
                    out_spec, n_spec, _k = speculative.accept_greedy(
                        drafts, greedy, drafted)
                    logp = jax.nn.log_softmax(all_logits, axis=-1)
                    lp_spec = jnp.take_along_axis(
                        logp, out_spec[:, :, None], axis=2)[:, :, 0]
                    # ISSUE 18: sampled spec rows accept via rejection
                    # sampling. Scatter the window distribution to vocab
                    # for acceptance and residual resampling
                    # (n-gram/greedy-draft proposals are deterministic,
                    # so draft_probs=None one-hot degeneration)
                    samp = drafted & ~jnp.asarray(sp["greedy"])
                    V = all_logits.shape[-1]
                    rows = jnp.arange(S * W, dtype=jnp.int32)[:, None]
                    tgt = jnp.zeros((S * W, V), jnp.float32).at[
                        rows, vidx.reshape(S * W, -1)].set(
                        vprobs.reshape(S * W, -1)).reshape(S, W, V)
                    out_ss, n_ss, _ks, keys_ss = speculative.accept_sampled(
                        drafts, tgt, None, keys, samp)
                    lp_ss = jnp.log(jnp.clip(jnp.take_along_axis(
                        tgt, out_ss[:, :, None], axis=2)[:, :, 0], 1e-20))
                    out_spec = jnp.where(samp[:, None], out_ss, out_spec)
                    n_spec = jnp.where(samp, n_ss, n_spec)
                    lp_spec = jnp.where(samp[:, None], lp_ss, lp_spec)
                    keys = jnp.where(samp[:, None], keys_ss, keys)
                return out_spec, lp_spec, n_spec, ck, cv, keys

            def no_draft(ck, cv, keys):
                return (jnp.zeros((S, W), jnp.int32),
                        jnp.zeros((S, W), jnp.float32),
                        jnp.zeros((S,), jnp.int32), ck, cv, keys)

            out_spec, lp_spec, n_spec, ck, cv, keys = jax.lax.cond(
                drafted.any(), verify, no_draft, ck, cv, keys)
            pad = jnp.zeros((S, D), jnp.int32)
            out = jnp.where(drafted[:, None], out_spec,
                            jnp.concatenate([ids0[:, None], pad], axis=1))
            lps = jnp.where(drafted[:, None], lp_spec,
                            jnp.concatenate(
                                [lps0[:, None], pad.astype(jnp.float32)],
                                axis=1))
            n_out = jnp.where(drafted, n_spec,
                              plain_rows.astype(jnp.int32))
            for j in range(W):   # W is static: unrolled ring pushes
                ring, ring_pos = sampling.update_ring(
                    ring, ring_pos, out[:, j], active & (j < n_out))
            lengths = lengths + n_out
            last = jnp.take_along_axis(
                out, jnp.maximum(n_out - 1, 0)[:, None], axis=1)[:, 0]
            tokens = jnp.where(active, last, tokens)
            return ((tokens, ck, cv, dck, dcv, lengths, ring, ring_pos,
                     keys, mu), (out.T, lps.T, n_out, drafted))

        carry = (tokens, ck, cv, dck, dcv, lengths, ring, ring_pos, keys,
                 mu)
        carry, (ids_all, lps_all, n_all, drafted_all) = jax.lax.scan(
            round_step, carry, None, length=n_rounds)
        (tokens, ck, cv, dck, dcv, lengths, ring, ring_pos, keys,
         mu) = carry
        R = n_rounds
        pack = jnp.concatenate(
            [ids_all.reshape(R * W, S).astype(jnp.float32),
             lps_all.reshape(R * W, S),
             n_all.astype(jnp.float32), drafted_all.astype(jnp.float32),
             mu[None, :]], axis=0)
        chain = self._pin_chain(tokens, lengths, ring, ring_pos, mu)
        if model_mode:
            return pack, ck, cv, keys, chain, dck, dcv
        return pack, ck, cv, keys, chain

    def _get_spec_tick_fn(self, n_rounds: int,
                          flags: tuple = (True, True, True)):
        key = ("spec_tick", n_rounds, flags)
        fn = self._burst_fns.get(key)
        if fn is None:
            donate = ((2, 3, 8, 15, 16) if self._spec_mode == "model"
                      else (2, 3, 8))
            fn = self._program(
                "spec_tick", (n_rounds, flags),
                f"{self._decode_attn()} + verify jnp:gather_mixed",
                lambda *a: self._spec_tick_body(*a, n_rounds=n_rounds,
                                                flags=flags),
                donate_argnums=donate)
            self._burst_fns[key] = fn
        return fn

    def _plan_spec(self, included: list, infl: list):
        """Spec plan for this tick: (n_rounds, spec_mask) or None for a
        plain burst. A slot joins spec rounds iff it admitted spec_ok
        (ungrammared, penalty/mirostat-free — greedy AND sampled since
        ISSUE 18) and has W = n_draft + 1 rows of headroom
        past the steps already in flight; everyone else in ``included``
        rides the same tick as a plain-decode row. Round count follows
        _pick_burst's sizing discipline with spec slots charged W rows
        and W tokens of budget per round (the device decides which
        rounds draft, ISSUE 34), floored to a power of two so only the
        precompiled ladder ever runs."""
        if self._spec_mode == "off" or self.ecfg.ga_n > 1:
            # spec rounds advance positions row=position; they are not
            # self-extend-aware — mutually exclusive features
            return None
        if self._spec_mode == "model" and self.dck is None:
            return None
        W = self.ecfg.n_draft + 1
        S = self.ecfg.num_slots
        C = self.ecfg.max_context
        mask = np.zeros((S,), np.bool_)
        for i in included:
            s = self.slots[i]
            # windowed slots decode singly: spec verify rows assume
            # row == position, which the snap-back rebase breaks
            if s.win_off > 0:
                continue
            if s.spec_ok and C - 2 - (s.cache_len + infl[i]) >= W:
                mask[i] = True
        if not mask.any():
            return None
        cap = max(1, self.ecfg.decode_burst // W)
        budget = 1
        for i in included:
            s = self.slots[i]
            used = s.cache_len + infl[i]
            rem = s.req.max_new_tokens - s.n_decoded - infl[i]
            if mask[i]:
                cap = min(cap, max(1, (C - 2 - used) // W))
                budget = max(budget, (rem + W - 1) // W)
            else:
                cap = min(cap, max(1, C - 2 - used))
                budget = max(budget, rem)
        cap = min(cap, budget)
        if self._sched is not None:
            # priority-weighted sizing, mirroring _pick_burst (ISSUE 11)
            pend = [0] * len(PRIORITY_CLASSES)
            dec_rank = None
            for s in self.slots:
                if s is None:
                    continue
                if s.phase == "prefill" and s.pending:
                    pend[s.prio] += 1
                elif s.phase == "decode":
                    dec_rank = (s.prio if dec_rank is None
                                else min(dec_rank, s.prio))
            cap = self._sched.burst_share(dec_rank, pend, cap)
        k = 1
        while k * 2 <= cap:
            k *= 2
        return k, mask

    def _count_kv_walk(self, n_steps: int, infl, rows):
        """/debug/state's kv_walk, at a dispatch of ``n_steps`` decode
        steps whose plain rows are the slots ``rows``: the page-table
        entries those steps span (num_slots x max_pages each: what the
        paged decode kernel's first form walked) and those that hold a
        live row (what it works on since PR 31: PERF.md section 6).
        ``state_walk`` likewise for a family with recurrent state: slots x
        state layers for every step that ran, and the live slots' share of
        it, which is all a state kernel that skips the others moves."""
        if not self._paged:
            return
        self._state_walk["slot_steps_live"] += (
            n_steps * len(rows) * self._state_layers)
        self._state_walk["slot_steps_grid"] += (
            n_steps * self.ecfg.num_slots * self._state_layers)
        pg = self._pool.page_size
        self._kv_walk["pages_live"] += n_steps * sum(
            -(-(int(self.lengths[i]) + infl[i]) // pg) for i in rows)
        self._kv_walk["pages_grid"] += (
            n_steps * self.ecfg.num_slots * self._pool.max_pages)

    def _sampler_branch(self, active) -> int:
        """1 where every step of the burst about to go out with ``active``
        takes the sampler's greedy branch, 0 where the window runs: the
        predicate the device evaluates (sampling.all_plain_greedy), on
        the host's vectors, for the burst's span and the counters. A spec
        tick's rounds ask it of their undrafted rows alone, so a 0 there
        says a round MAY take the window."""
        plain = int(sampling.all_plain_greedy(self.slot_params, active))
        self._sampler_bursts["plain_greedy" if plain else "window"] += 1
        return plain

    def _count_spec_kv_walk(self, b: "_Burst", live_idx):
        """kv_walk for a folded spec tick: the decode step ran in the
        rounds in which some row was not drafted, for those rows, each
        at the length its round began with (the mirrors still hold the
        lengths the tick started from)."""
        if not self._paged:
            return
        pg = self._pool.page_size
        plain = (b.n_out_np > 0) & ~b.drafted_np            # [R, S]
        self._kv_walk["pages_grid"] += (
            int(plain.any(axis=1).sum()) * self.ecfg.num_slots
            * self._pool.max_pages)
        for i in live_idx:
            before = int(self.lengths[i]) + np.cumsum(b.n_out_np[:, i]) \
                - b.n_out_np[:, i]
            self._kv_walk["pages_live"] += int(
                (-(-before // pg))[plain[:, i]].sum())

    def _dispatch_decode(self) -> bool:
        """Dispatch the next decode burst — or, when spec-eligible slots
        are decoding, a FUSED SPEC TICK (ISSUE 13: draft-propose +
        target-verify rounds for the eligible slots, plain decode steps
        for everyone else, ONE chained dispatch — no whole-engine
        spec/burst alternation) — if the pipeline has room and some
        decoding slot still has budget beyond the steps already in
        flight. Never blocks: burst-to-burst state
        (tokens/lengths/ring/mu) chains device-side, and host events are
        composed in as per-slot overrides (see _decode_burst_body)."""
        if self._n_inflight_bursts() >= self.ecfg.pipeline_depth:
            return False
        decoding = [i for i, s in enumerate(self.slots)
                    if s is not None and s.phase == "decode"]
        if not decoding:
            return False
        active = self.active_dev.copy()
        included = []
        infl = self._inflight_vec()   # one FIFO pass for all slots (ISSUE 9)
        for i in decoding:
            s = self.slots[i]
            if s.req.max_new_tokens - s.n_decoded - infl[i] <= 0:
                # in-flight steps already cover this slot's budget: mask it
                # out so it doesn't ride the new burst as garbage compute
                # (with depth-2 pipelining that waste measured ~30% of all
                # dispatched slot-steps on the wave-shaped bench). Release
                # happens when the in-flight results are emitted; grammar
                # rollbacks recover budget and simply re-include the slot
                # on a later dispatch.
                active[i] = False
                continue
            included.append(i)
        if not included:
            return False
        if self._win_pages:
            # snap-back BEFORE planning/ensure: demote cold middle pages
            # so the upcoming steps land inside the bounded working set
            # (the rebase rides _win_delta into the chain, so no
            # override — and no host sync — is forced)
            upcoming = self.ecfg.decode_burst * (self.ecfg.n_draft + 1) + 2
            for i in included:
                self._advance_window(i, infl[i] + upcoming)
        plan = self._plan_spec(included, infl)
        W = self.ecfg.n_draft + 1
        if plan is not None:
            n_steps, spec_mask = plan
        else:
            n_steps, spec_mask = self._pick_burst(infl_vec=infl), None
        if self._paged:
            C = self.ecfg.max_context
            for i in included:
                # spec-masked slots write up to W rows per round (the
                # rejected tail is overwritten by the next round)
                need = (n_steps * W if spec_mask is not None
                        and spec_mask[i] else n_steps)
                self._ensure_pages(i, min(C, int(self.lengths[i])
                                          + infl[i] + need + 2))
            self._commit_ptab()
        f = sampling.feature_flags(self.slot_params, self.active_dev)
        flags = (f["use_penalties"], f["use_typical"], f["use_mirostat"])
        if any(flags) and flags != (True, True, True):
            # only the two precompiled variants exist; mixed feature sets
            # use the full sampler rather than compiling mid-request
            flags = (True, True, True)
        fn = (self._get_spec_tick_fn(n_steps, flags) if plan is not None
              else self._get_burst_fn(n_steps, flags))
        t_d = time.monotonic()
        S = self.ecfg.num_slots
        ov_mask = np.zeros((S,), np.bool_)
        if self._chain is None:
            # cold chain: feed everything from the host mirrors
            chain = self._host_chain()
        else:
            chain = self._chain
            for i in self._override:
                ov_mask[i] = True
        cold = self._chain is None
        self._override.clear()
        # snapshot the PARTICIPATING SLOT OBJECTS: a slot index may be
        # released and re-admitted while this burst is in flight, and the
        # new occupant must never receive the stale burst's tokens
        burst_slots = [(i, self.slots[i]) for i in included]
        spp = sampling.pack_slot_params(self.slot_params)
        ovp = self._pack_ov(ov_mask)
        if self._bus is not None:
            self._bus.send("burst", k=n_steps, flags=flags,
                           chain=chain if cold else None,
                           spp=spp, active=active, ovp=ovp)
        self._tick_decode_tokens += n_steps * len(included)
        if plan is None:
            # a spec tick's device decides, round by round, which rows
            # take the decode step: _fold_burst counts those from the pack
            self._count_kv_walk(n_steps, infl, included)
        with self._annot(
                "decode_burst", steps=n_steps, slots=len(included),
                plain_greedy=self._sampler_branch(active),
                **({"spec_slots": int(spec_mask.sum()), "spec_width": W}
                   if plan is not None else {})):
            if plan is None:
                pack, self.ck, self.cv, self.rng_keys, self._chain = fn(
                    self.params, chain[0], self.ck, self.cv, chain[1],
                    chain[2], chain[3], self.bias, self.rng_keys,
                    spp, active, chain[4], ovp,
                )
            elif self._spec_mode == "model":
                (pack, self.ck, self.cv, self.rng_keys, self._chain,
                 self.dck, self.dcv) = fn(
                    self.params, chain[0], self.ck, self.cv, chain[1],
                    chain[2], chain[3], self.bias, self.rng_keys,
                    spp, active, chain[4], ovp, spec_mask,
                    self.draft_params, self.dck, self.dcv,
                )
            else:
                pack, self.ck, self.cv, self.rng_keys, self._chain = fn(
                    self.params, chain[0], self.ck, self.cv, chain[1],
                    chain[2], chain[3], self.bias, self.rng_keys,
                    spp, active, chain[4], ovp, spec_mask,
                )
        b = _Burst(n_steps, burst_slots, pack, t_dispatch=t_d)
        if plan is not None:
            b.spec_mask = spec_mask
            b.spec_width = W
            # dispatch-time snapshot for per-mode fold attribution: the
            # slot may be re-admitted with different params in flight
            b.spec_greedy = self.slot_params["greedy"].copy()
            st = self._spec_stats
            st["dispatches"] += 1
            if any(not spec_mask[i] for i in included):
                st["mixed_dispatches"] += 1
        self._enqueue(b, "spec_tick" if plan is not None else "decode_burst")
        return True

    def _live(self, i, snap):
        return self.slots[i] is snap and snap.phase == "decode"

    def _fold_burst(self, b: "_Burst"):
        """Sync a burst's packed results (ONE device->host transfer) and
        fold the device-side state evolution into the host mirrors. Cheap
        (~1ms past the device sync) and idempotent; emission is separate
        so it can overlap the NEXT dispatch."""
        if b.folded:
            return
        if not b.ready.is_set():
            self._wait_ready(b)   # worker-side sync in flight
        if b.err is not None:
            raise b.err
        packed = b.pack_np                  # [2K+1(+2), S] f32
        K = b.n_steps
        if self._n_route and (not b.group or b.head is not None):
            # the plain burst's last rows: its route stats, summed over
            # its steps (a fused per-slot admission carries none)
            b.experts_touched = self._fold_route(
                "decode", packed[-self._route_rows_n:].reshape(-1), K)
        if b.spec_width:
            # spec tick pack: ids/lps are [R*W, S] round-major, then the
            # [R, S] per-round emit counts and drafted bits, then mu
            KW = K * b.spec_width
            b.ids_np = packed[:KW].astype(np.int32)
            b.lps_np = packed[KW:2 * KW]
            b.n_out_np = packed[2 * KW:2 * KW + K].astype(np.int32)
            b.drafted_np = packed[2 * KW + K:2 * KW + 2 * K] > 0
            mu_np = packed[2 * KW + 2 * K]
        else:
            b.ids_np = packed[:K].astype(np.int32)
            b.lps_np = packed[K:2 * K]
            mu_np = packed[2 * K]
        if b.group:
            if b.head is not None:
                # early-emit split: the first tokens synced with the
                # HEAD (ready before this burst — same worker, dispatch
                # order); rebuild the slot-indexed rows the ring fold
                # below reads. The burst pack itself is a PLAIN pack
                # (no first-token rows).
                h = b.head
                if not h.ready.is_set():
                    self._wait_ready(h)
                if h.err is not None:
                    raise h.err
                S = self.ecfg.num_slots
                b.first_ids = np.zeros((S,), np.int32)
                b.first_lps = np.zeros((S,), np.float32)
                for gi, (i, _snap) in enumerate(b.group):
                    b.first_ids[i] = h.ids_np[gi]
                    b.first_lps[i] = h.lps_np[gi]
            else:
                b.first_ids = packed[2 * K + 1].astype(np.int32)
                b.first_lps = packed[2 * K + 2]
        live_idx = [i for i, snap in b.slots
                    if self._live(i, snap) and i not in b.skip_slots]
        for i in live_idx:
            self.mu[i] = mu_np[i]
        if b.spec_width:
            # fused spec tick: per-slot VARIABLE advance — each round
            # emitted n_out tokens (drafted rows: accepted prefix +
            # bonus; every other row: exactly 1 at position 0); the
            # mirrors must replay the device's ring/length evolution
            # token-by-token
            Wd = b.spec_width
            st = self._spec_stats
            st["rounds_verified"] += int(b.drafted_np.any(axis=1).sum())
            self._count_spec_kv_walk(b, live_idx)
            for i in live_idx:
                ns = b.n_out_np[:, i]
                tot = int(ns.sum())
                if tot <= 0:
                    continue
                self.cur_tokens[i] = b.ids_np[(K - 1) * Wd
                                              + int(ns[K - 1]) - 1, i]
                self.lengths[i] += tot
                rp = int(self.ring_pos[i])
                for r in range(K):
                    for j in range(int(ns[r])):
                        self.ring[i, rp % sampling.RING_N] = \
                            b.ids_np[r * Wd + j, i]
                        rp += 1
                self.ring_pos[i] = rp
                if b.spec_mask[i]:
                    # a drafted round emits its accepted prefix and the
                    # bonus, any other round the plain step's one token
                    nd = int(b.drafted_np[:, i].sum())
                    # ISSUE 18 per-mode split (greedy accept_greedy vs
                    # sampled rejection acceptance), attributed from the
                    # dispatch-time greedy snapshot
                    mode = ("greedy" if b.spec_greedy is None
                            or b.spec_greedy[i] else "sampled")
                    for c in (st, st["by_mode"][mode]):
                        c["rounds"] += K
                        c["rows_drafted"] += nd
                        c["proposed"] += nd * (Wd - 1)
                        c["accepted"] += int(
                            ns[b.drafted_np[:, i]].sum()) - nd
                        c["tokens"] += tot
            b.folded = True
            return
        for i in live_idx:
            self.cur_tokens[i] = b.ids_np[-1, i]
            self.lengths[i] += b.n_steps
        # fused groups: the in-fn first token precedes the burst ids in the
        # ring (mirror must match the device evolution)
        for i, snap in b.group:
            if self._live(i, snap) and i not in b.skip_slots:
                self.ring[i, self.ring_pos[i] % sampling.RING_N] = b.first_ids[i]
                self.ring_pos[i] += 1
        sampling.host_update_ring(self.ring, self.ring_pos, b.ids_np, live_idx)
        b.folded = True

    def _process_burst(self, b: "_Burst"):
        """Fold (if not already) then emit a burst's tokens (emission may
        release slots or trigger context shifts — both mark the device
        chain dirty). The burst's tokens reach the emitter as ONE batch
        (_flush_em_batch), which coalesces them per stream (see
        StreamEvent.token_ids)."""
        if b.head is not None and not b.head.processed:
            # the pipeline block-synced this burst past its own
            # not-yet-processed head (_drain_fifo's burst walk passes
            # non-burst items): emit the head's first tokens NOW, in
            # stream order, before the burst's. The burst is already out
            # of the FIFO, but rollback / shift / self-extend poisoning
            # inside the head's emission walks self._fifo — keep the
            # burst discoverable for the duration.
            if b.head in self._fifo:
                self._fifo.remove(b.head)
            self._fifo.appendleft(b)
            try:
                self._process_prefill(b.head)
            finally:
                self._fifo.remove(b)
        self._fold_burst(b)
        self._note_ready(b)
        if not b.group and b.t_dispatch:
            dt = (time.monotonic() - b.t_dispatch) * 1e3
            self._burst_ms_ema += 0.2 * (dt - self._burst_ms_ema)
        t_proc = time.monotonic()
        tr = self.tracer
        if b.t_dispatch:
            t_rdy = b.t_ready or t_proc
            self._hobserve("decode_burst_seconds",
                           max(0.0, t_rdy - b.t_dispatch))
            if self._t_last_burst:
                # burst-to-burst cadence / steps: the stream-visible ITL.
                # Spec ticks divide by the MEAN tokens actually emitted
                # per live slot (accepted + bonus), so acceptance shows
                # up as ITL improvement, not as phantom long bursts
                steps = b.n_steps
                if b.spec_width and b.n_out_np is not None:
                    per_slot = b.n_out_np.sum(axis=0)
                    live = per_slot[per_slot > 0]
                    if live.size:
                        steps = float(live.mean())
                self._hobserve("itl_seconds",
                               max(0.0, t_proc - self._t_last_burst)
                               / max(1.0, steps))
            self._t_last_burst = t_proc
            if tr.enabled:
                live = [(i, snap.req.request_id) for i, snap in b.slots
                        if self._live(i, snap) and i not in b.skip_slots]
                # the slots that rode the burst and their requests:
                # chrome_trace draws the slot tracks from these
                tr.record("decode_burst_device", "engine",
                          b.t_dispatch, t_rdy,
                          args={"steps": b.n_steps, "slots": len(b.slots),
                                "fused": bool(b.group),
                                "spec": bool(b.spec_width),
                                "slot_ids": [i for i, _ in live],
                                "rids": [r for _, r in live],
                                **({} if b.experts_touched is None else
                                   {"experts_touched": b.experts_touched}),
                                # a latent pool: the rows the live slots
                                # held when the burst began, at the least
                                **({"ctx_rows": int(sum(
                                    max(0, int(self.lengths[i]) - b.n_steps)
                                    for i, _ in live))}
                                   if self._latent_bytes is not None
                                   else {})})
                if b.spec_width:
                    # the fused program has no host-visible boundary
                    # between drafting and verifying: on the device they
                    # are the named scopes spec_draft / spec_verify
                    nsp, dr = b.n_out_np, b.drafted_np
                    spec_idx = [i for i, _s in b.slots if b.spec_mask[i]]
                    rows_drafted = int(dr.sum())
                    tr.record("spec_round", "engine", b.t_dispatch, t_rdy,
                              args={"mode": self._spec_mode,
                                    "rounds": b.n_steps,
                                    "rounds_verified": int(
                                        dr.any(axis=1).sum()),
                                    "rows_drafted": rows_drafted,
                                    "spec_slots": len(spec_idx),
                                    "proposed": rows_drafted
                                    * (b.spec_width - 1),
                                    "accepted": int(
                                        nsp[dr].sum()) - rows_drafted})
                tr.record("finish_detect", "engine", t_rdy, t_proc)
        rolled: set = set()   # grammar slots rolled back mid-burst
        try:
            # fused-admission slots: emit the in-fn sampled first token
            # before their burst tokens (this is their TTFT event)
            t1 = time.monotonic()
            for i, snap in b.group:
                if not self._live(i, snap) or i in b.skip_slots:
                    continue
                if b.head is not None:
                    # early-emit split: the head already emitted this
                    # slot's first token, stamped its TTFT, and set
                    # committed/cache_len (which the emission advanced —
                    # resetting them here would rewind the slot)
                    continue
                snap.cache_len = snap.written
                snap.committed = snap.written
                # charge only the prefill's share of the fused dispatch:
                # subtract the typical plain-burst latency (EMA) so the
                # timing stays comparable with the non-fused path
                snap.t_prefill_ms += max(
                    0.0, (t1 - b.t_dispatch) * 1e3 - self._burst_ms_ema)
                if snap.t_first_token == 0.0:
                    snap.t_first_token = t1
                    if snap.req.t_submit:
                        self._hobserve("ttft_seconds",
                                       t1 - snap.req.t_submit,
                                       rid=snap.req.request_id)
                    if tr.enabled:
                        tr.record("prefill", f"slot{i}", b.t_dispatch, t1,
                                  rid=snap.req.request_id,
                                  args={"prompt_tokens": snap.prompt_len,
                                        "fused": True})
                if not self._emit_token(i, int(b.first_ids[i]),
                                        float(b.first_lps[i])):
                    rolled.add(i)
            for i, _snap in b.group:
                self._process_fork_waiters(i)
            if b.spec_width:
                # fused spec tick: round-major emission, each slot emits
                # its round's n_out tokens (plain rows: 1 at position 0)
                Wd = b.spec_width
                for r in range(b.n_steps):
                    for i, snap in b.slots:
                        if i in rolled or i in b.skip_slots \
                                or not self._live(i, snap):
                            continue
                        for j in range(int(b.n_out_np[r, i])):
                            if i in rolled or not self._live(i, snap):
                                break
                            snap.committed = min(snap.committed + 1,
                                                 snap.cache_len)
                            if not self._emit_token(
                                    i, int(b.ids_np[r * Wd + j, i]),
                                    float(b.lps_np[r * Wd + j, i])):
                                rolled.add(i)
                                break
            else:
                for j in range(b.n_steps):
                    for i, snap in b.slots:
                        if i in rolled or i in b.skip_slots \
                                or not self._live(i, snap):
                            continue  # finished/shifted/replaced/rolled-back
                        # the step just wrote this slot's previous
                        # token's KV row
                        snap.committed = min(snap.committed + 1,
                                             snap.cache_len)
                        if not self._emit_token(i, int(b.ids_np[j, i]),
                                                float(b.lps_np[j, i])):
                            rolled.add(i)
        finally:
            self._flush_grammar_bias()
            self._flush_em_batch()

    def _emit_token(self, slot: int, token_id: int, logprob: float) -> bool:
        """Emit one token for a slot: the id-level control flow (EOS,
        grammar advance/rollback, length, context shift, KV bookkeeping)
        and NO text work — the token joins the per-tick batch handed to
        the emitter worker, which owns detok, stop-scan and every
        ``req.out`` put. Stop sequences are text-level, so they are
        detected by the EMITTER and fed back via
        ``_apply_emitter_notes``. Returns False when the slot's remaining
        tokens in the current burst must be skipped (a grammar-invalid
        speculative sample rolled the slot back, or a self-extend
        compression invalidated its in-flight positions)."""
        s = self.slots[slot]
        s.generated.append(token_id)
        s.n_decoded += 1
        self._total_tokens += 1
        finish = None
        shifted = False

        if token_id in self.eos_ids and not (s.req.ignore_eos and s.grammar is None):
            if s.grammar is not None and s.cur_penalty is not None \
                    and s.cur_penalty[token_id] != 0.0:
                # speculative EOS sampled under a STALE mask while the
                # grammar cannot terminate yet — discard and resume
                return self._rollback_grammar(slot, s)
            finish = "stop"
        elif s.grammar is not None and not self._advance_grammar(slot, s, token_id):
            # speculative token fell outside the grammar (stale mask mid-
            # burst) — roll back instead of emitting invalid output
            return self._rollback_grammar(slot, s)
        elif s.n_decoded >= s.req.max_new_tokens:
            finish = "length"
        elif s.win_off + s.cache_len + 1 >= self.ecfg.max_context - 1:
            if self.ecfg.context_shift:
                # the emitter still stop-scans this token; a stop that
                # completes here aborts the shifted slot via the note
                # channel — the re-prefill is wasted work, the emitted
                # OUTPUT ends at the stop either way
                self._context_shift(slot, s, token_id)
                shifted = True
            else:
                finish = "length"

        extended = False
        if finish is None and not shifted:
            # this token's KV is written by the next decode step
            self._cache_tokens[slot].append(token_id)
            s.cache_len += 1
            if self.ecfg.ga_n > 1 and s.mm_pos is None:
                extended = self._maybe_self_extend(slot, s)

        e = self._em_batch.get(slot)
        if e is None or e["snap"] is not s:
            e = self._em_batch[slot] = {
                "slot": slot, "snap": s, "tokens": [],
                "finish": None, "timings": None}
        # n_decoded is captured per token: the snapshot keeps mutating
        # while the batch rides the queue
        e["tokens"].append((token_id, logprob, s.n_decoded))
        if finish:
            timings = self._finish_timings(s, s.n_decoded,
                                           time.monotonic())
            e["finish"] = finish
            e["timings"] = timings
            self._finish_accounting(slot, s, finish, s.n_decoded,
                                    timings)
        return not extended

    def _flush_em_batch(self):
        """Hand the tick's accumulated token batch to the emitter as ONE
        queue item — per-slot FIFO order is the queue's FIFO order."""
        if self._em_batch:
            batch, self._em_batch = self._em_batch, {}
            self._emitter.push_batch(list(batch.values()))

    def _finish_timings(self, s: "_Slot", ndec: int, t_done: float) -> dict:
        """Final-event timings for an engine-detected finish (the
        emitter computes the same fields for the stops it detects
        itself)."""
        dt = t_done - s.t_first_token
        queue_wait_ms = max(0.0, (s.t_start - s.req.t_submit) * 1e3) \
            if s.req.t_submit else 0.0
        admit_to_first_ms = max(0.0, (s.t_first_token - s.t_start) * 1e3) \
            if s.t_first_token else 0.0
        return {
            "prefill_ms": s.t_prefill_ms,
            "queue_wait_ms": queue_wait_ms,
            "admit_to_first_ms": admit_to_first_ms,
            "reused_prompt_tokens": s.reused,
            "decode_tokens_per_s":
                (ndec - 1) / dt if dt > 0 and ndec > 1 else 0.0,
        }

    def _finish_accounting(self, slot: int, s: "_Slot", finish: str,
                           ndec: int, timings: dict):
        """Everything a finish does besides the stream puts (those
        belong to the emitter): TTFT decomposition, request span,
        slow-request log, goodput, completion event, prompt-cache save,
        slot release."""
        with self._decomp_lock:
            self._ttft_decomp.append(
                (timings["queue_wait_ms"], timings["admit_to_first_ms"],
                 s.t_prefill_ms))
        t_done = time.monotonic()
        if self.tracer.enabled and s.req.t_submit:
            self.tracer.record("request", f"slot{slot}",
                               s.req.t_submit, t_done,
                               rid=s.req.request_id,
                               args={"completion_tokens": ndec,
                                     "finish": finish})
        if self._slow_ms > 0:
            ttft_ms = timings["queue_wait_ms"] + timings["admit_to_first_ms"]
            e2e_ms = (t_done - s.req.t_submit) * 1e3 \
                if s.req.t_submit else 0.0
            if ttft_ms > self._slow_ms or e2e_ms > self._slow_ms:
                import json as _json
                import logging as _logging

                _logging.getLogger(__name__).warning(
                    "slow request %s: %s", s.req.request_id,
                    _json.dumps({
                        "threshold_ms": self._slow_ms,
                        "e2e_ms": round(e2e_ms, 1),
                        "ttft_ms": round(ttft_ms, 1),
                        "completion_tokens": ndec,
                        "spans": {k: (round(v, 1)
                                      if isinstance(v, float) else v)
                                  for k, v in timings.items()},
                    }, sort_keys=True))
        # goodput (ISSUE 8): ONLY clean finishes count — sheds, timeouts
        # and stall aborts never reach this branch
        self._goodput.add(ndec)
        self._slo_finish(s, ndec, t_done,
                         timings["queue_wait_ms"]
                         + timings["admit_to_first_ms"],
                         timings["queue_wait_ms"])
        EVENTS.emit("complete", rid=s.req.request_id, finish=finish,
                    completion_tokens=ndec,
                    e2e_ms=round((t_done - s.req.t_submit) * 1e3, 1)
                    if s.req.t_submit else None)
        self._save_prompt_cache(slot, s)
        self._release_slot(slot)

    def _make_emitter(self):
        from localai_tpu.engine.emitter import EmitterWorker

        def note(slot, snap, ndec, timings):
            with self._em_lock:
                self._em_notes.append(("stop", slot, snap, ndec, timings))
            self._wake.set()

        def note_abort(slot, snap):
            with self._em_lock:
                self._em_notes.append(("abort", slot, snap, 0, None))
            self._wake.set()

        return EmitterWorker(tracer=self.tracer, stream_event=StreamEvent,
                             merge_events=_merge_events, note_finish=note,
                             note_abort=note_abort)

    def _apply_emitter_notes(self):
        """Apply emitter-side finishes. ``stop`` notes are detected
        stop-sequence completions: the emitter has already truncated the
        text and closed the stream; the engine side releases the slot,
        pulls a racing context-shift re-prefill back out of the queue,
        and accounts the completion. ``abort`` notes are emitter-side
        item failures (e.g. a detokenizer exception) whose streams the
        emitter already failed — release only, no completion
        accounting. Tokens decoded past the
        note are discarded with the slot (same rule as any other
        in-flight invalidation)."""
        if not self._em_notes:
            return
        with self._em_lock:
            notes, self._em_notes = self._em_notes, []
        for kind, slot, snap, ndec, timings in notes:
            if self.slots[slot] is not snap:
                continue   # engine finished/aborted the slot first
            # a context shift may have queued this slot for re-prefill
            # right after the note-carrying token; the request is over
            try:
                self._prefill_queue.remove(slot)
            except ValueError:
                pass
            # in-flight bursts must not keep decoding for the dead slot
            for b in self._fifo:
                if isinstance(b, _Burst):
                    b.skip_slots.add(slot)
            if kind == "stop":
                self._finish_accounting(slot, snap, "stop", ndec,
                                        timings)
            else:
                self._release_slot(slot)
            self._process_fork_waiters(slot)

    def _check_emitter_wedge(self):
        """Watchdog coverage for a wedged EMITTER: if the worker has been
        stuck on one item longer than the dispatch stall budget (or died
        with work still queued), take over its queue, fail every affected
        stream directly, and build a fresh worker."""
        em = self._emitter
        stall_s = self.ecfg.dispatch_stall_ms / 1e3
        if stall_s <= 0:
            return
        t = em.t_item_start
        wedged = (t > 0 and time.monotonic() - t > stall_s) \
            or (not em.alive and em.qsize() > 0)
        if not wedged:
            return
        import logging

        logging.getLogger(__name__).error(
            "emitter wedged (> %d ms on one item); replacing worker",
            self.ecfg.dispatch_stall_ms)
        with self._lc_lock:
            self._lc["stalls"] += 1
        EVENTS.emit("emitter_wedge",
                    dispatch_stall_ms=self.ecfg.dispatch_stall_ms,
                    queued=em.qsize())
        # fail the streams of still-queued items (their tokens/finals
        # are lost with the worker) plus every still-active slot
        victims: dict = {}
        for it in em.takeover():
            if it[0] == "batch":
                for e in it[1]:
                    victims[id(e["snap"])] = e["snap"]
            else:
                victims[id(it[2])] = it[2]
        for i, s in enumerate(self.slots):
            if s is not None:
                victims[id(s)] = s
                self._release_slot(i)
                self._process_fork_waiters(i)
        for s in victims.values():
            s.req.out.put(StreamEvent(
                token_id=-1, text="", logprob=0.0, finish_reason="stop",
                error=(f"emitter wedged > {self.ecfg.dispatch_stall_ms} "
                       f"ms; request aborted"),
                error_kind="stall"))
            s.req.out.put(None)
        self._emitter = self._make_emitter()

    def _context_shift(self, slot: int, s: _Slot, token_id: int):
        """Cache full mid-generation: re-prefill the tail half of the logical
        context into the slot and keep generating (reference KV surgery:
        grpc-server.cpp:1832,1916-1927 — recomputed here; see module doc)."""
        history = self._cache_tokens[slot] + [token_id]
        keep = max(self.ecfg.max_context // 2, 1)
        new_ids = history[-keep:]
        if self._paged:
            # the shift re-prefills from row 0: retain the committed
            # full pages in the prefix cache (a parallel conversation
            # sharing this history can still splice them), then give the
            # table back and re-allocate lazily per chunk — never
            # rewrite a page another slot or the cache reads
            if s.win_off > 0:
                # windowed slot (ISSUE 16): sink-only retention + tail
                # offload — the compact table has no contiguous absolute
                # image for a full insert
                self._retire_window(slot, s)
            elif self._pcache is not None:
                n_ins = s.committed
                if self.ecfg.ga_n > 1:
                    # fully-compressed rows only (see _release_slot)
                    n_ins = min(n_ins, s.ga_blocks * self.ecfg.ga_w)
                self._pcache.insert(self._pool, slot,
                                    self._cache_tokens[slot][:n_ins])
            self._pool.release(slot, 0)
        s.phase = "prefill"
        s.pending = list(new_ids)
        s.written = 0
        s.cache_len = 0
        s.committed = 0
        s.win_off = 0
        s.chain_keys = []       # the token stream is re-based: new chain
        self._cache_tokens[slot] = list(new_ids)
        reused = 0
        if (self._paged and self._pcache is not None and s.mm_pos is None
                and self.ecfg.ga_n <= 1):
            # re-prefill reuse (ISSUE 16 satellite): the kept tail is the
            # SUFFIX of history this slot just retained/offloaded page by
            # page — but chain keys hash from the stream ROOT, so only a
            # kept window whose pages were retained under the SAME root
            # (e.g. a prior shift or a shared conversation prefix) can
            # splice. When it can, the shift's re-prefill shrinks to the
            # un-cached tail via the ordinary admission tiers, COW pages
            # and all, instead of recomputing the whole half-context.
            reused = self._paged_admission(slot, new_ids, 0,
                                           rid=s.req.request_id)
            s.win_off = self._adm_win_off
            s.pending = new_ids[reused + s.win_off:]
            s.written = reused
            s.reused = reused
        self._init_ga(slot, s, len(new_ids))
        if s.win_off:
            self.pos_offset[slot] = s.win_off
        self.active_dev[slot] = False
        self.lengths[slot] = 0
        # restart the penalty ring from the kept window
        self.ring, self.ring_pos = sampling.set_slot_ring(
            self.ring, self.ring_pos, slot, new_ids)
        self._prefill_queue.append(slot)
        # every in-flight burst dispatched before the shift sampled tokens
        # conditioned on the discarded context — drop this slot from them
        # (same invalidation rule as _rollback_grammar / self-extend)
        for b in self._fifo:
            if isinstance(b, _Burst):
                b.skip_slots.add(slot)

    def _retire_window(self, slot: int, s: "_Slot") -> int:
        """Shared windowed-slot retirement (ISSUE 16): the table holds
        sinks ++ tail window at COMPACT rows, so only the sink prefix is
        contiguous absolute truth the device tier may retain. The
        committed tail-window pages are offloaded under their ABSOLUTE
        chain keys first (with policy=demote the middle is already host-
        resident, so the whole chain survives for a future windowed
        re-admission), the sinks are retained, and the sink row count is
        returned for the caller's release/trim."""
        pool = self._pool
        pg = pool.page_size
        n_full = min(s.committed // pg, int(pool.owned[slot]))
        sink = min(self._win_sink, n_full)
        if self._hstore is not None and self._pcache is not None:
            base = s.win_off // pg
            keys = self._abs_chain_keys(slot, s, base + n_full)
            victims = []
            for t in range(sink, n_full):
                ap = base + t
                if ap >= len(keys) or self._hstore.contains(keys[ap]):
                    continue
                parent = keys[ap - 1] if ap > 0 else kvcache.PAGE_HASH_ROOT
                victims.append((keys[ap], parent, ap,
                                int(pool.ptab[slot, t])))
            if victims:
                self._dispatch_offload(victims)
        if self._pcache is not None and sink > 0:
            self._pcache.insert(pool, slot,
                                self._cache_tokens[slot][:sink * pg])
        return sink * pg

    def _release_slot(self, slot: int):
        # _cache_tokens is intentionally preserved (trimmed to rows whose KV
        # write actually executed) — the slot's rows stay valid and a future
        # request sharing a prefix reuses them
        s = self.slots[slot]
        if s is not None and s.win_off > 0 and self._paged:
            # snap-back window (ISSUE 16): compact bookkeeping no longer
            # maps 1:1 onto the absolute token history — retire via the
            # windowed path (offload tail, retain sinks only)
            sink_rows = self._retire_window(slot, s)
            self._pool.release(slot, sink_rows)
            self._cache_tokens[slot] = self._cache_tokens[slot][:sink_rows]
            self.slots[slot] = None
            self.active_dev[slot] = False
            self.lengths[slot] = 0
            return
        if s is not None:
            self._cache_tokens[slot] = self._cache_tokens[slot][:s.committed]
        if self._paged:
            # cross-release retention FIRST (while the slot's references
            # still pin the pages): committed full pages enter the
            # token-hash store and survive this slot's next tenant
            if self._pcache is not None:
                n_ins = len(self._cache_tokens[slot])
                if self.ecfg.ga_n > 1 and s is not None:
                    # only FULLY-COMPRESSED rows are stable under
                    # self-extend (later block completions never rotate
                    # them again) — the raw tail must not be retained
                    # under a token key that promises final-form rows
                    n_ins = min(n_ins, s.ga_blocks * self.ecfg.ga_w)
                self._pcache.insert(self._pool, slot,
                                    self._cache_tokens[slot][:n_ins])
                if self.kv_checkpoint and n_ins > 0:
                    # cluster mode (ISSUE 17): the finished chain also
                    # lands in the host tier at release, so a peer host
                    # can serve this prefix via the streaming transport
                    # even when the release-to-next-request gap is
                    # shorter than the watermark checkpoint cadence
                    self._offload_chain(self._cache_tokens[slot][:n_ins])
            # keep the retained prefix's pages in the table too (same
            # reuse story as _cache_tokens — the slot's own next request
            # reuses them for free); everything past returns to the pool
            self._pool.release(slot, len(self._cache_tokens[slot]))
        self.slots[slot] = None
        self.active_dev[slot] = False
        self.lengths[slot] = 0
