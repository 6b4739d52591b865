"""Engine replica pool: shared KV tiers, prefix-affinity routing, live
request migration (ISSUE 14).

The serving unit used to be ONE Engine per model, so one Python host
loop bounded every model's throughput no matter how much chip was left.
This module is the ROADMAP's multi-engine scale-out step 1+2: an
``EnginePool`` owns N Engine replicas of the same model (``engines=N``
on the options wire), all sharing

  * ONE ``HostPageStore`` (``SharedKV.host_store``) — per-replica
    device tiers, one host tier. The store's shared-mode mapping
    refcounts (kv_offload.py) guarantee an entry some replica's device
    tier still maps — or an in-flight migration is about to splice —
    is never budget-evicted from under a sibling.
  * ONE ``PoolPrefixIndex`` (prefix_cache.py) fed by each replica's
    PrefixPageCache membership callbacks: chain key -> {replica: depth}.

Routing (admission): a request goes to the replica holding the LONGEST
live/retained chain match for its prompt (prefix-affinity — the PR-2/3
chained block hashes make KV location-independent, so the match is
computed host-side from token ids alone); with no usable match it goes
to the least-loaded replica, where load = active slots + parked resumes
+ DRR-class-weighted queue pressure (a queue full of high-class work
presses harder on a normal-class arrival than a queue of low).

Live migration composes existing primitives, no new KV machinery:
pause on replica A (PR-10 preemption, ``park=False``), force-offload
the retained chain to the shared host tier (PR-3), adopt + resume as a
re-admission on replica B whose chain lookup splices the same pages
back. PR-10's resume ≡ fresh-re-admission contract makes the byte gate
well-defined: the migrated continuation equals a FRESH submission of
(prompt + tokens emitted so far) — the same contract the priority
bench gates, NOT bit-parity with an uninterrupted run (prefill-vs-
decode kernel numerics differ). Used for drain-free rebalancing when
one replica saturates, and for CRASH RECOVERY: when a replica's loop
dies (DejaVu's failure model), its queued, parked and in-flight
requests re-route to siblings and restore from the shared tier instead
of the client seeing an error (extends PR-7 in-engine recovery).

``engines=1`` never constructs a pool at all (backend/runner.py builds
a plain Engine), so single-engine behavior stays bit-for-bit.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Optional

from localai_tpu.engine import engine as eng
from localai_tpu.engine.prefix_cache import PoolPrefixIndex
from localai_tpu.engine.scheduler import (PRIORITY_RANK, ResumeEntry,
                                          parse_priority_weights)
from localai_tpu.services.eventlog import EVENTS

log = logging.getLogger(__name__)

# how many migration pin-sets to keep mapped before releasing the
# oldest (a pin protects a migrated chain from budget eviction until
# the target's restore has long since happened)
_MAX_PINS = 8


class _DrainAbort(Exception):
    """Internal: a scale-in drain couldn't place everything on siblings
    — the step is abandoned and the replica returns to service."""


class SharedKV:
    """The pool-scoped KV state every replica plugs into: one host-tier
    page store (created lazily by the first replica that wants one, so
    scope/page-size come from the real engine config) and one
    cross-replica prefix index. ``prefix_hooks(replica)`` returns the
    PrefixPageCache callbacks that keep both in sync with that
    replica's device tier."""

    def __init__(self):
        self._lock = threading.Lock()
        self.store = None            # kv_offload.HostPageStore | None
        self.store_path = ""
        self.index = PoolPrefixIndex()

    def host_store(self, scope: bytes, page_size: int, budget_mb: int,
                   store_path: str = ""):
        """The ONE shared HostPageStore (created on first call; loaded
        from ``store_path`` once — replicas never load or save it
        themselves)."""
        with self._lock:
            if self.store is None:
                from localai_tpu.engine.kv_offload import HostPageStore

                self.store = HostPageStore(scope, page_size, budget_mb)
                self.store_path = store_path
                if store_path:
                    n = self.store.load(store_path)
                    if n:
                        log.info("shared kv host store: reloaded %d pages"
                                 " from %s", n, store_path)
            else:
                assert self.store.scope == scope, \
                    "pool replicas must share one model scope"
                assert self.store.page_size == page_size
            return self.store

    def prefix_hooks(self, replica: int) -> dict:
        """Membership callbacks for replica's PrefixPageCache: keep the
        pool index AND the shared store's device-mapping refcounts in
        lockstep with the device tier. Called on that replica's engine
        loop thread; index/store methods lock internally."""

        def on_insert(key, depth, _r=replica):
            self.index.note_insert(_r, key, depth)
            if self.store is not None:
                self.store.map_key(key, _r)

        def on_remove(key, _r=replica):
            self.index.note_remove(_r, key)
            if self.store is not None:
                self.store.unmap_key(key, _r)

        def on_clear(_r=replica):
            self.index.clear_replica(_r)
            if self.store is not None:
                self.store.unmap_owner(_r)

        return {"on_insert": on_insert, "on_remove": on_remove,
                "on_clear": on_clear}

    def save(self) -> bool:
        """Persist the shared store ONCE (pool shutdown) — pool-scoped
        entries round-trip a single file, not one per replica."""
        if self.store is not None and self.store_path:
            return self.store.save(self.store_path)
        return False


class EnginePool:
    """N Engine replicas of one model behind prefix-affinity routing.

    Mirrors the Engine surface the gRPC servicer drives (submit /
    cancel / generate / generate_text / num_active / metrics /
    state_snapshot / trace_events / start / shutdown / tracer);
    anything else falls through to replica 0.
    """

    def __init__(self, engines: list, shared: SharedKV):
        assert engines, "EnginePool needs at least one replica"
        self._engines = list(engines)
        self._shared = shared
        self._lock = threading.Lock()
        self._dead = [False] * len(engines)
        # request routing memory: rid -> replica (bounded FIFO trim)
        self._where: dict = {}
        self._where_order: list = []
        # migration pins: (rid, [chain keys]) mapped under
        # ("migrate", rid) in the shared store; oldest released first
        self._pins: list = []
        self._migrations = {"rebalance": 0, "crash": 0}
        self.affinity_hits = 0
        self.affinity_misses = 0
        self._routed = 0
        w = self._engines[0].ecfg.priority_weights
        try:
            self._weights = parse_priority_weights(w)
        except ValueError:
            self._weights = (4, 2, 1)
        self._hk_stop = threading.Event()
        self._hk_thread: Optional[threading.Thread] = None
        # shared-store KV audit fold (ISSUE 15): the POOL scans the one
        # shared host tier on the housekeeping cadence — replicas only
        # scan stores they own, so shared violations count once
        self._t_kv_audit = time.monotonic()
        # --- dynamic resize / autoscaling (ISSUE 19) ---
        # build() stashes its ctor args so resize() can construct fresh
        # replicas; a pool assembled directly can't scale out.
        self._build_args: Optional[dict] = None
        self._precompile = False
        self._draining: set = set()   # replicas emptying toward retire
        self._retired: set = set()    # cleanly shut down (≠ crashed)
        self._resize_lock = threading.Lock()
        self._resize_thread: Optional[threading.Thread] = None
        self.target_replicas = len(engines)
        self._policy = None           # autoscale.AutoscalePolicy | None
        # admission-limit co-scaling (ISSUE 20): max_queued_requests is
        # a PER-REPLICA knob, so the pool's effective admission budget
        # is width-proportional — resize() rescales each live replica's
        # maxq_effective against the CONFIGURED width, so a scaled-in
        # pool sheds at the narrower width's limit instead of promising
        # the full fleet's queue depth
        self._configured_width = len(engines)
        self._maxq_base = self._engines[0].ecfg.max_queued_requests

    # ---------- construction ----------

    @classmethod
    def build(cls, model_cfg, params, tokenizer, engine_cfg=None,
              engines: int = 2, eos_token_ids=None, mesh=None,
              param_shardings=None, draft=None, family=None):
        """Construct N replicas around one SharedKV. Weights (params)
        are shared device buffers — replicas add slots and host loops,
        not model memory. Requires the preemptive scheduler: pause/
        resume IS the migration and crash-recovery primitive."""
        ecfg = engine_cfg or eng.EngineConfig()
        if (engines > 1 or ecfg.autoscale) and not ecfg.preempt:
            raise ValueError("engines>1/autoscale=1 requires preempt=1 "
                             "(pause/resume is the migration primitive)")
        shared = SharedKV()
        replicas = [
            eng.Engine(model_cfg, params, tokenizer, ecfg,
                       eos_token_ids=eos_token_ids, mesh=mesh,
                       param_shardings=param_shardings, draft=draft,
                       family=family, replica_id=i, shared_kv=shared)
            for i in range(max(1, int(engines)))]
        pool = cls(replicas, shared)
        # resize() rebuilds replicas from these; params are the SAME
        # shared device buffers, so a scale-out costs slots + a host
        # loop, never a weight load (the weight win lives in
        # weights.stream_llama_params on the gallery-swap path)
        pool._build_args = dict(
            model_cfg=model_cfg, params=params, tokenizer=tokenizer,
            ecfg=ecfg, eos_token_ids=eos_token_ids, mesh=mesh,
            param_shardings=param_shardings, draft=draft, family=family)
        return pool

    # ---------- lifecycle ----------

    def start(self, precompile: bool = False):
        self._precompile = precompile
        for e in self._engines:
            e.start(precompile=precompile)
        ecfg = self._engines[0].ecfg
        if ecfg.autoscale:
            # autoscale=0 (default) constructs NOTHING here: no policy
            # object, no extra thread — bit-for-bit the static pool
            from localai_tpu.engine.autoscale import AutoscalePolicy

            dwell = max(0.05, ecfg.autoscale_dwell_ms / 1000.0)
            self._policy = AutoscalePolicy(
                min_replicas=ecfg.autoscale_min,
                max_replicas=(ecfg.autoscale_max
                              or 2 * len(self._engines)),
                burn_out=ecfg.autoscale_burn_out,
                burn_in=ecfg.autoscale_burn_in,
                dwell_s=dwell,
                cooldown_s=max(dwell, ecfg.autoscale_cooldown_ms / 1000.0),
                idle_in_s=max(0.2, dwell * 0.75),
                flight=self._engines[0]._flight)
        self._hk_thread = threading.Thread(
            target=self._housekeeping, name="engine-pool", daemon=True)
        self._hk_thread.start()

    def shutdown(self):
        self._hk_stop.set()
        if self._hk_thread is not None:
            self._hk_thread.join(timeout=5)
        if self._resize_thread is not None:
            self._resize_thread.join(timeout=15)
        for i, e in enumerate(self._engines):
            if i in self._retired:
                continue    # scale-in already shut it down cleanly
            try:
                e.shutdown()
            except Exception:
                log.exception("replica %d shutdown failed", e.replica_id)
        # release any leftover migration pins, then persist ONCE
        with self._lock:
            pins, self._pins = self._pins, []
        for rid, keys in pins:
            self._unpin(rid, keys)
        self._shared.save()

    # ---------- passthroughs the servicer touches ----------

    @property
    def tracer(self):
        return self._engines[0].tracer

    @property
    def num_active(self) -> int:
        return sum(e.num_active for e in self._engines)

    def __getattr__(self, name):
        # anything not pool-aware (cfg, ecfg, tokenizer, eos_ids, ...)
        # answers from replica 0; private names never delegate
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._engines[0], name)

    def generate(self, req):
        out = self.submit(req)
        while True:
            ev = out.get()
            if ev is None:
                return
            yield ev

    def generate_text(self, req):
        events = list(self.generate(req))
        return "".join(e.text for e in events), events

    def cancel(self, request_id: str):
        i = self._where.get(request_id)
        if i is not None:
            self._engines[i].cancel(request_id)
        else:
            for e in self._alive_engines():
                e.cancel(request_id)

    # ---------- routing ----------

    def _alive(self, i: int) -> bool:
        return not self._dead[i]

    def _alive_engines(self):
        return [e for i, e in enumerate(self._engines) if not self._dead[i]]

    def _routable(self, i: int) -> bool:
        """Eligible for NEW work: alive and not draining toward a
        scale-in retire (a draining replica still finishes/migrates what
        it has — it just stops being a routing target)."""
        return not self._dead[i] and i not in self._draining

    def _routable_idx(self) -> list:
        return [i for i in range(len(self._engines)) if self._routable(i)]

    def _load(self, i: int, rank: int) -> float:
        """Replica load as seen by a class-``rank`` arrival: active
        slots + parked resumes + queue depth weighted by DRR class
        pressure (queued work of heavier classes presses harder)."""
        e = self._engines[i]
        w = self._weights
        with e._queue.mutex:
            qranks = [PRIORITY_RANK.get(r.priority, 1)
                      for r in e._queue.queue]
        wn = w[rank] if 0 <= rank < len(w) else 1
        pressure = sum(w[q] if 0 <= q < len(w) else 1
                       for q in qranks) / max(1, wn)
        parked = e._sched.resume_depth if e._sched is not None else 0
        return e.num_active + parked + pressure

    def _route(self, req) -> int:
        """Prefix-affinity first, least-loaded otherwise."""
        alive = self._routable_idx()
        if not alive:
            raise RuntimeError("engine pool: no live replicas")
        rank = PRIORITY_RANK.get(getattr(req, "priority", None), 1)
        if len(alive) == 1:
            self._routed += 1
            return alive[0]
        # longest live/retained chain match among live replicas
        pc = self._engines[alive[0]]._pcache
        best_i, best_depth = None, 0
        if pc is not None and getattr(req, "prompt_ids", None):
            keys = list(pc.chain_keys(req.prompt_ids))
            if keys:
                depths = self._shared.index.match_depths(keys)
                for i in alive:
                    d = depths.get(i, 0)
                    if d > best_depth or (d == best_depth and d > 0
                                          and best_i is not None
                                          and self._load(i, rank)
                                          < self._load(best_i, rank)):
                        best_i, best_depth = i, d
        self._routed += 1
        if best_i is not None and best_depth > 0:
            self.affinity_hits += 1
            return best_i
        self.affinity_misses += 1
        return min(alive, key=lambda i: (self._load(i, rank), i))

    def _note_where(self, rid: str, replica: int):
        with self._lock:
            if rid not in self._where:
                self._where_order.append(rid)
            self._where[rid] = replica
            while len(self._where_order) > 4096:
                old = self._where_order.pop(0)
                self._where.pop(old, None)

    def where(self, rid: str) -> Optional[int]:
        return self._where.get(rid)

    def submit(self, req) -> "queue.Queue":
        r = self._route(req)
        self._note_where(req.request_id, r)
        return self._engines[r].submit(req)

    # ---------- live migration ----------

    def _pin(self, rid: str, keys: list):
        """Hold migrated chain keys mapped in the shared store (owner
        ("migrate", rid)) so budget eviction can't race the target's
        restore; bounded — the oldest pin-set releases past _MAX_PINS."""
        if not keys:
            return
        drop = []
        with self._lock:
            self._pins.append((rid, keys))
            while len(self._pins) > _MAX_PINS:
                drop.append(self._pins.pop(0))
        for old_rid, old_keys in drop:
            self._unpin(old_rid, old_keys)

    def _unpin(self, rid: str, keys: list):
        store = self._shared.store
        if store is None:
            return
        owner = ("migrate", rid)
        for k in keys:
            store.unmap_key(k, owner)

    def _await_offload(self, keys: list, timeout_s: float = 2.5) -> bool:
        """Bounded wait for the chain tail to land in the shared store
        (offload puts are async through the source's sync worker). A
        timeout is not an error — the target re-prefills the identical
        history, still byte-exact, just slower."""
        store = self._shared.store
        if store is None or not keys:
            return False
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if store.contains(keys[-1]):
                return True
            time.sleep(0.005)
        return store.contains(keys[-1])

    def migrate(self, request_id: str, target: Optional[int] = None,
                reason: str = "rebalance", timeout_s: float = 10.0) -> bool:
        """Live-migrate one request to ``target`` (default: least-loaded
        other live replica). Pause on the source at its next tick top,
        force-offload the retained chain to the shared host tier, adopt
        on the target whose chain lookup splices the pages back. The
        client stream never closes — tokens continue from the target,
        byte-identical to a fresh re-admission of (prompt + emitted)."""
        src = self._where.get(request_id)
        if src is None or self._dead[src]:
            return False
        cands = [i for i in self._routable_idx() if i != src]
        if not cands:
            return False
        done = threading.Event()
        box: dict = {}

        def handoff(payload):
            box["p"] = payload
            done.set()

        self._engines[src].request_migration(request_id, handoff)
        if not done.wait(timeout_s):
            return False
        payload = box.get("p")
        if payload is None:
            return False
        kind = payload[0]
        rank = 1
        if target is None:
            target = min(cands, key=lambda i: (self._load(i, rank), i))
        if kind == "fresh":
            req = payload[1]
            self._note_where(request_id, target)
            self._engines[target].submit(req)
        else:
            entry, keys = payload[1], payload[2]
            self._pin(request_id, keys)
            self._await_offload(keys)
            if not self._engines[target].adopt_resume(entry):
                # target can't adopt (no scheduler): re-park at home
                self._engines[src].adopt_resume(entry)
                return False
            self._note_where(request_id, target)
        aud = self._engines[target]._kv_audit
        if aud is not None:
            aud.ledger.record("migrate", slot=(src, target), rid=request_id)
        self._migrations[reason] = self._migrations.get(reason, 0) + 1
        EVENTS.emit("migrate", rid=request_id, src=src, dst=target,
                    reason=reason, kind=kind,
                    n_decoded=(payload[1].n_decoded
                               if kind == "resume" else 0))
        return True

    # ---------- crash recovery ----------

    def _fail_stream(self, req, why: str):
        req.out.put(eng.StreamEvent(
            token_id=-1, text="", logprob=0.0, finish_reason="stop",
            error=why, error_kind="replica_down"))
        req.out.put(None)

    def _adopt_on_sibling(self, rid: str, entry: ResumeEntry, src: int,
                          reason: str = "crash") -> bool:
        cands = [i for i in self._routable_idx() if i != src]
        if not cands:
            return False
        rank = PRIORITY_RANK.get(entry.priority, 1)
        target = min(cands, key=lambda i: (self._load(i, rank), i))
        tgt = self._engines[target]
        if tgt._pcache is not None:
            keys = list(tgt._pcache.chain_keys(entry.ids))
            self._pin(rid, keys)
        if not tgt.adopt_resume(entry):
            return False
        if tgt._kv_audit is not None:
            tgt._kv_audit.ledger.record("adopt", slot=(src, target),
                                        rid=rid)
        self._note_where(rid, target)
        self._migrations[reason] = self._migrations.get(reason, 0) + 1
        EVENTS.emit("migrate", rid=rid, src=src, dst=target,
                    reason=reason, kind="resume",
                    n_decoded=entry.n_decoded)
        return True

    def _recover_replica(self, i: int):
        """A replica's loop thread died without shutdown (crashed host
        analogue). Its device tier is lost; everything it was serving
        re-routes to siblings and restores from the shared host tier —
        warm chains splice back, cold ones re-prefill the identical
        history (DejaVu: crash recovery from streamed cache)."""
        e = self._engines[i]
        self._dead[i] = True
        EVENTS.emit("replica_down", replica=i,
                    slots_in_flight=e.num_active,
                    queued=e._queue.qsize(),
                    parked=(e._sched.resume_depth
                            if e._sched is not None else 0))
        log.warning("engine pool: replica %d loop died; recovering", i)
        # settle client streams + detok state: the emitter owns both
        e._emitter.drain(2.0)
        # its device pages are gone: forget them pool-wide
        self._shared.index.clear_replica(i)
        if self._shared.store is not None:
            self._shared.store.unmap_owner(i)
        recovered = failed = 0
        # in-flight slots -> ResumeEntries adopted by siblings
        for slot, s in enumerate(e.slots):
            if s is None:
                continue
            e.slots[slot] = None
            rid = s.req.request_id
            ok = False
            if e._sched is not None and e._preempt_eligible(slot, s):
                hist = list(e._cache_tokens[slot])
                if len(hist) < s.prompt_len:
                    hist = list(s.req.prompt_ids) + list(s.generated)
                entry = ResumeEntry(
                    req=s.req, ids=hist, priority=s.req.priority,
                    generated=list(s.generated), n_decoded=s.n_decoded,
                    prompt_len=s.prompt_len, detok=s.detok,
                    held_text=s.held_text, t_start=s.t_start,
                    t_first_token=s.t_first_token or None,
                    t_prefill_ms=s.t_prefill_ms, mu=float(e.mu[slot]),
                    preempt_count=s.preempts)
                ok = self._adopt_on_sibling(rid, entry, src=i)
            if ok:
                recovered += 1
            else:
                failed += 1
                self._fail_stream(s.req, f"replica {i} died; request not "
                                         f"recoverable on a sibling")
        # parked resumes migrate wholesale
        if e._sched is not None:
            for entry in e._sched.drain_parked():
                if self._adopt_on_sibling(entry.req.request_id, entry,
                                          src=i):
                    recovered += 1
                else:
                    failed += 1
                    self._fail_stream(entry.req,
                                      f"replica {i} died; request not "
                                      f"recoverable on a sibling")
        # queued requests re-route (nothing computed: plain resubmit)
        while True:
            try:
                r = e._queue.get_nowait()
            except queue.Empty:
                break
            try:
                tgt = self._route(r)
                self._note_where(r.request_id, tgt)
                self._engines[tgt].submit(r)
                recovered += 1
            except Exception:
                failed += 1
                self._fail_stream(r, f"replica {i} died; no live sibling")
        EVENTS.emit("replica_recovered", replica=i, recovered=recovered,
                    failed=failed)
        log.warning("engine pool: replica %d recovery done "
                    "(recovered=%d failed=%d)", i, recovered, failed)

    # ---------- housekeeping ----------

    def _housekeeping(self):
        """Health checks + drain-free queue rebalancing + the autoscale
        policy tick, ~10 Hz."""
        while not self._hk_stop.wait(0.1):
            try:
                for i, e in enumerate(self._engines):
                    if self._dead[i] or e._thread is None:
                        continue
                    if not e.loop_alive and not e._stop:
                        self._recover_replica(i)
                self._rebalance_queued()
                self._autoscale_tick()
                t0 = time.monotonic()
                if t0 - self._t_kv_audit > 0.5:
                    self._t_kv_audit = t0
                    self._audit_shared()
            except Exception:
                log.exception("engine pool housekeeping failed")

    # ---------- autoscaling (ISSUE 19) ----------

    def autoscale_signals(self):
        """Policy-input snapshot over ROUTABLE replicas. Gathered on the
        housekeeping thread from plain attribute reads — no engine locks
        beyond what qsize()/SLO snapshots already take."""
        from localai_tpu.services.sysobs import AutoscaleSignals

        engines = [self._engines[i] for i in self._routable_idx()]
        queued = sum(e._queue.qsize() for e in engines)
        slots = sum(len(e.slots) for e in engines)
        active = sum(e.num_active for e in engines)
        burn = 0.0
        free = 1.0
        pre = 0.0
        for e in engines:
            if e._slo is not None and e._slo.enabled:
                burn = max(burn, e._slo.max_burn())
            if e._paged:
                free = min(free, e._pool.free_pages
                           / max(1, e._pool.num_pages))
            pre += getattr(e, "_preempt_rate_ewma", 0.0)
        # effective (co-scaled) admission budget, not the static knob:
        # a scaled-in pool's queue reads proportionally fuller, so the
        # scale-out trigger fires at the same relative pressure
        cap = sum(e.maxq_effective for e in engines)
        return AutoscaleSignals(
            replicas=len(engines), queued=queued,
            queue_frac=(queued / cap) if cap > 0 else 0.0,
            busy_frac=(active / slots) if slots else 0.0,
            burn_5m=burn, free_page_frac=free,
            preempt_rate_per_min=pre)

    def _autoscale_tick(self):
        """Feed the policy; execute a returned target on a worker thread
        so a multi-second spin-up/drain never blocks health checks. At
        most one resize in flight — the policy is not sampled while one
        runs (its signals would be mid-transition noise)."""
        if self._policy is None:
            return
        if self._resize_thread is not None and \
                self._resize_thread.is_alive():
            return
        tgt = self._policy.sample(self.autoscale_signals())
        if tgt is None or tgt == len(self._routable_idx()):
            return
        self.target_replicas = tgt
        self._resize_thread = threading.Thread(
            target=self._resize_safely, args=(tgt,),
            name="pool-resize", daemon=True)
        self._resize_thread.start()

    def _resize_safely(self, n: int):
        try:
            self.resize(n, reason="autoscale")
        except Exception:
            log.exception("engine pool: autoscale resize to %d failed", n)

    def resize(self, n: int, reason: str = "manual") -> int:
        """Bring the ROUTABLE replica count to ``n`` one step at a time;
        returns the resulting count. Scale-out appends a freshly started
        replica (shared device weights — no load; shared host KV tier —
        it splices warm chains from the first affinity hit). Scale-in
        drains the highest-index replica through the existing migrate
        path and retires it; a drain that cannot complete aborts the
        step and the replica returns to service (never strands work)."""
        with self._resize_lock:
            n = max(1, int(n))
            n0 = len(self._routable_idx())
            while True:
                cur = len(self._routable_idx())
                if cur == n:
                    break
                if cur < n:
                    self._scale_out(reason)
                else:
                    if not self._scale_in(reason):
                        break
            self.target_replicas = n
            got = len(self._routable_idx())
            if got != n0:
                for i in self._routable_idx():
                    # re-anchor the preemption-EWMA reserve to the new
                    # replica count (ISSUE 19 satellite)
                    self._engines[i].note_pool_resize(n0, got)
                self._rescale_admission(got)
            return got

    def _rescale_admission(self, width: int):
        """Admission-limit co-scaling (ISSUE 20): each live replica's
        effective max_queued_requests scales with live width over
        CONFIGURED width, so a scaled-in pool sheds at the narrower
        width's limit (half the replicas -> half the queue promise per
        survivor) instead of buffering the full fleet's depth behind
        fewer engines. At the configured width this is exactly the
        configured knob — bit-for-bit the static-pool behavior."""
        if self._maxq_base <= 0:
            return                  # unbounded stays unbounded
        eff = max(1, round(self._maxq_base * width
                           / max(1, self._configured_width)))
        for i in self._routable_idx():
            self._engines[i].maxq_effective = eff
        EVENTS.emit("queue_limit_rescaled", width=width,
                    configured=self._configured_width,
                    per_replica=eff, pool=eff * max(1, width))

    def _scale_out(self, reason: str):
        if self._build_args is None:
            raise RuntimeError("pool not built via EnginePool.build(); "
                               "resize unavailable")
        a = self._build_args
        rid = len(self._engines)
        t0 = time.monotonic()
        e = eng.Engine(a["model_cfg"], a["params"], a["tokenizer"],
                       a["ecfg"], eos_token_ids=a["eos_token_ids"],
                       mesh=a["mesh"],
                       param_shardings=a["param_shardings"],
                       draft=a["draft"], family=a["family"],
                       replica_id=rid, shared_kv=self._shared)
        # fully started BEFORE it becomes visible to routing: _dead grows
        # first so len(_engines) never outruns it for lock-free readers
        e.start(precompile=self._precompile)
        with self._lock:
            self._dead.append(False)
            self._engines.append(e)
        ms = (time.monotonic() - t0) * 1000.0
        EVENTS.emit("scale_out", replica=rid, reason=reason,
                    spinup_ms=round(ms, 1),
                    replicas=len(self._routable_idx()))
        log.info("engine pool: scale-out -> replica %d (%s, %.0f ms)",
                 rid, reason, ms)

    def _scale_in(self, reason: str, timeout_s: float = 10.0) -> bool:
        routable = self._routable_idx()
        if len(routable) <= 1:
            return False
        i = routable[-1]
        e = self._engines[i]
        self._draining.add(i)
        try:
            # 1) queued work: nothing computed — plain re-route
            while True:
                try:
                    r = e._queue.get_nowait()
                except queue.Empty:
                    break
                tgt = self._route(r)
                self._note_where(r.request_id, tgt)
                self._engines[tgt].submit(r)
            # 2) parked resumes: adopt on siblings (splice from shared)
            if e._sched is not None:
                parked = e._sched.drain_parked()
                for k, entry in enumerate(parked):
                    if not self._adopt_on_sibling(
                            entry.req.request_id, entry, src=i,
                            reason="scale_in"):
                        for rest in parked[k:]:
                            e._sched.adopt(rest)   # re-park, undrained
                        raise _DrainAbort()
            # 3) in-flight slots: live migration, byte-gate preserved
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                rids = [s.req.request_id for s in e.slots if s is not None]
                if not rids:
                    break
                for r_id in rids:
                    self.migrate(r_id, reason="scale_in")
                time.sleep(0.02)
            if any(s is not None for s in e.slots):
                raise _DrainAbort()
        except _DrainAbort:
            self._draining.discard(i)
            log.warning("engine pool: scale-in of replica %d aborted "
                        "(drain incomplete); replica stays in service", i)
            return False
        # empty: retire cleanly. Its device tier goes away with it.
        e.shutdown()
        self._shared.index.clear_replica(i)
        if self._shared.store is not None:
            self._shared.store.unmap_owner(i)
        with self._lock:
            self._dead[i] = True
            self._retired.add(i)
        self._draining.discard(i)
        EVENTS.emit("scale_in", replica=i, reason=reason,
                    replicas=len(self._routable_idx()))
        log.info("engine pool: scale-in retired replica %d (%s)",
                 i, reason)
        return True

    def _audit_shared(self):
        """Invariant scan of the SHARED host tier (ISSUE 15): byte
        accounting vs summed entry sizes, parent/child map consistency,
        sampled CRC of retained entries. Reports through the auditor the
        first replica attached to the store (its ledger already records
        the store-level transitions), so counters, events and flight
        dumps ride the same path as device-tier violations."""
        store = self._shared.store
        aud = store.audit if store is not None else None
        if aud is not None:
            aud.scan_shared(store)

    def _rebalance_queued(self):
        """When one replica has work QUEUED behind full slots while a
        sibling sits with a free slot and an empty queue, re-route one
        queued request (nothing computed yet — this is the zero-risk
        half of drain-free rebalancing; active-slot migration stays
        explicit via migrate())."""
        alive = self._routable_idx()
        if len(alive) < 2:
            return
        for i in alive:
            src = self._engines[i]
            if src._queue.qsize() == 0 or src._free_count() > 0:
                continue
            idle = [j for j in alive
                    if j != i and self._engines[j]._free_count() > 0
                    and self._engines[j]._queue.qsize() == 0]
            if not idle:
                continue
            with src._queue.mutex:
                r = src._queue.queue[0] if src._queue.queue else None
                if r is not None:
                    src._queue.queue.remove(r)
            if r is None:
                continue
            rank = PRIORITY_RANK.get(r.priority, 1)
            j = min(idle, key=lambda x: (self._load(x, rank), x))
            self._note_where(r.request_id, j)
            self._engines[j].submit(r)
            self._migrations["rebalance"] += 1
            EVENTS.emit("migrate", rid=r.request_id, src=i, dst=j,
                        reason="rebalance", kind="fresh")

    # ---------- observability ----------

    def metrics(self) -> dict:
        ms = [e.metrics() for e in self._engines]
        out = dict(ms[0])
        for k in ("slots_total", "slots_active", "queued",
                  "total_tokens_generated", "tokens_per_second_active",
                  "prompt_tokens_reused"):
            out[k] = sum(m.get(k) or 0 for m in ms)
        out["uptime_s"] = max(m.get("uptime_s", 0) for m in ms)
        out["engine_replicas"] = len(self._engines)
        out["engine_replicas_target"] = self.target_replicas
        # effective (co-scaled) pool admission budget ->
        # localai_engine_queue_limit (ISSUE 20)
        out["queue_limit"] = sum(self._engines[i].maxq_effective
                                 for i in self._routable_idx())
        out["replicas"] = [{
            "replica": i,
            "alive": not self._dead[i],
            "draining": i in self._draining,
            "queued": m.get("queued", 0) if not self._dead[i] else 0,
            "slots_in_flight": (m.get("slots_active", 0)
                                if not self._dead[i] else 0),
            "slots_total": m.get("slots_total", 0),
            "resume_depth": (m.get("scheduler") or {}).get(
                "resume_depth", 0),
            "resume_reserve_pages": (m.get("scheduler") or {}).get(
                "resume_reserve_pages", 0),
            "tokens": m.get("total_tokens_generated", 0),
        } for i, m in enumerate(ms)]
        out["pool"] = {
            "replicas_alive": sum(1 for d in self._dead if not d),
            "replicas_target": self.target_replicas,
            "affinity_hits": self.affinity_hits,
            "affinity_misses": self.affinity_misses,
            "routed": self._routed,
            "migrations": dict(self._migrations),
            "index_keys": len(self._shared.index),
        }
        if self._policy is not None:
            out["pool"]["autoscale"] = self._policy.snapshot()
        # lifecycle auditor (ISSUE 15): counters summed pool-wide (the
        # shared-store scans report through the attached auditor, so
        # they're inside one replica's snapshot already)
        kas = [m.get("kv_audit") for m in ms if m.get("kv_audit")]
        if kas:
            out["kv_audit"] = {
                "mode": kas[0].get("mode", "on"),
                "checks": sum(k.get("checks", 0) for k in kas),
                "violations": sum(k.get("violations", 0) for k in kas),
                "leaked_pages": sum(k.get("leaked_pages", 0) for k in kas),
                "ledger_events": sum(k.get("ledger_events", 0)
                                     for k in kas),
                "last_violations": [v for k in kas
                                    for v in k.get("last_violations",
                                                   [])][-16:],
            }
        return out

    def kv_audit_sweep(self, drained: bool = False) -> dict:
        """Pool-wide on-demand audit: shared host tier first (counters
        land on the attached replica's auditor), then every LIVE
        replica's full pass. Dead replicas are skipped — their device
        mirrors froze wherever the crash left them and their pages were
        recovered onto siblings, which the siblings' scans cover."""
        store = self._shared.store
        aud = store.audit if store is not None else None
        if aud is not None:
            aud.scan_shared(store)
        out = {"mode": "off", "checks": 0, "violations": 0,
               "leaked_pages": 0, "ledger_events": 0}
        for i, e in enumerate(self._engines):
            if self._dead[i]:
                continue
            snap = e.kv_audit_sweep(drained=drained)
            if snap.get("mode") != "off":
                out["mode"] = snap["mode"]
                for k in ("checks", "violations", "leaked_pages",
                          "ledger_events"):
                    out[k] += snap.get(k, 0)
        return out

    def kv_debug(self) -> dict:
        """/debug/kv merged view across replicas + the shared host tier
        (ISSUE 15)."""
        out = {
            "engine_replicas": len(self._engines),
            "engine_replicas_target": self.target_replicas,
            "replicas": [e.kv_debug() for e in self._engines],
            "pool_index_keys": len(self._shared.index),
        }
        store = self._shared.store
        if store is not None:
            out["shared_host"] = store.stats()
        return out

    def state_snapshot(self) -> dict:
        replicas = [e.state_snapshot() for e in self._engines]
        out = {
            # replicas share one process, so one set of devices
            **{k: replicas[0][k] for k in ("platform", "device_kind",
                                           "device_count", "device_mem",
                                           "compiles_process", "gc_full",
                                           "host_memory")},
            "engine_replicas": len(self._engines),
            "pool": {
                "replicas_alive": sum(1 for d in self._dead if not d),
                "affinity_hits": self.affinity_hits,
                "migrations": dict(self._migrations),
            },
            "replicas": replicas,
        }
        # target-vs-actual + last decision for /debug/state and /readyz
        # (ISSUE 19) — present whenever pooled so operators see the loop
        # (or that it's off)
        out["autoscale"] = {
            "enabled": self._policy is not None,
            "target": self.target_replicas,
            "replicas_alive": sum(1 for d in self._dead if not d),
            "replicas_routable": len(self._routable_idx()),
            "last_decision": (self._policy.last_decision
                              if self._policy is not None else None),
        }
        return out

    def trace_events(self) -> dict:
        out = self._engines[0].trace_events()
        evs = out.get("traceEvents")
        if isinstance(evs, list):
            for e in self._engines[1:]:
                more = e.trace_events().get("traceEvents")
                if isinstance(more, list):
                    evs.extend(more)
        return out
