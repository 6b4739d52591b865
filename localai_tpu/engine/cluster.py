"""Cluster router over N engine-pool worker hosts (ISSUE 17).

The serving unit grows one more level: a ``ClusterRouter`` fronts N
``ClusterHost``s, each a PR-14 ``EnginePool`` (replicas + one shared
host tier) made NETWORK-ADDRESSABLE by a ``KVWireServer``
(services/kv_wire.py) and peer-aware by a ``FederatedKV``
(engine/kv_stream.py). The PR-2/3 chained block hashes already make KV
location-independent, so everything the pool does across replicas —
prefix-affinity routing, live handoff, crash recovery — lifts across
hosts with the wire as the only new mechanism:

* ROUTING: the router polls each host's chain-key DIGEST (the pool
  prefix index + host-tier membership) over the wire and routes each
  request to the host holding the longest prefix match; peer-held
  chains a probe misses still stream in at admission through the
  federated tier, so a wrong guess costs a fetch, not a re-prefill.

* DISAGGREGATION (DejaVu / Splitwise): hosts carry a ``role`` —
  ``prefill`` hosts run admission + packed prefill only and retire each
  chain to the transport after its first token; the router hands the
  ResumeEntry to a ``decode`` host which pre-fetches the streamed chain
  and splices it. Decode ITL never queues behind a prefill wave.

* CRASH RECOVERY: a host whose engine loops die (accelerator/host loop
  lost; the wire server thread keeps serving the surviving host tier —
  loop death is not store death) is harvested exactly like a dead pool
  replica, one level up: in-flight slots and parked resumes re-adopt on
  sibling hosts whose federated tier streams the warm chains over; the
  client stream never closes (PR-10's resume ≡ fresh-re-admission
  contract makes the continuation byte-identical to re-submitting
  prompt + emitted).

* AUDIT (ISSUE 15, lifted cluster-wide): chain entries in flight on the
  wire are DECLARED EXTRAS, never leaks — ``kv_audit_sweep`` folds
  every host's sweep and checks all transports are quiesced.

* PROCESS MODE (ISSUE 20): hosts may run as real OS processes behind
  the control plane (services/cluster_rpc.py). The router drives a
  ``RemoteHostHandle`` through the exact same facade as an in-process
  ``ClusterHost`` (submit / cancel / chain_keys / metrics_snapshot /
  kv_audit_sweep / load / alive) — it is agnostic to whether a host is
  a thread or a PID. Remote liveness comes from a phi-accrual heartbeat
  detector: SUSPECT hosts (slow, answering late) are DE-PREFERRED in
  routing and skipped as KV-streaming targets but keep their streams;
  DEAD hosts (silent past ``cluster_dead_ms``, or the process exited)
  trigger recovery — each lost stream re-admits (prompt + delivered
  tokens) on a sibling, byte-identical by the PR-10 contract.

``cluster=off`` (the default) never constructs any of this — the
single-host PR-16 path is untouched, bit-for-bit. ``cluster_mode=
inproc`` (the default) builds only in-process hosts: no heartbeats, no
RPC, bit-for-bit PR-17.
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from typing import Optional

from localai_tpu.engine import engine as eng
from localai_tpu.engine.kv_stream import FederatedKV, KVStreamClient
from localai_tpu.engine.pool import EnginePool
from localai_tpu.engine.scheduler import PRIORITY_RANK, ResumeEntry
from localai_tpu.services.cluster_rpc import FailureDetector
from localai_tpu.services.eventlog import EVENTS
from localai_tpu.services.faults import FAULTS
from localai_tpu.services.kv_wire import KVWireServer, WireError

log = logging.getLogger(__name__)

# how many recovery/disagg chain pins to keep mapped before releasing
# the oldest (same bound and rationale as pool._MAX_PINS)
_MAX_PINS = 16
# digest poll cadence: affinity data may be this stale; staleness costs
# a federated fetch at admission, never correctness
_DIGEST_PERIOD_S = 0.25


class ClusterHost:
    """One worker host: an EnginePool + its shared KV tiers, serving its
    host tier to peers over the wire and consulting peers on misses.

    ``role``: ``both`` (default — a full host), ``prefill`` (admission +
    packed prefill only; finished prefills retire to the transport) or
    ``decode`` (receives disagg handoffs; the router keeps fresh
    arrivals away when a prefill host is alive)."""

    remote = False

    def __init__(self, host_id: int, pool: EnginePool, role: str = "both",
                 bind: str = "127.0.0.1"):
        assert role in ("both", "prefill", "decode"), role
        self.host_id = int(host_id)
        self.pool = pool
        self.role = role
        self._bind = bind
        self.server: Optional[KVWireServer] = None
        self.fed: Optional[FederatedKV] = None
        self.address = ""
        self.killed = False
        # host-scoped chaos identity: in-process hosts share the global
        # FAULTS table, so replica{N}_die would collide across hosts —
        # every engine loop on this host consumes one firing of this
        # name instead (kill() arms count=len(engines))
        self._die_fault = f"cluster{self.host_id}_die"
        for e in self.pool._engines:
            e._die_fault = self._die_fault

    # ---------- construction ----------

    @classmethod
    def build(cls, model_cfg, params, tokenizer, engine_cfg=None,
              host_id: int = 0, engines: int = 1, role: str = "both",
              bind: str = "127.0.0.1", **kw):
        """One host = one EnginePool with a role-annotated config.
        Requires the preemptive scheduler (pause/resume is the handoff
        primitive) and a host tier (the transport serves it)."""
        ecfg = engine_cfg or eng.EngineConfig()
        ecfg = dataclasses.replace(ecfg, disagg=role)
        if not ecfg.preempt:
            raise ValueError("cluster hosts require preempt=1 (pause/"
                             "resume is the handoff primitive)")
        if not ecfg.kv_offload or not ecfg.kv_prefix_cache:
            raise ValueError("cluster hosts require kv_offload=1 + the "
                             "prefix cache (the wire serves the host "
                             "tier)")
        pool = EnginePool.build(model_cfg, params, tokenizer, ecfg,
                                engines=max(1, int(engines)), **kw)
        return cls(host_id, pool, role=role, bind=bind)

    # ---------- lifecycle ----------

    def start(self, precompile: bool = False) -> str:
        self.pool.start(precompile=precompile)
        store = self.pool._shared.store
        if store is None:
            raise RuntimeError("cluster host has no shared host store "
                               "(kv_offload off, or a non-paged layout?)")
        self.server = KVWireServer(store, index=self.pool._shared.index,
                                   host_id=self.host_id, bind=self._bind)
        self.address = self.server.start()
        for e in self.pool._engines:
            # continuous warm-chain checkpointing (DejaVu): active
            # chains stream to the host tier on the watermark cadence
            # so a crash leaves near-current state for siblings to pull
            e.kv_checkpoint = True
        log.info("cluster host %d (%s) serving kv at %s",
                 self.host_id, self.role, self.address)
        return self.address

    def connect_peers(self, addresses: list):
        """Attach the federated tier: this host's store misses consult
        these peers (every other host's wire address)."""
        store = self.pool._shared.store
        ecfg = self.pool._engines[0].ecfg
        peers = [KVStreamClient(
                     a, store.scope, store.page_size,
                     timeout_s=ecfg.kv_stream_connect_timeout_ms / 1e3,
                     cooldown_s=ecfg.kv_stream_cooldown_ms / 1e3)
                 for a in addresses if a and a != self.address]
        self.fed = FederatedKV(store, peers,
                               neg_ttl_s=ecfg.kv_stream_negcache_ms / 1e3
                               ).attach()
        return self.fed

    def shutdown(self):
        if self.fed is not None:
            self.fed.close()
        if self.server is not None:
            self.server.stop()
        self.pool.shutdown()

    # ---------- health / chaos ----------

    @property
    def alive(self) -> bool:
        """False once every engine loop on the host died WITHOUT
        shutdown (the pool's crash asymmetry, host-wide). The wire
        server is deliberately not consulted: loop death with a live
        store is exactly the recoverable state."""
        if self.killed and all(not e.loop_alive for e in self.pool._engines):
            return False
        dead = [e for e in self.pool._engines
                if e._thread is not None
                and not e.loop_alive and not e._stop]
        return len(dead) < len(self.pool._engines)

    def kill(self):
        """Chaos: lose this host's engine loops (accelerator gone), but
        NOT its host tier or wire server — siblings stream the warm
        chains out of the carcass. The pool's own housekeeping stops
        FIRST so it cannot race the router's harvest by failing streams
        when it finds no live sibling replica."""
        self.killed = True
        self.pool._hk_stop.set()
        FAULTS.arm(self._die_fault, count=len(self.pool._engines))
        for e in self.pool._engines:
            e._wake.set()

    # ---------- load ----------

    def load(self, rank: int = 1) -> float:
        return sum(self.pool._load(i, rank)
                   for i in range(len(self.pool._engines))
                   if not self.pool._dead[i])

    # ---------- uniform host facade (ISSUE 20) ----------
    # The router drives every host — in-process or behind the control
    # plane — through exactly these methods, so it is agnostic to
    # whether a host is a thread or a PID.

    @property
    def state(self) -> str:
        return (FailureDetector.DEAD if not self.alive
                else FailureDetector.ALIVE)

    def submit(self, req) -> "queue.Queue":
        return self.pool.submit(req)

    def cancel(self, rid: str):
        self.pool.cancel(rid)

    def chain_keys(self, ids) -> list:
        pc = self.pool._engines[0]._pcache
        return list(pc.chain_keys(ids)) if pc is not None else []

    def metrics_snapshot(self) -> dict:
        return {
            "pool": self.pool.metrics(),
            "kv_stream": (self.fed.stats() if self.fed is not None else {}),
            "kv_stream_served": (self.server.stats()
                                 if self.server is not None else {}),
            "kv_debug": self.pool.kv_debug(),
        }

    def kv_audit_sweep(self, drained: bool = False) -> dict:
        out = dict(self.pool.kv_audit_sweep(drained=drained))
        out["stream_inflight"] = (self.fed.inflight
                                  if self.fed is not None else 0)
        return out


class ClusterRouter:
    """Front door over N ClusterHosts: cross-host prefix-affinity
    routing, disagg handoff brokering, host crash recovery, cluster-wide
    audit. Mirrors the pool surface the servicer drives (submit /
    generate / cancel / metrics / kv_audit_sweep / shutdown)."""

    def __init__(self, hosts: list):
        assert hosts, "ClusterRouter needs at least one host"
        self.hosts = list(hosts)
        self._dead = [False] * len(hosts)
        self._lock = threading.Lock()
        self._where: dict = {}
        self._where_order: list = []
        self._digests: list = [set() for _ in hosts]
        self._clients: list = [None] * len(hosts)
        self._t_digest = 0.0
        self._pins: list = []
        self._disagg_q: "queue.Queue" = queue.Queue()
        self.affinity_hits = 0
        self.affinity_misses = 0
        self.disagg_handoffs = 0
        self.hosts_recovered = 0
        self._routed = 0
        # remote (process-mode) host bookkeeping: streams re-adopted
        # after a crash/drain, idempotence guard per request id
        self.remote_recovered = 0
        self.drains = 0
        self._recovering: set = set()
        self._hk_stop = threading.Event()
        self._hk_thread: Optional[threading.Thread] = None

    # ---------- lifecycle ----------

    def start(self, precompile: bool = False):
        addrs = [h.start(precompile=precompile) for h in self.hosts]
        for h in self.hosts:
            h.connect_peers(addrs)
        # the router's own digest/stats connections ride the same wire
        # the federated tier uses — affinity data is whatever a peer
        # could learn, no in-process shortcuts. Remote hosts answer
        # DIGEST over the control plane instead (the idempotent-retry
        # path); their failover callbacks land here.
        for i, h in enumerate(self.hosts):
            if h.remote:
                h.on_stream_lost = self._remote_stream_lost
                h.on_state_change = self._remote_state_change
            else:
                store = h.pool._shared.store
                e0 = h.pool._engines[0].ecfg
                self._clients[i] = KVStreamClient(
                    addrs[i], store.scope, store.page_size,
                    timeout_s=e0.kv_stream_connect_timeout_ms / 1e3,
                    cooldown_s=e0.kv_stream_cooldown_ms / 1e3)
        # prefill-role engines hand finished chains to the router
        for i, h in enumerate(self.hosts):
            if h.role == "prefill" and not h.remote:
                for e in h.pool._engines:
                    e.disagg_handoff = self._make_handoff(i)
        self._hk_thread = threading.Thread(
            target=self._housekeeping, name="cluster-router", daemon=True)
        self._hk_thread.start()

    def shutdown(self):
        self._hk_stop.set()
        if self._hk_thread is not None:
            self._hk_thread.join(timeout=5)
        self._drain_disagg()        # nothing may strand in the broker
        with self._lock:
            pins, self._pins = self._pins, []
        for host_i, rid, keys in pins:
            self._unpin(host_i, rid, keys)
        for c in self._clients:
            if c is not None:
                c.close()
        for h in self.hosts:
            try:
                h.shutdown()
            except Exception:
                log.exception("cluster host %d shutdown failed", h.host_id)

    # ---------- routing ----------

    def _alive_hosts(self):
        return [i for i in range(len(self.hosts)) if not self._dead[i]]

    def _note_where(self, rid: str, host: int):
        with self._lock:
            if rid not in self._where:
                self._where_order.append(rid)
            self._where[rid] = host
            while len(self._where_order) > 4096:
                old = self._where_order.pop(0)
                self._where.pop(old, None)

    def where(self, rid: str) -> Optional[int]:
        return self._where.get(rid)

    def _poll_digests(self):
        """Refresh the per-host chain-key sets used for affinity. A
        host that fails to answer keeps its last digest — stale beats
        empty, and the federated fetch at admission is the backstop.
        SUSPECT remote hosts are skipped entirely: a slow peer keeps
        its streams but gets no new probe traffic from the router."""
        for i in self._alive_hosts():
            h = self.hosts[i]
            if h.remote:
                if h.state != FailureDetector.ALIVE:
                    continue
                try:
                    d = h.digest()
                except (OSError, WireError):
                    continue
            else:
                c = self._clients[i]
                if c is None or not c.online():
                    continue
                try:
                    d = c.digest()
                except (OSError, WireError):
                    continue
            self._digests[i] = {bytes.fromhex(k)
                                for k in d.get("keys", ())}

    def _penalty(self, i: int) -> int:
        """Routing de-preference: a SUSPECT host (slow but answering)
        sorts behind every healthy host at any load — degraded, not
        excluded; it still serves if it is all that's left."""
        return 0 if self.hosts[i].state == FailureDetector.ALIVE else 1

    def _match_depth(self, keys: list, digest: set) -> int:
        d = 0
        for k in keys:
            if k not in digest:
                break
            d += 1
        return d

    def _route(self, req, host: Optional[int] = None) -> int:
        alive = self._alive_hosts()
        if not alive:
            raise RuntimeError("cluster: no live hosts")
        if host is not None:
            if host not in alive:
                raise RuntimeError(f"cluster: host {host} is not live")
            self._routed += 1
            return host
        # fresh arrivals need a prefill-capable host; pure-decode hosts
        # receive work only through the disagg broker (unless they are
        # all that's left — serving beats failing)
        cands = [i for i in alive if self.hosts[i].role != "decode"]
        if not cands:
            cands = alive
        rank = PRIORITY_RANK.get(getattr(req, "priority", None), 1)
        self._routed += 1
        if len(cands) > 1 and getattr(req, "prompt_ids", None):
            keys = self.hosts[cands[0]].chain_keys(req.prompt_ids)
            best_i, best_d = None, 0
            for i in cands:
                if self._penalty(i):
                    continue            # a SUSPECT host never wins
                d = self._match_depth(keys, self._digests[i])
                if d > best_d or (d == best_d and d > 0
                                  and best_i is not None
                                  and self.hosts[i].load(rank)
                                  < self.hosts[best_i].load(rank)):
                    best_i, best_d = i, d
            if best_i is not None and best_d > 0:
                self.affinity_hits += 1
                return best_i
            self.affinity_misses += 1
        return min(cands, key=lambda i: (self._penalty(i),
                                         self.hosts[i].load(rank), i))

    def submit(self, req, host: Optional[int] = None) -> "queue.Queue":
        i = self._route(req, host=host)
        self._note_where(req.request_id, i)
        return self.hosts[i].submit(req)

    def generate(self, req, host: Optional[int] = None):
        out = self.submit(req, host=host)
        while True:
            ev = out.get()
            if ev is None:
                return
            yield ev

    def cancel(self, request_id: str):
        i = self._where.get(request_id)
        if i is not None and not self._dead[i]:
            self.hosts[i].cancel(request_id)
        else:
            for i in self._alive_hosts():
                self.hosts[i].cancel(request_id)

    # ---------- chain pinning ----------

    def _pin(self, host_i: int, rid: str, keys: list):
        """Map recovered/disagg chain keys in ``host_i``'s store under
        ("cluster", rid) so budget eviction can't beat the adoptive
        replica's restore; bounded, oldest released first."""
        if not keys:
            return
        store = self.hosts[host_i].pool._shared.store
        owner = ("cluster", rid)
        for k in keys:
            store.map_key(k, owner)
        drop = []
        with self._lock:
            self._pins.append((host_i, rid, keys))
            while len(self._pins) > _MAX_PINS:
                drop.append(self._pins.pop(0))
        for old in drop:
            self._unpin(*old)

    def _unpin(self, host_i: int, rid: str, keys: list):
        store = self.hosts[host_i].pool._shared.store
        owner = ("cluster", rid)
        for k in keys:
            store.unmap_key(k, owner)

    # ---------- disaggregation ----------

    def _make_handoff(self, src_host: int):
        """The callback a prefill-role engine fires (on its loop thread)
        with a finished-prefill ResumeEntry: enqueue for the router
        thread — the loop must not block on a peer fetch."""
        def handoff(entry, keys, _src=src_host):
            self._disagg_q.put((_src, entry, keys))
        return handoff

    def _drain_disagg(self):
        while True:
            try:
                src, entry, keys = self._disagg_q.get_nowait()
            except queue.Empty:
                return
            self._place_disagg(src, entry, keys)

    def _place_disagg(self, src: int, entry: ResumeEntry, keys: list):
        # ResumeEntry adoption is an in-process move (live slot state);
        # remote hosts receive work as fresh submissions only, so they
        # are never disagg targets. SUSPECT hosts are de-preferred: the
        # router stops placing KV-streaming work on a slow peer.
        rid = entry.req.request_id
        cands = [i for i in self._alive_hosts()
                 if i != src and not self.hosts[i].remote
                 and self.hosts[i].role != "prefill"]
        if not cands:
            # no decode host: hand the request back — the source engine
            # decodes it to completion (never strand a client stream)
            entry.req._no_disagg = True
            if not self._dead[src] and self._adopt_on(src, rid, entry):
                return
            for i in self._alive_hosts():
                if not self.hosts[i].remote \
                        and self._adopt_on(i, rid, entry):
                    return
            self.hosts[src].pool._fail_stream(
                entry.req, "disagg: no host can adopt")
            return
        rank = PRIORITY_RANK.get(entry.priority, 1)
        tgt = min(cands, key=lambda i: (self._penalty(i),
                                        self.hosts[i].load(rank), i))
        host = self.hosts[tgt]
        # stream the prefilled chain over BEFORE admission so the decode
        # host splices local, verified bytes (prefetch > demand-fetch:
        # one round-trip for the whole chain, off the engine loop)
        self._pin(tgt, rid, keys)
        if host.fed is not None and keys:
            host.fed.prefetch(keys)
        if not self._adopt_on(tgt, rid, entry):
            entry.req._no_disagg = True
            if self._dead[src] or not self._adopt_on(src, rid, entry):
                self.hosts[src].pool._fail_stream(
                    entry.req, "disagg: no host can adopt")
            return
        self.disagg_handoffs += 1
        # the source kept the chain mapped under ("disagg", rid) from
        # its force-offload; the decode host holds its own copy now
        src_store = self.hosts[src].pool._shared.store
        for k in keys:
            src_store.unmap_key(k, ("disagg", rid))
        EVENTS.emit("disagg_handoff", rid=rid, src=src, dst=tgt,
                    n_decoded=entry.n_decoded, keys=len(keys))

    def _adopt_on(self, host_i: int, rid: str, entry: ResumeEntry) -> bool:
        """Adopt a ResumeEntry on the least-loaded live replica of one
        host; the pool's note_where keeps its own cancel path working."""
        pool = self.hosts[host_i].pool
        rank = PRIORITY_RANK.get(entry.priority, 1)
        reps = [i for i in range(len(pool._engines)) if not pool._dead[i]]
        if not reps:
            return False
        r = min(reps, key=lambda i: (pool._load(i, rank), i))
        if not pool._engines[r].adopt_resume(entry):
            return False
        pool._note_where(rid, r)
        self._note_where(rid, host_i)
        aud = pool._engines[r]._kv_audit
        if aud is not None:
            aud.ledger.record("adopt", slot=("cluster", host_i), rid=rid)
        return True

    # ---------- crash recovery ----------

    def _recover_host(self, i: int):
        """A host's engine loops died (its device tiers are gone; its
        host tier and wire server survive). Everything it was serving
        re-adopts on sibling hosts: warm chains stream over the wire
        from the carcass store, cold ones re-prefill the identical
        history. Client streams never close — the StreamEvent queues
        ride the ResumeEntries (pool._recover_replica, one level up)."""
        host = self.hosts[i]
        with self._lock:
            if self._dead[i]:
                return              # another thread already harvesting
            self._dead[i] = True
        host.pool._hk_stop.set()    # no same-host recovery races
        self._digests[i] = set()
        EVENTS.emit("cluster_host_down", host=i, role=host.role)
        log.warning("cluster: host %d loop(s) died; recovering", i)
        recovered = failed = 0
        for e in host.pool._engines:
            r = e.replica_id
            if r < len(host.pool._dead):
                host.pool._dead[r] = True
            try:
                e._emitter.drain(2.0)
            except Exception:
                pass
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
                    ok = self._adopt_on_sibling_host(rid, entry, src=i)
                if ok:
                    recovered += 1
                else:
                    failed += 1
                    host.pool._fail_stream(
                        s.req, f"cluster host {i} died; request not "
                               f"recoverable on a sibling host")
            if e._sched is not None:
                for entry in e._sched.drain_parked():
                    if self._adopt_on_sibling_host(
                            entry.req.request_id, entry, src=i):
                        recovered += 1
                    else:
                        failed += 1
                        host.pool._fail_stream(
                            entry.req, f"cluster host {i} died")
            while True:
                try:
                    r2 = e._queue.get_nowait()
                except queue.Empty:
                    break
                try:
                    tgt = self._route(r2)
                    self._note_where(r2.request_id, tgt)
                    self.hosts[tgt].pool.submit(r2)
                    recovered += 1
                except Exception:
                    failed += 1
                    host.pool._fail_stream(
                        r2, f"cluster host {i} died; no live sibling")
        self.hosts_recovered += 1
        EVENTS.emit("cluster_host_recovered", host=i,
                    recovered=recovered, failed=failed)
        log.warning("cluster: host %d recovery done "
                    "(recovered=%d failed=%d)", i, recovered, failed)

    def _adopt_on_sibling_host(self, rid: str, entry: ResumeEntry,
                               src: int) -> bool:
        cands = [i for i in self._alive_hosts()
                 if i != src and not self.hosts[i].remote
                 and self.hosts[i].role != "prefill"]
        if not cands:
            cands = [i for i in self._alive_hosts()
                     if i != src and not self.hosts[i].remote]
        if not cands:
            return False
        rank = PRIORITY_RANK.get(entry.priority, 1)
        tgt = min(cands, key=lambda i: (self._penalty(i),
                                        self.hosts[i].load(rank), i))
        host = self.hosts[tgt]
        pc = host.pool._engines[0]._pcache
        keys = list(pc.chain_keys(entry.ids)) if pc is not None else []
        if keys:
            self._pin(tgt, rid, keys)
            if host.fed is not None:
                # pull the dead host's checkpointed chain into the
                # target's local tier before admission restores it
                host.fed.prefetch(keys)
        if not self._adopt_on(tgt, rid, entry):
            return False
        EVENTS.emit("migrate", rid=rid, src=("host", src),
                    dst=("host", tgt), reason="host_crash", kind="resume",
                    n_decoded=entry.n_decoded)
        return True

    # ---------- remote (process-mode) failure handling ----------

    def _remote_state_change(self, handle, prev: str, state: str):
        """Heartbeat-thread callback: failure-detector transitions.
        SUSPECT needs no action here — routing reads ``state`` live and
        de-prefers; DEAD marks the host down (its own heartbeat thread
        aborts the streams, which fail over via _remote_stream_lost)."""
        try:
            i = self.hosts.index(handle)
        except ValueError:
            return
        EVENTS.emit("cluster_host_state", host=i, prev=prev, state=state,
                    phi=round(handle.detector.phi(), 3))
        if state == FailureDetector.DEAD:
            self._mark_remote_dead(i)

    def _mark_remote_dead(self, i: int):
        with self._lock:
            if self._dead[i]:
                return
            self._dead[i] = True
        self._digests[i] = set()
        self.hosts_recovered += 1
        EVENTS.emit("cluster_host_down", host=i,
                    role=self.hosts[i].role, remote=True)
        log.warning("cluster: remote host %d declared dead; streams "
                    "fail over as they surface", i)

    def _remote_stream_lost(self, handle, req, emitted: list, reason: str):
        """A remote stream ended without EOF — the host crashed, hung
        past ``cluster_dead_ms``, or drained. Recovery is the PR-10
        contract from the CLIENT side: re-admit (prompt + delivered
        tokens) as a fresh continuation on a sibling and bridge its
        events into the original stream — byte-identical, because
        resume ≡ fresh re-admission. SUBMIT is never auto-retried; this
        path is the one and only re-drive, idempotent per request."""
        rid = req.request_id
        with self._lock:
            if rid in self._recovering:
                return
            self._recovering.add(rid)
        try:
            i = self.hosts.index(handle)
        except ValueError:
            i = -1
        remaining = int(req.max_new_tokens) - len(emitted)
        if remaining <= 0:
            # every token was delivered (and the last one carried the
            # finish reason); only the EOF marker was lost
            req.out.put(None)
            return
        cands = [j for j in self._alive_hosts()
                 if j != i and self.hosts[j].role != "prefill"]
        if not cands:
            cands = [j for j in self._alive_hosts() if j != i]
        if not cands:
            self._fail_remote_stream(req, f"cluster host "
                                     f"{handle.host_id} lost ({reason}); "
                                     f"no live sibling")
            return
        rank = PRIORITY_RANK.get(getattr(req, "priority", None), 1)
        tgt = min(cands, key=lambda j: (self._penalty(j),
                                        self.hosts[j].load(rank), j))
        host = self.hosts[tgt]
        hist = list(req.prompt_ids) + [int(t) for t in emitted]
        cont = eng.GenRequest(
            prompt_ids=hist, params=req.params,
            max_new_tokens=remaining,
            stop_sequences=list(req.stop_sequences or []),
            ignore_eos=req.ignore_eos, grammar=req.grammar,
            priority=req.priority,
            request_id=f"{rid}~r{len(emitted)}")
        # warm-chain pull: the dead host's wire server may survive a
        # drain (and a hang); a kill -9 lost it too — then the fetch
        # fails fast and the continuation re-prefills the identical
        # history. Correct either way, warm when possible.
        if not host.remote:
            keys = host.chain_keys(hist)
            if keys:
                self._pin(tgt, rid, keys)
                if host.fed is not None:
                    host.fed.prefetch(keys)
        self._note_where(rid, tgt)
        try:
            out = host.submit(cont)
        except Exception as e:
            self._fail_remote_stream(req, f"cluster: continuation "
                                     f"submit failed: {e}")
            return
        self.remote_recovered += 1
        EVENTS.emit("migrate", rid=rid, src=("host", i),
                    dst=("host", tgt),
                    reason=("host_drain" if reason == "drain"
                            else "host_crash"),
                    kind="readmit", n_decoded=len(emitted))
        t = threading.Thread(
            target=self._bridge_continuation,
            args=(req, out, len(emitted)),
            name=f"cluster-bridge-{rid[:8]}", daemon=True)
        t.start()

    def _bridge_continuation(self, req, out: "queue.Queue", k: int):
        """Pump continuation events into the ORIGINAL stream, with
        counters shifted so the client sees one uninterrupted request
        (completion tokens continue from the crash point; prompt size
        stays the original prompt, not prompt + delivered)."""
        plen = len(req.prompt_ids)
        while True:
            ev = out.get()
            if ev is None:
                req.out.put(None)
                return
            if ev.completion_tokens:
                ev = dataclasses.replace(
                    ev, completion_tokens=ev.completion_tokens + k,
                    prompt_tokens=plen)
            req.out.put(ev)

    def _fail_remote_stream(self, req, msg: str):
        log.warning("cluster: %s", msg)
        req.out.put(eng.StreamEvent(token_id=-1, text="", logprob=0.0,
                                    error=msg, error_kind="stall"))
        req.out.put(None)

    def drain_host(self, i: int, deadline_s: float = 30.0) -> dict:
        """Graceful drain (the clean half of the crash path): the host
        stops admissions, checkpoints active chains, and hands every
        stream off; continuations re-adopt on siblings through the same
        byte-gated path a crash uses. The host leaves routing."""
        h = self.hosts[i]
        self.drains += 1
        if h.remote:
            out = h.drain(deadline_s=deadline_s)
            with self._lock:
                self._dead[i] = True
            self._digests[i] = set()
            EVENTS.emit("cluster_host_drained", host=i, **{
                k: v for k, v in out.items() if isinstance(v, int)})
            return out
        # in-process: there is no admission surface to refuse through;
        # stop the loops cooperatively and let the loop-death recovery
        # path re-adopt the streams (same ResumeEntry machinery)
        h.kill()
        deadline = time.monotonic() + 5.0
        while h.alive and time.monotonic() < deadline:
            time.sleep(0.02)
        if not self._dead[i]:
            self._recover_host(i)
        return {"streams": 0, "handed_off": 0}

    # ---------- housekeeping ----------

    def _housekeeping(self):
        while not self._hk_stop.wait(0.05):
            try:
                for i, h in enumerate(self.hosts):
                    if self._dead[i]:
                        continue
                    if h.remote:
                        # belt-and-braces: the heartbeat thread owns
                        # DEAD transitions, but a process that exited
                        # between beats is caught here
                        if h.state == FailureDetector.DEAD:
                            self._mark_remote_dead(i)
                            h.abort_streams("crash")
                    elif not h.alive:
                        self._recover_host(i)
                self._drain_disagg()
                t0 = time.monotonic()
                if t0 - self._t_digest > _DIGEST_PERIOD_S:
                    self._t_digest = t0
                    self._poll_digests()
            except Exception:
                log.exception("cluster router housekeeping failed")

    # ---------- audit ----------

    def kv_audit_sweep(self, drained: bool = False) -> dict:
        """Cluster-wide fold of every live host's pool sweep, plus the
        transport conservation check: with the cluster quiesced no
        entry may still be in flight on any wire (a declared extra that
        never lands IS a leak)."""
        out = {"mode": "off", "checks": 0, "violations": 0,
               "leaked_pages": 0, "ledger_events": 0,
               "stream_inflight": 0}
        for i in self._alive_hosts():
            try:
                snap = self.hosts[i].kv_audit_sweep(drained=drained)
            except (OSError, WireError):
                continue            # a dead remote host has no sweep
            if snap.get("mode") != "off":
                out["mode"] = snap["mode"]
                for k in ("checks", "violations", "leaked_pages",
                          "ledger_events"):
                    out[k] += snap.get(k, 0)
            out["stream_inflight"] += snap.get("stream_inflight", 0)
        for i, h in enumerate(self.hosts):
            # dead IN-PROCESS hosts still hold a federated tier whose
            # in-flight fetches count against quiescence (the carcass
            # keeps serving); a dead remote host has no reachable tier
            if self._dead[i] and not h.remote and h.fed is not None:
                out["stream_inflight"] += h.fed.inflight
        if drained:
            if out["stream_inflight"]:
                out["violations"] += 1
                log.warning("cluster audit: %d stream fetches still in "
                            "flight after drain", out["stream_inflight"])
        return out

    # ---------- observability ----------

    def _host_snapshots(self) -> list:
        snaps = []
        for i, h in enumerate(self.hosts):
            if self._dead[i]:
                snaps.append(None)
                continue
            try:
                snaps.append(h.metrics_snapshot())
            except (OSError, WireError):
                snaps.append(None)  # unreachable remote: skip this poll
        return snaps

    def metrics(self) -> dict:
        snaps = self._host_snapshots()
        live = [s["pool"] for s in snaps if s is not None]
        out = dict(live[0]) if live else {}
        for k in ("slots_total", "slots_active", "queued", "queue_limit",
                  "total_tokens_generated", "prompt_tokens_reused"):
            out[k] = sum(m.get(k) or 0 for m in live)
        stream = {"fetches": 0, "hits": 0, "misses": 0, "pages": 0,
                  "bytes": 0, "pushes": 0, "pushed_pages": 0,
                  "corrupt_rejected": 0, "inflight": 0}
        served = {"serves": 0, "pages_out": 0, "bytes_out": 0}
        rpc = {"retries": {}, "timeouts": {}, "reconnects": 0}
        states, heartbeat = {}, {}
        for i, h in enumerate(self.hosts):
            s = snaps[i]
            if not h.remote:
                # dead in-process hosts keep their transport counters
                # (the carcass store served the recovery streams)
                fs = h.fed.stats() if h.fed is not None else {}
                ss = h.server.stats() if h.server is not None else {}
            else:
                fs = (s or {}).get("kv_stream") or {}
                ss = (s or {}).get("kv_stream_served") or {}
            for k in stream:
                stream[k] += fs.get(k, 0)
            for k in served:
                served[k] += ss.get(k, 0)
            states[str(h.host_id)] = (FailureDetector.DEAD
                                      if self._dead[i] else h.state)
            if h.remote:
                heartbeat[str(h.host_id)] = h.heartbeat_telemetry()
                hs = h.rpc_stats()
                for k in ("retries", "timeouts"):
                    for op, n in hs[k].items():
                        rpc[k][op] = rpc[k].get(op, 0) + n
                rpc["reconnects"] += hs["reconnects"]
        out["kv_stream"] = stream
        out["kv_stream_served"] = served
        out["cluster"] = {
            "hosts": len(self.hosts),
            "hosts_alive": len(self._alive_hosts()),
            "hosts_recovered": self.hosts_recovered,
            "remote_recovered": self.remote_recovered,
            "drains": self.drains,
            "routed": self._routed,
            "affinity_hits": self.affinity_hits,
            "affinity_misses": self.affinity_misses,
            "disagg_handoffs": self.disagg_handoffs
                               + sum(e.disagg_handoffs
                                     for h in self.hosts if not h.remote
                                     for e in h.pool._engines),
            "roles": {str(h.host_id): h.role for h in self.hosts},
            "host_states": states,
            "rpc": rpc,
            "heartbeat": heartbeat,
        }
        out["hosts"] = [{
            "host": h.host_id,
            "role": h.role,
            "alive": not self._dead[i],
            "remote": bool(h.remote),
            "state": states[str(h.host_id)],
            "address": h.address,
            "kv_stream": (h.fed.stats()
                          if not h.remote and h.fed is not None
                          else ((snaps[i] or {}).get("kv_stream") or {})),
        } for i, h in enumerate(self.hosts)]
        return out

    def kv_debug(self) -> dict:
        snaps = self._host_snapshots()
        return {
            "cluster_hosts": len(self.hosts),
            "hosts": [{
                "host": h.host_id, "role": h.role,
                "alive": not self._dead[i], "address": h.address,
                **((snaps[i] or {}).get("kv_debug") or {}),
                "kv_stream": ((snaps[i] or {}).get("kv_stream") or {}),
                "kv_serve": ((snaps[i] or {}).get("kv_stream_served")
                             or {}),
            } for i, h in enumerate(self.hosts)],
        }
