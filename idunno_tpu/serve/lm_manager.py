"""Cluster management for the LM serving tier (round-2 VERDICT item 3).

Round 2's LM tier was node-local: ``lm_serve`` built a pool on whichever
node took the RPC, so decode pools and train jobs sat outside the
cluster's core guarantees — not placed by the coordinator, not fair-shared,
not journaled to the standby, and dead with their node (queued + in-flight
requests lost; train jobs resumed only by manual re-``train_start``). The
reference applies its guarantees to *all* work: coordinator task placement
and failed-worker reassignment (`mp4_machinelearning.py:706-760`), standby
metadata replication (`:971-1011`).

This manager runs on the acting master and closes that gap for the LM tier:

- **Placement**: ``serve()``/``train()`` pick the least-loaded alive node
  (measured load: the scheduler book's in-flight CNN tasks per host, plus
  managed pools/jobs already placed there) and issue the node-local verb
  over the control RPC.
- **Journaling**: every submitted request's full descriptor (prompt,
  max_new, temperature, *pinned* seed) and its completion tokens live in a
  master-side journal. Sampling seeds are pinned at admission (default:
  the global request id), so a replayed request — greedy OR sampled — is
  token-exact.
- **Standby replication**: ``to_wire()``/``load_wire()`` ride the
  FailoverManager snapshot, so the standby adopts the pool registry and
  the journal along with the task book.
- **Recovery**: on a pool node's death the manager re-issues ``lm_serve``
  on a survivor and resubmits every unfinished request; a dead train-job
  node gets ``train_start(resume=True)`` on a survivor, resuming from the
  job's last store checkpoint. On coordinator failover the new master
  conservatively requeues every unfinished request (completions drained
  from a pool but not yet replicated are unrecoverable from the node;
  pinned seeds make the replay exact, and the journal dedupes).

Threading: verbs arrive on RPC handler threads, the pump runs on the
master loop, membership changes on the monitor thread — one RLock guards
the registry; all transport calls happen OUTSIDE the lock (a slow or dead
peer must never stall the registry).
"""
from __future__ import annotations

import re
import threading
import time
from typing import Any

from idunno_tpu.comm.message import Message
from idunno_tpu.comm.retry import call_with_retry
from idunno_tpu.comm.transport import Transport, TransportError
from idunno_tpu.config import ClusterConfig
from idunno_tpu.membership.epoch import (StaleEpoch, StaleScope, place_scope,
                                         pool_scope, reply_is_stale,
                                         reply_stale_scope, stamp_scoped)
from idunno_tpu.membership.service import MembershipService
from idunno_tpu.serve.admission import PRIORITIES, shed_reason
from idunno_tpu.serve.autoscaler import Autoscaler, AutoscalePolicy
from idunno_tpu.utils.spans import stamp_trace
from idunno_tpu.utils.types import MemberStatus, MessageType


def _default_slots() -> int:
    """The measured serving default (engine/serve_lm.DEFAULT_SLOTS),
    imported lazily — the manager must stay importable without paying the
    engine's jax import on nodes that never serve."""
    from idunno_tpu.engine.serve_lm import DEFAULT_SLOTS
    return DEFAULT_SLOTS

CONTROL = "control"

# request lifecycle: pending (not yet on any node) -> inflight (forwarded,
# node id known) -> done (tokens journaled). Recovery moves inflight back
# to pending; done, failed (node rejected the request — permanent, e.g.
# a validation error), cancelled (client lm_cancel), shed (the pool's QoS
# gateway rejected admission — serve/gateway.py) and expired (deadline_ms
# passed while queued) are terminal — recovery/resubmission must never
# replay a request the client was already told is out.
_PENDING, _INFLIGHT, _DONE, _FAILED = "pending", "inflight", "done", "failed"
_CANCELLED = "cancelled"
_SHED, _EXPIRED = "shed", "expired"

# the pool poll's error-string shape ("request {rid} failed: ...") —
# parsed by the group poll to remap replica rids to group ids
_ERR_RE = re.compile(r"^request (\d+) failed: (.*)$", re.S)


class LMPoolManager:
    """Acting-master registry + journal + recovery for decode pools and
    train jobs. Constructed on every node (the standby needs one to adopt
    into); only the acting master's instance pumps or places."""

    # an inflight request older than this is assumed lost (node-side error
    # consumed by a failed poll, or a drained-but-undelivered reply) and is
    # requeued — exact replay, so the only cost is wasted decode. The
    # effective timeout scales with the request's max_new at the pool's
    # measured per-token rate (a legitimately long decode or a post-recovery
    # recompile must not be declared lost — ADVICE r3). Capped at
    # max_request_attempts total forwards, then FAILED loudly; pool-level
    # requeues (resize/recovery) reset the count — only per-request
    # suspicion consumes the budget.
    request_timeout_s = 120.0
    request_timeout_slack = 4.0      # x measured decode time, + timeout base
    max_request_attempts = 3
    # pool builds / in-place rebuilds and train starts compile XLA programs
    # node-side (tens of seconds for a first-time shape on TPU); the
    # default 30 s control-RPC timeout would declare every routine
    # resize dead mid-compile and leak the still-building loop
    build_rpc_timeout_s = 300.0

    def __init__(self, host: str, config: ClusterConfig,
                 transport: Transport, membership: MembershipService,
                 inference_service=None) -> None:
        self.host = host
        self.config = config
        self.transport = transport
        self.membership = membership
        self.service = inference_service      # scheduler book = load signal
        # minimum seconds between APPLIED slot resizes per pool (config-
        # driven; instance attribute so tests can pin it per-manager): a
        # rebuild is a full recompile + in-flight requeue, so a rate
        # hovering on a share boundary must not thrash the pool
        self.resize_dwell_s = float(config.lm_resize_dwell_s)
        # wall-clock source for request bookkeeping (t_submitted/
        # t_forwarded, fair-share windows, resize dwell, drain stamps) —
        # injectable so seeded harnesses can pin it; the autoscaler keeps
        # its own separately-injected clock
        self.wall = time.time
        # per-node span recorder (utils/spans.py), wired by serve/node.py;
        # None = tracing off. Journaled requests carry their trace ctx in
        # to_wire, so a trace survives failover adoption
        self.spans = None
        self._lock = threading.RLock()
        # name -> {"spec": dict, "node": str|None, "next_rid": int,
        #          "requests": {rid: descriptor}}
        self._pools: dict[str, dict[str, Any]] = {}
        # name -> {"spec": dict, "node": str|None, "status": dict|None}
        self._jobs: dict[str, dict[str, Any]] = {}
        # replica pool GROUPS (serve/autoscaler.py): an lm_serve spec
        # carrying autoscale={...} creates one of these instead of a
        # single pool. Replicas are ordinary entries in _pools named
        # "{group}@r{i}"; the group journals routing state + every
        # scaling decision so failover replays scaling exactly.
        # name -> {"spec", "policy", "replicas", "next_replica",
        #          "tenants", "next_grid", "rid_map", "idem",
        #          "decisions", "next_seq", "t_last_decision",
        #          "route_counts"}
        self._groups: dict[str, dict[str, Any]] = {}
        # per-pool WAL delta baseline: the last FULL wire entry the scope
        # standby ACKed, so _replicate_pool can ship journal deltas and
        # fall back to a full entry on any gap (ISSUE 15)
        self._wal_shipped: dict[str, dict[str, Any]] = {}
        # measured prefill ship-time EWMAs per prefill replica (ISSUE 20
        # satellite): manager-local soft state feeding prefill-role
        # routing; replica -> (ewma_s, n). Deliberately NOT in the group
        # wire form — an adopter starts cold and re-measures.
        self._ttft_ewma: dict[str, tuple[float, int]] = {}
        # cumulative journal rows compacted out of shipped WAL segments
        # below the delivered low-water mark (ISSUE 17 satellite;
        # metrics_export: pool_wal_truncated)
        self.wal_truncated = 0
        # the control loop; tick() runs from pump_once, so it inherits
        # the acting-master gate. clock/gauges_fn are injectable
        # (tests/test_autoscaler.py, chaos harness).
        self.autoscaler = Autoscaler(self)
        # FailoverManager backref (wired by serve/node.py) so scaling
        # decisions replicate to the standby between snapshots
        self.failover = None
        membership.on_change(self._on_member_change)

    # -- placement ---------------------------------------------------------

    def _load_score(self, host: str) -> float:
        """Measured load on ``host``: in-flight CNN tasks the scheduler
        book currently assigns to it, plus LM pools and train jobs this
        manager already placed there (each pool/job owns the device for
        its steps, so it weighs like an in-flight task stream)."""
        score = 0.0
        if self.service is not None:
            score += len(self.service.scheduler.book.in_flight(host))
        with self._lock:
            score += sum(1 for p in self._pools.values()
                         if p["node"] == host)
            score += sum(1 for j in self._jobs.values()
                         if j["node"] == host and not self._job_over(j))
        return score

    @staticmethod
    def _job_over(job: dict[str, Any]) -> bool:
        # stop_requested records the USER's intent even when the node was
        # unreachable at train_stop time — a stop-requested job must never
        # be auto-resumed by recovery
        if job.get("stop_requested"):
            return True
        st = job.get("status") or {}
        return bool(st.get("done") or st.get("stopped") or st.get("error"))

    def _place(self) -> str:
        alive = sorted(self.membership.members.alive_hosts())
        if not alive:
            raise ValueError("no alive hosts to place on")
        master = self.membership.acting_master()

        def key(h: str):
            # control-plane hosts carry the pump/replication loops: bias
            # ties away from the acting master (and, lighter, the standby)
            # without ever excluding them — a loaded worker still loses to
            # an idle master
            bias = (0.5 if h == master
                    else 0.25 if h == self.config.standby_coordinator
                    else 0.0)
            return (self._load_score(h) + bias, h)

        return min(alive, key=key)

    def _call(self, node: str, payload: dict[str, Any],
              timeout: float = 30.0,
              scope: str | None = None) -> dict[str, Any]:
        """Control RPC to a node's LOCAL lm tier (``local``=True keeps the
        receiving dispatcher from routing back into its own manager).
        Stamped with this manager's epoch view: a node that has seen a
        higher epoch fences us with StaleEpoch (a TransportError subclass,
        so every catch-site treats it as transient — requests stay
        pending/journal-safe — while the observe demotes this node and the
        pump stops on its next is_acting_master gate).

        ``scope`` (pool-directed mutating verbs) adds the per-pool fence
        stamp beside the cluster stamp: a node that has seen a higher
        epoch FOR THAT POOL rejects with a stale-scope reply — this
        manager then steps down for the named scope only (dropping the
        fenced pool/group registry entries) while every other pool keeps
        serving; the StaleScope raise reaches catch-sites as an ordinary
        transient, but the drop has already happened, so nothing
        retries into the fence."""
        payload = dict(payload, local=True,
                       epoch=list(self.membership.epoch.view()))
        if scope is not None:
            stamp_scoped(self.membership.scopes, scope, payload)
        reply = self.transport.call(
            node, CONTROL, Message(MessageType.INFERENCE, self.host,
                                   payload), timeout=timeout)
        if reply is None:
            raise TransportError(f"no reply from {node}")
        if reply_is_stale(self.membership.epoch, reply):
            e, owner = self.membership.epoch.view()
            raise StaleEpoch(f"{node} fenced this manager: epoch {e} "
                             f"owned by {owner}", e, owner)
        fenced = reply_stale_scope(self.membership.scopes, reply)
        if fenced is not None:
            # fence BEFORE raising: StaleScope subclasses TransportError,
            # and most catch-sites swallow those as transient — the drop
            # here is what guarantees no retry loop into the fence
            self._fence_scope(fenced)
            e, owner = self.membership.scopes.fence(fenced).view()
            raise StaleScope(f"{node} fenced scope {fenced}: epoch {e} "
                             f"owned by {owner}", fenced, e, owner)
        if reply.type is MessageType.ERROR:
            raise ValueError(f"{node}: {reply.payload.get('error')}")
        return reply.payload

    def _fence_scope(self, scope: str) -> None:
        """Step down for ONE fenced pool scope: drop its pools — and its
        group, whose _ensure_group_replicas would otherwise re-serve the
        replicas this manager no longer owns — from the local registry.
        The scope's new owner adopted an at-least-as-new journal (per-pool
        WAL), so keeping a fenced copy here would double-serve the pool.
        Everything else — other pools/groups, train jobs, the CNN book,
        cluster-wide mastership — is untouched: that isolation is the
        point of the per-pool fence."""
        with self._lock:
            dropped = [n for n in self._pools if pool_scope(n) == scope]
            for n in dropped:
                del self._pools[n]
            for n in [g for g in self._groups if pool_scope(g) == scope]:
                del self._groups[n]
                dropped.append(n)
        if dropped and self.service is not None:
            self.service.metrics.record_counter("pool_scope_fenced")

    # -- scope ownership (ISSUE 15) ----------------------------------------

    def step_down_scope(self, scope: str) -> None:
        """Public step-down for one scope: drop its pools/groups from the
        local registry (the new owner holds an at-least-as-new journal).
        Same semantics as a fence-driven step-down."""
        self._fence_scope(scope)

    def _scope_held_locally(self, scope: str) -> bool:
        with self._lock:
            return (any(pool_scope(n) == scope for n in self._pools)
                    or any(pool_scope(g) == scope for g in self._groups))

    def _scope_names_nonempty(self) -> bool:
        with self._lock:
            return bool(self._pools or self._groups)

    def _scope_owner(self, scope: str) -> str | None:
        """Where ``scope``'s journal should live: the gossiped claim if
        its holder is alive, else the deterministic rendezvous placement
        over the alive hosts. None when the membership plane carries no
        ownership map (bare test doubles) — callers then serve locally,
        the pre-ISSUE-15 behavior."""
        owners = getattr(self.membership, "owners", None)
        if owners is None:
            return None
        claimed = owners.owner(scope)
        alive = set(self.membership.members.alive_hosts())
        if claimed in alive:
            return claimed
        return place_scope(scope, self.config.hosts, alive,
                           quarantined=self._quarantined_hosts())

    def _quarantined_hosts(self) -> set[str]:
        """Hosts the differential-health plane has quarantined (gray
        failure: heartbeat-alive but limping). Routing-only input — the
        set is empty on bare test doubles without a ledger."""
        h = getattr(self.membership, "health", None)
        return h.quarantined() if h is not None else set()

    def _claim_scope(self, scope: str) -> None:
        """Advisory ownership claim, gossiped on membership payloads.
        Routing-only: the scope FENCE stays the safety mechanism — a
        stale claim costs one redirect hop, never correctness."""
        owners = getattr(self.membership, "owners", None)
        if owners is not None and owners.owner(scope) != self.host:
            owners.claim(scope, self.host)

    def _assign_scope(self, owner: str, spec: dict[str, Any],
                      scope: str) -> dict[str, Any] | None:
        """Hand an lm_serve spec to the scope's placed owner. The payload
        routes into the owner's ``_route_cluster`` (placement="assign",
        NOT local) so the owner's manager journals the pool. Returns the
        owner's reply, or None when the owner is unreachable — the caller
        then serves locally and claims the scope itself."""
        payload = dict(spec, verb="lm_serve", placement="assign",
                       epoch=list(self.membership.epoch.view()))
        stamp_scoped(self.membership.scopes, scope, payload)
        try:
            reply = self.transport.call(
                owner, CONTROL,
                Message(MessageType.INFERENCE, self.host, payload),
                timeout=self.build_rpc_timeout_s)
        except TransportError:
            return None
        if reply is None or reply_is_stale(self.membership.epoch, reply):
            return None
        if reply.type is MessageType.ERROR:
            raise ValueError(f"{owner}: {reply.payload.get('error')}")
        return dict(reply.payload, owner=owner)

    def _step_down_moved_scopes(self) -> None:
        """Drop any locally-held scope whose gossiped claim names another
        ALIVE host: its adopter minted a higher claim (and fence) — the
        fence would reject us anyway on the next stamped call, this just
        stops the pump from re-serving a moved scope in the window before
        that rejection lands."""
        owners = getattr(self.membership, "owners", None)
        if owners is None:
            return
        with self._lock:
            held = {pool_scope(n) for n in self._pools}
            held.update(pool_scope(g) for g in self._groups)
        alive = set(self.membership.members.alive_hosts())
        for scope in held:
            o = owners.owner(scope)
            if o is not None and o != self.host and o in alive:
                self.step_down_scope(scope)

    # -- pools: client surface (acting master) -----------------------------

    def serve(self, spec: dict[str, Any],
              assigned: bool = False) -> dict[str, Any]:
        """Place a decode pool on the least-loaded alive node and register
        it. ``spec`` is the node-local ``lm_serve`` payload (name,
        prompt_len, max_len, slots, ...).

        Multi-owner placement (ISSUE 15): the pool's fence scope has a
        deterministic rendezvous owner over the alive hosts; when that
        owner is another host, this manager hands the WHOLE spec over
        (placement="assign") and the owner journals it locally — the
        acting master never funnels every scope. ``assigned=True`` is the
        landing half of that hop: serve here unconditionally, no
        re-forward."""
        spec = {k: v for k, v in spec.items()
                if k not in ("verb", "placement", "local", "reload")}
        scope = pool_scope(spec["name"])
        if not assigned and not self._scope_held_locally(scope):
            owner = self._scope_owner(scope)
            if owner is not None and owner != self.host:
                out = self._assign_scope(owner, spec, scope)
                if out is not None:
                    return out
                # owner unreachable: serve locally below and claim the
                # scope ourselves so routing follows the journal
        auto = spec.pop("autoscale", None)
        if auto is not None:
            return self._serve_group(spec, auto)
        name = spec["name"]
        with self._lock:
            if name in self._groups:
                raise ValueError(f"{name!r} is a replica group; serve "
                                 "replicas through its autoscale spec")
            if name in self._pools:
                return {"already": True,
                        "node": self._pools[name]["node"]}
            # reserve before the (slow) remote build so a concurrent serve
            # of the same name returns "already" instead of double-placing.
            # _recovering guards the build: the pump treats node=None as an
            # orphan, and without the flag it would concurrently re-place
            # this still-building pool on another node — leaking whichever
            # loop loses the race (the build is ~80 s on a cold TPU shape,
            # many pump periods long)
            entry = {"spec": dict(spec), "node": None,
                     "_recovering": True,
                     "next_rid": 0, "requests": {},
                     # client idempotency keys → rid: a client retrying a
                     # submit whose ACK was lost gets its ORIGINAL rid
                     # back instead of double-journaling (replicated with
                     # the journal so the dedupe survives failover)
                     "idem": {},
                     # per-pool WAL high-water: bumped on every
                     # replicate-worthy journal mutation; the standby and
                     # apply_pool_wal keep only strictly newer entries
                     "wal_seq": 0,
                     "done_total": 0, "failed_total": 0,
                     "cancelled_total": 0,
                     "shed_total": 0, "expired_total": 0,
                     # DistServe ledger (ISSUE 18): handoffs this pool
                     # PREFILLED for other pools' requests, keyed
                     # "{decode_pool}:{rid}" → state. Journaled so the
                     # ship edge is write-ahead in BOTH pools' WALs
                     # (the decode side rides its request row)
                     "handoffs": {},
                     "node_errors": [],
                     # measured service samples feeding the
                     # heterogeneous fair share: (seconds from
                     # submit to completion, new tokens)
                     "svc_samples": [],
                     "slots_now": int(spec.get("slots", _default_slots())),
                     "slots_cap": int(spec.get("slots", _default_slots())),
                     "slots_target_prev": None,
                     "t_last_resize": 0.0}
            self._pools[name] = entry
        # claim the scope at reservation time (not commit) so the gossiped
        # owner map converges while the ~80 s build runs; a failed build
        # leaves a harmless advisory claim (routing finds no pool)
        self._claim_scope(pool_scope(name))
        try:
            node = self._place()
            out = self._call(node, dict(spec, verb="lm_serve"),
                             timeout=self.build_rpc_timeout_s,
                             scope=pool_scope(name))
        except BaseException:
            with self._lock:
                # identity, not name: lm_stop + a re-serve may have
                # replaced the entry with a NEW generation mid-build —
                # deleting by name would destroy the newer reservation
                if self._pools.get(name) is entry:
                    del self._pools[name]
            raise
        with self._lock:
            # commit node + clear the build guard atomically, and only
            # into THIS build's entry: after lm_stop + re-serve the name
            # maps to a different generation whose build is still in
            # flight — committing into it would un-guard it mid-build
            if self._pools.get(name) is entry:
                entry["node"] = node
                entry["_recovering"] = False
                stale_node = None
            else:
                # stopped (or superseded) while the build RPC ran:
                # nothing must keep serving
                stale_node = node
        if stale_node is not None:
            self._stop_stale_loop(stale_node, name)
            return {"node": None, "stopped": True}
        return {"node": node, "slots": out.get("slots")}

    def _stop_stale_loop(self, node: str, name: str) -> None:
        """Best-effort lm_stop for a loop this manager just built but can
        no longer account for (the registry entry was stopped or re-placed
        while the build RPC ran) — an unaccounted live loop would decode
        into a dead outbox and hold device HBM indefinitely."""
        try:
            self._call(node, {"verb": "lm_stop", "name": name},
                       timeout=10.0, scope=pool_scope(name))
        except (TransportError, ValueError, OSError):
            pass

    def submit(self, name: str, prompt: list[int], max_new: int,
               temperature: float = 0.0, top_p: float = 1.0,
               top_k: int = 0, presence_penalty: float = 0.0,
               frequency_penalty: float = 0.0,
               stop: list[list[int]] | None = None,
               seed: int | None = None,
               tenant: str = "default", priority: str = "interactive",
               deadline_ms: float | None = None,
               idem_key: str | None = None,
               trace: tuple | None = None,
               handoff_from: str | None = None) -> int:
        """Journal a request (seed pinned NOW — replay after any failure
        must be token-exact even for sampled requests), then forward it to
        the pool's node. Forward failures leave it pending; the pump
        retries/relocates.

        ``handoff_from`` (DistServe, ISSUE 18) names a PREFILL replica
        that should fill the prompt's KV blocks and ship them to this
        pool's node before the forward — the journal entry carries the
        handoff state machine so a replay re-ships or falls back.

        QoS fields travel with the journal entry: the pool node's gateway
        decides admission at forward time, and a gateway shed comes back
        as a terminal journal state (never replayed). ``deadline_ms``
        bounds node-side queue wait measured from gateway admission — a
        replay after node death re-admits with a fresh deadline window."""
        if priority not in PRIORITIES:
            raise ValueError(f"priority must be one of {PRIORITIES}, "
                             f"got {priority!r}")
        with self._lock:
            is_group = name in self._groups
        if is_group:
            return self._group_submit(
                name, prompt, max_new, temperature=temperature,
                top_p=top_p, top_k=top_k,
                presence_penalty=presence_penalty,
                frequency_penalty=frequency_penalty, stop=stop,
                seed=seed, tenant=tenant, priority=priority,
                deadline_ms=deadline_ms, idem_key=idem_key, trace=trace)
        with self._lock:
            pool = self._pools.get(name)
            if pool is None:
                raise ValueError(f"no managed pool {name!r}; "
                                 "lm_serve (placement=auto) first")
            if idem_key is not None:
                prior = pool.setdefault("idem", {}).get(idem_key)
                if prior is not None:
                    # client retry of an already-journaled submit (its ACK
                    # was lost): same booking, exactly-once — and the
                    # retried hop leaves a duplicate-marked span so the
                    # waterfall shows the dedupe
                    if self.spans is not None and trace:
                        self.spans.record(
                            "lm.submit", trace=trace[0], parent=trace[1],
                            attrs={"pool": name, "rid": int(prior),
                                   "duplicate": True})
                    return int(prior)
            rid = pool["next_rid"]
            pool["next_rid"] += 1
            tr = None
            if self.spans is not None:
                # mint/extend the trace at the journal booking: the ctx
                # rides the journal entry (and the standby snapshot), so
                # forwards — including post-adoption replays — chain
                # under this span
                sp = self.spans.record(
                    "lm.submit",
                    trace=trace[0] if trace else None,
                    parent=trace[1] if trace else None,
                    attrs={"pool": name, "rid": rid, "managed": True})
                tr = [sp.trace_id, sp.span_id]
            req = {"trace": tr,
                   "prompt": [int(t) for t in prompt],
                   "max_new": int(max_new),
                   "temperature": float(temperature),
                   "top_p": float(top_p),
                   "top_k": int(top_k),
                   "presence_penalty": float(presence_penalty),
                   "frequency_penalty": float(frequency_penalty),
                   "stop": ([[int(t) for t in q] for q in stop]
                            if stop else None),
                   "seed": int(seed) if seed is not None else rid,
                   "tenant": str(tenant), "priority": str(priority),
                   "deadline_ms": (float(deadline_ms)
                                   if deadline_ms is not None else None),
                   # flipped on the FIRST successful forward: a replay of
                   # an admitted request bypasses gateway admission
                   # (readmit) — the client was told it was in, recovery
                   # must not shed it
                   "admitted": False,
                   # DistServe state machine (ISSUE 18): prefilling →
                   # shipping → adopted, any failure → fallback (decode-
                   # side prefill). Journaled + replicated with the row.
                   "handoff": ({"from": str(handoff_from),
                                "state": "prefilling",
                                "shipped": 0, "bytes": 0}
                               if handoff_from is not None else None),
                   "status": _PENDING, "node_id": None,
                   "tokens": None, "prompt_len": None, "delivered": False,
                   "t_forwarded": None, "attempts": 0,
                   "t_submitted": self.wall()}
            pool["requests"][rid] = req
            if idem_key is not None:
                pool["idem"][idem_key] = rid
            node = pool["node"]
        if node is not None:
            if req.get("handoff"):
                self._handoff_ship(name, node, rid, req)
            self._forward(name, node, rid, req)
        # write-ahead the booking (and the forward's inflight/admitted
        # commit) to the standby's per-pool WAL segment: an adoption right
        # after this ack replays exactly this journal, per scope
        self._replicate_pool(name)
        return rid

    def _forward(self, name: str, node: str, rid: int,
                 req: dict[str, Any]) -> None:
        payload = {
            "verb": "lm_submit", "name": name,
            "prompt": req["prompt"], "max_new": req["max_new"],
            "temperature": req["temperature"],
            "top_p": req.get("top_p", 1.0),
            "top_k": req.get("top_k", 0),
            "presence_penalty": req.get("presence_penalty", 0.0),
            "frequency_penalty": req.get("frequency_penalty", 0.0),
            "stop": req.get("stop"),
            "seed": req["seed"],
            "tenant": req.get("tenant", "default"),
            "priority": req.get("priority", "interactive"),
            "deadline_ms": req.get("deadline_ms"),
            "readmit": bool(req.get("admitted")),
            # node-side dedupe for a LOST-REPLY retry: attempts counts
            # prior successful forwards, so the pump's re-forward after
            # a dropped ACK reuses the key (the node returns its
            # existing row), while a watchdog requeue — attempts
            # already bumped — gets a fresh key and books a fresh row
            "idem": f"{name}:{rid}:{req['attempts']}"}
        fsp = None
        tr = req.get("trace")
        if self.spans is not None and tr:
            # one span per forward ATTEMPT: a retried/re-placed request
            # shows every hop (and which node finally took it); the
            # stamped ctx makes the node's lm.submit span its child
            fsp = self.spans.start(
                "lm.forward", trace=tr[0], parent=tr[1],
                attrs={"pool": name, "rid": rid, "node": node,
                       "attempt": int(req.get("attempts", 0))})
            stamp_trace(payload, fsp.ctx)
        try:
            out = self._call(node, payload, scope=pool_scope(name))
        except (TransportError, OSError) as e:
            if fsp is not None:
                self.spans.finish(fsp, error=type(e).__name__)
            return                      # stays pending; pump will retry
        except ValueError as e:
            if fsp is not None:
                self.spans.finish(fsp, error=str(e)[:120])
            with self._lock:
                pool = self._pools.get(name)
                req2 = pool["requests"].get(rid) if pool else None
                if "no lm_serve pool" in str(e):
                    # the node is alive but has NO loop under this name
                    # (stale snapshot / out-of-band lm_stop): recoverable —
                    # orphan the pool so the pump re-establishes it, and
                    # leave the request pending for the resubmission
                    if pool is not None and pool["node"] == node:
                        self._orphan_pool_locked(name)
                elif "still starting" in str(e):
                    # transient: the node is mid-rebuild behind a _Starting
                    # reservation (e.g. an in-place resize); the request
                    # stays pending and the pump re-forwards once the new
                    # loop is up — failing it here would turn routine
                    # autoscaling into user-visible request failures
                    pass
                elif req2 is not None and req2["status"] == _PENDING:
                    reason = shed_reason(str(e))
                    if reason is not None:
                        # the pool's QoS gateway shed it (quota /
                        # queue_full / backpressure) — journal-terminal,
                        # exactly like a cancel: recovery must never
                        # resubmit a request the client was told is out
                        req2["status"] = _SHED
                        req2["shed_reason"] = reason
                        req2["error"] = str(e)
                        pool["shed_total"] += 1
                    else:
                        # the node REJECTED the request (validation) —
                        # permanent; retrying would loop forever. Surface
                        # via poll().
                        req2["status"] = _FAILED
                        req2["error"] = str(e)
                        pool["failed_total"] += 1
            return
        if fsp is not None:
            self.spans.finish(fsp, node_id=int(out["id"]),
                              duplicate=bool(out.get("duplicate")))
        cancel_on_node = False
        with self._lock:
            # recovery may have requeued/re-placed while the RPC ran; only
            # a still-pending request on the same node takes the mapping
            pool = self._pools.get(name)
            if pool is not None and pool["node"] == node:
                status = pool["requests"].get(rid, {}).get("status")
                if status == _PENDING:
                    req2 = pool["requests"][rid]
                    req2["status"] = _INFLIGHT
                    req2["node_id"] = int(out["id"])
                    req2["t_forwarded"] = self.wall()
                    req2["attempts"] += 1
                    req2["admitted"] = True
                elif status == _CANCELLED:
                    # cancel() raced this forward: it saw a pending
                    # request with no node mapping, so no node-side
                    # cancel was sent — send it now, or the node decodes
                    # all max_new tokens into a dropped completion
                    cancel_on_node = True
        if cancel_on_node:
            try:
                self._call(node, {"verb": "lm_cancel", "name": name,
                                  "id": int(out["id"])}, timeout=10.0,
                           scope=pool_scope(name))
            except (TransportError, ValueError, OSError):
                pass              # best-effort: the row burns out on its own

    def poll(self, name: str) -> dict[str, Any]:
        """Completions not yet handed to a client. Delivery to the CLIENT
        is at-most-once per completion (a poll reply lost in transit is not
        re-sent — the tokens remain reproducible from the journaled seed).
        Pruning is deferred to the NEXT poll, so the delivered flag lives
        through at least one journal-replication cycle and a standby that
        adopts between polls does not re-deliver or re-decode completions
        the old master already handed out (ADVICE r3)."""
        with self._lock:
            is_group = name in self._groups
        if is_group:
            return self._group_poll(name)
        with self._lock:
            pool = self._pools.get(name)
            if pool is None:
                raise ValueError(f"no managed pool {name!r}")
            # prune what the PREVIOUS poll delivered: the journal (and
            # every standby snapshot) stays bounded by requests in flight
            # plus one delivered batch
            pruned = set()
            for rid in [r for r, q in pool["requests"].items()
                        if q["delivered"]]:
                del pool["requests"][rid]
                pruned.add(rid)
            if pruned and pool.get("idem"):
                # idempotency keys age out with the requests they booked
                pool["idem"] = {k: r for k, r in pool["idem"].items()
                                if r not in pruned}
            out, errors, cancelled = [], [], []
            shed, expired = [], []
            for rid, req in sorted(pool["requests"].items()):
                if req["status"] == _DONE:
                    req["delivered"] = True
                    out.append({"id": rid, "tokens": req["tokens"],
                                "prompt_len": req["prompt_len"],
                                # same completion shape as the node-direct
                                # lm_poll reply (control.py)
                                "service_s": req.get("service_s", 0.0),
                                **({"logprobs": req["logprobs"]}
                                   if req.get("logprobs") is not None
                                   else {})})
                elif req["status"] == _FAILED:
                    req["delivered"] = True
                    errors.append(f"request {rid} failed: "
                                  f"{req.get('error', '?')}")
                elif req["status"] == _CANCELLED:
                    req["delivered"] = True
                    cancelled.append(rid)
                elif req["status"] == _SHED:
                    req["delivered"] = True
                    shed.append({"id": rid,
                                 "reason": req.get("shed_reason", "?")})
                elif req["status"] == _EXPIRED:
                    req["delivered"] = True
                    expired.append(rid)
        reply: dict[str, Any] = {"completions": out}
        if errors:
            reply["errors"] = errors
        if cancelled:
            reply["cancelled"] = cancelled
        if shed:
            reply["shed"] = shed
        if expired:
            reply["expired"] = expired
        return reply

    def cancel(self, name: str, rid: int) -> dict[str, Any]:
        """Cancel a journaled request. Terminal immediately in the journal
        (recovery and the pump will never replay it); if it was inflight,
        the node-side cancel is forwarded best-effort — the node's partial
        completion is dropped by `_drain` (its node_id mapping is gone).
        Client-facing: the id shows up in the next poll's ``cancelled``
        list. Returns {"cancelled": False} for ids already terminal or
        never journaled."""
        with self._lock:
            is_group = name in self._groups
            route = self._group_rid_locked(name, rid) if is_group else None
        if is_group:
            # an unmapped group id is already terminal (pruned) or was
            # never booked — same {"cancelled": False} as a plain pool
            return (self.cancel(*route) if route is not None
                    else {"cancelled": False})
        with self._lock:
            pool = self._pools.get(name)
            if pool is None:
                raise ValueError(f"no managed pool {name!r}")
            req = pool["requests"].get(rid)
            if req is None or req["status"] not in (_PENDING, _INFLIGHT):
                return {"cancelled": False}
            was_inflight = req["status"] == _INFLIGHT
            node, node_id = pool["node"], req["node_id"]
            req["status"] = _CANCELLED
            req["node_id"] = None
            pool["cancelled_total"] += 1
        # journal-terminal transition: write it ahead per pool so an
        # adoption never replays a request the client was told is out
        self._replicate_pool(name)
        if was_inflight and node is not None and node_id is not None:
            try:
                self._call(node, {"verb": "lm_cancel", "name": name,
                                  "id": int(node_id)}, timeout=10.0,
                           scope=pool_scope(name))
            except (TransportError, ValueError, OSError):
                pass          # best-effort: the row burns out on its own
        return {"cancelled": True}

    def partial(self, name: str) -> dict[str, Any]:
        """Streaming surface for a managed pool: the node's live-row
        progress mapped back to journal request ids. Rows the journal no
        longer tracks as inflight (just cancelled / just drained) are
        dropped — a client must never see an id it didn't submit."""
        with self._lock:
            is_group = name in self._groups
        if is_group:
            return self._group_partial(name)
        with self._lock:
            pool = self._pools.get(name)
            if pool is None:
                raise ValueError(f"no managed pool {name!r}")
            node = pool["node"]
            id_map = {r["node_id"]: rid
                      for rid, r in pool["requests"].items()
                      if r["status"] == _INFLIGHT
                      and r["node_id"] is not None}
            traces = {rid: r["trace"][0]
                      for rid, r in pool["requests"].items()
                      if r.get("trace")}
        if node is None:
            return {"partial": []}
        try:
            out = self._call(node, {"verb": "lm_partial", "name": name},
                             timeout=10.0)
        except (TransportError, ValueError, OSError) as e:
            return {"partial": [], "error": str(e)}
        rows = []
        for row in out.get("partial", ()):
            if int(row["id"]) not in id_map:
                continue
            rid = id_map[int(row["id"])]
            # journal trace id wins (it is the root the `trace` verb
            # resolves); the node row's own id is the fallback — and an
            # untraced request gains no `trace` key at all
            row = dict(row, id=rid)
            tr = traces.get(rid) or row.get("trace")
            if tr:
                row["trace"] = tr
            elif "trace" in row:
                del row["trace"]
            rows.append(row)
        reply = {"partial": rows}
        if out.get("sheds"):
            # recent gateway rejections with reasons (tenant-keyed, not
            # rid-keyed — a shed request never got a node id)
            reply["sheds"] = out["sheds"]
        return reply

    def stats(self, name: str) -> dict[str, Any]:
        with self._lock:
            is_group = name in self._groups
        if is_group:
            return self._group_stats(name)
        with self._lock:
            pool = self._pools.get(name)
            if pool is None:
                raise ValueError(f"no managed pool {name!r}")
            node = pool["node"]
            counts = {s: 0 for s in (_PENDING, _INFLIGHT)}
            for req in pool["requests"].values():
                if req["status"] in counts:
                    counts[req["status"]] += 1
            # terminal states are cumulative counters (delivered requests
            # are pruned from the journal)
            counts[_DONE] = pool["done_total"]
            counts[_FAILED] = pool["failed_total"]
            counts[_CANCELLED] = pool["cancelled_total"]
            counts[_SHED] = pool["shed_total"]
            counts[_EXPIRED] = pool["expired_total"]
            node_errors = list(pool["node_errors"][-5:])
        out = {"node": node, "journal": counts}
        if node_errors:
            out["node_errors"] = node_errors
        if node is not None:
            try:
                out["pool"] = self._call(
                    node, {"verb": "lm_stats", "name": name})["stats"]
            except (TransportError, ValueError, OSError) as e:
                out["pool_error"] = str(e)
        return out

    def qos(self, name: str) -> dict[str, Any]:
        """QoS observability for a managed pool: journal-side terminal
        counters plus the node gateway's live stats (None when the pool
        runs without a gateway or its node is unreachable). For a
        replica GROUP, the reply carries the group block (policy,
        replicas with roles/states, recent scaling decisions, tenant
        map) plus each replica's own qos."""
        with self._lock:
            is_group = name in self._groups
        if is_group:
            return self._group_qos(name)
        with self._lock:
            pool = self._pools.get(name)
            if pool is None:
                raise ValueError(f"no managed pool {name!r}")
            node = pool["node"]
            out: dict[str, Any] = {
                "node": node,
                "journal": {"shed": pool["shed_total"],
                            "expired": pool["expired_total"],
                            "cancelled": pool["cancelled_total"],
                            "done": pool["done_total"]}}
        if node is not None:
            try:
                out["qos"] = self._call(
                    node, {"verb": "lm_qos", "name": name},
                    timeout=10.0)["qos"]
            except (TransportError, ValueError, OSError) as e:
                out["qos_error"] = str(e)
        return out

    def prefix_op(self, verb: str, name: str,
                  p: dict[str, Any]) -> dict[str, Any]:
        """Relay a cluster-prefix verb (`prefix_publish`/`prefix_probe`/
        `prefix_fetch`) to a managed pool's serving node — prefix state
        lives in the pool's radix tree and SDFS memo, the journal only
        knows the spec. For a replica GROUP, publish/fetch fan over
        every active replica (counters summed — warming touches every
        replica's local tree) while probe asks one live replica (the
        published set is cluster-global, any replica sees it)."""
        fwd: dict[str, Any] = {"verb": verb}
        if p.get("tokens") is not None:
            fwd["tokens"] = [int(t) for t in p["tokens"]]
        if p.get("tenant") is not None:
            fwd["tenant"] = str(p["tenant"])
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                pool = self._pools.get(name)
                if pool is None:
                    raise ValueError(f"no managed pool {name!r}")
                targets = [(name, pool["node"])]
            else:
                targets = [(r, self._pools[r]["node"])
                           for r, m in sorted(g["replicas"].items())
                           if m["state"] == "active"
                           and r in self._pools]
        targets = [(r, n) for r, n in targets if n is not None]
        if not targets:
            raise ValueError(f"{name!r}: no serving node for {verb}")
        if verb == "prefix_probe" or len(targets) == 1:
            rname, node = targets[0]
            return self._call(node, dict(fwd, name=rname),
                              scope=pool_scope(name))
        merged: dict[str, Any] = {"replicas": 0}
        for rname, node in targets:
            try:
                out = self._call(node, dict(fwd, name=rname),
                                 scope=pool_scope(name))
            except (TransportError, ValueError, OSError) as e:
                merged.setdefault("errors", []).append(
                    f"{rname}: {e}")
                continue
            merged["replicas"] += 1
            for k, v in out.items():
                if isinstance(v, (int, float)) and not isinstance(
                        v, bool):
                    merged[k] = merged.get(k, 0) + v
                elif k not in merged:
                    merged[k] = v
        return merged

    # -- DistServe KV handoff (ISSUE 18) -----------------------------------

    def kv_handoff(self, name: str, p: dict[str, Any]) -> dict[str, Any]:
        """Relay a client-initiated ``kv_handoff`` verb to a managed
        pool's serving node — like ``prefix_op``, the block/radix state
        lives on the node, the journal only knows the spec. A replica
        GROUP resolves to its first active replica (any replica can probe
        or ship; the manager's own routed handoffs pick replicas via
        ``_route_group_locked``, this path is the debugging/ops surface).
        A ship must orchestrate FROM the prefill replica's own host: its
        loop owns the exported blocks."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                pool = self._pools.get(name)
                if pool is None:
                    raise ValueError(f"no managed pool {name!r}")
                targets = [(name, pool["node"])]
            else:
                targets = [(r, self._pools[r]["node"])
                           for r, m in sorted(g["replicas"].items())
                           if m["state"] == "active" and r in self._pools]
        targets = [(r, n) for r, n in targets if n is not None]
        if not targets:
            raise ValueError(f"{name!r}: no serving node for kv_handoff")
        rname, node = targets[0]
        fwd = {k: v for k, v in p.items()
               if k in ("verb", "op", "tokens", "blobs", "from_depth",
                        "start_depth", "target_host", "target_name",
                        "timeout")}
        return self._call(node, dict(fwd, name=rname),
                          scope=pool_scope(name))

    def _handoff_ship(self, name: str, node: str, rid: int,
                      req: dict[str, Any]) -> None:
        """DistServe handoff leg: have the journaled PREFILL replica fill
        the prompt's KV blocks and ship them point-to-point to the decode
        pool's node BEFORE the request forwards there — the decode
        admission then hits the grafted radix chain and prefills only the
        trailing remainder (zero re-prefill for shipped blocks).

        State machine, write-ahead at every edge in BOTH pools' WALs:

            prefilling → shipping → adopted      (happy path)
                                  ↘ fallback     (any failure — decode-
                                                  side prefill, request
                                                  untouched)

        Death semantics: a manager death at prefilling/shipping replays
        the ship from the adopted journal (the pump re-runs this for
        pending rows with a non-terminal handoff state — safe because
        kv_handoff is naturally idempotent: re-probe + dedup grafts). A
        PREFILL-replica death fails the ship RPC after retries →
        fallback. A DECODE-replica death orphans the pool; re-placement
        resets adopted → prefilling (`_orphan_pool_locked`: the new node
        holds no blocks) and the recovery re-ships to the new node. The
        handoff is an optimization layered UNDER the journal: it never
        completes, fails, or doubles a request by itself."""
        hop = req.get("handoff") or {}
        pre_rname = hop.get("from")
        key = f"{name}:{rid}"
        with self._lock:
            pool = self._pools.get(name)
            live = pool["requests"].get(rid) if pool else None
            lhop = (live or {}).get("handoff")
            if (live is None or live["status"] != _PENDING
                    or not lhop
                    or lhop.get("state") in ("adopted", "fallback")):
                return
            pre = self._pools.get(pre_rname)
            pre_node = pre["node"] if pre is not None else None
            lhop["state"] = "shipping"
            if pre is not None:
                ledger = pre.setdefault("handoffs", {})
                ledger[key] = "shipping"
                # bounded ledger: oldest entries age out first (terminal
                # states carry no replay value; live ones are re-entered
                # by the pump from the decode side anyway)
                while len(ledger) > 128:
                    del ledger[next(iter(ledger))]
        if pre_node is None or pre_node == node:
            # prefill replica unplaced/gone, or colocated with the target
            # (same node serves both loops: its blocks are already local
            # only in the prefill POOL's tree, not the decode pool's — a
            # self-ship over loopback still works, but a colocated pair
            # means the role split degenerated; just prefill in place)
            self._handoff_done(name, rid, pre_rname, "fallback")
            return
        # write-ahead the SHIPPING edge to both scopes' WAL segments
        # before the RPC: an adopter replays the ship, never wonders
        # whether it ran (idempotent either way)
        self._replicate_pool(name)
        if pre_rname != name:
            self._replicate_pool(pre_rname)
        payload = {"verb": "kv_handoff", "op": "ship", "name": pre_rname,
                   "target_host": node, "target_name": name,
                   "tokens": list(req["prompt"])}
        sp = None
        tr = req.get("trace")
        if self.spans is not None and tr:
            sp = self.spans.start(
                "lm.handoff_ship", trace=tr[0], parent=tr[1],
                attrs={"pool": name, "rid": rid, "prefill": pre_rname,
                       "node": pre_node})
            stamp_trace(payload, sp.ctx)
        t_ship = self.wall()
        try:
            out = call_with_retry(
                lambda: self._call(pre_node, payload,
                                   scope=pool_scope(pre_rname)))
        except (TransportError, OSError, ValueError) as e:
            if sp is not None:
                self.spans.finish(sp, error=str(e)[:120], fallback=True)
            if self.service is not None:
                self.service.metrics.record_counter("kv_handoff_fallbacks")
            self._handoff_done(name, rid, pre_rname, "fallback")
            return
        if sp is not None:
            self.spans.finish(sp, shipped=int(out.get("shipped", 0)),
                              bytes=int(out.get("bytes", 0)))
        # measured-TTFT feed (ISSUE 20 satellite): the ship wall time IS
        # the prefill latency the decode replica skipped
        self._observe_ttft(pre_rname, self.wall() - t_ship)
        self._handoff_done(name, rid, pre_rname, "adopted",
                           shipped=int(out.get("shipped", 0)),
                           nbytes=int(out.get("bytes", 0)))

    def _handoff_done(self, name: str, rid: int, pre_rname: str | None,
                      state: str, shipped: int = 0,
                      nbytes: int = 0) -> None:
        """Commit a terminal handoff edge to both journals + WALs."""
        key = f"{name}:{rid}"
        with self._lock:
            pool = self._pools.get(name)
            live = pool["requests"].get(rid) if pool else None
            hop = (live or {}).get("handoff")
            if hop is not None:
                hop["state"] = state
                hop["shipped"] = int(shipped)
                hop["bytes"] = int(nbytes)
            pre = (self._pools.get(pre_rname)
                   if pre_rname is not None else None)
            if pre is not None and key in pre.get("handoffs", {}):
                pre["handoffs"][key] = state
        if pool is not None:
            self._replicate_pool(name)
        if pre is not None and pre_rname != name:
            self._replicate_pool(pre_rname)

    def stop(self, name: str) -> dict[str, Any]:
        with self._lock:
            is_group = name in self._groups
        if is_group:
            return self._group_stop(name)
        with self._lock:
            pool = self._pools.pop(name, None)
        if pool is None:
            return {"stopped": False}
        if pool["node"] is not None:
            try:
                self._call(pool["node"], {"verb": "lm_stop", "name": name},
                           scope=pool_scope(name))
            except (TransportError, ValueError, OSError):
                pass                    # node may already be dead
        return {"stopped": True}

    def managed_pools(self) -> list[str]:
        with self._lock:
            return sorted(set(self._pools) | set(self._groups))

    def has_pool(self, name: str) -> bool:
        # groups answer too: _route_cluster (serve/control.py) routes a
        # group-addressed verb through this manager exactly like a pool
        with self._lock:
            return name in self._pools or name in self._groups

    def trace_of(self, name: str, rid: int) -> str | None:
        """Trace id of a journaled request (None once pruned/untraced) —
        the `trace` control verb's lookup for managed pools."""
        with self._lock:
            route = self._group_rid_locked(name, rid)
        if route is not None:
            return self.trace_of(*route)
        with self._lock:
            pool = self._pools.get(name)
            if pool is None:
                return None
            tr = (pool["requests"].get(int(rid)) or {}).get("trace")
            return tr[0] if tr else None

    # -- replica pool groups (serve/autoscaler.py) -------------------------
    #
    # A group is routing + scaling state over ordinary managed pools
    # named "{group}@r{i}". All mechanism lives here (spawn / drain /
    # retire / rebalance as journaled, epoch-stamped decisions); the
    # POLICY — when to do which — lives in the Autoscaler's tick.

    def _as_now(self) -> float:
        """Group timing (dwell, drain windows, decision stamps) runs on
        the autoscaler's injectable clock, so fake-clock tests and the
        chaos harness drive it deterministically."""
        return float(self.autoscaler.clock())

    def group_names(self) -> list[str]:
        with self._lock:
            return sorted(self._groups)

    def has_group(self, name: str) -> bool:
        with self._lock:
            return name in self._groups

    def _group_rid_locked(self, name: str, rid: int):
        """(replica, replica-rid) for a group request id; None when the
        name is not a group or the id is unmapped. Caller holds the
        lock."""
        g = self._groups.get(name)
        if g is None:
            return None
        ent = g["rid_map"].get(int(rid))
        return (ent[0], int(ent[1])) if ent is not None else None

    @staticmethod
    def _tenant_weight_fn(g: dict[str, Any]):
        """WFQ weight lookup from the group spec's gateway quotas — the
        same weights serve/gateway.py fair-queues with; 1.0 default."""
        gw = g["spec"].get("gateway") or {}
        tq = gw.get("tenants") or {}
        try:
            default_w = float((gw.get("default") or {}).get("weight", 1.0))
        except (TypeError, ValueError):
            default_w = 1.0

        def weight(t: str) -> float:
            try:
                return max(float((tq.get(t) or {}).get(
                    "weight", default_w)), 1e-6)
            except (TypeError, ValueError):
                return 1.0

        return weight

    def _group_debts_locked(self, g: dict[str, Any],
                            replicas: list[str]) -> dict[str, float]:
        """WFQ debt per replica: outstanding (pending+inflight) journal
        entries weighted by 1/tenant-weight."""
        weight = self._tenant_weight_fn(g)
        debts: dict[str, float] = {}
        for r in replicas:
            pool = self._pools.get(r)
            debt = 0.0
            if pool is not None:
                for req in pool["requests"].values():
                    if req["status"] in (_PENDING, _INFLIGHT):
                        debt += 1.0 / weight(req.get("tenant", "default"))
            debts[r] = round(debt, 6)
        return debts

    def _record_decision_locked(self, name: str, g: dict[str, Any],
                                action: str, dwell: bool = True,
                                **attrs) -> dict[str, Any]:
        """Append a scaling decision to the group's journal: seq'd,
        epoch-stamped (a deposed master's decisions are refused with its
        whole managed journal — _route_cluster), span-recorded. ``dwell``
        False (policy updates) leaves the scaling damper untouched."""
        seq = g["next_seq"]
        g["next_seq"] += 1
        d: dict[str, Any] = {
            "seq": seq, "epoch": list(self.membership.epoch.view()),
            "action": action, "t": round(self._as_now(), 6), **attrs}
        g["decisions"].append(d)
        del g["decisions"][:-128]          # bounded journal window
        if dwell:
            g["t_last_decision"] = self._as_now()
        if self.spans is not None:
            sp = self.spans.record(
                f"autoscale.{action}",
                attrs={"group": name,
                       **{k: v for k, v in d.items()
                          if k in ("seq", "replica", "role", "tenant",
                                   "src", "dst", "p95")}})
            d["trace"] = [sp.trace_id, sp.span_id]
        return d

    def _replicate_scale(self, name: str,
                         decision: dict[str, Any] | None) -> None:
        """Push the decision — with the group's full wire entry — to the
        standby between snapshots (FailoverManager.wal_scale, mirroring
        the CNN task WAL): an adoption right after a scaling action must
        replay it exactly, not rediscover it."""
        fo = self.failover
        if fo is None or decision is None:
            return
        with self._lock:
            g = self._groups.get(name)
            entry = self._group_wire_locked(g) if g is not None else None
        if entry is not None:
            fo.wal_scale(name, decision, entry)

    def _serve_group(self, spec: dict[str, Any],
                     auto: Any) -> dict[str, Any]:
        """Create a replica group from an lm_serve spec carrying
        ``autoscale={...}`` and spawn its min_replicas decode replicas."""
        policy = AutoscalePolicy.from_config(
            self.config, auto if isinstance(auto, dict) else None)
        name = spec["name"]
        with self._lock:
            if name in self._groups:
                return {"already": True, "group": True,
                        "replicas": sorted(self._groups[name]["replicas"])}
            if name in self._pools:
                raise ValueError(f"{name!r} already names a managed pool")
            self._groups[name] = {
                "spec": dict(spec), "policy": policy.to_wire(),
                "replicas": {}, "next_replica": 0,
                "tenants": {}, "next_grid": 0, "rid_map": {},
                "idem": {}, "decisions": [], "next_seq": 0,
                "t_last_decision": 0.0,
                # prefill-heavy admission fraction since group creation:
                # feeds the autoscaler's role-split spawn choice.
                # "handoff" counts the prefill-heavy subset served in
                # DistServe handoff mode (ISSUE 18)
                "route_counts": {"total": 0, "prefill": 0, "handoff": 0}}
        self._claim_scope(pool_scope(name))
        spawned = []
        for _ in range(policy.min_replicas):
            d = self.group_spawn(name, role="decode")
            if d is not None:
                spawned.append(d["replica"])
        if not spawned:
            with self._lock:
                # nothing placed — withdraw so the caller's retry starts
                # clean instead of finding a zero-replica husk
                self._groups.pop(name, None)
            raise ValueError(
                f"group {name!r}: could not place any replica")
        return {"group": True, "node": None, "replicas": spawned}

    def group_spawn(self, name: str, role: str = "decode",
                    **attrs) -> dict[str, Any] | None:
        """Spawn one replica pool. Deterministic journaled names
        ("{group}@r{i}" via next_replica) are the spawn idempotency
        backstop: serve() answers "already" for an existing name, so a
        replayed spawn can never double-place (chaos invariant)."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                return None
            policy = AutoscalePolicy.from_wire(g["policy"])
            active = [r for r, m in g["replicas"].items()
                      if m["state"] == "active"]
            if len(active) >= policy.max_replicas:
                return None
            rname = f"{name}@r{g['next_replica']}"
            g["next_replica"] += 1
            rspec = dict(g["spec"], name=rname)
            # replica pools are named "{group}@r{i}" but must load the
            # GROUP's stored model — carry it explicitly (node-side
            # lm_serve loads p["model"] over the pool name)
            rspec.setdefault("model", name)
            if role == "prefill" and policy.prefill_chunk > 0:
                # DistServe's split, request-routing grained: the prefill
                # replica takes long-prompt admissions with chunked
                # prefill tuned on (Sarathi interleave, PR 7)
                rspec["prefill_chunk"] = int(policy.prefill_chunk)
        try:
            out = self.serve(rspec)
        except (TransportError, ValueError, OSError):
            return None        # autoscaler retries on a later tick
        with self._lock:
            g = self._groups.get(name)
            stale = g is None
            warm_tenants: list[str] = []
            if not stale:
                g["replicas"][rname] = {"role": role, "state": "active",
                                        "t_drain": 0.0}
                decision = self._record_decision_locked(
                    name, g, "spawn", replica=rname, role=role,
                    node=out.get("node"), **attrs)
                if g["spec"].get("cluster_prefix"):
                    warm_tenants = sorted(g["tenants"])
        if stale:
            self.stop(rname)   # group stopped mid-build: nothing serves
            return None
        self._replicate_scale(name, decision)
        if warm_tenants and out.get("node") is not None:
            self._warm_replica(name, rname, out["node"], warm_tenants)
        return decision

    def _warm_replica(self, group: str, rname: str, node: str,
                      tenants: list[str]) -> None:
        """Warm-at-spawn (ISSUE 17): a fresh replica of a cluster-prefix
        group fetches the published chains of the group's known tenants
        before traffic lands on it, so its first request for a published
        prefix prefills only the suffix. Best-effort — a warm failure
        never fails the spawn (the replica just starts cold, exactly
        like before this feature existed)."""
        for tenant in tenants:
            try:
                self._call(node, {"verb": "prefix_fetch", "name": rname,
                                  "tenant": tenant},
                           scope=pool_scope(group))
            except (TransportError, ValueError, OSError):
                pass

    @staticmethod
    def _replica_index(rname: str) -> int:
        try:
            return int(rname.rsplit("@r", 1)[1])
        except (IndexError, ValueError):
            return -1

    def group_retire_start(self, name: str, replica: str | None = None,
                           **attrs) -> dict[str, Any] | None:
        """Mark a replica DRAINING: it takes no new routing but keeps
        serving — and delivering — its journal. Default victim: the
        newest active replica. Its pinned tenants re-route by debt on
        their next submit."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                return None
            active = [r for r, m in g["replicas"].items()
                      if m["state"] == "active"]
            if len(active) <= 1:
                return None     # never drain the last live replica
            victim = replica if replica is not None else max(
                active, key=self._replica_index)
            m = g["replicas"].get(victim)
            if m is None or m["state"] != "active":
                return None
            m["state"] = "draining"
            m["t_drain"] = self._as_now()
            g["tenants"] = {t: r for t, r in g["tenants"].items()
                            if r != victim}
            decision = self._record_decision_locked(
                name, g, "retire_start", replica=victim, **attrs)
        self._replicate_scale(name, decision)
        return decision

    def group_retire(self, name: str, replica: str,
                     **attrs) -> dict[str, Any] | None:
        """Remove a DRAINED replica and stop its pool — only when every
        journaled request on it has been DELIVERED (zero admitted-
        request loss); the autoscaler additionally waits out
        drain_window_s before calling this."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                return None
            m = g["replicas"].get(replica)
            if m is None or m["state"] != "draining":
                return None
            pool = self._pools.get(replica)
            if pool is not None and any(
                    not r["delivered"]
                    for r in pool["requests"].values()):
                return None     # still owes the client work — keep it
            del g["replicas"][replica]
            g["rid_map"] = {grid: ent for grid, ent
                            in g["rid_map"].items()
                            if ent[0] != replica}
            decision = self._record_decision_locked(
                name, g, "retire", replica=replica, **attrs)
        self.stop(replica)
        self._replicate_scale(name, decision)
        return decision

    def group_rebalance(self, name: str, **attrs) -> dict[str, Any] | None:
        """Move the heaviest-debt tenant on the max-WFQ-debt decode
        replica to the min-debt one. New submissions only — outstanding
        work stays where it was journaled."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                return None
            policy = AutoscalePolicy.from_wire(g["policy"])
            decode = [r for r, m in g["replicas"].items()
                      if m["state"] == "active"
                      and m["role"] == "decode"]
            if len(decode) < 2:
                return None
            debts = self._group_debts_locked(g, decode)
            hi = max(decode, key=lambda r: (debts[r], r))
            lo = min(decode, key=lambda r: (debts[r], r))
            if debts[hi] - debts[lo] <= policy.rebalance_debt:
                return None
            weight = self._tenant_weight_fn(g)
            per_tenant: dict[str, float] = {}
            pool = self._pools.get(hi)
            if pool is not None:
                for req in pool["requests"].values():
                    if req["status"] in (_PENDING, _INFLIGHT):
                        t = req.get("tenant", "default")
                        if g["tenants"].get(t) == hi:
                            per_tenant[t] = (per_tenant.get(t, 0.0)
                                             + 1.0 / weight(t))
            if not per_tenant:
                return None     # debt is unpinned traffic; nothing to move
            tenant = max(per_tenant, key=lambda t: (per_tenant[t], t))
            g["tenants"][tenant] = lo
            decision = self._record_decision_locked(
                name, g, "rebalance", tenant=tenant, src=hi, dst=lo,
                debt_gap=round(debts[hi] - debts[lo], 4), **attrs)
        self._replicate_scale(name, decision)
        return decision

    def _route_group_locked(self, g: dict[str, Any], prompt_len: int,
                            tenant: str) -> tuple[str, str | None]:
        """Replica for a new admission, as ``(target, handoff_from)``.

        Prefill-heavy prompts (length >= prefill_len_threshold —
        serve/admission.py:is_prefill_heavy) with an active prefill
        replica go one of two ways (ISSUE 18):

        - **handoff mode** (the group also has an active DECODE replica
          and the spec carries a KV block pool): the request is routed
          to its tenant-sticky decode replica, and ``handoff_from``
          names the prefill replica that will fill + ship the KV blocks
          there first (``_handoff_ship``) — true DistServe, the decode
          replica never pays the prefill.
        - **whole-request mode** (no block pool, or prefill-only
          group): the prefill replica serves the request end to end,
          the pre-ISSUE-18 behavior.

        Everything else is tenant-sticky on decode replicas, new tenants
        landing on the least-WFQ-debt one.

        Gray-failure defense (ISSUE 20): replicas placed on QUARANTINED
        nodes (membership/health.py) are skipped — including a tenant's
        sticky assignment, which re-pins by debt on its next submit —
        unless every placed replica is quarantined, where availability
        wins and routing falls back to the full set. Among multiple
        prefill replicas the one with the lowest measured ship-time EWMA
        (``_ttft_ewma``, fed by ``_handoff_ship``) takes the admission;
        with no samples the order is unchanged (lowest replica index)."""
        from idunno_tpu.serve.admission import is_prefill_heavy
        policy = AutoscalePolicy.from_wire(g["policy"])
        active = sorted((r for r, m in g["replicas"].items()
                         if m["state"] == "active"
                         and r in self._pools),
                        key=self._replica_index)
        quarantined = self._quarantined_hosts()
        if quarantined:
            healthy = [r for r in active
                       if (self._pools.get(r) or {}).get("node")
                       not in quarantined]
            if healthy and len(healthy) < len(active):
                if self.service is not None:
                    self.service.metrics.record_counter(
                        "quarantine_reroutes", len(active) - len(healthy))
                active = healthy
        if not active:
            # transient mid-scale (every replica draining/unplaced):
            # land on any placed replica rather than failing the submit
            active = sorted((r for r in g["replicas"]
                             if r in self._pools),
                            key=self._replica_index)
        if not active:
            raise ValueError(
                f"group {g['spec'].get('name')!r} has no placed "
                "replica yet; still starting; retry shortly")
        g["route_counts"]["total"] += 1
        decode = [r for r in active
                  if g["replicas"][r]["role"] == "decode"] or active

        def sticky() -> str:
            assigned = g["tenants"].get(tenant)
            if assigned in decode:
                return assigned
            debts = self._group_debts_locked(g, decode)
            target = min(decode, key=lambda r: (debts[r], r))
            g["tenants"][tenant] = target
            return target

        if is_prefill_heavy(prompt_len, policy.prefill_len_threshold):
            g["route_counts"]["prefill"] += 1
            pre = [r for r in active
                   if g["replicas"][r]["role"] == "prefill"]
            if len(pre) > 1:
                # measured-TTFT routing (ISSUE 20 satellite): soft-state
                # ship-time EWMAs; unsampled replicas sort as 0.0 so they
                # attract traffic until measured, and with no samples at
                # all the key degenerates to the replica index — the
                # pre-EWMA order
                pre.sort(key=lambda r: (
                    self._ttft_ewma.get(r, (0.0, 0))[0],
                    self._replica_index(r)))
            has_decode = any(g["replicas"][r]["role"] == "decode"
                             for r in active)
            if pre and has_decode \
                    and int(g["spec"].get("kv_block_size") or 0) > 0:
                g["route_counts"]["handoff"] = (
                    g["route_counts"].get("handoff", 0) + 1)
                return sticky(), pre[0]
            if pre:
                return pre[0], None
        return sticky(), None

    def _observe_ttft(self, replica: str, seconds: float) -> None:
        """Record one measured prefill ship time for a prefill replica.
        Manager-local soft state (NOT journaled/wired): after failover
        the adopter simply starts cold and routing degrades to the
        replica-index order until it re-measures."""
        with self._lock:
            ewma, n = self._ttft_ewma.get(replica, (0.0, 0))
            ewma = seconds if n == 0 else 0.7 * ewma + 0.3 * seconds
            self._ttft_ewma[replica] = (ewma, n + 1)

    def _group_submit(self, name: str, prompt: list[int], max_new: int,
                      *, temperature: float, top_p: float, top_k: int,
                      presence_penalty: float, frequency_penalty: float,
                      stop: list[list[int]] | None, seed: int | None,
                      tenant: str, priority: str,
                      deadline_ms: float | None, idem_key: str | None,
                      trace: tuple | None) -> int:
        """Route a group submission to a replica and book the group-level
        id mapping. Group ids are their own sequence (next_grid); the
        seed defaults to the GROUP id so a post-failover replay is
        token-exact no matter which replica re-serves it."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                raise ValueError(f"no managed pool {name!r}; "
                                 "lm_serve (placement=auto) first")
            if idem_key is not None:
                prior = g["idem"].get(idem_key)
                if prior is not None:
                    return int(prior)
            rname, pre_rname = self._route_group_locked(
                g, len(prompt), str(tenant))
            grid = g["next_grid"]
            g["next_grid"] += 1
            if idem_key is not None:
                g["idem"][idem_key] = grid
        try:
            rid = self.submit(
                rname, prompt, max_new, temperature=temperature,
                top_p=top_p, top_k=top_k,
                presence_penalty=presence_penalty,
                frequency_penalty=frequency_penalty, stop=stop,
                seed=seed if seed is not None else grid,
                tenant=tenant, priority=priority,
                deadline_ms=deadline_ms, idem_key=None, trace=trace,
                handoff_from=pre_rname)
        except BaseException:
            with self._lock:
                g2 = self._groups.get(name)
                if (g2 is not None and idem_key is not None
                        and g2["idem"].get(idem_key) == grid):
                    del g2["idem"][idem_key]
            raise
        with self._lock:
            g2 = self._groups.get(name)
            if g2 is not None:
                # [replica, replica-rid, delivered]
                g2["rid_map"][grid] = [rname, rid, False]
        return grid

    def _group_poll(self, name: str) -> dict[str, Any]:
        """Merge every replica's poll, remapping ids to group ids. Same
        deferred-prune discipline as the pool poll: a mapping delivered
        now survives one more replication cycle before pruning."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                raise ValueError(f"no managed pool {name!r}")
            pruned = {grid for grid, ent in g["rid_map"].items()
                      if ent[2]}
            for grid in pruned:
                del g["rid_map"][grid]
            if pruned and g["idem"]:
                g["idem"] = {k: v for k, v in g["idem"].items()
                             if v not in pruned}
            replicas = sorted(g["replicas"], key=self._replica_index)
            rev = {(ent[0], int(ent[1])): grid
                   for grid, ent in g["rid_map"].items()}
        merged: dict[str, Any] = {"completions": []}
        delivered: set[int] = set()

        def remap(r: str, rid: int) -> int | None:
            grid = rev.get((r, int(rid)))
            if grid is not None:
                delivered.add(grid)
            return grid

        for r in replicas:
            try:
                out = self.poll(r)
            except ValueError:
                continue      # replica not placed yet / just retired
            for c in out.get("completions", ()):
                grid = remap(r, c["id"])
                if grid is not None:
                    merged["completions"].append(dict(c, id=grid))
            for e in out.get("errors", ()):
                m = _ERR_RE.match(str(e))
                grid = remap(r, int(m.group(1))) if m else None
                if grid is not None:
                    merged.setdefault("errors", []).append(
                        f"request {grid} failed: {m.group(2)}")
                elif not m:
                    merged.setdefault("errors", []).append(f"{r}: {e}")
            for key in ("cancelled", "expired"):
                for rid in out.get(key, ()):
                    grid = remap(r, rid)
                    if grid is not None:
                        merged.setdefault(key, []).append(grid)
            for s in out.get("shed", ()):
                grid = remap(r, s["id"])
                if grid is not None:
                    merged.setdefault("shed", []).append(
                        dict(s, id=grid))
        if delivered:
            with self._lock:
                g2 = self._groups.get(name)
                if g2 is not None:
                    for grid in delivered:
                        ent = g2["rid_map"].get(grid)
                        if ent is not None:
                            ent[2] = True
        return merged

    def _group_partial(self, name: str) -> dict[str, Any]:
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                raise ValueError(f"no managed pool {name!r}")
            replicas = sorted(g["replicas"], key=self._replica_index)
            rev = {(ent[0], int(ent[1])): grid
                   for grid, ent in g["rid_map"].items()}
        rows, sheds = [], []
        for r in replicas:
            try:
                out = self.partial(r)
            except ValueError:
                continue
            for row in out.get("partial", ()):
                grid = rev.get((r, int(row["id"])))
                if grid is not None:
                    rows.append(dict(row, id=grid, replica=r))
            sheds.extend(out.get("sheds", ()))
        reply: dict[str, Any] = {"partial": rows}
        if sheds:
            reply["sheds"] = sheds
        return reply

    def _group_stats(self, name: str) -> dict[str, Any]:
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                raise ValueError(f"no managed pool {name!r}")
            meta = {r: dict(m) for r, m in g["replicas"].items()}
        out: dict[str, Any] = {"group": True, "replicas": {}}
        journal: dict[str, int] = {}
        for r in sorted(meta, key=self._replica_index):
            try:
                st = self.stats(r)
            except ValueError:
                continue
            out["replicas"][r] = dict(st, role=meta[r]["role"],
                                      state=meta[r]["state"])
            for k, v in st.get("journal", {}).items():
                journal[k] = journal.get(k, 0) + int(v)
        out["journal"] = journal
        with self._lock:
            g = self._groups.get(name)
            if g is not None:
                out["tenants"] = dict(g["tenants"])
                out["route_counts"] = dict(g["route_counts"])
        return out

    def _group_qos(self, name: str) -> dict[str, Any]:
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                raise ValueError(f"no managed pool {name!r}")
            group_block = {
                "policy": dict(g["policy"]),
                "replicas": {r: dict(m)
                             for r, m in g["replicas"].items()},
                "tenants": dict(g["tenants"]),
                "route_counts": dict(g["route_counts"]),
                "decisions": [dict(d) for d in g["decisions"][-10:]],
                "decisions_total": g["next_seq"]}
            replicas = sorted(g["replicas"], key=self._replica_index)
        # forecast gauges (ISSUE 18): the predictive scale-ahead's view
        # of this group — predicted arrival rate + spawns it triggered
        group_block["forecast"] = self.autoscaler.forecast_view(name)
        out: dict[str, Any] = {"group": group_block, "replicas": {}}
        for r in replicas:
            try:
                out["replicas"][r] = self.qos(r)
            except ValueError:
                pass
        return out

    def _group_stop(self, name: str) -> dict[str, Any]:
        with self._lock:
            g = self._groups.pop(name, None)
        if g is None:
            return {"stopped": False}
        replicas = sorted(g["replicas"], key=self._replica_index)
        for r in replicas:
            self.stop(r)
        return {"stopped": True, "replicas": replicas}

    def autoscale_get(self, name: str) -> dict[str, Any]:
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                raise ValueError(f"no replica group {name!r}")
            return {"policy": dict(g["policy"]),
                    "replicas": {r: dict(m)
                                 for r, m in g["replicas"].items()},
                    "decisions": [dict(d) for d in g["decisions"][-20:]],
                    "decisions_total": g["next_seq"]}

    def autoscale_set(self, name: str,
                      updates: dict[str, Any]) -> dict[str, Any]:
        """Update the group's policy (the lm_autoscale verb). Journaled
        as a (dwell-exempt) decision, so failover replays the policy
        exactly like any other scaling state."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                raise ValueError(f"no replica group {name!r}")
            policy = AutoscalePolicy.from_wire(g["policy"]).merged(
                dict(updates))
            g["policy"] = policy.to_wire()
            decision = self._record_decision_locked(
                name, g, "policy", dwell=False, policy=policy.to_wire())
        self._replicate_scale(name, decision)
        return {"policy": policy.to_wire()}

    def group_view(self, name: str) -> dict[str, Any] | None:
        """Consistent read-only snapshot for one autoscaler tick: parsed
        policy, per-replica state/role/drain-time plus the UNDELIVERED
        journal count (the retire gate), the dwell anchor, route counts
        and current WFQ debts. None when the group doesn't exist."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                return None
            replicas: dict[str, Any] = {}
            for r, m in g["replicas"].items():
                pool = self._pools.get(r)
                undelivered = 0
                if pool is not None:
                    undelivered = sum(
                        1 for q in pool["requests"].values()
                        if not q["delivered"])
                replicas[r] = {"state": m["state"], "role": m["role"],
                               "t_drain": m["t_drain"],
                               "undelivered": undelivered,
                               "node": (pool or {}).get("node")}
            decode = [r for r, m in g["replicas"].items()
                      if m["state"] == "active" and m["role"] == "decode"]
            return {"policy": AutoscalePolicy.from_wire(g["policy"]),
                    "replicas": replicas,
                    "t_last_decision": g["t_last_decision"],
                    "route_counts": dict(g["route_counts"]),
                    "debts": self._group_debts_locked(g, decode)}

    def group_gauges(self, name: str) -> dict[str, Any]:
        """Live per-replica gauges for the autoscaler: the node
        gateway's interactive p95 queue wait (the Clockwork SLO signal)
        + its sample count, and the journal backlog. An unreachable or
        gateway-less replica reports n=0 — no samples can never trigger
        a scale-out."""
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                return {}
            targets = []
            for r, m in g["replicas"].items():
                if m["state"] != "active":
                    continue
                pool = self._pools.get(r)
                node = pool["node"] if pool is not None else None
                backlog = 0
                if pool is not None:
                    backlog = sum(
                        1 for q in pool["requests"].values()
                        if q["status"] in (_PENDING, _INFLIGHT))
                targets.append((r, node, backlog))
        out: dict[str, Any] = {}
        for r, node, backlog in targets:
            p95, n = 0.0, 0
            admitted: dict[str, int] = {}
            if node is not None:
                try:
                    qos = self._call(
                        node, {"verb": "lm_qos", "name": r},
                        timeout=10.0).get("qos")
                except (TransportError, ValueError, OSError):
                    qos = None
                classes = (qos or {}).get("classes") or {}
                w = (classes.get("interactive") or {}).get(
                    "queue_wait_s") or {}
                p95 = float(w.get("p95", 0.0))
                n = int(w.get("n", 0))
                # cumulative per-class admissions: the predictive
                # scale-ahead's arrival-rate signal (ISSUE 18)
                admitted = {c: int((cls or {}).get("admitted", 0))
                            for c, cls in classes.items()}
                # service-level health feed (ISSUE 20): the replica's
                # interactive p95 lands in the differential ledger as a
                # second breach channel beside raw RPC latency (the
                # ledger ignores it until a transport activated it)
                if n > 0 and p95 > 0.0:
                    health = getattr(self.membership, "health", None)
                    if health is not None:
                        health.observe_service(node, p95)
            out[r] = {"interactive_p95": p95, "n": n,
                      "backlog": backlog, "admitted": admitted}
        return out

    def _ensure_group_replicas(self) -> None:
        """Re-establish group replicas an adopted snapshot predated: an
        ACTIVE replica with no pool entry is re-served from the group
        spec (serve() is name-idempotent, so this can never double-
        place — the chaos invariant); a DRAINING one with no pool has no
        journal left to drain and retires."""
        with self._lock:
            missing, finished = [], []
            for name, g in self._groups.items():
                policy = AutoscalePolicy.from_wire(g["policy"])
                for r, m in g["replicas"].items():
                    if r in self._pools:
                        continue
                    if m["state"] == "active":
                        rspec = dict(g["spec"], name=r)
                        rspec.setdefault("model", name)
                        if (m["role"] == "prefill"
                                and policy.prefill_chunk > 0):
                            rspec["prefill_chunk"] = int(
                                policy.prefill_chunk)
                        missing.append(rspec)
                    else:
                        finished.append((name, r))
        for rspec in missing:
            try:
                self.serve(rspec)
            except (TransportError, ValueError, OSError):
                pass            # pump retries next period
        for name, r in finished:
            self.group_retire(name, r)

    @staticmethod
    def _group_from_wire(d: dict[str, Any]) -> dict[str, Any]:
        return {"spec": dict(d["spec"]), "policy": dict(d["policy"]),
                "replicas": {r: dict(m) for r, m
                             in d.get("replicas", {}).items()},
                "next_replica": int(d.get("next_replica", 0)),
                "tenants": dict(d.get("tenants", {})),
                "next_grid": int(d.get("next_grid", 0)),
                "rid_map": {int(grid): list(ent) for grid, ent
                            in d.get("rid_map", {}).items()},
                "idem": {k: int(v) for k, v
                         in d.get("idem", {}).items()},
                "decisions": [dict(x) for x in d.get("decisions", ())],
                "next_seq": int(d.get("next_seq", 0)),
                "t_last_decision": float(d.get("t_last_decision", 0.0)),
                "route_counts": dict(d.get(
                    "route_counts", {"total": 0, "prefill": 0}))}

    def _group_wire_locked(self, g: dict[str, Any]) -> dict[str, Any]:
        return {"spec": dict(g["spec"]), "policy": dict(g["policy"]),
                "replicas": {r: dict(m)
                             for r, m in g["replicas"].items()},
                "next_replica": int(g["next_replica"]),
                "tenants": dict(g["tenants"]),
                "next_grid": int(g["next_grid"]),
                "rid_map": {str(grid): list(ent)
                            for grid, ent in g["rid_map"].items()},
                "idem": dict(g["idem"]),
                "decisions": [dict(d) for d in g["decisions"]],
                "next_seq": int(g["next_seq"]),
                "t_last_decision": float(g["t_last_decision"]),
                "route_counts": dict(g["route_counts"])}

    def apply_scale_wal(self, deltas: dict[str, Any],
                        keep_scope=None) -> None:
        """Adoption-time replay of scale-WAL deltas (failover.py). Each
        delta carries the group's full wire entry at decision time;
        apply any strictly newer than the adopted snapshot — the
        decision journal is append-only, so 'newer' is just a longer
        log (next_seq). ``keep_scope`` filters to the group scopes this
        host actually adopts (scope-scoped adoption, ISSUE 15)."""
        with self._lock:
            for name, d in sorted(deltas.items()):
                entry = d.get("entry")
                if not entry:
                    continue
                if keep_scope is not None \
                        and not keep_scope(pool_scope(name)):
                    continue
                cur = self._groups.get(name)
                if (cur is None or int(cur["next_seq"])
                        < int(entry.get("next_seq", 0))):
                    self._groups[name] = self._group_from_wire(entry)

    # -- train jobs --------------------------------------------------------

    def train(self, spec: dict[str, Any]) -> dict[str, Any]:
        """Place a training job on the least-loaded alive node; on that
        node's death the job restarts on a survivor with resume=True,
        continuing from its last store checkpoint."""
        spec = {k: v for k, v in spec.items()
                if k not in ("verb", "placement", "local", "resume")}
        name = spec["name"]
        with self._lock:
            job = self._jobs.get(name)
            if job is not None and not self._job_over(job):
                raise ValueError(f"training job {name!r} already running "
                                 f"on {job['node']}")
            # _recovering guards the initial build exactly as in serve():
            # without it the pump sees node=None mid-build and _recover_job
            # starts a SECOND copy of the job (resume=True) on another
            # node — two jobs burning two chips, one unaccounted
            entry = {"spec": dict(spec), "node": None,
                     "_recovering": True,
                     "status": None, "stop_requested": False}
            self._jobs[name] = entry
        try:
            node = self._place()
            self._call(node, dict(spec, verb="train_start"),
                       timeout=self.build_rpc_timeout_s)
        except BaseException:
            with self._lock:
                # identity, not name (see serve()): a replaced-generation
                # entry must not be destroyed by this build's cleanup
                if self._jobs.get(name) is entry:
                    del self._jobs[name]
            raise
        with self._lock:
            # commit node + clear the build guard atomically, and only
            # into THIS build's entry (as serve()): after a stop + re-train
            # the name maps to a new generation still mid-build
            if self._jobs.get(name) is entry:
                entry["node"] = node
                entry["_recovering"] = False
                stale_node = None
            else:
                stale_node = node
        if stale_node is not None:
            # the job this build started answers to nobody — stop it
            # (best-effort; a chip-burning unaccounted trainer otherwise)
            try:
                self._call(stale_node, {"verb": "train_stop",
                                        "name": name}, timeout=10.0)
            except (TransportError, ValueError, OSError):
                pass
            return {"started": False, "stopped": True, "node": None}
        return {"started": True, "node": node}

    def train_status(self, name: str) -> dict[str, Any]:
        with self._lock:
            job = self._jobs.get(name)
            if job is None:
                raise ValueError(f"no managed training job {name!r}")
            node, cached = job["node"], job["status"]
        if node is not None:
            try:
                st = self._call(node, {"verb": "train_status",
                                       "name": name})
                with self._lock:
                    if name in self._jobs:
                        self._jobs[name]["status"] = st
                return dict(st, node=node)
            except (TransportError, ValueError, OSError):
                pass
        return dict(cached or {}, node=node, stale=True)

    def train_stop(self, name: str) -> dict[str, Any]:
        """Record the stop intent FIRST (so a dead/unreachable node can
        never turn an explicit stop into an auto-resume), then best-effort
        stop the node-local job; the pump retries unconfirmed stops."""
        with self._lock:
            job = self._jobs.get(name)
            if job is None:
                return {"stopped": False}
            job["stop_requested"] = True
            node = job["node"]
        out: dict[str, Any] = {"stopped": True}
        if node is not None:
            try:
                out = self._call(node, {"verb": "train_stop",
                                        "name": name})
                out["stopped"] = True
            except (TransportError, ValueError, OSError) as e:
                out["pending"] = f"node {node} unreachable ({e}); " \
                                 "stop is recorded and will be retried"
        with self._lock:
            if name in self._jobs and out.get("status"):
                self._jobs[name]["status"] = out["status"]
        return out

    def has_job(self, name: str) -> bool:
        with self._lock:
            return name in self._jobs

    # -- pump: runs on the acting master's master loop ---------------------

    def pump_once(self) -> None:
        """Forward pending requests, drain completions, refresh job
        status. All RPCs outside the lock.

        Multi-owner gate (ISSUE 15): any host holding pool scopes pumps
        ITS pools/groups — scope owners are full control planes for their
        journals, not passive standbys. Train jobs and the cluster-wide
        fair share stay acting-master duties (they arbitrate the shared
        CNN+LM capacity, which has exactly one arbiter)."""
        master = self.membership.is_acting_master
        self._step_down_moved_scopes()
        now = self.wall()
        with self._lock:
            has_lm = bool(self._pools or self._groups)
        if not master and not has_lm:
            return
        with self._lock:
            for pool in self._pools.values():
                self._requeue_stale_locked(pool, now)
            pools = {n: (p["node"],
                         [(rid, dict(r)) for rid, r in
                          sorted(p["requests"].items())
                          if r["status"] == _PENDING])
                     for n, p in self._pools.items()}
            jobs = ([(n, j["node"]) for n, j in self._jobs.items()
                     if not self._job_over(j)] if master else [])
            # stop-requested jobs whose node never confirmed: retry the
            # stop (the job may still be burning its node's chip)
            stop_retries = [
                (n, j["node"]) for n, j in self._jobs.items()
                if j.get("stop_requested") and j["node"] is not None
                and not ((j.get("status") or {}).get("stopped")
                         or (j.get("status") or {}).get("done")
                         or (j.get("status") or {}).get("error"))] \
                if master else []
        for name, (node, pending) in pools.items():
            if node is None:
                self._recover_pool(name)
                continue
            for rid, req in pending:
                ho = req.get("handoff")
                if ho and ho.get("state") in ("prefilling", "shipping"):
                    # replay-or-fallback: a death (ours or a peer's) mid-
                    # handoff left the journaled state non-terminal — the
                    # ship is idempotent, re-run it before the forward
                    self._handoff_ship(name, node, rid, req)
                self._forward(name, node, rid, req)
            self._drain(name, node)
        for name, node in jobs:
            if node is None:
                self._recover_job(name)
                continue
            try:
                st = self._call(node, {"verb": "train_status",
                                       "name": name}, timeout=10.0)
            except (TransportError, ValueError, OSError):
                continue
            with self._lock:
                if name in self._jobs:
                    self._jobs[name]["status"] = st
        for name, node in stop_retries:
            try:
                out = self._call(node, {"verb": "train_stop",
                                        "name": name}, timeout=10.0)
            except (TransportError, ValueError, OSError):
                continue
            with self._lock:
                if name in self._jobs and out.get("status"):
                    self._jobs[name]["status"] = out["status"]
        with self._lock:
            have_groups = bool(self._groups)
        if have_groups:
            # replica-group upkeep + the closed capacity loop — both run
            # only here, so they inherit the owner/master gate above
            self._ensure_group_replicas()
            self.autoscaler.tick()
        if master:
            self._update_fair_share()

    # -- heterogeneous fair share (round-2 VERDICT item 4) -----------------

    @staticmethod
    def _avg_request_s(pool: dict[str, Any]) -> float:
        s = pool["svc_samples"]
        return sum(x for x, _ in s) / len(s) if s else 0.0

    def allocation_view(self) -> dict[str, Any]:
        """c1/c2-style arbitration report: measured per-unit seconds and
        the fair worker-unit share for every live job — CNN query jobs
        (avg seconds per query) and LM decode pools (avg seconds per
        request, per-token breakdown included) — via the reference ratio
        formula generalized over the job union
        (`scheduler/fair.py:heterogeneous_shares`)."""
        from idunno_tpu.scheduler.fair import heterogeneous_shares

        n_workers = len(self.membership.members.alive_hosts())
        sched = self.service.scheduler if self.service else None
        cnn = {}
        if sched is not None:
            cnn = {m: sched.avg_query_time.get(m, 0.0)
                   for m in sched.active_models()}
        with self._lock:
            lm = {n: self._avg_request_s(p)
                  for n, p in self._pools.items()
                  if p["node"] is not None}
            tok = {n: (sum(s for s, _ in p["svc_samples"])
                       / max(sum(t for _, t in p["svc_samples"]), 1))
                   for n, p in self._pools.items() if p["svc_samples"]}
            slots = {n: p["slots_now"] for n, p in self._pools.items()}
        shares = heterogeneous_shares(cnn, lm, self.config.rate_factor,
                                      n_workers)
        jobs: dict[str, Any] = {}
        for m, t in cnn.items():
            jobs[f"cnn:{m}"] = {"avg_query_s": round(t, 4),
                                "share": shares.get(f"cnn:{m}", 0)}
        for n, t in lm.items():
            jobs[f"lm:{n}"] = {"avg_request_s": round(t, 4),
                               "avg_token_s": round(tok.get(n, 0.0), 5),
                               "share": shares.get(f"lm:{n}", 0),
                               "slots": slots.get(n)}
        return {"rate_factor": self.config.rate_factor,
                "n_workers": n_workers, "jobs": jobs}

    def _update_fair_share(self) -> None:
        """Apply the arbitration: feed each pool's measured per-request
        seconds into the CNN scheduler (whose assign() then computes
        shares over the job UNION, shrinking CNN worker counts while
        pools run), and resize each pool's slots toward its fair FRACTION
        of its own slot capacity. Slots are per-device batch rows, not
        workers, so the absolute worker-clamped share is the wrong scale
        (ADVICE r3: a lone 16-slot pool on a 1-node cluster must keep 16
        slots, not shrink to 1); a pool with no competing job keeps its
        full spec untouched. A resize rebuilds the pool (recompile), so it
        needs the same target on two consecutive pumps (hysteresis), a
        ``resize_dwell_s`` gap since the last applied resize (a rate
        hovering on a share boundary must not thrash), and can be pinned
        off per pool with spec ``fixed_slots=True``."""
        if self.service is None:
            return
        with self._lock:
            rates = {n: self._avg_request_s(p)
                     for n, p in self._pools.items()
                     if p["node"] is not None}
        self.service.scheduler.extra_jobs = {
            f"lm:{n}": t for n, t in rates.items()}
        if not rates:
            return
        view = self.allocation_view()
        jobs = view["jobs"]
        total_share = sum(j["share"] for j in jobs.values()) or 1
        now = self.wall()
        resize = []
        with self._lock:
            for name, pool in self._pools.items():
                job = jobs.get(f"lm:{name}")
                if (job is None or pool["node"] is None
                        or pool["spec"].get("fixed_slots")):
                    continue
                if len(jobs) == 1:
                    # the only measured job in the cluster — nothing to
                    # arbitrate against; full user-specced capacity
                    target = pool["slots_cap"]
                else:
                    # slots_cap is the user's spec — the pool may shrink
                    # below it while other jobs run and grow back, never
                    # beyond
                    frac = job["share"] / total_share
                    target = max(1, min(pool["slots_cap"],
                                        round(frac * pool["slots_cap"])))
                if (target != pool["slots_now"]
                        and target == pool["slots_target_prev"]
                        and now - pool.get("t_last_resize", 0.0)
                        >= self.resize_dwell_s):
                    resize.append((name, pool["node"], target))
                pool["slots_target_prev"] = target
        for name, node, target in resize:
            self._resize_pool(name, node, target)

    def _resize_pool(self, name: str, node: str, target: int) -> None:
        """Rebuild a resized pool IN PLACE on its current node:
        ``lm_serve reload=True`` makes the node stop the old serving loop
        before starting the new one, so nothing keeps decoding into a
        dead outbox or holding HBM (ADVICE r3 — re-placing via the
        recovery path could land on a DIFFERENT node and leak the old
        node's live loop). The manager's slot bookkeeping commits only
        AFTER the node confirms the rebuild — a bail-out (concurrent
        recovery, a racing build's _Starting reservation answering
        "already", node failure) must leave manager and node agreeing on
        the OLD slot count, with the hysteresis free to retry. Only if
        the node itself fails does this fall back to orphan + recovery."""
        with self._lock:
            entry = self._pools.get(name)
            if (entry is None or entry["node"] != node
                    or entry.get("_recovering")):
                return
            entry["_recovering"] = True
            spec = dict(entry["spec"], slots=target)
        try:
            try:
                out = self._call(node, dict(spec, verb="lm_serve",
                                            reload=True),
                                 timeout=self.build_rpc_timeout_s,
                                 scope=pool_scope(name))
            except (TransportError, ValueError, OSError):
                with self._lock:
                    if (self._pools.get(name) is entry
                            and entry["node"] == node):
                        self._orphan_pool_locked(name)
                return                  # pump re-places on a survivor
            if out.get("already") or out.get("stopped"):
                # 'already': a racing build holds the name's _Starting
                # reservation; 'stopped': an lm_stop won the race mid-
                # build and the fresh loop was immediately torn down. In
                # both cases nothing is serving the NEW slot count — keep
                # the old bookkeeping everywhere and let a later pump
                # (or the stop) settle it
                return
            with self._lock:
                # identity check: stopped (or replaced by a re-serve
                # generation) while the rebuild RPC ran means the fresh
                # loop answers to nobody — stop it (an lm_stop that landed
                # mid-build was already handled by the 'stopped' reply)
                stale = (self._pools.get(name) is not entry
                         or entry["node"] != node)
                if not stale:
                    entry["spec"]["slots"] = target
                    entry["slots_now"] = target
                    entry["t_last_resize"] = self.wall()
                    # the replaced loop dropped its in-flight requests;
                    # requeue for token-exact replay. attempts reset: a
                    # pool-level rebuild (and its recompile) must not
                    # consume a request's suspicion budget (ADVICE r3)
                    for req in entry["requests"].values():
                        if req["status"] == _INFLIGHT:
                            req["status"] = _PENDING
                            req["node_id"] = None
                            req["attempts"] = 0
                    pending = [(rid, dict(r)) for rid, r in
                               sorted(entry["requests"].items())
                               if r["status"] == _PENDING]
            if stale:
                self._stop_stale_loop(node, name)
                return
            for rid, req in pending:
                self._forward(name, node, rid, req)
        finally:
            with self._lock:
                # clear only THIS generation's guard: a replacement
                # entry's in-flight build must stay guarded
                if self._pools.get(name) is entry:
                    entry["_recovering"] = False

    def _requeue_stale_locked(self, pool: dict[str, Any],
                              now: float) -> None:
        """Watchdog: an inflight request can wedge without its node dying
        (the node's error list is a destructive read a failed poll can
        consume; a drained lm_poll reply can be lost to a timeout).
        Requeue anything inflight past its effective timeout — the base
        ``request_timeout_s`` stretched by the request's own expected
        decode time at the pool's measured per-token rate PLUS the
        expected node-side queue wait for the pool's current backlog
        (service-time samples no longer bake queue wait in, so the
        watchdog must model it: a large max_new behind a deep queue, or
        a from-scratch recompile after recovery, is slow with nothing
        wrong — ADVICE r3). FAIL after max_request_attempts forwards."""
        s = pool["svc_samples"]
        tok_s = (sum(x for x, _ in s) / max(sum(t for _, t in s), 1)
                 if s else 0.0)
        per_req_s = self._avg_request_s(pool)
        # no completions yet = no measured rate to stretch with, but the
        # FIRST requests are exactly the ones paying the from-scratch
        # compile — grant the build allowance instead of the bare base
        first_req_grace = 0.0 if s else self.build_rpc_timeout_s
        n_inflight = sum(1 for r in pool["requests"].values()
                         if r["status"] == _INFLIGHT)
        slots = max(int(pool.get("slots_now", 1)), 1)
        backlog_wait = per_req_s * (n_inflight / slots)
        for rid, req in pool["requests"].items():
            if req["status"] != _INFLIGHT:
                continue
            eff = (self.request_timeout_s + first_req_grace
                   + self.request_timeout_slack * (
                       req["max_new"] * tok_s + backlog_wait))
            if now - (req["t_forwarded"] or now) < eff:
                continue
            if req["attempts"] >= self.max_request_attempts:
                req["status"] = _FAILED
                req["error"] = (f"no completion after {req['attempts']} "
                                f"forwards x {eff:.0f}s")
                pool["failed_total"] += 1
            else:
                req["status"] = _PENDING
                req["node_id"] = None

    def _drain(self, name: str, node: str) -> None:
        # scoped: draining CONSUMES the node outbox (ownership transfers
        # to the poller), so a deposed pool owner must be fenced here or
        # it would steal completions the scope's new owner journals
        try:
            out = self._call(node, {"verb": "lm_poll", "name": name},
                             timeout=10.0, scope=pool_scope(name))
        except (TransportError, ValueError, OSError):
            return
        if not (out.get("completions") or out.get("errors")):
            return
        with self._lock:
            pool = self._pools.get(name)
            if pool is None or pool["node"] != node:
                return                  # stopped or re-placed mid-drain
            for e in out.get("errors", ()):
                # node-side loop errors are request-anonymous; keep them
                # for stats/debugging (the watchdog above unsticks any
                # request they wedged)
                if len(pool["node_errors"]) < 100:
                    pool["node_errors"].append(str(e))
            by_node_id = {r["node_id"]: r
                          for r in pool["requests"].values()
                          if r["status"] == _INFLIGHT}
            now = self.wall()
            for c in out.get("completions", ()):
                req = by_node_id.get(int(c["id"]))
                if req is not None:
                    if c.get("cancelled"):
                        # out-of-band node-side cancel (a local=True
                        # lm_cancel bypassing this manager): journal it as
                        # cancelled, and keep its partial service time out
                        # of the fair-share samples
                        req["status"] = _CANCELLED
                        req["node_id"] = None
                        pool["cancelled_total"] += 1
                        continue
                    if c.get("rejected") == "expired":
                        # the deadline passed in the gateway queue —
                        # journal-terminal (never replayed), no service
                        # sample: the request never reached a slot
                        req["status"] = _EXPIRED
                        req["node_id"] = None
                        pool["expired_total"] += 1
                        continue
                    req["status"] = _DONE
                    req["tokens"] = [int(t) for t in c["tokens"]]
                    req["prompt_len"] = int(c["prompt_len"])
                    if c.get("logprobs") is not None:
                        req["logprobs"] = [float(x)
                                           for x in c["logprobs"]]
                    req["service_s"] = round(
                        float(c.get("service_s", 0.0)), 6)
                    req["node_id"] = None
                    pool["done_total"] += 1
                    new_toks = len(req["tokens"]) - req["prompt_len"]
                    # fair-share signal: node-measured SERVICE time (slot
                    # admission → retirement), not master-side sojourn — a
                    # backlogged pool must not measure slower and grow its
                    # own share (round-3 VERDICT weak #4; the reference
                    # normalizes processing time, not queue time,
                    # `mp4_machinelearning.py:656-674`). Sojourn fallback
                    # only for a node predating the field.
                    svc = float(c.get("service_s", 0.0))
                    if svc <= 0.0:
                        svc = now - req["t_submitted"]
                    # cold-start completions funded the pool's one-time
                    # compiles (VERDICT item 4): their service time is
                    # capacity planning, not steady-state cost — keep
                    # them out of the fair-share/autoscaler demand signal
                    # (a warmup=True pool never produces one)
                    if not c.get("cold_start"):
                        pool["svc_samples"].append((svc, max(new_toks, 1)))
                        del pool["svc_samples"][:-32]    # rolling window
        # drained completions are unrecoverable from the node — write the
        # terminal transitions ahead so an adoption between here and the
        # next snapshot re-delivers instead of re-decoding
        self._replicate_pool(name)

    # -- recovery ----------------------------------------------------------

    def _on_member_change(self, host: str, old, new) -> None:
        if new is not MemberStatus.LEAVE:
            return
        # multi-owner gate (ISSUE 15): every manager holding pools — the
        # acting master AND every scope owner — recovers its own placed
        # nodes; a non-master owner must not strand a dead pool node
        if not (self.membership.is_acting_master
                or self._scope_names_nonempty()):
            return
        with self._lock:
            dead_pools = [n for n, p in self._pools.items()
                          if p["node"] == host]
            for n in dead_pools:
                self._orphan_pool_locked(n)
            dead_jobs = [n for n, j in self._jobs.items()
                         if j["node"] == host and not self._job_over(j)]
            for n in dead_jobs:
                self._jobs[n]["node"] = None
        if not (dead_pools or dead_jobs):
            return

        # re-place off-thread: this callback runs on the membership monitor
        # loop, and a pool rebuild (store fetch + device alloc) must not
        # stall failure detection for other hosts. pump_once retries any
        # recovery that fails here.
        def _recover():
            for n in dead_pools:
                self._recover_pool(n)
            for n in dead_jobs:
                self._recover_job(n)

        threading.Thread(target=_recover, daemon=True,
                         name=f"{self.host}-lm-recover").start()

    def _orphan_pool_locked(self, name: str) -> None:
        pool = self._pools[name]
        pool["node"] = None
        for req in pool["requests"].values():
            if req["status"] == _INFLIGHT:
                req["status"] = _PENDING
                req["node_id"] = None
                # pool-level requeue: the request did nothing wrong, and
                # the recovery rebuild's recompile must not eat into its
                # per-request suspicion budget (ADVICE r3)
                req["attempts"] = 0
            # a handoff adopted INTO the dead node is gone with it: the
            # re-placed pool holds no blocks, so re-enter the state
            # machine (the recovery re-ships to the new node; fallback
            # rows stay terminal — the prefill side already failed once)
            hop = req.get("handoff")
            if (hop and req["status"] == _PENDING
                    and hop.get("state") in ("shipping", "adopted")):
                hop["state"] = "prefilling"

    def _recover_pool(self, name: str) -> None:
        """Re-establish an orphaned pool on a survivor and resubmit every
        unfinished request (token-exact: seeds were pinned at admission).

        Prefix-cache pools recover the same way: kv_block_size /
        kv_cache_blocks ride the journaled spec, so the rebuilt pool has
        the same paged-cache config but an EMPTY radix tree — resubmitted
        requests cold-miss and recompute their own KV (never replaying
        another node's blocks), keeping the token-exactness contract
        (`tests/test_prefix_cache.py` rebuild test).

        Serialized per pool: the membership-change thread, the adoption
        thread and the pump can all reach here concurrently, and a second
        ``lm_serve reload=True`` landing on the same node would replace
        the first recovery's freshly built loop — stranding its
        just-forwarded requests as inflight ids of a dead loop until the
        watchdog times them out."""
        with self._lock:
            entry = self._pools.get(name)
            if (entry is None or entry["node"] is not None
                    or entry.get("_recovering")):
                return
            entry["_recovering"] = True
            spec = dict(entry["spec"])
        try:
            try:
                node = self._place()
                self._call(node, dict(spec, verb="lm_serve", reload=True),
                           timeout=self.build_rpc_timeout_s,
                           scope=pool_scope(name))
            except (TransportError, ValueError, OSError):
                return                  # pump retries next period
            with self._lock:
                # identity check: stopped, or replaced by a re-serve
                # generation (whose own build must not be committed into
                # or un-guarded by this recovery), while the rebuild RPC
                # ran — the fresh loop answers to nobody, stop it
                stale = (self._pools.get(name) is not entry
                         or entry["node"] is not None)
                if not stale:
                    entry["node"] = node
                    pending = [(rid, dict(r)) for rid, r in
                               sorted(entry["requests"].items())
                               if r["status"] == _PENDING]
            if stale:
                self._stop_stale_loop(node, name)
                return
            for rid, req in pending:
                ho = req.get("handoff")
                if ho and ho.get("state") in ("prefilling", "shipping"):
                    self._handoff_ship(name, node, rid, req)
                self._forward(name, node, rid, req)
        finally:
            with self._lock:
                # clear only THIS generation's guard
                if self._pools.get(name) is entry:
                    entry["_recovering"] = False

    def _recover_job(self, name: str) -> None:
        with self._lock:
            entry = self._jobs.get(name)
            if (entry is None or entry["node"] is not None
                    or entry.get("_recovering")):
                return
            entry["_recovering"] = True   # serialized like _recover_pool
            spec = dict(entry["spec"], resume=True)
        try:
            try:
                node = self._place()
                self._call(node, dict(spec, verb="train_start"),
                           timeout=self.build_rpc_timeout_s)
            except (TransportError, ValueError, OSError):
                return
            stale_node = None
            with self._lock:
                # identity check, as _recover_pool: a stop + re-train may
                # have replaced the entry mid-rebuild
                if (self._jobs.get(name) is entry
                        and entry["node"] is None
                        and not entry.get("stop_requested")):
                    entry["node"] = node
                else:
                    stale_node = node
            if stale_node is not None:
                try:
                    self._call(stale_node, {"verb": "train_stop",
                                            "name": name}, timeout=10.0)
                except (TransportError, ValueError, OSError):
                    pass
        finally:
            with self._lock:
                # clear only THIS generation's guard
                if self._jobs.get(name) is entry:
                    entry["_recovering"] = False

    # -- failover replication ---------------------------------------------

    @staticmethod
    def _pool_wire(p: dict[str, Any]) -> dict[str, Any]:
        """Wire form of one pool's registry entry + journal — the unit
        the periodic snapshot AND the per-pool WAL replicate."""
        return {"spec": dict(p["spec"]), "node": p["node"],
                "next_rid": p["next_rid"],
                "wal_seq": int(p.get("wal_seq", 0)),
                "done_total": p["done_total"],
                "failed_total": p["failed_total"],
                "cancelled_total": p["cancelled_total"],
                "shed_total": p["shed_total"],
                "expired_total": p["expired_total"],
                "svc_samples": [list(s) for s in p["svc_samples"]],
                "slots_now": p["slots_now"],
                "slots_cap": p["slots_cap"],
                "idem": dict(p.get("idem", {})),
                "handoffs": dict(p.get("handoffs", {})),
                "requests": {str(rid): dict(r) for rid, r
                             in p["requests"].items()}}

    @staticmethod
    def _pool_from_wire(p: dict[str, Any]) -> dict[str, Any]:
        return {"spec": dict(p["spec"]), "node": p["node"],
                "next_rid": int(p["next_rid"]),
                "wal_seq": int(p.get("wal_seq", 0)),
                "done_total": int(p.get("done_total", 0)),
                "failed_total": int(p.get("failed_total", 0)),
                "cancelled_total": int(p.get("cancelled_total", 0)),
                "shed_total": int(p.get("shed_total", 0)),
                "expired_total": int(p.get("expired_total", 0)),
                "node_errors": [],
                "svc_samples": [tuple(s) for s
                                in p.get("svc_samples", ())],
                "slots_now": int(p.get(
                    "slots_now",
                    p["spec"].get("slots", _default_slots()))),
                "slots_cap": int(p.get(
                    "slots_cap",
                    p["spec"].get("slots", _default_slots()))),
                "slots_target_prev": None,
                "t_last_resize": 0.0,
                "idem": {k: int(v) for k, v
                         in p.get("idem", {}).items()},
                "handoffs": {str(k): str(v) for k, v
                             in p.get("handoffs", {}).items()},
                # defaults first: a snapshot from an older master may
                # predate the watchdog/measurement fields
                "requests": {int(rid): {"t_forwarded": None,
                                        "attempts": 0, "top_p": 1.0,
                                        "top_k": 0,
                                        "t_submitted": 0.0,
                                        "tenant": "default",
                                        "priority": "interactive",
                                        "deadline_ms": None,
                                        "admitted": False,
                                        "handoff": None,
                                        "trace": None, **dict(r)}
                             for rid, r in p["requests"].items()}}

    @staticmethod
    def _pool_delta(base: dict[str, Any],
                    cur: dict[str, Any]) -> dict[str, Any]:
        """Delta frame between two wire entries: changed scalar fields +
        changed/removed request rows since the standby's acked base.
        Linear in the mutation, not the journal depth — the full-entry
        ship was quadratic at depth (ISSUE 15 satellite)."""
        fields = {k: v for k, v in cur.items()
                  if k not in ("requests", "idem") and base.get(k) != v}
        breq, creq = base.get("requests", {}), cur.get("requests", {})
        frame = {"delta": True,
                 "base_seq": int(base.get("wal_seq", 0)),
                 "wal_seq": int(cur.get("wal_seq", 0)),
                 "fields": fields,
                 "changed": {rid: req for rid, req in creq.items()
                             if breq.get(rid) != req},
                 "removed": [rid for rid in breq if rid not in creq]}
        if cur.get("idem") != base.get("idem"):
            frame["idem"] = dict(cur.get("idem", {}))
        return frame

    @staticmethod
    def _truncate_wire(entry: dict[str, Any]) \
            -> tuple[dict[str, Any], int]:
        """Compact a wire entry below the delivered LOW-WATER MARK: the
        contiguous run of rids from the bottom of the journal whose rows
        are all journal-terminal AND delivered carries no recovery value
        (an adopter neither resubmits terminal rows nor re-delivers
        delivered ones — poll() will prune them on its next call anyway)
        so the shipped WAL segment drops them, with their idem keys,
        instead of re-shipping them on every mutation (ISSUE 17
        satellite). Only the prefix below the first live/undelivered rid
        truncates — the segment stays a contiguous journal tail, and the
        `need_full` fallback stays correct across a truncated base: a
        delta against the truncated base lists later truncations as
        ``removed`` rows, and any base gap re-ships the (truncated) full
        entry. Returns (entry, rows_truncated); the input is untouched
        when nothing truncates."""
        reqs = entry["requests"]
        live = [int(rid) for rid, q in reqs.items()
                if q["status"] in (_PENDING, _INFLIGHT)
                or not q.get("delivered")]
        lwm = min(live) if live else int(entry["next_rid"])
        drop = {rid for rid in reqs if int(rid) < lwm}
        if not drop:
            return entry, 0
        entry = dict(entry)
        entry["requests"] = {rid: q for rid, q in reqs.items()
                             if rid not in drop}
        dropped = {int(rid) for rid in drop}
        if entry.get("idem"):
            entry["idem"] = {k: v for k, v in entry["idem"].items()
                             if int(v) not in dropped}
        return entry, len(drop)

    def _replicate_pool(self, name: str) -> None:
        """Push the pool's journal mutation to its scope standby's WAL
        segment (FailoverManager.wal_pool — the journal twin of the
        scale WAL) between snapshots. ``wal_seq`` is the per-pool
        monotone the standby's keep-newest and ``apply_pool_wal`` dedupe
        on, so a replayed/duplicated delta collapses per scope.

        Ships a DELTA since the standby's last acked full entry when one
        exists; any gap (standby restarted, a frame lost, a need_full
        NACK) falls back to the full entry — correctness never depends
        on the delta chain, only the byte count does."""
        fo = self.failover
        if fo is None:
            return
        with self._lock:
            p = self._pools.get(name)
            if p is None:
                return
            p["wal_seq"] = int(p.get("wal_seq", 0)) + 1
            entry, ncut = self._truncate_wire(self._pool_wire(p))
            if ncut:
                self.wal_truncated += ncut
            base = self._wal_shipped.get(name)
        frame = entry if base is None else self._pool_delta(base, entry)
        ack = fo.wal_pool(name, frame)
        if ack is not None and ack.get("need_full") and frame is not entry:
            ack = fo.wal_pool(name, entry)
        with self._lock:
            if ack is not None and not ack.get("need_full"):
                self._wal_shipped[name] = entry
            else:
                # unacked: the standby's held base is unknown — next
                # mutation re-ships full and re-seeds the chain
                self._wal_shipped.pop(name, None)

    def apply_pool_wal(self, deltas: dict[str, Any],
                       keep_scope=None) -> int:
        """Adoption-time replay of per-pool WAL deltas (failover.py).
        Each delta carries the pool's full wire entry at mutation time
        (the standby merges delta frames on receive, so adoption never
        sees a frame); apply exactly those strictly newer (by wal_seq)
        than the adopted snapshot's copy — one pool's fresher journal
        never disturbs another's. ``keep_scope`` (scope-scoped adoption)
        filters to the scopes this host actually adopts. Returns the
        number of pools replayed."""
        n = 0
        with self._lock:
            for name, d in sorted(deltas.items()):
                entry = d.get("entry")
                if not entry or entry.get("delta"):
                    continue
                if keep_scope is not None \
                        and not keep_scope(pool_scope(name)):
                    continue
                cur = self._pools.get(name)
                if (cur is None or int(cur.get("wal_seq", 0))
                        < int(entry.get("wal_seq", 0))):
                    self._pools[name] = self._pool_from_wire(entry)
                    n += 1
        return n

    def scope_names(self) -> list[str]:
        """Every pool fence scope this manager holds state for (replica
        pools collapse into their group's scope) — the set a scoped
        adoption mints strictly-higher epochs for."""
        with self._lock:
            return sorted({pool_scope(n) for n in self._pools}
                          | {pool_scope(g) for g in self._groups})

    def to_wire(self) -> dict[str, Any]:
        with self._lock:
            return {
                "pools": {n: self._pool_wire(p)
                          for n, p in self._pools.items()},
                "jobs": {n: {"spec": dict(j["spec"]), "node": j["node"],
                             "stop_requested": bool(
                                 j.get("stop_requested")),
                             "status": dict(j["status"])
                             if j["status"] else None}
                         for n, j in self._jobs.items()},
                "groups": {n: self._group_wire_locked(g)
                           for n, g in self._groups.items()},
            }

    def load_wire(self, snap: dict[str, Any], keep_scope=None) -> None:
        """Adopt a replicated snapshot. ``keep_scope=None`` is the
        wholesale replace (the pre-ISSUE-15 standby shape). With a
        predicate, adoption is scope-scoped and MERGING: only pools/
        groups whose scope passes load, a local copy that is already
        NEWER (per-pool wal_seq / group next_seq — WAL replay may have
        landed first) is kept, and everything this manager already
        holds — a surviving owner's own scopes — stays untouched. Jobs
        always load: they are an acting-master duty, and a filtered
        load only ever runs while adopting mastership."""
        with self._lock:
            for n, p in snap.get("pools", {}).items():
                if keep_scope is not None \
                        and not keep_scope(pool_scope(n)):
                    continue
                cur = self._pools.get(n)
                if (keep_scope is not None and cur is not None
                        and int(cur.get("wal_seq", 0))
                        >= int(p.get("wal_seq", 0))):
                    continue
                self._pools[n] = self._pool_from_wire(p)
            for n, d in snap.get("groups", {}).items():
                if keep_scope is not None \
                        and not keep_scope(pool_scope(n)):
                    continue
                cur = self._groups.get(n)
                if (keep_scope is not None and cur is not None
                        and int(cur["next_seq"])
                        >= int(d.get("next_seq", 0))):
                    continue
                self._groups[n] = self._group_from_wire(d)
            self._jobs = {
                n: {"spec": dict(j["spec"]), "node": j["node"],
                    "stop_requested": bool(j.get("stop_requested")),
                    "status": dict(j["status"]) if j["status"] else None}
                for n, j in snap.get("jobs", {}).items()}
            if keep_scope is None:
                self._pools = {n: p for n, p in self._pools.items()
                               if n in snap.get("pools", {})}
                self._groups = {n: g for n, g in self._groups.items()
                                if n in snap.get("groups", {})}

    def on_adopt(self) -> None:
        """Called by the failover manager when this standby becomes the
        coordinator — per scope. A pool whose node is still ALIVE keeps
        its inflight node-id mappings and keeps serving uninterrupted:
        the per-pool WAL replicated its journal through the last terminal
        transition, the node-side idempotency key
        (``{name}:{rid}:{attempts}``) dedupes any re-forward, and the
        watchdog (``_requeue_stale_locked``) token-exactly replays the
        rare row whose drained completion the old master never
        replicated. So adopting one pool's fence costs the OTHER pools
        zero resubmission (the chaos cross-pool-isolation invariant).
        Pools/jobs on dead nodes are orphaned — inflight requeued with
        pinned seeds, exactly-once via the journal — and re-placed;
        both paths also retry from the pump."""
        alive = set(self.membership.members.alive_hosts())
        with self._lock:
            pool_names = []
            for name, pool in self._pools.items():
                if pool["node"] is not None and pool["node"] in alive:
                    continue            # scope keeps serving as-is
                self._orphan_pool_locked(name)
                pool_names.append(name)
            job_names = []
            for name, job in self._jobs.items():
                if (job["node"] is not None and job["node"] not in alive
                        and not self._job_over(job)):
                    job["node"] = None
                    job_names.append(name)
        # rebuilds + resubmissions go off-thread: adopt() is called on the
        # membership monitor loop, which must keep detecting failures (the
        # same discipline as _on_member_change); the pump retries whatever
        # fails here
        def _recover():
            for name in pool_names:
                self._recover_pool(name)
            for name in job_names:
                self._recover_job(name)

        threading.Thread(target=_recover, daemon=True,
                         name=f"{self.host}-lm-adopt").start()
