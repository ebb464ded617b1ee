"""Host-side resilience: survive a sidecar that crashes, restarts, OR hangs.

The reference scheduler is stateless across restarts — etcd is the truth
and a restarted scheduler rebuilds cache+queue from informer LIST+WATCH
(app/server.go:249–271 informers Start + WaitForCacheSync).  In the
two-tier split, the HOST holds that informer truth and the sidecar's
device state is a pure cache of it — so when the sidecar dies, the host
reconnects and replays its object store, and the fresh sidecar rebuilds
exactly like the reference rebuilds from the apiserver.

``ResyncingClient`` is that host piece: a SidecarClient wrapper that
mirrors every object it ships (the informer-store analog), puts a
deadline on every call, detects a dead OR hung connection, reconnects
with jittered bounded retries, replays the full store in dependency
order, and re-issues the failed call.  Bound pods are replayed WITH
their node (the host learned the binding from the schedule response — in
the reference the binding lives in etcd), so a restarted sidecar's
resource accounting matches the pre-crash cluster.

Beyond the resync: a CIRCUIT BREAKER.  After ``breaker_threshold``
consecutive failures the client stops hammering the sidecar and enters
DEGRADED mode — filter/score evaluate host-side on a local engine built
from the same mirrored store (the in-process ops path the wire normally
bypasses; being the same deterministic engine, degraded bindings are
bit-identical to healthy ones) — while a background thread re-probes the
sidecar and the next dispatch after a successful probe replays the store
and resumes wire dispatch.  Observable via ``scheduler_sidecar_state``
and ``scheduler_degraded_dispatches_total`` on ``client.registry``; the
same semantics are mirrored by the Go plugin (go/tpubatchscore/client.go
SetDeadline + breaker, plugin.go Skip→default path)."""

from __future__ import annotations

import random
import threading
import time

from ..api import serialize
from ..framework.flight import FlightRecorder
from ..framework.metrics import MetricsRegistry
from . import sidecar_pb2 as pb
from .server import DeadlineExceeded, SidecarClient, fill_result

# Replay order: everything a pod references must exist before the pod.
_REPLAY_ORDER = (
    "Node", "StorageClass", "PersistentVolume", "PersistentVolumeClaim",
    "CSINode", "PodGroup", "PodDisruptionBudget", "ResourceSlice",
    "ResourceClaim", "Pod",
)


def _key(kind: str, obj) -> str:
    # remove("Node", uid) takes the node NAME; pods key by uid.
    return obj.uid if kind == "Pod" else obj.name


def _pin_fallback_to_cpu() -> None:
    """The degraded engine is HOST-side evaluation: a client that shares
    a machine with its sidecar must not race it for the chip (the sidecar
    holds it, and a second process that asks for it fails or hangs).  A
    process that has not initialised a JAX backend yet is held to the CPU
    one here; a process that already has one keeps it — the race is over
    either way."""
    import jax

    from ..utils import backend_initialized

    if not backend_initialized():
        jax.config.update("jax_platforms", "cpu")


class BreakerOpen(ConnectionError):
    """The circuit breaker tripped: the sidecar keeps failing and calls
    now degrade to host-side evaluation instead of hammering it."""


class ResyncingClient:
    def __init__(
        self,
        path: str,
        max_reconnect_s: float = 10.0,
        retry_interval_s: float = 0.05,
        deadline_s: float = 5.0,
        max_call_retries: int = 3,
        breaker_threshold: int = 3,
        probe_interval_s: float = 0.5,
        fallback_factory=None,
        socket_wrapper=None,
        registry=None,
        seed: int = 0,
        journal=None,
        journal_snapshot_every: int = 256,
    ):
        self.path = path
        self.max_reconnect_s = max_reconnect_s
        self.retry_interval_s = retry_interval_s
        # Per-call deadline (the SetDeadline the Go client mirrors): a
        # HUNG sidecar — process alive, dispatch wedged — fails calls in
        # bounded time instead of blocking the host forever.
        self.deadline_s = deadline_s
        # Reconnect+reissue attempts per call before the failure escapes.
        self.max_call_retries = max_call_retries
        # Consecutive failures (across calls) that open the breaker.
        self.breaker_threshold = breaker_threshold
        self.probe_interval_s = probe_interval_s
        # Degraded-mode engine factory; None → a default TPUScheduler.
        # Wire deployments pass the factory that matches the sidecar's
        # configuration so degraded decisions are bit-identical.
        self.fallback_factory = fallback_factory
        # Optional socket decorator applied on every (re)connect — the
        # fault-injection seam (faults.FaultPlan.wrap), so injected
        # faults survive reconnects like a genuinely sick sidecar would.
        self.socket_wrapper = socket_wrapper
        self.resyncs = 0  # observable: how many times the store was replayed
        self.degraded = False
        self._rng = random.Random(seed)  # jitter source, seedable
        self._consecutive_failures = 0
        self._store: dict[str, dict[str, object]] = {k: {} for k in _REPLAY_ORDER}
        self._ns_labels: dict[str, dict] = {}
        self.registry = registry or MetricsRegistry()
        self._state_gauge = self.registry.gauge(
            "scheduler_sidecar_state",
            "Sidecar dispatch state (1 on the active cell).",
        )
        self._degraded_counter = self.registry.counter(
            "scheduler_degraded_dispatches_total",
            "Schedule dispatches evaluated host-side (breaker open).",
        )
        self._timeout_counter = self.registry.counter(
            "scheduler_sidecar_call_timeouts_total",
            "Sidecar calls that hit the per-call deadline.",
        )
        self._breaker_counter = self.registry.counter(
            "scheduler_sidecar_breaker_trips_total",
            "Times consecutive failures opened the circuit breaker.",
        )
        # Wire round-trip attribution (the host half of the flight
        # recorder's phase story: what the sidecar's own phases can't see
        # is the socket + retry + resync cost of reaching it).
        self._rt_hist = self.registry.histogram(
            "scheduler_sidecar_round_trip_duration_seconds",
            "Wire round-trip duration of sidecar calls (retries and "
            "resyncs included), by call kind.",
        )
        # Host-side flight recorder: per-schedule wire timings plus the
        # breaker/degraded/resync transition markers; breaker trips
        # auto-dump (the incident the ring exists for).
        self.flight_recorder = FlightRecorder(component="host")
        self._fallback = None
        # Deletes applied while DEGRADED never reached the sidecar; a
        # hung-but-alive sidecar still holds those objects, so the
        # recovery replay (upserts only) must reconcile removals first.
        self._tombstones: list[tuple[str, str]] = []
        self._probe_stop = threading.Event()
        self._probe_thread: threading.Thread | None = None
        self._probe_conn: SidecarClient | None = None
        self._lock = threading.Lock()  # guards the probe handover
        # Serializes the whole client surface: the framed protocol is one
        # request/response stream per socket, so a metrics scrape thread
        # (ObservabilityHTTPServer(client=...)) interleaving with the
        # scheduling thread would desync seq numbers — or worse, frames.
        self._call_lock = threading.Lock()
        # Durable replay store (journal.Journal): when given, every
        # object upsert/remove and every learned BINDING is journaled
        # before the in-memory mirror mutates, and the mirror itself is
        # REBUILT from snapshot+journal at construction — a host kill no
        # longer forgets what it told the sidecar, and the post-crash
        # replay ships the same bound world a live host would have.
        self.journal = journal
        self.journal_snapshot_every = journal_snapshot_every
        if journal is not None:
            self._load_durable()
        self._client = self._connect()
        self._set_state("healthy")
        if journal is not None and (
            self._ns_labels or any(self._store.values())
        ):
            # Cold-start recovery: the fresh connection gets the durable
            # world before any caller traffic (the reference's
            # WaitForCacheSync-then-schedule ordering).
            self._replay()
            self.resyncs += 1
            # The dump is the artifact a killed-host chaos cell asserts:
            # a restarted host leaves evidence of what it recovered.
            self.flight_recorder.record_marker(
                "recovery",
                store={k: len(v) for k, v in self._store.items() if v},
            )
            self.flight_recorder.dump("recovery")

    # -- wiring ------------------------------------------------------------

    def _connect(self) -> SidecarClient:
        client = SidecarClient(self.path, deadline_s=self.deadline_s)
        if self.socket_wrapper is not None:
            client.sock = self.socket_wrapper(client.sock)
        return client

    def _set_state(self, state: str) -> None:
        for s in ("healthy", "degraded"):
            self._state_gauge.set(1.0 if s == state else 0.0, state=s)

    # -- informer-store bookkeeping ---------------------------------------

    def _record(self, kind: str, obj) -> None:
        self._store.setdefault(kind, {})[_key(kind, obj)] = obj

    # -- durable replay store (journal.py) ---------------------------------

    def _obj_from_data(self, kind: str, data: dict):
        if kind == "Pod":
            return serialize.pod_from_data(data)
        return serialize.build(serialize.KINDS[kind][0], data)

    def _load_durable(self) -> None:
        """Rebuild the replay store from snapshot + fenced journal replay
        (instead of only from the live mirror a dead process took with
        it)."""
        snap, records, _stats = self.journal.replay()
        if snap is not None:
            st = snap["state"]
            self._ns_labels = dict(st.get("ns_labels", {}))
            for kind, objs in st.get("store", {}).items():
                self._store[kind] = {}
                for data in objs:
                    obj = self._obj_from_data(kind, data)
                    self._store[kind][_key(kind, obj)] = obj
        for rec in records:
            rtype, d = rec["t"], rec["d"]
            if rtype == "add":
                obj = self._obj_from_data(d["kind"], d["obj"])
                self._store.setdefault(d["kind"], {})[
                    _key(d["kind"], obj)
                ] = obj
            elif rtype == "remove":
                self._apply_remove_local(d["kind"], d["uid"])
            elif rtype == "bind":
                p = self._store["Pod"].get(d["uid"])
                if p is not None:
                    p.spec.node_name = d["node"]
            elif rtype == "ns":
                self._ns_labels[d["namespace"]] = dict(d["labels"])

    def _journal_mutation(self, rtype: str, data: dict) -> None:
        if self.journal is not None:
            self.journal.append(rtype, data)

    def _journal_group(self):
        """One group-commit fsync barrier for a batch of mutations
        (journal.group(), ISSUE 15) — a no-op context when the replay
        store is unjournaled."""
        import contextlib

        if self.journal is None:
            return contextlib.nullcontext()
        return self.journal.group()

    def _maybe_checkpoint(self) -> None:
        """Checkpoint cadence — call AFTER the mutation has been applied
        to the in-memory store: the snapshot's seq covers every appended
        record and truncates the log, so snapshotting a store that does
        not yet hold the last record would durably lose it (the exact
        double-bind window the journal exists to close)."""
        j = self.journal
        if (
            j is not None
            and self.journal_snapshot_every
            and j.seq - j.snapshot_seq >= self.journal_snapshot_every
        ):
            j.snapshot(
                {
                    "ns_labels": dict(self._ns_labels),
                    "store": {
                        kind: [serialize.to_dict(o) for o in objs.values()]
                        for kind, objs in self._store.items()
                        if objs
                    },
                }
            )

    def _apply_remove_local(self, kind: str, uid: str) -> None:
        self._store.get(kind, {}).pop(uid, None)
        if kind == "Node":
            # Pods on a removed node vanish from scheduling state (the
            # engine's remove_node contract); the store must mirror that
            # or a later replay re-adds pods bound to a node that no
            # longer exists — a server-side error that wedges the replay.
            self._store["Pod"] = {
                u: p
                for u, p in self._store["Pod"].items()
                if p.spec.node_name != uid
            }

    # -- reconnect + replay ------------------------------------------------

    def _reconnect(self) -> None:
        deadline = time.monotonic() + self.max_reconnect_s
        while True:
            try:
                self._client = self._connect()
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise ConnectionError(
                        f"sidecar at {self.path} did not come back within "
                        f"{self.max_reconnect_s}s"
                    )
                time.sleep(self.retry_interval_s)
        self._replay()
        self.resyncs += 1
        self.flight_recorder.record_marker("resync", resyncs=self.resyncs)

    def _replay(self) -> None:
        for ns, labels in self._ns_labels.items():
            self._client.set_namespace_labels(ns, labels)
        for kind in _REPLAY_ORDER:
            for obj in self._store.get(kind, {}).values():
                self._client.add(kind, obj)

    def _note_failure(self, exc: Exception) -> None:
        """Count one failed attempt; trips the breaker at the threshold."""
        if isinstance(exc, DeadlineExceeded):
            self._timeout_counter.inc()
        self._consecutive_failures += 1
        if self._consecutive_failures >= self.breaker_threshold:
            self._enter_degraded()
            raise BreakerOpen(
                f"{self._consecutive_failures} consecutive sidecar failures"
                f" (last: {exc})"
            ) from exc

    def _with_resync(self, fn):
        """Run ``fn`` against the live client.  On a dead/hung connection,
        reconnect+replay and re-issue in a BOUNDED loop with jittered
        sleeps — a second crash during the replay or the re-issued call is
        retried, not fatal.  ``breaker_threshold`` consecutive failures
        raise BreakerOpen instead (the caller degrades host-side)."""
        attempts = 0
        while True:
            try:
                result = fn()
            except (ConnectionError, BrokenPipeError, OSError) as exc:
                failure = exc
            else:
                self._consecutive_failures = 0
                return result
            while True:
                self._note_failure(failure)  # may raise BreakerOpen
                attempts += 1
                if attempts > self.max_call_retries:
                    raise failure
                time.sleep(self.retry_interval_s * (0.5 + self._rng.random()))
                try:
                    self._reconnect()
                    break
                except (ConnectionError, BrokenPipeError, OSError) as exc:
                    failure = exc

    # -- degraded mode -----------------------------------------------------

    def _enter_degraded(self) -> None:
        if self.degraded:
            return
        self.degraded = True
        self._breaker_counter.inc()
        self._set_state("degraded")
        # The page-worthy transition: mark it and persist the evidence
        # (the ring holds the wire timings leading up to the trip).
        self.flight_recorder.record_marker(
            "breaker_trip", consecutive_failures=self._consecutive_failures
        )
        self.flight_recorder.record_marker("degraded_enter")
        self.flight_recorder.dump("breaker_trip")
        try:
            self._client.close()
        except OSError:
            pass
        self._start_probe()

    def _start_probe(self) -> None:
        self._probe_stop.clear()
        self._probe_thread = threading.Thread(
            target=self._probe_loop, daemon=True
        )
        self._probe_thread.start()

    def _probe_loop(self) -> None:
        """Background re-probe while degraded: dial + health until the
        sidecar answers, then park the verified connection for the next
        dispatch — the replay must interleave with the store, which only
        the caller's thread may touch."""
        while not self._probe_stop.wait(self.probe_interval_s):
            try:
                conn = self._connect()
                conn.health()
            except Exception:
                continue
            with self._lock:
                if self._probe_stop.is_set():
                    # close() already drained the handover slot: a
                    # connection parked now would leak.
                    conn.close()
                    return
                self._probe_conn = conn
            return

    def _maybe_recover(self) -> None:
        """Complete a recovery the probe thread initiated: replay the
        store through its verified connection and resume wire dispatch."""
        if not self.degraded:
            return
        with self._lock:
            conn, self._probe_conn = self._probe_conn, None
        if conn is None:
            return
        self._client = conn
        try:
            if self._tombstones:
                # The sidecar survived the outage WITH state: deletes made
                # while degraded (node removals, preemption victims) must
                # land before the upsert replay, or recovery resurrects
                # phantom objects a later batch could bind onto.  Node
                # removes are guarded by the live dump (remove_node of an
                # unknown node is a server error); pod deletes are
                # idempotent for unknown uids.
                state = self._client.dump()
                for kind, uid in self._tombstones:
                    if kind == "Node" and uid not in state.get("nodes", {}):
                        continue
                    self._client.remove(kind, uid)
            self._replay()
        except (ConnectionError, BrokenPipeError, OSError):
            # Died again between probe and replay: stay degraded.
            self._start_probe()
            return
        self._tombstones.clear()
        self.resyncs += 1
        self.degraded = False
        self._consecutive_failures = 0
        self._set_state("healthy")
        self.flight_recorder.record_marker(
            "degraded_exit", resyncs=self.resyncs
        )
        self._fallback = None  # its bindings live in the store; rebuild fresh

    def _ensure_fallback(self):
        """The degraded-mode engine, built by replaying the mirrored store
        host-side — the same in-process ops/eval path the wire normally
        offloads, so a breaker-open host keeps making progress with
        bit-identical decisions."""
        if self._fallback is None:
            from ..scheduler import TPUScheduler

            _pin_fallback_to_cpu()
            fb = (self.fallback_factory or TPUScheduler)()
            for ns, labels in self._ns_labels.items():
                fb.builder.set_namespace_labels(ns, dict(labels))
            for kind in _REPLAY_ORDER:
                for obj in self._store.get(kind, {}).values():
                    getattr(fb, serialize.KINDS[kind][1])(obj)
            self._fallback = fb
        return self._fallback

    def _dispatch_degraded(self, pods, drain: bool) -> list[pb.PodResult]:
        self._degraded_counter.inc()
        fb = self._ensure_fallback()
        for p in pods:
            fb.update_pod(p)
        outcomes = fb.schedule_all_pending() if drain else fb.schedule_batch()
        results = [fill_result(pb.PodResult(), o) for o in outcomes]
        for r in results:
            for vu in r.victim_uids:
                # Victims evicted host-side: the hung sidecar still holds
                # them bound — reconcile on recovery.
                self._tombstones.append(("Pod", vu))
        return results

    # -- client surface ----------------------------------------------------

    def _call_or_degraded(self, wire_fn, degraded_fn, kind: str = "call"):
        """The whole client-surface protocol in ONE place: finish any
        recovery the probe initiated, serve host-side while degraded,
        otherwise try the wire — with resync retries — and degrade when
        the breaker opens mid-call.  ``wire_fn`` must re-read
        ``self._client`` (a lambda over the attribute) so a retry after a
        reconnect targets the NEW connection.  Successful wire calls are
        timed into the round-trip histogram under ``kind`` (retries and
        replays included — the cost of REACHING the sidecar is exactly
        what the sidecar's own phase timings cannot see).  The call lock
        makes the surface thread-safe: one request/response at a time on
        the shared framed socket (and one mutator at a time on the
        store/fallback) — without it an HTTP scrape thread
        (ObservabilityHTTPServer(client=...)) interleaving with the
        scheduling thread would desync the frame stream."""
        with self._call_lock:
            self._maybe_recover()
            if not self.degraded:
                t0 = time.perf_counter()
                try:
                    result = self._with_resync(wire_fn)
                except BreakerOpen:
                    pass
                else:
                    self._rt_hist.observe(
                        time.perf_counter() - t0, call=kind
                    )
                    return result
            return degraded_fn()

    def set_namespace_labels(self, namespace: str, labels: dict) -> None:
        self._journal_mutation(
            "ns", {"namespace": namespace, "labels": dict(labels)}
        )
        self._ns_labels[namespace] = dict(labels)
        self._maybe_checkpoint()
        self._call_or_degraded(
            lambda: self._client.set_namespace_labels(namespace, labels),
            lambda: self._ensure_fallback().builder.set_namespace_labels(
                namespace, dict(labels)
            ),
            kind="add",
        )

    def add(self, kind: str, obj) -> None:
        self._journal_mutation(
            "add", {"kind": kind, "obj": serialize.to_dict(obj)}
        )
        self._record(kind, obj)
        self._maybe_checkpoint()
        self._call_or_degraded(
            lambda: self._client.add(kind, obj),
            lambda: self._fallback_add(kind, obj),
            kind="add",
        )

    def _fallback_add(self, kind: str, obj) -> None:
        fb = self._ensure_fallback()
        getattr(fb, serialize.KINDS[kind][1])(obj)

    def add_pending_batch(self, pods) -> None:
        """Ship one coalesced PendingPods hint frame (the flusher shape
        the soak driver and the Go plugin's informer backlog use).
        Hints are NOT cluster mutations: they are neither journaled nor
        mirrored into the replay store (a pod the scheduler never asks
        about must not be replayed into a restarted sidecar as if it
        were state), and while degraded they are simply dropped — the
        pods arrive again through Schedule, which is always correct."""
        self._call_or_degraded(
            lambda: self._client.add_pending_batch(pods),
            lambda: None,
            kind="add",
        )

    def remove(self, kind: str, uid: str) -> None:
        self._journal_mutation("remove", {"kind": kind, "uid": uid})
        self._apply_remove_local(kind, uid)
        self._maybe_checkpoint()
        self._call_or_degraded(
            lambda: self._client.remove(kind, uid),
            lambda: self._fallback_remove(kind, uid),
            kind="remove",
        )

    def _fallback_remove(self, kind: str, uid: str) -> None:
        self._tombstones.append((kind, uid))
        fb = self._ensure_fallback()
        if kind == "Node":
            # Tolerant: when the breaker opened on this very remove, the
            # fallback was just built from the store that ALREADY dropped
            # the node — there is nothing left to remove.
            if uid in fb.cache.nodes:
                fb.remove_node(uid)
        elif kind == "Pod":
            fb.delete_pod(uid)  # lenient for unknown uids
        else:
            remover = serialize.REMOVERS.get(kind)
            if remover is not None:
                getattr(fb, remover)(uid)  # the removers tolerate unknowns

    # Observability reads during an outage must not FORCE the fallback
    # engine into existence (its build replays the whole mirrored store —
    # seconds at scale) and must keep serving the outage-describing host
    # series: read the fallback only when a dispatch already built it.

    def dump(self) -> dict:
        return self._call_or_degraded(
            lambda: self._client.dump(),
            lambda: (
                self._fallback.dump_state()
                if self._fallback is not None
                else {
                    "degraded": True,
                    "store": {k: len(v) for k, v in self._store.items() if v},
                }
            ),
            kind="dump",
        )

    def host_health(self) -> dict:
        """The host's OWN health block (no wire touched): breaker and
        degraded state, so a liveness probe can tell degraded-but-serving
        from healthy — and from dead."""
        return {
            "sidecar_state": "degraded" if self.degraded else "healthy",
            "degraded": self.degraded,
            "breaker": {
                "consecutive_failures": self._consecutive_failures,
                "threshold": self.breaker_threshold,
                "trips": int(self._breaker_counter.total()),
            },
            "resyncs": self.resyncs,
            "pending_tombstones": len(self._tombstones),
            "journal_armed": self.journal is not None,
        }

    def health(self) -> dict:
        """healthz through the host: the sidecar's health frame when the
        wire is up, a host-synthesized liveness payload when degraded —
        always carrying the ``host`` breaker/degraded block."""
        state = self._call_or_degraded(
            lambda: self._client.health(),
            # Degraded-but-serving IS healthy for a liveness probe; the
            # host block below says which kind of healthy.
            lambda: {"healthy": True, "ready": True, "source": "host"},
            kind="health",
        )
        state["host"] = self.host_health()
        return state

    def flight(self, limit: int = 0) -> dict:
        """Flight-recorder readout through the host: the sidecar's ring
        when reachable (plus the host's own ring under ``host`` — wire
        round-trip timings and breaker/resync markers), the host ring
        alone while degraded."""
        doc = self._call_or_degraded(
            lambda: self._client.flight(limit),
            lambda: {"component": "scheduler", "unreachable": True,
                     "records": []},
            kind="flight",
        )
        doc["host"] = self.flight_recorder.snapshot(limit or None)
        return doc

    def explain(self, uid: str, seq: int = 0) -> dict:
        """Decision-provenance readout through the host: the sidecar's
        record when reachable, the warm-standby fallback engine's while
        degraded (its ring only holds decisions IT made), else an
        unreachable marker — never an exception for a read path."""
        return self._call_or_degraded(
            lambda: self._client.explain(uid, seq),
            lambda: (
                self._fallback.explain_pod(uid, seq=seq or None)
                if self._fallback is not None
                else {"uid": uid, "error": "sidecar unreachable (degraded)"}
            ),
            kind="explain",
        )

    def fleet(self, op: str, payload: dict | None = None) -> dict:
        """One partitioned-fleet protocol op against a shard owner behind
        this client (fleet/owner.py).  Fleet ops have NO degraded
        fallback by design: a shard owner the breaker gave up on is
        exactly the condition the fleet answers with TAKEOVER
        (fleet/takeover.py) — scheduling around it host-side would fork
        the shard's journal."""

        def _unreachable() -> dict:
            raise ConnectionError(
                f"fleet op {op!r}: shard owner unreachable (degraded) — "
                "take the shard over instead of degrading"
            )

        return self._call_or_degraded(
            lambda: self._client.fleet(op, payload),
            _unreachable,
            kind="fleet",
        )

    def _degraded_metrics(self) -> str:
        text = self.registry.render_text()
        if self._fallback is not None:
            # Disjoint family names: the host registry carries the
            # scheduler_sidecar_* series, the engine its scheduling ones.
            text += self._fallback.metrics.registry.render_text()
        return text

    def metrics(self) -> str:
        return self._call_or_degraded(
            lambda: self._client.metrics(), self._degraded_metrics,
            kind="metrics",
        )

    def events(self) -> list[dict]:
        return self._call_or_degraded(
            lambda: self._client.events(),
            lambda: (
                self._fallback.events.list()
                if self._fallback is not None
                else []
            ),
            kind="events",
        )

    def schedule(
        self, pods=(), drain: bool = True, trace=None
    ) -> list[pb.PodResult]:
        # Pending pods enter the store UNBOUND first: if the sidecar dies
        # mid-call the replay re-submits them (at-least-once; the engine's
        # upsert path makes re-delivery idempotent).  Journaled for the
        # same reason — a restarted HOST must re-submit them too.  Group
        # commit (ISSUE 15): ONE fsync barrier for the whole batch's add
        # records instead of one per pod, with the store mutations (the
        # apply) deferred past the barrier — journal-before-apply at
        # group scope, same contract as the scheduler's commit drain.
        pods = list(pods)
        with self._journal_group():
            for p in pods:
                self._journal_mutation(
                    "add", {"kind": "Pod", "obj": serialize.to_dict(p)}
                )
        for p in pods:
            self._record("Pod", p)
        t_wire = time.perf_counter()
        results = self._call_or_degraded(
            lambda: self._client.schedule(pods, drain=drain, trace=trace),
            lambda: self._dispatch_degraded(pods, drain),
            kind="schedule",
        )
        # Host flight record: the wire (or degraded host-eval) cost of
        # this dispatch — the phase the sidecar's own recorder can't see.
        # Empty drain polls stay off the ring (same gate as the
        # scheduler side): a 0.3s settle loop would otherwise evict every
        # incident-relevant record within minutes.
        if pods or any(r.node_name for r in results):
            self.flight_recorder.record_batch(
                {
                    "call": "schedule",
                    "pods": len(pods),
                    "bound": sum(1 for r in results if r.node_name),
                    "degraded": self.degraded,
                    "phases": {
                        "wire": round(time.perf_counter() - t_wire, 6)
                    },
                }
            )
        # Record bindings: the reference host persists them via the
        # apiserver; here the store is that persistence, so a later replay
        # re-adds bound pods as cache adds with their node set.
        by_uid = {p.uid: p for p in pods}
        staged_binds: list[tuple] = []  # (pod, node) applied post-barrier
        staged_removes: list[str] = []
        with self._journal_group():
            for r in results:
                p = by_uid.get(r.pod_uid) or self._store["Pod"].get(r.pod_uid)
                if p is None:
                    continue
                if r.node_name:
                    # Write-ahead: the learned binding is durable before
                    # the mirror records it — a host kill between the
                    # response and the next replay can no longer forget a
                    # commit the sidecar already made (the double-bind
                    # window).  The whole batch's records share one group
                    # fsync; the mirror mutations below run only after
                    # the barrier returned.
                    self._journal_mutation(
                        "bind", {"uid": r.pod_uid, "node": r.node_name}
                    )
                    staged_binds.append((p, r.node_name))
                for vu in r.victim_uids:
                    # Preemption victims were deleted sidecar-side;
                    # mirror that.
                    self._journal_mutation(
                        "remove", {"kind": "Pod", "uid": vu}
                    )
                    staged_removes.append(vu)
        for p, node_name in staged_binds:
            p.spec.node_name = node_name
        for vu in staged_removes:
            self._store["Pod"].pop(vu, None)
        self._maybe_checkpoint()
        return results

    def close(self) -> None:
        self._probe_stop.set()
        with self._lock:
            conn, self._probe_conn = self._probe_conn, None
        if conn is not None:
            conn.close()
        self._client.close()


class DecisionCache:
    """The plugin-local decision map fed by the sidecar's push stream —
    the Python emulation of the Go plugin's subscriber goroutine
    (go/tpubatchscore/plugin.go Subscriber), used by tests and the
    integrated benchmark driver.

    Owns its own subscribed connection and applies Push frames strictly
    in stream order, which is the whole consistency contract
    (proto/sidecar.proto Push): an invalidation frame precedes any
    decision recomputed after it, so an in-order consumer can never hold
    a decision from a rolled-back epoch.  A dedicated reader thread keeps
    the socket drained at all times (a stalled subscriber is dropped by
    the sidecar's bounded-blocking push); ``drain()`` then applies the
    buffered frames in the consumer's thread.  After a miss response the
    triggering batch's pushes were written BEFORE the response (same
    dispatch lock), so ``drain(min_frames=1)`` only ever waits out the
    reader thread's scheduling latency, not the sidecar.

    Across a sidecar RESTART the map is a dead epoch: the reader thread
    sees EOF, ``drain`` surfaces ConnectionError instead of pretending
    liveness, and the consumer falls back to the wire for every pod (a
    miss is always correct — the wire path re-evaluates) until it builds
    a fresh DecisionCache against the new sidecar."""

    def __init__(self, path: str):
        import threading

        self.client = SidecarClient(path)
        self.client.subscribe()
        self.sock = self.client.sock
        self.buf = bytearray()
        self.map: dict[str, pb.Decision] = {}
        self.epoch = 0
        self.frames = 0
        self._cond = threading.Condition()
        self._closed = False
        # The reader thread ONLY moves bytes off the socket — the Go
        # plugin's subscriber goroutine.  It must always be draining:
        # push frames can exceed the socket buffers (a big batch's
        # decisions), and the sidecar's bounded-blocking push drops a
        # subscriber whose socket stays full.  Frame parsing and map
        # application stay in the consumer thread, in stream order.
        self._reader = threading.Thread(target=self._read_loop, daemon=True)
        self._reader.start()

    def _read_loop(self) -> None:
        while True:
            try:
                chunk = self.sock.recv(1 << 20)
            except OSError:
                chunk = b""
            with self._cond:
                if chunk:
                    self.buf += chunk
                else:
                    self._closed = True
                self._cond.notify_all()
            if not chunk:
                return

    def drain(self, min_frames: int = 0, timeout: float = 1.0) -> int:
        """Apply every complete buffered Push frame; with ``min_frames``,
        wait up to ``timeout`` for at least that many (after a miss
        response, the triggering batch's pushes were written before the
        response, but the reader thread may still be mid-recv)."""
        deadline = None
        n = 0
        while True:
            with self._cond:
                frames, self.buf = self._frames_from(self.buf)
                if not frames and n < min_frames and not self._closed:
                    import time as _t

                    if deadline is None:
                        deadline = _t.monotonic() + timeout
                    left = deadline - _t.monotonic()
                    if left > 0:
                        self._cond.wait(left)
                        continue
            for push in frames:
                self._apply(push)
            n += len(frames)
            if n >= min_frames or not frames:
                break
        self.frames += n
        if n < min_frames and self._closed:
            raise ConnectionError("push stream closed")
        return n

    @staticmethod
    def _frames_from(buf: bytearray) -> tuple[list, bytearray]:
        out = []
        off = 0
        while len(buf) - off >= 4:
            ln = int.from_bytes(buf[off : off + 4], "big")
            if len(buf) - off - 4 < ln:
                break
            env = pb.Envelope()
            env.ParseFromString(bytes(buf[off + 4 : off + 4 + ln]))
            out.append(env.push)
            off += 4 + ln
        return out, buf[off:] if off else buf

    def _apply(self, push: pb.Push) -> None:
        # Invalidations first — a frame never carries both a rollback and
        # decisions from before it (the sidecar emits them separately, in
        # epoch order).
        if push.invalidate_all:
            self.map.clear()
        for uid in push.invalidate_uids:
            self.map.pop(uid, None)
        self.epoch = push.epoch
        for d in push.decisions:
            self.map[d.pod_uid] = d

    def pop(self, uid: str) -> pb.Decision | None:
        """Consume the cached decision for ``uid`` (PreFilter answering
        from the local map — schedule_one.go:491–502's cached-placement
        precedent), or None → the caller falls back to the wire."""
        return self.map.pop(uid, None)

    def close(self) -> None:
        self.client.close()
