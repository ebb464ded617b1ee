"""Sidecar server: hosts a TPUScheduler behind the framed-socket protocol.

This is the process boundary SURVEY §7 phase 6 describes: the host
scheduler keeps its informers/queue/binding and streams snapshot deltas +
pod batches here; the device pass answers with bindings, scores and
diagnosis (proto/sidecar.proto).  Framing is 4-byte big-endian length +
Envelope payload over a unix-domain (or TCP) socket — message-compatible
with a gRPC transport, which needs only the stub layer on the Go side.

The server is intentionally single-threaded per connection: the scheduler
is a sequential state machine (the reference's scheduling loop is too);
concurrency belongs to the host side (async binding, informers)."""

from __future__ import annotations

import os
import socket
import socketserver
import struct
import threading
import time

from ..api import serialize
from ..scheduler import ScheduleOutcome, TPUScheduler
from . import sidecar_pb2 as pb

_LEN = struct.Struct(">I")
MAX_FRAME = 64 << 20
# Bound on how much of an oversized frame the server will stream-discard
# to stay synchronized.  A length beyond this is almost certainly a
# garbage header (the stream is byte-desynced), so the connection drops
# instead of reading gigabytes of nothing.
MAX_DISCARD = 4 * MAX_FRAME


class FrameError(Exception):
    """A malformed frame.  ``recoverable`` means its bytes were fully
    consumed — the connection is still frame-synchronized and can carry
    an error response; otherwise the stream is hopelessly desynced and
    the connection must drop."""

    def __init__(self, msg: str, recoverable: bool):
        super().__init__(msg)
        self.recoverable = recoverable


def write_frame(sock: socket.socket, env: pb.Envelope) -> None:
    payload = env.SerializeToString()
    sock.sendall(_LEN.pack(len(payload)) + payload)


def _read_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def read_frame(sock: socket.socket) -> pb.Envelope | None:
    header = _read_exact(sock, _LEN.size)
    if header is None:
        return None
    (n,) = _LEN.unpack(header)
    if n > MAX_FRAME:
        raise ValueError(f"frame too large: {n}")
    payload = _read_exact(sock, n)
    if payload is None:
        return None
    env = pb.Envelope()
    env.ParseFromString(payload)
    return env


def read_frame_resync(sock: socket.socket) -> pb.Envelope | None:
    """Server-side framed read that SURVIVES a malformed frame where
    possible: an oversized length is stream-discarded and a garbage
    payload consumed, both raising a recoverable FrameError so the caller
    can answer with an error response instead of severing the connection
    (one bad message must not drop its healthy sibling requests).  Only a
    length too absurd to discard is unrecoverable."""
    n = _frame_length(sock)
    return None if n is None else _frame_body(sock, n)


def _frame_length(sock: socket.socket) -> int | None:
    """The frame's 4-byte header (None at EOF): on a served connection,
    the wait for the client."""
    header = _read_exact(sock, _LEN.size)
    return None if header is None else _LEN.unpack(header)[0]


def _frame_body(sock: socket.socket, n: int) -> pb.Envelope | None:
    """The rest of ``read_frame_resync``: the payload of a frame whose
    header said ``n`` bytes, and its parse."""
    if n > MAX_FRAME:
        if n > MAX_DISCARD:
            raise FrameError(
                f"frame length {n} beyond discard bound", recoverable=False
            )
        remaining = n
        while remaining:
            chunk = sock.recv(min(remaining, 1 << 20))
            if not chunk:
                return None  # EOF mid-discard
            remaining -= len(chunk)
        raise FrameError(f"frame too large: {n}", recoverable=True)
    payload = _read_exact(sock, n)
    if payload is None:
        return None
    env = pb.Envelope()
    try:
        env.ParseFromString(payload)
    except Exception as exc:  # framing intact, payload garbage
        raise FrameError(f"unparseable frame: {exc}", recoverable=True)
    return env


# A connection that has carried one of these is a served one: its reads are
# timed (`wire/await`, `wire/read`).  A subscribed push stream's read waits
# for EOF all its life and a scrape-only connection waits on its scraper, so
# neither opens them: an annotation always open on another thread would
# take the profiler's idle seconds over.
SERVED_KINDS = frozenset({"schedule", "add", "remove"})


class Ingest:
    """What the served path waits for and what an object costs it, summed
    as plain floats under the dispatch lock and written into the registry
    at scrape time (the speculation counters' idiom: the hot path pays an
    add, not a labelled counter update).  One a server: ``SidecarServer``
    holds it."""

    def __init__(self, registry) -> None:
        self.await_s = 0.0  # `wire/await` on served connections
        self.add_s = {"decode": 0.0, "scope": 0.0, "apply": 0.0}
        self.added: dict[str, int] = {}
        awaited = registry.counter(
            "scheduler_wire_await_seconds_total",
            "Seconds served connections waited for the client's next "
            "frame header (span wire/await).",
        )
        add_s = registry.counter(
            "scheduler_object_add_seconds_total",
            "Seconds of AddObject frames by stage: decode, the speculative "
            "frontend's scope (note_add), apply to the store.",
        )
        added = registry.counter(
            "scheduler_objects_added_total",
            "Cluster objects applied from AddObject frames, by kind (hints "
            "are not objects).",
        )

        def collect(_reg) -> None:
            awaited.set(self.await_s)
            for stage, v in self.add_s.items():
                add_s.set(v, stage=stage)
            for kind, n in self.added.items():
                added.set(float(n), kind=kind)

        registry.add_collector(collect)

    def note_add(self, kind: str, decode: float, scope: float, apply: float) -> None:
        s = self.add_s
        s["decode"] += decode
        s["scope"] += scope
        s["apply"] += apply
        self.added[kind] = self.added.get(kind, 0) + 1


class SidecarServer:
    """Serves one TPUScheduler over a unix-domain socket."""

    def __init__(
        self,
        path: str,
        scheduler: TPUScheduler | None = None,
        speculate: bool = False,
        lookahead: int | None = None,
        keepalive_s: float | None = None,
        health_extra: dict | None = None,
        http_port: int | None = None,
        http_host: str = "127.0.0.1",
        journal=None,
        snapshot_every_batches: int = 64,
        fleet_owner=None,
        **kw,
    ):
        self.path = path
        # Extra health-frame fields (e.g. leader-election state from
        # cmd_serve) merged into every health response.  The handler
        # closure captures this DICT object — mutate its contents to
        # change later responses; rebinding the attribute has no effect.
        self.health_extra = health_extra = health_extra or {}
        self.scheduler = scheduler or TPUScheduler(**kw)
        # Durability (journal.py): recover BEFORE serving — the first
        # frame must see the pre-crash world, exactly like the reference
        # waits out WaitForCacheSync before its loop — then arm the
        # write-ahead hooks for this tenure.
        if journal is not None:
            from ..journal import recover

            self.recovery_stats = recover(self.scheduler, journal)
            self.scheduler.attach_journal(
                journal, snapshot_every_batches=snapshot_every_batches
            )
        else:
            self.recovery_stats = None
        # Partitioned-fleet owner (fleet/owner.py, `serve --shard-of`):
        # the `fleet` frame dispatches through it.  Hung off the scheduler
        # so _dispatch — which receives only the scheduler — can reach it.
        self.fleet_owner = fleet_owner
        self.scheduler._fleet_owner = fleet_owner
        if fleet_owner is not None and journal is not None:
            # The owner was constructed BEFORE the serve-journal recovery
            # above replayed the pre-crash world — its recovered-taints
            # overlay (journal-authored lifecycle taints must survive the
            # router's host-truth node re-feed) would otherwise stay
            # empty in every `serve --shard-of` restart.
            fleet_owner.refresh_recovered_taints()
        # Wire deployments hand nominations back to the host (it owns the
        # victims' API deletes); the in-process inline commit would act on
        # them sidecar-side and desync the two views.
        self.scheduler.inline_preempt_commit = False
        self._thread: threading.Thread | None = None
        # Speculative batching frontend (speculate.py): PendingPod hints +
        # a decision cache let the one-pod-per-call integrated path keep
        # the device batch.  Off by default — per-call semantics (and the
        # golden transcripts) are unchanged unless the operator opts in.
        self.frontend = None
        if speculate:
            from .speculate import SpeculativeFrontend

            self.frontend = SpeculativeFrontend(self.scheduler, lookahead)

        sched = self.scheduler
        front = self.frontend
        span = sched.span
        self.ingest = ingest = Ingest(sched.metrics.registry)
        # The scheduler is a sequential state machine; connections are
        # threaded but dispatch is serialized (concurrency belongs to the
        # host side).
        lock = threading.Lock()
        self._lock = lock
        self._keepalive_stop = threading.Event()
        if keepalive_s and front is not None:
            # Push-stream keepalive: an empty Push frame at the current
            # epoch, so a subscriber behind a silent TCP partition can
            # bound its staleness with a read deadline (the Go
            # subscriber's 60s window; tests/fixtures leave this off to
            # stay deterministic).
            def _beat():
                while not self._keepalive_stop.wait(keepalive_s):
                    with lock:
                        env = pb.Envelope()
                        env.push.epoch = front.epoch
                        front._emit(env)

            threading.Thread(target=_beat, daemon=True).start()

        conns: set[socket.socket] = set()
        self._conns = conns

        class Handler(socketserver.BaseRequestHandler):
            def handle(self) -> None:
                try:
                    self._serve_frames()
                finally:
                    conns.discard(self.request)

            def _read_served(self):
                """A served connection's read: the wait for the header,
                then the payload and its parse, each a span (annotations
                only: they end outside the dispatch lock).  Returns the
                frame and the seconds waited."""
                with span("wire/await", label="") as aw:
                    n = _frame_length(self.request)
                if n is None:
                    return None, aw.dur_s
                with span("wire/read", label=""):
                    return _frame_body(self.request, n), aw.dur_s

            def _serve_frames(self) -> None:
                subscribed = served = False
                malformed = sched.metrics.registry.counter(
                    "sidecar_malformed_frames_total",
                    "Client frames rejected as oversized or unparseable.",
                )
                while True:
                    waited = 0.0
                    try:
                        if served and not subscribed:
                            env, waited = self._read_served()
                        else:
                            env = read_frame_resync(self.request)
                    except TimeoutError:
                        # Subscribed sockets carry a write timeout (push
                        # backpressure bound) which applies to this idle
                        # read too — just keep listening for EOF.
                        continue
                    except FrameError as fe:
                        malformed.inc()
                        if not fe.recoverable or subscribed:
                            # Desynced stream, or a write onto a one-way
                            # push stream: the connection is done.
                            return
                        # Frame consumed, stream synchronized: answer with
                        # an error response (seq 0 — the malformed payload
                        # never yielded one) and keep serving.
                        err = pb.Envelope()
                        err.response.error = f"bad frame: {fe}"
                        try:
                            write_frame(self.request, err)
                        except OSError:
                            return
                        continue
                    except (ValueError, OSError):
                        return
                    if env is None:
                        return
                    if subscribed:
                        # The push stream is one-way after the subscribe
                        # ack; a request frame here would race the pushes
                        # (two writers interleaving on one socket).  Drop
                        # the connection — the protocol violation is the
                        # client's.
                        return
                    kind = env.WhichOneof("msg") or ""
                    served = served or kind in SERVED_KINDS
                    out = pb.Envelope(seq=env.seq)
                    responded = False
                    # The wire's three boundaries: waiting for the
                    # dispatch lock (the span ends holding it), the
                    # dispatch, the response's write (outside the lock:
                    # an annotation only, no histogram).
                    with span("wire/lock_wait"):
                        lock.acquire()
                    try:
                        with span("wire/dispatch", kind=kind):
                            responded = _dispatch(
                                sched, env, out, front, self.request,
                                health_extra, ingest=ingest,
                            )
                    except Exception as exc:  # surface, don't kill the server
                        out.response.error = f"{type(exc).__name__}: {exc}"
                    finally:
                        # after the dispatch: a scrape holds the waits
                        # before the frames ahead of it, not its own (two
                        # scrapes around a window leave out the client's
                        # time after it, a profiler's stop among it)
                        ingest.await_s += waited
                        lock.release()
                    if responded:
                        subscribed = True
                        continue
                    try:
                        with span("wire/write", label=""):
                            write_frame(self.request, out)
                    except OSError:  # peer (or close()) severed mid-dispatch
                        return

        class Server(socketserver.ThreadingUnixStreamServer):
            daemon_threads = True

            def process_request(self, request, client_address):
                # Register in the ACCEPT thread, before the handler thread
                # spawns: close() then cannot miss a just-accepted socket
                # (shutdown() stops this loop first, so registration
                # happens-before the close() snapshot).
                conns.add(request)
                super().process_request(request, client_address)

        if os.path.exists(path):
            os.unlink(path)
        self._server = Server(path, Handler)
        # Optional plain-HTTP observability listener (/metrics, /healthz,
        # /events) over the SAME scheduler — Prometheus scrapes it while
        # the Go host speaks frames; 0 binds an ephemeral port (tests).
        self.http = None
        if http_port is not None:
            from .metrics_http import ObservabilityHTTPServer

            # Scrapes share the dispatch lock: render-time collectors read
            # scheduler dicts the dispatch thread mutates.
            self.http = ObservabilityHTTPServer(
                self.scheduler, http_port, host=http_host,
                health_extra=health_extra, lock=lock,
            )
            self.http.serve_background()

    def serve_background(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True
        )
        self._thread.start()

    def serve_forever(self) -> None:
        self._server.serve_forever()

    def close(self) -> None:
        self._keepalive_stop.set()
        if self.http is not None:
            self.http.close()
        self._server.shutdown()
        self._server.server_close()
        # Sever live connections too: handler threads otherwise keep
        # serving established sockets after shutdown(), so a "stopped"
        # server would silently answer from stale state (and a crash —
        # the case the host's resync exists for — kills them anyway).
        for conn in list(self._conns):
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
        if os.path.exists(self.path):
            os.unlink(self.path)


def _dispatch(
    sched: TPUScheduler,
    env: pb.Envelope,
    out: pb.Envelope,
    front=None,
    conn=None,
    health_extra: dict | None = None,
    *,
    ingest: Ingest,
) -> bool:
    """Handle one frame.  Returns True when the response was already
    written inside the dispatch lock (the subscribe handshake — its ack
    must be ordered against subsequent Push frames on the same socket,
    and every write to a subscriber happens under this lock)."""
    kind = env.WhichOneof("msg")
    if kind == "subscribe":
        # Turn this connection into a decision push stream (watch-stream
        # idiom).  Requires the speculative frontend — without it there
        # are no speculative decisions to stream.
        if front is None:
            raise ValueError("subscribe requires speculation enabled")
        if conn is None:
            raise ValueError("subscribe needs a connection")
        out.response.SetInParent()
        write_frame(conn, out)  # ack, ordered before any push frame
        # Bounded-blocking pushes: a subscriber that stops draining its
        # socket must not wedge the dispatch lock (and with it every
        # other connection).  The timeout turns backpressure into an
        # OSError and the frontend drops the sink — a stalled subscriber
        # has missed frames and must resubscribe anyway.
        conn.settimeout(5.0)

        def _sink(e, c=conn):
            try:
                write_frame(c, e)
            except OSError:
                # A failed/timed-out push leaves a partial frame on the
                # socket — unrecoverable for the stream.  shutdown() (not
                # close()) wakes the handler thread blocked in recv on
                # this fd without freeing the fd for reuse under it; the
                # handler's normal exit path owns the close (the same
                # pattern SidecarServer.close() uses).
                try:
                    c.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                raise

        front.add_sink(_sink)
        return True
    if kind == "health":
        # healthz/readyz analog (cmd/kube-scheduler/app/server.go:181–210):
        # a liveness surface the host can probe beyond a failed dial.
        # Same payload shape the plain-HTTP /healthz serves.
        import json as _json

        from .metrics_http import health_state

        state = health_state(sched, health_extra)
        state["speculation"] = front is not None
        state["epoch"] = front.epoch if front is not None else 0
        out.response.health_json = _json.dumps(state).encode()
        return False
    if kind == "metrics":
        # Prometheus text exposition over the wire — byte-identical to the
        # plain-HTTP /metrics scrape (one registry, one renderer).
        out.response.metrics_text = sched.metrics.registry.render_text().encode()
        return False
    if kind == "events":
        import json as _json

        out.response.events_json = _json.dumps(sched.events.list()).encode()
        return False
    if kind == "flight":
        # Flight-recorder readout: the per-batch phase-attribution ring +
        # transition markers as one JSON document (framework/flight.py) —
        # same payload the auto-dumps write and /debug/flight serves.
        import json as _json

        out.response.flight_json = _json.dumps(
            sched.flight.snapshot(env.flight.limit or None)
        ).encode()
        return False
    if kind == "explain":
        # Decision provenance (framework/provenance.py): one pod's
        # structured decision record — per-op attribution, the selectHost
        # tie-break trace, and the journal-reconstructed bit-identity
        # replay when available.  Read path only; sorted keys so two
        # same-seed servers emit byte-identical documents.
        import json as _json

        doc = sched.explain_pod(
            env.explain.uid, seq=env.explain.seq or None
        )
        out.response.explain_json = _json.dumps(doc, sort_keys=True).encode()
        return False
    if kind == "fleet":
        # Partitioned-fleet protocol (fleet/owner.py fleet_dispatch): one
        # frame = one op against this process's shard owner.  Requires
        # `serve --shard-of` — a plain sidecar has no shard identity.
        import json as _json

        owner = getattr(sched, "_fleet_owner", None)
        if owner is None:
            raise ValueError("fleet ops require serve --shard-of")
        from ..fleet.owner import fleet_dispatch

        result = fleet_dispatch(
            owner,
            env.fleet.op,
            _json.loads(env.fleet.payload_json or b"{}"),
        )
        out.response.fleet_json = _json.dumps(result).encode()
        return False
    if kind == "add":
        if env.add.kind == "PendingPod":
            # A pending-pod HINT (speculate.py): the host's informer saw an
            # unassigned pod the scheduler will likely ask about soon.  Not
            # a cluster mutation — without the speculative frontend it is
            # simply dropped (the pod arrives again via Schedule).
            if front is not None:
                front.add_hint_raw(env.add.object_json)
            out.response.SetInParent()
            return
        if env.add.kind == "PendingPods":
            # Batched hints: one frame carrying a JSON ARRAY of pods.  The
            # plugin's informer handlers fire per pod, but nothing forces
            # one frame per event — a flusher goroutine coalescing its
            # backlog sends one array and pays one ack (the same batching
            # client-go's Reflector does for its initial List).  The blob
            # is parsed lazily, under a later batch's device pass.
            if front is not None:
                front.add_hint_blob(env.add.object_json)
            out.response.SetInParent()
            return
        _add_object(sched, env.add.kind, env.add.object_json, front, ingest)
        out.response.SetInParent()
    elif kind == "remove":
        if front is not None:
            front.note_remove(env.remove.kind, env.remove.uid)
        remover = serialize.REMOVERS.get(env.remove.kind)
        if remover is None:
            raise ValueError(f"cannot remove kind {env.remove.kind}")
        getattr(sched, remover)(env.remove.uid)
        out.response.SetInParent()
    elif kind == "dump":
        import json

        state = sched.dump_state()
        if front is not None:
            state["speculation"] = front.stats.as_dict()
        out.response.dump_json = json.dumps(state).encode()
    elif kind == "schedule":
        # Cross-boundary trace join: install the client's trace context so
        # the batch's root span (scheduler.py ScheduleBatch) carries the
        # HOST's trace id — a slow server-side cycle then logs an id the
        # operator can grep in both processes' logs.
        if env.schedule.trace_id:
            sched.trace_parent = (
                env.schedule.trace_id, env.schedule.parent_span_id or None
            )
        sched.last_batch_span = None
        try:
            if front is not None and not env.schedule.drain:
                outcomes = front.schedule_raw(list(env.schedule.pod_json))
            else:
                if front is not None:
                    # A drain request bypasses the cache; flush it first so
                    # drained decisions and cached ones cannot double-commit.
                    front.flush_hints_to_queue()
                req_uids = []
                for raw in env.schedule.pod_json:
                    p = serialize.pod_from_json(raw)
                    req_uids.append(p.uid)
                    sched.add_pod(p)
                outcomes = (
                    sched.schedule_all_pending()
                    if env.schedule.drain
                    else sched.schedule_batch()
                )
                outcomes = list(outcomes)
                # At-least-once completion: a re-issued call (the host
                # timed out and lost the first response) may ask about
                # pods an earlier execution already committed — add_pod
                # dropped them, so the drain yields no outcome.  Answer
                # from the cache; the committed placement IS the
                # decision.  Pods still in a wait room (Permit/PreBind)
                # stay unanswered — their bind is not final.
                answered = {o.pod.uid for o in outcomes}
                waiting = {
                    e[0].pod.uid
                    for lst in sched.permit_waiting.values()
                    for e in lst
                } | set(sched.prebind_waiting)
                for uid in req_uids:
                    if uid in answered or uid in waiting:
                        continue
                    pr = sched.cache.pods.get(uid)
                    if pr is not None and pr.node_name:
                        outcomes.append(
                            ScheduleOutcome(pr.pod, pr.node_name)
                        )
        finally:
            sched.trace_parent = None
        span = sched.last_batch_span
        if span is not None and env.schedule.trace_id:
            out.response.span_id = span.span_id
        for o in outcomes:
            fill_result(out.response.results.add(), o)
    else:
        raise ValueError(f"unhandled message {kind}")


def _add_object(sched, obj_kind: str, raw: bytes, front, ingest) -> None:
    """One cluster object in, in three stages: decoded, the speculative
    frontend told which cached decisions survive it, applied to the store.
    `objects/add` spans them (an annotation: once an object, no histogram)
    and its own two clock readings bound the first and the last stage."""
    with sched.span("objects/add", label="", kind=obj_kind) as sp:
        if obj_kind == "NamespaceLabels":
            # {"namespace": ..., "labels": {...}} — the namespace informer
            # feeding affinity namespaceSelector matching.
            import json

            obj = json.loads(raw)
        else:
            obj = serialize.from_json(obj_kind, raw)
        t1 = time.perf_counter()
        if front is not None:
            front.note_add(obj_kind, obj)
        t2 = time.perf_counter()
        if obj_kind == "NamespaceLabels":
            sched.builder.set_namespace_labels(obj["namespace"], obj["labels"])
        else:
            getattr(sched, serialize.KINDS[obj_kind][1])(obj)
    ingest.note_add(obj_kind, t1 - sp.t0, t2 - t1, sp.t1 - t2)


def fill_result(r: pb.PodResult, o) -> pb.PodResult:
    """ScheduleOutcome → wire PodResult.  Shared with the host's degraded
    dispatch (sidecar/host.py), so the two serializations cannot drift."""
    r.pod_uid = o.pod.uid
    r.node_name = o.node_name or ""
    r.score = o.score
    r.feasible_nodes = o.feasible_nodes
    r.nominated_node = o.nominated_node or ""
    r.victims = o.victims
    r.victim_uids.extend(o.victim_uids)
    r.victim_names.extend(o.victim_names)
    if o.diagnosis is not None:
        r.unschedulable_plugins.extend(
            sorted(o.diagnosis.unschedulable_plugins)
        )
    return r


class DeadlineExceeded(ConnectionError):
    """A per-call deadline fired: the sidecar is reachable but not
    answering (hung, or drowning).  Distinct from a plain ConnectionError
    so the resilient host can count timeouts separately."""


class SidecarClient:
    """Minimal Python client (the same framing the native C++ client in
    native/sidecar_client.cc speaks)."""

    def __init__(self, path: str, deadline_s: float | None = None):
        """``deadline_s`` bounds every request/response round trip: a hung
        sidecar (process alive, dispatch wedged) turns into a TimeoutError
        the caller can retry/degrade on, instead of a recv that blocks
        forever.  None (the default) keeps unbounded blocking — fixtures
        and the golden transcripts rely on it; resilient hosts
        (sidecar/host.py ResyncingClient) always set one."""
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.connect(path)
        self.deadline_s = deadline_s
        if deadline_s is not None:
            self.sock.settimeout(deadline_s)
        self._seq = 0

    def _call(self, env: pb.Envelope) -> pb.Envelope:
        self._seq += 1
        env.seq = self._seq
        write_frame(self.sock, env)
        try:
            resp = read_frame(self.sock)
        except TimeoutError as exc:
            # The response may still arrive later — the connection is
            # desynced and must be treated as dead, not retried in place.
            raise DeadlineExceeded(
                f"sidecar call deadline ({self.deadline_s}s) exceeded"
            ) from exc
        if resp is None:
            raise ConnectionError("sidecar closed the connection")
        if resp.seq != self._seq:
            raise RuntimeError(
                f"protocol desync: seq {resp.seq} != {self._seq}"
            )
        if resp.response.error:
            raise RuntimeError(resp.response.error)
        return resp

    def set_namespace_labels(self, namespace: str, labels: dict) -> None:
        import json

        env = pb.Envelope()
        env.add.kind = "NamespaceLabels"
        env.add.object_json = json.dumps(
            {"namespace": namespace, "labels": labels}
        ).encode()
        self._call(env)

    def add(self, kind: str, obj) -> None:
        env = pb.Envelope()
        env.add.kind = kind
        env.add.object_json = serialize.to_json(obj)
        self._call(env)

    def add_stream(self, kind: str, objs) -> None:
        """Pipelined adds: ship frames while draining responses as they
        arrive.  Models the Go informer handlers, which fire
        asynchronously and don't gate the next event on the previous ack
        (frames are still processed in order — the protocol is sequential
        per connection).  Writes and reads interleave via select —
        write-everything-then-read deadlocks once the in-flight frames
        exceed the socket buffers (the server blocks writing acks, stops
        reading, and both sides stall).  ALL responses are drained before
        any error is raised, so a failed add cannot desync the connection
        for later calls."""
        import select

        pending = bytearray()
        for obj in objs:
            env = pb.Envelope()
            env.add.kind = kind
            env.add.object_json = serialize.to_json(obj)
            self._seq += 1
            env.seq = self._seq
            payload = env.SerializeToString()
            pending += _LEN.pack(len(payload)) + payload
        want = self._seq - len(objs)
        last = self._seq
        errors = []
        view = memoryview(pending)
        sock = self.sock
        sock.setblocking(False)
        try:
            while want < last or view:
                rl, wl, _ = select.select(
                    [sock], [sock] if view else [], []
                )
                if wl:
                    try:
                        n = sock.send(view[: 1 << 20])
                    except BlockingIOError:
                        n = 0
                    view = view[n:]
                if rl:
                    sock.setblocking(True)
                    try:
                        resp = read_frame(sock)
                    finally:
                        sock.setblocking(False)
                    if resp is None:
                        raise ConnectionError("sidecar closed the connection")
                    want += 1
                    if resp.seq != want:
                        raise RuntimeError(
                            f"protocol desync: seq {resp.seq} != {want}"
                        )
                    if resp.response.error:
                        errors.append(resp.response.error)
        finally:
            # setblocking(True) wipes any configured timeout; restore the
            # per-call deadline for subsequent requests.
            sock.settimeout(self.deadline_s)
        if errors:
            raise RuntimeError(
                f"{len(errors)} of {len(objs)} adds failed; first: {errors[0]}"
            )

    def add_pending_batch(self, pods) -> None:
        """One PendingPods frame carrying a JSON array of pods (the
        coalesced-hint form — see the server's PendingPods branch)."""
        env = pb.Envelope()
        env.add.kind = "PendingPods"
        env.add.object_json = (
            b"[" + b",".join(serialize.to_json(p) for p in pods) + b"]"
        )
        self._call(env)

    def remove(self, kind: str, uid: str) -> None:
        env = pb.Envelope()
        env.remove.kind = kind
        env.remove.uid = uid
        self._call(env)

    def dump(self) -> dict:
        """Debugger state dump of the live scheduler (the SIGUSR2 analog)."""
        import json

        env = pb.Envelope()
        env.dump.SetInParent()
        return json.loads(self._call(env).response.dump_json)

    def health(self) -> dict:
        """healthz/readyz probe (app/server.go:181–210 analog)."""
        import json

        env = pb.Envelope()
        env.health.SetInParent()
        return json.loads(self._call(env).response.health_json)

    def metrics(self) -> str:
        """Scrape the registry in Prometheus text exposition format —
        byte-identical to the sidecar's plain-HTTP /metrics payload."""
        env = pb.Envelope()
        env.metrics.SetInParent()
        return self._call(env).response.metrics_text.decode()

    def events(self) -> list[dict]:
        """Read the event-recorder ring (Scheduled / FailedScheduling /
        Preempted / GangWaiting, aggregated)."""
        import json

        env = pb.Envelope()
        env.events.SetInParent()
        return json.loads(self._call(env).response.events_json)

    def flight(self, limit: int = 0) -> dict:
        """Read the flight recorder: per-batch phase attribution records
        + state-transition markers (``limit`` keeps the newest N)."""
        import json

        env = pb.Envelope()
        env.flight.SetInParent()
        if limit:
            env.flight.limit = limit
        return json.loads(self._call(env).response.flight_json)

    def explain(self, uid: str, seq: int = 0) -> dict:
        """One pod's decision-provenance record
        (framework/provenance.py): per-op attribution columns, the
        selectHost tie-break trace, and the recorded live decision.
        ``seq`` pins the journal reconstruction point (0 = let the
        recorded capsule choose)."""
        import json

        env = pb.Envelope()
        env.explain.uid = uid
        if seq:
            env.explain.seq = seq
        return json.loads(self._call(env).response.explain_json or b"{}")

    def fleet(self, op: str, payload: dict | None = None) -> dict:
        """One partitioned-fleet protocol op against a shard owner
        (``serve --shard-of``): propose/commit/reserve/…, JSON in and
        out (fleet/owner.py fleet_dispatch)."""
        import json

        env = pb.Envelope()
        env.fleet.op = op
        env.fleet.payload_json = json.dumps(payload or {}).encode()
        return json.loads(self._call(env).response.fleet_json or b"{}")

    def subscribe(self) -> None:
        """Turn THIS connection into a decision push stream.  After the
        ack, use read_push() exclusively — request methods would desync
        against the server-initiated frames."""
        env = pb.Envelope()
        env.subscribe.SetInParent()
        self._call(env)
        # Push streams idle legitimately (no decisions to push): the
        # request/response deadline does not apply to them.
        self.sock.settimeout(None)

    def read_push(self) -> pb.Push | None:
        """Blocking read of the next Push frame (None on EOF)."""
        env = read_frame(self.sock)
        if env is None:
            return None
        if env.WhichOneof("msg") != "push":
            raise RuntimeError("non-push frame on a subscribed connection")
        return env.push

    def schedule(
        self, pods=(), drain: bool = True, trace=None
    ) -> list[pb.PodResult]:
        """``trace`` (a framework.tracing.Trace) propagates the host span's
        (trace_id, span_id) through the envelope; the server's batch span
        joins that trace and its span_id comes back on the response, where
        it is recorded as a step on the host span (the joined tree)."""
        env = pb.Envelope()
        env.schedule.drain = drain
        if trace is not None:
            env.schedule.trace_id = trace.trace_id
            env.schedule.parent_span_id = trace.span_id
        for p in pods:
            env.schedule.pod_json.append(serialize.to_json(p))
        resp = self._call(env)
        if trace is not None and resp.response.span_id:
            trace.step(f"sidecar batch span={resp.response.span_id}")
        return list(resp.response.results)

    def close(self) -> None:
        self.sock.close()
