"""Crash-safe scheduler state: the write-ahead binding journal.

The reference scheduler is stateless because etcd is the durable truth
(SURVEY layer 0, etcd3/store.go): a `kill -9` of kube-scheduler loses
nothing — bindings live in the apiserver, the queue rebuilds from a LIST.
Our host process kept bindings, queue/backoff state and the quarantine
pool in dicts, so a host kill silently forgot in-flight commits and could
double-bind on restart.  This module is the etcd stand-in:

- ``Journal``: a length-prefixed, CRC-checked write-ahead log.  Every
  binding/preemption/quarantine decision is appended — and fsync'd —
  BEFORE it is applied to live state, so the decision survives a crash
  landing anywhere after the append.  A torn final record (crash mid-
  write) fails its CRC/length check and is truncated away at open; the
  decision it described was never applied, so dropping it is exactly
  the etcd semantics of an unacknowledged write.

- Group commit (ISSUE 15, ISSUE 26): ``with journal.group():`` turns
  the appends of one commit stage into ONE fence check, ONE buffer, ONE
  ``write``, ONE ``flush`` and ONE fsync at group exit — the classic
  WAL group-commit optimization.  Inside the block ``append`` only
  encodes the record and buffers its bytes; no syscall runs a record.
  Journal-before-apply is preserved STRICTLY: callers stage their
  applies and run them only after ``group()`` returns, so no decision
  in the group is applied until the group's single fsync has returned.
  The group is all-or-nothing in the file: an exception out of the
  block, a fenced writer or a failed ``write`` leaves the log and
  ``seq`` as they were at group entry.  A crash inside the group's one
  write leaves a clean prefix (possibly with a torn tail the open-time
  repair truncates); none of the group's decisions were applied, so
  recovery replays exactly the acknowledged prefix — unacknowledged
  appends were never made live.  An append outside any group is a
  group of one through the same code.

- Epoch fencing: every record is stamped with the holder's lease epoch
  (framework/leaderelection.py FileLease.epoch).  Appends check the
  fence (the lease file's current epoch) and the log's own running
  maximum; a deposed leader lingering past failover gets
  ``StaleEpochError`` instead of a write, and — belt and braces — replay
  drops any record whose epoch is below the running maximum at its
  position, so even a racing stale append cannot resurrect state.

- Snapshots: ``snapshot()`` writes the full scheduler store + queue
  (backoff clocks, attempts, the quarantine pool) as one fsync'd JSON
  document via temp-file + ``os.replace`` (a crash mid-snapshot leaves
  the previous snapshot intact), then truncates the log at the snapshot
  barrier.  Records carry a monotonic ``seq`` and the snapshot stores
  the last included seq, so a crash BETWEEN the replace and the truncate
  replays nothing twice.

- Recovery: ``recover(scheduler, journal)`` rebuilds a fresh scheduler
  from snapshot + fenced journal replay.  The caller then reconciles
  against a LIST (informers.reconcile_after_recovery): journal bindings
  absent from the relist are re-applied, relist bindings absent from the
  journal win as host truth — the same DeltaFIFO-replace discipline a
  restarted kube-scheduler gets from its informer LIST.

Crash-point hooks: the module-level ``CRASH`` switch (faults.KillSwitch)
is consulted at the named points (pre-append, torn-append, post-append,
mid-snapshot, mid-truncate) so the chaos harness
(scripts/run_fault_matrix.py --kill) can SIGKILL the process at each
window and assert recovery lands bit-identical bindings.
"""

from __future__ import annotations

import json
import os
import struct
import time
import zlib

from .framework.metrics import Histogram, exponential_buckets
from .framework.tracing import NULL_SINK

_HDR = struct.Struct(">II")  # payload length, crc32(payload)
MAX_RECORD = 64 << 20

# Process-kill fault switch (faults.KillSwitch): None in production.
# Consulted at every named crash point; ``should_fire`` counts hits and
# returns True on the armed point's Nth, ``fire`` SIGKILLs the process.
CRASH = None


def _crash(point: str) -> None:
    c = CRASH
    if c is not None and c.should_fire(point):
        c.fire()


class StaleEpochError(RuntimeError):
    """An append was fenced: the writer's lease epoch is older than the
    current leader's.  The deposed holder must stop committing — its
    decisions no longer own the cluster."""


class Journal:
    """One journal directory: ``journal.wal`` + ``snapshot.json``.

    ``epoch`` is the holder's fencing token (FileLease.epoch); ``fence``
    is an optional zero-arg callable returning the CURRENT authoritative
    epoch (leaderelection.read_epoch over the lease file) consulted once
    a group: at entry and again before the group's write (an append
    outside a group is a group of one).  ``fsync`` False trades durability of the last few
    records for append latency (the fsync knob README documents); the
    snapshot path always fsyncs — it is the recovery floor."""

    WAL = "journal.wal"
    SNAP = "snapshot.json"

    def __init__(
        self,
        directory: str,
        epoch: int = 0,
        fence=None,
        fsync: bool = True,
    ) -> None:
        os.makedirs(directory, exist_ok=True)
        self.dir = directory
        self.epoch = epoch
        self.fence = fence
        self.fsync_enabled = fsync
        # recover() mutes appends while it replays the log through the
        # scheduler's own mutation surface (those calls would otherwise
        # re-journal every replayed decision).
        self.muted = False
        # Where spans go: the scheduler's sink once attached
        # (attach_journal); alone, the profiler's trace only.
        self.spans = NULL_SINK
        # Observability (exported as scheduler_journal_* by the
        # scheduler's collector once attached).
        self.appends = 0  # records whose bytes are in the file
        self.writes = 0  # `write` calls on the log: one a group
        self.fence_checks = 0  # at most two a group
        self.fsyncs = 0
        self.fsync_s = 0.0  # cumulative append-path fsync seconds
        self.fenced = 0  # groups rejected by the epoch fence
        # Group commit (ISSUE 15, ISSUE 26): appends made inside a `with
        # journal.group():` block are encoded into _group_buf and reach
        # the file in ONE write at the outermost exit, under ONE fsync.
        # _group_depth nests (an inner group rides the outermost write).
        self._group_depth = 0
        self._group_buf: list[bytes] = []
        self.group_commits = 0  # barriers that fsync'd >= 1 record
        self.group_appends = 0  # appends whose fsync was deferred
        self.last_group_size = 0
        self.max_group_size = 0
        self.snapshots = 0
        self.truncations = 0
        self.replayed = 0  # records applied by the last replay()
        self.replay_fenced = 0  # records dropped stale by the last replay()
        self.torn_bytes = 0  # trailing bytes dropped by open-time repair
        # One observation a write (write + flush of a whole group), so
        # `total` is the seconds spent putting bytes into the file.
        self.append_latency = Histogram(
            buckets=exponential_buckets(1e-6, 2, 24)
        )
        self.wal_path = os.path.join(directory, self.WAL)
        self.snap_path = os.path.join(directory, self.SNAP)
        # A leftover snapshot temp file is a torn snapshot write: the
        # replace never happened, so the previous snapshot (if any) is
        # the valid one and the temp is garbage.
        tmp = self.snap_path + ".tmp"
        if os.path.exists(tmp):
            os.unlink(tmp)
        snap = self.load_snapshot()
        self.snapshot_seq = snap["seq"] if snap else 0
        self._max_epoch = snap["epoch"] if snap else 0
        self.seq = self.snapshot_seq
        # Scan the existing log: learn seq/epoch high-water marks and
        # truncate a torn tail (a record whose bytes were cut by a crash
        # mid-append — its decision was never applied, so it never was).
        good_off = 0
        for off, rec in self._scan():
            self.seq = max(self.seq, rec["q"])
            self._max_epoch = max(self._max_epoch, rec["e"])
            good_off = off
        try:
            size = os.path.getsize(self.wal_path)
        except OSError:
            size = 0
        if size > good_off:
            self.torn_bytes = size - good_off
            with open(self.wal_path, "r+b") as f:
                f.truncate(good_off)
                os.fsync(f.fileno())
        self._f = open(self.wal_path, "ab")
        # The WAL's directory entry must be durable too: fsync'ing only
        # the file data leaves a freshly created journal.wal losable with
        # everything in it on some filesystems until the first snapshot's
        # directory fsync — defeating --journal-fsync always.
        self._fsync_dir()
        # Where this writer believes the log ends.  A mismatch at append
        # time means ANOTHER writer touched the file (a successor leader
        # appending, or its snapshot truncating) — the self-fencing
        # tripwire for deposed holders running without a fence callable.
        self._expected_size = min(size, good_off) if size else 0

    # -- the write path ----------------------------------------------------

    def _current_epoch(self) -> int:
        cur = self._max_epoch
        if self.fence is not None:
            cur = max(cur, self.fence())
        return cur

    def _check_fence(self) -> None:
        # Self-fencing tripwire: if the log's size is not where this
        # writer left it, another holder has written (or truncated at a
        # snapshot barrier) — adopt the file's epoch high-water mark
        # before judging our own.  The log is opened once, appended to
        # and truncated in place, never replaced, so the open file's
        # size is the path's size without the path walk.
        self.fence_checks += 1
        size = os.fstat(self._f.fileno()).st_size
        if size != self._expected_size:
            for _off, rec in self._scan():
                self._max_epoch = max(self._max_epoch, rec["e"])
            snap = self.load_snapshot()
            if snap is not None:
                self._max_epoch = max(self._max_epoch, snap["epoch"])
            self._expected_size = size
        cur = self._current_epoch()
        if self.epoch < cur:
            self.fenced += 1
            raise StaleEpochError(
                f"journal writer epoch {self.epoch} fenced by epoch {cur}"
            )

    def append(self, rtype: str, data: dict) -> int | None:
        """Record one decision BEFORE it is applied.  Returns the
        record's seq, or None while muted (recovery replay).

        Inside ``group()`` the record is encoded and buffered — no
        syscall — and is in the file, durable, only once the outermost
        group has exited; the caller must not apply the decision before
        that.  Outside a group it is a group of one: fence check, write,
        flush and fsync before this returns.  Raises StaleEpochError
        when this writer has been deposed."""
        if self.muted:
            return None
        seq = self.seq + 1
        payload = json.dumps(
            {"e": self.epoch, "q": seq, "t": rtype, "d": data},
            separators=(",", ":"),
        ).encode()
        self._group_buf.append(
            _HDR.pack(len(payload), zlib.crc32(payload)) + payload
        )
        self.seq = seq
        if not self._group_depth:
            self._write_group(grouped=False)
            if self.fsync_enabled:
                tf = time.perf_counter()
                os.fsync(self._f.fileno())
                self.fsync_s += time.perf_counter() - tf
                self.fsyncs += 1
        return seq

    def _write_group(self, grouped: bool) -> int:
        """Put the buffered records into the file: one fence check, one
        ``write``, one ``flush``.  All or nothing — a fenced writer or a
        failed write leaves the file and ``seq`` as they were before the
        first of these records was appended.  Returns the record count
        (not yet durable: the caller runs the fsync)."""
        buf, self._group_buf = self._group_buf, []
        try:
            self._check_fence()
            c = CRASH
            if c is not None:
                self._crash_points(c, buf, grouped)
            blob = b"".join(buf)
            try:
                # inside a drain: a sibling of `drain/journal_append` and
                # `drain/journal_fsync` under `pipeline/drain`
                with self.spans.span("drain/journal_write", label="") as sp:
                    self._f.write(blob)
                    self._f.flush()
            except BaseException:
                self._discard_partial_write()
                raise
            self.append_latency.observe(sp.dur_s)
        except BaseException:
            self.seq -= len(buf)
            raise
        self.writes += 1
        self.appends += len(buf)
        self._expected_size += len(blob)
        self._max_epoch = max(self._max_epoch, self.epoch)
        return len(buf)

    def _crash_points(self, c, buf: list[bytes], grouped: bool) -> None:
        """The per-record crash windows of the group's one write.  Each
        point is consulted once a record in record order (the kill
        matrix arms "the Nth append"), and a firing point dies with the
        file as a record-at-a-time writer would have left it: every
        earlier record whole, the armed one absent (pre-append), half
        there (torn-append; torn-group-tail inside a group) or whole
        (post-append).  None of the group was applied — applies wait
        for the group fsync — so recovery's prefix replay + idempotent
        re-run must converge on identical bindings."""
        for i, rec in enumerate(buf):
            if c.should_fire("pre-append"):
                self._die_with(c, buf[:i])
            if c.should_fire("torn-append") or (
                grouped and c.should_fire("torn-group-tail")
            ):
                half = _HDR.size + max(1, (len(rec) - _HDR.size) // 2)
                self._die_with(c, buf[:i] + [rec[:half]])
            if c.should_fire("post-append"):
                self._die_with(c, buf[: i + 1])

    def _die_with(self, c, chunks: list[bytes]) -> None:
        # Make the bytes durable so recovery actually sees them, then die.
        self._f.write(b"".join(chunks))
        self._f.flush()
        os.fsync(self._f.fileno())
        c.fire()

    def _discard_partial_write(self) -> None:
        """A ``write``/``flush`` of the group raised: cut the file back
        to where the group began.  The file object may still hold bytes
        it could not write, so it is closed (that flush may fail again)
        and the log reopened — same inode, the path is never replaced."""
        try:
            self._f.close()
        except OSError:
            pass
        with open(self.wal_path, "r+b") as f:
            f.truncate(self._expected_size)
            os.fsync(f.fileno())
        self._f = open(self.wal_path, "ab")

    # -- group commit (ISSUE 15, ISSUE 26) ---------------------------------

    def group(self) -> "_JournalGroup":
        """One fence check, one write and one fsync barrier for every
        append made inside the block::

            with journal.group():
                for decision in batch:
                    journal.append(...)   # encoded and buffered
            # returned: the whole group is in the file and durable — apply now.

        All or nothing in the file: if the block raises, the writer is
        fenced or the write fails, none of the group's records is
        written and ``seq`` is back where it was at entry.  Nested
        groups ride the outermost write.  With fsync disabled the
        barrier is a no-op (same durability trade the fsync knob
        already documents); muted journals skip everything.
        """
        return _JournalGroup(self)

    def _group_begin(self) -> None:
        if not self._group_depth and not self.muted:
            # A deposed holder stops here, before anything is buffered.
            self._check_fence()
        self._group_depth += 1

    def _group_commit(self) -> None:
        """Leave the group; at the outermost exit, write the buffered
        records ONCE and fsync ONCE.  Applies staged on this group must
        run only after this returns — journal-before-apply at group
        scope."""
        self._group_depth -= 1
        if self._group_depth > 0 or not self._group_buf:
            return
        n = self._write_group(grouped=True)
        self.group_appends += n
        self.last_group_size = n
        self.max_group_size = max(self.max_group_size, n)
        # The group's records are written (flushed) but not yet durable;
        # a SIGKILL here must recover to the same bindings with NONE of
        # the group applied.
        _crash("mid-group-fsync")
        self._barrier_fsync()
        self.group_commits += 1
        # Durable but not yet applied — the post-append analog at group
        # scope: recovery replays the whole group.
        _crash("post-group-fsync")

    def _group_abort(self) -> None:
        """Leave a group whose block raised; at the outermost exit, drop
        what it buffered — nothing of it reached the file — and give its
        seqs back."""
        self._group_depth -= 1
        if self._group_depth == 0 and self._group_buf:
            self.seq -= len(self._group_buf)
            self._group_buf = []

    def barrier(self) -> None:
        """Re-run a durability barrier: fsync everything written so far
        (fsync is file-wide and idempotent).  The drain-resume path uses
        it when a group's records were ALL written but the group's own
        fsync raised — re-entering ``group()`` would see nothing
        buffered and skip the fsync, silently acknowledging undurable
        records."""
        self._barrier_fsync()
        self.group_commits += 1

    def _barrier_fsync(self) -> None:
        """A group's one fsync, as the `drain/journal_fsync` span (which
        is also what ``fsync_s`` accumulates, and so what the histogram
        holds as ``journal_fsync``)."""
        if self.fsync_enabled:
            with self.spans.span("drain/journal_fsync", label="") as sp:
                os.fsync(self._f.fileno())
            self.fsync_s += sp.dur_s
            self.fsyncs += 1

    def snapshot(self, state: dict) -> None:
        """Checkpoint the full scheduler state and truncate the log at the
        barrier.  Atomic: temp + fsync + os.replace, so a crash at any
        point leaves either the old snapshot + full log or the new
        snapshot (+ a log whose records the seq filter skips)."""
        if self.muted:
            return
        self._check_fence()
        _crash("pre-snapshot")
        doc = {"epoch": self.epoch, "seq": self.seq, "state": state}
        with self.spans.span("snapshot/encode"):
            blob = json.dumps(doc, separators=(",", ":")).encode()
        with self.spans.span("snapshot/write", bytes=len(blob)):
            self._write_snapshot(blob)

    def _write_snapshot(self, blob: bytes) -> None:
        """Temp file + fsync + replace + directory fsync, then truncate
        the log at the barrier."""
        tmp = self.snap_path + ".tmp"
        c = CRASH
        with open(tmp, "wb") as f:
            if c is not None and c.should_fire("mid-snapshot"):
                # Crash mid-snapshot-write: a durable torn temp file the
                # next open must discard (the replace never happened).
                f.write(blob[: max(1, len(blob) // 2)])
                f.flush()
                os.fsync(f.fileno())
                c.fire()
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.snap_path)
        self._fsync_dir()
        self.snapshots += 1
        self.snapshot_seq = self.seq
        _crash("mid-truncate")
        # Truncate at the barrier: every surviving record is covered by
        # the snapshot's seq.  A crash landing before this point replays
        # them through the seq filter — harmless.
        os.ftruncate(self._f.fileno(), 0)
        if self.fsync_enabled:
            os.fsync(self._f.fileno())
        self._expected_size = 0
        self.truncations += 1
        _crash("post-truncate")

    def _fsync_dir(self) -> None:
        try:
            dfd = os.open(self.dir, os.O_RDONLY)
        except OSError:
            return
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    # -- the read path -----------------------------------------------------

    def _scan(self):
        """Yield (end_offset, record) for every valid record in the log,
        stopping at the first torn/corrupt one (everything after a bad
        record is untrustworthy — the stream lost its framing).
        Torn-tail truncation itself happens at __init__."""
        try:
            with open(self.wal_path, "rb") as f:
                blob = f.read()
        except OSError:
            return
        off = 0
        while len(blob) - off >= _HDR.size:
            n, crc = _HDR.unpack_from(blob, off)
            if n > MAX_RECORD or len(blob) - off - _HDR.size < n:
                break  # torn tail / garbage length
            payload = blob[off + _HDR.size : off + _HDR.size + n]
            if zlib.crc32(payload) != crc:
                break  # corrupt record: stop, don't guess
            try:
                rec = json.loads(payload)
            except ValueError:
                break
            off += _HDR.size + n
            yield off, rec

    def load_snapshot(self) -> dict | None:
        """The last durable checkpoint, or None (missing/corrupt — a
        corrupt snapshot means the replace itself was interrupted by
        something this format can't have produced; treat as cold)."""
        try:
            with open(self.snap_path, "rb") as f:
                doc = json.loads(f.read())
            if not isinstance(doc, dict) or "seq" not in doc:
                return None
            return doc
        except (OSError, ValueError):
            return None

    def replay(self, count: bool = True) -> tuple[dict | None, list[dict], dict]:
        """(snapshot doc or None, post-snapshot records in order, stats).
        Records already covered by the snapshot barrier (seq <= the
        snapshot's) are skipped; records from a deposed epoch (below the
        running maximum at their position) are dropped as fenced.
        ``count=False`` leaves the replayed/replay_fenced counters alone
        — the read-only mode the provenance reconstruction uses against
        a LIVE journal (an explain must not dent the recovery metrics)."""
        snap = self.load_snapshot()
        snap_seq = snap["seq"] if snap else 0
        max_e = snap["epoch"] if snap else 0
        records: list[dict] = []
        fenced = 0
        for _off, rec in self._scan():
            if rec["e"] < max_e:
                fenced += 1
                continue
            max_e = rec["e"]
            if rec["q"] <= snap_seq:
                continue
            records.append(rec)
        if count:
            self.replayed = len(records)
            self.replay_fenced = fenced
        return snap, records, {
            "snapshot": snap is not None,
            "snapshot_seq": snap_seq,
            "records": len(records),
            "fenced": fenced,
            "torn_bytes": self.torn_bytes,
        }

    def stats(self) -> dict:
        try:
            wal_bytes = os.path.getsize(self.wal_path)
        except OSError:
            wal_bytes = 0
        return {
            "dir": self.dir,
            "epoch": self.epoch,
            "seq": self.seq,
            "snapshot_seq": self.snapshot_seq,
            "appends": self.appends,
            "writes": self.writes,
            "fence_checks": self.fence_checks,
            "fsyncs": self.fsyncs,
            "fsync_s": round(self.fsync_s, 6),
            "fenced": self.fenced,
            "group_commits": self.group_commits,
            "group_appends": self.group_appends,
            "last_group_size": self.last_group_size,
            "max_group_size": self.max_group_size,
            "snapshots": self.snapshots,
            "truncations": self.truncations,
            "replayed": self.replayed,
            "replay_fenced": self.replay_fenced,
            "torn_bytes": self.torn_bytes,
            "wal_bytes": wal_bytes,
            # p99 over writes (one write + flush a group), not records.
            "append_p99_us": round(
                self.append_latency.quantile(0.99) * 1e6, 3
            ),
        }

    def close(self) -> None:
        try:
            self._f.close()
        except OSError:
            pass


class _JournalGroup:
    """Context manager for one group-commit barrier (Journal.group).
    All or nothing in the file: a clean exit writes the buffered
    records once and fsyncs once; an exception out of the block writes
    none of them and rewinds ``seq``, so whoever resumes the batch
    journals every record exactly once.  (Records counted as written
    while still in the buffer would be acknowledged state that no
    recovery replay could see.)"""

    def __init__(self, journal: Journal):
        self._j = journal

    def __enter__(self) -> Journal:
        self._j._group_begin()
        return self._j

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self._j._group_commit()
        else:
            self._j._group_abort()


# -- scheduler state <-> snapshot documents --------------------------------


def scheduler_state(sched) -> dict:
    """The snapshot document for one TPUScheduler: host store (nodes in
    row order, so restore reproduces row assignment), bound pods, the
    queue's durable state (backoff clocks, attempts, quarantine), gang
    credit, groups/PDBs, and live nominations.  Assumed-but-unbound pods
    (Permit/PreBind wait rooms) snapshot as PENDING — their bind was
    never final, so a restart retries them, like the reference retries
    an in-flight binding its informer never confirmed."""
    from .api import serialize

    front = getattr(sched, "_spec_frontend", None)
    waiting = [
        e[0] for entries in sched.permit_waiting.values() for e in entries
    ] + [e["qp"] for e in sched.prebind_waiting.values()]
    queue_state = sched.queue.durable_state()
    for qp in waiting:
        queue_state["entries"].append(
            {
                "pod": serialize.to_dict(qp.pod),
                "pool": "active",
                "attempts": qp.attempts,
                "age": 0.0,
                "plugins": [],
            }
        )
    return {
        "nodes": [
            serialize.to_dict(rec.node)
            for rec in sorted(sched.cache.nodes.values(), key=lambda r: r.row)
        ],
        "pods": [
            {"pod": serialize.to_dict(pr.pod), "node": pr.node_name}
            for uid, pr in sched.cache.pods.items()
            if pr.bound
        ],
        "queue": queue_state,
        "gang_bound": dict(sched.gang_bound),
        "pod_groups": [
            serialize.to_dict(g) for g in sched.pod_groups.values()
        ],
        "pdbs": [serialize.to_dict(p) for p in sched.pdbs.values()],
        "nominated": {
            uid: {"node": node, "priority": prio}
            for uid, (node, _delta, prio) in sched.nominator.items()
        },
        # Speculative decision-cache epoch: the cached DECISIONS are
        # assumed state and deliberately not persisted (recovery re-derives
        # them), but the epoch counter must survive — push subscribers hold
        # epoch-stamped entries, and a frontend reborn at 0 would emit
        # frames that violate the stream's monotonic-epoch contract.
        "spec_epoch": (
            front.epoch
            if front is not None
            else getattr(sched, "_recovered_spec_epoch", 0)
        ),
        # Failure-response loop (ISSUE 9): the lifecycle LOGICAL clock +
        # per-node heartbeats (the feed's clock keeps running across a
        # restart — recovering at zero would make every restored grace
        # fire instantly on the first renewal) and the incident counters
        # (a snapshot truncates the evict records that would otherwise
        # restore them — a recovered process must not report a clean
        # bill for an outage it just replayed).  evicted_uids capped:
        # loop-closure accounting is about recent incidents, not an
        # unbounded ledger.
        "node_lifecycle": {
            "heartbeats": dict(sched.node_lifecycle.heartbeats),
            "hw": sched.node_lifecycle._hw,
            "transitions": sched.node_lifecycle.transitions,
            # The GC's per-node unreachable clock: snapshot restore
            # re-adopts state from node taints at clock 0 (the nodes
            # load before the clock block), so without the original
            # transition stamps a recovered owner would age a dead node
            # toward the GC horizon from zero — sweeping EARLIER than
            # the uninterrupted run and diverging the chaos oracle.
            "gc_unreachable_since": dict(
                sched.pod_gc._unreachable_since
            ),
        },
        "failure_response": {
            "taint_evictions": sched.taint_eviction.evictions,
            "pod_gc_collected": dict(sched.pod_gc.collected),
            "evicted_uids": sorted(sched._evicted_uids)[:4096],
        },
    }


def recover(sched, journal: Journal) -> dict:
    """Rebuild a FRESH scheduler from durable state: apply the snapshot,
    then replay post-barrier journal records with epoch fencing.  Bind
    records naming a node the snapshot doesn't hold are parked on
    ``sched._recovered_bindings`` for the LIST reconcile
    (informers.reconcile_after_recovery) to re-apply once the node
    relists.  Returns replay stats.  Call BEFORE attach_journal — the
    replay drives the scheduler's own mutation surface, which must not
    re-journal."""
    snap, records, stats = journal.replay()
    _apply_replay(sched, journal, snap, records, stats)
    # Flight-recorder timeline: recovery is a state transition an operator
    # reconstructing an incident needs on the same axis as the batches —
    # and the dump is the artifact the crash harness asserts each killed
    # cell leaves behind.
    flight = getattr(sched, "flight", None)
    if flight is not None:
        flight.record_marker(
            "recovery",
            journal_epoch=journal.epoch,
            journal_seq=journal.seq,
            **stats,
        )
        # Dump only when recovery found something — a snapshot, replayable
        # records, or a torn tail the open-time repair truncated (a crash
        # mid-first-append leaves ONLY torn bytes, and that cell still
        # deserves its evidence).  A true cold start is not an incident,
        # and every test server would otherwise shed a file per
        # construction.
        if (
            stats.get("snapshot")
            or stats.get("records")
            or stats.get("torn_bytes")
        ):
            flight.dump("recovery")
    return stats


def reconstruct_at(sched, journal: Journal, upto_seq: int) -> dict:
    """Read-only state reconstruction: rebuild a FRESH scheduler's state
    AS OF journal seq ``upto_seq`` (snapshot + records with seq <=
    upto_seq) — the decision-provenance time machine (explain a committed
    binding against the store it was decided against).  Unlike recover(),
    nothing is truncated and no journal counters move, so it is safe
    against a LIVE journal; the target scheduler must be journal-less
    (its replayed mutations must not re-journal).  Raises ValueError when
    the snapshot barrier already covers seqs past ``upto_seq`` — the WAL
    prefix needed to stop earlier is gone."""
    if getattr(sched, "journal", None) is not None:
        raise ValueError(
            "reconstruct_at target must not have a journal attached"
        )
    snap, records, stats = journal.replay(count=False)
    snap_seq = snap["seq"] if snap else 0
    if snap_seq > upto_seq:
        raise ValueError(
            f"snapshot barrier at seq {snap_seq} already covers seq "
            f"{upto_seq}; the pre-{upto_seq} WAL prefix was truncated"
        )
    records = [r for r in records if r["q"] <= upto_seq]
    stats["records"] = len(records)
    stats["upto_seq"] = upto_seq
    _apply_replay(sched, None, snap, records, stats)
    return stats


def _apply_replay(sched, journal, snap, records, stats) -> None:
    """Apply one (snapshot, records) replay onto a fresh scheduler — the
    shared core of recover() and reconstruct_at().  Mutes the journal
    (when given) around the replay: the replay drives the scheduler's
    own mutation surface, which must not re-journal."""
    from .api import serialize

    if journal is not None:
        journal.muted = True
    # Visible to replay-driven hooks (fleet/owner.py routes replay-
    # surfaced evictions to a recovery bucket only the adopting router's
    # explicit drain — which filters replay-stale entries — may take).
    sched._in_recovery = True
    try:
        if snap is not None:
            st = snap["state"]
            for data in st.get("nodes", ()):
                sched.add_node(
                    serialize.build(serialize.KINDS["Node"][0], data)
                )
            for g in st.get("pod_groups", ()):
                sched.add_pod_group(
                    serialize.build(serialize.KINDS["PodGroup"][0], g)
                )
            for p in st.get("pdbs", ()):
                sched.add_pdb(
                    serialize.build(
                        serialize.KINDS["PodDisruptionBudget"][0], p
                    )
                )
            # The lifecycle LOGICAL clock restores BEFORE the bound-pod
            # re-adds below: handle_pod_assigned arms eviction deadlines
            # at _now(), and arming them against a rewound zero would
            # fire every restored grace on the feed's first continuing
            # renewal (the instant-eviction bug, one ordering level in).
            nl = st.get("node_lifecycle")
            if nl:
                for nname, ts in nl.get("heartbeats", {}).items():
                    if ts > sched.node_lifecycle.heartbeats.get(nname, -1.0):
                        sched.node_lifecycle.heartbeats[nname] = ts
                sched.node_lifecycle._hw = max(
                    sched.node_lifecycle._hw, nl.get("hw", 0.0)
                )
                sched.node_lifecycle.transitions = nl.get("transitions", 0)
                # Overwrite the note_state(…, 0.0) entries the node adds
                # above planted: the snapshot's transition stamps are the
                # GC horizon's true zero point.
                for nname, ts in nl.get("gc_unreachable_since", {}).items():
                    sched.pod_gc._unreachable_since[nname] = float(ts)
            for entry in st.get("pods", ()):
                pod = serialize.pod_from_data(entry["pod"])
                pod.spec.node_name = entry["node"]
                if entry["node"] in sched.cache.nodes:
                    sched.add_pod(pod)
            # Gang credit AFTER the bound adds (add_pod already credited
            # informer-delivered bound members; don't double-count —
            # overwrite with the snapshot's authoritative counts).
            sched.gang_bound = dict(st.get("gang_bound", {}))
            sched._recovered_spec_epoch = st.get("spec_epoch", 0)
            fr = st.get("failure_response")
            if fr:
                sched.taint_eviction.evictions = fr.get("taint_evictions", 0)
                sched.pod_gc.collected.update(
                    {
                        k: v
                        for k, v in fr.get("pod_gc_collected", {}).items()
                        if k in sched.pod_gc.collected
                    }
                )
                sched._evicted_uids.update(fr.get("evicted_uids", ()))
            sched.restore_queue(st.get("queue", {}))
            for uid, info in st.get("nominated", {}).items():
                qp = sched.queue._info.get(uid)
                if qp is not None and info["node"] in sched.cache.nodes:
                    sched.nominator[uid] = (
                        info["node"],
                        sched.builder.pod_delta_vectors(qp.pod),
                        info.get("priority", 0),
                    )
        pending: dict[str, dict] = {}
        # Fleet 2PC intents (fleet/owner.py): a ``gang_reserve`` with no
        # matching bind or ``gang_abort`` is an in-doubt reservation the
        # crash orphaned — PRESUMED ABORT: the assume it described was
        # never durable truth, so replay applies nothing and the router
        # re-admits the gang from scratch.  Surfaced for observability.
        in_doubt: dict[str, dict] = {}
        # Shard-map handoffs (fleet/shardmap.py): the acquiring owner
        # journals the transfer BEFORE rewriting the map file; a handoff
        # record whose version exceeds the on-disk map's means the
        # rewrite was lost — takeover redoes it idempotently.
        handoffs: list[dict] = []
        # node → (taints, state, ts) of its LAST replayed taint record
        # (records replay in order, so the latest wins) — the overlay +
        # GC-stamp source for nodes the host-truth re-feed re-delivers.
        taint_stamps: dict[str, tuple] = {}
        for rec in records:
            rtype, d = rec["t"], rec["d"]
            if rtype == "bind":
                pod = serialize.pod_from_data(d["pod"])
                pod.spec.node_name = d["node"]
                if d["node"] in sched.cache.nodes:
                    sched.add_pod(pod)
                else:
                    pending[pod.uid] = d
            elif rtype == "delete":
                pending.pop(d["uid"], None)
                sched.delete_pod(d["uid"])
            elif rtype == "taint":
                # Node-lifecycle taint write (ISSUE 9): re-apply the
                # journaled taint set through the same apply path — the
                # NODE_TAINT event re-arms eviction deadlines and the
                # lifecycle controller adopts the state the taints
                # encode.  The record's ts advances the logical clock
                # FIRST, so the re-armed deadlines start from the
                # incident's time, not a rewound zero — but ONLY when
                # there is lifecycle state to continue from (snapshot-
                # restored heartbeats): with no snapshot, the feed must
                # re-derive the whole incident from its op stream, and a
                # pre-advanced clock would compress the NotReady→
                # Unreachable grace ladder into one instant transition
                # (the fleet node-loss matrix's late-kill cells).  A
                # node the snapshot doesn't hold is gone; its taints
                # died with it.
                if sched.node_lifecycle.heartbeats:
                    sched.node_lifecycle._hw = max(
                        sched.node_lifecycle._hw, d.get("ts", 0.0)
                    )
                from .api import types as api_types

                taints = tuple(
                    serialize.build(api_types.Taint, nd)
                    for nd in d["taints"]
                )
                # Each taint record IS a lifecycle transition: restore
                # the incident counter (the apply path only ADOPTS state
                # — recounting there would double on live writes).
                from .controllers import state_from_taints

                sched.node_lifecycle.transitions += 1
                sched._note_lifecycle_transition(state_from_taints(taints))
                # Remember the record's (taints, state, clock) whether or
                # not the node is resident: a host-truth re-feed (the
                # takeover drivers) re-delivers the node, the overlay
                # re-applies these taints, and observe_node's adoption
                # corrects the GC horizon's zero point to the RECORDED
                # transition clock — without it a snapshotless recovery
                # that restores heartbeats by Lease RELIST (instead of
                # re-deriving the incident from a re-fed schedule) would
                # stamp unreachable_since at the feed clock and sweep
                # later than the uninterrupted run.
                taint_stamps[d["node"]] = (
                    taints, state_from_taints(taints), d.get("ts", 0.0)
                )
                if d["node"] in sched.cache.nodes:
                    sched._apply_node_taints(d["node"], taints)
            elif rtype == "evict":
                # Taint-eviction / pod-GC requeue: the binding unwinds
                # and the pod re-enters the queue unbound — replay keeps
                # the crash-interrupted eviction's requeue instead of
                # losing the pod.
                pending.pop(d["uid"], None)
                reason = d.get("reason", "")
                if sched.node_lifecycle.heartbeats:
                    # Same clock-continuation gate as the taint replay.
                    sched.node_lifecycle._hw = max(
                        sched.node_lifecycle._hw, d.get("ts", 0.0)
                    )
                sched._apply_eviction(
                    d["uid"], serialize.pod_from_data(d["pod"]), reason=reason
                )
                # Restore the incident counters the decision sites would
                # have bumped (the record's reason says whose eviction
                # this was) — the scheduler_taint_evictions_total /
                # scheduler_pod_gc_total families must carry an
                # incident's counts ACROSS the crash, or a recovered
                # process reports a clean bill for an outage it just
                # replayed.
                if reason == "taint-eviction":
                    sched.taint_eviction.evictions += 1
                elif reason.startswith("pod-gc-"):
                    key = reason[len("pod-gc-"):]
                    if key in sched.pod_gc.collected:
                        sched.pod_gc.collected[key] += 1
                        sched._note_pod_gc(key)
            elif rtype == "preempt":
                # Victims arrive via their own delete records; what the
                # preempt record restores is the NOMINATION — the claim
                # that routes the still-pending preemptor's retry onto
                # its freed node (nominator.go AddNominatedPod).
                qp = sched.queue._info.get(d["uid"])
                if qp is not None and d["node"] in sched.cache.nodes:
                    qp.pod.status.nominated_node_name = d["node"]
                    sched.nominator[d["uid"]] = (
                        d["node"],
                        sched.builder.pod_delta_vectors(qp.pod),
                        d.get("priority", 0),
                    )
            elif rtype == "quarantine":
                sched.queue.restore_quarantine(
                    serialize.pod_from_data(d["pod"]),
                    attempts=d.get("attempts", 1),
                )
            elif rtype == "release_quarantine":
                sched.queue.release_quarantine(d.get("uid"))
            elif rtype == "admission":
                # Weighted-fair admission debits (framework/fairness):
                # one record per commit group, ahead of the group's
                # binds.  Replay advances BOTH fairness ledgers — after
                # recovery the effective ledger equals the durable one,
                # so the next pop selects exactly what the uninterrupted
                # run selected (the --tenant-kill cells' bit-identical
                # admission-order contract).  A journal recovered into
                # an unarmed queue skips silently (arming is config).
                if sched.queue.admission is not None:
                    sched.queue.admission.replay_admission(
                        d.get("debits", ())
                    )
            elif rtype == "spec_epoch":
                # The speculative frontend's epoch at its last invalidation
                # (post-snapshot).  A frontend attached after recovery
                # resumes from here.
                sched._recovered_spec_epoch = max(
                    getattr(sched, "_recovered_spec_epoch", 0), d["epoch"]
                )
            elif rtype == "gang_reserve":
                in_doubt[d["uid"]] = d
            elif rtype == "gang_abort":
                in_doubt.pop(d["uid"], None)
            elif rtype == "handoff":
                handoffs.append(d)
        # A bind record resolves its reservation (phase 2 completed) —
        # whether it applied directly or parked for the LIST reconcile.
        for uid in [
            u for u in in_doubt if u in sched.cache.pods or u in pending
        ]:
            in_doubt.pop(uid, None)
        sched._recovered_bindings = pending
        sched._recovered_gang_intents = in_doubt
        sched._recovered_handoffs = handoffs
        sched._recovered_taint_stamps = taint_stamps
        stats["pending_bindings"] = len(pending)
        stats["in_doubt_reservations"] = len(in_doubt)
        stats["handoffs"] = len(handoffs)
    finally:
        if journal is not None:
            journal.muted = False
        sched._in_recovery = False
