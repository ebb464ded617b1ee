"""Leader election for the sidecar process — the single-active-scheduler
guarantee kube-scheduler gets from client-go's lease machinery.

Reference: cmd/kube-scheduler/app/server.go:140–170 (leaderElectAndRun
wraps the scheduler loop in leaderelection.RunOrDie over a Lease object)
and staging/src/k8s.io/client-go/tools/leaderelection/leaderelection.go
(acquire → renew loop → OnStartedLeading/OnStoppedLeading).

TPU-host adaptation: the reference's Lease object lives in the apiserver
because candidates run on different machines.  The sidecar's candidates
share a HOST (they guard one device/socket), so the lease is a kernel
advisory lock on a file — `flock(LOCK_EX)`.  That replaces the reference's
renew-deadline/clock-skew machinery with a strictly stronger primitive:
the kernel releases the lock the instant the holder dies (crash failover
with zero staleness window, where upstream waits out leaseDuration), and
"renewal" is implicit in holding the fd.  What is kept: blocking acquire
(standbys park until the incumbent goes), an identity record for
observability (the Lease's holderIdentity field), and release on clean
shutdown (leaderelection.go:295 releases the lease so successors need not
wait out the duration).

The lease also carries a MONOTONIC EPOCH (the Lease's leaseTransitions
analog): every successful acquire reads the previous holder's recorded
epoch — a crashed holder's record lingers, which is exactly what keeps
the counter monotonic across failovers — and writes epoch+1.  The epoch
is the fencing token the write-ahead binding journal (journal.py) stamps
on every record: a deposed leader that lingers past failover appends
with a stale epoch and is rejected at append time and ignored at replay,
so it can never corrupt durable state it no longer owns."""

from __future__ import annotations

import fcntl
import json
import os
import time


def read_epoch(path: str) -> int:
    """The epoch recorded in a lease file (0 when absent/unreadable) —
    the journal's fence source.  An open, a read, a close and a JSON
    parse by path (235 µs a call on the TPU host's filesystem, PERF.md):
    the journal consults it once a commit group, never once a record."""
    try:
        with open(path, "rb") as f:
            raw = f.read()
        return int(json.loads(raw).get("epoch", 0)) if raw else 0
    except (OSError, ValueError, AttributeError, TypeError):
        return 0


class FileLease:
    """An exclusive host-local lease: whoever holds the flock is leader.

    The lock file persists across holders (unlinking would race a standby
    that already opened the old inode); the JSON body names the current
    holder for operators, like `kubectl get lease -o yaml` shows
    holderIdentity."""

    def __init__(self, path: str, identity: str | None = None) -> None:
        self.path = path
        self.identity = identity or f"pid-{os.getpid()}"
        self._fd: int | None = None
        # The fencing epoch of THIS holder's tenure; 0 until acquired.
        self.epoch: int = 0

    @property
    def held(self) -> bool:
        return self._fd is not None

    def acquire(self, block: bool = True) -> bool:
        """Take the lease; with ``block`` park until the incumbent releases
        or dies (the standby pattern, leaderelection.go:245 acquire loop).
        Returns False only in non-blocking mode with a live incumbent."""
        if self._fd is not None:
            return True
        fd = os.open(self.path, os.O_RDWR | os.O_CREAT, 0o644)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | (0 if block else fcntl.LOCK_NB))
        except OSError:
            os.close(fd)
            return False
        # Record the holder AFTER winning (the loser must not clobber the
        # incumbent's record).  The epoch continues from whatever the file
        # records — a crashed holder's lingering record, a clean release's
        # epoch-only record — so it is monotonic across every transition.
        self.epoch = read_epoch(self.path) + 1
        os.ftruncate(fd, 0)
        os.pwrite(
            fd,
            json.dumps(
                {"holderIdentity": self.identity, "pid": os.getpid(),
                 "acquiredAt": time.time(), "epoch": self.epoch}
            ).encode(),
            0,
        )
        os.fsync(fd)  # the fencing token must survive a host crash
        # A freshly created lease file needs its directory entry durable
        # too, or a crash could lose the file and reset the epoch
        # sequence — letting a successor reuse a deposed epoch.
        try:
            dfd = os.open(os.path.dirname(self.path) or ".", os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass
        self._fd = fd
        return True

    def holder(self) -> dict | None:
        """The recorded holder (observability only — the flock, not this
        record, is the source of truth; a crashed holder's record lingers
        until the next acquire overwrites it)."""
        try:
            with open(self.path, "rb") as f:
                raw = f.read()
            return json.loads(raw) if raw else None
        except (OSError, ValueError):
            return None

    def release(self) -> None:
        """Clean handoff (leaderelection.go:295 ReleaseOnCancel): drop the
        holder record, then the lock, so a standby wakes immediately.  The
        EPOCH stays in the file — truncating it would reset the fencing
        counter and let a successor reuse a deposed leader's epoch."""
        if self._fd is None:
            return
        os.ftruncate(self._fd, 0)
        os.pwrite(self._fd, json.dumps({"epoch": self.epoch}).encode(), 0)
        os.fsync(self._fd)
        fcntl.flock(self._fd, fcntl.LOCK_UN)
        os.close(self._fd)
        self._fd = None

    def __enter__(self) -> "FileLease":
        self.acquire(block=True)
        return self

    def __exit__(self, *exc) -> None:
        self.release()
