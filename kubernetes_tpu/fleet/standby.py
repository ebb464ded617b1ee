"""Warm-standby owner pool: pre-warmed `serve` children a promotion
turns into shard owners in O(handoff) instead of ~15s of cold boot.

The gap (the round-11 autoscale soak's two-process leg, ``run_soak.py
--autoscale``, showed it): mid-incident elasticity — an autoscale split
under a crest, or a takeover replacing a SIGKILLed owner — paid the new
child's boot + XLA compile (~15s on that CPU box) right when the fleet
could least afford it.  Tesserae (arxiv 2508.04953) frames the
requirement: scaling actions are only usable under load when their cost
is O(handoff), not O(cold start).

This module keeps N children WARM: XLA programs compiled against the
live featurization schema (a probe propose/remove cycle at spawn),
journal dir pre-created, lease UNCLAIMED — the child owns nothing until
promoted.  Promotion is then: claim the slot (O_EXCL file — the
cross-process race arbiter), append the pool's own WAL record, apply
(``finish_promotion``), and hand the payload to the caller, who drives
the ordinary journaled handoff + lease claim.  Fleet-state correctness
across a SIGKILL anywhere in that window is the EXISTING takeover/redo
machinery's job — the pool only has to never double-offer a slot, which
the claim file + WAL replay guarantee (crash points
``standby-pre-claim`` / ``standby-mid-promotion`` /
``standby-post-promote``; scripts/run_fault_matrix.py --standby-kill).

A standby whose compiled schema no longer matches the live vocab is
retired and respawned (``sync_schema``), never promoted — a stale XLA
cache would recompile mid-incident, which is the exact cost the pool
exists to pre-pay.

Pool health is observable (``scheduler_fleet_standby_*`` families, one
construction site in framework/metrics.StandbyMetrics) and mirrored to
an atomic ``standby.json`` (temp + fsync + replace + dir-fsync, the
shardmap discipline) that `fleet status --sockets` renders without
touching the pool."""

from __future__ import annotations

import json
import os
import time

from .. import journal as _journal
from ..framework.metrics import MetricsRegistry, StandbyMetrics

MIRROR_NAME = "standby.json"
JOURNAL_NAME = "standby.journal"


def atomic_write_json(path: str, doc: dict) -> None:
    """Shardmap-grade atomic document write: temp + fsync + os.replace +
    directory fsync, so a reader never sees a torn mirror and a crash
    never loses the previous complete one."""
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    dfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


class _PoolJournal:
    """The pool's own tiny WAL: fsync'd JSONL of spawn/promote/evict
    records.  Reopen replays it so a slot consumed by a crashed
    promotion is never offered twice."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a", encoding="utf-8")

    def append(self, rec: dict) -> None:
        self._f.write(json.dumps(rec, sort_keys=True) + "\n")
        self._f.flush()
        os.fsync(self._f.fileno())

    def close(self) -> None:
        try:
            self._f.close()
        except OSError:
            pass

    @staticmethod
    def replay(path: str) -> list[dict]:
        recs: list[dict] = []
        try:
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        recs.append(json.loads(line))
                    except ValueError:
                        break  # torn tail: the complete prefix stands
        except OSError:
            pass
        return recs


class StandbySlot:
    """One warm child.  ``payload`` is whatever the factory produced —
    an in-process warmed scheduler bundle, or a handle to a spawned
    `serve --standby` process; the pool never looks inside it."""

    __slots__ = ("slot_id", "schema_version", "born_mono", "payload", "state")

    def __init__(self, slot_id: int, schema_version: int, payload):
        self.slot_id = slot_id
        self.schema_version = schema_version
        self.born_mono = time.monotonic()
        self.payload = payload
        self.state = "warm"

    def warm_age_s(self) -> float:
        return time.monotonic() - self.born_mono


class StandbyPool:
    """The pre-forked pool.  ``factory(slot_id) -> payload`` spawns and
    WARMS one child (XLA compiled against the live schema) up front;
    ``promote`` hands the oldest schema-matching slot to a caller in
    O(claim + WAL append) and refills the pool behind it.

    Cross-process safety: promoters racing over a shared ``state_dir``
    are arbitrated by O_EXCL claim files — exactly one wins each slot,
    the loser retries the next.  ``retire(payload)`` (optional) is
    called when a slot is evicted so a real child process can be
    reaped."""

    def __init__(
        self,
        state_dir: str,
        factory,
        size: int = 2,
        schema_version: int = 0,
        registry: MetricsRegistry | None = None,
        retire=None,
        mirror_path: str | None = None,
        fill: bool = True,
    ):
        os.makedirs(state_dir, exist_ok=True)
        self.state_dir = state_dir
        self.factory = factory
        self.size = int(size)
        self.schema_version = int(schema_version)
        self.retire = retire
        self.mirror_path = mirror_path or os.path.join(state_dir, MIRROR_NAME)
        self.metrics = StandbyMetrics(registry or MetricsRegistry())
        self.slots: list[StandbySlot] = []
        self.promotions: dict[str, int] = {}
        self.stale_evictions = 0
        self.misses = 0
        # WAL replay: slots a previous incarnation consumed (promoted or
        # evicted) stay consumed; ids are never reused.  A claim file
        # without a promote record is a promotion that died between
        # claim and append — conservatively consumed (the existing
        # takeover machinery owns the fleet-state half).
        consumed: set[int] = set()
        next_id = 0
        for rec in _PoolJournal.replay(
            os.path.join(state_dir, JOURNAL_NAME)
        ):
            sid = int(rec.get("slot", -1))
            next_id = max(next_id, sid + 1)
            op = rec.get("op")
            if op == "promote":
                consumed.add(sid)
                reason = rec.get("reason", "unknown")
                self.promotions[reason] = self.promotions.get(reason, 0) + 1
            elif op == "evict":
                consumed.add(sid)
                self.stale_evictions += int(
                    rec.get("why") == "schema-stale"
                )
        for name in sorted(os.listdir(state_dir)):
            if name.startswith("slot-") and name.endswith(".claim"):
                try:
                    consumed.add(int(name[len("slot-"):-len(".claim")]))
                except ValueError:
                    pass
        next_id = max(next_id, max(consumed) + 1 if consumed else 0)
        self._next_id = next_id
        self.journal = _PoolJournal(os.path.join(state_dir, JOURNAL_NAME))
        if fill:
            self.fill()
        self._write_mirror()

    # -- spawn / fill ------------------------------------------------------

    def _spawn(self) -> StandbySlot:
        sid = self._next_id
        self._next_id += 1
        # Spawn is journaled before the (expensive) warm factory runs so
        # a crash mid-warmup still retires the id: warmth is
        # reconstructible, identity is not.
        self.journal.append(
            {"op": "spawn", "slot": sid, "schema": self.schema_version}
        )
        slot = StandbySlot(sid, self.schema_version, self.factory(sid))
        self.slots.append(slot)
        return slot

    def fill(self) -> int:
        """Top the pool back up to ``size`` warm slots; returns how many
        were spawned."""
        spawned = 0
        while len(self.idle()) < self.size:
            self._spawn()
            spawned += 1
        if spawned:
            self._write_mirror()
        return spawned

    def idle(self) -> list[StandbySlot]:
        return [s for s in self.slots if s.state == "warm"]

    # -- promotion ---------------------------------------------------------

    def _claim_path(self, slot_id: int) -> str:
        return os.path.join(self.state_dir, f"slot-{slot_id}.claim")

    def _try_claim(self, slot_id: int) -> bool:
        """O_EXCL claim file: the cross-process race arbiter.  Exactly
        one promoter creates it; the loser moves on to the next slot."""
        try:
            fd = os.open(
                self._claim_path(slot_id),
                os.O_CREAT | os.O_EXCL | os.O_WRONLY,
            )
        except FileExistsError:
            return False
        try:
            os.write(fd, str(os.getpid()).encode("ascii"))
            os.fsync(fd)
        finally:
            os.close(fd)
        return True

    def promote(self, shard_id: int, reason: str = "promote"):
        """Hand the oldest schema-matching warm slot to the caller:
        claim → WAL append → apply (``finish_promotion``) → refill.
        Returns the slot's payload, or None on a pool miss (caller falls
        back to the cold-boot path it always had).

        Stale-schema slots are NEVER candidates — their compiled
        programs would recompile mid-incident."""
        t0 = time.perf_counter()
        for slot in sorted(
            self.idle(), key=lambda s: (s.born_mono, s.slot_id)
        ):
            if slot.schema_version != self.schema_version:
                continue
            _journal._crash("standby-pre-claim")
            if not self._try_claim(slot.slot_id):
                slot.state = "claimed-elsewhere"
                continue
            self.journal.append(
                {
                    "op": "promote",
                    "slot": slot.slot_id,
                    "shard": int(shard_id),
                    "reason": reason,
                    "schema": slot.schema_version,
                }
            )
            _journal._crash("standby-mid-promotion")
            self.finish_promotion(slot, shard_id, reason)
            _journal._crash("standby-post-promote")
            self.fill()
            self.metrics.promotion_seconds.observe(
                time.perf_counter() - t0, reason=reason
            )
            return slot.payload
        self.misses += 1
        self._write_mirror()
        return None

    def finish_promotion(self, slot: StandbySlot, shard_id: int, reason: str) -> None:
        """The promotion's apply half (WAL marker — journaled first by
        ``promote``): pool bookkeeping + metrics + mirror.  The fleet-
        side truth (map write, handoff, lease claim) belongs to the
        CALLER's journaled path."""
        slot.state = "promoted"
        self.promotions[reason] = self.promotions.get(reason, 0) + 1
        self.metrics.promotions.inc(reason=reason)
        self._write_mirror()

    # -- schema staleness --------------------------------------------------

    def sync_schema(self, live_version: int) -> int:
        """Adopt the live featurization schema version; retire + respawn
        every warm slot compiled against an older one.  Returns the
        eviction count.  A stale slot is never promoted — eviction is
        the only exit."""
        live_version = int(live_version)
        self.schema_version = live_version
        evicted = 0
        for slot in list(self.slots):
            if slot.state == "warm" and slot.schema_version != live_version:
                self.journal.append(
                    {
                        "op": "evict",
                        "slot": slot.slot_id,
                        "why": "schema-stale",
                        "schema": slot.schema_version,
                        "live": live_version,
                    }
                )
                slot.state = "evicted"
                self.stale_evictions += 1
                self.metrics.stale_evictions.inc()
                if self.retire is not None:
                    self.retire(slot.payload)
                evicted += 1
        if evicted:
            self.fill()
        self._write_mirror()
        return evicted

    # -- observability -----------------------------------------------------

    def status(self) -> dict:
        """JSON-clean pool health (the `fleet status` standby block's
        shape — also what the mirror file holds)."""
        idle = sorted(self.idle(), key=lambda s: s.slot_id)
        doc = {
            "size_target": self.size,
            "pool_size": len(idle),
            "schema_version": self.schema_version,
            "slots": [
                {
                    "slot": s.slot_id,
                    "warm_age_s": round(s.warm_age_s(), 3),
                    "schema": s.schema_version,
                }
                for s in idle
            ],
            "promotions": dict(sorted(self.promotions.items())),
            "promotions_total": sum(self.promotions.values()),
            "schema_stale_evictions": self.stale_evictions,
            "misses": self.misses,
        }
        self.metrics.pool_size.set(len(idle))
        for s in idle:
            self.metrics.warm_age.set(s.warm_age_s(), slot=str(s.slot_id))
        return doc

    def _write_mirror(self) -> None:
        atomic_write_json(self.mirror_path, self.status())

    def close(self) -> None:
        if self.retire is not None:
            for slot in self.slots:
                if slot.state == "warm":
                    self.retire(slot.payload)
        self.journal.close()


class StandbyServe:
    """The in-child half of a `serve --standby` process: sits in
    ``sched._fleet_owner`` while the child waits unclaimed, answering
    only ``standby_status`` and ``adopt_shard`` (fleet_dispatch routes
    here via the ``standby_dispatch`` hook).  Adoption builds the REAL
    ShardOwner around the already-warm scheduler — lease claim, journal
    recovery, shard guard — after which every fleet op flows through the
    ordinary dispatch table."""

    def __init__(self, sched, schema_version: int = 0):
        self.sched = sched
        self.schema_version = int(schema_version)
        self.born_mono = time.monotonic()
        self.owner = None

    def refresh_recovered_taints(self) -> None:
        # SidecarServer refreshes every fleet owner's recovered-taints
        # overlay at boot; a parked standby owns no journal to recover
        # from, so this is a no-op until adoption (which builds the real
        # ShardOwner against the adopted shard's journal).
        if self.owner is not None:
            self.owner.refresh_recovered_taints()

    def standby_dispatch(self, op: str, payload: dict) -> dict:
        from .owner import fleet_dispatch

        if self.owner is not None and op not in (
            "standby_status",
            "adopt_shard",  # idempotent: a retried adopt must not error
        ):
            return fleet_dispatch(self.owner, op, payload)
        if op == "standby_status":
            return {
                "standby": self.owner is None,
                "adopted_shard": (
                    None if self.owner is None else self.owner.shard_id
                ),
                "schema_version": self.schema_version,
                "warm_age_s": round(time.monotonic() - self.born_mono, 3),
            }
        if op == "adopt_shard":
            return self._adopt(payload)
        if op == "preempt_propose":
            # Eval-only dry run, allowed BEFORE adoption: the warm wave
            # compiles the preemption programs while the child is still
            # parked (nothing is deleted or nominated), so a promotion
            # never pays that compile mid-incident.
            from ..api import serialize

            cand = self.sched.preempt_propose(
                serialize.pod_from_data(payload["pod"])
            )
            return cand if cand is not None else {}
        raise ValueError(
            f"standby child not adopted; fleet op {op!r} unavailable"
        )

    def _adopt(self, payload: dict) -> dict:
        from .owner import ShardOwner
        from .shardmap import ShardMap

        if self.owner is not None:
            return {
                "adopted": self.owner.shard_id,
                "already": True,
                "recovery": self.owner.recovery_stats,
            }
        t0 = time.perf_counter()
        shard_id = int(payload["shard_id"])
        live = getattr(self.sched, "journal", None)
        if live is not None and payload.get("journal_dir") and (
            os.path.abspath(payload["journal_dir"])
            == os.path.abspath(getattr(live, "dir", ""))
        ):
            # The standby's own serve journal (pre-created at boot) is
            # NOT the adopted shard's WAL — re-opening the attached dir
            # from inside the serve thread deadlocks; fail loudly.
            raise ValueError(
                "adopt_shard journal_dir is the standby's own serve "
                "journal; pass the adopted shard's journal dir"
            )
        smap = None
        if payload.get("map_path"):
            smap = ShardMap.load(payload["map_path"])
        elif payload.get("map"):
            doc = payload["map"]
            smap = ShardMap(
                buckets=doc["buckets"],
                overrides=doc.get("overrides", {}),
                version=doc.get("version", 0),
                epoch=doc.get("epoch", 0),
            )
        self.owner = ShardOwner(
            shard_id,
            self.sched,
            shard_map=smap,
            state_dir=payload.get("journal_dir") or None,
            journal_fsync=bool(payload.get("journal_fsync", True)),
            snapshot_every_batches=int(payload.get("snapshot_every", 8)),
            lifecycle=payload.get("lifecycle") or None,
        )
        self.sched._fleet_owner = self
        return {
            "adopted": shard_id,
            "already": False,
            "recovery": self.owner.recovery_stats,
            "adopt_s": round(time.perf_counter() - t0, 6),
        }
