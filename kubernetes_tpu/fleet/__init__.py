"""Partitioned scheduler fleet: N scheduler processes, each owning a
disjoint node shard behind its own lease-epoch fence and WAL journal.

The single-process scheduler schedules a whole batch per device pass,
but millions of users means more than one scheduler process.
This package composes the primitives PRs 3–6 built — `FileLease` epoch
fencing, the write-ahead journal, the flight recorder, the soak harness
— into a horizontally scalable control plane, the shape Tesserae
(arxiv 2508.04953) gives placement policies: partition the cluster,
preserve the global constraints.

- ``shardmap``: the fsync'd, epoch-versioned shard-map file — which
  owner holds which nodes — with split/merge/rebalance and journaled
  handoff records.
- ``owner``: one shard's scheduler process: a TPUScheduler scoped to the
  shard's nodes behind its own lease epoch and journal, exposing the
  propose/commit/reserve protocol surface (in-process or over the
  sidecar Envelope wire via the ``fleet`` frame).
- ``router``: the thin fleet front door — assigns pods to shards by
  feasibility-aware hashing with a forwarding path for misroutes, and
  arbitrates the two decisions a partition cannot make locally:
  cross-shard preemption and gang admission spanning shards (two-phase
  reserve/commit with journaled intent records).
- ``takeover``: a dead owner's shard is taken over by a survivor with
  bit-identical journal replay behind an epoch bump.
- ``autoscaler``: the elastic half (ISSUE 11) — a deterministic
  load-driven control loop that watches per-shard binding-rate
  imbalance / queue depth / SLO / reachability on the logical clock
  and issues live split/merge/rebalance handoffs through the same
  journaled path, with hysteresis, cooldowns, and an actions-per-window
  budget so flapping load cannot thrash the map.

The oracle discipline carries over: an N-shard fleet binds
bit-identically to the single-scheduler run on the golden scenarios
(tests/test_fleet.py), and the SIGKILL crash matrix extends to shard
failover (scripts/run_fault_matrix.py --kill)."""

from .router import FleetRouter  # noqa: F401
from .shardmap import ShardMap  # noqa: F401
from .owner import (  # noqa: F401
    FleetOwnerUnreachable,
    ShardOwner,
    WireShardOwner,
    fleet_dispatch,
)
from .takeover import absorb_shard, recover_shard  # noqa: F401
from .standby import StandbyPool, StandbyServe  # noqa: F401
from .autoscaler import (  # noqa: F401
    AutoscalerConfig,
    FleetAutoscaler,
    choose_action,
    imbalance_ratios,
)
