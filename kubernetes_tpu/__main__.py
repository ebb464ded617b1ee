"""CLI: the cmd/kube-scheduler analog (config load → validate → run).

Subcommands:
  validate <config.json>          strict config validation (apis/config/validation)
  serve --socket PATH [...]       host the engine behind the sidecar protocol
                                  (--http-port adds /metrics + /healthz + /events;
                                  --journal-dir arms crash-safe durable state)
  recover --journal-dir DIR       offline recovery: rebuild scheduler state from
                                  snapshot + journal and print what survived
  bench [workload ...]            the scheduler_perf-style harness, in process:
                                  counts and correctness, never a speed
                                  (speed: python3 perfbench/run.py)
  soak [--seconds N ...]          open-loop traffic soak: SLO percentiles,
                                  speculation miss-rate knee, journal growth
  fleet <action> --map PATH       shard-map administration for the
                                  partitioned fleet (init/status/split/
                                  merge/rebalance, plus `autoscale`: an
                                  offline load-driven decision pass over
                                  live owners); serve --shard-of k/N
                                  joins a process to one shard
  dump --socket PATH              debugger state dump of a live sidecar
  metrics --socket PATH           Prometheus text scrape (or --events) of a live sidecar
  flight --socket PATH            flight-recorder readout (per-batch phase attribution)

Config file format (the KubeSchedulerConfiguration analog, JSON):
  {
    "profiles": [
      {"name": "default-scheduler",
       "filters": ["NodeResourcesFit", ...],
       "scorers": [["NodeResourcesFit", 1], ...],
       "percentage_of_nodes_to_score": 100,
       "scoring_strategy": {"type": "LeastAllocated",
                             "resources": [["cpu", 1], ["memory", 1]]}}
    ],
    "batch_size": 4096, "chunk_size": 64
  }
Omitted fields default like the in-tree defaults (default_plugins.go).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .framework.config import DEFAULT_PROFILE, Profile, ScoringStrategy, validate_profile


_PROFILE_KEYS = {
    "name", "filters", "scorers", "percentage_of_nodes_to_score",
    "hard_pod_affinity_weight", "tie_break_seed", "scoring_strategy",
}
_TOP_KEYS = {"profiles", "batch_size", "chunk_size"}


def load_config(path: str) -> dict:
    """Load + STRICTLY parse a config file: unknown keys are errors (the
    strict decoding the reference's scheme gives component configs).

    Two formats: the versioned external
    ``kubescheduler.config.k8s.io/v1`` form (detected by apiVersion/kind;
    defaulting + conversion in framework/configv1.py) and the flat native
    form below."""
    with open(path) as f:
        raw = json.load(f)
    from .framework import configv1

    if configv1.is_versioned(raw):
        return configv1.convert(raw)
    unknown = set(raw) - _TOP_KEYS
    if unknown:
        raise ValueError(f"unknown config keys: {sorted(unknown)}")
    profiles = []
    for p in raw.get("profiles", []):
        bad = set(p) - _PROFILE_KEYS
        if bad:
            raise ValueError(
                f"profile {p.get('name', '?')!r}: unknown keys {sorted(bad)}"
            )
        kwargs: dict = {}
        if "name" in p:
            kwargs["name"] = p["name"]
        if "filters" in p:
            kwargs["filters"] = tuple(p["filters"])
        if "scorers" in p:
            kwargs["scorers"] = tuple((n, int(w)) for n, w in p["scorers"])
        if "percentage_of_nodes_to_score" in p:
            kwargs["percentage_of_nodes_to_score"] = p["percentage_of_nodes_to_score"]
        if "hard_pod_affinity_weight" in p:
            kwargs["hard_pod_affinity_weight"] = p["hard_pod_affinity_weight"]
        if "tie_break_seed" in p:
            kwargs["tie_break_seed"] = p["tie_break_seed"]
        if "scoring_strategy" in p:
            ss = p["scoring_strategy"]
            kwargs["scoring_strategy"] = ScoringStrategy(
                type=ss.get("type", "LeastAllocated"),
                resources=tuple(
                    (n, int(w)) for n, w in ss.get("resources", [["cpu", 1], ["memory", 1]])
                ),
                shape=tuple(
                    (int(u), int(s)) for u, s in ss.get("shape", [[0, 0], [100, 10]])
                ),
            )
        profiles.append(Profile(**kwargs))
    return {
        "profiles": profiles or [DEFAULT_PROFILE],
        "batch_size": int(raw.get("batch_size", 256)),
        "chunk_size": int(raw.get("chunk_size", 1)),
        "feature_gates": None,  # legacy format has no gate surface
    }


def cmd_validate(args) -> int:
    try:
        cfg = load_config(args.config)
    except ValueError as exc:
        print(f"config: {exc}")
        return 1
    for w in cfg.get("warnings", ()):
        print(f"warning: {w}")
    bad = 0
    if cfg["batch_size"] % cfg["chunk_size"]:
        print(
            f"batch_size {cfg['batch_size']} is not a multiple of "
            f"chunk_size {cfg['chunk_size']}"
        )
        bad += 1
    for p in cfg["profiles"]:
        errs = validate_profile(p)
        for e in errs:
            print(f"{p.name}: {e}")
        bad += len(errs)
    print(f"{len(cfg['profiles'])} profile(s), {bad} violation(s)")
    return 1 if bad else 0


def _build_scheduler(args):
    """serve/recover's shared scheduler construction (config or flags)."""
    from .scheduler import TPUScheduler

    if args.config:
        cfg = load_config(args.config)
        for w in cfg.get("warnings", ()):
            print(f"warning: {w}", flush=True)
        profiles = cfg["profiles"]
        queue = None
        if "pod_initial_backoff_s" in cfg or "pod_max_backoff_s" in cfg:
            from .queue import SchedulingQueue

            queue = SchedulingQueue(
                initial_backoff_s=cfg.get("pod_initial_backoff_s", 1.0),
                max_backoff_s=cfg.get("pod_max_backoff_s", 10.0),
            )
        sched = TPUScheduler(
            profile=profiles[0],
            profiles=profiles[1:],
            batch_size=cfg["batch_size"],
            chunk_size=cfg["chunk_size"],
            feature_gates=cfg.get("feature_gates"),
            extenders=cfg.get("extenders"),
            queue=queue,
            pipeline_depth=getattr(args, "pipeline_depth", 1),
        )
    else:
        from .framework.config import named_extra_profiles

        # Named extra profiles (ISSUE 14: throughput-aware /
        # learned-scorer) registered beside the default; pods select
        # by schedulerName.  Full profile control stays with --config.
        profiles = named_extra_profiles(getattr(args, "profile", ""))
        mm_doc = None
        mm_path = getattr(args, "measured_matrix", "")
        if mm_path:
            # ISSUE 16: arm a MEASURED throughput matrix (the flight-
            # derived measured_matrix.json artifact) — it replaces the
            # synthetic matrix in the throughput-aware profile,
            # registering the profile if --profile did not.
            from .framework import measured
            from .ops.throughput import throughput_aware_profile

            try:
                mm_doc = measured.load(mm_path)
            except (OSError, ValueError) as e:
                raise SystemExit(f"--measured-matrix {mm_path}: {e}")
            profiles = [
                p for p in profiles if p.name != "throughput-aware-scheduler"
            ] + [throughput_aware_profile(matrix=measured.matrix_rows(mm_doc))]
        sched = TPUScheduler(
            batch_size=args.batch_size,
            chunk_size=args.chunk_size,
            pipeline_depth=getattr(args, "pipeline_depth", 1),
            tenant_attribution=not getattr(args, "no_observability", False),
            profiles=profiles,
        )
        if mm_doc is not None:
            # Publish the armed rows into the gauge family so a scrape
            # shows exactly what the profile scores against.
            sched.note_measured_matrix(mm_doc)
    return sched


def _open_journal(journal_dir: str, fsync: bool):
    """Acquire the journal directory's own lease (the fencing-epoch
    source — distinct from the serve socket's lease, which guards the
    SOCKET) and open the write-ahead journal under it.  Returns
    (lease, journal)."""
    from .framework.leaderelection import FileLease, read_epoch
    from .journal import Journal

    os.makedirs(journal_dir, exist_ok=True)
    lease_path = os.path.join(journal_dir, "lease")
    lease = FileLease(lease_path, identity=f"journal-{os.getpid()}")
    lease.acquire(block=True)
    journal = Journal(
        journal_dir,
        epoch=lease.epoch,
        fence=lambda: read_epoch(lease_path),
        fsync=fsync,
    )
    return lease, journal


def _fleet_owner_for(args, sched, lifecycle=None):
    """serve --shard-of k/N: bind this process to one shard of the
    partitioned fleet — load (or initialize) the shard map, install the
    shard guard, and return the ShardOwner the `fleet` frame dispatches
    through.  The serve journal (--journal-dir) doubles as the shard's
    WAL; the shard map file is shared by every owner and the router.
    ``lifecycle`` arms the PER-OWNER failure-response loop (ISSUE 10):
    the shard judges its own nodes from the Lease frames the router
    routes here, and its evictions ride fleet responses back to the
    router for fleet-wide requeue."""
    from .fleet import ShardMap, ShardOwner

    k, _, n = args.shard_of.partition("/")
    shard_id, n_shards = int(k), int(n)
    if os.path.exists(args.shard_map):
        # An existing map is the ownership truth; K may exceed the
        # original N — the elastic fleet spawns owners for shard ids the
        # autoscaler's splits create (the child adopts the live map via
        # the `set_map` fleet op before its first import).
        if shard_id < 0:
            raise SystemExit(f"--shard-of {args.shard_of}: need k >= 0")
        shard_map = ShardMap.load(args.shard_map)
    else:
        if not 0 <= shard_id < n_shards:
            raise SystemExit(
                f"--shard-of {args.shard_of}: need 0 <= k < N to "
                "initialize a fresh map"
            )
        shard_map = ShardMap(n_shards=n_shards)
        shard_map.save(args.shard_map)
    return ShardOwner(
        shard_id, sched, shard_map, lifecycle=lifecycle,
        observability=not getattr(args, "no_observability", False),
    )


def cmd_serve(args) -> int:
    from .sidecar import SidecarServer

    sched = _build_scheduler(args)
    node_grace = getattr(args, "node_grace_s", 0.0)
    lifecycle = None
    if node_grace > 0:
        lifecycle = {
            "node_grace_s": node_grace,
            "node_unreachable_s": getattr(args, "node_unreachable_s", 0.0),
            "gc_horizon_s": getattr(args, "gc_horizon_s", 0.0),
        }
    fleet_owner = None
    if getattr(args, "standby", False):
        # Warm-standby child (ISSUE 18): boot + compile NOW, own nothing.
        # The scheduler is warmed by the spawner over the ordinary wire
        # surface; fleet frames park at the StandbyServe shim until an
        # ``adopt_shard`` promotion builds the real ShardOwner (lease
        # claim + journal recovery) around the already-warm scheduler.
        if args.shard_of:
            raise SystemExit("--standby and --shard-of are exclusive: a "
                             "standby owns nothing until promoted")
        from .fleet.standby import StandbyServe

        fleet_owner = StandbyServe(sched)
    elif args.shard_of:
        if not args.journal_dir:
            # The serve journal doubles as the shard's WAL; an owner
            # without one would silently no-op every gang_reserve/bind/
            # handoff append the fleet's convergence story depends on.
            raise SystemExit("--shard-of requires --journal-dir")
        # The lifecycle flags arm PER OWNER (ShardOwner installs the
        # eviction-requeue hook the router drains) — before ISSUE 10 the
        # arming below was single-process only.
        fleet_owner = _fleet_owner_for(args, sched, lifecycle=lifecycle)
    elif lifecycle is not None:
        # Single-process arming (ISSUE 9): heartbeat staleness →
        # NotReady/Unreachable taints → tolerationSeconds eviction →
        # requeue, plus the pod-GC horizon sweep.
        sched.node_lifecycle.arm(
            grace_period_s=node_grace,
            unreachable_after_s=(
                getattr(args, "node_unreachable_s", 0.0) or node_grace * 2.5
            ),
        )
        sched.pod_gc.arm(
            gc_horizon_s=getattr(args, "gc_horizon_s", 0.0) or node_grace * 6
        )
    lease = None
    if args.leader_elect:
        # Single-active-sidecar guarantee (cmd-level leaderElectAndRun,
        # app/server.go:140): standbys park here until the incumbent
        # releases or dies, then take over the socket.
        from .framework.leaderelection import FileLease

        lease = FileLease(args.lease_file, identity=f"serve-{os.getpid()}")
        holder = lease.holder()
        if not lease.acquire(block=False):
            print(
                f"waiting for lease {args.lease_file}"
                + (f" held by {holder.get('holderIdentity')}" if holder else ""),
                flush=True,
            )
            lease.acquire(block=True)
        print(f"acquired lease {args.lease_file}", flush=True)
    journal_lease = journal = None
    if args.journal_dir:
        # Crash-safe durable state (journal.py): the server recovers the
        # pre-crash world from snapshot + write-ahead log before its
        # first frame, and every commit this tenure is fenced by the
        # journal lease's epoch.
        journal_lease, journal = _open_journal(
            args.journal_dir, fsync=args.journal_fsync == "always"
        )
    # The device contract (utils.require_device): asked only once the
    # leases are held — a parked standby must not sit on the chip — and
    # before the socket binds, so nothing ever listens without a device.
    # The health frame and /healthz carry what THIS process got.
    from .utils import require_device

    device = require_device()
    print(
        f"device: platform={device['platform']} "
        f"kind={device['device_kind']} count={device['n_devices']}",
        flush=True,
    )
    health = {"leader": True, "leaseFile": args.lease_file} if lease else {}
    health.update(device)
    if journal is not None:
        health["journalDir"] = args.journal_dir
    if fleet_owner is not None:
        if getattr(args, "standby", False):
            health["standby"] = True
        else:
            health["shard"] = fleet_owner.shard_id
            health["shardMap"] = args.shard_map
    srv = SidecarServer(
        args.socket,
        scheduler=sched,
        speculate=args.speculate,
        # Keepalive bounds a silently-partitioned subscriber's staleness
        # (the Go side reads with a 60s deadline); meaningless without
        # the push stream.
        keepalive_s=args.keepalive if args.speculate else None,
        health_extra=health,
        # Plain-HTTP observability (/metrics, /healthz, /events) for an
        # unmodified Prometheus; the framed `metrics` frame serves the
        # same bytes to hosts already on the socket.
        http_port=args.http_port if args.http_port >= 0 else None,
        http_host=args.http_host,
        journal=journal,
        snapshot_every_batches=args.snapshot_every,
        fleet_owner=fleet_owner,
    )
    if srv.recovery_stats is not None:
        print(
            f"recovered from {args.journal_dir}: "
            f"{json.dumps(srv.recovery_stats, sort_keys=True)} "
            f"(epoch {journal.epoch})",
            flush=True,
        )
    print(
        f"sidecar listening on {args.socket}"
        + (" (speculative)" if args.speculate else "")
        + (
            f", http observability on :{srv.http.port}"
            if srv.http is not None
            else ""
        ),
        flush=True,
    )
    # Graceful-kill black box: SIGTERM dumps the flight-recorder ring
    # (per-batch phase attribution + transition markers) before the
    # process exits — the last evidence an operator gets from a pod
    # being terminated.  SIGKILL is the chaos harness's business.
    sched.flight.install_sigterm()
    # The collector's pauses, counted from here on
    # (scheduler_gc_collections_total, scheduler_gc_pause_seconds_total),
    # and its old generation in the server's hands: frozen at every batch
    # boundary, swept at the checkpoint (framework/tracing.py).
    from .framework.tracing import PROCESS

    PROCESS.hook_gc()
    PROCESS.heap_armed = True
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        srv.close()
    except SystemExit:
        srv.close()
        raise
    finally:
        if journal_lease is not None:
            journal_lease.release()
        if lease is not None:
            lease.release()
    return 0


def cmd_recover(args) -> int:
    """Offline recovery: rebuild a scheduler from the journal directory
    and print what survived — the operator's post-crash triage surface
    (and the `recover` half the chaos harness drives end to end)."""
    from .journal import recover

    sched = _build_scheduler(args)
    lease, journal = _open_journal(
        args.journal_dir, fsync=args.journal_fsync == "always"
    )
    try:
        stats = recover(sched, journal)
        summary = {
            "journal": journal.stats(),
            "recovery": stats,
            "nodes": len(sched.cache.nodes),
            "bound_pods": sum(
                1 for pr in sched.cache.pods.values() if pr.bound
            ),
            "queue": sched.queue.depths(),
            "quarantine": sched.queue.quarantined(),
            "bindings": {
                uid: pr.node_name
                for uid, pr in sorted(sched.cache.pods.items())
                if pr.bound
            },
            # Journaled binds whose node no snapshot holds yet: durable,
            # parked until the host relists the node (the journal keeps
            # decisions, the host keeps the cluster).
            "pending_bindings": {
                uid: d["node"]
                for uid, d in sorted(sched._recovered_bindings.items())
            },
        }
        print(json.dumps(summary, indent=2, sort_keys=True))
    finally:
        lease.release()
    return 0


def cmd_bench(args) -> int:
    from .benchmarks.harness import main as bench_main, row_failed

    if args.profile_dir:
        # Device-side visibility (SURVEY §5: "add JAX profiler traces on
        # the sidecar"): a TensorBoard-loadable XPlane trace of the run.
        import jax

        with jax.profiler.trace(args.profile_dir):
            rows = bench_main(args.workloads or None)
        print(f"jax profiler trace written to {args.profile_dir}")
    else:
        rows = bench_main(args.workloads or None)
    return 1 if any(row_failed(r) for r in rows) else 0


def _parse_hetero_pools(spec: str) -> tuple:
    """--hetero-pools 'tpu-v4=5,tpu-v5e=3' → ((class, weight), ...).
    Malformed entries are CLI usage errors, not tracebacks."""
    pools = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        cls, sep, w = entry.partition("=")
        if not sep or not cls.strip():
            raise SystemExit(
                f"--hetero-pools: entry {entry!r} must be CLASS=WEIGHT"
            )
        try:
            weight = int(w)
        except ValueError:
            raise SystemExit(
                f"--hetero-pools: weight {w!r} for {cls.strip()!r} must "
                "be an integer"
            )
        if weight < 1:
            raise SystemExit(
                f"--hetero-pools: weight for {cls.strip()!r} must be >= 1"
            )
        pools.append((cls.strip(), weight))
    return tuple(pools)


def cmd_soak(args) -> int:
    """Open-loop soak (loadgen/): drive the deployment for --seconds at
    --rate pods/s, then sweep the speculation miss-rate knee over
    --knee-points invalidation intensities.  Prints the artifact JSON
    (the soak artifact schema) and optionally writes it to --out."""
    from .loadgen.soak import SoakConfig, run_soak, strip_private

    knee = tuple(
        float(x) for x in args.knee_points.split(",") if x.strip()
    )
    cfg = SoakConfig(
        seed=args.seed,
        nodes=args.nodes,
        zones=args.zones,
        churn_nodes=args.churn_nodes,
        rate_pods_per_s=args.rate,
        diurnal=args.diurnal,
        mix=args.mix,
        hetero_pools=_parse_hetero_pools(args.hetero_pools),
        profile=args.profile,
        duration_s=args.seconds,
        knee_points=knee,
        knee_phase_s=args.knee_phase,
        invalidation_rate_per_s=args.invalidation_rate,
        node_flap_period_s=args.flap_period,
        flap_down_s=args.flap_down,
        cold_consumer_period_s=args.cold_consumer_period,
        live_pod_cap=args.live_pod_cap,
        slo_budget_ms=args.slo_budget_ms,
        batch_size=args.batch_size,
        chunk_size=args.chunk_size,
        two_process=not args.in_process,
        journal_dir=args.journal_dir,
        journal_fsync=args.journal_fsync,
        snapshot_every=args.snapshot_every,
        pace=args.pace,
        out_dir=args.out_dir,
    )
    artifact = strip_private(run_soak(cfg))
    doc = json.dumps(artifact, indent=1, sort_keys=True)
    print(doc)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(doc + "\n")
    if artifact["slo"]["p99_ms"] > cfg.slo_budget_ms:
        print(
            f"soak: p99 {artifact['slo']['p99_ms']}ms exceeds the "
            f"{cfg.slo_budget_ms}ms SLO budget "
            f"({artifact['slo']['violations']} violations)",
            file=sys.stderr,
        )
    return 0


def cmd_fleet(args) -> int:
    """Shard-map administration (the operator surface of the partitioned
    fleet): init/status edit nothing but the fsync'd, epoch-versioned map
    file; split/merge/rebalance mutate the map AND print the handoff
    record the acquiring owner must journal before the data moves
    (fleet/router.py apply_handoff orchestrates the live transfer; this
    command is the offline half)."""
    from .fleet import ShardMap

    if args.action == "init":
        m = ShardMap(n_shards=args.shards, n_buckets=args.buckets)
        m.save(args.map)
        print(json.dumps({"initialized": args.map, **m.to_doc()}, indent=1))
        return 0
    m = ShardMap.load(args.map)
    if args.action == "status":
        doc = m.to_doc()
        doc["shard_buckets"] = {
            str(s): sum(1 for b in m.buckets if b == s) for s in m.shard_ids()
        }
        if args.sockets:
            # Live per-owner state over the wire (`serve --shard-of`
            # children): nodes/bindings plus the failure-response block —
            # armed flag, ready/notready/unreachable counts, eviction and
            # GC counters, requeues the router has not drained yet.
            from .sidecar import SidecarClient

            owners = {}
            for sock in args.sockets.split(","):
                sock = sock.strip()
                if not sock:
                    continue
                try:
                    client = SidecarClient(
                        sock, deadline_s=_cli_deadline(args)
                    )
                    try:
                        stats = client.fleet("stats", {})
                    finally:
                        client.close()
                    owners[sock] = {
                        "shard": stats.get("shard"),
                        "nodes": stats.get("nodes"),
                        "bound_pods": stats.get("bound_pods"),
                        "epoch": stats.get("epoch"),
                        "lifecycle": stats.get("lifecycle", {}),
                        # Per-shard tenant skew (top-K tenants by window
                        # commits from the owner's stats mirror): an
                        # operator sees which tenants dominate a shard
                        # without a soak run.
                        "tenants": stats.get("tenants", {}),
                    }
                    if stats.get("fairness") is not None:
                        # Weighted-fair admission mirror (router push,
                        # set_admission): fleet weights/caps plus the
                        # per-tenant status as of the last push —
                        # credit balances, virtual-time lag, pending
                        # depth, oldest wait, starvation-SLO verdict.
                        owners[sock]["fairness"] = stats["fairness"]
                except (OSError, RuntimeError) as exc:
                    owners[sock] = {"unreachable": str(exc)}
            doc["owners"] = owners
            # Measured-throughput block (ISSUE 16): fold every reachable
            # owner's flight ring into the fleet's measured matrix —
            # what `measured --out` would commit, inline in status.
            from .framework import measured
            from .sidecar import SidecarClient as _SC

            snaps = []
            for sock in args.sockets.split(","):
                sock = sock.strip()
                if not sock or "unreachable" in owners.get(sock, {}):
                    continue
                try:
                    client = _SC(sock, deadline_s=_cli_deadline(args))
                    try:
                        snaps.append(client.flight(limit=0))
                    finally:
                        client.close()
                except (OSError, RuntimeError):
                    continue
            if snaps:
                mdoc = measured.derive(snaps)
                doc["measured_throughput"] = {
                    "matrix": mdoc["matrix"],
                    "binds": mdoc["window"]["binds"],
                    "records": mdoc["window"]["records"],
                    "source_sha256": mdoc["source"]["sha256"],
                }
        state_path = _autoscale_state_path(args)
        if os.path.exists(state_path):
            # The autoscaler's status mirror (live loop or `fleet
            # autoscale` invocations): per-shard imbalance/queue/SLO
            # snapshot, last action + cooldowns, window budget.
            try:
                with open(state_path) as f:
                    doc["autoscaler"] = json.load(f)
            except (OSError, ValueError) as exc:
                doc["autoscaler"] = {"unreadable": str(exc)}
        standby_path = f"{args.map}.standby.json"
        if os.path.exists(standby_path):
            # The warm-standby pool's status mirror (ISSUE 18,
            # fleet/standby.py _write_mirror): pool size vs target,
            # per-slot warm age + schema version, promotion and
            # stale-eviction totals — the same atomic-mirror pattern as
            # the autoscaler block above.
            try:
                with open(standby_path) as f:
                    doc["standby"] = json.load(f)
            except (OSError, ValueError) as exc:
                doc["standby"] = {"unreadable": str(exc)}
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 0
    if args.action == "autoscale":
        return _fleet_autoscale(args, m)
    if args.action == "split":
        rec = m.split(args.shard, args.new_shard, drop_pins=args.drop_pins)
    elif args.action == "merge":
        rec = m.merge(args.into, args.absorbed)
    elif args.action == "rebalance":
        # Re-deal over the LIVE ids when the operator's --shards merely
        # restates the current count (a gapped id space after merges
        # must not resurrect an ownerless shard); an explicitly
        # DIFFERENT count is a resize statement — ids 0..N-1, the
        # operator is declaring those owners will exist.
        live = m.shard_ids()
        rec = m.rebalance(
            ids=live if args.shards == len(live) else list(range(args.shards)),
            drop_pins=args.drop_pins,
        )
    else:
        raise SystemExit(f"unknown fleet action {args.action!r}")
    m.save(args.map)
    print(json.dumps({"handoff": rec, "map": m.to_doc()}, indent=1))
    return 0


def _autoscale_state_path(args) -> str:
    return getattr(args, "state", "") or f"{args.map}.autoscaler.json"


def _fleet_autoscale(args, m) -> int:
    """One offline autoscaler decision pass (the `fleet autoscale`
    action): probe each live owner's monotone commit counter over the
    wire, difference against the state file's last probe into a window,
    run the SAME decision core the live loop uses (fleet/autoscaler.py
    ``choose_action``) under the same cooldown/budget damping, and print
    the recommendation — with ``--apply``, also mutate the map file
    (split/merge/rebalance, the offline half; the printed handoff record
    is what the acquiring owner must journal before data moves, exactly
    like the other fleet actions)."""
    import time

    from .fleet import AutoscalerConfig, choose_action
    from .sidecar import SidecarClient

    cfg = AutoscalerConfig(
        split_imbalance_hi=args.split_hi,
        merge_imbalance_lo=args.merge_lo,
        cooldown_s=args.cooldown,
        window_s=args.window,
        max_actions_per_window=args.budget,
        min_window_decisions=args.min_decisions,
        min_shards=args.min_shards,
        max_shards=args.max_shards,
    )
    state_path = _autoscale_state_path(args)
    state: dict = {}
    if os.path.exists(state_path):
        try:
            with open(state_path) as f:
                state = json.load(f)
        except (OSError, ValueError):
            state = {}
    now = time.time()
    commits: dict[int, int] = {}
    nodes_owned: dict[int, int] = {}
    unreachable: list[str] = []
    for sock in (s.strip() for s in args.sockets.split(",")):
        if not sock:
            continue
        try:
            client = SidecarClient(sock, deadline_s=_cli_deadline(args))
            try:
                stats = client.fleet("stats", {})
            finally:
                client.close()
            commits[int(stats["shard"])] = int(
                stats.get("load", {}).get("commits_total", 0)
            )
            # The capacity denominator of the imbalance signal: window
            # share is judged against the shard's NODE share (a shard
            # hosting half the fleet is not "hot" for serving half the
            # binds).
            nodes_owned[int(stats["shard"])] = int(stats.get("nodes", 0))
        except (OSError, RuntimeError) as exc:
            unreachable.append(f"{sock}: {exc}")
    doc: dict = {"clock": round(now, 3), "map": args.map}
    if unreachable:
        # Stale stats never drive an action — same contract as the live
        # loop's FleetOwnerUnreachable deferral.
        doc["deferred"] = "owner-unreachable"
        doc["unreachable"] = unreachable
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 1
    buckets_owned = {
        s: sum(1 for b in m.buckets if b == s) for s in m.shard_ids()
    }
    unprobed = sorted(set(buckets_owned) - set(commits))
    if unprobed:
        # A map shard with no probing socket is exactly as stale as an
        # unreachable one: defaulting its window to zero would read a
        # live, busy shard as cold and --apply could merge it away.
        doc["deferred"] = "unprobed-shard"
        doc["unprobed_shards"] = unprobed
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 1
    last = state.get("last_probe", {})
    reset = sorted(
        s for s, c in commits.items() if c < int(last.get(str(s), 0))
    )
    if reset:
        # The monotone counter moved BACKWARDS: the owner restarted
        # since the last probe (journal replay never re-counts commits),
        # so this window is unknowable — clamping it to zero would read
        # a busy, just-recovered shard as cold and --apply could merge
        # it away.  Re-baseline and defer; the next probe has a real
        # window.
        doc["deferred"] = "counter-reset"
        doc["reset_shards"] = reset
        state["last_probe"] = {str(s): c for s, c in commits.items()}
        state["last_run"] = doc
        tmp = f"{state_path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(state, f, indent=1, sort_keys=True)
        os.replace(tmp, state_path)
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 1
    window = {
        s: c - int(last.get(str(s), 0)) for s, c in commits.items()
    }
    action_times = [
        t for t in state.get("action_times", ()) if t > now - cfg.window_s
    ]
    blocked = frozenset(
        int(s)
        for s, until in state.get("cooldown_until", {}).items()
        if until > now
    )
    doc["window_commits"] = {str(s): window[s] for s in sorted(window)}
    doc["nodes_owned"] = {str(s): nodes_owned[s] for s in sorted(nodes_owned)}
    if len(action_times) >= cfg.max_actions_per_window:
        action, reason = None, "budget"
    else:
        action, reason = choose_action(
            window, buckets_owned, cfg, blocked, nodes_owned=nodes_owned
        )
    if action is None:
        doc["action"] = None
        doc["deferred"] = reason
    else:
        doc["action"] = action
        if args.apply:
            if action["op"] == "split":
                rec = m.split(action["from"], action["to"],
                              drop_pins=args.drop_pins)
            elif action["op"] == "merge":
                rec = m.merge(into=action["to"], absorbed=action["from"])
            else:
                rec = m.rebalance(
                    ids=action.get("shards") or m.shard_ids(),
                    drop_pins=args.drop_pins,
                )
            m.save(args.map)
            doc["handoff"] = rec
            doc["map_doc"] = m.to_doc()
            action_times.append(now)
            cooldowns = state.get("cooldown_until", {})
            for s in (action.get("from"), action.get("to")):
                if s is not None:
                    cooldowns[str(s)] = now + cfg.cooldown_s
            state["cooldown_until"] = cooldowns
        else:
            doc["note"] = "dry run; pass --apply to mutate the map"
    state["last_probe"] = {str(s): c for s, c in commits.items()}
    state["action_times"] = action_times
    state["last_run"] = doc
    tmp = f"{state_path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(state, f, indent=1, sort_keys=True)
    os.replace(tmp, state_path)
    print(json.dumps(doc, indent=1, sort_keys=True))
    return 0


def _cli_deadline(args) -> float | None:
    return args.deadline if args.deadline and args.deadline > 0 else None


def cmd_dump(args) -> int:
    from .sidecar import SidecarClient

    client = SidecarClient(args.socket, deadline_s=_cli_deadline(args))
    print(json.dumps(client.dump(), indent=2, sort_keys=True))
    client.close()
    return 0


def cmd_metrics(args) -> int:
    """Scrape a live sidecar's registry over the socket (the `metrics`
    frame) — same bytes its /metrics HTTP endpoint serves."""
    from .sidecar import SidecarClient

    client = SidecarClient(args.socket, deadline_s=_cli_deadline(args))
    if args.events:
        print(json.dumps(client.events(), indent=2))
    else:
        print(client.metrics(), end="")
    client.close()
    return 0


def cmd_flight(args) -> int:
    """Read a live sidecar's flight recorder (the `flight` frame): the
    per-batch phase-attribution ring + transition markers, as the same
    JSON document the auto-dumps write.  Pipe into
    scripts/profile_report.py for the phase-attribution table."""
    from .sidecar import SidecarClient

    client = SidecarClient(args.socket, deadline_s=_cli_deadline(args))
    print(json.dumps(client.flight(limit=args.limit), indent=1, sort_keys=True))
    client.close()
    return 0


def cmd_trace(args) -> int:
    """Export a live sidecar's flight ring as Perfetto/Chrome
    trace-event JSON (framework/trace_export.py) — same rendering the
    HTTP ``GET /debug/trace`` surface and scripts/export_trace.py
    produce, so a live deployment exports without file access.  Open the
    output in https://ui.perfetto.dev or chrome://tracing."""
    from .framework import trace_export
    from .sidecar import SidecarClient

    client = SidecarClient(args.socket, deadline_s=_cli_deadline(args))
    try:
        doc = client.flight(limit=args.limit)
    finally:
        client.close()
    text = trace_export.render(doc, timebase=args.timebase)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text)
        print(f"wrote {args.out}")
    else:
        print(text, end="")
    return 0


def cmd_explain(args) -> int:
    """Explain one pod's scheduling decision over the socket (the
    `explain` frame): per-op per-node filter verdicts with the rejecting
    plugin named, per-op score columns, the selectHost tie-break trace,
    and the recorded live decision — same JSON the HTTP
    ``GET /debug/explain?uid=`` surface serves."""
    from .sidecar import SidecarClient

    client = SidecarClient(args.socket, deadline_s=_cli_deadline(args))
    try:
        doc = client.explain(args.uid, seq=args.seq)
    finally:
        client.close()
    print(json.dumps(doc, indent=1, sort_keys=True))
    return 0 if "error" not in doc else 1


def cmd_measured(args) -> int:
    """Derive a measured throughput-matrix artifact
    (framework/measured.py) from flight dumps — committed soak dumps,
    merge_fleet documents, or a live sidecar's ring (--socket)."""
    from .framework import measured

    docs = []
    if args.socket:
        from .sidecar import SidecarClient

        client = SidecarClient(args.socket, deadline_s=_cli_deadline(args))
        try:
            docs.append(client.flight(limit=0))
        finally:
            client.close()
    for path in args.dumps:
        with open(path, "r", encoding="utf-8") as f:
            docs.append(json.load(f))
    if not docs:
        raise SystemExit("measured: need --socket and/or flight dump files")
    doc = measured.derive(docs, lc_lo=args.lc_lo, lc_hi=args.lc_hi)
    if not doc["matrix"]:
        raise SystemExit(
            "measured: no (workload class, accel class) binds in the "
            "window — run a heterogeneity profile workload first"
        )
    measured.validate(doc)
    if args.out:
        measured.save(doc, args.out)
        print(
            f"wrote {args.out} — {len(doc['matrix'])} workload classes, "
            f"{doc['window']['binds']} binds "
            f"(source sha {doc['source']['sha256'][:12]}…)"
        )
    else:
        print(json.dumps(doc, indent=1, sort_keys=True))
    return 0


def main(argv: list[str] | None = None) -> int:
    import logging

    # Surface the cycle spans (framework/tracing.py LogIfLong) and other
    # library logs on the CLI; library embedders configure their own.
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    ap = argparse.ArgumentParser(prog="kubernetes_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    v = sub.add_parser("validate", help="validate a scheduler config file")
    v.add_argument("config")
    v.set_defaults(fn=cmd_validate)

    s = sub.add_parser("serve", help="serve the sidecar protocol")
    s.add_argument("--socket", required=True)
    s.add_argument("--config", default="")
    s.add_argument("--batch-size", type=int, default=256)
    s.add_argument("--chunk-size", type=int, default=1)
    s.add_argument(
        "--pipeline-depth", type=int, default=1, metavar="DEPTH",
        help="software-pipeline the batch loop (ISSUE 15): depth 1 is "
        "the serial parity configuration; depth 2 dispatches batch k+1 "
        "before draining batch k's group-committed journal records, so "
        "the fsync + apply stage runs under the in-flight device pass "
        "(bindings bit-identical either way)",
    )
    s.add_argument(
        "--profile", default="",
        choices=("", "default", "throughput-aware", "learned-scorer"),
        help="register a named extra profile beside the default (ISSUE "
        "14 heterogeneity scorers); pods select it by schedulerName — "
        "full profile control (matrices, weights files) via --config",
    )
    s.add_argument(
        "--measured-matrix", default="", metavar="PATH",
        help="arm a measured throughput matrix artifact (ISSUE 16: "
        "framework/measured.py measured_matrix.json) — the throughput-"
        "aware profile scores against the MEASURED rows instead of the "
        "synthetic committed matrix, and the rows are published as "
        "scheduler_measured_throughput_millis gauges",
    )
    s.add_argument(
        "--speculate", action="store_true",
        help="enable the speculative frontend + decision push stream",
    )
    s.add_argument(
        "--keepalive", type=float, default=10.0,
        help="push-stream keepalive interval in seconds (speculate only)",
    )
    s.add_argument(
        "--http-port", type=int, default=-1, metavar="PORT",
        help="serve /metrics + /healthz + /events over plain HTTP "
        "(0 = ephemeral port, -1 = disabled)",
    )
    s.add_argument(
        "--http-host", default="127.0.0.1", metavar="HOST",
        help="bind address for the HTTP observability listener "
        "(0.0.0.0 for off-host Prometheus scrapes)",
    )
    s.add_argument(
        "--leader-elect", action="store_true",
        help="park until the lease file's flock is free (single active sidecar)",
    )
    s.add_argument(
        "--lease-file", default="/tmp/kubernetes_tpu-serve.lease",
        help="leader-election lease path (see framework/leaderelection.py)",
    )
    s.add_argument(
        "--journal-dir", default="",
        help="write-ahead binding journal directory (crash-safe durable "
        "state; empty = in-memory only, the pre-PR-3 behavior)",
    )
    s.add_argument(
        "--journal-fsync", choices=("always", "never"), default="always",
        help="fsync policy for journal appends (snapshots always fsync); "
        "'never' trades the last few records for append latency",
    )
    s.add_argument(
        "--snapshot-every", type=int, default=64, metavar="BATCHES",
        help="a checkpoint once the journal holds as many records past "
        "the last one as this many full batches (N x --batch-size "
        "records: the log a recovery replays; 0 disables periodic "
        "snapshots)",
    )
    s.add_argument(
        "--node-grace-s", type=float, default=0.0, metavar="SECONDS",
        help="arm the node-lifecycle controller: a Lease-tracked node "
        "whose heartbeat is older than this (on the logical Lease clock) "
        "is tainted NotReady, its pods evicted after tolerationSeconds "
        "and requeued (0 = disarmed, the consumer-only behavior); with "
        "--shard-of the loop arms PER OWNER — the router routes Lease "
        "frames to the owning shard and requeues its evictions "
        "fleet-wide",
    )
    s.add_argument(
        "--node-unreachable-s", type=float, default=0.0, metavar="SECONDS",
        help="staleness beyond which a NotReady node becomes Unreachable "
        "(0 = 2.5 × --node-grace-s)",
    )
    s.add_argument(
        "--gc-horizon-s", type=float, default=0.0, metavar="SECONDS",
        help="pod-GC horizon: pods still bound to a node Unreachable this "
        "long are evicted+requeued regardless of tolerations "
        "(0 = 6 × --node-grace-s)",
    )
    s.add_argument(
        "--shard-of", default="", metavar="K/N",
        help="join the partitioned fleet as shard K of N: only shard-map-"
        "owned nodes are absorbed, and the `fleet` frame (propose/commit/"
        "reserve/handoff ops) is served (kubernetes_tpu/fleet)",
    )
    s.add_argument(
        "--standby", action="store_true",
        help="boot as a warm-standby fleet child (ISSUE 18): compile the "
        "engine against the live featurization schema and park — no "
        "shard, no journal, lease unclaimed — until a router promotes it "
        "via the `fleet` frame's adopt_shard op (fleet/standby.py); "
        "promotion is a journaled handoff + lease claim instead of a "
        "~15s cold boot; mutually exclusive with --shard-of",
    )
    s.add_argument(
        "--no-observability", action="store_true",
        help="disable tenant attribution and the owner-side fleet "
        "observability surface (per-op flight records, op spans) — "
        "decisions are bit-identical either way; the soak's "
        "observability A/B leg passes this to serve children",
    )
    s.add_argument(
        "--shard-map", default="/tmp/kubernetes_tpu-shardmap.json",
        help="fsync'd, epoch-versioned shard-map file shared by every "
        "owner and the fleet router (created if absent)",
    )
    s.set_defaults(fn=cmd_serve)

    fle = sub.add_parser(
        "fleet", help="shard-map administration for the partitioned fleet"
    )
    fle.add_argument(
        "action",
        choices=(
            "init", "status", "split", "merge", "rebalance", "autoscale",
        ),
    )
    fle.add_argument("--map", required=True, help="shard-map file path")
    fle.add_argument("--shards", type=int, default=2,
                     help="shard count (init/rebalance)")
    fle.add_argument("--buckets", type=int, default=64,
                     help="fixed bucket count (init)")
    fle.add_argument("--shard", type=int, default=0, help="shard to split")
    fle.add_argument("--new-shard", type=int, default=1,
                     help="shard receiving the split half")
    fle.add_argument("--into", type=int, default=0,
                     help="surviving shard (merge)")
    fle.add_argument("--absorbed", type=int, default=1,
                     help="shard being absorbed (merge)")
    fle.add_argument(
        "--sockets", default="", metavar="SOCK,SOCK,...",
        help="status only: also query these live `serve --shard-of` "
        "owners over the wire and report per-owner node/binding counts "
        "plus lifecycle state (armed, ready/notready/unreachable, "
        "evictions, pending requeues)",
    )
    fle.add_argument(
        "--deadline", type=float, default=5.0,
        help="per-owner probe deadline in seconds (status --sockets); "
        "<=0 waits forever",
    )
    fle.add_argument(
        "--drop-pins", action="store_true",
        help="split only: explicitly DROP the split shard's override "
        "pins (they fall back to the bucket rule and the names ride the "
        "handoff record); by default pins survive on the source — never "
        "silently remapped",
    )
    fle.add_argument(
        "--state", default="", metavar="PATH",
        help="autoscaler state/status file (cooldowns, budget, last "
        "probe; default: <map>.autoscaler.json — `fleet status` embeds "
        "it when present)",
    )
    fle.add_argument(
        "--apply", action="store_true",
        help="autoscale only: mutate the map file when the decision "
        "core recommends an action (default: dry-run print)",
    )
    fle.add_argument("--split-hi", type=float, default=1.6,
                     help="autoscale: split at imbalance ratio >= this")
    fle.add_argument("--merge-lo", type=float, default=0.35,
                     help="autoscale: merge at imbalance ratio <= this")
    fle.add_argument("--cooldown", type=float, default=60.0,
                     help="autoscale: per-shard cooldown seconds")
    fle.add_argument("--window", type=float, default=300.0,
                     help="autoscale: actions-per-window budget window")
    fle.add_argument("--budget", type=int, default=2,
                     help="autoscale: max actions per window")
    fle.add_argument("--min-decisions", type=int, default=12,
                     help="autoscale: window commits below this are "
                     "noise (no action)")
    fle.add_argument("--min-shards", type=int, default=1)
    fle.add_argument("--max-shards", type=int, default=8)
    fle.set_defaults(fn=cmd_fleet)

    rec = sub.add_parser(
        "recover", help="offline recovery report from a journal directory"
    )
    rec.add_argument("--journal-dir", required=True)
    rec.add_argument("--config", default="")
    rec.add_argument("--batch-size", type=int, default=256)
    rec.add_argument("--chunk-size", type=int, default=1)
    rec.add_argument(
        "--journal-fsync", choices=("always", "never"), default="always"
    )
    rec.set_defaults(fn=cmd_recover)

    bench_help = (
        "run the in-process scheduler_perf-style rows: counts and "
        "correctness, never a speed (speed is python3 perfbench/run.py on "
        "the chip, recorded in PERF_LEDGER.jsonl and PERF.md)"
    )
    b = sub.add_parser("bench", help=bench_help, description=bench_help)
    b.add_argument("workloads", nargs="*")
    b.add_argument("--profile-dir", default="", help="write a jax.profiler trace here")
    b.set_defaults(fn=cmd_bench)

    sk = sub.add_parser(
        "soak", help="open-loop traffic soak (SLO percentiles + knee)"
    )
    sk.add_argument("--seed", type=int, default=6)
    sk.add_argument("--seconds", type=float, default=60.0,
                    help="sustained-phase duration (the SLO window)")
    sk.add_argument("--rate", type=float, default=60.0,
                    help="mean arrival rate, pods/s (open-loop)")
    sk.add_argument("--nodes", type=int, default=200)
    sk.add_argument("--zones", type=int, default=10)
    sk.add_argument("--churn-nodes", type=int, default=8)
    sk.add_argument("--mix", default="basic",
                    help="workload mix (loadgen.workloads.MIXES)")
    sk.add_argument("--hetero-pools", default="", metavar="CLASS=W,...",
                    help="accelerator-class node pools, e.g. "
                    "'tpu-v4=5,tpu-v5e=3,gpu-a100=2' (ISSUE 14; empty = "
                    "homogeneous)")
    sk.add_argument("--profile", default="",
                    choices=("", "default", "throughput-aware", "learned-scorer"),
                    help="extra registered profile the stream selects by "
                    "schedulerName (pair with --mix hetero)")
    sk.add_argument("--diurnal", action="store_true",
                    help="diurnally-modulated arrivals instead of flat Poisson")
    sk.add_argument("--knee-points", default="0.5,2,8,32,128", metavar="R,R,...",
                    help="invalidation intensities (events/s) for the knee sweep")
    sk.add_argument("--knee-phase", type=float, default=20.0,
                    help="seconds per knee intensity point")
    sk.add_argument("--invalidation-rate", type=float, default=0.1,
                    help="baseline invalidation events/s during the sustained phase")
    sk.add_argument("--flap-period", type=float, default=30.0,
                    help="seconds between node flaps (0 disables)")
    sk.add_argument("--flap-down", type=float, default=2.0)
    sk.add_argument("--cold-consumer-period", type=float, default=0.0,
                    help="seconds between cold push-consumer restarts (0 disables)")
    sk.add_argument("--live-pod-cap", type=int, default=2000,
                    help="bound pods beyond this retire oldest-first")
    sk.add_argument("--slo-budget-ms", type=float, default=250.0)
    sk.add_argument("--batch-size", type=int, default=512)
    sk.add_argument("--chunk-size", type=int, default=64)
    sk.add_argument("--in-process", action="store_true",
                    help="host the sidecar in-process instead of spawning serve")
    sk.add_argument("--journal-dir", default="",
                    help="journal directory (default: a run-scoped temp dir)")
    sk.add_argument("--journal-fsync", choices=("always", "never"),
                    default="always")
    sk.add_argument("--snapshot-every", type=int, default=64)
    sk.add_argument("--pace", choices=("real", "virtual"), default="real",
                    help="real = follow the arrival schedule's wall deadlines; "
                    "virtual = issue back to back (determinism checks)")
    sk.add_argument("--out", default="", help="also write the artifact JSON here")
    sk.add_argument("--out-dir", default="",
                    help="flight-dump / artifact directory (default: temp)")
    sk.set_defaults(fn=cmd_soak)

    d = sub.add_parser("dump", help="debugger dump of a live sidecar")
    d.add_argument("--socket", required=True)
    d.add_argument(
        "--deadline", type=float, default=10.0,
        help="per-call deadline in seconds (a hung sidecar fails the "
        "probe in bounded time); <=0 waits forever",
    )
    d.set_defaults(fn=cmd_dump)

    mtr = sub.add_parser(
        "metrics", help="scrape a live sidecar (Prometheus text / events)"
    )
    mtr.add_argument("--socket", required=True)
    mtr.add_argument(
        "--deadline", type=float, default=10.0,
        help="per-call deadline in seconds; <=0 waits forever",
    )
    mtr.add_argument(
        "--events", action="store_true",
        help="print the event-recorder ring as JSON instead of metrics",
    )
    mtr.set_defaults(fn=cmd_metrics)

    fl = sub.add_parser(
        "flight",
        help="read a live sidecar's flight recorder (phase attribution)",
    )
    fl.add_argument("--socket", required=True)
    fl.add_argument(
        "--limit", type=int, default=0,
        help="newest N records only (0 = the whole ring)",
    )
    fl.add_argument(
        "--deadline", type=float, default=10.0,
        help="per-call deadline in seconds; <=0 waits forever",
    )
    fl.set_defaults(fn=cmd_flight)

    tr = sub.add_parser(
        "trace",
        help="export a live sidecar's flight ring as Perfetto/Chrome "
        "trace-event JSON",
    )
    tr.add_argument("--socket", required=True)
    tr.add_argument(
        "--limit", type=int, default=0,
        help="newest N records only (0 = the whole ring)",
    )
    tr.add_argument(
        "--timebase", default="logical", choices=("logical", "wall"),
        help="logical = the deterministic timeline (wall fields "
        "stripped, byte-stable across same-seed runs); wall = honest "
        "wall-clock attribution",
    )
    tr.add_argument(
        "--out", default="", help="write here instead of stdout"
    )
    tr.add_argument(
        "--deadline", type=float, default=10.0,
        help="per-call deadline in seconds; <=0 waits forever",
    )
    tr.set_defaults(fn=cmd_trace)

    ex = sub.add_parser(
        "explain",
        help="explain one pod's scheduling decision: per-op attribution "
        "columns + the selectHost tie-break trace",
    )
    ex.add_argument("--socket", required=True)
    ex.add_argument("uid", help="pod uid (namespace/name)")
    ex.add_argument(
        "--seq", type=int, default=0,
        help="pin the journal reconstruction point to just before this "
        "seq (0 = let the recorded capsule choose)",
    )
    ex.add_argument(
        "--deadline", type=float, default=10.0,
        help="per-call deadline in seconds; <=0 waits forever",
    )
    ex.set_defaults(fn=cmd_explain)

    ms = sub.add_parser(
        "measured",
        help="derive a measured throughput-matrix artifact from flight "
        "dumps or a live sidecar",
    )
    ms.add_argument(
        "dumps", nargs="*",
        help="flight dump / merge_fleet JSON files to fold",
    )
    ms.add_argument("--socket", default="", help="also fold a live ring")
    ms.add_argument(
        "--lc-lo", type=float, default=None,
        help="logical window lower bound (inclusive)",
    )
    ms.add_argument(
        "--lc-hi", type=float, default=None,
        help="logical window upper bound (exclusive)",
    )
    ms.add_argument(
        "--out", default="",
        help="write the artifact here (e.g. measured_matrix.json) "
        "instead of stdout",
    )
    ms.add_argument(
        "--deadline", type=float, default=10.0,
        help="per-call deadline in seconds; <=0 waits forever",
    )
    ms.set_defaults(fn=cmd_measured)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
