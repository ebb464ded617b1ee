#!/usr/bin/env python
"""Fault-matrix sweep: every wire fault × every frame kind, against the
golden-transcript scenario, asserting BINDING DECISIONS ARE UNCHANGED —
plus (``--kill``) the CRASH matrix: SIGKILL the host at every journal
injection point and assert recovery lands bit-identical bindings.

The claim under test is the north star's robustness clause: the two-tier
host↔sidecar split must produce bit-identical binding decisions whether
the wire is healthy or failing — a transient hang/crash/slow response is
absorbed by the host's deadline+retry+resync machinery (sidecar/host.py
ResyncingClient), never by changing a placement.

Each case drives the golden ``basic_session`` scenario
(gen_golden_transcripts.scenario_objects: 4 nodes, bound pods, a
preemptor, an unschedulable pod) through a ResyncingClient whose socket
is wrapped by a seeded FaultPlan, and compares the full binding map —
including the preemption nomination and victim set — against a
fault-free baseline run.  Faults fire on the Nth frame of the targeted
kind, so the matrix probes every phase of the session: snapshot adds,
the scheduling batch, the delete that triggers requeue, the final drain.

The fast subset (one fault of each kind on the schedule frame) runs in
tier-1 via tests/test_faults.py::test_fault_matrix_fast; this script
sweeps the whole grid:

    JAX_PLATFORMS=cpu python scripts/run_fault_matrix.py

The CRASH matrix (PR 3's host-kill analog of the wire grid) drives the
same scenario in a CHILD process with the write-ahead journal armed and
``TPU_JOURNAL_KILL=point:nth`` SIGKILLing it at one journal crash point
(kubernetes_tpu/faults.py KillSwitch); the parent then runs a fresh
recovery child — snapshot + fenced journal replay + LIST reconcile
(informers.reconcile_after_recovery) + an idempotent re-run of the
scenario tail — and asserts the final binding map is bit-identical to an
uninterrupted run.  Host truth (the apiserver stand-in) is a durable
tombstone file written ahead of every delete, mirroring the reference's
ordering: the victim's API DELETE commits in etcd BEFORE the scheduler's
local state moves.

    JAX_PLATFORMS=cpu python scripts/run_fault_matrix.py --kill

Subsets: ``--fleet-kill`` (shard failover), ``--node-loss`` /
``--fleet-node-loss`` (the failure-response loop), ``--autoscale-kill``
(SIGKILL inside an autoscaler-initiated live resize — ISSUE 11),
``--pack-kill`` (packed chunks + carried DomTables — ISSUE 13),
``--pipeline-kill`` (SIGKILL inside the pipelined commit drain's
group-commit windows — ISSUE 15); all ride ``--kill``.  ``--only CELL``
narrows any matrix to labels containing the substring, and every cell
line prints its wall time.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# CPU-only, on purpose: a correctness tool that SIGKILLs and multiplies
# processes (up to N owners + standbys alive at once), and one chip
# belongs to one process.  Every process it starts inherits this.
os.environ["JAX_PLATFORMS"] = "cpu"

FAULT_KINDS = ("hang", "crash", "partial_write", "slow")
FRAME_KINDS = ("add", "remove", "schedule")

# The crash grid: every journal injection point, probed both early (the
# first commit of the session) and late (after state has accumulated —
# snapshots have run, the log has truncated).  torn-append leaves half a
# record's bytes on disk; mid-snapshot a torn checkpoint temp;
# mid-truncate a replaced snapshot with the log still full.
KILL_CASES = (
    ("pre-append", 1), ("pre-append", 3),
    ("post-append", 1), ("post-append", 2),
    ("torn-append", 1), ("torn-append", 2),
    ("pre-snapshot", 1), ("pre-snapshot", 2),
    ("mid-snapshot", 1), ("mid-snapshot", 2),
    ("mid-truncate", 1), ("mid-truncate", 2),
    ("post-truncate", 1), ("post-truncate", 2),
)

# The FLEET crash subset (shard failover): the golden scenario driven by
# a 2-shard partitioned fleet (kubernetes_tpu/fleet) — every owner
# journaled under its own lease epoch, a mid-scenario journaled handoff
# (node reassignment between shards) in the script — with the process
# SIGKILLed at journal injection points, pre-map-write included (the
# handoff's append→map-rewrite window).  Recovery is a TAKEOVER: fresh
# owners re-acquire each shard's lease (epoch bump fences the deposed
# writer), replay snapshot + fenced WAL, redo any journaled handoff the
# map file never saw, re-feed host truth idempotently, and re-run the
# scenario tail.  Final fleet bindings must be bit-identical to an
# unkilled fleet run, with a readable recovery flight dump per killed
# cell.
FLEET_KILL_CASES = (
    ("post-append", 1),
    ("post-append", 4),
    ("torn-append", 1),
    ("pre-append", 3),
    ("mid-snapshot", 1),
    ("pre-map-write", 1),
)

# The NODE-LOSS subset (ISSUE 9): the full failure-response production
# sequence — a node stops heartbeating mid-scenario, the node-lifecycle
# controller detects staleness on the logical Lease clock and WRITES the
# NotReady→Unreachable taints (journaled), tolerationSeconds graces are
# honored, the taint-eviction controller evicts, evicted pods requeue and
# the final drain reschedules them bit-identically onto surviving nodes —
# with the process SIGKILLed at journal points along the way, INCLUDING
# between the taint-write and the eviction (post-append on the taint
# record), and each killed cell leaving a readable flight dump + the
# scheduler_node_lifecycle_* / scheduler_pod_gc_* metric families in its
# metrics snapshot.  Append order in the scenario (snapshot-every-batch
# truncations interleave): bind×2 (the pending pods), taint(not-ready),
# evict(v1), taint(unreachable), evict(v2), evict(sticky — the pod-GC
# horizon), then the rebinds.
NODE_LOSS_CASES = (
    ("post-append", 3),   # right AFTER the not-ready taint write — the
                          # taint-write→eviction window the ISSUE names
    ("pre-append", 4),    # before the first eviction's record
    ("torn-append", 4),   # the first eviction's record torn mid-write
    ("post-append", 5),   # after the unreachable taint write
    ("pre-append", 6),    # before the second eviction
    ("post-append", 7),   # after the pod-GC eviction, before its rebind
    ("mid-snapshot", 2),  # checkpoint torn mid-incident
    ("post-truncate", 1),
)

# The WIRE crash subset (the ROADMAP layer-0 gap): the same scenario
# deployed as two processes — a journaled sidecar serving the framed
# socket and a journaled ResyncingClient host driving it — with HOST and
# SIDECAR SIGKILLed independently at journal injection points.  The
# killed side restarts (host: cold-start journal replay + store resync;
# sidecar: snapshot + fenced replay before its first frame, then the
# host's reconnect replay), the scenario tail re-runs idempotently, and
# the final binding map must be bit-identical to an unkilled wire run.
# Each killed cell must also leave a READABLE flight dump (the recovery
# auto-dump) in the cell's state dir.  Points are chosen past the first
# durable record, so a restart always has something to recover.
WIRE_KILL_CASES = (
    ("host", "post-append", 1),
    ("host", "torn-append", 3),
    ("host", "mid-snapshot", 1),
    ("sidecar", "post-append", 1),
    ("sidecar", "torn-append", 1),
    ("sidecar", "pre-append", 2),
)

# The AUTOSCALE crash subset (ISSUE 11): a 2-shard fleet with its load
# deliberately skewed (hot pods carry a selector only shard-0 nodes
# satisfy), the elastic autoscaler trips a SPLIT of the hot shard into a
# fresh journaled owner, and the process is SIGKILLed at the named
# points INSIDE that autoscaler-initiated handoff — the record durable
# but nothing imported (post-handoff-append), imports journaled but the
# map rewrite lost (pre-map-write), map durable but the source's drop
# interrupted (mid-drop), the handoff record torn mid-write, an imported
# binding's re-journal durable but unapplied, and a checkpoint torn
# mid-resize.  Recovery is a takeover over every shard directory on
# disk: lost map writes redo from the acquirer's journal, the map
# enforcement sweep finishes interrupted drops, the router adopts, the
# autoscaler re-primes its window FROM THE ADOPTED BINDINGS and
# re-decides — a split that never became durable re-fires identically
# (same hot shard, same new id), one that did reads as balanced and the
# tick is a no-op.  Final bindings AND the final map must be
# bit-identical to an unkilled run.  Nths map to the scenario's
# recorded append sequence (each commit = gang_reserve intent + bind):
# appends 1–20 = the ten pre-resize commits, 21 = the handoff record
# (torn-append@21 tears it), 22–26 = the imported bindings' re-journals
# on the acquiring owner, 27–30 = the post-resize commits;
# mid-snapshot@11 is the checkpoint torn right after the first
# post-resize commit.
AUTOSCALE_KILL_CASES = (
    ("post-handoff-append", 1),
    ("pre-map-write", 1),
    ("mid-drop", 1),
    ("torn-append", 21),
    ("post-append", 22),
    ("post-append", 28),
    ("mid-snapshot", 11),
)

# Per-call deadline for the sweep: small enough that a hang case costs
# ~deadline per retry, large enough that a CPU-backend device pass (with
# its XLA compile on first touch) never trips it spuriously.
DEADLINE_S = 30.0

# --only CELL (substring match on the printed labels) narrows any matrix
# to the named cells — the triage loop's re-run-one-cell surface.
ONLY: str | None = None


def _selected(label: str) -> bool:
    return ONLY is None or ONLY in label


def _cell_t0() -> float:
    import time as _time

    return _time.perf_counter()


def _cell_dt(t0: float) -> str:
    """Per-cell wall-time suffix for the verbose lines — triage needs to
    know WHICH cell eats the sweep's minutes."""
    import time as _time

    return f" ({_time.perf_counter() - t0:.1f}s)"


def _drive(plan=None):
    """Run the golden basic-session scenario through a ResyncingClient
    (wrapped by ``plan`` when given) and return the binding decisions:
    {pod uid: (node, nominated_node, sorted victim uids)}."""
    from gen_golden_transcripts import (
        scenario_objects,
        session_schedulers,
        wait_for_backoffs,
    )

    from kubernetes_tpu.sidecar.host import ResyncingClient
    from kubernetes_tpu.sidecar.server import SidecarServer

    nodes, bound, pending = scenario_objects()
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "sidecar.sock")
        srv = SidecarServer(
            path, scheduler=session_schedulers()["basic_session"]()
        )
        srv.serve_background()
        client = ResyncingClient(
            path,
            max_reconnect_s=5.0,
            retry_interval_s=0.02,
            deadline_s=DEADLINE_S,
            socket_wrapper=plan.wrap if plan is not None else None,
        )
        try:
            decisions = {}
            for n in nodes:
                client.add("Node", n)
            for p in bound:
                client.add("Pod", p)
            for r in client.schedule(pods=pending, drain=True):
                decisions[r.pod_uid] = (
                    r.node_name, r.nominated_node, tuple(sorted(r.victim_uids))
                )
            client.remove("Pod", "default/bound-2")
            wait_for_backoffs(srv.scheduler.queue)
            for r in client.schedule(pods=[], drain=True):
                decisions[r.pod_uid] = (
                    r.node_name, r.nominated_node, tuple(sorted(r.victim_uids))
                )
            return decisions
        finally:
            client.close()
            srv.close()


def matrix_cases(fault_kinds=FAULT_KINDS, frame_kinds=FRAME_KINDS, nth=1):
    """(label, FaultPlan) for each fault × frame-kind cell."""
    from kubernetes_tpu.faults import FaultPlan

    out = []
    for fk in fault_kinds:
        for op in frame_kinds:
            plan = FaultPlan(seed=7).add_rule(
                fk, op=op, nth=nth, delay_s=0.05
            )
            out.append((f"{fk}×{op}@{nth}", plan))
    return out


def run_matrix(cases=None, verbose=True) -> list[str]:
    """Run the given (label, plan) cases; returns the labels that
    DIVERGED from the fault-free baseline (empty == all held)."""
    baseline = _drive()
    assert baseline, "baseline produced no decisions"
    failures = []
    for label, plan in cases if cases is not None else matrix_cases():
        if not _selected(label):
            continue
        t0 = _cell_t0()
        got = _drive(plan)
        fired = list(plan.fired)
        if got != baseline:
            failures.append(label)
            if verbose:
                diff = {
                    k: (baseline.get(k), got.get(k))
                    for k in set(baseline) | set(got)
                    if baseline.get(k) != got.get(k)
                }
                print(f"FAIL {label}: fired={fired} diff={diff}{_cell_dt(t0)}")
        elif verbose:
            status = "ok  " if fired else "ok (fault never matched)"
            print(f"{status} {label}: fired={fired}{_cell_dt(t0)}")
    return failures


# -- the crash (host-kill) matrix ------------------------------------------


def _truth_deleted_path(state_dir: str) -> str:
    return os.path.join(state_dir, "truth.deleted")


def _truth_delete(state_dir: str, uid: str) -> None:
    """Durably tombstone a pod in host truth BEFORE the scheduler's local
    state changes — the apiserver-commit ordering the reference gets from
    prepareCandidate's API DELETE landing in etcd first."""
    with open(_truth_deleted_path(state_dir), "a") as f:
        f.write(uid + "\n")
        f.flush()
        os.fsync(f.fileno())


def _truth_deleted(state_dir: str) -> set:
    try:
        with open(_truth_deleted_path(state_dir)) as f:
            return {line.strip() for line in f if line.strip()}
    except OSError:
        return set()


def _truth_lease_path(state_dir: str) -> str:
    return os.path.join(state_dir, "truth.leases")


def _truth_lease(state_dir: str, name: str, ts: float) -> None:
    """Durably record a Lease renewal in host truth BEFORE the local
    apply — the apiserver holds the Lease object, so a successor's LIST
    sees every renewal the kubelet committed, including ones the dead
    owner never consumed.  Append-only like the other truth files (a
    torn final line is skipped by the reader)."""
    with open(_truth_lease_path(state_dir), "a") as f:
        f.write(f"{name} {ts}\n")
        f.flush()
        os.fsync(f.fileno())


def _truth_leases(state_dir: str) -> dict:
    """Host truth's CURRENT Lease per node: the max recorded renewal —
    what a LIST of coordination.k8s.io Leases returns."""
    out: dict[str, float] = {}
    try:
        with open(_truth_lease_path(state_dir)) as f:
            for line in f:
                parts = line.split()
                if len(parts) != 2:
                    continue  # torn tail line
                try:
                    ts = float(parts[1])
                except ValueError:
                    continue
                if ts > out.get(parts[0], -1.0):
                    out[parts[0]] = ts
    except OSError:
        pass
    return out


def _record_lease_truth(sched, state_dir: str) -> None:
    """Interpose renew_node_lease to commit host truth first (the
    victim's side of the Lease-relist takeover contract)."""
    orig = sched.renew_node_lease

    def renew(lease, _orig=orig):
        _truth_lease(state_dir, lease.node_name, lease.renew_time)
        _orig(lease)

    sched.renew_node_lease = renew


def _every_batch(sched) -> None:
    """The matrices probe the compaction windows, so their schedulers
    checkpoint at every batch boundary where the log grew: a cadence of one
    record (attach_journal states its cadence in full batches of records,
    which these short batches never fill)."""
    sched.snapshot_every_records = 1


def _attach_every_batch(sched, journal) -> None:
    sched.attach_journal(journal)
    _every_batch(sched)


def _journaled_scheduler(state_dir: str):
    """(scheduler, journal): the golden basic-session scheduler with the
    write-ahead journal armed under the journal lease's fencing epoch,
    and delete_pod interposed to tombstone host truth first."""
    from gen_golden_transcripts import session_schedulers

    from kubernetes_tpu.framework.leaderelection import FileLease, read_epoch
    from kubernetes_tpu.journal import Journal

    sched = session_schedulers()["basic_session"]()
    lease_path = os.path.join(state_dir, "lease")
    lease = FileLease(lease_path, identity=f"kill-{os.getpid()}")
    lease.acquire(block=True)
    journal = Journal(
        state_dir, epoch=lease.epoch, fence=lambda: read_epoch(lease_path)
    )
    orig_delete = sched.delete_pod

    def delete_pod(uid: str, notify: bool = True) -> None:
        _truth_delete(state_dir, uid)
        orig_delete(uid, notify)

    sched.delete_pod = delete_pod
    return sched, journal


def _run_scenario_tail(sched) -> dict:
    """The scenario's scheduling steps — idempotent, so the recovery
    child re-runs them verbatim: already-committed pods are answered
    from the cache, the delete of an already-deleted pod is a no-op."""
    from gen_golden_transcripts import wait_for_backoffs

    sched.schedule_all_pending(wait_backoff=True)
    sched.delete_pod("default/bound-2")
    wait_for_backoffs(sched.queue)
    sched.schedule_all_pending(wait_backoff=True)
    return {
        uid: pr.node_name
        for uid, pr in sched.cache.pods.items()
        if pr.bound
    }


def _audit_divergence(baseline_dir: str, state_dir: str, factory) -> None:
    """On a bit-identity FAIL, localize the first divergent decision —
    walk both cells' journals to the first disagreeing bind, reconstruct
    each side's store as of that decision, and print the (pod, op, node)
    cell instead of leaving a bare final-map diff.  Best-effort: the
    audit must never mask the FAIL it annotates."""
    try:
        import explain_diff

        report = explain_diff.explain_divergence(
            baseline_dir, state_dir, factory
        )
        for line in explain_diff.render(report).splitlines():
            print(f"     {line}")
    except Exception as exc:
        print(f"     explain_diff audit unavailable: {type(exc).__name__}: {exc}")


def _basic_session_factory():
    from gen_golden_transcripts import session_schedulers

    return session_schedulers()["basic_session"]()


def kill_child(state_dir: str) -> None:
    """The victim: run the scenario with journaling armed (snapshot every
    batch, so every injection point gets live windows).  When
    TPU_JOURNAL_KILL is set the process SIGKILLs itself mid-commit;
    otherwise it writes the final binding map."""
    from gen_golden_transcripts import scenario_objects

    from kubernetes_tpu.faults import KillSwitch

    sched, journal = _journaled_scheduler(state_dir)
    _attach_every_batch(sched, journal)
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    nodes, bound, pending = scenario_objects()
    for n in nodes:
        sched.add_node(n)
    for p in bound:
        sched.add_pod(p)
    for p in pending:
        sched.add_pod(p)
    bindings = _run_scenario_tail(sched)
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)


def recover_child(state_dir: str) -> None:
    """The successor: fresh scheduler, recover from snapshot + fenced
    journal replay, reconcile against the host-truth LIST (original
    objects minus durable tombstones), then re-run the scenario tail
    idempotently and write the final binding map."""
    import copy

    from gen_golden_transcripts import scenario_objects

    from kubernetes_tpu.informers import FakeSource, Reflector, reconcile_after_recovery
    from kubernetes_tpu.journal import recover

    sched, journal = _journaled_scheduler(state_dir)
    recover(sched, journal)
    _attach_every_batch(sched, journal)
    nodes, bound, pending = scenario_objects()
    deleted = _truth_deleted(state_dir)
    src_n, src_p = FakeSource(), FakeSource()
    for n in nodes:
        src_n.add(n.name, copy.deepcopy(n))
    for p in bound + pending:
        if p.uid not in deleted:
            src_p.add(p.uid, copy.deepcopy(p))
    reconcile_after_recovery(
        sched,
        Reflector(sched, "Node", src_n.lister, src_n.watcher),
        Reflector(sched, "Pod", src_p.lister, src_p.watcher),
    )
    bindings = _run_scenario_tail(sched)
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)


def _spawn(
    mode: str,
    state_dir: str,
    kill: str | None = None,
    extra_env: dict | None = None,
) -> int:
    env = dict(os.environ)
    env.pop("TPU_JOURNAL_KILL", None)
    env.pop("TPU_STANDBY_POOL", None)
    if kill:
        env["TPU_JOURNAL_KILL"] = kill
    if extra_env:
        env.update(extra_env)
    # Recovery flight dumps stay in the cell's state dir, not /tmp.
    env["TPU_FLIGHT_DIR"] = state_dir
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), mode, state_dir],
        env=env,
        capture_output=True,
        text=True,
    )
    if proc.returncode not in (0, -9):
        sys.stderr.write(proc.stdout + proc.stderr)
    return proc.returncode


def _read_bindings(state_dir: str) -> dict | None:
    try:
        with open(os.path.join(state_dir, "bindings.json")) as f:
            return json.load(f)
    except OSError:
        return None


def run_kill_matrix(cases=KILL_CASES, verbose=True) -> list[str]:
    """SIGKILL the scenario at each journal crash point, recover, and
    compare final bindings to an uninterrupted run.  Returns the labels
    that diverged (empty == crash matrix green)."""
    with tempfile.TemporaryDirectory() as td:
        base_dir = os.path.join(td, "baseline")
        os.makedirs(base_dir)
        rc = _spawn("--kill-child", base_dir)
        baseline = _read_bindings(base_dir)
        assert rc == 0 and baseline, "baseline kill-child run failed"
        failures = []
        for point, nth in cases:
            label = f"kill:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"{point}-{nth}")
            os.makedirs(state_dir)
            rc = _spawn("--kill-child", state_dir, kill=f"{point}:{nth}")
            if rc == 0:
                # The armed point's Nth hit never arrived (an honest
                # cell, like the wire grid's "fault never matched") —
                # but the run must still agree with the baseline.
                got = _read_bindings(state_dir)
                status = "ok (kill never fired)"
                if got != baseline:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn("--recover-child", state_dir)
            got = _read_bindings(state_dir)
            if rc != 0 or got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: rc={rc} diff={diff}{_cell_dt(t0)}")
                    _audit_divergence(
                        base_dir, state_dir, _basic_session_factory
                    )
            elif verbose:
                print(
                    f"ok   {label}: recovered bit-identical bindings"
                    f"{_cell_dt(t0)}"
                )
        return failures


# -- the PACK crash subset (ISSUE 13: packed chunks + carried DomTables) ----

# The conflict-aware packer's crash claim: the packed batch order and the
# carried DomTables are DERIVABLE state — a SIGKILL mid-batch (between a
# packed batch's journaled binds, with the carry warm) recovers from the
# journaled store alone, rebuilds the tables on device, and completes with
# bindings bit-identical to an uninterrupted packed run — which itself
# binds bit-identical to the chunk_size=1 sequential configuration on the
# same scenario (asserted once per sweep, ahead of the cells).
PACK_KILL_CASES = (
    ("post-append", 2),   # mid-batch: part of the batch's binds durable
    ("torn-append", 3),   # a bind record torn mid-write inside the batch
    ("mid-snapshot", 1),  # checkpoint torn while the carry is warm
    ("mid-truncate", 1),  # log truncation interrupted after a snapshot
)


def pack_scenario_objects():
    """Conflict-heavy scenario whose every score is UNIQUE and
    commit-invariant: the only scorer is NodeAffinity over per-pod
    rotated preferred-tier weights (state-independent, so the chunked
    mode's documented chunk-start resource-score drift cannot fire, and
    distinct weights leave no tie for the recovery child's resumed
    tie-break counter to flip), while the CLUSTERED anti-affinity colors
    make the packer actually reorder (the old duplicate-count halving
    would have collapsed the chunk)."""
    from kubernetes_tpu.api.wrappers import make_node, make_pod

    nodes = [
        make_node(f"pk{i}")
        .capacity({"cpu": "16", "memory": "16Gi", "pods": 32})
        .zone(f"z{i % 4}")
        .label("tier", f"t{i}")
        .obj()
        for i in range(12)
    ]
    pods = []
    for i in range(24):
        color = i // 4  # clustered: 6 colors × 4 pods (= zones: all bind)
        w = make_pod(f"pp{i:02d}").req({"cpu": "100m"}).label(
            "color", f"c{color}"
        ).pod_anti_affinity_in(
            "color", [f"c{color}"], "topology.kubernetes.io/zone"
        )
        for j in range(12):
            w = w.preferred_node_affinity_in(
                "tier", [f"t{j}"], weight=((j + 5 * i) % 12) + 1
            )
        pods.append(w.obj())
    return nodes, pods


def _pack_bare_scheduler(chunk: int):
    """The pack-kill scenario's scheduler configuration alone (no lease,
    no journal) — shared by the children and the explain_diff audit's
    reconstruction factory, so the two can never drift apart."""
    from kubernetes_tpu.framework.config import Profile
    from kubernetes_tpu.ops.common import registered_subset
    from kubernetes_tpu.scheduler import TPUScheduler

    return TPUScheduler(
        profile=registered_subset(
            Profile(
                name="pack-kill",
                filters=("NodeResourcesFit", "NodeAffinity", "InterPodAffinity"),
                scorers=(("NodeAffinity", 2),),
            )
        ),
        batch_size=8,
        chunk_size=chunk,
        enable_preemption=False,
    )


def _pack_scheduler(state_dir: str, chunk: int):
    from kubernetes_tpu.framework.leaderelection import FileLease, read_epoch
    from kubernetes_tpu.journal import Journal

    sched = _pack_bare_scheduler(chunk)
    lease_path = os.path.join(state_dir, "lease")
    lease = FileLease(lease_path, identity=f"packkill-{os.getpid()}")
    lease.acquire(block=True)
    journal = Journal(
        state_dir, epoch=lease.epoch, fence=lambda: read_epoch(lease_path)
    )
    return sched, journal


def _pack_child(state_dir: str, chunk: int) -> None:
    from kubernetes_tpu.faults import KillSwitch

    sched, journal = _pack_scheduler(state_dir, chunk)
    _attach_every_batch(sched, journal)
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    nodes, pods = pack_scenario_objects()
    for n in nodes:
        sched.add_node(n)
    for p in pods:
        sched.add_pod(p)
    sched.schedule_all_pending(wait_backoff=True)
    bindings = {
        uid: pr.node_name for uid, pr in sched.cache.pods.items() if pr.bound
    }
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)


def pack_kill_child(state_dir: str) -> None:
    _pack_child(state_dir, chunk=4)


def pack_seq_child(state_dir: str) -> None:
    """The chunk_size=1 parity configuration on the SAME scenario — the
    packed baseline must reproduce its bindings byte for byte."""
    _pack_child(state_dir, chunk=1)


def pack_recover_child(state_dir: str) -> None:
    import copy

    from kubernetes_tpu.informers import (
        FakeSource,
        Reflector,
        reconcile_after_recovery,
    )
    from kubernetes_tpu.journal import recover

    sched, journal = _pack_scheduler(state_dir, chunk=4)
    recover(sched, journal)
    # The carried DomTables are process state: recovery must start cold
    # and rebuild from the journaled store on the next dispatch.
    assert sched._dom_carry is None, "dom carry survived recovery"
    _attach_every_batch(sched, journal)
    nodes, pods = pack_scenario_objects()
    src_n, src_p = FakeSource(), FakeSource()
    for n in nodes:
        src_n.add(n.name, copy.deepcopy(n))
    for p in pods:
        src_p.add(p.uid, copy.deepcopy(p))
    reconcile_after_recovery(
        sched,
        Reflector(sched, "Node", src_n.lister, src_n.watcher),
        Reflector(sched, "Pod", src_p.lister, src_p.watcher),
    )
    sched.schedule_all_pending(wait_backoff=True)
    bindings = {
        uid: pr.node_name for uid, pr in sched.cache.pods.items() if pr.bound
    }
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)


def run_pack_kill_matrix(cases=PACK_KILL_CASES, verbose=True) -> list[str]:
    """SIGKILL the packed scenario at journal points mid-batch, recover,
    and compare final bindings to an uninterrupted packed run (itself
    asserted identical to the chunk=1 run).  Returns diverged labels."""
    with tempfile.TemporaryDirectory() as td:
        base_dir = os.path.join(td, "pack-baseline")
        os.makedirs(base_dir)
        rc = _spawn("--pack-kill-child", base_dir)
        baseline = _read_bindings(base_dir)
        assert rc == 0 and baseline, "pack baseline run failed"
        seq_dir = os.path.join(td, "pack-seq")
        os.makedirs(seq_dir)
        rc = _spawn("--pack-seq-child", seq_dir)
        seq = _read_bindings(seq_dir)
        assert rc == 0 and seq == baseline, (
            "packed run diverged from the chunk=1 parity configuration: "
            f"{ {k: (baseline.get(k), (seq or {}).get(k)) for k in set(baseline) | set(seq or {}) if baseline.get(k) != (seq or {}).get(k)} }"
        )
        if verbose:
            print("ok   packkill:baseline == chunk1 parity configuration")
        failures = []
        for point, nth in cases:
            label = f"packkill:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"pack-{point}-{nth}")
            os.makedirs(state_dir)
            rc = _spawn("--pack-kill-child", state_dir, kill=f"{point}:{nth}")
            if rc == 0:
                got = _read_bindings(state_dir)
                status = "ok (kill never fired)"
                if got != baseline:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn("--pack-recover-child", state_dir)
            got = _read_bindings(state_dir)
            if rc != 0 or got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: rc={rc} diff={diff}{_cell_dt(t0)}")
                    _audit_divergence(
                        base_dir, state_dir, lambda: _pack_bare_scheduler(4)
                    )
            elif verbose:
                print(
                    f"ok   {label}: recovery rebuilt DomTables, bindings "
                    f"bit-identical{_cell_dt(t0)}"
                )
        return failures


# -- the TENANT crash subset (ISSUE 17: weighted-fair admission) ------------

# The fairness ledger's crash claim: WFQ virtual-time tags, burst-credit
# balances, and pending-age stamps are journaled state — the commit drain
# journals each batch's ``admission`` debit record inside the group
# barrier before applying it to the durable ledger, and snapshots carry
# the ledger with its ABSOLUTE logical clock.  A SIGKILL mid-burst
# (credits exhausted, throttled tenants queued, aging escapes coming)
# must recover and complete with the ADMISSION ORDER and the bindings
# both bit-identical to an uninterrupted run — including the asymmetric
# cases where an admission record survives but its batch's binds do not
# (the pod re-admits WITHOUT a second debit, in durable order) and vice
# versa.  The scenario drives three weighted tenants (2:1:0.5) through a
# rate cap small enough that the initial burst credits exhaust mid-run
# and the tail drains on refills and aging escapes, on a stepwise
# logical clock the recovery child resumes at the recovered high-water
# mark.  Append order per batch: admission record first, then the
# batch's binds (snapshot-every-batch truncations interleave).
TENANT_KILL_CASES = (
    ("post-append", 2),   # admission durable, its batch's binds lost
    ("torn-append", 3),   # a bind of the first batch torn mid-write
    ("post-append", 7),   # mid-burst: a later batch's admission durable
    ("torn-append", 6),   # a later batch's admission record torn
    ("mid-snapshot", 1),  # ledger checkpoint torn while throttled
    ("mid-truncate", 2),  # truncation interrupted post-snapshot
)


def tenant_scenario_objects():
    """Three tenants with deliberately unequal pod counts on a cluster
    with room for all of them: the claim under test is ORDER, so every
    pod binds and the only degree of freedom is the admission sequence
    (NodeResourcesFit scoring makes placement order-sensitive)."""
    from kubernetes_tpu.api.wrappers import make_node, make_pod
    from kubernetes_tpu.framework.metrics import TENANT_LABEL_KEY

    nodes = [
        make_node(f"tn{i}")
        .capacity({"cpu": "8", "memory": "16Gi", "pods": 16})
        .zone(f"z{i % 2}")
        .obj()
        for i in range(4)
    ]
    pods = [
        make_pod(f"tp-{t}-{i:02d}").req({"cpu": "200m"}).label(
            TENANT_LABEL_KEY, t
        ).obj()
        for t, n in (("ten-a", 10), ("ten-b", 8), ("ten-c", 6))
        for i in range(n)
    ]
    return nodes, pods


def _tenant_scheduler(state_dir: str):
    from kubernetes_tpu.framework.config import Profile
    from kubernetes_tpu.framework.fairness import FairAdmission
    from kubernetes_tpu.framework.leaderelection import FileLease, read_epoch
    from kubernetes_tpu.journal import Journal
    from kubernetes_tpu.ops.common import registered_subset
    from kubernetes_tpu.scheduler import TPUScheduler

    sched = TPUScheduler(
        profile=registered_subset(
            Profile(
                name="tenant-kill",
                filters=("NodeResourcesFit",),
                scorers=(("NodeResourcesFit", 1),),
            )
        ),
        batch_size=4,
        enable_preemption=False,
    )
    # No injected clock: the policy runs on its note_time high-water
    # mark, which the snapshot carries absolutely and replayed debits
    # re-advance — the recovery child resumes the wave loop from it.
    sched.queue.arm_admission(
        FairAdmission(
            weights={"ten-a": 2.0, "ten-b": 1.0, "ten-c": 0.5},
            rate_pods_per_s=2.0,
            burst=3.0,
            aging_max_wait_s=3.0,
            slo_wait_budget_s=50.0,
        )
    )
    lease_path = os.path.join(state_dir, "lease")
    lease = FileLease(lease_path, identity=f"tenantkill-{os.getpid()}")
    lease.acquire(block=True)
    journal = Journal(
        state_dir, epoch=lease.epoch, fence=lambda: read_epoch(lease_path)
    )
    return sched, journal


def _tenant_drive(sched, t0: int = 0) -> None:
    """Stepwise logical waves: each wave advances the admission clock
    one logical second and drains everything admissible (the armed queue
    reports throttled when every tenant is credit-blocked — the wave
    loop, not polling, advances refills and aging).  The horizon is far
    past the 24 pods' drain point; both children run the same waves."""
    adm = sched.queue.admission
    for t in range(t0, 40):
        adm.note_time(float(t))
        sched.schedule_all_pending(wait_backoff=True)
        if not len(sched.queue) and not sched.has_inflight_work:
            break


def _tenant_write_result(sched, state_dir: str) -> None:
    bindings = {
        uid: pr.node_name
        for uid, pr in sched.cache.pods.items()
        if pr.bound
    }
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)
    with open(os.path.join(state_dir, "admission.json"), "w") as f:
        json.dump(list(sched.queue.admission.admitted_log), f)


def tenant_kill_child(state_dir: str) -> None:
    from kubernetes_tpu.faults import KillSwitch

    sched, journal = _tenant_scheduler(state_dir)
    _attach_every_batch(sched, journal)
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    nodes, pods = tenant_scenario_objects()
    for n in nodes:
        sched.add_node(n)
    for p in pods:
        sched.add_pod(p)
    _tenant_drive(sched)
    _tenant_write_result(sched, state_dir)


def tenant_recover_child(state_dir: str) -> None:
    import copy

    from kubernetes_tpu.informers import (
        FakeSource,
        Reflector,
        reconcile_after_recovery,
    )
    from kubernetes_tpu.journal import recover

    sched, journal = _tenant_scheduler(state_dir)
    recover(sched, journal)
    _attach_every_batch(sched, journal)
    nodes, pods = tenant_scenario_objects()
    src_n, src_p = FakeSource(), FakeSource()
    for n in nodes:
        src_n.add(n.name, copy.deepcopy(n))
    for p in pods:
        src_p.add(p.uid, copy.deepcopy(p))
    reconcile_after_recovery(
        sched,
        Reflector(sched, "Node", src_n.lister, src_n.watcher),
        Reflector(sched, "Pod", src_p.lister, src_p.watcher),
    )
    # The selectHost tie-break seed is the pod's global dispatch index
    # (scheduler._cycle at dispatch + batch offset) — not durable state.
    # In this retry-free scenario every admitted pod consumes exactly one
    # dispatch slot, so the recovered counter is the durably-bound count:
    # carried-over pods (admission durable, binds lost) re-dispatch at
    # precisely the slots they occupied in the uninterrupted run, because
    # the preadmitted drain preserves their admission order and batch
    # boundaries don't shift per-pod seeds.
    sched._cycle = sum(1 for pr in sched.cache.pods.values() if pr.bound)
    # Resume the wave loop AT the recovered clock high-water mark —
    # re-running the interrupted wave is idempotent: replayed admissions
    # are in the ledger (their unbound pods re-admit via the carry-over,
    # debit-free), and refills are min-clamped linear, so stepping the
    # same wave twice cannot over-refill.
    _tenant_drive(sched, t0=int(sched.queue.admission.now()))
    _tenant_write_result(sched, state_dir)


def _read_admission(state_dir: str) -> list | None:
    try:
        with open(os.path.join(state_dir, "admission.json")) as f:
            return json.load(f)
    except OSError:
        return None


def run_tenant_kill_matrix(
    cases=TENANT_KILL_CASES, verbose=True
) -> list[str]:
    """SIGKILL the weighted-fair admission scenario at journal points
    mid-burst, recover, and compare final bindings AND the durable
    admission order to an uninterrupted run.  Returns diverged labels."""
    with tempfile.TemporaryDirectory() as td:
        base_dir = os.path.join(td, "tenant-baseline")
        os.makedirs(base_dir)
        rc = _spawn("--tenant-kill-child", base_dir)
        baseline = _read_bindings(base_dir)
        base_order = _read_admission(base_dir)
        assert rc == 0 and baseline and base_order, (
            "tenant baseline run failed"
        )
        assert sorted(baseline) == sorted(base_order), (
            "tenant baseline did not drain: bindings and admission order "
            "cover different pods"
        )
        failures = []
        for point, nth in cases:
            label = f"tenantkill:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"tenant-{point}-{nth}")
            os.makedirs(state_dir)
            rc = _spawn(
                "--tenant-kill-child", state_dir, kill=f"{point}:{nth}"
            )
            if rc == 0:
                got = _read_bindings(state_dir)
                order = _read_admission(state_dir)
                status = "ok (kill never fired)"
                if got != baseline or order != base_order:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn("--tenant-recover-child", state_dir)
            got = _read_bindings(state_dir)
            order = _read_admission(state_dir)
            if rc != 0 or got != baseline or order != base_order:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    odiff = order != base_order
                    print(
                        f"FAIL {label}: rc={rc} diff={diff} "
                        f"order_diverged={odiff}{_cell_dt(t0)}"
                    )
            elif verbose:
                print(
                    f"ok   {label}: recovered bit-identical bindings + "
                    f"admission order{_cell_dt(t0)}"
                )
        return failures


# -- the PIPELINE crash subset (ISSUE 15: group commit + overlapped drain) --

# The pipelined commit drain's crash claim: a staged commit group is
# all-or-nothing-ACKNOWLEDGED — records go durable under ONE group fsync
# and no bind applies until the barrier returns, while a predispatched
# device pass for the NEXT batch is typically in flight over the drain.
# A SIGKILL anywhere inside the window (commit staged but nothing
# journaled; group written but the fsync not returned; fsync returned but
# nothing applied; the group's tail record torn mid-write) must recover
# to bindings bit-identical to an uninterrupted pipelined run — which
# itself binds bit-identical to the depth-1 serial configuration on the
# same scenario (asserted once per sweep, ahead of the cells).
PIPELINE_KILL_CASES = (
    ("stage-boundary", 1),    # staged, nothing journaled (first batch)
    ("stage-boundary", 3),    # same window, state accumulated
    ("mid-group-fsync", 1),   # group written, barrier not returned
    ("mid-group-fsync", 2),
    ("post-group-fsync", 1),  # durable, nothing applied
    ("torn-group-tail", 2),   # a group's tail record torn mid-write
)


def _pipeline_scheduler(state_dir: str, depth: int):
    """The pack-kill scenario's scheduler shape (unique, commit-invariant
    scores — see pack_scenario_objects) at pipeline depth ``depth``:
    batch 8 over 24 pods = 3+ batches, so predispatch + overlapped
    drains genuinely engage before the armed kill point fires.  Reuses
    _pack_scheduler so the two matrices can never drift apart on the
    profile shape the tie-free guarantee rests on."""
    sched, journal = _pack_scheduler(state_dir, chunk=4)
    sched.pipeline_depth = depth
    return sched, journal


def _pipeline_child(state_dir: str, depth: int) -> None:
    from kubernetes_tpu.faults import KillSwitch

    sched, journal = _pipeline_scheduler(state_dir, depth)
    sched.attach_journal(journal, snapshot_every_batches=2)
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    nodes, pods = pack_scenario_objects()
    for n in nodes:
        sched.add_node(n)
    for p in pods:
        sched.add_pod(p)
    sched.schedule_all_pending(wait_backoff=True)
    bindings = {
        uid: pr.node_name for uid, pr in sched.cache.pods.items() if pr.bound
    }
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)


def pipeline_kill_child(state_dir: str) -> None:
    _pipeline_child(state_dir, depth=2)


def pipeline_seq_child(state_dir: str) -> None:
    """The depth-1 serial parity configuration on the SAME scenario —
    the pipelined baseline must reproduce its bindings byte for byte."""
    _pipeline_child(state_dir, depth=1)


def pipeline_recover_child(state_dir: str) -> None:
    import copy

    from kubernetes_tpu.informers import (
        FakeSource,
        Reflector,
        reconcile_after_recovery,
    )
    from kubernetes_tpu.journal import recover

    from kubernetes_tpu.api import serialize

    sched, journal = _pipeline_scheduler(state_dir, depth=2)
    # The durable truth BEFORE replay mutates anything: bind uids in the
    # snapshot plus post-barrier records (replay() is a read-only scan;
    # this scenario journals no deletes, so the set only grows).
    snap, records, _ = journal.replay()
    durable = {
        serialize.pod_from_data(p["pod"]).uid
        for p in (snap or {"state": {}})["state"].get("pods", ())
    }
    durable.update(r["d"]["uid"] for r in records if r["t"] == "bind")
    recover(sched, journal)
    # A staged-but-unbarriered group must never have applied: every
    # binding recovery produced — applied to the cache or parked for the
    # LIST reconcile — must trace to a durable record.  (The final
    # bindings comparison proves completeness; this pins the DIRECTION:
    # nothing live ahead of its group's fsync.)
    applied = {
        uid for uid, pr in sched.cache.pods.items() if pr.bound
    } | set(sched._recovered_bindings)
    assert applied <= durable, (
        f"bindings with no durable record: {sorted(applied - durable)}"
    )
    sched.attach_journal(journal, snapshot_every_batches=2)
    nodes, pods = pack_scenario_objects()
    src_n, src_p = FakeSource(), FakeSource()
    for n in nodes:
        src_n.add(n.name, copy.deepcopy(n))
    for p in pods:
        src_p.add(p.uid, copy.deepcopy(p))
    reconcile_after_recovery(
        sched,
        Reflector(sched, "Node", src_n.lister, src_n.watcher),
        Reflector(sched, "Pod", src_p.lister, src_p.watcher),
    )
    sched.schedule_all_pending(wait_backoff=True)
    bindings = {
        uid: pr.node_name for uid, pr in sched.cache.pods.items() if pr.bound
    }
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)


def run_pipeline_kill_matrix(
    cases=PIPELINE_KILL_CASES, verbose=True
) -> list[str]:
    """SIGKILL the pipelined scenario inside the group-commit drain
    windows, recover, and compare final bindings to an uninterrupted
    pipelined run (itself asserted identical to the depth-1 serial
    configuration).  Returns diverged labels."""
    with tempfile.TemporaryDirectory() as td:
        base_dir = os.path.join(td, "pipe-baseline")
        os.makedirs(base_dir)
        rc = _spawn("--pipeline-kill-child", base_dir)
        baseline = _read_bindings(base_dir)
        assert rc == 0 and baseline, "pipeline baseline run failed"
        seq_dir = os.path.join(td, "pipe-seq")
        os.makedirs(seq_dir)
        rc = _spawn("--pipeline-seq-child", seq_dir)
        seq = _read_bindings(seq_dir)
        assert rc == 0 and seq == baseline, (
            "pipelined run diverged from the depth-1 parity configuration: "
            f"{ {k: (baseline.get(k), (seq or {}).get(k)) for k in set(baseline) | set(seq or {}) if baseline.get(k) != (seq or {}).get(k)} }"
        )
        if verbose:
            print("ok   pipekill:baseline == depth-1 parity configuration")
        failures = []
        for point, nth in cases:
            label = f"pipekill:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"pipe-{point}-{nth}")
            os.makedirs(state_dir)
            rc = _spawn(
                "--pipeline-kill-child", state_dir, kill=f"{point}:{nth}"
            )
            if rc == 0:
                got = _read_bindings(state_dir)
                status = "ok (kill never fired)"
                if got != baseline:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn("--pipeline-recover-child", state_dir)
            got = _read_bindings(state_dir)
            if rc != 0 or got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: rc={rc} diff={diff}{_cell_dt(t0)}")
                    _audit_divergence(
                        base_dir, state_dir, lambda: _pack_bare_scheduler(4)
                    )
            elif verbose:
                print(
                    f"ok   {label}: group-commit window recovered, "
                    f"bindings bit-identical{_cell_dt(t0)}"
                )
        return failures


# -- the FLEET crash matrix (shard failover via takeover) ------------------


def _takeover_factory(state_dir: str, base_factory):
    """Per-shard scheduler factories for the RECOVERY path.  Unarmed
    (TPU_STANDBY_POOL unset/0) every shard gets the cold ``base_factory``
    — the pre-ISSUE-18 takeover, untouched.  Armed, takeover owners draw
    their schedulers from a warm-standby pool (fleet/standby.py) with the
    cold factory as the miss fallback.  The pool only changes WHO serves
    the recovered shard; recover_shard's journal replay decides WHAT it
    owns — so armed and unarmed recoveries must land byte-identical
    bindings (the standbykill:fleet cell asserts exactly that)."""
    n = int(os.environ.get("TPU_STANDBY_POOL", "0") or 0)
    if n <= 0:
        return lambda k: base_factory
    from kubernetes_tpu.fleet.standby import StandbyPool

    pool = StandbyPool(
        os.path.join(state_dir, "standby-takeover"),
        lambda sid: {"sched": base_factory()},
        size=n,
    )

    def for_shard(k):
        def factory():
            payload = pool.promote(k, "takeover")
            return payload["sched"] if payload else base_factory()

        return factory

    return for_shard


def _fleet_build(state_dir: str, recover: bool = False):
    """(router, owners, map_path): a 2-shard journaled fleet running the
    golden basic-session configuration, every owner's delete_pod
    tombstoning host truth first (the same apiserver-commit ordering the
    single-process matrix models).  ``recover`` builds the owners through
    takeover.recover_shard — lease re-acquire, snapshot+WAL replay, lost
    map writes redone, map enforced on recovered state."""
    from gen_golden_transcripts import session_schedulers

    from kubernetes_tpu.fleet import FleetRouter, ShardMap, ShardOwner
    from kubernetes_tpu.fleet.takeover import recover_shard

    map_path = os.path.join(state_dir, "shardmap.json")
    if os.path.exists(map_path):
        smap = ShardMap.load(map_path)
    else:
        smap = ShardMap(n_shards=2, n_buckets=16)
        smap.save(map_path)
    factory = session_schedulers()["basic_session"]
    take = _takeover_factory(state_dir, factory) if recover else None
    owners = {}
    for k in range(2):
        sdir = os.path.join(state_dir, f"shard{k}")
        os.makedirs(sdir, exist_ok=True)
        if recover:
            owner = recover_shard(sdir, take(k), k, smap, map_path=map_path)
        else:
            owner = ShardOwner(k, factory(), smap, state_dir=sdir)
            _every_batch(owner.sched)
        orig_delete = owner.sched.delete_pod

        def delete_pod(uid: str, notify: bool = True, _orig=orig_delete):
            _truth_delete(state_dir, uid)
            _orig(uid, notify)

        owner.sched.delete_pod = delete_pod
        owners[k] = owner
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    return router, owners, map_path


def _fleet_initial_owner_of(name: str) -> int:
    from kubernetes_tpu.fleet import ShardMap

    return ShardMap(n_shards=2, n_buckets=16).owner_of(name)


def _fleet_tail(router, map_path: str, state_dir: str) -> dict:
    """The fleet scenario tail — idempotent, like _run_scenario_tail: a
    takeover re-runs it verbatim (committed pods are skipped by the
    router's adopted bindings, the handoff re-applies only if its map
    assignment never landed)."""
    from gen_golden_transcripts import wait_for_backoffs

    router.schedule_all_pending(wait_backoff=True)
    # Mid-scenario journaled handoff: node-1 (and its bound pod) moves to
    # the other shard — the pre-map-write window under test.
    init = _fleet_initial_owner_of("node-1")
    if router.shard_map.owner_of("node-1") == init:
        rec = router.shard_map.assign("node-1", 1 - init)
        router.apply_handoff(rec, map_path)
    if "default/bound-2" in router._pod_shard:
        router.remove_object("Pod", "default/bound-2")
    wait_for_backoffs(router.queue)
    router.schedule_all_pending(wait_backoff=True)
    bindings = router.bindings()
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)
    return bindings


def fleet_kill_child(state_dir: str) -> None:
    """The victim: drive the golden scenario through a 2-shard journaled
    fleet (snapshot every batch).  TPU_JOURNAL_KILL SIGKILLs the process
    at the armed point — whichever owner's journal (or the shard map
    write) hits it first, exactly where a power cut would land."""
    from gen_golden_transcripts import scenario_objects

    from kubernetes_tpu.faults import KillSwitch

    router, owners, map_path = _fleet_build(state_dir)
    # Armed AFTER construction: the map-init save is setup, not the
    # handoff window pre-map-write probes — and killing before anything
    # durable exists would leave a cell with nothing to recover.
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    nodes, bound, pending = scenario_objects()
    for n in nodes:
        router.add_object("Node", n)
    for p in bound:
        router.add_object("Pod", p)
    for p in pending:
        router.add_pod(p)
    _fleet_tail(router, map_path, state_dir)
    for owner in owners.values():
        owner.close()


def fleet_recover_child(state_dir: str) -> None:
    """The takeover: fresh owners recover each shard behind an epoch
    bump, the router adopts the recovered bindings, host truth re-feeds
    idempotently (tombstoned pods stay deleted), and the scenario tail
    re-runs."""
    from gen_golden_transcripts import scenario_objects

    router, owners, map_path = _fleet_build(state_dir, recover=True)
    deleted = _truth_deleted(state_dir)
    nodes, bound, pending = scenario_objects()
    for n in nodes:
        router.add_object("Node", n)
    # Parked journal bindings re-apply now that the nodes relisted, THEN
    # the router adopts the complete recovered truth — pods bound
    # pre-crash are skipped by the idempotent re-feed below.
    router.reconcile_recovered()
    router.adopt_bindings()
    for p in bound:
        if p.uid not in deleted:
            router.add_object("Pod", p)
    for p in pending:
        if p.uid not in deleted:
            router.add_pod(p)
    _fleet_tail(router, map_path, state_dir)
    for owner in owners.values():
        owner.close()


def run_fleet_kill_matrix(cases=FLEET_KILL_CASES, verbose=True) -> list[str]:
    """SIGKILL the 2-shard fleet at each journal/handoff crash point,
    take the shards over, and compare final fleet bindings to an
    unkilled fleet run (plus a readable recovery flight dump per killed
    cell).  Returns diverged labels."""
    with tempfile.TemporaryDirectory() as td:
        base_dir = os.path.join(td, "fleet-baseline")
        os.makedirs(base_dir)
        rc = _spawn("--fleet-kill-child", base_dir)
        baseline = _read_bindings(base_dir)
        assert rc == 0 and baseline, "fleet baseline run failed"
        failures = []
        for point, nth in cases:
            label = f"fleetkill:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"fleet-{point}-{nth}")
            os.makedirs(state_dir)
            rc = _spawn("--fleet-kill-child", state_dir, kill=f"{point}:{nth}")
            if rc == 0:
                got = _read_bindings(state_dir)
                status = "ok (kill never fired)"
                if got != baseline:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn("--fleet-recover-child", state_dir)
            got = _read_bindings(state_dir)
            if rc != 0 or got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: rc={rc} diff={diff}{_cell_dt(t0)}")
                continue
            if not _flight_dump_ok(state_dir):
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: no readable recovery flight dump")
                continue
            if verbose:
                print(
                    f"ok   {label}: takeover recovered bit-identical "
                    f"bindings{_cell_dt(t0)}"
                )
        return failures


# -- the STANDBY kill matrix (ISSUE 18) ------------------------------------
#
# The warm-standby pool's crash story splits in two.  FLEET-STATE
# correctness across a SIGKILL anywhere in a promotion is the EXISTING
# takeover/redo machinery's job — the pool's only own obligation is to
# NEVER OFFER A SLOT TWICE (claim file + pool-WAL replay), which
# _standby_pool_invariant checks in every recovery.  The RESUMABLE SOAK
# DRIVER's crash story is the checkpoint writer's: a kill inside the
# write window (digest journaled, os.replace unapplied) must leave the
# last durable generation as the resume anchor, and a --resume'd run
# must finish bit-identical to an uninterrupted same-seed twin.

STANDBY_KILL_CASES = (
    # The promotion window (fleet/standby.py promote): killed before the
    # O_EXCL claim, after claim + pool-WAL append but before the
    # finish_promotion apply, and right after the apply.
    ("promo", "standby-pre-claim", 1),
    ("promo", "standby-mid-promotion", 1),
    ("promo", "standby-post-promote", 1),
    # The promoted owner's handoff window: killed after the handoff
    # record's append, and between the append and the shard-map rewrite
    # (the "router killed between lease claim and map write" cell).
    ("promo", "post-handoff-append", 1),
    ("promo", "pre-map-write", 1),
    # The soak driver SIGKILLed inside its SECOND checkpoint's write
    # window — mid-checkpoint: generation record journaled, os.replace
    # never applied; --resume must anchor on generation 1.
    ("ckpt", "mid-checkpoint", 2),
    # Satellite-2 byte-identity: the ordinary shard-failover cell with
    # TPU_STANDBY_POOL=2 armed in the RECOVERY child — takeover owners
    # drawn warm from a pool instead of cold factories, same bindings.
    ("fleet", "post-append", 3),
)

# The resumable-driver cell's soak shape: small, virtual-paced, with the
# standby pool armed AND a scripted owner kill in the replayed prefix —
# the resume leg re-executes a pool promotion during replay, composing
# both halves of the ISSUE in one cell.
STANDBY_CKPT_CFG = dict(
    seed=11, nodes=32, zones=4, churn_nodes=4, rate_pods_per_s=24.0,
    duration_s=6.0, knee_points=(), invalidation_rate_per_s=0.15,
    node_flap_period_s=0.0, pace="virtual", batch_size=64, chunk_size=16,
    warm_pods=24, live_pod_cap=300, standby_pool=1,
    checkpoint_every_ops=30, scripted_events=((2.5, "owner_kill", 1),),
)


def _standby_pool_records(state_dir: str) -> list[dict]:
    from kubernetes_tpu.fleet.standby import JOURNAL_NAME, _PoolJournal

    return _PoolJournal.replay(
        os.path.join(state_dir, "standby", JOURNAL_NAME)
    )


def _standby_pool_invariant(state_dir: str) -> None:
    """The pool's no-double-offer contract: at most ONE promote record
    per slot id, and every promote record sits behind its O_EXCL claim
    file (the append is only reachable through a won claim)."""
    per_slot: dict[int, int] = {}
    for rec in _standby_pool_records(state_dir):
        if rec.get("op") == "promote":
            sid = int(rec["slot"])
            per_slot[sid] = per_slot.get(sid, 0) + 1
            claim = os.path.join(state_dir, "standby", f"slot-{sid}.claim")
            assert os.path.exists(claim), (
                f"promote record for slot {sid} without a claim file"
            )
    doubled = {s: n for s, n in sorted(per_slot.items()) if n > 1}
    assert not doubled, f"slots offered twice: {doubled}"


def standby_promo_child(state_dir: str) -> None:
    """The victim: a cold 2-shard fleet feeds the golden scenario's
    nodes + bound pods (durable per-shard appends), then shard-1's owner
    DIES and its replacement comes from a warm-standby POOL promotion
    (claim → pool-WAL append → finish_promotion) — a journaled TAKEOVER
    over the dead owner's journal dir, not a cold boot — after which the
    rebuilt router runs the scenario tail.  TPU_JOURNAL_KILL SIGKILLs
    inside the promotion window or inside the promoted fleet's
    handoff."""
    from gen_golden_transcripts import scenario_objects, session_schedulers

    from kubernetes_tpu.faults import KillSwitch
    from kubernetes_tpu.fleet import FleetRouter, ShardMap, ShardOwner
    from kubernetes_tpu.fleet.standby import StandbyPool

    map_path = os.path.join(state_dir, "shardmap.json")
    smap = ShardMap(n_shards=2, n_buckets=16)
    smap.save(map_path)
    factory = session_schedulers()["basic_session"]
    pool = StandbyPool(
        os.path.join(state_dir, "standby"),
        lambda sid: {"sched": factory()},
        size=1,
    )

    def wrap_delete(owner):
        orig_delete = owner.sched.delete_pod

        def delete_pod(uid, notify=True, _orig=orig_delete):
            _truth_delete(state_dir, uid)
            _orig(uid, notify)

        owner.sched.delete_pod = delete_pod
        return owner

    owners = {}
    for k in range(2):
        sdir = os.path.join(state_dir, f"shard{k}")
        os.makedirs(sdir, exist_ok=True)
        owners[k] = wrap_delete(ShardOwner(k, factory(), smap, state_dir=sdir))
        _every_batch(owners[k].sched)
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    nodes, bound, pending = scenario_objects()
    for n in nodes:
        router.add_object("Node", n)
    for p in bound:
        router.add_object("Pod", p)
    for p in pending:
        router.add_pod(p)
    # First batch SCHEDULES before the incident: every kill cell's
    # takeover then has durable journaled binds to recover (and a
    # recovery flight dump to leave as evidence) — an owner dying over
    # an empty journal would be a cold start, not an incident.
    router.schedule_all_pending(wait_backoff=True)
    # Shard-1's owner dies mid-incident (close releases the flock the
    # way a SIGKILL's process exit would).  Armed HERE: the points under
    # test are the REPLACEMENT's promotion window and the promoted
    # fleet's handoff — never the cold build or the initial map save.
    owners[1].close()
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    payload = pool.promote(1, "takeover")
    sched1 = payload["sched"] if payload else factory()
    owners[1] = wrap_delete(
        ShardOwner(
            1, sched1, smap, state_dir=os.path.join(state_dir, "shard1")
        )
    )
    _every_batch(owners[1].sched)
    # Rebuild the router over the recovered truth (the revive_owner
    # idiom): nodes relist, parked journal bindings re-apply, the router
    # adopts, bound pods re-feed idempotently, then the tail runs.
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    deleted = _truth_deleted(state_dir)
    for n in nodes:
        router.add_object("Node", n)
    router.reconcile_recovered()
    router.adopt_bindings()
    for p in bound:
        if p.uid not in deleted:
            router.add_object("Pod", p)
    for p in pending:
        if p.uid not in deleted:
            router.add_pod(p)
    _fleet_tail(router, map_path, state_dir)
    for owner in owners.values():
        owner.close()
    pool.close()


def standby_promo_recover_child(state_dir: str) -> None:
    """The takeover: verify the pool never double-offered, reopen it
    (WAL replay marks consumed slots — a claim file without its promote
    record is a promotion that died between claim and append,
    conservatively consumed), then recover BOTH shards through
    recover_shard with takeover owners drawn from the pool, re-run the
    tail, and re-verify the invariant (recovery's own promotions land
    on fresh slot ids)."""
    from gen_golden_transcripts import scenario_objects, session_schedulers

    from kubernetes_tpu.fleet import FleetRouter, ShardMap
    from kubernetes_tpu.fleet.standby import StandbyPool
    from kubernetes_tpu.fleet.takeover import recover_shard

    _standby_pool_invariant(state_dir)
    map_path = os.path.join(state_dir, "shardmap.json")
    smap = ShardMap.load(map_path)
    factory = session_schedulers()["basic_session"]
    pool = StandbyPool(
        os.path.join(state_dir, "standby"),
        lambda sid: {"sched": factory()},
        size=2,
    )
    owners = {}
    for k in range(2):
        sdir = os.path.join(state_dir, f"shard{k}")
        os.makedirs(sdir, exist_ok=True)

        def take(k=k):
            payload = pool.promote(k, "takeover")
            return payload["sched"] if payload else factory()

        owner = recover_shard(sdir, take, k, smap, map_path=map_path)
        orig_delete = owner.sched.delete_pod

        def delete_pod(uid, notify=True, _orig=orig_delete):
            _truth_delete(state_dir, uid)
            _orig(uid, notify)

        owner.sched.delete_pod = delete_pod
        owners[k] = owner
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    deleted = _truth_deleted(state_dir)
    nodes, bound, pending = scenario_objects()
    for n in nodes:
        router.add_object("Node", n)
    router.reconcile_recovered()
    router.adopt_bindings()
    for p in bound:
        if p.uid not in deleted:
            router.add_object("Pod", p)
    for p in pending:
        if p.uid not in deleted:
            router.add_pod(p)
    _fleet_tail(router, map_path, state_dir)
    _standby_pool_invariant(state_dir)
    with open(os.path.join(state_dir, "standby-recovery.json"), "w") as f:
        json.dump(pool.status(), f, sort_keys=True)
    for owner in owners.values():
        owner.close()
    pool.close()


def standby_ckpt_child(state_dir: str) -> None:
    """The victim: a small armed fleet soak (standby pool + scripted
    owner kill + checkpoint every 30 ops) with the kill switch armed —
    mid-checkpoint:2 SIGKILLs inside the second checkpoint's write
    window, after its generation record's journal append but before the
    os.replace apply."""
    from kubernetes_tpu.faults import KillSwitch
    from kubernetes_tpu.loadgen.soak import SoakConfig, run_fleet_soak

    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    cfg = SoakConfig(
        out_dir=os.path.join(state_dir, "out"),
        journal_dir=os.path.join(state_dir, "journal"),
        checkpoint_path=os.path.join(state_dir, "soak.ckpt"),
        **STANDBY_CKPT_CFG,
    )
    art = run_fleet_soak(cfg, shards=2)
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(art["determinism"], f, sort_keys=True)


def standby_ckpt_recover_child(state_dir: str) -> None:
    """--resume: anchor on the last DURABLE checkpoint generation,
    replay the op prefix in virtual pace against fresh journal dirs,
    verify the regenerated state digest, finish the run — the
    determinism block (bindings, timeline, driver-state digests) must be
    bit-identical to an uninterrupted same-seed twin's."""
    from kubernetes_tpu.loadgen.soak import SoakConfig, run_fleet_soak

    cfg = SoakConfig(
        out_dir=os.path.join(state_dir, "out-resume"),
        journal_dir=os.path.join(state_dir, "journal"),
        checkpoint_path=os.path.join(state_dir, "soak.ckpt"),
        resume=True,
        **STANDBY_CKPT_CFG,
    )
    art = run_fleet_soak(cfg, shards=2)
    assert art["resume"]["resumed"] and art["resume"]["digest_verified"], (
        art["resume"]
    )
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(art["determinism"], f, sort_keys=True)


def run_standby_kill_matrix(cases=STANDBY_KILL_CASES, verbose=True) -> list[str]:
    """SIGKILL inside the standby promotion window, the promoted fleet's
    handoff, and the soak driver's checkpoint write; recover (pool
    reopen + takeover, or --resume) and compare against unkilled
    baselines.  Also proves satellite-2 byte-identity: the pool-backed
    promo baseline equals the cold fleet baseline, and a pool-armed
    fleet recovery equals the unarmed one.  Returns diverged labels."""
    with tempfile.TemporaryDirectory() as td:
        failures = []
        promo_base = os.path.join(td, "standby-promo-baseline")
        os.makedirs(promo_base)
        rc = _spawn("--standby-promo-child", promo_base)
        promo_baseline = _read_bindings(promo_base)
        assert rc == 0 and promo_baseline, "standby promo baseline failed"
        fleet_base = os.path.join(td, "fleet-baseline")
        os.makedirs(fleet_base)
        rc = _spawn("--fleet-kill-child", fleet_base)
        fleet_baseline = _read_bindings(fleet_base)
        assert rc == 0 and fleet_baseline, "fleet baseline failed"
        if promo_baseline != fleet_baseline:
            # The pool must change WHO serves shard 1, never WHAT the
            # fleet binds.
            failures.append("standbykill:promo-baseline-parity")
            if verbose:
                print(
                    "FAIL standbykill: pool-promoted fleet baseline "
                    "diverged from the cold fleet baseline"
                )
        ckpt_base = os.path.join(td, "standby-ckpt-baseline")
        os.makedirs(ckpt_base)
        rc = _spawn("--standby-ckpt-child", ckpt_base)
        ckpt_baseline = _read_bindings(ckpt_base)
        assert rc == 0 and ckpt_baseline, "standby ckpt baseline failed"
        for family, point, nth in cases:
            label = f"standbykill:{family}:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"standby-{family}-{point}-{nth}")
            os.makedirs(state_dir)
            if family == "promo":
                child, recover, baseline, extra = (
                    "--standby-promo-child",
                    "--standby-promo-recover-child",
                    promo_baseline,
                    None,
                )
            elif family == "ckpt":
                child, recover, baseline, extra = (
                    "--standby-ckpt-child",
                    "--standby-ckpt-recover-child",
                    ckpt_baseline,
                    None,
                )
            else:  # the satellite-2 fleet cell: pool-armed RECOVERY
                child, recover, baseline, extra = (
                    "--fleet-kill-child",
                    "--fleet-recover-child",
                    fleet_baseline,
                    {"TPU_STANDBY_POOL": "2"},
                )
            rc = _spawn(child, state_dir, kill=f"{point}:{nth}")
            if rc == 0:
                got = _read_bindings(state_dir)
                status = "ok (kill never fired)"
                if got != baseline:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn(recover, state_dir, extra_env=extra)
            got = _read_bindings(state_dir)
            if rc != 0 or got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: rc={rc} diff={diff}{_cell_dt(t0)}")
                continue
            if family != "ckpt" and not _flight_dump_ok(state_dir):
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: no readable recovery flight dump")
                continue
            if verbose:
                print(
                    f"ok   {label}: recovered bit-identical"
                    f"{_cell_dt(t0)}"
                )
        return failures


# -- the NODE-LOSS matrix (the failure-response loop under SIGKILL) --------


def _truth_evicted_path(state_dir: str) -> str:
    return os.path.join(state_dir, "truth.evicted")


def _truth_evict(state_dir: str, uid: str) -> None:
    """Durably record an eviction in host truth BEFORE local state moves —
    the apiserver-side effect (pod deleted + controller recreates it
    unbound) lands in etcd first, exactly like the delete tombstones."""
    with open(_truth_evicted_path(state_dir), "a") as f:
        f.write(uid + "\n")
        f.flush()
        os.fsync(f.fileno())


def _truth_evicted(state_dir: str) -> set:
    try:
        with open(_truth_evicted_path(state_dir)) as f:
            return {line.strip() for line in f if line.strip()}
    except OSError:
        return set()


def _node_loss_scheduler(state_dir: str):
    """A journaled scheduler with the failure-response loop ARMED (grace
    5s / unreachable 12s / GC horizon 20s on the logical Lease clock) and
    TaintToleration in the filter set (a requeued eviction victim must
    not rebind to the cordoned dead node).  delete_pod AND evict_pod
    tombstone host truth first."""
    from kubernetes_tpu.framework.config import Profile
    from kubernetes_tpu.framework.leaderelection import FileLease, read_epoch
    from kubernetes_tpu.journal import Journal
    from kubernetes_tpu.scheduler import TPUScheduler

    sched = TPUScheduler(
        profile=Profile(
            name="node-loss",
            filters=(
                "NodeUnschedulable", "NodeName", "TaintToleration",
                "NodeResourcesFit",
            ),
            scorers=(("NodeResourcesFit", 1), ("TaintToleration", 3)),
        ),
        batch_size=8,
        chunk_size=1,
    )
    sched.node_lifecycle.arm(grace_period_s=5.0, unreachable_after_s=12.0)
    sched.pod_gc.arm(gc_horizon_s=20.0)
    lease_path = os.path.join(state_dir, "lease")
    lease = FileLease(lease_path, identity=f"nodeloss-{os.getpid()}")
    lease.acquire(block=True)
    journal = Journal(
        state_dir, epoch=lease.epoch, fence=lambda: read_epoch(lease_path)
    )
    orig_delete = sched.delete_pod
    orig_evict = sched.evict_pod

    def delete_pod(uid: str, notify: bool = True) -> None:
        _truth_delete(state_dir, uid)
        orig_delete(uid, notify)

    def evict_pod(uid: str, reason: str = "eviction", pod=None) -> bool:
        _truth_evict(state_dir, uid)
        return orig_evict(uid, reason=reason, pod=pod)

    sched.delete_pod = delete_pod
    sched.evict_pod = evict_pod
    return sched, journal


def node_loss_objects():
    """The node-death scenario: 4 nodes (nd1 is the doomed one), three
    pods riding nd1 with distinct grace shapes — v1 (4s tolerationSeconds,
    evicted in the NotReady window), v2 (8s, re-armed by the
    NotReady→Unreachable taint swap, evicted later), sticky (tolerates
    every NoExecute forever; only the pod-GC horizon reclaims it) — a
    filler bound elsewhere, and two pending pods."""
    from kubernetes_tpu.api import types as t
    from kubernetes_tpu.api.wrappers import make_node, make_pod

    from kubernetes_tpu.controllers import (
        NOT_READY_TAINT_KEY,
        UNREACHABLE_TAINT_KEY,
    )

    nodes = [
        make_node("nd1").capacity({"cpu": "8", "memory": "16Gi", "pods": 110})
        .zone("z0").obj(),
        make_node("n2").capacity({"cpu": "6", "memory": "12Gi", "pods": 110})
        .zone("z0").obj(),
        make_node("n3").capacity({"cpu": "8", "memory": "16Gi", "pods": 110})
        .zone("z1").obj(),
        make_node("n4").capacity({"cpu": "4", "memory": "8Gi", "pods": 110})
        .zone("z1").obj(),
    ]

    def graced(w, seconds):
        return (
            w.toleration(NOT_READY_TAINT_KEY, op=t.TOLERATION_OP_EXISTS,
                         effect=t.EFFECT_NO_EXECUTE, seconds=seconds)
            .toleration(UNREACHABLE_TAINT_KEY, op=t.TOLERATION_OP_EXISTS,
                        effect=t.EFFECT_NO_EXECUTE, seconds=seconds)
        )

    bound = [
        graced(make_pod("v1").req({"cpu": "1", "memory": "1Gi"}), 4)
        .node("nd1").obj(),
        graced(make_pod("v2").req({"cpu": "2", "memory": "2Gi"}), 8)
        .node("nd1").obj(),
        make_pod("sticky").req({"cpu": "1", "memory": "1Gi"})
        .toleration("", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE)
        .node("nd1").obj(),
        make_pod("filler").req({"cpu": "2", "memory": "2Gi"}).node("n2").obj(),
    ]
    pending = [
        make_pod("p1").req({"cpu": "1", "memory": "1Gi"}).obj(),
        make_pod("p2").req({"cpu": "1", "memory": "1Gi"}).obj(),
    ]
    return nodes, bound, pending


# Survivor Lease schedule: every 2 logical seconds to t=40 — carries the
# scenario past NotReady (>5), Unreachable (>12), v2's re-armed grace
# (14+8) and the GC horizon (14+20).
NODE_LOSS_LEASE_TS = tuple(float(ts) for ts in range(2, 41, 2))


def _node_loss_tail(sched, state_dir: str, lease_floor: dict | None = None) -> dict:
    """The scenario tail.  A recovery child passes ``lease_floor`` — the
    per-node stamps its Lease RELIST restored (takeover rung: heartbeat
    state comes from listing host truth's Lease objects, NOT from
    re-deriving it out of a re-fed schedule) — and feeds only the
    renewals newer than the floor; transitions are a pure function of
    the logical clock, so the run converges to the uninterrupted
    timeline either way."""
    from kubernetes_tpu.api import types as t

    fl = lease_floor or {}
    sched.schedule_all_pending(wait_backoff=True)
    for name in ("nd1", "n2", "n3", "n4"):
        if 0.0 > fl.get(name, -1.0):
            sched.renew_node_lease(t.Lease(name, 0.0))
    for ts in NODE_LOSS_LEASE_TS:
        for name in ("n2", "n3", "n4"):  # nd1 went silent after t=0
            if ts > fl.get(name, -1.0):
                sched.renew_node_lease(t.Lease(name, ts))
    sched.schedule_all_pending(wait_backoff=True)
    bindings = {
        uid: pr.node_name
        for uid, pr in sched.cache.pods.items()
        if pr.bound
    }
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)
    with open(os.path.join(state_dir, "metrics.json"), "w") as f:
        json.dump(
            {
                "registry": sched.metrics.registry.summary(),
                "node_lifecycle": sched.node_lifecycle.stats(),
                "pod_gc": sched.pod_gc.stats(),
                "taint_evictions": sched.taint_eviction.evictions,
            },
            f,
            sort_keys=True,
            default=str,
        )
    return bindings


def node_loss_child(state_dir: str) -> None:
    """The victim: run the node-death scenario with journaling armed;
    TPU_JOURNAL_KILL lands the SIGKILL at the armed journal point —
    post-append on the taint record being the taint-write→eviction
    window the acceptance bar names."""
    from kubernetes_tpu.faults import KillSwitch

    sched, journal = _node_loss_scheduler(state_dir)
    _attach_every_batch(sched, journal)
    _record_lease_truth(sched, state_dir)
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    nodes, bound, pending = node_loss_objects()
    for n in nodes:
        sched.add_node(n)
    for p in bound:
        sched.add_pod(p)
    for p in pending:
        sched.add_pod(p)
    _node_loss_tail(sched, state_dir)


def node_loss_recover_child(state_dir: str) -> None:
    """The successor: recover from snapshot + fenced replay (taint and
    evict records re-apply), reconcile against host truth — the dead
    node relists in its ORIGINAL untainted shape and the Reflector's
    recovered-taints overlay re-applies the journal-authored lifecycle
    taints; evicted pods relist UNBOUND (their durable eviction
    tombstones are the apiserver's recreate); the Lease RELIST (the
    ROADMAP takeover rung) restores pre-crash heartbeat state from host
    truth's CURRENT Lease objects, and only the post-crash slice of the
    schedule re-feeds — transitions are a pure function of the logical
    clock, so the history converges on the uninterrupted timeline."""
    import copy

    from kubernetes_tpu.api import types as t
    from kubernetes_tpu.informers import (
        FakeSource,
        Reflector,
        reconcile_after_recovery,
    )
    from kubernetes_tpu.journal import recover

    sched, journal = _node_loss_scheduler(state_dir)
    recover(sched, journal)
    _attach_every_batch(sched, journal)
    nodes, bound, pending = node_loss_objects()
    deleted = _truth_deleted(state_dir)
    evicted = _truth_evicted(state_dir)
    lease_truth = _truth_leases(state_dir)
    src_n, src_p, src_l = FakeSource(), FakeSource(), FakeSource()
    for n in nodes:
        src_n.add(n.name, copy.deepcopy(n))
    for p in bound + pending:
        if p.uid in deleted:
            continue
        obj = copy.deepcopy(p)
        if obj.uid in evicted:
            obj.spec.node_name = ""  # host truth: recreated unbound
        src_p.add(obj.uid, obj)
    for name in sorted(lease_truth):
        src_l.add(name, t.Lease(name, lease_truth[name]))
    reconcile_after_recovery(
        sched,
        Reflector(sched, "Node", src_n.lister, src_n.watcher),
        Reflector(sched, "Pod", src_p.lister, src_p.watcher),
        lease_reflector=Reflector(
            sched, "Lease", src_l.lister, src_l.watcher
        ),
    )
    _node_loss_tail(sched, state_dir, lease_floor=lease_truth)


def _node_loss_cell_evidence(state_dir: str) -> list[str]:
    """What a killed cell must leave behind: a readable recovery flight
    dump AND a metrics snapshot carrying the scheduler_node_lifecycle_* /
    scheduler_pod_gc_* families with real counts.  Returns the missing
    pieces (empty == complete)."""
    missing = []
    if not _flight_dump_ok(state_dir):
        missing.append("flight-dump")
    try:
        with open(os.path.join(state_dir, "metrics.json")) as f:
            doc = json.load(f)
        blob = json.dumps(doc)
        for fam in (
            "scheduler_node_lifecycle_transitions_total",
            "scheduler_node_lifecycle_state",
            "scheduler_pod_gc_total",
            "scheduler_taint_evictions_total",
        ):
            if fam not in blob:
                missing.append(f"metrics:{fam}")
        if doc.get("node_lifecycle", {}).get("transitions", 0) < 1:
            missing.append("metrics:no-transitions")
        if doc.get("taint_evictions", 0) < 1:
            missing.append("metrics:no-evictions")
        if doc.get("pod_gc", {}).get("collected", {}).get("unreachable", 0) < 1:
            missing.append("metrics:no-gc")
    except (OSError, ValueError):
        missing.append("metrics.json")
    return missing


def run_node_loss_matrix(cases=NODE_LOSS_CASES, verbose=True) -> list[str]:
    """SIGKILL the node-death scenario at each journal point (taint
    writes and evictions included), recover, and require (a) final
    bindings bit-identical to the uninterrupted run — the evicted pods
    REBOUND on surviving nodes, not merely deleted — and (b) a readable
    flight dump + lifecycle/GC metrics per killed cell."""
    with tempfile.TemporaryDirectory() as td:
        base_dir = os.path.join(td, "node-loss-baseline")
        os.makedirs(base_dir)
        rc = _spawn("--node-loss-child", base_dir)
        baseline = _read_bindings(base_dir)
        assert rc == 0 and baseline, "node-loss baseline run failed"
        # The baseline itself must show the loop closed: every nd1 pod
        # rebound elsewhere.
        for uid in ("default/v1", "default/v2", "default/sticky"):
            assert baseline.get(uid) not in (None, "", "nd1"), (
                f"baseline did not reschedule {uid}: {baseline}"
            )
        failures = []
        for point, nth in cases:
            label = f"nodeloss:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"nl-{point}-{nth}")
            os.makedirs(state_dir)
            rc = _spawn("--node-loss-child", state_dir, kill=f"{point}:{nth}")
            if rc == 0:
                got = _read_bindings(state_dir)
                status = "ok (kill never fired)"
                if got != baseline:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn("--node-loss-recover-child", state_dir)
            got = _read_bindings(state_dir)
            if rc != 0 or got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: rc={rc} diff={diff}")
                continue
            missing = _node_loss_cell_evidence(state_dir)
            if missing:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: missing evidence {missing}")
                continue
            if verbose:
                print(
                    f"ok   {label}: taint→grace→evict→requeue→rebind "
                    "recovered bit-identical, flight dump + metrics "
                    f"present{_cell_dt(t0)}"
                )
        return failures


# -- the FLEET node-loss matrix (the failure-response loop, fleet-native) --

# ISSUE 10: the node-death production sequence driven through the
# PARTITIONED fleet — Lease frames route to the owning shard, the owner's
# lifecycle controller journals the taints, its evictions ride fleet
# responses back to the router and rebind CROSS-SHARD — with the process
# SIGKILLed at journal points along the way, including inside the
# taint-write→eviction window (post-append on shard 0's taint record) and
# inside a mid-incident handoff's append→map-rewrite window
# (pre-map-write while nd1 is NotReady and eviction deadlines are armed).
# Recovery is a TAKEOVER: fresh armed owners replay snapshot + fenced WAL
# (replay-surfaced evictions park in the recovered bucket), the router
# adopts bindings, drains the pending requeues, host truth re-feeds
# idempotently, and the full lease schedule re-runs (renewals are
# monotone).  Final fleet bindings must be bit-identical to an unkilled
# fleet run — which itself must be bit-identical to the ARMED single
# scheduler on the same profile (the node-loss oracle).  Cell nths map
# to the baseline's recorded append sequence (both shards' journals +
# map writes interleave; the kill switch counts per point per process):
# appends 1–4 = p1/p2 commits (shard 1), 5 = the NotReady taint
# (shard 0, clock 6), 6–8 = the mid-incident handoff record + the two
# re-journaled imported binds (shard 0, clock 8), then the handoff's
# map rewrite (pre-map-write@1 — the init save precedes arming),
# 9 = v1's evict (clock 10), 10 = the Unreachable taint (clock 14),
# 11 = v2's evict (clock 22), 12 = sticky's GC evict (clock 34),
# 13–18 = the three rebind commits.
FLEET_NODE_LOSS_CASES = (
    ("post-append", 5),   # right AFTER the not-ready taint record — the
                          # taint-write→eviction window the ISSUE names
    ("torn-append", 6),   # the mid-incident handoff record torn
    ("pre-map-write", 1), # handoff journaled, map rewrite lost — while
                          # nd1 is NotReady and deadlines are armed
    ("pre-append", 9),    # before the first eviction's record
    ("torn-append", 9),   # the first eviction's record torn mid-write
    ("post-append", 10),  # after the unreachable taint write
    ("pre-append", 11),   # before the second eviction
    ("post-append", 12),  # after the GC eviction, before its rebind
    ("mid-snapshot", 3),  # checkpoint torn right after the first rebind
    ("post-truncate", 2),
)

# The dead node lives in shard 0; n3 starts in shard 1 and hands off to
# shard 0 mid-incident, so the transfer window overlaps the outage.
FLEET_NODE_LOSS_PINS = {"nd1": 0, "n2": 0, "n3": 1, "n4": 1}
FLEET_LIFECYCLE = {
    "node_grace_s": 5.0,
    "node_unreachable_s": 12.0,
    "gc_horizon_s": 20.0,
}


def _fleet_node_loss_sched():
    """The PARTITION-EXACT node-loss profile: TaintToleration stays a
    filter (a requeued victim must not rebind to the cordoned dead node)
    but is NOT a scorer — it normalizes over the candidate set, and
    per-shard normalization forks from the global one whenever a tainted
    node exists in some shards and not others (the documented Tesserae
    compromise in fleet/router.py).  Filters and per-node additive
    scores are shard-independent, so this profile holds the
    fleet-vs-single oracle bit for bit."""
    from kubernetes_tpu.framework.config import Profile
    from kubernetes_tpu.scheduler import TPUScheduler

    return TPUScheduler(
        profile=Profile(
            name="fleet-node-loss",
            filters=(
                "NodeUnschedulable", "NodeName", "TaintToleration",
                "NodeResourcesFit",
            ),
            scorers=(("NodeResourcesFit", 1),),
        ),
        batch_size=8,
        chunk_size=1,
    )


def _fleet_node_loss_build(state_dir: str, recover: bool = False):
    """(router, owners, map_path): a 2-shard journaled fleet with the
    failure-response loop ARMED PER OWNER, every owner's delete_pod AND
    evict_pod tombstoning host truth first."""
    from kubernetes_tpu.fleet import FleetRouter, ShardMap, ShardOwner
    from kubernetes_tpu.fleet.takeover import recover_shard

    map_path = os.path.join(state_dir, "shardmap.json")
    if os.path.exists(map_path):
        smap = ShardMap.load(map_path)
    else:
        smap = ShardMap(
            n_shards=2, n_buckets=16,
            overrides=dict(FLEET_NODE_LOSS_PINS),
        )
        smap.save(map_path)
    take = (
        _takeover_factory(state_dir, _fleet_node_loss_sched)
        if recover
        else None
    )
    owners = {}
    for k in range(2):
        sdir = os.path.join(state_dir, f"shard{k}")
        os.makedirs(sdir, exist_ok=True)
        if recover:
            owner = recover_shard(
                sdir, take(k), k, smap,
                map_path=map_path, lifecycle=FLEET_LIFECYCLE,
            )
        else:
            owner = ShardOwner(
                k, _fleet_node_loss_sched(), smap, state_dir=sdir,
                lifecycle=FLEET_LIFECYCLE,
            )
            _every_batch(owner.sched)
        orig_delete = owner.sched.delete_pod
        orig_evict = owner.sched.evict_pod

        def delete_pod(uid, notify=True, _orig=orig_delete):
            _truth_delete(state_dir, uid)
            _orig(uid, notify)

        def evict_pod(uid, reason="eviction", pod=None, _orig=orig_evict):
            _truth_evict(state_dir, uid)
            return _orig(uid, reason=reason, pod=pod)

        owner.sched.delete_pod = delete_pod
        owner.sched.evict_pod = evict_pod
        owners[k] = owner
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    return router, owners, map_path


def _fleet_node_loss_tail(
    router, owners, map_path: str, state_dir: str,
    initial_schedule: bool = True,
    lease_floor: dict | None = None,
):
    """The fleet node-death scenario tail — idempotent like the single
    one: Lease renewals are monotone, the handoff re-applies only if its
    map assignment never landed, committed pods are skipped by adopted
    routing.  A RECOVERY run passes ``initial_schedule=False``: pods the
    host truth re-fed unbound (tombstone-evicted mid-incident) must not
    schedule against un-re-derived state — the dead node relists
    untainted, and binding anything before the lease re-run re-cordons
    it would hand out placements the unkilled run never offered.
    ``lease_floor`` (recovery only) is the per-node stamp set the Lease
    relist already restored — only newer renewals re-feed (the takeover
    rung: relist, don't re-derive); the VICTIM run (floor None) records
    every renewal into host truth before applying it."""
    from gen_golden_transcripts import wait_for_backoffs

    from kubernetes_tpu.api import types as t

    record = lease_floor is None
    fl = lease_floor or {}

    def renew(name: str, ts: float) -> None:
        if record:
            _truth_lease(state_dir, name, ts)
        if ts > fl.get(name, -1.0):
            router.add_object("Lease", t.Lease(name, ts))

    if initial_schedule:
        router.schedule_all_pending(wait_backoff=True)
    for name in ("nd1", "n2", "n3", "n4"):
        renew(name, 0.0)
    for ts in NODE_LOSS_LEASE_TS:
        if ts == 8.0 and router.shard_map.owner_of("n3") == 1:
            # Mid-INCIDENT handoff: nd1 went NotReady at clock 6 and its
            # eviction deadlines are armed while n3 (and its bound pods)
            # transfers shard 1 → shard 0 through the journaled path —
            # the pre-map-write window overlapping the outage.
            rec = router.shard_map.assign("n3", 0)
            router.apply_handoff(rec, map_path)
        for name in ("n2", "n3", "n4"):  # nd1 went silent after t=0
            renew(name, ts)
    wait_for_backoffs(router.queue)
    router.schedule_all_pending(wait_backoff=True)
    bindings = router.bindings()
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)
    with open(os.path.join(state_dir, "metrics.json"), "w") as f:
        json.dump(
            {
                "router": {
                    "registry": router.registry.summary(),
                    "lifecycle": router.lifecycle_stats(),
                },
                "owners": {
                    str(k): {
                        "registry": o.sched.metrics.registry.summary(),
                        "stats": o.stats(),
                    }
                    for k, o in sorted(owners.items())
                },
            },
            f,
            sort_keys=True,
            default=str,
        )
    return bindings


def fleet_node_loss_child(state_dir: str) -> None:
    """The victim: the node-death scenario through a 2-shard armed
    journaled fleet; TPU_JOURNAL_KILL SIGKILLs at the armed point —
    whichever owner's journal (or the mid-incident map write) hits it."""
    from kubernetes_tpu.faults import KillSwitch

    router, owners, map_path = _fleet_node_loss_build(state_dir)
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    nodes, bound, pending = node_loss_objects()
    for n in nodes:
        router.add_object("Node", n)
    for p in bound:
        router.add_object("Pod", p)
    for p in pending:
        router.add_pod(p)
    _fleet_node_loss_tail(router, owners, map_path, state_dir)
    for owner in owners.values():
        owner.close()


def fleet_node_loss_single_child(state_dir: str) -> None:
    """The ORACLE half: the same scenario and lease schedule through ONE
    armed scheduler on the same partition-exact profile — the fleet
    baseline must reproduce these bindings bit for bit."""
    from kubernetes_tpu.api import types as t

    from gen_golden_transcripts import wait_for_backoffs

    sched = _fleet_node_loss_sched()
    sched.node_lifecycle.arm(
        grace_period_s=FLEET_LIFECYCLE["node_grace_s"],
        unreachable_after_s=FLEET_LIFECYCLE["node_unreachable_s"],
    )
    sched.pod_gc.arm(gc_horizon_s=FLEET_LIFECYCLE["gc_horizon_s"])
    nodes, bound, pending = node_loss_objects()
    for n in nodes:
        sched.add_node(n)
    for p in bound + pending:
        sched.add_pod(p)
    sched.schedule_all_pending(wait_backoff=True)
    for name in ("nd1", "n2", "n3", "n4"):
        sched.renew_node_lease(t.Lease(name, 0.0))
    for ts in NODE_LOSS_LEASE_TS:
        for name in ("n2", "n3", "n4"):
            sched.renew_node_lease(t.Lease(name, ts))
    wait_for_backoffs(sched.queue)
    sched.schedule_all_pending(wait_backoff=True)
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(
            {
                uid: pr.node_name
                for uid, pr in sched.cache.pods.items()
                if pr.bound
            },
            f,
            sort_keys=True,
        )


def fleet_node_loss_recover_child(state_dir: str) -> None:
    """The takeover: fresh ARMED owners recover each shard (lost map
    writes redone, replay-surfaced evictions parked in the recovered
    bucket), the router adopts bindings then drains the pending
    requeues, host truth re-feeds idempotently (the owner-side
    recovered-taints overlay keeps journal-authored lifecycle taints
    across the untainted relist; evicted pods relist unbound), the Lease
    RELIST restores kill-point heartbeat state from host truth (the
    ROADMAP takeover rung — relist, don't re-derive), and only the
    post-kill slice of the lease schedule re-feeds to convergence."""
    import copy

    from kubernetes_tpu.api import types as t

    router, owners, map_path = _fleet_node_loss_build(state_dir, recover=True)
    deleted = _truth_deleted(state_dir)
    evicted = _truth_evicted(state_dir)
    nodes, bound, pending = node_loss_objects()
    for n in nodes:
        router.add_object("Node", n)
    router.reconcile_recovered()
    router.adopt_bindings()
    router.drain_evictions()
    for p in bound + pending:
        if p.uid in deleted:
            continue
        obj = copy.deepcopy(p)
        if obj.uid in evicted and obj.uid not in router._pod_shard:
            obj.spec.node_name = ""  # host truth: recreated unbound
        elif obj.uid in router._pod_shard:
            # Already (re)bound per the owners' journals — deliver the
            # adopted placement, not the stale original node.
            continue
        router.add_object("Pod", obj)
    # Restore the tie-break cycle: the unkilled router burned one step
    # per QUEUE-scheduled pod (the scenario's pending pods) before the
    # incident's rebinds — adopted commits say how many of those pops
    # already happened, so the recovery's rebind steps line up with the
    # baseline's and score ties break identically.
    router._cycle = sum(1 for p in pending if p.uid in router._pod_shard)
    # Lease relist: host truth's CURRENT renewals (the kill-point
    # stamps) feed once, restoring the logical clock and heartbeat set
    # the dead fleet held — idempotent against the owners' own
    # journal-replayed lifecycle state.
    lease_truth = _truth_leases(state_dir)
    for name in sorted(lease_truth):
        router.add_object("Lease", t.Lease(name, lease_truth[name]))
    _fleet_node_loss_tail(
        router, owners, map_path, state_dir, initial_schedule=False,
        lease_floor=lease_truth,
    )
    for owner in owners.values():
        owner.close()


def _fleet_node_loss_cell_evidence(state_dir: str) -> list[str]:
    """A killed fleet cell must leave: a readable recovery flight dump,
    per-owner lifecycle/GC metrics with real counts (transitions and
    evictions restored across the crash), and router loop closure —
    every eviction absorbed and rebound, nothing pending."""
    missing = []
    if not _flight_dump_ok(state_dir):
        missing.append("flight-dump")
    try:
        with open(os.path.join(state_dir, "metrics.json")) as f:
            doc = json.load(f)
        blob = json.dumps(doc)
        for fam in (
            "scheduler_node_lifecycle_transitions_total",
            "scheduler_pod_gc_total",
            "scheduler_fleet_lifecycle_lease_frames_total",
            "scheduler_fleet_lifecycle_evictions_total",
        ):
            if fam not in blob:
                missing.append(f"metrics:{fam}")
        shard0 = doc["owners"]["0"]["stats"]["lifecycle"]
        if not shard0["armed"]:
            missing.append("lifecycle:not-armed")
        if shard0["transitions"] < 1:
            missing.append("lifecycle:no-transitions")
        if shard0["taint_evictions"] < 1:
            missing.append("lifecycle:no-evictions")
        if sum(shard0["pod_gc_collected"].values()) < 1:
            missing.append("lifecycle:no-gc")
        if shard0["pending_eviction_requeues"] != 0:
            missing.append("lifecycle:stranded-requeues")
        lc = doc["router"]["lifecycle"]
        if lc["pending_rebinds"] != 0:
            missing.append("router:pending-rebinds")
    except (OSError, ValueError, KeyError):
        missing.append("metrics.json")
    return missing


def run_fleet_node_loss_matrix(
    cases=FLEET_NODE_LOSS_CASES, verbose=True
) -> list[str]:
    """SIGKILL the fleet node-death scenario at each journal point,
    take the shards over, and require (a) final bindings bit-identical
    to the unkilled fleet — which must itself match the armed single
    scheduler (the node-loss oracle) — and (b) flight dump + lifecycle
    metrics + loop closure per killed cell."""
    with tempfile.TemporaryDirectory() as td:
        oracle_dir = os.path.join(td, "fleet-nl-single")
        os.makedirs(oracle_dir)
        rc = _spawn("--fleet-node-loss-single-child", oracle_dir)
        oracle = _read_bindings(oracle_dir)
        assert rc == 0 and oracle, "fleet node-loss single oracle failed"
        base_dir = os.path.join(td, "fleet-nl-baseline")
        os.makedirs(base_dir)
        rc = _spawn("--fleet-node-loss-child", base_dir)
        baseline = _read_bindings(base_dir)
        assert rc == 0 and baseline, "fleet node-loss baseline failed"
        failures = []
        if baseline != oracle:
            failures.append("fleetnodeloss:oracle")
            if verbose:
                diff = {
                    k: (oracle.get(k), baseline.get(k))
                    for k in set(oracle) | set(baseline)
                    if oracle.get(k) != baseline.get(k)
                }
                print(f"FAIL fleet-vs-single oracle: diff={diff}")
        elif verbose:
            print("ok   fleetnodeloss:oracle (fleet == armed single)")
        # The baseline itself must show the loop closed cross-shard.
        for uid in ("default/v1", "default/v2", "default/sticky"):
            assert baseline.get(uid) not in (None, "", "nd1"), (
                f"fleet baseline did not reschedule {uid}: {baseline}"
            )
        for point, nth in cases:
            label = f"fleetnodeloss:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"fnl-{point}-{nth}")
            os.makedirs(state_dir)
            rc = _spawn(
                "--fleet-node-loss-child", state_dir, kill=f"{point}:{nth}"
            )
            if rc == 0:
                got = _read_bindings(state_dir)
                status = "ok (kill never fired)"
                if got != baseline:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn("--fleet-node-loss-recover-child", state_dir)
            got = _read_bindings(state_dir)
            if rc != 0 or got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: rc={rc} diff={diff}")
                continue
            missing = _fleet_node_loss_cell_evidence(state_dir)
            if missing:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: missing evidence {missing}")
                continue
            if verbose:
                print(
                    f"ok   {label}: takeover replayed the incident, "
                    f"evictions finished, bindings bit-identical"
                    f"{_cell_dt(t0)}"
                )
        return failures


# -- the AUTOSCALE crash matrix (live resharding under SIGKILL, ISSUE 11) --


AUTOSCALE_N_BUCKETS = 16


def _autoscale_cfg():
    from kubernetes_tpu.fleet import AutoscalerConfig

    # Thresholds tuned so the scenario's 8-hot/2-cold commit skew over a
    # CAPACITY-SYMMETRIC map (six nodes per shard — the imbalance metric
    # measures window share against NODE share, so only skew the
    # capacity does not explain counts) lands shard 0 at ratio
    # 0.8/0.5 = 1.6 and trips exactly ONE split; the recovery's
    # re-decision (window re-primed from adopted bindings when the map
    # is still pre-resize) converges to the same one-action history,
    # killed anywhere, and a post-resize tick reads a near-empty window
    # and defers (quiet).
    return AutoscalerConfig(
        split_imbalance_hi=1.55,
        merge_imbalance_lo=0.05,
        decide_every_s=0.0,
        cooldown_s=0.0,
        window_s=100.0,
        max_actions_per_window=2,
        min_window_decisions=4,
        max_shards=4,
    )


def _autoscale_sched():
    """Partition-exact profile with NodeAffinity (the hot pods steer via
    node_selector) — filters + an additive scorer only, so fleet sizing
    never perturbs the per-node verdicts themselves."""
    from kubernetes_tpu.framework.config import Profile
    from kubernetes_tpu.scheduler import TPUScheduler

    return TPUScheduler(
        profile=Profile(
            name="autoscale",
            filters=(
                "NodeUnschedulable", "NodeName", "NodeAffinity",
                "NodeResourcesFit",
            ),
            scorers=(("NodeResourcesFit", 1),),
        ),
        batch_size=8,
        chunk_size=1,
    )


def _autoscale_node_names():
    """Six hot names bucket-owned by shard 0 and six cold ones by shard
    1 under the initial 2-shard map — crc32 is cross-process stable, so
    the skew is a property of the names, not of overrides (pins survive
    splits by design and would anchor the load).  Node counts are EQUAL
    per shard on purpose: the imbalance metric is capacity-aware
    (window share vs node share), so the 8/2 commit skew reads as load
    the capacity does not explain.  The hot six straddle the split
    boundary (three in the bucket half a split keeps, three in the half
    it moves), so the moved nodes carry real bindings through the
    journaled import."""
    from kubernetes_tpu.fleet import ShardMap
    from kubernetes_tpu.fleet.shardmap import stable_shard_hash

    probe = ShardMap(n_shards=2, n_buckets=AUTOSCALE_N_BUCKETS)
    owned = [i for i, s in enumerate(probe.buckets) if s == 0]
    keep_half = set(owned[: len(owned) // 2])
    move_half = set(owned[len(owned) // 2:])
    cands = [f"an{i}" for i in range(400)]
    keep = [
        n for n in cands
        if stable_shard_hash(n, AUTOSCALE_N_BUCKETS) in keep_half
    ][:3]
    move = [
        n for n in cands
        if stable_shard_hash(n, AUTOSCALE_N_BUCKETS) in move_half
    ][:3]
    hot = keep + move
    cold = [n for n in cands if probe.owner_of(n) == 1][:6]
    return hot, cold


def autoscale_objects():
    """The skewed-load scenario: hot nodes carry ``hot=1`` and distinct
    capacities (no score ties anywhere in the run — recovery re-burns
    tie-break steps at different batch boundaries), hot pods carry the
    matching selector and cold pods the ``cold=1`` selector (placement
    skew is a property of the pod set, not of score accidents), so
    shard 0 commits 8 of 10 decisions over half the fleet's nodes and
    the capacity-aware imbalance ratio lands at 0.8/0.5 = 1.6 — above
    the 1.55 split threshold."""
    from kubernetes_tpu.api.wrappers import make_node, make_pod

    hot, cold = _autoscale_node_names()
    nodes = [
        make_node(n)
        .capacity({"cpu": str(8 + i), "memory": "32Gi", "pods": 64})
        .label("hot", "1")
        .obj()
        for i, n in enumerate(hot)
    ] + [
        make_node(n)
        .capacity({"cpu": str(4 + i), "memory": "16Gi", "pods": 64})
        .label("cold", "1")
        .obj()
        for i, n in enumerate(cold)
    ]
    pending = [
        make_pod(f"h{i}")
        .req({"cpu": f"{500 + i * 10}m", "memory": "256Mi"})
        .node_selector({"hot": "1"})
        .obj()
        for i in range(8)
    ] + [
        make_pod(f"f{i}")
        .req({"cpu": f"{300 + i * 10}m", "memory": "128Mi"})
        .node_selector({"cold": "1"})
        .obj()
        for i in range(2)
    ]
    post = [
        make_pod(f"post{i}")
        .req({"cpu": f"{200 + i * 10}m", "memory": "64Mi"})
        .node_selector({"hot": "1"})
        .obj()
        for i in range(2)
    ]
    return nodes, pending, post


def _autoscale_build(state_dir: str, recover: bool = False):
    """(router, autoscaler, owners, map_path): the skewed 2-shard
    journaled fleet with the elastic autoscaler wired over it.
    ``recover`` takes over every shard DIRECTORY on disk — the map may
    not have heard of a split-created shard whose handoff record is the
    only durable trace (redo_lost_map_writes closes exactly that)."""
    import glob

    from kubernetes_tpu.fleet import (
        FleetAutoscaler,
        FleetRouter,
        ShardMap,
        ShardOwner,
    )
    from kubernetes_tpu.fleet.takeover import recover_shard

    map_path = os.path.join(state_dir, "shardmap.json")
    if os.path.exists(map_path):
        smap = ShardMap.load(map_path)
    else:
        smap = ShardMap(n_shards=2, n_buckets=AUTOSCALE_N_BUCKETS)
        smap.save(map_path)

    def _wrap_truth(owner):
        orig_delete = owner.sched.delete_pod

        def delete_pod(uid, notify=True, _orig=orig_delete):
            _truth_delete(state_dir, uid)
            _orig(uid, notify)

        owner.sched.delete_pod = delete_pod
        return owner

    def make_owner(k: int) -> ShardOwner:
        sdir = os.path.join(state_dir, f"shard{k}")
        os.makedirs(sdir, exist_ok=True)
        owner = ShardOwner(k, _autoscale_sched(), smap, state_dir=sdir)
        _every_batch(owner.sched)
        return _wrap_truth(owner)

    owners = {}
    if recover:
        from kubernetes_tpu.fleet.shardmap import read_version
        from kubernetes_tpu.fleet.takeover import redo_handoff

        # Take over every shard DIRECTORY on disk — a split-created
        # shard may exist only as a journal whose handoff record is the
        # sole durable trace of the resize.  No map enforcement here:
        # mid-transfer, bindings can live solely on the LOSING side, and
        # an enforcement drop would force re-scheduling (placements
        # could diverge); the recovery child instead FINISHES the
        # transfer through the journaled import path.
        shard_ids = sorted(
            {
                int(os.path.basename(d)[len("shard"):])
                for d in glob.glob(os.path.join(state_dir, "shard*"))
                if os.path.isdir(d)
                and os.path.basename(d)[len("shard"):].isdigit()
            }
            | set(smap.shard_ids())
        )
        for k in shard_ids:
            sdir = os.path.join(state_dir, f"shard{k}")
            os.makedirs(sdir, exist_ok=True)
            owners[k] = _wrap_truth(
                recover_shard(sdir, _autoscale_sched, k, shard_map=None)
            )
        # Redo lost map writes from every owner's recovered handoff
        # records (the append→map-rewrite window), then install guards
        # at the converged map.
        lost = []
        for k in sorted(owners):
            recs = (
                getattr(owners[k].sched, "_recovered_handoffs", None)
                or []
            )
            lost += [r for r in recs if r["version"] > smap.version]
        for rec in sorted(lost, key=lambda r: r["version"]):
            redo_handoff(smap, rec)
        if smap.version > read_version(map_path):
            smap.save(map_path)
        doc = smap.to_doc()
        for k in sorted(owners):
            owners[k].set_map(doc)
    else:
        for k in range(2):
            owners[k] = make_owner(k)
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    autoscaler = FleetAutoscaler(
        router,
        _autoscale_cfg(),
        map_path=map_path,
        owner_provider=make_owner,
        state_path=os.path.join(state_dir, "autoscaler.json"),
    )
    return router, autoscaler, owners, map_path


def _autoscale_tail(
    router, autoscaler, owners, map_path: str, state_dir: str,
    initial_schedule: bool = True,
):
    """The scenario tail — idempotent: the script's one autoscaler
    evaluation ran against the VERSION-0 map, and the map version is the
    durable marker of whether its effect landed.  A recovery whose map
    is still at version 0 re-primes from the adopted bindings (the
    pre-resize distribution — the kill necessarily predates any
    post-resize commit) and re-decides the identical split; a recovery
    whose map already advanced ticks unprimed, reads a near-empty
    window, and defers (quiet) — the resize is history, not a pending
    decision.  Post-resize pods prove the elastic fleet still serves."""
    from gen_golden_transcripts import wait_for_backoffs

    if initial_schedule:
        router.schedule_all_pending(wait_backoff=True)
    if router.shard_map.version == 0:
        autoscaler.prime_from_bindings()
    autoscaler.tick(1.0)
    _nodes, _pending, post = autoscale_objects()
    for p in post:
        if p.uid not in router._pod_shard:
            router.add_pod(p)
    wait_for_backoffs(router.queue)
    router.schedule_all_pending(wait_backoff=True)
    bindings = router.bindings()
    with open(os.path.join(state_dir, "bindings.json"), "w") as f:
        json.dump(bindings, f, sort_keys=True)
    with open(os.path.join(state_dir, "autoscale.json"), "w") as f:
        json.dump(
            {
                "map": router.shard_map.to_doc(),
                "actions": autoscaler.actions,
                "deferrals": autoscaler.deferrals,
                "status": autoscaler.status(),
                "registry": router.registry.summary(),
            },
            f,
            sort_keys=True,
            default=str,
        )
    return bindings


def autoscale_kill_child(state_dir: str) -> None:
    """The victim: skewed load trips the autoscaler's split;
    TPU_JOURNAL_KILL SIGKILLs inside the autoscaler-initiated handoff
    (post-handoff-append / pre-map-write / mid-drop / torn record /
    imported-bind re-journal / checkpoint)."""
    from kubernetes_tpu.faults import KillSwitch

    router, autoscaler, owners, map_path = _autoscale_build(state_dir)
    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    nodes, pending, _post = autoscale_objects()
    for n in nodes:
        router.add_object("Node", n)
    for p in pending:
        router.add_pod(p)
    _autoscale_tail(router, autoscaler, owners, map_path, state_dir)
    for owner in owners.values():
        owner.close()


def autoscale_recover_child(state_dir: str) -> None:
    """The takeover: every shard directory recovers behind an epoch
    bump, lost map writes redo, the map-enforcement sweep finishes
    interrupted drops, the router adopts, and the tail re-runs — the
    autoscaler's re-decision converging on the same one-split history."""
    router, autoscaler, owners, map_path = _autoscale_build(
        state_dir, recover=True
    )
    deleted = _truth_deleted(state_dir)
    nodes, pending, post = autoscale_objects()
    for n in nodes:
        router.add_object("Node", n)
    # Finish any transfer the crash interrupted: nodes a losing owner
    # still holds that the (possibly just-redone) map assigns elsewhere
    # move NOW through the journaled import path — their bindings ride
    # along instead of being dropped and re-scheduled, so placements
    # stay bit-identical to the unkilled run.  The synthetic record's
    # version equals the durable map's, so a later recovery never
    # mistakes it for a lost map write; with nothing left to move the
    # sweep is a no-op.
    router.apply_handoff(
        {"op": "rebalance", "version": router.shard_map.version}, None
    )
    router.reconcile_recovered()
    router.adopt_bindings()
    for p in pending:
        if p.uid not in deleted and p.uid not in router._pod_shard:
            router.add_pod(p)
    # Tie-break continuity (the fleet node-loss recovery's trick): the
    # dead router burned one step per queue-scheduled pod, post-resize
    # commits included.
    router._cycle = sum(
        1 for p in pending + post if p.uid in router._pod_shard
    )
    _autoscale_tail(router, autoscaler, owners, map_path, state_dir)
    for owner in owners.values():
        owner.close()


def _autoscale_cell_evidence(state_dir: str) -> list[str]:
    """A killed autoscale cell must leave: a readable recovery flight
    dump, a final map showing the split (3 shards), exactly one split in
    the converged action history or a no-op tick over an already-resized
    map, and the scheduler_fleet_autoscaler_* families in the metrics
    snapshot."""
    missing = []
    if not _flight_dump_ok(state_dir):
        missing.append("flight-dump")
    try:
        with open(os.path.join(state_dir, "autoscale.json")) as f:
            doc = json.load(f)
        shards = sorted(set(doc["map"]["buckets"]))
        if len(shards) != 3:
            missing.append(f"map:{len(shards)}-shards")
        blob = json.dumps(doc)
        if "scheduler_fleet_autoscaler_imbalance_ratio" not in blob:
            missing.append("metrics:imbalance_ratio")
        # The recovery's tick either re-acted (actions_total) or read
        # the durable resize and deferred (deferrals_total) — one of
        # the two families must have materialized.
        if (
            "scheduler_fleet_autoscaler_actions_total" not in blob
            and "scheduler_fleet_autoscaler_deferrals_total" not in blob
        ):
            missing.append("metrics:no-autoscaler-families")
    except (OSError, ValueError, KeyError):
        missing.append("autoscale.json")
    return missing


def run_autoscale_kill_matrix(
    cases=AUTOSCALE_KILL_CASES, verbose=True
) -> list[str]:
    """SIGKILL the fleet inside an autoscaler-initiated split at each
    named point, take the shards over, and require final bindings AND
    the final shard map bit-identical to an unkilled run, plus a flight
    dump + autoscaler metrics per killed cell."""
    with tempfile.TemporaryDirectory() as td:
        base_dir = os.path.join(td, "autoscale-baseline")
        os.makedirs(base_dir)
        rc = _spawn("--autoscale-kill-child", base_dir)
        baseline = _read_bindings(base_dir)
        assert rc == 0 and baseline, "autoscale baseline run failed"
        with open(os.path.join(base_dir, "autoscale.json")) as f:
            base_auto = json.load(f)
        base_map = base_auto["map"]
        assert [a["op"] for a in base_auto["actions"]] == ["split"], (
            f"baseline must trip exactly one split: {base_auto['actions']}"
        )
        failures = []
        for point, nth in cases:
            label = f"autoscalekill:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"as-{point}-{nth}")
            os.makedirs(state_dir)
            rc = _spawn(
                "--autoscale-kill-child", state_dir, kill=f"{point}:{nth}"
            )
            if rc == 0:
                got = _read_bindings(state_dir)
                status = "ok (kill never fired)"
                if got != baseline:
                    failures.append(label)
                    status = "FAIL (no kill, diverged)"
                if verbose:
                    print(f"{status} {label}{_cell_dt(t0)}")
                continue
            if rc != -9:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: child exited {rc}, expected SIGKILL")
                continue
            rc = _spawn("--autoscale-recover-child", state_dir)
            got = _read_bindings(state_dir)
            if rc != 0 or got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: rc={rc} diff={diff}{_cell_dt(t0)}")
                continue
            try:
                with open(os.path.join(state_dir, "autoscale.json")) as f:
                    got_map = json.load(f)["map"]
            except (OSError, ValueError, KeyError):
                got_map = None
            if got_map is None or (
                got_map["buckets"] != base_map["buckets"]
                or got_map["overrides"] != base_map["overrides"]
            ):
                failures.append(label)
                if verbose:
                    print(
                        f"FAIL {label}: recovered map diverged "
                        f"({got_map} vs {base_map}){_cell_dt(t0)}"
                    )
                continue
            missing = _autoscale_cell_evidence(state_dir)
            if missing:
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: missing evidence {missing}")
                continue
            if verbose:
                print(
                    f"ok   {label}: mid-resize kill converged — same "
                    f"split, same map, bit-identical bindings"
                    f"{_cell_dt(t0)}"
                )
        return failures


# -- the WIRE crash matrix (host and sidecar killed independently) ---------


def _wire_lease_journal(jdir: str, who: str):
    """(lease, journal) for one side's own journal directory — each side
    fences its log with its own lease epoch, exactly like the two real
    deployments would."""
    from kubernetes_tpu.framework.leaderelection import FileLease, read_epoch
    from kubernetes_tpu.journal import Journal

    os.makedirs(jdir, exist_ok=True)
    lease_path = os.path.join(jdir, "lease")
    lease = FileLease(lease_path, identity=f"{who}-{os.getpid()}")
    lease.acquire(block=True)
    journal = Journal(
        jdir, epoch=lease.epoch, fence=lambda: read_epoch(lease_path)
    )
    return lease, journal


def wire_sidecar_child(state_dir: str) -> None:
    """The sidecar half: the golden basic-session scheduler behind the
    framed socket, write-ahead journal armed (snapshot every batch).  A
    restart recovers snapshot + fenced replay before its first frame
    (SidecarServer's recover-before-serve contract); when
    TPU_JOURNAL_KILL is set, the process SIGKILLs itself mid-commit."""
    from gen_golden_transcripts import session_schedulers

    from kubernetes_tpu.faults import KillSwitch
    from kubernetes_tpu.sidecar.server import SidecarServer

    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    _lease, journal = _wire_lease_journal(
        os.path.join(state_dir, "sidecar-journal"), "wire-sidecar"
    )
    srv = SidecarServer(
        os.path.join(state_dir, "sidecar.sock"),
        scheduler=session_schedulers()["basic_session"](),
        journal=journal,
    )
    _every_batch(srv.scheduler)
    srv.serve_forever()


def wire_host_child(state_dir: str) -> None:
    """The host half: a journaled ResyncingClient driving the scenario
    over the wire.  Breaker effectively disabled and retries generous —
    the cell under test is crash recovery, not degraded mode, so a dead
    sidecar is ridden out through reconnect+replay while the parent
    restarts it.  Idempotent: a restarted host re-runs the whole script
    (already-committed pods are answered from the sidecar's cache)."""
    import time as _time

    from gen_golden_transcripts import scenario_objects

    from kubernetes_tpu.faults import KillSwitch
    from kubernetes_tpu.sidecar.host import ResyncingClient

    ks = KillSwitch.from_env()
    if ks is not None:
        ks.arm()
    lease, journal = _wire_lease_journal(
        os.path.join(state_dir, "host-journal"), "wire-host"
    )
    client = ResyncingClient(
        os.path.join(state_dir, "sidecar.sock"),
        max_reconnect_s=60.0,
        retry_interval_s=0.1,
        deadline_s=DEADLINE_S,
        max_call_retries=50,
        breaker_threshold=10**9,
        journal=journal,
        journal_snapshot_every=4,
    )
    try:
        nodes, bound, pending = scenario_objects()
        for n in nodes:
            client.add("Node", n)
        for p in bound:
            client.add("Pod", p)
        client.schedule(pods=pending, drain=True)
        client.remove("Pod", "default/bound-2")

        def bindings() -> dict:
            state = client.dump()
            return {
                uid: info["node"]
                for uid, info in state.get("pods", {}).items()
                if info.get("bound")
            }

        # Settle loop (the cross-process stand-in for wait_for_backoffs):
        # drain until the binding map is stable across three rounds — the
        # preemptor's nominated retry sits behind a backoff timer.
        last, stable = None, 0
        deadline = _time.monotonic() + 120.0
        while _time.monotonic() < deadline and stable < 3:
            client.schedule(pods=[], drain=True)
            cur = bindings()
            if cur == last:
                stable += 1
            else:
                last, stable = cur, 0
            _time.sleep(0.3)
        with open(os.path.join(state_dir, "bindings.json"), "w") as f:
            json.dump(last or {}, f, sort_keys=True)
    finally:
        client.close()
        lease.release()


def _spawn_bg(mode: str, state_dir: str, kill: str | None = None):
    env = dict(os.environ)
    env.pop("TPU_JOURNAL_KILL", None)
    if kill:
        env["TPU_JOURNAL_KILL"] = kill
    # Flight auto-dumps (the recovery dump each killed cell must leave)
    # land in the cell's state dir.
    env["TPU_FLIGHT_DIR"] = state_dir
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), mode, state_dir],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


def _wait_socket(state_dir: str, timeout_s: float = 30.0) -> bool:
    """Wait until the sidecar is actually ACCEPTING on its socket.  A
    bare existence check is dead code here: SIGKILL never unlinks the
    unix socket file, so the stale path from the killed instance would
    satisfy it before the restarted server has bound."""
    import socket as _socket
    import time as _time

    path = os.path.join(state_dir, "sidecar.sock")
    deadline = _time.monotonic() + timeout_s
    while _time.monotonic() < deadline:
        s = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
        try:
            s.connect(path)
            return True
        except OSError:
            _time.sleep(0.05)
        finally:
            s.close()
    return False


def _flight_dump_ok(state_dir: str) -> bool:
    """A readable recovery flight dump exists in the cell's state dir."""
    import glob

    for path in glob.glob(os.path.join(state_dir, "flight-*recovery*.json")):
        try:
            with open(path) as f:
                doc = json.load(f)
            if any(
                r.get("event") == "recovery" for r in doc.get("records", [])
            ):
                return True
        except (OSError, ValueError):
            continue
    return False


def _run_wire_cell(state_dir: str, side: str | None, kill: str | None):
    """One wire session: start sidecar + host children, restart whichever
    side gets SIGKILLed, return (bindings, kill_fired)."""
    import time as _time

    os.makedirs(state_dir, exist_ok=True)
    host = None
    sidecar = _spawn_bg(
        "--wire-sidecar-child", state_dir,
        kill if side == "sidecar" else None,
    )
    try:
        assert _wait_socket(state_dir), "sidecar socket never appeared"
        host = _spawn_bg(
            "--wire-host-child", state_dir, kill if side == "host" else None
        )
        kill_fired = False
        while True:
            rc = host.poll()
            if sidecar.poll() is not None:
                # The sidecar died (the armed kill, if targeting it).  A
                # clean exit here is unexpected either way — restart it;
                # recovery-before-first-frame brings the pre-crash world
                # back and the host's resync replays the store.
                kill_fired = kill_fired or sidecar.returncode == -9
                sidecar = _spawn_bg("--wire-sidecar-child", state_dir)
                if not _wait_socket(state_dir):
                    return None, kill_fired
            if rc is not None:
                if rc == -9:
                    # The host died mid-commit: restart it; cold-start
                    # journal replay + idempotent scenario re-run.
                    kill_fired = True
                    host = _spawn_bg("--wire-host-child", state_dir)
                    continue
                if rc != 0:
                    _out, err = host.communicate()
                    sys.stderr.write(err or "")
                    return None, kill_fired
                break
            _time.sleep(0.05)
        return _read_bindings(state_dir), kill_fired
    finally:
        # Reap BOTH children on every exit path — an early return (a
        # restarted sidecar that never binds) must not leak a host still
        # writing into the about-to-be-deleted tempdir.
        for proc in (host, sidecar):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()


def run_wire_kill_matrix(cases=WIRE_KILL_CASES, verbose=True) -> list[str]:
    """SIGKILL host and sidecar independently at journal crash points in
    a two-process wire deployment; assert bit-identical recovery AND a
    readable flight dump per killed cell.  Returns diverged labels."""
    with tempfile.TemporaryDirectory() as td:
        base_dir = os.path.join(td, "wire-baseline")
        baseline, _fired = _run_wire_cell(base_dir, None, None)
        assert baseline, "wire baseline produced no bindings"
        failures = []
        for side, point, nth in cases:
            label = f"wirekill:{side}:{point}@{nth}"
            if not _selected(label):
                continue
            t0 = _cell_t0()
            state_dir = os.path.join(td, f"wire-{side}-{point}-{nth}")
            got, fired = _run_wire_cell(state_dir, side, f"{point}:{nth}")
            if got != baseline:
                failures.append(label)
                if verbose:
                    diff = {
                        k: (baseline.get(k), (got or {}).get(k))
                        for k in set(baseline) | set(got or {})
                        if baseline.get(k) != (got or {}).get(k)
                    }
                    print(f"FAIL {label}: fired={fired} diff={diff}")
                continue
            if fired and not _flight_dump_ok(state_dir):
                failures.append(label)
                if verbose:
                    print(f"FAIL {label}: no readable recovery flight dump")
                continue
            if verbose:
                status = "ok  " if fired else "ok (kill never fired)"
                print(f"{status} {label}{_cell_dt(t0)}")
        return failures


def main() -> int:
    global ONLY
    if "--only" in sys.argv:
        # Narrow any matrix to cells whose label contains the given
        # substring (e.g. --only autoscalekill:pre-map-write@1) and
        # print per-cell wall time — the one-cell triage loop.
        ONLY = sys.argv[sys.argv.index("--only") + 1]
        print(
            f"--only {ONLY!r}: running matching cells only (the summary "
            "line still counts the full case list)"
        )
    if "--kill-child" in sys.argv:
        kill_child(sys.argv[sys.argv.index("--kill-child") + 1])
        return 0
    if "--recover-child" in sys.argv:
        recover_child(sys.argv[sys.argv.index("--recover-child") + 1])
        return 0
    if "--pack-kill-child" in sys.argv:
        pack_kill_child(sys.argv[sys.argv.index("--pack-kill-child") + 1])
        return 0
    if "--pack-seq-child" in sys.argv:
        pack_seq_child(sys.argv[sys.argv.index("--pack-seq-child") + 1])
        return 0
    if "--pack-recover-child" in sys.argv:
        pack_recover_child(
            sys.argv[sys.argv.index("--pack-recover-child") + 1]
        )
        return 0
    if "--pack-kill" in sys.argv:
        # The packed-chunk/DomTables-carry subset alone (rides --kill).
        failures = run_pack_kill_matrix()
        if failures:
            print(
                f"{len(failures)} of {len(PACK_KILL_CASES)} pack kill "
                f"cases diverged: {failures}"
            )
            return 1
        print(
            f"all {len(PACK_KILL_CASES)} pack kill cases: mid-batch "
            "SIGKILL under the conflict-aware packer recovered with "
            "DomTables rebuilt from the journaled store, bindings "
            "bit-identical (packed baseline == chunk1 parity)"
        )
        return 0
    if "--tenant-kill-child" in sys.argv:
        tenant_kill_child(
            sys.argv[sys.argv.index("--tenant-kill-child") + 1]
        )
        return 0
    if "--tenant-recover-child" in sys.argv:
        tenant_recover_child(
            sys.argv[sys.argv.index("--tenant-recover-child") + 1]
        )
        return 0
    if "--tenant-kill" in sys.argv:
        # The weighted-fair admission subset alone (rides --kill).
        failures = run_tenant_kill_matrix()
        if failures:
            print(
                f"{len(failures)} of {len(TENANT_KILL_CASES)} tenant kill "
                f"cases diverged: {failures}"
            )
            return 1
        print(
            f"all {len(TENANT_KILL_CASES)} tenant kill cases: SIGKILL "
            "mid-burst under weighted-fair admission recovered the WFQ "
            "ledger from snapshot + journaled debits, admission order "
            "AND bindings bit-identical"
        )
        return 0
    if "--pipeline-kill-child" in sys.argv:
        pipeline_kill_child(
            sys.argv[sys.argv.index("--pipeline-kill-child") + 1]
        )
        return 0
    if "--pipeline-seq-child" in sys.argv:
        pipeline_seq_child(
            sys.argv[sys.argv.index("--pipeline-seq-child") + 1]
        )
        return 0
    if "--pipeline-recover-child" in sys.argv:
        pipeline_recover_child(
            sys.argv[sys.argv.index("--pipeline-recover-child") + 1]
        )
        return 0
    if "--pipeline-kill" in sys.argv:
        # The group-commit/overlapped-drain subset alone (rides --kill).
        failures = run_pipeline_kill_matrix()
        if failures:
            print(
                f"{len(failures)} of {len(PIPELINE_KILL_CASES)} pipeline "
                f"kill cases diverged: {failures}"
            )
            return 1
        print(
            f"all {len(PIPELINE_KILL_CASES)} pipeline kill cases: SIGKILL "
            "inside the group-commit drain windows recovered with NO "
            "staged bind applied ahead of its group fsync, bindings "
            "bit-identical (pipelined baseline == depth-1 parity)"
        )
        return 0
    if "--node-loss-child" in sys.argv:
        node_loss_child(sys.argv[sys.argv.index("--node-loss-child") + 1])
        return 0
    if "--node-loss-recover-child" in sys.argv:
        node_loss_recover_child(
            sys.argv[sys.argv.index("--node-loss-recover-child") + 1]
        )
        return 0
    if "--node-loss" in sys.argv:
        # The failure-response-loop subset alone (also rides --kill).
        failures = run_node_loss_matrix()
        if failures:
            print(
                f"{len(failures)} of {len(NODE_LOSS_CASES)} node-loss "
                f"cases diverged: {failures}"
            )
            return 1
        print(
            f"all {len(NODE_LOSS_CASES)} node-loss cases: staleness → "
            "taint → grace → eviction → requeue → bit-identical reschedule, "
            "with a flight dump + lifecycle/GC metrics per cell"
        )
        return 0
    if "--wire-sidecar-child" in sys.argv:
        wire_sidecar_child(
            sys.argv[sys.argv.index("--wire-sidecar-child") + 1]
        )
        return 0
    if "--wire-host-child" in sys.argv:
        wire_host_child(sys.argv[sys.argv.index("--wire-host-child") + 1])
        return 0
    if "--fleet-node-loss-child" in sys.argv:
        fleet_node_loss_child(
            sys.argv[sys.argv.index("--fleet-node-loss-child") + 1]
        )
        return 0
    if "--fleet-node-loss-single-child" in sys.argv:
        fleet_node_loss_single_child(
            sys.argv[sys.argv.index("--fleet-node-loss-single-child") + 1]
        )
        return 0
    if "--fleet-node-loss-recover-child" in sys.argv:
        fleet_node_loss_recover_child(
            sys.argv[sys.argv.index("--fleet-node-loss-recover-child") + 1]
        )
        return 0
    if "--fleet-node-loss" in sys.argv:
        # The fleet-native failure-response subset (also rides --kill).
        failures = run_fleet_node_loss_matrix()
        if failures:
            print(
                f"{len(failures)} of {len(FLEET_NODE_LOSS_CASES)} fleet "
                f"node-loss cases diverged: {failures}"
            )
            return 1
        print(
            f"all {len(FLEET_NODE_LOSS_CASES)} fleet node-loss cases: "
            "per-owner staleness → journaled taint → eviction → router "
            "requeue → cross-shard rebind recovered bit-identical (fleet "
            "== armed single), flight dump + lifecycle metrics per cell"
        )
        return 0
    if "--autoscale-kill-child" in sys.argv:
        autoscale_kill_child(
            sys.argv[sys.argv.index("--autoscale-kill-child") + 1]
        )
        return 0
    if "--autoscale-recover-child" in sys.argv:
        autoscale_recover_child(
            sys.argv[sys.argv.index("--autoscale-recover-child") + 1]
        )
        return 0
    if "--autoscale-kill" in sys.argv:
        # The mid-resize subset alone (also rides --kill): SIGKILL
        # inside an autoscaler-initiated split.
        failures = run_autoscale_kill_matrix()
        if failures:
            print(
                f"{len(failures)} of {len(AUTOSCALE_KILL_CASES)} "
                f"autoscale kill cases diverged: {failures}"
            )
            return 1
        print(
            f"all {len(AUTOSCALE_KILL_CASES)} autoscale kill cases: a "
            "SIGKILL inside the live resize converged to the same split, "
            "same map, bit-identical bindings"
        )
        return 0
    if "--standby-promo-child" in sys.argv:
        standby_promo_child(
            sys.argv[sys.argv.index("--standby-promo-child") + 1]
        )
        return 0
    if "--standby-promo-recover-child" in sys.argv:
        standby_promo_recover_child(
            sys.argv[sys.argv.index("--standby-promo-recover-child") + 1]
        )
        return 0
    if "--standby-ckpt-child" in sys.argv:
        standby_ckpt_child(
            sys.argv[sys.argv.index("--standby-ckpt-child") + 1]
        )
        return 0
    if "--standby-ckpt-recover-child" in sys.argv:
        standby_ckpt_recover_child(
            sys.argv[sys.argv.index("--standby-ckpt-recover-child") + 1]
        )
        return 0
    if "--standby-kill" in sys.argv:
        # The warm-standby promotion + resumable-driver subset (ISSUE
        # 18; also rides --kill).
        failures = run_standby_kill_matrix()
        if failures:
            print(
                f"{len(failures)} of {len(STANDBY_KILL_CASES)} standby "
                f"kill cases diverged: {failures}"
            )
            return 1
        print(
            f"all {len(STANDBY_KILL_CASES)} standby kill cases: SIGKILL "
            "inside the promotion window / checkpoint write recovered "
            "bit-identical with no slot offered twice"
        )
        return 0
    if "--fleet-kill-child" in sys.argv:
        fleet_kill_child(sys.argv[sys.argv.index("--fleet-kill-child") + 1])
        return 0
    if "--fleet-recover-child" in sys.argv:
        fleet_recover_child(
            sys.argv[sys.argv.index("--fleet-recover-child") + 1]
        )
        return 0
    if "--fleet-kill" in sys.argv:
        # The shard-failover subset alone (also rides --kill).
        failures = run_fleet_kill_matrix()
        if failures:
            print(
                f"{len(failures)} of {len(FLEET_KILL_CASES)} fleet kill "
                f"cases diverged: {failures}"
            )
            return 1
        print(
            f"all {len(FLEET_KILL_CASES)} shard-failover cases recovered "
            "to bit-identical bindings with flight dumps"
        )
        return 0
    if "--kill" in sys.argv:
        failures = run_kill_matrix()
        # The wire-deployment subset rides --kill (the ROADMAP layer-0
        # gap): host and sidecar SIGKILLed independently.
        failures += run_wire_kill_matrix()
        # The shard-failover subset (fleet takeover) rides --kill too.
        failures += run_fleet_kill_matrix()
        # And the failure-response-loop subset (node death mid-scenario).
        failures += run_node_loss_matrix()
        # And its fleet-native form (node death inside a shard).
        failures += run_fleet_node_loss_matrix()
        # And the elastic-resize subset (SIGKILL inside an autoscaler-
        # initiated split).
        failures += run_autoscale_kill_matrix()
        # And the packed-chunk/DomTables-carry subset (ISSUE 13).
        failures += run_pack_kill_matrix()
        # And the pipelined group-commit drain subset (ISSUE 15).
        failures += run_pipeline_kill_matrix()
        # And the weighted-fair admission subset (ISSUE 17).
        failures += run_tenant_kill_matrix()
        # And the warm-standby promotion + resumable driver (ISSUE 18).
        failures += run_standby_kill_matrix()
        total = (
            len(KILL_CASES) + len(WIRE_KILL_CASES) + len(FLEET_KILL_CASES)
            + len(NODE_LOSS_CASES) + len(FLEET_NODE_LOSS_CASES)
            + len(AUTOSCALE_KILL_CASES) + len(PACK_KILL_CASES)
            + len(PIPELINE_KILL_CASES) + len(TENANT_KILL_CASES)
            + len(STANDBY_KILL_CASES)
        )
        if failures:
            print(f"{len(failures)} of {total} kill cases diverged: {failures}")
            return 1
        print(
            f"all {total} crash-matrix cases (in-process + wire + fleet) "
            "recovered to bit-identical bindings with flight dumps"
        )
        return 0
    # The full grid also sweeps nth=2 (the fault lands mid-session, after
    # state has accumulated — for schedule, the post-delete drain) — both
    # phases must hold.  The scenario carries a single remove frame, so
    # remove@2 reports "fault never matched"; that's the honest grid.
    cases = matrix_cases() + matrix_cases(nth=2)
    failures = run_matrix(cases)
    if failures:
        print(f"{len(failures)} of {len(cases)} cases diverged: {failures}")
        return 1
    print(f"all {len(cases)} fault-matrix cases produced identical bindings")
    return 0


if __name__ == "__main__":
    sys.exit(main())
