"""Full default-profile scalar oracle: a sequential scheduler composing the
per-plugin scalar references (reference_impl.py) into end-to-end decisions —
filters → truncation → fused normalized-weighted scoring → seeded tie-break
→ greedy-reprieve preemption → nominated retry — mirroring, decision for
decision, the device engine in parity mode (chunk_size=1).

Used by tests/test_parity.py (in-process) and scripts/parity_ab.py (over
the sidecar wire) for the bit-identical-bindings A/B the north star
requires (schedule_one.go:411–920, preemption.go:148–470).

Scope (r4): the FULL default profile — the compute plugins (unschedulable/
name/taints/node-affinity/ports/fit/spread/inter-pod-affinity + all five
scorers) AND the host-state plugins: VolumeBinding (bound PV affinity,
WFFC candidate/provisioner topology, PreBind binding with smallest-fitting
PV), VolumeZone, VolumeRestrictions (device conflicts + RWOP),
NodeVolumeLimits (CSI attach limits), DynamicResources (counted devices,
delayed allocation), and SchedulingGates (gated pods never enter the
queue).  build_fixture carries the objects that make them ACTIVE."""

from __future__ import annotations

import heapq
import itertools
from dataclasses import dataclass, field

from kubernetes_tpu.api import types as t

from reference_impl import (
    MAX_NODE_SCORE,
    RefClaims,
    RefNodeState,
    RefVolumes,
    balanced_allocation_score,
    dra_commit,
    dra_filter,
    fit_score,
    fits_request,
    ipa_filter,
    ipa_score,
    node_affinity_filter,
    node_affinity_score_raw,
    node_ports_filter,
    node_volume_limits_filter,
    spread_filter,
    spread_score,
    taint_toleration_filter,
    taint_toleration_score_raw,
    volume_binding_filter,
    volume_commit,
    volume_restrictions_filter,
    volume_zone_filter,
)
from test_parity import hash_u32, interleave_zones, num_feasible_nodes_to_find


def default_normalize(raws: dict[str, int], feasible: list[str], reverse: bool) -> dict[str, int]:
    """Scalar DefaultNormalizeScore (plugins/helper/normalize_score.go)."""
    mx = max((raws.get(n, 0) for n in feasible), default=0)
    out = {}
    for n in feasible:
        if mx == 0:
            out[n] = MAX_NODE_SCORE if reverse else 0
            continue
        s = raws.get(n, 0) * MAX_NODE_SCORE // mx
        out[n] = MAX_NODE_SCORE - s if reverse else s
    return out


@dataclass
class Decision:
    pod: t.Pod
    node: str | None
    nominated: str | None = None
    victims: tuple[str, ...] = ()


@dataclass
class _Queued:
    pod: t.Pod
    nominated: str | None = None


class FullOracleScheduler:
    """Sequential scalar scheduler over the default plugin set with the
    engine's queue/batch/preemption discipline (parity mode)."""

    def __init__(
        self,
        nodes: list[t.Node],
        pct: int | None = None,
        seed: int = 0,
        hard_pod_affinity_weight: int = 1,
        batch_size: int = 128,
        ns_labels: dict[str, dict[str, str]] | None = None,
        pdbs: list[t.PodDisruptionBudget] | None = None,
        vols: RefVolumes | None = None,
        claims: RefClaims | None = None,
    ):
        self.nodes = list(nodes)  # row order = insertion order
        self.states = {n.name: RefNodeState(node=n) for n in nodes}
        by_zone: dict[str, list[str]] = {}
        for n in nodes:
            z = n.metadata.labels.get("topology.kubernetes.io/zone", "")
            by_zone.setdefault(z, []).append(n.name)
        self.order = interleave_zones(by_zone)
        self.pct = pct
        self.seed = seed
        self.hard_w = hard_pod_affinity_weight
        self.batch_size = batch_size
        self.ns_labels = ns_labels or {}
        self.pdbs = list(pdbs or [])
        self.start = 0
        self.step = 0
        self._seq = itertools.count()
        self._heap: list = []
        self._info: dict[str, _Queued] = {}
        # Nominator overlay: uid → (node, pod) — freed capacity a preemptor
        # claimed; other pods' fit checks count it (framework.go:973).
        self.nominator: dict[str, tuple[str, t.Pod]] = {}
        self.vols = vols or RefVolumes()
        self.claims = claims or RefClaims()
        self.pvc_users: dict[str, int] = {}
        self.gated: list[t.Pod] = []

    # -- cluster mutation (bound pods) --------------------------------------

    def add_bound(self, pod: t.Pod) -> None:
        self.states[pod.spec.node_name].pods.append(pod)
        for pvc in self.vols.pod_pvcs(pod):
            if pvc is not None:
                self.pvc_users[pvc.uid] = self.pvc_users.get(pvc.uid, 0) + 1

    # -- queue --------------------------------------------------------------

    def add(self, pod: t.Pod, nominated: str | None = None) -> None:
        if pod.spec.scheduling_gates:
            # PreEnqueue: SchedulingGates parks gated pods out of every
            # queue (schedulinggates/scheduling_gates.go).
            self.gated.append(pod)
            return
        q = self._info.get(pod.uid)
        if q is None:
            q = _Queued(pod=pod)
            self._info[pod.uid] = q
        q.nominated = nominated
        heapq.heappush(
            self._heap, (-pod.spec.priority, next(self._seq), pod.uid)
        )

    def _pop_batch(self) -> list[_Queued]:
        out = []
        while self._heap and len(out) < self.batch_size:
            _, _, uid = heapq.heappop(self._heap)
            q = self._info.pop(uid, None)
            if q is not None:
                out.append(q)
        return out

    # -- one scheduling cycle ----------------------------------------------

    def _pods_on(self) -> dict[str, list[t.Pod]]:
        return {name: st.pods for name, st in self.states.items()}

    def _filter(self, pod: t.Pod, exclude_uid: str | None = None) -> dict[str, bool]:
        """All filter plugins in profile order, incl. the nominator overlay
        (a nominated pod's claim counts against OTHER pods' fit)."""
        pods_on = self._pods_on()
        spread_ok = spread_filter(pod, self.nodes, pods_on)
        ipa_ok = ipa_filter(pod, self.nodes, pods_on, self.ns_labels)
        out = {}
        unsched_taint = t.Taint(
            key="node.kubernetes.io/unschedulable", effect=t.EFFECT_NO_SCHEDULE
        )
        for n in self.nodes:
            st = self.states[n.name]
            ok = not n.spec.unschedulable or any(
                tol.tolerates(unsched_taint) for tol in pod.spec.tolerations
            )
            if ok and pod.spec.node_name:
                ok = pod.spec.node_name == n.name
            ok = ok and taint_toleration_filter(pod, n)
            ok = ok and node_affinity_filter(pod, n)
            ok = ok and node_ports_filter(pod, st.pods)
            if ok:
                ok = not fits_request(pod, st)
            if ok:
                # Nominator overlay (RunFilterPluginsWithNominatedPods /
                # ops/noderesources.py): when the pod's priority ≤ the
                # node's max nominated priority, it must ALSO fit with
                # every nominated pod's claim counted (self excluded).
                overlay = [
                    p
                    for uid2, (nn, p) in self.nominator.items()
                    if nn == n.name and uid2 != (exclude_uid or "")
                ]
                if overlay and pod.spec.priority <= max(
                    p.spec.priority for p in overlay
                ):
                    st2 = RefNodeState(node=n, pods=st.pods + overlay)
                    ok = not fits_request(pod, st2)
            ok = ok and spread_ok[n.name] and ipa_ok[n.name]
            # Host-state plugins (volume quartet + DRA).
            ok = ok and volume_restrictions_filter(
                pod, st.pods, self.vols, self.pvc_users
            )
            ok = ok and node_volume_limits_filter(pod, n, st.pods, self.vols)
            ok = ok and volume_binding_filter(pod, n, self.vols)
            ok = ok and volume_zone_filter(pod, n, self.vols)
            ok = ok and dra_filter(pod, n, self.claims)
            out[n.name] = ok
        return out

    def _score(self, pod: t.Pod, feasible: list[str]) -> dict[str, int]:
        pods_on = self._pods_on()
        feas_map = {n: n in feasible for n in self.states}
        taint = default_normalize(
            {n.name: taint_toleration_score_raw(pod, n) for n in self.nodes},
            feasible, reverse=True,
        )
        naff = default_normalize(
            {n.name: node_affinity_score_raw(pod, n) for n in self.nodes},
            feasible, reverse=False,
        )
        spread = spread_score(pod, self.nodes, pods_on, feas_map)
        ipa = ipa_score(
            pod, self.nodes, pods_on, feas_map, self.hard_w, self.ns_labels
        )
        total = {}
        for name in feasible:
            st = self.states[name]
            total[name] = (
                3 * taint[name]
                + 2 * naff[name]
                + 1 * fit_score(pod, st)
                + 2 * spread[name]
                + 2 * ipa[name]
                + 1 * balanced_allocation_score(pod, st)
                # ImageLocality: fixtures carry no images → inactive on the
                # engine side; a uniform 0 here never changes the argmax.
            )
        return total

    def _schedule_one(self, q: _Queued) -> Decision:
        pod = q.pod
        n_all = len(self.order)
        limit = num_feasible_nodes_to_find(self.pct, n_all)
        full = self._filter(pod, exclude_uid=pod.uid)
        feasible: list[str] = []  # rotated scan order
        processed = n_all
        for j in range(n_all):
            name = self.order[(self.start + j) % n_all]
            if not full[name]:
                continue
            if len(feasible) == limit:
                processed = j
                break
            feasible.append(name)
        tie_rand = hash_u32((self.seed * 2654435761 + self.step) & 0xFFFFFFFF)
        self.step += 1
        self.start = (self.start + processed) % n_all
        if not feasible:
            return Decision(pod=pod, node=None)
        # Nominated fast path (schedule_one.go:491–502 / engine eval_pod):
        # take the nominated node whenever it is feasible.
        if q.nominated and q.nominated in feasible:
            pick = q.nominated
        else:
            scores = self._score(pod, feasible)
            best = max(scores.values())
            ties = [n for n in feasible if scores[n] == best]
            pick = ties[tie_rand % len(ties)]
        self.states[pick].pods.append(pod)
        self.nominator.pop(pod.uid, None)
        # Reserve/PreBind: bind delayed volumes + allocate claims on the
        # chosen node (volume_binding.go:521; dynamicresources PreBind).
        volume_commit(pod, self.states[pick].node, self.vols, self.pvc_users)
        dra_commit(pod, pick, self.claims)
        return Decision(pod=pod, node=pick)

    # -- preemption (greedy reprieve, scalar) --------------------------------

    def _preempt(self, pod: t.Pod) -> Decision:
        if pod.spec.preemption_policy == t.PREEMPT_NEVER:
            return Decision(pod=pod, node=None)
        prio = pod.spec.priority
        pods_on = self._pods_on()

        def matched(p: t.Pod) -> list[int]:
            return [
                i
                for i, pdb in enumerate(self.pdbs)
                if pdb.namespace == p.namespace
                and t.label_selector_matches(pdb.selector, p.metadata.labels)
            ]

        candidates: list[tuple[str, list[t.Pod]]] = []
        for n in self.nodes:
            st = self.states[n.name]
            lower = [p for p in st.pods if p.spec.priority < prio]
            if not lower:
                continue
            # Release-independent filters must already pass (VolumeBinding
            # and VolumeZone are invariant under pod removal — evicting
            # moves no volume; build_preempt_pass treats them the same).
            if not (
                (not n.spec.unschedulable)
                and taint_toleration_filter(pod, n)
                and node_affinity_filter(pod, n)
                and volume_binding_filter(pod, n, self.vols)
                and volume_zone_filter(pod, n, self.vols)
            ):
                continue
            # DRA hard candidacy: a missing claim or a claim pinned to
            # another node is unresolvable by eviction; a device SHORTAGE
            # is resolvable but skips the reprieve (every lower-priority
            # pod goes; the retry validates against post-eviction truth —
            # preemption.py _RELEASE_DEPENDENT/resolvable_ops).
            dra_hard_ok = True
            for claim in self.claims.pod_claims(pod):
                if claim is None or (
                    claim.allocated_node and claim.allocated_node != n.name
                ):
                    dra_hard_ok = False
                    break
            if not dra_hard_ok:
                continue
            # RWOP exclusivity is the engine's remaining evict-all route
            # (preemption.py divergences): a blocked preemptor skips the
            # reprieve; everything else — device conflicts, CSI attach
            # counts, DRA device shortage — releases in the what-if (r5).
            res_fail = any(
                pvc is not None
                and t.RWOP in pvc.access_modes
                and self.pvc_users.get(pvc.uid, 0) > 0
                for pvc in self.vols.pod_pvcs(pod)
            )
            keep = [p for p in st.pods if p.spec.priority >= prio]

            def dra_filter_trial(removed: list[t.Pod]) -> bool:
                """dra_filter with the victims' claim charges released:
                a claim frees its devices on n exactly when evicting the
                removed set would empty its reservations — the same
                reserved_for rule the eviction code below applies, so the
                what-if and post-eviction truth agree (review finding:
                a claim co-reserved by an external consumer never
                releases)."""
                removed_uids = {p.uid for p in removed}
                released: dict[str, int] = {}
                seen: set[str] = set()
                for p in removed:
                    for claim in self.claims.pod_claims(p):
                        if (
                            claim is None
                            or claim.uid in seen
                            or claim.allocated_node != n.name
                            or not set(claim.reserved_for) <= removed_uids
                        ):
                            continue
                        seen.add(claim.uid)
                        released[claim.device_class] = (
                            released.get(claim.device_class, 0) + claim.count
                        )
                need: dict[str, int] = {}
                for claim in self.claims.pod_claims(pod):
                    if claim is None:
                        return False
                    if claim.allocated_node:
                        if claim.allocated_node != n.name:
                            return False
                        continue
                    need[claim.device_class] = (
                        need.get(claim.device_class, 0) + claim.count
                    )
                for cls, cnt in need.items():
                    if self.claims.free(n.name, cls) + released.get(cls, 0) < cnt:
                        return False
                return True

            def ok_with(removed: list[t.Pod]) -> bool:
                trial = {
                    name: (
                        [p for p in ps if p not in removed]
                        if name == n.name
                        else ps
                    )
                    for name, ps in pods_on.items()
                }
                st2 = RefNodeState(node=n, pods=trial[n.name])
                if fits_request(pod, st2):
                    return False
                if not node_ports_filter(pod, st2.pods):
                    return False
                if not spread_filter(pod, self.nodes, trial)[n.name]:
                    return False
                if not ipa_filter(pod, self.nodes, trial, self.ns_labels)[n.name]:
                    return False
                # Volume/DRA releases (r5): the trial pod set drives the
                # device-conflict and attach-count checks directly; DRA
                # uses the claim-crossing release above.  The RWOP check
                # is excluded here (empty user map) exactly like the
                # engine's what-if forces vr_rwop_ok — the res_fail
                # evict-all route owns RWOP semantics.
                if not volume_restrictions_filter(
                    pod, st2.pods, self.vols, {}
                ):
                    return False
                if not node_volume_limits_filter(pod, n, st2.pods, self.vols):
                    return False
                if not dra_filter_trial(removed):
                    return False
                return True

            if not ok_with(lower):
                continue
            # Violating classification with simulated budget consumption,
            # most-important-first (filterPodsWithPDBViolation).
            remaining = [max(p.disruptions_allowed, 0) for p in self.pdbs]
            viol: dict[str, bool] = {}
            for p in sorted(
                st.pods, key=lambda p: (-p.spec.priority, p.status.start_time)
            ):
                v = False
                for i in matched(p):
                    if remaining[i] > 0:
                        remaining[i] -= 1
                    else:
                        v = True
                viol[p.uid] = v
            # Greedy reprieve: violating most-important-first, then
            # non-violating most-important-first.  Nodes whose failure
            # includes an unsimulated-resolvable op (DRA shortage) skip
            # reprieve: every lower-priority pod goes.
            victims = list(lower)
            if not res_fail:
                order = sorted(
                    lower,
                    key=lambda p: (
                        not viol.get(p.uid, False),
                        -p.spec.priority,
                        p.status.start_time,
                    ),
                )
                for p in order:
                    trial_victims = [v for v in victims if v is not p]
                    if ok_with(trial_victims):
                        victims = trial_victims
            if victims:
                candidates.append((n.name, victims))

        if not candidates:
            return Decision(pod=pod, node=None)

        def criteria(entry):
            name, victims = entry
            viols = 0
            rem = [max(p.disruptions_allowed, 0) for p in self.pdbs]
            cnt = [0] * len(self.pdbs)
            for p in victims:
                for i in matched(p):
                    cnt[i] += 1
            viols = sum(max(c - r, 0) for c, r in zip(cnt, rem))
            mx = max(p.spec.priority for p in victims)
            ssum = sum(p.spec.priority for p in victims)
            earliest = min(
                (p.status.start_time for p in victims if p.spec.priority == mx),
            )
            start_key = -int(earliest * 1e6)
            return (viols, mx, ssum, len(victims), start_key)

        # Lexicographic minimum; ties → lowest row index (engine argmax).
        row = {n.name: i for i, n in enumerate(self.nodes)}
        best = min(candidates, key=lambda e: (criteria(e), row[e[0]]))
        name, victims = best
        for v in victims:
            self.states[name].pods.remove(v)
            for i in matched(v):
                self.pdbs[i].disruptions_allowed -= 1
            # The engine's delete_pod releases the victim's claim
            # reservations (the DRA claim-release control loop: a claim
            # deallocates when its last reserver goes) and its RWOP usage
            # counts — the retry validates against post-eviction truth on
            # both sides.
            for claim in self.claims.pod_claims(v):
                if claim is None:
                    continue
                claim.reserved_for = tuple(
                    u for u in claim.reserved_for if u != v.uid
                )
                if claim.allocated_node and not claim.reserved_for:
                    key = (claim.allocated_node, claim.device_class)
                    self.claims.allocated[key] = (
                        self.claims.allocated.get(key, 0) - claim.count
                    )
                    claim.allocated_node = ""
            for pvc in self.vols.pod_pvcs(v):
                if pvc is not None and self.pvc_users.get(pvc.uid):
                    self.pvc_users[pvc.uid] -= 1
        self.nominator[pod.uid] = (name, pod)
        return Decision(
            pod=pod, node=None, nominated=name,
            victims=tuple(v.uid for v in victims),
        )

    # -- driver (mirrors schedule_batch + prefetch ordering) -----------------

    def run(
        self, pods: list[t.Pod], max_rounds: int = 1000,
        prefetch: bool = True,
    ) -> list[Decision]:
        """``prefetch`` mirrors the engine's featurize-overlap: when on,
        this batch's preemption requeues land in batch k+2.  The engine
        gates prefetch OFF for batches whose active ops read a host catalog
        every batch mutates (DynamicResources; scheduler.py
        _batch_traced), so full-surface fixtures run both sides with
        prefetch=False (and the engine pinned off) for a deterministic
        alignment."""
        for p in pods:
            self.add(p)
        decisions: list[Decision] = []
        prefetched: list[_Queued] | None = None
        for _ in range(max_rounds):
            batch = prefetched if prefetched is not None else self._pop_batch()
            prefetched = None
            if not batch:
                break
            results = [self._schedule_one(q) for q in batch]
            nxt = self._pop_batch() if prefetch else []
            prefetched = nxt if nxt else None
            for q, d in zip(batch, results):
                if d.node is None:
                    d = self._preempt(q.pod)
                    if d.nominated:
                        self.add(q.pod, nominated=d.nominated)
                decisions.append(d)
        return decisions


# ---------------------------------------------------------------------------
# Shared A/B fixture (tests/test_parity_default.py + scripts/parity_ab.py)
# ---------------------------------------------------------------------------

ZONE = "topology.kubernetes.io/zone"


def build_fixture(n_nodes: int = 304, n_pending: int = 120, n_tiny: int = 10,
                  volumes: bool = False):
    """Deterministic default-profile A/B fixture: heterogeneous tainted/
    labeled nodes, seeded bound pods, a pending mix exercising every
    compute plugin, and a preemption theater (tiny saturated pool + vips).
    Every non-vip pod is schedulable on first attempt, so oracle and
    engine agree on the event-free flow.

    ``volumes=True`` (r4) adds the host-state surface: bound-PV pods
    (VolumeBinding affinity + VolumeZone), WFFC static PVs with forced
    smallest-fitting choice, dynamically provisioned claims under
    allowedTopologies, CSI attach limits, an RWOP contention pair,
    counted-device DRA claims (incl. one missing claim), and gated pods.
    Returns (nodes, bound, pending, pdbs, objects) where ``objects`` is
    the extra-object dict (empty when volumes=False)."""
    from kubernetes_tpu.api.wrappers import make_node, make_pod, make_pv, make_pvc

    nodes = []
    for i in range(n_nodes):
        w = (
            make_node(f"node-{i:04d}")
            .capacity({"cpu": "8" if i % 3 else "16", "memory": "32Gi", "pods": 64})
            .zone(f"zone-{i % 4}")
            .region("r1")
        )
        if i % 7 == 0:
            w = w.taint("dedicated", "gpu", t.EFFECT_NO_SCHEDULE)
        if i % 11 == 0:
            w = w.label("disk", "ssd")
        nodes.append(w.obj())
    for i in range(n_tiny):
        nodes.append(
            make_node(f"tiny-{i}")
            .capacity({"cpu": "1", "memory": "4Gi", "pods": 8})
            .zone(f"zone-{i % 4}")
            .region("r1")
            .label("pool", "tiny")
            .obj()
        )

    bound = []
    for i in range(max(n_nodes // 8, 8)):
        bound.append(
            make_pod(f"seed-{i}")
            .req({"cpu": "500m", "memory": "1Gi"})
            .label("color", f"c{i % 8}")
            .start_time(float(i))
            .node(f"node-{(i * 13) % n_nodes:04d}")
            .obj()
        )
    for i in range(n_tiny):
        bound.append(
            make_pod(f"filler-{i}")
            .req({"cpu": "800m", "memory": "1Gi"})
            .label("app", "low")
            .priority(1)
            .start_time(100.0 + i)
            .node(f"tiny-{i}")
            .obj()
        )

    pending = []
    for i in range(n_pending):
        kind = i % 6
        w = make_pod(f"p-{i:04d}").req({"cpu": "700m", "memory": "1Gi"})
        if kind == 0:
            w = w.label("app", f"a{i % 5}")
        elif kind == 1:
            w = w.preferred_node_affinity_in(ZONE, [f"zone-{i % 4}"], weight=30)
        elif kind == 2:
            w = (
                w.toleration("dedicated", value="gpu", effect=t.EFFECT_NO_SCHEDULE)
                .preferred_node_affinity_in("disk", ["ssd"], weight=10)
            )
        elif kind == 3:
            w = w.label("color", f"c{i % 8}").preferred_pod_affinity_in(
                "color", [f"c{i % 8}"], ZONE, weight=25
            )
        elif kind == 4:
            w = w.label("anti", f"x{i}").pod_anti_affinity_in(
                "anti", [f"x{i}"], ZONE
            )
        else:
            w = w.label("app", f"s{i % 3}").spread_constraint(
                2, ZONE, t.SCHEDULE_ANYWAY, "app", [f"s{i % 3}"]
            )
        pending.append(w.obj())
    for i in range(max(n_tiny - 4, 2)):
        pending.append(
            make_pod(f"vip-{i}")
            .req({"cpu": "900m"})
            .priority(50)
            .node_affinity_in("pool", ["tiny"])
            .obj()
        )
    pdbs = [
        t.PodDisruptionBudget(
            name="low-guard",
            namespace="default",
            selector=t.LabelSelector(match_labels=(("app", "low"),)),
            disruptions_allowed=max(n_tiny - 2, 1),
        )
    ]
    objects: dict = {}
    if volumes:
        classes = [
            # One static class per WFFC claim: candidate sets don't overlap,
            # so no same-batch PV race (the engine resolves races by
            # reserve-failure + retry — covered in test_volumes — which a
            # sequential oracle cannot mirror step-for-step).
            *[
                t.StorageClass(
                    name=f"sc-static-{i}",
                    provisioner="kubernetes.io/no-provisioner",
                    binding_mode=t.BINDING_WAIT_FOR_FIRST_CONSUMER,
                )
                for i in range(4)
            ],
            t.StorageClass(
                name="sc-dyn", provisioner="csi.example.com",
                binding_mode=t.BINDING_WAIT_FOR_FIRST_CONSUMER,
                allowed_topologies=t.NodeSelector(terms=(
                    t.NodeSelectorTerm(match_expressions=(
                        t.NodeSelectorRequirement(
                            ZONE, t.OP_IN, ("zone-0", "zone-1")
                        ),
                    )),
                )),
            ),
        ]
        pvs, pvcs = [], []
        # Bound-PV pods: PV pinned to one zone via node affinity AND zone
        # labels (VolumeBinding + VolumeZone both constrain).
        for i in range(6):
            z = f"zone-{i % 4}"
            pvs.append(make_pv(f"pv-bound-{i}", capacity="8Gi",
                               zone=z, node_affinity_zone=[z]))
            pvcs.append(make_pvc(f"bpvc-{i}", volume_name=f"pv-bound-{i}"))
            pvs[-1].claim_ref = f"default/bpvc-{i}"
        # WFFC static pool: distinct capacities force the smallest-fitting
        # choice (FindMatchingVolume) deterministically on both sides.
        for i in range(4):
            pvs.append(make_pv(f"pv-wffc-{i}", capacity=f"{2 + i}Gi",
                               storage_class=f"sc-static-{i}",
                               node_affinity_zone=[f"zone-{i % 4}"]))
            pvcs.append(make_pvc(f"wpvc-{i}", storage_class=f"sc-static-{i}",
                                 request=f"{2 + i}Gi"))
        # Dynamic provisioning under allowedTopologies (zone-0/1 only).
        for i in range(4):
            pvcs.append(make_pvc(f"dpvc-{i}", storage_class="sc-dyn",
                                 request="1Gi"))
        # RWOP contention: two pods want the same single-writer claim.
        pvs.append(make_pv("pv-rwop", capacity="4Gi",
                           access_modes=(t.RWOP,)))
        pvcs.append(make_pvc("rwop-claim", volume_name="pv-rwop",
                             access_modes=(t.RWOP,)))
        pvs[-1].claim_ref = "default/rwop-claim"
        # CSI attach limits on the ssd nodes (driver = sc-dyn provisioner).
        csinodes = [
            t.CSINode(name=f"node-{i:04d}", driver_limits={"csi.example.com": 2})
            for i in range(0, n_nodes, 11)
        ]
        # DRA: gpu devices on the first 8 nodes, 2 each; 6 one-device
        # claims (fits), plus a pod referencing a claim that doesn't exist.
        slices = [
            t.ResourceSlice(node_name=f"node-{i:04d}", device_class="gpu", count=2)
            for i in range(8)
        ]
        dclaims = [
            t.ResourceClaim(name=f"gclaim-{i}", device_class="gpu", count=1)
            for i in range(6)
        ]
        vol_pending = []
        for i in range(6):
            vol_pending.append(
                make_pod(f"vb-{i}").req({"cpu": "200m"}).pvc_volume(f"bpvc-{i}").obj()
            )
        for i in range(4):
            vol_pending.append(
                make_pod(f"vw-{i}").req({"cpu": "200m"}).pvc_volume(f"wpvc-{i}").obj()
            )
        for i in range(4):
            # ssd affinity makes the CSI attach limit BITE (only the ssd
            # nodes carry CSINode records).
            vol_pending.append(
                make_pod(f"vd-{i}").req({"cpu": "200m"})
                .node_affinity_in("disk", ["ssd"])
                .pvc_volume(f"dpvc-{i}").obj()
            )
        # rw-a gets priority so it pops (and commits) in an EARLIER batch
        # than rw-b: featurization is batch-wide, so the loser must be
        # featurized after the winner's PreBind bumped the RWOP use count.
        vol_pending.append(
            make_pod("rw-a").req({"cpu": "100m"}).priority(5)
            .pvc_volume("rwop-claim").obj()
        )
        vol_pending.append(
            make_pod("rw-b").req({"cpu": "100m"}).pvc_volume("rwop-claim").obj()
        )
        for i in range(6):
            vol_pending.append(
                make_pod(f"dra-{i}").req({"cpu": "100m"})
                .resource_claim(f"gclaim-{i}").obj()
            )
        vol_pending.append(
            make_pod("dra-missing").req({"cpu": "100m"})
            .resource_claim("no-such-claim").obj()
        )
        gated = [
            make_pod(f"gated-{i}").req({"cpu": "100m"})
            .scheduling_gate("example.com/hold").obj()
            for i in range(2)
        ]
        # Volume/DRA preemption theater (r5): nodes feasible ONLY via a
        # volume/DRA victim, with a same-priority bystander that must
        # REPRIEVE — pins the what-if's released volume/DRA tensors (the
        # old evict-all route would take the bystander too).
        nodes.append(
            make_node("volpre-0")
            .capacity({"cpu": "64", "memory": "64Gi", "pods": 64})
            .zone("zone-0").region("r1").label("pool", "volpre").obj()
        )
        bound.append(
            make_pod("vpre-holder").req({"cpu": "500m"}).priority(1)
            .label("kind", "holder").start_time(300.0)
            .device_volume("shared-disk-0").node("volpre-0").obj()
        )
        bound.append(
            make_pod("vpre-bystander").req({"cpu": "500m"}).priority(1)
            .label("kind", "bystander").start_time(301.0)
            .node("volpre-0").obj()
        )
        vol_pending.append(
            make_pod("vip-vol").req({"cpu": "500m"}).priority(50)
            .node_affinity_in("pool", ["volpre"])
            .device_volume("shared-disk-0").obj()
        )
        nodes.append(
            make_node("drapre-0")
            .capacity({"cpu": "64", "memory": "64Gi", "pods": 64})
            .zone("zone-1").region("r1").label("pool", "drapre").obj()
        )
        slices.append(
            t.ResourceSlice(node_name="drapre-0", device_class="pgpu", count=1)
        )
        held = t.ResourceClaim(
            name="dheld", device_class="pgpu", count=1,
            allocated_node="drapre-0",
            reserved_for=("default/dpre-holder",),
        )
        dclaims.append(held)
        dclaims.append(t.ResourceClaim(name="dwant", device_class="pgpu", count=1))
        bound.append(
            make_pod("dpre-holder").req({"cpu": "500m"}).priority(1)
            .label("kind", "holder").start_time(302.0)
            .resource_claim("dheld").node("drapre-0").obj()
        )
        bound.append(
            make_pod("dpre-bystander").req({"cpu": "500m"}).priority(1)
            .label("kind", "bystander").start_time(303.0)
            .node("drapre-0").obj()
        )
        vol_pending.append(
            make_pod("vip-dra").req({"cpu": "500m"}).priority(50)
            .node_affinity_in("pool", ["drapre"])
            .resource_claim("dwant").obj()
        )
        pending = pending + vol_pending + gated
        objects = dict(
            classes=classes, pvs=pvs, pvcs=pvcs, csinodes=csinodes,
            slices=slices, dclaims=dclaims,
            gated_uids={p.uid for p in gated},
        )
    return nodes, bound, pending, pdbs, objects
