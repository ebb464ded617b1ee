"""Volume catalog: host-side PV/PVC/StorageClass/CSINode state + binding.

The host half of the volume plugins (reference:
plugins/volumebinding/binder.go FindPodVolumes/AssumePodVolumes,
volumezone, nodevolumelimits).  String/object matching stays on the host;
the device ops consume compiled requirement programs and per-node count
tensors produced from this catalog.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from .api import types as t

# Zone/region label keys a PV may carry (volumezone/volume_zone.go
# topologyLabels; both GA and legacy beta names).
ZONE_KEYS = (
    "topology.kubernetes.io/zone",
    "failure-domain.beta.kubernetes.io/zone",
)
REGION_KEYS = (
    "topology.kubernetes.io/region",
    "failure-domain.beta.kubernetes.io/region",
)

NO_PROVISIONER = "kubernetes.io/no-provisioner"


def claim_uids(pod: t.Pod) -> list[str]:
    """The uid (namespace/name) of the claim behind each of the pod's
    volumes that names one, in volume order, repeats included."""
    ns = pod.namespace
    return [f"{ns}/{v.pvc}" for v in pod.spec.volumes if v.pvc]


@dataclass
class VolumeCatalog:
    pvs: dict[str, t.PersistentVolume] = field(default_factory=dict)
    pvcs: dict[str, t.PersistentVolumeClaim] = field(default_factory=dict)
    classes: dict[str, t.StorageClass] = field(default_factory=dict)
    csinodes: dict[str, t.CSINode] = field(default_factory=dict)
    # PVC uid → number of pods using it (for ReadWriteOncePod conflicts,
    # volumerestrictions/volume_restrictions.go).
    pvc_users: dict[str, int] = field(default_factory=dict)
    # Bumped on every catalog mutation; featurization caches key on it so a
    # PV/PVC/class change invalidates cached pod features.
    epoch: int = 0
    # storage class → {pv name: pv} of UNBOUND static PVs: candidates_for
    # was an O(all PVs) scan per call (~2s of a 5k-pod CSI workload);
    # maintained at exactly the claim_ref mutation sites.  Also the
    # chunk-conflict gate (class_has_static_candidates): only a finite PV
    # pool makes same-batch PreBinds race.
    unbound: dict[str, dict[str, "t.PersistentVolume"]] = field(
        default_factory=dict
    )
    # WFFC dynamic provisioning mode.  "sync" models an instantaneous
    # provisioner (the PreBind creates the PV in-process — the round-3
    # behavior, right for self-contained benchmarks).  "wait" mirrors the
    # reference (volume_binding.go:521 BindPodVolumes): PreBind writes a
    # provisioning INTENT (the volume.kubernetes.io/selected-node
    # annotation trigger) and the bind completes only when the external
    # provisioner's PV arrives via add_pv, or times out and unreserves.
    wffc_provisioning: str = "sync"
    # pvc uid → selected node name, while a provisioning intent is open.
    provisioning: dict[str, str] = field(default_factory=dict)

    # -- object events -------------------------------------------------------

    def add_pv(self, pv: t.PersistentVolume) -> list[str]:
        """Upsert a PV (informer).  Returns the uids of PVCs whose open
        provisioning intent this PV fulfils (the provisioner created the
        volume pre-bound via claimRef) — the scheduler completes their
        waiting PreBinds."""
        old = self.pvs.get(pv.name)
        if old is not None and not old.claim_ref:
            self.unbound.get(old.storage_class, {}).pop(old.name, None)
        self.pvs[pv.name] = pv
        if not pv.claim_ref:
            self.unbound.setdefault(pv.storage_class, {})[pv.name] = pv
        self.epoch += 1
        fulfilled: list[str] = []
        if pv.claim_ref and pv.claim_ref in self.provisioning:
            pvc = self.pvcs.get(pv.claim_ref)
            if pvc is not None and not pvc.volume_name:
                pvc.volume_name = pv.name
                del self.provisioning[pv.claim_ref]
                fulfilled.append(pvc.uid)
        return fulfilled

    def class_has_static_candidates(self, storage_class: str) -> bool:
        """Any unclaimed static PV in this class?  (Chunk-conflict gate:
        only a finite PV pool makes same-batch PreBinds race.)"""
        return bool(self.unbound.get(storage_class))

    def add_pvc(self, pvc: t.PersistentVolumeClaim) -> None:
        self.pvcs[pvc.uid] = pvc
        self.epoch += 1

    def add_class(self, sc: t.StorageClass) -> None:
        self.classes[sc.name] = sc
        self.epoch += 1

    def add_csinode(self, csinode: t.CSINode) -> None:
        self.csinodes[csinode.name] = csinode
        self.epoch += 1

    def adjust_pvc_users(self, pvc_uids: list[str], delta: int) -> None:
        """A pod using these claims was assumed (+1) or left (-1).  What
        featurization reads of the count is whether a ReadWriteOncePod
        claim has a user (VolumeRestrictions), so only such a claim's (or
        an unknown one's) count is a catalog mutation: a batch of pods
        with claims of other modes commits without dropping every cached
        feature row behind it."""
        for uid in pvc_uids:
            left = self.pvc_users.get(uid, 0) + delta
            if left:
                self.pvc_users[uid] = left
            else:
                self.pvc_users.pop(uid, None)
            pvc = self.pvcs.get(uid)
            if pvc is None or t.RWOP in pvc.access_modes:
                self.epoch += 1

    def claim_featsig(self, uid: str) -> tuple | None:
        """What featurization reads of a claim apart from its name, where
        that is the same for every claim like it, else None (the claim is
        featurized by itself).  Pods that differ only in the names of such
        claims share one featurization (engine/features.py): a bound claim
        whose volume has no node affinity and no zone labels gives every
        volume op the same answer whatever it is called."""
        pvc = self.pvcs.get(uid)
        if pvc is None:
            return ("missing",)
        if not pvc.volume_name:
            return None  # candidates and provisioner topology: its own
        pv = self.pvs.get(pvc.volume_name)
        if pv is None:
            return ("lost",)
        if (pv.node_affinity is not None and pv.node_affinity.terms) or pv.labels:
            return None  # PV topology compiles into the pod's programs
        busy = t.RWOP in pvc.access_modes and self.pvc_users.get(uid, 0) > 0
        return ("bound", pv.csi_driver, busy)

    # -- pod classification --------------------------------------------------

    def pod_pvcs(self, pod: t.Pod) -> list[t.PersistentVolumeClaim | None]:
        """The pod's claims (None for dangling references)."""
        out = []
        for vol in pod.spec.volumes:
            if vol.pvc:
                out.append(self.pvcs.get(f"{pod.namespace}/{vol.pvc}"))
        return out

    def classify(self, pvc: t.PersistentVolumeClaim):
        """→ ("bound", pv) | ("delayed", candidates, sc) |
        ("unbound_immediate", None) | ("lost", None).

        Mirrors volume_binding.go: bound claims resolve their PV; unbound
        claims with a WaitForFirstConsumer class bind at schedule time
        (candidates = matching unbound PVs, dynamic provisioning as
        fallback); unbound Immediate claims are UnschedulableAndUnresolvable
        until the PV controller binds them."""
        if pvc.volume_name:
            pv = self.pvs.get(pvc.volume_name)
            return ("bound", pv) if pv is not None else ("lost", None)
        sc = self.classes.get(pvc.storage_class)
        if sc is not None and sc.binding_mode == t.BINDING_WAIT_FOR_FIRST_CONSUMER:
            return ("delayed", self.candidates_for(pvc), sc)
        return ("unbound_immediate", None)

    def candidates_for(self, pvc: t.PersistentVolumeClaim) -> list[t.PersistentVolume]:
        """Static PVs this claim could bind (class, access modes, size —
        volumebinding's PV matching, persistentvolume/util.go FindMatchingVolume)."""
        out = []
        for pv in self.unbound.get(pvc.storage_class, {}).values():
            if not set(pvc.access_modes) <= set(pv.access_modes):
                continue
            if pv.capacity < pvc.request:
                continue
            out.append(pv)
        return out

    def pvc_driver(self, pvc: t.PersistentVolumeClaim) -> str:
        """CSI driver for attach-limit counting (nodevolumelimits/csi.go):
        bound → PV's driver; unbound → the class's provisioner."""
        if pvc.volume_name:
            pv = self.pvs.get(pvc.volume_name)
            if pv is not None and pv.csi_driver:
                return pv.csi_driver
            return ""
        sc = self.classes.get(pvc.storage_class)
        if sc is not None and sc.provisioner != NO_PROVISIONER:
            return sc.provisioner
        return ""

    # -- zone requirements (VolumeZone) -------------------------------------

    @staticmethod
    def zone_requirements(pv: t.PersistentVolume) -> list[t.NodeSelectorRequirement]:
        """A bound PV's zone/region labels as node requirements.  Label
        values may be ``__``-separated sets (volumehelpers.LabelZonesToSet)."""
        reqs = []
        for key in ZONE_KEYS + REGION_KEYS:
            v = pv.labels.get(key)
            if v is not None:
                reqs.append(
                    t.NodeSelectorRequirement(key, t.OP_IN, tuple(v.split("__")))
                )
        return reqs

    # -- bind (the PreBind step) --------------------------------------------

    def bind_pod_volumes(self, pod: t.Pod, node: t.Node) -> list | None:
        """Bind the pod's delayed claims on the chosen node (the in-process
        analog of volumebinding PreBind, volume_binding.go:521).  Returns
        None when a claim can no longer be satisfied there (a same-batch
        race lost) — the caller forgets the pod (assume/forget protocol) —
        else a list of undo records for ``unbind_pod_volumes`` (a gang whose
        Permit admission later collapses must revert its members' binds)."""
        chosen: list[tuple[t.PersistentVolumeClaim, t.PersistentVolume | None]] = []
        own_refs: dict[str, int] = {}
        for vol in pod.spec.volumes:
            if vol.pvc:
                uid = f"{pod.namespace}/{vol.pvc}"
                own_refs[uid] = own_refs.get(uid, 0) + 1
        for pvc in self.pod_pvcs(pod):
            if pvc is None:
                return None
            # Re-check ReadWriteOncePod here: a same-batch peer may have
            # assumed the claim after this pod was featurized (the pod's own
            # assume already counted its references).
            if t.RWOP in pvc.access_modes:
                others = self.pvc_users.get(pvc.uid, 0) - own_refs.get(pvc.uid, 0)
                if others > 0:
                    return None
            kind, *_rest = self.classify(pvc)
            if kind in ("bound",):
                continue
            if kind in ("lost", "unbound_immediate"):
                return None
            sc = self.classes.get(pvc.storage_class)
            cands = [
                pv
                for pv in self.candidates_for(pvc)
                if t.node_selector_matches(
                    pv.node_affinity, node.metadata.labels, node.name
                )
            ]
            if cands:
                # Smallest satisfying PV (FindMatchingVolume picks the
                # smallest that fits).
                pv = min(cands, key=lambda p: p.capacity)
                chosen.append((pvc, pv))
            elif sc is not None and sc.provisioner != NO_PROVISIONER:
                ok = sc.allowed_topologies is None or t.node_selector_matches(
                    sc.allowed_topologies, node.metadata.labels, node.name
                )
                if not ok:
                    return None
                chosen.append((pvc, None))  # dynamically provisioned
            else:
                return None
        undo: list[tuple[str, t.PersistentVolumeClaim, str]] = []
        for pvc, pv in chosen:
            if pv is None:
                if self.wffc_provisioning == "wait":
                    # The provisioning trigger (AssumePodVolumes + the
                    # selected-node annotation): the claim stays unbound
                    # until the provisioner's PV lands (add_pv) or the
                    # PreBind wait times out.
                    self.provisioning[pvc.uid] = node.name
                    self.epoch += 1
                    undo.append(("intent", pvc, node.name))
                    continue
                name = f"provisioned-{pvc.namespace}-{pvc.name}"
                self.add_pv(
                    t.PersistentVolume(
                        name=name,
                        capacity=pvc.request,
                        access_modes=pvc.access_modes,
                        storage_class=pvc.storage_class,
                        claim_ref=pvc.uid,
                        csi_driver=self.pvc_driver(pvc),
                    )
                )
                pvc.volume_name = name
                undo.append(("provisioned", pvc, name))
            else:
                pv.claim_ref = pvc.uid
                pvc.volume_name = pv.name
                self.unbound.get(pv.storage_class, {}).pop(pv.name, None)
                self.epoch += 1
                undo.append(("static", pvc, pv.name))
        return undo

    def unbind_pod_volumes(self, undo: list) -> None:
        """Revert a bind_pod_volumes (gang Permit collapse after PreBind):
        release static PVs, delete phantom provisioned PVs."""
        for kind, pvc, pv_name in undo:
            if kind == "intent":
                # Withdraw the provisioning trigger; a PV the provisioner
                # already delivered stays in the catalog (the claim keeps
                # its binding — rebinding elsewhere later is a no-op race
                # the classify() bound path resolves).
                if not pvc.volume_name:
                    self.provisioning.pop(pvc.uid, None)
                continue
            pvc.volume_name = ""
            if kind == "provisioned":
                self.pvs.pop(pv_name, None)
            else:
                pv = self.pvs.get(pv_name)
                if pv is not None:
                    pv.claim_ref = None
                    self.unbound.setdefault(pv.storage_class, {})[pv.name] = pv
        if undo:
            self.epoch += 1
