"""Device tensor schema and the host→device snapshot engine.

This is the TPU-native replacement for the reference's `NodeInfo` aggregation
(pkg/scheduler/framework/types.go:714) and incremental `Cache.UpdateSnapshot`
(pkg/scheduler/backend/cache/cache.go:186).  Where the reference keeps one Go
struct per node and copies changed nodes into a per-cycle `Snapshot`, we keep
the whole cluster as a struct-of-arrays (one row per node, padded to a bucketed
capacity) mirrored between host numpy staging arrays and device HBM:

  * Host-driven changes (node add/update/remove, pod delete, informer events)
    dirty individual rows; `flush()` ships only dirty rows via a jitted row
    scatter — the analog of the generation-diff copy in UpdateSnapshot.
  * Device-driven changes (the engine's scan commits a pod per step) already
    live on device; the host applies the same deltas to its staging arrays
    after each batch so the mirrors stay equal without re-upload.

All shapes are static under jit; capacities grow in buckets (powers of two) so
shape changes — and hence XLA recompiles — are logarithmic in cluster growth.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .api import types as t
from .intern import InternTable
from .volumes import claim_uids

# Sentinel for "label value is not an integer" (Gt/Lt operators).
INT_SENTINEL = np.int64(-(2**62))

# Host-port slots per pod in the batch features.  The reference has no limit,
# but the device commit needs a static shape; >8 distinct host ports on one
# pod is pathological, and such pods are rejected at delta time.
POD_PORT_SLOTS = 8

# Fixed resource columns; scalar/extended resources are interned after these.
RES_CPU, RES_MEMORY, RES_EPHEMERAL = 0, 1, 2
FIXED_RESOURCES = (t.CPU, t.MEMORY, t.EPHEMERAL_STORAGE)


def _bucket(n: int, floor: int = 8) -> int:
    """Smallest power-of-two capacity ≥ n (min floor)."""
    c = floor
    while c < n:
        c *= 2
    return c


@dataclass(frozen=True)
class Schema:
    """Static capacities of the device tensors (jit shape parameters)."""

    N: int = 64  # node rows
    R: int = 4  # resource columns (fixed 3 + scalars)
    LS: int = 16  # label slots per node
    TS: int = 8  # taint slots per node
    TV: int = 8  # taint vocabulary size (pod intolerable-taint bitmasks)
    TK: int = 4  # topology-key slots
    DV: int = 8  # max domain (topology-value) vocabulary across topo keys
    G: int = 8  # pod label-group rows
    ET: int = 8  # existing-pod (anti-)affinity term rows
    VD: int = 8  # in-tree device-volume vocabulary rows
    DR: int = 8  # CSI driver vocabulary rows
    CV: int = 8  # rows for CSI claims that several known pods SHARE (not one a claim)
    DC: int = 4  # DRA device-class vocabulary rows
    CLM: int = 8  # DRA claim vocabulary rows
    P: int = 8  # host-port (proto,ip,port) triple rows
    PK: int = 8  # host-port (proto,port) key rows
    IM: int = 8  # image slots per node

    def grown(self, **mins: int) -> "Schema":
        """Return a schema with each named capacity grown to cover its min."""
        changes = {}
        for name, need in mins.items():
            cur = getattr(self, name)
            if need > cur:
                changes[name] = _bucket(need, cur)
        return dataclasses.replace(self, **changes) if changes else self


@jax.tree_util.register_dataclass
@dataclass
class ClusterState:
    """The device-resident cluster: one row per node (axis sized Schema.N).

    This is the tensorized `NodeInfo` (types.go:714): Allocatable/Requested
    become (N, R) int64 matrices, labels/taints become interned id slots,
    affinity bookkeeping becomes per-group and per-term count matrices.
    """

    # Row occupancy & scalars -------------------------------------------------
    valid: jax.Array  # (N,) bool — row holds a live node
    name_id: jax.Array  # (N,) i32 — interned node name (NodeName plugin)
    unschedulable: jax.Array  # (N,) bool — node.Spec.Unschedulable
    num_pods: jax.Array  # (N,) i32 — len(NodeInfo.Pods)
    allowed_pods: jax.Array  # (N,) i32 — Allocatable.AllowedPodNumber

    # Resources ---------------------------------------------------------------
    alloc: jax.Array  # (N, R) i64 — NodeInfo.Allocatable
    req: jax.Array  # (N, R) i64 — NodeInfo.Requested
    nonzero_req: jax.Array  # (N, 2) i64 — NodeInfo.NonZeroRequested (cpu, mem)

    # Labels (node affinity / selectors) --------------------------------------
    label_key_ids: jax.Array  # (N, LS) i32, -1 pad
    label_pair_ids: jax.Array  # (N, LS) i32, -1 pad
    label_int_vals: jax.Array  # (N, LS) i64, INT_SENTINEL if not integral

    # Topology ----------------------------------------------------------------
    topo_vals: jax.Array  # (N, TK) i32 — per topo-key-slot value id, -1 missing

    # Taints ------------------------------------------------------------------
    taint_ids: jax.Array  # (N, TS) i32, -1 pad

    # Host ports --------------------------------------------------------------
    port_counts: jax.Array  # (P, N) i32 — pods using exact (proto,ip,port)
    portkey_counts: jax.Array  # (PK, N) i32 — pods using (proto,port) any ip

    # Affinity bookkeeping ----------------------------------------------------
    group_counts: jax.Array  # (G, N) i32 — pods of label-group g on node n
    et_counts: jax.Array  # (ET, N) i32 — pods carrying interned term e

    # Volumes -----------------------------------------------------------------
    dev_counts: jax.Array  # (VD, N) i32 — pods using in-tree device d
    dev_rw_counts: jax.Array  # (VD, N) i32 — non-read-only uses of device d
    csi_used: jax.Array  # (DR, N) i32 — DISTINCT attached volumes per driver
    csi_limit: jax.Array  # (DR, N) i32 — CSINode allocatable count (default inf)
    # (CV, N) i32 — pods on node using SHARED claim v.  A claim one known
    # pod uses has no row: it is one of csi_used, nothing more.
    csivol_counts: jax.Array
    dra_cap: jax.Array  # (DC, N) i32 — devices published per class (ResourceSlices)
    dra_alloc: jax.Array  # (DC, N) i32 — devices consumed by DISTINCT claims
    dra_claim_counts: jax.Array  # (CLM, N) i32 — pods on node reserving claim c

    # Images ------------------------------------------------------------------
    image_ids: jax.Array  # (N, IM) i32, -1 pad
    image_sizes: jax.Array  # (N, IM) i64 — size of image at same slot


# Field → which axis indexes nodes (0 = leading, 1 = trailing).
_NODE_AXIS: dict[str, int] = {
    "valid": 0,
    "name_id": 0,
    "unschedulable": 0,
    "num_pods": 0,
    "allowed_pods": 0,
    "alloc": 0,
    "req": 0,
    "nonzero_req": 0,
    "label_key_ids": 0,
    "label_pair_ids": 0,
    "label_int_vals": 0,
    "topo_vals": 0,
    "taint_ids": 0,
    "port_counts": 1,
    "portkey_counts": 1,
    "group_counts": 1,
    "et_counts": 1,
    "dev_counts": 1,
    "dev_rw_counts": 1,
    "csi_used": 1,
    "csi_limit": 1,
    "csivol_counts": 1,
    "dra_cap": 1,
    "dra_alloc": 1,
    "dra_claim_counts": 1,
    "image_ids": 0,
    "image_sizes": 0,
}


def _host_arrays(s: Schema) -> dict[str, np.ndarray]:
    return {
        "valid": np.zeros(s.N, np.bool_),
        "name_id": np.full(s.N, -1, np.int32),
        "unschedulable": np.zeros(s.N, np.bool_),
        "num_pods": np.zeros(s.N, np.int32),
        "allowed_pods": np.zeros(s.N, np.int32),
        "alloc": np.zeros((s.N, s.R), np.int64),
        "req": np.zeros((s.N, s.R), np.int64),
        "nonzero_req": np.zeros((s.N, 2), np.int64),
        "label_key_ids": np.full((s.N, s.LS), -1, np.int32),
        "label_pair_ids": np.full((s.N, s.LS), -1, np.int32),
        "label_int_vals": np.full((s.N, s.LS), INT_SENTINEL, np.int64),
        "topo_vals": np.full((s.N, s.TK), -1, np.int32),
        "taint_ids": np.full((s.N, s.TS), -1, np.int32),
        "port_counts": np.zeros((s.P, s.N), np.int32),
        "portkey_counts": np.zeros((s.PK, s.N), np.int32),
        "group_counts": np.zeros((s.G, s.N), np.int32),
        "et_counts": np.zeros((s.ET, s.N), np.int32),
        "dev_counts": np.zeros((s.VD, s.N), np.int32),
        "dev_rw_counts": np.zeros((s.VD, s.N), np.int32),
        "csi_used": np.zeros((s.DR, s.N), np.int32),
        "csi_limit": np.full((s.DR, s.N), 2**31 - 1, np.int32),
        "csivol_counts": np.zeros((s.CV, s.N), np.int32),
        "dra_cap": np.zeros((s.DC, s.N), np.int32),
        "dra_alloc": np.zeros((s.DC, s.N), np.int32),
        "dra_claim_counts": np.zeros((s.CLM, s.N), np.int32),
        "image_ids": np.full((s.N, s.IM), -1, np.int32),
        "image_sizes": np.zeros((s.N, s.IM), np.int64),
    }


def parse_label_int(v: str) -> int:
    """Value of a label as int for Gt/Lt, or INT_SENTINEL."""
    try:
        return int(v)
    except ValueError:
        return int(INT_SENTINEL)


class _DirtyRows(set):
    """Dirty-row set that bumps the builder's mutation epoch on add — the
    one funnel every host-side row mutation already goes through."""

    def __init__(self, builder: "SnapshotBuilder"):
        super().__init__()
        self._builder = builder

    def add(self, row: int) -> None:
        self._builder.mutation_epoch += 1
        super().add(row)


class SnapshotBuilder:
    """Owns the host staging arrays, the intern table, and the device mirror.

    The scheduler's cache calls ``set_node_row`` / ``clear_node_row`` /
    ``apply_pod_delta`` as cluster events arrive; the engine calls ``state()``
    before each device pass to get an up-to-date ClusterState (flushing dirty
    rows), and ``absorb_device_state`` after the pass to adopt the
    scan-committed tensors as the new device truth.
    """

    def __init__(self, interns: InternTable | None = None, schema: Schema | None = None):
        self.interns = interns or InternTable()
        self.schema = schema or Schema()
        # Vectorized selector↔group matching + the incremental (ET, G)
        # term↔group match matrix — the featurization hot path (replaces
        # per-pod Python loops over every interned term/group).
        from .intern import GroupIndex, TermIndex

        self.group_index = GroupIndex(self.interns)
        # Namespace → labels, for namespaceSelector matching in affinity terms
        # (the analog of the scheduler's namespace lister snapshot,
        # interpodaffinity/plugin.go GetNamespaceLabelsSnapshot).  Update via
        # set_namespace_labels (bumps ns_epoch for the featurization cache).
        self.namespace_labels: dict[str, dict[str, str]] = {}
        self.ns_epoch = 0
        # Feature gates snapshot (plugins/registry.go:49 snapshots gates
        # into plfeature.Features for plugin constructors); the scheduler
        # stamps its gates here so featurizers see them via
        # FeaturizeContext.gates.  None → defaults.
        self.feature_gates = None
        self.term_index = TermIndex(
            self.interns, self.group_index, self.namespace_labels
        )
        # Optional multi-chip mesh: node axis sharded, everything else
        # replicated (parallel/mesh.py).
        self.mesh = None
        # Host-side volume objects (PV/PVC/StorageClass/CSINode).
        from .volumes import VolumeCatalog

        self.volumes = VolumeCatalog()
        # Host-side DRA objects (ResourceClaims/ResourceSlices).
        from .dra import ClaimCatalog

        self.dra = ClaimCatalog()
        self.host = _host_arrays(self.schema)
        self._device: ClusterState | None = None
        # Monotonic host-mutation counter: bumps on EVERY dirtying host
        # write (row dirtied or full-rebuild flagged) — the validity token
        # for derived device-side caches (the scheduler's carried DomTables
        # key on (schema, mutation_epoch): any host mutation since the
        # carry was stashed forces a rebuild).  Bumped centrally by the
        # _DirtyRows set and the _dirty_all property so a future mutation
        # site cannot forget it.
        self.mutation_epoch = 0
        self._dirty_rows: _DirtyRows = _DirtyRows(self)
        self._dirty_all = True  # device needs a full (re)build
        # Resource-name → column index (fixed columns pre-assigned).
        self.res_col: dict[str, int] = {r: i for i, r in enumerate(FIXED_RESOURCES)}
        # Featurization cache (engine/features.py): version token → per-pod
        # feature/delta entries valid only while no vocabulary/schema grows.
        self.feat_cache: tuple[tuple, dict, list] | None = None
        # Inputs of the pass that stay on the device between dispatches
        # (scheduler._pass_inputs): by slot, device arrays and the key they
        # were made for.  Part of the device mirror: dropped wherever the
        # mirror is.
        self.resident: dict = {}
        # CSI attach budget (NodeVolumeLimits).  The limit is over DISTINCT
        # volumes a node (nodevolumelimits/csi.go), and a PV carries one
        # claim_ref, so pods reach one volume only through one claim.  A
        # claim that ONE known pod references (pending, in flight or bound)
        # is therefore just one of csi_used[driver, node] while its pod is
        # there: no table, upload or jit shape grows with such claims.
        # Only a claim that TWO OR MORE known pods reference holds a row
        # of csivol_counts, so that a volume already attached to a node is
        # not counted twice there.  Rows follow the users lazily
        # (settle_csi_claims), never while a pass is in flight.
        #   csi_users: claim uid -> its one known pod's uid, or the set of
        #     them (scheduler._note_claims keeps it; a bare string for the
        #     one user, so that a claim of a pod's own costs no container);
        #   csi_rows: shared claim uid -> row; csi_unsettled: claims whose
        #     row does not follow their users yet; csi_epoch: bumped by
        #     every promotion and release (feature_version).
        # Where a claim's applied users are is not kept here: a promotion
        # is rare and reads it off the cache's pod records.
        self.csi_users: dict[str, str | set] = {}
        self.csi_rows: dict[str, int] = {}
        self._csi_free_rows: list[int] = []
        self.csi_unsettled: set[str] = set()
        self.csi_epoch = 0

    @property
    def _dirty_all(self) -> bool:
        return self._dirty_all_flag

    @_dirty_all.setter
    def _dirty_all(self, value: bool) -> None:
        # Setting (not clearing) the full-rebuild flag is a host mutation:
        # bump the epoch so derived device caches (carried DomTables)
        # invalidate.  Clearing happens only in state() after the flush.
        if value:
            self.mutation_epoch += 1
        self._dirty_all_flag = value

    # -- capacity management -------------------------------------------------

    def _ensure(self, **mins: int) -> None:
        grown = self.schema.grown(**mins)
        if grown is self.schema:
            return
        old, olds = self.host, self.schema
        self.schema = grown
        self.host = _host_arrays(grown)
        for k, a in old.items():
            sl = tuple(slice(0, d) for d in a.shape)
            self.host[k][sl] = a
        del olds
        self._dirty_all = True

    def resource_column(self, name: str) -> int:
        col = self.res_col.get(name)
        if col is None:
            col = len(self.res_col)
            self._ensure(R=col + 1)
            self.res_col[name] = col
        return col

    # -- node rows -------------------------------------------------------------

    def set_node_row(self, row: int, node: t.Node) -> None:
        """(Re)write a node's static attributes into its row. Pod-derived
        state (req, counts) is managed separately via apply_pod_delta."""
        it = self.interns
        labels = node.metadata.labels
        self._ensure(
            N=row + 1,
            LS=len(labels),
            TS=len(node.spec.taints),
            IM=sum(len(img.names) for img in node.status.images),
        )
        # Pre-intern all resource columns so R is final before writing.
        for rname in node.status.allocatable:
            if rname != t.PODS:
                self.resource_column(rname)
        h = self.host
        h["valid"][row] = True
        h["name_id"][row] = it.node_names.id(node.name)
        h["unschedulable"][row] = node.spec.unschedulable
        h["allowed_pods"][row] = node.status.allocatable.get(t.PODS, 110)
        h["alloc"][row] = 0
        for rname, v in node.status.allocatable.items():
            if rname == t.PODS:
                continue
            h["alloc"][row, self.resource_column(rname)] = v
        # Labels.
        h["label_key_ids"][row] = -1
        h["label_pair_ids"][row] = -1
        h["label_int_vals"][row] = INT_SENTINEL
        for i, (k, v) in enumerate(labels.items()):
            h["label_key_ids"][row, i] = it.label_keys.id(k)
            h["label_pair_ids"][row, i] = it.label_pairs.id((k, v))
            h["label_int_vals"][row, i] = parse_label_int(v)
        # Topology: every label key is a potential topology key; we only
        # materialize keys something has referenced (lazily via featurize), but
        # hostname/zone/region are always hot, so intern any key already known.
        h["topo_vals"][row] = -1
        for k, v in labels.items():
            if k in it.topo_keys:
                slot = it.topo_key_slot(k)
                if slot < self.schema.TK:
                    h["topo_vals"][row, slot] = it.topo_value_id(k, v)
        # Taints.
        h["taint_ids"][row] = -1
        for i, taint in enumerate(node.spec.taints):
            h["taint_ids"][row, i] = it.taints.id((taint.key, taint.value, taint.effect))
        # Images: one slot per (image, name) alias so lookups by any CRI name
        # hit (NodeInfo.ImageStates is keyed by every name, types.go).
        h["image_ids"][row] = -1
        h["image_sizes"][row] = 0
        slot = 0
        for img in node.status.images:
            for alias in img.names:
                h["image_ids"][row, slot] = it.images.id(alias)
                h["image_sizes"][row, slot] = img.size_bytes
                slot += 1
        # Last: growth swaps self.host for fresh copies, so every write via
        # the local alias above must land before it.
        self._ensure(DV=it.max_topo_vocab())
        self._dirty_rows.add(row)

    def set_dra_cap(self, row: int, node_name: str, device_class: str) -> None:
        """Refresh a node row's device-count columns for one class — the
        bare-class pool AND every selector pool of the class — from the
        claim catalog (ResourceSlice informer)."""
        self.dra.ensure_pool(device_class, ())
        for sig in self.dra.pools_by_class.get(device_class, ()):
            self.set_pool_cap(row, node_name, sig)

    def set_pool_cap(self, row: int, node_name: str, sig: str) -> None:
        """One pool's cap column for one node (new-pool backfill path)."""
        cid = self.interns.device_classes.id(sig)
        self._ensure(DC=cid + 1)
        self.host["dra_cap"][cid, row] = self.dra.pool_cap(node_name, sig)
        self._dirty_rows.add(row)

    def apply_dra_correction(self, row: int, charges, sign: int) -> None:
        """Pool-overlap correction charges (ClaimCatalog.corr_events): a
        direct dra_alloc adjustment outside the claim-transition system —
        applied once at allocation, reversed once at deallocation."""
        cids = [
            (self.interns.device_classes.id(sig), cnt) for sig, cnt in charges
        ]
        self._ensure(DC=max((c for c, _ in cids), default=-1) + 1)
        for cid, cnt in cids:
            self.host["dra_alloc"][cid, row] += sign * cnt
        self._dirty_rows.add(row)

    def set_pool_alloc(self, row: int, sig: str, value: int) -> None:
        """New-pool alloc backfill: owned devices matching a pool that was
        registered after their allocation."""
        cid = self.interns.device_classes.id(sig)
        self._ensure(DC=cid + 1)
        self.host["dra_alloc"][cid, row] = value
        self._dirty_rows.add(row)

    def apply_external_claim(
        self, row: int, claim_uid: str, charges, sign: int
    ) -> None:
        """Charge/release an EXTERNALLY-allocated claim on a node row as a
        PHANTOM reservation: it rides the same per-claim 0↔1 transition
        accounting local reservations use (apply_pod_delta / the in-scan
        commit), so a local pod reserving the same claim sees prev ≥ 1 and
        cannot double-charge the devices — and its later removal (a 2→1
        transition) cannot discharge them either.  ``charges`` lists the
        claim's per-request (pool sig, count) — the claim count moves once,
        every request pool charges."""
        kid = self.interns.dra_claims.id(claim_uid)
        cids = [
            (self.interns.device_classes.id(sig), cnt) for sig, cnt in charges
        ]
        # Intern + grow BEFORE taking the host alias (_ensure swaps
        # self.host for fresh copies on growth).
        self._ensure(
            CLM=kid + 1, DC=max((c for c, _ in cids), default=-1) + 1
        )
        h = self.host
        prev = h["dra_claim_counts"][kid, row]
        h["dra_claim_counts"][kid, row] = prev + sign
        if (sign > 0 and prev == 0) or (sign < 0 and prev == 1):
            for cid, cnt in cids:
                h["dra_alloc"][cid, row] += sign * cnt
        self._dirty_rows.add(row)

    def set_csinode_limits(self, row: int, csinode) -> None:
        """Apply CSINode.spec.drivers allocatable counts to a node row
        (nodevolumelimits/csi.go reads CSINode for the attach limit)."""
        for driver, limit in csinode.driver_limits.items():
            did = self.interns.drivers.id(driver)
            self._ensure(DR=did + 1)
            self.host["csi_limit"][did, row] = limit
        self._dirty_rows.add(row)

    def ensure_topo_key(self, key: str) -> int:
        """Intern a topology key and backfill topo_vals for existing nodes.
        Returns the key's slot. Called by featurization when a pod references
        a topology key no node row has materialized yet."""
        known = key in self.interns.topo_keys
        slot = self.interns.topo_key_slot(key)
        self._ensure(TK=slot + 1)
        if not known:
            # Backfill: topo value = node's label value for this key.
            pair_col = self.host["label_key_ids"]
            key_id = self.interns.label_keys.get(key)
            if key_id >= 0:
                rows = np.nonzero((pair_col == key_id).any(axis=1))[0]
                for row in rows:
                    s = int(np.nonzero(pair_col[row] == key_id)[0][0])
                    pair = self.interns.label_pairs.value(int(self.host["label_pair_ids"][row, s]))
                    self.host["topo_vals"][row, slot] = self.interns.topo_value_id(key, pair[1])
                    self._dirty_rows.add(row)
            self._ensure(DV=self.interns.max_topo_vocab())
        return slot

    def batch_invariants(self) -> dict[str, np.ndarray]:
        """Batch-invariant device inputs for the engine's DomTables: every
        interned (anti-)affinity term's topology slot and hostname flag.
        These are properties of the term vocabulary, not of any pod — built
        once per batch (after featurization interned new terms, before the
        state flush, since interning a term's topology key can grow TK/DV
        and backfill node rows)."""
        it = self.interns
        self._ensure(ET=max(len(it.terms), 1))
        for tid in range(len(it.terms)):
            self.ensure_topo_key(it.terms.value(tid)[2])
        et_slot = np.zeros(self.schema.ET, np.int32)
        et_host = np.zeros(self.schema.ET, np.bool_)
        for tid in range(len(it.terms)):
            topo_key = it.terms.value(tid)[2]
            et_slot[tid] = it.topo_keys.get(topo_key)
            et_host[tid] = topo_key == it.HOSTNAME_KEY
        return {"et_slot": et_slot, "et_host": et_host}

    def set_namespace_labels(self, namespace: str, labels: dict[str, str]) -> None:
        """Namespace label updates (the namespace informer feeding
        interpodaffinity's namespaceSelector matching).  Mutate ONLY through
        this method: the featurization cache keys on ns_epoch."""
        self.namespace_labels[namespace] = dict(labels)
        self.ns_epoch += 1

    def feature_version(self) -> tuple:
        """Cheap O(#vocabs) token identifying everything pod featurization
        can read besides the pod itself; any change invalidates cached
        features (and drops the prefetched batch).  Called once per
        cache-missing pod — no content hashing.

        Deliberately EXCLUDES vocabularies whose growth cannot change any
        cached feature: node_names / label_keys / label_pairs / ports /
        images / topo value vocabularies are referenced by STABLE ids inside
        compiled requirement programs and delta vectors, never by
        vocabulary-sized arrays.  (Node churn interns a fresh node name +
        hostname value every add — including those here re-featurized every
        batch and killed the prefetch overlap: the r2 mixed-churn laggard.)
        terms/groups stay: ET/G-sized masks AND the batch-ordering
        invariant (engine/features.py) both depend on them; taints stay
        (TV-sized toleration masks)."""
        it = self.interns
        return (
            self.schema,
            len(it.terms),
            len(it.groups),
            len(it.namespaces),
            len(it.taints),
            len(it.devices),
            len(it.drivers),
            len(it.device_classes),
            self.volumes.epoch,
            self.dra.epoch,
            self.ns_epoch,
            self.csi_epoch,
        )

    def clear_node_row(self, row: int) -> None:
        h = self.host
        for k, a in _host_arrays(dataclasses.replace(self.schema, N=1)).items():
            if _NODE_AXIS[k] == 0:
                h[k][row] = a[0]
            else:
                h[k][:, row] = a[:, 0]
        self._dirty_rows.add(row)

    # -- pod deltas ------------------------------------------------------------

    def pod_delta_vectors(self, pod: t.Pod) -> dict:
        """Precompute the row-delta a pod applies when (un)assigned to a node.
        Mirrors NodeInfo.AddPodInfo / RemovePod (types.go:990,1022)."""
        request = pod.resource_request()
        cols = {r: self.resource_column(r) for r in request if r != t.PODS}
        req_vec = np.zeros(self.schema.R, np.int64)
        for rname, col in cols.items():
            req_vec[col] = request[rname]
        cpu, mem = pod.non_zero_request()
        gid = self.interns.group_id(pod.namespace, pod.metadata.labels)
        self._ensure(G=gid + 1)
        # Intern the pod's own (anti-)affinity terms so assigning it bumps
        # et_counts — the state behind InterPodAffinity's
        # existingAntiAffinityCounts and existing-pod score terms
        # (interpodaffinity/filtering.go:155 getExistingAntiAffinityCounts,
        # scoring.go:106-123 processExistingPod).
        own_terms: list[int] = []
        aff = pod.spec.affinity
        if aff is not None:
            pa, paa = aff.pod_affinity, aff.pod_anti_affinity
            for cat, terms in ((0, pa.required if pa else ()), (1, paa.required if paa else ())):
                for term in terms:
                    own_terms.append(self.interns.term_id(cat, 0, term, pod.namespace))
            for cat, wterms in ((2, pa.preferred if pa else ()), (3, paa.preferred if paa else ())):
                for wt in wterms:
                    own_terms.append(self.interns.term_id(cat, wt.weight, wt.term, pod.namespace))
        self._ensure(ET=len(self.interns.terms))
        # Volumes: in-tree device uses, CSI volume attachments, PVC refs.
        # CSI attachments are keyed by CLAIM and deduped within the pod
        # (nodevolumelimits/csi.go:219 — a claim referenced twice, or a
        # volume shared with pods already on the node, attaches once).  A
        # claim of the pod's own carries no identity to the device (slot
        # id -1: one more of csi_used); a shared claim carries its row of
        # csivol_counts, and the presence check against it happens at
        # filter/commit time.
        devices: list[tuple[int, bool]] = []
        pvc_uids: list[str] = []
        csivols: dict[str, int] = {}  # claim uid → driver id (dedup by claim)
        # Any claim whose driver has a finite attach limit somewhere?  Such
        # pods defer behind same-node chunk-mates (shared per-driver budget).
        vol_csi_lim = False
        # Does any claim bind at PreBind (unbound WaitForFirstConsumer)?
        # Only those race against other pods' PreBinds — pods with only
        # BOUND claims never conflict in a chunk (engine _conflict_pairs).
        vol_unbound = False
        for vol in pod.spec.volumes:
            if vol.device_id:
                vid = self.interns.devices.id(vol.device_id)
                devices.append((vid, not vol.read_only))
            if vol.pvc:
                uid = f"{pod.namespace}/{vol.pvc}"
                pvc_uids.append(uid)
                pvc = self.volumes.pvcs.get(uid)
                if pvc is not None and not pvc.volume_name:
                    # Race only over a finite static-PV pool: a class served
                    # purely by a provisioner mints a fresh PV at PreBind —
                    # nothing another pod can steal (volumes.bind_pod_volumes
                    # fails deterministically there, not by race).
                    if self.volumes.class_has_static_candidates(
                        pvc.storage_class
                    ):
                        vol_unbound = True
                if pvc is not None:
                    driver = self.volumes.pvc_driver(pvc)
                    if driver:
                        did = self.interns.drivers.id(driver)
                        # Keyed by claim uid: a PV carries one claim_ref, so
                        # pods share a volume only through a shared PVC — and
                        # the claim key is stable across the unbound→bound
                        # transition (the PV name is not).
                        csivols.setdefault(uid, did)
                        if (
                            did < self.schema.DR
                            and (self.host["csi_limit"][did] < 2**31 - 1).any()
                        ):
                            vol_csi_lim = True
        self._ensure(
            VD=len(self.interns.devices),
            DR=len(self.interns.drivers),
        )
        # DRA claims, deduped by claim and accounted per DISTINCT claim like
        # CSI volumes: dra_alloc moves only on a claim's 0↔1 reservation
        # transition on a node, so the device tensors and the ClaimCatalog
        # (which allocates per claim) can never diverge for shared claims.
        # One SLOT per device REQUEST (structured parameters): slots of the
        # same claim share its id; ``first`` marks the slot that moves the
        # claim count, every slot charges its own selector POOL.  Only
        # UNALLOCATED claims race over the free-device pool (chunk-conflict
        # gate).
        dra_claims: list[tuple[int, int, int, bool, bool]] = []
        if pod.spec.resource_claims:
            seen_claims: set[str] = set()
            for claim in self.dra.pod_claims(pod):
                if claim is None or claim.uid in seen_claims:
                    continue  # missing claims are the op's featurize concern
                seen_claims.add(claim.uid)
                kid = self.interns.dra_claims.id(claim.uid)
                unalloc = not claim.allocated_node
                first = True
                for sig, cnt in self.dra.charge_pools(claim):
                    cid = self.interns.device_classes.id(sig)
                    self._ensure(DC=cid + 1, CLM=kid + 1)
                    dra_claims.append((kid, cid, cnt, unalloc, first))
                    first = False
        host_ports = pod.host_ports()
        if len(host_ports) > POD_PORT_SLOTS:
            raise ValueError(
                f"pod {pod.uid} has {len(host_ports)} host ports (max {POD_PORT_SLOTS})"
            )
        ports = []
        for proto, ip, port in host_ports:
            triple = self.interns.ports.id((proto, ip, port))
            # Intern the wildcard triple too so P covers it (NodePorts' filter
            # gathers it for the specific-IP conflict rule).
            wild = self.interns.ports.id((proto, "0.0.0.0", port))
            pk = self.interns.ports.id((proto, None, port))  # key-level row
            self._ensure(P=max(triple, wild) + 1, PK=pk + 1)
            ports.append((triple, pk))
        return {
            "req": req_vec,
            "nonzero": np.array([cpu, mem], np.int64),
            "group": gid,
            "ports": ports,
            "own_terms": own_terms,
            "devices": devices,
            # (claim uid, driver id) in the pod's volume order.  The claim's
            # row, if it has one, is looked up where the delta is applied
            # (csi_rows), as the device's slot id was where it was featurized.
            "csivols": list(csivols.items()),
            "pvcs": pvc_uids,
            "vol_unbound": vol_unbound,
            "vol_csi_lim": vol_csi_lim,
            "dra_claims": dra_claims,
        }

    def apply_pod_delta(self, row: int, delta: dict, sign: int, device_already: bool) -> None:
        """Apply a pod's delta to host staging.  ``device_already=True`` when
        the device applied the same commit inside the scan (no re-upload).

        The delta may predate later resource-column growth (deltas live in
        PodRecords for the pod's lifetime); re-pad to the current schema."""
        h = self.host
        if delta["req"].shape[0] < self.schema.R:
            delta["req"] = np.pad(delta["req"], (0, self.schema.R - delta["req"].shape[0]))
        h["req"][row] += sign * delta["req"]
        h["nonzero_req"][row] += sign * delta["nonzero"]
        h["num_pods"][row] += sign
        h["group_counts"][delta["group"], row] += sign
        for triple, pk in delta["ports"]:
            h["port_counts"][triple, row] += sign
            h["portkey_counts"][pk, row] += sign
        for tid in delta.get("own_terms", ()):
            h["et_counts"][tid, row] += sign
        for vid, rw in delta.get("devices", ()):
            h["dev_counts"][vid, row] += sign
            if rw:
                h["dev_rw_counts"][vid, row] += sign
        prev_by_kid: dict[int, int] = {}
        for kid, cid, cnt, _unalloc, first in delta.get("dra_claims", ()):
            if first:
                prev_by_kid[kid] = h["dra_claim_counts"][kid, row]
                h["dra_claim_counts"][kid, row] += sign
            prev = prev_by_kid[kid]
            if (sign > 0 and prev == 0) or (sign < 0 and prev == 1):
                h["dra_alloc"][cid, row] += sign * cnt
        for uid, did in delta.get("csivols", ()):
            rid = self.csi_rows.get(uid)
            if rid is None:
                # A claim of the pod's own: one distinct volume, here.
                h["csi_used"][did, row] += sign
            else:
                # Distinct-volume accounting of a shared claim: csi_used
                # counts it where its per-node pod count crosses 0↔1.
                prev = h["csivol_counts"][rid, row]
                h["csivol_counts"][rid, row] = prev + sign
                if (sign > 0 and prev == 0) or (sign < 0 and prev == 1):
                    h["csi_used"][did, row] += sign
        self.volumes.adjust_pvc_users(delta.get("pvcs", []), sign)
        if not device_already:
            self._dirty_rows.add(row)

    # -- CSI claims: counted, or shared with a row ---------------------------

    def _csi_mark(self, uid: str) -> None:
        if isinstance(self.csi_users.get(uid), set) != (uid in self.csi_rows):
            self.csi_unsettled.add(uid)
        else:
            self.csi_unsettled.discard(uid)

    def note_claim_users(self, pod: t.Pod, sign: int) -> None:
        """A pod the scheduler knows (pending, in flight or bound) came
        (+1) or went (-1): keep csi_users.  Idempotent by pod uid, and
        wrong only on the safe side: a user never noted leaves its claim
        counted (two users on one node then count twice: pessimistic), one
        never taken back leaves a row in use."""
        users = self.csi_users
        for uid in claim_uids(pod):
            cur = users.get(uid)
            if sign > 0:
                if cur is None:
                    users[uid] = pod.uid
                    continue  # the common case: nothing to settle
                if isinstance(cur, set):
                    cur.add(pod.uid)
                elif cur != pod.uid:
                    users[uid] = {cur, pod.uid}
            elif cur is None:
                continue
            elif isinstance(cur, set):
                cur.discard(pod.uid)
                if len(cur) == 1:
                    users[uid] = next(iter(cur))
            elif cur == pod.uid:
                del users[uid]
            self._csi_mark(uid)

    def settle_csi_claims(self, spots) -> tuple[int, int]:
        """Make csivol_counts' rows follow the claims' users: a claim that
        two or more known pods reference is PROMOTED into a row, filled
        from where its applied users are; a claim whose users fell back to
        one or none is RELEASED and its row recycled.  ``spots(claim uid,
        pod uids)`` yields (node row, driver id) once for every applied
        user of the claim (the scheduler reads them off the cache's pod
        records).  Returns (promoted, released).

        Call only while no dispatched pass is uncommitted on the host (the
        scheduler's _settle_csi_claims): a pod in flight was featurized
        under the rows as they stood and will be applied under them as
        they stand.  A change bumps csi_epoch, so features cached under the
        old rows (and a prefetched batch) are dropped."""
        if not self.csi_unsettled:
            return 0, 0
        promoted = released = 0
        h = self.host
        for uid in sorted(self.csi_unsettled):
            users, rid = self.csi_users.get(uid), self.csi_rows.get(uid)
            wants = isinstance(users, set)
            if wants and rid is None:
                rid = (
                    self._csi_free_rows.pop()
                    if self._csi_free_rows
                    else len(self.csi_rows)
                )
                self._ensure(CV=rid + 1)
                h = self.host
                self.csi_rows[uid] = rid
                for row, did in spots(uid, users):
                    # Each applied user had charged csi_used by one; the
                    # claim is ONE volume a node.
                    if h["csivol_counts"][rid, row]:
                        h["csi_used"][did, row] -= 1
                    h["csivol_counts"][rid, row] += 1
                    self._dirty_rows.add(row)
                promoted += 1
            elif not wants and rid is not None:
                for row in np.nonzero(h["csivol_counts"][rid])[0]:
                    # At most one applied user is left, and it goes on
                    # charging csi_used by one.
                    h["csivol_counts"][rid, row] = 0
                    self._dirty_rows.add(int(row))
                del self.csi_rows[uid]
                self._csi_free_rows.append(rid)
                released += 1
        self.csi_unsettled.clear()
        if promoted or released:
            self.csi_epoch += 1
        return promoted, released

    def csi_claim_counts(self) -> tuple[int, int]:
        """(counted, shared): known claims the budget holds as a per-node
        count, and claims that hold a row."""
        shared = len(self.csi_rows)
        return max(len(self.csi_users) - shared, 0), shared

    # -- device mirror ---------------------------------------------------------

    def set_mesh(self, mesh) -> None:
        """Shard the node axis over ``mesh``.  An existing device mirror is
        RESHARDED in place (device-to-device movement) instead of rebuilt
        from host staging (VERDICT r1: set_mesh forced a full re-upload)."""
        self.mesh = mesh
        self.resident.clear()
        if self._device is not None and not self._dirty_all:
            from .parallel.mesh import shard_cluster_state

            self._device = shard_cluster_state(self._device, mesh)
        else:
            self._dirty_all = True

    def state(self) -> ClusterState:
        """Return the device ClusterState, flushing pending host changes."""
        if self._dirty_all or self._device is None:
            self._device = ClusterState(
                **{k: jnp.asarray(v) for k, v in self.host.items()}
            )
            if self.mesh is not None:
                from .parallel.mesh import shard_cluster_state

                self._device = shard_cluster_state(self._device, self.mesh)
            self._dirty_all = False
            self._dirty_rows.clear()
            return self._device
        if self._dirty_rows:
            rows = np.fromiter(self._dirty_rows, np.int32)
            # FIXED chunk shape so the scatter compiles exactly once per
            # schema (a per-bucket shape costs a fresh ~0.5s XLA compile the
            # first time a workload dirties that many rows — inside the
            # measured window for preemption bursts).  Padding repeats
            # row[0] (idempotent scatter of identical values); scattering
            # 1024 rows when few are dirty is trivial device work.
            CH = 1024
            for lo in range(0, len(rows), CH):
                sl = rows[lo : lo + CH]
                padded = np.full(CH, sl[0], np.int32)
                padded[: len(sl)] = sl
                updates0 = {
                    k: self.host[k][padded] for k, ax in _NODE_AXIS.items() if ax == 0
                }
                updates1 = {
                    k: self.host[k][:, padded] for k, ax in _NODE_AXIS.items() if ax == 1
                }
                # One coalesced transfer for index + all update arrays.
                idx_d, up0_d, up1_d = jax.device_put((padded, updates0, updates1))
                self._device = _scatter_rows(self._device, idx_d, up0_d, up1_d)
            self._dirty_rows.clear()
        return self._device

    def absorb_device_state(self, state: ClusterState) -> None:
        """Adopt the post-scan device tensors as the current device mirror."""
        self._device = state

    def invalidate_device(self) -> None:
        """Recovery: drop the device mirror (and the featurization cache)
        so the next state() rebuilds everything from host staging — host
        truth is authoritative, the device tensors are a pure cache."""
        self._dirty_all = True
        self.feat_cache = None
        self.resident.clear()

    def host_mirror_equal(self, atol: int = 0) -> bool:
        """Consistency check host staging vs device (the analog of the cache
        comparer in backend/cache/debugger): True iff mirrors agree."""
        if self._device is None:
            return True
        st = self.state()
        for k, hv in self.host.items():
            dv = np.asarray(getattr(st, k))
            if not np.array_equal(hv, dv):
                return False
        return True


@jax.jit
def _scatter_rows(state: ClusterState, idx: jax.Array, updates0: dict, updates1: dict) -> ClusterState:
    new = {}
    for f in dataclasses.fields(ClusterState):
        arr = getattr(state, f.name)
        if f.name in updates0:
            new[f.name] = arr.at[idx].set(updates0[f.name])
        else:
            new[f.name] = arr.at[:, idx].set(updates1[f.name])
    return ClusterState(**new)
