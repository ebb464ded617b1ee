"""Pod-batch featurization: list[Pod] → padded device feature tensors.

The host-side analog of the reference's PreFilter extension point
(runtime/framework.go:698): everything about a pod that the device pass needs
is computed once per pod here (resource vectors, interned ids, compiled
selector programs) and shipped as one (K, …) batch.  Padding rows carry
valid=False and are ignored by the engine's commit."""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from ..api import types as t
from ..framework.config import Profile
from ..ops import common as opcommon
from ..snapshot import POD_PORT_SLOTS, SnapshotBuilder, _bucket
from ..utils import const_array
from ..volumes import claim_uids

opcommon.feature_fill("ipa_own_terms", -1)
opcommon.feature_fill("vol_dev_ids", -1)
opcommon.feature_fill("vol_dev_rw", 0)
opcommon.feature_fill("vol_csi_ids", -1)
opcommon.feature_fill("vol_csi_drv", -1)
opcommon.feature_fill("vol_unbound", 0)
opcommon.feature_fill("vol_csi_lim", 0)
opcommon.feature_fill("dra_claim_ids", -1)
opcommon.feature_fill("dra_claim_cls", -1)
opcommon.feature_fill("dra_claim_cnt", 0)
opcommon.feature_fill("dra_claim_first", False)
opcommon.feature_fill("dra_claim_unalloc", 0)
# Injected by the scheduler AFTER featurization (nomination lives in pod
# STATUS; the featurize cache keys on spec only).
opcommon.feature_fill("nominated_row", -1)

# The empty-case singletons (hoisted: building even a cache key per pod
# costs more than it saves at millions of pods).
_PORTS_EMPTY = const_array(POD_PORT_SLOTS, -1, np.int32)
_I32_NEG1 = const_array(1, -1, np.int32)
_I32_ZERO = const_array(1, 0, np.int32)
_BOOL_FALSE = const_array(1, 0, np.bool_)


def pin_name(pod: t.Pod):
    """The single node a pod's own constraints reduce its candidate set to,
    or None: a required node affinity of exactly one term with one
    metadata.name In [one value] matchFields (nodeaffinity.go PreFilter's
    PreFilterResult.NodeNames)."""
    aff = pod.spec.affinity
    na = aff.node_affinity if aff else None
    if na is not None and na.required is not None and len(na.required.terms) == 1:
        term = na.required.terms[0]
        if not term.match_expressions and len(term.match_fields) == 1:
            mf = term.match_fields[0]
            if (
                mf.key == "metadata.name"
                and mf.operator == t.OP_IN
                and len(mf.values) == 1
            ):
                return mf.values[0]
    return None


def pod_sig(pod: t.Pod):
    """The featurization cache key for an in-process pod.  Workload pods
    are stamped from templates, so (namespace, labels, spec) collapses
    thousands of pods onto a handful of signatures (names/uids excluded:
    featurization never reads them).  Built through the ONE shared key
    constructor (serialize.featsig_from_data — the same function that
    stamps wire pods), so wire-fed and in-process copies of one template
    share cache entries by string equality."""
    from ..api import serialize

    return serialize.featsig_from_data(
        pod.namespace,
        pod.metadata.labels,
        serialize._codegen().dumper(t.PodSpec)(pod.spec),
    )


_PODSPEC_FIELDS: tuple[str, ...] = ()


def _spec_eq_mod_pin(a: t.PodSpec, b: t.PodSpec) -> bool:
    """Structural equality of two PIN-SHAPED pod specs modulo the pinned
    node name (both already passed pin_name, so the affinity shape is
    exactly one required term with one single-value matchField).  Direct
    field comparison — no tree hashing: for the daemonset template this is
    ~20 mostly-None comparisons, an order of magnitude cheaper than a
    canonical signature walk."""
    global _PODSPEC_FIELDS
    if not _PODSPEC_FIELDS:
        # node_name excluded: pods are always UNASSIGNED when featurized,
        # but a stored template's spec mutates at bind (the in-place
        # spec.node_name write) — comparing it would kill every later hit.
        _PODSPEC_FIELDS = tuple(
            f.name
            for f in dataclasses.fields(t.PodSpec)
            if f.name not in ("affinity", "node_name")
        )
    for name in _PODSPEC_FIELDS:
        if getattr(a, name) != getattr(b, name):
            return False
    aa, bb = a.affinity, b.affinity
    if (
        aa.pod_affinity != bb.pod_affinity
        or aa.pod_anti_affinity != bb.pod_anti_affinity
    ):
        return False
    na, nb = aa.node_affinity, bb.node_affinity
    if na.preferred != nb.preferred:
        return False
    ta, tb = na.required.terms[0], nb.required.terms[0]
    if ta.match_expressions != tb.match_expressions:
        return False
    ma, mb = ta.match_fields[0], tb.match_fields[0]
    return ma.key == mb.key and ma.operator == mb.operator


def _claim_key(sig, pod: t.Pod, builder: SnapshotBuilder):
    """The featurization cache key of a pod whose signature is ``sig``.
    The signature leaves the NAMES of the pod's claims out
    (serialize.featsig_from_data), so that pods that differ only there
    share one featurization; what featurization reads of each claim comes
    back in here: the catalog's answer for it (VolumeCatalog.claim_featsig),
    its row if it is shared, and which of the pod's volumes name one claim
    twice.  A claim that has to be featurized by itself stands in the key
    under its own name."""
    if not pod.spec.volumes:
        return sig
    uids = claim_uids(pod)
    if not uids:
        return sig
    cat, rows = builder.volumes, builder.csi_rows
    parts = []
    for uid in uids:
        rid = rows.get(uid)
        fs = cat.claim_featsig(uid) if rid is None else None
        parts.append(uid if fs is None else fs)
    if len(uids) > 1:
        parts.append(tuple(uids.index(u) for u in uids))
    return (sig, tuple(parts))


def _own_claims(delta: dict, pod: t.Pod) -> dict:
    """A cached delta made the delta of ``pod``: everything in it is the
    template's but the names of the claims, which are the pod's own.  The
    key (_claim_key) holds the two pods' claims to the same order, the same
    repeats and the same drivers."""
    pvcs = delta.get("pvcs")
    if not pvcs:
        return delta
    uids = claim_uids(pod)
    if uids == pvcs:
        return delta
    slot = {old: i for i, old in reversed(list(enumerate(pvcs)))}
    delta["csivols"] = [(uids[slot[old]], did) for old, did in delta["csivols"]]
    delta["pvcs"] = uids
    return delta


def build_pod_batch(
    pods: list[t.Pod],
    builder: SnapshotBuilder,
    profile: Profile,
    k: int,
    force_active: frozenset[str] | None = None,
    sample_into: dict | None = None,
    info: dict | None = None,
) -> tuple[dict, list[dict], frozenset[str]]:
    """Featurize up to ``k`` pods into a dict of (k, …) numpy arrays, plus the
    per-pod commit deltas (reused by the cache's assume step so pods are
    featurized exactly once) and the batch's ACTIVE op set — ops whose
    ``is_active`` predicate is False for every pod are skipped here and
    compiled out of the batch's pass (the batch analog of PreFilter Skip).

    Featurization may grow vocabularies/schema (new scalar resources, label
    pairs, topology keys), which is why it must run before the device state is
    flushed for the pass.

    ``info`` (optional) receives ``uniform_key``: the one cache key every
    pod of the batch shares, where every row of the batch is the same row,
    else None."""
    assert len(pods) <= k
    fctx = opcommon.FeaturizeContext(builder=builder, profile=profile)
    all_ops = [opcommon.get(name) for name in dict.fromkeys(
        list(profile.filters) + [s for s, _ in profile.scorers]
    )]
    # Cache keys first (memoized on the pod object — hashing the spec tree
    # is ~half of featurize cost; a pod's spec/labels only change by
    # arriving as a NEW object on the informer path; bind's in-place
    # spec.node_name write happens after the pod's last featurization).
    # NAME-PINNED pods (the daemonset shape — thousands of pods differing
    # only in the matchFields node name) skip signatures entirely: they
    # match against pin TEMPLATES by direct field comparison, and a hit
    # stamps only the interned pin id (see the template block below).
    # Pinned pods whose NodeAffinity featurize would take the general path
    # (addedAffinity / preferred terms embed the name id in program
    # tensors a patch can't reach) are featurized per pod, uncached.
    templatable = profile.added_affinity is None
    keys: list = []
    pins: list = []
    for pod in pods:
        # The memo is profile-independent (the cache's version token, not
        # the key, carries the profile); wire-built pods arrive with it
        # pre-stamped from the raw JSON (serialize.pod_from_data).
        memo = getattr(pod, "_featsig", None)
        if memo is not None:
            keys.append(_claim_key(memo, pod, builder))
            pins.append(None)
            continue
        pin = pin_name(pod)
        if pin is not None:
            keys.append(None)
            pins.append(
                pin
                if templatable and not pod.spec.affinity.node_affinity.preferred
                else None
            )
            continue
        key = pod_sig(pod)
        pod._featsig = key
        keys.append(_claim_key(key, pod, builder))
        pins.append(None)
    if force_active is not None:
        # Rebuild for the strict tail: the pass is already compiled for this
        # op set; features must match it exactly.
        ops = [op for op in all_ops if op.name in force_active]
    else:
        # is_active reads only (labels, spec) and builder catalogs, so one
        # REPRESENTATIVE per distinct key/template suffices — template
        # workloads collapse 4096 predicate scans to a handful (the
        # O(ops × pods) inactive-op scan was a measured featurize cost).
        seen: dict = {}
        pin_reps: list = []
        pin_buckets: dict = {}  # (ns, labels-items) → candidate reps
        for pod, key, pin in zip(pods, keys, pins):
            if key is not None:
                seen.setdefault(key, pod)
            elif pin is not None:
                bkey = (pod.namespace, tuple(sorted(pod.metadata.labels.items())))
                bucket = pin_buckets.setdefault(bkey, [])
                # Spec-distinct pods within a bucket are rare; past the cap
                # just take every pod as a rep (the pre-optimization
                # behavior — only extra is_active calls, never wrong).
                if len(bucket) > 16 or not any(
                    _spec_eq_mod_pin(pod.spec, rep.spec) for rep in bucket
                ):
                    bucket.append(pod)
                    pin_reps.append(pod)
            else:
                pin_reps.append(pod)  # unique-featurized pinned pod
        reps = list(seen.values()) + pin_reps
        ops = [
            op
            for op in all_ops
            if op.is_active is None or any(op.is_active(p, fctx) for p in reps)
        ]
    active = frozenset(op.name for op in ops)
    fctx.active = active
    per_pod: list[dict] = []
    deltas: list[dict] = []
    # Featurization cache: identical (namespace, labels, spec) pods produce
    # identical features/deltas as long as nothing featurization reads has
    # changed (vocabularies, schema, volumes, namespace labels — the version
    # token).  An entry whose own featurization grew a vocabulary is NOT
    # cached: a pod featurized before term/group T was interned legitimately
    # lacks T's feature bits only because every pod of T's group schedules
    # after it — reusing those features for a later pod would break that
    # ordering invariant.
    version = (builder.feature_version(), profile, active)
    if builder.feat_cache is None or builder.feat_cache[0] != version:
        builder.feat_cache = (version, {}, [])
    store = builder.feat_cache[1]
    # Uniform-batch stack cache: a template workload's whole batch is ONE
    # signature, so the stacked (k, …) tensors are a pure function of
    # (signature, count, k) under the version token — tile once, reuse
    # across batches (the per-pod stack/pad loop was the residual
    # featurize cost after the row cache).  The returned dict is shallow-
    # copied per use: consumers assign fresh keys (nominated_row,
    # uniform_all, pin_row) but never mutate the arrays.
    uniform_key = None
    uniform_version = version
    one_key = bool(pods) and keys[0] is not None and all(
        k2 == keys[0] for k2 in keys
    )
    if info is not None:
        info["uniform_key"] = keys[0] if one_key else None
    if sample_into is None and force_active is None and one_key:
        # Count-independent: every row is the template row (broadcast
        # views), so a 1-pod warm batch and a 1000-pod measured batch share
        # the entry; only `valid` depends on the count and is built fresh.
        uniform_key = ("#stacked", keys[0], k)
        hit = store.get(uniform_key)
        if hit is not None:
            tmpl_batch, delta0 = hit
            batch = dict(tmpl_batch)
            valid = np.zeros(k, np.bool_)
            valid[: len(pods)] = True
            batch["valid"] = valid
            return (
                batch,
                [_own_claims(dict(delta0), pod) for pod in pods],
                active,
            )
    # Pin templates: (ns, labels, spec, feats, delta) per distinct pinned
    # template, living beside the key store under the same version token.
    templates = builder.feat_cache[2]
    for pod, key, pin in zip(pods, keys, pins):
        if key is not None:
            hit = store.get(key)
            if hit is not None:
                deltas.append(_own_claims(dict(hit[1]), pod))
                per_pod.append(dict(hit[0]))
                continue
        elif pin is not None:
            tmpl = None
            for cand in templates:
                if (
                    pod.namespace == cand[0]
                    and pod.metadata.labels == cand[1]
                    and _spec_eq_mod_pin(pod.spec, cand[2])
                ):
                    tmpl = cand
                    break
            if tmpl is not None:
                feats = dict(tmpl[3])
                # The ONLY pin-dependent feature is the interned name id
                # (the NodeAffinity pin fast path's (1,1,1) value tensor).
                # Present only when NodeAffinity is in the profile — a
                # NodeAffinity-less profile still pins via the host-side
                # pin_row, and its dicts must stay homogeneous.
                if "na_req_vals" in feats:
                    vals = np.empty((1, 1, 1), np.int32)
                    vals[0, 0, 0] = fctx.interns.node_names.id(pin)
                    feats["na_req_vals"] = vals
                deltas.append(dict(tmpl[4]))
                per_pod.append(feats)
                continue
        delta = builder.pod_delta_vectors(pod)
        deltas.append(delta)
        # Host ports are base commit features: the scan's _commit and the host
        # apply_pod_delta must apply the *same* delta or the mirrors desync.
        # Empty-case arrays are shared immutable singletons (const_array):
        # most pods carry no ports/devices/claims, and per-pod allocation of
        # all-pad arrays was a measurable slice of featurize cost.
        if delta["ports"]:
            port_triples = np.full(POD_PORT_SLOTS, -1, np.int32)
            port_keys = np.full(POD_PORT_SLOTS, -1, np.int32)
            for j, (triple, pk) in enumerate(delta["ports"][:POD_PORT_SLOTS]):
                port_triples[j] = triple
                port_keys[j] = pk
        else:
            port_triples = port_keys = _PORTS_EMPTY
        own = delta["own_terms"]
        if own:
            own_terms = np.full(_bucket(len(own), 1), -1, np.int32)
            own_terms[: len(own)] = own
        else:
            own_terms = _I32_NEG1
        devs = delta["devices"]
        if devs:
            dev_ids = np.full(_bucket(len(devs), 1), -1, np.int32)
            dev_rw = np.zeros(dev_ids.shape[0], np.bool_)
            for j, (vid, rw) in enumerate(devs):
                dev_ids[j] = vid
                dev_rw[j] = rw
        else:
            dev_ids = _I32_NEG1
            dev_rw = _BOOL_FALSE
        dcl = delta["dra_claims"]
        if dcl:
            # One slot per device REQUEST (structured parameters); slots of
            # a claim share kid, `first` marks the count-moving one.
            dra_ids = np.full(_bucket(len(dcl), 1), -1, np.int32)
            dra_cls = np.full(dra_ids.shape[0], -1, np.int32)
            dra_cnt = np.zeros(dra_ids.shape[0], np.int32)
            dra_unalloc = np.zeros(dra_ids.shape[0], np.bool_)
            dra_first = np.zeros(dra_ids.shape[0], np.bool_)
            for j, (kid, cid, cnt, unalloc, first) in enumerate(dcl):
                dra_ids[j] = kid
                dra_cls[j] = cid
                dra_cnt[j] = cnt
                dra_unalloc[j] = unalloc
                dra_first[j] = first
        else:
            dra_ids = dra_cls = _I32_NEG1
            dra_cnt = _I32_ZERO
            dra_unalloc = _BOOL_FALSE
            dra_first = _BOOL_FALSE
        cvols = delta["csivols"]
        if cvols:
            csi_ids = np.full(_bucket(len(cvols), 1), -1, np.int32)
            csi_drv = np.full(csi_ids.shape[0], -1, np.int32)
            for j, (uid, did) in enumerate(cvols):
                # A claim of the pod's own has no row: the device needs to
                # know only its driver.
                csi_ids[j] = builder.csi_rows.get(uid, -1)
                csi_drv[j] = did
        else:
            csi_ids = csi_drv = _I32_NEG1
        feats = {
            "ipa_own_terms": own_terms,
            "vol_dev_ids": dev_ids,
            "vol_dev_rw": dev_rw,
            "vol_csi_ids": csi_ids,
            "vol_csi_drv": csi_drv,
            "req": delta["req"],
            "nonzero": delta["nonzero"],
            "group": np.int32(delta["group"]),
            "priority": np.int32(pod.spec.priority),
            "port_triples": port_triples,
            "port_keys": port_keys,
            "dra_claim_ids": dra_ids,
            "dra_claim_cls": dra_cls,
            "dra_claim_cnt": dra_cnt,
            "dra_claim_first": dra_first,
            "dra_claim_unalloc": dra_unalloc,
            # Chunked-pass conflict classes (engine/pass_.py _conflict_pairs):
            # only PreBind-racing claims (unbound WFC) conflict any-vs-any;
            # bound claims conflict only on SHARED volume/device ids.
            "vol_unbound": np.bool_(delta["vol_unbound"]),
            "vol_csi_lim": np.bool_(delta["vol_csi_lim"]),
        }
        # plugin_execution_duration_seconds{plugin, Featurize}: the
        # per-plugin measurable unit of the batch engine (the device pass
        # fuses the rest), recorded only on ~10% of batches like the
        # reference (schedule_one.go:48 pluginMetricsSamplePercent).
        for op in ops:
            if op.featurize is not None:
                if sample_into is None:
                    feats.update(op.featurize(pod, fctx))
                else:
                    t0 = time.perf_counter()
                    feats.update(op.featurize(pod, fctx))
                    sample_into[op.name] = (
                        sample_into.get(op.name, 0.0)
                        + time.perf_counter() - t0
                    )
        per_pod.append(feats)
        v2 = (builder.feature_version(), profile, active)
        if v2 != version:  # this pod grew a vocabulary — new cache generation
            version = v2
            store = {}
            templates = []
            builder.feat_cache = (version, store, templates)
        elif key is not None:
            if len(store) > 8192:
                store.clear()
            store[key] = (dict(feats), dict(delta))
        elif pin is not None and len(templates) < 8:
            templates.append(
                (pod.namespace, dict(pod.metadata.labels), pod.spec,
                 dict(feats), dict(delta))
            )

    if not per_pod:
        raise ValueError("empty pod batch")

    # Stack + pad. Schema/vocab growth during featurization means early pods
    # may have shorter feature arrays than late ones — pad every key to the
    # per-key max shape with its registered fill (0 for counts, -1 for ids).
    keys = per_pod[-1].keys()
    for key in keys:
        shapes = {f[key].shape for f in per_pod}
        if len(shapes) > 1:
            target = tuple(max(dims) for dims in zip(*shapes))
            fill = opcommon.FEATURE_FILLS.get(key, 0)
            for f in per_pod:
                a = f[key]
                if a.shape != target:
                    pad = [(0, tgt - cur) for cur, tgt in zip(a.shape, target)]
                    f[key] = np.pad(a, pad, constant_values=fill)
    if (
        uniform_key is not None
        and (builder.feature_version(), profile, active) == uniform_version
    ):
        # Uniform fast path — no stack at all: every row (including the
        # padding region, which `valid` gates) is a zero-copy broadcast
        # view of the template row.  Version compared against the capture
        # from BEFORE featurizing: a batch whose first pod grew a
        # vocabulary must not be cached (its row legitimately lacks the
        # new feature bits — the same ordering invariant the per-pod
        # store honors above).  The cached arrays are read-only views;
        # consumers assign fresh keys but never write rows.
        f0 = per_pod[0]
        batch = {
            key: np.broadcast_to(
                np.asarray(val), (k,) + np.asarray(val).shape
            )
            for key, val in f0.items()
        }
        store[uniform_key] = (dict(batch), dict(deltas[0]))
        valid = np.zeros(k, np.bool_)
        valid[: len(pods)] = True
        batch["valid"] = valid
        return batch, deltas, active
    batch = {}
    for key in keys:
        rows = [f[key] for f in per_pod]
        stacked = np.stack(rows)
        pad_width = [(0, k - len(pods))] + [(0, 0)] * (stacked.ndim - 1)
        batch[key] = np.pad(stacked, pad_width)
    batch["valid"] = np.zeros(k, np.bool_)
    batch["valid"][: len(pods)] = True
    return batch, deltas, active
