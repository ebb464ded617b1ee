"""`topology_spreading_5kn` at a size the CPU holds (ISSUE 33).

(1) The program tied to the benchmark's plain reference
(`perfbench/references/topology_spreading.py`, numpy, upstream's
PodTopologySpread filter): a `TPUScheduler` chunked as served over 201
nodes in three zones and 600 pods of the configuration's two templates,
seeded objects in the wire's own JSON, every binding replayed through
`Replay`: no pod past the constraint, gap 0.0 at every decision of the
strictly ordered batches, the zones driven to `maxSkew` apart and never
past it; with the spread filter taken out of the profile the same replay
reads `infeasible` > 0, so the comparison can tell.

(2) `scheduler_pass_filter_rejecting_pods_total{plugin}` and the flight
record's `filter_rejecting`: for every filter op of the compiled pass the
pods for which it ruled out a node, from the fail masks a batch fetches
anyway, counted once a pod where a strict tail re-ran it; host arithmetic
only, so the lowered programs of the accepted configurations' shapes are
text for text the same with and without it."""

import json
import os
import sys
from dataclasses import replace

import numpy as np
import pytest

from kubernetes_tpu.api import serialize
from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.framework.config import DEFAULT_PROFILE
from kubernetes_tpu.ops.common import registered_subset
from kubernetes_tpu.scheduler import TPUScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from perfbench import correct, objects  # noqa: E402  the benchmark's side: no program code

ZONE = "topology.kubernetes.io/zone"
SPREAD = "PodTopologySpread"
SEED, NODES, INITIAL, MEASURED = 33, 201, 200, 400
PROFILE = replace(registered_subset(DEFAULT_PROFILE), percentage_of_nodes_to_score=100)


def _config(name: str, nodes: int = NODES) -> dict:
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        config = json.load(f)
    config["cluster"]["nodes"] = nodes
    return config


def _scheduler(config: dict, profile=PROFILE, batch_size: int = 64) -> TPUScheduler:
    s = TPUScheduler(profile=profile, batch_size=batch_size, chunk_size=8, enable_preemption=False)
    for raw in objects.Nodes(config, SEED).jsons:
        s.add_node(serialize.node_from_json(raw))
    return s


def _counts(s: TPUScheduler) -> dict:
    return {dict(k)["plugin"]: int(v) for k, v in s._filter_rejecting_counter.values.items()}


def _recorded(s: TPUScheduler) -> dict:
    out: dict = {}
    for r in s.flight.records():
        for plugin, n in r.get("filter_rejecting", {}).items():
            out[plugin] = out.get(plugin, 0) + n
    return out


# -- (1) the program against the plain reference -------------------------------


def _replayed(profile):
    """The scheduler's bindings of the initial and then the measured pods,
    in the order it returned them, through the reference's replay."""
    config = _config("topology_spreading_5kn")
    nodes = objects.Nodes(config, SEED)
    pods = objects.Pods(config, SEED, INITIAL + MEASURED, INITIAL)
    s = _scheduler(config, profile)
    order = []
    for lo, hi in ((0, INITIAL), (INITIAL, INITIAL + MEASURED)):
        for raw in pods.jsons[lo:hi]:
            s.add_pod(serialize.pod_from_json(raw))
        order += [(o.pod.uid, o.node_name) for o in s.schedule_all_pending()]
    assert [uid for uid, _ in order] == pods.uids and all(node for _, node in order)
    ref = correct.load_reference(config["reference"])
    replay = ref.Replay(ref.Cluster(nodes.jsons, nodes.names))
    by_uid = dict(zip(pods.uids, pods.jsons))
    measured = set(pods.uids[INITIAL:])
    for uid, node in order:
        replay.step(uid, node, ref.pod_facts(by_uid[uid]), uid in measured)
    return s, replay


def test_every_binding_passes_the_references_filter_at_gap_zero_and_the_skew_reaches_five():
    s, replay = _replayed(PROFILE)
    assert s.metrics.pack_width == 1  # one class as large as the batch: the ordered program
    batches = [r for r in s.flight.records() if r.get("pods")][-7:]
    assert [r["pods"] for r in batches] == [64] * 6 + [16] == [r["scan_steps"] for r in batches]
    assert replay.infeasible == 0 and replay.unknown_node == 0
    assert len(replay.gaps) == MEASURED and not any(replay.gaps)
    assert replay.skew_max == 5
    assert replay.cluster.over_capacity() == 0
    # the filter bit, and only for pods that carry the constraint
    counts = _counts(s)
    assert 0 < counts[SPREAD] <= MEASURED and not any(v for k, v in counts.items() if k != SPREAD)
    assert _recorded(s) == {SPREAD: counts[SPREAD]}
    assert all(SPREAD not in r["filter_rejecting"] for r in s.flight.records()[:4])  # the initial pods


def test_without_the_spread_filter_the_replay_reads_infeasible():
    profile = replace(PROFILE, filters=tuple(f for f in PROFILE.filters if f != SPREAD))
    s, replay = _replayed(profile)
    assert replay.infeasible > 0 and replay.skew_max > 5
    assert replay.cluster.over_capacity() == 0
    assert SPREAD not in _counts(s)  # not an op of this compiled pass


# -- (2) the counter -----------------------------------------------------------


def test_known_fail_masks_give_the_counter_and_the_flight_record_the_same_counts():
    s = TPUScheduler(profile=PROFILE, batch_size=8, chunk_size=8, enable_preemption=False)
    names = ["A", "B", "C", "D"]
    #                 B     D     C+D (sent back)  A+C   padding
    fails = np.array([0b10, 0b1000, 0b1100, 0, 0b0101, 0b1111, 0b1111, 0b1111], np.uint32)
    picks = np.array([3, -1, -3, 0, 1, -1, -1, -1], np.int32)
    acc = s._flight_acc = {}
    try:
        s._count_filter_rejections(fails, picks, 5, names)
        s._count_filter_rejections(fails, picks, 5, names)  # a second batch adds
    finally:
        s._flight_acc = None
    assert _counts(s) == {"A": 2, "B": 2, "C": 2, "D": 2}
    assert acc["filter_rejecting"] == {"A": 2, "B": 2, "C": 2, "D": 2}
    s._count_filter_rejections(fails[:1] * 0, picks[:1], 1, names)  # outside a batch: the counter only
    assert _counts(s) == {"A": 2, "B": 2, "C": 2, "D": 2}


def test_a_pod_the_strict_tail_re_ran_is_counted_once_with_the_tails_mask():
    """16 pods at chunk 8, three of one colour with anti-affinity to their
    own colour over the zone: two share a chunk, one defers and the strict
    tail decides it against the committed state, where its two mates' zones
    are closed to it.  InterPodAffinity rules nodes out for the second and
    the third pod of the colour and for nobody else."""
    colors = [0, 0, 0] + list(range(1, 14))
    s = TPUScheduler(profile=registered_subset(DEFAULT_PROFILE), batch_size=16,
                     chunk_size=8, enable_preemption=False)
    for i in range(24):
        s.add_node(make_node(f"n{i}").capacity({"cpu": "4", "memory": "16Gi", "pods": 8})
                   .zone(f"z{i % 4}").obj())
    for i, color in enumerate(colors):
        s.add_pod(make_pod(f"p{i}").req({"cpu": "100m"}).label("color", f"c{color}")
                  .pod_anti_affinity_in("color", [f"c{color}"], ZONE).obj())
    calls = []
    real = s._count_filter_rejections
    s._count_filter_rejections = lambda *a: (calls.append(a[2]), real(*a))
    assert all(o.node_name for o in s.schedule_all_pending())
    assert s.metrics.deferred >= 1 and int(s._dispatch_counter.get(kind="tail")) >= 1
    assert calls == [16]  # once a batch, after its tail
    counts = _counts(s)
    assert counts["InterPodAffinity"] == 2 and sum(counts.values()) == 2
    assert _recorded(s) == {"InterPodAffinity": 2}


@pytest.mark.parametrize("name", ["basic_5kn", "podaffinity_5kn"])
def test_the_lowered_programs_are_the_same_text_with_and_without_the_counter(name, monkeypatch):
    """The counter reads an array the batch fetched anyway: no output is
    added and no program changes.  Every program the scheduler asks its
    pass cache for while it schedules an accepted configuration's pods
    (chunked and, for the affinity pods, the ordered fallback) lowers to
    the same text whether `_count_filter_rejections` runs or not."""
    config = _config(name, nodes=12)
    pods = objects.Pods(config, SEED, 28, 8)

    def lowered(counting: bool) -> dict:
        s = _scheduler(config, batch_size=16)
        if not counting:
            s._count_filter_rejections = lambda *a: None
        texts: dict = {}
        get = s.passes.get

        def capture(*a, **k):
            fn = get(*a, **k)

            def run(*args):
                key = (a[3:], tuple(sorted(k.items())), len(texts))
                texts[key] = fn.lower(*args).as_text()
                return fn(*args)

            return run

        s.passes.get = capture
        for raw in pods.jsons:
            s.add_pod(serialize.pod_from_json(raw))
        assert all(o.node_name for o in s.schedule_all_pending())
        assert bool(_counts(s)) == counting
        return texts

    with_counter, without = lowered(True), lowered(False)
    assert with_counter and list(with_counter) == list(without)
    assert with_counter == without
