"""The comparison that decides ``correct``.

What is compared is what the timed path produced: every pod the window
asked for (and every pod of the set-up, which went through the same
calls), the node each was answered with, the order in which the sidecar
committed them (the push stream's order, each wire answer placed ahead of
the batch it started), and what ``recover`` reads back out of the journal
once the server has stopped.  The plain reference named by the
configuration replays the commits on its own cluster.

A reference module that defines ``COMPANION_KINDS`` (a tuple of kinds) is
given the configuration's companion objects of those kinds
(objects.Companions): the nodes' when its cluster is built, a pod's own
beside the pod's JSON.  A module without the name is called as it always
was.  What such a reference holds the answers to (an attach limit, a claim
bound to a volume that exists) it adds to ``over_capacity`` and
``infeasible``, as its configuration's guarantees state.

Each number has a limit of its own; exact comparisons have the limit 0,
``score_gap_mean`` has the limit the configuration file carries, set from
chip readings as PERF.md records.
"""

from __future__ import annotations

import os

from . import spec


def load_reference(name: str):
    here = os.path.dirname(os.path.abspath(__file__))
    return spec.load_file_module(os.path.join(here, "references", name + ".py"),
                                 "perfbench_reference_" + name)


def stand_up(ref, node_jsons, node_names, companions=None):
    """(the reference's cluster, facts(uid, raw)): the one place that knows
    which references take companions.  ``companions``: objects.Companions
    of the run, or None where the configuration has none."""
    kinds = getattr(ref, "COMPANION_KINDS", None)
    if kinds is None:
        return ref.Cluster(node_jsons, node_names), lambda uid, raw: ref.pod_facts(raw)
    of_nodes = {kind: [] for kind in kinds}
    for kind, jsons in (companions.of_nodes if companions is not None else ()):
        if kind in of_nodes:
            of_nodes[kind].extend(jsons)

    def facts(uid, raw):
        own = companions.of_uid(uid) if companions is not None else {}
        return ref.pod_facts(raw, {kind: own.get(kind, []) for kind in kinds})

    return ref.Cluster(node_jsons, node_names, companions=of_nodes), facts


def compare(config: dict, node_jsons, node_names, pod_json_by_uid: dict,
            commit_order, asked: dict, measured: set, recovered: dict | None,
            companions=None) -> dict:
    """``asked``: uid -> node for every pod this run asked for ("" = came
    back without one).  ``commit_order``: [(uid, node)] as committed.
    ``measured``: uids due in the window (their gaps are the ones
    averaged).  ``recovered``: uid -> node out of the journal, or None if
    it could not be read, or a call that gives either once the replay is
    done (the run reads the journal back meanwhile).  ``companions``: the
    run's objects.Companions, for a reference that asks for them.  Returns {"numbers": {name: {"value", "limit"}}
    in the order they are printed, "info": what a reader wants beside them}."""
    ref = load_reference(config["reference"])
    cluster, facts = stand_up(ref, node_jsons, node_names, companions)
    replay = ref.Replay(cluster)
    seen: dict[str, str] = {}
    conflicts = 0
    for uid, node in commit_order:
        if not node:
            continue  # an unschedulable verdict binds nothing
        if uid in seen:
            conflicts += seen[uid] != node
            continue
        seen[uid] = node
        raw = pod_json_by_uid.get(uid)
        if raw is None:
            replay.unknown_node += 1
            continue
        replay.step(uid, node, facts(uid, raw), uid in measured)
    unanswered = sum(1 for node in asked.values() if not node)
    conflicts += sum(1 for uid, node in asked.items() if node and seen.get(uid, node) != node)
    never_committed = sum(1 for uid, node in asked.items() if node and uid not in seen)
    if callable(recovered):
        recovered = recovered()
    if recovered is None:
        lost = len(asked)
    else:
        lost = sum(1 for uid, node in asked.items() if node and recovered.get(uid) != node)
    gaps = replay.gaps
    limits = config.get("correct", {})
    out = {
        "unanswered": {"value": unanswered, "limit": 0},
        "journal_lost": {"value": lost, "limit": 0},
        "answer_conflicts": {"value": conflicts + never_committed + replay.unknown_node, "limit": 0},
        "over_capacity_nodes": {"value": cluster.over_capacity(), "limit": 0},
        "infeasible": {"value": replay.infeasible, "limit": 0},
        "score_gap_mean": {
            "value": (sum(gaps) / len(gaps)) if gaps else float("inf"),
            "limit": float(limits.get("score_gap_mean_limit", 0.0)),
        },
    }
    info = {
        "compared": len(gaps), "score_gap_max": max(gaps) if gaps else None,
        "replayed": len(seen), "infeasible_examples": replay.examples,
    }
    return {"numbers": out, "info": info}


def verdict(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())
