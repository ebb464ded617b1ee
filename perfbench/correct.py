"""The comparison that decides ``correct``.

What is compared is what the timed path produced: every pod the window
asked for (and every pod of the set-up, which went through the same
calls), the node each was answered with, the order in which the sidecar
committed them (the push stream's order, each wire answer placed ahead of
the batch it started), and what ``recover`` reads back out of the journal
once the server has stopped.  The plain reference named by the
configuration replays the commits on its own cluster.

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


def compare(config: dict, node_jsons, node_names, pod_json_by_uid: dict,
            commit_order, asked: dict, measured: set, recovered: dict | None) -> dict:
    """``asked``: uid -> node for every pod this run asked for ("" = came
    back without one).  ``commit_order``: [(uid, node)] as committed.
    ``measured``: uids due in the window (their gaps are the ones
    averaged).  ``recovered``: uid -> node out of the journal, or None if
    it could not be read, or a call that gives either once the replay is
    done (the run reads the journal back meanwhile).  Returns {"numbers": {name: {"value", "limit"}}
    in the order they are printed, "info": what a reader wants beside them}."""
    ref = load_reference(config["reference"])
    cluster = ref.Cluster(node_jsons, node_names)
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
        replay.step(uid, node, ref.pod_facts(raw), uid in measured)
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
