"""The table of peaks, and the bytes one scheduling pass has to move.

Peaks are keyed by the ``device_kind`` JAX reports.  A kind that is not in
the table is an error, never a default.
"""

from __future__ import annotations

from .objects import cycle_length

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12, "hbm_bytes": 16e9},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12, "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no peak {what!r} on record for device kind {device_kind!r}") from None


def _selectors(config: dict):
    """(topology key, label selector) of everything in the measured pods'
    template that makes a decision read other pods by domain: each
    required pod (anti-)affinity term and each topology spread constraint."""
    spec = config["pod"]["template"]["spec"]
    aff = spec.get("affinity") or {}
    for side in ("pod_affinity", "pod_anti_affinity"):
        for term in (aff.get(side) or {}).get("required", ()):
            yield term["topology_key"], term.get("label_selector") or {}
    for c in spec.get("topology_spread_constraints") or ():
        yield c["topology_key"], c.get("label_selector") or {}


def _selected_values(config: dict, selector: dict) -> int:
    """Label values a selector selects: one per ``matchLabels`` pair and
    per ``In`` value, times the length of a cycle where the value is one of
    the configuration's cyclic variables."""
    cycles = config["pod"].get("cycles", {})
    values = [v for _, v in selector.get("match_labels", ())]
    for e in selector.get("match_expressions", ()):
        values += e.get("values", ())
    return sum(max((cycle_length(c) for var, c in cycles.items() if "{" + var + "}" in v), default=1)
               for v in values)


def node_row_bytes(config: dict) -> int:
    """What one node contributes to the state a decision reads: allocatable
    and requested cpu, memory and pods as 64-bit integers (the
    configuration's own units: millicores and bytes, which overflow 32
    bits), and, where the measured pods' template carries required pod
    (anti-)affinity terms or topology spread constraints, one 32-bit domain
    id for each distinct topology key they name and one 32-bit count for
    each label value they select (under a hostname key the domain is the
    node, and the count is still one a node)."""
    selectors = list(_selectors(config))
    keys = {key for key, _ in selectors}
    return 6 * 8 + 4 * len(keys) + 4 * sum(_selected_values(config, sel) for _, sel in selectors)


def pass_bytes(config: dict, pods: int) -> int:
    """Bytes one pass over ``pods`` pending pods has to move between HBM
    and the cores, from the cell's shapes alone and whatever implements
    the pass: the node table read once and written back once (the steps in
    between depend on each other, but a table of some hundreds of KB can
    stay on the chip between them), plus each pod's request going in and
    its pick and score coming out."""
    n = int(config["cluster"]["nodes"])
    per_pod = 2 * 8 + 4 + 8  # cpu and memory in; node row and score out
    return 2 * n * node_row_bytes(config) + pods * per_pod
