"""The table of peaks, and the bytes one scheduling pass has to move.

Peaks are keyed by the ``device_kind`` JAX reports.  A kind that is not in
the table is an error, never a default.
"""

from __future__ import annotations

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


def _selected_values(config: dict) -> int:
    """Label values that the pod template's required pod (anti-)affinity
    terms select: one per ``matchLabels`` pair and per ``In`` value, times
    the length of a cycle where the value is one of the configuration's
    cyclic variables."""
    pod = config["pod"]
    cycles = pod.get("cycles", {})
    aff = pod["template"]["spec"].get("affinity") or {}
    n = 0
    for side in ("pod_affinity", "pod_anti_affinity"):
        for term in (aff.get(side) or {}).get("required", ()):
            sel = term.get("label_selector") or {}
            values = [v for _, v in sel.get("match_labels", ())]
            for e in sel.get("match_expressions", ()):
                values += e.get("values", ())
            for v in values:
                n += max((c["count"] for var, c in cycles.items() if "{" + var + "}" in v), default=1)
    return n


def node_row_bytes(config: dict) -> int:
    """What one node contributes to the state a decision reads: allocatable
    and requested cpu, memory and pods as 64-bit integers (the
    configuration's own units: millicores and bytes, which overflow 32
    bits), and, where the pod template carries required pod (anti-)affinity
    terms, one 32-bit domain id for the topology key and one 32-bit count
    per label value they select."""
    row = 6 * 8
    groups = _selected_values(config)
    if groups:
        row += 4 + 4 * groups
    return row


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
