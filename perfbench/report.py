"""From one run's raw material to the lines it prints."""

from __future__ import annotations

import os
import statistics

from . import correct, loops, peaks, spec, trace


GEN2 = 'scheduler_gc_collections_total{generation="2"}'  # full collections of the serving process


def load_reader(home: str, name: str):
    return spec.load_file_module(os.path.join(home, "metrics", name + ".py"),
                                 "perfbench_metric_" + name.replace(".", "_"))


class Ctx:
    """What a metric's reader may read.  ``window`` is the client's side
    (loops.Window: among the rest ``companion_objects`` and
    ``companion_s``, the pods' companion objects sent inside the window and
    the seconds their adds took, ``echo_objects`` and ``echo_s``, the bind
    echoes posted inside it and the loop's own seconds in making and
    posting them, beside ``hint_frames`` and ``hint_s``; where the
    configuration has them, the churn's ``churn_pods``, ``nominations``,
    ``victims_removed``, ``preempt_retries`` and ``churn_due_to_bound_s``,
    and the recycle's ``recycle_objects`` and ``recycle_s``),
    ``records`` the flight recorder's per-batch records of
    the window (of a traced run: those closed before the profiler's stop
    began; ``window_records`` has them all, for a reader that divides a
    counter of the whole window), ``before``/``after`` the metrics frame
    at the window's two ends, ``trace`` the reduced profiler trace of a
    traced run (else None)."""

    def __init__(self, raw: dict, tr: dict | None):
        self.window = raw["window"]
        self.window_records = raw["records"]
        self.records = raw.get("records_before_stop", raw["records"])
        self.before = raw["scrape0"]
        self.after = raw["scrape1"]
        self.trace = tr
        self.config = raw["config"]
        self.mix = raw["mix"]
        self.device = raw["device"]
        self.setup_s = raw["setup_s"]
        self.percentile = loops.percentile
        self.peaks = peaks

    def delta(self, key: str) -> float:
        return self.after.get(key, 0.0) - self.before.get(key, 0.0)

    def pods(self) -> int:
        return sum(int(r.get("pods", 0)) for r in self.records)

    def window_pods(self) -> int:
        return sum(int(r.get("pods", 0)) for r in self.window_records)

    def phase_s(self, name: str) -> float:
        return sum(float(r.get("phases", {}).get(name, 0.0)) for r in self.records)


def build(bench: dict, raw: dict, traced: bool, rehearsal: bool, rate) -> tuple[dict, dict]:
    w = raw["window"]
    cell = raw["cell"]
    ctx = Ctx(raw, None)
    if traced:
        ctx.trace = trace.reduce(raw["trace_dir"], ctx.records, rehearsal)
    tr = ctx.trace
    group = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(bench, group, cell["name"]):
        value = load_reader(bench["home"], m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    after = raw["scrape1"]
    device = dict(raw["device"])
    peak = after.get('scheduler_device_memory_bytes{kind="peak_bytes_in_use"}')
    device["memory_peak_bytes"] = int(peak) if peak is not None else 0
    if traced and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    numbers = raw["verdict"]["numbers"]
    compiled0 = raw["scrape0"].get("scheduler_jax_compiled_programs")
    compiled1 = after.get("scheduler_jax_compiled_programs")
    ce = raw["cache_entries"]
    # every program the serving process built or loaded inside the window
    # (scheduler_jax_compiles_total; the pass variants the scheduler holds
    # are among them), beside the files the cache gained
    jax_compiles = max(int(ctx.delta("scheduler_jax_compiles_total")), int((compiled1 or 0) - (compiled0 or 0)))
    compiled_in_window = (ce["window_close"] - ce["window_open"]) + jax_compiles
    failed = w.asked - w.bound
    result = {
        "correct": bool(correct.verdict(numbers)),
        "attempted": w.asked,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if traced and tr is not None:
        result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    if rehearsal:
        result["rehearsal"] = "CPU rehearsal at toy sizes: not a chip run, no number here is a measurement"
    if rate is not None:
        result["study_rate_pods_per_s"] = rate
    if w.short:
        result["window_short"] = w.short
    # every object beside nodes and pods that the run sent (the nodes'
    # companions, the set-up pods', the window's), and the seconds the
    # window's took
    sent = raw.get("companions") or {}
    result["companion_objects"] = sent.get("of_nodes", 0) + sent.get("setup", 0) + w.companion_objects
    result["companion_s"] = w.companion_s
    # where the configuration has them: the churn's pods and what their
    # preemptions did inside the window, and the pods recycled between
    # backlogs with the seconds taken out of the window's length
    if raw.get("churn") is not None:
        for key in ("churn_pods", "nominations", "victims_removed", "preempt_retries", "churn_due_to_bound_s"):
            result[key] = getattr(w, key)
    if raw.get("recycle") is not None:
        result["recycle_objects"] = w.recycle_objects
        result["recycle_s"] = w.recycle_s
    result["compared"] = numbers

    recs = raw["records"]
    snaps = [float(r["phases"].get("snapshot", 0.0)) for r in recs]
    summary = {
        "cell": cell["name"], "seconds_asked": raw["seconds"], "window_s": w.seconds,
        "pods_asked": w.asked, "pods_bound": w.bound, "local_hits": w.hits, "wire_misses": w.misses,
        "hint_frames": w.hint_frames, "hint_s": w.hint_s, "wire_s": w.wire_s,
        "companion_objects": result["companion_objects"], "companion_s": w.companion_s,
        "companions": dict(sent, window=w.companion_objects, window_s=w.companion_s,
                           window_echoes=w.echo_objects, window_echo_s=w.echo_s),
        "setup_s": raw["setup_s"], "listening_s": raw["listening_s"], "nodes_added_s": raw["nodes_s"],
        "objects_built_s": raw.get("built_s"), "plan": raw.get("plan"), "window_short": w.short,
        "batches": len(recs),
        "batch_pods": [int(r.get("pods", 0)) for r in recs],
        "batch_wall_ms": [round(float(r.get("wall_s", 0.0)) * 1e3, 3) for r in recs],
        "batch_closed_at_s": [round(float(r["ts"]) - raw["wall_open"], 3) for r in recs],
        "batch_fsync_ms": [round(float(r.get("journal", {}).get("fsync_s", 0.0)) * 1e3, 3) for r in recs],
        "batch_fsyncs": [int(r.get("journal", {}).get("fsyncs", 0)) for r in recs],
        "batch_device_ms": [round(float(r["phases"].get("device", 0.0)) * 1e3, 3) for r in recs],
        "batch_drain_ms": [round(float(r["phases"].get("drain", 0.0)) * 1e3, 3) for r in recs],
        "batch_commit_ms": [round(float(r["phases"].get("commit", 0.0)) * 1e3, 3) for r in recs],
        "batch_featurize_ms": [round(float(r["phases"].get("featurize", 0.0)) * 1e3, 3) for r in recs],
        "batch_snapshot_ms": [round(x * 1e3, 3) for x in snaps],
        "checkpoints": int(ctx.delta("scheduler_journal_snapshots_total")),
        "checkpoint_s": sum(x for x in snaps if x > 0.001),
        "deferred_pods": int(ctx.delta("scheduler_deferred_pods_total")),
        "server_gc_gen2_collections": int(ctx.delta(GEN2)) if GEN2 in after else "not measured",
        "client_gc": "frozen for the window",
        "markers": [m.get("event") for m in raw["markers"]],
        "compiled_programs": [compiled0, compiled1], "jax_compiles_in_window": jax_compiles,
        "cache_entries": ce,
        "compiled_in_window": compiled_in_window,
        "os_cpu_count": os.cpu_count(),
        "miss_wall_ms": [round((b - a) * 1e3, 3) for _, a, b in w.miss_at],
        "push": raw["push"], "compare_info": raw["verdict"]["info"], "serve_rc": raw["serve_rc"],
        "device": device, "rehearsal": rehearsal,
    }
    if raw.get("churn") is not None:
        summary["churn"] = dict(raw["churn"], window={k: result[k] for k in (
            "churn_pods", "nominations", "victims_removed", "preempt_retries")})
    if raw.get("recycle") is not None:
        summary["recycle"] = dict(raw["recycle"], window=w.recycle_objects, window_s=w.recycle_s)
    if w.due_t:
        # pods due and not yet answered, at the window's middle and at the
        # last due time: a queue that grows says the rate is past the knee
        t0 = w.t_open
        mid, end = t0 + 0.5 * (w.due_t[-1] - t0), w.due_t[-1]
        for name, at in (("backlog_mid_pods", mid), ("backlog_end_pods", end)):
            summary[name] = sum(1 for d in w.due_t if d <= at) - sum(1 for a in w.answer_t if a <= at)
        lat = [a - d for a, d in zip(w.answer_t, w.due_t)]
        summary["latency_ms"] = {f"p{q}": loops.percentile(lat, q) * 1e3 for q in (50, 90, 95, 99)}
        summary["latency_ms"]["max"] = max(lat) * 1e3
        summary["latency_ms"]["mean"] = sum(lat) / len(lat) * 1e3
        summary["latency_sorted_ms"] = [round(x * 1e3, 1) for x in sorted(lat)]
        summary["offered_pods_per_s"] = len(w.due_t) / (w.due_t[-1] - t0)
    if w.lag_s:
        summary["generator_lag_p99_ms"] = loops.percentile(w.lag_s, 99) * 1e3
        summary["generator_lag_max_ms"] = max(w.lag_s) * 1e3
        summary["generator_waited"] = len(w.lag_s)
    elif w.due_t:
        summary["generator_waited"] = 0
    marks = raw.get("trace_marks") or {}
    if tr is not None:
        summary["trace"] = {k: tr[k] for k in (
            "window_s", "busy_s", "idle_s", "idle_named_share", "ops", "device_events", "host_events",
            "span_events", "modules", "module_events", "pass_device_s", "pods_in_slice", "passes_in_slice",
            "planes", "xplane_bytes")}
    if "stop" in marks:
        # what the stop cost, by the serving process's clock, and what a
        # traced run's readers were not shown because of it
        summary["trace_stop"] = {
            "stop_s": (marks["stop"][1] - marks["stop"][0]) * 1e-9,
            "start_s": (marks["start"][1] - marks["start"][0]) * 1e-9,
            "started_at_s": round(marks["start"][0] * 1e-9 - raw["wall_open"], 3),
            "stop_began_at_s": round(marks["stop"][0] * 1e-9 - raw["wall_open"], 3),
            "records_left_out": len(ctx.window_records) - len(ctx.records),
            "pods_left_out": ctx.window_pods() - ctx.pods(),
        }
    return result, summary


def earlier_lines(summary: dict) -> list[str]:
    """What a reader must not miss, said before the result."""
    out = []
    if summary["compiled_in_window"]:
        out.append(f"perfbench: WARNING {summary['compiled_in_window']} program(s) were compiled or "
                   "first loaded inside the window: this run is not a measurement")
    lag = summary.get("generator_lag_p99_ms")
    if lag is not None:
        p50 = summary["latency_ms"]["p50"]
        out.append(f"perfbench: generator lag p99 {lag:.3f} ms, max {summary['generator_lag_max_ms']:.3f} ms, "
                   f"over {summary['generator_waited']} waits" + (
                       " — NOT small beside the median latency" if lag > 0.1 * p50 else ""))
    elif summary.get("generator_waited") == 0:
        out.append("perfbench: the asking loop never had to wait for a due time: the system is behind its arrivals")
    if summary["window_short"]:
        out.append(f"perfbench: WARNING {summary['window_short']}: this run is not a measurement at the "
                   "length asked for (traffic/<mix>.json: prebuild_pods_per_s; the cluster's room)")
    churn = summary.get("churn")
    if churn and churn["exhausted"]:
        out.append(f"perfbench: WARNING the churn used every one of its {churn['prebuilt']} prebuilt pods "
                   f"({churn['window']['churn_pods']} of them in the window): it stopped before the window "
                   "closed, so what followed is not the row's traffic (cell.CHURN_SETUP_S)")
    if summary["rehearsal"]:
        out.append("perfbench: CPU REHEARSAL at toy sizes: not a chip run")
    return out


def brief(summary: dict) -> dict:
    """The summary with its per-batch lists cut to what a line can hold
    (the whole of it is in timeline.json beside the journal)."""
    out = {}
    for k, v in summary.items():
        if isinstance(v, list) and len(v) > 24 and all(isinstance(x, (int, float)) for x in v):
            out[k] = {"n": len(v), "min": min(v), "median": statistics.median(v), "max": max(v),
                      "first": v[:12]}
        else:
            out[k] = v
    return out
