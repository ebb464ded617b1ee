#!/usr/bin/env python3
"""Turn a flight-recorder dump — or a SOAK artifact — into tables.

Input: the JSON document the flight recorder produces everywhere — an
auto-dump file (engine fault / quarantine / breaker trip / SIGTERM /
recovery), `python -m kubernetes_tpu flight --socket S`, or
`GET /debug/flight` (pipe via `-`) — or a soak artifact (what
scripts/run_soak.py writes to its ``--out`` and the ``soak`` subcommand
prints; none is committed).  Output: where the time went — aggregate
per-phase seconds and share, per-batch percentiles, the sampled
per-plugin table, and the transition-marker timeline; for soak
artifacts, the SLO block, the miss-rate knee curve, journal growth, and
the per-phase serving table.

    python scripts/profile_report.py /tmp/flight-scheduler-123-001-quarantine.json
    python -m kubernetes_tpu flight --socket S | python scripts/profile_report.py -
    python scripts/profile_report.py <soak artifact>.json

Fleet mode (``--fleet``): render ONE merged timeline from a partitioned
fleet's flight logs — either a pre-merged document (the fleet soak's
``fleet-flight-merged.json``, or a SOAK artifact carrying a
``fleet_timeline`` block) or several raw per-owner dumps merged on the
spot::

    python scripts/profile_report.py --fleet fleet-flight-merged.json
    python scripts/profile_report.py --fleet owner0.json owner1.json router.json

Output: per-component batch/phase totals, fleet busy-time overlap
(parallelism), the critical-path attribution (which component+phase
gated each instant of fleet busy time), the logical-clock timeline
tail, and any slow-span trees (the joined router→owner→sidecar path).

Stdlib-only on purpose: this must run on the operator's laptop against a
dump scp'd out of an incident, with no JAX (or repo) install — merging
raw dumps loads ``framework/flight.py`` by file path (it is itself
stdlib-only), never the JAX-importing package root.
"""

from __future__ import annotations

import json
import os
import re
import sys


def _percentile(values: list[float], q: float) -> float:
    if not values:
        return 0.0
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[idx]


def _fmt_s(v: float) -> str:
    return f"{v * 1000:.1f}ms" if v < 1.0 else f"{v:.3f}s"


def _table(rows: list[tuple], headers: tuple) -> str:
    widths = [
        max(len(str(r[i])) for r in rows + [headers])
        for i in range(len(headers))
    ]
    lines = [
        "  ".join(str(h).ljust(w) for h, w in zip(headers, widths)),
        "  ".join("-" * w for w in widths),
    ]
    for r in rows:
        lines.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(lines)


def report(doc: dict) -> str:
    out: list[str] = []
    records = doc.get("records", [])
    batches = [r for r in records if r.get("kind") == "batch"]
    markers = [r for r in records if r.get("kind") == "marker"]
    out.append(
        f"flight dump: component={doc.get('component', '?')} "
        f"records={len(records)} (capacity {doc.get('capacity', '?')}, "
        f"{doc.get('recorded', len(records))} recorded lifetime)"
        + (f" reason={doc['reason']}" if doc.get("reason") else "")
    )

    if batches:
        # Aggregate per-phase attribution.
        totals: dict[str, float] = {}
        per_batch: dict[str, list[float]] = {}
        wall = 0.0
        for b in batches:
            wall += b.get("wall_s", 0.0)
            for phase, secs in (b.get("phases") or {}).items():
                totals[phase] = totals.get(phase, 0.0) + secs
                per_batch.setdefault(phase, []).append(secs)
        tiled = sum(
            v for k, v in totals.items()
            if k not in ("journal_append", "journal_fsync", "hint_decode")
        )
        pods = sum(b.get("pods", 0) for b in batches)
        bound = sum(b.get("scheduled", b.get("bound", 0)) for b in batches)
        out.append(
            f"\n{len(batches)} batches, {pods} pods ({bound} bound), "
            f"{_fmt_s(wall)} batch wall time"
        )
        rows = []
        for phase, total in sorted(totals.items(), key=lambda kv: -kv[1]):
            samples = per_batch[phase]
            share = total / wall if wall > 0 else 0.0
            rows.append(
                (
                    phase,
                    _fmt_s(total),
                    f"{share:6.1%}",
                    _fmt_s(_percentile(samples, 0.50)),
                    _fmt_s(_percentile(samples, 0.99)),
                )
            )
        out.append(
            _table(rows, ("phase", "total", "share", "p50/batch", "p99/batch"))
        )
        if wall > 0:
            out.append(
                f"tiled phases cover {tiled / wall:.1%} of batch wall time "
                "(journal_append/journal_fsync/hint_decode nest inside or "
                "overlap the tiles)"
            )
        # Pipeline overlap (ISSUE 15): per-batch stage records carry the
        # wall saved vs the serial stage sum when featurize / device /
        # commit-drain overlapped.
        ov = [b["overlap"] for b in batches if b.get("overlap")]
        if ov:
            saved = sum(o.get("saved_s", 0.0) for o in ov)
            serial = sum(o.get("serial_s", 0.0) for o in ov)
            overlapped = sum(1 for o in ov if o.get("saved_s", 0.0) > 0)
            out.append(
                f"pipeline overlap: {_fmt_s(saved)} wall saved vs "
                f"{_fmt_s(serial)} serial stage sum "
                f"({saved / serial:.1%} coverage) across "
                f"{overlapped}/{len(ov)} overlapped batches"
                if serial > 0
                else "pipeline overlap: no stage records"
            )

        # The span tree (framework/tracing.SpanSink): totals by name over
        # every batch that carries one, then the slowest batch's own tree
        # with each span at its measured start.
        spanned = [b for b in batches if b.get("spans")]
        if spanned:
            out.append("\n" + span_summary(spanned))
            slowest = max(spanned, key=lambda b: b.get("wall_s", 0.0))
            out.append(
                f"\nspan tree of the slowest batch (seq {slowest.get('seq', '?')}, "
                f"{slowest.get('pods', 0)} pods, {_fmt_s(slowest.get('wall_s', 0.0))}):"
            )
            out.extend(span_tree(slowest["spans"]))
            waits = [b["queue_wait"] for b in batches if b.get("queue_wait")]
            n = sum(w["pods"] for w in waits)
            if n:
                out.append(
                    f"queue wait (pop minus first held): mean "
                    f"{sum(w['sum_ms'] for w in waits) / n:.3f} ms over {n} pods, "
                    f"max {max(w['max_ms'] for w in waits):.3f} ms"
                )

        # Sampled per-plugin durations.
        plugins: dict[str, float] = {}
        for b in batches:
            for key, secs in (b.get("plugins") or {}).items():
                plugins[key] = plugins.get(key, 0.0) + secs
        if plugins:
            out.append("\nsampled per-plugin durations:")
            out.append(
                _table(
                    [
                        (k, _fmt_s(v))
                        for k, v in sorted(plugins.items(), key=lambda kv: -kv[1])
                    ],
                    ("plugin/point", "total (sampled)"),
                )
            )

    if markers:
        out.append("\ntransition markers:")
        for mk in markers:
            fields = {
                k: v
                for k, v in mk.items()
                if k not in ("kind", "seq", "ts", "event")
            }
            tail = " ".join(f"{k}={v}" for k, v in sorted(fields.items()))
            out.append(
                f"  seq={mk.get('seq', '?')} ts={mk.get('ts', '?')} "
                f"{mk.get('event', '?')}" + (f" {tail}" if tail else "")
            )

    # A host-merged document (ResyncingClient.flight()) nests the host's
    # own ring under "host": report it recursively.
    host = doc.get("host")
    if isinstance(host, dict) and host.get("records"):
        out.append("\n--- host ring ---")
        out.append(report(host))
    return "\n".join(out)


def span_summary(batches: list[dict]) -> str:
    """Seconds by span name over the batches' span lists, with the
    accumulated sub-times (a span's fifth element) beside them."""
    totals: dict[str, list] = {}
    for b in batches:
        for sp in b["spans"]:
            t = totals.setdefault(sp[0], [0, 0.0, {}])
            t[0] += 1
            t[1] += sp[2] * 1e-6
            for k, v in (sp[4] if len(sp) > 4 else {}).items():
                t[2][k] = t[2].get(k, 0) + v
    pods = sum(b.get("pods", 0) for b in batches) or 1
    rows = [
        (name, n, _fmt_s(secs), f"{secs / pods * 1e6:.1f}",
         " ".join(f"{k}={v}" for k, v in sorted(extra.items())))
        for name, (n, secs, extra) in sorted(totals.items(), key=lambda kv: -kv[1][1])
    ]
    return _table(rows, ("span", "count", "total", "us/pod", "accumulated"))


def span_tree(spans: list) -> list[str]:
    """One record's spans as an indented tree, in start order."""
    depth: list[int] = []
    lines = []
    for sp in spans:
        parent = sp[3]
        d = depth[parent] + 1 if 0 <= parent < len(depth) else 0
        depth.append(d)
        extra = " ".join(f"{k}={v}" for k, v in sorted((sp[4] if len(sp) > 4 else {}).items()))
        lines.append(
            f"  {'  ' * d}{sp[0]}  @{sp[1] / 1e3:.3f}ms  {sp[2] / 1e3:.3f}ms"
            + (f"  {extra}" if extra else "")
        )
    return lines


def _decision_latency_split(doc: dict) -> str:
    """Table of scheduler_slo_decision_latency_seconds by tenant and
    component (total / queue_wait / service), folded over phases from
    the artifact's registry dump.  The split separates admission wait
    (driver backlog or a fairness rate cap) from the scheduler's own
    service time — a capped tenant shows a fat queue_wait next to an
    unchanged service column."""
    hists = (doc.get("fleet_metrics") or {}).get("histograms") or {}
    cells = hists.get("scheduler_slo_decision_latency_seconds") or {}
    agg: dict[tuple, list] = {}
    for key, cell in cells.items():
        labels = dict(re.findall(r'(\w+)="([^"]*)"', key))
        comp = labels.get("component", "total")
        tenant = labels.get("tenant", "-")
        a = agg.setdefault((tenant, comp), [0, 0.0])
        a[0] += cell.get("count", 0)
        a[1] += cell.get("sum", 0.0)
    if not any(comp != "total" for _, comp in agg):
        return ""
    rows = []
    for tenant in sorted({t for t, _ in agg}):
        def _mean(comp):
            n, s = agg.get((tenant, comp), (0, 0.0))
            return (s / n * 1e3) if n else 0.0
        total, qwait, svc = (
            _mean("total"), _mean("queue_wait"), _mean("service")
        )
        n = agg.get((tenant, "total"), (0, 0.0))[0]
        rows.append(
            (
                tenant, n, f"{total:.1f}ms", f"{qwait:.1f}ms",
                f"{svc:.1f}ms",
                f"{100 * qwait / total:.0f}%" if total else "-",
            )
        )
    return _table(
        rows,
        ("tenant", "samples", "mean total", "queue_wait", "service",
         "wait share"),
    )


def soak_report(doc: dict) -> str:
    """Render one soak artifact: SLO, knee curve, journal growth,
    per-phase serving table."""
    out = []
    cfg = doc.get("config", {})
    out.append(
        f"soak artifact: seed={doc.get('seed')} pace={doc.get('pace')} "
        f"mix={cfg.get('mix')} nodes={cfg.get('nodes')} "
        f"rate={cfg.get('rate_pods_per_s')}/s wall={doc.get('wall_s')}s"
    )
    slo = doc.get("slo", {})
    out.append(
        f"\nSLO (sustained phase, budget {slo.get('budget_ms')}ms): "
        f"p50 {slo.get('p50_ms')}ms  p99 {slo.get('p99_ms')}ms  "
        f"p999 {slo.get('p999_ms')}ms  "
        f"violations {slo.get('violations')}/{slo.get('decisions')} "
        f"({100 * slo.get('violation_rate', 0):.2f}%)  "
        f"sustained {doc.get('sustained_pods_per_sec')} pods/s"
    )
    knee = doc.get("knee", {})
    if knee.get("points"):
        out.append(
            f"\nmiss-rate knee (miss cost {knee.get('miss_cost_ms')}ms, "
            f"knee @ {knee.get('knee_intensity_per_s')} invalidations/s):"
        )
        rows = [
            (
                p["intensity_per_s"], f"{p['hit_rate']:.1%}",
                p["decisions"], f"{p['p50_ms']}ms", f"{p['p99_ms']}ms",
            )
            for p in knee["points"]
        ]
        out.append(
            _table(rows, ("inval/s", "hit rate", "decisions", "p50", "p99"))
        )
    j = doc.get("journal", {})
    out.append(
        f"\njournal: wal max {j.get('wal_bytes_max')}B, "
        f"final {j.get('wal_bytes_final')}B, "
        f"{j.get('compactions_observed')} compaction cycles observed, "
        f"bounded={j.get('bounded')}"
    )
    asc = doc.get("autoscale")
    if asc:
        out.append(
            f"\nautoscale: {asc.get('splits')} split(s) / "
            f"{asc.get('merges')} merge(s) over "
            f"{asc.get('hot_serving_nodes')} hot nodes "
            f"(hot fraction {asc.get('hot_fraction')}), "
            f"deferrals {asc.get('deferrals')}"
        )
        for rec in asc.get("split_recovery", ()):
            pre, post = rec.get("pre", {}), rec.get("post_worst_of_pair", {})
            out.append(
                f"  split @{rec.get('t_split')}s shard {rec.get('shard')}"
                f"→+{rec.get('new_shard')}: p99 {pre.get('p99_ms')}ms → "
                f"{post.get('p99_ms')}ms "
                f"(recovered: {rec.get('p99_recovered')})"
            )
    tn = doc.get("tenants")
    if tn and tn.get("per_tenant"):
        out.append("\nper-tenant SLO split:")
        rows = [
            (
                name, t.get("arrivals"), t.get("decisions"),
                t.get("bound"), f"{t.get('p50_ms')}ms",
                f"{t.get('p99_ms')}ms", f"{t.get('p999_ms')}ms",
                t.get("violations"),
            )
            for name, t in sorted(tn["per_tenant"].items())
        ]
        out.append(
            _table(
                rows,
                ("tenant", "arrivals", "dec", "bound", "p50", "p99",
                 "p999", "viol"),
            )
        )
        counters = tn.get("counters") or {}
        if counters:
            out.append("admission-fairness counters (per tenant):")
            for name, c in sorted(counters.items()):
                pairs = " ".join(
                    f"{k}={int(v)}" for k, v in sorted(c.items())
                )
                out.append(f"  {name}: {pairs}")
        split = _decision_latency_split(doc)
        if split:
            out.append(
                "decision-latency component split (queue_wait = admission "
                "wait — backlog or rate cap; service = scheduler time):"
            )
            out.append(split)
    adm = doc.get("admission")
    if adm and adm.get("armed"):
        st = adm.get("status") or {}
        out.append(
            f"\nweighted-fair admission: vtime {st.get('vtime')}  "
            f"admitted {adm.get('admitted_total')} "
            f"(order sha {str(adm.get('admission_order_sha256', ''))[:12]}…)  "
            f"throttle hits {st.get('throttle_hits')}  aging escapes "
            f"{st.get('aging_escapes')}  starvation violations "
            f"{st.get('starvation_violations')}"
        )
        rows = [
            (
                name, t.get("weight"), t.get("credits"),
                t.get("vtime_lag"), t.get("pending"),
                t.get("oldest_wait_s"), t.get("slo"),
            )
            for name, t in sorted((st.get("tenants") or {}).items())
        ]
        if rows:
            out.append(
                _table(
                    rows,
                    ("tenant", "weight", "credits", "vt-lag", "pending",
                     "oldest-wait", "slo"),
                )
            )
    ft = doc.get("fleet_timeline")
    if ft:
        out.append(
            f"\nfleet timeline: {ft.get('events')} events merged "
            f"(sha {str(ft.get('timeline_sha256', ''))[:12]}…), "
            f"parallelism {(ft.get('wall') or {}).get('parallelism')}× — "
            f"render with `profile_report.py --fleet {ft.get('file')}`"
        )
        if ft.get("perfetto"):
            out.append(
                f"perfetto trace: {ft['perfetto']} (next to the merged "
                "doc; open in ui.perfetto.dev / chrome://tracing)"
            )
        mt = ft.get("measured_throughput") or {}
        if mt.get("matrix"):
            out.append(
                f"measured throughput ({mt.get('binds')} binds folded, "
                f"source sha {str(mt.get('source_sha256', ''))[:12]}…):"
            )
            out.append(_measured_matrix_table(mt["matrix"]))
    nl = doc.get("node_loss")
    if nl:
        lc = nl.get("lifecycle", {})
        out.append(
            f"\nnode loss: {nl.get('node_deaths')} deaths / "
            f"{nl.get('node_revives')} revives, "
            f"{lc.get('transitions')} lifecycle transitions "
            f"(states {lc.get('states')}), "
            f"{nl.get('evictions')} evictions, "
            f"{nl.get('gc_collected')} GC-collected, "
            f"{nl.get('reschedules')} pods rescheduled elsewhere, "
            f"{nl.get('lease_renewals')} lease renewals"
        )
    sb = doc.get("standby")
    if sb and sb.get("enabled"):
        pool = sb.get("pool") or {}
        lat = sb.get("promotion_latency") or {}
        out.append(
            f"\nwarm-standby pool: {sb.get('served_from_pool')} "
            f"promotion(s) served warm, {sb.get('cold_fallbacks')} cold "
            f"fallback(s) — warm promotion p50 {lat.get('p50_ms')}ms, "
            f"max {lat.get('max_ms')}ms; pool size "
            f"{pool.get('pool_size')}/{pool.get('size_target')}, "
            f"{pool.get('schema_stale_evictions')} schema-stale "
            f"eviction(s), {pool.get('misses')} miss(es)"
        )
        rows = [
            (
                p.get("t"), p.get("shard"), p.get("reason"),
                "warm" if p.get("from_pool") else "COLD",
                f"{p.get('latency_s')}s",
            )
            for p in sb.get("promotions") or ()
        ]
        if rows:
            out.append(
                _table(rows, ("t", "shard", "reason", "path", "latency"))
            )
    rs = doc.get("resume")
    if rs and rs.get("enabled"):
        out.append(
            f"\nresumable driver: checkpoint every "
            f"{rs.get('checkpoint_every_ops')} ops, generation "
            f"{rs.get('checkpoint_generation')}"
            + (
                f" — RESUMED from op {rs.get('resume_op_index')} "
                f"(digest verified: {rs.get('digest_verified')})"
                if rs.get("resumed")
                else ""
            )
        )
    for twin in doc.get("resume_twin_check") or ():
        out.append(
            f"  resume twin '{twin.get('name')}': kill@op"
            f"{twin.get('kill_after_op')} → resumed from op "
            f"{twin.get('resume_op_index')}, bit-identical "
            f"{twin.get('bit_identical')}"
        )
    iw = doc.get("incident_windows")
    if iw:
        steady = iw.get("steady") or {}
        out.append(
            f"\nincident windows ({iw.get('window_s')}s incident + "
            f"{iw.get('window_s')}s recovery; steady = outside all "
            f"windows): steady p50 {steady.get('p50_ms')}ms p99 "
            f"{steady.get('p99_ms')}ms over {steady.get('decisions')} "
            f"decisions"
        )
        rows = [
            (
                p.get("t"), p.get("family"),
                (p.get("incident") or {}).get("decisions"),
                f"{(p.get('incident') or {}).get('p99_ms')}ms",
                f"{(p.get('recovery') or {}).get('p99_ms')}ms",
            )
            for p in iw.get("incidents") or ()
        ]
        if rows:
            out.append(
                _table(
                    rows,
                    ("t", "incident", "dec", "p99-in", "p99-recovery"),
                )
            )
    svc = doc.get("service_slo")
    if svc and svc.get("worst_p99_ms") is not None:
        per = svc.get("per_tenant_service_p99_ms") or {}
        out.append(
            "\nservice-only p99 (cap-attributed queue wait stripped via "
            "the component split): worst "
            f"{svc.get('worst_p99_ms')}ms — "
            + ", ".join(f"{t} {v}ms" for t, v in per.items())
        )
    gates = doc.get("production_gates")
    if gates:
        out.append(
            f"\nproduction gates: starvation violations "
            f"{gates.get('starvation_violations')}, "
            f"{gates.get('promotions')} promotion(s) "
            f"({', '.join(gates.get('promotion_reasons') or ())}) all from "
            f"pool={gates.get('every_owner_from_pool')}, max promotion "
            f"{gates.get('max_promotion_latency_s')}s vs "
            f"{gates.get('cold_boot_baseline_s')}s cold boot, "
            f"{gates.get('splits')} split(s), all families active="
            f"{gates.get('all_families_active')}"
        )
    phases = doc.get("phases", [])
    if phases:
        out.append("\nper-phase serving:")
        rows = []
        for p in phases:
            lat = p.get("latency", {})
            rows.append(
                (
                    p["name"], p.get("invalidation_rate_per_s"),
                    p.get("decisions"), p.get("hits"), p.get("misses"),
                    f"{lat.get('p50_ms')}ms", f"{lat.get('p99_ms')}ms",
                    p.get("retired"),
                )
            )
        out.append(
            _table(
                rows,
                ("phase", "inval/s", "dec", "hits", "miss", "p50", "p99",
                 "retired"),
            )
        )
    det = doc.get("determinism", {})
    if det:
        out.append(
            f"\ndeterminism: arrivals sha {det.get('arrival_sha256', '')[:12]}… "
            f"bindings sha {det.get('bindings_sha256', '')[:12]}…"
            + (
                "  (cross-check: identical)"
                if (doc.get("determinism_check") or {}).get(
                    "bindings_identical"
                )
                else ""
            )
        )
    if doc.get("incidents"):
        out.append(f"incidents: {', '.join(doc['incidents'])}")
    return "\n".join(out)


def _measured_matrix_table(matrix: dict) -> str:
    """Render one measured (or synthetic) milli-throughput matrix —
    workload-class rows × accelerator-class columns."""
    accels = sorted({a for row in matrix.values() for a in row})
    rows = [
        (wclass, *(row.get(a, "-") for a in accels))
        for wclass, row in sorted(matrix.items())
    ]
    return _table(rows, ("workload class", *accels))


def _load_flight_module():
    """Import ``kubernetes_tpu/framework/flight.py`` by FILE PATH (it is
    stdlib-only; the package root imports JAX and must stay out)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "..", "kubernetes_tpu", "framework", "flight.py",
    )
    spec = importlib.util.spec_from_file_location("_tpu_flight", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fleet_report(doc: dict, timeline_tail: int = 40) -> str:
    """Render one merged fleet document (framework/flight.merge_fleet):
    per-component totals, overlap/parallelism, critical-path
    attribution, the logical-clock timeline tail, and slow-span trees."""
    out: list[str] = []
    comps = doc.get("components", {})
    # A SOAK artifact's fleet_timeline block stores the count under
    # "events"; a raw merge document under "timeline_events".
    n_events = doc.get("timeline_events", doc.get("events"))
    out.append(
        f"fleet flight merge: {len(comps)} components, "
        f"{n_events} timeline events "
        f"(timeline sha {str(doc.get('timeline_sha256', ''))[:12]}…)"
    )
    if doc.get("perfetto"):
        # The fleet soak writes the trace-event twin next to the merged
        # doc and stamps the filename here.
        out.append(
            f"perfetto trace: {doc['perfetto']} (open in ui.perfetto.dev "
            "/ chrome://tracing)"
        )
    rows = []
    for name, c in sorted(comps.items()):
        phases = ", ".join(
            f"{k} {_fmt_s(v)}" for k, v in sorted(
                (c.get("phases") or {}).items(), key=lambda kv: -kv[1]
            )
        )
        rows.append(
            (name, c.get("batches", 0), c.get("markers", 0),
             _fmt_s(c.get("busy_s", 0.0)), phases or "-")
        )
    out.append(
        _table(rows, ("component", "batches", "markers", "busy", "phases"))
    )
    wall = doc.get("wall", {})
    out.append(
        f"\nfleet wall: components busy {_fmt_s(wall.get('busy_s_total', 0))} "
        f"over {_fmt_s(wall.get('union_busy_s', 0))} union busy time — "
        f"overlap {_fmt_s(wall.get('overlap_s', 0))}, "
        f"parallelism {wall.get('parallelism', 0)}×"
    )
    crit = doc.get("critical_path") or doc.get("critical_path_top") or []
    if crit:
        out.append("\ncritical path (which slice gated fleet progress):")
        out.append(
            _table(
                [
                    (c["component"], c["phase"], _fmt_s(c["seconds"]),
                     f"{c['share']:.1%}")
                    for c in crit
                ],
                ("component", "phase", "seconds", "share"),
            )
        )
    timeline = doc.get("timeline") or []
    if timeline:
        tail = timeline[-timeline_tail:]
        out.append(
            f"\ntimeline (logical clock; last {len(tail)} of "
            f"{len(timeline)}):"
        )
        for e in tail:
            extra = {
                k: v
                for k, v in e.items()
                if k not in ("component", "seq", "kind", "lc")
            }
            tail_s = " ".join(f"{k}={v}" for k, v in sorted(extra.items()))
            out.append(
                f"  lc={e.get('lc', '-')} {e['component']}#{e.get('seq')} "
                f"{e.get('kind')}" + (f" {tail_s}" if tail_s else "")
            )
    for span in doc.get("slow_spans") or []:
        out.append("\nslow span (joined router→owner→sidecar tree):")
        parts: list[str] = []
        _render_span(span, parts, "  ")
        out.extend(parts)
    return "\n".join(out)


def _render_span(span: dict, parts: list[str], indent: str) -> None:
    """Serialized span tree renderer (tracing.render_span_dict's shape,
    re-implemented here so the report stays repo-free)."""
    ids = f"trace={span.get('trace_id')} span={span.get('span_id')}"
    if span.get("parent_span_id"):
        ids += f" parent={span['parent_span_id']}"
    fields = " ".join(
        f"{k}={v}" for k, v in (span.get("fields") or {}).items()
    )
    parts.append(
        f'{indent}"{span.get("name")}" '
        f"total={span.get('duration_ms', 0)}ms {ids}"
        + (f" {fields}" if fields else "")
    )
    for msg, off in span.get("steps") or ():
        parts.append(f"{indent}  {msg} (@{off}ms)")
    for child in span.get("children") or ():
        _render_span(child, parts, indent + "  ")


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    fleet = False
    prod = False
    if args and args[0] == "--prod":
        # Force the soak rendering (standby pool, resume twins, incident
        # windows, production gates) — the production-day artifact routes
        # there by metric anyway; the flag covers partial/renamed docs.
        prod = True
        args = args[1:]
    if args and args[0] == "--fleet":
        fleet = True
        args = args[1:]
    if not args or (not fleet and len(args) != 1):
        print(__doc__.strip(), file=sys.stderr)
        return 2

    def load(arg: str) -> dict:
        if arg == "-":
            return json.load(sys.stdin)
        with open(arg, "r", encoding="utf-8") as f:
            return json.load(f)

    if fleet:
        if len(args) == 1:
            doc = load(args[0])
            if doc.get("metric") == "fleet_flight_merge":
                print(fleet_report(doc))
                return 0
            if doc.get("fleet_timeline"):
                # A fleet SOAK artifact: render its merged-timeline
                # block (the full merged document sits next to the
                # artifact under the file it names).
                print(fleet_report(doc["fleet_timeline"]))
                return 0
            # A single raw dump still merges (degenerate fleet of one).
            docs = [doc]
        else:
            docs = [load(a) for a in args]
        flight_mod = _load_flight_module()
        print(fleet_report(flight_mod.merge_fleet(docs)))
        return 0
    doc = load(args[0])
    if isinstance(doc.get("parsed"), dict):
        # A recorded-trajectory wrapper (the driver's capture format).
        doc = doc["parsed"]
    if prod or str(doc.get("metric", "")).startswith(
        ("soak_", "fleet_soak_", "tenant_soak")
    ) or ("knee" in doc and "slo" in doc):
        print(soak_report(doc))
    else:
        print(report(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
