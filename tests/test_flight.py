"""Flight recorder + per-phase latency attribution (ISSUE 5 tentpole).

The acceptance claims:

- one bounded-memory ring record per scheduled batch, whose tiled phase
  timings (featurize/device/commit/snapshot/other) sum to the batch's
  wall time;
- `scheduler_phase_duration_seconds{phase}` (and the sampled
  `scheduler_plugin_duration_seconds{plugin,extension_point}`)
  histograms appear in the registry exposition;
- dumps fire automatically on quarantine/engine fault and are readable
  via the `flight` frame, `GET /debug/flight`, and the `flight` CLI
  subcommand — all serving the same document;
- FailedScheduling/Preempted events carry the originating trace_id so
  they join their batch's flight record;
- /healthz tells degraded-but-serving from healthy (breaker/degraded
  state + journal-armed status), and a HOST-side HTTP listener keeps
  answering /metrics and /events while the breaker is open (the PR 2
  in-process guarantee, now covered over HTTP).
"""

import json
import os
import subprocess
import sys
import tempfile
import urllib.request

import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.faults import FaultPlan
from kubernetes_tpu.framework.config import fit_only_profile
from kubernetes_tpu.framework.flight import FlightRecorder
from kubernetes_tpu.scheduler import TPUScheduler
from kubernetes_tpu.sidecar.host import ResyncingClient
from kubernetes_tpu.sidecar.metrics_http import ObservabilityHTTPServer
from kubernetes_tpu.sidecar.server import SidecarClient, SidecarServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _node(name, cpu="8"):
    return make_node(name).capacity(
        {"cpu": cpu, "memory": "16Gi", "pods": 110}
    ).obj()


def _pod(name, cpu="100m"):
    return make_pod(name).req({"cpu": cpu, "memory": "64Mi"}).obj()


def _mk_sched(**kw):
    kw.setdefault("profile", fit_only_profile())
    kw.setdefault("batch_size", 8)
    return TPUScheduler(**kw)


def _serve(**kw):
    path = tempfile.mktemp(suffix=".sock")
    srv = SidecarServer(path, scheduler=_mk_sched(), **kw)
    srv.serve_background()
    return path, srv


def _http_get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return r.status, r.read()


# ---------------------------------------------------------------------------
# The ring itself


def test_ring_is_bounded_and_orders_markers_with_batches():
    fr = FlightRecorder(capacity=4, component="t")
    for i in range(9):
        fr.record_batch({"pods": i})
    fr.record_marker("breaker_trip", consecutive_failures=3)
    recs = fr.records()
    assert len(recs) == 4  # bounded: newest 4 of 10
    assert fr.snapshot()["recorded"] == 10
    assert recs[-1]["kind"] == "marker"
    assert recs[-1]["event"] == "breaker_trip"
    assert recs[-1]["consecutive_failures"] == 3
    # seq is monotonic across kinds — the ring reads as one timeline.
    assert [r["seq"] for r in recs] == sorted(r["seq"] for r in recs)
    assert fr.records(limit=2) == recs[-2:]


def test_batch_record_phases_tile_the_batch_wall_time():
    s = _mk_sched()
    for i in range(3):
        s.add_node(_node(f"n{i}"))
    for i in range(6):
        s.add_pod(_pod(f"p{i}"))
    out = s.schedule_batch()
    assert sum(1 for o in out if o.node_name) == 6
    (rec,) = s.flight.records()
    assert rec["kind"] == "batch"
    assert rec["pods"] == 6 and rec["scheduled"] == 6
    assert rec["trace_id"] and rec["span_id"]
    phases = rec["phases"]
    for phase in ("featurize", "device", "commit", "other"):
        assert phase in phases
    # The tiling contract: segments share boundary timestamps, so they
    # sum to the batch wall time (within rounding).
    assert abs(sum(phases.values()) - rec["wall_s"]) < 5e-3
    assert phases["device"] > 0


def test_phase_and_plugin_histograms_render_in_the_registry():
    s = _mk_sched()
    for i in range(3):
        s.add_node(_node(f"n{i}"))
    # Enough single-pod batches to pass the 1-in-10 per-site plugin
    # sampling gate at least once; distinct labels defeat the featurize
    # memo (a memo hit skips the per-op loop the sampler times).
    for i in range(12):
        s.add_pod(
            make_pod(f"p{i}")
            .req({"cpu": "100m", "memory": "64Mi"})
            .label("uniq", f"u{i}")
            .obj()
        )
        s.schedule_batch()
    text = s.metrics.registry.render_text()
    assert 'scheduler_phase_duration_seconds_bucket{le=' not in text  # labeled
    assert 'scheduler_phase_duration_seconds_bucket{' in text
    assert 'phase="device"' in text
    assert 'phase="featurize"' in text
    assert 'scheduler_plugin_duration_seconds_bucket{' in text
    assert 'extension_point="Featurize"' in text
    # The summary carries the same families (the dump/bench surface).
    summ = s.metrics.registry.summary()
    assert "scheduler_phase_duration_seconds" in summ["histograms"]


def test_quarantine_auto_dumps_and_event_joins_by_trace_id(tmp_path):
    s = _mk_sched()
    s.flight.dump_dir = str(tmp_path)
    FaultPlan().add_rule("engine", pod="default/bad").install_engine(s)
    for i in range(2):
        s.add_node(_node(f"n{i}"))
    s.add_pod(_pod("good"))
    s.add_pod(_pod("bad"))
    out = s.schedule_batch()
    by_uid = {o.pod.uid: o for o in out}
    assert by_uid["default/good"].node_name
    assert by_uid["default/bad"].node_name is None
    # Markers on the ring: the engine fault and the quarantine decision.
    events = [r["event"] for r in s.flight.records() if r["kind"] == "marker"]
    assert "engine_fault" in events and "quarantine" in events
    # ONE auto-dump per incident (written at the outermost recovery
    # exit, so it carries the quarantine markers too) — not a file per
    # bisect halving or per poison pod.
    dumps = sorted(os.listdir(tmp_path))
    assert len(dumps) == 1 and "engine_fault" in dumps[0]
    with open(tmp_path / dumps[0]) as f:
        doc = json.load(f)
    marks = [r for r in doc["records"] if r.get("event") == "quarantine"]
    assert marks and marks[0]["pod"] == "default/bad"
    # The FailedScheduling event carries the originating trace id, which
    # matches the quarantine marker's — event ↔ flight-record join.
    ev = [
        e for e in s.events.list()
        if e["reason"] == "FailedScheduling" and e["object"] == "default/bad"
    ]
    assert ev and ev[0]["trace_id"] == marks[0]["trace_id"]


def test_preempted_event_carries_trace_id():
    s = TPUScheduler(profile=fit_only_profile(), batch_size=4)
    s.add_node(_node("n0", cpu="2"))
    s.add_pod(make_pod("low").req({"cpu": "2"}).priority(1).obj())
    s.schedule_all_pending()
    s.add_pod(make_pod("vip").req({"cpu": "2"}).priority(1000).obj())
    s.schedule_all_pending(wait_backoff=True)
    ev = [e for e in s.events.list() if e["reason"] == "Preempted"]
    assert ev and ev[0]["trace_id"]


# ---------------------------------------------------------------------------
# The three read surfaces serve one document


def test_flight_frame_http_and_cli_agree(capsys):
    path, srv = _serve(http_port=0)
    client = SidecarClient(path)
    try:
        client.add("Node", _node("n0"))
        client.schedule([_pod("p0")], drain=True)
        frame = client.flight()
        assert frame["count"] == 1
        (rec,) = frame["records"]
        assert rec["phases"]["device"] > 0
        status, body = _http_get(srv.http.port, "/debug/flight")
        assert status == 200
        http_doc = json.loads(body)
        assert http_doc["records"] == frame["records"]
        # ?limit= keeps the newest N.
        status, body = _http_get(srv.http.port, "/debug/flight?limit=1")
        assert json.loads(body)["count"] == 1
        # CLI subcommand prints the same document.
        from kubernetes_tpu.__main__ import main as cli_main

        assert cli_main(["flight", "--socket", path]) == 0
        cli_doc = json.loads(capsys.readouterr().out)
        assert cli_doc["records"] == frame["records"]
    finally:
        client.close()
        srv.close()


def test_host_flight_merges_wire_ring_and_round_trip_series():
    path, srv = _serve()
    client = ResyncingClient(path, deadline_s=30.0)
    try:
        client.add("Node", _node("n0"))
        client.schedule([_pod("p0")], drain=True)
        doc = client.flight()
        assert doc["component"] == "scheduler" and doc["count"] >= 1
        host = doc["host"]
        assert host["component"] == "host"
        (rec,) = host["records"]
        assert rec["phases"]["wire"] > 0 and rec["bound"] == 1
        text = client.registry.render_text()
        assert "scheduler_sidecar_round_trip_duration_seconds_bucket" in text
        assert 'call="schedule"' in text
    finally:
        client.close()
        srv.close()


# ---------------------------------------------------------------------------
# /healthz: degraded-but-serving vs healthy; journal-armed status


def test_healthz_reports_journal_armed_both_ways(tmp_path):
    from kubernetes_tpu.journal import Journal
    from kubernetes_tpu.sidecar.metrics_http import health_state

    s = _mk_sched()
    assert health_state(s)["journal_armed"] is False
    s.attach_journal(Journal(str(tmp_path), epoch=1))
    state = health_state(s)
    assert state["journal_armed"] is True
    assert state["journal"]["epoch"] == 1


def test_degraded_host_serves_http_metrics_events_healthz_and_flight():
    """Satellite: the HTTP path of the PR 2 degraded-observability
    guarantee — /metrics and /events keep answering while the breaker is
    open, and /healthz says degraded-but-serving."""
    plan = (
        FaultPlan(seed=1)
        .add_rule("hang", op="schedule", every=True)
        .add_rule("hang", op="health", every=True)
    )
    path, srv = _serve()
    client = ResyncingClient(
        path,
        deadline_s=0.4,
        retry_interval_s=0.01,
        probe_interval_s=0.05,
        breaker_threshold=3,
        socket_wrapper=plan.wrap,
        fallback_factory=_mk_sched,
    )
    http = ObservabilityHTTPServer(client=client)
    http.serve_background()
    try:
        client.add("Node", _node("n0"))
        res = client.schedule([make_pod("p0").req({"cpu": "2"}).obj()])
        assert client.degraded and res[0].node_name  # degraded, serving
        # /healthz: degraded-but-serving, with the breaker counters.
        status, body = _http_get(http.port, "/healthz")
        assert status == 200
        state = json.loads(body)
        assert state["healthy"] is True
        assert state["host"]["sidecar_state"] == "degraded"
        assert state["host"]["breaker"]["trips"] == 1
        assert state["host"]["journal_armed"] is False
        # /metrics: the host registry (outage series) answers.
        status, body = _http_get(http.port, "/metrics")
        assert status == 200
        text = body.decode()
        assert 'scheduler_sidecar_state{state="degraded"} 1' in text
        assert "scheduler_degraded_dispatches_total 1" in text
        # /events: the fallback engine's ring answers.
        status, body = _http_get(http.port, "/events")
        assert status == 200
        events = json.loads(body)
        assert any(e["reason"] == "Scheduled" for e in events)
        # /debug/flight: the host ring, with the breaker-trip marker.
        status, body = _http_get(http.port, "/debug/flight")
        assert status == 200
        doc = json.loads(body)
        marks = [
            r for r in doc["host"]["records"] if r.get("kind") == "marker"
        ]
        assert any(m["event"] == "breaker_trip" for m in marks)
    finally:
        http.close()
        client.close()
        srv.close()


def test_breaker_trip_auto_dumps_host_ring(tmp_path):
    plan = (
        FaultPlan(seed=3)
        .add_rule("hang", op="schedule", every=True)
        .add_rule("hang", op="health", every=True)
    )
    path, srv = _serve()
    client = ResyncingClient(
        path,
        deadline_s=0.3,
        retry_interval_s=0.01,
        probe_interval_s=0.05,
        breaker_threshold=3,
        socket_wrapper=plan.wrap,
        fallback_factory=_mk_sched,
    )
    client.flight_recorder.dump_dir = str(tmp_path)
    try:
        client.add("Node", _node("n0"))
        client.schedule([_pod("p0")])
        assert client.degraded
        dumps = [d for d in os.listdir(tmp_path) if "breaker_trip" in d]
        assert dumps
        with open(tmp_path / dumps[0]) as f:
            doc = json.load(f)
        assert any(
            r.get("event") == "breaker_trip" for r in doc["records"]
        )
    finally:
        client.close()
        srv.close()


# ---------------------------------------------------------------------------
# profile_report.py


def test_profile_report_renders_phase_attribution_table(tmp_path):
    s = _mk_sched()
    for i in range(2):
        s.add_node(_node(f"n{i}"))
    for i in range(6):
        s.add_pod(_pod(f"p{i}"))
    s.schedule_all_pending()
    dump = s.flight.dump("manual", path=str(tmp_path / "dump.json"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "profile_report.py"), dump],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "phase" in proc.stdout and "device" in proc.stdout
    assert "share" in proc.stdout


# ---------------------------------------------------------------------------
# bench surface


def test_run_workload_reports_phase_attribution_coverage():
    """The bench acceptance bar in miniature: the tiled phases cover
    >= 95% of the measured wall time on a real (small) workload."""
    from kubernetes_tpu.benchmarks.harness import Workload, run_workload

    w = Workload(
        name="flight_mini",
        baseline_pods_per_sec=0.0,
        build=lambda: _mk_sched(batch_size=32),
        nodes=lambda s: [s.add_node(_node(f"n{i}")) for i in range(8)],
        warmup=lambda s: [s.add_pod(_pod(f"w{i}")) for i in range(32)],
        measured=lambda s: [s.add_pod(_pod(f"m{i}")) for i in range(96)]
        and 96,
    )
    r = run_workload(w)
    assert r["scheduled"] == 96
    pa = r["phase_attribution"]
    assert pa["phases"]["device"] > 0
    # the tiling, and not the spans inside it a second time
    assert 0.95 <= pa["coverage"] <= 1.001
    assert not [k for k in pa["phases"] if "/" in k]


def test_live_registry_families_are_all_cataloged(tmp_path):
    """The README catalog (generated statically) must cover every family
    the LIVE registry renders — scheduler, journal, and host-side series
    alike (the catalog going stale fails here, not in production)."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import check_lint

    tp = check_lint.load_tpulint()
    cataloged = {e["name"] for e in tp.collect_catalog(REPO)}

    from kubernetes_tpu.journal import Journal

    s = _mk_sched()
    s.attach_journal(Journal(str(tmp_path), epoch=1))
    for i in range(2):
        s.add_node(_node(f"n{i}"))
    for i in range(12):
        s.add_pod(
            make_pod(f"p{i}")
            .req({"cpu": "100m", "memory": "64Mi"})
            .label("uniq", f"u{i}")
            .obj()
        )
        s.schedule_batch()
    path, srv = _serve()
    client = ResyncingClient(path, deadline_s=30.0)
    try:
        client.add("Node", _node("h0"))
        client.schedule([_pod("hp0")], drain=True)
        rendered = s.metrics.registry.render_text()
        rendered += client.registry.render_text()
    finally:
        client.close()
        srv.close()
    live = {
        line.split()[2]
        for line in rendered.splitlines()
        if line.startswith("# TYPE ")
    }
    missing = live - cataloged
    assert not missing, (
        f"live registry families missing from the catalog: {sorted(missing)}"
        " — regenerate README's section with scripts/check_lint.py --catalog"
    )
