#!/usr/bin/env python3
"""chip_smoke.py — the served path, end to end, on the chip.

The quickest proof that the system still starts on the accelerator.  This
process is the CLIENT: it never initialises a JAX backend (asserted before
it exits).  Each phase runs in a child process of its own — the only
process touching the chip at that time — under a deadline; a phase that
fails or times out prints the tail of its child's log and the smoke exits
non-zero; with no accelerator it prints no result line at all.

  serve   spawn ``python -m kubernetes_tpu serve`` as a deployment would
          (journal fsync always, speculative frontend, pipeline depth 2,
          batch 4096 / chunk 64, full default profile) on the cluster of
          upstream scheduler_perf SchedulingBasic/5000Nodes_10000Pods
          (5,000 nodes of 16 CPU / 64 Gi / 110 pods in 3 zones; pods of
          900m / 2 Gi).  Over a bare SidecarClient: add the nodes, one
          warm-up batch, three drain=True backlog requests, then one
          coalesced PendingPods hint followed by one-pod drain=False
          calls (what go/tpubatchscore/plugin.go does each cycle).
          Checks: the server's own health frame names the device; every
          pod of every request is bound to a node that exists; the
          client's own per-node sums stay within CPU / memory / pod
          capacity; no engine fault, nothing quarantined, host mirror ==
          device mirror, the journal fsync'd.  Then SIGTERM the server
          and read every acknowledged binding back out of the journal
          through the ``recover`` entry point.
  parity  ``scripts/parity_ab.py --default``: full default profile with
          preemption, volumes and DRA over the wire against the scalar
          oracle — bindings, nominations and victim sets bit-identical.

Without an accelerator this FAILS.  ``--rehearsal`` (with
JAX_PLATFORMS=cpu, at tiny sizes) runs the same phases on the CPU and
says so: ``"on_chip": false``.

    python chip_smoke.py                      # the chip, full size
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal --nodes 64 \\
        --batch-size 64 --chunk-size 8 --drain-pods 64 --hint-pods 32 \\
        --parity-nodes 100 --parity-pods 120

(the parity fixture needs about 100 nodes: below that it fails on the
fixture, at the parent commit too, not on the engine).

Stdout on success is two lines.  The LAST is the verdict and nothing else,
the device as the serving process's JAX reported it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The line before it is the summary, one JSON object (``platform`` /
``device_kind`` / ``n_devices``, ``on_chip``, per-phase verdicts and
counts, compile-cache entries before/after, wall seconds of each request,
``"claim": null``).  The summary also lands in ``<out>/summary.json`` pass
or fail, next to each child's log and the flight dumps.  On failure the
summary goes to stderr; stdout carries ``{"ok": false, "device": ...}``
only if a serving process got far enough to name its device, else nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

NODE_CPU_MILLI = 16_000
NODE_MEM_BYTES = 64 << 30
NODE_PODS = 110
POD_CPU_MILLI = 900
POD_MEM_BYTES = 2 << 30


class SmokeFailure(Exception):
    """One phase failed; ``log`` names the child log whose tail to show."""

    def __init__(self, msg: str, log: str | None = None):
        super().__init__(msg)
        self.log = log


def _cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )


def _cache_entries() -> int:
    try:
        return sum(
            1 for n in os.listdir(_cache_dir()) if not n.endswith("-atime")
        )
    except OSError:
        return 0


def _tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")
    except OSError:
        return ""


def _metric_total(text: str, name: str) -> float:
    """Sum of one family's samples in a Prometheus text scrape."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):][:1] in (" ", "{"):
            total += float(line.rsplit(" ", 1)[1])
    return total


class Children:
    """Every process the smoke starts, each in its own session so one
    killpg reaps whatever it spawned; ``stop_all`` runs on every exit."""

    def __init__(self):
        self.procs: list[subprocess.Popen] = []

    def spawn(self, argv, log_path, env, stdout=None) -> subprocess.Popen:
        log = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                argv, stdout=stdout or log, stderr=log, cwd=ROOT, env=env,
                start_new_session=True,
            )
        finally:
            log.close()  # the child holds its own dup
        self.procs.append(proc)
        return proc

    def stop(self, proc: subprocess.Popen, sig=signal.SIGTERM,
             grace_s: float = 60.0) -> int | None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, sig)
            except ProcessLookupError:
                pass
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        return proc.returncode

    def stop_all(self) -> None:
        for proc in self.procs:
            self.stop(proc, signal.SIGKILL, grace_s=10.0)


def _run_child(children, argv, log_path, env, deadline: float, what: str) -> str:
    """Run one child to completion under the deadline; its stdout."""
    out_path = log_path + ".stdout"
    with open(out_path, "wb") as out:
        proc = children.spawn(argv, log_path, env, stdout=out)
        try:
            rc = proc.wait(timeout=max(deadline - time.monotonic(), 1.0))
        except subprocess.TimeoutExpired:
            children.stop(proc, signal.SIGKILL, grace_s=10.0)
            raise SmokeFailure(f"{what} timed out", log_path)
    with open(out_path, encoding="utf-8", errors="replace") as f:
        stdout = f.read()
    if rc != 0:
        raise SmokeFailure(f"{what} exited rc={rc}", log_path)
    return stdout


def _make_nodes(n: int, rng: random.Random):
    from kubernetes_tpu.api.wrappers import make_node

    nodes = [
        make_node(f"node-{i}")
        .capacity({"cpu": "16", "memory": "64Gi", "pods": NODE_PODS})
        .label("topology.kubernetes.io/zone", f"zone-{i % 3}")
        .obj()
        for i in range(n)
    ]
    rng.shuffle(nodes)  # arrival order (hence device row) from the seed
    return nodes


def _make_pods(tag: str, n: int, rng: random.Random):
    from kubernetes_tpu.api.wrappers import make_pod

    return [
        make_pod(f"{tag}-{i}-{rng.getrandbits(32):08x}")
        .req({"cpu": "900m", "memory": "2Gi"})
        .obj()
        for i in range(n)
    ]


def phase_serve(args, out: str, env: dict, children: Children,
                deadline: float, summary: dict) -> None:
    from kubernetes_tpu.sidecar import SidecarClient

    rng = random.Random(args.seed)
    log_path = os.path.join(out, "serve.log")
    journal_dir = os.path.join(out, "journal")
    shutil.rmtree(journal_dir, ignore_errors=True)
    sock_dir = tempfile.mkdtemp(prefix="smoke-")  # unix paths are short
    sock = os.path.join(sock_dir, "serve.sock")
    serve_argv = [
        sys.executable, "-m", "kubernetes_tpu", "serve", "--socket", sock,
        "--journal-dir", journal_dir, "--journal-fsync", "always",
        "--speculate", "--pipeline-depth", "2",
        "--batch-size", str(args.batch_size),
        "--chunk-size", str(args.chunk_size),
    ]
    ph = summary["phases"]["serve"]
    acked: dict[str, str] = {}
    requests: list[dict] = []
    ph["requests"] = requests  # filled as they are answered
    t_spawn = time.monotonic()
    proc = children.spawn(serve_argv, log_path, env)
    client = None
    try:
        while not os.path.exists(sock):
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"serve exited rc={proc.returncode} before listening",
                    log_path,
                )
            if time.monotonic() > deadline:
                raise SmokeFailure("serve never bound its socket", log_path)
            time.sleep(0.05)
        ph["listening_s"] = round(time.monotonic() - t_spawn, 3)
        client = SidecarClient(
            sock, deadline_s=max(deadline - time.monotonic(), 1.0)
        )
        # The device, as reported by the process that owns it.
        health = client.health()
        for key in ("platform", "device_kind", "n_devices"):
            if key not in health:
                raise SmokeFailure(f"health frame carries no {key!r}", log_path)
            summary[key] = health[key]
        summary["on_chip"] = health["platform"] == "tpu"
        if not summary["on_chip"] and not args.rehearsal:
            raise SmokeFailure(
                f"serve runs on {health['platform']!r}, not the chip",
                log_path,
            )

        nodes = _make_nodes(args.nodes, rng)
        node_names = {n.name for n in nodes}
        client.add_stream("Node", nodes)
        ph["nodes"] = len(nodes)

        def take(results, pods, what: str) -> None:
            by_uid = {r.pod_uid: r for r in results}
            for p in pods:
                r = by_uid.get(p.uid)
                if r is None or not r.node_name:
                    raise SmokeFailure(
                        f"{what}: pod {p.uid} came back unbound "
                        f"({list(r.unschedulable_plugins) if r else 'no result'})",
                        log_path,
                    )
                if r.node_name not in node_names:
                    raise SmokeFailure(
                        f"{what}: pod {p.uid} bound to unknown node "
                        f"{r.node_name!r}", log_path,
                    )
                if acked.setdefault(p.uid, r.node_name) != r.node_name:
                    raise SmokeFailure(
                        f"{what}: pod {p.uid} answered twice, differently",
                        log_path,
                    )

        def drain(tag: str, n: int) -> None:
            pods = _make_pods(tag, n, rng)
            t0 = time.monotonic()
            results = client.schedule(pods, drain=True)
            dt = time.monotonic() - t0
            take(results, pods, tag)
            requests.append(
                {"request": tag, "pods": n, "seconds": round(dt, 4)}
            )

        drain("warmup", args.drain_pods)
        ph["cold_start_to_first_answer_s"] = round(
            time.monotonic() - t_spawn, 3
        )
        for k in range(args.drains):
            drain(f"drain{k}", args.drain_pods)

        # The plugin's per-cycle shape: one coalesced hint frame, then one
        # Schedule call per pod.
        pods = _make_pods("hinted", args.hint_pods, rng)
        t0 = time.monotonic()
        client.add_pending_batch(pods)
        requests.append({
            "request": "pending_pods_hint", "pods": len(pods),
            "seconds": round(time.monotonic() - t0, 4),
        })
        per_call: list[float] = []
        t0 = time.monotonic()
        for p in pods:
            t1 = time.monotonic()
            results = client.schedule([p], drain=False)
            per_call.append(time.monotonic() - t1)
            take(results, [p], "one-pod call")
        per_call.sort()
        requests.append({
            "request": "one_pod_calls", "pods": len(pods),
            "seconds": round(time.monotonic() - t0, 4),
            "call_p50_s": round(per_call[len(per_call) // 2], 6),
            "call_max_s": round(per_call[-1], 6),
        })
        ph["pods_sent"] = (
            args.drain_pods * (args.drains + 1) + args.hint_pods
        )
        ph["pods_bound"] = len(acked)

        # The client's own capacity ledger.
        per_node: dict[str, int] = {}
        for node in acked.values():
            per_node[node] = per_node.get(node, 0) + 1
        worst = max(per_node.values())
        if (
            worst * POD_CPU_MILLI > NODE_CPU_MILLI
            or worst * POD_MEM_BYTES > NODE_MEM_BYTES
            or worst > NODE_PODS
        ):
            raise SmokeFailure(
                f"a node holds {worst} pods — over its capacity", log_path
            )
        ph["max_pods_on_a_node"] = worst

        metrics = client.metrics()
        dump = client.dump()
        ph["engine_faults"] = int(
            _metric_total(metrics, "scheduler_engine_faults_total")
        )
        ph["quarantined"] = len(dump["queue"]["quarantine"])
        ph["mirror_equal"] = bool(dump["mirror_equal"])
        ph["journal_fsyncs"] = int(
            _metric_total(metrics, "scheduler_journal_fsync_total")
        )
        ph["speculation"] = dump.get("speculation")
        if ph["engine_faults"] or ph["quarantined"]:
            raise SmokeFailure(
                f"engine_faults={ph['engine_faults']} "
                f"quarantined={ph['quarantined']}", log_path,
            )
        if not ph["mirror_equal"]:
            raise SmokeFailure("host and device mirrors differ", log_path)
        if ph["journal_fsyncs"] <= 0:
            raise SmokeFailure("the journal never fsync'd", log_path)
    finally:
        if client is not None:
            client.close()
        ph["serve_rc"] = children.stop(proc, signal.SIGTERM)
        shutil.rmtree(sock_dir, ignore_errors=True)

    # Durability: every acknowledged binding must come back out of the
    # journal, through the journal's own reader.
    stdout = _run_child(
        children,
        [sys.executable, "-m", "kubernetes_tpu", "recover",
         "--journal-dir", journal_dir,
         "--batch-size", str(args.batch_size),
         "--chunk-size", str(args.chunk_size)],
        os.path.join(out, "recover.log"), env, deadline, "recover",
    )
    doc = json.loads(stdout[stdout.index("{"):])
    # Binds replayed onto snapshot-held nodes, plus those parked until the
    # host relists their node (no snapshot yet): both are durable.
    recovered = {**doc["pending_bindings"], **doc["bindings"]}
    ph["journal_bindings"] = len(recovered)
    lost = [u for u, n in acked.items() if recovered.get(u) != n]
    if lost or len(recovered) != len(acked):
        raise SmokeFailure(
            f"{len(lost)} acknowledged binding(s) not in the journal "
            f"(e.g. {lost[:3]}); journal holds {len(recovered)}, "
            f"acknowledged {len(acked)}",
            os.path.join(out, "recover.log"),
        )


def phase_parity(args, out: str, env: dict, children: Children,
                 deadline: float, summary: dict) -> None:
    log_path = os.path.join(out, "parity.log")
    stdout = _run_child(
        children,
        [sys.executable, os.path.join(ROOT, "scripts", "parity_ab.py"),
         "--default", str(args.parity_nodes), str(args.parity_pods)],
        log_path, env, deadline, "parity_ab",
    )
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    if not lines:
        raise SmokeFailure("parity_ab printed no result", log_path)
    res = json.loads(lines[-1])
    ph = summary["phases"]["parity"]
    ph.update(
        nodes=res["nodes"], pods=res["pods"], bound=res["bound"],
        nominations=res["nominations"], victims=res["victims"],
        parity_mismatches=res["mismatches"], nom_ok=res["nom_ok"],
        vic_ok=res["vic_ok"], platform=res["platform"],
    )
    if not (res["parity"] and res["mismatches"] == 0 and res["nom_ok"]
            and res["vic_ok"]):
        ph["first_divergence"] = res.get("first_divergence")
        raise SmokeFailure(
            f"parity failed: {json.dumps(res)[:1500]}", log_path
        )
    if res["platform"] != summary["platform"]:
        raise SmokeFailure(
            f"parity ran on {res['platform']!r}, serve on "
            f"{summary['platform']!r}", log_path,
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--batch-size", type=int, default=4096)
    ap.add_argument("--chunk-size", type=int, default=64)
    ap.add_argument("--drain-pods", type=int, default=4096,
                    help="pods per drain=True request (and the warm-up)")
    ap.add_argument("--drains", type=int, default=3)
    ap.add_argument("--hint-pods", type=int, default=1024)
    ap.add_argument("--parity-nodes", type=int, default=1000)
    ap.add_argument("--parity-pods", type=int, default=1200)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "smoke"))
    ap.add_argument("--deadline", type=float, default=1140.0,
                    help="seconds for the whole run, compilation included")
    ap.add_argument("--rehearsal", action="store_true",
                    help="allow the CPU (JAX_PLATFORMS=cpu): same phases, "
                    "reported as on_chip false")
    args = ap.parse_args(argv)

    asked = os.environ.get("JAX_PLATFORMS", "")
    if asked.strip().lower() == "cpu" and not args.rehearsal:
        print(
            f"chip_smoke: JAX_PLATFORMS={asked!r} sends the program to the "
            "CPU — this is the chip check (pass --rehearsal to rehearse "
            "on the CPU)", file=sys.stderr,
        )
        return 2

    from kubernetes_tpu.utils import backend_initialized

    out = os.path.abspath(args.out)
    os.makedirs(os.path.join(out, "flight"), exist_ok=True)
    env = dict(os.environ)
    env["TPU_FLIGHT_DIR"] = os.path.join(out, "flight")
    t_start = time.monotonic()
    deadline = t_start + args.deadline
    summary: dict = {
        "ok": False,
        "platform": None, "device_kind": None, "n_devices": 0,
        "on_chip": False,
        "rehearsal": args.rehearsal,
        "seed": args.seed,
        "phases": {"serve": {"ok": False}, "parity": {"ok": False}},
        "compile_cache": {"dir": _cache_dir(),
                          "entries_before": _cache_entries()},
    }
    children = Children()
    failure: SmokeFailure | None = None
    try:
        for name, phase in (("serve", phase_serve), ("parity", phase_parity)):
            t0 = time.monotonic()
            ph = summary["phases"][name]
            try:
                phase(args, out, env, children, deadline, summary)
                ph["ok"] = True
            except SmokeFailure as exc:
                failure = exc
            except (OSError, RuntimeError, ValueError, KeyError) as exc:
                # An error frame from the server, a lost connection, a
                # per-call deadline, an unparseable child result.
                failure = SmokeFailure(
                    f"{type(exc).__name__}: {exc}",
                    os.path.join(out, f"{name}.log"),
                )
            finally:
                if failure is not None:
                    ph["error"] = str(failure)
                ph["seconds"] = round(time.monotonic() - t0, 3)
                summary["compile_cache"][f"entries_after_{name}"] = (
                    _cache_entries()
                )
            if failure is not None:
                break
    finally:
        children.stop_all()
    cc = summary["compile_cache"]
    cc["serve_added"] = cc.get("entries_after_serve", 0) - cc["entries_before"]
    if "entries_after_parity" in cc:
        cc["parity_added"] = (
            cc["entries_after_parity"] - cc["entries_after_serve"]
        )
    summary["seconds"] = round(time.monotonic() - t_start, 3)
    # The client's own discipline: this process never touched the device.
    summary["parent_backend_initialized"] = backend_initialized()
    if failure is None and summary["parent_backend_initialized"]:
        failure = SmokeFailure("the smoke's parent initialised a JAX backend")
    summary["ok"] = failure is None
    summary["claim"] = None
    with open(os.path.join(out, "summary.json"), "w", encoding="utf-8") as f:
        json.dump(summary, f, indent=1)
        f.write("\n")
    # The verdict line: exactly these keys, the device as the serving
    # process's JAX reported it (None until a server named one).
    verdict = None
    if summary["platform"] is not None:
        verdict = json.dumps({
            "ok": summary["ok"],
            "device": {
                "platform": str(summary["platform"]),
                "kind": str(summary["device_kind"]),
                "count": int(summary["n_devices"]),
            },
        })
    if failure is not None:
        print(f"chip_smoke: FAILED — {failure}", file=sys.stderr)
        if failure.log:
            print(f"--- tail of {failure.log} ---\n{_tail(failure.log)}",
                  file=sys.stderr)
        print(json.dumps(summary), file=sys.stderr)
        if verdict is not None:
            print(verdict, flush=True)
        return 1
    print(json.dumps(summary))
    print(verdict, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
