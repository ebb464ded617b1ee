"""What one `Journal.append` of a `basic_5kn` bind record is made of.

Times N calls (default 4,096, one backlog batch) of each piece of the
append path at the journal's real path depth
(`<checkout>/.perfbench_out/<name>/journal`, where the benchmark puts
it), then the whole thing through the program's own `Journal` as `serve`
builds it (lease-file fence, fsync on, one `group()`).  Prints one JSON
object: microseconds a call.  Touches no device; run it on the machine
whose filesystem is in question:

    chiprun -- python scripts/profile_journal_append.py
"""

import argparse
import functools
import json
import os
import shutil
import struct
import sys
import time
import zlib

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

from kubernetes_tpu.api import serialize  # noqa: E402
from kubernetes_tpu.framework.leaderelection import FileLease, read_epoch  # noqa: E402
from kubernetes_tpu.journal import Journal  # noqa: E402

_HDR = struct.Struct(">II")


@functools.cache
def pod_template() -> str:
    """The `basic_5kn` measured pod's wire template, as JSON text."""
    with open(os.path.join(ROOT, "perfbench/configs/basic_5kn.json")) as f:
        cfg = json.load(f)["pod"]
    return json.dumps(cfg["template"]).replace(
        "{namespace}", cfg["namespaces"]["measured"]
    )


def bind_record(i: int) -> tuple:
    """(pod, record data) of the i-th measured pod of `basic_5kn`, as
    `TPUScheduler._journal_bind` builds it."""
    pod = serialize.pod_from_data(
        json.loads(pod_template().replace("{name}", f"pod-{i}"))
    )
    data = {"uid": pod.uid, "node": f"node-{i % 5000}", "pod": serialize.to_dict(pod)}
    return pod, data


def per_call_us(fn, n: int) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return round((time.perf_counter() - t0) / n * 1e6, 3)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument(
        "--dir",
        default=os.path.join(ROOT, ".perfbench_out", "profile_journal_append", "journal"),
    )
    args = ap.parse_args()
    n = args.n
    jdir = os.path.abspath(args.dir)
    shutil.rmtree(jdir, ignore_errors=True)
    os.makedirs(jdir)
    lease_path = os.path.join(jdir, "lease")
    lease = FileLease(lease_path, identity="probe")
    lease.acquire(block=True)
    pod, data = bind_record(0)
    rec = {"e": 1, "q": 1, "t": "bind", "d": data}
    payload = json.dumps(rec, separators=(",", ":")).encode()
    buf = _HDR.pack(len(payload), zlib.crc32(payload)) + payload

    out = {
        "n": n,
        "journal_dir": jdir,
        "path_components": len([p for p in jdir.split(os.sep) if p]) + 1,
        "record_bytes": len(buf),
    }
    wal = os.path.join(jdir, "probe.wal")
    with open(wal, "ab") as f:
        fd = f.fileno()
        f.write(buf)
        f.flush()
        us = {
            "getsize_path": per_call_us(lambda: os.path.getsize(wal), n),
            "fstat_fd": per_call_us(lambda: os.fstat(fd).st_size, n),
            "read_epoch_lease": per_call_us(lambda: read_epoch(lease_path), n),
            "to_dict_pod": per_call_us(lambda: serialize.to_dict(pod), n),
            "json_dumps_record": per_call_us(
                lambda: json.dumps(rec, separators=(",", ":")).encode(), n
            ),
            "crc32_pack": per_call_us(
                lambda: _HDR.pack(len(payload), zlib.crc32(payload)) + payload, n
            ),
            "tell": per_call_us(f.tell, n),
        }

        def write_flush():
            f.write(buf)
            f.flush()

        us["write_flush_one_record"] = per_call_us(write_flush, n)
        blob = buf * n
        t0 = time.perf_counter()
        f.write(blob)
        f.flush()
        us["write_flush_n_records_once"] = round((time.perf_counter() - t0) * 1e6, 3)
        t0 = time.perf_counter()
        os.fsync(fd)
        us["fsync_after"] = round((time.perf_counter() - t0) * 1e6, 3)
    out["us_per_call"] = us

    # The program's own path, as `serve` opens it: what the pieces add up to.
    journal = Journal(
        jdir, epoch=lease.epoch, fence=lambda: read_epoch(lease_path), fsync=True
    )
    records = [bind_record(i)[1] for i in range(n)]
    t0 = time.perf_counter()
    with journal.group():
        for d in records:
            journal.append("bind", d)
        t_loop = time.perf_counter()
    t1 = time.perf_counter()
    out["journal_group"] = {
        "append_loop_us_per_record": round((t_loop - t0) / n * 1e6, 3),
        "group_exit_us": round((t1 - t_loop) * 1e6, 3),
        "stats": {
            k: v
            for k, v in journal.stats().items()
            if k in ("appends", "writes", "fence_checks", "fsyncs", "group_commits", "wal_bytes")
        },
    }
    journal.close()
    lease.release()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
