"""Shared by the benchmark's tests: the repo root on the path, and the
command a test uses to run a cell as the driver would."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def run_cell(workload: str, out: str, seconds: float = 1.5, trace: int = 0,
             rehearsal: bool = True, env_extra=None, timeout: float = 420.0,
             root: str = ROOT):
    """``perfbench/run.py`` of the checkout at ``root`` in a child, started
    from there as the driver starts it, on the CPU.  (returncode, stdout
    lines, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as a serve child gets
    # a compile cache of the run's own: the checkout's is shared with every
    # test that runs beside this one, and a file another test's server
    # writes there during this window would read as compiled inside it
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(out, "jax_cache")
    env.update(env_extra or {})
    argv = [sys.executable, os.path.join(root, "perfbench", "run.py"),
            "--workload", workload, "--seed", "2400000777", "--seconds", str(seconds),
            "--trace", str(trace), "--out", out]
    if rehearsal:
        argv.append("--rehearsal")
    p = subprocess.run(argv, cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    return p.returncode, p.stdout.splitlines(), p.stderr
