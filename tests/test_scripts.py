"""Every script left under scripts/ still loads (ISSUE 31): a script that
imports a module the tree no longer has fails here, not in the hands of
whoever needs it next.  Each is loaded by file path in a process of its
own, as ``python scripts/<name>.py`` would find it, and has to leave JAX's
backend uninitialised: a launcher that holds the chip starves its children
(tests/test_bringup.py, "one process for each chip")."""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = sorted(
    os.path.basename(p) for p in glob.glob(os.path.join(REPO, "scripts", "*.py"))
)

_LOAD = """
import importlib.util, sys
path = sys.argv[1]
spec = importlib.util.spec_from_file_location("_script_under_test", path)
mod = importlib.util.module_from_spec(spec)
sys.modules[spec.name] = mod
spec.loader.exec_module(mod)
from kubernetes_tpu.utils import backend_initialized
print("backend_initialized", backend_initialized())
"""


def test_the_scripts_directory_is_not_empty():
    assert SCRIPTS


@pytest.mark.parametrize("name", SCRIPTS)
def test_script_loads_and_initialises_no_backend(name):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _LOAD, os.path.join(REPO, "scripts", name)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.split()[-2:] == ["backend_initialized", "False"], (
        proc.stdout[-1000:], proc.stderr[-1000:]
    )
