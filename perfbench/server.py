"""The serving process: started as a deployment starts it, stopped, and its
journal read back.  This process is the CLIENT and never initialises a JAX
backend; the child is the only process on the chip."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time


def serve_argv(config: dict, sock: str, journal_dir: str, traced: bool,
               trace_sock: str = "", trace_dir: str = "") -> list[str]:
    """``python -m kubernetes_tpu serve`` with the configuration's flags
    and every other flag at its default.  The traced run goes through the
    benchmark's launcher, which calls the same entry in its own process."""
    s = config["serve"]
    flags = [
        "serve", "--socket", sock, "--journal-dir", journal_dir,
        "--journal-fsync", str(s["journal_fsync"]),
        "--pipeline-depth", str(s["pipeline_depth"]),
        "--batch-size", str(s["batch_size"]),
        "--chunk-size", str(s["chunk_size"]),
    ]
    if s.get("speculate"):
        flags.insert(3, "--speculate")
    if traced:
        launcher = os.path.join(os.path.dirname(os.path.abspath(__file__)), "launcher.py")
        return [sys.executable, launcher, "--control", trace_sock,
                "--trace-dir", trace_dir, "--"] + flags
    return [sys.executable, "-m", "kubernetes_tpu"] + flags


class Server:
    def __init__(self, argv: list[str], root: str, log_path: str, env: dict):
        self.log_path = log_path
        with open(log_path, "ab") as log:
            self.proc = subprocess.Popen(
                argv, stdout=log, stderr=log, cwd=root, env=env,
                start_new_session=True,
            )

    def wait_listening(self, sock: str, deadline_s: float) -> None:
        t_end = time.monotonic() + deadline_s
        while not os.path.exists(sock):
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"serve exited rc={self.proc.returncode} before listening\n"
                    + self.log_tail()
                )
            if time.monotonic() > t_end:
                raise RuntimeError("serve never bound its socket\n" + self.log_tail())
            time.sleep(0.02)

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return f.read()[-n:].decode("utf-8", "replace")
        except OSError:
            return ""

    def stop(self, sig=signal.SIGTERM, grace_s: float = 60.0):
        proc = self.proc
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


def recover_bindings(config: dict, journal_dir: str, root: str, log_path: str,
                     env: dict, timeout_s: float = 240.0) -> dict[str, str]:
    """Every binding the journal holds, through the program's own
    ``recover`` entry point (snapshot + write-ahead log).  It only reads
    files, so it is sent to the host's CPU whatever the cell runs on."""
    s = config["serve"]
    env = dict(env, JAX_PLATFORMS="cpu")
    argv = [sys.executable, "-m", "kubernetes_tpu", "recover",
            "--journal-dir", journal_dir,
            "--batch-size", str(s["batch_size"]),
            "--chunk-size", str(s["chunk_size"])]
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE, stderr=log,
                                cwd=root, env=env, start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            raise RuntimeError("recover timed out")
    if proc.returncode != 0:
        raise RuntimeError(f"recover exited rc={proc.returncode}")
    text = out.decode("utf-8", "replace")
    doc = json.loads(text[text.index("{"):])
    return {**doc["pending_bindings"], **doc["bindings"]}


class TraceControl:
    """The client's end of the launcher's control socket."""

    def __init__(self, path: str):
        self.path = path
        self.started = False

    def _ask(self, word: str) -> str:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.settimeout(300.0)
            s.connect(self.path)
            s.sendall(word.encode() + b"\n")
            return s.makefile().readline().strip()

    def start(self, seconds: float) -> tuple[int, int]:
        """Starts the slice and arms its end ``seconds`` later, inside the
        serving process.  Unix nanoseconds just before and just after."""
        reply = self._ask(f"start {seconds!r}").split()
        if reply[:1] != ["ok"]:
            raise RuntimeError(f"profiler did not start: {reply}")
        self.started = True
        return int(reply[1]), int(reply[2])

    def stop(self) -> tuple[int, int]:
        """Ends the slice now unless its own end has come, waits for the
        stop to be done, and returns the serving process's clock just
        before and just after it."""
        reply = self._ask("stop").split()
        if reply[:1] != ["ok"]:
            raise RuntimeError(f"profiler did not stop: {reply}")
        return int(reply[1]), int(reply[2])
