#!/usr/bin/env python3
"""The traced run's serving process.  Only the process that holds the chip
can trace it, and ``serve`` has no profiler switch, so this starts the
program's own ``serve`` entry in this process and puts a profiler session
under a control socket beside it:

  start <seconds>  begins a session and arms its end: ``seconds`` later a
                   timer thread of this process stops it, wherever a pass
                   stands, so the slice has an end of its own and nobody's
                   loop waits on the stop
  stop             ends the session now if the timer has not yet, waits
                   until the stop is done, and says what it cost

The slice's two ends are events of the trace itself (``perfbench/slice_start``
after the session has started, ``perfbench/slice_end`` before it is
stopped), so the reduction reads them on the trace's own clock.  The
session is stopped with ``ProfilerSession.stop`` and its XSpace written as
it comes: ``jax.profiler.stop_trace`` also exports every event as gzipped
JSON for a viewer nobody opens here, which is most of what its stop costs
(PERF.md section 3).  Nothing else differs from
``python -m kubernetes_tpu serve``.
"""

from __future__ import annotations

import argparse
import os
import socket
import socketserver
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.trace import MARK_END, MARK_START  # noqa: E402  the slice's ends, as the reduction finds them


class Slice:
    """One profiler session with an armed end.  ``stop`` may be called by
    the timer and by the control socket; the first call stops, every call
    returns the same marks once the stop is done."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self._lock = threading.Lock()
        self._session = None
        self._timer = None
        self.stop_marks: tuple[int, int] | None = None

    def start(self, seconds: float) -> tuple[int, int]:
        import jax
        from jax._src.lib import _profiler
        from jax.profiler import TraceAnnotation

        with self._lock:
            if self._session is not None or self.stop_marks is not None:
                raise RuntimeError("one slice a run")
            # device and host-runtime events only: the Python tracer would
            # record every call of the server's own host path and slow
            # what it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.devices()  # the backend before the session, as start_trace has it
            t0 = time.time_ns()
            self._session = _profiler.ProfilerSession(opts)
            with TraceAnnotation(MARK_START):
                pass
            t1 = time.time_ns()
            self._timer = threading.Timer(seconds, self.stop)
            self._timer.daemon = True
            self._timer.start()
        return t0, t1

    def stop(self) -> tuple[int, int]:
        from jax.profiler import TraceAnnotation

        with self._lock:
            if self.stop_marks is None:
                if self._session is None:
                    raise RuntimeError("no slice was started")
                self._timer.cancel()
                with TraceAnnotation(MARK_END):
                    pass
                t0 = time.time_ns()
                xspace = self._session.stop()
                self._session = None
                out = os.path.join(self.trace_dir, "plugins", "profile", "slice")
                os.makedirs(out, exist_ok=True)
                with open(os.path.join(out, socket.gethostname() + ".xplane.pb"), "wb") as f:
                    f.write(xspace)
                self.stop_marks = (t0, time.time_ns())
            return self.stop_marks


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True)
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    slice_ = Slice(args.trace_dir)

    class Handler(socketserver.StreamRequestHandler):
        def handle(self) -> None:
            words = self.rfile.readline().decode().split()
            try:
                if words[:1] == ["start"] and len(words) == 2:
                    marks = slice_.start(float(words[1]))
                elif words == ["stop"]:
                    marks = slice_.stop()
                else:
                    raise ValueError(f"unknown words {words!r}")
                reply = f"ok {marks[0]} {marks[1]}\n"
            except Exception as exc:  # the client must hear of it
                reply = f"error {type(exc).__name__}: {exc}\n"
            self.wfile.write(reply.encode())

    if os.path.exists(args.control):
        os.unlink(args.control)
    control = socketserver.ThreadingUnixStreamServer(args.control, Handler)
    control.daemon_threads = True
    threading.Thread(target=control.serve_forever, daemon=True).start()

    from kubernetes_tpu.__main__ import main as program_main

    return program_main(rest) or 0


if __name__ == "__main__":
    sys.exit(main())
