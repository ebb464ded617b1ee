#!/usr/bin/env python3
"""The traced run's serving process.  Only the process that holds the chip
can trace it, and ``serve`` has no profiler switch, so this starts the
program's own ``serve`` entry in this process and puts ``jax.profiler``
under a control socket beside it: ``start`` begins a trace into
``--trace-dir``, ``stop`` ends it.  Each reply carries the host's clock
around the call, which is how the client lays its own spans and the flight
records on the trace's clock.  Nothing else differs from
``python -m kubernetes_tpu serve``.
"""

from __future__ import annotations

import argparse
import os
import socketserver
import sys
import threading
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True)
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    sys.path.insert(0, os.getcwd())

    import jax

    class Handler(socketserver.StreamRequestHandler):
        def handle(self) -> None:
            word = self.rfile.readline().decode().strip()
            t0 = time.time_ns()
            try:
                if word == "start":
                    # device and host-runtime events only: the Python
                    # tracer would record every call of the server's own
                    # host path and slow what it measures
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(args.trace_dir, profiler_options=opts)
                elif word == "stop":
                    jax.profiler.stop_trace()
                else:
                    raise ValueError(f"unknown word {word!r}")
                reply = f"ok {t0} {time.time_ns()}\n"
            except Exception as exc:  # the client must hear of it
                reply = f"error {type(exc).__name__}: {exc}\n"
            self.wfile.write(reply.encode())

    if os.path.exists(args.control):
        os.unlink(args.control)
    control = socketserver.UnixStreamServer(args.control, Handler)
    threading.Thread(target=control.serve_forever, daemon=True).start()

    from kubernetes_tpu.__main__ import main as program_main

    return program_main(rest) or 0


if __name__ == "__main__":
    sys.exit(main())
