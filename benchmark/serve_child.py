"""The one process that holds the chip.

Its main thread enters the program through the program's own entry,
``pilosa_tpu.cli.main(["server", ...])`` — the ``cli server`` a user
starts, with its shipped options.  A side thread answers three verbs on a
control socket, one JSON line each way, because only the process that
holds the chip can name its devices or trace it:

``device``            platform, kind, count, memory_peak_bytes
``trace_start <dir>`` ``jax.profiler.start_trace``
``trace_stop``        ``jax.profiler.stop_trace``
"""

from __future__ import annotations

import argparse
import json
import os
import socketserver
import sys
import threading

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def device_block() -> dict:
    import jax

    devs = jax.local_devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
        # the backend of a CPU rehearsal reports no memory statistics
        "memory_peak_bytes": max(peaks) if peaks else None,
    }


def trace_start(path: str) -> dict:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the host's Python frames are not read; they bloat the trace
    opts.host_tracer_level = 1
    jax.profiler.start_trace(path, profiler_options=opts)
    return {}


def trace_stop() -> dict:
    import jax

    jax.profiler.stop_trace()
    return {}


class Control(socketserver.StreamRequestHandler):
    def handle(self) -> None:
        for line in self.rfile:
            words = line.decode().split()
            try:
                if words == ["device"]:
                    out = device_block()
                elif len(words) == 2 and words[0] == "trace_start":
                    out = trace_start(words[1])
                elif words == ["trace_stop"]:
                    out = trace_stop()
                else:
                    raise ValueError(f"unknown verb {line!r}")
                out["ok"] = True
            except Exception as e:  # the parent ends the run on it
                out = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            self.wfile.write(json.dumps(out).encode() + b"\n")
            self.wfile.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--bind", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--control-port", type=int, required=True)
    args = ap.parse_args(argv)

    control = socketserver.ThreadingTCPServer(("127.0.0.1", args.control_port), Control)
    control.daemon_threads = True
    threading.Thread(target=control.serve_forever, daemon=True).start()

    sys.path.insert(0, REPO)
    from pilosa_tpu import cli

    return cli.main(["server", "-d", args.data_dir, "--bind", args.bind, "-c", args.config])


if __name__ == "__main__":
    sys.exit(main())
