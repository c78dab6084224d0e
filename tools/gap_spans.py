"""Name the device's idle gaps by the program span that was open through them.

    python tools/gap_spans.py <trace dir or .xplane.pb> [--top 10]
    python tools/gap_spans.py --record <dir>     # a small trace of this program

A profiler trace taken while the program serves (the benchmark's ``--trace 1``,
an operator's session) holds two things on one clock: the device's operations
(``/device:TPU:<n>``, line ``XLA Ops``) and, in the host plane, one event per
program span, because entering a ``tracing.Span`` enters a
``jax.profiler.TraceAnnotation`` of the same name on the thread that ran it.
``benchmark/trace_reduce.py`` defines what a gap is (the stretches between the
union of the operations' intervals) and names each by the device operation that
ended it.  This tool takes the same gaps and names each by the innermost program
annotation that was open on the dispatcher thread (the line that holds
``batcher.flight``) through most of it, or ``idle`` when none was: the dispatcher
sat in ``queue.get``.

Prints one JSON object: ``gaps`` (the longest first: span, seconds, the share of
the gap that span covered, the chain of spans open around it), ``by_span``
(seconds of all gaps by that name) and ``dispatcher`` (the thread line).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
sys.path.insert(0, REPO)

import trace_reduce  # noqa: E402  (the benchmark's definition of a gap, not a copy)

FLIGHT = "batcher.flight"
IDLE = "idle"
HOST_PLANE = "/host:CPU"


def span_blocks() -> set[str]:
    """First segments of the span table's names: what marks a host event as
    one of the program's spans."""
    from pilosa_tpu.obs import tracing

    return {name.split(".")[0] for name, _, _ in tracing.registered()} | {"http", "executor"}


def device_gaps(planes, stand_in: bool) -> list[tuple[float, float]]:
    """(start_s, end_s) of every gap between device operations, per device
    plane, as ``trace_reduce.union_seconds`` finds them."""
    out = []
    for plane in planes:
        intervals = sorted((lo, hi) for _, lo, hi in trace_reduce.op_events(plane, stand_in))
        _, gaps = trace_reduce.union_seconds(intervals)
        out += [(intervals[i][0] - length, intervals[i][0]) for length, i in gaps]
    return out


def host_spans(plane, blocks: set[str]) -> dict[str, list[tuple[str, float, float]]]:
    """{thread line: [(span name, start_s, end_s)]} of the program's
    annotations in the host plane."""
    out = {}
    for i, line in enumerate(plane.lines):
        if line.name.startswith(trace_reduce.CPU_STAND_IN):
            continue
        evs = []
        for e in line.events:
            name = e.name.split("#", 1)[0]
            parts = name.split(".")
            if len(parts) == 2 and parts[0] in blocks and e.duration_ns > 0:
                evs.append((name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
        if evs:
            out[f"{i}:{line.name}"] = evs  # thread names repeat
    return out


def name_gap(gap: tuple[float, float], spans: list[tuple[str, float, float]]) -> tuple[str, float, list[str]]:
    """(innermost span open through most of the gap, share it covered, the
    chain of spans around it outermost first)."""
    lo, hi = gap
    length = hi - lo
    open_through = []
    for name, s, e in spans:
        covered = min(hi, e) - max(lo, s)
        if covered > 0.5 * length:
            open_through.append((e - s, name, covered / length))
    if not open_through:
        return IDLE, 0.0, []
    open_through.sort(reverse=True)  # the longest is the outermost
    _, name, share = open_through[-1]
    return name, share, [n for _, n, _ in open_through]


def gap_spans(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    file = trace_reduce.find_trace(path)
    if file is None:
        raise SystemExit(f"no .xplane.pb under {path}")
    planes = list(ProfileData.from_file(file).planes)
    device = [p for p in planes if p.name.startswith("/device:") and "TPU" in p.name.upper()]
    stand_in = not device
    host = [p for p in planes if p.name == HOST_PLANE]
    if stand_in:
        device = host
    lines = host_spans(host[0], span_blocks()) if host else {}
    dispatcher = [ln for ln, evs in lines.items() if any(n == FLIGHT for n, _, _ in evs)]
    spans = [ev for ln in dispatcher for ev in lines[ln]]
    gaps = sorted(device_gaps(device, stand_in), key=lambda g: g[0] - g[1])
    named = []
    by_span: dict[str, float] = {}
    for gap in gaps:
        name, share, chain = name_gap(gap, spans)
        by_span[name] = by_span.get(name, 0.0) + (gap[1] - gap[0])
        if len(named) < top:
            named.append({"span": name, "seconds": gap[1] - gap[0], "covered": share, "chain": chain})
    return {
        "stand_in": stand_in, "dispatcher": dispatcher, "gap_count": len(gaps),
        "gap_seconds": sum(hi - lo for lo, hi in gaps),
        "gaps": named,
        "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "spans_seen": {ln: sorted({n for n, _, _ in evs}) for ln, evs in lines.items()},
    }


def record(out_dir: str) -> str:
    """Record a small trace of this program on whatever device JAX has: one
    in-process node, a few flights of pair counts, a filtered TopN and BSI
    range counts through HTTP, under a profiler session started as the
    benchmark's server child starts its own.  ``tests/testdata/`` holds one
    taken on a v5e."""
    import threading
    import time
    import urllib.request

    import jax

    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.server.node import NodeServer

    node = NodeServer(host="127.0.0.1", port=0, rescache_entries=0)
    node.start()
    try:
        idx = node.holder.create_index("gs")
        idx.create_field("f")
        idx.create_field("v", FieldOptions(field_type="int", min_=0, max_=1000))
        bits = [(r, c) for r in range(1, 9) for c in range(r, 4000, r)]
        node.api.import_bits("gs", "f", {"rowIDs": [r for r, _ in bits], "columnIDs": [c for _, c in bits]})
        cols = list(range(1, 4000, 3))
        node.api.import_bits("gs", "v", {"columnIDs": cols, "values": [c % 997 for c in cols]})

        def read(q):
            req = urllib.request.Request(node.uri + "/index/gs/query", data=q.encode(), method="POST")
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.read()

        def rounds(seed):
            for k in range(3):
                a, b = 1 + (seed + k) % 8, 1 + (seed + k + 3) % 8
                read(f"Count(Intersect(Row(f={a}), Row(f={b}))) Count(Union(Row(f={b}), Row(f={a})))")
                read(f"TopN(f, Row(f={a}), n=3)")
                read(f"Count(Row(v < {100 + 50 * seed + k})) Count(Row(v > {700 - 20 * seed - k}))")
                time.sleep(0.01)

        rounds(0)  # compiles stay out of the trace
        rounds(1)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        try:
            threads = [threading.Thread(target=rounds, args=(s,)) for s in (2, 3, 4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
        finally:
            jax.profiler.stop_trace()
    finally:
        node.stop()
    return trace_reduce.find_trace(out_dir)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--record", metavar="DIR")
    args = ap.parse_args(argv)
    if args.record:
        args.trace = record(args.record)
        print(f"recorded {args.trace} ({os.path.getsize(args.trace)} bytes)", file=sys.stderr)
    if not args.trace:
        ap.error("a trace, or --record DIR")
    print(json.dumps(gap_spans(args.trace, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
