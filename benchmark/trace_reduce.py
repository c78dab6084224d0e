"""From a profiler trace (``.xplane.pb``) to device numbers.

    python benchmark/trace_reduce.py <trace dir or .xplane.pb>

prints one JSON object: per device the union of the intervals in which an
operation ran (``busy_s``), the time of each operation by the name the
trace prints, and the longest gaps between operations.  ``run.py`` starts
it as a process of its own once the server has gone, so the parent never
imports JAX; only ``jax.profiler.ProfileData`` is used, and no backend.

A TPU trace has one plane per chip, ``/device:TPU:<n>``, whose ``XLA Ops``
line holds the operations.  The CPU backend of a rehearsal has no device
plane; its XLA thread lines in ``/host:CPU`` stand in, and the result says
so (``stand_in``), so that nothing takes them for a device.
"""

from __future__ import annotations

import glob
import json
import os
import sys

OP_LINES = ("XLA Ops", "XLA Modules")  # the first a device plane has
CPU_STAND_IN = "tf_XLAPjRtCpuClient"


def find_trace(path: str) -> str | None:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Length of the union of (start, end) intervals, and its gaps as
    (length, index of the interval that ends the gap), longest first."""
    busy = 0.0
    gaps = []
    cur_lo = cur_hi = None
    for i, (lo, hi) in enumerate(sorted(intervals)):
        if cur_hi is None:
            cur_lo, cur_hi = lo, hi
        elif lo > cur_hi:
            busy += cur_hi - cur_lo
            gaps.append((lo - cur_hi, i))
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        busy += cur_hi - cur_lo
    return busy, sorted(gaps, reverse=True)


def op_name(text: str) -> str:
    """The trace prints an operation as its whole HLO line; its name is
    what stands before `` = ``."""
    return text.split(" = ", 1)[0][:80]


def op_events(plane, stand_in: bool) -> list[tuple[str, float, float]]:
    """(name, start_s, end_s) of the operations of one plane."""
    lines = list(plane.lines)
    if stand_in:
        chosen = [ln for ln in lines if ln.name.startswith(CPU_STAND_IN)]
    else:
        by_name = {ln.name: ln for ln in lines}
        chosen = [by_name[n] for n in OP_LINES if n in by_name][:1]
    out = []
    for ln in chosen:
        for e in ln.events:
            if e.duration_ns > 0:
                out.append((op_name(e.name), e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    return out


def reduce_planes(planes: list[tuple[str, list[tuple[str, float, float]]]], stand_in: bool) -> dict:
    """``planes``: (plane name, operations) per device."""
    devices = []
    per_op: dict[str, float] = {}
    gaps_named: list[tuple[float, str]] = []
    for name, events in planes:
        busy, gaps = union_seconds([(lo, hi) for _, lo, hi in events])
        devices.append({"plane": name, "busy_s": busy, "ops": len(events)})
        ordered = sorted(events, key=lambda e: (e[1], e[2]))
        for length, i in gaps[:10]:
            gaps_named.append((length, "before " + ordered[i][0]))
        for op, lo, hi in events:
            per_op[op] = per_op.get(op, 0.0) + (hi - lo)
    n = max(len(devices), 1)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "stand_in": stand_in,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / n,          # averaged over the chips
        "busy_s_busiest": max((d["busy_s"] for d in devices), default=0.0),
        "op_seconds": sum(per_op.values()) / n,
        "op_count": sum(d["ops"] for d in devices),
        "device_ops": [[k, v / n] for k, v in top[:10]],
        "idle_gaps": [[k, v] for v, k in sorted(gaps_named, reverse=True)[:10]],
    }


def reduce_trace(path: str) -> dict:
    """An empty or missing trace reduces to zeros, not to missing keys."""
    from jax.profiler import ProfileData

    file = find_trace(path)
    if file is None:
        return reduce_planes([], False)
    data = ProfileData.from_file(file)
    planes = list(data.planes)
    device = [p for p in planes if p.name.startswith("/device:") and "TPU" in p.name.upper()]
    stand_in = not device
    if stand_in:
        device = [p for p in planes if p.name == "/host:CPU"]
    out = reduce_planes([(p.name, op_events(p, stand_in)) for p in device], stand_in)
    out["lines"] = {p.name: [ln.name for ln in p.lines][:12] for p in device}
    return out


if __name__ == "__main__":
    print(json.dumps(reduce_trace(sys.argv[1])))
