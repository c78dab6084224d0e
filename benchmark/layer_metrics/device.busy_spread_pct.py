"""How unevenly the chips shared the traced window's device work: 100 x
(busiest - idlest chip's ``busy_s``) / busiest, over ``trace.devices``
(``trace_reduce.py``: the union of the intervals in which an operation ran,
per device plane).  Near 0 every launch spread over the mesh; near 100 one chip
did the work (fragment copies are dealt ``shard % devices``, a stack's shards
in blocks).

One device has nothing to spread over and reads 0, as a trace reduced by an
older ``trace_reduce.py`` without the per-device list does; a trace without a
device operation is no reading at all (``device.idle_pct.py``)."""


def read(ctx: dict) -> float:
    trace = ctx["trace"]
    if not trace["op_count"]:
        raise KeyError("the trace holds no device operation")
    busy = [d["busy_s"] for d in trace.get("devices", [])]
    if len(busy) < 2 or not max(busy):
        return 0.0
    return 100.0 * (max(busy) - min(busy)) / max(busy)
