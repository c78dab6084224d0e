"""Flight items that a batch lane of the executor handed back to the per-call
path, over the reads answered in the window: the delta of every lane and reason
of ``serving_cache.lane_declines`` (``/debug/vars``; pilosa_tpu/exec/executor.py
``_lane_decline``) over ``window.reads``.  The log of the run has the block by
lane and reason; ``mesh`` has to read 0 where every lane takes a sharded stack.

Reads 0 on a program without the counter (see ``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    lanes = ctx["vars"].get("serving_cache", {}).get("lane_declines")
    reads = ctx["window"]["reads"]
    if lanes is None or not reads:
        return 0.0
    return sum(n for by_reason in lanes.values() for n in by_reason.values()) / reads
