"""How many ``GroupBy`` levels the executor's GroupBy lane had enqueued on the
device and not yet pulled when it waited for one, the awaited one included, as a
mean over the lane's pulls of the traced window: the delta of
``serving_cache.groupby_lane_inflight_sum`` over that of
``serving_cache.groupby_lane_pulls`` (``/debug/vars``; pilosa_tpu/exec/executor.py
``Executor._groupby_lane``).  1.0 is the call-by-call order, every level awaited
before the next call's is launched; a flight of several filtered ``GroupBy``
calls reads their number, less what the lane's byte bound held back.

Reads 0 on a program without the counter (see ``listener.ms_per_read.py``) and in
a window in which the lane pulled nothing."""


def read(ctx: dict) -> float:
    lane = ctx["vars"].get("serving_cache", {})
    inflight, pulls = lane.get("groupby_lane_inflight_sum"), lane.get("groupby_lane_pulls")
    if inflight is None or not pulls:
        return 0.0
    return inflight / pulls
