"""Share of the traced window's filtered ``Sum`` calls whose filter was built on
the device, in the Sum's own program over the field stacks, in %: the delta of
``serving_cache.sum_lane_device_filters`` over that delta plus the delta of
``serving_cache.sum_lane_host_filters`` (``/debug/vars``;
pilosa_tpu/exec/executor.py ``Executor._batch_bsi_sums``).  A filter the lane
did not sign, or whose stacks declined, is evaluated on the host and uploaded as
an ``[S, P, W]`` tensor, or left to the per-call path: both count as host.

Reads 0 on a program without the counter (see ``listener.ms_per_read.py``) and in
a window in which the lane met no filtered ``Sum``."""


def read(ctx: dict) -> float:
    lane = ctx["vars"].get("serving_cache", {})
    device, host = lane.get("sum_lane_device_filters"), lane.get("sum_lane_host_filters", 0)
    if not device:
        return 0.0
    return 100.0 * device / (device + host)
