"""Share of the traced window in which no operation ran on the device; on
several devices, of the busiest.  A trace without a device operation is
no reading at all: the run ends there."""


def read(ctx: dict) -> float:
    trace = ctx["trace"]
    if not trace["op_count"]:
        raise KeyError("the trace holds no device operation")
    return 100.0 * (1.0 - trace["busy_s_busiest"] / trace["window_s"])
