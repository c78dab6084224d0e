"""Share of the window's device-or-host decisions that the host tier took: the
delta of ``kernels.dispatch_lanes.host`` (``/debug/vars``; pilosa_tpu/ops/
kernels.py ``record_host_op``: a count or a row taken on the host's copy of the
rows) over that and the delta of ``devledger.totals.launches`` (every launch on
the device, whichever site made it), in %.  ``benchmark/README.md`` once wrote
the denominator as the three lanes ``host`` + ``xla`` + ``pallas``; a lone pair
count's launch is ``exec.astbatch``'s and is in none of them, so on the chip that
read 0 / 0.  A lane that never dispatched is absent from the block and counts 0;
a window in which neither side did anything has nothing to read and ends the run."""


def read(ctx: dict) -> float:
    host = ctx["vars"]["kernels"]["dispatch_lanes"].get("host") or 0
    launches = ctx["vars"]["devledger"]["totals"]["launches"]
    if not host + launches:
        raise ValueError("neither a host operation nor a launch in the window: no share to read")
    return 100.0 * host / (host + launches)
