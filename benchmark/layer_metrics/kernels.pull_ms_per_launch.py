"""Host time blocked on a device result, in ms a launch: ``spans.kernels.pull``
seconds over ``devledger.totals.launches``.  It stands beside
``kernels.device_ms_per_read``: what the host waits against what the device
works.

Reads 0 on a program from before the span table (see
``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    spans = ctx["vars"].get("spans")
    if spans is None:
        return 0.0
    launches = ctx["vars"]["devledger"]["totals"]["launches"]
    return 1000.0 * spans["kernels"]["pull"]["seconds"] / launches if launches else 0.0
