"""Time a flight's dispatcher thread waited for a device result, in ms a
flight: the delta of ``spans.batcher.flight.device_wait_seconds`` over that of
its ``count`` (``/debug/vars`` ``spans``; pilosa_tpu/obs/tracing.py: a
``kernels.pull`` span's duration less its thread's CPU is its device wait, and
every span adds its own and its same-thread children's to its parent's, so the
flight's row holds the device wait of its own tree and not that of an
importing handler's refresh beside it) over the traced window.  What kernels
and overlap buy.  A wait for the interpreter after the result arrived is in it:
the span cannot tell the two apart.

Reads 0 on a program whose rows lack the column (see
``listener.ms_per_read.py``) and in a window without a flight."""


def read(ctx: dict) -> float:
    flight = (ctx["vars"].get("spans") or {}).get("batcher", {}).get("flight", {})
    wait, flights = flight.get("device_wait_seconds"), flight.get("count")
    if wait is None or not flights:
        return 0.0
    return 1000.0 * wait / flights
