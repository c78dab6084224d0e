"""Interpreter time of the one dispatcher thread a flight, in ms: the delta of
``spans.batcher.flight.cpu_seconds`` over that of its ``count`` (``/debug/vars``
``spans``; pilosa_tpu/obs/tracing.py: a span under ``batcher.flight`` reads
``time.thread_time_ns()`` beside the monotonic clock at each end, so the row
holds what the dispatcher's thread ran between a flight's two ends) over the
traced window.  What faster lane code buys.  With
``batcher.device_wait_ms_per_flight`` and ``batcher.stalled_ms_per_flight`` it
adds up to the flight's wall time, ``spans.batcher.flight.seconds`` a flight.
Where the host's thread clock ticks (10 ms steps on the chip's host) only the
window's sum means anything: about 170 flights of 26 ms are some 440 ticks.

Reads 0 on a program whose rows lack the column (see
``listener.ms_per_read.py``) and in a window without a flight."""


def read(ctx: dict) -> float:
    flight = (ctx["vars"].get("spans") or {}).get("batcher", {}).get("flight", {})
    cpu, flights = flight.get("cpu_seconds"), flight.get("count")
    if cpu is None or not flights:
        return 0.0
    return 1000.0 * cpu / flights
