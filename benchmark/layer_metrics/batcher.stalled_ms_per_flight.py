"""What is left of a flight when the dispatcher's interpreter time and its
device wait are taken away, in ms a flight: (``seconds`` - ``cpu_seconds`` -
``device_wait_seconds``) of ``spans.batcher.flight`` over its ``count``
(``/debug/vars`` ``spans``, pilosa_tpu/obs/tracing.py) over the traced window.
The thread was runnable and waited for the interpreter, or waited for a lock,
an upload or a queue: what less work on the handler threads, or a dispatcher
outside the interpreter's lock, buys.  The three ``batcher.*_ms_per_flight``
add up to ``seconds`` a flight; on a clock that ticks a short window can read
this one below 0.

Reads 0 on a program whose rows lack the columns (see
``listener.ms_per_read.py``) and in a window without a flight."""


def read(ctx: dict) -> float:
    flight = (ctx["vars"].get("spans") or {}).get("batcher", {}).get("flight", {})
    cpu, flights = flight.get("cpu_seconds"), flight.get("count")
    if cpu is None or not flights:
        return 0.0
    return 1000.0 * (flight["seconds"] - cpu - flight["device_wait_seconds"]) / flights
