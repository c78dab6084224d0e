"""Thread CPU a handler spends on a request, in ms: the delta of
``http.handlerCpuSeconds`` over that of ``http.requests`` (``/debug/vars``;
pilosa_tpu/server/http.py ``Handler.handle_one_request``: two reads of
``time.thread_time()`` around a request, from its line to its response
written and booked) over the traced window.  Every request of the window
counts, an import of ``taxi.ingest-serve`` and the harness's own
``/debug/vars`` among the reads.  Interpreter time, not a span's wall time:
what the handlers hold the one interpreter for.

Reads 0 on a program without the counter (see ``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    http = ctx["vars"].get("http") or {}
    cpu, requests = http.get("handlerCpuSeconds"), http.get("requests")
    if cpu is None or not requests:
        return 0.0
    return 1000.0 * cpu / requests
