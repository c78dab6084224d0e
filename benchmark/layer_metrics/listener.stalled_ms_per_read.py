"""What a handler thread spends in ``/query`` neither running nor waiting for
its flight, in ms a read: (``spans.http.query.seconds`` - its ``cpu_seconds`` -
``spans.batcher.queueWait.seconds`` - ``spans.batcher.dispatch.seconds``) over
``spans.http.query.count`` (``/debug/vars`` ``spans``; pilosa_tpu/obs/tracing.py
``book_cpu``: the handler books into its root span the thread CPU it reads
around a request anyway, pilosa_tpu/server/http.py ``handle_one_request``: from
before the request's line to the response written and booked, so the head's
parse counts as run) over the traced window.  The handler was runnable and
waited for the interpreter, or for a lock: it stands beside
``listener.ms_per_read`` (wall) and ``listener.cpu_ms_per_read`` (CPU, every
request).  A read the result cache answered has no flight to wait for and
counts with the rest.

Reads 0 on a program whose rows lack the column (see
``listener.ms_per_read.py``) and in a window without a read."""


def read(ctx: dict) -> float:
    spans = ctx["vars"].get("spans") or {}
    query = spans.get("http", {}).get("query", {})
    cpu, reads = query.get("cpu_seconds"), query.get("count")
    if cpu is None or not reads:
        return 0.0
    batcher = spans["batcher"]
    waited = batcher["queueWait"]["seconds"] + batcher["dispatch"]["seconds"]
    return 1000.0 * (query["seconds"] - cpu - waited) / reads
