"""Mean time a batched read waited between its enqueue and the start of the
flight that carried it, in ms: ``spans.batcher.queueWait`` of the span table
(``/debug/vars``, pilosa_tpu/obs/tracing.py), seconds over count.

Reads 0 on a program from before the span table (see
``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    spans = ctx["vars"].get("spans")
    if spans is None:
        return 0.0
    wait = spans["batcher"]["queueWait"]
    return 1000.0 * wait["seconds"] / wait["count"] if wait["count"] else 0.0
