"""Flight time that is neither enqueue, upload nor pull, in ms a flight: the
self seconds of ``batcher.flight`` and of every span of the ``planner`` and
``executor`` blocks of the span table, over the flights of the window.  The
``kernels`` block (``h2d``, ``enqueue``, ``pull``) is what it leaves out.

Reads 0 on a program from before the span table (see
``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    spans = ctx["vars"].get("spans")
    if spans is None:
        return 0.0
    flight = spans["batcher"]["flight"]
    if not flight["count"]:
        return 0.0
    host = flight["self_seconds"] + sum(
        row["self_seconds"] for block in ("planner", "executor") for row in spans[block].values())
    return 1000.0 * host / flight["count"]
