"""Share of the time between the two ``/debug/vars`` readings that the one
dispatcher thread spent inside a flight: ``spans.batcher.flight`` seconds over
``trace.window_s``, in %.  The readings bracket the window with its lead-in and
the reads still in flight at its end, and the profiler session
(``trace.window_s``) brackets the readings, so the share stays under 100; over
``window.seconds``, which is shorter than what the delta covers, it would not.
Neither high nor low is good by itself (the manifest needs a direction and says
lower): near 100 the dispatcher is the bottleneck, near 0 the reads never
reach it.

Reads 0 on a program from before the span table (see
``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    spans = ctx["vars"].get("spans")
    if spans is None:
        return 0.0
    return 100.0 * spans["batcher"]["flight"]["seconds"] / ctx["trace"]["window_s"]
