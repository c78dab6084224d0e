"""Handler-thread time of a read that is not admission, the result-cache
probe or waiting for its flight: the self time of ``http.query`` plus its
children ``http.decode``, ``api.parse`` and ``http.encode``, in ms a read,
from the span table (``/debug/vars`` ``spans``, pilosa_tpu/obs/tracing.py)
over the traced window.

A program from before the span table has no such block; this file is laid
over such a checkout when its PR is checked against its parent, and reads
0 there instead of ending the run.  A block that lacks one of the names
still ends it."""


def read(ctx: dict) -> float:
    spans = ctx["vars"].get("spans")
    if spans is None:
        return 0.0
    http, api = spans["http"], spans["api"]
    reads = http["query"]["count"]
    if not reads:
        return 0.0
    seconds = (http["query"]["self_seconds"] + http["decode"]["seconds"]
               + api["parse"]["seconds"] + http["encode"]["seconds"])
    return 1000.0 * seconds / reads
