"""Share of the flights' time spent in the BSI lane: ``spans.executor.batchBSI``
seconds over ``spans.batcher.flight`` seconds, in %.

Reads 0 on a program from before the span table (see
``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    spans = ctx["vars"].get("spans")
    if spans is None:
        return 0.0
    flight = spans["batcher"]["flight"]["seconds"]
    return 100.0 * spans["executor"]["batchBSI"]["seconds"] / flight if flight else 0.0
