"""Share of the flights' time that no batch lane took: the seconds of the
per-call fall-through's spans (``spans.executor.execute<Call>``, opened in
``Executor.execute_batch`` around ``_execute_call`` for every call that the
pair-count, compiled-tree and BSI lanes left unanswered; only there, so a
call's own children open none and nothing counts twice) over
``spans.batcher.flight`` seconds, in %.  A window in which every call rode a
lane reads 0; one without a flight has nothing to read and ends the run."""


def read(ctx: dict) -> float:
    spans = ctx["vars"]["spans"]
    flight = spans["batcher"]["flight"]["seconds"]
    if not flight:
        raise ValueError("no flight in the window: no share of one to read")
    percall = sum(row["seconds"] for name, row in spans.get("executor", {}).items()
                  if name.startswith("execute"))
    return 100.0 * percall / flight
