"""Result-cache hits answered with the bytes an earlier hit of the same entry
was sent as, over all hits of the traced window: the delta of
``rescache.encodedHits`` over that of ``rescache.hits`` (``/debug/vars``;
pilosa_tpu/exec/rescache.py ``ResultCache.snapshot``), in percent.  A hit
that is not counted here copied its answer or encoded it again: the first
hit of an entry's life, a request of several calls, a keyed index, a request
that collects a profile.

Reads 0 on a program without the counter (see ``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    cache = ctx["vars"].get("rescache", {})
    encoded, hits = cache.get("encodedHits"), cache.get("hits")
    if encoded is None or not hits:
        return 0.0
    return 100.0 * encoded / hits
